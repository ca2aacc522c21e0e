"""Programs layer: seconds of jaxpr tracing, lowering and backend compile
(persistent-cache loads included) from the start of the process to the
window, read from the program's compile account
(``telemetry.compile_account()``).  ``run.py`` runs one cell per process
and the armed compile guard shows that the window compiles nothing, so the
account read after the window is set-up's.  A program without the account
gives nothing."""


def read(run):
    from bevy_ggrs_tpu import telemetry

    account = getattr(telemetry, "compile_account", None)
    if account is None:
        return None
    a = account()
    return a["trace_s"] + a["lower_s"] + a["backend_s"]
