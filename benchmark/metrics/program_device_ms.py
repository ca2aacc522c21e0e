"""Programs layer: device milliseconds per driver tick of the programs that
simulate and load frames, from the profiler trace, averaged over the chips
the cell uses.

The programs are matched by the jit function names they carry
(``XLA Modules`` events are named ``<name>(<fingerprint>)``):
``jit_core`` / ``jit_recycling`` are the exact-depth wave programs
(``ops/batch.py``), and ``jit_body`` is the name of the padded and sharded
wave programs, the solo packed resim (``ops/resim.py``) and the fused
snapshot loads (``snapshot/lazy.py``).  Checksum and slice helpers are not
counted."""

PROGRAMS = ("jit_body", "jit_core", "jit_recycling")


def program_seconds(run):
    """Mean over the cell's chips of the matched programs' device seconds."""
    if run.trace is None or not run.trace["devices"]:
        return None
    devs = list(run.trace["devices"].values())[:run.n_devices]
    per = [sum(s for m, s in d["modules_s"].items() if m in PROGRAMS)
           for d in devs]
    return sum(per) / len(per)


def read(run):
    s = program_seconds(run)
    if not s or not run.driver_ticks:
        return None
    return s / run.driver_ticks * 1e3
