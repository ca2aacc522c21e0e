"""Programs layer: share of the HBM roofline the simulate-and-load programs
reach.  The least bytes they need (each saved frame's registered state
written once, each loaded state read once: ``harness/nbytes.py``, counted
from the flight recorder's per-tick ``advances`` and ``rollbacks``), over
their device time (``program_device_ms``) on every chip used, times the
chip's peak HBM bytes/s (``harness/peaks.py``).  Bound by bytes: these
programs do no matrix work."""

from harness.nbytes import least_bytes
from harness.peaks import peaks


def read(run):
    ms = run.value("program_device_ms")
    if not ms:
        return None
    seconds = ms * 1e-3 * run.driver_ticks * run.n_devices
    need = least_bytes(run.state_bytes, run.advances, run.loads)
    if not need:
        return None
    return 100.0 * need / (seconds * peaks(run.device_kind)["hbm_bytes_per_s"])
