"""Readback layer (``snapshot/lazy.py``): 99th percentile over every driver
tick of the window of the ``readback_block`` phase, the host blocked on a
device->host pull (``BatchChecks.pull_pending``, the drain's wait), read
from the flight recorder's per-tick entries (a tick that did not block
counts as 0).  A program whose phase catalog lacks the phase gives
nothing."""

import statistics


def read(run):
    from bevy_ggrs_tpu.telemetry import PHASES

    if "readback_block" not in PHASES:
        return None
    vals = [e.get("phases", {}).get("readback_block", 0.0)
            for e in run.flight_ticks]
    if len(vals) < 2:
        return None
    return statistics.quantiles(vals, n=100)[98]
