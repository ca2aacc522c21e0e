"""Readback layer (``snapshot/lazy.py``): 99th percentile over every driver
tick of the window of the ``readback_harvest`` phase, read from the flight
recorder's per-tick entries (a tick that did not harvest counts as 0)."""

import statistics


def read(run):
    vals = [e.get("phases", {}).get("readback_harvest", 0.0)
            for e in run.flight_ticks]
    if len(vals) < 2:
        return None
    return statistics.quantiles(vals, n=100)[98]
