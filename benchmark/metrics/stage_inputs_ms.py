"""Upload layer (``utils/staging.py``, ``ops/packing.py``): milliseconds per
driver tick in the ``stage_inputs`` phase (program phase timers)."""


def read(run):
    if not run.driver_ticks:
        return None
    return run.phase_s.get("stage_inputs", 0.0) / run.driver_ticks * 1e3
