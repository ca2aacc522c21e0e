"""Driver layer (``runner.py``, ``batch_runner.py``): device dispatches per
driver tick, from the runners' ``device_dispatches`` counters."""


def read(run):
    if not run.driver_ticks:
        return None
    return run.dispatches / run.driver_ticks
