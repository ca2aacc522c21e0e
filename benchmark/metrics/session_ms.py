"""Session layer (``session/``): milliseconds per driver tick in the
``net_poll`` and ``session_step`` phases (the program's phase timers,
``stats()["phases"]``), summed over the cell's runners."""


def read(run):
    if not run.driver_ticks:
        return None
    s = run.phase_s.get("net_poll", 0.0) + run.phase_s.get("session_step", 0.0)
    return s / run.driver_ticks * 1e3
