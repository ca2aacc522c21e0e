"""Session-ticks completed per second of the window (host clock).

One session is one P2P peer or one SyncTest lobby; one session-tick is one
session advancing one frame.  The end-of-window drain is inside the window."""


def read(run):
    return run.session_ticks / run.window_s
