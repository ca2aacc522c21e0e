"""99th percentile of one driver tick's wall time over every driver tick of
the window (host clock); a driver tick is one ``GgrsRunner.update`` or one
``BatchedRunner.tick``, and the drain counts in the last one."""

import statistics


def read(run):
    if len(run.ticks) < 2:
        return None
    return statistics.quantiles(run.ticks, n=100)[98] * 1e3
