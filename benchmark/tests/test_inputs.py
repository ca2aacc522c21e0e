"""The seeded input function: pure, quiet at the start, and mispredicting
at the same rate for every seed."""

import numpy as np

from harness.inputs import QUIET_FRAMES, InputGen


def test_same_seed_same_table_and_large_seeds_work():
    a = InputGen(2**40 + 3, 5, 100).table(400, 2)
    b = InputGen(2**40 + 3, 5, 100).table(400, 2)
    assert np.array_equal(a, b)
    assert not np.array_equal(a, InputGen(7, 5, 100).table(400, 2))


def test_quiet_start_then_per_frame_toggles_then_periodic_flips():
    t = InputGen(11, 5, 100).table(1100, 2)
    assert not t[:QUIET_FRAMES].any()
    assert (t[QUIET_FRAMES + 1:100] != t[QUIET_FRAMES:99]).all()
    for seed in (1, 2, 3):
        t = InputGen(seed, 5, 100).table(1100, 2)
        changes = (t[101:] != t[100:-1]).sum(axis=0)
        assert all(c in (199, 200) for c in changes)
