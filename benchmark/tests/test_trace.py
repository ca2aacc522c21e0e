"""The trace reduction on synthetic events and on a recorded v5e trace."""

import gzip
import json
import os

import pytest

from harness.trace import module_name, op_name, reduce, self_times, union

DATA = os.path.join(os.path.dirname(__file__), "data")


def test_union_merges_overlaps_and_keeps_gaps():
    assert union([(5, 7), (0, 2), (1, 3), (7, 8), (10, 11)]) == [
        (0, 3), (5, 8), (10, 11)]


def test_self_times_subtract_nested_ops():
    ops = [("while", 0, 100), ("a", 10, 30), ("b", 40, 50), ("c", 120, 130)]
    assert self_times(ops) == [70, 20, 10, 10]


def test_names():
    assert module_name("jit_core(9125821836799227444)") == "jit_core"
    assert op_name("%fusion.139 = (u32[16]{0}) fusion(u32[16] %p)") == "fusion.139"


def synthetic():
    # window 0..100 ns on the host; the device runs two programs with a gap
    # in which the host was in input_gen, and a gap under net_deliver
    return {
        "host": [("window", 0, 100), ("tick", 0, 60), ("input_gen", 20, 40),
                 ("tick", 60, 100), ("net_deliver", 85, 100)],
        "devices": {"/device:TPU:0": {
            "modules": [("jit_core(1)", 0, 20), ("jit_body(2)", 40, 80)],
            "ops": [("%f.1 = x", 0, 10), ("%f.2 = x", 5, 20),
                    ("%while.1 = x", 40, 80), ("%g.1 = x", 50, 60)],
        }},
    }


def test_reduce_synthetic():
    r = reduce(synthetic())
    assert r["window_s"] == pytest.approx(100e-9)
    dev = r["devices"]["/device:TPU:0"]
    assert dev["busy_s"] == pytest.approx(60e-9)
    assert dev["modules_s"] == pytest.approx({"jit_core": 20e-9,
                                              "jit_body": 40e-9})
    gaps = dict(r["idle_gaps"])
    assert gaps == pytest.approx({"input_gen": 20e-9, "net_deliver": 20e-9})
    ops = dict(r["device_ops"])
    assert ops["jit_body/while.1"] == pytest.approx(30e-9)
    assert ops["jit_body/g.1"] == pytest.approx(10e-9)
    assert ops["jit_core/f.2"] == pytest.approx(15e-9)


def test_reduce_recorded_v5e_trace():
    """Three ticks of 16 particles lobbies on one v5e chip (16-lobby
    BatchedRunner, check distance 7): the device ran the padded k=8 wave
    (``jit_body``, a scan) and the k=1 wave (``jit_core``) back to back."""
    with gzip.open(os.path.join(DATA, "v5e_batched16_3ticks.json.gz"), "rt") as f:
        ev = json.load(f)
    r = reduce(ev)
    dev = r["devices"]["/device:TPU:0"]
    assert 0 < dev["busy_s"] <= r["window_s"]
    assert dev["busy_s"] / r["window_s"] > 0.95
    assert set(dev["modules_s"]) == {"jit_body", "jit_core"}
    assert dev["modules_s"]["jit_core"] == pytest.approx(4.395e-3, rel=1e-3)
    assert sum(dev["modules_s"].values()) <= r["window_s"]
    own = sum(s for _, s in r["device_ops"])
    assert own <= dev["busy_s"] * 1.0001
