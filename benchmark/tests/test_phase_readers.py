"""The readers of ``readback_block_p99_ms`` and ``setup_compile_s`` on a
synthetic run, on a program that lacks what they read, and in a traced
run of a small batched cell."""

import time

import pytest

from harness.measure import Run, run_cell
from harness import spec

from test_faults import small_particles


def _ticks(blocks, n):
    """``n`` flight tick entries, the first ``len(blocks)`` blocking."""
    out = []
    for i in range(n):
        phases = {"session_step": 0.5}
        if i < len(blocks):
            phases["readback_block"] = blocks[i]
        out.append({"kind": "tick", "phases": phases})
    return out


def test_readback_block_p99_counts_ticks_without_the_phase_as_zero():
    read = spec.metric_reader("readback_block_p99_ms")
    # one tick in 32 blocks, as the deferred SyncTest comparison does
    run = Run(flight_ticks=_ticks([440.0, 450.0, 460.0, 470.0], 128))
    v = read(run)
    assert 440.0 <= v <= 470.0
    assert read(Run(flight_ticks=_ticks([], 128))) == 0.0
    assert read(Run(flight_ticks=_ticks([], 1))) is None


def test_readback_block_p99_gives_nothing_without_the_phase(monkeypatch):
    from bevy_ggrs_tpu import telemetry

    monkeypatch.setattr(telemetry, "PHASES", tuple(
        p for p in telemetry.PHASES if p != "readback_block"))
    read = spec.metric_reader("readback_block_p99_ms")
    assert read(Run(flight_ticks=_ticks([450.0], 64))) is None


def test_setup_compile_s_sums_the_account(monkeypatch):
    from bevy_ggrs_tpu import telemetry

    monkeypatch.setattr(telemetry, "compile_account", lambda: {
        "programs": 26, "cache_hits": 20, "cache_misses": 6,
        "trace_s": 1.5, "lower_s": 0.25, "backend_s": 8.0})
    read = spec.metric_reader("setup_compile_s")
    assert read(Run()) == pytest.approx(9.75)
    monkeypatch.delattr(telemetry, "compile_account")
    assert read(Run()) is None


def test_traced_batched_run_reports_both_metrics():
    r = run_cell(small_particles(), seed=2**33 + 11, seconds=1.0, trace=True,
                 t_proc0=time.perf_counter(), require_chip=False)
    assert r["correct"], r["checks"]
    m = r["metrics"]
    assert m["readback_block_p99_ms"]["value"] >= 0.0
    assert m["readback_block_p99_ms"]["unit"] == "ms"
    assert m["setup_compile_s"]["value"] > 0.0
    assert m["setup_compile_s"]["unit"] == "s"
