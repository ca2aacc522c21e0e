"""Tick-phase latency attribution tests — histogram percentiles, guarded
phase timers (disabled-path cost + enabled-path series), the always-on
flight recorder (ring bound, dump, desync embedding, reconciliation), the
bench-history regression gate, the lint's mirrored phase catalog,
``readback_block`` self time, phase events on the JAX profiler's trace,
and the process-wide compile account."""

import dataclasses
import importlib.util
import json
import os
import time

import numpy as np
import pytest

from bevy_ggrs_tpu import telemetry
from tests.test_synctest import make_counter_app, make_runner


@pytest.fixture(autouse=True)
def _clean_telemetry():
    # registry/timeline/flight ring are process globals: isolate every test
    telemetry.disable()
    telemetry.reset()
    telemetry.configure_forensics(None)
    telemetry.configure_flight(maxlen=256, enabled=True)
    yield
    telemetry.disable()
    telemetry.reset()
    telemetry.configure_forensics(None)
    telemetry.configure_flight(maxlen=256, enabled=True)


# ------------------------------------------------- histogram percentiles


def test_percentile_from_buckets_uniform():
    telemetry.enable()
    h = telemetry.registry().histogram(
        "lat_ms", "l", buckets=telemetry.LATENCY_MS_BUCKETS
    )
    # 100 uniform values in (0, 10]: true p50 ~5, p95 ~9.5
    for i in range(1, 101):
        h.observe(i / 10.0)
    p50 = h.percentile(0.5)
    p95 = h.percentile(0.95)
    assert 4.0 <= p50 <= 6.0, p50
    assert 8.5 <= p95 <= 10.0, p95
    ps = h.percentiles()
    assert set(ps) == {"p50", "p95", "p99"}
    assert ps["p50"] == p50


def test_percentile_overflow_clamps_to_last_bound():
    telemetry.enable()
    h = telemetry.registry().histogram("big_ms", "b", buckets=(1.0, 2.0))
    h.observe(50.0)  # lands past every finite bucket
    assert h.percentile(0.5) == 2.0


def test_percentile_empty_series_is_none():
    telemetry.enable()
    h = telemetry.registry().histogram("empty_ms", "e", buckets=(1.0,))
    assert h.percentile(0.5) is None


def test_summary_derived_latency_percentiles():
    telemetry.enable()
    ps = telemetry.PhaseSet(owner="solo")
    for _ in range(5):
        ps.begin_tick()
        with ps.phase("wave_dispatch"):
            pass
        ps.end_tick(frame=1)
    derived = telemetry.summary()["derived"]["latency_ms"]
    assert "tick_phase_ms" in derived
    (key, row), = [
        (k, v) for k, v in derived["tick_phase_ms"].items()
        if "wave_dispatch" in k
    ]
    assert row["count"] == 5
    assert row["p50"] is not None and row["p50"] >= 0


# ------------------------------------------------------------ phase timers


def test_phase_timers_populate_histogram_series():
    telemetry.enable()
    ps = telemetry.PhaseSet(owner="solo")
    ps.begin_tick()
    with ps.phase("rollback_load"):
        time.sleep(0.001)
    ps.note_rollback(3)
    ps.end_tick(frame=7)
    h = telemetry.registry().histogram(
        "tick_phase_ms", "", buckets=telemetry.LATENCY_MS_BUCKETS
    )
    s = h.snapshot(phase="rollback_load", owner="solo")
    assert s["count"] == 1
    assert s["sum"] >= 1.0  # slept 1ms
    wall = telemetry.registry().histogram(
        "tick_wall_ms", "", buckets=telemetry.LATENCY_MS_BUCKETS
    ).snapshot(owner="solo")
    assert wall["count"] == 1


def test_phase_unknown_name_raises():
    ps = telemetry.PhaseSet()
    with pytest.raises(KeyError):
        ps.phase("made_up_phase")


def test_phase_totals_reconcile():
    ps = telemetry.PhaseSet(owner="solo")
    for _ in range(10):
        ps.begin_tick()
        with ps.phase("session_step"):
            pass
        with ps.phase("wave_dispatch"):
            pass
        ps.end_tick()
    t = ps.totals()
    assert t["ticks"] == 10
    # totals() rounds each part to 6 decimals, so compare with abs slack
    attributed = sum(t["phase_seconds"].values())
    assert attributed == pytest.approx(t["attributed_seconds"], abs=1e-5)
    assert t["wall_seconds"] == pytest.approx(
        t["attributed_seconds"] + t["unattributed_seconds"], abs=1e-5
    )


def test_phase_timers_disabled_path_is_cheap():
    # flight off + telemetry off: entering a phase must be one boolean
    # check. Bound the per-cycle cost generously (CI hosts are noisy) —
    # the point is catching an accidental perf_counter/dict hit on the
    # disabled path, which would cost 10x this bound.
    telemetry.configure_flight(enabled=False)
    ps = telemetry.PhaseSet(owner="solo")
    p1, p2 = ps.phase("net_poll"), ps.phase("wave_dispatch")
    n = 20000
    ps.begin_tick()
    assert ps._on is False
    t0 = time.perf_counter()
    for _ in range(n):
        with p1:
            pass
        with p2:
            pass
    dt = time.perf_counter() - t0
    per_cycle_us = dt / n * 1e6
    assert per_cycle_us < 20.0, f"{per_cycle_us:.2f}us per 2-phase cycle"
    # nothing was recorded anywhere
    ps.end_tick()
    assert ps.ticks == 0
    assert len(telemetry.flight_recorder()) == 0
    assert telemetry.registry().metrics() == []


def test_phase_timers_flight_only_no_registry_families():
    # telemetry disabled, flight on: entries land in the ring but the
    # registry must stay empty (no histogram families created)
    ps = telemetry.PhaseSet(owner="solo")
    ps.begin_tick()
    with ps.phase("store_save"):
        pass
    ps.end_tick(frame=3)
    assert telemetry.registry().metrics() == []
    entries = telemetry.flight_recorder().snapshot("tick")
    assert len(entries) == 1
    assert entries[0]["frame"] == 3
    assert "store_save" in entries[0]["phases"]


# -------------------------------------------------------- flight recorder


def test_flight_ring_bound_and_clear():
    fr = telemetry.flight_recorder()
    fr.set_maxlen(8)
    for i in range(20):
        fr.record("tick", i=i)
    assert len(fr) == 8
    assert [e["i"] for e in fr.snapshot()] == list(range(12, 20))
    fr.clear()
    assert len(fr) == 0


def test_flight_reconciliation_invariant():
    ps = telemetry.PhaseSet(owner="solo")
    for _ in range(5):
        ps.begin_tick()
        with ps.phase("wave_dispatch"):
            time.sleep(0.0005)
        with ps.phase("store_save"):
            pass
        ps.end_tick()
    for e in telemetry.flight_recorder().snapshot("tick"):
        total = sum(e["phases"].values()) + e["unattributed_ms"]
        # rounding each part to 4 decimals bounds the drift
        assert total == pytest.approx(e["wall_ms"], abs=0.01)


def test_dump_flight_record(tmp_path):
    fr = telemetry.flight_recorder()
    fr.record("tick", wall_ms=1.0)
    path = tmp_path / "flight.json"
    telemetry.dump_flight_record(str(path))
    data = json.loads(path.read_text())
    assert data["maxlen"] == fr.maxlen
    assert data["events"][0]["kind"] == "tick"


def test_flight_disabled_records_nothing():
    telemetry.configure_flight(enabled=False)
    fr = telemetry.flight_recorder()
    fr.record("tick", x=1)
    assert len(fr) == 0


def test_desync_report_embeds_flight_record(tmp_path):
    # telemetry NEVER enabled: the report's flight_record section must
    # still hold the recent tick history (the always-on black box)
    telemetry.configure_forensics(str(tmp_path))
    app = make_counter_app()
    runner, mismatches = make_runner(app, check_distance=2)
    for _ in range(6):
        runner.tick()
    w = runner.world
    runner.world = dataclasses.replace(
        w, comps={**w.comps, "counter": w.comps["counter"] + 1000}
    )
    runner._world_checksum = app.checksum_fn(runner.world)
    for _ in range(6):
        runner.tick()
    assert mismatches, "corruption never tripped the synctest comparison"
    reports = sorted(tmp_path.glob("desync_synctest_mismatch_*.json"))
    assert reports
    rep = json.loads(reports[0].read_text())
    flight = rep["flight_record"]
    ticks = [e for e in flight if e["kind"] == "tick"]
    assert ticks, "no tick entries in the embedded flight record"
    assert "phases" in ticks[-1] and "wall_ms" in ticks[-1]


def test_phase_breakdown_exact_percentiles():
    entries = [
        {"kind": "tick", "wall_ms": float(i), "unattributed_ms": 0.0,
         "phases": {"wave_dispatch": float(i)}}
        for i in range(1, 101)
    ]
    bd = telemetry.phase_breakdown(entries)
    assert bd["wave_dispatch"]["count"] == 100
    assert bd["wave_dispatch"]["p50"] == pytest.approx(50.5)
    assert bd["(wall)"]["p99"] == pytest.approx(99.01)
    table = telemetry.format_phase_table(bd)
    assert "wave_dispatch" in table and "p50" in table


# ------------------------------------------------------- timeline dropped


def test_timeline_dropped_counter_and_summary():
    telemetry.enable()
    tl = telemetry.Timeline(maxlen=4)
    for i in range(7):
        tl.record("ev", i=i)
    assert len(tl) == 4
    assert tl.dropped == 3
    c = telemetry.registry().counter("timeline_events_dropped_total", "")
    assert c.value() == 3
    tl.clear()
    assert tl.dropped == 0
    # the process-default timeline surfaces its own count in summary()
    assert "timeline_events_dropped" in telemetry.summary()


# -------------------------------------------------- prometheus escaping


def test_prometheus_label_value_escaping():
    telemetry.enable()
    telemetry.count("esc_total", peer='a"b\\c\nd')
    text = telemetry.registry().render_prometheus()
    assert 'peer="a\\"b\\\\c\\nd"' in text


def test_prometheus_histogram_exposition():
    telemetry.enable()
    ps = telemetry.PhaseSet(owner="solo")
    ps.begin_tick()
    with ps.phase("net_poll"):
        pass
    ps.end_tick()
    text = telemetry.registry().render_prometheus()
    assert 'tick_phase_ms_bucket{' in text
    assert 'le="+Inf"' in text
    assert "tick_phase_ms_sum{" in text
    assert "tick_phase_ms_count{" in text


# ------------------------------------------------------- runner wiring


def test_runner_stats_phases_and_compile():
    app = make_counter_app()
    runner, _ = make_runner(app, check_distance=2)
    for _ in range(10):
        runner.tick()
    st = runner.stats()
    assert st["phases"]["ticks"] > 0
    assert st["phases"]["unattributed_pct"] < 50.0
    assert "wave_dispatch" in st["phases"]["phase_seconds"]
    assert st["compile_ms"], "first dispatches were not timed"
    assert all(v > 0 for v in st["compile_ms"].values())


def test_packed_staging_attributed_to_stage_inputs():
    """The packed single-upload path must keep its host-side staging work
    (pack rows + synchronous commit) attributed under ``stage_inputs`` —
    and the totals must still reconcile (attributed + unattributed = wall),
    so the packing refactor cannot silently open an attribution hole."""
    app = make_counter_app()
    runner, mismatches = make_runner(app)
    for _ in range(12):
        runner.tick()
    assert mismatches == []
    st = runner.stats()
    assert st["packed"], "driver did not take the packed path"
    t = st["phases"]
    assert t["phase_seconds"].get("stage_inputs", 0.0) > 0.0
    attributed = sum(t["phase_seconds"].values())
    assert attributed == pytest.approx(t["attributed_seconds"], abs=1e-5)
    assert t["wall_seconds"] == pytest.approx(
        t["attributed_seconds"] + t["unattributed_seconds"], abs=1e-5
    )


# ------------------------------------------------------- bench history


def _load_bench_history():
    spec = importlib.util.spec_from_file_location(
        "bench_history",
        os.path.join(os.path.dirname(__file__), "..", "scripts",
                     "bench_history.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _write_record(dir, n, parsed, rc=0):
    with open(os.path.join(dir, f"BENCH_r{n:02d}.json"), "w") as f:
        json.dump({"n": n, "cmd": "bench", "rc": rc, "tail": "",
                   "parsed": parsed}, f)


def test_bench_history_detects_regression(tmp_path):
    bh = _load_bench_history()
    _write_record(tmp_path, 1, {"value": 1000.0, "platform": "cpu"})
    _write_record(tmp_path, 2, {"value": 800.0, "platform": "cpu"})
    assert bh.main(["--dir", str(tmp_path), "--threshold", "0.10"]) == 1
    assert bh.main(["--dir", str(tmp_path), "--threshold", "0.10",
                    "--warn-only"]) == 0
    # a looser threshold passes
    assert bh.main(["--dir", str(tmp_path), "--threshold", "0.25"]) == 0


def test_bench_history_compares_best_prior_same_platform(tmp_path):
    bh = _load_bench_history()
    _write_record(tmp_path, 1, {"value": 900.0, "platform": "cpu"})
    _write_record(tmp_path, 2, {"value": 90000.0, "platform": "tpu"})
    _write_record(tmp_path, 3, {"value": 880.0, "platform": "cpu"})
    # the tpu record must NOT count as the best prior for a cpu latest
    assert bh.main(["--dir", str(tmp_path), "--threshold", "0.10"]) == 0
    records = bh.load_records(str(tmp_path))
    _, _, rows, regs = bh.compare(records, 0.10)
    (row,) = [r for r in rows if r[0] == "value"]
    assert row[1] == 900.0 and row[2] == 1


def test_bench_history_skips_crashed_and_new_metrics(tmp_path):
    bh = _load_bench_history()
    _write_record(tmp_path, 1, {"value": 5000.0, "platform": "cpu"}, rc=1)
    _write_record(tmp_path, 2, {"value": 1000.0, "platform": "cpu"})
    _write_record(
        tmp_path, 3,
        {"value": 990.0, "brand_new_fps": 123.0, "platform": "cpu"},
    )
    # rc=1 record ignored (else value would regress 80%); new metric passes
    assert bh.main(["--dir", str(tmp_path), "--threshold", "0.10"]) == 0


def test_bench_history_excludes_non_throughput_keys():
    bh = _load_bench_history()
    metrics = bh.throughput_metrics({
        "value": 10.0, "spread": 0.5, "bytes_per_resim_frame": 720000,
        "pipeline_unattributed_pct": 3.0, "entities": 10000,
        "canonical_mode_fps": 5.0, "pipeline_speedup": 1.2,
        "tpu_fallback_to_cpu": True,
    })
    assert set(metrics) == {"value", "canonical_mode_fps",
                            "pipeline_speedup"}


def test_bench_history_upload_census_gates_on_increase(tmp_path):
    """The stage_uploads census metrics are LOWER-is-better: an extra
    upload per tick (1.0 -> 2.0) must fail the gate even while every
    throughput metric improves."""
    bh = _load_bench_history()
    assert set(bh.floor_metrics({
        "uploads_per_tick_packed": 1.0, "dispatches_per_tick_packed": 1.0,
        "megastep_uploads_per_flush": 1.0, "value": 10.0, "spread": 0.1,
    })) == {"uploads_per_tick_packed", "dispatches_per_tick_packed",
            "megastep_uploads_per_flush"}
    _write_record(tmp_path, 1, {"value": 1000.0,
                                "uploads_per_tick_packed": 1.0,
                                "platform": "cpu"})
    _write_record(tmp_path, 2, {"value": 1500.0,
                                "uploads_per_tick_packed": 2.0,
                                "platform": "cpu"})
    assert bh.main(["--dir", str(tmp_path), "--threshold", "0.10"]) == 1
    # holding the floor passes
    _write_record(tmp_path, 3, {"value": 1500.0,
                                "uploads_per_tick_packed": 1.0,
                                "platform": "cpu"})
    assert bh.main(["--dir", str(tmp_path), "--threshold", "0.10"]) == 0


def test_bench_history_megastep_flatness_is_higher_is_better(tmp_path):
    """megastep_frames_per_dispatch ~ N when flushes stay fused; a fall
    (the program splitting into multiple dispatches) is the regression."""
    bh = _load_bench_history()
    _write_record(tmp_path, 1, {"megastep_frames_per_dispatch": 8.0,
                                "platform": "cpu"})
    _write_record(tmp_path, 2, {"megastep_frames_per_dispatch": 4.0,
                                "platform": "cpu"})
    assert bh.main(["--dir", str(tmp_path), "--threshold", "0.10"]) == 1
    _write_record(tmp_path, 3, {"megastep_frames_per_dispatch": 8.0,
                                "platform": "cpu"})
    assert bh.main(["--dir", str(tmp_path), "--threshold", "0.10"]) == 0


# ------------------------------------------------------------- lint mirror


def test_lint_phase_catalog_matches_package():
    spec = importlib.util.spec_from_file_location(
        "lint_imports",
        os.path.join(os.path.dirname(__file__), "..", "scripts",
                     "lint_imports.py"),
    )
    lint = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(lint)
    assert lint.PHASE_CATALOG == set(telemetry.PHASES)


def test_lint_check_phases_flags_misuse():
    import ast

    spec = importlib.util.spec_from_file_location(
        "lint_imports",
        os.path.join(os.path.dirname(__file__), "..", "scripts",
                     "lint_imports.py"),
    )
    lint = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(lint)
    bad = ast.parse(
        "with ps.phase('not_a_phase'):\n    pass\n"
        "t = ps.phase('net_poll')\n"
        "with ps.phase(name):\n    pass\n"
    )
    msgs = [m for _, m in lint.check_phases(bad)]
    assert any("not in the phase catalog" in m for m in msgs)
    assert any("must be a with-statement" in m for m in msgs)
    assert any("one string literal" in m for m in msgs)
    good = ast.parse("with ps.phase('wave_dispatch'):\n    pass\n")
    assert lint.check_phases(good) == []


# ------------------------------------------- readback_block (self time)


def _assert_tick_invariant(entries):
    """sum(phases) + unattributed == wall for every flight tick entry."""
    assert entries
    for e in entries:
        total = sum(e["phases"].values()) + e["unattributed_ms"]
        assert total == pytest.approx(e["wall_ms"], abs=1e-3), e


def test_readback_block_is_self_time_inside_session_step():
    from bevy_ggrs_tpu.telemetry import phases

    ps = telemetry.PhaseSet(owner="solo")
    ps.begin_tick()
    with ps.phase("session_step"):
        time.sleep(0.002)
        # a pull site below the driver reaches the ticking set
        with phases.phase("readback_block"):
            time.sleep(0.02)
        time.sleep(0.002)
    ps.end_tick(frame=1)
    (e,) = telemetry.flight_recorder().snapshot("tick")
    assert e["phases"]["readback_block"] >= 20.0
    # the block's seconds moved out of the enclosing phase
    assert 4.0 <= e["phases"]["session_step"] < 15.0
    _assert_tick_invariant([e])
    t = ps.totals()
    assert t["phase_seconds"]["readback_block"] >= 0.02
    assert t["phase_seconds"]["session_step"] < 0.015
    assert t["wall_seconds"] == pytest.approx(
        t["attributed_seconds"] + t["unattributed_seconds"], abs=1e-5
    )


def test_readback_block_outside_any_phase_or_tick():
    from bevy_ggrs_tpu.telemetry import phases

    ps = telemetry.PhaseSet(owner="solo")
    ps.begin_tick()
    with ps.phase("wave_dispatch"):
        pass
    # inside the tick but after the last phase closed: nothing to move
    with phases.phase("readback_block"):
        time.sleep(0.005)
    ps.end_tick()
    # between ticks the module-level timer is a no-op
    with phases.phase("readback_block"):
        time.sleep(0.005)
    ps.begin_tick()
    with ps.phase("store_save"):
        pass
    ps.end_tick()
    first, second = telemetry.flight_recorder().snapshot("tick")
    assert first["phases"]["readback_block"] >= 5.0
    assert first["phases"]["wave_dispatch"] >= 0.0
    assert "readback_block" not in second["phases"]
    _assert_tick_invariant([first, second])


class _SlowHostChecks:
    """A host-backed checksum batch whose copy never lands on its own:
    harvest leaves it pending, so the session's comparison forces it
    through ``BatchChecks.pull_pending`` (``jax.device_get`` reads it
    through ``__array__``, which stands in for the device wait)."""

    def __init__(self, delay_s: float):
        self.delay_s = delay_s

    def is_ready(self) -> bool:
        return False

    def __array__(self, dtype=None, copy=None):
        time.sleep(self.delay_s)
        return np.zeros((2, 2), dtype=dtype or np.uint32)


def test_forced_pull_inside_session_step_lands_in_readback_block():
    from bevy_ggrs_tpu.snapshot.lazy import BatchChecks, readback_stats

    app = make_counter_app()
    runner, mismatches = make_runner(app, check_distance=2)
    for _ in range(4):
        runner.tick()
    forced0 = readback_stats()["forced"]
    # two equal checksums of a far frame: the SyncTest comparison of the
    # next tick forces them (CPU compares every tick), inside session_step
    batch = BatchChecks(_SlowHostChecks(0.03))
    runner.session._cells[10_000] = [batch.ref(0), batch.ref(1)]
    telemetry.flight_recorder().clear()
    runner.tick()
    assert mismatches == []
    assert readback_stats()["forced"] == forced0 + 1
    (e,) = telemetry.flight_recorder().snapshot("tick")
    assert e["phases"]["readback_block"] >= 30.0
    assert e["phases"]["session_step"] < 30.0
    _assert_tick_invariant([e])
    # the forensics entry stays beside it
    (fr,) = telemetry.flight_recorder().snapshot("forced_readback")
    assert fr["blocked_ms"] >= 30.0
    assert runner.stats()["phases"]["phase_seconds"]["readback_block"] >= 0.03


def test_sync_mode_drain_wait_is_readback_block():
    app = make_counter_app()
    runner, mismatches = make_runner(app, check_distance=2)
    runner.pipeline = False
    for _ in range(6):
        runner.tick()
    assert mismatches == []
    entries = telemetry.flight_recorder().snapshot("tick")
    assert all("readback_block" in e["phases"] for e in entries[-3:])
    _assert_tick_invariant(entries)


# ------------------------------------------------ phases on the profiler


class _CountingTraceMe:
    """Stands in for the profiler's TraceMe: counts the events built."""

    enabled = False
    built: list = []

    def __init__(self, name):
        _CountingTraceMe.built.append(name)

    @staticmethod
    def is_enabled():
        return _CountingTraceMe.enabled

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def test_no_trace_event_built_while_the_profiler_is_off(monkeypatch):
    from bevy_ggrs_tpu.telemetry import phases

    monkeypatch.setattr(phases, "_TraceMe", _CountingTraceMe)
    monkeypatch.setattr(_CountingTraceMe, "built", [])
    app = make_counter_app()
    runner, _ = make_runner(app, check_distance=2)
    for _ in range(5):
        runner.tick()
    assert runner.stats()["phases"]["ticks"] == 5
    assert _CountingTraceMe.built == []
    # recording: one event per phase entry, named as in the catalog
    monkeypatch.setattr(_CountingTraceMe, "enabled", True)
    for _ in range(3):
        runner.tick()
    assert _CountingTraceMe.built
    assert set(_CountingTraceMe.built) <= set(telemetry.PHASES)
    assert {"session_step", "wave_dispatch"} <= set(_CountingTraceMe.built)


def test_profiler_trace_holds_the_phase_names(tmp_path):
    import jax
    from jax.profiler import ProfileData

    app = make_counter_app()
    runner, _ = make_runner(app, check_distance=2)
    for _ in range(4):
        runner.tick()  # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(4):
            runner.tick()
    (path,) = tmp_path.glob("**/*.xplane.pb")
    pd = ProfileData.from_file(str(path))
    names = set()
    for plane in pd.planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                names.update(e.name for e in line.events)
    assert {"session_step", "wave_dispatch", "store_save"} <= names
    # none of the benchmark's own annotation names
    assert not {"window", "tick", "net_deliver", "input_gen"} & set(
        telemetry.PHASES
    )


# ------------------------------------------------------- compile account


def test_compile_account_counts_a_fresh_jit():
    import jax

    x = jax.device_put(np.arange(7, dtype=np.float32))
    before = telemetry.compile_account()
    out = jax.jit(lambda x: x * 3 + 1)(x)
    after = telemetry.compile_account()
    assert np.asarray(out)[2] == 7.0
    assert after["programs"] == before["programs"] + 1
    for k in ("trace_s", "lower_s", "backend_s"):
        assert after[k] > before[k], k
    assert telemetry.summary()["compiles"]["programs"] == after["programs"]


def test_compile_account_cache_events_mirror_to_registry():
    from jax import monitoring

    telemetry.enable()
    before = telemetry.compile_account()
    monitoring.record_event("/jax/compilation_cache/cache_hits")
    monitoring.record_event("/jax/compilation_cache/cache_hits")
    monitoring.record_event("/jax/compilation_cache/cache_misses")
    monitoring.record_event_duration_secs(
        "/jax/core/compile/backend_compile_duration", 0.25
    )
    after = telemetry.compile_account()
    assert after["cache_hits"] == before["cache_hits"] + 2
    assert after["cache_misses"] == before["cache_misses"] + 1
    assert after["programs"] == before["programs"] + 1
    assert after["backend_s"] == pytest.approx(before["backend_s"] + 0.25)
    reg = telemetry.registry()
    assert reg.counter("compile_cache_hits_total", "").value() == 2
    assert reg.counter("compile_cache_misses_total", "").value() == 1
    assert reg.counter("compile_programs_total", "").value() == 1
    assert reg.counter("compile_seconds_total", "").value(
        stage="backend") == pytest.approx(0.25)
    ratio = after["cache_hits"] / (after["cache_hits"] + after["cache_misses"])
    assert reg.gauge("compile_cache_hit_ratio", "").value() == pytest.approx(
        ratio)
    text = reg.render_prometheus()
    assert "compile_cache_hit_ratio" in text


def test_steady_ticks_never_reach_the_compile_account():
    app = make_counter_app()
    runner, _ = make_runner(app, check_distance=2)
    for _ in range(8):
        runner.tick()  # warm every program the steady ticks use
    before = telemetry.compile_account()
    for _ in range(8):
        runner.tick()
    assert telemetry.compile_account() == before
