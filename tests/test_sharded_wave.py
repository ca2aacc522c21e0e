"""Device-sharded many-worlds executor: lobbies across the mesh.

The acceptance oracle is the unsharded path — ``ShardedWaveExecutor`` must
be BIT-identical to ``BucketedWaveExecutor`` on identical waves (stacked
states AND checksums), and ``BatchedRunner(mesh=...)`` must reproduce the
unsharded runner's checksums tick-for-tick with the SyncTest oracle green.
Runs on the conftest-forced 8-virtual-device CPU mesh (``eight_devices``)."""

import os
import sys

import numpy as np
import pytest

from bevy_ggrs_tpu import BatchedRunner, SyncTestSession, telemetry
from bevy_ggrs_tpu.batch_runner import ShardPlanner
from bevy_ggrs_tpu.models import fixed_point, stress
from bevy_ggrs_tpu.ops.batch import (
    BucketedWaveExecutor,
    ShardedWaveExecutor,
    stack_worlds,
)
from bevy_ggrs_tpu.parallel import make_lobby_mesh
from bevy_ggrs_tpu.session.events import InputStatus

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "scripts"))
import multichip_bench  # noqa: E402  (scripts/ is not a package)


def _wave(app, m, k_max, seed=0):
    """A deterministic [m, k_max] staging wave: worlds, inputs, status."""
    rng = np.random.default_rng(seed)
    worlds = stack_worlds([app.init_state() for _ in range(m)])
    inputs = rng.integers(0, 16, (m, k_max, app.num_players)).astype(np.uint8)
    status = np.full((m, k_max, app.num_players), InputStatus.CONFIRMED,
                     np.int8)
    return worlds, inputs, status


def _tree_equal(a, b):
    import jax

    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(la, lb))


# -- executor-level bit-equality -------------------------------------------

@pytest.mark.parametrize("m,ks", [
    (8, [8] * 8),            # exact wave, M == D
    (16, [8] * 16),          # exact wave, M == 2D
    (12, [3, 8, 1, 5, 8, 2, 7, 4, 8, 1, 6, 8]),  # ragged, M not div by D
    (13, [5] * 13),          # non-power-of-two M, M not div by D
    (6, [2, 7, 1, 4, 8, 3]),  # ragged, M < D
], ids=["exact-m8", "exact-m16", "ragged-m12", "uniform-m13", "ragged-m6"])
@pytest.mark.parametrize("app_factory", [
    lambda: stress.make_app(64, capacity=64),
    fixed_point.make_app,
], ids=["stress", "fixed_point"])
def test_sharded_wave_bit_equality(eight_devices, app_factory, m, ks):
    """Sharded vs unsharded on the identical wave: same bucket choice, and
    bit-equal finals, stacked snapshots, and checksum rows."""
    k_max = 8
    app = app_factory()
    mesh = make_lobby_mesh(len(eight_devices))
    ref = BucketedWaveExecutor(app, k_max)
    sh = ShardedWaveExecutor(app, k_max, mesh)
    worlds, inputs, status = _wave(app, m, k_max)
    starts = np.arange(m, dtype=np.int32) * 3

    rb, rf, rs, rc = ref.run_wave(worlds, inputs, status, starts, ks)
    sb, sf, ss, sc = sh.run_wave(worlds, inputs, status, starts, ks)

    assert sb == rb
    assert _tree_equal(sf, rf), "finals diverged"
    assert _tree_equal(ss, rs), "stacked snapshots diverged"
    assert np.array_equal(np.asarray(sc), np.asarray(rc)), "checksums diverged"


def test_sharded_wave_mixed_depth_sequence(eight_devices):
    """Several consecutive waves of different bucket depths (program-cache
    reuse across waves) stay bit-equal, with finals threaded wave to wave."""
    app = stress.make_app(64, capacity=64)
    mesh = make_lobby_mesh(8)
    ref = BucketedWaveExecutor(app, 8)
    sh = ShardedWaveExecutor(app, 8, mesh)
    m = 12
    worlds, inputs, status = _wave(app, m, 8)
    rw = sw = worlds
    for tick, ks in enumerate([[1] * m, [4, 2, 1, 4, 3, 4, 1, 2, 4, 4, 1, 3],
                               [8] * m, [1] * m]):
        starts = np.full((m,), tick * 8, np.int32)
        _, rw, _, rc = ref.run_wave(rw, inputs, status, starts, ks)
        _, sw, _, sc = sh.run_wave(sw, inputs, status, starts, ks)
        assert np.array_equal(np.asarray(sc), np.asarray(rc)), f"tick {tick}"
    assert _tree_equal(sw, rw)


def test_sharded_executor_bookkeeping(eight_devices):
    """pad_lobbies math, recycle refusal, and per-device harvest census."""
    app = stress.make_app(64, capacity=64)
    mesh = make_lobby_mesh(8)
    sh = ShardedWaveExecutor(app, 8, mesh)
    assert [sh.pad_lobbies(m) for m in (1, 7, 8, 9, 16, 17)] == \
        [8, 8, 8, 16, 16, 24]
    with pytest.raises(ValueError, match="recycle_outputs"):
        ShardedWaveExecutor(app, 8, mesh, recycle_outputs=True)

    worlds, inputs, status = _wave(app, 16, 8)
    _, finals, _, _ = sh.run_wave(worlds, inputs, status,
                                  np.zeros(16, np.int32), [8] * 16)
    census = sh.harvest_shards(finals)
    assert census["n_devices"] == 8
    assert census["devices_touched"] == 8
    assert all(v > 0 for v in census["buffers_per_device"].values())
    assert sh.stats()["shard_devices"] == 8


# -- telemetry --------------------------------------------------------------

def test_sharded_telemetry_counters(eight_devices):
    """sharded_wave_dispatches / shard_program_compiles counters and the
    shard_imbalance_ratio gauge flow through the BoundMetric path."""
    telemetry.disable()
    telemetry.reset()
    telemetry.enable()
    try:
        app = stress.make_app(64, capacity=64)
        sh = ShardedWaveExecutor(app, 8, make_lobby_mesh(8))
        worlds, inputs, status = _wave(app, 8, 8)
        sh.run_wave(worlds, inputs, status, np.zeros(8, np.int32), [8] * 8)
        sh.run_wave(worlds, inputs, status, np.zeros(8, np.int32),
                    [4, 8, 1, 2, 8, 3, 5, 6])
        reg = telemetry.registry()
        assert reg.counter("sharded_wave_dispatches_total").value() == 2
        # exact + padded program at bucket 8
        assert reg.counter("shard_program_compiles_total").value() == 2

        planner = ShardPlanner(12, 4)
        plan = planner.plan([8, 8, 8, 0, 0, 0, 0, 0, 0, 0, 0, 0])
        # shard 0 holds all 3 active lobbies of 3 total -> 3 * 4 / 3 = 4.0
        assert plan["imbalance_ratio"] == pytest.approx(4.0)
        assert reg.gauge("shard_imbalance_ratio").value() == pytest.approx(4.0)
        balanced = planner.plan([1] * 12)
        assert balanced["imbalance_ratio"] == pytest.approx(1.0)
        assert planner.max_imbalance == pytest.approx(4.0)
    finally:
        telemetry.disable()
        telemetry.reset()


# -- driver level -----------------------------------------------------------

def _lobby_inputs(lobby, tick, handles):
    rng = np.random.default_rng(1000 * lobby + tick)
    return {h: np.uint8(rng.integers(0, 16)) for h in handles}


def _run_driver(app_factory, m, ticks, mesh):
    app = app_factory()
    t = [0]

    def read_inputs(lobby, handles):
        return _lobby_inputs(lobby, t[0], handles)

    sessions = [
        SyncTestSession(num_players=2, input_shape=(), input_dtype=np.uint8,
                        check_distance=2, compare_interval=1)
        for _ in range(m)
    ]
    br = BatchedRunner(app, sessions, read_inputs=read_inputs, mesh=mesh)
    sums = [[] for _ in range(m)]
    for _ in range(ticks):
        br.tick()
        t[0] += 1
        for b in range(m):
            sums[b].append(br.lobby_checksum(b))
    br.finish()  # SyncTest oracle: raises on any restore mismatch
    return br, sums


def test_batched_runner_sharded_matches_unsharded(eight_devices):
    """M=6 lobbies (not divisible by D=8, so two permanent pad lanes) with
    rollbacks: checksums AND dispatch counts must match the unsharded
    runner exactly — sharding may not cost extra dispatches per tick."""
    factory = lambda: stress.make_app(64, capacity=64)
    M, TICKS = 6, 18
    ref, ref_sums = _run_driver(factory, M, TICKS, mesh=None)
    sh, sh_sums = _run_driver(factory, M, TICKS, mesh=make_lobby_mesh(8))
    assert sh_sums == ref_sums
    assert sh.device_dispatches == ref.device_dispatches
    assert isinstance(sh.exec, ShardedWaveExecutor)
    s = sh.stats()["sharded"]
    assert s["devices"] == 8 and s["pad_lanes"] == 2
    assert s["waves_planned"] > 0


def test_batched_runner_single_device_fallback():
    """A 1-device mesh (or a 1-device backend) must fall back to the plain
    BucketedWaveExecutor — no shard_map, no planner, no pad lanes."""
    app = stress.make_app(64, capacity=64)
    br = BatchedRunner(
        app,
        [SyncTestSession(num_players=2, input_shape=(),
                         input_dtype=np.uint8, check_distance=2,
                         compare_interval=1)],
        read_inputs=lambda lobby, handles: {h: np.uint8(0) for h in handles},
        mesh=make_lobby_mesh(1),
    )
    assert not isinstance(br.exec, ShardedWaveExecutor)
    assert br.planner is None
    assert "sharded" not in br.stats()
    br.tick()
    br.finish()


# -- multichip harness rule -------------------------------------------------

def test_multichip_empty_tail_is_skipped_never_ok():
    """The MULTICHIP record rule: rc==0 with EMPTY output must be skipped,
    never ok (the regression this PR fixes: every historical record carried
    ok=true with tail='')."""
    assert multichip_bench.classify(0, "") == {
        "rc": 0, "ok": False, "skipped": True,
    }
    assert multichip_bench.classify(0, "  \n ") == {
        "rc": 0, "ok": False, "skipped": True,
    }
    assert multichip_bench.classify(0, "MULTICHIP_METRICS {}") == {
        "rc": 0, "ok": True, "skipped": False,
    }
    # a failure is a failure, not a skip, output or not
    assert multichip_bench.classify(1, "") == {
        "rc": 1, "ok": False, "skipped": False,
    }
    assert multichip_bench.classify(124, "partial") == {
        "rc": 124, "ok": False, "skipped": False,
    }


def test_multichip_metrics_parse():
    tail = (
        'noise line\n'
        'MULTICHIP_METRICS {"program": "canonical", "wall_secs": 1.0}\n'
        'MULTICHIP_METRICS not-json\n'
        'MULTICHIP_METRICS {"program": "sharded_wave", "lobbies": 16}\n'
    )
    metrics = multichip_bench.parse_metrics(tail)
    assert [m["program"] for m in metrics] == ["canonical", "sharded_wave"]


# -- in-wave loads ----------------------------------------------------------

def test_sharded_in_wave_loads_match_unsharded_separate_loads(eight_devices):
    """Lobby-sharded runner with SyncTest loads served inside the resim
    wave (each device selects its own lanes' source rows) against the
    unsharded runner with every load on the separate fused dispatch: every
    live world leaf, live checksum and ring entry bit-identical.  M=6 on 8
    devices keeps two pad lanes in the sharded source buffers."""
    import jax

    from bevy_ggrs_tpu.snapshot.lazy import materialize
    from bevy_ggrs_tpu.snapshot.checksum import checksum_to_int

    M, TICKS = 6, 12
    tick = [0]

    def read_inputs(lobby, handles):
        return _lobby_inputs(lobby, tick[0], handles)

    def runner(mesh):
        sessions = [
            SyncTestSession(num_players=2, input_shape=(),
                            input_dtype=np.uint8, check_distance=3,
                            compare_interval=1)
            for _ in range(M)
        ]
        return BatchedRunner(fixed_point.make_app(), sessions,
                             read_inputs=read_inputs, mesh=mesh)

    sh = runner(make_lobby_mesh(8))
    ref = runner(None)
    ref._defer_loads = lambda *args: {}  # every load on the fused dispatch
    for _ in range(TICKS):
        sh.tick()
        ref.tick()
        tick[0] += 1
        live = jax.tree.map(lambda a: a[:M], sh.worlds)
        assert _tree_equal(live, ref.worlds)
        for b in range(M):
            assert sh.lobby_checksum(b) == ref.lobby_checksum(b)
            assert sh.rings[b].frames() == ref.rings[b].frames()
            for f in ref.rings[b].frames():
                (s_sh, c_sh), (s_ref, c_ref) = (
                    sh.rings[b].peek(f), ref.rings[b].peek(f)
                )
                assert _tree_equal(materialize(s_sh), materialize(s_ref))
                assert checksum_to_int(c_sh) == checksum_to_int(c_ref)
    sh.finish()
    ref.finish()
    s = sh.stats()
    assert s["in_wave_loads"] == s["rollbacks"] > 0, s
    assert s["fused_loads"] == 0, s
    assert ref.stats()["in_wave_loads"] == 0


@pytest.mark.parametrize("kind", ["packed_padded_load", "packed_exact_load"])
def test_sharded_load_wave_has_no_collectives(eight_devices, kind):
    """The load-carrying sharded wave splits its source buffer by the same
    lobby spec as the worlds: each lane selects its own row, so the
    compiled program holds no collective (the separate fused load gathers
    with replicated indices from lobby-sharded buffers instead)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from bevy_ggrs_tpu.parallel.mesh import LOBBY_AXIS

    app = stress.make_app(64, capacity=64)
    mesh = make_lobby_mesh(8)
    m, bucket, src_k = 8, 4, 4
    ex = ShardedWaveExecutor(app, bucket, mesh)
    lobby = NamedSharding(mesh, P(LOBBY_AXIS))
    w = app.init_state()
    worlds = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct((m, *a.shape), a.dtype,
                                       sharding=lobby), w)
    src = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct((m, src_k, *a.shape), a.dtype,
                                       sharding=lobby), w)
    pk = jax.ShapeDtypeStruct((m, bucket + 1, app.packed_spec.width),
                              jnp.int8, sharding=lobby)
    hlo = ex._get_fn(kind, bucket).lower(worlds, pk, src).compile().as_text()
    for op in ("all-reduce", "all-gather", "collective-permute", "all-to-all"):
        assert op not in hlo, f"unexpected {op} in the sharded load wave"
