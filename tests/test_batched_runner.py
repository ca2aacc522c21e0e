"""BatchedRunner (many-worlds server driver): M lobbies through one fused
dispatch per wave must match M independent GgrsRunners checksum-for-checksum,
with the SyncTest oracle green inside the batch (proving the batched
save/load/ring plumbing restores exactly what it saved)."""

import numpy as np
import pytest

from bevy_ggrs_tpu import BatchedRunner, GgrsRunner, SyncTestSession
from bevy_ggrs_tpu.models import fixed_point, stress


def _session(check_distance=4):
    return SyncTestSession(
        num_players=2, input_shape=(), input_dtype=np.uint8,
        check_distance=check_distance, compare_interval=1,
    )


def _lobby_inputs(lobby, tick, handles):
    rng = np.random.default_rng(1000 * lobby + tick)
    return {h: np.uint8(rng.integers(0, 16)) for h in handles}


def _solo_checksums(app_factory, lobby, ticks, check_distance=4):
    app = app_factory()
    t = [0]

    def read_inputs(handles):
        out = _lobby_inputs(lobby, t[0], handles)
        t[0] += 1
        return out

    runner = GgrsRunner(app, _session(check_distance), read_inputs=read_inputs)
    out = []
    for _ in range(ticks):
        runner.tick()
        out.append(runner.checksum)
    runner.finish()
    return out


@pytest.mark.parametrize("app_factory", [
    lambda: stress.make_app(128, capacity=128),
    fixed_point.make_app,
], ids=["stress", "fixed_point"])
def test_batched_runner_matches_independent_runners(app_factory):
    M, TICKS = 3, 25
    app = app_factory()
    tcount = [0]

    def read_inputs(lobby, handles):
        # same per-(lobby, tick) stream the solo runners consume
        return _lobby_inputs(lobby, tcount[0], handles)

    br = BatchedRunner(app, [_session() for _ in range(M)],
                       read_inputs=read_inputs)
    batched = [[] for _ in range(M)]
    for _ in range(TICKS):
        br.tick()
        tcount[0] += 1
        for b in range(M):
            batched[b].append(br.lobby_checksum(b))
    br.finish()  # SyncTest oracle: raises on any batched-restore mismatch

    for b in range(M):
        solo = _solo_checksums(app_factory, b, TICKS)
        assert batched[b] == solo, f"lobby {b} diverged from its solo run"


def test_batched_runner_dispatch_count():
    """The whole point: M lobbies per tick must cost O(waves) dispatches,
    not O(M) — the warmed-up synctest shape is 2 dispatches/tick (the two
    run waves; the loads ride the resim wave, see
    test_in_wave_loads_two_dispatches_per_tick), and `device_dispatches`
    counts load and store waves too, so a per-lobby fallback would blow the
    bound."""
    M, TICKS = 8, 12
    app = stress.make_app(64, capacity=64)
    br = BatchedRunner(app, [_session(check_distance=3) for _ in range(M)],
                       read_inputs=_lobby_inputs_tickless)
    for _ in range(TICKS):
        br.tick()
    br.finish()
    s = br.stats()
    assert s["device_dispatches"] <= 3 * TICKS, s
    assert s["fallback_loads"] == 0, s
    assert all(f == TICKS for f in s["frames"]), s


def test_batched_runner_freezes_the_heap_after_compiling_ticks():
    """A tick that builds programs collects once and freezes what survives,
    so later full collections skip set-up's objects; a steady tick freezes
    nothing, and a process that turned the collector off is left alone."""
    import gc

    from bevy_ggrs_tpu.telemetry.compiles import programs_built

    gc.unfreeze()
    try:
        br = BatchedRunner(stress.make_app(64, capacity=64),
                           [_session(check_distance=3) for _ in range(2)],
                           read_inputs=_lobby_inputs_tickless)
        built = programs_built()
        br.tick()
        assert programs_built() > built
        assert gc.get_freeze_count() > 0
        for _ in range(10):  # past the ramp's first loads and full waves
            br.tick()
        built = programs_built()
        frozen = gc.get_freeze_count()
        young = [[] for _ in range(500)]
        br.tick()
        assert programs_built() == built
        assert gc.get_freeze_count() <= frozen, "a steady tick froze the heap"
        del young

        gc.unfreeze()
        gc.disable()
        try:
            off = BatchedRunner(stress.make_app(48, capacity=48),
                                [_session(check_distance=3)],
                                read_inputs=_lobby_inputs_tickless)
            built = programs_built()
            off.tick()
            assert programs_built() > built
            assert gc.get_freeze_count() == 0
        finally:
            gc.enable()
    finally:
        gc.unfreeze()


def test_batched_runner_staggers_synctest_comparisons():
    """Lobbies ticked together compare on different ticks of the deferred
    interval (one tick never pays every lobby's comparison), and the
    oracle still holds."""
    M, INTERVAL = 4, 4
    sessions = [SyncTestSession(num_players=2, input_shape=(),
                                input_dtype=np.uint8, check_distance=3,
                                compare_interval=INTERVAL)
                for _ in range(M)]
    br = BatchedRunner(stress.make_app(64, capacity=64), sessions,
                       read_inputs=_lobby_inputs_tickless)
    per_tick = []
    for _ in range(12):
        br.tick()
        per_tick.append(sum(s._ticks_since_compare == 0 for s in sessions))
    assert per_tick == [1] * 12, per_tick
    br.finish()  # raises on any mismatch


def test_batched_runner_dispatches_flat_in_lobby_count():
    """O(1)-dispatch acceptance shape: the same lockstep workload at M=4 and
    M=16 must cost the SAME number of device dispatches per tick."""
    per_m = {}
    for m in (4, 16):
        app = stress.make_app(64, capacity=64)
        br = BatchedRunner(app, [_session(check_distance=2) for _ in range(m)],
                           read_inputs=_lobby_inputs_tickless)
        for _ in range(10):
            br.tick()
        br.finish()
        per_m[m] = br.stats()["device_dispatches"]
    assert per_m[4] == per_m[16], per_m


def test_bucketed_executor_buckets_and_counters():
    """Bucket selection, compile caching and dispatch counters: repeated
    same-shape waves must reuse programs (compile count stays flat)."""
    from bevy_ggrs_tpu.ops.batch import BucketedWaveExecutor, bucket_sizes

    assert bucket_sizes(12) == (1, 2, 4, 8, 12)
    assert bucket_sizes(8) == (1, 2, 4, 8)
    assert bucket_sizes(1) == (1,)

    M, K = 3, 5
    app = stress.make_app(32, capacity=32)
    from bevy_ggrs_tpu.ops.batch import stack_worlds

    worlds = stack_worlds([app.init_state() for _ in range(M)])
    ex = BucketedWaveExecutor(app, K)
    assert ex.bucket_for(1) == 1 and ex.bucket_for(3) == 4
    assert ex.bucket_for(5) == 5
    with pytest.raises(ValueError):
        ex.bucket_for(6)

    inputs = np.zeros((M, K, 2), np.uint8)
    status = np.zeros((M, K, 2), np.int8)
    starts = np.zeros((M,), np.int32)
    # lockstep k=1 wave -> exact bucket-1 program
    bucket, _f, stacked, checks = ex.run_wave(worlds, inputs, status, starts,
                                              [1, 1, 1])
    assert bucket == 1 and checks.shape == (M, 2)
    # ragged wave (k_hot=3) -> padded bucket-4 program
    bucket, _f, _s, checks = ex.run_wave(worlds, inputs, status, starts,
                                         [3, 0, 1])
    assert bucket == 4 and checks.shape == (M * 4, 2)
    compiles = ex.compile_count
    for _ in range(3):  # same shapes again: no new programs
        ex.run_wave(worlds, inputs, status, starts, [1, 1, 1])
        ex.run_wave(worlds, inputs, status, starts, [3, 0, 1])
    st = ex.stats()
    assert ex.compile_count == compiles, st
    assert st["bucket_hist"][1] == 4 and st["bucket_hist"][4] == 4
    assert st["wave_dispatches"] == 8


def test_bucketed_executor_exact_matches_padded():
    """The exact (unmasked) full-wave program must be bit-identical to the
    padded program at the same depth for a variant-stable sim — the executor
    switches between them by wave shape."""
    from bevy_ggrs_tpu.ops.batch import BucketedWaveExecutor, stack_worlds

    M, K = 2, 4
    app = stress.make_app(64, capacity=64)
    worlds = stack_worlds([app.init_state() for _ in range(M)])
    rng = np.random.default_rng(7)
    inputs = rng.integers(0, 16, size=(M, K, 2), dtype=np.uint8)
    status = np.zeros((M, K, 2), np.int8)
    starts = np.zeros((M,), np.int32)
    ex = BucketedWaveExecutor(app, K)
    _b, f_exact, s_exact, c_exact = ex.run_wave(
        worlds, inputs, status, starts, [K] * M
    )
    # force the padded program by making one lane ragged, then rerun the
    # SAME full wave through the padded builder directly
    from bevy_ggrs_tpu.ops.batch import make_batched_padded_fn

    padded = make_batched_padded_fn(app, K, unroll=ex.unroll,
                                    fused_checksums=ex.fused_checksums)
    f_pad, s_pad, c_pad = padded(worlds, inputs, status, starts,
                                 np.full((M,), K, np.int32))
    import jax

    for a, b in zip(jax.tree.leaves(f_exact), jax.tree.leaves(f_pad)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(c_exact), np.asarray(c_pad))
    for a, b in zip(jax.tree.leaves(s_exact), jax.tree.leaves(s_pad)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _lobby_inputs_tickless(lobby, handles):
    rng = np.random.default_rng(lobby)
    return {h: np.uint8(rng.integers(0, 16)) for h in handles}


def test_batched_runner_mixed_source_loads_match_solo():
    """Partial-fusion load coverage: per-lobby check_distance/compare_interval
    make every load wave MIXED — lobby 0 rolls back 4 frames (older ring
    rows), lobby 1 rolls back 2 (a different, more recent stacked buffer),
    lobby 2 only loads every other tick (so some waves it doesn't load at
    all) — and the whole wave must still be served by ONE fused gather and
    stay bit-identical to three independent GgrsRunners."""
    configs = [dict(check_distance=4, compare_interval=1),
               dict(check_distance=2, compare_interval=1),
               dict(check_distance=3, compare_interval=2)]
    TICKS = 25

    def make_session(cfg):
        return SyncTestSession(
            num_players=2, input_shape=(), input_dtype=np.uint8, **cfg,
        )

    app = fixed_point.make_app()  # input-sensitive: a wrong restore desyncs
    tcount = [0]

    def read_inputs(lobby, handles):
        return _lobby_inputs(lobby, tcount[0], handles)

    br = BatchedRunner(app, [make_session(c) for c in configs],
                       read_inputs=read_inputs)

    # spy the load waves to prove they were mixed (partial participation)
    load_waves = []
    orig_do_loads = br._do_loads

    def spying_do_loads(wave_ops, *args):
        n = sum(1 for op in wave_ops
                if op is not None and op.load_frame is not None)
        if n:
            load_waves.append(n)
        return orig_do_loads(wave_ops, *args)

    br._do_loads = spying_do_loads

    batched = [[] for _ in configs]
    for _ in range(TICKS):
        br.tick()
        tcount[0] += 1
        for b in range(len(configs)):
            batched[b].append(br.lobby_checksum(b))
    br.finish()  # SyncTest oracle across every lobby

    s = br.stats()
    # every load was served without per-lobby fallback, by either fused
    # path: inside the next run wave or by the separate fused gather
    assert s["fallback_loads"] == 0, s
    assert s["fused_loads"] + s["in_wave_loads"] > 0, s
    # the mix really happened: some waves had loads from only PART of the
    # lobbies (lobby 2 skips every other tick)
    assert any(0 < n < len(configs) for n in load_waves), load_waves

    for b, cfg in enumerate(configs):
        solo_app = fixed_point.make_app()
        t = [0]

        def solo_inputs(handles, _b=b, _t=t):
            out = _lobby_inputs(_b, _t[0], handles)
            _t[0] += 1
            return out

        runner = GgrsRunner(solo_app, make_session(cfg),
                            read_inputs=solo_inputs)
        solo = []
        for _ in range(TICKS):
            runner.tick()
            solo.append(runner.checksum)
        runner.finish()
        assert batched[b] == solo, f"lobby {b} diverged from its solo run"


def test_batched_runner_p2p_pair_in_one_batch():
    """Both peers of ONE P2P game hosted as two lanes of the same batch —
    the in-process server shape.  They must sync, advance, and agree."""
    from bevy_ggrs_tpu import PlayerType, SessionBuilder, SessionState
    from bevy_ggrs_tpu.session.channel import ChannelNetwork

    app = stress.make_app(64, capacity=64)
    net = ChannelNetwork(latency_hops=1)
    sessions = []
    for i in range(2):
        b = (SessionBuilder(input_shape=(), input_dtype=np.uint8)
             .with_num_players(2).with_input_delay(1)
             .add_player(PlayerType.LOCAL, i)
             .add_player(PlayerType.REMOTE, 1 - i, "b" if i == 0 else "a"))
        sessions.append(b.start_p2p_session(net.endpoint("a" if i == 0 else "b")))

    def read_inputs(lobby, handles):
        return {h: np.uint8((lobby * 7 + h * 3) & 0xF) for h in handles}

    br = BatchedRunner(app, sessions, read_inputs=read_inputs)
    for _ in range(400):
        net.deliver()
        br.tick()
        if all(s.current_state() == SessionState.RUNNING for s in sessions):
            break
    assert all(s.current_state() == SessionState.RUNNING for s in sessions)
    for _ in range(60):
        net.deliver()
        br.tick()
    s = br.stats()
    assert min(s["frames"]) > 40, s
    # both lanes simulate the same game from the same inputs: once both
    # peers have confirmed a frame, their checksums for it must agree —
    # compare live checksums at equal frames
    if s["frames"][0] == s["frames"][1]:
        assert br.lobby_checksum(0) == br.lobby_checksum(1)


def _make_quantized_app():
    """One-player App whose float column rides a non-identity (bf16
    quantize) snapshot strategy."""
    import dataclasses

    import jax.numpy as jnp

    from bevy_ggrs_tpu import App, QuantizeStrategy
    from bevy_ggrs_tpu.snapshot import active_mask, spawn

    app = App(num_players=1, capacity=4, input_shape=(),
              input_dtype=np.uint8)
    app.rollback_component("x", (), jnp.float32,
                           strategy=QuantizeStrategy(), checksum=True)
    app.rollback_component("n", (), jnp.int32, checksum=True)

    def step(world, ctx):
        m = active_mask(world)
        return dataclasses.replace(world, comps={
            "x": jnp.where(m & world.has["x"],
                           world.comps["x"] * 1.001 + 0.01,
                           world.comps["x"]),
            "n": jnp.where(m & world.has["n"], world.comps["n"] + 1,
                           world.comps["n"]),
        })

    def setup(world):
        world, _ = spawn(app.reg, world, {"x": 0.3, "n": 0})
        return world

    app.set_step(step)
    app.set_setup(setup)
    return app


def test_batched_runner_non_identity_fused_saves_match_solo():
    """Non-identity strategies flow through the ONE-dispatch vmapped
    store_state save path (and the fused load applies load_state): quantized
    bf16 ring storage under batched SyncTest with mixed per-lobby rollback
    depths must restore exactly and stay bit-identical to solo runners (the
    per-frame store->load canonicalization absorbs any sub-bf16 float
    drift, so the comparison is exact)."""
    make_qapp = _make_quantized_app

    def make_sess(cd):
        return SyncTestSession(num_players=1, input_shape=(),
                               input_dtype=np.uint8, check_distance=cd,
                               compare_interval=1)

    cds = [3, 2, 3]
    TICKS = 15
    br = BatchedRunner(
        make_qapp(), [make_sess(cd) for cd in cds],
        read_inputs=lambda lobby, handles: {h: np.uint8(0) for h in handles},
    )
    batched = [[] for _ in cds]
    for _ in range(TICKS):
        br.tick()
        for b in range(len(cds)):
            batched[b].append(br.lobby_checksum(b))
    br.finish()  # SyncTest oracle: fused-stored rows must restore exactly
    s = br.stats()
    assert s["fallback_loads"] == 0, s

    for b, cd in enumerate(cds):
        runner = GgrsRunner(
            make_qapp(), make_sess(cd),
            read_inputs=lambda handles: {h: np.uint8(0) for h in handles},
        )
        solo = []
        for _ in range(TICKS):
            runner.tick()
            solo.append(runner.checksum)
        runner.finish()
        assert batched[b] == solo, f"lobby {b} diverged from its solo run"


def test_batched_runner_rejects_canonical_mode():
    app = stress.make_app(64, capacity=64)
    app.canonical_depth = 8
    with pytest.raises(ValueError):
        BatchedRunner(app, [_session()])


def test_batched_runner_staggered_p2p_rollback_waves():
    """The realistic server shape: several independent P2P games in ONE
    batch, each over a channel with a DIFFERENT latency/jitter, with
    flipping inputs — rollback waves hit different lobbies on different
    ticks, so load waves are partial (some lanes load while others
    advance), exercising the scatter-load fallback rather than the
    lockstep fused path the SyncTest tests cover.  Correctness oracle:
    an INPUT-SENSITIVE model (fixed_point — the stress model's step
    ignores inputs and would make this vacuous) whose two lanes per game
    must be checksum-identical at every mutually CONFIRMED ring frame
    (frames above confirmed may legitimately differ: one lane saved them
    with the remote input still predicted)."""
    from bevy_ggrs_tpu import PlayerType, SessionBuilder, SessionState
    from bevy_ggrs_tpu.session.channel import ChannelNetwork
    from bevy_ggrs_tpu.snapshot.checksum import checksum_to_int

    GAMES = 3
    app = fixed_point.make_app()
    nets, sessions = [], []
    for g in range(GAMES):
        net = ChannelNetwork(
            latency_hops=1 + g, jitter_hops=g, seed=100 + g
        )
        nets.append(net)
        for i in range(2):
            b = (SessionBuilder(input_shape=(), input_dtype=np.uint8)
                 .with_num_players(2).with_input_delay(1)
                 .with_max_prediction_window(8)
                 .add_player(PlayerType.LOCAL, i)
                 .add_player(PlayerType.REMOTE, 1 - i,
                             f"g{g}b" if i == 0 else f"g{g}a"))
            sessions.append(
                b.start_p2p_session(net.endpoint(f"g{g}a" if i == 0 else f"g{g}b"))
            )

    tick_no = [0]

    def read_inputs(lobby, handles):
        game = lobby // 2
        # different flip periods per game => mispredictions at different ticks
        on = (tick_no[0] // (4 + 2 * game)) % 2 == 0
        return {h: np.uint8(0x3 if on else 0xC) for h in handles}

    br = BatchedRunner(app, sessions, read_inputs=read_inputs)

    # record load-wave participation to prove waves were PARTIAL
    wave_profile = []
    orig_do_loads = br._do_loads

    def spying_do_loads(wave_ops, *args):
        n_load = sum(
            1 for op in wave_ops
            if op is not None and op.load_frame is not None
        )
        if n_load:
            wave_profile.append(n_load)
        return orig_do_loads(wave_ops, *args)

    br._do_loads = spying_do_loads

    def drive(n):
        for _ in range(n):
            tick_no[0] += 1
            for net in nets:
                net.deliver()
            br.tick()

    for _ in range(400):
        for net in nets:
            net.deliver()
        br.tick()
        if all(s.current_state() == SessionState.RUNNING for s in sessions):
            break
    assert all(s.current_state() == SessionState.RUNNING for s in sessions)
    drive(120)

    s = br.stats()
    assert min(s["frames"]) > 80, s
    assert br.rollbacks > 0
    # staggered: at least one load wave covered SOME but not ALL lanes
    assert wave_profile, "no rollback waves at all"
    assert any(n < 2 * GAMES for n in wave_profile), wave_profile
    # every game's two lanes agree at every mutually confirmed ring frame
    from bevy_ggrs_tpu.utils.frames import frame_le

    for g in range(GAMES):
        a, b = 2 * g, 2 * g + 1
        compared = 0
        for _ in range(8):
            conf = min(br.confirmed[a], br.confirmed[b])
            shared = [
                f for f in set(br.rings[a].frames()) & set(br.rings[b].frames())
                if frame_le(f, conf)
            ]
            if shared:
                break
            drive(1)
        assert shared, f"game {g}: no mutually confirmed ring frame"
        for f in sorted(shared):
            ca = checksum_to_int(br.rings[a].peek(f)[1])
            cb = checksum_to_int(br.rings[b].peek(f)[1])
            assert ca == cb, f"game {g} desynced at frame {f}"
            compared += 1
        assert compared > 0


# -- in-wave loads ------------------------------------------------------------

def _separate_loads(br):
    """Serve every load of ``br`` by the separate fused-load dispatch (no
    load rides a run wave): the reference the in-wave path must match."""
    br._defer_loads = lambda *args: {}
    return br


def _assert_trees_equal(a, b):
    import jax

    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _assert_rings_equal(ra, rb):
    """Same stored frames, bit-identical states and checksums."""
    from bevy_ggrs_tpu.snapshot.checksum import checksum_to_int
    from bevy_ggrs_tpu.snapshot.lazy import materialize

    assert ra.frames() == rb.frames()
    for f in ra.frames():
        (sa, ca), (sb, cb) = ra.peek(f), rb.peek(f)
        _assert_trees_equal(materialize(sa), materialize(sb))
        assert checksum_to_int(ca) == checksum_to_int(cb), f


@pytest.mark.parametrize("check_distance", [3, 4], ids=["padded", "exact"])
@pytest.mark.parametrize("app_factory", [
    lambda: stress.make_app(64, capacity=64),
    fixed_point.make_app,
], ids=["stress", "fixed_point"])
def test_in_wave_loads_bit_identical_to_separate_loads(app_factory,
                                                       check_distance):
    """SyncTest loads served inside the resim wave (a per-lane select keyed
    by the packed prefix) leave every world leaf, every live checksum and
    every ring entry bit-identical to loads served by the separate fused
    dispatch — through the padded (check distance 3, bucket 4) and the
    exact (check distance 4) load-carrying programs."""
    M, TICKS = 4, 14
    tick = [0]

    def read_inputs(lobby, handles):
        return _lobby_inputs(lobby, tick[0], handles)

    in_wave, separate = [
        BatchedRunner(app_factory(),
                      [_session(check_distance) for _ in range(M)],
                      read_inputs=read_inputs)
        for _ in range(2)
    ]
    _separate_loads(separate)
    for _ in range(TICKS):
        in_wave.tick()
        separate.tick()
        tick[0] += 1
        _assert_trees_equal(in_wave.worlds, separate.worlds)
        for b in range(M):
            assert in_wave.lobby_checksum(b) == separate.lobby_checksum(b)
            _assert_rings_equal(in_wave.rings[b], separate.rings[b])
    in_wave.finish()
    separate.finish()
    s, r = in_wave.stats(), separate.stats()
    assert s["in_wave_loads"] == s["rollbacks"] > 0, s
    assert s["fused_loads"] == 0 and s["fallback_loads"] == 0, s
    assert r["in_wave_loads"] == 0 and r["fused_loads"] > 0, r


def test_in_wave_loads_two_dispatches_per_tick():
    """Warmed-up SyncTest: the 1-frame wave and the resim wave that carries
    every lobby's load — at most 2 device dispatches a tick, whatever M."""
    M, WARM, TICKS = 8, 5, 10
    app = stress.make_app(64, capacity=64)
    br = BatchedRunner(app, [_session(check_distance=3) for _ in range(M)],
                       read_inputs=_lobby_inputs_tickless)
    for _ in range(WARM):
        br.tick()
    d0, l0 = br.device_dispatches, br.in_wave_loads
    for _ in range(TICKS):
        br.tick()
    br.finish()
    s = br.stats()
    assert br.device_dispatches - d0 <= 2 * TICKS, s
    assert br.in_wave_loads - l0 == M * TICKS, s
    assert s["fused_loads"] == 0 and s["fallback_loads"] == 0, s


def _scripted_pair(scripts, separate=False):
    """Two scripted box_game lobbies, one tick per script entry."""
    from bevy_ggrs_tpu.models import box_game
    from tests.test_speculative_runner import ScriptedSession

    sessions = [ScriptedSession([]) for _ in scripts]
    for sess, make in zip(sessions, scripts):
        sess.script = make(sess)
    br = BatchedRunner(box_game.make_app(num_players=2), sessions)
    if separate:
        _separate_loads(br)
    for _ in range(len(sessions[0].script)):
        br.tick()
    return br


def _save(sess, f):
    from bevy_ggrs_tpu.session.requests import SaveCell, SaveRequest

    return SaveRequest(f, SaveCell(sess, f))


def test_in_wave_load_then_save_saves_the_loaded_state():
    """A Save before the first Advance of a run that follows a deferred
    load (a P2P rollback to the live frame) must store the LOADED state: the
    lane's resident row is not loaded until the wave program runs."""
    from bevy_ggrs_tpu.models import box_game
    from bevy_ggrs_tpu.session.requests import LoadRequest
    from tests.test_speculative_runner import adv

    a = [box_game.keys_to_input(right=True), 0]
    b = [box_game.keys_to_input(up=True), 0]

    def script(sess):
        return [
            [_save(sess, 0), adv(a), _save(sess, 1), adv(a)],
            [LoadRequest(0), _save(sess, 0), adv(b), _save(sess, 1), adv(b)],
        ]

    in_wave = _scripted_pair([script, script])
    separate = _scripted_pair([script, script], separate=True)
    assert in_wave.stats()["in_wave_loads"] == 2
    assert separate.stats()["in_wave_loads"] == 0
    _assert_trees_equal(in_wave.worlds, separate.worlds)
    for b_ in range(2):
        _assert_rings_equal(in_wave.rings[b_], separate.rings[b_])
        assert in_wave.lobby_checksum(b_) == separate.lobby_checksum(b_)


def test_in_wave_loads_from_two_buffers_use_the_fused_load():
    """Loads of one wave that read two past buffers (lobby 0 a pre-wave
    resident world, lobby 1 a stacked wave output) are all served by the
    separate fused load, with the same result as forcing it."""
    from bevy_ggrs_tpu.session.requests import LoadRequest
    from tests.test_speculative_runner import adv

    x = [3, 0]

    def lobby0(sess):
        return [[_save(sess, 0), adv(x), _save(sess, 1), adv(x)],
                [LoadRequest(0), adv(x), adv(x)]]

    def lobby1(sess):
        return [[_save(sess, 0), adv(x), _save(sess, 1), adv(x)],
                [LoadRequest(1), adv(x)]]

    br = _scripted_pair([lobby0, lobby1])
    ref = _scripted_pair([lobby0, lobby1], separate=True)
    s = br.stats()
    assert s["in_wave_loads"] == 0 and s["fused_loads"] == 1, s
    _assert_trees_equal(br.worlds, ref.worlds)


def test_in_wave_loads_off_for_non_identity_strategy():
    """A non-identity strategy's loads need its ``load_state`` transform,
    which only the separate fused load applies: nothing is deferred."""
    sessions = [
        SyncTestSession(num_players=1, input_shape=(), input_dtype=np.uint8,
                        check_distance=3, compare_interval=1)
        for _ in range(2)
    ]
    br = BatchedRunner(
        _make_quantized_app(), sessions,
        read_inputs=lambda lobby, handles: {h: np.uint8(0) for h in handles},
    )
    for _ in range(8):
        br.tick()
    br.finish()
    s = br.stats()
    assert s["rollbacks"] > 0 and s["in_wave_loads"] == 0, s
    assert s["fused_loads"] > 0, s


def test_in_wave_loads_off_with_speculation():
    """With speculation on, a missed lookup's load goes through the
    separate fused load (speculation hits consume the run op the load would
    ride); the speculation-less runner serves the same load in-wave, with
    the same result."""
    from tests.test_batched_speculation import _run_pair, _spec

    corrected = np.uint8(9)  # not among the hedged values: a miss
    spec = _run_pair(_spec([0, 1, 2, 3]), corrected).stats()
    plain = _run_pair(None, corrected)
    assert spec["speculation"]["misses"] >= 1
    assert spec["in_wave_loads"] == 0 and spec["fused_loads"] == 1, spec
    assert plain.stats()["in_wave_loads"] == 1
