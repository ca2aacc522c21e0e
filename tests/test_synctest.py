"""SyncTest integration tests — the workhorse layer (SURVEY §4.3): a full
app + session + driver, continuously re-simulating ``check_distance`` frames
every tick so rollback correctness is exercised by construction.  Ports the
reference patterns: value==frame-count invariant, negative-control injected
non-determinism (tests/synctest.rs:83-125), despawn-across-rollback (:59-75),
snapshot pruning after confirm (:129-153)."""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from bevy_ggrs_tpu import (
    App, GgrsRunner, MismatchedChecksumError, SyncTestSession,
)
from bevy_ggrs_tpu.models import box_game
from bevy_ggrs_tpu.snapshot import active_count, active_mask, despawn_where, spawn


def make_counter_app(despawn_at=None, retention=8):
    # retention: despawn-retirement horizon; slots free at frame despawn+retention
    app = App(num_players=1, capacity=4, input_shape=(), input_dtype=np.uint8,
              retention=retention)
    app.rollback_component("counter", (), jnp.int32, checksum=True)

    def step(world, ctx):
        mask = active_mask(world) & world.has["counter"]
        cnt = jnp.where(mask, world.comps["counter"] + 1, world.comps["counter"])
        world = dataclasses.replace(world, comps={**world.comps, "counter": cnt})
        if despawn_at is not None:
            kill = mask & (ctx.frame == despawn_at)
            world = despawn_where(app.reg, world, kill, ctx.frame)
        return world

    def setup(world):
        world, _ = spawn(app.reg, world, {"counter": 0})
        return world

    app.set_step(step)
    app.set_setup(setup)
    return app


def make_runner(app, check_distance=2, **kw):
    session = SyncTestSession(
        num_players=app.num_players,
        input_shape=app.input_shape,
        input_dtype=app.input_dtype,
        check_distance=check_distance,
    )
    mismatches = []
    runner = GgrsRunner(
        app, session, on_mismatch=mismatches.append, **kw
    )
    return runner, mismatches


@pytest.mark.parametrize("check_distance", [0, 2, 7])
def test_counter_equals_frame_count(check_distance):
    app = make_counter_app()
    runner, mismatches = make_runner(app, check_distance)
    for _ in range(20):
        runner.tick()
    assert mismatches == []
    assert runner.frame == 20
    assert int(runner.world.comps["counter"][0]) == 20


def test_negative_control_detects_injected_nondeterminism():
    # the reference proves its detector fires by injecting non-determinism
    # (tests/synctest.rs:83-125); here: poke checksummed state behind the
    # session's back mid-run
    app = make_counter_app()
    runner, mismatches = make_runner(app, check_distance=3)
    for _ in range(10):
        runner.tick()
    assert mismatches == []
    runner.world = dataclasses.replace(
        runner.world,
        comps={**runner.world.comps, "counter": runner.world.comps["counter"] + 1000},
    )
    runner._world_checksum = app.checksum_fn(runner.world)
    for _ in range(6):
        runner.tick()
    assert len(mismatches) >= 1


def test_despawn_across_rollback():
    app = make_counter_app(despawn_at=10, retention=8)
    runner, mismatches = make_runner(app, check_distance=3)
    for _ in range(15):
        runner.tick()
    # entity disabled immediately, still allocated within the retention window
    assert int(active_count(runner.world)) == 0
    for _ in range(10):
        runner.tick()
    assert mismatches == []
    # past frame despawn_at + retention -> slot hard-freed
    assert not bool(runner.world.alive[0])


def test_snapshot_pruning_after_confirm():
    app = make_counter_app()
    runner, _ = make_runner(app, check_distance=2)
    for _ in range(30):
        runner.tick()
    assert len(runner.ring) <= runner.ring.depth
    # everything older than the confirmed frame was pruned
    assert all(f >= runner.confirmed for f in runner.ring.frames())


def test_non_checksummed_component_still_rolls_back():
    app = App(num_players=1, capacity=4, input_shape=(), input_dtype=np.uint8)
    app.rollback_component("cs", (), jnp.int32, checksum=True)
    app.rollback_component("plain", (), jnp.int32, checksum=False)

    def step(world, ctx):
        m = active_mask(world)
        return dataclasses.replace(
            world,
            comps={
                "cs": jnp.where(m, world.comps["cs"] + 1, world.comps["cs"]),
                "plain": jnp.where(m, world.comps["plain"] + 2, world.comps["plain"]),
            },
        )

    def setup(world):
        world, _ = spawn(app.reg, world, {"cs": 0, "plain": 0})
        return world

    app.set_step(step)
    app.set_setup(setup)
    runner, mismatches = make_runner(app, check_distance=2)
    for _ in range(12):
        runner.tick()
    assert mismatches == []
    assert int(runner.world.comps["plain"][0]) == 24


def test_box_game_synctest_moves_player():
    app = box_game.make_app(num_players=2)

    def read_inputs(handles):
        return {h: box_game.keys_to_input(right=(h == 0)) for h in handles}

    session = SyncTestSession(
        num_players=2, input_shape=(), input_dtype=np.uint8, check_distance=2
    )
    mismatches = []
    runner = GgrsRunner(
        app, session, read_inputs=read_inputs, on_mismatch=mismatches.append
    )
    x0 = float(runner.world.comps["pos"][0, 0])
    for _ in range(30):
        runner.tick()
    assert mismatches == []
    assert float(runner.world.comps["pos"][0, 0]) > x0  # player 0 moved right
    # player 1 (no input) only drifts by friction: vel stays 0
    assert float(jnp.abs(runner.world.comps["vel"][1]).max()) == 0.0


def test_input_delay_shifts_effect():
    app = box_game.make_app(num_players=1, capacity=4)
    session = SyncTestSession(
        num_players=1, input_shape=(), input_dtype=np.uint8,
        check_distance=0, input_delay=5,
    )
    runner = GgrsRunner(
        app,
        session,
        read_inputs=lambda hs: {h: box_game.keys_to_input(right=True) for h in hs},
    )
    for _ in range(3):
        runner.tick()
    # inputs delayed by 5 frames: nothing has moved yet
    assert float(jnp.abs(runner.world.comps["vel"][0]).max()) == 0.0
    for _ in range(10):
        runner.tick()
    assert float(runner.world.comps["vel"][0, 0]) > 0.0


def test_accumulator_runs_multiple_frames_per_update():
    app = make_counter_app()
    runner, _ = make_runner(app, check_distance=1)
    runner.update(5.5 / 60.0)  # one big host tick -> 5 ggrs frames
    assert runner.frame == 5


def test_session_restart_resets_driver():
    app = make_counter_app()
    runner, _ = make_runner(app, check_distance=2)
    for _ in range(10):
        runner.tick()
    assert runner.frame == 10
    runner.set_session(
        SyncTestSession(num_players=1, input_shape=(), input_dtype=np.uint8,
                        check_distance=2)
    )
    assert runner.frame == 0
    assert len(runner.ring) == 0
    for _ in range(4):
        runner.tick()
    assert runner.frame == 4


# -- deferred comparison (compare_interval > 1; the accelerator default) ----
# The CPU auto default is 1 (prompt), so these pin the deferred path
# explicitly: batching, the widened cell GC horizon, check_now, and the
# runner's end-of-run / session-swap flush.


def _deferred_runner(interval, check_distance=3):
    app = make_counter_app()
    session = SyncTestSession(
        num_players=1, input_shape=(), input_dtype=np.uint8,
        check_distance=check_distance, compare_interval=interval,
    )
    mismatches = []
    runner = GgrsRunner(app, session, on_mismatch=mismatches.append)
    return runner, session, mismatches


def _inject_divergence(runner):
    runner.world = dataclasses.replace(
        runner.world,
        comps={**runner.world.comps,
               "counter": runner.world.comps["counter"] + 1000},
    )
    runner._world_checksum = runner.app.checksum_fn(runner.world)


def test_deferred_compare_batches_and_still_detects():
    runner, session, mismatches = _deferred_runner(interval=8)
    for _ in range(10):
        runner.tick()
    assert mismatches == []
    _inject_divergence(runner)
    bad_frame = runner.frame
    # detection is deferred but must land within one compare interval, and
    # the widened cell GC horizon must keep the frames alive until compared
    for i in range(session.compare_interval() + session.check_distance + 2):
        runner.tick()
        if mismatches:
            break
    assert mismatches, "deferred comparison never fired"
    assert any(f >= bad_frame - session.check_distance
               for f in mismatches[0].mismatched_frames)


def test_stagger_compares_moves_the_next_comparison_forward_only():
    def ticks_that_compare(session, n):
        out = []
        for t in range(1, n + 1):
            session.add_local_input(0, np.uint8(0))
            session.advance_frame()
            if session._ticks_since_compare == 0:
                out.append(t)
        return out

    def fresh():
        return SyncTestSession(num_players=1, input_shape=(),
                               input_dtype=np.uint8, check_distance=2,
                               compare_interval=8)

    s = fresh()
    s.stagger_compares(3)
    assert ticks_that_compare(s, 20) == [5, 13]
    s = fresh()
    s.stagger_compares(8 + 3)  # modulo the interval
    assert ticks_that_compare(s, 6) == [5]
    s = fresh()
    assert ticks_that_compare(s, 6) == []
    s.stagger_compares(5)  # past the count: the next tick, never later
    assert ticks_that_compare(s, 9) == [1, 9]


def test_staggered_deferred_compare_still_detects():
    runner, session, mismatches = _deferred_runner(interval=8)
    session.stagger_compares(5)
    for _ in range(10):
        runner.tick()
    assert mismatches == []
    _inject_divergence(runner)
    bad_frame = runner.frame
    for i in range(session.compare_interval() + session.check_distance + 2):
        runner.tick()
        if mismatches:
            break
    assert mismatches, "deferred comparison never fired"
    assert any(f >= bad_frame - session.check_distance
               for f in mismatches[0].mismatched_frames)


class _LateChecksum:
    """A checksum provider whose device copy lands when ``ready`` is set."""

    def __init__(self, value):
        self.value, self.ready, self.forced = value, False, 0

    def peek(self):
        return self.value if self.ready else None

    def __call__(self):
        self.forced += 1
        return self.value


def test_deferred_compare_leaves_unlanded_young_frames_for_the_next():
    s = SyncTestSession(num_players=1, input_shape=(), input_dtype=np.uint8,
                        check_distance=2, compare_interval=4)
    s.current_frame = 10
    young = [_LateChecksum(7), _LateChecksum(8)]  # age 1: disagree
    old = [_LateChecksum(5), _LateChecksum(5)]  # age 4 > d + 1: complete
    for p in young:
        s._on_cell_saved(9, p)
    for p in old:
        s._on_cell_saved(6, p)
    s._check_mismatches(force=False)  # deferred: no wait, no raise
    assert [p.forced for p in young] == [0, 0]
    assert [p.forced for p in old] == [1, 1]
    assert s.pending_comparisons() == 1
    for p in young:
        p.ready = True
    with pytest.raises(MismatchedChecksumError) as e:
        s._check_mismatches(force=False)
    assert e.value.mismatched_frames == [9]


def test_forced_compare_waits_for_every_frame():
    s = SyncTestSession(num_players=1, input_shape=(), input_dtype=np.uint8,
                        check_distance=2, compare_interval=4)
    s.current_frame = 10
    young = [_LateChecksum(7), _LateChecksum(8)]
    for p in young:
        s._on_cell_saved(9, p)
    with pytest.raises(MismatchedChecksumError):
        s.check_now()
    assert [p.forced for p in young] == [1, 1]


def test_check_now_forces_pending_comparisons():
    runner, session, mismatches = _deferred_runner(interval=64)
    for _ in range(10):
        runner.tick()
    _inject_divergence(runner)
    for _ in range(session.check_distance + 1):
        runner.tick()  # divergent resim saves recorded, not yet compared
    assert mismatches == []  # interval=64: nothing compared yet
    with pytest.raises(Exception):
        session.check_now()


def test_runner_finish_flushes_deferred_comparisons():
    runner, session, mismatches = _deferred_runner(interval=64)
    for _ in range(10):
        runner.tick()
    _inject_divergence(runner)
    for _ in range(session.check_distance + 1):
        runner.tick()
    assert mismatches == []
    runner.finish()  # end-of-run flush routes to on_mismatch
    assert mismatches


def test_session_swap_flushes_deferred_comparisons():
    runner, session, mismatches = _deferred_runner(interval=64)
    for _ in range(10):
        runner.tick()
    _inject_divergence(runner)
    for _ in range(session.check_distance + 1):
        runner.tick()
    assert mismatches == []
    runner.set_session(None)  # replacing the session must not drop checks
    assert mismatches
