"""SoA world-state tests: deterministic slot allocation, stable rollback ids
(RollbackOrdered semantics, /root/reference/src/snapshot/rollback.rs:62-99),
deferred despawn / resurrect-by-restore (src/snapshot/despawn.rs), hierarchy
recursive despawn, spawn_many determinism, component/resource presence."""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bevy_ggrs_tpu import telemetry
from bevy_ggrs_tpu.snapshot import (
    Registry,
    active_mask,
    active_count,
    spawn,
    spawn_many,
    despawn,
    despawn_recursive,
    despawn_confirmed,
    insert_component,
    remove_component,
    insert_resource,
    remove_resource,
)


def make_reg(cap=16):
    reg = Registry(cap)
    reg.register_component("pos", (2,), jnp.float32, checksum=True)
    reg.register_component("hp", (), jnp.int32, default=100)
    return reg


def test_spawn_assigns_monotonic_ids_and_first_free_slots():
    reg = make_reg()
    w = reg.init_state()
    slots = []
    for i in range(4):
        w, s = spawn(reg, w, {"pos": jnp.array([i, i], jnp.float32)})
        slots.append(int(s))
    assert slots == [0, 1, 2, 3]
    assert [int(w.rollback_id[s]) for s in slots] == [0, 1, 2, 3]
    assert int(w.next_id) == 4
    assert not bool(w.overflow)


def test_slot_reuse_keeps_order_monotonic():
    # RollbackOrdered never forgets: a reused slot gets a NEW, larger id
    reg = make_reg()
    w = reg.init_state()
    w, s0 = spawn(reg, w, {})
    w, s1 = spawn(reg, w, {})
    w = despawn(reg, w, s0, frame=0)
    w = despawn_confirmed(reg, w, confirmed=0)  # hard-free slot 0
    assert not bool(w.alive[0])
    w, s2 = spawn(reg, w, {})
    assert int(s2) == 0  # first free slot reused
    assert int(w.rollback_id[0]) == 2  # fresh id, never id 0 again


def test_despawn_is_deferred_and_disabling():
    reg = make_reg()
    w = reg.init_state()
    w, s = spawn(reg, w, {})
    w = despawn(reg, w, s, frame=5)
    # still allocated, but excluded from the active mask immediately
    assert bool(w.alive[int(s)])
    assert not bool(active_mask(w)[int(s)])
    # not confirmed yet -> stays allocated
    w2 = despawn_confirmed(reg, w, confirmed=4)
    assert bool(w2.alive[int(s)])
    # confirmed -> hard-freed
    w3 = despawn_confirmed(reg, w, confirmed=5)
    assert not bool(w3.alive[int(s)])
    assert int(w3.rollback_id[int(s)]) == -1


def test_resurrect_via_snapshot_restore():
    # marking after frame F is invisible in F's snapshot: restoring F IS the
    # EntityResurrect pass (despawn.rs:69-87)
    reg = make_reg()
    w = reg.init_state()
    w, s = spawn(reg, w, {})
    snapshot = w  # save at frame 3
    w = despawn(reg, w, s, frame=5)
    restored = snapshot  # rollback to frame 3
    assert bool(active_mask(restored)[int(s)])


def test_overflow_flag():
    reg = make_reg(cap=2)
    w = reg.init_state()
    w, _ = spawn(reg, w, {})
    w, _ = spawn(reg, w, {})
    assert not bool(w.overflow)
    w, _ = spawn(reg, w, {})
    assert bool(w.overflow)


def test_spawn_many_deterministic():
    reg = make_reg(cap=8)
    w = reg.init_state()
    w, _ = spawn(reg, w, {})  # occupy slot 0
    rows = jnp.arange(6, dtype=jnp.float32).reshape(3, 2)
    w = spawn_many(reg, w, {"pos": rows}, count=3)
    assert int(active_count(w)) == 4
    # rows land in ascending free slots 1,2,3 with ids 1,2,3
    assert [int(w.rollback_id[i]) for i in (1, 2, 3)] == [1, 2, 3]
    assert jnp.allclose(w.comps["pos"][1:4], rows)
    # count can be traced/partial
    w2 = spawn_many(reg, w, {"pos": rows}, count=2)
    assert int(active_count(w2)) == 6
    assert int(w2.next_id) == 6


def test_spawn_many_overflow():
    reg = make_reg(cap=4)
    w = reg.init_state()
    rows = jnp.zeros((6, 2), jnp.float32)
    w = spawn_many(reg, w, {"pos": rows}, count=6)
    assert int(active_count(w)) == 4
    assert bool(w.overflow)


def test_hierarchy_recursive_despawn():
    reg = make_reg()
    reg.register_hierarchy()
    w = reg.init_state()
    w, root = spawn(reg, w, {})
    w, mid = spawn(reg, w, {Registry.PARENT: root})
    w, leaf = spawn(reg, w, {Registry.PARENT: mid})
    w, other = spawn(reg, w, {})
    w = despawn_recursive(reg, w, root, frame=7)
    am = active_mask(w)
    assert not bool(am[int(root)])
    assert not bool(am[int(mid)])
    assert not bool(am[int(leaf)])
    assert bool(am[int(other)])


def test_component_presence():
    reg = make_reg()
    w = reg.init_state()
    w, s = spawn(reg, w, {"pos": jnp.zeros(2)})
    assert bool(w.has["pos"][int(s)])
    assert not bool(w.has["hp"][int(s)])
    w = insert_component(reg, w, s, "hp", 42)
    assert bool(w.has["hp"][int(s)])
    assert int(w.comps["hp"][int(s)]) == 42
    w = remove_component(reg, w, s, "hp")
    assert not bool(w.has["hp"][int(s)])


def test_resource_lifecycle():
    reg = make_reg()
    reg.register_resource("score", jnp.int32(0), present=False)
    w = reg.init_state()
    assert not bool(w.res_present["score"])
    w = insert_resource(reg, w, "score", 10)
    assert bool(w.res_present["score"])
    assert int(w.res["score"]) == 10
    w = remove_resource(reg, w, "score")
    assert not bool(w.res_present["score"])


def test_required_component_inserted_on_spawn():
    reg = Registry(4)
    reg.register_component("tag", (), jnp.int32, default=7, required=True)
    w = reg.init_state()
    w, s = spawn(reg, w, {})
    assert bool(w.has["tag"][int(s)])
    assert int(w.comps["tag"][int(s)]) == 7


def test_ops_are_jittable():
    reg = make_reg()

    @jax.jit
    def build(w):
        w, s = spawn(reg, w, {"pos": jnp.ones(2)})
        w = despawn(reg, w, s, frame=3)
        w = despawn_confirmed(reg, w, confirmed=3)
        return w

    w = build(reg.init_state())
    assert int(active_count(w)) == 0
    assert int(w.next_id) == 1


def test_cloned_entity_gets_fresh_id():
    # EntityCloner regression analog (/root/reference/src/snapshot/
    # rollback.rs:121-196): copying an entity's components into a new spawn
    # must mint a NEW rollback id, never alias the source's identity
    reg = make_reg()
    w = reg.init_state()
    w, src = spawn(reg, w, {"pos": jnp.array([3.0, 4.0]), "hp": 7})
    clone_comps = {
        "pos": w.comps["pos"][src],
        "hp": w.comps["hp"][src],
    }
    w, dup = spawn(reg, w, clone_comps)
    assert int(w.rollback_id[int(src)]) != int(w.rollback_id[int(dup)])
    assert int(w.rollback_id[int(dup)]) == 1
    assert jnp.allclose(w.comps["pos"][int(dup)], w.comps["pos"][int(src)])


# -- spawn_many: per-row scatter vs the capacity-wide gather -----------------

_EQ_CAP = 4096
_EQ_LOBBIES = 4


def _eq_reg():
    reg = Registry(_EQ_CAP)
    reg.register_component("pos", (3,), jnp.float32)
    reg.register_component("hp", (), jnp.int32, default=100)  # never passed
    reg.register_component("tag", (), jnp.int32, default=7, required=True)
    return reg


def _spawn_many_gather_oracle(reg, w, comps, count):
    """spawn_many as it was first written: every slot gathers a source row."""
    rows = next(iter(comps.values())).shape[0]
    count = jnp.minimum(jnp.asarray(count, jnp.int32), rows)
    free = ~w.alive
    rank = jnp.cumsum(free) - 1
    take = free & (rank < count)
    n_taken = jnp.sum(take).astype(jnp.int32)
    row_of_slot = jnp.where(take, rank, 0)
    new_comps = dict(w.comps)
    new_has = dict(w.has)
    for name, spec in reg.components.items():
        tk = take.reshape((-1,) + (1,) * len(spec.shape))
        if name in comps:
            src = jnp.asarray(comps[name], spec.dtype)[row_of_slot]
            new_comps[name] = jnp.where(tk, src, new_comps[name])
            new_has[name] = jnp.where(take, True, new_has[name])
        elif spec.required:
            new_comps[name] = jnp.where(tk, spec.default, new_comps[name])
            new_has[name] = jnp.where(take, True, new_has[name])
        else:
            new_has[name] = jnp.where(take, False, new_has[name])
    return dataclasses.replace(
        w,
        comps=new_comps,
        has=new_has,
        alive=w.alive | take,
        rollback_id=jnp.where(take, w.next_id + row_of_slot, w.rollback_id),
        despawn_pending=jnp.where(take, False, w.despawn_pending),
        next_id=w.next_id + n_taken,
        overflow=w.overflow | (n_taken < count),
    )


def _eq_worlds(reg, share, seed):
    """Lobby-stacked worlds with ``share`` of the slots alive (random
    positions per lobby) and every column and mask holding noise."""
    rng = np.random.default_rng(seed)
    cap = reg.capacity
    n_alive = int(round(share * cap))
    worlds = []
    for lobby in range(_EQ_LOBBIES):
        w = reg.init_state()
        alive = np.zeros(cap, bool)
        alive[rng.permutation(cap)[:n_alive]] = True
        worlds.append(dataclasses.replace(
            w,
            comps={
                "pos": jnp.asarray(rng.normal(size=(cap, 3)), jnp.float32),
                "hp": jnp.asarray(rng.integers(0, 50, cap), jnp.int32),
                "tag": jnp.asarray(rng.integers(0, 50, cap), jnp.int32),
            },
            has={n: jnp.asarray(rng.random(cap) < 0.5) for n in w.has},
            alive=jnp.asarray(alive),
            rollback_id=jnp.asarray(
                np.where(alive, rng.integers(0, 1000, cap), -1), jnp.int32),
            despawn_pending=jnp.asarray(alive & (rng.random(cap) < 0.1)),
            next_id=jnp.int32(1000 + lobby),
            overflow=jnp.asarray(bool(lobby % 2)),
        ))
    return jax.tree.map(lambda *xs: jnp.stack(xs), *worlds)


@pytest.mark.parametrize("rows,path", [(8, "scatter"), (512, "gather")])
@pytest.mark.parametrize("count_of", ["zero", "fewer", "all", "more"])
@pytest.mark.parametrize("share", [0.0, 0.3, 0.9995, 1.0],
                         ids=["empty", "sparse", "nearly_full", "full"])
def test_spawn_many_matches_gather_oracle(share, count_of, rows, path):
    # 99.95% leaves 2 free slots of 4096: every count above 2 overflows
    reg = _eq_reg()
    counts = {"zero": 0, "fewer": rows // 2, "all": rows, "more": rows + 5}
    count = counts[count_of]
    world = _eq_worlds(reg, share, seed=int(share * 1e4) * 1000 + count)
    pos = jnp.asarray(
        np.random.default_rng(rows).normal(size=(_EQ_LOBBIES, rows, 3)),
        jnp.float32)
    telemetry.reset()
    telemetry.enable()
    try:
        got = jax.vmap(lambda w, p: spawn_many(reg, w, {"pos": p}, count))(
            world, pos)
        traced = telemetry.registry().counter("spawn_many_traces_total")
        assert traced.value(path=path) == 1
    finally:
        telemetry.disable()
        telemetry.reset()
    want = jax.vmap(
        lambda w, p: _spawn_many_gather_oracle(reg, w, {"pos": p}, count))(
        world, pos)
    for (kp, a), b in zip(jax.tree_util.tree_leaves_with_path(want),
                          jax.tree_util.tree_leaves(got)):
        np.testing.assert_array_equal(
            np.asarray(b), np.asarray(a), err_msg=jax.tree_util.keystr(kp))


def _gather_index_dims(hlo_text):
    """Shapes of the index operands of every gather in StableHLO text."""
    return [
        tuple(int(d) for d in m.group(1).split("x")[:-1])
        for m in re.finditer(
            r'"?stablehlo\.gather"?\(.*?:\s*\(tensor<[^>]*>,\s*tensor<([^>]*)>',
            hlo_text)
    ]


def test_particles_step_spawns_by_scatter_and_pool_fill_by_gather():
    from bevy_ggrs_tpu.models import particles
    from bevy_ggrs_tpu.ops.resim import _make_ctx

    cap, rate = 1024, 64
    app = particles.make_app(rate=rate, ttl=8, capacity=cap)
    world = app.reg.init_state()
    ctx = _make_ctx(jnp.zeros((2,), jnp.uint8), jnp.zeros((2,), jnp.int8),
                    0, 0, app.fps, app.seed)
    telemetry.reset()
    telemetry.enable()
    try:
        traced = telemetry.registry().counter("spawn_many_traces_total")
        step = particles.make_step(app, rate, ttl=8)
        step_hlo = jax.jit(step).lower(world, ctx).as_text()
        assert (traced.value(path="scatter"), traced.value(path="gather")) == (1, 0)
        cols = {"pos": jnp.zeros((cap, 3)), "vel": jnp.zeros((cap, 3)),
                "ttl": jnp.ones((cap,), jnp.int32)}
        fill_hlo = jax.jit(
            lambda w: spawn_many(app.reg, w, cols, count=cap)).lower(world).as_text()
        assert (traced.value(path="scatter"), traced.value(path="gather")) == (1, 1)
    finally:
        telemetry.disable()
        telemetry.reset()
    step_gathers = _gather_index_dims(step_hlo)
    assert step_gathers, "the slot search should gather from the free count"
    assert all(cap not in dims for dims in step_gathers), step_gathers
    # the detector sees the capacity-wide gather where it is meant to be
    assert any(cap in dims for dims in _gather_index_dims(fill_hlo))
