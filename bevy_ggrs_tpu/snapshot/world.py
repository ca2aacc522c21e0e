"""Columnar SoA world state + registry — the TPU-native ECS substrate.

The reference snapshots per-type ``HashMap<RollbackId, C>`` keyed by a stable
``RollbackId`` assigned on spawn (/root/reference/src/snapshot/rollback.rs:34-59),
reconciles live-vs-snapshot entity sets on load (src/snapshot/entity.rs:55-99),
and rewrites stale entity references through a ``RollbackEntityMap``
(src/snapshot/rollback_entity_map.rs).  Those mechanisms exist because host-ECS
entity ids are unstable across despawn/respawn.

This build inverts the layout: every registered component is a fixed-capacity
device-resident column ``[capacity, *shape]``, entity identity is (slot,
rollback_id), and a snapshot is the *entire* :class:`WorldState` pytree.
Restoring a snapshot restores the allocator, ids, masks, and columns wholesale,
so:

- entity reconciliation / respawn-with-same-id is automatic (slots are stable);
- ``RollbackEntityMap`` is the identity (slot indices stay valid) — the
  MapEntities pass (src/snapshot/component_map.rs) becomes a no-op by design;
- deferred-despawn markers behave exactly like the reference's
  ``RollbackDespawned`` disabling component (src/snapshot/despawn.rs): a marker
  set after frame F is absent from F's snapshot, so rolling back to F *is* the
  EntityResurrect pass.

Invariants preserved from the reference:

- ``rollback_id`` is assigned once per logical entity, monotonically — the
  ``RollbackOrdered`` never-forget insertion order (rollback.rs:62-99) is the id
  itself, giving checksums a stable per-entity index.
- despawn is deferred until the frame is confirmed
  (despawn.rs:89-112 -> :func:`despawn_confirmed`); marked entities are
  excluded from the active mask the way disabling components hide entities
  from queries (despawn.rs:114-143).
- spawn order is deterministic: first free slot, ids in call order.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import telemetry
from .strategy import Strategy, CopyStrategy


@jax.tree_util.register_dataclass
@dataclass
class WorldState:
    """The complete rollback-visible simulation state (a JAX pytree).

    Everything here is restored wholesale on rollback.  Host-side state that
    must NOT roll back (render caches, etc.) simply lives outside this pytree
    — the analog of not registering a type for rollback.
    """

    comps: Dict[str, jnp.ndarray]  # name -> [capacity, *shape]
    has: Dict[str, jnp.ndarray]  # name -> bool[capacity] (entity has comp)
    res: Dict[str, Any]  # resource name -> pytree
    res_present: Dict[str, jnp.ndarray]  # name -> bool scalar
    alive: jnp.ndarray  # bool[capacity]
    rollback_id: jnp.ndarray  # int32[capacity]; -1 = free slot
    despawn_pending: jnp.ndarray  # bool[capacity]
    despawn_frame: jnp.ndarray  # int32[capacity] (valid iff pending)
    next_id: jnp.ndarray  # int32 scalar: total entities ever spawned
    overflow: jnp.ndarray  # bool scalar: a spawn found no free slot


def active_mask(w: WorldState) -> jnp.ndarray:
    """Alive and not marked for deferred despawn — what 'queries' see.

    Mirrors ``RollbackDespawned`` being a disabling component
    (/root/reference/src/snapshot/despawn.rs:114-129)."""
    return w.alive & ~w.despawn_pending


@dataclass(frozen=True)
class ComponentSpec:
    """Static registration record for one component column."""
    name: str
    shape: Tuple[int, ...]
    dtype: Any
    default: Any
    checksum: bool
    hash_fn: Optional[Callable[[jnp.ndarray], jnp.ndarray]]
    strategy: Strategy
    required: bool  # inserted on every spawn (cf. #[require(Rollback)] patterns)


@dataclass(frozen=True)
class ResourceSpec:
    """Static registration record for one resource."""
    name: str
    init: Any
    checksum: bool
    hash_fn: Optional[Callable[[Any], jnp.ndarray]]
    present: bool
    strategy: Strategy


class Registry:
    """Host-side static registration of rollback state.

    The analog of the ``RollbackApp`` extension-trait registration surface
    (/root/reference/src/snapshot/rollback_app.rs:31-133): components and
    resources opt in to snapshotting, checksumming (optionally with a custom
    hash), and a store/load strategy."""

    PARENT = "child_of"  # reserved hierarchy component (ChildOf analog)

    def __init__(self, capacity: int):
        if capacity <= 0:
            raise ValueError("capacity must be positive")
        self.capacity = capacity
        self.components: Dict[str, ComponentSpec] = {}
        self.resources: Dict[str, ResourceSpec] = {}

    # -- registration ------------------------------------------------------

    def register_component(
        self,
        name: str,
        shape: Tuple[int, ...] = (),
        dtype: Any = jnp.float32,
        default: Any = None,
        checksum: bool = False,
        hash_fn: Optional[Callable] = None,
        strategy: Strategy = CopyStrategy,
        required: bool = False,
    ) -> "Registry":
        """Register a fixed-shape component column (see RollbackApp surface notes)."""
        if name in self.components:
            raise ValueError(f"component {name!r} already registered")
        # defaults live as NUMPY values: registry-held device arrays captured
        # inside jitted spawn ops become per-call parameter buffers (a
        # measured per-call slow path, docs/tpu_notes.md §1); numpy embeds
        # as XLA literals
        np_dtype = np.dtype(jnp.dtype(dtype).name) if not isinstance(dtype, np.dtype) else dtype
        if default is None:
            default = np.zeros(shape, np_dtype)
        else:
            default = np.asarray(default, np_dtype)
            if default.shape != tuple(shape):
                raise ValueError(
                    f"default for {name!r} has shape {default.shape}, want {shape}"
                )
        self.components[name] = ComponentSpec(
            name, tuple(shape), dtype, default, checksum, hash_fn, strategy, required
        )
        return self

    def register_hierarchy(self) -> "Registry":
        """Register the parent-link component (``ChildOf`` analog).

        Parent references are slot indices; because snapshots restore the
        allocator wholesale, slots are stable and no parent remap is needed on
        rollback (cf. /root/reference/src/snapshot/childof_snapshot.rs, whose
        inline remap exists only because host-ECS ids are unstable)."""
        return self.register_component(
            self.PARENT, (), jnp.int32, default=np.int32(-1), checksum=True
        )

    @property
    def has_hierarchy(self) -> bool:
        return self.PARENT in self.components

    def register_resource(
        self,
        name: str,
        init: Any,
        checksum: bool = False,
        hash_fn: Optional[Callable] = None,
        present: bool = True,
        strategy: Strategy = CopyStrategy,
    ) -> "Registry":
        """Register a resource pytree (with optional initial absence)."""
        if name in self.resources:
            raise ValueError(f"resource {name!r} already registered")
        init = jax.tree.map(np.asarray, init)  # numpy: see register_component
        self.resources[name] = ResourceSpec(
            name, init, checksum, hash_fn, present, strategy
        )
        return self

    # -- state construction ------------------------------------------------

    def init_state(self) -> WorldState:
        """Allocate the empty fixed-capacity WorldState for this registry."""
        cap = self.capacity
        comps = {
            n: jnp.broadcast_to(s.default, (cap, *s.shape)).astype(s.dtype)
            for n, s in self.components.items()
        }
        has = {n: jnp.zeros((cap,), bool) for n in self.components}
        res = {n: jax.tree.map(jnp.asarray, s.init) for n, s in self.resources.items()}
        res_present = {
            n: jnp.asarray(s.present, bool) for n, s in self.resources.items()
        }
        return WorldState(
            comps=comps,
            has=has,
            res=res,
            res_present=res_present,
            alive=jnp.zeros((cap,), bool),
            rollback_id=jnp.full((cap,), -1, jnp.int32),
            despawn_pending=jnp.zeros((cap,), bool),
            despawn_frame=jnp.zeros((cap,), jnp.int32),
            next_id=jnp.int32(0),
            overflow=jnp.asarray(False),
        )

    # -- snapshot strategies ----------------------------------------------

    def store_state(self, w: WorldState) -> WorldState:
        """Apply per-type store strategies before a snapshot is retained.

        With all-Copy strategies this is the identity; a quantizing strategy
        (e.g. bf16 ring storage) halves snapshot HBM at store time — the
        TPU-meaningful analog of the reference's Copy/Clone/Reflect strategy
        choice (/root/reference/src/snapshot/strategy.rs:22-110)."""
        comps = dict(w.comps)
        for n, s in self.components.items():
            if s.strategy.store is not None:
                comps[n] = s.strategy.store(comps[n])
        res = dict(w.res)
        for n, s in self.resources.items():
            if s.strategy.store is not None:
                res[n] = jax.tree.map(s.strategy.store, res[n])
        return dataclasses.replace(w, comps=comps, res=res)

    def load_state(self, stored: WorldState) -> WorldState:
        """Inverse of :meth:`store_state` applied when a snapshot is restored."""
        comps = dict(stored.comps)
        for n, s in self.components.items():
            if s.strategy.load is not None:
                comps[n] = s.strategy.load(comps[n]).astype(s.dtype)
        res = dict(stored.res)
        for n, s in self.resources.items():
            if s.strategy.load is not None:
                res[n] = jax.tree.map(s.strategy.load, res[n])
        return dataclasses.replace(stored, comps=comps, res=res)

    def is_identity_strategy(self) -> bool:
        return all(
            s.strategy.store is None and s.strategy.load is None
            for s in list(self.components.values()) + list(self.resources.values())
        )


# ---------------------------------------------------------------------------
# Entity operations (all jit-traceable; Registry is static)
# ---------------------------------------------------------------------------


def spawn(
    reg: Registry, w: WorldState, comps: Optional[Dict[str, Any]] = None
) -> Tuple[WorldState, jnp.ndarray]:
    """Spawn one entity in the first free slot; returns (world, slot).

    Assigns the next monotonic rollback id — the on-add hook + RollbackOrdered
    push of the reference (/root/reference/src/snapshot/rollback.rs:45-59).
    If the world is full nothing is written (live entities are untouched), the
    ``overflow`` flag is set (checked host-side), and the returned slot is -1."""
    comps = comps or {}
    free = ~w.alive
    any_free = jnp.any(free)
    slot = jnp.argmax(free).astype(jnp.int32)  # first free slot (0 when full)

    def put(arr, value):
        # masked write: a full world must leave slot 0's live state intact
        return arr.at[slot].set(jnp.where(any_free, value, arr[slot]))

    new_comps = dict(w.comps)
    new_has = dict(w.has)
    for name, spec in reg.components.items():
        if name in comps:
            row = jnp.asarray(comps[name], spec.dtype)
            new_comps[name] = put(new_comps[name], row)
            new_has[name] = put(new_has[name], True)
        elif spec.required:
            new_comps[name] = put(new_comps[name], spec.default)
            new_has[name] = put(new_has[name], True)
        else:
            new_has[name] = put(new_has[name], False)
    unknown = set(comps) - set(reg.components)
    if unknown:
        raise KeyError(f"spawn with unregistered components: {sorted(unknown)}")
    return (
        dataclasses.replace(
            w,
            comps=new_comps,
            has=new_has,
            alive=put(w.alive, True),
            rollback_id=put(w.rollback_id, w.next_id),
            despawn_pending=put(w.despawn_pending, False),
            next_id=w.next_id + any_free.astype(w.next_id.dtype),
            overflow=w.overflow | ~any_free,
        ),
        jnp.where(any_free, slot, jnp.int32(-1)),
    )


_SEARCH_BLOCK = 128  # slots per block of spawn_many's slot search (one lane row)


def spawn_many(
    reg: Registry, w: WorldState, comps: Dict[str, jnp.ndarray], count
) -> WorldState:
    """Spawn up to ``rows`` entities at once (vectorized).

    ``comps`` maps names to ``[rows, *shape]`` arrays; ``count`` (traced scalar
    <= rows) limits how many actually spawn — the particles stress test spawns
    ``--rate`` per frame this way (/root/reference/examples/stress_tests/
    particles.rs:258-271).  Row ``r`` lands in the ``r``-th free slot in
    ascending order with id ``next_id + r``, so the result is deterministic;
    rows that find no free slot set ``overflow``.

    The static shapes pick one of two formulations, bit-identical in result.
    A burst into a large pool (``rows * ceil(log2(capacity)) < capacity``,
    e.g. the particles step) searches the inclusive free count for each row's
    slot and scatters only the ``rows`` rows into each column.  A fill of
    most of the pool (the models' setup) has every slot gather its source row
    instead, one pass over the capacity that the per-row search would not
    beat.  Each trace counts its path in the telemetry counter
    ``spawn_many_traces_total{path=scatter|gather}``."""
    rows = next(iter(comps.values())).shape[0]
    cap = w.alive.shape[0]
    count = jnp.minimum(jnp.asarray(count, jnp.int32), rows)
    free = ~w.alive
    incl = jnp.cumsum(free, dtype=jnp.int32)  # free slots at or before i
    take = free & (incl <= count)  # the first `count` free slots
    n_taken = jnp.sum(take).astype(jnp.int32)
    if rows * max(1, (cap - 1).bit_length()) < cap:
        path = "scatter"
        # row r's slot is the first index where incl reaches r + 1: the
        # blocks that end below r + 1, then the slots below it in the next
        # block.  One row gather per row, where a binary search chains
        # log2(cap) dependent scalar gathers (on a v5e, 1.9 ms more device
        # time a tick for 16 lobbies of 13,664 slots)
        row = jnp.arange(rows, dtype=jnp.int32)
        nb = -(-cap // _SEARCH_BLOCK)
        blocks = jnp.pad(incl, (0, nb * _SEARCH_BLOCK - cap), mode="edge")
        blocks = blocks.reshape(nb, _SEARCH_BLOCK)
        need = (row + 1)[:, None]
        blk = jnp.sum(blocks[:, -1] < need, axis=1, dtype=jnp.int32)
        inside = blocks[jnp.minimum(blk, nb - 1)] < need
        slot = blk * _SEARCH_BLOCK + jnp.sum(inside, axis=1, dtype=jnp.int32)
        slot = jnp.where(row < n_taken, slot, cap)  # out of range: dropped

        def put_rows(col, src):
            # one scalar update per element: a row-sized update window
            # would pin the row minor in the column's layout, which the
            # TPU pads to 128 lanes (42x the bytes of a [capacity, 3] column)
            at = jnp.ix_(slot, *(jnp.arange(n) for n in col.shape[1:]))
            return col.at[at].set(src, mode="drop")
    else:
        path = "gather"
        row_of_slot = jnp.where(take, incl - 1, 0)  # row feeding each slot

        def put_rows(col, src):
            return put(col, src[row_of_slot])

    def put(col, value):
        # a value every taken slot shares is a select, not a scatter: on a
        # v5e a 1,600-row scatter into a bool column costs more (≈35 us)
        # than a pass over it
        return jnp.where(take.reshape((-1,) + (1,) * (col.ndim - 1)), value, col)

    telemetry.count("spawn_many_traces_total", path=path,
                    help="spawn_many traces by formulation (scatter|gather)")
    new_comps = dict(w.comps)
    new_has = dict(w.has)
    for name, spec in reg.components.items():
        if name in comps:
            src = jnp.asarray(comps[name], spec.dtype)
            new_comps[name] = put_rows(new_comps[name], src)
            new_has[name] = put(new_has[name], True)
        elif spec.required:
            new_comps[name] = put(new_comps[name], spec.default)
            new_has[name] = put(new_has[name], True)
        else:
            new_has[name] = put(new_has[name], False)
    return dataclasses.replace(
        w,
        comps=new_comps,
        has=new_has,
        alive=w.alive | take,
        rollback_id=put(w.rollback_id, w.next_id + incl - 1),  # id = row
        despawn_pending=put(w.despawn_pending, False),
        next_id=w.next_id + n_taken,
        overflow=w.overflow | (n_taken < count),
    )


def despawn(reg: Registry, w: WorldState, slot, frame) -> WorldState:
    """Mark ``slot`` for deferred despawn at ``frame``.

    The entity stays allocated (so a rollback before ``frame`` revives it —
    restoring the pre-mark snapshot IS the EntityResurrect pass,
    /root/reference/src/snapshot/despawn.rs:69-87) but is excluded from
    :func:`active_mask` immediately, like the disabling marker (:114-143)."""
    return dataclasses.replace(
        w,
        despawn_pending=w.despawn_pending.at[slot].set(True),
        despawn_frame=w.despawn_frame.at[slot].set(jnp.asarray(frame, jnp.int32)),
    )


def despawn_where(reg: Registry, w: WorldState, mask: jnp.ndarray, frame) -> WorldState:
    """Vectorized deferred despawn of every slot where ``mask`` (ttl expiry etc)."""
    mask = mask & w.alive
    return dataclasses.replace(
        w,
        despawn_pending=w.despawn_pending | mask,
        despawn_frame=jnp.where(mask, jnp.asarray(frame, jnp.int32), w.despawn_frame),
    )


def despawn_recursive(reg: Registry, w: WorldState, slot, frame) -> WorldState:
    """Deferred despawn of ``slot`` and all its descendants.

    Mirrors ``despawn_rollback``'s recursive marking including children
    (/root/reference/src/snapshot/despawn.rs:114-129).  Requires
    :meth:`Registry.register_hierarchy`."""
    if not reg.has_hierarchy:
        return despawn(reg, w, slot, frame)
    parent = w.comps[Registry.PARENT].astype(jnp.int32)
    has_parent = w.has[Registry.PARENT] & (parent >= 0)
    pidx = jnp.clip(parent, 0, reg.capacity - 1)
    init = jnp.zeros_like(w.alive).at[slot].set(True)

    def body(mark):
        prop = w.alive & has_parent & mark[pidx]
        return mark | prop

    def cond(carry):
        prev, cur = carry
        return jnp.any(prev != cur)

    def step(carry):
        _, cur = carry
        return cur, body(cur)

    _, mark = jax.lax.while_loop(cond, step, (jnp.zeros_like(init), init))
    return despawn_where(reg, w, mark, frame)


def despawn_confirmed(reg: Registry, w: WorldState, confirmed) -> WorldState:
    """Hard-free every slot whose despawn frame is confirmed.

    The ``AdvanceWorldSystems::DespawnConfirmed`` pass
    (/root/reference/src/snapshot/despawn.rs:89-112); wrapping i32 compare."""
    confirmed = jnp.asarray(confirmed, jnp.int32)
    kill = w.despawn_pending & ((w.despawn_frame - confirmed) <= 0)
    new_has = {n: h & ~kill for n, h in w.has.items()}
    return dataclasses.replace(
        w,
        has=new_has,
        alive=w.alive & ~kill,
        rollback_id=jnp.where(kill, -1, w.rollback_id),
        despawn_pending=w.despawn_pending & ~kill,
    )


# -- component / resource presence ops --------------------------------------


def insert_component(
    reg: Registry, w: WorldState, slot, name: str, value
) -> WorldState:
    """Give `slot` the component `name` with `value` (presence mask set)."""
    spec = reg.components[name]
    return dataclasses.replace(
        w,
        comps={**w.comps, name: w.comps[name].at[slot].set(jnp.asarray(value, spec.dtype))},
        has={**w.has, name: w.has[name].at[slot].set(True)},
    )


def remove_component(reg: Registry, w: WorldState, slot, name: str) -> WorldState:
    """Clear `slot`'s presence of component `name` (column value retained)."""
    return dataclasses.replace(
        w, has={**w.has, name: w.has[name].at[slot].set(False)}
    )


def insert_resource(reg: Registry, w: WorldState, name: str, value) -> WorldState:
    """Insert/overwrite a registered resource (present flag set).

    Mid-session insert/remove round-trips through rollback exactly like the
    reference's 4-case resource merge (/root/reference/src/snapshot/
    resource_snapshot.rs:82-98) because presence is part of the snapshot."""
    spec = reg.resources[name]
    value = jax.tree.map(
        lambda v, i: jnp.asarray(v, i.dtype), value, spec.init
    )
    return dataclasses.replace(
        w,
        res={**w.res, name: value},
        res_present={**w.res_present, name: jnp.asarray(True)},
    )


def remove_resource(reg: Registry, w: WorldState, name: str) -> WorldState:
    """Mark a registered resource absent (value retained for restore)."""
    return dataclasses.replace(
        w, res_present={**w.res_present, name: jnp.asarray(False)}
    )


def active_count(w: WorldState) -> jnp.ndarray:
    """Number of alive, not-despawn-pending entities."""
    return jnp.sum(active_mask(w)).astype(jnp.int32)
