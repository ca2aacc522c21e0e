"""Device-mesh sharding of the rollback world — scale past one chip.

The reference is a single-process library; its scaling axes are entity count
and rollback depth.  Here the entity (capacity) axis shards across a
``jax.sharding.Mesh`` "data" axis, and speculative input branches shard
across a "spec" axis — SPMD via sharding annotations, letting XLA insert the
collectives (the scaling-book recipe: pick a mesh, annotate, let XLA place
psum/all-gather on ICI).

Correctness notes:
- the checksum reduces over the entity axis with *wrapping uint32 addition*
  (snapshot/checksum.py:12-19) — associative/commutative integer arithmetic,
  exact under any sharding (a plain psum), so sharded and single-device runs
  produce bit-identical checksums as long as the state bits match;
- ``spawn`` takes an argmax and ``spawn_many`` a cumsum of the free mask over
  the sharded axis (a small burst then searches that cumsum and
  scatters its rows into the sharded columns); XLA inserts the collectives,
  and the result is deterministic regardless of layout.
"""

from __future__ import annotations

from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..app import App
from ..ops.resim import resim
from ..snapshot.world import WorldState

DATA_AXIS = "data"
SPEC_AXIS = "spec"
LOBBY_AXIS = "lobby"


def make_lobby_mesh(n_devices: Optional[int] = None, devices=None) -> Mesh:
    """1-D device mesh over the ``"lobby"`` axis — the many-worlds scale-out
    shape (ops/batch.ShardedWaveExecutor): each device owns a contiguous
    block of lobby lanes and runs the SAME bucketed wave program on them,
    so a wave of M lobbies costs O(1) dispatches per device.

    Orthogonal to :func:`make_mesh`: that mesh shards ONE world over its
    entity axis; this one shards MANY whole worlds over the lobby axis
    (no collectives at all — lobbies never communicate)."""
    devices = devices if devices is not None else jax.devices()
    if n_devices is None:
        n_devices = len(devices)
    if n_devices < 1:
        raise ValueError(f"lobby mesh needs >= 1 device, got {n_devices}")
    return Mesh(np.array(devices[:n_devices]), (LOBBY_AXIS,))


def lobby_sharding(mesh: Mesh, ndim: int = 1) -> NamedSharding:
    """NamedSharding splitting the leading (lobby) axis over the mesh."""
    return NamedSharding(mesh, P(LOBBY_AXIS, *([None] * (ndim - 1))))


def shard_lobby_worlds(mesh: Mesh, worlds):
    """Place a stacked ``[M, ...]`` many-worlds pytree onto the lobby mesh
    (every leaf's leading axis split over ``"lobby"``; M must divide by the
    device count — the BatchedRunner pads its resident world to ensure it)."""
    return jax.device_put(
        worlds, jax.tree.map(lambda a: lobby_sharding(mesh, a.ndim), worlds)
    )


def make_mesh(
    n_data: Optional[int] = None, n_spec: int = 1, devices=None
) -> Mesh:
    """Build a (data x spec) device mesh from the available devices."""
    devices = devices if devices is not None else jax.devices()
    if n_data is None:
        n_data = len(devices) // n_spec
    use = np.array(devices[: n_data * n_spec]).reshape(n_data, n_spec)
    return Mesh(use, (DATA_AXIS, SPEC_AXIS))


def world_sharding(reg, mesh: Mesh, world: WorldState):
    """NamedSharding pytree: capacity-axis leaves shard over "data",
    scalars/resources replicate."""
    cap = reg.capacity

    def leaf_sharding(x):
        if x.ndim >= 1 and x.shape[0] == cap:
            return NamedSharding(mesh, P(DATA_AXIS, *([None] * (x.ndim - 1))))
        return NamedSharding(mesh, P())

    return jax.tree.map(leaf_sharding, world)


def shard_world(app: App, mesh: Mesh, world: WorldState) -> WorldState:
    """Place a world onto the mesh with entity-axis sharding."""
    return jax.device_put(world, world_sharding(app.reg, mesh, world))


def make_sharded_resim_fn(app: App, mesh: Mesh):
    """jit resim with the world sharded over the mesh "data" axis.

    Shapes: inputs_seq [k, P, ...]; returns (final, stacked, checksums) with
    the same entity-axis sharding on states."""
    fps, seed, reg, step = app.fps, app.seed, app.reg, app.step
    retention = app.retention

    @jax.jit
    def fn(world, inputs_seq, status_seq, start_frame):
        return resim(
            reg, step, world, inputs_seq, status_seq, start_frame, retention,
            fps, seed
        )

    def wrapped(world, inputs_seq, status_seq, start_frame, _unused=None):
        world = shard_world(app, mesh, world)
        return fn(world, inputs_seq, status_seq, start_frame)

    return wrapped


def make_sharded_speculate_fn(app: App, mesh: Mesh):
    """Speculative fan-out with branches over "spec" x entities over "data".

    ``inputs_branches``: [M, k, P, ...] sharded over the "spec" axis; the
    broadcast world shards over "data".  One jit call evaluates all branches
    across the whole mesh."""
    fps, seed, reg, step = app.fps, app.seed, app.reg, app.step
    retention = app.retention

    @jax.jit
    def fn(world, inputs_branches, status_branches, start_frame):
        return jax.vmap(
            lambda inp, stat: resim(
                reg, step, world, inp, stat, start_frame, retention, fps, seed
            )
        )(inputs_branches, status_branches)

    def wrapped(world, inputs_branches, status_branches, start_frame, _unused=None):
        world = shard_world(app, mesh, world)
        spec_sharding = NamedSharding(
            mesh, P(SPEC_AXIS, *([None] * (inputs_branches.ndim - 1)))
        )
        inputs_branches = jax.device_put(inputs_branches, spec_sharding)
        status_branches = jax.device_put(
            status_branches,
            NamedSharding(mesh, P(SPEC_AXIS, *([None] * (status_branches.ndim - 1)))),
        )
        return fn(world, inputs_branches, status_branches, start_frame)

    return wrapped


def make_sharded_canonical_fn(app: App, mesh: Mesh):
    """The canonical [branches, depth] program sharded over the mesh:
    entities over "data", branch lanes over "spec" — the full TPU-first
    shape (bit-determinism + speculation + multi-chip in one dispatch).

    Signature matches ``app.branched_fn``:
    fn(world, inputs[B, K, P, ...], status[B, K, P], start_frame, n_real[B]).
    """
    fn = app.branched_fn  # jitted; sharding comes from input placement

    def wrapped(world, inputs_b, status_b, start_frame, n_real):
        world = shard_world(app, mesh, world)
        spec = lambda nd: NamedSharding(mesh, P(SPEC_AXIS, *([None] * (nd - 1))))
        inputs_b = jax.device_put(jax.numpy.asarray(inputs_b), spec(np.ndim(inputs_b)))
        status_b = jax.device_put(jax.numpy.asarray(status_b), spec(np.ndim(status_b)))
        n_real = jax.device_put(jax.numpy.asarray(n_real), spec(1))
        return fn(world, inputs_b, status_b, start_frame, n_real)

    return wrapped
