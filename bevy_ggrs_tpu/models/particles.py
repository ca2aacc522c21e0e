"""particles — the stress-test / benchmark workload.

Behavioral port of the reference's particles stress test
(/root/reference/examples/stress_tests/particles.rs): every frame spawn
``rate`` particles with seeded-random velocity and ttl, integrate gravity,
decrement ttl, despawn on expiry; the RNG state is itself rollback state
(particles.rs:125-128,243 keeps a Xoshiro256PlusPlus as a rollback resource)
so resimulated frames reproduce identical spawns; Transform participates in
the checksum via its raw f32 bit pattern (particles.rs:207-222).

TPU-native shape: a fixed-capacity pool, a ``spawn_many`` scatter of the
``rate`` new rows per frame (never a pass that gathers for every slot), a
counter-based PRNG (one uint32 counter resource -> ``jax.random`` key per
frame — the rollback-able equivalent of the sequential Xoshiro), and all
physics as masked SoA ops.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from ..app import App
from ..ops.resim import StepCtx
from ..snapshot.world import WorldState, active_mask, despawn_where, spawn_many

GRAVITY = np.float32(-9.8)
DEFAULT_TTL = 120  # frames (2 s at 60 fps, particles.rs ttl)


def make_step(app: App, rate: int, ttl: int = DEFAULT_TTL):
    """Build the particles step: ttl decay, gravity, seeded spawn bursts."""
    reg = app.reg

    def step(world: WorldState, ctx: StepCtx) -> WorldState:
        m = active_mask(world) & world.has["ttl"]
        # ttl decrement + expiry despawn
        new_ttl = jnp.where(m, world.comps["ttl"] - 1, world.comps["ttl"])
        world = dataclasses.replace(world, comps={**world.comps, "ttl": new_ttl})
        world = despawn_where(reg, world, m & (new_ttl <= 0), ctx.frame)

        # integrate
        m3 = (active_mask(world) & world.has["vel"])[:, None]
        vel = world.comps["vel"] + jnp.array([0.0, GRAVITY, 0.0]) * ctx.delta_seconds
        pos = world.comps["pos"] + vel * ctx.delta_seconds
        world = dataclasses.replace(
            world,
            comps={
                **world.comps,
                "vel": jnp.where(m3, vel, world.comps["vel"]),
                "pos": jnp.where(m3, pos, world.comps["pos"]),
            },
        )

        # seeded spawn burst — RNG counter is a rollback resource, so a resim
        # of this frame reproduces the exact same particles
        counter = world.res["rng_counter"]
        key = jax.random.fold_in(jax.random.PRNGKey(app.seed), counter)
        kv, kp = jax.random.split(key)
        new_vel = jax.random.uniform(
            kv, (rate, 3), jnp.float32, minval=-2.0, maxval=2.0
        )
        new_pos = jnp.zeros((rate, 3), jnp.float32).at[:, 1].set(
            jax.random.uniform(kp, (rate,), jnp.float32)
        )
        world = spawn_many(
            reg,
            world,
            {
                "pos": new_pos,
                "vel": new_vel,
                "ttl": jnp.full((rate,), ttl, jnp.int32),
            },
            count=rate,
        )
        return dataclasses.replace(
            world, res={**world.res, "rng_counter": counter + 1}
        )

    return step


def make_app(
    rate: int = 100,
    ttl: int = DEFAULT_TTL,
    capacity: int | None = None,
    num_players: int = 2,
    fps: int = 60,
    checksum: bool = True,
    seed: int = 0,
    quantize: bool = False,
) -> App:
    """Build the particles stress App (capacity sized for rate x ttl).

    ``quantize`` stores the float columns' ring snapshots in bf16 — the
    registration-strategy A/B knob of the reference's ``--reflect`` flag
    (/root/reference/examples/stress_tests/particles.rs:169-201), exercising
    the only non-identity Strategy under checksums/desync detection."""
    if capacity is None:
        capacity = rate * (ttl + 8) + 64  # steady state + rollback headroom
    app = App(
        num_players=num_players,
        capacity=capacity,
        fps=fps,
        input_shape=(),
        input_dtype=np.uint8,
        seed=seed,
    )
    from ..snapshot.strategy import CopyStrategy, QuantizeStrategy

    strat = QuantizeStrategy(jnp.bfloat16) if quantize else CopyStrategy
    app.rollback_component("pos", (3,), jnp.float32, checksum=checksum,
                           strategy=strat)
    app.rollback_component("vel", (3,), jnp.float32, checksum=checksum,
                           strategy=strat)
    app.rollback_component("ttl", (), jnp.int32, checksum=checksum)
    app.rollback_resource("rng_counter", jnp.uint32(0), checksum=checksum)
    app.set_step(make_step(app, rate, ttl))
    return app
