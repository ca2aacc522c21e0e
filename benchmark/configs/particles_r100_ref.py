"""Plain reference of the particles stress test (bevy_ggrs
examples/stress_tests/particles.rs), written from its description alone.

Every frame ``f`` (1, 2, ...): each live particle's ttl drops by one and a
particle whose ttl reaches 0 is gone; every particle still live falls under
gravity (``vel.y += -9.8 * dt``, then ``pos += vel * dt``); then ``rate``
new particles appear with ttl ``ttl``, velocity uniform in [-2, 2)^3 and
position (0, uniform [0, 1), 0).  The random numbers of frame ``f`` come
from ``fold_in(PRNGKey(seed), c0 + f - 1)``, split into a velocity key and a
position key: the spawn counter is rollback state that starts at ``c0``.
Particles are numbered in spawn order from 0.  Inputs are not read.

All particles share one ttl, so the live ones are the last ``ttl`` bursts:
the state is a ring of ``ttl`` bursts of ``rate`` particles, and burst ``f``
takes the slot of burst ``f - ttl``, which expires in that same frame.
"""

from __future__ import annotations

import numpy as np

FLOATS = ("pos", "vel")
EXACT = ("ttl",)
USES_INPUTS = False
GRAVITY = -9.8


def simulate(cfg: dict, frames, inputs, seeded: dict, dtype="float32") -> dict:
    """``{frame: {"id", "pos", "vel", "ttl"}}`` for each frame asked for."""
    import jax
    import jax.numpy as jnp

    p = cfg["make_app"]
    rate, ttl, fps, app_seed = p["rate"], p["ttl"], p["fps"], p["seed"]
    c0 = int(seeded["rng_counter"])
    want = sorted(set(int(f) for f in frames))
    last = want[-1]
    slot_of = np.full(last + 1, -1, np.int32)
    slot_of[want] = np.arange(len(want), dtype=np.int32)
    dt_ = jnp.dtype(dtype)

    def run(slot_of):
        dt = jnp.asarray(np.float32(1.0 / fps), dt_)
        g_dt = jnp.array([0.0, GRAVITY, 0.0], dt_) * dt
        base = jax.random.PRNGKey(app_seed)
        state = (
            jnp.zeros((ttl, rate, 3), dt_),
            jnp.zeros((ttl, rate, 3), dt_),
            jnp.zeros((ttl, rate), jnp.int32),
            jnp.full((ttl,), -1, jnp.int32),
        )
        outs = (
            jnp.zeros((len(want), ttl, rate, 3), dt_),
            jnp.zeros((len(want), ttl, rate, 3), dt_),
            jnp.zeros((len(want), ttl, rate), jnp.int32),
            jnp.full((len(want), ttl), -1, jnp.int32),
        )

        def frame(carry, f):
            (pos, vel, life, born), outs = carry
            live = (born >= 0)[:, None]
            life = jnp.where(live, life - 1, life)
            keep = live & (life > 0)
            vel2 = vel + g_dt
            pos2 = pos + vel2 * dt
            vel = jnp.where(keep[..., None], vel2, vel)
            pos = jnp.where(keep[..., None], pos2, pos)
            born = jnp.where(keep[:, 0], born, -1)
            key = jax.random.fold_in(
                base, (jnp.uint32(c0) + (f - 1).astype(jnp.uint32)))
            kv, kp = jax.random.split(key)
            nv = jax.random.uniform(kv, (rate, 3), jnp.float32, -2.0, 2.0)
            ny = jax.random.uniform(kp, (rate,), jnp.float32)
            npos = jnp.zeros((rate, 3), jnp.float32).at[:, 1].set(ny)
            s = f % ttl
            pos = pos.at[s].set(npos.astype(dt_))
            vel = vel.at[s].set(nv.astype(dt_))
            life = life.at[s].set(ttl)
            born = born.at[s].set(f)
            st = (pos, vel, life, born)
            k = slot_of[f]
            outs = jax.lax.cond(
                k >= 0,
                lambda o: tuple(a.at[k].set(b) for a, b in zip(o, st)),
                lambda o: o, outs)
            return (st, outs), None

        (_, outs), _ = jax.lax.scan(
            frame, (state, outs), jnp.arange(1, last + 1, dtype=jnp.int32))
        return outs

    with jax.default_matmul_precision("highest"):
        pos, vel, life, born = jax.device_get(jax.jit(run)(slot_of))
    result = {}
    for i, f in enumerate(want):
        b = born[i]
        live = b >= 0
        ids = ((b[live].astype(np.int64) - 1) * rate)[:, None] + np.arange(rate)
        order = np.argsort(ids.reshape(-1), kind="stable")
        result[f] = {
            "id": ids.reshape(-1)[order],
            "pos": np.asarray(pos[i][live], np.float32).reshape(-1, 3)[order],
            "vel": np.asarray(vel[i][live], np.float32).reshape(-1, 3)[order],
            "ttl": life[i][live].reshape(-1)[order],
        }
    return result
