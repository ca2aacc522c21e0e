"""Plain reference of box_game (bevy_ggrs examples/box_game/box_game.rs) as
this repository ports it, written from its description alone.

Player ``h`` of ``n`` starts at ``2 * (cos a, sin a)`` with ``a = 2 pi h / n``
at rest, and is entity ``h``.  The step that computes frame ``f`` reads the
inputs labelled ``f - 1``: a 4-bit mask (up 1, down 2, left 4, right 8).
It accelerates by 0.005 per pressed direction (x: right - left, z: down -
up), multiplies the velocity by the friction 0.9975, scales a speed above
0.05 back to 0.05, moves by the velocity, and clamps the position to
[-4, 4] on both axes.
"""

from __future__ import annotations

import numpy as np

FLOATS = ("pos", "vel")
EXACT = ()
USES_INPUTS = True

MOVEMENT_SPEED = 0.005
MAX_SPEED = 0.05
FRICTION = 0.9975
ARENA_HALF = 4.0


def simulate(cfg: dict, frames, inputs, seeded: dict, dtype="float32") -> dict:
    """``{frame: {"id", "pos", "vel"}}`` for each frame asked for;
    ``inputs[label, handle]`` holds every label below the last frame."""
    import jax
    import jax.numpy as jnp

    n = cfg["make_app"]["num_players"]
    want = sorted(set(int(f) for f in frames))
    last = want[-1]
    inputs = np.asarray(inputs[:last], np.uint8)
    if inputs.shape[0] < last:
        raise ValueError(f"inputs cover {inputs.shape[0]} labels, need {last}")
    slot_of = np.full(last + 1, -1, np.int32)
    slot_of[want] = np.arange(len(want), dtype=np.int32)
    d = jnp.dtype(dtype)
    angle = 2.0 * np.pi * np.arange(n) / n
    pos0 = np.stack([np.cos(angle) * 2.0, np.sin(angle) * 2.0], -1)
    pos0 = pos0.astype(np.float32)

    def run(inputs, slot_of):
        def c(x):
            return jnp.asarray(np.float32(x), d)

        def frame(carry, x):
            (pos, vel), outs = carry
            f, inp = x

            def bit(b):
                return ((inp >> b) & 1).astype(d)

            acc = jnp.stack([(bit(3) - bit(2)) * c(MOVEMENT_SPEED),
                             (bit(1) - bit(0)) * c(MOVEMENT_SPEED)], -1)
            vel = (vel + acc) * c(FRICTION)
            speed = jnp.sqrt(jnp.sum(vel * vel, -1, keepdims=True))
            scale = jnp.where(speed > c(MAX_SPEED),
                              c(MAX_SPEED) / jnp.maximum(speed, c(1e-9)),
                              c(1.0))
            vel = vel * scale
            pos = jnp.clip(pos + vel, -c(ARENA_HALF), c(ARENA_HALF))
            k = slot_of[f]
            outs = jax.lax.cond(
                k >= 0,
                lambda o: (o[0].at[k].set(pos), o[1].at[k].set(vel)),
                lambda o: o, outs)
            return ((pos, vel), outs), None

        st = (jnp.asarray(pos0, d), jnp.zeros((n, 2), d))
        outs = (jnp.zeros((len(want), n, 2), d), jnp.zeros((len(want), n, 2), d))
        fs = jnp.arange(1, last + 1, dtype=jnp.int32)
        (_, outs), _ = jax.lax.scan(frame, (st, outs), (fs, inputs))
        return outs

    with jax.default_matmul_precision("highest"):
        pos, vel = jax.device_get(jax.jit(run)(inputs, slot_of))
    ids = np.arange(n, dtype=np.int64)
    return {
        f: {"id": ids, "pos": np.asarray(pos[i], np.float32),
            "vel": np.asarray(vel[i], np.float32)}
        for i, f in enumerate(want)
    }
