"""Build a configuration's App through the program's own model module."""

from __future__ import annotations

import dataclasses
import importlib

import numpy as np

from .inputs import mix


def seeded_values(cfg: dict, seed: int) -> dict:
    """World values a configuration draws from the seed (resource -> int).

    ``seeded_resource`` names a uint32 resource whose initial value comes
    from the seed; the model's compiled programs stay the same for every
    seed, so the compile cache serves every run."""
    name = cfg.get("seeded_resource")
    return {} if not name else {name: mix(seed, 0x5EED) & 0xFFFFFFFF}


def build_app(cfg: dict, seed: int, control: bool = False):
    """``bevy_ggrs_tpu.models.<model>.make_app(**make_app)``, with the
    control's ``make_app`` options on top when ``control`` is set."""
    model = importlib.import_module("bevy_ggrs_tpu.models." + cfg["model"])
    kwargs = dict(cfg["make_app"])
    if control:
        kwargs.update(cfg["control"].get("make_app", {}))
    app = model.make_app(**kwargs)
    values = seeded_values(cfg, seed)
    if values:
        import jax.numpy as jnp

        def setup(world):
            res = dict(world.res)
            for k, v in values.items():
                res[k] = jnp.asarray(np.uint32(v), res[k].dtype)
            return dataclasses.replace(world, res=res)

        app.set_setup(setup)
    return app
