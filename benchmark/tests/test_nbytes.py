"""The least-bytes count matches the shapes the program registers."""

import jax
import numpy as np
import pytest

from harness import spec
from harness.apps import build_app
from harness.nbytes import least_bytes, registered_state_bytes


@pytest.mark.parametrize("config", ["particles_r100", "box_game_p2p"])
def test_registered_state_bytes_match_the_world(config):
    cfg = spec.load_json(f"{spec.BENCH_DIR}/configs/{config}.json")
    app = build_app(cfg, seed=1)
    world = app.init_state()
    want = sum(np.asarray(a).nbytes for a in world.comps.values())
    want += sum(np.asarray(a).nbytes for a in jax.tree.leaves(world.res))
    assert registered_state_bytes(app.reg) == want


def test_particles_state_is_28_bytes_a_slot_plus_the_counter():
    cfg = spec.load_json(f"{spec.BENCH_DIR}/configs/particles_r100.json")
    app = build_app(cfg, seed=1)
    assert registered_state_bytes(app.reg) == 13664 * (12 + 12 + 4) + 4
    assert least_bytes(10, 8, 1) == 90
