"""A run drives the whole harness with the timed path broken underneath and
``correct`` comes out false; a sound run of the same small cell comes out
true.  The lower-precision control comes out false too.

The cells are the committed ones cut to a size a test can hold (2 lobbies
of a 10-particle-per-frame world, a short P2P warm-up); the chip check
skips, everything else runs as ``run.py`` runs it."""

import dataclasses
import time

import jax.numpy as jnp
import pytest

from harness import spec
from harness.measure import run_cell


def small_particles():
    cell = spec.load_cell("particles_synctest_m16")
    cell.config = dict(cell.config, make_app=dict(
        cell.config["make_app"], rate=10, ttl=20, capacity=10 * 36 + 64))
    cell.traffic = dict(cell.traffic, lobbies=2, warm_frames=60, min_states=2)
    return cell


def small_p2p(name="boxgame_p2p_pair"):
    cell = spec.load_cell(name)
    cell.traffic = dict(cell.traffic, hold_sweeps=1, toggle_frames=260,
                        warm_frames=270, sample_every=4, min_states=10)
    return cell


def small_particles_p2p():
    cell = small_p2p("particles_p2p_pair")
    cell.config = dict(cell.config, make_app=dict(
        cell.config["make_app"], rate=10, ttl=20, capacity=10 * 36 + 64))
    return cell


def run(cell, **kw):
    return run_cell(cell, seed=2**33 + 7, seconds=1.0, trace=False,
                    t_proc0=time.perf_counter(), require_chip=False, **kw)


def wrap_step(app, fn):
    step = app.step
    app.set_step(lambda w, ctx: fn(step(w, ctx), ctx))


def nudge(w, ctx):
    pos = w.comps["pos"]
    pos = jnp.where(ctx.frame % 7 == 0, pos + 0.01, pos)
    return dataclasses.replace(w, comps={**w.comps, "pos": pos})


@pytest.mark.parametrize("make", [small_particles, small_p2p])
def test_sound_run_is_correct(make):
    r = run(make())
    assert r["correct"], r["checks"]


@pytest.mark.parametrize("make", [small_particles, small_p2p])
def test_step_returning_state_unchanged_fails(make):
    r = run(make(), app_hook=lambda app: app.set_step(lambda w, ctx: w))
    assert not r["correct"]


@pytest.mark.parametrize("make", [small_particles, small_p2p])
def test_answer_altered_where_produced_fails(make):
    r = run(make(), app_hook=lambda app: wrap_step(app, nudge))
    assert not r["correct"]
    assert r["checks"]["state_err"]["value"] > r["checks"]["state_err"]["limit"]


def test_half_the_lobbies_left_out_fails(monkeypatch):
    from bevy_ggrs_tpu.ops import batch

    orig = batch.BucketedWaveExecutor.run_wave_packed

    def half(self, worlds, packed, ks):
        import jax

        bucket, finals, stacked, checks = orig(self, worlds, packed, ks)
        h = len(ks) // 2
        finals = jax.tree.map(lambda f, w: f.at[h:].set(w[h:]), finals, worlds)
        return bucket, finals, stacked, checks

    monkeypatch.setattr(batch.BucketedWaveExecutor, "run_wave_packed", half)
    r = run(small_particles())
    assert not r["correct"]


def test_exchange_between_peers_left_out_fails(monkeypatch):
    drv = small_p2p().driver()
    warm = drv.Driver.warm

    def cut_after_warm(self):
        warm(self)
        self.net.loss = 1.0

    cell = small_p2p()
    monkeypatch.setattr(cell, "driver", lambda: drv)
    monkeypatch.setattr(drv.Driver, "warm", cut_after_warm)
    r = run(cell)
    assert not r["correct"]


@pytest.mark.parametrize("make,control", [
    (small_particles, "program"),
    (small_particles, "reference"),
    (small_p2p, "reference"),
    (small_particles_p2p, "reference"),
])
def test_lower_precision_control_fails(make, control):
    r = run(make(), control=control)
    assert not r["correct"]
    assert r["checks"]["state_err"]["value"] > r["checks"]["state_err"]["limit"]


def test_fault_healed_before_the_window_ends_fails():
    """A wrong step in a few frames early in the window: its particles die
    before the end, so only the states sampled inside the window see it."""
    cell = small_particles()

    def early(w, ctx):
        bad = (ctx.frame > 62) & (ctx.frame <= 66)
        pos = w.comps["pos"]
        return dataclasses.replace(
            w, comps={**w.comps, "pos": jnp.where(bad, pos + 0.01, pos)})

    r = run(cell, app_hook=lambda app: wrap_step(app, early))
    assert not r["correct"]
    assert min(r["info"]["frames_compared"]) <= 66 + 20
    assert max(r["info"]["frames_compared"]) > 66 + 20
