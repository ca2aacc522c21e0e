"""SyncTest lobbies on one BatchedRunner: a closed loop of ``BatchedRunner
.tick`` calls, every lobby a SyncTestSession that re-simulates
``check_distance`` frames each tick.  ``mesh_devices`` > 1 shards the
lobbies over that many chips (``make_lobby_mesh``).

Traffic keys: ``lobbies``, ``mesh_devices``, ``period`` and
``toggle_frames`` (the inputs, ``harness/inputs.py``), ``warm_frames``
(set-up ticks until every lobby reaches this frame; the SyncTest ramp and
deferred comparisons happen there), ``sample_every`` (one driver tick in
this many, at a seeded phase, keeps one seeded lobby's confirmed state
from its snapshot ring for the comparison), ``min_states`` (states the
check must compare: every lobby's world at the end plus the samples).
"""

from __future__ import annotations

from harness.inputs import InputGen, mix


class Driver:
    def __init__(self, app, cfg: dict, traffic: dict, seed: int, marks):
        import jax

        from bevy_ggrs_tpu import BatchedRunner, SyncTestSession

        self.app, self.cfg, self.traffic = app, cfg, traffic
        self.marks = marks
        self.seed = int(seed)
        self.sampling = False
        self.samples = {}
        s = cfg["session"]
        m = int(traffic["lobbies"])
        n_dev = int(traffic.get("mesh_devices", 1))
        self.sessions = [
            SyncTestSession(
                num_players=app.num_players, input_shape=app.input_shape,
                input_dtype=app.input_dtype,
                check_distance=s["check_distance"],
                max_prediction=s["max_prediction"])
            for _ in range(m)
        ]
        self.gens = [
            InputGen(mix(seed, b), traffic["period"], traffic["toggle_frames"])
            for b in range(m)
        ]
        mesh = None
        if n_dev > 1:
            from bevy_ggrs_tpu.parallel import make_lobby_mesh

            mesh = make_lobby_mesh(n_dev)
        self.devices = jax.devices()[:n_dev]
        self.mismatches = []
        self.runner = BatchedRunner(
            app, self.sessions, read_inputs=self._inputs, mesh=mesh,
            on_mismatch=lambda b, e: self.mismatches.append((b, str(e))),
        )
        if n_dev > 1 and self.runner.mesh is None:
            raise RuntimeError("BatchedRunner did not take the lobby mesh")

    def _inputs(self, b, handles):
        with self.marks("input_gen"):
            s = self.sessions[b]
            label = s.current_frame + s.input_delay
            return {h: self.gens[b].value(label, h) for h in handles}

    def warm(self) -> None:
        target = int(self.traffic["warm_frames"])
        while min(self.runner.frames) < target:
            self.runner.tick()
        self.drain()
        self.sampling = True

    def step(self, ticks: list, clock) -> None:
        with self.marks("tick", step=len(ticks)):
            t0 = clock()
            self.runner.tick()
            ticks.append(clock() - t0)
        self._sample(len(ticks))

    def _sample(self, tick: int) -> None:
        """Keep one lobby's confirmed state as the ring holds it (a handle
        into a saved output; no dispatch, no transfer)."""
        every = int(self.traffic["sample_every"])
        if not self.sampling or mix(self.seed, 0x5A3, tick // every) % every \
                != tick % every:
            return
        b = mix(self.seed, 0x5A4, tick) % len(self.sessions)
        f = self.runner.confirmed[b]
        if f >= 0 and (b, f) not in self.samples:
            snap = self.runner.rings[b].peek(f)
            if snap is not None:
                self.samples[(b, f)] = snap[0]

    def drain(self) -> None:
        import jax

        jax.block_until_ready(self.runner.worlds)

    def session_frames(self) -> int:
        return int(sum(self.runner.frames))

    def counters(self) -> dict:
        st = self.runner.stats()
        return {
            "device_dispatches": st["device_dispatches"],
            "phase_seconds": dict(st["phases"]["phase_seconds"]),
        }

    def collect(self, names) -> dict:
        """After the window: the sampled states, every lobby's live world,
        and the oracle's verdict.  Returns ``{"states": [(key, frame,
        entities, gen)], "oracle_failures": n}``."""
        from bevy_ggrs_tpu.snapshot.lazy import materialize
        from harness.compare import read_entities

        self.sampling = False
        self.runner.finish()
        states = []
        for (b, f), stored in sorted(self.samples.items()):
            world = self.app.reg.load_state(materialize(stored))
            states.append((f"lobby{b}", f, read_entities(world, names),
                           self.gens[b]))
        for b in range(len(self.sessions)):
            world = self.runner.lobby_world(b)
            states.append((f"lobby{b}", self.runner.frames[b],
                           read_entities(world, names), self.gens[b]))
        return {"states": states, "oracle_failures": len(self.mismatches)}

    def close(self) -> None:
        self.runner = None
        self.sessions = None
        self.samples = None
