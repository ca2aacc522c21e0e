"""P2P peers over an in-process lossy network (``harness/network.py``): a
closed loop in which one iteration advances the network one hop, the
protocol's timer clock one frame (``1 / fps`` seconds) and calls every
peer's ``GgrsRunner.update(1 / fps)`` once.

The protocol's timers (sync retries, quality reports that feed time sync
and run-slow, keep-alives, disconnect) read that loop-driven clock, not the
wall clock: every iteration stands for one frame of a 60 Hz game, as the
network's hops do, so the work of a run (rollbacks, frames simulated,
frames skipped) is the same for every run of a seed, however fast the host.

Traffic keys: ``peers``, ``latency_hops``, ``loss``, ``network_seed``
(the loss pattern: the same for every run, so that every seed's peers keep
the same timing relation to each other; the run's seed varies the inputs),
``period`` (frames between input changes), ``toggle_frames`` and
``warm_frames`` (set-up runs until every peer passes this frame),
``hold_sweeps`` (set-up holds the network back for 0, 1, ...
max_prediction + 2 iterations, this many times over, so every rollback
depth compiles in set-up), ``sample_every`` (about one confirmed frame in
this many is kept for the comparison), ``min_states`` (sampled states the
check must compare).
"""

from __future__ import annotations

import sys

from harness.inputs import InputGen, mix
from harness.network import LossyNetwork

HANDSHAKE_ITERATIONS = 6000
_WALL = {}  # the session modules' own now_s, kept past a driver that failed


class LoopClock:
    """Stands in for the session modules' ``now_s`` timer clock while the
    driver lives: seconds that advance by ``step`` per loop iteration."""

    def __init__(self, step: float):
        from bevy_ggrs_tpu.session import protocol

        if not callable(getattr(protocol, "now_s", None)):
            raise RuntimeError("the P2P protocol no longer reads now_s")
        self.t = 1.0
        self.step = step
        self.wall = _WALL.setdefault("now_s", protocol.now_s)
        self.patched = [m for name, m in list(sys.modules.items())
                        if name.startswith("bevy_ggrs_tpu.session")
                        and callable(getattr(m, "now_s", None))]
        for m in self.patched:
            m.now_s = self.now

    def now(self) -> float:
        return self.t

    def tick(self) -> None:
        self.t += self.step

    def restore(self) -> None:
        for m in self.patched:
            m.now_s = self.wall
        self.patched = []


class Driver:
    def __init__(self, app, cfg: dict, traffic: dict, seed: int, marks):
        import jax

        from bevy_ggrs_tpu import (
            DesyncDetection, GgrsRunner, PlayerType, SessionBuilder,
        )

        self.app, self.cfg, self.traffic = app, cfg, traffic
        self.marks = marks
        self.seed = int(seed)
        s = cfg["session"]
        self.delay = int(s["input_delay"])
        self.max_prediction = int(s["max_prediction"])
        n = int(traffic["peers"])
        self.clock = LoopClock(1.0 / app.fps)
        self.net = LossyNetwork(traffic["latency_hops"], traffic["loss"],
                                traffic["network_seed"])
        names = [f"peer{i}" for i in range(n)]
        socks = [self.net.endpoint(nm) for nm in names]
        self.gen = InputGen(seed, traffic["period"], traffic["toggle_frames"])
        self.devices = jax.devices()[:1]
        self.sampling = False
        self.samples = [dict() for _ in range(n)]
        self.runners = []
        for i in range(n):
            b = (SessionBuilder.for_app(app)
                 .with_input_delay(self.delay)
                 .with_max_prediction_window(self.max_prediction)
                 .with_desync_detection_mode(
                     DesyncDetection.on(s["desync_interval"]))
                 .add_player(PlayerType.LOCAL, i))
            for j in range(n):
                if j != i:
                    b = b.add_player(PlayerType.REMOTE, j, names[j])
            session = b.start_p2p_session(socks[i])
            self.runners.append(GgrsRunner(
                app, session,
                read_inputs=lambda hs, s=session: self._inputs(s, hs),
                on_confirmed=lambda f, i=i: self._confirmed(i, f),
            ))

    def _inputs(self, session, handles):
        with self.marks("input_gen"):
            label = session.current_frame + self.delay
            return {h: self.gen.value(label, h) for h in handles}

    def _confirmed(self, i: int, frame: int) -> None:
        if (self.sampling and frame not in self.samples[i]
                and mix(self.seed, 0xC0F, frame)
                % self.traffic["sample_every"] == 0):
            snap = self.runners[i].ring.peek(frame)
            if snap is not None:
                self.samples[i][frame] = snap[0]

    def _iterate(self, deliver: bool = True, delta=None) -> None:
        self.clock.tick()
        if deliver:
            self.net.deliver()
        d = 1.0 / self.app.fps if delta is None else delta
        for r in self.runners:
            r.update(d)

    def warm(self) -> None:
        from bevy_ggrs_tpu import SessionState

        for _ in range(HANDSHAKE_ITERATIONS):
            if all(r.session.current_state() == SessionState.RUNNING
                   for r in self.runners):
                break
            self._iterate(delta=0.0)
        else:
            raise RuntimeError("P2P peers never finished the sync handshake")
        settle = 2 * int(self.traffic["latency_hops"]) + 4
        for _ in range(int(self.traffic["hold_sweeps"])):
            for hold in range(self.max_prediction + 3):
                for _ in range(hold):
                    self._iterate(deliver=False)
                for _ in range(settle):
                    self._iterate()
        target = int(self.traffic["warm_frames"])
        while min(r.frame for r in self.runners) < target:
            self._iterate()
        self.drain()
        self.sampling = True

    def step(self, ticks: list, clock) -> None:
        self.clock.tick()
        with self.marks("net_deliver"):
            self.net.deliver()
        d = 1.0 / self.app.fps
        for r in self.runners:
            with self.marks("tick", step=len(ticks)):
                t0 = clock()
                r.update(d)
                ticks.append(clock() - t0)

    def drain(self) -> None:
        import jax

        jax.block_until_ready([r.world for r in self.runners])

    def session_frames(self) -> int:
        return int(sum(r.frame for r in self.runners))

    def counters(self) -> dict:
        out = {"device_dispatches": 0, "phase_seconds": {}}
        for r in self.runners:
            st = r.stats()
            out["device_dispatches"] += st["device_dispatches"]
            for k, v in st["phases"]["phase_seconds"].items():
                out["phase_seconds"][k] = out["phase_seconds"].get(k, 0.0) + v
        return out

    def collect(self, names) -> dict:
        """After the window: the sampled confirmed states of every peer and
        the count of desyncs the peers' own checksum exchange reported."""
        from bevy_ggrs_tpu.session.events import DesyncDetected
        from bevy_ggrs_tpu.snapshot.lazy import materialize
        from harness.compare import read_entities

        self.sampling = False
        states = []
        for i, r in enumerate(self.runners):
            for f, stored in sorted(self.samples[i].items()):
                world = self.app.reg.load_state(materialize(stored))
                states.append((f"peer{i}", f, read_entities(world, names),
                               self.gen))
        desyncs = sum(isinstance(e, DesyncDetected)
                      for r in self.runners for e in r.events)
        return {"states": states, "oracle_failures": desyncs}

    def close(self) -> None:
        self.clock.restore()
        self.runners = None
        self.samples = None
