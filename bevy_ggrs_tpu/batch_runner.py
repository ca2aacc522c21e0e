"""BatchedRunner — the many-worlds game server driver.

The reference runs ONE session per process (`Session` is a singleton Bevy
resource, /root/reference/src/lib.rs:79-88); a server hosting M lobbies runs
M processes, each dispatching its own tiny sim.  A TPU inverts the economics:
one chip eats hundreds of small worlds per pass, and the fixed
per-dispatch submission cost dominates small worlds — so M serial
dispatches are the one thing the server must not do.

This driver owns M sessions (any mix of SyncTest / P2P / in-process — they
only need the GgrsRequest protocol) over ONE resident ``[M, ...]`` stacked
world.  Each server tick it:

1. polls every session and collects its request list (host-side, cheap);
2. splits each lobby's list into an ordered sequence of ops —
   ``Load(frame)`` / ``Run([Save|Advance ...])`` — exactly the segments
   GgrsRunner fuses per lobby (runner.py _handle_requests);
3. executes ops positionally as WAVES across lobbies: wave w batches every
   lobby's w-th Run into ONE dispatch through the shape-bucketed executor
   (ops/batch.BucketedWaveExecutor: smallest power-of-two depth bucket
   covering the wave's ``k_hot``, exact unmasked program for full waves,
   ``n_real``-masked program for ragged ones), and serves Load ops from
   per-lobby snapshot rings.  A load of the lobby's OWN past row (its lane
   of a past wave output or resident world) rides the lobby's next run
   wave: the packed prefix's ``has_load``/``load_slot`` words select the
   row inside the wave program, lane-locally, with no dispatch of its own
   (``_defer_loads``).  Every other load — rows of *different* past
   buffers, non-identity strategies, speculation, a list ending on a
   Load — is served by ONE fused mixed-source gather: lobbies are grouped
   per buffer (snapshot/lazy.plan_row_gather) and scattered into the
   resident world in a single jitted program.

The steady-state tick therefore costs a CONSTANT number of device
dispatches independent of the lobby count M (one per run wave, one per
load wave that could not ride a run wave, plus one fused ``store_state``
dispatch for non-identity strategies) — verified by the dispatch-flatness
gate in bench.py's batched stage.  Inputs stage through persistent
preallocated host buffers (no per-tick allocation), and saves store
``LazySlice(stacked, (lobby, frame_idx))`` handles — one ``[M, K, ...]``
buffer per wave backs every lobby's ring rows, with checksum pulls riding
the process-wide BatchChecks fusion (snapshot/lazy.py).

Bit-equality caveat (same as ops/batch.py): the vmapped program is a
DIFFERENT XLA program than the single-lobby one, so for variant-unstable
float sims a batched lobby is not guaranteed bit-identical to a solo run of
the same inputs; integer/fixed-point sims and variant-stable steps (probe
with ops/variant_probe.py) batch exactly — proven by
tests/test_batched_runner.py against M independent GgrsRunners.  Canonical
modes are refused for the same reason (make_batched_resim_fn docstring).
"""

from __future__ import annotations

import gc
import time
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

from . import telemetry
from .app import App
from .ops.batch import (
    BucketedWaveExecutor,
    DraftWaveScheduler,
    ShardedWaveExecutor,
    stack_worlds,
)
from .ops.speculation import SpeculationCache, SpeculationConfig
from .session.events import (
    DesyncDetected,
    InputStatus,
    MismatchedChecksumError,
    NotSynchronizedError,
    PredictionThresholdError,
    SessionState,
)
from .session.requests import AdvanceRequest, GgrsRequest, LoadRequest, SaveRequest
from .session.synctest import SyncTestSession
from .snapshot.lazy import (
    BatchChecks,
    LazySlice,
    fused_gather_rows,
    fused_load_rows,
    materialize,
    plan_row_gather,
    readback_queue,
)
from .snapshot.ring import SnapshotRing, rollback_many
from .telemetry.compiles import programs_built
from .utils import compile_guard
from .utils.frames import NULL_FRAME, frame_add


class _Op:
    __slots__ = ("load_frame", "load_cause", "run")

    def __init__(self, load_frame=None, run=None, load_cause=None):
        self.load_frame = load_frame  # int | None
        self.load_cause = load_cause  # RollbackCause | None
        self.run = run  # List[GgrsRequest] | None


def _split_ops(requests: List[GgrsRequest]) -> List[_Op]:
    """[Load?](Advance|Save)* request list -> ordered Load/Run ops
    (the same maximal-run fusion as GgrsRunner._handle_requests)."""
    ops: List[_Op] = []
    i, n = 0, len(requests)
    while i < n:
        r = requests[i]
        if isinstance(r, LoadRequest):
            ops.append(_Op(load_frame=r.frame, load_cause=r.cause))
            i += 1
        else:
            j = i
            while j < n and isinstance(requests[j], (AdvanceRequest, SaveRequest)):
                j += 1
            ops.append(_Op(run=requests[i:j]))
            i = j
    return ops


class ShardPlanner:
    """Host-side shard accounting for the lobby-sharded executor.

    Lobby lanes map to devices STATICALLY — lobby ``b`` lives on device
    ``b // (m_pad / D)`` (contiguous blocks, the layout shard_map splits the
    stacked world into) — so the "packing" decision the planner owns is the
    per-tick bucket shape: it derives each device's active-lane count and
    hottest advance depth from the wave's ``ks``, publishes the tick's
    ``shard_imbalance_ratio`` gauge (max/mean active lobbies per device —
    1.0 is a perfectly flat wave), and tracks the worst ratio seen.  A
    ratio that stays high is the signal to re-home lobbies across devices
    (a roadmap item — re-homing moves resident state, so it must be rare
    and amortized, not per-tick)."""

    def __init__(self, n_lobbies: int, n_devices: int):
        self.n_lobbies = int(n_lobbies)
        self.n_devices = int(n_devices)
        self.m_pad = -(-self.n_lobbies // self.n_devices) * self.n_devices
        self.lanes_per_shard = self.m_pad // self.n_devices
        self.last_imbalance = 1.0
        self.max_imbalance = 1.0
        self.waves_planned = 0
        self._g_imbalance = telemetry.registry().bind_gauge(
            "shard_imbalance_ratio",
            "max/mean active lobbies per device for the tick's run wave",
        )

    def shard_of(self, b: int) -> int:
        """Device index owning lobby lane ``b``."""
        return b // self.lanes_per_shard

    def plan(self, ks: Sequence[int]) -> dict:
        """Pack one wave's per-lobby advance counts into per-device
        buckets; returns ``{"active_per_shard", "k_hot_per_shard",
        "imbalance_ratio"}`` and publishes the gauge."""
        active = [0] * self.n_devices
        hot = [0] * self.n_devices
        for b, k in enumerate(ks):
            if k > 0:
                d = self.shard_of(b)
                active[d] += 1
                hot[d] = max(hot[d], k)
        total = sum(active)
        ratio = (max(active) * self.n_devices / total) if total else 1.0
        self.last_imbalance = ratio
        self.max_imbalance = max(self.max_imbalance, ratio)
        self.waves_planned += 1
        self._g_imbalance.set(ratio)
        return {
            "active_per_shard": active,
            "k_hot_per_shard": hot,
            "imbalance_ratio": ratio,
        }


class BatchedRunner:
    """M lobbies, one fused device dispatch per wave (module docstring).

    With ``mesh=`` (a ``parallel.make_lobby_mesh()`` handle) the lobby axis
    additionally shards across the mesh's devices: the resident stacked
    world is padded to a device-count multiple, placed with lobby-axis
    sharding, and every wave dispatches through the
    :class:`~.ops.batch.ShardedWaveExecutor` — O(1) dispatches PER DEVICE
    per tick.  Falls back to the single-device executor automatically when
    the mesh has one device or the process sees only one device."""

    def __init__(
        self,
        app: App,
        sessions: Sequence,
        read_inputs: Optional[Callable[[int, List[int]], Dict[int, np.ndarray]]] = None,
        on_mismatch: Optional[Callable[[int, MismatchedChecksumError], None]] = None,
        on_event: Optional[Callable[[int, object], None]] = None,
        k_max: Optional[int] = None,
        pipeline: bool = True,
        packed: bool = True,
        mesh=None,
        speculation: Optional[SpeculationConfig] = None,
    ):
        if app.canonical_depth is not None or app.canonical_branches is not None:
            raise ValueError(
                "BatchedRunner is incompatible with canonical mode "
                "(see ops/batch.make_batched_resim_fn)"
            )
        self.app = app
        self.sessions = list(sessions)
        m = len(self.sessions)
        if m == 0:
            raise ValueError("BatchedRunner needs at least one session")
        # the deepest run any session can emit in one tick: a rollback spans
        # the full window plus the live advance
        windows = []
        for s in self.sessions:
            w = (
                s.rollback_window()
                if hasattr(s, "rollback_window")
                else s.max_prediction()
            )
            windows.append(max(w, s.max_prediction()))
            if self.app.retention < w:
                raise ValueError(
                    f"App(retention={self.app.retention}) < session rollback "
                    f"window ({w}) — see GgrsRunner.set_session"
                )
        self.k_max = k_max if k_max is not None else max(windows) + 1
        # SyncTest lobbies started together would all run their deferred
        # comparisons on the same tick, one tick in every interval paying
        # every lobby's; give each lobby its own phase of the interval
        for b, s in enumerate(self.sessions):
            if isinstance(s, SyncTestSession):
                s.stagger_compares(b * s.compare_interval() // m)
        self.read_inputs = read_inputs or (
            lambda lobby, handles: {h: app.zero_inputs()[h] for h in handles}
        )
        self.on_mismatch = on_mismatch
        self.on_event = on_event
        # lobby-mesh sharding: only engage when the mesh actually spans
        # multiple devices AND the process can see them (single-device
        # fallback keeps laptops/1-chip hosts on the proven path)
        self.mesh = None
        self.planner: Optional[ShardPlanner] = None
        if mesh is not None and int(mesh.devices.size) > 1:
            import jax as _jx

            if len(_jx.devices()) > 1:
                self.mesh = mesh
        if self.mesh is not None:
            self.planner = ShardPlanner(m, int(self.mesh.devices.size))
            m_pad = self.planner.m_pad
        else:
            m_pad = m
        self._m_pad = m_pad
        # resident world: padded to a device-count multiple in sharded mode
        # (pad lanes are permanently idle — every wave masks them at
        # n_real=0, no session ever maps to them) and placed with lobby-axis
        # sharding so each device owns its contiguous block of lanes
        self.worlds = stack_worlds([app.init_state() for _ in range(m_pad)])
        # shape-bucketed wave programs replace the single k_max-deep padded
        # fn: a 1-advance lockstep wave dispatches the exact k=1 program, a
        # ragged rollback wave the smallest masked bucket covering it.
        # recycle_outputs stays OFF here — the rings below hold LazySlice
        # handles into past stacked outputs, so they must never be donated.
        if self.mesh is not None:
            from .parallel.mesh import shard_lobby_worlds

            self.worlds = shard_lobby_worlds(self.mesh, self.worlds)
            self.exec = ShardedWaveExecutor(app, self.k_max, self.mesh)
        else:
            self.exec = BucketedWaveExecutor(app, self.k_max)
        # per-lobby live-world checksum handles (ONE vmapped dispatch for
        # all M rows; leading saves reuse these instead of dispatching)
        import jax as _jax

        from .snapshot.checksum import world_checksum as _wc

        self._batch_checksum_fn = _jax.jit(
            lambda ws: _jax.vmap(lambda w: _wc(app.reg, w))(ws)
        )
        # pipelined readback: start non-blocking device->host checksum
        # copies at dispatch time and collect them at the next tick() —
        # same engine as GgrsRunner (docs/architecture.md "Tick pipeline")
        self.pipeline = bool(pipeline)
        self._rbq = readback_queue()
        init_batch = BatchChecks(self._batch_checksum_fn(self.worlds))
        if self.pipeline:
            self._rbq.start(init_batch)
        self._world_checksum = [init_batch.ref(b) for b in range(m)]
        # device-memory accounting (telemetry/devmem.py): the resident
        # stacked world, the per-lobby snapshot rings (one padded-world
        # footprint per stored entry) and the staging buffers below all
        # report under this instance's namespace and die with it
        import weakref

        from .utils.mem import tree_device_bytes

        self._devmem_tag = telemetry.devmem.scope("batched")
        weakref.finalize(self, telemetry.devmem.forget_scope, self._devmem_tag)
        worlds_nbytes = tree_device_bytes(self.worlds)
        telemetry.devmem.note(self._devmem_tag + "/worlds", worlds_nbytes)
        row_nbytes = worlds_nbytes // max(m_pad, 1)
        self.rings = [SnapshotRing(depth=max(windows) + 2) for _ in range(m)]
        for b, ring in enumerate(self.rings):
            ring.set_accounting(
                f"{self._devmem_tag}/ring{b}", row_nbytes
            )
        self.frames = [0] * m  # per-lobby RollbackFrameCount
        self.confirmed = [NULL_FRAME] * m
        self.ticks = 0
        self.rollbacks = 0
        self.device_dispatches = 0
        self.fused_loads = 0
        self.fallback_loads = 0
        self.in_wave_loads = 0
        # loads handed to a later run wave: wave index -> (source buffer,
        # {lobby: stored LazySlice}); consumed by that wave's _do_runs
        self._deferred: Dict[int, tuple] = {}
        self.stalled = [0] * m
        self._np = self.sessions[0].num_players()
        for s in self.sessions:
            if s.num_players() != self._np:
                raise ValueError("all lobbies must share num_players "
                                 "(one batched input tensor)")
        # persistent staging: the per-tick input/status tensors are filled in
        # place every wave instead of re-allocated (allocation churn was a
        # measurable slice of the 1-CPU-host tick).  Idle/padded lanes keep
        # stale rows — the padded program's n_real mask discards them, the
        # exact program never sees them.
        self._stage_inputs = np.zeros(
            (m_pad, self.k_max, self._np, *app.input_shape), app.input_dtype
        )
        self._stage_status = np.zeros((m_pad, self.k_max, self._np), np.int8)
        self._stage_starts = np.zeros((m_pad,), np.int32)
        # packed single-upload staging (ops/packing.py): the wave's inputs,
        # status, per-lobby start frames AND per-lobby n_real ride ONE
        # persistent int8 buffer — a wave costs one host->device upload
        # instead of 3-4.  Pad lanes (sharded mode) are zeroed here and
        # never written, so their prefix reads n_real=0 forever; idle REAL
        # lanes get their prefix rewritten every wave (a stale nonzero
        # n_real from a previous wave would resurrect dead advances).
        self.packed = bool(packed)
        self._stage_packed = (
            app.packed_spec.new_batch_buffer(m_pad, self.k_max)
            if self.packed else None
        )
        telemetry.devmem.note(
            self._devmem_tag + "/staging",
            self._stage_inputs.nbytes + self._stage_status.nbytes
            + self._stage_starts.nbytes,
        )
        if self._stage_packed is not None:
            telemetry.devmem.note(
                self._devmem_tag + "/packed_staging",
                self._stage_packed.nbytes,
            )
        # Speculative draft waves (docs/architecture.md "Speculative rollback
        # servicing"): per-lobby branch caches filled by an EXTRA wave that
        # only occupies lanes the active bucket left idle; on a LoadRequest
        # whose corrected run was fully hedged the rollback is served as a
        # row scatter of the cached final plus LazySlice ring pushes —
        # zero resim frames.  The mode matrix is strict (ValueError, never a
        # silent fallback): drafts ride the packed batch staging, cached
        # branch states scatter STRAIGHT into the resident world so the
        # snapshot strategy must be identity, and the draft gather/scatter
        # is not yet shard-aware.
        self.spec_caches: Optional[List[SpeculationCache]] = None
        self.spec_config = speculation
        self.draft_waves = 0
        self.cache_served_frames = 0
        self._last_wave = None  # (prev_worlds, stacked, ks) of last run wave
        self._last_adv: Optional[List[list]] = None
        self._draft_sched: Optional[DraftWaveScheduler] = None
        self._stage_packed_draft = None
        if speculation is not None:
            if not self.packed:
                raise ValueError(
                    "BatchedRunner speculation requires packed=True: draft "
                    "waves ride the packed single-upload batch staging "
                    "(mode matrix in docs/architecture.md)"
                )
            if not self.app.reg.is_identity_strategy():
                raise ValueError(
                    "BatchedRunner speculation requires an identity snapshot "
                    "strategy: cached branch states scatter straight into "
                    "the resident stacked world on a hit (mode matrix in "
                    "docs/architecture.md)"
                )
            if self.mesh is not None:
                raise ValueError(
                    "BatchedRunner speculation is not shard-aware yet: the "
                    "draft wave's base gather and hit scatter assume a "
                    "single-device resident world (mode matrix in "
                    "docs/architecture.md)"
                )
            depth = max(speculation.depth, 1)
            if depth > self.k_max:
                raise ValueError(
                    f"speculation depth {depth} exceeds k_max={self.k_max}; "
                    "drafts dispatch through the same bucketed wave "
                    "executor as real runs"
                )
            self.spec_caches = [
                SpeculationCache(app, speculation) for _ in range(m)
            ]
            self._draft_sched = DraftWaveScheduler(m_pad)
            self._draft_bucket = self.exec.bucket_for(depth)
            self._stage_packed_draft = app.packed_spec.new_batch_buffer(
                m_pad, self._draft_bucket
            )
            telemetry.devmem.note(
                self._devmem_tag + "/draft_staging",
                self._stage_packed_draft.nbytes,
            )
            self._m_drafts = telemetry.registry().bind_counter(
                "draft_dispatches_total",
                "speculative draft dispatches issued into idle pipeline "
                "slots / spare wave lanes",
            )
        # stable bound-method refs: snapshot-strategy hooks fused into the
        # batched load/save programs (and the jit-cache keys of
        # fused_load_rows / fused_gather_rows)
        if self.app.reg.is_identity_strategy():
            self._load_transform = None
            self._store_transform = None
        else:
            self._load_transform = self.app.reg.load_state
            self._store_transform = self.app.reg.store_state
        # tick-phase latency attribution (flight recorder + tick_phase_ms
        # histograms — docs/observability.md "Phase timers")
        self._phases = telemetry.PhaseSet(owner="batched")
        # pre-bound argument-free counters: name+help registered ONCE here,
        # per-tick increments are attribute checks (not dict/string traffic)
        _treg = telemetry.registry()
        self._m_ticks = _treg.bind_counter(
            "server_ticks_total", "batched-server ticks (all lobbies)"
        )
        self._m_dispatches = _treg.bind_counter(
            "device_dispatches_total",
            "fused device dispatches (resim + load + store waves)",
        )
        self._m_resim_frames = _treg.bind_counter(
            "resim_frames_total",
            "frames resimulated beyond the first of each dispatch",
        )
        self._m_rollbacks = _treg.bind_counter(
            "rollbacks_total", "LoadRequests executed"
        )
        self._m_fused_loads = _treg.bind_counter(
            "fused_load_dispatches_total",
            "load waves served by one mixed-source gather",
        )
        self._m_fallback_loads = _treg.bind_counter(
            "fallback_load_rows_total",
            "load rows served by per-lobby scatter (non-LazySlice snapshot)",
        )
        self._m_loads = _treg.bind_counter(
            "batched_loads_total",
            "rollback loads by path: in_wave (selected inside the next run "
            "wave) or separate (the fused load dispatch)",
        )

    # -- per-tick driver ----------------------------------------------------

    def tick(self) -> None:
        """One server tick: poll + step every lobby, flush as waves."""
        self.ticks += 1
        self._m_ticks.inc()
        ph = self._phases
        ph.begin_tick()
        built0 = programs_built()
        if self.pipeline:
            # harvest last tick's landed checksum copies before the lobby
            # polls publish them (never blocks)
            with ph.phase("readback_harvest"):
                self._rbq.harvest()
        per_lobby_ops: List[List[_Op]] = []
        for b, s in enumerate(self.sessions):
            per_lobby_ops.append(self._collect_ops(b, s))
        n_waves = max((len(ops) for ops in per_lobby_ops), default=0)
        self._last_wave = None
        self._last_adv = None
        for w in range(n_waves):
            wave_ops = [
                ops[w] if w < len(ops) else None for ops in per_lobby_ops
            ]
            self._do_loads(wave_ops, per_lobby_ops, w)
            self._do_runs(wave_ops, w)
        if self.spec_caches is not None:
            # hedge the tick's predicted transitions into the lanes the last
            # run wave left idle (draft capacity, not extra census)
            self._speculate_idle_lanes()
        for b, s in enumerate(self.sessions):
            cf = s.confirmed_frame()
            self.confirmed[b] = cf
            self.rings[b].confirm(cf)
        if programs_built() != built0:
            _settle_heap()
        if n_waves:
            # handshake-only ticks (no lobby emitted an op) stay out of the
            # flight ring — they would evict the interesting entries.  The
            # residency stamp feeds the trace counter track; gated so the
            # fully-disabled path computes nothing (telemetry/trace.py)
            if ph.on:
                ph.end_tick(
                    frame=max(self.frames), lobbies=len(self.sessions),
                    device_bytes=telemetry.devmem.total(),
                    pipeline_depth=(
                        self._rbq.depth() if self.pipeline else 0
                    ),
                )
            else:
                ph.end_tick(
                    frame=max(self.frames), lobbies=len(self.sessions)
                )

    def _collect_ops(self, b: int, s) -> List[_Op]:
        with self._phases.phase("net_poll"):
            if hasattr(s, "poll_remote_clients"):
                s.poll_remote_clients()
            if hasattr(s, "events") and (
                self.on_event is not None or telemetry.enabled()
            ):
                for ev in s.events():
                    if isinstance(ev, DesyncDetected):
                        telemetry.record(
                            "checksum_mismatch", source="p2p", lobby=b,
                            frames=[ev.frame], local_checksum=ev.local_checksum,
                            remote_checksum=ev.remote_checksum,
                            addr=repr(ev.addr),
                        )
                        if telemetry.forensics_dir() is not None:
                            # lobby_world is a device gather — only pay it
                            # when a report will actually be written
                            telemetry.write_desync_report(
                                "p2p_desync", reg=self.app.reg,
                                world=self.lobby_world(b), frames=[ev.frame],
                                local_checksum=ev.local_checksum,
                                remote_checksum=ev.remote_checksum,
                                addr=ev.addr, lobby=b,
                            )
                    if self.on_event is not None:
                        self.on_event(b, ev)
        if isinstance(s, SyncTestSession):
            handles = list(range(s.num_players()))
        else:
            if s.current_state() != SessionState.RUNNING:
                return []  # still handshaking: poll only
            handles = list(s.local_player_handles())
        for h, v in self.read_inputs(b, handles).items():
            s.add_local_input(h, v)
        try:
            with self._phases.phase("session_step"):
                requests = s.advance_frame()
        except MismatchedChecksumError as e:
            self._report_mismatch(b, e)
            if self.on_mismatch is not None:
                self.on_mismatch(b, e)
                return []
            raise
        except PredictionThresholdError:
            self.stalled[b] += 1
            telemetry.count(
                "stalled_frames_total", help="ticks skipped on stall",
                kind="p2p", lobby=b,
            )  # cold path (exceptional), help re-pass is fine here
            telemetry.record("stall", lobby=b, frame=self.frames[b],
                             reason="prediction_threshold")
            return []
        except NotSynchronizedError:
            return []
        return _split_ops(requests)

    # -- loads --------------------------------------------------------------

    def _do_loads(
        self,
        wave_ops: List[Optional[_Op]],
        per_lobby_ops: Optional[List[List[_Op]]] = None,
        w: int = 0,
    ) -> None:
        loads = [
            (b, op.load_frame, op.load_cause)
            for b, op in enumerate(wave_ops)
            if op is not None and op.load_frame is not None
        ]
        if not loads:
            return
        self.rollbacks += len(loads)
        for b, f, _c in loads:
            self._phases.note_rollback(self.frames[b] - f)
        if telemetry.enabled():
            for b, f, cause in loads:
                depth = self.frames[b] - f
                # cause-less loads (legacy session types) blame "unknown" so
                # rollback_cause_total summed over handles still equals
                # rollbacks_total across every driver
                blamed = cause.handle if cause is not None else "unknown"
                if blamed is None:
                    blamed = "unknown"
                lateness = cause.lateness if cause is not None else depth
                telemetry.count("rollbacks_total", lobby=b)
                telemetry.count(
                    "rollback_cause_total",
                    help="rollbacks attributed to the peer whose input "
                         "caused them",
                    lobby=b, handle=blamed,
                )
                telemetry.observe(
                    "rollback_depth", depth, lobby=b,
                )
                telemetry.observe(
                    "input_lateness_frames", lateness,
                    "frames late the blamed input arrived",
                    lobby=b, handle=blamed,
                )
                telemetry.record(
                    "rollback", lobby=b, to_frame=f,
                    from_frame=self.frames[b], depth=depth,
                    handle=blamed, lateness=lateness,
                    cause_kind=cause.kind if cause is not None else "unknown",
                )
        # Speculation hit servicing: a Load whose FOLLOWING run (the next
        # wave's op for that lobby) was fully hedged is served entirely from
        # the lobby's branch cache — the ring pop is bookkeeping, the world
        # restore is one row scatter of the cached final, the run's saves
        # become LazySlice handles into the branch stack, and the consumed
        # run op is blanked so the next wave never dispatches it.  Partial
        # hits (corrected inputs hedged for a prefix only) fall through to
        # the miss path: serving them would split one run op across cache
        # and wave, shifting every other lobby's wave alignment.
        hits: Dict[int, tuple] = {}
        if self.spec_caches is not None and per_lobby_ops is not None:
            for b, f, _c in loads:
                ops_b = per_lobby_ops[b]
                nxt = ops_b[w + 1] if w + 1 < len(ops_b) else None
                if nxt is None or not nxt.run:
                    continue
                advs = [r for r in nxt.run if isinstance(r, AdvanceRequest)]
                if not advs:
                    continue
                got = self.spec_caches[b].lookup_seq(
                    f, np.stack([a.inputs for a in advs])
                )
                full = got is not None and got[0] == len(advs)
                telemetry.count(
                    "speculation_hits_total" if full
                    else "speculation_misses_total",
                    help="speculative branch-cache lookups",
                )
                if full:
                    hits[b] = (f, got, nxt)
        if hits:
            t_hit = time.perf_counter()
            with self._phases.phase("rollback_load"):
                for b, (f, got, nxt) in hits.items():
                    d, states_fn, checks_b = got
                    # bookkeeping-only rollback: pop the newer ring entries,
                    # keep the target's stored handle for leading saves
                    stored, cs0 = self.rings[b].rollback(f)
                    self.spec_caches[b].invalidate_after(f)
                    cbc = BatchChecks(checks_b)
                    self.worlds = _set_row(self.worlds, b, states_fn(d - 1))
                    self.device_dispatches += 1
                    self._m_dispatches.inc()
                    if self.pipeline:
                        self._rbq.start(cbc)
                    self._world_checksum[b] = cbc.ref(d - 1)
                    self.frames[b] = frame_add(f, d)
                    self.cache_served_frames += d
                    c = 0
                    for r in nxt.run:
                        if isinstance(r, AdvanceRequest):
                            c += 1
                        elif c == 0:
                            self.rings[b].push(r.frame, (stored, cs0))
                            r.cell.save(r.frame, cs0)
                        else:
                            cs = cbc.ref(c - 1)
                            self.rings[b].push(
                                r.frame,
                                (LazySlice(states_fn.stacked, c - 1), cs),
                            )
                            r.cell.save(r.frame, cs)
                    per_lobby_ops[b][w + 1] = None  # run consumed
                    telemetry.record(
                        "speculation_hit", lobby=b, frame=f, depth=d,
                        advances=d,
                    )
            telemetry.observe(
                "rollback_service_ms", (time.perf_counter() - t_hit) * 1e3,
                "wall ms to service one rollback (LoadRequest + its "
                "following Advance/Save run)",
                buckets=telemetry.LATENCY_MS_BUCKETS,
                path="hit",
            )
            loads = [(b, f, c) for b, f, c in loads if b not in hits]
            if not loads:
                return
        t_miss = time.perf_counter()
        with self._phases.phase("rollback_load"):
            # batched mixed-source load: roll every ring back, group the
            # stored LazySlice handles by backing stacked buffer, and serve
            # the whole wave — even when lobbies load from DIFFERENT past
            # dispatches' buffers — as ONE jitted gather+scatter.  A
            # non-identity strategy's load_state hook is vmapped into the
            # same program.
            entries = rollback_many(
                self.rings, [(b, f) for b, f, _c in loads]
            )
            deferred = self._defer_loads(entries, per_lobby_ops, w)
            groups, fallback = plan_row_gather(
                [(b, stored) for b, (stored, _cs) in entries
                 if b not in deferred]
            )
            if groups:
                self.worlds = fused_load_rows(
                    self.worlds, groups, self._load_transform
                )
                self.device_dispatches += 1
                self.fused_loads += 1
                self._m_dispatches.inc()
                self._m_fused_loads.inc()
                self._m_loads.inc(
                    sum(len(g[3]) for g in groups), path="separate"
                )
            for b, stored in fallback:
                # rare path: a ring entry that is a concrete pytree (not a
                # LazySlice into a stacked buffer) — per-lobby scatter
                state = self.app.reg.load_state(materialize(stored))
                self.worlds = _set_row(self.worlds, b, state)
                self.device_dispatches += 1
                self.fallback_loads += 1
                self._m_dispatches.inc()
                self._m_fallback_loads.inc()
            for b, (_stored, cs) in entries:
                self._world_checksum[b] = cs
            for b, f, _c in loads:
                self.frames[b] = f
                if self.spec_caches is not None:
                    # branches hedged from now-superseded predicted states
                    # must not serve future lookups (SpeculationCache
                    # .invalidate_after)
                    self.spec_caches[b].invalidate_after(f)
        if self.spec_caches is not None:
            telemetry.observe(
                "rollback_service_ms", (time.perf_counter() - t_miss) * 1e3,
                "wall ms to service one rollback (LoadRequest + its "
                "following Advance/Save run)",
                buckets=telemetry.LATENCY_MS_BUCKETS,
                path="miss",
            )

    def _defer_loads(
        self, entries, per_lobby_ops, w: int
    ) -> Dict[int, LazySlice]:
        """Hand the wave's loads of each lobby's OWN past row to that
        lobby's next run wave (``w + 1``), where the wave program selects
        the row lane-locally (``BucketedWaveExecutor.stage_loads``) — the
        load costs no dispatch, no index upload and, sharded, no collective.

        Deferral needs the packed prefix, the identity strategy (no
        ``load_state`` transform) and speculation off; under those every
        ``LazySlice`` ring entry is a row of a past wave output or resident
        world, both ``[M_pad, ...]``.  A load then qualifies when its entry
        is a ``LazySlice`` into lane ``b`` and the lobby's next op is a run
        with at least one advance (so that wave dispatches).  When the
        qualifying loads read more than one buffer none is deferred.  The
        host bookkeeping (ring, frame, checksum) stays with the caller.
        Returns the deferred ``{lobby: stored}``."""
        if (not self.packed or self._load_transform is not None
                or self.spec_caches is not None or per_lobby_ops is None):
            return {}
        picked: Dict[int, LazySlice] = {}
        buf = None
        for b, (stored, _cs) in entries:
            if not isinstance(stored, LazySlice):
                continue
            if _lane_slot(stored)[0] != b:
                continue
            ops_b = per_lobby_ops[b]
            nxt = ops_b[w + 1] if w + 1 < len(ops_b) else None
            if nxt is None or not any(
                isinstance(r, AdvanceRequest) for r in (nxt.run or ())
            ):
                continue
            if buf is None:
                buf = stored._stacked
            elif stored._stacked is not buf:
                return {}  # two source buffers: the fused load serves all
            picked[b] = stored
        if picked:
            self._deferred[w + 1] = (buf, picked)
            self.in_wave_loads += len(picked)
            self._m_loads.inc(len(picked), path="in_wave")
        return picked

    # -- runs ---------------------------------------------------------------

    def _do_runs(self, wave_ops: List[Optional[_Op]], w: int) -> None:
        m = len(self.sessions)
        # loads _defer_loads handed to this wave: their lanes start from
        # their own row of `load_src` inside the wave program
        load_src, in_wave = self._deferred.pop(w, (None, {}))
        runs = [op.run if op is not None else None for op in wave_ops]
        adv = [
            [r for r in (run or []) if isinstance(r, AdvanceRequest)]
            for run in runs
        ]
        ks = [len(a) for a in adv]
        if not any(run for run in runs):
            return
        k_hot = max(ks)
        if k_hot > self.k_max:
            raise ValueError(
                f"lobby requested a {k_hot}-frame run > k_max={self.k_max}; "
                "raise BatchedRunner(k_max=...)"
            )
        identity = self.app.reg.is_identity_strategy()
        stacked = batch = None
        bucket = 0
        pre_checksum = list(self._world_checksum)
        prev_worlds = self.worlds
        ph = self._phases
        if k_hot > 0:
            ph.note_advances(sum(ks))
            bucket = self.exec.bucket_for(k_hot)
            # persistent staging fill (no per-tick allocation): write each
            # lobby's rows in place, repeat the last real row through the
            # bucket tail (padding inputs never affect results — masked by
            # n_real — but keeping them finite avoids garbage-driven traps)
            with ph.phase("stage_inputs"):
                if self.packed:
                    from .ops.packing import (
                        pack_prefix,
                        pack_row,
                        repeat_last_row,
                    )

                    pspec = self.app.packed_spec
                    packed = self._stage_packed
                    for b, a in enumerate(adv):
                        kb = len(a)
                        lane = packed[b]
                        # prefix rewritten EVERY wave: an idle lane must
                        # read n_real=0 (and no load) even if a past wave
                        # left payload
                        stored = in_wave.get(b)
                        if stored is None:
                            pack_prefix(lane, self.frames[b], kb)
                        else:
                            pack_prefix(lane, self.frames[b], kb, 1,
                                        _lane_slot(stored)[1])
                        for i, x in enumerate(a):
                            pack_row(pspec, lane, i, x.inputs, x.status)
                        repeat_last_row(lane, kb, bucket)
                else:
                    inputs, status = self._stage_inputs, self._stage_status
                    starts = self._stage_starts
                    starts[:m] = self.frames  # pad lanes keep 0
                    for b, a in enumerate(adv):
                        kb = len(a)
                        if not kb:
                            continue
                        bi, bs = inputs[b], status[b]
                        for i, x in enumerate(a):
                            bi[i] = x.inputs
                            bs[i] = x.status
                        if kb < bucket:
                            bi[kb:bucket] = bi[kb - 1]
                            bs[kb:bucket] = bs[kb - 1]
            self.device_dispatches += 1
            self._m_dispatches.inc()
            self._m_resim_frames.inc(sum(max(k - 1, 0) for k in ks))
            telemetry.record(
                "dispatch", batched=True, k_hot=k_hot,
                active_lobbies=sum(1 for k in ks if k > 0),
            )
            # sharded mode: the planner packs the wave into per-device
            # buckets (gauge + imbalance tracking) and the executor sees
            # the full padded lane list so its M is device-divisible (the
            # resident world/staging are already padded — no per-wave
            # pad/trim dispatches on this path)
            wave_ks = ks
            if self.planner is not None:
                self.planner.plan(ks)
                wave_ks = ks + [0] * (self._m_pad - m)
            with ph.phase("wave_dispatch"):
                if self.packed:
                    if in_wave:
                        self.exec.stage_loads(load_src)
                    bucket, finals, stacked, checks_flat = (
                        self.exec.run_wave_packed(
                            self.worlds, self._stage_packed, wave_ks
                        )
                    )
                else:
                    bucket, finals, stacked, checks_flat = self.exec.run_wave(
                        self.worlds, inputs, status, starts, wave_ks
                    )
                batch = BatchChecks(checks_flat)
                if self.pipeline:
                    self._rbq.start(batch)
                self.worlds = finals
                for b in range(m):
                    if ks[b] > 0:
                        self.frames[b] = frame_add(self.frames[b], ks[b])
                        self._world_checksum[b] = batch.ref(
                            b * bucket + ks[b] - 1
                        )
            if self.spec_caches is not None:
                # draft-wave inputs (_speculate_idle_lanes): which lanes the
                # active bucket left idle, and each drafting lobby's base
                # state (the one feeding its LAST advance)
                self._last_wave = (prev_worlds, stacked, list(ks))
                self._last_adv = adv
        with ph.phase("store_save"):
            # collect this wave's saves as (lobby, advance-count-before, req)
            saves = []
            for b, run in enumerate(runs):
                if not run:
                    continue
                c = 0
                for r in run:
                    if isinstance(r, AdvanceRequest):
                        c += 1
                    else:
                        saves.append((b, c, r))
            if not saves:
                return
            handles = []
            for b, c, _r in saves:
                if c == 0:
                    # pre-dispatch save: slice the PREVIOUS resident world's
                    # row (still alive in prev_worlds); its checksum handle
                    # was tracked, not recomputed.  A lane loading in this
                    # wave saves its loaded entry: its prev_worlds row
                    # predates the load
                    handles.append(in_wave.get(b) or LazySlice(prev_worlds, b))
                else:
                    handles.append(LazySlice(stacked, (b, c - 1)))
            if not identity:
                # one-dispatch non-identity saves: gather every saved row
                # (mixed prev_worlds / stacked sources) and vmap the
                # strategy's store_state over them in ONE jitted program;
                # ring entries become LazySlice handles into the fused
                # stored stack instead of M materialized pytrees
                groups, _none = plan_row_gather(list(enumerate(handles)))
                stored_stack = fused_gather_rows(groups, self._store_transform)
                order = np.concatenate([g[3] for g in groups])
                pos = np.empty_like(order)
                pos[order] = np.arange(len(order), dtype=order.dtype)
                handles = [
                    LazySlice(stored_stack, int(pos[j]))
                    for j in range(len(saves))
                ]
                self.device_dispatches += 1
                self._m_dispatches.inc()
            for (b, c, r), stored in zip(saves, handles):
                cs = (
                    pre_checksum[b] if c == 0
                    else batch.ref(b * bucket + (c - 1))
                )
                self.rings[b].push(r.frame, (stored, cs))
                # the ref itself is the provider (callable, with a
                # non-blocking peek() for the pipelined consume path)
                r.cell.save(r.frame, cs)

    # -- speculative draft waves --------------------------------------------

    def _speculate_idle_lanes(self) -> None:
        """One EXTRA packed wave that fills ONLY the lanes the tick's last
        run wave left idle (``ks[b] == 0``) with candidate-input draft
        branches, assigned by :class:`~.ops.batch.DraftWaveScheduler`.

        Each assigned lane loads its drafting lobby's pre-advance base state
        (a LazySlice gather into a functional COPY of the resident world —
        the live state is never touched), advances its candidate row
        ``depth`` frames, and the stacked outputs fill the lobby's branch
        cache for ``_do_loads``'s verified-hit servicing.  A tick with no
        idle lanes, or no predicted last advance, drafts nothing — drafts
        consume spare lanes, never widen the active bucket."""
        if self._last_wave is None:
            return
        prev_worlds, stacked, ks = self._last_wave
        adv = self._last_adv
        m = len(self.sessions)
        cfg = self.spec_config
        depth = max(cfg.depth, 1)
        idle = [b for b in range(m) if ks[b] == 0]
        if not idle:
            return
        wants = []
        cands_by_lobby: Dict[int, np.ndarray] = {}
        for b in range(m):
            a = adv[b]
            if not a or ks[b] == 0:
                continue
            last = a[-1]
            if not np.any(np.asarray(last.status) == InputStatus.PREDICTED):
                continue
            cands = np.asarray(
                cfg.candidates_fn(last.inputs), self.app.input_dtype
            )
            if cands.shape[0] == 0:
                continue
            cands_by_lobby[b] = cands
            wants.append((b, cands.shape[0]))
        if not wants:
            return
        plan = self._draft_sched.plan(idle, wants)
        if not plan:
            return
        rows = []
        for b, _ci, lane in plan:
            kb = ks[b]
            # the state feeding the lobby's LAST advance: the second-newest
            # stacked frame, or (single-advance waves) the pre-wave resident
            # row — same derivation as GgrsRunner's last_adv_src
            src = (
                LazySlice(stacked, (b, kb - 2)) if kb >= 2
                else LazySlice(prev_worlds, b)
            )
            rows.append((lane, src))
        with self._phases.phase("wave_dispatch"):
            groups, fallback = plan_row_gather(rows)
            draft_worlds = self.worlds
            if groups:
                draft_worlds = fused_load_rows(draft_worlds, groups, None)
                self.device_dispatches += 1
                self._m_dispatches.inc()
            for lane, stored in fallback:
                draft_worlds = _set_row(
                    draft_worlds, lane, materialize(stored)
                )
                self.device_dispatches += 1
                self._m_dispatches.inc()
            from .ops.packing import pack_prefix, pack_row, repeat_last_row

            pspec = self.app.packed_spec
            packed = self._stage_packed_draft
            bucket = self._draft_bucket
            draft_ks = [0] * self._m_pad
            zero_status = np.zeros((self._np,), np.int8)
            for b, ci, lane in plan:
                lane_buf = packed[lane]
                pack_prefix(lane_buf, frame_add(self.frames[b], -1), depth)
                pack_row(
                    pspec, lane_buf, 0, cands_by_lobby[b][ci], zero_status
                )
                repeat_last_row(lane_buf, 1, bucket)
                draft_ks[lane] = depth
            for lane in range(self._m_pad):
                if draft_ks[lane] == 0:
                    # unassigned lanes must read n_real=0 even if a past
                    # draft wave left payload bytes behind
                    pack_prefix(packed[lane], 0, 0)
            _b, _finals, d_stacked, d_checks = self.exec.run_wave_packed(
                draft_worlds, packed, draft_ks
            )
            # finals are DISCARDED: drafts never touch the resident world
            self.device_dispatches += 1
            self._m_dispatches.inc()
            self.draft_waves += 1
            self._m_drafts.inc()
        import jax as _jax

        by_lobby: Dict[int, list] = {}
        for b, ci, lane in plan:
            by_lobby.setdefault(b, []).append((ci, lane))
        checks_m = d_checks.reshape(self._m_pad, bucket, 2)
        for b, pairs in by_lobby.items():
            lanes = np.array([lane for _ci, lane in pairs], np.int32)
            cands_b = np.stack(
                [cands_by_lobby[b][ci] for ci, _lane in pairs]
            )
            stacked_l = _jax.tree.map(lambda a: a[lanes, :depth], d_stacked)
            self.spec_caches[b].fill_from_branched(
                frame_add(self.frames[b], -1), cands_b, stacked_l,
                checks_m[lanes, :depth], offset=0, depth_eff=depth,
            )

    # -- observability ------------------------------------------------------

    def _report_mismatch(self, b: int, e: MismatchedChecksumError) -> None:
        """Lobby SyncTest mismatch: timeline event + forensics report."""
        telemetry.record(
            "checksum_mismatch", source="synctest", lobby=b,
            frames=list(e.mismatched_frames), current_frame=e.current_frame,
        )
        if telemetry.forensics_dir() is not None:
            # lobby_world is a device gather — only pay it when a report
            # will actually be written
            telemetry.write_desync_report(
                "synctest_mismatch", reg=self.app.reg,
                world=self.lobby_world(b), frames=e.mismatched_frames, lobby=b,
            )

    def arm_compile_guard(self) -> bool:
        """Declare warmup over: with ``BGT_COMPILE_GUARD=1`` (or
        :func:`~bevy_ggrs_tpu.utils.compile_guard.set_compile_guard`) any
        later wave-program compile raises
        :class:`~bevy_ggrs_tpu.utils.compile_guard.RecompileError` naming
        the owner/kind and bumps ``recompiles_steady_total{owner}``.
        Returns True when armed; no-op (False) when the guard is off."""
        return compile_guard.guard().arm()

    def stats(self) -> dict:
        """Driver + executor counters: ticks, rollbacks, device dispatches,
        fused/fallback/in-wave load counts, per-lobby frame state, and the
        wave executor's compile/dispatch/bucket histogram stats."""
        out = {
            "lobbies": len(self.sessions),
            "packed": self.packed,
            "ticks": self.ticks,
            "rollbacks": self.rollbacks,
            "device_dispatches": self.device_dispatches,
            "fused_loads": self.fused_loads,
            "fallback_loads": self.fallback_loads,
            "in_wave_loads": self.in_wave_loads,
            "stalled_frames": list(self.stalled),
            "frames": list(self.frames),
            "confirmed": list(self.confirmed),
            "phases": self._phases.totals(),
        }
        if self.spec_caches is not None:
            out["speculation"] = {
                "hits": sum(c.hits for c in self.spec_caches),
                "misses": sum(c.misses for c in self.spec_caches),
                "draft_waves": self.draft_waves,
                "draft_lanes_filled": self._draft_sched.lanes_filled,
                "dropped_candidates": self._draft_sched.dropped_candidates,
                "cache_served_frames": self.cache_served_frames,
                "cached_bytes": sum(
                    c.cached_bytes for c in self.spec_caches
                ),
            }
        if self.planner is not None:
            out["sharded"] = {
                "devices": self.planner.n_devices,
                "lanes_per_shard": self.planner.lanes_per_shard,
                "pad_lanes": self._m_pad - len(self.sessions),
                "imbalance_last": round(self.planner.last_imbalance, 4),
                "imbalance_max": round(self.planner.max_imbalance, 4),
                "waves_planned": self.planner.waves_planned,
            }
        out.update(self.exec.stats())
        return out

    def lobby_world(self, b: int):
        """Materialize lobby ``b``'s live world (one gather dispatch)."""
        return _row(self.worlds, b)

    def lobby_checksum(self, b: int) -> int:
        """Lobby ``b``'s live 64-bit world checksum (an allowlisted flush
        point: forces the fused batched pull — see snapshot/lazy.py —
        though a landed async copy makes it free)."""
        from .snapshot.checksum import checksum_to_int

        self._rbq.harvest()
        return checksum_to_int(self._world_checksum[b])

    def finish(self) -> None:
        """Flush deferred checksum comparisons on every lobby session."""
        self._rbq.harvest()
        for b, s in enumerate(self.sessions):
            if hasattr(s, "check_now"):
                try:
                    s.check_now()
                except MismatchedChecksumError as e:
                    self._report_mismatch(b, e)
                    if self.on_mismatch is not None:
                        self.on_mismatch(b, e)
                    else:
                        raise


def _settle_heap() -> None:
    """After a tick that built programs: collect once, then freeze what
    survives (``gc.freeze``).  A full collection walks every tracked
    object, and JAX's traced programs and caches make that over 100k
    objects, tens of ms a collection; a many-lobby tick loop promotes
    enough objects to run one every few dozen ticks, so those
    collections set the tick's tail.  Frozen, set-up's objects drop out
    of later collections, which walk only what ticks allocated since.
    Frozen objects still die by reference count; a cycle among them is
    collected at the next tick that builds a program (unfreeze,
    collect, freeze).  A process that turned the collector off keeps
    it off."""
    if not gc.isenabled():
        return
    gc.unfreeze()
    gc.collect()
    gc.freeze()


def _lane_slot(stored: LazySlice):
    """``(lane, slot)`` a ring entry names: ``stacked[lane, slot]`` of a
    stacked wave output, or ``(lane, 0)`` for a row of a world-shaped
    buffer."""
    i = stored._i
    return i if isinstance(i, tuple) else (i, 0)


# -- jitted row helpers (one dispatch each; compiled once) -------------------

_row_jit = None
_set_row_jit = None


def _row(tree, b: int):
    global _row_jit
    import jax

    if _row_jit is None:
        _row_jit = jax.jit(lambda t, i: jax.tree.map(lambda a: a[i], t))
    return _row_jit(tree, np.int32(b))


def _set_row(tree, b: int, row):
    global _set_row_jit
    import jax

    if _set_row_jit is None:
        _set_row_jit = jax.jit(
            lambda t, i, r: jax.tree.map(lambda a, x: a.at[i].set(x), t, r)
        )
    return _set_row_jit(tree, np.int32(b), row)
