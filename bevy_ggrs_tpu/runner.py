"""GgrsRunner — the schedule driver (``run_ggrs_schedules`` analog,
/root/reference/src/schedule_systems.rs:19-289).

Owns the fixed-timestep accumulator (ns-precision period, run-slow x11/10 —
schedule_systems.rs:31-38), polls remote clients every host tick, steps the
session, and dispatches its request stream to the device.

The key TPU-first move is in :meth:`_handle_requests`: the reference executes
every request as a separate host-ECS schedule run (:189-270); here a maximal
``[Load?] (Advance|Save)*`` run is fused into ONE compiled ``lax.scan`` call
that returns all intermediate states and checksums — a rollback of depth N is
one device dispatch.  Checksums are handed to the session as lazy providers so
device->host syncs only happen when the protocol needs the value."""

from __future__ import annotations

import time
from typing import Callable, Dict, List, Optional

import numpy as np

from . import telemetry
from .app import App
from .session.events import (
    DesyncDetected,
    InputStatus,
    InvalidRequestError,
    MismatchedChecksumError,
    NotSynchronizedError,
    PredictionThresholdError,
    SessionState,
)
from .session.requests import AdvanceRequest, GgrsRequest, LoadRequest, SaveRequest
from .session.synctest import SyncTestSession
from .snapshot.checksum import checksum_to_int
from .snapshot.lazy import (
    BatchChecks,
    LazySlice,
    materialize,
    readback_queue,
    wrap_single_checksum,
)
from .snapshot.ring import SnapshotRing
from .ops.resim import slice_frame
from .ops.speculation import SpeculationCache, SpeculationConfig
from .utils import compile_guard
from .utils.frames import NULL_FRAME, frame_add
from .utils.tracing import trace_log


class GgrsRunner:
    """The schedule driver: fixed-timestep loop, session stepping, fused request dispatch (see module docstring)."""
    def __init__(
        self,
        app: App,
        session=None,
        read_inputs: Optional[Callable[[List[int]], Dict[int, np.ndarray]]] = None,
        on_event: Optional[Callable] = None,
        on_mismatch: Optional[Callable[[MismatchedChecksumError], None]] = None,
        initial_state=None,
        speculation: Optional[SpeculationConfig] = None,
        on_advance: Optional[Callable] = None,
        on_confirmed: Optional[Callable[[int], None]] = None,
        coalesce_frames: int = 1,
        pipeline: bool = True,
        packed: Optional[bool] = None,
        megastep: bool = False,
        input_queue: bool = False,
        measure_rollback_service: bool = False,
    ):
        self.app = app
        self.read_inputs = read_inputs or (lambda handles: {h: app.zero_inputs()[h] for h in handles})
        self.on_event = on_event
        self.on_mismatch = on_mismatch
        self.on_advance = on_advance  # (frame, inputs, status) per AdvanceFrame
        self.on_confirmed = on_confirmed  # (frame) when confirmed advances
        self.world = initial_state if initial_state is not None else app.init_state()
        if initial_state is not None and not app.reg.is_identity_strategy():
            # same canonicalization App.init_state applies: the frame-0
            # snapshot must restore exactly the live state (lossy strategies)
            self.world = app.reg.load_state(app.reg.store_state(self.world))
        self._world_checksum = wrap_single_checksum(app.checksum_fn(self.world))
        self.ring: SnapshotRing = SnapshotRing(depth=8)
        self.frame = 0  # RollbackFrameCount
        self.confirmed = NULL_FRAME  # ConfirmedFrameCount
        self.accumulator = 0.0
        self.run_slow = False
        self.local_players: List[int] = []
        self.events: List = []
        self.session = None
        self.stalled_frames = 0  # PredictionThreshold skips (observability)
        # Tick coalescing: when one host update owes N > 1 sim frames (the
        # run-behind / fast-forward / catch-up shapes), collect all N ticks'
        # session requests and flush them through ONE _handle_requests call
        # — consecutive advances fuse into a single k=N dispatch instead of
        # N submissions (each submission pays a fixed dispatch + upload
        # latency; the spectator's catchup path already emits
        # multi-advance lists and proves the fused shape).  1 = flush every
        # tick (the reference cadence).  Variant note: k already varies with
        # rollback depth, so coalescing adds no NEW program-variant risk
        # beyond what rollbacks pose (canonical mode pads either way), but
        # canonical apps must keep coalesce_frames + window <= depth.
        if coalesce_frames < 1:
            raise ValueError("coalesce_frames must be >= 1")
        self.coalesce_frames = coalesce_frames
        if (
            speculation is not None
            and app.canonical_depth is not None
            and app.canonical_branches is None
        ):
            raise ValueError(
                "speculation under bit-determinism requires the canonical-"
                "branched program: set App(canonical_branches=M+1) so hedges "
                "run inside the SAME fixed [branches, depth] dispatch every "
                "peer uses (docs/determinism.md)"
            )
        self.spec_cache = (
            SpeculationCache(app, speculation) if speculation is not None else None
        )
        # ordered cache-maintenance ops — ("inv", frame) invalidations and
        # ("spec", src_fn, ring_handle, start_frame, inputs) hedges —
        # recorded during request handling, applied in recorded order by
        # _flush_speculation at the next seam
        self._pending_speculate = []
        # observability counters (network_stats covers the wire; these cover
        # the sim driver — rollback frequency/depth is THE rollback-netcode
        # health metric)
        self.ticks = 0
        self.rollbacks = 0
        self.rollback_frames = 0  # total resimulated frames
        self.device_dispatches = 0
        self.donated_dispatches = 0  # dispatches that donated the input world
        # HBM guard for lazy ring saves: storing LazySlice handles keeps the
        # whole [k, ...] stacked resim buffer alive while ANY of its frames
        # is ringed — O(ring_depth x k) world copies worst case.  Above this
        # per-dispatch stacked-buffer size the driver materializes each save
        # (one extra device-side copy per frame, no host transfer), bounding
        # ring memory to O(ring_depth) worlds.  Small worlds keep the lazy
        # handles (the per-slice dispatch is the cost that matters there).
        self.ring_materialize_bytes = 64 * 2**20
        # Buffer donation: when provably safe, the dispatch donates the input
        # state's buffers so XLA reuses them in place instead of allocating a
        # fresh world per dispatch (round-1 NOTES gap #4).  Safe = the state
        # object is referenced ONLY by self.world: False at init (the caller
        # may hold the initial state) and after any event that aliases the
        # world with the ring; True after every dispatch/rollback that leaves
        # self.world holding a freshly materialized buffer.  Speculation
        # paths retain the pre-state after dispatch, so donation stays off
        # whenever a SpeculationCache is attached.
        self.enable_donation = True
        self._world_donatable = False
        self._last_stacked = None  # previous dispatch's stacked saves
        self._last_k = 0
        self._last_stacked_frame: Optional[int] = None
        # Tick pipelining (docs/architecture.md "Tick pipeline"): dispatch
        # frame N's fused program, start its checksum readback as a
        # NON-blocking async copy, and do tick N+1's host work (network
        # poll, input collection, ring bookkeeping on lazy refs) before
        # anything touches N's outputs.  Landed copies are harvested at the
        # top of each update(); the in-flight window is one dispatch deep
        # (the next dispatch's XLA data dependency on `final` serializes
        # naturally).  pipeline=False restores the pre-pipeline synchronous
        # shape (no async starts, no harvest) — the bench's sync baseline.
        self.pipeline = bool(pipeline)
        self._rbq = readback_queue()
        self.pipeline_degrades = 0  # loads that targeted in-flight output
        # Persistent solo-runner staging (the BatchedRunner's pinned-buffer
        # pattern): steady-state ticks fill these in place instead of
        # allocating a fresh np.stack per dispatch.  Sized lazily from the
        # first dispatch, grown geometrically when a deeper run appears;
        # jit sees the same [k, ...] shapes np.stack produced (views of the
        # capacity buffer), so no new trace variants.  Safe to reuse across
        # dispatches: jax copies numpy arguments to device buffers at call
        # time (the BatchedRunner has shipped this shape since PR 2).
        self._stage_inputs: Optional[np.ndarray] = None
        self._stage_status: Optional[np.ndarray] = None
        self._stage_cap = 0
        # Packed single-upload staging (ops/packing.py): the three per-
        # dispatch uploads (inputs, status, frame scalar) fuse into ONE
        # persistent int8 buffer split in-program by a pure bitcast —
        # killing 2/3 of the per-tick link-latency share the dispatch-floor
        # census attributed to uploads (docs/dispatch_floor.md).  Tri-state:
        # None (the default) auto-falls-back to the unpacked path when the
        # app has no packed program (canonical_branches mode); an EXPLICIT
        # packed=True raises instead of silently degrading — the mode
        # matrix in docs/architecture.md "Speculative rollback servicing".
        if packed is None:
            self.packed = app.packed_resim_fn is not None
        else:
            self.packed = bool(packed)
            if self.packed and app.packed_resim_fn is None:
                raise ValueError(
                    "packed=True but the app ships no packed program "
                    "(canonical_branches keeps its own [B, K] dispatch "
                    "shape); pass packed=None to allow the automatic "
                    "three-upload fallback — see the mode matrix in "
                    "docs/architecture.md"
                )
        self._stage_packed: Optional[np.ndarray] = None
        self._packed_cap = 0
        # Device-resident input queue (utils/staging.StagingQueue): rotate
        # the packed staging buffers so the per-upload transfer block
        # overlaps the NEXT tick's host work instead of stalling this one
        self.input_queue = bool(input_queue)
        if self.input_queue and not self.packed:
            raise ValueError(
                "input_queue rotates the packed staging buffer and so "
                "requires the packed upload path; enable packed (or drop "
                "input_queue) — see the mode matrix in docs/architecture.md"
            )
        self._packed_queue = None  # StagingQueue, sized lazily
        # Honest rollback-servicing latency (bench.py stage_speculation):
        # close the async-dispatch window inside the measured span so the
        # hit/miss rollback_service_ms histograms compare retired work
        self.measure_rollback_service = bool(measure_rollback_service)
        self.cache_served_frames = 0  # rollback frames served from cache
        # Upload census (always-on plain ints, like device_dispatches):
        # host->device array uploads issued by fused dispatches, and total
        # bytes staged through packed buffers — the numbers the bench.py
        # "uploads" stage gates on
        self.host_uploads = 0
        self.packed_upload_bytes = 0
        _treg = telemetry.registry()
        self._m_uploads = _treg.bind_histogram(
            "uploads_per_dispatch",
            "host->device uploads issued per fused dispatch (1 on the "
            "packed path)",
            buckets=(1, 2, 3, 4, 8),
        )
        self._m_packed_bytes = _treg.bind_counter(
            "packed_upload_bytes",
            "bytes staged through packed single-upload buffers",
        )
        # Device-resident megastep (ops/megastep.py): opt-in mode where a
        # whole coalesced flush — including the rollback load, when its
        # target is still resident in the on-device snapshot ring — runs as
        # ONE dispatch fed by ONE packed upload.  The host keeps a
        # slot->frame mirror of the device ring; misses fall back to the
        # host ring's materialize path (bit-identical by construction).
        self.megastep = bool(megastep)
        if self.megastep:
            if not app.reg.is_identity_strategy():
                raise ValueError(
                    "megastep requires an identity snapshot strategy: the "
                    "device ring stores live stacked states, and a lossy "
                    "strategy's store/load round-trip would need to run "
                    "inside the ring select"
                )
            if speculation is not None:
                raise ValueError(
                    "megastep and speculation are mutually exclusive (the "
                    "megastep flush has no per-frame lookup seam)"
                )
            if app.canonical_branches is not None:
                raise ValueError(
                    "megastep is incompatible with canonical_branches "
                    "(the branched program owns its own dispatch shape)"
                )
            # the ring aliases every recent state, so donation is never safe
            self.enable_donation = False
        self.megastep_dispatches = 0
        self.fused_ring_loads = 0  # rollbacks served from the device ring
        self._ms_fn = None
        self._ms_ring = None
        self._ms_ring_frames = None
        self._ms_k = 0  # megastep program depth (k_max)
        self._ms_slots = 0  # device ring depth R
        self._dev_frames: Dict[int, int] = {}  # slot -> resident frame
        # stacked-save device bytes depend only on the dispatch depth k
        # (shapes are static per app), so compute once per depth instead of
        # walking the pytree every tick
        self._stacked_bytes_by_k: dict = {}
        # Tick-phase attribution (telemetry/phases.py): guarded timers per
        # hot-loop phase feeding the always-on flight recorder and — while
        # telemetry is enabled — the tick_phase_ms histograms.  compile_ms
        # keeps first-dispatch wall time per program variant (the trace+
        # compile cost of the make_*_fn-built programs, paid at first call).
        self._phases = telemetry.PhaseSet(owner="solo")
        self.compile_ms: Dict[str, float] = {}
        self._seen_variants: set = set()
        # Periodic per-peer NetworkStats/TimeSync sampler (telemetry/
        # netstats.py); attached by set_session for sessions that expose
        # network_stats, polled inside the net_poll phase
        self._netstats = None
        # device-memory accounting namespace (telemetry/devmem.py): the
        # ring / megastep-ring / staging owners live under this tag and die
        # with the runner, so long processes never accumulate stale rows
        import weakref

        self._devmem_tag = telemetry.devmem.scope("solo")
        weakref.finalize(self, telemetry.devmem.forget_scope, self._devmem_tag)
        self._world_nbytes = 0  # one world's device footprint (set_session)
        if session is not None:
            self.set_session(session)

    # -- live world access ---------------------------------------------------

    @property
    def world(self):
        """The live WorldState.  Assigning to it (the supported
        external-write pattern, e.g. desync-injection tests) marks the
        state non-donatable: the caller may still hold references to the
        buffers, so the next dispatch must not hand them to XLA."""
        return self._world

    @world.setter
    def world(self, value) -> None:
        """Replace the live world; externally-set states are never donated
        (the caller may hold references to their buffers)."""
        self._world = value
        self._world_donatable = False

    # -- session lifecycle (restart semantics, schedule_systems.rs:70-79) ---

    def set_session(self, session) -> None:
        """Insert (or replace) the session; None resets driver state the way
        removing the ``Session`` resource does in the reference.

        An outgoing session with deferred checksum comparison is flushed
        first so no frame leaves the driver uncompared."""
        if self.session is not None and self.session is not session:
            self._flush_session_checks()
        self.session = session
        self.accumulator = 0.0
        self.run_slow = False
        self.local_players = []
        self.frame = 0
        self.confirmed = NULL_FRAME
        self.ring.clear()
        self._last_stacked = None
        self._last_stacked_frame = None
        # megastep device-ring state is sized from the session's windows;
        # a new session rebuilds it lazily at the first flush
        self._ms_fn = None
        self._ms_ring = None
        self._ms_ring_frames = None
        self._dev_frames = {}
        if session is not None:
            # despawn-retirement safety invariant (ops/resim.py docstring):
            # slots hard-freed at frame-retention must never sit inside the
            # rollback window, or a rollback could restore a snapshot whose
            # despawn the corrected inputs would have cancelled
            mp = session.max_prediction()
            window = (
                session.rollback_window()
                if hasattr(session, "rollback_window")
                else mp
            )
            if self.app.retention < window:
                raise ValueError(
                    f"App(retention={self.app.retention}) < session rollback "
                    f"window ({window}): raise retention to at least the "
                    "deepest rollback the session can request (see "
                    "ops/resim.py despawn-retirement invariant)"
                )
            if (
                self.app.canonical_depth is not None
                and self.coalesce_frames + window > self.app.canonical_depth
            ):
                # a rollback landing in the same coalesced flush as catch-up
                # ticks fuses a (window + coalesce)-long run; the canonical
                # program cannot pad past its fixed depth, so failing here
                # beats a timing-dependent crash minutes into a session
                raise ValueError(
                    f"coalesce_frames ({self.coalesce_frames}) + rollback "
                    f"window ({window}) exceeds canonical_depth "
                    f"({self.app.canonical_depth}); lower coalesce_frames or "
                    "raise App(canonical_depth=...)"
                )
            if (
                isinstance(session, SyncTestSession)
                and self.coalesce_frames
                > session.check_distance + session.compare_interval() + 2
            ):
                # the session GCs comparison cells check_distance +
                # compare_interval + 2 frames back every advance; a deeper
                # flush cadence would land resim checksums AFTER the cell
                # was collected, silently skipping those comparisons — the
                # determinism oracle must fail loudly instead of thinning
                raise ValueError(
                    f"coalesce_frames ({self.coalesce_frames}) exceeds the "
                    "SyncTest comparison-cell horizon (check_distance + "
                    "compare_interval + 2 = "
                    f"{session.check_distance + session.compare_interval() + 2}"
                    "); lower coalesce_frames or raise check_distance/"
                    "compare_interval"
                )
            # ring must hold a snapshot window frames back even if a session
            # reports rollback_window > max_prediction
            self.ring.set_depth(self._ring_depth(session))
            # device-memory accounting: ring residency = stored snapshots x
            # one world's footprint (docs/observability.md "Tracing &
            # device memory"); shapes are static so compute the unit once
            from .utils.mem import tree_device_bytes

            self._world_nbytes = tree_device_bytes(self._world)
            self.ring.set_accounting(
                self._devmem_tag + "/snapshot_ring", self._world_nbytes
            )
            # sessions may start at a nonzero frame (wraparound tests, resumed
            # sessions); mirror it so ctx.frame/time agree from tick one
            cur = getattr(session, "current_frame", 0)
            self.frame = cur() if callable(cur) else cur
        if session is not None and hasattr(session, "network_stats"):
            from .telemetry.netstats import NetStatsSampler

            self._netstats = NetStatsSampler(session)
        else:
            self._netstats = None

    def _ring_depth(self, session) -> int:
        """Snapshot-ring capacity: the deepest rollback window the session
        can request, plus every save a maximally coalesced flush can push
        before the end-of-flush confirm prunes (one formula — a second copy
        drifting from this one is how rings get undersized)."""
        mp = session.max_prediction()
        window = (
            session.rollback_window()
            if hasattr(session, "rollback_window")
            else mp
        )
        return max(mp, window) + 1 + self.coalesce_frames

    def _flush_session_checks(self) -> None:
        """Force any deferred checksum comparisons on the current session,
        routing a mismatch to ``on_mismatch`` like a ticking one would."""
        s = self.session
        if s is None or not hasattr(s, "check_now"):
            return
        # free harvest first: copies that already landed won't count as
        # forced readbacks in the flush below
        self._rbq.harvest()
        try:
            s.check_now()
        except MismatchedChecksumError as e:
            trace_log("SyncTest mismatch (flush): %s", e)
            self._report_mismatch(e)
            if self.on_mismatch is not None:
                self.on_mismatch(e)
            else:
                raise

    def finish(self) -> None:
        """End-of-run hook: flush deferred checksum comparisons (SyncTest
        with ``compare_interval`` > 1 would otherwise leave the final window
        of frames uncompared — see docs/debugging-desyncs.md §1)."""
        self._flush_session_checks()

    # -- fixed-timestep driver (schedule_systems.rs:19-83) ------------------

    def update(self, delta_seconds: float) -> None:
        """One host tick: accumulate time, poll the network, run 0+ GGRS frames."""
        fps_delta = (1.0 / self.app.fps) * (1.1 if self.run_slow else 1.0)
        self.accumulator += delta_seconds
        if self.session is None:
            self.accumulator = 0.0
            return
        ph = self._phases
        ph.begin_tick()
        if self.pipeline:
            # collect last tick's landed checksum copies BEFORE the network
            # poll, so the session's desync driver publishes them this tick
            # without ever blocking on the device
            with ph.phase("readback_harvest"):
                self._rbq.harvest()
        if hasattr(self.session, "poll_remote_clients"):
            with ph.phase("net_poll"):
                self.session.poll_remote_clients()
                self._drain_events()
                if self._netstats is not None:
                    self._netstats.poll()
                if telemetry.enabled():
                    self._record_network_stats()
        pending: List[GgrsRequest] = []
        pending_ticks = 0
        ran_requests = False
        stepped = 0
        while self.accumulator >= fps_delta:
            self.accumulator -= fps_delta
            stepped += 1
            if hasattr(self.session, "frames_ahead"):
                self.run_slow = self.session.frames_ahead() > 0
            with ph.phase("session_step"):
                reqs = self._step_session()
            if reqs:
                pending.extend(reqs)
                pending_ticks += 1
                if pending_ticks >= self.coalesce_frames:
                    self._handle_requests(pending)
                    pending = []
                    pending_ticks = 0
                    ran_requests = True
            fps_delta = (1.0 / self.app.fps) * (1.1 if self.run_slow else 1.0)
        if pending:
            self._handle_requests(pending)
            ran_requests = True
        if ran_requests and not self.pipeline:
            # synchronous mode: zero-deep in-flight window — retire this
            # tick's device work (world + checksum readback) before the
            # driver returns, exactly the behavior pipelining replaces
            with ph.phase("readback_harvest"):
                self._drain_inflight()
        if stepped:
            # idle accumulator polls (sub-frame deltas, handshake spins)
            # don't flood the flight ring with empty entries.  The counter
            # stamps (device residency, in-flight readbacks) feed the
            # Chrome-trace counter tracks (telemetry/trace.py); guarded on
            # the recording gate so the fully-disabled path computes nothing
            if ph.on:
                ph.end_tick(
                    frame=self.frame,
                    device_bytes=telemetry.devmem.total(),
                    pipeline_depth=self._rbq.depth() if self.pipeline else 0,
                )
            else:
                ph.end_tick(frame=self.frame)

    @property
    def checksum(self) -> int:
        """Current world checksum as the 64-bit cross-peer value (the
        user-readable ``Checksum`` resource analog, checksum.rs:48-56).
        Forces a device sync (an allowlisted flush point — free when the
        async copy already landed)."""
        if self.pipeline:
            self._rbq.harvest()
        return checksum_to_int(self._world_checksum)

    def _drain_inflight(self) -> None:
        """Flush the in-flight window: collect landed async readbacks and
        block until the live world's dispatch completes, so external reads
        observe the post-dispatch state (allowlisted in the hot-loop purity
        lint — this IS the blocking point)."""
        import jax

        if self.pipeline:
            self._rbq.harvest()
        else:
            # synchronous mode: retire checksum readbacks with the tick —
            # these count as forced (blocking) pulls in the readback stats
            BatchChecks.pull_pending()
        with self._phases.phase("readback_block"):
            jax.block_until_ready(self._world.comps)

    def read_components(self, names=None) -> dict:
        """Fetch component columns (and the active mask) to host numpy in one
        transfer — the render-readback path.  ``names=None`` fetches all.
        Drains the in-flight dispatch window first so a mid-pipeline read
        can't observe a stale world."""
        import jax

        from .snapshot.world import active_mask

        self._drain_inflight()
        names = list(names) if names is not None else list(self.app.reg.components)
        arrays = {n: self.world.comps[n] for n in names}
        for n in names:
            arrays[f"__has_{n}__"] = self.world.has[n]
        arrays["__active__"] = active_mask(self.world)
        out = jax.device_get(arrays)
        return {k: np.asarray(v) for k, v in out.items()}

    def profile(self, logdir: str):
        """Context manager: capture a jax profiler trace of driver activity
        (device side of the span log — view with TensorBoard/XProf)."""
        import contextlib

        import jax

        @contextlib.contextmanager
        def cm():
            with jax.profiler.trace(logdir):
                yield self

        return cm()

    def stats(self) -> dict:
        """Driver health counters (rollback frequency/depth, dispatches,
        stalls, speculation hit rate)."""
        return {
            "overflow": bool(np.asarray(self.world.overflow)),
            "ticks": self.ticks,
            "rollbacks": self.rollbacks,
            "resimulated_frames": self.rollback_frames,
            "device_dispatches": self.device_dispatches,
            "donated_dispatches": self.donated_dispatches,
            "host_uploads": self.host_uploads,
            "packed": self.packed,
            "packed_upload_bytes": self.packed_upload_bytes,
            "megastep": self.megastep,
            "megastep_dispatches": self.megastep_dispatches,
            "fused_ring_loads": self.fused_ring_loads,
            "stalled_frames": self.stalled_frames,
            "speculation_hits": getattr(self.spec_cache, "hits", 0),
            "speculation_misses": getattr(self.spec_cache, "misses", 0),
            "speculation_cached_bytes": getattr(self.spec_cache, "cached_bytes", 0),
            "speculation_draft_dispatches": getattr(
                self.spec_cache, "draft_dispatches", 0
            ),
            "cache_served_frames": self.cache_served_frames,
            "input_queue": self.input_queue,
            "staging_deferred_blocks": getattr(
                self._packed_queue, "deferred_blocks", 0
            ),
            "staging_landed_free": getattr(
                self._packed_queue, "landed_free", 0
            ),
            "frame": self.frame,
            "confirmed": self.confirmed,
            "pipeline": self.pipeline,
            "pipeline_degrades": self.pipeline_degrades,
            "phases": self._phases.totals(),
            "compile_ms": dict(self.compile_ms),
        }

    def tick(self) -> None:
        """Run exactly one GGRS frame (manual-clock test pattern — the
        TimeUpdateStrategy::ManualDuration analog, tests/common/mod.rs:45-55)."""
        self.update(1.0 / self.app.fps)

    # -- per-session-type steps ---------------------------------------------

    def _step_session(self) -> Optional[List[GgrsRequest]]:
        """One session tick: returns its request list (to be flushed by the
        caller — possibly coalesced with other ticks'), or None if the tick
        produced nothing (stall, handshake, mismatch)."""
        self.ticks += 1
        telemetry.count("ticks_total", help="session ticks stepped")
        s = self.session
        if isinstance(s, SyncTestSession):
            return self._step_synctest()
        if getattr(s, "is_spectator", False):
            return self._step_spectator()
        return self._step_p2p()

    def _step_synctest(self) -> Optional[List[GgrsRequest]]:
        s = self.session
        self.local_players = list(range(s.num_players()))
        for handle, value in self.read_inputs(self.local_players).items():
            s.add_local_input(handle, value)
        try:
            return s.advance_frame()
        except MismatchedChecksumError as e:
            trace_log("SyncTest mismatch: %s", e)
            self._report_mismatch(e)
            if self.on_mismatch is not None:
                self.on_mismatch(e)
            return None

    def _step_p2p(self) -> Optional[List[GgrsRequest]]:
        s = self.session
        self.local_players = list(s.local_player_handles())
        if s.current_state() == SessionState.RUNNING:
            for handle, value in self.read_inputs(self.local_players).items():
                s.add_local_input(handle, value)
        try:
            requests = s.advance_frame()
        except PredictionThresholdError:
            trace_log("frame %d skipped: prediction threshold", self.frame)
            self.stalled_frames += 1
            telemetry.count("stalled_frames_total", help="ticks skipped on stall", kind="p2p")
            telemetry.record("stall", frame=self.frame, reason="prediction_threshold")
            return None
        except NotSynchronizedError:
            return None  # still in the sync handshake; sim time does not advance
        self._drain_events()
        return requests

    def _step_spectator(self) -> Optional[List[GgrsRequest]]:
        s = self.session
        self.local_players = []
        if s.current_state() != SessionState.RUNNING:
            return None
        try:
            return s.advance_frame()
        except PredictionThresholdError:
            trace_log("spectator frame skipped: waiting for host input")
            self.stalled_frames += 1
            telemetry.count(
                "stalled_frames_total", help="ticks skipped on stall", kind="spectator"
            )
            telemetry.record("stall", frame=self.frame, reason="waiting_for_host")
            return None
        except NotSynchronizedError:
            return None

    def _drain_events(self) -> None:
        s = self.session
        if hasattr(s, "events"):
            for ev in s.events():
                self.events.append(ev)
                if isinstance(ev, DesyncDetected):
                    self._report_desync(ev)
                if self.on_event is not None:
                    self.on_event(ev)

    def _record_network_stats(self) -> None:
        """Mirror per-peer NetworkStats into telemetry gauges plus one
        timeline event per peer (called once per host tick while enabled)."""
        s = self.session
        handles = getattr(s, "remote_handle_addr", None)
        if handles is None:
            if getattr(s, "is_spectator", False):
                behind = s.frames_behind_host()
                telemetry.gauge_set(
                    "spectator_frames_behind", behind, "spectator catchup lag"
                )
                telemetry.record("network_stats", peer="host", frames_behind=behind)
            return
        for h in sorted(handles):
            try:
                st = s.network_stats(h)
            except InvalidRequestError:
                continue  # endpoint gone (legacy raising sessions)
            if not st.is_live:
                continue  # local / spectator / disconnected handle
            telemetry.gauge_set("ping_ms", st.ping_ms, "round-trip ping", peer=h)
            telemetry.gauge_set(
                "send_queue_len", st.send_queue_len, "pending outbound inputs",
                peer=h,
            )
            telemetry.gauge_set("kbps_sent", st.kbps_sent, "outbound bandwidth", peer=h)
            telemetry.gauge_set(
                "local_frames_behind", st.local_frames_behind,
                "our frame lag vs this peer", peer=h,
            )
            telemetry.gauge_set(
                "remote_frames_behind", st.remote_frames_behind,
                "peer's frame lag vs us", peer=h,
            )
            telemetry.record(
                "network_stats", peer=h, ping_ms=st.ping_ms,
                send_queue_len=st.send_queue_len, kbps_sent=st.kbps_sent,
                local_frames_behind=st.local_frames_behind,
                remote_frames_behind=st.remote_frames_behind,
            )
        if hasattr(s, "frames_ahead"):
            telemetry.observe(
                "input_latency_frames", max(s.frames_ahead(), 0),
                "frames the session runs ahead of confirmed remote input",
            )

    def _report_mismatch(self, e: MismatchedChecksumError) -> None:
        """SyncTest mismatch: timeline event + forensics report (the report
        is written only when a forensics directory is configured)."""
        telemetry.record(
            "checksum_mismatch", source="synctest",
            frames=list(e.mismatched_frames), current_frame=e.current_frame,
        )
        telemetry.write_desync_report(
            "synctest_mismatch", reg=self.app.reg, world=self.world,
            frames=e.mismatched_frames,
        )

    def _report_desync(self, ev: DesyncDetected) -> None:
        """P2P DesyncDetected: timeline event + forensics report.

        The report carries every resolved local per-frame checksum the
        session still holds, so two peers' reports can be frame-aligned
        offline (``replay_tool.py merge-reports``)."""
        telemetry.record(
            "checksum_mismatch", source="p2p", frames=[ev.frame],
            local_checksum=ev.local_checksum,
            remote_checksum=ev.remote_checksum, addr=repr(ev.addr),
        )
        local = getattr(self.session, "_local_checksums", None) or {}
        telemetry.write_desync_report(
            "p2p_desync", reg=self.app.reg, world=self.world,
            frames=[ev.frame], local_checksum=ev.local_checksum,
            remote_checksum=ev.remote_checksum, addr=ev.addr,
            checksums={f: v for f, v in local.items() if isinstance(v, int)},
        )

    # -- request dispatch (the TPU-offload seam, SURVEY §3.6) ---------------

    def _handle_requests(self, requests: List[GgrsRequest]) -> None:
        s = self.session
        # mirror session -> driver counters (schedule_systems.rs:195-220)
        self.ring.set_depth(self._ring_depth(s))
        self.confirmed = s.confirmed_frame()
        i = 0
        n = len(requests)
        while i < n:
            r = requests[i]
            if isinstance(r, LoadRequest):
                if self.megastep:
                    # fuse the load into the following run's megastep
                    # dispatch when its target is device-ring resident
                    j = i + 1
                    while j < n and isinstance(
                        requests[j], (AdvanceRequest, SaveRequest)
                    ):
                        j += 1
                    self._run_megastep(r, requests[i + 1:j])
                    i = j
                else:
                    # rollback servicing seam: the Load plus its
                    # following Advance/Save run are one unit — a
                    # verified speculation hit replaces BOTH the ring
                    # materialize and the resim with cache selects
                    j = i + 1
                    while j < n and isinstance(
                        requests[j], (AdvanceRequest, SaveRequest)
                    ):
                        j += 1
                    self._service_rollback(r, requests[i + 1:j])
                    i = j
            else:
                j = i
                while j < n and isinstance(
                    requests[j], (AdvanceRequest, SaveRequest)
                ):
                    j += 1
                if self.megastep:
                    self._run_megastep(None, requests[i:j])
                else:
                    self._run_batch(requests[i:j])
                i = j
        # prune AFTER processing (discard_old_snapshots): with coalesced
        # ticks, an early tick's Load can target a frame below a LATER
        # tick's confirmed frame (the session takes first_incorrect per
        # tick, then lets confirmed rise) — pruning up front would evict
        # the rollback target (a MissingSnapshotError once a donated
        # world is gone)
        self.ring.confirm(self.confirmed)
        # fire AFTER the batch: a corrective Load/Advance in the same
        # request list must land before observers treat the frame as
        # final (a replay watermark reading final_frames() from this
        # hook would otherwise persist the mispredicted inputs)
        if self.on_confirmed is not None and self.confirmed != NULL_FRAME:
            self.on_confirmed(self.confirmed)
        # drafts for the live frame ride the idle post-tick slot: the
        # fan-out dispatch + cache bookkeeping happen after every
        # rollback in this list has been serviced (and timed)
        self._flush_speculation()

    def _flush_speculation(self) -> None:
        """Apply the cache-maintenance ops recorded during request handling.

        Deferral keeps the hedge fan-out (an M-branch, depth-deep dispatch
        plus cache bookkeeping) AND the invalidation drops (synchronous
        buffer deallocation) OFF the rollback-servicing critical path:
        ``rollback_service_ms{path=hit}`` times the rollback itself, not
        next tick's drafts or last tick's frees.  Ops replay in recorded
        order, so a mid-list correction still drops the branches an earlier
        run hedged from a superseded state.  Called before a Load's
        servicing timer starts (same-list ordering as the old inline calls)
        and at the end of ``_handle_requests``."""
        pending, self._pending_speculate = self._pending_speculate, []
        for op in pending:
            if op[0] == "inv":
                self.spec_cache.invalidate_after(op[1])
                continue
            _, src_fn, hit_handle, start, inputs = op
            if src_fn is None:
                # depth-1 full hit: the pre-advance source is the rollback
                # target itself — materialize the ring handle (one slice
                # dispatch at most; still zero resim frames)
                src = self.app.reg.load_state(materialize(hit_handle))
            else:
                src = src_fn()
            self.spec_cache.speculate(src, start, inputs)
        if pending and self.measure_rollback_service:
            # measurement mode only: retire drafts in the slot that issued
            # them so no later servicing span waits on them through device
            # serialization
            self.spec_cache.drain_drafts()

    def _note_rollback(self, frame: int, cause=None) -> None:
        """Rollback attribution shared by the host-materialize load path and
        the megastep's fused device-ring load: counters, cause blame, and
        the always-on flight-recorder entry.

        ``cause`` is the session's :class:`RollbackCause`; a cause-less
        legacy/replay load blames handle ``"unknown"`` so
        ``rollback_cause_total`` summed over handles always equals
        ``rollbacks_total``."""
        depth = self.frame - frame
        self.rollbacks += 1
        self._phases.note_rollback(depth)
        blamed = cause.handle if cause is not None else "unknown"
        if blamed is None:
            blamed = "unknown"
        lateness = cause.lateness if cause is not None else depth
        kind = cause.kind if cause is not None else "unknown"
        mismatch = bool(cause.mismatch) if cause is not None else False
        telemetry.count("rollbacks_total", help="LoadRequests executed")
        telemetry.observe(
            "rollback_depth", depth,
            "frames rolled back per LoadRequest",
        )
        telemetry.count(
            "rollback_cause_total",
            help="rollbacks attributed to the peer whose input caused them",
            handle=blamed,
        )
        telemetry.observe(
            "input_lateness_frames", lateness,
            "frames late the blamed input arrived (rollback depth it forced)",
            handle=blamed,
        )
        telemetry.record("rollback", to_frame=frame, from_frame=self.frame,
                         depth=depth, handle=blamed, lateness=lateness,
                         mismatch=mismatch, cause_kind=kind)
        fr = telemetry.flight_recorder()
        if fr.enabled:
            # the always-on ring gets the attributed entry too, so a desync
            # report's flight_record section names the blamed peer even when
            # the metrics registry was off
            fr.record("rollback", to_frame=frame, from_frame=self.frame,
                      depth=depth, handle=blamed, lateness=lateness,
                      mismatch=mismatch, cause_kind=kind)

    def _load(self, frame: int, cause=None) -> None:
        """LoadGameState: restore the ring snapshot for ``frame``
        (schedule_systems.rs:238-249).

        ``cause`` is the session's :class:`RollbackCause` attribution; when
        a legacy/replay path supplies none the rollback is attributed to
        handle ``"unknown"`` so ``rollback_cause_total`` summed over handles
        always equals ``rollbacks_total``."""
        self._note_rollback(frame, cause)
        with self._phases.phase("rollback_load"):
            stored, checksum = self.ring.rollback(frame)
            was_lazy = isinstance(stored, LazySlice)
            if (
                self.pipeline
                and was_lazy
                and self._last_stacked is not None
                and stored._stacked is self._last_stacked
            ):
                # the Load targets the most recent dispatch's stacked output:
                # the materialize below carries an XLA data dependency on that
                # dispatch, so the one-deep window degrades to the synchronous
                # shape for this tick (correct by construction; counted so the
                # degradation rate is observable)
                self.pipeline_degrades += 1
                telemetry.count(
                    "pipeline_degrade_total",
                    help="loads targeting the in-flight dispatch's output "
                         "(pipeline degraded to synchronous for that tick)",
                )
            self.world = self.app.reg.load_state(materialize(stored))
            self._world_checksum = checksum
            self.frame = frame
        # LazySlice materialization / non-identity decode produce fresh
        # buffers; a materialized identity snapshot IS the ring's object
        self._world_donatable = (
            was_lazy or not self.app.reg.is_identity_strategy()
        )
        self._last_stacked = None
        self._last_stacked_frame = None
        if self.spec_cache is not None:
            # branches hedged from now-superseded predicted states must not
            # serve future lookups (see SpeculationCache.invalidate_after);
            # the drop (buffer deallocation) is deferred to the flush seam so
            # it stays off the timed servicing path — _flush_speculation runs
            # before any later lookup can observe the stale entries
            self._pending_speculate.append(("inv", frame))

    def _service_rollback(self, load: LoadRequest, run: List[GgrsRequest]) -> None:
        """Service one LoadRequest plus its following Advance/Save run.

        The speculation cache is consulted FIRST: a verified hit (the
        corrected input sequence was hedged last tick) services the rollback
        entirely from cached branch states — the ring pop is bookkeeping
        only (the megastep fused-load pattern), the restored state and every
        resaved frame are device-side selects, and zero frames resimulate.
        A miss falls back to the existing materialize + resim path.  Both
        paths feed the ``rollback_service_ms{path=hit|miss}`` histogram —
        the number the bench's >=5x hit-path gate reads."""
        # issue any drafts recorded by an earlier run in this coalesced
        # request list BEFORE the load (and before the timer): the hedge
        # must precede the correction exactly as it did when speculate()
        # fired inline, so invalidate_after can drop superseded branches
        self._flush_speculation()
        if self.measure_rollback_service:
            import jax

            # bgt: ignore[BGT010, BGT011]: deliberate — measurement mode
            # only (bench.py _speculation_service_arm): retire the PIPELINED
            # BACKLOG (previous ticks' advance + draft dispatches) before
            # the timer starts, so the span times this rollback's servicing
            # and not whatever was already in flight
            jax.block_until_ready(self.world.comps)
        t0 = time.perf_counter()
        adv = [r for r in run if isinstance(r, AdvanceRequest)]
        got = None
        if self.spec_cache is not None and adv:
            got = self.spec_cache.lookup_seq(
                load.frame, np.stack([a.inputs for a in adv])
            )
            telemetry.count(
                "speculation_hits_total" if got is not None
                else "speculation_misses_total",
                help="speculative branch-cache lookups",
            )
        if got is not None:
            self._note_rollback(load.frame, load.cause)
            with self._phases.phase("rollback_load"):
                # bookkeeping-only rollback: pop the ring entries above the
                # target and keep the stored handle — no materialize, no
                # load_state; the world restore is the cache select inside
                # _run_batch (O(1) in rollback depth)
                stored, checksum = self.ring.rollback(load.frame)
                self.frame = load.frame
            self._pending_speculate.append(("inv", load.frame))
            self._last_stacked = None
            self._last_stacked_frame = None
            self._world_donatable = False
            telemetry.record(
                "speculation_hit", frame=load.frame, depth=got[0],
                advances=len(adv),
            )
            self._run_batch(run, hit=got, hit_pre=(stored, checksum))
        else:
            self._load(load.frame, load.cause)
            self._run_batch(run)
        if self.measure_rollback_service:
            import jax

            # bgt: ignore[BGT010, BGT011]: deliberate — measurement mode
            # only (bench.py stage_speculation): retire the servicing work
            # inside the timed span so hit and miss p99 compare the same
            # thing
            jax.block_until_ready(self.world.comps)
        telemetry.observe(
            "rollback_service_ms", (time.perf_counter() - t0) * 1e3,
            "wall ms to service one rollback (LoadRequest + its following "
            "Advance/Save run)",
            buckets=telemetry.LATENCY_MS_BUCKETS,
            path="hit" if got is not None else "miss",
        )

    def _stage_rows(self, adv: List[AdvanceRequest]):
        """Fill the persistent pinned input/status buffers in place and
        return ``[k, ...]`` views — the BatchedRunner staging pattern ported
        to the solo runner, so steady-state ticks allocate nothing on host.
        Views of the capacity buffer have exactly the shapes ``np.stack``
        produced, so the jit cache sees no new variants."""
        k = len(adv)
        row_in = np.asarray(adv[0].inputs)
        row_st = np.asarray(adv[0].status)
        if (
            self._stage_inputs is None
            or self._stage_cap < k
            or self._stage_inputs.shape[1:] != row_in.shape
            or self._stage_inputs.dtype != row_in.dtype
            or self._stage_status.shape[1:] != row_st.shape
            or self._stage_status.dtype != row_st.dtype
        ):
            self._stage_cap = max(k, self._stage_cap * 2)
            self._stage_inputs = np.zeros(
                (self._stage_cap, *row_in.shape), row_in.dtype
            )
            self._stage_status = np.zeros(
                (self._stage_cap, *row_st.shape), row_st.dtype
            )
            telemetry.devmem.note(
                self._devmem_tag + "/staging",
                self._stage_inputs.nbytes + self._stage_status.nbytes,
            )
        from .utils import staging

        san = staging.sanitizer()
        san.guard_write(self._stage_inputs, "runner._stage_rows/inputs")
        san.guard_write(self._stage_status, "runner._stage_rows/status")
        for i, a in enumerate(adv):
            self._stage_inputs[i] = a.inputs
            self._stage_status[i] = a.status
        # the buffers are rewritten next tick: commit synchronously so the
        # in-flight upload can never read the next tick's bytes
        commit = staging.commit
        return commit(self._stage_inputs[:k]), commit(self._stage_status[:k])

    def _stage_packed_rows(self, adv: List[AdvanceRequest], start_frame: int,
                           k_pad: Optional[int] = None,
                           has_load: int = 0, load_slot: int = 0):
        """Pack a run's advances into the persistent single-upload buffer
        (ops/packing.py) and return the ``[k_pad + 1, W]`` view: prefix row
        (frame / n_real / load words) + one payload row per frame.  The
        fixed-shape programs (canonical, megastep) pass ``k_pad > k``;
        padded rows repeat the last real row and are masked by ``n_real``."""
        from .ops.packing import pack_prefix, pack_row, repeat_last_row

        spec = self.app.packed_spec
        k = len(adv)
        kp = k_pad if k_pad is not None else k
        if self.input_queue:
            # device-resident input queue: rotate depth-2 staging buffers so
            # the upload overlaps the next tick's host work (StagingQueue)
            from .utils.staging import StagingQueue

            if self._packed_queue is None or self._packed_cap < kp:
                self._packed_cap = max(kp, self._packed_cap * 2)
                cap = self._packed_cap
                self._packed_queue = StagingQueue(lambda: spec.new_buffer(cap))
                telemetry.devmem.note(
                    self._devmem_tag + "/packed_staging",
                    self._packed_queue.nbytes,
                )
            buf = self._packed_queue.acquire()
        else:
            if self._stage_packed is None or self._packed_cap < kp:
                self._packed_cap = max(kp, self._packed_cap * 2)
                self._stage_packed = spec.new_buffer(self._packed_cap)
                telemetry.devmem.note(
                    self._devmem_tag + "/packed_staging",
                    self._stage_packed.nbytes,
                )
            buf = self._stage_packed
        pack_prefix(buf, start_frame, k, has_load, load_slot)
        for i, a in enumerate(adv):
            pack_row(spec, buf, i, a.inputs, a.status)
        repeat_last_row(buf, k, kp)
        if self.input_queue:
            # non-blocking start: the queue blocks (if ever) at the matching
            # acquire(), two ticks from now
            return self._packed_queue.commit(buf[:kp + 1])
        # commit synchronously: the buffer is rewritten next dispatch and
        # the upload itself is asynchronous (see utils/staging.py)
        from .utils.staging import commit

        return commit(buf[:kp + 1])

    def _note_dispatch_uploads(self, n: int, packed_buf=None) -> None:
        """Upload census: ``n`` host->device uploads rode this dispatch
        (always-on ints + the pre-bound telemetry family)."""
        self.host_uploads += n
        self._m_uploads.observe(n)
        if packed_buf is not None:
            self.packed_upload_bytes += packed_buf.nbytes
            self._m_packed_bytes.inc(packed_buf.nbytes)

    def _run_batch(self, run: List[GgrsRequest], hit=None, hit_pre=None) -> None:
        """Execute a maximal Advance/Save run as one fused device call.

        ``hit``/``hit_pre`` come from :meth:`_service_rollback` when the
        rollback's corrected input sequence was hedged: ``hit`` is the
        ``lookup_seq`` result serving the first ``skip`` advances as cache
        selects (a fully-hedged rollback dispatches NO resim at all) and
        ``hit_pre`` is the ``(stored_handle, checksum)`` the ring pop
        returned for the rollback target — the pre-run state, needed for
        defensive leading saves and depth-1 re-speculation.  With
        speculation enabled the live frame's predicted transition fans out
        candidate branches for the next tick either way."""
        adv = [r for r in run if isinstance(r, AdvanceRequest)]
        k = len(adv)
        ph = self._phases
        ph.note_advances(k)
        identity = self.app.reg.is_identity_strategy()
        if not hasattr(self._world_checksum, "to_int"):
            # tolerate external writes of a bare uint32[2] device checksum
            self._world_checksum = wrap_single_checksum(self._world_checksum)
        pre_world, pre_checksum = self.world, self._world_checksum
        pre_frame = self.frame
        if self.on_advance is not None:
            for i, a in enumerate(adv):
                self.on_advance(frame_add(pre_frame, i + 1), a.inputs, a.status)
        stacked = None
        batch_checks = None  # BatchChecks over this dispatch's stacked checksums
        skip = 0
        cache_states = cache_bc = None
        hit_handle = hit_checksum = None
        if hit is not None:
            # rollback served from the speculation cache (_service_rollback
            # already popped the ring and set self.frame to the target):
            # state, checksum and frame advance are device-side selects of
            # the verified branch — zero resim frames for the served prefix
            skip, cache_states, cache_checks = hit
            cache_bc = BatchChecks(cache_checks)
            self.world = cache_states(skip - 1)
            self._world_checksum = cache_bc.ref(skip - 1)
            self.frame = frame_add(self.frame, skip)
            self.cache_served_frames += skip
            telemetry.count(
                "cache_served_frames_total", skip,
                help="rollback frames served from the speculation cache "
                     "instead of resimulated",
            )
            hit_handle, hit_checksum = hit_pre
        # state feeding the LAST advance (used to speculate the next tick),
        # as a THUNK — slicing it out of a stacked buffer is a device
        # dispatch, so resolution is deferred to _flush_speculation, off the
        # timed servicing path.  With a full cache hit (skip == k)
        # self.world is already the POST-advance state: the pre-advance
        # source is the previous cached frame, or for a single served
        # advance the rollback target itself (resolved from the ring handle
        # at the flush — speculating from the post-advance state would
        # double-advance the hedge branches, states one frame ahead of
        # their labels)
        last_adv_src = (lambda w=self.world: w)
        if skip == k:
            last_adv_src = (
                (lambda cs=cache_states, i=skip - 2: cs(i))
                if skip >= 2 else None
            )
        use_branched = (
            self.spec_cache is not None and self.app.canonical_branches is not None
        )
        # packed single-upload dispatch (the default): one int8 buffer
        # replaces the inputs/status/frame upload triple.  The branched
        # program keeps its own [B, K] shape (app.packed_resim_fn is None
        # under canonical_branches, so self.packed is already False there).
        use_packed = self.packed and not use_branched
        # Donation decision + pre-resolution of leading (c==0) saves.  A
        # leading save stores the PRE-dispatch state; donation kills that
        # buffer, so it must be serviceable without pre_world: identity
        # strategies slice it out of the PREVIOUS dispatch's stacked saves
        # (bit-identical: final == stacked[-1]); lossy strategies encode it
        # before the dispatch runs.
        leading_saves = []
        for r in run:
            if isinstance(r, AdvanceRequest):
                break
            if isinstance(r, SaveRequest):
                leading_saves.append(r)
        c0_stored = None
        donate = (
            self.enable_donation
            and self.spec_cache is None
            and self._world_donatable
            and k - skip > 0
            and not use_branched
            and (
                self.app.packed_resim_fn_donated if use_packed
                else self.app.resim_fn_donated
            ) is not None
        )
        if donate and leading_saves:
            if identity:
                if self._last_stacked is not None and all(
                    r.frame == self._last_stacked_frame for r in leading_saves
                ):
                    c0_stored = LazySlice(self._last_stacked, self._last_k - 1)
                else:
                    donate = False  # must ring pre_world itself
            else:
                c0_stored = self.app.reg.store_state(materialize(pre_world))
        if k - skip > 0:
            self.device_dispatches += 1
            self.rollback_frames += max(k - skip - 1, 0)
            telemetry.count("device_dispatches_total", help="fused resim dispatches")
            telemetry.count(
                "resim_frames_total", max(k - skip - 1, 0),
                help="frames resimulated beyond the first of each dispatch",
            )
            if donate:
                telemetry.count(
                    "donated_dispatches_total", help="dispatches donating the input world"
                )
            pk = None
            if use_packed:
                # fixed-shape canonical programs take a canonical_depth-
                # deep buffer with the real count in the prefix; the
                # per-k programs take exactly [k+1, W]
                K = self.app.canonical_depth
                if K is not None and k - skip > K:
                    raise ValueError(
                        f"resim depth {k - skip} exceeds canonical_depth "
                        f"{K}; raise App(canonical_depth=...) above "
                        "every session window"
                    )
                with ph.phase("stage_inputs"):
                    pk = self._stage_packed_rows(
                        adv[skip:], self.frame, k_pad=K
                    )
            else:
                with ph.phase("stage_inputs"):
                    inputs, status = self._stage_rows(adv[skip:])
            variant = (
                "branched" if use_branched
                else (("packed_" if use_packed else "")
                      + ("donated" if donate else "plain")),
                k - skip,
            )
            fresh = variant not in self._seen_variants
            t_build = time.perf_counter() if fresh else 0.0
            with ph.phase("wave_dispatch"):
                if use_branched:
                    final, stacked, checks = self._dispatch_branched(
                        inputs, status, adv[-1]
                    )
                    self._note_dispatch_uploads(4)
                elif use_packed:
                    fn = (
                        self.app.packed_resim_fn_donated if donate
                        else self.app.packed_resim_fn
                    )
                    if donate:
                        self.donated_dispatches += 1
                    final, stacked, checks = fn(self.world, pk)
                    self._note_dispatch_uploads(1, pk)
                else:
                    fn = (
                        self.app.resim_fn_donated if donate
                        else self.app.resim_fn
                    )
                    if donate:
                        self.donated_dispatches += 1
                    final, stacked, checks = fn(
                        self.world, inputs, status, self.frame
                    )
                    self._note_dispatch_uploads(3)
                batch_checks = BatchChecks(checks)
                if self.pipeline:
                    # ahead-of-tick readback: the device->host checksum
                    # copy rides behind the dispatch; harvest() collects
                    # it next tick while the device runs frame N+1
                    self._rbq.start(batch_checks)
            if fresh:
                self._note_compile(variant, time.perf_counter() - t_build)
            if self.spec_cache is not None and k - skip >= 2:
                last_adv_src = (
                    lambda s=stacked, i=k - skip - 2: slice_frame(s, i)
                )
            self.world = final
            self._world_checksum = batch_checks.ref(k - skip - 1)
            self.frame = frame_add(self.frame, k - skip)
            self._last_stacked = stacked
            self._last_k = k - skip
            self._last_stacked_frame = self.frame
            self._world_donatable = True  # final is a fresh buffer
        materialize_saves = False
        if stacked is not None:
            stacked_bytes = self._stacked_bytes_by_k.get(k - skip)
            if stacked_bytes is None:
                from .utils.mem import tree_device_bytes

                stacked_bytes = tree_device_bytes(stacked)
                self._stacked_bytes_by_k[k - skip] = stacked_bytes
            materialize_saves = stacked_bytes > self.ring_materialize_bytes
            telemetry.gauge_set(
                "save_bytes", stacked_bytes,
                "device bytes of the last dispatch's stacked save buffer",
            )
            telemetry.record(
                "dispatch", frame=self.frame, advances=k - skip, skipped=skip,
                donated=donate, save_bytes=stacked_bytes,
            )
        pushed_pre_world = False
        with ph.phase("store_save"):
            c = 0  # advances seen so far within the run
            for r in run:
                if isinstance(r, AdvanceRequest):
                    c += 1
                    continue
                if c == 0:
                    if hit is not None:
                        # leading save after a cache-served rollback: the
                        # live world predates the target, but the ring pop
                        # already handed us the target's stored form —
                        # re-push it (the megastep loaded_pair pattern)
                        self.ring.push(r.frame, (hit_handle, hit_checksum))
                        r.cell.save(r.frame, hit_checksum)
                        continue
                    if c0_stored is not None:
                        # pre-resolved (donation path): pre_world's buffers
                        # may already be dead — serve from the previous
                        # dispatch's stacked saves / the pre-encoded store
                        self.ring.push(r.frame, (c0_stored, pre_checksum))
                        # the ref itself is the provider: callable (forcing)
                        # with a non-blocking peek() for the pipelined path
                        r.cell.save(r.frame, pre_checksum)
                        continue
                    state_s, cs = pre_world, pre_checksum
                    pushed_pre_world = identity
                elif c <= skip:
                    # cache-served frame: store a lazy handle into the
                    # branch's stacked states (alias — the cache entry keeps
                    # the buffer alive anyway); slicing dispatches only on a
                    # later rollback, keeping hit servicing at O(1) dispatches
                    state_s = LazySlice(cache_states.stacked, c - 1)
                    if materialize_saves:
                        state_s = state_s.materialize()
                    cs = cache_bc.ref(c - 1)
                else:
                    # defer the per-frame slice: the ring stores a handle into
                    # the stacked buffer; slicing dispatches only on rollback
                    # (or eagerly for big worlds — see ring_materialize_bytes)
                    state_s = LazySlice(stacked, c - 1 - skip)
                    if materialize_saves:
                        state_s = state_s.materialize()
                    cs = batch_checks.ref(c - 1 - skip)
                stored = (
                    state_s
                    if identity
                    else self.app.reg.store_state(materialize(state_s))
                )
                self.ring.push(r.frame, (stored, cs))
                r.cell.save(r.frame, cs)
        if pushed_pre_world and self._world is pre_world:
            # save-only run (or full cache skip): the ring now aliases the
            # live world object; the next dispatch must not donate it
            self._world_donatable = False
        if (
            materialize_saves
            or self.spec_cache is not None
            or not self.enable_donation
            or not identity
        ):
            # retaining the stacked buffer only pays off when the NEXT
            # dispatch's leading save can be served from it (identity +
            # donation); otherwise it would just pin k extra world copies
            # in device memory — exactly what ring_materialize_bytes bounds
            self._last_stacked = None
            self._last_stacked_frame = None
        # hedge the live frame: if its inputs were (partly) predicted, fan out
        # candidate branches for the same transition (the branched program
        # already did this inside its own dispatch)
        if (
            not (self.spec_cache is not None and self.app.canonical_branches)
            and self.spec_cache is not None
            and k > 0
            and np.any(adv[-1].status == InputStatus.PREDICTED)
        ):
            # record the hedge only; _flush_speculation issues the draft
            # fan-out at the next seam (before a following Load's timer, or
            # at the tick boundary) so drafts ride the otherwise-idle slot
            # instead of the rollback-servicing critical path
            self._pending_speculate.append(
                ("spec", last_adv_src, hit_handle,
                 frame_add(self.frame, -1), adv[-1].inputs)
            )

    # -- device-resident megastep (ops/megastep.py) -------------------------

    def _ensure_megastep(self) -> None:
        """Lazily build the megastep program + device ring for the current
        session (depth formulas mirror ``_ring_depth``/``set_session``: one
        fixed-shape program per session, so every flush runs the same
        machine code)."""
        if self._ms_fn is not None:
            return
        from .ops.megastep import init_device_ring, make_megastep_fn

        s = self.session
        mp = s.max_prediction()
        window = (
            s.rollback_window() if hasattr(s, "rollback_window") else mp
        )
        # deepest session-shaped run: a rollback landing in the same
        # coalesced flush as catch-up ticks (the canonical-depth bound in
        # set_session)
        self._ms_k = self.coalesce_frames + max(window, mp)
        # one more slot than the host ring so k_max < R: within a single
        # dispatch no two written frames share a slot (a duplicate scatter
        # index would make the writeback order-dependent)
        self._ms_slots = self._ring_depth(s) + 1
        app = self.app
        self._ms_fn = make_megastep_fn(
            app.reg, app.step, app.packed_spec, app.fps, seed=app.seed,
            retention=app.retention, k_max=self._ms_k,
            ring_slots=self._ms_slots,
        )
        self._ms_ring, self._ms_ring_frames = init_device_ring(
            self.world, self._ms_slots
        )
        self._dev_frames = {}
        # device-memory accounting: the on-device ring is a fixed
        # [slots, ...] stacked world plus the slot->frame vector
        from .utils.mem import tree_device_bytes

        telemetry.devmem.note(
            self._devmem_tag + "/megastep_ring",
            tree_device_bytes(self._ms_ring)
            + tree_device_bytes(self._ms_ring_frames),
        )

    def _dev_slot(self, frame: int) -> Optional[int]:
        """Device-ring slot currently holding ``frame``, or None when the
        frame was overwritten / never written.  The host mirror makes the
        check exact: a miss degrades to the host materialize path, never to
        a wrong row.  Python ``%`` is non-negative like jnp's (divisor-sign)
        ``%``, so wrapped int32 frames map to the same slot on both sides."""
        slot = frame % self._ms_slots
        return slot if self._dev_frames.get(slot) == frame else None

    def _run_megastep(
        self, load: Optional[LoadRequest], run: List[GgrsRequest]
    ) -> None:
        """Megastep flush: an optional LoadRequest plus its following
        Advance/Save run as ONE device dispatch fed by ONE packed upload —
        including the rollback itself, when its target frame is still
        resident in the on-device snapshot ring (ops/megastep.py)."""
        self._ensure_megastep()
        ph = self._phases
        n_adv = sum(1 for r in run if isinstance(r, AdvanceRequest))
        has_load = 0
        load_slot = 0
        loaded_pair = None
        if load is not None:
            slot = self._dev_slot(load.frame) if n_adv > 0 else None
            if slot is None:
                # ring miss (or a load with nothing to replay): host
                # materialize path — bit-identical, one extra dispatch
                self._load(load.frame, load.cause)
            else:
                self._note_rollback(load.frame, load.cause)
                with ph.phase("rollback_load"):
                    # bookkeeping only: pop newer host-ring entries and take
                    # the checksum handle; the STATE restore happens inside
                    # the megastep dispatch (no materialize, no extra
                    # dispatch, no host sync)
                    stored, checksum = self.ring.rollback(load.frame)
                    loaded_pair = (stored, checksum)
                    self._world_checksum = checksum
                    self.frame = load.frame
                self.fused_ring_loads += 1
                telemetry.count(
                    "fused_ring_loads_total",
                    help="rollback loads served from the device ring inside "
                         "the megastep dispatch",
                )
                has_load = 1
                load_slot = slot
                self._last_stacked = None
                self._last_stacked_frame = None
        if not run:
            return
        # chunk the run so each dispatch carries at most k_max advances
        # (session-shaped runs always fit — k_max covers a maximal rollback
        # + coalesced catch-up — but replay/tool request lists can be longer)
        i, n = 0, len(run)
        while i < n:
            j, c = i, 0
            while j < n:
                if isinstance(run[j], AdvanceRequest):
                    if c == self._ms_k:
                        break
                    c += 1
                j += 1
            self._megastep_chunk(run[i:j], has_load, load_slot, loaded_pair)
            has_load, load_slot, loaded_pair = 0, 0, None
            i = j

    def _megastep_chunk(
        self, run: List[GgrsRequest], has_load: int, load_slot: int,
        loaded_pair,
    ) -> None:
        """One megastep dispatch: <= k_max advances (+ interleaved saves),
        optionally consuming a fused device-ring load in the same program."""
        ph = self._phases
        adv = [r for r in run if isinstance(r, AdvanceRequest)]
        k = len(adv)
        ph.note_advances(k)
        if not hasattr(self._world_checksum, "to_int"):
            self._world_checksum = wrap_single_checksum(self._world_checksum)
        pre_world, pre_checksum = self.world, self._world_checksum
        pre_frame = self.frame
        if self.on_advance is not None:
            for i, a in enumerate(adv):
                self.on_advance(frame_add(pre_frame, i + 1), a.inputs, a.status)
        stacked = None
        batch_checks = None
        if k > 0:
            self.device_dispatches += 1
            self.megastep_dispatches += 1
            self.rollback_frames += max(k - 1, 0)
            telemetry.count("device_dispatches_total", help="fused resim dispatches")
            telemetry.count(
                "resim_frames_total", max(k - 1, 0),
                help="frames resimulated beyond the first of each dispatch",
            )
            with ph.phase("stage_inputs"):
                pk = self._stage_packed_rows(
                    adv, self.frame, k_pad=self._ms_k,
                    has_load=has_load, load_slot=load_slot,
                )
            variant = ("megastep", self._ms_k)
            fresh = variant not in self._seen_variants
            t_build = time.perf_counter() if fresh else 0.0
            with ph.phase("wave_dispatch"):
                (final, self._ms_ring, self._ms_ring_frames, stacked,
                 checks) = self._ms_fn(
                    self.world, self._ms_ring, self._ms_ring_frames, pk
                )
                self._note_dispatch_uploads(1, pk)
                batch_checks = BatchChecks(checks)
                if self.pipeline:
                    self._rbq.start(batch_checks)
            if fresh:
                self._note_compile(variant, time.perf_counter() - t_build)
            # host mirror of the device ring writeback (slot -> frame)
            R = self._ms_slots
            for i in range(k):
                f = frame_add(self.frame, i + 1)
                self._dev_frames[f % R] = f
            self.world = final
            self._world_checksum = batch_checks.ref(k - 1)
            self.frame = frame_add(self.frame, k)
        materialize_saves = False
        if stacked is not None:
            key = ("megastep", self._ms_k)
            stacked_bytes = self._stacked_bytes_by_k.get(key)
            if stacked_bytes is None:
                from .utils.mem import tree_device_bytes

                stacked_bytes = tree_device_bytes(stacked)
                self._stacked_bytes_by_k[key] = stacked_bytes
            materialize_saves = stacked_bytes > self.ring_materialize_bytes
            telemetry.gauge_set(
                "save_bytes", stacked_bytes,
                "device bytes of the last dispatch's stacked save buffer",
            )
            telemetry.record(
                "dispatch", frame=self.frame, advances=k, skipped=0,
                donated=False, save_bytes=stacked_bytes, megastep=True,
            )
        with ph.phase("store_save"):
            c = 0  # advances seen so far within the run
            for r in run:
                if isinstance(r, AdvanceRequest):
                    c += 1
                    continue
                if c == 0:
                    if loaded_pair is not None:
                        # leading save after a fused ring load: self.world
                        # was NOT updated host-side (the device selected the
                        # ring row), so re-push the rollback's own handle —
                        # the exact state/checksum the host path would store
                        state_s, cs = loaded_pair
                    else:
                        state_s, cs = pre_world, pre_checksum
                        if self._world is pre_world:
                            # ring aliases the live world (donation is
                            # already off in megastep mode; kept for parity)
                            self._world_donatable = False
                else:
                    # megastep requires identity strategies (ctor), so the
                    # lazy stacked-row handle IS the stored representation
                    state_s = LazySlice(stacked, c - 1)
                    if materialize_saves:
                        state_s = state_s.materialize()
                    cs = batch_checks.ref(c - 1)
                self.ring.push(r.frame, (state_s, cs))
                r.cell.save(r.frame, cs)

    def _note_compile(self, variant, dt: float) -> None:
        """Record a program variant's first-dispatch wall time (trace +
        compile dominate the first call of each ``(kind, depth)`` jit
        variant — later calls hit the executable cache), into
        :attr:`compile_ms`, the flight recorder and (when telemetry is on)
        the ``program_compile_ms`` histogram."""
        kind, depth = variant
        self._seen_variants.add(variant)
        ms = dt * 1e3
        self.compile_ms[f"{kind}_k{depth}"] = round(ms, 3)
        telemetry.flight_recorder().record(
            "compile", owner="solo", program=kind, k=depth, ms=round(ms, 3)
        )
        telemetry.observe(
            "program_compile_ms", ms,
            "wall ms of each program variant's first dispatch (trace+compile)",
            buckets=telemetry.LATENCY_MS_BUCKETS,
            owner="solo", kind=kind,
        )
        compile_guard.notify("solo", kind, ms)

    def arm_compile_guard(self) -> bool:
        """Declare warmup over: with ``BGT_COMPILE_GUARD=1`` (or
        :func:`~bevy_ggrs_tpu.utils.compile_guard.set_compile_guard`) any
        later program compile raises
        :class:`~bevy_ggrs_tpu.utils.compile_guard.RecompileError` naming
        the owner/kind and bumps ``recompiles_steady_total{owner}``.
        Returns True when armed; no-op (False) when the guard is off."""
        return compile_guard.guard().arm()

    def _dispatch_branched(self, inputs, status, last_adv):
        """One canonical [B, K] dispatch: lane 0 = the real batch; hedge
        lanes replay the real prefix then hold a candidate input from the
        last transition onward (cache entries come out of the same program
        every peer runs — bit-determinism preserved)."""
        import jax as _jax

        app = self.app
        B, K = app.canonical_branches, app.canonical_depth
        k = inputs.shape[0]
        if k > K:
            raise ValueError(f"resim depth {k} exceeds canonical_depth {K}")
        from .ops.resim import pad_repeat_last

        pad = K - k
        inputs_p = pad_repeat_last(np.asarray(inputs), pad)
        status_p = pad_repeat_last(np.asarray(status), pad)
        ib = np.broadcast_to(inputs_p[None], (B, *inputs_p.shape)).copy()
        sb = np.broadcast_to(status_p[None], (B, *status_p.shape)).copy()
        n_real = np.full((B,), k, np.int32)
        hedging = bool(np.any(last_adv.status == InputStatus.PREDICTED))
        cands = None
        if hedging:
            cands = np.asarray(
                self.spec_cache.config.candidates_fn(last_adv.inputs),
                app.input_dtype,
            )[: B - 1]
            for b in range(cands.shape[0]):
                ib[1 + b, k - 1:] = cands[b]  # real prefix, candidate held
                sb[1 + b, k - 1:] = 0  # hedges evaluate as confirmed
                n_real[1 + b] = K
        finals, stacked, checks = app.branched_fn(
            self.world, ib, sb, self.frame, n_real
        )
        if hedging and cands is not None and cands.shape[0] > 0:
            m = cands.shape[0]
            hedge_stacked = _jax.tree.map(lambda a: a[1:1 + m], stacked)
            self.spec_cache.fill_from_branched(
                frame_add(self.frame, k - 1), cands,
                hedge_stacked, checks[1:1 + m],
                offset=k - 1, depth_eff=K - (k - 1),
            )
        from .ops.resim import trim_frames
        from .snapshot.lazy import tree_index

        final0, (stacked0, checks0) = tree_index(
            (finals, trim_frames((stacked, checks), k, axis=1)), 0
        )
        return final0, stacked0, checks0
