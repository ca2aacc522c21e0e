"""Many-worlds: vmap the frame engine over a leading LOBBY axis.

The reference runs one session per process; a TPU chip is absurdly
underutilized by one small rollback sim (the 10k-entity stress world uses
<1% of v5e HBM bandwidth per frame).  This module batches M independent
game worlds — separate lobbies on a game server, a tournament bracket, an
RL population — into ONE dispatch: ``jit(vmap(lax.scan(step)))`` over a
``[M, ...]`` stacked world, with per-lobby inputs and frame counters.

Lobby independence is exact: vmap lanes share machine code, not data, so
lobby b's bits never depend on the other lanes (the same lane-independence
argument as the canonical-branched speculation program, docs/determinism.md)
— proven by the bit-equality test against M separate single-lobby runs
(tests/test_batched_lobbies.py).

Composes with the per-lobby driver loop: each lobby's session/protocol runs
host-side as usual; a server collects each lobby's pending (state, inputs)
work items and flushes them through one batched dispatch instead of M
serial ones (amortizing the per-dispatch submission cost that dominates
small worlds — docs/tpu_notes.md §3b).

Backend note: the win is an ACCELERATOR win (M submissions -> 1, and the
chip is wide enough to eat M small worlds in one pass).  On CPU, measured
8x2000-entity lobbies run ~0.8x the serial rate — XLA:CPU gains nothing
from lane-stacking tiny elementwise work; use per-lobby dispatches there.
"""

from __future__ import annotations

import time
from typing import Dict, List, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..snapshot.world import WorldState
from .resim import resim, resim_padded


def stack_worlds(worlds: List[WorldState]) -> WorldState:
    """Stack M structurally-identical worlds into one [M, ...] pytree."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *worlds)  # bgt: ignore[BGT071]: len(worlds) is the caller's lobby count — bucketed to wave capacity before dispatch, so the traced length is shape-stable per bucket


def unstack_world(batched: WorldState, i: int) -> WorldState:
    """Extract lobby ``i`` from a stacked world (one jitted dispatch)."""
    from ..snapshot.lazy import tree_index

    return tree_index(batched, i)


def make_batched_resim_fn(app):
    """jit(vmap(resim)) over the lobby axis.

    ``fn(batched_world, inputs[M, k, P, ...], status[M, k, P],
    start_frames[M]) -> (finals[M], stacked[M, k], checksums[M, k, 2])`` —
    every lobby advances k frames in one dispatch; per-lobby start frames
    keep independent clocks (lobbies need not be in lockstep).

    Refuses canonical-mode apps: canonical mode exists because the compiled
    program's shape IS a lobby-wide determinism constant for variant-
    unstable float sims (docs/determinism.md), and a vmapped M-lobby program
    is a different program than the single-lobby one the lobby's peers run —
    batching would reintroduce exactly the drift canonical mode removes.
    Integer/fixed-point and variant-stable sims (probe with
    ops/variant_probe.py) batch safely."""
    if app.canonical_depth is not None or app.canonical_branches is not None:
        raise ValueError(
            "many-worlds batching is incompatible with canonical mode: the "
            "batched program differs from the single-lobby canonical "
            "program every peer dispatches, breaking the one-program "
            "bit-determinism guarantee (see make_batched_resim_fn docstring)"
        )
    reg, step, fps = app.reg, app.step, app.fps
    seed, retention = app.seed, app.retention

    @jax.jit
    def fn(batched_world, inputs_b, status_b, start_frames):
        return jax.vmap(
            lambda w, inp, st, f: resim(
                reg, step, w, inp, st, f, retention, fps, seed
            )
        )(batched_world, inputs_b, status_b, start_frames)

    return fn


def make_batched_padded_fn(app, k_max: int, donate: bool = False, *,
                           unroll: int = 1, fused_checksums: bool = False):
    """jit(vmap(resim_padded)) over the lobby axis — the BatchedRunner's
    dispatch: every lobby advances up to ``k_max`` frames in ONE call, with
    per-lobby ``n_real`` masking (a lobby with no pending work passes its
    lane through unchanged at ``n_real=0``).

    ``fn(batched_world[M], inputs[M, k_max, P, ...], status[M, k_max, P],
    start_frames[M], n_real[M]) -> (finals[M], stacked[M, k_max],
    checksums_flat[M * k_max, 2])`` — checksums come out pre-flattened so
    one BatchChecks wraps the whole dispatch (row ``b * k_max + i``).

    Same canonical-mode refusal (and rationale) as
    :func:`make_batched_resim_fn`.  ``donate=True`` donates the batched
    world for in-place lane updates (the server's resident-world fast
    path).  ``unroll``/``fused_checksums`` forward to
    :func:`..ops.resim.resim_padded` (defaults = the historical program)."""
    if app.canonical_depth is not None or app.canonical_branches is not None:
        raise ValueError(
            "many-worlds batching is incompatible with canonical mode "
            "(see make_batched_resim_fn)"
        )
    reg, step, fps = app.reg, app.step, app.fps
    seed, retention = app.seed, app.retention

    def body(batched_world, inputs_b, status_b, start_frames, n_real):
        finals, stacked, checks = jax.vmap(
            lambda w, inp, st, f, nr: resim_padded(
                reg, step, w, inp, st, f, nr, retention, fps, seed,
                unroll=unroll, fused_checksums=fused_checksums,
            )
        )(batched_world, inputs_b, status_b, start_frames, n_real)
        return finals, stacked, checks.reshape(-1, 2)

    return jax.jit(body, donate_argnums=(0,) if donate else ())


def make_batched_exact_fn(app, k: int, *, unroll: int = 1,
                          fused_checksums: bool = False,
                          donate_outputs: bool = False):
    """jit(vmap(resim)) at an EXACT depth ``k`` — the unmasked full-wave
    program.

    When every active lane advances exactly ``k`` frames the per-frame
    ``n_real`` mask of :func:`make_batched_padded_fn` buys nothing and costs
    a full-world select per frame (~12% of the batched tick on the CPU
    reference host); this builder drops it.  Signature:
    ``fn(batched_world[M], inputs[M, k, P, ...], status[M, k, P],
    start_frames[M]) -> (finals[M], stacked[M, k], checks_flat[M*k, 2])``.

    ``donate_outputs=True`` appends two dummy parameters
    ``(prev_stacked, prev_checks)`` — the PREVIOUS call's stacked/checks
    outputs — marked as donated: XLA aliases their buffers onto this call's
    outputs, so the steady-state wave loop recycles its two big output
    allocations instead of churning the host allocator every dispatch
    (measured +10-15% agg throughput and a 4-8x spread reduction on the
    1-CPU bench host).  Callers own the aliasing contract: the passed
    previous outputs are DEAD after the call (see
    :class:`BucketedWaveExecutor`, which manages this automatically)."""
    if app.canonical_depth is not None or app.canonical_branches is not None:
        raise ValueError(
            "many-worlds batching is incompatible with canonical mode "
            "(see make_batched_resim_fn)"
        )
    reg, step, fps = app.reg, app.step, app.fps
    seed, retention = app.seed, app.retention

    def core(batched_world, inputs_b, status_b, start_frames):
        finals, stacked, checks = jax.vmap(
            lambda w, inp, st, f: resim(
                reg, step, w, inp, st, f, retention, fps, seed,
                unroll=unroll, fused_checksums=fused_checksums,
            )
        )(batched_world, inputs_b, status_b, start_frames)
        return finals, stacked, checks.reshape(-1, 2)

    if not donate_outputs:
        return jax.jit(core)

    def recycling(batched_world, inputs_b, status_b, start_frames,
                  prev_stacked, prev_checks):
        del prev_stacked, prev_checks  # donated for output aliasing only
        return core(batched_world, inputs_b, status_b, start_frames)

    return jax.jit(recycling, donate_argnums=(4, 5))


def _lane_load(w, src, has_load, load_slot):
    """A lane's in-wave rollback load: where the packed prefix's
    ``has_load`` word is set, the lane starts from its OWN row of the load
    source instead of the resident world — slot ``load_slot`` of a stacked
    ``[k', ...]`` source (a past wave's saved frames), or the whole row of a
    world-shaped source (a past resident world).  A per-lane select, not a
    gather across lanes: no index array rides along, and the lobby-sharded
    form needs no collective.  The picked values are copied bit for bit, so
    the wave matches a separate load followed by the plain wave."""
    take = has_load != 0

    def pick(a, s):
        if s.ndim > a.ndim:
            s = jax.lax.dynamic_index_in_dim(s, load_slot, axis=0,
                                             keepdims=False)
        return jnp.where(take, s, a)

    return jax.tree.map(pick, w, src)


def make_batched_packed_padded_fn(app, k: int, *, unroll: int = 1,
                                  fused_checksums: bool = False):
    """Packed single-upload variant of :func:`make_batched_padded_fn`:
    ``fn(batched_world[M], packed int8[M, k + 1, W]) -> (finals[M],
    stacked[M, k], checks_flat[M * k, 2])``.

    Each lane's prefix row carries its OWN ``(start_frame, n_real)`` (ops/
    packing.py), so the per-lobby start-frame and mask vectors that used to
    ride as separate uploads are folded into the one buffer — a wave costs
    one host->device upload total instead of four.  The unpack is a pure
    bitcast; arithmetic is unchanged, so lanes stay bit-identical to the
    unpacked program (tests/test_packed.py).

    Called as ``fn(batched_world, packed_b, src_b)`` the program also
    serves in-wave rollback loads: ``src_b`` is one past buffer with the
    lobby axis leading (a stacked wave output or a past resident world),
    vmapped with the worlds, and each lane whose prefix sets ``has_load``
    starts from its own row of it (:func:`_lane_load`).  Without ``src_b``
    the traced program is the plain wave."""
    if app.canonical_depth is not None or app.canonical_branches is not None:
        raise ValueError(
            "many-worlds batching is incompatible with canonical mode "
            "(see make_batched_resim_fn)"
        )
    from .packing import unpack_seq

    reg, step, fps = app.reg, app.step, app.fps
    seed, retention = app.seed, app.retention
    pspec = app.packed_spec

    def lane(w, pk, *src):
        inputs, status, start, n_real, has_load, load_slot = unpack_seq(
            pspec, pk
        )
        if src:
            w = _lane_load(w, src[0], has_load, load_slot)
        return resim_padded(
            reg, step, w, inputs, status, start, n_real, retention, fps,
            seed, unroll=unroll, fused_checksums=fused_checksums,
        )

    def body(batched_world, packed_b, *src_b):
        finals, stacked, checks = jax.vmap(lane)(
            batched_world, packed_b, *src_b
        )
        return finals, stacked, checks.reshape(-1, 2)

    return jax.jit(body)


def make_batched_packed_exact_fn(app, k: int, *, unroll: int = 1,
                                 fused_checksums: bool = False,
                                 donate_outputs: bool = False):
    """Packed single-upload variant of :func:`make_batched_exact_fn` (the
    unmasked full-wave program): ``fn(batched_world[M],
    packed int8[M, k + 1, W]) -> (finals, stacked, checks_flat)``; the
    per-lane prefix supplies the start frame (``n_real`` is ignored — every
    lane advances exactly ``k``).  ``donate_outputs=True`` appends the
    previous call's ``(prev_stacked, prev_checks)`` as donated parameters,
    same recycling contract as the unpacked builder.  Without donation the
    program takes the optional in-wave load source ``src_b`` of
    :func:`make_batched_packed_padded_fn`."""
    if app.canonical_depth is not None or app.canonical_branches is not None:
        raise ValueError(
            "many-worlds batching is incompatible with canonical mode "
            "(see make_batched_resim_fn)"
        )
    from .packing import unpack_seq

    reg, step, fps = app.reg, app.step, app.fps
    seed, retention = app.seed, app.retention
    pspec = app.packed_spec

    def lane(w, pk, *src):
        inputs, status, start, _nr, has_load, load_slot = unpack_seq(
            pspec, pk
        )
        if src:
            w = _lane_load(w, src[0], has_load, load_slot)
        return resim(
            reg, step, w, inputs, status, start, retention, fps, seed,
            unroll=unroll, fused_checksums=fused_checksums,
        )

    def core(batched_world, packed_b, *src_b):
        finals, stacked, checks = jax.vmap(lane)(
            batched_world, packed_b, *src_b
        )
        return finals, stacked, checks.reshape(-1, 2)

    if not donate_outputs:
        return jax.jit(core)

    def recycling(batched_world, packed_b, prev_stacked, prev_checks):
        del prev_stacked, prev_checks  # donated for output aliasing only
        return core(batched_world, packed_b)

    return jax.jit(recycling, donate_argnums=(2, 3))


def bucket_sizes(k_max: int) -> Tuple[int, ...]:
    """Power-of-two depth buckets up to (and always including) ``k_max``:
    ``bucket_sizes(12) == (1, 2, 4, 8, 12)``.  A wave whose hottest lobby
    advances ``k_hot`` frames dispatches the smallest bucket >= k_hot, so
    the compile count is O(log k_max) while a typical 1-advance lockstep
    wave stops paying for a k_max-frame scan."""
    if k_max < 1:
        raise ValueError(f"k_max must be >= 1, got {k_max}")
    sizes = []
    b = 1
    while b < k_max:
        sizes.append(b)
        b *= 2
    sizes.append(k_max)
    return tuple(sizes)


class BucketedWaveExecutor:
    """Shape-bucketed dispatcher for BatchedRunner waves.

    The old hot loop compiled ONE ``k_max``-deep padded program and ran every
    wave through it — a 1-advance lockstep tick scanned ``k_max`` frames per
    lobby with all but one masked off.  This executor keeps a small cache of
    programs keyed by ``(kind, bucket)``:

    - ``bucket`` ∈ :func:`bucket_sizes(k_max)` — the smallest power-of-two
      depth covering the wave's ``k_hot``, so the wasted scan length is < 2x
      and the compile count is O(log k_max), not O(k_max) (jit itself adds a
      (M, world-spec) axis to the cache key: a new lobby count or world
      structure retraces, same shapes hit).
    - ``kind`` — ``exact`` when every lane advances exactly ``bucket`` frames
      (no mask, ~12% faster) or ``padded`` (per-lane ``n_real`` masking) for
      ragged/partial waves.  A packed wave handed a load source
      (:meth:`stage_loads`) runs the ``*_load`` kind of either: the same
      program with the source as a third argument.

    All programs run ``unroll=2`` scans with the checksum reduction hoisted
    out of the scan body; both are bit-identical transformations for the
    repo's uint32 wrapping-add checksum (see ``ops/resim.resim``), and exact
    vs padded equality for variant-stable sims is covered by
    tests/test_batched_runner.py.

    ``recycle_outputs=True`` additionally routes full waves through the
    donating program of :func:`make_batched_exact_fn`, recycling the
    previous wave's stacked/checks buffers into the new outputs.  Only
    enable it when NOTHING retains those outputs across calls — the
    BatchedRunner can't (its snapshot rings hold LazySlice handles into
    past stacked buffers), the throughput bench can and does.

    Dispatch/compile behavior is observable three ways: the
    ``batched_wave_dispatches_total`` / ``batched_program_compiles_total``
    telemetry counters (pre-bound, argument-free), the plain-int
    ``dispatch_count`` / ``compile_count`` attributes, and the per-bucket
    histogram from :meth:`stats`.
    """

    def __init__(self, app, k_max: int, *, unroll: int = 2,
                 fused_checksums: bool = True, recycle_outputs: bool = False):
        if app.canonical_depth is not None or app.canonical_branches is not None:
            raise ValueError(
                "many-worlds batching is incompatible with canonical mode "
                "(see make_batched_resim_fn)"
            )
        self.app = app
        self.k_max = int(k_max)
        self.unroll = unroll
        self.fused_checksums = fused_checksums
        self.recycle_outputs = recycle_outputs
        self.buckets = bucket_sizes(self.k_max)
        self._fns: Dict[Tuple[str, int], object] = {}
        self._prev_out: Dict[Tuple[str, int], tuple] = {}
        self.compile_count = 0  # programs built (per (kind, bucket))
        self.dispatch_count = 0
        self.bucket_hist: Dict[int, int] = {b: 0 for b in self.buckets}
        # first-dispatch wall time per program variant: jit compiles lazily,
        # so the first call of each (kind, bucket) pays trace+compile — the
        # device-time attribution bench/stats surface (keys "exact_k4", ...)
        self.compile_ms: Dict[str, float] = {}
        self._timed: Set[Tuple[str, int, str]] = set()
        self._load_src = None  # stage_loads -> the next run_wave_packed
        self._owner = "wave"
        from .. import telemetry

        _reg = telemetry.registry()
        self._m_dispatches = _reg.bind_counter(
            "batched_wave_dispatches_total",
            "wave dispatches through the bucketed executor",
        )
        self._m_compiles = _reg.bind_counter(
            "batched_program_compiles_total",
            "bucketed wave programs built (kind x bucket)",
        )
        # upload census (same family the solo runner binds): run_wave_packed
        # issues ONE upload per wave; the unpacked run_wave issues 3 (4 for
        # ragged waves, which add the n_real vector)
        self.host_uploads = 0
        self.packed_upload_bytes = 0
        self._m_uploads = _reg.bind_histogram(
            "uploads_per_dispatch",
            "host->device uploads issued per fused dispatch (1 on the "
            "packed path)",
            buckets=(1, 2, 3, 4, 8),
        )
        self._m_packed_bytes = _reg.bind_counter(
            "packed_upload_bytes",
            "bytes staged through packed single-upload buffers",
        )

    def _note_uploads(self, n: int, packed_buf=None) -> None:
        self.host_uploads += n
        self._m_uploads.observe(n)
        if packed_buf is not None:
            self.packed_upload_bytes += packed_buf.nbytes
            self._m_packed_bytes.inc(packed_buf.nbytes)

    def bucket_for(self, k_hot: int) -> int:
        """Smallest bucket >= ``k_hot`` (raises beyond ``k_max``)."""
        if k_hot > self.k_max:
            raise ValueError(
                f"wave depth {k_hot} exceeds k_max={self.k_max}"
            )
        for b in self.buckets:
            if b >= k_hot:
                return b
        raise AssertionError("unreachable: buckets end at k_max")

    def _get_fn(self, kind: str, bucket: int):
        fn = self._fns.get((kind, bucket))
        if fn is None:
            if kind == "exact":
                fn = make_batched_exact_fn(
                    self.app, bucket, unroll=self.unroll,
                    fused_checksums=self.fused_checksums,
                )
            elif kind == "exact_recycle":
                fn = make_batched_exact_fn(
                    self.app, bucket, unroll=self.unroll,
                    fused_checksums=self.fused_checksums, donate_outputs=True,
                )
            elif kind in ("packed_exact", "packed_exact_load"):
                # the *_load kinds: the same program called with a source
                fn = make_batched_packed_exact_fn(
                    self.app, bucket, unroll=self.unroll,
                    fused_checksums=self.fused_checksums,
                )
            elif kind == "packed_exact_recycle":
                fn = make_batched_packed_exact_fn(
                    self.app, bucket, unroll=self.unroll,
                    fused_checksums=self.fused_checksums, donate_outputs=True,
                )
            elif kind in ("packed_padded", "packed_padded_load"):
                fn = make_batched_packed_padded_fn(
                    self.app, bucket, unroll=self.unroll,
                    fused_checksums=self.fused_checksums,
                )
            else:
                fn = make_batched_padded_fn(
                    self.app, bucket, unroll=self.unroll,
                    fused_checksums=self.fused_checksums,
                )
            self._fns[(kind, bucket)] = fn
            self.compile_count += 1
            self._m_compiles.inc()
        return fn

    def _dispatch(self, kind: str, bucket: int, *args, variant: str = ""):
        """Call the ``(kind, bucket)`` wave program, timing its FIRST call.

        jit returns instantly at build time and compiles at first dispatch,
        so that call's wall time IS the program's trace+compile cost; it
        lands in :attr:`compile_ms`, the flight recorder and (telemetry on)
        the ``program_compile_ms`` histogram.  ``variant`` names an argument
        shape the one jitted program is traced for separately (the load
        source's depth), so each such compile is timed too.  Steady-state
        overhead over a raw ``_get_fn(...)(...)`` call: one extra set
        lookup."""
        timed = (kind, bucket, variant)
        if timed in self._timed:
            return self._fns[(kind, bucket)](*args)
        fn = self._get_fn(kind, bucket)
        t0 = time.perf_counter()
        out = fn(*args)
        ms = (time.perf_counter() - t0) * 1e3
        self._timed.add(timed)
        self.compile_ms[f"{kind}_k{bucket}{variant}"] = round(ms, 3)
        from .. import telemetry

        telemetry.flight_recorder().record(
            "compile", owner=self._owner, program=kind, k=bucket,
            ms=round(ms, 3),
        )
        telemetry.observe(
            "program_compile_ms", ms,
            "wall ms of each program variant's first dispatch (trace+compile)",
            buckets=telemetry.LATENCY_MS_BUCKETS,
            owner=self._owner, kind=kind,
        )
        from ..utils import compile_guard

        compile_guard.notify(self._owner, kind, ms)
        return out

    def run_wave(self, worlds, inputs, status, starts, ks):
        """Dispatch one wave; returns ``(bucket, finals, stacked,
        checks_flat)``.

        ``inputs``/``status`` are the full ``[M, >=bucket, ...]`` staging
        buffers (host or device); the executor slices ``[:, :bucket]``
        itself.  ``ks`` is the per-lobby advance count (0 = idle lane);
        ``checks_flat`` rows are ``b * bucket + i``."""
        ks = list(ks)
        k_hot = max(ks)
        if k_hot <= 0:
            raise ValueError("run_wave needs at least one advancing lobby")
        bucket = self.bucket_for(k_hot)
        exact = all(k == bucket for k in ks)
        # persistent staging buffers are rewritten next wave: commit the
        # sliced uploads synchronously so the asynchronous transfer can
        # never read a later wave's bytes (utils/staging.py)
        from ..utils import staging
        from ..utils.staging import commit

        inp = commit(inputs[:, :bucket])
        st = commit(status[:, :bucket])
        starts = commit(np.asarray(starts, np.int32))
        self.dispatch_count += 1
        self.bucket_hist[bucket] += 1
        self._m_dispatches.inc()
        if exact:
            self._note_uploads(3)
            if self.recycle_outputs:
                key = ("exact_recycle", bucket)
                prev = self._prev_out.pop(key, None)
                if prev is None:
                    # first call at this bucket: nothing to recycle yet
                    finals, stacked, checks = self._dispatch(
                        "exact", bucket, worlds, inp, st, starts
                    )
                else:
                    san = staging.sanitizer()
                    san.guard_donated(prev[0], "batch.run_wave/stacked")
                    san.guard_donated(prev[1], "batch.run_wave/checks")
                    finals, stacked, checks = self._dispatch(
                        *key, worlds, inp, st, starts, *prev
                    )
                    # the dispatch donated prev's device buffers: any
                    # later reuse of those handles is a race
                    san.donate(prev[0], "exact_recycle stacked")
                    san.donate(prev[1], "exact_recycle checks")
                self._prev_out[key] = (stacked, checks)
            else:
                finals, stacked, checks = self._dispatch(
                    "exact", bucket, worlds, inp, st, starts
                )
        else:
            self._note_uploads(4)
            n_real = np.asarray(ks, np.int32)
            finals, stacked, checks = self._dispatch(
                "padded", bucket, worlds, inp, st, starts, n_real
            )
        return bucket, finals, stacked, checks

    def stage_loads(self, src) -> None:
        """Name the load source of the NEXT :meth:`run_wave_packed` call:
        every lane whose packed prefix sets ``has_load`` starts from its own
        row of ``src`` inside the wave program (slot ``load_slot`` of a past
        stacked wave output ``[M, k', ...]``, or the row of a past resident
        world ``[M, ...]``) instead of from the resident world — a rollback
        load with no dispatch of its own.  That call consumes it."""
        self._load_src = src

    def run_wave_packed(self, worlds, packed, ks):
        """Dispatch one wave fed by the packed single-upload staging buffer
        ``packed int8[M, >= bucket + 1, W]`` (per-lane prefix row carries
        that lobby's start frame and ``n_real`` — ops/packing.py); same
        return contract as :meth:`run_wave`.  The whole wave costs ONE
        host->device upload (the resident stacked world never leaves the
        device).  A source named by :meth:`stage_loads` routes the wave
        through the load-carrying programs (never the recycling one)."""
        src, self._load_src = self._load_src, None
        ks = list(ks)
        k_hot = max(ks)
        if k_hot <= 0:
            raise ValueError("run_wave needs at least one advancing lobby")
        bucket = self.bucket_for(k_hot)
        exact = all(k == bucket for k in ks)
        from ..utils import staging
        from ..utils.staging import commit

        pk = commit(packed[:, :bucket + 1])
        self.dispatch_count += 1
        self.bucket_hist[bucket] += 1
        self._m_dispatches.inc()
        self._note_uploads(1, pk)
        if src is not None:
            finals, stacked, checks = self._dispatch(
                "packed_exact_load" if exact else "packed_padded_load",
                bucket, worlds, pk, src, variant=_src_variant(worlds, src),
            )
        elif exact:
            if self.recycle_outputs:
                key = ("packed_exact_recycle", bucket)
                prev = self._prev_out.pop(key, None)
                if prev is None:
                    finals, stacked, checks = self._dispatch(
                        "packed_exact", bucket, worlds, pk
                    )
                else:
                    san = staging.sanitizer()
                    san.guard_donated(prev[0], "batch.run_wave_packed/stacked")
                    san.guard_donated(prev[1], "batch.run_wave_packed/checks")
                    finals, stacked, checks = self._dispatch(
                        *key, worlds, pk, *prev
                    )
                    san.donate(prev[0], "packed_exact_recycle stacked")
                    san.donate(prev[1], "packed_exact_recycle checks")
                self._prev_out[key] = (stacked, checks)
            else:
                finals, stacked, checks = self._dispatch(
                    "packed_exact", bucket, worlds, pk
                )
        else:
            finals, stacked, checks = self._dispatch(
                "packed_padded", bucket, worlds, pk
            )
        return bucket, finals, stacked, checks

    def stats(self) -> dict:
        """Executor-side counters for bench/tests: dispatches, compiles,
        per-bucket dispatch histogram, live jit cache entries."""
        jit_entries = 0
        for fn in self._fns.values():
            try:
                jit_entries += fn._cache_size()
            except Exception:
                pass
        return {
            "wave_dispatches": self.dispatch_count,
            "program_compiles": self.compile_count,
            "bucket_hist": {k: v for k, v in self.bucket_hist.items() if v},
            "jit_entries": jit_entries,
            "compile_ms": dict(self.compile_ms),
            "host_uploads": self.host_uploads,
            "packed_upload_bytes": self.packed_upload_bytes,
        }


# -- device-sharded many-worlds executor -------------------------------------

def make_sharded_padded_fn(app, k: int, mesh, *, unroll: int = 1,
                           fused_checksums: bool = False):
    """The ``n_real``-masked bucketed wave program sharded over a
    ``"lobby"`` mesh axis via ``shard_map``.

    Each device receives its contiguous ``M/D`` block of lobby lanes and
    runs ``vmap(resim_padded)`` over them — the SAME SPMD program on every
    device, so a wave of M lobbies on D devices costs one dispatch per
    device instead of one device doing all M lanes.  Lobbies never
    communicate, so the body contains NO collectives; the checksum
    post-pass (``fused_checksums``) runs per-lane inside the shard, which
    keeps it bit-exact (the uint32 wrapping-add reduction never crosses a
    shard boundary).  Signature matches :func:`make_batched_padded_fn`
    with M divisible by the mesh size (the executor pads)."""
    if app.canonical_depth is not None or app.canonical_branches is not None:
        raise ValueError(
            "many-worlds batching is incompatible with canonical mode "
            "(see make_batched_resim_fn)"
        )
    from jax.sharding import PartitionSpec as P

    from ..parallel.mesh import LOBBY_AXIS

    reg, step, fps = app.reg, app.step, app.fps
    seed, retention = app.seed, app.retention
    spec = P(LOBBY_AXIS)

    def local(batched_world, inputs_b, status_b, start_frames, n_real):
        return jax.vmap(
            lambda w, inp, st, f, nr: resim_padded(
                reg, step, w, inp, st, f, nr, retention, fps, seed,
                unroll=unroll, fused_checksums=fused_checksums,
            )
        )(batched_world, inputs_b, status_b, start_frames, n_real)

    sharded = jax.shard_map(
        local, mesh=mesh,
        in_specs=(spec, spec, spec, spec, spec),
        out_specs=(spec, spec, spec),
        check_vma=False,  # no replication to track: lanes are independent
    )

    def body(batched_world, inputs_b, status_b, start_frames, n_real):
        finals, stacked, checks = sharded(
            batched_world, inputs_b, status_b, start_frames, n_real
        )
        return finals, stacked, checks.reshape(-1, 2)

    return jax.jit(body)


def make_sharded_exact_fn(app, k: int, mesh, *, unroll: int = 1,
                          fused_checksums: bool = False):
    """Exact-depth (unmasked) wave program over the ``"lobby"`` mesh axis —
    the sharded analog of :func:`make_batched_exact_fn` (no
    ``donate_outputs`` variant: output recycling and cross-device layout
    donation do not compose safely, and the sharded path's win is dispatch
    parallelism, not allocator churn)."""
    if app.canonical_depth is not None or app.canonical_branches is not None:
        raise ValueError(
            "many-worlds batching is incompatible with canonical mode "
            "(see make_batched_resim_fn)"
        )
    from jax.sharding import PartitionSpec as P

    from ..parallel.mesh import LOBBY_AXIS

    reg, step, fps = app.reg, app.step, app.fps
    seed, retention = app.seed, app.retention
    spec = P(LOBBY_AXIS)

    def local(batched_world, inputs_b, status_b, start_frames):
        return jax.vmap(
            lambda w, inp, st, f: resim(
                reg, step, w, inp, st, f, retention, fps, seed,
                unroll=unroll, fused_checksums=fused_checksums,
            )
        )(batched_world, inputs_b, status_b, start_frames)

    sharded = jax.shard_map(
        local, mesh=mesh,
        in_specs=(spec, spec, spec, spec),
        out_specs=(spec, spec, spec),
        check_vma=False,
    )

    def body(batched_world, inputs_b, status_b, start_frames):
        finals, stacked, checks = sharded(
            batched_world, inputs_b, status_b, start_frames
        )
        return finals, stacked, checks.reshape(-1, 2)

    return jax.jit(body)


def make_sharded_packed_padded_fn(app, k: int, mesh, *, unroll: int = 1,
                                  fused_checksums: bool = False):
    """Packed single-upload variant of :func:`make_sharded_padded_fn`:
    ``fn(batched_world[M], packed int8[M, k + 1, W])`` with both arguments
    sharded over the ``"lobby"`` mesh axis.  Each device unpacks its own
    block of lanes (prefix bitcast is per-lane, no collectives).  The
    optional in-wave load source ``src_b`` (see
    :func:`make_batched_packed_padded_fn`) is split by the same lobby spec,
    so each device reads only its own lanes' source rows."""
    if app.canonical_depth is not None or app.canonical_branches is not None:
        raise ValueError(
            "many-worlds batching is incompatible with canonical mode "
            "(see make_batched_resim_fn)"
        )
    from jax.sharding import PartitionSpec as P

    from ..parallel.mesh import LOBBY_AXIS
    from .packing import unpack_seq

    reg, step, fps = app.reg, app.step, app.fps
    seed, retention = app.seed, app.retention
    pspec = app.packed_spec
    spec = P(LOBBY_AXIS)

    def lane(w, pk, *src):
        inputs, status, start, n_real, has_load, load_slot = unpack_seq(
            pspec, pk
        )
        if src:
            w = _lane_load(w, src[0], has_load, load_slot)
        return resim_padded(
            reg, step, w, inputs, status, start, n_real, retention, fps,
            seed, unroll=unroll, fused_checksums=fused_checksums,
        )

    def local(batched_world, packed_b, *src_b):
        return jax.vmap(lane)(batched_world, packed_b, *src_b)

    sharded = jax.shard_map(
        local, mesh=mesh,
        in_specs=spec,  # every argument: its lobby block
        out_specs=(spec, spec, spec),
        check_vma=False,  # no replication to track: lanes are independent
    )

    def body(batched_world, packed_b, *src_b):
        finals, stacked, checks = sharded(batched_world, packed_b, *src_b)
        return finals, stacked, checks.reshape(-1, 2)

    return jax.jit(body)


def make_sharded_packed_exact_fn(app, k: int, mesh, *, unroll: int = 1,
                                 fused_checksums: bool = False):
    """Packed single-upload variant of :func:`make_sharded_exact_fn` (no
    recycling variant, same rationale as the unpacked sharded builder),
    with the same optional in-wave load source ``src_b`` as
    :func:`make_sharded_packed_padded_fn`."""
    if app.canonical_depth is not None or app.canonical_branches is not None:
        raise ValueError(
            "many-worlds batching is incompatible with canonical mode "
            "(see make_batched_resim_fn)"
        )
    from jax.sharding import PartitionSpec as P

    from ..parallel.mesh import LOBBY_AXIS
    from .packing import unpack_seq

    reg, step, fps = app.reg, app.step, app.fps
    seed, retention = app.seed, app.retention
    pspec = app.packed_spec
    spec = P(LOBBY_AXIS)

    def lane(w, pk, *src):
        inputs, status, start, _nr, has_load, load_slot = unpack_seq(
            pspec, pk
        )
        if src:
            w = _lane_load(w, src[0], has_load, load_slot)
        return resim(
            reg, step, w, inputs, status, start, retention, fps, seed,
            unroll=unroll, fused_checksums=fused_checksums,
        )

    def local(batched_world, packed_b, *src_b):
        return jax.vmap(lane)(batched_world, packed_b, *src_b)

    sharded = jax.shard_map(
        local, mesh=mesh,
        in_specs=spec,  # every argument: its lobby block
        out_specs=(spec, spec, spec),
        check_vma=False,
    )

    def body(batched_world, packed_b, *src_b):
        finals, stacked, checks = sharded(batched_world, packed_b, *src_b)
        return finals, stacked, checks.reshape(-1, 2)

    return jax.jit(body)


class ShardedWaveExecutor(BucketedWaveExecutor):
    """:class:`BucketedWaveExecutor` whose wave programs shard the lobby
    axis over a device mesh — the many-lobbies-across-the-mesh executor
    (docs/architecture.md "Many-worlds sharding").

    Same bucket/kind cache and :meth:`run_wave` contract as the parent;
    the differences:

    - programs come from :func:`make_sharded_padded_fn` /
      :func:`make_sharded_exact_fn`: one SPMD dispatch drives every device,
      each owning a contiguous ``M_pad / D`` block of lobby lanes;
    - waves whose lobby count M is NOT divisible by the device count D are
      padded to ``M_pad = ceil(M/D) * D`` with idle lanes (``n_real = 0``
      — masked out by the padded program) and the outputs are trimmed back
      to M rows in one extra jitted dispatch.  Callers that control their
      resident world (BatchedRunner) pre-pad to M_pad so the steady state
      never pays the pad/trim pair;
    - ``recycle_outputs`` is refused (donating sharded outputs across waves
      is not supported);
    - dispatch/compile counts surface through the
      ``sharded_wave_dispatches_total`` / ``shard_program_compiles_total``
      telemetry counters (pre-bound) alongside the parent's plain-int
      attributes, and :meth:`stats` adds the device count.

    Bit-exactness: shard_map hands each device the identical per-lane
    program the unsharded vmap runs, and lanes never communicate, so for
    variant-stable sims the sharded wave is bit-identical to the unsharded
    one — enforced by tests/test_sharded_wave.py against
    :class:`BucketedWaveExecutor` on identical waves.
    """

    def __init__(self, app, k_max: int, mesh, *, unroll: int = 2,
                 fused_checksums: bool = True, recycle_outputs: bool = False):
        if recycle_outputs:
            raise ValueError(
                "ShardedWaveExecutor does not support recycle_outputs "
                "(cross-wave donation of lobby-sharded buffers)"
            )
        super().__init__(app, k_max, unroll=unroll,
                         fused_checksums=fused_checksums)
        self.mesh = mesh
        self.n_devices = int(mesh.devices.size)
        self._owner = "sharded"
        from .. import telemetry

        _reg = telemetry.registry()
        self._m_sharded_dispatches = _reg.bind_counter(
            "sharded_wave_dispatches_total",
            "wave dispatches through the lobby-sharded executor",
        )
        self._m_shard_compiles = _reg.bind_counter(
            "shard_program_compiles_total",
            "lobby-sharded wave programs built (kind x bucket)",
        )
        self._trim_fns: Dict[Tuple[int, int, int], object] = {}
        # staging commits land lobby-axis-sharded so the shard_map programs
        # read device-local rows with no reshard (utils/staging.py)
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as _P

        from ..parallel.mesh import LOBBY_AXIS

        self._stage_sharding = NamedSharding(mesh, _P(LOBBY_AXIS))

    def _commit_sharded(self, arr):
        """Synchronous lobby-sharded upload of a (reused) staging buffer —
        same rewrite-race rationale as the parent's plain commits."""
        from ..utils.staging import commit

        return commit(arr, self._stage_sharding)

    def pad_lobbies(self, m: int) -> int:
        """Smallest multiple of the device count >= ``m``."""
        d = self.n_devices
        return -(-m // d) * d

    def _get_fn(self, kind: str, bucket: int):
        fn = self._fns.get((kind, bucket))
        if fn is None:
            if kind == "exact":
                fn = make_sharded_exact_fn(
                    self.app, bucket, self.mesh, unroll=self.unroll,
                    fused_checksums=self.fused_checksums,
                )
            elif kind == "padded":
                fn = make_sharded_padded_fn(
                    self.app, bucket, self.mesh, unroll=self.unroll,
                    fused_checksums=self.fused_checksums,
                )
            elif kind in ("packed_exact", "packed_exact_load"):
                fn = make_sharded_packed_exact_fn(
                    self.app, bucket, self.mesh, unroll=self.unroll,
                    fused_checksums=self.fused_checksums,
                )
            elif kind in ("packed_padded", "packed_padded_load"):
                fn = make_sharded_packed_padded_fn(
                    self.app, bucket, self.mesh, unroll=self.unroll,
                    fused_checksums=self.fused_checksums,
                )
            else:  # pragma: no cover - parent never asks for *_recycle here
                raise ValueError(f"sharded executor has no {kind!r} programs")
            self._fns[(kind, bucket)] = fn
            self.compile_count += 1
            self._m_compiles.inc()
            self._m_shard_compiles.inc()
        return fn

    def run_wave(self, worlds, inputs, status, starts, ks):
        """Dispatch one lobby-sharded wave (same contract as the parent:
        returns ``(bucket, finals, stacked, checks_flat)`` with
        ``checks_flat`` rows at ``b * bucket + i`` over the CALLER's M
        lobbies).  Pads M to a device-count multiple when needed; padded
        lanes ride the masked program at ``n_real = 0`` and are trimmed
        from the outputs before returning."""
        ks = list(ks)
        m = len(ks)
        m_pad = self.pad_lobbies(m)
        pad = m_pad - m
        if pad:
            worlds = _pad_rows(worlds, pad)
            inputs = np.concatenate(
                [inputs, np.broadcast_to(inputs[-1:], (pad, *inputs.shape[1:]))]
            )
            status = np.concatenate(
                [status, np.broadcast_to(status[-1:], (pad, *status.shape[1:]))]
            )
            starts = np.concatenate(
                [np.asarray(starts, np.int32), np.zeros((pad,), np.int32)]
            )
            ks = ks + [0] * pad
        k_hot = max(ks)
        if k_hot <= 0:
            raise ValueError("run_wave needs at least one advancing lobby")
        bucket = self.bucket_for(k_hot)
        exact = all(k == bucket for k in ks)
        inp = self._commit_sharded(np.ascontiguousarray(inputs[:, :bucket]))
        st = self._commit_sharded(np.ascontiguousarray(status[:, :bucket]))
        starts = self._commit_sharded(np.asarray(starts, np.int32))
        self.dispatch_count += 1
        self.bucket_hist[bucket] += 1
        self._m_dispatches.inc()
        self._m_sharded_dispatches.inc()
        if exact:
            self._note_uploads(3)
            finals, stacked, checks = self._dispatch(
                "exact", bucket, worlds, inp, st, starts
            )
        else:
            self._note_uploads(4)
            n_real = np.asarray(ks, np.int32)
            finals, stacked, checks = self._dispatch(
                "padded", bucket, worlds, inp, st, starts, n_real
            )
        if pad:
            finals, stacked, checks = self._trim_wave(
                finals, stacked, checks, m, m_pad, bucket
            )
        return bucket, finals, stacked, checks

    def run_wave_packed(self, worlds, packed, ks):
        """Packed single-upload sharded wave (same contract as the parent's
        :meth:`run_wave_packed`).  Padded lobby lanes get a zeroed prefix
        (``n_real = 0``) so the masked program passes them through — the
        pad block is built host-side, so the wave still costs ONE upload.
        A source named by :meth:`stage_loads` is split by the same lobby
        spec as the worlds."""
        src, self._load_src = self._load_src, None
        ks = list(ks)
        m = len(ks)
        m_pad = self.pad_lobbies(m)
        pad = m_pad - m
        if pad:
            from .packing import pack_prefix

            worlds = _pad_rows(worlds, pad)
            if src is not None:
                src = _pad_rows(src, pad)
            pad_block = np.repeat(packed[-1:], pad, axis=0)
            for r in range(pad):
                pack_prefix(pad_block[r], 0, 0)
            packed = np.concatenate([packed, pad_block])
            ks = ks + [0] * pad
        k_hot = max(ks)
        if k_hot <= 0:
            raise ValueError("run_wave needs at least one advancing lobby")
        bucket = self.bucket_for(k_hot)
        exact = all(k == bucket for k in ks)
        pk = self._commit_sharded(np.ascontiguousarray(packed[:, :bucket + 1]))
        self.dispatch_count += 1
        self.bucket_hist[bucket] += 1
        self._m_dispatches.inc()
        self._m_sharded_dispatches.inc()
        self._note_uploads(1, pk)
        if src is not None:
            finals, stacked, checks = self._dispatch(
                "packed_exact_load" if exact else "packed_padded_load",
                bucket, worlds, pk, src, variant=_src_variant(worlds, src),
            )
        elif exact:
            finals, stacked, checks = self._dispatch(
                "packed_exact", bucket, worlds, pk
            )
        else:
            finals, stacked, checks = self._dispatch(
                "packed_padded", bucket, worlds, pk
            )
        if pad:
            finals, stacked, checks = self._trim_wave(
                finals, stacked, checks, m, m_pad, bucket
            )
        return bucket, finals, stacked, checks

    def _trim_wave(self, finals, stacked, checks, m, m_pad, bucket):
        """Drop the padded lobby rows from a wave's outputs (ONE jitted
        dispatch for the whole triple, compiled per (m, m_pad, bucket))."""
        fn = self._trim_fns.get((m, m_pad, bucket))
        if fn is None:

            def body(fin, stk, chk):
                fin = jax.tree.map(lambda a: a[:m], fin)
                stk = jax.tree.map(lambda a: a[:m], stk)
                chk = chk.reshape(m_pad, bucket, 2)[:m].reshape(-1, 2)
                return fin, stk, chk

            fn = self._trim_fns[(m, m_pad, bucket)] = jax.jit(body)
        return fn(finals, stacked, checks)

    def harvest_shards(self, outputs) -> dict:
        """Block until a wave's outputs have retired on EVERY device and
        report the per-shard layout: device count, lanes per device, and
        per-device buffer residency.  This is the sharded bench stage's
        per-device metrics probe — an allowlisted hot-loop purity flush
        point (scripts/lint_imports.py): never call it from the steady-state
        dispatch path."""
        jax.block_until_ready(outputs)
        leaves = jax.tree.leaves(outputs)
        per_dev: Dict[str, int] = {}
        for leaf in leaves:
            shards = getattr(leaf, "addressable_shards", None)
            if not shards:
                continue
            for s in shards:
                key = str(s.device)
                per_dev[key] = per_dev.get(key, 0) + 1
        return {
            "n_devices": self.n_devices,
            "devices_touched": len(per_dev),
            "buffers_per_device": per_dev,
        }

    def stats(self) -> dict:
        """Parent counters plus ``shard_devices`` (mesh size)."""
        out = super().stats()
        out["shard_devices"] = self.n_devices
        return out


def _src_variant(worlds, src) -> str:
    """Compile-accounting name of a load source's shape: ``_src<k'>`` for a
    stacked ``[M, k', ...]`` wave output, ``_src0`` for a world-shaped
    ``[M, ...]`` one (each is its own trace of the load-carrying program)."""
    w, s = jax.tree.leaves(worlds)[0], jax.tree.leaves(src)[0]
    return f"_src{s.shape[1]}" if s.ndim > w.ndim else "_src0"


_pad_rows_jits: Dict[int, object] = {}


def _pad_rows(tree, pad: int):
    """Extend every leaf's leading (lobby) axis by ``pad`` rows repeating
    row 0 (ONE jitted dispatch, compiled per pad count x tree shape).  The
    pad lanes only ever run masked (``n_real = 0``) so their content is
    irrelevant — repeating a real row keeps the arithmetic finite."""
    fn = _pad_rows_jits.get(pad)
    if fn is None:

        def body(t):
            return jax.tree.map(
                lambda a: jnp.concatenate(
                    [a, jnp.broadcast_to(a[:1], (pad, *a.shape[1:]))]
                ),
                t,
            )

        fn = _pad_rows_jits[pad] = jax.jit(body)
    return fn(tree)


class DraftWaveScheduler:
    """Assign speculative draft branches to the wave lanes the active bucket
    left idle (BatchedRunner speculation — docs/architecture.md "Speculative
    rollback servicing").

    The batched tick's run wave only occupies the lanes of lobbies that
    advanced this tick with ``ks[b] > 0``; the rest of the ``[M, ...]``
    dispatch is dead weight.  ``plan()`` fills exactly those idle lanes with
    candidate branches — round-robin across the drafting lobbies so one
    lobby's wide candidate fan cannot starve the rest — and NEVER touches an
    active lane, so the draft wave's lane census is disjoint from the real
    wave's by construction.  Candidates that do not fit this tick are
    dropped (counted in ``dropped_candidates``), not queued: a stale draft
    for a frame the session has moved past can never be looked up again."""

    def __init__(self, m_pad: int):
        self.m_pad = int(m_pad)
        self.waves_planned = 0
        self.lanes_filled = 0
        self.dropped_candidates = 0

    def plan(
        self, idle_lanes: List[int], wants: List[Tuple[int, int]]
    ) -> List[Tuple[int, int, int]]:
        """``wants`` is ``[(lobby, n_candidates)]``; returns assignments
        ``[(lobby, candidate_index, lane)]`` using at most the given idle
        lanes."""
        lanes = list(idle_lanes)
        queues = [[b, 0, n] for b, n in wants if n > 0]  # lobby, next, total
        out: List[Tuple[int, int, int]] = []
        qi = 0
        while lanes and queues:
            if qi >= len(queues):
                qi = 0
            b, nxt, total = queues[qi]
            out.append((b, nxt, lanes.pop(0)))
            queues[qi][1] = nxt + 1
            if nxt + 1 >= total:
                queues.pop(qi)
            else:
                qi += 1
        self.waves_planned += 1
        self.lanes_filled += len(out)
        self.dropped_candidates += sum(t - n for _b, n, t in queues)
        return out
