"""Lazy device->host views — the driver's transfer-amortization layer.

The reference hands checksums to the session as plain integers because its
whole pipeline is host-side (/root/reference/src/schedule_systems.rs:223-237).
On TPU the checksum lives on device, and every blocking device->host pull
costs a fixed round-trip whatever its payload, while an async dispatch costs
far less; a second read of an already-pulled array is a cached host read
(jax caches the host copy per-Array).  (The round-1..5 measurements behind
this, 2026, on a since-retired remote-attached setup, are in
docs/tpu_notes.md §3.)

Consequences, and the design here:

- :class:`BatchChecks` wraps one dispatch's stacked ``uint32[k, 2]`` checksum
  output and registers itself in a process-wide pending set.  Forcing ANY
  instance pulls EVERY pending instance in a single ``jax.device_get`` call —
  so the fixed round-trip cost is paid once per *pull*, not once per frame.
- :class:`ChecksumRef` is a light (batch, row) handle used wherever the
  driver used to hold a per-frame device checksum; ``to_int()`` is the lazy
  provider the session protocols consume.
- :class:`LazySlice` defers ``stacked[i]`` materialization of per-frame saved
  states: the snapshot ring stores (stacked-buffer, index) handles and only
  issues the slicing dispatches for the one frame a rollback actually loads.
- :class:`ReadbackQueue` (the pipelined tick engine's harvest side) starts a
  NON-blocking device->host copy per checksum batch at dispatch time
  (``jax.Array.copy_to_host_async``) and collects landed values on later
  ticks (``is_ready`` + cached host read) — so the per-frame ``send_checksum``
  path never blocks on the device.  Blocking pulls still exist, but only at
  flush points (``finish()``, ``set_session``, forensics) and as a GC-horizon
  backstop; each one is counted as a *forced* readback.

All of this is also correct (and nearly free) on CPU, where device_get is a
memcpy.
"""

from __future__ import annotations

import time
import weakref
from typing import Optional

import numpy as np

# Process-wide async-readback accounting.  Plain always-on counters (bench and
# tests read these without enabling telemetry); mirrored into the telemetry
# registry when it is enabled.
_stats = {"harvested": 0, "forced": 0, "blocked_seconds": 0.0}

# On the CPU backend device buffers ARE host memory: the staged
# copy_to_host_async is pure dispatch-path overhead (a profiler-wrapped jax
# call, ~60us/tick at small N) and np.asarray on a ready array is zero-copy,
# so the harvest path only needs is_ready().  Real accelerators keep the
# staged copy — that is what makes the later read non-blocking there.
_skip_staged_copy: Optional[bool] = None


def _staged_copy_needed() -> bool:
    global _skip_staged_copy
    if _skip_staged_copy is None:
        try:
            import jax
            _skip_staged_copy = jax.devices()[0].platform == "cpu"
        except Exception:  # pragma: no cover - no jax in stub-only tests
            _skip_staged_copy = True
    return not _skip_staged_copy


def readback_stats() -> dict:
    """Snapshot of {harvested, forced, blocked_seconds} since process start."""
    return dict(_stats)


def _note_readback(harvested: int = 0, forced: int = 0,
                   blocked_s: float = 0.0) -> None:
    _stats["harvested"] += harvested
    _stats["forced"] += forced
    _stats["blocked_seconds"] += blocked_s
    from .. import telemetry

    if harvested:
        telemetry.count("readback_harvested_total", harvested,
                        help="checksum readbacks collected without blocking "
                             "(async copy had landed)")
    if forced:
        telemetry.count("readback_forced_total", forced,
                        help="checksum readbacks that blocked the host "
                             "(flush points / sync mode)")
        # always-on black box: forced pulls are the pipeline's degrade
        # signal, so they earn a flight-ring entry even with telemetry off
        telemetry.flight_recorder().record(
            "forced_readback", n=forced,
            blocked_ms=round(blocked_s * 1e3, 3),
        )
    if blocked_s:
        telemetry.count("host_blocked_seconds", blocked_s,
                        help="host seconds spent blocked in device->host "
                             "checksum pulls")


class BatchChecks:
    """One dispatch's stacked checksums (uint32[k, 2] on device), pulled to
    host lazily and *collectively* (all pending instances in one transfer)."""

    _pending: "weakref.WeakSet[BatchChecks]" = weakref.WeakSet()

    __slots__ = ("_dev", "_host", "_async", "__weakref__")

    def __init__(self, dev):
        self._dev = dev
        self._host: Optional[np.ndarray] = None
        self._async = False
        BatchChecks._pending.add(self)

    def start_async(self) -> None:
        """Begin the non-blocking device->host copy for this batch.

        Called at dispatch time by the pipelined runner; by the time a
        session wants the value the transfer has usually landed and
        :meth:`try_host` is a cached read.  A batch whose checksums live
        SHARDED across a device mesh (the lobby-sharded wave executor,
        ops/batch.ShardedWaveExecutor) gets one non-blocking copy PER
        SHARD — each device's block starts moving independently, and the
        later harvest assembles the host array from the per-shard copies
        without ever serializing the devices against each other.  No-op on
        objects without the jax.Array async-copy surface (host-backed test
        stubs)."""
        if self._host is not None or self._async:
            return
        if not _staged_copy_needed():
            # CPU: harvest gates on is_ready() alone; adoption is zero-copy
            self._async = True
            return
        shards = self._shards()
        if shards is not None:
            # sharded checksums: one staged copy per device shard
            for s in shards:
                copy = getattr(s.data, "copy_to_host_async", None)
                if copy is not None:
                    copy()
            self._async = True
            return
        copy = getattr(self._dev, "copy_to_host_async", None)
        if copy is not None:
            copy()
            self._async = True

    def _shards(self):
        """The batch's addressable device shards when it is split across a
        mesh (>= 2 shards), else None (the single-device fast path)."""
        shards = getattr(self._dev, "addressable_shards", None)
        if shards is not None and len(shards) > 1:
            return shards
        return None

    def _transfer_landed(self) -> bool:
        """True when reading the device value would not block (for a
        sharded batch: every shard's copy has landed)."""
        shards = self._shards()
        if shards is not None:
            for s in shards:
                ready = getattr(s.data, "is_ready", None)
                if ready is not None and not ready():
                    return False
            return True
        ready = getattr(self._dev, "is_ready", None)
        return bool(ready()) if ready is not None else True

    def _adopt_host(self) -> None:
        """Take the completed transfer (cached host copy / host-backed
        array) without a meaningful block, and count the harvest."""
        self._host = np.asarray(self._dev, dtype=np.uint64)
        BatchChecks._pending.discard(self)
        _note_readback(harvested=1)

    def try_host(self) -> Optional[np.ndarray]:
        """Non-blocking :meth:`host`: the uint64[k, 2] copy if it can be had
        without stalling, else None.  Starts the async copy as a side effect
        so un-started batches converge even without a pipelining runner."""
        if self._host is not None:
            return self._host
        self.start_async()
        if self._async and not self._transfer_landed():
            return None
        self._adopt_host()
        return self._host

    def host(self) -> np.ndarray:
        """uint64[k, 2] host copy; first call pulls every pending batch."""
        if self._host is None:
            BatchChecks.pull_pending()
        if self._host is None:  # defensive: a failed pull leaves us pending
            raise RuntimeError(
                "checksum batch was never pulled (a prior device->host "
                "transfer failed); retry pull_pending() once the backend "
                "is reachable"
            )
        return self._host

    def ref(self, i: int) -> "ChecksumRef":
        return ChecksumRef(self, i)

    @classmethod
    def pull_pending(cls) -> None:
        """Pull every unforced batch in one blocking ``jax.device_get``.

        ``device_get`` over a list starts every device->host copy before it
        waits on the first, so the batches share one transfer latency.  No
        program runs here: a jitted concatenation of the pending set would
        compile once per distinct (count, shapes) tuple, which is a steady-
        state recompile whenever a forced pull meets a new pending mix."""
        import jax

        from ..telemetry import phases

        pending = [b for b in cls._pending if b._host is None]
        if not pending:
            cls._pending.clear()
            return
        # Readback accounting: a pending batch whose async copy already
        # landed is a harvest (this pull won't wait on it); the rest are
        # forced (the host blocks until their dispatch completes).
        landed = sum(1 for b in pending if b._async and b._transfer_landed())
        t0 = time.perf_counter()
        # batches leave the pending set only AFTER the pull succeeds: if
        # device_get raises, every batch stays pending and the next pull
        # retries instead of orphaning them with _host=None.  The wait is
        # the running tick's readback_block phase, taken out of the phase
        # that forced it (the SyncTest comparison inside session_step).
        with phases.phase("readback_block"):
            hosts = jax.device_get([b._dev for b in pending])
            for b, h in zip(pending, hosts):
                b._host = np.asarray(h, dtype=np.uint64)
        cls._pending.clear()
        _note_readback(harvested=landed, forced=len(pending) - landed,
                       blocked_s=time.perf_counter() - t0)


class ChecksumRef:
    """Handle to row ``i`` of a :class:`BatchChecks` — the per-frame checksum."""

    __slots__ = ("_batch", "_i")

    def __init__(self, batch: BatchChecks, i: int):
        self._batch = batch
        self._i = i

    def to_int(self) -> int:
        """The 64-bit cross-peer checksum value (forces the batched pull)."""
        a = self._batch.host()[self._i]
        return int((a[0] << np.uint64(32)) | a[1])

    # A ref IS the session's checksum provider: calling it forces (the flush
    # paths), peek() is the non-blocking read the pipelined desync driver
    # retries until the async copy lands.
    __call__ = to_int

    def peek(self) -> Optional[int]:
        """Non-blocking :meth:`to_int`: the value if the batched device->host
        copy has landed, else None (starting the copy if needed)."""
        h = self._batch.try_host()
        if h is None:
            return None
        a = h[self._i]
        return int((a[0] << np.uint64(32)) | a[1])

    def device(self):
        """Lazy uint32[2] device row (a dispatch, not a transfer)."""
        return self._batch._dev[self._i]

    def __array__(self, dtype=None, copy=None):
        a = self._batch.host()[self._i]
        return np.asarray(a, dtype=dtype if dtype is not None else np.uint64)


def wrap_single_checksum(cs) -> ChecksumRef:
    """Wrap a bare uint32[2] device checksum as a 1-row batch ref."""
    return BatchChecks(cs[None]).ref(0)


class ReadbackQueue:
    """The pipelined tick engine's readback coordinator.

    ``start(batch)`` begins a non-blocking device->host copy right after a
    dispatch; ``harvest()`` (called once per runner tick, and at the top of
    the sessions' compare paths) finalizes every batch whose copy has landed
    and async-starts any stragglers that entered the pending set some other
    way (``wrap_single_checksum``, spec-cache batches).  ``flush()`` is the
    blocking everything-now path for the existing flush points.

    The :class:`BatchChecks` process-wide pending set is the queue — there is
    no second registry to leak, and one queue instance serves every runner in
    the process (the batched pull already fuses across them anyway)."""

    def start(self, batch: BatchChecks) -> None:
        batch.start_async()

    def harvest(self) -> int:
        """Finalize landed transfers; returns how many were collected."""
        if not BatchChecks._pending:
            return 0
        n = 0
        for b in list(BatchChecks._pending):
            if b._host is not None:
                BatchChecks._pending.discard(b)
                continue
            if not b._async:
                b.start_async()
                if not b._async:
                    continue  # no async surface: leave for the forced path
            if b._transfer_landed():
                b._adopt_host()
                n += 1
        from .. import telemetry

        if telemetry.enabled():
            telemetry.gauge_set("pipeline_depth", float(self.depth()),
                                help="checksum dispatches in flight "
                                     "(async readbacks not yet landed)")
        return n

    def depth(self) -> int:
        """Batches still in flight (pending and unharvested)."""
        return sum(1 for b in BatchChecks._pending if b._host is None)

    def flush(self) -> None:
        """Blocking pull of everything still pending (flush points only;
        counted as forced readbacks unless the copies already landed)."""
        BatchChecks.pull_pending()


_readback_queue: Optional[ReadbackQueue] = None


def readback_queue() -> ReadbackQueue:
    """The process-wide :class:`ReadbackQueue` singleton."""
    global _readback_queue
    if _readback_queue is None:
        _readback_queue = ReadbackQueue()
    return _readback_queue


class LazySlice:
    """Deferred ``tree.map(a[i])`` over a stacked resim output — the ring
    stores these so per-frame save slicing never dispatches unless loaded.

    ``i`` may also be an ``(outer, inner)`` pair for doubly-stacked buffers
    (the BatchedRunner's ``[lobby, frame, ...]`` dispatch outputs)."""

    __slots__ = ("_stacked", "_i")

    def __init__(self, stacked, i):
        self._stacked = stacked
        self._i = i

    def materialize(self):
        """Slice the frame out of the stacked buffer (ONE jitted dispatch);
        the result no longer pins the parent buffer."""
        if isinstance(self._i, tuple):
            return tree_index2(self._stacked, *self._i)
        return tree_index(self._stacked, self._i)


def materialize(obj):
    """LazySlice -> concrete pytree; anything else passes through."""
    return obj.materialize() if isinstance(obj, LazySlice) else obj


def tree_index(stacked, i: int):
    """``tree.map(a[i])`` as ONE jitted dispatch.

    Eager per-leaf indexing costs one device op round-trip per leaf; the
    jitted dynamic-index program slices every
    leaf in a single dispatch."""
    import jax

    global _tree_index_jit
    if _tree_index_jit is None:
        _tree_index_jit = jax.jit(
            lambda t, j: jax.tree.map(lambda a: a[j], t)
        )
    return _tree_index_jit(stacked, np.int32(i))


_tree_index_jit = None


def plan_row_gather(handles):
    """Group ``(target_row, snapshot)`` pairs by backing stacked buffer for
    one fused gather — the planning half of the BatchedRunner's mixed-source
    load/save paths.

    Each :class:`LazySlice` names a row of some stacked dispatch output —
    ``stacked[i]`` or ``stacked[b, i]``.  A wave where different lobbies load
    from DIFFERENT buffers (staggered rollbacks, partially-idle lobbies) used
    to fall back to one gather + one scatter dispatch per lobby; grouping the
    handles by ``id(buffer)`` turns the whole wave into one jitted program
    over a handful of source buffers (:func:`fused_load_rows` /
    :func:`fused_gather_rows`).

    Returns ``(groups, fallback)``: ``groups`` is a list of
    ``(buffer, lanes_i32[n], idxs_i32[n] | None, targets_i32[n])`` in
    first-seen order (deterministic given the handle order, which keeps the
    jit cache warm across ticks with the same wave shape); ``fallback``
    collects non-LazySlice snapshots for the caller's slow path."""
    by = {}
    order = []
    fallback = []
    for tgt, stored in handles:
        if not isinstance(stored, LazySlice):
            fallback.append((tgt, stored))
            continue
        if isinstance(stored._i, tuple):
            lane, idx = stored._i
        else:
            lane, idx = stored._i, None
        key = (id(stored._stacked), idx is None)
        g = by.get(key)
        if g is None:
            g = by[key] = (stored._stacked, [], [], [])
            order.append(key)
        g[1].append(lane)
        g[2].append(idx)
        g[3].append(tgt)
    groups = []
    for key in order:
        buf, lanes, idxs, tgts = by[key]
        groups.append((
            buf,
            np.asarray(lanes, np.int32),
            None if key[1] else np.asarray(idxs, np.int32),
            np.asarray(tgts, np.int32),
        ))
    return groups, fallback


_fused_load_jits: dict = {}


def _gather_group_rows(buf, lanes, idxs):
    import jax

    if idxs is None:
        return jax.tree.map(lambda a: a[lanes], buf)
    return jax.tree.map(lambda a: a[lanes, idxs], buf)


def fused_load_rows(worlds, groups, transform=None):
    """ONE jitted dispatch: gather rows out of several stacked source
    buffers and scatter them into the resident ``[M, ...]`` worlds at
    ``targets`` — the mixed-source batched load.

    ``groups`` comes from :func:`plan_row_gather`.  ``transform`` (optional)
    is vmapped over the gathered rows before the scatter — the non-identity
    snapshot strategies' ``load_state`` hook, fused into the same program.
    The jitted body is cached per ``transform`` object (hold a stable
    reference!) and re-traced by jax per group structure/shape, so
    steady-state wave shapes hit the cache."""
    import jax

    fn = _fused_load_jits.get(transform)
    if fn is None:

        def body(worlds, groups):
            for buf, lanes, idxs, targets in groups:
                rows = _gather_group_rows(buf, lanes, idxs)
                if transform is not None:
                    rows = jax.vmap(transform)(rows)
                worlds = jax.tree.map(
                    lambda w, r: w.at[targets].set(r), worlds, rows
                )
            return worlds

        fn = _fused_load_jits[transform] = jax.jit(body)
    return fn(worlds, tuple(groups))


_fused_gather_jits: dict = {}


def fused_gather_rows(groups, transform=None):
    """ONE jitted dispatch: gather rows from several stacked buffers into a
    fresh ``[n, ...]`` stack (group-concatenation order), optionally mapping
    ``transform`` over the rows (vmapped).

    The BatchedRunner's non-identity save path uses this to run
    ``store_state`` over every saved row of a wave in one dispatch instead
    of a per-lobby materialize loop; row ``j`` of the result backs a
    ``LazySlice(result, j)`` ring entry.  Output row order follows the
    groups' target arrays concatenated in order — callers map their logical
    indices through that permutation host-side (no device permute)."""
    import jax

    fn = _fused_gather_jits.get(transform)
    if fn is None:

        def body(groups):
            parts = [
                _gather_group_rows(buf, lanes, idxs)
                for buf, lanes, idxs, _t in groups
            ]
            if len(parts) == 1:
                rows = parts[0]
            else:
                import jax.numpy as jnp

                rows = jax.tree.map(
                    lambda *xs: jnp.concatenate(xs, axis=0), *parts
                )
            if transform is not None:
                rows = jax.vmap(transform)(rows)
            return rows

        fn = _fused_gather_jits[transform] = jax.jit(body)
    return fn(tuple(groups))


def tree_index2(stacked, b: int, i: int):
    """``tree.map(a[b, i])`` as ONE jitted dispatch (doubly-stacked
    ``[lobby, frame, ...]`` buffers; see :func:`tree_index`)."""
    import jax

    global _tree_index2_jit
    if _tree_index2_jit is None:
        _tree_index2_jit = jax.jit(
            lambda t, bb, ii: jax.tree.map(lambda a: a[bb, ii], t)
        )
    return _tree_index2_jit(stacked, np.int32(b), np.int32(i))


_tree_index2_jit = None
