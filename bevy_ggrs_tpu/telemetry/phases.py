"""Tick-phase latency attribution — guarded timers for the driver hot loop.

A regression in any single phase of the save→load→advance loop (input
staging, wave dispatch, checksum harvest, rollback load, store/save,
network poll, session stepping) is invisible to event counters until an
aggregate bench gate trips.  This module gives each driver a
:class:`PhaseSet`: a fixed catalog of reusable context-manager timers
(:data:`PHASES`) whose per-tick accumulations feed three sinks at tick end:

- the **flight recorder** (:mod:`.flight`, always on): one ring entry per
  tick with the phase breakdown, wall tick time and the ``unattributed_ms``
  residual — ``sum(phases) + unattributed == wall`` by construction;
- the **metrics registry** (only while telemetry is enabled): one
  ``tick_phase_ms{phase=...,owner=...}`` histogram observation per active
  phase plus ``tick_wall_ms`` / ``tick_unattributed_ms``, all on the
  log-spaced :data:`~.metrics.LATENCY_MS_BUCKETS` so
  ``telemetry.summary()["derived"]`` can report p50/p95/p99 per phase;
- **cumulative totals** on the set itself (:meth:`PhaseSet.totals`) — what
  ``bench.py``'s pipeline stage reconciles against wall time (the
  ``unattributed_ms <= 10%`` gate).

While a JAX profiler trace is being recorded, every phase also opens a
host trace event named after it (``jax.profiler.TraceAnnotation``) on the
ticking thread, so the profiler's idle gaps can be put down to the phase
the host was in.

``readback_block`` is **self time**: the host blocked on a device→host
pull (``BatchChecks.pull_pending``, the sync-mode drain).  The pull sites
sit below the drivers (``snapshot/lazy.py``), so they reach the set whose
tick is running through the module-level :func:`phase`, registered by
:meth:`PhaseSet.begin_tick`.  When the block runs inside another phase
(the deferred SyncTest comparison inside ``session_step``), its seconds
move out of that phase, and ``sum(phases) + unattributed == wall`` still
holds.

Cost discipline (a 2% tick budget): each timer is a preallocated object.
Entering it is ONE boolean check when the set is off (flight recorder,
telemetry and profiler all off).  When on, it is one assignment (the open
phase, for ``readback_block``), one boolean check (is the profiler
recording — read once per tick in :meth:`PhaseSet.begin_tick`), two
``perf_counter()`` calls and a float add; a trace event is built only
while the profiler records.  No registry traffic happens inside phases —
histogram observes are batched into ``end_tick``.  The hot-loop lint
(``scripts/lint_imports.py``) checks every ``phase("...")`` site in the
drivers names a catalog phase and sits inside a ``with`` block.
"""

from __future__ import annotations

import contextlib
import time
from typing import Optional

from jax.profiler import TraceAnnotation as _TraceMe

from . import flight as _flight
from .metrics import LATENCY_MS_BUCKETS, _label_key, registry

# The phase catalog — every hot-loop phase of the solo and batched drivers.
# scripts/lint_imports.py mirrors this set (stdlib-only, cannot import the
# package); tests/test_phases.py asserts the two stay identical.
PHASES = (
    "net_poll",          # poll_remote_clients + event drain + net stats
    "session_step",      # session advance_frame (input/ack/checksum protocol)
    "stage_inputs",      # fill the persistent host staging buffers
    "wave_dispatch",     # fused device program submission (+ readback start)
    "readback_harvest",  # collect landed async checksum copies (never blocks)
    "rollback_load",     # ring rollback + world restore
    "store_save",        # ring pushes + save-cell publication
    "readback_block",    # host blocked on a device->host pull (self time)
)
_BLOCK = PHASES.index("readback_block")
_ZEROS = [0.0] * len(PHASES)

# The set whose tick is running (registered by PhaseSet.begin_tick, cleared
# by end_tick): how pull sites below the drivers reach their tick's timers.
_ACTIVE: Optional["PhaseSet"] = None


def _quantile(sorted_vals, q: float) -> float:
    """Linear-interpolated quantile of an already-sorted list."""
    idx = q * (len(sorted_vals) - 1)
    lo = int(idx)
    hi = min(lo + 1, len(sorted_vals) - 1)
    frac = idx - lo
    return sorted_vals[lo] * (1.0 - frac) + sorted_vals[hi] * frac


def phase_breakdown(entries, qs=(0.5, 0.95, 0.99)) -> dict:
    """EXACT per-phase latency percentiles over flight-recorder ``tick``
    entries (the ``--phase-breakdown`` table of scripts/profile_tick.py and
    scripts/replay_tool.py).

    Unlike the registry histograms — which estimate percentiles from
    log-spaced buckets — the flight ring holds each tick's exact
    millisecond values, so a bounded window gets exact quantiles, and it
    works without telemetry ever having been enabled.  Returns
    ``{phase: {"p50": ..., "p95": ..., "p99": ..., "count": n}}`` in
    catalog order plus ``(wall)`` / ``(unattributed)`` rows."""
    series: dict = {}
    for e in entries:
        if e.get("kind") != "tick":
            continue
        for name, ms in e.get("phases", {}).items():
            series.setdefault(name, []).append(ms)
        series.setdefault("(wall)", []).append(e.get("wall_ms", 0.0))
        series.setdefault("(unattributed)", []).append(
            e.get("unattributed_ms", 0.0)
        )
    out = {}
    order = [*PHASES, "(unattributed)", "(wall)"]
    for name in order:
        vals = series.get(name)
        if not vals:
            continue
        vals.sort()
        row = {f"p{q * 100:g}": round(_quantile(vals, q), 4) for q in qs}
        row["count"] = len(vals)
        out[name] = row
    return out


def format_phase_table(breakdown: dict) -> str:
    """Render a :func:`phase_breakdown` dict as the aligned text table the
    profiling scripts print."""
    if not breakdown:
        return "  (no flight-recorder tick entries in the window)"
    qcols = [k for k in next(iter(breakdown.values())) if k != "count"]
    lines = [
        "  " + f"{'phase':18s} {'count':>6} "
        + " ".join(f"{q + ' ms':>10}" for q in qcols)
    ]
    for name, row in breakdown.items():
        lines.append(
            f"  {name:18s} {row['count']:>6} "
            + " ".join(f"{row[q]:>10.3f}" for q in qcols)
        )
    return "\n".join(lines)


class _Phase:
    """One reusable guarded timer: ``with ps.phase("wave_dispatch"): ...``.

    Not reentrant (each catalog phase times a single non-nested region of
    the tick).  When the owning set is off, ``__enter__`` is one boolean
    check and ``__exit__`` one ``is None`` check."""

    __slots__ = ("_ps", "_i", "_name", "_t0", "_tm")

    def __init__(self, ps: "PhaseSet", i: int):
        self._ps = ps
        self._i = i
        self._name = PHASES[i]
        self._t0: Optional[float] = None
        self._tm = None  # the profiler event while one is open

    def __enter__(self) -> "_Phase":
        ps = self._ps
        if ps._on:
            ps._open = self
            if ps._trace:
                self._tm = _TraceMe(self._name)
                self._tm.__enter__()
            self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        t0 = self._t0
        if t0 is not None:
            self._ps._acc[self._i] += time.perf_counter() - t0
            self._t0 = None
            if self._tm is not None:
                self._tm.__exit__(None, None, None)
                self._tm = None
        return False


class _SelfPhase(_Phase):
    """``readback_block``: a phase that may run inside another one and
    takes its seconds out of it (self time).  Entered at the rare pull
    sites only, so the bookkeeping of the enclosing phase is paid there."""

    __slots__ = ("_outer",)

    def __init__(self, ps: "PhaseSet", i: int):
        super().__init__(ps, i)
        self._outer: Optional[_Phase] = None

    def __enter__(self) -> "_SelfPhase":
        ps = self._ps
        if ps._on:
            outer = ps._open
            # the last phase entered is still open iff its timer runs
            self._outer = (
                outer if outer is not None and outer._t0 is not None else None
            )
            if ps._trace:
                self._tm = _TraceMe(self._name)
                self._tm.__enter__()
            self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        t0 = self._t0
        if t0 is not None:
            dt = time.perf_counter() - t0
            acc = self._ps._acc
            acc[self._i] += dt
            if self._outer is not None:
                acc[self._outer._i] -= dt
                self._outer = None
            self._t0 = None
            if self._tm is not None:
                self._tm.__exit__(None, None, None)
                self._tm = None
        return False


# what phase() hands out while no recorded tick is running
_OFF = contextlib.nullcontext()


def phase(name: str):
    """The ``name`` timer of the set whose tick is running, or a no-op
    context outside a recorded tick — for timing sites below the drivers
    (the device→host pulls of ``snapshot/lazy.py``), which cannot reach
    the runner that called them."""
    ps = _ACTIVE
    if ps is None:
        return _OFF
    return ps._timers[name]


class PhaseSet:
    """Per-driver phase accounting: timers, per-tick flush, run totals.

    One instance per runner (``owner`` labels its series: ``"solo"`` /
    ``"batched"``).  The driver calls :meth:`begin_tick` at the top of its
    update, runs phases via ``with self._phases.phase("..."):``, notes
    decisions (:meth:`note_rollback` / :meth:`note_advances`), and calls
    :meth:`end_tick` once per tick that did work."""

    def __init__(self, owner: str = "solo"):
        self.owner = owner
        self._reg = registry()
        self._acc = [0.0] * len(PHASES)
        self._timers = {
            name: (_SelfPhase if i == _BLOCK else _Phase)(self, i)
            for i, name in enumerate(PHASES)
        }
        self._on = False
        self._trace = False  # the profiler records this tick
        self._open: Optional[_Phase] = None  # the phase entered last
        self._t_tick = 0.0
        self._tick_rollbacks = 0
        self._tick_rollback_depth = 0
        self._tick_advances = 0
        # cumulative run totals (always-on; the bench reconciliation source)
        self.ticks = 0
        self.wall_seconds = 0.0
        self.attributed_seconds = 0.0
        self.unattributed_seconds = 0.0
        self.phase_seconds = {name: 0.0 for name in PHASES}
        # registry handles, re-resolved when the registry generation moves
        self._gen = -1
        self._hist = None
        self._h_wall = None
        self._h_unattr = None
        self._keys = {}
        self._owner_key = ()

    @property
    def on(self) -> bool:
        """Whether this tick is being recorded (set by :meth:`begin_tick`:
        flight recorder, telemetry or the profiler on).  Drivers gate optional
        ``end_tick(**extra)`` computations on it so the fully-disabled tick
        path stays one boolean check."""
        return self._on

    def phase(self, name: str) -> _Phase:
        """The catalog timer for ``name`` (KeyError on a non-catalog name —
        a typo here would silently grow ``unattributed_ms``)."""
        return self._timers[name]

    def begin_tick(self) -> None:
        """Arm the timers for one driver tick (refreshes the on/off gate:
        flight recorder, telemetry or the profiler on) and register this
        set as the one :func:`phase` hands out."""
        global _ACTIVE
        self._trace = _TraceMe.is_enabled()
        self._on = _flight._FLIGHT.enabled or self._reg.enabled or self._trace
        if self._on:
            _ACTIVE = self
            # time spent in timers between ticks (a pull outside any
            # recorded tick) belongs to no tick's wall
            self._acc[:] = _ZEROS
            self._t_tick = time.perf_counter()
            self._tick_rollbacks = 0
            self._tick_rollback_depth = 0
            self._tick_advances = 0

    def note_rollback(self, depth: int) -> None:
        """Count one rollback decision into this tick's flight entry."""
        if self._on:
            self._tick_rollbacks += 1
            if depth > self._tick_rollback_depth:
                self._tick_rollback_depth = depth

    def note_advances(self, n: int) -> None:
        """Count ``n`` advanced frames into this tick's flight entry."""
        if self._on:
            self._tick_advances += n

    def _rebind(self) -> None:
        reg = self._reg
        self._hist = reg.histogram(
            "tick_phase_ms",
            "per-tick milliseconds spent in each hot-loop phase",
            buckets=LATENCY_MS_BUCKETS,
        )
        self._h_wall = reg.histogram(
            "tick_wall_ms", "wall milliseconds per driver tick",
            buckets=LATENCY_MS_BUCKETS,
        )
        self._h_unattr = reg.histogram(
            "tick_unattributed_ms",
            "per-tick wall milliseconds not covered by any phase timer",
            buckets=LATENCY_MS_BUCKETS,
        )
        self._keys = {
            name: _label_key({"phase": name, "owner": self.owner})
            for name in PHASES
        }
        self._owner_key = _label_key({"owner": self.owner})
        self._gen = reg.generation

    def end_tick(self, frame: Optional[int] = None, **extra) -> None:
        """Flush one tick's accumulations: flight entry, histograms,
        cumulative totals.  ``extra`` fields ride into the flight entry
        (e.g. ``lobbies=M`` for the batched driver)."""
        global _ACTIVE
        if not self._on:
            return
        if _ACTIVE is self:
            _ACTIVE = None
        wall = time.perf_counter() - self._t_tick
        attributed = 0.0
        phases_ms = {}
        acc = self._acc
        tot = self.phase_seconds
        for i, name in enumerate(PHASES):
            v = acc[i]
            if v:
                attributed += v
                tot[name] += v
                phases_ms[name] = round(v * 1e3, 4)
                acc[i] = 0.0
        unattr = max(wall - attributed, 0.0)
        self.ticks += 1
        self.wall_seconds += wall
        self.attributed_seconds += attributed
        self.unattributed_seconds += unattr
        fr = _flight._FLIGHT
        if fr.enabled:
            fr.record(
                "tick", owner=self.owner, frame=frame,
                wall_ms=round(wall * 1e3, 4), phases=phases_ms,
                unattributed_ms=round(unattr * 1e3, 4),
                rollbacks=self._tick_rollbacks,
                rollback_depth=self._tick_rollback_depth,
                advances=self._tick_advances, **extra,
            )
        reg = self._reg
        if reg.enabled:
            if self._gen != reg.generation:
                self._rebind()
            keys = self._keys
            hist = self._hist
            for name, ms in phases_ms.items():
                hist.observe_key(keys[name], ms)
            self._h_wall.observe_key(self._owner_key, wall * 1e3)
            self._h_unattr.observe_key(self._owner_key, unattr * 1e3)

    def totals(self) -> dict:
        """Cumulative attribution since construction: per-phase seconds,
        wall/attributed/unattributed seconds, tick count, and the
        ``unattributed_pct`` the pipeline bench stage gates on."""
        return {
            "owner": self.owner,
            "ticks": self.ticks,
            "wall_seconds": round(self.wall_seconds, 6),
            "attributed_seconds": round(self.attributed_seconds, 6),
            "unattributed_seconds": round(self.unattributed_seconds, 6),
            "unattributed_pct": round(
                100.0 * self.unattributed_seconds / self.wall_seconds, 2
            ) if self.wall_seconds else 0.0,
            "phase_seconds": {
                k: round(v, 6) for k, v in self.phase_seconds.items() if v
            },
        }
