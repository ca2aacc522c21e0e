"""Steady-state recompile sentinel — the runtime twin of the BGT07x lints.

Every hot-path guarantee the engine ships (1+1 upload/dispatch census,
O(1) speculative servicing, bit-exact migration) silently assumes XLA
programs stay *cached*: one stray recompile is a 10-50ms cliff in the
middle of a 60Hz tick.  The BGT070/BGT071 static rules catch the hazards
a parser can prove (per-call-varying ``static_argnums``, data-dependent
shapes); this module catches the rest at runtime.

Usage mirrors the ``BGT_SANITIZE`` transfer sanitizer:

* ``BGT_COMPILE_GUARD=1`` (or :func:`set_compile_guard`) enables the
  guard process-wide; it starts **disarmed** so warmup compiles pass.
* After warmup, call :meth:`GgrsRunner.arm_compile_guard` /
  :meth:`BatchedRunner.arm_compile_guard` (or :meth:`CompileGuard.arm`
  directly).  From that point ANY program compile observed by the
  engine's compile-accounting sites (``runner._note_compile``, the wave
  executor's first-dispatch timer) increments
  ``recompiles_steady_total{owner}`` and raises :class:`RecompileError`
  naming the owner and program kind — the same sites that already emit
  the ``compile`` flight instant and ``program_compile_ms`` histogram,
  so armed runs add no parallel metric names for warmup compiles.
* ``arm(watch_jax=True)`` additionally trips on every backend compile
  the telemetry compile account's ``jax.monitoring`` listener sees
  (``telemetry/compiles.py``), so compiles *outside* the hooked sites
  (a stray ``jax.jit`` in user code — exactly what BGT070 flags
  statically) trip the guard too.

Disabled (the default), the whole feature is one module-global load and
attribute check per *compile event* — steady-state ticks never reach the
hook at all, same budget discipline as the transfer sanitizer.
"""

from __future__ import annotations

import os
from typing import List, Tuple

from .. import telemetry

_ENV = "BGT_COMPILE_GUARD"

_HELP = (
    "program compiles observed after the BGT_COMPILE_GUARD sentinel was "
    "armed (steady state; a healthy run stays at 0)"
)


class RecompileError(RuntimeError):
    """A program compiled while the guard was armed (steady state)."""

    def __init__(self, owner: str, kind: str, ms: float = 0.0):
        self.owner = owner
        self.kind = kind
        self.ms = ms
        super().__init__(
            f"steady-state recompile: owner={owner!r} kind={kind!r} "
            f"({ms:.1f}ms) — a post-warmup compile means a cache-key or "
            "shape-stability hazard (see BGT070/BGT071 in "
            "docs/static-analysis.md); every such compile is a frame-time "
            "cliff the tick budget cannot absorb"
        )


class CompileGuard:
    """Post-warmup compile sentinel (module singleton; see :func:`guard`)."""

    __slots__ = ("enabled", "armed", "watch_jax", "steady_compiles")

    def __init__(self, enabled: bool = None):
        if enabled is None:
            enabled = os.environ.get(_ENV, "0") not in ("", "0", "false")
        self.enabled = bool(enabled)
        self.armed = False
        self.watch_jax = False
        # (owner, kind, ms) of every armed-state compile observed —
        # retained even though _trip raises, for post-mortem asserts
        self.steady_compiles: List[Tuple[str, str, float]] = []

    def arm(self, watch_jax: bool = False) -> bool:
        """Declare warmup over.  No-op (returns False) unless the guard
        is enabled, so engine code may call this unconditionally.

        ``watch_jax=True`` also trips on compiles the engine's own
        accounting never sees (raw ``jax.jit`` in user code), via the
        compile account's backend-compile listener (:func:`on_jax_compile`)."""
        if not self.enabled:
            return False
        self.armed = True
        if watch_jax:
            self.watch_jax = True
        return True

    def disarm(self) -> None:
        """Back to warmup: compiles pass again (and ``watch_jax`` ends)."""
        self.armed = False
        self.watch_jax = False

    def notify(self, owner: str, kind: str, ms: float = 0.0) -> None:
        """Hook for compile-accounting sites; raises when armed."""
        if self.armed:
            self._trip(owner, str(kind), ms)

    def _trip(self, owner: str, kind: str, ms: float) -> None:
        self.steady_compiles.append((owner, kind, ms))
        telemetry.count("recompiles_steady_total", help=_HELP, owner=owner)
        raise RecompileError(owner, kind, ms)


_GUARD = CompileGuard()


def on_jax_compile(event: str, duration: float) -> None:
    """Backend-compile hook of the process's one ``jax.monitoring``
    listener (``telemetry/compiles.py``; listeners cannot be unregistered,
    so the guard defers to the current singleton's state)."""
    g = _GUARD
    if g.armed and g.watch_jax:
        g._trip("jax", event, duration * 1e3)


def guard() -> CompileGuard:
    """The process-wide guard (the instance engine hooks consult)."""
    return _GUARD


def set_compile_guard(enabled: bool) -> CompileGuard:
    """Swap in a fresh guard (tests/bench): enabled as given, disarmed,
    empty history.  Returns the new singleton."""
    global _GUARD
    _GUARD = CompileGuard(enabled=enabled)
    return _GUARD


def notify(owner: str, kind: str, ms: float = 0.0) -> None:
    """Module-level fast path for engine hooks: one global load plus one
    attribute check when disarmed (<1.5us, benched in stage_uploads)."""
    g = _GUARD
    if g.armed:
        g._trip(owner, str(kind), ms)
