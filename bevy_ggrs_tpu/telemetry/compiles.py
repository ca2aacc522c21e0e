"""Process-wide compile account: programs built, persistent-cache hits and
misses, and the seconds of jaxpr tracing, lowering and backend compile.

One ``jax.monitoring`` listener pair, installed when the package is
imported (:func:`install`), reads JAX's own compile events:

- ``/jax/core/compile/backend_compile_duration`` — one program produced.
  JAX wraps ``compile_or_get_cached`` in it, so a persistent-cache load is
  counted here too, and its retrieval seconds are in ``backend_s``;
- ``/jax/core/compile/jaxpr_trace_duration`` and
  ``jaxpr_to_mlir_module_duration`` — tracing and lowering seconds;
- ``/jax/compilation_cache/cache_hits`` and ``cache_misses`` — persistent
  cache outcomes (a miss is recorded when the fresh program is written).

:func:`compile_account` reads the totals since import; while telemetry is
enabled each event is mirrored to the ``compile_*`` registry counters and
the ``compile_cache_hit_ratio`` gauge, so the Prometheus endpoint serves
the hit rate.  ``jax.monitoring`` cannot unregister a listener, so the
compile guard's ``watch_jax`` sentinel (``utils/compile_guard.py``) rides
this one listener instead of adding its own.  Steady ticks compile
nothing, so they never reach any of this.
"""

from __future__ import annotations

from . import metrics as _metrics

_BACKEND = "/jax/core/compile/backend_compile_duration"
_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    _BACKEND: "backend",
}
_CACHE = {
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
}

_account = {"programs": 0, "cache_hits": 0, "cache_misses": 0,
            "trace_s": 0.0, "lower_s": 0.0, "backend_s": 0.0}
_installed = False


def compile_account() -> dict:
    """Totals since the package was imported: ``programs`` (backend
    compiles, persistent-cache loads included), ``cache_hits``,
    ``cache_misses``, the seconds ``trace_s``, ``lower_s`` and
    ``backend_s``, and ``cache_hit_ratio`` (None before any cache event)."""
    n = _account["cache_hits"] + _account["cache_misses"]
    return dict(_account,
                cache_hit_ratio=_account["cache_hits"] / n if n else None)


def programs_built() -> int:
    """``compile_account()["programs"]`` without building the dict: cheap
    enough to read every tick."""
    return _account["programs"]


def _on_duration(event: str, duration: float, **_kw) -> None:
    stage = _STAGES.get(event)
    if stage is None:
        return
    _account[stage + "_s"] += duration
    if stage == "backend":
        _account["programs"] += 1
    reg = _metrics.registry()
    if reg.enabled:
        reg.counter(
            "compile_seconds_total",
            "seconds of jaxpr tracing, lowering and backend compile "
            "(persistent-cache loads included in backend)",
        ).inc(duration, stage=stage)
        if stage == "backend":
            reg.counter(
                "compile_programs_total",
                "programs produced by a backend compile or a persistent-"
                "cache load",
            ).inc()
    if stage == "backend":
        # the compile guard's watch_jax sentinel (raises when armed)
        from ..utils import compile_guard

        compile_guard.on_jax_compile(event, duration)


def _on_event(event: str, **_kw) -> None:
    outcome = _CACHE.get(event)
    if outcome is None:
        return
    _account["cache_hits" if outcome == "hit" else "cache_misses"] += 1
    reg = _metrics.registry()
    if reg.enabled:
        if outcome == "hit":
            reg.counter(
                "compile_cache_hits_total",
                "programs loaded from the persistent compilation cache",
            ).inc()
        else:
            reg.counter(
                "compile_cache_misses_total",
                "programs compiled and written to the persistent "
                "compilation cache",
            ).inc()
        reg.gauge(
            "compile_cache_hit_ratio",
            "persistent compilation cache hits over hits plus misses "
            "since import",
        ).set(compile_account()["cache_hit_ratio"])


def install() -> None:
    """Register the listeners (once per process; called on import)."""
    global _installed
    if _installed:
        return
    from jax import monitoring

    monitoring.register_event_duration_secs_listener(_on_duration)
    monitoring.register_event_listener(_on_event)
    _installed = True
