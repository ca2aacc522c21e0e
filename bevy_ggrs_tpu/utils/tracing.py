"""Debug logging for the host-side driver.

Tick phases are timed by :mod:`..telemetry.phases` (flight recorder,
histograms, and host events on the JAX profiler's trace); the JAX profiler
covers the device side (``jax.profiler.trace``).
"""

from __future__ import annotations

import logging

logger = logging.getLogger("bevy_ggrs_tpu")


def trace_log(msg: str, *args) -> None:
    """Debug-level log line on the framework logger."""
    logger.debug(msg, *args)
