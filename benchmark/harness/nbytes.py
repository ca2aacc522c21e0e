"""The least device bytes the simulated frames need, from registered shapes.

One registered state is every registered component column at the world's
capacity plus every registered resource.  A saved frame writes one such
state and a loaded frame reads one; nothing else is counted (no presence
masks, no allocator columns, no checksum re-reads), so the count is an
under-count by design and a share of the peak built on it cannot pass 100%.
"""

from __future__ import annotations

import math

import jax
import numpy as np


def registered_state_bytes(reg) -> int:
    """Bytes of one registered state of ``reg`` (a program Registry)."""
    total = 0
    for spec in reg.components.values():
        total += (reg.capacity * math.prod(spec.shape)
                  * np.dtype(spec.dtype).itemsize)
    for spec in reg.resources.values():
        for leaf in jax.tree.leaves(spec.init):
            total += np.asarray(leaf).nbytes
    return int(total)


def least_bytes(state_bytes: int, frames_saved: int, states_loaded: int) -> int:
    """Bytes a window needs at least: each saved frame written once and each
    loaded state read once."""
    return int(state_bytes) * (int(frames_saved) + int(states_loaded))

