"""Seeded player inputs: one pure function of (seed, frame label, handle).

The program is fed through it and the plain reference reads the same
table, so both see the same input for every frame.  A frame label is the
frame an input is applied at (the session's current frame plus its input
delay when the input is added), and the step that computes frame ``f``
reads the inputs labelled ``f - 1``.

Labels below ``QUIET_FRAMES`` are 0 (no key pressed): a session with an
input delay of ``d`` applies the blank input to its first ``d`` frames,
and the quiet start keeps the inputs the same on both sides for any delay
below it.  Labels from there to ``toggle_frames`` alternate every frame:
set-up holds the network back on those frames, and inputs that change
every frame make every held frame a misprediction, so set-up reaches every
rollback depth.
From ``toggle_frames`` on, each handle changes its input every ``period``
frames at a seeded phase, to a seeded value of the other parity, so every
seed mispredicts equally often.
"""

from __future__ import annotations

import numpy as np

_MASK = (1 << 64) - 1
QUIET_FRAMES = 16


def mix(*words: int) -> int:
    """splitmix64 over a sequence of integers (any size, any sign)."""
    h = 0x9E3779B97F4A7C15
    for w in words:
        h = (h + (int(w) & _MASK) + 0x9E3779B97F4A7C15) & _MASK
        z = h
        z = ((z ^ (z >> 30)) * 0xBF58476D1CE4E5B9) & _MASK
        z = ((z ^ (z >> 27)) * 0x94D049BB133111EB) & _MASK
        h = z ^ (z >> 31)
    return h


class InputGen:
    """Inputs of one lobby: ``value(label, handle)`` -> uint8."""

    def __init__(self, seed: int, period: int, toggle_frames: int):
        if period < 2:
            raise ValueError("period must be at least 2 frames")
        self.seed = int(seed)
        self.period = int(period)
        self.toggle_frames = int(toggle_frames)

    def value(self, label: int, handle: int) -> np.uint8:
        if label < QUIET_FRAMES:
            return np.uint8(0)
        if label < self.toggle_frames:
            return np.uint8(1 + 8 * ((label + handle) & 1))
        phase = mix(self.seed, handle, 1) % self.period
        block = (label + phase) // self.period
        r = mix(self.seed, handle, 2, block)
        return np.uint8(((r % 8) << 1) | (block & 1))

    def table(self, n_labels: int, players: int) -> np.ndarray:
        """``uint8[n_labels, players]``: the input of every label from 0."""
        out = np.zeros((n_labels, players), np.uint8)
        for f in range(n_labels):
            for h in range(players):
                out[f, h] = self.value(f, h)
        return out
