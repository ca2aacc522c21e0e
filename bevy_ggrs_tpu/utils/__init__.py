from .frames import (
    NULL_FRAME,
    I32_MIN,
    I32_MAX,
    wrap_i32,
    frame_add,
    frame_diff,
    frame_lt,
    frame_le,
    frame_gt,
    frame_ge,
    frame_max,
    frame_min,
)
from .tracing import trace_log

__all__ = [
    "NULL_FRAME",
    "I32_MIN",
    "I32_MAX",
    "wrap_i32",
    "frame_add",
    "frame_diff",
    "frame_lt",
    "frame_le",
    "frame_gt",
    "frame_ge",
    "frame_max",
    "frame_min",
    "trace_log",
]
