"""Reduce a profiler trace of the measured window to numbers.

``load_events`` reads the ``.xplane.pb`` the JAX profiler writes and keeps
what the reduction needs, in nanoseconds on the trace's one clock:

- per device plane (``/device:TPU:<n>``): the ``XLA Ops`` line (the
  device's compute stream) and the ``XLA Modules`` line (one event per
  program run, named ``jit_<function>(<fingerprint>)``);
- the host thread line that holds the benchmark's annotations
  (``window``, ``tick``, ``net_deliver``, ``input_gen``) and JAX's own
  host events (dispatch, transfers) nested inside them.

``reduce`` clips everything to the ``window`` annotation and gives, per
device, the busy seconds (union of op intervals), the seconds of each
program by function name, and the idle gaps attributed to the innermost
host event that covered each gap's midpoint.
"""

from __future__ import annotations

import bisect
import glob
import os


def load_events(trace_dir: str) -> dict:
    """Events of the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(max(paths, key=os.path.getmtime))
    out = {"devices": {}, "host": []}
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CUSTOM" not in plane.name:
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(line.name)
                if key:
                    dev[key] = [(e.name, e.start_ns, e.end_ns)
                                for e in line.events]
            out["devices"][plane.name] = dev
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                events = [(e.name, e.start_ns, e.end_ns) for e in line.events]
                if any(n == "window" for n, _, _ in events):
                    out["host"] = events
    return out


def module_name(event_name: str) -> str:
    """``jit_core(9125...)`` -> ``jit_core``."""
    return event_name.split("(", 1)[0]


def op_name(event_name: str) -> str:
    """``%fusion.139 = (u32[16]...) fusion(...)`` -> ``fusion.139``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def _clip(items, t0, t1):
    for name, s, e in items:
        s, e = max(s, t0), min(e, t1)
        if e > s:
            yield name, s, e


def union(intervals):
    """Merged, sorted ``[(start, end)]`` of possibly overlapping intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def self_times(ops):
    """Each op's duration less the ops nested in it (a ``while`` op spans
    the ops of its body on the same line), in the order of ``ops``."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    own = [e - s for _, s, e in ops]
    stack = []
    for i in order:
        _, s, e = ops[i]
        while stack and ops[stack[-1]][2] <= s:
            stack.pop()
        if stack and e <= ops[stack[-1]][2]:
            own[stack[-1]] -= e - s
        stack.append(i)
    return own


def window_of(ev: dict):
    """The extent of the ``window`` annotation around the measured loop."""
    spans = [(s, e) for n, s, e in ev["host"] if n == "window"]
    if not spans:
        raise ValueError("trace holds no window annotation")
    return min(s for s, _ in spans), max(e for _, e in spans)


class Innermost:
    """Innermost host event covering a time: the latest-starting event on
    the (properly nested) host thread that has not ended yet."""

    def __init__(self, host, t0, t1):
        self.events = sorted(_clip(host, t0, t1), key=lambda x: x[1])
        self.starts = [s for _, s, _ in self.events]

    def at(self, t) -> str:
        i = bisect.bisect_right(self.starts, t) - 1
        for j in range(i, max(i - 5000, -1), -1):
            name, s, e = self.events[j]
            if e >= t:
                return name
        return "(no host event)"


def reduce(ev: dict) -> dict:
    """``{"window_s", "devices": {plane: {"busy_s", "modules_s"}},
    "idle_gaps": [[label, s]...], "device_ops": [[name, s]...]}``;
    idle gaps and ops are summed over devices, largest first."""
    t0, t1 = window_of(ev)
    host = Innermost(ev["host"], t0, t1)
    devices, gaps, ops_s = {}, {}, {}
    for plane, dev in sorted(ev["devices"].items()):
        ops = list(_clip(dev["ops"], t0, t1))
        busy = union((s, e) for _, s, e in ops)
        mods = sorted(_clip(dev["modules"], t0, t1), key=lambda x: x[1])
        modules_s = {}
        for name, s, e in mods:
            m = module_name(name)
            modules_s[m] = modules_s.get(m, 0.0) + (e - s) * 1e-9
        mstart = [s for _, s, _ in mods]
        for (name, s, e), own in zip(ops, self_times(ops)):
            i = bisect.bisect_right(mstart, s) - 1
            mod = module_name(mods[i][0]) if i >= 0 and mods[i][2] >= s else "?"
            key = f"{mod}/{op_name(name)}"
            ops_s[key] = ops_s.get(key, 0.0) + own * 1e-9
        prev = t0
        for s, e in busy + [(t1, t1)]:
            if s > prev:
                label = host.at((prev + s) / 2)
                gaps[label] = gaps.get(label, 0.0) + (s - prev) * 1e-9
            prev = max(prev, e)
        devices[plane] = {
            "busy_s": sum(e - s for s, e in busy) * 1e-9,
            "modules_s": modules_s,
        }
    top = lambda d: [[k, v] for k, v in
                     sorted(d.items(), key=lambda kv: -kv[1])[:10]]
    return {"window_s": (t1 - t0) * 1e-9, "devices": devices,
            "idle_gaps": top(gaps), "device_ops": top(ops_s)}
