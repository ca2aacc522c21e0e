"""Find a cell's parts by name: BENCHMARK.json, then files named after them.

- a configuration ``<config>`` is ``configs/<config>.json`` with its plain
  reference ``configs/<config>_ref.py`` beside it;
- a traffic mix ``<traffic>`` is ``traffic/<traffic>.json``, and names the
  driver ``drivers/<driver>.py`` that runs it;
- a metric ``<name>`` is read by ``metrics/<name>.py`` (or, for a split
  name ``<base>.<cells>``, by ``metrics/<base>.py``).  A cell reports the
  end-to-end metrics whose ``workloads`` list it (or that have none), and
  the per-layer metrics that list it or that move one of those.

Nothing is registered in code: a cell, configuration, traffic mix or
metric is added by adding its files and its entry in BENCHMARK.json.
"""

from __future__ import annotations

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    """Import the Python file ``path`` as module ``name``."""
    if not os.path.isfile(path):
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One entry of ``workloads`` with its configuration, traffic mix and
    the metrics BENCHMARK.json asks of it."""

    def __init__(self, bench: dict, name: str):
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                           f"(have {sorted(cells)})")
        self.entry = cells[name]
        self.name = name
        self.chips = int(self.entry["chips"])
        self.run_seconds = int(bench["run_seconds"])
        self.config = load_json(
            os.path.join(BENCH_DIR, "configs", self.entry["config"] + ".json"))
        self.traffic = load_json(
            os.path.join(BENCH_DIR, "traffic", self.entry["traffic"] + ".json"))
        self.end_to_end = [m for m in bench["end_to_end"] if self._has(m)]
        reported = {m["name"] for m in self.end_to_end}
        self.per_layer = [
            m for m in bench["per_layer"]
            if self._has(m) and ("workloads" in m or m["moves"] in reported)]

    def _has(self, metric: dict) -> bool:
        return "workloads" not in metric or self.name in metric["workloads"]

    def reference(self):
        name = self.entry["config"]
        return load_module(
            os.path.join(BENCH_DIR, "configs", name + "_ref.py"),
            f"bench_ref_{name}")

    def driver(self):
        name = self.traffic["driver"]
        return load_module(
            os.path.join(BENCH_DIR, "drivers", name + ".py"),
            f"bench_driver_{name}")


def load_cell(name: str) -> Cell:
    return Cell(load_json(os.path.join(ROOT, "BENCHMARK.json")), name)


def metric_reader(name: str):
    """The ``read(run)`` function of ``metrics/<name>.py``; a split name
    ``<base>.<cells>`` (one quantity reported under another name in other
    cells) falls back to ``metrics/<base>.py``."""
    path = os.path.join(BENCH_DIR, "metrics", name + ".py")
    if not os.path.isfile(path):
        path = os.path.join(BENCH_DIR, "metrics",
                            name.split(".", 1)[0] + ".py")
    return load_module(path, f"bench_metric_{name.replace('.', '_')}").read
