"""Analysis configuration — the repo's allowlists and scopes in one place.

Fixture tests construct a :class:`Config` pointing at the corpus under
``tests/lint_fixtures/`` instead of the real drivers; everything else uses
the defaults below.  The allowlists themselves are meta-linted (``BGT012``:
every allowlisted function must still exist in its target file) so they
cannot rot silently when drivers are refactored.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Set, Tuple

# -- hot-loop purity ---------------------------------------------------------
# file (repo-relative posix suffix) -> functions allowed to force
# device->host reads.  These are the sanctioned flush funnels: calling one
# from hot-loop code is fine (that is their job); forcing *syntax* anywhere
# else in these files — or reaching a forcing helper through a call chain
# (BGT011) — is not.
PURITY_ALLOW: Dict[str, Set[str]] = {
    "bevy_ggrs_tpu/runner.py": {
        "checksum",               # user-facing flush point (property)
        "read_components",        # render readback (drains first)
        "_drain_inflight",        # THE blocking point the others share
        "_flush_session_checks",  # finish()/set_session flush
    },
    "bevy_ggrs_tpu/batch_runner.py": {
        "lobby_checksum",         # user-facing flush point
        "finish",                 # end-of-run flush
    },
    "bevy_ggrs_tpu/ops/batch.py": {
        "harvest_shards",         # per-device metrics probe (bench/dryrun
                                  # only — never called from the tick path)
    },
    "bevy_ggrs_tpu/session/p2p.py": {
        "check_now",              # finish()/set_session flush hook
        "_resolve_checksum",      # the one sanctioned force/peek funnel
    },
}

# attribute accesses that force (or can force) a device sync
PURITY_ATTRS = frozenset({"to_int", "block_until_ready", "device_get"})
# bare-name calls that force
PURITY_NAMES = frozenset({"checksum_to_int"})

# the package whose call graph the interprocedural pass builds
PACKAGE_DIR = "bevy_ggrs_tpu"

# -- tick-phase timer discipline ---------------------------------------------
# The catalog itself is extracted from telemetry/phases.py by AST literal
# parsing (no jax import) — see rules_phases.extract_phase_catalog.
PHASES_MODULE = "bevy_ggrs_tpu/telemetry/phases.py"
PHASE_FILES: Tuple[str, ...] = (
    "bevy_ggrs_tpu/runner.py",
    "bevy_ggrs_tpu/batch_runner.py",
    # readback_block: the device->host pulls below the drivers
    "bevy_ggrs_tpu/snapshot/lazy.py",
)

# -- metric-name <-> docs-catalog cross-check --------------------------------
METRIC_DOCS = "docs/observability.md"

# -- rule-id <-> docs-catalog cross-check ------------------------------------
RULE_DOCS = "docs/static-analysis.md"

# -- concurrency scope (BGT060/061/062) --------------------------------------
# Threaded (or thread-adjacent) control-plane modules: the attribute/lock
# map and the blocking-under-lock / lock-order checks run only here.  The
# fleet modules are poll-driven single-threaded TODAY, but they are the
# modules a future thread would be added to — covering them now means the
# rule fires on the PR that adds the thread, not three PRs later.
CONCURRENCY_MODULES: Tuple[str, ...] = (
    "bevy_ggrs_tpu/fleet/worker.py",
    "bevy_ggrs_tpu/fleet/scheduler.py",
    "bevy_ggrs_tpu/fleet/protocol.py",
    "bevy_ggrs_tpu/fleet/observe.py",
    "bevy_ggrs_tpu/telemetry/metrics.py",
    "bevy_ggrs_tpu/telemetry/prometheus.py",
    "scripts/room_server.py",
)

# module suffix -> extra background-thread entry points ("Cls.method" /
# bare function qualnames).  Thread(target=...) functions and do_* methods
# of HTTP handler classes are detected automatically; this covers
# CROSS-module entries the per-module scan cannot see — the Prometheus
# exporter's scrape threads call straight into the metric mutators.
THREAD_ROOTS: Dict[str, Set[str]] = {
    "bevy_ggrs_tpu/telemetry/metrics.py": {
        "Counter.inc", "Gauge.set", "Gauge.set_key", "Gauge.inc",
        "Histogram.observe", "Histogram.observe_key",
        "_Metric.series", "MetricsRegistry._get_or_create",
        "MetricsRegistry.metrics", "MetricsRegistry.render_prometheus",
    },
    # the fleet exporter's scrape threads call the observer's read surface
    # (fleet/observe.py routes) while the scheduler poll thread ingests
    "bevy_ggrs_tpu/fleet/observe.py": {
        "FleetObserver.fleet_snapshot", "FleetObserver.fleet_qos",
        "FleetObserver.active_alerts", "FleetObserver.alert_history",
        "FleetObserver.window", "FleetObserver.rate",
    },
}

# calls that can block the holder of a lock (BGT061): attribute names
# (socket/array sync surface) and dotted prefixes (module calls)
BLOCKING_CALL_ATTRS = frozenset({
    "recvfrom", "recv", "accept", "connect", "sendall",
    "block_until_ready", "join",
})
BLOCKING_CALL_DOTTED: Tuple[str, ...] = (
    "time.sleep", "subprocess.", "select.select", "socket.create_connection",
)

# -- transfer-race scope (BGT063) --------------------------------------------
# files whose UNBARRIERED jax.device_put calls are findings by themselves
# (the staging funnels: every reused-buffer upload is routed through here,
# so an unbarriered upload inside one is the PR 8 hazard by construction).
# Elsewhere, an unbarriered upload only becomes a finding when a reused
# staging buffer provably flows into it through the call graph.
TRANSFER_GUARD_FILES: Tuple[str, ...] = (
    "bevy_ggrs_tpu/utils/staging.py",
)
# constructors whose result counts as a persistent host staging buffer
# when assigned to an attribute that is also subscript-written
STAGING_FACTORY_NAMES = frozenset({
    "empty", "zeros", "ones", "full", "frombuffer", "empty_like",
    "zeros_like",
})
STAGING_FACTORY_ATTRS = frozenset({"new_buffer", "new_batch_buffer"})

# -- jit cache-key hazards (BGT070) ------------------------------------------
# Functions allowed to create jit callables per call: factories that bake a
# program per (shape, config) and whose CALLERS memoize the result.  Name
# prefixes cover the repo's make_*/build_*/init_* convention; the explicit
# set covers one-off exceptions.  ``__init__``, ``@cached_property`` /
# ``@lru_cache`` bodies, keyed memo-cache assignments
# (``cache[key] = jax.jit(...)``) and lazy module singletons
# (``global _fn; _fn = jax.jit(...)``) are exempted structurally.
JIT_FACTORY_PREFIXES: Tuple[str, ...] = (
    "make_", "build_", "init_", "_make_", "_build_",
)
JIT_FACTORY_ALLOW: frozenset = frozenset()

# -- solo/batched twin map (BGT073) ------------------------------------------
# Declared duplicated hot-path implementations between the solo GgrsRunner
# and the batched/wave stack: ``("file::Qual.name", "file::Qual.name",
# expect, note)``.  expect="sync": the pair must stay identical after AST
# normalization (locals renamed, docstrings/phase labels stripped) — any
# divergence is a finding.  expect="drift": documented divergence, carried
# in LINT_twins.json as the work-list for the ROADMAP-5 unification; a
# drift pair that CONVERGES is also a finding (promote it to sync).
TWIN_MAP: Tuple[Tuple[str, str, str, str], ...] = (
    ("bevy_ggrs_tpu/runner.py::GgrsRunner.arm_compile_guard",
     "bevy_ggrs_tpu/batch_runner.py::BatchedRunner.arm_compile_guard",
     "sync", "compile-guard arming hook — kept bit-identical"),
    ("bevy_ggrs_tpu/runner.py::GgrsRunner.update",
     "bevy_ggrs_tpu/batch_runner.py::BatchedRunner.tick",
     "drift", "per-tick orchestration + phase wrapping"),
    ("bevy_ggrs_tpu/runner.py::GgrsRunner._report_mismatch",
     "bevy_ggrs_tpu/batch_runner.py::BatchedRunner._report_mismatch",
     "drift", "synctest mismatch forensics (batched adds the lobby index)"),
    ("bevy_ggrs_tpu/runner.py::GgrsRunner._flush_speculation",
     "bevy_ggrs_tpu/batch_runner.py::BatchedRunner._speculate_idle_lanes",
     "drift", "speculative-draft seam (solo drains, batched fills idle lanes)"),
    ("bevy_ggrs_tpu/runner.py::GgrsRunner._service_rollback",
     "bevy_ggrs_tpu/batch_runner.py::BatchedRunner._do_loads",
     "drift", "rollback servicing (solo per-request, batched fused wave)"),
    ("bevy_ggrs_tpu/runner.py::GgrsRunner._stage_packed_rows",
     "bevy_ggrs_tpu/batch_runner.py::BatchedRunner._do_runs",
     "drift", "packed input staging ahead of dispatch"),
    ("bevy_ggrs_tpu/runner.py::GgrsRunner.finish",
     "bevy_ggrs_tpu/batch_runner.py::BatchedRunner.finish",
     "drift", "end-of-run flush + session check drain"),
    ("bevy_ggrs_tpu/runner.py::GgrsRunner._note_dispatch_uploads",
     "bevy_ggrs_tpu/ops/batch.py::BucketedWaveExecutor._note_uploads",
     "sync", "host-upload census accounting"),
    ("bevy_ggrs_tpu/runner.py::GgrsRunner._note_compile",
     "bevy_ggrs_tpu/ops/batch.py::BucketedWaveExecutor._dispatch",
     "drift", "program-compile accounting (first-dispatch timing)"),
)
TWINS_JSON = "LINT_twins.json"

# -- determinism-hazard scopes -----------------------------------------------
# step/sim code: the only places wall-clock reads, jitted debug callbacks
# and frozen-world mutation are hazards *by construction* (session code
# legitimately reads monotonic clocks for timeouts — host-side only)
SIM_DIR_NAMES = frozenset({"models", "ops"})


def _in_sim_code(rel: str) -> bool:
    from pathlib import PurePosixPath

    return bool(SIM_DIR_NAMES & set(PurePosixPath(rel).parts))


@dataclasses.dataclass
class Config:
    """Overridable analysis configuration (defaults = this repo)."""

    purity_allow: Dict[str, Set[str]] = dataclasses.field(
        default_factory=lambda: {k: set(v) for k, v in PURITY_ALLOW.items()}
    )
    purity_attrs: frozenset = PURITY_ATTRS
    purity_names: frozenset = PURITY_NAMES
    package_dir: str = PACKAGE_DIR
    phases_module: str = PHASES_MODULE
    phase_files: Tuple[str, ...] = PHASE_FILES
    metric_docs: str = METRIC_DOCS
    rule_docs: str = RULE_DOCS
    # project-level cross-checks (metrics/docs/stale-allowlist) only make
    # sense against the real repo; fixture runs turn them off
    project_checks: bool = True
    concurrency_modules: Tuple[str, ...] = CONCURRENCY_MODULES
    thread_roots: Dict[str, Set[str]] = dataclasses.field(
        default_factory=lambda: {k: set(v) for k, v in THREAD_ROOTS.items()}
    )
    blocking_call_attrs: frozenset = BLOCKING_CALL_ATTRS
    blocking_call_dotted: Tuple[str, ...] = BLOCKING_CALL_DOTTED
    transfer_guard_files: Tuple[str, ...] = TRANSFER_GUARD_FILES
    staging_factory_names: frozenset = STAGING_FACTORY_NAMES
    staging_factory_attrs: frozenset = STAGING_FACTORY_ATTRS
    jit_factory_prefixes: Tuple[str, ...] = JIT_FACTORY_PREFIXES
    jit_factory_allow: frozenset = JIT_FACTORY_ALLOW
    twin_map: Tuple[Tuple[str, str, str, str], ...] = TWIN_MAP
    # repo-root-relative path the BGT073 duplication inventory is written
    # to on full project runs; None disables the write (fixture runs)
    twins_json: str = TWINS_JSON
    # True for `--changed` runs: the corpus is a changed-files slice, so
    # reverse (stale-entry) docs checks and the stale-suppression
    # meta-rule would false-positive on everything the slice omits
    partial_corpus: bool = False

    def in_concurrency_scope(self, rel: str) -> bool:
        return any(rel.endswith(suffix) for suffix in self.concurrency_modules)

    def thread_roots_for(self, rel: str) -> Set[str]:
        for suffix, roots in self.thread_roots.items():
            if rel.endswith(suffix):
                return roots
        return set()

    def is_transfer_guard_file(self, rel: str) -> bool:
        return any(rel.endswith(suffix) for suffix in self.transfer_guard_files)

    def purity_allowlist_for(self, rel: str):
        """The allowlist for ``rel`` if the purity rules cover it, else None."""
        for suffix, allow in self.purity_allow.items():
            if rel.endswith(suffix):
                return allow
        return None

    def in_sim_code(self, rel: str) -> bool:
        return _in_sim_code(rel)
