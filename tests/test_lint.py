"""Determinism-analyzer tests: fixture corpus, interprocedural regression,
and the repo-at-HEAD-lints-clean gate.

The corpus under ``tests/lint_fixtures/`` carries a true-positive, a
suppressed, and a clean fixture per rule; these tests parameterize over
them so the analyzer is tested like product code.  The interprocedural
test is the acceptance criterion for BGT011: the two-deep forcing chain
(``tick -> grab -> pull``) that the old intra-function ``check_purity``
provably misses (it returns no problems for ``hot.py``) is flagged at the
call site with the full witness chain.

No jax import anywhere in this module — the analyzer is stdlib-only and
so are its tests.
"""

import json
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from scripts.lint import RULES, run  # noqa: E402
from scripts.lint.config import Config  # noqa: E402
from scripts.lint.core import (  # noqa: E402
    load_baseline,
    parse_suppressions,
    write_baseline,
)
from scripts.lint.rules.docs import docs_rule_ids  # noqa: E402
from scripts.lint.rules.metrics import (  # noqa: E402
    collect_metric_names,
    docs_metric_names,
)
from scripts.lint.rules.phases import extract_phase_catalog  # noqa: E402
from scripts.lint.rules.purity import check_purity  # noqa: E402
from scripts.lint.rules.trace_kinds import (  # noqa: E402
    collect_trace_kinds,
    docs_trace_kinds,
)

FIXTURES = ROOT / "tests" / "lint_fixtures"

# string-literal copies of the ignore syntax are assembled from halves so
# the analyzer's line-based comment scan never sees the pattern in THIS
# file's source
_IG = "# bgt: " + "ignore"


def lint_paths(paths, **overrides):
    """Run the framework over explicit fixture paths with a quiet config
    (project-level cross-checks off unless a test turns them on)."""
    overrides.setdefault("project_checks", False)
    cfg = Config(**overrides)
    findings, _files = run([str(p) for p in paths], root=ROOT, config=cfg)
    return findings


def only(findings, rule_id):
    return [f for f in findings if f.rule == rule_id]


# -- file-scoped rule triples -------------------------------------------------

# (rule id, fixture stem, expected positive count)
TRIPLES = [
    ("BGT001", "bgt001", 1),
    ("BGT002", "bgt002", 1),
    ("BGT041", "bgt041", 3),
    ("BGT042", "bgt042", 3),
    ("BGT040", "models/bgt040", 3),
    ("BGT043", "models/bgt043", 3),
    ("BGT044", "models/bgt044", 3),
    ("BGT005", "bgt005", 1),
    ("BGT070", "bgt070", 4),
    ("BGT071", "models/bgt071", 5),
    ("BGT072", "models/bgt072", 2),
]


@pytest.mark.parametrize("rule_id,stem,n_pos", TRIPLES,
                         ids=[t[0] for t in TRIPLES])
def test_fixture_positive_fires(rule_id, stem, n_pos):
    hits = only(lint_paths([FIXTURES / f"{stem}_positive.py"]), rule_id)
    assert len(hits) == n_pos, [f.as_dict() for f in hits]
    assert all(not f.suppressed for f in hits)
    assert all(f.severity == "error" for f in hits)


@pytest.mark.parametrize("rule_id,stem,n_pos", TRIPLES,
                         ids=[t[0] for t in TRIPLES])
def test_fixture_suppression_respected(rule_id, stem, n_pos):
    hits = only(lint_paths([FIXTURES / f"{stem}_suppressed.py"]), rule_id)
    assert hits, "the suppressed fixture must still trip the rule"
    assert all(f.suppressed for f in hits)
    assert all(f.suppress_reason for f in hits), \
        "fixture suppressions all carry a justification"


@pytest.mark.parametrize("rule_id,stem,n_pos", TRIPLES,
                         ids=[t[0] for t in TRIPLES])
def test_fixture_clean_is_clean(rule_id, stem, n_pos):
    assert only(lint_paths([FIXTURES / f"{stem}_clean.py"]), rule_id) == []


def test_bgt003_syntax_error():
    hits = only(lint_paths([FIXTURES / "bgt003_positive.py"]), "BGT003")
    assert len(hits) == 1 and not hits[0].suppressed


def test_bgt004_unknown_suppression_id():
    hits = only(lint_paths([FIXTURES / "bgt004_positive.py"]), "BGT004")
    assert len(hits) == 1
    assert "BGT999" in hits[0].message
    assert only(lint_paths([FIXTURES / "bgt004_clean.py"]), "BGT004") == []


# -- hot-loop purity: intra-function (BGT010) ---------------------------------

PURITY_CFG = dict(
    purity_allow={"lint_fixtures/purity/hot.py": {"sanctioned"}},
)


def test_bgt010_positive_suppressed_and_allowlisted():
    findings = lint_paths([FIXTURES / "purity" / "hot.py"], **PURITY_CFG)
    hits = only(findings, "BGT010")
    assert len(hits) == 2, [f.as_dict() for f in hits]
    live = [f for f in hits if not f.suppressed]
    assert len(live) == 1 and "tick" in live[0].message
    gone = [f for f in hits if f.suppressed]
    assert len(gone) == 1 and "also_bad" in gone[0].message
    # the allowlisted funnel's own .device_get is never flagged
    src = (FIXTURES / "purity" / "hot.py").read_text().splitlines()
    sanction_line = next(i for i, ln in enumerate(src, 1) if ".device_get" in ln)
    assert all(f.line != sanction_line for f in hits)


# -- hot-loop purity: interprocedural (BGT011) --------------------------------


def _interproc_paths(pkg):
    d = FIXTURES / pkg
    return [d / "__init__.py", d / "hot.py", d / "helpers.py", d / "leaf.py"]


def _interproc_cfg(pkg):
    return dict(
        package_dir=f"tests/lint_fixtures/{pkg}",
        purity_allow={f"lint_fixtures/{pkg}/hot.py": set()},
    )


def test_bgt011_catches_two_deep_chain_the_old_check_misses():
    """THE acceptance criterion: hot.py has no forcing syntax, so the old
    intra-function rule is blind to it; the call graph flags the call site
    with the full tick -> grab -> pull witness chain."""
    import ast

    hot = FIXTURES / "interproc" / "hot.py"
    assert check_purity(ast.parse(hot.read_text()), allow=set()) == [], \
        "the old intra-function check must provably miss this fixture"

    findings = lint_paths(_interproc_paths("interproc"),
                          **_interproc_cfg("interproc"))
    hits = only(findings, "BGT011")
    assert len(hits) == 1, [f.as_dict() for f in findings]
    f = hits[0]
    assert f.path.endswith("interproc/hot.py") and not f.suppressed
    # the message carries the whole chain down to the direct forcing site
    for fragment in ("tick", "grab", "pull", "block_until_ready", "leaf.py"):
        assert fragment in f.message, f.message
    # and no BGT010 anywhere: there is no forcing syntax in the hot file
    assert only(findings, "BGT010") == []


def test_bgt011_seed_line_suppression_sanctions_every_caller():
    findings = lint_paths(_interproc_paths("interproc_suppressed"),
                          **_interproc_cfg("interproc_suppressed"))
    assert only(findings, "BGT011") == [], \
        "suppressing at the seed (forcing) line must clear the whole chain"


def test_bgt011_clean_chain_is_clean():
    findings = lint_paths(_interproc_paths("interproc_clean"),
                          **_interproc_cfg("interproc_clean"))
    assert only(findings, "BGT011") == []


def test_bgt011_packed_staging_chain_flagged():
    """The packed single-upload hot path's exact shape: stage_packed_rows
    -> commit_staging -> upload, with the forcing (the synchronous staging
    commit that makes persistent-buffer reuse safe) two calls deep.  The
    analyzer must surface it at the driver call site with the full chain —
    the real bevy_ggrs_tpu/utils/staging.py commit is sanctioned at its
    seed line, and this fixture is what proves that sanction is load-
    bearing rather than the chain being invisible."""
    import ast

    hot = FIXTURES / "interproc_packed" / "hot.py"
    assert check_purity(ast.parse(hot.read_text()), allow=set()) == [], \
        "the intra-function check must provably miss the staging chain"

    findings = lint_paths(_interproc_paths("interproc_packed"),
                          **_interproc_cfg("interproc_packed"))
    hits = only(findings, "BGT011")
    assert len(hits) == 1, [f.as_dict() for f in findings]
    f = hits[0]
    assert f.path.endswith("interproc_packed/hot.py") and not f.suppressed
    for fragment in ("stage_packed_rows", "commit_staging", "upload",
                     "block_until_ready", "leaf.py"):
        assert fragment in f.message, f.message


def test_bgt011_packed_staging_clean_chain_is_clean():
    findings = lint_paths(_interproc_paths("interproc_packed_clean"),
                          **_interproc_cfg("interproc_packed_clean"))
    assert only(findings, "BGT011") == []


# -- stale-allowlist meta-lint (BGT012) ---------------------------------------


def test_bgt012_flags_rotted_allowlist_entry():
    findings = lint_paths(
        [FIXTURES / "purity" / "hot.py"],
        purity_allow={"lint_fixtures/purity/hot.py": {"sanctioned", "ghost_fn"}},
        project_checks=True,
    )
    hits = only(findings, "BGT012")
    assert len(hits) == 1 and "ghost_fn" in hits[0].message
    # existing entries are not flagged
    assert "sanctioned" not in hits[0].message


def test_bgt012_flags_missing_target_file():
    findings = lint_paths(
        [FIXTURES / "purity" / "hot.py"],
        purity_allow={"lint_fixtures/purity/gone.py": {"whatever"}},
        project_checks=True,
    )
    hits = only(findings, "BGT012")
    assert len(hits) == 1 and "does not exist" in hits[0].message


# -- tick-phase discipline (BGT020/021/022) -----------------------------------

PHASES_CFG = dict(
    phases_module="tests/lint_fixtures/phases/phases.py",
    phase_files=("lint_fixtures/phases/driver.py",),
    purity_allow={},
    project_checks=True,  # the reverse (stale-catalog) check needs it
)


def test_phase_rules_on_fixture_driver():
    findings = lint_paths([FIXTURES / "phases" / "driver.py"], **PHASES_CFG)
    bgt020 = only(findings, "BGT020")
    assert len(bgt020) == 2
    assert any("typo_phase" in f.message for f in bgt020)
    assert any("one string literal" in f.message for f in bgt020)
    bgt021 = only(findings, "BGT021")
    assert len(bgt021) == 1 and "checksum" in bgt021[0].message
    stale = only(findings, "BGT022")
    assert len(stale) == 1 and "never_timed" in stale[0].message


def test_phase_reverse_check_skipped_on_partial_corpus():
    """A partial-path run must not call a phase stale just because the
    driver that times it was not linted."""
    cfg = dict(PHASES_CFG)
    cfg["phase_files"] = ("lint_fixtures/phases/driver.py",
                          "lint_fixtures/phases/other_driver.py")
    findings = lint_paths([FIXTURES / "phases" / "driver.py"], **cfg)
    assert only(findings, "BGT022") == []


def test_extract_phase_catalog(tmp_path):
    cat = extract_phase_catalog(FIXTURES / "phases" / "phases.py")
    assert cat == {"inputs", "advance", "checksum", "blocked", "never_timed"}
    assert extract_phase_catalog(tmp_path / "missing.py") is None
    bad = tmp_path / "dynamic.py"
    bad.write_text("PHASES = tuple(x for x in names)\n")
    assert extract_phase_catalog(bad) is None


def test_bgt022_on_unextractable_catalog(tmp_path):
    findings = lint_paths(
        [FIXTURES / "bgt001_clean.py"],
        phases_module="tests/lint_fixtures/phases/no_such_catalog.py",
        purity_allow={},
        project_checks=True,
    )
    hits = only(findings, "BGT022")
    assert len(hits) == 1 and "AST literal parsing" in hits[0].message


def test_real_catalog_extracts_and_matches_package():
    """The real telemetry/phases.py catalog must stay AST-extractable —
    that is the contract replacing the old hand-mirrored copy."""
    cat = extract_phase_catalog(ROOT / "bevy_ggrs_tpu/telemetry/phases.py")
    assert cat and {"session_step", "readback_block"} <= cat


# -- metric and rule docs cross-checks (BGT03x / BGT05x) ----------------------


def test_metric_name_collection_and_docs_parse():
    import ast

    tree = ast.parse(
        "reg.counter('ticks_total')\n"
        "reg.bind_gauge('depth_now', lobby=3)\n"
        "telemetry.count('rollbacks_total')\n"
        "other.count('not_a_metric')\n"  # non-telemetry receiver: ignored
    )
    assert collect_metric_names(tree) == {
        "ticks_total", "depth_now", "rollbacks_total",
    }
    md = (
        "| metric | labels | meaning |\n"
        "|--------|--------|---------|\n"
        "| `ticks_total` | - | ticks |\n"
        "\nprose mentioning `not_in_a_table`\n"
    )
    assert docs_metric_names(md) == {"ticks_total"}


def test_bgt031_skipped_on_partial_corpus():
    """A single-file run must not call every documented metric stale just
    because the files registering them were not linted (the same guard as
    the BGT022 reverse check)."""
    findings = lint_paths([FIXTURES / "bgt001_clean.py"],
                          purity_allow={}, project_checks=True)
    assert only(findings, "BGT031") == []


def test_bgt030_and_bgt031_on_synthetic_tree(tmp_path):
    """Both directions fire against a synthetic repo root whose corpus IS
    complete (the package __init__ is linted)."""
    pkg = tmp_path / "bevy_ggrs_tpu"
    pkg.mkdir()
    (pkg / "__init__.py").write_text(
        "def setup(reg):\n"
        "    reg.counter('undocumented_total')\n"
        "    reg.gauge('documented_now')\n"
    )
    docs = tmp_path / "docs"
    docs.mkdir()
    (docs / "observability.md").write_text(
        "| metric | labels | meaning |\n"
        "|--------|--------|---------|\n"
        "| `documented_now` | - | fine |\n"
        "| `ghost_metric` | - | stale |\n"
    )
    cfg = Config(purity_allow={}, project_checks=True,
                 phases_module="no/such/phases.py")
    findings, _files = run([str(pkg / "__init__.py")], root=tmp_path,
                           config=cfg)
    b30 = only(findings, "BGT030")
    assert len(b30) == 1 and "undocumented_total" in b30[0].message
    b31 = only(findings, "BGT031")
    assert len(b31) == 1 and "ghost_metric" in b31[0].message


def test_trace_kind_collection_and_docs_parse():
    import ast

    tree = ast.parse(
        "telemetry.record('rollback', to_frame=3)\n"
        "fr.record('tick', frame=1)\n"
        "telemetry.record(kind, x=1)\n"       # dynamic: not collectable
        "recorder.append('not_a_record')\n"   # not a .record call
        "telemetry.record('Not_A_Kind')\n"    # fails the kind regex
    )
    assert collect_trace_kinds(tree) == [("rollback", 1), ("tick", 2)]
    md = (
        "| kind | source | meaning |\n"
        "|------|--------|---------|\n"
        "| `rollback` | runner | blamed rollback |\n"
        "\nprose mentioning `not_in_a_table`\n"
        "| metric | labels | meaning |\n"
        "|--------|--------|---------|\n"
        "| `ticks_total` | - | a METRIC table, not a kind table |\n"
    )
    assert docs_trace_kinds(md) == {"rollback"}


BGT032_CFG = dict(purity_allow={}, project_checks=True,
                  phases_module="no/such/phases.py")


def test_bgt032_fixture_triple():
    """The fixture triple runs against the REAL docs catalog (fixtures are
    not tests to this pass), so the positive's private kind fires and the
    clean fixture's catalogued ``rollback`` does not."""
    pos = only(lint_paths([FIXTURES / "bgt032_positive.py"], **BGT032_CFG),
               "BGT032")
    assert len(pos) == 1 and not pos[0].suppressed
    assert "zzz_private_event" in pos[0].message
    sup = only(lint_paths([FIXTURES / "bgt032_suppressed.py"], **BGT032_CFG),
               "BGT032")
    assert len(sup) == 1 and sup[0].suppressed and sup[0].suppress_reason
    assert only(lint_paths([FIXTURES / "bgt032_clean.py"], **BGT032_CFG),
                "BGT032") == []


def test_bgt033_skipped_on_partial_corpus():
    findings = lint_paths([FIXTURES / "bgt001_clean.py"],
                          purity_allow={}, project_checks=True)
    assert only(findings, "BGT033") == []


def test_bgt032_and_bgt033_on_synthetic_tree(tmp_path):
    """Both directions against a synthetic root with a complete corpus."""
    pkg = tmp_path / "bevy_ggrs_tpu"
    pkg.mkdir()
    (pkg / "__init__.py").write_text(
        "def emit(telemetry):\n"
        "    telemetry.record('undocumented_kind', frame=1)\n"
        "    telemetry.record('documented_kind', frame=2)\n"
    )
    docs = tmp_path / "docs"
    docs.mkdir()
    (docs / "observability.md").write_text(
        "| kind | source | meaning |\n"
        "|------|--------|---------|\n"
        "| `documented_kind` | pkg | fine |\n"
        "| `ghost_kind` | nowhere | stale |\n"
    )
    cfg = Config(purity_allow={}, project_checks=True,
                 phases_module="no/such/phases.py")
    findings, _files = run([str(pkg / "__init__.py")], root=tmp_path,
                           config=cfg)
    b32 = only(findings, "BGT032")
    assert len(b32) == 1 and "undocumented_kind" in b32[0].message
    assert b32[0].line == 2  # reported at the emission line
    b33 = only(findings, "BGT033")
    assert len(b33) == 1 and "ghost_kind" in b33[0].message


def test_rule_docs_catalog_matches_registry_exactly():
    """docs/static-analysis.md documents exactly the registered rule set —
    the human-readable half of the BGT050/BGT051 gate."""
    ids = docs_rule_ids((ROOT / "docs/static-analysis.md").read_text())
    assert ids == set(RULES)


# -- concurrency & transfer races (BGT06x) ------------------------------------

# same triple contract as TRIPLES, but each rule needs its fixture files
# pulled into the analyzer's scope (concurrency_modules / package_dir)
CONCUR_TRIPLES = [
    ("BGT060", "bgt060", 1),
    ("BGT061", "bgt061", 2),
    ("BGT062", "bgt062", 1),
    ("BGT063", "bgt063", 2),
]


def _concur_cfg(stem):
    if stem == "bgt063":
        return dict(package_dir="tests/lint_fixtures")
    return dict(concurrency_modules=(
        f"{stem}_positive.py", f"{stem}_suppressed.py", f"{stem}_clean.py",
    ))


@pytest.mark.parametrize("rule_id,stem,n_pos", CONCUR_TRIPLES,
                         ids=[t[0] for t in CONCUR_TRIPLES])
def test_concurrency_fixture_positive_fires(rule_id, stem, n_pos):
    hits = only(lint_paths([FIXTURES / f"{stem}_positive.py"],
                           **_concur_cfg(stem)), rule_id)
    assert len(hits) == n_pos, [f.as_dict() for f in hits]
    assert all(not f.suppressed for f in hits)
    assert all(f.severity == "error" for f in hits)


@pytest.mark.parametrize("rule_id,stem,n_pos", CONCUR_TRIPLES,
                         ids=[t[0] for t in CONCUR_TRIPLES])
def test_concurrency_fixture_suppression_respected(rule_id, stem, n_pos):
    hits = only(lint_paths([FIXTURES / f"{stem}_suppressed.py"],
                           **_concur_cfg(stem)), rule_id)
    assert hits, "the suppressed fixture must still trip the rule"
    assert all(f.suppressed for f in hits)
    assert all(f.suppress_reason for f in hits)


@pytest.mark.parametrize("rule_id,stem,n_pos", CONCUR_TRIPLES,
                         ids=[t[0] for t in CONCUR_TRIPLES])
def test_concurrency_fixture_clean_is_clean(rule_id, stem, n_pos):
    assert only(lint_paths([FIXTURES / f"{stem}_clean.py"],
                           **_concur_cfg(stem)), rule_id) == []


def test_bgt060_declared_thread_roots_engage_the_analysis():
    """No ``Thread(...)`` in the module: the analysis is vacuous until the
    entry point is declared in config.THREAD_ROOTS (the telemetry
    registry's situation — its scrape thread lives in scripts/)."""
    path = FIXTURES / "bgt060_roots.py"
    scope = dict(concurrency_modules=("bgt060_roots.py",))
    assert only(lint_paths([path], **scope), "BGT060") == []
    hits = only(lint_paths(
        [path], thread_roots={"bgt060_roots.py": {"Series.bump"}}, **scope,
    ), "BGT060")
    assert len(hits) == 1, [f.as_dict() for f in hits]
    assert "_vals" in hits[0].message


def test_bgt060_real_registry_locking_is_load_bearing(tmp_path):
    """Strip the metrics registry's ``with self._reg._lock:`` blocks and
    BGT060 must fire — proof the rule watches the real control plane and
    the repo's locking is what keeps HEAD clean."""
    src = (ROOT / "bevy_ggrs_tpu/telemetry/metrics.py").read_text()
    assert "with self._reg._lock:" in src
    stripped = src.replace("with self._reg._lock:", "if True:")
    mod = tmp_path / "metrics_unlocked.py"
    mod.write_text(stripped)
    from scripts.lint.config import THREAD_ROOTS
    hits = only(lint_paths(
        [mod],
        concurrency_modules=("metrics_unlocked.py",),
        thread_roots={
            "metrics_unlocked.py":
                THREAD_ROOTS["bevy_ggrs_tpu/telemetry/metrics.py"],
        },
    ), "BGT060")
    assert hits, "unlocked cross-thread registry writes must be flagged"
    assert only(lint_paths(
        [ROOT / "bevy_ggrs_tpu/telemetry/metrics.py"]), "BGT060") == []


def _transfer_paths(pkg):
    d = FIXTURES / pkg
    return [d / "__init__.py", d / "driver.py", d / "helper.py"]


def test_bgt063_interprocedural_chain_flagged():
    """The reused staging buffer flows into a helper that uploads it
    un-barriered one call away — flagged at the driver's call site with
    the chain down to the direct device_put."""
    findings = lint_paths(_transfer_paths("transfer"),
                          package_dir="tests/lint_fixtures/transfer")
    hits = only(findings, "BGT063")
    assert len(hits) == 1, [f.as_dict() for f in findings]
    f = hits[0]
    assert f.path.endswith("transfer/driver.py") and not f.suppressed
    for fragment in ("flush", "self.buf", "upload_rows", "un-barriered",
                     "helper.py"):
        assert fragment in f.message, f.message


def test_bgt063_seed_suppression_sanctions_every_caller():
    findings = lint_paths(
        _transfer_paths("transfer_suppressed"),
        package_dir="tests/lint_fixtures/transfer_suppressed",
    )
    assert only(findings, "BGT063") == [], \
        "suppressing at the seed (upload) line must clear the chain"
    # ...and the seed comment is load-bearing, not stale (BGT005)
    assert only(findings, "BGT005") == []


def test_bgt063_clean_chain_is_clean():
    findings = lint_paths(_transfer_paths("transfer_clean"),
                          package_dir="tests/lint_fixtures/transfer_clean")
    assert only(findings, "BGT063") == []


# -- incremental (--changed) slice --------------------------------------------


def test_expand_dependents_pulls_in_reverse_importers():
    from scripts.lint.incremental import expand_dependents

    out = expand_dependents({"bevy_ggrs_tpu/fleet/protocol.py"}, ROOT)
    assert "bevy_ggrs_tpu/fleet/protocol.py" in out
    # worker and scheduler import the protocol module; linting them is what
    # keeps cross-file rules honest on the slice
    assert "bevy_ggrs_tpu/fleet/worker.py" in out
    assert "bevy_ggrs_tpu/fleet/scheduler.py" in out


def test_expand_dependents_ignores_non_corpus_files():
    from scripts.lint.incremental import expand_dependents

    assert expand_dependents(
        {"docs/observability.md", "no/such/file.py"}, ROOT) == []


def test_changed_slice_agrees_with_full_run():
    """On the files it lints, a --changed slice must report exactly the
    full run's findings, minus the whole-repo reverse checks the partial
    corpus structurally cannot support."""
    from scripts.lint.incremental import expand_dependents

    PARTIAL_SKIPPED = {"BGT005", "BGT022", "BGT031", "BGT033", "BGT073"}
    slice_paths = expand_dependents(
        {"bevy_ggrs_tpu/fleet/protocol.py"}, ROOT)
    assert slice_paths

    def key(fs, paths):
        return sorted(
            (f.rule, f.path, f.line, f.suppressed)
            for f in fs
            if f.path in paths and f.rule not in PARTIAL_SKIPPED
        )

    sliced, _ = run(slice_paths, root=ROOT,
                    config=Config(partial_corpus=True))
    full, _ = run(None, root=ROOT, config=Config())
    in_slice = set(slice_paths)
    assert key(sliced, in_slice) == key(full, in_slice)


def test_changed_cli_exits_zero():
    res = subprocess.run(
        [sys.executable, "-m", "scripts.lint", "--changed"],
        cwd=ROOT, capture_output=True, text=True, timeout=120,
    )
    assert res.returncode == 0, res.stdout + res.stderr
    assert "--changed" in res.stdout


# -- suppression parsing ------------------------------------------------------


def test_parse_suppressions_same_line_and_block():
    src = (
        "x = compute()  " + _IG + "[BGT001]: same-line reason\n"
        + _IG + "[BGT042]: a standalone comment covers\n"
        "# the whole block below it\n"
        "y = sum(stuff)\n"
    )
    covers, unknown = parse_suppressions(src)
    assert unknown == []
    assert covers[1]["BGT001"] == "same-line reason"
    # the standalone comment on line 2 covers lines 2-4 (through the block
    # to the first code line)
    for line in (2, 3, 4):
        assert covers[line]["BGT042"] == "a standalone comment covers"
    assert "BGT042" not in covers.get(1, {})


def test_parse_suppressions_unknown_id_reported():
    src = "x = 1  " + _IG + "[BGT998, BGT001]\n"
    covers, unknown = parse_suppressions(src)
    assert unknown == [(1, "BGT998")]
    assert covers[1] == {"BGT001": ""}


# -- baseline round-trip ------------------------------------------------------


def test_baseline_round_trip(tmp_path):
    findings = lint_paths([FIXTURES / "bgt041_positive.py"])
    live = [f for f in findings if not f.suppressed]
    assert live
    bl = tmp_path / "baseline.json"
    write_baseline(bl, findings)
    known = load_baseline(bl)
    assert {f.fingerprint() for f in live} == known
    # fingerprints are line-number-free on purpose
    assert all(len(fp) == 3 for fp in known)


# -- the gate itself ----------------------------------------------------------


def test_repo_at_head_lints_clean_and_json_report(tmp_path):
    """`python -m scripts.lint` exits 0 at HEAD and the JSON report has the
    documented shape — the exact invocation scripts/check.sh gates on."""
    report_path = tmp_path / "lint_report.json"
    res = subprocess.run(
        [sys.executable, "-m", "scripts.lint", "--json", str(report_path)],
        cwd=ROOT, capture_output=True, text=True, timeout=120,
    )
    assert res.returncode == 0, res.stdout + res.stderr
    report = json.loads(report_path.read_text())
    assert report["version"] == 1
    assert report["counts"]["errors"] == 0
    assert report["counts"]["findings"] == 0
    assert {r["id"] for r in report["rules"]} == set(RULES)
    for f in report["findings"]:  # only suppressed ones remain at HEAD
        assert f["suppressed"] and f["suppress_reason"]
        assert {"rule", "name", "severity", "path", "line", "message"} \
            <= set(f)


def test_shim_cli_still_works():
    """`python scripts/lint_imports.py` (the pre-framework invocation)
    delegates to the framework with the same exit semantics."""
    res = subprocess.run(
        [sys.executable, "scripts/lint_imports.py"],
        cwd=ROOT, capture_output=True, text=True, timeout=120,
    )
    assert res.returncode == 0, res.stdout + res.stderr
    assert "lint:" in res.stdout


# -- recompilation & engine drift (BGT07x) ------------------------------------


def _shape_chain_paths(pkg):
    d = FIXTURES / pkg
    return [d / "__init__.py", d / "digest.py",
            d / "ops" / "__init__.py", d / "ops" / "hot.py"]


def test_bgt071_chain_flagged_at_sim_call_site():
    """The interprocedural acceptance shape: ops/hot.py has no hazard
    syntax, the jnp.stack over a dynamic sequence lives in non-sim
    digest.py — the chain finding lands at the sim-scope call site with
    the full witness path down to the seed."""
    findings = lint_paths(_shape_chain_paths("shape_chain"),
                          package_dir="tests/lint_fixtures/shape_chain")
    hits = only(findings, "BGT071")
    assert len(hits) == 1, [f.as_dict() for f in findings]
    f = hits[0]
    assert f.path.endswith("shape_chain/ops/hot.py") and not f.suppressed
    for fragment in ("tick", "fold_parts", "digest.py", "stack"):
        assert fragment in f.message, f.message


def test_bgt071_seed_sanction_clears_every_caller():
    findings = lint_paths(
        _shape_chain_paths("shape_chain_suppressed"),
        package_dir="tests/lint_fixtures/shape_chain_suppressed")
    assert only(findings, "BGT071") == [], \
        "suppressing at the seed (hazard) line must clear the whole chain"


# BGT073 twin pairs share the two fixture halves; each test declares its
# own map (the config is the rule's input surface)
_TWINS = "tests/lint_fixtures/twins"
_SOLO = f"{_TWINS}/solo.py::Solo"
_BATCH = f"{_TWINS}/batched.py::Batched"


def _twin_findings(twin_map):
    d = FIXTURES / "twins"
    return only(lint_paths([d / "solo.py", d / "batched.py"],
                           twin_map=twin_map, twins_json=None), "BGT073")


def test_bgt073_sync_pair_in_sync_is_clean():
    # different local names AND a different telemetry label string: both
    # must normalize away
    assert _twin_findings(
        ((f"{_SOLO}.drain", f"{_BATCH}.drain", "sync", "queue drain"),)
    ) == []


def test_bgt073_declared_sync_pair_drifted_fires():
    hits = _twin_findings(
        ((f"{_SOLO}.tally", f"{_BATCH}.tally", "sync", "input tally"),))
    assert len(hits) == 1 and hits[0].path.endswith("twins/solo.py")
    assert "declared-sync twin drifted" in hits[0].message
    assert "similarity" in hits[0].message


def test_bgt073_declared_drift_pair_converged_fires():
    hits = _twin_findings(
        ((f"{_SOLO}.ping", f"{_BATCH}.ping", "drift", "clock probe"),))
    assert len(hits) == 1
    assert "declared-drift twin converged" in hits[0].message


def test_bgt073_map_rot_fires():
    hits = _twin_findings(
        ((f"{_SOLO}.gone", f"{_BATCH}.drain", "sync", "rotted ref"),))
    assert len(hits) == 1
    assert "twin map rot" in hits[0].message and "gone" in hits[0].message


def test_bgt073_partial_corpus_is_silent():
    d = FIXTURES / "twins"
    findings = lint_paths(
        [d / "solo.py", d / "batched.py"],
        twin_map=((f"{_SOLO}.tally", f"{_BATCH}.tally", "sync", "t"),),
        twins_json=None, partial_corpus=True)
    assert only(findings, "BGT073") == []


def test_twins_json_inventory_written(tmp_path):
    """Full-project-run shape: project_checks on + twins_json set writes
    the ROADMAP-5 work-list with per-pair status and similarity."""
    import shutil

    d = tmp_path / "twins"
    d.mkdir()
    for name in ("solo.py", "batched.py"):
        shutil.copy(FIXTURES / "twins" / name, d / name)
    cfg = Config(
        project_checks=True, twins_json="out_twins.json",
        metric_docs="docs/observability.md",
        rule_docs="docs/static-analysis.md",
        twin_map=(
            ("twins/solo.py::Solo.drain", "twins/batched.py::Batched.drain",
             "sync", "queue drain"),
            ("twins/solo.py::Solo.tally", "twins/batched.py::Batched.tally",
             "drift", "input tally"),
        ),
    )
    run([str(d / "solo.py"), str(d / "batched.py")],
        root=tmp_path, config=cfg)
    payload = json.loads((tmp_path / "out_twins.json").read_text())
    assert payload["version"] == 1 and payload["drifted"] == 1
    by_solo = {p["solo"]: p for p in payload["pairs"]}
    assert by_solo["twins/solo.py::Solo.drain"]["status"] == "in_sync"
    assert by_solo["twins/solo.py::Solo.drain"]["similarity"] == 1.0
    tally = by_solo["twins/solo.py::Solo.tally"]
    assert tally["status"] == "drifted" and 0 < tally["similarity"] < 1
    assert tally["solo_lines"] >= 1 and tally["batched_lines"] >= 1


def test_repo_twin_map_references_resolve_and_inventory_is_emitted():
    """The REAL twin map: every declared pair resolves at HEAD (no rot)
    and the repo-root LINT_twins.json inventory carries >= 5 pairs."""
    findings, _files = run(None, root=ROOT, config=Config())
    assert only([f for f in findings if not f.suppressed], "BGT073") == []
    payload = json.loads((ROOT / "LINT_twins.json").read_text())
    assert len(payload["pairs"]) >= 5
    assert all(p["status"] in ("in_sync", "drifted") for p in payload["pairs"])


# -- content-hash result cache (--cache) --------------------------------------


def _norm_findings(findings):
    return sorted(
        (f.rule, f.path, f.line, f.message, f.suppressed) for f in findings
    )


def test_cache_cold_and_warm_agree_exactly_with_full_run(tmp_path):
    """Unlike --changed (which drops whole-repo reverse checks), --cache
    must reproduce the full run's findings EXACTLY — whole-corpus rules
    run fresh and per-file results replay from the manifest."""
    from scripts.lint.cache import cached_run

    cache = tmp_path / "cache.json"
    cold, _, stats = cached_run(ROOT, cache_path=cache)
    assert stats["mode"] == "rebuild" and stats["reused"] == 0
    warm, _, stats = cached_run(ROOT, cache_path=cache)
    assert stats["mode"] == "warm" and stats["analyzed"] == 0
    assert stats["reused"] > 0
    plain, _ = run(None, root=ROOT, config=Config())
    assert _norm_findings(warm) == _norm_findings(plain)
    assert _norm_findings(cold) == _norm_findings(plain)


def _mini_repo(tmp_path):
    pkg = tmp_path / "bevy_ggrs_tpu"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    (pkg / "util.py").write_text("def helper():\n    return 1\n")
    (pkg / "main.py").write_text(
        "import os\n\nfrom bevy_ggrs_tpu.util import helper\n\n\n"
        "def tick():\n    return helper()\n")
    return pkg


def test_cache_slices_on_mutation_and_still_agrees(tmp_path):
    """Mutating one file re-analyzes its bidirectional import closure
    (the file plus its importer) and the merged result matches a fresh
    full run; adding a file falls back to a rebuild."""
    from scripts.lint.cache import cached_run

    pkg = _mini_repo(tmp_path)
    cfg = Config(project_checks=False)
    cache = tmp_path / "cache.json"
    _f, _x, stats = cached_run(tmp_path, config=cfg, cache_path=cache)
    assert stats["mode"] == "rebuild"

    (pkg / "util.py").write_text("import sys\n\n\ndef helper():\n    return 1\n")
    warm, _x, stats = cached_run(tmp_path, config=cfg, cache_path=cache)
    assert stats["mode"] == "warm"
    assert stats["analyzed"] >= 2, "importer main.py must re-enter the slice"
    plain, _x = run(None, root=tmp_path, config=cfg)
    assert _norm_findings(warm) == _norm_findings(plain)
    assert any(f.rule == "BGT001" and f.path.endswith("util.py")
               for f in warm), "fresh finding on the mutated file"

    (pkg / "extra.py").write_text("X = 1\n")
    _f, _x, stats = cached_run(tmp_path, config=cfg, cache_path=cache)
    assert stats["mode"] == "rebuild", "a changed file SET rebuilds"


def test_cache_cli_timings_and_soft_time_budget(tmp_path):
    """--cache --timings prints the per-family wall-time table; an
    exceeded --time-budget warns but stays a soft gate (exit 0), and
    --time-budget-hard turns it into a failure."""
    res = subprocess.run(
        [sys.executable, "-m", "scripts.lint", "--cache", "--timings",
         "--time-budget", "0.001"],
        cwd=ROOT, capture_output=True, text=True, timeout=180,
    )
    assert res.returncode == 0, res.stdout + res.stderr
    assert "lint: cache" in res.stdout
    assert "lint-timing: total" in res.stdout
    assert "WARNING" in res.stdout and "soft" in res.stdout

    from scripts.lint.core import main as lint_main
    rc = lint_main(["--cache", "--time-budget", "0.001",
                    "--time-budget-hard"])
    assert rc == 1
