"""Solo/batched twin-drift — BGT073.

ROADMAP item 5 (collapse the ``GgrsRunner``/``BatchedRunner``
duplication) is blocked on nobody knowing precisely *which* paired
hot-path implementations have drifted.  This rule answers that
mechanically: ``scripts/lint/config.py`` declares the twin map — pairs
of ``file::Qual.name`` references with an expectation — and the pass
compares each pair after normalizing both ASTs:

- docstrings dropped, type annotations stripped,
- argument/local names renamed to positional placeholders in first-use
  order (``self`` and free/global names keep their spelling),
- string literals inside telemetry/phase calls (``.phase("...")``,
  ``.record("...")``, ``telemetry.count("...")``) blanked, so a
  ``"rollback"`` vs ``"batched_rollback"`` label is not drift.

``expect="sync"`` pairs must normalize identically — divergence is a
finding on the solo definition line.  ``expect="drift"`` pairs are the
documented duplication inventory; one that CONVERGES is also a finding
(promote it to sync so the map stays honest).  A reference naming a
missing function is map rot (same idea as BGT012).

Full project runs additionally emit ``LINT_twins.json`` — the
machine-readable duplication inventory (pair, status, similarity ratio,
line counts) that is the work-list for the ROADMAP-5 unification.
"""

from __future__ import annotations

import ast
import copy
import difflib
import json
from typing import Dict, List, Optional

from ..core import Context, Finding, lint_pass, rule

rule(
    "BGT073", "solo-batched-twin-drift",
    summary="declared solo/batched twin pair drifted (or a declared drift "
            "converged) — keep the twin map honest",
)

# calls whose string-literal args are labels, not semantics
_LABEL_CALL_ATTRS = frozenset({
    "phase", "record", "count", "observe", "gauge_set", "inc",
    "observe_key", "set_key",
})


def _strip_labels_and_docs(fn: ast.AST) -> None:
    """In place: drop docstrings, annotations and label strings."""
    for node in ast.walk(fn):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            node.returns = None
            if (node.body and isinstance(node.body[0], ast.Expr)
                    and isinstance(node.body[0].value, ast.Constant)
                    and isinstance(node.body[0].value.value, str)):
                node.body = node.body[1:] or [ast.Pass()]
        elif isinstance(node, ast.arg):
            node.annotation = None
        elif isinstance(node, ast.Call):
            attr = (node.func.attr if isinstance(node.func, ast.Attribute)
                    else node.func.id if isinstance(node.func, ast.Name)
                    else None)
            if attr in _LABEL_CALL_ATTRS:
                for i, a in enumerate(node.args):
                    if isinstance(a, ast.Constant) and isinstance(a.value, str):
                        node.args[i] = ast.Constant(value="")
                for k in node.keywords:
                    if isinstance(k.value, ast.Constant) and isinstance(
                            k.value.value, str):
                        k.value = ast.Constant(value="")


def _rename_locals(fn: ast.AST) -> None:
    """In place: rename args + locally-bound names to placeholders in
    first-binding order; free (closure/global/builtin) names keep their
    spelling so cross-module references still have to match."""
    mapping: Dict[str, str] = {}

    def bind(name: str) -> None:
        if name not in mapping:
            mapping[name] = f"_v{len(mapping)}"

    for node in ast.walk(fn):
        if isinstance(node, ast.arg):
            bind(node.arg)
        elif isinstance(node, ast.Name) and isinstance(node.ctx, ast.Store):
            bind(node.id)
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                and node is not fn:
            bind(node.name)
    for node in ast.walk(fn):
        if isinstance(node, ast.arg) and node.arg in mapping:
            node.arg = mapping[node.arg]
        elif isinstance(node, ast.Name) and node.id in mapping:
            node.id = mapping[node.id]
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                and node is not fn and node.name in mapping:
            node.name = mapping[node.name]
        elif isinstance(node, ast.Global):
            node.names = [mapping.get(n, n) for n in node.names]
        elif isinstance(node, ast.Nonlocal):
            node.names = [mapping.get(n, n) for n in node.names]


def normalize_dump(fn: ast.AST) -> str:
    """Comparable dump of a function def (see module docstring)."""
    fn = copy.deepcopy(fn)
    fn.name = "_twin"
    fn.decorator_list = []
    _strip_labels_and_docs(fn)
    _rename_locals(fn)
    return ast.dump(fn, annotate_fields=False, include_attributes=False)


def find_qualname(tree: ast.AST, qual: str) -> Optional[ast.AST]:
    parts = qual.split(".")

    def descend(node, remaining):
        head, rest = remaining[0], remaining[1:]
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.ClassDef)) and child.name == head:
                if not rest:
                    return child if isinstance(
                        child, (ast.FunctionDef, ast.AsyncFunctionDef)
                    ) else None
                found = descend(child, rest)
                if found is not None:
                    return found
        return None

    return descend(tree, parts)


def _resolve(ctx: Context, ref: str):
    """``(sf, fn_node, rel, qual)`` for a ``file::Qual.name`` ref; the
    missing part is None."""
    rel, _, qual = ref.partition("::")
    sf = ctx.by_suffix(rel)
    if sf is None or sf.tree is None:
        return None, None, rel, qual
    return sf, find_qualname(sf.tree, qual), rel, qual


@lint_pass
def twin_drift_pass(ctx: Context) -> List[Finding]:
    cfg = ctx.config
    if getattr(cfg, "partial_corpus", False) or not cfg.twin_map:
        return []
    out: List[Finding] = []
    inventory: List[dict] = []
    corpus_complete = True

    for solo_ref, batch_ref, expect, note in cfg.twin_map:
        solo_sf, solo_fn, solo_rel, solo_qual = _resolve(ctx, solo_ref)
        batch_sf, batch_fn, batch_rel, batch_qual = _resolve(ctx, batch_ref)
        if solo_sf is None or batch_sf is None:
            # a twinned file missing from the corpus: not a full run
            corpus_complete = False
            continue
        rot = []
        if solo_fn is None:
            rot.append((solo_rel, solo_qual))
        if batch_fn is None:
            rot.append((batch_rel, batch_qual))
        if rot:
            for rel, qual in rot:
                out.append(Finding(
                    "BGT073", rel, 0,
                    f"twin map rot: {qual!r} no longer exists in {rel} — "
                    "the declared solo/batched pair "
                    f"({solo_ref} <-> {batch_ref}) rotted under a "
                    "refactor; update TWIN_MAP (scripts/lint/config.py)",
                ))
            inventory.append({
                "solo": solo_ref, "batched": batch_ref, "expect": expect,
                "status": "missing", "similarity": 0.0, "note": note,
            })
            continue
        dump_a = normalize_dump(solo_fn)
        dump_b = normalize_dump(batch_fn)
        in_sync = dump_a == dump_b
        similarity = 1.0 if in_sync else round(
            difflib.SequenceMatcher(None, dump_a, dump_b).ratio(), 3)
        if expect == "sync" and not in_sync:
            out.append(Finding(
                "BGT073", solo_rel, solo_fn.lineno,
                f"declared-sync twin drifted: {solo_qual} vs "
                f"{batch_ref} normalize differently (similarity "
                f"{similarity:.0%}) — re-align the implementations or "
                "re-declare the pair as drift in TWIN_MAP "
                "(scripts/lint/config.py)",
            ))
        elif expect == "drift" and in_sync:
            out.append(Finding(
                "BGT073", solo_rel, solo_fn.lineno,
                f"declared-drift twin converged: {solo_qual} and "
                f"{batch_ref} now normalize identically — promote the "
                "pair to expect=\"sync\" in TWIN_MAP so the "
                "duplication inventory stays honest",
            ))
        inventory.append({
            "solo": solo_ref, "batched": batch_ref, "expect": expect,
            "status": "in_sync" if in_sync else "drifted",
            "similarity": similarity,
            "solo_lines": _body_lines(solo_fn),
            "batched_lines": _body_lines(batch_fn),
            "note": note,
        })

    # the machine-readable ROADMAP-5 work-list, full project runs only
    twins_json = getattr(cfg, "twins_json", None)
    if cfg.project_checks and twins_json and corpus_complete:
        payload = {
            "version": 1,
            "generated_by": "scripts.lint BGT073 (twin_drift_pass)",
            "pairs": inventory,
            "drifted": sum(1 for p in inventory if p["status"] == "drifted"),
        }
        path = ctx.root / twins_json
        path.write_text(json.dumps(payload, indent=2) + "\n")
    return out


def _body_lines(fn: ast.AST) -> int:
    end = getattr(fn, "end_lineno", fn.lineno)
    return int(end - fn.lineno + 1)
