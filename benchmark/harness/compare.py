"""Compare the program's worlds with the plain reference's.

Entities are matched by their rollback id (the spawn sequence number, which
the reference counts the same way).  Integer columns and the set of live
ids must agree exactly; float columns are held to a limit on the widest gap
relative to the column's largest reference value in that state.
"""

from __future__ import annotations

import numpy as np


def read_entities(world, names) -> dict:
    """Live entities of one program WorldState on the host, sorted by id:
    ``{"id": int64[n], name: array[n, ...]}``."""
    import jax

    host = jax.device_get({
        "alive": world.alive, "pending": world.despawn_pending,
        "id": world.rollback_id,
        **{n: world.comps[n] for n in names},
        **{"has_" + n: world.has[n] for n in names},
    })
    live = np.asarray(host["alive"]) & ~np.asarray(host["pending"])
    for n in names:
        live &= np.asarray(host["has_" + n])
    ids = np.asarray(host["id"])[live].astype(np.int64)
    order = np.argsort(ids, kind="stable")
    out = {"id": ids[order]}
    for n in names:
        out[n] = np.asarray(host[n])[live][order]
    return out


def compare(prog: dict, ref: dict, floats, exact) -> tuple:
    """``(exact_mismatches, widest_relative_gap)`` of one state.

    Mismatches count ids present on one side only plus matched entities
    whose exact columns differ.  The gap is the largest
    ``|program - reference|`` over matched entities and float columns,
    divided by that column's largest ``|reference|``."""
    pid, rid = prog["id"], ref["id"]
    common, pi, ri = np.intersect1d(pid, rid, return_indices=True)
    mismatches = int(len(pid) + len(rid) - 2 * len(common))
    for n in exact:
        a = np.asarray(prog[n])[pi]
        b = np.asarray(ref[n])[ri]
        diff = a != b
        if diff.ndim > 1:
            diff = diff.reshape(len(diff), -1).any(axis=1)
        mismatches += int(diff.sum())
    gap = 0.0
    for n in floats:
        a = np.asarray(prog[n], np.float64)[pi]
        b = np.asarray(ref[n], np.float64)[ri]
        if b.size == 0:
            continue
        scale = max(float(np.abs(b).max()), 1e-12)
        gap = max(gap, float(np.abs(a - b).max()) / scale)
    return mismatches, gap
