"""The numbers that decide ``correct`` for a training cell, and their limits.

Each number compares the program's states with the plain reference's, leaf
by leaf, by the gap between the two norms (not the norm of the difference),
as a share of the larger of the reference leaf's norm and the median
leaf's. A leaf is one array of the dense parameters (an MLP weight or
bias, or any other part of the model's tree), or one embedding table (the
rows read at that point; a table none of whose rows is read there has no
leaf). Leaves whose reference value is under a thousandth of the median
leaf's are left out: they move by rounding alone.

The states read are given by :func:`points`: the first steps from the
seed, and the first steps after warm-up, when the scratchpad is full and
hits, evictions, refills and write-backs share its slots.
"""
from __future__ import annotations

import numpy as np

#: below this share of the median leaf, a reference leaf is rounding noise
NEGLIGIBLE = 1e-3
#: steps checked from the start, and again after warm-up
FIRST = 3
#: steps a row is away before it counts as returning: the program's hold
#: window (3 past steps, the step, 2 future) keeps a row resident through
#: fewer, so a returning row has as a rule been evicted and is fetched back
AWAY = 6


def points(warmup: int) -> dict:
    """What the check reads, for a run whose first ``warmup`` steps are
    warm-up and whose next ``FIRST`` steps are checked in steady state.

    ``rows``: {name: (steps whose ids name those rows, steps in which they
    were read before or None, steps in which they were not)}; ``returning``
    names the rows of the first steady step that earlier steps read and
    the ``AWAY`` steps before it did not: rows evicted and fetched back,
    whose values the write-back had to carry. ``grads``: (a, b, rows): the
    gradient step b applied, (state a - state b) / lr, a = b - 1.
    ``changes``: (n, rows, with the MLPs): what steps 1..n moved, state n
    - state 0; the returning rows are compared among themselves, so that
    the MLPs' larger changes do not set the median leaf they are held to.
    ``losses``: the steps whose losses are compared. ``last``: the steps
    the reference has to follow. States are read right after a step: the
    rows a step trains are in the scratchpad then, and so are the next
    step's, already filled.
    """
    if warmup <= max(FIRST, AWAY):
        raise ValueError(f"warm-up of {warmup} steps: needs more than "
                         f"{max(FIRST, AWAY)}")
    s1, s3 = warmup + 1, warmup + FIRST
    return {
        "rows": {"first": (tuple(range(1, FIRST + 1)), None, ()),
                 "step1": ((1,), None, ()),
                 "steady1": ((s1,), None, ()),
                 "steady3": ((s3,), None, ()),
                 "returning": ((s1,), tuple(range(1, s1 - AWAY)),
                               tuple(range(s1 - AWAY, s1)))},
        "grads": [(0, 1, "step1"), (warmup, s1, "steady1")],
        "changes": [(FIRST, "first", True), (warmup, "returning", False),
                    (s1, "steady1", True), (s3, "steady3", True)],
        "losses": list(range(1, FIRST + 1)) + list(range(s1, s3 + 1)),
        "last": s3,
    }


def row_ids(pts: dict, ids_by_step) -> dict:
    """{name: sorted global ids} of the rows ``pts`` names; ``ids_by_step``
    holds the ids of steps 1, 2, ... in order."""
    def union(steps):
        return np.unique(np.concatenate(
            [np.ravel(ids_by_step[k - 1]) for k in steps] or [np.zeros(0, np.int64)]))

    out = {}
    for name, (steps, before, not_in) in pts["rows"].items():
        ids = union(steps)
        if before is not None:
            ids = np.intersect1d(ids, union(before), assume_unique=True)
        out[name] = np.setdiff1d(ids, union(not_in), assume_unique=True)
    return out


def reads(pts: dict) -> dict:
    """{steps done: names of the rows read then}, the program's snapshots
    (state 0 is the seed's, and needs none)."""
    out: dict = {}
    for a, b, name in pts["grads"]:
        for n in (a, b):
            out.setdefault(n, set()).add(name)
    for n, name, _ in pts["changes"]:
        out.setdefault(n, set()).add(name)
    out.pop(0, None)
    return out


def _paths(tree, name=""):
    """(name, array) of each array in a tree of dicts and lists: dict keys
    in sorted order, joined by ``.``, list positions appended to the key
    (``bottom0.w``)."""
    if isinstance(tree, dict):
        for k in sorted(tree):
            yield from _paths(tree[k], f"{name}.{k}" if name else str(k))
    elif isinstance(tree, (list, tuple)):
        for i, x in enumerate(tree):
            yield from _paths(x, f"{name}{i}")
    else:
        yield name, tree


def _tree_map(fn, *trees):
    """``fn`` over the arrays of trees of one structure, leaf by leaf."""
    a = trees[0]
    if isinstance(a, dict):
        return {k: _tree_map(fn, *(t[k] for t in trees)) for k in a}
    if isinstance(a, (list, tuple)):
        return [_tree_map(fn, *xs) for xs in zip(*trees)]
    return fn(*trees)


def leaves(dense, rows: np.ndarray, table_of_row: np.ndarray, tables: int):
    """Flatten one model state: the dense parameters (any tree of dicts and
    lists, such as the bottom and top MLPs) leaf by leaf in a fixed order,
    named by path, then one leaf per embedding table that has rows here
    (every row of a small table may be read again later, so that none is
    among the write-back rows)."""
    out = [(n, np.asarray(a, np.float64)) for n, a in _paths(dense)]
    for t in range(tables):
        mine = table_of_row == t
        if mine.any():
            out.append((f"table{t}", np.asarray(rows[mine], np.float64)))
    return out


def worst_leaf_gap(prog, ref):
    """Worst gap between per-leaf norms. ``prog``/``ref``: [(name, array)]
    in the same order. Returns (gap, leaf name, leaves compared); the gap
    is not a number where there is no leaf."""
    if not ref:
        return float("nan"), "no leaf", 0
    nref = np.array([np.linalg.norm(a) for _, a in ref])
    nprog = np.array([np.linalg.norm(a) for _, a in prog])
    med = float(np.median(nref))
    keep = nref >= NEGLIGIBLE * med
    gaps = np.abs(nprog - nref) / np.maximum(nref, med)
    gaps[~keep] = -1.0
    i = int(np.argmax(gaps))
    return float(gaps[i]), ref[i][0], int(keep.sum())


def loss_gap(prog_losses, ref_losses) -> float:
    """Worst relative gap of the per-step losses."""
    p = np.asarray(prog_losses, np.float64)
    r = np.asarray(ref_losses, np.float64)
    return float(np.max(np.abs(p - r) / np.abs(r)))


def numbers(cfg, lr, pts, p, r, tbl):
    """The compared numbers for one run, and where each was worst.

    ``p`` (program) and ``r`` (reference or control) each hold ``loss``
    ({step: loss}), ``mlps`` ({steps done: tree of the dense parameters},
    0 the start) and ``rows`` ({(steps done, name): rows}; state 0 is the
    initial rows). Either may hold ``rows[("end", "writeback")]``: ``p``
    the rows touched in the checked steps and never again, read from the
    host tier after the run's final flush, ``r`` the same rows after the
    last checked step;
    ``rows[(0, "writeback")]`` are their initial rows. ``tbl``: {name: the
    table of each row}.
    """
    T = cfg["num_tables"]

    def sub(a, b, k=1.0):  # (a - b) * k, leaf by leaf
        return _tree_map(lambda x, y: (np.asarray(x, np.float64)
                                       - np.asarray(y, np.float64)) * k, a, b)

    def grad(s, a, b, name):
        d = (s["rows"][(a, name)] - s["rows"][(b, name)]) / lr
        return leaves(sub(s["mlps"][a], s["mlps"][b], 1 / lr), d, tbl[name], T)

    def change(s, n, name, mlps):
        m = sub(s["mlps"][n], s["mlps"][0]) if mlps else {}
        return leaves(m, s["rows"][(n, name)] - s["rows"][(0, name)], tbl[name], T)

    steps = pts["losses"]
    out = {"loss_gap": loss_gap([p["loss"][k] for k in steps],
                                [r["loss"][k] for k in steps])}
    g = [(worst_leaf_gap(grad(p, a, b, nm), grad(r, a, b, nm)), f"{nm}@{b}")
         for a, b, nm in pts["grads"]]
    c = [(worst_leaf_gap(change(p, *x), change(r, *x)), f"{x[1]}@{x[0]}")
         for x in pts["changes"]]
    for key, got in (("grad", g), ("change", c)):
        (gap, leaf, _), at = max(got, key=lambda x: x[0][0])
        out[f"{key}_gap"] = gap
        out[f"{key}_where"] = f"{at} {leaf}"
        out[f"{key}_at"] = {at: v[0] for v, at in got}
    if ("end", "writeback") in p["rows"]:
        gap, leaf, _ = worst_leaf_gap(change(p, "end", "writeback", False),
                                      change(r, "end", "writeback", False))
        out["writeback_gap"], out["writeback_where"] = gap, leaf
    return out


def verdict(nums: dict, limits: dict):
    """(correct, [(name, value, limit)]) for every number with a limit.
    A number that is missing or not finite fails."""
    rows, ok = [], True
    for name, limit in limits.items():
        v = nums.get(name)
        good = v is not None and np.isfinite(v) and v <= limit
        ok = ok and good
        rows.append((name, v, limit))
    return ok, rows
