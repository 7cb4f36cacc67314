"""Input quarantine — graph sanitation before planning (DESIGN.md §12).

Every scoring path downstream of `ScoringEngine.plan()` assumes clean
inputs: square binary symmetric adjacency, int labels in range, no
non-finite values. A production stream violates each of those eventually,
and one malformed graph used to poison the whole micro-batch (a shape error
deep inside packing, or NaNs silently spreading through a packed tile that
also holds 30 healthy pairs).

This module turns that into a per-request outcome: `validate_pairs` scans a
batch host-side and splits it into valid pairs (scored normally) and
quarantined pairs, each with a structured `InvalidGraph` record naming the
pair, side and every reason. The engine (lenient mode, the default) scores
quarantined pairs as NaN — the standard "no answer" marker that survives
serialization — and surfaces the records on the `ScorePlan`; strict mode
raises `GraphValidationError` with the same records attached.

`graph_problems` is the one exact check of a graph. `validate_pairs` runs it
only on the graphs a grouped pass cannot clear: a walk over the call's
distinct graph objects checks each one's form (a dict with a square numeric
adjacency of n > 0 nodes, labels 1-D of length n with an integer dtype), then
the adjacencies are stacked per (node count, dtype) and each group's values
are checked in a few whole-array reductions (every entry 0 or 1, which also
rejects NaN and Inf; a zero diagonal; symmetry), and all labels in one
concatenation (in [0, n_labels)). Any graph of another form, or flagged by a
reduction, is re-checked alone by `graph_problems`, so the records are the
per-graph ones by construction, and the cost grows with the number of
distinct sizes rather than the number of graphs. Engines built with
`validation="off"` skip it entirely (trusted in-process generators,
benchmarks).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Sequence

import numpy as np


@dataclass(frozen=True)
class InvalidGraph:
    """One quarantined graph: which pair and side of the call it came from,
    and every validation failure found (not just the first — a client fixing
    its producer wants the full list)."""
    pair: int                 # pair position within the call
    side: int                 # 0 = lhs, 1 = rhs
    reasons: tuple            # tuple[str, ...], human-readable

    def __str__(self) -> str:
        return (f"pair {self.pair} side {self.side}: "
                + "; ".join(self.reasons))


class GraphValidationError(ValueError):
    """Strict-mode rejection; `.records` carries the InvalidGraph list."""

    def __init__(self, records: Sequence[InvalidGraph]):
        self.records = tuple(records)
        lines = ", ".join(str(r) for r in self.records[:4])
        more = (f" (+{len(self.records) - 4} more)"
                if len(self.records) > 4 else "")
        super().__init__(
            f"{len(self.records)} invalid graph(s) in batch: {lines}{more}")


def graph_problems(g, *, n_labels: int | None = None) -> list[str]:
    """Every validation failure of one graph dict (empty list == valid).

    Checks, in dependency order (later checks assume earlier ones hold):
      * structure — a dict with an "adj" key; adjacency array-like, 2-D,
        square, at least one node;
      * dtype — numeric adjacency (object/str arrays are rejected before
        any arithmetic touches them);
      * values — finite (no NaN/Inf), binary {0, 1} (covers negative
        entries), zero diagonal (raw adjacency carries no self loops —
        normalization adds A+I itself), symmetric (undirected contract;
        the symmetric-A' training VJP exploits it);
      * labels, when present — 1-D of length n, integer dtype (float labels
        can smuggle NaN and break the W1 row gather), in [0, n_labels).
    Missing labels are NOT invalid here: the engine's label-free contract
    error stays in charge of that case.
    """
    if not isinstance(g, dict) or "adj" not in g:
        return ["missing adjacency ('adj')"]
    problems: list[str] = []
    try:
        adj = np.asarray(g["adj"])
    except Exception:
        return ["adjacency is not array-like"]
    if adj.dtype == object or adj.dtype.kind in "USV":
        return [f"non-numeric adjacency dtype {adj.dtype}"]
    if adj.ndim != 2 or adj.shape[0] != adj.shape[1]:
        return [f"adjacency not square (shape {adj.shape})"]
    n = adj.shape[0]
    if n == 0:
        return ["empty graph (0 nodes)"]
    if not np.isfinite(adj).all():
        problems.append("non-finite adjacency entries (NaN/Inf)")
    else:
        if not ((adj == 0) | (adj == 1)).all():
            problems.append("non-binary adjacency entries")
        if np.asarray(adj.diagonal()).any():
            problems.append("self loops on the diagonal (raw adjacency "
                            "must be hollow; normalization adds A+I)")
        if not (adj == adj.T).all():
            problems.append("asymmetric adjacency (graphs are undirected)")
    if "labels" in g:
        try:
            labels = np.asarray(g["labels"])
        except Exception:
            problems.append("labels are not array-like")
            return problems
        if labels.ndim != 1 or labels.shape[0] != n:
            problems.append(f"ragged labels (shape {labels.shape} for "
                            f"{n} nodes)")
        elif labels.dtype.kind not in "iu":
            problems.append(f"non-integer label dtype {labels.dtype}")
        else:
            if labels.size and int(labels.min()) < 0:
                problems.append("negative node labels")
            if (n_labels is not None and labels.size
                    and int(labels.max()) >= n_labels):
                problems.append(f"node label {int(labels.max())} out of "
                                f"range [0, {n_labels})")
    return problems


#: adjacency dtype kinds the grouped pass checks: bool, signed and unsigned
#: integers, floats. Any other kind goes to `graph_problems` alone.
_GROUPED_KINDS = "biuf"


def _flagged(adjs: list) -> np.ndarray:
    """Positions in one (n, dtype) group of the adjacencies that fail a
    value check: an entry other than 0 or 1 (so also NaN or Inf), a nonzero
    diagonal, or asymmetry. The last two are read off `s == 1`, which
    equals `s` wherever the first holds. A sound group costs one pass of
    whole-group reductions; only a failing one is split per graph."""
    k, n = len(adjs), adjs[0].shape[0]
    s = adjs[0][None] if k == 1 else np.concatenate(adjs).reshape(k, n, n)
    one = s == 1
    binary = one | (s == 0)
    diagonal = one.diagonal(axis1=1, axis2=2)
    symmetric = one == one.transpose(0, 2, 1)
    if binary.all() and not diagonal.any() and symmetric.all():
        return np.empty(0, np.int64)
    return np.flatnonzero(~(binary.all(axis=(1, 2)) & ~diagonal.any(axis=1)
                            & symmetric.all(axis=(1, 2))))


def _needs_exact(graphs, n_labels: int | None) -> dict:
    """The graphs the grouped pass cannot clear, by id(): every graph that
    `graph_problems` would reject is among them (a sound graph may be too;
    it then costs one exact check)."""
    exact: dict = {}
    groups: dict = {}                  # (n, dtype) -> ([graph], [adj])
    lab_of: list = []
    lab_arrays: list = []
    for g in graphs:
        if not isinstance(g, dict) or "adj" not in g:
            exact[id(g)] = g
            continue
        try:
            adj = np.asarray(g["adj"])
            labels = np.asarray(g["labels"]) if "labels" in g else None
        except Exception:          # graph_problems reports it
            exact[id(g)] = g
            continue
        n = adj.shape[0] if adj.ndim == 2 else 0
        if (n == 0 or adj.shape[1] != n
                or adj.dtype.kind not in _GROUPED_KINDS
                or labels is not None and (labels.ndim != 1
                                           or labels.shape[0] != n
                                           or labels.dtype.kind not in "iu")):
            exact[id(g)] = g
            continue
        members, adjs = groups.setdefault((n, adj.dtype), ([], []))
        members.append(g)
        adjs.append(adj)
        if labels is not None:
            lab_of.append(g)
            lab_arrays.append(labels)
    for members, adjs in groups.values():
        exact.update((id(members[j]), members[j]) for j in _flagged(adjs))
    if lab_arrays:
        # Unsafe casting wraps uint64 labels past int64's range negative,
        # which flags them: a flag only costs an exact check.
        lab = np.concatenate(lab_arrays, dtype=np.int64, casting="unsafe")
        out = lab < 0
        if n_labels is not None:
            out |= lab >= n_labels
        if out.any():
            owner = np.repeat(np.arange(len(lab_arrays)),
                              [len(a) for a in lab_arrays])
            exact.update((id(lab_of[j]), lab_of[j])
                         for j in np.unique(owner[out]))
    return exact


def validate_pairs(pairs: Sequence[tuple], *, n_labels: int | None = None,
                   stats: dict | None = None) -> tuple[np.ndarray, tuple]:
    """Split a batch of graph pairs into valid and quarantined.

    Returns `(valid_idx, records)`: `valid_idx` the int64 positions of pairs
    where BOTH sides pass, `records` a tuple of `InvalidGraph` (one per bad
    graph — a pair with two bad sides yields two records), exactly what
    `graph_problems` on each graph gives. Distinct graph *objects* are
    validated once per call (1-vs-N batches repeat the query and hot corpus
    dicts; the memo is per-call only, like the engine's graph-key memo,
    because id() values are not stable across GC). The grouped pass of the
    module docstring decides which graphs `graph_problems` checks alone;
    their number adds to `stats["validate_exact_graphs"]`.
    """
    distinct: dict = {}                # id(graph) -> graph, first seen first
    malformed = False
    for pair in pairs:
        if isinstance(pair, (tuple, list)) and len(pair) == 2:
            distinct[id(pair[0])] = pair[0]
            distinct[id(pair[1])] = pair[1]
        else:
            malformed = True
    exact = _needs_exact(distinct.values(), n_labels)
    if stats is not None:
        stats["validate_exact_graphs"] = (
            stats.get("validate_exact_graphs", 0) + len(exact))
    problems = {key: graph_problems(g, n_labels=n_labels)
                for key, g in exact.items()}
    if not malformed and not any(problems.values()):
        return np.arange(len(pairs), dtype=np.int64), ()
    records: list[InvalidGraph] = []
    valid: list[int] = []
    for i, pair in enumerate(pairs):
        if not isinstance(pair, (tuple, list)) or len(pair) != 2:
            records.append(InvalidGraph(i, 0, ("not a (g1, g2) pair",)))
            continue
        ok = True
        for side, g in enumerate(pair):
            found = problems.get(id(g))
            if found:
                ok = False
                records.append(InvalidGraph(i, side, tuple(found)))
        if ok:
            valid.append(i)
    return np.asarray(valid, np.int64), tuple(records)
