"""Seeded synthetic graph families, parameterized by a configuration's
`graphs` block (host numpy, no program imports).

`molecule` is the AIDS-like surrogate of the repository's
`data/graphs.py` (random spanning tree plus a few extra bonds, uniform
node labels, `edit` pairs with a known GED bound), corrected to reach the
published averages: node counts are rounded rather than truncated, and
extra edges are drawn among non-edges so none is lost to a collision.
`ego` is an IMDB-like ego network: one ego node linked to every other node
and a union of random cliques (cast lists) over the rest, grown until the
graph holds its share of the published edge count.

Every graph is a fresh dict {"adj": [n, n] float32 0/1, "labels": [n]
int32}.
"""

from __future__ import annotations

import numpy as np


def node_count(rng: np.random.Generator, spec: dict) -> int:
    dist = spec["nodes"]
    if dist["dist"] == "normal":
        x = rng.normal(dist["mean"], dist["sd"])
    elif dist["dist"] == "shifted_lognormal":
        x = dist["shift"] + rng.lognormal(dist["mu"], dist["sigma"])
    else:
        raise ValueError(f"unknown node-count distribution {dist['dist']!r}")
    return int(np.clip(np.rint(x), dist["min"], dist["max"]))


def _add_random_edges(rng, adj: np.ndarray, count: int) -> None:
    """Add `count` distinct new undirected edges (fewer only if the graph
    is complete)."""
    n = adj.shape[0]
    free = n * (n - 1) // 2 - int(np.triu(adj, 1).sum())
    for _ in range(min(count, free)):
        while True:
            a, b = rng.integers(0, n, 2)
            if a != b and not adj[a, b]:
                adj[a, b] = adj[b, a] = 1.0
                break


def molecule(rng: np.random.Generator, spec: dict, n: int) -> dict:
    adj = np.zeros((n, n), np.float32)
    perm = rng.permutation(n)
    for i in range(1, n):
        j = perm[rng.integers(0, i)]
        adj[perm[i], j] = adj[j, perm[i]] = 1.0
    _add_random_edges(rng, adj, int(rng.poisson(spec["extra_edges_mean"])))
    labels = rng.integers(0, spec["n_labels"], n).astype(np.int32)
    return {"adj": adj, "labels": labels}


def ego(rng: np.random.Generator, spec: dict, n: int) -> dict:
    adj = np.zeros((n, n), np.float32)
    adj[0, 1:] = adj[1:, 0] = 1.0
    others = n - 1
    target = int(np.rint(spec["clique_fill"] * others * (others - 1) / 2))
    lo, hi = spec["clique_size"]
    while int(np.triu(adj[1:, 1:], 1).sum()) < target:
        size = int(min(others, rng.integers(lo, hi + 1)))
        members = 1 + rng.choice(others, size, replace=False)
        adj[np.ix_(members, members)] = 1.0
        adj[members, members] = 0.0
    labels = rng.integers(0, spec["n_labels"], n).astype(np.int32)
    return {"adj": adj, "labels": labels}


FAMILIES = {"molecule": molecule, "ego": ego}


def graph(rng: np.random.Generator, spec: dict, n: int | None = None) -> dict:
    """One graph of the family; `n` nodes, or a count drawn from `rng`."""
    if n is None:
        n = node_count(rng, spec)
    return FAMILIES[spec["family"]](rng, spec, n)


def sized_graphs(rng, spec: dict, count: int, size_seed: int) -> list[dict]:
    """`count` graphs whose node counts come from `size_seed` and whose
    edges and labels come from `rng`: every run seed gets the same sizes
    in the same places, and so the same compiled shapes."""
    sizes = np.random.default_rng(size_seed)
    return [graph(rng, spec, node_count(sizes, spec)) for _ in range(count)]


def fixed_graphs(rng, spec: dict, count: int, size_seed: int) -> list[dict]:
    """`count` graphs whose node counts and edges come from `size_seed`
    and whose labels come from `rng`: every run seed gets the same graphs
    up to their labels, and so the same work in the same places."""
    structure = np.random.default_rng(size_seed)
    out = []
    for _ in range(count):
        g = graph(structure, spec)
        g["labels"] = rng.integers(0, spec["n_labels"],
                                   len(g["labels"])).astype(np.int32)
        out.append(g)
    return out


def edit(rng: np.random.Generator, g: dict, n_edits: int,
         n_labels: int) -> dict:
    """`n_edits` random edge additions, edge deletions or relabels; the node
    count is kept, so the GED is at most `n_edits`."""
    adj = g["adj"].copy()
    labels = g["labels"].copy()
    n = adj.shape[0]
    for _ in range(n_edits):
        op = rng.integers(0, 3)
        if op == 0 and n > 1:
            a, b = rng.integers(0, n, 2)
            if a != b:
                adj[a, b] = adj[b, a] = 1.0
        elif op == 1:
            rr, cc = np.nonzero(np.triu(adj, 1))
            if len(rr):
                i = rng.integers(0, len(rr))
                adj[rr[i], cc[i]] = adj[cc[i], rr[i]] = 0.0
        else:
            labels[rng.integers(0, n)] = rng.integers(0, n_labels)
    return {"adj": adj, "labels": labels}


def ged_target(n_edits: int, n1: int, n2: int) -> float:
    """SimGNN's label: exp(-GED / ((n1 + n2) / 2))."""
    return float(np.exp(-2.0 * n_edits / (n1 + n2)))


def independent_pairs(rng, spec: dict, n_pairs: int,
                      size_seed: int) -> list[tuple]:
    """Random query pairs of independent graphs (the paper's 10,000 random
    pairs), shaped by `size_seed` and labelled from `rng`."""
    gs = fixed_graphs(rng, spec, 2 * n_pairs, size_seed)
    return list(zip(gs[0::2], gs[1::2]))


def edit_pairs(rng, spec: dict, n_pairs: int, max_edits: int,
               sizes: np.random.Generator):
    """(pairs, targets): each pair is (g, edit(g, k)) with k uniform in
    [0, max_edits] and SimGNN's GED-derived target; node counts from
    `sizes`."""
    pairs, targets = [], []
    for _ in range(n_pairs):
        g1 = graph(rng, spec, node_count(sizes, spec))
        k = int(rng.integers(0, max_edits + 1))
        g2 = edit(rng, g1, k, spec["n_labels"])
        pairs.append((g1, g2))
        targets.append(ged_target(k, g1["adj"].shape[0], g2["adj"].shape[0]))
    return pairs, np.asarray(targets, np.float32)


def realized(graphs) -> dict:
    """Mean nodes and undirected edges, and the node range, of a graph
    list."""
    nodes = np.asarray([g["adj"].shape[0] for g in graphs], np.float64)
    edges = np.asarray([np.triu(g["adj"], 1).sum() for g in graphs],
                       np.float64)
    return {"graphs": len(graphs), "mean_nodes": float(nodes.mean()),
            "mean_edges": float(edges.mean()),
            "min_nodes": int(nodes.min()), "max_nodes": int(nodes.max()),
            "over_64": int((nodes > 64).sum())}
