"""Property-based invariants for the pair-packing planner (hypothesis):
over random graph sizes, degrees and budgets —

  * every pair lands in exactly one live tile slot (`pair_index` is a
    permutation of the input order under `pair_mask`);
  * segment IDs are contiguous per pair and sized exactly to each graph;
  * unpacking a packed `[T, P]` score tile recovers the input order;
  * `with_edges=True` CSR+COO round-trips the normalized adjacency's
    non-zeros exactly (count AND values);
  * the placement and every packed array are bitwise those of the plain
    first-fit-decreasing scan and per-pair fill (`_oracle_pack`), on both
    packing paths, while the first-fit makes fewer tile checks.

Each property is a plain `_check_*` helper driven by a seeded generator so
the invariants are runnable without hypothesis too; the hypothesis wrappers
explore the (seed, n_pairs, budget) space in CI.
"""

import numpy as np
import pytest

from repro.core.batching import (PackedPairBatch, pack_pairs,
                                 packed_pair_edges, unpack_pair_scores)
from repro.core.gcn import normalized_adjacency
from repro.data.graphs import random_graph

pytest.importorskip("hypothesis", reason="hypothesis not installed "
                    "(pip install -r requirements-dev.txt)")
from hypothesis import given, settings, strategies as st  # noqa: E402


def _random_pairs(seed: int, n_pairs: int, node_budget: int,
                  max_degree: float):
    rng = np.random.default_rng(seed)
    deg = None if max_degree <= 0 else float(rng.uniform(1.0, max_degree))
    return [(random_graph(rng, int(rng.integers(2, node_budget + 1)),
                          avg_degree=deg),
             random_graph(rng, int(rng.integers(2, node_budget + 1)),
                          avg_degree=deg))
            for _ in range(n_pairs)]


def _check_slots_and_segments(seed, n_pairs, node_budget, max_degree):
    pairs = _random_pairs(seed, n_pairs, node_budget, max_degree)
    packed, stats = pack_pairs(pairs, node_budget)
    live = np.asarray(packed.pair_mask) > 0
    idxs = np.asarray(packed.pair_index)[live]
    # exactly one live slot per input pair, none invented
    assert sorted(idxs.tolist()) == list(range(n_pairs))
    assert stats["n_pairs"] == n_pairs
    for side, (seg_a, mask_a) in enumerate(
            ((packed.seg1, packed.mask1), (packed.seg2, packed.mask2))):
        seg = np.asarray(seg_a)
        mask = np.asarray(mask_a) > 0
        for t in range(seg.shape[0]):
            for p in np.flatnonzero(live[t]):
                nodes = np.flatnonzero((seg[t] == p) & mask[t])
                pair = pairs[int(np.asarray(packed.pair_index)[t, p])]
                # sized exactly to the packed graph, contiguous run
                assert len(nodes) == pair[side]["adj"].shape[0]
                assert np.array_equal(nodes,
                                      np.arange(nodes[0], nodes[-1] + 1))
            # no live node belongs to a dead slot
            assert set(np.unique(seg[t][mask[t]])) <= set(
                np.flatnonzero(live[t]))


def _check_unpack_roundtrip(seed, n_pairs, node_budget, max_degree):
    pairs = _random_pairs(seed, n_pairs, node_budget, max_degree)
    packed, _ = pack_pairs(pairs, node_budget)
    rng = np.random.default_rng(seed + 1)
    scores_tp = rng.normal(size=np.asarray(packed.pair_mask).shape).astype(
        np.float32)
    out = unpack_pair_scores(scores_tp, packed, n_pairs)
    live = np.asarray(packed.pair_mask) > 0
    pair_index = np.asarray(packed.pair_index)
    for t, p in zip(*np.nonzero(live)):
        assert out[pair_index[t, p]] == scores_tp[t, p]


def _check_edges_roundtrip(seed, n_pairs, node_budget, max_degree,
                           nbr_budget):
    pairs = _random_pairs(seed, n_pairs, node_budget, max_degree)
    edge_budget = None if nbr_budget is None else node_budget * nbr_budget
    packed, stats = pack_pairs(pairs, node_budget, with_edges=True,
                               edge_budget=edge_budget)
    nb = packed.node_budget
    for side, (adj, mask, csr, ov) in enumerate((
            (packed.adj1, packed.mask1, packed.edges.edges1,
             packed.edges.overflow1),
            (packed.adj2, packed.mask2, packed.edges.edges2,
             packed.edges.overflow2))):
        a_norm = np.asarray(normalized_adjacency(np.asarray(adj),
                                                 np.asarray(mask)))
        nnz = int(np.count_nonzero(a_norm))
        n_csr = int(np.asarray(csr.edge_mask).sum())
        n_ov = int(np.asarray(ov.edge_mask).sum())
        # nnz round-trips exactly: every A' non-zero is in CSR or COO,
        # no pad slot carries weight
        assert n_csr + n_ov == nnz
        key = "nnz_lhs" if side == 0 else "nnz_rhs"
        assert stats[key] == nnz
        # value-exact dense reconstruction (weights copied, never recomputed)
        dense = np.zeros_like(a_norm)
        for eb in (csr, ov):
            snd = np.asarray(eb.senders)
            rcv = np.asarray(eb.receivers)
            w = np.asarray(eb.weights) * np.asarray(eb.edge_mask)
            for t in range(dense.shape[0]):
                np.add.at(dense[t], (rcv[t], snd[t]), w[t])
        assert np.array_equal(dense, a_norm)
        # CSR plane layout: slot s holds an in-edge of node s % NB
        rcv = np.asarray(csr.receivers)
        assert np.array_equal(rcv % nb,
                              np.broadcast_to(np.arange(nb * (rcv.shape[-1]
                                                              // nb)) % nb,
                                              rcv.shape))


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 2**31 - 1), st.integers(1, 12),
       st.sampled_from((16, 32, 64)), st.sampled_from((0.0, 3.0, 6.0)))
def test_every_pair_in_exactly_one_slot_with_contiguous_segments(
        seed, n_pairs, node_budget, max_degree):
    _check_slots_and_segments(seed, n_pairs, node_budget, max_degree)


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 2**31 - 1), st.integers(1, 12),
       st.sampled_from((16, 32, 64)), st.sampled_from((0.0, 4.0)))
def test_unpack_recovers_packed_scores(seed, n_pairs, node_budget,
                                       max_degree):
    _check_unpack_roundtrip(seed, n_pairs, node_budget, max_degree)


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 2**31 - 1), st.integers(1, 8),
       st.sampled_from((16, 32, 64)), st.sampled_from((0.0, 3.0, 8.0)),
       st.sampled_from((None, 4, 8)))
def test_packed_edges_roundtrip_adjacency_nnz(seed, n_pairs, node_budget,
                                              max_degree, nbr_budget):
    _check_edges_roundtrip(seed, n_pairs, node_budget, max_degree,
                           nbr_budget)


# ------------------------------------------ placement parity with plain FFD

def _oracle_pack(pairs, nb, slots_per_tile):
    """The plain first-fit-decreasing scan over every tile and the per-pair
    fill, which `pack_pairs` must reproduce bitwise. Returns the host arrays
    in `PackedPairBatch` field order and the number of tile checks made."""
    sizes = [(a["adj"].shape[0], b["adj"].shape[0]) for a, b in pairs]
    cap = slots_per_tile or len(pairs) or 1
    order = sorted(range(len(pairs)), key=lambda i: -(sizes[i][0] + sizes[i][1]))
    tiles, probes = [], 0                # [used1, used2, [pair idx]]
    for i in order:
        n1, n2 = sizes[i]
        for t in tiles:
            probes += 1
            if t[0] + n1 <= nb and t[1] + n2 <= nb and len(t[2]) < cap:
                t[0] += n1
                t[1] += n2
                t[2].append(i)
                break
        else:
            tiles.append([n1, n2, [i]])
    n_tiles = len(tiles) or 1
    most = max((len(t[2]) for t in tiles), default=1)
    p = slots_per_tile or max(8, -(-most // 8) * 8)
    sides = [(np.zeros((n_tiles, nb, nb), np.float32),
              np.zeros((n_tiles, nb), np.int32),
              np.zeros((n_tiles, nb), np.float32),
              np.zeros((n_tiles, nb), np.int32)) for _ in range(2)]
    pair_mask = np.zeros((n_tiles, p), np.float32)
    pair_index = np.zeros((n_tiles, p), np.int32)
    for t, (_, _, items) in enumerate(tiles):
        offs = [0, 0]
        for s, idx in enumerate(items):
            pair_mask[t, s] = 1.0
            pair_index[t, s] = idx
            for side, g in enumerate(pairs[idx]):
                adj, labels, mask, seg = sides[side]
                n, o = g["adj"].shape[0], offs[side]
                adj[t, o:o + n, o:o + n] = g["adj"]
                labels[t, o:o + n] = g["labels"]
                mask[t, o:o + n] = 1.0
                seg[t, o:o + n] = s
                offs[side] += n
    return (*sides[0], *sides[1], pair_mask, pair_index), probes


def _sized_graph(rng, n, density):
    upper = np.triu(rng.random((n, n)) < density, 1)
    return {"adj": (upper | upper.T).astype(np.float32),
            "labels": rng.integers(0, 29, n).astype(np.int32)}


def _pool(kind, node_budget, seed=0):
    """Pairs whose node counts follow a traffic shape: `aids` N(25.6, 8) in
    [5, NB]; `imdb` 6.5 + LogNormal(1.39, 1) in [7, NB], denser; `tiny`
    1-3 nodes a side, so the slot cap binds; `lopsided` big lhs and tiny
    rhs, so the lhs budget binds; `single` one pair; `empty` none."""
    rng = np.random.default_rng(seed)
    if kind in ("aids", "imdb"):
        x = (rng.normal(25.6, 8.0, (1000, 2)) if kind == "aids"
             else 6.5 + rng.lognormal(1.39, 1.0, (1000, 2)))
        lo = 5 if kind == "aids" else 7
        sizes = np.clip(np.rint(x), lo, node_budget).astype(int)
    elif kind == "tiny":
        sizes = rng.integers(1, 4, (300, 2))
    elif kind == "lopsided":
        sizes = np.stack([rng.integers(20, 41, 200),
                          rng.integers(1, 4, 200)], 1)
    else:
        sizes = np.full((int(kind == "single"), 2), 9)
    density = 0.45 if kind == "imdb" else 0.1
    return [(_sized_graph(rng, int(a), density),
             _sized_graph(rng, int(b), density)) for a, b in sizes]


_PARITY_CASES = (
    [(kind, nb, slots) for kind in ("aids", "imdb") for nb in (32, 64, 128)
     for slots in (8, 16, None)]
    + [("tiny", 64, 8), ("lopsided", 64, 16), ("lopsided", 64, None),
       ("single", 64, 16), ("empty", 64, 16), ("empty", 64, None)])


@pytest.mark.parametrize("with_edges", (False, True))
@pytest.mark.parametrize("kind,node_budget,slots", _PARITY_CASES)
def test_pack_pairs_matches_plain_first_fit(kind, node_budget, slots,
                                            with_edges):
    pairs = _pool(kind, node_budget)
    want, oracle_probes = _oracle_pack(pairs, node_budget, slots)
    edge_budget = 4 * node_budget if with_edges else None
    packed, stats = pack_pairs(pairs, node_budget, slots_per_tile=slots,
                               with_edges=with_edges, edge_budget=edge_budget)
    got = [np.asarray(a) for a in packed[:10]]
    for name, g, w in zip(PackedPairBatch._fields, got, want):
        assert g.dtype == w.dtype and np.array_equal(g, w), name
    assert stats["n_tiles"] == want[-1].shape[0]
    if kind == "tiny":
        assert np.asarray(packed.pair_mask).all(axis=1)[:-1].all()
    if len(pairs) == 1000:
        assert stats["ffd_probes"] < oracle_probes
    else:
        assert stats["ffd_probes"] <= oracle_probes
    if with_edges:
        expect = packed_pair_edges(PackedPairBatch(*want), edge_budget)
        for e_got, e_want in zip(packed.edges, expect):
            for g, w in zip(e_got, e_want):
                assert np.array_equal(np.asarray(g), np.asarray(w))
