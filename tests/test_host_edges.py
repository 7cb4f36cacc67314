"""Host-built packed-sparse A' edge lists (`core.batching`, DESIGN.md §9).

`pack_pairs(with_edges=True)` builds the ELL planes and the COO overflow
from the host tile arrays it has just filled, and copies only the edge
lists to the device. Here they are held, array for array and bit for bit,
to an oracle built the other way: `gcn.normalized_adjacency` of the packed
adjacency on the default backend, `np.nonzero` of the result, and the same
ELL layout. The byte counters pin that no dense `[T, NB, NB]` array goes to
the device and nothing comes back, and `degree_rsqrt_misses` that a warm
degree table makes no device call.
"""

import jax
import numpy as np
import pytest

from repro.core import batching
from repro.core.batching import next_pow2, pack_pairs, packed_pair_edges
from repro.core.engine import ScoringEngine
from repro.core.gcn import normalized_adjacency
from repro.core.simgnn import SimGNNConfig, init_simgnn_params
from repro.data.graphs import random_graph

PLANES = ("senders", "receivers", "weights", "edge_mask")


def _graph(adj: np.ndarray, rng) -> dict:
    return {"adj": adj.astype(np.float32),
            "labels": rng.integers(0, 29, adj.shape[0]).astype(np.int32)}


def _sizes(rng, lo, hi, nb):
    return int(rng.integers(min(lo, nb), min(hi, nb) + 1))


def _aids(rng, nb):
    return random_graph(rng, _sizes(rng, 9, 40, nb), avg_degree=2.1)


def _imdb(rng, nb):
    return random_graph(rng, _sizes(rng, 7, 60, nb), avg_degree=8.0)


def _weighted(rng, nb):
    """Symmetric dyadic weights (k/4) and self loops of weight 1/2, 1 or
    -1 (that last cancels A + I's diagonal): every row sums exactly in
    float32, in any order."""
    g = random_graph(rng, _sizes(rng, 5, 40, nb), avg_degree=3.0)
    n = g["adj"].shape[0]
    w = rng.integers(1, 13, (n, n)) / 4.0
    adj = g["adj"] * np.triu(w, 1)
    adj = adj + adj.T
    loops = rng.random(n) < 0.3
    adj[loops, loops] = rng.choice([0.5, 1.0, -1.0], int(loops.sum()))
    return _graph(adj, rng)


def _hub(rng, nb):
    """A star: the hub's in-degree outruns any small neighbor budget."""
    n = _sizes(rng, 12, 40, nb)
    adj = np.zeros((n, n), np.float32)
    adj[0, 1:] = adj[1:, 0] = 1.0
    return _graph(adj, rng)


def _single(rng, nb):
    return _graph(np.zeros((1, 1), np.float32), rng)


#: pool -> (graph maker, neighbors per node of the requested edge budget;
#: None sizes it to the realized in-degree).
POOLS = {
    "aids": (_aids, 4),
    "imdb": (_imdb, None),
    "weighted_self_loops": (_weighted, 4),
    "degree_tail_to_overflow": (_hub, 2),
    "one_node_graphs": (_single, None),
}


def _pairs(pool, nb, seed, n=12):
    make = POOLS[pool][0]
    rng = np.random.default_rng(seed)
    return [(make(rng, nb), make(rng, nb)) for _ in range(n)]


def _oracle(adj_mask, edge_budget, overflow_budget):
    """The 16 edge arrays (per side: ELL senders, receivers, weights, mask,
    then the overflow's four), from the device-normalized adjacency."""
    nb = adj_mask[0][0].shape[-1]
    found = []
    for adj, mask in adj_mask:
        a_norm = np.asarray(normalized_adjacency(adj, mask))
        tiles, rows, cols = np.nonzero(a_norm)
        key = tiles * nb + rows
        rank = np.arange(key.size) - np.searchsorted(key, key, side="left")
        found.append((a_norm.shape[0], tiles, rows, cols,
                      a_norm[tiles, rows, cols], rank))
    if edge_budget is None:
        d = next_pow2(max(int(f[5].max()) + 1 if f[5].size else 0
                          for f in found), floor=2)
    else:
        d = edge_budget // nb
    ov_need = max((int(np.bincount(f[1][f[5] >= d]).max())
                   for f in found if (f[5] >= d).any()), default=0)
    e_ov = next_pow2(ov_need, floor=max(8, overflow_budget))
    out = []
    for t, tiles, rows, cols, w, rank in found:
        ell = [np.zeros((t, d, nb), np.int16),
               np.broadcast_to(np.arange(nb, dtype=np.int16),
                               (t, d, nb)).copy(),
               np.zeros((t, d, nb), np.float32),
               np.zeros((t, d, nb), np.float32)]
        ov = [np.zeros((t, e_ov), np.int16), np.zeros((t, e_ov), np.int16),
              np.zeros((t, e_ov), np.float32),
              np.zeros((t, e_ov), np.float32)]
        used = np.zeros(t, np.int64)
        for i in range(tiles.size):
            tt, r, c, k = tiles[i], rows[i], cols[i], rank[i]
            if k < d:
                ell[0][tt, k, r], ell[2][tt, k, r] = c, w[i]
                ell[3][tt, k, r] = 1.0
            else:
                j = used[tt]
                used[tt] += 1
                ov[0][tt, j], ov[1][tt, j], ov[2][tt, j] = c, r, w[i]
                ov[3][tt, j] = 1.0
        out += [a.reshape(t, d * nb) for a in ell] + ov
    return out


def _arrays(edges):
    return [np.asarray(getattr(eb, f))
            for side in ((edges.edges1, edges.overflow1),
                         (edges.edges2, edges.overflow2))
            for eb in side for f in PLANES]


def _assert_bitwise(got, want):
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        assert np.array_equal(g.view(np.uint8), w.view(np.uint8))


@pytest.mark.parametrize("nb", [32, 64, 128])
@pytest.mark.parametrize("pool", sorted(POOLS))
def test_host_edges_equal_the_device_normalized_oracle(pool, nb):
    pairs = _pairs(pool, nb, seed=nb)
    d = POOLS[pool][1]
    edge_budget = None if d is None else nb * d
    packed, stats = pack_pairs(pairs, nb, with_edges=True,
                               edge_budget=edge_budget)
    assert isinstance(packed.adj1, np.ndarray)
    assert isinstance(packed.adj2, np.ndarray)
    sides = ((packed.adj1, np.asarray(packed.mask1)),
             (packed.adj2, np.asarray(packed.mask2)))
    want = _oracle(sides, edge_budget, 8)
    _assert_bitwise(_arrays(packed.edges), want)
    if pool == "degree_tail_to_overflow":
        assert want[7].sum() + want[15].sum() > 0
    assert (stats["nnz_lhs"], stats["nnz_rhs"]) == (
        int(want[3].sum() + want[7].sum()),
        int(want[11].sum() + want[15].sum()))


def test_arbitrary_float_weights_keep_the_structure():
    """Rows whose weights do not sum exactly have no order-free float32
    degree: the device's reduction order is its own. The edges are still
    the same, and each weight within a few ulp."""
    rng = np.random.default_rng(5)
    pairs = []
    for _ in range(8):
        gs = []
        for _ in range(2):
            g = random_graph(rng, int(rng.integers(9, 40)), avg_degree=3.0)
            w = rng.random(g["adj"].shape).astype(np.float32)
            gs.append(_graph(g["adj"] * (w + w.T), rng))
        pairs.append(tuple(gs))
    packed, _ = pack_pairs(pairs, 64, with_edges=True, edge_budget=256)
    want = _oracle(((packed.adj1, np.asarray(packed.mask1)),
                    (packed.adj2, np.asarray(packed.mask2))), 256, 8)
    got = _arrays(packed.edges)
    for i, (g, w) in enumerate(zip(got, want)):
        if i % 4 == 2:                     # weights
            np.testing.assert_allclose(g, w, rtol=1e-6, atol=0)
        else:
            assert np.array_equal(g, w)


def test_pack_pairs_copies_only_the_edge_lists():
    pairs = _pairs("aids", 64, seed=11, n=20)
    pack_pairs(pairs, 64, with_edges=True, edge_budget=256)
    packed, stats = pack_pairs(pairs, 64, with_edges=True, edge_budget=256)
    assert stats["degree_rsqrt_misses"] == 0
    t, nb, p = stats["n_tiles"], 64, stats["slots_per_tile"]
    e, e_ov = stats["edge_budget"], stats["overflow_budget"]
    tiles = t * (2 * nb * (4 + 4 + 4) + p * (4 + 4))
    edges = 2 * t * (e + e_ov) * (2 + 2 + 4 + 4)
    assert (stats["h2d_bytes"], stats["d2h_bytes"]) == (tiles + edges, 0)
    assert isinstance(packed.adj1, np.ndarray)
    assert isinstance(packed.mask1, jax.Array)
    assert isinstance(packed.edges.edges1.weights, jax.Array)


def test_the_fallback_reads_device_adjacency_back_once():
    pairs = _pairs("aids", 64, seed=12)
    with_edges, _ = pack_pairs(pairs, 64, with_edges=True, edge_budget=256)
    plain, _ = pack_pairs(pairs, 64)
    assert isinstance(plain.adj1, jax.Array)
    stats: dict = {}
    edges = packed_pair_edges(plain, 256, stats=stats)
    _assert_bitwise(_arrays(edges), _arrays(with_edges.edges))
    t = plain.adj1.shape[0]
    assert stats["d2h_bytes"] == 2 * (t * 64 * 64 * 4 + t * 64 * 4)


def test_degree_misses_are_counted_once(monkeypatch):
    monkeypatch.setattr(batching, "_INV_SQRT_DEGREE", {})
    pairs = _pairs("weighted_self_loops", 64, seed=13)
    _, first = pack_pairs(pairs, 64, with_edges=True, edge_budget=256)
    assert first["degree_rsqrt_misses"] == len(batching._INV_SQRT_DEGREE) > 0
    _, second = pack_pairs(pairs, 64, with_edges=True, edge_budget=256)
    assert second["degree_rsqrt_misses"] == 0


def test_engine_reports_degree_misses_only_when_nonzero(monkeypatch):
    cfg = SimGNNConfig()
    params = init_simgnn_params(jax.random.PRNGKey(0), cfg)
    pairs = _pairs("aids", 64, seed=14, n=8)
    monkeypatch.setattr(batching, "_INV_SQRT_DEGREE", {})
    eng = ScoringEngine(params, cfg, path="packed_sparse")
    assert eng.health()["counters"] == {"h2d_bytes": 0, "d2h_bytes": 0}
    eng.score(pairs)
    misses = len(batching._INV_SQRT_DEGREE)
    assert misses > 0
    assert eng.health()["counters"]["degree_rsqrt_misses"] == misses
    eng.score(pairs)
    assert eng.health()["counters"]["degree_rsqrt_misses"] == misses
    warm = ScoringEngine(params, cfg, path="packed_sparse")
    warm.score(pairs)
    assert "degree_rsqrt_misses" not in warm.health()["counters"]
