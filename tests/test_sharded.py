"""Device-sharded execution layer tests (DESIGN.md §16).

The parity matrix: every sharded executor — packed score (dense + sparse),
data-parallel loss_and_grad, per-shard search scans — against its
single-device twin at device counts {1, 2, 8}. Scores are pinned BITWISE
(same block tiles, same dot products, tile-independent programs); grads at
the 1e-6 gate (the cross-device psum re-associates the chunk sums).

Multi-device rows need simulated host devices; run the full matrix with

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python -m pytest -q tests/test_sharded.py

(CI does — see the tier-1 `sharded` step). Under a plain single-device run
the multi-device rows skip and the policy/dtype/span rows still execute.
"""

import jax
import numpy as np
import pytest

from repro.core.batching import EdgeBatch, PackedEdges, pack_pairs
from repro.core.engine import ScoringEngine
from repro.core.profile import TraceRecorder, cost_key
from repro.core.simgnn import SimGNNConfig, init_simgnn_params
from repro.data.graphs import random_graph
from repro.distributed.sharding import tile_runtime
from repro.kernels import ops
from repro.serve.search import SimilaritySearchServer
from repro.testing import faults

NDEV = jax.local_device_count()

def needs(n):
    return pytest.mark.skipif(
        NDEV < n, reason=f"needs {n} host devices (run under "
        "XLA_FLAGS=--xla_force_host_platform_device_count=8)")


CFG = SimGNNConfig()
PARAMS = init_simgnn_params(jax.random.PRNGKey(0), CFG)
DEVICE_COUNTS = [1, 2, 8]


def _mixed_pairs(seed, n_pairs, max_n=32, avg_degree=4):
    rng = np.random.default_rng(seed)
    return [(random_graph(rng, int(rng.integers(5, max_n + 1)),
                          avg_degree=avg_degree),
             random_graph(rng, int(rng.integers(5, max_n + 1)),
                          avg_degree=avg_degree))
            for _ in range(n_pairs)]


PAIRS = _mixed_pairs(0, 48)
TARGETS = np.linspace(0.0, 1.0, len(PAIRS)).astype(np.float32)

_BASE = {}


def _single_device_engine(path):
    if path not in _BASE:
        _BASE[path] = ScoringEngine(PARAMS, CFG, path=path)
    return _BASE[path]


# ----------------------------------------------------------- shape policy

def test_sharded_tile_plan_balances_tiles_over_devices():
    """Few tiles on many devices shrink tile_block instead of padding to
    devices x policy-block (the planner's tile -> device balance)."""
    nb = ops.packed_node_budget(CFG.max_nodes)
    policy = ops.sharded_tile_block(nb, sparse=True)
    target, tb = ops.sharded_tile_plan(20, nb, 8, sparse=True)
    assert target == 32 and tb == min(policy, 4)
    # every device owns a whole number of tile_block programs
    for t in (1, 7, 20, 51, 128):
        for nd in DEVICE_COUNTS:
            target, tb = ops.sharded_tile_plan(t, nb, nd, sparse=True)
            assert target >= t and target % (nd * tb) == 0
            assert tb <= policy
    # one device degenerates to the unsharded power-of-two pad
    target, tb = ops.sharded_tile_plan(20, nb, 1)
    assert target == 32 and tb == ops.sharded_tile_block(nb)


def test_plan_devices_clamps_small_batches():
    """Tiny batches don't spread over the mesh: each device must see at
    least MIN_PACK_PAIRS pairs, halving the count until it does."""
    off_mesh = ScoringEngine(PARAMS, CFG, path="packed_sparse")
    assert off_mesh.plan(PAIRS).devices == 1


# ------------------------------------------------- score parity matrix

@pytest.mark.parametrize("nd", DEVICE_COUNTS)
@pytest.mark.parametrize("path", ["packed_dense", "packed_sparse"])
def test_score_parity_bitwise(path, nd):
    if NDEV < nd:
        pytest.skip(f"needs {nd} host devices")
    ref = _single_device_engine(path).score(PAIRS)
    eng = ScoringEngine(PARAMS, CFG, path=path, runtime=tile_runtime(nd))
    plan = eng.plan(PAIRS)
    assert plan.devices == nd
    got = eng.score(PAIRS)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    if nd > 1:
        ps = eng.last_pack_stats
        assert ps["devices"] == nd
        assert len(ps["device_occupancy"]) == nd
        assert 0.0 < sum(ps["device_occupancy"]) / nd <= 1.0
        assert eng.last_plan.devices == nd


# ---------------------------------------------------- train parity matrix

@pytest.mark.parametrize("nd", DEVICE_COUNTS)
def test_grad_parity(nd):
    if NDEV < nd:
        pytest.skip(f"needs {nd} host devices")
    base = _single_device_engine("packed_sparse")
    ref_s, ref_g = base.loss_and_grad(PAIRS, TARGETS)
    eng = ScoringEngine(PARAMS, CFG, path="packed_sparse",
                        runtime=tile_runtime(nd))
    s, g = eng.loss_and_grad(PAIRS, TARGETS)
    assert float(np.max(np.abs(np.asarray(s) - np.asarray(ref_s)))) <= 1e-6
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(ref_g)):
        assert float(np.max(np.abs(np.asarray(a) - np.asarray(b)))) <= 1e-6


# --------------------------------------------------- degradation ladder

@needs(2)
def test_dead_shard_collapses_to_single_device():
    """A dead shard (fault at the sharded executor) costs the mesh, never
    the batch: the §12 ladder's new rung re-serves the call single-device,
    bitwise equal to an unsharded engine, and the degradation is counted
    under the `path@Nd` rung name on health()."""
    ref = _single_device_engine("packed_sparse").score(PAIRS)
    eng = ScoringEngine(PARAMS, CFG, path="packed_sparse",
                        runtime=tile_runtime(2))
    with faults.inject("sharded:packed_sparse", "raise", times=1):
        got = eng.score(PAIRS)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    assert "packed_sparse@2d" in eng.last_plan.degraded_from
    h = eng.health()
    assert h["counters"]["errors:packed_sparse@2d"] == 1
    assert any(k.startswith("packed_sparse@2d[") for k in h["breakers"])
    # healthy mesh next call: sharded again, no residual degradation
    got = eng.score(PAIRS)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    assert eng.last_plan.degraded_from == ()


@needs(2)
def test_dead_shard_in_training_collapses():
    base = _single_device_engine("packed_sparse")
    ref_s, ref_g = base.loss_and_grad(PAIRS, TARGETS)
    eng = ScoringEngine(PARAMS, CFG, path="packed_sparse",
                        runtime=tile_runtime(2))
    with faults.inject("sharded:train:packed_sparse", "raise", times=1):
        s, g = eng.loss_and_grad(PAIRS, TARGETS)
    assert float(np.max(np.abs(np.asarray(s) - np.asarray(ref_s)))) <= 1e-6
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(ref_g)):
        assert float(np.max(np.abs(np.asarray(a) - np.asarray(b)))) <= 1e-6
    assert "packed_sparse@2d" in eng.last_plan.degraded_from
    assert eng.health()["counters"]["errors:train:packed_sparse@2d"] == 1


# -------------------------------------------------- int16 index planes

def test_int16_edge_planes_bitwise():
    """Narrow neighbor-plane dtype (node_budget < 2**15 -> int16): the
    planes ARE int16 and score bit-identically to an int32 copy."""
    nb = ops.packed_node_budget(CFG.max_nodes)
    assert nb < 2 ** 15
    packed, _ = pack_pairs(PAIRS, nb, slots_per_tile=max(8, nb // 4),
                           with_edges=True)
    e = packed.edges
    for side in (e.edges1, e.edges2):
        assert np.asarray(side.senders).dtype == np.int16
    for side in (e.overflow1, e.overflow2):
        assert np.asarray(side.senders).dtype == np.int16
        assert np.asarray(side.receivers).dtype == np.int16

    def widen(eb):
        return EdgeBatch(np.asarray(eb.senders, np.int32),
                         np.asarray(eb.receivers, np.int32),
                         eb.weights, eb.edge_mask)

    wide = packed._replace(edges=PackedEdges(
        widen(e.edges1), widen(e.edges2),
        widen(e.overflow1), widen(e.overflow2)))
    np.testing.assert_array_equal(
        np.asarray(ops.pair_score_sparse(PARAMS, packed)),
        np.asarray(ops.pair_score_sparse(PARAMS, wide)))


# ------------------------------------------------- per-shard search scans

def _corpus_and_queries():
    rng = np.random.default_rng(7)
    corpus = [random_graph(rng, int(rng.integers(6, 24)), avg_degree=4)
              for _ in range(64)]
    queries = [random_graph(rng, int(rng.integers(6, 24)), avg_degree=4)
               for _ in range(4)]
    return corpus, queries


@needs(8)
@pytest.mark.parametrize("nd", [2, 8])
def test_sharded_search_topk_bit_identical(nd):
    """Per-shard prefilter scans + host merge return the same top-k,
    bit-for-bit (indices AND scores), as the unsharded two-stage path."""
    corpus, queries = _corpus_and_queries()
    ref = SimilaritySearchServer(PARAMS, CFG, shard_rows=8)
    ref.index(corpus)
    srv = SimilaritySearchServer(PARAMS, CFG, shard_rows=8,
                                 runtime=tile_runtime(nd))
    srv.index(corpus)
    assert srv.health()["prefilter"]["spans"] == nd
    want = ref.search(queries, k=10, mode="two_stage", prefilter_m=16)
    got = srv.search(queries, k=10, mode="two_stage", prefilter_m=16)
    for (wi, ws), (gi, gs) in zip(want, got):
        np.testing.assert_array_equal(gi, wi)
        np.testing.assert_array_equal(gs, ws)
    assert srv.engine.counters["prefilter_span_scans"] > 0
    assert srv.engine.last_plan.devices == nd


@needs(2)
def test_sharded_search_dead_span_degrades():
    corpus, queries = _corpus_and_queries()
    srv = SimilaritySearchServer(PARAMS, CFG, shard_rows=8,
                                 runtime=tile_runtime(2))
    srv.index(corpus)
    exact = srv.search(queries, k=10, mode="exact")
    with faults.inject("prefilter", "raise", times=1):
        got = srv.search(queries, k=10, mode="two_stage", prefilter_m=16)
    for (wi, ws), (gi, gs) in zip(exact, got):
        np.testing.assert_array_equal(gi, wi)
    assert srv.stats.prefilter_degraded == len(queries)
    assert srv.health()["counters"]["prefilter_degraded"] == len(queries)


def test_prefilter_spans_block_aligned():
    srv = SimilaritySearchServer(PARAMS, CFG, shard_rows=8)
    srv.engine.n_devices = 4                  # spans follow the mesh width
    spans = srv._prefilter_spans(70, 8)
    assert spans[0][0] == 0 and spans[-1][1] == 70
    for (lo, hi), (lo2, _) in zip(spans, spans[1:]):
        assert hi == lo2 and lo % 8 == 0
    assert len(spans) <= 4
    # fewer blocks than devices collapses to fewer spans
    srv.engine.n_devices = 8
    assert len(srv._prefilter_spans(10, 8)) == 2


# ----------------------------------------------------- profile schema v2

@needs(8)
def test_trace_records_carry_device_count():
    rec = TraceRecorder(capacity=64)
    eng = ScoringEngine(PARAMS, CFG, path="packed_sparse",
                        runtime=tile_runtime(8), recorder=rec)
    eng.score(PAIRS)
    rows = [r for r in rec.records() if r.kind == "score"]
    assert rows and rows[-1].n_devices == 8
    assert cost_key(rows[-1].path, rows[-1].n_devices) == "packed_sparse@8d"


def test_cost_key_single_device_is_bare_path():
    assert cost_key("packed_dense", 1) == "packed_dense"
    assert cost_key("train:packed_dense", 4) == "train:packed_dense@4d"
