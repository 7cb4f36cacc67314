"""Packed-pair megakernel (kernels/packed_pair.py, DESIGN.md §8) tests:
planner round-trip, parity sweeps (tile budgets / odd batches / bf16),
first-layer one-hot elimination exactness, oversized-query routing,
MicroBatcher flush stats, and pad-neutrality of the shared kernel bodies.

Tolerance policy: the fp32 packed path must match the pure-jnp reference at
the 1e-6 acceptance bound (scores, post-sigmoid); bf16 inputs at the 2e-2
bound from tests/test_megakernel.py.
"""

import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.batching import (bucket_for, bucket_pairs, pack_pairs,
                                 pad_graphs, unpack_pair_scores, EdgeBatch)
from repro.core.engine import ScoringEngine
from repro.core.simgnn import (SimGNNConfig, init_simgnn_params, pair_score,
                               pair_score_from_labels)
from repro.data.graphs import random_graph
from repro.kernels import grad, ops
from repro.kernels.packed_pair import packed_pair_score
from repro.kernels.sparse_pair import sparse_pair_score
from repro.kernels.common import normalize_adjacency_block

CFG = SimGNNConfig()
PARAMS = init_simgnn_params(jax.random.PRNGKey(0), CFG)


def _mixed_pairs(seed, n_pairs, max_n=64):
    rng = np.random.default_rng(seed)
    return [(random_graph(rng, int(rng.integers(5, max_n + 1))),
             random_graph(rng, int(rng.integers(5, max_n + 1))))
            for _ in range(n_pairs)]


def _reference_scores(params, pairs, n_labels=CFG.n_node_labels):
    out = np.zeros(len(pairs), np.float32)
    for b, (lhs, rhs, idxs) in bucket_pairs(pairs, n_labels,
                                            allow_oversize=True).items():
        s = pair_score(params, lhs.adj, lhs.feats, lhs.mask,
                       rhs.adj, rhs.feats, rhs.mask)
        out[idxs] = np.asarray(s)
    return out


# ------------------------------------------------------------------- planner

def test_pack_pairs_round_trip_layout():
    pairs = _mixed_pairs(0, 17)
    packed, stats = pack_pairs(pairs, 64)
    adj = [np.asarray(packed.adj1), np.asarray(packed.adj2)]
    lab = [np.asarray(packed.labels1), np.asarray(packed.labels2)]
    mask = [np.asarray(packed.mask1), np.asarray(packed.mask2)]
    seg = [np.asarray(packed.seg1), np.asarray(packed.seg2)]
    pm, pidx = np.asarray(packed.pair_mask), np.asarray(packed.pair_index)

    assert pm.sum() == len(pairs)
    placed = sorted(pidx[pm > 0].tolist())
    assert placed == list(range(len(pairs)))      # each pair exactly once
    for t in range(pm.shape[0]):
        for side in (0, 1):
            assert mask[side][t].sum() <= 64      # node budget respected
        for p in np.flatnonzero(pm[t] > 0):
            i = pidx[t, p]
            for side, g in enumerate(pairs[i]):
                rows = np.flatnonzero((seg[side][t] == p) & (mask[side][t] > 0))
                n = g["adj"].shape[0]
                assert len(rows) == n             # contiguous segment range
                assert (np.diff(rows) == 1).all()
                o = rows[0]
                np.testing.assert_array_equal(adj[side][t, o:o + n, o:o + n],
                                              g["adj"])
                np.testing.assert_array_equal(lab[side][t, o:o + n],
                                              g["labels"])
    # adjacency is block-diagonal: nothing outside own segment's range
    for side in (0, 1):
        same_seg = (seg[side][:, :, None] == seg[side][:, None, :])
        assert (adj[side] * ~same_seg == 0).all()
    assert 0 < stats["occupancy_lhs"] <= 1.0
    assert stats["slots_per_tile"] % 8 == 0


def test_pack_pairs_rejects_oversize():
    pairs = [(random_graph(np.random.default_rng(0), 80),
              random_graph(np.random.default_rng(1), 10))]
    with pytest.raises(ValueError):
        pack_pairs(pairs, 64)


# -------------------------------------------------------------------- parity

@pytest.mark.parametrize("node_budget", [64, 96, 128])
def test_packed_parity_across_tile_budgets(node_budget):
    pairs = _mixed_pairs(1, 24)
    packed, _ = pack_pairs(pairs, node_budget)
    s = ops.pair_score_packed(PARAMS, packed, interpret=True)
    out = unpack_pair_scores(s, packed, len(pairs))
    np.testing.assert_allclose(out, _reference_scores(PARAMS, pairs),
                               rtol=0, atol=1e-6)


@pytest.mark.parametrize("batch", [1, 7, 13])
def test_packed_parity_odd_batches(batch):
    """Any pair count works: T pads to a tile_block multiple, pad tiles and
    pad pair slots never leak into outputs."""
    pairs = _mixed_pairs(2 + batch, batch)
    packed, _ = pack_pairs(pairs, 64)
    s = ops.pair_score_packed(PARAMS, packed, interpret=True)
    out = unpack_pair_scores(s, packed, len(pairs))
    assert out.shape == (batch,)
    np.testing.assert_allclose(out, _reference_scores(PARAMS, pairs),
                               rtol=0, atol=1e-6)


def test_packed_bf16_inputs():
    """bf16 in / fp32 accumulate: within the 2e-2 bound (labels stay int32)."""
    pairs = _mixed_pairs(5, 12)
    packed, _ = pack_pairs(pairs, 64)
    to16 = lambda t: jax.tree.map(
        lambda x: x.astype(jnp.bfloat16)
        if jnp.issubdtype(x.dtype, jnp.floating) else x, t)
    s16 = ops.pair_score_packed(to16(PARAMS), to16(packed), interpret=True)
    assert s16.dtype == jnp.bfloat16
    out = unpack_pair_scores(s16.astype(jnp.float32), packed, len(pairs))
    ref = _reference_scores(PARAMS, pairs)
    rel = np.max(np.abs(out - ref)) / (np.max(np.abs(ref)) + 1e-9)
    assert rel < 2e-2


def test_packed_variadic_gcn_depth():
    cfg = SimGNNConfig(gcn_dims=(64, 48, 32, 16))
    params = init_simgnn_params(jax.random.PRNGKey(2), cfg)
    pairs = _mixed_pairs(6, 9, max_n=32)
    packed, _ = pack_pairs(pairs, 64)
    s = ops.pair_score_packed(params, packed, interpret=True)
    out = unpack_pair_scores(s, packed, len(pairs))
    ref = np.zeros(len(pairs), np.float32)
    for b, (lhs, rhs, idxs) in bucket_pairs(pairs, cfg.n_node_labels).items():
        ref[idxs] = np.asarray(pair_score(params, lhs.adj, lhs.feats, lhs.mask,
                                          rhs.adj, rhs.feats, rhs.mask))
    np.testing.assert_allclose(out, ref, rtol=0, atol=1e-6)


def _leading_operands(fn, skip: int = 0) -> list[str]:
    """Names of `fn`'s positional tile operands: those before (and
    including) `pair_mask`, after `skip` leading non-operand parameters."""
    names = list(inspect.signature(fn).parameters)[skip:]
    return names[:names.index("pair_mask") + 1]


@pytest.mark.parametrize("sparse", [False, True])
def test_kernel_operands_match_kernels_and_engine(sparse):
    """`PackedPairBatch.kernel_operands` is the one statement of the kernel
    operand order: one operand per leading array parameter of the kernel
    and of its grad twin, every operand tile-leading, `pair_mask` last; and
    the ops executor on those operands scores bitwise like the engine's
    packed executor on the same pool."""
    path = "packed_sparse" if sparse else "packed_dense"
    pairs = _mixed_pairs(11, 40, max_n=32)
    eng = ScoringEngine(PARAMS, CFG, path=path)
    got = eng.score(pairs)
    assert eng.last_plan.path == path and not eng.last_plan.degraded_from
    ps = eng.last_pack_stats
    packed, _ = pack_pairs(pairs, ps["node_budget"],
                           slots_per_tile=ps["slots_per_tile"],
                           with_edges=sparse,
                           edge_budget=ps.get("edge_budget"),
                           overflow_budget=ps.get("overflow_budget", 8))

    operands = packed.kernel_operands(sparse)
    kernel = sparse_pair_score if sparse else packed_pair_score
    twin = (grad.sparse_pair_score_grad if sparse
            else grad.packed_pair_score_grad)
    names = _leading_operands(kernel)
    assert len(operands) == len(names) == len(_leading_operands(twin, 1))
    assert names == _leading_operands(twin, 1)
    assert operands[-1] is packed.pair_mask
    assert {np.shape(x)[0] for x in operands} == {ps["n_tiles"]}

    score = ops.pair_score_sparse if sparse else ops.pair_score_packed
    s = score(PARAMS, packed, quantize_tiles=True)
    np.testing.assert_array_equal(
        unpack_pair_scores(s, packed, len(pairs)), np.asarray(got))


# ------------------------------------------- first-layer one-hot elimination

def test_label_gather_first_layer_is_exact():
    """one_hot(labels) @ W1 == W1[labels] bit-exactly, end to end."""
    pairs = _mixed_pairs(7, 10)
    lhs = pad_graphs([p[0] for p in pairs], CFG.n_node_labels, 64)
    rhs = pad_graphs([p[1] for p in pairs], CFG.n_node_labels, 64)
    s_feats = pair_score(PARAMS, lhs.adj, lhs.feats, lhs.mask,
                         rhs.adj, rhs.feats, rhs.mask)
    s_labels = pair_score_from_labels(PARAMS, lhs.adj, lhs.labels, lhs.mask,
                                      rhs.adj, rhs.labels, rhs.mask)
    np.testing.assert_array_equal(np.asarray(s_feats), np.asarray(s_labels))


def test_pad_graphs_carries_int_labels():
    g = random_graph(np.random.default_rng(11), 9)
    gb = pad_graphs([g], CFG.n_node_labels, 16)
    assert gb.labels.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(gb.labels[0, :9]), g["labels"])
    assert (np.asarray(gb.labels[0, 9:]) == 0).all()
    # feats is the one-hot of labels on real rows
    np.testing.assert_array_equal(
        np.asarray(jnp.argmax(gb.feats[0, :9], -1)), g["labels"])


# ------------------------------------------------------ oversized query path

def test_bucket_for_oversize_power_of_two():
    assert bucket_for(65, allow_oversize=True) == 128
    assert bucket_for(200, allow_oversize=True) == 256
    with pytest.raises(ValueError):
        bucket_for(65)


def test_server_scores_oversized_graphs():
    """Regression: a query beyond the largest bucket / node budget must not
    kill score() — it routes to power-of-two overflow buckets."""
    from repro.serve.batching import simgnn_query_server

    rng = np.random.default_rng(13)
    pairs = _mixed_pairs(14, 6) + [(random_graph(rng, 90),
                                    random_graph(rng, 20))]
    ref_server = simgnn_query_server(PARAMS, CFG)
    kern_server = simgnn_query_server(PARAMS, CFG, use_kernels=True)
    out_ref = ref_server(pairs)
    out_k = kern_server(pairs)
    assert out_ref.shape == out_k.shape == (7,)
    assert (out_ref > 0).all()
    np.testing.assert_allclose(out_k, out_ref, rtol=1e-4, atol=1e-5)
    assert 128 in kern_server.bucket_fns        # oversize fell back to bucket


def test_server_packed_routing_and_stats():
    from repro.serve.batching import simgnn_query_server

    pairs = _mixed_pairs(15, 20)
    packed_server = simgnn_query_server(PARAMS, CFG, use_kernels=True)
    bucketed_server = simgnn_query_server(PARAMS, CFG, use_kernels=True,
                                          packing=False)
    out_p = packed_server(pairs)
    out_b = bucketed_server(pairs)
    np.testing.assert_allclose(out_p, _reference_scores(PARAMS, pairs),
                               rtol=0, atol=1e-6)
    np.testing.assert_allclose(out_p, out_b, rtol=1e-5, atol=1e-6)
    st = packed_server.last_pack_stats
    assert st is not None and st["n_pairs"] == 20
    assert 0 < st["occupancy_lhs"] <= 1.0
    assert not packed_server.bucket_fns          # nothing fell back
    assert bucketed_server.bucket_fns            # bucketed path kept buckets


# ------------------------------------------------------- MicroBatcher stats

def test_microbatcher_flush_stats():
    from repro.serve.batching import MicroBatcher

    now = [0.0]
    mb = MicroBatcher(lambda reqs: list(reqs), max_batch=4, max_wait_s=1.0,
                      clock=lambda: now[0])
    for i in range(8):                 # two size-triggered flushes
        mb.submit(i)
    mb.submit(8)
    now[0] = 2.0                       # deadline passes with 1 pending
    assert mb.poll() == [8]
    mb.submit(9)
    assert mb.flush() == [9]           # manual, occupancy 1/4
    assert mb.flush() is None          # empty queue: nothing ran (None, not
    assert mb.poll() is None           # an empty result list) ...
    st = mb.stats
    assert st.batches == 4 and st.requests == 10
    assert st.size_flushes == 2
    assert st.deadline_flushes == 1
    assert st.manual_flushes == 1      # ... and does not count as a flush
    assert st.mean_occupancy == pytest.approx((1 + 1 + 0.25 + 0.25) / 4)


# ------------------------------------------------- kernel-body pad neutrality

def test_edge_aggregate_pad_edges_are_neutral():
    """Pad edge slots (senders=0, weight 0) must contribute exact zeros to
    receiver row 0 — the slot every pad edge points at."""
    from repro.core.batching import edge_aggregate

    rng = np.random.default_rng(17)
    n, e_real, e_pad = 6, 4, 12
    senders = np.zeros((1, e_real + e_pad), np.int32)
    receivers = np.zeros((1, e_real + e_pad), np.int32)
    weights = np.zeros((1, e_real + e_pad), np.float32)
    emask = np.zeros((1, e_real + e_pad), np.float32)
    senders[0, :e_real] = [1, 2, 3, 4]
    receivers[0, :e_real] = [2, 0, 1, 0]
    weights[0, :e_real] = rng.uniform(0.5, 1.5, e_real)
    emask[0, :e_real] = 1.0
    eb = EdgeBatch(jnp.asarray(senders), jnp.asarray(receivers),
                   jnp.asarray(weights), jnp.asarray(emask))
    hw = jnp.asarray(rng.normal(size=(1, n, 3)).astype(np.float32))
    out = np.asarray(edge_aggregate(eb, hw))
    expect = np.zeros((1, n, 3), np.float32)
    for s, r, w in zip(senders[0, :e_real], receivers[0, :e_real],
                       weights[0, :e_real]):
        expect[0, r] += w * np.asarray(hw)[0, s]
    np.testing.assert_array_equal(out, expect)    # exact, incl. row 0


def test_normalize_adjacency_block_isolated_and_masked_nodes():
    """Isolated real nodes get the self-loop weight 1; masked (pad) node
    rows/cols are exactly zero even though the in-kernel identity covers
    the whole tile."""
    adj = np.zeros((1, 6, 6), np.float32)
    adj[0, 0, 1] = adj[0, 1, 0] = 1.0   # one edge; node 2 isolated but real
    mask = np.asarray([[1, 1, 1, 0, 0, 0]], np.float32)
    a = np.asarray(normalize_adjacency_block(jnp.asarray(adj),
                                             jnp.asarray(mask)))
    assert a[0, 2, 2] == 1.0                       # isolated: D^-1/2 I D^-1/2
    assert (a[0, 3:, :] == 0).all() and (a[0, :, 3:] == 0).all()
    np.testing.assert_allclose(a[0, 0, 1], 0.5, atol=1e-6)  # deg 2 <-> deg 2
    # parity with the core (non-kernel) normalization on the same block
    from repro.core.gcn import normalized_adjacency
    np.testing.assert_allclose(
        a, np.asarray(normalized_adjacency(jnp.asarray(adj),
                                           jnp.asarray(mask))),
        rtol=1e-6, atol=1e-7)
