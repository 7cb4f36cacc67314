"""SimGNN as released: each GCN layer adds its bias after aggregation,
H' = ReLU(A'HW + b) (Kipf & Welling; Bai et al.'s released code).

Every scoring path, the search server's default embedder, the kernels'
oracle and the training twins' gradients are held, with nonzero GCN
biases, to the benchmark's plain float32 reference
(`bench/reference/simgnn.py`, which imports nothing of the program) at
`highest` precision; one case shows that the bias added before
aggregation, A'(HW + b), misses the same tolerance by at least tenfold.
With all-zero biases every GCN body gives bitwise the output of the layer
computed with no bias term at all, so bias-free configurations score as
they did before the bias moved.
"""

import functools
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.batching import pack_pairs
from repro.core.engine import (PARITY_ATOL_F32, PATHS, TRAIN_PATHS,
                               ScoringEngine)
from repro.core.gcn import gcn_layer, normalized_adjacency
from repro.core.simgnn import SimGNNConfig
from repro.data.graphs import random_graph
from repro.kernels.common import (csr_aggregate_block_sym, gcn_layers_block,
                                  gcn_layers_edge_block)
from repro.kernels.ref import fused_gcn_att_ref

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench")
HI = jax.lax.Precision.HIGHEST
N = 64                    # the node budget: every test graph fits one pad
#: per-leaf max abs error of gradients against reference autodiff
#: (tests/test_grad.py's f32 bound).
GRAD_ATOL = 1e-5
#: max abs error of graph embeddings, relative to the largest entry.
EMBED_RTOL = 1e-5


def _load_reference():
    spec = importlib.util.spec_from_file_location(
        "bench_reference_simgnn",
        os.path.join(BENCH, "reference", "simgnn.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


REF = _load_reference()
with open(os.path.join(BENCH, "configs", "simgnn_aids.json")) as _f:
    RELEASED = json.load(_f)
CFG = SimGNNConfig(n_node_labels=RELEASED["n_node_labels"],
                   gcn_dims=tuple(RELEASED["gcn_dims"]),
                   ntn_k=RELEASED["ntn_k"],
                   fcn_dims=tuple(RELEASED["fcn_dims"]),
                   max_nodes=RELEASED["max_nodes"])


@functools.lru_cache(maxsize=None)
def _params():
    params = REF.init_params(15, RELEASED)
    assert RELEASED["gcn_bias"]
    assert all(float(jnp.abs(p["b"]).min()) > 0 for p in params["gcn"])
    return params


@functools.lru_cache(maxsize=None)
def _pairs():
    rng = np.random.default_rng(15)
    return tuple((random_graph(rng, int(rng.integers(5, N + 1))),
                  random_graph(rng, int(rng.integers(5, N + 1))))
                 for _ in range(10))


def _padded(side: int):
    return REF.pad([p[side] for p in _pairs()], CFG.n_node_labels, N)


@functools.lru_cache(maxsize=None)
def _reference_scores() -> np.ndarray:
    return np.asarray(REF.pair_scores(_params(), _padded(0), _padded(1)))


def _bias_first_embed(params, adj, feats, mask):
    """The reference's embedding with each GCN bias added before
    aggregation, A'(HW + b)."""
    a = REF.normalized_adjacency(adj, mask)
    h = feats
    for layer in params["gcn"]:
        hw = jnp.einsum("bnf,fg->bng", h, layer["w"], precision=HI)
        h = jax.nn.relu(jnp.einsum("bnm,bmg->bng", a, hw + layer["b"],
                                   precision=HI)) * mask[..., None]
    n = jnp.maximum(mask.sum(-1, keepdims=True), 1.0)
    c = jnp.tanh(jnp.dot(h.sum(1) / n, params["att"]["w"], precision=HI))
    att = jax.nn.sigmoid(jnp.einsum("bnf,bf->bn", h, c, precision=HI)) * mask
    return jnp.einsum("bn,bnf->bf", att, h, precision=HI)


def _targets() -> np.ndarray:
    return np.random.default_rng(16).uniform(0, 1, len(_pairs())).astype(
        np.float32)


def _case(case: str):
    """(error, tolerance) of one case against the reference."""
    params = _params()
    if case.startswith("score-"):
        path = case[len("score-"):]
        engine = ScoringEngine(params, CFG, path=path)
        got = engine.score(list(_pairs()))
        assert engine.last_plan.path == path
        return (float(np.abs(got - _reference_scores()).max()),
                PARITY_ATOL_F32[path])
    if case in ("embed_graphs", "kernel_oracle"):
        adj, feats, mask = _padded(0)
        if case == "embed_graphs":
            got = ScoringEngine(params, CFG).embed_graphs(
                [p[0] for p in _pairs()])
        else:
            got = fused_gcn_att_ref(normalized_adjacency(adj, mask), feats,
                                    mask, params["gcn"], params["att"]["w"])
        want = np.asarray(REF.embed(params, adj, feats, mask))
        return (float(np.abs(got - want).max() / np.abs(want).max()),
                EMBED_RTOL)
    if case.startswith("grad-"):
        path = case[len("grad-"):]
        engine = ScoringEngine(params, CFG, path=path)
        loss, grads = engine.loss_and_grad(list(_pairs()), _targets())
        assert engine.last_plan.path == path
        ref_loss, ref_grads = REF.loss_and_grad_jit(
            params, _padded(0), _padded(1), jnp.asarray(_targets()))
        assert all(float(jnp.abs(g["b"]).max()) > 0
                   for g in ref_grads["gcn"])
        leaves, ref_leaves = (jax.tree.leaves(grads),
                              jax.tree.leaves(ref_grads))
        assert len(leaves) == len(ref_leaves)
        err = max([abs(float(loss) - float(ref_loss))]
                  + [float(jnp.max(jnp.abs(a - b)))
                     for a, b in zip(leaves, ref_leaves)])
        return err, GRAD_ATOL
    assert case == "bias_before_aggregation"
    got = np.asarray(REF.head(params, _bias_first_embed(params, *_padded(0)),
                              _bias_first_embed(params, *_padded(1))))
    return (float(np.abs(got - _reference_scores()).max()),
            max(PARITY_ATOL_F32.values()))


CASES = ([f"score-{p}" for p in PATHS] + ["embed_graphs", "kernel_oracle"]
         + [f"grad-{p}" for p in TRAIN_PATHS] + ["bias_before_aggregation"])


@pytest.mark.parametrize("case", CASES)
def test_released_simgnn_matches_reference(case):
    """Sound cases within their tolerance; the old bias placement at least
    ten times outside the loosest score tolerance."""
    with jax.default_matmul_precision("highest"):
        err, tol = _case(case)
    if case == "bias_before_aggregation":
        assert err >= 10 * tol, (err, tol)
    else:
        assert err <= tol, (err, tol)


# ------------------------------------------- zero biases change nothing

def _packed():
    packed, _ = pack_pairs(list(_pairs())[:4], N, with_edges=True,
                           edge_budget=N * 4)
    return packed


def _no_bias_layer(body: str, w, h, packed):
    """The layer with no bias term at all, as each body computes it."""
    mask = packed.mask1
    if body == "gcn_layer":
        a = normalized_adjacency(packed.adj1, mask)
        hw = jnp.einsum("bnf,fg->bng", h, w)
        return jax.nn.relu(jnp.einsum("bnm,bmg->bng", a, hw)) * mask[..., None]
    gb, n = mask.shape
    hw = jnp.dot(h.reshape(gb * n, -1), w, precision=HI,
                 preferred_element_type=jnp.float32).reshape(gb, n, -1)
    if body == "gcn_layers_block":
        a = normalized_adjacency(packed.adj1, mask)
        agg = jax.lax.dot_general(a, hw, (((2,), (1,)), ((0,), (0,))),
                                  precision=HI,
                                  preferred_element_type=jnp.float32)
    else:
        e = packed.edges
        agg = csr_aggregate_block_sym(e.edges1.senders, e.edges1.weights,
                                      e.overflow1.senders,
                                      e.overflow1.receivers,
                                      e.overflow1.weights, hw)
    return jnp.maximum(agg, 0.0) * mask[..., None]


def _zero_bias_layer(body: str, w, h, packed):
    b = jnp.zeros((w.shape[1],), jnp.float32)
    mask = packed.mask1
    if body == "gcn_layer":
        a = normalized_adjacency(packed.adj1, mask)
        return gcn_layer({"w": w, "b": b}, a, h, mask)
    if body == "gcn_layers_block":
        a = normalized_adjacency(packed.adj1, mask)
        return gcn_layers_block(a, h, mask, [(w, b)])
    e = packed.edges
    return gcn_layers_edge_block(e.edges1.senders, e.edges1.weights,
                                 e.overflow1.senders, e.overflow1.receivers,
                                 e.overflow1.weights, h, mask, [(w, b)])


@pytest.mark.parametrize("body", ["gcn_layer", "gcn_layers_block",
                                  "gcn_layers_edge_block"])
def test_zero_bias_is_bitwise_the_layer_without_bias(body):
    packed = _packed()
    rng = np.random.default_rng(17)
    t, nb = np.asarray(packed.mask1).shape
    h = jnp.asarray(rng.normal(size=(t, nb, 24)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(24, 16)).astype(np.float32))
    got = np.asarray(_zero_bias_layer(body, w, h, packed))
    want = np.asarray(_no_bias_layer(body, w, h, packed))
    np.testing.assert_array_equal(got.view(np.uint32), want.view(np.uint32))
    assert np.count_nonzero(got) > 0
