"""Chip-compile rehearsal of the SimGNN serve/search kernels (DESIGN.md §2).

Every Pallas kernel on the serving and search path is compiled by Mosaic
for a described (not attached) TPU v5e chip, at the AIDS configuration's
widths (`configs/simgnn_aids.py`: 29 labels, GCN 128/64/32, NTN K=16, FCN
16->8->4->1, 64-node tiles) and the tile counts the engine compiles for a
256-pair call, with `interpret=False`. Interpret-mode tests cannot see what
this catches: lowerings Mosaic lacks (vector gathers, top_k, lane->sublane
shape casts), unaligned blocks and scoped-VMEM overruns. Each compiled
program must contain the kernel as a `tpu_custom_call`.

The topology is described inside a module fixture, never on import: only
one process may load the TPU library, and pytest-xdist workers all import
this file. The persistent compilation cache is off around the compiles (an
entry written for a described chip cannot be read back without one).
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.simgnn_aids import CONFIG
from repro.core.batching import bucket_pairs, pack_pairs
from repro.core.simgnn import init_simgnn_params
from repro.data.graphs import query_pairs
from repro.kernels import ops, retrieval
from repro.kernels.common import flatten_layer_params

CORPUS_ROWS = 8192


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def aids():
    """Params, a packed 256-pair AIDS-like call and bucketed pairs."""
    params = init_simgnn_params(jax.random.PRNGKey(0), CONFIG)
    pairs = query_pairs(0, 256)
    nb = ops.packed_node_budget(CONFIG.max_nodes)
    packed, _ = pack_pairs(pairs, nb, slots_per_tile=max(8, nb // 4),
                           with_edges=True,
                           edge_budget=ops.packed_edge_budget(nb, 2.1))
    buckets = bucket_pairs(pairs[:64], CONFIG.n_node_labels,
                           allow_oversize=True)
    corpus = np.random.default_rng(0).standard_normal(
        (CORPUS_ROWS, CONFIG.gcn_dims[-1])).astype(np.float32)
    return params, packed, buckets[max(buckets)], corpus


def _packed(sparse):
    """The engine's single-device packed executor (`ops` body: tile plan,
    pad, kernel, slice), quantized as the engine calls it."""
    score = ops.pair_score_sparse if sparse else ops.pair_score_packed

    def build(params, packed, bucket, corpus):
        return (lambda p, pk: score(p, pk, quantize_tiles=True,
                                    interpret=False)), (params, packed)
    return build


def _bucketed_mega(params, packed, bucket, corpus):
    lhs, rhs, _ = bucket
    return (lambda p, *a: ops.pair_score_megakernel(p, *a, interpret=False),
            (params, lhs.adj, lhs.feats, lhs.mask,
             rhs.adj, rhs.feats, rhs.mask))


def _fused_gcn(params, packed, bucket, corpus):
    lhs, _, _ = bucket
    return (lambda p, *a: ops.graph_embeddings_fused(p, *a, interpret=False),
            (params, lhs.adj, lhs.feats, lhs.mask))


def _head(params, packed, bucket, corpus):
    return (lambda p, a, b: ops.pair_scores_fused(p, a, b, block_pairs=128,
                                                  interpret=False),
            (params, corpus, corpus[::-1]))


def _topm(params, packed, bucket, corpus):
    return (lambda q, c: retrieval._blocked_topm(
        q, c, m=64, block_cols=retrieval.RETRIEVAL_MAX_BLOCK_COLS,
        interpret=False), (corpus[:128], corpus))


def _topm_ntn(params, packed, bucket, corpus):
    uq, dq = retrieval.collapse_query_ntn(params["ntn"], corpus[:8])
    flat = tuple(flatten_layer_params(params["fcn"]))
    return (lambda u, d, c, f: retrieval._blocked_topm_ntn(
        u, d, c, f, m=64, block_cols=retrieval.RETRIEVAL_MAX_BLOCK_COLS,
        interpret=False), (uq, dq, corpus, flat))


KERNELS = {"packed_sparse": _packed(True), "packed_dense": _packed(False),
           "bucketed_mega": _bucketed_mega, "fused_gcn": _fused_gcn,
           "head": _head, "blocked_topm": _topm,
           "blocked_topm_ntn": _topm_ntn}


@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_kernel_compiles_for_v5e(kernel, one_chip, no_persistent_cache,
                                 aids):
    fn, args = KERNELS[kernel](*aids)
    specs = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), jnp.asarray(a).dtype,
                                       sharding=one_chip), args)
    compiled = jax.jit(fn).lower(*specs).compile()
    assert "tpu_custom_call" in compiled.as_text()
