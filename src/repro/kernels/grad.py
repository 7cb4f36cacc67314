"""Differentiable twins of the packed pair-score megakernels (DESIGN.md §11).

`pl.pallas_call` has no autodiff rule, so the packed inference kernels
(`packed_pair.py`, `sparse_pair.py`) cannot be fed to `jax.grad` directly.
But their compute BODIES live in `kernels/common.py` as pure-jnp functions
of values — and since those bodies now carry `jax.custom_vjp` rules whose
backward passes reuse the forward edge planes (transpose-aggregation), the
same single-pass dataflow becomes differentiable simply by composing the
bodies under `jit` instead of under `pallas_call`:

  * `packed_pair_score_grad`  — the §8 dense block-diagonal tile path;
  * `sparse_pair_score_grad`  — the §9 packed-CSR edge-centric path.

Both take the exact operands the inference kernels take
(`core.batching.PackedPairBatch.kernel_operands`) and return the same
`[T, P]` pair-slot scores (zero at pad slots), so one packing pass per
training batch serves the forward AND backward passes of every
accumulation microbatch. On TPU the bodies lower to the same MXU-shaped
contractions XLA would fuse anyway; what the Pallas wrapper adds for
inference (explicit VMEM residency across stages) is redundant under
autodiff, which must spill residuals to HBM regardless.

`core.engine.ScoringEngine.loss_and_grad` is the dispatch point; nothing
else should import these directly.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.common import (gcn_layers_block, gcn_layers_edge_block,
                                  normalize_adjacency_block, ntn_fcn_block,
                                  segment_att_pool_block)


def _layer_values(layers) -> list[tuple[jax.Array, jax.Array]]:
    """[{'w','b'}, ...] param dicts -> [(w, b), ...] values (the form the
    `*_block` bodies take; `read_layer_refs` is the in-kernel analogue)."""
    return [(p["w"], p["b"]) for p in layers]


def _ntn_transposes(params):
    """Host-side NTN pre-transposes shared with the Pallas wrappers:
    W [K,F,F] -> [F, K*F] and V [K,2F] -> [2F, K] so both contractions in
    `ntn_fcn_block` are pure matmuls."""
    f = params["gcn"][-1]["w"].shape[1]
    k = params["ntn"]["b"].shape[0]
    wt = jnp.transpose(params["ntn"]["w"], (1, 0, 2)).reshape(f, k * f)
    vt = params["ntn"]["v"].T
    return wt, vt


def _head_scores(params, hg, t, p, pair_mask):
    """Segment embeddings [2T, P, F] -> masked [T, P] pair-slot scores."""
    f = hg.shape[-1]
    wt, vt = _ntn_transposes(params)
    scores = ntn_fcn_block(hg[:t].reshape(t * p, f), hg[t:].reshape(t * p, f),
                           wt, vt, params["ntn"]["b"],
                           _layer_values(params["fcn"]))          # [T*P, 1]
    return scores.reshape(t, p) * pair_mask.astype(jnp.float32)


def packed_pair_score_grad(params, adj1, labels1, mask1, seg1,
                           adj2, labels2, mask2, seg2,
                           pair_mask) -> jax.Array:
    """Differentiable §8 packed-dense scorer: the same stage sequence as
    `packed_pair._kernel` (stack sides -> in-graph normalization -> GCN
    stack with W1 label gather -> segment Att pool -> NTN/FCN) on values.
    pack_pairs layout in, [T, P] pair-slot scores out (zero at pad slots)."""
    t = adj1.shape[0]
    p = pair_mask.shape[-1]
    cat = lambda a, b: jnp.concatenate([a, b], 0)
    adj = cat(adj1, adj2).astype(jnp.float32)
    labels = cat(labels1, labels2)
    mask = cat(mask1, mask2).astype(jnp.float32)
    seg = cat(seg1, seg2)

    a_norm = normalize_adjacency_block(adj, mask)
    h = gcn_layers_block(a_norm, None, mask, _layer_values(params["gcn"]),
                         labels=labels)                           # [2T, NB, F]
    hg = segment_att_pool_block(h, mask, seg, params["att"]["w"], p)
    return _head_scores(params, hg, t, p, pair_mask)


def sparse_pair_score_grad(params,
                           nbr1, nbr_w1, ov_snd1, ov_rcv1, ov_w1,
                           labels1, mask1, seg1,
                           nbr2, nbr_w2, ov_snd2, ov_rcv2, ov_w2,
                           labels2, mask2, seg2,
                           pair_mask) -> jax.Array:
    """Differentiable §9 packed-sparse scorer: aggregation runs from the
    packed-CSR edge planes (`csr_aggregate_block`, whose custom VJP swaps
    sender/receiver planes in the backward pass) — mirror of
    `sparse_pair._kernel`. pack_pairs(with_edges=True) layout in, [T, P]
    pair-slot scores out."""
    t = mask1.shape[0]
    p = pair_mask.shape[-1]
    cat = lambda a, b: jnp.concatenate([a, b], 0)
    nbr = cat(nbr1, nbr2)
    nw = cat(nbr_w1, nbr_w2).astype(jnp.float32)
    ovs = cat(ov_snd1, ov_snd2)
    ovr = cat(ov_rcv1, ov_rcv2)
    ovw = cat(ov_w1, ov_w2).astype(jnp.float32)
    labels = cat(labels1, labels2)
    mask = cat(mask1, mask2).astype(jnp.float32)
    seg = cat(seg1, seg2)

    # No normalization stage: the edge weights already hold A' non-zeros.
    h = gcn_layers_edge_block(nbr, nw, ovs, ovr, ovw, None, mask,
                              _layer_values(params["gcn"]),
                              labels=labels)                      # [2T, NB, F]
    hg = segment_att_pool_block(h, mask, seg, params["att"]["w"], p)
    return _head_scores(params, hg, t, p, pair_mask)

