"""Single-pass SimGNN pair-score megakernel (DESIGN.md §7).

This is the full realization of SPA-GCN's cross-stage dataflow pipeline
(paper §3.3, Fig. 4): ONE `pallas_call` whose program takes a block of graph
*pairs* — G1 and G2 tiles co-resident in VMEM — and runs

    adjacency normalization -> N-layer GCN -> Att pooling -> NTN -> FCN
    -> sigmoid

entirely in-register/VMEM, writing only the final [B] similarity scores back
to HBM. Nothing else touches off-chip memory: raw adjacency, features and
masks are read once per block, weights are broadcast to every program, and
every intermediate (A', all layer activations, graph embeddings, NTN slices)
lives and dies inside the program. This subsumes the two-kernel path
(`fused_gcn.py` + `simgnn_head.py`), which round-trips the graph embeddings
through HBM between stages 2 and 3.

The two graphs of each pair are stacked into one [2*GB, ...] block before the
GCN stack, so every matmul sees twice the rows (same trick as
`core.simgnn.pair_score`: on TPU, engine reuse is free and batching the two
sides doubles MXU occupancy). The layer loops are variadic — any
`SimGNNConfig.gcn_dims` / `fcn_dims` compiles — and accumulate in fp32 with
bf16 inputs supported (bf16 in / fp32 accumulate / out-dtype store).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.common import (compiler_params, flatten_layer_params,
                                  gcn_att_block, leading_block_spec,
                                  normalize_adjacency_block, ntn_fcn_block,
                                  pallas_call, read_layer_refs,
                                  replicated_spec, should_interpret)


def _kernel(n_gcn_layers,
            adj1_ref, feats1_ref, mask1_ref, adj2_ref, feats2_ref, mask2_ref,
            *refs):
    out_ref, refs = refs[-1], refs[:-1]
    gcn_refs, refs = refs[:2 * n_gcn_layers], refs[2 * n_gcn_layers:]
    watt_ref, wt_ref, vt_ref, ntn_b_ref = refs[:4]
    fcn_refs = refs[4:]
    gb = adj1_ref.shape[0]

    # Stack the pair into one [2*GB, ...] block: one normalization, one GCN
    # stack, one Att stage for both sides (double MXU occupancy).
    adj = jnp.concatenate([adj1_ref[...], adj2_ref[...]], 0).astype(jnp.float32)
    h0 = jnp.concatenate([feats1_ref[...], feats2_ref[...]], 0).astype(jnp.float32)
    mask = jnp.concatenate([mask1_ref[...], mask2_ref[...]], 0).astype(jnp.float32)

    a_norm = normalize_adjacency_block(adj, mask)          # stage 0 (host prep
                                                           # in the paper)
    hg = gcn_att_block(a_norm, h0, mask, read_layer_refs(gcn_refs),
                       watt_ref[...])                      # stages 1-2
    scores = ntn_fcn_block(hg[:gb], hg[gb:], wt_ref[...], vt_ref[...],
                           ntn_b_ref[...],
                           read_layer_refs(fcn_refs))      # stages 3-4
    out_ref[...] = scores.astype(out_ref.dtype)            # [GB, 1]


@functools.partial(jax.jit, static_argnames=("block_pairs", "interpret"))
def fused_pair_score(adj1: jax.Array, feats1: jax.Array, mask1: jax.Array,
                     adj2: jax.Array, feats2: jax.Array, mask2: jax.Array,
                     gcn_params, att_w: jax.Array, ntn_params, fcn_params, *,
                     block_pairs: int = 8,
                     interpret: bool | None = None) -> jax.Array:
    """Raw adjacency/features/masks for both sides of B graph pairs ->
    [B] similarity scores, in one pallas_call. B must be a multiple of
    block_pairs (ops.py pads; pad pairs have all-zero masks and their scores
    are sliced off)."""
    if interpret is None:
        interpret = should_interpret()
    b, n, _ = adj1.shape
    assert b % block_pairs == 0, (b, block_pairs)
    f = gcn_params[-1]["w"].shape[1]
    k = ntn_params["b"].shape[0]
    # Host-side pre-transposes (same layouts as simgnn_head.py): W [K,F,F]
    # -> [F, K*F], V [K,2F] -> [2F, K] so the kernel sees pure matmuls.
    wt = jnp.transpose(ntn_params["w"], (1, 0, 2)).reshape(f, k * f)
    vt = ntn_params["v"].T
    weights = (flatten_layer_params(gcn_params)
               + [att_w, wt, vt, ntn_params["b"]]
               + flatten_layer_params(fcn_params))

    def blk(shape):
        return leading_block_spec((block_pairs,) + shape)

    f0 = feats1.shape[-1]
    out = pallas_call(
        functools.partial(_kernel, len(gcn_params)),
        name="fused_pair_score",
        grid=(b // block_pairs,),
        in_specs=[blk((n, n)), blk((n, f0)), blk((n,)),
                  blk((n, n)), blk((n, f0)), blk((n,))]
                 + [replicated_spec(a) for a in weights],
        out_specs=blk((1,)),
        out_shape=jax.ShapeDtypeStruct((b, 1), feats1.dtype),
        compiler_params=compiler_params(("parallel",)),
        interpret=interpret,
    )(adj1, feats1, mask1, adj2, feats2, mask2, *weights)
    return out[:, 0]
