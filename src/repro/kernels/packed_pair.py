"""Node-packed sparsity-aware pair-score megakernel (DESIGN.md §8).

Same single-pass dataflow as `fused_pair.py` — normalization -> GCN stack ->
Att -> NTN -> FCN, nothing but final scores touching HBM — but the program's
unit of work is a *packed tile*, not a padded pair: `core.batching.pack_pairs`
first-fit-decreasing-packs many variable-size graph pairs into fixed
`[node_budget]` node tiles with per-node segment IDs, so

  * pad zeros shrink from per-graph bucket padding (up to ~2x of every row)
    to the tile's FFD slack (~10%), and
  * the first GCN layer's one-hot feature multiply disappears entirely: the
    kernel carries int32 node labels and gathers W1 rows
    (`gcn_layers_block(labels=...)`), never materializing the
    [N, n_labels] one-hot block (~n_labels-fold feature HBM traffic cut).

Per-graph stages become segment-ID forms of the same MXU-shaped ops:
adjacency normalization needs no change (the packed adjacency is
block-diagonal and the masked normalization factors per graph), Att pooling
contracts against the segment one-hot (`segment_att_pool_block`), and the
NTN/FCN head scores every pair slot of the tile in one [TB*P, F] block.
Pad node slots carry mask 0 / segment 0 and contribute exact zeros; pad pair
slots are zeroed by `pair_mask` on the way out.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.common import (compiler_params, flatten_layer_params,
                                  gcn_layers_block, leading_block_spec,
                                  normalize_adjacency_block, ntn_fcn_block,
                                  pallas_call, read_layer_refs,
                                  replicated_spec, segment_att_pool_block,
                                  should_interpret)


def _kernel(n_gcn_layers,
            adj1_ref, lab1_ref, mask1_ref, seg1_ref,
            adj2_ref, lab2_ref, mask2_ref, seg2_ref, pmask_ref, *refs):
    out_ref, refs = refs[-1], refs[:-1]
    gcn_refs, refs = refs[:2 * n_gcn_layers], refs[2 * n_gcn_layers:]
    watt_ref, wt_ref, vt_ref, ntn_b_ref = refs[:4]
    fcn_refs = refs[4:]
    tb = adj1_ref.shape[0]
    p = pmask_ref.shape[1]

    # Stack lhs/rhs tiles into one [2*TB, ...] block (engine reuse ->
    # batching, DESIGN.md §2): one normalization, GCN stack and Att stage
    # serve both sides of every pair.
    adj = jnp.concatenate([adj1_ref[...], adj2_ref[...]], 0).astype(jnp.float32)
    labels = jnp.concatenate([lab1_ref[...], lab2_ref[...]], 0)
    mask = jnp.concatenate([mask1_ref[...], mask2_ref[...]], 0).astype(jnp.float32)
    seg = jnp.concatenate([seg1_ref[...], seg2_ref[...]], 0)

    # Block-diagonal A': masked normalization factors per packed graph.
    a_norm = normalize_adjacency_block(adj, mask)
    h = gcn_layers_block(a_norm, None, mask, read_layer_refs(gcn_refs),
                         labels=labels)                    # [2*TB, NB, F]
    hg = segment_att_pool_block(h, mask, seg, watt_ref[...], p)  # [2*TB, P, F]
    f = hg.shape[-1]
    scores = ntn_fcn_block(hg[:tb].reshape(tb * p, f),
                           hg[tb:].reshape(tb * p, f),
                           wt_ref[...], vt_ref[...], ntn_b_ref[...],
                           read_layer_refs(fcn_refs))      # [TB*P, 1]
    # Pair slots stay on sublanes ([TB, P, 1] out block): a [TB*P, 1] ->
    # [TB, P] cast would move them onto lanes, which Mosaic cannot lower.
    out_ref[...] = (scores.reshape(tb, p, 1)
                    * pmask_ref[...].astype(jnp.float32)).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tile_block", "interpret"))
def packed_pair_score(adj1: jax.Array, labels1: jax.Array, mask1: jax.Array,
                      seg1: jax.Array, adj2: jax.Array, labels2: jax.Array,
                      mask2: jax.Array, seg2: jax.Array, pair_mask: jax.Array,
                      gcn_params, att_w: jax.Array, ntn_params, fcn_params, *,
                      tile_block: int = 4,
                      interpret: bool | None = None) -> jax.Array:
    """Packed tiles (pack_pairs layout) -> [T, P] pair-slot scores in one
    pallas_call. T must be a multiple of tile_block (ops.py pads; pad tiles
    have all-zero masks and pair_mask zeroes their slots)."""
    if interpret is None:
        interpret = should_interpret()
    t, nb, _ = adj1.shape
    assert t % tile_block == 0, (t, tile_block)
    p = pair_mask.shape[-1]
    f = gcn_params[-1]["w"].shape[1]
    k = ntn_params["b"].shape[0]
    # Host-side pre-transposes (same layouts as fused_pair.py): W [K,F,F]
    # -> [F, K*F], V [K,2F] -> [2F, K] so the kernel sees pure matmuls.
    wt = jnp.transpose(ntn_params["w"], (1, 0, 2)).reshape(f, k * f)
    vt = ntn_params["v"].T
    weights = (flatten_layer_params(gcn_params)
               + [att_w, wt, vt, ntn_params["b"]]
               + flatten_layer_params(fcn_params))

    def blk(shape):
        return leading_block_spec((tile_block,) + shape)

    out = pallas_call(
        functools.partial(_kernel, len(gcn_params)),
        name="packed_pair_score",
        grid=(t // tile_block,),
        in_specs=[blk((nb, nb)), blk((nb,)), blk((nb,)), blk((nb,)),
                  blk((nb, nb)), blk((nb,)), blk((nb,)), blk((nb,)),
                  blk((p, 1))]
                 + [replicated_spec(a) for a in weights],
        out_specs=blk((p, 1)),
        out_shape=jax.ShapeDtypeStruct((t, p, 1), mask1.dtype),
        compiler_params=compiler_params(("parallel",)),
        interpret=interpret,
    )(adj1, labels1, mask1, seg1, adj2, labels2, mask2, seg2,
      pair_mask[..., None], *weights)
    return out[..., 0]
