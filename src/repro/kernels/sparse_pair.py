"""Edge-centric packed-sparse (packed-CSR) pair-score megakernel
(DESIGN.md §9).

Same single-pass dataflow and tile format as `packed_pair.py` — FFD-packed
segment-ID tiles, segment Att pooling, NTN/FCN on tile-aligned pair slots,
nothing but final scores touching HBM — but the GCN aggregation is
*edge-centric*: instead of multiplying the dense `[NB, NB]` block-diagonal
adjacency through the MXU (>95% structural zeros at AIDS-like degree ~2),
the kernel streams the tile's A' non-zeros in the packed-CSR layout of
`core.batching.packed_pair_edges` and accumulates messages in-kernel
(`gcn_layers_edge_block`). This is the TPU realization of the paper's
central sparsity claim (§3.2.2: "read only the non-zero A' elements"),
LW-GCN's compressed row format, and Accel-GCN's degree-aware workload
split:

  * per layer, aggregation costs O(NB·D·F) gathered messages (D = per-node
    neighbor budget from the `ops.packed_edge_budget` ladder — 4 at
    AIDS-like degree ~2.1) instead of O(NB²·F) MACs — ~14x fewer
    aggregation FLOPs at the default budgets; the regular ELLPACK planes
    reduce with statically-unrolled contiguous adds (no scatter), only the
    heavy-tail overflow edges take a small one-hot contraction;
  * the first layer keeps PR 2's one-hot elimination: int32 labels ride
    into the kernel and the widest H·W becomes a W1 row gather
    (`gcn_layers_edge_block(labels=...)`), so no [N, n_labels] one-hot is
    ever materialized;
  * the adjacency block and the in-kernel normalization disappear
    entirely: edge weights are the host-precomputed normalized A' entries
    (block-diagonal by construction, exact-zero pad slots), the FPGA
    host-preprocessing role; HBM traffic per tile side drops from NB²
    adjacency floats to ~2·(NB·D + E_ov) edge words (~8x at the default
    budgets).

Pad edge slots point at node 0 with zero weight and are neutral without any
branch; pad node slots carry mask 0 / segment 0; pad pair slots are zeroed
by `pair_mask` on the way out — the same exact-zero discipline as §8.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.common import (compiler_params, flatten_layer_params,
                                  gcn_layers_edge_block, leading_block_spec,
                                  ntn_fcn_block, pallas_call, read_layer_refs,
                                  replicated_spec, segment_att_pool_block,
                                  should_interpret)


#: Scoped VMEM for one packed-sparse program. The one-hot gathers keep a
#: tile's working set near 2.7 MB at NB=64 (v5e compile: 21.4 MB at 8
#: tiles per program), above Mosaic's 16 MiB default; `ops.sparse_tile_block`
#: sizes programs to fit this limit.
SPARSE_VMEM_LIMIT = 32 * 1024 * 1024


def _kernel(n_gcn_layers,
            nbr1_ref, nw1_ref, ovs1_ref, ovr1_ref, ovw1_ref,
            lab1_ref, mask1_ref, seg1_ref,
            nbr2_ref, nw2_ref, ovs2_ref, ovr2_ref, ovw2_ref,
            lab2_ref, mask2_ref, seg2_ref,
            pmask_ref, *refs):
    out_ref, refs = refs[-1], refs[:-1]
    gcn_refs, refs = refs[:2 * n_gcn_layers], refs[2 * n_gcn_layers:]
    watt_ref, wt_ref, vt_ref, ntn_b_ref = refs[:4]
    fcn_refs = refs[4:]
    tb = mask1_ref.shape[0]
    p = pmask_ref.shape[1]

    # Stack lhs/rhs tiles into one [2*TB, ...] block (engine reuse ->
    # batching, DESIGN.md §2): one GCN stack and Att stage serve both sides.
    cat = lambda a, b: jnp.concatenate([a[...], b[...]], 0)
    nbr = cat(nbr1_ref, nbr2_ref)
    nw = cat(nw1_ref, nw2_ref).astype(jnp.float32)
    ovs = cat(ovs1_ref, ovs2_ref)
    ovr = cat(ovr1_ref, ovr2_ref)
    ovw = cat(ovw1_ref, ovw2_ref).astype(jnp.float32)
    labels = cat(lab1_ref, lab2_ref)
    mask = cat(mask1_ref, mask2_ref).astype(jnp.float32)
    seg = cat(seg1_ref, seg2_ref)

    # No normalization stage: the edge weights already hold A' non-zeros.
    h = gcn_layers_edge_block(nbr, nw, ovs, ovr, ovw, None, mask,
                              read_layer_refs(gcn_refs),
                              labels=labels)                 # [2*TB, NB, F]
    hg = segment_att_pool_block(h, mask, seg, watt_ref[...], p)  # [2*TB, P, F]
    f = hg.shape[-1]
    scores = ntn_fcn_block(hg[:tb].reshape(tb * p, f),
                           hg[tb:].reshape(tb * p, f),
                           wt_ref[...], vt_ref[...], ntn_b_ref[...],
                           read_layer_refs(fcn_refs))        # [TB*P, 1]
    # Pair slots stay on sublanes ([TB, P, 1] out block): a [TB*P, 1] ->
    # [TB, P] cast would move them onto lanes, which Mosaic cannot lower.
    out_ref[...] = (scores.reshape(tb, p, 1)
                    * pmask_ref[...].astype(jnp.float32)).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tile_block", "interpret"))
def sparse_pair_score(nbr1: jax.Array, nbr_w1: jax.Array,
                      ov_snd1: jax.Array, ov_rcv1: jax.Array,
                      ov_w1: jax.Array, labels1: jax.Array,
                      mask1: jax.Array, seg1: jax.Array,
                      nbr2: jax.Array, nbr_w2: jax.Array,
                      ov_snd2: jax.Array, ov_rcv2: jax.Array,
                      ov_w2: jax.Array, labels2: jax.Array,
                      mask2: jax.Array, seg2: jax.Array,
                      pair_mask: jax.Array,
                      gcn_params, att_w: jax.Array, ntn_params, fcn_params, *,
                      tile_block: int = 4,
                      interpret: bool | None = None) -> jax.Array:
    """Packed tiles in packed-CSR edge form (pack_pairs(with_edges=True)
    layout) -> [T, P] pair-slot scores in one pallas_call. T must be a
    multiple of tile_block (ops.py pads; pad tiles have all-zero
    masks/weights and pair_mask zeroes their slots)."""
    if interpret is None:
        interpret = should_interpret()
    t, nb = mask1.shape
    assert t % tile_block == 0, (t, tile_block)
    e = nbr1.shape[-1]
    e_ov = ov_snd1.shape[-1]
    p = pair_mask.shape[-1]
    f = gcn_params[-1]["w"].shape[1]
    k = ntn_params["b"].shape[0]
    # Host-side pre-transposes (same layouts as packed_pair.py): W [K,F,F]
    # -> [F, K*F], V [K,2F] -> [2F, K] so the kernel sees pure matmuls.
    wt = jnp.transpose(ntn_params["w"], (1, 0, 2)).reshape(f, k * f)
    vt = ntn_params["v"].T
    weights = (flatten_layer_params(gcn_params)
               + [att_w, wt, vt, ntn_params["b"]]
               + flatten_layer_params(fcn_params))

    def blk(shape):
        return leading_block_spec((tile_block,) + shape)

    def idx(plane):
        # The narrow int16 index planes of `packed_pair_edges` widen on
        # device: Mosaic tiles 16-bit rows in pairs of sublanes, which a
        # tile_block below 16 cannot fill.
        return plane.astype(jnp.int32)

    side = [blk((e,)), blk((e,)), blk((e_ov,)), blk((e_ov,)), blk((e_ov,)),
            blk((nb,)), blk((nb,)), blk((nb,))]
    out = pallas_call(
        functools.partial(_kernel, len(gcn_params)),
        name="sparse_pair_score",
        grid=(t // tile_block,),
        in_specs=side + side + [blk((p, 1))]
                 + [replicated_spec(a) for a in weights],
        out_specs=blk((p, 1)),
        out_shape=jax.ShapeDtypeStruct((t, p, 1), mask1.dtype),
        compiler_params=compiler_params(("parallel",),
                                        vmem_limit_bytes=SPARSE_VMEM_LIMIT),
        interpret=interpret,
    )(idx(nbr1), nbr_w1, idx(ov_snd1), idx(ov_rcv1), ov_w1,
      labels1, mask1, seg1,
      idx(nbr2), nbr_w2, idx(ov_snd2), idx(ov_rcv2), ov_w2,
      labels2, mask2, seg2, pair_mask[..., None], *weights)
    return out[..., 0]
