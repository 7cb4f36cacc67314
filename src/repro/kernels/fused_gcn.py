"""Fused N-layer GCN + attention-pooling Pallas TPU kernel.

This is the TPU realization of SPA-GCN's central mechanism: the FPGA dataflow
pipeline that runs *all* GCN layers plus the Att stage with no off-chip
traffic for intermediates (paper §3.3, Fig. 4). Here one grid program
processes a block of `GB` graphs: adjacency, features, and every layer's
intermediate live in VMEM for the program's whole lifetime; layer weights
(tiny: <=128x128) are broadcast to all programs and read from HBM once per
block — the paper's "read each element only once" principle.

Parallelism mapping (paper Table 2 -> TPU):
  SIMD_FT / SIMD_Agg  -> MXU lanes (feature dim, 128-wide)
  DF (node duplication)-> sublanes (graphs x nodes rows of the matmul)
  inter-layer pipeline -> in-VMEM loop over layers (no HBM spill at all,
                          strictly stronger than FIFO pipelining)
  query replication    -> grid over graph blocks x chips over the mesh

The kernel is variadic over GCN depth — any `SimGNNConfig.gcn_dims` length
compiles (the layer loop lives in `common.gcn_att_block`, shared with the
end-to-end megakernel in `fused_pair.py`). The grid dimension is 'parallel':
graph blocks are independent (the paper's replicated pipelines).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.common import (compiler_params, flatten_layer_params,
                                  gcn_att_block, leading_block_spec,
                                  pallas_call, read_layer_refs,
                                  replicated_spec, should_interpret)


def _kernel(adj_ref, feats_ref, mask_ref, *refs):
    out_ref, watt_ref, layer_refs = refs[-1], refs[-2], refs[:-2]
    adj = adj_ref[...].astype(jnp.float32)          # [GB, N, N]
    h = feats_ref[...].astype(jnp.float32)          # [GB, N, F0]
    mask = mask_ref[...].astype(jnp.float32)        # [GB, N]
    hg = gcn_att_block(adj, h, mask, read_layer_refs(layer_refs),
                       watt_ref[...])
    out_ref[...] = hg.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_graphs", "interpret"))
def fused_gcn_att(adj_norm: jax.Array, feats: jax.Array, mask: jax.Array,
                  gcn_params, att_w: jax.Array, *,
                  block_graphs: int = 8,
                  interpret: bool | None = None) -> jax.Array:
    """adj_norm [B,N,N] (pre-normalized A'), feats [B,N,F0], mask [B,N]
    -> graph embeddings [B, F_last]. B must be a multiple of block_graphs
    (ops.py pads). `gcn_params` may hold any number of layers."""
    if interpret is None:
        interpret = should_interpret()
    b, n, _ = adj_norm.shape
    assert b % block_graphs == 0, (b, block_graphs)
    flat = flatten_layer_params(gcn_params)
    f_out = gcn_params[-1]["w"].shape[1]
    grid = (b // block_graphs,)

    def blk(shape):   # per-graph-block operand
        return leading_block_spec((block_graphs,) + shape)

    return pallas_call(
        _kernel,
        name="fused_gcn_att",
        grid=grid,
        in_specs=[blk((n, n)), blk((n, feats.shape[-1])), blk((n,))]
                 + [replicated_spec(a) for a in flat + [att_w]],
        out_specs=blk((f_out,)),
        out_shape=jax.ShapeDtypeStruct((b, f_out), feats.dtype),
        compiler_params=compiler_params(("parallel",)),
        interpret=interpret,
    )(adj_norm, feats, mask, *flat, att_w)
