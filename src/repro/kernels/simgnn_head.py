"""Fused NTN + FCN head Pallas kernel (SimGNN stages 3-4, paper §4.3 Fig. 9).

One program scores a block of graph pairs: the K bilinear similarity slices,
the linear term, and the whole fully-connected reduction run back-to-back in
VMEM. The bilinear tensor contraction  h1^T W[k] h2  is reshaped into a single
MXU matmul  (GB, F) @ (F, K*F)  followed by an elementwise reduce against h2 —
the TPU version of the paper's observation that NTN is "a series of fixed-size
MVMs" best served by one small dense engine. The compute body lives in
`common.ntn_fcn_block`, shared with the end-to-end megakernel
(`fused_pair.py`), and is variadic over FCN depth.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.common import (compiler_params, flatten_layer_params,
                                  leading_block_spec, ntn_fcn_block,
                                  pallas_call, read_layer_refs,
                                  replicated_spec, should_interpret)


def _kernel(h1_ref, h2_ref, wt_ref, vt_ref, b_ref, *fcn_refs):
    out_ref = fcn_refs[-1]
    scores = ntn_fcn_block(h1_ref[...].astype(jnp.float32),
                           h2_ref[...].astype(jnp.float32),
                           wt_ref[...], vt_ref[...], b_ref[...],
                           read_layer_refs(fcn_refs[:-1]))
    out_ref[...] = scores.astype(out_ref.dtype)                     # [GB, 1]


@functools.partial(jax.jit, static_argnames=("block_pairs", "interpret"))
def simgnn_head(hg1: jax.Array, hg2: jax.Array, ntn_params, fcn_params, *,
                block_pairs: int = 128,
                interpret: bool | None = None) -> jax.Array:
    """hg1/hg2 [B, F] graph embeddings -> [B] similarity scores in (0,1)."""
    if interpret is None:
        interpret = should_interpret()
    b, f = hg1.shape
    assert b % block_pairs == 0, (b, block_pairs)
    k = ntn_params["b"].shape[0]
    # Pre-transpose on the host side of the call: W [K,F,F] -> [F, K*F],
    # V [K,2F] -> [2F, K] so the kernel sees pure matmul layouts.
    wt = jnp.transpose(ntn_params["w"], (1, 0, 2)).reshape(f, k * f)
    vt = ntn_params["v"].T
    fcn_flat = flatten_layer_params(fcn_params)

    def blk(shape):
        return leading_block_spec((block_pairs,) + shape)

    out = pallas_call(
        _kernel,
        name="simgnn_head",
        grid=(b // block_pairs,),
        in_specs=[blk((f,)), blk((f,)), replicated_spec(wt),
                  replicated_spec(vt), replicated_spec(ntn_params["b"])]
                 + [replicated_spec(a) for a in fcn_flat],
        out_specs=blk((1,)),
        out_shape=jax.ShapeDtypeStruct((b, 1), hg1.dtype),
        compiler_params=compiler_params(("parallel",)),
        interpret=interpret,
    )(hg1, hg2, wt, vt, ntn_params["b"], *fcn_flat)
    return out[:, 0]
