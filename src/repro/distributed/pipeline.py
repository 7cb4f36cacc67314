"""GPipe-style pipeline parallelism over a 'stage' mesh axis.

Exact, autodiff-compatible microbatch pipelining expressed with
shard_map + lax.ppermute (the jax-native mapping of the paper-era
"dataflow pipeline between stages" onto a TPU mesh — DESIGN.md §6):

  * stage s owns a contiguous slice of layers (params stacked on a leading
    [S, ...] axis sharded over 'stage');
  * at tick t, stage 0 injects microbatch t, every stage applies its slice
    to its current activation, results rotate s -> s+1 via ppermute;
  * after S + M - 1 ticks the last stage has emitted all M microbatches;
    outputs are recovered with a masked psum (only the last stage's buffer
    is nonzero).

Backward through ppermute is the reverse permute, so jax.grad of a
pipelined loss *is* the backward pipeline — no custom scheduling code.
This is bubble-optimal GPipe (bubble fraction (S-1)/(S+M-1)); 1F1B-style
re-ordering is a scheduling refinement on the same primitive.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def gpipe(stage_fn, mesh: Mesh, *, axis: str = "stage",
          n_microbatches: int | None = None):
    """Build a pipelined apply: (params_stacked [S,...], x [B,...]) -> y.

    stage_fn(stage_params, x_mb) -> y_mb must preserve the activation shape
    (homogeneous d_model across stages, as in all our transformer stacks).
    """
    s = mesh.shape[axis]

    def apply(params_stacked, x):
        m = n_microbatches or s
        assert x.shape[0] % m == 0, (x.shape, m)
        micro = x.reshape(m, x.shape[0] // m, *x.shape[1:])

        pspecs = jax.tree.map(lambda _: P(axis), params_stacked)

        @functools.partial(
            jax.shard_map, mesh=mesh,
            in_specs=(pspecs, P()),            # params sharded, data replicated
            out_specs=P(),
            check_vma=False)
        def pipelined(params_local, micro_all):
            sidx = jax.lax.axis_index(axis)
            mb = micro_all.shape[1]
            buf = jnp.zeros_like(micro_all[0])
            out = jnp.zeros_like(micro_all)
            perm = [(i, (i + 1) % s) for i in range(s)]
            for t in range(m + s - 1):
                inject = micro_all[min(t, m - 1)]
                is_first = (sidx == 0) & (t < m)
                cur = jnp.where(is_first, inject, buf)
                y = stage_fn(jax.tree.map(lambda p: p[0], params_local), cur)
                w = t - (s - 1)                 # microbatch finished this tick
                if w >= 0:
                    write = (sidx == s - 1)
                    out = out.at[w].set(jnp.where(write, y, out[w]))
                buf = jax.lax.ppermute(y, axis, perm)
            # only the last stage holds real outputs; sum-off the zeros
            out = jnp.where(sidx == s - 1, out, jnp.zeros_like(out))
            return jax.lax.psum(out, axis)

        y = pipelined(params_stacked, micro)
        return y.reshape(x.shape[0], *y.shape[2:])

    return apply


def stack_stage_params(per_stage_params: list):
    """[stage0_tree, stage1_tree, ...] -> single tree with leading S axis."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *per_stage_params)
