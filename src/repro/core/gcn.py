"""Graph Convolutional Network core (Kipf & Welling) for batches of small graphs.

Paper mapping (SPA-GCN §2.1/§3.2): one GCN layer computes

    H^{l+1} = ReLU( A' · (H^l · W^l) + b^l )

with the bias added after aggregation, as Kipf & Welling and SimGNN's
released code add it, and the multiplication order A'(HW) chosen over (A'H)W
because both operands of each product stay sparse-x-dense (fewer ops — same
argument as the paper).
On TPU the graphs are processed as a *batch* of padded [N, F] tiles so every
matmul is a dense MXU-shaped batched GEMM; structural sparsity is removed by
size-bucketing (see core/batching.py) rather than by dynamic zero-skipping
(see DESIGN.md §2 for why that FPGA mechanism does not transfer).

All functions are natively batched: adjacency [B, N, N], features [B, N, F],
node mask [B, N]. They are pure and `jit`/`vmap`/`grad`-compatible.
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp

Array = jax.Array


def normalized_adjacency(adj: Array, mask: Array) -> Array:
    """A' = D^-1/2 (A + I) D^-1/2 restricted to valid (masked) nodes.

    adj:  [B, N, N] 0/1 (or weighted) adjacency, padded with zeros.
    mask: [B, N] 1.0 for real nodes, 0.0 for padding.
    Padding rows/cols of the result are exactly zero, so padded nodes
    neither send nor receive messages.
    """
    m = mask[..., :, None] * mask[..., None, :]            # [B, N, N]
    eye = jnp.eye(adj.shape[-1], dtype=adj.dtype)
    a_tilde = (adj + eye) * m                              # self loops on real nodes only
    deg = jnp.sum(a_tilde, axis=-1)                        # [B, N]
    inv_sqrt = inv_sqrt_degree(deg)
    return a_tilde * inv_sqrt[..., :, None] * inv_sqrt[..., None, :]


def inv_sqrt_degree(deg: Array) -> Array:
    """The diagonal of D^-1/2 in `normalized_adjacency`: rsqrt of each
    positive degree, 0 for the rest. `core.batching` evaluates this same
    expression for the A' weights it builds on the host, so that they stay
    bitwise equal to the device's."""
    return jnp.where(deg > 0, jax.lax.rsqrt(jnp.maximum(deg, 1e-12)), 0.0)


def init_gcn_params(key: Array, feature_dims: Sequence[int], dtype=jnp.float32):
    """Glorot-init a stack of GCN layers: dims (f0, f1, ..., fL)."""
    layers = []
    for i in range(len(feature_dims) - 1):
        key, sub = jax.random.split(key)
        fan_in, fan_out = feature_dims[i], feature_dims[i + 1]
        scale = jnp.sqrt(2.0 / (fan_in + fan_out)).astype(dtype)
        w = jax.random.normal(sub, (fan_in, fan_out), dtype) * scale
        b = jnp.zeros((fan_out,), dtype)
        layers.append({"w": w, "b": b})
    return layers


def gcn_layer(params, adj_norm: Array, h: Array, mask: Array, *,
              activation: bool = True) -> Array:
    """One GCN layer on a padded batch: A'(H·W) + b, in A'(H·W) order
    (paper §3). The bias is added after aggregation; added before it, A'
    would scale it, off by (A'1 - 1) b.

    adj_norm: [B, N, N], h: [B, N, Fin], mask: [B, N] -> [B, N, Fout].
    """
    hw = jnp.einsum("bnf,fg->bng", h, params["w"])
    out = jnp.einsum("bnm,bmg->bng", adj_norm, hw) + params["b"]
    if activation:
        out = jax.nn.relu(out)
    return out * mask[..., None]


def gcn_stack(layers, adj_norm: Array, h: Array, mask: Array) -> Array:
    """Full GCN: ReLU between layers (incl. after the last one, as SimGNN does
    before attention pooling — matches the released SimGNN reference)."""
    for p in layers:
        h = gcn_layer(p, adj_norm, h, mask, activation=True)
    return h


def gcn_stack_from_labels(layers, adj_norm: Array, labels: Array,
                          mask: Array) -> Array:
    """GCN stack whose input is int32 node labels instead of one-hot features.

    The first layer's H·W becomes a W1 row gather: one_hot(labels) @ W1 ==
    W1[labels] *exactly* (each one-hot row sums a single non-zero product),
    so this is bit-identical to `gcn_stack` on one-hot feats while never
    materializing the [B, N, n_labels] block — the pure-jnp reference for
    the kernels' first-layer one-hot elimination (DESIGN.md §8).
    labels: [B, N] int32 (pad slots may hold any valid label; masked out).
    Every layer adds its bias after aggregation, as `gcn_layer` does.
    """
    hw = jnp.take(layers[0]["w"], labels, axis=0)
    h = jnp.einsum("bnm,bmg->bng", adj_norm, hw) + layers[0]["b"]
    h = jax.nn.relu(h) * mask[..., None]
    for p in layers[1:]:
        h = gcn_layer(p, adj_norm, h, mask, activation=True)
    return h


def activation_sparsity(h: Array, mask: Array) -> Array:
    """Fraction of exact zeros among real-node activations (paper §3.4 reports
    52%/47% for SimGNN layers 2/3; we measure rather than exploit — DESIGN §2)."""
    valid = mask[..., None] * jnp.ones_like(h)
    zeros = jnp.sum((h == 0) * valid)
    return zeros / jnp.maximum(jnp.sum(valid), 1.0)
