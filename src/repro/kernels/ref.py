"""Pure-jnp oracles for every Pallas kernel in this package.

Each `*_ref` mirrors its kernel's semantics exactly (same masking, same
normalization) using only jax.numpy — these are the ground truth for the
per-kernel allclose sweeps in tests/test_kernels.py.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

Array = jax.Array
NEG_INF = -1e30


def fused_gcn_att_ref(adj_norm: Array, feats: Array, mask: Array,
                      gcn_params, att_w: Array) -> Array:
    """Oracle for kernels/fused_gcn.py: 3 GCN layers + att pooling.
    Takes the *normalized* adjacency (kernel parity). Each layer is
    ReLU(A'(HW) + b) * mask, the bias added after aggregation."""
    h = feats.astype(jnp.float32)
    for p in gcn_params:
        hw = jnp.einsum("bnf,fg->bng", h, p["w"].astype(jnp.float32))
        h = jnp.einsum("bnm,bmg->bng", adj_norm.astype(jnp.float32),
                       hw) + p["b"]
        h = jax.nn.relu(h) * mask[..., None]
    n_valid = jnp.maximum(jnp.sum(mask, axis=-1, keepdims=True), 1.0)
    mean_h = jnp.sum(h * mask[..., None], axis=-2) / n_valid
    c = jnp.tanh(mean_h @ att_w.astype(jnp.float32))
    a = jax.nn.sigmoid(jnp.einsum("bnf,bf->bn", h, c)) * mask
    return jnp.einsum("bn,bnf->bf", a, h).astype(feats.dtype)


def simgnn_head_ref(hg1: Array, hg2: Array, ntn_params, fcn_params) -> Array:
    """Oracle for kernels/simgnn_head.py: NTN + FCN -> [B] scores."""
    h1 = hg1.astype(jnp.float32)
    h2 = hg2.astype(jnp.float32)
    bilinear = jnp.einsum("bf,kfg,bg->bk", h1,
                          ntn_params["w"].astype(jnp.float32), h2)
    cat = jnp.concatenate([h1, h2], axis=-1)
    linear = jnp.einsum("bf,kf->bk", cat, ntn_params["v"].astype(jnp.float32))
    s = jax.nn.relu(bilinear + linear + ntn_params["b"])
    for i, p in enumerate(fcn_params):
        s = s @ p["w"].astype(jnp.float32) + p["b"]
        if i + 1 < len(fcn_params):
            s = jax.nn.relu(s)
    return jax.nn.sigmoid(s[..., 0]).astype(hg1.dtype)


def flash_attention_ref(q: Array, k: Array, v: Array, *,
                        causal: bool = True, window: int | None = None,
                        softcap: float | None = None) -> Array:
    """Oracle for kernels/flash_attn.py. q [B,T,H,D], k/v [B,S,KV,D]."""
    b, t, h, d = q.shape
    _, s_len, kv, _ = k.shape
    group = h // kv
    k = jnp.repeat(k, group, axis=2)
    v = jnp.repeat(v, group, axis=2)
    s = jnp.einsum("bthd,bshd->bhts", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * (d ** -0.5)
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    q_pos = jnp.arange(t)[:, None]
    kv_pos = jnp.arange(s_len)[None, :]
    mask = jnp.ones((t, s_len), bool)
    if causal:
        mask &= kv_pos <= q_pos
    if window is not None:
        mask &= (q_pos - kv_pos) < window
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(mask, p, 0.0)
    return jnp.einsum("bhts,bshd->bthd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


def wkv6_ref(r: Array, k: Array, v: Array, w: Array, u: Array) -> Array:
    """Oracle for kernels/wkv6.py — direct sequential recurrence."""
    b, t, h, kd = r.shape
    vd = v.shape[-1]

    def head_scan(r_h, k_h, v_h, w_h, u_h):     # [T,K],[T,K],[T,V],[T,K],[K]
        def step(s, inp):
            rt, kt, vt, wt = inp
            o = rt @ s + jnp.sum(rt * u_h * kt) * vt
            s = wt[:, None] * s + kt[:, None] * vt[None, :]
            return s, o
        s0 = jnp.zeros((kd, vd), jnp.float32)
        _, o = jax.lax.scan(step, s0, (r_h, k_h, v_h, w_h))
        return o

    f32 = lambda x: x.astype(jnp.float32)
    over_heads = jax.vmap(head_scan, in_axes=(1, 1, 1, 1, 0), out_axes=1)
    over_batch = jax.vmap(over_heads, in_axes=(0, 0, 0, 0, None))
    out = over_batch(f32(r), f32(k), f32(v), f32(w), f32(u))
    return out.astype(r.dtype)
