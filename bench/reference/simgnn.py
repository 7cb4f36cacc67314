"""Plain float32 SimGNN (Bai et al., WSDM'19) in jax.numpy: the
benchmark's weights, its reference forward pass, loss and optimizer.

Follows the published description: three GCN layers
H' = ReLU(A' H W + b) with A' = D^-1/2 (A + I) D^-1/2 (Kipf & Welling),
global context-aware attention pooling, a neural tensor network of K
slices and an FCN down to one sigmoid score. Graphs arrive one-hot
encoded over the configuration's node labels. Imports nothing of the
program under test; callers set the matmul precision
(`jax.default_matmul_precision("highest")` for the reference, a lower one
for the control).

The weight tree has the layout the program's engine takes
({"gcn": [{"w", "b"}], "att": {"w"}, "ntn": {"w", "v", "b"},
"fcn": [{"w", "b"}]}), initialized as the widely used PyTorch SimGNN
(benedekrozemberczki/SimGNN) does: GCN weights, the attention matrix and
all three NTN tensors Xavier-uniform, the FCN as `torch.nn.Linear`
(weights and biases uniform in +-1/sqrt(fan_in)) times the
configuration's `init.fcn_scale`, which spreads the scores of an
untrained model over a range like a trained one's. GCN biases are
uniform in +-1/sqrt(fan_in), nonzero as a trained model's are, where the
configuration's `gcn_bias` is true, and zero where it is false (a model
without GCN biases).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def dims(cfg: dict) -> tuple:
    return (cfg["n_node_labels"],) + tuple(cfg["gcn_dims"])


@functools.partial(jax.jit, static_argnums=(1,))
def _init(key, shape_key):
    cfg = dict(shape_key)
    f_in = dims(cfg)
    f = cfg["gcn_dims"][-1]
    k = cfg["ntn_k"]
    fcn = (k,) + tuple(cfg["fcn_dims"]) + (1,)
    scale = cfg["fcn_init_scale"]
    keys = iter(jax.random.split(key, 16))

    def uniform(shape, bound):
        return jax.random.uniform(next(keys), shape, jnp.float32,
                                  -bound, bound)

    def xavier(shape, fan_in, fan_out):
        return uniform(shape, (6.0 / (fan_in + fan_out)) ** 0.5)

    return {
        "gcn": [{"w": xavier((a, b), a, b),
                 "b": (uniform((b,), a ** -0.5) if cfg["gcn_bias"]
                       else jnp.zeros((b,), jnp.float32))}
                for a, b in zip(f_in[:-1], f_in[1:])],
        "att": {"w": xavier((f, f), f, f)},
        "ntn": {"w": xavier((k, f, f), f * k, f * k),
                "v": xavier((k, 2 * f), 2 * f, k),
                "b": xavier((k,), 1, k)},
        "fcn": [{"w": uniform((a, b), scale * a ** -0.5),
                 "b": uniform((b,), scale * a ** -0.5)}
                for a, b in zip(fcn[:-1], fcn[1:])],
    }


def shape_key(cfg: dict) -> tuple:
    return (("n_node_labels", cfg["n_node_labels"]),
            ("gcn_dims", tuple(cfg["gcn_dims"])), ("ntn_k", cfg["ntn_k"]),
            ("fcn_dims", tuple(cfg["fcn_dims"])),
            ("fcn_init_scale", float(cfg["init"]["fcn_scale"])),
            ("gcn_bias", bool(cfg["gcn_bias"])))


def init_params(seed: int, cfg: dict):
    """Weights from the run's seed, made on the device in one jitted call."""
    key = jax.random.PRNGKey(int(np.random.default_rng(seed).integers(2**31)))
    return _init(key, shape_key(cfg))


# ------------------------------------------------------------------ forward
#
# Every contraction goes through `_mm` with a `mode`: "highest" is float32
# at full precision (the reference); "high" splits each operand into a
# bfloat16-precision high part and low part and sums the three products
# that matter, as the TPU's three-pass `high` precision does (the control
# for a float32-at-highest configuration). The rounding is
# `reduce_precision`, which no compiler elides, so the control is the same
# on every backend.

HIGHEST = jax.lax.Precision.HIGHEST


def _bf16(x):
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _mm(spec: str, a, b, mode: str):
    def one(x, y):
        return jnp.einsum(spec, x, y, precision=HIGHEST)

    if mode == "highest":
        return one(a, b)
    if mode != "high":
        raise ValueError(f"unknown precision mode {mode!r}")
    a_hi, b_hi = _bf16(a), _bf16(b)
    a_lo, b_lo = _bf16(a - a_hi), _bf16(b - b_hi)
    return one(a_hi, b_hi) + (one(a_hi, b_lo) + one(a_lo, b_hi))


def normalized_adjacency(adj, mask):
    m = mask[:, :, None] * mask[:, None, :]
    a = (adj + jnp.eye(adj.shape[-1], dtype=adj.dtype)) * m
    deg = a.sum(-1)
    inv = jnp.where(deg > 0, 1.0 / jnp.sqrt(jnp.maximum(deg, 1e-12)), 0.0)
    return a * inv[:, :, None] * inv[:, None, :]


def embed(params, adj, feats, mask, mode: str = "highest"):
    """[B, N, N] adjacency, [B, N, L] one-hot labels, [B, N] mask -> [B, F]."""
    a = normalized_adjacency(adj, mask)
    h = feats
    for layer in params["gcn"]:
        hw = _mm("bnf,fg->bng", h, layer["w"], mode)
        h = jax.nn.relu(_mm("bnm,bmg->bng", a, hw, mode) + layer["b"])
        h = h * mask[..., None]
    n = jnp.maximum(mask.sum(-1, keepdims=True), 1.0)
    c = jnp.tanh(_mm("bf,fg->bg", h.sum(1) / n, params["att"]["w"], mode))
    att = jax.nn.sigmoid(_mm("bnf,bf->bn", h, c, mode)) * mask
    return _mm("bn,bnf->bf", att, h, mode)


def head(params, h1, h2, mode: str = "highest"):
    """NTN + FCN on graph embeddings [B, F] x [B, F] -> [B] scores."""
    ntn = params["ntn"]
    t = _mm("bf,kfg->bkg", h1, ntn["w"], mode)
    bil = _mm("bkg,bg->bk", t, h2, mode)
    lin = _mm("bf,kf->bk", jnp.concatenate([h1, h2], -1), ntn["v"], mode)
    s = jax.nn.relu(bil + lin + ntn["b"])
    for i, layer in enumerate(params["fcn"]):
        s = _mm("bi,ij->bj", s, layer["w"], mode) + layer["b"]
        if i + 1 < len(params["fcn"]):
            s = jax.nn.relu(s)
    return jax.nn.sigmoid(s[:, 0])


def pair_scores(params, g1, g2, mode: str = "highest"):
    return head(params, embed(params, *g1, mode=mode),
                embed(params, *g2, mode=mode), mode=mode)


def loss(params, g1, g2, target, mode: str = "highest"):
    return jnp.mean((pair_scores(params, g1, g2, mode) - target) ** 2)


embed_jit = jax.jit(embed, static_argnames="mode")
head_jit = jax.jit(head, static_argnames="mode")
loss_and_grad_jit = jax.jit(jax.value_and_grad(loss),
                            static_argnames="mode")


# ---------------------------------------------------------------- batching

def pad(graphs, n_labels: int, n: int):
    """Dense padded arrays (adj, one-hot feats, mask) for graphs of at most
    `n` nodes."""
    b = len(graphs)
    adj = np.zeros((b, n, n), np.float32)
    feats = np.zeros((b, n, n_labels), np.float32)
    mask = np.zeros((b, n), np.float32)
    for i, g in enumerate(graphs):
        k = g["adj"].shape[0]
        adj[i, :k, :k] = g["adj"]
        feats[i, np.arange(k), g["labels"]] = 1.0
        mask[i, :k] = 1.0
    return adj, feats, mask


def size_class(n: int) -> int:
    """Padded width of a graph of `n` nodes: the next power of two >= 8."""
    return max(8, 1 << (int(n) - 1).bit_length())


def embed_graphs(params, graphs, n_labels: int, block: int = 512,
                 mode: str = "highest"):
    """[len(graphs), F] reference embeddings, graphs grouped by padded width
    and run in blocks of `block` so the working set stays small."""
    f = params["att"]["w"].shape[0]
    out = np.zeros((len(graphs), f), np.float32)
    groups: dict[int, list[int]] = {}
    for i, g in enumerate(graphs):
        groups.setdefault(size_class(g["adj"].shape[0]), []).append(i)
    for n, idx in sorted(groups.items()):
        for lo in range(0, len(idx), block):
            part = idx[lo:lo + block]
            arrays = pad([graphs[i] for i in part], n_labels, n)
            if len(part) < block:      # one compiled shape per width
                arrays = tuple(np.concatenate(
                    [x, np.zeros((block - len(part),) + x.shape[1:],
                                 x.dtype)]) for x in arrays)
            out[part] = np.asarray(
                embed_jit(params, *arrays, mode=mode))[:len(part)]
    return out


def head_blocks(params, h1, h2, block: int = 65536,
                mode: str = "highest"):
    """[B] head scores of embedding rows, in fixed-size blocks."""
    out = np.zeros(len(h1), np.float32)
    for lo in range(0, len(h1), block):
        a, b = h1[lo:lo + block], h2[lo:lo + block]
        k = len(a)
        if k < block:
            a = np.concatenate([a, np.zeros((block - k, a.shape[1]), a.dtype)])
            b = np.concatenate([b, np.zeros((block - k, b.shape[1]), b.dtype)])
        out[lo:lo + k] = np.asarray(head_jit(params, a, b, mode=mode))[:k]
    return out


def score_pairs(params, pairs, n_labels: int, mode: str = "highest"):
    """Reference scores of graph pairs: both sides embedded, then the head."""
    g1 = embed_graphs(params, [p[0] for p in pairs], n_labels, mode=mode)
    g2 = embed_graphs(params, [p[1] for p in pairs], n_labels, mode=mode)
    return head_blocks(params, g1, g2, block=max(1024, len(pairs)),
                       mode=mode)


# --------------------------------------------------------------- optimizer

def cosine_lr(step: int, peak_lr: float, warmup: int, total: int,
              floor: float = 0.1) -> float:
    """Warm-up then cosine decay; `step` counts from 0 for the first
    update."""
    s = step + 1.0
    if s < warmup:
        return peak_lr * s / warmup
    frac = min(max((s - warmup) / max(1, total - warmup), 0.0), 1.0)
    return peak_lr * (floor + (1 - floor) * 0.5 * (1 + np.cos(np.pi * frac)))


def clip(grads, max_norm: float):
    leaves = jax.tree.leaves(grads)
    norm = jnp.sqrt(sum(jnp.sum(g * g) for g in leaves))
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-9))
    return jax.tree.map(lambda g: g * scale, grads)


def adamw(params, grads, m, v, step: int, lr: float, *, b1: float, b2: float,
          eps: float, weight_decay: float):
    """One AdamW update (`step` counts from 1); weight decay on matrices
    only."""
    c1, c2 = 1.0 - b1 ** step, 1.0 - b2 ** step
    m = jax.tree.map(lambda a, g: b1 * a + (1 - b1) * g, m, grads)
    v = jax.tree.map(lambda a, g: b2 * a + (1 - b2) * g * g, v, grads)

    def upd(p, mm, vv):
        d = (mm / c1) / (jnp.sqrt(vv / c2) + eps)
        if p.ndim >= 2:
            d = d + weight_decay * p
        return p - lr * d
    return jax.tree.map(upd, params, m, v), m, v
