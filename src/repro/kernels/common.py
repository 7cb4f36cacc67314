"""Shared Pallas utilities: interpret-mode policy, compiler params, and the
SimGNN layer-loop / block-spec helpers used by all three SimGNN kernels.

All kernels in this package target TPU (pl.pallas_call + BlockSpec VMEM
tiling), where Mosaic compiles them. On the CPU backend they run with
interpret=True, which executes the kernel body with jnp semantics;
`should_interpret()` picks the mode from the backend so the same ops.py
wrappers run in both places, and raises on any other backend.

The `*_block` functions below are the in-VMEM compute bodies shared by
`fused_gcn.py`, `simgnn_head.py`, and the end-to-end megakernel
`fused_pair.py` (DESIGN.md §7): they take *values* already read from refs,
are variadic over layer count, and accumulate in fp32 regardless of the
input dtype (bf16 in / fp32 accumulate / out-dtype store).

The gather/segment aggregation bodies additionally carry `jax.custom_vjp`
rules (DESIGN.md §11): the backward pass of an edge aggregation is the SAME
aggregation with the sender and receiver planes swapped (A' is symmetric in
structure; its transpose-multiply is another edge sweep), so the packed-CSR
/ COO layouts built for the forward pass serve the backward pass unchanged
— no transposed layout is ever materialized. Integer index planes get
`float0` cotangents (indices have no tangent space), which also keeps
autodiff from tracing through the gathers. These rules are what makes the
packed scoring paths differentiable end-to-end (`kernels/grad.py`,
`core.engine.ScoringEngine.loss_and_grad`).
"""

from __future__ import annotations

import functools
from collections import Counter

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

#: Precision of every f32 contraction in the SimGNN kernel bodies. A TPU
#: matmul at default precision (Mosaic's and XLA's alike) is one MXU pass
#: that rounds f32 operands to bf16; HIGHEST keeps the full f32 mantissa,
#: which the f32 parity bounds (`core/engine.PARITY_ATOL_F32`) need and
#: which makes the one-hot gathers below reproduce their rows bit for bit.
HIGHEST = jax.lax.Precision.HIGHEST


def should_interpret() -> bool:
    """Interpret mode exactly on the CPU backend (tests, laptops); Mosaic
    compiles the kernels on TPU. Any other backend raises: there is no
    kernel lowering for it, and silently interpreting on an accelerator
    would hide that no kernel ran."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(f"no Pallas kernel lowering for backend {backend!r}; "
                       "the kernels run on TPU (or interpreted on CPU)")


#: (kernel name, interpret) -> times a SimGNN kernel was traced in this
#: process. Tracing precedes every compile (and every compile-cache hit),
#: so this is the proof `chip_smoke.py` checks that each kernel it drove
#: was lowered by Mosaic and none ran interpreted.
TRACED_KERNELS: Counter = Counter()


def pallas_call(kernel, *, name: str, interpret: bool, **kwargs):
    """`pl.pallas_call` under a stable kernel name (the name profiler
    traces and `TRACED_KERNELS` key on)."""
    TRACED_KERNELS[(name, bool(interpret))] += 1
    return pl.pallas_call(kernel, name=name, interpret=interpret, **kwargs)


def compiler_params(dimension_semantics: tuple[str, ...], *,
                    vmem_limit_bytes: int | None = None):
    """Grid dimension semantics for Mosaic ('parallel' dims may be reordered;
    'arbitrary' dims run sequentially so VMEM scratch carries across steps)
    and, where a kernel's program needs more than Mosaic's default scoped
    VMEM (16 MiB on v5e), its limit. Interpret mode ignores them."""
    return pltpu.CompilerParams(dimension_semantics=dimension_semantics,
                                vmem_limit_bytes=vmem_limit_bytes)


# ---------------------------------------------------------------- block specs

def leading_block_spec(block_shape: tuple[int, ...]) -> pl.BlockSpec:
    """BlockSpec tiling only the leading (grid) dimension: program i sees
    rows [i*block, (i+1)*block) and the full extent of every other axis."""
    nd = len(block_shape)
    return pl.BlockSpec(block_shape, lambda i: (i,) + (0,) * (nd - 1))


def replicated_spec(a: jax.Array) -> pl.BlockSpec:
    """BlockSpec broadcasting a whole (small) array to every program — used
    for weights, which are read from HBM once per block (the paper's 'read
    each element only once' principle)."""
    return pl.BlockSpec(a.shape, lambda i: (0,) * a.ndim)


# ----------------------------------------------------- variadic layer plumbing

def flatten_layer_params(layers) -> list[jax.Array]:
    """[{'w','b'}, ...] -> [w1, b1, w2, b2, ...] for variadic kernel args."""
    flat = []
    for p in layers:
        flat += [p["w"], p["b"]]
    return flat


def read_layer_refs(refs) -> list[tuple[jax.Array, jax.Array]]:
    """Inverse of `flatten_layer_params` inside a kernel: a flat tuple of
    (w, b) refs -> list of (w, b) *values*."""
    assert len(refs) % 2 == 0, len(refs)
    return [(refs[2 * i][...], refs[2 * i + 1][...])
            for i in range(len(refs) // 2)]


# ------------------------------------------------------------- VJP plumbing

def _int_zeros(x: jax.Array) -> np.ndarray:
    """float0 cotangent for an integer index plane: indices have no tangent
    space, and returning float0 (rather than float zeros) is what custom_vjp
    requires for int-dtype primals."""
    return np.zeros(np.shape(x), jax.dtypes.float0)


def onehot_block(idx: jax.Array, n: int) -> jax.Array:
    """One-hot of an index plane along a new minor axis: idx [..., M] int ->
    [..., M, n] fp32, built from an iota compare so Mosaic can lower it
    (Mosaic has no general vector gather)."""
    ids = jax.lax.broadcasted_iota(jnp.int32, idx.shape + (n,), idx.ndim)
    return (idx.astype(jnp.int32)[..., None] == ids).astype(jnp.float32)


def gather_rows_block(x: jax.Array, idx: jax.Array) -> jax.Array:
    """Per-graph row gather x[g, idx[g, m], :] as a one-hot contraction:
    x [G, N, F], idx [G, M] int -> [G, M, F] fp32. Exact (each output row
    sums one product with 1.0 at HIGHEST precision), so it is bit-identical
    to `take_along_axis` for finite rows."""
    return jax.lax.dot_general(onehot_block(idx, x.shape[1]),
                               x.astype(jnp.float32),
                               (((2,), (1,)), ((0,), (0,))),
                               precision=HIGHEST,
                               preferred_element_type=jnp.float32)


@jax.custom_vjp
def label_gather(w: jax.Array, labels: jax.Array) -> jax.Array:
    """First-layer one-hot elimination as a differentiable gather:
    `one_hot(labels) @ W == W[labels]` exactly. The one-hot lives only as
    an in-VMEM [M, n_labels] compare result contracted at HIGHEST precision
    (exact: one non-zero product per row), so the [N, n_labels] feature
    block never crosses HBM. The custom backward keeps the same discipline:
    dW = one_hot(labels)^T @ g is ONE MXU-shaped [n_labels, M] x [M, F]
    contraction instead of autodiff's per-row scatter-add.
    w [L, F], labels [G, N] int32 -> [G*N, F] fp32."""
    onehot = onehot_block(labels, w.shape[0])               # [G, N, L]
    return jnp.dot(onehot.reshape(-1, w.shape[0]), w.astype(jnp.float32),
                   precision=HIGHEST, preferred_element_type=jnp.float32)


def _label_gather_fwd(w, labels):
    return label_gather(w, labels), (w, labels)


def _label_gather_bwd(res, g):
    w, labels = res
    flat = labels.reshape(-1)
    l_ids = jax.lax.broadcasted_iota(jnp.int32, (w.shape[0], flat.shape[0]),
                                     0)
    onehot_t = (flat[None, :] == l_ids).astype(jnp.float32)     # [L, M]
    dw = jnp.dot(onehot_t, g.astype(jnp.float32),
                 precision=HIGHEST, preferred_element_type=jnp.float32)
    return dw.astype(w.dtype), _int_zeros(labels)


label_gather.defvjp(_label_gather_fwd, _label_gather_bwd)


# ------------------------------------------------------------ in-VMEM bodies

def normalize_adjacency_block(adj: jax.Array, mask: jax.Array) -> jax.Array:
    """In-kernel A' = D^-1/2 (A + I) D^-1/2 on a [GB, N, N] block.

    Same math as core.gcn.normalized_adjacency (parity-tested); the identity
    is built from broadcasted_iota so Mosaic can lower it. fp32 in/out.
    """
    _, n, _ = adj.shape
    rows = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    eye = (rows == cols).astype(adj.dtype)
    m = mask[:, :, None] * mask[:, None, :]
    a_tilde = (adj + eye[None]) * m
    deg = jnp.sum(a_tilde, axis=-1)
    inv_sqrt = jnp.where(deg > 0, jax.lax.rsqrt(jnp.maximum(deg, 1e-12)), 0.0)
    return a_tilde * inv_sqrt[:, :, None] * inv_sqrt[:, None, :]


def _add_bias(h: jax.Array, b: jax.Array) -> jax.Array:
    """[GB, N, F] aggregated features plus the layer's [F] bias. The add
    runs on the [GB*N, F] view: Mosaic broadcasts a 1-D vector over rows
    of a 2-D block, not over a 3-D one."""
    gb, n, f = h.shape
    return (h.reshape(gb * n, f) + b.astype(jnp.float32)).reshape(gb, n, f)


def gcn_layers_block(adj_norm: jax.Array, h: jax.Array | None,
                     mask: jax.Array, layer_wb, *,
                     labels: jax.Array | None = None) -> jax.Array:
    """Variadic GCN stack on one graph block, all in VMEM.

    adj_norm [GB, N, N], h [GB, N, F0], mask [GB, N] (fp32) -> [GB, N, F_last].
    layer_wb: list of (w, b) values, any length (SimGNNConfig.gcn_dims).
    Each layer is ReLU(A'(H·W) + b) * mask: the bias is added after
    aggregation, in the ReLU-and-mask epilogue (core.gcn.gcn_layer).

    With `labels` [GB, N] int32, the first layer's H·W is replaced by a row
    gather of W1 (one_hot(labels) @ W1 == W1[labels] exactly, since one-hot
    matmul rows sum a single non-zero product): no [N, n_labels] one-hot is
    ever materialized or multiplied, cutting the first layer's feature HBM
    traffic ~n_labels-fold and skipping its MXU pass. `h` may be None then.
    """
    gb, n, _ = adj_norm.shape
    for li, (w, b) in enumerate(layer_wb):
        if li == 0 and labels is not None:
            # Structural feature sparsity: one-hot first layer as a gather
            # (custom VJP: dW1 is one one-hot contraction, no scatter).
            hw = label_gather(w, labels)
        else:
            # Feature Transformation (paper MULT+ACC): one 2D MXU matmul for
            # the whole graph block — (GB*N, Fin) @ (Fin, Fout).
            hw = jnp.dot(h.reshape(gb * n, -1), w.astype(jnp.float32),
                         precision=HIGHEST, preferred_element_type=jnp.float32)
        hw = hw.reshape(gb, n, -1)
        # Aggregation (paper ACG): one batched contraction [GB,N,N]@[GB,N,F]
        # — a single MXU-shaped op instead of a per-graph unrolled dot loop.
        h = jax.lax.dot_general(adj_norm, hw, (((2,), (1,)), ((0,), (0,))),
                                precision=HIGHEST,
                                preferred_element_type=jnp.float32)
        # Bias after aggregation, then ReLU + mask: the paper's max(0,.)
        # unit at the ACG output.
        h = jnp.maximum(_add_bias(h, b), 0.0) * mask[..., None]
    return h


def _edge_aggregate(senders, receivers, weights, hw):
    """Raw segment-sum edge aggregation body (no VJP rule — see the public
    `edge_aggregate_block` wrapper)."""
    gb, n, f = hw.shape
    e = senders.shape[-1]
    gathered = jnp.take_along_axis(hw, senders[..., None], axis=1)  # [GB,E,F]
    msgs = (gathered * weights[..., None].astype(jnp.float32)).reshape(gb * e, f)
    offs = jnp.arange(gb, dtype=jnp.int32)[:, None] * n              # [GB,1]
    flat = jax.ops.segment_sum(msgs, (receivers + offs).reshape(gb * e),
                               num_segments=gb * n)
    return flat.reshape(gb, n, f)


def _edge_weight_cotangent(senders, receivers, hw, g):
    """dL/dw for one edge list: per edge, <g[receiver], hw[sender]> — the
    same two gathers as the forward pass, reduced over F. [GB, E] fp32."""
    g_r = jnp.take_along_axis(g.astype(jnp.float32), receivers[..., None],
                              axis=1)
    h_s = jnp.take_along_axis(hw.astype(jnp.float32), senders[..., None],
                              axis=1)
    return jnp.sum(g_r * h_s, axis=-1)


@jax.custom_vjp
def edge_aggregate_block(senders: jax.Array, receivers: jax.Array,
                         weights: jax.Array, hw: jax.Array) -> jax.Array:
    """In-kernel segment-sum aggregation from a tile-local edge list:
    out[g, r, :] = sum over edges e with receivers[g, e] == r of
    weights[g, e] * hw[g, senders[g, e], :].

    senders/receivers [GB, E] int32, weights [GB, E] (A' non-zeros, pad
    slots exact zero), hw [GB, N, F] -> [GB, N, F]. This is the edge-centric
    replacement for the dense `adj_norm @ hw` contraction: O(E·F) messages
    instead of O(N²·F) MACs, the paper's 'read only the non-zero A'
    elements' (§3.2.2) inside the kernel. Pad edges gather row `senders=0`,
    multiply by an exact-zero weight and land on receiver 0 — neutral by
    construction, no masking branch needed. Same gather + segment-sum idiom
    as `core.batching.edge_aggregate` (parity-tested), but flattened to ONE
    segment reduction over [GB*E] with per-block receiver offsets — one
    large scatter schedules better than GB small ones on every backend.

    Custom VJP (DESIGN.md §11): the cotangent of `hw` is the SAME edge sweep
    with sender/receiver planes swapped (transpose-aggregation), so the
    backward pass reuses the forward layout; pad edges stay exactly neutral
    in both directions (their weight is an exact zero factor of every
    product).
    """
    return _edge_aggregate(senders, receivers, weights, hw)


def _edge_aggregate_fwd(senders, receivers, weights, hw):
    return _edge_aggregate(senders, receivers, weights, hw), (
        senders, receivers, weights, hw)


def _edge_aggregate_bwd(res, g):
    senders, receivers, weights, hw = res
    d_hw = _edge_aggregate(receivers, senders, weights, g)    # swapped planes
    d_w = _edge_weight_cotangent(senders, receivers, hw, g)
    return (_int_zeros(senders), _int_zeros(receivers),
            d_w.astype(weights.dtype), d_hw.astype(hw.dtype))


edge_aggregate_block.defvjp(_edge_aggregate_fwd, _edge_aggregate_bwd)


def _overflow_aggregate(ov_snd, ov_rcv, ov_w, hw):
    """Raw COO one-hot contraction body (no VJP rule — see the public
    `overflow_aggregate_block` wrapper)."""
    gb, n, f = hw.shape
    e_ov = ov_snd.shape[-1]
    gathered = gather_rows_block(hw, ov_snd)                       # [GB,Eo,F]
    msgs = gathered * ov_w[..., None].astype(jnp.float32)
    node_ids = jax.lax.broadcasted_iota(jnp.int32, (gb, n, e_ov), 1)
    scat = (ov_rcv.astype(jnp.int32)[:, None, :]
            == node_ids).astype(jnp.float32)                       # [GB,N,Eo]
    return jax.lax.dot_general(scat, msgs, (((2,), (1,)), ((0,), (0,))),
                               precision=HIGHEST,
                               preferred_element_type=jnp.float32)


@jax.custom_vjp
def overflow_aggregate_block(ov_snd: jax.Array, ov_rcv: jax.Array,
                             ov_w: jax.Array, hw: jax.Array) -> jax.Array:
    """Aggregate the small COO overflow list (in-degree > D spill) as a
    one-hot contraction: out = onehot(receivers)^T @ (w * hw[senders]).
    With E_ov <= ~32 the [N, E_ov] @ [E_ov, F] matmul is a few percent of a
    dense layer and stays MXU-shaped — no scatter anywhere in the kernel.

    Custom VJP (DESIGN.md §11): dL/d(hw) is the same one-hot contraction
    with the sender/receiver roles swapped — a literal argument swap of the
    forward body — so the backward pass stays MXU-shaped too."""
    return _overflow_aggregate(ov_snd, ov_rcv, ov_w, hw)


def _overflow_aggregate_fwd(ov_snd, ov_rcv, ov_w, hw):
    return _overflow_aggregate(ov_snd, ov_rcv, ov_w, hw), (
        ov_snd, ov_rcv, ov_w, hw)


def _overflow_aggregate_bwd(res, g):
    ov_snd, ov_rcv, ov_w, hw = res
    d_hw = _overflow_aggregate(ov_rcv, ov_snd, ov_w, g)       # swapped planes
    d_w = _edge_weight_cotangent(ov_snd, ov_rcv, hw, g)
    return (_int_zeros(ov_snd), _int_zeros(ov_rcv),
            d_w.astype(ov_w.dtype), d_hw.astype(hw.dtype))


overflow_aggregate_block.defvjp(_overflow_aggregate_fwd,
                                _overflow_aggregate_bwd)


def _csr_aggregate(nbr, nbr_w, ov_snd, ov_rcv, ov_w, hw):
    """Raw packed-CSR aggregation body (no VJP rule — see the public
    `csr_aggregate_block` wrapper)."""
    n = hw.shape[1]
    d = nbr.shape[-1] // n
    # Plane reduction as D-1 statically-unrolled adds: plane k (static lane
    # slice k*N of the ELL planes) gathers its [GB, N, F] messages by one
    # exact one-hot contraction, so only one plane's one-hot is live at a
    # time and the reduction stays a pure vector add chain.
    out = None
    for k in range(d):
        sl = slice(k * n, (k + 1) * n)
        msgs = (gather_rows_block(hw, nbr[:, sl])
                * nbr_w[:, sl][..., None].astype(jnp.float32))
        out = msgs if out is None else out + msgs
    return out + _overflow_aggregate(ov_snd, ov_rcv, ov_w, hw)


@jax.custom_vjp
def csr_aggregate_block(nbr: jax.Array, nbr_w: jax.Array,
                        ov_snd: jax.Array, ov_rcv: jax.Array,
                        ov_w: jax.Array, hw: jax.Array) -> jax.Array:
    """Degree-aware packed-CSR aggregation (DESIGN.md §9) — scatter-free.

    nbr/nbr_w [GB, N*D] are ELLPACK neighbor *planes* (slot s holds the
    (s // N)-th in-edge of node s % N), so accumulating a node's neighbors
    is a gather + a sum of D contiguous [N, F] planes — fully vectorizable,
    no scatter at all. The heavy tail (nodes with in-degree > D) arrives as
    the small COO overflow list and takes a one-hot contraction
    (`overflow_aggregate_block`) — Accel-GCN's degree-aware workload split:
    regular rows on the vector path, outlier rows on the matrix path. Pad
    slots carry exact-zero weights.

    Custom VJP (DESIGN.md §11): the backward pass runs over the SAME
    ELLPACK/COO planes. The receiver of ELL slot s is the implicit s % N,
    so gathering the output cotangent by receiver is a free plane tiling;
    the message cotangents then scatter to the *senders* with one flat
    segment-sum (the `edge_aggregate_block` idiom) while the COO tail is
    again a literal sender/receiver swap of the one-hot contraction. No
    transposed edge layout is ever built.
    """
    return _csr_aggregate(nbr, nbr_w, ov_snd, ov_rcv, ov_w, hw)


def _csr_aggregate_fwd(nbr, nbr_w, ov_snd, ov_rcv, ov_w, hw):
    return _csr_aggregate(nbr, nbr_w, ov_snd, ov_rcv, ov_w, hw), (
        nbr, nbr_w, ov_snd, ov_rcv, ov_w, hw)


def _csr_bwd_outputs(res, g32, d_hw):
    """Shared tail of both CSR backward rules: the per-slot weight
    cotangents (orientation-exact regardless of A' symmetry — XLA DCEs
    them when only param grads are requested) and the output tuple.
    Receiver gather is free: ELL slot d*N + r reads g[r] — D plane tiles.
    """
    nbr, nbr_w, ov_snd, ov_rcv, ov_w, hw = res
    d = nbr.shape[-1] // hw.shape[1]
    g_r = jnp.tile(g32, (1, d, 1))                               # [GB,N*D,F]
    h_s = jnp.take_along_axis(hw.astype(jnp.float32), nbr[..., None], axis=1)
    d_nbr_w = jnp.sum(g_r * h_s, axis=-1)                        # [GB, N*D]
    d_ov_w = _edge_weight_cotangent(ov_snd, ov_rcv, hw, g32)
    return (_int_zeros(nbr), d_nbr_w.astype(nbr_w.dtype),
            _int_zeros(ov_snd), _int_zeros(ov_rcv),
            d_ov_w.astype(ov_w.dtype), d_hw.astype(hw.dtype))


def _csr_aggregate_bwd(res, g):
    nbr, nbr_w, ov_snd, ov_rcv, ov_w, hw = res
    gb, n, f = hw.shape
    d = nbr.shape[-1] // n
    g32 = g.astype(jnp.float32)
    # Generic transpose-aggregation: gather the cotangent by the implicit
    # receivers (plane tiling), scatter to senders with one flat
    # segment-sum (the edge_aggregate_block idiom).
    msgs = (jnp.tile(g32, (1, d, 1))
            * nbr_w[..., None].astype(jnp.float32)).reshape(gb * n * d, f)
    offs = jnp.arange(gb, dtype=jnp.int32)[:, None] * n
    d_hw = jax.ops.segment_sum(msgs, (nbr + offs).reshape(gb * n * d),
                               num_segments=gb * n).reshape(gb, n, f)
    d_hw = d_hw + _overflow_aggregate(ov_rcv, ov_snd, ov_w, g32)
    return _csr_bwd_outputs(res, g32, d_hw)


csr_aggregate_block.defvjp(_csr_aggregate_fwd, _csr_aggregate_bwd)


@jax.custom_vjp
def csr_aggregate_block_sym(nbr: jax.Array, nbr_w: jax.Array,
                            ov_snd: jax.Array, ov_rcv: jax.Array,
                            ov_w: jax.Array, hw: jax.Array) -> jax.Array:
    """`csr_aggregate_block` for a structurally SYMMETRIC A' — which every
    normalized adjacency in this codebase is (undirected graphs + self
    loops, and symmetry survives the block-diagonal packing). Identical
    forward; the backward exploits A'^T == A': the `hw` cotangent
    d_hw = A'^T g = A' g is the SAME scatter-free forward aggregation
    applied to the output cotangent — plane adds + the small one-hot
    contraction, zero scatters in the backward pass (the generic rule's
    scatter-by-sender segment-sum disappears). Note the symmetry argument
    only holds for the COMBINED ELL+COO split: a single edge may sit in the
    ELL planes while its mirror spilled to the overflow list, so neither
    part is symmetric alone — the backward therefore re-runs the whole
    combined aggregation, never the parts separately. Per-slot weight
    cotangents keep the generic (orientation-exact) rule.

    `gcn_layers_edge_block` (the GCN stack, where A' is symmetric by
    construction) uses this variant; callers with directed/asymmetric edge
    lists must use `csr_aggregate_block`.
    """
    return _csr_aggregate(nbr, nbr_w, ov_snd, ov_rcv, ov_w, hw)


def _csr_aggregate_sym_bwd(res, g):
    nbr, nbr_w, ov_snd, ov_rcv, ov_w, hw = res
    g32 = g.astype(jnp.float32)
    # A' symmetric: transpose-aggregation IS the forward aggregation on g.
    d_hw = _csr_aggregate(nbr, nbr_w, ov_snd, ov_rcv, ov_w, g32)
    return _csr_bwd_outputs(res, g32, d_hw)


csr_aggregate_block_sym.defvjp(_csr_aggregate_fwd, _csr_aggregate_sym_bwd)


def gcn_layers_edge_block(nbr: jax.Array, nbr_w: jax.Array,
                          ov_snd: jax.Array, ov_rcv: jax.Array,
                          ov_w: jax.Array, h: jax.Array | None,
                          mask: jax.Array, layer_wb, *,
                          labels: jax.Array | None = None) -> jax.Array:
    """Variadic GCN stack whose aggregation runs from the packed-CSR edge
    lists (DESIGN.md §9) — the sparse twin of `gcn_layers_block`.

    The dense path's per-layer `adj_norm @ (H·W)` batched contraction is
    replaced by `csr_aggregate_block`; the feature transform (H·W matmul,
    or the first-layer W1 label gather when int `labels` are given) and
    the epilogue, bias after aggregation then ReLU and mask, are
    identical. No adjacency or in-kernel normalization at all: the edge
    weights are the host-precomputed A' non-zeros (the FPGA
    host-preprocessing role, paper §3.2.2), so the [GB, N, N] block never
    exists on-chip.
    """
    gb, n = mask.shape
    for li, (w, b) in enumerate(layer_wb):
        if li == 0 and labels is not None:
            # Structural feature sparsity: one-hot first layer as a gather
            # (custom VJP: dW1 is one one-hot contraction, no scatter).
            hw = label_gather(w, labels)
        else:
            hw = jnp.dot(h.reshape(gb * n, -1), w.astype(jnp.float32),
                         precision=HIGHEST, preferred_element_type=jnp.float32)
        hw = hw.reshape(gb, n, -1)
        # A' is symmetric here by construction (undirected + self loops),
        # so the sym variant's scatter-free transpose-aggregate backward
        # applies (DESIGN.md §11).
        h = csr_aggregate_block_sym(nbr, nbr_w, ov_snd, ov_rcv, ov_w, hw)
        h = jnp.maximum(_add_bias(h, b), 0.0) * mask[..., None]
    return h


def att_pool_block(h: jax.Array, mask: jax.Array,
                   att_w: jax.Array) -> jax.Array:
    """Att stage (paper §4.2, Eq. 3): h [GB, N, F], mask [GB, N] -> [GB, F]."""
    n_valid = jnp.maximum(jnp.sum(mask, axis=1, keepdims=True), 1.0)   # [GB,1]
    mean_h = jnp.sum(h * mask[..., None], axis=1) / n_valid            # [GB,F]
    c = jnp.tanh(jnp.dot(mean_h, att_w.astype(jnp.float32),
                         precision=HIGHEST,
                         preferred_element_type=jnp.float32))          # [GB,F]
    att = jax.nn.sigmoid(jnp.sum(h * c[:, None, :], axis=-1)) * mask   # [GB,N]
    return jnp.sum(att[..., None] * h, axis=1)                         # [GB,F]


def gcn_att_block(adj_norm: jax.Array, h: jax.Array, mask: jax.Array,
                  layer_wb, att_w: jax.Array, *,
                  labels: jax.Array | None = None) -> jax.Array:
    """GCN stack + per-graph Att pooling: [GB, N, F0] -> [GB, F_last]."""
    h = gcn_layers_block(adj_norm, h, mask, layer_wb, labels=labels)
    return att_pool_block(h, mask, att_w)


def segment_onehot(seg: jax.Array, mask: jax.Array,
                   n_segments: int) -> jax.Array:
    """Segment-membership matrix S [GB, P, N] from per-node segment IDs.

    S[g, p, n] = 1 iff node slot n belongs to segment p AND is a real node.
    Built from broadcasted_iota so Mosaic can lower it; pad slots (mask 0)
    are zero in every segment row, so S-contractions give them exact-zero
    contributions without any branch.
    """
    gb, n = seg.shape
    p_ids = jax.lax.broadcasted_iota(jnp.int32, (gb, n_segments, n), 1)
    return (seg[:, None, :] == p_ids).astype(jnp.float32) * mask[:, None, :]


def _seg_att_pool_from_onehot(h, mask, s, att_w):
    """Segment Att pooling given a precomputed segment one-hot S [GB, P, N]
    (the shared body of `segment_att_pool_block`'s forward AND backward)."""
    counts = jnp.maximum(jnp.sum(s, axis=-1, keepdims=True), 1.0)      # [GB,P,1]
    batched = (((2,), (1,)), ((0,), (0,)))
    mean_h = jax.lax.dot_general(s, h, batched,
                                 precision=HIGHEST,
                                 preferred_element_type=jnp.float32) / counts
    gb, p, f = mean_h.shape
    c = jnp.tanh(jnp.dot(mean_h.reshape(gb * p, f), att_w.astype(jnp.float32),
                         precision=HIGHEST,
                         preferred_element_type=jnp.float32)).reshape(gb, p, f)
    # Per-node context = its own segment's c, fetched by one S^T contraction.
    c_node = jax.lax.dot_general(s, c, (((1,), (1,)), ((0,), (0,))),
                                 precision=HIGHEST,
                                 preferred_element_type=jnp.float32)   # [GB,N,F]
    att = jax.nn.sigmoid(jnp.sum(h * c_node, axis=-1)) * mask          # [GB,N]
    return jax.lax.dot_general(s, att[..., None] * h, batched,
                               precision=HIGHEST,
                               preferred_element_type=jnp.float32)     # [GB,P,F]


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def segment_att_pool_block(h: jax.Array, mask: jax.Array, seg: jax.Array,
                           att_w: jax.Array, n_segments: int) -> jax.Array:
    """Att pooling per *segment* of a packed tile (DESIGN.md §8).

    h [GB, N, F], seg [GB, N] int32 in [0, P) -> [GB, P, F] — the per-graph
    leading dim of `att_pool_block` becomes a segment axis: the per-graph
    mean/softmax-sigmoid/sum reductions turn into contractions against the
    segment one-hot S, so all three stay MXU-shaped batched matmuls. Empty
    segments (pad pair slots) yield all-zero embeddings.

    Custom VJP (DESIGN.md §11): the segment one-hot S is built once in the
    forward pass and saved as a residual — the backward differentiates the
    pure-matmul body against the SAME S (matmul transposes are matmuls), so
    the int32 `seg` plane never enters autodiff (float0 cotangent) and the
    iota-compare that builds S is never re-traced. Pad node slots are zero
    rows of S, so their cotangents are exact zeros in both directions.
    """
    s = segment_onehot(seg, mask, n_segments)                          # [GB,P,N]
    return _seg_att_pool_from_onehot(h, mask, s, att_w)


def _segment_att_pool_fwd(h, mask, seg, att_w, n_segments):
    s = segment_onehot(seg, mask, n_segments)
    return _seg_att_pool_from_onehot(h, mask, s, att_w), (
        h, mask, seg, s, att_w)


def _segment_att_pool_bwd(n_segments, res, g):
    h, mask, seg, s, att_w = res
    _, pull = jax.vjp(_seg_att_pool_from_onehot, h, mask, s, att_w)
    d_h, d_mask, d_s, d_att_w = pull(g.astype(jnp.float32))
    # S = onehot(seg) * mask[:, None, :] also carries mask sensitivity:
    # dS/dmask[g, n] is 1 only at row seg[g, n], fetched by one gather.
    d_mask = d_mask + jnp.take_along_axis(d_s, seg[:, None, :],
                                          axis=1)[:, 0, :]
    return (d_h.astype(h.dtype), d_mask.astype(mask.dtype),
            _int_zeros(seg), d_att_w.astype(att_w.dtype))


segment_att_pool_block.defvjp(_segment_att_pool_fwd, _segment_att_pool_bwd)


def ntn_fcn_block(h1: jax.Array, h2: jax.Array, wt: jax.Array, vt: jax.Array,
                  bias: jax.Array, fcn_wb) -> jax.Array:
    """NTN + FCN on one pair block, all in VMEM: h1/h2 [GB, F] -> [GB, 1]
    sigmoid scores. `wt` is W [K,F,F] pre-reshaped to [F, K*F] and `vt` is
    V [K,2F] transposed, so both contractions are pure matmuls."""
    f = h1.shape[-1]
    k = bias.shape[0]
    t = jnp.dot(h1, wt.astype(jnp.float32),
                precision=HIGHEST,
                preferred_element_type=jnp.float32)                    # [GB,K*F]
    # Slice k of the bilinear form is lane block k of t against h2: K
    # static lane slices (Mosaic has no [GB, K*F] -> [GB, K, F] cast).
    bilinear = jnp.concatenate(
        [jnp.sum(t[:, i * f:(i + 1) * f] * h2, axis=-1, keepdims=True)
         for i in range(k)], axis=-1)                                  # [GB,K]
    cat = jnp.concatenate([h1, h2], axis=-1)                           # [GB,2F]
    linear = jnp.dot(cat, vt.astype(jnp.float32),
                     precision=HIGHEST, preferred_element_type=jnp.float32)
    s = jnp.maximum(bilinear + linear + bias.astype(jnp.float32), 0.0)
    n_fc = len(fcn_wb)
    for i, (w, b) in enumerate(fcn_wb):
        s = jnp.dot(s, w.astype(jnp.float32),
                    precision=HIGHEST,
                    preferred_element_type=jnp.float32) + b.astype(jnp.float32)
        if i + 1 < n_fc:
            s = jnp.maximum(s, 0.0)
    return jax.nn.sigmoid(s)                                           # [GB,1]
