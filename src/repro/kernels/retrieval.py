"""Blocked streaming top-M retrieval prefilter (DESIGN.md §14).

The two-stage query path (serve/search.py) shortlists M candidates per
query with a cheap embedding-space proxy before the exact NTN+FCN rerank.
The proxy scan is this kernel: a [Q, F] query-vector block against the
resident [N, F] corpus matrix, streamed in VMEM-sized *column* blocks of
`block_cols` corpus rows. Each sequential grid step computes one
[BQ, block_cols] score tile, merges it into a running per-query top-M
(score + corpus index) held in the revisited output refs, and moves on —
the full [Q, N] score matrix is NEVER materialized, which is the whole
point at corpus scale (a million rows is a 4 GB float32 score matrix per
128-query batch; the running state is [Q, M]). Same row-blocked streaming
discipline Accel-GCN applies to aggregation, pointed at the retrieval scan.

The proxy itself is a plain dot product in embedding space. For ranking
fidelity against the real NTN+FCN head, `fit_prefilter_calibration`
ridge-fits the head's logit as a linear function of the NTN bilinear
features and collapses the fit into ONE F-vector per query
(`prefilter_query_vectors`), so calibration changes nothing about the
kernel — only what is fed to it.

Shard alignment: `retrieval_block_cols` sizes the column block to the
persisted shard rows of `core/store.py` (DESIGN.md §13), so the kernel's
sequential block loop walks the corpus in 1:1 correspondence with the
on-disk shards — the unit a later multi-process sharded server distributes.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import (HIGHEST, compiler_params,
                                  flatten_layer_params, pallas_call,
                                  read_layer_refs, replicated_spec,
                                  should_interpret)

__all__ = ["RETRIEVAL_MAX_BLOCK_COLS", "NEG_FILL", "retrieval_block_cols",
           "blocked_topm", "blocked_topm_ntn", "collapse_query_ntn",
           "topm_reference", "ntn_logit_reference",
           "fit_prefilter_calibration", "prefilter_query_vectors"]

#: Hard ceiling on corpus rows per streamed block — the block-shape guard
#: that enforces "never materialize [Q, N]": a [128, 1024] f32 score tile
#: is 512 KB of VMEM; one block spanning a million-row corpus would not be.
RETRIEVAL_MAX_BLOCK_COLS = 1024

#: Finite sentinel for non-finite proxy scores (NaN corpus/query embedding
#: rows from dropped embed buckets, DESIGN.md §12). Finite on purpose: it
#: still outranks the -inf slots used for top-M init placeholders and
#: padded corpus columns, so NaN rows rank LAST among real rows but padding
#: and placeholders can never surface as results.
NEG_FILL = float(np.float32(-3.0e38))


def retrieval_block_cols(n_corpus: int, *,
                         shard_rows: int | None = None) -> int:
    """Corpus-column block size for `blocked_topm`.

    With `shard_rows` (the persisted shard size of the serving index), the
    block is the shard itself when it fits the VMEM ceiling — sequential
    grid step j then scans exactly shard j. Oversized shards are halved
    until they fit, so blocks still nest evenly inside shard boundaries.
    Without a store, the block is the corpus rounded up to a power of two,
    capped at `RETRIEVAL_MAX_BLOCK_COLS`.
    """
    if n_corpus < 1:
        raise ValueError(f"n_corpus must be >= 1, got {n_corpus}")
    if shard_rows is not None and shard_rows >= 1:
        b = int(shard_rows)
        while b > RETRIEVAL_MAX_BLOCK_COLS and b % 2 == 0:
            b //= 2
        return min(b, RETRIEVAL_MAX_BLOCK_COLS)
    b = 8
    while b < n_corpus and b < RETRIEVAL_MAX_BLOCK_COLS:
        b *= 2
    return b


def _merge_topm(out_s_ref, out_i_ref, s, *, m: int, block_cols: int,
                n_valid: int):
    """Fold one [BQ, block_cols] score tile into the running per-query
    top-M held in the revisited output refs (sequential grid dim 1)."""
    j = pl.program_id(1)
    # The guard the tests assert: one program only ever sees a
    # [BQ, block_cols] score tile, never [Q, N].
    assert s.shape[1] == block_cols, s.shape
    col = j * block_cols + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(jnp.isfinite(s), s, NEG_FILL)           # NaN rows rank last
    s = jnp.where(col < n_valid, s, -jnp.inf)             # padding never wins

    @pl.when(j == 0)
    def _init():                                          # noqa: ANN202
        out_s_ref[...] = jnp.full(out_s_ref.shape, -jnp.inf, out_s_ref.dtype)
        out_i_ref[...] = jnp.zeros(out_i_ref.shape, out_i_ref.dtype)

    # Merge the running top-M (positions [0, M)) with this block (positions
    # M + c) by M rounds of max extraction; Mosaic has no top_k. Round r
    # takes the largest (score, -position) strictly after round r-1's pick,
    # so ties resolve to the EARLIEST position — running entries come from
    # earlier (lower-index) blocks, so that is the ascending corpus index,
    # the same order the exact path's stable sort produces. Tracking the
    # last pick instead of a taken-mask also ranks the -inf placeholders
    # and padding correctly (they tie at -inf and go by position).
    run_s, run_i = out_s_ref[...], out_i_ref[...]
    pos_r = jax.lax.broadcasted_iota(jnp.int32, run_s.shape, 1)
    pos_b = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + m
    slot = jax.lax.broadcasted_iota(jnp.int32, run_s.shape, 1)
    none = m + block_cols

    def pick(r, carry):
        last_s, last_p, top_s, top_i = carry                  # [BQ, 1] x2

        def after(v, pos):
            return (v < last_s) | ((v == last_s) & (pos > last_p))

        ok_r, ok_b = after(run_s, pos_r), after(s, pos_b)
        best = jnp.maximum(
            jnp.max(jnp.where(ok_r, run_s, -jnp.inf), axis=1, keepdims=True),
            jnp.max(jnp.where(ok_b, s, -jnp.inf), axis=1, keepdims=True))
        first = jnp.minimum(
            jnp.min(jnp.where(ok_r & (run_s == best), pos_r, none), axis=1,
                    keepdims=True),
            jnp.min(jnp.where(ok_b & (s == best), pos_b, none), axis=1,
                    keepdims=True))
        idx = jnp.maximum(
            jnp.max(jnp.where(pos_r == first, run_i, -1), axis=1,
                    keepdims=True),
            jnp.max(jnp.where(pos_b == first, col, -1), axis=1,
                    keepdims=True))
        top_s = jnp.where(slot == r, best, top_s)
        top_i = jnp.where(slot == r, idx, top_i)
        return best, first, top_s, top_i

    bq = run_s.shape[0]
    init = (jnp.full((bq, 1), jnp.inf, jnp.float32),
            jnp.full((bq, 1), -1, jnp.int32), run_s, run_i)
    _, _, top_s, top_i = jax.lax.fori_loop(0, m, pick, init)
    out_s_ref[...] = top_s
    out_i_ref[...] = top_i


def _topm_kernel(qv_ref, c_ref, out_s_ref, out_i_ref, *, m: int,
                 block_cols: int, n_valid: int):
    qb = qv_ref[...].astype(jnp.float32)                  # [BQ, F]
    cb = c_ref[...].astype(jnp.float32)                   # [BN, F]
    s = jax.lax.dot_general(qb, cb, (((1,), (1,)), ((), ())),
                            precision=HIGHEST,
                            preferred_element_type=jnp.float32)
    _merge_topm(out_s_ref, out_i_ref, s, m=m, block_cols=block_cols,
                n_valid=n_valid)


def _topm_ntn_kernel(uq_ref, dq_ref, c_ref, *refs, m: int, block_cols: int,
                     n_valid: int):
    out_s_ref, out_i_ref = refs[-2], refs[-1]
    layers = read_layer_refs(refs[:-2])                   # (W^T, b column)
    cb = c_ref[...].astype(jnp.float32)                   # [BN, F]
    rows = []
    for q in range(uq_ref.shape[0]):
        # Exact NTN activations, query side pre-collapsed: all K slices of
        # the bilinear+linear form for query q are ONE [K, F] x [F, BN]
        # matmul against the corpus block (the pairwise head pays a K*F-wide
        # contraction PER PAIR — the 1-vs-N structure is the whole saving).
        # Corpus rows stay on lanes through the whole FCN, so every layer
        # is a [out, in] x [in, BN] matmul and the logits come out as a row.
        x = jax.lax.dot_general(uq_ref[q].astype(jnp.float32), cb,
                                (((1,), (1,)), ((), ())),
                                precision=HIGHEST,
                                preferred_element_type=jnp.float32)
        x = jnp.maximum(x + dq_ref[q].astype(jnp.float32), 0.0)   # [K, BN]
        # Exact FCN on the activation tile; the pre-sigmoid logit is the
        # proxy (sigmoid is monotone, so top-M is unchanged by skipping it).
        for li, (wl, bl) in enumerate(layers):
            x = jnp.dot(wl.astype(jnp.float32), x,
                        precision=HIGHEST, preferred_element_type=jnp.float32)
            x = x + bl.astype(jnp.float32)
            if li + 1 < len(layers):
                x = jnp.maximum(x, 0.0)
        rows.append(x)                                    # [1, BN]
    _merge_topm(out_s_ref, out_i_ref, jnp.concatenate(rows, axis=0), m=m,
                block_cols=block_cols, n_valid=n_valid)


def _rep2(a) -> pl.BlockSpec:
    """2D-grid replicated spec: every program sees the whole (small) array."""
    return pl.BlockSpec(a.shape, lambda i, j: (0,) * a.ndim)


def _pad_pow2(q: int, cap: int) -> tuple[int, int]:
    """(padded rows, query-block rows): queries pad to a power of two so the
    jit cache holds one executable per shape *class*, not per batch size."""
    qp = 8
    while qp < q:
        qp *= 2
    return qp, min(cap, qp)


@functools.partial(jax.jit,
                   static_argnames=("m", "block_cols", "interpret"))
def _blocked_topm(qv, corpus, *, m: int, block_cols: int, interpret: bool):
    q, f = qv.shape
    n = corpus.shape[0]
    qp, block_q = _pad_pow2(q, 128)
    npad = -(-n // block_cols) * block_cols
    qv = jnp.pad(qv.astype(jnp.float32), ((0, qp - q), (0, 0)))
    cp = jnp.pad(corpus.astype(jnp.float32), ((0, npad - n), (0, 0)))
    kern = functools.partial(_topm_kernel, m=m, block_cols=block_cols,
                             n_valid=n)
    out_s, out_i = pallas_call(
        kern,
        name="blocked_topm",
        grid=(qp // block_q, npad // block_cols),
        in_specs=[pl.BlockSpec((block_q, f), lambda i, j: (i, 0)),
                  pl.BlockSpec((block_cols, f), lambda i, j: (j, 0))],
        # Constant index along j: the per-query running top-M lives in the
        # revisited output block across the sequential column scan.
        out_specs=[pl.BlockSpec((block_q, m), lambda i, j: (i, 0)),
                   pl.BlockSpec((block_q, m), lambda i, j: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((qp, m), jnp.float32),
                   jax.ShapeDtypeStruct((qp, m), jnp.int32)],
        compiler_params=compiler_params(("parallel", "arbitrary")),
        interpret=interpret,
    )(qv, cp)
    return out_s[:q], out_i[:q]


@functools.partial(jax.jit,
                   static_argnames=("m", "block_cols", "interpret"))
def _blocked_topm_ntn(uq, dq, corpus, fcn_flat, *, m: int, block_cols: int,
                      interpret: bool):
    q, kf = uq.shape
    k = dq.shape[1]
    n, f = corpus.shape
    # Smaller query block than the dot kernel: the kernel unrolls one
    # [K, block_cols] activation tile per query of the block.
    qp, block_q = _pad_pow2(q, 8)
    npad = -(-n // block_cols) * block_cols
    # Query operands as [Q, K, F] / [Q, K, 1] so uq_ref[q] is a [K, F]
    # matmul operand and dq_ref[q] a column (no lane->sublane cast in the
    # kernel); FCN weights transposed to [out, in] with column biases.
    uq = jnp.pad(uq.astype(jnp.float32),
                 ((0, qp - q), (0, 0))).reshape(qp, k, f)
    dq = jnp.pad(dq.astype(jnp.float32), ((0, qp - q), (0, 0)))[..., None]
    cp = jnp.pad(corpus.astype(jnp.float32), ((0, npad - n), (0, 0)))
    fcn_flat = tuple(a.T if a.ndim == 2 else a[:, None] for a in fcn_flat)
    kern = functools.partial(_topm_ntn_kernel, m=m, block_cols=block_cols,
                             n_valid=n)
    out_s, out_i = pallas_call(
        kern,
        name="blocked_topm_ntn",
        grid=(qp // block_q, npad // block_cols),
        in_specs=[pl.BlockSpec((block_q, k, f), lambda i, j: (i, 0, 0)),
                  pl.BlockSpec((block_q, k, 1), lambda i, j: (i, 0, 0)),
                  pl.BlockSpec((block_cols, f), lambda i, j: (j, 0))]
                 + [_rep2(a) for a in fcn_flat],
        out_specs=[pl.BlockSpec((block_q, m), lambda i, j: (i, 0)),
                   pl.BlockSpec((block_q, m), lambda i, j: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((qp, m), jnp.float32),
                   jax.ShapeDtypeStruct((qp, m), jnp.int32)],
        compiler_params=compiler_params(("parallel", "arbitrary")),
        interpret=interpret,
    )(uq, dq, cp, *fcn_flat)
    return out_s[:q], out_i[:q]


def blocked_topm(qv, corpus, m: int, *, block_cols: int | None = None,
                 interpret: bool | None = None):
    """Streaming top-M proxy scan: `(scores [Q, M], indices [Q, M])`.

    `qv` is [Q, F] query vectors (raw embeddings for a dot proxy, or
    `prefilter_query_vectors` output for the calibrated proxy), `corpus`
    the resident [N, F] matrix. Scores within each row are descending;
    indices are corpus row numbers. M is clamped to N. Raises `ValueError`
    if `block_cols` exceeds `RETRIEVAL_MAX_BLOCK_COLS` — the caller-visible
    half of the never-materialize-[Q, N] contract.
    """
    qv = jnp.asarray(qv, jnp.float32)
    corpus = jnp.asarray(corpus, jnp.float32)
    if qv.ndim != 2 or corpus.ndim != 2 or qv.shape[1] != corpus.shape[1]:
        raise ValueError(f"shape mismatch: qv {qv.shape} vs corpus "
                         f"{corpus.shape}")
    args = _scan_args(qv.shape[0], corpus.shape[0], m, block_cols, interpret)
    if args is None:
        return (np.zeros((qv.shape[0], 0), np.float32),
                np.zeros((qv.shape[0], 0), np.int32))
    s, i = _blocked_topm(qv, corpus, **args)
    return np.asarray(s), np.asarray(i)


def blocked_topm_ntn(uq, dq, corpus, fcn_params, m: int, *,
                     block_cols: int | None = None,
                     interpret: bool | None = None):
    """Exact streamed NTN+FCN top-M scan — the escalated proxy rung.

    `(uq, dq)` come from `collapse_query_ntn`: the query side of the NTN is
    folded into one [K, F] matrix + [K] offset per query (paid once), so
    each corpus block costs K matmul slices + the tiny FCN instead of the
    pairwise head's per-pair K*F-wide contraction — exact ranking at a
    fraction of the full-head scan's work, still never materializing
    [Q, N]. Returns `(logits [Q, M], indices [Q, M])`; logits are
    pre-sigmoid exact head scores (monotone in the served similarity).
    """
    uq = jnp.asarray(uq, jnp.float32)
    dq = jnp.asarray(dq, jnp.float32)
    corpus = jnp.asarray(corpus, jnp.float32)
    if uq.shape[1] != dq.shape[1] * corpus.shape[1]:
        raise ValueError(f"uq {uq.shape} is not [Q, K*F] for dq {dq.shape} "
                         f"and corpus {corpus.shape}")
    args = _scan_args(uq.shape[0], corpus.shape[0], m, block_cols, interpret)
    if args is None:
        return (np.zeros((uq.shape[0], 0), np.float32),
                np.zeros((uq.shape[0], 0), np.int32))
    flat = tuple(jnp.asarray(a, jnp.float32)
                 for a in flatten_layer_params(fcn_params))
    s, i = _blocked_topm_ntn(uq, dq, corpus, flat, **args)
    return np.asarray(s), np.asarray(i)


def _scan_args(q: int, n: int, m: int, block_cols: int | None,
               interpret: bool | None) -> dict | None:
    """Shared clamp/guard policy of both scan wrappers (None: empty scan)."""
    if q == 0 or n == 0:
        return None
    if block_cols is None:
        block_cols = retrieval_block_cols(n)
    if block_cols > RETRIEVAL_MAX_BLOCK_COLS:
        raise ValueError(
            f"block_cols={block_cols} exceeds RETRIEVAL_MAX_BLOCK_COLS="
            f"{RETRIEVAL_MAX_BLOCK_COLS}: a block that wide materializes "
            "the score matrix the streaming scan exists to avoid")
    return {"m": int(max(1, min(m, n))), "block_cols": int(block_cols),
            "interpret": should_interpret() if interpret is None
            else interpret}


def topm_reference(qv, corpus, m: int):
    """Dense numpy reference for `blocked_topm` (same sentinel and tie
    order): materializes [Q, N] — tests only."""
    s = np.asarray(qv, np.float32) @ np.asarray(corpus, np.float32).T
    return _rank_reference(s, m)


def collapse_query_ntn(ntn_params, hq) -> tuple[np.ndarray, np.ndarray]:
    """Fold the NTN's query side into per-query scan operands.

    Slice k of the NTN pre-activation is
    `h_q W_k h_c + v_k[:F]·h_q + v_k[F:]·h_c + b_k`; grouping by the
    candidate gives `(h_q W_k + v_k[F:])·h_c + (v_k[:F]·h_q + b_k)`.
    Returns `(uq [Q, K*F], dq [Q, K])` — the candidate-facing matrices and
    the per-query constants. One K·F² contraction per query, amortized
    over the whole corpus scan."""
    w = np.asarray(ntn_params["w"], np.float32)             # [K, F, F]
    v = np.asarray(ntn_params["v"], np.float32)             # [K, 2F]
    b = np.asarray(ntn_params["b"], np.float32)             # [K]
    hq = np.asarray(hq, np.float32)
    f = w.shape[1]
    uq = np.einsum("qf,kfg->qkg", hq, w) + v[None, :, f:]
    dq = hq @ v[:, :f].T + b[None, :]
    return (uq.reshape(hq.shape[0], -1).astype(np.float32),
            dq.astype(np.float32))


def ntn_logit_reference(uq, dq, corpus, fcn_params, m: int):
    """Dense numpy reference for `blocked_topm_ntn`: materializes [Q, N]
    — tests only."""
    corpus = np.asarray(corpus, np.float32)
    q, (n, f) = np.asarray(uq).shape[0], corpus.shape
    k = np.asarray(dq).shape[1]
    a = np.einsum("qkf,nf->qnk", np.asarray(uq, np.float32).reshape(q, k, f),
                  corpus) + np.asarray(dq, np.float32)[:, None, :]
    x = np.maximum(a, 0.0)
    for li, p in enumerate(fcn_params):
        x = x @ np.asarray(p["w"], np.float32) + np.asarray(p["b"],
                                                            np.float32)
        if li + 1 < len(fcn_params):
            x = np.maximum(x, 0.0)
    return _rank_reference(x[..., 0], m)


def _rank_reference(s: np.ndarray, m: int):
    s = np.where(np.isfinite(s), s, np.float32(NEG_FILL)).astype(np.float32)
    m = int(max(1, min(m, s.shape[1])))
    order = np.argsort(-s, axis=1, kind="stable")[:, :m]
    return (np.take_along_axis(s, order, axis=1),
            order.astype(np.int32))


# ------------------------------------------------------------- calibration

def fit_prefilter_calibration(ntn_w, hq, hc, exact_scores, *,
                              ridge: float = 1e-4) -> dict:
    """Fit the proxy so dot-product ranking tracks the exact head.

    The head's pre-sigmoid score is (through the FCN) a nonlinear function
    of the K NTN activations  relu(h_q W_k h_c + v_k·[h_q; h_c] + b_k).
    Ridge-regressing the exact score's logit on the bilinear features
    phi_k = h_q W_k h_c  plus h_c (the candidate half of the linear term)
    and h_q captures the head's dominant linear structure; everything
    query-only is rank-constant per query and irrelevant to top-M. The fit
    collapses into coefficients (alpha [K], beta [F]) such that

        proxy(q, c) = (sum_k alpha_k (h_q @ W_k) + beta) · h_c

    — i.e. one calibrated F-vector per query (`prefilter_query_vectors`)
    and the scan stays a pure blocked dot product. Returns
    {"alpha", "beta", "r2", "n_samples"}; `r2` is the in-sample fit quality
    on logits (diagnostic — recall@k is the metric that gates).
    """
    w = np.asarray(ntn_w, np.float32)                       # [K, F, F]
    hq = np.asarray(hq, np.float32)
    hc = np.asarray(hc, np.float32)
    y = np.asarray(exact_scores, np.float64)
    ok = (np.isfinite(hq).all(axis=-1) & np.isfinite(hc).all(axis=-1)
          & np.isfinite(y))
    hq, hc, y = hq[ok], hc[ok], y[ok]
    if len(y) < w.shape[0]:
        raise ValueError(f"need >= {w.shape[0]} finite calibration pairs, "
                         f"got {len(y)}")
    y = np.log(np.clip(y, 1e-6, 1 - 1e-6)) - np.log1p(
        -np.clip(y, 1e-6, 1 - 1e-6))
    t = np.einsum("qf,kfg->qkg", hq, w)                     # [S, K, F]
    phi = np.einsum("qkg,qg->qk", t, hc)                    # [S, K]
    x = np.concatenate([phi, hc, hq, np.ones((len(y), 1))],
                       axis=1).astype(np.float64)
    k, f = w.shape[0], w.shape[1]
    # Ridge in the normal equations; scale-aware lambda so wildly different
    # feature magnitudes (bilinear vs raw embedding) are penalized evenly.
    g = x.T @ x
    lam = ridge * np.trace(g) / g.shape[0]
    coef = np.linalg.solve(g + lam * np.eye(g.shape[0]), x.T @ y)
    pred = x @ coef
    ss_res = float(((y - pred) ** 2).sum())
    ss_tot = float(((y - y.mean()) ** 2).sum()) or 1.0
    return {"alpha": coef[:k].astype(np.float32),
            "beta": coef[k:k + f].astype(np.float32),
            "r2": round(1.0 - ss_res / ss_tot, 6),
            "n_samples": int(len(y))}


def prefilter_query_vectors(ntn_w, hq, calib: dict) -> np.ndarray:
    """Collapse calibrated coefficients into per-query scan vectors:
    `[Q, F]` such that `qv @ corpus.T` is the calibrated proxy score.
    Costs one K·F² contraction per query — paid once, amortized over the
    whole N-row scan."""
    w = np.asarray(ntn_w, np.float32)
    hq = np.asarray(hq, np.float32)
    t = np.einsum("qf,kfg->qkg", hq, w)                     # [Q, K, F]
    return (np.einsum("k,qkg->qg", calib["alpha"], t)
            + calib["beta"][None, :]).astype(np.float32)
