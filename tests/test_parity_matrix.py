"""Engine-wide parity matrix: every scoring path × dtype × odd/even batch,
asserted against ONE dense-reference score vector from a single source of
truth (this file's `REF` and the engine's `PARITY_ATOL_F32` table) —
replacing the per-file ad-hoc comparisons as the parity contract.

f32 bounds: the pure-jnp reference and the packed/embedding-cached kernels
hold 1e-6 (post-sigmoid scores); the two bucketed fused-GCN paths
(two_kernel, bucketed_mega) re-derive normalization inside the kernel in a
different contraction order and hold 2e-5 — the bound their own seed tests
established. bf16 inputs hold the 2e-2 band everywhere (fp32 accumulation).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.batching import bucket_pairs
from repro.core.engine import PARITY_ATOL_F32, PATHS, ScoringEngine
from repro.core.simgnn import SimGNNConfig, init_simgnn_params, pair_score
from repro.data.graphs import random_graph

CFG = SimGNNConfig()

#: single source of truth for the f32 parity bound of every path (kept
#: with the engine so the on-chip smoke test holds the same bounds).
ATOL_F32 = PARITY_ATOL_F32
ATOL_BF16 = 2e-2
BATCHES = (7, 12)        # odd (pads every block policy) and even


@functools.lru_cache(maxsize=None)
def _params(dtype: str):
    p = init_simgnn_params(jax.random.PRNGKey(0), CFG)
    if dtype == "bfloat16":
        p = jax.tree.map(
            lambda x: x.astype(jnp.bfloat16)
            if x.dtype == jnp.float32 else x, p)
    return p


@functools.lru_cache(maxsize=None)
def _pairs(batch: int):
    rng = np.random.default_rng(100 + batch)
    return tuple((random_graph(rng, int(rng.integers(5, 65))),
                  random_graph(rng, int(rng.integers(5, 65))))
                 for _ in range(batch))


@functools.lru_cache(maxsize=None)
def _reference(batch: int) -> tuple:
    """The dense f32 reference: bucketed pure-jnp `pair_score`."""
    out = np.zeros(batch, np.float32)
    for b, (lhs, rhs, idxs) in bucket_pairs(
            _pairs(batch), CFG.n_node_labels, allow_oversize=True).items():
        out[idxs] = np.asarray(pair_score(
            _params("float32"), lhs.adj, lhs.feats, lhs.mask,
            rhs.adj, rhs.feats, rhs.mask))
    return tuple(out)


@pytest.mark.parametrize("batch", BATCHES)
@pytest.mark.parametrize("dtype", ("float32", "bfloat16"))
@pytest.mark.parametrize("path", PATHS)
def test_parity_matrix(path, dtype, batch):
    assert path in ATOL_F32, f"new path {path} missing a parity bound"
    engine = ScoringEngine(_params(dtype), CFG, path=path)
    out = engine.score(list(_pairs(batch)))
    ref = np.asarray(_reference(batch), np.float32)
    atol = ATOL_F32[path] if dtype == "float32" else ATOL_BF16
    np.testing.assert_allclose(out, ref, rtol=0, atol=atol)
    assert engine.last_plan.path == path


def test_matrix_covers_every_engine_path():
    """The matrix and the engine registry cannot drift apart silently."""
    assert set(ATOL_F32) == set(PATHS)


@pytest.mark.parametrize("batch", BATCHES)
def test_cold_measured_planner_matches_threshold(batch):
    """With an empty profile the measured planner (the default) must be
    BIT-identical to the legacy threshold rules — same auto-dispatch path,
    same reason, byte-equal scores (DESIGN.md §15 cold-fallback contract).
    The planner may only change decisions once it has fitted a model."""
    pairs = list(_pairs(batch))
    measured = ScoringEngine(_params("float32"), CFG, planner="measured")
    threshold = ScoringEngine(_params("float32"), CFG, planner="threshold")
    out_m = np.asarray(measured.score(pairs))
    out_t = np.asarray(threshold.score(pairs))
    assert measured.last_plan.path == threshold.last_plan.path
    assert measured.last_plan.reason == threshold.last_plan.reason
    assert not measured.last_plan.cost_estimates      # cold: no predictions
    assert out_m.tobytes() == out_t.tobytes()
