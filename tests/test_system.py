"""End-to-end behaviour tests for the paper's system (SimGNN on SPA-GCN).

These are the paper-level claims reduced to testable form:
  * training the SimGNN pipeline on GED-labelled pairs reduces the loss;
  * the fused kernel path and the jnp path agree end-to-end;
  * the query server (batching + size bucketing) returns order-correct
    scores and benefits from batching (Fig. 11 mechanism, smoke-level);
  * identical graphs score higher than heavily edited ones after training.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.simgnn_aids import CONFIG as SCFG
from repro.core.engine import ScoringEngine
from repro.core.simgnn import init_simgnn_params, pair_score
from repro.data.graphs import pair_stream, query_pairs
from repro.serve.batching import simgnn_query_server
from repro.train.optimizer import adamw_init
from repro.train.step import build_simgnn_train_step


def _train(n_steps=60, batch=32, seed=0, stream=None):
    params = init_simgnn_params(jax.random.PRNGKey(seed), SCFG)
    opt = adamw_init(params)
    # The engine routes the forward AND backward passes (DESIGN.md §11):
    # auto dispatch picks packed-sparse on this molecule-like stream.
    step = build_simgnn_train_step(ScoringEngine(params, SCFG),
                                   peak_lr=2e-3)
    stream = stream or pair_stream(seed, batch)
    losses = []
    for _ in range(n_steps):
        params, opt, m = step(params, opt, next(stream))
        losses.append(float(m["loss"]))
    return params, losses


def _binary_batch(seed, batch):
    """Pairs that are either identical (target 1.0) or unrelated (0.2) — a
    discrimination learnable in CI time (full GED regression needs thousands
    of steps; the paper trains offline and accelerates inference)."""
    from repro.data.graphs import random_graph
    rng = np.random.default_rng(seed)
    pairs, targets = [], []
    for _ in range(batch):
        g1 = random_graph(rng)
        if rng.random() < 0.5:
            g2, t = g1, 1.0
        else:
            g2, t = random_graph(rng), 0.2
        pairs.append((g1, g2))
        targets.append(t)
    return {"pairs": pairs, "target": np.asarray(targets, np.float32)}


def test_training_reduces_loss():
    _, losses = _train()
    first = np.mean(losses[:5])
    last = np.mean(losses[-5:])
    assert last < first * 0.7, (first, last)


def test_trained_model_ranks_similarity():
    """End-to-end trainability: gradients flow through all four stages and
    the model can fit a fixed set of binary-similarity pairs, ranking
    identical above unrelated pairs. (Full GED generalization needs
    thousands of steps — the paper trains offline and accelerates
    inference, so CI asserts the memorization/ranking sanity level.)"""
    from repro.core.batching import pad_graphs

    batch = _binary_batch(0, 48)
    params = init_simgnn_params(jax.random.PRNGKey(0), SCFG)
    opt = adamw_init(params)
    engine = ScoringEngine(params, SCFG)
    step = build_simgnn_train_step(engine, peak_lr=5e-3)
    losses = []
    for _ in range(250):
        params, opt, m = step(params, opt, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] * 0.25, (losses[0], losses[-1])
    assert engine.last_plan.path in ("packed_sparse", "packed_dense")
    b1 = pad_graphs([p[0] for p in batch["pairs"]], 29, 64)
    b2 = pad_graphs([p[1] for p in batch["pairs"]], 29, 64)
    pred = np.asarray(pair_score(params, b1.adj, b1.feats, b1.mask,
                                 b2.adj, b2.feats, b2.mask))
    tgt = np.asarray(batch["target"])
    mean_id = pred[tgt > 0.5].mean()
    mean_far = pred[tgt < 0.5].mean()
    assert mean_id > mean_far + 0.15, (mean_id, mean_far)


def test_query_server_bucketing_and_order():
    params = init_simgnn_params(jax.random.PRNGKey(0), SCFG)
    pairs = query_pairs(3, 12)
    score = simgnn_query_server(params, SCFG)
    out = score(pairs)
    assert out.shape == (12,)
    assert ((out > 0) & (out < 1)).all()
    # kernel path produces the same scores in the same order
    score_k = simgnn_query_server(params, SCFG, use_kernels=True)
    out_k = score_k(pairs)
    np.testing.assert_allclose(out, out_k, rtol=1e-4, atol=1e-5)


def test_microbatcher_amortization():
    from repro.serve.batching import MicroBatcher
    calls = []

    def run_batch(reqs):
        calls.append(len(reqs))
        return [r * 2 for r in reqs]

    # generous deadline: this test asserts size-triggered flushes only, and
    # must not race the wall clock on a loaded CI runner
    mb = MicroBatcher(run_batch, max_batch=4, max_wait_s=60.0)
    outs = []
    for i in range(10):
        r = mb.submit(i)
        if r:
            outs += r
    outs += mb.flush() or []        # None contract: nothing ran -> no batch
    assert outs == [0, 2, 4, 6, 8, 10, 12, 14, 16, 18]
    assert calls == [4, 4, 2]       # batched, not 10 single calls
    assert mb.flush() is None       # drained queue: nothing ran, not []


class _FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def test_microbatcher_deadline_flush_on_submit():
    """A submit arriving after the oldest request's deadline flushes even
    though max_batch is far away."""
    from repro.serve.batching import MicroBatcher
    clk = _FakeClock()
    mb = MicroBatcher(lambda reqs: [r * 2 for r in reqs], max_batch=100,
                      max_wait_s=0.01, clock=clk)
    assert mb.submit(1) is None
    clk.t += 0.005
    assert mb.submit(2) is None          # deadline measured from the OLDEST
    clk.t += 0.006                       # oldest has now waited 11ms > 10ms
    assert mb.submit(3) == [2, 4, 6]
    assert mb.pending == []


def test_microbatcher_deadline_poll():
    """The idle-loop pump: poll() flushes a stranded partial batch exactly
    when the deadline expires, and deadline_in() reports the time left."""
    from repro.serve.batching import MicroBatcher
    clk = _FakeClock()
    mb = MicroBatcher(lambda reqs: [r * 2 for r in reqs], max_batch=100,
                      max_wait_s=0.01, clock=clk)
    assert mb.poll() is None             # empty: nothing due
    assert mb.deadline_in() is None
    mb.submit(7)
    assert mb.poll() is None             # deadline not reached yet
    assert abs(mb.deadline_in() - 0.01) < 1e-12
    clk.t += 0.02
    assert mb.deadline_in() == 0.0
    assert mb.poll() == [14]
    assert mb.poll() is None             # drained


def test_compile_cache_dir(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins untouched; without it the cache goes
    to the fixed `<checkout>/.jax_cache` (never a temp, pid or time path)."""
    import os

    from repro.launch import compile_cache

    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/cache/elsewhere")
        assert compile_cache.enable() == "/cache/elsewhere"
        assert jax.config.jax_compilation_cache_dir == prev
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        want = os.path.join(repo, ".jax_cache")
        assert compile_cache.enable() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
