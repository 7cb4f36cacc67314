"""Chaos suite (DESIGN.md §12): every fault-tolerance transition driven
deterministically through `repro.testing.faults`.

Covers: the degradation ladder on all six scoring paths (degraded output
stays within the healthy parity band), all three fault modes (raise / oom /
nan), circuit-breaker open -> half-open -> closed with an injected clock,
input quarantine (lenient NaN + structured records, strict raise), the
guarded training ladder (grad parity after degrade, NaN-step skip with
bit-identical optimizer state), MicroBatcher per-request deadlines and
retry-with-backoff, the search server surviving failed corpus embeds, the
mid-stream checkpoint resume contract, and the warn-once reset hook.

CI runs this file as its own step so a robustness regression is
distinguishable from a functional one at a glance.
"""

import shutil
import warnings

import jax
import numpy as np
import pytest

from repro.core.engine import (DEGRADE_LADDER, PATHS, ScoringEngine,
                               tree_all_finite)
from repro.core.health import CLOSED, HALF_OPEN, OPEN, CircuitBreaker
from repro.core.simgnn import SimGNNConfig, init_simgnn_params
from repro.core.validate import GraphValidationError, graph_problems
from repro.data.graphs import random_graph, search_pairs
from repro.testing import faults

CFG = SimGNNConfig()
PARAMS = init_simgnn_params(jax.random.PRNGKey(0), CFG)


class _FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _pairs(seed, n, max_n=24, avg_degree=2.0):
    rng = np.random.default_rng(seed)
    return [(random_graph(rng, int(rng.integers(5, max_n + 1)),
                          avg_degree=avg_degree),
             random_graph(rng, int(rng.integers(5, max_n + 1)),
                          avg_degree=avg_degree))
            for _ in range(n)]


def _engine(path="auto", **kw):
    kw.setdefault("clock", _FakeClock())
    return ScoringEngine(PARAMS, CFG, path=path, **kw)


def _ref_scores(pairs):
    return ScoringEngine(PARAMS, CFG, path="reference").score(pairs)


# ---------------------------------------------------- ladder: scoring paths

@pytest.mark.parametrize("path,atol", [
    ("packed_sparse", 1e-6), ("packed_dense", 2e-5),
    ("bucketed_mega", 2e-5), ("two_kernel", 2e-5)])
def test_degraded_call_matches_reference(path, atol):
    """Forcing the planned path's executor to crash completes the call on
    the next rung, within the reference parity band, and records the
    degradation on the republished plan."""
    pairs = _pairs(0, 12)
    eng = _engine(path)
    with faults.inject(path) as plan:
        out = eng.score(pairs)
    assert plan.triggered >= 1
    np.testing.assert_allclose(out, _ref_scores(pairs), rtol=0, atol=atol)
    assert eng.last_plan.degraded_from[0] == path
    assert eng.last_plan.attempts >= 2
    assert eng.counters[f"errors:{path}"] == 1


def test_embedding_cache_degrades_on_total_embed_failure():
    """When the embed executor AND its reference retry both die, the cached
    path's scores are NaN -> the ladder treats the rung as failed and the
    bucketed megakernel recomputes the batch from raw graphs."""
    pairs = _pairs(1, 8)
    eng = _engine("embedding_cache")
    with faults.inject("embed"), faults.inject("embed_fallback"):
        out = eng.score(pairs)
    np.testing.assert_allclose(out, _ref_scores(pairs), rtol=0, atol=2e-5)
    assert eng.last_plan.degraded_from[0] == "embedding_cache"
    assert eng.counters["embed_dropped_graphs"] > 0


def test_reference_is_terminal_fault_propagates():
    """The reference rung has no fallback: a fault there exhausts the
    ladder and the original error propagates (never a silent wrong answer)."""
    eng = _engine("reference")
    with faults.inject("reference"):
        with pytest.raises(faults.FaultError):
            eng.score(_pairs(2, 4))


def test_whole_ladder_walk_on_cascading_faults():
    """packed_sparse -> packed_dense -> bucketed_mega all dead: the call
    still completes on the dense jnp reference."""
    pairs = _pairs(3, 8)
    eng = _engine("packed_sparse")
    with faults.inject("packed_sparse"), faults.inject("packed_dense"), \
            faults.inject("bucketed_mega"):
        out = eng.score(pairs)
    np.testing.assert_allclose(out, _ref_scores(pairs), rtol=0, atol=1e-6)
    assert eng.last_plan.degraded_from == ("packed_sparse", "packed_dense",
                                           "bucketed_mega")
    assert eng.last_plan.attempts == 4


def test_degrade_false_pins_path():
    eng = _engine("packed_sparse", degrade=False)
    with faults.inject("packed_sparse"):
        with pytest.raises(faults.FaultError):
            eng.score(_pairs(4, 8))


@pytest.mark.parametrize("site", ["embed", "head", "prefilter"])
def test_degrade_false_disables_fallbacks(site):
    """degrade=False also turns off the embed, head and prefilter retries:
    the first failure raises and no fallback counter moves — a run that
    must prove its kernels ran (chip_smoke.py) cannot be served silently by
    the jnp reference instead."""
    from repro.serve.search import SimilaritySearchServer

    rng = np.random.default_rng(21)
    corpus = [random_graph(rng, int(n), avg_degree=2.0)
              for n in rng.integers(5, 17, 24)]
    query = random_graph(rng, 9, avg_degree=2.0)
    srv = SimilaritySearchServer(PARAMS, CFG, degrade=False)
    if site != "embed":
        srv.index(corpus)
    with faults.inject(site) as plan:
        with pytest.raises(faults.FaultError):
            if site == "embed":
                srv.index(corpus)
            elif site == "head":
                srv.topk(query, k=5)
            else:
                srv.topk(query, k=5, mode="two_stage", prefilter_m=8)
    assert plan.triggered == 1
    counters = srv.engine.counters
    assert counters[f"errors:{site}"] == 1
    assert counters["embed_fallbacks"] == 0
    assert counters["prefilter_degraded"] == 0


@pytest.mark.parametrize("mode", ["oom", "nan"])
def test_oom_and_nan_modes_degrade(mode):
    """RESOURCE_EXHAUSTED and silently-NaN-ing kernels both count as rung
    failures — the NaN case via the engine's finite-output check, since a
    corrupting kernel raises nothing on its own."""
    pairs = _pairs(5, 8)
    eng = _engine("packed_dense")
    with faults.inject("packed_dense", mode=mode) as plan:
        out = eng.score(pairs)
    assert plan.triggered == 1
    assert np.isfinite(out).all()
    np.testing.assert_allclose(out, _ref_scores(pairs), rtol=0, atol=2e-5)
    assert eng.last_plan.degraded_from == ("packed_dense",)


def test_ladder_covers_every_path():
    """Every dispatchable path reaches the terminal reference rung."""
    for path in PATHS:
        rungs = (path,) + DEGRADE_LADDER[path]
        assert rungs[-1] == "reference"


# --------------------------------------------------------- circuit breakers

def test_breaker_state_machine():
    clk = _FakeClock()
    br = CircuitBreaker(failure_threshold=2, cooldown_s=10.0, clock=clk)
    assert br.state == CLOSED and br.allow()
    br.record_failure()
    assert br.state == CLOSED          # 1 < threshold
    br.record_failure()
    assert br.state == OPEN
    assert not br.allow() and br.rejections == 1
    clk.t = 9.9
    assert not br.allow()
    clk.t = 10.0                       # cool-down elapsed: probe allowed
    assert br.allow() and br.state == HALF_OPEN
    br.record_failure()                # probe fails: reopen, backoff x2
    assert br.state == OPEN and br.current_cooldown() == 20.0
    clk.t = 10.0 + 20.0
    assert br.allow()
    br.record_success()                # probe succeeds: closed, backoff reset
    assert br.state == CLOSED and br.open_count == 0
    assert br.current_cooldown() == 10.0


def test_breaker_opens_and_cools_down_through_engine():
    """3 consecutive packed_sparse failures open its breaker; while open
    the rung is skipped without an attempt; after the cool-down one probe
    runs and (healthy again) closes it."""
    clk = _FakeClock()
    pairs = _pairs(6, 8)
    eng = _engine("packed_sparse", clock=clk, breaker_threshold=3,
                  breaker_cooldown_s=5.0)
    with faults.inject("packed_sparse"):
        for _ in range(3):
            eng.score(pairs)
    (key,) = [k for k in eng.breakers if k[0] == "packed_sparse"]
    assert eng.breakers[key].state == OPEN
    eng.score(pairs)                   # open: serve fallback, no attempt
    assert eng.counters["breaker_rejected:packed_sparse"] == 1
    assert eng.last_plan.degraded_from == ("packed_sparse",)
    assert eng.last_plan.attempts == 1
    clk.t += 5.0                       # cool-down elapsed -> half-open probe
    out = eng.score(pairs)
    assert eng.breakers[key].state == CLOSED
    assert eng.last_plan.degraded_from == ()
    np.testing.assert_allclose(out, _ref_scores(pairs), rtol=0, atol=1e-6)


def test_acceptance_batch512_sparse_fault():
    """The §12 acceptance case: packed_sparse forced to fail on a batch-512
    sparse stream -> the call completes via packed_dense within 1e-6 of the
    healthy scores, the breaker opens after the threshold, and health()
    reports it."""
    pairs = search_pairs(11, 512, avg_degree=2.1)
    clk = _FakeClock()
    eng = _engine("auto", clock=clk, breaker_threshold=2)
    healthy = eng.score(pairs)
    assert eng.last_plan.path == "packed_sparse"   # the paper's workload
    with faults.inject("packed_sparse") as plan:
        degraded = eng.score(pairs)
        assert plan.triggered == 1
        assert eng.last_plan.degraded_from == ("packed_sparse",)
        np.testing.assert_allclose(degraded, healthy, rtol=0, atol=1e-6)
        eng.score(pairs)               # second consecutive failure -> open
    health = eng.health()
    (name,) = [k for k in health["breakers"] if "packed_sparse" in k]
    assert health["breakers"][name]["state"] == OPEN
    assert health["counters"]["errors:packed_sparse"] == 2
    out = eng.score(pairs)             # open breaker: packed_dense serves
    assert eng.last_plan.attempts == 1
    np.testing.assert_allclose(out, healthy, rtol=0, atol=1e-6)


# --------------------------------------------------------------- quarantine

def _valid_graph(n=6, seed=0):
    return random_graph(np.random.default_rng(seed), n, avg_degree=2.0)


@pytest.mark.parametrize("mutate,needle", [
    (lambda g: g["adj"].__setitem__((0, 1), np.nan), "non-finite"),
    (lambda g: g["adj"].__setitem__((0, 1), 1.0), "symmetric"),
    (lambda g: g["adj"].__setitem__((0, 0), 1.0), "self loops"),
    (lambda g: g["adj"].__setitem__((0, 1), 2.0), "binary"),
    (lambda g: g.__setitem__("labels", g["labels"][:-1]), "ragged"),
    (lambda g: g["labels"].__setitem__(0, CFG.n_node_labels), "out of range"),
    (lambda g: g.__setitem__("adj", g["adj"][:1]), "square"),
    (lambda g: g.__setitem__("adj", np.zeros((0, 0), np.float32)), "empty"),
])
def test_graph_problems_catches(mutate, needle):
    g = _valid_graph()
    g = {"adj": g["adj"].copy(), "labels": g["labels"].copy()}
    if needle == "symmetric":
        g["adj"][0, 1] = 1.0
        g["adj"][1, 0] = 0.0
        problems = graph_problems(g, n_labels=CFG.n_node_labels)
    else:
        mutate(g)
        problems = graph_problems(g, n_labels=CFG.n_node_labels)
    assert any(needle in p for p in problems), problems


def test_lenient_quarantine_scores_nan_keeps_rest():
    """One malformed request NaNs its own score only — the valid pairs of
    the same batch still score within the parity band (no poisoned batch)."""
    pairs = _pairs(7, 6)
    bad = {"adj": np.full((4, 4), np.nan, np.float32),
           "labels": np.zeros(4, np.int32)}
    mixed = [(bad, pairs[0][1])] + pairs[1:]
    eng = _engine("auto")
    out = eng.score(mixed)
    assert np.isnan(out[0])
    np.testing.assert_allclose(out[1:], _ref_scores(pairs[1:]),
                               rtol=0, atol=1e-6)
    (rec,) = eng.last_plan.quarantined
    assert rec.pair == 0 and rec.side == 0 and rec.reasons
    assert eng.counters["quarantined_graphs"] == 1


def test_strict_validation_raises_with_records():
    bad = {"adj": np.asarray([[0, 2], [2, 0]], np.float32),
           "labels": np.zeros(2, np.int32)}
    eng = _engine("auto", validation="strict")
    with pytest.raises(GraphValidationError) as ei:
        eng.score([(bad, _valid_graph())])
    assert ei.value.records[0].pair == 0


def test_validation_off_skips_quarantine():
    pairs = _pairs(8, 4)
    eng = _engine("packed_sparse", validation="off")
    out = eng.score(pairs)
    assert eng.last_plan.quarantined == ()
    np.testing.assert_allclose(out, _ref_scores(pairs), rtol=0, atol=1e-6)


def test_unknown_validation_mode_rejected():
    with pytest.raises(ValueError, match="validation"):
        _engine(validation="paranoid")


# ---------------------------------------------------------- guarded training

def test_train_ladder_degrades_with_grad_parity():
    pairs = _pairs(9, 12)
    tgt = np.linspace(0.1, 0.9, 12).astype(np.float32)
    eng = _engine("packed_sparse")
    l0, g0 = eng.loss_and_grad(pairs, tgt)
    with faults.inject("train:packed_sparse", mode="nan") as plan:
        l1, g1 = eng.loss_and_grad(pairs, tgt)
    assert plan.triggered == 1
    assert eng.last_plan.degraded_from == ("packed_sparse",)
    assert abs(float(l0) - float(l1)) < 1e-6
    for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-5)


def test_nonfinite_targets_dropped_not_poisoning():
    pairs = _pairs(10, 8)
    tgt = np.linspace(0.1, 0.9, 8).astype(np.float32)
    poisoned = tgt.copy()
    poisoned[3] = np.nan
    eng = _engine("reference")
    keep = [i for i in range(8) if i != 3]
    l_clean, g_clean = eng.loss_and_grad([pairs[i] for i in keep], tgt[keep])
    l_pois, g_pois = eng.loss_and_grad(pairs, poisoned)
    assert eng.counters["nonfinite_targets"] == 1
    assert abs(float(l_clean) - float(l_pois)) < 1e-6
    for a, b in zip(jax.tree.leaves(g_clean), jax.tree.leaves(g_pois)):
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-6)


def test_step_skip_preserves_optimizer_state_bitwise():
    """A step whose loss/grads are non-finite after every engine-level
    recovery is SKIPPED: params and optimizer state come back bit-identical
    and the skip is counted; the next clean step proceeds normally."""
    from repro.train.optimizer import adamw_init
    from repro.train.step import build_simgnn_train_step

    pairs = _pairs(12, 6)
    batch = {"pairs": pairs,
             "target": np.linspace(0.2, 0.8, 6).astype(np.float32)}
    eng = _engine("reference")      # terminal rung: NaN serves, guard skips
    step = build_simgnn_train_step(eng)
    params, opt_state = PARAMS, adamw_init(PARAMS)
    with faults.inject("train:reference", mode="nan"):
        p1, o1, metrics = step(params, opt_state, batch)
    assert float(metrics["skipped"]) == 1.0
    assert eng.counters["train_skipped_steps"] == 1
    for a, b in zip(jax.tree.leaves((params, opt_state)),
                    jax.tree.leaves((p1, o1))):
        if hasattr(a, "dtype"):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        else:
            assert a == b
    p2, o2, metrics2 = step(p1, o1, batch)      # clean step advances
    assert "skipped" not in metrics2
    assert int(metrics2["step"]) == int(np.asarray(o1.step)) + 1
    assert not all(np.array_equal(np.asarray(a), np.asarray(b))
                   for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)))


def test_tree_all_finite():
    assert tree_all_finite({"a": np.ones(3)}, np.float32(1.0))
    assert not tree_all_finite({"a": np.asarray([1.0, np.nan])})
    assert tree_all_finite(np.asarray([1, 2], np.int32))  # ints never NaN


def test_midstream_kill_resumes_bit_identical(tmp_path):
    """The §12 acceptance case for training: a run killed mid-stream and
    resumed from its checkpoint ends with BIT-IDENTICAL params + optimizer
    state vs an uninterrupted run (atomic checkpoints + deterministic
    per-step batch replay)."""
    from repro.train import loop
    from repro.train.optimizer import adamw_init
    from repro.train.step import build_simgnn_train_step

    rngs = [np.random.default_rng(100 + s) for s in range(6)]
    batches = [{"pairs": [(random_graph(r, 8, avg_degree=2.0),
                           random_graph(r, 8, avg_degree=2.0))
                          for _ in range(4)],
                "target": r.uniform(0.2, 0.9, 4).astype(np.float32)}
               for r in rngs]

    def run(ckpt_dir, n_steps):
        eng = _engine("reference")
        step = build_simgnn_train_step(eng)
        return loop.run(step, PARAMS, adamw_init(PARAMS),
                        lambda s: batches[s], n_steps=n_steps,
                        ckpt_dir=str(ckpt_dir), ckpt_every=2, log_every=100)

    p_full, o_full, _ = run(tmp_path / "full", 6)
    # "Killed" after 3 steps: drop the exit-time save so the only surviving
    # checkpoint is the mid-stream one at step 2 (ckpt_every=2), exactly
    # what a hard kill leaves behind.
    run(tmp_path / "killed", 3)
    shutil.rmtree(tmp_path / "killed" / "step_000000003")
    p_res, o_res, _ = run(tmp_path / "killed", 6)
    for a, b in zip(jax.tree.leaves((p_full, o_full)),
                    jax.tree.leaves((p_res, o_res))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# --------------------------------------------------------- serving resilience

def test_microbatcher_request_timeout():
    from repro.serve.batching import MicroBatcher, TimeoutResult

    clk = _FakeClock()
    mb = MicroBatcher(lambda reqs: [r * 2 for r in reqs], max_batch=10,
                      max_wait_s=1.0, clock=clk)
    assert mb.submit(1, timeout_s=0.05) is None
    assert mb.submit(2) is None
    assert abs(mb.deadline_in() - 0.05) < 1e-12   # per-request < group wait
    clk.t = 0.06
    out = mb.poll()                    # expired deadline triggers the flush
    assert isinstance(out[0], TimeoutResult)
    assert out[0].request == 1 and abs(out[0].waited_s - 0.06) < 1e-12
    assert out[1] == 4                 # live request still served, in place
    assert mb.stats.expired_flushes == 1
    assert mb.stats.expired_requests == 1
    assert mb.pending == []


def test_microbatcher_deadline_in_clamps_to_zero():
    from repro.serve.batching import MicroBatcher

    clk = _FakeClock()
    mb = MicroBatcher(lambda reqs: reqs, max_batch=10, max_wait_s=0.01,
                      clock=clk)
    mb.pending.append("r")             # stage without flushing
    mb._deadlines.append((None, clk.t))
    mb.oldest_ts = clk.t
    clk.t = 5.0                        # long overdue
    assert mb.deadline_in() == 0.0     # clamped, never negative


def test_microbatcher_retry_then_success():
    from repro.serve.batching import MicroBatcher

    calls, naps = [], []

    def flaky(reqs):
        calls.append(list(reqs))
        if len(calls) < 3:
            raise RuntimeError("transient")
        return [r + 1 for r in reqs]

    mb = MicroBatcher(flaky, max_batch=2, flush_retries=2,
                      retry_backoff_s=0.01, sleep=naps.append,
                      clock=_FakeClock())
    out = mb.submit(1)
    assert out is None
    out = mb.submit(2)                 # size flush -> fail, fail, succeed
    assert out == [2, 3]
    assert len(calls) == 3
    assert naps == [0.01, 0.02]        # exponential backoff
    assert mb.stats.retries == 2 and mb.stats.failed_flushes == 0


def test_microbatcher_retry_exhaustion_drains_queue():
    from repro.serve.batching import MicroBatcher

    def dead(reqs):
        raise RuntimeError("kernel down")

    mb = MicroBatcher(dead, max_batch=2, flush_retries=1,
                      sleep=lambda s: None, clock=_FakeClock())
    mb.submit(1)
    with pytest.raises(RuntimeError, match="kernel down"):
        mb.submit(2)
    assert mb.pending == []            # drained: later traffic unaffected
    assert mb.stats.failed_flushes == 1
    assert mb.stats.dropped_requests == 2
    assert mb.submit(3) is None        # queue works again


def test_search_server_survives_failed_corpus_shard():
    """A corpus bucket whose embed AND reference retry both fail is dropped
    (NaN rows, counted), the rest of the index serves, and NaN rows never
    reach the top-k."""
    from repro.serve.search import SimilaritySearchServer

    rng = np.random.default_rng(13)
    # Two size buckets: n<=8 and n in (8, 16].
    corpus = [random_graph(rng, n, avg_degree=2.0)
              for n in [6, 7, 8, 12, 13, 14, 15, 16]]
    srv = SimilaritySearchServer(PARAMS, CFG)
    with faults.inject("embed"), faults.inject("embed_fallback", times=1):
        emb = srv.index(corpus)
    dropped = int((~np.isfinite(emb).all(axis=-1)).sum())
    assert 0 < dropped < len(corpus)
    assert srv.stats.failed_embeddings == dropped
    assert srv.health()["failed_embeddings"] == dropped
    query = random_graph(rng, 9, avg_degree=2.0)
    k = len(corpus) - dropped
    idx, scores = srv.topk(query, k=k)
    assert np.isfinite(scores).all()   # NaN rows ranked out of the top-k
    assert len(idx) == k


def test_query_server_validation_passthrough():
    from repro.serve.batching import simgnn_query_server

    bad = {"adj": np.full((3, 3), np.inf, np.float32),
           "labels": np.zeros(3, np.int32)}
    score_fn = simgnn_query_server(PARAMS, CFG, use_kernels=True)
    pairs = _pairs(14, 3)
    out = score_fn([(bad, pairs[0][1])] + pairs[1:])
    assert np.isnan(out[0]) and np.isfinite(out[1:]).all()
    assert score_fn.last_plan.quarantined[0].pair == 0
    strict = simgnn_query_server(PARAMS, CFG, validation="strict")
    with pytest.raises(GraphValidationError):
        strict([(bad, pairs[0][1])])


# ------------------------------------------------------------- misc hooks

def test_reset_grow_warnings_hook():
    from repro.core import batching as cb
    from repro.data.graphs import random_graph as rg

    rng = np.random.default_rng(15)
    batch = cb.pad_graphs([rg(rng, 12, avg_degree=4.0)],
                          CFG.n_node_labels, 16)
    cb.reset_grow_warnings()
    with warnings.catch_warnings(record=True) as first:
        warnings.simplefilter("always")
        cb.to_edge_batch(batch, max_edges=4)
    assert any("growing the edge budget" in str(w.message) for w in first)
    with warnings.catch_warnings(record=True) as again:
        warnings.simplefilter("always")
        cb.to_edge_batch(batch, max_edges=4)
    assert not again                   # warn-once per process
    cb.reset_grow_warnings()           # the supported reset hook
    with warnings.catch_warnings(record=True) as after:
        warnings.simplefilter("always")
        cb.to_edge_batch(batch, max_edges=4)
    assert any("growing the edge budget" in str(w.message) for w in after)


def test_fault_hook_disarms_on_exit():
    from repro.core import engine as engine_mod

    with faults.inject("packed_dense"):
        assert engine_mod._FAULT_HOOK is not None
    assert engine_mod._FAULT_HOOK is None
    # and a healthy engine is unaffected afterwards
    eng = _engine("packed_dense")
    out = eng.score(_pairs(16, 4))
    assert eng.last_plan.degraded_from == ()
    assert np.isfinite(out).all()


# ------------------------------------------------ profile seam (§15 tracing)

def test_profile_record_fault_never_fails_scoring():
    """A crashing trace recorder must never fail the scoring call it is
    observing: the scores stay finite and healthy, the error is only
    counted (`profile_record_errors`), and the recorder keeps working once
    the fault clears (DESIGN.md §15 observability-is-free contract)."""
    from repro.core.profile import TraceRecorder

    rec = TraceRecorder(clock=_FakeClock())
    eng = _engine("packed_sparse", recorder=rec)
    pairs = _pairs(40, 6)
    with faults.inject("profile") as plan:
        out = eng.score(pairs)
    assert plan.triggered == 1
    assert np.isfinite(out).all()
    assert eng.last_plan.degraded_from == ()          # scoring untouched
    assert eng.counters["profile_record_errors"] == 1
    assert rec.total_records == 0                     # the record was lost
    eng.score(pairs)                                  # fault cleared
    assert rec.total_records == 1
    assert eng.counters["profile_record_errors"] == 1


def test_profile_record_fault_never_fails_training():
    """Same contract on the training side: loss_and_grad under an injected
    recorder fault still returns finite grads and counts the error."""
    from repro.core.profile import TraceRecorder

    eng = _engine("packed_dense", recorder=TraceRecorder(clock=_FakeClock()))
    batch = _pairs(41, 4)
    targets = np.linspace(0.1, 0.9, len(batch)).astype(np.float32)
    with faults.inject("profile", mode="raise") as plan:
        loss, grads = eng.loss_and_grad(batch, targets)
    assert plan.triggered >= 1
    assert tree_all_finite(loss, grads)
    assert eng.counters["profile_record_errors"] >= 1
