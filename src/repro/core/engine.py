"""ScoringEngine — the unified path-selection layer for SimGNN pair scoring
(DESIGN.md §9, §10).

Six scoring paths coexist in this codebase, each fastest somewhere:

  reference      pure-jnp `core.simgnn.pair_score`, bucketed; the parity
                 anchor and the no-kernels fallback.
  two_kernel     fused GCN+Att then fused NTN+FCN head (embeddings
                 round-trip HBM); building blocks for embedding-only
                 callers, benchmark comparator.
  bucketed_mega  ONE pallas_call per size bucket (DESIGN.md §7); handles
                 any feature kind, serves as the oversize fallback.
  packed_dense   FFD node-packed segment-ID tiles, dense block-diagonal
                 adjacency matmul (DESIGN.md §8); wins on dense-adjacency
                 streams.
  packed_sparse  packed tiles aggregated from the A' non-zero edge list
                 (DESIGN.md §9); wins on sparse (AIDS-like) streams —
                 the paper's own workload.
  embedding_cache  per-graph GCN+Att embeddings served from an LRU keyed
                 by a canonical graph hash, only the NTN+FCN head runs per
                 query (DESIGN.md §10); wins on 1-vs-N search where the
                 corpus side recurs across queries.

Before this layer existed, the routing logic lived as ad-hoc branching
inside `serve.batching.simgnn_query_server`. The engine makes the decision
explicit and inspectable: `plan()` measures the workload (batch size, node
counts, *measured* edge density, label kind) and returns a `ScorePlan`
naming the chosen path, the pairs it covers, the oversize fallback split
and the reason — `score()` then executes it. The serving wrapper is a thin
shim that keeps its public `score_fn` contract.

Since DESIGN.md §11 the engine dispatches BOTH directions of the model:
`loss_and_grad()` plans with the same machinery but restricts dispatch to
the VJP-capable paths (`TRAIN_PATHS`: reference | packed_dense |
packed_sparse — the packed executors are the custom-VJP jnp twins in
`kernels/grad.py`, since `pallas_call` has no autodiff rule), packs once
per batch, and reuses the packed layout across gradient-accumulation
microbatches. `train.step.build_simgnn_train_step` is the thin training
shim, exactly as the query server is the thin serving shim.

All compiled-callable caches (one per size bucket, `bucket_fns`) and packing
statistics (`last_pack_stats`) live on the engine instance, so a serving
process holds exactly one engine per model and every executable is reused
across calls (the paper's 'customize per workload' principle, Table 2).

Since DESIGN.md §12 the same ladder doubles as the fault-tolerance chain:
inputs are quarantined before planning (`core/validate.py` — invalid pairs
score NaN instead of poisoning the batch), a failing or NaN-producing
executor steps the call down the degradation ladder
(packed_sparse -> packed_dense -> bucketed_mega -> reference), and a
per-(path, shape-class) circuit breaker (`core/health.py`) stops retrying a
persistently broken path during a cool-down. `ScorePlan` records
`quarantined`/`degraded_from`/`attempts`; `health()` reports breaker states
and error counters. `repro.testing.faults` drives all of it
deterministically through the `_FAULT_HOOK` seam below.

Since DESIGN.md §15 the engine also measures itself: every executed work
item appends a `TraceRecord` (path, shape stats, pack occupancy, wall
seconds) to `self.recorder` (`core/profile.py` — ring + optional JSONL
profile), and `plan()` argmins a per-path latency model ridge-fitted from
that profile whenever every candidate path has `PLANNER_MIN_SUPPORT` clean
records — the hand-tuned `SPARSE_MAX_DEGREE` / `CACHE_MIN_HIT_FRAC`
thresholds below remain only as the bit-identical cold-profile fallback.
`benchmarks/replay.py` re-runs a captured mixed trace and gates
predicted-vs-measured error, so every future kernel's crossover point is
regression-tested data rather than folklore.

`score()` names its host stages as profiler spans (`core.profile.span`,
`engine.<stage>` for each of `profile.STAGES`, one per stage per call on
every executor), and counts the bytes it copies host -> device and back on
`counters["h2d_bytes"]`/`counters["d2h_bytes"]` at the same sites.
"""

from __future__ import annotations

import functools
import time
from collections import Counter, OrderedDict
from dataclasses import dataclass, field, replace
from typing import Callable, Sequence

import jax
import numpy as np

from repro.core.cache import EmbeddingCache, graph_fingerprint, graph_key
from repro.core.health import CircuitBreaker
from repro.core.profile import (TraceRecorder, cost_key, fit_cost_model,
                                span, trace_features)
from repro.core.validate import GraphValidationError, validate_pairs

PATHS = ("reference", "two_kernel", "bucketed_mega", "packed_dense",
         "packed_sparse", "embedding_cache")
PACKED_PATHS = ("packed_dense", "packed_sparse")
#: paths with a VJP-capable executor (DESIGN.md §11): the dense reference
#: is plain jnp, the packed paths have custom-VJP twins in kernels/grad.py.
#: The bucketed paths run inside pallas_call (no autodiff rule) and the
#: embedding cache serves stale non-differentiable activations.
TRAIN_PATHS = ("reference", "packed_dense", "packed_sparse")

#: f32 parity bound of every path: max abs error of post-sigmoid scores
#: against the dense pure-jnp reference (`tests/test_parity_matrix.py`,
#: `chip_smoke.py`). The reference and the packed/embedding-cached paths
#: hold 1e-6; the two bucketed fused-GCN paths re-derive normalization
#: inside the kernel in a different contraction order and hold 2e-5.
PARITY_ATOL_F32 = {
    "reference": 1e-6,
    "two_kernel": 2e-5,
    "bucketed_mega": 2e-5,
    "packed_dense": 1e-6,
    "packed_sparse": 1e-6,
    "embedding_cache": 1e-6,
}

#: Graceful-degradation ladder (DESIGN.md §12): when a path's executor
#: fails (raises, exhausts resources, or emits non-finite scores on
#: validated inputs) the call steps down to the next rung — specialized
#: paths degrade toward the dense jnp reference, which is the terminal
#: rung and never degrades further. This is SPA-GCN's flexibility argument
#: turned into a fault-tolerance chain: every rung computes the same
#: scores, only the execution strategy changes.
DEGRADE_LADDER = {
    "packed_sparse": ("packed_dense", "bucketed_mega", "reference"),
    "packed_dense": ("bucketed_mega", "reference"),
    "bucketed_mega": ("reference",),
    "two_kernel": ("bucketed_mega", "reference"),
    "embedding_cache": ("bucketed_mega", "reference"),
    "reference": (),
}
#: Training ladder: restricted to the VJP-capable executors (§11).
TRAIN_DEGRADE_LADDER = {
    "packed_sparse": ("packed_dense", "reference"),
    "packed_dense": ("reference",),
    "reference": (),
}


def _rung_name(path: str, devices: int) -> str:
    """Display/counter name of a ladder rung: the bare path single-device,
    `path@Nd` when the rung runs tile-sharded over N mesh devices
    (DESIGN.md §16) — matches `profile.cost_key` so counters, breaker
    snapshots and cost-model keys all read the same."""
    return path if devices <= 1 else f"{path}@{int(devices)}d"

#: Fault-injection seam (DESIGN.md §12): `repro.testing.faults.inject()`
#: arms this with a hook; production leaves it None (one attribute read per
#: executor call). The engine routes EVERY kernel/executor invocation
#: through `_call` so injected faults hit warm engines too — their jitted
#: callables are cached on the instance, out of monkeypatching's reach.
_FAULT_HOOK: Callable | None = None


def _call(site: str, thunk: Callable):
    hook = _FAULT_HOOK
    return hook(site, thunk) if hook is not None else thunk()


class NonFiniteOutput(RuntimeError):
    """An executor produced NaN/Inf scores (or grads) for inputs that
    passed validation — treated exactly like a crash by the degradation
    ladder: silently-corrupting kernels must not outrank loud ones."""


def tree_all_finite(*trees) -> bool:
    """True iff every floating leaf of the given pytrees is finite —
    the one-line guard `train.step` uses to skip poisoned update steps
    (DESIGN.md §12) without naming any dispatch path."""
    for leaf in jax.tree.leaves(trees):
        arr = np.asarray(leaf)
        if (np.issubdtype(arr.dtype, np.floating)
                and not np.isfinite(arr).all()):
            return False
    return True


def _empty_idx() -> np.ndarray:
    return np.empty(0, np.int64)


@dataclass(frozen=True)
class WorkloadStats:
    """Measured properties of one score() call's pairs — the dispatch
    inputs. Densities are measured from the adjacency non-zeros, never
    assumed from the generator."""
    n_pairs: int
    max_nodes: int = 0
    mean_nodes: float = 0.0
    avg_degree: float = 0.0      # 2E/V over all graphs (self loops excluded)
    density: float = 0.0         # nnz / sum(n_i^2)
    has_labels: bool = True      # every graph carries int node labels


@dataclass(frozen=True)
class ScorePlan:
    """An explicit, inspectable dispatch decision for one batch of pairs.

    `path` scores the pairs at `fit_idx`; pairs at `over_idx` (too large for
    the packed node budget, or the whole batch on bucketed paths) run on
    `fallback` through power-of-two size buckets. `reason` is the
    human-readable dispatch rationale (surfaced by examples/simgnn_search).

    On the embedding-cached path the plan additionally carries the hit/miss
    split (DESIGN.md §10): `graph_keys` holds the canonical key of every
    graph the plan covers (all lhs graphs, then all rhs graphs, quarantined
    pairs excluded), `cached_idx` the positions whose embedding is already
    resident, and `to_embed_idx` the positions that will actually be
    embedded — the *first* occurrence of each uncached key, so
    `len(to_embed_idx)` is the number of GCN+Att runs a `score()` will pay
    (later duplicates ride along for free).

    Fault-tolerance fields (DESIGN.md §12): `quarantined` holds the
    structured `InvalidGraph` records of inputs rejected by validation
    (lenient mode — those pairs score NaN and appear in neither `fit_idx`
    nor `over_idx`). After execution, the engine republishes the plan on
    `last_plan` with `degraded_from` (the rungs that failed or were
    breaker-rejected, in order) and `attempts` (executor invocations
    actually tried — 1 per work item on a healthy call).
    """
    path: str
    fallback: str
    fit_idx: np.ndarray
    over_idx: np.ndarray
    stats: WorkloadStats
    reason: str
    cached_idx: np.ndarray = field(default_factory=_empty_idx)
    to_embed_idx: np.ndarray = field(default_factory=_empty_idx)
    graph_keys: tuple = ()
    quarantined: tuple = ()
    degraded_from: tuple = ()
    attempts: int = 1
    #: two-stage retrieval (DESIGN.md §14): the top-M shortlist size the
    #: prefilter scan used before the exact rerank (0 = no prefilter ran).
    prefilter_m: int = 0
    #: device-sharded execution (DESIGN.md §16): mesh devices the planner
    #: assigned this call's packed tiles to (1 = unsharded; always 1 off-mesh
    #: and on unpacked paths). The §12 ladder's first degradation for a
    #: devices>1 plan is the same path collapsed to a single device.
    devices: int = 1
    #: measured-planner estimates (DESIGN.md §15): predicted wall seconds
    #: per candidate path when the fitted cost model drove this decision;
    #: empty when the threshold rules did (cold profile / forced path).
    cost_estimates: dict = field(default_factory=dict)


class ScoringEngine:
    """Single dispatch point from graph-pair batches to scores.

    path="auto" selects per call from measured workload statistics; any
    explicit path name in `PATHS` forces that path (oversized pairs still
    fall back to bucketed scoring — nothing kills a call). Thresholds are
    class attributes so deployments can tune them.
    """

    #: densest stream the edge-centric kernel should take: beyond ~4
    #: neighbors/node the edge list stops being much smaller than the dense
    #: block and the MXU matmul wins (LW-GCN/Accel-GCN report the same
    #: degree-bound regime; the measured planner, DESIGN.md §15, replaces
    #: this threshold once it has a profile).
    SPARSE_MAX_DEGREE = 4.0
    #: below this many pairs, FFD packing cannot fill even one tile enough
    #: to beat a single bucketed launch.
    MIN_PACK_PAIRS = 4
    #: auto flips to the embedding-cached path when at least this fraction
    #: of the call's unique graphs already have resident embeddings — below
    #: it the misses' GCN+Att recompute (now unbatched with the rest of the
    #: stream) erodes the head-only win (DESIGN.md §10 break-even).
    CACHE_MIN_HIT_FRAC = 0.5
    #: tiles per backward chunk on the packed training paths (DESIGN.md
    #: §11): the fwd+bwd of a chunk must fit cache — one monolithic
    #: backward over every tile thrashes (measured ~1.5x slower on the
    #: batch-256 stream), so the executor ALWAYS scans tile chunks,
    #: accumulating loss and grads; gradient accumulation then falls out
    #: for free (`accum_steps` just guarantees at least that many chunks).
    TRAIN_TILE_CHUNK = 16
    #: measured planner (DESIGN.md §15): a candidate path needs at least
    #: this many clean trace records before the cost model may steer it —
    #: below it, dispatch stays on the threshold rules above (the "cold"
    #: fallback, pinned bit-identical by test).
    PLANNER_MIN_SUPPORT = 8
    #: refit the cost model after this many new records (fitting is a
    #: handful of 5x5 solves — cheap, but not per-call cheap).
    PLANNER_REFIT_EVERY = 32

    def __init__(self, params, cfg, *, path: str = "auto",
                 node_budget: int | None = None,
                 edge_budget: int | None = None,
                 cache_size: int = 4096,
                 embed_with_kernels: bool = False,
                 validation: str = "lenient",
                 degrade: bool = True,
                 breaker_threshold: int = 3,
                 breaker_cooldown_s: float = 30.0,
                 clock: Callable[[], float] = time.monotonic,
                 recorder: TraceRecorder | None = None,
                 planner: str = "measured",
                 runtime=None,
                 grad_fn=None):
        if path != "auto" and path not in PATHS:
            raise ValueError(f"unknown path {path!r}; expected 'auto' or one "
                             f"of {PATHS}")
        if validation not in ("strict", "lenient", "off"):
            raise ValueError(f"unknown validation mode {validation!r}; "
                             "expected 'strict', 'lenient' or 'off'")
        if planner not in ("measured", "threshold"):
            raise ValueError(f"unknown planner mode {planner!r}; expected "
                             "'measured' or 'threshold'")
        from repro.kernels.ops import packed_node_budget

        self.params = params
        self.cfg = cfg
        self.path = path
        self.node_budget = (packed_node_budget(cfg.max_nodes)
                            if node_budget is None else node_budget)
        self.edge_budget = edge_budget
        # Bucketed-path flavor this engine instance uses (forced reference /
        # two_kernel engines bucket through themselves; every other path
        # falls back to the §7 megakernel).
        self._bucket_flavor = (path if path in ("reference", "two_kernel")
                               else "bucketed_mega")
        #: per-graph embedding LRU (DESIGN.md §10); capacity 0 disables it.
        self.cache = EmbeddingCache(cache_size)
        # Embedding executor flavor: the default pure-jnp jit keeps cached
        # scores within the 1e-6 parity band of the dense reference (the
        # embed stage is the amortized cold stage, so its speed is not the
        # point); `embed_with_kernels=True` opts indexing throughput into
        # the fused GCN+Att kernel (two-kernel stage 1, ~2e-5 parity).
        self._embed_kernels = embed_with_kernels
        self.bucket_fns: dict[int, Callable] = {}
        self.last_pack_stats: dict | None = None
        self.last_plan: ScorePlan | None = None
        self._ref_fn: Callable | None = None
        self._embed_ref_fn: Callable | None = None
        self._head_fn: Callable | None = None
        #: jitted value_and_grad executors, one per
        #: (train path, chunk, devices, grad-fn kind).
        self._train_fns: dict[tuple, Callable] = {}
        # ---- device-sharded execution (DESIGN.md §16) ----
        #: mesh + axis-role bundle (`distributed.sharding.Runtime`); None
        #: (or a mesh-less Runtime) keeps every path single-device — the
        #: engine then behaves bit-identically to its pre-mesh self.
        self.runtime = runtime
        self.n_devices = (int(runtime.n_devices)
                          if runtime is not None else 1)
        #: per-(path, device-count, tile_block) shard_map executables — the
        #: sharded twin of `bucket_fns` (jit caches per padded shape inside
        #: each entry).
        self._sharded_fns: dict[tuple[str, int, int], Callable] = {}
        #: sub-meshes over the first k mesh devices, built lazily (the §12
        #: collapse rung and the planner's pair-count clamp both shrink k).
        self._tile_meshes: dict[int, object] = {}
        #: swappable gradient-function object (train/sgf.py, paxml-style):
        #: wraps loss -> value_and_grad so clipped / DP variants slot into
        #: `loss_and_grad` without touching the executor cache logic.
        if grad_fn is None:
            from repro.train.sgf import StandardGradient
            grad_fn = StandardGradient()
        self.grad_fn = grad_fn
        #: realized COO overflow budget of past sparse packs — reused as the
        #: floor of later packs so one heavy batch doesn't make every
        #: subsequent batch re-derive (and re-compile) a different [T, E_ov]
        #: shape (the `to_edge_batch` realized-budget reuse, PR 5 satellite).
        self._overflow_floor: int = 8
        # ---- fault tolerance (DESIGN.md §12) ----
        #: "strict" raises GraphValidationError on any invalid input,
        #: "lenient" (default) quarantines per pair (NaN score), "off"
        #: skips validation (trusted in-process generators, benchmarks).
        self.validation = validation
        #: False pins every call to its planned path and turns off the
        #: embed, head and prefilter retries too — failures propagate
        #: (debugging / parity harnesses / on-chip bring-up, where a silent
        #: fallback would hide that no kernel ran); True (default) walks
        #: the ladder.
        self.degrade = degrade
        self.breaker_threshold = breaker_threshold
        self.breaker_cooldown_s = breaker_cooldown_s
        self._clock = clock
        #: per-(path, shape-class) circuit breakers, created lazily.
        self.breakers: dict[tuple, CircuitBreaker] = {}
        #: error/degradation/quarantine counters reported by `health()`,
        #: with the cumulative bytes scoring copied to and from the device.
        self.counters: Counter = Counter(h2d_bytes=0, d2h_bytes=0)
        #: bucketed callables for non-default flavors — only populated when
        #: degradation crosses flavors (e.g. bucketed_mega -> reference on a
        #: kernel-flavored engine). `bucket_fns` keeps its public int-keyed
        #: contract for the engine's own flavor.
        self._alt_bucket_fns: dict[tuple, Callable] = {}
        self._embed_fallback_fn: Callable | None = None
        self._head_fallback_fn: Callable | None = None
        # ---- measured planner (DESIGN.md §15) ----
        #: per-call trace ring (+ optional JSONL persistence) every executed
        #: work item appends to; pass a `TraceRecorder(path=...)` to persist
        #: a profile, or a shared recorder so replicas pool their samples.
        self.recorder = TraceRecorder(clock=clock) if recorder is None \
            else recorder
        #: "measured" (default): argmin the fitted per-path cost model when
        #: every candidate has `PLANNER_MIN_SUPPORT` clean records, else the
        #: threshold rules; "threshold": always the threshold rules (parity
        #: harnesses, the replay benchmark's measurement engines).
        self.planner = planner
        self._model = None
        self._model_fit_at = -1

    # ------------------------------------------------------------- planning

    def workload_stats(self, pairs: Sequence[tuple], *,
                       measure_density: bool = True) -> WorkloadStats:
        """Measure the dispatch inputs from the raw pair dicts (host numpy).

        Density measurement scans every adjacency (O(sum n_i^2)) — noise
        next to the packing planner, but pure waste on paths that never
        read it, so `plan()` disables it when the forced path ignores
        density (stats then report degree/density 0).
        """
        if not pairs:
            return WorkloadStats(0)
        sizes: list[int] = []
        nnz = 0.0
        cells = 0.0
        has_labels = True
        for g1, g2 in pairs:
            for g in (g1, g2):
                n = g["adj"].shape[0]
                sizes.append(n)
                if measure_density:
                    nnz += float(np.count_nonzero(g["adj"]))
                    cells += n * n
                has_labels = has_labels and "labels" in g
        nodes = sum(sizes)
        return WorkloadStats(
            n_pairs=len(pairs), max_nodes=max(sizes),
            mean_nodes=nodes / len(sizes),
            avg_degree=nnz / max(nodes, 1), density=nnz / max(cells, 1.0),
            has_labels=has_labels)

    def _select(self, stats: WorkloadStats, cache_hit_frac: float = 0.0, *,
                train: bool = False, n_to_embed: int = 0,
                keys_known: bool = False) -> tuple[str, str, dict]:
        """Dispatch decision: (path, reason, cost_estimates).

        Forced paths, empty calls and label-free batches are structural —
        no model can override them. Otherwise the measured planner
        (DESIGN.md §15) argmins the fitted per-path latency model when
        every candidate path has enough clean trace support; a cold or
        partially-supported profile falls back BIT-IDENTICALLY to the
        threshold rules in `_select_threshold` (pinned by test).
        """
        if self.path != "auto":
            if train and self.path not in TRAIN_PATHS:
                raise ValueError(
                    f"path {self.path!r} has no VJP-capable executor; "
                    f"training dispatch is restricted to {TRAIN_PATHS} "
                    "(DESIGN.md §11)")
            return self.path, f"forced path={self.path}", {}
        if stats.n_pairs == 0:
            return "reference", "empty call", {}
        if not stats.has_labels:
            # The packed kernels structurally require int labels (W1 row
            # gather); the bucketed megakernel is the dense-feats-capable
            # slot, though today's bucketed executor still builds one-hots
            # from labels (a dense-feats executor is ROADMAP backlog).
            # Training has no bucketed executor, so it degrades to the
            # reference (which will state the label contract on execution).
            return (("reference" if train else "bucketed_mega"),
                    "graphs without int labels cannot take a packed path",
                    {})
        est = self._planner_estimates(stats, train=train,
                                      n_to_embed=n_to_embed,
                                      keys_known=keys_known)
        if est is not None:
            # Deterministic tie-break: predicted cost, then PATHS order.
            path = min(est, key=lambda p: (est[p], PATHS.index(p)))
            ms = ", ".join(f"{p}={est[p] * 1e3:.2f}ms"
                           for p in sorted(est, key=est.get))
            return (path, f"measured cost model argmin ({ms})", est)
        path, reason = self._select_threshold(stats, cache_hit_frac,
                                              train=train)
        return path, reason, {}

    def _select_threshold(self, stats: WorkloadStats,
                          cache_hit_frac: float = 0.0, *,
                          train: bool = False) -> tuple[str, str]:
        """The hand-tuned threshold rules — the cold-profile fallback the
        measured planner must reproduce bit-identically when it lacks
        support (DESIGN.md §15; decision table pinned by
        tests/test_profile.py and the parity-matrix cold-planner test)."""
        if not train and cache_hit_frac >= self.CACHE_MIN_HIT_FRAC:
            return ("embedding_cache",
                    f"{cache_hit_frac:.0%} of unique graphs have resident "
                    f"embeddings (>= {self.CACHE_MIN_HIT_FRAC:.0%}): only "
                    "the NTN+FCN head runs")
        if stats.n_pairs < self.MIN_PACK_PAIRS:
            return (("reference" if train else "bucketed_mega"),
                    f"batch of {stats.n_pairs} too small to fill packed tiles"
                    f" (< {self.MIN_PACK_PAIRS})")
        if stats.avg_degree <= self.SPARSE_MAX_DEGREE:
            return ("packed_sparse",
                    f"measured avg degree {stats.avg_degree:.2f} <= "
                    f"{self.SPARSE_MAX_DEGREE:g}: edge list beats dense "
                    "adjacency")
        return ("packed_dense",
                f"measured avg degree {stats.avg_degree:.2f} > "
                f"{self.SPARSE_MAX_DEGREE:g}: dense MXU matmul wins")

    # ------------------------------------------ measured planner (§15)

    def _cost_model(self):
        """The fitted per-path latency model, refit lazily every
        `PLANNER_REFIT_EVERY` new records (None while the profile is too
        small for even one path)."""
        rec = self.recorder
        if rec is None or rec.total_records < self.PLANNER_MIN_SUPPORT:
            return self._model
        if (self._model_fit_at < 0
                or rec.total_records - self._model_fit_at
                >= self.PLANNER_REFIT_EVERY):
            self._model = fit_cost_model(
                rec.records(), min_support=self.PLANNER_MIN_SUPPORT)
            self._model_fit_at = rec.total_records
            self.counters["planner_refits"] += 1
        return self._model

    def _planner_estimates(self, stats: WorkloadStats, *, train: bool,
                           n_to_embed: int, keys_known: bool) -> dict | None:
        """Predicted wall seconds per candidate path, or None when the
        profile cannot steer this call (planner pinned to thresholds, no
        model yet, or any candidate below `PLANNER_MIN_SUPPORT` — partial
        support falls back whole, so the argmin never compares a measured
        path against an unmeasured one).

        Candidates are the auto-dispatchable executors: the three packed/
        bucketed scoring paths (plus the embedding-cached path whenever
        this call hashed keys — the >= 50% residency flip becomes a
        measured crossover), or `TRAIN_PATHS` under train. The dense
        reference stays out of the scoring candidate set exactly as it is
        under the threshold rules: it is the parity anchor and terminal
        degradation rung, not a latency contender.
        """
        if self.planner != "measured":
            return None
        model = self._cost_model()
        if model is None:
            return None
        # Candidate keys carry the device count the planner would actually
        # assign (profile.cost_key): an 8-device wall must never predict a
        # single-device call or vice versa (DESIGN.md §16).
        if train:
            cand = {p: cost_key(f"train:{p}", self._plan_devices(p, stats))
                    for p in TRAIN_PATHS}
        else:
            cand = {p: cost_key(p, self._plan_devices(p, stats))
                    for p in ("bucketed_mega", "packed_dense",
                              "packed_sparse")}
            if keys_known:
                cand["embedding_cache"] = "embedding_cache"
        if not model.supports(cand.values()):
            return None
        est = {}
        for path, key in cand.items():
            feats = trace_features(
                stats.n_pairs, stats.mean_nodes, stats.avg_degree,
                n_to_embed if path == "embedding_cache" else 0)
            est[path] = model.predict(key, feats)
        return est

    def _plan_devices(self, path: str, stats: WorkloadStats) -> int:
        """Mesh devices to assign a call's packed tiles to (DESIGN.md §16).

        Only the packed paths shard (their [T, ...] tile axis is the
        partition unit); the count halves until every device owns at least
        `MIN_PACK_PAIRS` pairs — a 3-pair call on an 8-device mesh runs
        single-device rather than shipping near-empty tiles to 7 chips.
        """
        nd = self.n_devices
        if nd <= 1 or path not in PACKED_PATHS:
            return 1
        while nd > 1 and stats.n_pairs < nd * self.MIN_PACK_PAIRS:
            nd //= 2
        return max(nd, 1)

    def _tile_mesh(self, devices: int):
        """1-D tile mesh over the first `devices` devices of the runtime
        mesh (cached: shard_map closures keep mesh identity stable)."""
        from jax.sharding import Mesh

        from repro.distributed.sharding import TILE_AXIS

        mesh = self._tile_meshes.get(devices)
        if mesh is None:
            devs = self.runtime.mesh.devices.reshape(-1)[:devices]
            mesh = Mesh(devs, (TILE_AXIS,))
            self._tile_meshes[devices] = mesh
        return mesh

    def _sharded_fn(self, path: str, devices: int,
                    tile_block: int) -> Callable:
        """Jitted shard_map executor for a packed path at a
        (device count, tile_block) — the per-shape-class executable cache
        the §16 refactor replaces the single global wrappers with.
        tile_block comes from `ops.sharded_tile_plan`, which
        balance-shrinks it so few tiles spread over many devices."""
        key = (path, devices, tile_block)
        fn = self._sharded_fns.get(key)
        if fn is None:
            from repro.kernels import ops

            fn, _ = ops.build_pair_score_sharded(
                self._tile_mesh(devices), self.node_budget,
                sparse=path == "packed_sparse", tile_block=tile_block)
            self._sharded_fns[key] = fn
        return fn

    def _record_trace(self, kind: str, path: str, n_pairs: int,
                      plan: ScorePlan, wall_s: float, *,
                      degraded: Sequence[str] = (), attempts: int = 1,
                      n_devices: int = 1):
        """Append one executed work item to the trace ring (DESIGN.md §15).
        Routed through the §12 fault seam (site "profile") and guarded:
        a failing recorder must never fail the scoring call it observes."""
        rec = self.recorder
        if rec is None:
            return
        pstats = self.last_pack_stats or {}
        occ = (float(pstats.get("occupancy_lhs", 0.0)
                     + pstats.get("occupancy_rhs", 0.0)) / 2.0
               if pstats else 0.0)
        with span("record"):
            try:
                _call("profile", lambda: rec.record(
                    kind=kind, path=path, n_pairs=int(n_pairs),
                    max_nodes=plan.stats.max_nodes,
                    mean_nodes=plan.stats.mean_nodes,
                    avg_degree=plan.stats.avg_degree,
                    density=plan.stats.density, occupancy=occ,
                    to_embed=len(plan.to_embed_idx),
                    degraded_from=list(degraded), attempts=int(attempts),
                    wall_s=float(wall_s), n_devices=int(n_devices)))
            except Exception:
                self.counters["profile_record_errors"] += 1

    def _graph_keys(self, pairs: Sequence[tuple]) -> tuple:
        """Canonical keys of every graph in the call: all lhs, then all rhs
        (the flattened order `ScorePlan.cached_idx`/`to_embed_idx` index).

        Hashes each distinct graph *object* once per call (1-vs-N batches
        repeat the query dict and hot corpus dicts many times — the id()
        memo turns 2·B WL hashes into one per unique object). The memo
        lives only for this call: id() values are not stable across GC.
        """
        memo: dict[int, bytes] = {}

        def key_of(g: dict) -> bytes:
            k = memo.get(id(g))
            if k is None:
                k = memo[id(g)] = graph_key(g)
            return k
        return tuple(key_of(p[side]) for side in (0, 1) for p in pairs)

    def plan(self, pairs: Sequence[tuple], *,
             train: bool = False) -> ScorePlan:
        """Measure the workload and decide — without running anything.

        With `train=True` the decision is restricted to the VJP-capable
        paths (`TRAIN_PATHS`, DESIGN.md §11): the cached path never steers
        (its embeddings carry no gradients), the small-batch / label-free
        degrades land on the dense reference instead of the bucketed
        megakernel, and the oversize fallback is the reference executor.

        Validation runs FIRST (DESIGN.md §12): invalid pairs are
        quarantined before any stats/packing code touches them (a malformed
        adjacency must fail as a structured record, not a shape error deep
        inside the planner). Quarantined pairs appear only in
        `plan.quarantined`; `fit_idx`/`over_idx` index the original batch
        but cover valid pairs only. Strict mode raises instead.
        """
        n = len(pairs)
        quarantined: tuple = ()
        valid_idx = np.arange(n, dtype=np.int64)
        if self.validation != "off" and n:
            with span("validate"):
                valid_idx, quarantined = validate_pairs(
                    pairs, n_labels=self.cfg.n_node_labels,
                    stats=self.counters)
            if quarantined and self.validation == "strict":
                raise GraphValidationError(quarantined)
        valid = (pairs if len(valid_idx) == n
                 else [pairs[i] for i in valid_idx])
        # Density only steers the auto sparse/dense split and the sparse
        # edge budget; forced paths that ignore it skip the O(sum n_i^2)
        # adjacency scan.
        with span("stats"):
            stats = self.workload_stats(
                valid, measure_density=self.path in ("auto", "packed_sparse"))
        # The cache steers dispatch only when it could hold answers: keys
        # are hashed (O(sum n_i), host-side) iff the path is forced to the
        # cached one, or auto sees a non-empty cache — a cold cache costs
        # auto streams nothing. Training never hashes: no path it may pick
        # reads the cache.
        keys: tuple = ()
        hit_frac = 0.0
        n_to_embed = 0
        if not train and len(valid) and stats.has_labels \
                and self.cache.capacity > 0 and (
                self.path == "embedding_cache"
                or (self.path == "auto" and len(self.cache))):
            with span("graph_keys"):
                keys = self._graph_keys(valid)
                unique = set(keys)
                hits = sum(1 for k in unique if k in self.cache)
            hit_frac = hits / len(unique)
            n_to_embed = len(unique) - hits
        with span("select"):
            path, reason, est = self._select(stats, hit_frac, train=train,
                                             n_to_embed=n_to_embed,
                                             keys_known=bool(keys))
            cached_idx = to_embed_idx = np.empty(0, np.int64)
            if path == "embedding_cache" and keys:
                hit = [k in self.cache for k in keys]
                cached_idx = np.flatnonzero(hit)
                first = {k: i for i, k in reversed(list(enumerate(keys)))}
                to_embed_idx = np.asarray(
                    sorted(i for k, i in first.items() if not hit[i]),
                    np.int64)
            if path in PACKED_PATHS:
                fits = np.asarray(
                    [max(g1["adj"].shape[0], g2["adj"].shape[0])
                     <= self.node_budget for g1, g2 in valid], bool)
                fit_idx = valid_idx[np.flatnonzero(fits)]
                over_idx = valid_idx[np.flatnonzero(~fits)]
            elif path == "embedding_cache":
                # The embed stage buckets internally with power-of-two
                # overflow, so nothing is oversized for this path.
                fit_idx = valid_idx
                over_idx = np.empty(0, np.int64)
            else:
                fit_idx = np.empty(0, np.int64)
                over_idx = valid_idx
            fallback = "reference" if train else self._bucket_flavor
            return ScorePlan(path=path, fallback=fallback,
                             fit_idx=fit_idx, over_idx=over_idx, stats=stats,
                             reason=reason, cached_idx=cached_idx,
                             to_embed_idx=to_embed_idx, graph_keys=keys,
                             quarantined=quarantined, cost_estimates=est,
                             devices=self._plan_devices(path, stats))

    # ------------------------------------------------------------ execution

    def _bucket_fn(self, bucket: int, flavor: str | None = None) -> Callable:
        """One cached callable per size bucket (built lazily, reused across
        calls; XLA caches one executable per padded batch shape inside).

        `flavor` overrides the engine's own bucketed flavor — used by the
        degradation ladder (e.g. a kernel-flavored engine stepping down to
        the jnp reference). The engine-flavor cache keeps its public
        int-keyed `bucket_fns` contract; other flavors live in a side cache.
        """
        from repro.core.simgnn import pair_score
        from repro.kernels import ops

        if flavor is None or flavor == self._bucket_flavor:
            flavor = self._bucket_flavor
            cache, key = self.bucket_fns, bucket
        else:
            cache, key = self._alt_bucket_fns, (flavor, bucket)
        if key not in cache:
            if flavor == "reference":
                if self._ref_fn is None:    # shared: jit caches per shape
                    self._ref_fn = jax.jit(pair_score)
                cache[key] = self._ref_fn
            elif flavor == "two_kernel":
                cache[key] = ops.simgnn_pair_score_kernel
            else:
                cache[key] = jax.jit(functools.partial(
                    ops.pair_score_megakernel,
                    block_pairs=ops.megakernel_block_pairs(bucket)))
        return cache[key]

    def _score_bucketed(self, pairs, idx: np.ndarray, out: np.ndarray,
                        flavor: str | None = None):
        from repro.core.batching import bucket_pairs, to_host

        site = flavor or self._bucket_flavor
        with span("pack"):
            buckets = bucket_pairs(pairs, self.cfg.n_node_labels,
                                   allow_oversize=True, stats=self.counters)
        for bucket, (lhs, rhs, idxs) in buckets.items():
            fn = self._bucket_fn(bucket, flavor)
            with span("kernel"):
                s = _call(site, lambda fn=fn, lhs=lhs, rhs=rhs: fn(
                    self.params, lhs.adj, lhs.feats, lhs.mask,
                    rhs.adj, rhs.feats, rhs.mask))
            out[idx[idxs]] = to_host((s,), self.counters)[0]

    def _score_packed(self, pairs, idx: np.ndarray, out: np.ndarray,
                      sparse: bool, stats: WorkloadStats, devices: int = 1):
        """Packed scoring: pack host-side, score the tiles, unpack. On one
        device the `kernels.ops` executor pads and runs the kernel under
        fault site `<path>`. With `devices > 1` the tile axis is sharded
        over that many mesh devices (DESIGN.md §16): T pads to the
        `ops.sharded_tile_plan` target, the cached shard_map executor runs
        under site `sharded:<path>` — a dead shard surfaces there and the
        §12 ladder collapses the call to the single-device rung — and
        `last_pack_stats` gains the per-device tile spans."""
        from repro.core.batching import unpack_pair_scores
        from repro.kernels import ops

        path = "packed_sparse" if sparse else "packed_dense"
        packed, pstats = self._pack(pairs, sparse, stats)
        if devices > 1:
            t = packed.mask1.shape[0]
            target, tile_block = ops.sharded_tile_plan(
                t, self.node_budget, devices, sparse=sparse)
            fn = self._sharded_fn(path, devices, tile_block)
            with span("kernel"):
                operands = ops.pad_tiles(packed.kernel_operands(sparse),
                                         target)
                s = _call(f"sharded:{path}",
                          lambda: fn(self.params, operands))[:t]
            per_device = target // devices
            pstats = dict(
                pstats, devices=devices, tiles=t, tiles_padded=target,
                # live-tile fraction of each device's span (pad tiles append
                # at the end, so trailing devices absorb the padding waste).
                device_occupancy=[
                    max(0, min(t - d * per_device, per_device)) / per_device
                    for d in range(devices)])
        else:
            score = ops.pair_score_sparse if sparse else ops.pair_score_packed
            with span("kernel"):
                s = _call(path, lambda: score(self.params, packed,
                                              quantize_tiles=True))
        self.last_pack_stats = pstats
        with span("unpack"):
            out[idx] = unpack_pair_scores(s, packed, len(pairs),
                                          stats=self.counters)

    def _pack(self, pairs, sparse: bool, stats: WorkloadStats):
        """Pack a scoring work item into tiles (one `engine.pack` span) and
        count the bytes packing copied. Fixed slots_per_tile + power-of-two
        tile/edge quantization keep the compiled-shape set small (O(log T)
        executables) under varying batch sizes and FFD outcomes."""
        from repro.core.batching import pack_pairs

        slots = max(8, self.node_budget // 4)
        with span("pack"):
            if sparse:
                packed, pstats = self._pack_sparse(pairs, slots,
                                                   stats.avg_degree)
            else:
                packed, pstats = pack_pairs(pairs, self.node_budget,
                                            slots_per_tile=slots)
        self.counters["h2d_bytes"] += pstats["h2d_bytes"]
        self.counters["d2h_bytes"] += pstats["d2h_bytes"]
        return packed, pstats

    def _pack_sparse(self, pairs, slots: int, avg_degree: float):
        """Shared sparse packing (scoring + training): ladder-sized edge
        budget, with the engine's realized overflow budget from earlier
        calls as the floor so one heavy batch doesn't flip the compiled
        [T, E_ov] shape back and forth across the stream."""
        from repro.core.batching import pack_pairs
        from repro.kernels import ops

        edge_budget = self.edge_budget
        if edge_budget is None:
            edge_budget = ops.packed_edge_budget(self.node_budget, avg_degree)
        packed, pstats = pack_pairs(pairs, self.node_budget,
                                    slots_per_tile=slots, with_edges=True,
                                    edge_budget=edge_budget,
                                    overflow_budget=self._overflow_floor)
        self._overflow_floor = max(self._overflow_floor,
                                   pstats["overflow_budget"])
        if pstats["degree_rsqrt_misses"]:
            self.counters["degree_rsqrt_misses"] += (
                pstats["degree_rsqrt_misses"])
        return packed, pstats

    # ------------------------------------- degradation + breakers (§12)

    def _shape_class(self, stats: WorkloadStats) -> tuple:
        """Power-of-two (batch, nodes) bucket a breaker is keyed on: a path
        that dies on 128-node overflow traffic keeps serving 64-node calls
        normally, and the key space stays O(log^2) like the executable set."""
        from repro.core.batching import next_pow2

        return (next_pow2(max(stats.n_pairs, 1), floor=1),
                next_pow2(max(stats.max_nodes, 1), floor=8))

    def _breaker(self, path: str, shape_class: tuple) -> CircuitBreaker:
        key = (path, shape_class)
        br = self.breakers.get(key)
        if br is None:
            br = self.breakers[key] = CircuitBreaker(
                failure_threshold=self.breaker_threshold,
                cooldown_s=self.breaker_cooldown_s, clock=self._clock)
        return br

    def _execute_rung(self, rung: str, devices: int, sub, idx: np.ndarray,
                      out: np.ndarray, plan: ScorePlan):
        if rung in PACKED_PATHS:
            self._score_packed(sub, idx, out, rung == "packed_sparse",
                               plan.stats, devices)
        elif rung == "embedding_cache":
            self._score_cached(sub, idx, out, plan)
        else:
            self._score_bucketed(sub, idx, out, flavor=rung)

    def _ladder_rungs(self, start: str, devices: int,
                      ladder: dict) -> tuple:
        """(path, devices) rung sequence for one work item: the planned
        rung first; for a sharded start the SECOND rung is the same path
        collapsed to a single device (DESIGN.md §16 — a bad shard costs the
        mesh, never the batch), then the ordinary single-device ladder.
        degrade=False pins the call to its planned rung as before."""
        if not self.degrade:
            return ((start, devices),)
        rungs = [(start, devices)]
        if devices > 1:
            rungs.append((start, 1))
        rungs.extend((r, 1) for r in ladder.get(start, ()))
        return tuple(rungs)

    def _run_score_ladder(self, start: str, sub, idx: np.ndarray,
                          out: np.ndarray, plan: ScorePlan
                          ) -> tuple[int, list, str, int]:
        """Execute one work item (a pair subset) starting at `start`,
        stepping down `DEGRADE_LADDER` on failure (DESIGN.md §12).

        A rung fails by raising OR by producing non-finite scores for
        validated inputs (a silently-corrupting kernel). Each non-reference
        rung is guarded by its (rung-name, shape-class) breaker — sharded
        rungs breaker separately from their single-device twin, so a mesh
        with one persistently dead shard cools down while single-device
        keeps serving. While open, the rung is skipped outright and the
        next rung serves; once half-open, one probe runs. The terminal
        reference rung has no breaker and no finite check — by then NaN
        means the *model* is non-finite, which quarantine cannot rule out
        and retries cannot fix. Returns (attempts, degraded-rung names,
        the path that served, the device count it served at); re-raises
        only if every rung failed.
        """
        devices = plan.devices if start == plan.path else 1
        rungs = self._ladder_rungs(start, devices, DEGRADE_LADDER)
        sc = self._shape_class(plan.stats)
        degraded: list[str] = []
        attempts = 0
        last_err: Exception | None = None
        for rung, nd in rungs:
            name = _rung_name(rung, nd)
            terminal = rung == "reference"
            br = None if terminal else self._breaker(name, sc)
            if br is not None and not br.allow():
                self.counters[f"breaker_rejected:{name}"] += 1
                degraded.append(name)
                continue
            attempts += 1
            try:
                self._execute_rung(rung, nd, sub, idx, out, plan)
                if not terminal and not np.isfinite(out[idx]).all():
                    raise NonFiniteOutput(
                        f"{name} produced non-finite scores for validated "
                        "inputs")
                if br is not None:
                    br.record_success()
                return attempts, degraded, rung, nd
            except Exception as exc:
                if br is not None:
                    br.record_failure()
                self.counters[f"errors:{name}"] += 1
                degraded.append(name)
                last_err = exc
                if rung in PACKED_PATHS:
                    self.last_pack_stats = None   # stats of a failed attempt
        raise last_err if last_err is not None else RuntimeError(
            f"no executable rung for {start} (ladder exhausted)")

    def health(self) -> dict:
        """Inspectable fault-tolerance + planner state (DESIGN.md §12/§15):
        breaker snapshots keyed by path and shape class, error/degradation/
        quarantine counters with the bytes scoring copied to and from the
        device (`h2d_bytes`, `d2h_bytes`), the graphs validation checked
        one by one (`validate_exact_graphs`), the degrees sparse packing
        sent to the device for their rsqrt (`degree_rsqrt_misses`, present
        once nonzero), the embedding-LRU counters, and
        the measured planner (profile size, fitted model support +
        residuals)."""
        rec = self.recorder
        planner: dict = {"mode": self.planner,
                         "enabled": self._model is not None
                         and bool(self._model.weights)}
        if rec is not None:
            planner.update(records=rec.total_records,
                           records_dropped=int(
                               rec.counters["records_dropped"]),
                           record_errors=int(rec.counters["record_errors"]))
        if self._model is not None:
            planner["model"] = self._model.snapshot()
        return {
            "breakers": {
                f"{path}[pairs<={b},nodes<={n}]": br.snapshot()
                for (path, (b, n)), br in sorted(self.breakers.items())},
            "counters": dict(self.counters),
            "cache": self.cache.stats(),
            "planner": planner,
        }

    # -------------------------------------------------------- training path

    def _train_fn(self, path: str, chunk_tiles: int,
                  devices: int = 1) -> Callable:
        """One jitted value_and_grad executor per (train path, chunk size,
        device count, gradient-function kind) — cached on the engine like
        `bucket_fns`, so a training loop reuses one executable per padded
        shape. A packed path's function maps (params, targets, operands)
        -> (sum of squared errors, d/dparams), `operands` being
        `PackedPairBatch.kernel_operands`, scanning `chunk_tiles`-tile
        chunks of the packed batch (cache blocking AND accumulation
        microbatching in one mechanism — the packed planes are packed once
        and only the scan slice moves).

        The raw loss -> value_and_grad transform is delegated to the
        engine's swappable `grad_fn` object (`train/sgf.py`, paxml-style),
        so clipped / DP variants change the executor without touching this
        cache logic. With `devices > 1` the whole chunk-scan runs under
        shard_map — each device scans only its tile span — and loss + grad
        tree are `psum`-reduced over the tile axis (DESIGN.md §16), OUTSIDE
        the grad object: per-microbatch transforms compose with the
        cross-device reduction unchanged.
        """
        key = (path, chunk_tiles, devices, self.grad_fn.cache_key)
        if key not in self._train_fns:
            import jax.numpy as jnp

            if path == "reference":
                from repro.core.simgnn import pair_score_from_labels

                def sse(params, tgt, *arrays):
                    return jnp.sum(
                        (pair_score_from_labels(params, *arrays) - tgt) ** 2)
            else:
                from repro.kernels import grad as kgrad

                score_fn = (kgrad.sparse_pair_score_grad
                            if path == "packed_sparse"
                            else kgrad.packed_pair_score_grad)

                def sse(params, tgt, operands):
                    # Pad pair slots score exact zero against target zero.
                    return jnp.sum((score_fn(params, *operands) - tgt) ** 2)

            grad_fn = self.grad_fn.value_and_grad(sse)
            if path == "reference":
                fn = grad_fn
            else:
                def fn(params, tgt, operands):
                    t = tgt.shape[0]
                    n_chunks = t // chunk_tiles
                    if n_chunks <= 1:
                        return grad_fn(params, tgt, operands)

                    def chunk(x):
                        return x.reshape((n_chunks, chunk_tiles)
                                         + x.shape[1:])
                    xs = jax.tree.map(chunk, (tgt, operands))

                    def micro(acc, mb):
                        s, g = grad_fn(params, *mb)
                        return (acc[0] + s,
                                jax.tree.map(jnp.add, acc[1], g)), None
                    zero = (jnp.zeros((), jnp.float32),
                            jax.tree.map(
                                lambda p: jnp.zeros(p.shape, jnp.float32),
                                params))
                    (s, g), _ = jax.lax.scan(micro, zero, xs)
                    return s, g
                if devices > 1:
                    from jax.sharding import PartitionSpec as P

                    from repro.distributed.sharding import TILE_AXIS

                    scan = fn

                    def local(params, tgt, operands):
                        return jax.lax.psum(scan(params, tgt, operands),
                                            TILE_AXIS)
                    fn = jax.shard_map(
                        local, mesh=self._tile_mesh(devices),
                        in_specs=(P(), P(TILE_AXIS), P(TILE_AXIS)),
                        out_specs=P(), check_vma=False)
            self._train_fns[key] = jax.jit(fn)
        return self._train_fns[key]

    def _packed_sse(self, params, fit_pairs, fit_targets: np.ndarray,
                    plan: ScorePlan, accum_steps: int,
                    path: str | None = None, devices: int = 1):
        """Sum-of-squared-errors + grads of the packed fit split: pack ONCE,
        scatter targets to [T, P] pair slots, pad the tile axis to a chunk
        multiple (pad tiles are all-zero: exact-zero scores, targets and
        grads), run the chunk-scanning custom-VJP executor. `path` defaults
        to the planned path; the train ladder passes the current rung.
        With `devices > 1` the tile axis pads to a devices x chunk multiple
        and runs the shard_map + psum executor (DESIGN.md §16) under the
        `sharded:train:<path>` fault site."""
        import jax.numpy as jnp

        from repro.core.batching import next_pow2, pack_pairs
        from repro.kernels import ops

        path = plan.path if path is None else path
        sparse = path == "packed_sparse"
        slots = max(8, self.node_budget // 4)
        if sparse:
            packed, pstats = self._pack_sparse(fit_pairs, slots,
                                               plan.stats.avg_degree)
        else:
            packed, pstats = pack_pairs(fit_pairs, self.node_budget,
                                        slots_per_tile=slots)
        self.last_pack_stats = pstats

        pair_mask = np.asarray(packed.pair_mask)
        pair_index = np.asarray(packed.pair_index)
        tgt = np.zeros(pair_mask.shape, np.float32)
        live = pair_mask > 0
        tgt[live] = fit_targets[pair_index[live]]

        # Chunk small enough that accum_steps chunks exist and that padding
        # never exceeds the batch itself (all powers of two), then pad T to
        # a chunk multiple — bounded pad-tile waste (< one chunk) vs. up to
        # 2x for power-of-two T quantization. Sharded calls pad to a
        # devices x chunk multiple instead, so every device scans whole
        # chunks of its tile span.
        t = pair_mask.shape[0]
        chunk_tiles = min(self.TRAIN_TILE_CHUNK, next_pow2(t, floor=1))
        while chunk_tiles > 1 and (-(-t // chunk_tiles)) < accum_steps:
            chunk_tiles //= 2
        multiple = chunk_tiles * max(devices, 1)
        operands = ops.pad_tiles(packed.kernel_operands(sparse), multiple)
        if devices > 1:
            self.last_pack_stats = dict(pstats, devices=devices, tiles=t,
                                        tiles_padded=operands[0].shape[0])
        fn = self._train_fn(path, chunk_tiles, devices)
        site = (f"sharded:train:{path}" if devices > 1
                else f"train:{path}")
        return _call(site, lambda: fn(
            params, ops._pad_batch(jnp.asarray(tgt), multiple)[0], operands))

    def _reference_sse(self, params, pairs, targets: np.ndarray):
        """SSE + grads of the dense-reference executor (the train-mode
        fallback for oversized pairs and tiny batches), bucketed like
        `_score_bucketed` with power-of-two overflow buckets."""
        import jax.numpy as jnp

        from repro.core.batching import bucket_pairs

        fn = self._train_fn("reference", 1)
        sse = jnp.zeros((), jnp.float32)
        grads = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                             params)
        for _, (lhs, rhs, idxs) in bucket_pairs(
                pairs, self.cfg.n_node_labels, allow_oversize=True).items():
            s, g = _call("train:reference",
                         lambda lhs=lhs, rhs=rhs, idxs=idxs: fn(
                             params, jnp.asarray(targets[idxs]),
                             lhs.adj, lhs.labels, lhs.mask,
                             rhs.adj, rhs.labels, rhs.mask))
            sse = sse + s
            grads = jax.tree.map(jnp.add, grads, g)
        return sse, grads

    def _run_train_ladder(self, start: str, params, sub,
                          tgt: np.ndarray, plan: ScorePlan,
                          accum_steps: int) -> tuple:
        """Training twin of `_run_score_ladder`: walk the VJP-capable
        `TRAIN_DEGRADE_LADDER`, breaker-gated per (train:path, shape-class)
        — train breakers are separate from score breakers because the
        executors are (custom-VJP twins vs. pallas kernels). Non-terminal
        rungs that emit non-finite loss/grads for finite targets fail like
        crashes; the reference rung serves whatever it computes (a NaN
        there is the model's, and `train.step` skips the update).
        Like the score ladder, a sharded start collapses to its
        single-device twin before crossing paths (DESIGN.md §16). Returns
        (sse, grads, attempts, degraded, the path that served, the device
        count it served at)."""
        devices = plan.devices if start == plan.path else 1
        rungs = self._ladder_rungs(start, devices, TRAIN_DEGRADE_LADDER)
        sc = self._shape_class(plan.stats)
        degraded: list[str] = []
        attempts = 0
        last_err: Exception | None = None
        for rung, nd in rungs:
            name = _rung_name(rung, nd)
            terminal = rung == "reference"
            br = (None if terminal
                  else self._breaker(f"train:{name}", sc))
            if br is not None and not br.allow():
                self.counters[f"breaker_rejected:train:{name}"] += 1
                degraded.append(name)
                continue
            attempts += 1
            try:
                if rung in PACKED_PATHS:
                    s, g = self._packed_sse(params, sub, tgt, plan,
                                            accum_steps, path=rung,
                                            devices=nd)
                else:
                    s, g = self._reference_sse(params, sub, tgt)
                if not terminal and not tree_all_finite(s, g):
                    raise NonFiniteOutput(
                        f"train:{name} produced non-finite loss/grads for "
                        "finite targets")
                if br is not None:
                    br.record_success()
                return s, g, attempts, degraded, rung, nd
            except Exception as exc:
                if br is not None:
                    br.record_failure()
                self.counters[f"errors:train:{name}"] += 1
                degraded.append(name)
                last_err = exc
                if rung in PACKED_PATHS:
                    self.last_pack_stats = None
        raise last_err if last_err is not None else RuntimeError(
            f"no executable train rung for {start} (ladder exhausted)")

    def loss_and_grad(self, pairs: Sequence[tuple], targets, *,
                      params=None, accum_steps: int = 1):
        """MSE loss and parameter gradients for one batch of graph pairs —
        the differentiable twin of `score()` (DESIGN.md §11).

        Plans with the same `ScorePlan` machinery but restricted to the
        VJP-capable paths (`TRAIN_PATHS`); the oversize-fallback split is
        preserved with the dense reference as the fallback executor. Packed
        paths pack ONCE per call and ALWAYS scan the tiles in
        `TRAIN_TILE_CHUNK`-sized chunks (cache blocking); `accum_steps`
        (a power of two) guarantees at least that many chunks — gradient
        accumulation without re-packing, since only the scan slice moves.

        Fault tolerance (DESIGN.md §12): non-finite targets are dropped
        before planning (a poisoned label would NaN the whole SSE), invalid
        graphs are quarantined by `plan()`, and each work item walks
        `TRAIN_DEGRADE_LADDER` on executor failure. The loss is normalized
        by the number of pairs actually scored, so dropped/quarantined
        pairs do not deflate the gradient signal.

        `params` defaults to the engine's own (serving) params; a training
        loop passes its evolving copy. Returns `(loss, grads)` with
        loss = mean_i (pred_i - target_i)^2 over the scored pairs and grads
        a pytree like `params` (fp32 accumulation).
        """
        import jax.numpy as jnp

        if accum_steps < 1 or accum_steps & (accum_steps - 1):
            raise ValueError(f"accum_steps must be a power of two, got "
                             f"{accum_steps}")
        params = self.params if params is None else params
        targets = np.asarray(targets, np.float32).reshape(-1)
        if targets.shape[0] != len(pairs):
            raise ValueError(f"{len(pairs)} pairs but {targets.shape[0]} "
                             "targets")
        finite_t = np.isfinite(targets)
        if not finite_t.all():
            self.counters["nonfinite_targets"] += int((~finite_t).sum())
            keep = np.flatnonzero(finite_t)
            pairs = [pairs[i] for i in keep]
            targets = targets[keep]
        plan = self.plan(pairs, train=True)
        self.last_plan = plan
        self.last_pack_stats = None
        if plan.quarantined:
            self.counters["quarantined_graphs"] += len(plan.quarantined)
        zero = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        if not len(pairs):
            return jnp.zeros((), jnp.float32), zero
        if not plan.stats.has_labels:
            raise ValueError(
                "graphs must carry int node labels ('labels'); a dense-"
                "feats executor is not implemented yet (ROADMAP open item)")
        sse = jnp.zeros((), jnp.float32)
        grads = zero
        degraded: list[str] = []
        attempts = 0
        n_live = 0
        for start, idx in ((plan.path, plan.fit_idx),
                           ("reference", plan.over_idx)):
            if not len(idx):
                continue
            t0 = self._clock()
            s, g, a, d, rung, nd = self._run_train_ladder(
                start, params, [pairs[i] for i in idx], targets[idx],
                plan, accum_steps)
            jax.block_until_ready(g)
            self._record_trace("train", f"train:{rung}", len(idx), plan,
                               self._clock() - t0, degraded=d, attempts=a,
                               n_devices=nd)
            sse = sse + s
            grads = jax.tree.map(jnp.add, grads, g)
            attempts += a
            degraded.extend(d)
            n_live += len(idx)
        self.last_plan = replace(plan, degraded_from=tuple(degraded),
                                 attempts=max(attempts, 1))
        if not n_live:
            return jnp.zeros((), jnp.float32), zero
        n = float(n_live)
        return sse / n, jax.tree.map(lambda x: x / n, grads)

    # ------------------------------------------------- embedding-cached path

    def _embed_fn(self) -> Callable:
        """(params, adj, feats, mask) -> [B, F] graph embeddings, jit-cached.

        Pure-jnp `graph_embedding` by default (the parity anchor — per-graph
        results are bit-identical across batch compositions and pad widths,
        which the cache correctness tests rely on); the fused GCN+Att kernel
        when the engine was built with `embed_with_kernels=True`.
        """
        if self._embed_ref_fn is None:
            if self._embed_kernels:
                from repro.core.gcn import normalized_adjacency
                from repro.kernels import ops

                def fused(params, adj, feats, mask):
                    a_norm = normalized_adjacency(adj, mask)
                    return ops.graph_embeddings_fused(params, a_norm, feats,
                                                      mask)
                self._embed_ref_fn = fused
            else:
                from repro.core.simgnn import graph_embedding
                self._embed_ref_fn = jax.jit(graph_embedding)
        return self._embed_ref_fn

    def embed_graphs(self, graphs: Sequence[dict], *,
                     keys: Sequence[bytes] | None = None) -> np.ndarray:
        """Per-graph `[F]` GCN+Att embeddings through the cache.

        Hits are served from the LRU; unique misses are bucketed by size
        (power-of-two overflow for oversized graphs), embedded in batched
        calls, and inserted. Returns `[len(graphs), F]` float32 in input
        order — duplicates within one call are embedded once.
        """
        from repro.core.batching import bucket_for, pad_graphs, to_host

        f = self.cfg.gcn_dims[-1]
        out = np.zeros((len(graphs), f), np.float32)
        if not graphs:
            return out
        if keys is None:
            keys = [graph_key(g) for g in graphs]
        # One LRU access per *unique* key: duplicates within a call are one
        # logical lookup (hit/miss counters stay per-graph, not per-slot).
        # Lookups carry the structural fingerprint so a WL-key collision
        # evicts-and-misses instead of serving another graph's row.
        seen: dict[bytes, np.ndarray | None] = {}
        misses: "OrderedDict[bytes, list[int]]" = OrderedDict()
        for i, k in enumerate(keys):
            if k not in seen:
                seen[k] = self.cache.get(k, graph_fingerprint(graphs[i]))
            emb = seen[k]
            if emb is not None:
                out[i] = emb
            else:
                misses.setdefault(k, []).append(i)
        if not misses:
            return out
        buckets: dict[int, list[tuple[bytes, dict]]] = {}
        for k, idxs in misses.items():
            g = graphs[idxs[0]]
            b = bucket_for(g["adj"].shape[0], allow_oversize=True)
            buckets.setdefault(b, []).append((k, g))
        embed = self._embed_fn()
        for b, items in sorted(buckets.items()):
            with span("pack"):
                batch = pad_graphs([g for _, g in items],
                                   self.cfg.n_node_labels, b,
                                   stats=self.counters)

            def run(site, fn):
                with span("kernel"):
                    h = _call(site, lambda: fn(
                        self.params, batch.adj, batch.feats, batch.mask))
                h = to_host((h,), self.counters)[0].astype(np.float32,
                                                         copy=False)
                if not np.isfinite(h).all():
                    raise NonFiniteOutput(
                        f"{site} produced non-finite embeddings")
                return h

            # Per-bucket degradation (DESIGN.md §12): a failing embed batch
            # retries once on the pure-jnp reference embedder; if that also
            # fails, ONLY this bucket's graphs are dropped (NaN rows, never
            # cached) — the other buckets and every cache hit still serve.
            # degrade=False raises instead.
            try:
                hg = run("embed", embed)
            except Exception:
                self.counters["errors:embed"] += 1
                if not self.degrade:
                    raise
                try:
                    hg = run("embed_fallback", self._embed_fallback())
                    self.counters["embed_fallbacks"] += 1
                except Exception:
                    self.counters["errors:embed_fallback"] += 1
                    self.counters["embed_dropped_graphs"] += len(items)
                    for k, _ in items:
                        out[misses[k]] = np.nan
                    continue
            for (k, g), emb in zip(items, hg):
                emb = emb.copy()
                emb.setflags(write=False)
                self.cache.put(k, emb, graph_fingerprint(g))
                out[misses[k]] = emb
        return out

    def prefilter_topm(self, qv, corpus_emb, m: int, *,
                       block_cols: int | None = None,
                       ntn_operands: tuple | None = None
                       ) -> tuple[np.ndarray, np.ndarray]:
        """Blocked streaming top-M prefilter scan (DESIGN.md §14).

        The first stage of a two-stage query: shortlist `m` corpus rows per
        query without ever materializing the [Q, N] score matrix. With
        `ntn_operands=(uq, dq)` (from `kernels.retrieval.collapse_query_ntn`)
        the scan runs the exact streamed NTN+FCN proxy; otherwise `qv` is
        dotted against the corpus directly (raw or calibrated vectors).
        Routed through the §12 fault seam (site "prefilter") so chaos tests
        and real kernel failures surface here — callers degrade to the
        exact full scan and the counters record it. Raises on any failure;
        corrupt output (non-finite scores from finite inputs, out-of-range
        indices) is promoted to `NonFiniteOutput` rather than served.
        """
        from repro.kernels import retrieval

        self.counters["prefilter_calls"] += 1
        try:
            if ntn_operands is not None:
                uq, dq = ntn_operands
                s, i = _call("prefilter", lambda: retrieval.blocked_topm_ntn(
                    uq, dq, corpus_emb, self.params["fcn"], m,
                    block_cols=block_cols))
            else:
                s, i = _call("prefilter", lambda: retrieval.blocked_topm(
                    qv, corpus_emb, m, block_cols=block_cols))
            s, i = np.asarray(s, np.float32), np.asarray(i)
            n = np.asarray(corpus_emb).shape[0]
            if i.size and not ((i >= 0) & (i < n)).all():
                raise NonFiniteOutput(
                    "prefilter returned out-of-range candidate indices")
            if s.size and np.isnan(s).any():
                raise NonFiniteOutput("prefilter returned NaN scores")
        except Exception:
            self.counters["errors:prefilter"] += 1
            raise
        self.counters["prefilter_queries"] += len(np.asarray(qv))
        return s, i

    def _embed_fallback(self) -> Callable:
        """Pure-jnp reference embedder used as the per-bucket retry when the
        configured embed executor fails — always available, kernel-free."""
        if self._embed_fallback_fn is None:
            from repro.core.simgnn import graph_embedding
            self._embed_fallback_fn = jax.jit(graph_embedding)
        return self._embed_fallback_fn

    def _head(self) -> Callable:
        if self._head_fn is None:
            if self._bucket_flavor == "reference":
                self._head_fn = self._head_fallback()
            else:
                from repro.kernels import ops

                def head(params, h1, h2):
                    bp = max(8, min(128, -(-h1.shape[0] // 8) * 8))
                    return ops.pair_scores_fused(params, h1, h2,
                                                 block_pairs=bp)
                self._head_fn = head
        return self._head_fn

    def _head_fallback(self) -> Callable:
        if self._head_fallback_fn is None:
            from repro.core.simgnn import fcn_head, ntn_scores

            self._head_fallback_fn = jax.jit(
                lambda params, h1, h2: fcn_head(
                    params["fcn"], ntn_scores(params["ntn"], h1, h2)))
        return self._head_fallback_fn

    def pair_scores_from_embeddings(self, hg1, hg2) -> np.ndarray:
        """Batched NTN+FCN head on precomputed `[B, F]` graph embeddings —
        the entire per-query cost of a warm 1-vs-N search (DESIGN.md §10).
        Runs the fused head kernel (`kernels/simgnn_head.py`) except on
        forced-reference engines, which stay kernel-free. A failing or
        NaN-emitting head retries once on the jnp reference head (unless
        the engine was built with `degrade=False`, which raises); pairs
        whose *embeddings* are already NaN (dropped embed buckets) score
        NaN without tripping the retry."""
        from repro.core.batching import to_device, to_host

        hg1 = np.asarray(hg1, np.float32)
        hg2 = np.asarray(hg2, np.float32)
        row_ok = (np.isfinite(hg1).all(axis=-1)
                  & np.isfinite(hg2).all(axis=-1))
        h1, h2 = to_device((hg1, hg2), self.counters)

        def run(site, fn):
            with span("kernel"):
                s = _call(site, lambda: fn(self.params, h1, h2))
            s = to_host((s,), self.counters)[0].astype(np.float32,
                                                       copy=False)
            if not np.isfinite(s[row_ok]).all():
                raise NonFiniteOutput(
                    f"{site} produced non-finite scores for finite "
                    "embeddings")
            return s

        try:
            return run("head", self._head())
        except Exception:
            self.counters["errors:head"] += 1
            if not self.degrade:
                raise
            return run("head_fallback", self._head_fallback())

    def _score_cached(self, pairs, idx: np.ndarray, out: np.ndarray,
                      plan: ScorePlan):
        n = len(pairs)
        keys = plan.graph_keys if len(plan.graph_keys) == 2 * n else None
        hg1 = self.embed_graphs([p[0] for p in pairs],
                                keys=keys[:n] if keys else None)
        hg2 = self.embed_graphs([p[1] for p in pairs],
                                keys=keys[n:] if keys else None)
        out[idx] = self.pair_scores_from_embeddings(hg1, hg2)

    def score(self, pairs: Sequence[tuple]) -> np.ndarray:
        """Score a batch of graph-pair dicts in original order.

        Fault tolerance (DESIGN.md §12): quarantined pairs score NaN;
        each work item (the planned path's fit split, the fallback's
        oversize split) walks the degradation ladder on executor failure.
        The executed plan — including `degraded_from` and `attempts` — is
        republished on `last_plan`. The call runs in one `engine.score`
        span, its stages in spans of their own (`core.profile.STAGES`).
        """
        with span("score"):
            return self._score(pairs)

    def _score(self, pairs: Sequence[tuple]) -> np.ndarray:
        out = np.zeros(len(pairs), np.float32)
        plan = self.plan(pairs)
        self.last_plan = plan
        # Stats describe the *latest* call only: a bucketed call must not
        # leave a previous packed call's occupancy lying around.
        self.last_pack_stats = None
        if plan.quarantined:
            self.counters["quarantined_graphs"] += len(plan.quarantined)
            out[sorted({rec.pair for rec in plan.quarantined})] = np.nan
        if len(plan.fit_idx) or len(plan.over_idx):
            if not plan.stats.has_labels:
                # Every executor today builds features from int labels
                # (pad_graphs one-hots, packed kernels gather W1 rows); fail
                # with the contract instead of a KeyError deep inside
                # padding.
                raise ValueError(
                    "graphs must carry int node labels ('labels'); a dense-"
                    "feats executor is not implemented yet (ROADMAP open "
                    "item)")
            degraded: list[str] = []
            attempts = 0
            for start, idx in ((plan.path, plan.fit_idx),
                               (plan.fallback, plan.over_idx)):
                if not len(idx):
                    continue
                t0 = self._clock()
                a, d, rung, nd = self._run_score_ladder(
                    start, [pairs[i] for i in idx], idx, out, plan)
                self._record_trace("score", rung, len(idx), plan,
                                   self._clock() - t0, degraded=d,
                                   attempts=a, n_devices=nd)
                attempts += a
                degraded.extend(d)
            self.last_plan = replace(plan, degraded_from=tuple(degraded),
                                     attempts=max(attempts, 1))
        return out

    __call__ = score
