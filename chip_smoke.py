#!/usr/bin/env python3
"""On-chip smoke test of the SimGNN main path through Mosaic-compiled kernels.

    python chip_smoke.py              # one TPU chip: score, search, train
    python chip_smoke.py --chips 4    # four chips: the tile-sharded path only

One chip runs three phases through the objects a user calls, at the AIDS
configuration's full widths (`configs/simgnn_aids.py`) with random weights
from `--seed`:

  score   `ScoringEngine(path="auto", degrade=False)` on 1024 AIDS-like
          query pairs in calls of 256 (the plan must pick packed_sparse),
          plus one call each forced to packed_dense and bucketed_mega, all
          checked against the dense `core.simgnn.pair_score` reference at
          "highest" matmul precision within the f32 parity bounds.
  search  `SimilaritySearchServer` indexes an 8192-graph Zipf corpus and
          serves 8 batches of two-stage top-10 queries (blocked top-M scan
          kernel + head kernel), checked against exact search: recall@10.
  train   5 steps of `build_simgnn_train_step(engine)` at batch 128; loss
          and grads finite, one step's grads checked against dense autodiff
          of the reference at "highest" precision.

`--chips 4` runs only the device-sharded path (DESIGN.md §16): scoring over
`tile_runtime(4)` against the same calls on one device (bitwise), and the
sharded `loss_and_grad` with its psum against one device.

Every phase prints one line. The script exits non-zero when JAX finds no
TPU (printing no result), when a kernel was traced in interpret mode, when
any plan degraded or any error/fallback counter moved, or when a parity
check fails. The last line of stdout is one JSON object:
{"ok": ..., "device": {"platform", "kind", "count"}}. Compile and warm
wall times are set-up information, not performance measurements.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(HERE, "src")

#: Parity bounds (max abs error of post-sigmoid scores / per-leaf grads).
GRAD_ATOL_F32 = 1e-5        # engine grads vs dense autodiff (test_grad.py)
SHARDED_ATOL = 1e-6         # sharded vs one-device loss/grads (§16)
MIN_RECALL_AT_10 = 0.99

SCORE_PAIRS, SCORE_CALL = 1024, 256
CORPUS, QUERY_BATCHES, QUERIES_PER_BATCH, PREFILTER_M = 8192, 8, 8, 64
TRAIN_STEPS, TRAIN_BATCH = 5, 128


class Smoke:
    """Collects per-phase results and failures."""

    def __init__(self):
        self.failures: list[str] = []

    def check(self, ok: bool, what: str) -> None:
        if not ok:
            self.failures.append(what)

    def phase(self, name: str, fn, *args) -> None:
        t0 = time.perf_counter()
        try:
            info = fn(self, *args)
        except Exception as exc:                   # report, keep going
            traceback.print_exc(file=sys.stderr)
            self.failures.append(f"{name}: {type(exc).__name__}: {exc}"[:400])
            info = {"error": type(exc).__name__}
        info["phase_s"] = time.perf_counter() - t0
        print(f"[{name}] " + json.dumps(info, default=float), flush=True)


def _timed(fn):
    """(fn(), wall seconds), waiting for any device arrays in the result."""
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def _max_err(a, b) -> float:
    import numpy as np
    return float(np.max(np.abs(np.asarray(a, np.float64)
                               - np.asarray(b, np.float64))))


def _engine_clean(smoke: Smoke, engine, what: str) -> None:
    """No degraded rung and no error/fallback counter above zero."""
    bad = {k: v for k, v in engine.counters.items()
           if v and (k.startswith("errors:") or k.startswith("breaker_")
                     or k in ("embed_fallbacks", "prefilter_degraded"))}
    smoke.check(not bad, f"{what}: counters {bad}")
    plan = engine.last_plan
    if plan is not None:
        smoke.check(plan.degraded_from == (),
                    f"{what}: degraded_from {plan.degraded_from}")


def _reference_scores(params, cfg, pairs):
    """Dense pure-jnp `pair_score`, bucketed, at "highest" precision."""
    import jax
    import numpy as np

    from repro.core.batching import bucket_pairs
    from repro.core.simgnn import pair_score

    out = np.zeros(len(pairs), np.float32)
    with jax.default_matmul_precision("highest"):
        fn = jax.jit(pair_score)
        for _, (lhs, rhs, idxs) in bucket_pairs(
                pairs, cfg.n_node_labels, allow_oversize=True).items():
            out[idxs] = np.asarray(fn(params, lhs.adj, lhs.feats, lhs.mask,
                                      rhs.adj, rhs.feats, rhs.mask))
    return out


def _kernels_traced(smoke: Smoke, names: tuple, what: str) -> list:
    from repro.kernels.common import TRACED_KERNELS

    traced = sorted({n for n, _ in TRACED_KERNELS})
    interp = sorted({n for n, i in TRACED_KERNELS if i})
    smoke.check(not interp, f"{what}: kernels traced in interpret mode "
                            f"{interp}")
    for n in names:
        smoke.check((n, False) in TRACED_KERNELS,
                    f"{what}: kernel {n} was not compiled")
    return traced


# ------------------------------------------------------------------ phases

def phase_score(smoke: Smoke, params, cfg, seed: int) -> dict:
    from repro.core.engine import PARITY_ATOL_F32, ScoringEngine
    from repro.data.graphs import query_pairs

    pairs = query_pairs(seed, SCORE_PAIRS)
    ref = _reference_scores(params, cfg, pairs)
    engine = ScoringEngine(params, cfg, path="auto", degrade=False)
    calls, walls = [], []
    for lo in range(0, SCORE_PAIRS, SCORE_CALL):
        out, wall = _timed(lambda: engine.score(pairs[lo:lo + SCORE_CALL]))
        calls.append(out)
        walls.append(wall)
        plan = engine.last_plan
        smoke.check(plan.path == "packed_sparse",
                    f"score: auto planned {plan.path}, not packed_sparse")
        _engine_clean(smoke, engine, "score[auto]")
    import numpy as np
    err = {"auto": _max_err(np.concatenate(calls), ref)}
    smoke.check(err["auto"] <= PARITY_ATOL_F32["packed_sparse"],
                f"score: packed_sparse max err {err['auto']:.3g}")
    info = {"pairs": SCORE_PAIRS, "path": engine.last_plan.path,
            "first_call_s": walls[0], "warm_call_s": min(walls[1:])}
    for path in ("packed_dense", "bucketed_mega"):
        forced = ScoringEngine(params, cfg, path=path, degrade=False)
        out, wall = _timed(lambda: forced.score(pairs[:SCORE_CALL]))
        err[path] = _max_err(out, ref[:SCORE_CALL])
        smoke.check(err[path] <= PARITY_ATOL_F32[path],
                    f"score: {path} max err {err[path]:.3g}")
        _engine_clean(smoke, forced, f"score[{path}]")
        info[f"{path}_first_call_s"] = wall
    info["max_abs_err"] = err
    info["kernels"] = _kernels_traced(
        smoke, ("sparse_pair_score", "packed_pair_score", "fused_pair_score"),
        "score")
    return info


def phase_search(smoke: Smoke, params, cfg, seed: int) -> dict:
    import numpy as np

    from repro.data.graphs import random_graph, zipf_corpus
    from repro.serve.search import SimilaritySearchServer

    corpus = zipf_corpus(seed, CORPUS)
    server = SimilaritySearchServer(params, cfg, cache_size=2 * CORPUS,
                                    degrade=False)
    _, index_s = _timed(lambda: server.index(corpus))
    smoke.check(server.stats.failed_embeddings == 0,
                f"search: {server.stats.failed_embeddings} failed embeddings")
    rng = np.random.default_rng(seed + 1)
    recalls, walls = [], []
    for _ in range(QUERY_BATCHES):
        queries = [random_graph(rng) for _ in range(QUERIES_PER_BATCH)]
        two, wall = _timed(lambda: server.search(
            queries, 10, mode="two_stage", prefilter_m=PREFILTER_M))
        walls.append(wall)
        exact = server.search(queries, 10, mode="exact")
        for (ti, _), (ei, _) in zip(two, exact):
            recalls.append(len(set(ti.tolist()) & set(ei.tolist()))
                           / max(len(ei), 1))
    recall = float(np.mean(recalls))
    smoke.check(recall >= MIN_RECALL_AT_10,
                f"search: recall@10 {recall:.4f} < {MIN_RECALL_AT_10}")
    smoke.check(server.stats.prefilter_degraded == 0,
                f"search: prefilter_degraded {server.stats.prefilter_degraded}")
    smoke.check(server.stats.prefilter_queries
                == QUERY_BATCHES * QUERIES_PER_BATCH,
                f"search: {server.stats.prefilter_queries} two-stage queries")
    _engine_clean(smoke, server.engine, "search")
    proxy = server.health()["prefilter"]["proxy"]
    scan = "blocked_topm" if proxy == "linear" else "blocked_topm_ntn"
    return {"corpus": CORPUS, "queries": len(recalls),
            "recall_at_10": recall, "proxy": proxy,
            "prefilter_degraded": server.stats.prefilter_degraded,
            "index_s": index_s, "first_batch_s": walls[0],
            "warm_batch_s": min(walls[1:]),
            "kernels": _kernels_traced(smoke, (scan, "simgnn_head"),
                                       "search")}


def phase_train(smoke: Smoke, params, cfg, seed: int) -> dict:
    import jax
    import numpy as np

    from repro.core.engine import ScoringEngine, tree_all_finite
    from repro.core.simgnn import simgnn_loss
    from repro.data.graphs import pair_stream
    from repro.train.optimizer import adamw_init
    from repro.train.step import build_simgnn_train_step

    engine = ScoringEngine(params, cfg, degrade=False)
    stream = pair_stream(seed, TRAIN_BATCH, max_nodes=cfg.max_nodes)
    first = next(stream)
    # Grad check on the first batch, both sides at "highest" precision.
    with jax.default_matmul_precision("highest"):
        loss, grads = engine.loss_and_grad(first["pairs"], first["target"],
                                           params=params)
        dense = {k: first[k] for k in ("adj1", "feats1", "mask1", "adj2",
                                       "feats2", "mask2", "target")}
        ref_loss, ref_grads = jax.value_and_grad(simgnn_loss)(params, dense)
    smoke.check(engine.last_plan.path == "packed_sparse",
                f"train: planned {engine.last_plan.path}")
    grad_err = max(_max_err(a, b) for a, b in zip(jax.tree.leaves(grads),
                                                  jax.tree.leaves(ref_grads)))
    loss_err = abs(float(loss) - float(ref_loss))
    smoke.check(grad_err <= GRAD_ATOL_F32 and loss_err <= GRAD_ATOL_F32,
                f"train: grad err {grad_err:.3g}, loss err {loss_err:.3g}")

    step_fn = build_simgnn_train_step(engine, peak_lr=1e-3)
    p, opt = params, adamw_init(params)
    losses, walls = [], []
    batch = first
    for _ in range(TRAIN_STEPS):
        (p, opt, metrics), wall = _timed(lambda: step_fn(p, opt, batch))
        walls.append(wall)
        losses.append(float(metrics["loss"]))
        smoke.check("skipped" not in metrics, "train: a step was skipped")
        _engine_clean(smoke, engine, "train")
        batch = next(stream)
    smoke.check(all(np.isfinite(losses)) and tree_all_finite(p),
                f"train: non-finite loss or params {losses}")
    return {"steps": TRAIN_STEPS, "batch": TRAIN_BATCH,
            "path": engine.last_plan.path, "losses": losses,
            "grad_max_abs_err": grad_err, "loss_abs_err": loss_err,
            "first_step_s": walls[0], "warm_step_s": min(walls[1:])}


def phase_sharded(smoke: Smoke, params, cfg, seed: int, n: int) -> dict:
    import jax
    import numpy as np

    from repro.core.engine import ScoringEngine
    from repro.data.graphs import pair_stream, query_pairs
    from repro.distributed.sharding import tile_runtime
    from repro.kernels import ops

    runtime = tile_runtime(n)
    mesh_ids = sorted(d.id for d in runtime.mesh.devices.reshape(-1))
    smoke.check(len(set(mesh_ids)) == n, f"sharded: mesh devices {mesh_ids}")
    one = ScoringEngine(params, cfg, path="auto", degrade=False)
    many = ScoringEngine(params, cfg, path="auto", degrade=False,
                         runtime=runtime)
    pairs = query_pairs(seed, SCORE_PAIRS)
    same, walls = True, []
    for lo in range(0, SCORE_PAIRS, SCORE_CALL):
        sub = pairs[lo:lo + SCORE_CALL]
        ref = one.score(sub)
        out, wall = _timed(lambda: many.score(sub))
        walls.append(wall)
        same &= out.tobytes() == ref.tobytes()
        smoke.check(many.last_plan.devices == n,
                    f"sharded: plan used {many.last_plan.devices} devices")
        occ = many.last_pack_stats.get("device_occupancy", [])
        smoke.check(len(occ) == n and min(occ) > 0,
                    f"sharded: device occupancy {occ}")
        _engine_clean(smoke, many, "sharded score")
    smoke.check(same, "sharded: scores differ from one device")

    # Where the tiles live: the shard_map executor's output has one shard
    # per mesh device, each holding its own span of the tile axis.
    plan = many.plan(pairs[:SCORE_CALL])
    packed, _ = many._pack_sparse(pairs[:SCORE_CALL],
                                  max(8, many.node_budget // 4),
                                  plan.stats.avg_degree)
    t = packed.mask1.shape[0]
    target, tb = ops.sharded_tile_plan(t, many.node_budget, n, sparse=True)
    fn = many._sharded_fn("packed_sparse", n, tb)
    out = fn(params, ops.pad_tiles(packed.kernel_operands(True), target))
    shard_devs = sorted(s.device.id for s in out.addressable_shards)
    spans = sorted({s.data.shape[0] for s in out.addressable_shards})
    smoke.check(shard_devs == mesh_ids and spans == [target // n],
                f"sharded: output shards on {shard_devs}, spans {spans}")

    batch = next(pair_stream(seed, TRAIN_BATCH, max_nodes=cfg.max_nodes))
    l1, g1 = one.loss_and_grad(batch["pairs"], batch["target"])
    ln, gn = many.loss_and_grad(batch["pairs"], batch["target"])
    smoke.check(many.last_plan.devices == n,
                f"sharded train: plan used {many.last_plan.devices} devices")
    _engine_clean(smoke, many, "sharded train")
    loss_err = abs(float(ln) - float(l1))
    grad_err = max(_max_err(a, b) for a, b in zip(jax.tree.leaves(gn),
                                                  jax.tree.leaves(g1)))
    smoke.check(loss_err <= SHARDED_ATOL and grad_err <= SHARDED_ATOL,
                f"sharded train: loss err {loss_err:.3g}, grad err "
                f"{grad_err:.3g}")
    return {"devices": n, "mesh_device_ids": mesh_ids,
            "scores_bitwise_equal": bool(same),
            "output_shard_devices": shard_devs, "tiles": t,
            "tiles_padded": target, "train_loss_abs_err": loss_err,
            "train_grad_max_abs_err": grad_err,
            "first_call_s": walls[0], "warm_call_s": min(walls[1:]),
            "kernels": _kernels_traced(smoke, ("sparse_pair_score",),
                                       "sharded")}


# -------------------------------------------------------------------- main

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"chip_smoke: no repro package under {SRC}; run this script "
              "from a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)

    import jax

    from repro.launch import compile_cache

    cache_dir = compile_cache.enable()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform "
              f"{devices[0].platform!r}); nothing was run", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1

    from repro.configs.simgnn_aids import CONFIG
    from repro.core.simgnn import init_simgnn_params
    from repro.kernels.common import should_interpret

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    print(f"[device] {json.dumps(device)} compile_cache={cache_dir}",
          flush=True)
    smoke = Smoke()
    smoke.check(not should_interpret(), "kernels would run interpreted")
    params = init_simgnn_params(jax.random.PRNGKey(args.seed), CONFIG)
    if args.chips == 1:
        smoke.phase("score", phase_score, params, CONFIG, args.seed)
        smoke.phase("search", phase_search, params, CONFIG, args.seed)
        smoke.phase("train", phase_train, params, CONFIG, args.seed)
    else:
        smoke.phase("sharded", phase_sharded, params, CONFIG, args.seed,
                    args.chips)
    for f in smoke.failures:
        print(f"[fail] {f}", flush=True)
    print(json.dumps({"ok": not smoke.failures, "device": device}),
          flush=True)
    return 0 if not smoke.failures else 1


if __name__ == "__main__":
    sys.exit(main())
