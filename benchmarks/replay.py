"""Recorded-trace replay: the measured-planner regression gate
(DESIGN.md §15).

SPA-GCN's crossover points between execution strategies are workload
properties, not constants — so the only durable regression test over all
six engine paths at once is MEASURED: capture a mixed traffic trace
(score paths, the embedding-cached search flow, the train paths), persist
it as a versioned JSONL profile (`core/profile.py`), then REPLAY the same
deterministic workloads against a live engine whose planner runs on the
cost model fitted from that profile.

Phases (one process, so every path is jit-warm before anything is timed):

  capture — forced-path engines share one `TraceRecorder`; each workload
            is run unrecorded first (compile warm-up must not pollute the
            profile), then recorded `reps` times per path. Workloads are
            regenerated from pinned seeds (`data/graphs.py` streams:
            independent search pairs at several sizes x degrees, Zipf
            query batches for the cached path, GED pair batches for the
            train paths), so replay needs no graph serialization — the
            profile stores only shapes and timings.
  replay  — the profile is loaded back through `TraceRecorder.load`
            (garbled lines dropped-and-counted), an auto engine plans
            every score workload with `planner="measured"`, and each
            candidate path's REAL latency is measured on the same warm
            forced engines.

`--check` (CI gate, acceptance criteria of ISSUE 9):
  * the planner is actually warm: every replayed plan carries
    `cost_estimates` (a cold fallback here means capture under-supported
    a candidate path);
  * per-path predicted-vs-measured latency error <= 35% median across
    replayed calls (and the fit's own in-sample residual medape <= 35%
    for every fitted path, train paths included);
  * the planner's chosen path is measured-best, or within 10% of the
    best, on >= 80% of replayed calls;
  * cold-planner fallback: with an empty profile, `planner="measured"`
    plans bit-identically (path AND reason) to `planner="threshold"` on
    every replayed workload, score and train.

Usage:  PYTHONPATH=src python benchmarks/replay.py [--tiny] [--check]
            [--trace replay_profile.jsonl] [--out replay_bench.json]

On this CPU-only container kernels run in interpret mode — absolute times
are the trajectory baseline, not TPU times; the gates compare paths
against each other and the model against its own measurements, so they
hold on any substrate.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

import numpy as np

from repro.configs.simgnn_aids import CONFIG as CFG
from repro.core.engine import TRAIN_PATHS, ScoringEngine
from repro.core.profile import TraceRecorder, fit_cost_model, read_profile
from repro.core.simgnn import init_simgnn_params
from repro.data.graphs import (pair_stream, random_graph, search_pairs,
                               zipf_corpus, zipf_query_stream)

def time_call(fn, *, repeats: int = 2, reduce: str = "min") -> float:
    """Wall-clock seconds over `repeats` blocking calls of a zero-arg
    thunk — the replay harness's measurement primitive (DESIGN.md §15).
    reduce="min" measures *capability* (scheduler noise only ever adds
    time); reduce="median" matches the central tendency of per-call trace
    records, which is what a cost model fitted on them predicts."""
    import jax

    if reduce not in ("min", "median"):
        raise ValueError(f"unknown reduce {reduce!r}")
    times = []
    for _ in range(max(1, repeats)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[0] if reduce == "min" else times[len(times) // 2]


def finish_check(records: list, failures: list, *, bench: str,
                 out: str | None, check: bool):
    """Gate epilogue: appends a `policy: "check"` record carrying the
    verdict, writes the `--out` JSON artifact BEFORE exiting (so CI gets
    the measurements and the exact failure strings even when the gate
    fails), then applies the `--check` exit-code contract. `failures` is
    the caller's (possibly empty) list of human-readable reasons."""
    rec = {"bench": bench, "policy": "check", "checked": bool(check),
           "ok": not failures, "failures": list(failures)}
    records.append(rec)
    print("BENCH " + json.dumps(rec))
    if out:
        with open(out, "w") as f:
            json.dump(records, f, indent=1)
    if check and failures:
        print("CHECK FAILED: " + "; ".join(failures))
        sys.exit(1)
    if check:
        print("CHECK OK")


#: the auto-dispatchable scoring candidates the replay gate measures —
#: exactly the candidate set `ScoringEngine._planner_estimates` prices
#: when no cache keys are hashed.
SCORE_CANDIDATES = ("bucketed_mega", "packed_dense", "packed_sparse")

MAX_MEDIAN_ERR = 0.35       # per-path |pred - measured| / measured, median
MIN_CHOSEN_OK = 0.80        # fraction of calls where chosen is near-best
CHOSEN_MARGIN = 1.10        # "near-best": within 10% of the measured best
TINY_CHOSEN_MARGIN = 1.25   # tiny workloads run ~5 ms, where 10% is inside
                            # scheduler noise between near-tied candidates
TINY_MAX_MEDIAN_ERR = 0.50  # same reason for the error gates: tiny walls
                            # (~3 ms cached-path calls) are timer-noise
                            # dominated; CI's default grid keeps 0.35


def score_workloads(tiny: bool) -> list[dict]:
    """Deterministic scoring workloads, regenerated identically by capture
    and replay: independent-pair batches across sizes x degrees so the
    fitted model sees both the pairs term and the edges term move."""
    sizes = (6, 12, 24) if tiny else (8, 16, 32)
    degrees = (None, 6.0)
    out = []
    for i, n in enumerate(sizes):
        for j, deg in enumerate(degrees):
            out.append({
                "name": f"score_n{n}_deg{deg if deg else 'aids'}",
                "pairs": search_pairs(seed=100 + 10 * i + j, n_pairs=n,
                                      avg_degree=deg)})
    return out


def train_workloads(tiny: bool) -> list[dict]:
    sizes = (4, 8) if tiny else (6, 12, 24)
    out = []
    for i, n in enumerate(sizes):
        batch = next(pair_stream(seed=300 + i, batch=n))
        out.append({"name": f"train_b{n}", "pairs": batch["pairs"],
                    "target": batch["target"]})
    return out


def _detached(engines: dict) -> None:
    for eng in engines.values():
        eng.recorder = None


def _attached(engines: dict, rec: TraceRecorder) -> None:
    for eng in engines.values():
        eng.recorder = rec


def build_measure_engines(params) -> dict:
    """One forced-path engine per scoring candidate. `degrade=False` so a
    measurement can never silently time a different rung than its label;
    `planner="threshold"` so nothing here ever consults the model it is
    generating data for."""
    return {p: ScoringEngine(params, CFG, path=p, validation="off",
                             degrade=False, planner="threshold")
            for p in SCORE_CANDIDATES}


def capture(params, trace_path: str, *, tiny: bool,
            score_engines: dict) -> dict:
    """Record the mixed profile and flush it to `trace_path`; returns
    capture stats for the BENCH record."""
    reps = 3 if tiny else 4
    recorder = TraceRecorder(path=trace_path)
    sws, tws = score_workloads(tiny), train_workloads(tiny)

    # --- score paths. The warm-up call runs UNRECORDED immediately before
    # each workload's recorded reps: it absorbs compilation AND pins the
    # exact compiled shapes the reps will hit (the sparse pack's realized
    # overflow budget ratchets across workloads, so warming everything
    # first would leave later recompiles inside recorded calls — exactly
    # the timing pollution the clean-record rule exists to keep out).
    for eng in score_engines.values():
        for w in sws:
            eng.recorder = None
            eng.score(w["pairs"])
            eng.recorder = recorder
            for _ in range(reps):
                eng.score(w["pairs"])
    _detached(score_engines)

    # --- embedding-cached path: Zipf query batches over a fixed corpus,
    # captured in the path's steady state — the regime the planner prices.
    # Everything shape- or state-cold runs UNRECORDED first: the whole
    # corpus is pre-embedded (so no recorded call pays a corpus miss), the
    # four single-miss embed shapes `(1, bucket)` are pre-compiled (each
    # recorded call embeds exactly its one fresh query; miss batches pad
    # to the miss count, so an unseen count means an XLA compile mid-
    # record), and each stream's first batch warms its head shape.
    cached = ScoringEngine(params, CFG, path="embedding_cache",
                           validation="off", planner="threshold")
    n_corpus = 24 if tiny else 48
    batch_sizes = (8, 16) if tiny else (12, 24)
    rng = np.random.default_rng(0xCAFE)
    cached.recorder = None
    for n in (6, 12, 24, 48):
        cached.embed_graphs([random_graph(rng, n)])
    for si, batch in enumerate(batch_sizes):
        stream = zipf_query_stream(seed=500 + si, batch=batch,
                                   n_corpus=n_corpus)
        cached.recorder = None
        cached.embed_graphs(zipf_corpus(500 + si, n_corpus))
        cached.score(next(stream)["pairs"])
        cached.recorder = recorder
        # 6 tiny batches: 2 streams x 4 would leave exactly min-support
        # records, where one noisy ~3 ms wall swings the in-sample medape
        # past the gate under machine load.
        for _ in range(6 if tiny else 5):
            cached.score(next(stream)["pairs"])

    # --- train paths: forced VJP-capable engines through loss_and_grad,
    # same warm-then-record-per-workload discipline as the score paths.
    t_reps = 4 if tiny else 3
    for path in TRAIN_PATHS:
        eng = ScoringEngine(params, CFG, path=path, validation="off",
                            degrade=False, planner="threshold")
        for w in tws:
            eng.recorder = None
            eng.loss_and_grad(w["pairs"], w["target"])
            eng.recorder = recorder
            for _ in range(t_reps):
                eng.loss_and_grad(w["pairs"], w["target"])

    flushed = recorder.flush()
    return {"records": recorder.total_records, "flushed": flushed,
            "flush_errors": int(recorder.counters["flush_errors"])}


def replay(params, trace_path: str, *, tiny: bool, score_engines: dict,
           records: list, failures: list) -> None:
    """Re-run the captured workloads against the profile-warmed planner
    and append one BENCH record per workload + the model summary."""
    profile, dropped = read_profile(trace_path)
    recorder = TraceRecorder.load(trace_path)
    auto = ScoringEngine(params, CFG, validation="off",
                         planner="measured", recorder=recorder)
    model = fit_cost_model(profile,
                           min_support=ScoringEngine.PLANNER_MIN_SUPPORT)
    snap = model.snapshot()
    records.append({"bench": "replay", "policy": "model",
                    "trace_records": len(profile),
                    "records_dropped": dropped, **snap})
    print("BENCH " + json.dumps(records[-1]))
    max_err = TINY_MAX_MEDIAN_ERR if tiny else MAX_MEDIAN_ERR
    for path, medape in snap["residual_medape"].items():
        if medape > max_err:
            failures.append(f"in-sample residual medape {medape:.2f} > "
                            f"{max_err} on {path}")

    _detached(score_engines)
    per_path_err: dict[str, list] = {p: [] for p in SCORE_CANDIDATES}
    chosen_ok = 0
    sws = score_workloads(tiny)
    for w in sws:
        plan = auto.plan(w["pairs"])
        est = plan.cost_estimates
        if not est:
            failures.append(f"planner cold on replay of {w['name']}: "
                            f"{plan.reason}")
            continue
        measured = {p: time_call(
            lambda p=p: score_engines[p].score(w["pairs"]),
            repeats=5 if tiny else 3, reduce="median")
            for p in est}
        best = min(measured.values())
        margin = TINY_CHOSEN_MARGIN if tiny else CHOSEN_MARGIN
        ok = measured[plan.path] <= margin * best
        chosen_ok += ok
        for p in est:
            per_path_err[p].append(abs(est[p] - measured[p]) / measured[p])
        rec = {"bench": "replay", "workload": w["name"],
               "n_pairs": len(w["pairs"]), "chosen": plan.path,
               "chosen_ok": bool(ok),
               "predicted_s": {p: round(v, 6) for p, v in est.items()},
               "measured_s": {p: round(v, 6)
                              for p, v in measured.items()}}
        records.append(rec)
        print("BENCH " + json.dumps(rec))

    for p, errs in per_path_err.items():
        if not errs:
            continue
        med = float(np.median(errs))
        records.append({"bench": "replay", "policy": "path_error",
                        "path": p, "median_err": round(med, 4),
                        "calls": len(errs)})
        print("BENCH " + json.dumps(records[-1]))
        if med > max_err:
            failures.append(f"median predicted-vs-measured error "
                            f"{med:.2f} > {max_err} on {p}")
    n_planned = sum(1 for r in records
                    if r.get("bench") == "replay" and "chosen" in r)
    if n_planned:
        frac = chosen_ok / n_planned
        records.append({"bench": "replay", "policy": "chosen",
                        "ok_frac": round(frac, 4), "calls": n_planned})
        print("BENCH " + json.dumps(records[-1]))
        if frac < MIN_CHOSEN_OK:
            failures.append(f"planner chose a near-best path on only "
                            f"{frac:.0%} of calls (< {MIN_CHOSEN_OK:.0%})")

    # --- cold fallback: an empty profile must leave the measured planner
    # bit-identical to the threshold rules on every replayed workload.
    cold_m = ScoringEngine(params, CFG, validation="off",
                           planner="measured")
    cold_t = ScoringEngine(params, CFG, validation="off",
                           planner="threshold")
    mismatches = []
    for w in sws:
        pm, pt = cold_m.plan(w["pairs"]), cold_t.plan(w["pairs"])
        if (pm.path, pm.reason) != (pt.path, pt.reason):
            mismatches.append(f"{w['name']}: {pm.path} != {pt.path}")
    for w in train_workloads(tiny):
        pm = cold_m.plan(w["pairs"], train=True)
        pt = cold_t.plan(w["pairs"], train=True)
        if (pm.path, pm.reason) != (pt.path, pt.reason):
            mismatches.append(f"{w['name']}: {pm.path} != {pt.path}")
    records.append({"bench": "replay", "policy": "cold_fallback",
                    "mismatches": mismatches})
    print("BENCH " + json.dumps(records[-1]))
    if mismatches:
        failures.append("cold planner diverged from threshold rules: "
                        + "; ".join(mismatches))


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--tiny", action="store_true",
                    help="smaller workloads (CI smoke / laptops)")
    ap.add_argument("--check", action="store_true",
                    help="exit non-zero when a replay gate fails")
    ap.add_argument("--trace", default=None,
                    help="profile JSONL path (default: a temp file; pass "
                         "a path to keep the captured profile)")
    ap.add_argument("--out", default=None, help="JSON artifact path")
    args = ap.parse_args(argv)

    import jax
    params = init_simgnn_params(jax.random.PRNGKey(0), CFG)
    tmp = None
    trace = args.trace
    if trace is None:
        tmp = tempfile.mkdtemp(prefix="replay_profile_")
        trace = os.path.join(tmp, "profile.jsonl")

    records: list = []
    failures: list = []
    score_engines = build_measure_engines(params)
    cap = capture(params, trace, tiny=args.tiny,
                  score_engines=score_engines)
    records.append({"bench": "replay", "policy": "capture", **cap})
    print("BENCH " + json.dumps(records[-1]))
    if cap["flush_errors"]:
        failures.append(f"profile flush failed {cap['flush_errors']}x")
    replay(params, trace, tiny=args.tiny, score_engines=score_engines,
           records=records, failures=failures)
    finish_check(records, failures, bench="replay", out=args.out,
                 check=args.check)


if __name__ == "__main__":
    main()
