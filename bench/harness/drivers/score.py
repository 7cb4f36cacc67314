"""Closed-loop bulk pair scoring through `ScoringEngine(path="auto").score`.

Set-up draws a pool of independent query pairs and cuts it into calls of
`pairs_per_call`: the node counts and edges come from the traffic file's
`size_seed`, so every run seed does the same work; the labels, the weights
and the call the window starts from come from the run's seed. The window
cycles through those calls, each with fresh request dicts, one call after
the other. Every answer of the window is compared with the plain reference
afterwards.
"""

from __future__ import annotations

import time
from collections import Counter

import numpy as np

from harness import cost, graphs
from harness.drivers.common import (bad_counters, fresh, new_records,
                                    program_config)


#: the least spread of the reference scores that the score error is taken
#: as a share of: a hundredth of the score's range [0, 1].
SPREAD_FLOOR = 0.01


class Driver:
    kind = "score"

    def __init__(self, cell, seed: int, *, control: str | None = None):
        self.cell, self.seed, self.control = cell, seed, control
        self.config, self.traffic = cell.config, cell.traffic

    # ---------------------------------------------------------------- set-up

    def setup(self, compiles) -> dict:
        import jax

        from reference import simgnn as ref

        cfg = self.config
        self.params = ref.init_params(self.seed, cfg)
        jax.block_until_ready(self.params)
        rng = np.random.default_rng(self.seed)
        pool = graphs.independent_pairs(rng, cfg["graphs"],
                                        self.traffic["pool_pairs"],
                                        self.traffic["size_seed"])
        per = self.traffic["pairs_per_call"]
        self.calls = [pool[i:i + per] for i in range(0, len(pool), per)]
        self.first = int(rng.integers(len(self.calls)))
        self.call_flops = [sum(cost.pair_cost(cfg, a, b)[0] for a, b in c)
                           for c in self.calls]
        self.call_bytes = [sum(cost.pair_cost(cfg, a, b)[1] for a, b in c)
                           + cost.param_bytes(cfg) for c in self.calls]
        self.pool_stats = graphs.realized([g for p in pool for g in p])
        if self.control:
            self.score = self._control_score
        else:
            from repro.core.engine import ScoringEngine

            self.engine = ScoringEngine(self.params, program_config(cfg),
                                        path="auto")
            self.score = self.engine.score
        # Warm every shape the window will send: whole passes over the pool
        # until one compiles nothing new (the sparse path's overflow budget
        # only grows, so the second pass settles it).
        passes = []
        for _ in range(4):
            before = compiles.lowered
            for c in self.calls:
                self.score([(fresh(a), fresh(b)) for a, b in c])
            passes.append(compiles.lowered - before)
            if passes[-1] == 0:
                break
        return {"warm_passes_lowered": passes, "pool": self.pool_stats}

    def _control_score(self, pairs):
        from reference import simgnn as ref

        return ref.score_pairs(self.params, pairs,
                               self.config["n_node_labels"], mode=self.control)

    # ---------------------------------------------------------------- window

    def window(self, seconds: float, clock=time.perf_counter) -> None:
        import jax

        rec = None if self.control else self.engine.recorder
        self.outputs: list[tuple[int, np.ndarray]] = []
        self.plan_s: list[float] = []
        self.occupancy: list[float] = []
        self.paths: Counter = Counter()
        self.degraded: list = []
        n = len(self.calls)
        t0 = clock()
        deadline = t0 + seconds
        i = 0
        while True:
            k = (self.first + i) % n
            c = self.calls[k]
            request = [(fresh(a), fresh(b)) for a, b in c]
            before = rec.total_records if rec is not None else 0
            with jax.profiler.TraceAnnotation("bench.score_call"):
                a = clock()
                out = self.score(request)
                b = clock()
            self.outputs.append((k, np.asarray(out)))
            if rec is not None:
                new = new_records(rec, before)
                self.plan_s.append((b - a) - sum(r.wall_s for r in new))
                self.paths.update(r.path for r in new)
                self.degraded.extend(r.degraded_from for r in new
                                     if r.degraded_from)
                stats = self.engine.last_pack_stats
                if stats:
                    self.occupancy.append(0.5 * (stats["occupancy_lhs"]
                                                 + stats["occupancy_rhs"]))
            i += 1
            if b >= deadline:
                break
        self.window_s = b - t0
        self.n_calls = i

    # --------------------------------------------------------------- results

    @property
    def attempted(self) -> int:
        return sum(len(o) for _, o in self.outputs)

    @property
    def failed(self) -> int:
        return int(sum((~np.isfinite(o)).sum() for _, o in self.outputs))

    def end_to_end(self) -> dict:
        return {"pairs_per_s": self.attempted / self.window_s}

    def layer_counters(self) -> dict:
        flops = sum(self.call_flops[k] for k, _ in self.outputs)
        nbytes = sum(self.call_bytes[k] for k, _ in self.outputs)
        return {"kind": self.kind, "window_s": self.window_s,
                "plan_s": self.plan_s, "occupancy": self.occupancy,
                "useful_flops": flops, "useful_bytes": nbytes}

    def record(self) -> dict:
        """What goes on the earlier output lines and into the run's file."""
        out = {"calls": self.n_calls, "pairs": self.attempted,
               "paths": dict(self.paths), "degraded": len(self.degraded),
               "score_errors": getattr(self, "errors", None),
               "pool": self.pool_stats}
        out["counters"] = self.engine_counters
        return out

    def release(self) -> None:
        """Drop the program's state before the reference runs."""
        self.engine_counters = ({} if self.control else
                                {k: int(v) for k, v in
                                 self.engine.counters.items()})
        self.engine = self.score = None

    # ----------------------------------------------------------- correctness

    def checks(self) -> list[tuple[str, float, float]]:
        """(name, value, limit) of every number compared."""
        from reference import simgnn as ref

        want = {k: ref.score_pairs(self.params, self.calls[k],
                                   self.config["n_node_labels"])
                for k in sorted({k for k, _ in self.outputs})}
        d = np.concatenate([np.abs(got.astype(np.float64) - want[k])
                            for k, got in self.outputs])
        d = np.where(np.isfinite(d), d, np.inf)
        # The widest gap swings from seed to seed by a few float32 steps,
        # and a seed whose scores barely spread hides a lower precision;
        # the root mean square error over every answer of the window, as a
        # share of the reference scores' spread (floored), separates the
        # program from the `high` control on every seed read (PERF.md).
        spread = float(np.std(np.concatenate(list(want.values()))))
        self.errors = {"max_abs": float(d.max()),
                       "rms": float(np.sqrt(np.mean(d * d))),
                       "reference_std": spread}
        rel = self.errors["rms"] / max(spread, SPREAD_FLOOR)
        lim = self.cell.limits
        out = [("score_rms_err_rel", rel, lim["score_rms_err_rel"]),
               ("nonfinite_scores", float(self.failed), 0.0),
               ("degraded_plans", float(len(self.degraded)), 0.0)]
        if not self.control:
            bad = bad_counters(self.engine_counters)
            out.append(("error_counters", float(sum(bad.values())), 0.0))
        return out
