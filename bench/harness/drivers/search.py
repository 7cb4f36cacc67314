"""Open-loop top-k similarity search through the program's `MicroBatcher`
in front of `SimilaritySearchServer.search`.

Set-up draws the corpus and a query pool (node counts from the traffic
file's `size_seed`, edges, labels and weights from the run's seed), indexes the corpus
(calibration included), and warms every query count a flush can carry
through embedding, prefilter and rerank. The window sends queries on a
Poisson schedule fixed in the traffic file: the gaps are one fixed draw,
put in another order by each seed, so every seed offers the same load.
Each query is a fresh dict drawn by Zipf from the pool, and is timed from
when it was due until its batch returned. Afterwards a sample of the
answered queries, drawn from the seed, is compared with an exact search
by the plain reference: their embeddings as the program's cache holds
them, the scores of the rows returned, and the recall of the reference's
top k.
"""

from __future__ import annotations

import time
from dataclasses import dataclass

import numpy as np

from harness import cost, graphs
from harness.drivers.common import (bad_counters, fresh, program_config,
                                    quantiles)

#: fixed seed of the arrival gaps; each run's seed only reorders them.
GAP_SEED = 0x5EA2C4


@dataclass
class Query:
    pool_idx: int
    due: float                 # seconds after the window opened
    graph: dict
    sent: float = float("nan")
    done: float = float("nan")
    result: tuple | None = None


class Driver:
    kind = "search"

    def __init__(self, cell, seed: int, *, control: str | None = None,
                 rate_qps: float | None = None):
        self.cell, self.seed, self.control = cell, seed, control
        self.config, self.traffic = cell.config, cell.traffic
        self.rate = float(rate_qps or self.traffic["rate_qps"])

    # ---------------------------------------------------------------- set-up

    def setup(self, compiles) -> dict:
        import jax

        from reference import simgnn as ref

        cfg, tr = self.config, self.traffic
        self.params = ref.init_params(self.seed, cfg)
        jax.block_until_ready(self.params)
        rng = np.random.default_rng(self.seed)
        spec = cfg["graphs"]
        self.corpus = graphs.sized_graphs(rng, spec, tr["corpus_graphs"],
                                          tr["size_seed"])
        self.pool = graphs.sized_graphs(rng, spec, tr["query_pool"],
                                        tr["size_seed"] + 1)
        rank = rng.permutation(len(self.pool))
        p = 1.0 / (rank + 1.0) ** tr["zipf_exponent"]
        self.popularity = p / p.sum()
        self.rng = rng
        self.k, self.m = tr["k"], tr["prefilter_m"]
        self.server = None
        if self.control:
            self._control_index()
        else:
            from repro.serve.search import SimilaritySearchServer

            self.server = SimilaritySearchServer(
                self.params, program_config(cfg), **tr.get("server", {}))
            self.server.index([fresh(g) for g in self.corpus])
            self.search = self._program_search
        self.query_flops = [cost.embed_flops(cfg, *cost.graph_size(g))
                            for g in self.pool]
        warm = self._warm(compiles)
        return {"corpus": graphs.realized(self.corpus),
                "rate_qps": self.rate, **warm}

    def _warm(self, compiles) -> dict:
        """Every query count 1..max_batch through embedding (each node
        bucket, all misses), prefilter and rerank."""
        tr = self.traffic
        spec = self.config["graphs"]
        rng = np.random.default_rng(self.seed ^ 0x3A3A)
        before = compiles.lowered
        if not self.control:
            by_bucket: dict[int, list[dict]] = {}
            need = tr["max_batch"] * (tr["max_batch"] + 1) // 2
            from repro.core.batching import bucket_for
            tries = 0
            buckets = {bucket_for(g["adj"].shape[0], allow_oversize=True)
                       for g in self.corpus + self.pool}
            while (any(len(by_bucket.get(b, [])) < need for b in buckets)
                   and tries < 200 * need):
                g = graphs.graph(rng, spec)
                b = bucket_for(g["adj"].shape[0], allow_oversize=True)
                if b in buckets and len(by_bucket.setdefault(b, [])) < need:
                    by_bucket[b].append(g)
                tries += 1
            for b, gs in sorted(by_bucket.items()):
                lo = 0
                for q in range(1, tr["max_batch"] + 1):
                    if lo + q > len(gs):
                        break
                    self.server.engine.embed_graphs(gs[lo:lo + q])
                    lo += q
        for q in range(1, tr["max_batch"] + 1):
            idx = rng.choice(len(self.pool), q, p=self.popularity)
            self.search([fresh(self.pool[i]) for i in idx])
        return {"warm_lowered": compiles.lowered - before}

    def _program_search(self, graphs_):
        return self.server.search(graphs_, self.k, mode=self.traffic["mode"],
                                  prefilter_m=self.m)

    def _control_index(self):
        from reference import simgnn as ref

        self.control_emb = ref.embed_graphs(
            self.params, self.corpus, self.config["n_node_labels"],
            mode=self.control)
        self.search = self._control_search

    def _control_search(self, graphs_):
        """Exact search by the reference at the control's precision."""
        from reference import simgnn as ref

        out = []
        hq = ref.embed_graphs(self.params, graphs_,
                              self.config["n_node_labels"], block=16,
                              mode=self.control)
        for h in hq:
            s = ref.head_blocks(self.params,
                                np.broadcast_to(h, self.control_emb.shape),
                                self.control_emb, mode=self.control)
            top = np.argsort(-s, kind="stable")[:self.k]
            out.append((top, s[top]))
        return out

    # ---------------------------------------------------------------- window

    def schedule(self, seconds: float) -> list[float]:
        """Due times in [0, seconds): a fixed set of exponential gaps at the
        cell's rate, reordered by the run's seed."""
        n = int(np.ceil(self.rate * seconds * 1.25)) + 16
        gaps = np.random.default_rng(GAP_SEED).exponential(1.0 / self.rate, n)
        gaps = np.random.default_rng(self.seed).permutation(gaps)
        due = np.cumsum(gaps) - gaps[0]
        return [float(t) for t in due if t < seconds]

    def window(self, seconds: float, clock=time.perf_counter,
               sleep=time.sleep) -> None:
        import jax

        from repro.serve.batching import MicroBatcher

        tr = self.traffic
        due = self.schedule(seconds)
        picks = self.rng.choice(len(self.pool), len(due), p=self.popularity)
        self.queries = [Query(int(i), t, fresh(self.pool[i]))
                        for i, t in zip(picks, due)]
        self.flush_walls: list[float] = []
        self.prefilter_s: list[float] = []
        self.rerank_s: list[float] = []
        self.flush_sizes: list[int] = []
        stats = self.server.stats if self.server is not None else None

        def run_batch(batch):
            pf = stats.prefilter_seconds if stats else 0.0
            rr = stats.rerank_seconds if stats else 0.0
            with jax.profiler.TraceAnnotation("bench.search_flush"):
                a = clock()
                results = self.search([q.graph for q in batch])
                b = clock()
            self.flush_walls.append(b - a)
            self.flush_sizes.append(len(batch))
            if stats:
                self.prefilter_s.append(stats.prefilter_seconds - pf)
                self.rerank_s.append(stats.rerank_seconds - rr)
            for q, r in zip(batch, results):
                q.done, q.result = b - t0, r
            return results

        self.batcher = MicroBatcher(run_batch, max_batch=tr["max_batch"],
                                    max_wait_s=tr["max_wait_s"], clock=clock)
        t0 = clock()
        nxt = 0
        n = len(self.queries)
        while True:
            now = clock() - t0
            while nxt < n and self.queries[nxt].due <= now:
                q = self.queries[nxt]
                q.sent = clock() - t0
                self.batcher.submit(q)
                nxt += 1
            self.batcher.poll()
            if nxt >= n:
                self.batcher.flush()
                break
            wait = self.queries[nxt].due - (clock() - t0)
            if wait <= 0:
                continue
            dl = self.batcher.deadline_in()
            if dl is not None:
                wait = min(wait, dl)
            # A floor on the nap: the batcher may report its deadline as
            # due a rounding error before it agrees to flush.
            with jax.profiler.TraceAnnotation("bench.wait_arrival"):
                sleep(max(wait, 20e-6))
        self.window_s = max(seconds, clock() - t0)

    # --------------------------------------------------------------- results

    @property
    def attempted(self) -> int:
        return len(self.queries)

    @property
    def failed(self) -> int:
        return sum(1 for q in self.queries if q.result is None)

    def latencies_ms(self) -> np.ndarray:
        return np.asarray([(q.done - q.due) * 1e3 if q.result is not None
                           else np.inf for q in self.queries])

    def end_to_end(self) -> dict:
        lat = self.latencies_ms()
        return {"search_p95_ms": float(np.percentile(lat, 95))}

    def layer_counters(self) -> dict:
        cfg = self.config
        n = len(self.corpus)
        head = cost.head_flops(cfg)
        done = [q for q in self.queries if q.result is not None]
        useful = sum(self.query_flops[q.pool_idx] + n * head for q in done)
        proxy = getattr(self, "proxy", "ntn_exact")
        per_row = head if proxy == "ntn_exact" else 2.0 * cfg["gcn_dims"][-1]
        f = cfg["gcn_dims"][-1]
        return {"kind": self.kind, "window_s": self.window_s,
                "flushes": len(self.flush_sizes),
                "flushed_queries": sum(self.flush_sizes),
                "prefilter_s": self.prefilter_s, "rerank_s": self.rerank_s,
                "useful_flops": useful,
                "search_wall_s": sum(self.flush_walls),
                "scan_flops": sum(q * n * per_row for q in self.flush_sizes),
                "scan_bytes": sum(4.0 * n * f + 4.0 * q * (f + 2 * self.m)
                                  for q in self.flush_sizes)}

    def record(self) -> dict:
        late = [(q.sent - q.due) * 1e3 for q in self.queries]
        lat = self.latencies_ms()
        out = {"queries": self.attempted, "answered":
               self.attempted - self.failed,
               "rate_qps": self.rate, "offered_qps":
               self.attempted / max(self.window_s, 1e-9),
               "latency_ms": quantiles(lat[np.isfinite(lat)]),
               "generator_late_ms": quantiles(late),
               "flushes": len(self.flush_sizes),
               "mean_flush": (float(np.mean(self.flush_sizes))
                              if self.flush_sizes else 0.0),
               "counters": self.engine_counters}
        if not self.control:
            out.update(proxy=self.proxy,
                       flush_stats=vars(self.batcher.stats).copy(),
                       search_stats=self.search_stats)
        return out

    def release(self) -> None:
        """Keep what the comparison needs, then drop the program's state:
        the sampled queries' embeddings as the window left them in the
        program's cache (a query whose entry was evicted is not compared)."""
        self.checked = self.sample()
        distinct = sorted({q.pool_idx for q in self.checked})
        self.query_emb = {}
        if self.control:
            from reference import simgnn as ref

            self.engine_counters = {}
            emb = ref.embed_graphs(self.params,
                                   [self.pool[i] for i in distinct],
                                   self.config["n_node_labels"], block=16,
                                   mode=self.control)
            self.query_emb = dict(zip(distinct, emb))
        else:
            from repro.core.cache import graph_fingerprint, graph_key

            engine = self.server.engine
            for i in distinct:
                g = fresh(self.pool[i])
                e = engine.cache.get(graph_key(g), graph_fingerprint(g))
                if e is not None:
                    self.query_emb[i] = np.array(e, np.float32)
            self.engine_counters = {k: int(v) for k, v in
                                    engine.counters.items()}
            self.proxy = self.server.health()["prefilter"]["proxy"]
            self.search_stats = self.server.stats.as_dict()
            self.server = self.search = None

    # ----------------------------------------------------------- correctness

    def sample(self) -> list[Query]:
        """Answered queries to compare: up to `check_queries` drawn from the
        seed, unanswered ones counted apart."""
        done = [q for q in self.queries if q.result is not None]
        k = min(len(done), self.traffic["check_queries"])
        pick = np.random.default_rng(self.seed ^ 0xC4EC).choice(
            len(done), k, replace=False)
        return [done[i] for i in sorted(pick)]

    def checks(self) -> list[tuple[str, float, float]]:
        from reference import simgnn as ref

        n_labels = self.config["n_node_labels"]
        sample = self.checked
        distinct = sorted({q.pool_idx for q in sample})
        emb = ref.embed_graphs(self.params, self.corpus, n_labels)
        hq = ref.embed_graphs(self.params, [self.pool[i] for i in distinct],
                              n_labels)
        want = {i: ref.head_blocks(self.params,
                                   np.broadcast_to(h, emb.shape), emb)
                for i, h in zip(distinct, hq)}
        lim = self.cell.limits
        tie = lim["score_max_abs_err"]
        err = 0.0
        hits = total = short = 0
        for q in sample:
            idx, got = q.result
            idx = np.asarray(idx)
            s = want[q.pool_idx]
            k = min(self.k, len(s))
            if len(set(idx.tolist())) < k:
                short += 1
                continue
            kth = np.sort(s)[::-1][k - 1]
            err = max(err, float(np.max(np.abs(
                np.asarray(got, np.float64) - s[idx]))))
            # A returned row counts as a hit where the reference ranks it
            # in the top k, ties within the score limit included.
            hits += int(np.sum(s[idx] >= kth - tie))
            total += k
        recall = hits / total if total else 0.0
        # Query embeddings, relative to each one's largest reference entry.
        ref_q = dict(zip(distinct, hq))
        emb_err = max((float(np.max(np.abs(e - ref_q[i]))
                             / np.max(np.abs(ref_q[i])))
                       for i, e in self.query_emb.items()), default=np.inf)
        bad = bad_counters(self.engine_counters)
        return [("query_embedding_rel_err", emb_err,
                 lim["query_embedding_rel_err"]),
                ("score_max_abs_err", err, lim["score_max_abs_err"]),
                ("recall_shortfall", 1.0 - recall,
                 1.0 - self.traffic["min_recall_at_k"]),
                ("short_answers", float(short), 0.0),
                ("unanswered", float(self.failed), 0.0),
                ("error_counters", float(sum(bad.values())), 0.0)]
