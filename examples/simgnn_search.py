"""The paper's end-to-end workload: a graph-similarity search service.

Streams query pairs (AIDS-like synthetic compounds), scores them through the
unified scoring engine (core/engine.py, DESIGN.md §9) and reports throughput
— the queries/s metric of paper Tables 5/6 and Fig. 11. The engine measures
each batch's density and picks a path (packed-sparse on the AIDS-like
default stream); `--path` forces any of the six paths, `--avg-degree`
changes the stream's sparsity to see the dispatch flip.

`--topk` switches to the 1-vs-N service (DESIGN.md §10): index a fixed
corpus once through `serve.search.SimilaritySearchServer`, then serve
top-k queries from the Zipf-skewed stream — each query pays one embedding
plus the fused NTN+FCN head over the corpus, and the report shows the
cache hit rate and per-stage time split.

`--mode two_stage` (with `--topk`) serves through the blocked streaming
top-M prefilter + exact rerank instead of the full scan (DESIGN.md §14);
`--topm` sets the shortlist size M. The report adds the per-stage
prefilter/gather/rerank split, the calibration ladder's chosen proxy,
and the sampled recall vs the exact scan (every 4th query is also served
exactly and the top-k overlap recorded).

    PYTHONPATH=src python examples/simgnn_search.py --queries 2000 --batch 256
    PYTHONPATH=src python examples/simgnn_search.py --kernels --path auto
    PYTHONPATH=src python examples/simgnn_search.py --topk 5 --corpus 256
    PYTHONPATH=src python examples/simgnn_search.py --topk 5 --corpus 4096 \
        --mode two_stage --topm 64
"""

import argparse
import time

import jax

from repro.configs.simgnn_aids import CONFIG as CFG
from repro.core.engine import PATHS
from repro.core.simgnn import init_simgnn_params
from repro.data.graphs import query_pairs, search_pairs, zipf_corpus, \
    zipf_query_stream
from repro.launch import compile_cache
from repro.serve.batching import simgnn_query_server
from repro.serve.search import SimilaritySearchServer


def main():
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--queries", type=int, default=2000)
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--kernels", action="store_true",
                    help="use the fused Pallas paths (interpret mode on CPU)")
    ap.add_argument("--path", default=None, choices=("auto",) + PATHS,
                    help="force a scoring path (default: flags -> engine)")
    ap.add_argument("--avg-degree", type=float, default=None,
                    help="stream degree knob (AIDS-like ~2.1 default); "
                         "switches to the independent-size search stream")
    ap.add_argument("--topk", type=int, default=None,
                    help="1-vs-N mode: index a corpus once, serve top-k "
                         "queries through the embedding cache (§10)")
    ap.add_argument("--corpus", type=int, default=256,
                    help="corpus size for --topk mode")
    ap.add_argument("--mode", default="exact",
                    choices=("exact", "two_stage"),
                    help="--topk query path: exact full-head scan, or the "
                         "blocked top-M prefilter + exact rerank (§14)")
    ap.add_argument("--topm", type=int, default=64,
                    help="two_stage shortlist size M (clamped to corpus)")
    ap.add_argument("--index-dir", default=None,
                    help="persist/reload the corpus index here (§13): "
                         "loads the verified shard store if present "
                         "(selectively re-embedding bad shards), else "
                         "builds the index once and saves it")
    args = ap.parse_args()

    params = init_simgnn_params(jax.random.PRNGKey(0), CFG)
    if args.topk is not None:
        run_topk(params, args)
        return
    if args.avg_degree is None:
        pairs = query_pairs(seed=1, n_pairs=args.queries)
    else:
        pairs = search_pairs(seed=1, n_pairs=args.queries,
                             avg_degree=args.avg_degree)
    score = simgnn_query_server(params, CFG, use_kernels=args.kernels,
                                path=args.path)

    # warmup (compile the engine's executables for this shape set)
    score(pairs[: args.batch])
    plan = score.last_plan
    print(f"engine plan: path={plan.path} ({plan.reason}); "
          f"{len(plan.fit_idx)} packed / {len(plan.over_idx)} bucketed")

    t0 = time.time()
    results = []
    for i in range(0, len(pairs), args.batch):
        results.append(score(pairs[i:i + args.batch]))
    dt = time.time() - t0
    qps = len(pairs) / dt
    print(f"scored {len(pairs)} queries in {dt:.2f}s -> {qps:,.0f} query/s "
          f"(batch={args.batch}, kernels={args.kernels}, "
          f"path={score.last_plan.path})")
    if score.last_pack_stats:
        st = score.last_pack_stats
        print(f"last pack: {st['n_tiles']} tiles, occupancy "
              f"{st['occupancy_lhs']:.2f}/{st['occupancy_rhs']:.2f}"
              + (f", edge occupancy {st['edge_occupancy']:.2f}"
                 if "edge_occupancy" in st else ""))
    print(f"first scores: {[f'{s:.3f}' for s in results[0][:6]]}")


def run_topk(params, args):
    """1-vs-N similarity search through the embedding cache (§10), with
    optional durable-index persist/reload (§13) and the two-stage
    prefilter+rerank query path (§14)."""
    from repro.core.store import StoreError

    two_stage = args.mode == "two_stage"
    server = SimilaritySearchServer(
        params, CFG, embed_with_kernels=args.kernels,
        # Sampled recall: every 4th two-stage query is ALSO served
        # exactly and the top-k overlap recorded on stats (§14).
        recall_sample_every=4 if two_stage else 0)
    corpus = zipf_corpus(seed=1, n_corpus=args.corpus,
                         avg_degree=args.avg_degree)
    loaded = False
    if args.index_dir:
        t0 = time.time()
        try:
            server.load(args.index_dir, corpus)
            st = server.stats
            print(f"loaded persisted index from {args.index_dir} in "
                  f"{time.time() - t0:.2f}s ({st.shards_loaded} shards "
                  f"verified, {st.shards_recovered} recovered, "
                  f"{st.rows_reembedded} rows re-embedded)")
            loaded = True
        except StoreError as exc:
            print(f"persisted index unusable ({exc}); rebuilding")
    if not loaded:
        t0 = time.time()
        server.index(corpus)
        print(f"indexed {len(corpus)} corpus graphs in "
              f"{time.time() - t0:.2f}s (embeddings resident, LRU "
              f"{server.engine.cache.stats()['size']} entries)")
        if args.index_dir:
            server.save(args.index_dir)
            print(f"saved index shards + manifest to {args.index_dir}")

    stream = zipf_query_stream(seed=1, batch=args.batch,
                               n_corpus=args.corpus,
                               avg_degree=args.avg_degree)
    n_queries = max(1, args.queries // args.batch)
    kw = ({"mode": "two_stage", "prefilter_m": args.topm}
          if two_stage else {})
    server.topk(next(stream)["query"], k=args.topk, **kw)  # compile warmup
    t0 = time.time()
    last = None
    for _ in range(n_queries):
        last = server.topk(next(stream)["query"], k=args.topk, **kw)
    dt = time.time() - t0
    st = server.stats
    pairs_s = st.pairs_scored / dt if dt else float("inf")
    print(f"served {n_queries} {args.mode} top-{args.topk} queries vs "
          f"corpus of {args.corpus} in {dt:.2f}s -> "
          f"{n_queries / dt:,.1f} query/s ({pairs_s:,.0f} pair-scores/s)")
    if two_stage:
        pf = server.health()["prefilter"]
        busy = (st.embed_seconds + st.prefilter_seconds + st.gather_seconds
                + st.rerank_seconds + st.topk_seconds)
        if busy:
            print(f"stage split: embed {st.embed_seconds / busy:.0%}, "
                  f"prefilter {st.prefilter_seconds / busy:.0%}, "
                  f"gather {st.gather_seconds / busy:.0%}, "
                  f"rerank {st.rerank_seconds / busy:.0%}, "
                  f"topk {st.topk_seconds / busy:.0%} "
                  f"(M={args.topm}, block {pf['block_cols']}, "
                  f"proxy {pf['proxy']})")
        if st.recall_samples:
            print(f"sampled recall vs exact: {st.recall_mean:.4f} over "
                  f"{st.recall_samples} samples "
                  f"({st.prefilter_degraded} degraded to exact)")
    else:
        busy = st.embed_seconds + st.head_seconds + st.topk_seconds
        if busy:
            # Corpus embeddings are served from the resident index matrix,
            # so the LRU hit rate only moves when clients repeat queries.
            print(f"stage split: embed {st.embed_seconds / busy:.0%}, "
                  f"head {st.head_seconds / busy:.0%}, "
                  f"topk {st.topk_seconds / busy:.0%}; "
                  f"repeated-query hit rate {server.hit_rate:.0%}")
    idx, scores = last
    print("top results: " + ", ".join(
        f"#{i}={s:.3f}" for i, s in zip(idx, scores)))


if __name__ == "__main__":
    main()
