"""General benchmark harness: cell discovery, traffic generation, drivers,
trace reduction, cost model and correctness checks. Everything that belongs
to one configuration, traffic mix, cell or per-layer metric lives in a file
of its own under `bench/configs`, `bench/traffic`, `bench/workloads` and
`bench/metrics`, found by the name `BENCHMARK.json` gives it."""
