"""Host stage spans and transfer counters of `ScoringEngine.score`
(`core.profile.span`, DESIGN.md §15).

Every executor's call is read back from a real profiler trace: each stage
it runs appears as an `engine.<stage>` span nested inside the call's
`engine.score` span, the number of spans does not grow with the batch (no
per-graph or per-pair spans), and the engine's `h2d_bytes`/`d2h_bytes`
counters are the exact bytes of the arrays copied, computed here from the
packed shapes.
"""

import glob
from collections import Counter

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core.engine import ScoringEngine
from repro.core.profile import SPAN_PREFIX, STAGES
from repro.core.simgnn import SimGNNConfig, init_simgnn_params
from repro.data.graphs import random_graph

CFG = SimGNNConfig()
PARAMS = init_simgnn_params(jax.random.PRNGKey(0), CFG)

COMMON = {"score", "validate", "stats", "select", "to_device", "to_host",
          "wait", "kernel", "record"}
#: the stages each executor's call runs.
WANT = {
    "packed_sparse": COMMON | {"pack", "edges", "unpack"},
    "packed_dense": COMMON | {"pack", "unpack"},
    "bucketed_mega": COMMON | {"pack"},
    # warm cache: every embedding resident, only the head runs.
    "embedding_cache": COMMON | {"graph_keys"},
}


def _pairs(seed, n, *, big=0):
    """`n` AIDS-like pairs of 9-16 nodes (one size bucket), the first `big`
    of them with a 70-node lhs (over the 64-node tile budget)."""
    rng = np.random.default_rng(seed)

    def graph(i):
        size = 70 if i < big else int(rng.integers(9, 17))
        return random_graph(rng, size, avg_degree=2.0)
    return [(graph(i), random_graph(rng, int(rng.integers(9, 17)),
                                    avg_degree=2.0)) for i in range(n)]


def _traced_spans(eng, pairs, tmp_path) -> list[tuple]:
    """(name, start_ns, end_ns, stats) of every `engine.*` span one traced
    `score()` call records."""
    with jax.profiler.trace(str(tmp_path)):
        eng.score(pairs)
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    return [(e.name, e.start_ns, e.end_ns, dict(e.stats))
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host")
            for line in plane.lines for e in line.events
            if e.name.startswith(SPAN_PREFIX)]


def _engine(path):
    eng = ScoringEngine(PARAMS, CFG, path=path)
    if path == "embedding_cache":
        eng.score(_pairs(0, 8))          # every graph resident afterwards
    return eng


@pytest.mark.parametrize("path", sorted(WANT))
def test_every_stage_appears_nested_in_the_call(path, tmp_path):
    spans = _traced_spans(_engine(path), _pairs(0, 8), tmp_path)
    names = {n[len(SPAN_PREFIX):] for n, *_ in spans}
    assert names == WANT[path]
    assert names <= set(STAGES)
    calls = [(a, b) for n, a, b, _ in spans if n == "engine.score"]
    assert len(calls) == 1
    (c0, c1), = calls
    assert all(c0 <= a <= b <= c1 for _, a, b, _ in spans)


def test_the_bucketed_fallback_runs_its_stages_inside_the_call(tmp_path):
    eng = ScoringEngine(PARAMS, CFG, path="packed_sparse")
    pairs = _pairs(1, 8, big=1)
    spans = _traced_spans(eng, pairs, tmp_path)
    assert len(eng.last_plan.over_idx) == 1
    count = Counter(n for n, *_ in spans)
    # one packed-sparse and one bucketed work item, each dispatched and
    # recorded once, in one call.
    assert count["engine.score"] == 1
    assert count["engine.kernel"] == 2
    assert count["engine.record"] == 2
    assert count["engine.pack"] == 2


@pytest.mark.parametrize("path", ["packed_sparse", "packed_dense",
                                  "bucketed_mega"])
def test_span_count_does_not_grow_with_the_batch(path, tmp_path):
    eng = _engine(path)
    few = Counter(n for n, *_ in _traced_spans(eng, _pairs(2, 8),
                                               tmp_path / "few"))
    many = Counter(n for n, *_ in _traced_spans(eng, _pairs(3, 64),
                                                tmp_path / "many"))
    assert few == many
    assert sum(few.values()) <= 20


def _expected_bytes(path, eng, pairs, scores_shape=None) -> tuple[int, int]:
    """(host->device, device->host) bytes of one call, from the shapes."""
    f32 = i32 = 4
    i16 = 2
    if path == "bucketed_mega":
        b, n, labels = len(pairs), 16, CFG.n_node_labels
        side = b * (n * labels * f32 + n * n * f32 + n * f32 + i32 + n * i32)
        return 2 * side, b * f32
    st = eng.last_pack_stats
    t, nb, p = st["n_tiles"], st["node_budget"], st["slots_per_tile"]
    # labels, mask, seg per side; pair_mask, pair_index
    tiles = t * (2 * nb * (i32 + f32 + i32) + p * (f32 + i32))
    dense_adj = t * 2 * nb * nb * f32
    unpack = t * p * (f32 + f32 + i32)      # scores, pair_mask, pair_index
    if path == "packed_dense":
        return tiles + dense_adj, unpack
    # packed_sparse builds its edge lists on the host: the dense adjacency
    # stays there, and nothing of the edge extraction is read back.
    e, e_ov = st["edge_budget"], st["overflow_budget"]
    per_plane = 2 * i16 + 2 * f32            # senders, receivers, w, mask
    edges = 2 * t * (e + e_ov) * per_plane
    return tiles + edges, unpack


@pytest.mark.parametrize("path", ["packed_sparse", "packed_dense",
                                  "bucketed_mega"])
def test_transfer_counters_are_the_bytes_of_the_arrays_copied(path,
                                                              tmp_path):
    eng = ScoringEngine(PARAMS, CFG, path=path)
    pairs = _pairs(4, 12)
    assert eng.health()["counters"] == {"h2d_bytes": 0, "d2h_bytes": 0}
    spans = _traced_spans(eng, pairs, tmp_path)
    h2d, d2h = _expected_bytes(path, eng, pairs)
    assert (eng.counters["h2d_bytes"], eng.counters["d2h_bytes"]) \
        == (h2d, d2h)
    # the same counts ride on the copy spans...
    assert sum(s.get("bytes", 0) for n, _, _, s in spans
               if n == "engine.to_device") == h2d
    assert sum(s.get("bytes", 0) for n, _, _, s in spans
               if n == "engine.to_host") == d2h
    # ...and the counters are cumulative, reported by health().
    eng.score(pairs)
    counters = eng.health()["counters"]
    assert (counters["h2d_bytes"], counters["d2h_bytes"]) \
        == (2 * h2d, 2 * d2h)
