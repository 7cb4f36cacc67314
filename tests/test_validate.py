"""Batched input validation (`core.validate.validate_pairs`, DESIGN.md §12).

`validate_pairs` groups a call's graphs by node count and dtype and checks
each group's values in whole-array reductions; `graph_problems` re-checks
alone whatever the grouped pass cannot clear. These tests hold it to a
per-graph oracle built from `graph_problems` (the same `valid_idx` and the
same `InvalidGraph` records, element for element) over random batches that
mix sound graphs of 1-89 nodes with every fault the quarantine knows, and
pin the `validate_exact_graphs` counter that says how often the exact
fallback ran.
"""

import jax
import numpy as np
import pytest

from repro.core.engine import ScoringEngine
from repro.core.simgnn import SimGNNConfig, init_simgnn_params
from repro.core.validate import InvalidGraph, graph_problems, validate_pairs
from repro.data.graphs import search_pairs

N_LABELS = SimGNNConfig().n_node_labels


def oracle(pairs, n_labels):
    """`validate_pairs` as one `graph_problems` call per distinct graph
    object, in pair order."""
    memo: dict = {}
    records, valid = [], []
    for i, pair in enumerate(pairs):
        if not isinstance(pair, (tuple, list)) or len(pair) != 2:
            records.append(InvalidGraph(i, 0, ("not a (g1, g2) pair",)))
            continue
        ok = True
        for side, g in enumerate(pair):
            if id(g) not in memo:
                memo[id(g)] = graph_problems(g, n_labels=n_labels)
            if memo[id(g)]:
                ok = False
                records.append(InvalidGraph(i, side, tuple(memo[id(g)])))
        if ok:
            valid.append(i)
    return np.asarray(valid, np.int64), tuple(records)


def assert_matches_oracle(pairs, n_labels=N_LABELS):
    valid, records = validate_pairs(pairs, n_labels=n_labels)
    want_valid, want_records = oracle(pairs, n_labels)
    assert valid.dtype == np.int64
    np.testing.assert_array_equal(valid, want_valid)
    assert records == want_records
    return valid, records


def sound(rng, n, dtype=np.float32):
    """A random undirected hollow graph of `n` nodes with labels."""
    upper = np.triu(rng.random((n, n)) < min(1.0, 3.0 / max(n, 1)), 1)
    adj = (upper | upper.T).astype(dtype)
    return {"adj": adj,
            "labels": rng.integers(0, N_LABELS, n).astype(np.int32)}


def _set_sym(g, i, j, v):
    g["adj"][i, j] = g["adj"][j, i] = v


#: graph -> faulty graph; every fault of `test_faults.py::
#: test_graph_problems_catches` first, then the forms the grouped pass
#: hands to `graph_problems` whole.
FAULTS = {
    "nan": lambda g: g["adj"].__setitem__((0, 1), np.nan),
    "asymmetric": lambda g: (g["adj"].__setitem__((0, 1), 1.0),
                             g["adj"].__setitem__((1, 0), 0.0)),
    "self_loop": lambda g: g["adj"].__setitem__((0, 0), 1.0),
    "non_binary": lambda g: _set_sym(g, 0, 1, 2.0),
    "ragged_labels": lambda g: g.__setitem__("labels", g["labels"][:-1]),
    "label_out_of_range": lambda g: g["labels"].__setitem__(0, N_LABELS),
    "non_square": lambda g: g.__setitem__("adj", g["adj"][:1]),
    "empty": lambda g: g.__setitem__("adj", np.zeros((0, 0), np.float32)),
    "inf": lambda g: _set_sym(g, 0, 1, np.inf),
    "minus_one": lambda g: _set_sym(g, 0, 1, -1.0),
    "half": lambda g: _set_sym(g, 0, 1, 0.5),
    "object_adj": lambda g: g.__setitem__("adj", g["adj"].astype(object)),
    "string_adj": lambda g: g.__setitem__("adj", g["adj"].astype(str)),
    "one_d_adj": lambda g: g.__setitem__("adj", g["adj"][0]),
    "ragged_list_adj": lambda g: g.__setitem__("adj", [[0, 1], [1]]),
    "float_labels": lambda g: g.__setitem__(
        "labels", g["labels"].astype(np.float32)),
    "negative_labels": lambda g: g["labels"].__setitem__(0, -1),
    "bool_labels": lambda g: g.__setitem__(
        "labels", g["labels"].astype(bool)),
    "huge_uint_labels": lambda g: g.__setitem__(
        "labels", np.full(len(g["labels"]), 2**63 + 5, np.uint64)),
    "none_labels": lambda g: g.__setitem__("labels", None),
    "two_d_labels": lambda g: g.__setitem__("labels", g["labels"][None]),
    "no_adj": lambda g: g.pop("adj"),
    "nan_and_bad_label": lambda g: (g["adj"].__setitem__((0, 1), np.nan),
                                    g["labels"].__setitem__(0, -3)),
}
#: a sound graph in a form the grouped pass leaves to `graph_problems`.
COMPLEX = {"complex_adj": lambda g: g.__setitem__(
    "adj", g["adj"].astype(np.complex64))}
#: sides that are not graph dicts at all.
NOT_GRAPHS = {"none": None, "string": "graph", "list": [[0, 1], [1, 0]],
              "int": 7}


def faulty(rng, name, n):
    g = sound(rng, max(n, 2))
    {**FAULTS, **COMPLEX}[name](g)
    return g


def variant(rng, n):
    """A sound graph in one of the accepted forms: float32/float64/int/
    bool/uint8 or list adjacency, int32/int64/uint8 labels or none."""
    g = sound(rng, n)
    form = rng.integers(7)
    if form == 1:
        g["adj"] = g["adj"].astype(np.float64)
    elif form == 2:
        g["adj"] = g["adj"].astype(np.int64)
    elif form == 3:
        g["adj"] = g["adj"].astype(bool)
    elif form == 4:
        g["adj"] = g["adj"].astype(np.uint8)
    elif form == 5:
        g["adj"] = g["adj"].tolist()
    label_form = rng.integers(5)
    if label_form == 1:
        g["labels"] = g["labels"].astype(np.int64)
    elif label_form == 2:
        g["labels"] = g["labels"].astype(np.uint8)
    elif label_form == 3:
        del g["labels"]
    return g


def random_batch(seed, n_pairs):
    """Random pairs mixing sound graphs of 1-89 nodes, every fault, non-dict
    sides, malformed pairs and repeated dicts (1-vs-N)."""
    rng = np.random.default_rng(seed)
    names = sorted(FAULTS) + sorted(COMPLEX)
    seen: list = []
    pairs: list = []

    def side():
        r = rng.random()
        if r < 0.15 and seen:
            return seen[rng.integers(len(seen))]
        if r < 0.3:
            g = faulty(rng, names[rng.integers(len(names))],
                       int(rng.integers(2, 90)))
        elif r < 0.33:
            return list(NOT_GRAPHS.values())[rng.integers(len(NOT_GRAPHS))]
        else:
            g = variant(rng, int(rng.integers(1, 90)))
        seen.append(g)
        return g

    for _ in range(n_pairs):
        r = rng.random()
        if r < 0.03:
            pairs.append((side(), side(), side()))
        elif r < 0.05:
            pairs.append(side())
        elif r < 0.07:
            pairs.append([side(), side()])
        else:
            pairs.append((side(), side()))
    return pairs


@pytest.mark.parametrize("seed", range(16))
def test_random_batches_match_oracle(seed):
    n_pairs = int(np.random.default_rng(seed).integers(1, 80))
    assert_matches_oracle(random_batch(seed, n_pairs))


@pytest.mark.parametrize("seed", range(4))
def test_random_batches_match_oracle_without_label_bound(seed):
    assert_matches_oracle(random_batch(100 + seed, 40), n_labels=None)


@pytest.mark.parametrize("name", sorted(FAULTS))
def test_fault_among_same_size_graphs(name):
    """One faulty graph inside a group of sound graphs of its size and
    dtype: only it is quarantined, with `graph_problems`' reasons."""
    rng = np.random.default_rng(sorted(FAULTS).index(name))
    n = 11
    pairs = [(sound(rng, n), sound(rng, n)) for _ in range(6)]
    bad = faulty(rng, name, n)
    pairs.insert(3, (sound(rng, n), bad))
    valid, records = assert_matches_oracle(pairs)
    assert len(valid) == 6
    assert [(r.pair, r.side) for r in records] == [(3, 1)]
    assert records[0].reasons == tuple(graph_problems(bad,
                                                      n_labels=N_LABELS))


@pytest.mark.parametrize("name", sorted(NOT_GRAPHS))
def test_non_graph_sides(name):
    rng = np.random.default_rng(3)
    pairs = [(sound(rng, 5), NOT_GRAPHS[name]), (sound(rng, 5),) * 2]
    valid, records = assert_matches_oracle(pairs)
    np.testing.assert_array_equal(valid, [1])
    assert records[0].reasons == ("missing adjacency ('adj')",)


@pytest.mark.parametrize("pair", [
    "three_tuple", "single_dict", "none", "empty_tuple"])
def test_malformed_pairs(pair):
    rng = np.random.default_rng(4)
    g = sound(rng, 6)
    bad = {"three_tuple": (g, g, g), "single_dict": g, "none": None,
           "empty_tuple": ()}[pair]
    valid, records = assert_matches_oracle([(g, g), bad, (g, sound(rng, 6))])
    np.testing.assert_array_equal(valid, [0, 2])
    assert records == (InvalidGraph(1, 0, ("not a (g1, g2) pair",)),)


@pytest.mark.parametrize("n", [1, 2, 13, 64, 89])
@pytest.mark.parametrize("dtype", [np.float32, np.float64, np.int32, bool])
def test_sound_sizes_and_dtypes(n, dtype):
    """Sound graphs of one size and dtype clear the grouped pass with no
    exact check, and a planted self loop in one is still caught."""
    rng = np.random.default_rng(n)
    pairs = [(sound(rng, n, dtype), sound(rng, n, dtype)) for _ in range(5)]
    stats: dict = {}
    valid, records = validate_pairs(pairs, n_labels=N_LABELS, stats=stats)
    np.testing.assert_array_equal(valid, np.arange(5))
    assert records == () and stats["validate_exact_graphs"] == 0
    pairs[2][0]["adj"][0, 0] = 1
    assert_matches_oracle(pairs)


def test_complex_adjacency_checked_exactly():
    """A complex 0/1 adjacency passes `graph_problems`; the grouped pass
    leaves it to the exact check, which the counter shows."""
    rng = np.random.default_rng(8)
    pairs = [(sound(rng, 7), faulty(rng, "complex_adj", 7)),
             (sound(rng, 7), sound(rng, 7))]
    stats: dict = {}
    valid, records = validate_pairs(pairs, n_labels=N_LABELS, stats=stats)
    np.testing.assert_array_equal(valid, [0, 1])
    assert records == () and stats["validate_exact_graphs"] == 1
    assert_matches_oracle(pairs)


def test_repeated_query_checked_once():
    """1-vs-N: a repeated query dict is one graph; a faulty one yields one
    record per pair it appears in."""
    rng = np.random.default_rng(5)
    query = sound(rng, 9)
    pairs = [(query, sound(rng, int(rng.integers(1, 30))))
             for _ in range(20)]
    stats: dict = {}
    validate_pairs(pairs, n_labels=N_LABELS, stats=stats)
    assert stats["validate_exact_graphs"] == 0
    query["adj"][0, 1] = 2.0
    stats.clear()
    valid, records = assert_matches_oracle(pairs)
    assert len(valid) == 0 and len(records) == 20
    validate_pairs(pairs, n_labels=N_LABELS, stats=stats)
    assert stats["validate_exact_graphs"] == 1


def test_empty_call():
    valid, records = validate_pairs([], n_labels=N_LABELS)
    assert valid.dtype == np.int64 and valid.size == 0 and records == ()


# ---------------------------------------------------- the fallback counter

def test_exact_counter_zero_on_clean_batch():
    pairs = search_pairs(0, 1000, avg_degree=2.1)
    stats: dict = {}
    valid, records = validate_pairs(pairs, n_labels=N_LABELS, stats=stats)
    np.testing.assert_array_equal(valid, np.arange(1000))
    assert records == ()
    assert stats == {"validate_exact_graphs": 0}


def test_exact_counter_counts_flagged_graphs():
    """Planted faults: the counter is the number of distinct graphs the
    grouped pass flagged (a graph with two faults counts once), and it adds
    up over calls."""
    pairs = [(dict(a), dict(b)) for a, b in search_pairs(1, 1000,
                                                         avg_degree=2.1)]
    rng = np.random.default_rng(6)
    planted = {3: "nan", 500: "label_out_of_range", 999: "nan_and_bad_label"}
    for i, name in planted.items():
        n = pairs[i][1]["adj"].shape[0]
        pairs[i] = (pairs[i][0], faulty(rng, name, n))
    pairs[7] = (pairs[7][0], None)
    stats: dict = {"validate_exact_graphs": 2}
    valid, records = assert_matches_oracle(pairs)
    validate_pairs(pairs, n_labels=N_LABELS, stats=stats)
    assert stats["validate_exact_graphs"] == 2 + 4
    assert sorted(r.pair for r in records) == [3, 7, 500, 999]
    assert len(valid) == 996


def test_health_reports_exact_counter():
    cfg = SimGNNConfig()
    eng = ScoringEngine(init_simgnn_params(jax.random.PRNGKey(0), cfg), cfg,
                        path="reference")
    pairs = search_pairs(2, 8, avg_degree=2.1)
    eng.score(pairs)
    assert eng.health()["counters"]["validate_exact_graphs"] == 0
    bad = {"adj": np.full((4, 4), np.nan, np.float32),
           "labels": np.zeros(4, np.int32)}
    out = eng.score([(bad, pairs[0][1])] + pairs[1:])
    assert np.isnan(out[0])
    assert eng.health()["counters"]["validate_exact_graphs"] == 1
