"""The graph generators: deterministic in their seeds, and at the
published averages of each configuration."""

import json
import os

import _paths  # noqa: F401
import numpy as np
import pytest

from harness import graphs


def _spec(name):
    with open(os.path.join(_paths.BENCH, "configs", name + ".json")) as f:
        return json.load(f)["graphs"]


@pytest.mark.parametrize("name", ["simgnn_aids", "simgnn_imdb"])
def test_same_seed_same_graphs(name):
    spec = _spec(name)
    a = graphs.sized_graphs(np.random.default_rng(5), spec, 50, 11)
    b = graphs.sized_graphs(np.random.default_rng(5), spec, 50, 11)
    for x, y in zip(a, b):
        assert np.array_equal(x["adj"], y["adj"])
        assert np.array_equal(x["labels"], y["labels"])


@pytest.mark.parametrize("name", ["simgnn_aids", "simgnn_imdb"])
def test_size_seed_fixes_sizes_and_run_seed_changes_contents(name):
    spec = _spec(name)
    a = graphs.sized_graphs(np.random.default_rng(1), spec, 50, 11)
    b = graphs.sized_graphs(np.random.default_rng(2), spec, 50, 11)
    assert [g["adj"].shape for g in a] == [g["adj"].shape for g in b]
    assert any(not np.array_equal(x["adj"], y["adj"]) for x, y in zip(a, b))


@pytest.mark.parametrize("name", ["simgnn_aids", "simgnn_imdb"])
def test_fixed_graphs_share_their_structure_across_run_seeds(name):
    spec = _spec(name)
    a = graphs.fixed_graphs(np.random.default_rng(1), spec, 50, 11)
    b = graphs.fixed_graphs(np.random.default_rng(2), spec, 50, 11)
    assert all(np.array_equal(x["adj"], y["adj"]) for x, y in zip(a, b))
    same_labels = all(np.array_equal(x["labels"], y["labels"])
                      for x, y in zip(a, b))
    assert same_labels == (spec["n_labels"] == 1)


@pytest.mark.parametrize("name,nodes,edges,node_tol,edge_tol,count", [
    ("simgnn_aids", 25.6, 27.6, 0.5, 0.6, 4000),
    ("simgnn_imdb", 13.0, 65.9, 0.6, 5.0, 4000),
])
def test_realized_averages_match_the_published_ones(name, nodes, edges,
                                                    node_tol, edge_tol,
                                                    count):
    spec = _spec(name)
    gs = [graphs.graph(np.random.default_rng(i), spec) for i in range(count)]
    got = graphs.realized(gs)
    assert abs(got["mean_nodes"] - nodes) <= node_tol
    assert abs(got["mean_edges"] - edges) <= edge_tol
    assert got["min_nodes"] >= spec["nodes"]["min"]
    assert got["max_nodes"] <= spec["nodes"]["max"]


def test_graphs_are_simple_undirected_and_labelled_in_range():
    for name in ("simgnn_aids", "simgnn_imdb"):
        spec = _spec(name)
        for g in graphs.sized_graphs(np.random.default_rng(3), spec, 40, 4):
            adj = g["adj"]
            assert np.array_equal(adj, adj.T)
            assert not np.any(np.diag(adj))
            assert set(np.unique(adj)) <= {0.0, 1.0}
            assert g["labels"].min() >= 0
            assert g["labels"].max() < spec["n_labels"]


def test_molecules_are_connected_and_ego_nets_have_a_hub():
    aids, imdb = _spec("simgnn_aids"), _spec("simgnn_imdb")
    for g in graphs.sized_graphs(np.random.default_rng(4), aids, 30, 5):
        n = g["adj"].shape[0]
        reach = np.eye(n, dtype=bool)
        for _ in range(n):
            reach = reach | ((reach.astype(np.float32) @ g["adj"]) > 0)
        assert reach[0].all()
    for g in graphs.sized_graphs(np.random.default_rng(4), imdb, 30, 5):
        assert g["adj"][0, 1:].all()


def test_edit_pairs_keep_node_counts_and_give_sim_gnn_targets():
    spec = _spec("simgnn_aids")
    pairs, targets = graphs.edit_pairs(np.random.default_rng(0), spec, 20, 8,
                                       np.random.default_rng(1))
    for (a, b), t in zip(pairs, targets):
        assert a["adj"].shape == b["adj"].shape
        n = a["adj"].shape[0]
        k = -np.log(t) * n            # target = exp(-2k / (n + n))
        assert abs(k - round(k)) < 1e-4 and 0 <= round(k) <= 8
