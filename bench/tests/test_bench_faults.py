"""A whole run of each cell on the CPU at a test size, with the check for a
chip skipped: the sound program comes out correct, and the control (the
reference at `high` precision in the program's place) and each fault the
cell can have, planted in the timed path, come out not correct."""

import io
import time

import _paths  # noqa: F401
import numpy as np
import pytest

from harness import spec
from harness.runner import run


def jax_highest():
    import jax

    return jax.default_matmul_precision("highest")

SMALL = {
    "aids_gcn_nobias_score_b1000": {"pairs_per_call": 16, "pool_pairs": 32},
    "imdb_gcn_nobias_score_b1000": {"pairs_per_call": 16, "pool_pairs": 32},
    "aids_search_2stage": {"corpus_graphs": 300, "query_pool": 24,
                           "rate_qps": 40, "max_batch": 4,
                           "check_queries": 8},
}
#: the control needs no program, so the score cells run it at their own
#: size (the whole pool of 10,000 pairs).
CONTROL = {
    "aids_gcn_nobias_score_b1000": {},
    "imdb_gcn_nobias_score_b1000": {},
    "aids_search_2stage": SMALL["aids_search_2stage"],
}


#: the search cell is not in BENCHMARK.json (the program is at fault on
#: the chip, PERF.md); its driver is held to these limits here.
SEARCH_LIMITS = {"query_embedding_rel_err": 1e-6, "score_max_abs_err": 1e-6}


def _cell(name):
    if name == "aids_search_2stage":
        cell = spec.make_cell(name, "simgnn_aids_gcn_nobias",
                              "search_2stage")
        cell.limits = dict(SEARCH_LIMITS)
        return cell
    return spec.load_cell(name)


def _run(cell_name, tmp_path, traffic, control=None, seconds=0.3,
         config=None):
    cell = _cell(cell_name)
    if config:
        cell = spec.with_config(cell, config)
    cell.traffic.update(traffic)
    return run(cell, 2**31 + 17, seconds, False, t_start=time.perf_counter(),
               control=control, require_device=False, cache=False,
               runs_dir=str(tmp_path), log=io.StringIO())


def _failed_checks(result):
    return {k for k, v in result["checks"].items()
            if not v["value"] <= v["limit"]}


@pytest.mark.parametrize("cell", sorted(SMALL))
def test_sound_program_is_correct(cell, tmp_path):
    result = _run(cell, tmp_path, SMALL[cell])
    assert result["correct"], result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert list(result)[-1] == "checks"


@pytest.mark.parametrize("cell", sorted(CONTROL))
def test_control_at_high_precision_is_not_correct(cell, tmp_path):
    result = _run(cell, tmp_path, CONTROL[cell], control="high")
    assert not result["correct"]


def _plant_in_score(monkeypatch, fault):
    from repro.core.engine import ScoringEngine

    sound = ScoringEngine.score

    def broken(self, pairs):
        if fault == "altered":
            out = sound(self, pairs)
            out[len(out) // 2] += 1e-3
            return out
        half = len(pairs) // 2           # score half, fill in their mean
        out = np.empty(len(pairs), np.float32)
        out[:half] = sound(self, pairs[:half])
        out[half:] = out[:half].mean()
        return out

    monkeypatch.setattr(ScoringEngine, "score", broken)


@pytest.mark.parametrize("fault", ["altered", "half_batch"])
@pytest.mark.parametrize("cell", ["aids_gcn_nobias_score_b1000", "imdb_gcn_nobias_score_b1000"])
def test_score_faults_are_not_correct(cell, fault, tmp_path, monkeypatch):
    _plant_in_score(monkeypatch, fault)
    result = _run(cell, tmp_path, SMALL[cell])
    assert not result["correct"]
    assert "score_rms_err_rel" in _failed_checks(result)


@pytest.mark.parametrize("fault", ["altered_score", "altered_row",
                                   "half_batch"])
def test_search_faults_are_not_correct(fault, tmp_path, monkeypatch):
    from repro.serve.search import SimilaritySearchServer

    sound = SimilaritySearchServer.search

    def broken(self, queries, k=10, **kw):
        if fault == "half_batch":
            # Serve half the flush, answer the rest with the first answer.
            half = max(1, len(queries) // 2)
            out = sound(self, queries[:half], k, **kw)
            return out + [out[0]] * (len(queries) - half)
        out = sound(self, queries, k, **kw)
        idx, s = out[0]
        if fault == "altered_score":
            s = s.copy()
            s[0] += 1e-3
        else:
            idx = idx.copy()
            idx[0] = (idx[0] + len(self.corpus) // 2) % len(self.corpus)
        return [(idx, s)] + out[1:]

    monkeypatch.setattr(SimilaritySearchServer, "search", broken)
    result = _run("aids_search_2stage", tmp_path,
                  dict(SMALL["aids_search_2stage"], check_queries=64),
                  seconds=1.0)
    assert not result["correct"]


def _bias_first_scores(self, pairs):
    """The reference with each GCN bias added before aggregation,
    A'(HW + b), in the program's place."""
    import jax
    import jax.numpy as jnp

    from reference import simgnn as ref

    hi = jax.lax.Precision.HIGHEST

    def embed(params, adj, feats, mask):
        a = ref.normalized_adjacency(adj, mask)
        h = feats
        for layer in params["gcn"]:
            hw = jnp.einsum("bnf,fg->bng", h, layer["w"], precision=hi)
            h = jax.nn.relu(jnp.einsum("bnm,bmg->bng", a, hw + layer["b"],
                                       precision=hi)) * mask[..., None]
        n = jnp.maximum(mask.sum(-1, keepdims=True), 1.0)
        c = jnp.tanh(jnp.dot(h.sum(1) / n, params["att"]["w"], precision=hi))
        att = jax.nn.sigmoid(jnp.einsum("bnf,bf->bn", h, c,
                                        precision=hi)) * mask
        return jnp.einsum("bn,bnf->bf", att, h, precision=hi)

    labels = self.config["n_node_labels"]
    sides = [embed(self.params, *ref.pad([p[i] for p in pairs], labels, 64))
             for i in (0, 1)]
    return np.asarray(ref.head(self.params, *sides))


@pytest.mark.parametrize("config,correct", [("simgnn_aids", False),
                                            ("simgnn_aids_gcn_nobias", True)])
def test_gcn_bias_before_aggregation_shows_only_where_there_are_biases(
        config, correct, tmp_path, monkeypatch):
    from harness.drivers import score

    monkeypatch.setattr(score.Driver, "_control_score", _bias_first_scores)
    cell = "aids_gcn_nobias_score_b1000"
    with jax_highest():
        result = _run(cell, tmp_path, SMALL[cell], control="high",
                      config=config)
    assert result["correct"] is correct, result["checks"]
