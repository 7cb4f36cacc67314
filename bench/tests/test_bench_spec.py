"""Cell discovery by name, and `BENCHMARK.json` against the shape the
benchmark's contract gives it."""

import json
import os
import re

import _paths  # noqa: F401
import pytest

from harness import spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BENCH = spec.benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_is_found_by_name(cell):
    c = spec.load_cell(cell)
    entry = next(w for w in BENCH["workloads"] if w["name"] == cell)
    assert c.config["name"] == entry["config"]
    assert c.chips == entry["chips"] == 1
    assert c.traffic["driver"] in ("score", "search", "train")
    assert {m["name"] for m in c.end_to_end} >= {"setup_s"}
    assert len(c.end_to_end) >= 2 and c.per_layer
    assert c.limits


def test_an_unknown_cell_names_the_known_ones():
    with pytest.raises(KeyError, match=CELLS[0]):
        spec.load_cell("no_such_cell")


def test_metrics_apply_by_their_workloads_lists():
    c = spec.load_cell("aids_gcn_nobias_score_b1000")
    names = {m["name"] for m in c.end_to_end + c.per_layer}
    assert "pairs_per_s" in names and "plan_ms.score" in names
    assert not any(n.endswith(".search") for n in names)


EMPTY = {"trace": None, "peaks": None, "plan_s": [], "occupancy": [],
         "prefilter_s": [], "rerank_s": [], "useful_flops": 0.0,
         "useful_bytes": 0.0, "window_s": 1.0, "search_wall_s": 0.0,
         "scan_flops": 0.0, "scan_bytes": 0.0, "flushes": 0,
         "flushed_queries": 0, "compiles_in_window": None}


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_every_per_layer_metric_has_a_reader_that_reads_nothing_from_nothing(
        metric):
    assert spec.metric_reader(metric)(dict(EMPTY)) is None


def test_benchmark_file_keeps_to_the_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    configs = {c["name"] for c in BENCH["configs"]}
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/")
        assert os.path.exists(os.path.join(spec.ROOT, c["file"]))
        assert any(w["config"] == c["name"] for w in BENCH["workloads"])
    pairs = set()
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in configs and len(w["why"]) <= 200
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in e2e
        for cell in m.get("workloads", CELLS):
            reported = e2e[m["moves"]].get("workloads", CELLS)
            assert cell in CELLS and cell in reported
    everything = BENCH["configs"] + BENCH["workloads"] + \
        BENCH["end_to_end"] + BENCH["per_layer"]
    names = [x["name"] for x in everything]
    assert all(NAME.match(n) for n in names)
    assert len(set(names)) == len(names)
    assert all(UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
               for m in BENCH["end_to_end"] + BENCH["per_layer"])
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        assert len(f.read()) <= 64 * 1024
    json.dumps(BENCH)
