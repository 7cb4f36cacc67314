"""The program's `engine.*` stage spans in a small synthetic profiler trace:
exclusive times, the per-call division, the bytes of the copy spans, the
idle gaps split among the stages they cross, and the trace reduction left
as it was."""

import _paths  # noqa: F401
import pytest
from test_bench_trace import TRACE as BENCH_ONLY_TRACE

US = 1_000_000        # picoseconds per microsecond

#: (name, start us, end us, bytes): two `score()` calls inside
#: `bench.score_call`, the first on the packed-sparse path, the second on
#: the dense one (no `engine.edges`).
HOST = [
    ("bench.window", 0, 40, 0),
    ("bench.score_call", 1, 19, 0),
    ("engine.score", 2, 18, 0),
    ("engine.validate", 2.5, 3.5, 0),
    ("engine.stats", 3.5, 4, 0),
    ("engine.select", 4, 4.5, 0),
    ("engine.pack", 5, 10, 0),
    ("engine.to_device", 6, 7, 1000),
    ("engine.edges", 7, 9, 0),
    ("engine.wait", 7.5, 8, 0),
    ("engine.to_host", 8, 8.5, 500),
    ("engine.kernel", 10, 11, 0),
    ("engine.unpack", 11, 14, 0),
    ("engine.wait", 11, 13, 0),
    ("engine.to_host", 13, 13.5, 200),
    ("engine.record", 15, 16, 0),
    ("bench.score_call", 21, 31, 0),
    ("engine.score", 22, 30, 0),
    ("engine.validate", 22, 24, 0),
    ("engine.stats", 24, 25, 0),
    ("engine.select", 25, 25.5, 0),
    ("engine.pack", 25.5, 27, 0),
    ("engine.to_device", 26, 26.5, 3000),
    ("engine.kernel", 27, 27.5, 0),
    ("engine.unpack", 27.5, 29.5, 0),
    ("engine.wait", 27.5, 29, 0),
    ("engine.to_host", 29, 29.2, 100),
    ("engine.record", 29.5, 29.8, 0),
]
#: device ops of the one chip: idle in [3, 5.5), [11.5, 13.2), [20, 40).
OPS = [(0, 3), (5.5, 11.5), (13.2, 20)]


def _events(rows, names):
    out = []
    for name, a, b, nbytes in rows:
        stats = (f" stats {{ metadata_id: 1 int64_value: {nbytes} }}"
                 if nbytes else "")
        out.append(f"events {{ metadata_id: {names.index(name) + 1} "
                   f"offset_ps: {round(a * US)} "
                   f"duration_ps: {round(b * US) - round(a * US)}{stats} }}")
    return "\n".join(out)


def _metadata(names):
    return "\n".join(f'event_metadata {{ key: {i + 1} value {{ id: {i + 1} '
                     f'name: "{n}" }} }}' for i, n in enumerate(names))


def _trace(host_rows):
    names = sorted({r[0] for r in host_rows})
    return f'''
planes {{
  id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0
    {_events([("op", a, b, 0) for a, b in OPS], ["op"])} }}
  {_metadata(["op"])}
}}
planes {{
  id: 2 name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: 0
    {_events(host_rows, names)} }}
  {_metadata(names)}
  stat_metadata {{ key: 1 value {{ id: 1 name: "bytes" }} }}
}}
'''


def _profile(host_rows):
    from jax.profiler import ProfileData

    return ProfileData.from_text_proto(_trace(host_rows))


@pytest.fixture(scope="module")
def stages():
    from harness.stages import reduce_stages

    return reduce_stages(_profile(HOST))


def test_calls_are_the_engine_score_spans(stages):
    assert stages.calls == 2
    assert stages.window_s == pytest.approx(40e-6)


@pytest.mark.parametrize("name,exclusive_us", [
    ("engine.score", 4 + 0.2),          # 16 - 12 and 8 - 7.8
    ("engine.validate", 1 + 2),
    ("engine.pack", (5 - 1 - 2) + (1.5 - 0.5)),
    ("engine.edges", 2 - 0.5 - 0.5),
    ("engine.unpack", (3 - 2 - 0.5) + (2 - 1.5 - 0.2)),
    ("engine.wait", 0.5 + 2 + 1.5),
    ("engine.to_host", 0.5 + 0.5 + 0.2),
])
def test_exclusive_time_leaves_out_nested_engine_spans(stages, name,
                                                       exclusive_us):
    assert stages.exclusive_s[name] == pytest.approx(exclusive_us * 1e-6)


def test_copy_spans_carry_their_bytes(stages):
    assert stages.nbytes["engine.to_device"] == 4000
    assert stages.nbytes["engine.to_host"] == 800
    assert stages.nbytes["engine.pack"] == 0


@pytest.mark.parametrize("metric,value", [
    ("validate_ms.score", 1.5e-3),
    ("stats_ms.score", 0.75e-3),
    ("pack_ms.score", 1.5e-3),
    ("edges_ms.score", 0.5e-3),
    ("transfer_ms.score", (1 + 0.5 + 0.5 + 0.5 + 0.2) / 2 * 1e-3),
    ("transfer_mb.score", 2400 / 1e6),
    ("wait_ms.score", 2e-3),
    ("other_host_ms.score", 2.1e-3),
])
def test_readers_divide_by_the_calls_in_the_window(stages, metric, value):
    from harness import spec

    read = spec.metric_reader(metric)
    assert read({"engine_stages": stages}) == pytest.approx(value)


def test_idle_gaps_are_split_exactly_among_the_stages_they_cross(stages):
    # [3, 5.5) crosses validate, stats, select, the call itself and pack;
    # [11.5, 13.2) unpack's wait and its copy back; [20, 40) every stage of
    # the second call, then nothing but the window.
    want_us = {
        "engine.validate": 0.5 + 2, "engine.stats": 0.5 + 1,
        "engine.select": 0.5 + 0.5, "engine.score": 0.5 + 0.2,
        "engine.pack": 0.5 + 0.5 + 0.5, "engine.wait": 1.5 + 1.5,
        "engine.to_host": 0.2 + 0.2, "engine.to_device": 0.5,
        "engine.kernel": 0.5, "engine.unpack": 0.3, "engine.record": 0.3,
        # [20, 22): its midpoint lies inside the second bench.score_call.
        "bench.score_call": 2, "no host span": 10,
    }
    assert stages.idle_s.keys() == want_us.keys()
    for name, us in want_us.items():
        assert stages.idle_s[name] == pytest.approx(us * 1e-6), name


def test_idle_seconds_are_conserved(stages):
    from harness import trace

    red = trace.reduce_profile(_profile(HOST))
    assert sum(stages.idle_s.values()) \
        == pytest.approx(red.window_s - red.busy_s)
    assert sum(stages.idle_s.values()) \
        == pytest.approx(sum(red.idle_by_activity.values()))


def test_engine_spans_leave_the_trace_reduction_as_it_was():
    from harness import trace

    bench_rows = [r for r in HOST if r[0].startswith("bench.")]
    with_engine = trace.reduce_profile(_profile(HOST))
    without = trace.reduce_profile(_profile(bench_rows))
    assert with_engine.busy_s == without.busy_s
    assert with_engine.window_s == without.window_s
    assert with_engine.op_seconds == without.op_seconds
    assert with_engine.idle_by_activity == without.idle_by_activity


def test_without_engine_spans_the_split_is_the_existing_labelling():
    from jax.profiler import ProfileData

    from harness import stages, trace

    profile = ProfileData.from_text_proto(BENCH_ONLY_TRACE)
    red = trace.reduce_profile(profile)
    got = stages.reduce_stages(profile)
    assert got.idle_s == red.idle_by_activity        # bit for bit
    assert got.window_s == red.window_s
    assert got.calls == 0


@pytest.mark.parametrize("metric", [
    "validate_ms.score", "stats_ms.score", "pack_ms.score", "edges_ms.score",
    "transfer_ms.score", "transfer_mb.score", "wait_ms.score",
    "other_host_ms.score"])
def test_readers_find_nothing_without_engine_spans(metric):
    from jax.profiler import ProfileData

    from harness import spec, stages

    bare = stages.reduce_stages(ProfileData.from_text_proto(BENCH_ONLY_TRACE))
    read = spec.metric_reader(metric)
    assert read({"engine_stages": bare}) is None
    assert read({}) is None                  # an untraced run


def test_a_dense_only_window_has_no_edges_time():
    from harness import spec
    from harness.stages import reduce_stages

    dense = [r for r in HOST if r[1] >= 20 or r[0] == "bench.window"]
    got = reduce_stages(_profile(dense))
    assert got.calls == 1
    read = spec.metric_reader("edges_ms.score")
    assert read({"engine_stages": got}) is None


STAGE_METRICS = ("validate_ms.score", "stats_ms.score", "pack_ms.score",
                 "edges_ms.score", "transfer_ms.score", "transfer_mb.score",
                 "wait_ms.score", "other_host_ms.score")


@pytest.mark.parametrize("cell", ["aids_gcn_nobias_score_b1000",
                                  "imdb_gcn_nobias_score_b1000"])
def test_a_traced_run_reports_the_stage_metrics_its_cell_lists(cell,
                                                               tmp_path):
    import io
    import time

    from harness import spec
    from harness.runner import run

    c = spec.load_cell(cell)
    c.traffic.update({"pairs_per_call": 16, "pool_pairs": 32})
    result = run(c, 2**31 + 29, 0.5, True, t_start=time.perf_counter(),
                 require_device=False, cache=False, runs_dir=str(tmp_path),
                 log=io.StringIO())
    listed = {m["name"] for m in c.per_layer if m["name"] in STAGE_METRICS}
    assert listed == set(STAGE_METRICS) - (
        set() if cell.startswith("aids") else {"edges_ms.score"})
    got = {k: v["value"] for k, v in result["metrics"].items()
           if k in STAGE_METRICS}
    assert got.keys() == listed
    assert all(v > 0 for v in got.values()), got
    assert result["correct"]
