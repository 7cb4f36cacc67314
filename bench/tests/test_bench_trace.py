"""The trace reduction on a small synthetic profiler trace."""

import _paths  # noqa: F401
import pytest

TRACE = '''
planes {
  id: 1
  name: "/device:TPU:0"
  lines {
    id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 2000000
             stats { metadata_id: 9 str_value: "jit(score)/sparse_pair_score" } }
    events { metadata_id: 2 offset_ps: 2000000 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 7000000 duration_ps: 1000000 }
    events { metadata_id: 2 offset_ps: 11000000 duration_ps: 4000000 }
  }
  lines {
    id: 2 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 3 offset_ps: 0 duration_ps: 20000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%custom-call.1 = f32[8]{0} custom-call(f32[8]{0} %p)" } }
  event_metadata { key: 2 value { id: 2 name: "%fusion.2 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop" } }
  event_metadata { key: 3 value { id: 3 name: "jit_score" } }
  stat_metadata { key: 9 value { id: 9 name: "tf_op" } }
}
planes {
  id: 2
  name: "/device:TPU:1"
  lines {
    id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 1000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%fusion.7 = f32[8]{0} fusion(f32[8]{0} %q)" } }
}
planes {
  id: 3
  name: "/host:CPU"
  lines {
    id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 12000000 }
    events { metadata_id: 2 offset_ps: 500000 duration_ps: 6000000 }
    events { metadata_id: 3 offset_ps: 4000000 duration_ps: 100000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.score_call" } }
  event_metadata { key: 3 value { id: 3 name: "unrelated" } }
}
'''


@pytest.fixture(scope="module")
def reduced():
    from jax.profiler import ProfileData

    from harness import trace

    return trace.reduce_profile(ProfileData.from_text_proto(TRACE))


def test_window_is_the_bench_window_span(reduced):
    assert reduced.window_s == pytest.approx(12e-6)
    assert reduced.chips == 2


def test_busy_is_the_union_of_ops_inside_the_window_averaged_over_chips(
        reduced):
    # chip 0: [1, 4) union [7, 8) union [11, 12) (clipped at the window's
    # end) = 5 us; chip 1: [1, 2) = 1 us; mean 3 us.
    assert reduced.busy_s == pytest.approx(3e-6)


def test_op_seconds_are_summed_per_name_and_clipped(reduced):
    assert reduced.op_seconds["custom-call"] == pytest.approx(2e-6)
    assert reduced.op_seconds["fusion"] == pytest.approx(5e-6)
    assert reduced.device_seconds() == pytest.approx(7e-6)


def test_kernel_time_matches_names_and_naming_stats(reduced):
    assert reduced.kernel_seconds(["sparse_pair_score"]) \
        == pytest.approx(2e-6)
    assert reduced.kernel_seconds(["blocked_topm"]) == 0.0


def test_idle_gaps_are_labelled_by_the_innermost_bench_span(reduced):
    # chip 0 idles in [0, 1), [4, 7) and [8, 11) us; the midpoints 0.5
    # and 5.5 lie inside bench.score_call [0.5, 6.5), 9.5 only inside the
    # window itself.
    idle = reduced.idle_by_activity
    assert idle["bench.score_call"] == pytest.approx(4e-6)
    assert idle["no host span"] == pytest.approx(3e-6)
    assert "unrelated" not in idle


def test_breakdown_lists_top_ops_and_gaps(reduced):
    from harness import trace

    out = trace.breakdown(reduced, top=2)
    assert [k for k, _ in out["device_ops"]] == ["fusion", "custom-call"]
    assert out["idle_gaps"][0][0] == "bench.score_call"


@pytest.mark.parametrize("hlo,name", [
    ("%sparse_pair_score.1 = f32[512,16,1]{2,1,0} custom-call(s32[512]{0} %a)",
     "sparse_pair_score"),
    ("%broadcast_multiply_fusion = f32[8]{0} fusion(f32[8]{0} %x), kind=kLoop",
     "broadcast_multiply_fusion"),
    ("%copy-done.6 = f32[4]{0} copy-done(f32[4]{0} %c)", "copy-done"),
    ("jit_score", "jit_score"),
])
def test_short_names_drop_the_instruction_text_and_suffix(hlo, name):
    from harness import trace

    assert trace.short_name(hlo) == name


def test_a_trace_without_device_ops_reads_nothing():
    from jax.profiler import ProfileData

    from harness import readers, trace

    red = trace.reduce_profile(ProfileData.from_text_proto(
        'planes { id: 1 name: "/host:CPU" }'))
    assert red.busy_s == 0.0
    assert readers.idle_share({"trace": red}) is None
