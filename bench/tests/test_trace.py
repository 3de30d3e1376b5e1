"""The trace reduction on a small synthetic trace with known busy time,
idle gaps and program time."""

import pytest
from jax.profiler import ProfileData

from bench import trace as tr

# Device: ops at [1, 3) and [6, 7) us, one decode program over [1, 7) and
# one prefill over [8, 9); host: a tick span over [1, 11) with a step span
# over [2, 7) inside it.
XSPACE = """
planes {
  id: 1
  name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 500000 }
    events { metadata_id: 2 offset_ps: 5000000 duration_ps: 1000000 }
    events { metadata_id: 1 offset_ps: 7000000 duration_ps: 1000000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 6000000 }
    events { metadata_id: 4 offset_ps: 7000000 duration_ps: 1000000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "dot.2" } }
  event_metadata { key: 3 value { id: 3 name: "jit_serve_step(7)" } }
  event_metadata { key: 4 value { id: 4 name: "jit__prefill(9)" } }
}
planes {
  id: 2
  name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 5000000 }
    events { metadata_id: 3 offset_ps: 0 duration_ps: 500000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.tick" } }
  event_metadata { key: 2 value { id: 2 name: "bench.step" } }
  event_metadata { key: 3 value { id: 3 name: "PjitFunction" } }
}
"""


@pytest.fixture(scope="module")
def trace():
    return tr.from_profile(ProfileData.from_text_proto(XSPACE))


def test_window_is_the_benchmark_spans(trace):
    assert trace.window == (1000.0, 11000.0)
    assert [s[0] for s in trace.spans] == ["bench.tick", "bench.step"]


def test_busy_is_the_union_of_op_intervals(trace):
    # [1, 3) + [6, 7) + [8, 9): overlapping ops count once.
    assert tr.busy_s(trace, "/device:TPU:0") == pytest.approx(4e-6)
    assert tr.mean_busy_s(trace) == pytest.approx(4e-6)
    assert trace.window_s == pytest.approx(10e-6)


def test_program_times(trace):
    assert tr.program_times(trace, "jit_serve_step") == pytest.approx([6e-6])
    assert tr.program_times(trace, "jit__prefill") == pytest.approx([1e-6])
    assert tr.program_times(trace, "jit_other") == []


def test_top_ops_sum_executions_per_program(trace):
    top = dict(tr.top_ops(trace))
    assert top["jit_serve_step/fusion.1"] == pytest.approx(2e-6)
    assert top["jit__prefill/fusion.1"] == pytest.approx(1e-6)
    assert top["jit_serve_step/dot.2"] == pytest.approx(1.5e-6)


@pytest.mark.parametrize("name,label", [
    ("%fusion.3 = f32[8,128]{1,0:T(8,128)} fusion(f32[8] %p), kind=kLoop",
     "fusion.3 f32[8,128]"),
    ("%while.16 = (s32[], f32[2]) while((s32[], f32[2]) %t)", "while.16"),
    ("copy-start.2", "copy-start.2"),
])
def test_op_labels(name, label):
    assert tr.op_label(name) == label


def test_idle_gaps_are_named_by_the_innermost_span(trace):
    gaps = tr.idle_gaps(trace)
    # Idle: [3, 6) under bench.step, [9, 11) and [7, 8) under the tick.
    assert gaps[0][0] == "bench.step"
    assert gaps[0][1] == pytest.approx(3e-6)
    assert [g[0] for g in gaps[1:]] == ["bench.tick", "bench.tick"]
    assert sum(g[1] for g in gaps) == pytest.approx(6e-6)


def test_a_trace_without_device_ops_reads_nothing():
    empty = tr.from_profile(ProfileData.from_text_proto(
        XSPACE.split("planes {\n  id: 2")[0].replace(
            '"/device:TPU:0"', '"/host:metadata"')))
    assert empty.ops == {} and tr.mean_busy_s(empty) == 0.0
    assert tr.idle_gaps(empty) == []
