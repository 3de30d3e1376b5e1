"""The trace reduction on small synthetic traces with known busy time,
idle gaps and program time, and with the program's host spans, op
scopes and counters."""

import dataclasses

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


# ---------------------------------------------------------------------------
# What the program marks: host spans, op scopes (stat ``tf_op`` of the
# ops' event metadata) and counters
# ---------------------------------------------------------------------------
# Times in us from 1 (offset 0 at 1000 ns). Device: a decode program over
# [1, 6) with ops attention [1, 2), the layer loop while.4 [1, 4) around
# an MF dot [2.5, 3.5) and a kv_write scatter [3.5, 4), an op with no
# scope [5, 6); a second decode run [8, 9) with attention [8, 8.5); a
# prefill run [9.5, 10) whose op shares a name with the decode program's.
# Host: bench.tick [1, 11) with bench.step [1.4, 7.6); the program's
# batcher.tick [1, 9.8) holding batcher.queue [1.2, 1.4), serve.step
# [1.5, 7.5) (serve.dispatch [1.5, 2), serve.sync [2, 7.2)) and
# batcher.clock [7.6, 8.2); batcher.tick [9.8, 10.8) holding batcher.clock
# [10, 10.5); and host events that are not program spans.
ATTN = "jit(serve_step)/while/body/attention"
MF = "jit(serve_step)/while/body/closed_call/mf_projection/lossless"
KV = "jit(serve_step)/while/body/kv_write/vmap()"
XSPACE_PROGRAM = """
planes {
  id: 1
  name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 1000000 }
    events { metadata_id: 4 offset_ps: 0 duration_ps: 3000000 }
    events { metadata_id: 2 offset_ps: 1500000 duration_ps: 1000000 }
    events { metadata_id: 3 offset_ps: 2500000 duration_ps: 500000 }
    events { metadata_id: 5 offset_ps: 4000000 duration_ps: 1000000 }
    events { metadata_id: 1 offset_ps: 7000000 duration_ps: 500000 }
    events { metadata_id: 6 offset_ps: 8500000 duration_ps: 500000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 7 offset_ps: 0 duration_ps: 5000000 }
    events { metadata_id: 7 offset_ps: 7000000 duration_ps: 1000000 }
    events { metadata_id: 8 offset_ps: 8500000 duration_ps: 500000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.1"
    stats { metadata_id: 1 uint64_value: 7 }
    stats { metadata_id: 2 str_value: "%(attn)s/dot_general" } } }
  event_metadata { key: 2 value { id: 2 name: "dot.2"
    stats { metadata_id: 1 int64_value: 7 }
    stats { metadata_id: 2 str_value: "%(mf)s/dot_general" } } }
  event_metadata { key: 3 value { id: 3 name: "fusion.3"
    stats { metadata_id: 1 uint64_value: 7 }
    stats { metadata_id: 2 ref_value: 3 } } }
  event_metadata { key: 4 value { id: 4 name: "while.4"
    stats { metadata_id: 1 uint64_value: 7 }
    stats { metadata_id: 2 str_value: "jit(serve_step)/while" } } }
  event_metadata { key: 5 value { id: 5 name: "copy.5" } }
  event_metadata { key: 6 value { id: 6 name: "fusion.1"
    stats { metadata_id: 1 uint64_value: 9 }
    stats { metadata_id: 2 str_value: "jit(_prefill)/attention/dot_general" }
  } }
  event_metadata { key: 7 value { id: 7 name: "jit_serve_step(7)" } }
  event_metadata { key: 8 value { id: 8 name: "jit__prefill(9)" } }
  stat_metadata { key: 1 value { id: 1 name: "program_id" } }
  stat_metadata { key: 2 value { id: 2 name: "tf_op" } }
  stat_metadata { key: 3 value { id: 3 name: "%(kv)s/scatter" } }
}
planes {
  id: 2
  name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 400000 duration_ps: 6200000 }
    events { metadata_id: 3 offset_ps: 0 duration_ps: 8800000 }
    events { metadata_id: 4 offset_ps: 200000 duration_ps: 200000 }
    events { metadata_id: 5 offset_ps: 500000 duration_ps: 6000000 }
    events { metadata_id: 6 offset_ps: 500000 duration_ps: 500000 }
    events { metadata_id: 7 offset_ps: 1000000 duration_ps: 5200000 }
    events { metadata_id: 8 offset_ps: 6600000 duration_ps: 600000 }
    events { metadata_id: 3 offset_ps: 8800000 duration_ps: 1000000 }
    events { metadata_id: 8 offset_ps: 9000000 duration_ps: 500000 }
    events { metadata_id: 9 offset_ps: 1000000 duration_ps: 1000000 }
    events { metadata_id: 10 offset_ps: 1000000 duration_ps: 1000000 }
    events { metadata_id: 11 offset_ps: 1000000 duration_ps: 1000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.tick" } }
  event_metadata { key: 2 value { id: 2 name: "bench.step" } }
  event_metadata { key: 3 value { id: 3 name: "batcher.tick" } }
  event_metadata { key: 4 value { id: 4 name: "batcher.queue" } }
  event_metadata { key: 5 value { id: 5 name: "serve.step" } }
  event_metadata { key: 6 value { id: 6 name: "serve.dispatch" } }
  event_metadata { key: 7 value { id: 7 name: "serve.sync" } }
  event_metadata { key: 8 value { id: 8 name: "batcher.clock" } }
  event_metadata { key: 9 value { id: 9 name: "copy.1" } }
  event_metadata { key: 10 value { id: 10 name: "$harness.py:1 serve" } }
  event_metadata { key: 11 value { id: 11 name: "PjitFunction(serve_step)" } }
}
""" % {"attn": ATTN, "mf": MF, "kv": KV}
COUNTERS = {"serve_prefill_tokens_total": 30.0,
            "serve_prefill_slab_tokens_total": 120.0,
            "serve_decode_steps_total": 2.0}


@pytest.fixture(scope="module")
def marked(tmp_path_factory):
    """The profile above as written to disk and loaded as a run loads
    it."""
    raw = ProfileData.text_proto_to_serialized_xspace(XSPACE_PROGRAM)
    d = tmp_path_factory.mktemp("trace")
    (d / "plugins" / "profile" / "run").mkdir(parents=True)
    (d / "plugins" / "profile" / "run" / "host.xplane.pb").write_bytes(raw)
    return tr.load(str(d))


def run_data(trace, counters=None, **kw):
    from bench.harness import RunData
    return RunData([], 10.0, 1.0, {}, None, trace, counters or {}, **kw)


def read(name, run):
    from bench.harness import reader
    return reader(name)(run)


def test_program_spans_are_kept_apart_from_the_benchmarks(marked):
    assert [s[0] for s in marked.spans] == ["bench.tick", "bench.step"]
    assert marked.window == (1000.0, 11000.0)
    assert sorted({s[0] for s in marked.program_spans}) == [
        "batcher.clock", "batcher.queue", "batcher.tick", "serve.dispatch",
        "serve.step", "serve.sync"]
    assert len(marked.program_spans) == 8


def test_op_scopes_are_read_from_the_event_metadata(marked):
    assert marked.scopes == {"/device:TPU:0": {
        (7, "fusion.1"): ATTN + "/dot_general",
        (7, "dot.2"): MF + "/dot_general",
        (7, "fusion.3"): KV + "/scatter",
        (7, "while.4"): "jit(serve_step)/while",
        (9, "fusion.1"): "jit(_prefill)/attention/dot_general"}}
    assert tr.program_id("jit_serve_step(7)") == 7
    assert tr.program_id("jit_serve_step") is None


def test_scope_times_per_execution_by_hand(marked):
    # Two decode runs: attention 1 + 0.5 us, MF 1, kv_write 0.5, no
    # scope 1; the layer loop that holds them is left out.
    assert tr.scope_times(marked, "jit_serve_step") == pytest.approx(
        {ATTN: 0.75e-6, MF: 0.5e-6, KV: 0.25e-6, "": 0.5e-6})
    assert tr.scope_times(marked, "jit__prefill") == pytest.approx(
        {"jit(_prefill)/attention": 0.5e-6})
    assert tr.scope_times(marked, "jit_other") == {}
    times = tr.scope_times(marked, "jit_serve_step")
    assert tr.under(times, "attention", "kv_write") == pytest.approx(1e-6)
    assert tr.under(times, "mf_projection") == pytest.approx(0.5e-6)
    assert tr.under(times, "while") == pytest.approx(1.5e-6)


def test_idle_by_innermost_program_span_by_hand(marked):
    # Idle [4, 5), [6, 8), [8.5, 9.5), [10, 11) of the window [1, 11).
    idle = tr.idle_by_span(marked)
    assert idle == pytest.approx({
        "serve.sync": 2.2e-6, "serve.step": 0.3e-6,
        "batcher.tick": 1.4e-6, "batcher.clock": 0.9e-6, "": 0.2e-6})
    assert sum(idle.values()) == pytest.approx(
        marked.window_s - tr.mean_busy_s(marked))


def test_span_times_less_their_children_by_hand(marked):
    assert tr.span_times(marked, "batcher.tick") == pytest.approx(
        [8.8e-6, 1.0e-6])
    assert tr.span_times(marked, "batcher.tick", less=(
        "serve.sync", "batcher.clock")) == pytest.approx([3.0e-6, 0.5e-6])


def test_the_program_readers_by_hand(marked):
    run = run_data(marked, COUNTERS)
    assert read("decode_attention_ms", run) == pytest.approx(1e-3)
    assert read("decode_mf_ms", run) == pytest.approx(0.5e-3)
    assert read("host_tick_ms", run) == pytest.approx(1.75e-3)
    assert read("idle_in_program_share", run) == pytest.approx(39.0)
    for split in ("prefill_pad_share.decode", "prefill_pad_share.chat"):
        assert read(split, run) == pytest.approx(75.0)


def test_the_program_readers_read_nothing_where_nothing_is_marked(trace):
    run = run_data(trace)
    for name in ("decode_attention_ms", "decode_mf_ms", "host_tick_ms",
                 "idle_in_program_share", "prefill_pad_share.decode"):
        assert read(name, run) is None


@pytest.mark.parametrize("name,value", [
    ("decode_step_ms", 6e-3), ("prefill_wave_ms.decode", 1e-3),
    ("device_idle_share", 60.0)])
def test_the_former_readers_read_as_before(trace, name, value):
    assert read(name, run_data(trace)) == pytest.approx(value)


def test_program_marks_leave_the_former_readings_as_they_were(marked):
    run = run_data(marked, COUNTERS)
    assert read("decode_step_ms", run) == pytest.approx(3e-3)
    assert read("prefill_wave_ms.chat", run) == pytest.approx(0.5e-3)
    assert read("device_idle_share", run) == pytest.approx(50.0)
    top = dict(tr.top_ops(marked))
    assert top["jit_serve_step/fusion.1"] == pytest.approx(1.5e-6)
    assert "jit_serve_step/while.4" not in top
    gaps = tr.idle_gaps(marked)
    assert gaps[0] == ["bench.step", pytest.approx(2e-6)]
    assert [g[0] for g in gaps[1:]] == ["bench.step", "bench.tick",
                                        "bench.tick"]


def test_decode_mfu_reads_the_configurations_count():
    from bench import harness
    from bench.peaks import peaks_for
    from bench.stats import Record
    from bench.tests.test_opcount import QWEN3, SIZES, old_token_ops
    recs = [Record(0, 0.0, (1, 2, 3), 4, token_s=[1.0, 2.0, 11.0]),
            Record(1, 0.0, (1,), 4, token_s=[0.5])]
    peaks = peaks_for("TPU v5 lite")
    run = harness.RunData(recs, 10.0, 1.0, SIZES, peaks,
                          token_ops=QWEN3.token_ops)
    ops = old_token_ops(SIZES, 3) + old_token_ops(SIZES, 4) + \
        old_token_ops(SIZES, 1)
    assert read("decode_mfu", run) == pytest.approx(
        100.0 * ops / 10.0 / peaks.bf16_flops)
    assert read("decode_mfu", dataclasses.replace(run, token_ops=None)) \
        is None
