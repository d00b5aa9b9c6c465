"""The engine split's reading of program spans in a profiler trace, its
arithmetic, and its counters around a tiny cell's window on the CPU."""

import time
from pathlib import Path

import pytest

from bench import engine_split, harness, trace
from bench.tests.tiny import make_root

TESTDATA = Path(__file__).resolve().parent.parent / "testdata"
#: 0.5 s of ``automotive.batch`` traced on one TPU v5e before the program
#: had spans: 15 flushes, each two crossbar kernel calls
RECORDED = TESTDATA / "automotive_batch.xplane.pb"
#: 0.7 s of ``automotive.batch`` traced on one TPU v5e with the program's
#: own spans and named kernel: 18 flushes
RECORDED_SPANS = TESTDATA / "automotive_batch_spans.xplane.pb"

#: the engine's spans on their own line, nested as the engine opens them
#: (a barrier around the compile, dispatch and retires it runs), with
#: ``flush`` given as an argument in the name or as a stat; the device
#: is busy from 1 to 2 us of a 10-us window
NESTED = '''
planes {
  id: 1
  name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 1000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%recross_crossbar_reduce.2 custom-call" } }
}
planes {
  id: 2
  name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
  }
  lines { id: 2 name: "recross-flush-driver" timestamp_ns: 0
    events { metadata_id: 2 offset_ps: 500000 duration_ps: 1000000 }
    events { metadata_id: 3 offset_ps: 1500000 duration_ps: 1000000
             stats { metadata_id: 1 int64_value: 0 } }
    events { metadata_id: 4 offset_ps: 3000000 duration_ps: 6000000 }
    events { metadata_id: 5 offset_ps: 3500000 duration_ps: 1000000 }
    events { metadata_id: 6 offset_ps: 4500000 duration_ps: 500000 }
    events { metadata_id: 7 offset_ps: 5000000 duration_ps: 1000000 }
    events { metadata_id: 8 offset_ps: 6000000 duration_ps: 2000000 }
    events { metadata_id: 5 offset_ps: 11000000 duration_ps: 1000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "recross.compile#flush=0#" } }
  event_metadata { key: 3 value { id: 3 name: "recross.dispatch" } }
  event_metadata { key: 4 value { id: 4 name: "recross.barrier#flush=2#" } }
  event_metadata { key: 5 value { id: 5 name: "recross.compile#flush=1#" } }
  event_metadata { key: 6 value { id: 6 name: "recross.dispatch#flush=1#" } }
  event_metadata { key: 7 value { id: 7 name: "recross.retire#flush=0#" } }
  event_metadata { key: 8 value { id: 8 name: "recross.retire#flush=1#" } }
  stat_metadata { key: 1 value { id: 1 name: "flush" } }
}
'''


def test_program_spans_of_a_synthetic_trace(tmp_path):
    from jax.profiler import ProfileData

    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(NESTED))
    spans, idle = engine_split.program_spans(path)
    us = 1e-6
    # the compile at 11 us lies outside the window; the barrier's self
    # time is its 6 us less the compile, dispatch and two retires in it
    assert {k: v["count"] for k, v in spans.items()} == {
        "recross.compile": 2, "recross.dispatch": 2, "recross.retire": 2,
        "recross.barrier": 1}
    assert {k: v["total_s"] for k, v in spans.items()} == pytest.approx({
        "recross.compile": 2 * us, "recross.dispatch": 1.5 * us,
        "recross.retire": 3 * us, "recross.barrier": 6 * us})
    assert {k: v["self_s"] for k, v in spans.items()} == pytest.approx({
        "recross.compile": 2 * us, "recross.dispatch": 1.5 * us,
        "recross.retire": 3 * us, "recross.barrier": 1.5 * us})
    # idle 0..1 and 2..10 us, split by the innermost span open
    assert idle == pytest.approx({
        engine_split.OUTSIDE_SPANS: 2 * us, "recross.compile": 1.5 * us,
        "recross.dispatch": 1 * us, "recross.barrier": 1.5 * us,
        "recross.retire": 3 * us})
    s = trace.reduce(path)
    assert sum(idle.values()) == pytest.approx(s.window_s - s.busy_s)
    text = engine_split.describe_spans(spans, idle)
    assert "program span recross.barrier: 1 events" in text
    assert "device idle in recross.retire" in text


@pytest.mark.parametrize("name, short", [
    ("recross.retire#flush=12#", "recross.retire"),
    ("recross.barrier", "recross.barrier"),
])
def test_span_name_drops_annotation_arguments(name, short):
    assert engine_split.span_name(name) == short


def test_recorded_trace_reduces_as_before():
    """The chip trace recorded before the program had spans reduces to
    exactly what the accepted benchmark read from it, and holds no
    program span."""
    s = trace.reduce(RECORDED)
    assert (s.busy_s, s.kernel_s, s.kernel_events) == (0.009469662, 0.009339226, 30)
    assert s.device_ops == [
        ["%fn.3 custom-call", 0.004677338], ["%fn.2 custom-call", 0.004661888],
        ["%slice.3 slice", 8.0738e-05], ["%copy copy", 1.2862e-05],
        ["%slice_bitcast_fusion fusion", 1.1247e-05],
        ["%broadcast_maximum_fusion fusion", 6.421e-06],
        ["%pad_maximum_fusion fusion", 6.41e-06],
        ["%slice_bitcast_fusion.1 fusion", 5.818e-06],
        ["%broadcast_maximum_fusion.1 fusion", 5.506e-06],
        ["%slice.8 slice", 1.055e-06]]
    assert s.idle_gaps == [["bench.submit", 0.349044814],
                           ["bench.drain", 0.162886743]]
    spans, idle = engine_split.program_spans(RECORDED)
    assert spans == {}
    assert idle == pytest.approx({engine_split.OUTSIDE_SPANS: s.window_s - s.busy_s})


def test_recorded_spans_trace_has_each_flushs_spans():
    s = trace.reduce(RECORDED_SPANS)
    spans, idle = engine_split.program_spans(RECORDED_SPANS)
    flushes = s.kernel_events // 2
    assert flushes == 18
    for name in ("recross.compile", "recross.dispatch", "recross.retire"):
        assert spans[name]["count"] == flushes, name
    assert spans["recross.barrier"]["count"] == 1
    assert sum(idle.values()) == pytest.approx(s.window_s - s.busy_s)
    # the kernel is found by its name in the op events, and the flush
    # program by its name in the module events
    ops, modules = [], []
    for plane in trace.load(RECORDED_SPANS).planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == trace.OPS_LINE:
                    ops += [(e.name, trace._stats(e)) for e in line.events]
                elif line.name == "XLA Modules":
                    modules += [e.name for e in line.events]
    kernels = [name for name, st in ops if trace.is_kernel(name, st)]
    assert len(kernels) == s.kernel_events
    assert all(trace.short_name(n).startswith("%recross_crossbar_reduce.")
               for n in kernels)
    assert sum("recross_crossbar_reduce" in n for n, _ in ops) == len(kernels)
    assert modules and all(n.startswith("jit_recross_flush(") for n in modules)


BEFORE = {"batches": 10, "host_compile_s": 1.0, "submit_s": 2.0,
          "submits": 1000, "handoff_full_s": 0.5, "engine_wait_s": 0.25,
          "route_s": 0.125, "routed": 1000}
AFTER = {"batches": 30, "host_compile_s": 1.24, "submit_s": 3.0,
         "submits": 6120, "handoff_full_s": 0.9, "engine_wait_s": 0.45,
         "route_s": 0.381, "routed": 6120}
SPANS = {"recross.compile": {"count": 20, "total_s": 0.24, "self_s": 0.24},
         "recross.dispatch": {"count": 20, "total_s": 0.02, "self_s": 0.02},
         "recross.retire": {"count": 20, "total_s": 0.3, "self_s": 0.3},
         "recross.barrier": {"count": 1, "total_s": 0.05, "self_s": 0.01}}
TIMINGS = {"cooccurrence": 1.5, "grouping": 3.0, "placement": 1.25}


@pytest.mark.parametrize("name, value", [
    ("submit_us_per_bag", (1.0 - 0.4) / 5120 * 1e6),
    ("handoff_full_pct", 100 * 0.4 / 2.0),
    ("engine_wait_pct", 100 * 0.2 / 2.0),
    ("route_us_per_bag", 0.256 / 5120 * 1e6),
    ("dispatch_ms_per_flush", 0.02 / 20 * 1e3),
    ("retire_ms_per_flush", 0.3 / 20 * 1e3),
    ("plan_cooccurrence_s", 1.5),
    ("plan_grouping_s", 3.0),
    ("plan_placement_s", 1.25),
    # wait 0.2 + route 0.256 + compile 0.24 + dispatch 0.02 + retire 0.3
    # + barrier 0.01 = 1.026 s of a 2-s window
    ("engine_accounting_pct", 51.3),
])
def test_split_value(name, value):
    s = engine_split.split(BEFORE, AFTER, 2.0, SPANS, TIMINGS)
    assert s[name] == pytest.approx(value)


def test_split_per_flush_adds_the_pieces():
    s = engine_split.split(BEFORE, AFTER, 2.0, SPANS, TIMINGS)
    assert s["flushes"] == 20
    assert s["per_flush_ms"] == pytest.approx({
        "flush": 100.0, "submit": 30.0, "handoff_full": 20.0, "route": 12.8,
        "compile": 12.0, "dispatch": 1.0, "retire": 15.0, "barrier": 0.5,
        "engine_wait": 10.0})


def test_split_of_an_empty_window_reads_nothing():
    s = engine_split.split(BEFORE, BEFORE, 2.0, {}, None)
    for name in ("submit_us_per_bag", "route_us_per_bag",
                 "dispatch_ms_per_flush", "retire_ms_per_flush",
                 "plan_cooccurrence_s"):
        assert s[name] is None, name
    assert s["engine_accounting_pct"] == 0.0


def test_counters_around_a_tiny_window(tmp_path):
    """The wrapped driver reads the server's counters around the window
    alone: every bag the window submitted is counted once as submitted
    and once as routed, and no blocked hand-off outlasts its submit."""
    root = make_root(tmp_path)
    cell = harness.load_cell("tiny.batch", root)
    got = engine_split.measure(cell, 2**33 + 29, 0.3, False,
                               t_process=time.perf_counter(), need_chip=False)
    assert got["result"]["correct"] is True
    w, s = got["window"], got["split"]
    assert s["flushes"] > 0 and s["window_s"] == w.t_end - w.t_first
    assert s["submit_us_per_bag"] > 0 and s["route_us_per_bag"] > 0
    assert 0 <= s["handoff_full_pct"] <= 100 and 0 <= s["engine_wait_pct"] <= 100
    per = s["per_flush_ms"]
    assert per["route"] * s["flushes"] == pytest.approx(
        s["route_us_per_bag"] * w.attempted / 1e3)
    assert all(v >= 0 for v in per.values())
    assert all(s[k] > 0 for k in ("plan_cooccurrence_s", "plan_grouping_s",
                                  "plan_placement_s"))
    assert got["spans"] == {} and s["dispatch_ms_per_flush"] == 0.0


def test_without_a_chip_the_script_prints_no_result(capsys):
    assert engine_split.main(["--workload", "automotive.batch", "--seed", "1",
                              "--seconds", "1"]) == 2
    assert capsys.readouterr().out == ""
