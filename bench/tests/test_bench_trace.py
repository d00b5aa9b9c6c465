"""The reduction from a profiler trace to device metrics."""

from pathlib import Path

import pytest

from bench import trace

#: 0.5 s of ``automotive.batch`` traced on one TPU v5e (``--trace 1``
#: with a short window): 15 flushes, each two crossbar kernel calls
RECORDED = Path(__file__).resolve().parent.parent / "testdata" / "automotive_batch.xplane.pb"

SYNTHETIC = '''
planes {
  id: 1
  name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 3000000 duration_ps: 1000000 }
    events { metadata_id: 1 offset_ps: 3500000 duration_ps: 1000000 }
    events { metadata_id: 1 offset_ps: 20000000 duration_ps: 1000000 }
  }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 3 offset_ps: 1000000 duration_ps: 2200000 }
    events { metadata_id: 3 offset_ps: 4000000 duration_ps: 1500000 }
  }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "custom-call.2" } }
  event_metadata { key: 3 value { id: 3 name: "jit_fn" } }
}
planes {
  id: 2
  name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 3000000 duration_ps: 2000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.drain" } }
}
'''


def test_reduction_of_a_synthetic_trace(tmp_path):
    from jax.profiler import ProfileData

    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(SYNTHETIC))
    s = trace.reduce(path)
    # window 0..10 us; ops 1..3, 4..5 and 4.5..5.5 us (overlapping), and
    # one at 21 us outside the window; programs 1..3.2 and 4..5.5 us
    assert s.window_s == pytest.approx(10e-6)
    assert s.busy_s == pytest.approx(3.7e-6)
    assert s.kernel_s == pytest.approx(1e-6) and s.kernel_events == 1
    assert dict(s.device_ops) == pytest.approx({"fusion.1": 3e-6,
                                                "custom-call.2": 1e-6})
    gaps = dict(s.idle_gaps)
    assert gaps == pytest.approx({"no host event": 1e-6 + 4.5e-6,
                                  "bench.drain": 0.8e-6})
    assert sum(gaps.values()) == pytest.approx(s.window_s - s.busy_s)


def test_describe_lists_planes_lines_and_events(tmp_path):
    from jax.profiler import ProfileData

    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(SYNTHETIC))
    text = trace.describe(path, events=1)
    assert "plane '/device:TPU:0'" in text and "line 'XLA Ops': 4 events" in text
    assert "'fusion.1' start 1000.0 dur 2000.0" in text


def test_reduction_needs_the_window_span(tmp_path):
    from jax.profiler import ProfileData

    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(
        SYNTHETIC.replace("bench.window", "other")))
    with pytest.raises(ValueError):
        trace.reduce(path)


def test_reduction_of_a_trace_recorded_on_the_chip():
    s = trace.reduce(RECORDED)
    assert s.window_s == pytest.approx(0.521401219)
    # every flush program is two Pallas calls, and nothing else matches
    names = [e.name for p in trace.load(RECORDED).planes
             if p.name.startswith("/device:")
             for line in p.lines if line.name == trace.OPS_LINE
             for e in line.events]
    assert len(names) == 151
    assert s.kernel_events == sum("tpu_custom_call" in n for n in names) == 30
    assert 0 < s.kernel_s <= s.busy_s < s.window_s
    assert s.kernel_s == pytest.approx(0.009339226)
    assert s.busy_s == pytest.approx(0.009469662)
    assert [op for op, _ in s.device_ops[:2]] == ["%fn.3 custom-call",
                                                  "%fn.2 custom-call"]
    gaps = dict(s.idle_gaps)
    assert set(gaps) == {"bench.submit", "bench.drain"}
    assert sum(gaps.values()) == pytest.approx(s.window_s - s.busy_s)


@pytest.mark.parametrize("name, short", [
    ('%fn.3 = f32[16,8,128]{2,1,0:T(8,128)S(1)} custom-call(s32[16,56]{1,0} %a), '
     'custom_call_target="tpu_custom_call"', "%fn.3 custom-call"),
    ("%copy-start = (f32[1,32]{1,0:T(8,128)S(1)}, u32[]{:S(2)}) copy-start(f32[1,32] %b)",
     "%copy-start copy-start"),
    ("fusion.1", "fusion.1"),
])
def test_short_name_drops_shapes_and_operands(name, short):
    assert trace.short_name(name) == short
