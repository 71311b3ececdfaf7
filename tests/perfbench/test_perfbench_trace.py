"""The reduction from a profiler trace to the benchmark's device numbers,
on hand-made intervals and on a small trace recorded on a TPU v5e by
``tests/perfbench/record_trace.py``."""
from pathlib import Path

import pytest

from perfbench import trace

FIXTURE = Path(__file__).resolve().parent / "data" / "tpu_trace.xplane.pb"


def test_busy_union_and_gaps():
    busy = [(1.0, 2.0), (1.5, 3.0), (5.0, 6.0), (9.0, 12.0)]
    assert trace.merge(busy) == [[1.0, 3.0], [5.0, 6.0], [9.0, 12.0]]
    inside = trace.clip(busy, 0.0, 10.0)
    assert trace.covered(inside) == 4.0  # 2 + 1 + 1
    assert trace.gaps(busy, 0.0, 10.0) == [(0.0, 1.0), (3.0, 5.0), (6.0, 9.0)]
    assert trace.gaps([], 0.0, 1.0) == [(0.0, 1.0)]


def test_self_time_takes_nested_ops_off_their_parent():
    ops = [(0.0, 10.0, "while"), (1.0, 3.0, "gather"), (4.0, 5.0, "min"),
           (4.2, 4.4, "inner"), (12.0, 13.0, "gather")]
    got = trace.self_times(ops)
    assert got == pytest.approx({"while": 7.0, "gather": 3.0, "min": 0.8,
                                 "inner": 0.2})


def test_gaps_are_named_by_the_innermost_host_span():
    spans = [(0.0, 10.0, "bench.window"), (2.0, 6.0, "bench.step"),
             (2.5, 5.5, "backend.peek")]
    got = trace.name_gaps([(3.0, 5.0), (7.0, 8.0), (11.0, 12.0)], spans)
    assert got == {"backend.peek": 2.0, "bench.window": 1.0,
                   "host: outside spans": 1.0}


def test_summary_of_a_window():
    dev = {"/device:TPU:0": [(0.0, 2.0, "a"), (3.0, 4.0, "b"),
                             (3.2, 3.5, "c"), (20.0, 21.0, "a")]}
    spans = [(1.0, 5.0, "bench.window"), (2.0, 3.0, "backend.peek")]
    s = trace.summarize(dev, spans, 1.0, 5.0)
    assert s.window_s == 4.0 and s.devices == 1
    assert s.busy_s == pytest.approx(2.0)  # [1, 2] and [3, 4]
    assert dict(s.idle_gaps) == pytest.approx({"backend.peek": 1.0,
                                               "bench.window": 1.0})
    assert dict(s.device_ops) == pytest.approx({"a": 1.0, "b": 0.7, "c": 0.3})


def test_hlo_op_names_are_shortened():
    assert trace.op_name(
        "%fusion.49 = f32[3342336,8]{1,0:T(8,128)} fusion(f32[8,4]{0,1} "
        "%bitcast.115), kind=kCustom") == "fusion.49 = f32[3342336,8] fusion"
    assert trace.op_name("%while.4 = (f32[4,256]{0,1:T(8,128)}, s32[]) "
                         "while((f32[4,256]) %t), condition=%c"
                         ) == "while.4 = (...) while"
    assert trace.op_name("copy.5") == "copy.5"


def test_recorded_tpu_trace():
    s = trace.summarize_file(str(FIXTURE))
    assert s.devices == 1
    assert 0.0 < s.busy_s < s.window_s < 1.0
    idle = dict(s.idle_gaps)
    assert sum(idle.values()) + s.busy_s == pytest.approx(s.window_s)
    assert set(idle) <= {"bench.window", "bench.arrivals", "bench.step",
                         "bench.drain", "backend.step", "backend.peek",
                         "backend.take_row", "backend.reset_lanes"}
    assert "backend.peek" in idle  # the host waits on the device there
    ops = dict(s.device_ops)
    assert len(ops) == trace.TOP
    assert any(" while" in name for name in ops)
    assert 0 < sum(ops.values()) <= s.busy_s * (1 + 1e-9)
