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


def test_collective_kinds():
    assert trace.collective_kind("pmin.12 = f32[2,8] all-reduce") == "all-reduce"
    assert trace.collective_kind(
        "all_to_all.8 = f32[8,4,16384] all-to-all") == "all-to-all"
    assert trace.collective_kind("cp.1 = (...) collective-permute-start"
                                 ) == "collective-permute-start"
    assert trace.collective_kind("ag.2 = f32[8] all-gather-done"
                                 ) == "all-gather-done"
    assert trace.collective_kind("fusion.49 = f32[8,512] fusion") is None
    assert trace.collective_kind("all-reduce.3") is None  # no kind kept


def test_collective_time_and_its_exposed_part():
    """A synchronous all-to-all inside a while loop is all exposed; an
    asynchronous all-gather in flight over a fusion is hidden but for its
    start and done ops."""
    ops = [(0.0, 10.0, "while.1 = (...) while"),
           (1.0, 2.0, "a2a.1 = f32[8] all-to-all"),
           (3.0, 3.5, "ag.1 = f32[8] all-gather-start"),
           (3.5, 6.0, "fusion.1 = f32[8] fusion"),
           (6.0, 6.5, "ag.2 = f32[8] all-gather-done"),
           (8.0, 9.0, "fusion.2 = f32[8] fusion")]
    assert trace.collective_spans(ops) == [(1.0, 2.0), (3.0, 6.5)]
    s = trace.summarize({"/device:TPU:0": ops}, [], 0.0, 10.0)
    assert s.collective_s == pytest.approx(4.5)  # [1, 2] and [3, 6.5]
    assert s.collective_exposed_s == pytest.approx(2.0)  # a2a, start, done
    assert s.collective_exposed_s <= s.collective_s <= s.busy_s
    # a pair cut by the window's edge is in flight up to that edge
    cut = trace.summarize({"/device:TPU:0": ops}, [], 0.0, 5.0)
    assert cut.collective_s == pytest.approx(3.0)  # [1, 2] and [3, 5]
    assert trace.overlap([(0.0, 2.0), (3.0, 5.0)], [(1.0, 4.0)]) == 2.0


def test_busy_is_averaged_over_the_cells_chips():
    """A chip of the cell that ran no op counts as idle, not as absent; a
    trace with more busy devices than the cell's chips is refused."""
    dev = {"/device:TPU:0": [(0.0, 2.0, "a = f32[8] all-reduce")],
           "/device:TPU:1": [(0.0, 1.0, "b = f32[8] all-reduce")]}
    s = trace.summarize(dev, [], 0.0, 4.0, chips=4)
    assert s.devices == 2
    assert s.busy_s == pytest.approx(0.75)
    assert s.collective_s == s.collective_exposed_s == pytest.approx(0.75)
    with pytest.raises(ValueError, match="2 devices"):
        trace.summarize(dev, [], 0.0, 4.0, chips=1)


@pytest.mark.parametrize("name,busy_s,window_s,idle", [
    ("tpu_trace.xplane.pb", 0.001422951000000068, 0.061065189,
     97.66978367986371),
    ("tpu_trace_scoped.xplane.pb", 0.06809366100000003, 0.10115010100000002,
     32.680580318945985),
])
def test_one_chip_traces_read_as_before(name, busy_s, window_s, idle):
    """The committed one-chip traces: busy time and idle share exactly as
    the reduction read them before it counted collectives and chips, and
    no collective, so the collective readers report nothing."""
    from perfbench import layers

    s = trace.summarize_file(str(FIXTURE.parent / name))
    assert (s.busy_s, s.window_s, s.devices) == (busy_s, window_s, 1)
    assert s.collective_s == s.collective_exposed_s == 0.0
    window = type("W", (), {"trips": 100})()
    run = type("R", (), {"trace": s, "window": window})()
    assert layers.device_idle_share(run) == idle
    assert layers.collective_ms_per_phase(run) is None
    assert layers.collective_exposed_ms_per_phase(run) is None
