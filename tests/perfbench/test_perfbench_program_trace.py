"""The reading of the program's own spans and scopes from a profiler trace
(``perfbench/program_trace.py``): the protobuf decoder on a hand-made file,
the arithmetic on hand-made intervals, and two traces recorded on a TPU v5e,
one from before the program had spans and scopes and one after
(``tests/perfbench/record_scoped_trace.py``)."""
from pathlib import Path
from types import SimpleNamespace

import pytest

from perfbench import layers, trace
from perfbench import program_trace as pt

DATA = Path(__file__).resolve().parent / "data"
PLAIN = DATA / "tpu_trace.xplane.pb"
SCOPED = DATA / "tpu_trace_scoped.xplane.pb"
STEP = "jit__step_batch_impl"


# -- a hand-made XSpace ---------------------------------------------------------


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        byte, n = n & 0x7F, n >> 7
        out.append(byte | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _len(field: int, payload: bytes) -> bytes:
    return _varint(field << 3 | 2) + _varint(len(payload)) + payload


def _int(field: int, n: int) -> bytes:
    return _varint(field << 3) + _varint(n)


def _entry(key: int, value: bytes) -> bytes:
    return _int(1, key) + _len(2, value)


def _plane(name, events, stats):
    """``events``: [(id, name, [(stat id, field, value)])]; ``stats``:
    {stat id: stat name}."""
    out = _len(2, name.encode())
    for sid, sname in stats.items():
        out += _len(5, _entry(sid, _int(1, sid) + _len(2, sname.encode())))
    for eid, ename, estats in events:
        body = _int(1, eid) + _len(2, ename.encode())
        for sid, field, value in estats:
            v = _len(field, value.encode()) if isinstance(value, str) \
                else _int(field, value)
            body += _len(5, _int(1, sid) + v)
        out += _len(4, _entry(eid, body))
    return out


def test_op_metadata_decodes_scope_paths_and_programs():
    stats = {1: "program_id", 2: "tf_op", 3: "flops",
             4: "jit(_peek)/reduce_or:"}
    dev = _plane("/device:TPU:0", [
        (1, "%fusion.49 = f32[8]", [(1, 3, 77), (2, 5,
         "jit(_step_batch_impl)/while/body/sssp.relax/jit(_take)/gather:")]),
        (2, "%copy.80 = f32[8]", [(1, 3, 77), (3, 3, 12)]),
        (3, "%fusion.1 = f32[8]", [(1, 3, 78), (2, 7, 4)]),  # by reference
    ], stats)
    host = _plane("/host:CPU", [(1, "bench.window", [])], {})
    data = _len(1, dev) + _len(1, host) + _int(7, 1)  # unknown field skipped
    got = pt.op_metadata(data)
    assert list(got) == ["/device:TPU:0"]
    assert got["/device:TPU:0"] == [
        ("%fusion.49 = f32[8]", 77,
         "jit(_step_batch_impl)/while/body/sssp.relax/jit(_take)/gather:"),
        ("%copy.80 = f32[8]", 77, None),
        ("%fusion.1 = f32[8]", 78, "jit(_peek)/reduce_or:"),
    ]


def test_scope_and_program_names():
    assert pt.scope_of("jit(f)/while/body/sssp.relax/jit(_take)/gather:") \
        == "sssp.relax"
    assert pt.scope_of("jit(f)/sssp.prime/cond/sssp.settle/and") \
        == "sssp.settle"
    assert pt.scope_of("jit(_reset_lanes_impl)/ge") is None
    assert pt.scope_of(None) is None
    assert pt.split_module("jit__step_batch_impl(1541)") == (STEP, 1541)
    assert pt.split_module("SFence") == ("SFence", None)


def test_ops_are_named_by_scope_program_or_the_op_before():
    names = pt.OpNames([
        ("a", 1, "jit(f)/while/body/sssp.relax/gather:"),
        ("b", 1, "jit(f)/while/cond/reduce_or:"),
        ("a", 2, "jit(g)/min:"),  # the same op name in another program
        ("c", 1, None),
    ])
    assert names.collisions == 1
    mods = [(0.0, 10.0, "f(1)"), (20.0, 30.0, "g(2)")]
    ops = [(1.0, 2.0, "a"), (2.0, 3.0, "c"),  # c follows a scoped op
           (3.0, 4.0, "b"), (4.0, 5.0, "c"),  # c follows an unscoped one
           (21.0, 22.0, "a"), (40.0, 41.0, "c")]
    got = pt.label_ops(ops, mods, names, 0.0, 100.0)
    assert [(n, i) for _, _, n, i in got] == [
        ("sssp.relax", False), ("sssp.relax", True), ("f", False),
        ("f", False), ("g", False), ("no program", False)]


def test_chunk_gaps_latency_and_round_host_time():
    runs = [(1.0, 3.0), (4.0, 6.0), (9.0, 10.0)]
    busy = runs + [(3.5, 3.8), (6.0, 7.0)]
    assert pt.chunk_gaps(runs, busy, 0.0, 20.0) == pytest.approx(
        (1.0 - 0.3) + (3.0 - 1.0))
    assert pt.chunk_gaps(runs, busy, 2.0, 20.0) == pytest.approx(2.0)
    spans = [(0.0, 4.0, "serving.round"), (0.1, 0.5, "serving.admit"),
             (0.5, 0.9, "serving.dispatch"), (0.9, 3.2, "serving.sync"),
             (3.3, 3.9, "serving.harvest"), (3.4, 3.6, "serving.take_row"),
             (5.0, 5.5, "serving.round"), (5.1, 5.2, "serving.admit")]
    assert pt.round_host(spans, 0.0, 10.0) == pytest.approx([4.0 - 2.3 - 0.2,
                                                             0.5])
    assert pt.round_host(spans, 1.0, 10.0) == pytest.approx([0.5])
    launch, ret = pt.chunk_latency(runs[:1], spans, 0.0, 0.0, 10.0)
    assert launch == pytest.approx(0.5) and ret == pytest.approx(0.2)
    # the device's times moved onto the host's clock
    launch, ret = pt.chunk_latency(runs[:1], spans, 0.1, 0.0, 10.0)
    assert launch == pytest.approx(0.6) and ret == pytest.approx(0.1)
    assert pt.chunk_latency(runs, spans, 0.0, 0.0, 10.0) == (0.0, 0.0)


def test_clock_offset_is_bounded_by_causality():
    """Device times reported early: a step cannot start before the host
    dispatched it, and a peek must end before the host's sync returns."""
    mods = [(0.5, 3.5, f"{STEP}(5)"), (3.6, 3.7, "jit__peek(6)"),
            (10.7, 13.0, f"{STEP}(5)"), (13.2, 13.5, "jit__peek(6)")]
    spans = [(1.0, 1.2, "serving.dispatch"), (1.2, 4.7, "serving.sync"),
             (11.0, 11.1, "serving.dispatch"), (11.1, 15.0, "serving.sync")]
    assert pt.clock_offset(mods, spans) == pytest.approx((0.5, 1.0))
    # a take_row the host waited on tightens both bounds
    took = mods + [(14.0, 14.2, "jit__take_row(7)")]
    assert pt.clock_offset(took, spans + [(14.6, 14.9, "serving.take_row")]
                           ) == pytest.approx((0.6, 0.7))
    assert pt.clock_offset(mods[:2], spans) is None  # unpaired


def test_summary_and_metrics_of_a_window():
    scoped = {"jit(s)/while/body/sssp.relax/gather:": "r",
              "jit(s)/while/body/sssp.thresholds/reduce_min:": "t",
              "jit(s)/while/body/sssp.settle/and:": "m"}
    entries = {"/device:TPU:0": [(n, 5, path) for path, n in scoped.items()]
               + [("p", 6, "jit(_peek)/reduce_or:")]}
    ops = {"/device:TPU:0": [
        (0.0, 4.0, "w"),  # the while op, no metadata, around the body ops
        (0.5, 2.5, "r"), (2.5, 3.0, "t"), (3.0, 3.5, "m"),
        (4.5, 5.0, "p"),
        (6.0, 8.0, "r"), (8.0, 8.5, "t"),
    ]}
    modules = {"/device:TPU:0": [(0.0, 4.0, f"{STEP}(5)"),
                                 (4.5, 5.0, "jit__peek(6)"),
                                 (6.0, 8.5, f"{STEP}(5)")]}
    spans = [(-1.0, 9.0, "bench.window"),
             (-0.5, 4.6, "serving.round"), (-0.4, 0.0, "serving.dispatch"),
             (0.0, 4.6, "serving.sync"),
             (5.5, 8.8, "serving.round"), (5.6, 5.8, "serving.dispatch"),
             (5.8, 8.8, "serving.sync")]
    p = pt.summarize(ops, modules, entries, spans, -1.0, 9.0)
    assert p.busy_s == pytest.approx(7.0)
    assert p.device_s_by_scope == pytest.approx({
        "sssp.relax": 4.0, "sssp.thresholds": 1.0, "sssp.settle": 0.5,
        "jit__peek": 0.5, STEP: 1.0})
    assert p.inherited_s == 0.0
    assert p.step_runs == 2 and p.dispatches == 2
    assert p.chunk_gap_s == pytest.approx(2.0 - 0.5)
    # the gaps [-1, 0] and [5, 6] fall between a round's start and its
    # dispatch, [4, 4.5] and [8.5, 9] while the host waits in a sync
    assert p.idle_by_program_span == pytest.approx(
        {"serving.round": 2.0, "serving.sync": 1.0})
    assert p.round_host_s == pytest.approx([5.1 - 4.6, 3.3 - 3.0])
    assert p.launch_s == pytest.approx(0.4 + 0.4)
    assert p.return_s == pytest.approx(0.6 + 0.3)
    assert p.clock_offset_s is None  # one peek for two syncs: unpaired
    assert pt.relax_device_ms_per_phase(p, 4) == pytest.approx(1e3)
    assert pt.criteria_device_ms_per_phase(p, 4) == pytest.approx(375.0)
    assert pt.chunk_gap_ms(p) == pytest.approx(750.0)
    assert pt.round_host_ms_per_chunk(p) == pytest.approx(400.0)
    assert pt.round_host_ms_max(p) == pytest.approx(500.0)
    assert pt.relax_device_ms_per_phase(p, 0) is None
    assert pt.relax_device_ms_per_phase(None, 4) is None


def test_trace_from_before_the_spans_and_scopes_reads_empty():
    """A program without them, as the parent's: the scope and span metrics
    find nothing and return None; what only the XLA Modules line gives is
    still read."""
    entries = pt.op_metadata(PLAIN.read_bytes())["/device:TPU:0"]
    assert sum(tf is not None for _, _, tf in entries) == 40
    assert "jit(_reset_lanes_impl)/ge" in {tf.rstrip(":") for _, _, tf
                                           in entries if tf}
    p = pt.read_file(str(PLAIN))
    assert p.dispatches == 0 and p.round_host_s == []
    assert not any(k.startswith("sssp.") for k in p.device_s_by_scope)
    assert pt.relax_device_ms_per_phase(p, 100) is None
    assert pt.criteria_device_ms_per_phase(p, 100) is None
    assert pt.round_host_ms_per_chunk(p) is None
    assert p.step_runs > 0 and pt.chunk_gap_ms(p) > 0
    s = trace.summarize_file(str(PLAIN))
    assert p.busy_s == pytest.approx(s.busy_s)
    assert sum(p.device_s_by_scope.values()) <= s.busy_s * (1 + 1e-9)
    assert set(p.device_s_by_scope) <= {STEP, "jit__peek", "jit__take_row",
                                        "jit__reset_lanes_impl",
                                        "jit_convert_element_type"}


@pytest.mark.parametrize("name", ["tpu_trace.xplane.pb",
                                  "tpu_trace_scoped.xplane.pb",
                                  "tpu_trace_sharded.xplane.pb"])
def test_one_load_serves_both_reducers(name):
    """A file read once by ``program_trace.load`` gives both reducers what
    each reads from the path itself, to the bit."""
    path = str(DATA / name)
    chips = 4 if "sharded" in name else 1
    xplane = pt.load(path)
    assert trace.read_xplane(xplane) == trace.read_xplane(path)
    assert trace.summarize_file(xplane, chips=chips) == trace.summarize_file(
        path, chips=chips)
    assert pt.read_file(xplane, chips=chips) == pt.read_file(path,
                                                             chips=chips)


def test_recorded_trace_with_spans_and_scopes():
    p = pt.read_file(str(SCOPED))
    scoped = {k: t for k, t in p.device_s_by_scope.items()
              if k.startswith("sssp.")}
    # the settle mask has no op of its own on the chip: XLA fuses it into
    # the ops of its neighbours, which carry their own scopes
    assert {"sssp.thresholds", "sssp.relax", "sssp.update"} <= set(scoped)
    # the step program's time falls under its stages' scopes
    assert sum(scoped.values()) >= 0.9 * (sum(scoped.values())
                                          + p.device_s_by_scope.get(STEP, 0.0))
    assert 0 < sum(p.device_s_by_scope.values()) <= p.busy_s * (1 + 1e-9)
    idle = p.window_s - p.busy_s
    assert 0 < pt.chunk_gap_ms(p) * 1e-3 * p.step_runs <= idle * (1 + 1e-9)
    assert p.name_collisions >= 0
    # the round's spans are on the trace's clock, around the device's gaps
    assert p.dispatches > 0 and len(p.round_host_s) >= p.dispatches
    assert all(t >= 0 for t in p.round_host_s)
    assert "serving.sync" in p.idle_by_program_span
    assert sum(p.idle_by_program_span.values()) == pytest.approx(idle)
    # the profiler puts the device's times early here; causality bounds
    # the shift that moves them onto the host's clock
    lo, hi = p.clock_offset_s
    assert 0 < lo <= hi
    assert 0 < p.launch_s and 0 < p.return_s
    assert pt.relax_device_ms_per_phase(p, 1) > 0
    assert pt.round_host_ms_per_chunk(p) > 0


@pytest.mark.parametrize("step,others", [
    (STEP, ["jit__reset_lanes_impl", "jit_convert_element_type"]),
    ("jit_step", ["jit__reset_sharded_impl", "jit_convert_element_type"]),
])
def test_step_program_is_what_the_dispatch_launches(step, others):
    """The program a device runs just before each peek, whatever its name;
    the round's other programs (a reset launched in admission, the take_row
    of a harvest) run elsewhere in the order."""
    reset, convert = others
    order = [reset, convert, step, "jit__peek", convert, "jit__take_row",
             step, "jit__peek", reset, step, "jit__peek"]
    mods = [(float(i), i + 0.5, f"{name}({i})")
            for i, name in enumerate(order)]
    assert pt.step_program(mods[::-1]) == step  # in order of start
    assert pt.step_program(mods[:3]) is None  # no peek: nothing launched
    assert pt.step_program([]) is None


def test_scoped_trace_reads_as_before():
    """The one-chip trace reads to the bit what it read while the step
    program was found by its name, ``jit__step_batch_impl``."""
    p = pt.read_file(str(SCOPED))
    assert (p.step_runs, p.dispatches) == (7, 7)
    assert p.window_s == 0.10115010100000002
    assert p.busy_s == 0.06809366100000003
    assert p.chunk_gap_s == 0.02715859399999998
    assert p.launch_s == 0.006644968500000077
    assert p.return_s == 0.012913495499999983
    assert p.clock_offset_s == (0.0008784970000000059, 0.0020557000000000075)
    assert p.device_s_by_scope["sssp.relax"] == 0.0643574029999997
    assert p.device_s_by_scope[STEP] == 0.0013845540000002043
    assert p.idle_by_program_span["serving.sync"] == 0.017831995000000017
    assert sum(p.round_host_s) == sum([
        0.0023795299999999978, 0.002027710000000016, 0.0017118700000000042,
        0.0016849800000000026, 0.0015063599999999983, 0.0016721100000000044,
        0.001103948999999993])
    assert pt.chunk_gap_ms(p) == 3.87979914285714
    assert pt.relax_device_ms_per_phase(p, 100) == 0.643574029999997
    assert pt.criteria_device_ms_per_phase(p, 100) == 0.007168620000002
    plain = pt.read_file(str(PLAIN))
    assert (plain.step_runs, plain.chunk_gap_s) == (15, 0.05419009300000001)


def test_device_times_are_averaged_over_the_cells_chips():
    """A chip that ran no op in the window counts as idle, as in
    ``trace.summarize``; more chips with ops than the cell has is an
    error."""
    ops = {"/device:TPU:0": [(0.0, 2.0, "a"), (4.0, 5.0, "a")]}
    modules = {"/device:TPU:0": [(0.0, 2.0, f"{STEP}(5)"),
                                 (2.0, 2.5, "jit__peek(6)"),
                                 (4.0, 5.0, f"{STEP}(5)")]}
    one = pt.summarize(ops, modules, {}, [], 0.0, 10.0)
    two = pt.summarize(ops, modules, {}, [], 0.0, 10.0, chips=2)
    assert one.step_runs == two.step_runs == 2
    assert one.busy_s == 3.0 and two.busy_s == 1.5
    assert one.chunk_gap_s == 2.0 and two.chunk_gap_s == 1.0
    assert one.device_s_by_scope == {STEP: 3.0}
    assert two.device_s_by_scope == {STEP: 1.5}
    ops["/device:TPU:1"] = ops["/device:TPU:0"]
    with pytest.raises(ValueError, match="2 devices ran ops"):
        pt.summarize(ops, modules, {}, [], 0.0, 10.0)


@pytest.mark.parametrize("reader,read", [
    ("relax_ms_per_phase", lambda p: pt.relax_device_ms_per_phase(p, 50)),
    ("criteria_ms_per_phase",
     lambda p: pt.criteria_device_ms_per_phase(p, 50)),
    ("chunk_gap_ms", pt.chunk_gap_ms),
    ("round_host_ms_per_chunk", pt.round_host_ms_per_chunk),
])
def test_layer_readers_read_the_programs_trace(reader, read):
    """The per-layer readers over a run's program trace and the phases of
    its window; an untraced run (no program trace) reads nothing."""
    p = pt.read_file(str(SCOPED))
    window = SimpleNamespace(trips=50)
    got = getattr(layers, reader)(SimpleNamespace(program=p, window=window))
    assert got is not None and got > 0
    assert got == read(p)
    assert getattr(layers, reader)(
        SimpleNamespace(program=None, window=window)) is None
