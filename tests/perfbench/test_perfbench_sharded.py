"""A cell's ``chips`` picks its backend: the harness on the CPU, with four
host devices for the test-only four-chip cells ``tiny-g500-sharded``
(``layout: "coo"``) and ``tiny-g500-sharded-sliced``, and the collective
and program-trace reductions on a small trace recorded on four TPU v5e
chips by ``tests/perfbench/record_sharded_trace.py``. Nothing here measures
a device.

What the harness asks of ``ShardedBackend`` follows its constructor: a key
of ``SHARDED_FIXED`` that the constructor does not take is held to the one
value the backend serves, and one that it takes goes through. Each test of
that rule runs against the program's constructor and against
``run_sharded_faults.stand_in_backend``, which takes both keys, so the
tests hold unchanged on the day the program's constructor takes them. The
stand-in serves the sliced layout through a step whose exchange is another
than the program's (an ``all_gather`` in place of ``_exchange_min_batch``'s
``all_to_all``), so the planted faults are shown to reach a step that the
program's exchange function never enters."""
import inspect
import json
import os
import re
import subprocess
import sys
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

import repro.serving
from perfbench import graphs, layers, reference, trace
from perfbench import program_trace as pt
from perfbench.peaks import PEAKS
from perfbench.run import SHARDED_FIXED, make_backend, run_cell
from perfbench.spec import load_cell
from repro.core.graph import from_coo
from run_sharded_faults import FAULTS, stand_in_backend

HERE = Path(__file__).resolve().parent
DATA = HERE / "data"
FIXTURE = DATA / "tpu_trace_sharded.xplane.pb"
PEAK = PEAKS["TPU v5 lite"]
BIG_SEED = 2**31 + 12345
COO, SLICED = "tiny-g500-sharded", "tiny-g500-sharded-sliced"
CONSTRUCTORS = pytest.mark.parametrize("stand_in", [False, True],
                                       ids=["program", "stand_in"])


def _takes(stand_in: bool, key: str) -> bool:
    cls = stand_in_backend() if stand_in else repro.serving.ShardedBackend
    return key in inspect.signature(cls).parameters


def _refusal(key: str) -> str:
    return re.escape(SHARDED_FIXED[key][1])


@pytest.fixture(scope="module")
def sharded_runs():
    """``run_sharded_faults.py WORKLOAD`` on four host devices, each
    workload and constructor once: a JSON line per run, by mode. The device
    count has to be set before JAX is imported, so the runs take a process
    of their own."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    memo = {}

    def runs(workload: str = COO, stand_in: bool = False) -> dict:
        if (workload, stand_in) not in memo:
            cmd = [sys.executable, str(HERE / "run_sharded_faults.py"),
                   workload] + (["--stand-in"] if stand_in else [])
            p = subprocess.run(cmd, capture_output=True, text=True, env=env,
                               timeout=600)
            assert p.returncode == 0, p.stderr[-4000:]
            lines = [json.loads(line) for line in p.stdout.splitlines()
                     if line.startswith("{")]
            memo[workload, stand_in] = {r["mode"]: r for r in lines}
        return memo[workload, stand_in]

    return runs


def test_four_chip_cell_is_served_through_sharded_backend(sharded_runs):
    r = sharded_runs()["sound"]
    assert r["correct"], r["checks"]
    assert (r["backend"], r["mesh_devices"], r["chips"]) == (
        "ShardedBackend", 4, 4)
    assert len(r["memory_peak_bytes_by_chip"]) == 4
    assert r["attempted"] > 0 and r["failed"] == 0
    assert r["compiles_in_window"] == 0
    assert r["program_exchange_calls"] > 0


@pytest.mark.parametrize("fault", FAULTS)
def test_broken_sharded_path_is_not_correct(sharded_runs, fault):
    r = sharded_runs()[fault]
    assert r["backend"] == "ShardedBackend"
    assert not r["correct"], r["checks"]


@CONSTRUCTORS
def test_sliced_four_chip_cell_follows_the_constructor(sharded_runs,
                                                       stand_in):
    """Refused with ``SHARDED_FIXED``'s reason while the constructor lacks
    ``layout``; served, correct and with no compile in the window once it
    takes it."""
    r = sharded_runs(SLICED, stand_in)["sound"]
    if not _takes(stand_in, "layout"):
        assert re.search(_refusal("layout"), r["refused"]), r
        return
    assert r["correct"], r["checks"]
    assert (r["backend"], r["mesh_devices"], r["chips"]) == (
        "ShardedBackend", 4, 4)
    assert r["attempted"] > 0 and r["failed"] == 0
    assert r["compiles_in_window"] == 0
    if stand_in:  # its own exchange, not the program's
        assert r["program_exchange_calls"] == 0


@CONSTRUCTORS
@pytest.mark.parametrize("fault", FAULTS)
def test_broken_sliced_path_follows_the_constructor(sharded_runs, stand_in,
                                                    fault):
    r = sharded_runs(SLICED, stand_in)[fault]
    if not _takes(stand_in, "layout"):
        assert re.search(_refusal("layout"), r["refused"]), r
        return
    assert r["backend"] == "ShardedBackend"
    assert not r["correct"], r["checks"]
    if stand_in:  # planted under a step that never enters the program's
        assert r["program_exchange_calls"] == 0


@pytest.fixture(scope="module")
def tiny_graph():
    cfg = load_cell("tiny-g500-sharded", DATA).config
    src, dst, w, n, _ = graphs.build(cfg, BIG_SEED)
    return from_coo(src, dst, w, n), cfg


@pytest.fixture
def no_mesh(monkeypatch):
    """The test process has one host device: ``make_backend``'s mesh over
    four is a placeholder here, for constructors that record their call."""
    import jax

    monkeypatch.setattr(jax, "make_mesh", lambda *a, **kw: "mesh")


def _recording(signature, calls):
    """A ``ShardedBackend`` stand-in with constructor ``signature`` that
    records the keywords it is built with."""

    class Recording:
        __signature__ = signature

        def __init__(self, g, mesh, axes, **kw):
            calls.append(kw)

    return Recording


def _signature(takes):
    """A ``ShardedBackend`` constructor that takes the keys ``takes`` of
    ``SHARDED_FIXED`` and lacks the others."""
    P = inspect.Parameter
    return inspect.Signature(
        [P(n, P.POSITIONAL_OR_KEYWORD) for n in ("g", "mesh", "axes")]
        + [P("criterion", P.KEYWORD_ONLY, default=None)]
        + [P(k, P.KEYWORD_ONLY, default=SHARDED_FIXED[k][0]) for k in takes])


@pytest.mark.parametrize("key,value", [("layout", "sliced"),
                                       ("point_queries", True)])
def test_sharded_backend_refuses_a_key_it_does_not_take(
        tiny_graph, no_mesh, monkeypatch, key, value):
    g, cfg = tiny_graph
    calls = []
    others = [k for k in SHARDED_FIXED if k != key]
    monkeypatch.setattr(repro.serving, "ShardedBackend",
                        _recording(_signature(others), calls))
    with pytest.raises(ValueError, match=_refusal(key)):
        make_backend(g, {**cfg, key: value}, 4)
    assert calls == []
    make_backend(g, {**cfg, key: SHARDED_FIXED[key][0]}, 4)  # the one value
    assert len(calls) == 1 and key not in calls[0]


@pytest.mark.parametrize("key,value", [("layout", "sliced"),
                                       ("point_queries", False),
                                       ("point_queries", True)])
def test_sharded_backend_takes_a_key_it_takes(tiny_graph, no_mesh,
                                              monkeypatch, key, value):
    """The configured value reaches the constructor unchanged; the key it
    lacks, at its one value, is not passed."""
    g, cfg = tiny_graph
    calls = []
    monkeypatch.setattr(repro.serving, "ShardedBackend",
                        _recording(_signature([key]), calls))
    make_backend(g, {**cfg, key: value}, 4)
    [kw] = calls
    assert kw[key] == value and type(kw[key]) is type(value)
    assert set(kw) == {key, "criterion"}
    assert kw["criterion"] == cfg["criterion"]


@pytest.mark.parametrize("key,value", [("layout", "sliced"),
                                       ("point_queries", True)])
def test_program_sharded_backend_follows_its_signature(
        tiny_graph, no_mesh, monkeypatch, key, value):
    """Against the program's constructor: refused where it lacks the key,
    passed through where it takes it."""
    g, cfg = tiny_graph
    signature = inspect.signature(repro.serving.ShardedBackend)
    if key not in signature.parameters:
        with pytest.raises(ValueError, match=_refusal(key)):
            make_backend(g, {**cfg, key: value}, 4)
        return
    calls = []
    monkeypatch.setattr(repro.serving, "ShardedBackend",
                        _recording(signature, calls))
    make_backend(g, {**cfg, key: value}, 4)
    assert calls[0][key] == value


def test_one_chip_cell_builds_static_backend_as_before(monkeypatch):
    """``run_cell`` builds a one-chip cell's ``StaticBackend`` with the
    same call as before the backend followed the cell's chips."""
    import repro.serving

    calls = []

    class Recording(repro.serving.StaticBackend):
        def __init__(self, *args, **kw):
            calls.append((args, kw))
            super().__init__(*args, **kw)

    monkeypatch.setattr(repro.serving, "StaticBackend", Recording)
    cell = load_cell("tiny-g500-closed", DATA)
    result, _, info = run_cell(cell, BIG_SEED, 0.3, False,
                               time.perf_counter(), PEAK)
    assert result["correct"] and info["chips"] == 1
    assert len(info["memory_peak_bytes_by_chip"]) == 1
    [(args, kw)] = calls
    assert len(args) == 1 and args[0].n == 256
    assert kw == {"layout": "sliced", "criterion": "instatic|outstatic",
                  "point_queries": False}


def test_solve_hbm_roofline_divides_by_the_cells_chips():
    src = np.array([0, 1, 0, 0], np.int32)
    dst = np.array([1, 2, 2, 2], np.int32)
    w = np.array([0.5, 0.25, 1.0, 0.9], np.float32)
    ref = reference.Reference(src, dst, w, 4)
    req = SimpleNamespace(outcome="ok", t_completed=1.0, cache_hit=False,
                          coalesced=False, target=None, source=0)
    window = SimpleNamespace(t0=0.0, seconds=2.0, requests=[req])

    def roofline(**chips):
        return layers.solve_hbm_roofline(layers.Run(
            setup_s=0.0, window=window, drain_end=2.0, n=4, lanes=2,
            trace=SimpleNamespace(busy_s=3e-9), ref_rows=ref.rows([0]),
            reference=ref, peak=PEAK, **chips))

    need = layers.solve_bytes(4, 4, 2)
    one = 100.0 * need / PEAK["hbm_bytes_per_s"] / 3e-9  # the one-chip form
    assert roofline() == roofline(chips=1) == one
    assert roofline(chips=4) == pytest.approx(one / 4, rel=1e-15)


def test_recorded_four_chip_tpu_trace():
    s = trace.summarize_file(str(FIXTURE), chips=4)
    assert s.devices == 4
    assert 0.0 < s.busy_s < s.window_s < 1.0
    assert 0.0 < s.collective_exposed_s <= s.collective_s <= s.busy_s
    devices, _ = trace.read_xplane(str(FIXTURE))
    kinds = {trace.collective_kind(name)
             for events in devices.values() for _, _, name in events}
    assert kinds - {None} == {"all-reduce", "all-to-all"}
    window = SimpleNamespace(trips=100)
    run = SimpleNamespace(trace=s, window=window)
    assert layers.collective_ms_per_phase(run) == 1e3 * s.collective_s / 100
    assert 0.0 < layers.collective_exposed_ms_per_phase(run) <= (
        layers.collective_ms_per_phase(run))


def test_recorded_four_chip_trace_finds_the_sharded_step():
    """The program's trace of the four-chip cell: the step program is found
    by what the dispatch launches (``jit_step`` here), so the chunk gaps are
    read per chip and averaged over the cell's chips."""
    p = pt.read_file(str(FIXTURE), chips=4)
    devices, _ = trace.read_xplane(str(FIXTURE))
    assert len(devices) == 4
    assert p.step_runs == p.dispatches == 6
    assert pt.chunk_gap_ms(p) is not None and pt.chunk_gap_ms(p) > 0
    assert 0 < p.chunk_gap_s * 4 <= 4 * (p.window_s - p.busy_s)
    assert p.busy_s == pytest.approx(trace.summarize_file(
        str(FIXTURE), chips=4).busy_s)
    lo, hi = p.clock_offset_s
    assert 0 < lo <= hi
    assert p.launch_s > 0 and p.return_s > 0
    # the sharded step carries no sssp.* scope: the scoped readers are
    # silent, the rest read
    assert pt.relax_device_ms_per_phase(p, 100) is None
    assert pt.round_host_ms_per_chunk(p) > 0
    with pytest.raises(ValueError, match="4 devices ran ops"):
        pt.read_file(str(FIXTURE), chips=2)
