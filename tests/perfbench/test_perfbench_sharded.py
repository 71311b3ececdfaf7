"""A cell's ``chips`` picks its backend: the harness on the CPU, with four
host devices for the test-only four-chip cell ``tiny-g500-sharded``, and
the collective reduction on a small trace recorded on four TPU v5e chips
by ``tests/perfbench/record_sharded_trace.py``. Nothing here measures a
device."""
import json
import os
import subprocess
import sys
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from perfbench import graphs, layers, reference, trace
from perfbench.peaks import PEAKS
from perfbench.run import make_backend, run_cell
from perfbench.spec import load_cell
from repro.core.graph import from_coo

HERE = Path(__file__).resolve().parent
DATA = HERE / "data"
FIXTURE = DATA / "tpu_trace_sharded.xplane.pb"
PEAK = PEAKS["TPU v5 lite"]
BIG_SEED = 2**31 + 12345


@pytest.fixture(scope="module")
def sharded_runs():
    """``run_sharded_faults.py`` on four host devices: a JSON line per run,
    by mode. The device count has to be set before JAX is imported, so the
    runs take a process of their own."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, str(HERE / "run_sharded_faults.py")],
                       capture_output=True, text=True, env=env, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    runs = [json.loads(line) for line in p.stdout.splitlines()
            if line.startswith("{")]
    return {r["mode"]: r for r in runs}


def test_four_chip_cell_is_served_through_sharded_backend(sharded_runs):
    r = sharded_runs["sound"]
    assert r["correct"], r["checks"]
    assert (r["backend"], r["mesh_devices"], r["chips"]) == (
        "ShardedBackend", 4, 4)
    assert len(r["memory_peak_bytes_by_chip"]) == 4
    assert r["attempted"] > 0 and r["failed"] == 0
    assert r["compiles_in_window"] == 0


@pytest.mark.parametrize("fault", ["state_unchanged", "answer_altered",
                                   "half_lanes_dropped", "exchange_left_out"])
def test_broken_sharded_path_is_not_correct(sharded_runs, fault):
    r = sharded_runs[fault]
    assert r["backend"] == "ShardedBackend"
    assert not r["correct"], r["checks"]


@pytest.fixture(scope="module")
def tiny_graph():
    cfg = load_cell("tiny-g500-sharded", DATA).config
    src, dst, w, n, _ = graphs.build(cfg, BIG_SEED)
    return from_coo(src, dst, w, n), cfg


@pytest.mark.parametrize("change,message", [
    ({"layout": "sliced"}, "layout 'coo'"),
    ({"point_queries": True}, "s->t target lanes"),
])
def test_sharded_backend_refuses_what_it_cannot_serve(tiny_graph, change,
                                                      message):
    g, cfg = tiny_graph
    with pytest.raises(ValueError, match=message):
        make_backend(g, {**cfg, **change}, 4)


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
