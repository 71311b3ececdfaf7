"""The benchmark's harness on the CPU: spec loading, generators, traffic,
the reference and its control, and whole runs of a test-only cell with the
served path sound and broken. Nothing here measures a device."""
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

from perfbench import graphs, layers, reference, serve
from perfbench.peaks import PEAKS
from perfbench.run import run_cell
from perfbench.spec import ROOT, load_cell
from perfbench.traffic import FULL, Traffic

DATA = Path(__file__).resolve().parent / "data"
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
PEAK = PEAKS["TPU v5 lite"]
BIG_SEED = 2**31 + 12345


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_loads_by_name(cell):
    c = load_cell(cell)
    names = {m.name for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer
    for m in c.end_to_end + c.per_layer:
        assert callable(m.read)
    assert set(c.config["limits"]) == {"unanswered", "reach_mismatch",
                                       "not_fixpoint", "rel_gap_max"}
    g = graphs.GENERATORS[c.config["generator"]]
    assert g is not None and c.traffic["loop"] in ("open", "closed")


def test_test_only_cell_comes_from_its_own_files():
    """A cell, a configuration, a traffic mix and a metric added as files
    and entries only, found by name like the shipped ones."""
    c = load_cell("tiny-road-open", DATA)
    assert c.config["side"] == 10
    assert c.traffic["rate_per_s"] == 40.0
    assert "answered_count" in {m.name for m in c.per_layer}
    assert {m.name for m in c.end_to_end} == {"queries_per_s",
                                              "latency_p95_ms", "setup_s"}
    with pytest.raises(KeyError):
        load_cell("no-such-cell", DATA)


@pytest.mark.parametrize("scale", [8, 10])
def test_graph500_generator(scale):
    cfg = {"generator": "graph500", "structure_seed": 11, "scale": scale,
           "edgefactor": 16, "a": 0.57, "b": 0.19, "c": 0.19}
    s, d, w, n, labels = graphs.build(cfg, BIG_SEED)
    s2, d2, w2, _, _ = graphs.build(cfg, BIG_SEED)
    assert n == 1 << scale
    assert np.array_equal(s, s2) and np.array_equal(w, w2)
    # another seed: one structure and weights, relabelled by the seed
    s3, d3, w3, _, labels3 = graphs.build(cfg, 1)
    assert np.array_equal(w3, w) and not np.array_equal(s3, s)
    back = np.argsort(labels)  # served label -> generator's vertex
    assert np.array_equal(labels3[back[s]], s3)
    assert np.array_equal(labels3[back[d]], d3)
    assert not np.array_equal(
        graphs.build(dict(cfg, structure_seed=12), BIG_SEED)[2], w)
    half = s.size // 2  # arcs come as (u, v) then (v, u) with one weight
    assert np.array_equal(s[:half], d[half:]) and np.array_equal(d[:half], s[half:])
    assert np.array_equal(w[:half], w[half:])
    assert np.all(s != d) and np.all((w >= 0) & (w < 1))
    edges = 16 * n
    assert 0.97 * edges <= half <= edges  # only self loops dropped
    deg = np.bincount(s, minlength=n)
    assert deg.max() > 20 * deg.mean()  # Kronecker hubs
    assert np.array_equal(np.sort(deg), np.sort(np.bincount(s3, minlength=n)))
    t = Traffic({"pool": "nonzero_degree", "support": None, "zipf_s": None},
                n, deg, 11, labels)
    roots, tgt = t.draw(500)
    assert np.all(deg[roots] > 0) and np.all(tgt == FULL)
    # the other seed's copy is asked the same roots, under its labels
    t3 = Traffic({"pool": "nonzero_degree", "support": None, "zipf_s": None},
                 n, np.bincount(s3, minlength=n), 11, labels3)
    roots3, _ = t3.draw(500)
    assert np.array_equal(labels3[back[roots]], roots3)


@pytest.mark.parametrize("rate", [7.3, 20.0])
def test_open_schedule_is_seeded_with_a_fixed_count(rate):
    params = {"loop": "open", "rate_per_s": rate, "st_share": 0.8,
              "pool": "all", "support": 64, "zipf_s": 1.0}

    def schedule(set_seed):
        return Traffic(params, 1000, np.ones(1000), set_seed).schedule(20.0)

    a, b, c = schedule(77), schedule(77), schedule(5)
    for x, y in zip(a, b):
        assert np.array_equal(x, y)
    assert a[0].size == c[0].size == round(rate * 20)
    assert not np.array_equal(a[1], c[1])
    times = a[0]
    assert np.all(np.diff(times) >= 0) and times[0] > 0 and times[-1] < 20


def test_closed_loop_prefix_holds_one_multiset():
    """A closed loop consumes a prefix of the drawn queries: whatever the
    run's seed, it asks the same vertices in the same order, each under the
    labels of that seed's copy of the graph."""
    params = {"loop": "closed", "outstanding": 16, "pool": "nonzero_degree",
              "support": 1024, "zipf_s": 1.0}
    cfg = {"generator": "graph500", "structure_seed": 11, "scale": 12,
           "edgefactor": 16, "a": 0.57, "b": 0.19, "c": 0.19}

    def prefix(seed):
        s, _, _, n, labels = graphs.build(cfg, seed)
        t = Traffic(params, n, np.bincount(s, minlength=n), 77, labels)
        src = np.concatenate([t.draw(serve.CLOSED_BLOCK)[0] for _ in "ab"])
        return src, np.argsort(labels)[src]  # served and generator's ids

    (a, a_gen), (c, c_gen) = prefix(BIG_SEED), prefix(5)
    assert not np.array_equal(a, c)
    assert np.array_equal(a_gen, c_gen)


class _FakeServer:
    """Two lanes, each request three rounds in a lane; a round is 1 s."""

    def __init__(self):
        self.t, self.queue, self.lanes = 0.0, [], [None, None]
        self.metrics = type("M", (), {"engine_trips": 0})()
        self.in_system = []  # requests in the system before each round

    def clock(self):
        return self.t

    def submit(self, source, t_arrival, target=None):
        r = type("R", (), {"source": source, "outcome": None})()
        self.queue.append(r)
        return r

    @property
    def idle(self):
        return not self.queue and self.lanes == [None, None]

    def step(self):
        self.in_system.append(len(self.queue) + 2 - self.lanes.count(None))
        for k, lane in enumerate(self.lanes):
            if lane is None and self.queue:
                self.lanes[k] = [self.queue.pop(0), 0]
        self.t += 1.0
        self.metrics.engine_trips += 1
        done = []
        for k, lane in enumerate(self.lanes):
            if lane is not None:
                lane[1] += 1
                if lane[1] == 3:
                    lane[0].outcome = "ok"
                    done.append(lane[0])
                    self.lanes[k] = None
        return done


def test_closed_loop_ramps_up_and_closes_answered():
    """The closed loop reaches its outstanding count one request per round,
    and its window closes once everything it sent is answered."""
    server = _FakeServer()
    backend = type("B", (), {"snapshot": lambda self: {"step": 0}})()
    traffic = Traffic({"loop": "closed", "outstanding": 4, "pool": "all",
                       "support": None, "zipf_s": None}, 50, np.ones(50), 3)
    w = serve.run_window(server, backend, traffic, 20.0, server.clock)
    assert server.in_system[:5] == [1, 2, 3, 4, 4]
    assert server.idle and all(r.outcome == "ok" for r in w.requests)
    assert w.answered_in_window == len(w.requests) > 20 * 2 / 3 - 2
    assert w.seconds == server.t > 20.0
    assert w.trips == server.t


def test_zipf_support_and_mix_shares():
    params = {"st_share": 0.8, "pool": "all", "support": 50, "zipf_s": 1.0}
    t = Traffic(params, 10_000, np.ones(10_000), 4)
    src, tgt = t.draw(20_000)
    assert set(np.unique(src)) <= set(t.support.tolist())
    st = tgt != FULL
    assert abs(st.mean() - 0.8) < 0.02
    assert set(np.unique(tgt[st])) <= set(t.support.tolist())
    counts = np.array([(src == v).sum() for v in t.support])
    harmonic = (1.0 / np.arange(1, 51)).sum()
    assert abs(counts[0] / src.size - 1 / harmonic) < 0.02  # rank 1's share
    assert counts[0] > counts[9] > counts[49]


def test_reference_on_a_hand_graph():
    # 0 -> 1 (0.5), 1 -> 2 (0.25), 0 -> 2 (1.0, and a parallel 0.9), 3 alone
    src = np.array([0, 1, 0, 0], np.int32)
    dst = np.array([1, 2, 2, 2], np.int32)
    w = np.array([0.5, 0.25, 1.0, 0.9], np.float32)
    ref = reference.Reference(src, dst, w, 4)
    row = ref.rows([0])[0]
    assert np.array_equal(row, [0.0, 0.5, 0.75, np.inf])
    assert ref.fixpoint(row.astype(np.float32), 0)
    assert not ref.fixpoint(np.float32([0, 0.5, 0.9, np.inf]), 0)
    # arcs out of the reached vertices 0, 1, 2: 3 + 1 + 0, parallel counted
    assert ref.reached_arcs(row) == 4
    assert layers.solve_bytes(4, 4, 2) == 4 * 8 / 2 + 4 * 4
    ok = reference.compare([(0, None, row.astype(np.float32)),
                            (0, 2, row.astype(np.float32))], ref, {0: row})
    assert ok == {"reach_mismatch": 0, "rel_gap_max": 0.0, "not_fixpoint": 0}
    bad = reference.compare([(0, 3, np.float32([0, 0.5, 0.75, 1.0]))],
                            ref, {0: row})
    assert bad["reach_mismatch"] == 1


@pytest.mark.parametrize("config", ["tiny-g500", "tiny-road",
                                    "tiny-g500-sharded"])
def test_pooled_reference_rows_are_the_sequential_rows(config, monkeypatch):
    """Rows solved in the worker pool of a large graph are, bit for bit,
    the rows solved in this process in blocks of ``ROW_BLOCK``."""
    cfg = load_cell(next(w["name"] for w in json.loads(
        (DATA / "BENCHMARK.json").read_text())["workloads"]
        if w["config"] == config), DATA).config
    s, d, w, n, _ = graphs.build(cfg, BIG_SEED)
    ref = reference.Reference(s, d, w, n)
    sources = np.nonzero(np.bincount(s, minlength=n))[0][::3].tolist()
    assert len(sources) > reference.ROW_BLOCK  # more than one block
    sequential = ref.rows(sources)
    monkeypatch.setattr(reference, "POOL_ARCS", 0)
    monkeypatch.setattr(reference, "ROW_WORKERS", 3)
    pooled = ref.rows(sources + sources[:5])
    assert list(pooled) == list(sequential) == sorted(sources)
    for x in sources:
        assert pooled[x].dtype == np.float64
        assert np.array_equal(pooled[x], sequential[x])


def test_bf16_rounding_matches_ml_dtypes():
    ml = pytest.importorskip("ml_dtypes")
    x = np.random.default_rng(0).uniform(0, 1000, 10_000).astype(np.float32)
    x = np.concatenate([x, np.float32([0.0, np.inf, 1.0, 3.0e38])])
    want = x.astype(ml.bfloat16).astype(np.float32)
    assert np.array_equal(reference.round_bf16(x), want)


@pytest.mark.parametrize("cfg", [
    {"generator": "grid_road", "structure_seed": 1, "side": 12,
     "diag_frac": 0.05},
    {"generator": "graph500", "structure_seed": 1, "scale": 9,
     "edgefactor": 16, "a": 0.57, "b": 0.19, "c": 0.19},
])
def test_control_fails_the_comparison(cfg):
    """The reference in bfloat16, put in the program's place, is refused by
    the limits that the shipped configurations state."""
    s, d, w, n, _ = graphs.build(cfg, BIG_SEED)
    ref = reference.Reference(s, d, w, n)
    sources = np.nonzero(np.bincount(s, minlength=n))[0][:6].tolist()
    rows64 = ref.rows(sources)
    exact = [(x, None, rows64[x].astype(np.float32)) for x in sources]
    control = ref.bf16_rows(sources)
    checks = reference.compare([(x, None, control[x]) for x in sources],
                               ref, rows64)
    limit = load_cell("g500-cold-closed").config["limits"]["rel_gap_max"]
    assert checks["rel_gap_max"] > limit
    assert checks["not_fixpoint"] == len(sources)
    assert reference.compare(exact, ref, rows64)["rel_gap_max"] < limit


def _tiny_run(cell, trace=False, tmp_path=None, **hooks):
    return run_cell(load_cell(cell, DATA), BIG_SEED, 0.5, trace,
                    time.perf_counter(), PEAK, trace_dir=tmp_path, **hooks)


@pytest.mark.parametrize("cell", ["tiny-road-open", "tiny-g500-closed"])
def test_tiny_cell_end_to_end(cell, tmp_path):
    result, checks, info = _tiny_run(cell)
    assert result["correct"], result["checks"]
    assert list(result)[-1] == "checks"
    assert result["attempted"] > 0 and result["failed"] == 0
    assert "setup_s" in result["metrics"] and "queries_per_s" in result["metrics"]
    assert info["compiles_in_window"] == 0
    if load_cell(cell, DATA).traffic["loop"] == "closed":  # none cut off
        assert info["answered_in_window"] == result["attempted"]
    traced, _, _ = _tiny_run(cell, trace=True, tmp_path=tmp_path / "t")
    assert traced["correct"]
    assert "answered_count" in traced["metrics"]
    assert {"busy_s", "window_s"} <= set(traced["device"])
    assert set(traced["breakdown"]) == {"device_ops", "idle_gaps"}


def test_traced_run_reads_the_programs_own_trace(tmp_path):
    """A ``--trace 1`` run reads the program's spans from its trace before
    deleting it: the fixture cell's ``round_host_ms_per_chunk.analytics``
    reads the ``serving.round`` spans, and the read's wall time is in the
    ``info``; an untraced run reads no trace."""
    result, _, info = _tiny_run("tiny-road-open", trace=True,
                                tmp_path=tmp_path / "t")
    assert result["correct"], result["checks"]
    host = result["metrics"]["round_host_ms_per_chunk.analytics"]
    assert host["unit"] == "ms" and host["value"] > 0
    assert 0 < info["program_trace_s"] < info["trace_read_s"]
    assert not (tmp_path / "t").exists()
    _, _, untraced = _tiny_run("tiny-road-open")
    assert untraced["program_trace_s"] is None
    assert untraced["trace_read_s"] is None


class _Broken:
    """A backend under the served path with one fault planted."""

    def __init__(self, inner, fault):
        self.inner, self.fault = inner, fault
        self.g, self.criterion = inner.g, inner.criterion
        self.point_queries = getattr(inner, "point_queries", False)
        self.n = inner.n

    def init(self, lanes):
        return self.inner.init(lanes)

    def reset_lanes(self, state, sources, **kw):
        return self.inner.reset_lanes(state, sources, **kw)

    def step(self, state, k, **kw):
        if self.fault == "state_unchanged":
            return state
        return self.inner.step(state, k, **kw)

    def peek(self, state):
        return self.inner.peek(state)

    def take_row(self, state, lane):
        row = self.inner.take_row(state, lane)
        if self.fault == "answer_altered":
            row = row.copy()
            row[np.isfinite(row) & (row > 0)] *= np.float32(1.01)
        if self.fault == "half_lanes_dropped" and lane % 2:
            row = self.inner.take_row(state, lane - 1)  # another lane's answer
        return row


@pytest.mark.parametrize("fault", ["state_unchanged", "answer_altered",
                                   "half_lanes_dropped"])
def test_broken_served_path_is_not_correct(fault, monkeypatch):
    monkeypatch.setattr(serve, "DRAIN_LIMIT_S", 1.0)
    result, checks, _ = _tiny_run(
        "tiny-g500-closed", wrap_backend=lambda b: _Broken(b, fault))
    assert not result["correct"], checks


def test_run_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(ROOT / "perfbench" / "run.py"), "--workload",
         BENCH["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        timeout=300, cwd=ROOT)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "no TPU" in p.stderr
