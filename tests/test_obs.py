"""Tests for the observability layer (PR 7).

Covers the three contracts DESIGN.md Sec. 11 states:

  * **trace validity** — the tracer's export is structurally valid Chrome
    trace-event JSON (golden-file check: meta first, ts-sorted, matched
    B/E nesting, well-formed X/C events), the validator rejects each
    malformation class, and the ``python -m repro.obs`` CLI round-trips a
    file unchanged in event count.
  * **aggregate exactness under windowing** — histogram count/sum/min/max
    and every ServingMetrics mean/max survive window wrap bit-exactly;
    only percentile keys read the bounded windows. Includes the
    regression for the pre-PR-7 ``cache_hit_rate`` denominator (coalesced
    followers never consulted the cache) and windowed-max bugs.
  * **telemetry attribution** — per-criterion settle attribution from the
    batched stepper partitions the settled set: integer-exact sums to
    ``settled_per_phase``, and telemetry-off results stay bit-identical.
"""
import json

import numpy as np
import pytest

from repro.core.static_engine import run_phased_static_batch
from repro.graphs import uniform_gnp
from repro.obs import Observability
from repro.obs.__main__ import main as obs_main
from repro.obs.registry import Histogram, MetricsRegistry, prom_name
from repro.obs.tracer import (
    NULL_TRACER,
    Tracer,
    load_trace,
    validate_events,
    validate_trace_file,
)
from repro.serving.metrics import ServingMetrics
from repro.serving.queue import Request


class FakeClock:
    """Deterministic injectable clock: each read advances 1 ms."""

    def __init__(self):
        self.t = 100.0

    def __call__(self) -> float:
        self.t += 1e-3
        return self.t


def _golden_tracer() -> Tracer:
    """One of everything the tracer can emit, on a deterministic clock."""
    tr = Tracer(clock=FakeClock())
    tr.name_thread("lane 0", "serving lane 0")
    tr.name_thread("scheduler", "scheduler")
    tr.begin("src 7", cat="request", tid="lane 0", source=7)
    with tr.span("step", cat="step", tid="scheduler", busy=1):
        with tr.span("chunk", cat="chunk", tid="scheduler"):
            pass
    tr.counter("scheduler load", {"queue_depth": 3, "busy_lanes": 1})
    tr.instant("cache hit", cat="request", tid="scheduler", source=7)
    tr.end("src 7", cat="request", tid="lane 0", phases=12)
    return tr


# ---------------------------------------------------------------------------
# trace validity (golden file + validator + CLI round-trip)
# ---------------------------------------------------------------------------


def test_trace_export_is_valid_chrome_trace(tmp_path):
    tr = _golden_tracer()
    assert validate_events(tr.events()) == []

    path = tmp_path / "trace.json"
    tr.export(str(path))
    assert validate_trace_file(str(path)) == []

    doc = json.loads(path.read_text())
    events = doc["traceEvents"]
    assert events == tr.events()  # export round-trips the event list
    # golden structure: metadata first, then body sorted by ts
    metas = [e for e in events if e["ph"] == "M"]
    body = [e for e in events if e["ph"] != "M"]
    assert events[: len(metas)] == metas
    assert all(e["ph"] == "M" and e["name"] == "thread_name" for e in metas)
    ts = [e["ts"] for e in body]
    assert ts == sorted(ts)
    # one of each emitted phase made it through
    assert {e["ph"] for e in body} == {"X", "B", "E", "i", "C"}
    for e in body:
        assert e["pid"] == "repro"
        if e["ph"] == "X":
            assert e["dur"] >= 0
    # span nesting recorded as X events: chunk inside step on one tid
    step = next(e for e in body if e["name"] == "step")
    chunk = next(e for e in body if e["name"] == "chunk")
    assert step["ts"] <= chunk["ts"]
    assert chunk["ts"] + chunk["dur"] <= step["ts"] + step["dur"]


@pytest.mark.parametrize(
    "mutate, phrase",
    [
        (lambda evs: evs.append({"ph": "Z", "name": "x", "pid": 1, "tid": 1,
                                 "ts": 9e9}), "unknown ph"),
        (lambda evs: evs.append({"ph": "E", "name": "never-opened",
                                 "pid": "repro", "tid": "lane 9",
                                 "ts": 9e9}), "no open 'B'"),
        (lambda evs: evs.append({"ph": "B", "name": "left-open",
                                 "pid": "repro", "tid": "lane 9",
                                 "ts": 9e9}), "never closed"),
        (lambda evs: evs.append({"ph": "i", "name": "time-travel",
                                 "pid": "repro", "tid": "m", "ts": -1.0}),
         "bad ts"),
        (lambda evs: evs.insert(0, {"ph": "i", "name": "unsorted",
                                    "pid": "repro", "tid": "m", "ts": 9e9}),
         "not sorted"),
        (lambda evs: evs.append({"ph": "C", "name": "load", "pid": "repro",
                                 "tid": "c", "ts": 9e9,
                                 "args": {"depth": "three"}}),
         "numeric args"),
        (lambda evs: evs.append({"ph": "X", "name": "negative-span",
                                 "pid": "repro", "tid": "m", "ts": 9e9,
                                 "dur": -5}), "bad dur"),
    ],
)
def test_validator_rejects_malformed_events(mutate, phrase):
    events = _golden_tracer().events()
    mutate(events)
    errors = validate_events(events)
    assert errors, f"expected a {phrase!r} error"
    assert any(phrase in e for e in errors), errors


def test_mismatched_be_names_rejected():
    tr = Tracer(clock=FakeClock())
    tr.begin("alpha", tid="t")
    tr.end("beta", tid="t")
    errors = validate_events(tr.events())
    assert any("does not match" in e for e in errors), errors


def test_cli_validate_export_dashboard(tmp_path, capsys):
    tr = _golden_tracer()
    trace = tmp_path / "trace.json"
    tr.export(str(trace))
    rt = tmp_path / "trace_rt.json"

    assert obs_main(["validate", str(trace)]) == 0
    assert obs_main(["export", str(trace), "-o", str(rt)]) == 0
    assert obs_main(["validate", str(rt)]) == 0
    assert len(load_trace(str(rt))) == len(load_trace(str(trace)))

    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"traceEvents": [
        {"ph": "E", "name": "x", "pid": 1, "tid": 1, "ts": 0}]}))
    assert obs_main(["validate", str(bad)]) == 1

    reg = MetricsRegistry()
    reg.counter("serving.completed", "done").inc(3)
    reg.histogram("serving.latency_s").observe(0.25)
    report = tmp_path / "report.json"
    report.write_text(reg.to_json())
    capsys.readouterr()
    assert obs_main(["dashboard", str(report)]) == 0
    out = capsys.readouterr().out
    assert "serving.completed" in out and "serving.latency_s" in out


def test_cli_dashboard_portfolio_view(tmp_path, capsys):
    # a saved tuning ledger renders the portfolio view: per-family win
    # rates over lane counts, qps, and settle-attribution share drift
    ledger = {
        "portfolio:flat-uniform-shallow:b4:delta@x2:sliced": {
            "qps": 40.0, "settle_attribution": {"light": 6, "heavy": 2},
        },
        "portfolio:flat-uniform-shallow:b4:instatic|outstatic:padded": {
            "qps": 10.0,
            "settle_attribution": {"instatic": 9, "outstatic": 1},
        },
        "portfolio:skew-uniform-shallow:b4:delta@x2:sliced": {
            "qps": 20.0, "settle_attribution": {"light": 2, "heavy": 6},
        },
        "mosaic:relax:n64:d8:b1:l1": {"block_rows": 64},  # non-portfolio key
    }
    path = tmp_path / "ledger.json"
    path.write_text(json.dumps(ledger))
    capsys.readouterr()
    assert obs_main(["dashboard", str(path)]) == 0
    out = capsys.readouterr().out
    assert "family flat-uniform-shallow" in out
    assert "family skew-uniform-shallow" in out
    assert "delta@x2:sliced" in out and "win 100%" in out
    assert "instatic|outstatic:padded" in out and "win   0%" in out
    # shares render normalised; drift is measured against the fleet mean
    assert "light=0.75" in out and "heavy=0.75" in out
    # delta@x2's shares flip between families: each sits 0.25 from the
    # fleet mean of 0.5; the one-family engine drifts 0.00 by definition
    assert "drift 0.25" in out and "drift 0.00" in out
    assert "mosaic:relax" not in out


def test_disabled_tracer_is_inert():
    with NULL_TRACER.span("x"):  # a profiler annotation only
        pass
    NULL_TRACER.begin("x")
    NULL_TRACER.end("x")
    NULL_TRACER.instant("x")
    NULL_TRACER.counter("x", {"v": 1})
    NULL_TRACER.name_thread("t", "thread")
    assert len(NULL_TRACER) == 0 and NULL_TRACER.events() == []


# the golden tracer's export, as the tracer wrote it before its spans also
# became profiler annotations: the Chrome sink must not change
GOLDEN_EVENTS = [
    {"ph": "M", "name": "thread_name", "pid": "repro", "tid": "lane 0",
     "ts": 0, "args": {"name": "serving lane 0"}},
    {"ph": "M", "name": "thread_name", "pid": "repro", "tid": "scheduler",
     "ts": 0, "args": {"name": "scheduler"}},
    {"ph": "B", "name": "src 7", "cat": "request", "pid": "repro",
     "tid": "lane 0", "ts": 1000.0, "args": {"source": 7}},
    {"ph": "X", "name": "step", "cat": "step", "pid": "repro",
     "tid": "scheduler", "ts": 2000.0, "dur": 3000.0, "args": {"busy": 1}},
    {"ph": "X", "name": "chunk", "cat": "chunk", "pid": "repro",
     "tid": "scheduler", "ts": 3000.0, "dur": 1000.0},
    {"ph": "C", "name": "scheduler load", "cat": "default", "pid": "repro",
     "tid": "counters", "ts": 6000.0,
     "args": {"queue_depth": 3, "busy_lanes": 1}},
    {"ph": "i", "name": "cache hit", "cat": "request", "pid": "repro",
     "tid": "scheduler", "ts": 7000.0, "s": "t", "args": {"source": 7}},
    {"ph": "E", "name": "src 7", "cat": "request", "pid": "repro",
     "tid": "lane 0", "ts": 8000.0, "args": {"phases": 12}},
]


def _profiled_host_events(log_dir) -> set:
    import glob
    import os

    from jax.profiler import ProfileData

    path = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    return {e.name for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events}


def test_spans_are_profiler_annotations_enabled_or_not(tmp_path):
    """Both sinks: a disabled tracer's span still enters the profiler's
    annotation and keeps no Chrome event; an enabled tracer's span is
    annotated too, and its Chrome export is the one it always was."""
    import jax

    jax.profiler.start_trace(str(tmp_path))
    try:
        with NULL_TRACER.span("obs.test.disabled", cat="step", busy=1):
            pass
        tr = _golden_tracer()
    finally:
        jax.profiler.stop_trace()
    assert len(NULL_TRACER) == 0 and NULL_TRACER.events() == []
    assert tr.events() == GOLDEN_EVENTS
    names = _profiled_host_events(str(tmp_path))
    assert {"obs.test.disabled", "step", "chunk"} <= names
    # instants, counters and the request B/E spans stay Chrome-only
    assert not names & {"src 7", "scheduler load", "cache hit"}


def test_tracer_event_bound_counts_drops():
    tr = Tracer(clock=FakeClock(), max_events=2)
    for k in range(5):
        tr.instant(f"e{k}")
    assert len(tr._events) == 2 and tr.dropped == 3
    assert validate_events(tr.events()) == []  # truncated stays valid


# ---------------------------------------------------------------------------
# aggregate exactness under windowing
# ---------------------------------------------------------------------------


def _check_hist_exact(values, window):
    h = Histogram("t", window=window)
    for v in values:
        h.observe(v)
    assert h.count == len(values)
    seq_sum = 0.0  # same left-to-right accumulation the histogram does —
    for v in values:  # "exact" means never-forgotten, not re-ordered
        seq_sum += float(v)
    assert h.sum == seq_sum
    assert h.min == min(values) and h.max == max(values)
    # the window holds exactly the last `window` observations
    tail = values[-window:]
    assert list(h._window) == [float(v) for v in tail]
    assert h.percentile(50) == pytest.approx(float(np.percentile(tail, 50)))


def test_histogram_aggregates_exact_under_windowing():
    rng = np.random.default_rng(0)
    for trial in range(50):
        window = int(rng.integers(1, 12))
        count = int(rng.integers(1, 80))
        scale = float(10.0 ** rng.integers(-6, 7))
        values = (rng.standard_normal(count) * scale).tolist()
        _check_hist_exact(values, window)
    # adversarial shape: true max exits the window immediately
    _check_hist_exact([1e9] + [0.001] * 100, window=4)


def test_histogram_aggregates_exact_hypothesis():
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    finite = st.floats(allow_nan=False, allow_infinity=False,
                       width=32)

    @given(values=st.lists(finite, min_size=1, max_size=64),
           window=st.integers(1, 8))
    @settings(max_examples=100, deadline=None)
    def prop(values, window):
        _check_hist_exact(values, window)

    prop()


def _req(rid, *, latency, wait=0.0, cache_hit=False, coalesced=False,
         phases=None, source=0):
    return Request(
        req_id=rid, source=source, t_arrival=float(rid),
        t_admitted=float(rid) + wait, t_completed=float(rid) + latency,
        phases=phases, cache_hit=cache_hit, coalesced=coalesced,
    )


def test_serving_metrics_exact_max_after_window_wrap():
    """Regression: pre-PR-7 report() took max() over bounded deques, so a
    wrapped window forgot the true latency/phases maxima."""
    m = ServingMetrics(lanes=4, window=8)
    m.record_completion(_req(0, latency=9.5, wait=2.5, phases=70))
    for k in range(1, 30):  # flush the window with small completions
        m.record_completion(_req(k, latency=0.01, wait=0.0, phases=3))
    assert 9.5 not in m._latencies  # the window really did forget it
    rep = m.report()
    assert rep["latency_max_s"] == 9.5
    assert rep["queue_wait_max_s"] == 2.5
    assert rep["phases_per_query_max"] == 70
    total = 70 + 3 * 29
    assert rep["phases_per_query_mean"] == pytest.approx(total / 30)
    assert rep["latency_mean_s"] == pytest.approx((9.5 + 0.01 * 29) / 30)


def test_serving_metrics_cache_hit_rate_denominator():
    """Regression: cache_hit_rate must exclude coalesced followers — they
    attached to an in-flight query and never consulted the cache."""
    m = ServingMetrics(lanes=2)
    for k in range(2):
        m.record_completion(_req(k, latency=0.1, cache_hit=True))
    for k in range(2, 4):
        m.record_completion(_req(k, latency=0.1, coalesced=True))
    for k in range(4, 10):
        m.record_completion(_req(k, latency=0.1, phases=5))
    rep = m.report()
    assert rep["queries_completed"] == 10
    assert rep["engine_served"] == 6
    assert rep["cache_hit_rate"] == pytest.approx(2 / (2 + 6))
    assert rep["coalesce_rate"] == pytest.approx(2 / 10)
    # phases statistics are engine-served-only (hits/followers spent none)
    assert rep["phases_per_query_mean"] == pytest.approx(5.0)


def test_serving_metrics_streams_into_registry():
    reg = MetricsRegistry()
    m = ServingMetrics(lanes=2, registry=reg)
    m.record_completion(_req(0, latency=0.5, phases=9))
    m.record_completion(_req(1, latency=0.2, cache_hit=True))
    m.record_step(busy_lanes=1, trips_advanced=4)
    assert reg.get("serving.completed").value == 2
    assert reg.get("serving.cache_hits").value == 1
    h = reg.get("serving.latency_s")
    assert h.count == 2 and h.max == 0.5
    assert reg.get("serving.engine_trips").value == 4
    prom = reg.to_prometheus()
    assert "serving_latency_s_count 2" in prom
    assert prom_name("serving.latency_s") == "serving_latency_s"


def test_registry_kind_conflict_and_counter_monotonicity():
    reg = MetricsRegistry()
    reg.counter("x.y")
    with pytest.raises(TypeError):
        reg.gauge("x.y")
    with pytest.raises(ValueError):
        reg.counter("x.y").inc(-1)


# ---------------------------------------------------------------------------
# stepper telemetry attribution
# ---------------------------------------------------------------------------


def test_attribution_partitions_settled_and_off_path_identical():
    g = uniform_gnp(160, 8.0 / 160, seed=3)
    srcs = [0, 40, 80]
    base = run_phased_static_batch(g, srcs, criterion="in|out",
                                   trace_len=g.n + 1)
    tele = run_phased_static_batch(g, srcs, criterion="in|out",
                                   trace_len=g.n + 1, telemetry=True)
    # telemetry must not perturb the solve
    assert np.array_equal(np.asarray(base.dist), np.asarray(tele.dist))
    assert np.array_equal(np.asarray(base.settled_per_phase),
                          np.asarray(tele.settled_per_phase))
    # off path carries no rings; on path partitions the settled set exactly
    assert base.settle_attribution is None
    assert base.fringe_per_phase is None and base.relax_per_phase is None
    attr = np.asarray(tele.settle_attribution)
    sp = np.asarray(tele.settled_per_phase)
    assert attr.shape[:2] == sp.shape and attr.shape[2] == 2  # in, out
    assert np.array_equal(attr.sum(axis=2), sp)
    assert (attr >= 0).all()
    # total attributed settles == reachable vertices across the batch
    assert attr.sum() == np.isfinite(np.asarray(tele.dist)).sum()
    # fringe/relax rings populated on the same phases the solve ran
    fr = np.asarray(tele.fringe_per_phase)
    phases = np.asarray(tele.phases)
    for b in range(len(srcs)):
        assert fr[b, 0] == 1  # phase 0 fringe is the source alone
        assert (fr[b, : phases[b]] > 0).all()


def test_observability_bundle_modes():
    on = Observability.enabled()
    off = Observability.disabled()
    assert on.tracer.enabled and not off.tracer.enabled
    with on.tracer.span("s"):
        pass
    assert len(on.tracer.events()) == 1
    with off.tracer.span("s"):
        pass
    assert len(off.tracer) == 0 and off.tracer.events() == []
