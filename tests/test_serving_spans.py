"""The scheduler's spans on the profiler's clock.

A profiler trace around a few ``ContinuousBatcher.step`` calls on a tiny
grid, read back from the host plane: ``serving.round`` holds
``serving.admit``, ``serving.dispatch``, ``serving.sync`` and
``serving.harvest`` (with one ``serving.take_row`` per lane taken), with no
``obs`` given, the same through ``ResilientBatcher``; and with a registry the
``serving.round_host_s`` histogram holds each round's host time.
"""
import glob
import os

import jax
import pytest
from jax.profiler import ProfileData

from repro.graphs import grid_road
from repro.obs import Observability
from repro.serving import ContinuousBatcher, ResilientBatcher, StaticBackend

ROUND_PARTS = ("serving.admit", "serving.dispatch", "serving.sync",
               "serving.harvest")


class CountingBackend:
    """Delegating proxy over a backend that counts its ``step`` calls."""

    def __init__(self, inner):
        self.inner = inner
        self.steps = 0

    def __getattr__(self, name):
        return getattr(self.inner, name)

    def step(self, *args, **kw):
        self.steps += 1
        return self.inner.step(*args, **kw)


def _host_spans(log_dir):
    """``[(start_ns, end_ns, name)]`` of the ``serving.*`` host events."""
    path = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            out += [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                    for e in line.events if e.name.startswith("serving.")]
    return sorted(out)


def _inside(inner, outer) -> bool:
    return outer[0] <= inner[0] and inner[1] <= outer[1]


@pytest.fixture(scope="module")
def grid():
    return grid_road(6, 6, seed=3)


def _serve_traced(tmp_path, server_cls, g, **kw):
    backend = CountingBackend(StaticBackend(g))
    server = server_cls(g, lanes=2, backend=backend, phases_per_step=4, **kw)
    server.submit(0)
    server.drain()  # compiles outside the trace
    steps0, rounds = backend.steps, 0
    jax.profiler.start_trace(str(tmp_path))
    try:
        for s in (5, 11, 17):
            server.submit(s)
        while not server.idle:
            server.step()
            rounds += 1
    finally:
        jax.profiler.stop_trace()
    return _host_spans(str(tmp_path)), backend.steps - steps0, rounds


@pytest.mark.parametrize("server_cls", [ContinuousBatcher, ResilientBatcher])
def test_round_spans_nest_on_the_profiler_clock(tmp_path, grid, server_cls):
    spans, dispatched, rounds = _serve_traced(tmp_path, server_cls, grid)
    by = {}
    for s in spans:
        by.setdefault(s[2], []).append(s)
    assert len(by["serving.round"]) == rounds
    assert len(by["serving.dispatch"]) == dispatched > 0
    assert len(by["serving.sync"]) == dispatched
    assert len(by["serving.admit"]) == rounds
    assert len(by["serving.take_row"]) == 3  # one per request answered
    for name in ROUND_PARTS:
        for part in by[name]:
            assert sum(_inside(part, r) for r in by["serving.round"]) == 1
    for take in by["serving.take_row"]:
        assert sum(_inside(take, h) for h in by["serving.harvest"]) == 1
    for r in by["serving.round"]:  # the parts of a round come in order
        parts = [s for s in spans if s[2] in ROUND_PARTS and _inside(s, r)]
        names = [p[2] for p in parts]
        assert names[0] == "serving.admit"
        assert names in (["serving.admit"],
                         ["serving.admit", "serving.dispatch", "serving.sync"],
                         ["serving.admit", "serving.dispatch", "serving.sync",
                          "serving.harvest"])
        ends = [p[1] for p in parts]
        assert ends == sorted(ends)


def test_round_host_time_lands_in_the_registry(grid):
    obs = Observability.enabled()
    server = ContinuousBatcher(grid, lanes=2, phases_per_step=4, obs=obs)
    for s in (0, 5, 11):
        server.submit(s)
    server.drain()
    rounds = server.metrics.steps
    hist = obs.registry.histogram("serving.round_host_s")
    assert hist.count == rounds > 0
    assert 0.0 <= hist.min <= hist.max
    chrome = [e for e in obs.tracer.events() if e["ph"] == "X"]
    walls = [e["dur"] * 1e-6 for e in chrome if e["name"] == "serving.round"]
    assert len(walls) == rounds
    # each round's host time is its wall less its waits on the device
    assert hist.max <= max(walls) + 1e-3
    assert {e["name"] for e in chrome} == {
        "serving.round", *ROUND_PARTS, "serving.take_row"}
