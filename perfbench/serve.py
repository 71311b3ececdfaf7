"""Drive ``ContinuousBatcher`` through one measured window.

The entry point the window drives is the program's ``ContinuousBatcher``
over the cell's backend, with the wall clock. ``TimedBackend`` is the
benchmark's own delegating proxy: it counts the calls into the backend and
puts a named host span around each, so that the device's idle gaps can be
named by what the host was doing.
"""
from __future__ import annotations

import dataclasses
import gc
import time

import numpy as np
from jax.profiler import TraceAnnotation

from perfbench.traffic import FULL, Traffic

DRAIN_LIMIT_S = 60.0  # how long answers due in the window are waited for
CLOSED_BLOCK = 1024  # closed-loop queries drawn at a time


class TimedBackend:
    """Delegating proxy over an ``EngineBackend``: call counts of ``step`` /
    ``peek`` / ``take_row`` / ``reset_lanes``, each in a ``backend.*``
    host span."""

    CALLS = ("step", "peek", "take_row", "reset_lanes")

    def __init__(self, inner):
        self.inner = inner
        self.g = inner.g
        self.criterion = inner.criterion
        self.point_queries = getattr(inner, "point_queries", False)
        self.calls = dict.fromkeys(self.CALLS, 0)

    @property
    def n(self) -> int:
        return self.inner.n

    def init(self, lanes):
        return self.inner.init(lanes)

    def _timed(self, name, *args, **kw):
        with TraceAnnotation(f"backend.{name}"):
            out = getattr(self.inner, name)(*args, **kw)
        self.calls[name] += 1
        return out

    def step(self, state, k_phases, **kw):
        return self._timed("step", state, k_phases, **kw)

    def peek(self, state):
        return self._timed("peek", state)

    def take_row(self, state, lane):
        return self._timed("take_row", state, lane)

    def reset_lanes(self, state, sources, **kw):
        return self._timed("reset_lanes", state, sources, **kw)

    def snapshot(self):
        return dict(self.calls)


@dataclasses.dataclass
class Window:
    """What one measured window did, as the metric readers see it."""

    loop: str
    seconds: float  # measured window length (host clock); a closed loop's
    #   runs on from the time's end until what it sent is answered
    t0: float  # window start on the host clock
    requests: list  # every Request submitted in the window, in order
    answered_in_window: int
    lateness_s: list  # open loop: submit time minus scheduled time
    chunks: int  # backend.step calls (engine chunks) in the window
    trips: int  # engine phases advanced in the window
    unanswered: int = 0  # set after the drain


def _submit(server, src, tgt, t_arrival):
    return server.submit(int(src), t_arrival=t_arrival,
                         target=None if tgt == FULL else int(tgt))


def run_window(server, backend: TimedBackend, traffic: Traffic,
               seconds: float, clock) -> Window:
    """Offer the cell's traffic for ``seconds`` of wall time.

    A closed loop starts empty and adds one request per scheduling round
    until ``outstanding`` are in the system, then sends one for each
    answered: the lanes start a chunk apart and stay out of step, as a
    client's do once it has run a while, instead of finishing in waves of
    all lanes together. It sends requests ahead of the lanes that answer
    them, so its window closes only once they are answered: when the time
    is up it sends nothing more, steps until everything it sent is
    answered (up to ``DRAIN_LIMIT_S``), and reads the clock after that.
    All of that work counts, over all of that time: no solve in flight is
    cut off at the close, and none is counted unfinished."""
    loop = traffic.params["loop"]
    reqs: list = []
    lateness: list = []
    calls0 = backend.snapshot()
    trips0 = server.metrics.engine_trips
    answered = retired = 0
    if loop == "open":
        times, srcs, tgts = traffic.schedule(seconds)
    else:
        outstanding = int(traffic.params["outstanding"])
        srcs, tgts = traffic.draw(CLOSED_BLOCK)
    i = 0

    def send(now):  # closed loop: the next query of the mix
        nonlocal i, srcs, tgts
        if i == srcs.size:
            srcs, tgts = traffic.draw(CLOSED_BLOCK)
            i = 0
        reqs.append(_submit(server, srcs[i], tgts[i], now))
        i += 1

    def advance():
        nonlocal answered, retired
        with TraceAnnotation("bench.step"):
            done = server.step()
        answered += sum(r.outcome == "ok" for r in done)
        retired += len(done)
        return done

    t0 = clock()
    end = t0 + seconds
    with TraceAnnotation("bench.window"):
        while True:
            now = clock()
            if now >= end:
                break
            if loop == "open":
                with TraceAnnotation("bench.arrivals"):
                    while i < times.size and t0 + times[i] <= now:
                        reqs.append(_submit(server, srcs[i], tgts[i],
                                            t0 + times[i]))
                        lateness.append(now - (t0 + times[i]))
                        i += 1
                if server.idle:
                    nxt = t0 + times[i] if i < times.size else end
                    with TraceAnnotation("bench.idle_wait"):
                        time.sleep(max(0.0, min(nxt, end) - clock()))
                    continue
            elif len(reqs) - retired < outstanding:
                with TraceAnnotation("bench.arrivals"):
                    send(now)
            done = advance()
            now = clock()
            if loop == "closed" and now < end:
                with TraceAnnotation("bench.arrivals"):
                    for _ in done:
                        send(now)
        if loop == "closed":
            stop = clock() + DRAIN_LIMIT_S
            with TraceAnnotation("bench.close"):
                while not server.idle and clock() < stop:
                    advance()
    t1 = clock()
    calls1 = backend.snapshot()
    if loop == "open":  # arrivals due before the close that the loop passed
        for j in range(i, times.size):
            reqs.append(_submit(server, srcs[j], tgts[j], t0 + times[j]))
            lateness.append(t1 - (t0 + times[j]))
    return Window(
        loop=loop, seconds=t1 - t0, t0=t0, requests=reqs,
        answered_in_window=answered, lateness_s=lateness,
        chunks=calls1["step"] - calls0["step"],
        trips=server.metrics.engine_trips - trips0,
    )


def drain(server, window: Window, clock):
    """Wait, up to ``DRAIN_LIMIT_S``, for every request of the window."""
    stop = clock() + DRAIN_LIMIT_S
    with TraceAnnotation("bench.drain"):
        while not server.idle and clock() < stop:
            server.step()
    window.unanswered = sum(r.outcome != "ok" for r in window.requests)


def warm_up(make_server):
    """Compile the cell's served programs before the window, on a throwaway
    server over the same backend: admission (with an s->t lane where the
    server takes them), one phase of the step and the peek, twice, and a
    harvest. The chunk length is a traced operand, so one phase compiles
    the program that the window runs with its default chunk."""
    server = make_server(phases_per_step=1)
    server.submit(0)
    if server.point_queries:
        server.submit(1 % server.backend.n, target=0)
    server.step()
    # a second round admits into and steps the state that the first
    # returned: a mesh backend's is sharded where its fresh state is not
    server.submit(2 % server.backend.n)
    server.step()
    server.backend.take_row(server.state, 0)


class GcPauses:
    """Collections of the Python garbage collector while active, by
    generation, with the longest pause: host stalls that the device sees
    as idle time."""

    def __init__(self):
        self.count = [0, 0, 0]
        self.longest_s = 0.0
        self._t0 = 0.0

    def _on(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            self.count[info["generation"]] += 1
            self.longest_s = max(self.longest_s, time.perf_counter() - self._t0)

    def __enter__(self):
        gc.callbacks.append(self._on)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._on)

    def summary(self) -> dict:
        return {"collections": self.count, "longest_s": self.longest_s}


def percentile(xs, q) -> float:
    """Linear-interpolated percentile, as ``numpy.percentile`` gives it."""
    return float(np.percentile(np.asarray(xs, np.float64), q))
