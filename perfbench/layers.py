"""Metric arithmetic shared by the readers in ``perfbench/metrics/``.

Every reader is ``read(run) -> float | None`` over a :class:`Run`; one that
finds nothing to read returns None and the metric is left out of the line.
Percentiles are taken over all requests of the window, never from medians
of chunks.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from perfbench import program_trace
from perfbench.serve import Window, percentile


@dataclasses.dataclass
class Run:
    setup_s: float
    window: Window
    drain_end: float  # host clock when the drain stopped
    n: int
    lanes: int
    trace: object | None  # trace.TraceSummary of the window (--trace 1)
    ref_rows: dict  # source -> float64 reference row
    reference: object  # reference.Reference
    peak: dict  # peaks.PEAKS entry of the device
    chips: int = 1  # chips the cell serves on
    program: object | None = None  # program_trace.ProgramTrace (--trace 1)


def _in_window(run: Run):
    end = run.window.t0 + run.window.seconds
    return [r for r in run.window.requests
            if r.outcome == "ok" and r.t_completed <= end]


def _engine(reqs):
    return [r for r in reqs if not (r.cache_hit or r.coalesced)]


def queries_per_s(run: Run):
    """Requests answered in the window over its length (a closed loop's
    window includes answering what it sent before the time was up)."""
    return run.window.answered_in_window / run.window.seconds


def setup_s(run: Run):
    return run.setup_s


def _latency_ms(run: Run, q: float):
    """From each request's scheduled time to its answer; one still
    unanswered when the drain stopped counts at its age then."""
    if run.window.loop != "open" or not run.window.requests:
        return None
    lat = [(r.t_completed if r.outcome == "ok" else run.drain_end)
           - r.t_arrival for r in run.window.requests]
    return 1e3 * percentile(lat, q)


def latency_p95_ms(run: Run):
    return _latency_ms(run, 95)


def cache_answer_share(run: Run):
    done = _in_window(run)
    if not done:
        return None
    return 100.0 * sum(r.cache_hit or r.coalesced for r in done) / len(done)


def phases_per_query(run: Run):
    eng = _engine(_in_window(run))
    if not eng:
        return None
    return float(np.mean([r.phases for r in eng]))


def relax_ms_per_phase(run: Run):
    """Device time under the program's ``sssp.relax`` scope per phase."""
    return program_trace.relax_device_ms_per_phase(run.program,
                                                   run.window.trips)


def criteria_ms_per_phase(run: Run):
    """Device time under the settle criterion's scopes per phase."""
    return program_trace.criteria_device_ms_per_phase(run.program,
                                                      run.window.trips)


def chunk_gap_ms(run: Run):
    """Device idle between consecutive step executions, per chunk."""
    return program_trace.chunk_gap_ms(run.program)


def round_host_ms_per_chunk(run: Run):
    """Host time of the program's ``serving.round`` spans, less the waits
    in ``serving.sync`` and ``serving.take_row``, per dispatched chunk."""
    return program_trace.round_host_ms_per_chunk(run.program)


def device_idle_share(run: Run):
    if run.trace is None or run.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - run.trace.busy_s / run.trace.window_s)


def phase_device_ms(run: Run):
    if run.trace is None or not run.window.trips:
        return None
    return 1e3 * run.trace.busy_s / run.window.trips


def _collective_ms_per_phase(run: Run, field: str):
    t = run.trace
    if t is None or t.collective_s <= 0 or not run.window.trips:
        return None
    return 1e3 * getattr(t, field) / run.window.trips


def collective_ms_per_phase(run: Run):
    """Device time with a collective in flight, per chip, over the phases
    advanced; None where the window holds no collective op."""
    return _collective_ms_per_phase(run, "collective_s")


def collective_exposed_ms_per_phase(run: Run):
    """The part of it in which the chip runs the collective and nothing
    else (the collectives' self time), over the phases advanced."""
    return _collective_ms_per_phase(run, "collective_exposed_s")


def solve_bytes(reached_arcs: int, n: int, lanes: int) -> float:
    """Least HBM bytes of one full-row solve: each arc reached (4-byte id,
    4-byte weight) read once and shared by the ``lanes`` lanes of a phase,
    and the row of ``n`` float32 distances written once."""
    return reached_arcs * 8.0 / lanes + n * 4.0


def solve_hbm_roofline(run: Run):
    """Least time of the full-row engine solves answered in the traced
    window at the HBM peak of the cell's chips, over the busy time per chip
    in it."""
    if run.trace is None or run.trace.busy_s <= 0:
        return None
    rows = [r for r in _engine(_in_window(run)) if r.target is None]
    if not rows:
        return None
    need = sum(solve_bytes(run.reference.reached_arcs(run.ref_rows[r.source]),
                           run.n, run.lanes) for r in rows)
    return (100.0 * need / (run.chips * run.peak["hbm_bytes_per_s"])
            / run.trace.busy_s)
