"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's numbers.

- busy: the union of the intervals in which an op ran on a device, over
  the traced window, averaged over the cell's chips (a chip that ran no op
  counts as idle);
- collectives: ops whose HLO kind is a collective (``COLLECTIVES``, with
  their ``-start``/``-done`` forms). Their total time is the union of the
  synchronous ops' intervals and of the spans from each ``-start`` to its
  ``-done``, within the device's busy time; the exposed part is their self
  time, in which the device does the collective and nothing else. What an
  asynchronous pair's span holds besides is hidden under other ops. Both
  are averaged over the cell's chips;
- idle gaps: the complements of those intervals in the window, each named
  by the innermost host span (``TraceAnnotation``) covering its midpoint;
- op top-list: device time by op name, counted as self time (an op's
  duration less the ops nested in it on the same line).

Device planes are named ``/device:<kind>:<i>``; their ops lie on the line
``XLA Ops``. Host spans are the events of the host planes whose names
start with one of ``HOST_PREFIXES``. ``load`` reads a file once for every
reducer (this one and ``perfbench/program_trace.py``), and each reducer
takes either a path or what ``load`` returned.
"""
from __future__ import annotations

import dataclasses
import glob
import math
import os
import re
from collections import defaultdict

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIXES = ("bench.", "backend.")
TOP = 10
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float  # mean over the cell's chips
    devices: int  # devices that ran an op in the window
    device_ops: list  # [[name, seconds], ...] by self time, top first
    idle_gaps: list  # [[host span name, seconds], ...], largest first
    collective_s: float = 0.0  # mean over the cell's chips
    collective_exposed_s: float = 0.0  # its self time, the same mean


def merge(intervals):
    """Sorted, disjoint union of ``(start, end)`` intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def covered(intervals) -> float:
    return float(sum(e - s for s, e in merge(intervals)))


def gaps(busy, lo, hi):
    """The idle intervals of ``[lo, hi]`` outside the merged ``busy``."""
    out, t = [], lo
    for s, e in merge(clip(busy, lo, hi)):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def self_times(events):
    """``{name: self time}`` of ``(start, end, name)`` events on one line,
    nested ones subtracted from the op that contains them."""
    tot = defaultdict(float)
    stack: list = []  # (end, name) of the ops open at the current start
    for s, e, name in sorted(events, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            stack.pop()
        if stack:  # directly nested: its time is not its parent's own
            tot[stack[-1][1]] -= e - s
        tot[name] += e - s
        stack.append((e, name))
    return dict(tot)


def collective_kind(name: str) -> str | None:
    """The collective an op of :func:`op_name`'s form runs, with ``-start``
    or ``-done`` where it is one half of an asynchronous pair; None for
    any other op."""
    kind = name.rsplit(" ", 1)[-1]
    base = kind.removesuffix("-start").removesuffix("-done")
    return kind if base in COLLECTIVES else None


def collective_spans(events):
    """The intervals in which a collective of ``(start, end, op name)``
    events on one line is in flight: a synchronous op's own interval, and
    for an asynchronous pair the span from the ``-start`` op's start to the
    end of the next ``-done`` of its kind (a pair cut by the window's edge
    runs to that edge)."""
    spans, open_ = [], defaultdict(list)
    for s, e, name in sorted(events):
        kind = collective_kind(name)
        if kind is None:
            continue
        if kind.endswith("-start"):
            open_[kind.removesuffix("-start")].append(s)
        elif kind.endswith("-done"):
            starts = open_[kind.removesuffix("-done")]
            spans.append((starts.pop(0) if starts else -math.inf, e))
        else:
            spans.append((s, e))
    spans += [(s, math.inf) for starts in open_.values() for s in starts]
    return spans


def overlap(a, b) -> float:
    """Length of the intersection of two unions of intervals."""
    a, b = merge(a), merge(b)
    tot, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            tot += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def name_gaps(idle, spans):
    """Sum idle time by the innermost host span covering each gap's
    midpoint (``"host: outside spans"`` where none does). Spans nest, so
    one sweep in time order with a stack of open spans finds it."""
    out = defaultdict(float)
    spans = sorted(spans, key=lambda x: (x[0], -x[1]))
    stack: list = []  # (end, name) of the spans open at the sweep point
    j = 0
    for s, e in sorted(idle, key=lambda x: x[0] + x[1]):
        mid = 0.5 * (s + e)
        while j < len(spans) and spans[j][0] <= mid:
            while stack and stack[-1][0] < spans[j][0]:
                stack.pop()
            stack.append((spans[j][1], spans[j][2]))
            j += 1
        while stack and stack[-1][0] < mid:
            stack.pop()
        out[stack[-1][1] if stack else "host: outside spans"] += e - s
    return out


def summarize(device_events, host_spans, lo, hi,
              chips: int = 1) -> TraceSummary:
    """Numbers of the window ``[lo, hi]`` (seconds on the trace's clock)
    from ``{device: [(start, end, op name)]}`` and host spans, averaged
    over the cell's ``chips``."""
    busy_by_dev, idle_by_name, ops = [], defaultdict(float), defaultdict(float)
    coll = exposed = 0.0
    for events in device_events.values():
        iv = clip([(s, e) for s, e, _ in events], lo, hi)
        if not iv:
            continue
        busy_by_dev.append(covered(iv))
        for name, t in name_gaps(gaps(iv, lo, hi), host_spans).items():
            idle_by_name[name] += t
        inside = [(max(s, lo), min(e, hi), n) for s, e, n in events
                  if e > lo and s < hi]
        for name, t in self_times(inside).items():
            ops[name] += t
            if collective_kind(name) is not None:
                exposed += t
        coll += overlap(clip(collective_spans(events), lo, hi), iv)
    if len(busy_by_dev) > chips:
        raise ValueError(f"{len(busy_by_dev)} devices ran ops in the window "
                         f"of a cell on {chips} chips")
    top_ops = sorted(ops.items(), key=lambda x: -x[1])[:TOP]
    top_gaps = sorted(idle_by_name.items(), key=lambda x: -x[1])[:TOP]
    return TraceSummary(
        window_s=hi - lo,
        busy_s=sum(busy_by_dev) / chips,
        devices=len(busy_by_dev),
        device_ops=[[n, t / chips] for n, t in top_ops],
        idle_gaps=[[n, t / chips] for n, t in top_gaps],
        collective_s=coll / chips,
        collective_exposed_s=exposed / chips,
    )


def op_name(hlo: str) -> str:
    """``%fusion.49 = f32[8,512]{1,0:T(8,128)} fusion(...), ...`` ->
    ``fusion.49 = f32[8,512] fusion``: the op, its output and its kind."""
    lhs, _, rhs = hlo.partition(" = ")
    lhs = lhs.lstrip("%")
    m = re.match(r"\s*(\(.*?\)|\S+)\s+([\w\-]+)\(",
                 re.sub(r"\{[^}]*\}", "", rhs))
    if not m:
        return lhs
    shape = "(...)" if m.group(1).startswith("(") else m.group(1)
    return f"{lhs} = {shape} {m.group(2)}"


@dataclasses.dataclass
class Xplane:
    """One ``.xplane.pb`` file, read once: its bytes, the events of each
    device plane's ``XLA Ops`` and ``XLA Modules`` lines, and the host's
    events of the prefixes asked for, as ``(start, end, name)`` in seconds
    with their names as recorded."""
    data: bytes
    lines: dict  # {device plane: {line name: [(start, end, name)]}}
    host: list  # [(start, end, name)], in the file's order


def load(path, prefixes=HOST_PREFIXES) -> Xplane:
    """Read ``path`` and its ``ProfileData`` once, keeping the host events
    whose names start with one of ``prefixes``."""
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        data = f.read()
    lines, host = {}, []
    for plane in ProfileData.from_serialized_xspace(data).planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    lines.setdefault(plane.name, {}).setdefault(
                        line.name, []).extend(
                        (e.start_ns * 1e-9,
                         (e.start_ns + e.duration_ns) * 1e-9, e.name)
                        for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [(e.start_ns * 1e-9,
                          (e.start_ns + e.duration_ns) * 1e-9, e.name)
                         for e in line.events
                         if e.name.startswith(prefixes)]
    return Xplane(data, lines, host)


def read_xplane(src):
    """``({device plane: [(start, end, op)]}, [(start, end, host span)])``
    in seconds, from one ``.xplane.pb`` file (a path, or its ``load``)."""
    xp = src if isinstance(src, Xplane) else load(src)
    devices, names = {}, {}
    for plane, by_line in xp.lines.items():
        evs = []
        for s, e, raw in by_line.get(OPS_LINE, ()):
            name = names.get(raw)
            if name is None:
                name = names[raw] = op_name(raw)
            evs.append((s, e, name))
        if evs:
            devices[plane] = evs
    spans = [x for x in xp.host if x[2].startswith(HOST_PREFIXES)]
    return devices, spans


def find_xplane(log_dir) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def summarize_file(src, window_span: str = "bench.window",
                   chips: int = 1) -> TraceSummary:
    """Summary of the window that the host span ``window_span`` marks, over
    a cell on ``chips`` chips, from a path or its ``load``."""
    devices, spans = read_xplane(src)
    win = [(s, e) for s, e, n in spans if n == window_span]
    if not win:
        raise ValueError(f"no host span {window_span!r} in the trace")
    lo, hi = win[-1]
    return summarize(devices, spans, lo, hi, chips)
