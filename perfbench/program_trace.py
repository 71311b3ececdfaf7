"""Read the program's own spans and scopes from a profiler trace.

``perfbench/trace.py`` names the device's time by HLO op and its idle gaps
by the benchmark's spans. This module reads what the program itself puts
into the same ``.xplane.pb``, on the same clock:

- the host spans whose names start with ``serving.``, which
  ``ContinuousBatcher`` enters in every scheduling round (``serving.round``
  over ``serving.admit``, ``serving.dispatch``, ``serving.sync`` and
  ``serving.harvest`` with one ``serving.take_row`` per lane taken);
- the ``jax.named_scope`` of each device op (``sssp.prime``,
  ``sssp.out_scan``, ``sssp.thresholds``, ``sssp.settle``, ``sssp.relax``,
  ``sssp.update``), and the program each op ran in;
- the step program: whatever program ``serving.dispatch`` launches, which
  is named after the backend (``jit__step_batch_impl`` on one chip,
  ``jit_step`` over a mesh). The round launches nothing between the
  dispatch and the ``peek`` of ``serving.sync``, so the step program is
  the one that each device runs just before each execution of
  ``jit__peek``, the serving layer's read that every backend shares.

``jax.profiler.ProfileData`` gives the ops and the ``XLA Modules`` line, but
not the stats of an op's metadata, where the scope path lies (the ``tf_op``
stat, e.g. ``jit(_step_batch_impl)/while/body/sssp.relax/gather:``). A small
protobuf wire-format decoder reads those entries from the file; the ops are
joined to them by metadata name, and by program id where a name collides.
An op whose path holds no
``sssp.`` scope is counted under its program's name. An op with no
``tf_op`` at all is one XLA made itself (a relayout copy, the loop it builds
for a wide reduction) and takes the scope of the op that ran just before it
in the same execution of its program, where that op had one; the seconds so
named are kept apart (``inherited_s``).

A trace of a program that has no such spans or scopes reads as empty here:
each metric function then returns None.
"""
from __future__ import annotations

import bisect
import dataclasses
import re
from collections import Counter, defaultdict

from perfbench import trace

SCOPE_PREFIX = "sssp."
SPAN_PREFIX = "serving."
SYNC_PROGRAM = "jit__peek"
# (host span, the program it runs, whether the host waits for its end);
# ``serving.dispatch`` runs the step program, found by ``step_program``
WAITED_CALLS = (("serving.sync", SYNC_PROGRAM, True),
                ("serving.take_row", "jit__take_row", True))
MODULES_LINE = trace.MODULES_LINE
HOST_PREFIXES = (SPAN_PREFIX, "bench.")
CRITERIA_SCOPES = ("sssp.out_scan", "sssp.thresholds", "sssp.settle")
RELAX_SCOPE = "sssp.relax"
HOST_WAITS = ("serving.sync", "serving.take_row")

# field numbers of the XSpace proto (tsl/profiler/protobuf/xplane.proto)
XSPACE_PLANES = 1
XPLANE_NAME, XPLANE_EVENT_METADATA, XPLANE_STAT_METADATA = 2, 4, 5
XEVENT_METADATA_NAME, XEVENT_METADATA_STATS = 2, 5
XSTAT_METADATA_ID, XSTAT_UINT64, XSTAT_STR, XSTAT_REF = 1, 3, 5, 7
XSTAT_METADATA_NAME = 2
MAP_VALUE = 2  # a map entry is {key = 1, value = 2}


# -- protobuf wire format ---------------------------------------------------


def _varint(buf, i: int):
    out = shift = 0
    while True:
        byte = buf[i]
        i += 1
        out |= (byte & 0x7F) << shift
        shift += 7
        if byte < 0x80:
            return out, i


def fields(buf, lo: int = 0, hi: int | None = None):
    """``(field number, value)`` of each field of the message in
    ``buf[lo:hi]``: an int for a varint, ``(start, end)`` offsets for a
    length-delimited field (not copied), raw bytes for a fixed width."""
    i, hi = lo, len(buf) if hi is None else hi
    while i < hi:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            value = (i, i + size)
            i += size
        elif kind in (1, 5):
            size = 8 if kind == 1 else 4
            value = bytes(buf[i:i + size])
            i += size
        else:
            raise ValueError(f"unsupported protobuf wire type {kind}")
        yield key >> 3, value


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_values(buf, entry):
    for f, v in fields(buf, *entry):
        if f == MAP_VALUE:
            yield v


def op_metadata(data: bytes) -> dict:
    """``{device plane name: [(metadata name, program id, tf_op)]}`` from
    the bytes of an ``.xplane.pb``; program id and tf_op are None where the
    entry carries no such stat."""
    buf = memoryview(data)
    out = {}
    for f, plane in fields(buf):
        if f != XSPACE_PLANES:
            continue
        name, events, stat_names = "", [], {}
        for g, v in fields(buf, *plane):
            if g == XPLANE_NAME:
                name = _text(buf, v)
            elif g == XPLANE_EVENT_METADATA:
                events.extend(_map_values(buf, v))
            elif g == XPLANE_STAT_METADATA:
                for meta in _map_values(buf, v):
                    sid, sname = 0, ""
                    for h, w in fields(buf, *meta):
                        if h == XSTAT_METADATA_ID:
                            sid = w
                        elif h == XSTAT_METADATA_NAME:
                            sname = _text(buf, w)
                    stat_names[sid] = sname
        if not name.startswith("/device:"):
            continue
        entries = []
        for ev in events:
            ename, program, tf_op = "", None, None
            for h, w in fields(buf, *ev):
                if h == XEVENT_METADATA_NAME:
                    ename = _text(buf, w)
                elif h == XEVENT_METADATA_STATS:
                    stat = dict(fields(buf, *w))
                    kind = stat_names.get(stat.get(XSTAT_METADATA_ID))
                    if kind == "program_id":
                        program = stat.get(XSTAT_UINT64)
                    elif kind == "tf_op":
                        if XSTAT_STR in stat:
                            tf_op = _text(buf, stat[XSTAT_STR])
                        elif XSTAT_REF in stat:
                            tf_op = stat_names.get(stat[XSTAT_REF])
            entries.append((ename, program, tf_op))
        out[name] = entries
    return out


# -- naming device ops --------------------------------------------------------


def scope_of(tf_op: str | None) -> str | None:
    """The innermost ``sssp.`` scope of an op's ``tf_op`` path, if any."""
    if not tf_op:
        return None
    found = None
    for part in tf_op.split("/"):
        if part.startswith(SCOPE_PREFIX):
            found = part.rstrip(":")
    return found


def split_module(name: str):
    """``jit__step_batch_impl(1541...)`` -> (``jit__step_batch_impl``,
    1541...); a name with no id gives (name, None)."""
    m = re.fullmatch(r"(.*)\((\d+)\)", name)
    if not m:
        return name, None
    return m.group(1), int(m.group(2))


class OpNames:
    """Label of each device op: its ``sssp.`` scope, else its program."""

    def __init__(self, entries):
        self.by_program = {}
        tf_ops = defaultdict(set)
        for name, program, tf_op in entries:
            if tf_op is not None:
                self.by_program[(program, name)] = tf_op
                tf_ops[name].add(tf_op)
        self.by_name = {n: next(iter(v)) for n, v in tf_ops.items()
                        if len(v) == 1}
        # op names that carry more than one scope path: by name alone they
        # are ambiguous, and only the program id tells them apart
        self.collisions = len(tf_ops) - len(self.by_name)

    def tf_op(self, name: str, program_id) -> str | None:
        """By metadata name; by program id too where the name collides."""
        found = self.by_name.get(name)
        if found is None:
            found = self.by_program.get((program_id, name))
        return found


def label_ops(events, mods, names: OpNames, lo: float, hi: float):
    """``(start, end, label, inherited)`` of the ops of ``events`` in
    ``[lo, hi]``, clipped to it: the op's ``sssp.`` scope, else its
    program's name; an op with no ``tf_op`` inherits the scope of the op
    before it in the same program execution (``inherited`` True)."""
    mod_starts = [s for s, _, _ in mods]
    out, prev = [], (None, None)  # (module index, label) of the op before
    for s, e, name in sorted(events):
        if e <= lo or s >= hi:
            continue
        j = bisect.bisect_right(mod_starts, s) - 1
        if j >= 0 and s < mods[j][1]:
            program, pid = split_module(mods[j][2])
        else:
            j, program, pid = None, "no program", None
        tf_op = names.tf_op(name, pid)
        scope = scope_of(tf_op)
        inherited = False
        if tf_op is None and prev[0] == j and prev[1] is not None \
                and prev[1].startswith(SCOPE_PREFIX):
            scope, inherited = prev[1], True
        label = scope if scope is not None else program
        out.append((max(s, lo), min(e, hi), label, inherited))
        prev = (j, label)
    return out


# -- the program's trace --------------------------------------------------------


@dataclasses.dataclass
class ProgramTrace:
    window_s: float
    busy_s: float  # mean over the cell's chips
    device_s_by_scope: dict  # {sssp scope or program name: self seconds}
    inherited_s: float  # of those under a scope, seconds named by inheritance
    step_runs: int  # step program executions that start in the window
    chunk_gap_s: float  # device idle between consecutive step executions
    idle_by_program_span: dict  # {innermost serving span: idle seconds}
    round_host_s: list  # per serving.round: wall less its waits
    dispatches: int  # serving.dispatch spans in the window
    launch_s: float  # sum of: step start less its serving.dispatch start
    return_s: float  # sum of: serving.sync end less the step's end
    clock_offset_s: tuple | None  # causality bounds on the device's shift
    name_collisions: int


def _runs(modules, program: str | None):
    return sorted((s, e) for s, e, name in modules
                  if split_module(name)[0] == program)


def step_program(modules) -> str | None:
    """The name of the program that ``serving.dispatch`` launches: the one
    a device runs most often just before an execution of ``jit__peek``;
    None where the device ran no peek."""
    names = [split_module(name)[0] for _, _, name in sorted(modules)]
    before = Counter(a for a, b in zip(names, names[1:])
                     if b == SYNC_PROGRAM and a != SYNC_PROGRAM)
    return before.most_common(1)[0][0] if before else None


def chunk_gaps(step_runs, busy, lo: float, hi: float) -> float:
    """Device idle between the end of each step execution and the start of
    the next, over the executions in ``[lo, hi]``: the time the device
    waits on the host between chunks (other programs run there too, and
    count as busy)."""
    runs = [(s, e) for s, e in step_runs if lo <= s and e <= hi]
    merged = trace.merge(busy)
    idle = 0.0
    for (_, e0), (s1, _) in zip(runs, runs[1:]):
        span = s1 - e0
        if span > 0:
            idle += span - trace.covered(trace.clip(merged, e0, s1))
    return idle


def _ordered(spans, name):
    return sorted((s, e) for s, e, n in spans if n == name)


def clock_offset(mods, spans):
    """Bounds ``(lo, hi)`` on the seconds to add to a device's times to put
    them on the host's clock, or None where the trace cannot pair them.

    The profiler aligns the two clocks only roughly (within a millisecond
    or two on the chip). Causality bounds the shift: the k-th execution of a
    program starts after the k-th host span that runs it began, and where
    the host waits for it (``serving.sync``, ``serving.take_row``) it ends
    before that span ends."""
    lo, hi = [], []
    calls = (("serving.dispatch", step_program(mods), False),) + WAITED_CALLS
    for span, program, waits in calls:
        runs, host = _runs(mods, program), _ordered(spans, span)
        if not host or len(host) != len(runs):
            continue
        lo.append(max(h[0] - d[0] for h, d in zip(host, runs)))
        if waits:
            hi.append(min(h[1] - d[1] for h, d in zip(host, runs)))
    if not lo or not hi:
        return None
    return max(lo), min(hi)


def chunk_latency(step_runs, spans, shift: float, lo: float, hi: float):
    """``(launch, return)`` seconds summed over the ``serving.dispatch``
    spans in ``[lo, hi]``, with the device's times moved by ``shift``: from
    the span's start to the start of the step execution it dispatched, and
    from that execution's end to the end of the ``serving.sync`` that
    waited on it. Dispatches, executions and syncs pair in order."""
    runs = sorted(step_runs)
    dispatch = _ordered(spans, "serving.dispatch")
    syncs = _ordered(spans, "serving.sync")
    if len(dispatch) != len(runs) or len(syncs) != len(runs):
        return 0.0, 0.0
    launch = ret = 0.0
    for (ds, de), (rs, re_), (_, se) in zip(dispatch, runs, syncs):
        if lo <= ds and de <= hi:
            launch += rs + shift - ds
            ret += se - (re_ + shift)
    return launch, ret


def round_host(spans, lo: float, hi: float) -> list:
    """For each ``serving.round`` span in ``[lo, hi]``: its wall time less
    the ``serving.sync`` and ``serving.take_row`` spans inside it."""
    rounds = sorted((s, e) for s, e, n in spans
                    if n == "serving.round" and lo <= s and e <= hi)
    waits = sorted((s, e) for s, e, n in spans if n in HOST_WAITS)
    starts = [s for s, _ in waits]
    out = []
    for s, e in rounds:
        j = bisect.bisect_left(starts, s)
        wait = 0.0
        while j < len(waits) and waits[j][0] < e:
            wait += min(waits[j][1], e) - waits[j][0]
            j += 1
        out.append((e - s) - wait)
    return out


def summarize(device_ops, modules, entries, spans, lo, hi,
              chips: int = 1) -> ProgramTrace:
    """The program's numbers of the window ``[lo, hi]`` from
    ``{device: [(start, end, op name)]}``, ``{device: [(start, end, module
    name)]}``, ``{device: op metadata entries}`` and the host spans; device
    times per chip, averaged over the cell's ``chips``."""
    busy_by_dev, collisions, step_runs, gap, inherited = [], 0, 0, 0.0, 0.0
    launch = ret = 0.0
    offset = None
    by_scope, idle_by_span = defaultdict(float), defaultdict(float)
    serving = [x for x in spans if x[2].startswith(SPAN_PREFIX)]
    for dev, events in device_ops.items():
        iv = trace.clip([(s, e) for s, e, _ in events], lo, hi)
        if not iv:
            continue
        busy_by_dev.append(trace.covered(iv))
        names = OpNames(entries.get(dev, ()))
        collisions = max(collisions, names.collisions)
        mods = sorted(modules.get(dev, ()))
        labelled = label_ops(events, mods, names, lo, hi)
        for label, t in trace.self_times(
                [(s, e, n) for s, e, n, _ in labelled]).items():
            by_scope[label] += t
        # the same ops with the inherited ones apart: their self time
        direct = trace.self_times(
            [(s, e, (n, i)) for s, e, n, i in labelled])
        inherited += sum(t for (_, i), t in direct.items() if i)
        runs = _runs(mods, step_program(mods))
        step_runs = max(step_runs, sum(lo <= s < hi for s, _ in runs))
        gap += chunk_gaps(runs, iv, lo, hi)
        # host spans name the device's gaps and latencies on the host's
        # clock: move the device's times by the middle of the bounds
        bounds = clock_offset(mods, serving)
        offset = offset or bounds
        shift = 0.0
        if bounds is not None and bounds[0] <= bounds[1]:
            shift = 0.5 * (bounds[0] + bounds[1])
        dl, dr = chunk_latency(runs, serving, shift, lo, hi)
        launch, ret = launch + dl, ret + dr
        idle = [(s + shift, e + shift) for s, e in trace.gaps(iv, lo, hi)]
        for name, t in trace.name_gaps(idle, serving).items():
            idle_by_span[name] += t
    if len(busy_by_dev) > chips:
        raise ValueError(f"{len(busy_by_dev)} devices ran ops in the window "
                         f"of a cell on {chips} chips")
    return ProgramTrace(
        window_s=hi - lo,
        busy_s=sum(busy_by_dev) / chips,
        device_s_by_scope={n: t / chips for n, t in sorted(
            by_scope.items(), key=lambda x: -x[1])},
        inherited_s=inherited / chips,
        step_runs=step_runs,
        chunk_gap_s=gap / chips,
        idle_by_program_span={n: t / chips for n, t in sorted(
            idle_by_span.items(), key=lambda x: -x[1])},
        round_host_s=round_host(serving, lo, hi),
        dispatches=sum(n == "serving.dispatch" and lo <= s and e <= hi
                       for s, e, n in serving),
        launch_s=launch / chips,
        return_s=ret / chips,
        clock_offset_s=offset,
        name_collisions=collisions,
    )


def load(path) -> trace.Xplane:
    """``trace.load`` keeping the host spans of both reducers: one read of
    the file serves ``trace.summarize_file`` and ``read_file``."""
    return trace.load(path, trace.HOST_PREFIXES + HOST_PREFIXES)


def read_file(src, window_span: str = "bench.window",
              chips: int = 1) -> ProgramTrace:
    """The program's numbers of the window that the host span
    ``window_span`` marks, from one ``.xplane.pb`` file (a path, or its
    ``load``) of a cell on ``chips`` chips."""
    xp = src if isinstance(src, trace.Xplane) else load(src)
    entries = op_metadata(xp.data)
    device_ops, modules = {}, {}
    for plane, by_line in xp.lines.items():
        for line, into in ((trace.OPS_LINE, device_ops),
                           (MODULES_LINE, modules)):
            if line in by_line:
                into.setdefault(plane, []).extend(by_line[line])
    spans = [x for x in xp.host if x[2].startswith(HOST_PREFIXES)]
    win = [(s, e) for s, e, n in spans if n == window_span]
    if not win:
        raise ValueError(f"no host span {window_span!r} in the trace")
    lo, hi = win[-1]
    return summarize(device_ops, modules, entries, spans, lo, hi, chips)


# -- per-layer metrics ----------------------------------------------------------


def _scoped_ms_per_phase(pt: ProgramTrace | None, scopes, trips: int):
    if pt is None or not trips:
        return None
    found = [pt.device_s_by_scope[s] for s in scopes
             if s in pt.device_s_by_scope]
    if not found:
        return None
    return 1e3 * sum(found) / trips


def relax_device_ms_per_phase(pt: ProgramTrace | None, trips: int):
    """Device self time of the ops under ``sssp.relax`` in the window over
    the phases advanced in it."""
    return _scoped_ms_per_phase(pt, (RELAX_SCOPE,), trips)


def criteria_device_ms_per_phase(pt: ProgramTrace | None, trips: int):
    """The same for ``sssp.out_scan``, ``sssp.thresholds`` and
    ``sssp.settle``: the settle criterion's work."""
    return _scoped_ms_per_phase(pt, CRITERIA_SCOPES, trips)


def chunk_gap_ms(pt: ProgramTrace | None):
    """Device idle between consecutive step executions, per step execution
    (chunk) of the window."""
    if pt is None or not pt.step_runs:
        return None
    return 1e3 * pt.chunk_gap_s / pt.step_runs


def round_host_ms_per_chunk(pt: ProgramTrace | None):
    """Host time of the window's rounds (wall less ``serving.sync`` and
    ``serving.take_row``) over its ``serving.dispatch`` spans."""
    if pt is None or not pt.dispatches:
        return None
    return 1e3 * sum(pt.round_host_s) / pt.dispatches


def round_host_ms_max(pt: ProgramTrace | None):
    if pt is None or not pt.round_host_s:
        return None
    return 1e3 * max(pt.round_host_s)
