"""The plain reference and the comparison that decides ``correct``.

The reference is scipy's Dijkstra in float64 over the benchmark's own COO
arrays (parallel arcs reduced to their lightest), independent of the
program. Every answer delivered in a run is compared with it:

- ``unanswered``: requests of the window with no answer a minute past
  the close (limit 0);
- ``reach_mismatch``: compared distances that are infinite on one side
  only (limit 0);
- ``rel_gap_max``: the widest relative gap ``|d - ref| / ref`` over every
  compared distance finite on both sides (full rows: every vertex; s->t:
  the target);
- ``not_fixpoint``: full rows that are not the exact float32 fixed point
  ``d[v] = min over arcs (u, v) of fl32(d[u] + w)``, ``d[source] = 0``,
  that the service guarantees (limit 0).

``bf16_rows`` is the control: the same shortest paths with every sum
rounded to bfloat16, the precision below the configuration's float32.
"""
from __future__ import annotations

import math
import multiprocessing
import os
from concurrent.futures import ProcessPoolExecutor
from itertools import repeat

import numpy as np
import scipy.sparse as sp
from scipy.sparse.csgraph import dijkstra

ROW_BLOCK = 32  # reference rows per Dijkstra call
# Graphs of this many arcs or more have their rows solved in a pool of
# worker processes. The pool takes about 3 s to start its workers and send
# each the graph; 32 Graph500 rows took 1.9 s in one process and 3.7 s in
# the pool at 1.8 M arcs (scale 16), 8.3 s and 8.2 s at 7.6 M (scale 18),
# and 45 s and 18 s at 30 M (scale 20), on an 8-core x86 host
# (tests/perfbench/time_reference.py).
POOL_ARCS = 1 << 23
ROW_WORKERS = min(8, os.cpu_count() or 1)
F32_TINY = float(np.finfo(np.float32).tiny)


def round_bf16(x: np.ndarray) -> np.ndarray:
    """float32 -> nearest bfloat16 (ties to even), kept as float32."""
    bits = np.ascontiguousarray(x, np.float32).view(np.uint32)
    up = bits + np.uint32(0x7FFF) + ((bits >> np.uint32(16)) & np.uint32(1))
    out = (up & np.uint32(0xFFFF0000)).view(np.float32)
    return np.where(np.isnan(x), x, out)


class Reference:
    """Shortest-path reference over one graph's COO arcs."""

    def __init__(self, src, dst, w, n: int):
        src = np.asarray(src, np.int64)
        dst = np.asarray(dst, np.int64)
        w = np.asarray(w, np.float32)
        self.n = n
        self.out_arcs = np.bincount(src, minlength=n)  # parallel arcs count
        key = src * n + dst
        order = np.argsort(key, kind="stable")
        key, ws = key[order], w[order]
        first = np.concatenate([[True], key[1:] != key[:-1]])
        starts = np.nonzero(first)[0]
        wmin = np.minimum.reduceat(ws, starts)
        u, v = key[starts] // n, key[starts] % n
        # scipy reads a stored zero as no arc: a zero weight is stored as
        # the least positive float64 and read back as 0 below
        self.csr = sp.csr_matrix(
            (np.where(wmin == 0, np.nextafter(0.0, 1.0), wmin), (u, v)),
            shape=(n, n))
        # in-arcs sorted by head, for the float32 fixed-point check
        by_dst = np.argsort(v, kind="stable")
        self.in_src, self.in_w = u[by_dst], wmin[by_dst]
        in_deg = np.bincount(v, minlength=n)
        self.has_in = in_deg > 0
        self.in_starts = (np.cumsum(in_deg) - in_deg)[self.has_in]
        # out-arcs as CSR, for the bfloat16 control
        self.out_ptr = np.concatenate([[0], np.cumsum(np.bincount(u, minlength=n))])
        self.out_dst, self.out_w = v, wmin

    def rows(self, sources) -> dict[int, np.ndarray]:
        """float64 distance rows of ``sources``. Each row is solved on its
        own, so a row does not depend on the block it is solved in: on a
        graph of ``POOL_ARCS`` arcs or more the sources are spread over
        ``ROW_WORKERS`` spawned processes, in blocks of at most
        ``ROW_BLOCK``."""
        sources = sorted({int(s) for s in sources})
        pool = self.csr.nnz >= POOL_ARCS and len(sources) > 1
        size = ROW_BLOCK
        if pool:
            size = min(ROW_BLOCK, math.ceil(len(sources) / ROW_WORKERS))
        blocks = [sources[lo:lo + size] for lo in range(0, len(sources), size)]
        if not pool:
            solved = map(_dijkstra_rows, repeat(self.csr), blocks)
            return {s: d for block, rows in zip(blocks, solved)
                    for s, d in zip(block, rows)}
        with ProcessPoolExecutor(
                min(ROW_WORKERS, len(blocks)),
                mp_context=multiprocessing.get_context("spawn"),
                initializer=_keep_csr, initargs=(self.csr,)) as ex:
            return {s: d for block, rows in zip(blocks,
                                                ex.map(_kept_rows, blocks))
                    for s, d in zip(block, rows)}

    def fixpoint(self, row: np.ndarray, source: int) -> bool:
        """Whether ``row`` is the exact float32 relaxation fixed point."""
        row = np.asarray(row, np.float32)
        cand = row[self.in_src] + self.in_w  # float32 sums, as served
        want = np.full(self.n, np.inf, np.float32)
        want[self.has_in] = np.minimum.reduceat(cand, self.in_starts)
        want[source] = 0.0
        return bool(np.array_equal(row, want))

    def reached_arcs(self, row64: np.ndarray) -> int:
        """Arcs (parallel ones included) out of the vertices a row reaches."""
        return int(self.out_arcs[np.isfinite(row64)].sum())

    def bf16_rows(self, sources) -> dict[int, np.ndarray]:
        """The control: rows whose every sum is rounded to bfloat16, by
        label-correcting relaxation from each source to its fixed point."""
        sources = sorted({int(s) for s in sources})
        w16 = round_bf16(self.out_w)
        deg = np.diff(self.out_ptr)
        out: dict[int, np.ndarray] = {}
        for lo in range(0, len(sources), ROW_BLOCK):
            block = np.asarray(sources[lo:lo + ROW_BLOCK], np.int64)
            n = self.n
            d = np.full(block.size * n, np.inf, np.float32)
            front = np.arange(block.size) * n + block
            d[front] = 0.0
            while front.size:
                lane, v = np.divmod(front, n)
                cnt = deg[v]
                first = np.repeat(self.out_ptr[v] - (np.cumsum(cnt) - cnt), cnt)
                arc = first + np.arange(int(cnt.sum()))
                tgt = np.repeat(lane * n, cnt) + self.out_dst[arc]
                cand = round_bf16(np.repeat(d[front], cnt) + w16[arc])
                better = cand < d[tgt]
                tgt, cand = tgt[better], cand[better]
                np.minimum.at(d, tgt, cand)
                front = np.unique(tgt)
            out.update(zip(block.tolist(), d.reshape(block.size, n)))
        return out


def _dijkstra_rows(csr, block) -> np.ndarray:
    """float64 rows of the sources ``block`` over ``csr``."""
    d = dijkstra(csr, directed=True, indices=block)
    return np.where(d < 1e-300, 0.0, d)


_CSR = None  # a pool worker's graph, sent once when the worker starts


def _keep_csr(csr) -> None:
    global _CSR
    _CSR = csr


def _kept_rows(block) -> np.ndarray:
    return _dijkstra_rows(_CSR, block)


def compare(answers, ref: Reference, rows64: dict[int, np.ndarray]) -> dict:
    """Compare ``answers`` — (source, target or None, row) of every request
    answered — with the reference rows; returns the compared numbers."""
    gap = 0.0
    reach = 0
    not_fix = 0
    checked: set[int] = set()
    for source, target, row in answers:
        want = rows64[source]
        got = np.asarray(row)
        if target is not None:
            got, want = got[target:target + 1], want[target:target + 1]
        elif id(row) not in checked:  # cache hits share their row object
            checked.add(id(row))
            not_fix += not ref.fixpoint(got, source)
        got = got.astype(np.float64)
        fin_got, fin_want = np.isfinite(got), np.isfinite(want)
        reach += int(np.count_nonzero(fin_got != fin_want))
        both = fin_got & fin_want
        if np.any(both):
            g, r = got[both], want[both]
            # a zero distance (the source, zero-weight arcs) must be served
            # as zero: over the least normal float32 any gap there is huge
            rel = np.abs(g - r) / np.maximum(r, F32_TINY)
            gap = max(gap, float(np.max(rel)))
    return {"reach_mismatch": reach, "rel_gap_max": gap,
            "not_fixpoint": not_fix}
