"""Graph representation for the phased-SSSP engine.

Graphs are stored as fixed-shape COO edge arrays (``src``, ``dst``, ``w``)
plus precomputed static per-vertex edge-weight minima, the quantities the
Crauser-style criteria need:

  ``in_min_static[v]  = min_{(w,v) in E} c(w,v)``   (M'[v] in the paper)
  ``out_min_static[v] = min_{(v,w) in E} c(v,w)``   (M[v]  in the paper)

Padding convention: edge arrays may be padded to a fixed length with
``w = +inf`` and ``src = dst = 0``; +inf edge weights are neutral for every
min-plus reduction in the engine, so no separate validity mask is required.

An ELL (padded per-row) view of the *incoming* adjacency is available via
:func:`to_ell_in`; it is the layout consumed by the Pallas pull-relaxation
kernel (row-major ``(n, max_in_deg)`` tiles map directly onto VMEM blocks).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

INF = jnp.inf


@partial(
    jax.tree_util.register_dataclass,
    data_fields=["src", "dst", "w", "in_min_static", "out_min_static"],
    meta_fields=["n", "m"],
)
@dataclasses.dataclass(frozen=True)
class Graph:
    """A directed graph with non-negative edge costs, as device arrays."""

    n: int
    m: int  # padded edge-array length (>= true edge count)
    src: jax.Array  # (m,) int32
    dst: jax.Array  # (m,) int32
    w: jax.Array  # (m,) float32, +inf on padding
    in_min_static: jax.Array  # (n,) float32
    out_min_static: jax.Array  # (n,) float32

    @property
    def num_real_edges(self) -> jax.Array:
        return jnp.sum(jnp.isfinite(self.w))


def from_coo(src, dst, w, n: int, pad_to: int | None = None) -> Graph:
    """Build a :class:`Graph` from COO numpy/JAX arrays."""
    src = np.asarray(src, dtype=np.int32)
    dst = np.asarray(dst, dtype=np.int32)
    w = np.asarray(w, dtype=np.float32)
    assert src.shape == dst.shape == w.shape
    if np.any(w < 0):
        raise ValueError("edge costs must be non-negative")
    # `w < 0` is False for NaN, so check non-finiteness explicitly: a NaN
    # weight would otherwise poison every min-plus reduction downstream
    # (NaN propagates through minimum) and silently corrupt all distances.
    # +inf alone is allowed — it is the padding sentinel, neutral under min.
    if np.any(~np.isfinite(w) & ~(w == np.inf)):
        raise ValueError(
            "edge costs must be finite (or +inf for padding); got NaN/-inf"
        )
    m = src.shape[0]
    if pad_to is not None and pad_to > m:
        pad = pad_to - m
        src = np.concatenate([src, np.zeros(pad, np.int32)])
        dst = np.concatenate([dst, np.zeros(pad, np.int32)])
        w = np.concatenate([w, np.full(pad, np.inf, np.float32)])
        m = pad_to
    in_min = np.full(n, np.inf, np.float32)
    out_min = np.full(n, np.inf, np.float32)
    np.minimum.at(in_min, dst, w)
    np.minimum.at(out_min, src, w)
    return Graph(
        n=n,
        m=m,
        src=jnp.asarray(src),
        dst=jnp.asarray(dst),
        w=jnp.asarray(w),
        in_min_static=jnp.asarray(in_min),
        out_min_static=jnp.asarray(out_min),
    )


def to_numpy_csr(g: Graph):
    """(indptr, indices, weights) CSR over outgoing edges; drops padding."""
    src = np.asarray(g.src)
    dst = np.asarray(g.dst)
    w = np.asarray(g.w)
    real = np.isfinite(w)
    src, dst, w = src[real], dst[real], w[real]
    order = np.argsort(src, kind="stable")
    src, dst, w = src[order], dst[order], w[order]
    indptr = np.zeros(g.n + 1, np.int64)
    np.add.at(indptr, src + 1, 1)
    indptr = np.cumsum(indptr)
    return indptr, dst, w


def _build_ell(from_ids, to_ids, w, n, pad_multiple):
    """(n, D) ELL rows keyed by ``to_ids`` holding (from_id, weight) pairs."""
    real = np.isfinite(w)
    from_ids, to_ids, w = from_ids[real], to_ids[real], w[real]
    deg = np.zeros(n, np.int64)
    np.add.at(deg, to_ids, 1)
    max_deg = int(deg.max()) if deg.size and deg.max() > 0 else 1
    d_pad = -(-max_deg // pad_multiple) * pad_multiple
    cols = np.full((n, d_pad), n, np.int32)  # sentinel neighbour id == n
    ws = np.full((n, d_pad), np.inf, np.float32)
    order = np.argsort(to_ids, kind="stable")
    from_ids, to_ids, w = from_ids[order], to_ids[order], w[order]
    # position of each edge within its row
    slot = np.arange(len(to_ids)) - np.searchsorted(to_ids, to_ids, side="left")
    cols[to_ids, slot] = from_ids
    ws[to_ids, slot] = w
    return jnp.asarray(cols), jnp.asarray(ws)


def to_ell_in(g: Graph, pad_multiple: int = 8):
    """ELL layout of *incoming* adjacency: (n, D) source-ids and weights.

    Rows are destination vertices; columns hold (source, weight) pairs padded
    with ``src = n`` (a sentinel row appended by consumers) and ``w = +inf``.
    ``D`` is the max in-degree rounded up to ``pad_multiple`` (min 1 lane so
    isolated-source graphs still produce a well-formed array).

    Memoised per :class:`Graph` instance (keyed by ``pad_multiple``): the
    serving path answers many queries against one long-lived graph, and the
    CSR->ELL rebuild would otherwise dominate small-batch latency. The cache
    lives in the instance ``__dict__`` (bypassing the frozen-dataclass
    setattr guard) and is deliberately *not* a pytree field, so jit
    flatten/unflatten round-trips simply drop it.
    """
    cache = g.__dict__.setdefault("_ell_in_cache", {})
    hit = cache.get(pad_multiple)
    if hit is not None:
        return hit
    out = _build_ell(np.asarray(g.src), np.asarray(g.dst), np.asarray(g.w),
                     g.n, pad_multiple)
    cache[pad_multiple] = out
    return out


def to_ell_out(g: Graph, pad_multiple: int = 8):
    """ELL layout of *outgoing* adjacency: (n, D) target-ids and weights.

    The transpose twin of :func:`to_ell_in` — rows are source vertices,
    columns hold (target, weight) pairs, D = max out-degree rounded up.
    Consumed by the dynamic OUT-family criterion keys (``out_dyn`` /
    ``out_weak`` / ``out_full``): ``ell_key_min`` reduces a gate vector
    indexed by the *target* status over these rows, which is exactly
    ``min over out-edges staying unsettled`` from the paper's Eq. 2/3/7.
    Memoised per Graph instance like the incoming view.
    """
    cache = g.__dict__.setdefault("_ell_out_cache", {})
    hit = cache.get(pad_multiple)
    if hit is not None:
        return hit
    out = _build_ell(np.asarray(g.dst), np.asarray(g.src), np.asarray(g.w),
                     g.n, pad_multiple)
    cache[pad_multiple] = out
    return out


def out_degrees(g: Graph) -> jax.Array:
    """(n,) int32 real out-degrees (padding edges excluded), memoised.

    The batched steppers carry this vector for the ``relax_edges`` counter;
    before memoisation every ``init_batch_state`` recomputed it with a
    device ``segment_sum`` — a per-admission cost in serving. Cached in the
    instance ``__dict__`` like the ELL views (dropped by jit flattening).
    """
    hit = g.__dict__.get("_out_deg_cache")
    if hit is not None:
        return hit
    src = np.asarray(g.src)
    w = np.asarray(g.w)
    deg = np.zeros(g.n, np.int32)
    np.add.at(deg, src[np.isfinite(w)], 1)
    out = jnp.asarray(deg)
    g.__dict__["_out_deg_cache"] = out
    return out


# ---------------------------------------------------------------------------
# Degree-sliced ELL: stop paying max-degree padding on skewed graphs
# ---------------------------------------------------------------------------


class EllSlice(NamedTuple):
    """One degree bucket of a sliced ELL view.

    ``rows[i]`` is the vertex that slice-row ``i`` belongs to; a *split*
    heavy vertex contributes several rows (same ``rows`` id, disjoint edge
    chunks), merged back through :class:`SlicedEll`'s plan — exact, because f32
    ``min`` has no rounding, so the merge is bit-identical to the padded
    single-row reduction in any order.
    """

    rows: jax.Array  # (R_b,) int32 vertex ids (repeats allowed: split rows)
    cols: jax.Array  # (R_b, D_b) int32 neighbour ids (sentinel id = n)
    ws: jax.Array  # (R_b, D_b) f32, +inf padding


class SlicedEll(NamedTuple):
    """A degree-sliced ELL adjacency view: one :class:`EllSlice` per bucket.

    Plain-ELL pads every vertex to the maximum degree, so one rmat-style hub
    makes *every* row tile pay ``D_max`` lanes. Slicing buckets rows by
    degree (each bucket padded only to its own width) and splits rows beyond
    the last width into chunks, bounding padded slots by ~2x the real edge
    count instead of ``n * D_max``. Zero-degree vertices appear in no slice
    (the consumer's +inf merge identity is exactly their empty-min value).

    The slice->vertex merge is a two-level *gather* plan over the row-major
    concatenation of all slices with one +inf slot appended (position
    ``total``, the sentinel). ``merge_heavy[h, c]`` is the position of the
    c-th slice-row of the h-th *split* vertex (one with more than one row;
    padded with the sentinel), so its heavy min is
    ``min_c concat[merge_heavy[h, c]]``. ``merge_first[v]`` is the position
    of v's only row, the sentinel when v has none, or ``total + 1 + h``
    when v is the h-th split vertex: the slot of its heavy min once those
    are appended after the +inf slot. Every vertex pays one gather and only
    the H split vertices pay for C, the largest chunk count; H = 0 (no
    vertex split) leaves the single ``(n,)`` gather. Gathers, not a
    scatter-min (scatters serialise on CPU and row-conflict on TPU); f32
    ``min`` is exact in any order.
    """

    slices: tuple[EllSlice, ...]
    merge_heavy: jax.Array  # (H, C) int32 positions into concat(slices)+[inf]
    merge_first: jax.Array  # (n,) int32 positions into that + H heavy mins

    @property
    def widths(self) -> tuple[int, ...]:
        return tuple(int(s.cols.shape[1]) for s in self.slices)

    @property
    def padded_slots(self) -> int:
        return sum(int(s.cols.size) for s in self.slices)

    @property
    def split_vertices(self) -> int:
        """H: vertices whose adjacency is split over more than one row."""
        return int(self.merge_heavy.shape[0])

    @property
    def merge_slots(self) -> int:
        """Entries one merge gathers per vector: n + H * C."""
        return int(self.merge_first.shape[0]) + int(self.merge_heavy.size)


def default_slice_boundaries(deg: np.ndarray, pad_multiple: int = 8,
                             max_slices: int = 4) -> tuple[int, ...]:
    """Bucket widths for :func:`to_ell_in_sliced`: geometric (x4) from
    ``pad_multiple`` up to the 95th-percentile degree, at most
    ``max_slices`` buckets. Rows beyond the last width are split into
    chunks of that width, so hubs never widen a bucket."""
    deg = deg[deg > 0]
    if deg.size == 0:
        return (pad_multiple,)
    p95 = int(np.percentile(deg, 95))
    widths = [pad_multiple]
    while widths[-1] < p95 and len(widths) < max_slices:
        widths.append(widths[-1] * 4)
    return tuple(widths)


def _build_ell_sliced(from_ids, to_ids, w, n, pad_multiple, boundaries,
                      split):
    """Slice rows keyed by ``to_ids`` into per-degree-bucket ELL tiles."""
    real = np.isfinite(w)
    from_ids, to_ids, w = from_ids[real], to_ids[real], w[real]
    deg = np.zeros(n, np.int64)
    np.add.at(deg, to_ids, 1)
    if boundaries is None:
        boundaries = default_slice_boundaries(deg, pad_multiple)
    widths = sorted(
        {max(pad_multiple, -(-int(b) // pad_multiple) * pad_multiple)
         for b in boundaries}
    )
    if split is None:
        split = widths[-1]
    split = max(pad_multiple, -(-int(split) // pad_multiple) * pad_multiple)
    if split < widths[-1]:
        raise ValueError(
            f"split threshold {split} below the widest bucket {widths[-1]}"
        )
    # per-edge slot within its row (same stable order as _build_ell)
    order = np.argsort(to_ids, kind="stable")
    from_ids, to_ids, w = from_ids[order], to_ids[order], w[order]
    slot = np.arange(len(to_ids)) - np.searchsorted(to_ids, to_ids, "left")
    slices = []
    lo = 0
    for width in widths:
        last = width == widths[-1]
        if last:
            vmask = deg > lo  # widest bucket also owns the split rows
        else:
            vmask = (deg > lo) & (deg <= width)
        verts = np.nonzero(vmask)[0]
        if verts.size == 0:
            lo = width
            continue
        use_w = split if last else width
        # chunk index of each row occurrence: vertex v with degree d gets
        # ceil(d / use_w) rows; edge at slot s lands in chunk s // use_w
        chunks = np.maximum(1, -(-deg[verts] // use_w)) if last else np.ones(
            verts.size, np.int64
        )
        rows = np.repeat(verts, chunks).astype(np.int32)
        # row offset of each vertex's first chunk within this slice
        first = np.zeros(n, np.int64)
        first[verts] = np.cumsum(chunks) - chunks
        emask = vmask[to_ids]
        e_to, e_from, e_w, e_slot = (
            to_ids[emask], from_ids[emask], w[emask], slot[emask]
        )
        r = first[e_to] + e_slot // use_w
        c = e_slot % use_w
        cols_b = np.full((rows.size, use_w), n, np.int32)
        ws_b = np.full((rows.size, use_w), np.inf, np.float32)
        cols_b[r, c] = e_from
        ws_b[r, c] = e_w
        slices.append(EllSlice(
            rows=jnp.asarray(rows), cols=jnp.asarray(cols_b),
            ws=jnp.asarray(ws_b),
        ))
        lo = width
    if not slices:  # edgeless graph: one empty well-formed slice
        slices.append(EllSlice(
            rows=jnp.zeros((0,), jnp.int32),
            cols=jnp.full((0, widths[0]), n, jnp.int32),
            ws=jnp.full((0, widths[0]), np.inf, jnp.float32),
        ))
    # gather-based merge plan: position of each vertex's slice-rows in the
    # row-major slice concatenation (sentinel = total rows -> +inf slot)
    all_rows = np.concatenate([np.asarray(s.rows) for s in slices])
    total = all_rows.shape[0]
    occ = np.zeros(n, np.int64)
    np.add.at(occ, all_rows, 1)
    order = np.argsort(all_rows, kind="stable")
    srt = all_rows[order]
    rank = np.arange(total) - np.searchsorted(srt, srt, side="left")
    merge_first = np.full(n, total, np.int64)
    merge_first[srt[rank == 0]] = order[rank == 0]
    heavy = np.nonzero(occ > 1)[0]
    merge_first[heavy] = total + 1 + np.arange(heavy.size)
    c_max = max(int(occ.max()) if occ.size else 1, 1)
    merge_heavy = np.full((heavy.size, c_max), total, np.int32)
    h_of = np.full(n, -1, np.int64)
    h_of[heavy] = np.arange(heavy.size)
    in_heavy = h_of[srt] >= 0
    merge_heavy[h_of[srt[in_heavy]], rank[in_heavy]] = order[in_heavy]
    return SlicedEll(slices=tuple(slices),
                     merge_heavy=jnp.asarray(merge_heavy),
                     merge_first=jnp.asarray(merge_first.astype(np.int32)))


def _sliced_cache_key(pad_multiple, boundaries, split):
    return (pad_multiple,
            None if boundaries is None else tuple(int(b) for b in boundaries),
            None if split is None else int(split))


def _ledger_boundaries(side: str, n: int):
    """Tuned bucket boundaries from the kernel tuning ledger, if any.

    Imported lazily: ``repro.kernels.config`` is dependency-free, but the
    graph module must stay importable without the kernel package in
    pathological partial-install states, and the lookup is only needed when
    no explicit boundaries were given.
    """
    from repro.kernels.config import resolve_slice_boundaries

    return resolve_slice_boundaries(side, n)


def to_ell_in_sliced(g: Graph, pad_multiple: int = 8,
                     boundaries=None, split: int | None = None) -> SlicedEll:
    """Degree-sliced ELL view of the *incoming* adjacency.

    ``boundaries`` are bucket widths (rounded up to ``pad_multiple``);
    when omitted, a tuning-ledger entry for this (side, n) — written by
    ``repro.kernels.config.autotune_slicing`` — wins over the
    :func:`default_slice_boundaries` of the in-degree distribution. Rows
    with degree beyond ``split`` (default: the widest bucket) are split
    into width-``split`` chunks merged by the consumer. Memoised per Graph
    instance keyed by the full parameter tuple, like :func:`to_ell_in`.
    """
    if boundaries is None:
        boundaries = _ledger_boundaries("in", g.n)
    cache = g.__dict__.setdefault("_ell_in_sliced_cache", {})
    key = _sliced_cache_key(pad_multiple, boundaries, split)
    hit = cache.get(key)
    if hit is not None:
        return hit
    out = _build_ell_sliced(np.asarray(g.src), np.asarray(g.dst),
                            np.asarray(g.w), g.n, pad_multiple, boundaries,
                            split)
    cache[key] = out
    return out


def to_ell_out_sliced(g: Graph, pad_multiple: int = 8,
                      boundaries=None, split: int | None = None) -> SlicedEll:
    """Degree-sliced ELL view of the *outgoing* adjacency (transpose twin
    of :func:`to_ell_in_sliced`, same ledger consultation), memoised per
    Graph instance."""
    if boundaries is None:
        boundaries = _ledger_boundaries("out", g.n)
    cache = g.__dict__.setdefault("_ell_out_sliced_cache", {})
    key = _sliced_cache_key(pad_multiple, boundaries, split)
    hit = cache.get(key)
    if hit is not None:
        return hit
    out = _build_ell_sliced(np.asarray(g.dst), np.asarray(g.src),
                            np.asarray(g.w), g.n, pad_multiple, boundaries,
                            split)
    cache[key] = out
    return out


def transpose(g: Graph) -> Graph:
    """The reverse graph (incoming edges become outgoing)."""
    return Graph(
        n=g.n,
        m=g.m,
        src=g.dst,
        dst=g.src,
        w=g.w,
        in_min_static=g.out_min_static,
        out_min_static=g.in_min_static,
    )
