"""Pallas TPU megakernels: one adjacency scan does everything it can.

PR 4 made the strengthened criteria first-class but paid for it in passes:
every dynamic key was its own full-ELL kernel launch over the *same*
adjacency the relax kernel re-read one launch later. These kernels collapse
that: a single ``(block_rows, D)`` tile load feeds several gather-min
reductions at once, so the ``in|out`` phase body shrinks from 4 adjacency
passes (in_full, out_dyn, out_full, relax) to 2 scans — one over the in-ELL,
one over the out-ELL (DESIGN.md Sec. 9 prices this).

Three kernels:

  * :func:`ell_gather_min_batch` — the single-sweep workhorse: V gather
    vectors, one cols/ws tile load, V row-mins. Composes ``ell_relax`` and
    any number of *independent* ``ell_key_min`` passes (gates that are
    elementwise in status) into one launch. Also the per-slice kernel of the
    degree-sliced layout (``repro.core.graph.to_ell_in_sliced``).
  * :func:`ell_relax_keys_batch` — the fused in-scan. Two sweeps over the
    same tiles inside ONE launch: sweep 0 writes the relax update ``upd``
    into a VMEM-resident output, sweep 1 gathers the *next phase's* in-side
    key mins through gates that may depend on ``upd`` (a vertex enters the
    fringe exactly when its update is finite, so post-phase gates are
    ``min(ga, gb, gc + fin)`` with ``fin = 0`` where ``upd`` is finite else
    ``+inf`` — see ``criteria.in_scan_gate_parts`` for the algebra). This is
    what lets the engine *carry* in-side keys across phases instead of
    re-scanning the in-ELL at the top of every phase.
  * :func:`ell_keys_dep_batch` — the fused out-scan for plans whose OUT key
    depends on another OUT key (``out_full <- out_dyn``, paper Eq. 2).
    Sweep 0 computes the independent keys, sweep 1 re-reads the resident
    key stack to build the dependent gate ``min(dga, dgb + key_dep)`` and
    reduces it in the same launch. The adjacency streams twice through
    VMEM, but phase cost on every backend we measure is dominated by launch
    count, not tile re-streaming (BENCH_fused.json).

Index-space convention: the gather vectors and the row outputs share ONE
padded index space of size ``rows_pad = ceil((n + 1) / block_rows) *
block_rows`` (sentinel id ``n`` included), because sweep-1 gathers *from a
sweep-0 output*. All padding carries min-neutral values (+inf weights,
cols = 0), so results are bit-identical to the composed single-purpose
kernels for any ``block_rows`` — f32 min is exact under any association.
Compiled (Mosaic) runs want ``block_rows`` to be a multiple of 128 so this
shared space stays lane-aligned; interpret mode accepts any size.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import config as _kcfg

INF = jnp.inf


def _pad_rows(cols, ws, rows_pad):
    n = cols.shape[0]
    if rows_pad != n:
        cols = jnp.pad(cols, ((0, rows_pad - n), (0, 0)))
        ws = jnp.pad(ws, ((0, rows_pad - n), (0, 0)), constant_values=INF)
    return cols, ws


def _pad_idx(vec, idx_pad):
    """Pad the trailing (index-space) axis with min-neutral +inf."""
    pad = idx_pad - vec.shape[-1]
    if pad == 0:
        return vec
    width = [(0, 0)] * (vec.ndim - 1) + [(0, pad)]
    return jnp.pad(vec, width, constant_values=INF)


def _rows_pad_for(n: int, block_rows: int) -> int:
    # one shared space for rows AND gather indices: must cover sentinel n
    return -(-(n + 1) // block_rows) * block_rows


# ---------------------------------------------------------------------------
# 1. single-sweep multi-vector gather-min
# ---------------------------------------------------------------------------


def _gather_min_kernel(vecs_ref, cols_ref, ws_ref, out_ref):
    idx = cols_ref[...]  # (Bn, D) int32, shared by every vector and lane
    w = ws_ref[...]  # (Bn, D) f32, +inf padding
    vecs = vecs_ref[...]  # (V, B, n_idx) f32 gather vectors
    vals = jnp.take(vecs, idx, axis=2) + w[None, None]  # (V, B, Bn, D)
    out_ref[...] = jnp.min(vals, axis=3)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def ell_gather_min_batch(
    vecs: jax.Array,  # (V, B, n) f32 gather vectors (unpadded)
    cols: jax.Array,  # (n_rows, D) int32 neighbour ids (sentinel allowed)
    ws: jax.Array,  # (n_rows, D) f32, +inf padding
    *,
    block_rows: int = 256,
    interpret: bool | None = None,
) -> jax.Array:
    """Returns (V, B, n_rows) f32: per-vector per-lane row-min of
    ``vecs[v, b, cols] + ws``.

    V vectors share one adjacency tile load per grid step — this is the
    composed ``ell_relax_batch`` + K x ``ell_key_min_batch`` traffic at the
    cost of a single launch. Padding (rows and index space) is handled
    here; gather indices may reference the sentinel id ``n``.
    """
    interpret = _kcfg.resolve_interpret(interpret)
    v, b, n = vecs.shape
    n_rows, d_pad = cols.shape
    # at least one row tile: an empty adjacency (e.g. an empty degree
    # bucket) still lowers to a well-formed single-step grid
    rows_pad = max(-(-n_rows // block_rows), 1) * block_rows
    idx_pad = max(rows_pad, _rows_pad_for(n, block_rows))
    cols, ws = _pad_rows(cols, ws, rows_pad)
    vecs = _pad_idx(vecs, idx_pad)
    grid = rows_pad // block_rows
    out = pl.pallas_call(
        _gather_min_kernel,
        grid=(grid,),
        in_specs=[
            pl.BlockSpec(vecs.shape, lambda i: (0, 0, 0)),  # whole stack in VMEM
            pl.BlockSpec((block_rows, d_pad), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, d_pad), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((v, b, block_rows), lambda i: (0, 0, i)),
        out_shape=jax.ShapeDtypeStruct((v, b, rows_pad), jnp.float32),
        interpret=interpret,
    )(vecs, cols, ws)
    return out[:, :, :n_rows]


# ---------------------------------------------------------------------------
# 2. fused in-scan: relax + next-phase in-side keys
# ---------------------------------------------------------------------------


def _relax_keys_kernel_single(dmask_ref, ga_ref, gb_ref, gc_ref, cols_ref,
                              ws_ref, upd_ref, keys_ref):
    """One-tile variant: both sweeps in a single grid step, no predication
    and no dynamic stores (the grid machinery those need costs more than
    this whole scan at one-tile sizes)."""
    idx = cols_ref[...]  # (rows_pad, D) — rows_pad == n_idx here
    w = ws_ref[...]
    d = dmask_ref[...]
    upd = jnp.min(jnp.take(d, idx, axis=1) + w[None], axis=2)  # (B, n_idx)
    fin = jnp.where(upd < INF, 0.0, INF)
    gate = jnp.minimum(
        ga_ref[...], jnp.minimum(gb_ref[...], gc_ref[...] + fin[None])
    )
    keys_ref[...] = jnp.min(jnp.take(gate, idx, axis=2) + w[None, None], axis=3)
    upd_ref[...] = upd


def _relax_keys_kernel(dmask_ref, ga_ref, gb_ref, gc_ref, cols_ref, ws_ref,
                       upd_ref, keys_ref, *, block_rows: int):
    sweep = pl.program_id(0)
    i = pl.program_id(1)
    idx = cols_ref[...]  # (Bn, D) — the SAME tile in both sweeps
    w = ws_ref[...]

    @pl.when(sweep == 0)
    def _relax():
        d = dmask_ref[...]  # (B, n_idx) settled-masked distances
        vals = jnp.take(d, idx, axis=1) + w[None]  # (B, Bn, D)
        upd_ref[:, pl.ds(i * block_rows, block_rows)] = jnp.min(vals, axis=2)

    @pl.when(sweep == 1)
    def _keys():
        # the full upd vector is resident by now (sweep 0 wrote every slice);
        # a vertex joins the fringe iff its update is finite
        fin = jnp.where(upd_ref[...] < INF, 0.0, INF)  # (B, n_idx)
        gate = jnp.minimum(
            ga_ref[...], jnp.minimum(gb_ref[...], gc_ref[...] + fin[None])
        )  # (K, B, n_idx) — post-settle gates, criteria.in_scan_gate_parts
        vals = jnp.take(gate, idx, axis=2) + w[None, None]  # (K, B, Bn, D)
        keys_ref[:, :, pl.ds(i * block_rows, block_rows)] = jnp.min(vals, axis=3)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def ell_relax_keys_batch(
    dmask: jax.Array,  # (B, n) f32 settled-masked distances (unpadded)
    ga: jax.Array,  # (K, B, n) f32 gate part a (see criteria.in_scan_gate_parts)
    gb: jax.Array,  # (K, B, n) f32 gate part b
    gc: jax.Array,  # (K, B, n) f32 gate part c (paired with the fin term)
    cols: jax.Array,  # (n, D) int32 incoming ELL (sentinel id = n)
    ws: jax.Array,  # (n, D) f32, +inf padding
    *,
    block_rows: int = 256,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Fused in-scan: returns ``(upd (B, n), keys (K, B, n))``.

    ``upd`` is exactly ``ell_relax_batch``'s output for ``dmask``; ``keys[k]``
    is exactly ``ell_key_min_batch`` evaluated on the *post-phase* gate
    ``min(ga[k], gb[k], gc[k] + fin(upd))`` — i.e. the in-side dynamic keys
    of the NEXT phase, emitted from the same tile loads that produced the
    relax update. K must be >= 1 (plans with no in-side dynamic keys use the
    plain relax kernel; fusing nothing would only add traffic).
    """
    interpret = _kcfg.resolve_interpret(interpret)
    if ga.ndim != 3 or ga.shape[0] < 1:
        raise ValueError(f"need a (K>=1, B, n) gate stack; got {ga.shape}")
    b, n = dmask.shape
    k = ga.shape[0]
    n_rows, d_pad = cols.shape
    rows_pad = max(-(-n_rows // block_rows) * block_rows,
                   _rows_pad_for(n, block_rows))
    cols, ws = _pad_rows(cols, ws, rows_pad)
    dmask, ga, gb, gc = (
        _pad_idx(x, rows_pad) for x in (dmask, ga, gb, gc)
    )
    n_tiles = rows_pad // block_rows
    if n_tiles == 1:
        grid = (1,)
        kernel = _relax_keys_kernel_single
        tile_map = lambda i: (0, 0)  # noqa: E731 — one tile, constant maps
        maps2 = lambda i: (0, 0)  # noqa: E731
        maps3 = lambda i: (0, 0, 0)  # noqa: E731
    else:
        grid = (2, n_tiles)
        kernel = functools.partial(_relax_keys_kernel, block_rows=block_rows)
        tile_map = lambda s, i: (i, 0)  # noqa: E731
        maps2 = lambda s, i: (0, 0)  # noqa: E731
        maps3 = lambda s, i: (0, 0, 0)  # noqa: E731
    upd, keys = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(dmask.shape, maps2),
            pl.BlockSpec(ga.shape, maps3),
            pl.BlockSpec(gb.shape, maps3),
            pl.BlockSpec(gc.shape, maps3),
            pl.BlockSpec((block_rows, d_pad), tile_map),
            pl.BlockSpec((block_rows, d_pad), tile_map),
        ],
        out_specs=[
            # constant index maps: both outputs stay VMEM-resident across the
            # whole grid, which is what lets sweep 1 gather from sweep 0's upd
            pl.BlockSpec((b, rows_pad), maps2),
            pl.BlockSpec((k, b, rows_pad), maps3),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, rows_pad), jnp.float32),
            jax.ShapeDtypeStruct((k, b, rows_pad), jnp.float32),
        ],
        interpret=interpret,
    )(dmask, ga, gb, gc, cols, ws)
    return upd[:, :n_rows], keys[:, :, :n_rows]


def ell_relax_keys(dmask, ga, gb, gc, cols, ws, *, block_rows: int = 256,
                   interpret: bool | None = None):
    """1-D entry point: ``(n,)`` dmask, ``(K, n)`` gate parts ->
    ``(upd (n,), keys (K, n))``."""
    upd, keys = ell_relax_keys_batch(
        dmask[None], ga[:, None], gb[:, None], gc[:, None], cols, ws,
        block_rows=block_rows, interpret=interpret,
    )
    return upd[0], keys[:, 0]


# ---------------------------------------------------------------------------
# 2b. one-launch megascans over a degree-SLICED adjacency (interpret shape)
# ---------------------------------------------------------------------------
#
# A sliced layout normally costs one kernel launch per degree bucket per
# reduction round; under the interpret machinery each launch carries real
# emulation overhead, so a 3-bucket in|out phase pays 12 launches. These
# variadic single-launch kernels run at grid=(1,) with every bucket's tiles
# and the two-level gather-merge plan resident, folding a whole scan — all
# buckets, both dependent reductions, and the slice->vertex merges — into
# ONE launch.
# They are the sliced twins of the one-tile megakernel bodies above (no
# predication, no dynamic stores) and are bit-identical to the per-bucket
# decomposition. Compiled (Mosaic) runs keep the per-bucket tiled path —
# these bodies assume everything fits at once, which is the interpret/CPU
# regime (and the per-shard regime after vertex partitioning).


def _merge_parts(parts, merge_heavy, merge_first, lead):
    """(..., R_b) bucket partials -> (..., n) via the two-level gather plan
    of :class:`~repro.core.graph.SlicedEll`: the split vertices' rows are
    min-reduced into H heavy slots appended after the +inf slot, then one
    ``(n,)`` gather reads every vertex's row, sentinel or heavy slot.

    ``lead`` is the leading shape (parts may be empty: an edgeless graph
    has no buckets, and every merge_first entry reads the +inf slot).
    ``merge_heavy`` is None where there is no split vertex (H = 0)."""
    flat = jnp.concatenate(
        parts + [jnp.full(lead + (1,), INF, jnp.float32)], axis=-1
    )
    if merge_heavy is not None and merge_heavy.shape[0]:
        heavy = jnp.min(jnp.take(flat, merge_heavy, axis=-1), axis=-1)
        flat = jnp.concatenate([flat, heavy], axis=-1)
    return jnp.take(flat, merge_first, axis=-1)


def _slice_mins(vec, slice_refs):
    """Per-bucket row-mins of one gather vector stack (..., n_idx)."""
    parts = []
    for cols_ref, ws_ref in slice_refs:
        idx = cols_ref[...]
        w = ws_ref[...]
        parts.append(jnp.min(
            jnp.take(vec, idx, axis=-1) + w[(None,) * (vec.ndim - 1)], axis=-1
        ))
    return parts


def _pad_back(vec_n, n_idx):
    """(..., n) -> (..., n_idx) with +inf (re-enter the gather index space)."""
    pad = [(0, 0)] * (vec_n.ndim - 1) + [(0, n_idx - vec_n.shape[-1])]
    return jnp.pad(vec_n, pad, constant_values=INF)


def _plan_operands(sliced):
    """BlockSpecs and operands of the merge plan: ``merge_first``, then
    ``merge_heavy`` only where some vertex is split (a zero-size block
    does not lower)."""
    specs = [pl.BlockSpec(sliced.merge_first.shape, lambda: (0,))]
    operands = [sliced.merge_first]
    if sliced.split_vertices:
        specs.append(pl.BlockSpec(sliced.merge_heavy.shape, lambda: (0, 0)))
        operands.append(sliced.merge_heavy)
    return specs, operands


def _plan_refs(refs, n_plan):
    """``(merge_heavy, merge_first, rest)`` from a kernel's refs that start
    with the :func:`_plan_operands` blocks."""
    heavy = refs[1][...] if n_plan == 2 else None
    return heavy, refs[0][...], refs[n_plan:]


@functools.partial(jax.jit, static_argnames=("interpret",))
def ell_sliced_gather_min_batch(vecs, sliced, *, interpret: bool | None = None):
    """One-launch sliced multi-vector gather-min: (V, B, n) row-mins of
    ``vecs`` over every bucket of a ``SlicedEll``, merged in-kernel."""
    interpret = _kcfg.resolve_interpret(interpret)
    v, b, n = vecs.shape
    # empty buckets contribute no rows (and zero-size blocks do not
    # lower); the merge plan's concat order is preserved by skipping
    slices = tuple(s for s in sliced.slices if s.rows.shape[0])
    n_idx = -(-(n + 1) // 128) * 128

    plan_specs, plan = _plan_operands(sliced)

    def kernel(vecs_ref, *refs):
        heavy, first, refs = _plan_refs(refs, len(plan))
        slice_refs = [(refs[2 * i], refs[2 * i + 1]) for i in range(len(slices))]
        parts = _slice_mins(vecs_ref[...], slice_refs)
        out_ref = refs[-1]
        out_ref[...] = _merge_parts(parts, heavy, first, (v, b))

    in_specs = [pl.BlockSpec((v, b, n_idx), lambda: (0, 0, 0))] + plan_specs
    operands = [_pad_idx(vecs, n_idx)] + plan
    for s in slices:
        in_specs += [pl.BlockSpec(s.cols.shape, lambda: (0, 0)),
                     pl.BlockSpec(s.ws.shape, lambda: (0, 0))]
        operands += [s.cols, s.ws]
    return pl.pallas_call(
        kernel,
        grid=(),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((v, b, n), lambda: (0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((v, b, n), jnp.float32),
        interpret=interpret,
    )(*operands)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ell_sliced_relax_keys_batch(dmask, ga, gb, gc, sliced, *,
                                interpret: bool | None = None):
    """One-launch sliced fused in-scan: ``(upd (B, n), keys (K, B, n))`` —
    the sliced twin of :func:`ell_relax_keys_batch` (relax buckets, merge,
    post-phase gates from ``fin(upd)``, key buckets, merge — one launch)."""
    interpret = _kcfg.resolve_interpret(interpret)
    b, n = dmask.shape
    k = ga.shape[0]
    # empty buckets contribute no rows (and zero-size blocks do not
    # lower); the merge plan's concat order is preserved by skipping
    slices = tuple(s for s in sliced.slices if s.rows.shape[0])
    n_idx = -(-(n + 1) // 128) * 128

    plan_specs, plan = _plan_operands(sliced)

    def kernel(dmask_ref, ga_ref, gb_ref, gc_ref, *refs):
        heavy, first, refs = _plan_refs(refs, len(plan))
        slice_refs = [(refs[2 * i], refs[2 * i + 1]) for i in range(len(slices))]
        upd_ref, keys_ref = refs[-2], refs[-1]
        upd = _merge_parts(_slice_mins(dmask_ref[...], slice_refs), heavy,
                           first, (b,))
        fin = _pad_back(jnp.where(upd < INF, 0.0, INF), n_idx)
        gate = jnp.minimum(
            ga_ref[...], jnp.minimum(gb_ref[...], gc_ref[...] + fin[None])
        )
        keys_ref[...] = _merge_parts(_slice_mins(gate, slice_refs), heavy,
                                     first, (k, b))
        upd_ref[...] = upd

    in_specs = [pl.BlockSpec((b, n_idx), lambda: (0, 0)),
                pl.BlockSpec((k, b, n_idx), lambda: (0, 0, 0)),
                pl.BlockSpec((k, b, n_idx), lambda: (0, 0, 0)),
                pl.BlockSpec((k, b, n_idx), lambda: (0, 0, 0))] + plan_specs
    operands = [_pad_idx(dmask, n_idx), _pad_idx(ga, n_idx),
                _pad_idx(gb, n_idx), _pad_idx(gc, n_idx)] + plan
    for s in slices:
        in_specs += [pl.BlockSpec(s.cols.shape, lambda: (0, 0)),
                     pl.BlockSpec(s.ws.shape, lambda: (0, 0))]
        operands += [s.cols, s.ws]
    return pl.pallas_call(
        kernel,
        grid=(),
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((b, n), lambda: (0, 0)),
                   pl.BlockSpec((k, b, n), lambda: (0, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((b, n), jnp.float32),
                   jax.ShapeDtypeStruct((k, b, n), jnp.float32)],
        interpret=interpret,
    )(*operands)


@functools.partial(jax.jit, static_argnames=("dep_idx", "interpret"))
def ell_sliced_keys_dep_batch(gates, dga, dgb, sliced, *, dep_idx: int = 0,
                              interpret: bool | None = None):
    """One-launch sliced fused out-scan: keys ``(K0 + 1, B, n)`` — the
    sliced twin of :func:`ell_keys_dep_batch`."""
    interpret = _kcfg.resolve_interpret(interpret)
    k0, b, n = gates.shape
    if not 0 <= dep_idx < k0:
        raise ValueError(f"dep_idx {dep_idx} out of range for K0={k0}")
    # empty buckets contribute no rows (and zero-size blocks do not
    # lower); the merge plan's concat order is preserved by skipping
    slices = tuple(s for s in sliced.slices if s.rows.shape[0])
    n_idx = -(-(n + 1) // 128) * 128

    plan_specs, plan = _plan_operands(sliced)

    def kernel(gates_ref, dga_ref, dgb_ref, *refs):
        heavy, first, refs = _plan_refs(refs, len(plan))
        slice_refs = [(refs[2 * i], refs[2 * i + 1]) for i in range(len(slices))]
        keys_ref = refs[-1]
        keys0 = _merge_parts(_slice_mins(gates_ref[...], slice_refs), heavy,
                             first, (k0, b))
        dep = _pad_back(keys0[dep_idx], n_idx)
        gate = jnp.minimum(dga_ref[...], dgb_ref[...] + dep)
        dep_key = _merge_parts(_slice_mins(gate, slice_refs), heavy, first,
                               (b,))
        keys_ref[...] = jnp.concatenate([keys0, dep_key[None]], axis=0)

    in_specs = [pl.BlockSpec((k0, b, n_idx), lambda: (0, 0, 0)),
                pl.BlockSpec((b, n_idx), lambda: (0, 0)),
                pl.BlockSpec((b, n_idx), lambda: (0, 0))] + plan_specs
    operands = [_pad_idx(gates, n_idx), _pad_idx(dga, n_idx),
                _pad_idx(dgb, n_idx)] + plan
    for s in slices:
        in_specs += [pl.BlockSpec(s.cols.shape, lambda: (0, 0)),
                     pl.BlockSpec(s.ws.shape, lambda: (0, 0))]
        operands += [s.cols, s.ws]
    return pl.pallas_call(
        kernel,
        grid=(),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((k0 + 1, b, n), lambda: (0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((k0 + 1, b, n), jnp.float32),
        interpret=interpret,
    )(*operands)


# ---------------------------------------------------------------------------
# 3. fused out-scan with one dependent key (out_full <- out_dyn)
# ---------------------------------------------------------------------------


def _keys_dep_kernel_single(gates_ref, dga_ref, dgb_ref, cols_ref, ws_ref,
                            keys_ref, *, dep_idx: int):
    """One-tile variant: both sweeps in one grid step, no predication and
    only static stores (see _relax_keys_kernel_single)."""
    idx = cols_ref[...]
    w = ws_ref[...]
    k0 = gates_ref.shape[0]
    keys0 = jnp.min(
        jnp.take(gates_ref[...], idx, axis=2) + w[None, None], axis=3
    )  # (K0, B, n_idx) — rows_pad == n_idx here
    gate = jnp.minimum(dga_ref[...], dgb_ref[...] + keys0[dep_idx])
    dep = jnp.min(jnp.take(gate, idx, axis=1) + w[None], axis=2)
    keys_ref[...] = jnp.concatenate([keys0, dep[None]], axis=0)


def _keys_dep_kernel(gates_ref, dga_ref, dgb_ref, cols_ref, ws_ref, keys_ref,
                     *, block_rows: int, dep_idx: int):
    sweep = pl.program_id(0)
    i = pl.program_id(1)
    idx = cols_ref[...]
    w = ws_ref[...]
    k0 = gates_ref.shape[0]

    @pl.when(sweep == 0)
    def _independent():
        gates = gates_ref[...]  # (K0, B, n_idx)
        vals = jnp.take(gates, idx, axis=2) + w[None, None]
        keys_ref[:k0, :, pl.ds(i * block_rows, block_rows)] = jnp.min(vals, axis=3)

    @pl.when(sweep == 1)
    def _dependent():
        dep = keys_ref[dep_idx]  # (B, n_idx) — resident from sweep 0
        gate = jnp.minimum(dga_ref[...], dgb_ref[...] + dep)
        vals = jnp.take(gate, idx, axis=1) + w[None]  # (B, Bn, D)
        keys_ref[k0, :, pl.ds(i * block_rows, block_rows)] = jnp.min(vals, axis=2)


@functools.partial(
    jax.jit, static_argnames=("dep_idx", "block_rows", "interpret")
)
def ell_keys_dep_batch(
    gates: jax.Array,  # (K0, B, n) f32 independent out-side gates
    dga: jax.Array,  # (B, n) f32 dependent-gate part a (0 on F, +inf else)
    dgb: jax.Array,  # (B, n) f32 dependent-gate part b (0 on U, +inf else)
    cols: jax.Array,  # (n, D) int32 outgoing ELL (sentinel id = n)
    ws: jax.Array,  # (n, D) f32, +inf padding
    *,
    dep_idx: int = 0,
    block_rows: int = 256,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused out-scan: returns keys ``(K0 + 1, B, n)``.

    Rows ``[:K0]`` are the independent keys (bitwise ``ell_key_min_batch``
    per gate); row ``K0`` is the dependent key reduced through the gate
    ``min(dga, dgb + keys[dep_idx])`` — for ``out_full`` that is "targets in
    F contribute the edge, targets in U contribute edge + the target's
    out_dyn" (paper Eq. 2), computed in the same launch that produced
    ``out_dyn``.
    """
    interpret = _kcfg.resolve_interpret(interpret)
    k0, b, n = gates.shape
    if not 0 <= dep_idx < k0:
        raise ValueError(f"dep_idx {dep_idx} out of range for K0={k0}")
    n_rows, d_pad = cols.shape
    rows_pad = max(-(-n_rows // block_rows) * block_rows,
                   _rows_pad_for(n, block_rows))
    cols, ws = _pad_rows(cols, ws, rows_pad)
    gates = _pad_idx(gates, rows_pad)
    dga = _pad_idx(dga, rows_pad)
    dgb = _pad_idx(dgb, rows_pad)
    n_tiles = rows_pad // block_rows
    if n_tiles == 1:
        grid = (1,)
        kernel = functools.partial(_keys_dep_kernel_single, dep_idx=dep_idx)
        tile_map = lambda i: (0, 0)  # noqa: E731 — one tile, constant maps
        maps2 = lambda i: (0, 0)  # noqa: E731
        maps3 = lambda i: (0, 0, 0)  # noqa: E731
    else:
        grid = (2, n_tiles)
        kernel = functools.partial(
            _keys_dep_kernel, block_rows=block_rows, dep_idx=dep_idx
        )
        tile_map = lambda s, i: (i, 0)  # noqa: E731
        maps2 = lambda s, i: (0, 0)  # noqa: E731
        maps3 = lambda s, i: (0, 0, 0)  # noqa: E731
    keys = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(gates.shape, maps3),
            pl.BlockSpec(dga.shape, maps2),
            pl.BlockSpec(dgb.shape, maps2),
            pl.BlockSpec((block_rows, d_pad), tile_map),
            pl.BlockSpec((block_rows, d_pad), tile_map),
        ],
        out_specs=pl.BlockSpec((k0 + 1, b, rows_pad), maps3),
        out_shape=jax.ShapeDtypeStruct((k0 + 1, b, rows_pad), jnp.float32),
        interpret=interpret,
    )(gates, dga, dgb, cols, ws)
    return keys[:, :, :n_rows]


def register_kernels(reg):
    """Register this module's kernel contracts (``kernels/registry.py``)."""
    from repro.kernels import registry as R

    n, b, k = R.FIXTURE_N, R.FIXTURE_B, R.FIXTURE_K

    def cases_gather():
        cols, ws = R.fixture_ell()
        vecs = R.fixture_rows((k, b, n))
        return (
            R.SpecCase("multi_tile", (vecs, cols, ws),
                       {"block_rows": R.SMALL_BLOCK_ROWS}),
            R.SpecCase("one_tile", (vecs, cols, ws)),
        )

    def cases_relax_keys():
        cols, ws = R.fixture_ell()
        dmask = R.fixture_rows((b, n), seed=6)
        ga = R.fixture_rows((k, b, n), seed=7)
        gb = R.fixture_rows((k, b, n), seed=8)
        gc = R.fixture_rows((k, b, n), seed=9)
        return (
            R.SpecCase("two_sweep", (dmask, ga, gb, gc, cols, ws),
                       {"block_rows": R.SMALL_BLOCK_ROWS}),
            R.SpecCase("one_tile", (dmask, ga, gb, gc, cols, ws)),
        )

    def cases_keys_dep():
        cols, ws = R.fixture_ell()
        gates = R.fixture_rows((k, b, n), seed=10)
        dga = R.fixture_rows((b, n), seed=11)
        dgb = R.fixture_rows((b, n), seed=12)
        return (
            R.SpecCase("two_sweep", (gates, dga, dgb, cols, ws),
                       {"block_rows": R.SMALL_BLOCK_ROWS, "dep_idx": 1}),
            R.SpecCase("one_tile", (gates, dga, dgb, cols, ws)),
        )

    def cases_sliced_gather():
        sl = R.fixture_sliced(side="in")
        vecs = R.fixture_rows((k, b, n), seed=13)
        return (R.SpecCase("sliced", (vecs, sl)),)

    def cases_sliced_relax_keys():
        sl = R.fixture_sliced(side="in")
        dmask = R.fixture_rows((b, n), seed=14)
        ga = R.fixture_rows((k, b, n), seed=15)
        gb = R.fixture_rows((k, b, n), seed=16)
        gc = R.fixture_rows((k, b, n), seed=17)
        return (R.SpecCase("sliced", (dmask, ga, gb, gc, sl)),)

    def cases_sliced_keys_dep():
        sl = R.fixture_sliced(side="out")
        gates = R.fixture_rows((k, b, n), seed=18)
        dga = R.fixture_rows((b, n), seed=19)
        dgb = R.fixture_rows((b, n), seed=20)
        return (R.SpecCase("sliced", (gates, dga, dgb, sl)),)

    reg.register(R.KernelContract(
        name="ell_gather_min_batch", module=__name__,
        wrapper=ell_gather_min_batch, make_cases=cases_gather,
        notes="stacked multi-vector gather-min; tiled, one writer per tile",
    ))
    reg.register(R.KernelContract(
        name="ell_relax_keys_batch", module=__name__,
        wrapper=ell_relax_keys_batch, make_cases=cases_relax_keys,
        resident_outputs=(0, 1),
        notes="two-sweep fused in-scan: sweep 1 gathers from the resident "
              "upd output, so both outputs use constant index maps",
    ))
    reg.register(R.KernelContract(
        name="ell_keys_dep_batch", module=__name__,
        wrapper=ell_keys_dep_batch, make_cases=cases_keys_dep,
        resident_outputs=(0,),
        notes="two-sweep fused out-scan: dependent key row reads the "
              "resident independent rows from sweep 0",
    ))
    reg.register(R.KernelContract(
        name="ell_sliced_gather_min_batch", module=__name__,
        wrapper=ell_sliced_gather_min_batch, make_cases=cases_sliced_gather,
        notes="grid=() sliced megascan: single instance, no race surface",
    ))
    reg.register(R.KernelContract(
        name="ell_sliced_relax_keys_batch", module=__name__,
        wrapper=ell_sliced_relax_keys_batch,
        make_cases=cases_sliced_relax_keys,
        notes="grid=() sliced fused in-scan over degree buckets",
    ))
    reg.register(R.KernelContract(
        name="ell_sliced_keys_dep_batch", module=__name__,
        wrapper=ell_sliced_keys_dep_batch, make_cases=cases_sliced_keys_dep,
        notes="grid=() sliced fused out-scan over degree buckets",
    ))
