"""Jitted public wrappers around the Pallas kernels.

Execution policy (interpret-vs-compiled, tile sizes) is resolved by
``repro.kernels.config``: ``interpret=None`` means "compiled Mosaic on TPU,
interpret elsewhere, unless ``REPRO_KERNEL_MODE`` overrides", and
``block_rows=None`` / ``block=None`` consult the tuning ledger before
falling back to a VMEM-budget default. Explicit arguments always win.

Every batched wrapper also accepts ``use_pallas``. The default (False) runs
the ref.py XLA form — the path the engines run, on TPU as on CPU — *through
the same padding/masking code* as the kernel path, so the two can never
drift apart bitwise; ``use_pallas=True`` opts into the Pallas kernels.
Engines select the path, never pad themselves (this is THE one home of the
sentinel/alignment convention).

Adjacency layouts: wrappers taking an ``ell`` argument accept either the
padded ``(cols, ws)`` pair (``to_ell_in``) or a degree-sliced
``SlicedEll`` (``to_ell_in_sliced``) — sliced layouts run a one-launch
variadic megascan under interpret (all buckets + the two-level gather
merge, ``merge_heavy`` then ``merge_first``, inside one kernel) or one
tiled call per bucket on compiled backends (split heavy rows fold in the
merge; f32 min is exact, so both layouts return bit-identical results).

The production engines (``repro.core.static_engine`` stepper and everything
built on it) consume the batched 2-D entry points; the 1-D
``relax_settled``/``static_thresholds`` wrappers are retained as reference
surfaces — ``tests/test_kernels.py`` pins the 2-D kernels row-for-row
against them (DESIGN.md Sec. 5), so they must stay bit-consistent.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import config as kcfg
from repro.kernels import ref as kref
from repro.kernels.ell_key_min import ell_key_min, ell_key_min_batch
from repro.kernels.ell_relax import ell_relax, ell_relax_batch
from repro.kernels.ell_relax_keys import (
    _merge_parts,
    _pad_idx,
    ell_gather_min_batch,
    ell_keys_dep_batch,
    ell_relax_keys_batch,
    ell_sliced_gather_min_batch,
    ell_sliced_keys_dep_batch,
    ell_sliced_relax_keys_batch,
)
from repro.kernels.frontier_crit import (
    frontier_crit,
    frontier_crit_batch,
    frontier_crit_lanes_batch,
)

INF = jnp.inf

# A bucket's gather writes its slots as f32[slots, V·B] with V·B (keys times
# serving lanes) in the minor dimension, which a TPU (8, 128) tile pads to a
# multiple of 128 lanes: with 8 lanes each slot takes 512 B. Buckets of more
# slots than a slab of this many bytes holds run as a loop over row tiles
# (``_gather_min_tiled``), so the slab stays in VMEM and never goes to HBM.
_TILE_SLAB_BYTES = 16 * 2**20


def _tile_rows(width: int, lanes: int) -> int:
    """Rows of a ``width``-wide bucket per tile whose padded slab of
    ``lanes`` (V·B) lanes per slot fits ``_TILE_SLAB_BYTES``."""
    slot_bytes = 4 * 128 * -(-lanes // 128)
    return max(1, _TILE_SLAB_BYTES // (width * slot_bytes))


def _is_sliced(ell) -> bool:
    """Duck-typed layout test (SlicedEll is a NamedTuple with ``slices``)."""
    return hasattr(ell, "slices")


def pad_lane_batch(x: jax.Array, fill=INF) -> jax.Array:
    """(B, n) -> (B, lane_pad) with ``fill`` beyond column n.

    THE sentinel/alignment convention of every single-purpose ELL gather
    kernel: one extra slot for the sentinel neighbour id (index n) plus
    rounding to the 128-lane multiple, all carrying a min-neutral fill.
    Kernel and ref paths share this helper *inside* the wrappers below, so
    the two paths can never drift apart bitwise. (The fused megakernels own
    a wider padding — their gather space must also cover the row tiles —
    inside ``ell_relax_keys.py``.)
    """
    b, n = x.shape
    lane_pad = -(-(n + 1) // 128) * 128
    return jnp.full((b, lane_pad), fill, jnp.float32).at[:, :n].set(x)


# The one slice->vertex merge implementation (concat + inf sentinel, the
# split vertices' take(merge_heavy) + min, one take(merge_first)) is
# ell_relax_keys._merge_parts; the sliced kernel bodies and this host-side
# path must share it so the merge convention can never diverge between them.


def relax_settled(
    d: jax.Array,  # (n,) f32 tentative distances
    settle_mask: jax.Array,  # (n,) bool — vertices settled this phase
    ell_cols: jax.Array,  # (n, D) int32 incoming ELL (sentinel id = n)
    ell_ws: jax.Array,  # (n, D) f32
    *,
    block_rows: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Candidate-update vector: upd[v] = min over in-edges from settled sources.

    The sentinel slot (index n) and any alignment padding carry +inf, so
    padded ELL entries are neutral.
    """
    interpret = kcfg.resolve_interpret(interpret)
    n = d.shape[0]
    if block_rows is None:
        block_rows = kcfg.resolve_block_rows("relax", n, ell_cols.shape[1])
    lane_pad = -(-(n + 1) // 128) * 128
    dmask = jnp.full((lane_pad,), INF, jnp.float32)
    dmask = dmask.at[:n].set(jnp.where(settle_mask, d, INF))
    return ell_relax(dmask, ell_cols, ell_ws, block_rows=block_rows,
                     interpret=interpret)


def static_thresholds(
    d: jax.Array,
    status: jax.Array,
    out_min_static: jax.Array,
    *,
    block: int | None = None,
    interpret: bool | None = None,
):
    """(min_F d, L_out, |F|) for the INSTATIC/OUTSTATIC criteria, fused."""
    interpret = kcfg.resolve_interpret(interpret)
    if block is None:
        block = kcfg.resolve_block(d.shape[0])
    return frontier_crit(d, status, out_min_static, block=block, interpret=interpret)


def relax_settled_batch(
    d: jax.Array,  # (B, n) f32 tentative distances, one row per query
    settle_mask: jax.Array,  # (B, n) bool — per-row vertices settled this phase
    ell_cols: jax.Array,  # (n, D) int32 incoming ELL shared by the batch
    ell_ws: jax.Array,  # (n, D) f32
    *,
    block_rows: int | None = None,
    interpret: bool | None = None,
    use_pallas: bool = False,
) -> jax.Array:
    """Batched candidate updates (B, n); one adjacency load serves all rows."""
    interpret = kcfg.resolve_interpret(interpret)
    b, n = d.shape
    dmask = pad_lane_batch(jnp.where(settle_mask, d, INF))
    if not use_pallas:
        return kref.ell_relax_batch_ref(dmask, ell_cols, ell_ws)
    if block_rows is None:
        block_rows = kcfg.resolve_block_rows("relax", n, ell_cols.shape[1], b)
    return ell_relax_batch(
        dmask, ell_cols, ell_ws, block_rows=block_rows, interpret=interpret
    )


def relax_settled_batch_sliced(
    d: jax.Array,  # (B, n)
    settle_mask: jax.Array,  # (B, n)
    sliced,  # SlicedEll over the incoming adjacency
    *,
    block_rows: int | None = None,
    interpret: bool | None = None,
    use_pallas: bool = False,
) -> jax.Array:
    """Sliced-layout twin of :func:`relax_settled_batch` (bit-identical)."""
    dmask = jnp.where(settle_mask, d, INF)
    return gather_min_batch_sliced(
        dmask[None], sliced, block_rows=block_rows, interpret=interpret,
        use_pallas=use_pallas,
    )[0]


def gather_min_batch_sliced(
    vecs: jax.Array,  # (V, B, n) f32 gather vectors (unpadded)
    sliced,  # SlicedEll
    *,
    block_rows: int | None = None,
    interpret: bool | None = None,
    use_pallas: bool = False,
) -> jax.Array:
    """(V, B, n) per-vector row-mins over a degree-sliced adjacency.

    Interpret runs the one-launch megascan (every bucket + the gather-merge
    inside a single kernel — launch emulation dominates per-bucket calls
    there); compiled backends run one tiled multi-vector call per bucket and
    merge outside. Bit-identical either way.
    """
    interpret = kcfg.resolve_interpret(interpret)
    if use_pallas and interpret:
        # already resolved interpret=True by the guard above
        return ell_sliced_gather_min_batch(
            vecs, sliced, interpret=True)  # repro: allow(hardcoded-interpret)
    v, b, n = vecs.shape
    # the XLA form's gather source, padded with the +inf sentinel column
    # once for every bucket and tile
    src = None if use_pallas else _pad_idx(vecs, n + 1)
    parts = []
    for s in sliced.slices:
        if s.rows.shape[0] == 0:
            continue  # zero rows: contributes nothing to the concat order
        if not use_pallas:
            if parts:
                # each bucket's gather after the previous bucket's row-min,
                # narrowest first: left free, XLA's TPU scheduler starts
                # with the widest and spills the gather source to HBM for
                # the next (on a v5e at Graph500 scale 16, the 128-wide
                # bucket's gather then takes 4.2 ms per phase, not 0.9)
                src, _ = jax.lax.optimization_barrier((src, parts[-1]))
            parts.append(_gather_min_tiled(src, s.cols, s.ws))
            continue
        br = block_rows
        if br is None:
            br = kcfg.resolve_block_rows(
                "gather_sliced", n, s.cols.shape[1], b, vecs=v, outs=v,
                n_rows=s.rows.shape[0],
            )
        parts.append(ell_gather_min_batch(
            vecs, s.cols, s.ws, block_rows=br, interpret=interpret
        ))
    return _merge_parts(parts, sliced.merge_heavy, sliced.merge_first, (v, b))


def _gather_min_tiled(src, cols, ws):
    """``kref.ell_gather_min_batch_ref`` over row tiles of ``_tile_rows``
    rows, from the gather source ``src`` (V, B, n + 1) whose column n is
    +inf: each tile gathers, adds ``ws`` and takes its row-min before the
    next, so no slab outgrows VMEM. The last tile is padded with the
    sentinel column n, and the min is exact: bit-identical rows."""
    v, b, n = src.shape[0], src.shape[1], src.shape[2] - 1

    def tile(cw):
        c, w = cw
        # columns lie in [0, n]: clipping reads what the oracle's default
        # (fill) mode reads, without a bounds mask over every slot
        g = jnp.take(src, c, axis=2, mode="clip")
        return jnp.min(g + w[None, None], axis=-1)

    r, d = cols.shape
    rows = _tile_rows(d, v * b)
    if r <= rows:
        return tile((cols, ws))
    tiles = -(-r // rows)
    pad = ((0, tiles * rows - r), (0, 0))
    cols = jnp.pad(cols, pad, constant_values=n).reshape(tiles, rows, d)
    ws = jnp.pad(ws, pad).reshape(tiles, rows, d)
    out = jax.lax.map(tile, (cols, ws))
    return jnp.moveaxis(out, 0, 2).reshape(v, b, tiles * rows)[..., :r]


def static_thresholds_batch(
    d: jax.Array,  # (B, n)
    status: jax.Array,  # (B, n)
    out_min_static: jax.Array,  # (n,) shared
    *,
    block: int | None = None,
    interpret: bool | None = None,
):
    """Per-row (min_F d, L_out, |F|) — each (B,) — in one fused pass."""
    interpret = kcfg.resolve_interpret(interpret)
    if block is None:
        block = kcfg.resolve_block(d.shape[1])
    return frontier_crit_batch(
        d, status, out_min_static, block=block, interpret=interpret
    )


def crit_thresholds_batch(
    d: jax.Array,  # (B, n)
    status: jax.Array,  # (B, n)
    keys: jax.Array | None,  # (K, n) shared | (K, B, n) per-lane | None
    *,
    block: int | None = None,
    interpret: bool | None = None,
    use_pallas: bool = False,
):
    """Plan-lane thresholds: (mins (1+K, B), |F| (B,)) in one fused pass.

    The criterion-plan generalisation of :func:`static_thresholds_batch`:
    ``mins[0]`` is min_F d, ``mins[1+k]`` the OUT lane for ``keys[k]``.
    """
    if not use_pallas:
        return kref.frontier_crit_lanes_batch_ref(d, status, keys)
    interpret = kcfg.resolve_interpret(interpret)
    if block is None:
        block = kcfg.resolve_block(d.shape[1])
    return frontier_crit_lanes_batch(d, status, keys, block=block,
                                     interpret=interpret)


def key_min_batch(
    gate: jax.Array,  # (B, n) f32 per-lane criterion gate (not yet padded)
    ell_cols: jax.Array,  # (n, D) int32 adjacency (incoming OR outgoing view)
    ell_ws: jax.Array,  # (n, D) f32
    *,
    block_rows: int | None = None,
    interpret: bool | None = None,
    use_pallas: bool = False,
) -> jax.Array:
    """Dynamic criterion key (B, n): per-lane min of gate[neighbour] + w.

    Pads the gate to the lane multiple with +inf so the sentinel slot
    (index n) and alignment padding are neutral, mirroring
    :func:`relax_settled_batch`'s masking convention (both paths).
    """
    interpret = kcfg.resolve_interpret(interpret)
    padded = pad_lane_batch(gate)
    if not use_pallas:
        return kref.ell_key_min_batch_ref(padded, ell_cols, ell_ws)
    if block_rows is None:
        block_rows = kcfg.resolve_block_rows(
            "key_min", gate.shape[1], ell_cols.shape[1], gate.shape[0]
        )
    return ell_key_min_batch(
        padded, ell_cols, ell_ws, block_rows=block_rows, interpret=interpret
    )


def key_min_batch_any(gate, ell, **kw) -> jax.Array:
    """:func:`key_min_batch` over either adjacency layout."""
    if _is_sliced(ell):
        return gather_min_batch_sliced(gate[None], ell, **kw)[0]
    return key_min_batch(gate, ell[0], ell[1], **kw)


def weight_gated_ell(ell, delta):
    """Light/heavy weight-gated twins of an adjacency view.

    The Delta-stepping lowering: ``light`` keeps edge weights ``w <= delta``
    and masks the rest to +inf (min-neutral, exactly like padding slots);
    ``heavy`` keeps ``w > delta``. Column ids are shared with the input
    view, so both twins ride the ordinary key-min/gather kernels unchanged
    — the light/heavy split costs a weights-only elementwise pass, not a
    second adjacency layout. ``delta`` may be a traced scalar: the gates
    are data, so every bucket width shares one compiled program. Works on
    the padded ``(cols, ws)`` pair and on ``SlicedEll`` (per-slice gating;
    +inf padding lands in the heavy gate's +inf branch unchanged).
    """
    if _is_sliced(ell):
        def gated(keep_light: bool):
            return ell._replace(slices=tuple(
                s._replace(ws=jnp.where((s.ws <= delta) == keep_light,
                                        s.ws, INF))
                for s in ell.slices
            ))
        return gated(True), gated(False)
    cols, ws = ell
    return ((cols, jnp.where(ws <= delta, ws, INF)),
            (cols, jnp.where(ws > delta, ws, INF)))


def delta_relax_batch(
    d: jax.Array,  # (B, n) f32 tentative distances
    light_from: jax.Array,  # (B, n) bool — this light round's work set
    heavy_from: jax.Array,  # (B, n) bool — removed set on its heavy turn
    ell_light,  # light-gated incoming view (padded pair or SlicedEll)
    ell_heavy,  # heavy-gated incoming view (same layout)
    *,
    block_rows: int | None = None,
    interpret: bool | None = None,
    use_pallas: bool = False,
) -> jax.Array:
    """Delta-stepping candidate updates (B, n): the light relaxation of
    ``light_from`` and the heavy relaxation of ``heavy_from``, min-merged.

    Both sides are ordinary +inf-gated key-min scans over the
    :func:`weight_gated_ell` twins — a lane on a light round carries an
    empty heavy gate (and vice versa), so mixed-mode batches stay one
    uniform program. Masking mirrors :func:`relax_settled_batch` (shared
    padding path), so kernel and ref paths cannot drift bitwise.
    """
    kw = dict(block_rows=block_rows, interpret=interpret,
              use_pallas=use_pallas)
    upd_light = key_min_batch_any(jnp.where(light_from, d, INF), ell_light,
                                  **kw)
    upd_heavy = key_min_batch_any(jnp.where(heavy_from, d, INF), ell_heavy,
                                  **kw)
    return jnp.minimum(upd_light, upd_heavy)


# ---------------------------------------------------------------------------
# Goal-directed (bound-gated) variants: s->t pruning (DESIGN.md Sec. 13)
# ---------------------------------------------------------------------------


def _bound_gate(d: jax.Array, settle_mask: jax.Array,
                bound: jax.Array) -> jax.Array:
    """Prune relax sources at or beyond the lane's target bound.

    ``bound`` is (B,) f32 — the target's current tentative distance (+inf
    on full-solve lanes, which makes the gate a per-lane no-op). A settled
    vertex with ``d >= bound`` can only emit updates ``>= bound`` (f32 add
    of a non-negative weight is monotone), and ``bound`` never rises below
    the target's final distance, so dropping these sources can never
    change ``dist[target]`` — the correctness argument DESIGN.md Sec. 13
    spells out. The ``>=`` edge is safe: equality at the bound implies the
    target's tentative already equals its final distance.
    """
    return settle_mask & (d < bound[:, None])


def relax_settled_gated_batch(
    d: jax.Array,  # (B, n) f32 tentative distances
    settle_mask: jax.Array,  # (B, n) bool — vertices settled this phase
    bound: jax.Array,  # (B,) f32 per-lane pruning bound (+inf = off)
    ell,  # (cols, ws) padded ELL or SlicedEll — incoming adjacency
    *,
    block_rows: int | None = None,
    interpret: bool | None = None,
    use_pallas: bool = False,
) -> jax.Array:
    """Goal-directed twin of :func:`relax_settled_batch` (either layout):
    settled sources at or beyond ``bound`` are masked out of the scan."""
    gated = _bound_gate(d, settle_mask, bound)
    kw = dict(block_rows=block_rows, interpret=interpret,
              use_pallas=use_pallas)
    if _is_sliced(ell):
        return relax_settled_batch_sliced(d, gated, ell, **kw)
    return relax_settled_batch(d, gated, ell[0], ell[1], **kw)


def in_scan_relax_keys_gated_batch(
    d: jax.Array,  # (B, n) f32 tentative distances
    settle_mask: jax.Array,  # (B, n) bool — vertices settled this phase
    bound: jax.Array,  # (B,) f32 per-lane pruning bound (+inf = off)
    gate_parts,  # tuple of (ga, gb, gc) triples, one per in-scan key
    ell,  # (cols, ws) padded ELL or SlicedEll — incoming adjacency
    *,
    block_rows: int | None = None,
    interpret: bool | None = None,
    use_pallas: bool = False,
):
    """Goal-directed twin of :func:`in_scan_relax_keys_batch`.

    Only the RELAX side is bound-gated; the key gates (built by the caller
    from the full post-settle status) pass through untouched, and the
    fused kernel's ``fin(upd)`` fringe-entry term then reflects the pruned
    update — so the emitted keys stay bitwise what re-deriving them from
    the pruned state's status would give (the carried-key invariant the
    stepper's priming relies on survives pruning unchanged).
    """
    gated = _bound_gate(d, settle_mask, bound)
    return in_scan_relax_keys_batch(
        d, gated, gate_parts, ell, block_rows=block_rows,
        interpret=interpret, use_pallas=use_pallas,
    )


# ---------------------------------------------------------------------------
# Fused single-scan entry points (DESIGN.md Sec. 9)
# ---------------------------------------------------------------------------


def _gather_padded(vecs, cols, ws, kind, block_rows, interpret):
    """One single-sweep multi-vector gather over a padded ELL."""
    v, b, _ = vecs.shape
    if block_rows is None:
        block_rows = kcfg.resolve_block_rows(
            kind, vecs.shape[2], cols.shape[1], b, vecs=v, outs=v,
            n_rows=cols.shape[0],
        )
    return ell_gather_min_batch(vecs, cols, ws, block_rows=block_rows,
                                interpret=interpret)


def _use_fused(n: int, n_rows: int, block_rows: int, interpret: bool) -> bool:
    """Whether a dependent two-reduction scan runs as ONE fused launch.

    Policy (``config.scan_fusion``): ``fused``/``split`` force it; ``auto``
    fuses on compiled backends (launches cost real time there) and, under
    interpret, only when the scan is a single tile — the one-tile megakernel
    body has no predication/dynamic-store machinery, which is what makes
    fusion win under emulation too (BENCH_fused.json measures all three).
    """
    mode = kcfg.scan_fusion()
    if mode != "auto":
        return mode == "fused"
    if not interpret:
        return True
    return max(n_rows, n + 1) <= block_rows


def in_scan_relax_keys_batch(
    d: jax.Array,  # (B, n) f32 tentative distances
    settle_mask: jax.Array,  # (B, n) bool — vertices settled this phase
    gate_parts,  # tuple of (ga, gb, gc) triples, one per in-scan key
    ell,  # (cols, ws) padded ELL or SlicedEll — INCOMING adjacency
    *,
    block_rows: int | None = None,
    interpret: bool | None = None,
    use_pallas: bool = False,
):
    """The fused in-scan: ``(upd (B, n), keys (K, B, n))``.

    ``upd`` is this phase's relax update; ``keys[k]`` is the k-th in-side
    dynamic key evaluated on the *post-phase* status via the gate
    ``min(ga, gb, gc + fin(upd))`` (``criteria.in_scan_gate_parts``). On the
    padded layout the scan shape follows ``config.scan_fusion()``: the
    two-sweep megakernel (one launch, shared tile loads — the compiled-mode
    shape) or the split decomposition (relax gather -> XLA gate -> key
    gather; what the interpret machinery prefers). On the sliced layout the
    cross-slice ``upd`` dependency forces the split shape per bucket. Every
    combination is bitwise identical.
    """
    b, n = d.shape
    dmask = jnp.where(settle_mask, d, INF)
    ga = jnp.stack([p[0] for p in gate_parts])
    gb = jnp.stack([p[1] for p in gate_parts])
    gc = jnp.stack([p[2] for p in gate_parts])
    if _is_sliced(ell):
        if use_pallas and kcfg.resolve_interpret(interpret):
            # already resolved interpret=True by the guard above
            return ell_sliced_relax_keys_batch(
                dmask, ga, gb, gc, ell,
                interpret=True)  # repro: allow(hardcoded-interpret)
        upd = gather_min_batch_sliced(
            dmask[None], ell, block_rows=block_rows, interpret=interpret,
            use_pallas=use_pallas,
        )[0]
        fin = jnp.where(upd < INF, 0.0, INF)
        gates = jnp.minimum(ga, jnp.minimum(gb, gc + fin[None]))
        keys = gather_min_batch_sliced(
            gates, ell, block_rows=block_rows, interpret=interpret,
            use_pallas=use_pallas,
        )
        return upd, keys
    cols, ws = ell
    if not use_pallas:
        return kref.ell_relax_keys_batch_ref(dmask, ga, gb, gc, cols, ws)
    interpret = kcfg.resolve_interpret(interpret)
    if block_rows is None:
        block_rows = kcfg.resolve_block_rows(
            "relax_keys", n, cols.shape[1], b,
            vecs=1 + 3 * len(gate_parts), outs=1 + len(gate_parts),
            n_rows=cols.shape[0],
        )
    if not _use_fused(n, cols.shape[0], block_rows, interpret):
        upd = _gather_padded(dmask[None], cols, ws, "relax", block_rows,
                             interpret)[0]
        fin = jnp.where(upd < INF, 0.0, INF)
        gates = jnp.minimum(ga, jnp.minimum(gb, gc + fin[None]))
        return upd, _gather_padded(gates, cols, ws, "key_min", block_rows,
                                   interpret)
    return ell_relax_keys_batch(
        dmask, ga, gb, gc, cols, ws, block_rows=block_rows,
        interpret=interpret,
    )


def out_scan_keys_batch(
    gates: jax.Array,  # (K0, B, n) f32 independent out-side key gates
    dep_parts,  # (dga, dgb, dep_idx) for the dependent key, or None
    ell,  # (cols, ws) padded ELL or SlicedEll — OUTGOING adjacency
    *,
    block_rows: int | None = None,
    interpret: bool | None = None,
    use_pallas: bool = False,
) -> jax.Array:
    """The fused out-scan: keys ``(K0 [+1], B, n)``.

    All independent out-side keys ride one multi-vector scan; a dependent
    key (``out_full``) adds a second sweep inside the same launch on the
    padded layout, or one more bucket round on the sliced layout.
    """
    k0, b, n = gates.shape
    sliced = _is_sliced(ell)
    if dep_parts is None:
        if sliced:
            return gather_min_batch_sliced(
                gates, ell, block_rows=block_rows, interpret=interpret,
                use_pallas=use_pallas,
            )
        cols, ws = ell
        if not use_pallas:
            return kref.ell_gather_min_batch_ref(gates, cols, ws)
        interpret = kcfg.resolve_interpret(interpret)
        return _gather_padded(gates, cols, ws, "out_scan", block_rows,
                              interpret)
    dga, dgb, dep_idx = dep_parts
    if sliced and use_pallas and kcfg.resolve_interpret(interpret):
        # already resolved interpret=True by the guard above
        return ell_sliced_keys_dep_batch(
            gates, dga, dgb, ell, dep_idx=dep_idx,
            interpret=True)  # repro: allow(hardcoded-interpret)
    if not sliced and not use_pallas:
        cols, ws = ell
        return kref.ell_keys_dep_batch_ref(gates, dga, dgb, dep_idx, cols, ws)
    if not sliced:
        interpret = kcfg.resolve_interpret(interpret)
        cols, ws = ell
        if block_rows is None:
            block_rows = kcfg.resolve_block_rows(
                "out_scan_dep", n, cols.shape[1], b, vecs=k0 + 2,
                outs=k0 + 1, n_rows=cols.shape[0],
            )
        if _use_fused(n, cols.shape[0], block_rows, interpret):
            return ell_keys_dep_batch(
                gates, dga, dgb, cols, ws, dep_idx=dep_idx,
                block_rows=block_rows, interpret=interpret,
            )

    def scan(vs, kind):
        if sliced:
            return gather_min_batch_sliced(
                vs, ell, block_rows=block_rows, interpret=interpret,
                use_pallas=use_pallas,
            )
        return _gather_padded(vs, ell[0], ell[1], kind, block_rows,
                              kcfg.resolve_interpret(interpret))

    keys0 = scan(gates, "out_scan")
    gate = jnp.minimum(dga, dgb + keys0[dep_idx])
    dep_key = scan(gate[None], "key_min")
    return jnp.concatenate([keys0, dep_key], axis=0)


def register_kernels(reg):
    """Register the engine-facing wrapper contracts (``kernels/registry.py``).

    These are the callables the engines actually invoke; auditing them (in
    addition to the raw kernels) covers the padding/masking/layout-dispatch
    code the raw-kernel contracts cannot see. Resident/counter whitelists
    mirror the kernels each wrapper may delegate to.
    """
    from repro.kernels import registry as R

    n, b, k = R.FIXTURE_N, R.FIXTURE_B, R.FIXTURE_K
    thr = {"resident_outputs": (0, 1), "counter_outputs": (1,)}

    def cases_relax_settled():
        cols, ws = R.fixture_ell()
        d = R.fixture_rows((n,), seed=30)
        settle = R.fixture_status((n,), seed=31) == 1
        return (
            R.SpecCase("default", (d, settle, cols, ws)),
            R.SpecCase("multi_tile", (d, settle, cols, ws),
                       {"block_rows": R.SMALL_BLOCK_ROWS}),
        )

    def cases_static_thresholds():
        d = R.fixture_rows((n,), seed=32)
        status = R.fixture_status((n,), seed=33)
        out_min = R.fixture_rows((n,), seed=34)
        return (
            R.SpecCase("default", (d, status, out_min)),
            R.SpecCase("multi_step", (d, status, out_min), {"block": 4}),
        )

    def cases_relax_settled_batch():
        cols, ws = R.fixture_ell()
        d = R.fixture_rows((b, n), seed=35)
        settle = R.fixture_status((b, n), seed=36) == 1
        return (
            R.SpecCase("default", (d, settle, cols, ws)),
            R.SpecCase("multi_tile", (d, settle, cols, ws),
                       {"block_rows": R.SMALL_BLOCK_ROWS}),
        )

    def cases_relax_settled_sliced():
        sl = R.fixture_sliced(side="in")
        d = R.fixture_rows((b, n), seed=37)
        settle = R.fixture_status((b, n), seed=38) == 1
        return (R.SpecCase("sliced", (d, settle, sl)),)

    def cases_gather_sliced():
        sl = R.fixture_sliced(side="in")
        vecs = R.fixture_rows((k, b, n), seed=39)
        return (R.SpecCase("sliced", (vecs, sl)),)

    def cases_static_thresholds_batch():
        d = R.fixture_rows((b, n), seed=40)
        status = R.fixture_status((b, n), seed=41)
        out_min = R.fixture_rows((n,), seed=42)
        return (
            R.SpecCase("default", (d, status, out_min)),
            R.SpecCase("multi_step", (d, status, out_min), {"block": 4}),
        )

    def cases_crit_thresholds():
        d = R.fixture_rows((b, n), seed=43)
        status = R.fixture_status((b, n), seed=44)
        shared = R.fixture_rows((k, n), seed=45)
        per_lane = R.fixture_rows((k, b, n), seed=46)
        return (
            R.SpecCase("nokeys", (d, status, None)),
            R.SpecCase("shared_keys", (d, status, shared), {"block": 4}),
            R.SpecCase("per_lane_keys", (d, status, per_lane)),
        )

    def cases_key_min():
        cols, ws = R.fixture_ell()
        gate = R.fixture_rows((b, n), seed=47)
        return (
            R.SpecCase("default", (gate, cols, ws)),
            R.SpecCase("multi_tile", (gate, cols, ws),
                       {"block_rows": R.SMALL_BLOCK_ROWS}),
        )

    def cases_key_min_any():
        gate = R.fixture_rows((b, n), seed=48)
        return (
            R.SpecCase("padded", (gate, R.fixture_ell())),
            R.SpecCase("sliced", (gate, R.fixture_sliced(side="in"))),
        )

    def _gate_parts(seed0):
        return tuple(
            (R.fixture_rows((b, n), seed=seed0 + 3 * i),
             R.fixture_rows((b, n), seed=seed0 + 3 * i + 1),
             R.fixture_rows((b, n), seed=seed0 + 3 * i + 2))
            for i in range(k)
        )

    def cases_in_scan():
        ell = R.fixture_ell()
        sl = R.fixture_sliced(side="in")
        d = R.fixture_rows((b, n), seed=49)
        settle = R.fixture_status((b, n), seed=50) == 1
        gp = _gate_parts(51)
        return (
            R.SpecCase("fused", (d, settle, gp, ell)),
            R.SpecCase("split", (d, settle, gp, ell),
                       {"block_rows": R.SMALL_BLOCK_ROWS}),
            R.SpecCase("sliced", (d, settle, gp, sl)),
        )

    def cases_delta_relax():
        delta = jnp.float32(0.5)
        ell_l, ell_h = weight_gated_ell(R.fixture_ell(), delta)
        sl_l, sl_h = weight_gated_ell(R.fixture_sliced(side="in"), delta)
        d = R.fixture_rows((b, n), seed=70)
        light_from = R.fixture_status((b, n), seed=71) == 1
        heavy_from = R.fixture_status((b, n), seed=72) == 2
        return (
            R.SpecCase("padded", (d, light_from, heavy_from, ell_l, ell_h)),
            R.SpecCase("padded_multi_tile",
                       (d, light_from, heavy_from, ell_l, ell_h),
                       {"block_rows": R.SMALL_BLOCK_ROWS}),
            R.SpecCase("sliced", (d, light_from, heavy_from, sl_l, sl_h)),
        )

    def cases_relax_gated():
        ell = R.fixture_ell()
        sl = R.fixture_sliced(side="in")
        d = R.fixture_rows((b, n), seed=80)
        settle = R.fixture_status((b, n), seed=81) == 1
        # mix of active bounds and +inf (full-solve) lanes
        bound = R.fixture_rows((b,), seed=82, inf_frac=0.4)
        return (
            R.SpecCase("padded", (d, settle, bound, ell)),
            R.SpecCase("padded_multi_tile", (d, settle, bound, ell),
                       {"block_rows": R.SMALL_BLOCK_ROWS}),
            R.SpecCase("sliced", (d, settle, bound, sl)),
        )

    def cases_in_scan_gated():
        ell = R.fixture_ell()
        sl = R.fixture_sliced(side="in")
        d = R.fixture_rows((b, n), seed=83)
        settle = R.fixture_status((b, n), seed=84) == 1
        bound = R.fixture_rows((b,), seed=85, inf_frac=0.4)
        gp = _gate_parts(86)
        return (
            R.SpecCase("fused", (d, settle, bound, gp, ell)),
            R.SpecCase("split", (d, settle, bound, gp, ell),
                       {"block_rows": R.SMALL_BLOCK_ROWS}),
            R.SpecCase("sliced", (d, settle, bound, gp, sl)),
        )

    def cases_out_scan():
        ell = R.fixture_ell()
        sl = R.fixture_sliced(side="out")
        gates = R.fixture_rows((k, b, n), seed=60)
        dga = R.fixture_rows((b, n), seed=61)
        dgb = R.fixture_rows((b, n), seed=62)
        return (
            R.SpecCase("independent", (gates, None, ell)),
            R.SpecCase("dep_fused", (gates, (dga, dgb, 0), ell)),
            R.SpecCase("dep_split", (gates, (dga, dgb, 1), ell),
                       {"block_rows": R.SMALL_BLOCK_ROWS}),
            R.SpecCase("dep_sliced", (gates, (dga, dgb, 0), sl)),
        )

    for name, fn, cases, extra in (
        ("relax_settled", relax_settled, cases_relax_settled, {}),
        ("static_thresholds", static_thresholds, cases_static_thresholds,
         thr),
        ("relax_settled_batch", relax_settled_batch,
         cases_relax_settled_batch, {}),
        ("relax_settled_batch_sliced", relax_settled_batch_sliced,
         cases_relax_settled_sliced, {}),
        ("gather_min_batch_sliced", gather_min_batch_sliced,
         cases_gather_sliced, {}),
        ("static_thresholds_batch", static_thresholds_batch,
         cases_static_thresholds_batch, thr),
        ("crit_thresholds_batch", crit_thresholds_batch,
         cases_crit_thresholds, thr),
        ("key_min_batch", key_min_batch, cases_key_min, {}),
        ("key_min_batch_any", key_min_batch_any, cases_key_min_any, {}),
        ("delta_relax_batch", delta_relax_batch, cases_delta_relax, {}),
        ("relax_settled_gated_batch", relax_settled_gated_batch,
         cases_relax_gated, {}),
        ("in_scan_relax_keys_gated_batch", in_scan_relax_keys_gated_batch,
         cases_in_scan_gated, {"resident_outputs": (0, 1)}),
        ("in_scan_relax_keys_batch", in_scan_relax_keys_batch,
         cases_in_scan, {"resident_outputs": (0, 1)}),
        ("out_scan_keys_batch", out_scan_keys_batch, cases_out_scan,
         {"resident_outputs": (0,)}),
    ):
        if fn not in (relax_settled, static_thresholds,
                      static_thresholds_batch):
            # the contracts audit the kernel path, which callers opt into
            fn = partial(fn, use_pallas=True)
        reg.register(R.KernelContract(
            name=name, module=__name__, wrapper=fn, make_cases=cases,
            **extra,
        ))
