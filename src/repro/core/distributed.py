"""Distributed phased SSSP: shard_map vertex partition over the device mesh.

The TPU analogue of the paper's shared-memory parallelisation (Sec. 5):

  paper (p threads)                      | here (P devices)
  ---------------------------------------+--------------------------------
  static vertex ownership v/p == i       | block vertex partition over mesh
  per-thread priority queue -> local min | local masked min over d_loc
  reduction over thread minima           | lax.pmin (scalar collective)
  owner-buffered remote relaxations      | min-reduce-scatter of candidate
                                         |   distance vectors (one collective
                                         |   round per phase)
  busy-wait barrier per phase            | SPMD lockstep (implicit)

Two exchange schedules are implemented (the §Perf hillclimb compares them):
  * ``allreduce``      — ``lax.pmin`` over the full (n,) candidate vector;
                         every device then slices its block. Simple; moves
                         ~2x the bytes (ring all-reduce) and materialises n
                         floats per device.
  * ``reduce_scatter`` — ``all_to_all`` of the (P, n_loc) candidate blocks +
                         local min: each device receives only contributions
                         for vertices it owns ((P-1)/P x n_loc floats in,
                         the bandwidth-optimal schedule).

The phase loop runs *inside* shard_map, so one phase = one fused XLA step
with exactly one vector collective + a few small ``(B,)`` reductions — this
is the program whose HLO the multi-pod dry-run lowers for the 256/512-chip
meshes.

Two generations of the engine live here:

  * the **legacy single-query program** (:func:`shard_graph` +
    :func:`make_distributed_sssp`): one source baked into the sharded
    state, one monolithic while_loop per call. Kept as the bit-exactness
    reference for the stepper and as the dry-run lowering target.
  * the **resumable sharded batch stepper** (:class:`ShardedBatchState` +
    :func:`shard_graph_batch` / :func:`init_sharded_batch_state` /
    :func:`step_sharded_batch` / :func:`reset_sharded_lanes` /
    :func:`harvest_sharded`): the distributed twin of the static engine's
    stepper API (``repro.core.static_engine``, DESIGN.md Sec. 7). B query
    lanes share one mesh-sharded graph; every per-phase collective is a
    ``(B,)``- or ``(B, n_loc)``-shaped vector amortised across all lanes,
    and the loop can be chunked / early-exited / lane-reset between chunks
    exactly like the single-device stepper — which is what lets
    ``repro.serving.ContinuousBatcher`` serve continuous traffic over a
    sharded graph through the same adapter surface.

:func:`run_distributed` is a thin B=1 wrapper over the stepper (bit-exact
against the legacy program on both exchange schedules, pinned by
``tests/test_distributed_batch.py``).
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import criteria as C
from repro.core.graph import Graph, transpose
from repro.core.static_engine import (
    DEFAULT_CRITERION,
    EMPTY_LANE,
    KEEP_LANE,
    BatchedResult,
    _fresh_rows,
    _limb_add,
    combine_limbs,
    validate_sources,
)

INF = jnp.inf


@partial(
    jax.tree_util.register_dataclass,
    data_fields=["src_local", "dst", "w", "d_init", "status_init", "in_min", "out_min"],
    meta_fields=["n", "n_pad", "n_loc", "num_shards"],
)
@dataclasses.dataclass(frozen=True)
class ShardedGraph:
    """Vertex-blocked graph: shard s owns vertices [s*n_loc, (s+1)*n_loc)."""

    n: int
    n_pad: int
    n_loc: int
    num_shards: int
    src_local: jax.Array  # (P, E_loc) int32, local (in-block) source index
    dst: jax.Array  # (P, E_loc) int32, global destination
    w: jax.Array  # (P, E_loc) f32, +inf padding
    d_init: jax.Array  # (n_pad,) f32
    status_init: jax.Array  # (n_pad,) int32
    in_min: jax.Array  # (n_pad,) f32
    out_min: jax.Array  # (n_pad,) f32


def _partition_edges(g: Graph, num_shards: int, pad_multiple: int):
    """Block-partition vertices; group out-edges by owning shard (numpy).

    Returns ``(n_loc, n_pad, src_l, dst_l, w_l, out_deg)`` where the edge
    arrays are ``(num_shards, e_loc)`` with local (in-block) source ids,
    global destinations, and +inf-padded weights, and ``out_deg`` is the
    ``(n_pad,)`` int32 real-out-degree vector (0 on padding vertices).
    """
    n = g.n
    n_loc = -(-n // num_shards)
    n_loc = -(-n_loc // pad_multiple) * pad_multiple
    n_pad = n_loc * num_shards
    src = np.asarray(g.src)
    dst = np.asarray(g.dst)
    w = np.asarray(g.w)
    real = np.isfinite(w)
    src, dst, w = src[real], dst[real], w[real]
    out_deg = np.bincount(src, minlength=n_pad).astype(np.int32)
    blk = src // n_loc
    counts = np.bincount(blk, minlength=num_shards)
    e_loc = max(int(counts.max()) if counts.size else 1, 1)
    e_loc = -(-e_loc // pad_multiple) * pad_multiple
    src_l = np.zeros((num_shards, e_loc), np.int32)
    dst_l = np.zeros((num_shards, e_loc), np.int32)
    w_l = np.full((num_shards, e_loc), np.inf, np.float32)
    order = np.argsort(blk, kind="stable")
    src, dst, w, blk = src[order], dst[order], w[order], blk[order]
    slot = np.arange(len(src)) - np.searchsorted(blk, blk, side="left")
    src_l[blk, slot] = src - blk * n_loc
    dst_l[blk, slot] = dst
    w_l[blk, slot] = w
    return n_loc, n_pad, src_l, dst_l, w_l, out_deg


def _pad_min_vec(vec, n_pad: int) -> jnp.ndarray:
    v = np.asarray(vec)
    return jnp.asarray(
        np.concatenate([v, np.full(n_pad - v.shape[0], np.inf, np.float32)])
    )


def shard_graph(g: Graph, num_shards: int, source: int = 0,
                pad_multiple: int = 8) -> ShardedGraph:
    """Shard the graph and bake in single-query init state (legacy program).

    ``source`` must be a real vertex id in ``[0, n)``: numpy wrap-around
    indexing would otherwise seed a *different* vertex for a negative id
    (silently solving the wrong query), and a source in the padding range
    ``[n, n_pad)`` would seed an unreachable padding vertex (silently
    all-inf distances).
    """
    if not 0 <= int(source) < g.n:
        raise ValueError(f"source must be in [0, {g.n}); got {source}")
    n = g.n
    n_loc, n_pad, src_l, dst_l, w_l, _ = _partition_edges(g, num_shards, pad_multiple)
    d0 = np.full(n_pad, np.inf, np.float32)
    d0[source] = 0.0
    st0 = np.zeros(n_pad, np.int32)
    st0[source] = 1
    return ShardedGraph(
        n=n, n_pad=n_pad, n_loc=n_loc, num_shards=num_shards,
        src_local=jnp.asarray(src_l), dst=jnp.asarray(dst_l), w=jnp.asarray(w_l),
        d_init=jnp.asarray(d0), status_init=jnp.asarray(st0),
        in_min=_pad_min_vec(g.in_min_static, n_pad),
        out_min=_pad_min_vec(g.out_min_static, n_pad),
    )


def _exchange_min(contrib, axes, n_loc, schedule):
    """Combine per-device candidate vectors; return this device's block."""
    if schedule == "allreduce":
        full = jax.lax.pmin(contrib, axes)
        idx = jax.lax.axis_index(axes)
        return jax.lax.dynamic_slice(full, (idx * n_loc,), (n_loc,))
    # reduce_scatter(min) built from all_to_all + local min
    num = contrib.shape[0] // n_loc
    blocks = contrib.reshape(num, n_loc)
    # Row j of `blocks` is our contribution to shard j; after all_to_all row j
    # holds shard j's contribution to OUR block.
    recv = jax.lax.all_to_all(blocks, axes, split_axis=0, concat_axis=0, tiled=False)
    return jnp.min(recv, axis=0)


def make_distributed_sssp(mesh: Mesh, axes, *, schedule: str = "reduce_scatter",
                          max_phases: int = 0):
    """Build the jitted SPMD phased-SSSP program for `mesh`.

    `axes` is the mesh-axis name (or tuple of names) the vertex dimension is
    sharded over; the criterion is INSTATIC|OUTSTATIC (the paper's parallel
    implementation). Returns fn(sharded_graph) -> (dist (n_pad,), phases).
    """
    if isinstance(axes, str):
        axes = (axes,)
    vspec = P(axes)
    espec = P(axes, None)

    def spmd(d, status, in_min, out_min, src_l, dst_g, w, cap):
        # shapes inside shard_map: d/status/... (n_loc,), edges (1, E_loc)
        src_l = src_l[0]
        dst_g = dst_g[0]
        w = w[0]
        n_loc = d.shape[0]
        n_pad = n_loc * int(np.prod([mesh.shape[a] for a in axes]))

        def thresholds(d, status):
            fringe = status == 1
            min_fd = jax.lax.pmin(jnp.min(jnp.where(fringe, d, INF)), axes)
            l_out = jax.lax.pmin(jnp.min(jnp.where(fringe, d + out_min, INF)), axes)
            return min_fd, l_out, fringe

        def any_fringe(status):
            return jax.lax.psum(jnp.sum(status == 1), axes) > 0

        def body(state):
            d, status, phases, _ = state
            min_fd, l_out, fringe = thresholds(d, status)
            settle = fringe & (
                (d - in_min <= min_fd) | (d <= l_out) | (d <= min_fd)
            )
            cand = jnp.where(settle[src_l], d[src_l] + w, INF)
            contrib = jax.ops.segment_min(cand, dst_g, num_segments=n_pad)
            upd = _exchange_min(contrib, axes, n_loc, schedule)
            new_d = jnp.minimum(d, upd)
            new_status = jnp.where(
                settle, 2, jnp.where((status == 0) & (upd < INF), 1, status)
            )
            return new_d, new_status, phases + 1, any_fringe(new_status)

        def cond(state):
            *_, phases, go = state
            return go & (phases < cap)

        state0 = (d, status, jnp.int32(0), any_fringe(status))
        d, status, phases, _ = jax.lax.while_loop(cond, body, state0)
        # the loop condition is a psum, so every device ran the same trips
        return d, phases

    # replication checking stays off: the bodies return per-shard blocks
    mapped = jax.shard_map(
        spmd,
        mesh=mesh,
        in_specs=(vspec, vspec, vspec, vspec, espec, espec, espec, P()),
        out_specs=(vspec, P()),
        check_vma=False,
    )

    @jax.jit
    def run(sg: ShardedGraph, cap):
        return mapped(
            sg.d_init, sg.status_init, sg.in_min, sg.out_min,
            sg.src_local, sg.dst, sg.w, cap,
        )

    return run


# ---------------------------------------------------------------------------
# Resumable sharded batch stepper (DESIGN.md Sec. 7)
# ---------------------------------------------------------------------------


@partial(
    jax.tree_util.register_dataclass,
    data_fields=["src_local", "dst", "w", "tsrc_local", "tdst", "tw",
                 "in_min", "out_min", "out_deg"],
    meta_fields=["n", "n_pad", "n_loc", "num_shards"],
)
@dataclasses.dataclass(frozen=True)
class ShardedBatchGraph:
    """Query-independent sharded graph for the batch stepper.

    Unlike the legacy :class:`ShardedGraph` it bakes in *no* source state —
    queries live in :class:`ShardedBatchState` lanes, so one sharded graph
    serves arbitrarily many batches/resets (the serving workload).

    Carries up to *two* edge partitions: the forward one (edges grouped by
    the owner of their source — the relax push and the IN-family dynamic
    keys flow along it) and optionally the transpose one (edges grouped by
    the owner of their *destination* — the OUT-family dynamic keys reduce
    "over my out-edges gated by the target's status", so the gate is
    evaluated at the target's owner and the contribution exchanged back to
    the source's owner). The transpose arrays double the edge memory, so
    front-ends that know the criterion up front
    (``run_sharded_batch``/``ShardedBackend``) only build them when the
    plan carries dynamic OUT keys; plans without such keys never ship them
    into the step program either way.
    """

    n: int
    n_pad: int
    n_loc: int
    num_shards: int
    src_local: jax.Array  # (P, E_loc) int32, local (in-block) source index
    dst: jax.Array  # (P, E_loc) int32, global destination
    w: jax.Array  # (P, E_loc) f32, +inf padding
    tsrc_local: jax.Array | None  # (P, E_loc_t) int32, local index of the
    #   edge's DST (None when sharded with with_transpose=False)
    tdst: jax.Array | None  # (P, E_loc_t) int32, global id of the edge's SRC
    tw: jax.Array | None  # (P, E_loc_t) f32, +inf padding
    in_min: jax.Array  # (n_pad,) f32, +inf on padding vertices
    out_min: jax.Array  # (n_pad,) f32, +inf on padding vertices
    out_deg: jax.Array  # (n_pad,) int32 real out-degrees (0 on padding)


@partial(
    jax.tree_util.register_dataclass,
    data_fields=["dist", "status", "trips", "phases", "sum_fringe",
                 "sum_fringe_hi", "relax_edges", "relax_edges_hi",
                 "dist_true", "settled_trace"],
    meta_fields=["n", "criterion"],
)
@dataclasses.dataclass(frozen=True)
class ShardedBatchState:
    """Resumable state of a sharded batched phase loop (one row per lane).

    The mesh twin of :class:`~repro.core.static_engine.BatchState`: a pure
    fixed-shape pytree whose ``(B, n_pad)`` vertex arrays are block-sharded
    over the mesh's vertex axis inside ``step_sharded_batch`` (each device
    holds ``(B, n_loc)``). Same counter semantics as the static stepper, so
    :func:`harvest_sharded` yields a drop-in ``BatchedResult``. The
    criterion is static metadata selecting the compiled SPMD step program;
    dynamic keys are recomputed shard-locally every phase and never carried
    (they are pure functions of status).
    """

    n: int  # true vertex count; columns in [n, n_pad) are padding
    dist: jax.Array  # (B, n_pad) f32 tentative distances
    status: jax.Array  # (B, n_pad) int32 (0=U, 1=F, 2=S)
    trips: jax.Array  # scalar int32 loop trips since init (wrap-safe deltas)
    phases: jax.Array  # (B,) int32 phases each lane's current query was live
    sum_fringe: jax.Array  # (B,) uint32 per-lane sum over live phases of |F|
    #   — low limb of a two-limb counter (see BatchState.sum_fringe)
    sum_fringe_hi: jax.Array  # (B,) int32 high limb
    relax_edges: jax.Array  # (B,) uint32 per-lane out-edges relaxed (low limb)
    relax_edges_hi: jax.Array  # (B,) int32 high limb
    dist_true: jax.Array | None  # (B, n_pad) f32 per-lane true distances
    #   (+inf on padding columns), only when the plan includes 'oracle'
    settled_trace: jax.Array  # (B, trace_len) int32 ring of per-phase settle
    #   counts, same semantics as BatchState.settled_trace (phase p of a
    #   lane's current query lands in slot p % trace_len; 1 = cheap off).
    #   Lane-replicated across the mesh: the settle count is already a psum,
    #   so every device writes the identical ring.
    criterion: str  # canonical criterion string; static: selects the plan

    @property
    def num_lanes(self) -> int:
        return self.dist.shape[0]

    @property
    def n_pad(self) -> int:
        return self.dist.shape[1]

    @property
    def plan(self) -> C.CritPlan:
        return C.plan_for(self.criterion)


def shard_graph_batch(g: Graph, num_shards: int, pad_multiple: int = 8,
                      with_transpose: bool = True) -> ShardedBatchGraph:
    """Block-partition vertices for the batch stepper (no baked-in source).

    ``with_transpose`` additionally builds the transpose edge partition that
    feeds the dynamic OUT-family criterion keys (see
    :class:`ShardedBatchGraph`). It defaults on so a hand-sharded graph
    accepts every criterion; front-ends that know the criterion pass
    ``plan.needs_out_adjacency`` to skip the second partition (and its
    doubled edge memory) for plans that never read it.
    """
    n_loc, n_pad, src_l, dst_l, w_l, out_deg = _partition_edges(
        g, num_shards, pad_multiple
    )
    tsrc_l = tdst_l = tw_l = None
    if with_transpose:
        _, _, tsrc_l, tdst_l, tw_l, _ = _partition_edges(
            transpose(g), num_shards, pad_multiple
        )
        tsrc_l, tdst_l, tw_l = map(jnp.asarray, (tsrc_l, tdst_l, tw_l))
    return ShardedBatchGraph(
        n=g.n, n_pad=n_pad, n_loc=n_loc, num_shards=num_shards,
        src_local=jnp.asarray(src_l), dst=jnp.asarray(dst_l), w=jnp.asarray(w_l),
        tsrc_local=tsrc_l, tdst=tdst_l, tw=tw_l,
        in_min=_pad_min_vec(g.in_min_static, n_pad),
        out_min=_pad_min_vec(g.out_min_static, n_pad),
        out_deg=jnp.asarray(out_deg),
    )


def place_sharded_graph(sg: ShardedBatchGraph, mesh: Mesh,
                        axes) -> ShardedBatchGraph:
    """Put each shard's edge rows and vertex block on its own device.

    A graph left on the default device would be re-scattered to the mesh on
    every step call, and would sit whole on the first device meanwhile.
    """
    axes = (axes,) if isinstance(axes, str) else tuple(axes)

    def put(x, spec):
        return None if x is None else jax.device_put(
            x, NamedSharding(mesh, spec))

    edge, vert = P(axes, None), P(axes)
    return dataclasses.replace(
        sg,
        src_local=put(sg.src_local, edge), dst=put(sg.dst, edge),
        w=put(sg.w, edge), tsrc_local=put(sg.tsrc_local, edge),
        tdst=put(sg.tdst, edge), tw=put(sg.tw, edge),
        in_min=put(sg.in_min, vert), out_min=put(sg.out_min, vert),
        out_deg=put(sg.out_deg, vert),
    )


def _pad_dist_true(dist_true, plan: C.CritPlan, b: int, n: int, n_pad: int):
    """(B, n_pad) f32 dist_true (or None): true rows, +inf padding columns."""
    if not plan.needs_oracle:
        return None
    if dist_true is None:
        raise ValueError(
            f"criterion {plan.criterion!r} includes 'oracle': per-lane "
            f"dist_true of shape ({b}, {n}) is required"
        )
    dt = np.asarray(dist_true, np.float32)
    if dt.shape != (b, n):
        raise ValueError(f"dist_true must have shape ({b}, {n}); got {dt.shape}")
    out = np.full((b, n_pad), np.inf, np.float32)
    out[:, :n] = dt
    return jnp.asarray(out)


def init_sharded_batch_state(sg: ShardedBatchGraph, sources,
                             criterion: str = DEFAULT_CRITERION,
                             dist_true=None,
                             trace_len: int = 1) -> ShardedBatchState:
    """Fresh ``(B, n_pad)`` stepper state for B lanes over one sharded graph.

    ``sources[i] == -1`` (:data:`~repro.core.static_engine.EMPTY_LANE`)
    leaves lane ``i`` empty. Sources are validated against the *true* vertex
    count ``sg.n``, never ``n_pad``: an id in the padding range would seed a
    fringe on a vertex with no edges and silently answer all-inf.

    ``criterion`` is any string ``run_phased`` accepts; a plan containing
    ``'oracle'`` requires per-lane ``dist_true`` rows ``(B, n)``.
    ``trace_len`` sizes the per-lane settled-per-phase ring (same semantics
    as the static stepper's; the default 1 keeps it off).
    """
    plan = C.plan_for(criterion)
    src_np = validate_sources(
        sources, sg.n, EMPTY_LANE, f"in [0, {sg.n}) or -1 for an empty lane"
    )
    if trace_len < 1:
        raise ValueError(f"trace_len must be >= 1; got {trace_len}")
    d0, st0 = _fresh_rows(jnp.asarray(src_np), sg.n_pad)
    b = src_np.shape[0]
    # one distinct buffer per counter: a shared zeros array would make the
    # state pytree alias itself, and donating it then fails ("donate the
    # same buffer twice") on the first donated step/reset
    return ShardedBatchState(
        n=sg.n, dist=d0, status=st0, trips=jnp.int32(0),
        phases=jnp.zeros((b,), jnp.int32),
        sum_fringe=jnp.zeros((b,), jnp.uint32),
        sum_fringe_hi=jnp.zeros((b,), jnp.int32),
        relax_edges=jnp.zeros((b,), jnp.uint32),
        relax_edges_hi=jnp.zeros((b,), jnp.int32),
        dist_true=_pad_dist_true(dist_true, plan, b, sg.n, sg.n_pad),
        settled_trace=jnp.zeros((b, int(trace_len)), jnp.int32),
        criterion=plan.criterion,
    )


def _exchange_min_batch(contrib, axes, n_loc, schedule):
    """Batched :func:`_exchange_min`: combine (B, n_pad) candidate vectors
    across devices, return this device's (B, n_loc) block. One vector
    collective per phase serves all B lanes."""
    if schedule == "allreduce":
        full = jax.lax.pmin(contrib, axes)
        idx = jax.lax.axis_index(axes)
        return jax.lax.dynamic_slice_in_dim(full, idx * n_loc, n_loc, axis=1)
    num = contrib.shape[1] // n_loc
    blocks = contrib.reshape(contrib.shape[0], num, n_loc)
    # Slice j of axis 1 is our contribution to shard j; after all_to_all it
    # holds shard j's contribution to OUR block (exactly the legacy schedule,
    # with the lane axis riding along in one message).
    recv = jax.lax.all_to_all(blocks, axes, split_axis=1, concat_axis=1,
                              tiled=False)
    return jnp.min(recv, axis=1)


_SHARDED_STEP_CACHE: dict = {}


def _get_sharded_step(mesh: Mesh, axes, schedule: str,
                      stop_on_lane_finish: bool, donate: bool,
                      criterion: str):
    """Build (and memoise) the jitted SPMD chunked-step program.

    One compiled program per (mesh, axes, schedule, early-exit flag,
    donation, criterion) — ``k_phases`` and the graph/state arrays are
    traced operands, so chunk sizes and repeated calls never recompile.

    Criterion-plan lowering on the mesh (DESIGN.md Sec. 8/9): each *dynamic*
    key is recomputed shard-locally every phase — the IN-family keys ride
    the forward edge partition (the gate lives at the source's owner, the
    key lands at the destination's owner, exactly the relax dataflow), the
    OUT-family keys ride the transpose partition (gate at the destination's
    owner, key back at the source's owner). Same-side *independent* keys
    share ONE fused gated push + segment-min over their partition (the mesh
    twin of the single-scan phase body: the local edge arrays are read once
    per side per phase, not once per key); the exchange stays one round per
    key, and the dependent ``out_full`` still needs its own pass after its
    ``out_dyn`` input is exchanged. The fused threshold pmin widens from
    ``(2, B)`` to ``(L, B)`` where L = 1 + |OUT terms|.
    """
    key = (mesh, tuple(axes), schedule, bool(stop_on_lane_finish),
           bool(donate), criterion)
    hit = _SHARDED_STEP_CACHE.get(key)
    if hit is not None:
        return hit
    if schedule not in ("allreduce", "reduce_scatter"):
        raise ValueError(f"unknown exchange schedule: {schedule!r}")
    plan = C.plan_for(criterion)
    needs_t = plan.needs_out_adjacency
    needs_o = plan.needs_oracle
    axes = tuple(axes)
    bspec = P(None, axes)  # (B, n_pad) lane-replicated, vertex-sharded
    vspec = P(axes)
    espec = P(axes, None)
    rspec = P()
    num_shards = int(np.prod([mesh.shape[a] for a in axes]))

    def spmd(d, status, phases, sum_f, sum_f_hi, redges, redges_hi,
             trips, trace, in_min, out_min, out_deg, src_l, dst_g, w,
             tsrc_l, tdst_g, tw, dist_true, k):
        # shapes inside shard_map: d/status/dist_true (B, n_loc); in_min/
        # out_min/out_deg (n_loc,); edge partitions (1, E_loc); counters and
        # the (B, trace_len) trace ring replicated. tsrc_l/tdst_g/tw and
        # dist_true are zero-size dummies unless the plan needs them (static
        # shapes keep one spec list).
        src_l, dst_g, w = src_l[0], dst_g[0], w[0]
        tsrc_l, tdst_g, tw = tsrc_l[0], tdst_g[0], tw[0]
        n_loc = d.shape[1]
        n_pad = n_loc * num_shards
        trace_len = trace.shape[1]
        rows_b = jnp.arange(d.shape[0])
        start = trips

        def live_vec(status):
            return jax.lax.psum(
                jnp.sum(status == 1, axis=1, dtype=jnp.int32), axes
            ) > 0

        live0 = live_vec(status)  # (B,) lanes live at chunk entry

        def keys_exchange(gates, from_l, to_g, ws):
            """Fused same-side key rounds: ONE gated push + local segmin
            over the edge partition for all K stacked gates, then one
            exchange round per key (the exchange schedule is unchanged —
            only the local scan fuses).

            Padding edges carry w = +inf (and gates are never -inf), so
            they contribute a neutral +inf — the same masking convention as
            the relax push and the ELL sentinel slots.
            """
            cand = gates[:, :, from_l] + ws[None, None]  # (K, B, E_loc)
            contrib = jax.vmap(jax.vmap(
                lambda c: jax.ops.segment_min(c, to_g, num_segments=n_pad)
            ))(cand)
            return [
                _exchange_min_batch(contrib[i], axes, n_loc, schedule)
                for i in range(gates.shape[0])
            ]

        def dyn_keys(status):
            keys = {}
            by_name = {s.name: s for s in plan.keys}
            # independent keys, grouped by side: one local scan per side
            for names, (from_l, to_g, ws) in (
                (plan.in_scan_keys, (src_l, dst_g, w)),
                (plan.out_scan_keys, (tsrc_l, tdst_g, tw)),
            ):
                if not names:
                    continue
                gates = jnp.stack([
                    C.key_gate(by_name[nm], status, in_min, out_min, keys)
                    for nm in names
                ])
                for nm, key in zip(names, keys_exchange(gates, from_l, to_g, ws)):
                    keys[nm] = key
            if plan.out_scan_dep is not None:
                spec = by_name[plan.out_scan_dep]
                gate = C.key_gate(spec, status, in_min, out_min, keys)
                keys[spec.name] = keys_exchange(
                    gate[None], tsrc_l, tdst_g, tw
                )[0]
            return keys

        def body(carry):
            (d, status, phases, sum_f, sum_f_hi, redges, redges_hi,
             trips, trace, _) = carry
            fringe = status == 1
            keys = dyn_keys(status)
            # one fused (L, B) pmin: min fringe distance + the plan's OUT lanes
            lanes = [jnp.min(jnp.where(fringe, d, INF), axis=1)]
            for t in plan.out_terms:
                kk = out_min[None] if t == "static" else keys[t]
                lanes.append(jnp.min(jnp.where(fringe, d + kk, INF), axis=1))
            mins = jax.lax.pmin(jnp.stack(lanes), axes)
            settle = C.plan_union_mask(
                plan, d, fringe, mins, keys, in_min, dist_true
            )
            if plan.needs_fallback:
                # bare-oracle guard needs a global any(): one extra (B,) psum
                any_mask = jax.lax.psum(
                    jnp.sum(settle, axis=1, dtype=jnp.int32), axes
                ) > 0
                dijk = fringe & (d <= mins[0][:, None])
                settle = jnp.where(any_mask[:, None], settle, dijk)
            cand = jnp.where(settle[:, src_l], d[:, src_l] + w[None], INF)
            contrib = jax.vmap(
                lambda c: jax.ops.segment_min(c, dst_g, num_segments=n_pad)
            )(cand)
            upd = _exchange_min_batch(contrib, axes, n_loc, schedule)
            new_d = jnp.minimum(d, upd)
            new_status = jnp.where(
                settle, 2, jnp.where((status == 0) & (upd < INF), 1, status)
            )
            # one fused (4, B) psum: |F| this phase, relaxed out-edges, the
            # post-update live-lane counts the loop condition needs, and the
            # per-lane settle count the trace ring records
            counts = jax.lax.psum(
                jnp.stack([
                    jnp.sum(fringe, axis=1, dtype=jnp.int32),
                    jnp.sum(jnp.where(settle, out_deg[None], 0),
                            axis=1, dtype=jnp.int32),
                    jnp.sum(new_status == 1, axis=1, dtype=jnp.int32),
                    jnp.sum(settle, axis=1, dtype=jnp.int32),
                ]),
                axes,
            )
            n_f, d_redges, live_cnt, n_settled = (
                counts[0], counts[1], counts[2], counts[3]
            )
            new_live = live_cnt > 0
            go = jnp.any(new_live) & (trips + 1 - start < k)
            if stop_on_lane_finish:
                # end the chunk as soon as any entry-live lane terminates,
                # so the scheduler can refill it instead of idling it out
                go &= jnp.all(new_live == live0)
            alive = (n_f > 0).astype(jnp.int32)  # finished lanes stop counting
            # ring write, same semantics as BatchState.settled_trace: phase p
            # lands in slot p % trace_len; dead lanes must not write (their
            # stuck slot may hold a wrapped live entry). All inputs are
            # psums / replicated, so every device writes the same ring.
            idx = phases % trace_len
            new_trace = trace.at[rows_b, idx].set(
                jnp.where(n_f > 0, n_settled, trace[rows_b, idx])
            )
            # the (4, B) psum stays int32 (per-phase counts are bounded);
            # only the running totals carry into two uint32/int32 limbs
            sf_lo, sf_hi = _limb_add(sum_f, sum_f_hi, n_f.astype(jnp.uint32))
            re_lo, re_hi = _limb_add(
                redges, redges_hi, d_redges.astype(jnp.uint32)
            )
            return (new_d, new_status, phases + alive, sf_lo, sf_hi,
                    re_lo, re_hi, trips + 1, new_trace, go)

        def cond(carry):
            return carry[-1]

        go0 = jnp.any(live0) & (k > 0)
        carry = (d, status, phases, sum_f, sum_f_hi, redges, redges_hi,
                 trips, trace, go0)
        (d, status, phases, sum_f, sum_f_hi, redges, redges_hi,
         trips, trace, _) = jax.lax.while_loop(cond, body, carry)
        return d, status, phases, sum_f, sum_f_hi, redges, redges_hi, trips, trace

    # replication checking stays off: the bodies return per-shard blocks
    mapped = jax.shard_map(
        spmd,
        mesh=mesh,
        in_specs=(bspec, bspec, rspec, rspec, rspec, rspec, rspec, rspec,
                  rspec, vspec, vspec, vspec, espec, espec, espec,
                  espec, espec, espec, bspec, rspec),
        out_specs=(bspec, bspec, rspec, rspec, rspec, rspec, rspec,
                   rspec, rspec),
    )

    def step(state: ShardedBatchState, src_l, dst_g, w, tsrc_l, tdst_g, tw,
             in_min, out_min, out_deg, k):
        b = state.dist.shape[0]
        if not needs_t:
            # zero-size transpose dummies: nothing crosses the wire, the
            # traced body never indexes them (plan is static)
            p = src_l.shape[0]
            tsrc_l = jnp.zeros((p, 0), jnp.int32)
            tdst_g = jnp.zeros((p, 0), jnp.int32)
            tw = jnp.zeros((p, 0), jnp.float32)
        dist_true = state.dist_true
        if not needs_o:
            # (B, 0) dummy: sharded to (B, 0) blocks, never read by the body
            dist_true = jnp.zeros((b, 0), jnp.float32)
        (d, status, phases, sum_f, sum_f_hi, redges, redges_hi,
         trips, trace) = mapped(
            state.dist, state.status, state.phases, state.sum_fringe,
            state.sum_fringe_hi, state.relax_edges, state.relax_edges_hi,
            state.trips, state.settled_trace,
            in_min, out_min, out_deg, src_l, dst_g, w,
            tsrc_l, tdst_g, tw, dist_true, k,
        )
        return dataclasses.replace(
            state, dist=d, status=status, phases=phases, sum_fringe=sum_f,
            sum_fringe_hi=sum_f_hi, relax_edges=redges,
            relax_edges_hi=redges_hi, trips=trips, settled_trace=trace,
        )

    fn = jax.jit(step, donate_argnums=(0,) if donate else ())
    _SHARDED_STEP_CACHE[key] = fn
    return fn


def step_sharded_batch(
    sg: ShardedBatchGraph,
    state: ShardedBatchState,
    mesh: Mesh,
    axes,
    k_phases: int,
    schedule: str = "reduce_scatter",
    stop_on_lane_finish: bool = False,
    donate: bool = False,
) -> ShardedBatchState:
    """Advance the sharded phase loop by up to ``k_phases`` more trips.

    Same contract as :func:`~repro.core.static_engine.step_batch`: returns
    after ``k_phases`` trips, earlier when every lane's fringe is empty, or
    — with ``stop_on_lane_finish`` — as soon as any lane that was live on
    entry terminates. ``k_phases`` is a traced operand (no recompiles across
    chunk sizes); one compiled program is cached per
    (mesh, axes, schedule, flags).

    ``donate=True`` donates the state's buffers for in-place update on
    accelerator backends — same aliasing caveat as the static stepper:
    results of an earlier :func:`harvest_sharded` alias them, so copy before
    donating.
    """
    if isinstance(axes, str):
        axes = (axes,)
    num = int(np.prod([mesh.shape[a] for a in axes]))
    if num != sg.num_shards:
        raise ValueError(
            f"graph was sharded for {sg.num_shards} devices but mesh axes "
            f"{axes} span {num}"
        )
    if C.plan_for(state.criterion).needs_out_adjacency and sg.tsrc_local is None:
        raise ValueError(
            f"criterion {state.criterion!r} needs dynamic OUT keys but the "
            f"graph was sharded with with_transpose=False; re-shard with "
            f"shard_graph_batch(..., with_transpose=True)"
        )
    fn = _get_sharded_step(mesh, axes, schedule, stop_on_lane_finish, donate,
                           state.criterion)
    return fn(state, sg.src_local, sg.dst, sg.w,
              sg.tsrc_local, sg.tdst, sg.tw,
              sg.in_min, sg.out_min, sg.out_deg, jnp.int32(k_phases))


def _reset_sharded_impl(state: ShardedBatchState, sources,
                        new_dist_true) -> ShardedBatchState:
    touch = sources >= EMPTY_LANE  # KEEP_LANE rows pass through unchanged
    fresh_d, fresh_s = _fresh_rows(sources, state.dist.shape[1])

    def ctr(old):
        return jnp.where(touch, 0, old)

    dist_true = state.dist_true
    if dist_true is not None and new_dist_true is not None:
        dist_true = jnp.where(touch[:, None], new_dist_true, dist_true)
    return dataclasses.replace(
        state,
        dist=jnp.where(touch[:, None], fresh_d, state.dist),
        status=jnp.where(touch[:, None], fresh_s, state.status),
        phases=ctr(state.phases),
        sum_fringe=ctr(state.sum_fringe),
        sum_fringe_hi=ctr(state.sum_fringe_hi),
        relax_edges=ctr(state.relax_edges),
        relax_edges_hi=ctr(state.relax_edges_hi),
        dist_true=dist_true,
        settled_trace=jnp.where(touch[:, None], 0, state.settled_trace),
    )


_reset_sharded = jax.jit(_reset_sharded_impl)
_reset_sharded_donate = jax.jit(_reset_sharded_impl, donate_argnums=(0,))


def reset_sharded_lanes(state: ShardedBatchState, sources,
                        donate: bool = False,
                        dist_true=None) -> ShardedBatchState:
    """Re-initialise several lanes in one device call (sharded twin of
    :func:`~repro.core.static_engine.reset_lanes`).

    ``sources`` is ``(B,)``: ``-2`` keeps a lane's bits untouched, ``-1``
    parks it empty, a vertex id in ``[0, n)`` starts a fresh query there.
    Ids are validated against the true ``n`` — the padding range is invalid.
    On an oracle-plan state, refilling a lane requires fresh ``dist_true``
    rows ``(B, n)``.
    """
    src_np = validate_sources(
        sources, state.n, KEEP_LANE,
        f"in [0, {state.n}), -1 (park) or -2 (keep)",
        expect_lanes=state.num_lanes,
    )
    dt = None
    if state.dist_true is not None:
        if dist_true is None and (src_np >= 0).any():
            raise ValueError(
                "criterion includes 'oracle': refilling lanes requires "
                "dist_true rows (B, n)"
            )
        if dist_true is not None:
            dt = _pad_dist_true(dist_true, state.plan, state.num_lanes,
                                state.n, state.n_pad)
    elif dist_true is not None:
        raise ValueError(
            f"criterion {state.criterion!r} does not read dist_true"
        )
    fn = _reset_sharded_donate if donate else _reset_sharded
    return fn(state, jnp.asarray(src_np), dt)


def sharded_lanes_active(state: ShardedBatchState) -> np.ndarray:
    """(B,) bool host array: which lanes still have a non-empty fringe."""
    return np.asarray(jnp.any(state.status == 1, axis=1))


def harvest_sharded(state: ShardedBatchState) -> BatchedResult:
    """Freeze a sharded stepper state into a (padding-free) BatchedResult.

    Same trace honesty rule as the static :func:`~repro.core.static_engine.
    harvest`: a length-1 ring was never a trace (it holds only the last
    phase's count), so it maps to None rather than a fake one-slot profile.
    """
    trace = state.settled_trace if state.settled_trace.shape[1] > 1 else None
    # drop the padding columns on the host: a global slice to n columns is
    # not divisible by the mesh axes, which sharding-typed meshes refuse
    return BatchedResult(
        dist=np.asarray(state.dist)[:, : state.n],
        status=np.asarray(state.status)[:, : state.n].astype(np.int8),
        phases=state.phases,
        sum_fringe=combine_limbs(state.sum_fringe, state.sum_fringe_hi),
        relax_edges=combine_limbs(state.relax_edges, state.relax_edges_hi),
        total_phases=state.trips,
        settled_per_phase=trace,
    )


def run_sharded_batch(g: Graph, mesh: Mesh, axes, sources,
                      schedule: str = "reduce_scatter",
                      max_phases: int | None = None,
                      criterion: str = DEFAULT_CRITERION,
                      dist_true=None, trace_len: int = 1) -> BatchedResult:
    """One-shot batched distributed solve: shard, init, drain, harvest."""
    if isinstance(axes, str):
        axes = (axes,)
    num = int(np.prod([mesh.shape[a] for a in axes]))
    sg = shard_graph_batch(
        g, num, with_transpose=C.plan_for(criterion).needs_out_adjacency
    )
    state = init_sharded_batch_state(sg, sources, criterion=criterion,
                                     dist_true=dist_true, trace_len=trace_len)
    cap = int(max_phases) if max_phases is not None else g.n + 1
    state = step_sharded_batch(sg, state, mesh, axes, cap, schedule=schedule)
    return harvest_sharded(state)


def run_distributed(g: Graph, mesh: Mesh, axes, source: int = 0,
                    schedule: str = "reduce_scatter",
                    criterion: str = DEFAULT_CRITERION,
                    dist_true=None):
    """Convenience wrapper: shard, run, return (dist (n,), phases).

    Since the stepper refactor this is a thin B=1 front-end over
    :func:`step_sharded_batch`; results are bit-exact against the legacy
    single-query program (``tests/test_distributed_batch.py`` pins it).
    ``dist_true`` is the (n,) true-distance row (oracle plans only).
    """
    if not 0 <= int(source) < g.n:
        raise ValueError(f"source must be in [0, {g.n}); got {source}")
    dt = None if dist_true is None else np.asarray(dist_true, np.float32)[None]
    res = run_sharded_batch(g, mesh, axes, [int(source)], schedule=schedule,
                            criterion=criterion, dist_true=dt)
    return res.dist[0], res.phases[0]
