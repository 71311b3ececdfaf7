"""Production phased-SSSP engine executing compiled criterion plans.

Kernel-backed implementation of *any* registered criterion disjunction
(``repro.core.criteria``), lowered through a
:class:`~repro.core.criteria.CritPlan` (the default remains
``INSTATIC | OUTSTATIC`` — the criterion the paper implements in parallel).
The phase body is *single-scan*: one adjacency scan per ELL view per phase,
however many dynamic keys the plan carries (DESIGN.md Sec. 9):

  1. the fused **out-scan** (plans with out-side dynamic keys only): every
     independent out-side key gathers from one pass over the outgoing ELL;
     a dependent key (``out_full``) adds a second sweep inside the same
     launch;
  2. ``frontier_crit`` lane kernel: one pass over vertex state -> the plan's
     ``L = 1 + |OUT terms|`` fused thresholds + fringe size. In-side keys
     are read from ``BatchState.crit_keys`` — they were emitted by the
     previous phase's in-scan (see 3) and are bitwise what recomputing from
     the current status would give;
  3. settle-mask (elementwise over the plan's terms) + the fused **in-scan**
     (``ell_relax_keys``): one pass over the incoming ELL emits this phase's
     relax update *and* the next phase's in-side key mins (gated on the
     post-settle status) from the same tile loads. Plans with no in-side
     keys run the plain relax kernel.

Cost model: at most 2 adjacency scans + 1 vertex pass per phase for every
registered criterion (the all-static default keeps its 1 + 1), traded
against the phase-count reduction of the stronger criterion — this is what
makes ``in|out``'s phase-count win show up on the wall clock
(BENCH_fused.json; PR 4's composed pipeline paid 4 adjacency passes). The
plan is static jit metadata carried on the state (``BatchState.criterion``),
so each criterion compiles exactly one step program; the dynamic keys are
data, carried in ``BatchState.crit_keys``. Carried in-side keys are valid
exactly when ``BatchState.keys_valid`` says so — admission (init/reset)
invalidates them, and ``step_batch`` re-primes with one composed key pass
before entering the loop (f32 min is exact, so a re-primed key is bitwise
the carried one for undisturbed lanes).

Both ELL arguments accept the padded ``(cols, ws)`` layout or the
degree-sliced ``SlicedEll`` (``to_ell_in_sliced``) — results are
bit-identical; sliced wins on skewed (rmat-style) degree distributions
where padded rows pay the hub width (DESIGN.md Sec. 9).

This is the single-device building block that ``repro.core.distributed``
shard_maps over the production mesh. The phase body runs the ref.py XLA
forms by default — the program that compiles for TPU; ``use_pallas=True``
opts into the Pallas kernels (bit-identical math), and every
engine x criterion combination is bit-exact per row against ``run_phased``
with the same criterion string (pinned by ``tests/test_stepper_criteria.py``).

Stepper API (the resumable core every front-end shares):

  * :func:`init_batch_state` scatters B sources into fresh ``(B, n)`` state
    (``-1`` marks an empty lane: all-+inf distances, no fringe — a fixed
    point that rides along at zero phase cost);
  * :func:`step_batch` advances the jitted phase loop by *up to* ``k_phases``
    more trips (stops early when every lane's fringe is empty), returning a
    new :class:`BatchState` with identical shapes — so it can be called again;
  * :func:`reset_lane` re-initialises one lane's ``(n,)`` slice in place
    (new source or parked empty) without touching the other lanes — the
    admission primitive of ``repro.serving``;
  * :func:`harvest` freezes a state into a :class:`BatchedResult`.

``run_phased_static`` (B=1) and ``run_phased_static_batch`` (one-shot batch)
are thin wrappers over the same stepper, so all three front-ends execute the
*identical* jitted phase body — bit-exactness between them is structural,
not coincidental. Each phase the body performs the same float ops per row
regardless of what the other rows are doing, which is what lets the serving
layer admit/retire queries mid-flight while preserving per-query results
bit-for-bit (DESIGN.md Sec. 6).

Batch amortisation: one ELL adjacency load per phase serves the whole batch
(the adjacency is the dominant memory traffic, so throughput scales nearly
linearly in B until the gather saturates — see DESIGN.md Sec. 3). A finished
or empty row has an empty fringe, so its settle mask is all-false and its
state is a fixed point; per-row phase/work counters advance only while the
row is live.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import criteria as C
from repro.core import policies as P
from repro.core.graph import (
    Graph,
    out_degrees,
    to_ell_in,
    to_ell_in_sliced,
    to_ell_out,
    to_ell_out_sliced,
)
from repro.core.phased import PhasedResult

INF = jnp.inf

EMPTY_LANE = -1  # sentinel source id: lane holds no query
KEEP_LANE = -2  # sentinel source id for reset_lanes: leave the lane untouched

DEFAULT_CRITERION = "instatic|outstatic"  # the paper's parallel implementation


def _limb_add(lo, hi, inc):
    """Add a non-negative uint32 increment to a two-limb (u32, i32) counter.

    Device int64 needs jax_enable_x64 (off in prod), so cumulative work
    counters accumulate as uint32 low + int32 high limbs; the carry is
    exact as long as one increment stays below 2^32 (a single phase would
    have to relax four billion edges to break that).
    """
    new_lo = lo + inc
    return new_lo, hi + (new_lo < lo).astype(jnp.int32)


def combine_limbs(lo, hi) -> np.ndarray:
    """Host-side rebuild of a two-limb counter as int64 (syncs to host)."""
    lo64 = np.asarray(lo).astype(np.int64)
    hi64 = np.asarray(hi).astype(np.int64)
    return (hi64 << np.int64(32)) + lo64


@partial(
    jax.tree_util.register_dataclass,
    data_fields=[
        "dist", "status", "trips", "phases", "sum_fringe", "sum_fringe_hi",
        "relax_edges", "relax_edges_hi",
        "out_deg", "crit_keys", "keys_valid", "dist_true", "settled_trace",
        "fringe_trace", "relax_trace", "attr_trace", "delta", "target",
    ],
    meta_fields=["criterion"],
)
@dataclasses.dataclass(frozen=True)
class BatchState:
    """Resumable state of a batched phase loop (one row per lane).

    A pure pytree of fixed-shape device arrays: ``step_batch`` maps it to a
    new state of identical shapes, so the loop can be chunked, paused, and
    individual lanes reset between chunks without recompilation. The
    criterion rides along as *static metadata* (it keys the compiled step
    program), the criterion's dynamic per-vertex keys as *data*.
    """

    dist: jax.Array  # (B, n) f32 tentative distances
    status: jax.Array  # (B, n) int32 (0=U, 1=F, 2=S)
    trips: jax.Array  # scalar int32: loop trips since init (wraps at 2^31 in
    #   a very-long-lived server; consumers must accumulate wrap-safe deltas,
    #   as ContinuousBatcher does — int64 needs jax_enable_x64, off in prod)
    phases: jax.Array  # (B,) int32: phases each lane's current query was live
    sum_fringe: jax.Array  # (B,) uint32: per-lane sum over live phases of |F|
    #   — LOW limb of a two-limb counter (device int64 needs jax_enable_x64,
    #   off in prod); ``harvest``/``combine_limbs`` rebuild the int64 total
    sum_fringe_hi: jax.Array  # (B,) int32: high limb (carries past 2^32)
    relax_edges: jax.Array  # (B,) uint32: per-lane out-edges relaxed — low
    #   limb; a flat int32 here overflows on reachable workloads (a 2^27-edge
    #   graph wraps within ~16 dense phases), the int32 wrap the kernel
    #   auditor's counter pass exists to flag
    relax_edges_hi: jax.Array  # (B,) int32: high limb
    out_deg: jax.Array  # (n,) int32: graph out-degrees (carried for counters)
    crit_keys: jax.Array | None  # (K, B, n) f32 policy-owned carried stack,
    #   or None when the policy carries none. CriterionPolicy: the plan's
    #   dynamic keys (ordered like ``plan.keys``) — out-side slots hold the
    #   last executed phase's values (recomputed in-phase, never read
    #   stale); in-side slots hold the keys for the CURRENT status —
    #   emitted by the previous phase's fused in-scan, or re-primed by
    #   step_batch when ``keys_valid`` is False (bitwise equal either way:
    #   f32 min is exact). DeltaPolicy: slot 0 = last_processed tentative,
    #   slot 1 = removed-from-bucket flag (see repro.core.policies).
    keys_valid: jax.Array | None  # scalar bool: in-side slots of crit_keys
    #   match the current status. False after init/reset (admission touches
    #   status without scanning the adjacency); None when the plan carries
    #   no in-side dynamic keys.
    dist_true: jax.Array | None  # (B, n) f32 per-lane true distances, only
    #   when the plan includes 'oracle'; None otherwise
    settled_trace: jax.Array  # (B, trace_len) int32 ring of per-phase settle
    #   counts: phase p of a lane's current query lands in slot p % trace_len
    #   (size the ring >= expected phases for a full profile; 1 = cheap off)
    fringe_trace: jax.Array | None  # (B, trace_len) int32 ring of per-phase
    #   |F| at phase entry, or None unless init'd with telemetry=True —
    #   together with relax_trace/attr_trace these are the extended
    #   telemetry rings repro.obs.phase_telemetry decodes
    relax_trace: jax.Array | None  # (B, trace_len) int32 ring of per-phase
    #   out-edges relaxed (per-phase counts are bounded by m, so int32 is
    #   safe where the *cumulative* counter above needs two limbs)
    attr_trace: jax.Array | None  # (B, trace_len, T) int32 ring of
    #   per-criterion settle attribution: slot [., p, k] counts vertices
    #   this phase settled that criteria.attribution_terms(plan)[k] proved
    #   FIRST (first-true in canonical member order) — a partition of the
    #   settled set, so summing over k reproduces settled_trace exactly
    delta: jax.Array | None  # scalar f32 bucket width, only on DeltaPolicy
    #   states (pure data: every bucket width shares one compiled program);
    #   None on criterion-policy states
    target: jax.Array | None  # (B,) int32 per-lane target vertex for s->t
    #   queries (-1 = full solve), or None when the state was initialised
    #   without target lanes. Pytree-STRUCTURAL like the telemetry rings:
    #   target=None states keep the exact pre-target pytree (and therefore
    #   the exact compiled programs). When present, a lane's fringe is
    #   demoted the phase its target settles (early exit) and the criterion
    #   policies prune relax sources at ``tent >= dist[target]`` — so only
    #   ``dist[lane, target[lane]]`` (plus every vertex nearer than it) is
    #   guaranteed final on a target lane; the rest of the row is partial.
    criterion: str  # canonical policy spec; static: selects the compiled
    #   phase policy (criterion string -> CriterionPolicy, "delta" ->
    #   DeltaPolicy — see repro.core.policies)

    @property
    def num_lanes(self) -> int:
        return self.dist.shape[0]

    @property
    def n(self) -> int:
        return self.dist.shape[1]

    @property
    def policy(self) -> P.PhasePolicy:
        return P.policy_for(self.criterion)

    @property
    def plan(self) -> C.CritPlan:
        """The compiled criterion plan (criterion-policy states only)."""
        return C.plan_for(self.criterion)


@partial(
    jax.tree_util.register_dataclass,
    data_fields=[
        "dist", "status", "phases", "sum_fringe", "relax_edges", "total_phases",
        "settled_per_phase", "fringe_per_phase", "relax_per_phase",
        "settle_attribution", "target",
    ],
    meta_fields=[],
)
@dataclasses.dataclass(frozen=True)
class BatchedResult:
    """Result of one batched multi-source solve over a shared graph."""

    dist: jax.Array  # (B, n) f32 final distances (inf = unreachable)
    status: jax.Array  # (B, n) int8 (0=U, 1=F, 2=S)
    phases: jax.Array  # (B,) int32: phases each row was live for
    sum_fringe: jax.Array  # (B,) int64 host: per-row sum over phases of |F|
    #   (two-limb device counters combined by ``harvest``)
    relax_edges: jax.Array  # (B,) int64 host: per-row out-edges relaxed
    total_phases: jax.Array  # scalar int32: loop trips since state init —
    #   equals max over rows for a one-shot batch; cumulative (spans every
    #   query the lanes ever served) when harvested from a resumed state
    settled_per_phase: jax.Array | None = None  # (B, trace_len) int32 ring of
    #   per-phase settle counts (see BatchState.settled_trace), or None when
    #   the producing engine carries no trace (the sharded stepper)
    fringe_per_phase: jax.Array | None = None  # (B, trace_len) int32 ring of
    #   per-phase fringe sizes, only from telemetry-enabled stepper states
    relax_per_phase: jax.Array | None = None  # (B, trace_len) int32 ring of
    #   per-phase relaxed out-edges, only with telemetry
    settle_attribution: jax.Array | None = None  # (B, trace_len, T) int32
    #   per-criterion settle attribution ring (BatchState.attr_trace), only
    #   with telemetry; T indexes criteria.attribution_terms(plan)
    target: jax.Array | None = None  # (B,) int32 per-lane target vertex
    #   (-1 = full solve), only from target-enabled states: on a target
    #   lane only dist[lane, target[lane]] (and nearer vertices) is final


def validate_sources(sources, n: int, lo: int, range_desc: str,
                     expect_lanes: int | None = None) -> np.ndarray:
    """Validate a host-side source vector and return it as int32 numpy.

    The one gatekeeper every lane-initialisation front-end funnels through
    (static and sharded engines alike): rejects non-integer dtypes, empty or
    non-1-D shapes, and any id outside ``[lo, n)`` — in the *original* dtype,
    because casting first would let ids beyond int32 wrap into the valid
    range and silently answer the wrong query.
    """
    src_np = np.atleast_1d(np.asarray(sources))
    if expect_lanes is not None and src_np.shape != (expect_lanes,):
        raise ValueError(
            f"sources must have shape ({expect_lanes},); got {src_np.shape}"
        )
    if src_np.ndim != 1 or src_np.size == 0:
        raise ValueError(
            f"sources must be a non-empty (B,) vector; got shape {src_np.shape}"
        )
    if src_np.dtype.kind not in "iu":
        raise ValueError(f"sources must be integer vertex ids; got {src_np.dtype}")
    if int(src_np.min()) < lo or int(src_np.max()) >= n:
        raise ValueError(f"sources must be {range_desc}; got {src_np}")
    return src_np.astype(np.int32)


def _fresh_rows(sources, n: int):
    """(B, n) dist/status rows for fresh queries: the single source of truth
    for lane initialisation — init and both reset paths share it, which is
    what makes 'a reset lane is bitwise a fresh solve' hold by construction.
    Source -1 (or below) yields an empty all-+inf, fringe-free row."""
    b = sources.shape[0]
    rows = jnp.arange(b)
    valid = sources >= 0
    col = jnp.clip(sources, 0, n - 1)
    d = jnp.full((b, n), INF, jnp.float32).at[rows, col].set(
        jnp.where(valid, 0.0, INF)
    )
    status = jnp.zeros((b, n), jnp.int32).at[rows, col].set(
        jnp.where(valid, 1, 0)
    )
    return d, status


@partial(jax.jit, static_argnames=("criterion", "trace_len", "telemetry"))
def _init_state(g: Graph, out_deg: jax.Array, sources: jax.Array, dist_true,
                delta, targets, criterion: str, trace_len: int,
                telemetry: bool = False) -> BatchState:
    policy = P.policy_for(criterion)
    n = g.n
    b = sources.shape[0]
    d0, status0 = _fresh_rows(sources, n)
    zeros_b = jnp.zeros((b,), jnp.int32)
    zeros_b_u = jnp.zeros((b,), jnp.uint32)
    ring = jnp.zeros((b, trace_len), jnp.int32)
    n_terms = len(policy.attribution_terms())
    return BatchState(
        dist=d0,
        status=status0,
        trips=jnp.int32(0),
        phases=zeros_b,
        sum_fringe=zeros_b_u,
        sum_fringe_hi=zeros_b,
        relax_edges=zeros_b_u,
        relax_edges_hi=zeros_b,
        out_deg=out_deg,
        crit_keys=(
            policy.fresh_keys(b, n) if policy.num_key_slots() else None
        ),
        keys_valid=policy.init_keys_valid(),
        dist_true=dist_true,
        settled_trace=ring,
        fringe_trace=ring if telemetry else None,
        relax_trace=ring if telemetry else None,
        attr_trace=(
            jnp.zeros((b, trace_len, n_terms), jnp.int32) if telemetry
            else None
        ),
        delta=delta,
        target=targets,
        criterion=criterion,
    )


def _validate_targets(targets, b: int, n: int):
    """(B,) int32 target vector (or None): each entry a vertex id for an
    s->t lane or -1 for a full solve. Reuses the source gatekeeper — the
    same silent-wrong-answer hazards (wrapping ids, bad shapes) apply."""
    if targets is None:
        return None
    t_np = validate_sources(
        targets, n, EMPTY_LANE, f"in [0, {n}) or -1 for a full-solve lane",
        expect_lanes=b,
    )
    return jnp.asarray(t_np)


def _validate_dist_true(dist_true, policy: P.PhasePolicy, b: int, n: int):
    """(B, n) f32 dist_true when the policy reads it, else None.

    A provided ``dist_true`` on a non-oracle policy is dropped (the
    reference ``run_phased`` accepts-and-ignores it the same way), so
    callers can plumb it unconditionally.
    """
    if not policy.needs_oracle:
        return None
    if dist_true is None:
        raise ValueError(
            f"criterion {policy.spec!r} includes 'oracle': per-lane "
            f"dist_true of shape ({b}, {n}) is required"
        )
    dt = jnp.asarray(dist_true, jnp.float32)
    if dt.shape != (b, n):
        raise ValueError(
            f"dist_true must have shape ({b}, {n}); got {dt.shape}"
        )
    return dt


def _validate_delta(policy: P.PhasePolicy, g: Graph, delta):
    """Scalar f32 bucket width for delta-policy states, else None.

    Delta-stepping needs a positive finite ``delta`` (defaulting to the
    Meyer-Sanders heuristic); criterion policies must not receive one —
    silently ignoring it would read as "the engine used my bucket width".
    """
    if not policy.uses_delta:
        if delta is not None:
            raise ValueError(
                f"criterion {policy.spec!r} does not take a delta bucket "
                f"width; use criterion='delta' for delta-stepping"
            )
        return None
    if delta is None:
        from repro.core.delta_stepping import default_delta

        delta = default_delta(g)
    delta = float(delta)
    if not (np.isfinite(delta) and delta > 0):
        raise ValueError(
            f"delta must be a positive finite bucket width; got {delta}"
        )
    return jnp.float32(delta)


def init_batch_state(
    g: Graph,
    sources,
    criterion: str = DEFAULT_CRITERION,
    dist_true=None,
    trace_len: int = 1,
    telemetry: bool = False,
    delta: float | None = None,
    targets=None,
) -> BatchState:
    """Fresh ``(B, n)`` stepper state for B lanes over one shared graph.

    ``sources[i] == -1`` (:data:`EMPTY_LANE`) leaves lane ``i`` empty — an
    all-+inf fixed point with no fringe that costs nothing per phase and can
    later be populated with :func:`reset_lane`.

    ``criterion`` is any policy spec: a string ``run_phased`` accepts (a
    criterion plan) or ``"delta"`` (delta-stepping on the same stepper —
    see :mod:`repro.core.policies`); it is canonicalised and stored as
    static metadata on the state, selecting the compiled step program. A
    plan containing ``'oracle'`` additionally requires per-lane
    ``dist_true`` rows ``(B, n)``; ``criterion="delta"`` takes the bucket
    width ``delta`` (default: the Meyer-Sanders heuristic) as pure data.
    ``trace_len`` sizes the per-lane settled-per-phase ring (``>=``
    expected phases records the full profile; the default 1 keeps the
    state small).

    ``telemetry=True`` additionally allocates the fringe/relax rings and the
    ``(B, trace_len, T)`` per-criterion settle-attribution ring that
    :func:`repro.obs.telemetry.phase_telemetry` decodes. Off by default: the
    extra rings change the pytree structure (one recompile) and add scatter
    writes per phase.

    ``targets`` enables per-lane s->t queries: a ``(B,)`` int vector where
    entry ``i`` is lane ``i``'s target vertex (-1 = ordinary full solve).
    Like the telemetry rings it is pytree-structural — the default None
    keeps the state (and every compiled program touching it) bit-identical
    to a target-free build. On a target lane the stepper exits as soon as
    the target settles and the criterion policies prune relax work beyond
    ``dist[target]``, so only the target's distance (and every vertex that
    settles nearer) is guaranteed on that lane's harvested row.
    """
    policy = P.policy_for(criterion)
    src_np = validate_sources(
        sources, g.n, EMPTY_LANE, f"in [0, {g.n}) or -1 for an empty lane"
    )
    if trace_len < 1:
        raise ValueError(f"trace_len must be >= 1; got {trace_len}")
    dt = _validate_dist_true(dist_true, policy, src_np.shape[0], g.n)
    dl = _validate_delta(policy, g, delta)
    tg = _validate_targets(targets, src_np.shape[0], g.n)
    # out-degrees memoised per Graph instance: admission (init/reset) runs
    # per query in serving, the segment-sum it used to pay does not
    return _init_state(
        g, out_degrees(g), jnp.asarray(src_np), dt, dl, tg, policy.spec,
        int(trace_len), bool(telemetry)
    )


def _step_batch_impl(
    g: Graph, ell_in, ell_out, state: BatchState,
    k_phases, use_pallas: bool, stop_on_lane_finish: bool = False,
) -> BatchState:
    """The stepper chassis: policy phases inside a chunked while_loop.

    The policy (selected by ``state.criterion``, static) owns the settle
    decision and the carried ``crit_keys`` stack; the chassis owns the loop
    condition, ring writes, and the two-limb work counters — all gated per
    lane on ``n_fringe > 0`` so finished/empty lanes stay fixed points.
    """
    policy = P.policy_for(state.criterion)
    b = state.dist.shape[0]
    start = state.trips
    live0 = jnp.any(state.status == 1, axis=1)  # (B,) lanes live at entry
    trace_len = state.settled_trace.shape[1]
    rows_b = jnp.arange(b)

    # once-per-chunk invariant repair (e.g. re-priming carried in-side keys
    # after admission) + loop-invariant operands the body closes over
    with jax.named_scope("sssp.prime"):
        state = policy.prime(g, ell_in, state, use_pallas)
        aux = policy.prepare(g, ell_in, ell_out, state, use_pallas)

    def cond(s):
        live = jnp.any(s.status == 1, axis=1)  # lanes never revive, live <= live0
        go = jnp.any(live) & (s.trips - start < k_phases)
        if stop_on_lane_finish:
            # end the chunk as soon as any entry-live lane terminates, so the
            # scheduler can refill it instead of letting it idle out the chunk
            go &= jnp.all(live == live0)
        return go

    def body(s):
        out = policy.phase(g, aux, s, use_pallas)
        n_f, n_settled, relax_inc = out.n_fringe, out.n_settled, out.relax_inc
        with jax.named_scope("sssp.update"):
            new_status = out.status
            if s.target is not None:
                # target-aware early exit: the phase a lane's target settles,
                # its answer dist[target] is final under the active criterion
                # (a settled vertex never updates again), so the remaining
                # fringe is demoted and the lane becomes a fixed point. Every
                # done-lane consumer — the cond below, stop_on_lane_finish,
                # lanes_active, the serving peek — already reads "no fringe",
                # so the exit rides the existing chunking unchanged. The phase
                # itself still counts (the lane was live through it).
                tcol = jnp.clip(s.target, 0, s.dist.shape[1] - 1)
                hit = (s.target >= 0) & (new_status[rows_b, tcol] == 2)
                new_status = jnp.where(
                    hit[:, None] & (new_status == 1), 0, new_status
                )
            live = (n_f > 0).astype(jnp.int32)  # finished/empty lanes stop counting
            # ring write: phase p lands in slot p % trace_len; dead lanes must
            # not write (their stuck slot may hold a wrapped live entry)
            idx = s.phases % trace_len
            lane_on = n_f > 0
            trace = s.settled_trace.at[rows_b, idx].set(
                jnp.where(lane_on, n_settled, s.settled_trace[rows_b, idx])
            )
            fringe_trace, relax_trace, attr_trace = (
                s.fringe_trace, s.relax_trace, s.attr_trace
            )
            if attr_trace is not None:
                fringe_trace = fringe_trace.at[rows_b, idx].set(
                    jnp.where(lane_on, n_f, fringe_trace[rows_b, idx])
                )
                relax_trace = relax_trace.at[rows_b, idx].set(
                    jnp.where(lane_on, relax_inc.astype(jnp.int32),
                              relax_trace[rows_b, idx])
                )
                attr_trace = attr_trace.at[rows_b, idx].set(
                    jnp.where(lane_on[:, None], out.attr_counts,
                              attr_trace[rows_b, idx])
                )
            # cumulative work counters are two-limb (u32 lo + i32 hi): summing
            # the per-phase increments in uint32 keeps even a >2^31-edge phase
            # exact, and the carry extends past 2^32
            sf_lo, sf_hi = _limb_add(
                s.sum_fringe, s.sum_fringe_hi, n_f.astype(jnp.uint32)
            )
            re_lo, re_hi = _limb_add(s.relax_edges, s.relax_edges_hi, relax_inc)
            return BatchState(
                dist=out.dist,
                status=new_status,
                trips=s.trips + 1,
                phases=s.phases + live,
                sum_fringe=sf_lo,
                sum_fringe_hi=sf_hi,
                relax_edges=re_lo,
                relax_edges_hi=re_hi,
                out_deg=s.out_deg,
                crit_keys=out.crit_keys,
                keys_valid=s.keys_valid,
                dist_true=s.dist_true,
                settled_trace=trace,
                fringe_trace=fringe_trace,
                relax_trace=relax_trace,
                attr_trace=attr_trace,
                delta=s.delta,
                target=s.target,
                criterion=s.criterion,
            )

    return jax.lax.while_loop(cond, body, state)


_STEP_STATICS = ("use_pallas", "stop_on_lane_finish")
_step_batch = jax.jit(_step_batch_impl, static_argnames=_STEP_STATICS)
# donating variant: XLA may update the (B, n) state in place instead of
# copying it per call (no-op on CPU, which ignores donation)
_step_batch_donate = jax.jit(
    _step_batch_impl, static_argnames=_STEP_STATICS, donate_argnums=(3,)
)


def step_batch(
    g: Graph,
    state: BatchState,
    k_phases: int,
    ell=None,
    use_pallas: bool = False,
    stop_on_lane_finish: bool = False,
    donate: bool = False,
    ell_out=None,
) -> BatchState:
    """Advance the phase loop by up to ``k_phases`` more trips.

    Returns after ``k_phases`` trips, or earlier when every lane's fringe is
    empty (possibly immediately), or — with ``stop_on_lane_finish`` — as soon
    as any lane that was live on entry terminates (the continuous batcher
    uses this to refill finished lanes with zero idle trips). ``k_phases`` is
    a traced operand, so varying it does not trigger recompilation; shapes
    are fixed by ``(B, n)`` and the state's policy spec selects the
    compiled body (stored as static metadata, so each policy compiles
    once).

    ``ell``/``ell_out`` accept the padded ``(cols, ws)`` pair *or* a
    degree-sliced ``SlicedEll`` (``to_ell_in_sliced``/``to_ell_out_sliced``)
    — results are bit-identical between layouts. ``ell_out`` is built (and
    memoised) on demand only when the policy needs the outgoing adjacency
    (OUT-side dynamic keys), matching ``ell``'s layout when derived.

    ``donate=True`` donates the input state's buffers so accelerator
    backends update them in place rather than copying ~8·B·n bytes per
    chunk. Only pass it when nothing else references those buffers — in
    particular, results of an earlier :func:`harvest` alias them.
    """
    if ell is None:
        ell = to_ell_in(g)
    policy = P.policy_for(state.criterion)
    if policy.needs_out_adjacency:
        if ell_out is None:
            ell_out = (
                to_ell_out_sliced(g) if hasattr(ell, "slices") else to_ell_out(g)
            )
    else:
        ell_out = None
    fn = _step_batch_donate if donate else _step_batch
    return fn(
        g, ell, ell_out, state, jnp.int32(k_phases), bool(use_pallas),
        bool(stop_on_lane_finish),
    )


def _reset_lanes_impl(state: BatchState, sources, new_dist_true,
                      new_targets=None) -> BatchState:
    b, n = state.dist.shape
    touch = sources >= EMPTY_LANE  # KEEP_LANE rows pass through unchanged
    fresh_d, fresh_s = _fresh_rows(sources, n)

    def ctr(old):
        return jnp.where(touch, 0, old)

    dist_true = state.dist_true
    if dist_true is not None and new_dist_true is not None:
        dist_true = jnp.where(touch[:, None], new_dist_true, dist_true)
    target = state.target
    if target is not None:
        # touched lanes take their new target (default -1 = full solve);
        # KEEP_LANE rows keep theirs — in-flight s->t queries unaffected
        fresh_t = (jnp.full((b,), EMPTY_LANE, jnp.int32)
                   if new_targets is None else new_targets)
        target = jnp.where(touch, fresh_t, target)
    return BatchState(
        dist=jnp.where(touch[:, None], fresh_d, state.dist),
        status=jnp.where(touch[:, None], fresh_s, state.status),
        trips=state.trips,
        phases=ctr(state.phases),
        sum_fringe=ctr(state.sum_fringe),
        sum_fringe_hi=ctr(state.sum_fringe_hi),
        relax_edges=ctr(state.relax_edges),
        relax_edges_hi=ctr(state.relax_edges_hi),
        out_deg=state.out_deg,
        crit_keys=(
            None if state.crit_keys is None
            else jnp.where(
                touch[None, :, None],
                P.policy_for(state.criterion).fresh_keys(b, n),
                state.crit_keys,
            )
        ),
        # a touched lane's in-side key slots no longer match its status;
        # the next step_batch re-primes them (one composed pass) before
        # entering the loop
        keys_valid=(
            None if state.keys_valid is None
            else state.keys_valid & ~jnp.any(touch)
        ),
        dist_true=dist_true,
        settled_trace=jnp.where(touch[:, None], 0, state.settled_trace),
        fringe_trace=(
            None if state.fringe_trace is None
            else jnp.where(touch[:, None], 0, state.fringe_trace)
        ),
        relax_trace=(
            None if state.relax_trace is None
            else jnp.where(touch[:, None], 0, state.relax_trace)
        ),
        attr_trace=(
            None if state.attr_trace is None
            else jnp.where(touch[:, None, None], 0, state.attr_trace)
        ),
        delta=state.delta,
        target=target,
        criterion=state.criterion,
    )


def _reset_lane_impl(state: BatchState, lane, source, target) -> BatchState:
    b = state.dist.shape[0]
    vec = jnp.full((b,), KEEP_LANE, jnp.int32).at[lane].set(source)
    tvec = None
    if state.target is not None:
        tvec = jnp.full((b,), EMPTY_LANE, jnp.int32).at[lane].set(target)
    return _reset_lanes_impl(state, vec, None, tvec)


_reset_lane = jax.jit(_reset_lane_impl)
_reset_lane_donate = jax.jit(_reset_lane_impl, donate_argnums=(0,))


_reset_lanes = jax.jit(_reset_lanes_impl)
_reset_lanes_donate = jax.jit(_reset_lanes_impl, donate_argnums=(0,))


def reset_lanes(state: BatchState, sources, donate: bool = False,
                dist_true=None, targets=None) -> BatchState:
    """Re-initialise several lanes in one device call.

    ``sources`` is a ``(B,)`` int vector aligned with the lanes: entry
    ``-2`` (:data:`KEEP_LANE`) leaves that lane's bits untouched, ``-1``
    (:data:`EMPTY_LANE`) parks it empty, and a vertex id starts a fresh
    query there. Semantically identical to a sequence of :func:`reset_lane`
    calls, but an admission burst costs one dispatch regardless of how many
    lanes it refills (the continuous batcher's admission path).

    On an oracle-plan state, refilling a lane with a real source requires
    fresh per-lane ``dist_true`` rows ``(B, n)`` (touched rows replace the
    stored ones); parking/keeping lanes does not.

    On a target-enabled state (``init_batch_state(..., targets=...)``),
    ``targets`` optionally assigns each *touched* lane its new target
    vertex (-1 = full solve, the default when omitted); KEEP_LANE rows
    keep their current target. A target-free state rejects ``targets`` —
    the field is pytree-structural and cannot appear mid-flight.
    """
    src_np = validate_sources(
        sources, state.n, KEEP_LANE,
        f"in [0, {state.n}), -1 (park) or -2 (keep)",
        expect_lanes=state.num_lanes,
    )
    if targets is not None and state.target is None:
        raise ValueError(
            "state was initialised without target lanes; pass "
            "init_batch_state(..., targets=...) to enable s->t queries "
            "(the target field is pytree-structural)"
        )
    tg = _validate_targets(targets, state.num_lanes, state.n)
    dt = None
    if state.dist_true is not None:
        if dist_true is None and (src_np >= 0).any():
            raise ValueError(
                "criterion includes 'oracle': refilling lanes requires "
                "dist_true rows (B, n)"
            )
        if dist_true is not None:
            dt = jnp.asarray(dist_true, jnp.float32)
            if dt.shape != state.dist.shape:
                raise ValueError(
                    f"dist_true must have shape {state.dist.shape}; got {dt.shape}"
                )
    elif dist_true is not None:
        raise ValueError(
            f"criterion {state.criterion!r} does not read dist_true"
        )
    fn = _reset_lanes_donate if donate else _reset_lanes
    return fn(state, jnp.asarray(src_np), dt, tg)


def reset_lane(
    state: BatchState, lane: int, source: int = EMPTY_LANE,
    donate: bool = False, target: int = EMPTY_LANE,
) -> BatchState:
    """Re-initialise one lane's ``(n,)`` slice for a new query (or park it).

    Only row ``lane`` of every per-lane array changes; the other lanes'
    bits are untouched, so queries in flight are unaffected. This is the
    admission primitive of the continuous batcher: a freshly reset lane is
    bitwise identical to row ``lane`` of a fresh :func:`init_batch_state`,
    so the query it carries runs exactly as if it had been solved alone.

    ``donate=True`` lets accelerator backends scatter the row into the
    existing buffers instead of copying the full ``(B, n)`` state (same
    aliasing caveat as :func:`step_batch`; CPU ignores donation).
    """
    if not 0 <= lane < state.num_lanes:
        raise ValueError(f"lane must be in [0, {state.num_lanes}); got {lane}")
    if not EMPTY_LANE <= source < state.n:
        raise ValueError(f"source must be in [0, {state.n}) or -1; got {source}")
    if state.dist_true is not None and source >= 0:
        raise ValueError(
            "criterion includes 'oracle': use reset_lanes(..., dist_true=...) "
            "to refill a lane with its true-distance row"
        )
    if target != EMPTY_LANE:
        if state.target is None:
            raise ValueError(
                "state was initialised without target lanes; pass "
                "init_batch_state(..., targets=...) to enable s->t queries"
            )
        if not EMPTY_LANE <= target < state.n:
            raise ValueError(
                f"target must be in [0, {state.n}) or -1; got {target}"
            )
    fn = _reset_lane_donate if donate else _reset_lane
    return fn(state, jnp.int32(lane), jnp.int32(source), jnp.int32(target))


def lanes_active(state: BatchState) -> np.ndarray:
    """(B,) bool host array: which lanes still have a non-empty fringe."""
    return np.asarray(jnp.any(state.status == 1, axis=1))


def harvest(state: BatchState) -> BatchedResult:
    """Freeze a stepper state into a :class:`BatchedResult`.

    ``settled_per_phase`` is the ``(B, trace_len)`` ring only when tracing
    was actually enabled (``trace_len > 1``); a length-1 ring holds just the
    last phase's count, and handing that out as "the trace" is exactly the
    plausible-but-fake-profile hazard PR 3 removed — so it maps to None.
    """
    traced = state.settled_trace.shape[1] > 1
    trace = state.settled_trace if traced else None

    def ring(x):
        # same honesty rule for the telemetry rings: a trace_len=1 ring
        # holds only the last phase and must not read as a profile
        return x if traced and x is not None else None

    return BatchedResult(
        dist=state.dist,
        status=state.status.astype(jnp.int8),
        phases=state.phases,
        # combine the two-limb device counters into host int64 (the same
        # result-level convention as delta_stepping's DeltaResult)
        sum_fringe=combine_limbs(state.sum_fringe, state.sum_fringe_hi),
        relax_edges=combine_limbs(state.relax_edges, state.relax_edges_hi),
        total_phases=state.trips,
        settled_per_phase=trace,
        fringe_per_phase=ring(state.fringe_trace),
        relax_per_phase=ring(state.relax_trace),
        settle_attribution=ring(state.attr_trace),
        target=state.target,
    )


def _resolve_layout(g: Graph, ell, ell_out, layout: str):
    """Build the requested incoming view when the caller passed none.

    The outgoing view is deliberately NOT built here: only plans with
    dynamic OUT keys read it, and :func:`step_batch` derives one matching
    the incoming layout on demand — eagerly building (and memoising) a
    transpose view the default criterion never touches would double the
    resident adjacency for nothing.
    """
    if layout not in ("padded", "sliced"):
        raise ValueError(f"layout must be 'padded' or 'sliced'; got {layout!r}")
    if ell is None:
        ell = to_ell_in_sliced(g) if layout == "sliced" else to_ell_in(g)
    return ell, ell_out


def run_phased_static(
    g: Graph,
    source: int = 0,
    ell=None,
    use_pallas: bool = False,
    max_phases: int | None = None,
    criterion: str = DEFAULT_CRITERION,
    dist_true=None,
    trace_len: int | None = None,
    ell_out=None,
    layout: str = "padded",
    delta: float | None = None,
    target: int | None = None,
) -> PhasedResult:
    """Phased SSSP on the B=1 stepper, any policy spec.

    ``trace_len`` sizes the settled-per-phase ring; the default (None)
    covers the phase cap so the result carries the *full* per-phase profile
    — the policy's cap bounds its phase count, so the ring never wraps
    (criterion plans match ``run_phased``'s trace exactly). ``dist_true``
    is the (n,) true-distance row, required iff the criterion includes
    'oracle'. ``delta`` is the bucket width for ``criterion="delta"``
    (default ``default_delta(g)``). ``layout`` selects the ELL views built
    when none are passed ("sliced" buckets rows by degree — bit-identical
    results, faster on skewed graphs).

    ``target`` turns the run into an s->t query: the loop exits the phase
    the target settles (with goal-directed pruning on criterion plans), so
    only ``dist[target]`` — bit-exact against the full solve — and the
    vertices that settled before it are guaranteed on the returned row.
    """
    ell, ell_out = _resolve_layout(g, ell, ell_out, layout)
    policy = P.policy_for(criterion)
    cap = int(max_phases) if max_phases is not None else policy.phase_cap(g.n)
    if not 0 <= int(source) < g.n:
        raise ValueError(f"source must be in [0, {g.n}); got {source}")
    if trace_len is None:
        trace_len = cap
    dt = None
    if dist_true is not None:
        dt = jnp.asarray(dist_true, jnp.float32).reshape(1, g.n)
    state = init_batch_state(
        g, [int(source)], criterion=criterion, dist_true=dt,
        trace_len=trace_len, delta=delta,
        targets=None if target is None else [int(target)],
    )
    state = step_batch(
        g, state, cap, ell=ell, use_pallas=use_pallas, ell_out=ell_out
    )
    return PhasedResult(
        dist=state.dist[0],
        status=state.status[0].astype(jnp.int8),
        phases=state.phases[0],
        sum_fringe=combine_limbs(state.sum_fringe, state.sum_fringe_hi)[0],
        # same honesty rule as harvest(): an explicitly disabled ring
        # (trace_len=1 holds only the last phase) reads as "not traced",
        # never as a one-slot pseudo-profile
        settled_per_phase=(
            state.settled_trace[0] if trace_len > 1 else None
        ),
        relax_edges=combine_limbs(state.relax_edges, state.relax_edges_hi)[0],
    )


def run_phased_static_batch(
    g: Graph,
    sources,
    ell=None,
    use_pallas: bool = False,
    max_phases: int | None = None,
    criterion: str = DEFAULT_CRITERION,
    dist_true=None,
    trace_len: int = 1,
    ell_out=None,
    layout: str = "padded",
    telemetry: bool = False,
    delta: float | None = None,
    targets=None,
) -> BatchedResult:
    """Batched phased SSSP: B sources, one graph, one phase loop.

    Args:
      g: the shared input graph.
      sources: (B,) int source vertex ids (one SSSP query per row).
      ell: optional precomputed ``to_ell_in(g)`` or ``to_ell_in_sliced(g)``
        — pass it when answering many batches against the same graph so the
        ELL build is paid once (both builders also memoise per Graph
        instance).
      use_pallas: Pallas kernels (True) vs the ref.py XLA forms (False, the
        default); bit-identical.
      max_phases: safety cap on loop trips (default the policy's cap:
        criterion plans settle >= 1 vertex per phase so n+1 suffices;
        delta-stepping uses the legacy 4n+16 light/heavy-round bound).
      criterion: any registered criterion disjunction (default the paper's
        ``instatic|outstatic``) or ``"delta"`` for bucketed delta-stepping;
        selects the compiled policy.
      dist_true: (B, n) per-row true distances, required iff the criterion
        includes 'oracle'.
      trace_len: settled-per-phase ring length per row (default 1 = off).
      ell_out: optional precomputed outgoing view for dynamic OUT keys.
      layout: ELL layout built when none is passed ("padded" | "sliced");
        bit-identical results either way.
      telemetry: also record fringe/relax-edge rings and per-term settle
        attribution (exposed on the result when ``trace_len > 1``);
        see :mod:`repro.obs.telemetry` for the decoder.
      delta: bucket width for ``criterion="delta"`` (default
        ``default_delta(g)``); rejected for criterion policies.
      targets: optional (B,) per-lane target vertices (-1 = full solve):
        target lanes early-exit (and prune, on criterion plans) the phase
        their target settles — only ``dist[i, targets[i]]`` is guaranteed
        on those rows, bit-exact against the full solve.

    Row ``i`` of the result equals ``run_phased_static(g, sources[i],
    criterion=criterion)`` exactly (same float ops in the same phase
    structure, per-row).
    """
    ell, ell_out = _resolve_layout(g, ell, ell_out, layout)
    # fail loudly on any invalid id: out-of-range sources would otherwise be
    # silently dropped by the scatter (all-inf row, 0 phases)
    src_np = validate_sources(sources, g.n, 0, f"in [0, {g.n})")
    policy = P.policy_for(criterion)
    cap = int(max_phases) if max_phases is not None else policy.phase_cap(g.n)
    state = init_batch_state(
        g, src_np, criterion=criterion, dist_true=dist_true,
        trace_len=trace_len, telemetry=telemetry, delta=delta,
        targets=targets,
    )
    state = step_batch(
        g, state, cap, ell=ell, use_pallas=use_pallas, ell_out=ell_out
    )
    return harvest(state)
