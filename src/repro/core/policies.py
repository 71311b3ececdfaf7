"""Phase policies: the settle-decision layer of the static stepper.

A :class:`PhasePolicy` owns everything about a phase that decides *which*
fringe vertices to process and *what* per-vertex bookkeeping to carry
between phases; the stepper (``repro.core.static_engine``) owns everything
else — lane admission, the chunked ``while_loop``, two-limb work counters,
telemetry rings, harvest. Concretely a policy provides:

  * static per-state metadata (the canonical ``spec`` string carried as
    ``BatchState.criterion``, adjacency-side needs, attribution terms);
  * the layout of the policy-owned carried data (``BatchState.crit_keys``,
    a ``(K, B, n)`` f32 stack) plus the per-lane fresh fill used by
    admission (init / reset_lanes), so "a reset lane is bitwise a fresh
    solve" stays structural;
  * ``prime`` — a once-per-chunk invariant repair run before the loop;
  * ``prepare`` — loop-invariant operands derived from graph + state;
  * ``phase`` — the body: one :class:`PhaseOutcome` per trip.

Two policies exist:

  * :class:`CriterionPolicy` wraps a compiled
    :class:`~repro.core.criteria.CritPlan` — the paper's settle criteria,
    lowered exactly as before the policy split (same kernels, same float
    ops, bit-identical programs for every criterion string).
  * :class:`DeltaPolicy` is Delta-stepping (Meyer & Sanders) on the same
    substrate: buckets of width ``BatchState.delta`` become *weight-gated
    key lanes* — the incoming ELL is split into light (w <= delta) and
    heavy (w > delta) +inf-gated twins once per chunk, and every phase is
    one fused threshold pass (bucket id = ``floor(d/delta)`` fed through
    ``crit_thresholds_batch``) plus one double-gated adjacency scan
    (``kernels.ops.delta_relax_batch``). The carried stack holds the
    classic drain bookkeeping: slot 0 = ``last_processed`` tentative
    distance, slot 1 = the removed-from-bucket flag. A lane is on a
    *light round* while any bucket vertex has ``d < last_processed``
    (reprocessing instead of explicit reinsertion); otherwise the phase is
    its *heavy turn*: removed vertices relax their heavy edges once and
    settle, which advances the bucket. Per-lane mixed rounds are fine —
    the body is uniform, lanes gate themselves.

Bucket membership deliberately uses the per-vertex bucket index
(``floor(d/delta) == lane_min``) rather than the legacy loop's
``lo <= d < hi`` interval compare: multiplying the bucket id back by
``delta`` can round past the lane minimum in f32, excluding the argmin
vertex from its own bucket and livelocking the drain. The index compare
is exact by construction (the argmin's index *is* the lane min), and the
final distances are unchanged either way — both schedules converge to the
unique f32 min-plus fixed point (f32 min is exact, f32 add is monotone),
which is also why ``DeltaPolicy`` distances are bit-exact against both
``run_phased`` and the legacy host loop for every delta
(``tests/test_delta_policy.py``).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import criteria as C
from repro.core.graph import Graph
from repro.kernels import ops as kops

INF = jnp.inf

DELTA_SPEC = "delta"  # the canonical spec string selecting DeltaPolicy


class PhaseOutcome(NamedTuple):
    """What one policy phase hands back to the stepper chassis.

    The chassis turns this into the next ``BatchState``: ring writes and
    the two-limb counters are gated on ``n_fringe > 0`` (dead lanes are
    fixed points and must not write), exactly as before the policy split.
    """

    dist: jax.Array  # (B, n) f32 post-phase tentative distances
    status: jax.Array  # (B, n) int32 post-phase status (0=U, 1=F, 2=S)
    crit_keys: jax.Array | None  # (K, B, n) f32 carried stack (or None)
    n_fringe: jax.Array  # (B,) int32 |F| at phase entry (the live gauge)
    n_settled: jax.Array  # (B,) int32 vertices settled this phase
    relax_inc: jax.Array  # (B,) uint32 out-edges relaxed this phase
    attr_counts: jax.Array | None  # (B, T) int32 attribution slots, only
    #   when the state carries an attr ring (T = len(attribution_terms()))


class PhasePolicy:
    """Interface of a settle policy (see module docstring).

    Instances are created once per canonical spec (:func:`policy_for` is
    cached) and treated as static jit metadata — they must be stateless
    beyond their construction arguments.
    """

    spec: str  # canonical spec string (== BatchState.criterion)
    uses_delta: bool = False  # reads BatchState.delta (bucket width)
    needs_oracle: bool = False  # requires per-lane dist_true rows
    needs_out_adjacency: bool = False  # phase reads the outgoing ELL

    def attribution_terms(self) -> tuple[str, ...]:
        """Names of the per-phase attribution slots, in recorded order."""
        raise NotImplementedError

    def share_terms(self) -> tuple[str, ...]:
        """The attribution slots that are *counts* (summable into shares);
        everything a portfolio record may aggregate. Defaults to all."""
        return self.attribution_terms()

    def num_key_slots(self) -> int:
        """Depth K of the carried ``crit_keys`` stack (0 = no stack)."""
        raise NotImplementedError

    def fresh_keys(self, b: int, n: int) -> jax.Array | None:
        """(K, B, n) carried-stack values of a freshly admitted lane."""
        raise NotImplementedError

    def init_keys_valid(self) -> jax.Array | None:
        """Initial ``keys_valid`` flag (None when the policy never primes)."""
        return None

    def phase_cap(self, n: int) -> int:
        """Default safety cap on loop trips for a full solve over n vertices."""
        raise NotImplementedError

    def prime(self, g: Graph, ell_in, state, use_pallas: bool):
        """Once-per-chunk invariant repair before entering the loop."""
        return state

    def prepare(self, g: Graph, ell_in, ell_out, state, use_pallas: bool):
        """Loop-invariant operands the phase body closes over."""
        raise NotImplementedError

    def phase(self, g: Graph, aux, s, use_pallas: bool) -> PhaseOutcome:
        """Advance state ``s`` by one phase."""
        raise NotImplementedError


# ---------------------------------------------------------------------------
# CriterionPolicy: the compiled-plan path (bit-identical to the pre-policy
# engine — these helpers moved here verbatim from static_engine)
# ---------------------------------------------------------------------------


def _spec_by_name(plan: C.CritPlan, name: str) -> C.KeySpec:
    return plan.keys[[k.name for k in plan.keys].index(name)]


def _compute_out_keys(plan: C.CritPlan, g: Graph, status, ell_out,
                      use_pallas: bool) -> dict:
    """The plan's out-side dynamic keys for the current status, from ONE
    fused scan over the outgoing adjacency: name -> (B, n) f32.

    Independent keys (elementwise gates) share the scan's tile loads; the
    dependent ``out_full`` adds a second sweep inside the same launch,
    gated by the ``out_dyn`` the first sweep produced (paper Eq. 2's
    two-hop slack).
    """
    if not (plan.out_scan_keys or plan.out_scan_dep):
        return {}
    gates = jnp.stack([
        C.key_gate(_spec_by_name(plan, nm), status, g.in_min_static,
                   g.out_min_static, {})
        for nm in plan.out_scan_keys
    ])
    dep_parts = None
    names = list(plan.out_scan_keys)
    if plan.out_scan_dep is not None:
        spec = _spec_by_name(plan, plan.out_scan_dep)
        dga, dgb = C.dep_gate_parts(spec, status)
        dep_parts = (dga, dgb, plan.out_scan_keys.index(spec.aux))
        names.append(plan.out_scan_dep)
    keys = kops.out_scan_keys_batch(gates, dep_parts, ell_out,
                                    use_pallas=use_pallas)
    return {nm: keys[i] for i, nm in enumerate(names)}


def _recompute_in_keys(plan: C.CritPlan, g: Graph, status, ell_in,
                       use_pallas: bool) -> jax.Array:
    """(K_in, B, n) in-side keys for the *current* status via composed
    key-min passes — the priming path after admission; the steady state
    carries them out of the fused in-scan instead."""
    return jnp.stack([
        kops.key_min_batch_any(
            C.key_gate(_spec_by_name(plan, nm), status, g.in_min_static,
                       g.out_min_static, {}),
            ell_in, use_pallas=use_pallas,
        )
        for nm in plan.in_scan_keys
    ])


def _in_slot_indices(plan: C.CritPlan) -> list[int]:
    """Positions of the in-scan keys inside the ``plan.keys`` stack."""
    order = [k.name for k in plan.keys]
    return [order.index(nm) for nm in plan.in_scan_keys]


def _threshold_keys(plan: C.CritPlan, g: Graph, keys: dict, b: int):
    """Key stack for the fused lane reduction: None (no OUT members),
    ``(K, n)`` shared (all static — the default plan pays no per-lane key
    traffic), or ``(K, B, n)`` per-lane (any dynamic OUT key)."""
    if not plan.out_terms:
        return None
    if all(t == "static" for t in plan.out_terms):
        return g.out_min_static[None]
    return jnp.stack([
        jnp.broadcast_to(g.out_min_static, (b, g.n)) if t == "static"
        else keys[t]
        for t in plan.out_terms
    ])


class CriterionPolicy(PhasePolicy):
    """Settle policy executing a compiled :class:`~repro.core.criteria.CritPlan`.

    The carried ``crit_keys`` stack holds the plan's dynamic keys (ordered
    like ``plan.keys``); in-side slots are emitted by the fused in-scan and
    re-primed once per chunk when admission invalidated them
    (``keys_valid``). The phase body is bitwise the pre-policy engine's.
    """

    def __init__(self, plan: C.CritPlan):
        self.plan = plan
        self.spec = plan.criterion

    @property
    def needs_oracle(self) -> bool:
        return self.plan.needs_oracle

    @property
    def needs_out_adjacency(self) -> bool:
        return self.plan.needs_out_adjacency

    def attribution_terms(self) -> tuple[str, ...]:
        return C.attribution_terms(self.plan)

    def num_key_slots(self) -> int:
        return len(self.plan.keys)

    def fresh_keys(self, b: int, n: int) -> jax.Array | None:
        if not self.plan.keys:
            return None
        return jnp.zeros((len(self.plan.keys), b, n), jnp.float32)

    def init_keys_valid(self) -> jax.Array | None:
        return jnp.asarray(False) if self.plan.in_scan_keys else None

    def phase_cap(self, n: int) -> int:
        # every live lane settles >= 1 vertex per phase under any criterion
        return n + 1

    def prime(self, g: Graph, ell_in, state, use_pallas: bool):
        import dataclasses

        plan = self.plan
        in_slots = _in_slot_indices(plan)
        if not in_slots:
            return state
        # re-prime carried in-side keys once per chunk: admission (init /
        # reset) touches status without scanning the adjacency, so the
        # carried slots may be stale. Recomputing equals the carried values
        # bitwise wherever they were valid (exact min), so one cond per
        # *chunk* — not per phase — restores the invariant the loop body
        # relies on: crit_keys in-side slots always match s.status.
        primed = jax.lax.cond(
            state.keys_valid,
            lambda: state.crit_keys,
            lambda: state.crit_keys.at[jnp.asarray(in_slots)].set(
                _recompute_in_keys(plan, g, state.status, ell_in, use_pallas)
            ),
        )
        return dataclasses.replace(
            state, crit_keys=primed, keys_valid=jnp.asarray(True)
        )

    def prepare(self, g: Graph, ell_in, ell_out, state, use_pallas: bool):
        return (ell_in, ell_out)

    def phase(self, g: Graph, aux, s, use_pallas: bool) -> PhaseOutcome:
        plan = self.plan
        ell_in, ell_out = aux
        b = s.dist.shape[0]
        in_slots = _in_slot_indices(plan)
        d, status = s.dist, s.status
        fringe = status == 1
        with jax.named_scope("sssp.out_scan"):
            # --- out-scan: every out-side dynamic key from one fused launch
            keys = _compute_out_keys(plan, g, status, ell_out, use_pallas)
        with jax.named_scope("sssp.thresholds"):
            # in-side keys ride in from the previous phase's in-scan (or the
            # pre-loop priming); by invariant they match the current status
            for i, nm in zip(in_slots, plan.in_scan_keys):
                keys[nm] = s.crit_keys[i]
            mins, n_f = kops.crit_thresholds_batch(
                d, status, _threshold_keys(plan, g, keys, b),
                use_pallas=use_pallas,
            )
        with jax.named_scope("sssp.settle"):
            term_masks = None
            if s.attr_trace is not None:
                # telemetry path: materialise each member's settle mask so the
                # attribution ring can credit every settled vertex to the first
                # member that proved it; the union is boolean-identical to
                # plan_union_mask (same masks, OR'd)
                term_masks = C.plan_term_masks(
                    plan, d, fringe, mins, keys, g.in_min_static, s.dist_true
                )
                settle = term_masks[0]
                for m in term_masks[1:]:
                    settle = settle | m
            else:
                settle = C.plan_union_mask(
                    plan, d, fringe, mins, keys, g.in_min_static, s.dist_true
                )
            if plan.needs_fallback:
                # bare-oracle plans can produce an empty mask on a non-empty
                # fringe (f32-vs-f64 tolerance); reproduce evaluate()'s DIJK
                # guard per lane so progress — and run_phased parity — hold
                dijk = fringe & (d <= mins[0][:, None])
                settle = jnp.where(
                    jnp.any(settle, axis=1, keepdims=True), settle, dijk
                )
        with jax.named_scope("sssp.relax"):
            # --- goal-directed pruning bound (target lanes only): the target's
            # current tentative distance. Settled sources at or beyond it can
            # never improve tent(target) (non-negative f32 adds are monotone
            # and the bound never drops below the target's final distance), so
            # the gated relax variants drop them from the scans — the settle
            # DECISION above is untouched, only the relax work shrinks. The
            # branch is structural: target-free states trace the exact
            # pre-target program.
            bound = None
            if s.target is not None:
                b_rows = jnp.arange(b)
                tcol = jnp.clip(s.target, 0, d.shape[1] - 1)
                bound = jnp.where(s.target >= 0, d[b_rows, tcol], INF)
                relax_from = settle & (d < bound[:, None])
            else:
                relax_from = settle
            # --- in-scan: relax this phase; fused plans also emit the NEXT
            # phase's in-side keys from the same tile loads
            next_in = None
            if in_slots:
                # key gates come from the FULL settle mask (they encode the
                # post-settle status, which pruning does not change)
                parts = [
                    C.in_scan_gate_parts(_spec_by_name(plan, nm), status, settle,
                                         g.in_min_static[None])
                    for nm in plan.in_scan_keys
                ]
                if bound is not None:
                    upd, next_in = kops.in_scan_relax_keys_gated_batch(
                        d, settle, bound, parts, ell_in, use_pallas=use_pallas
                    )
                else:
                    upd, next_in = kops.in_scan_relax_keys_batch(
                        d, settle, parts, ell_in, use_pallas=use_pallas
                    )
            elif bound is not None:
                upd = kops.relax_settled_gated_batch(
                    d, settle, bound, ell_in, use_pallas=use_pallas
                )
            elif kops._is_sliced(ell_in):
                upd = kops.relax_settled_batch_sliced(
                    d, settle, ell_in, use_pallas=use_pallas
                )
            else:
                upd = kops.relax_settled_batch(
                    d, settle, ell_in[0], ell_in[1], use_pallas=use_pallas
                )
        with jax.named_scope("sssp.update"):
            new_d = jnp.minimum(d, upd)
            new_status = jnp.where(
                settle, 2, jnp.where((status == 0) & (upd < INF), 1, status)
            )
            n_settled = jnp.sum(settle, axis=1, dtype=jnp.int32)
            relax_inc = jnp.sum(
                jnp.where(relax_from, s.out_deg[None], 0).astype(jnp.uint32),
                axis=1, dtype=jnp.uint32,
            )
            attr_counts = None
            if s.attr_trace is not None:
                # first-true claiming partitions the settled set over the plan's
                # members in canonical order; a vertex proven by several members
                # counts once, so per-term counts sum exactly to n_settled
                claimed = jnp.zeros_like(settle)
                counts = []
                for m in term_masks:
                    take = m & settle & ~claimed
                    counts.append(jnp.sum(take, axis=1, dtype=jnp.int32))
                    claimed = claimed | take
                if plan.needs_fallback:
                    # residual slot: vertices the DIJK progress guard settled
                    counts.append(n_settled - sum(counts))
                attr_counts = jnp.stack(counts, axis=1)  # (B, T)
            crit_keys = s.crit_keys
            if plan.keys:
                crit_keys = jnp.stack([keys[k.name] for k in plan.keys])
                for j, i in enumerate(in_slots):
                    crit_keys = crit_keys.at[i].set(next_in[j])
        return PhaseOutcome(
            dist=new_d, status=new_status, crit_keys=crit_keys,
            n_fringe=n_f, n_settled=n_settled, relax_inc=relax_inc,
            attr_counts=attr_counts,
        )


# ---------------------------------------------------------------------------
# DeltaPolicy: Delta-stepping as weight-gated key lanes on the same stepper
# ---------------------------------------------------------------------------


class DeltaPolicy(PhasePolicy):
    """Delta-stepping (Meyer & Sanders) as a stepper phase policy.

    Carried stack (``crit_keys``), per lane per vertex:

      * slot 0 — ``last_processed``: the tentative distance at which the
        vertex last had its light edges relaxed this drain (+inf = not yet;
        a vertex whose ``d`` drops below it re-enters the round — the
        reprocessing formulation of bucket reinsertion);
      * slot 1 — ``removed``: 1.0 once the vertex was processed by any
        light round of the current drain (its heavy edges fire on the
        lane's heavy turn, after which both slots reset for the next
        bucket).

    The bucket id needs no carried scalar: every active tentative distance
    is ``>= lane minimum`` (weights are non-negative, so a drain can never
    create work below its own bucket), hence ``floor(d/delta)`` reduced
    over the fringe — one ``crit_thresholds_batch`` pass — recovers it
    each phase, keeping admission/reset semantics identical to the
    criterion path. ``delta`` itself is pure data (``BatchState.delta``),
    so every bucket width shares one compiled program.

    Attribution terms: ``light`` (bucket vertices processed on a light
    round), ``heavy`` (vertices settled on the heavy turn — equals the
    settled ring), ``bucket`` (the lane's bucket id that phase; an id, not
    a count, so it is excluded from ``share_terms``).
    """

    spec = DELTA_SPEC
    uses_delta = True
    needs_out_adjacency = False

    def attribution_terms(self) -> tuple[str, ...]:
        return ("light", "heavy", "bucket")

    def share_terms(self) -> tuple[str, ...]:
        return ("light", "heavy")

    def num_key_slots(self) -> int:
        return 2

    def fresh_keys(self, b: int, n: int) -> jax.Array:
        return jnp.stack([
            jnp.full((b, n), INF, jnp.float32),  # last_processed
            jnp.zeros((b, n), jnp.float32),  # removed
        ])

    def phase_cap(self, n: int) -> int:
        # light rounds are label-correcting: a bucket can reprocess its
        # vertices several times before the heavy turn — the same bound the
        # legacy host loop uses
        return 4 * n + 16

    def prepare(self, g: Graph, ell_in, ell_out, state, use_pallas: bool):
        delta = state.delta
        ell_light, ell_heavy = kops.weight_gated_ell(ell_in, delta)
        # per-vertex light/heavy out-degrees for the relax-work counters
        # (COO padding carries w=+inf, so `finite` masks it out)
        finite = jnp.isfinite(g.w)
        deg_light = jax.ops.segment_sum(
            (finite & (g.w <= delta)).astype(jnp.int32), g.src,
            num_segments=g.n,
        )
        deg_heavy = state.out_deg - deg_light
        return (ell_light, ell_heavy, deg_light, deg_heavy)

    def phase(self, g: Graph, aux, s, use_pallas: bool) -> PhaseOutcome:
        ell_light, ell_heavy, deg_light, deg_heavy = aux
        d, status = s.dist, s.status
        fringe = status == 1
        last_proc = s.crit_keys[0]
        removed = s.crit_keys[1] > 0.5
        with jax.named_scope("sssp.thresholds"):
            # bucket id per fringe vertex; the fused threshold kernel reduces it
            # to the lane's current bucket and counts the fringe in one pass
            bidx = jnp.where(fringe, jnp.floor(d / s.delta), INF)
            mins, n_f = kops.crit_thresholds_batch(
                bidx, status, None, use_pallas=use_pallas
            )
        with jax.named_scope("sssp.settle"):
            b_lane = mins[0]  # (B,) current bucket id (+inf on empty lanes)
            in_bucket = fringe & (bidx == b_lane[:, None])
            cur = in_bucket & (d < last_proc)  # light-round work set
            light_round = jnp.any(cur, axis=1)  # (B,)
            heavy_turn = ~light_round  # drain done (or lane idle)
            heavy_from = heavy_turn[:, None] & removed
            settle = heavy_from  # the bucket settles on its heavy turn
        with jax.named_scope("sssp.relax"):
            # one double-gated adjacency scan: light edges from this round's
            # work set, heavy edges from the removed set on the heavy turn
            upd = kops.delta_relax_batch(
                d, cur, heavy_from, ell_light, ell_heavy, use_pallas=use_pallas
            )
        with jax.named_scope("sssp.update"):
            new_d = jnp.minimum(d, upd)
            new_status = jnp.where(
                settle, 2, jnp.where((status == 0) & (upd < INF), 1, status)
            )
            # drain bookkeeping: light rounds record the processed tentatives
            # and extend `removed`; the heavy turn resets both for the next
            # bucket (an idle lane is a fixed point: both already fresh)
            new_last = jnp.where(
                heavy_turn[:, None], INF, jnp.where(cur, d, last_proc)
            )
            new_removed = jnp.where(heavy_turn[:, None], False, removed | cur)
            crit_keys = jnp.stack([new_last, new_removed.astype(jnp.float32)])
            n_settled = jnp.sum(settle, axis=1, dtype=jnp.int32)
            relax_inc = (
                jnp.sum(jnp.where(cur, deg_light[None], 0), axis=1,
                        dtype=jnp.int32)
                + jnp.sum(jnp.where(heavy_from, deg_heavy[None], 0), axis=1,
                          dtype=jnp.int32)
            ).astype(jnp.uint32)
            attr_counts = None
            if s.attr_trace is not None:
                n_light = jnp.sum(cur, axis=1, dtype=jnp.int32)
                bucket_id = jnp.where(n_f > 0, b_lane, 0.0).astype(jnp.int32)
                attr_counts = jnp.stack([n_light, n_settled, bucket_id], axis=1)
        return PhaseOutcome(
            dist=new_d, status=new_status, crit_keys=crit_keys,
            n_fringe=n_f, n_settled=n_settled, relax_inc=relax_inc,
            attr_counts=attr_counts,
        )


# ---------------------------------------------------------------------------
# Spec resolution
# ---------------------------------------------------------------------------


def canonical_spec(spec: str) -> str:
    """Canonicalise a policy spec: ``"delta"`` or any criterion string."""
    if isinstance(spec, str) and spec.strip().lower() == DELTA_SPEC:
        return DELTA_SPEC
    return C.canonical(spec)


@functools.lru_cache(maxsize=None)
def _policy_for_canonical(spec: str) -> PhasePolicy:
    if spec == DELTA_SPEC:
        return DeltaPolicy()
    return CriterionPolicy(C.plan_for(spec))


def policy_for(spec: str) -> PhasePolicy:
    """The (cached) :class:`PhasePolicy` a spec string selects.

    ``"delta"`` selects :class:`DeltaPolicy`; anything else must be a
    registered criterion disjunction and selects its
    :class:`CriterionPolicy`. The returned instance is static jit
    metadata: one compiled step program per canonical spec.
    """
    return _policy_for_canonical(canonical_spec(spec))
