"""Engine backend adapters: the seam between scheduler and solver.

:class:`ContinuousBatcher` never touches an engine directly — it drives an
:class:`EngineBackend`, a five-method adapter (``init`` / ``step`` /
``reset_lanes`` / ``peek`` / ``take_row``) over any resumable B-lane phase
stepper. Two implementations exist:

  * :class:`StaticBackend` — the single-device stepper
    (``repro.core.static_engine``): ``(B, n)`` state, ELL pull scans.
  * :class:`ShardedBackend` — the mesh stepper
    (``repro.core.distributed``): ``(B, n_pad)`` state block-sharded over
    the mesh's vertex axis, COO push + one vector collective per phase.

Both expose identical semantics — a lane is a fixed point when empty or
finished, a reset lane is bitwise a fresh solve, ``stop_on_lane_finish``
ends a chunk on the first lane termination — so the scheduler's
admission/coalescing/cache/metrics machinery is backend-agnostic and every
completed request's distances are bit-exact against a standalone
``run_phased_static`` solve regardless of which engine served it
(pinned by the shared parametrised test in ``tests/test_serving.py``).
"""
from __future__ import annotations

import dataclasses
from typing import Protocol, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import criteria as C
from repro.core import policies as P
from repro.core.delta_stepping import default_delta
from repro.core.graph import (
    Graph,
    out_degrees,
    to_ell_in,
    to_ell_in_sliced,
    to_ell_out,
    to_ell_out_sliced,
)
from repro.core.static_engine import (
    DEFAULT_CRITERION,
    EMPTY_LANE,
    BatchState,
    init_batch_state,
    reset_lanes,
    run_phased_static_batch,
    step_batch,
)


def _serving_plan(criterion: str) -> C.CritPlan:
    """Validate and canonicalise a serving criterion.

    'oracle' is rejected: a clairvoyant criterion needs the answer it is
    supposed to compute as input, which no online workload has.
    """
    plan = C.plan_for(criterion)
    if plan.needs_oracle:
        raise ValueError(
            "serving backends cannot run the 'oracle' criterion: it requires "
            "per-query true distances up front"
        )
    return plan


def _serving_policy(spec: str) -> P.PhasePolicy:
    """Validate and resolve a serving policy spec (criterion or "delta").

    Same oracle rejection as :func:`_serving_plan`, lifted to the policy
    layer so delta-stepping backends pass through.
    """
    pol = P.policy_for(spec)
    if pol.needs_oracle:
        raise ValueError(
            "serving backends cannot run the 'oracle' criterion: it requires "
            "per-query true distances up front"
        )
    return pol


@jax.jit
def _peek(state):
    """One fused device read per step: (trips, per-lane live flag, phases)."""
    return state.trips, jnp.any(state.status == 1, axis=1), state.phases


@jax.jit
def _take_row(dist, lane):
    # traced lane index -> one compile total (a python-int index or a
    # variable-length fancy-index would recompile per lane / per count)
    return jax.lax.dynamic_index_in_dim(dist, lane, keepdims=False)


@runtime_checkable
class EngineBackend(Protocol):
    """What the scheduler needs from a resumable B-lane engine."""

    g: Graph
    criterion: str  # canonical criterion string the engine solves with —
    #   part of the serving cache key: rows computed under different criteria
    #   coincide only in exact arithmetic, so they must never share entries

    @property
    def n(self) -> int:
        """Vertex count queries are validated against."""
        ...

    def init(self, lanes: int):
        """Fresh all-empty state with ``lanes`` lanes."""
        ...

    def step(self, state, k_phases: int, *, stop_on_lane_finish: bool = True,
             donate: bool = False):
        """Advance up to ``k_phases`` trips (early exit on lane finish)."""
        ...

    def reset_lanes(self, state, sources: np.ndarray, *, donate: bool = False,
                    targets: np.ndarray | None = None):
        """Re-init the lanes ``sources`` selects (KEEP_LANE passes through).

        ``targets`` (point-capable backends only) gives each admitted lane
        its s->t target vertex, ``EMPTY_LANE`` for a full solve."""
        ...

    def peek(self, state) -> tuple[int, np.ndarray, np.ndarray]:
        """(trips, (B,) bool live flags, (B,) int phases) — one device sync."""
        ...

    def take_row(self, state, lane: int) -> np.ndarray:
        """Lane ``lane``'s (n,) f32 distance row as a fresh host-owned array
        (never aliasing the state buffers — the scheduler donates those to
        the next engine call)."""
        ...


class StaticBackend:
    """Adapter over the single-device static-engine stepper.

    ``layout`` selects the resident adjacency views ("padded" ELL or the
    degree-sliced "sliced" layout — bit-identical results, the sliced one
    wins on skewed degree distributions); an explicit ``ell`` overrides it.
    ``policy`` accepts any policy spec (criterion disjunction or
    ``"delta"``) and takes precedence over ``criterion`` — the two
    keywords exist so pre-portfolio callers keep working; ``delta`` is the
    bucket width for the delta policy (default ``default_delta(g)``).
    Execution mode / tile sizes resolve through ``repro.kernels.config``
    (env overrides + tuning ledger), so a server process tuned at startup
    serves every later query with the tuned configuration.

    ``point_queries=True`` initialises target-capable lane state (the
    pytree-structural ``BatchState.target`` field, DESIGN.md Sec. 13), so
    the scheduler can mix full solves and early-exiting s->t lanes in one
    batch. Off by default: a target-free server stays bit-identical to the
    pre-target engine program.
    """

    def __init__(self, g: Graph, ell=None, use_pallas: bool = False,
                 criterion: str = DEFAULT_CRITERION, layout: str = "padded",
                 policy: str | None = None, delta: float | None = None,
                 point_queries: bool = False):
        spec = policy if policy is not None else criterion
        pol = _serving_policy(spec)
        if layout not in ("padded", "sliced"):
            raise ValueError(
                f"layout must be 'padded' or 'sliced'; got {layout!r}"
            )
        sliced = layout == "sliced"
        self.g = g
        if ell is None:
            ell = to_ell_in_sliced(g) if sliced else to_ell_in(g)
        self.ell = ell
        self.ell_out = None
        if pol.needs_out_adjacency:
            self.ell_out = to_ell_out_sliced(g) if sliced else to_ell_out(g)
        self.use_pallas = bool(use_pallas)
        self.criterion = pol.spec
        self.point_queries = bool(point_queries)
        self.delta = None
        if pol.uses_delta:
            self.delta = float(delta) if delta is not None else default_delta(g)
        elif delta is not None:
            raise ValueError(
                f"policy {pol.spec!r} does not take a delta bucket width; "
                "use policy='delta' for delta-stepping"
            )

    @property
    def n(self) -> int:
        return self.g.n

    def init(self, lanes: int) -> BatchState:
        empty = np.full(lanes, EMPTY_LANE, np.int32)
        return init_batch_state(
            self.g, empty, criterion=self.criterion, delta=self.delta,
            targets=empty if self.point_queries else None,
        )

    def step(self, state, k_phases, *, stop_on_lane_finish=True, donate=False):
        return step_batch(
            self.g, state, k_phases, ell=self.ell, use_pallas=self.use_pallas,
            stop_on_lane_finish=stop_on_lane_finish, donate=donate,
            ell_out=self.ell_out,
        )

    def reset_lanes(self, state, sources, *, donate=False, targets=None):
        return reset_lanes(state, sources, donate=donate, targets=targets)

    def peek(self, state):
        trips, active, phases = _peek(state)
        return int(trips), np.asarray(active), np.asarray(phases)

    def take_row(self, state, lane):
        return np.asarray(_take_row(state.dist, jnp.int32(lane)))


class ShardedBackend:
    """Adapter over the mesh-sharded batch stepper.

    The same scheduler then serves continuous traffic against a graph whose
    vertex state lives block-partitioned across the device mesh — lanes are
    rows of the ``(B, n_pad)`` sharded state, and each scheduling round's
    ``step`` runs the fused shard_map phase loop (DESIGN.md Sec. 7).
    """

    def __init__(self, g: Graph, mesh, axes, schedule: str = "reduce_scatter",
                 pad_multiple: int = 8, criterion: str = DEFAULT_CRITERION):
        # imported lazily-ish at construction: the distributed module pulls
        # in shard_map machinery the static serving path never needs
        from repro.core.distributed import place_sharded_graph, shard_graph_batch

        if isinstance(axes, str):
            axes = (axes,)
        self.g = g
        self.mesh = mesh
        self.axes = tuple(axes)
        self.schedule = schedule
        plan = _serving_plan(criterion)
        self.criterion = plan.criterion
        num = int(np.prod([mesh.shape[a] for a in self.axes]))
        # the backend's criterion is fixed for its lifetime, so only build
        # the transpose edge partition when the plan's dynamic OUT keys
        # will actually read it (it doubles resident edge memory)
        self.sg = place_sharded_graph(
            shard_graph_batch(g, num, pad_multiple=pad_multiple,
                              with_transpose=plan.needs_out_adjacency),
            mesh, self.axes,
        )

    @property
    def n(self) -> int:
        return self.g.n

    def init(self, lanes: int):
        from repro.core.distributed import init_sharded_batch_state

        return init_sharded_batch_state(
            self.sg, np.full(lanes, EMPTY_LANE, np.int32),
            criterion=self.criterion,
        )

    def step(self, state, k_phases, *, stop_on_lane_finish=True, donate=False):
        from repro.core.distributed import step_sharded_batch

        return step_sharded_batch(
            self.sg, state, self.mesh, self.axes, k_phases,
            schedule=self.schedule, stop_on_lane_finish=stop_on_lane_finish,
            donate=donate,
        )

    def reset_lanes(self, state, sources, *, donate=False, targets=None):
        from repro.core.distributed import reset_sharded_lanes

        if targets is not None:
            raise ValueError(
                "ShardedBackend does not support s->t target lanes; serve "
                "point queries through a point-capable StaticBackend"
            )
        return reset_sharded_lanes(state, sources, donate=donate)

    def peek(self, state):
        trips, active, phases = _peek(state)
        return int(trips), np.asarray(active), np.asarray(phases)

    def take_row(self, state, lane):
        # slice off the padding columns so consumers (cache, parity checks)
        # see the same (n,) row shape as the static backend
        return np.asarray(_take_row(state.dist, jnp.int32(lane)))[: state.n]


# ---------------------------------------------------------------------------
# Engine portfolio: measured policy x layout routing
# ---------------------------------------------------------------------------


def _degree_bucket(g: Graph) -> str:
    """``max/mean`` out-degree >= 4 reads as a skewed (power-law-ish) graph
    — the regime where the sliced layout and bucketed scheduling pay off —
    everything else as flat."""
    deg = np.asarray(out_degrees(g), np.float64)
    mean = float(deg.mean()) if deg.size else 0.0
    if mean <= 0.0:
        return "flat"
    return "skew" if float(deg.max()) / mean >= 4.0 else "flat"


def _weight_bucket(g: Graph) -> str:
    """Coefficient of variation of the (finite) edge weights: >= 0.9 reads
    as heavy-tailed (exponential sits at 1.0, uniform at ~0.58) — the
    regime where delta-stepping's bucket width choice actually matters."""
    w = np.asarray(g.w, np.float64)
    w = w[np.isfinite(w)]
    mean = float(w.mean()) if w.size else 0.0
    if mean <= 0.0:
        return "uniform"
    return "heavy" if float(w.std()) / mean >= 0.9 else "uniform"


def _depth_bucket(g: Graph) -> str:
    """Cheap hop-diameter proxy: one host BFS (out-edges, unweighted) from
    the max-out-degree vertex; eccentricity > 2*log2(n) reads as a deep
    (road/grid-like) graph, where phase counts scale with depth rather
    than log n and static criteria lose ground to dynamic ones. (A grid's
    centre eccentricity ~sqrt(n) clears the bound from ~6x6 up; expander
    families sit at O(log n) and never do.)"""
    from repro.core.graph import to_numpy_csr

    n = g.n
    if n <= 1:
        return "shallow"
    indptr, indices, _ = to_numpy_csr(g)
    counts_all = np.diff(indptr)
    start = int(np.argmax(counts_all))
    seen = np.zeros(n, bool)
    seen[start] = True
    frontier = np.array([start], np.int64)
    ecc = 0
    while frontier.size:
        starts = indptr[frontier]
        counts = counts_all[frontier]
        total = int(counts.sum())
        if total == 0:
            break
        offs = np.repeat(starts, counts) + (
            np.arange(total) - np.repeat(np.cumsum(counts) - counts, counts)
        )
        nbr = np.unique(indices[offs])
        nbr = nbr[~seen[nbr]]
        if nbr.size == 0:
            break
        seen[nbr] = True
        frontier = nbr
        ecc += 1
    return "deep" if ecc > 2.0 * np.log2(n) else "shallow"


def graph_family(g: Graph) -> str:
    """Workload bucket the portfolio ledger keys on: ``<deg>-<wt>-<depth>``.

    Three cheap axes — degree skew (``flat``/``skew``), weight tail
    (``uniform``/``heavy``) and a BFS hop-diameter proxy
    (``shallow``/``deep``) — each the regime boundary for one routing
    decision: layout, bucket width, and criterion dynamism respectively.
    The buckets are deliberately crude: the ledger records *measurements*,
    so a family only needs to be stable enough that graphs sharing it rank
    the candidates the same way. Memoised on the graph instance (the depth
    proxy walks the CSR once); never contains ``:`` (ledger key syntax)
    or ``-``-free ambiguity — :func:`family_fallbacks` parses the leading
    axis back out for pre-rich-key ledger records.
    """
    fam = g.__dict__.get("_graph_family")
    if fam is None:
        fam = f"{_degree_bucket(g)}-{_weight_bucket(g)}-{_depth_bucket(g)}"
        g.__dict__["_graph_family"] = fam
    return fam


def family_fallbacks(family: str) -> tuple[str, ...]:
    """Ledger lookup order for a family key.

    The rich ``<deg>-<wt>-<depth>`` family first, then its leading degree
    bucket — which IS the whole family name records carried before the
    weight/depth axes existed — so a ledger written by an older process
    keeps routing traffic instead of forcing a re-probe.
    """
    coarse = family.split("-", 1)[0]
    return (family,) if coarse == family else (family, coarse)


@dataclasses.dataclass(frozen=True)
class EngineCandidate:
    """One engine configuration the portfolio may route a workload to."""

    policy: str  # policy spec ("in|out", "delta", ...)
    layout: str  # "padded" | "sliced"
    delta: float | None = None  # absolute bucket width override (delta only)
    delta_scale: float | None = None  # x default_delta(g): the graph-relative
    #   form a Delta-grid needs — an absolute width only means something for
    #   one weight distribution, a scale sweeps around the Meyer-Sanders
    #   default on every family

    @property
    def spec(self) -> str:
        return P.canonical_spec(self.policy)

    @property
    def ledger_policy(self) -> str:
        """The policy segment of the portfolio ledger key.

        Delta-grid members must not collide in the ledger, so the bucket
        override is part of the name; the no-override spelling stays the
        bare spec, keeping every pre-grid ledger record addressable.
        """
        if self.delta is not None:
            return f"{self.spec}@d{self.delta:g}"
        if self.delta_scale is not None:
            return f"{self.spec}@x{self.delta_scale:g}"
        return self.spec

    def resolve_delta(self, g: Graph) -> float | None:
        """The absolute bucket width this candidate runs ``g`` with."""
        if self.delta is not None:
            return float(self.delta)
        if self.delta_scale is not None:
            return float(self.delta_scale) * default_delta(g)
        return None  # policy default (default_delta) downstream


DEFAULT_CANDIDATES: tuple[EngineCandidate, ...] = (
    EngineCandidate("instatic|outstatic", "padded"),
    EngineCandidate("in|out", "padded"),
    EngineCandidate("in|out", "sliced"),
    EngineCandidate("delta", "padded"),
    EngineCandidate("delta", "sliced"),
    # Delta-grid around the Meyer-Sanders default (delta's strong layout):
    # bucket width steers the light/heavy phase split, and the best point
    # is a measured property of the family, not a closed form
    EngineCandidate("delta", "sliced", delta_scale=0.5),
    EngineCandidate("delta", "sliced", delta_scale=2.0),
    EngineCandidate("delta", "sliced", delta_scale=4.0),
)


def _attribution_totals(result, spec: str) -> dict[str, int]:
    """Sum the harvested ``settle_attribution`` ring over lanes and phases,
    restricted to the policy's share terms (criterion members, or
    light/heavy for delta — the bucket-id gauge is not summable)."""
    if result.settle_attribution is None:
        return {}
    pol = P.policy_for(spec)
    terms = pol.attribution_terms()
    share = set(pol.share_terms())
    attr = np.asarray(result.settle_attribution)  # (B, trace_len, T)
    return {
        t: int(attr[:, :, k].sum())
        for k, t in enumerate(terms)
        if t in share
    }


def measure_portfolio(
    g: Graph,
    *,
    lanes: int = 8,
    candidates: tuple[EngineCandidate, ...] = DEFAULT_CANDIDATES,
    ledger=None,
    use_pallas: bool = False,
    registry=None,
    repeats: int = 2,
) -> dict[tuple[str, str], dict]:
    """Probe every candidate on ``g`` and record measured entries.

    Each candidate solves the same ``lanes``-source batch twice: once with
    telemetry (doubles as compile warmup; yields phase counts and the
    policy's settle-attribution shares) and then timed without telemetry
    (median of ``repeats``). Entries land in the tuning ledger under
    :func:`~repro.kernels.config.portfolio_ledger_key` so later processes
    can route without re-probing; returns (ledger_policy, layout) -> entry
    (Delta-grid members carry their bucket override in the policy name).
    """
    from repro.kernels import config as kcfg
    from repro.obs.timer import timed

    if ledger is None:
        ledger = kcfg.global_ledger()
    family = graph_family(g)
    sources = (np.arange(lanes, dtype=np.int64) * 7919) % g.n
    out: dict[tuple[str, str], dict] = {}
    for cand in candidates:
        spec = cand.spec
        pol = P.policy_for(spec)
        kw: dict = {"criterion": spec, "layout": cand.layout,
                    "use_pallas": use_pallas}
        delta_eff = None
        if pol.uses_delta:
            delta_eff = cand.resolve_delta(g)
            kw["delta"] = delta_eff  # None -> default_delta(g) downstream
        probe = run_phased_static_batch(
            g, sources, trace_len=pol.phase_cap(g.n), telemetry=True, **kw
        )
        jax.block_until_ready(probe.dist)

        def solve(kw=kw):
            return jax.block_until_ready(
                run_phased_static_batch(g, sources, **kw).dist
            )

        # the telemetry probe compiled a *different* program (rings on),
        # so warm the timed one explicitly — timed() has no implicit warmup
        solve()
        wall_s, _ = timed(solve, repeats=repeats)
        entry = kcfg.record_portfolio(
            ledger, family, lanes, cand.ledger_policy, cand.layout,
            wall_s=wall_s,
            phases=int(np.asarray(probe.phases).sum()),
            queries=lanes,
            delta=delta_eff,
            attribution=_attribution_totals(probe, spec),
        )
        out[(cand.ledger_policy, cand.layout)] = entry
        if registry is not None:
            registry.gauge(
                f"portfolio.qps.{cand.ledger_policy}.{cand.layout}",
                "measured queries/s for one portfolio candidate",
            ).set(entry["qps"])
    return out


def pick_engine(
    family: str,
    lanes: int,
    candidates: tuple[EngineCandidate, ...] = DEFAULT_CANDIDATES,
    ledger=None,
) -> EngineCandidate:
    """The measured-best candidate for (family, lanes) from the ledger.

    Ranks by recorded qps over the candidates that have entries, reading
    the rich family key first and falling back to its pre-rich coarse
    degree bucket (:func:`family_fallbacks`); with no entries at all the
    first candidate (the paper's default criterion) is the safe fallback —
    routing never blocks on a probe.
    """
    from repro.kernels import config as kcfg

    if ledger is None:
        ledger = kcfg.global_ledger()
    entries: dict = {}
    for fam in family_fallbacks(family):
        entries = kcfg.portfolio_entries(ledger, fam, lanes)
        if entries:
            break
    best, best_qps = None, -1.0
    for cand in candidates:
        entry = entries.get((cand.ledger_policy, cand.layout))
        if entry is not None and entry.get("qps", 0.0) > best_qps:
            best, best_qps = cand, float(entry["qps"])
    return best if best is not None else candidates[0]


class PortfolioBackend:
    """An :class:`EngineBackend` that picks its engine from the ledger.

    At construction it resolves ``graph_family(g)``, consults the tuning
    ledger's portfolio records for that (family, lanes) and instantiates
    the measured-best policy x layout as an inner :class:`StaticBackend`
    (``probe=True`` — or an empty ledger — runs :func:`measure_portfolio`
    first, so the first server against a new family pays one probe and
    every later one routes from the recorded entries). All five protocol
    methods delegate, so the scheduler sees an ordinary backend whose
    ``criterion`` reflects the routed policy.
    """

    def __init__(self, g: Graph, lanes_hint: int = 8,
                 candidates: tuple[EngineCandidate, ...] = DEFAULT_CANDIDATES,
                 ledger=None, use_pallas: bool = False, probe: bool = False,
                 registry=None, point_queries: bool = False):
        from repro.kernels import config as kcfg

        if not candidates:
            raise ValueError("candidates must be non-empty")
        if ledger is None:
            ledger = kcfg.global_ledger()
        self.family = graph_family(g)
        self.lanes_hint = int(lanes_hint)
        if probe or not any(
            kcfg.portfolio_entries(ledger, fam, self.lanes_hint)
            for fam in family_fallbacks(self.family)
        ):
            measure_portfolio(
                g, lanes=self.lanes_hint, candidates=candidates,
                ledger=ledger, use_pallas=use_pallas, registry=registry,
            )
        self.choice = pick_engine(self.family, self.lanes_hint, candidates,
                                  ledger)
        self.inner = StaticBackend(
            g, use_pallas=use_pallas, layout=self.choice.layout,
            policy=self.choice.policy, delta=self.choice.resolve_delta(g),
            point_queries=point_queries,
        )
        self.g = g
        self.criterion = self.inner.criterion
        self.point_queries = self.inner.point_queries

    @property
    def n(self) -> int:
        return self.inner.n

    def init(self, lanes: int) -> BatchState:
        return self.inner.init(lanes)

    def step(self, state, k_phases, *, stop_on_lane_finish=True, donate=False):
        return self.inner.step(state, k_phases,
                               stop_on_lane_finish=stop_on_lane_finish,
                               donate=donate)

    def reset_lanes(self, state, sources, *, donate=False, targets=None):
        return self.inner.reset_lanes(state, sources, donate=donate,
                                      targets=targets)

    def peek(self, state):
        return self.inner.peek(state)

    def take_row(self, state, lane):
        return self.inner.take_row(state, lane)
