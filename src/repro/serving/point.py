"""Point-to-point (s->t) queries: early-exit lanes and bidirectional search.

:func:`run_point_to_point` / :class:`PointBackend` answer single-pair
shortest-path queries against the batched phase stepper (DESIGN.md
Sec. 13). The *forward* lane is an ordinary target lane — it runs the
engine from ``source`` with ``BatchState.target = target``, so it inherits
both target optimisations: the lane early-exits the phase its target
settles, and the criterion policies prune relaxations past the target's
tentative distance. Its ``dist[target]`` is bit-exact against a full
``run_phased`` solve (the pruning-soundness argument lives with
``repro.kernels.ops._bound_gate``).

*Bidirectional* mode couples a second lane: the same engine run from
``target`` on the memoised transpose graph, whose labels satisfy
``d_b[v] == dist_g(v -> t)``. The two lanes share a best-seen meeting
bound ``mu = min_v fl(d_f[v] + d_b[v])`` — every tentative label is the
f32 length of a real path, so each ``mu`` candidate upper-bounds the exact
s->t distance. The bound is used for two *bitwise-safe* purposes only:

  * **backward retirement** — once the backward fringe's minimum distance
    passes ``mu``, no further backward phase can improve the bound, so the
    backward lane stops paying for phases;
  * **unreachability certification** — if the backward lane exhausts
    ``target``'s in-ball without reaching ``source``, no s->t path exists
    and the query answers ``inf`` immediately, while the forward lane
    alone would have had to flood ``source``'s entire out-component (its
    early exit never fires on an unreachable target).

``mu`` is deliberately NOT used to prune the forward lane or as the
answer: ``fl(d_f[v] + d_b[v])`` associates the path sum differently from
the forward left-to-right evaluation that defines the engine's bitwise
contract, so it can round *below* the forward-final ``dist[t]`` and would
break bit-exactness (DESIGN.md Sec. 13 spells out the rounding argument).
The authoritative answer is always the forward lane's ``dist[target]``.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.delta_stepping import default_delta
from repro.core.graph import (
    Graph,
    to_ell_in,
    to_ell_in_sliced,
    to_ell_out,
    to_ell_out_sliced,
    transpose,
)
from repro.core.static_engine import (
    DEFAULT_CRITERION,
    init_batch_state,
    step_batch,
)
from repro.serving.backends import _serving_policy

INF = float("inf")


def transpose_memo(g: Graph) -> Graph:
    """``transpose(g)``, memoised on the graph instance.

    The backward lane's adjacency; memoised so a server answering many
    s->t queries against one graph builds the reverse ELL exactly once.
    """
    tr = g.__dict__.get("_transpose")
    if tr is None:
        tr = transpose(g)
        g.__dict__["_transpose"] = tr
    return tr


@jax.jit
def _meet(df, db):
    """Best meeting bound over the current labels: ``(mu, argmin vertex)``.

    ``fl(df[v] + db[v])`` concatenates a real s->v path with a real v->t
    path, so every finite entry upper-bounds the exact s->t distance.
    """
    tot = df[0] + db[0]
    v = jnp.argmin(tot)
    return tot[v], v


@jax.jit
def _lane_stats(state):
    """One device read per lane per chunk: (live, phases, min fringe d)."""
    fringe = state.status[0] == 1
    return (
        jnp.any(fringe),
        state.phases[0],
        jnp.min(jnp.where(fringe, state.dist[0], jnp.inf)),
    )


@dataclasses.dataclass(frozen=True)
class PointResult:
    """One answered s->t query.

    ``distance`` (== ``dist[target]``) is bit-exact vs the full-solve
    ``run_phased`` row; the rest of ``dist`` is partial — goal-directed
    pruning only guarantees labels at or nearer than the target.
    """

    source: int
    target: int
    distance: float
    dist: np.ndarray  # forward lane's (n,) row; only dist[target] guaranteed
    phases_forward: int
    phases_backward: int  # 0 in forward-only mode
    mu: float  # best meeting bound seen (upper bound on distance)
    meeting_vertex: int | None
    unreachable_certified: bool  # backward lane proved no s->t path exists


class PointBackend:
    """Reusable s->t query engine over one graph (forward + backward views).

    Construction resolves the policy/layout exactly like
    :class:`~repro.serving.backends.StaticBackend`; the backward (transpose)
    adjacency is built lazily on the first bidirectional query and memoised,
    so forward-only use never pays for it. ``query`` answers one (s, t)
    pair; ``run_point_to_point`` wraps a per-graph memoised instance.
    """

    def __init__(self, g: Graph, *, criterion: str = DEFAULT_CRITERION,
                 policy: str | None = None, layout: str = "padded",
                 use_pallas: bool = False, bidirectional: bool = True,
                 phases_per_chunk: int = 8):
        spec = policy if policy is not None else criterion
        pol = _serving_policy(spec)
        if layout not in ("padded", "sliced"):
            raise ValueError(
                f"layout must be 'padded' or 'sliced'; got {layout!r}"
            )
        if phases_per_chunk < 1:
            raise ValueError(
                f"phases_per_chunk must be >= 1; got {phases_per_chunk}"
            )
        self.g = g
        self.layout = layout
        self.criterion = pol.spec
        self._pol = pol
        sliced = layout == "sliced"
        self.ell = to_ell_in_sliced(g) if sliced else to_ell_in(g)
        self.ell_out = None
        if pol.needs_out_adjacency:
            self.ell_out = to_ell_out_sliced(g) if sliced else to_ell_out(g)
        self.use_pallas = bool(use_pallas)
        self.bidirectional = bool(bidirectional)
        self.phases_per_chunk = int(phases_per_chunk)
        # same bucket width both directions: the transpose has the same
        # weight multiset, so default_delta agrees
        self.delta = default_delta(g) if pol.uses_delta else None
        self._bwd_views = None  # (gt, ell, ell_out) built on first use

    def _backward(self):
        if self._bwd_views is None:
            gt = transpose_memo(self.g)
            sliced = self.layout == "sliced"
            ell = to_ell_in_sliced(gt) if sliced else to_ell_in(gt)
            ell_out = None
            if self._pol.needs_out_adjacency:
                ell_out = to_ell_out_sliced(gt) if sliced else to_ell_out(gt)
            self._bwd_views = (gt, ell, ell_out)
        return self._bwd_views

    def query(self, source: int, target: int) -> PointResult:
        """Answer one s->t query; ``distance`` is bit-exact vs run_phased."""
        n = self.g.n
        source, target = int(source), int(target)
        for name, v in (("source", source), ("target", target)):
            if not 0 <= v < n:
                raise ValueError(f"{name} must be in [0, {n}); got {v}")
        fwd = init_batch_state(
            self.g, np.array([source], np.int32), criterion=self.criterion,
            delta=self.delta, targets=np.array([target], np.int32),
        )
        bwd = bwd_graph = bwd_ell = bwd_ell_out = None
        if self.bidirectional:
            bwd_graph, bwd_ell, bwd_ell_out = self._backward()
            bwd = init_batch_state(
                bwd_graph, np.array([target], np.int32),
                criterion=self.criterion, delta=self.delta,
                targets=np.array([source], np.int32),
            )
        k = self.phases_per_chunk
        cap = self._pol.phase_cap(n)
        mu, meet_v = INF, None
        phases_b = 0
        bwd_live = bwd is not None
        unreachable = False
        while True:
            fwd = step_batch(
                self.g, fwd, k, ell=self.ell, use_pallas=self.use_pallas,
                stop_on_lane_finish=True, ell_out=self.ell_out,
            )
            f_live, f_phases, _ = (np.asarray(x) for x in _lane_stats(fwd))
            if not f_live:
                break
            if bwd_live:
                bwd = step_batch(
                    bwd_graph, bwd, k, ell=bwd_ell,
                    use_pallas=self.use_pallas, stop_on_lane_finish=True,
                    ell_out=bwd_ell_out,
                )
                b_live, b_phases, b_min = (
                    np.asarray(x) for x in _lane_stats(bwd)
                )
                phases_b = int(b_phases)
                m, v = _meet(fwd.dist, bwd.dist)
                if float(m) < mu:
                    mu, meet_v = float(m), int(v)
                if not b_live:
                    bwd_live = False
                    if float(np.asarray(bwd.dist[0, source])) == INF:
                        # the backward lane exhausted target's in-ball
                        # without reaching source (its own early exit only
                        # fires on a *finite* settle), so no s->t path
                        # exists — stop flooding the forward component
                        unreachable = True
                        break
                elif float(b_min) >= mu:
                    # no backward fringe vertex can improve mu any more;
                    # retire the lane, the forward lane owns the answer
                    bwd_live = False
            if int(f_phases) >= cap:
                raise RuntimeError(
                    f"s->t query exceeded the policy phase cap {cap}; "
                    "the engine should terminate within it on any input"
                )
        row = np.asarray(fwd.dist[0])
        return PointResult(
            source=source,
            target=target,
            distance=float(row[target]),
            dist=row,
            phases_forward=int(np.asarray(fwd.phases)[0]),
            phases_backward=phases_b,
            mu=mu,
            meeting_vertex=meet_v,
            unreachable_certified=unreachable,
        )


def run_point_to_point(
    g: Graph,
    source: int,
    target: int,
    *,
    criterion: str = DEFAULT_CRITERION,
    policy: str | None = None,
    layout: str = "padded",
    use_pallas: bool = False,
    bidirectional: bool = True,
    phases_per_chunk: int = 8,
) -> PointResult:
    """One-shot s->t query (memoises one :class:`PointBackend` per config).

    The backend is cached on the graph instance keyed by the resolved
    configuration, so repeated calls against one graph reuse the forward
    and transpose adjacency views and all compiled programs.
    """
    cache = g.__dict__.setdefault("_point_backends", {})
    spec = policy if policy is not None else criterion
    key = (spec, layout, bool(use_pallas), bool(bidirectional),
           int(phases_per_chunk))
    backend = cache.get(key)
    if backend is None:
        backend = PointBackend(
            g, criterion=criterion, policy=policy, layout=layout,
            use_pallas=use_pallas, bidirectional=bidirectional,
            phases_per_chunk=phases_per_chunk,
        )
        cache[key] = backend
    return backend.query(source, target)
