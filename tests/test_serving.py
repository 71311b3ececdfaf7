"""Tests for the continuous-batching serving subsystem (``repro.serving``).

Centerpiece: the acceptance property — for *any* interleaving of arrivals,
admissions, lane assignments, and chunk boundaries, every completed request
carries distances bit-identical to a standalone ``run_phased_static`` solve
of its source (and identical per-query phase counts for engine-served
requests). Randomised over graphs, arrival patterns, lane counts, and chunk
lengths with seeded rngs.
"""
import json

import jax
import numpy as np
import pytest

from repro.core.static_engine import run_phased_static
from repro.graphs import grid_road, uniform_gnp, webgraph
from repro.serving import (
    ArrivalQueue,
    ContinuousBatcher,
    DistCache,
    ServingMetrics,
    ShardedBackend,
    StaticBackend,
    graph_key,
)

BACKENDS = ["static", "sharded"]


def _make_backend(kind: str, g):
    """Backend under test; 'sharded' runs the mesh stepper on a 1-device
    mesh so the adapter parity is exercised in-process (the 8-fake-device
    variant lives in tests/test_distributed_batch.py)."""
    if kind == "static":
        return StaticBackend(g)
    mesh = jax.make_mesh((jax.device_count(),), ("v",))
    return ShardedBackend(g, mesh, ("v",))

GRAPHS = {
    "gnp": lambda: uniform_gnp(180, 9 / 180, seed=31),
    "grid": lambda: grid_road(11, 9, seed=32),
    "web": lambda: webgraph(160, 6, seed=33),
}


@pytest.fixture(scope="module", params=list(GRAPHS))
def graph(request):
    return request.param, GRAPHS[request.param]()


@pytest.fixture(scope="module")
def solo_cache():
    # keyed by CONTENT hash, never id(): the memo outlives the graphs and a
    # GC'd graph's id can be recycled by a fresh one, silently returning a
    # different graph's rows (observed as a rare order-dependent flake)
    memo = {}

    def solo(g, s):
        key = (graph_key(g), int(s))
        if key not in memo:
            memo[key] = run_phased_static(g, int(s))
        return memo[key]

    return solo


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_property_bit_exact_under_random_arrivals(graph, solo_cache, seed):
    """Random arrival bursts x random lane counts x random chunk lengths."""
    name, g = graph
    rng = np.random.default_rng(100 + seed)
    lanes = int(rng.integers(1, 6))
    k = int(rng.integers(1, 12))
    n_q = int(rng.integers(8, 20))
    sources = rng.integers(0, g.n, n_q)
    server = ContinuousBatcher(g, lanes=lanes, phases_per_step=k)

    submitted = 0
    while submitted < n_q or not server.idle:
        burst = int(rng.integers(0, 4))
        for s in sources[submitted:submitted + burst]:
            server.submit(int(s))
        submitted = min(submitted + burst, n_q)
        server.step()
    assert len(server.completed) == n_q

    for req in server.completed:
        solo = solo_cache(g, req.source)
        np.testing.assert_array_equal(
            req.dist, np.asarray(solo.dist),
            err_msg=f"{name}: req {req.req_id} (src {req.source}) diverged")
        # no cache in this server: every request is engine-served, so the
        # per-query phase structure must match a standalone solve exactly
        assert not req.cache_hit and not req.coalesced
        assert int(req.phases) == int(solo.phases), (name, req.req_id)


def test_order_and_lane_assignment_is_arrival_fifo(graph):
    name, g = graph
    server = ContinuousBatcher(g, lanes=2, phases_per_step=4)
    for s in (0, 1, 2, 3):
        server.submit(s)
    server.drain(max_steps=2000)
    # FIFO admission: first two requests got lanes 0/1 in order
    first_two = sorted(server.completed, key=lambda r: r.req_id)[:2]
    assert [r.lane for r in first_two] == [0, 1]
    assert all(r.dist is not None for r in server.completed)


def test_duplicates_coalesce_and_then_hit_cache(graph, solo_cache):
    name, g = graph
    cache = DistCache(capacity=16)
    server = ContinuousBatcher(g, lanes=2, phases_per_step=4, cache=cache)
    for s in (5, 5, 7, 5):
        server.submit(int(s) % g.n)
    done = server.drain(max_steps=2000)
    engine = [r for r in done if not r.cache_hit and not r.coalesced]
    dupes = [r for r in done if r.cache_hit or r.coalesced]
    # only the first 5 and the 7 burn lanes; both duplicate 5s ride along
    # (coalesced onto the in-flight lane) or hit the cache, never a lane
    assert len(engine) == 2 and len(dupes) == 2
    solo = solo_cache(g, 5 % g.n)
    for r in done:
        if r.source == 5 % g.n:
            np.testing.assert_array_equal(r.dist, np.asarray(solo.dist))
    for r in dupes:
        assert r.phases == 0 and r.lane is None
    # a fresh duplicate after completion is a genuine cache hit
    server.submit(5 % g.n)
    (late,) = server.drain(max_steps=2000)
    assert late.cache_hit and late.phases == 0
    np.testing.assert_array_equal(late.dist, np.asarray(solo.dist))
    assert cache.hits == len([r for r in [*done, late] if r.cache_hit])
    # one lookup per classification: every non-hit classification is a miss
    assert cache.misses == len([r for r in [*done, late] if not r.cache_hit])


def test_cache_hit_served_even_when_all_lanes_busy(graph):
    name, g = graph
    cache = DistCache(capacity=8)
    server = ContinuousBatcher(g, lanes=1, phases_per_step=1, cache=cache)
    server.submit(3)
    server.drain(max_steps=2000)  # source 3 now cached
    server.submit(8 % g.n)  # occupies the only lane
    server.step()
    assert server.busy_lanes == 1
    # an engine-bound request queues first, the cached duplicate behind it:
    # the hit must overtake (it needs no lane) instead of waiting in FIFO
    blocked = server.submit(9 % g.n)
    server.submit(3)
    done = server.step()
    hits = [r for r in done if r.cache_hit]
    assert len(hits) == 1 and hits[0].source == 3  # did not wait for the lane
    assert blocked.t_completed is None  # engine-bound one still queued/live
    server.drain(max_steps=2000)
    assert blocked.t_completed is not None  # and is not starved


def test_completed_retention_is_bounded(graph):
    name, g = graph
    server = ContinuousBatcher(g, lanes=2, retain_completed=3)
    for s in range(5):
        server.submit(s)
    done = server.drain(max_steps=2000)
    assert len(done) == 5  # delivery path is unaffected by retention
    assert len(server.completed) == 3  # only the newest survive


def test_cache_rows_are_readonly_and_lru_evicts():
    c = DistCache(capacity=2)
    c.put("g", "crit", 1, np.ones(4))
    c.put("g", "crit", 2, np.full(4, 2.0))
    assert c.get("g", "crit", 1) is not None  # refresh 1 -> 2 becomes LRU
    c.put("g", "crit", 3, np.full(4, 3.0))
    assert c.evictions == 1
    assert c.get("g", "crit", 2) is None  # evicted
    assert c.get("g", "crit", 1) is not None
    assert c.get("g", "crit", 3) is not None
    row = c.get("g", "crit", 1)
    with pytest.raises(ValueError):
        row[0] = 99.0
    assert len(c) == 2
    with pytest.raises(ValueError):
        DistCache(capacity=0)


def test_graph_key_is_content_based():
    g1 = uniform_gnp(60, 0.1, seed=5)
    g2 = uniform_gnp(60, 0.1, seed=5)  # same content, distinct instance
    g3 = uniform_gnp(60, 0.1, seed=6)
    assert graph_key(g1) == graph_key(g2)
    assert graph_key(g1) != graph_key(g3)
    assert graph_key(g1) == graph_key(g1)  # memoised path


def test_cache_does_not_leak_across_graphs():
    g1 = uniform_gnp(60, 0.1, seed=5)
    g3 = uniform_gnp(60, 0.1, seed=6)
    cache = DistCache()
    s1 = ContinuousBatcher(g1, lanes=1, cache=cache)
    s1.submit(0)
    s1.drain(max_steps=500)
    s3 = ContinuousBatcher(g3, lanes=1, cache=cache)
    s3.submit(0)
    done = s3.drain(max_steps=500)
    assert not done[0].cache_hit  # different graph content -> no hit
    solo = run_phased_static(g3, 0)
    np.testing.assert_array_equal(done[0].dist, np.asarray(solo.dist))


def test_cache_does_not_leak_across_criteria():
    """Poisoned-cache double-serve: two servers over the SAME graph but
    different criteria share a cache object. A row poisoned under one
    criterion's key must never be served by the other — with pluggable
    criteria the answers only coincide in exact arithmetic, and a shared
    entry would silently break the bitwise engine-answer contract."""
    g = uniform_gnp(120, 8 / 120, seed=7)
    cache = DistCache()
    a = ContinuousBatcher(g, lanes=1, cache=cache)  # default criterion
    a.submit(3)
    a.drain(max_steps=500)
    assert (graph_key(g), a.criterion, 3) in cache
    # poison the default-criterion entry so any cross-criterion hit is loud
    # (a well-formed entry with a matching checksum: this test is about key
    # confinement, not the integrity machinery — see test_resilience.py)
    import zlib

    from repro.serving.cache import _Entry

    poisoned = np.full(g.n, -1.0, np.float32)
    cache._d[(graph_key(g), a.criterion, 3)] = _Entry(
        poisoned, zlib.crc32(poisoned.tobytes()), 0.0)
    b = ContinuousBatcher(g, lanes=1, cache=cache, criterion="in|out")
    b.submit(3)
    done = b.drain(max_steps=500)
    assert not done[0].cache_hit  # different criterion -> not a hit
    solo = run_phased_static(g, 3, criterion="in|out")
    np.testing.assert_array_equal(done[0].dist, np.asarray(solo.dist))
    # and the poisoned row stayed confined to its own key
    assert cache.get(graph_key(g), b.criterion, 3) is not None
    np.testing.assert_array_equal(
        cache.get(graph_key(g), a.criterion, 3), poisoned)


@pytest.mark.parametrize("kind", BACKENDS)
def test_backend_criterion_is_plumbed_end_to_end(kind):
    """A server configured with a strengthened criterion must deliver rows
    bit-exact vs the standalone engine under that criterion, with that
    criterion's (smaller) phase counts."""
    g = uniform_gnp(150, 8 / 150, seed=44)
    if kind == "static":
        backend = StaticBackend(g, criterion="insimple|outsimple")
    else:
        mesh = jax.make_mesh((jax.device_count(),), ("v",))
        backend = ShardedBackend(g, mesh, ("v",), criterion="insimple|outsimple")
    assert backend.criterion == "insimple|outsimple"
    server = ContinuousBatcher(g, lanes=2, phases_per_step=5, backend=backend,
                               cache=DistCache(capacity=8))
    for s in (0, 7, 0, 149):
        server.submit(s)
    done = server.drain(max_steps=2000)
    for req in done:
        solo = run_phased_static(g, req.source, criterion="insimple|outsimple")
        np.testing.assert_array_equal(req.dist, np.asarray(solo.dist),
                                      err_msg=f"{kind}: src {req.source}")
        if not (req.cache_hit or req.coalesced):
            assert int(req.phases) == int(solo.phases)
    # criterion spelling is canonicalised; a mismatched override is rejected
    assert ContinuousBatcher(
        g, backend=StaticBackend(g, criterion="out|in"), criterion="in|out"
    ).criterion == "in|out"
    with pytest.raises(ValueError, match="disagrees"):
        ContinuousBatcher(g, backend=backend, criterion="in|out")
    with pytest.raises(ValueError, match="oracle"):
        StaticBackend(g, criterion="oracle")


def test_metrics_report_is_json_and_consistent(graph):
    name, g = graph
    server = ContinuousBatcher(g, lanes=3, phases_per_step=5,
                               cache=DistCache(capacity=8))
    srcs = [0, 1, 0, 2, 1, 0]
    for s in srcs:
        server.submit(s)
    server.drain(max_steps=2000)
    rep = json.loads(server.metrics.to_json())
    assert rep["queries_completed"] == len(srcs)
    assert rep["cache_hits"] == sum(r.cache_hit for r in server.completed)
    assert rep["coalesced"] == sum(r.coalesced for r in server.completed)
    assert 0.0 < rep["lane_occupancy"] <= 1.0
    assert rep["latency_p50_s"] <= rep["latency_p99_s"] <= rep["latency_max_s"] + 1e-12
    assert rep["throughput_qps"] > 0
    assert rep["steps"] == server.metrics.steps >= 1
    assert rep["phases_per_query_mean"] > 0
    assert rep["engine_trips"] == int(server.state.trips)


@pytest.mark.parametrize("kind", BACKENDS)
def test_backend_adapters_share_scheduler_semantics(kind, solo_cache):
    """The adapter acceptance test: the same trace served through either
    engine backend yields identical admission/coalescing/cache behaviour and
    bit-exact per-request distances vs standalone solves."""
    g = uniform_gnp(160, 8 / 160, seed=41)
    backend = _make_backend(kind, g)
    server = ContinuousBatcher(g, lanes=3, phases_per_step=5,
                               cache=DistCache(capacity=16), backend=backend)
    trace = [9, 9, 0, 158, 9, 0, 77]
    for s in trace:
        server.submit(s)
    done = server.drain(max_steps=2000)
    assert len(done) == len(trace)
    for req in done:
        solo = solo_cache(g, req.source)
        np.testing.assert_array_equal(
            req.dist, np.asarray(solo.dist),
            err_msg=f"{kind}: req {req.req_id} (src {req.source})")
        assert req.dist.shape == (g.n,)  # sharded padding never leaks out
        if not (req.cache_hit or req.coalesced):
            assert int(req.phases) == int(solo.phases), (kind, req.req_id)
    # identical dedup classification regardless of backend: the first 9 and
    # the first 0 burn lanes, later duplicates coalesce or hit the cache
    engine_served = [r for r in done if not r.cache_hit and not r.coalesced]
    assert sorted(r.source for r in engine_served) == [0, 9, 77, 158], kind
    rep = json.loads(server.metrics.to_json())
    assert rep["queries_completed"] == len(trace)
    assert rep["engine_trips"] == int(server.state.trips)
    # fresh duplicates after completion are cache hits on both backends
    server.submit(9)
    (late,) = server.drain(max_steps=2000)
    assert late.cache_hit
    np.testing.assert_array_equal(late.dist, np.asarray(solo_cache(g, 9).dist))


@pytest.mark.parametrize("kind", BACKENDS)
def test_completed_rows_survive_donated_engine_reuse(kind, solo_cache):
    """Copy-before-donate discipline (the harvest-then-donate hazard).

    ``step``/``reset_lanes`` with ``donate=True`` may invalidate the engine
    state's old buffers, so the scheduler must hand out host-owned row
    copies. Force donation on (even on CPU, where XLA ignores it, this pins
    the call path) and check rows delivered earlier stay bit-identical while
    the donated state is mutated by later queries reusing the same lanes."""
    g = grid_road(9, 9, seed=42)
    server = ContinuousBatcher(g, lanes=2, phases_per_step=4,
                               backend=_make_backend(kind, g), donate=True)
    assert server._donate  # the override actually arms donation
    for s in (0, 40, 80):
        server.submit(s)
    first = server.drain(max_steps=2000)
    snapshots = [(r, r.dist.copy()) for r in first]
    # second wave re-uses (and donate-resets) every lane several times
    for s in (17, 63, 5, 71):
        server.submit(s)
    server.drain(max_steps=2000)
    for req, snap in snapshots:
        assert isinstance(req.dist, np.ndarray)
        assert not req.dist.flags.writeable  # mutation must fail loudly
        np.testing.assert_array_equal(req.dist, snap,
                                      err_msg=f"{kind}: src {req.source}")
        np.testing.assert_array_equal(
            req.dist, np.asarray(solo_cache(g, req.source).dist))


def test_arrival_queue_fifo_and_latency_fields():
    q = ArrivalQueue()
    a = q.push(3, t_arrival=1.0)
    b = q.push(4, t_arrival=2.0)
    assert len(q) == 2 and q.peek() is a
    assert q.pop() is a and q.pop() is b
    assert len(q) == 0 and not q
    assert a.latency is None and a.queue_wait is None
    a.t_admitted, a.t_completed = 1.5, 3.0
    assert a.queue_wait == 0.5 and a.latency == 2.0
    assert q.total_enqueued == 2


def test_submit_validates_source(graph):
    name, g = graph
    server = ContinuousBatcher(g, lanes=1)
    with pytest.raises(ValueError, match="source"):
        server.submit(g.n)
    with pytest.raises(ValueError, match="source"):
        server.submit(-1)
    with pytest.raises(ValueError, match="lanes"):
        ContinuousBatcher(g, lanes=0)
    with pytest.raises(ValueError, match="phases_per_step"):
        ContinuousBatcher(g, lanes=1, phases_per_step=0)


def test_metrics_empty_report():
    rep = ServingMetrics(lanes=4).report()
    json.dumps(rep)
    assert rep["queries_completed"] == 0
    assert rep["throughput_qps"] == 0.0
    assert rep["lane_occupancy"] == 0.0


def test_ell_conversion_is_memoised_per_graph():
    from repro.core.graph import to_ell_in

    g = uniform_gnp(80, 0.1, seed=9)
    a = to_ell_in(g)
    b = to_ell_in(g)
    assert a[0] is b[0] and a[1] is b[1]  # cache hit returns same arrays
    c = to_ell_in(g, pad_multiple=16)  # different layout -> distinct entry
    assert c[0] is not a[0] and c[0].shape[1] % 16 == 0


def _entry_point_calls(g):
    """One call through every serving entry point at its defaults."""
    from repro.core.static_engine import run_phased_static_batch
    from repro.kernels.config import TuningLedger
    from repro.serving import PointBackend, PortfolioBackend
    from repro.serving.backends import EngineCandidate

    yield lambda: run_phased_static(g, 0)
    yield lambda: run_phased_static_batch(g, [0, 3])
    yield lambda: PointBackend(g).query(0, g.n - 1)
    for kw in ({}, {"layout": "sliced", "criterion": "in|out"},
               {"policy": "delta"}):
        def serve(kw=kw):
            server = ContinuousBatcher(
                g, lanes=2, backend=StaticBackend(g, point_queries=True, **kw),
                point_queries=True)
            server.submit(0)
            server.submit(1, target=g.n - 1)
            return server.drain(max_steps=1000)
        yield serve
    yield lambda: PortfolioBackend(
        g, lanes_hint=2, ledger=TuningLedger(), probe=True,
        candidates=(EngineCandidate("in|out", "sliced"),))


def test_serving_entry_points_reach_no_pallas_kernel_by_default():
    """The served path is the XLA stepper: no entry point traces a Pallas
    kernel unless the caller passes ``use_pallas=True`` (the control)."""
    from repro.analysis.kernel_audit import capture_pallas_calls

    g = grid_road(5, 6, seed=1)
    calls: list = []
    jax.clear_caches()  # force a fresh trace of every program below
    try:
        with capture_pallas_calls(calls):
            for call in _entry_point_calls(g):
                call()
            assert calls == []
            run_phased_static(g, 0, use_pallas=True, max_phases=1)
            assert calls, "the recorder saw no kernel on the opt-in path"
    finally:
        jax.clear_caches()  # drop programs traced with the fake kernels


class _InnerProxy:
    """A wrapper that holds its backend as ``inner`` (as the portfolio and
    timing proxies do) and exposes no adjacency of its own."""

    def __init__(self, inner):
        self.inner = inner
        self.g = inner.g
        self.criterion = inner.criterion
        self.point_queries = inner.point_queries

    def init(self, lanes):
        return self.inner.init(lanes)


@pytest.mark.parametrize("layout,wrap", [
    ("sliced", False), ("sliced", True), ("padded", False)])
def test_batcher_gauges_the_sliced_merge_plan(layout, wrap):
    """With ``obs``, a server over a degree-sliced backend records the merge
    plan's size once, at construction; a padded backend records neither."""
    from repro.graphs import kronecker
    from repro.obs import Observability

    g = kronecker(7, seed=21)
    backend = StaticBackend(g, layout=layout)
    obs = Observability.enabled()
    ContinuousBatcher(g, lanes=2, obs=obs,
                      backend=_InnerProxy(backend) if wrap else backend)
    names = set(obs.registry.names())
    if layout == "padded":
        assert not {"layout.split_vertices", "layout.merge_slots"} & names
        return
    assert backend.ell.split_vertices > 0  # the hubs split
    assert (obs.registry.get("layout.split_vertices").value
            == backend.ell.split_vertices)
    assert (obs.registry.get("layout.merge_slots").value
            == backend.ell.merge_slots
            == g.n + backend.ell.merge_heavy.size)
