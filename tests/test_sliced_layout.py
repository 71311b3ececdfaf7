"""Degree-sliced ELL acceptance: any bucket boundaries / split thresholds
produce bit-identical engine results to the padded layout (f32 min is exact,
so slicing is a pure layout decision), plus the builders' structural
invariants and the memoisation satellites."""
import jax
import numpy as np
import pytest

from repro.core import run_phased
from repro.core.graph import (
    default_slice_boundaries,
    from_coo,
    out_degrees,
    to_ell_in,
    to_ell_in_sliced,
    to_ell_out_sliced,
)
from repro.core.static_engine import (
    harvest,
    init_batch_state,
    lanes_active,
    reset_lanes,
    run_phased_static_batch,
    step_batch,
)
from repro.graphs import grid_road, kronecker, uniform_gnp
from repro.kernels import ops
from repro.kernels.ell_relax_keys import _merge_parts
from repro.kernels.ops import gather_min_batch_sliced
from repro.kernels.ref import ell_gather_min_batch_ref

# the "property" sweep: bucket boundaries x split thresholds, including
# degenerate single-bucket and aggressive row-splitting configurations
LAYOUT_CASES = [
    (None, None),  # auto boundaries from the degree distribution
    ((8,), 8),  # single narrow bucket: every hub row splits
    ((8, 16), 16),
    ((8, 64), None),
    ((24,), 48),  # split wider than the bucket
]


@pytest.mark.parametrize("boundaries,split", LAYOUT_CASES)
@pytest.mark.parametrize("crit", ["instatic|outstatic", "in|out"])
def test_sliced_layouts_bit_identical_to_padded(boundaries, split, crit):
    g = kronecker(7, seed=21)  # skewed: splits actually happen
    srcs = np.asarray([0, 5, g.n - 1], np.int32)
    want = run_phased_static_batch(g, srcs, criterion=crit)
    ell = to_ell_in_sliced(g, boundaries=boundaries, split=split)
    ell_out = to_ell_out_sliced(g, boundaries=boundaries, split=split)
    got = run_phased_static_batch(g, srcs, criterion=crit, ell=ell,
                                  ell_out=ell_out)
    np.testing.assert_array_equal(np.asarray(got.dist), np.asarray(want.dist))
    np.testing.assert_array_equal(np.asarray(got.status), np.asarray(want.status))
    np.testing.assert_array_equal(np.asarray(got.phases), np.asarray(want.phases))
    np.testing.assert_array_equal(np.asarray(got.sum_fringe),
                                  np.asarray(want.sum_fringe))
    np.testing.assert_array_equal(np.asarray(got.relax_edges),
                                  np.asarray(want.relax_edges))


@pytest.mark.parametrize("boundaries,split", LAYOUT_CASES)
@pytest.mark.parametrize("side", ["in", "out"])
def test_merge_plan_heavy_table_and_first_gather(boundaries, split, side):
    """The two-level merge plan: one heavy row per split vertex listing
    exactly its slice-rows, and one ``merge_first`` entry per vertex that
    points at its only row, the +inf sentinel, or its heavy slot."""
    g = kronecker(7, seed=21)
    build = to_ell_in_sliced if side == "in" else to_ell_out_sliced
    se = build(g, boundaries=boundaries, split=split)
    rows = np.concatenate([np.asarray(s.rows) for s in se.slices])
    total = rows.size
    occ = np.bincount(rows, minlength=g.n)
    split_v = np.nonzero(occ > 1)[0]
    heavy = np.asarray(se.merge_heavy)
    first = np.asarray(se.merge_first)
    h, c = heavy.shape
    assert h == split_v.size == se.split_vertices
    assert c == max(int(occ.max()), 1)
    assert first.shape == (g.n,)
    assert se.merge_slots == g.n + h * c
    if boundaries == (8,):
        assert h > 0  # every hub splits
    for i, v in enumerate(split_v):
        pos = heavy[i][heavy[i] != total]
        assert sorted(pos.tolist()) == np.nonzero(rows == v)[0].tolist()
        assert first[v] == total + 1 + i
    single = np.nonzero(occ == 1)[0]
    np.testing.assert_array_equal(rows[first[single]], single)
    assert (first[occ == 0] == total).all()
    # the merge reads each vertex's min over its own rows, exactly
    vals = np.random.default_rng(3).random((2, total)).astype(np.float32)
    want = np.full((2, g.n), np.inf, np.float32)
    np.minimum.at(want, (slice(None), rows), vals)
    got = _merge_parts([vals], se.merge_heavy, se.merge_first, (2,))
    np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("make", [
    lambda: grid_road(11, 9, seed=55),
    lambda: uniform_gnp(200, 0.01, seed=7),
])
def test_merge_plan_without_split_vertices(make):
    """Where the largest degree fits the widest bucket no vertex splits:
    H = 0, the merge is the one ``(n,)`` gather, and the interpret
    megascan (which then carries no heavy table) agrees with the XLA form."""
    g = make()
    se = to_ell_in_sliced(g)
    assert se.split_vertices == 0 and se.merge_heavy.shape[0] == 0
    assert se.merge_slots == g.n
    vecs = np.random.default_rng(1).random((2, 3, g.n)).astype(np.float32)
    want = gather_min_batch_sliced(vecs, se)
    got = gather_min_batch_sliced(vecs, se, use_pallas=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    srcs = np.asarray([0, g.n - 1], np.int32)
    np.testing.assert_array_equal(
        np.asarray(run_phased_static_batch(g, srcs, ell=se).dist),
        np.asarray(run_phased_static_batch(g, srcs).dist))


@pytest.mark.parametrize("v", [1, 3])
@pytest.mark.parametrize("tiles", ["below", "one", "ragged"])
@pytest.mark.parametrize("width", [8, 32, 128, 512])
def test_tiled_gather_min_is_the_whole_gather_bit_for_bit(width, tiles, v):
    """A bucket's gather-min in row tiles of ``_tile_rows`` rows returns
    the one-gather oracle's rows bit for bit: below one tile, exactly one,
    and several with a ragged tail (sentinel-padded), with sentinel
    columns, an all-+inf lane in every vector and, for V = 3, an all-+inf
    vector."""
    n = 300
    per_tile = ops._tile_rows(width, v * 8)
    r = {"below": per_tile // 2 + 1, "one": per_tile,
         "ragged": 2 * per_tile + per_tile // 3 + 1}[tiles]
    rng = np.random.default_rng(width * 10 + v)
    cols = rng.integers(0, n, (r, width)).astype(np.int32)
    sentinel = rng.random((r, width)) < 0.25
    cols[sentinel] = n
    ws = rng.random((r, width)).astype(np.float32)
    ws[sentinel & (rng.random((r, width)) < 0.5)] = np.inf
    vecs = rng.random((v, 8, n)).astype(np.float32)
    vecs[rng.random(vecs.shape) < 0.3] = np.inf
    vecs[:, 0] = np.inf
    if v == 3:
        vecs[-1] = np.inf
    want = np.asarray(ell_gather_min_batch_ref(vecs, cols, ws))
    src = ops._pad_idx(vecs, n + 1)
    got = np.asarray(jax.jit(ops._gather_min_tiled)(src, cols, ws))
    assert got.shape == want.shape == (v, 8, r)
    assert got.tobytes() == want.tobytes()
    assert np.isinf(got[:, 0]).all() and np.isfinite(got).any()


@pytest.mark.parametrize("lanes,slot_bytes",
                         [(1, 512), (8, 512), (128, 512), (129, 1024),
                          (192, 1024), (384, 1536)])
def test_tile_rows_keep_the_padded_slab_to_its_bytes(lanes, slot_bytes):
    """A tile's rows follow the slab's padded bytes per slot (V·B lanes
    padded to a multiple of 128), so every caller's slab stays within
    ``_TILE_SLAB_BYTES``, and a bucket too wide for one row still gets one."""
    for width in (8, 32, 128, 512):
        rows = ops._tile_rows(width, lanes)
        assert rows * width * slot_bytes <= ops._TILE_SLAB_BYTES
        assert (rows + 1) * width * slot_bytes > ops._TILE_SLAB_BYTES
    assert ops._tile_rows(ops._TILE_SLAB_BYTES, lanes) == 1


def test_sliced_stepper_chunking_and_reset():
    """The stepper contract survives the sliced layout: chunked stepping,
    early exit, and lane resets stay invisible, and a reset lane re-primes
    its carried in-side keys (keys_valid flag) correctly."""
    g = grid_road(11, 9, seed=55)
    ell = to_ell_in_sliced(g)
    ell_out = to_ell_out_sliced(g)
    srcs = np.asarray([0, g.n - 1, 17], np.int32)
    full = run_phased_static_batch(g, srcs, criterion="in|out")
    state = init_batch_state(g, srcs, criterion="in|out")
    assert bool(state.keys_valid) is False  # admission invalidates carries
    while lanes_active(state).any():
        state = step_batch(g, state, 3, ell=ell, ell_out=ell_out,
                           stop_on_lane_finish=True)
    assert bool(state.keys_valid) is True
    res = harvest(state)
    np.testing.assert_array_equal(np.asarray(res.dist), np.asarray(full.dist))
    np.testing.assert_array_equal(np.asarray(res.phases), np.asarray(full.phases))
    state = reset_lanes(state, np.asarray([-2, 40, -1], np.int32))
    assert bool(state.keys_valid) is False  # reset touched a lane
    while lanes_active(state).any():
        state = step_batch(g, state, 7, ell=ell, ell_out=ell_out)
    after = harvest(state)
    np.testing.assert_array_equal(np.asarray(after.dist[0]),
                                  np.asarray(full.dist[0]))
    gen = run_phased(g, 40, "in|out")
    np.testing.assert_array_equal(np.asarray(after.dist[1]), np.asarray(gen.dist))
    assert int(after.phases[1]) == int(gen.phases)
    assert np.isinf(np.asarray(after.dist[2])).all()


def test_static_plan_keeps_keys_valid_none():
    g = uniform_gnp(64, 0.1, seed=1)
    state = init_batch_state(g, [0])
    assert state.keys_valid is None and state.crit_keys is None


def test_sliced_builder_structure():
    g = kronecker(7, seed=21)
    cols, _ = to_ell_in(g)
    se = to_ell_in_sliced(g, boundaries=(8,), split=8)
    deg = np.zeros(g.n, np.int64)
    dst, w = np.asarray(g.dst), np.asarray(g.w)
    np.add.at(deg, dst[np.isfinite(w)], 1)
    rows = np.concatenate([np.asarray(s.rows) for s in se.slices])
    # every positive-degree vertex appears; zero-degree vertices never do
    assert set(rows.tolist()) == set(np.nonzero(deg)[0].tolist())
    # split bookkeeping: vertex v occurs ceil(deg/8) times, slot counts match
    occ = np.zeros(g.n, np.int64)
    np.add.at(occ, rows, 1)
    np.testing.assert_array_equal(occ[deg > 0], -(-deg[deg > 0] // 8))
    # real (finite) slots equal the real edge count, bucket-wide padding only
    finite = sum(int(np.isfinite(np.asarray(s.ws)).sum()) for s in se.slices)
    assert finite == int(np.isfinite(w).sum())
    # hub graphs shrink: sliced slots well under padded n * D_max
    assert se.padded_slots < g.n * cols.shape[1]
    # memoisation: same params hit the cache, new params rebuild
    assert to_ell_in_sliced(g, boundaries=(8,), split=8) is se
    assert to_ell_in_sliced(g, boundaries=(8, 16), split=16) is not se
    with pytest.raises(ValueError, match="split"):
        to_ell_in_sliced(g, boundaries=(8, 64), split=8)


def test_default_boundaries_and_edge_cases():
    assert default_slice_boundaries(np.array([], np.int64)) == (8,)
    assert default_slice_boundaries(np.array([0, 0, 0], np.int64)) == (8,)
    bs = default_slice_boundaries(np.array([1] * 95 + [500] * 5, np.int64))
    assert bs[0] == 8 and len(bs) <= 4
    # edgeless graph still yields a well-formed (empty) slice
    g = from_coo(np.zeros(0, np.int32), np.zeros(0, np.int32),
                 np.zeros(0, np.float32), n=5)
    se = to_ell_in_sliced(g)
    assert len(se.slices) == 1 and se.slices[0].rows.shape == (0,)
    res = run_phased_static_batch(g, [2], ell=se)
    assert np.isinf(np.asarray(res.dist)[0, :2]).all()
    assert float(res.dist[0, 2]) == 0.0


def test_out_degrees_memoised():
    g = uniform_gnp(120, 0.05, seed=3)
    deg = out_degrees(g)
    assert out_degrees(g) is deg  # instance cache hit
    src, w = np.asarray(g.src), np.asarray(g.w)
    want = np.zeros(g.n, np.int32)
    np.add.at(want, src[np.isfinite(w)], 1)
    np.testing.assert_array_equal(np.asarray(deg), want)
    # the stepper state carries the memoised vector's values (init no longer
    # recomputes a segment-sum; jit still copies the operand into the state)
    state = init_batch_state(g, [0, 7])
    np.testing.assert_array_equal(np.asarray(state.out_deg), want)
