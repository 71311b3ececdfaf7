"""Per-kernel validation: Pallas (interpret=True) vs pure-jnp ref.py oracles,
swept over shapes/dtypes. Deterministic only — the hypothesis property sweeps
live in test_property_sssp.py so this module never needs optional deps."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import to_ell_in
from repro.graphs import uniform_gnp
from repro.kernels import relax_settled, relax_settled_batch
from repro.kernels.ell_relax import ell_relax, ell_relax_batch
from repro.kernels.frontier_crit import frontier_crit, frontier_crit_batch
from repro.kernels.ref import (
    ell_relax_batch_ref,
    ell_relax_ref,
    frontier_crit_batch_ref,
    frontier_crit_ref,
)

from helpers import mk_ell as _mk_ell

INF = np.inf


def _mk_dmask(rng, shape):
    dmask = rng.uniform(0, 10, shape).astype(np.float32)
    dmask[rng.random(shape) < 0.5] = INF
    return jnp.asarray(dmask)


@pytest.mark.parametrize("n,d,block", [
    (8, 1, 8), (64, 8, 16), (100, 24, 32), (256, 16, 256), (300, 8, 128),
    (1000, 40, 256),
])
def test_ell_relax_shapes(n, d, block):
    rng = np.random.default_rng(n * 7 + d)
    n_pad = -(-(n + 1) // 128) * 128
    cols, ws = _mk_ell(rng, n, d, n_pad)
    dmask = _mk_dmask(rng, n_pad)
    out = ell_relax(dmask, cols, ws, block_rows=block, interpret=True)
    ref = ell_relax_ref(dmask, cols, ws)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-6)


@pytest.mark.parametrize("b,n,d,block", [
    (1, 64, 8, 16), (4, 100, 24, 32), (8, 300, 8, 128), (16, 256, 16, 256),
])
def test_ell_relax_batch_shapes(b, n, d, block):
    rng = np.random.default_rng(b * 31 + n * 7 + d)
    n_pad = -(-(n + 1) // 128) * 128
    cols, ws = _mk_ell(rng, n, d, n_pad)
    dmask = _mk_dmask(rng, (b, n_pad))
    out = ell_relax_batch(dmask, cols, ws, block_rows=block, interpret=True)
    ref = ell_relax_batch_ref(dmask, cols, ws)
    assert out.shape == (b, n)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_ell_relax_batch_rows_match_single():
    """Each batch row must be bit-identical to the 1-D kernel on that row."""
    rng = np.random.default_rng(99)
    n, d, b = 200, 12, 6
    n_pad = -(-(n + 1) // 128) * 128
    cols, ws = _mk_ell(rng, n, d, n_pad)
    dmask = _mk_dmask(rng, (b, n_pad))
    out = np.asarray(ell_relax_batch(dmask, cols, ws, block_rows=64, interpret=True))
    for i in range(b):
        row = np.asarray(ell_relax(dmask[i], cols, ws, block_rows=64, interpret=True))
        np.testing.assert_array_equal(out[i], row)


@pytest.mark.parametrize("n,block", [(16, 16), (100, 64), (2048, 2048),
                                     (4100, 2048), (77, 32)])
def test_frontier_crit_shapes(n, block):
    rng = np.random.default_rng(n)
    d = rng.uniform(0, 5, n).astype(np.float32)
    status = rng.integers(0, 3, n).astype(np.int32)
    om = rng.uniform(0, 1, n).astype(np.float32)
    got = frontier_crit(jnp.asarray(d), jnp.asarray(status), jnp.asarray(om),
                        block=block, interpret=True)
    want = frontier_crit_ref(jnp.asarray(d), jnp.asarray(status), jnp.asarray(om))
    for g, w in zip(got, want):
        assert float(g) == pytest.approx(float(w), rel=1e-6)
    assert got[2].dtype == jnp.int32  # fringe counts never live in f32 lanes


@pytest.mark.parametrize("b,n,block", [(1, 100, 64), (4, 77, 32), (8, 300, 128),
                                       (16, 2048, 2048)])
def test_frontier_crit_batch_shapes(b, n, block):
    rng = np.random.default_rng(b * 13 + n)
    d = jnp.asarray(rng.uniform(0, 5, (b, n)).astype(np.float32))
    status = jnp.asarray(rng.integers(0, 3, (b, n)).astype(np.int32))
    om = jnp.asarray(rng.uniform(0, 1, n).astype(np.float32))
    got = frontier_crit_batch(d, status, om, block=block, interpret=True)
    want = frontier_crit_batch_ref(d, status, om)
    assert got[2].dtype == jnp.int32
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_frontier_crit_empty_fringe():
    n = 64
    d = jnp.zeros((n,), jnp.float32)
    status = jnp.zeros((n,), jnp.int32)  # all unexplored
    om = jnp.ones((n,), jnp.float32)
    minf, lout, cnt = frontier_crit(d, status, om, interpret=True)
    assert np.isinf(float(minf)) and np.isinf(float(lout)) and int(cnt) == 0


def test_frontier_crit_batch_mixed_empty_rows():
    """Rows with no fringe report (+inf, +inf, 0) without touching others."""
    n, b = 128, 4
    rng = np.random.default_rng(3)
    d = jnp.asarray(rng.uniform(0, 5, (b, n)).astype(np.float32))
    status = jnp.zeros((b, n), jnp.int32).at[1, 7].set(1).at[3, 100].set(1)
    om = jnp.asarray(rng.uniform(0, 1, n).astype(np.float32))
    minf, lout, cnt = frontier_crit_batch(d, status, om, block=32, interpret=True)
    minf, lout, cnt = map(np.asarray, (minf, lout, cnt))
    assert np.isinf(minf[[0, 2]]).all() and np.isinf(lout[[0, 2]]).all()
    assert cnt.tolist() == [0, 1, 0, 1]
    assert minf[1] == float(d[1, 7]) and minf[3] == float(d[3, 100])


def test_relax_settled_matches_push_formulation():
    g = uniform_gnp(300, 8 / 300, seed=5)
    cols, ws = to_ell_in(g)
    rng = np.random.default_rng(0)
    d = rng.uniform(0, 3, g.n).astype(np.float32)
    settle = rng.random(g.n) < 0.4
    upd = np.asarray(relax_settled(jnp.asarray(d), jnp.asarray(settle), cols, ws))
    # push-style oracle over COO
    src, dst, w = np.asarray(g.src), np.asarray(g.dst), np.asarray(g.w)
    cand = np.where(settle[src] & np.isfinite(w), d[src] + w, INF)
    push = np.full(g.n, INF, np.float32)
    np.minimum.at(push, dst, cand)
    finite = np.isfinite(push)
    assert (np.isfinite(upd) == finite).all()
    np.testing.assert_allclose(upd[finite], push[finite], rtol=1e-6)


def test_relax_settled_batch_matches_single():
    g = uniform_gnp(250, 8 / 250, seed=6)
    cols, ws = to_ell_in(g)
    rng = np.random.default_rng(1)
    b = 8
    d = jnp.asarray(rng.uniform(0, 3, (b, g.n)).astype(np.float32))
    settle = jnp.asarray(rng.random((b, g.n)) < 0.4)
    upd = np.asarray(relax_settled_batch(d, settle, cols, ws, use_pallas=True))
    for i in range(b):
        single = np.asarray(relax_settled(d[i], settle[i], cols, ws))
        np.testing.assert_array_equal(upd[i], single)
