"""Fused-megakernel validation: interpret-mode bit-exact parity of
``ell_relax_keys[_batch]`` / ``ell_gather_min_batch`` / ``ell_keys_dep_batch``
against the COMPOSED single-purpose kernels (``ell_relax`` + ``ell_key_min``)
and the ref.py oracles, plus the execution-config layer (mode resolution,
VMEM-budget tile sizing, tuning ledger)."""
import os

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import config as kcfg
from repro.kernels import ops as kops
from repro.kernels.ell_key_min import ell_key_min_batch
from repro.kernels.ell_relax import ell_relax_batch
from repro.kernels.ell_relax_keys import (
    ell_gather_min_batch,
    ell_keys_dep_batch,
    ell_relax_keys,
    ell_relax_keys_batch,
)
from repro.kernels.ref import (
    ell_gather_min_batch_ref,
    ell_keys_dep_batch_ref,
    ell_relax_keys_batch_ref,
)

INF = np.inf


def _mk_ell(rng, n, d):
    """Random ELL with sentinel entries and +inf padding."""
    cols = rng.integers(0, n + 1, (n, d)).astype(np.int32)
    ws = rng.uniform(0, 2, (n, d)).astype(np.float32)
    ws[cols == n] = INF
    ws[rng.random((n, d)) < 0.3] = INF
    return jnp.asarray(cols), jnp.asarray(ws)


def _mk_vecs(rng, shape):
    v = rng.uniform(0, 5, shape).astype(np.float32)
    v[rng.random(shape) < 0.4] = INF
    return jnp.asarray(v)


@pytest.mark.parametrize("n,d,b,k,block", [
    (100, 7, 3, 2, 64), (256, 16, 1, 1, 256), (300, 5, 4, 2, 128),
])
def test_relax_keys_fused_matches_composed(n, d, b, k, block):
    """The tentpole parity pin: one fused launch == relax kernel + per-key
    key-min kernels, bitwise, for any block size."""
    rng = np.random.default_rng(n * 31 + d)
    cols, ws = _mk_ell(rng, n, d)
    dmask = _mk_vecs(rng, (b, n))
    ga = _mk_vecs(rng, (k, b, n))
    gb = _mk_vecs(rng, (k, b, n))
    gc = jnp.asarray(
        np.where(rng.random((k, b, n)) < 0.5, 0.0, INF).astype(np.float32)
    )
    upd, keys = ell_relax_keys_batch(dmask, ga, gb, gc, cols, ws,
                                     block_rows=block, interpret=True)
    # composed relax (its own padding convention — same values)
    comp_upd = ell_relax_batch(kops.pad_lane_batch(dmask), cols, ws,
                               block_rows=block, interpret=True)
    np.testing.assert_array_equal(np.asarray(upd), np.asarray(comp_upd))
    # composed keys: gate materialised on the host, one key-min pass per key
    fin = jnp.where(jnp.isfinite(upd), 0.0, INF)
    for i in range(k):
        gate = jnp.minimum(ga[i], jnp.minimum(gb[i], gc[i] + fin))
        comp = ell_key_min_batch(kops.pad_lane_batch(gate), cols, ws,
                                 block_rows=block, interpret=True)
        np.testing.assert_array_equal(np.asarray(keys[i]), np.asarray(comp))
    # and the ref oracle
    upd_r, keys_r = ell_relax_keys_batch_ref(dmask, ga, gb, gc, cols, ws)
    np.testing.assert_array_equal(np.asarray(upd), np.asarray(upd_r))
    np.testing.assert_array_equal(np.asarray(keys), np.asarray(keys_r))
    # 1-D wrapper rows match the batch rows
    u1, k1 = ell_relax_keys(dmask[0], ga[:, 0], gb[:, 0], gc[:, 0], cols, ws,
                            block_rows=block, interpret=True)
    np.testing.assert_array_equal(np.asarray(u1), np.asarray(upd[0]))
    np.testing.assert_array_equal(np.asarray(k1), np.asarray(keys[:, 0]))


@pytest.mark.parametrize("n,d,b,v,block", [(128, 9, 2, 3, 64), (200, 4, 1, 1, 256)])
def test_gather_min_multi_vector_matches_composed(n, d, b, v, block):
    rng = np.random.default_rng(n + v)
    cols, ws = _mk_ell(rng, n, d)
    vecs = _mk_vecs(rng, (v, b, n))
    out = ell_gather_min_batch(vecs, cols, ws, block_rows=block, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(ell_gather_min_batch_ref(vecs, cols, ws))
    )
    for i in range(v):
        comp = ell_key_min_batch(kops.pad_lane_batch(vecs[i]), cols, ws,
                                 block_rows=block, interpret=True)
        np.testing.assert_array_equal(np.asarray(out[i]), np.asarray(comp))


def test_keys_dep_fused_matches_composed():
    """out_full-style dependent key: sweep 1's gate reads sweep 0's output."""
    rng = np.random.default_rng(5)
    n, d, b, k0 = 150, 6, 3, 2
    cols, ws = _mk_ell(rng, n, d)
    gates = _mk_vecs(rng, (k0, b, n))
    dga = jnp.asarray(np.where(rng.random((b, n)) < 0.4, 0.0, INF).astype(np.float32))
    dgb = jnp.asarray(np.where(rng.random((b, n)) < 0.4, 0.0, INF).astype(np.float32))
    for dep_idx in range(k0):
        keys = ell_keys_dep_batch(gates, dga, dgb, cols, ws, dep_idx=dep_idx,
                                  block_rows=64, interpret=True)
        ref = ell_keys_dep_batch_ref(gates, dga, dgb, dep_idx, cols, ws)
        np.testing.assert_array_equal(np.asarray(keys), np.asarray(ref))
        gate = jnp.minimum(dga, dgb + keys[dep_idx])
        comp = ell_key_min_batch(kops.pad_lane_batch(gate), cols, ws,
                                 block_rows=64, interpret=True)
        np.testing.assert_array_equal(np.asarray(keys[k0]), np.asarray(comp))
    with pytest.raises(ValueError, match="dep_idx"):
        ell_keys_dep_batch(gates, dga, dgb, cols, ws, dep_idx=k0,
                           interpret=True)


def test_ops_fused_entry_points_use_pallas_parity():
    """The engine-facing wrappers: kernel and ref paths bit-identical, both
    layouts (padding lives in ONE place per wrapper now)."""
    from repro.core.graph import to_ell_in, to_ell_in_sliced
    from repro.graphs import kronecker

    g = kronecker(7, seed=9)
    rng = np.random.default_rng(1)
    b = 3
    d = _mk_vecs(rng, (b, g.n))
    settle = jnp.asarray(rng.random((b, g.n)) < 0.3)
    parts = []
    for _ in range(2):
        parts.append((
            _mk_vecs(rng, (b, g.n)), _mk_vecs(rng, (b, g.n)),
            jnp.asarray(np.where(rng.random((b, g.n)) < 0.5, 0.0, INF)
                        .astype(np.float32)),
        ))
    outs = []
    for ell in (to_ell_in(g), to_ell_in_sliced(g)):
        for pallas in (True, False):
            outs.append(kops.in_scan_relax_keys_batch(
                d, settle, parts, ell, use_pallas=pallas, interpret=True
            ))
    for upd, keys in outs[1:]:
        np.testing.assert_array_equal(np.asarray(outs[0][0]), np.asarray(upd))
        np.testing.assert_array_equal(np.asarray(outs[0][1]), np.asarray(keys))
    gates = jnp.stack([p[0] for p in parts])
    dep = (parts[0][1], parts[1][2], 1)
    outs = [
        kops.out_scan_keys_batch(gates, dp, ell, use_pallas=pallas,
                                 interpret=True)
        for dp in (None, dep)
        for ell in (to_ell_in(g), to_ell_in_sliced(g))
        for pallas in (True, False)
    ]
    for i in (1, 2, 3):
        np.testing.assert_array_equal(np.asarray(outs[0]), np.asarray(outs[i]))
    for i in (5, 6, 7):
        np.testing.assert_array_equal(np.asarray(outs[4]), np.asarray(outs[i]))


# ---------------------------------------------------------------------------
# execution config
# ---------------------------------------------------------------------------


def test_kernel_mode_env_override(monkeypatch):
    monkeypatch.delenv("REPRO_KERNEL_MODE", raising=False)
    assert kcfg.resolve_interpret(True) is True
    assert kcfg.resolve_interpret(False) is False
    # auto: interpret everywhere but TPU (this CI runs on CPU)
    assert kcfg.resolve_interpret(None) is True
    monkeypatch.setenv("REPRO_KERNEL_MODE", "compiled")
    assert kcfg.resolve_interpret(None) is False
    monkeypatch.setenv("REPRO_KERNEL_MODE", "interpret")
    assert kcfg.resolve_interpret(None) is True
    monkeypatch.setenv("REPRO_KERNEL_MODE", "nonsense")
    with pytest.raises(ValueError, match="REPRO_KERNEL_MODE"):
        kcfg.resolve_interpret(None)


def test_feasible_block_rows_respects_budget():
    # a huge working set leaves only the smallest candidate
    small = kcfg.feasible_block_rows(1 << 20, 4096, 8, budget=1 << 20)
    assert small == kcfg.BLOCK_ROWS_CANDIDATES[:1]
    # a tiny one admits everything
    assert kcfg.feasible_block_rows(256, 8, 1) == kcfg.BLOCK_ROWS_CANDIDATES
    # estimate is monotone in block_rows
    assert (kcfg.scan_vmem_bytes(1024, 64, 4, 512)
            > kcfg.scan_vmem_bytes(1024, 64, 4, 128))


def test_tuning_ledger_roundtrip_and_resolution(tmp_path, monkeypatch):
    monkeypatch.delenv("REPRO_KERNEL_MODE", raising=False)
    kcfg.reset_global_ledger()
    led = kcfg.global_ledger()
    key = kcfg.ledger_key("relax", 4096, 32, 4)
    # untuned default prefers one grid step: smallest candidate covering all
    # rows (here the largest feasible, since n+1 > 4096)
    assert kcfg.resolve_block_rows("relax", 4096, 32, 4) == 4096
    assert kcfg.resolve_block_rows("relax", 300, 32, 4) == 512
    led.put(key, {"block_rows": 512})
    assert kcfg.resolve_block_rows("relax", 4096, 32, 4) == 512
    path = str(tmp_path / "ledger.json")
    led.save(path)
    fresh = kcfg.TuningLedger(path)
    assert fresh.get(key) == {"block_rows": 512}
    # malformed files load nothing instead of raising (a tuning record is a
    # measurement memo; losing it re-measures — see test_kernel_config for
    # the full corruption-tolerance sweep)
    bad = tmp_path / "bad.json"
    bad.write_text("[1, 2]")
    assert kcfg.TuningLedger(str(bad)).entries == {}
    kcfg.reset_global_ledger()


def test_autotune_block_rows_measures_and_ledgers():
    kcfg.reset_global_ledger()
    rng = np.random.default_rng(0)
    n, d, b = 300, 8, 2
    cols, ws = _mk_ell(rng, n, d)
    dmask = _mk_vecs(rng, (b, n))

    def make_call(block_rows):
        padded = kops.pad_lane_batch(dmask)
        return lambda: ell_relax_batch(padded, cols, ws,
                                       block_rows=block_rows, interpret=True)

    led = kcfg.TuningLedger()
    best = kcfg.autotune_block_rows("relax", make_call, n, d, b, reps=1,
                                    ledger=led)
    assert best in kcfg.BLOCK_ROWS_CANDIDATES
    entry = led.get(kcfg.ledger_key("relax", n, d, b))
    assert entry["block_rows"] == best and entry["wall_s"] > 0
    assert len(entry["measured"]) >= 1


def test_autotune_slicing_ledger_feeds_the_builders():
    from repro.core.graph import to_ell_in, to_ell_in_sliced
    from repro.graphs import kronecker

    g = kronecker(7, seed=2)
    rng = np.random.default_rng(3)
    d = _mk_vecs(rng, (1, g.n))
    settle = jnp.asarray(rng.random((1, g.n)) < 0.5)

    def make_call(bset):
        if bset is None:
            cols, ws = to_ell_in(g)
            return lambda: kops.relax_settled_batch(d, settle, cols, ws,
                                                    interpret=True,
                                                    use_pallas=True)
        sl = to_ell_in_sliced(g, boundaries=bset)
        return lambda: kops.relax_settled_batch_sliced(d, settle, sl,
                                                       interpret=True,
                                                       use_pallas=True)

    led = kcfg.TuningLedger()
    win = kcfg.autotune_slicing(make_call, g.n,
                                boundary_sets=(None, (8, 32)), reps=1,
                                ledger=led)
    entry = led.get(kcfg.slicing_ledger_key("in", g.n))
    assert set(entry["measured"]) == {"padded", "[8, 32]"}
    assert win is None or tuple(win) == (8, 32)
    # the tune-then-serve loop actually closes: a builder with no explicit
    # boundaries consults the (global) ledger and uses the winner
    kcfg.reset_global_ledger()
    kcfg.global_ledger().put(kcfg.slicing_ledger_key("in", g.n),
                             {"boundaries": [8, 32]})
    try:
        tuned = to_ell_in_sliced(g)
        assert tuned is to_ell_in_sliced(g, boundaries=(8, 32))
        # a padded winner (boundaries None) falls back to the default
        kcfg.global_ledger().put(kcfg.slicing_ledger_key("in", g.n),
                                 {"boundaries": None})
        assert kcfg.resolve_slice_boundaries("in", g.n) is None
    finally:
        kcfg.reset_global_ledger()
