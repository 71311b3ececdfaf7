"""Pure-jnp oracles for every Pallas kernel (the correctness contract).

Each ``*_ref`` computes the identical function with plain jax.numpy; tests
sweep shapes/dtypes and assert allclose between kernel (interpret=True on
CPU) and oracle.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

INF = jnp.inf


def ell_relax_ref(dmask: jax.Array, cols: jax.Array, ws: jax.Array) -> jax.Array:
    """upd[v] = min_j dmask[cols[v, j]] + ws[v, j]."""
    return jnp.min(jnp.take(dmask, cols, axis=0) + ws, axis=1)


def frontier_crit_ref(d: jax.Array, status: jax.Array, out_min: jax.Array):
    fringe = status == 1
    min_fd = jnp.min(jnp.where(fringe, d, INF))
    l_out = jnp.min(jnp.where(fringe, d + out_min, INF))
    n_f = jnp.sum(fringe, dtype=jnp.int32)
    return min_fd, l_out, n_f


def ell_relax_batch_ref(dmask: jax.Array, cols: jax.Array, ws: jax.Array) -> jax.Array:
    """upd[b, v] = min_j dmask[b, cols[v, j]] + ws[v, j]."""
    return jnp.min(jnp.take(dmask, cols, axis=1) + ws[None], axis=-1)


def frontier_crit_batch_ref(d: jax.Array, status: jax.Array, out_min: jax.Array):
    """Per-batch-row (min_F d, L_out, |F|) over (B, n) state; out_min shared."""
    fringe = status == 1
    min_fd = jnp.min(jnp.where(fringe, d, INF), axis=1)
    l_out = jnp.min(jnp.where(fringe, d + out_min[None], INF), axis=1)
    n_f = jnp.sum(fringe, axis=1, dtype=jnp.int32)
    return min_fd, l_out, n_f


def ell_key_min_ref(gate: jax.Array, cols: jax.Array, ws: jax.Array) -> jax.Array:
    """key[v] = min_j gate[cols[v, j]] + ws[v, j] (dynamic criterion key)."""
    return jnp.min(jnp.take(gate, cols, axis=0) + ws, axis=1)


def ell_key_min_batch_ref(gate: jax.Array, cols: jax.Array, ws: jax.Array) -> jax.Array:
    """key[b, v] = min_j gate[b, cols[v, j]] + ws[v, j]; adjacency shared."""
    return jnp.min(jnp.take(gate, cols, axis=1) + ws[None], axis=-1)


def _pad_idx_ref(vec: jax.Array, idx_pad: int) -> jax.Array:
    """THE index-space padding convention of the fused kernels, shared so
    oracle and kernel paths cannot drift (ell_relax_keys owns it)."""
    from repro.kernels.ell_relax_keys import _pad_idx

    return _pad_idx(vec, idx_pad) if idx_pad > vec.shape[-1] else vec


def ell_gather_min_batch_ref(vecs: jax.Array, cols: jax.Array,
                             ws: jax.Array) -> jax.Array:
    """out[v, b, r] = min_j vecs[v, b, cols[r, j]] + ws[r, j] — the composed
    relax/key-min traffic of the single-sweep multi-vector megakernel.

    Unlike the per-kernel refs above, the megakernel oracles take the
    UNPADDED (..., n) gather vectors (matching their kernel wrappers, which
    own the coupled row/index padding) and pad here — the sentinel id ``n``
    must stay in bounds or ``jnp.take``'s clip mode would silently gather a
    real vertex.
    """
    vecs = _pad_idx_ref(vecs, vecs.shape[-1] + 1)
    return jnp.min(jnp.take(vecs, cols, axis=2) + ws[None, None], axis=-1)


def ell_relax_keys_batch_ref(dmask, ga, gb, gc, cols, ws):
    """Fused in-scan oracle: (upd (B, n), keys (K, B, n)).

    ``upd`` is ``ell_relax_batch_ref`` on ``dmask``; ``keys[k]`` is
    ``ell_key_min_batch_ref`` on the post-phase gate
    ``min(ga[k], gb[k], gc[k] + fin)`` where ``fin`` is 0 on vertices whose
    update is finite (they join the fringe) and +inf elsewhere — including
    every padding/sentinel slot, whose upd is +inf by construction.
    Inputs are unpadded (B, n) / (K, B, n), as for the kernel wrapper.
    """
    n_rows = cols.shape[0]
    idx_pad = dmask.shape[-1] + 1
    dmask, ga, gb, gc = (_pad_idx_ref(x, idx_pad) for x in (dmask, ga, gb, gc))
    upd = jnp.min(jnp.take(dmask, cols, axis=1) + ws[None], axis=-1)  # (B, n)
    fin = jnp.full(dmask.shape, INF, jnp.float32).at[:, :n_rows].set(
        jnp.where(upd < INF, 0.0, INF)
    )
    gate = jnp.minimum(ga, jnp.minimum(gb, gc + fin[None]))
    keys = jnp.min(jnp.take(gate, cols, axis=2) + ws[None, None], axis=-1)
    return upd, keys


def ell_keys_dep_batch_ref(gates, dga, dgb, dep_idx, cols, ws):
    """Fused out-scan oracle: keys (K0 + 1, B, n); row K0 is the dependent
    key reduced through ``min(dga, dgb + keys[dep_idx])``. Inputs unpadded."""
    n_rows = cols.shape[0]
    idx_pad = gates.shape[-1] + 1
    gates = _pad_idx_ref(gates, idx_pad)
    keys0 = jnp.min(jnp.take(gates, cols, axis=2) + ws[None, None], axis=-1)
    dep = jnp.full((gates.shape[1], idx_pad), INF, jnp.float32).at[
        :, :n_rows
    ].set(keys0[dep_idx])
    gate = jnp.minimum(_pad_idx_ref(dga, idx_pad),
                       _pad_idx_ref(dgb, idx_pad) + dep)
    dep_key = jnp.min(jnp.take(gate, cols, axis=1) + ws[None], axis=-1)
    return jnp.concatenate([keys0, dep_key[None]], axis=0)


def frontier_crit_lanes_batch_ref(d: jax.Array, status: jax.Array,
                                  keys: jax.Array | None):
    """Per-row plan-lane thresholds: (mins (1+K, B), |F| (B,)).

    ``keys`` is ``(K, n)`` (shared static keys), ``(K, B, n)`` (per-lane
    dynamic keys) or None (K = 0); mins[0] = min_F d, mins[1+k] =
    min_F (d + keys[k]).
    """
    fringe = status == 1
    rows = [jnp.min(jnp.where(fringe, d, INF), axis=1)]
    if keys is not None:
        for k in range(keys.shape[0]):
            kk = keys[k]
            term = d + (kk if kk.ndim == 2 else kk[None, :])
            rows.append(jnp.min(jnp.where(fringe, term, INF), axis=1))
    n_f = jnp.sum(fringe, axis=1, dtype=jnp.int32)
    return jnp.stack(rows), n_f


def ell_sliced_gather_min_batch_ref(vecs, sliced):
    """Sliced multi-vector gather-min oracle: per-bucket refs + the shared
    gather-merge plan (``_merge_parts`` is the one merge implementation)."""
    from repro.kernels.ell_relax_keys import _merge_parts

    parts = [
        ell_gather_min_batch_ref(vecs, s.cols, s.ws)
        for s in sliced.slices
        if s.rows.shape[0]
    ]
    return _merge_parts(parts, sliced.merge_heavy, sliced.merge_first,
                        vecs.shape[:-1])


def ell_sliced_relax_keys_batch_ref(dmask, ga, gb, gc, sliced):
    """Sliced fused in-scan oracle (bitwise the split decomposition)."""
    upd = ell_sliced_gather_min_batch_ref(dmask[None], sliced)[0]
    fin = jnp.where(upd < INF, 0.0, INF)
    gates = jnp.minimum(ga, jnp.minimum(gb, gc + fin[None]))
    return upd, ell_sliced_gather_min_batch_ref(gates, sliced)


def ell_sliced_keys_dep_batch_ref(gates, dga, dgb, sliced, *, dep_idx=0):
    """Sliced fused out-scan oracle: independent rows then the dependent
    key reduced through ``min(dga, dgb + keys[dep_idx])``."""
    keys0 = ell_sliced_gather_min_batch_ref(gates, sliced)
    gate = jnp.minimum(dga, dgb + keys0[dep_idx])
    dep = ell_sliced_gather_min_batch_ref(gate[None], sliced)
    return jnp.concatenate([keys0, dep], axis=0)


def register_kernels(reg):
    """Bind the oracle onto every registered contract.

    This module is last in ``registry.KERNEL_MODULES``, so every contract
    already exists; ``collect()`` then refuses any that slipped through
    unbound. Oracles are called with each spec case's POSITIONAL args only
    (the auditor drops wrapper-tuning kwargs like ``block_rows``), so they
    must agree with the wrapper on output shapes/dtypes for the defaults.
    """
    import functools

    from repro.kernels import ops

    def relax_settled_ref(d, settle_mask, cols, ws):
        n = d.shape[0]
        lane_pad = -(-(n + 1) // 128) * 128
        dmask = jnp.full((lane_pad,), INF, jnp.float32)
        dmask = dmask.at[:n].set(jnp.where(settle_mask, d, INF))
        return ell_relax_ref(dmask, cols, ws)

    def keys_dep_ref(gates, dga, dgb, cols, ws):
        return ell_keys_dep_batch_ref(gates, dga, dgb, 0, cols, ws)

    no_pallas = functools.partial
    for name, oracle in (
        ("ell_relax", ell_relax_ref),
        ("ell_relax_batch", ell_relax_batch_ref),
        ("ell_key_min", ell_key_min_ref),
        ("ell_key_min_batch", ell_key_min_batch_ref),
        ("ell_gather_min_batch", ell_gather_min_batch_ref),
        ("ell_relax_keys_batch", ell_relax_keys_batch_ref),
        ("ell_keys_dep_batch", keys_dep_ref),
        ("ell_sliced_gather_min_batch", ell_sliced_gather_min_batch_ref),
        ("ell_sliced_relax_keys_batch", ell_sliced_relax_keys_batch_ref),
        ("ell_sliced_keys_dep_batch", ell_sliced_keys_dep_batch_ref),
        ("frontier_crit", frontier_crit_ref),
        ("frontier_crit_batch", frontier_crit_batch_ref),
        ("frontier_crit_lanes_batch", frontier_crit_lanes_batch_ref),
        ("relax_settled", relax_settled_ref),
        ("static_thresholds", frontier_crit_ref),
        ("relax_settled_batch",
         no_pallas(ops.relax_settled_batch, use_pallas=False)),
        ("relax_settled_batch_sliced",
         no_pallas(ops.relax_settled_batch_sliced, use_pallas=False)),
        ("gather_min_batch_sliced",
         no_pallas(ops.gather_min_batch_sliced, use_pallas=False)),
        ("static_thresholds_batch", frontier_crit_batch_ref),
        ("crit_thresholds_batch", frontier_crit_lanes_batch_ref),
        ("key_min_batch", no_pallas(ops.key_min_batch, use_pallas=False)),
        ("key_min_batch_any",
         no_pallas(ops.key_min_batch_any, use_pallas=False)),
        ("delta_relax_batch",
         no_pallas(ops.delta_relax_batch, use_pallas=False)),
        ("relax_settled_gated_batch",
         no_pallas(ops.relax_settled_gated_batch, use_pallas=False)),
        ("in_scan_relax_keys_gated_batch",
         no_pallas(ops.in_scan_relax_keys_gated_batch, use_pallas=False)),
        ("in_scan_relax_keys_batch",
         no_pallas(ops.in_scan_relax_keys_batch, use_pallas=False)),
        ("out_scan_keys_batch",
         no_pallas(ops.out_scan_keys_batch, use_pallas=False)),
    ):
        reg.bind_oracle(name, oracle)
