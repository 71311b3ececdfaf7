"""Model layers: RMSNorm, RoPE, chunked GQA attention, SwiGLU MLP, MoE
(sort-based capacity dispatch), Mamba2 SSD (chunked scan + O(1) decode step),
cross-attention for vision stubs.

Conventions:
  * params are nested dicts of jnp arrays; every ``init_*`` has a matching
    ``apply_*`` (full-sequence) and, where autoregression exists, ``*_decode``
    (single-token with carried state).
  * shapes: x (B, S, D); attention heads H query / K kv heads, head dim Dh.
  * compute follows input dtype (bf16 on TPU); softmax/norm statistics in f32.
  * ``shd`` (ShardingCtx) threads mesh-axis names for with_sharding_constraint
    on the few activation tensors whose placement XLA should not be left to
    guess (MoE dispatch buffers, layer-boundary hiddens). ``shd=None`` = no
    constraints (single-device smoke tests).
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig


@dataclasses.dataclass(frozen=True)
class ShardingCtx:
    dp: tuple[str, ...]  # batch/data axes (("pod","data") multi-pod)
    tp: str  # tensor/model axis
    mesh: Any = None  # jax Mesh; enables shard_map (expert-parallel MoE)

    def cs(self, x, *spec):
        return jax.lax.with_sharding_constraint(x, P(*spec))

    @property
    def tp_size(self) -> int:
        return int(self.mesh.shape[self.tp]) if self.mesh is not None else 1


def cshard(shd: ShardingCtx | None, x, *spec):
    return x if shd is None else shd.cs(x, *spec)


# --------------------------------------------------------------------------
# basics
# --------------------------------------------------------------------------

def rms_norm(x, scale, eps=1e-5):
    # Variance via an f32-accumulating dot product: statistics are exact-ish
    # f32, but NO elementwise-f32 (B, S, D) tensor ever exists. (An upcast
    # there gets hoisted by XLA across the remat-saved residual stack,
    # quadrupling training memory at 90B scale — see EXPERIMENTS.md §Perf.)
    var = jnp.einsum(
        "...d,...d->...", x, x, preferred_element_type=jnp.float32
    ) / x.shape[-1]
    inv = jax.lax.rsqrt(var + eps)[..., None].astype(x.dtype)
    return x * inv * scale.astype(x.dtype)


def rope(x, positions, theta):
    """x: (..., S, H, Dh); positions: (S,) int. Rotates first/second halves."""
    dh = x.shape[-1]
    half = dh // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[:, None].astype(jnp.float32) * freq  # (S, half)
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def _dense(key, shape, dtype, scale=None):
    scale = scale if scale is not None else 1.0 / np.sqrt(shape[0])
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


# --------------------------------------------------------------------------
# attention (self / cross), full-sequence chunked + decode
# --------------------------------------------------------------------------

def init_attention(cfg: ModelConfig, key, dtype) -> dict[str, Any]:
    D, H, K, dh = cfg.d_model, cfg.n_heads, cfg.n_kv, cfg.d_head
    ks = jax.random.split(key, 4)
    p = {
        "wq": _dense(ks[0], (D, H * dh), dtype),
        "wk": _dense(ks[1], (D, K * dh), dtype),
        "wv": _dense(ks[2], (D, K * dh), dtype),
        "wo": _dense(ks[3], (H * dh, D), dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H * dh,), dtype)
        p["bk"] = jnp.zeros((K * dh,), dtype)
        p["bv"] = jnp.zeros((K * dh,), dtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((dh,), dtype)
        p["k_norm"] = jnp.ones((dh,), dtype)
    return p


def _project_q(cfg, p, x):
    B, S, _ = x.shape
    q = jnp.einsum("bsd,dh->bsh", x, p["wq"])
    if cfg.qkv_bias:
        q = q + p["bq"]
    q = q.reshape(B, S, cfg.n_heads, cfg.d_head)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.rms_eps)
    return q


def _project_kv(cfg, p, x):
    B, T, _ = x.shape
    k = jnp.einsum("btd,dh->bth", x, p["wk"])
    v = jnp.einsum("btd,dh->bth", x, p["wv"])
    if cfg.qkv_bias:
        k, v = k + p["bk"], v + p["bv"]
    k = k.reshape(B, T, cfg.n_kv, cfg.d_head)
    v = v.reshape(B, T, cfg.n_kv, cfg.d_head)
    if cfg.qk_norm:
        k = rms_norm(k, p["k_norm"], cfg.rms_eps)
    return k, v


def _sdpa(cfg, q, k, v, q_pos, k_pos, causal):
    """Grouped-query attention, query-chunked so no (S, S) score tensor is ever
    materialised (peak transient is (B, K, G, chunk, T) f32 per chunk)."""
    B, S, H, dh = q.shape
    K = k.shape[2]
    G = H // K
    scale = 1.0 / np.sqrt(dh)
    qg = q.reshape(B, S, K, G, dh)

    def attend(qc, qp):  # qc: (B, C, K, G, dh); qp: (C,)
        s = jnp.einsum("bckgd,btkd->bkgct", qc, k).astype(jnp.float32) * scale
        if causal:
            mask = qp[:, None] >= k_pos[None, :]  # (C, T)
            s = jnp.where(mask[None, None, None], s, -1e30)
        a = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        return jnp.einsum("bkgct,btkd->bckgd", a, v)

    chunk = min(cfg.attn_chunk, S)
    if S % chunk != 0:
        chunk = S  # fall back to one chunk for odd smoke shapes
    if chunk == S:
        o = attend(qg, q_pos)
    else:
        nc = S // chunk
        qr = jnp.moveaxis(qg.reshape(B, nc, chunk, K, G, dh), 1, 0)
        pr = q_pos.reshape(nc, chunk)
        # checkpoint each chunk: backward-of-scan then saves only the chunk
        # inputs and recomputes the (chunk, T) scores chunk-by-chunk, instead
        # of stacking all chunks' f32 score tensors (the full S x T matrix).
        attend_ckpt = jax.checkpoint(attend)
        _, o = jax.lax.scan(lambda c, inp: (c, attend_ckpt(*inp)), None, (qr, pr))
        o = jnp.moveaxis(o, 0, 1).reshape(B, S, K, G, dh)
    return o.reshape(B, S, H * dh)


def apply_attention(cfg, p, x, positions, kv_source=None, causal=True):
    """Full-sequence attention. kv_source != None => cross-attention (no RoPE
    on the cross branch; keys come from the vision/frontend embeddings)."""
    q = _project_q(cfg, p, x)
    cross = kv_source is not None
    src = kv_source if cross else x
    k, v = _project_kv(cfg, p, src)
    if not cross:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
        k_pos = positions
    else:
        k_pos = jnp.arange(src.shape[1])
        causal = False
    o = _sdpa(cfg, q, k, v, positions, k_pos, causal)
    return jnp.einsum("bsh,hd->bsd", o, p["wo"])


def apply_attention_decode(cfg, p, x, cache, pos):
    """One-token step. cache: {'k','v'}: (B, Smax, K, dh); pos: scalar index of
    the slot this token writes. Returns (out (B,1,D), new cache)."""
    B = x.shape[0]
    q = _project_q(cfg, p, x)  # (B, 1, H, dh)
    k_new, v_new = _project_kv(cfg, p, x)
    pos_arr = pos[None] if pos.ndim == 0 else pos
    q = rope(q, pos_arr, cfg.rope_theta)
    k_new = rope(k_new, pos_arr, cfg.rope_theta)
    ck = jax.lax.dynamic_update_slice(
        cache["k"], k_new.astype(cache["k"].dtype), (0, pos, 0, 0))
    cv = jax.lax.dynamic_update_slice(
        cache["v"], v_new.astype(cache["v"].dtype), (0, pos, 0, 0))
    smax = ck.shape[1]
    K = cfg.n_kv
    G = cfg.n_heads // K
    qg = q.reshape(B, 1, K, G, cfg.d_head)
    s = jnp.einsum("bckgd,btkd->bkgct", qg, ck).astype(jnp.float32)
    s = s / np.sqrt(cfg.d_head)
    mask = jnp.arange(smax) <= pos
    s = jnp.where(mask[None, None, None, None, :], s, -1e30)
    a = jax.nn.softmax(s, axis=-1).astype(cv.dtype)
    o = jnp.einsum("bkgct,btkd->bckgd", a, cv).reshape(B, 1, cfg.n_heads * cfg.d_head)
    return jnp.einsum("bsh,hd->bsd", o, p["wo"]), {"k": ck, "v": cv}


def apply_cross_attention_decode(cfg, p, x, cache):
    """Decode-time cross-attention: keys/values precomputed from the vision
    embeddings at prefill and carried in the cache (static)."""
    B = x.shape[0]
    q = _project_q(cfg, p, x)
    K = cfg.n_kv
    G = cfg.n_heads // K
    qg = q.reshape(B, 1, K, G, cfg.d_head)
    s = jnp.einsum("bckgd,btkd->bkgct", qg, cache["xk"]).astype(jnp.float32)
    s = s / np.sqrt(cfg.d_head)
    a = jax.nn.softmax(s, axis=-1).astype(cache["xv"].dtype)
    o = jnp.einsum("bkgct,btkd->bckgd", a, cache["xv"])
    o = o.reshape(B, 1, cfg.n_heads * cfg.d_head)
    return jnp.einsum("bsh,hd->bsd", o, p["wo"]), cache


# --------------------------------------------------------------------------
# FFNs: SwiGLU MLP / MoE (+ optional arctic-style dense residual)
# --------------------------------------------------------------------------

def init_mlp(key, d_model, d_ff, dtype):
    ks = jax.random.split(key, 3)
    return {
        "wi": _dense(ks[0], (d_model, d_ff), dtype),
        "wg": _dense(ks[1], (d_model, d_ff), dtype),
        "wo": _dense(ks[2], (d_ff, d_model), dtype),
    }


def apply_mlp(p, x):
    h = jnp.einsum("bsd,df->bsf", x, p["wi"])
    g = jnp.einsum("bsd,df->bsf", x, p["wg"])
    return jnp.einsum("bsf,fd->bsd", jax.nn.silu(g) * h, p["wo"])


def init_moe(cfg: ModelConfig, key, dtype):
    E, D, Fh = cfg.n_experts, cfg.d_model, cfg.d_expert
    ks = jax.random.split(key, 4)
    return {
        "router": _dense(ks[0], (D, E), jnp.float32),  # router math in f32
        "wi": _dense(ks[1], (E, D, Fh), dtype),
        "wg": _dense(ks[2], (E, D, Fh), dtype),
        "wo": _dense(ks[3], (E, Fh, D), dtype),
    }


def moe_capacity(cfg: ModelConfig, tokens: int) -> int:
    cap = int(np.ceil(cfg.capacity_factor * tokens * cfg.top_k / cfg.n_experts))
    return max(8, -(-cap // 8) * 8)


def _moe_dispatch_compute(cfg: ModelConfig, router, xt, capacity: int,
                          expert_fn):
    """Routing + sort-based capacity dispatch on a flat (T, D) token block.

    Tokens are ranked within their expert by a stable sort of expert ids; the
    first ``capacity`` per expert are scattered into an (E, C, D) buffer and
    run through ``expert_fn(buf) -> (E, C, D)``; results are gathered back
    weighted by the (renormalised) router probabilities. Out-of-capacity
    assignments drop via scatter mode='drop' / gather fill 0.
    """
    T, D = xt.shape
    E, k = cfg.n_experts, cfg.top_k
    C = capacity
    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), router)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, k)  # (T, k)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)

    eid = top_i.reshape(-1)  # (T*k,)
    order = jnp.argsort(eid, stable=True)
    eid_sorted = eid[order]
    seg_start = jnp.searchsorted(eid_sorted, eid_sorted, side="left")
    rank_sorted = jnp.arange(T * k, dtype=jnp.int32) - seg_start
    ranks = jnp.zeros((T * k,), jnp.int32).at[order].set(rank_sorted)
    keep = ranks < C
    dest = jnp.where(keep, eid * C + ranks, E * C)  # OOB => dropped

    xa = jnp.broadcast_to(xt[:, None, :], (T, k, D)).reshape(T * k, D)
    buf = jnp.zeros((E * C, D), xt.dtype).at[dest].set(xa, mode="drop")
    yb = expert_fn(buf.reshape(E, C, D))
    ya = jnp.take(yb.reshape(E * C, D), dest, axis=0, mode="fill", fill_value=0)
    y = ya * (top_p.reshape(T * k, 1) * keep[:, None]).astype(ya.dtype)
    return y.reshape(T, k, D).sum(axis=1)


def _expert_ffn(p, buf):
    h = jnp.einsum("ecd,edf->ecf", buf, p["wi"])
    g = jnp.einsum("ecd,edf->ecf", buf, p["wg"])
    return jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * h, p["wo"])


def _dp_size(shd: ShardingCtx) -> int:
    return int(np.prod([shd.mesh.shape[a] for a in shd.dp]))


def apply_moe(cfg: ModelConfig, p, x, shd: ShardingCtx | None = None):
    """Top-k MoE with sort-based capacity dispatch (dropping, static shapes).

    Two execution paths:

    * **EP / shard_map** (training & prefill on a mesh): tokens stay local to
      their device (batch over dp, sequence over tp); each device routes its
      own tokens into a local (E, C_loc, D) buffer, ONE all-to-all over the
      model axis re-buckets them by owning expert shard, the local experts
      run, and a reverse all-to-all returns results — the canonical
      expert-parallel schedule (exactly 2 all-to-alls per MoE layer, no
      GSPMD-inferred all-gathers; the global-view scatter variant cost
      120+ GiB/chip on arctic-480b — see EXPERIMENTS.md §Perf).
    * **global-view fallback** (no mesh / decode / indivisible shapes):
      plain XLA scatter-dispatch; fine for small T.
    """
    B, S, D = x.shape
    E = cfg.n_experts
    use_ep = (
        shd is not None
        and shd.mesh is not None
        and S % (16 * shd.tp_size) == 0
        and E % shd.tp_size == 0
        and B % _dp_size(shd) == 0
    )
    if not use_ep:
        T = B * S
        y = _moe_dispatch_compute(
            cfg, p["router"], x.reshape(T, D), moe_capacity(cfg, T),
            lambda buf: _expert_ffn(p, buf),
        )
        return y.reshape(B, S, D)
    return _apply_moe_ep(cfg, p, x, shd)


def _apply_moe_ep(cfg: ModelConfig, p, x, shd: ShardingCtx):
    B, S, D = x.shape
    E, k = cfg.n_experts, cfg.top_k
    tp_size = shd.tp_size
    dp_size = _dp_size(shd)
    t_loc = (B // dp_size) * (S // tp_size)
    c_loc = max(8, -(-int(cfg.capacity_factor * t_loc * k / E) // 8) * 8)
    e_loc = E // tp_size

    def spmd(xb, router, wi, wg, wo):
        # xb: (B/dp, S/tp, D) local tokens; expert weights local: (E/tp, D, F)
        tl = xb.shape[0] * xb.shape[1]
        xt = xb.reshape(tl, D)

        def expert_fn(buf):  # buf: (E, C_loc, D) local contributions
            b = buf.reshape(tp_size, e_loc, c_loc, D)
            recv = jax.lax.all_to_all(b, shd.tp, split_axis=0, concat_axis=0)
            # row j now holds peer j's tokens for OUR experts
            mine = jnp.moveaxis(recv, 0, 1).reshape(e_loc, tp_size * c_loc, D)
            h = jnp.einsum("ecd,edf->ecf", mine, wi)
            g = jnp.einsum("ecd,edf->ecf", mine, wg)
            yb = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * h, wo)
            yb = jnp.moveaxis(yb.reshape(e_loc, tp_size, c_loc, D), 1, 0)
            back = jax.lax.all_to_all(yb, shd.tp, split_axis=0, concat_axis=0)
            return back.reshape(E, c_loc, D)

        y = _moe_dispatch_compute(cfg, router, xt, c_loc, expert_fn)
        return y.reshape(xb.shape)

    # replication checking stays off: the bodies return per-shard blocks
    mapped = jax.shard_map(
        spmd,
        mesh=shd.mesh,
        in_specs=(
            P(shd.dp, shd.tp, None),  # tokens: batch over dp, seq over tp
            P(None, None),            # router replicated
            P(shd.tp, None, None),    # experts over tp (EP)
            P(shd.tp, None, None),
            P(shd.tp, None, None),
        ),
        out_specs=P(shd.dp, shd.tp, None),
        check_vma=False,
    )
    return mapped(x, p["router"], p["wi"], p["wg"], p["wo"])


# --------------------------------------------------------------------------
# Mamba2 (SSD): chunked scan for sequences, O(1) state update for decode
# --------------------------------------------------------------------------

def init_mamba(cfg: ModelConfig, key, dtype):
    """Per-segment projections (wz/wx/wb/wc/wdt) instead of one fused
    in_proj: every output is sharded on its own last dim, so TP slicing is
    always shard-aligned — the fused layout cost ~90 GB/unit of all-gather +
    collective-permute on jamba-398B (EXPERIMENTS.md §Perf iteration 10)."""
    D = cfg.d_model
    H, Pd, N, G = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_groups
    d_in = cfg.d_inner
    gn = G * N
    ks = jax.random.split(key, 9)
    return {
        "wz": _dense(ks[0], (D, d_in), dtype),
        "wx": _dense(ks[1], (D, d_in), dtype),
        "wb": _dense(ks[2], (D, gn), dtype),
        "wc": _dense(ks[3], (D, gn), dtype),
        "wdt": _dense(ks[4], (D, H), dtype),
        "conv_wx": _dense(ks[5], (cfg.ssm_conv, d_in), dtype, scale=0.5),
        "conv_wb": _dense(ks[6], (cfg.ssm_conv, gn), dtype, scale=0.5),
        "conv_wc": _dense(ks[7], (cfg.ssm_conv, gn), dtype, scale=0.5),
        "conv_bx": jnp.zeros((d_in,), dtype),
        "conv_bb": jnp.zeros((gn,), dtype),
        "conv_bc": jnp.zeros((gn,), dtype),
        "A_log": jnp.log(
            jax.random.uniform(ks[8], (H,), jnp.float32, 1.0, 16.0)
        ),
        "D": jnp.ones((H,), jnp.float32),
        "dt_bias": jnp.zeros((H,), jnp.float32),
        "norm": jnp.ones((d_in,), dtype),
        "out_proj": _dense(jax.random.fold_in(ks[8], 1), (d_in, D), dtype),
    }


def _mamba_project(cfg, p, u):
    """Per-segment projections; returns z, x_pre, B_pre, C_pre, dt
    (pre-conv). Depthwise convolution is applied per segment by callers —
    identical math to convolving the concatenation."""
    z = jnp.einsum("bsd,dp->bsp", u, p["wz"])
    x = jnp.einsum("bsd,dp->bsp", u, p["wx"])
    Bm = jnp.einsum("bsd,dp->bsp", u, p["wb"])
    Cm = jnp.einsum("bsd,dp->bsp", u, p["wc"])
    dt = jnp.einsum("bsd,dp->bsp", u, p["wdt"])
    return z, x, Bm, Cm, dt


def _causal_conv(xbc, w, b, window):
    """Depthwise causal conv over sequence: xbc (B,S,C), w (k,C)."""
    pad = jnp.pad(xbc, ((0, 0), (window - 1, 0), (0, 0)))
    out = sum(
        pad[:, i : i + xbc.shape[1], :] * w[i][None, None, :] for i in range(window)
    )
    return jax.nn.silu(out + b[None, None, :])


def _mamba_conv_all(cfg, p, x, Bm, Cm):
    x = _causal_conv(x, p["conv_wx"], p["conv_bx"], cfg.ssm_conv)
    Bm = _causal_conv(Bm, p["conv_wb"], p["conv_bb"], cfg.ssm_conv)
    Cm = _causal_conv(Cm, p["conv_wc"], p["conv_bc"], cfg.ssm_conv)
    return x, Bm, Cm


def apply_mamba(cfg: ModelConfig, p, u):
    """Chunked SSD (state-space duality) forward over a full sequence.

    Within chunks of length Q the semiseparable kernel is applied as a masked
    (Q, Q) matmul (MXU-friendly); across chunks the (H, N, P) states are
    combined with an associative scan — O(S Q) + O(S/Q) work instead of a
    length-S sequential recurrence.
    """
    B, S, _ = u.shape
    H, Pd, N, G = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_groups
    d_in = cfg.d_inner
    z, x0, B0, C0, dt = _mamba_project(cfg, p, u)
    x0, B0, C0 = _mamba_conv_all(cfg, p, x0, B0, C0)
    x = x0.reshape(B, S, H, Pd)
    Bm = B0.reshape(B, S, G, N)
    Cm = C0.reshape(B, S, G, N)
    rep = H // G
    Bh = jnp.repeat(Bm, rep, axis=2)  # (B,S,H,N)
    Ch = jnp.repeat(Cm, rep, axis=2)

    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])  # (B,S,H)
    A = -jnp.exp(p["A_log"])  # (H,) negative
    la = dt * A[None, None, :]  # log decay per step (B,S,H), <= 0
    xdt = x * dt[..., None].astype(x.dtype)  # fold dt into input

    Q = min(cfg.ssm_chunk, S)
    if S % Q != 0:
        Q = S
    nc = S // Q
    # reshape to chunks
    lc = la.reshape(B, nc, Q, H)
    cum = jnp.cumsum(lc, axis=2)  # (B,nc,Q,H) inclusive
    tot = cum[:, :, -1, :]  # (B,nc,H)
    xq = xdt.reshape(B, nc, Q, H, Pd)
    bq = Bh.reshape(B, nc, Q, H, N)
    cq = Ch.reshape(B, nc, Q, H, N)

    # --- intra-chunk: masked semiseparable matmul
    # decay(i,j) = exp(cum_i - cum_j) for i >= j (applied position-pairwise)
    dif = cum[:, :, :, None, :] - cum[:, :, None, :, :]  # (B,nc,Qi,Qj,H)
    mask = jnp.tril(jnp.ones((Q, Q), bool))
    # mask INSIDE the exp: at masked (i<j) positions dif > 0 overflows exp()
    # and its cotangent becomes inf*0=NaN in the backward pass otherwise
    dif = jnp.where(mask[None, None, :, :, None], dif, -1e30)
    dec = jnp.exp(dif)
    scores = jnp.einsum("bcihn,bcjhn->bcijh", cq.astype(jnp.float32),
                        bq.astype(jnp.float32))
    y_intra = jnp.einsum("bcijh,bcjhp->bcihp", scores * dec, xq.astype(jnp.float32))

    # --- chunk states: S_c = sum_j exp(tot - cum_j) B_j x_j^T  (H, N, P)
    wj = jnp.exp(tot[:, :, None, :] - cum)  # (B,nc,Q,H)
    st = jnp.einsum("bcjhn,bcjhp->bchnp",
                    (bq.astype(jnp.float32) * wj[..., None]),
                    xq.astype(jnp.float32))

    # --- inter-chunk associative scan over running states
    def combine(a, b):
        d1, s1 = a
        d2, s2 = b
        return d1 * d2, s1 * d2[..., None, None] + s2

    decay_tot = jnp.exp(tot)  # (B,nc,H)
    dscan, sscan = jax.lax.associative_scan(combine, (decay_tot, st), axis=1)
    # state entering chunk c is sscan at c-1 (zero for c=0)
    s_in = jnp.concatenate(
        [jnp.zeros_like(sscan[:, :1]), sscan[:, :-1]], axis=1
    )  # (B,nc,H,N,P)
    y_inter = jnp.einsum("bcihn,bchnp->bcihp",
                         cq.astype(jnp.float32) * jnp.exp(cum)[..., None], s_in)

    y = (y_intra + y_inter).reshape(B, S, H, Pd)
    y = y + x.astype(jnp.float32) * p["D"][None, None, :, None]
    y = y.reshape(B, S, d_in).astype(u.dtype)
    y = y * jax.nn.silu(z)
    y = rms_norm(y, p["norm"], cfg.rms_eps)
    return jnp.einsum("bsd,dp->bsp", y, p["out_proj"])


def mamba_state_init(cfg: ModelConfig, batch, dtype=jnp.float32):
    H, Pd, N, G = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_groups
    k = cfg.ssm_conv - 1
    return {
        "convx": jnp.zeros((batch, k, cfg.d_inner), dtype),
        "convb": jnp.zeros((batch, k, G * N), dtype),
        "convc": jnp.zeros((batch, k, G * N), dtype),
        "ssm": jnp.zeros((batch, H, N, Pd), jnp.float32),
    }


def _conv_step(seg, state_seg, w, b, window):
    """One-token depthwise conv: returns (activated (B, C), new state)."""
    conv_in = jnp.concatenate([state_seg, seg], axis=1)  # (B, k, C)
    out = sum(conv_in[:, i, :] * w[i][None, :] for i in range(window))
    return jax.nn.silu(out + b[None, :]), conv_in[:, 1:, :]


def apply_mamba_decode(cfg: ModelConfig, p, u, state):
    """Single-token SSD step: s <- exp(dt A) s + dt B x ; y = C s + D x."""
    B = u.shape[0]
    H, Pd, N, G = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_groups
    d_in = cfg.d_inner
    z, x0, B0, C0, dt = _mamba_project(cfg, p, u)  # (B,1,*)
    x1, new_cx = _conv_step(x0, state["convx"], p["conv_wx"], p["conv_bx"],
                            cfg.ssm_conv)
    B1, new_cb = _conv_step(B0, state["convb"], p["conv_wb"], p["conv_bb"],
                            cfg.ssm_conv)
    C1, new_cc = _conv_step(C0, state["convc"], p["conv_wc"], p["conv_bc"],
                            cfg.ssm_conv)

    x = x1.reshape(B, H, Pd)
    Bm = B1.reshape(B, G, N)
    Cm = C1.reshape(B, G, N)
    rep = H // G
    Bh = jnp.repeat(Bm, rep, axis=1)  # (B,H,N)
    Ch = jnp.repeat(Cm, rep, axis=1)
    dt1 = jax.nn.softplus(dt[:, 0, :].astype(jnp.float32) + p["dt_bias"])  # (B,H)
    A = -jnp.exp(p["A_log"])
    decay = jnp.exp(dt1 * A[None, :])  # (B,H)
    xf = x.astype(jnp.float32) * dt1[..., None]
    s_new = state["ssm"] * decay[..., None, None] + jnp.einsum(
        "bhn,bhp->bhnp", Bh.astype(jnp.float32), xf
    )
    y = jnp.einsum("bhn,bhnp->bhp", Ch.astype(jnp.float32), s_new)
    y = y + x.astype(jnp.float32) * p["D"][None, :, None]
    y = y.reshape(B, 1, d_in).astype(u.dtype)
    y = y * jax.nn.silu(z)
    y = rms_norm(y, p["norm"], cfg.rms_eps)
    out = jnp.einsum("bsd,dp->bsp", y, p["out_proj"])
    return out, {"convx": new_cx, "convb": new_cb, "convc": new_cc,
                 "ssm": s_new}
