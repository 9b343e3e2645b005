"""Shared neural layers: norms, rotary embeddings, GQA and MLA attention.

Everything is functional: `init_*` builds a param pytree, `*_fwd` applies
it.  Per-layer params are stacked on axis 0 by the model assembly and
consumed through `jax.lax.scan` (bounded compile time, production-sane).
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ops as kops
from . import dist
from .config import ModelConfig, YaRNConfig

Params = Dict[str, jax.Array]


def _init(key, shape, scale, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


# ---------------------------------------------------------------------- norms
def init_rmsnorm(d: int, dtype) -> Params:
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(p: Params, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * p["scale"].astype(jnp.float32)).astype(dt)


# ----------------------------------------------------------------------- rope
def _yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def rope_freqs(d_rot: int, theta: float,
               scaling: Optional[YaRNConfig] = None) -> jax.Array:
    """Inverse frequencies of the d_rot / 2 rotated pairs.  With YaRN
    scaling, the pairs that turn more than ``beta_fast`` times over the
    original context keep their frequency, those that turn fewer than
    ``beta_slow`` times are divided by ``factor``, and a linear ramp
    blends the ones between (DeepSeek-V2's ``yarn_find_correction_range``
    and ``yarn_linear_ramp_mask``)."""
    base = theta ** (jnp.arange(0, d_rot, 2, dtype=jnp.float32) / d_rot)
    if scaling is None:
        return 1.0 / base
    s = scaling

    def dim(rotations):
        return (d_rot * math.log(s.original_max_position
                                 / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))
    lo = max(math.floor(dim(s.beta_fast)), 0)
    hi = min(math.ceil(dim(s.beta_slow)), d_rot - 1)
    if lo == hi:
        hi += 0.001
    ramp = jnp.clip((jnp.arange(d_rot // 2, dtype=jnp.float32) - lo)
                    / (hi - lo), 0.0, 1.0)
    keep = 1.0 - ramp
    return (1.0 / (s.factor * base)) * ramp + (1.0 / base) * keep


def softmax_scale(d_qk: int, scaling: Optional[YaRNConfig]) -> float:
    """d_qk ** -0.5, times YaRN's mscale_all_dim factor squared."""
    m = (_yarn_mscale(scaling.factor, scaling.mscale_all_dim)
         if scaling is not None and scaling.mscale_all_dim else 1.0)
    return d_qk ** -0.5 * m * m


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               fraction: float = 1.0,
               scaling: Optional[YaRNConfig] = None) -> jax.Array:
    """Rotary embedding on the first `fraction` of head dims, rotating
    interleaved pairs (0, 1), (2, 3), ...

    x: (..., S, H, D); positions: (..., S) broadcastable.
    """
    d = x.shape[-1]
    d_rot = int(d * fraction)
    d_rot -= d_rot % 2
    if d_rot == 0:
        return x
    xr, xp = x[..., :d_rot], x[..., d_rot:]
    freqs = rope_freqs(d_rot, theta, scaling)           # (d_rot/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs   # (..., S, d_rot/2)
    ang = ang[..., None, :]                               # head axis
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = xr[..., 0::2].astype(jnp.float32), xr[..., 1::2].astype(jnp.float32)
    out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    out = out.reshape(xr.shape).astype(x.dtype)
    return jnp.concatenate([out, xp], axis=-1) if d_rot < d else out


# ------------------------------------------------------------------ embedding
def init_embedding(key, cfg: ModelConfig) -> Params:
    dt = jnp.dtype(cfg.param_dtype)
    k1, k2 = jax.random.split(key)
    v = cfg.padded_vocab
    p = {"tok": _init(k1, (v, cfg.d_model), 0.02, dt)}
    if not cfg.tie_embeddings:
        p["unembed"] = _init(k2, (v, cfg.d_model), cfg.d_model ** -0.5, dt)
    return p


def embed(p: Params, tokens: jax.Array, cfg: ModelConfig) -> jax.Array:
    return p["tok"].astype(jnp.dtype(cfg.compute_dtype))[tokens]


def unembed(p: Params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    w = p.get("unembed", p["tok"]).astype(jnp.dtype(cfg.compute_dtype))
    return jnp.einsum("...d,vd->...v", x, w)


# -------------------------------------------------------------- GQA attention
def init_gqa(key, cfg: ModelConfig, d_model: Optional[int] = None) -> Params:
    d = d_model or cfg.d_model
    H, K, Dh = cfg.n_heads, cfg.n_kv, cfg.d_head
    dt = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 4)
    s = d ** -0.5
    return {
        "wq": _init(ks[0], (d, H, Dh), s, dt),
        "wk": _init(ks[1], (d, K, Dh), s, dt),
        "wv": _init(ks[2], (d, K, Dh), s, dt),
        "wo": _init(ks[3], (H, Dh, d), (H * Dh) ** -0.5, dt),
    }


def gqa_fwd(p: Params, x: jax.Array, cfg: ModelConfig, *,
            positions: jax.Array,
            cache: Optional[Tuple[jax.Array, jax.Array]] = None,
            cache_index: Optional[jax.Array] = None,
            kv_source: Optional[jax.Array] = None,
            causal: bool = True, return_kv: bool = False,
            kv_start: Optional[jax.Array] = None):
    """GQA/MQA attention.  Modes:
       * train/prefill: cache is None, full self-attention over x.
       * decode:        cache=(k,v) with (B,S,K,Dh); writes at cache_index.
       * cross:         kv_source given (encoder memory), no rope on kv.
    kv_start (B,) masks each row's left padding (serving batches).
    Returns (out, new_cache).
    """
    ct = jnp.dtype(cfg.compute_dtype)
    q = dist.constrain_heads(
        jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(ct)))
    src = x if kv_source is None else kv_source
    k = dist.constrain_heads(
        jnp.einsum("bsd,dhk->bshk", src, p["wk"].astype(ct)))
    v = dist.constrain_heads(
        jnp.einsum("bsd,dhk->bshk", src, p["wv"].astype(ct)))
    if kv_source is None:  # rope only for self-attention
        q = apply_rope(q, positions, cfg.rope_theta, cfg.rope_fraction)
        k = apply_rope(k, positions, cfg.rope_theta, cfg.rope_fraction)
    new_cache = None
    if return_kv and cache is None:
        # prefill: emit the cache content directly (no zero buffer to
        # update — a full-size zeros+dynamic-update carry costs ~2x the
        # cache in live temps; see EXPERIMENTS.md deepseek iteration)
        out = kops.attention(q, k, v, causal=causal and kv_source is None,
                             kv_start=kv_start, block_q=cfg.attn_block_q,
                             block_kv=cfg.attn_block_kv)
        out = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(ct))
        return out, (k, v)
    if cache is not None:
        ck, cv = cache
        ck = jax.lax.dynamic_update_slice_in_dim(ck, k.astype(ck.dtype), cache_index, axis=1)
        cv = jax.lax.dynamic_update_slice_in_dim(cv, v.astype(cv.dtype), cache_index, axis=1)
        new_cache = (ck, cv)
        k, v = ck, cv
        kv_len = jnp.asarray(cache_index + x.shape[1], jnp.int32)
        out = kops.attention(q, k, v, causal=False, kv_valid_len=kv_len,
                             kv_start=kv_start, block_q=cfg.attn_block_q,
                             block_kv=cfg.attn_block_kv)
    else:
        out = kops.attention(q, k, v, causal=causal and kv_source is None,
                             block_q=cfg.attn_block_q, block_kv=cfg.attn_block_kv)
    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(ct))
    return out, new_cache


# -------------------------------------------------------------- MLA attention
def init_mla(key, cfg: ModelConfig) -> Params:
    m, d, H = cfg.mla, cfg.d_model, cfg.n_heads
    dt = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 6)
    d_q = m.d_nope + m.d_rope
    if m.q_lora:
        q = {"wq_a": _init(ks[0], (d, m.q_lora), d ** -0.5, dt),
             "wq_b": _init(ks[1], (m.q_lora, H, d_q), m.q_lora ** -0.5, dt)}
    else:
        q = {"wq": _init(ks[0], (d, H, d_q), d ** -0.5, dt)}
    return {
        **q,
        "wkv_a": _init(ks[2], (d, m.kv_lora), d ** -0.5, dt),
        "kv_norm": init_rmsnorm(m.kv_lora, dt),
        "wk_rope": _init(ks[3], (d, m.d_rope), d ** -0.5, dt),
        "wkv_b": _init(ks[4], (m.kv_lora, H, m.d_nope + m.d_v),
                       m.kv_lora ** -0.5, dt),
        "wo": _init(ks[5], (H, m.d_v, d), (H * m.d_v) ** -0.5, dt),
    }


def mla_fwd(p: Params, x: jax.Array, cfg: ModelConfig, *,
            positions: jax.Array,
            cache: Optional[Tuple[jax.Array, jax.Array]] = None,
            cache_index: Optional[jax.Array] = None,
            causal: bool = True, return_kv: bool = False,
            kv_start: Optional[jax.Array] = None):
    """Multi-head latent attention (DeepSeek-V2).

    The kv latent is RMS-normalised before its expansion and before it is
    cached.  Cache stores only (c_kv, k_rope): (B,S,kv_lora) +
    (B,S,d_rope) — the compressed latents.  Decode uses the *absorbed*
    formulation (Wkv_b folded into the query/output) so per-step FLOPs
    scale with kv_lora, not H x (d_nope + d_v).
    """
    m = cfg.mla
    ct = jnp.dtype(cfg.compute_dtype)
    if m.q_lora:
        q = jnp.einsum("bsd,dq->bsq", x, p["wq_a"].astype(ct))
        q = jnp.einsum("bsq,qhk->bshk", q, p["wq_b"].astype(ct))
    else:
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(ct))
    q = dist.constrain_heads(q)
    q_nope, q_rope = q[..., :m.d_nope], q[..., m.d_nope:]
    yarn = cfg.rope_scaling
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta, scaling=yarn)
    c_kv = rmsnorm(p["kv_norm"],
                   jnp.einsum("bsd,dc->bsc", x, p["wkv_a"].astype(ct)),
                   cfg.norm_eps)
    k_rope = jnp.einsum("bsd,dr->bsr", x, p["wk_rope"].astype(ct))
    k_rope = apply_rope(k_rope[:, :, None, :], positions, cfg.rope_theta,
                        scaling=yarn)[:, :, 0]
    scale = softmax_scale(m.d_nope + m.d_rope, yarn)

    if cache is None:
        kv = dist.constrain_heads(
            jnp.einsum("bsc,chk->bshk", c_kv, p["wkv_b"].astype(ct)))
        k_nope, v = kv[..., :m.d_nope], kv[..., m.d_nope:]
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                      (*k_nope.shape[:3], m.d_rope))], -1)
        qf = jnp.concatenate([q_nope, q_rope], -1)
        out = kops.attention(qf, k, v, causal=causal, scale=scale,
                             kv_start=kv_start, block_q=cfg.attn_block_q,
                             block_kv=cfg.attn_block_kv)
        out = jnp.einsum("bshv,hvd->bsd", out, p["wo"].astype(ct))
        return out, ((c_kv, k_rope) if return_kv else None)

    # ---- decode: absorbed attention in compressed space -------------------
    cc, cr = cache
    cc = jax.lax.dynamic_update_slice_in_dim(cc, c_kv.astype(cc.dtype), cache_index, axis=1)
    cr = jax.lax.dynamic_update_slice_in_dim(cr, k_rope.astype(cr.dtype), cache_index, axis=1)
    f32 = jnp.float32
    wb_k = p["wkv_b"].astype(f32)[..., :m.d_nope]        # (c, H, d_nope)
    wb_v = p["wkv_b"].astype(f32)[..., m.d_nope:]        # (c, H, d_v)
    # f32 score math: the latents stay bf16 in HBM (decode is bandwidth-
    # bound); casting after load costs ~nothing and keeps the absorbed
    # formulation numerically equal to the direct one.
    q_abs = jnp.einsum("bshk,chk->bshc", q_nope.astype(f32), wb_k)
    scores = (jnp.einsum("bshc,btc->bhst", q_abs, cc.astype(f32))
              + jnp.einsum("bshr,btr->bhst", q_rope.astype(f32),
                           cr.astype(f32))) * scale
    t = jnp.arange(cc.shape[1])
    qpos = cache_index + jnp.arange(x.shape[1])     # per-query causal mask
    mask = (t[None, :] <= qpos[:, None])[None, None]
    if kv_start is not None:
        mask = mask & (t[None, :] >= kv_start[:, None])[:, None, None]
    scores = jnp.where(mask, scores, -jnp.inf)
    attn = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bhst,btc->bshc", attn, cc.astype(f32))
    out = jnp.einsum("bshc,chv->bshv", ctx, wb_v).astype(ct)  # absorb o-proj
    out = jnp.einsum("bshv,hvd->bsd", out, p["wo"].astype(ct))
    return out, (cc, cr)


# ---------------------------------------------------------------- dense FFN
def init_swiglu(key, d: int, d_ff: int, dtype) -> Params:
    ks = jax.random.split(key, 3)
    return {
        "w_gate": _init(ks[0], (d, d_ff), d ** -0.5, dtype),
        "w_up": _init(ks[1], (d, d_ff), d ** -0.5, dtype),
        "w_down": _init(ks[2], (d_ff, d), d_ff ** -0.5, dtype),
    }


def swiglu_fwd(p: Params, x: jax.Array, compute_dtype) -> jax.Array:
    ct = jnp.dtype(compute_dtype)
    g = jnp.einsum("bsd,df->bsf", x, p["w_gate"].astype(ct))
    u = jnp.einsum("bsd,df->bsf", x, p["w_up"].astype(ct))
    return jnp.einsum("bsf,fd->bsd", jax.nn.silu(g) * u, p["w_down"].astype(ct))
