"""Flash attention as Pallas TPU kernels: forward and backward.

Layout: the wrapper views q as (B, H, S, D) and k/v as (B, K, S, D), so
every block is (1, 1, block, D).  Its last two dimensions are a multiple
of 16 and the full head dim, which the TPU tiling accepts for any head
count and d_head (a head axis blocked at 1 in the second-to-last
position is refused by the compiler).  GQA is handled in the k/v
index_map (h -> h // G), so kv tiles are fetched per query head without
materializing the head broadcast in HBM.

Forward: online softmax over grid (batch, q_heads, q_blocks, kv_blocks),
kv innermost-sequential ("arbitrary"), running (max, denom, acc) in VMEM
scratch.  Fully masked kv blocks are skipped with pl.when (no MXU work);
diagonal blocks apply the triangular mask.  The per-row logsumexp is an
output so the backward pass can rebuild the probabilities blockwise.

Backward (FlashAttention-2): one kernel accumulates dq over kv blocks,
one accumulates dk/dv over q blocks for each query head; the wrapper sums
dk/dv over the heads of a kv group.

``kv_start`` (B,) int32, scalar-prefetched into SMEM, masks the keys
before each row's first real token: serving batches are left-padded to a
length the tiling accepts.  Zeros mean no padding.

VMEM per forward step (bf16, block_q=512, block_kv=1024, d=128):
    q 512x128, k/v 1024x128 each (double-buffered), acc 512x128 f32,
    stats 2 x 512x1 f32  ~ 1.7 MB << the 16 MB scoped VMEM of a v5e.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ops import fit_block

NEG_INF = -1e30
_F32 = jnp.float32


def _keep(q_start, k_start, start, shape, causal: bool):
    """Mask of the (block_q, block_kv) scores that may attend."""
    cols = k_start + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    keep = cols >= start
    if causal:
        rows = q_start + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        keep = keep & (cols <= rows)
    return keep


def _runs(q_start, k_start, start, block_q, block_kv, causal: bool):
    """Whether a (q block, kv block) pair holds any unmasked score."""
    run = k_start + block_kv > start
    if causal:
        run = run & (k_start <= q_start + block_q - 1)
    return run


def _scores(q, k, scale):
    return jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                               preferred_element_type=_F32) * scale


# ------------------------------------------------------------------ forward
def _fwd_kernel(start_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref, *, scale, causal, block_q, block_kv,
                offset):
    b, qi, ki = pl.program_id(0), pl.program_id(2), pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = qi * block_q + offset       # absolute query positions
    k_start = ki * block_kv
    start = start_ref[b]

    @pl.when(_runs(q_start, k_start, start, block_q, block_kv, causal))
    def _step():
        v = v_ref[0, 0]
        s = _scores(q_ref[0, 0], k_ref[0, 0], scale)
        keep = _keep(q_start, k_start, start, s.shape, causal)
        s = jnp.where(keep, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        # a row with no live key yet (left padding) keeps l = 0 and ends
        # as zeros, as on the jnp path
        p = jnp.where(keep, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=_F32)
        m_ref[...] = m_new

    @pl.when(ki == pl.num_programs(3) - 1)
    def _finish():
        den = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / den).astype(o_ref.dtype)
        lse_ref[0, 0] = m_ref[...] + jnp.log(den)


def _fwd(q, k, v, kv_start, causal, scale, block_q, block_kv, interpret):
    B, H, Sq, D = q.shape
    K, Skv = k.shape[1], k.shape[2]
    G = H // K
    kern = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                             block_q=block_q, block_kv=block_kv,
                             offset=Skv - Sq)
    q_spec = pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j, s: (b, h, i, 0))
    kv_spec = pl.BlockSpec((1, 1, block_kv, D),
                           lambda b, h, i, j, s: (b, h // G, j, 0))
    row_spec = pl.BlockSpec((1, 1, block_q, 1),
                            lambda b, h, i, j, s: (b, h, i, 0))
    return pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, H, Sq // block_q, Skv // block_kv),
            in_specs=[q_spec, kv_spec, kv_spec],
            out_specs=[q_spec, row_spec],
            scratch_shapes=[pltpu.VMEM((block_q, D), _F32),
                            pltpu.VMEM((block_q, 1), _F32),
                            pltpu.VMEM((block_q, 1), _F32)]),
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((B, H, Sq, 1), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="flash_attention_fwd",
    )(kv_start, q, k, v)


# ----------------------------------------------------------------- backward
def _probs(q_ref, k_ref, lse_ref, q_start, k_start, start, scale, causal):
    s = _scores(q_ref[0, 0], k_ref[0, 0], scale)
    keep = _keep(q_start, k_start, start, s.shape, causal)
    return jnp.where(keep, jnp.exp(s - lse_ref[0, 0]), 0.0)


def _dq_kernel(start_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, d_ref,
               dq_ref, acc_ref, *, scale, causal, block_q, block_kv, offset):
    b, qi, ki = pl.program_id(0), pl.program_id(2), pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * block_q + offset
    k_start = ki * block_kv
    start = start_ref[b]

    @pl.when(_runs(q_start, k_start, start, block_q, block_kv, causal))
    def _step():
        k = k_ref[0, 0]
        p = _probs(q_ref, k_ref, lse_ref, q_start, k_start, start, scale,
                   causal)
        dp = _scores(do_ref[0, 0], v_ref[0, 0], 1.0)
        ds = p * (dp - d_ref[0, 0])
        acc_ref[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=_F32) * scale

    @pl.when(ki == pl.num_programs(3) - 1)
    def _finish():
        dq_ref[0, 0] = acc_ref[...].astype(dq_ref.dtype)


def _dkv_kernel(start_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, d_ref,
                dk_ref, dv_ref, dk_acc, dv_acc, *, scale, causal, block_q,
                block_kv, offset):
    b, ki, qi = pl.program_id(0), pl.program_id(2), pl.program_id(3)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q_start = qi * block_q + offset
    k_start = ki * block_kv
    start = start_ref[b]

    @pl.when(_runs(q_start, k_start, start, block_q, block_kv, causal))
    def _step():
        q, do = q_ref[0, 0], do_ref[0, 0]
        p = _probs(q_ref, k_ref, lse_ref, q_start, k_start, start, scale,
                   causal)
        tn = (((0,), (0,)), ((), ()))              # contract the q rows
        dv_acc[...] += jax.lax.dot_general(p.astype(do.dtype), do, tn,
                                           preferred_element_type=_F32)
        dp = _scores(do, v_ref[0, 0], 1.0)
        ds = p * (dp - d_ref[0, 0])
        dk_acc[...] += jax.lax.dot_general(ds.astype(q.dtype), q, tn,
                                           preferred_element_type=_F32) * scale

    @pl.when(qi == pl.num_programs(3) - 1)
    def _finish():
        dk_ref[0, 0] = dk_acc[...]
        dv_ref[0, 0] = dv_acc[...]


def _bwd_calls(q, k, v, kv_start, o, lse, do, causal, scale, block_q,
               block_kv, interpret):
    B, H, Sq, D = q.shape
    K, Skv = k.shape[1], k.shape[2]
    G = H // K
    nq, nk = Sq // block_q, Skv // block_kv
    delta = jnp.sum(do.astype(_F32) * o.astype(_F32), axis=-1, keepdims=True)
    kw = dict(scale=scale, causal=causal, block_q=block_q, block_kv=block_kv,
              offset=Skv - Sq)
    args = (kv_start, q, k, v, do, lse, delta)

    # dq: grid (b, h, q block, kv block)
    q_spec = pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j, s: (b, h, i, 0))
    kv_spec = pl.BlockSpec((1, 1, block_kv, D),
                           lambda b, h, i, j, s: (b, h // G, j, 0))
    row_spec = pl.BlockSpec((1, 1, block_q, 1),
                            lambda b, h, i, j, s: (b, h, i, 0))
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, **kw),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B, H, nq, nk),
            in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
            out_specs=q_spec,
            scratch_shapes=[pltpu.VMEM((block_q, D), _F32)]),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret, name="flash_attention_dq",
    )(*args)

    # dk/dv per query head: grid (b, h, kv block, q block)
    q_spec = pl.BlockSpec((1, 1, block_q, D), lambda b, h, j, i, s: (b, h, i, 0))
    kv_spec = pl.BlockSpec((1, 1, block_kv, D),
                           lambda b, h, j, i, s: (b, h // G, j, 0))
    row_spec = pl.BlockSpec((1, 1, block_q, 1),
                            lambda b, h, j, i, s: (b, h, i, 0))
    out_spec = pl.BlockSpec((1, 1, block_kv, D),
                            lambda b, h, j, i, s: (b, h, j, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, **kw),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B, H, nk, nq),
            in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
            out_specs=[out_spec, out_spec],
            scratch_shapes=[pltpu.VMEM((block_kv, D), _F32),
                            pltpu.VMEM((block_kv, D), _F32)]),
        out_shape=[jax.ShapeDtypeStruct((B, H, Skv, D), _F32)] * 2,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret, name="flash_attention_dkv",
    )(*args)
    group_sum = lambda x: x.reshape(B, K, G, Skv, D).sum(2)   # noqa: E731
    return dq, group_sum(dk).astype(k.dtype), group_sum(dv).astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _attend(q, k, v, kv_start, causal, scale, block_q, block_kv, interpret):
    return _fwd(q, k, v, kv_start, causal, scale, block_q, block_kv,
                interpret)[0]


def _attend_fwd(q, k, v, kv_start, causal, scale, block_q, block_kv,
                interpret):
    o, lse = _fwd(q, k, v, kv_start, causal, scale, block_q, block_kv,
                  interpret)
    return o, (q, k, v, kv_start, o, lse)


def _attend_bwd(causal, scale, block_q, block_kv, interpret, res, do):
    q, k, v, kv_start, o, lse = res
    dq, dk, dv = _bwd_calls(q, k, v, kv_start, o, lse, do, causal, scale,
                            block_q, block_kv, interpret)
    return dq, dk, dv, None


_attend.defvjp(_attend_fwd, _attend_bwd)


@functools.partial(jax.jit, static_argnames=("causal", "scale", "block_q",
                                             "block_kv", "interpret"))
def flash_attention(q, k, v, kv_start=None, *, causal: bool = True,
                    scale=None, block_q: int = 512, block_kv: int = 1024,
                    interpret: bool = False):
    """q: (B, Sq, H, D); k/v: (B, Skv, K, D) with H % K == 0.

    ``block_q``/``block_kv`` are upper bounds: each is lowered to the
    largest multiple of 16 that divides its length (:func:`fit_block`);
    a length with no such block raises ValueError.  ``kv_start`` (B,)
    masks keys before each row's first real token.  Differentiable.
    """
    B, Sq, H, D = q.shape
    Skv = k.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    bq, bkv = fit_block(Sq, block_q), fit_block(Skv, block_kv)
    if not (bq and bkv):
        raise ValueError(f"no TPU block fits seq lengths {(Sq, Skv)}")
    if kv_start is None:
        kv_start = jnp.zeros((B,), jnp.int32)
    heads_major = lambda x: jnp.swapaxes(x, 1, 2)           # noqa: E731
    o = _attend(heads_major(q), heads_major(k), heads_major(v),
                jnp.asarray(kv_start, jnp.int32), causal, scale, bq, bkv,
                interpret)
    return heads_major(o)
