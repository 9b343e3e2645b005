"""Flash-decode: single-token attention against a long KV cache (Pallas).

The decode cells' arithmetic intensity is ~2 flops/byte — the kernel's job
is to stream the cache through VMEM exactly once at full HBM bandwidth
while accumulating the online-softmax stats in scratch.

Layout: the (B, S, K, D) cache is viewed, for free, as (B, S, K*D), and a
block holds every kv head of ``block_kv`` positions: (1, block_kv, K*D),
whose last two dimensions the TPU tiling accepts for any K and d_head.
Each query head is expanded to K*D lanes that are zero outside its own kv
group, so one (H, K*D) x (K*D, block_kv) matmul yields every head's
scores against its own group; the wrapper picks each head's group out of
the (H, K*D) output.  That spends K times the score FLOPs, which a
bandwidth-bound decode step does not notice.  Grid (batch, kv_blocks)
with the kv dimension innermost-sequential.

Valid-length masking (cache filled up to ``kv_valid_len``) and padding
masking (keys before ``kv_start[b]``, the left padding of a serving
batch) are block-exact: blocks with no live key are skipped with pl.when.

VMEM per step: k,v tiles 2 x block_kv x K*D (double-buffered), acc
H x K*D f32, stats 2 x H x 1 f32 (e.g. 2 x 1024 x 128 bf16 ~ 1 MB).
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


def _kernel(vlen_ref, start_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref,
            l_ref, *, block_kv: int):
    b, ki = pl.program_id(0), pl.program_id(1)
    vlen = vlen_ref[0]
    start = start_ref[b]
    k_start = ki * block_kv

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when((k_start < vlen) & (k_start + block_kv > start))
    def _step():
        v = v_ref[0]                                        # (T, K*D)
        s = jax.lax.dot_general(q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
                                preferred_element_type=_F32)  # (H, T)
        t_abs = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where((t_abs < vlen) & (t_abs >= start), s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=_F32)
        m_ref[...] = m_new

    @pl.when(ki == pl.num_programs(1) - 1)
    def _finish():
        den = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / den).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "block_kv",
                                             "interpret"))
def flash_decode(q, k, v, kv_valid_len, kv_start=None, *, scale=None,
                 block_kv: int = 1024, interpret: bool = False):
    """q: (B, 1, H, D); k/v: (B, S, K, D); kv_valid_len: () int32;
    kv_start: (B,) int32 or None.  Returns (B, 1, H, D).

    ``block_kv`` is an upper bound, lowered by :func:`fit_block`; a cache
    length with no fitting block raises ValueError.
    """
    B, sq, H, D = q.shape
    assert sq == 1, "decode kernel is single-token"
    _, S, K, Dv = v.shape
    assert Dv == D, "decode kernel needs d_k == d_v"
    G = H // K
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    bkv = fit_block(S, block_kv)
    if not bkv:
        raise ValueError(f"no TPU block fits cache length {S}")
    if kv_start is None:
        kv_start = jnp.zeros((B,), jnp.int32)
    # query head h -> its kv group's lanes of a (K*D,) row, zeros elsewhere
    own = (jnp.arange(H)[:, None] // G == jnp.arange(K)[None, :])  # (H, K)
    qx = (q[:, 0, :, None, :] * scale) * own[None, :, :, None].astype(q.dtype)
    KD = K * D
    spec_q = pl.BlockSpec((1, H, KD), lambda b, j, vl, st: (b, 0, 0))
    spec_kv = pl.BlockSpec((1, bkv, KD), lambda b, j, vl, st: (b, j, 0))
    out = pl.pallas_call(
        functools.partial(_kernel, block_kv=bkv),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, S // bkv),
            in_specs=[spec_q, spec_kv, spec_kv],
            out_specs=spec_q,
            scratch_shapes=[pltpu.VMEM((H, KD), _F32),
                            pltpu.VMEM((H, 1), _F32),
                            pltpu.VMEM((H, 1), _F32)]),
        out_shape=jax.ShapeDtypeStruct((B, H, KD), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="flash_decode",
    )(jnp.asarray(kv_valid_len, jnp.int32).reshape(1),
      jnp.asarray(kv_start, jnp.int32), qx.reshape(B, H, KD),
      k.reshape(B, S, KD), v.reshape(B, S, KD))
    out = out.reshape(B, H, K, D)
    out = jnp.sum(out * own[None, :, :, None].astype(out.dtype), axis=2)
    return out[:, None]
