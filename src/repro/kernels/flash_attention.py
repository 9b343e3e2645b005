"""Flash attention as Pallas TPU kernels: forward and backward.

Layout: the wrapper views q as (B, H, S, D), k as (B, K, S, D) and v as
(B, K, S, Dv), so every block is (1, 1, rows, D or Dv).  Its last two
dimensions are a multiple of 16 and the full head dim, which the TPU
tiling accepts for any head count and d_head (a head axis blocked at 1
in the second-to-last position is refused by the compiler).  The value
width Dv (the output's and dv's) may differ from the q/k width D, as in
latent attention (D 192, Dv 128).  GQA is handled in the k/v
index_map (h -> h // G), so the head broadcast is never materialized in
HBM.

Block schedule.  The queries split into q blocks of ``bq`` rows and the
keys into chunks of ``bc`` rows (``block_q`` and ``block_kv``, each
lowered by :func:`fit_block`).  The grid is (batch, q head, pair) and
runs over the live (q block, kv chunk) pairs only, from a table built
at trace time and scalar-prefetched: for causal attention the pairs at
or below the diagonal, so no grid step is spent on a dead pair.  A pair
runs with no mask unless the diagonal crosses it or ``kv_start[b]``
cuts it; a pair wholly before ``kv_start[b]`` is skipped, and its chunk
index is clamped to the first live one so its step fetches nothing new.
A chunk wider than the q block keeps a step's fixed costs (the pipeline
step, the softmax's per-row statistics) to fewer, larger score tiles,
which on a v5e outweighs the masked area it adds.

Forward: online softmax over a q block's pairs, which follow each other
in the table, with the running (max, denom, acc) in VMEM scratch; the
max and denominator are kept in all 128 lanes of a row, so applying
them to a score tile repeats whole vregs instead of broadcasting a
lane.  The per-row logsumexp is an output so the backward pass can
rebuild the probabilities pair by pair.

Backward (FlashAttention-2): the dq kernel has the forward's grid.  The
dkv kernel visits the same pairs kv-major, accumulating dk/dv over a kv
chunk's q blocks, from the diagonal one to the end.  It works on
transposed scores (kv rows, q rows), so every product is a plain or a
right-transposed one and each q block's logsumexp and delta are one
lane row of (q blocks, bq) tiles, resident whole.  dk/dv come out per
query head (f32); the wrapper sums them over the heads of a kv group.

``kv_start`` (B,) int32, scalar-prefetched into SMEM, masks the keys
before each row's first real token: serving batches are left-padded to a
length the tiling accepts.  Zeros mean no padding.

At trace time ``kernels.attn_chunks`` and ``kernels.attn_chunks_masked``
(:mod:`repro.telemetry`) count the (q block, kv chunk) pairs one call
visits and the ones it masks, summed over heads and batch rows, for
``kv_start`` zero (left padding only drops pairs).

VMEM per grid step at the live shape (S 2,304, bq 384, bc 768; a row
of d 64 pads to 128 lanes, so d 128 takes the same): q, o and dO blocks
of 96 KiB and k, v chunks of 192 KiB, two pipeline buffers each; the
forward's f32 accumulator and max/denominator rows (192 KiB each); the
(384, 1) f32 logsumexp and delta blocks (192 KiB each, a row padded to
128 lanes) or dkv's (6, 384) tiles (12 KiB); dkv's f32 dk, dv chunks
(384 KiB each, accumulator and two output buffers); and the (384, 768)
f32 score tile with its products, 1,152 KiB each.  About 5 MiB for the
forward and 7 MiB for dkv, inside the 16 MiB of scoped VMEM of a v5e.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import telemetry

from .ops import fit_block

NEG_INF = -1e30
_F32 = jnp.float32
_LANES = 128

_NN = (((1,), (0,)), ((), ()))          # a @ b
_NT = (((1,), (1,)), ((), ()))          # a @ b.T


def _keep(q_start, k_start, start, shape, causal: bool, keys: int = 1):
    """Mask of the scores that may attend: keys along axis ``keys``."""
    cols = k_start + jax.lax.broadcasted_iota(jnp.int32, shape, keys)
    keep = cols >= start
    if causal:
        rows = q_start + jax.lax.broadcasted_iota(jnp.int32, shape, 1 - keys)
        keep = keep & (cols <= rows)
    return keep


def _lanes(x, n):
    """A (rows, 128) column whose lanes repeat one value, as (rows, n):
    whole lane tiles are repeated, which moves no data; any other width
    broadcasts the first lane."""
    if n <= _LANES:
        return x[:, :n]
    if n % _LANES == 0:
        return pltpu.repeat(x, n // _LANES, 1)
    return x[:, :1]


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims, preferred_element_type=_F32)


def _split_scale(scale: float):
    """(on the operand, on the scores): a power-of-two scale goes on the
    rows of q (of k in dkv), where it is exact and costs d_head
    multiplies a row instead of one a score; any other stays on the
    scores."""
    return (scale, 1.0) if math.frexp(scale)[0] == 0.5 else (1.0, scale)


def _scaled(x, factor):
    return x if factor == 1.0 else x * factor


# ------------------------------------------------------------ block pairs
def _pairs(nq, bq, nk, bc, offset, causal: bool, kv_major: bool):
    """The (q block, kv chunk) pairs a call visits for ``kv_start`` zero,
    as one flat int32 table of five rows: q block, kv chunk, first and
    last step of the accumulating block, and whether the diagonal crosses
    the pair.  The accumulating side's steps follow each other: q-major
    for the forward and dq, kv-major for dkv."""
    pairs = [(i, j) for i in range(nq) for j in range(nk)
             if not causal or j * bc <= i * bq + offset + bq - 1]
    if kv_major:
        pairs.sort(key=lambda p: (p[1], p[0]))
    acc = [p[1] if kv_major else p[0] for p in pairs]
    n = len(pairs)
    first = [t == 0 or acc[t - 1] != acc[t] for t in range(n)]
    last = [t == n - 1 or acc[t + 1] != acc[t] for t in range(n)]
    diag = [causal and j * bc + bc - 1 > i * bq + offset for i, j in pairs]
    return np.array([[i for i, _ in pairs], [j for _, j in pairs], first,
                     last, diag], np.int32)


def _count_chunks(B: int, H: int, Sq: int, Skv: int, bq: int, bc: int,
                  causal: bool = True):
    """(live, masked) (q block, kv chunk) pairs of one call, for
    ``kv_start`` zero."""
    tab = _pairs(Sq // bq, bq, Skv // bc, bc, Skv - Sq, causal, False)
    return B * H * tab.shape[1], B * H * int(tab[4].sum())


def _step(tab_ref, t, T, start, bc, run):
    """Call ``run(qi, ki, masked)`` for pair ``t`` of the table if it is
    live: the mask is built only where the diagonal or ``kv_start``
    crosses it.  Returns whether ``t`` is its block's last step."""
    qi, ki, diag = tab_ref[t], tab_ref[T + t], tab_ref[4 * T + t]
    live = ki * bc + bc > start
    cut = (diag == 1) | (ki * bc < start)

    @pl.when(live & cut)
    def _masked():
        run(qi, ki, True)

    @pl.when(live & jnp.logical_not(cut))
    def _clean():
        run(qi, ki, False)
    return tab_ref[3 * T + t] == 1


# ------------------------------------------------------------------ forward
def _fwd_kernel(tab_ref, start_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref, *, scale, causal, bq, bc, offset, T):
    b, t = pl.program_id(0), pl.program_id(2)
    start = start_ref[b]
    on_q, on_s = _split_scale(scale)

    @pl.when(tab_ref[2 * T + t] == 1)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def run(qi, ki, masked):
        q = _scaled(q_ref[0, 0], on_q)
        v = v_ref[0, 0]
        s = _scaled(_dot(q, k_ref[0, 0], _NT), on_s)
        if masked:
            keep = _keep(qi * bq + offset, ki * bc, start, s.shape, causal)
            s = jnp.where(keep, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - _lanes(m_new, bc))
        if masked:
            # a row with no live key yet (left padding) keeps l = 0 and
            # ends as zeros, as on the jnp path
            p = jnp.where(keep, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = (acc_ref[...] * _lanes(alpha, acc_ref.shape[1])
                        + _dot(p.astype(v.dtype), v, _NN))
        m_ref[...] = m_new

    @pl.when(_step(tab_ref, t, T, start, bc, run))
    def _finish():
        den = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / _lanes(den, acc_ref.shape[1])
                       ).astype(o_ref.dtype)
        lse_ref[0, 0] = (m_ref[...] + jnp.log(den))[:, :1]


def _specs(G, T, bq, bc, D):
    """Block specs over grid (b, h, pair) of width D: the pair's q block,
    its kv chunk (a chunk wholly before ``kv_start`` maps to the first
    live one, so its step fetches nothing new) and the q block's row
    stats."""
    q = pl.BlockSpec((1, 1, bq, D), lambda b, h, t, tab, st: (b, h, tab[t], 0))
    kv = pl.BlockSpec((1, 1, bc, D), lambda b, h, t, tab, st: (
        b, h // G, jnp.maximum(tab[T + t], st[b] // bc), 0))
    row = pl.BlockSpec((1, 1, bq, 1), lambda b, h, t, tab, st: (b, h, tab[t], 0))
    return q, kv, row


def _fwd(q, k, v, kv_start, causal, scale, bq, bc, interpret):
    B, H, Sq, D = q.shape
    K, Skv, Dv = k.shape[1], k.shape[2], v.shape[3]
    offset = Skv - Sq
    tab = _pairs(Sq // bq, bq, Skv // bc, bc, offset, causal, False)
    T = tab.shape[1]
    q_spec, kv_spec, row_spec = _specs(H // K, T, bq, bc, D)
    o_spec, v_spec, _ = _specs(H // K, T, bq, bc, Dv)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal, bq=bq,
                          bc=bc, offset=offset, T=T),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(B, H, T),
            in_specs=[q_spec, kv_spec, v_spec],
            out_specs=[o_spec, row_spec],
            scratch_shapes=[pltpu.VMEM((bq, Dv), _F32),
                            pltpu.VMEM((bq, _LANES), _F32),
                            pltpu.VMEM((bq, _LANES), _F32)]),
        out_shape=[jax.ShapeDtypeStruct((B, H, Sq, Dv), q.dtype),
                   jax.ShapeDtypeStruct((B, H, Sq, 1), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_attention_fwd",
    )(jnp.asarray(tab.reshape(-1)), kv_start, q, k, v)


# ----------------------------------------------------------------- backward
def _dq_kernel(tab_ref, start_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, d_ref,
               dq_ref, acc_ref, *, scale, causal, bq, bc, offset, T):
    b, t = pl.program_id(0), pl.program_id(2)
    start = start_ref[b]
    on_q, on_s = _split_scale(scale)

    @pl.when(tab_ref[2 * T + t] == 1)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def run(qi, ki, masked):
        q, do, k = _scaled(q_ref[0, 0], on_q), do_ref[0, 0], k_ref[0, 0]
        lse, delta = (_lanes(jnp.broadcast_to(r[0, 0], (bq, _LANES)), bc)
                      for r in (lse_ref, d_ref))
        p = jnp.exp(_scaled(_dot(q, k, _NT), on_s) - lse)
        if masked:
            p = jnp.where(_keep(qi * bq + offset, ki * bc, start, p.shape,
                                causal), p, 0.0)
        ds = p * (_dot(do, v_ref[0, 0], _NT) - delta)
        acc_ref[...] += _dot(ds.astype(k.dtype), k, _NN)

    @pl.when(_step(tab_ref, t, T, start, bc, run))
    def _finish():
        dq_ref[0, 0] = (acc_ref[...] * scale).astype(dq_ref.dtype)


def _dkv_kernel(tab_ref, start_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                d_ref, dk_ref, dv_ref, dk_acc, dv_acc, *, scale, causal, bq,
                bc, offset, T):
    b, t = pl.program_id(0), pl.program_id(2)
    start = start_ref[b]
    on_k, on_s = _split_scale(scale)

    @pl.when(tab_ref[2 * T + t] == 1)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def run(qi, ki, masked):
        k, v = _scaled(k_ref[0, 0], on_k), v_ref[0, 0]
        q, do = q_ref[0, 0], do_ref[0, 0]
        # transposed scores (kv rows, q rows): the q rows' statistics are
        # a lane row of the (q blocks, bq) tiles
        p = jnp.exp(_scaled(_dot(k, q, _NT), on_s)
                    - lse_ref[0, 0, pl.ds(qi, 1), :])
        if masked:
            p = jnp.where(_keep(qi * bq + offset, ki * bc, start, p.shape,
                                causal, keys=0), p, 0.0)
        dv_acc[...] += _dot(p.astype(do.dtype), do, _NN)
        ds = p * (_dot(v, do, _NT) - d_ref[0, 0, pl.ds(qi, 1), :])
        dk_acc[...] += _dot(ds.astype(q.dtype), q, _NN)

    @pl.when(_step(tab_ref, t, T, start, bc, run))
    def _finish():
        dk_ref[0, 0] = dk_acc[...] * scale
        dv_ref[0, 0] = dv_acc[...]


def _bwd_calls(q, k, v, kv_start, o, lse, do, causal, scale, bq, bc,
               interpret):
    B, H, Sq, D = q.shape
    K, Skv, Dv = k.shape[1], k.shape[2], v.shape[3]
    G, nq, offset = H // K, Sq // bq, Skv - Sq
    delta = jnp.sum(do.astype(_F32) * o.astype(_F32), axis=-1, keepdims=True)
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))
    kw = dict(scale=scale, causal=causal, bq=bq, bc=bc, offset=offset)

    # dq: pairs q-major
    tab = _pairs(nq, bq, Skv // bc, bc, offset, causal, False)
    T = tab.shape[1]
    q_spec, kv_spec, row_spec = _specs(G, T, bq, bc, D)
    o_spec, v_spec, _ = _specs(G, T, bq, bc, Dv)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, T=T, **kw),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(B, H, T),
            in_specs=[q_spec, kv_spec, v_spec, o_spec, row_spec, row_spec],
            out_specs=q_spec,
            scratch_shapes=[pltpu.VMEM((bq, D), _F32)]),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=params, interpret=interpret,
        name="flash_attention_dq",
    )(jnp.asarray(tab.reshape(-1)), kv_start, q, k, v, do, lse, delta)

    # dk/dv per query head: pairs kv-major, the q side's row stats resident
    tab = _pairs(nq, bq, Skv // bc, bc, offset, causal, True)
    T = tab.shape[1]

    def specs(width):
        q = pl.BlockSpec((1, 1, bq, width),
                         lambda b, h, t, tab, st: (b, h, tab[t], 0))
        kv = pl.BlockSpec((1, 1, bc, width),
                          lambda b, h, t, tab, st: (b, h // G, tab[T + t], 0))
        out = pl.BlockSpec((1, 1, bc, width),
                           lambda b, h, t, tab, st: (b, h, tab[T + t], 0))
        return q, kv, out
    q_spec, kv_spec, dk_spec = specs(D)
    o_spec, v_spec, dv_spec = specs(Dv)
    row_spec = pl.BlockSpec((1, 1, nq, bq), lambda b, h, t, tab, st: (b, h, 0, 0))
    tiles = lambda x: x.reshape(B, H, nq, bq)               # noqa: E731
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, T=T, **kw),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(B, H, T),
            in_specs=[q_spec, kv_spec, v_spec, o_spec, row_spec, row_spec],
            out_specs=[dk_spec, dv_spec],
            scratch_shapes=[pltpu.VMEM((bc, D), _F32),
                            pltpu.VMEM((bc, Dv), _F32)]),
        out_shape=[jax.ShapeDtypeStruct((B, H, Skv, D), _F32),
                   jax.ShapeDtypeStruct((B, H, Skv, Dv), _F32)],
        compiler_params=params, interpret=interpret,
        name="flash_attention_dkv",
    )(jnp.asarray(tab.reshape(-1)), kv_start, q, k, v, do, tiles(lse),
      tiles(delta))
    group_sum = lambda x: x.reshape(B, K, G, Skv, -1).sum(2)  # noqa: E731
    return dq, group_sum(dk).astype(k.dtype), group_sum(dv).astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _attend(q, k, v, kv_start, causal, scale, bq, bc, interpret):
    return _fwd(q, k, v, kv_start, causal, scale, bq, bc, interpret)[0]


def _attend_fwd(q, k, v, kv_start, causal, scale, bq, bc, interpret):
    o, lse = _fwd(q, k, v, kv_start, causal, scale, bq, bc, interpret)
    return o, (q, k, v, kv_start, o, lse)


def _attend_bwd(causal, scale, bq, bc, interpret, res, do):
    q, k, v, kv_start, o, lse = res
    dq, dk, dv = _bwd_calls(q, k, v, kv_start, o, lse, do, causal, scale,
                            bq, bc, interpret)
    return dq, dk, dv, None


_attend.defvjp(_attend_fwd, _attend_bwd)


@functools.partial(jax.jit, static_argnames=("causal", "scale", "block_q",
                                             "block_kv", "interpret"))
def flash_attention(q, k, v, kv_start=None, *, causal: bool = True,
                    scale=None, block_q: int = 512, block_kv: int = 1024,
                    interpret: bool = False):
    """q: (B, Sq, H, D); k: (B, Skv, K, D); v: (B, Skv, K, Dv) with
    H % K == 0.  Returns (B, Sq, H, Dv).

    ``block_q``/``block_kv`` are upper bounds: each is lowered to the
    largest multiple of 16 that divides its length (:func:`fit_block`);
    a length with no such block raises ValueError.  ``kv_start`` (B,)
    masks keys before each row's first real token.  Differentiable.
    """
    B, Sq, H, D = q.shape
    Skv = k.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    bq, bc = fit_block(Sq, block_q), fit_block(Skv, block_kv)
    if not (bq and bc):
        raise ValueError(f"no TPU block fits seq lengths {(Sq, Skv)}")
    live, masked = _count_chunks(B, H, Sq, Skv, bq, bc, causal)
    telemetry.count("kernels.attn_chunks", live)
    telemetry.count("kernels.attn_chunks_masked", masked)
    if kv_start is None:
        kv_start = jnp.zeros((B,), jnp.int32)
    heads_major = lambda x: jnp.swapaxes(x, 1, 2)           # noqa: E731
    o = _attend(heads_major(q), heads_major(k), heads_major(v),
                jnp.asarray(kv_start, jnp.int32), causal, scale, bq, bc,
                interpret)
    return heads_major(o)
