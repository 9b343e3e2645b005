"""Mamba-2 SSD chunked scan as a Pallas TPU kernel.

Grid (batch, heads, chunks) with the chunk dimension innermost-sequential:
the inter-chunk SSM state (d_head x d_state, fp32) lives in VMEM scratch
and is carried across the chunk iterations, so the HBM traffic is exactly
one read of (x, dt, B, C) and one write of y per token — the kernel is
bandwidth-optimal for the training/prefill pass.

Within a chunk the computation is the quadratic "attention form" of SSD:
  y[t] = C_t . (sum_{u<=t} dA(u->t) dt_u B_u x_u) + dA(0->t) . state_in
tiled to (chunk x chunk) gates on the VPU and (chunk x d_state) x
(d_state x d_head) matmuls on the MXU.

VMEM per step (chunk=256, p=64, n=64):
  x 256x64, B/C 256x64, gates 256x256 f32, state 64x64 f32  ~ 0.6 MB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, o_ref, state_ref, *,
            chunk: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0, :, 0, :].astype(jnp.float32)        # (c, p)
    dt = dt_ref[0, :, 0].astype(jnp.float32)         # (c,)
    A = a_ref[0]                                     # scalar (per head)
    B = b_ref[0].astype(jnp.float32)                 # (c, n)
    C = c_ref[0].astype(jnp.float32)                 # (c, n)
    D = d_ref[0]

    la = dt * A                                      # log decay per step, <= 0
    cs = jnp.cumsum(la)                              # within-chunk cumulative
    # ---- intra-chunk attention form -----------------------------------------
    seg = cs[:, None] - cs[None, :]                  # decay u -> t
    rows = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    gate = jnp.exp(jnp.where(cols <= rows, seg, -1e30))
    cb = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())))   # (c, c)
    w = cb * gate
    xdt = x * dt[:, None]
    y = jax.lax.dot_general(w, xdt, (((1,), (0,)), ((), ())))  # (c, p)
    # ---- inter-chunk contribution -------------------------------------------
    state = state_ref[...]                           # (n, p)
    y += jnp.exp(cs)[:, None] * jax.lax.dot_general(
        C, state, (((1,), (0,)), ((), ())))
    # ---- update carried state ----------------------------------------------
    total = cs[chunk - 1]
    decay_to_end = jnp.exp(total - cs)               # (c,)
    state_ref[...] = state * jnp.exp(total) + jax.lax.dot_general(
        B * (decay_to_end * dt)[:, None], x, (((0,), (0,)), ((), ())))
    o_ref[0, :, 0, :] = (y + x * D).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, A, B, C, D, *, chunk: int = 256, interpret: bool = False):
    """Shapes as kernels.ref.naive_ssd: x (b,s,h,p), dt (b,s,h), A (h,),
    B/C (b,s,n), D (h,).  s must divide by chunk."""
    b, s, h, p = x.shape
    n = B.shape[-1]
    chunk = min(chunk, s)
    assert s % chunk == 0
    nc = s // chunk
    grid = (b, h, nc)
    kern = functools.partial(_kernel, chunk=chunk)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, 1, p), lambda i, j, c: (i, c, j, 0)),
            pl.BlockSpec((1, chunk, 1), lambda i, j, c: (i, c, j)),
            pl.BlockSpec((1,), lambda i, j, c: (j,)),
            pl.BlockSpec((1, chunk, n), lambda i, j, c: (i, c, 0)),
            pl.BlockSpec((1, chunk, n), lambda i, j, c: (i, c, 0)),
            pl.BlockSpec((1,), lambda i, j, c: (j,)),
        ],
        out_specs=pl.BlockSpec((1, chunk, 1, p), lambda i, j, c: (i, c, j, 0)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, dt, A, B, C, D)
