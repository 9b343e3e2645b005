"""Grouped matrix products of a dropless expert layer, as Pallas TPU kernels.

The rows of the left operand come grouped by expert: group ``e`` holds
``counts[e]`` rows and starts on a tile of ``tm`` rows, so each row tile
belongs to one expert and no tile is masked.  Rows after the last
group's tile (the rest of the static bound) are never visited: the grid's
row axis is as long as the live tiles, a number known only on the
device, so the work follows the rows routed and not the bound.  The
output rows past the live tiles are left unwritten.

- :func:`gmm` (``moe_gmm``): ``out[r] = lhs[r] @ rhs[e(r)]`` (or
  ``@ rhs[e(r)].T``), over grid (column block, live row tile).  A column
  block of one expert's weights stays in VMEM while that expert's tiles
  pass, so the weights are read once per column block.
- :func:`tgmm` (``moe_tgmm``): the weights' gradient,
  ``out[e] = lhs[rows of e].T @ dout[rows of e]``, over grid (row block
  of the result, column block, live row tile), summing an expert's tiles
  in an f32 accumulator.  An expert with no rows gets no tile; the
  caller zeroes its block.

The group table (each tile's expert) and the live tile count are
scalar-prefetched.  Interpret mode runs the same kernels on the CPU.
``kernels.moe_gmm`` and ``kernels.moe_tgmm`` (:mod:`repro.telemetry`)
count the grouped products traced, as ``kernels.ops`` calls them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import telemetry

_F32 = jnp.float32
#: VMEM a call may use: an expert's (2,048 x 1,408) bf16 weight block,
#: double-buffered, with row tiles and the f32 product, is about 18 MiB
_VMEM = 64 * 1024 * 1024
#: the largest weight block (bytes) a column block may hold
_BLOCK_BYTES = 8 * 1024 * 1024
#: the largest f32 accumulator (bytes) of tgmm's result block
_ACC_BYTES = 4 * 1024 * 1024


def _split(n: int, other: int, itemsize: int, budget: int) -> int:
    """The widest block of ``n`` columns (``n`` halved while it stays a
    multiple of 128) with ``other x block x itemsize`` within budget."""
    b = n
    while other * b * itemsize > budget and b % 256 == 0:
        b //= 2
    return b


def tile_groups(counts: jax.Array, n_tiles: int, tm: int):
    """(each tile's group (n_tiles,), live tiles (1,)) for groups of
    ``counts`` rows, each starting on a tile."""
    ends = jnp.cumsum(-(-counts // tm))
    group = jnp.searchsorted(ends, jnp.arange(n_tiles), side="right")
    group = jnp.minimum(group, counts.shape[0] - 1).astype(jnp.int32)
    return group, ends[-1:].astype(jnp.int32)


# ------------------------------------------------------------------ gmm
def _gmm_kernel(group_ref, live_ref, lhs_ref, rhs_ref, out_ref, *,
                transpose):
    dims = (((1,), (1,) if transpose else (0,)), ((), ()))
    out_ref[...] = jax.lax.dot_general(
        lhs_ref[...], rhs_ref[0], dims,
        preferred_element_type=_F32).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tm", "transpose", "interpret"))
def gmm(lhs, rhs, counts, *, tm: int, transpose: bool = False,
        interpret: bool = False):
    """lhs (M, K) grouped by ``counts`` (E,); rhs (E, K, N), or (E, N, K)
    with ``transpose``.  Returns (M, N) in lhs's dtype."""
    M, K = lhs.shape
    N = rhs.shape[1] if transpose else rhs.shape[2]
    group, live = tile_groups(counts, M // tm, tm)
    tn = _split(N, K, rhs.dtype.itemsize, _BLOCK_BYTES)
    if transpose:
        rhs_spec = pl.BlockSpec((1, tn, K), lambda j, t, g, n: (g[t], j, 0))
    else:
        rhs_spec = pl.BlockSpec((1, K, tn), lambda j, t, g, n: (g[t], 0, j))
    return pl.pallas_call(
        functools.partial(_gmm_kernel, transpose=transpose),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(N // tn, live[0]),
            in_specs=[pl.BlockSpec((tm, K), lambda j, t, g, n: (t, 0)),
                      rhs_spec],
            out_specs=pl.BlockSpec((tm, tn), lambda j, t, g, n: (t, j))),
        out_shape=jax.ShapeDtypeStruct((M, N), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM),
        interpret=interpret,
        name="moe_gmm",
    )(group, live, lhs, rhs)


# ----------------------------------------------------------------- tgmm
def _tgmm_kernel(group_ref, live_ref, lhs_ref, dout_ref, out_ref, acc_ref):
    t = pl.program_id(2)
    g = group_ref[t]

    @pl.when((t == 0) | (group_ref[jnp.maximum(t - 1, 0)] != g))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        lhs_ref[...], dout_ref[...], (((0,), (0,)), ((), ())),
        preferred_element_type=_F32)

    last = pl.num_programs(2) - 1

    @pl.when((t == last) | (group_ref[jnp.minimum(t + 1, last)] != g))
    def _store():
        out_ref[0] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tm", "n_groups", "interpret"))
def tgmm(lhs, dout, counts, *, tm: int, n_groups: int,
         interpret: bool = False):
    """lhs (M, K) and dout (M, N) grouped by ``counts`` (E,).  Returns
    (E, K, N) in lhs's dtype; a group with no rows is left unwritten."""
    M, K = lhs.shape
    N = dout.shape[1]
    group, live = tile_groups(counts, M // tm, tm)
    tn = _split(N, K, 4, _ACC_BYTES)
    tk = _split(K, tn, 4, _ACC_BYTES)
    return pl.pallas_call(
        _tgmm_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(K // tk, N // tn, live[0]),
            in_specs=[pl.BlockSpec((tm, tk), lambda i, j, t, g, n: (t, i)),
                      pl.BlockSpec((tm, tn), lambda i, j, t, g, n: (t, j))],
            out_specs=pl.BlockSpec((1, tk, tn),
                                   lambda i, j, t, g, n: (g[t], i, j)),
            scratch_shapes=[pltpu.VMEM((tk, tn), _F32)]),
        out_shape=jax.ShapeDtypeStruct((n_groups, K, N), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM),
        interpret=interpret,
        name="moe_tgmm",
    )(group, live, lhs, dout)


# ------------------------------------------------------------ with a vjp
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def grouped_matmul(lhs, rhs, counts, tm: int, interpret: bool = False):
    """Differentiable :func:`gmm`: dlhs is a transposed ``moe_gmm``,
    drhs a ``moe_tgmm``."""
    return gmm(lhs, rhs, counts, tm=tm, interpret=interpret)


def _grouped_fwd(lhs, rhs, counts, tm, interpret):
    return grouped_matmul(lhs, rhs, counts, tm, interpret), (lhs, rhs,
                                                             counts)


def _grouped_bwd(tm, interpret, res, dout):
    lhs, rhs, counts = res
    telemetry.count("kernels.moe_gmm")
    telemetry.count("kernels.moe_tgmm")
    dlhs = gmm(dout, rhs, counts, tm=tm, transpose=True, interpret=interpret)
    drhs = tgmm(lhs, dout, counts, tm=tm, n_groups=rhs.shape[0],
                interpret=interpret)
    drhs = jnp.where(counts[:, None, None] > 0, drhs, 0).astype(rhs.dtype)
    return dlhs, drhs, None


grouped_matmul.defvjp(_grouped_fwd, _grouped_bwd)
