"""Train step: grad + AdamW, with microbatch accumulation and optional
int8 gradient compression (error feedback) for the cross-pod reduction.

Distributed-optimization knobs (DESIGN.md §5):
  * microbatches > 1   — gradient accumulation via lax.scan (activation
    memory / pipeline-style overlap lever).
  * compress_grads     — simulate-able int8 quantized all-reduce with error
    feedback: quantize per-tensor, dequantize, residual kept in fp32 state.
    On real multi-host meshes the quantized tensor is what crosses the pod
    link (XLA reduces the int8->fp32 dequantized values; bytes recorded in
    the roofline as 1/4 of fp32).
"""
from __future__ import annotations

from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import TrainBatch, loss_fn
from repro.models.model import MOE_METRICS
from repro.models.config import ModelConfig
from .optimizer import AdamW, AdamWState


class TrainState(NamedTuple):
    params: Any
    opt: AdamWState
    ef: Any = None          # error-feedback residuals (compression only)


def make_train_state(params, opt: AdamW, compress: bool = False) -> TrainState:
    ef = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params) \
        if compress else None
    return TrainState(params=params, opt=opt.init(params), ef=ef)


def _quantize_int8(g):
    amax = jnp.max(jnp.abs(g)) + 1e-12
    q = jnp.clip(jnp.round(g / amax * 127.0), -127, 127).astype(jnp.int8)
    return q, amax


def _dequantize_int8(q, amax):
    return q.astype(jnp.float32) * (amax / 127.0)


def make_train_step(cfg: ModelConfig, opt: AdamW, *,
                    microbatches: int = 1, compress_grads: bool = False,
                    grad_shardings=None):
    """Returns train_step(state, batch) -> (state, metrics).

    `grad_shardings` (optional NamedSharding pytree matching params) pins
    the accumulated-gradient layout so XLA's scan partitioner cannot drift
    into involuntary resharding inside the accumulation loop.
    """
    from repro.models import dist

    def grads_of(params, batch: TrainBatch):
        (loss, metrics), grads = jax.value_and_grad(
            lambda p: loss_fn(p, batch, cfg), has_aux=True)(params)
        return loss, metrics, grads

    def train_step(state: TrainState, batch: TrainBatch):
        params = state.params
        if microbatches == 1:
            loss, metrics, grads = grads_of(params, batch)
        else:
            def split(x):
                b = x.shape[0]
                x = x.reshape(microbatches, b // microbatches, *x.shape[1:])
                # microbatch dim replicated; per-microbatch batch stays
                # sharded over pod x data
                return dist.constrain(x, None, "batch",
                                      *([None] * (x.ndim - 2)))
            mb = jax.tree.map(split, batch)

            def acc_fn(carry, b):
                loss_a, grads_a = carry
                b = jax.tree.map(dist.constrain_batch, b)
                loss, metrics, grads = grads_of(params, b)
                grads = jax.tree.map(jnp.add, grads_a, grads)
                grads = dist.constrain_tree(grads, grad_shardings)
                return (loss_a + loss, grads), metrics

            zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                                 params)
            zeros = dist.constrain_tree(zeros, grad_shardings)
            (loss, grads), metrics = jax.lax.scan(
                acc_fn, (jnp.zeros(()), zeros), mb)
            loss = loss / microbatches
            grads = jax.tree.map(lambda g: g / microbatches, grads)
            # the last microbatch's metrics; the MoE counts of all of them
            metrics = {k: v.sum(0) if k in MOE_METRICS else v[-1]
                       for k, v in metrics.items()}

        ef = state.ef
        if compress_grads:
            def comp(g, e):
                g = g.astype(jnp.float32) + e
                q, amax = _quantize_int8(g)
                gq = _dequantize_int8(q, amax)
                return gq, g - gq
            out = jax.tree.map(comp, grads, ef)
            two = lambda t: isinstance(t, tuple) and len(t) == 2
            grads = jax.tree.map(lambda t: t[0], out, is_leaf=two)
            ef = jax.tree.map(lambda t: t[1], out, is_leaf=two)

        new_params, new_opt, gnorm = opt.update(grads, state.opt, params)
        metrics = dict(metrics)
        metrics.update(loss=loss, grad_norm=gnorm,
                       lr=opt.lr_at(new_opt.step))
        return TrainState(new_params, new_opt, ef), metrics

    return train_step
