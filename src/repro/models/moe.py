"""Dropless mixture-of-experts FFN over the experts held here.

The router keeps its full width and picks every token's top-k experts.
This device computes the part of the result that its own experts give:
the experts it holds (the first ``MoEConfig.n_held``, the chip's share
of an expert-parallel deployment), or their slice over the mesh's
``model`` axis.  What absent experts would add is left out, and
the shard outputs over ``model`` combine with one psum: token
activations are replicated over ``model`` between blocks (TP layout), so
each shard routes locally and no all-to-all is needed.

Every assignment to a held expert is computed; none is dropped.  The
held assignments are grouped by expert into one static bound of rows
(T * min(k, held) plus one tile per expert, since each group starts on a
tile of ``kernels.ops.gmm_tile`` rows), and one grouped matrix product
per weight (:func:`kernels.ops.grouped_matmul`) runs the experts' SwiGLU
over the live tiles only.  The shared experts run once, densely.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import ops as kops
from .config import ModelConfig
from .layers import _init

Params = Dict[str, jax.Array]

# mesh context lives in models.dist; re-exported here for callers
from .dist import get_mesh, set_mesh  # noqa: E402,F401
from . import dist as _dist           # noqa: E402

#: the MoE readings a layer adds to the model's carry: the balance loss,
#: then assignments computed by held experts and the largest held
#: expert's assignments
N_STATS = 3


def init_moe(key, cfg: ModelConfig) -> Params:
    m, d = cfg.moe, cfg.d_model
    dt = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 5)
    E = m.held
    p = {
        "router": _init(ks[0], (d, m.n_experts), d ** -0.5, jnp.float32),
        "w1": _init(ks[1], (E, d, m.d_expert), d ** -0.5, dt),
        "w3": _init(ks[2], (E, d, m.d_expert), d ** -0.5, dt),
        "w2": _init(ks[3], (E, m.d_expert, d), m.d_expert ** -0.5, dt),
    }
    if m.n_shared:
        f = m.n_shared * m.d_expert
        k1, k2, k3 = jax.random.split(ks[4], 3)
        p["shared"] = {
            "w_gate": _init(k1, (d, f), d ** -0.5, dt),
            "w_up": _init(k2, (d, f), d ** -0.5, dt),
            "w_down": _init(k3, (f, d), f ** -0.5, dt),
        }
    return p


def route(x: jax.Array, router: jax.Array, cfg: ModelConfig):
    """Softmax router over all ``n_experts`` and greedy top-k.  x:
    (..., d).  Returns (probs (..., E), weights (..., k), experts (..., k));
    the weights are renormalised only with ``norm_topk_prob``."""
    m = cfg.moe
    probs = jax.nn.softmax(x.astype(jnp.float32) @ router, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, m.top_k)
    if m.norm_topk_prob:
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    return probs, top_w, top_e


def balance_loss(probs: jax.Array, top_e: jax.Array,
                 cfg: ModelConfig) -> jax.Array:
    """E * sum_e (share of picks of e) x (mean router probability of e),
    over each sequence (``seq_aux``, DeepSeek-V2) or over the whole batch
    (Switch), averaged over those groups.  probs (B, S, E), top_e (B, S, k).
    """
    m = cfg.moe
    E = m.n_experts
    picks = jax.nn.one_hot(top_e, E, dtype=jnp.float32).sum(-2)  # (B, S, E)
    axes = (1,) if m.seq_aux else (0, 1)
    share = jnp.mean(picks, axis=axes) / m.top_k
    return E * jnp.mean(jnp.sum(share * jnp.mean(probs, axis=axes), -1))


def held_experts(x2d, top_w, top_e, w1, w3, w2, e_start, n_held: int):
    """The held experts' part of the layer's output, dropless.

    x2d (T, d); top_w, top_e (T, k); w* (n_held, ...) for experts
    ``e_start .. e_start + n_held - 1``.  Returns (y (T, d), per-expert
    assignments (n_held,)).
    """
    T, d = x2d.shape
    k = top_e.shape[1]
    tm = kops.gmm_tile(T * min(k, n_held), n_held)
    # a token picks k distinct experts, so at most min(k, held) held
    # ones, and a group's padding is under a tile: the grouped rows never
    # pass the bound, and no assignment is dropped
    rows = -(-(T * min(k, n_held) + n_held * (tm - 1)) // tm) * tm
    held = (top_e >= e_start) & (top_e < e_start + n_held)
    el = jnp.where(held, top_e - e_start, n_held).reshape(T * k)
    onehot = el[:, None] == jnp.arange(n_held)[None]        # (T k, n_held)
    counts = jnp.sum(onehot, axis=0, dtype=jnp.int32)
    rank = jnp.take_along_axis(
        jnp.cumsum(onehot, axis=0, dtype=jnp.int32),
        jnp.minimum(el, n_held - 1)[:, None], axis=1)[:, 0] - 1
    tiles = -(-counts // tm)
    first = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                             jnp.cumsum(tiles)[:-1]]) * tm
    pos = jnp.where(el < n_held,
                    first[jnp.minimum(el, n_held - 1)] + rank, rows)
    # each row's token; group padding and rows past the live tiles name
    # no token (T, out of bounds) and read zeros
    tok = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
    src = jnp.full((rows,), T, jnp.int32).at[pos].set(tok, mode="drop")
    pos = pos.reshape(T, k)
    xs = _dispatch(x2d, src, pos)
    g = kops.grouped_matmul(xs, w1.astype(x2d.dtype), counts, tm)
    u = kops.grouped_matmul(xs, w3.astype(x2d.dtype), counts, tm)
    h = kops.grouped_matmul((jax.nn.silu(g) * u).astype(x2d.dtype),
                            w2.astype(x2d.dtype), counts, tm)
    y = _combine(h, top_w.astype(x2d.dtype), src, pos)
    return y, counts


# Dispatch and combine move rows by two maps the forward knows both ways:
# each row's token (src) and each token's rows (pos, ``rows`` where the
# pick is not held).  Each transpose is then a gather by the other map,
# not a scatter-add; rows past the live tiles are never read.
def _pick(a, idx):
    return a.at[idx].get(mode="fill", fill_value=0)


def _sum_picks(a, pos, w=None):
    """sum_j (w[:, j] x) a[pos[:, j]], one pick at a time: no (T, k, d)
    buffer."""
    out = 0
    for j in range(pos.shape[1]):
        rows = _pick(a, pos[:, j])
        out = out + (rows if w is None else w[:, j, None] * rows)
    return out


@jax.custom_vjp
def _dispatch(x2d, src, pos):
    """The rows' tokens: x2d[src], zeros where src is out of bounds."""
    return _pick(x2d, src)


def _dispatch_fwd(x2d, src, pos):
    return _pick(x2d, src), (src, pos)


def _dispatch_bwd(res, dxs):
    src, pos = res
    return _sum_picks(dxs, pos), None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine(h, w, src, pos):
    """Each token's picks, weighted: sum_j w[:, j] h[pos[:, j]]."""
    return _sum_picks(h, pos, w)


def _combine_fwd(h, w, src, pos):
    return _combine(h, w, src, pos), (h, w, src, pos)


def _combine_bwd(res, dy):
    h, w, src, pos = res
    # each row's weight: its pick's (0 on padding and dead rows)
    w_row = jnp.zeros((h.shape[0],), w.dtype).at[pos.reshape(-1)].set(
        w.reshape(-1), mode="drop")
    dh = w_row[:, None] * _pick(dy, src)
    dw = jnp.stack([jnp.sum(_pick(h, pos[:, j]) * dy, axis=-1)
                    for j in range(pos.shape[1])], axis=1).astype(w.dtype)
    return dh, dw, None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def _stats(counts, model_axis: bool):
    """(assignments, largest held expert's) as float32."""
    total, top = jnp.sum(counts), jnp.max(counts)
    if model_axis:
        total = jax.lax.psum(total, "model")
        top = jax.lax.pmax(top, "model")
    return jnp.stack([total, top]).astype(jnp.float32)


def moe_fwd(p: Params, x: jax.Array, cfg: ModelConfig
            ) -> Tuple[jax.Array, jax.Array]:
    """Routed experts held here (+ shared experts).  Returns (y, readings
    (N_STATS,)): the balance loss, then the held experts' assignments
    and the largest held expert's."""
    m = cfg.moe
    B, S, d = x.shape
    probs, top_w, top_e = route(x, p["router"], cfg)
    aux = balance_loss(probs, top_e, cfg)
    mesh = _dist.get_mesh()
    if mesh is not None and "model" in mesh.axis_names:
        ep = mesh.shape["model"]
        n_local = m.held // ep
        bspec = P(_flat_batch_spec(), None, None)

        def shard_fn(xs, tw, te, w1, w3, w2):
            b, s = xs.shape[:2]
            e0 = jax.lax.axis_index("model") * n_local
            y, counts = held_experts(
                xs.reshape(b * s, d), tw.reshape(b * s, -1),
                te.reshape(b * s, -1), w1, w3, w2, e0, n_local)
            counts = jax.lax.psum(counts, _dist.batch_axes())
            y = jax.lax.psum(y, "model")
            return y.reshape(xs.shape), _stats(counts, True)

        y, stats = jax.shard_map(
            shard_fn, mesh=mesh,
            in_specs=(bspec, bspec, bspec, P("model", None, None),
                      P("model", None, None), P("model", None, None)),
            out_specs=(bspec, P()),
            check_vma=False,
        )(x, top_w, top_e, p["w1"], p["w3"], p["w2"])
    else:
        y, counts = held_experts(
            x.reshape(B * S, d), top_w.reshape(B * S, -1),
            top_e.reshape(B * S, -1), p["w1"], p["w3"], p["w2"], 0, m.held)
        y = y.reshape(B, S, d)
        stats = _stats(counts, False)
    if m.n_shared:
        sh = p["shared"]
        ct = x.dtype
        g = jnp.einsum("bsd,df->bsf", x, sh["w_gate"].astype(ct))
        u = jnp.einsum("bsd,df->bsf", x, sh["w_up"].astype(ct))
        y = y + jnp.einsum("bsf,fd->bsd", jax.nn.silu(g) * u,
                           sh["w_down"].astype(ct))
    return y, jnp.concatenate([aux[None], stats])


def _flat_batch_spec():
    ba = _dist.batch_axes()
    return ba if len(ba) > 1 else ba[0]
