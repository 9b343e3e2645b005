"""Mixture-of-Experts FFN with expert parallelism.

Experts are sharded over the mesh's `model` axis.  Because token
activations are replicated over `model` between blocks (TP layout), each
expert shard can gather the tokens routed to *its* experts locally and the
shard outputs combine with a single psum — the same collective cost as a
dense TP FFN, with no all-to-all and no dense dispatch einsum (whose
E x C FLOPs multiplier would swamp the roofline).

Dispatch is capacity-based (GShard-style token dropping) implemented with
sort-free scatter/gather so dispatch costs O(T k d) moves and ~0 FLOPs.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .config import ModelConfig
from .layers import _init

Params = Dict[str, jax.Array]

# mesh context lives in models.dist; re-exported here for callers
from .dist import get_mesh, set_mesh  # noqa: E402
from . import dist as _dist           # noqa: E402


def init_moe(key, cfg: ModelConfig) -> Params:
    m, d = cfg.moe, cfg.d_model
    dt = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 5)
    p = {
        "router": _init(ks[0], (d, m.n_experts), d ** -0.5, jnp.float32),
        "w1": _init(ks[1], (m.n_experts, d, m.d_expert), d ** -0.5, dt),
        "w3": _init(ks[2], (m.n_experts, d, m.d_expert), d ** -0.5, dt),
        "w2": _init(ks[3], (m.n_experts, m.d_expert, d), m.d_expert ** -0.5, dt),
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


def _capacity(n_tokens: int, cfg: ModelConfig) -> int:
    m = cfg.moe
    c = math.ceil(n_tokens * m.top_k / m.n_experts * m.capacity_factor)
    return max(8, -(-c // 8) * 8)


def _moe_local(x2d, router, w1, w3, w2, cfg: ModelConfig,
               e_start, n_local: int, capacity: int):
    """Per-shard MoE: route all local tokens, run the local expert slice.

    x2d: (T, d); w*: (E_loc, ...); e_start: first local expert id.
    Returns (partial y (T, d), partial aux-loss scalars).
    """
    m = cfg.moe
    T, d = x2d.shape
    ct = x2d.dtype
    logits = (x2d.astype(jnp.float32) @ router).astype(jnp.float32)  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, m.top_k)                      # (T, k)
    top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    # ---- flatten assignments, keep only local experts ----------------------
    A = T * m.top_k
    eid = top_e.reshape(A)
    gate = top_w.reshape(A)
    tok = jnp.repeat(jnp.arange(T), m.top_k)
    local = (eid >= e_start) & (eid < e_start + n_local)
    el = jnp.where(local, eid - e_start, 0)
    onehot = (el[:, None] == jnp.arange(n_local)[None]) & local[:, None]
    pos = jnp.cumsum(onehot.astype(jnp.int32), axis=0) - 1
    pos = jnp.take_along_axis(pos, el[:, None], axis=1)[:, 0]
    keep = local & (pos < capacity)
    slot = jnp.where(keep, pos, capacity)          # overflow -> trash slot
    # ---- dispatch: (E_loc, C+1, d) buffer ----------------------------------
    buf = jnp.zeros((n_local, capacity + 1, d), ct)
    buf = buf.at[el, slot].add(jnp.where(keep[:, None], x2d[tok], 0))
    buf = buf[:, :capacity]
    # ---- expert FFN (batched over local experts) ---------------------------
    g = jnp.einsum("ecd,edf->ecf", buf, w1.astype(ct))
    u = jnp.einsum("ecd,edf->ecf", buf, w3.astype(ct))
    h = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, w2.astype(ct))
    # ---- combine back -------------------------------------------------------
    hp = jnp.concatenate([h, jnp.zeros((n_local, 1, d), ct)], axis=1)
    contrib = hp[el, slot] * (gate * keep).astype(ct)[:, None]
    y = jnp.zeros((T, d), ct).at[tok].add(contrib)
    # ---- load-balance aux (Switch-style), local partial sums ---------------
    frac_prob = jnp.mean(probs, axis=0)                    # (E,)
    assigned = jnp.zeros((m.n_experts,), jnp.float32).at[eid].add(
        jnp.ones((A,), jnp.float32))
    return y, frac_prob, assigned, jnp.asarray(T, jnp.float32)


def moe_fwd(p: Params, x: jax.Array, cfg: ModelConfig
            ) -> Tuple[jax.Array, jax.Array]:
    """Routed experts (+optional shared experts).  Returns (y, aux_loss)."""
    m = cfg.moe
    B, S, d = x.shape
    mesh = _dist.get_mesh()
    if mesh is not None and "model" in mesh.axis_names:
        ep = mesh.shape["model"]
        n_local = m.n_experts // ep
        cap = _capacity(B * S // _batch_shards(mesh), cfg)

        def shard_fn(xs, router, w1, w3, w2):
            T = xs.shape[0] * xs.shape[1]
            j = jax.lax.axis_index("model")
            tc = m.token_chunk
            if tc and T > tc and T % tc == 0:
                # chunked dispatch: capacity and the (T*k, d) gather/
                # scatter buffers scale with the chunk, not the batch
                cap_c = max(8, -(-cap * tc // T // 8) * 8)

                def chunk_fn(xc):
                    return _moe_local(xc, router, w1, w3, w2, cfg,
                                      j * n_local, n_local, cap_c)
                y, fp, asg, t = jax.lax.map(
                    chunk_fn, xs.reshape(T // tc, tc, d))
                y = y.reshape(T, d)
                fp = jnp.mean(fp, axis=0)
                asg = jnp.sum(asg, axis=0)
                t = jnp.sum(t)
            else:
                y, fp, asg, t = _moe_local(xs.reshape(T, d), router, w1, w3,
                                           w2, cfg, j * n_local, n_local, cap)
            y = jax.lax.psum(y, "model")
            ba = _dist.batch_axes()
            fp = jax.lax.pmean(fp, ba)
            asg = jax.lax.psum(asg, ba + ("model",))
            t = jax.lax.psum(t, ba + ("model",))
            return y.reshape(xs.shape), fp, asg, t

        y, fp, asg, t = jax.shard_map(
            shard_fn, mesh=mesh,
            in_specs=(P(_flat_batch_spec(), None, None),
                      P(None, None), P("model", None, None),
                      P("model", None, None), P("model", None, None)),
            out_specs=(P(_flat_batch_spec(), None, None), P(None), P(None), P()),
            check_vma=False,
        )(x, p["router"], p["w1"], p["w3"], p["w2"])
    else:
        cap = _capacity(B * S, cfg)
        y, fp, asg, t = _moe_local(x.reshape(B * S, d), p["router"], p["w1"],
                                   p["w3"], p["w2"], cfg, 0, m.n_experts, cap)
        y = y.reshape(B, S, d)
    frac_tokens = asg / jnp.maximum(t * m.top_k, 1.0)
    aux = m.n_experts * jnp.sum(fp * frac_tokens)
    if m.n_shared:
        sh = p["shared"]
        ct = x.dtype
        g = jnp.einsum("bsd,df->bsf", x, sh["w_gate"].astype(ct))
        u = jnp.einsum("bsd,df->bsf", x, sh["w_up"].astype(ct))
        y = y + jnp.einsum("bsf,fd->bsd", jax.nn.silu(g) * u,
                           sh["w_down"].astype(ct))
    return y, aux


def _flat_batch_spec():
    ba = _dist.batch_axes()
    return ba if len(ba) > 1 else ba[0]


def _batch_shards(mesh) -> int:
    n = 1
    for a in _dist.batch_axes():
        n *= mesh.shape[a]
    return n
