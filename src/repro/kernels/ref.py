"""Pure-jnp oracles for every kernel (small shapes only; used by tests)."""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp


def naive_attention(q, k, v, *, causal: bool = True,
                    scale: Optional[float] = None,
                    kv_valid_len=None) -> jax.Array:
    """Softmax attention, materializing full scores.

    q: (B, Sq, H, D); k: (B, Skv, K, D); v: (B, Skv, K, Dv) with
    H % K == 0 (GQA broadcast).
    With kv_valid_len: mask positions t >= valid_len (decode against cache);
    query i is aligned so that position of q[i] = valid_len - Sq + i.
    """
    B, Sq, H, D = q.shape
    K = k.shape[2]
    G = H // K
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    qg = q.reshape(B, Sq, K, G, D).astype(jnp.float32)
    s = jnp.einsum("bskgd,btkd->bkgst", qg, k.astype(jnp.float32)) * scale
    Skv = k.shape[1]
    ti = jnp.arange(Skv)
    if kv_valid_len is not None:
        qpos = kv_valid_len - Sq + jnp.arange(Sq)
        mask = ti[None, :] <= qpos[:, None]
        s = jnp.where(mask[None, None, None], s, -jnp.inf)
    elif causal:
        mask = ti[None, :] <= jnp.arange(Sq)[:, None] + (Skv - Sq)
        s = jnp.where(mask[None, None, None], s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgst,btkd->bskgd", a, v.astype(jnp.float32))
    return o.reshape(B, Sq, H, -1).astype(q.dtype)


def naive_ssd(x, dt, A, B, C, D) -> jax.Array:
    """Mamba-2 SSD reference: sequential recurrence over time.

    x: (b, s, h, p)   input per head
    dt: (b, s, h)     positive step sizes
    A: (h,)           negative decay rate per head
    B, C: (b, s, n)   input/output projections (shared across heads)
    D: (h,)           skip
    Returns (b, s, h, p).
    """
    b, s, h, p = x.shape
    n = B.shape[-1]
    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)
    decay = jnp.exp(dtf * A[None, None, :])            # (b,s,h)

    def step(state, t):
        st, = state
        # st: (b, h, p, n)
        db = dtf[:, t, :, None, None] * B[:, t, None, None, :]  # (b,h,1,n)
        st = st * decay[:, t, :, None, None] + xf[:, t, :, :, None] * db
        y = jnp.einsum("bhpn,bn->bhp", st, C[:, t].astype(jnp.float32))
        return (st,), y

    st0 = jnp.zeros((b, h, p, n), jnp.float32)
    (_,), ys = jax.lax.scan(step, (st0,), jnp.arange(s))
    y = jnp.moveaxis(ys, 0, 1)                          # (b,s,h,p)
    y = y + xf * D[None, None, :, None]
    return y.astype(x.dtype)


def naive_mlstm(q, k, v, i_gate, f_gate) -> jax.Array:
    """xLSTM mLSTM reference: sequential matrix-memory recurrence.

    q,k,v: (b, s, h, d); i_gate,f_gate: (b, s, h) pre-activation.
    Stabilized exponential gating per the xLSTM paper.
    """
    b, s, h, d = q.shape
    qf, kf, vf = (t.astype(jnp.float32) for t in (q, k, v))
    logf = jax.nn.log_sigmoid(f_gate.astype(jnp.float32))  # (b,s,h)
    i_ = i_gate.astype(jnp.float32)

    def step(carry, t):
        Cm, nm, m = carry  # (b,h,d,d), (b,h,d), (b,h)
        m_new = jnp.maximum(logf[:, t] + m, i_[:, t])
        fd = jnp.exp(logf[:, t] + m - m_new)           # (b,h)
        id_ = jnp.exp(i_[:, t] - m_new)
        Cm = Cm * fd[..., None, None] + id_[..., None, None] * \
            jnp.einsum("bhd,bhe->bhde", kf[:, t], vf[:, t])
        nm = nm * fd[..., None] + id_[..., None] * kf[:, t]
        num = jnp.einsum("bhd,bhde->bhe", qf[:, t], Cm)
        den = jnp.abs(jnp.einsum("bhd,bhd->bh", qf[:, t], nm))
        y = num / jnp.maximum(den, jnp.exp(-m_new))[..., None]
        return (Cm, nm, m_new), y

    init = (jnp.zeros((b, h, d, d), jnp.float32),
            jnp.zeros((b, h, d), jnp.float32),
            jnp.full((b, h), -jnp.inf, jnp.float32))
    _, ys = jax.lax.scan(step, init, jnp.arange(s))
    return jnp.moveaxis(ys, 0, 1).astype(q.dtype)
