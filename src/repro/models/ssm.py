"""State-space and recurrent blocks: Mamba-2 (SSD) and xLSTM (mLSTM/sLSTM).

Train/prefill paths use the chunked-parallel scans from repro.kernels.ops;
decode paths carry O(1) recurrent state per layer — this is what makes the
`long_500k` shape tractable for the ssm/hybrid families (DESIGN.md §4).
"""
from __future__ import annotations

import math
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ops as kops
from . import dist
from .config import ModelConfig
from .layers import _init, init_rmsnorm, rmsnorm

Params = Dict[str, jax.Array]


# ================================================================== Mamba-2
class MambaState(NamedTuple):
    conv_x: jax.Array   # (B, W-1, d_in)   channel-sharded over model
    conv_bc: jax.Array  # (B, W-1, 2*d_state)  replicated
    ssm: jax.Array      # (B, H, P, N)     head-sharded over model


def init_mamba2(key, cfg: ModelConfig) -> Params:
    """Projections are split (x / BC / dt / z) so every piece keeps a clean
    Megatron-style layout: channels+heads shard over `model` end-to-end,
    with a single psum at w_out (EXPERIMENTS.md zamba2 iterations)."""
    s = cfg.ssm
    d = cfg.d_model
    d_in = s.expand * d
    nh = d_in // s.d_head
    dt = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 6)
    return {
        "w_x": _init(ks[0], (d, d_in), d ** -0.5, dt),
        "w_z": _init(ks[1], (d, d_in), d ** -0.5, dt),
        "w_bc": _init(ks[2], (d, 2 * s.d_state), d ** -0.5, dt),
        "w_dt": _init(ks[3], (d, nh), d ** -0.5, dt),
        "conv_x_w": _init(ks[4], (s.conv_width, d_in), 0.5, dt),
        "conv_x_b": jnp.zeros((d_in,), dt),
        "conv_bc_w": _init(ks[5], (s.conv_width, 2 * s.d_state), 0.5, dt),
        "conv_bc_b": jnp.zeros((2 * s.d_state,), dt),
        "a_log": jnp.log(jnp.linspace(1.0, 16.0, nh)).astype(jnp.float32),
        "d_skip": jnp.ones((nh,), jnp.float32),
        "dt_bias": jnp.zeros((nh,), jnp.float32),
        "norm": init_rmsnorm(d_in, dt)["scale"],
        "w_out": _init(ks[0], (d_in, d), d_in ** -0.5, dt),
    }


def _causal_conv(x, w, b, state: Optional[jax.Array]):
    """Depthwise causal conv along time.  x: (B,S,C); w: (W,C).
    state (B,W-1,C) carries the tail for decode; returns (y, new_state)."""
    W = w.shape[0]
    if state is None:
        pad = jnp.zeros((x.shape[0], W - 1, x.shape[2]), x.dtype)
    else:
        pad = state.astype(x.dtype)
    xp = jnp.concatenate([pad, x], axis=1)
    y = sum(xp[:, i:i + x.shape[1]] * w[i][None, None] for i in range(W))
    return y + b[None, None], xp[:, -(W - 1):]


def mamba2_fwd(p: Params, x: jax.Array, cfg: ModelConfig, *,
               state: Optional[MambaState] = None,
               return_state: bool = False
               ) -> Tuple[jax.Array, Optional[MambaState]]:
    s = cfg.ssm
    ct = jnp.dtype(cfg.compute_dtype)
    d_in = s.expand * cfg.d_model
    nh = d_in // s.d_head
    xi = jnp.einsum("bsd,de->bse", x, p["w_x"].astype(ct))
    z = jnp.einsum("bsd,de->bse", x, p["w_z"].astype(ct))
    bc = jnp.einsum("bsd,de->bse", x, p["w_bc"].astype(ct))
    dt = jnp.einsum("bsd,de->bse", x, p["w_dt"].astype(ct))
    conv_x, cx_state = _causal_conv(
        xi, p["conv_x_w"].astype(ct), p["conv_x_b"].astype(ct),
        state.conv_x if state is not None else None)
    conv_bc, cbc_state = _causal_conv(
        bc, p["conv_bc_w"].astype(ct), p["conv_bc_b"].astype(ct),
        state.conv_bc if state is not None else None)
    xs = jax.nn.silu(conv_x)
    B, C = jnp.split(jax.nn.silu(conv_bc), 2, axis=-1)
    # heads/channels shard over `model`: the SSD work distributes instead
    # of being redundantly replicated (EXPERIMENTS.md zamba2 iterations)
    xh = dist.constrain_heads(xs.reshape(*xs.shape[:2], nh, s.d_head))
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"][None, None])
    dt = dist.constrain_heads(dt)
    A = -jnp.exp(p["a_log"])
    if state is None:
        if return_state:
            y, ssm = kops.ssd_scan(xh, dt, A, B, C, p["d_skip"],
                                   chunk=s.chunk, return_final_state=True)
            new_state = MambaState(conv_x=cx_state, conv_bc=cbc_state,
                                   ssm=ssm)
        else:
            y = kops.ssd_scan(xh, dt, A, B, C, p["d_skip"], chunk=s.chunk)
            new_state = None
    else:
        ssm, y = kops.ssd_step(state.ssm, xh[:, 0], dt[:, 0], A,
                               B[:, 0], C[:, 0], p["d_skip"])
        y = y[:, None]
        new_state = MambaState(conv_x=cx_state, conv_bc=cbc_state, ssm=ssm)
    y = y.reshape(*y.shape[:2], d_in)
    y = rmsnorm({"scale": p["norm"]}, y * jax.nn.silu(z), cfg.norm_eps)
    return jnp.einsum("bse,ed->bsd", y, p["w_out"].astype(ct)), new_state


def init_mamba_state(cfg: ModelConfig, batch: int) -> MambaState:
    s = cfg.ssm
    d_in = s.expand * cfg.d_model
    nh = d_in // s.d_head
    ct = jnp.dtype(cfg.compute_dtype)
    return MambaState(
        conv_x=jnp.zeros((batch, s.conv_width - 1, d_in), ct),
        conv_bc=jnp.zeros((batch, s.conv_width - 1, 2 * s.d_state), ct),
        ssm=jnp.zeros((batch, nh, s.d_head, s.d_state), jnp.float32))


# ==================================================================== mLSTM
class MLSTMState(NamedTuple):
    conv: jax.Array   # (B, W-1, f*d)
    C: jax.Array      # (B, H, Dh, Dh) matrix memory
    n: jax.Array      # (B, H, Dh)
    m: jax.Array      # (B, H) stabilizer


def init_mlstm(key, cfg: ModelConfig) -> Params:
    x = cfg.xlstm
    d = cfg.d_model
    f = int(x.proj_factor_m * d)
    dt = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 7)
    return {
        "w_up": _init(ks[0], (d, 2 * f), d ** -0.5, dt),
        "conv_w": _init(ks[1], (x.conv_width, f), 0.5, dt),
        "conv_b": jnp.zeros((f,), dt),
        "wq": _init(ks[2], (f, f), f ** -0.5, dt),
        "wk": _init(ks[3], (f, f), f ** -0.5, dt),
        "wv": _init(ks[4], (f, f), f ** -0.5, dt),
        "w_if": _init(ks[5], (f, 2 * cfg.n_heads), f ** -0.5, dt),
        "b_if": jnp.concatenate([jnp.zeros((cfg.n_heads,)),
                                 jnp.linspace(3.0, 6.0, cfg.n_heads)]).astype(dt),
        "norm": init_rmsnorm(f, dt)["scale"],
        "w_down": _init(ks[6], (f, d), f ** -0.5, dt),
    }


def mlstm_fwd(p: Params, x: jax.Array, cfg: ModelConfig, *,
              state: Optional[MLSTMState] = None,
              return_state: bool = False):
    xc = cfg.xlstm
    ct = jnp.dtype(cfg.compute_dtype)
    d = cfg.d_model
    f = int(xc.proj_factor_m * d)
    H = cfg.n_heads
    dh = f // H
    up = jnp.einsum("bsd,de->bse", x, p["w_up"].astype(ct))
    xi, z = jnp.split(up, 2, axis=-1)
    conv_out, conv_state = _causal_conv(
        xi, p["conv_w"].astype(ct), p["conv_b"].astype(ct),
        state.conv if state is not None else None)
    xq = jax.nn.silu(conv_out)
    q = jnp.einsum("bsf,fe->bse", xq, p["wq"].astype(ct)) * dh ** -0.5
    k = jnp.einsum("bsf,fe->bse", xq, p["wk"].astype(ct)) * dh ** -0.5
    v = jnp.einsum("bsf,fe->bse", xi, p["wv"].astype(ct))
    gates = jnp.einsum("bsf,fg->bsg", xq, p["w_if"].astype(ct)) + \
        p["b_if"].astype(ct)[None, None]
    ig, fg = gates[..., :H], gates[..., H:]
    qh = q.reshape(*q.shape[:2], H, dh)
    kh = k.reshape(*k.shape[:2], H, dh)
    vh = v.reshape(*v.shape[:2], H, dh)
    if state is None:
        if return_state:
            y, (C2, n2, m2) = kops.mlstm_scan(qh, kh, vh, ig, fg, chunk=xc.chunk,
                                              return_final_state=True)
            new_state = MLSTMState(conv=conv_state, C=C2, n=n2, m=m2)
        else:
            y = kops.mlstm_scan(qh, kh, vh, ig, fg, chunk=xc.chunk)
            new_state = None
    else:
        y, C2, n2, m2 = _mlstm_step(state, qh[:, 0], kh[:, 0], vh[:, 0],
                                    ig[:, 0], fg[:, 0])
        y = y[:, None]
        new_state = MLSTMState(conv=conv_state, C=C2, n=n2, m=m2)
    y = y.reshape(*y.shape[:2], f)
    y = rmsnorm({"scale": p["norm"]}, y, cfg.norm_eps) * jax.nn.silu(z)
    return jnp.einsum("bsf,fd->bsd", y, p["w_down"].astype(ct)), new_state


def _mlstm_step(st: MLSTMState, q, k, v, ig, fg):
    qf, kf, vf = (t.astype(jnp.float32) for t in (q, k, v))
    logf = jax.nn.log_sigmoid(fg.astype(jnp.float32))
    i_ = ig.astype(jnp.float32)
    m_new = jnp.maximum(logf + st.m, i_)
    fd = jnp.exp(logf + st.m - m_new)
    id_ = jnp.exp(i_ - m_new)
    C = st.C * fd[..., None, None] + id_[..., None, None] * \
        jnp.einsum("bhd,bhe->bhde", kf, vf)
    n = st.n * fd[..., None] + id_[..., None] * kf
    num = jnp.einsum("bhd,bhde->bhe", qf, C)
    den = jnp.abs(jnp.einsum("bhd,bhd->bh", qf, n))
    y = num / jnp.maximum(den, jnp.exp(-m_new))[..., None]
    return y.astype(q.dtype), C, n, m_new


def init_mlstm_state(cfg: ModelConfig, batch: int) -> MLSTMState:
    x = cfg.xlstm
    f = int(x.proj_factor_m * cfg.d_model)
    H = cfg.n_heads
    dh = f // H
    ct = jnp.dtype(cfg.compute_dtype)
    return MLSTMState(conv=jnp.zeros((batch, x.conv_width - 1, f), ct),
                      C=jnp.zeros((batch, H, dh, dh), jnp.float32),
                      n=jnp.zeros((batch, H, dh), jnp.float32),
                      m=jnp.full((batch, H), -1e30, jnp.float32))


# ==================================================================== sLSTM
class SLSTMState(NamedTuple):
    c: jax.Array  # (B, H, Dh)
    n: jax.Array
    h: jax.Array
    m: jax.Array  # (B, H, Dh)


def init_slstm(key, cfg: ModelConfig) -> Params:
    x = cfg.xlstm
    d = cfg.d_model
    H = cfg.n_heads
    dh = d // H
    f = int(x.proj_factor_s * d)
    dt = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 4)
    return {
        # input projections for 4 gates (i, f, z, o)
        "w_x": _init(ks[0], (d, 4 * d), d ** -0.5, dt),
        # block-diagonal recurrent weights per head
        "w_r": _init(ks[1], (4, H, dh, dh), dh ** -0.5, dt),
        "b": jnp.concatenate(
            [jnp.zeros((d,)), jnp.linspace(3.0, 6.0, d), jnp.zeros((2 * d,))]
        ).astype(dt),
        "norm": init_rmsnorm(d, dt)["scale"],
        "w_ff1": _init(ks[2], (d, f), d ** -0.5, dt),
        "w_ff2": _init(ks[3], (f, d), f ** -0.5, dt),
    }


def _slstm_cell(p4r, carry: SLSTMState, gx):
    """One sLSTM step.  gx: (B, 4, H, Dh) input-gate preactivations."""
    c, n, h, m = carry
    r = jnp.einsum("bhd,ghde->bghe", h, p4r)            # recurrent part
    g = gx.astype(jnp.float32) + r.astype(jnp.float32)
    i_, f_, z_, o_ = g[:, 0], g[:, 1], g[:, 2], g[:, 3]
    logf = jax.nn.log_sigmoid(f_)
    m_new = jnp.maximum(logf + m, i_)
    c = c * jnp.exp(logf + m - m_new) + jnp.exp(i_ - m_new) * jnp.tanh(z_)
    n = n * jnp.exp(logf + m - m_new) + jnp.exp(i_ - m_new)
    h_new = jax.nn.sigmoid(o_) * c / jnp.maximum(n, 1e-6)
    return SLSTMState(c, n, h_new.astype(h.dtype), m_new), h_new


def _slstm_scan(w_r, st: SLSTMState, gx):
    """Time scan over (B_local, S, 4, H, dh) gate preactivations."""
    st, ys = jax.lax.scan(lambda c, g: _slstm_cell(w_r, c, g),
                          st, jnp.moveaxis(gx, 1, 0))
    return st, jnp.moveaxis(ys, 0, 1)


def slstm_fwd(p: Params, x: jax.Array, cfg: ModelConfig, *,
              state: Optional[SLSTMState] = None,
              return_state: bool = False):
    ct = jnp.dtype(cfg.compute_dtype)
    B, S, d = x.shape
    H = cfg.n_heads
    dh = d // H
    gx = jnp.einsum("bsd,de->bse", x, p["w_x"].astype(ct)) + \
        p["b"].astype(ct)[None, None]
    gx = gx.reshape(B, S, 4, H, dh)
    w_r = p["w_r"].astype(ct)
    st = state if state is not None else SLSTMState(
        c=jnp.zeros((B, H, dh), jnp.float32),
        n=jnp.zeros((B, H, dh), jnp.float32),
        h=jnp.zeros((B, H, dh), ct),
        m=jnp.full((B, H, dh), -1e30, jnp.float32))
    if S == 1:
        st, y = _slstm_cell(w_r, st, gx[:, 0])
        ys = y[:, None].astype(ct)
    else:
        from . import dist
        mesh = dist.get_mesh()
        ba = dist.batch_axes()
        nb = 1
        if mesh is not None:
            import numpy as _np
            nb = int(_np.prod([mesh.shape[a] for a in ba]))
        if mesh is not None and B % nb == 0 and nb > 1:
            # shard_map over batch: the recurrent-weight gradient psum
            # happens ONCE at the boundary instead of per scan step (XLA
            # otherwise emits an all-reduce of dW_r inside the 4096-step
            # time loop — see EXPERIMENTS.md §Perf xlstm iteration).
            from jax.sharding import PartitionSpec as P
            bspec = ba if len(ba) > 1 else ba[0]

            def body(w_r_, st_, gx_):
                return _slstm_scan(w_r_, st_, gx_)

            st_spec = SLSTMState(*([P(bspec)] * 4))
            st, ys = jax.shard_map(
                body, mesh=mesh,
                in_specs=(P(), st_spec, P(bspec)),
                out_specs=(st_spec, P(bspec)),
                check_vma=False)(w_r, st, gx)
        else:
            st, ys = _slstm_scan(w_r, st, gx)
        ys = ys.astype(ct)
    y = ys.reshape(B, S, d)
    y = rmsnorm({"scale": p["norm"]}, y, cfg.norm_eps)
    ff = jnp.einsum("bsd,df->bsf", y, p["w_ff1"].astype(ct))
    y = jnp.einsum("bsf,fd->bsd", jax.nn.gelu(ff), p["w_ff2"].astype(ct))
    return y, (st if state is not None or return_state else None)
