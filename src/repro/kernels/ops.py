"""Jit-ready kernel wrappers.

`attention` / `ssd_scan` / `mlstm_scan` dispatch between:
  * the Pallas TPU kernels (pl.pallas_call, VMEM-tiled) on TPU, and
  * mathematically identical chunked-jnp implementations everywhere else
    (CPU dry-run + tests) so the lowered HLO has *exact* causal FLOPs —
    the roofline reads these numbers.

The causal path is "binary blocked": the S x S causal triangle is split
into log2(S/block) levels of equal-shape rectangles plus a batched
block-diagonal, every level one batched matmul.  Exact FLOPs (no masked
waste), O(S * block) live memory, O(log S) HLO size.
"""
from __future__ import annotations

import collections
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import telemetry

# "jnp" | "pallas" | "interpret" (Pallas kernels in interpret mode, for
# CPU rehearsals of the TPU path) | None=auto
_BACKEND_OVERRIDE: Optional[str] = None


#: the bf16 sublane tile: a Pallas block's second-to-last dim must be a
#: multiple of it (or the whole axis)
SEQ_ALIGN = 16

#: the largest row tile of a grouped product: each expert's rows start
#: on a tile (see :func:`gmm_tile`)
GMM_TILE = 256

#: (attention mode, implementation) -> times traced; read by callers that
#: must show which implementation ran (e.g. chip_smoke.py)
traced_impls: collections.Counter = collections.Counter()


def fit_block(n: int, want: int) -> int:
    """Largest multiple of :data:`SEQ_ALIGN` that is <= ``want`` and
    divides ``n``; 0 when none does (the kernels cannot tile ``n``)."""
    for b in range(min(want, n) // SEQ_ALIGN * SEQ_ALIGN, 0, -SEQ_ALIGN):
        if n % b == 0:
            return b
    return 0


def padded_len(n: int) -> int:
    """``n`` rounded up to a length the attention kernels can tile."""
    return -(-n // SEQ_ALIGN) * SEQ_ALIGN


def set_backend(name: Optional[str]) -> Optional[str]:
    """Steer attention to an implementation; returns the previous choice,
    so a caller can restore it."""
    global _BACKEND_OVERRIDE
    prev, _BACKEND_OVERRIDE = _BACKEND_OVERRIDE, name
    return prev


def _use_pallas() -> bool:
    if _BACKEND_OVERRIDE in ("pallas", "interpret"):
        return True
    if _BACKEND_OVERRIDE == "jnp":
        return False
    return jax.default_backend() == "tpu"


def _interpret() -> bool:
    return _BACKEND_OVERRIDE == "interpret"


# ============================================================== soft helpers
def _merge(o1, l1, o2, l2):
    """Combine two partial attentions via their logsumexps."""
    m = jnp.maximum(l1, l2)
    w1 = jnp.exp(l1 - m)
    w2 = jnp.exp(l2 - m)
    den = w1 + w2
    o = (o1 * (w1 / den)[..., None] + o2 * (w2 / den)[..., None])
    return o, m + jnp.log(den)


def _sdp(qg, k, v, scale, mask=None):
    """One dense block: qg (..., Sq, K, G, D) x k/v (..., T, K, D), GQA.
    Returns (out (..., Sq, K, G, Dv), lse (..., Sq, K, G))."""
    s = jnp.einsum("...skgd,...tkd->...kgst", qg, k) * scale
    if mask is not None:
        s = jnp.where(mask, s, -jnp.inf)
    m = jnp.maximum(jnp.max(s, axis=-1, keepdims=True), -1e30)
    p = jnp.exp(s - m)
    den = jnp.sum(p, axis=-1)                      # (..., K, G, Sq)
    o = jnp.einsum("...kgst,...tkd->...skgd", p, v)
    # a fully masked row (left padding) has den 0: keep it finite, since
    # 0 * NaN in a later p @ v would reach the real rows
    o = o / jnp.maximum(jnp.moveaxis(den, -1, -3), 1e-30)[..., None]
    lse = m[..., 0] + jnp.log(jnp.maximum(den, 1e-30))
    return o, jnp.moveaxis(lse, -1, -3)            # lse -> (..., Sq, K, G)


def _rect_chunked(qg, k, v, scale, block_kv: int, block_q: int = 0):
    """Non-causal attention of qg against full k/v, scanned over kv chunks
    (and q chunks when the rectangle is tall, bounding live scores to
    block_q x block_kv per head).  qg: (B, M, Sq, K, G, D); k/v:
    (B, M, T, K, D).  Returns (out, lse)."""
    Sq, T = qg.shape[2], k.shape[2]
    if block_q and Sq > block_q and Sq % block_q == 0:
        nq = Sq // block_q
        qb = jnp.moveaxis(
            qg.reshape(*qg.shape[:2], nq, block_q, *qg.shape[3:]), 2, 0)

        def qbody(qblk):
            return _rect_chunked(qblk, k, v, scale, block_kv)

        o, lse = jax.lax.map(qbody, qb)
        o = jnp.moveaxis(o, 0, 2).reshape(*qg.shape[:-1], v.shape[-1])
        lse = jnp.moveaxis(lse, 0, 2).reshape(qg.shape[:-1])
        return o, lse
    nk = max(1, math.ceil(T / block_kv))
    if T % nk != 0:  # fall back to single chunk when not divisible
        o, lse = _sdp(qg, k, v, scale)
        return o, lse
    ck = k.reshape(*k.shape[:2], nk, T // nk, *k.shape[3:])
    cv = v.reshape(*v.shape[:2], nk, T // nk, *v.shape[3:])

    def body(carry, xs):
        o_acc, l_acc = carry
        kb, vb = xs
        o, l = _sdp(qg, kb, vb, scale)
        return _merge(o_acc, l_acc, o, l), None

    o0 = jnp.zeros((*qg.shape[:-1], v.shape[-1]), qg.dtype)
    l0 = jnp.full(qg.shape[:-1], -jnp.inf, qg.dtype)
    (o, lse), _ = jax.lax.scan(body, (o0, l0),
                               (jnp.moveaxis(ck, 2, 0), jnp.moveaxis(cv, 2, 0)))
    return o, lse


def _causal_binary(qg, k, v, scale, block_q: int, block_kv: int):
    """Exact-FLOPs causal attention via binary block decomposition.

    qg: (B, S, K, G, D); k/v: (B, S, K, D).  S must be a power-of-two
    multiple of the leaf block (callers pad); returns (B, S, K, G, Dv).
    """
    B, S, K, G, D = qg.shape
    Dv = v.shape[-1]
    leaf = min(block_q, S)
    nb = S // leaf
    # ---- block-diagonal causal leaves (one batched op) ---------------------
    qb = qg.reshape(B, nb, leaf, K, G, D)
    kb = k.reshape(B, nb, leaf, K, D)
    vb = v.reshape(B, nb, leaf, K, Dv)
    ti = jnp.arange(leaf)
    mask = (ti[None, :] <= ti[:, None])[None, None, None, None]  # (1,1,1,1,s,t)
    out, lse = _sdp(qb, kb, vb, scale, mask=mask)
    out = out.astype(jnp.float32)
    # ---- levels of strictly-lower rectangles -------------------------------
    size = 1
    while size < nb:
        R = leaf * size                 # rectangle side
        m = nb // (2 * size)            # rectangles at this level
        q_r = qg.reshape(B, m, 2 * R, K, G, D)[:, :, R:]
        k_r = k.reshape(B, m, 2 * R, K, D)[:, :, :R]
        v_r = v.reshape(B, m, 2 * R, K, Dv)[:, :, :R]
        o_r, l_r = _rect_chunked(q_r, k_r, v_r, scale, block_kv,
                                 block_q=4 * leaf)
        # merge into the running accumulators for those query rows
        out_v = out.reshape(B, m, 2 * R, K, G, -1)
        lse_v = lse.reshape(B, m, 2 * R, K, G)
        o_hi, l_hi = _merge(out_v[:, :, R:], lse_v[:, :, R:],
                            o_r.astype(jnp.float32), l_r.astype(jnp.float32))
        out = jnp.concatenate([out_v[:, :, :R], o_hi], axis=2).reshape(out.shape)
        lse = jnp.concatenate([lse_v[:, :, :R], l_hi], axis=2).reshape(lse.shape)
        size *= 2
    return out


# ================================================================= attention
def attention(q, k, v, *, causal: bool = True, scale: Optional[float] = None,
              kv_valid_len=None, kv_start=None, block_q: int = 512,
              block_kv: int = 1024):
    """Multi-head attention with GQA.

    q: (B, Sq, H, D); k/v: (B, Skv, K, Dk/Dv), H % K == 0.
      * kv_valid_len set   -> decode against a cache (mask t > pos).
      * causal             -> exact binary-blocked causal attention.
      * else               -> full (cross/encoder) attention, kv-chunked.
    kv_start (B,) int32, if given, masks keys before each row's first
    real token (left-padded serving batches).

    On TPU the Pallas kernels take causal self-attention and single-token
    decode whenever :func:`fit_block` can tile the lengths; every other
    shape takes the jnp path.
    """
    B, Sq, H, D = q.shape
    K = k.shape[2]
    G = H // K
    Skv = k.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    ct = q.dtype
    qg = q.reshape(B, Sq, K, G, D)
    mode = ("decode" if kv_valid_len is not None
            else "causal" if causal else "full")

    if _use_pallas():
        starts = (jnp.zeros((B,), jnp.int32) if kv_start is None
                  else jnp.asarray(kv_start, jnp.int32))
        if mode == "causal" and Sq == Skv and fit_block(Sq, block_q) \
                and fit_block(Skv, block_kv):
            from . import flash_attention as fa
            traced_impls[(mode, "pallas")] += 1
            return _per_shard(
                lambda q, k, v, st: fa.flash_attention(
                    q, k, v, st, causal=True, scale=scale, block_q=block_q,
                    block_kv=block_kv, interpret=_interpret()),
                (q, k, v, starts), (q, k, v, starts))
        if mode == "decode" and Sq == 1 and v.shape[-1] == D \
                and fit_block(Skv, block_kv):
            from . import flash_decode as fd
            traced_impls[(mode, "pallas")] += 1
            return _per_shard(
                lambda q, k, v, st, vl: fd.flash_decode(
                    q, k, v, vl, st, scale=scale, block_kv=block_kv,
                    interpret=_interpret()),
                (q, k, v, starts, jnp.asarray(kv_valid_len, jnp.int32)),
                (q, k, v, starts))
    traced_impls[(mode, "jnp")] += 1

    if mode == "causal" and kv_start is None and Sq == Skv and Sq > block_q \
            and Sq % block_q == 0 and _is_pow2(Sq // block_q):
        out = _causal_binary(qg.astype(jnp.float32), k.astype(jnp.float32),
                             v.astype(jnp.float32), scale, block_q, block_kv)
        return out.reshape(B, Sq, H, -1).astype(ct)

    # ---- small / decode / cross / padded path ------------------------------
    qf = qg.astype(jnp.float32)
    kf, vf = k.astype(jnp.float32), v.astype(jnp.float32)
    ti = jnp.arange(Skv)
    mask = None
    if kv_valid_len is not None:
        qpos = kv_valid_len - Sq + jnp.arange(Sq)
        mask = (ti[None, :] <= qpos[:, None])[None, None, None]
    elif causal:
        mask = (ti[None, :] <= jnp.arange(Sq)[:, None] + (Skv - Sq))[None, None, None]
    if kv_start is not None:
        live = (ti[None, :] >= jnp.asarray(kv_start)[:, None])
        live = live[:, None, None, None, :]               # (B, 1, 1, 1, T)
        mask = live if mask is None else mask & live
    o, _ = _sdp(qf[:, None], kf[:, None], vf[:, None], scale,
                mask=mask[:, None] if mask is not None else None)
    return o[:, 0].reshape(B, Sq, H, -1).astype(ct)


def _per_shard(kernel, args, batched):
    """Run a Pallas kernel under the model's mesh, if one is set.

    XLA cannot partition a Mosaic kernel, so on a multi-device mesh each
    device runs it on its own shard of the batch (``jax.shard_map``);
    the arguments in ``batched`` are split over the batch axes when the
    batch divides, and every other argument is replicated.
    """
    from repro.models import dist          # the mesh the model runs under
    mesh = dist.get_mesh()
    if mesh is None or mesh.size == 1:
        return kernel(*args)
    axes = dist.batch_axes()
    n = math.prod(mesh.shape[a] for a in axes)
    split = args[0].shape[0] % n == 0
    bspec = P(axes if len(axes) > 1 else axes[0]) if split else P()
    specs = tuple(bspec if any(a is b for b in batched) else P()
                  for a in args)
    return jax.shard_map(kernel, mesh=mesh, in_specs=specs, out_specs=bspec,
                         check_vma=False)(*args)


def gmm_tile(n_rows: int, n_groups: int) -> int:
    """Row tile of a grouped product over ``n_rows`` assignments to
    ``n_groups`` experts: the largest power of two from
    :data:`SEQ_ALIGN` to :data:`GMM_TILE` whose padding, up to a tile a
    group, stays within ``n_rows`` (a decode step's few rows take small
    tiles, a training step's many rows large ones)."""
    tm = GMM_TILE
    while tm > SEQ_ALIGN and n_groups * tm > n_rows:
        tm //= 2
    return tm


def grouped_matmul(lhs, rhs, counts, tm: int):
    """Each expert's rows of ``lhs`` (M, K) times its matrix of ``rhs``
    (E, K, N).  Expert ``e``'s ``counts[e]`` rows come after the groups
    before it, each group starting on a tile of ``tm`` rows; rows after
    the last group are not computed (the Pallas kernel leaves them
    unwritten, ``ragged_dot`` zero).  Differentiable."""
    telemetry.count("kernels.moe_gmm")
    if _use_pallas():
        from . import moe_gmm
        traced_impls[("grouped", "pallas")] += 1
        return moe_gmm.grouped_matmul(lhs, rhs, counts, tm, _interpret())
    traced_impls[("grouped", "jnp")] += 1
    sizes = -(-counts // tm) * tm
    return jax.lax.ragged_dot(lhs, rhs, sizes.astype(jnp.int32))


def _is_pow2(n: int) -> bool:
    return n > 0 and (n & (n - 1)) == 0


# ================================================================== SSD scan
def ssd_scan(x, dt, A, B, C, D, *, chunk: int = 256,
             return_final_state: bool = False):
    """Mamba-2 SSD: chunked parallel scan (matches kernels.ref.naive_ssd).

    Shapes as in the reference.  Chunk-local quadratic attention-form +
    carried inter-chunk state; one lax.scan over chunks.  With
    return_final_state, also returns the (b,h,p,n) state after the last
    token (prefill -> decode handoff).
    """
    if _use_pallas() and not return_final_state:
        from . import ssd_scan as kern
        return kern.ssd_scan(x, dt, A, B, C, D, chunk=chunk,
                             interpret=_interpret())
    return _ssd_jnp(x, dt, A, B, C, D, chunk, return_final_state)


def _ssd_jnp(x, dt, A, Bm, Cm, D, chunk: int, return_final_state: bool = False):
    b, s, h, p = x.shape
    n = Bm.shape[-1]
    c = min(chunk, s)
    nc = s // c
    assert s % c == 0, f"seq {s} not divisible by chunk {c}"
    xf = x.astype(jnp.float32).reshape(b, nc, c, h, p)
    dtf = dt.astype(jnp.float32).reshape(b, nc, c, h)
    Bf = Bm.astype(jnp.float32).reshape(b, nc, c, n)
    Cf = Cm.astype(jnp.float32).reshape(b, nc, c, n)
    la = dtf * A[None, None, None, :]            # log decay per step (<=0)
    cs = jnp.cumsum(la, axis=2)                  # within-chunk cumulative
    total = cs[:, :, -1, :]                      # (b,nc,h)

    # ---- intra-chunk (attention form): y_t = sum_{u<=t} C_t.B_u dA(u->t) x_u
    seg = cs[:, :, :, None, :] - cs[:, :, None, :, :]     # (b,nc,t,u,h)
    ti, ui = jnp.arange(c), jnp.arange(c)
    causal = (ui[None, :] <= ti[:, None])[None, None, :, :, None]
    # mask in log space: exp of a masked +big region would give inf * 0
    # = NaN in the backward pass
    gate = jnp.exp(jnp.where(causal, seg, -1e30))
    cb = jnp.einsum("bktn,bkun->bktu", Cf, Bf)
    w = cb[..., None] * gate                      # (b,nc,t,u,h)
    y_intra = jnp.einsum("bktuh,bkuhp->bkthp", w, xf * dtf[..., None])

    # ---- chunk states & inter-chunk scan -----------------------------------
    decay_to_end = jnp.exp(total[:, :, None, :] - cs)     # (b,nc,c,h)
    states = jnp.einsum("bkch,bkcn,bkchp->bkhpn",
                        decay_to_end * dtf, Bf, xf)

    def carry_fn(st, xs):
        st_k, tot_k = xs                          # (b,h,p,n), (b,h)
        new = st * jnp.exp(tot_k)[:, :, None, None] + st_k
        return new, st                            # emit state BEFORE chunk k

    st0 = jnp.zeros((b, h, p, n), jnp.float32)
    final, prev = jax.lax.scan(carry_fn, st0,
                               (jnp.moveaxis(states, 1, 0), jnp.moveaxis(total, 1, 0)))
    prev = jnp.moveaxis(prev, 0, 1)               # (b,nc,h,p,n) state entering k
    y_inter = jnp.einsum("bkcn,bkch,bkhpn->bkchp", Cf, jnp.exp(cs), prev)
    y = (y_intra + y_inter).reshape(b, s, h, p)
    y = y + x.astype(jnp.float32) * D[None, None, :, None]
    y = y.astype(x.dtype)
    return (y, final) if return_final_state else y


def ssd_step(state, x_t, dt_t, A, B_t, C_t, D):
    """Single decode step of the SSD recurrence.  state: (b,h,p,n)."""
    xf = x_t.astype(jnp.float32)
    dtf = dt_t.astype(jnp.float32)
    decay = jnp.exp(dtf * A[None, :])
    st = state * decay[..., None, None] + jnp.einsum(
        "bhp,bn->bhpn", xf * dtf[..., None], B_t.astype(jnp.float32))
    y = jnp.einsum("bhpn,bn->bhp", st, C_t.astype(jnp.float32))
    y = y + xf * D[None, :, None]
    return st, y.astype(x_t.dtype)


# ================================================================ mLSTM scan
def mlstm_scan(q, k, v, i_gate, f_gate, *, chunk: int = 256,
               return_final_state: bool = False):
    """Chunked-parallel mLSTM (matches kernels.ref.naive_mlstm).  With
    return_final_state also returns the (C, n, m) matrix memory after the
    last token."""
    return _mlstm_jnp(q, k, v, i_gate, f_gate, min(chunk, q.shape[1]),
                      return_final_state)


def _mlstm_jnp(q, k, v, ig, fg, chunk: int, return_final_state: bool = False):
    b, s, h, d = q.shape
    c = chunk
    assert s % c == 0
    nc = s // c
    qf = q.astype(jnp.float32).reshape(b, nc, c, h, d)
    kf = k.astype(jnp.float32).reshape(b, nc, c, h, d)
    vf = v.astype(jnp.float32).reshape(b, nc, c, h, d)
    logf = jax.nn.log_sigmoid(fg.astype(jnp.float32)).reshape(b, nc, c, h)
    ii = ig.astype(jnp.float32).reshape(b, nc, c, h)
    csf = jnp.cumsum(logf, axis=2)                 # (b,nc,c,h)
    total = csf[:, :, -1, :]

    # log-weights: within-chunk decay from u to t plus input gate at u
    seg = csf[:, :, :, None, :] - csf[:, :, None, :, :]   # (b,nc,t,u,h)
    lw = seg + ii[:, :, None, :, :]
    ti = jnp.arange(c)
    causal = (ti[None, :] <= ti[:, None])[None, None, :, :, None]
    lw = jnp.where(causal, lw, -jnp.inf)
    # stabilizer per (chunk, t): running max over available inputs
    m_intra = jnp.max(lw, axis=3)                  # (b,nc,t,h)

    def carry_fn(carry, xs):
        # inter-chunk stabilized matrix memory
        Cs, ns, m = carry                          # (b,h,d,d),(b,h,d),(b,h)
        kc, vc, ic, lfc, csfc, totc = xs
        m_loc = jnp.max(csfc[:, -1, None, :] - csfc + ic, axis=1)  # (b,h)
        m_new = jnp.maximum(m + totc, m_loc)
        w = jnp.exp(csfc[:, -1, None, :] - csfc + ic - m_new[:, None, :])
        Cc = jnp.einsum("bch,bchd,bche->bhde", w, kc, vc)
        nc_ = jnp.einsum("bch,bchd->bhd", w, kc)
        scale_old = jnp.exp(m + totc - m_new)
        C_out = Cs * scale_old[..., None, None] + Cc
        n_out = ns * scale_old[..., None] + nc_
        return (C_out, n_out, m_new), (Cs, ns, m)

    init = (jnp.zeros((b, h, d, d), jnp.float32),
            jnp.zeros((b, h, d), jnp.float32),
            jnp.full((b, h), -1e30, jnp.float32))
    final, (Cprev, nprev, mprev) = jax.lax.scan(
        carry_fn, init,
        tuple(jnp.moveaxis(t, 1, 0) for t in
              (kf, vf, ii, logf, csf, total)))
    Cprev = jnp.moveaxis(Cprev, 0, 1)              # state entering chunk
    nprev = jnp.moveaxis(nprev, 0, 1)
    mprev = jnp.moveaxis(mprev, 0, 1)              # (b,nc,h)

    # combine intra + inter with shared stabilizer
    m_inter = mprev[:, :, None, :] + csf           # (b,nc,c,h)
    m_tot = jnp.maximum(m_intra, m_inter)
    w_intra = jnp.exp(lw - m_tot[:, :, :, None, :])
    s_qk = jnp.einsum("bkthd,bkuhd->bktuh", qf, kf)
    num = jnp.einsum("bktuh,bkuhe->bkthe", s_qk * w_intra, vf)
    den = jnp.einsum("bktuh,bkuhd->bkthd", w_intra, kf)
    den = jnp.einsum("bkthd,bkthd->bkth", qf, den)
    w_int = jnp.exp(m_inter - m_tot)
    num = num + jnp.einsum("bkth,bkthd,bkhde->bkthe", w_int, qf, Cprev)
    den = den + jnp.einsum("bkth,bkthd,bkhd->bkth", w_int, qf, nprev)
    den = jnp.maximum(jnp.abs(den), jnp.exp(-m_tot))
    y = (num / den[..., None]).reshape(b, s, h, d)
    y = y.astype(q.dtype)
    return (y, final) if return_final_state else y
