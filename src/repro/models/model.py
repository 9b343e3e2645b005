"""Model assembly: init / loss / prefill / decode for every family.

Families and their block structure:
  dense|moe|vlm : [GQA or MLA attention] + [SwiGLU or MoE FFN], scanned.
  ssm (xLSTM)   : mLSTM blocks with sLSTM every `slstm_every` (python loop —
                  small models, heterogeneous params).
  hybrid        : Mamba-2 stack, one *shared-weight* GQA+FFN block applied
                  every `attn_every` layers (Zamba-style), single scan with
                  an inlined conditional.
  audio         : enc-dec; encoder non-causal GQA blocks, decoder adds
                  cross-attention to the (stub) frame embeddings.

Caches: homogeneous families carry stacked (L, ...) cache arrays through
the layer scan; recurrent families carry O(1) states (see ssm.py).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from . import dist
from . import moe as moe_mod
from . import ssm as ssm_mod
from .config import ModelConfig
from .layers import (_init, embed, gqa_fwd, init_embedding, init_gqa,
                     init_mla, init_rmsnorm, init_swiglu, mla_fwd, rmsnorm,
                     swiglu_fwd, unembed)

Params = Dict[str, Any]


# ------------------------------------------------------------------ utilities
def _remat(fn, cfg: ModelConfig, in_scan: bool = True):
    """Activation checkpointing.  prevent_cse=False is only sound inside a
    lax.scan body (the scan barrier already blocks CSE); for python-loop
    layer stacks XLA would CSE the recompute away and silently undo remat
    (caught by the xlstm memory probe, EXPERIMENTS.md §Perf)."""
    if cfg.remat == "full":
        return jax.checkpoint(fn, prevent_cse=not in_scan)
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, prevent_cse=not in_scan,
            policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)
    return fn


def _stack_init(key, n: int, init_fn):
    return jax.vmap(init_fn)(jax.random.split(key, n))


# ============================================================= dense/moe block
def _init_block(key, cfg: ModelConfig, moe_layer: bool) -> Params:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    dt = jnp.dtype(cfg.param_dtype)
    p = {
        "ln1": init_rmsnorm(cfg.d_model, dt),
        "ln2": init_rmsnorm(cfg.d_model, dt),
        "attn": init_mla(k1, cfg) if cfg.mla else init_gqa(k1, cfg),
    }
    if moe_layer:
        p["moe"] = moe_mod.init_moe(k2, cfg)
    else:
        p["ffn"] = init_swiglu(k3, cfg.d_model, cfg.d_ff, dt)
    return p


def _block_fwd(p: Params, x, cfg: ModelConfig, *, positions, cache=None,
               cache_index=None, causal=True, moe_layer=False,
               return_kv=False, kv_start=None):
    x = dist.constrain_batch(x)
    attn_fn = mla_fwd if cfg.mla else gqa_fwd
    h, new_cache = attn_fn(p["attn"], rmsnorm(p["ln1"], x, cfg.norm_eps), cfg,
                           positions=positions, cache=cache,
                           cache_index=cache_index, causal=causal,
                           return_kv=return_kv, kv_start=kv_start)
    x = x + h
    hn = rmsnorm(p["ln2"], x, cfg.norm_eps)
    if moe_layer:
        h, moe = moe_mod.moe_fwd(p["moe"], hn, cfg)
    else:
        h, moe = swiglu_fwd(p["ffn"], hn, cfg.compute_dtype), 0.0
    return dist.constrain_batch(x + h), new_cache, moe


# ================================================================== init
def init_params(key, cfg: ModelConfig) -> Params:
    keys = jax.random.split(key, 8)
    p: Params = {"embed": init_embedding(keys[0], cfg),
                 "ln_f": init_rmsnorm(cfg.d_model, jnp.dtype(cfg.param_dtype))}
    fam = cfg.family
    if fam in ("dense", "moe", "vlm"):
        n_pre = cfg.moe.first_dense if cfg.moe else 0
        if n_pre:
            p["pre_layers"] = _stack_init(
                keys[1], n_pre, lambda k: _init_block(k, cfg, False))
        p["layers"] = _stack_init(
            keys[2], cfg.n_layers - n_pre,
            lambda k: _init_block(k, cfg, cfg.moe is not None))
    elif fam == "ssm":
        xl = cfg.xlstm
        assert cfg.n_layers % xl.slstm_every == 0, "xlstm group structure"
        n_groups = cfg.n_layers // xl.slstm_every
        n_m = xl.slstm_every - 1
        k1, k2 = jax.random.split(keys[1])
        p["slstm"] = _stack_init(k1, n_groups,
                                 lambda k: ssm_mod.init_slstm(k, cfg))
        m_flat = _stack_init(k2, n_groups * n_m,
                             lambda k: ssm_mod.init_mlstm(k, cfg))
        p["mlstm"] = jax.tree.map(
            lambda a: a.reshape(n_groups, n_m, *a.shape[1:]), m_flat)
    elif fam == "hybrid":
        p["layers"] = _stack_init(keys[1], cfg.n_layers,
                                  lambda k: ssm_mod.init_mamba2(k, cfg))
        p["shared_attn"] = _init_block(keys[2], cfg, False)
    elif fam == "audio":
        p["enc_layers"] = _stack_init(
            keys[1], cfg.n_enc_layers, lambda k: _init_block(k, cfg, False))
        p["layers"] = _stack_init(
            keys[2], cfg.n_layers, lambda k: _init_dec_block(k, cfg))
        p["ln_enc"] = init_rmsnorm(cfg.d_model, jnp.dtype(cfg.param_dtype))
    else:
        raise ValueError(fam)
    if fam == "vlm" and cfg.n_patches:
        p["patch_proj"] = _init(keys[3], (cfg.d_model, cfg.d_model),
                                cfg.d_model ** -0.5,
                                jnp.dtype(cfg.param_dtype))
    return p


def _init_dec_block(key, cfg: ModelConfig) -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    dt = jnp.dtype(cfg.param_dtype)
    return {
        "ln1": init_rmsnorm(cfg.d_model, dt),
        "ln_x": init_rmsnorm(cfg.d_model, dt),
        "ln2": init_rmsnorm(cfg.d_model, dt),
        "attn": init_gqa(k1, cfg),
        "xattn": init_gqa(k2, cfg),
        "ffn": init_swiglu(k3, cfg.d_model, cfg.d_ff, dt),
    }


def _dec_block_fwd(p, x, enc, cfg, *, positions, cache=None, cache_index=None,
                   return_kv=False):
    x = dist.constrain_batch(x)
    h, new_self = gqa_fwd(p["attn"], rmsnorm(p["ln1"], x, cfg.norm_eps), cfg,
                          positions=positions,
                          cache=None if cache is None else cache[:2],
                          cache_index=cache_index, causal=True,
                          return_kv=return_kv)
    x = x + h
    h, _ = gqa_fwd(p["xattn"], rmsnorm(p["ln_x"], x, cfg.norm_eps), cfg,
                   positions=positions, kv_source=enc, causal=False)
    x = x + h
    h = swiglu_fwd(p["ffn"], rmsnorm(p["ln2"], x, cfg.norm_eps),
                   cfg.compute_dtype)
    return x + h, new_self



# ---------------------------------------------------------------- hybrid util
def _hybrid_split(cfg: ModelConfig, stacked):
    """(L, ...) stacked mamba params/states -> ((G, k, ...), (tail, ...))."""
    k = cfg.attn_every
    g = cfg.n_layers // k
    body = jax.tree.map(lambda a: a[:g * k].reshape(g, k, *a.shape[1:]),
                        stacked)
    tail = jax.tree.map(lambda a: a[g * k:], stacked)
    return body, tail


def _hybrid_join(cfg: ModelConfig, body, tail):
    return jax.tree.map(
        lambda b, t: jnp.concatenate(
            [b.reshape(-1, *b.shape[2:]), t], axis=0), body, tail)


# ============================================================ forward (train)
class TrainBatch(NamedTuple):
    tokens: jax.Array                      # (B, S) inputs
    labels: jax.Array                      # (B, S) next-token targets
    extra: Optional[jax.Array] = None      # vlm patches / audio frames


def forward(params: Params, batch: TrainBatch, cfg: ModelConfig) -> Tuple[jax.Array, jax.Array]:
    """Returns (logits (B,S,V) or (B,S_text,V), MoE readings): the MoE
    layers' (N_STATS,) readings summed over layers, the balance loss
    first (zeros without MoE layers)."""
    fam = cfg.family
    x = embed(params["embed"], batch.tokens, cfg)
    aux_total = jnp.zeros((moe_mod.N_STATS,), jnp.float32)
    if fam == "vlm" and batch.extra is not None:
        ct = jnp.dtype(cfg.compute_dtype)
        patches = jnp.einsum("bpd,de->bpe", batch.extra.astype(ct),
                             params["patch_proj"].astype(ct))
        x = dist.constrain_batch(jnp.concatenate([patches, x], axis=1))
    B, S = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))

    if fam in ("dense", "moe", "vlm"):
        moe_layer = cfg.moe is not None

        def body(carry, lp):
            h, aux = carry
            h2, _, a = _block_fwd(lp, h, cfg, positions=positions,
                                  moe_layer=moe_layer)
            return (h2, aux + a), None

        if "pre_layers" in params:
            def pre_body(carry, lp):
                h, aux = carry
                h2, _, a = _block_fwd(lp, h, cfg, positions=positions,
                                      moe_layer=False)
                return (h2, aux + a), None
            (x, aux_total), _ = jax.lax.scan(
                _remat(pre_body, cfg), (x, aux_total), params["pre_layers"])
        (x, aux_total), _ = jax.lax.scan(
            _remat(body, cfg), (x, aux_total), params["layers"])
    elif fam == "ssm":
        def m_body(h, lp):
            h = dist.constrain_batch(h)
            d, _ = ssm_mod.mlstm_fwd(lp, h, cfg)
            return dist.constrain_batch(h + d), None

        def group_body(h, gp):
            sp, mp = gp
            h = dist.constrain_batch(h)
            d, _ = ssm_mod.slstm_fwd(sp, h, cfg)
            h = dist.constrain_batch(h + d)
            h, _ = jax.lax.scan(_remat(m_body, cfg), h, mp)
            return h, None

        x, _ = jax.lax.scan(_remat(group_body, cfg), x,
                            (params["slstm"], params["mlstm"]))
    elif fam == "hybrid":
        shared = params["shared_attn"]
        gp, tail = _hybrid_split(cfg, params["layers"])

        def m_body(h, lp):
            h = dist.constrain_batch(h)
            d, _ = ssm_mod.mamba2_fwd(lp, h, cfg)
            return dist.constrain_batch(h + d), None

        def group_body(h, glp):
            h, _ = jax.lax.scan(_remat(m_body, cfg), h, glp)
            h, _, _ = _block_fwd(shared, h, cfg, positions=positions)
            return h, None

        x, _ = jax.lax.scan(_remat(group_body, cfg), x, gp)
        x, _ = jax.lax.scan(_remat(m_body, cfg), x, tail)
    elif fam == "audio":
        enc = batch.extra.astype(jnp.dtype(cfg.compute_dtype))
        e_pos = jnp.broadcast_to(jnp.arange(enc.shape[1])[None],
                                 enc.shape[:2])

        def enc_body(h, lp):
            h2, _, _ = _block_fwd(lp, h, cfg, positions=e_pos, causal=False)
            return h2, None
        enc, _ = jax.lax.scan(_remat(enc_body, cfg), enc, params["enc_layers"])
        enc = rmsnorm(params["ln_enc"], enc, cfg.norm_eps)

        def dec_body(h, lp):
            h2, _ = _dec_block_fwd(lp, h, enc, cfg, positions=positions)
            return h2, None
        x, _ = jax.lax.scan(_remat(dec_body, cfg), x, params["layers"])

    x = rmsnorm(params["ln_f"], x, cfg.norm_eps)
    if fam == "vlm" and batch.extra is not None:
        x = x[:, batch.extra.shape[1]:]
    logits = unembed(params["embed"], x, cfg)
    return logits, aux_total


def loss_fn(params: Params, batch: TrainBatch, cfg: ModelConfig):
    """Mean next-token NLL over the unembed rows, 1e-4 of the mean squared
    log Z, and the MoE balance loss at ``aux_alpha``.  Metrics of MoE
    models add the held experts' readings (``moe_rows`` and
    ``moe_rows_max``)."""
    logits, moe = forward(params, batch, cfg)
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    # gold logit via masked reduction: take_along_axis over the
    # model-sharded vocab dim would all-gather the full logits
    # (EXPERIMENTS.md: seamless/internvl train memory iteration)
    v_iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape,
                                      logits.ndim - 1)
    gold = jnp.sum(jnp.where(v_iota == batch.labels[..., None],
                             logits, 0.0), axis=-1)
    nll = (logz - gold).mean()
    zloss = 1e-4 * (logz ** 2).mean()
    aux = moe[0]
    loss = nll + zloss + (cfg.moe.aux_alpha * aux if cfg.moe else 0.0)
    metrics = {"nll": nll, "aux": aux, "zloss": zloss}
    if cfg.moe:
        metrics.update(zip(MOE_METRICS, moe[1:]))
    return loss, metrics


#: loss_fn's MoE readings, summed over layers (and microbatches)
MOE_METRICS = ("moe_rows", "moe_rows_max")


# ======================================================== caches + decode step
def init_cache(cfg: ModelConfig, batch: int, max_seq: int):
    """Concrete zero-filled cache pytree for serving."""
    ct = jnp.dtype(cfg.compute_dtype)
    fam = cfg.family
    if fam in ("dense", "moe", "vlm"):
        L = cfg.n_layers - (cfg.moe.first_dense if cfg.moe else 0)
        Lp = cfg.moe.first_dense if cfg.moe else 0
        if cfg.mla:
            m = cfg.mla
            mk = lambda n: (jnp.zeros((n, batch, max_seq, m.kv_lora), ct),
                            jnp.zeros((n, batch, max_seq, m.d_rope), ct))
        else:
            mk = lambda n: (jnp.zeros((n, batch, max_seq, cfg.n_kv, cfg.d_head), ct),
                            jnp.zeros((n, batch, max_seq, cfg.n_kv, cfg.d_head), ct))
        out = {"layers": mk(L)}
        if Lp:
            out["pre_layers"] = mk(Lp)
        return out
    if fam == "ssm":
        xl = cfg.xlstm
        n_groups = cfg.n_layers // xl.slstm_every
        n_m = xl.slstm_every - 1
        B, H, dh = batch, cfg.n_heads, cfg.d_model // cfg.n_heads
        s_state = ssm_mod.SLSTMState(
            c=jnp.zeros((B, H, dh), jnp.float32),
            n=jnp.zeros((B, H, dh), jnp.float32),
            h=jnp.zeros((B, H, dh), ct),
            m=jnp.full((B, H, dh), -1e30, jnp.float32))
        m_state = ssm_mod.init_mlstm_state(cfg, batch)
        stack = lambda st, n: jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (n, *a.shape)).copy(), st)
        return {"slstm": stack(s_state, n_groups),
                "mlstm": jax.tree.map(
                    lambda a: jnp.broadcast_to(
                        a[None, None], (n_groups, n_m, *a.shape)).copy(),
                    m_state)}
    if fam == "hybrid":
        n_apps = cfg.n_layers // cfg.attn_every
        mamba = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (cfg.n_layers, *x.shape)),
            ssm_mod.init_mamba_state(cfg, batch))
        attn = (jnp.zeros((n_apps, batch, max_seq, cfg.n_kv, cfg.d_head), ct),
                jnp.zeros((n_apps, batch, max_seq, cfg.n_kv, cfg.d_head), ct))
        return {"mamba": mamba, "attn": attn}
    if fam == "audio":
        return {
            "self": (jnp.zeros((cfg.n_layers, batch, max_seq, cfg.n_kv, cfg.d_head), ct),
                     jnp.zeros((cfg.n_layers, batch, max_seq, cfg.n_kv, cfg.d_head), ct)),
            "enc": jnp.zeros((batch, cfg.enc_len, cfg.d_model), ct),
        }
    raise ValueError(fam)


def decode_step(params: Params, cache, tokens, pos, cfg: ModelConfig,
                kv_start=None):
    """One token for every sequence.  tokens: (B, 1); pos: scalar index.
    kv_start (B,), attention families only: cache slots before it are
    left padding and masked.  Returns (logits (B, V), new_cache)."""
    fam = cfg.family
    x = embed(params["embed"], tokens, cfg)
    B = x.shape[0]
    positions = jnp.full((B, 1), pos, jnp.int32)

    if fam in ("dense", "moe", "vlm"):
        def body(h, xs):
            lp, ck = xs
            h2, new_ck, _ = _block_fwd(lp, h, cfg, positions=positions,
                                       cache=ck, cache_index=pos,
                                       moe_layer=cfg.moe is not None,
                                       kv_start=kv_start)
            return h2, new_ck
        new_cache = dict(cache)
        if "pre_layers" in params:
            def pre_body(h, xs):
                lp, ck = xs
                h2, new_ck, _ = _block_fwd(lp, h, cfg, positions=positions,
                                           cache=ck, cache_index=pos,
                                           moe_layer=False, kv_start=kv_start)
                return h2, new_ck
            x, new_cache["pre_layers"] = jax.lax.scan(
                pre_body, x, (params["pre_layers"], cache["pre_layers"]))
        x, new_cache["layers"] = jax.lax.scan(
            body, x, (params["layers"], cache["layers"]))
    elif fam == "ssm":
        def m_body(h, xs):
            lp, st = xs
            d, st2 = ssm_mod.mlstm_fwd(lp, h, cfg, state=st)
            return h + d, st2

        def group_body(h, xs):
            sp, mp, s_st, m_st = xs
            d, s_st2 = ssm_mod.slstm_fwd(sp, h, cfg, state=s_st)
            h = h + d
            h, m_st2 = jax.lax.scan(m_body, h, (mp, m_st))
            return h, (s_st2, m_st2)

        x, (s_new, m_new) = jax.lax.scan(
            group_body, x, (params["slstm"], params["mlstm"],
                            cache["slstm"], cache["mlstm"]))
        new_cache = {"slstm": s_new, "mlstm": m_new}
    elif fam == "hybrid":
        mamba_new, attn_new, x = _hybrid_decode(params, cache, x, positions,
                                                pos, cfg)
        new_cache = {"mamba": mamba_new, "attn": attn_new}
    elif fam == "audio":
        enc = cache["enc"]
        def body(h, xs):
            lp, ck = xs
            h2, new_self = _dec_block_fwd(lp, h, enc, cfg,
                                          positions=positions,
                                          cache=(ck[0], ck[1]),
                                          cache_index=pos)
            return h2, new_self
        x, new_self = jax.lax.scan(body, x, (params["layers"], cache["self"]))
        new_cache = {"self": new_self, "enc": enc}

    x = rmsnorm(params["ln_f"], x, cfg.norm_eps)
    logits = unembed(params["embed"], x[:, 0], cfg)[..., :cfg.vocab]
    return logits, new_cache


def _hybrid_decode(params, cache, x, positions, pos, cfg: ModelConfig):
    """Hybrid decode: group scan [k mamba + shared attn], per-application
    attention caches consumed as scan xs (no dynamic indexing)."""
    shared = params["shared_attn"]
    gp, tail = _hybrid_split(cfg, params["layers"])
    gst, tail_st = _hybrid_split(cfg, cache["mamba"])

    def m_body(h, xs):
        lp, mst = xs
        d, mst2 = ssm_mod.mamba2_fwd(lp, h, cfg, state=mst)
        return h + d, mst2

    def group_body(h, xs):
        glp, gmst, ck, cv = xs
        h, mst2 = jax.lax.scan(m_body, h, (glp, gmst))
        h, new_c, _ = _block_fwd(shared, h, cfg, positions=positions,
                                 cache=(ck, cv), cache_index=pos)
        return h, (mst2, new_c[0], new_c[1])

    ck, cv = cache["attn"]
    x, (gst2, ck2, cv2) = jax.lax.scan(group_body, x, (gp, gst, ck, cv))
    x, tail_st2 = jax.lax.scan(m_body, x, (tail, tail_st))
    mamba_new = _hybrid_join(cfg, gst2, tail_st2)
    return mamba_new, (ck2, cv2), x


# ---------------------------------------------------------------- prefill
def prefill(params: Params, tokens, cfg: ModelConfig,
            extra: Optional[jax.Array] = None, kv_start=None):
    """Process a full prompt; returns (last-token logits, cache).

    Implemented as forward + cache extraction for the attention families;
    recurrent families run their chunked scans and keep final states.
    kv_start (B,), dense/moe/vlm only: tokens before it are left padding
    and masked as keys.
    """
    fam = cfg.family
    B, S = tokens.shape
    x = embed(params["embed"], tokens, cfg)
    if fam == "vlm" and extra is not None:
        ct = jnp.dtype(cfg.compute_dtype)
        patches = jnp.einsum("bpd,de->bpe", extra.astype(ct),
                             params["patch_proj"].astype(ct))
        x = dist.constrain_batch(jnp.concatenate([patches, x], axis=1))
        S = x.shape[1]
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    max_seq = S

    if fam in ("dense", "moe", "vlm"):
        def body(h, lp):
            h2, kv, _ = _block_fwd(lp, h, cfg, positions=positions,
                                   moe_layer=cfg.moe is not None,
                                   return_kv=True, kv_start=kv_start)
            return h2, kv
        cache = {}
        if "pre_layers" in params:
            def pre_body(h, lp):
                h2, kv, _ = _block_fwd(lp, h, cfg, positions=positions,
                                       moe_layer=False, return_kv=True,
                                       kv_start=kv_start)
                return h2, kv
            x, cache["pre_layers"] = jax.lax.scan(
                pre_body, x, params["pre_layers"])
        x, cache["layers"] = jax.lax.scan(body, x, params["layers"])
    elif fam == "audio":
        enc = extra.astype(jnp.dtype(cfg.compute_dtype))
        e_pos = jnp.broadcast_to(jnp.arange(enc.shape[1])[None], enc.shape[:2])

        def enc_body(h, lp):
            h2, _, _ = _block_fwd(lp, h, cfg, positions=e_pos, causal=False)
            return h2, None
        enc, _ = jax.lax.scan(enc_body, enc, params["enc_layers"])
        enc = rmsnorm(params["ln_enc"], enc, cfg.norm_eps)
        def dec_body(h, lp):
            h2, kv = _dec_block_fwd(lp, h, enc, cfg, positions=positions,
                                    return_kv=True)
            return h2, kv
        x, new_self = jax.lax.scan(dec_body, x, params["layers"])
        cache = {"self": new_self, "enc": enc}
    elif fam == "ssm":
        def m_body(h, lp):
            d, st = ssm_mod.mlstm_fwd(lp, h, cfg, return_state=True)
            return h + d, st

        def group_body(h, gp):
            sp, mp = gp
            d, s_st = ssm_mod.slstm_fwd(sp, h, cfg, return_state=True)
            h = h + d
            h, m_st = jax.lax.scan(m_body, h, mp)
            return h, (s_st, m_st)

        x, (s_st, m_st) = jax.lax.scan(
            group_body, x, (params["slstm"], params["mlstm"]))
        cache = {"slstm": s_st, "mlstm": m_st}
    elif fam == "hybrid":
        shared = params["shared_attn"]
        gp, tail = _hybrid_split(cfg, params["layers"])

        def m_body(h, lp):
            d, mst = ssm_mod.mamba2_fwd(lp, h, cfg, return_state=True)
            return h + d, mst

        def group_body(h, glp):
            h, mst = jax.lax.scan(m_body, h, glp)
            h, kv, _ = _block_fwd(shared, h, cfg, positions=positions,
                                  return_kv=True)
            return h, (mst, kv[0], kv[1])

        x, (gst, ck, cv) = jax.lax.scan(group_body, x, gp)
        x, tail_st = jax.lax.scan(m_body, x, tail)
        cache = {"mamba": _hybrid_join(cfg, gst, tail_st),
                 "attn": (ck, cv)}
    else:
        raise NotImplementedError(fam)

    x = rmsnorm(params["ln_f"], x[:, -1:], cfg.norm_eps)
    logits = unembed(params["embed"], x[:, 0], cfg)[..., :cfg.vocab]
    return logits, cache
