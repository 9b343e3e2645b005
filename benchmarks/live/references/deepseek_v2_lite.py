"""Plain reference of the deepseek-v2-lite payload, as the configuration runs it.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``, every product also at
``Precision.HIGHEST``: no kernels, no cache, no capacity, no grouping of
rows.  It imports nothing of the program under test.  Weights and
training batches are drawn from the seed by the recipe the configuration
states (``jax.random.normal`` per tensor, scaled, stored in bfloat16; the
router in float32), so the reference starts from the same numbers
without taking any from the program.

The equations are DeepSeek-V2's (arXiv:2405.04434; the published
``modeling_deepseek.py``), with the configuration's cut:

- MLA without q compression: q = x Wq (16 heads of 128 + 64); the kv
  latent c = RMSNorm(x Wkv_a) (512 wide), k_nope and v from c Wkv_b; one
  64-wide rotary key x Wk_rope shared by the heads.  Rotary pairs are
  interleaved (DeepSeek-V2 permutes q_pe and k_pe into halves, then
  rotates halves: the same dot products).  YaRN frequencies (factor 40
  over 4,096 positions, beta 32 and 1) and the softmax scale
  192 ** -0.5 * (0.1 * mscale_all_dim * ln 40 + 1) ** 2.
- Layer 0 has a dense SwiGLU; the others an MoE: a softmax router over
  all ``router_experts`` (64), greedy top-6, the weights not
  renormalised (x routed_scaling_factor 1), the sum over the experts
  held here (the first ``n_routed_experts`` of the router's) of
  weight x SwiGLU_e(x), each held expert computed for every token with
  weight 0 where it was not picked; plus the shared experts, one SwiGLU
  of 2 x 1,408, once.  What the absent experts would add is left out, as
  the program leaves it out.
- The loss: mean NLL over the ``unembed_rows`` (the eighth of the
  vocabulary held here), 1e-4 of the mean squared log Z (the program's
  z-loss, a departure listed in the configuration) and aux_loss_alpha x
  the sequence-wise balance loss (seq_aux) summed over the MoE layers.

Every loss term is a mean over sequences of a per-sequence mean over
equal-length sequences, so the reference takes the gradient one sequence
at a time and averages: the same number as the program's microbatches,
with an eighth of the activations.  Attention is taken in blocks of
queries and the logits in blocks of positions, so that 4 x 8,192 fits.

``precision="fp8"`` is the control: every matrix product takes its two
operands through a per-tensor scaled float8 (e4m3 forward, e5m2 for the
cotangents), the step below the bfloat16 the configuration states.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
BF16 = jnp.bfloat16
HIGHEST = jax.lax.Precision.HIGHEST
#: queries per block of the causal attention, positions per logits block
Q_BLOCK = 1024
LOGITS_BLOCK = 2048


def sizes(cfg: dict) -> dict:
    """The reference's sizes from a configuration file's keys."""
    y = cfg["rope_scaling"]
    return {"L": cfg["num_hidden_layers"], "d": cfg["hidden_size"],
            "H": cfg["num_attention_heads"], "K": cfg["num_key_value_heads"],
            "Dh": cfg["head_dim"], "F": cfg["intermediate_size"],
            "V": cfg["vocab_size"], "Vp": cfg["unembed_rows"],
            "P": cfg["num_image_token"], "theta": cfg["rope_theta"],
            "eps": cfg["rms_norm_eps"],
            "kv_lora": cfg["kv_lora_rank"], "d_nope": cfg["qk_nope_head_dim"],
            "d_rope": cfg["qk_rope_head_dim"], "d_v": cfg["v_head_dim"],
            "E": cfg["router_experts"], "E_held": cfg["n_routed_experts"],
            "top_k": cfg["num_experts_per_tok"],
            "Fe": cfg["moe_intermediate_size"],
            "n_shared": cfg["n_shared_experts"],
            "dense": cfg["first_k_dense_replace"],
            "rsf": cfg["routed_scaling_factor"],
            "alpha": cfg["aux_loss_alpha"],
            "yarn": (y["factor"], y["original_max_position_embeddings"],
                     y["beta_fast"], y["beta_slow"], y["mscale"],
                     y["mscale_all_dim"])}


# --------------------------------------------------------------- weights
def _normal(key, shape, scale, dtype=BF16):
    return (jax.random.normal(key, shape, F32) * scale).astype(dtype)


def _swiglu_w(key, d, f):
    k = jax.random.split(key, 3)
    return {"w_gate": _normal(k[0], (d, f), d ** -0.5),
            "w_up": _normal(k[1], (d, f), d ** -0.5),
            "w_down": _normal(k[2], (f, d), f ** -0.5)}


def _layer(key, sz, moe: bool):
    d, H = sz["d"], sz["H"]
    k1, k2, k3, _ = jax.random.split(key, 4)
    a = jax.random.split(k1, 6)          # a[1] would be q's second factor
    attn = {"wq": _normal(a[0], (d, H, sz["d_nope"] + sz["d_rope"]),
                          d ** -0.5),
            "wkv_a": _normal(a[2], (d, sz["kv_lora"]), d ** -0.5),
            "kv_norm": {"scale": jnp.ones((sz["kv_lora"],), BF16)},
            "wk_rope": _normal(a[3], (d, sz["d_rope"]), d ** -0.5),
            "wkv_b": _normal(a[4], (sz["kv_lora"], H,
                                    sz["d_nope"] + sz["d_v"]),
                             sz["kv_lora"] ** -0.5),
            "wo": _normal(a[5], (H, sz["d_v"], d), (H * sz["d_v"]) ** -0.5)}
    out = {"attn": attn, "ln1": {"scale": jnp.ones((d,), BF16)},
           "ln2": {"scale": jnp.ones((d,), BF16)}}
    if moe:
        m = jax.random.split(k2, 5)
        E, Fe = sz["E_held"], sz["Fe"]
        out["moe"] = {"router": _normal(m[0], (d, sz["E"]), d ** -0.5, F32),
                      "w1": _normal(m[1], (E, d, Fe), d ** -0.5),
                      "w3": _normal(m[2], (E, d, Fe), d ** -0.5),
                      "w2": _normal(m[3], (E, Fe, d), Fe ** -0.5),
                      "shared": _swiglu_w(m[4], d, sz["n_shared"] * Fe)}
    else:
        out["ffn"] = _swiglu_w(k3, d, sz["F"])
    return out


@partial(jax.jit, static_argnames=("sz_items",))
def _init(key, sz_items):
    sz = dict(sz_items)
    d = sz["d"]
    keys = jax.random.split(key, 8)
    e1, e2 = jax.random.split(keys[0])
    stack = lambda k, n, moe: jax.vmap(                       # noqa: E731
        lambda kk: _layer(kk, sz, moe))(jax.random.split(k, n))
    return {"embed": {"tok": _normal(e1, (sz["Vp"], d), 0.02),
                      "unembed": _normal(e2, (sz["Vp"], d), d ** -0.5)},
            "ln_f": {"scale": jnp.ones((d,), BF16)},
            "pre_layers": stack(keys[1], sz["dense"], False),
            "layers": stack(keys[2], sz["L"] - sz["dense"], True)}


def init_weights(seed: int, sz: dict):
    """The whole model's weights from ``PRNGKey(seed)``, drawn in one
    compiled program (eager draws round differently on the TPU)."""
    return _init(jax.random.PRNGKey(seed), _items(sz))


def _items(sz: dict):
    return tuple(sorted(sz.items()))


def train_batch(seed: int, step: int, batch: int, text_len: int, sz: dict):
    """Step ``step``'s training batch: a drifting token walk over the
    vocabulary held here, numpy-generated from (seed, step)."""
    rng = np.random.default_rng((seed * 1_000_003 + step) % (2 ** 63))
    base = rng.integers(0, sz["V"], size=(batch, 1), dtype=np.int64)
    drift = rng.integers(-32, 33, size=(batch, text_len + 1), dtype=np.int64)
    toks = np.abs(base + np.cumsum(drift, axis=1)) % sz["V"]
    return toks[:, :-1].astype(np.int32), toks[:, 1:].astype(np.int32)


# ------------------------------------------------------------- precision
def _scaled_cast(x, dtype):
    amax = jnp.max(jnp.abs(x))
    s = jnp.where(amax > 0, amax / float(jnp.finfo(dtype).max), 1.0)
    return (x / s).astype(dtype).astype(F32) * s


@jax.custom_vjp
def _q8(x):
    return _scaled_cast(x, jnp.float8_e4m3fn)


def _q8_fwd(x):
    return _q8(x), None


def _q8_bwd(_, g):
    return (_scaled_cast(g, jnp.float8_e5m2),)


_q8.defvjp(_q8_fwd, _q8_bwd)


def _mm(prec: str):
    """einsum at the reference's precision."""
    if prec == "f32":
        return partial(jnp.einsum, precision=HIGHEST,
                       preferred_element_type=F32)
    if prec == "fp8":
        def mm(spec, a, b):
            return jnp.einsum(spec, _q8(a.astype(F32)), _q8(b.astype(F32)),
                              precision=HIGHEST, preferred_element_type=F32)
        return mm
    raise ValueError(prec)


# ---------------------------------------------------------------- model
def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(F32)


def _yarn_mscale(factor, m):
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_inv_freq(d: int, theta: float, yarn) -> np.ndarray:
    """DeepSeek-V2's YaRN inverse frequencies for ``d`` rotary dims."""
    factor, orig, beta_fast, beta_slow = yarn[:4]
    extra = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float64) / d)
    inter = extra / factor

    def corr_dim(rot):
        return d * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(theta))
    low = max(math.floor(corr_dim(beta_fast)), 0)
    high = min(math.ceil(corr_dim(beta_slow)), d - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(d // 2) - low) / (high - low), 0.0, 1.0)
    mask = 1.0 - ramp                   # 1: keep the original frequency
    return (inter * (1 - mask) + extra * mask).astype(np.float32)


def _rope(x, pos, sz):
    """Rotate interleaved pairs (0, 1), (2, 3), ... of the last axis of
    x (S, ..., d) by YaRN's angles."""
    d = x.shape[-1]
    yarn = sz["yarn"]
    ang = pos[:, None].astype(F32) * jnp.asarray(
        yarn_inv_freq(d, sz["theta"], yarn))
    m = _yarn_mscale(yarn[0], yarn[4]) / _yarn_mscale(yarn[0], yarn[5])
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (d // 2,)
    cos, sin = (jnp.cos(ang) * m).reshape(shape), (jnp.sin(ang) * m).reshape(
        shape)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     -1).reshape(x.shape)


def softmax_scale(sz) -> float:
    yarn = sz["yarn"]
    m = _yarn_mscale(yarn[0], yarn[5]) if yarn[5] else 1.0
    return (sz["d_nope"] + sz["d_rope"]) ** -0.5 * m * m


def _attention(q, k, v, scale, mm):
    """Causal softmax attention of one sequence, a block of queries at a
    time.  q, k (S, H, dq); v (S, H, dv)."""
    S = q.shape[0]
    c = min(Q_BLOCK, S)
    qb = q.reshape(S // c, c, *q.shape[1:])

    def block(_, xs):
        i, qc = xs
        s = mm("qhd,thd->hqt", qc, k) * scale
        keep = jnp.arange(S)[None, :] <= (i * c + jnp.arange(c))[:, None]
        p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
        return None, mm("hqt,thd->qhd", p, v)

    _, o = jax.lax.scan(jax.checkpoint(block), None,
                        (jnp.arange(S // c), qb))
    return o.reshape(S, *o.shape[2:])


def _mla(x, w, sz, mm):
    """MLA over one sequence x (S, d)."""
    S = x.shape[0]
    pos = jnp.arange(S)
    dn = sz["d_nope"]
    q = mm("sd,dhk->shk", x, w["wq"])
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], pos, sz)], -1)
    c = _rms(mm("sd,dc->sc", x, w["wkv_a"]), w["kv_norm"]["scale"],
             sz["eps"])
    k_pe = _rope(mm("sd,dr->sr", x, w["wk_rope"]), pos, sz)
    kv = mm("sc,chk->shk", c, w["wkv_b"])
    H = kv.shape[1]
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(
        k_pe[:, None, :], (S, H, sz["d_rope"]))], -1)
    o = _attention(q, k, kv[..., dn:], softmax_scale(sz), mm)
    return mm("shv,hvd->sd", o, w["wo"])


def _swiglu(x, w, mm):
    return mm("sf,fd->sd", jax.nn.silu(mm("sd,df->sf", x, w["w_gate"]))
              * mm("sd,df->sf", x, w["w_up"]), w["w_down"])


def _moe(x, w, sz, mm):
    """The MoE FFN over one sequence x (S, d): (output, balance loss)."""
    probs = jax.nn.softmax(mm("sd,de->se", x, w["router"]), axis=-1)
    top_w, top_e = jax.lax.top_k(probs, sz["top_k"])
    top_w = top_w * sz["rsf"]
    E = sz["E"]
    picks = jax.nn.one_hot(top_e, E, dtype=F32)              # (S, k, E)
    ce = jnp.sum(picks, (0, 1)) / (x.shape[0] * sz["top_k"] / E)
    aux = jnp.sum(ce * jnp.mean(probs, 0))
    gates = jnp.einsum("ske,sk->se", picks, top_w)            # exact
    held = gates[:, :sz["E_held"]]

    def expert(acc, ew):
        g, w1, w3, w2 = ew
        h = jax.nn.silu(mm("sd,df->sf", x, w1)) * mm("sd,df->sf", x, w3)
        return acc + g[:, None] * mm("sf,fd->sd", h, w2), None

    routed, _ = jax.lax.scan(jax.checkpoint(expert), jnp.zeros_like(x),
                             (held.T, w["w1"], w["w3"], w["w2"]))
    return routed + _swiglu(x, w["shared"], mm), aux


def _trunk(params, x, sz, mm):
    """The layers and the final norm over one sequence x (S, d); returns
    (hidden states, summed balance loss)."""
    def block(moe):
        def body(carry, lp):
            h, aux = carry
            w = jax.tree.map(lambda a: a.astype(F32), lp)
            h = h + _mla(_rms(h, w["ln1"]["scale"], sz["eps"]), w["attn"],
                         sz, mm)
            hn = _rms(h, w["ln2"]["scale"], sz["eps"])
            if moe:
                f, a = _moe(hn, w["moe"], sz, mm)
                aux = aux + a
            else:
                f = _swiglu(hn, w["ffn"], mm)
            return (h + f, aux), None
        return jax.checkpoint(body)

    carry = (x, jnp.zeros((), F32))
    carry, _ = jax.lax.scan(block(False), carry, params["pre_layers"])
    (x, aux), _ = jax.lax.scan(block(True), carry, params["layers"])
    return _rms(x, params["ln_f"]["scale"], sz["eps"]), aux


def _loss(params, tokens, labels, sz, mm):
    """One sequence's loss, as the configuration defines it."""
    x = params["embed"]["tok"].astype(F32)[tokens]
    x, aux = _trunk(params, x, sz, mm)
    S, d = x.shape
    c = min(LOGITS_BLOCK, S)

    def body(acc, xl):
        xc, lc = xl
        logits = mm("sd,vd->sv", xc, params["embed"]["unembed"])
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lc[:, None], -1)[:, 0]
        return (acc[0] + jnp.sum(logz - gold), acc[1] + jnp.sum(logz ** 2)), None

    (nll, z2), _ = jax.lax.scan(jax.checkpoint(body),
                                (jnp.zeros((), F32), jnp.zeros((), F32)),
                                (x.reshape(S // c, c, d),
                                 labels.reshape(S // c, c)))
    return nll / S + 1e-4 * z2 / S + sz["alpha"] * aux


@partial(jax.jit, static_argnames=("sz_items", "prec"))
def _grad(params, tokens, labels, sz_items, prec):
    """Mean loss and mean gradient over the step's sequences."""
    sz = dict(sz_items)
    mm = _mm(prec)

    def body(acc, seq):
        loss, g = jax.value_and_grad(_loss)(params, *seq, sz, mm)
        return (acc[0] + loss, jax.tree.map(jnp.add, acc[1], g)), None

    zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, F32), params)
    (loss, grads), _ = jax.lax.scan(body, (jnp.zeros((), F32), zeros),
                                    (tokens, labels))
    n = tokens.shape[0]
    return loss / n, jax.tree.map(lambda g: g / n, grads)


ADAM = {"lr": 3e-4, "b1": 0.9, "b2": 0.95, "eps": 1e-8, "wd": 0.1,
        "warmup": 10, "total": 10_000, "min_lr_frac": 0.1, "clip": 1.0}


def _lr(step):
    a = ADAM
    warm = min(step / a["warmup"], 1.0)
    t = min(max((step - a["warmup"]) / (a["total"] - a["warmup"]), 0.0), 1.0)
    return a["lr"] * warm * (a["min_lr_frac"] + (1 - a["min_lr_frac"])
                             * 0.5 * (1 + math.cos(math.pi * t)))


@partial(jax.jit, donate_argnums=(0, 1, 2, 3))
def _adam(params, m, v, grads, step, lr, dtypes):
    a = ADAM
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, a["clip"] / (gnorm + 1e-9))
    c1 = 1 - a["b1"] ** step
    c2 = 1 - a["b2"] ** step

    def upd(g, m, v, p, dt):
        g = g * scale
        m2 = a["b1"] * m + (1 - a["b1"]) * g
        v2 = a["b2"] * v + (1 - a["b2"]) * g * g
        u = (m2 / c1) / (jnp.sqrt(v2 / c2) + a["eps"]) + a["wd"] * p
        return (p - lr * u).astype(dt.dtype), m2, v2

    out = jax.tree.map(upd, grads, m, v, params, dtypes)
    is3 = lambda t: isinstance(t, tuple) and len(t) == 3  # noqa: E731
    pick = lambda i: jax.tree.map(lambda t: t[i], out, is_leaf=is3)  # noqa
    return pick(0), pick(1), pick(2), jax.tree.map(lambda g: g * scale, grads)


def train(seed: int, n_steps: int, batch: int, text_len: int,
          microbatches: int, sz: dict, prec: str = "f32",
          half_batch: bool = False) -> Dict:
    """Run ``n_steps`` AdamW steps from the seed's weights on the seed's
    batches.  Returns each step's loss, the first step's clipped gradient
    as the optimizer applies it (on the host), and the weights after the
    last step.  The weights are rounded to their stored type (bfloat16;
    the router float32) after every update and held in float32.  The
    gradient is the same mean for any ``microbatches``.  ``half_batch``
    plants a fault: the first half of each batch stands in for the
    whole, so the mean is taken over it alone."""
    del microbatches
    items = _items(sz)
    stored = init_weights(seed, sz)
    dtypes = jax.tree.map(lambda p: jnp.zeros((), p.dtype), stored)
    params = jax.tree.map(lambda p: p.astype(F32), stored)
    del stored
    # the moments wait on the host while the gradient is taken: the
    # device holds the weights, the gradient and the activations
    m = jax.tree.map(lambda p: np.zeros(p.shape, np.float32), params)
    v = jax.tree.map(lambda p: np.zeros(p.shape, np.float32), params)
    losses, first_grad = [], None
    with jax.default_matmul_precision("highest"):
        for step in range(n_steps):
            tokens, labels = train_batch(seed, step, batch, text_len, sz)
            if half_batch:            # a fault: the second half left out
                tokens, labels = (np.concatenate([a[:batch // 2]] * 2)
                                  for a in (tokens, labels))
            loss, grads = _grad(params, tokens, labels, items, prec)
            losses.append(float(loss))
            stored, m, v, clipped = _adam(params, jax.device_put(m),
                                          jax.device_put(v), grads,
                                          jnp.float32(step + 1),
                                          jnp.float32(_lr(step + 1)), dtypes)
            m, v = jax.device_get((m, v))
            # the update leaves the program in its stored type (a convert
            # pair inside one program may keep excess precision); widen
            # it for the next step
            params = jax.tree.map(lambda p: p.astype(F32), stored)
            del stored
            if first_grad is None:
                first_grad = jax.tree.map(np.asarray, clipped)
            del clipped
    del m, v
    return {"losses": losses, "first_grad": first_grad, "params": params}


@partial(jax.jit, static_argnames=("sz_items", "prec", "n_out"))
def _serve_logits(params, tokens, start, sz_items, prec, n_out):
    sz = dict(sz_items)
    mm = _mm(prec)
    x = params["embed"]["tok"].astype(F32)[tokens]
    x, _ = _trunk(params, x, sz, mm)
    rows = jax.lax.dynamic_slice_in_dim(x, start, n_out, axis=0)
    return mm("sd,vd->sv", rows, params["embed"]["unembed"])[:, :sz["V"]]


def serve_logits(params, prompt: np.ndarray, tokens_out, pad_to: int,
                 sz: dict, prec: str = "f32") -> np.ndarray:
    """Teacher-forced logits (n, V) that predict each served token: the
    prompt followed by the served tokens, padded at the end to ``pad_to``
    (causal, so the pad changes nothing before it), or past it to a
    whole number of query blocks."""
    seq = np.concatenate([prompt, np.asarray(tokens_out[:-1], np.int32)])
    if pad_to > Q_BLOCK:
        pad_to = -(-pad_to // Q_BLOCK) * Q_BLOCK
    toks = np.zeros((pad_to,), np.int32)
    toks[:len(seq)] = seq
    with jax.default_matmul_precision("highest"):
        out = _serve_logits(params, toks, len(prompt) - 1, _items(sz), prec,
                            len(tokens_out))
    return np.asarray(out, np.float32)
