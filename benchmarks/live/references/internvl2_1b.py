"""Plain reference of the internvl2-1b payload, as the configuration runs it.

Straightforward ``jax.numpy`` in float32 with every matrix product at
``Precision.HIGHEST``: no kernels, no cache, no batching tricks.  It
imports nothing of the program under test.  Weights and training batches
are drawn from the seed by the same recipe the configuration states
(``jax.random.normal`` per tensor, scaled, stored in bfloat16), so the
reference starts from the same numbers without taking any from the
program.

Departures from the published model (InternVL2-1B, arXiv:2404.16821) are
the program's and are listed in the configuration file: no q/k/v bias, a
separate output matrix padded to ``unembed_rows``, interleaved rotary
pairs, RMS norm eps 1e-5, and a stub vision tower (patch embeddings from
the seed, one projection).

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


def sizes(cfg: dict) -> dict:
    """The reference's sizes from a configuration file's keys."""
    return {"L": cfg["num_hidden_layers"], "d": cfg["hidden_size"],
            "H": cfg["num_attention_heads"], "K": cfg["num_key_value_heads"],
            "Dh": cfg["head_dim"], "F": cfg["intermediate_size"],
            "V": cfg["vocab_size"], "Vp": cfg["unembed_rows"],
            "P": cfg["num_image_token"], "theta": cfg["rope_theta"],
            "eps": cfg["rms_norm_eps"]}


# --------------------------------------------------------------- weights
def _normal(key, shape, scale):
    return (jax.random.normal(key, shape, F32) * scale).astype(BF16)


def _layer(key, sz):
    d, H, K, Dh, F = sz["d"], sz["H"], sz["K"], sz["Dh"], sz["F"]
    k1, _, k3, _ = jax.random.split(key, 4)
    a = jax.random.split(k1, 4)
    f = jax.random.split(k3, 3)
    s = d ** -0.5
    return {"attn": {"wq": _normal(a[0], (d, H, Dh), s),
                     "wk": _normal(a[1], (d, K, Dh), s),
                     "wv": _normal(a[2], (d, K, Dh), s),
                     "wo": _normal(a[3], (H, Dh, d), (H * Dh) ** -0.5)},
            "ffn": {"w_gate": _normal(f[0], (d, F), s),
                    "w_up": _normal(f[1], (d, F), s),
                    "w_down": _normal(f[2], (F, d), F ** -0.5)},
            "ln1": {"scale": jnp.ones((d,), BF16)},
            "ln2": {"scale": jnp.ones((d,), BF16)}}


@partial(jax.jit, static_argnames=("sz_items",))
def _init(key, sz_items):
    sz = dict(sz_items)
    d = sz["d"]
    keys = jax.random.split(key, 8)
    e1, e2 = jax.random.split(keys[0])
    layers = jax.vmap(lambda k: _layer(k, sz))(
        jax.random.split(keys[2], sz["L"]))
    return {"embed": {"tok": _normal(e1, (sz["Vp"], d), 0.02),
                      "unembed": _normal(e2, (sz["Vp"], d), d ** -0.5)},
            "ln_f": {"scale": jnp.ones((d,), BF16)},
            "layers": layers,
            "patch_proj": _normal(keys[3], (d, d), d ** -0.5)}


def init_weights(seed: int, sz: dict):
    """bfloat16 weights of the whole model from ``PRNGKey(seed)``, drawn in
    one compiled program (eager draws round differently on the TPU)."""
    return _init(jax.random.PRNGKey(seed), tuple(sorted(sz.items())))


def train_batch(seed: int, step: int, batch: int, text_len: int, sz: dict):
    """Step ``step``'s training batch: a drifting token walk and patch
    embeddings, numpy-generated from (seed, step)."""
    rng = np.random.default_rng((seed * 1_000_003 + step) % (2 ** 63))
    base = rng.integers(0, sz["V"], size=(batch, 1), dtype=np.int64)
    drift = rng.integers(-32, 33, size=(batch, text_len + 1), dtype=np.int64)
    toks = np.abs(base + np.cumsum(drift, axis=1)) % sz["V"]
    patches = rng.standard_normal((batch, sz["P"], sz["d"])) * 0.02
    return (toks[:, :-1].astype(np.int32), toks[:, 1:].astype(np.int32),
            patches.astype(np.float32))


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


def _rope(x, pos, theta):
    """Rotate interleaved pairs (0, 1), (2, 3), ... of the head dims."""
    d = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    ang = pos[:, None].astype(F32) * freqs              # (S, d/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     -1).reshape(x.shape)


def _block(x, lp, sz, mm):
    """One decoder layer over (B, S, d) float32 with causal attention."""
    B, S, _ = x.shape
    H, K, Dh = sz["H"], sz["K"], sz["Dh"]
    G = H // K
    w = jax.tree.map(lambda a: a.astype(F32), lp)
    h = _rms(x, w["ln1"]["scale"], sz["eps"])
    pos = jnp.arange(S)
    q = _rope(mm("bsd,dhk->bshk", h, w["attn"]["wq"]), pos, sz["theta"])
    k = _rope(mm("bsd,dhk->bshk", h, w["attn"]["wk"]), pos, sz["theta"])
    v = mm("bsd,dhk->bshk", h, w["attn"]["wv"])
    q = q.reshape(B, S, K, G, Dh)
    s = mm("bqkgd,btkd->bkgqt", q, k) / math.sqrt(Dh)
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = mm("bkgqt,btkd->bqkgd", p, v).reshape(B, S, H, Dh)
    x = x + mm("bshk,hkd->bsd", o, w["attn"]["wo"])
    h = _rms(x, w["ln2"]["scale"], sz["eps"])
    f = jax.nn.silu(mm("bsd,df->bsf", h, w["ffn"]["w_gate"])) \
        * mm("bsd,df->bsf", h, w["ffn"]["w_up"])
    return x + mm("bsf,fd->bsd", f, w["ffn"]["w_down"])


def _trunk(params, x, sz, mm):
    def body(h, lp):
        return _block(h, lp, sz, mm), None
    x, _ = jax.lax.scan(jax.checkpoint(body), x, params["layers"])
    return _rms(x, params["ln_f"]["scale"], sz["eps"])


def _loss(params, tokens, labels, patches, sz, mm, chunk=512):
    """Next-token loss of one microbatch, as the configuration defines it:
    mean NLL over all unembed rows plus 1e-4 of the mean squared log Z.
    The logits are made ``chunk`` positions at a time, so that they fit."""
    tok = params["embed"]["tok"].astype(F32)
    x = tok[tokens]
    pe = mm("bpd,de->bpe", patches, params["patch_proj"])
    x = jnp.concatenate([pe, x], axis=1)
    x = _trunk(params, x, sz, mm)[:, patches.shape[1]:]
    B, S, d = x.shape
    c = min(chunk, S)
    xs = x.reshape(B, S // c, c, d).swapaxes(0, 1)
    ls = labels.reshape(B, S // c, c).swapaxes(0, 1)

    def body(acc, xl):
        xc, lc = xl
        logits = mm("bsd,vd->bsv", xc, params["embed"]["unembed"])
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lc[..., None], -1)[..., 0]
        return (acc[0] + jnp.sum(logz - gold), acc[1] + jnp.sum(logz ** 2)), None

    (nll, z2), _ = jax.lax.scan(jax.checkpoint(body),
                                (jnp.zeros((), F32), jnp.zeros((), F32)),
                                (xs, ls))
    n = B * S
    return nll / n + 1e-4 * z2 / n


@partial(jax.jit, static_argnames=("sz_items", "prec", "microbatches"))
def _grad(params, tokens, labels, patches, sz_items, prec, microbatches):
    """Mean loss and mean gradient over the microbatches of one step."""
    sz = dict(sz_items)
    mm = _mm(prec)
    split = lambda a: a.reshape(microbatches, -1, *a.shape[1:])  # noqa: E731

    def body(acc, mb):
        loss, g = jax.value_and_grad(_loss)(params, *mb, sz, mm)
        return (acc[0] + loss, jax.tree.map(jnp.add, acc[1], g)), None

    zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, F32), params)
    (loss, grads), _ = jax.lax.scan(
        body, (jnp.zeros((), F32), zeros),
        (split(tokens), split(labels), split(patches)))
    return loss / microbatches, jax.tree.map(lambda g: g / microbatches,
                                             grads)


ADAM = {"lr": 3e-4, "b1": 0.9, "b2": 0.95, "eps": 1e-8, "wd": 0.1,
        "warmup": 10, "total": 10_000, "min_lr_frac": 0.1, "clip": 1.0}


def _lr(step):
    a = ADAM
    warm = min(step / a["warmup"], 1.0)
    t = min(max((step - a["warmup"]) / (a["total"] - a["warmup"]), 0.0), 1.0)
    return a["lr"] * warm * (a["min_lr_frac"] + (1 - a["min_lr_frac"])
                             * 0.5 * (1 + math.cos(math.pi * t)))


@partial(jax.jit, donate_argnums=(0, 1, 2, 3))
def _adam(params, m, v, grads, step, lr):
    a = ADAM
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, a["clip"] / (gnorm + 1e-9))
    c1 = 1 - a["b1"] ** step
    c2 = 1 - a["b2"] ** step

    def upd(g, m, v, p):
        g = g * scale
        m2 = a["b1"] * m + (1 - a["b1"]) * g
        v2 = a["b2"] * v + (1 - a["b2"]) * g * g
        u = (m2 / c1) / (jnp.sqrt(v2 / c2) + a["eps"]) + a["wd"] * p
        return (p - lr * u).astype(BF16), m2, v2

    out = jax.tree.map(upd, grads, m, v, params)
    is3 = lambda t: isinstance(t, tuple) and len(t) == 3  # noqa: E731
    pick = lambda i: jax.tree.map(lambda t: t[i], out, is_leaf=is3)  # noqa
    return pick(0), pick(1), pick(2), jax.tree.map(lambda g: g * scale, grads)


def train(seed: int, n_steps: int, batch: int, text_len: int,
          microbatches: int, sz: dict, prec: str = "f32",
          half_batch: bool = False) -> Dict:
    """Run ``n_steps`` AdamW steps from the seed's weights on the seed's
    batches.  Returns each step's loss, the first step's clipped gradient
    as the optimizer applies it (on the host), and the weights after the
    last step.  The weights are rounded
    to bfloat16 after every update, as the configuration stores them, and
    held in float32.  ``half_batch`` plants a fault: the first half of
    each batch stands in for the whole, so the mean is taken over it
    alone."""
    items = tuple(sorted(sz.items()))
    params = jax.tree.map(lambda p: p.astype(F32), init_weights(seed, sz))
    m = jax.tree.map(lambda p: jnp.zeros(p.shape, F32), params)
    v = jax.tree.map(lambda p: jnp.zeros(p.shape, F32), params)
    losses, first_grad = [], None
    for step in range(n_steps):
        tokens, labels, patches = train_batch(seed, step, batch, text_len, sz)
        if half_batch:            # a fault: the second half left out
            tokens, labels, patches = (
                np.concatenate([a[:batch // 2]] * 2)
                for a in (tokens, labels, patches))
        loss, grads = _grad(params, tokens, labels, patches, items, prec,
                            microbatches)
        losses.append(float(loss))
        stored, m, v, clipped = _adam(params, m, v, grads,
                                      jnp.float32(step + 1),
                                      jnp.float32(_lr(step + 1)))
        # the update leaves the program as bfloat16 (a convert pair inside
        # one program may keep excess precision); widen it for the next step
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
    x = _trunk(params, x, sz, mm)
    rows = jax.lax.dynamic_slice_in_dim(x, start, n_out, axis=1)
    return mm("bsd,vd->bsv", rows, params["embed"]["unembed"])[..., :sz["V"]]


def serve_logits(params, prompt: np.ndarray, tokens_out, pad_to: int,
                 sz: dict, prec: str = "f32") -> np.ndarray:
    """Teacher-forced logits (n, V) that predict each served token: the
    prompt followed by the served tokens, padded at the end to ``pad_to``
    (causal, so the pad changes nothing before it)."""
    seq = np.concatenate([prompt, np.asarray(tokens_out[:-1], np.int32)])
    toks = np.zeros((1, pad_to), np.int32)
    toks[0, :len(seq)] = seq
    out = _serve_logits(params, toks, len(prompt) - 1,
                        tuple(sorted(sz.items())), prec, len(tokens_out))
    return np.asarray(out[0], np.float32)
