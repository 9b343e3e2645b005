"""The dropless expert layer, its grouped-product kernels and YaRN rotary
scaling, on the CPU: the Pallas kernels interpreted against
``jax.lax.ragged_dot``, the layer against a dense sum over experts, and
the YaRN table against its closed form."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import telemetry
from repro.configs import get_config
from repro.kernels import moe_gmm, ops
from repro.models import moe as moe_mod
from repro.models.config import ModelConfig, MoEConfig
from repro.models.layers import rope_freqs, softmax_scale

rng = np.random.default_rng(7)
TM = 16


def _grouped(counts, K, dt=jnp.float32):
    """Rows grouped by ``counts``, each group from a tile of TM rows, and
    a few tiles past the live ones."""
    counts = np.asarray(counts)
    tiles = -(-counts // TM)
    M = (tiles.sum() + 3) * TM
    lhs = np.zeros((M, K), np.float32)
    start = 0
    for c, t in zip(counts, tiles):
        lhs[start:start + c] = rng.standard_normal((c, K))
        start += t * TM
    return jnp.asarray(lhs, dt), int(tiles.sum()) * TM


@pytest.mark.parametrize("counts", [[20, 0, 35], [16, 16, 16], [0, 0, 5]])
def test_gmm_matches_ragged_dot(counts):
    K, N = 128, 256
    lhs, live = _grouped(counts, K)
    rhs = jnp.asarray(rng.standard_normal((len(counts), K, N)), jnp.float32)
    c = jnp.asarray(counts, jnp.int32)
    sizes = (-(-c // TM) * TM).astype(jnp.int32)

    def f(l, r):
        return moe_gmm.grouped_matmul(l, r, c, TM, True)[:live]

    def g(l, r):
        return jax.lax.ragged_dot(l, r, sizes)[:live]
    assert float(jnp.abs(f(lhs, rhs) - g(lhs, rhs)).max()) < 1e-4
    ct = jnp.asarray(rng.standard_normal((live, N)), jnp.float32)
    ga = jax.grad(lambda l, r: jnp.sum(f(l, r) * ct), (0, 1))(lhs, rhs)
    gb = jax.grad(lambda l, r: jnp.sum(g(l, r) * ct), (0, 1))(lhs, rhs)
    assert float(jnp.abs(ga[0][:live] - gb[0][:live]).max()) < 1e-4
    # an expert with no rows takes a zero gradient
    assert float(jnp.abs(ga[1] - gb[1]).max()) < 1e-3


def test_tgmm_splits_wide_results():
    """A weight gradient wider than one accumulator block is built from
    several (row block, column block) results."""
    counts = jnp.asarray([40, 24], jnp.int32)
    lhs, live = _grouped([40, 24], 1024)
    dout = jnp.asarray(rng.standard_normal((lhs.shape[0], 2048)), jnp.float32)
    out = moe_gmm.tgmm(lhs, dout, counts, tm=TM, n_groups=2, interpret=True)
    starts = [0, 48]
    for e, (s0, c) in enumerate(zip(starts, [40, 24])):
        want = lhs[s0:s0 + c].T @ dout[s0:s0 + c]
        assert float(jnp.abs(out[e] - want).max()) < 1e-3


def _cfg(n_held=0, top_k=2, seq_aux=False):
    return ModelConfig(
        name="t", family="moe", n_layers=2, d_model=32, n_heads=2, n_kv=2,
        d_ff=64, vocab=64, param_dtype="float32", compute_dtype="float32",
        moe=MoEConfig(n_experts=8, top_k=top_k, d_expert=16, n_shared=1,
                      n_held=n_held,
                      norm_topk_prob=False, seq_aux=seq_aux))


def _dense_routed(x2d, top_w, top_e, p, e_start, n_held):
    """Every held expert over every token, weighted by its pick."""
    y = jnp.zeros_like(x2d)
    for e in range(n_held):
        w = jnp.sum(jnp.where(top_e == e_start + e, top_w, 0.0), -1)
        h = jax.nn.silu(x2d @ p["w1"][e]) * (x2d @ p["w3"][e])
        y = y + w[:, None] * (h @ p["w2"][e])
    return y


@pytest.mark.parametrize("backend", ["interpret", "jnp"])
def test_held_experts_match_a_dense_sum(backend):
    cfg = _cfg(n_held=4)
    p = moe_mod.init_moe(jax.random.PRNGKey(0), cfg)
    x = jnp.asarray(rng.standard_normal((24, 32)), jnp.float32)
    _, top_w, top_e = moe_mod.route(x, p["router"], cfg)
    prev = ops.set_backend(backend)
    try:
        y, counts = moe_mod.held_experts(
            x, top_w, top_e, p["w1"], p["w3"], p["w2"], 2, 4)
    finally:
        ops.set_backend(prev)
    want = _dense_routed(x, top_w, top_e, p, 2, 4)
    assert float(jnp.abs(y - want).max()) < 1e-4
    held = (top_e >= 2) & (top_e < 6)
    assert int(counts.sum()) == int(held.sum())


@pytest.mark.parametrize("T,k,held,tm", [
    (8, 8, 64, 16), (24, 2, 4, 16), (96, 2, 4, 32), (512, 2, 4, 256),
    (16384, 6, 8, 256)])
def test_group_tile_follows_the_rows(T, k, held, tm):
    """A decode step's few rows take 16-row tiles and a training step's
    many rows 256-row ones: the padding, under a tile a group, stays
    within the assignments."""
    assert ops.gmm_tile(T * min(k, held), held) == tm


def test_held_experts_on_wide_tiles_match_a_dense_sum():
    """512 tokens over 4 held experts group on 256-row tiles."""
    cfg = _cfg(n_held=4)
    p = moe_mod.init_moe(jax.random.PRNGKey(4), cfg)
    x = jnp.asarray(rng.standard_normal((512, 32)), jnp.float32)
    _, top_w, top_e = moe_mod.route(x, p["router"], cfg)
    prev = ops.set_backend("interpret")
    try:
        y, counts = moe_mod.held_experts(
            x, top_w, top_e, p["w1"], p["w3"], p["w2"], 0, 4)
    finally:
        ops.set_backend(prev)
    want = _dense_routed(x, top_w, top_e, p, 0, 4)
    assert float(jnp.abs(y - want).max()) < 1e-4
    assert int(counts.sum()) == int(jnp.sum(top_e < 4))


@pytest.mark.parametrize("backend", ["interpret", "jnp"])
def test_expert_layer_gradient_matches_a_dense_sum(backend):
    """The layer's gradients (router, held experts, shared expert and the
    input) equal those of the dense sum over experts: the dispatch's and
    the combine's transposes are gathers, rows past the live tiles are
    never read."""
    cfg = _cfg(n_held=4)
    p = moe_mod.init_moe(jax.random.PRNGKey(3), cfg)
    x = jnp.asarray(rng.standard_normal((1, 24, 32)), jnp.float32)
    ct = jnp.asarray(rng.standard_normal((1, 24, 32)), jnp.float32)

    def layer(p, x):
        return jnp.sum(moe_mod.moe_fwd(p, x, cfg)[0] * ct)

    def dense(p, x):
        _, top_w, top_e = moe_mod.route(x[0], p["router"], cfg)
        sh = p["shared"]
        shared = (jax.nn.silu(x[0] @ sh["w_gate"]) * (x[0] @ sh["w_up"])
                  ) @ sh["w_down"]
        return jnp.sum((_dense_routed(x[0], top_w, top_e, p, 0, 4) + shared)
                       * ct[0])
    prev = ops.set_backend(backend)
    try:
        got = jax.grad(layer, (0, 1))(p, x)
    finally:
        ops.set_backend(prev)
    want = jax.grad(dense, (0, 1))(p, x)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert float(jnp.abs(a - b).max()) <= 1e-5 * float(
            jnp.abs(b).max()) + 1e-7


def test_every_token_on_one_expert_drops_nothing():
    """A router that sends every token to expert 0: its group holds all
    T rows, past any capacity a GShard layer would give it, and every
    assignment is computed."""
    cfg = _cfg(n_held=4, top_k=2)
    p = moe_mod.init_moe(jax.random.PRNGKey(1), cfg)
    p["router"] = p["router"].at[:, 0].set(0.0).at[0, 0].set(50.0)
    T = 40
    x = jnp.asarray(np.abs(rng.standard_normal((1, T, 32))) + 1.0,
                    jnp.float32)
    prev = ops.set_backend("interpret")
    try:
        y, stats = moe_mod.moe_fwd(p, x, cfg)
    finally:
        ops.set_backend(prev)
    _, top_w, top_e = moe_mod.route(x[0], p["router"], cfg)
    assert bool(jnp.all(top_e[:, 0] == 0))
    rows, rows_max = (float(v) for v in stats[1:])
    assert rows_max == T
    assert rows == float(jnp.sum(top_e < 4))
    sh = p["shared"]
    shared = (jax.nn.silu(x[0] @ sh["w_gate"]) * (x[0] @ sh["w_up"])
              ) @ sh["w_down"]
    want = _dense_routed(x[0], top_w, top_e, p, 0, 4) + shared
    assert float(jnp.abs(y[0] - want).max()) < 1e-4


def test_grouped_product_counts_its_calls():
    before = telemetry.counters()
    cfg = _cfg(n_held=4)
    p = moe_mod.init_moe(jax.random.PRNGKey(2), cfg)
    x = jnp.asarray(rng.standard_normal((1, 8, 32)), jnp.float32)
    jax.grad(lambda p: jnp.sum(moe_mod.moe_fwd(p, x, cfg)[0]))(p)
    after = telemetry.counters()
    grew = {k: after.get(k, 0) - before.get(k, 0)
            for k in ("kernels.moe_gmm", "kernels.moe_tgmm")}
    assert grew["kernels.moe_gmm"] == 3           # x W1, x W3, h W2


@pytest.mark.parametrize("seq_aux", [False, True])
def test_balance_loss(seq_aux):
    """E * sum_e share_e * mean prob_e, per sequence with seq_aux: a
    uniform router with every expert picked alike gives 1."""
    cfg = _cfg(top_k=2, seq_aux=seq_aux)
    B, S, E = 2, 8, 8
    probs = jnp.full((B, S, E), 1.0 / E)
    top_e = jnp.asarray(np.arange(B * S * 2).reshape(B, S, 2) % E)
    assert abs(float(moe_mod.balance_loss(probs, top_e, cfg)) - 1.0) < 1e-6
    # all picks on expert 0 of sequence 0 only: per sequence, not pooled
    p2 = jax.nn.softmax(jnp.asarray(rng.standard_normal((B, S, E))), -1)
    t2 = top_e.at[0].set(0)
    picks = jax.nn.one_hot(t2, E).sum(-2)
    axes = (1,) if seq_aux else (0, 1)
    want = E * jnp.mean(jnp.sum(picks.mean(axes) / 2 * p2.mean(axes), -1))
    assert abs(float(moe_mod.balance_loss(p2, t2, cfg)) - float(want)) < 1e-6


def test_yarn_table_closed_form():
    """DeepSeek-V2-Lite's YaRN (factor 40 over 4,096 positions, beta 32
    and 1, 64 rotary dims): pairs 0-10 keep theta's frequency, pairs 23
    and on are divided by 40, and a linear ramp over 13 pairs blends the
    ones between; the softmax scale is 192 ** -0.5 times
    (0.1 x 0.707 x ln 40 + 1) ** 2."""
    y = get_config("deepseek_v2_lite").rope_scaling
    f = np.asarray(rope_freqs(64, 10000.0, y), np.float64)
    low = math.floor(64 * math.log(4096 / (32 * 2 * math.pi))
                     / (2 * math.log(10000)))
    high = math.ceil(64 * math.log(4096 / (1 * 2 * math.pi))
                     / (2 * math.log(10000)))
    assert (low, high) == (10, 23)
    for i in range(32):
        base = 10000.0 ** (-2 * i / 64)
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        want = base / 40 * ramp + base * (1 - ramp)
        assert abs(f[i] - want) <= 1e-6 * want, i
    m = 0.1 * 0.707 * math.log(40) + 1
    assert abs(softmax_scale(192, y) - 192 ** -0.5 * m * m) < 1e-12
    assert softmax_scale(192, None) == 192 ** -0.5
    np.testing.assert_array_equal(
        np.asarray(rope_freqs(64, 10000.0)),
        np.asarray(1.0 / 10000.0 ** (jnp.arange(0, 64, 2) / 64)))
    # factor 1 leaves every frequency as theta gives it
    np.testing.assert_allclose(
        np.asarray(rope_freqs(64, 10000.0, dataclasses.replace(y, factor=1.0))),
        np.asarray(rope_freqs(64, 10000.0)), rtol=1e-6)
