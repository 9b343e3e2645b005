"""The deepseek-v2-lite configuration against its plain reference
(``references/deepseek_v2_lite.py``) on the CPU at a small size: the
configuration file against the program's ModelConfig, the training loss
and first gradients, prefill then absorbed decode through the normalised
latent cache, the expert shares against the uncut layer, the CPU
rehearsal of the cell with its control, and the cell's readers."""
import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

LIVE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(LIVE))

from livebench import bench, moe_flops, stack  # noqa: E402
from livebench_rehearsal import control_fails, rehearse  # noqa: E402

CONFIG = "live1.deepseek_v2_lite"
CELL = CONFIG + ".train_steady"
SEED = 3_000_000_123


@pytest.fixture(scope="module")
def files():
    b = bench.benchmark()
    cfg = bench.load_config(b, CONFIG)
    ref = bench.load_module(LIVE / "references" / "deepseek_v2_lite.py",
                            "reference_deepseek_v2_lite")
    return b, cfg, ref


def _small(files, **kw):
    """The rehearsal's sizes (2 layers, d_model 64, 4 heads, vocab 512;
    every MLA and MoE width as published), computing in float32."""
    _, cfg, ref = files
    c = stack.program_config(cfg, rehearse=True).with_(
        compute_dtype="float32", **kw)
    return c, stack.rehearsal_sizes(ref.sizes(cfg)), ref


def test_the_file_states_every_key_of_the_program(files):
    """program_config compares the dense keys; this compares the MLA,
    MoE and YaRN ones too."""
    b, cfg, _ = files
    c = stack.program_config(cfg, rehearse=False)
    m, e, y = c.mla, c.moe, c.rope_scaling
    stated = {
        "kv_lora_rank": m.kv_lora, "q_lora_rank": m.q_lora,
        "qk_nope_head_dim": m.d_nope, "qk_rope_head_dim": m.d_rope,
        "v_head_dim": m.d_v, "router_experts": e.n_experts,
        "n_routed_experts": e.held,
        "num_experts_per_tok": e.top_k, "moe_intermediate_size": e.d_expert,
        "n_shared_experts": e.n_shared, "first_k_dense_replace": e.first_dense,
        "norm_topk_prob": e.norm_topk_prob, "seq_aux": e.seq_aux,
        "aux_loss_alpha": e.aux_alpha, "routed_scaling_factor": 1,
        "scoring_func": "softmax", "topk_method": "greedy",
        "hidden_act": "silu", "n_group": 1, "topk_group": 1,
        "moe_layer_freq": 1, "attention_bias": False}
    for k, v in stated.items():
        assert cfg[k] == v, k
    rs = cfg["rope_scaling"]
    assert rs["type"] == "yarn"
    assert (rs["factor"], rs["original_max_position_embeddings"],
            rs["beta_fast"], rs["beta_slow"], rs["mscale"],
            rs["mscale_all_dim"]) == (y.factor, y.original_max_position,
                                      y.beta_fast, y.beta_slow, y.mscale,
                                      y.mscale_all_dim)
    assert c.train_microbatches == cfg["trainer"]["microbatches"]
    (entry,) = [x for x in b["configs"] if x["name"] == CONFIG]
    assert set(entry["reduced"]) == set(cfg["reduced_why"]) == set(
        cfg["published"])
    assert cfg["published"] == {"num_hidden_layers": 27,
                                "n_routed_experts": 64, "vocab_size": 102400}
    assert e.held == 8 and e.n_experts == 64 and e.top_k == 6


def _init_program(cfg, seed):
    from repro.models import init_params
    return jax.jit(lambda k: init_params(k, cfg))(jax.random.PRNGKey(seed))


def _names(tree):
    return [jax.tree_util.keystr(p)
            for p, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


def test_training_loss_and_first_gradients(files):
    from repro.training import AdamW, make_train_state, make_train_step
    from repro.training import synthetic_batch
    cfg, sz, ref = _small(files)
    B, S = 2, 64
    params = _init_program(cfg, SEED)
    w = ref.init_weights(SEED, sz)
    assert _names(params) == _names(w)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(w)):
        assert a.dtype == b.dtype and bool(jnp.all(a == b))
    opt = AdamW(warmup=10, total_steps=10_000)
    step = jax.jit(make_train_step(cfg, opt, microbatches=2))
    state = make_train_state(params, opt)
    losses = []
    for i in range(2):
        state, metrics = step(state, synthetic_batch(cfg, B, S, seed=SEED,
                                                     step=i))
        losses.append(float(metrics["loss"]))
        if i == 0:
            grad = jax.tree.map(lambda m: np.asarray(m) / 0.1, state.opt.m)
            assert 0 < metrics["moe_rows_max"] <= metrics["moe_rows"]
    r = ref.train(SEED, 2, B, S, 2, sz)
    for a, b in zip(losses, r["losses"]):
        assert abs(a - b) / abs(b) < 1e-5
    # the program's gradient of a bfloat16 weight is rounded to bfloat16
    for a, b in zip(jax.tree.leaves(grad), jax.tree.leaves(r["first_grad"])):
        scale = float(np.abs(b).max()) + 1e-12
        assert float(np.abs(a - b).max()) < 8e-3 * scale
        assert abs(np.linalg.norm(a) - np.linalg.norm(b)) < 1e-3 * (
            np.linalg.norm(b) + 1e-12)


def test_prefill_then_absorbed_decode_matches_the_forward(files):
    """Prefill a prompt, then decode teacher-forced tokens through the
    normalised latent cache (the absorbed formulation): each step's
    logits equal the reference's full forward at that position."""
    from repro.models import decode_step, prefill
    cfg, sz, ref = _small(files)
    params = _init_program(cfg, SEED + 1)
    w = ref.init_weights(SEED + 1, sz)
    rng = np.random.default_rng(5)
    P, N = 48, 6
    toks = rng.integers(0, cfg.vocab, P + N).astype(np.int32)
    want = ref.serve_logits(w, toks[:P], toks[P:], P + N, sz)
    logits, cache = prefill(params, jnp.asarray(toks[None, :P]), cfg)
    cache = jax.tree.map(
        lambda c: jnp.pad(c, [(0, 0), (0, 0), (0, N), (0, 0)]), cache)
    got = [np.asarray(logits[0])]
    for i in range(N - 1):
        logits, cache = decode_step(params, cache,
                                    jnp.asarray(toks[None, P + i:P + i + 1]),
                                    P + i, cfg)
        got.append(np.asarray(logits[0]))
    got = np.stack(got)
    assert got.shape == want.shape
    assert float(np.abs(got - want).max()) < 2e-3 * float(np.abs(want).max())


def test_expert_shares_add_up_to_the_uncut_layer(files):
    """Eight chips' shares of the expert layer (experts 0-7, 8-15, ...,
    each share's router columns rolled so that its experts come first),
    the shared experts counted once, add up to the reference's layer
    with all 64 experts, and every assignment is computed once."""
    from repro.models import moe as moe_mod
    cfg, sz, ref = _small(files)
    full = dict(sz, E_held=64, L=2, dense=1)
    w = ref.init_weights(SEED, full)["layers"]
    w = jax.tree.map(lambda a: a[0], w)["moe"]
    x = jnp.asarray(np.random.default_rng(9).standard_normal((1, 40, 64)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        want, _ = ref._moe(x[0], jax.tree.map(lambda a: a.astype(jnp.float32),
                                              w), full, ref._mm("f32"))
        total, rows = jnp.zeros_like(x), 0.0
        assert cfg.moe.held == 8
        for j in range(8):
            p = {k: w[k][8 * j:8 * j + 8] for k in ("w1", "w3", "w2")}
            p["router"] = jnp.roll(w["router"], -8 * j, axis=1)
            p["shared"] = jax.tree.map(
                lambda a: a if j == 0 else jnp.zeros_like(a), w["shared"])
            y, stats = moe_mod.moe_fwd(p, x, cfg)
            total, rows = total + y, rows + float(stats[1])
    assert float(jnp.abs(total[0] - want).max()) < 1e-4 * float(
        jnp.abs(want).max())
    assert rows == 40 * cfg.moe.top_k


def test_rehearsal_is_correct_and_its_control_is_not():
    out = rehearse(CELL, control=True)
    assert out["correct"], out["checks"]
    assert set(out["checks"]) == {"decisions", "init", "loss", "grad",
                                  "update"}
    assert control_fails(out, CONFIG), out["control"]


# ------------------------------------------------------------- readers
def _run(files, rows=(12_000.0, 12_400.0)):
    b, cfg, ref = files
    run = stack.Run(cell=CELL, cfg=cfg, mix={}, sz=ref.sizes(cfg),
                    seconds=40.0, seed=1, chips=1, rehearse=False,
                    trainer=dict(cfg["trainer"]),
                    peak={"bf16_flops_per_s": 197e12,
                          "hbm_bytes_per_s": 819e9})
    run.t0, run.t1 = 100.0, 140.0
    run.train.steps = [stack.Step(100.0 + i, 101.0 + i, 1.0, i + 1, i == 0)
                       for i in range(5)]
    return run


@pytest.fixture
def rec(monkeypatch):
    from repro import telemetry
    r = telemetry.Recorder()
    monkeypatch.setattr(telemetry, "rows", r.rows)
    monkeypatch.setattr(telemetry, "dropped_since", r.dropped_since)
    return r


def test_moe_readers(files, rec):
    run = _run(files)
    rec.record("moe.rows", 99.0, 100.0, n=1.0)          # before the window
    for i, n in enumerate((120_000.0, 124_000.0)):
        rec.record("moe.rows", 101.0 + i, 102.0 + i, n=n)
    sz, calls_a_step = run.sz, 5 * 2
    f, by = moe_flops.gmm_call_cost(122_000.0 / calls_a_step, sz)
    ideal = max(f / 197e12, by / 819e9)
    run.trace = {"op_totals": {"moe_gmm.3": (90 * ideal * 2, 90.0),
                               "moe_tgmm.1": (30 * ideal * 2, 30.0),
                               "flash_attention_fwd.2": (1.0, 24.0)}}
    assert abs(bench.read_metric("moe_gmm_roofline", run) - 50.0) < 1e-9
    mfu = bench.read_metric("moe_train_mfu", run)
    want = 100.0 * moe_flops.train_step_flops(4, 8192, 122_000.0, sz) \
        / 197e12
    assert abs(mfu - want) < 1e-9 and 0 < mfu < 100
    assert bench.read_metric("mla_attention_roofline", run) > 0


def test_moe_readers_find_nothing_without_the_rows(files, rec):
    """A program that records no moe.rows (the parent's) gives no value,
    and raises nothing."""
    run = _run(files)
    run.trace = {"op_totals": {}}
    assert bench.read_metric("moe_gmm_roofline", run) is None
    assert bench.read_metric("moe_train_mfu", run) is None
    assert bench.read_metric("mla_attention_roofline", run) is None


def test_operation_counts_per_token():
    """The forward's operations per token at the cut, part by part:
    MLA projections 166 MFLOP and causal attention at 8k 252, the MoE
    layers' experts and routers 240 at the mean load (0.75 held rows a
    token), the dense layer 134, the head 52."""
    sz = {"L": 6, "dense": 1, "d": 2048, "H": 16, "d_nope": 128,
          "d_rope": 64, "d_v": 128, "kv_lora": 512, "E": 64, "E_held": 8,
          "n_shared": 2, "Fe": 1408, "F": 10944, "Vp": 12800}
    S = 8192
    per_token = moe_flops.train_step_flops(1, S, 0.75 * 5 * S, sz) / 3 / S
    attn = moe_flops.attention_fwd_flops(1, S, sz) / S
    assert math.isclose(attn / 1e6, 252, rel_tol=0.01)
    assert math.isclose(per_token / 1e6, 166 + 252 + 240 + 134 + 52,
                        rel_tol=0.01)
