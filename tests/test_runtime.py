"""Integration tests: elastic runtime, serving, checkpoint/restart,
straggler detection, and a miniature multi-device dry-run.

Multi-device cases run in a subprocess so the 8-device XLA flag does not
leak into the rest of the suite (the main process stays single-device).
"""
import os
import subprocess
import sys
import tempfile

import jax
import numpy as np
import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_py(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=540)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


SMALL_CFG = """
from repro.models.config import ModelConfig
CFG = ModelConfig(name="t", family="dense", n_layers=2, d_model=64,
                  n_heads=4, n_kv=2, d_ff=128, vocab=256,
                  tie_embeddings=True, param_dtype="float32",
                  compute_dtype="float32", attn_block_q=32, attn_block_kv=32)
"""


def test_elastic_shrink_expand_preserves_training():
    """Resize must not corrupt the train state: loss keeps decreasing and
    params stay identical through a round-trip re-shard."""
    out = run_py(SMALL_CFG + """
import jax, jax.numpy as jnp, numpy as np
from repro.runtime import ElasticJob
devs = jax.devices()
job = ElasticJob(1, CFG, kind="malleable", batch=8, seq=32, seed=0)
job.start(devs[:4])
for _ in range(3): m = job.step()
before = jax.tree.map(lambda x: np.asarray(x), job.state.params)
job.resize(devs[:2])     # shrink
after = jax.tree.map(lambda x: np.asarray(x), job.state.params)
errs = [np.abs(a-b).max() for a,b in zip(jax.tree.leaves(before), jax.tree.leaves(after))]
print("reshard_err", max(errs))
m1 = job.step()
job.resize(devs[:6])     # expand
m2 = job.step()
print("loss_seq", m["loss"], m1["loss"], m2["loss"])
assert all(np.isfinite([m["loss"], m1["loss"], m2["loss"]]))
""")
    reshard_err = float(out.split("reshard_err")[1].split()[0])
    assert reshard_err == 0.0


def test_preempt_resume_from_checkpoint():
    out = run_py(SMALL_CFG + """
import jax, numpy as np, tempfile
from repro.runtime import ElasticJob
devs = jax.devices()
d = tempfile.mkdtemp()
job = ElasticJob(1, CFG, kind="malleable", batch=8, seq=32,
                 ckpt_dir=d, ckpt_every=100, seed=0)
job.start(devs[:4])
for _ in range(4): job.step()
params_at_preempt = [np.asarray(x) for x in jax.tree.leaves(job.state.params)]
job.preempt(warning=True)       # 2-minute-warning checkpoint
assert job.mesh is None
job2 = ElasticJob(1, CFG, kind="malleable", batch=8, seq=32,
                  ckpt_dir=d, seed=0)
job2.resume(devs[4:8])          # different nodes entirely
assert job2.step_idx == 4
restored = [np.asarray(x) for x in jax.tree.leaves(job2.state.params)]
err = max(np.abs(a-b).max() for a,b in zip(params_at_preempt, restored))
print("resume_err", err)
job2.step()
""")
    assert float(out.split("resume_err")[1].split()[0]) == 0.0


def test_deterministic_restart_same_stream():
    """Restart-from-checkpoint must replay the same data stream: training
    A->(10 steps) equals A->(5 steps)->ckpt->restore->(5 steps)."""
    out = run_py(SMALL_CFG + """
import jax, numpy as np, tempfile
from repro.models import init_params
from repro.training import AdamW, make_train_state, make_train_step, \
    synthetic_batch, checkpoint
opt = AdamW(lr=1e-3, warmup=2, total_steps=20)
step = jax.jit(make_train_step(CFG, opt))
def train(state, a, b):
    for i in range(a, b):
        state, _ = step(state, synthetic_batch(CFG, 4, 32, seed=7, step=i))
    return state
s0 = make_train_state(init_params(jax.random.PRNGKey(0), CFG), opt)
sA = train(s0, 0, 10)
s0 = make_train_state(init_params(jax.random.PRNGKey(0), CFG), opt)
sB = train(s0, 0, 5)
d = tempfile.mkdtemp()
checkpoint.save(d, 5, sB)
sB = checkpoint.restore(d, sB)
sB = train(sB, 5, 10)
err = max(np.abs(np.asarray(a, np.float64)-np.asarray(b, np.float64)).max()
          for a,b in zip(jax.tree.leaves(sA.params), jax.tree.leaves(sB.params)))
print("restart_err", err)
""", devices=1)
    assert float(out.split("restart_err")[1].split()[0]) < 1e-6


def test_mini_dryrun_with_moe_shard_map():
    """Lower+compile a train step for a reduced MoE arch on a 4x2 mesh —
    the same code path as the production dry-run, incl. expert-parallel
    shard_map."""
    run_py("""
import jax
from repro.configs.reduced import reduced
from repro.launch.dryrun import build_lowerable, cost_analysis_dict
from repro.launch.mesh import make_mesh
from repro.models import SHAPES_BY_NAME, set_mesh
from repro.models.config import ShapeSpec
from repro.sharding import batch_axes
cfg = reduced("olmoe_1b_7b").with_(train_microbatches=2)
shape = ShapeSpec("t", 64, 16, "train")
mesh = make_mesh((4, 2), ("data", "model"))
set_mesh(mesh, batch_axes(mesh))
fn, args, in_sh, out_sh, donate = build_lowerable(cfg, shape, mesh)
with mesh:
    c = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                donate_argnums=donate).lower(*args).compile()
print("compiled_ok", cost_analysis_dict(c).get("flops", 0) > 0)
""")


def test_mini_dryrun_decode_cache_sharding():
    run_py("""
import jax
from repro.configs.reduced import reduced
from repro.launch.dryrun import build_lowerable
from repro.launch.mesh import make_mesh
from repro.models import set_mesh
from repro.models.config import ShapeSpec
from repro.sharding import batch_axes
cfg = reduced("llama3_8b")
shape = ShapeSpec("d", 64, 8, "decode")
mesh = make_mesh((4, 2), ("data", "model"))
set_mesh(mesh, batch_axes(mesh))
fn, args, in_sh, out_sh, donate = build_lowerable(cfg, shape, mesh)
with mesh:
    c = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                donate_argnums=donate).lower(*args).compile()
print("compiled_ok")
""")


def test_serving_engine_batches_and_latency():
    from repro.models import init_params
    from repro.models.config import ModelConfig
    from repro.serving import Request, ServeEngine
    cfg = ModelConfig(name="t", family="dense", n_layers=2, d_model=64,
                      n_heads=4, n_kv=2, d_ff=128, vocab=128,
                      tie_embeddings=True, param_dtype="float32",
                      compute_dtype="float32", attn_block_q=32,
                      attn_block_kv=32)
    params = init_params(jax.random.PRNGKey(0), cfg)
    eng = ServeEngine(cfg, params, max_seq=64)
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=rng.integers(0, 128, 8 + i,
                                               dtype=np.int32),
                    max_new_tokens=8) for i in range(3)]
    eng.serve_batch(reqs)
    for r in reqs:
        assert len(r.tokens_out) == 8
        assert r.first_token_at is not None and r.done_at >= r.first_token_at
    # determinism
    reqs2 = [Request(rid=r.rid, prompt=r.prompt, max_new_tokens=8)
             for r in reqs]
    eng.serve_batch(reqs2)
    assert all(a.tokens_out == b.tokens_out for a, b in zip(reqs, reqs2))


def test_serve_engine_stays_on_its_devices():
    """An engine handed devices 2-3 keeps its params and cache there, its
    left-padded batch answers like each request alone, and its decode
    loop gives the tokens a one-device engine gives."""
    out = run_py(SMALL_CFG + """
import jax, numpy as np
from repro.models import init_params
from repro.serving import ServeEngine
devs = jax.devices()
eng = ServeEngine(CFG, init_params(jax.random.PRNGKey(0), CFG), max_seq=64,
                  devices=devs[2:4])
rng = np.random.default_rng(0)
prompts = [rng.integers(0, 256, n, dtype=np.int32) for n in (5, 21)]
with eng._placed():
    _, cache, _, _, logits = eng._start(prompts)
where = {d.id for x in jax.tree.leaves((eng.params, cache)) for d in x.devices()}
print("where", sorted(where))
alone = [eng.step_logits(p, [])[0] for p in prompts]
err = max(np.abs(np.asarray(logits[i], np.float32) - alone[i]).max()
          for i in range(2))
print("pad_err", err)
from repro.serving import Request
solo = ServeEngine(CFG, init_params(jax.random.PRNGKey(0), CFG), max_seq=64)
prompts.append(rng.integers(0, 256, 9, dtype=np.int32))
def serve(e, n):
    reqs = [Request(rid=i, prompt=p, max_new_tokens=6)
            for i, p in enumerate(prompts[:n])]
    e.serve_batch(reqs)
    return [r.tokens_out for r in reqs]
# two rows split over the two devices, three replicated on both
print("served_alike", all(serve(eng, n) == serve(solo, n) for n in (2, 3)))
""", devices=4)
    assert out.split("where")[1].split("\n")[0].strip() == "[2, 3]"
    assert float(out.split("pad_err")[1].split()[0]) < 1e-4
    assert "served_alike True" in out


def test_preempt_frees_device_state_and_resumes_bit_identically():
    out = run_py(SMALL_CFG + """
import jax, numpy as np, tempfile
from repro.runtime import ElasticJob
devs = jax.devices()
d = tempfile.mkdtemp()
job = ElasticJob(1, CFG, kind="malleable", batch=8, seq=32,
                 ckpt_dir=d, ckpt_every=100, seed=0)
job.start(devs[2:4])
for _ in range(3): job.step()
held = jax.tree.leaves(job.state)
saved = [np.asarray(x) for x in held]
job.preempt(warning=True)
print("freed", job.state is None and all(x.is_deleted() for x in held))
job.resume(devs[0:2])
back = [np.asarray(x) for x in jax.tree.leaves(job.state)]
print("identical", all(np.array_equal(a, b) for a, b in zip(saved, back)))
print("on", sorted({d.id for x in jax.tree.leaves(job.state) for d in x.devices()}))
nockpt = ElasticJob(2, CFG, kind="rigid", batch=8, seq=32,
                    ckpt_dir=tempfile.mkdtemp(), ckpt_every=100, seed=0)
nockpt.start(devs[:2])
nockpt.step()
nockpt.preempt(warning=False)    # rigid, no checkpoint yet: state is kept
print("kept", nockpt.state is not None)
nockpt.resume(devs[2:4])
print("resumed_step", nockpt.step_idx)
""", devices=4)
    assert "freed True" in out and "identical True" in out
    assert "on [0, 1]" in out and "kept True" in out
    assert "resumed_step 1" in out


@pytest.mark.parametrize("preset", [False, True])
def test_compile_cache_dir(preset, tmp_path):
    from repro import compile_cache
    preset = str(tmp_path / "cache") if preset else None
    code = ("import jax; from repro.compile_cache import enable_compile_cache;"
            "print(enable_compile_cache(), jax.config.jax_compilation_cache_dir)")
    env = dict(os.environ, PYTHONPATH=SRC)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if preset:
        env["JAX_COMPILATION_CACHE_DIR"] = preset
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    want = preset or str(compile_cache.DEFAULT_DIR)
    assert out.stdout.split() == [want, want]
    assert compile_cache.DEFAULT_DIR.name == ".jax_cache"
    assert compile_cache.DEFAULT_DIR.parent == \
        compile_cache.Path(SRC).resolve().parent


def test_straggler_monitor():
    from repro.runtime import StragglerMonitor
    mon = StragglerMonitor(threshold=2.0)
    for _ in range(10):
        assert not mon.observe(1.0)
    assert mon.observe(5.0)          # 5x the EMA
    assert len(mon.events) == 1
    assert not mon.observe(1.0)      # EMA not poisoned by the spike
