"""The span and counter recorder (repro.telemetry), and the spans the live
path records: serving, training, and the preempt/restore split."""
import os
import subprocess
import sys
import threading
import time

import pytest

from repro import telemetry
from repro.telemetry import Recorder

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _by_name(rows):
    out = {}
    for r in rows:
        out.setdefault(r.name, []).append(r)
    return out


def _children(rows, parent):
    return [r for r in rows if r.parent == parent.seq]


def test_nesting_parent_and_self_time():
    rec = Recorder()
    with rec.span("outer"):
        time.sleep(0.002)
        with rec.span("inner"):
            time.sleep(0.004)
        with rec.span("inner"):
            with rec.span("leaf"):
                time.sleep(0.001)
    rows = rec.rows()
    assert [r.name for r in rows] == ["outer", "inner", "inner", "leaf"]
    outer, a, b, leaf = rows
    assert outer.parent is None
    assert a.parent == b.parent == outer.seq and leaf.parent == b.seq
    assert outer.t0 <= a.t0 <= a.t1 <= b.t0 <= leaf.t0 <= leaf.t1 <= \
        b.t1 <= outer.t1
    dur = [r.t1 - r.t0 for r in rows]
    assert outer.self_s == pytest.approx(dur[0] - dur[1] - dur[2], abs=1e-9)
    assert b.self_s == pytest.approx(dur[2] - dur[3], abs=1e-9)
    assert leaf.self_s == dur[3] and outer.self_s >= 0.002
    assert rec.rows("inner") == [a, b]
    assert rec.rows(since=b.t0) == [b, leaf]


def test_key_is_inherited_and_n_can_be_set_inside():
    rec = Recorder()
    with rec.span("batch", key=7, n=3):
        with rec.span("step") as sp:
            sp.n = 5
        with rec.span("other", key="x"):
            pass
    batch, step, other = rec.rows()
    assert (batch.key, batch.n) == (7, 3)
    assert (step.key, step.n) == (7, 5)
    assert other.key == "x"
    assert (sp.t0, sp.t1) == (step.t0, step.t1)


def test_record_has_no_parent():
    rec = Recorder()
    with rec.span("start"):
        rec.record("wait", 1.0, 2.0, key=4, n=1)
    wait = rec.rows("wait")[0]
    assert (wait.t0, wait.t1, wait.parent, wait.key) == (1.0, 2.0, None, 4)
    assert rec.rows("start")[0].self_s == pytest.approx(
        rec.rows("start")[0].t1 - rec.rows("start")[0].t0)


def test_the_ring_is_bounded_and_counts_what_it_drops():
    rec = Recorder(maxlen=4)
    for i in range(10):
        with rec.span("s", n=i):
            pass
    rows = rec.rows()
    assert [r.n for r in rows] == [6, 7, 8, 9]
    assert rec.dropped == 6 and rec.summary()["dropped"] == 6
    assert rec.dropped_since(rows[0].t0 - 1.0)
    assert not rec.dropped_since(rows[0].t0)


def test_counters_sum_across_threads():
    rec = Recorder()
    rec.count("a", 2)
    rec.count("a", 3.5)
    rec.count("b")
    prev = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work():
            for _ in range(2000):
                with rec.span("t"):
                    rec.count("c")
        threads = [threading.Thread(target=work) for _ in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(prev)
    assert rec.counters() == {"a": 5.5, "b": 1, "c": 32000}
    rows = rec.rows("t")
    assert len(rows) == 32000 and all(r.parent is None for r in rows)
    assert len({r.seq for r in rows}) == 32000


def test_summary():
    rec = Recorder()
    for _ in range(3):
        with rec.span("a"):
            pass
    rec.count("k", 2)
    s = rec.summary()
    assert s["spans"]["a"]["count"] == 3
    assert set(s["spans"]["a"]) == {"count", "total_s", "self_p50_s",
                                    "self_p99_s"}
    assert s["counters"] == {"k": 2} and s["dropped"] == 0


def test_a_cold_jit_records_a_compile_under_the_open_span():
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: x * 3 + 1)
    x = jnp.ones((5,))
    rec = Recorder()
    with rec.span("cold") as cold:
        f(x).block_until_ready()
    with rec.span("cached") as cached:
        f(x).block_until_ready()
    rows = rec.rows()
    compiles = [r for r in rows if r.name == "jax.compile"]
    assert compiles and all(r.parent == cold.seq for r in compiles)
    assert any("lambda" in str(r.key) for r in compiles)
    assert not [r for r in rows if r.parent == cached.seq]
    top = [r for r in rows if r.name == "cold"][0]
    assert top.self_s < top.t1 - top.t0
    c = rec.counters()
    assert c["jax.compiles"] == len(compiles) and c["jax.traces"] >= 1
    assert c["jax.compile_s"] == pytest.approx(
        sum(r.t1 - r.t0 for r in compiles))


def test_importing_the_recorder_and_the_cluster_pulls_in_no_jax():
    code = ("import sys, repro.telemetry, repro.runtime.cluster; "
            "from repro import telemetry\n"
            "with telemetry.span('x'): pass\n"
            "sys.exit(1 if 'jax' in sys.modules else 0)")
    proc = subprocess.run([sys.executable, "-c", code],
                          env={**os.environ, "PYTHONPATH": SRC},
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr


# ------------------------------------------------------------ the live path
def _cfg():
    """InternVL2-1B at the benchmark rehearsal's reduced CPU sizes."""
    from repro.configs import get_config
    return get_config("internvl2_1b").with_(
        n_layers=2, d_model=64, n_heads=4, n_kv=2, d_head=16, d_ff=128,
        vocab=512, n_patches=8, attn_block_q=64, attn_block_kv=64,
        train_microbatches=2)


def _engine():
    import jax

    from repro.models import init_params
    from repro.serving import ServeEngine
    cfg = _cfg()
    return ServeEngine(cfg, init_params(jax.random.PRNGKey(0), cfg),
                       max_seq=48)


def _serve(eng, new_tokens=5):
    import numpy as np

    from repro.serving import Request
    rng = np.random.default_rng(1)
    reqs = [Request(rid=i, prompt=rng.integers(0, 512, n, dtype=np.int32),
                    max_new_tokens=new_tokens)
            for i, n in enumerate((9, 16, 30))]
    return eng.serve_batch(reqs)


def test_serve_batch_records_prefill_and_each_decode_step():
    eng = _engine()
    since = time.monotonic()
    _serve(eng)                        # compiles every shape
    warm = telemetry.rows("serve.batch", since=since)
    before = telemetry.counters()
    since = time.monotonic()
    reqs = _serve(eng)
    rows = telemetry.rows(since=since)
    assert not [r for r in rows if r.name == "jax.compile"]
    batch = [r for r in rows if r.name == "serve.batch"]
    assert len(batch) == 1 and batch[0].n == 3
    kids = _by_name(_children(rows, batch[0]))
    assert set(kids) == {"serve.prefill", "serve.decode_step"}
    (pre,) = kids["serve.prefill"]
    steps = kids["serve.decode_step"]
    assert len(steps) == 5 - 1
    assert pre.n == 9 + 16 + 30 and pre.key == batch[0].key
    assert [r.name for r in _children(rows, pre)] == ["serve.token_sync"]
    sent = []
    for s in steps:
        parts = _by_name(_children(rows, s))
        assert len(parts["serve.token_sync"]) == 1
        assert len(parts.get("serve.dispatch", [])) <= 1
        assert set(parts) <= {"serve.dispatch", "serve.token_sync"}
        sent += [d.n for d in parts.get("serve.dispatch", [])]
        assert s.key == batch[0].key and s.n == 3
    # the first step enqueues steps 1 and 2, each later one the step
    # after it, and the last step nothing: no step runs past the batch
    assert sent == [2, 1, 1]
    after = telemetry.counters()
    got = sum(len(r.tokens_out) for r in reqs)
    assert after["serve.tokens_out"] - before.get("serve.tokens_out", 0) \
        == got == 15
    assert after["serve.decode_slots"] - before.get(
        "serve.decode_slots", 0) == 3 * 4
    assert after["serve.prompt_tokens"] - before.get(
        "serve.prompt_tokens", 0) == 55
    assert after["serve.padded_tokens"] - before.get(
        "serve.padded_tokens", 0) == 3 * 32
    assert all(r.first_token_at == pre.t1 for r in reqs)
    assert warm[0].key != batch[0].key      # each batch has a key of its own


def test_a_profiler_trace_holds_the_program_spans(tmp_path):
    import jax
    from jax.profiler import ProfileData
    eng = _engine()
    _serve(eng)                        # compiled before the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        _serve(eng)
    finally:
        jax.profiler.stop_trace()
    files = [os.path.join(d, f) for d, _, fs in os.walk(tmp_path)
             for f in fs if f.endswith(".xplane.pb")]
    pd = ProfileData.from_file(files[0])
    names = {ev.name for plane in pd.planes for line in plane.lines
             for ev in line.events}
    assert {"serve.batch", "serve.prefill", "serve.decode_step",
            "serve.dispatch", "serve.token_sync"} <= names


def _job(tmp_path, jid):
    from repro.runtime import ElasticJob
    return ElasticJob(jid, _cfg(), kind="malleable", batch=2, seq=24,
                      ckpt_dir=str(tmp_path / f"j{jid}"), ckpt_every=10 ** 6,
                      seed=0)


def test_train_step_records_its_four_parts(tmp_path):
    import jax
    job = _job(tmp_path, 41)
    job.start(jax.devices()[:1])
    job.step()
    since = time.monotonic()
    job.step()
    rows = telemetry.rows(since=since)
    (step,) = [r for r in rows if r.name == "train.step"]
    assert (step.key, step.n) == (41, 2 * (24 + 8))
    kids = _children(rows, step)
    assert [r.name for r in kids] == ["train.batch", "train.place",
                                      "train.dispatch", "train.sync"]
    assert all(r.key == 41 for r in kids)
    assert not [r for r in rows if r.name == "jax.compile"]
    assert job.monitor.n == 2


def test_preempt_and_resume_record_their_splits(tmp_path):
    import jax
    job = _job(tmp_path, 42)
    job.start(jax.devices()[:1])
    job.step()
    since = time.monotonic()
    job.preempt(warning=True)
    job.resume(jax.devices()[:1])
    rows = telemetry.rows(since=since)
    names = _by_name(rows)
    (pre,) = names["elastic.preempt"]
    assert [r.name for r in _children(rows, pre)] == [
        "ckpt.device_get", "ckpt.write", "ckpt.fsync", "ckpt.fsync",
        "elastic.free"]
    (res,) = names["elastic.resume"]
    assert [r.name for r in _children(rows, res)] == [
        "elastic.jit", "ckpt.load", "ckpt.place"]
    (get,) = names["ckpt.device_get"]
    (load,) = names["ckpt.load"]
    assert get.n == load.n > 0
    assert pre.n == res.n > get.n // 2      # the file's bytes
    assert pre.key == res.key == get.key == 42


def test_resize_is_awaited_and_its_recompile_shows_in_the_next_step(
        tmp_path):
    import jax
    job = _job(tmp_path, 43)
    job.start(jax.devices()[:1])
    job.step()
    since = time.monotonic()
    dt = job.resize(jax.devices()[:1])
    (row,) = telemetry.rows("elastic.resize", since=since)
    assert dt == row.t1 - row.t0 and row.key == 43
    assert row.n == sum(x.nbytes for x in jax.tree.leaves(job.state))
    job.step()
    rows = telemetry.rows(since=since)
    (step,) = [r for r in rows if r.name == "train.step"]
    (dispatch,) = [r for r in _children(rows, step)
                   if r.name == "train.dispatch"]
    assert [r.name for r in _children(rows, dispatch)] and all(
        r.name == "jax.compile" for r in _children(rows, dispatch))
    assert not hasattr(job, "resize_costs")


class _FakeJob:
    """The scheduling surface of an ElasticJob, without jax."""

    def __init__(self, jid):
        self.jid, self.kind, self.ckpt_every = jid, "malleable", 50
        self.ckpt_dir, self.state, self.step_idx = None, None, 0

    def start(self, devices):
        self.state = object()

    def step(self):
        self.step_idx += 1
        return {}


def test_the_launcher_and_cluster_record_admission_and_vacate():
    from types import SimpleNamespace

    from repro.runtime import LiveCluster
    from repro.service import AdmissionQueue, LiveClusterLauncher
    cluster = LiveCluster([f"dev{i}" for i in range(4)])
    launcher = LiveClusterLauncher(cluster, lambda spec: _FakeJob(spec.jid))
    q = AdmissionQueue(base_jid=7_000_000)
    train = q.submit_training(n_max=2, runtime_s=10.0, n_min=1)
    od = q.submit_inference(nodes=2, hold_s=1.0)
    since = time.monotonic()
    launcher.start_job(train, 2)
    launcher.start_job(od, 2)
    launcher.tick()
    launcher.finish(SimpleNamespace(job=od))
    rows = telemetry.rows(since=since)
    names = _by_name(rows)
    waits = [r for r in telemetry.rows("admission.wait")
             if r.key in (train.jid, od.jid) and r.t1 >= since]
    assert [w.key for w in waits] == [train.jid, od.jid]
    assert all(w.parent is None and w.t0 < since <= w.t1 for w in waits)
    starts = names["launch.start_job"]
    assert [(s.key, s.n) for s in starts] == [(train.jid, 2), (od.jid, 2)]
    (acq,) = names["cluster.acquire_od"]
    assert acq.parent == starts[1].seq and (acq.key, acq.n) == (-1, 2)
    (rel,) = names["cluster.release_od"]
    assert (rel.key, rel.n) == (-1, 2)
    assert len(names["launch.tick"]) == 1


def test_the_service_report_carries_the_summary():
    from repro.core import JobSpec, JobType
    from repro.service import SchedulerService, ServiceConfig
    jobs = [JobSpec(jid=i, jtype=JobType.RIGID, project="t",
                    submit_time=10.0 * i, size=1, t_estimate=50.0,
                    t_actual=30.0) for i in range(4)]
    since = time.monotonic()
    svc = SchedulerService(ServiceConfig(n_nodes=2), jobs=jobs)
    rep = svc.run_replay()
    batches = telemetry.rows("service.event_batch", since=since)
    assert len(batches) == len(svc.monitor.decision_ms) > 0
    assert [(r.t1 - r.t0) * 1e3 for r in batches] == \
        svc.monitor.decision_ms
    t = rep.telemetry
    assert t["spans"]["service.event_batch"]["count"] >= len(batches)
    assert set(t) == {"spans", "counters", "dropped"}
