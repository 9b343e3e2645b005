"""The live stack a cell drives, and the window that measures it.

One run builds the program's own live path and nothing beside it:
``AdmissionQueue`` -> ``SchedulerService(...).run_live`` ->
``LiveClusterLauncher`` -> ``LiveCluster`` -> ``ElasticJob`` /
``ServeEngine``.  The service loop runs on a thread of its own; this
module's main thread is the submitter: it admits the jobs on the mix's
open-loop schedule and keeps the window's clock.

What the harness adds is recording, around its calls into the program:
a subclass of ``ElasticJob`` that keeps each step's loss and time and the
readings the correctness check needs, a subclass of
``LiveClusterLauncher`` that marks when an on-demand job is started and
released, and the ``job_factory`` / ``serve_fn`` the launcher calls.
"""
from __future__ import annotations

import math
import shutil
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

from . import traffic as traffic_mod
from .tracing import Spans

#: the reduced sizes of the CPU rehearsal (tests only): same code paths,
#: every width small enough for Pallas in interpret mode
REHEARSAL = {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv": 2,
             "d_head": 16, "d_ff": 128, "vocab": 512, "n_patches": 8,
             "attn_block_q": 64, "attn_block_kv": 64}
#: how long past the window's end a request may still be served
LATE_S = 60.0
REHEARSAL_TRAINER = {"batch": 2, "text_len": 56, "microbatches": 2}
REHEARSAL_REQUESTS = {"prompt_lens": [[9, 16], [25, 32]],
                      "weights": [0.5, 0.5], "block": 2, "new_tokens": 6,
                      "max_batch": 2, "rate_per_s": 3.0, "tail_s": 0.5}


@dataclass
class Step:
    t0: float
    t1: float
    loss: float
    idx: int                      # the step's number, 1-based
    fresh: bool                   # first step after a (re)jit


@dataclass
class TrainRecord:
    steps: List[Step] = field(default_factory=list)
    p0_sum: Optional[np.ndarray] = None       # params before step 1
    p0: Optional[dict] = None                 # the same, on the host
    grad_m: Optional[Dict[str, float]] = None  # |m| per leaf after step 1
    p_check: Optional[dict] = None            # params after the last check step
    pre_sum: Optional[np.ndarray] = None      # whole state before preempt
    post_sum: Optional[np.ndarray] = None     # whole state after restore


@dataclass
class Run:
    """Everything a run recorded; metric readers read from it."""

    cell: str
    cfg: dict
    mix: dict
    sz: dict
    seconds: float
    seed: int
    chips: int
    rehearse: bool
    work: Path = Path(".")
    trainer: dict = field(default_factory=dict)
    peak: Optional[dict] = None
    spans: Spans = field(default_factory=Spans)
    t_proc: float = 0.0
    t0: float = math.nan          # the window
    t1: float = math.nan
    requests: List[dict] = field(default_factory=list)
    batches: List[dict] = field(default_factory=list)
    train: TrainRecord = field(default_factory=TrainRecord)
    od: Dict[str, float] = field(default_factory=dict)
    trace: Optional[dict] = None
    serve_devices: int = 0

    @property
    def setup_s(self) -> float:
        return self.t0 - self.t_proc

    @property
    def tokens_per_step(self) -> int:
        t = self.trainer
        return t["batch"] * (t["text_len"] + self.sz["P"]) if t else 0


# ------------------------------------------------------------ program side
def program_config(cfg: dict, rehearse: bool):
    """The program's ModelConfig for a configuration file, checked against
    every size the file states."""
    from repro.configs import get_config
    c = get_config(cfg["model"])
    stated = {"n_layers": cfg["num_hidden_layers"],
              "d_model": cfg["hidden_size"],
              "n_heads": cfg["num_attention_heads"],
              "n_kv": cfg["num_key_value_heads"], "d_head": cfg["head_dim"],
              "d_ff": cfg["intermediate_size"], "vocab": cfg["vocab_size"],
              "n_patches": cfg["num_image_token"],
              "rope_theta": cfg["rope_theta"], "norm_eps": cfg["rms_norm_eps"],
              "tie_embeddings": cfg["tie_word_embeddings"],
              "padded_vocab": cfg["unembed_rows"],
              "param_dtype": cfg["param_dtype"],
              "compute_dtype": cfg["compute_dtype"], "remat": cfg["remat"]}
    differ = {k: (getattr(c, k), v) for k, v in stated.items()
              if getattr(c, k) != v}
    if differ:
        raise ValueError(f"the program's {cfg['model']} is not the "
                         f"configuration {cfg['name']}: {differ}")
    c = c.with_(train_microbatches=cfg["trainer"]["microbatches"])
    if rehearse:
        c = c.with_(**REHEARSAL,
                    train_microbatches=REHEARSAL_TRAINER["microbatches"])
    return c


def rehearsal_sizes(sz: dict) -> dict:
    r = REHEARSAL
    return dict(sz, L=r["n_layers"], d=r["d_model"], H=r["n_heads"],
                K=r["n_kv"], Dh=r["d_head"], F=r["d_ff"], V=r["vocab"],
                Vp=r["vocab"], P=r["n_patches"])


def _tools():
    """Small jitted readers of device state: per-leaf checksums of the
    bits and per-leaf float32 norms."""
    import jax
    import jax.numpy as jnp

    def bits(x):
        if x.dtype.itemsize == 2:
            u = jax.lax.bitcast_convert_type(x, jnp.uint16).astype(jnp.uint32)
        else:
            u = jax.lax.bitcast_convert_type(x, jnp.uint32)
        u = u.reshape(-1)
        w = (jnp.arange(u.size, dtype=jnp.uint32) % 65521) + 1
        return jnp.stack([jnp.sum(u), jnp.sum(u * w)])

    checksum = jax.jit(lambda t: [bits(x) for x in jax.tree.leaves(t)])
    norms = jax.jit(lambda t: [jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))) for x in jax.tree.leaves(t)])
    return checksum, norms


def leaf_names(tree) -> List[str]:
    import jax
    return [jax.tree_util.keystr(p)
            for p, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


class Stack:
    """The program's live path for one cell, with the harness's records."""

    def __init__(self, run: Run):
        import jax

        from repro.runtime import ElasticJob, LiveCluster
        from repro.service import (AdmissionQueue, LiveClusterLauncher,
                                   SchedulerService, ServiceConfig)
        self.jax = jax
        self.run = run
        self.cfg = program_config(run.cfg, run.rehearse)
        self.devices = jax.devices()[:run.chips]
        self.sched = traffic_mod.make(run.mix, run.seed, run.seconds,
                                      self.cfg.vocab)
        self.max_seq = (run.cfg["serving"]["max_seq"] if not run.rehearse
                        else max(hi for _, hi in
                                 REHEARSAL_REQUESTS["prompt_lens"])
                        + REHEARSAL_REQUESTS["new_tokens"])
        self.ckpt = run.work / "ckpt"
        shutil.rmtree(self.ckpt, ignore_errors=True)
        self.checksum, self.norms = _tools()
        self.jobs: Dict[int, object] = {}
        self.engine = None
        self.ready = threading.Event()
        self.go = threading.Event()
        self.ticked = threading.Event()
        self.error: Optional[BaseException] = None
        self._weights_fn = {}
        spans = run.spans
        stack = self

        class BenchJob(ElasticJob):
            def _jit(self):
                super()._jit()
                self._fresh = True

            def start(self, devices):
                if self.state is not None:
                    return super().start(devices)
                # the program's own init, with the seed's key passed in
                # rather than held as a constant: one compiled program
                # serves every seed, so set-up compiles nothing new
                from repro.models import init_params
                from repro.sharding import tree_shardings
                from repro.training import make_train_state
                self.devices = list(devices)
                self.mesh = self._build(self.devices)
                self._jit()
                cfg, opt = self.cfg, self.opt

                def init_state(key):
                    return make_train_state(init_params(key, cfg), opt)
                key = jax.random.PRNGKey(self.seed)
                sh = tree_shardings(jax.eval_shape(init_state, key), cfg,
                                    self.mesh)
                self.state = jax.jit(init_state, out_shardings=sh)(key)

            def step(self):
                rec = run.train
                if self.step_idx == 0 and rec.p0_sum is None:
                    rec.p0_sum = stack._sums(self.state.params)
                    rec.p0 = jax.device_get(self.state.params)
                fresh = getattr(self, "_fresh", True)
                self._fresh = False
                t0 = time.monotonic()
                with spans.span("train_step"):
                    metrics = super().step()      # float(loss) syncs
                rec.steps.append(Step(t0, time.monotonic(), metrics["loss"],
                                      self.step_idx, fresh))
                if self.step_idx == 1 and rec.grad_m is None:
                    rec.grad_m = stack._leaf_norms(self.state.opt.m)
                if self.step_idx == stack.n_check and rec.p_check is None:
                    rec.p_check = jax.device_get(self.state.params)
                return metrics

            def preempt(self, warning: bool = True):
                run.train.pre_sum = stack._sums(self.state)
                with spans.span("checkpoint"):
                    super().preempt(warning)

            def resume(self, devices):
                with spans.span("restore"):
                    super().resume(devices)
                run.train.post_sum = stack._sums(self.state)

        class BenchLauncher(LiveClusterLauncher):
            def start_job(self, job, size):
                with spans.span("start_job"):
                    super().start_job(job, size)

            def finish(self, rec):
                if rec.job.jid not in self.od_nodes:
                    return super().finish(rec)
                with spans.span("release"):
                    return super().finish(rec)

            def tick(self):
                with spans.span("tick"):
                    super().tick()
                stack.ticked.set()

        self.BenchJob = BenchJob
        self.n_check = run.mix.get("check", {}).get("train_steps", 0)
        self.cluster = LiveCluster(self.devices,
                                   arrival_policy=run.cfg["arrival_policy"])
        acquire = self.cluster.acquire_for_ondemand

        def timed_acquire(need):
            with spans.span("vacate"):
                return acquire(need)
        self.cluster.acquire_for_ondemand = timed_acquire
        self.launcher = BenchLauncher(self.cluster, self.job_factory,
                                      serve_fn=self.serve_fn,
                                      steps_per_tick=1, target_steps=10 ** 9)
        self.queue = AdmissionQueue()
        self.svc_cfg = ServiceConfig(n_nodes=len(self.devices),
                                     mechanism=run.cfg["mechanism"],
                                     speed=1.0)
        self.svc = SchedulerService(self.svc_cfg, launcher=self.launcher)
        step_until = self.svc.core.step_until

        def timed_step_until(t):
            with spans.span("scheduler_pass"):
                return step_until(t)
        self.svc.core.step_until = timed_step_until
        self.thread: Optional[threading.Thread] = None

    # ---------------------------------------------------------- readers
    def _sums(self, tree) -> np.ndarray:
        return np.stack([np.asarray(x) for x in self.checksum(tree)])

    def _leaf_norms(self, tree) -> Dict[str, float]:
        return dict(zip(leaf_names(tree),
                        [float(x) for x in self.norms(tree)]))

    # ---------------------------------------------------------- payloads
    def job_factory(self, spec):
        t = self.run.trainer
        job = self.BenchJob(spec.jid, self.cfg, kind="malleable",
                            batch=t["batch"], seq=t["text_len"],
                            ckpt_dir=str(self.ckpt / f"j{spec.jid}"),
                            ckpt_every=10 ** 9, seed=self.run.seed)
        self.jobs[spec.jid] = job
        return job

    def weights(self, devices):
        """The server's weights, made on ``devices`` in one jitted call
        from the seed, in the type they are served in."""
        jax = self.jax
        from jax.sharding import Mesh, NamedSharding
        from jax.sharding import PartitionSpec as P

        from repro.models import init_params
        key = tuple(d.id for d in devices)
        if key not in self._weights_fn:
            mesh = Mesh(np.asarray(devices).reshape(len(devices), 1),
                        ("data", "model"))
            cfg = self.cfg
            self._weights_fn[key] = jax.jit(
                lambda k: init_params(k, cfg),
                out_shardings=NamedSharding(mesh, P()))
        w = self._weights_fn[key](jax.random.PRNGKey(self.run.seed + 1))
        return jax.block_until_ready(w)

    def new_engine(self, devices):
        from repro.serving import ServeEngine
        return ServeEngine(self.cfg, self.weights(devices),
                           max_seq=self.max_seq, devices=devices)

    def warm_serving(self, engine):
        """Run every (batch, prompt length) the mix can make once, so the
        window compiles nothing."""
        from repro.kernels.ops import padded_len
        from repro.serving import Request
        n = self.run.mix["requests"]["new_tokens"]
        rng = np.random.default_rng(0)
        for b, s in traffic_mod.shapes(self.run.mix, padded_len):
            engine.serve_batch([Request(
                rid=-1, prompt=rng.integers(0, self.cfg.vocab, s,
                                            dtype=np.int32),
                max_new_tokens=n) for _ in range(b)])

    def serve_fn(self, spec, devices):
        """The on-demand job's payload: make the engine on the vacated
        devices, then serve its request stream, every request of it: one
        served late is late, not lost.  The stream arrives during the
        hold; the job returns when it is served, so its end event (due at
        the hold's end) is handled after that."""
        run, sched = self.run, self.sched
        with run.spans.span("serve_setup"):
            engine = self.new_engine(devices)
        run.serve_devices = len(devices)
        self.engine = engine
        if sched.od_due_s is None:          # starts during set-up
            self.warm_serving(engine)
            self.ready.set()
            self.go.wait()
            t_stream = run.t0
        else:
            t_stream = run.od["due"]
        run.od["stream_t0"] = t_stream
        return self.serve_stream(engine, t_stream,
                                 run.t0 + run.seconds + LATE_S)

    def serve_stream(self, engine, t_stream: float, deadline: float):
        """Serve the schedule's requests, due from ``t_stream`` on, in
        static batches of up to ``max_batch`` in arrival order, until all
        are served.  A request still waiting at ``deadline`` is never
        served: it counts as failed."""
        from repro.serving import Request
        run, sched = self.run, self.sched
        reqs = sched.requests
        due = [t_stream + r.due_s for r in reqs]
        waiting: deque = deque()
        i = 0
        while True:
            now = time.monotonic()
            while i < len(reqs) and due[i] <= now:
                waiting.append(i)
                i += 1
            if now >= deadline or (not waiting and i >= len(reqs)):
                break
            if not waiting:
                time.sleep(max(0.0, min(due[i], deadline) - now))
                continue
            take = [waiting.popleft()
                    for _ in range(min(sched.max_batch, len(waiting)))]
            batch = [Request(rid=reqs[j].rid, prompt=reqs[j].prompt,
                             max_new_tokens=reqs[j].new_tokens) for j in take]
            t_start = time.monotonic()
            with run.spans.span("serve_batch"):
                engine.serve_batch(batch)
            run.batches.append({"B": len(batch),
                                "S": max(len(r.prompt) for r in batch),
                                "lens": [len(r.prompt) for r in batch],
                                "t_due": due[take[0]], "t_start": t_start,
                                "t_first": batch[0].first_token_at,
                                "t_done": max(r.done_at for r in batch),
                                "n_decode": max(len(r.tokens_out)
                                                for r in batch) - 1})
            for j, r in zip(take, batch):
                run.requests[j].update(first=r.first_token_at, done=r.done_at,
                                       tokens=list(r.tokens_out))
        return [r for r in run.requests if "tokens" in r]

    # ------------------------------------------------------------ driver
    def _serve_loop_thread(self):
        try:
            self.svc.run_live(self.queue)
        except BaseException as e:          # surfaced by the main thread
            self.error = e
            self.ready.set()
            self.ticked.set()

    def _check(self):
        if self.error is not None:
            raise RuntimeError("the service loop failed") from self.error

    def _wait(self, ev: threading.Event, what: str, timeout: float = 1500):
        if not ev.wait(timeout):
            raise TimeoutError(f"set-up: {what} did not happen")
        self._check()

    def setup(self) -> None:
        """Everything before the window: warm serving shapes, start the
        service, admit the trainer and drive it through its first steps
        (through the window's own call), or start the serving job."""
        run, sched = self.run, self.sched
        run.requests = [{"rid": r.rid, "due_s": r.due_s,
                         "prompt_len": len(r.prompt),
                         "prompt": r.prompt, "new_tokens": r.new_tokens}
                        for r in sched.requests]
        if sched.od_nodes and sched.od_due_s is not None:
            with run.spans.span("warm_serving"):
                engine = self.new_engine(self.devices[:sched.od_nodes])
                self.warm_serving(engine)
                del engine
        self.thread = threading.Thread(target=self._serve_loop_thread,
                                       name="service", daemon=True)
        self.thread.start()
        if sched.trainer:
            t = run.trainer
            self.queue.submit_training(n_max=t["n_max"], n_min=t["n_min"],
                                       runtime_s=t["runtime_s"])
            self._wait(self.ticked, "the trainer's first step")
            info = next(iter(self.launcher.infos.values()))
            while info.steps_done < self.n_check:
                self.cluster.step_all(1)
            if sched.od_nodes:              # the preempt reads this
                self._sums(next(iter(self.jobs.values())).state)
        if sched.od_nodes and sched.od_due_s is None:
            self.queue.submit_inference(nodes=sched.od_nodes, hold_s=1e6)
            self._wait(self.ready, "the serving engine")

    def window(self) -> None:
        run, sched = self.run, self.sched
        run.t0 = time.monotonic()
        end = run.t0 + run.seconds
        self.go.set()
        with run.spans.span("window"):
            if sched.od_nodes and sched.od_due_s is not None:
                _sleep_until(run.t0 + sched.od_due_s, self)
                run.od["due"] = time.monotonic()
                self.queue.submit_inference(
                    nodes=sched.od_nodes, hold_s=sched.od_hold_s,
                    submit_time=self.svc.clock.now_sim())
            if sched.trainer == "step_all":
                while time.monotonic() < end:
                    self.cluster.step_all(1)
            _sleep_until(end, self)
        run.t1 = end

    def drain(self) -> None:
        """End the service: every remaining event is processed at once
        (the clock stops pacing), the queue closes, the loop returns."""
        self.svc.clock.speed = math.inf
        self.queue.close()
        self.thread.join()
        self._check()

    def specs(self):
        return [rec.job for rec in self.svc.core.records.values()]

    def free(self) -> None:
        """Drop every device array the program holds."""
        for job in self.jobs.values():
            job._free()
        self.engine = None
        for a in self.jax.live_arrays():
            a.delete()
        shutil.rmtree(self.ckpt, ignore_errors=True)


def _sleep_until(t: float, stack: Stack) -> None:
    while True:
        stack._check()
        dt = t - time.monotonic()
        if dt <= 0:
            return
        time.sleep(min(dt, 0.05))
