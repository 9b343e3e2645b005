#!/usr/bin/env python3
"""Bring-up smoke run of the hybrid scheduler's live path on a TPU.

    python chip_smoke.py              # one chip: the two phases below
    python chip_smoke.py --chips 4    # malleable resize across four chips
    python chip_smoke.py --rehearse   # the same phases at a reduced size on
                                      # the CPU, Pallas kernels interpreted

One chip:

1. decisions: an ``Experiment(device="jax")`` grid on Theta's 4,392 nodes
   (BASE, CUA&SPAA, CUP&STEAL x mixes W1, W5 x 2 seeds) is replayed as one
   jitted device program and parity-checked against the numpy engine.
2. live: internvl2-1b at its published widths (random weights from a seed)
   trains as a malleable job through AdmissionQueue -> SchedulerService
   (CUA&SPAA) -> LiveClusterLauncher -> LiveCluster.  An on-demand
   inference job arrives with notice; the job is preempted with a
   checkpoint, ServeEngine answers the on-demand batch on the vacated
   chip, and training resumes from the checkpoint.

``--chips 4`` runs only the resize phase: the malleable job trains on a
(4, 1) data mesh, a 2-node on-demand burst shrinks it to 2 chips, serving
runs on the 2 vacated chips, and the repaid lease expands it back to 4.

Every phase prints one JSON line.  The last stdout line is
``{"ok": true, "device": {...}}``, printed only when every check passed on
a TPU.  Without a TPU (and without ``--rehearse``) the run exits 2 and
prints no result.  Everything runs in this one process: a chip belongs to
one process.  Checkpoints go to ``.smoke_out/ckpt`` in the checkout and
are removed when the run ends.
"""
from __future__ import annotations

import argparse
import collections
import json
import math
import os
import shutil
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
OUT = HERE / ".smoke_out"
MECHANISMS = ("BASE", "CUA&SPAA", "CUP&STEAL")
#: logits of the Pallas and jnp attention paths agree to this fraction of
#: the largest reference logit.  Both compute in bf16 with f32
#: accumulation, but the kernels feed the MXU bf16 operands (p is rounded
#: to bf16 before p @ v) where the jnp path upcasts to f32, so each of the
#: 24 layers adds an independent bf16 rounding (2^-8 relative) to the
#: residual stream; 2^-4 leaves room for that drift and still fails on a
#: wrong mask, block or head mapping, which moves logits by O(1).
LOGIT_RTOL = 2.0 ** -4
N_CHECK_POSITIONS = 4
#: the on-demand job's batch: prompts of different lengths, so the serving
#: engine left-pads and masks a ragged batch
REQUESTS_PER_NODE = 4


class SmokeFailure(RuntimeError):
    """A check of the smoke run did not hold."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


# ------------------------------------------------------------------ payloads
def _recording_job_class():
    """ElasticJob that keeps what the checks read: losses, step times,
    the attention implementation its step traced, and whether each
    reshard left the params bit-identical."""
    import jax
    import numpy as np

    from repro.kernels import ops
    from repro.runtime import ElasticJob

    def host_bytes(tree):
        return [np.asarray(x).tobytes() for x in jax.tree.leaves(
            jax.device_get(tree))]

    class RecordingJob(ElasticJob):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            self.losses, self.step_s, self.reshards = [], [], []
            self.impls = collections.Counter()
            self.memory = None

        def step(self):
            before = collections.Counter(ops.traced_impls)
            t0 = time.perf_counter()
            metrics = super().step()            # float() waits for the chip
            self.step_s.append(time.perf_counter() - t0)
            self.impls.update(ops.traced_impls - before)
            self.losses.append(metrics["loss"])
            if self.memory is None:
                ma = self._step_fn.lower(self.state, self.next_batch()) \
                    .compile().memory_analysis()
                self.memory = {
                    "argument_gb": ma.argument_size_in_bytes / 1e9,
                    "temp_gb": ma.temp_size_in_bytes / 1e9,
                    "output_gb": ma.output_size_in_bytes / 1e9,
                    "alias_gb": ma.alias_size_in_bytes / 1e9}
            return metrics

        def resize(self, devices):
            before = host_bytes(self.state.params)
            cost = super().resize(devices)
            self.reshards.append({
                "to_chips": len(devices), "reshard_s": cost,
                "params_identical": before == host_bytes(self.state.params)})
            return cost

    return RecordingJob


def _prompt(p: dict, vocab: int):
    import numpy as np
    rng = np.random.default_rng(p["rid"])
    return rng.integers(0, vocab, p["prompt_len"], dtype=np.int32)


def _serve(engine, plan, vocab):
    """One pass of the planned batch; returns (requests, ttft_s,
    decode_s_per_token)."""
    from repro.serving import Request
    reqs = [Request(rid=p["rid"], prompt=_prompt(p, vocab),
                    max_new_tokens=p["max_new_tokens"]) for p in plan]
    engine.serve_batch(reqs)
    ttft = max(r.first_token_at - r.submitted_at for r in reqs)
    n_dec = max(len(r.tokens_out) for r in reqs) - 1
    dec = max(r.done_at - r.first_token_at for r in reqs) / max(n_dec, 1)
    return reqs, ttft, dec


def _max_rel_diff(a, b) -> float:
    import numpy as np
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))


def _live_setup(jax, cfg, devices, batch, seq, n_max, n_min, od_nodes,
                serve_check):
    """Build the live stack, admit one malleable job and one on-demand
    job with notice, and run the service until it drains.  Returns what
    the checks read."""
    from repro.core.job import JobType
    from repro.models import init_params
    from repro.runtime import LiveCluster
    from repro.service import (AdmissionQueue, LiveClusterLauncher,
                               SchedulerService, ServiceConfig, plan_requests,
                               shadow_fidelity)
    from repro.service.decisionlog import decision_digest
    from repro.serving import ServeEngine

    ckpt = OUT / "ckpt"
    shutil.rmtree(ckpt, ignore_errors=True)
    RecordingJob = _recording_job_class()
    jobs, served = {}, []

    def job_factory(spec):
        check(spec.jtype is JobType.MALLEABLE, "only malleable jobs admitted")
        job = RecordingJob(spec.jid, cfg, kind="malleable", batch=batch,
                           seq=seq, ckpt_dir=str(ckpt / f"j{spec.jid}"),
                           ckpt_every=10 ** 9, seed=0)
        jobs[spec.jid] = job
        return job

    def serve_fn(spec, devs):
        from repro.kernels import ops
        train = next(iter(jobs.values()))
        with jax.default_device(devs[0]):
            params = init_params(jax.random.PRNGKey(1), cfg)
        engine = ServeEngine(cfg, params, max_seq=128, devices=devs)
        before = collections.Counter(ops.traced_impls)
        plan = plan_requests(spec, vocab=cfg.vocab,
                             per_node=REQUESTS_PER_NODE)
        reqs, ttft, dec = _serve(engine, plan, cfg.vocab)
        impls = ops.traced_impls - before
        reqs2, ttft2, dec2 = _serve(engine, plan, cfg.vocab)
        param_devs = {d for x in jax.tree.leaves(engine.params)
                      for d in x.devices()}
        row = {"devices": sorted(d.id for d in devs),
               "train_devices": sorted(d.id for d in train.devices),
               "train_state_freed": train.state is None,
               "param_devices": sorted(d.id for d in param_devs),
               "requests": len(reqs),
               "new_tokens": [len(r.tokens_out) for r in reqs],
               "prefill_impl": sorted(i for m, i in impls if m == "causal"),
               "decode_impl": sorted(i for m, i in impls if m == "decode"),
               "ttft_s_first_pass": ttft, "decode_s_per_token_first_pass": dec,
               "ttft_s": ttft2, "decode_s_per_token": dec2,
               "greedy_repeats": [r.tokens_out for r in reqs]
               == [r.tokens_out for r in reqs2]}
        row.update(serve_check(engine, plan[0], reqs[0], devs))
        served.append(row)
        return reqs

    cluster = LiveCluster(devices, arrival_policy="SPAA")
    launcher = LiveClusterLauncher(cluster, job_factory, serve_fn=serve_fn,
                                   steps_per_tick=2, target_steps=8)
    queue = AdmissionQueue()
    specs = [queue.submit_training(n_max=n_max, n_min=n_min,
                                   runtime_s=100.0),
             queue.submit_inference(nodes=od_nodes, hold_s=10.0,
                                    submit_time=20.0, notice_lead_s=10.0)]
    queue.close()
    svc_cfg = ServiceConfig(n_nodes=len(devices), mechanism="CUA&SPAA",
                            speed=10.0)
    svc = SchedulerService(svc_cfg, launcher=launcher)
    rep = svc.run_live(queue)
    for info in launcher.infos.values():      # the training tail
        while info.status == "running":
            cluster.step_all(1)
    fid = shadow_fidelity(specs, ServiceConfig(n_nodes=len(devices),
                                               mechanism="CUA&SPAA"))
    job = jobs[specs[0].jid]
    info = launcher.infos[specs[0].jid]
    # live mode also logs each admission; the shadow replay of the same
    # jobs starts from a trace and logs only the placement decisions
    placed = [r for r in svc.log.rows if r["event"] != "admit"]
    return {"job": job, "info": info, "served": served, "report": rep,
            "fidelity": fid, "cluster_log": cluster.log,
            "placement_digest": decision_digest(
                [{**r, "seq": i} for i, r in enumerate(placed)])}


def _common_checks(out: dict) -> dict:
    job, rep, fid = out["job"], out["report"], out["fidelity"]
    check(len(out["served"]) == 1, f"served {len(out['served'])} batches")
    srv = out["served"][0]
    check(all(math.isfinite(x) for x in job.losses), f"losses {job.losses}")
    check(fid.ok and out["placement_digest"] == fid.digest_reference,
          "live decisions differ from the shadow replay")
    check(srv["greedy_repeats"], "greedy tokens differ on a second pass")
    check(srv["requests"] > 1, f"served {srv['requests']} request(s)")
    check(all(n == 16 for n in srv["new_tokens"]), "short generations")
    steady = sorted(job.step_s[1:]) or job.step_s
    return {"losses": job.losses,
            "first_step_s_incl_compile": job.step_s[0],
            "step_s_median": steady[len(steady) // 2],
            "step_s": job.step_s,
            "train_impl": sorted(i for m, i in job.impls if m == "causal"),
            "memory_analysis": job.memory,
            "decision_log_rows": rep.n_decisions,
            "placement_digest": out["placement_digest"][:16],
            "shadow_digest_matches": True,
            "cluster_events": [{k: v for k, v in e.items() if k != "t"}
                               for e in out["cluster_log"]],
            "served": srv}


# --------------------------------------------------------------------- phases
def phase_decisions(rehearse: bool) -> None:
    from repro.core import decision_jax
    from repro.core.experiment import Experiment
    from repro.core.workloads import WorkloadConfig

    kw = {"n_jobs": 200} if rehearse else {}
    workloads = [WorkloadConfig(notice_mix=m, **kw) for m in ("W1", "W5")]
    exp = Experiment(mechanisms=MECHANISMS, workloads=workloads,
                     seeds=(0, 1), processes=0, device="jax",
                     device_dtype=decision_jax.device_dtype())
    t0 = time.perf_counter()
    res = exp.run()
    rep = res.device_report
    emit("decisions", nodes=workloads[0].n_nodes, n_jobs=workloads[0].n_jobs,
         cells=rep.n_cells, dtype=rep.dtype,
         parity=("exact" if rep.dtype == "float64"
                 else f"rtol {decision_jax.FLOAT32_RTOL} + invariants"),
         n_calls=rep.n_calls,
         parity_ok=rep.parity_ok, n_mismatches=rep.n_mismatches,
         mismatch_sample=[repr(m) for m in rep.mismatches[:2]],
         compile_s=rep.compile_s, device_s=rep.device_s,
         sweep_s=time.perf_counter() - t0)
    check(rep.n_calls > 0, "no decision reached the device")
    check(rep.parity_ok, f"{rep.n_mismatches} device decisions diverge")


def phase_live(jax, cfg, batch, seq) -> None:
    from repro.kernels import ops
    from repro.serving import ServeEngine

    dev = jax.devices()[0]

    def serve_check(engine, plan_row, req, devs):
        cont = req.tokens_out[:N_CHECK_POSITIONS - 1]
        prompt = _prompt(plan_row, cfg.vocab)
        mine = engine.step_logits(prompt, cont)
        prev = ops.set_backend("jnp")
        try:
            ref = ServeEngine(cfg, engine.params, max_seq=engine.max_seq,
                              devices=devs).step_logits(prompt, cont)
        finally:
            ops.set_backend(prev)
        err = _max_rel_diff(mine, ref)
        return {"logits_vs_jnp_max_rel_diff": err,
                "logits_vs_jnp_rtol": LOGIT_RTOL,
                "logits_top1_agree": [bool(a.argmax() == b.argmax())
                                      for a, b in zip(mine, ref)],
                "logits_positions": len(mine)}

    out = _live_setup(jax, cfg, [dev], batch, seq, n_max=1, n_min=1,
                      od_nodes=1, serve_check=serve_check)
    row = _common_checks(out)
    job, info, srv = out["job"], out["info"], row["served"]
    stats = dev.memory_stats() or {}
    row.update(arch=cfg.name, params_m=cfg.param_count() / 1e6,
               batch=batch, seq=seq, microbatches=cfg.train_microbatches,
               preempts=info.preempt_count, steps=job.step_idx,
               peak_bytes_in_use=stats.get("peak_bytes_in_use"))
    emit("live", **row)
    check(info.preempt_count == 1, "the malleable job was not preempted")
    check(srv["train_state_freed"], "preempted state still on the chip")
    check(job.step_idx == 8, f"job ended at step {job.step_idx}")
    check(row["train_impl"] == srv["prefill_impl"] == srv["decode_impl"]
          == ["pallas"], "attention did not run as the Pallas kernels")
    check(srv["logits_vs_jnp_max_rel_diff"] <= LOGIT_RTOL,
          "Pallas logits disagree with the jnp attention path")


def phase_resize(jax, cfg, batch, seq) -> None:
    devices = jax.devices()[:4]
    check(len(devices) == 4, f"--chips 4 found {len(jax.devices())} devices")

    def serve_check(engine, plan_row, req, devs):
        return {}

    out = _live_setup(jax, cfg, devices, batch, seq, n_max=4, n_min=2,
                      od_nodes=2, serve_check=serve_check)
    row = _common_checks(out)
    job, srv = out["job"], row["served"]
    row.update(arch=cfg.name, batch=batch, seq=seq,
               microbatches=cfg.train_microbatches, reshards=job.reshards)
    emit("resize", **row)
    check([r["to_chips"] for r in job.reshards] == [2, 4],
          f"reshards {job.reshards}")
    check(all(r["params_identical"] for r in job.reshards),
          "params changed across a reshard")
    check(set(srv["param_devices"]) == set(srv["devices"])
          and not set(srv["devices"]) & set(srv["train_devices"]),
          "serving did not run on the vacated chips alone")


# ----------------------------------------------------------------------- main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rehearse", action="store_true",
                    help="reduced size on the CPU, Pallas interpreted; "
                         "never reports ok")
    args = ap.parse_args(argv)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        if args.chips == 4:
            os.environ["XLA_FLAGS"] = (
                "--xla_force_host_platform_device_count=4 "
                + os.environ.get("XLA_FLAGS", ""))
    sys.path.insert(0, str(HERE / "src"))
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              "use --rehearse for the CPU rehearsal", file=sys.stderr)
        return 2

    from repro.configs import get_config
    from repro.configs.reduced import reduce_config
    from repro.kernels import ops

    cfg = get_config("internvl2_1b")
    if args.rehearse:
        ops.set_backend("interpret")
        cfg = reduce_config(cfg).with_(param_dtype="bfloat16",
                                       compute_dtype="bfloat16")
    OUT.mkdir(exist_ok=True)
    try:
        if args.chips == 4:
            if args.rehearse:
                phase_resize(jax, cfg.with_(train_microbatches=2), 8, 56)
            else:
                phase_resize(jax, cfg.with_(train_microbatches=2), 8, 2048)
        else:
            phase_decisions(args.rehearse)
            if args.rehearse:
                phase_live(jax, cfg.with_(train_microbatches=2), 4, 56)
            else:
                phase_live(jax, cfg, 8, 2048)
    finally:
        shutil.rmtree(OUT / "ckpt", ignore_errors=True)
    if args.rehearse:
        emit("rehearsal", passed=True,
             note="CPU rehearsal at a reduced size; not a chip result")
        return 0
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
