"""End-to-end driver: the scheduler service running REAL JAX jobs.

    PYTHONPATH=src python examples/elastic_cluster_demo.py

8 placeholder devices form the "cluster".  Two malleable training jobs
and one rigid job are admitted through the service's front door
(AdmissionQueue); a paced on-demand inference burst arrives mid-run with
advance notice.  The service's policy core (CUA&SPAA) decides WHAT
starts/shrinks WHEN; the LiveClusterLauncher executes each decision on a
LiveCluster, whose registry-resolved arrival policy picks WHICH physical
nodes move (paper §III-B2/B3).  Everything is real: training state
re-shards across meshes, the rigid job checkpoints, the on-demand job
runs batched decoding on the vacated nodes, and the lease is repaid when
the burst finishes.  See docs/service.md for the architecture.
"""
import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                           + os.environ.get("XLA_FLAGS", ""))

import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.core.job import JobType  # noqa: E402
from repro.models import init_params  # noqa: E402
from repro.models.config import ModelConfig  # noqa: E402
from repro.runtime import ElasticJob, LiveCluster  # noqa: E402
from repro.service import (AdmissionQueue, LiveClusterLauncher,  # noqa: E402
                           SchedulerService, ServiceConfig, plan_requests)
from repro.serving import Request, ServeEngine  # noqa: E402

SMALL = ModelConfig(name="demo-lm", family="dense", n_layers=2, d_model=128,
                    n_heads=4, n_kv=4, d_ff=256, vocab=1024,
                    tie_embeddings=True, param_dtype="float32",
                    compute_dtype="float32", attn_block_q=64,
                    attn_block_kv=64)


def main():
    devices = jax.devices()
    print(f"cluster: {len(devices)} nodes ({devices[0].platform})")
    cluster = LiveCluster(devices, arrival_policy="SPAA")
    tmp = tempfile.mkdtemp(prefix="hybrid_demo_")

    def job_factory(spec):
        kind = "malleable" if spec.jtype is JobType.MALLEABLE else "rigid"
        return ElasticJob(spec.jid, SMALL, kind=kind, batch=8, seq=64,
                          ckpt_dir=f"{tmp}/j{spec.jid}", ckpt_every=10,
                          seed=spec.jid % 97)

    serve_state = {}

    def serve_fn(job, devices):
        """Run the on-demand payload on the devices the cluster vacated."""
        key = tuple(d.id for d in devices)
        if key not in serve_state:
            params = init_params(jax.random.PRNGKey(9), SMALL)
            serve_state[key] = ServeEngine(SMALL, params, max_seq=128,
                                           devices=devices)
        reqs = []
        for p in plan_requests(job, vocab=SMALL.vocab):
            rng = np.random.default_rng(p["rid"])
            reqs.append(Request(
                rid=p["rid"],
                prompt=rng.integers(0, SMALL.vocab, p["prompt_len"],
                                    dtype=np.int32),
                max_new_tokens=p["max_new_tokens"]))
        serve_state[key].serve_batch(reqs)
        print(f"  served {sum(len(r.tokens_out) for r in reqs)} tokens for "
              f"{len(reqs)} requests on vacated devices {list(key)}")
        return reqs

    launcher = LiveClusterLauncher(cluster, job_factory, serve_fn=serve_fn,
                                   steps_per_tick=2, target_steps=40)

    # ---- admit the hybrid workload through the service's front door -------
    queue = AdmissionQueue()
    m1 = queue.submit_training(n_max=3, runtime_s=40.0, n_min=1)
    m2 = queue.submit_training(n_max=3, runtime_s=40.0, n_min=1)
    r3 = queue.submit_rigid(nodes=2, runtime_s=40.0)
    od = queue.submit_inference(nodes=4, hold_s=8.0, submit_time=15.0,
                                notice_lead_s=5.0)
    queue.close()
    print(f"admitted: malleable {m1.jid},{m2.jid} rigid {r3.jid} "
          f"on-demand {od.jid} (4 nodes at t=15s, 5s notice)")

    # ---- the service paces the trace at 40 sim-s/wall-s -------------------
    svc = SchedulerService(
        ServiceConfig(n_nodes=len(devices), mechanism="CUA&SPAA", speed=40.0),
        launcher=launcher)
    rep = svc.run_live(queue)

    infos = launcher.infos
    print(f"\nservice drained in {rep.wall_s:.2f}s wall "
          f"({rep.n_decisions} decisions, p99={rep.latency['p99_ms']:.2f}ms)")
    print("decision log (deterministic fields):")
    for row in svc.log.rows:
        det = {k: v for k, v in row.items()
               if k not in ("wall", "mono", "latency_ms")}
        print("  ", det)

    # ---- drain the training tail on the live cluster ----------------------
    while any(i.status in ("running", "waiting") for i in infos.values()):
        cluster.step_all(5)
    steps = {jid: i.steps_done for jid, i in sorted(infos.items())}
    shrinks = sum(i.shrink_count for i in infos.values())
    preempts = sum(i.preempt_count for i in infos.values())
    print(f"\nall training done: steps={steps} "
          f"shrinks={shrinks} preempts={preempts}")
    print("\ncluster event log:")
    for e in cluster.log:
        print("  ", {k: v for k, v in e.items() if k != "t"})


if __name__ == "__main__":
    main()
