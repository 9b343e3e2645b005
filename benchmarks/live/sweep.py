#!/usr/bin/env python3
"""Find the highest request rate the serving engine sustains on one chip.

    python3 benchmarks/live/sweep.py --workload <serving cell> \
        --rates 8,10,12 --seconds 15 --seed <n>

Builds the cell's serving engine once (weights from the seed, every shape
warmed), then offers the cell's request stream open loop at each rate for
``--seconds`` and prints one JSON line per rate: requests due, served and
left waiting, time to first token (p50, p95, and the mean of the first
and last quarter of requests, which drift apart when the backlog grows).
A one-off measurement that fixes the rate in the traffic files; the
benchmark's runs never call it.
"""
import time

T_PROC = time.monotonic()

import argparse  # noqa: E402
import copy  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    from livebench import bench, stack as stack_mod, traffic
    import numpy as np

    b = bench.benchmark()
    cell, cfg, mix = bench.find_cell(b, args.workload)
    ref = bench.load_module(HERE / "references" / f"{cfg['reference']}.py",
                            "ref")
    bench._compile_cache()
    import jax
    if jax.devices()[0].platform != "tpu":
        print("sweep.py: no TPU", file=sys.stderr)
        return 3
    bench.WORK.mkdir(exist_ok=True)
    run = stack_mod.Run(cell=args.workload, cfg=cfg, mix=mix,
                        sz=ref.sizes(cfg), seconds=args.seconds,
                        seed=args.seed, chips=1, rehearse=False,
                        work=bench.WORK)
    st = stack_mod.Stack(run)
    engine = st.new_engine(st.devices[:1])
    st.warm_serving(engine)
    print(json.dumps({"setup_s": time.monotonic() - T_PROC}), flush=True)
    for rate in [float(r) for r in args.rates.split(",")]:
        m = copy.deepcopy(mix)
        m["requests"]["rate_per_s"] = rate
        m["ondemand"] = {"nodes": 1, "due_s": None, "hold_s": None}
        st.sched = traffic.make(m, args.seed, args.seconds, st.cfg.vocab)
        run.requests = [{"rid": r.rid, "due_s": r.due_s,
                         "prompt_len": len(r.prompt), "prompt": r.prompt,
                         "new_tokens": r.new_tokens}
                        for r in st.sched.requests]
        run.batches = []
        t0 = time.monotonic()
        st.serve_stream(engine, t0, t0 + args.seconds)
        served = [r for r in run.requests if "first" in r]
        ttft = [r["first"] - (t0 + r["due_s"]) for r in served]
        q = max(1, len(ttft) // 4)
        print(json.dumps({
            "rate": rate, "due": len(run.requests), "served": len(served),
            "waiting": len(run.requests) - len(served),
            "ttft_p50": float(np.percentile(ttft, 50)) if ttft else None,
            "ttft_p95": float(np.percentile(ttft, 95)) if ttft else None,
            "ttft_first_q": float(np.mean(ttft[:q])) if ttft else None,
            "ttft_last_q": float(np.mean(ttft[-q:])) if ttft else None,
            "mean_batch": float(np.mean([x["B"] for x in run.batches]))
            if run.batches else None}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
