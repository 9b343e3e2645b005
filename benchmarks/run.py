"""Benchmark harness — one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--quick|--full] [--only NAME]

Prints ``name,us_per_call,derived`` CSV rows per the harness contract and
writes the full row dicts to results/bench/*.json.  Sections:

  table2      baseline FCFS/EASY                    (paper Table II)
  fig6        6 mechanisms x W1-W5                  (paper Figure 6)
  fig7        checkpoint frequency sweep            (paper Figure 7)
  scenarios   scenario presets x mechanisms         (docs/workloads.md)
  obs10       decision latency                      (paper Obs 10)
  dispatch    policy-API overhead vs seed           (BENCH_scheduler.json)
  profile     cProfile top-frame table of the      (results/bench/
              month-dense replay hot loop           profile.json; CI artifact)
  scale       engine wall clock 600 -> 6k -> 50k,   (results/bench/scale.json
              streaming==materialized sha gates,     + BENCH_scheduler.json)
              the batch-rounds fidelity-vs-speed
              curve (+ digest gate at rounds=0),
              the 1M-job multi-year rung, and the
              full-year streaming rung with
              per-mode peak RSS
  service     shadow scheduler service replay:      (results/bench/
              fidelity digest vs offline simulator   service.json;
              + decision-latency SLO gates           docs/service.md)
  faults      chaos gate: SIGKILL-style crash ->    (results/bench/
              recover -> digest == uninterrupted,    faults.json;
              + MTBF-sweep determinism + goodput     docs/faults.md)
  campaign    mini trace-zoo campaign run twice:    (results/bench/
              cells/sec + peak RSS + byte-identical  campaign.json;
              artifact gate                          docs/campaigns.md)
  device      sweeps-on-device: a >= 600-cell       (results/bench/
              mechanism grid replayed as ONE jitted  device_sweep.json;
              device program, parity-gated per cell  docs/performance.md)
              against the numpy engine
  roofline    per (arch x shape) roofline terms     (EXPERIMENTS §Roofline)

Scale tiers: --quick runs (600, 2k) with the paired pre-PR baseline at
600 jobs; the default adds the 6k steady-load and month-dense pairs
(the latter gates the >= 10x speedup acceptance); --full adds the
50k-job Theta-scale sweep.  Every mode appends the streaming-identity
sha rows, the batch-rounds fidelity curve (--quick probes a single
round size on the small tier; other modes run the full curve plus the
1M-job multi-year rung) and a full-year streaming replay
(benchmarks/bench_scale: 110k jobs/365d, or a density-preserving 20k
"quick year" under --quick) with per-mode peak RSS.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

from repro.compile_cache import enable_compile_cache

from . import (bench_campaign, bench_decision, bench_faults, bench_profile,
               bench_roofline, bench_scale, bench_scheduler, bench_service)

OUT = "results/bench"


def _provenance(mode: str, seeds, n_jobs: int) -> dict:
    """Stamped into every artifact so quick CI output cannot be mistaken
    for paper-scale reference results."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    try:
        commit = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"], cwd=root,
            capture_output=True, text=True, check=True, timeout=10
        ).stdout.strip()
        dirty = subprocess.run(
            ["git", "status", "--porcelain", "--untracked-files=no"],
            cwd=root, capture_output=True, text=True, check=True,
            timeout=10).stdout.strip()
        if dirty:
            commit += "+dirty"
    except (OSError, subprocess.SubprocessError):
        commit = "unknown"
    return {"mode": mode, "seeds": list(seeds), "n_jobs": n_jobs,
            "commit": commit,
            "generated": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())}


def _emit(section: str, rows, t0: float, provenance: dict) -> None:
    os.makedirs(OUT, exist_ok=True)
    if isinstance(rows, dict):
        rows = [rows]
    with open(os.path.join(OUT, f"{section}.json"), "w") as f:
        json.dump({"provenance": provenance, "rows": rows}, f, indent=1,
                  default=str)
    for r in rows:
        us = r.get("us_per_call")
        if us is None:
            us = round(r.get("seconds", time.perf_counter() - t0) * 1e6, 1)
        derived = r.get("derived") or ",".join(
            f"{k}={v:.4g}" for k, v in r.items()
            if isinstance(v, (int, float)) and k not in
            ("seconds", "us_per_call"))
        print(f"{r.get('name', section)},{us},{derived}")


def main(argv=None) -> int:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small workloads (CI)")
    ap.add_argument("--full", action="store_true",
                    help="paper-scale averaging (10 traces)")
    ap.add_argument("--only", default=None)
    args = ap.parse_args(argv)
    mode = "quick" if args.quick else "full" if args.full else "default"
    seeds = (0,) if args.quick else tuple(range(10)) if args.full else (0, 1, 2)
    n_jobs = 300 if args.quick else 900 if args.full else 600
    prov = _provenance(mode, seeds, n_jobs)

    want = lambda s: args.only is None or args.only == s
    failures = []

    base = None
    mech_rows = None
    if want("table2"):
        t0 = time.perf_counter()
        base = bench_scheduler.bench_baseline(seeds=seeds, n_jobs=n_jobs)
        _emit("table2", base, t0, prov)
    if want("fig6"):
        t0 = time.perf_counter()
        mech_rows = bench_scheduler.bench_mechanisms(seeds=seeds,
                                                     n_jobs=n_jobs)
        _emit("fig6", mech_rows, t0, prov)
    if base is not None and mech_rows is not None:
        fails = bench_scheduler.validate_observations(base, mech_rows)
        for f in fails:
            print(f"VALIDATION-FAIL,{f}", file=sys.stderr)
        failures += fails
        if not fails:
            print("validate_observations,0,all paper observations hold")
    if want("fig7"):
        t0 = time.perf_counter()
        rows = bench_scheduler.bench_checkpoint(
            seeds=seeds[:2], n_jobs=n_jobs)
        _emit("fig7", rows, t0, dict(prov, seeds=list(seeds[:2])))
    if want("scenarios"):
        t0 = time.perf_counter()
        trace = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tests", "data", "sample.swf")
        rows = bench_scheduler.bench_scenarios(
            seeds=seeds[:2], n_jobs=n_jobs,
            swf_trace=trace if os.path.exists(trace) else None)
        _emit("scenarios", rows, t0, dict(prov, seeds=list(seeds[:2])))
    if want("obs10"):
        t0 = time.perf_counter()
        rows = bench_decision.bench_decision_kernels()
        e2e = bench_decision.bench_decision_e2e()
        rows.append(e2e)
        # e2e always runs at full-system scale regardless of --quick/--full
        _emit("obs10", rows, t0,
              dict(prov, seeds=list(bench_decision.E2E_SEEDS),
                   n_jobs=bench_decision.E2E_N_JOBS,
                   note="seeds/n_jobs describe od_arrival_decision; kernel "
                        "rows are synthetic (scale in their derived field)"))
        if not e2e["within_bound"]:
            fail = (f"Obs10: od_arrival_decision p99 {e2e['p99_us']:.0f}us "
                    f"> bound {e2e['bound_us']:.0f}us")
            print(f"VALIDATION-FAIL,{fail}", file=sys.stderr)
            failures.append(fail)
    if want("dispatch"):
        t0 = time.perf_counter()
        # always the seed-0 600-job trace, independent of --quick/--full
        row = bench_scheduler.bench_policy_dispatch()
        _emit("dispatch", row, t0,
              dict(prov, seeds=[0], n_jobs=row["n_jobs"]))
        if row.get("within_budget") is False:
            fail = (f"dispatch: overhead {row['overhead_pct']:+.1f}% "
                    f"> budget {row['budget_pct']:.0f}%")
            print(f"VALIDATION-FAIL,{fail}", file=sys.stderr)
            failures.append(fail)
    if want("profile"):
        t0 = time.perf_counter()
        # quick profiles a smaller month-dense slice; ranking is what
        # matters and it is stable across the scale-down
        rows = bench_profile.bench_profile(
            n_jobs=1500 if args.quick else 6000,
            horizon_days=7.5 if args.quick else 30.0)
        _emit("profile", rows, t0, dict(prov, seeds=[0],
                                        n_jobs=rows[0]["n_jobs"]))
    if want("scale"):
        t0 = time.perf_counter()
        if args.quick:
            scales = ((600, 21.0), (2000, 70.0))
            baseline_max = 600
        elif args.full:
            scales = ((600, 21.0), (6000, 210.0), (6000, 30.0),
                      (50000, 1750.0))
            baseline_max = 6000
        else:
            scales = ((600, 21.0), (6000, 210.0), (6000, 30.0))
            baseline_max = 6000
        rows = bench_scheduler.bench_scale(scales=scales,
                                           baseline_max_jobs=baseline_max)
        # streaming == materialized identity tiers + the full-year rung
        # (scaled-down 20k "quick year" under --quick; see bench_scale)
        identity_tiers = ((600, 21.0),) if args.quick \
            else ((600, 21.0), (6000, 210.0))
        rows += bench_scale.bench_stream_identity(tiers=identity_tiers)
        # batch-rounds fidelity-vs-speed curve (quick: one round size on
        # the small tier — digest + drift gates only; else the full
        # >= 5-point curve on the month-dense scheduling-bound tier)
        if args.quick:
            batch_rows = bench_scale.bench_batch_fidelity(
                n_jobs=600, horizon_days=21.0, round_sizes=(0.0, 900.0),
                repeats=1)
        else:
            batch_rows = bench_scale.bench_batch_fidelity()
        rows += batch_rows
        if not args.quick:
            rows += bench_scale.bench_million()
        rows += bench_scale.bench_full_year(
            n_jobs=20_000 if args.quick else bench_scale.YEAR_N_JOBS)
        _emit("scale", rows, t0,
              dict(prov, seeds=[0],
                   note="n_jobs varies per row; see each row"))
        for r in rows:
            if r.get("jobs_match") is False:
                fail = (f"scale: {r['name']} streamed job trace diverges "
                        "from the materialized trace")
                print(f"VALIDATION-FAIL,{fail}", file=sys.stderr)
                failures.append(fail)
            if r.get("mode") == "stream" and r.get("n_completed") is not None \
                    and r["n_completed"] < r["n_jobs"]:
                fail = (f"scale: {r['name']} completed only "
                        f"{r['n_completed']}/{r['n_jobs']} jobs")
                print(f"VALIDATION-FAIL,{fail}", file=sys.stderr)
                failures.append(fail)
        for r in rows:
            if r.get("records_match") is False:
                fail = (f"scale: {r['name']} records diverge from the "
                        f"paired reference run")
                print(f"VALIDATION-FAIL,{fail}", file=sys.stderr)
                failures.append(fail)
            if r.get("decision_p99_ms") is not None \
                    and not r["decision_within_bound"]:
                fail = (f"scale: {r['name']} decision p99 "
                        f"{r['decision_p99_ms']}ms > 10ms bound")
                print(f"VALIDATION-FAIL,{fail}", file=sys.stderr)
                failures.append(fail)
            # the acceptance gate: month-dense 6k replay >= 10x
            if r["name"].startswith("scale_") and "speedup" in r \
                    and r["n_jobs"] >= 6000 \
                    and r["horizon_days"] <= 31.0 \
                    and r["speedup"] < bench_scheduler.SCALE_SPEEDUP_TARGET:
                fail = (f"scale: {r['name']} speedup {r['speedup']}x < "
                        f"{bench_scheduler.SCALE_SPEEDUP_TARGET}x target")
                print(f"VALIDATION-FAIL,{fail}", file=sys.stderr)
                failures.append(fail)
        # batch-rounds gates: the rounds=0 digest gate rides the
        # records_match loop above; here the fidelity/speed acceptance
        curve = [r for r in batch_rows if r["batch_rounds"] > 0]
        drifted = [r for r in curve
                   if abs(r["od_drift_pct"]) > bench_scale.BATCH_OD_DRIFT_PCT]
        if args.quick:
            # CI smoke: bounded od drift at the single probed round size
            for r in drifted:
                fail = (f"scale: {r['name']} od drift "
                        f"{r['od_drift_pct']:+.2f}% > "
                        f"{bench_scale.BATCH_OD_DRIFT_PCT:.0f}% bound")
                print(f"VALIDATION-FAIL,{fail}", file=sys.stderr)
                failures.append(fail)
        elif any("speedup" in r for r in curve) and not any(
                r.get("speedup", 0.0) >= bench_scale.BATCH_SPEEDUP_TARGET
                and abs(r["od_drift_pct"])
                <= bench_scale.BATCH_OD_DRIFT_PCT for r in curve):
            # "speedup" is the scale_* rows' convention: measured vs the
            # pre-PR engine (hot loop + batching combined).  Like the
            # >= 10x scale gate, this one can only run where git history
            # is available to rebuild that baseline.
            fail = (f"scale: no batch round size reaches "
                    f"{bench_scale.BATCH_SPEEDUP_TARGET:.0f}x speedup "
                    f"(vs pre-engine) at "
                    f"<= {bench_scale.BATCH_OD_DRIFT_PCT:.0f}% od drift")
            print(f"VALIDATION-FAIL,{fail}", file=sys.stderr)
            failures.append(fail)
    if want("service"):
        t0 = time.perf_counter()
        svc_cells = bench_service.CELLS[:1] if args.quick \
            else bench_service.CELLS
        svc_jobs = 150 if args.quick else 300
        rows = bench_service.bench_service(cells=svc_cells, n_jobs=svc_jobs)
        _emit("service", rows, t0,
              dict(prov, seeds=[0], n_jobs=svc_jobs))
        for r in rows:
            if not r["fidelity_ok"]:
                fail = (f"service: {r['name']} shadow decisions diverge "
                        "from the offline simulator (digests_match="
                        f"{r['digests_match']}, records_match="
                        f"{r['records_match']})")
                print(f"VALIDATION-FAIL,{fail}", file=sys.stderr)
                failures.append(fail)
            if not r["slo_ok"]:
                fail = (f"service: {r['name']} decision p99 "
                        f"{r['decision_p99_ms']}ms > "
                        f"{r['decision_bound_ms']}ms bound")
                print(f"VALIDATION-FAIL,{fail}", file=sys.stderr)
                failures.append(fail)
    if want("faults"):
        t0 = time.perf_counter()
        rows = bench_faults.bench_faults(
            n_jobs=100 if args.quick else 150, quick=args.quick)
        _emit("faults", rows, t0,
              dict(prov, seeds=[2, 3],
                   n_jobs=100 if args.quick else 150,
                   note="recover rows use seed 3, mtbf rows seed 2"))
        for r in rows:
            if r.get("digest_match") is False:
                fail = (f"faults: {r['name']} recovered decision stream "
                        "diverges from the uninterrupted run")
                print(f"VALIDATION-FAIL,{fail}", file=sys.stderr)
                failures.append(fail)
            if r.get("deterministic") is False:
                fail = (f"faults: {r['name']} fault-injected cell is not "
                        "job-for-job reproducible")
                print(f"VALIDATION-FAIL,{fail}", file=sys.stderr)
                failures.append(fail)
    if want("campaign"):
        t0 = time.perf_counter()
        try:
            rows = bench_campaign.bench_campaign()
        except ValueError as e:  # CampaignSpecError / zoo integrity
            fail = f"campaign: spec/zoo validation failed: {e}"
            print(f"VALIDATION-FAIL,{fail}", file=sys.stderr)
            failures.append(fail)
            rows = []
        if rows:
            # the mini campaign runs at fixed fixture scale; seeds and
            # job counts come from the spec, not --quick/--full
            _emit("campaign", rows, t0,
                  dict(prov, seeds="per-spec", n_jobs="per-spec",
                       note="spec-defined scale; see each row"))
        for r in rows:
            if not r["deterministic"]:
                fail = (f"campaign: {r['name']} artifacts differ between "
                        "two identical runs (rows/report must be "
                        "byte-deterministic)")
                print(f"VALIDATION-FAIL,{fail}", file=sys.stderr)
                failures.append(fail)
    if want("device"):
        # This section initialises a JAX backend, which then holds the
        # process's devices: it must run in a process that forks nothing
        # afterwards (a forked worker cannot use the parent's chip).
        # jax is optional in lightweight CI: skip (with a visible row)
        # rather than fail when the device backend is absent
        try:
            import jax  # noqa: F401
            have_jax = True
        except ImportError:
            have_jax = False
        if have_jax:
            from . import bench_device_sweep
            t0 = time.perf_counter()
            rows = bench_device_sweep.bench_device_sweep(quick=args.quick)
            _emit("device_sweep", rows, t0,
                  dict(prov, seeds="per-row", n_jobs="per-row",
                       note="grid tier fixed per mode; see each row"))
            for r in rows:
                if not r["parity_ok"]:
                    fail = (f"device: {r['name']} {r['n_mismatches']} device "
                            "decision(s) diverge from the numpy engine "
                            f"(sample: {r['mismatch_sample'][:1]})")
                    print(f"VALIDATION-FAIL,{fail}", file=sys.stderr)
                    failures.append(fail)
                if not r["within_bound"]:
                    fail = (f"device: {r['name']} {r['us_per_call']}us/call "
                            f"> bound {r['bound_us']}us (program likely "
                            "fragmented or retracing)")
                    print(f"VALIDATION-FAIL,{fail}", file=sys.stderr)
                    failures.append(fail)
        else:
            print("device_sweep,0,skipped: jax not installed")
    if want("roofline"):
        t0 = time.perf_counter()
        rows = bench_roofline.rows(multi_pod=False)
        if rows:
            _emit("roofline", rows, t0, prov)
        else:
            print("roofline,0,no dry-run artifacts found (run "
                  "repro.launch.dryrun first)")
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
