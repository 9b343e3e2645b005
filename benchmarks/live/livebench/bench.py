"""One run of one cell: set-up, the measured window, the metrics, and the
comparison with the plain reference that decides ``correct``.

Everything that belongs to one configuration, traffic mix or metric is
found by name: ``configs/<config>.json``, ``traffic/<mix>.json``,
``metrics/<metric>.py`` and ``references/<reference>.py`` under this
benchmark's directory, as ``BENCHMARK.json`` names them.
"""
from __future__ import annotations

import copy
import gc
import importlib.util
import statistics
import json
import os
import shutil
import tempfile
import time
from pathlib import Path
from typing import Optional

HERE = Path(__file__).resolve().parents[1]          # benchmarks/live
ROOT = HERE.parents[1]                              # the checkout
WORK = HERE / ".work"

#: exact comparisons: their limit is 0
EXACT = {"decisions": 0, "restore": 0, "init": 0}


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def benchmark(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def load_config(bench: dict, name: str, root: Path = ROOT) -> dict:
    conf = {c["name"]: c for c in bench["configs"]}[name]
    with open(root / conf["file"]) as f:
        return json.load(f)


def find_cell(bench: dict, name: str, root: Path = ROOT):
    """(cell entry, configuration file, mix file) of a cell, by name."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    cfg = load_config(bench, cell["config"], root)
    with open(root / "benchmarks" / "live" / "traffic"
              / f"{cell['traffic']}.json") as f:
        mix = json.load(f)
    return cell, cfg, mix


def cell_metrics(bench: dict, cell: str, traced: bool) -> list:
    """The metric entries a cell reports: its end-to-end metrics, or with
    tracing its per-layer ones (listed for it, or, without a list, those
    whose end-to-end metric it reports)."""
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or cell in m["workloads"]]
    if not traced:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in names)]


def read_metric(name: str, run, root: Path = ROOT) -> Optional[float]:
    mod = load_module(root / "benchmarks" / "live" / "metrics" / f"{name}.py",
                      f"metric_{name}")
    v = mod.read(run)
    return None if v is None else float(v)


def rehearsal_mix(mix: dict, scale: float) -> dict:
    """The mix at the CPU rehearsal's sizes and time scale."""
    from .stack import REHEARSAL_REQUESTS
    mix = copy.deepcopy(mix)
    od = mix.get("ondemand")
    if od:
        for k in ("due_s", "hold_s"):
            if od[k] is not None:
                od[k] *= scale
    if mix.get("requests"):
        mix["requests"].update(REHEARSAL_REQUESTS)
    return mix


def _compile_cache() -> None:
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          str(ROOT / ".jax_cache"))
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      os.environ["JAX_COMPILATION_CACHE_DIR"])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def run_cell(name: str, seed: int, seconds: float, trace: bool,
             t_proc: float, rehearse: bool = False,
             control: bool = False, log=print,
             bench: Optional[dict] = None) -> dict:
    """One run of cell ``name``; returns the result line as a dict.
    ``bench`` stands in for BENCHMARK.json (a test may add a cell)."""
    bench = bench or benchmark()
    cell, cfg, mix = find_cell(bench, name)
    ref = load_module(HERE / "references" / f"{cfg['reference']}.py",
                      f"reference_{cfg['reference']}")
    sz = ref.sizes(cfg)
    if not rehearse:
        _compile_cache()
    import jax

    from . import stack as stack_mod, tracing

    devices = jax.devices()
    peak = None
    if not rehearse:
        if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
            raise NoChip(f"JAX found {len(devices)} {devices[0].platform} "
                         f"device(s); {name} needs {cell['chips']} TPU chips")
        with open(HERE / "peaks.json") as f:
            peaks = json.load(f)
        if devices[0].device_kind not in peaks:
            raise KeyError(f"no peaks for {devices[0].device_kind!r} in "
                           "peaks.json")
        peak = peaks[devices[0].device_kind]
    trainer = dict(cfg["trainer"]) if mix.get("trainer") else {}
    if rehearse:
        sz = stack_mod.rehearsal_sizes(sz)
        mix = rehearsal_mix(mix, seconds / bench["run_seconds"])
        if trainer:
            trainer.update(stack_mod.REHEARSAL_TRAINER)
    # a rehearsal may run beside others (test workers): a directory of its own
    work = Path(tempfile.mkdtemp(prefix="livebench-")) if rehearse else WORK
    work.mkdir(exist_ok=True)
    run = stack_mod.Run(cell=name, cfg=cfg, mix=mix, sz=sz, seconds=seconds,
                        seed=seed, chips=cell["chips"], rehearse=rehearse,
                        work=work, trainer=trainer, peak=peak,
                        spans=tracing.Spans(traced=trace), t_proc=t_proc)
    try:
        return _measure(bench, ref, run, devices, trace, control, log)
    finally:
        if rehearse:
            shutil.rmtree(work, ignore_errors=True)


def _full_collections(pauses: list):
    """A gc callback that records (start, seconds) of each full
    collection."""
    t = [0.0]

    def cb(phase, info):
        if info["generation"] == 2:
            if phase == "start":
                t[0] = time.monotonic()
            else:
                pauses.append((t[0], time.monotonic() - t[0]))
    return cb


def stalls(run, pauses) -> list:
    """Lines for standard error on where the window lost time: its
    longest training steps, its slowest decode batches and longest waits
    between batches, and full collections, each at its offset in the
    window."""
    t0, lines = run.t0, []
    steps = [s for s in run.train.steps if t0 <= s.t0 < run.t1]
    if steps:
        med = statistics.median(s.t1 - s.t0 for s in steps)
        worst = sorted(steps, key=lambda s: s.t0 - s.t1)[:3]
        lines.append(f"window steps: {len(steps)}, median {med:.4f} s; "
                     "longest " + ", ".join(
                         f"{s.t1 - s.t0:.4f} s at {s.t0 - t0:.2f} s"
                         for s in worst))
    bs = [b for b in run.batches if b["n_decode"] > 0]
    if bs:
        per = [(b["t_done"] - b["t_first"]) / b["n_decode"] for b in bs]
        med = statistics.median(per)
        worst = sorted(zip(per, bs), key=lambda x: -x[0])[:3]
        lines.append(f"batches: {len(bs)}, median {med * 1e3:.3f} ms a "
                     "decode step; slowest " + ", ".join(
                         f"{p * 1e3:.3f} ms (B {b['B']}, S {b['S']}) at "
                         f"{b['t_first'] - t0:.2f} s" for p, b in worst))
        waits = sorted(((b["t_start"] - max(a["t_done"], b["t_due"]),
                         b["t_start"])
                        for a, b in zip(run.batches, run.batches[1:])),
                       reverse=True)[:3]
        lines.append("longest host gaps before a due batch: " + ", ".join(
            f"{w * 1e3:.1f} ms at {t - t0:.2f} s" for w, t in waits))
    inside = [(t, d) for t, d in pauses if t0 <= t < run.t1]
    lines.append(f"full collections in the window: {len(inside)}"
                 + "".join(f", {d * 1e3:.1f} ms at {t - t0:.2f} s"
                           for t, d in inside))
    return lines


def _measure(bench, ref, run, devices, trace, control, log) -> dict:
    import jax

    from . import correct, stack as stack_mod, tracing
    name, sz, mix, trainer = run.cell, run.sz, run.mix, run.trainer
    rehearse = run.rehearse
    st = stack_mod.Stack(run)
    st.setup()
    # set-up's garbage is collected in set-up, not by a full collection
    # that falls somewhere in the window
    gc.collect()
    trace_dir = run.work / "trace"
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir))
    pauses = []
    gc.callbacks.append(_full_collections(pauses))
    try:
        st.window()
        st.drain()
    finally:
        gc.callbacks.pop()
        if trace:
            jax.profiler.stop_trace()
    for line in stalls(run, pauses):
        log(line)
    if trace:
        dev, host = tracing.events(tracing.load(str(trace_dir)))
        run.trace = tracing.reduce(dev, host, run.seconds)
        shutil.rmtree(trace_dir, ignore_errors=True)

    used = devices[:run.chips]
    stats = [d.memory_stats() or {} for d in used]
    mem_peak = max((s.get("peak_bytes_in_use", 0) for s in stats), default=0)
    metrics = {}
    for m in cell_metrics(bench, name, trace):
        v = read_metric(m["name"], run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    n_steps = sum(1 for s in run.train.steps if run.t0 <= s.t0 < run.t1)
    failed = sum(1 for r in run.requests if "tokens" not in r)
    attempted = len(run.requests) + n_steps

    t_ref = time.monotonic()
    st.free()
    numbers, ctl = {"decisions": correct.decisions(st)}, {}
    r = correct.restore(run)
    if r is not None:
        numbers["restore"] = r
    if trainer:
        n, c = correct.training(st, ref, sz, control, log)
        numbers.update(n)
        ctl.update(c)
    if mix.get("requests"):
        n, c = correct.serving(st, ref, sz, control)
        numbers.update(n)
        ctl.update(c)
    limits = dict(run.cfg.get("limits", {}), **EXACT)
    if rehearse:
        limits.update(run.cfg.get("rehearsal_limits", {}))
    ok, checks = correct.judge(numbers, limits)
    log(f"reference and checks took {time.monotonic() - t_ref:.1f} s")

    dev0 = devices[0]
    out = {"correct": ok, "attempted": attempted, "failed": failed,
           "metrics": metrics if not rehearse else {},
           "device": {"platform": dev0.platform, "kind": dev0.device_kind,
                      "count": len(devices),
                      "memory_peak_bytes": int(mem_peak)}}
    if trace and run.trace is not None:
        out["device"]["busy_s"] = run.trace["busy_s"]
        out["device"]["window_s"] = run.trace["window_s"]
        out["breakdown"] = {"device_ops": run.trace["device_ops"],
                            "idle_gaps": run.trace["idle_gaps"]}
    if rehearse:
        out["rehearsal_values"] = {k: v["value"] for k, v in metrics.items()}
    if control:
        out["control"] = ctl
    out["checks"] = checks
    return out
