"""Cells, mixes and metrics are found by name: a new one takes new files
and a BENCHMARK.json entry, and no edit of a file that is there."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

LIVE = Path(__file__).resolve().parents[1]
ROOT = LIVE.parents[1]
sys.path.insert(0, str(LIVE))

from livebench import bench, traffic  # noqa: E402


def _copy(tmp: Path) -> Path:
    shutil.copy(ROOT / "BENCHMARK.json", tmp / "BENCHMARK.json")
    shutil.copytree(LIVE, tmp / "benchmarks" / "live",
                    ignore=shutil.ignore_patterns(".work", "__pycache__"))
    return tmp


def test_new_cell_mix_and_metric_from_new_files(tmp_path):
    root = _copy(tmp_path)
    live = root / "benchmarks" / "live"
    mix = json.loads((live / "traffic" / "serve_only.json").read_text())
    mix["name"] = "burst"
    mix["requests"]["rate_per_s"] = 3.0
    (live / "traffic" / "burst.json").write_text(json.dumps(mix))
    (live / "metrics" / "served_count.py").write_text(
        "def read(run):\n    return len(run.requests) or None\n")
    b = json.loads((root / "BENCHMARK.json").read_text())
    cell = "live1.internvl2_1b.burst"
    b["workloads"].append({"name": cell, "config": "live1.internvl2_1b",
                           "traffic": "burst", "chips": 1, "why": "test"})
    for m in b["end_to_end"]:
        if m["name"] == "od_ttft_p95_s":
            m["workloads"].append(cell)
    b["per_layer"].append({"name": "served_count", "unit": "requests",
                           "better": "higher", "source": "host_clock",
                           "layer": "serving", "moves": "od_ttft_p95_s"})
    (root / "BENCHMARK.json").write_text(json.dumps(b))

    b = bench.benchmark(root)
    entry, cfg, found = bench.find_cell(b, cell, root)
    assert entry["traffic"] == "burst" and found["requests"]["rate_per_s"] == 3
    assert cfg["name"] == "live1.internvl2_1b"
    s = traffic.make(found, 5, b["run_seconds"], cfg["vocab_size"])
    assert len(s.requests) == round(3.0 * s.stream_s)
    e2e = [m["name"] for m in bench.cell_metrics(b, cell, traced=False)]
    assert e2e == ["od_ttft_p95_s", "setup_s"]
    layer = [m["name"] for m in bench.cell_metrics(b, cell, traced=True)]
    assert layer == ["served_count"]
    run = SimpleNamespace(requests=[{}, {}])
    assert bench.read_metric("served_count", run, root) == 2.0
    # the new metric has no workloads list, so it reaches every cell that
    # reports od_ttft_p95_s, the existing ones too
    assert "served_count" in [m["name"] for m in bench.cell_metrics(
        b, "live1.internvl2_1b.serve_only", traced=True)]


def _cli(cwd: Path, env_extra: dict):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="", **env_extra)
    return subprocess.run(
        [sys.executable, "benchmarks/live/run.py", "--workload",
         "live1.internvl2_1b.serve_only", "--seed", "4294967311",
         "--seconds", "40", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_without_a_tpu_no_result_and_nonzero():
    p = _cli(ROOT, {})
    assert p.returncode == 3
    assert p.stdout == ""
    assert "TPU" in p.stderr


def test_alone_in_a_directory_fails(tmp_path):
    p = _cli(_copy(tmp_path), {})
    assert p.returncode != 0
    assert p.stdout == ""
