"""Shared by the rehearsal tests: one CPU run of a cell at the reduced
sizes, Pallas kernels interpreted."""
import sys
import time
from pathlib import Path

LIVE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(LIVE))

from livebench import bench, correct  # noqa: E402

SEED = 3_000_000_123          # above 32 signed bits, as the driver's are
SECONDS = {"live1.internvl2_1b.od_stream": 8.0}


def rehearse(cell: str, control: bool = False, entry=None) -> dict:
    """``entry``: a workloads entry for a cell BENCHMARK.json does not
    list (yet)."""
    from repro.kernels import ops
    b = bench.benchmark()
    if entry is not None:
        b["workloads"].append(entry)
    prev = ops.set_backend("interpret")
    try:
        return bench.run_cell(cell, SEED, SECONDS.get(cell, 4.0), False,
                              time.monotonic(), rehearse=True,
                              control=control, log=lambda m: None, bench=b)
    finally:
        ops.set_backend(prev)


def control_fails(out: dict, config: str = "live1.internvl2_1b") -> bool:
    """The control's numbers judged against the configuration's limits."""
    cfg = bench.load_config(bench.benchmark(), config)
    limits = dict(cfg["limits"], **cfg.get("rehearsal_limits", {}))
    ctl = {k: v for k, v in out["control"].items() if k in limits}
    ok, _ = correct.judge(ctl, limits)
    return not ok
