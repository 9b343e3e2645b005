#!/usr/bin/env python3
"""Run one cell of the live benchmark once, on the chips of this machine.

    python3 benchmarks/live/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Run from the root of a checkout.  The cell, its configuration and its
traffic mix are found by name through BENCHMARK.json.  The last line of
standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics), ``device`` and, traced, a
``breakdown``; its last key, ``checks``, holds every number compared with
the plain reference beside its limit, as do the last lines of standard
error.  Without a TPU, or with fewer chips than the cell needs, it exits
3 and prints no result.

``--control 1`` also reads the control (the reference in float8 put in
the program's place) and adds its numbers under ``control``; the
benchmark's own runs do not use it.
"""
import time

T_PROC = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from livebench import bench

    def log(msg):
        print(msg, file=sys.stderr, flush=True)
    try:
        out = bench.run_cell(args.workload, args.seed, args.seconds,
                             bool(args.trace), T_PROC,
                             control=bool(args.control), log=log)
    except bench.NoChip as e:
        log(f"run.py: {e}")
        return 3
    for name, (value, limit) in out["checks"].items():
        log(f"check {name}: {value!r} limit {limit!r}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
