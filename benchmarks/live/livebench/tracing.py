"""Host spans and the reduction of a profiler trace to device metrics.

Spans are recorded by the harness around its calls into each layer of
the program, on the host clock (``time.monotonic``).  In a traced run
each span is also a ``jax.profiler.TraceAnnotation`` named ``bench:<name>``,
so it lands in the profiler's trace on the device's clock, where the idle
gaps of the device are attributed to what the host was doing.

The reduction reads the ``.xplane.pb`` file with
``jax.profiler.ProfileData``: device planes are those named
``/device:TPU:<n>``, their operations the events of the line
``XLA Ops``, named by the HLO instruction (``flash_decode.3`` for the
text ``%flash_decode.3 = ...``); loops and calls, which contain other
operations, are left out.  Host spans are events whose name starts with
``bench:``.
"""
from __future__ import annotations

import contextlib
import glob
import os
import threading
import time
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

PREFIX = "bench:"
#: operations that contain others: their time is their body's
CONTAINERS = ("while.", "conditional.", "call.")


class Spans:
    """Named host-clock intervals, safe to record from several threads."""

    def __init__(self, traced: bool = False):
        self.traced = traced
        self.rows: List[Tuple[str, float, float]] = []
        self._lock = threading.Lock()

    @contextlib.contextmanager
    def span(self, name: str):
        ann = None
        if self.traced:
            import jax
            ann = jax.profiler.TraceAnnotation(PREFIX + name)
            ann.__enter__()
        t0 = time.monotonic()
        try:
            yield
        finally:
            t1 = time.monotonic()
            if ann is not None:
                ann.__exit__(None, None, None)
            with self._lock:
                self.rows.append((name, t0, t1))

    def of(self, name: str) -> List[Tuple[float, float]]:
        return [(a, b) for n, a, b in self.rows if n == name]


# ------------------------------------------------------------- reduction
def load(trace_dir: str):
    """The ProfileData of the newest trace under ``trace_dir``."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return ProfileData.from_file(files[-1])


def events(pd) -> Tuple[Dict[str, List[tuple]], List[tuple]]:
    """(device ops per device plane, host bench spans).  Each event is
    (name, start_ns, end_ns)."""
    dev: Dict[str, List[tuple]] = {}
    host: List[tuple] = []
    for plane in pd.planes:
        is_dev = plane.name.startswith("/device:TPU:") \
            and "SparseCore" not in plane.name
        for line in plane.lines:
            if is_dev and line.name != "XLA Ops":
                continue
            for ev in line.events:
                start = ev.start_ns
                if is_dev:
                    name = op_name(ev.name)
                    if not name.startswith(CONTAINERS):
                        dev.setdefault(plane.name, []).append(
                            (name, start, start + ev.duration_ns))
                elif ev.name.startswith(PREFIX):
                    host.append((ev.name[len(PREFIX):], start,
                                 start + ev.duration_ns))
    return dev, host


def op_name(text: str) -> str:
    """``%name.3 = type op(...)`` -> ``name.3``."""
    return text.split(" = ", 1)[0].strip().lstrip("%")


def union(intervals: List[tuple]) -> List[Tuple[float, float]]:
    """Merged (start, end) of overlapping intervals."""
    out: List[List[float]] = []
    for a, b in sorted((i[-2], i[-1]) for i in intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals, lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def reduce(dev: Dict[str, List[tuple]], host: List[tuple],
           seconds: Optional[float] = None) -> dict:
    """Busy seconds (the union of device operations, averaged over the
    devices), the window's length, the ten longest device operations by
    total time, and the ten longest idle gaps, each named by the innermost
    host span around its middle and when it began (``checkpoint@5.1s``).
    The window opens with the host span ``window`` and lasts ``seconds``
    (or as long as that span), or ends with the last device event the
    trace kept, if that comes first."""
    ws = [s for s in host if s[0] == "window"]
    if ws:
        lo = ws[0][1]
        window = (lo, lo + seconds * 1e9 if seconds else ws[0][2])
    else:
        allev = [e for evs in dev.values() for e in evs]
        window = (min(e[1] for e in allev), max(e[2] for e in allev))
    lo, hi = window
    # the profiler keeps a bounded number of device events: where it
    # stopped early, the traced window ends with the last one it kept
    ends = [max(e[2] for e in evs) for evs in dev.values() if evs]
    if ends:
        hi = min(hi, max(ends))
    busy, gaps = [], []
    per_op: Dict[str, float] = defaultdict(float)
    for evs in dev.values():
        merged = clip(union(evs), lo, hi)
        busy.append(sum(b - a for a, b in merged))
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        gaps += [(edges[i], edges[i + 1])
                 for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]
        for name, a, b in evs:
            if b > lo and a < hi:
                per_op[name] += (min(b, hi) - max(a, lo)) / len(dev)
    named = []
    for a, b in gaps:
        mid, best, width = (a + b) / 2, "none", None
        for name, s0, s1 in host:
            if name != "window" and s0 <= mid <= s1 and \
                    (width is None or s1 - s0 < width):
                best, width = name, s1 - s0
        named.append((f"{best}@{(a - lo) / 1e9:.1f}s", (b - a) / 1e9))
    named.sort(key=lambda x: -x[1])
    top_ops = sorted(per_op.items(), key=lambda x: -x[1])[:10]
    return {"busy_s": (sum(busy) / len(busy) / 1e9) if busy else 0.0,
            "window_s": (hi - lo) / 1e9,
            "device_ops": [[n, s / 1e9] for n, s in top_ops],
            "idle_gaps": [[n, s] for n, s in named[:10]],
            "op_totals": _totals(dev)}


def _totals(dev) -> Dict[str, Tuple[float, float]]:
    """(seconds, calls) of each operation over the whole trace, per
    device."""
    t: Dict[str, List[float]] = defaultdict(lambda: [0.0, 0.0])
    for evs in dev.values():
        for name, a, b in evs:
            t[name][0] += (b - a) / 1e9 / len(dev)
            t[name][1] += 1.0 / len(dev)
    return {n: (v[0], v[1]) for n, v in t.items()}


def kernel_seconds(red: dict, kernel: str) -> Tuple[float, float]:
    """(device seconds, calls) over the whole trace of the operations
    named ``kernel`` (``kernel.<n>``), per device."""
    hits = [v for n, v in red["op_totals"].items()
            if n == kernel or n.startswith(kernel + ".")]
    return sum(v[0] for v in hits), sum(v[1] for v in hits)
