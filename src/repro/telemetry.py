"""Spans and counters of the live path, kept in memory.

Every layer of the live path (serving, training, the elastic operations,
the cluster, the service) records what it does as *spans*: named
intervals on the host clock, ``time.monotonic()`` (the clock of
``Request.first_token_at`` and ``LiveCluster.log``), each with the span
open around it on the same thread as its parent.  Spans of one request
or job share a ``key``: the serving batch's number, or the job's id.  A
span's *self time* is its duration less the part of it that its child
spans cover, so the blocking path of a step splits into parts that add
up.  *Counters* are plain sums.

Once jax is imported, each span is also a ``jax.profiler.TraceAnnotation``
of the same name, so a profiler trace shows it on the host plane, on the
device trace's clock.  A ``jax.monitoring`` listener, installed with the
first span recorded after jax is imported, turns each backend compile
into a ``jax.compile`` row under the span open on the compiling thread
(keyed by the function's name), so a compile inside a step is taken out
of the step's self time and shows apart.

Always on: rows go into a bounded ring held by a :class:`Recorder`; no
thread, file or setting.  A span costs about 2 us.  This module imports
nothing from jax, so jax-free code (``repro.runtime.cluster``, the
service) records spans too.

    from repro import telemetry
    with telemetry.span("train.step", key=jid, n=tokens):
        ...
    telemetry.summary()   # per name: count, total, self-time p50/p99
"""
from __future__ import annotations

import itertools
import math
import statistics
import sys
import threading
import time
from collections import defaultdict, deque
from typing import Dict, List, NamedTuple, Optional

#: rows the ring keeps: over four 40 s serve_only windows (about 11 k
#: decode steps of three rows each) with their set-up
MAXLEN = 1 << 18

_COMPILE = "/jax/core/compile/backend_compile_duration"
_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_retrieval_time_sec"


class Row(NamedTuple):
    """One recorded interval.  ``parent`` is the ``seq`` of the span open
    around it on its thread (None at the top); ``self_s`` is its duration
    less what its children cover."""

    seq: int
    name: str
    t0: float
    t1: float
    parent: Optional[int]
    key: object
    n: float
    self_s: float


_seq = itertools.count()
_local = threading.local()          # .stack: the thread's open spans
_annotation = None                  # jax.profiler.TraceAnnotation, once seen
_jax_lock = threading.Lock()


def _stack() -> list:
    try:
        return _local.stack
    except AttributeError:
        _local.stack = []
        return _local.stack


def _find_jax():
    """TraceAnnotation once jax is imported; installs the compile
    listener the first time."""
    global _annotation
    jax = sys.modules.get("jax")
    profiler = getattr(jax, "profiler", None)
    monitoring = getattr(jax, "monitoring", None)
    if profiler is None or monitoring is None:
        return None
    with _jax_lock:
        if _annotation is None:
            monitoring.register_event_duration_secs_listener(_on_duration)
            _annotation = profiler.TraceAnnotation
    return _annotation


def _on_duration(event: str, duration: float, **kw) -> None:
    """jax.monitoring listener, called on the thread that compiles; it
    records into the recorder of the span open there."""
    stack = _stack()
    top = stack[-1] if stack else None
    rec = top.rec if top is not None else RECORDER
    if event == _COMPILE:
        t1 = time.monotonic()
        rec._add((next(_seq), "jax.compile", t1 - duration, t1,
                  top.seq if top is not None else None,
                  kw.get("fun_name"), 1))
        rec.count("jax.compiles")
        rec.count("jax.compile_s", duration)
    elif event == _TRACE:
        rec.count("jax.traces")
    elif event == _CACHE_HIT:
        rec.count("jax.cache_hits")


class _Span:
    """The context manager :meth:`Recorder.span` returns; ``t0``/``t1``
    are readable once it has closed, and ``n`` may be set inside."""

    __slots__ = ("rec", "name", "key", "n", "seq", "parent", "t0", "t1",
                 "_ann")

    def __init__(self, rec: "Recorder", name: str, key, n):
        self.rec, self.name, self.key, self.n = rec, name, key, n

    def __enter__(self) -> "_Span":
        stack = _stack()
        top = stack[-1] if stack else None
        self.parent = top.seq if top is not None else None
        if self.key is None and top is not None:
            self.key = top.key
        self.seq = next(_seq)
        ann = _annotation or _find_jax()
        if ann is not None:
            self._ann = ann(self.name)
            self._ann.__enter__()
        else:
            self._ann = None
        stack.append(self)
        self.t0 = time.monotonic()
        return self

    def __exit__(self, *exc) -> None:
        self.t1 = time.monotonic()
        _stack().pop()
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        self.rec._add((self.seq, self.name, self.t0, self.t1, self.parent,
                       self.key, self.n))


class Recorder:
    """A bounded ring of rows and a table of counters.  Safe to record
    into from several threads.  ``dropped`` counts rows the ring let go."""

    def __init__(self, maxlen: int = MAXLEN):
        self._ring: deque = deque(maxlen=maxlen)
        self._counters: Dict[str, float] = defaultdict(float)
        self._lock = threading.Lock()
        self.dropped = 0
        self._dropped_t1 = -math.inf     # latest end of a dropped row

    # ------------------------------------------------------------ record
    def span(self, name: str, key=None, n: float = 0) -> _Span:
        """A context manager that records one row when it closes.  ``key``
        defaults to the enclosing span's; ``n`` is the work it did."""
        return _Span(self, name, key, n)

    def record(self, name: str, t0: float, t1: float, key=None,
               n: float = 0) -> None:
        """A row whose ends were stamped in different places or threads
        (``time.monotonic()``).  It has no parent."""
        self._add((next(_seq), name, t0, t1, None, key, n))

    def count(self, name: str, n: float = 1) -> None:
        with self._lock:
            self._counters[name] += n

    def _add(self, row: tuple) -> None:
        with self._lock:
            if len(self._ring) == self._ring.maxlen:
                self.dropped += 1
                self._dropped_t1 = max(self._dropped_t1, self._ring[0][3])
            self._ring.append(row)

    # ------------------------------------------------------------- read
    def rows(self, name: Optional[str] = None,
             since: Optional[float] = None) -> List[Row]:
        """Rows in the order they opened, with their self time; only those
        named ``name`` and starting at or after ``since``, if given."""
        with self._lock:
            snap = sorted(self._ring)
        kids: Dict[int, list] = defaultdict(list)
        for r in snap:
            if r[4] is not None:
                kids[r[4]].append((r[2], r[3]))
        return [Row(*r, _self_s(r[2], r[3], kids.get(r[0], ())))
                for r in snap
                if (name is None or r[1] == name)
                and (since is None or r[2] >= since)]

    def counters(self) -> Dict[str, float]:
        with self._lock:
            return dict(self._counters)

    def dropped_since(self, t: float) -> bool:
        """Whether the ring let go of a row that ended at or after ``t``:
        a reader of rows from ``t`` on would miss some."""
        return self._dropped_t1 >= t

    def summary(self) -> dict:
        """Per span name: count, total seconds, and p50/p99 of self time;
        then the counters and ``dropped``."""
        by: Dict[str, list] = defaultdict(list)
        for r in self.rows():
            by[r.name].append(r)
        spans = {}
        for name, rs in sorted(by.items()):
            s = sorted(r.self_s for r in rs)
            spans[name] = {"count": len(rs),
                           "total_s": sum(r.t1 - r.t0 for r in rs),
                           "self_p50_s": statistics.median(s),
                           "self_p99_s": s[math.ceil(0.99 * len(s)) - 1]}
        return {"spans": spans, "counters": self.counters(),
                "dropped": self.dropped}


def _self_s(t0: float, t1: float, children) -> float:
    """t1 - t0 less the union of ``children`` clipped to [t0, t1]."""
    covered, end = 0.0, t0
    for a, b in sorted(children):
        a, b = max(a, end), min(b, t1)
        if b > a:
            covered += b - a
            end = b
    return (t1 - t0) - covered


#: the process's recorder, which the program's spans go to
RECORDER = Recorder()
span = RECORDER.span
record = RECORDER.record
count = RECORDER.count
rows = RECORDER.rows
counters = RECORDER.counters
summary = RECORDER.summary
dropped_since = RECORDER.dropped_since
