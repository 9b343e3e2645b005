"""The host's work in a train step before its program is queued, from
the program's own spans: the median over the window's ``train.step``
rows that compiled nothing (no ``jax.compile`` row beneath them) of the
step's duration less its ``train.sync`` child (the wait for the step's
results), in milliseconds.  None for a program that records no spans, or
where the recorder let go of a row the window needs."""
import statistics


def read(run):
    try:
        from repro import telemetry
    except ImportError:
        return None
    if telemetry.dropped_since(run.t0):
        return None
    rows = telemetry.rows(since=run.t0)
    by_seq = {r.seq: r for r in rows}
    compiled = set()
    for r in rows:
        if r.name == "jax.compile":
            p = r.parent
            while p in by_seq:
                compiled.add(p)
                p = by_seq[p].parent
    sync = {r.parent: r.t1 - r.t0 for r in rows if r.name == "train.sync"}
    host = [(r.t1 - r.t0 - sync.get(r.seq, 0.0)) * 1e3 for r in rows
            if r.name == "train.step" and r.t0 < run.t1
            and r.seq not in compiled]
    return statistics.median(host) if host else None
