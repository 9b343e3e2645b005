"""The host's part of a decode step, from the program's own spans: the
median over the window's ``serve.decode_step`` rows of the step's
duration less its ``serve.token_sync`` child (the wait for the token),
in milliseconds.  None for a program that records no spans, or where the
recorder let go of a row the window needs."""
import statistics


def read(run):
    try:
        from repro import telemetry
    except ImportError:
        return None
    if telemetry.dropped_since(run.t0):
        return None
    rows = telemetry.rows(since=run.t0)
    sync = {r.parent: r.t1 - r.t0 for r in rows
            if r.name == "serve.token_sync"}
    host = [(r.t1 - r.t0 - sync.get(r.seq, 0.0)) * 1e3 for r in rows
            if r.name == "serve.decode_step" and r.t0 < run.t1]
    return statistics.median(host) if host else None
