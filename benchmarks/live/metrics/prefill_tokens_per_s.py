"""Prefill's rate, from the program's own spans: the real (unpadded)
prompt tokens of the window's ``serve.prefill`` rows over the sum of
their durations, each ending when the batch's first tokens reach the
host.  Padding lowers it.  None for a program that records no spans, or
where the recorder let go of a row the window needs."""


def read(run):
    try:
        from repro import telemetry
    except ImportError:
        return None
    if telemetry.dropped_since(run.t0):
        return None
    rows = [r for r in telemetry.rows("serve.prefill", since=run.t0)
            if r.t0 < run.t1]
    secs = sum(r.t1 - r.t0 for r in rows)
    return sum(r.n for r in rows) / secs if secs > 0 else None
