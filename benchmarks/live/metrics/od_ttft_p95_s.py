"""p95 over every on-demand request due in the window of the time from
its due time (open loop) to its first token, served in the window or
after it.  A request never served counts with its wait until the
harness stopped waiting, a lower bound."""
from livebench.stack import LATE_S
from livebench.traffic import percentile


def read(run):
    if not run.requests:
        return None
    start = run.od.get("stream_t0")
    if start is None:
        return None
    lat = []
    for r in run.requests:
        due = start + r["due_s"]
        lat.append(r["first"] - due if "first" in r
                   else run.t1 + LATE_S - due)
    return percentile(lat, 95)
