"""p95 over served requests of the time per output token after the
first: (done - first token) / (new tokens - 1)."""
from livebench.traffic import percentile


def read(run):
    t = [(r["done"] - r["first"]) / (len(r["tokens"]) - 1) * 1e3
         for r in run.requests if len(r.get("tokens", ())) > 1]
    return percentile(t, 95) if t else None
