"""Median over served batches of the host time per decode step, from the
first token to the batch's last, each step ending in the token sync."""
import statistics


def read(run):
    t = [(b["t_done"] - b["t_first"]) / b["n_decode"] * 1e3
         for b in run.batches if b["n_decode"] > 0]
    return statistics.median(t) if t else None
