"""Median time of the trainer's steady steps (not the first after a
compile, start or restore), each ending in the loss sync."""
import statistics


def read(run):
    t = [s.t1 - s.t0 for s in run.train.steps if not s.fresh]
    return statistics.median(t) if t else None
