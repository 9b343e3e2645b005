"""The malleable job's tokens per second of window: each step's tokens,
prorated by the share of the step that lies inside the window, over the
window's seconds.  Every stall counts."""


def read(run):
    if not run.trainer:
        return None
    done = 0.0
    for s in run.train.steps:
        inside = min(s.t1, run.t1) - max(s.t0, run.t0)
        if inside > 0:
            done += run.tokens_per_step * inside / (s.t1 - s.t0)
    return done / (run.t1 - run.t0)
