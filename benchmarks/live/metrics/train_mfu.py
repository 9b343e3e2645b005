"""Model FLOP/s utilization of training: the step's forward and backward
operations (6 N per token plus causal attention, recomputation not
counted) over the median steady step time (as train_step_s), the chips
and the bf16 peak."""
import statistics

from livebench import flops


def read(run):
    if run.peak is None:
        return None
    t = [s.t1 - s.t0 for s in run.train.steps if not s.fresh]
    if not t:
        return None
    tr = run.trainer
    f = flops.train_step_flops(tr["batch"], tr["text_len"], run.sz)
    return 100.0 * f / (statistics.median(t) * run.chips
                        * run.peak["bf16_flops_per_s"])
