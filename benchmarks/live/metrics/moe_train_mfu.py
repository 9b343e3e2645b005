"""Model FLOP/s utilization of the MoE trainer: the step's forward and
backward operations (every token through MLA, the dense layer, the
routers, the shared experts and the head; the routed experts for the
window's mean ``moe.rows`` a step; causal attention; recomputation not
counted) over the median steady step time (as train_step_s), the chips
and the bf16 peak.  None for a program that records no ``moe.rows``."""
import statistics

from livebench import moe_flops


def read(run):
    if run.peak is None or not run.trainer:
        return None
    rows = moe_flops.window_rows_per_step(run)
    t = [s.t1 - s.t0 for s in run.train.steps if not s.fresh]
    if rows is None or not t:
        return None
    tr = run.trainer
    f = moe_flops.train_step_flops(tr["batch"], tr["text_len"], rows, run.sz)
    return 100.0 * f / (statistics.median(t) * run.chips
                        * run.peak["bf16_flops_per_s"])
