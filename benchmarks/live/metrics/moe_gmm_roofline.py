"""The grouped-product kernels' (``moe_gmm``, ``moe_tgmm``) share of
their roofline: the least time of one call at the larger of its
operations over the bf16 peak and its bytes over the HBM bandwidth, for
the mean rows a call was routed in the window (the program's
``moe.rows`` over the window's steps, per MoE layer and microbatch),
times the calls the trace holds, over their device time.  None for a
program that records no ``moe.rows``."""
from livebench import moe_flops
from livebench.flops import roofline_s
from livebench.tracing import kernel_seconds


def read(run):
    if run.peak is None or run.trace is None or not run.trainer:
        return None
    rows = moe_flops.window_rows_per_step(run)
    if rows is None:
        return None
    calls_per_step = (run.sz["L"] - run.sz["dense"]) * run.trainer[
        "microbatches"]
    f, b = moe_flops.gmm_call_cost(rows / calls_per_step, run.sz)
    secs, calls = (sum(x) for x in zip(
        kernel_seconds(run.trace, "moe_gmm"),
        kernel_seconds(run.trace, "moe_tgmm")))
    if secs <= 0:
        return None
    return 100.0 * calls * roofline_s(f, b, run.peak) / secs
