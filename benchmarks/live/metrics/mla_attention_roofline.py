"""The causal flash-attention kernels' (forward, dq, dkv) share of their
roofline at latent attention's widths (q/k 192, v 128): for every call
in the trace, the larger of its operations over the bf16 peak and its
bytes over the HBM bandwidth, summed, over the kernels' device time.
Each call is one layer of one microbatch."""
from livebench import moe_flops
from livebench.flops import roofline_s
from livebench.tracing import kernel_seconds


def read(run):
    if run.peak is None or run.trace is None or not run.trainer:
        return None
    t = run.trainer
    costs = moe_flops.mla_attention_costs(t["batch"] // t["microbatches"],
                                          t["text_len"], run.sz)
    ideal, spent = 0.0, 0.0
    for name, (f, b) in costs.items():
        secs, calls = kernel_seconds(run.trace, name)
        ideal += calls * roofline_s(f, b, run.peak)
        spent += secs
    return 100.0 * ideal / spent if spent > 0 else None
