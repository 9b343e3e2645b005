"""The causal flash-attention kernels' (forward, dq, dkv) share of their
roofline: for every call in the trace, the larger of its operations over
the bf16 peak and its bytes over the HBM bandwidth, summed, over the
kernels' device time.  Each call is one layer of one microbatch."""
from livebench import flops
from livebench.tracing import kernel_seconds


def read(run):
    if run.peak is None:
        return None
    if run.trace is None or not run.trainer:
        return None
    t = run.trainer
    mb = t["batch"] // t["microbatches"]
    costs = flops.flash_attention_costs(mb, t["text_len"] + run.sz["P"],
                                        run.sz)
    ideal, spent = 0.0, 0.0
    for name, (f, b) in costs.items():
        secs, calls = kernel_seconds(run.trace, name)
        ideal += calls * flops.roofline_s(f, b, run.peak)
        spent += secs
    return 100.0 * ideal / spent if spent > 0 else None
