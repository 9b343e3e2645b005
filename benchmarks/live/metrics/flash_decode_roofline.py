"""The decode kernel's share of its roofline: the least time of each
decode call (one per layer per step, each row reading its own keys and
values up to the step) at the larger of its compute and memory bound,
averaged over the calls the served batches made, times the calls the
trace holds, over their device time.  The average makes the share
independent of events the profiler drops."""
from livebench import flops
from livebench.tracing import kernel_seconds


def read(run):
    if run.peak is None or run.trace is None or not run.batches:
        return None
    secs, calls = kernel_seconds(run.trace, "flash_decode")
    if secs <= 0:
        return None
    ideal, expected = 0.0, 0
    for b in run.batches:
        for i in range(1, b["n_decode"] + 1):
            f, by = flops.flash_decode_cost([n + i for n in b["lens"]],
                                            run.sz)
            ideal += run.sz["L"] * flops.roofline_s(f, by, run.peak)
            expected += run.sz["L"]
    return 100.0 * ideal / expected * calls / secs
