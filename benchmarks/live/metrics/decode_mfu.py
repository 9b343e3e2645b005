"""The decode steps' share of the chips' bf16 peak: every served batch's
decode operations (each row against its own tokens) over its decode
time, on the serving devices."""
from livebench import flops


def read(run):
    if run.peak is None:
        return None
    work, secs = 0.0, 0.0
    for b in run.batches:
        if b["n_decode"] < 1:
            continue
        work += sum(flops.decode_step_flops([n + i for n in b["lens"]],
                                            run.sz)
                    for i in range(1, b["n_decode"] + 1))
        secs += b["t_done"] - b["t_first"]
    if secs <= 0:
        return None
    return 100.0 * work / (secs * run.serve_devices
                           * run.peak["bf16_flops_per_s"])
