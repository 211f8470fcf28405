"""Model FLOP/s utilization of the whole window, in percent: the model
FLOPs of every token trained in the window (the configuration's
``flops_per_token``: forward and backward, no recomputation) over the
window's seconds times the chips times their bf16 peak."""
import flops


def read(ctx):
    dev = ctx["device"]
    peak = flops.peaks(dev["kind"])["bf16_flops"]
    return flops.mfu(ctx["flops_per_token"], ctx["tokens"], ctx["window_s"],
                     dev["count"], peak)
