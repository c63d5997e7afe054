"""The whole step's share of the chips' peak: FLOPs the forward and backward
need (the `step_flops` of the configuration's reference module, called with
the sizes the data module's `done` record names) over median step time x
chips x peak."""
from benchmarks import counters, obsread


def reduce(ctx):
    count = getattr(ctx["reference"], "step_flops", None)
    if count is None:
        return None
    flops = count(ctx["config"]["model"], **ctx["ref_info"])
    step_s = obsread.step_median_s(ctx["events"], ctx["first_epoch"])
    peak = counters.device_peaks(ctx["device"]["kind"])["bf16_flops"]
    return 100.0 * flops / (step_s * ctx["chips"] * peak)
