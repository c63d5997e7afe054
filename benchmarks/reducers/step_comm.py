"""In-step collective seconds per traced step: `which` is 'exchange' (the
halo all-to-all / collective-permute) or 'reduce' (the gradient all-reduce)."""
from benchmarks import tracelib


def reduce(ctx, which):
    ex, rd, _ = tracelib.step_comm_from_events(ctx["trace_events"],
                                               expect_exchange=False)
    value = ex if which == "exchange" else rd
    return value if value > 0 else None
