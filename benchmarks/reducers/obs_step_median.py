"""Median of the loop's own step_s (dispatch to loss ready)."""
from benchmarks import obsread


def reduce(ctx):
    return obsread.step_median_s(ctx["events"], ctx["first_epoch"])
