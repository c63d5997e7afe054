"""Seconds of SpMM layout building in the timed call (0 on a cache hit)."""
from benchmarks import obsread


def reduce(ctx):
    return obsread.layout_build_s(ctx["events"])
