"""95th percentile of the walls between consecutive epoch events."""
from benchmarks import obsread


def reduce(ctx):
    return obsread.epoch_p95_s(ctx["events"], ctx["first_epoch"])
