"""Share of the window not inside a blocked step: 1 - sum(step_s) / window."""
from benchmarks import obsread


def reduce(ctx):
    return 100.0 * obsread.host_gap_share(ctx["events"], ctx["first_epoch"])
