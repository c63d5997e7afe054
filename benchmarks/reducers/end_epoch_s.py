"""epoch_s: wall of the whole timed window over all its epochs (obs log)."""


def reduce(ctx):
    return ctx["epoch_s"]
