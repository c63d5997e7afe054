"""setup_s: process start to the first epoch of the timed window."""


def reduce(ctx):
    return ctx["setup_s"]
