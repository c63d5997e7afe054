"""peak_hbm_gib: largest peak_bytes_in_use over the cell's devices."""


def reduce(ctx):
    return ctx["peak_bytes"] / 2 ** 30
