"""Idle share of the fullest device over the traced window: 1 - busy union /
window."""
from benchmarks import tracelib


def reduce(ctx):
    busy, window_s = tracelib.device_busy(ctx["trace_events"])
    if not busy:
        return None
    return 100.0 * (1.0 - max(busy.values()) / window_s)
