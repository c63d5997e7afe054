"""Seconds per traced step inside one named kernel, on the fullest device."""
from benchmarks import tracelib


def reduce(ctx, kernel):
    spans = tracelib.kernel_spans(ctx["trace_events"], kernel)
    steps = len(tracelib.launches(ctx["trace_events"]))
    if not spans or not steps:
        return None
    return max(sum(d for d, _ in sp) for sp in spans.values()) / steps
