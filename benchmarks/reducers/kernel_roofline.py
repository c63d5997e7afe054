"""A dense-tile kernel's share of its roofline: the least time the chip could
take for the spans' work (the larger of FLOPs over peak FLOP/s and bytes over
peak bytes/s) over the time the spans took. The work of a span is read from
its own operand shapes in the trace (tiles [B, TR, TC] int8, slabs
[n, TC, H], output [R + 1, TR, H])."""
import re

from benchmarks import counters, tracelib

_SHAPE = re.compile(r"(s8|bf16|f32|s32)\[(\d+),(\d+),(\d+)\]")
_ITEM = {"s8": 1, "bf16": 2, "f32": 4, "s32": 4}


def span_work(long_name: str):
    """(flops, bytes) of one kernel call from its HLO text, or None."""
    shapes = _SHAPE.findall(long_name)
    tiles = [s for s in shapes if s[0] == "s8"]
    if not tiles:
        return None
    _, b, tr, tc = tiles[0]
    b, tr, tc = int(b), int(tr), int(tc)
    others = [s for s in shapes if s is not tiles[0]]
    out = next((s for s in others if s[0] in ("f32", "s32")
                and int(s[2]) == tr), None)
    slab = next((s for s in others if s is not out and int(s[2]) == tc), None)
    if out is None or slab is None:
        return None
    width = int(out[3])
    flops = counters.tile_matmul_flops(b, tr, tc, width)
    nbytes = counters.tile_matmul_bytes(
        b, tr, tc, width, int(out[1]), 1, _ITEM[slab[0]], _ITEM[out[0]])
    return flops, nbytes


def reduce(ctx, kernel):
    spans = tracelib.kernel_spans(ctx["trace_events"], kernel)
    if not spans:
        return None
    peaks = counters.device_peaks(ctx["device"]["kind"])
    dev = max(spans, key=lambda d: sum(x for x, _ in spans[d]))
    least = took = 0.0
    bounds = set()
    for dur, long_name in spans[dev]:
        work = span_work(long_name)
        if work is None:
            return None
        t, bound = counters.roofline_seconds(*work, peaks)
        least += t
        took += dur
        bounds.add(bound)
    if took <= 0:
        return None
    ctx["breakdown_notes"][kernel + "_bound"] = "+".join(sorted(bounds))
    return 100.0 * least / took
