"""From the program's own names in a profiler trace to seconds: device
operations by the `jax.named_scope` they were traced under, idle gaps by the
host span that covers them.

The vocabulary is the program's and is read from it, not retyped: the scope
table and `innermost_scope` of `bnsgcn_tpu/utils/traceparse.py`, the span
prefix and epoch mark of `bnsgcn_tpu/obs.py`. A program that has neither (the
parent of PR 27) gives every reader here nothing to read: `None`, no error.

Where the names land (v5e, jax 0.9.0, my chip run, PR 27): each event of a
device's `XLA Ops` lane carries its instruction's `op_name` in `args.tf_op`
("jit(train_step)/jvp()/layer_1/agg_residual/gather:"). The lane nests: a
`while` covers the fusions of its body. Only events that no other event of the
lane covers are counted, so a loop counts once. A loop instruction itself
carries no `tf_op` (the compiler rebuilds it); it is booked to the scope that
names most of the time of the events it covers. A fusion across two scopes is
booked to the one scope its metadata names. No reader matches an instruction's
name, except the collectives by opcode, as `tracelib` does.
"""

from __future__ import annotations

import bisect

from benchmarks import tracelib

COLLECTIVE = "collective"      # found by opcode, as tracelib finds them
UNSCOPED = "unscoped"
UNNAMED = "unnamed"


def program_scopes():
    """(table of scope names, innermost_scope) of the program beside the
    benchmark, or None where it has none."""
    try:
        from bnsgcn_tpu.utils import traceparse
        return traceparse.SCOPES, traceparse.innermost_scope
    except (ImportError, AttributeError):
        return None


def program_spans():
    """(span prefix, epoch mark) of the program's host spans, or None."""
    try:
        from bnsgcn_tpu import obs
        return obs.SPAN_PREFIX, obs.EPOCH_MARK
    except (ImportError, AttributeError):
        return None


def busiest_device(events):
    busy, _ = tracelib.device_busy(events)
    return max(busy, key=busy.get) if busy else None


def lane_events(events, dev, lane=tracelib.OPS_LANE):
    procs, tnames = tracelib.process_names(events), tracelib.thread_names(events)
    return [ev for ev in events if ev.get("ph") == "X"
            and procs.get(ev.get("pid")) == dev
            and tnames.get((ev["pid"], ev.get("tid"))) == lane]


def top_level(lane):
    """[(event, [events it covers])] of one lane: the events no other event
    of the lane covers, in time order."""
    out = []
    end = float("-inf")
    for ev in sorted(lane, key=lambda e: (float(e["ts"]),
                                          -float(e.get("dur", 0.0)))):
        if float(ev["ts"]) >= end:
            out.append((ev, []))
            end = float(ev["ts"]) + float(ev.get("dur", 0.0))
        else:
            out[-1][1].append(ev)
    return out


def event_scope(ev, covered, innermost):
    """The table scope of one top-level event: its own `tf_op`'s innermost
    scope; for an event without one (a loop), the scope that names most of
    the time of the events it covers; COLLECTIVE by opcode; else UNSCOPED."""
    name = ev.get("name", "")
    if tracelib.EXCHANGE_PAT.search(name) or tracelib.REDUCE_PAT.search(name):
        return COLLECTIVE
    op_name = (ev.get("args") or {}).get("tf_op")
    if op_name:
        return innermost(op_name.rstrip(":")) or UNSCOPED
    took = {}
    for c in covered:
        c_name = (c.get("args") or {}).get("tf_op")
        scope = innermost(c_name.rstrip(":")) if c_name else None
        if scope:
            took[scope] = took.get(scope, 0.0) + float(c.get("dur", 0.0))
    return max(took, key=took.get) if took else UNSCOPED


def traced_steps(events, window):
    """train_step launches that fall inside the traced window."""
    return sum(1 for ts in tracelib.launches(events) if ts >= window[0])


def scope_seconds(events):
    """({scope: seconds per traced step}, steps) on the busiest device inside
    `tracelib.traced_window`, COLLECTIVE and UNSCOPED among the keys; None
    where the trace holds no device lane or the program has no scope table."""
    table = program_scopes()
    window = tracelib.traced_window(events) if events else None
    if table is None or window is None:
        return None
    _, innermost = table
    dev = busiest_device(events)
    steps = traced_steps(events, window)
    if dev is None or not steps:
        return None
    t0, t1 = window
    took = {}
    for ev, covered in top_level(lane_events(events, dev)):
        s = float(ev["ts"])
        e = s + float(ev.get("dur", 0.0))
        if e <= t0 or s >= t1:
            continue
        scope = event_scope(ev, covered, innermost)
        took[scope] = took.get(scope, 0.0) + (min(e, t1) - max(s, t0)) / 1e6
    return {k: v / steps for k, v in took.items()}, steps


def scope_seconds_of(ctx):
    """`scope_seconds` of a run's trace, read once for all its metrics."""
    if "scope_seconds" not in ctx:
        ctx["scope_seconds"] = scope_seconds(ctx["trace_events"])
    return ctx["scope_seconds"]


def host_spans(events):
    """[(start_us, end_us, name)] of the program's host spans in the trace
    (the prefix taken off; the epoch mark left out), by start."""
    names = program_spans()
    if names is None:
        return []
    prefix, epoch_mark = names
    procs = tracelib.process_names(events)
    out = []
    for ev in events:
        if ev.get("ph") != "X":
            continue
        # the trace writer keeps an annotation's full text ("bns:wait") in
        # `args.long_name` and shows what follows the colon as the name
        name = (ev.get("args") or {}).get("long_name") or ev.get("name", "")
        if (isinstance(name, str) and name.startswith(prefix)
                and name != epoch_mark
                and not procs.get(ev.get("pid"), "").startswith("/device:")):
            s = float(ev["ts"])
            out.append((s, s + float(ev.get("dur", 0.0)), name[len(prefix):]))
    out.sort()
    return out


def covering_span(spans, starts, t):
    """Name of the innermost span covering time `t` (the latest started one
    that has not ended), or UNNAMED."""
    i = bisect.bisect_right(starts, t) - 1
    while i >= 0:
        s, e, name = spans[i]
        if e >= t:
            return name
        i -= 1
    return UNNAMED


def named_gaps(events):
    """[(name, seconds)] of every idle gap on the busiest device inside the
    traced window, named by the innermost host span over the gap's midpoint;
    None where there is nothing to read (no device lane, or a program
    without host spans)."""
    window = tracelib.traced_window(events) if events else None
    if window is None or program_spans() is None:
        return None
    dev = busiest_device(events)
    if dev is None:
        return None
    t0, t1 = window
    spans = host_spans(events)
    if not spans:
        return None
    starts = [s for s, _, _ in spans]
    busy = tracelib.merged([(max(s, t0), min(e, t1)) for s, e, _ in
                            tracelib.device_op_spans(events)[dev]
                            if e > t0 and s < t1])
    gaps = []
    prev = t0
    for s, e in busy + [[t1, t1]]:
        if s > prev:
            gaps.append((covering_span(spans, starts, (prev + s) / 2),
                         (s - prev) / 1e6))
        prev = max(prev, e)
    return gaps
