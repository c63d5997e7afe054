"""Reduction from a profiler trace to numbers: the benchmark's yardstick.

`load_trace_events`, `attribute`, `program_cost` and `step_comm_from_events`
are the benchmark's copy of the program's `utils/traceparse.py` as of PR 22
(sound since then; checked on the recorded v5e trace under `tests/data/`).
Copied so that a later PR cannot move the yardstick. New here: the device
busy/idle reduction (`device_busy`), the kernel-span reduction
(`kernel_spans`, after `chip_smoke.executed_step_ops`) and the breakdown.

A trace is the chrome-trace JSON the JAX profiler writes beside its
`.xplane.pb`: processes `/device:TPU:k` with an `XLA Ops` lane of executed
operations, and `/host:CPU` with the Python thread's `PjitFunction(<name>)`
launch spans. Times are microseconds.
"""

from __future__ import annotations

import bisect
import glob
import gzip
import json
import os
import re

EXCHANGE_PAT = re.compile(r"all[-_]to[-_]all|collective[-_]permute", re.I)
REDUCE_PAT = re.compile(r"all[-_]reduce|reduce[-_]scatter|all[-_]gather",
                        re.I)
HOST_PROGRAMS = ("train_step", "exchange_only")
_LAUNCH_PAT = re.compile(r"^(?:PjitFunction\((\w+)\)|jit_(\w+))$")
OPS_LANE = "XLA Ops"


class TraceError(ValueError):
    """A profiler window that cannot be read or attributed."""


def load_trace_events(trace_dir):
    """Events of the newest <host>.trace.json.gz under trace_dir."""
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins/profile/*/*.trace.json.gz")), key=os.path.getmtime)
    if not paths:
        raise TraceError(f"no plugins/profile/*/*.trace.json.gz under "
                         f"{trace_dir}")
    try:
        with gzip.open(paths[-1], "rt") as f:
            return json.load(f).get("traceEvents", []), paths[-1]
    except (OSError, EOFError, ValueError) as ex:
        raise TraceError(f"unreadable trace {paths[-1]}: {ex}") from ex


def _host_program(name):
    m = _LAUNCH_PAT.match(name)
    if m:
        fn = m.group(1) or m.group(2)
        for prog in HOST_PROGRAMS:
            if fn.startswith(prog):
                return prog
    return None


def thread_names(events):
    return {(ev["pid"], ev["tid"]): ev["args"].get("name", "")
            for ev in events
            if ev.get("ph") == "M" and ev.get("name") == "thread_name"}


def process_names(events):
    return {ev["pid"]: ev["args"].get("name", "") for ev in events
            if ev.get("ph") == "M" and ev.get("name") == "process_name"}


def launches(events, program="train_step"):
    """Start times (us) of the host launches of `program`, nested duplicate
    spans (~1 us apart) counted once."""
    procs = process_names(events)
    raw = sorted(float(ev["ts"]) for ev in events if ev.get("ph") == "X"
                 and _host_program(ev.get("name", "")) == program
                 and not procs.get(ev.get("pid"), "").startswith("/device:"))
    out = []
    for ts in raw:
        if not out or ts - out[-1] >= 100:
            out.append(ts)
    return out


def attribute(events):
    """Collective events per host program and device lane (see the program's
    traceparse.attribute): {program: {"exchange"|"reduce": {lane: [(ts,
    dur_us)]}, "launches": N}}."""
    tnames = thread_names(events)
    raw = sorted((float(ev["ts"]), _host_program(ev.get("name", "")))
                 for ev in events if ev.get("ph") == "X"
                 and _host_program(ev.get("name", "")) is not None)
    found = []
    for ts, prog in raw:
        if found and found[-1][1] == prog and ts - found[-1][0] < 100:
            continue
        found.append((ts, prog))
    out = {p: {"exchange": {}, "reduce": {}, "launches": 0}
           for p in HOST_PROGRAMS + ("other",)}
    for _, prog in found:
        out[prog]["launches"] += 1
    starts = [ts for ts, _ in found]
    for ev in events:
        if ev.get("ph") != "X":
            continue
        name = ev.get("name", "")
        if EXCHANGE_PAT.search(name):
            cat = "exchange"
        elif REDUCE_PAT.search(name):
            cat = "reduce"
        else:
            continue
        lane = (ev["pid"], tnames.get((ev["pid"], ev["tid"]), ev["tid"]))
        if lane[1] == "python":
            continue
        i = bisect.bisect_right(starts, float(ev["ts"])) - 1
        prog = found[i][1] if i >= 0 else "other"
        out[prog][cat].setdefault(lane, []).append(
            (float(ev["ts"]), float(ev.get("dur", 0.0))))
    for prog in out:
        for cat in ("exchange", "reduce"):
            for lane in out[prog][cat]:
                out[prog][cat][lane].sort()
    return out


def program_cost(bucket, cat="exchange"):
    """(raw_sum_us, min_over_lanes_us, events_per_lane, n_lanes): lane i's
    k-th collective span includes its wait for the other participants, so the
    minimum over lanes at each position is the op's own cost."""
    lanes = bucket[cat]
    if not lanes:
        return 0.0, 0.0, 0, 0
    raw = sum(d for evs in lanes.values() for _, d in evs)
    n = max(len(evs) for evs in lanes.values())
    min_est = sum(min(evs[k][1] for evs in lanes.values() if len(evs) > k)
                  for k in range(n))
    return raw, min_est, n, len(lanes)


def step_comm_from_events(events, expect_exchange: bool):
    """Per train step (exchange_s, reduce_s, n_steps)."""
    attr = attribute(events)
    steps = attr["train_step"]["launches"]
    if steps < 1:
        raise TraceError("no train_step launch in the trace window")
    _, ex_us, ex_n, _ = program_cost(attr["train_step"], "exchange")
    _, rd_us, _, _ = program_cost(attr["train_step"], "reduce")
    if ex_n == 0 and expect_exchange:
        raise TraceError(f"{steps} train_step launch(es) but no device "
                         f"exchange span: the profiler lost the device ops")
    return ex_us / steps / 1e6, rd_us / steps / 1e6, steps


# ---------------------------------------------------------------------------
# new with the benchmark
# ---------------------------------------------------------------------------

def device_op_spans(events):
    """{device name: [(start_us, end_us, op name)]} of the `XLA Ops` lanes."""
    procs, tnames = process_names(events), thread_names(events)
    out = {}
    for ev in events:
        if ev.get("ph") != "X":
            continue
        dev = procs.get(ev.get("pid"), "")
        if not dev.startswith("/device:"):
            continue
        if tnames.get((ev["pid"], ev.get("tid"))) != OPS_LANE:
            continue
        ts = float(ev["ts"])
        out.setdefault(dev, []).append(
            (ts, ts + float(ev.get("dur", 0.0)), ev.get("name", "")))
    for spans in out.values():
        spans.sort()
    return out


def merged(spans):
    out = []
    for s, e in sorted(spans):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


MODULES_LANE = "XLA Modules"


def traced_window(events):
    """(start_us, end_us) of the traced steady steps: from the second
    train_step launch (the first traced step absorbs the profiler's own
    start-up, a stall of 0.2 s on the v5e, PR 26) to the end of the last
    device operation; from the first launch where there are fewer than three.
    None where the trace holds no train_step launch or no device operation (a
    CPU trace has no device lanes)."""
    starts = launches(events)
    spans = device_op_spans(events)
    if not starts or not spans:
        return None
    end = max(e for sp in spans.values() for _, e, _ in sp)
    return (starts[1] if len(starts) >= 3 else starts[0]), end


def device_busy(events):
    """{device: busy seconds}: the union of the operation intervals on each
    device's XLA Ops lane inside the traced window; and the window's length
    in seconds. ({}, 0.0) where there is nothing to read."""
    win = traced_window(events)
    if win is None:
        return {}, 0.0
    t0, t1 = win
    busy = {}
    for dev, spans in device_op_spans(events).items():
        clipped = [(max(s, t0), min(e, t1)) for s, e, _ in spans
                   if e > t0 and s < t1]
        busy[dev] = sum(e - s for s, e in merged(clipped)) / 1e6
    return busy, (t1 - t0) / 1e6


def module_spans(events, dev):
    """Merged intervals in which a compiled program ran on `dev` (its
    `XLA Modules` lane)."""
    procs, tnames = process_names(events), thread_names(events)
    return merged([(float(ev["ts"]), float(ev["ts"]) + float(ev.get("dur", 0)))
                   for ev in events if ev.get("ph") == "X"
                   and procs.get(ev.get("pid")) == dev
                   and tnames.get((ev["pid"], ev.get("tid"))) == MODULES_LANE])


def idle_gaps(events, top=10):
    """The longest idle gaps on the busiest device over the whole trace (the
    profiler's start-up included), named by where they fall: `inside_step`
    while a compiled program is running on the device, `between_steps` while
    none is (the host's per-epoch work: loss read, guard, obs, checkpoint)."""
    busy, _ = device_busy(events)
    if not busy:
        return []
    dev = max(busy, key=busy.get)
    all_spans = device_op_spans(events)[dev]
    t0 = launches(events)[0]
    t1 = max(e for _, e, _ in all_spans)
    spans = merged([(s, e) for s, e, _ in all_spans if e > t0 and s < t1])
    mods = module_spans(events, dev)
    gaps = []
    prev = t0
    for s, e in spans + [[t1, t1]]:
        if s > prev:
            mid = (prev + s) / 2
            inside = any(a <= mid <= b for a, b in mods)
            gaps.append(("inside_step" if inside else "between_steps",
                         (s - prev) / 1e6))
        prev = max(prev, e)
    gaps.sort(key=lambda g: -g[1])
    return [[k, v] for k, v in gaps[:top]]


def top_device_ops(events, top=10):
    """Operations by total time on the busiest device, instances of one
    operation (`name.N`) summed."""
    busy, _ = device_busy(events)
    if not busy:
        return []
    dev = max(busy, key=busy.get)
    t0, t1 = traced_window(events)
    total = {}
    for s, e, name in device_op_spans(events)[dev]:
        if e > t0 and s < t1:
            base = re.sub(r"\.\d+$", "", name)
            total[base] = total.get(base, 0.0) + (e - s) / 1e6
    return [[k, v] for k, v in sorted(total.items(),
                                      key=lambda kv: -kv[1])[:top]]


def kernel_spans(events, kernel_name):
    """{device: [(dur_s, long_name)]} of the spans of one named kernel
    (`<kernel_name>` or `<kernel_name>.N`) on the devices' op lanes."""
    pat = re.compile(re.escape(kernel_name) + r"(\.\d+)?$")
    procs = process_names(events)
    out = {}
    for ev in events:
        dev = procs.get(ev.get("pid"), "")
        if (ev.get("ph") == "X" and dev.startswith("/device:")
                and pat.match(ev.get("name", ""))):
            out.setdefault(dev, []).append(
                (float(ev.get("dur", 0.0)) / 1e6,
                 (ev.get("args") or {}).get("long_name", "")))
    return out
