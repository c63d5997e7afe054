"""Profiler-trace parsing: attribute device collectives to host programs.

The reference measures its Comm column as in-step wall-clock around each
send/recv (``helper/timer/comm_timer.py:21-25``). Under XLA a wall-clock
span inside a jitted step is meaningless, and the 2026-07-30 cross-check
on an 8-device host-platform mesh (hw_logs/trace_comm_table.log) showed the
exchange-only microbench overstates the real in-step collective cost by
1.5-26x — host dispatch dominates for small quantized payloads. The truthful equivalent
of the reference's measurement is the profiler trace itself: every device
collective span, attributed to the train_step that launched it, with a
min-over-lanes estimate that strips rendezvous wait (lane i's span
includes waiting for the other participants; the minimum across lanes at
each collective position ~= the last-arriver's span ~= the true op cost).

This module holds the parsing core; ``tools/trace_comm.py`` is the CLI
that builds the fidelity table, and ``run.py`` calls
``step_comm_per_epoch`` on a short auto-trace so the printed Comm(s) /
Reduce(s) columns report trace-derived in-step numbers.
"""

from __future__ import annotations

import bisect
import glob
import gzip
import json
import os
import re

# Device collective spans by HLO instruction name. Both spellings occur: the
# TPU names an instruction after the jax primitive that made it
# (`all_to_all.32`, seen on a v5e under jax 0.9) or after the HLO opcode
# (`all-reduce.7`; XLA:CPU uses the opcode for every collective).
EXCHANGE_PAT = re.compile(r"all[-_]to[-_]all|collective[-_]permute", re.I)
REDUCE_PAT = re.compile(r"all[-_]reduce|reduce[-_]scatter|all[-_]gather",
                        re.I)
# Host programs by name prefix: --halo-refresh K>1 (and the --tune K-anneal)
# launch train_step_full / train_step_cached / exchange_only_refresh, which
# are train steps and exchange sweeps like the K=1 programs.
HOST_PROGRAMS = ("train_step", "exchange_only")
_LAUNCH_PAT = re.compile(r"^(?:PjitFunction\((\w+)\)|jit_(\w+))$")

# --overlap split phase scopes (trainer._split_agg_for wraps the interior /
# frontier aggregations in jax.named_scope, which XLA threads into op
# metadata; profiler events carry it in the name or an args value)
INTERIOR_PAT = re.compile(r"interior_agg", re.I)
FRONTIER_PAT = re.compile(r"frontier_agg", re.I)

# Device scopes of the train step: one `jax.named_scope` per layer boundary,
# entered where the work happens (models/gnn.py, ops/, trainer.py) through
# these constants, never a retyped literal. XLA threads the scope path into
# each instruction's `op_name` metadata ("jit(train_step)/.../layer_1/linear/
# dot_general"; backward ops carry JAX's "transpose(jvp(linear))" wrapper),
# and the profiler writes it beside every device event, so a reader finds an
# operation's layer by `innermost_scope` and not by a compiler-chosen name
# (`while`, `fusion.N`). The gradient all-reduce is emitted by AD's transpose
# outside any scope and stays found by opcode (REDUCE_PAT). What the compiler
# does to the names (v5e, PR 27): a loop it rebuilds (`while`) loses its
# op_name while the fusions of its body keep theirs; a fusion across two
# scopes carries the one name of its root; its own copies carry none.
BNS_SAMPLE = "bns_sample"          # sampling + halo: make_halo_plan*
HALO_EXCHANGE = "halo_exchange"    # sampling + halo: the fused exchange
HALO_START = "halo_start"          # --overlap split keeps its four phases
HALO_FINISH = "halo_finish"
INTERIOR_AGG = "interior_agg"
FRONTIER_AGG = "frontier_agg"
AGG_TILES = "agg_tiles"            # dense tiles: block_spmm._dense, fwd + bwd
AGG_RESIDUAL = "agg_residual"      # residual gather: ell._ell_apply, fwd + bwd
AGG_COO = "agg_coo"                # residual gather: spmm.gather_scatter_sum
ATTENTION = "attention"            # model (GAT)
LINEAR = "linear"                  # model
NORM = "norm"
DROPOUT = "dropout"
LOSS = "loss"
OPTIMIZER = "optimizer"            # step: tx.update + apply_updates
PP_PRECOMPUTE = "pp_precompute"    # set-up: trainer.local_precompute
LAYER = "layer"                    # model: `layer_<i>`, parent of the above

SCOPES = (BNS_SAMPLE, HALO_EXCHANGE, HALO_START, HALO_FINISH, INTERIOR_AGG,
          FRONTIER_AGG, AGG_TILES, AGG_RESIDUAL, AGG_COO, ATTENTION, LINEAR,
          NORM, DROPOUT, LOSS, OPTIMIZER, PP_PRECOMPUTE, LAYER)


def layer_scope(i: int) -> str:
    return f"{LAYER}_{i}"


# one path component of an op_name that is a table scope, bare or inside
# transform wrappers (`transpose(jvp(agg_tiles))`); `jit(norm)` is a jitted
# function of that name, not a scope
_SCOPE_PART = re.compile(
    r"^(?:(?!p?jit\()\w+\()*(?:(" + "|".join(s for s in SCOPES if s != LAYER)
    + r")|" + LAYER + r"_\d+)\)*$")


def innermost_scope(op_name: str):
    """The innermost table scope on an `op_name` path (`layer_3` reads as
    LAYER), or None where the path holds none."""
    for part in reversed(op_name.split("/")):
        m = _SCOPE_PART.match(part)
        if m:
            return m.group(1) or LAYER
    return None


def _host_program(name):
    """The HOST_PROGRAMS bucket a host launch span belongs to, or None."""
    m = _LAUNCH_PAT.match(name)
    if m:
        fn = m.group(1) or m.group(2)
        for prog in HOST_PROGRAMS:
            if fn.startswith(prog):
                return prog
    return None


class TraceError(ValueError):
    """A profiler window that cannot be read or attributed; the message
    names why. Raised, never swallowed: a caller that wants to carry on
    without traced numbers catches it and says so."""


def load_trace_events(trace_dir):
    """Newest <host>.trace.json.gz under trace_dir (chrome trace format)."""
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins/profile/*/*.trace.json.gz")), key=os.path.getmtime)
    if not paths:
        raise TraceError(
            f"the profiler wrote no plugins/profile/*/*.trace.json.gz under "
            f"{trace_dir}")
    try:
        with gzip.open(paths[-1], "rt") as f:
            return json.load(f).get("traceEvents", []), paths[-1]
    except (OSError, EOFError, ValueError) as ex:
        raise TraceError(f"unreadable trace {paths[-1]}: {ex}") from ex


def _thread_names(events):
    names = {}
    for ev in events:
        if ev.get("ph") == "M" and ev.get("name") == "thread_name":
            names[(ev["pid"], ev["tid"])] = ev["args"].get("name", "")
    return names


def attribute(events):
    """Collective events per host program, with per-lane alignment.

    Returns {program: {"exchange"|"reduce": {lane: [(ts, dur_us)...]},
    "launches": N, "sweeps": N}} plus an "other" bucket for collectives
    outside any known program span. Device events are attributed to the
    latest host-program launch whose start ts precedes them (dispatch is
    ordered and run.py block-waits between programs, so launch order =
    device order). Host launch spans appear as nested duplicate events
    ~1 us apart — deduped by a 100 us proximity window. "sweeps" counts
    maximal consecutive runs of exchange_only launches: one Comm(s)
    sample fires the program once per layer width back-to-back.
    """
    tnames = _thread_names(events)
    raw_launches = []          # (ts, program)
    for ev in events:
        if ev.get("ph") != "X":
            continue
        prog = _host_program(ev.get("name", ""))
        if prog is not None:
            raw_launches.append((float(ev["ts"]), prog))
    raw_launches.sort()
    launches = []
    for ts, prog in raw_launches:
        if launches and launches[-1][1] == prog and ts - launches[-1][0] < 100:
            continue
        launches.append((ts, prog))
    out = {p: {"exchange": {}, "reduce": {}, "launches": 0, "sweeps": 0}
           for p in HOST_PROGRAMS + ("other",)}
    prev = None
    for _, prog in launches:
        out[prog]["launches"] += 1
        if prog == "exchange_only" and prev != "exchange_only":
            out[prog]["sweeps"] += 1
        prev = prog
    starts = [ts for ts, _ in launches]
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
        if lane[1] == "python":        # host-side dispatch wrapper, not device
            continue
        i = bisect.bisect_right(starts, float(ev["ts"])) - 1
        prog = launches[i][1] if i >= 0 else "other"
        out[prog][cat].setdefault(lane, []).append(
            (float(ev["ts"]), float(ev.get("dur", 0.0))))
    for prog in out:
        for cat in ("exchange", "reduce"):
            for lane in out[prog][cat]:
                out[prog][cat][lane].sort()
    return out


def program_cost(bucket, cat="exchange"):
    """(raw_sum_us, min_over_lanes_us, events_per_lane, n_lanes)."""
    lanes = bucket[cat]
    if not lanes:
        return 0.0, 0.0, 0, 0
    raw = sum(d for evs in lanes.values() for _, d in evs)
    n = max(len(evs) for evs in lanes.values())
    min_est = sum(min(evs[k][1] for evs in lanes.values() if len(evs) > k)
                  for k in range(n))
    return raw, min_est, n, len(lanes)


def _ev_matches(ev, pat):
    """Scope match against the event name OR any string arg value: a v5e
    trace carries the HLO op_name metadata, where named_scope lands, in
    `args.tf_op` ("jit(train_step)/jvp()/layer_1/agg_residual/gather:",
    checked on the chip at PR 27), not in the instruction name."""
    if pat.search(ev.get("name", "")):
        return True
    args = ev.get("args") or {}
    return any(isinstance(v, str) and pat.search(v) for v in args.values())


def _merged(spans):
    out = []
    for s, e in sorted(spans):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _intersect_us(a, b):
    """Total overlap time between two span lists (us)."""
    a, b = _merged(a), _merged(b)
    i = j = 0
    tot = 0.0
    while i < len(a) and j < len(b):
        s = max(a[i][0], b[j][0])
        e = min(a[i][1], b[j][1])
        if e > s:
            tot += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def overlap_from_events(events):
    """--overlap split observability: did the halo collective actually run
    concurrently with the interior SpMM?

    Exchange spans come from the train_step attribution (so exchange_only
    microbench collectives never pollute the check); interior/frontier
    compute spans are collected by scope name on the device lanes. Per-lane
    interval intersection of exchange x interior is the time the wire was
    genuinely hidden under independent compute. Returns per-step ms buckets
    {n_steps, exchange_ms, interior_ms, frontier_ms, hidden_ms, overlapped}
    or None when the trace carries no interior/frontier scopes (a fused run,
    or a profiler that dropped op metadata)."""
    attr = attribute(events)
    steps = attr["train_step"]["launches"]
    ex_lanes = {lane: [(ts, ts + d) for ts, d in evs]
                for lane, evs in attr["train_step"]["exchange"].items()}
    tnames = _thread_names(events)
    scope_lanes = {"interior": {}, "frontier": {}}
    for ev in events:
        if ev.get("ph") != "X":
            continue
        lane = (ev["pid"], tnames.get((ev["pid"], ev["tid"]), ev["tid"]))
        if lane[1] == "python":
            continue
        for cat, pat in (("interior", INTERIOR_PAT),
                         ("frontier", FRONTIER_PAT)):
            if _ev_matches(ev, pat):
                scope_lanes[cat].setdefault(lane, []).append(
                    (float(ev["ts"]),
                     float(ev["ts"]) + float(ev.get("dur", 0.0))))
    if not scope_lanes["interior"] and not scope_lanes["frontier"]:
        return None
    sums = {"exchange": sum(e - s for sp in ex_lanes.values()
                            for s, e in sp)}
    for cat in ("interior", "frontier"):
        sums[cat] = sum(e - s for sp in scope_lanes[cat].values()
                        for s, e in sp)
    hidden = sum(_intersect_us(ex_lanes.get(lane, []), sp)
                 for lane, sp in scope_lanes["interior"].items())
    n = max(steps, 1)
    return {"n_steps": steps,
            "exchange_ms": sums["exchange"] / n / 1e3,
            "interior_ms": sums["interior"] / n / 1e3,
            "frontier_ms": sums["frontier"] / n / 1e3,
            "hidden_ms": hidden / n / 1e3,
            # 'overlapped' = a meaningful fraction (>5%) of the collective
            # time coincided with interior compute on the same device lane
            "overlapped": (hidden > 0.05 * sums["exchange"]
                           if sums["exchange"] > 0 else False)}


def overlap_report(trace_dir):
    """overlap_from_events over the newest trace in `trace_dir` (None = the
    trace holds no interior/frontier scopes; an unreadable trace raises
    TraceError)."""
    return overlap_from_events(load_trace_events(trace_dir)[0])


def _replica_groups(ev):
    """Parse the HLO `replica_groups={{0,1},{2,3}}` attribute from a
    collective event's name or string args (TPU traces carry the HLO text
    in 'long_name'/'hlo_text' metadata). None when absent — CPU traces and
    stripped profiles fall back to the op-kind heuristic in comm_by_axis."""
    texts = [ev.get("name", "")]
    texts += [v for v in (ev.get("args") or {}).values() if isinstance(v, str)]
    for s in texts:
        m = re.search(r"replica_groups=\{(\{[^{}]*\}(?:,\{[^{}]*\})*)\}", s)
        if m:
            return [[int(x) for x in grp.split(",") if x.strip()]
                    for grp in re.findall(r"\{([^{}]*)\}", m.group(1))]
    return None


def classify_axis(groups, n_parts: int, n_replicas: int = 1,
                  n_feat: int = 1) -> str:
    """Mesh axis a collective's replica_groups reduce over, for the
    ('replicas', 'parts', 'feat') device order of parallel/replicas.
    make_mesh (device id = (r * n_parts + p) * n_feat + f, replicas outer,
    feat innermost):

      * one group of every device               -> the fused gradient/loss
        reduce: 'replicas x parts x feat' on a 3-D mesh, 'replicas x parts'
        / 'parts x feat' on the 2-D meshes, plain 'parts' on 1-D;
      * groups of n_feat CONSECUTIVE ids aligned to n_feat -> 'feat' (the
        per-layer partial psum of the tensor axis);
      * groups of n_parts ids at stride n_feat, first id inside the feat-0
        block of its replica row           -> 'parts' (halo traffic, one
        group per (replica, feat) lane);
      * groups of n_replicas ids at stride P*T   -> 'replicas' (a pure
        replica-axis reduce — the fused trainer never emits one, so seeing
        it flags an unfused double collective).
    """
    if not groups or not groups[0]:
        return "unknown"
    size = len(groups[0])
    if any(len(g) != size for g in groups):
        return "unknown"
    full = n_parts * n_replicas * n_feat
    if size == full:
        label = [n for n, on in (("replicas", n_replicas > 1), ("parts", True),
                                 ("feat", n_feat > 1)) if on]
        return " x ".join(label) if len(label) > 1 else "parts"
    if n_feat > 1 and size == n_feat and all(
            g == list(range(g[0], g[0] + n_feat)) and g[0] % n_feat == 0
            for g in groups):
        return "feat"
    if size == n_parts and all(
            all(b - a == n_feat for a, b in zip(g, g[1:]))
            and g[0] % (n_parts * n_feat) < n_feat
            for g in groups):
        return "parts"
    if n_replicas > 1 and size == n_replicas and all(
            all(b - a == n_parts * n_feat for a, b in zip(g, g[1:]))
            for g in groups):
        return "replicas"
    return "unknown"


def comm_by_axis(events, n_parts: int, n_replicas: int = 1, n_feat: int = 1):
    """Device collective time grouped by mesh axis: {axis: {kind: us}}.

    `kind` is 'exchange' (all-to-all / collective-permute — the per-layer
    halo hop) or 'reduce' (all-reduce family — the per-layer feat psum of a
    --feat run, or the fused gradient mean). Axis comes from the event's
    replica_groups when the trace carries HLO metadata — on a 3-D mesh this
    is what splits halo ('parts') vs feat-psum ('feat') vs gradient
    ('replicas x parts x feat') time; otherwise the op kind decides (halo
    exchanges only ever ride 'parts'; a reduce defaults to the full-mesh
    gradient label — without groups a feat psum is indistinguishable from
    it, so --by-axis needs an attribute-carrying trace to separate them).

    Spans are reduced with the SAME min-over-lanes estimator as
    `program_cost`: lane i's k-th collective span includes its rendezvous
    wait for the other participants, so the minimum across lanes at each
    position ~= the last-arriver's span ~= the true op cost. A raw
    cross-lane sum would multiply every op by the lane count and skew
    toward whichever axis accumulates more straggler wait (the 1.5-26x
    overstatement documented at the top of this module) — exactly the
    comparison --by-axis exists to get right."""
    tnames = _thread_names(events)
    by_key = {}                 # (axis, kind) -> {lane: [(ts, dur), ...]}
    for ev in events:
        if ev.get("ph") != "X":
            continue
        name = ev.get("name", "")
        if EXCHANGE_PAT.search(name):
            kind = "exchange"
        elif REDUCE_PAT.search(name):
            kind = "reduce"
        else:
            continue
        lane = (ev.get("pid"),
                tnames.get((ev.get("pid"), ev.get("tid")), ev.get("tid")))
        if lane[1] == "python":
            continue
        groups = _replica_groups(ev)
        if groups is not None:
            axis = classify_axis(groups, n_parts, n_replicas, n_feat)
        elif kind == "exchange":
            axis = "parts"
        else:
            label = [n for n, on in (("replicas", n_replicas > 1),
                                     ("parts", True), ("feat", n_feat > 1))
                     if on]
            axis = " x ".join(label) if len(label) > 1 else "parts"
        by_key.setdefault((axis, kind), {}).setdefault(lane, []).append(
            (float(ev["ts"]), float(ev.get("dur", 0.0))))
    out = {}
    for (axis, kind), lanes in by_key.items():
        for evs in lanes.values():
            evs.sort()
        _, est, _, _ = program_cost({kind: lanes}, kind)
        out.setdefault(axis, {})[kind] = est
    return out


def step_comm_from_events(events, expect_exchange: bool):
    """Per-train_step in-step (exchange_s, reduce_s, n_steps) over already-
    loaded events — run.py loads the trace ONCE and feeds both this and
    overlap_from_events (a multi-epoch trace re-parse costs seconds of
    host stall between epochs).

    Raises TraceError when the window cannot be attributed. The trace
    alone cannot tell a program that exchanges nothing from a window that
    lost its device ops, so the caller says which program ran
    (`expect_exchange`; run.py records it as `exchanges` in the obs `trace`
    event for offline readers). True (every multi-part step that exchanges
    activations): a window without exchange spans means the profiler lost
    the device ops (e.g. the step compiled inside the window) — reported as
    that, never as a fabricated 0.0000 column. False (a 1-part program, or
    --halo-mode grad-only): no exchange span is the truth, 0 s."""
    attr = attribute(events)
    steps = attr["train_step"]["launches"]
    if steps < 1:
        raise TraceError("no train_step launch in the trace window")
    _, ex_us, ex_n, _ = program_cost(attr["train_step"], "exchange")
    _, rd_us, _, _ = program_cost(attr["train_step"], "reduce")
    if ex_n == 0 and expect_exchange:
        raise TraceError(
            f"{steps} train_step launch(es) in the trace window but no "
            f"device exchange span (all-to-all / collective-permute): the "
            f"profiler lost the device ops")
    return ex_us / steps / 1e6, rd_us / steps / 1e6, steps


def step_comm_per_epoch(trace_dir, expect_exchange: bool):
    """step_comm_from_events over the newest trace in `trace_dir`; raises
    TraceError when the trace is missing, unreadable or unattributable."""
    return step_comm_from_events(load_trace_events(trace_dir)[0],
                                 expect_exchange)
