"""Render a bnsgcn_tpu obs event log (--obs-log JSONL) as a human report.

The telemetry bus (bnsgcn_tpu/obs.py) leaves one machine-readable artifact
per run: a rank-tagged JSONL event log. This tool answers "where did the
time/bytes go, on which rank, in which epoch" AFTER the run — including
after the machine that ran it is gone:

  python tools/obs_report.py RUN.jsonl              # one-run report
  python tools/obs_report.py RUN.jsonl R1.jsonl ... # explicit multi-rank merge
  python tools/obs_report.py --compare A.jsonl B.jsonl   # trajectory diff
  python tools/obs_report.py RUN.jsonl --json       # summary as one JSON line

Sections (each rendered only when the log carries its events):
  * run header — config, RxPxT mesh, halo strategy/wire, partition stats,
    the aggregation's counts (`spmm`: tiles, dense edges, residual slots,
    calls a step)
  * set-up — the timeline: the OS's start of the process (`proc_start`),
    the boot spans `import` and `backend_init`, then the `span` events as
    a tree under `run_training_setup` (seconds and share of the root, each
    span's `compile` account: trace / lower / compile-or-cache-load
    seconds, cache hits / misses; `first_call:<program>`, what compiling
    or loading each jitted program of the loop cost, among them), then the
    warm-up: the epochs that compiled, with their programs and account
  * per-epoch table — loss, step ms, comm ms ([traced]/[sampled]), param
    norm, eval accuracy joined on epoch, "recompiled: <programs>" on an
    epoch in which jax compiled; multi-rank logs merge per rank
    (rank files `PATH.r<N>` are auto-discovered next to PATH). Where the
    records carry the loop's host account: dispatch / wait / boundary ms,
    and a stalls list (epochs whose wait exceeds the median by 10%, with
    the process counters that say what the host did)
  * comm-vs-compute split — per-epoch means from the epoch records; when a
    `trace`/`profile` event names a still-existing trace dir, the split is
    re-derived from the device spans via utils/traceparse (the ground truth);
    the `trace` event's `start_wall` lays the traced epochs' `epoch` events
    on the trace's clock (seconds after the window opened)
  * lifecycle — rollbacks, preemptions, injections, watchdog fires,
    coordinator decisions, post-mortem dump paths (exits 75/76/77/78)
  * cross-rank epochs — rank 0's merged `epoch_ranks` records (the
    piggybacked agree_step summaries)
  * serving — per-tier p50/p99 + refresh lag from `serve_drain`
  * serving fleet — per-backend tier splits + router fan-out counts when
    the log carries sharded-serving events (`serve_drain` records tagged
    with a backend id, plus the router's `serve_fleet` drain record; the
    backends' `.rN` sibling logs merge in via the same auto-discovery)
  * continual training — per-cycle before/after accuracy, fold mode
    (incremental vs repartition), promote/rollback outcome; --compare adds
    cycle-aligned accuracy deltas between two continual runs
  * bench — per-variant epoch times from a bench.py --obs-log

--compare prints an epoch-aligned loss/step diff plus the header deltas —
the bench-trajectory audit for hardware-window runs (bench.py records each
run's obs-log path in its result JSON).
"""

from __future__ import annotations

import argparse
import glob
import json
import math
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from bnsgcn_tpu.obs import (BOOT_PARENT, EVENT_KINDS, FIRST_CALL,  # noqa: E402
                            SETUP_SPANS, load_events)

LIFECYCLE_KINDS = ("inject", "rollback", "preempt", "watchdog_fire",
                   "divergence_abort", "coord_decision", "profile_request",
                   "profile", "halo_refresh", "strict_exec",
                   "reorder", "layout_build", "tune_decision", "resize")

# static-preflight verdicts (lint.sh gates 2-4 with --obs-log): the
# audit that gated a pod run sits in the same log as the run it gated
AUDIT_KINDS = ("ir_audit", "proto_audit", "perf_audit")

# continual training on an evolving graph (continual.py): per-cycle
# ingestion/fine-tune records plus the serving side's adoption events
CONTINUAL_KINDS = ("continual_cycle", "artifact_update", "promote")

# the report's sub-vocabularies must stay inside the bus registry —
# graftlint checks the emit sites, this checks the reader
assert (set(LIFECYCLE_KINDS) | set(AUDIT_KINDS) | set(CONTINUAL_KINDS)
        <= set(EVENT_KINDS)), \
    sorted((set(LIFECYCLE_KINDS) | set(AUDIT_KINDS) | set(CONTINUAL_KINDS))
           - set(EVENT_KINDS))


def load_run(paths: list[str]) -> list[dict]:
    """Events of one run, merged across the given files plus any auto-
    discovered per-rank siblings (`PATH.r<N>`), sorted by timestamp."""
    seen = []
    for p in paths:
        seen.append(p)
        # rank siblings only (PATH.r<digits>): PATH.r1.1 is rank 1's
        # ROTATION, which load_events already prepends when reading PATH.r1
        # — globbing it as a primary path would double-count its events
        seen.extend(sorted(
            m for m in glob.glob(glob.escape(p) + ".r*")
            if re.fullmatch(r"\.r\d+", m[len(p):])))
    events: list[dict] = []
    for p in dict.fromkeys(seen):       # de-dup, keep order
        if not os.path.exists(p):
            raise FileNotFoundError(f"no obs log at {p}")
        events.extend(load_events(p))
    events.sort(key=lambda e: e.get("ts", 0.0))
    return events


def summarize(events: list[dict]) -> dict:
    """Structured digest of one run's events (the --json output)."""
    out: dict = {"header": None, "epochs": {}, "evals": {}, "lifecycle": [],
                 "epoch_ranks": [], "serve": None, "serve_header": None,
                 "serve_drains": [], "serve_fleet": None,
                 "run_end": None, "traces": [], "bench": [], "audits": [],
                 "continual": [], "spans": [], "unknown_kinds": {}}
    for ev in events:
        k = ev.get("kind")
        if k is not None and k not in EVENT_KINDS:
            # a log written by a newer/older build: surface, don't drop
            out["unknown_kinds"][k] = out["unknown_kinds"].get(k, 0) + 1
        if k == "run_header" and out["header"] is None:
            out["header"] = ev
        elif k == "epoch":
            out["epochs"].setdefault(int(ev["epoch"]), {})[
                int(ev.get("rank", 0))] = ev
        elif k == "eval":
            out["evals"][int(ev["epoch"])] = ev
        elif k in LIFECYCLE_KINDS:
            out["lifecycle"].append(ev)
        elif k in AUDIT_KINDS:
            out["audits"].append(ev)
        elif k in CONTINUAL_KINDS:
            out["continual"].append(ev)
        elif k == "epoch_ranks":
            out["epoch_ranks"].append(ev)
        elif k == "serve_drain":
            out["serve_drains"].append(ev)
            # the single-host slot keeps its pre-fleet meaning: backend
            # shards tag their drains with a backend id, the single-host
            # server does not — existing consumers of "serve" see exactly
            # what they saw before sharded serving existed
            if "backend" not in ev:
                out["serve"] = ev
        elif k == "serve_fleet":
            out["serve_fleet"] = ev
        elif k == "serve_header":
            out["serve_header"] = ev
        elif k == "run_end" and int(ev.get("rank", 0)) == 0:
            out["run_end"] = ev
        elif k == "trace":
            out["traces"].append(ev)
        elif k == "span" and int(ev.get("rank", 0)) == 0:
            out["spans"].append(ev)
        elif k == "bench_variant":
            out["bench"].append(ev)
    return out


def _num(v) -> float:
    """Event numbers may arrive NaN-sanitized as strings ("nan"/"inf" —
    obs._sanitize keeps every line strict JSON); a diverged-run log is
    exactly what this tool must render, so coerce instead of crashing."""
    if isinstance(v, (int, float)):
        return float(v)
    if isinstance(v, str):
        try:
            return float(v)
        except ValueError:
            return float("nan")
    return float("nan")


def _mean(xs):
    xs = [_num(x) for x in xs if x is not None]
    xs = [x for x in xs if math.isfinite(x)]
    return sum(xs) / len(xs) if xs else 0.0


def _elide(rows, head=20, tail=15):
    if len(rows) <= head + tail + 1:
        return rows, False
    return rows[:head] + rows[-tail:], True


def _slots_desc(slots) -> str:
    """'r0:[p0,p1] r1:[p2,p3]' from a [P] part -> hosting-rank list (the
    'slots' field a RESIZE verdict carries). Local twin of
    parallel/replicas.slot_desc — importing it would pull jax into a tool
    that must render logs on a bare host."""
    by: dict = {}
    for p, r in enumerate(slots or []):
        by.setdefault(int(r), []).append(p)
    return " ".join(f"r{r}:[{','.join('p%d' % p for p in ps)}]"
                    for r, ps in sorted(by.items()))


def _resize_verdicts(s: dict) -> list[dict]:
    """De-duplicated RESIZE verdicts in timestamp order: every member
    (and a grow's joiner) mirrors the same agreed verdict into its own
    rank log, so a merged multi-rank run carries one event per rank per
    verdict — collapse them to the verdict itself."""
    out, seen = [], set()
    for ev in s["lifecycle"]:
        if ev["kind"] != "resize":
            continue
        key = (int(_num(ev.get("epoch"))), str(ev.get("trigger")),
               int(_num(ev.get("old_world"))), int(_num(ev.get("world"))),
               int(_num(ev.get("nonce"))))
        if key in seen:
            continue
        seen.add(key)
        out.append(ev)
    return out


def _compile_desc(acct: dict) -> str:
    """One `compile` account: trace / lower / compile-or-cache-load seconds
    (each the union of its spans) and the persistent cache's hits / misses."""
    return (f"trace {_num(acct.get('trace_s')):.3f} lower "
            f"{_num(acct.get('lower_s')):.3f} compile "
            f"{_num(acct.get('compile_s')):.3f} s, hits "
            f"{acct.get('hits', 0)} misses {acct.get('misses', 0)}")


def _render_setup(spans: list[dict], write, epochs: dict):
    """Set-up as a timeline: the process's start, the boot spans, the
    `span` events as a tree (seconds, share of the root, compile account),
    then the warm-up epochs that compiled."""
    kids: dict = {}
    for ev in spans:
        kids.setdefault(ev.get("parent"), []).append(ev)
    names = {ev.get("name") for ev in spans}
    roots = [ev for ev in spans if ev.get("parent") not in names]
    setup = next((ev for ev in roots if ev.get("name") == SETUP_SPANS[0]),
                 None)
    total = (_num(setup.get("dur_s")) if setup is not None else
             max((_num(ev.get("dur_s")) for ev in roots), default=0.0))
    write("")
    write("set-up (span events):")
    write("  phase                                  seconds   share")
    boot = kids.get(BOOT_PARENT, [])
    born = next((ev["proc_start"] for ev in boot
                 if ev.get("proc_start") is not None), None)
    if born is not None:
        first = min(_num(ev.get("t0")) for ev in boot)
        write(f"  {'process start (OS)':<36}  {'-':>8}       -  at "
              f"{_num(born):.3f}, {first - _num(born):.3f} s before "
              f"the first boot span")

    def walk(ev, depth):
        d = _num(ev.get("dur_s"))
        # a first call runs in the loop, after the root has closed; a boot
        # span runs before it
        share = (f"{d / total:6.1%}" if total > 0
                 and not str(ev.get("name")).startswith(FIRST_CALL)
                 and ev.get("parent") != BOOT_PARENT
                 else "     -")
        calls = f"  ({ev['calls']} calls)" if "calls" in ev else ""
        comp = (f"  [{_compile_desc(ev['compile'])}]" if ev.get("compile")
                else "")
        write(f"  {'  ' * depth + str(ev.get('name')):<36}  {d:8.3f}  "
              f"{share}{calls}{comp}")
        for kid in sorted(kids.get(ev.get("name"), []),
                          key=lambda k: _num(k.get("t0"))):
            walk(kid, depth + 1)

    for ev in sorted(roots, key=lambda k: _num(k.get("t0"))):
        walk(ev, 0)
    compiled = sorted((e, by_r[0]) for e, by_r in epochs.items()
                      if by_r.get(0, {}).get("compile"))
    if setup is None or not compiled:
        return
    end = _num(setup.get("t0")) + _num(setup.get("dur_s"))
    last_e, last = compiled[-1]
    write(f"  warm-up: {_num(last.get('ts')) - end:.3f} s from the root's "
          f"end to epoch {last_e}, the last epoch that compiled:")
    for e, ev in compiled:
        acct = ev["compile"]
        write(f"    E{e}: {', '.join(acct.get('programs') or ['-'])} "
              f"[{_compile_desc(acct)}]")


def _stalls(epochs: dict) -> list[dict]:
    """Rank 0's epochs whose blocking wait exceeds the run's median by 10%
    (the first epoch, which compiles, left out)."""
    evs = [by_r[0] for e, by_r in sorted(epochs.items())
           if 0 in by_r and "wait_s" in by_r[0]][1:]
    if len(evs) < 3:
        return []
    waits = sorted(_num(ev["wait_s"]) for ev in evs)
    med = waits[len(waits) // 2]
    return [dict(ev, over_s=_num(ev["wait_s"]) - med) for ev in evs
            if _num(ev["wait_s"]) > 1.1 * med]


def _slots_per_edge(sp: dict) -> str:
    """The residual's real edges and what the bucket geometry lays down for
    each (padding included); empty for a header written before the count."""
    slots = [sp.get(f"residual_slots_{d}") for d in ("fwd", "bwd")]
    edges = [sp.get(f"residual_edges_{d}") for d in ("fwd", "bwd")]
    if None in edges or not all(edges):
        return ""
    return (f" for {edges[0]} / {edges[1]} edges "
            f"({slots[0] / edges[0]:.3f} / {slots[1] / edges[1]:.3f} slots "
            f"an edge)")


def _dense_via(sp: dict) -> str:
    """The dense-tile implementation each direction ran (pallas | xla);
    empty for a header written before the count."""
    paths = [sp.get(f"dense_path_{d}") for d in ("fwd", "bwd")]
    if None in paths:
        return ""
    return (f" via {paths[0]}" if paths[0] == paths[1]
            else f" via {paths[0]} fwd / {paths[1]} bwd")


def _dense_share(sp: dict) -> str:
    """The share of all edges the dense tiles carry and the device bytes of
    the one stack both directions read; empty for a header written before
    the count."""
    share, nbytes = sp.get("dense_share"), sp.get("tile_stack_bytes")
    if share is None or nbytes is None:
        return ""
    return f" ({share:.1%} of all) in one {nbytes / 2**20:.0f} MiB stack"


def _agg_widths(sp: dict) -> str:
    """The columns each aggregation of a step gathers, in layer order, and
    the layers that project before they aggregate (fin -> fout); empty for
    a header written before the count."""
    widths = [sp.get(f"agg_width_{d}") for d in ("fwd", "bwd")]
    if None in widths:
        return ""
    narrow = ", ".join(f"layer {n['layer']} ({n['fin']} -> {n['fout']})"
                       for n in sp.get("narrow_layers") or ())
    return (f" | widths {widths[0]} fwd / {widths[1]} bwd"
            + (f"; projects first: {narrow}" if narrow else ""))


def render(s: dict, write=print):
    if s.get("unknown_kinds"):
        write("WARNING: event kinds outside obs.EVENT_KINDS (build skew?): "
              + " ".join(f"{k}x{n}"
                         for k, n in sorted(s["unknown_kinds"].items())))
    hdr = s["header"]
    if hdr is not None:
        cfg = hdr.get("config", {})
        write(f"run: {cfg.get('dataset', '?')} {cfg.get('model', '?')} "
              f"L={cfg.get('n_layers', '?')} H={cfg.get('n_hidden', '?')} "
              f"rate={cfg.get('sampling_rate', '?')} "
              f"seed={cfg.get('seed', '?')}")
        write(f"mesh: {hdr.get('mesh')} ({hdr.get('replicas')}x"
              f"{hdr.get('parts')}x{hdr.get('feat')} replicas x parts x "
              f"feat) | halo {hdr.get('halo')}/{hdr.get('wire')}: "
              f"{hdr.get('wire_mb_per_exchange')} MB/exchange/device")
        # staleness-bounded refresh (--halo-refresh K > 1 / grad-only) runs
        # carry a steady-state figure next to the peak one
        if hdr.get("halo_mode", "exchange") != "exchange" \
                or int(hdr.get("halo_refresh", 1) or 1) > 1:
            write(f"halo refresh: K={hdr.get('halo_refresh')} "
                  f"mode={hdr.get('halo_mode')} | steady-state "
                  f"{hdr.get('wire_mb_steady')} MB/exchange/device")
        part = hdr.get("partition") or {}
        if part:
            write("partition: " + " ".join(f"{k}={v}"
                                           for k, v in sorted(part.items())))
        sp = hdr.get("spmm") or {}
        if sp:
            # counts where the aggregation's work is defined (per part
            # maxima): what a traced second under `agg_tiles` /
            # `agg_residual` is a rate of
            write(f"spmm: {sp.get('path')} | dense tiles "
                  f"{sp.get('tiles_fwd')} fwd / {sp.get('tiles_bwd')} bwd"
                  + _dense_via(sp) + f" carry {sp.get('dense_edges')} "
                  "edges" + _dense_share(sp) + " | residual slots "
                  f"{sp.get('residual_slots_fwd')} fwd / "
                  f"{sp.get('residual_slots_bwd')} bwd a call"
                  + _slots_per_edge(sp) + " | "
                  f"{sp.get('agg_calls_per_step')} aggregations a step "
                  f"({sp.get('agg_calls_fwd')} fwd + "
                  f"{sp.get('agg_calls_bwd')} bwd)" + _agg_widths(sp))
    # reorder + layout-build get dedicated lines (and are dropped from the
    # generic lifecycle dump below — one record each, better as a summary)
    ro = next((ev for ev in s["lifecycle"] if ev["kind"] == "reorder"), None)
    if ro is not None:
        write(f"reorder: {ro.get('mode')} -> {ro.get('resolved')} "
              f"[{ro.get('algorithm')} t{ro.get('tile')}] tile coverage "
              f"{100 * _num(ro.get('coverage_before')):.1f}% -> "
              f"{100 * _num(ro.get('coverage_after')):.1f}% "
              f"({ro.get('build_ms')} ms"
              + (", order cached" if ro.get("cached") else "") + ")")
    lb = [ev for ev in s["lifecycle"] if ev["kind"] == "layout_build"]
    if lb:
        stages = " + ".join(
            f"{ev.get('stage')} {ev.get('ms')} ms"
            + (" (cached)" if ev.get("cached") else "") for ev in lb)
        write(f"layout build: {stages} | total "
              f"{sum(_num(ev.get('ms')) for ev in lb):.1f} ms")
    if s.get("spans"):
        _render_setup(s["spans"], write, s["epochs"])
    # --tune decision trail as a schedule table (also dropped from the
    # generic lifecycle dump): WHEN each comm lever moved, WHY, and the
    # trigger metrics the controller read — the per-run audit of the
    # closed-loop tuner
    td = [ev for ev in s["lifecycle"] if ev["kind"] == "tune_decision"]
    if td:
        write("")
        write(f"tune schedule ({len(td)} applied decision(s)):")
        write("  epoch   change                          reason")
        for ev in td:
            ch = " ".join(f"{k}={v}" for k, v in sorted(
                (ev.get("changes") or {}).items()))
            trig = ev.get("trigger") or {}
            tr = ("  [" + " ".join(f"{k}={v}"
                                   for k, v in sorted(trig.items())) + "]"
                  if trig else "")
            write(f"  {int(_num(ev.get('epoch'))):5d}   {ch:<30}  "
                  f"{ev.get('reason')}{tr}")
    # elastic RESIZE verdicts as a world-size timeline (also dropped from
    # the generic lifecycle dump): WHEN the world changed, WHY (ranklost
    # shrink vs rejoin grow), where training restarted from, and which
    # rank hosts which parts afterwards
    rz = _resize_verdicts(s)
    if rz:
        write("")
        write(f"elastic resizes ({len(rz)} verdict(s)):")
        write("  epoch   world  trigger   restart  source            parts")
        for ev in rz:
            lost = [int(r) for r in ev.get("lost") or []]
            write(f"  {int(_num(ev.get('epoch'))):5d}   "
                  f"{int(_num(ev.get('old_world')))}->"
                  f"{int(_num(ev.get('world')))}   "
                  f"{str(ev.get('trigger')):<8}  "
                  f"{int(_num(ev.get('restart'))):7d}  "
                  f"{str(ev.get('source')):<16}  "
                  f"{_slots_desc(ev.get('slots'))}"
                  + (f"  (lost {lost})" if lost else ""))
    if s["audits"]:
        write("")
        write("preflight audits:")
        for ev in s["audits"]:
            ok = "clean" if ev.get("ok") else "FAIL"
            if ev["kind"] == "ir_audit":
                scope = f"{ev.get('n_variants')} variant(s)"
            elif ev["kind"] == "perf_audit":
                scope = (f"{ev.get('n_records')} record(s) / "
                         f"{ev.get('n_variants')} variant(s)")
            else:
                scope = (f"{ev.get('n_schedules')} schedule(s) / "
                         f"{ev.get('n_scenarios')} scenario(s)")
            counts = ev.get("counts") or {}
            by_rule = (" [" + " ".join(f"{k}x{v}"
                                       for k, v in sorted(counts.items()))
                       + "]" if counts else "")
            write(f"  {ev['kind']}: {ok} — {scope}, "
                  f"{ev.get('n_findings')} finding(s), "
                  f"{ev.get('errors')} error(s) in {ev.get('elapsed_s')} s"
                  + by_rule)
    epochs = s["epochs"]
    if epochs:
        ranks = sorted({r for by_r in epochs.values() for r in by_r})
        multi = len(ranks) > 1
        write("")
        write("per-epoch" + (f" (ranks {ranks})" if multi else "") + ":")
        # wire column only when epoch records carry the per-epoch figure
        # (duty-cycled under --halo-refresh: full-refresh epochs pay peak,
        # steady epochs the chunk-sized fraction) AND the header gives a
        # peak to compute the saving against
        peak_mb = _num((hdr or {}).get("wire_mb_per_exchange"))
        has_wire = any("wire_mb" in ev for by_r in epochs.values()
                       for ev in by_r.values())
        # the loop's host account (obs.span): the step's two halves and the
        # host wall between the previous loss and this dispatch
        has_host = any("dispatch_s" in ev for by_r in epochs.values()
                       for ev in by_r.values())
        cols = ("  epoch   loss        step_ms   comm_ms[t=traced,"
                "s=sampled]  param_norm  eval")
        write(cols + ("      wire_mb(saved)" if has_wire else "")
              + ("   disp_ms   wait_ms    bnd_ms" if has_host else "")
              + ("  rank" if multi else ""))
        rows = []
        for e in sorted(epochs):
            for r in sorted(epochs[e]):
                ev = epochs[e][r]
                ez = s["evals"].get(e, {})
                acc = next((v for k, v in ez.items() if k.endswith("_acc")),
                           None)
                comm = ev.get("comm_s")
                wire = ""
                if has_wire:
                    w = _num(ev.get("wire_mb"))
                    if math.isfinite(w):
                        saved = (f" (-{(1 - w / peak_mb):.0%})"
                                 if math.isfinite(peak_mb) and peak_mb > 0
                                 and w < peak_mb else "")
                        wire = f"   {w:8.4f}{saved:<8}"
                    else:
                        wire = f"   {'-':>8}{'':<8}"
                rows.append(
                    f"  {e:5d}   {_num(ev.get('loss')):<9.4f}  "
                    f"{_num(ev.get('step_s', 0.0)) * 1e3:8.2f}  "
                    + (f"{_num(comm) * 1e3:7.2f}"
                       f"[{ev.get('comm_tag', '?')[:1]}]{'':<15}"
                       if comm is not None else f"{'-':>9}{'':<17}")
                    + f"  {ev.get('param_norm', ''):<10}  "
                    + (f"{_num(acc):.4f}" if acc is not None else "-")
                    + wire
                    + ("".join(
                        f"  {_num(ev[k]) * 1e3:8.3f}" if k in ev
                        else f"  {'-':>8}"
                        for k in ("dispatch_s", "wait_s", "boundary_s"))
                       if has_host else "")
                    + (f"     r{r}" if multi else "")
                    + ("  recompiled: " + ", ".join(
                        ev["compile"].get("programs") or ["-"])
                       if ev.get("compile") else ""))
        rows, elided = _elide(rows)
        for row in rows:
            write(row)
        if elided:
            write(f"  ... ({len(epochs)} epochs total; middle elided)")
        stalls = _stalls(epochs)
        if stalls:
            write("")
            write(f"stalls (wait over the median by 10%: {len(stalls)} "
                  f"epoch(s); nivcsw up with cpu_s flat = a descheduled "
                  f"host):")
            write("  epoch   wait_ms   over_ms   nivcsw  majflt    cpu_s")
            for ev in stalls[:20]:
                write(f"  {int(ev['epoch']):5d}  {_num(ev['wait_s']) * 1e3:8.2f}"
                      f"  {ev['over_s'] * 1e3:8.2f}  {ev.get('nivcsw', '-'):>7}"
                      f"  {ev.get('majflt', '-'):>6}  {ev.get('cpu_s', '-'):>7}")
        # comm vs compute (the first recorded epoch carries the XLA compile
        # and would dominate a raw mean — drop it when there is more data)
        es = sorted(epochs)
        body = es[1:] if len(es) > 3 else es
        steps = [ev.get("step_s") for e in body
                 for ev in epochs[e].values()]
        comms = [ev.get("comm_s") for e in body for ev in epochs[e].values()
                 if ev.get("comm_tag") == "traced"]
        tag = "traced"
        if not comms:
            comms = [ev.get("comm_s") for e in body
                     for ev in epochs[e].values()
                     if ev.get("comm_s") is not None]
            tag = "sampled"
        mt, mc = _mean(steps), _mean(comms)
        write("")
        write(f"comm vs compute (excl. compile epoch): step {mt * 1e3:.2f} "
              f"ms | comm [{tag}] {mc * 1e3:.2f} ms"
              + (f" ({mc / mt:.0%} of step)" if mt > 0 else ""))
    for tr in s["traces"]:
        td = tr.get("trace_dir")
        line = (f"trace @E{tr.get('epoch')}: comm {tr.get('comm_s', 0) * 1e3:.2f} ms "
                f"reduce {tr.get('reduce_s', 0) * 1e3:.2f} ms per step")
        if td and os.path.isdir(td):
            # the trace still exists: re-derive the split from device spans
            from bnsgcn_tpu.utils import traceparse
            try:
                # `exchanges`: whether the traced program exchanges at all
                # (False at 1 part / grad-only), as the run recorded it
                parsed = traceparse.step_comm_per_epoch(
                    td, tr.get("exchanges", True))
                line += (f" | re-parsed from {td}: exchange "
                         f"{parsed[0] * 1e3:.2f} ms reduce "
                         f"{parsed[1] * 1e3:.2f} ms over {parsed[2]} steps")
            except traceparse.TraceError as ex:
                line += f" | re-parse of {td} failed: {ex}"
        write(line)
        t_open = tr.get("start_wall")
        if t_open is not None and epochs:
            # the obs events of the traced epochs on the trace's clock:
            # seconds after start_trace at which each `epoch` event was
            # written (the window closes inside the epoch that emits `trace`;
            # `ts` is rounded to the millisecond, so the epoch before the
            # window can read a hair after its opening)
            laid = [(e, _num(by_r[0].get("ts")) - _num(t_open))
                    for e, by_r in sorted(epochs.items()) if 0 in by_r
                    and _num(by_r[0].get("ts")) - _num(t_open) >= 1e-3
                    and e <= int(_num(tr.get("epoch")))]
            write(f"  window opened at {t_open} (wall clock); epoch events "
                  f"at " + " ".join(f"E{e} +{dt:.3f}s" for e, dt in laid))
    life = [ev for ev in s["lifecycle"]
            if ev["kind"] not in ("reorder", "layout_build",
                                  "tune_decision", "resize")]
    if life:
        write("")
        write("lifecycle:")
        for ev in life:
            extra = {k: v for k, v in ev.items()
                     if k not in ("ts", "kind", "rank")}
            write(f"  r{ev.get('rank', 0)} {ev['kind']}: "
                  + " ".join(f"{k}={v}" for k, v in sorted(extra.items())))
    if s["epoch_ranks"]:
        write("")
        write(f"cross-rank epochs (merged by rank 0, "
              f"{len(s['epoch_ranks'])} records):")
        rows = []
        for ev in s["epoch_ranks"]:
            ranks = ev.get("ranks", {})
            rows.append(f"  E{ev.get('epoch'):5d} [{ev.get('decision')}] "
                        + " | ".join(
                            f"r{r}: loss {i.get('loss')} "
                            f"step {i.get('step_ms')} ms"
                            # numeric sort: JSON keys are strings, and a
                            # world >= 10 must not render r10 before r2
                            for r, i in sorted(
                                ranks.items(),
                                key=lambda kv: (not kv[0].isdigit(),
                                                int(kv[0])
                                                if kv[0].isdigit()
                                                else kv[0]))))
        rows, elided = _elide(rows)
        for row in rows:
            write(row)
        if elided:
            write("  ...")
    if s["serve"] is not None:
        sv = s["serve"]
        write("")
        write("serving:")
        write(f"  {sv.get('requests')} requests (A {sv.get('tier_a')} / B "
              f"{sv.get('tier_b')}), {sv.get('deltas')} deltas, "
              f"{sv.get('refreshed_nodes')} rows refreshed")
        write(f"  tier A p50 {sv.get('tier_a_p50_ms')} ms p99 "
              f"{sv.get('tier_a_p99_ms')} ms | tier B p50 "
              f"{sv.get('tier_b_p50_ms')} ms p99 {sv.get('tier_b_p99_ms')} ms")
        write(f"  refresh lag p50 {sv.get('refresh_lag_p50_s')} s p99 "
              f"{sv.get('refresh_lag_p99_s')} s")
    shards = [ev for ev in s.get("serve_drains", []) if "backend" in ev]
    fleet = s.get("serve_fleet")
    if shards or fleet is not None:
        write("")
        write("serving fleet:")
        if fleet is not None:
            write(f"  router: {fleet.get('requests')} requests routed "
                  f"(A {fleet.get('tier_a')} / B {fleet.get('tier_b')}) | "
                  f"{fleet.get('deltas')} deltas over "
                  f"{fleet.get('fanout_rpcs')} fan-out RPCs | "
                  f"{fleet.get('evictions')} evictions | "
                  f"{fleet.get('parts')}x{fleet.get('replicas')} "
                  f"parts x replicas"
                  + (f" | {fleet.get('shutdown_acked')} shutdown ack(s)"
                     if fleet.get("shutdown_acked") is not None else ""))
            if fleet.get("availability") is not None:
                write(f"  availability: {_num(fleet.get('availability')):.4f} "
                      f"(ok {fleet.get('requests_ok')} / degraded "
                      f"{fleet.get('requests_degraded')} / failed "
                      f"{fleet.get('requests_failed')}) | "
                      f"{fleet.get('failovers')} failover(s), p99 "
                      f"{_num(fleet.get('failover_p99_ms')):.2f} ms | "
                      f"{fleet.get('recoveries')} recovery(ies)"
                      + (f", last outage "
                         f"{_num(fleet.get('recovery_s')):.2f} s"
                         if fleet.get("recovery_s") is not None else "")
                      + f" | WAL {fleet.get('wal_queued')} queued / "
                        f"{fleet.get('wal_replayed')} replayed")
        if shards:
            write("  backend   req(A/B)        A p50/p99 ms    "
                  "B p50/p99 ms    lag p99 s  queue  halo hit/fetch")
        for ev in sorted(shards, key=lambda e: (_num(e.get("part")),
                                                _num(e.get("replica")))):
            reqs = (f"{ev.get('requests')}"
                    f"({ev.get('tier_a')}/{ev.get('tier_b')})")
            write(f"  {ev.get('backend', '?'):<8}  {reqs:<14}  "
                  f"{_num(ev.get('tier_a_p50_ms')):6.2f}/"
                  f"{_num(ev.get('tier_a_p99_ms')):<7.2f}  "
                  f"{_num(ev.get('tier_b_p50_ms')):6.2f}/"
                  f"{_num(ev.get('tier_b_p99_ms')):<7.2f}  "
                  f"{_num(ev.get('refresh_lag_p99_s')):9.3f}  "
                  f"{ev.get('queue_depth', '-'):>5}  "
                  f"{ev.get('halo_hits', 0)}/{ev.get('halo_fetches', 0)}")
    if s.get("continual"):
        cycles = [ev for ev in s["continual"]
                  if ev["kind"] == "continual_cycle"]
        updates = {int(_num(ev.get("cycle"))): ev for ev in s["continual"]
                   if ev["kind"] == "artifact_update"}
        promotes = [ev for ev in s["continual"] if ev["kind"] == "promote"]
        write("")
        write("continual training:")
        if any(not ev.get("noop") for ev in cycles):
            write("  cycle  deltas       fold            before    after  "
                  "   d_acc    outcome")
        for ev in sorted(cycles, key=lambda e: _num(e.get("cycle"))):
            c = int(_num(ev.get("cycle")))
            if ev.get("noop"):
                write(f"  {c:5d}  no-op (cursor {ev.get('consumed')}, "
                      f"source {ev.get('source', '?')})")
                continue
            upd = updates.get(c, {})
            fold = "repartition" if ev.get("repartitioned") else "incremental"
            if not ev.get("repartitioned") and "touched" in upd:
                fold += f"({len(upd['touched'])}p)"
            ba, aa = _num(ev.get("before_acc")), _num(ev.get("after_acc"))
            span = (f"[{ev.get('consumed_from')},"
                    f"{ev.get('consumed_to')})")
            write(f"  {c:5d}  {span:<11}  {fold:<14}  {ba:<8.4f}  "
                  f"{aa:<8.4f} {aa - ba:+8.4f}   "
                  + ("promoted" if ev.get("promoted") else "rolled_back"))
        # serving-side adoption events (a serve log replaying promotions
        # shows these without any continual_cycle records alongside)
        for ev in promotes:
            st = ev.get("status", "?")
            if st == "adopted":
                write(f"  promote adopted: cycle {ev.get('cycle')} "
                      f"(tail {ev.get('tail')} -> {ev.get('dirty')} dirty)")
            else:
                write(f"  promote {st}: {ev.get('reason', '?')}")
    if s["bench"]:
        write("")
        write("bench variants:")
        has_pred = any("predicted_step_s" in ev for ev in s["bench"])
        resids = []
        for ev in s["bench"]:
            line = (f"  {ev.get('name'):<32} {ev.get('epoch_s')} s/epoch "
                    f"(min {ev.get('min_epoch_s')}) loss {ev.get('loss')} "
                    f"[{ev.get('backend')}]")
            if has_pred and "predicted_step_s" in ev:
                p, m = _num(ev["predicted_step_s"]), _num(ev.get("epoch_s"))
                if math.isfinite(p) and math.isfinite(m) and m > 0:
                    resids.append(p / m - 1.0)
                    line += (f" | predicted {p} s "
                             f"({p / m - 1.0:+.1%} residual)")
                else:
                    line += f" | predicted {ev['predicted_step_s']} s"
            write(line)
        if resids:
            # graftperf calibration health in one line: where the model's
            # predictions landed against THIS log's measurements (gate 4
            # audits the committed records; this audits the live window)
            rs = sorted(abs(r) for r in resids)
            write(f"  perf prediction: {len(resids)} predicted cell(s), "
                  f"|residual| median {rs[len(rs) // 2]:.1%} "
                  f"max {rs[-1]:.1%}")
    end = s["run_end"]
    if end is not None:
        write("")
        if "interrupted" in end:
            write(f"run INTERRUPTED by {end['interrupted']} after "
                  f"{end.get('epochs_done')} epochs (final loss "
                  f"{end.get('final_loss')})")
        else:
            write(f"run end: epoch {end.get('epoch_time_s')} s | final loss "
                  f"{end.get('final_loss')} | best val "
                  f"{end.get('best_val_acc')} | test {end.get('test_acc')} | "
                  f"{end.get('rollbacks')} rollback(s)")


def compare(sa: dict, sb: dict, name_a: str, name_b: str, write=print):
    """Epoch-aligned trajectory diff: the bench-window audit."""
    write(f"compare: A = {name_a}")
    write(f"         B = {name_b}")
    for tag, s in (("A", sa), ("B", sb)):
        hdr = s["header"] or {}
        cfg = hdr.get("config", {})
        write(f"  {tag}: {cfg.get('model', '?')} spmm={cfg.get('spmm', '?')} "
              f"halo={hdr.get('halo', '?')}/{hdr.get('wire', '?')} mesh="
              f"{hdr.get('mesh', '?')} wire_mb={hdr.get('wire_mb_per_exchange')}"
              f" halo_refresh={hdr.get('halo_refresh', 1)}"
              f" steady_mb={hdr.get('wire_mb_steady')}"
              f" reorder={cfg.get('reorder', 'off')}")
    ka = ((sa["header"] or {}).get("halo_refresh", 1),
          (sa["header"] or {}).get("halo_mode", "exchange"))
    kb = ((sb["header"] or {}).get("halo_refresh", 1),
          (sb["header"] or {}).get("halo_mode", "exchange"))
    if ka != kb:
        # the comm split differs BY DESIGN between these runs — step/loss
        # deltas below mix a staleness effect with everything else
        write(f"  NOTE: halo refresh differs (A K={ka[0]} mode={ka[1]} vs "
              f"B K={kb[0]} mode={kb[1]}) — comm volume and staleness are "
              f"part of the trajectory delta")
    ra = ((sa["header"] or {}).get("config", {}) or {}).get("reorder", "off")
    rb = ((sb["header"] or {}).get("config", {}) or {}).get("reorder", "off")
    if ra != rb:
        # row order changes sum-reduction pairing: losses ULP-drift apart
        # even when the math is the same aggregation
        write(f"  NOTE: reorder differs (A {ra} vs B {rb}) — step-time "
              f"deltas include the tile-coverage effect, and loss deltas "
              f"at round-off scale are expected from the row permutation")
    # tuned-vs-static diff: a run with tune_decision events changes
    # K/mode/strategy/wire MID-RUN, so the header comparison above only
    # describes its launch point — name every retune epoch explicitly
    ta = [ev for ev in sa["lifecycle"] if ev["kind"] == "tune_decision"]
    tb = [ev for ev in sb["lifecycle"] if ev["kind"] == "tune_decision"]
    if ta or tb:
        def _trail(evs):
            return ", ".join(
                f"E{int(_num(ev.get('epoch')))}:" + "/".join(
                    f"{k}={v}" for k, v in sorted(
                        (ev.get("changes") or {}).items()))
                for ev in evs) or "static"
        write(f"  NOTE: --tune retuned the comm stack mid-run "
              f"(A: {_trail(ta)} | B: {_trail(tb)}) — step/wire deltas past "
              f"those epochs are schedule effects, not noise")
    # elastic-resize divergence: a shrink refolds the sampling/dropout
    # streams under a fresh resize nonce, so two runs whose RESIZE trails
    # differ part ways AT the earliest differing resize epoch by design
    za, zb = _resize_verdicts(sa), _resize_verdicts(sb)
    if za or zb:
        def _rtrail(evs):
            return ", ".join(
                f"E{int(_num(ev.get('epoch')))}:{ev.get('trigger')} "
                f"{int(_num(ev.get('old_world')))}->"
                f"{int(_num(ev.get('world')))}"
                for ev in evs) or "none"
        if _rtrail(za) != _rtrail(zb):
            first = min(int(_num(ev.get("epoch"))) for ev in za + zb)
            write(f"  NOTE: elastic RESIZE trails differ (A: {_rtrail(za)} "
                  f"| B: {_rtrail(zb)}) — a shrink refolds the sampling/"
                  f"dropout streams under a new resize nonce, so loss "
                  f"deltas from epoch {first} on are the resize effect, "
                  f"not noise")
    if sa["bench"] or sb["bench"]:
        by = {}
        for tag, s in (("a", sa), ("b", sb)):
            for ev in s["bench"]:
                by.setdefault(ev.get("name"), {})[tag] = ev
        write("")
        write("  variant                          A s/epoch   B s/epoch   B/A")
        for name in sorted(by):
            a, b = by[name].get("a"), by[name].get("b")
            ea = a.get("epoch_s") if a else None
            eb = b.get("epoch_s") if b else None
            ratio = (f"{eb / ea:.3f}" if ea and eb else "-")
            write(f"  {name:<32} {ea if ea is not None else '-':>9}   "
                  f"{eb if eb is not None else '-':>9}   {ratio}")
    # continual-cycle accuracy trajectories: aligned per cycle index, the
    # within-cycle fine-tune gain for each run plus the A-vs-B gap after
    # each promotion decision
    ca = {int(_num(ev.get("cycle"))): ev for ev in sa.get("continual", [])
          if ev.get("kind") == "continual_cycle" and not ev.get("noop")}
    cb = {int(_num(ev.get("cycle"))): ev for ev in sb.get("continual", [])
          if ev.get("kind") == "continual_cycle" and not ev.get("noop")}
    if ca or cb:
        write("")
        write("  cycle   after_A   gain_A    after_B   gain_B    "
              "dafter(B-A)")
        for c in sorted(set(ca) | set(cb)):
            a, b = ca.get(c), cb.get(c)

            def _cell(ev):
                if ev is None:
                    return "-", "-"
                aa = _num(ev.get("after_acc"))
                ga = aa - _num(ev.get("before_acc"))
                mark = "" if ev.get("promoted") else "*"
                return f"{aa:.4f}{mark}", f"{ga:+.4f}"
            av, ag = _cell(a)
            bv, bg = _cell(b)
            d = (f"{_num(b.get('after_acc')) - _num(a.get('after_acc')):+9.4f}"
                 if a is not None and b is not None else "        -")
            write(f"  {c:5d}   {av:<8}  {ag:<8}  {bv:<8}  {bg:<8}  {d}")
        if any(not ev.get("promoted") for ev in
               list(ca.values()) + list(cb.values())):
            write("  (* = cycle rolled back: fine-tune failed the "
                  "validation gate, serving kept prior weights)")
    ea = {e: list(r.values())[0] for e, r in sa["epochs"].items()}
    eb = {e: list(r.values())[0] for e, r in sb["epochs"].items()}
    shared = sorted(set(ea) & set(eb))
    if shared:
        write("")
        write("  epoch   loss_A     loss_B     dloss      step_A_ms  step_B_ms")
        rows = []
        for e in shared:
            la, lb = _num(ea[e].get("loss")), _num(eb[e].get("loss"))
            rows.append(f"  {e:5d}   {la:<9.4f}  {lb:<9.4f}  "
                        f"{(lb - la):+9.4f}  "
                        f"{_num(ea[e].get('step_s', 0)) * 1e3:9.2f}  "
                        f"{_num(eb[e].get('step_s', 0)) * 1e3:9.2f}")
        rows, elided = _elide(rows)
        for row in rows:
            write(row)
        if elided:
            write(f"  ... ({len(shared)} shared epochs; middle elided)")
        body = shared[1:] if len(shared) > 3 else shared   # drop compile epoch
        ma = _mean([ea[e].get("step_s") for e in body])
        mb = _mean([eb[e].get("step_s") for e in body])
        write(f"  mean step (excl. compile epoch): A {ma * 1e3:.2f} ms | "
              f"B {mb * 1e3:.2f} ms"
              + (f" | B/A {mb / ma:.3f}" if ma > 0 else ""))
    for tag, s in (("A", sa), ("B", sb)):
        end = s["run_end"] or {}
        if end:
            write(f"  {tag} end: final loss {end.get('final_loss')} "
                  f"epoch {end.get('epoch_time_s')} s "
                  + (f"(interrupted: {end['interrupted']})"
                     if "interrupted" in end else ""))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("logs", nargs="*", help="obs JSONL log(s) of ONE run "
                   "(rank siblings PATH.r<N> auto-discovered)")
    p.add_argument("--compare", nargs=2, metavar=("RUN_A", "RUN_B"),
                   help="diff two runs' logs epoch-by-epoch instead")
    p.add_argument("--json", action="store_true",
                   help="emit the structured summary as one JSON line")
    args = p.parse_args(argv)
    if args.compare:
        sa = summarize(load_run([args.compare[0]]))
        sb = summarize(load_run([args.compare[1]]))
        if args.json:
            print(json.dumps({"a": sa["run_end"], "b": sb["run_end"]},
                             default=str))
        else:
            compare(sa, sb, args.compare[0], args.compare[1])
        return 0
    if not args.logs:
        p.error("give at least one obs log (or --compare A B)")
    events = load_run(args.logs)
    if not events:
        print(f"no parseable events in {args.logs}", file=sys.stderr)
        return 1
    s = summarize(events)
    if args.json:
        print(json.dumps(s, default=str))
    else:
        render(s)
    return 0


if __name__ == "__main__":
    sys.exit(main())
