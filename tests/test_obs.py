"""Unified telemetry bus (bnsgcn_tpu/obs.py) + its wiring.

Unit level: the streaming histogram against known-quantile inputs (the
fixed-log-bucket error bound), registry snapshots, event-log rotation bound
and strict-JSON sanitization. Integration level: `--obs off` is pinned
bitwise against `on` (the bus must never perturb training math), a real
`--inject nan@..` CLI run leaves header + epoch + rollback + run_end events
that tools/obs_report.py renders without error [quickgate], and a genuine
2-process coordinated run produces rank 0's merged cross-rank epoch record
(the agree_step piggyback — no extra collective) [quickgate]. Serving:
`stats` carries registry-backed per-tier p50/p99 + refresh lag, and the
`metrics` op serves the full registry snapshot.
"""

import json
import os
import re
import socket
import subprocess
import sys

import numpy as np
import pytest

from bnsgcn_tpu import obs as obs_mod
from bnsgcn_tpu.config import Config, parse_config
from bnsgcn_tpu.data.graph import sbm_graph

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ----------------------------------------------------------------------------
# histogram / registry units
# ----------------------------------------------------------------------------

def test_histogram_known_quantiles():
    """1..1000 observed in shuffled order: every quantile must land within
    the documented bucket error bound (sqrt(growth) - 1 ~= 4.4% at the
    default growth) of the exact order statistic."""
    h = obs_mod.Histogram()
    vals = np.arange(1, 1001, dtype=np.float64)
    rng = np.random.default_rng(0)
    rng.shuffle(vals)
    for v in vals:
        h.observe(float(v))
    assert h.count == 1000
    assert h.total == pytest.approx(float(vals.sum()))
    assert h.vmin == 1.0 and h.vmax == 1000.0
    for q, exact in ((50, 500.0), (90, 900.0), (99, 990.0)):
        got = h.percentile(q)
        assert abs(got - exact) <= 0.06 * exact, (q, got, exact)
    snap = h.snapshot()
    assert snap["count"] == 1000 and snap["max"] == 1000.0
    assert snap["p50"] == pytest.approx(h.percentile(50))


def test_histogram_empty_single_and_clamping():
    h = obs_mod.Histogram()
    assert h.percentile(50) == 0.0
    assert h.snapshot()["count"] == 0
    h.observe(3.7)
    # a one-sample histogram must report the sample, not a bucket midpoint
    # outside [vmin, vmax]
    assert h.percentile(50) == pytest.approx(3.7)
    assert h.percentile(99) == pytest.approx(3.7)
    h2 = obs_mod.Histogram()
    h2.observe(0.0)         # underflow bucket (below lo)
    h2.observe(1e9)         # overflow bucket
    h2.observe(float("nan"))    # non-finite: dropped, never a crash
    h2.observe(float("inf"))
    assert h2.count == 2
    assert h2.percentile(1) == pytest.approx(0.0)
    assert h2.percentile(99) == pytest.approx(1e9)


def test_registry_snapshot_and_idempotent_instruments():
    r = obs_mod.Registry()
    c = r.counter("a/b")
    c.inc()
    c.inc(4)
    assert r.counter("a/b") is c            # creation is idempotent
    r.gauge("g").set(2.5)
    r.histogram("h").observe(10.0)
    snap = r.snapshot()
    assert snap["counters"]["a/b"] == 5
    assert snap["gauges"]["g"] == 2.5
    assert snap["histograms"]["h"]["count"] == 1


# ----------------------------------------------------------------------------
# event log: rank tag, rotation bound, strict JSON
# ----------------------------------------------------------------------------

def test_eventlog_emit_and_load(tmp_path):
    path = str(tmp_path / "obs.jsonl")
    ev = obs_mod.EventLog(path, rank=3)
    ev.emit("epoch", epoch=1, loss=0.5)
    ev.emit("rollback", epoch=2, restart=1)
    ev.close()
    got = obs_mod.load_events(path)
    assert [e["kind"] for e in got] == ["epoch", "rollback"]
    assert all(e["rank"] == 3 and "ts" in e for e in got)


def test_eventlog_rotation_bound(tmp_path):
    """A size-capped log rotates once (PATH.1) and total disk stays bounded
    at ~2x the cap no matter how many events land."""
    path = str(tmp_path / "obs.jsonl")
    ev = obs_mod.EventLog(path, max_bytes=2000)
    for i in range(300):
        ev.emit("epoch", epoch=i, loss=1.0 / (i + 1))
    ev.close()
    assert os.path.exists(path) and os.path.exists(path + ".1")
    total = os.path.getsize(path) + os.path.getsize(path + ".1")
    assert total <= 2 * 2000 + 200      # one event of slack per file
    # both generations parse, and load_events stitches them oldest-first
    got = obs_mod.load_events(path)
    assert len(got) >= 2
    assert got[0]["epoch"] < got[-1]["epoch"]


def test_eventlog_nan_is_strict_json(tmp_path):
    """The rollback event's whole point is recording a NaN loss — the line
    must still parse under a STRICT reader (no bare NaN token)."""
    path = str(tmp_path / "obs.jsonl")
    ev = obs_mod.EventLog(path)
    ev.emit("rollback", loss=float("nan"), inf=float("inf"),
            nested={"v": float("nan")})
    ev.close()
    line = open(path).read().strip()

    def no_const(_):
        raise AssertionError("non-strict JSON constant in event line")

    rec = json.loads(line, parse_constant=no_const)
    assert rec["loss"] == "nan" and rec["nested"]["v"] == "nan"


def test_rank_log_path_and_make_obs(tmp_path):
    assert obs_mod.rank_log_path("/x/o.jsonl", 0) == "/x/o.jsonl"
    assert obs_mod.rank_log_path("/x/o.jsonl", 2) == "/x/o.jsonl.r2"
    cfg = Config(obs="off", obs_log=str(tmp_path / "o.jsonl"))
    assert obs_mod.make_obs(cfg, log=lambda *a: None) is None
    cfg = Config(obs="on", obs_log=str(tmp_path / "o.jsonl"))
    obs = obs_mod.make_obs(cfg, rank=1, log=lambda *a: None)
    obs.emit("x")
    obs.close()
    assert os.path.exists(str(tmp_path / "o.jsonl.r1"))


def test_obs_report_renders_nan_sanitized_records(tmp_path):
    """A --resilience off diverged run logs epoch records with loss "nan"
    (the strict-JSON sanitization); the report tool must render — not
    crash on — exactly the log it exists to triage."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import obs_report
    finally:
        sys.path.pop(0)
    path = str(tmp_path / "div.jsonl")
    ev = obs_mod.EventLog(path)
    for e in range(3):
        ev.emit("epoch", epoch=e, loss=float("nan") if e else 1.2,
                step_s=0.01, comm_s=float("nan"), comm_tag="sampled")
    ev.emit("eval", epoch=2, val_acc=float("nan"))
    ev.close()
    s = obs_report.summarize(obs_report.load_run([path]))
    lines = []
    obs_report.render(s, write=lines.append)
    assert any("nan" in ln for ln in lines)
    obs_report.compare(s, s, path, path, write=lines.append)


def test_obs_report_elastic_resize_section_and_compare_note(tmp_path):
    """An elastic run's resize events (every member mirrors the agreed
    verdict into its own rank log) render as ONE de-duplicated world-size
    timeline, and --compare flags a resize-trail difference as a NOTE —
    the trajectories part ways at the shrink epoch by design."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import obs_report
    finally:
        sys.path.pop(0)
    path = str(tmp_path / "elastic.jsonl")
    ev = obs_mod.EventLog(path)
    for rank in (0, 1):     # rank 1's mirror of the same shrink verdict
        ev.emit("resize", rank=rank, epoch=3, old_world=2, world=1,
                members=[0], lost=[1], slots=[0, 0], trigger="ranklost",
                nonce=1, restart=2, source="ckpt_E1.ckpt")
    ev.emit("resize", rank=0, epoch=5, old_world=1, world=2,
            members=[0, 1], lost=[], slots=[0, 1], trigger="rejoin",
            nonce=1, restart=2, source="ckpt_E1.ckpt")
    ev.close()
    s = obs_report.summarize(obs_report.load_run([path]))
    assert len(obs_report._resize_verdicts(s)) == 2     # mirrors collapsed
    lines = []
    obs_report.render(s, write=lines.append)
    text = "\n".join(lines)
    assert "elastic resizes (2 verdict(s)):" in text
    assert "2->1   ranklost" in text and "(lost [1])" in text
    assert "1->2   rejoin" in text
    assert "r0:[p0,p1]" in text and "r0:[p0] r1:[p1]" in text
    # --compare: a resized run vs an uninterrupted one gets the NOTE...
    plain = str(tmp_path / "plain.jsonl")
    pv = obs_mod.EventLog(plain)
    pv.emit("epoch", epoch=0, loss=1.0, step_s=0.01)
    pv.close()
    sp = obs_report.summarize(obs_report.load_run([plain]))
    lines = []
    obs_report.compare(sp, s, plain, path, write=lines.append)
    note = next(ln for ln in lines if "elastic RESIZE" in ln)
    assert "A: none" in note and "E3:ranklost 2->1" in note
    assert "from epoch 3 on" in note
    # ...while identical resize trails stay silent
    lines = []
    obs_report.compare(s, s, path, path, write=lines.append)
    assert not any("elastic RESIZE" in ln for ln in lines)


def test_obs_report_serving_fleet_section(tmp_path):
    """Sharded-serving logs (router rank 0 + backend `.rN` siblings) render
    a per-backend fleet table plus the router fan-out line, while the
    legacy single-host `serve` slot keeps its meaning: it only ever holds a
    drain record WITHOUT a backend tag."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import obs_report
    finally:
        sys.path.pop(0)
    path = str(tmp_path / "fleet.jsonl")
    ev = obs_mod.EventLog(path)       # rank 0 = the router
    ev.emit("serve_fleet", parts=2, replicas=1, shutdown_acked=2,
            requests=40, tier_a=36, tier_b=4, deltas=3, fanout_rpcs=9,
            evictions=0)
    ev.close()
    for part in (0, 1):               # backend shards on sibling logs
        bev = obs_mod.EventLog(obs_mod.rank_log_path(path, 1 + part))
        bev.emit("serve_drain", requests=20, tier_a=18, tier_b=2,
                 deltas=3, refreshed_nodes=5, part=part, replica=0,
                 backend=f"p{part}.r0", n_own=150, queue_depth=0,
                 tier_a_p50_ms=0.4, tier_a_p99_ms=1.1, tier_b_p50_ms=8.0,
                 tier_b_p99_ms=20.0, refresh_lag_p50_s=0.01,
                 refresh_lag_p99_s=0.05, halo_cached=7, halo_fetches=2,
                 halo_hits=11)
        bev.close()
    s = obs_report.summarize(obs_report.load_run([path]))
    assert s["serve"] is None                 # no untagged drain in this log
    assert len(s["serve_drains"]) == 2
    assert s["serve_fleet"]["fanout_rpcs"] == 9
    lines = []
    obs_report.render(s, write=lines.append)
    text = "\n".join(lines)
    assert "serving fleet:" in text
    assert "p0.r0" in text and "p1.r0" in text
    assert "9 fan-out RPCs" in text
    # a single-host drain (no backend tag) still lands in the legacy slot
    s2 = obs_report.summarize([{"kind": "serve_drain", "requests": 1,
                                "ts": 0.0}])
    assert s2["serve"] is not None and s2["serve_drains"]


def test_write_postmortem_failure_returns_empty():
    """An unwritable post-mortem dir returns "" (no breadcrumb to a ghost
    file) instead of a path that was never written."""
    assert obs_mod.write_postmortem("/proc/nonexistent/pm", "t") == ""


def test_eventlog_unwritable_path_degrades_not_raises(capsys):
    """An unwritable $BNSGCN_OBS_LOG must degrade to a no-log run at
    construction — never crash-loop a requeued relaunch before training."""
    ev = obs_mod.EventLog("/proc/nonexistent/obs.jsonl")
    ev.emit("epoch", epoch=0)       # no-op, no raise
    ev.close()
    assert "telemetry log disabled" in capsys.readouterr().err


def test_eventlog_bad_max_mb_env_degrades(tmp_path, monkeypatch, capsys):
    monkeypatch.setenv("BNSGCN_OBS_MAX_MB", "64MB")
    ev = obs_mod.EventLog(str(tmp_path / "o.jsonl"))
    assert ev.max_bytes == 64 * 2 ** 20
    ev.emit("x")
    ev.close()
    assert "bad $BNSGCN_OBS_MAX_MB" in capsys.readouterr().err


def test_eventlog_emit_bounded_skips_on_held_lock(tmp_path):
    """The watchdog's exit-path emit must give up on a held writer lock
    (a disk-stalled main thread inside emit) instead of deadlocking the
    os._exit(77) escape hatch."""
    ev = obs_mod.EventLog(str(tmp_path / "o.jsonl"))
    ev.emit("a")
    assert ev._lock.acquire()       # simulate a stalled writer holding it
    try:
        t0 = __import__("time").monotonic()
        ev.emit_bounded("watchdog_fire", timeout_s=0.2)
        assert __import__("time").monotonic() - t0 < 2.0
    finally:
        ev._lock.release()
    ev.emit_bounded("b")            # lock free again: this one lands
    ev.close()
    kinds = [e["kind"] for e in obs_mod.load_events(str(tmp_path / "o.jsonl"))]
    assert kinds == ["a", "b"]      # the blocked emit was skipped, not queued


def test_write_postmortem(tmp_path):
    r = obs_mod.Registry()
    r.counter("c").inc()
    path = obs_mod.write_postmortem(str(tmp_path / "pm"), "watchdog_E3",
                                    text="hung", registry=r)
    body = open(path).read()
    assert "hung" in body and "all-thread stacks" in body
    metrics = path.replace(".txt", "_metrics.json")
    assert json.load(open(metrics))["counters"]["c"] == 1


def test_cli_obs_flags_parse():
    cfg = parse_config(["--obs", "off", "--obs-log", "/tmp/x.jsonl",
                        "--obs-dir", "/tmp/pm"])
    assert (cfg.obs, cfg.obs_log, cfg.obs_dir) == ("off", "/tmp/x.jsonl",
                                                   "/tmp/pm")
    assert parse_config([]).obs == "on"


# ----------------------------------------------------------------------------
# host spans: obs.span
# ----------------------------------------------------------------------------

def test_span_nesting_parent_and_exclusive_accumulator(tmp_path):
    import time
    ob = obs_mod.Obs(str(tmp_path / "o.jsonl"))
    with obs_mod.span(ob, "guard", emit=True) as outer:
        time.sleep(0.01)
        with obs_mod.span(ob, "norm_probe", emit=True) as inner:
            time.sleep(0.02)
        assert inner.parent == "guard" and ob._spans == [outer]
    with obs_mod.span(ob, "guard"):
        time.sleep(0.005)
    assert ob._spans == [] and outer.parent is None
    assert inner.dur_s >= 0.02 and outer.dur_s >= inner.dur_s + 0.01
    acc = ob.take_phases()
    # a name's seconds are its own: the child's are taken out, and two
    # spans of one name add up
    assert set(acc) == {"guard", "norm_probe"}
    assert abs(acc["norm_probe"] - inner.dur_s) < 1e-5
    assert 0.015 <= acc["guard"] < outer.dur_s - inner.dur_s + 0.02
    assert ob.take_phases() == {}                  # the account starts anew
    ob.close()
    ev = [e for e in obs_mod.load_events(str(tmp_path / "o.jsonl"))]
    assert [(e["kind"], e["name"], e["parent"]) for e in ev] == [
        ("span", "norm_probe", "guard"), ("span", "guard", None)]
    assert ev[1]["t0"] <= ev[0]["t0"] and ev[1]["dur_s"] >= ev[0]["dur_s"]


def test_span_begin_end_and_a_span_left_open_by_a_raise(tmp_path):
    ob = obs_mod.Obs("")
    sp = obs_mod.span(ob, "pre").begin()
    with pytest.raises(RuntimeError):
        with obs_mod.span(ob, "trace_io"):
            obs_mod.span(ob, "log").begin()         # never ended
            raise RuntimeError("inside")
    assert ob._spans == [sp]                        # the stack healed
    sp.end()
    assert ob._spans == [] and set(ob.take_phases()) == {"pre", "trace_io"}


def test_span_under_obs_off_constructs_nothing():
    off = obs_mod.span(None, "pre")
    assert off is obs_mod.span(None, "wait", emit=True) is obs_mod.NO_SPAN
    assert not isinstance(off, obs_mod.Span)
    with off as sp:
        assert sp.begin() is sp and sp.end() is None and sp.dur_s == 0.0


def test_first_call_span_and_rusage_deltas(tmp_path):
    ob = obs_mod.Obs(str(tmp_path / "o.jsonl"))
    for s in (1.0, 0.1, 0.3, 0.2, 0.25):
        ob.note_call("train_step", s)               # emitted at the fourth
    ob.note_call("param_global_norm", 0.5)
    ob.note_call("param_global_norm", 0.1)
    ob.note_call("once", 0.7)                       # nothing to hold it against
    ob.flush_first_calls()
    ob.flush_first_calls()                          # idempotent
    d = ob.rusage_delta()
    assert d == {"cpu_s": 0.0, "nivcsw": 0, "majflt": 0}    # the baseline
    sum(i * i for i in range(200000))
    d = ob.rusage_delta()
    assert d["cpu_s"] > 0 and d["nivcsw"] >= 0 and d["majflt"] >= 0
    ob.close()
    ev = {e["name"]: e for e in obs_mod.load_events(str(tmp_path / "o.jsonl"))}
    assert set(ev) == {"first_call:train_step", "first_call:param_global_norm"}
    assert ev["first_call:train_step"]["dur_s"] == 0.8      # 1.0 - median
    assert ev["first_call:train_step"]["calls"] == 4
    assert ev["first_call:param_global_norm"]["dur_s"] == 0.4
    assert all(e["parent"] == obs_mod.SETUP_SPANS[0] for e in ev.values())


def test_spans_land_in_a_profiler_window_on_the_python_thread(tmp_path):
    """While a window is open every span is a TraceAnnotation under the
    program's prefix, and the epoch mark carries the epoch number."""
    import jax
    from bnsgcn_tpu.utils import traceparse
    ob = obs_mod.Obs("")
    jax.profiler.start_trace(str(tmp_path))
    try:
        for epoch in (3, 4):
            ob.epoch_begin(epoch)
            with obs_mod.span(ob, "guard"), obs_mod.span(ob, "norm_probe"):
                jax.numpy.ones(8).sum().block_until_ready()
        ob.epoch_end()
    finally:
        jax.profiler.stop_trace()
    events, _ = traceparse.load_trace_events(str(tmp_path))
    tnames = traceparse._thread_names(events)

    def full(e):    # the writer shows "guard" and keeps "bns:guard" here
        return (e.get("args") or {}).get("long_name", "")

    mine = [e for e in events if e.get("ph") == "X"
            and full(e).startswith(obs_mod.SPAN_PREFIX)]
    assert sorted(full(e) for e in mine) == sorted(
        2 * ["bns:epoch", "bns:guard", "bns:norm_probe"])
    assert {tnames.get((e["pid"], e["tid"])) for e in mine} == {"python"}
    marks = [e for e in mine if full(e) == obs_mod.EPOCH_MARK]
    assert sorted(int(e["args"]["step_num"]) for e in marks) == [3, 4]
    for e in mine:
        if full(e) == "bns:norm_probe":             # inside its guard's span
            g = next(x for x in mine if full(x) == "bns:guard"
                     and x["ts"] <= e["ts"] <= x["ts"] + x["dur"])
            assert e["ts"] + e["dur"] <= g["ts"] + g["dur"] + 1e-3


# ----------------------------------------------------------------------------
# the compile account and the boot stamps
# ----------------------------------------------------------------------------

def test_compile_account_is_the_union_of_each_kinds_spans():
    recs = [("trace", 2.0, 3.0, "inner"),          # inside outer's trace
            ("trace", 2.5, 2.6, "sin"),            # inside both
            ("trace", 1.0, 4.0, "outer"),
            ("trace", 3.5, 5.0, "tail"),           # overlaps outer's end
            ("lower", 5.0, 6.0, "jit(outer)"),
            ("compile", 6.0, 9.0, "jit(outer)"),
            ("compile", 7.0, 8.0, "jit(nested)"),  # inside the outer one
            ("hits", 6.5, 6.5, ""), ("hits", 7.5, 7.5, ""),
            ("misses", 8.5, 8.5, ""),
            ("compile", 10.0, 10.5, "jit(later)")]
    a = obs_mod.compile_account(recs, programs=True)
    # a sum would read 6.1 s of tracing for the 4 s the union holds
    assert a == {"trace_s": 4.0, "lower_s": 1.0, "compile_s": 3.5,
                 "hits": 2, "misses": 1, "t0": 1.0,
                 "programs": ["jit(outer)", "jit(later)"]}
    # clipped to a window: the parts outside it, and instants, drop out
    w = obs_mod.compile_account(recs, 3.0, 7.0)
    assert w == {"trace_s": 2.0, "lower_s": 1.0, "compile_s": 1.0,
                 "hits": 1, "misses": 0}
    assert obs_mod.compile_account(recs, 11.0, 12.0) == {}
    assert obs_mod.compile_account([]) == {}


def _listeners():
    from jax._src import monitoring
    return (list(monitoring.get_event_time_span_listeners()),
            list(monitoring.get_event_listeners()))


def test_setup_spans_and_epochs_carry_what_compiled_inside_them(tmp_path):
    """make_obs subscribes the Obs to the process's one registration: an
    emitting span carries the account of what compiled inside it, the
    outermost one's close hands the rest to the loop, and take_compiles
    gives an epoch its account once, {} when nothing compiled."""
    import jax
    import jax.numpy as jnp
    path = str(tmp_path / "o.jsonl")
    ob = obs_mod.make_obs(Config(obs="on", obs_log=path), log=lambda *a: None)
    assert obs_mod._on_time_span in _listeners()[0]
    assert obs_mod._on_event in _listeners()[1]

    def fresh(k):                   # a program nothing has compiled yet
        return jax.jit(lambda x: jnp.sin(x) * k)(np.arange(3.0 + k))

    with obs_mod.span(ob, "run_training_setup", emit=True):
        with obs_mod.span(ob, "init_training", emit=True):
            fresh(1).block_until_ready()
        with obs_mod.span(ob, "place", emit=True):
            pass
        fresh(2).block_until_ready()
    assert ob.take_compiles() == {}         # read by the set-up spans
    fresh(3).block_until_ready()
    epoch0 = ob.take_compiles()
    assert epoch0["compile_s"] > 0 and epoch0["trace_s"] > 0
    assert "jit(<lambda>)" in epoch0["programs"]
    assert ob.take_compiles() == {}
    ob.close()
    assert obs_mod._on_time_span not in _listeners()[0] or any(
        r() is not None for r in obs_mod._subscribers)
    fresh(4).block_until_ready()            # closed: heard by nobody
    assert ob.take_compiles() == {}
    ev = {e["name"]: e for e in obs_mod.load_events(path)
          if e["kind"] == "span" and e["parent"] != obs_mod.BOOT_PARENT}
    assert "compile" not in ev["place"]
    inner, root = ev["init_training"]["compile"], ev[
        "run_training_setup"]["compile"]
    assert set(inner) == {"trace_s", "lower_s", "compile_s", "hits",
                          "misses"}
    assert 0 < inner["compile_s"] < root["compile_s"]
    assert root["trace_s"] >= inner["trace_s"]


def test_cache_hits_and_misses_are_counted():
    ob = obs_mod.Obs("")
    obs_mod.subscribe_compiles(ob.on_compile)
    try:
        from jax import monitoring
        monitoring.record_event("/jax/compilation_cache/cache_hits")
        monitoring.record_event("/jax/compilation_cache/cache_hits")
        monitoring.record_event("/jax/compilation_cache/cache_misses")
        monitoring.record_event("/jax/compilation_cache/other")
    finally:
        obs_mod.unsubscribe_compiles(ob.on_compile)
    a = ob.take_compiles()
    assert (a["hits"], a["misses"], a["compile_s"]) == (2, 1, 0.0)


def test_compile_records_from_other_threads_are_neither_lost_nor_raced():
    """A compile on another thread (the host eval thread) lands while the
    loop takes an epoch's account and a set-up span reads its own: every
    record is taken exactly once and nothing raises."""
    import threading
    ob = obs_mod.Obs("")
    n_threads, n_each = 4, 5000
    go = threading.Event()

    def feed():
        go.wait()
        for i in range(n_each):
            ob.on_compile("hits", float(i), float(i), "")

    threads = [threading.Thread(target=feed) for _ in range(n_threads)]
    for t in threads:
        t.start()
    go.set()
    taken = 0
    while any(t.is_alive() for t in threads):
        ob._read_compiles(clear=False)              # a nested span's read
        taken += ob.take_compiles().get("hits", 0)
    for t in threads:
        t.join()
    taken += ob.take_compiles().get("hits", 0)
    assert taken == n_threads * n_each


def test_boot_stamps_are_written_out_at_make_obs(tmp_path, monkeypatch):
    import time
    monkeypatch.setattr(obs_mod, "_BOOT", {})
    obs_mod.boot_begin("import")
    time.sleep(0.01)
    obs_mod.boot_end("import", proc_start=obs_mod.proc_start_wall())
    obs_mod.boot_begin("backend_init")
    obs_mod.boot_end("backend_init")
    obs_mod.boot_begin("backend_init")          # a name keeps its first
    time.sleep(0.02)
    obs_mod.boot_end("backend_init")
    obs_mod.boot_end("never_begun")
    path = str(tmp_path / "o.jsonl")
    obs_mod.make_obs(Config(obs="on", obs_log=path),
                     log=lambda *a: None).close()
    ev = obs_mod.load_events(path)
    assert [(e["kind"], e["name"], e["parent"]) for e in ev] == [
        ("span", "import", "process"), ("span", "backend_init", "process")]
    imp, be = ev
    assert imp["dur_s"] >= 0.01 and be["dur_s"] < 0.02
    assert imp["t0"] <= be["t0"] <= time.time()
    # the OS's start of this process: before its import, within a second
    assert imp["proc_start"] <= imp["t0"] + 1.0
    assert imp["proc_start"] > imp["t0"] - 7 * 24 * 3600


def test_obs_report_setup_timeline_and_recompiled_epochs(tmp_path):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import obs_report
    acct = {"trace_s": 3.5, "lower_s": 2.1, "compile_s": 2.4, "hits": 1,
            "misses": 0}
    events = [
        {"ts": 103.0, "kind": "span", "rank": 0, "name": "import",
         "parent": "process", "t0": 101.0, "dur_s": 2.0, "proc_start": 100.5},
        {"ts": 110.0, "kind": "span", "rank": 0, "name": "backend_init",
         "parent": "process", "t0": 103.5, "dur_s": 6.5},
        {"ts": 112.0, "kind": "span", "rank": 0, "name": "place",
         "parent": "run_training_setup", "t0": 111.0, "dur_s": 1.0,
         "compile": dict(acct, trace_s=0.25)},
        {"ts": 114.0, "kind": "span", "rank": 0,
         "name": "run_training_setup", "parent": None, "t0": 110.0,
         "dur_s": 4.0, "compile": acct}]
    for e in range(5):
        ev = {"ts": 122.0 + e, "kind": "epoch", "rank": 0, "epoch": e,
              "loss": 1.0, "step_s": 0.4}
        if e in (0, 3):
            ev["compile"] = dict(acct, programs=[f"jit(p{e})"], t0=121.0 + e)
        events.append(ev)
    out = []
    obs_report.render(obs_report.summarize(events), write=out.append)
    text = "\n".join(out)
    tree = text[text.index("set-up (span events):"):].splitlines()
    assert [ln.split()[0] for ln in tree[2:7]] == [
        "process", "import", "backend_init", "run_training_setup", "place"]
    assert "at 100.500, 0.500 s before the first boot span" in tree[2]
    assert "%" not in tree[3] and "%" not in tree[4]
    assert "100.0%" in tree[5] and "25.0%" in tree[6]
    assert ("[trace 3.500 lower 2.100 compile 2.400 s, hits 1 misses 0]"
            in tree[5])
    assert "[trace 0.250 lower" in tree[6]
    assert tree[7].strip() == ("warm-up: 11.000 s from the root's end to "
                               "epoch 3, the last epoch that compiled:")
    assert tree[8].strip().startswith("E0: jit(p0) [trace 3.500")
    assert tree[9].strip().startswith("E3: jit(p3) [trace")
    rows = [ln for ln in out if re.match(r"^\s+\d+\s+1\.0000", ln)]
    assert [ln.endswith("recompiled: jit(p%d)" % e) for e, ln in
            enumerate(rows)] == [True, False, False, True, False]


def test_obs_report_setup_tree_host_columns_and_stalls(tmp_path):
    """tools/obs_report.py renders the `span` events as a tree with shares,
    the epoch records' dispatch / wait / boundary, and the epochs whose wait
    stands out with the counters that say what the host did."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import obs_report
    log = obs_mod.EventLog(str(tmp_path / "o.jsonl"))
    log.emit("span", name="place", parent="run_training_setup", t0=1.0,
             dur_s=0.5)
    log.emit("span", name="build_step_fns", parent="run_training_setup",
             t0=0.2, dur_s=0.25)
    log.emit("span", name="run_training_setup", parent=None, t0=0.0,
             dur_s=2.0)
    log.emit("span", name="first_call:train_step",
             parent="run_training_setup", t0=2.0, dur_s=30.0, calls=4)
    for e in range(8):
        stalled = e == 5
        log.emit("epoch", epoch=e, loss=1.0, step_s=0.7 if stalled else 0.6,
                 dispatch_s=0.001, wait_s=0.699 if stalled else 0.599,
                 boundary_s=0.003, boundary={"pre": 0.001},
                 cpu_s=0.01, nivcsw=9 if stalled else 0, majflt=0)
    log.close()
    out = []
    obs_report.render(obs_report.summarize(
        obs_report.load_run([str(tmp_path / "o.jsonl")])), write=out.append)
    text = "\n".join(out)
    tree = text[text.index("set-up (span events):"):].splitlines()
    assert [ln.split()[0] for ln in tree[2:6]] == [
        "run_training_setup", "build_step_fns", "place",
        "first_call:train_step"]
    assert "25.0%" in tree[4] and "12.5%" in tree[3]
    assert "(4 calls)" in tree[5] and "%" not in tree[5]
    assert "disp_ms   wait_ms    bnd_ms" in text
    stalls = text[text.index("stalls (wait over the median"):].splitlines()
    assert len([ln for ln in stalls[2:] if ln.strip()
                and ln.split()[0].isdigit()]) == 1
    assert stalls[2].split()[:4] == ["5", "699.00", "100.00", "9"]


def test_obs_report_spmm_counts_and_trace_clock():
    """The header's `spmm` counts get their line, and a `trace` event's
    `start_wall` lays the traced epochs' `epoch` events on the trace's clock
    (an epoch written before the window opened is left out)."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import obs_report
    events = [{"ts": 90.0, "kind": "run_header", "rank": 0, "config": {},
               "spmm": {"path": "hybrid", "tiles_fwd": 190, "tiles_bwd": 190,
                        "tile_stack_bytes": 190 << 18, "dense_share": 0.76993,
                        "dense_path_fwd": "pallas",
                        "dense_path_bwd": "pallas",
                        "dense_edges": 669167, "residual_slots_fwd": 220512,
                        "residual_slots_bwd": 219424,
                        "residual_edges_fwd": 200000,
                        "residual_edges_bwd": 200000, "agg_calls_fwd": 3,
                        "agg_calls_bwd": 3, "agg_calls_per_step": 6,
                        "agg_width_fwd": [256, 256, 41],
                        "agg_width_bwd": [256, 256, 41],
                        "narrow_layers": [{"layer": 3, "fin": 256,
                                           "fout": 41}]}}]
    events += [{"ts": 100.0 + 0.5 * e, "kind": "epoch", "rank": 0, "epoch": e,
                "loss": 1.0, "step_s": 0.5} for e in range(5, 11)]
    events.append({"ts": 104.6, "kind": "trace", "rank": 0, "epoch": 9,
                   "comm_s": 0.0, "reduce_s": 0.0, "exchanges": False,
                   "trace_dir": None, "start_wall": 102.75})
    out = []
    obs_report.render(obs_report.summarize(events), write=out.append)
    spmm = next(ln for ln in out if ln.startswith("spmm: "))
    assert spmm == ("spmm: hybrid | dense tiles 190 fwd / 190 bwd via pallas "
                    "carry 669167 edges (77.0% of all) in one 48 MiB stack | "
                    "residual slots 220512 fwd / 219424 "
                    "bwd a call for 200000 / 200000 edges (1.103 / 1.097 "
                    "slots an edge) | 6 aggregations a step (3 fwd + 3 bwd)"
                    " | widths [256, 256, 41] fwd / [256, 256, 41] bwd; "
                    "projects first: layer 3 (256 -> 41)")
    # a header written before the edges, the paths, the widths and the
    # stack were counted keeps its old line
    del events[0]["spmm"]["tile_stack_bytes"]
    del events[0]["spmm"]["dense_share"]
    for d in ("fwd", "bwd"):
        del events[0]["spmm"][f"residual_edges_{d}"]
        del events[0]["spmm"][f"dense_path_{d}"]
        del events[0]["spmm"][f"agg_width_{d}"]
    out = []
    obs_report.render(obs_report.summarize(events), write=out.append)
    spmm = next(ln for ln in out if ln.startswith("spmm: "))
    assert "190 bwd carry 669167 edges | residual" in spmm
    assert "slots 220512 fwd / 219424 bwd a call | 6 aggregations" in spmm
    assert spmm.endswith("(3 fwd + 3 bwd)")
    laid = out[out.index(next(ln for ln in out
                              if ln.startswith("trace @E9"))) + 1]
    assert laid.strip() == ("window opened at 102.75 (wall clock); epoch "
                            "events at E6 +0.250s E7 +0.750s E8 +1.250s "
                            "E9 +1.750s")


# ----------------------------------------------------------------------------
# --obs off == on, bitwise (the bus must never touch training math)
# ----------------------------------------------------------------------------

@pytest.fixture(scope="module")
def small_graph():
    return sbm_graph(n_nodes=240, n_class=3, n_feat=8, p_in=0.12, p_out=0.01,
                     seed=3)


def _base_cfg(tmp_path, **kw):
    d = dict(dataset="sbm", model="graphsage", n_partitions=2, n_layers=2,
             n_hidden=8, sampling_rate=0.5, dropout=0.5, use_pp=True,
             eval=False, n_epochs=8, log_every=2, seed=7, comm_trace=False,
             part_path=str(tmp_path / "parts"),
             ckpt_path=str(tmp_path / "ckpt"),
             results_path=str(tmp_path / "res"))
    d.update(kw)
    return Config(**d)


def test_obs_off_bitwise_identical_to_on(tmp_path, small_graph, monkeypatch):
    from bnsgcn_tpu.run import run_training
    subscribed = []
    real = obs_mod.subscribe_compiles
    monkeypatch.setattr(obs_mod, "subscribe_compiles",
                        lambda fn: (subscribed.append(fn), real(fn)))
    before = _listeners()
    r_off = run_training(
        _base_cfg(tmp_path, obs="off", ckpt_path=str(tmp_path / "c0")),
        g=small_graph, verbose=False)
    # off (and no --strict-exec) registers no jax.monitoring listener
    assert subscribed == [] and _listeners() == before
    r_on = run_training(
        _base_cfg(tmp_path, obs="on",
                  obs_log=str(tmp_path / "obs.jsonl"),
                  ckpt_path=str(tmp_path / "c1")),
        g=small_graph, verbose=False)
    np.testing.assert_array_equal(r_off.losses, r_on.losses)
    assert r_off.final_loss == r_on.final_loss
    # on subscribes once, and the run's close leaves it subscribed no more
    assert len(subscribed) == 1
    assert all(r() != subscribed[0] for r in obs_mod._subscribers)
    # and the on-run actually recorded its trail
    kinds = {e["kind"] for e in
             obs_mod.load_events(str(tmp_path / "obs.jsonl"))}
    assert {"run_header", "epoch", "run_end"} <= kinds


def test_epochs_carry_compile_only_where_a_program_first_ran(tmp_path,
                                                             small_graph):
    """Epoch 0 names the step it compiled; the norm probe's first log epoch
    names the probe; no other epoch carries `compile`. A second run in the
    process builds a new step (a jit of its own) but its probe is served by
    jax's in-memory cache: only its epoch 0 carries one."""
    import jax
    from bnsgcn_tpu.run import run_training
    jax.clear_caches()
    runs = []
    for i in range(2):
        log = str(tmp_path / f"obs{i}.jsonl")
        run_training(_base_cfg(tmp_path, obs_log=log,
                               ckpt_path=str(tmp_path / f"c{i}")),
                     g=small_graph, verbose=False)
        runs.append(obs_mod.load_events(log))
    probe = 1                               # log_every 2
    for i, evs in enumerate(runs):
        ep = {e["epoch"]: e for e in evs if e["kind"] == "epoch"}
        assert sorted(e for e, ev in ep.items() if "compile" in ev) == (
            [0, probe] if i == 0 else [0]), i
        assert "jit(train_step)" in ep[0]["compile"]["programs"]
        assert ep[0]["compile"]["compile_s"] > 0
        spans = {e["name"]: e for e in evs if e["kind"] == "span"}
        assert spans["run_training_setup"]["compile"]["trace_s"] > 0
        assert spans["import"]["parent"] == obs_mod.BOOT_PARENT
        assert spans["backend_init"]["parent"] == obs_mod.BOOT_PARENT
    first = {e["epoch"]: e for e in runs[0] if e["kind"] == "epoch"}
    assert first[probe]["compile"]["programs"] == ["jit(param_global_norm)"]


def test_rollback_run_leaves_lifecycle_trail(tmp_path, small_graph,
                                             monkeypatch):
    """In-process: a nan@E5 divergence leaves inject + rollback events whose
    fields match the RunResult, and the header records the resolved mesh."""
    monkeypatch.setenv("BNSGCN_RETRY_BACKOFF_S", "0")
    from bnsgcn_tpu.run import run_training
    log = str(tmp_path / "obs.jsonl")
    res = run_training(_base_cfg(tmp_path, obs_log=log, inject="nan@E5"),
                       g=small_graph, verbose=False)
    evs = obs_mod.load_events(log)
    kinds = [e["kind"] for e in evs]
    assert kinds.count("run_header") == 1 and "run_end" in kinds
    hdr = next(e for e in evs if e["kind"] == "run_header")
    assert hdr["parts"] == 2 and hdr["config"]["model"] == "graphsage"
    assert hdr["wire_mb_per_exchange"] > 0
    rb = [e for e in evs if e["kind"] == "rollback"]
    assert len(rb) == len(res.rollbacks) == 1
    assert rb[0]["epoch"] == 5 and rb[0]["nonce"] == 1
    assert rb[0]["loss"] == "nan"       # sanitized, not a bare NaN token
    inj = [e for e in evs if e["kind"] == "inject"]
    assert inj and inj[0]["kind_injected"] == "nan"
    # per-epoch records cover every EXECUTED epoch: the diverged epoch-5
    # pass rolls back before its record (no poisoned row), and the restart
    # epoch (4, from the epoch-3 checkpoint) is recorded twice
    eps = [e["epoch"] for e in evs if e["kind"] == "epoch"]
    assert eps.count(4) == 2 and eps.count(5) == 1
    assert max(eps) == 7


# ----------------------------------------------------------------------------
# e2e through the real CLI (the artifact the ROADMAP campaigns audit)
# ----------------------------------------------------------------------------

def _env(extra=None):
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               BNSGCN_RETRY_BACKOFF_S="0", BNSGCN_COORD_TIMEOUT_S="60",
               PYTHONPATH=REPO)
    env.update(extra or {})
    return env


BASE_ARGS = [
    "--dataset", "sbm", "--partition-method", "random", "--n-partitions", "2",
    "--model", "graphsage", "--n-layers", "2", "--n-hidden", "8",
    "--sampling-rate", "0.5", "--use-pp", "--n-epochs", "8",
    "--log-every", "2", "--no-eval", "--no-comm-trace",
    "--fix-seed", "--seed", "11",
]


@pytest.mark.quickgate
def test_cli_obs_e2e_and_report(tmp_path):
    """A real `--inject nan@E5` CLI run produces a parseable JSONL log with
    header + epoch + rollback + run_end, and tools/obs_report.py renders it
    without error."""
    log = str(tmp_path / "obs.jsonl")
    r = subprocess.run(
        [sys.executable, "-m", "bnsgcn_tpu.main"] + BASE_ARGS
        + ["--part-path", str(tmp_path / "parts"),
           "--ckpt-path", str(tmp_path / "ckpt"),
           "--results-path", str(tmp_path / "res"),
           "--inject", "nan@E5", "--obs-log", log],
        capture_output=True, text=True, timeout=240, cwd=REPO, env=_env())
    assert r.returncode == 0, r.stdout + r.stderr
    kinds = [e["kind"] for e in obs_mod.load_events(log)]
    for want in ("run_header", "epoch", "inject", "rollback", "run_end"):
        assert want in kinds, (want, kinds)
    rep = subprocess.run(
        [sys.executable, "tools/obs_report.py", log],
        capture_output=True, text=True, timeout=60, cwd=REPO, env=_env())
    assert rep.returncode == 0, rep.stdout + rep.stderr
    assert "rollback" in rep.stdout and "per-epoch" in rep.stdout
    # --compare against itself must also render (the bench-window audit path)
    cmp_ = subprocess.run(
        [sys.executable, "tools/obs_report.py", "--compare", log, log],
        capture_output=True, text=True, timeout=60, cwd=REPO, env=_env())
    assert cmp_.returncode == 0, cmp_.stdout + cmp_.stderr
    assert "mean step" in cmp_.stdout


@pytest.mark.quickgate
def test_two_rank_merged_epoch_record(tmp_path):
    """2 real coordinated processes (the PR-5 harness): each rank's epoch
    summary piggybacks on agree_step's verdict value, and rank 0's log holds
    ONE merged `epoch_ranks` record per epoch naming both ranks — no new
    collective existed for this (pinned by the coord suite's lockstep seq
    accounting staying green)."""
    subprocess.run(
        [sys.executable, "-m", "bnsgcn_tpu.partition_cli",
         "--dataset", "sbm", "--partition-method", "random",
         "--n-partitions", "2", "--fix-seed",
         "--part-path", str(tmp_path / "parts")],
        env=_env(), check=True, capture_output=True, cwd=REPO)
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    log = str(tmp_path / "obs.jsonl")
    procs = []
    for rank in (0, 1):
        cmd = ([sys.executable, "-m", "bnsgcn_tpu.main"] + BASE_ARGS
               + ["--skip-partition", "--n-epochs", "6",
                  "--part-path", str(tmp_path / "parts"),
                  "--ckpt-path", str(tmp_path / f"ck{rank}"),
                  "--results-path", str(tmp_path / "res"),
                  "--coord", "tcp", "--coord-port", str(port),
                  "--coord-world", "2", "--coord-rank", str(rank),
                  "--obs-log", log])
        procs.append(subprocess.Popen(cmd, stdout=subprocess.PIPE,
                                      stderr=subprocess.STDOUT, text=True,
                                      cwd=REPO, env=_env()))
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=240)
            outs.append((p.returncode, out))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.communicate()
    assert [rc for rc, _ in outs] == [0, 0], outs
    # rank 0 owns the bare path; rank 1 wrote its own .r1 sibling
    ev0 = obs_mod.load_events(log)
    merged = [e for e in ev0 if e["kind"] == "epoch_ranks"]
    assert merged, [e["kind"] for e in ev0]
    for rec in merged:
        assert set(rec["ranks"]) == {"0", "1"}
        for info in rec["ranks"].values():
            assert "loss" in info and "step_ms" in info
    # exactly one merged record per executed epoch, all on rank 0
    assert sorted(rec["epoch"] for rec in merged) == list(range(6))
    assert all(rec["rank"] == 0 for rec in merged)
    ev1 = obs_mod.load_events(log + ".r1")
    assert any(e["kind"] == "epoch" and e["rank"] == 1 for e in ev1)
    assert not any(e["kind"] == "epoch_ranks" for e in ev1)


# ----------------------------------------------------------------------------
# serving: registry-backed stats + the metrics op
# ----------------------------------------------------------------------------

def test_serve_stats_percentiles_and_metrics_op():
    import jax

    from bnsgcn_tpu import serve
    from bnsgcn_tpu.models.gnn import init_params, spec_from_config
    g = sbm_graph(n_nodes=120, n_class=3, n_feat=8, p_in=0.12, p_out=0.01,
                  seed=3)
    cfg = Config(dataset="sbm", model="graphsage", n_layers=2, n_hidden=8,
                 use_pp=True, n_feat=g.n_feat, n_class=g.n_class,
                 n_train=g.n_train)
    spec = spec_from_config(cfg)
    params, state = init_params(jax.random.key(0), spec)
    core = serve.build_core(cfg, g, params, state, log=lambda *a: None)
    try:
        for n in (1, 2, 3):
            core.predict(n)                 # tier A
        core.add_edges([[0, 1]])
        core.predict(1)                     # dirty -> tier B
        core.flush()
        st = core.snapshot_stats()
        # previously counters only; now registry-backed latency + lag
        assert st["tier_a_p50_ms"] > 0 and st["tier_a_p99_ms"] > 0
        assert st["tier_b_p50_ms"] > 0
        assert st["tier_b_p99_ms"] >= st["tier_b_p50_ms"]
        assert st["refresh_lag_p50_s"] > 0  # the flushed dirty row's age
        assert st["refresh_lag_s"] == 0.0   # nothing left dirty
        assert st["queue_depth"] == 0
        # old counter vocabulary intact (BENCH/serve_bench compatibility)
        assert st["requests"] == 4 and st["tier_b"] == 1
        server = serve.ServeServer(core, port=0, log=lambda *a: None)
        try:
            m = server._handle({"op": "metrics"})
            assert m["ok"]
            hists = m["metrics"]["histograms"]
            assert hists["serve/latency_ms/A"]["count"] == 3
            assert hists["serve/latency_ms/B"]["count"] == 1
            assert hists["serve/refresh_lag_s"]["count"] >= 1
            assert m["metrics"]["gauges"]["serve/dirty"] == 0
            s2 = server._handle({"op": "stats"})
            assert s2["ok"] and s2["tier_b_p99_ms"] > 0
        finally:
            server.drain(timeout_s=5.0)
    finally:
        core.close()
