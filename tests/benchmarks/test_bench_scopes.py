"""The readers of the program's own names (benchmarks/scopelib.py and the
reducers of PR 27's metrics) on a trace recorded on the chip, on hand-made
traces, and against a program that has no names to read.

`data/v5e_p1_scoped.trace.json.gz` is a trimmed window of a small P=1 hybrid
+ Pallas run on a TPU v5e (`synth-reddit:0.1`, GraphSAGE 4 x 256, bf16; my
chip run, PR 27): the device's `XLA Ops` / `XLA Modules` / `Steps` lanes with
each event's `tf_op`, the kernel's `long_name`, the host's `bns:` spans and
step launches. `data/v5e_p1_scoped.obs.jsonl` is that run's obs log.
"""

import gzip
import json
import os

import pytest

import bench_tiny
from benchmarks import harness, obsread, scopelib, tracelib

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
NEW = ["residual.gather_s", "residual.slot_rate", "tiles.apply_s",
       "model.dense_s", "step.unscoped_pct",
       "loop.dispatch_s", "loop.boundary_s", "loop.ckpt_s",
       "loop.stall_max_s", "loop.gap_named_pct", "setup.run_training_s",
       "setup.place_s", "setup.precompute_s", "setup.first_step_s"]
FROM_TRACE = NEW[:5] + ["loop.gap_named_pct"]


@pytest.fixture(scope="module")
def chip():
    with gzip.open(os.path.join(DATA, "v5e_p1_scoped.trace.json.gz"),
                   "rt") as f:
        trace = json.load(f)["traceEvents"]
    events = obsread.read_events(os.path.join(DATA, "v5e_p1_scoped.obs.jsonl"))
    return trace, events


def ctx_of(trace, events, first=11):
    return {"trace_events": trace, "events": events, "first_epoch": first,
            "breakdown_notes": {}, "device": {"kind": "TPU v5 lite"}}


def read(name, ctx):
    m = harness.load_metrics()[name]
    return harness.load_reducer(m["reducer"])(ctx, **m.get("args", {}))


# ----------------------------------------------------------------------------
# the recorded trace
# ----------------------------------------------------------------------------

def test_scope_sums_of_the_recorded_trace(chip):
    trace, _ = chip
    took, steps = scopelib.scope_seconds(trace)
    assert steps == 3
    want = {"agg_residual": 0.017286, "agg_tiles": 0.001702,
            "dropout": 0.000494, "norm": 0.000481, "unscoped": 0.000327,
            "linear": 0.000315, "loss": 0.000139, "halo_exchange": 2.4e-05,
            "layer": 9e-06, "bns_sample": 2e-06, "optimizer": 1e-06}
    assert {k: round(v, 6) for k, v in took.items()} == want
    # every busy microsecond is booked once: the scopes tile the busy time
    busy, _ = tracelib.device_busy(trace)
    assert abs(sum(took.values()) * steps - max(busy.values())) < 1e-5


def test_a_loop_counts_once_and_is_booked_by_what_it_covers(chip):
    trace, _ = chip
    dev = scopelib.busiest_device(trace)
    lane = scopelib.lane_events(trace, dev)
    top = scopelib.top_level(lane)
    assert len(lane) == 19570 and len(top) == 6706
    assert sum(1 + len(c) for _, c in top) == len(lane)
    _, innermost = scopelib.program_scopes()
    loops = [(e, c) for e, c in top if c]
    assert len(loops) == 72                 # 18 a step, four traced steps
    for e, covered in loops:
        # the compiler's loop carries no op_name of its own
        assert "tf_op" not in e.get("args", {})
        assert all(e["ts"] <= c["ts"] and c["ts"] + c["dur"]
                   <= e["ts"] + e["dur"] + 1e-3 for c in covered)
        assert scopelib.event_scope(e, covered, innermost) == "agg_residual"
    # booked once: the loops' own time, not theirs plus their bodies'
    took, steps = scopelib.scope_seconds(trace)
    inside = sum(c["dur"] for _, cs in loops for c in cs) / 1e6
    assert inside > 0.05 and took["agg_residual"] * steps < 0.06


def test_idle_gaps_of_the_recorded_trace_are_named_by_host_phase(chip):
    trace, _ = chip
    spans = scopelib.host_spans(trace)
    assert {n for _, _, n in spans} == {"pre", "dispatch", "wait",
                                        "loss_fetch", "obs_emit", "guard",
                                        "norm_probe"}       # no epoch mark
    gaps = scopelib.named_gaps(trace)
    by = {}
    for name, s in gaps:
        by[name] = by.get(name, 0.0) + s
    assert max(gaps, key=lambda g: g[1]) == ("norm_probe",
                                             pytest.approx(0.242336, abs=1e-6))
    assert by["wait"] == pytest.approx(0.005971, abs=1e-6)
    assert by["unnamed"] < 1e-5
    ctx = ctx_of(*chip)
    assert read("loop.gap_named_pct", ctx) > 99.99
    notes = ctx["breakdown_notes"]
    assert notes["largest_idle_gap"].startswith("norm_probe 0.2423")
    assert notes["idle_by_phase"].startswith("norm_probe 0.2423, wait 0.005971")


def test_the_new_metrics_on_the_recorded_run(chip):
    ctx = ctx_of(*chip)
    got = {n: read(n, ctx) for n in NEW}
    assert all(v is not None for v in got.values()), got
    assert got["residual.gather_s"] == pytest.approx(0.017286, abs=1e-6)
    assert got["tiles.apply_s"] == pytest.approx(0.001702, abs=1e-6)
    assert got["model.dense_s"] == pytest.approx(0.001438, abs=2e-6)
    assert got["step.unscoped_pct"] == pytest.approx(1.575, abs=0.01)
    # 220,512 slots a forward call, 3 + 3 calls a step (run_header.spmm)
    head = next(e for e in chip[1] if e["kind"] == "run_header")["spmm"]
    slots = 3 * (head["residual_slots_fwd"] + head["residual_slots_bwd"])
    assert got["residual.slot_rate"] == pytest.approx(
        slots / 0.017286 / 1e6, rel=1e-3)
    # `dispatch` holds the epoch_dev upload; the boundary ends before it
    assert got["loop.dispatch_s"] == pytest.approx(0.001375)
    assert got["loop.boundary_s"] == pytest.approx(0.000709)
    assert got["loop.ckpt_s"] == pytest.approx(0.01882)     # epoch 19's write
    assert 0 <= got["loop.stall_max_s"] < 0.002
    assert ctx["breakdown_notes"]["stall_max"].startswith("epoch ")
    assert "nivcsw 0 majflt 0" in ctx["breakdown_notes"]["stall_max"]
    assert got["setup.run_training_s"] == pytest.approx(14.481308)
    assert got["setup.place_s"] == pytest.approx(0.76696)
    assert got["setup.precompute_s"] == pytest.approx(4.413223)
    assert got["setup.first_step_s"] == pytest.approx(36.800099)


# ----------------------------------------------------------------------------
# hand-made traces
# ----------------------------------------------------------------------------

def _meta():
    return [
        {"ph": "M", "pid": 3, "name": "process_name",
         "args": {"name": "/device:TPU:0"}},
        {"ph": "M", "pid": 3, "tid": 3, "name": "thread_name",
         "args": {"name": "XLA Ops"}},
        {"ph": "M", "pid": 7, "name": "process_name",
         "args": {"name": "/host:CPU"}},
        {"ph": "M", "pid": 7, "tid": 1, "name": "thread_name",
         "args": {"name": "python"}}]


def _op(ts, dur, name, tf_op=None):
    ev = {"ph": "X", "pid": 3, "tid": 3, "ts": ts, "dur": dur, "name": name}
    if tf_op:
        ev["args"] = {"tf_op": tf_op}
    return ev


def _host(ts, dur, full):
    # as the trace writer stores an annotation: the text after the colon as
    # the name, the whole of it as long_name
    return {"ph": "X", "pid": 7, "tid": 1, "ts": ts, "dur": dur,
            "name": full.split(":")[-1], "args": {"long_name": full}}


def _launch(ts):
    return {"ph": "X", "pid": 7, "tid": 1, "ts": ts, "dur": 5,
            "name": "PjitFunction(train_step)"}


@pytest.fixture()
def made():
    ops = [
        # step 1: an op under two nested scopes; a loop with no op_name over
        # two fusions; a collective found by opcode; an op under no scope
        _op(1000, 100, "fusion.7",
            "jit(train_step)/jvp()/layer_1/attention/dropout/select_n:"),
        _op(1100, 300, "while.3"),
        _op(1110, 200, "fusion.8",
            "jit(train_step)/jvp()/layer_1/agg_residual/gather:"),
        _op(1310, 50, "fusion.9",
            "jit(train_step)/jvp()/layer_1/linear/dot_general:"),
        _op(1400, 40, "all-reduce.1",
            "jit(train_step)/transpose(jvp())/layer_1/linear/psum:"),
        _op(1440, 60, "copy-done.2"),
        _op(1500, 20, "fusion.10", "jit(train_step)/jvp()/layer_2/mul:"),
        # step 2, after two idle gaps
        _op(2000, 100, "fusion.7",
            "jit(train_step)/transpose(jvp())/layer_1/"
            "transpose(jvp(agg_tiles))/dot_general:"),
        _op(2400, 100, "fusion.11", "jit(train_step)/optimizer/add:"),
    ]
    host = [_launch(990), _launch(1990),
            _host(900, 2000, "bns:epoch"),          # the mark names nothing
            _host(1500, 450, "bns:guard"),
            _host(1600, 200, "bns:norm_probe")]
    return _meta() + ops + host


def test_innermost_scope_loops_collectives_and_the_unscoped(made):
    took, steps = scopelib.scope_seconds(made)
    assert steps == 2
    us = {k: round(v * steps * 1e6) for k, v in took.items()}
    assert us == {"dropout": 100,           # innermost of attention/dropout
                  "agg_residual": 300,      # the loop, once, by its body
                  "collective": 40,         # by opcode, whatever its scope
                  "unscoped": 60,
                  "layer": 20,              # layer_2 alone: the parent scope
                  "agg_tiles": 100,         # transpose(jvp(agg_tiles))
                  "optimizer": 100}
    ctx = ctx_of(made, [])
    assert read("residual.gather_s", ctx) == pytest.approx(150e-6)
    # the optimizer's own kernels count with the model's dense work: the
    # chip fuses the matrices' updates into `linear`'s weight-gradient dots
    assert read("model.dense_s", ctx) == pytest.approx(110e-6)
    assert read("step.unscoped_pct", ctx) == pytest.approx(100 * 60 / 720)


def test_a_gap_under_no_span_is_unnamed_and_the_child_span_wins(made):
    gaps = scopelib.named_gaps(made)
    # 990-1000, from the launch to the first operation, and 2100-2400 lie
    # under the epoch mark alone; 1520-2000: midpoint 1760 lies in guard >
    # norm_probe
    assert gaps == [("unnamed", pytest.approx(10e-6)),
                    ("norm_probe", pytest.approx(480e-6)),
                    ("unnamed", pytest.approx(300e-6))]
    ctx = ctx_of(made, [])
    assert read("loop.gap_named_pct", ctx) == pytest.approx(100 * 480 / 790)
    assert ctx["breakdown_notes"] == {
        "idle_by_phase": "norm_probe 0.00048, unnamed 0.00031",
        "largest_idle_gap": "norm_probe 0.00048"}


def test_a_scope_the_table_does_not_hold_is_an_error(made):
    from benchmarks.reducers import scope_time
    with pytest.raises(ValueError, match="not in the program's scope table"):
        scope_time.reduce(ctx_of(made, []), scopes=["while"])


# ----------------------------------------------------------------------------
# nothing to read: the parent's program, a CPU trace, an untraced log
# ----------------------------------------------------------------------------

def test_a_program_without_names_gives_nothing_and_raises_nothing(
        chip, monkeypatch):
    """The parent of PR 27 has no scope table, no span prefix, no `span`
    events and no host fields on its `epoch` events: every new metric is
    left out of the line."""
    trace, events = chip
    monkeypatch.setattr(scopelib, "program_scopes", lambda: None)
    monkeypatch.setattr(scopelib, "program_spans", lambda: None)
    old = [{k: v for k, v in e.items()
            if k in ("ts", "kind", "rank", "epoch", "loss", "step_s")}
           for e in events if e["kind"] in ("epoch", "run_header")]
    ctx = ctx_of(trace, old)
    assert {n: read(n, ctx) for n in NEW} == dict.fromkeys(NEW)
    assert ctx["breakdown_notes"] == {}


def test_a_trace_without_device_lanes_or_without_scopes(chip):
    trace, events = chip
    host_only = [e for e in trace if e.get("pid") != 3]
    bare = [{k: v for k, v in e.items() if k != "args"}
            if e.get("pid") == 3 and e.get("ph") == "X" else e
            for e in trace]
    for name in FROM_TRACE:
        assert read(name, ctx_of(host_only, events)) is None
    # device lanes whose events carry no op_name: all busy time is unscoped,
    # no scope finds anything
    ctx = ctx_of(bare, events)
    assert read("step.unscoped_pct", ctx) == pytest.approx(100.0)
    for name in FROM_TRACE[:4]:
        assert read(name, ctx) is None


# ----------------------------------------------------------------------------
# through the harness
# ----------------------------------------------------------------------------

def test_manifest_lists_the_new_metrics_with_both_cells():
    with open(os.path.join(bench_tiny.REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    listed = {m["name"]: m for m in manifest["per_layer"]}
    cells = sorted(w["name"] for w in manifest["workloads"])
    files = harness.load_metrics()
    for name in NEW:
        assert listed[name]["workloads"] == files[name]["workloads"] == cells
        assert listed[name]["source"] == (
            "device_trace" if name in FROM_TRACE[:5] else "program_span")
    # appended after what PR 26 listed, nothing before them moved
    assert [m["name"] for m in manifest["per_layer"]][-len(NEW):] == NEW


def test_harness_reports_what_a_cpu_run_can_read(tmp_path):
    """A traced run of the tiny cell through the real run_training: the
    metrics that read the obs log are on the line, those that read device
    lanes are left out (a CPU trace has none)."""
    root = bench_tiny.make_root(str(tmp_path / "root"))
    for name in NEW:
        path = os.path.join(root, "metrics", name + ".json")
        with open(path) as f:
            m = json.load(f)
        m["workloads"] = ["tiny.p1"]
        with open(path, "w") as f:
            json.dump(m, f)
    rc, res, _ = bench_tiny.run_cell(root, seed=2**31 + 5, seconds=1.0,
                                     trace=True)
    assert rc == 0 and res["correct"] is True
    got = set(res["metrics"]) & set(NEW)
    # the window opens at epoch 11: epoch 20's boundary holds the first
    # checkpoint write inside it
    want = set(NEW) - set(FROM_TRACE) - (
        set() if res["attempted"] >= 10 else {"loop.ckpt_s"})
    assert got == want, sorted(got ^ want)
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert 0 < m["loop.dispatch_s"] < m["step.device_s"]
    assert 0 < m["loop.boundary_s"] and m.get("loop.ckpt_s", 1) > 0
    assert m["loop.stall_max_s"] >= 0
    assert (m["setup.place_s"] + m["setup.precompute_s"]
            < m["setup.run_training_s"])
    assert m["setup.first_step_s"] > 0
    assert res["notes"]["stall_max"].startswith("epoch ")
