"""Unit tests for tools/trace_comm.py's trace attribution logic.

The parser feeds the Comm(s) fidelity cross-check (reference comm_timer
semantics, helper/timer/comm_timer.py:21-25); these tests pin its three
non-obvious behaviors on a synthetic chrome trace: nested-duplicate launch
dedup, device-event -> host-program attribution by launch order, and the
min-over-lanes wait-stripping estimate.
"""

import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools"))

from trace_comm import attribute, program_cost  # noqa: E402


def _meta(pid, tid, name):
    return {"ph": "M", "pid": pid, "tid": tid, "name": "thread_name",
            "args": {"name": name}}


def _ev(pid, tid, name, ts, dur):
    return {"ph": "X", "pid": pid, "tid": tid, "name": name,
            "ts": ts, "dur": dur}


def make_trace():
    """Host lane launches train_step twice (each as a ~1us-apart duplicate
    pair), then one exchange_only sweep of two back-to-back fires; two
    device lanes carry collectives after each launch."""
    ev = [_meta(1, 0, "python"), _meta(1, 10, "dev0"), _meta(1, 11, "dev1")]
    # step 1 @ t=1000 (duplicate at 1000.5), step 2 @ t=5000 (+dup)
    for t in (1000.0, 1000.5, 5000.0, 5000.5):
        ev.append(_ev(1, 0, "PjitFunction(train_step)", t, 300))
    # one microbench sweep: two consecutive fires @ 9000, 9500 (+dups)
    for t in (9000.0, 9000.2, 9500.0, 9500.2):
        ev.append(_ev(1, 0, "PjitFunction(exchange_only)", t, 100))
    # device collectives: per step, one a2a per lane with asymmetric wait
    # (lane0 waits: dur 50; lane1 arrives last: dur 10) + one all-reduce
    for t0 in (1100.0, 5100.0):
        ev.append(_ev(1, 10, "all-to-all.1", t0, 50))
        ev.append(_ev(1, 11, "all-to-all.1", t0 + 40, 10))
        ev.append(_ev(1, 10, "all-reduce.2", t0 + 60, 7))
        ev.append(_ev(1, 11, "all-reduce.2", t0 + 60, 7))
    # microbench fires: one a2a per lane per fire
    for t0 in (9100.0, 9600.0):
        ev.append(_ev(1, 10, "all-to-all.9", t0, 20))
        ev.append(_ev(1, 11, "all-to-all.9", t0 + 15, 5))
    # a collective before any launch lands in "other"
    ev.append(_ev(1, 10, "all-gather.0", 10.0, 3))
    return ev


def test_launch_dedup_and_sweeps():
    attr = attribute(make_trace())
    assert attr["train_step"]["launches"] == 2
    assert attr["exchange_only"]["launches"] == 2
    assert attr["exchange_only"]["sweeps"] == 1


def test_attribution_categories():
    attr = attribute(make_trace())
    raw, _, nev, nl = program_cost(attr["train_step"], "exchange")
    assert nl == 2 and nev == 2          # 2 steps x 1 a2a per lane
    assert raw == 2 * (50 + 10)
    rraw, _, _, _ = program_cost(attr["train_step"], "reduce")
    assert rraw == 2 * (7 + 7)
    oraw, _, _, _ = program_cost(attr["other"], "reduce")
    assert oraw == 3                     # pre-launch all-gather

    mraw, _, mev, _ = program_cost(attr["exchange_only"], "exchange")
    assert mev == 2 and mraw == 2 * (20 + 5)


def test_min_over_lanes_strips_waiter():
    attr = attribute(make_trace())
    _, est, _, _ = program_cost(attr["train_step"], "exchange")
    # per step the last-arriving lane's span (10) is the true cost
    assert est == 2 * 10
    _, mest, _, _ = program_cost(attr["exchange_only"], "exchange")
    assert mest == 2 * 5


def test_refresh_programs_attribute_as_their_k1_twins():
    """--halo-refresh K>1 (and the --tune K-anneal) launch train_step_full /
    train_step_cached / exchange_only_refresh instead of the K=1 programs.
    They are train steps and exchange sweeps: an exact-name match counted
    no train step and the (fatal) trace window killed every K>1 run."""
    from bnsgcn_tpu.utils.traceparse import step_comm_from_events
    ev = []
    for e in make_trace():
        name = e.get("name", "")
        if name == "PjitFunction(train_step)":
            # first step a full refresh, second a cache hit
            e = dict(e, name="PjitFunction(train_step_full)"
                     if e["ts"] < 2000 else "jit_train_step_cached")
        elif name == "PjitFunction(exchange_only)":
            e = dict(e, name="PjitFunction(exchange_only_refresh)")
        ev.append(e)
    attr = attribute(ev)
    assert attr["train_step"]["launches"] == 2
    assert attr["exchange_only"]["launches"] == 2
    assert attr["exchange_only"]["sweeps"] == 1
    assert attr == attribute(make_trace())
    # a program that merely ends in a known name is not one
    assert attribute([_ev(1, 0, "PjitFunction(my_train_step)", 1.0, 1)])[
        "train_step"]["launches"] == 0
    ex_s, rd_s, steps = step_comm_from_events(ev, True)
    assert steps == 2 and abs(ex_s - 10e-6) < 1e-9 and abs(rd_s - 7e-6) < 1e-9


def test_host_lane_collectives_ignored():
    ev = make_trace()
    ev.append(_ev(1, 0, "all-to-all.7", 1200.0, 999))   # python lane
    attr = attribute(ev)
    raw, _, _, _ = program_cost(attr["train_step"], "exchange")
    assert raw == 2 * (50 + 10)


def test_overlap_report_detects_hidden_exchange():
    """--overlap split observability: exchange spans that coincide with
    interior_agg compute on the same device lane count as hidden; scope
    names are matched in the event name OR any string arg (TPU traces put
    the named_scope path in op metadata args)."""
    from bnsgcn_tpu.utils.traceparse import overlap_from_events

    ev = [_meta(1, 0, "python"), _meta(1, 10, "dev0"), _meta(1, 11, "dev1")]
    ev.append(_ev(1, 0, "PjitFunction(train_step)", 1000.0, 300))
    # lane dev0: a2a @ [1100, 1180]; interior fusion @ [1120, 1220] (via
    # args metadata) -> 60 us hidden; frontier afterwards
    ev.append(_ev(1, 10, "all-to-all.3", 1100.0, 80))
    fused = _ev(1, 10, "fusion.7", 1120.0, 100)
    fused["args"] = {"long_name": "jit(train_step)/interior_agg/fusion.7"}
    ev.append(fused)
    ev.append(_ev(1, 11, "frontier_agg/add.1", 1200.0, 40))
    rep = overlap_from_events(ev)
    assert rep is not None and rep["n_steps"] == 1
    assert abs(rep["exchange_ms"] - 0.080) < 1e-9
    assert abs(rep["interior_ms"] - 0.100) < 1e-9
    assert abs(rep["frontier_ms"] - 0.040) < 1e-9
    assert abs(rep["hidden_ms"] - 0.060) < 1e-9
    assert rep["overlapped"]

    # serialized schedule (exchange fully before interior) -> not overlapped
    ev2 = [_meta(1, 0, "python"), _meta(1, 10, "dev0")]
    ev2.append(_ev(1, 0, "PjitFunction(train_step)", 1000.0, 300))
    ev2.append(_ev(1, 10, "all-to-all.3", 1100.0, 80))
    ev2.append(_ev(1, 10, "interior_agg/fusion.7", 1200.0, 100))
    rep2 = overlap_from_events(ev2)
    assert rep2 is not None and not rep2["overlapped"]
    assert rep2["hidden_ms"] == 0.0

    # fused-run trace (no scope spans at all) -> None, caller logs fallback
    assert overlap_from_events(make_trace()) is None


def test_comm_by_axis_classifies_replica_groups():
    """--by-axis breakdown (replica-axis observability): collectives carrying
    HLO replica_groups are attributed to the mesh axis they reduce over in
    the ('replicas','parts') device order (id = r*P + p, replicas outer);
    attribute-stripped events fall back to the op-kind heuristic."""
    from bnsgcn_tpu.utils.traceparse import classify_axis, comm_by_axis

    P, R = 4, 2
    # parts-axis groups: one consecutive run per replica row
    assert classify_axis([[0, 1, 2, 3], [4, 5, 6, 7]], P, R) == "parts"
    # replica-axis groups: stride-P pairs
    assert classify_axis([[0, 4], [1, 5], [2, 6], [3, 7]], P, R) == "replicas"
    # the fused gradient reduce spans the whole mesh
    assert classify_axis([[0, 1, 2, 3, 4, 5, 6, 7]], P, R) == "replicas x parts"
    # 1-D mesh: the full-mesh group IS the parts axis
    assert classify_axis([[0, 1, 2, 3]], 4, 1) == "parts"
    # misaligned consecutive ids (crossing a replica-row boundary) are not
    # a parts-axis group
    assert classify_axis([[2, 3, 4, 5]], P, R) == "unknown"
    assert classify_axis([], P, R) == "unknown"

    ev = [_meta(1, 0, "python"), _meta(1, 10, "dev0")]
    a2a = _ev(1, 10, "all-to-all.1", 100.0, 30)
    a2a["args"] = {"long_name": "all-to-all, replica_groups={{0,1,2,3},{4,5,6,7}}"}
    ev.append(a2a)
    ar = _ev(1, 10, "all-reduce.2", 200.0, 11)
    ar["args"] = {"long_name": "all-reduce, replica_groups={{0,1,2,3,4,5,6,7}}"}
    ev.append(ar)
    # no replica_groups metadata: op-kind heuristic
    ev.append(_ev(1, 10, "collective-permute.3", 300.0, 5))
    ev.append(_ev(1, 10, "all-reduce.4", 400.0, 7))
    # host (python) lane collectives are ignored as everywhere else
    ev.append(_ev(1, 0, "all-to-all.9", 500.0, 999))
    table = comm_by_axis(ev, P, R)
    assert table["parts"]["exchange"] == 30 + 5
    assert table["replicas x parts"]["reduce"] == 11 + 7
    assert "replicas" not in table     # the fused trainer emits none

    # 1-D mesh fallback: reduces land on 'parts'
    table1 = comm_by_axis([_meta(1, 10, "dev0"),
                           _ev(1, 10, "all-reduce.4", 0.0, 7)], 4, 1)
    assert table1["parts"]["reduce"] == 7

    # multi-lane traces reduce with the min-over-lanes estimator (same as
    # program_cost): the waiter lane's 50 us span is rendezvous wait, the
    # last arriver's 10 us is the true op cost — a raw cross-lane sum
    # (60 us) would skew the axis comparison by straggler wait
    ev3 = [_meta(1, 10, "dev0"), _meta(1, 11, "dev1")]
    ev3.append(_ev(1, 10, "all-to-all.1", 100.0, 50))
    ev3.append(_ev(1, 11, "all-to-all.1", 140.0, 10))
    assert comm_by_axis(ev3, P, R)["parts"]["exchange"] == 10


def test_comm_by_axis_classifies_3_axis_groups():
    """3-D ('replicas','parts','feat') mesh observability: a synthetic
    3-axis trace splits halo ('parts'), per-layer feat psum ('feat') and
    fused gradient ('replicas x parts x feat') device time so --by-axis can
    report each. Device id = (r*P + p)*T + f (replicas outer, feat inner —
    parallel/replicas.make_mesh)."""
    from bnsgcn_tpu.utils.traceparse import classify_axis, comm_by_axis

    P, R, T = 2, 2, 2        # ids: r0p0={0,1} r0p1={2,3} r1p0={4,5} r1p1={6,7}
    # feat groups: T consecutive ids per (replica, part), aligned to T
    assert classify_axis([[0, 1], [2, 3], [4, 5], [6, 7]], P, R, T) == "feat"
    # parts groups: stride-T pairs, one per (replica, feat) lane
    assert classify_axis([[0, 2], [1, 3], [4, 6], [5, 7]], P, R, T) == "parts"
    # replica groups: stride P*T
    assert classify_axis([[0, 4], [1, 5], [2, 6], [3, 7]], P, R, T) == "replicas"
    # the fused gradient reduce spans all three axes
    assert classify_axis([[0, 1, 2, 3, 4, 5, 6, 7]], P, R, T) == \
        "replicas x parts x feat"
    # replica-free (1, P, T) mesh labels
    assert classify_axis([[0, 1, 2, 3]], P, 1, T) == "parts x feat"
    assert classify_axis([[0, 1], [2, 3]], P, 1, T) == "feat"
    assert classify_axis([[0, 2], [1, 3]], P, 1, T) == "parts"
    # feat-misaligned consecutive pairs are not a feat group
    assert classify_axis([[1, 2], [5, 6]], P, R, T) == "unknown"
    # 2-D calls (no feat arg) keep their historical labels
    assert classify_axis([[0, 1, 2, 3], [4, 5, 6, 7]], 4, 2) == "parts"

    ev = [_meta(1, 10, "dev0")]
    a2a = _ev(1, 10, "all-to-all.1", 100.0, 30)
    a2a["args"] = {"long_name":
                   "all-to-all, replica_groups={{0,2},{1,3},{4,6},{5,7}}"}
    ev.append(a2a)
    fpsum = _ev(1, 10, "all-reduce.2", 200.0, 13)
    fpsum["args"] = {"long_name":
                     "all-reduce, replica_groups={{0,1},{2,3},{4,5},{6,7}}"}
    ev.append(fpsum)
    grad = _ev(1, 10, "all-reduce.3", 300.0, 9)
    grad["args"] = {"long_name":
                    "all-reduce, replica_groups={{0,1,2,3,4,5,6,7}}"}
    ev.append(grad)
    # attribute-stripped reduce: op-kind fallback lands on the full mesh
    ev.append(_ev(1, 10, "all-reduce.4", 400.0, 4))
    table = comm_by_axis(ev, P, R, T)
    assert table["parts"]["exchange"] == 30
    assert table["feat"]["reduce"] == 13
    assert table["replicas x parts x feat"]["reduce"] == 9 + 4


def test_step_comm_per_epoch_raises_without_exchange_events(tmp_path):
    """A trace window holding train_step launches but NO device exchange
    events (observed when the step compiles inside the window on XLA:CPU)
    must raise a TraceError that names the cause, not fabricate a 0.0 Comm
    column — unless the program exchanges nothing (1 part / grad-only),
    where 0 s is the truth."""
    import gzip
    import json

    from bnsgcn_tpu.utils.traceparse import TraceError, step_comm_per_epoch

    def write_trace(events):
        d = tmp_path / "plugins" / "profile" / "run1"
        d.mkdir(parents=True, exist_ok=True)
        with gzip.open(d / "host.trace.json.gz", "wt") as f:
            json.dump({"traceEvents": events}, f)

    # launches but no collectives -> named error, or a true 0 s
    write_trace([_meta(1, 0, "python"), _meta(1, 10, "dev0"),
                 _ev(1, 0, "PjitFunction(train_step)", 1000.0, 300)])
    with pytest.raises(TraceError, match="no device exchange span"):
        step_comm_per_epoch(str(tmp_path), True)
    assert step_comm_per_epoch(str(tmp_path), False) == (0.0, 0.0, 1)

    # healthy window -> per-step seconds
    write_trace(make_trace())
    parsed = step_comm_per_epoch(str(tmp_path), True)
    ex_s, rd_s, steps = parsed
    assert steps == 2
    # min-over-lanes: 2 steps x last-arriver span 10 us -> 10us/step
    assert abs(ex_s - 10e-6) < 1e-9
    assert abs(rd_s - 7e-6) < 1e-9

    # missing trace dir -> named error
    with pytest.raises(TraceError, match="wrote no"):
        step_comm_per_epoch(str(tmp_path / "nope"), True)


def test_obs_report_reparses_with_the_rule_the_run_used(tmp_path, capsys):
    """The live run and tools/obs_report.py read the same trace by the same
    rule: run.py records `exchanges` (False at 1 part / grad-only) in the obs
    `trace` event and the report passes it to the parser. Without it the
    report called a 1-part run's own trace 'lost' after the run had printed
    [traced] 0.0000 for it."""
    import gzip
    import json

    import obs_report
    d = tmp_path / "prof" / "plugins" / "profile" / "run1"
    d.mkdir(parents=True)
    with gzip.open(d / "host.trace.json.gz", "wt") as f:
        json.dump({"traceEvents": [
            _meta(1, 0, "python"), _meta(1, 10, "dev0"),
            _ev(1, 0, "PjitFunction(train_step)", 1000.0, 300)]}, f)

    def report(exchanges):
        log = tmp_path / f"obs_{exchanges}.jsonl"
        log.write_text(json.dumps({
            "kind": "trace", "epoch": 9, "comm_s": 0.0, "reduce_s": 0.0,
            "exchanges": exchanges, "trace_dir": str(tmp_path / "prof")})
            + "\n")
        assert obs_report.main([str(log)]) == 0
        return capsys.readouterr().out

    assert "exchange 0.00 ms reduce 0.00 ms over 1 steps" in report(False)
    assert "failed: 1 train_step launch(es)" in report(True)


def test_step_comm_on_a_recorded_v5e_trace():
    """The reduction runs on what a TPU really writes. tests/data/
    v5e_p4_step_comm.trace.json.gz is a P=4 auto-trace window recorded on
    four v5e chips (PR 22), cut down to launches + collective spans. The
    chip names its all-to-all instructions `all_to_all.N` (underscores,
    after the jax primitive) and its all-reduces `all-reduce.N`: a parser
    that only knew the hyphenated opcode spelling found 4 train steps and
    no exchange at all, and run.py printed [sampled] numbers for it."""
    import gzip
    import json

    from bnsgcn_tpu.utils.traceparse import (attribute, comm_by_axis,
                                             step_comm_from_events)
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "v5e_p4_step_comm.trace.json.gz")
    with gzip.open(path, "rt") as f:
        events = json.load(f)["traceEvents"]
    attr = attribute(events)
    assert attr["train_step"]["launches"] == 4
    # 3 exchanging layers x (forward + transposed backward) per step, on
    # each of the 4 device lanes; one fused gradient all-reduce family
    lanes = attr["train_step"]["exchange"]
    assert len(lanes) == 4 and {len(v) for v in lanes.values()} == {24}
    assert {len(v) for v in attr["train_step"]["reduce"].values()} == {8}
    ex_s, rd_s, steps = step_comm_from_events(events, True)
    assert steps == 4 and 0 < rd_s < ex_s < 1e-3
    assert set(comm_by_axis(events, 4)) == {"parts"}
