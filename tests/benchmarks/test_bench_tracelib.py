"""The busy/idle and kernel-span reducers and the copied step_comm reduction
on the recorded v5e trace in tests/data/ (P=4, four train steps)."""

import gzip
import json
import os

import pytest

import bench_tiny
from benchmarks import tracelib

FIXTURE = os.path.join(bench_tiny.REPO, "tests", "data",
                       "v5e_p4_step_comm.trace.json.gz")


@pytest.fixture(scope="module")
def events():
    with gzip.open(FIXTURE, "rt") as f:
        return json.load(f)["traceEvents"]


def test_step_comm_equals_the_programs_own_reduction(events):
    from bnsgcn_tpu.utils import traceparse
    mine = tracelib.step_comm_from_events(events, expect_exchange=True)
    assert mine == traceparse.step_comm_from_events(events, True)
    ex_s, rd_s, steps = mine
    assert steps == 4 and ex_s > 0 and rd_s > 0


def test_launches_count_each_step_once(events):
    assert len(tracelib.launches(events)) == 4
    assert len(tracelib.launches(events, "exchange_only")) == 3


def test_busy_is_the_union_of_the_op_lane(events):
    busy, window_s = tracelib.device_busy(events)
    assert set(busy) == {f"/device:TPU:{k}" for k in range(4)}
    spans = tracelib.device_op_spans(events)
    t0, t1 = tracelib.traced_window(events)
    for dev, sp in spans.items():
        raw = sum(min(e, t1) - max(s, t0) for s, e, _ in sp
                  if e > t0 and s < t1) / 1e6
        assert 0 < busy[dev] <= raw + 1e-12
        assert busy[dev] < window_s
    assert window_s == pytest.approx((t1 - t0) / 1e6)


def _synthetic(n_steps=1):
    ev = [{"ph": "M", "pid": 3, "name": "process_name",
           "args": {"name": "/device:TPU:0"}},
          {"ph": "M", "pid": 3, "tid": 2, "name": "thread_name",
           "args": {"name": "XLA Modules"}},
          {"ph": "M", "pid": 3, "tid": 3, "name": "thread_name",
           "args": {"name": "XLA Ops"}},
          {"ph": "M", "pid": 9, "tid": 1, "name": "thread_name",
           "args": {"name": "python"}}]
    for k in range(n_steps):
        t = 2000.0 * k
        ev += [{"ph": "X", "pid": 9, "tid": 1, "ts": t, "dur": 5.0,
                "name": "PjitFunction(train_step)"},
               {"ph": "X", "pid": 3, "tid": 2, "ts": t + 100.0, "dur": 900.0,
                "name": "jit_train_step(123)"},
               {"ph": "X", "pid": 3, "tid": 3, "ts": t + 100.0, "dur": 300.0,
                "name": "fusion.1"},
               {"ph": "X", "pid": 3, "tid": 3, "ts": t + 200.0, "dur": 100.0,
                "name": "fusion.2"},
               {"ph": "X", "pid": 3, "tid": 3, "ts": t + 900.0, "dur": 100.0,
                "name": "bns_tile_matmul.3", "args": {"long_name": "x"}}]
    return ev


def test_busy_union_does_not_count_overlap_twice():
    ev = _synthetic()
    busy, window_s = tracelib.device_busy(ev)
    assert busy == {"/device:TPU:0": pytest.approx(400e-6)}
    assert window_s == pytest.approx(1000e-6)
    gaps = tracelib.idle_gaps(ev)
    assert gaps[0] == ["inside_step", pytest.approx(500e-6)]
    assert gaps[1] == ["between_steps", pytest.approx(100e-6)]
    assert tracelib.top_device_ops(ev)[0] == ["fusion", pytest.approx(400e-6)]
    assert tracelib.kernel_spans(ev, "bns_tile_matmul") == {
        "/device:TPU:0": [(pytest.approx(100e-6), "x")]}


def test_window_leaves_out_the_first_traced_step():
    """The first traced step carries the profiler's start-up: with three
    launches or more the busy window starts at the second."""
    ev = _synthetic(4)
    busy, window_s = tracelib.device_busy(ev)
    assert window_s == pytest.approx((3 * 2000.0 - 1000.0) * 1e-6)
    assert busy["/device:TPU:0"] == pytest.approx(3 * 400e-6)
    # the gaps are listed over the whole trace, the first step included
    kinds = [k for k, _ in tracelib.idle_gaps(ev)]
    assert kinds.count("between_steps") == 4 and "inside_step" in kinds


def test_kernel_spans_by_name_on_the_recorded_trace(events):
    spans = tracelib.kernel_spans(events, "all_to_all")
    assert set(spans) == {f"/device:TPU:{k}" for k in range(4)}
    assert all("all-to-all(" in ln for sp in spans.values() for _, ln in sp)
    assert tracelib.kernel_spans(events, "bns_tile_matmul") == {}


def test_a_trace_without_device_lanes_reads_nothing():
    ev = [{"ph": "X", "pid": 1, "tid": 1, "ts": 0.0, "dur": 5.0,
           "name": "PjitFunction(train_step)"}]
    assert tracelib.device_busy(ev) == ({}, 0.0)
    assert tracelib.idle_gaps(ev) == [] and tracelib.top_device_ops(ev) == []


def test_reducers_read_the_recorded_trace(events):
    from benchmarks import harness
    ctx = {"trace_events": events, "breakdown_notes": {},
           "device": {"kind": "TPU v5 lite"}}
    idle = harness.load_reducer("device_idle")(ctx)
    assert 0 < idle < 100
    ex = harness.load_reducer("step_comm")(ctx, which="exchange")
    rd = harness.load_reducer("step_comm")(ctx, which="reduce")
    assert ex == tracelib.step_comm_from_events(events, True)[0] and rd > 0
    per_step = harness.load_reducer("kernel_time")(ctx, kernel="all_to_all")
    assert per_step > 0
    assert harness.load_reducer("kernel_time")(
        ctx, kernel="bns_tile_matmul") is None
    assert harness.load_reducer("kernel_roofline")(
        ctx, kernel="bns_tile_matmul") is None
