"""Elastic world size (--elastic on): coordinated RESIZE instead of exit.

The tentpole contract, proven with real subprocesses on the CPU container
(the same external-rank harness as tests/test_coord_e2e.py):

* rank loss at W=2 -> the survivor detects the heartbeat silence, agrees a
  RESIZE verdict with itself, re-maps both parts onto its slots
  (mesh.plan_slots — no METIS rerun), restores the agreed checkpoint with
  the resize nonce folded into the sampling/dropout streams, and trains to
  completion with exit 0 — no process ever exits non-zero;
* a replacement rank relaunched after the shrink verdict rejoins through
  the lost-rank beacon, the world grows back to W=2, and the healed final
  loss is BITWISE the shrink-only run's (grow restores the newest valid
  checkpoint with NO new nonce, so the replay is timing-independent);
* --elastic off (the default) and --elastic on with no fault are both
  bitwise-identical to the historical coordinated pair;
* the verdict cadence knob ($BNSGCN_COORD_AGREE_EVERY) defers off-boundary
  exchanges while latching the worst local state — verdict latency is at
  most K boundaries, and `final=True` always flushes.

tools/fault_matrix.sh runs the shrink/grow stages from the shell.
"""

import os
import re
import socket
import subprocess
import sys
import threading
import time

import pytest

from bnsgcn_tpu import obs as obs_mod
from bnsgcn_tpu.config import ConfigError
from bnsgcn_tpu.parallel.coord import Coordinator, TcpTransport
from bnsgcn_tpu.parallel.mesh import plan_slots, slot_members
from bnsgcn_tpu.parallel.replicas import slot_desc
from bnsgcn_tpu.resilience import FaultPlan

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

BASE_ARGS = [
    "--dataset", "sbm", "--partition-method", "random", "--n-partitions", "2",
    "--model", "graphsage", "--n-layers", "2", "--n-hidden", "8",
    "--sampling-rate", "0.5", "--use-pp", "--n-epochs", "8",
    "--log-every", "2", "--no-eval", "--no-comm-trace",
    "--fix-seed", "--seed", "11", "--skip-partition",
]


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _env(extra=None):
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               BNSGCN_RETRY_BACKOFF_S="0", BNSGCN_COORD_TIMEOUT_S="60",
               # fast loss detection: 3s > the 2s alive-beat period
               BNSGCN_ELASTIC_DEAD_S="3",
               PYTHONPATH=REPO)
    env.update(extra or {})
    return env


def _prepartition(tmp_path):
    subprocess.run(
        [sys.executable, "-m", "bnsgcn_tpu.partition_cli",
         "--dataset", "sbm", "--partition-method", "random",
         "--n-partitions", "2", "--fix-seed",
         "--part-path", str(tmp_path / "parts")],
        env=_env(), check=True, capture_output=True, cwd=REPO)


def _cmd(tmp_path, ckpt, port, rank, extra_args=()):
    return ([sys.executable, "-m", "bnsgcn_tpu.main"] + BASE_ARGS
            + ["--part-path", str(tmp_path / "parts"),
               "--ckpt-path", str(ckpt),
               "--results-path", str(tmp_path / "res"),
               "--coord", "tcp", "--coord-port", str(port),
               "--coord-world", "2", "--coord-rank", str(rank)]
            + list(extra_args))


def _spawn(tmp_path, ckpt, port, rank, extra_args=(), env=None, tag=""):
    """One rank process with stdout to a FILE (pollable mid-run)."""
    logf = open(tmp_path / f"rank{rank}{tag}.log", "w")
    p = subprocess.Popen(
        _cmd(tmp_path, ckpt, port, rank, extra_args),
        stdout=logf, stderr=subprocess.STDOUT, text=True, cwd=REPO,
        env=env or _env())
    p._logf, p._logpath = logf, logf.name
    return p


def _finish(p, timeout=240):
    try:
        rc = p.wait(timeout=timeout)
    finally:
        if p.poll() is None:
            p.kill()
            p.wait()
        p._logf.close()
    with open(p._logpath) as f:
        return rc, f.read()


def _wait_for(path, needle, timeout=120):
    dl = time.time() + timeout
    while time.time() < dl:
        with open(path) as f:
            if needle in f.read():
                return True
        time.sleep(0.25)
    return False


def _run_pair(tmp_path, ckpt, extra_args=(), env=None, timeout=240):
    port = _free_port()
    procs = [_spawn(tmp_path, ckpt, port, r, extra_args, env=env)
             for r in (0, 1)]
    return [_finish(p, timeout) for p in procs]


def _final_loss(out: str) -> str:
    m = re.search(r"RESULT final_loss=(\S+)", out)
    assert m, f"no RESULT line in output:\n{out[-2000:]}"
    return m.group(1)       # string compare == bitwise pin


# ----------------------------------------------------------------------------
# part -> slot planning (mesh.plan_slots) + rendering
# ----------------------------------------------------------------------------

def test_plan_slots_contiguous_balanced_blocks():
    assert plan_slots(4, 2) == (0, 0, 1, 1)
    assert plan_slots(5, 2) == (0, 0, 0, 1, 1)
    assert plan_slots(4, 3) == (0, 0, 1, 2)
    # identity at P == W: today's worker-per-part layout
    assert plan_slots(4, 4) == (0, 1, 2, 3)
    assert plan_slots(1, 1) == (0,)
    with pytest.raises(ValueError):
        plan_slots(4, 0)
    with pytest.raises(ValueError):
        plan_slots(2, 3)            # empty workers are never planned


def test_slot_members_inverse_view():
    assert slot_members((0, 0, 1, 1)) == {0: [0, 1], 1: [2, 3]}
    # works on part -> rank maps too (a RESIZE decision's 'slots')
    assert slot_members((2, 2, 5, 5)) == {2: [0, 1], 5: [2, 3]}


def test_slot_desc_renders_hosting_ranks():
    assert slot_desc((0, 0, 1, 1), [0, 1]) == "rank0:[p0,p1] rank1:[p2,p3]"
    # survivor set {0, 2}: parts re-hosted onto the remaining rank ids
    assert slot_desc((0, 0, 2, 2), [0, 2]) == "rank0:[p0,p1] rank2:[p2,p3]"
    # empty map = identity world (worker == part)
    assert slot_desc((), [0, 1]) == "rank0:[p0] rank1:[p1]"


# ----------------------------------------------------------------------------
# --inject ranklost grammar
# ----------------------------------------------------------------------------

def test_ranklost_grammar_requires_rank_target():
    with pytest.raises(ConfigError, match="losing every rank"):
        FaultPlan.parse("ranklost@E3")
    # targeted form arms only the named rank; the other ranks validate the
    # term but skip it
    assert FaultPlan.parse("ranklost@E3:r1", rank=1).faults == {
        "ranklost": {3}}
    assert FaultPlan.parse("ranklost@E3:r1", rank=0).empty()
    with pytest.raises(ValueError, match="unknown --inject fault"):
        FaultPlan.parse("rankloss@E3:r1")


# ----------------------------------------------------------------------------
# verdict cadence ($BNSGCN_COORD_AGREE_EVERY)
# ----------------------------------------------------------------------------

def _cadence_pair(k=None):
    port = _free_port()
    t0 = TcpTransport("127.0.0.1", port, serve=True)
    t1 = TcpTransport("127.0.0.1", port, serve=False)
    return (Coordinator(0, 2, t0, 10.0, log=lambda *a: None),
            Coordinator(1, 2, t1, 10.0, log=lambda *a: None))


def _run2(f0, f1):
    out, errs = {}, {}

    def wrap(rank, fn):
        try:
            out[rank] = fn()
        except Exception as ex:
            errs[rank] = ex

    ts = [threading.Thread(target=wrap, args=(r, f))
          for r, f in ((0, f0), (1, f1))]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=30)
    assert not errs, errs
    return out[0], out[1]


def test_cadence_defers_latches_and_bounds_verdict_latency(monkeypatch):
    """K=3: off-boundary calls return an immediate deferred 'ok' with no
    exchange; a 'diverged' reported at call 0 latches and MUST be decided
    by call 2 (the K-th boundary) — verdict latency <= K boundaries."""
    monkeypatch.setenv("BNSGCN_COORD_AGREE_EVERY", "3")
    c0, c1 = _cadence_pair()
    try:
        assert c0.agree_every == c1.agree_every == 3

        def decide(name, states):
            assert name == "rollback" and states[1] == "diverged"
            return {"decision": "rollback", "restart": 1, "nonce": 1,
                    "source": "<test>", "backoff_s": 0.0}

        # calls 0 and 1: both ranks defer instantly (no exchange — no
        # threads needed), rank 1's diverged latches
        for ep, s1 in ((0, "diverged"), (1, "ok")):
            d0 = c0.agree(ep, "ok", decide_fn=decide)
            d1 = c1.agree(ep, s1)
            assert d0 == {"decision": "ok", "epoch": ep, "deferred": True}
            assert d1 == {"decision": "ok", "epoch": ep, "deferred": True}
        # call 2 is the K-th boundary: the latched diverged must surface
        d0, d1 = _run2(lambda: c0.agree(2, "ok", decide_fn=decide),
                       lambda: c1.agree(2, "ok"))
        for d in (d0, d1):
            assert d["decision"] == "rollback" and not d.get("deferred")
            assert d["restart"] == 1
    finally:
        c0.close()
        c1.close()


def test_cadence_final_flushes_off_boundary(monkeypatch):
    """final=True (the last step boundary) always exchanges, so a latched
    verdict can never die with the run."""
    monkeypatch.setenv("BNSGCN_COORD_AGREE_EVERY", "4")
    c0, c1 = _cadence_pair()
    try:
        d0 = c0.agree(0, "ok")
        d1 = c1.agree(0, "preempted")
        assert d0.get("deferred") and d1.get("deferred")

        def decide(name, states):
            return {"decision": name, "ranks": [r for r, s in states.items()
                                                if s == "preempted"]}

        d0, d1 = _run2(
            lambda: c0.agree(1, "ok", decide_fn=decide, final=True),
            lambda: c1.agree(1, "ok", final=True))
        for d in (d0, d1):
            assert d["decision"] == "preempt" and not d.get("deferred")
        assert d0["ranks"] == [1]
    finally:
        c0.close()
        c1.close()


def test_cadence_default_is_every_boundary():
    c0, c1 = _cadence_pair()
    try:
        assert c0.agree_every == 1
        d0, d1 = _run2(lambda: c0.agree(0, "ok"), lambda: c1.agree(0, "ok"))
        assert not d0.get("deferred") and not d1.get("deferred")
    finally:
        c0.close()
        c1.close()


# ----------------------------------------------------------------------------
# subprocess e2e: shrink, grow, bitwise pins
# ----------------------------------------------------------------------------

def test_elastic_on_needs_coordinator():
    """--elastic on without the rank coordinator is a named config error
    (exit 2), never a silent no-op."""
    r = subprocess.run(
        [sys.executable, "-m", "bnsgcn_tpu.main"] + BASE_ARGS
        + ["--coord", "off", "--elastic", "on", "--part-path", "/nonexistent"],
        capture_output=True, text=True, timeout=120, cwd=REPO, env=_env())
    assert r.returncode == 2, (r.stdout, r.stderr)
    assert "--elastic on needs the rank coordinator" in r.stderr


@pytest.mark.quickgate
def test_elastic_shrink_trains_through_rank_loss(tmp_path):
    """The tentpole pin, shrink half: rank 1 vanishes at epoch 3 with no
    goodbye; the survivor imputes the loss from heartbeat silence, agrees
    a RESIZE with itself, re-hosts both parts, folds the resize nonce, and
    trains to completion — exit 0 on every process, resize obs event with
    the part -> rank map emitted."""
    _prepartition(tmp_path)
    obs_log = str(tmp_path / "obs.jsonl")
    outs = _run_pair(tmp_path, tmp_path / "ck",
                     ["--elastic", "on", "--inject", "ranklost@E3:r1",
                      "--obs-log", obs_log])
    assert [rc for rc, _ in outs] == [0, 0], outs
    r0, r1 = outs[0][1], outs[1][1]
    assert "imputing 'lost'" in r0, r0[-2000:]
    assert "agreed resize, world 2 -> 1 (survivors [0])" in r0
    assert "world resized to 1 (members [0], lost [1])" in r0
    assert "ranklost resize to world 1" in r0 and "rank0:[p0,p1]" in r0
    assert "resize-nonce 1" in r0
    assert "RESULT final_loss=" in r0          # trained to completion
    assert "injected rank loss at epoch 3" in r1
    assert "RESULT" not in r1                  # the lost rank never finished
    ev = [e for e in obs_mod.load_events(obs_log) if e["kind"] == "resize"]
    assert len(ev) == 1, ev
    assert ev[0]["old_world"] == 2 and ev[0]["world"] == 1
    assert ev[0]["members"] == [0] and ev[0]["lost"] == [1]
    assert ev[0]["slots"] == [0, 0] and ev[0]["trigger"] == "ranklost"
    assert ev[0]["nonce"] == 1


@pytest.mark.quickgate
def test_elastic_grow_round_trip_bitwise_replay(tmp_path):
    """The tentpole pin, grow half: after the shrink verdict a replacement
    rank 1 relaunches (same CLI, no injection — the documented contract),
    finds the lost-rank beacon, rejoins through the grant handshake, and
    the world grows back to 2. Both ranks finish with exit 0 and BITWISE
    equal final losses; the healed loss also equals a shrink-only run of
    the same fault — grow restores the newest valid checkpoint with NO new
    nonce, so the outcome is independent of when the rejoin happened."""
    _prepartition(tmp_path)
    # throttle epochs so the fast CPU run stays alive across the
    # replacement's process startup (JAX init + compile)
    env = _env({"BNSGCN_EPOCH_THROTTLE_S": "1.0"})
    args = ["--elastic", "on", "--n-epochs", "24"]
    port = _free_port()
    p0 = _spawn(tmp_path, tmp_path / "ck", port, 0, args, env=env)
    p1 = _spawn(tmp_path, tmp_path / "ck", port, 1,
                args + ["--inject", "ranklost@E3:r1"], env=env)
    rc1, out1 = _finish(p1)
    assert rc1 == 0 and "injected rank loss" in out1, out1[-2000:]
    # the relaunch contract: the replacement comes up AFTER the shrink
    # verdict has landed on the survivor
    assert _wait_for(p0._logpath, "world resized to 1"), "no shrink verdict"
    p1b = _spawn(tmp_path, tmp_path / "ck", port, 1, args, env=env, tag="b")
    rc0, out0 = _finish(p0, timeout=300)
    rc1b, out1b = _finish(p1b, timeout=300)
    assert rc0 == 0 and rc1b == 0, (rc0, out0[-2000:], rc1b, out1b[-2000:])
    assert "rejoined at epoch" in out0 and "world resized to 2" in out0
    assert "rejoining a resized world (lost-rank beacon found)" in out1b
    assert "rejoined world 2" in out1b and "in lockstep" in out1b
    healed = _final_loss(out0)
    assert _final_loss(out1b) == healed        # joiner is bitwise in step

    # deterministic replay: the same fault with NO rejoin must land on the
    # same trajectory (throttle off — wall time never changes the numbers)
    outs = _run_pair(tmp_path, tmp_path / "ck_replay",
                     args + ["--inject", "ranklost@E3:r1"], timeout=300)
    assert outs[0][0] == 0, outs[0][1][-2000:]
    assert _final_loss(outs[0][1]) == healed


@pytest.mark.quickgate
def test_elastic_off_and_idle_elastic_on_are_bitwise_identical(tmp_path):
    """--elastic off (the default protocol, exit-code table unchanged) and
    --elastic on with no fault must both be bitwise the historical
    coordinated pair: elastic only changes what a rank LOSS means."""
    _prepartition(tmp_path)
    off = _run_pair(tmp_path, tmp_path / "ck_off")
    assert [rc for rc, _ in off] == [0, 0], off
    want = _final_loss(off[0][1])
    assert _final_loss(off[1][1]) == want
    on = _run_pair(tmp_path, tmp_path / "ck_on", ["--elastic", "on"])
    assert [rc for rc, _ in on] == [0, 0], on
    assert _final_loss(on[0][1]) == want
    assert _final_loss(on[1][1]) == want
    # no resize machinery ever engaged
    for _, out in on:
        assert "resize" not in out
