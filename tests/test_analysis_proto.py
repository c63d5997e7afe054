"""graftcheck-proto (bnsgcn_tpu/analysis/proto/): protocol model checking.

Seeded-protocol-bug fixtures per invariant — each named bug in
analysis/proto/seeded.py reverts one design decision of the coordination
protocol (confirm barrier, doubled ack windows, prune horizon, file
boot-token pinning, worst-wins reduction) and the checker MUST catch it
with the documented rule and a replayable minimized schedule — plus unit
coverage for the deterministic scheduler (replay determinism, hang
detection, DFS enumeration) and the quickgate clean-at-HEAD gate:
`python -m bnsgcn_tpu.analysis proto` explores >= 1000 schedules across
>= 8 scenarios with zero findings inside the CI budget.
"""

import json
import os
import re
import subprocess
import sys

import pytest

from bnsgcn_tpu.analysis.proto import run_proto_audit, run_replay
from bnsgcn_tpu.analysis.proto.explore import run_schedule
from bnsgcn_tpu.analysis.proto.scenarios import ALL_SCENARIOS
from bnsgcn_tpu.analysis.proto.sim import Scheduler

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_REPLAY_RE = re.compile(r"--replay '([^']+)'")


def _env():
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO)
    return env


# ----------------------------------------------------------------------------
# the scheduler itself
# ----------------------------------------------------------------------------

def test_scheduler_replay_is_deterministic(tmp_path):
    """Same scenario + fault + prescribed prefix => identical trail,
    outcomes, and op trace — the property every --replay rests on."""
    scenario = next(s for s in ALL_SCENARIOS if s.name == "agree-ok")
    a = run_schedule(scenario, 0, [1, 0, 1], str(tmp_path), None)
    b = run_schedule(scenario, 0, [1, 0, 1], str(tmp_path), None)
    assert a.choices == b.choices
    assert a.outcomes == b.outcomes
    assert [t[1:] for t in a.trace] == [t[1:] for t in b.trace]


def test_scheduler_detects_hang():
    sched = Scheduler(time_budget=1.0)

    def stuck():
        while True:
            sched.sleep(10.0)       # sleeps forever past the budget

    sched.spawn(0, stuck)
    sched.run()
    assert sched.hung
    assert sched.actors[0].state == "aborted"   # unwound, thread joined


def test_dfs_explores_distinct_schedules(tmp_path):
    scenario = next(s for s in ALL_SCENARIOS if s.name == "broadcast-resume")
    seen = set()
    prefix = []
    for _ in range(50):
        rec = run_schedule(scenario, 0, prefix, str(tmp_path), None)
        key = tuple(rec.choices)
        assert key not in seen      # every DFS step is a NEW interleaving
        seen.add(key)
        from bnsgcn_tpu.analysis.proto.explore import _next_prefix
        nxt = _next_prefix(rec.choices, rec.options)
        if nxt is None:
            break
        prefix = nxt
    assert len(seen) > 1


# ----------------------------------------------------------------------------
# seeded protocol bugs: each must be caught, with a working replay
# ----------------------------------------------------------------------------

SEEDED = [
    # (bug, scenario that catches it, rule that must fire)
    ("confirm-removed", "agree-preempt", "proto-exit-code"),
    ("ack-window-dropped", "slow-decide", "proto-exit-code"),
    ("retire-horizon-1", "retirement-lag", "proto-retired-live-key"),
    ("pin-before-get", "file-relaunch", "proto-exit-code"),
    ("reduce-order-flipped", "agree-worst-wins", "proto-reduce-order"),
    ("rejoin-token-unchecked", "rejoin-stale-token", "proto-exit-code"),
    ("failover-retries-nonidempotent-write", "wal-replay-vs-live-delta",
     "proto-duplicate-write"),
]


@pytest.mark.parametrize("bug,scenario,rule", SEEDED,
                         ids=[b for b, _, _ in SEEDED])
def test_seeded_bug_caught_and_replayable(bug, scenario, rule):
    report = run_proto_audit(scenarios=[scenario], max_schedules=400,
                             seed_bug=bug)
    assert report["ok"] is False
    assert rule in report["counts"], report["counts"]
    finding = next(f for f in report["findings"] if f["rule"] == rule)
    assert finding["file"].startswith(f"proto://{scenario}#")
    spec = _REPLAY_RE.search(finding["message"]).group(1)
    # the minimized schedule reproduces the violation under the seed...
    rep = run_replay(spec, seed_bug=bug)
    assert rep["ok"] is False
    assert rule in {v["rule"] for v in rep["violations"]}
    # ...and the SAME schedule is clean on the real protocol at HEAD
    assert run_replay(spec)["ok"] is True


def test_unknown_seed_bug_and_bad_spec_raise():
    with pytest.raises(ValueError, match="unknown seeded bug"):
        run_proto_audit(scenarios=["agree-ok"], max_schedules=100,
                        seed_bug="no-such-bug")
    with pytest.raises(ValueError, match="bad replay spec"):
        run_replay("not-a-spec")
    with pytest.raises(ValueError, match="unknown scenario"):
        run_proto_audit(scenarios=["no-such-scenario"])


# ----------------------------------------------------------------------------
# elastic RESIZE scenarios: the default schedules pin the verdict shapes
# ----------------------------------------------------------------------------

def test_elastic_scenarios_resize_through_rank_loss(tmp_path):
    """crash-during-resize fault 'shrink' (rank 2 dies at its first
    heartbeat): both survivors finish DONE — no exit code at all — on the
    same shrunken member set, restored at the agreed epoch."""
    s = next(x for x in ALL_SCENARIOS if x.name == "crash-during-resize")
    assert [n for n, _ in s.faults()][1] == "shrink"
    rec = run_schedule(s, 1, [], str(tmp_path), None)
    assert rec.outcomes[2] == ("crashed",)
    vals = {r: json.loads(o[1]) for r, o in rec.outcomes.items()
            if o[0] == "done"}
    assert set(vals) == {0, 1}
    assert all(v == {"resizes": 1, "members": [0, 1]} for v in vals.values())


def test_elastic_scenario_rejoin_skips_stale_grant(tmp_path):
    """rejoin-stale-token nominal: the joiner reads the planted stale
    grant, skips it on the token mismatch, and adopts the fresh one —
    both ranks converge on the grown member set and the same seq."""
    s = next(x for x in ALL_SCENARIOS if x.name == "rejoin-stale-token")
    rec = run_schedule(s, 0, [], str(tmp_path), None)
    vals = {r: json.loads(o[1]) for r, o in rec.outcomes.items()
            if o[0] == "done"}
    assert set(vals) == {0, 1}
    assert vals[0] == vals[1]
    assert vals[0]["members"] == [0, 1] and vals[0]["restart"] == 6


# ----------------------------------------------------------------------------
# CLI + obs event
# ----------------------------------------------------------------------------

def test_cli_audit_emits_proto_audit_event(tmp_path):
    log = tmp_path / "obs.jsonl"
    r = subprocess.run(
        [sys.executable, "-m", "bnsgcn_tpu.analysis", "proto", "-q",
         "--scenario", "broadcast-resume,agree-preempt",
         "--max-schedules", "200", "--json", "-",
         "--obs-log", str(log)],
        capture_output=True, text=True, timeout=300, cwd=REPO, env=_env())
    assert r.returncode == 0, r.stdout + r.stderr
    data = json.loads(r.stdout)
    assert data["ok"] is True and data["n_scenarios"] == 2
    from bnsgcn_tpu.obs import load_events
    evs = [e for e in load_events(str(log)) if e.get("kind") == "proto_audit"]
    assert len(evs) == 1 and evs[0]["ok"] is True
    assert evs[0]["n_schedules"] == data["n_schedules"]
    # the report renderer gives the preflight verdict its own section
    rep = subprocess.run(
        [sys.executable, "tools/obs_report.py", str(log)],
        capture_output=True, text=True, timeout=120, cwd=REPO, env=_env())
    assert rep.returncode == 0, rep.stdout + rep.stderr
    assert "proto_audit: clean" in rep.stdout


def test_cli_replay_bad_spec_exits_2():
    r = subprocess.run(
        [sys.executable, "-m", "bnsgcn_tpu.analysis", "proto",
         "--replay", "bogus"],
        capture_output=True, text=True, timeout=120, cwd=REPO, env=_env())
    assert r.returncode == 2
    assert "bad replay spec" in r.stderr


# ----------------------------------------------------------------------------
# the gate
# ----------------------------------------------------------------------------

@pytest.mark.quickgate
def test_proto_audit_clean_at_head(tmp_path):
    """The gate: the real Coordinator/ResilienceManager protocol explores
    clean at HEAD — >= 1000 distinct schedules across >= 8 scenarios
    (crashes, delays, torn acks, stale boot tokens, duplicate relaunches)
    with zero findings and zero explore errors, inside the CI budget."""
    rep = tmp_path / "proto.json"
    r = subprocess.run(
        [sys.executable, "-m", "bnsgcn_tpu.analysis", "proto", "-q",
         "--json", str(rep)],
        capture_output=True, text=True, timeout=300, cwd=REPO, env=_env())
    assert r.returncode == 0, r.stdout + r.stderr
    data = json.loads(rep.read_text())
    assert data["ok"] is True and data["findings"] == []
    assert data["errors"] == []
    assert data["n_scenarios"] >= 8
    assert data["n_schedules"] >= 1000
    assert data["elapsed_s"] <= 120
    names = {row["name"] for row in data["scenarios"]}
    assert {"agree-ok", "rollback-ack", "file-boot-stale",
            "file-relaunch", "resize-during-rollback",
            "crash-during-resize", "rejoin-stale-token",
            "router-failover", "rejoin-stale-incarnation",
            "wal-replay-vs-live-delta"} <= names
    # file-transport scenarios ran the REAL FileTransport
    assert all(row["schedules"] > 0 for row in data["scenarios"])
    # truncation, if any, is recorded — never silent
    assert set(data["truncated"]) <= names
