"""Self-healing serving fleet (serve_router.py health machinery): the
unit matrix for the pure state — HealthState transitions including the
circuit breaker, the bounded failover DeltaWAL, the --inject serve-fault
grammar, degraded answer payloads, incarnation-token refusal, and
health-filtered fleet snapshots — plus the subprocess e2e: a 2x2 fleet
through servekill@3:p0.r0 mid-load with ZERO failed client answers,
a WAL-queued delta during the outage, and the relaunched backend
rejoining bitwise through WAL replay + warm-up.
The schedule-exploration twin lives in analysis/proto (router-failover /
rejoin-stale-incarnation / wal-replay-vs-live-delta scenarios)."""

import time

import numpy as np
import pytest

from bnsgcn_tpu import resilience
from bnsgcn_tpu import serve_router as sr
from bnsgcn_tpu.config import ConfigError

_silent = lambda *a, **k: None  # noqa: E731


def _policy(suspect_after=1, down_after=2, readmit=1, breaker_flaps=99,
            breaker_window_s=100.0, breaker_hold_s=5.0, spotcheck=1):
    """Env-independent policy: every threshold pinned explicitly so the
    unit matrix is immune to BNSGCN_SERVE_* leaking in from the host."""
    pol = sr.HealthPolicy(0.0)
    pol.probe_timeout_s = 0.2
    pol.suspect_after = suspect_after
    pol.down_after = down_after
    pol.readmit = readmit
    pol.breaker_flaps = breaker_flaps
    pol.breaker_window_s = breaker_window_s
    pol.breaker_hold_s = breaker_hold_s
    pol.spotcheck = spotcheck
    pol.hedge_floor_ms = 0.0
    return pol


# ----------------------------------------------------------------------------
# HealthState: every transition of the up/suspect/down/quarantined machine
# ----------------------------------------------------------------------------

def test_health_up_suspect_down_thresholds():
    hs = sr.HealthState(_policy(suspect_after=2, down_after=3))
    assert hs.on_fail(1.0) is None and hs.state == "up"
    assert hs.on_fail(2.0) == "suspect"
    assert hs.on_fail(3.0) == "down"
    assert hs.down_since == 3.0


def test_health_suspect_recovers_to_up_without_warmup():
    hs = sr.HealthState(_policy(suspect_after=1, down_after=3, readmit=2))
    assert hs.on_fail(1.0) == "suspect"
    assert hs.on_ok(2.0) is None        # streak 1/2
    assert hs.on_ok(3.0) == "up"        # no warm-up gate: never left
    assert hs.state == "up" and hs.oks == 0


def test_health_ok_resets_fail_streak():
    hs = sr.HealthState(_policy(suspect_after=2, down_after=3))
    hs.on_fail(1.0)
    hs.on_ok(2.0)
    assert hs.on_fail(3.0) is None      # streak restarted, still up
    assert hs.state == "up"


def test_health_down_earns_ready_then_admit_reports_outage():
    hs = sr.HealthState(_policy(down_after=2, readmit=2))
    hs.on_fail(1.0)
    assert hs.on_fail(2.0) == "down"
    assert hs.on_ok(3.0) is None
    assert hs.on_ok(4.0) == "ready"     # warm-up gate before up
    assert hs.state == "down"           # ready is a verdict, not a state
    assert hs.admit(10.0) == pytest.approx(8.0)
    assert hs.state == "up" and hs.down_since is None


def test_health_reject_warmup_re_earns_whole_streak():
    hs = sr.HealthState(_policy(down_after=1, readmit=2))
    hs.on_fail(1.0)
    hs.on_ok(2.0)
    assert hs.on_ok(3.0) == "ready"
    hs.reject_warmup()                  # spot-check failed: stay down
    assert hs.state == "down"
    assert hs.on_ok(4.0) is None        # streak starts over
    assert hs.on_ok(5.0) == "ready"


def test_health_breaker_quarantines_flapping_backend():
    hs = sr.HealthState(_policy(down_after=1, readmit=1, breaker_flaps=2,
                                breaker_window_s=100.0, breaker_hold_s=5.0))
    assert hs.on_fail(1.0) == "down"    # flap 1
    assert hs.on_ok(2.0) == "ready"
    hs.admit(2.0)
    assert hs.on_fail(3.0) == "quarantined"   # flap 2 inside the window
    assert hs.hold_until == pytest.approx(8.0)
    assert hs.on_ok(4.0) is None        # breaker holds: successes ignored
    assert hs.state == "quarantined"
    assert hs.on_ok(9.0) == "ready"     # hold expired: resumes as down,
    assert hs.state == "down"           # must re-earn the streak + warm-up


def test_health_breaker_window_forgets_old_flaps():
    hs = sr.HealthState(_policy(down_after=1, readmit=1, breaker_flaps=2,
                                breaker_window_s=10.0))
    assert hs.on_fail(1.0) == "down"
    hs.on_ok(2.0)
    hs.admit(2.0)
    # second flap lands OUTSIDE the window: plain down, no quarantine
    assert hs.on_fail(50.0) == "down"
    assert hs.state == "down"


# ----------------------------------------------------------------------------
# DeltaWAL: bound, commit order, per-replica cursors, retirement
# ----------------------------------------------------------------------------

def test_wal_orders_pending_per_replica_and_retires_full_entries():
    wal = sr.DeltaWAL(cap=8, slots=2)
    s1 = wal.record(0, {"op": "apply_feat", "node": 1}, taken={0})
    s2 = wal.record(0, {"op": "apply_delta", "edges": [[1, 2]]}, taken=set())
    assert s1 is not None and s2 == s1 + 1
    # replica 1 missed both, replica 0 only the second — commit order
    assert [op["op"] for _, op in wal.pending_for(0, 1)] == \
           ["apply_feat", "apply_delta"]
    assert [seq for seq, _ in wal.pending_for(0, 0)] == [s2]
    wal.mark_taken(0, 1, [s1])          # every slot took s1: it retires
    assert wal.depth(0) == 1
    assert wal.pending_for(0, 1) == [(s2, {"op": "apply_delta",
                                           "edges": [[1, 2]]})]
    wal.mark_taken(0, 0, [s2])
    wal.mark_taken(0, 1, [s2])
    assert wal.depth(0) == 0 and wal.snapshot() == {}
    assert wal.queued == 2
    assert wal.replayed == 3            # three per-replica confirmations


def test_wal_skips_fully_taken_and_bounds_per_part():
    wal = sr.DeltaWAL(cap=2, slots=2)
    assert wal.record(0, {"op": "mark"}, taken={0, 1}) is None
    assert wal.depth(0) == 0            # every slot took it: nothing queued
    wal.record(0, {"op": "a"}, taken=set())
    wal.record(0, {"op": "b"}, taken=set())
    with pytest.raises(sr.RouteError, match="WAL full"):
        wal.record(0, {"op": "c"}, taken=set())
    # the bound is per part: another part still has room
    assert wal.record(1, {"op": "c"}, taken=set()) is not None


# ----------------------------------------------------------------------------
# --inject serve-fault grammar (resilience.ServeFaultPlan)
# ----------------------------------------------------------------------------

def test_serve_fault_plan_targets_one_backend():
    plan = resilience.ServeFaultPlan.parse("servekill@3:p0.r1",
                                           part=0, replica=1)
    assert plan.faults == {"servekill": {3}}
    assert not plan.pop("servekill", 2)
    assert plan.pop("servekill", 3)
    assert not plan.pop("servekill", 3)     # fires exactly once
    assert plan.empty()
    # the same term scoped to a DIFFERENT backend parses to nothing
    other = resilience.ServeFaultPlan.parse("servekill@3:p0.r1",
                                            part=1, replica=0)
    assert other.empty()


def test_serve_fault_plan_servedrop_may_stay_fleet_wide():
    plan = resilience.ServeFaultPlan.parse("servedrop@2", part=1, replica=1)
    assert plan.pop("servedrop", 2)


def test_serve_fault_plan_ignores_training_terms():
    plan = resilience.ServeFaultPlan.parse("nan@E5,servedrop@2,sigterm@E3",
                                           part=0, replica=0)
    assert plan.faults == {"servedrop": {2}}


def test_serve_fault_plan_grammar_errors():
    with pytest.raises(ConfigError, match="needs an explicit"):
        resilience.ServeFaultPlan.parse("servekill@3")     # no target
    with pytest.raises(ValueError, match="bad --inject term"):
        resilience.ServeFaultPlan.parse("servehang@x:p0.r0")
    with pytest.raises(ValueError, match="backend target"):
        resilience.ServeFaultPlan.parse("servekill@3:r0.p0")


# ----------------------------------------------------------------------------
# RouterCore units (no sockets: the only registered backend is marked down
# before anything would dial it, so every path below is pure in-memory —
# except stale-ok, which dials port 1 once and times out in ~0.25 s)
# ----------------------------------------------------------------------------

def _down_core(degraded):
    core = sr.RouterCore(np.zeros(4, dtype=np.int32), 1, replicas=1,
                         hops=1, log=_silent, route_timeout_s=0.5,
                         delta_timeout_s=0.5, health=_policy(),
                         degraded=degraded)
    core.register_backend(0, 0, "127.0.0.1", 1, incarnation="inc-A")
    core._note_fail(0, 0, "unit: process died")
    core._note_fail(0, 0, "unit: process died")
    assert core.health_snapshot()["p0.r0"] == "down"
    return core


def test_degraded_partial_answers_tagged_unavailable_rows():
    core = _down_core("partial")
    row = core.predict(2)
    assert row["ok"] is True and row["status"] == "unavailable"
    assert row["node"] == 2 and row["part"] == 0 and "err" in row
    rows = core.predict_many([0, 3])
    assert [r["node"] for r in rows] == [0, 3]
    assert all(r["status"] == "unavailable" for r in rows)
    assert core.stats["requests_degraded"] == 3
    assert core.stats["requests_failed"] == 0
    core.close()


def test_degraded_off_raises_and_counts_failed():
    core = _down_core("off")
    with pytest.raises(sr.RouteError, match="no live backend"):
        core.predict(0)
    assert core.stats["requests_failed"] == 1
    core.close()


def test_degraded_stale_ok_falls_back_to_unavailable_when_unreachable():
    # stale-ok first tries a possibly-stale tier-A batch from ANY
    # registered replica; with the only one unreachable it must still
    # degrade the answer, not fail the request
    core = _down_core("stale-ok")
    row = core.predict(1)
    assert row["ok"] is True and row["status"] == "unavailable"
    core.close()


def test_stale_incarnation_token_is_refused():
    core = _down_core("partial")
    # respawn registers a fresh token: inc-A is retired, slot re-admitted
    # (replicas=1: WAL empty + no up peer means trivially-true warm-up)
    resp = core.register_backend(0, 0, "127.0.0.1", 2, incarnation="inc-B")
    assert resp["state"] == "up"
    with pytest.raises(sr.RouteError, match="stale incarnation"):
        core.register_backend(0, 0, "127.0.0.1", 3, incarnation="inc-A")
    # the zombie never displaced the live endpoint... and the CURRENT
    # token may re-register (same process reconnecting is not a zombie)
    assert core.fleet.endpoint(0, 0)["port"] == 2
    core.register_backend(0, 0, "127.0.0.1", 2, incarnation="inc-B")
    core.close()


def test_fleet_snapshot_drops_down_replicas_unless_all_down():
    core = sr.RouterCore(np.zeros(4, dtype=np.int32), 1, replicas=2,
                         hops=1, log=_silent, route_timeout_s=0.5,
                         health=_policy(), degraded="partial")
    core.register_backend(0, 0, "127.0.0.1", 1, incarnation="a")
    core.register_backend(0, 1, "127.0.0.1", 2, incarnation="b")
    core._note_fail(0, 0, "unit")
    core._note_fail(0, 0, "unit")
    entries = core.fleet_snapshot()["0"]
    assert [e["replica"] for e in entries] == [1]   # down replica filtered
    core._note_fail(0, 1, "unit")
    core._note_fail(0, 1, "unit")
    entries = core.fleet_snapshot()["0"]
    # every replica down: the raw list stays so errors name dead backends
    assert sorted(e["replica"] for e in entries) == [0, 1]
    core.close()


def test_write_fanout_skips_down_replica_and_wal_queues():
    core = _down_core("partial")
    out = core.update_feat(0, [1.0, 2.0])           # only replica is down
    assert out == {"ok": True, "dirty_new": 0, "dirty_total": 0}
    # both the feature write and its dirty-mark wave queued for the slot
    assert core.wal.depth(0) == 2
    assert core.wal.pending_for(0, 0)[0][1]["op"] == "apply_feat"
    assert core.stats["wal_queued"] == core.wal.queued == 2
    core.close()


# ----------------------------------------------------------------------------
# subprocess e2e: kill -> failover -> WAL -> rejoin, through the real CLI
# ----------------------------------------------------------------------------

@pytest.mark.quickgate
def test_e2e_servekill_failover_and_bitwise_rejoin(tmp_path, monkeypatch):
    """2 parts x 2 replicas behind a probing router in degraded 'partial'
    mode; p0.r0 dies hard (--inject servekill@3:p0.r0) under client load.
    Zero client answers may fail or degrade (its peer replica covers), a
    delta landing during the outage queues in the failover WAL, and the
    relaunched process (fresh incarnation) rejoins through WAL replay +
    warm-up — after which both p0 replicas answer tier-A bitwise."""
    from test_serve_dist_e2e import (_dump, _free_port, _setup_fleet_dirs,
                                     _spawn)
    from bnsgcn_tpu import serve

    monkeypatch.setenv("BNSGCN_SERVE_DOWN_AFTER", "2")  # subprocesses inherit
    args, g, cfg2, params, state, owner = _setup_fleet_dirs(tmp_path)
    rport = _free_port()
    router = _spawn("serve-router", args,
                    ["--serve-port", str(rport), "--part-replicas", "2",
                     "--serve-degraded", "partial", "--serve-probe-s", "0.2"])
    procs = [("router", router)]

    def backend(part, rep, extra=()):
        b = _spawn("serve-backend", args,
                   ["--serve-part", str(part), "--serve-replica", str(rep),
                    "--serve-router", f"127.0.0.1:{rport}",
                    "--serve-dir", str(tmp_path / f"sdir{part}{rep}"),
                    *extra])
        procs.append((f"backend p{part}.r{rep}", b))
        return b

    victim = backend(0, 0, ["--inject", "servekill@3:p0.r0"])
    for part, rep in ((0, 1), (1, 0), (1, 1)):
        backend(part, rep)

    def req(payload, timeout_s=60.0):
        return serve.request(rport, payload, timeout_s=timeout_s)

    def bad_rows(resp):
        rows = resp["results"] if resp.get("ok") else [resp]
        return [x for x in rows
                if not x.get("ok") or x.get("status", "ok") != "ok"]

    try:
        deadline = time.monotonic() + 300
        while True:
            for name, p in procs:
                if p.poll() is not None:
                    raise AssertionError(f"{name} died rc={p.returncode}:\n"
                                         f"{_dump(procs)}")
            try:
                r = req({"op": "fleet"}, timeout_s=2.0)
                # all four replicas up, not just one per part: the kill
                # lands on p0.r0's third data request, and on a loaded host
                # p0.r1 may still be starting then - no peer to fail over to
                h = req({"op": "health"}, timeout_s=2.0)["health"]
                if (r.get("ok") and not r.get("missing_parts")
                        and all(h.get(f"p{p}.r{k}") == "up"
                                for p in (0, 1) for k in (0, 1))):
                    break
            except Exception:
                pass
            assert time.monotonic() < deadline, f"fleet:\n{_dump(procs)}"
            time.sleep(0.5)

        nodes = [int(n) for n in np.flatnonzero(owner == 0)[:5]] + \
                [int(n) for n in np.flatnonzero(owner == 1)[:5]]
        bad = []
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:      # load until the kill lands
            bad += bad_rows(req({"op": "predict_many", "nodes": nodes}))
            h = req({"op": "health"}, timeout_s=5.0)
            if h["health"].get("p0.r0") in ("down", "quarantined"):
                break
            time.sleep(0.1)
        else:
            raise AssertionError(f"p0.r0 never marked down:\n{_dump(procs)}")
        for _ in range(2):                      # failover keeps serving
            bad += bad_rows(req({"op": "predict_many", "nodes": nodes}))
        assert bad == [], f"client saw bad answers through the kill: {bad}"
        assert victim.wait(timeout=60) == 1     # os._exit(1), no drain

        # a delta lands during the outage: the WAL queues it for the slot
        r = req({"op": "add_edges",
                 "edges": [[nodes[0], nodes[5]], [nodes[5], nodes[0]]]},
                timeout_s=120.0)
        assert r.get("ok"), r
        h = req({"op": "health"}, timeout_s=5.0)
        assert sum(h["wal_depth"].values()) > 0

        # relaunch: fresh incarnation, WAL replay, warm-up, back to 'up'
        backend(0, 0)
        deadline = time.monotonic() + 300
        while True:
            h = req({"op": "health"}, timeout_s=5.0)
            if h["health"].get("p0.r0") == "up":
                break
            assert time.monotonic() < deadline, \
                f"rejoin stuck {h['health']}:\n{_dump(procs)}"
            time.sleep(0.5)
        assert sum(h["wal_depth"].values()) == 0    # cursor drained
        stats = req({"op": "stats"}, timeout_s=60.0)
        assert stats["wal_replayed"] > 0
        assert h["availability"]["requests_failed"] == 0

        # rejoined replica is bitwise: flush the dirty frontier, then both
        # p0 replicas must answer identical tier-A scores directly
        assert req({"op": "flush"}, timeout_s=300.0)["ok"]
        p0 = req({"op": "fleet"})["parts"]["0"]
        assert len(p0) == 2
        for v in nodes[:5]:
            answers = [serve.request(e["port"],
                                     {"op": "predict", "node": v,
                                      "tier": "A"}, timeout_s=60.0)
                       for e in p0]
            assert all(a.get("ok") for a in answers), (v, answers)
            assert answers[0]["scores"] == answers[1]["scores"], f"node {v}"

        req({"op": "shutdown"}, timeout_s=30.0)
        assert router.wait(timeout=120) == 0, _dump(procs)
        for name, p in procs[1:]:
            if p is victim:
                continue
            assert p.wait(timeout=120) == 0, f"{name}:\n{_dump(procs)}"
    finally:
        for _, p in procs:
            if p.poll() is None:
                p.kill()
