"""Closed-loop communication auto-tuner (`--tune {off,schedule,auto}`).

  * schedule grammar: parse/merge/sort, every malformed entry a named
    ConfigError; mode validation (auto is single-process only, a schedule
    text without --tune schedule is an error, not silently ignored);
  * decide() on synthetic metric streams: the staleness anneal fires only
    after a full window + AUTO_HOLD consecutive flat verdicts, every move
    starts an AUTO_COOLDOWN dwell, the ladder is MONOTONE (never loosens),
    and the strategy/codec moves are one-shot — the controller cannot
    flip-flop by construction;
  * Tuner recovery: decisions are sticky — rewind() reverts the levers to
    the restart point but keeps the history, on_epoch_end() replays it by
    epoch, restore() reconstructs a schedule (pure function of the epoch)
    or adopts the checkpointed auto history;
  * the CLI path: `--tune off` is bitwise-pinned to the no-flag run, a
    scheduled run emits a tune_decision per applied move with a clean
    --strict-exec audit (each retune re-arms the compile allowance), and a
    faulted run replays the SAME schedule after rollback — bitwise
    deterministic across two identical injected runs.

No reference equivalent: BNS-GCN freezes every comm lever at launch; the
epoch-boundary feedback loop is a capability upgrade built on the obs bus.
"""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from bnsgcn_tpu.config import Config, ConfigError
from bnsgcn_tpu import tune
from bnsgcn_tpu.tune import (AUTO_COOLDOWN, AUTO_HOLD, AUTO_WINDOW,
                             STALENESS_LADDER, AutoState, Tuner,
                             _ladder_pos, bench_schedule, decide,
                             parse_schedule, startup_changes, validate_mode)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ----------------------------------------------------------------------------
# schedule grammar
# ----------------------------------------------------------------------------

@pytest.mark.quickgate
def test_parse_schedule_grammar_merge_and_sort():
    """Entries parse through the lever aliases, same-epoch entries merge
    into one fold, and the result is epoch-sorted regardless of input
    order."""
    sched = parse_schedule("K=1@60, wire=bf16@30 ,K=4@0,K=2@30,mode=grad-only@0")
    assert [ep for ep, _ in sched] == [0, 30, 60]
    by = dict(sched)
    assert by[0] == {"halo_refresh": 4, "halo_mode": "grad-only"}
    assert by[30] == {"halo_wire": "bf16", "halo_refresh": 2}
    assert by[60] == {"halo_refresh": 1}
    # lowercase k aliases the same lever; empty text parses to nothing
    assert parse_schedule("k=2@5") == [(5, {"halo_refresh": 2})]
    assert parse_schedule("") == [] and parse_schedule("  , ,") == []
    # strategy alias maps to halo_exchange with a CONCRETE strategy
    assert parse_schedule("strategy=ragged@3") == [(3, {"halo_exchange":
                                                        "ragged"})]


@pytest.mark.quickgate
def test_parse_schedule_rejects_malformed_entries():
    for bad, why in (
            ("K=4", "missing @epoch"),
            ("K@4", "missing =value"),
            ("K=4@x", "non-integer epoch"),
            ("warp=9@0", "unknown lever"),
            ("K=fast@0", "non-integer K"),
            ("K=0@0", "K < 1"),
            ("K=4@-1", "negative epoch"),
            ("mode=sometimes@0", "bad mode value"),
            ("strategy=auto@0", "schedule must pick a CONCRETE strategy"),
            ("wire=int4@0", "unknown codec"),
            ("K=4@2,k=2@2", "same lever twice at one epoch"),
    ):
        with pytest.raises(ConfigError):
            parse_schedule(bad), why


@pytest.mark.quickgate
def test_validate_mode():
    validate_mode(Config(tune="off"))
    validate_mode(Config(tune="schedule", tune_schedule="K=2@3"))
    validate_mode(Config(tune="auto"))
    # a schedule text under any other mode is an error, never silently dropped
    with pytest.raises(ConfigError, match="only read under"):
        validate_mode(Config(tune="off", tune_schedule="K=2@3"))
    with pytest.raises(ConfigError, match="needs a --tune-schedule"):
        validate_mode(Config(tune="schedule"))
    with pytest.raises(ConfigError, match="off/schedule/auto"):
        validate_mode(Config(tune="always"))
    # rank-local timings would desync retuned programs across ranks
    with pytest.raises(ConfigError, match="single-process"):
        validate_mode(Config(tune="auto"), multi_host=True)
    with pytest.raises(ConfigError, match="single-process"):
        validate_mode(Config(tune="auto"), coordinated=True)
    # the declarative schedule is rank-symmetric: allowed everywhere
    validate_mode(Config(tune="schedule", tune_schedule="K=2@3"),
                  multi_host=True, coordinated=True)


@pytest.mark.quickgate
def test_startup_changes():
    # schedule: only the epoch-0 entries that actually differ fold in
    ch, why = startup_changes(Config(tune="schedule",
                                     tune_schedule="K=4@0,K=1@9"))
    assert ch == {"halo_refresh": 4} and why == "schedule@0"
    ch, _ = startup_changes(Config(tune="schedule", halo_refresh=4,
                                   tune_schedule="K=4@0,K=1@9"))
    assert ch == {}
    # auto coarsens a fine exchange launch point to the K=4 rung...
    ch, why = startup_changes(Config(tune="auto"))
    assert ch == {"halo_refresh": 4} and "coarse" in why
    # ...but never loosens a launch point already at/above that rung
    assert startup_changes(Config(tune="auto", halo_refresh=8))[0] == {}
    assert startup_changes(Config(tune="auto",
                                  halo_mode="grad-only"))[0] == {}
    assert startup_changes(Config(tune="off")) == ({}, "")


@pytest.mark.quickgate
def test_bench_schedule_is_a_monotone_anneal():
    for n in (3, 8, 12, 100):
        sched = bench_schedule(n)
        eps = [ep for ep, _ in sched]
        ks = [ch["halo_refresh"] for _, ch in sched]
        assert eps[0] == 0 and eps == sorted(set(eps)), (n, sched)
        assert ks == [4, 2, 1], (n, sched)


# ----------------------------------------------------------------------------
# decide(): the pure feedback policy on synthetic streams
# ----------------------------------------------------------------------------

def _feed(st, losses, comm_frac=0.0):
    for lo in losses:
        st.observe({"loss": lo, "step_s": 1.0,
                    "comm_s": comm_frac if comm_frac else None})


@pytest.mark.quickgate
def test_decide_needs_full_window_then_hold_then_moves():
    """A flat loss stream: no verdict until the window fills, no move until
    the flat verdict holds AUTO_HOLD consecutive epochs, then exactly one
    ladder tightening (K=4 -> K=2) that clears the window and starts a
    cooldown dwell."""
    st, levers = AutoState(), {"halo_mode": "exchange", "halo_refresh": 4,
                               "halo_exchange": "padded",
                               "halo_wire": "native"}
    moved = None
    for i in range(AUTO_WINDOW + AUTO_HOLD):
        st.observe({"loss": 1.0})       # perfectly flat
        out = decide(st, levers)
        if out is not None:
            moved = (i, out)
            break
    assert moved is not None, "flat stream never tightened the staleness"
    i, (changes, reason, trigger) = moved
    # window must be full AND the verdict held AUTO_HOLD times first
    assert i == AUTO_WINDOW + AUTO_HOLD - 1 - 1, i  # 0-indexed epoch count
    assert changes == {"halo_refresh": 2} and "tighten" in reason
    assert "rel_improvement" in trigger and "threshold" in trigger
    assert st.cooldown == AUTO_COOLDOWN and st.losses == []
    # the dwell: nothing fires for AUTO_COOLDOWN epochs even though the
    # stream stays flat
    levers["halo_refresh"] = 2
    for _ in range(AUTO_COOLDOWN):
        st.observe({"loss": 1.0})
        assert decide(st, levers) is None


@pytest.mark.quickgate
def test_decide_improving_loss_never_tightens():
    st, levers = AutoState(), {"halo_mode": "exchange", "halo_refresh": 4}
    loss = 10.0
    for _ in range(30):
        st.observe({"loss": loss})
        loss *= 0.90                    # 10%/epoch: far above every rtol
        assert decide(st, levers) is None
    assert st.flat == 0


@pytest.mark.quickgate
def test_decide_ladder_is_monotone_and_single_lever():
    """Drive a long mixed stream (flat bursts separated by improving
    bursts) through the whole ladder from grad-only: the ladder position
    NEVER decreases, each decision moves at most the staleness pair, and
    once K=1 is reached no staleness move ever fires again — the
    no-flip-flop proof on a synthetic stream."""
    st = AutoState()
    levers = {"halo_mode": "grad-only", "halo_refresh": 1,
              "halo_exchange": "padded", "halo_wire": "bf16"}
    positions = [_ladder_pos(levers)]
    stream = ([1.0] * 12 + [0.5, 0.4, 0.3, 0.25] + [0.25] * 12
              + [0.12, 0.1] + [0.1] * 12 + [0.1] * 20)
    for lo in stream:
        st.observe({"loss": lo})
        out = decide(st, levers)
        if out is not None:
            changes, _, _ = out
            assert set(changes) <= {"halo_mode", "halo_refresh"}, changes
            levers.update(changes)
        positions.append(_ladder_pos(levers))
    assert positions == sorted(positions), "ladder loosened mid-run"
    assert _ladder_pos(levers) == len(STALENESS_LADDER) - 1, levers
    assert levers["halo_mode"] == "exchange" and levers["halo_refresh"] == 1
    # bottom rung: a permanently flat stream produces no further move
    for _ in range(20):
        st.observe({"loss": 0.1})
        assert decide(st, levers) is None


@pytest.mark.quickgate
def test_decide_comm_share_strategy_then_wire_one_shot():
    """At the bottom of the ladder with a high measured comm share: the
    strategy re-pick fires first (when retune_strategy found a cheaper
    one), then after the dwell the codec anneal native->bf16, then NOTHING
    — both moves are one-shot, no matter how long the share stays high."""
    st = AutoState()
    levers = {"halo_mode": "exchange", "halo_refresh": 1,
              "halo_exchange": "padded", "halo_wire": "native"}
    alt = ("shift", "shift beats padded on bytes at this skew")
    fired = []
    for _ in range(40):
        st.observe({"loss": 0.1, "step_s": 1.0, "comm_s": 0.6})
        out = decide(st, levers, strategy_alt=alt)
        if out is not None:
            changes, reason, trigger = out
            fired.append(changes)
            levers.update(changes)
            assert trigger["comm_frac"] == pytest.approx(0.6)
    assert fired == [{"halo_exchange": "shift"}, {"halo_wire": "bf16"}]
    assert st.strategy_moved and st.wire_moved
    # below the share threshold nothing ever fires
    st2 = AutoState()
    for _ in range(20):
        st2.observe({"loss": 0.1, "step_s": 1.0, "comm_s": 0.1})
        assert decide(st2, levers, strategy_alt=alt) is None


@pytest.mark.quickgate
def test_decide_no_strategy_alt_goes_straight_to_wire():
    st = AutoState()
    levers = {"halo_mode": "exchange", "halo_refresh": 1,
              "halo_exchange": "ragged", "halo_wire": "native"}
    fired = []
    for _ in range(20):
        st.observe({"loss": 0.1, "step_s": 1.0, "comm_s": 0.5})
        out = decide(st, levers)    # launch strategy already wins on bytes
        if out is not None:
            fired.append(out[0])
            levers.update(out[0])
    # bf16 is the ONLY codec move auto takes by itself; fp8/int8 stay opt-in
    assert fired == [{"halo_wire": "bf16"}]


# ----------------------------------------------------------------------------
# Tuner: sticky history, rewind/replay, restore
# ----------------------------------------------------------------------------

_LEVERS0 = {"halo_refresh": 4, "halo_mode": "exchange",
            "halo_exchange": "padded", "halo_wire": "native"}


def _sched_tuner(text="K=4@0,K=2@3,K=1@6", levers=None):
    cfg = Config(tune="schedule", tune_schedule=text)
    return Tuner(cfg, levers=dict(levers or _LEVERS0), log=lambda *a: None)


@pytest.mark.quickgate
def test_tuner_schedule_decides_at_boundaries():
    """on_epoch_end(e) returns the decision taking effect at e+1; entries
    equal to the applied levers fold to nothing."""
    t = _sched_tuner()
    t.record_startup({"halo_refresh": 4}, "schedule@0")
    decisions = {}
    for e in range(8):
        d = t.on_epoch_end(e, {"loss": 1.0})
        if d is not None:
            decisions[e] = d
    assert sorted(decisions) == [2, 5]
    assert decisions[2]["epoch"] == 3 and \
        decisions[2]["changes"] == {"halo_refresh": 2}
    assert decisions[5]["epoch"] == 6 and \
        decisions[5]["changes"] == {"halo_refresh": 1}
    assert decisions[2]["reason"] == "schedule"
    assert t.levers["halo_refresh"] == 1 and t.max_seen == 8


@pytest.mark.quickgate
def test_tuner_rewind_keeps_history_and_replays():
    """Rollback to epoch 4: the levers revert to the epoch-4 fold (K=2) but
    the epoch-6 decision stays recorded, and the healed run REPLAYS it at
    the same boundary instead of re-deriving anything."""
    t = _sched_tuner()
    t.record_startup({"halo_refresh": 4}, "schedule@0")
    for e in range(8):
        t.on_epoch_end(e, {"loss": 1.0})
    assert t.levers["halo_refresh"] == 1
    diff = t.rewind(4)
    assert diff == {"halo_refresh": 2}          # back to the epoch-4 levers
    assert t.levers["halo_refresh"] == 2
    assert len(t.history) == 3                  # startup + 2 moves, all kept
    replayed = {}
    for e in range(4, 8):
        d = t.on_epoch_end(e, {"loss": 9.9})    # post-rollback metrics differ
        if d is not None:
            replayed[e] = d
    assert sorted(replayed) == [5]
    assert replayed[5]["reason"] == "replay" and \
        replayed[5]["changes"] == {"halo_refresh": 1}
    assert t.levers["halo_refresh"] == 1
    # rewinding to a point where nothing differs returns None (no actuation)
    t2 = _sched_tuner()
    t2.record_startup({"halo_refresh": 4}, "schedule@0")
    assert t2.rewind(0) is None


@pytest.mark.quickgate
def test_tuner_restore_reconstructs_schedule():
    """A resumed process builds a FRESH Tuner with the launch levers, then
    restore(start_epoch) reconstructs the history a schedule implies (pure
    function of the epoch) and returns the diff to actuate before the first
    resumed step."""
    t = _sched_tuner()                  # resumed run built with K=4 levers
    t.record_startup({"halo_refresh": 4}, "schedule@0")
    diff = t.restore(5, None)           # schedule says K=2 since epoch 3
    assert diff == {"halo_refresh": 2}
    assert t.max_seen == 5 and t.levers["halo_refresh"] == 2
    # the remaining entry still fires as a FRESH schedule decision
    d = t.on_epoch_end(5, {"loss": 1.0})
    assert d["epoch"] == 6 and d["changes"] == {"halo_refresh": 1} and \
        d["reason"] == "schedule"
    # resume before any non-zero entry: nothing to actuate
    t2 = _sched_tuner()
    t2.record_startup({"halo_refresh": 4}, "schedule@0")
    assert t2.restore(2, None) is None


@pytest.mark.quickgate
def test_tuner_auto_state_dict_roundtrip():
    """auto persists its sticky history through extra['tune']; the resumed
    Tuner adopts it, actuates the fold diff, and REPLAYS the recorded
    decisions instead of re-deriving them from (different) resumed
    metrics."""
    cfg = Config(tune="auto", halo_refresh=4)
    t = Tuner(cfg, levers=dict(_LEVERS0), log=lambda *a: None)
    t.record_startup({"halo_refresh": 4}, "auto-start")
    fired = {}
    for e in range(16):
        d = t.on_epoch_end(e, {"loss": 1.0})    # flat: anneal walks the ladder
        if d is not None:
            fired[d["epoch"]] = d
    assert fired, "flat stream produced no auto decision"
    first_ep = min(fired)
    state = t.state_dict()
    assert state["mode"] == "auto" and len(state["history"]) == 1 + len(fired)
    # simulate the checkpoint JSON round-trip
    state = json.loads(json.dumps(state))
    resumed = Tuner(cfg, levers=dict(_LEVERS0), log=lambda *a: None)
    resumed.record_startup({"halo_refresh": 4}, "auto-start")
    diff = resumed.restore(first_ep, state)
    assert diff == fired[first_ep]["changes"]
    assert resumed.max_seen == t.max_seen
    # every later recorded decision REPLAYS at its boundary, fresh metrics
    # notwithstanding
    replayed = {}
    for e in range(first_ep, t.max_seen):
        d = resumed.on_epoch_end(e, {"loss": 123.0})
        if d is not None:
            replayed[d["epoch"]] = d
    later = {ep: f for ep, f in fired.items() if ep > first_ep}
    assert sorted(replayed) == sorted(later)
    for ep, f in later.items():
        assert replayed[ep]["reason"] == "replay" and \
            replayed[ep]["changes"] == f["changes"]
    assert resumed.levers == t.levers
    # a mode-mismatched checkpoint state is warned about and ignored
    msgs = []
    other = Tuner(cfg, levers=dict(_LEVERS0), log=msgs.append)
    other.restore(2, {"mode": "schedule", "max_seen": 9,
                      "history": [{"epoch": 3, "changes":
                                   {"halo_refresh": 2}, "reason": "schedule",
                                   "trigger": {}}]})
    assert other.history == []
    assert any("ignoring" in m for m in msgs), msgs


# ----------------------------------------------------------------------------
# e2e through the CLI: bitwise pin, events + strict audit, fault replay
# ----------------------------------------------------------------------------

BASE_ARGS = [
    "--dataset", "sbm", "--partition-method", "random", "--n-partitions", "2",
    "--model", "graphsage", "--n-layers", "2", "--n-hidden", "8",
    "--sampling-rate", "0.5", "--use-pp", "--n-epochs", "8",
    "--log-every", "2", "--no-eval", "--no-comm-trace",
    "--fix-seed", "--seed", "11",
]


def _env(extra=None):
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               BNSGCN_RETRY_BACKOFF_S="0", PYTHONPATH=REPO)
    env.update(extra or {})
    return env


def _run(tmp_path, extra_args=(), timeout=240):
    cmd = ([sys.executable, "-m", "bnsgcn_tpu.main"] + BASE_ARGS
           + ["--part-path", str(tmp_path / "parts"),
              "--ckpt-path", str(tmp_path / "ckpt"),
              "--results-path", str(tmp_path / "res")]
           + list(extra_args))
    return subprocess.run(cmd, capture_output=True, text=True,
                          timeout=timeout, cwd=REPO, env=_env())


def _final_loss(stdout: str) -> float:
    m = re.search(r"RESULT final_loss=(\S+)", stdout)
    assert m, f"no RESULT line in output:\n{stdout[-2000:]}"
    return float(m.group(1))


def _load_events(path):
    from bnsgcn_tpu.obs import load_events
    return load_events(path)


def _tune_trail(path):
    """(epoch, sorted changes, reason) per tune_decision — the applied
    schedule a run walked."""
    return [(e["epoch"], tuple(sorted(e["changes"].items())), e["reason"])
            for e in _load_events(path) if e["kind"] == "tune_decision"]


@pytest.mark.quickgate
def test_cli_tune_off_is_bitwise_pinned(tmp_path):
    """`--tune off` (the default) must be bitwise identical to a run that
    never heard of the flag: same final loss, no controller artifacts."""
    base = _run(tmp_path / "a")
    assert base.returncode == 0, base.stdout + base.stderr
    off = _run(tmp_path / "b", ["--tune", "off"])
    assert off.returncode == 0, off.stdout + off.stderr
    assert _final_loss(base.stdout) == _final_loss(off.stdout)
    assert "[tune]" not in off.stdout


@pytest.mark.quickgate
def test_cli_tune_off_rejects_schedule_text(tmp_path):
    r = _run(tmp_path, ["--tune", "off", "--tune-schedule", "K=2@3"])
    assert r.returncode == 2, (r.returncode, r.stdout, r.stderr)
    assert "only read under --tune schedule" in (r.stdout + r.stderr)


@pytest.mark.quickgate
def test_cli_schedule_events_and_strict_audit(tmp_path):
    """A declarative anneal under --strict-exec: the epoch-0 fold plus both
    mid-run retunes each land a tune_decision event, every retune replays a
    logged full-refresh (reason retune), the strict audit stays CLEAN with
    one re-arm per retune, and the report tool renders the applied
    schedule."""
    log = str(tmp_path / "obs.jsonl")
    r = _run(tmp_path, ["--n-epochs", "10", "--tune", "schedule",
                        "--tune-schedule", "K=4@0,K=2@4,K=1@7",
                        "--strict-exec", "--obs-log", log])
    assert r.returncode == 0, r.stdout + r.stderr
    assert "[tune] schedule@0" in r.stdout
    assert re.search(r"\[tune\] epoch 4: schedule -> halo_refresh=2",
                     r.stdout), r.stdout[-4000:]
    assert re.search(r"\[tune\] epoch 7: schedule -> halo_refresh=1",
                     r.stdout), r.stdout[-4000:]
    evs = _load_events(log)
    hdr = next(e for e in evs if e["kind"] == "run_header")
    assert hdr["config"]["tune"] == "schedule"
    assert "K=2@4" in hdr["config"]["tune_schedule"]
    td = [e for e in evs if e["kind"] == "tune_decision"]
    assert [e["epoch"] for e in td] == [0, 4, 7], td
    assert [e["reason"] for e in td] == ["schedule@0", "schedule",
                                         "schedule"], td
    assert td[1]["changes"] == {"halo_refresh": 2}
    assert td[2]["changes"] == {"halo_refresh": 1}
    # the K=4->2 retune invalidates the PR-10 halo cache (a logged full
    # refresh); the K=1 retune DROPS the cache machinery — the plain step
    # has nothing to refresh, so exactly one retune refresh appears
    ref = [e["reason"] for e in evs if e["kind"] == "halo_refresh"]
    assert ref.count("retune") == 1, ref
    # strict-exec: the retune recompiles are SANCTIONED (re-armed), audit
    # line reports them and zero violations
    m = re.search(r"(\d+) retune re-arm\(s\), (\d+) violation\(s\)",
                  r.stdout)
    assert m, r.stdout[-4000:]
    assert (int(m.group(1)), int(m.group(2))) == (2, 0)
    # the report tool renders the applied schedule as a table
    rep = subprocess.run([sys.executable, "tools/obs_report.py", log],
                         capture_output=True, text=True, timeout=60,
                         cwd=REPO, env=_env())
    assert rep.returncode == 0, rep.stdout + rep.stderr
    assert "tune schedule (3 applied decision(s))" in rep.stdout
    # --compare against itself surfaces the retune NOTE (schedule effects,
    # not noise)
    cmp_ = subprocess.run([sys.executable, "tools/obs_report.py",
                           "--compare", log, log],
                          capture_output=True, text=True, timeout=60,
                          cwd=REPO, env=_env())
    assert cmp_.returncode == 0, cmp_.stdout + cmp_.stderr
    assert "retuned the comm stack mid-run" in cmp_.stdout


@pytest.mark.quickgate
def test_cli_rollback_replays_schedule_deterministically(tmp_path):
    """nan@E5 one epoch after a scheduled retune (K=2@5): the rollback
    rewinds the levers to the restart point (a tune_decision with reason
    rollback), the healed run REPLAYS the recorded K=2 move at the same
    boundary (reason replay), and two identical injected runs land
    bitwise-equal final losses with identical applied-schedule trails."""
    losses, trails = [], []
    for i in (0, 1):
        log = str(tmp_path / f"obs{i}.jsonl")
        r = _run(tmp_path, ["--tune", "schedule",
                            "--tune-schedule", "K=4@0,K=2@5",
                            "--inject", "nan@E5",
                            "--ckpt-path", str(tmp_path / f"ck{i}"),
                            "--obs-log", log])
        assert r.returncode == 0, r.stdout + r.stderr
        kinds = [e["kind"] for e in _load_events(log)]
        assert "rollback" in kinds
        trail = _tune_trail(log)
        reasons = [t[2] for t in trail]
        assert "rollback" in reasons and "replay" in reasons, trail
        # the replayed move re-applies exactly the recorded change
        rep = next(t for t in trail if t[2] == "replay")
        assert rep == (5, (("halo_refresh", 2),), "replay"), trail
        losses.append(_final_loss(r.stdout))
        trails.append(trail)
    assert losses[0] == losses[1], losses
    assert trails[0] == trails[1], trails


@pytest.mark.slow
def test_cli_resume_continues_the_schedule(tmp_path):
    """sigterm@E3 under a 3-stage schedule, then --resume twice from copies
    of the same checkpoint: restore() reconstructs the schedule state, the
    remaining entries still fire at their epochs, and the two resumed runs
    land bitwise-identical final losses."""
    interrupted = _run(tmp_path, ["--n-epochs", "10", "--tune", "schedule",
                                  "--tune-schedule", "K=4@0,K=2@2,K=1@7",
                                  "--inject", "sigterm@E3"])
    assert interrupted.returncode == 75, (
        interrupted.returncode, interrupted.stderr[-2000:])
    losses = []
    for i in (0, 1):
        ck = str(tmp_path / f"ck_resume{i}")
        shutil.copytree(str(tmp_path / "ckpt"), ck)
        log = str(tmp_path / f"obs_resume{i}.jsonl")
        r = _run(tmp_path, ["--n-epochs", "10", "--tune", "schedule",
                            "--tune-schedule", "K=4@0,K=2@2,K=1@7",
                            "--resume", "--skip-partition",
                            "--ckpt-path", ck, "--obs-log", log])
        assert r.returncode == 0, r.stdout + r.stderr
        assert "Resumed from" in r.stdout
        trail = _tune_trail(log)
        # the K=2@2 entry predates the resume point: actuated as a resume
        # diff; the K=1@7 entry fires fresh at its boundary
        assert any(t[2] == "resume" and ("halo_refresh", 2) in t[1]
                   for t in trail), trail
        assert any(t[0] == 7 and ("halo_refresh", 1) in t[1]
                   for t in trail), trail
        losses.append(_final_loss(r.stdout))
    assert losses[0] == losses[1], losses
