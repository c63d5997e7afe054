#!/bin/bash
# Deterministic fault-injection matrix through the real CLI — the shell
# twin of tests/test_resilience_e2e.py + tests/test_coord_e2e.py, runnable
# on any host (CPU mesh by default) or on hardware before a long window:
# each of the four --inject kinds must recover via its designed path / exit
# code, a sigterm-interrupted + resumed run must reach the uninterrupted
# run's final loss, and the MULTI-HOST stages drive two real coordinated
# rank processes (--coord tcp, no XLA collectives needed) through partial
# SIGTERM, coordinated NaN rollback, and the elastic RESIZE round trip
# (rank loss -> shrink to W=1 -> relaunch -> grow back to W=2).
#
#   JAX_PLATFORMS=cpu tools/fault_matrix.sh [workdir]
#
# Exit-code contract (bnsgcn_tpu/resilience.py, README "Fault tolerance"):
#   75  preempted, resumable checkpoint written (relaunch with --resume)
#   76  divergence unrecovered after --resil-retries rollbacks
#   77  hung step / coordinator exchange timeout (peer liveness on stderr)
#   78  coordinated abort: a rank cannot load the agreed checkpoint
set -u
cd "$(dirname "$0")/.."
WORK=${1:-$(mktemp -d /tmp/bnsgcn_faults.XXXXXX)}
mkdir -p "$WORK"
export JAX_PLATFORMS=${JAX_PLATFORMS:-cpu}
export BNSGCN_RETRY_BACKOFF_S=0
# the 2-part mesh needs 2 devices; force a virtual CPU mesh unless the
# caller already forces one (or runs on real hardware)
if [ "$JAX_PLATFORMS" = cpu ] && \
   ! printf '%s' "${XLA_FLAGS:-}" | grep -q host_platform_device_count; then
  export XLA_FLAGS="${XLA_FLAGS:-} --xla_force_host_platform_device_count=2"
fi

BASE="--dataset sbm --partition-method random --n-partitions 2 \
  --model graphsage --n-layers 2 --n-hidden 8 --sampling-rate 0.5 --use-pp \
  --n-epochs 8 --log-every 2 --no-eval --no-comm-trace --fix-seed --seed 11 \
  --part-path $WORK/parts --results-path $WORK/res"

FAIL=0
check() {  # check <name> <want_rc> <got_rc>
  if [ "$3" -eq "$2" ]; then
    echo "PASS  $1 (exit $3)"
  else
    echo "FAIL  $1: want exit $2, got $3 (log: $WORK/$1.log)"
    FAIL=1
  fi
}

# every stage also leaves an obs telemetry log (bnsgcn_tpu/obs.py) and must
# have recorded the MATCHING lifecycle event — the machine-readable twin of
# the stderr lines the greps below pin
check_event() {  # check_event <stage> <obs_log> <kind>
  if grep -q "\"kind\": \"$3\"" "$2" 2>/dev/null; then
    echo "PASS  $1 obs event '$3'"
  else
    echo "FAIL  $1: no '$3' event in obs log $2"
    FAIL=1
  fi
}

echo "== graftlint: the repo must be static-analysis clean =="
# hazards the matrix exercises at runtime (deadlock-prone collectives,
# exit-code drift, unguarded shared state) are exactly what the lint
# proves absent from the source first; a dirty tree fails the matrix
# before any training run spends time. This runs all three tiers —
# AST, IR, and the protocol model checker (gate 3), whose enumerated
# crash/delay schedules subsume the single interleaving each matrix
# cell below happens to hit.
bash tools/lint.sh -q > "$WORK/lint.log" 2>&1
check lint 0 $?

echo "== uninterrupted reference run =="
python -m bnsgcn_tpu.main $BASE --ckpt-path "$WORK/ck_ref" \
  --obs-log "$WORK/obs_ref.jsonl" > "$WORK/ref.log" 2>&1
check ref 0 $?
REF_LOSS=$(grep -o 'RESULT final_loss=[^ ]*' "$WORK/ref.log" | cut -d= -f2)
check_event ref "$WORK/obs_ref.jsonl" run_header
check_event ref "$WORK/obs_ref.jsonl" epoch
check_event ref "$WORK/obs_ref.jsonl" run_end

echo "== nan@E5: divergence rollback, run completes =="
python -m bnsgcn_tpu.main $BASE --ckpt-path "$WORK/ck_nan" \
  --obs-log "$WORK/obs_nan.jsonl" --inject nan@E5 > "$WORK/nan.log" 2>&1
check nan 0 $?
grep -q 'rolled back to' "$WORK/nan.log" \
  || { echo "FAIL  nan: no rollback line"; FAIL=1; }
check_event nan "$WORK/obs_nan.jsonl" rollback

echo "== nan@E5 under --halo-refresh 4: rollback invalidates the halo cache =="
# the rollback restores a checkpoint saved WITHOUT the cache, so recovery
# must replay a full-refresh epoch (reason=rollback in the obs log) — a
# stale cache surviving the rollback would silently corrupt the replay
python -m bnsgcn_tpu.main $BASE --halo-refresh 4 --ckpt-path "$WORK/ck_k4" \
  --obs-log "$WORK/obs_k4.jsonl" --inject nan@E5 > "$WORK/nan_k4.log" 2>&1
check nan_k4 0 $?
grep -q 'rolled back to' "$WORK/nan_k4.log" \
  || { echo "FAIL  nan_k4: no rollback line"; FAIL=1; }
grep -q 'full refresh at epoch 4 (rollback)' "$WORK/nan_k4.log" \
  || { echo "FAIL  nan_k4: no cache-invalidation full-refresh line"; FAIL=1; }
check_event nan_k4 "$WORK/obs_k4.jsonl" rollback
check_event nan_k4 "$WORK/obs_k4.jsonl" halo_refresh
K4_LOSS=$(grep -o 'RESULT final_loss=[^ ]*' "$WORK/nan_k4.log" | cut -d= -f2)

echo "== sigterm@E3: resumable exit 75, then --resume matches ref =="
python -m bnsgcn_tpu.main $BASE --ckpt-path "$WORK/ck_sig" \
  --obs-log "$WORK/obs_sig.jsonl" --inject sigterm@E3 \
  > "$WORK/sigterm.log" 2>&1
check sigterm 75 $?
check_event sigterm "$WORK/obs_sig.jsonl" preempt
python -m bnsgcn_tpu.main $BASE --ckpt-path "$WORK/ck_sig" \
  --resume --skip-partition --seed 999 > "$WORK/resume.log" 2>&1
check resume 0 $?
RES_LOSS=$(grep -o 'RESULT final_loss=[^ ]*' "$WORK/resume.log" | cut -d= -f2)
if [ "$REF_LOSS" != "$RES_LOSS" ]; then
  echo "FAIL  resume: final loss $RES_LOSS != uninterrupted $REF_LOSS"
  FAIL=1
else
  echo "PASS  resume loss matches uninterrupted ($REF_LOSS)"
fi

echo "== ckpt-corrupt@E6 + nan@E6: fallback past the torn checkpoint =="
python -m bnsgcn_tpu.main $BASE --ckpt-path "$WORK/ck_cor" \
  --inject ckpt-corrupt@E6,nan@E6 > "$WORK/corrupt.log" 2>&1
check ckpt-corrupt 0 $?
grep -q 'skipping corrupt checkpoint' "$WORK/corrupt.log" \
  || { echo "FAIL  ckpt-corrupt: chain walk not logged"; FAIL=1; }

echo "== hang@E3: watchdog stack dump + exit 77 =="
BNSGCN_WATCHDOG_MIN_S=1.5 BNSGCN_WATCHDOG_FACTOR=2 \
  BNSGCN_WATCHDOG_GRACE_S=120 \
  python -m bnsgcn_tpu.main $BASE --ckpt-path "$WORK/ck_hang" \
  --obs-log "$WORK/obs_hang.jsonl" --inject hang@E3 > "$WORK/hang.log" 2>&1
check hang 77 $?
grep -q 'watchdog' "$WORK/hang.log" \
  || { echo "FAIL  hang: no watchdog dump"; FAIL=1; }
check_event hang "$WORK/obs_hang.jsonl" watchdog_fire
grep -q 'post-mortem dump' "$WORK/hang.log" \
  || { echo "FAIL  hang: no post-mortem dump path on stderr"; FAIL=1; }

# ---- multi-host stages: two real coordinated rank processes. The
# coordinator is XLA-free, so these run on the CPU container where jaxlib
# refuses multiprocess collectives; each process is a full single-host
# trainer (same broadcast seed => bit-identical state) coupled only through
# the --coord tcp channel. ----
COORD_PORT=${COORD_PORT:-19119}
run_pair() {  # run_pair <tag> <ckpt0> <ckpt1> [extra args...]
  local tag=$1 ck0=$2 ck1=$3; shift 3
  python -m bnsgcn_tpu.main $BASE --skip-partition --ckpt-path "$ck0" \
    --coord tcp --coord-port "$COORD_PORT" --coord-world 2 --coord-rank 0 \
    "$@" > "$WORK/${tag}_r0.log" 2>&1 &
  local P0=$!
  python -m bnsgcn_tpu.main $BASE --skip-partition --ckpt-path "$ck1" \
    --coord tcp --coord-port "$COORD_PORT" --coord-world 2 --coord-rank 1 \
    "$@" > "$WORK/${tag}_r1.log" 2>&1 &
  local P1=$!
  wait $P0; RC0=$?
  wait $P1; RC1=$?
  COORD_PORT=$((COORD_PORT + 2))
}

echo "== multi-host: sigterm@E3 on rank 1 only -> agreed exit 75 on both =="
run_pair mh_sig "$WORK/ck_mh" "$WORK/ck_mh" --inject sigterm@E3:r1
check mh_sig_r0 75 $RC0
check mh_sig_r1 75 $RC1
grep -q 'agreed preemption' "$WORK/mh_sig_r0.log" \
  || { echo "FAIL  mh_sig: no agreed-preemption line"; FAIL=1; }

echo "== multi-host: --resume both ranks matches the uninterrupted loss =="
run_pair mh_res "$WORK/ck_mh" "$WORK/ck_mh" --resume --seed 999
check mh_res_r0 0 $RC0
check mh_res_r1 0 $RC1
for r in 0 1; do
  MH_LOSS=$(grep -o 'RESULT final_loss=[^ ]*' "$WORK/mh_res_r$r.log" | cut -d= -f2)
  if [ "$REF_LOSS" != "$MH_LOSS" ]; then
    echo "FAIL  mh_res_r$r: final loss $MH_LOSS != uninterrupted $REF_LOSS"
    FAIL=1
  else
    echo "PASS  mh_res_r$r loss matches uninterrupted ($MH_LOSS)"
  fi
done

echo "== multi-host: nan@E5 on rank 0 -> coordinated rollback, same nonce =="
run_pair mh_nan "$WORK/ck_mhn" "$WORK/ck_mhn" --inject nan@E5:r0 \
  --obs-log "$WORK/obs_mh_nan.jsonl"
check mh_nan_r0 0 $RC0
check mh_nan_r1 0 $RC1
check_event mh_nan "$WORK/obs_mh_nan.jsonl" epoch_ranks
check_event mh_nan "$WORK/obs_mh_nan.jsonl" rollback
check_event mh_nan_r1 "$WORK/obs_mh_nan.jsonl.r1" rollback
grep -q 'agreed rollback to' "$WORK/mh_nan_r0.log" \
  || { echo "FAIL  mh_nan: rank 0 did not decide a rollback"; FAIL=1; }
grep -q 'agreed rollback (decided by rank 0)' "$WORK/mh_nan_r1.log" \
  || { echo "FAIL  mh_nan: rank 1 did not apply the agreed rollback"; FAIL=1; }
L0=$(grep -o 'RESULT final_loss=[^ ]*' "$WORK/mh_nan_r0.log" | cut -d= -f2)
L1=$(grep -o 'RESULT final_loss=[^ ]*' "$WORK/mh_nan_r1.log" | cut -d= -f2)
if [ -z "$L0" ] || [ "$L0" != "$L1" ]; then
  echo "FAIL  mh_nan: rank losses diverged ('$L0' vs '$L1')"; FAIL=1
else
  echo "PASS  mh_nan ranks agree on the healed loss ($L0)"
fi

echo "== multi-host: nan@E5:r0 under --halo-refresh 4 matches single-host =="
# coordinated rollback with an ACTIVE halo cache on both ranks: both must
# invalidate, replay the full-refresh epoch, and land bitwise on the
# single-host K=4 healed loss (the recovery path is rank-consistent AND
# cache-state-free)
run_pair mh_k4 "$WORK/ck_mhk4" "$WORK/ck_mhk4" --halo-refresh 4 \
  --inject nan@E5:r0 --obs-log "$WORK/obs_mh_k4.jsonl"
check mh_k4_r0 0 $RC0
check mh_k4_r1 0 $RC1
check_event mh_k4 "$WORK/obs_mh_k4.jsonl" halo_refresh
check_event mh_k4 "$WORK/obs_mh_k4.jsonl" rollback
L0=$(grep -o 'RESULT final_loss=[^ ]*' "$WORK/mh_k4_r0.log" | cut -d= -f2)
L1=$(grep -o 'RESULT final_loss=[^ ]*' "$WORK/mh_k4_r1.log" | cut -d= -f2)
if [ -z "$L0" ] || [ "$L0" != "$L1" ] || [ "$L0" != "$K4_LOSS" ]; then
  echo "FAIL  mh_k4: losses r0='$L0' r1='$L1' single-host='$K4_LOSS'"; FAIL=1
else
  echo "PASS  mh_k4 ranks match the single-host K=4 healed loss ($L0)"
fi

# ---- elastic stages: rank LOSS becomes a coordinated RESIZE instead of
# exit 77. Same harness pair; --elastic on, fast heartbeat-silence
# detection, and the coord window the e2e suite pins. ----
export BNSGCN_ELASTIC_DEAD_S=3
export BNSGCN_COORD_TIMEOUT_S=60

echo "== multi-host elastic: ranklost@E3:r1 -> survivor resizes to W=1 =="
run_pair mh_shrink "$WORK/ck_el" "$WORK/ck_el" --elastic on \
  --inject ranklost@E3:r1 --obs-log "$WORK/obs_mh_shrink.jsonl"
check mh_shrink_r0 0 $RC0
check mh_shrink_r1 0 $RC1
grep -q 'world resized to 1 (members \[0\], lost \[1\])' \
  "$WORK/mh_shrink_r0.log" \
  || { echo "FAIL  mh_shrink: survivor did not agree the shrink"; FAIL=1; }
grep -q 'RESULT final_loss=' "$WORK/mh_shrink_r0.log" \
  || { echo "FAIL  mh_shrink: survivor did not train to completion"; FAIL=1; }
check_event mh_shrink "$WORK/obs_mh_shrink.jsonl" resize
ls "$WORK"/ck_el/*.ckpt >/dev/null 2>&1 \
  || { echo "FAIL  mh_shrink: no checkpoint left behind"; FAIL=1; }

echo "== multi-host elastic: shrink, relaunch rank 1, grow back (2->1->2) =="
# the documented relaunch contract: the replacement comes up AFTER the
# shrink verdict, with the SAME CLI minus --inject. Epochs are throttled
# so the W=1 survivor is still training when the replacement finishes its
# JAX init; the healed loss must equal a shrink-only replay of the same
# fault (grow restores the newest checkpoint with NO new nonce).
EL_ARGS="--elastic on --n-epochs 24"
grow_rank() {  # grow_rank <rank> <log> [extra args...]
  local rank=$1 log=$2; shift 2
  BNSGCN_EPOCH_THROTTLE_S=1.0 python -m bnsgcn_tpu.main $BASE $EL_ARGS \
    --skip-partition --ckpt-path "$WORK/ck_grow" \
    --coord tcp --coord-port "$COORD_PORT" --coord-world 2 \
    --coord-rank "$rank" --obs-log "$WORK/obs_mh_grow.jsonl" \
    "$@" > "$WORK/$log.log" 2>&1 &
}
grow_rank 0 mh_grow_r0
G0=$!
grow_rank 1 mh_grow_r1 --inject ranklost@E3:r1
wait $!; check mh_grow_r1 0 $?
SEEN=1
for _ in $(seq 1 240); do
  grep -q 'world resized to 1' "$WORK/mh_grow_r0.log" && { SEEN=0; break; }
  sleep 0.5
done
[ $SEEN -eq 0 ] \
  || { echo "FAIL  mh_grow: no shrink verdict on the survivor"; FAIL=1; }
grow_rank 1 mh_grow_r1b
G1B=$!
wait $G0; check mh_grow_r0 0 $?
wait $G1B; check mh_grow_r1b 0 $?
COORD_PORT=$((COORD_PORT + 2))
grep -q 'world resized to 2' "$WORK/mh_grow_r0.log" \
  || { echo "FAIL  mh_grow: survivor never grew back to W=2"; FAIL=1; }
grep -q 'rejoined world 2' "$WORK/mh_grow_r1b.log" \
  || { echo "FAIL  mh_grow: replacement did not rejoin"; FAIL=1; }
grep -q '"trigger": "rejoin"' "$WORK/obs_mh_grow.jsonl" \
  || { echo "FAIL  mh_grow: no rejoin resize obs event"; FAIL=1; }
GROW_LOSS=$(grep -o 'RESULT final_loss=[^ ]*' "$WORK/mh_grow_r0.log" | cut -d= -f2)
R1B_LOSS=$(grep -o 'RESULT final_loss=[^ ]*' "$WORK/mh_grow_r1b.log" | cut -d= -f2)
if [ -z "$GROW_LOSS" ] || [ "$GROW_LOSS" != "$R1B_LOSS" ]; then
  echo "FAIL  mh_grow: joiner loss '$R1B_LOSS' != survivor '$GROW_LOSS'"
  FAIL=1
else
  echo "PASS  mh_grow joiner bitwise in step ($GROW_LOSS)"
fi
# deterministic replay: same fault, NO rejoin, throttle off — the healed
# trajectory must be independent of wall time and of when the rejoin came
run_pair mh_grow_rep "$WORK/ck_grow_rep" "$WORK/ck_grow_rep" $EL_ARGS \
  --inject ranklost@E3:r1
check mh_grow_rep_r0 0 $RC0
REP_LOSS=$(grep -o 'RESULT final_loss=[^ ]*' "$WORK/mh_grow_rep_r0.log" | cut -d= -f2)
if [ -z "$REP_LOSS" ] || [ "$REP_LOSS" != "$GROW_LOSS" ]; then
  echo "FAIL  mh_grow: replay loss '$REP_LOSS' != round-trip '$GROW_LOSS'"
  FAIL=1
else
  echo "PASS  mh_grow round-trip matches the shrink-only replay ($REP_LOSS)"
fi

# ---- serving-fleet stages: the self-healing router through the real CLI.
# One health-probing router (degraded=partial) over the 2-part shard map
# the training stages produced, part replicas = 2; backend p0.r0 is armed
# with `--inject servekill@3:p0.r0` and dies hard (os._exit, no drain) at
# its 3rd routed data-path request. The client load must see ZERO failed
# answers through the kill (read failover), a graph delta landing while
# the victim is gone must queue in the router's WAL, and the relaunched
# process — fresh incarnation token, same CLI minus --inject — must
# rejoin through WAL replay + the bitwise warm-up gate back to 'up'. ----
SPORT=$((COORD_PORT + 500))
SRV="--dataset sbm --partition-method random --n-partitions 2 \
  --model graphsage --n-layers 2 --n-hidden 8 --sampling-rate 0.5 --use-pp \
  --fix-seed --seed 11 --part-path $WORK/parts --results-path $WORK/res \
  --ckpt-path $WORK/ck_ref"
serve_backend() {  # serve_backend <part> <replica> <log> [extra...]
  local part=$1 rep=$2 log=$3; shift 3
  python -m bnsgcn_tpu.main serve-backend $SRV \
    --serve-part "$part" --serve-replica "$rep" \
    --serve-router "127.0.0.1:$SPORT" \
    --serve-dir "$WORK/sdir_p${part}r${rep}" \
    "$@" > "$WORK/$log.log" 2>&1 &
}

echo "== serve_kill: servekill@3:p0.r0 mid-load -> zero failed answers =="
python -m bnsgcn_tpu.main serve-router $SRV --serve-port "$SPORT" \
  --part-replicas 2 --serve-degraded partial --serve-probe-s 0.2 \
  --obs-log "$WORK/obs_serve.jsonl" > "$WORK/serve_router.log" 2>&1 &
SRV_ROUTER=$!
serve_backend 0 0 serve_p0r0 --inject servekill@3:p0.r0
SRV_P0R0=$!
serve_backend 0 1 serve_p0r1
SRV_P0R1=$!
serve_backend 1 0 serve_p1r0
SRV_P1R0=$!
serve_backend 1 1 serve_p1r1
SRV_P1R1=$!
python - "$SPORT" <<'PYEOF' > "$WORK/serve_kill.log" 2>&1
import json, sys, time
from bnsgcn_tpu import serve
port = int(sys.argv[1])
deadline = time.monotonic() + 300
while True:                                 # fleet complete = no missing parts
    try:
        r = serve.request(port, {"op": "fleet"}, timeout_s=2.0)
        if r.get("ok") and not r.get("missing_parts"):
            break
    except Exception:
        pass
    assert time.monotonic() < deadline, "fleet never came up"
    time.sleep(0.5)
nodes = list(range(10))

def bad_rows(resp):
    # a row is bad if it failed OR was answered degraded — with a live
    # replica of every part, neither is acceptable
    rows = resp["results"] if resp.get("ok") else [resp]
    return sum(1 for x in rows
               if not x.get("ok") or x.get("status", "ok") != "ok")

failed, rounds = 0, 0
deadline = time.monotonic() + 60
while time.monotonic() < deadline:          # load until the kill is detected
    rounds += 1
    failed += bad_rows(serve.request(
        port, {"op": "predict_many", "nodes": nodes}, timeout_s=60.0))
    h = serve.request(port, {"op": "health"}, timeout_s=5.0)
    if h["health"].get("p0.r0") in ("down", "quarantined"):
        break
    time.sleep(0.1)
else:
    raise AssertionError("router never marked p0.r0 down")
for _ in range(3):                          # post-kill: failover keeps serving
    failed += bad_rows(serve.request(
        port, {"op": "predict_many", "nodes": nodes}, timeout_s=60.0))
# a delta lands while the victim is gone: its slot's WAL must queue it
r = serve.request(port, {"op": "add_edges",
                         "edges": [[0, 1], [2, 3], [4, 5], [6, 7]]},
                  timeout_s=120.0)
assert r.get("ok"), r
h = serve.request(port, {"op": "health"}, timeout_s=5.0)
wal = sum(h["wal_depth"].values())
print(f"RESULT serve_kill rounds={rounds} failed={failed} "
      f"p0r0={h['health'].get('p0.r0')} wal_depth={wal}")
assert failed == 0, f"{failed} client answer(s) failed despite a live replica"
assert wal > 0, "no WAL entry queued for the dead replica"
PYEOF
check serve_kill 0 $?
wait $SRV_P0R0
check serve_kill_exit 1 $?      # the victim died hard, not a clean drain
grep -q '\[inject\] servekill at data-path request 3' "$WORK/serve_p0r0.log" \
  || { echo "FAIL  serve_kill: no injection line on the victim"; FAIL=1; }

echo "== serve_rejoin: relaunch p0.r0 -> WAL replay, warm-up, back to 'up' =="
serve_backend 0 0 serve_p0r0b
SRV_P0R0B=$!
python - "$SPORT" <<'PYEOF' > "$WORK/serve_rejoin.log" 2>&1
import json, sys, time
from bnsgcn_tpu import serve
port = int(sys.argv[1])
deadline = time.monotonic() + 300
while True:                                 # rejoin = p0.r0 re-admitted 'up'
    h = serve.request(port, {"op": "health"}, timeout_s=5.0)
    if h["health"].get("p0.r0") == "up":
        break
    assert time.monotonic() < deadline, f"p0.r0 stuck: {h['health']}"
    time.sleep(0.5)
assert sum(h["wal_depth"].values()) == 0, f"WAL not drained: {h['wal_depth']}"
stats = serve.request(port, {"op": "stats"}, timeout_s=60.0)
replayed = stats.get("wal_replayed", 0)
failed = sum(1 for x in serve.request(
    port, {"op": "predict_many", "nodes": list(range(10))},
    timeout_s=60.0)["results"]
    if not x.get("ok") or x.get("status", "ok") != "ok")
avail = h["availability"]
print(f"RESULT serve_rejoin wal_replayed={replayed} failed={failed} "
      f"availability={avail['availability']} failovers={avail['failovers']}")
assert replayed > 0, "rejoin admitted p0.r0 without replaying its WAL tail"
assert failed == 0
serve.request(port, {"op": "shutdown"}, timeout_s=30.0)
PYEOF
SRV_RC=$?
check serve_rejoin 0 $SRV_RC
if [ $SRV_RC -ne 0 ]; then
  # the client never reached the shutdown op: put the fleet down so the
  # waits below cannot hang the matrix
  kill $SRV_ROUTER $SRV_P0R0B $SRV_P0R1 $SRV_P1R0 $SRV_P1R1 2>/dev/null
fi
wait $SRV_ROUTER;  check serve_router 0 $?
wait $SRV_P0R0B;   check serve_p0r0b 0 $?
wait $SRV_P0R1;    check serve_p0r1 0 $?
wait $SRV_P1R0;    check serve_p1r0 0 $?
wait $SRV_P1R1;    check serve_p1r1 0 $?
grep -q 'replayed' "$WORK/serve_router.log" \
  || { echo "FAIL  serve_rejoin: no WAL replay line on the router"; FAIL=1; }

[ $FAIL -eq 0 ] && echo "fault matrix: ALL PASS ($WORK)" \
  || echo "fault matrix: FAILURES (logs in $WORK)"
exit $FAIL
