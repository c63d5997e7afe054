"""Multi-host coordinated resilience through the real CLI: a genuine
2-process fault matrix on the CPU container.

jaxlib's CPU client refuses cross-process XLA collectives here (the 4
test_multihost.py env-skips), but the rank coordinator needs none: with
`--coord-rank/--coord-world` each process runs the full single-host trainer
(same seed => bit-identical replicated state, the property a real pod's
replicated loss/params give for free) coupled only through the out-of-band
coordinator — so every multi-host recovery path PR 4 could only exercise
single-host runs here as real processes with real exit codes:

* partial SIGTERM (one rank) -> BOTH ranks agree, checkpoint, exit 75, and
  `--resume` reproduces the uninterrupted final loss bit-for-bit;
* NaN on one rank -> coordinated rollback: both ranks restore the same
  checkpoint epoch with the same retry nonce, final losses bitwise equal
  each other AND the single-host rollback of the same fault;
* a hung rank -> the healthy rank's coordinator exchange times out, dumps
  peer liveness naming the straggler, and exits 77;
* a torn local checkpoint copy at resume -> the coordinator ack aborts ALL
  ranks loudly (exit 78) instead of desyncing the epoch schedule.

tools/fault_matrix.sh runs the same stages from the shell.
"""

import os
import re
import shutil
import socket
import subprocess
import sys

import pytest

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


def _cmd(tmp_path, ckpt, extra_args=()):
    return ([sys.executable, "-m", "bnsgcn_tpu.main"] + BASE_ARGS
            + ["--part-path", str(tmp_path / "parts"),
               "--ckpt-path", str(ckpt),
               "--results-path", str(tmp_path / "res")]
            + list(extra_args))


def _run_single(tmp_path, ckpt, extra_args=(), timeout=240):
    """One uncoordinated (--coord off) single-host run — the reference."""
    return subprocess.run(
        _cmd(tmp_path, ckpt, ["--coord", "off"] + list(extra_args)),
        capture_output=True, text=True, timeout=timeout, cwd=REPO,
        env=_env())


def _run_pair(tmp_path, ckpts, extra_args=(), rank_env=None, timeout=240):
    """Two coordinated rank processes; returns the CompletedProcess-likes
    [(rc, out), (rc, out)]. `ckpts` is one shared path or a per-rank pair;
    `rank_env` an optional {rank: {env}} overlay."""
    if isinstance(ckpts, (str, os.PathLike)):
        ckpts = (ckpts, ckpts)
    port = _free_port()
    procs = []
    for r in (0, 1):
        cmd = _cmd(tmp_path, ckpts[r],
                   ["--coord", "tcp", "--coord-port", str(port),
                    "--coord-world", "2", "--coord-rank", str(r)]
                   + list(extra_args))
        procs.append(subprocess.Popen(
            cmd, stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            cwd=REPO, env=_env((rank_env or {}).get(r))))
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=timeout)
            outs.append((p.returncode, out))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.communicate()
    return outs


def _final_loss(out: str) -> str:
    m = re.search(r"RESULT final_loss=(\S+)", out)
    assert m, f"no RESULT line in output:\n{out[-2000:]}"
    return m.group(1)       # string compare == bitwise pin


@pytest.mark.quickgate
def test_partial_sigterm_agreed_exit75_and_bitwise_resume(tmp_path):
    """The acceptance pin: SIGTERM injected on rank 1 ONLY -> the agreed
    verdict turns it into a clean all-rank resumable exit 75, and the
    resumed pair reproduces the uninterrupted run's final loss bit-for-bit
    on both ranks (the resumed seed also survives a conflicting --seed)."""
    _prepartition(tmp_path)
    ref = _run_single(tmp_path, tmp_path / "ck_ref")
    assert ref.returncode == 0, ref.stdout[-2000:]
    want = _final_loss(ref.stdout)

    outs = _run_pair(tmp_path, tmp_path / "ck",
                     ["--inject", "sigterm@E3:r1"])
    assert [rc for rc, _ in outs] == [75, 75], outs
    for _, out in outs:
        assert "agreed preemption (requested by rank(s) [1])" in out, out[-2000:]
        assert "resumable checkpoint" in out

    outs = _run_pair(tmp_path, tmp_path / "ck", ["--resume", "--seed", "999"])
    assert [rc for rc, _ in outs] == [0, 0], outs
    for _, out in outs:
        assert "Resumed (agreed via coordinator)" in out, out[-2000:]
        assert _final_loss(out) == want


def test_coordinated_nan_rollback_same_epoch_same_nonce(tmp_path):
    """NaN poisoned on rank 0 only: the agreed verdict rolls BOTH ranks back
    to the same checkpoint epoch with the same retry nonce, and the healed
    pair's final loss is bitwise equal the single-host rollback of the same
    fault — coordination changes who decides, never the numbers."""
    _prepartition(tmp_path)
    single = _run_single(tmp_path, tmp_path / "ck_one", ["--inject", "nan@E5"])
    assert single.returncode == 0, single.stdout[-2000:]
    assert "rolled back to" in single.stdout
    want = _final_loss(single.stdout)

    outs = _run_pair(tmp_path, tmp_path / "ck", ["--inject", "nan@E5:r0"])
    assert [rc for rc, _ in outs] == [0, 0], outs
    assert ("agreed rollback to" in outs[0][1]
            and "restarting all ranks at epoch 4 with retry-nonce 1"
            in outs[0][1]), outs[0][1][-2000:]
    assert ("agreed rollback (decided by rank 0): epoch 5 -> restart 4"
            in outs[1][1] and "retry-nonce 1" in outs[1][1]), outs[1][1][-2000:]
    assert _final_loss(outs[0][1]) == _final_loss(outs[1][1]) == want


def test_coordinator_timeout_exits_77_with_peer_liveness(tmp_path):
    """Rank 1 hangs mid-step: rank 0's verdict exchange must time out
    within the bounded deadline, dump the peer-liveness table naming the
    rank that stalled (one epoch behind), and exit 77; the hung rank's own
    watchdog also exits 77 — no process is ever left hanging forever."""
    _prepartition(tmp_path)
    outs = _run_pair(
        tmp_path, tmp_path / "ck", ["--inject", "hang@E3:r1"],
        rank_env={
            # rank 0 is healthy: only its coordinator deadline may fire
            0: {"BNSGCN_COORD_TIMEOUT_S": "6",
                "BNSGCN_WATCHDOG_MIN_S": "120",
                "BNSGCN_WATCHDOG_GRACE_S": "120"},
            # rank 1 is the hung one: its in-process watchdog fires
            1: {"BNSGCN_COORD_TIMEOUT_S": "6",
                "BNSGCN_WATCHDOG_MIN_S": "2", "BNSGCN_WATCHDOG_FACTOR": "2",
                "BNSGCN_WATCHDOG_GRACE_S": "120"},
        }, timeout=300)
    assert [rc for rc, _ in outs] == [77, 77], outs
    r0 = outs[0][1]
    assert "timed out" in r0 and "rank 1's epoch-3 verdict" in r0, r0[-2000:]
    assert "peer liveness" in r0 and "rank 1: step hb" in r0
    assert "(epoch 2)" in r0            # the straggler is one epoch behind
    assert "[watchdog] step hung" in outs[1][1]


def test_torn_local_checkpoint_copy_aborts_resume_on_all_ranks(tmp_path):
    """Rank-consistent recovery (satellite bugfix): rank 0 broadcasts its
    checkpoint CHOICE and every rank must ack loading it. Rank 1's local
    copy of the chosen file is torn -> the resume aborts loudly on BOTH
    ranks (exit 78) naming the rank and the file, instead of rank 1
    silently walking to an older epoch or failing mid-epoch."""
    _prepartition(tmp_path)
    outs = _run_pair(tmp_path, tmp_path / "ck", ["--inject", "sigterm@E5"])
    assert [rc for rc, _ in outs] == [75, 75], outs

    # rank 1 gets its own (rsync'd-local-disk style) copy, newest file torn
    shutil.copytree(tmp_path / "ck", tmp_path / "ck_r1")
    from bnsgcn_tpu.resilience import corrupt_file
    newest = max((tmp_path / "ck_r1").glob("*_5.ckpt"))
    corrupt_file(str(newest))

    outs = _run_pair(tmp_path, (tmp_path / "ck", tmp_path / "ck_r1"),
                     ["--resume"])
    assert [rc for rc, _ in outs] == [78, 78], outs
    for _, out in outs:
        assert "resume aborted by agreement" in out, out[-2000:]
        assert "rank 1:" in out and "_5.ckpt" in out
