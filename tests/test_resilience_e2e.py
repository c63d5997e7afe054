"""Fault-injection e2e through the real CLI: exit codes are the contract.

A requeue wrapper only ever sees the process exit status, so these tests drive
`python -m bnsgcn_tpu.main` in a subprocess and assert the resilience exit
codes directly: 75 preempted-resumable, 77 hung-step watchdog. The
sigterm-then-resume pair additionally pins bit-for-bit continuation: the
resumed run's RESULT final_loss equals the uninterrupted run's.

tools/fault_matrix.sh runs the same matrix from the shell for manual/CI use.
"""

import os
import re
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

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
               BNSGCN_RETRY_BACKOFF_S="0")
    env.update(extra or {})
    return env


def _run(tmp_path, extra_args=(), extra_env=None, timeout=240):
    cmd = ([sys.executable, "-m", "bnsgcn_tpu.main"] + BASE_ARGS
           + ["--part-path", str(tmp_path / "parts"),
              "--ckpt-path", str(tmp_path / "ckpt"),
              "--results-path", str(tmp_path / "res")]
           + list(extra_args))
    return subprocess.run(cmd, capture_output=True, text=True,
                          timeout=timeout, cwd=REPO, env=_env(extra_env))


def _final_loss(stdout: str) -> float:
    m = re.search(r"RESULT final_loss=(\S+)", stdout)
    assert m, f"no RESULT line in output:\n{stdout[-2000:]}"
    return float(m.group(1))


@pytest.mark.quickgate
def test_sigterm_preempts_resumable_then_resume_reaches_same_loss(tmp_path):
    """The acceptance pin: sigterm@E3 exits EXIT_PREEMPTED with a resumable
    checkpoint, and `--resume` reaches the same final loss as an
    uninterrupted run of the same seed."""
    full = _run(tmp_path)
    assert full.returncode == 0, full.stderr[-2000:]
    want = _final_loss(full.stdout)

    interrupted = _run(tmp_path, ["--inject", "sigterm@E3",
                                  "--ckpt-path", str(tmp_path / "ckpt_b")])
    assert interrupted.returncode == 75, (
        interrupted.returncode, interrupted.stderr[-2000:])
    assert "resumable checkpoint" in (interrupted.stdout + interrupted.stderr)

    # resume with a DIFFERENT seed flag: the checkpoint's saved seed must win
    resumed = _run(tmp_path, ["--resume", "--seed", "999", "--skip-partition",
                              "--ckpt-path", str(tmp_path / "ckpt_b")])
    assert resumed.returncode == 0, resumed.stderr[-2000:]
    assert "Resumed from" in resumed.stdout
    got = _final_loss(resumed.stdout)
    np.testing.assert_allclose(got, want, rtol=1e-6)


def test_hang_injection_trips_watchdog_with_stack_dump(tmp_path):
    """hang@E3 blocks the step; the in-process watchdog (deadline shrunk via
    env) must dump all-thread stacks + live-array state and exit 77."""
    r = _run(tmp_path, ["--inject", "hang@E3"],
             extra_env={"BNSGCN_WATCHDOG_MIN_S": "1.5",
                        "BNSGCN_WATCHDOG_FACTOR": "2",
                        "BNSGCN_WATCHDOG_GRACE_S": "120"},
             timeout=300)
    assert r.returncode == 77, (r.returncode, r.stderr[-2000:])
    assert "[watchdog] step hung" in r.stderr
    assert "Current thread" in r.stderr or "Thread 0x" in r.stderr
    assert "live arrays" in r.stderr


def test_resume_walks_past_zero_byte_latest_checkpoint(tmp_path):
    """Truncate the newest checkpoint after a preemption: --resume must fall
    back to the previous periodic file instead of crashing, losing only the
    epochs in between."""
    interrupted = _run(tmp_path, ["--inject", "sigterm@E5"])
    assert interrupted.returncode == 75, interrupted.stderr[-2000:]
    ckpt_dir = str(tmp_path / "ckpt")
    cks = sorted(os.listdir(ckpt_dir), key=lambda f: int(f.split("_")[-1][:-5]))
    open(os.path.join(ckpt_dir, cks[-1]), "wb").close()    # zero-byte newest
    resumed = _run(tmp_path, ["--resume", "--skip-partition"])
    assert resumed.returncode == 0, resumed.stderr[-2000:]
    assert "skipping corrupt checkpoint" in resumed.stdout
    assert re.search(r"Resumed from .*_3\.ckpt", resumed.stdout), (
        resumed.stdout[-2000:])
