"""Real multi-process SPMD: two jax.distributed processes (4 CPU devices
each) drive one 8-part mesh end-to-end — partial artifact loading,
process-local placement, seed broadcast, shared-PRNG BNS exchange across
hosts, and resume-broadcast. The reference's multi-node flow
(scripts/reddit_multi_node.sh) without a cluster (SURVEY §4: 'multi-node
without a cluster')."""

import os
import re
import socket
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _launch(rank, port, tmp, epochs, resume=False, mesh_eval=False,
            inductive=False, model="graphsage", spmm=None):
    env = os.environ.copy()
    env.update({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
        "PYTHONPATH": REPO,
    })
    cmd = [sys.executable, "-m", "bnsgcn_tpu.main",
           "--dataset", "sbm", "--n-partitions", "8", "--model", model,
           "--n-layers", "2", "--n-hidden", "16", "--n-epochs", str(epochs),
           "--log-every", "10", "--sampling-rate", "0.5", "--use-pp",
           "--fix-seed", "--skip-partition",
           "--n-nodes", "2", "--node-rank", str(rank), "--port", str(port),
           "--part-path", f"{tmp}/parts", "--ckpt-path", f"{tmp}/ckpt",
           "--results-path", f"{tmp}/res"]
    if spmm:
        cmd += ["--spmm", spmm]
    cmd.append("--eval-device" if mesh_eval else "--no-eval")
    if mesh_eval:
        cmd.append("mesh")
    if inductive:
        cmd.append("--inductive")
    if resume:
        cmd.append("--resume")
    return subprocess.Popen(cmd, env=env, stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True, cwd=REPO)


def test_two_process_training_and_resume(tmp_path):
    tmp = str(tmp_path)
    env = os.environ.copy()
    env.update({"JAX_PLATFORMS": "cpu",
                "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
                "PYTHONPATH": REPO})
    subprocess.run([sys.executable, "-m", "bnsgcn_tpu.partition_cli",
                    "--dataset", "sbm", "--n-partitions", "8", "--fix-seed",
                    "--part-path", f"{tmp}/parts"],
                   env=env, check=True, capture_output=True, cwd=REPO)

    port = _free_port()
    procs = [_launch(r, port, tmp, epochs=12) for r in (0, 1)]
    outs = [p.communicate(timeout=280)[0] for p in procs]
    assert all(p.returncode == 0 for p in procs), outs
    # identical losses on both ranks == shared-PRNG BNS + replicated params hold
    losses = [[ln for ln in o.splitlines() if "Loss" in ln][-1].split()[-1]
              for o in outs]
    assert losses[0] == losses[1], losses

    port = _free_port()
    procs = [_launch(r, port, tmp, epochs=20, resume=True) for r in (0, 1)]
    outs = [p.communicate(timeout=280)[0] for p in procs]
    assert all(p.returncode == 0 for p in procs), outs
    for o in outs:
        assert re.search(r"Resumed \(agreed via coordinator\) from \S+ at "
                         r"epoch 10", o), o[-2000:]
    losses2 = [[ln for ln in o.splitlines() if "Loss" in ln][-1].split()[-1]
               for o in outs]
    assert losses2[0] == losses2[1]
    assert float(losses2[0]) < float(losses[0])   # training continued
    # ELL ran multi-host (geometry from meta.json — no segment fallback)
    assert "falling back" not in outs[0]

    # mesh-distributed eval across both processes (collective test eval incl.)
    port = _free_port()
    procs = [_launch(r, port, tmp, epochs=12, mesh_eval=True) for r in (0, 1)]
    outs = [p.communicate(timeout=280)[0] for p in procs]
    assert all(p.returncode == 0 for p in procs), outs
    assert "Test Result" in outs[0]               # rank 0 reports
    assert "Validation Accuracy" not in outs[1]   # rank 1 stays silent


def test_two_process_gat_ell_attention(tmp_path):
    """Multi-host GAT rides the ELL attention path (gat_fwd + bwd geometry
    from meta.json — no segment fallback), trains with identical losses on
    both ranks, and custom-VJP backward runs under jax.distributed."""
    tmp = str(tmp_path)
    env = os.environ.copy()
    env.update({"JAX_PLATFORMS": "cpu",
                "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
                "PYTHONPATH": REPO})
    subprocess.run([sys.executable, "-m", "bnsgcn_tpu.partition_cli",
                    "--dataset", "sbm", "--n-partitions", "8", "--fix-seed",
                    "--part-path", f"{tmp}/parts"],
                   env=env, check=True, capture_output=True, cwd=REPO)
    port = _free_port()
    procs = [_launch(r, port, tmp, epochs=25, model="gat") for r in (0, 1)]
    outs = [p.communicate(timeout=280)[0] for p in procs]
    assert all(p.returncode == 0 for p in procs), outs
    losses = [[ln for ln in o.splitlines() if "Loss" in ln] for o in outs]
    assert losses[0] and losses[0][-1].split()[-1] == losses[1][-1].split()[-1]
    first = float(losses[0][0].split()[-1])
    last = float(losses[0][-1].split()[-1])
    assert last < first, (first, last)
    assert "falling back" not in outs[0]          # ELL attention ran


def test_two_process_hybrid_spmm(tmp_path):
    """Multi-host --spmm hybrid: each process tiles its LOCAL parts and the
    stack/residual shapes agree via the host allgather — identical losses,
    no ell fallback."""
    tmp = str(tmp_path)
    env = os.environ.copy()
    env.update({"JAX_PLATFORMS": "cpu",
                "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
                "PYTHONPATH": REPO})
    subprocess.run([sys.executable, "-m", "bnsgcn_tpu.partition_cli",
                    "--dataset", "sbm", "--n-partitions", "8", "--fix-seed",
                    "--part-path", f"{tmp}/parts"],
                   env=env, check=True, capture_output=True, cwd=REPO)
    port = _free_port()
    procs = [_launch(r, port, tmp, epochs=25, spmm="hybrid") for r in (0, 1)]
    outs = [p.communicate(timeout=280)[0] for p in procs]
    assert all(p.returncode == 0 for p in procs), outs
    losses = [[ln for ln in o.splitlines() if "Loss" in ln] for o in outs]
    assert losses[0] and losses[0][-1].split()[-1] == losses[1][-1].split()[-1]
    assert float(losses[0][-1].split()[-1]) < float(losses[0][0].split()[-1])
    assert "falling back" not in outs[0]


def test_two_process_inductive_mesh_eval(tmp_path):
    """Inductive multi-host mesh eval: rank 0 partitions the eval subgraphs
    behind a barrier; all ranks join the collective val/test evals."""
    tmp = str(tmp_path)
    env = os.environ.copy()
    env.update({"JAX_PLATFORMS": "cpu",
                "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
                "PYTHONPATH": REPO})
    subprocess.run([sys.executable, "-m", "bnsgcn_tpu.partition_cli",
                    "--dataset", "sbm", "--n-partitions", "8", "--fix-seed",
                    "--inductive", "--part-path", f"{tmp}/parts"],
                   env=env, check=True, capture_output=True, cwd=REPO)
    port = _free_port()
    procs = [_launch(r, port, tmp, epochs=12, mesh_eval=True, inductive=True)
             for r in (0, 1)]
    outs = [p.communicate(timeout=280)[0] for p in procs]
    assert all(p.returncode == 0 for p in procs), outs
    assert "Test Result" in outs[0]
    assert "Accuracy" in outs[0]
