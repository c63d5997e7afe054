"""CLI launcher (reference main.py).

Differences by design: the reference forks one process per partition and
rendezvous over gloo/MPI (main.py:35-62); under SPMD a single process drives
every local device, and multi-host pods use `jax.distributed.initialize`
(--n-nodes > 1) instead of mpirun re-exec.

  python -m bnsgcn_tpu.main --dataset reddit --n-partitions 8 \
      --model graphsage --n-layers 4 --n-hidden 256 --sampling-rate 0.1 \
      --use-pp --inductive

Subcommands: `python -m bnsgcn_tpu.main serve ...` starts the online
inference server (serve.py) against a trained checkpoint — two-tier node
prediction with delta ingestion; exits 75 on a graceful SIGTERM drain.
"""

from __future__ import annotations

import os
import random
import sys
import time

from bnsgcn_tpu import resilience
from bnsgcn_tpu.config import Config, ConfigError, parse_config
from bnsgcn_tpu.parallel import coord
from bnsgcn_tpu.run import prepare_partition, run_training
from bnsgcn_tpu.utils.platform import place_compile_cache


def main(argv=None):
    argv = sys.argv[1:] if argv is None else list(argv)
    place_compile_cache()
    if argv and argv[0] == "serve":
        # online inference serving rides the same flag vocabulary but a
        # different lifecycle (long-running server, drain-on-SIGTERM) —
        # dispatch before the training config/seed handling below
        from bnsgcn_tpu import serve
        return serve.serve_main(argv[1:])
    if argv and argv[0] == "serve-router":
        # partition-sharded serving, router half: fronts one backend fleet
        # (per-part shards x replicas), owns routing + delta fan-out;
        # imports no model code until the CLI body runs
        from bnsgcn_tpu import serve_router
        return serve_router.router_main(argv[1:])
    if argv and argv[0] == "serve-backend":
        # partition-sharded serving, backend half: one process per
        # (part, replica) owning that shard's table/CSR/delta state
        from bnsgcn_tpu import serve_backend
        return serve_backend.backend_main(argv[1:])
    if argv and argv[0] == "continual":
        # continual training on an evolving graph: consume the serving
        # delta journal, fold it into the partition artifacts
        # incrementally, warm-start a fine-tune, promote the refreshed
        # checkpoint back to serving (exit 2 on config errors, like serve)
        from bnsgcn_tpu import continual
        sys.exit(continual.continual_main(argv[1:]))
    cfg = parse_config(argv)
    if not cfg.fix_seed:
        # reference randomizes the seed unless --fix-seed (main.py:13-16)
        cfg = cfg.replace(seed=random.randrange(1 << 31))
    if not cfg.graph_name:
        cfg = cfg.replace(graph_name=cfg.derive_graph_name())

    if cfg.n_nodes > 1:
        import jax
        from jax.experimental import multihost_utils
        jax.distributed.initialize(
            coordinator_address=f"{cfg.master_addr}:{cfg.port}",
            num_processes=cfg.n_nodes, process_id=cfg.node_rank)
        # every process must share the (possibly randomized) seed: the
        # zero-communication BNS sampling and the replicated param init both
        # depend on it being identical everywhere
        import numpy as np
        seed = multihost_utils.broadcast_one_to_all(np.int64(cfg.seed))
        cfg = cfg.replace(seed=int(seed))

    # coordination rank 0 only (cfg.coord_rank > 0 is a harness-mode peer
    # process sharing the partition dir — two builders would race); real
    # multi-host keeps the node_rank gate + barrier below. The peer-skip
    # is only safe because run_training's coordinator barrier exists — with
    # coordination disabled there is NO cross-process sync at all, so that
    # combination must be a named config error, not a silent race.
    if (cfg.coord_world and cfg.coord_world > 1 and not cfg.skip_partition
            and (cfg.resilience != "on" or cfg.coord == "off")):
        print("--coord-world > 1 with coordination disabled (--coord off / "
              "--resilience off) has no cross-process partition barrier: "
              "pre-partition with partition_cli and pass --skip-partition",
              file=sys.stderr)
        sys.exit(2)
    if not cfg.skip_partition and cfg.node_rank == 0 and cfg.coord_rank <= 0:
        t0 = time.time()
        prepare_partition(cfg, load=False)
        print(f"partition ready in {time.time() - t0:.1f}s -> {cfg.part_path}")

    if cfg.n_nodes > 1:
        from jax.experimental import multihost_utils
        # barrier: ranks != 0 must not read artifacts before rank 0 finishes
        # writing them (part_path must be on a shared filesystem, or use
        # partition_cli + --skip-partition to pre-distribute — README.md:116)
        multihost_utils.sync_global_devices("bnsgcn_partition_ready")

    # resilience exit-code contract (README "Fault tolerance"): preemption
    # and divergence map to DISTINCT nonzero codes so a requeue wrapper can
    # tell "relaunch with --resume" (75) from "needs human triage" (76);
    # the hung-step watchdog exits 77 from inside resilience.py itself.
    try:
        res = run_training(cfg)
    except ConfigError as ex:
        # a named configuration error (e.g. replicas x parts x feat exceeds
        # the device budget): deterministic argument problem — exit 2 like
        # argparse, so a requeue wrapper never relaunches it
        print(f"[config] {ex}", file=sys.stderr)
        sys.exit(2)
    except resilience.RankLostExit as ex:
        # --inject ranklost@E<e>:r<rank> fired on THIS rank: the process
        # vanishes mid-run so the survivors' heartbeat liveness (not a
        # goodbye message) must detect the loss — exactly what a real
        # preempted host looks like. Exit 0: the harness asserts the
        # SURVIVORS' resize, not this rank's demise.
        print(f"[resilience] injected rank loss at epoch {ex.epoch}: "
              f"exiting without goodbye (survivors must detect via "
              f"liveness and RESIZE)")
        sys.stdout.flush()
        sys.stderr.flush()
        os._exit(0)
    except resilience.PreemptedError as ex:
        print(f"[resilience] {ex}")
        sys.stdout.flush()
        sys.stderr.flush()
        # os._exit, not sys.exit: concurrent.futures joins non-daemon eval
        # workers at interpreter shutdown, and a minutes-long in-flight host
        # eval would overrun the preemption grace window — the platform's
        # SIGKILL would then replace exit 75 with 137 and break the requeue
        # wrapper's resume contract. The resumable checkpoint is already
        # fsync'd; nothing else needs a clean unwind.
        os._exit(resilience.EXIT_PREEMPTED)
    except resilience.DivergenceError as ex:
        print(f"[resilience] {ex}", file=sys.stderr)
        sys.exit(resilience.EXIT_DIVERGED)
    except coord.CoordTimeout as ex:
        # a peer (or the rank-0 server) stopped answering: the coordinator
        # already printed the peer-liveness table naming the stalled rank.
        # Same exit code as the hung-step watchdog — to a requeue wrapper
        # both mean "the job hung; stderr says where".
        print(f"[coord] {ex}", file=sys.stderr)
        sys.stdout.flush()
        sys.stderr.flush()
        os._exit(resilience.EXIT_WATCHDOG)
    except coord.CoordAbort as ex:
        # the ranks AGREED to abort (e.g. a peer cannot load the chosen
        # checkpoint): distinct code — triage, not a blind requeue
        print(f"[coord] {ex}", file=sys.stderr)
        sys.stdout.flush()
        sys.stderr.flush()
        os._exit(resilience.EXIT_COORD_ABORT)
    # machine-parseable summary for harnesses (fault-matrix e2e compares a
    # resumed run's final loss against an uninterrupted one through this)
    print("RESULT final_loss=%.9e best_val=%.6f test=%.6f"
          % (res.final_loss, res.best_val_acc, res.test_acc))
    return res


if __name__ == "__main__":
    main()
