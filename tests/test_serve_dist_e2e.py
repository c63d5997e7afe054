"""Distributed serving e2e: a real router subprocess fronting two real
backend subprocesses (serve-router / serve-backend CLI entry points, real
TCP), proving the routed fleet serves BITWISE what the single-host server
serves — tier A, tier B with cross-part closures, and post-delta refresh —
then shuts the whole fleet down cleanly through one client op."""

import json
import os
import socket
import subprocess
import sys
import time

import jax
import numpy as np
import pytest

from bnsgcn_tpu import checkpoint as ckpt
from bnsgcn_tpu import serve
from bnsgcn_tpu.config import Config
from bnsgcn_tpu.models.gnn import init_params, spec_from_config

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("", 0))
        return s.getsockname()[1]


def _env():
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu")
    return env


def _setup_fleet_dirs(tmp_path):
    """One checkpoint + partition artifacts (random 2-way owner map over
    the deterministic sbm graph) + the flag set every process launches
    with. Returns (args, g, cfg2, params, state, owner)."""
    cfg = Config(dataset="sbm", model="graphsage", n_layers=2, n_hidden=8,
                 use_pp=True, seed=3, sampling_rate=1.0,
                 ckpt_path=str(tmp_path / "ckpt"),
                 part_path=str(tmp_path / "parts"))
    cfg = cfg.replace(graph_name=cfg.derive_graph_name())
    from bnsgcn_tpu.data.datasets import load_data
    g, _, _ = load_data(cfg)
    cfg2 = cfg.replace(n_feat=g.n_feat, n_class=g.n_class, n_train=g.n_train)
    params, state = init_params(jax.random.key(3), spec_from_config(cfg2))
    ckpt.save_checkpoint(ckpt.final_path(cfg2), params=params,
                         bn_state=state, epoch=7, best_acc=0.5, seed=3)
    # the serving shard map, in the training artifacts' own format
    rng = np.random.default_rng(11)
    owner = rng.integers(0, 2, size=g.n_nodes).astype(np.int32)
    owner[:2] = [0, 1]
    part_dir = os.path.join(cfg.part_path, cfg.graph_name)
    os.makedirs(part_dir, exist_ok=True)
    gnids = [np.flatnonzero(owner == p).astype(np.int64) for p in (0, 1)]
    with open(os.path.join(part_dir, "meta.json"), "w") as f:
        json.dump({"n_parts": 2, "n_inner": [len(x) for x in gnids]}, f)
    for p, ids in enumerate(gnids):
        np.savez(os.path.join(part_dir, f"part{p}.npz"), global_nid=ids)
    args = ["--dataset", "sbm", "--model", "graphsage", "--n-layers", "2",
            "--n-hidden", "8", "--use-pp", "--fix-seed", "--seed", "3",
            "--ckpt-path", str(tmp_path / "ckpt"),
            "--part-path", str(tmp_path / "parts")]
    return args, g, cfg2, params, state, owner


def _spawn(subcmd, args, extra):
    cmd = [sys.executable, "-m", "bnsgcn_tpu.main", subcmd] + args + extra
    return subprocess.Popen(cmd, env=_env(), cwd=REPO,
                            stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True)


def _dump(procs):
    out = []
    for name, p in procs:
        p.kill()
        try:
            out.append(f"--- {name} ---\n{p.stdout.read()[-3000:]}")
        except Exception:
            pass
    return "\n".join(out)


@pytest.mark.quickgate
def test_e2e_two_backend_fleet_bitwise_and_clean_shutdown(tmp_path):
    args, g, cfg2, params, state, owner = _setup_fleet_dirs(tmp_path)
    rport = _free_port()
    router = _spawn("serve-router", args, ["--serve-port", str(rport)])
    procs = [("router", router)]
    backends = []
    try:
        for part in (0, 1):
            # --serve-refresh-s 0: the background refresher otherwise races
            # the post-delta tier-B assertions (it can clean a dirty node in
            # the ~1s the in-process ref spends compiling between the two
            # predicts); this test drains via the explicit `flush` op instead
            b = _spawn("serve-backend", args,
                       ["--serve-part", str(part),
                        "--serve-router", f"127.0.0.1:{rport}",
                        "--serve-refresh-s", "0",
                        "--serve-dir", str(tmp_path / f"sdir{part}")])
            backends.append(b)
            procs.append((f"backend{part}", b))
        # fleet complete = router answers `fleet` with no missing parts
        deadline = time.monotonic() + 300
        while True:
            for name, p in procs:
                if p.poll() is not None:
                    raise AssertionError(f"{name} died rc={p.returncode}:\n"
                                         f"{_dump(procs)}")
            try:
                r = serve.request(rport, {"op": "fleet"}, timeout_s=2.0)
                if r.get("ok") and not r.get("missing_parts"):
                    break
            except Exception:
                pass
            if time.monotonic() > deadline:
                raise AssertionError(f"fleet never ready:\n{_dump(procs)}")
            time.sleep(0.5)

        # the single-host reference, in-process from the same checkpoint
        ref = serve.build_core(cfg2, g, params, state, log=lambda *a: None)
        try:
            probe = [0, 1, 17, 123, g.n_nodes - 1]
            for v in probe:
                r = serve.request(rport, {"op": "predict", "node": v})
                local = ref.predict(v)
                assert r["ok"] and r["tier"] == "A"
                assert r["scores"] == local["scores"], f"node {v}"
                assert r["part"] == owner[v]
            r = serve.request(rport, {"op": "predict_many", "nodes": probe})
            assert [x["scores"] for x in r["results"]] == \
                   [ref.predict(v)["scores"] for v in probe]

            # cross-part delta: apply fans to both owners, the mark BFS
            # crosses the cut, tier-B closures pull remote halo rows
            u = int(np.flatnonzero(owner == 0)[4])
            w = int(np.flatnonzero(owner == 1)[4])
            r = serve.request(rport, {"op": "add_edges",
                                      "edges": [[u, w], [w, u]]},
                              timeout_s=120.0)
            ref_r = ref.add_edges([[u, w], [w, u]])
            assert r["ok"] and r["dirty_total"] == ref_r["dirty_total"]
            for v in (u, w):
                r = serve.request(rport, {"op": "predict", "node": v},
                                  timeout_s=120.0)
                local = ref.predict(v)
                assert r["tier"] == local["tier"] == "B", f"node {v}"
                assert r["scores"] == local["scores"], f"node {v}"

            # post-delta refresh: drain the dirty frontier everywhere, then
            # tier A is bitwise again
            r = serve.request(rport, {"op": "flush"}, timeout_s=300.0)
            ref.flush()
            assert r["ok"]
            assert serve.request(rport, {"op": "dirty"})["count"] == 0
            for v in (u, w):
                r = serve.request(rport, {"op": "predict", "node": v})
                local = ref.predict(v)
                assert r["tier"] == local["tier"] == "A", f"node {v}"
                assert r["scores"] == local["scores"], f"node {v}"

            stats = serve.request(rport, {"op": "stats"})
            assert stats["router"] and len(stats["backends"]) == 2
            assert stats["deltas"] == 1 and stats["evictions"] == 0
        finally:
            ref.close()

        # one client op shuts the whole fleet down: router forwards the
        # shutdown, every backend drains + flushes its delta-log shard and
        # exits 0, then the router exits 0
        serve.request(rport, {"op": "shutdown"})
        assert router.wait(timeout=120) == 0, _dump(procs)
        for part, b in enumerate(backends):
            assert b.wait(timeout=120) == 0, _dump(procs)
            log = os.path.join(str(tmp_path / f"sdir{part}"),
                               f"delta_log.p{part}.r0.jsonl")
            assert os.path.exists(log)      # the journaled delta survived
            with open(log) as f:
                assert any(json.loads(ln)["op"] == "apply_delta"
                           for ln in f if ln.strip())
    finally:
        for _, p in procs:
            if p.poll() is None:
                p.kill()
