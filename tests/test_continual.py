"""Continual training on an evolving graph (continual.py, data/incremental.py,
serve.py promotion protocol).

What is pinned, per ISSUE/ROADMAP:
  (a) the incrementally-updated artifact is ARRAY-FOR-ARRAY bitwise a
      from-scratch build of the mutated graph at the same part assignment,
      and produces bitwise-identical eval logits through the partitioned
      forward across all three halo strategies x reorder on/off;
  (b) the staleness budget (staleness_decision) re-partitions exactly when
      edge-cut growth or imbalance crosses the configured thresholds;
  (c) --cycle-nonce refolds the BNS/dropout streams deterministically:
      same nonce -> bitwise-identical losses, different nonce -> different
      draws, nonce 0 -> bitwise the historical (pre-continual) run;
  (d) promotion rollback: a corrupted/stale promotion blob is rejected and
      the prior serving table/params stay live bitwise; the run_cycle
      accuracy gate keeps serving weights while the consumed cursor still
      advances (deltas are facts, only weights roll back);
  (e) quickgate e2e: train -> subprocess serve -> mutate via deltas ->
      `main continual --continual-source server` -> the promoted serving
      answers reflect the fine-tuned weights.
"""

import dataclasses
import json
import os
import subprocess
import sys
from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bnsgcn_tpu import checkpoint as ckpt
from bnsgcn_tpu import continual, serve
from bnsgcn_tpu.config import Config, ConfigError
from bnsgcn_tpu.data import incremental as inc
from bnsgcn_tpu.data.artifacts import PartitionArtifacts, build_artifacts
from bnsgcn_tpu.data.graph import sbm_graph
from bnsgcn_tpu.data.partitioner import (degree_norm_row, degree_tables,
                                         partition_graph,
                                         validate_artifact_dir)
from bnsgcn_tpu.data.reorder import apply_reorder, compute_orders
from bnsgcn_tpu.evaluate import full_graph_embeddings, gather_parts
from bnsgcn_tpu.models.gnn import ModelSpec, init_params, spec_from_config
from bnsgcn_tpu.parallel.mesh import make_parts_mesh
from bnsgcn_tpu.run import run_training
from bnsgcn_tpu.trainer import (build_block_arrays, build_step_fns,
                                place_blocks, place_replicated)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ----------------------------------------------------------------------------
# (a) incremental fold == from-scratch build at the pinned assignment
# ----------------------------------------------------------------------------

@lru_cache(maxsize=None)
def _evolved():
    """Base 4-part artifact, a delta batch touching a strict subset of the
    parts (own + cross-part edges + one feature row), the incremental fold,
    and the from-scratch rebuild of the mutated graph at the SAME part_of."""
    g = sbm_graph(n_nodes=240, n_class=3, n_feat=6, seed=1)
    pid = partition_graph(g, 4, seed=0)
    art = build_artifacts(g, pid)
    _, part_of, _ = inc._global_maps(art)
    by_part = {p: np.flatnonzero(part_of == p) for p in range(4)}
    edges = [
        # own-part edges inside part 0 and part 1
        [int(by_part[0][0]), int(by_part[0][3])],
        [int(by_part[1][2]), int(by_part[1][5])],
        # cross-part edges (grow the boundary/halo tables both directions)
        [int(by_part[0][1]), int(by_part[1][0])],
        [int(by_part[1][1]), int(by_part[0][2])],
        [int(by_part[0][4]), int(by_part[1][3])],
    ]
    entries = [{"op": "add_edges", "edges": edges[:2]},
               {"op": "update_feat", "node": int(by_part[0][0]),
                "feat": [0.5] * g.n_feat},
               {"op": "add_edges", "edges": edges[2:]}]
    batch = inc.delta_batch(entries)
    incr_art, info = inc.update_artifacts(art, batch)
    g2 = inc.apply_delta_batch(g, batch)
    scratch_art = build_artifacts(g2, part_of)
    return g2, art, incr_art, scratch_art, info


def test_incremental_artifact_bitwise_vs_scratch():
    g2, art, incr_art, scratch_art, info = _evolved()
    # the deltas deliberately touch only parts {0, 1}
    assert set(info["touched_edges"]) == {0, 1}
    for f in dataclasses.fields(PartitionArtifacts):
        a = getattr(incr_art, f.name)
        b = getattr(scratch_art, f.name)
        if isinstance(a, np.ndarray):
            assert a.dtype == b.dtype and a.shape == b.shape, f.name
            assert np.array_equal(a, b), f"field {f.name} diverged"
        elif f.name == "ell_geometry":
            assert (a is None) == (b is None)
            if a is not None:
                assert json.dumps(a, sort_keys=True, default=str) == \
                    json.dumps(b, sort_keys=True, default=str)
        else:
            assert a == b, f.name


def _part_logits(g, art, strategy: str, reorder: bool) -> np.ndarray:
    """Global-order forward logits through the real partitioned stack."""
    if reorder:
        art = apply_reorder(art, compute_orders(art, tile_r=32))
    cfg = Config(model="graphsage", dropout=0.0, use_pp=False, norm="layer",
                 n_train=g.n_train, sampling_rate=1.0, spmm="ell",
                 halo_exchange=strategy, n_partitions=4, n_feat=g.n_feat,
                 n_class=g.n_class,
                 reorder="cluster" if reorder else "off")
    spec = ModelSpec("graphsage", (g.n_feat, 16, g.n_class), norm="layer",
                     dropout=0.0, train_size=g.n_train)
    mesh = make_parts_mesh(4)
    fns, _, tables, _ = build_step_fns(cfg, spec, art, mesh)
    blk_np = build_block_arrays(art, "graphsage")
    blk_np.update(fns.extra_blk)
    for k in fns.drop_blk_keys:
        blk_np.pop(k, None)
    blk = place_blocks(blk_np, mesh)
    tb = place_replicated(tables, mesh)
    params, state = init_params(jax.random.key(5), spec)
    params = place_replicated(params, mesh)
    state = place_replicated(state, mesh)
    logits = fns.forward(params, state, jnp.uint32(0), blk, tb,
                         jax.random.key(0))
    return gather_parts(art, np.asarray(logits))


@pytest.mark.parametrize("reorder", [False, True], ids=["raw", "reorder"])
@pytest.mark.parametrize("strategy", ["padded", "shift", "ragged"])
def test_incremental_eval_logits_bitwise_pin(strategy, reorder):
    g2, _, incr_art, scratch_art, _ = _evolved()
    got = _part_logits(g2, incr_art, strategy, reorder)
    want = _part_logits(g2, scratch_art, strategy, reorder)
    assert np.array_equal(got, want), \
        f"eval logits diverged for halo={strategy} reorder={reorder}"


# ----------------------------------------------------------------------------
# (b) staleness budget thresholds + partitioner helpers
# ----------------------------------------------------------------------------

def test_staleness_decision_thresholds():
    base = {"cut": 100, "edges": [50, 50], "imbalance": 1.0}
    ok = {"cut": 120, "edges": [60, 60], "imbalance": 1.2}
    repart, why = inc.staleness_decision(ok, base, 1.5, 2.0)
    assert not repart and why["repartition"] is False
    assert why["cut_growth"] == pytest.approx(1.2)
    # cut growth past budget
    repart, why = inc.staleness_decision(
        {"cut": 160, "edges": [80, 80], "imbalance": 1.0}, base, 1.5, 2.0)
    assert repart and why["cut_growth"] == pytest.approx(1.6)
    # imbalance past budget, cut fine
    repart, why = inc.staleness_decision(
        {"cut": 100, "edges": [150, 10], "imbalance": 2.5}, base, 1.5, 2.0)
    assert repart and why["imbalance"] == pytest.approx(2.5)
    # a zero-cut baseline must not divide by zero
    repart, _ = inc.staleness_decision(
        {"cut": 0, "edges": [10, 10], "imbalance": 1.0},
        {"cut": 0, "edges": [10, 10], "imbalance": 1.0}, 1.5, 2.0)
    assert not repart


def test_degree_norm_row_matches_artifact_rows():
    g2, _, incr_art, _, _ = _evolved()
    in_deg, _ = degree_tables(g2.src, g2.dst, g2.n_nodes)
    for p in range(incr_art.n_parts):
        ids = incr_art.global_nid[p][incr_art.global_nid[p] >= 0]
        row = degree_norm_row(in_deg, ids, incr_art.pad_inner)
        assert np.array_equal(row, incr_art.in_deg[p])


def test_validate_artifact_dir_named_config_error(tmp_path):
    d = tmp_path / "parts"
    d.mkdir()
    np.savez(d / "part0.npz", x=np.zeros(1))
    np.savez(d / "part3.npz", x=np.zeros(1))
    with pytest.raises(ConfigError, match="part"):
        validate_artifact_dir(str(d), 4, None)


# ----------------------------------------------------------------------------
# (c) cycle-nonce stream refolding determinism
# ----------------------------------------------------------------------------

def _nonce_cfg(tmp_path, tag: str, nonce: int) -> Config:
    return Config(dataset="sbm", model="graphsage", n_partitions=2,
                  n_layers=2, n_hidden=8, sampling_rate=0.5, dropout=0.5,
                  use_pp=True, eval=False, n_epochs=3, log_every=2, seed=7,
                  cycle_nonce=nonce,
                  part_path=str(tmp_path / "parts"),
                  ckpt_path=str(tmp_path / f"ckpt_{tag}"),
                  results_path=str(tmp_path / f"res_{tag}"))


def test_cycle_nonce_determinism(tmp_path):
    g = sbm_graph(n_nodes=240, n_class=3, n_feat=8, p_in=0.12, p_out=0.01,
                  seed=3)
    hist = run_training(_nonce_cfg(tmp_path, "hist", 0), g=g, verbose=False)
    # nonce 0 (the default / --continual off path) is bitwise the
    # historical run: the fold is gated, not applied-with-zero
    again = run_training(_nonce_cfg(tmp_path, "again", 0), g=g,
                         verbose=False)
    assert again.losses == hist.losses
    # a cycle nonce refolds both the BNS sampling and dropout streams
    c1 = run_training(_nonce_cfg(tmp_path, "c1", 1), g=g, verbose=False)
    assert c1.losses != hist.losses
    # and is itself deterministic: same nonce -> bitwise-identical draws
    c1b = run_training(_nonce_cfg(tmp_path, "c1b", 1), g=g, verbose=False)
    assert c1b.losses == c1.losses
    # distinct cycles get distinct streams
    c2 = run_training(_nonce_cfg(tmp_path, "c2", 2), g=g, verbose=False)
    assert c2.losses != c1.losses


# ----------------------------------------------------------------------------
# (d) promotion protocol: corrupt/stale rejection, export cursor, acc gate
# ----------------------------------------------------------------------------

@lru_cache(maxsize=None)
def _serve_setup():
    g = sbm_graph(n_nodes=300, n_class=4, n_feat=8, seed=0)
    cfg = Config(dataset="sbm", model="graphsage", n_layers=2, n_hidden=8,
                 use_pp=True, n_feat=g.n_feat, n_class=g.n_class,
                 n_train=g.n_train, serve_max_batch=16)
    spec = spec_from_config(cfg)
    params, state = init_params(jax.random.key(1), spec)
    return g, cfg, spec, params, state


def _promotion_blob(serve_dir: str, cycle: int, scale: float = 1.5):
    """A promotion blob carrying 'fine-tuned' (scaled) weights + the
    matching full-graph table."""
    g, cfg, spec, params, state = _serve_setup()
    p2 = jax.tree.map(lambda x: x * scale, params)
    hidden, logits = full_graph_embeddings(p2, state, spec, g)
    return ckpt.write_promotion(
        serve_dir, params=p2, bn_state=state, hidden=hidden, logits=logits,
        lineage={"cycle": cycle, "consumed": 0}), np.asarray(logits)


def test_promote_rollback_on_corrupt_then_adopt_then_stale(tmp_path):
    g, cfg, spec, params, state = _serve_setup()
    core = serve.build_core(cfg, g, params, state, log=lambda *a, **k: None)
    try:
        before = np.asarray(core.predict(11)["scores"])
        promo, new_logits = _promotion_blob(str(tmp_path), cycle=1)
        # corrupted blob: rejected by the integrity chain, prior table live
        corrupt = str(tmp_path / "corrupt.blob")
        blob = bytearray(open(promo, "rb").read())
        blob[40] ^= 0xFF
        blob[41] ^= 0xFF
        open(corrupt, "wb").write(bytes(blob))
        r = core.promote(corrupt)
        assert not r["ok"] and "rejected" in r["err"]
        assert core.stats["promotions"] == 0
        assert np.array_equal(np.asarray(core.predict(11)["scores"]), before)
        # the intact blob adopts atomically: tier-A now serves the promoted
        # table bitwise
        r = core.promote(promo)
        assert r["ok"] and r["cycle"] == 1
        assert core.stats["promotions"] == 1
        got = core.predict(11)
        assert got["tier"] == "A"
        assert np.array_equal(
            np.asarray(got["scores"], new_logits.dtype), new_logits[11])
        # re-promoting the same cycle is stale (double-promote guard)
        r = core.promote(promo)
        assert not r["ok"] and "stale" in r["err"]
        assert core.stats["promotions"] == 1
    finally:
        core.close()


def test_promotion_admissible_rule():
    ok, _ = serve.promotion_admissible(1, 0)
    assert ok
    for cyc, adopted in ((1, 1), (1, 2), (0, 0)):
        ok, why = serve.promotion_admissible(cyc, adopted)
        assert not ok and "stale" in why


def test_export_deltas_cursor_semantics(tmp_path):
    g, cfg, spec, params, state = _serve_setup()
    core = serve.build_core(cfg, g, params, state, log=lambda *a, **k: None)
    try:
        core.add_edges([(7, 5)])
        core.add_edges([(11, 9)])
        r = core.export_deltas(0)
        assert r["ok"] and r["total"] == 2 and len(r["deltas"]) == 2
        r = core.export_deltas(1)
        assert r["ok"] and len(r["deltas"]) == 1
        assert r["deltas"][0]["edges"] == [[11, 9]]
        # a cursor past the journal is a named error, not an empty tail
        assert not core.export_deltas(3)["ok"]
        # compaction folds the prefix: an older cursor must resync
        core.compact(str(tmp_path))
        r = core.export_deltas(1)
        assert r["ok"] and r.get("snapshot_required") and r["folded"] == 2
        r = core.export_deltas(2)
        assert r["ok"] and not r.get("snapshot_required") \
            and r["deltas"] == []
    finally:
        core.close()


def _trained(tmp_path, tag="base"):
    """A short real training run: artifacts on disk + a serving ckpt."""
    cfg = Config(dataset="sbm", model="graphsage", n_partitions=2,
                 n_layers=2, n_hidden=8, sampling_rate=1.0, dropout=0.0,
                 use_pp=True, eval=True, n_epochs=4, log_every=2, seed=5,
                 part_path=str(tmp_path / "parts"),
                 ckpt_path=str(tmp_path / f"ckpt_{tag}"),
                 results_path=str(tmp_path / f"res_{tag}"),
                 serve_dir=str(tmp_path / "serve"))
    cfg = cfg.replace(graph_name=cfg.derive_graph_name())
    from bnsgcn_tpu.data.datasets import load_data
    g, _, _ = load_data(cfg)
    cfg = cfg.replace(n_feat=g.n_feat, n_class=g.n_class, n_train=g.n_train)
    run_training(cfg, g=g, verbose=False)
    return cfg, g


def _write_delta_log(serve_dir: str, g, seed=9, k=10):
    os.makedirs(serve_dir, exist_ok=True)
    rng = np.random.default_rng(seed)
    pairs = rng.integers(0, g.n_nodes, (k, 2))
    entries = [{"op": "add_edges",
                "edges": [[int(u), int(v)] for u, v in pairs if u != v]}]
    with open(os.path.join(serve_dir, "delta_log.jsonl"), "w") as f:
        for e in entries:
            f.write(json.dumps(e) + "\n")
    return entries


def test_run_cycle_acc_gate_rolls_back_but_cursor_advances(tmp_path):
    cfg, g = _trained(tmp_path)
    _write_delta_log(cfg.serve_dir, g)
    # an impossible gate (the fine-tune would need +1.0 val acc) forces the
    # rollback path: weights stay, the consumed cursor still advances
    out = continual.run_cycle(
        cfg.replace(cycle_epochs=1, continual_acc_drop=-1.0),
        log=lambda *a, **k: None)
    assert out["ok"] and not out["promoted"] and out["consumed"] == 1
    assert not os.path.exists(ckpt.promotion_path(cfg.serve_dir))
    st = continual.load_state(cfg.serve_dir)
    assert st["cycle"] == 1 and st["consumed"] == 1
    # the next cycle has nothing left to consume
    out = continual.run_cycle(cfg.replace(cycle_epochs=1),
                              log=lambda *a, **k: None)
    assert out.get("noop")


def test_continual_main_noop_and_config_exit(tmp_path):
    args = ["--dataset", "sbm", "--model", "graphsage",
            "--n-partitions", "2", "--use-pp", "--fix-seed", "--seed", "5",
            "--part-path", str(tmp_path / "parts"),
            "--ckpt-path", str(tmp_path / "ckpt"),
            "--serve-dir", str(tmp_path / "serve")]
    # empty serve dir: a clean no-op, exit 0
    assert continual.continual_main(args) == 0
    # deltas but no artifacts/checkpoint to fold them into: exit 2, named
    g = sbm_graph(n_nodes=60, n_class=3, n_feat=4, seed=0)
    _write_delta_log(str(tmp_path / "serve"), g, k=3)
    assert continual.continual_main(args) == 2


# ----------------------------------------------------------------------------
# (e) quickgate e2e: train -> serve -> deltas -> continual -> promoted answers
# ----------------------------------------------------------------------------

def _env():
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu")
    return env


def _free_port() -> int:
    import socket
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _cli_flags(cfg: Config) -> list:
    return ["--dataset", "sbm", "--model", "graphsage",
            "--n-partitions", "2", "--n-layers", "2", "--n-hidden", "8",
            "--use-pp", "--fix-seed", "--seed", "5",
            "--sampling-rate", "1.0", "--dropout", "0.0",
            "--graph-name", cfg.graph_name,
            "--part-path", cfg.part_path, "--ckpt-path", cfg.ckpt_path,
            "--serve-dir", cfg.serve_dir]


@pytest.mark.quickgate
def test_e2e_train_serve_mutate_continual_promote(tmp_path):
    import time
    cfg, g = _trained(tmp_path)
    port = _free_port()
    flags = _cli_flags(cfg)
    p = subprocess.Popen(
        [sys.executable, "-m", "bnsgcn_tpu.main", "serve"] + flags
        + ["--serve-port", str(port)],
        env=_env(), cwd=REPO, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True)
    try:
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            if p.poll() is not None:
                raise AssertionError(f"server died rc={p.returncode}:\n"
                                     f"{p.stdout.read()[-2000:]}")
            try:
                if serve.request(port, {"op": "ping"},
                                 timeout_s=1.0).get("ok"):
                    break
            except Exception:
                time.sleep(0.2)
        else:
            raise AssertionError("server never became ready")
        before = serve.request(port, {"op": "predict", "node": 3})
        assert before["ok"]
        # mutate the live graph through the serving delta journal
        rng = np.random.default_rng(2)
        edges = [[int(u), int(v)]
                 for u, v in rng.integers(0, g.n_nodes, (8, 2)) if u != v]
        r = serve.request(port, {"op": "add_edges", "edges": edges})
        assert r["ok"]
        # one continual cycle against the live server: export handshake,
        # incremental fold, warm-start fine-tune, live promotion
        out = subprocess.run(
            [sys.executable, "-m", "bnsgcn_tpu.main", "continual"] + flags
            + ["--serve-port", str(port), "--continual-source", "server",
               "--cycle-epochs", "2", "--cycles", "1"],
            env=_env(), cwd=REPO, capture_output=True, text=True,
            timeout=420)
        assert out.returncode == 0, \
            f"continual failed:\n{out.stdout[-3000:]}\n{out.stderr[-3000:]}"
        stats = serve.request(port, {"op": "stats"})
        assert stats["promotions"] == 1
        st = continual.load_state(cfg.serve_dir)
        assert st["cycle"] == 1 and st["last"]["promoted"]
        # one add_edges request = one journal entry = one cursor step
        assert st["consumed"] == 1
        # the promoted serving answers reflect the fine-tuned weights
        after = serve.request(port, {"op": "predict", "node": 3})
        assert after["ok"]
        assert not np.array_equal(np.asarray(before["scores"]),
                                  np.asarray(after["scores"]))
        promo = ckpt.read_promotion(ckpt.promotion_path(cfg.serve_dir))
        assert int(promo["lineage"]["cycle"]) == 1
        logits = np.asarray(promo["logits"])
        # some tier-A (clean) node must serve the promoted table bitwise
        for v in range(0, g.n_nodes, max(1, g.n_nodes // 40)):
            got = serve.request(port, {"op": "predict", "node": int(v)})
            if got["tier"] == "A":
                assert np.array_equal(
                    np.asarray(got["scores"], logits.dtype), logits[v])
                break
        else:
            raise AssertionError("no clean tier-A node found")
    finally:
        p.terminate()
        try:
            p.wait(timeout=30)
        except subprocess.TimeoutExpired:
            p.kill()
