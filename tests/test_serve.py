"""Online inference serving (bnsgcn_tpu/serve.py): the two-tier contract.

What is pinned, per ISSUE/ROADMAP:
  (a) tier-A scores are BITWISE the full-eval logits for clean nodes (the
      table is the eval forward's own output — serving must never drift
      from what training reported);
  (b) tier-B fresh L-hop re-aggregation equals a recompute-from-scratch on
      the mutated graph for dirty nodes, across GCN/SAGE/GAT;
  (c) batching invariance: a request scored alone is bitwise the same
      request scored inside a full padded-SpMM bucket (per-row edge order
      is batch-composition-invariant by construction);
  (d) delta ingestion marks and refreshes EXACTLY the <= L-hop forward
      closure of the touched nodes — and refresh touches nothing else
      (clean table rows stay bitwise untouched);
  (e) quickgate e2e: a real subprocess server + TCP client round trip, and
      the SIGTERM drain -> exit 75 -> resumable delta-log replay contract
      (the serving twin of tests/test_resilience_e2e.py).
"""

import json
import os
import socket
import subprocess
import sys
import threading
import time
from functools import lru_cache

import jax
import numpy as np
import pytest

from bnsgcn_tpu import checkpoint as ckpt
from bnsgcn_tpu import serve
from bnsgcn_tpu.config import Config, ConfigError
from bnsgcn_tpu.data.graph import Graph, sbm_graph
from bnsgcn_tpu.evaluate import full_graph_embeddings, full_graph_logits
from bnsgcn_tpu.models.gnn import init_params, spec_from_config

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

MODELS = [("gcn", False, 1), ("graphsage", True, 1), ("gat", False, 2)]
MODEL_IDS = [m[0] for m in MODELS]


@lru_cache(maxsize=None)
def _setup(model: str, use_pp: bool, heads: int):
    g = sbm_graph(n_nodes=300, n_class=4, n_feat=8, seed=0)
    cfg = Config(dataset="sbm", model=model, n_layers=2, n_hidden=8,
                 heads=heads, use_pp=use_pp, n_feat=g.n_feat,
                 n_class=g.n_class, n_train=g.n_train, serve_max_batch=16)
    spec = spec_from_config(cfg)
    params, state = init_params(jax.random.key(1), spec)
    return g, cfg, spec, params, state


def _core(model, use_pp, heads):
    g, cfg, spec, params, state = _setup(model, use_pp, heads)
    return g, spec, params, state, serve.build_core(
        cfg, g, params, state, log=lambda *a, **k: None)


def _appended(g: Graph, edges) -> Graph:
    """Ground-truth graph with `edges` appended — what tier B must match."""
    src = np.concatenate([g.src, np.asarray([u for u, _ in edges])]).astype(
        g.src.dtype)
    dst = np.concatenate([g.dst, np.asarray([v for _, v in edges])]).astype(
        g.dst.dtype)
    return Graph(g.n_nodes, src, dst, g.feat, g.label, g.train_mask,
                 g.val_mask, g.test_mask, g.multilabel)


def _fwd_closure(src, dst, seeds, hops):
    """Independent (edge-list scan) forward closure the dirty set must equal."""
    seen = set(int(s) for s in seeds)
    frontier = set(seen)
    for _ in range(hops):
        nxt = {int(d) for s, d in zip(src, dst) if int(s) in frontier} - seen
        seen |= nxt
        frontier = nxt
    return seen


# ----------------------------------------------------------------------------
# (a) tier A bitwise vs full eval
# ----------------------------------------------------------------------------

@pytest.mark.parametrize("model,use_pp,heads", MODELS, ids=MODEL_IDS)
def test_tier_a_bitwise_vs_full_eval(model, use_pp, heads):
    g, spec, params, state, core = _core(model, use_pp, heads)
    try:
        ref = full_graph_logits(params, state, spec, g)
        for v in (0, 7, 123, g.n_nodes - 1):
            r = core.predict(v)
            assert r["tier"] == "A"
            assert np.array_equal(np.asarray(r["scores"], ref.dtype), ref[v])
            assert r["pred"] == int(np.argmax(ref[v]))
    finally:
        core.close()


# ----------------------------------------------------------------------------
# (b) tier B == recompute-from-scratch for dirty nodes after edge appends
# ----------------------------------------------------------------------------

@pytest.mark.parametrize("model,use_pp,heads", MODELS, ids=MODEL_IDS)
def test_tier_b_matches_scratch_recompute(model, use_pp, heads):
    g, spec, params, state, core = _core(model, use_pp, heads)
    try:
        edges = [(7, 5), (11, 5), (7, 5)]      # incl. a multi-edge
        core.add_edges(edges)
        ref2 = full_graph_logits(params, state, spec, _appended(g, edges))
        dirty = sorted(core.dirty)[:6] + [5]
        for v in set(dirty):
            r = core.predict(v)
            assert r["tier"] == "B", f"node {v} should be dirty"
            np.testing.assert_allclose(np.asarray(r["scores"]), ref2[v],
                                       rtol=1e-5, atol=1e-5)
    finally:
        core.close()


def test_tier_b_exact_after_feature_update():
    g, spec, params, state, core = _core("graphsage", True, 1)
    try:
        new_feat = np.full(g.n_feat, 0.25, dtype=np.float32)
        core.update_feat(9, new_feat)
        g2 = Graph(g.n_nodes, g.src, g.dst, g.feat.copy(), g.label,
                   g.train_mask, g.val_mask, g.test_mask, g.multilabel)
        g2.feat[9] = new_feat
        ref2 = full_graph_logits(params, state, spec, g2)
        assert 9 in core.dirty
        r = core.predict(9)
        assert r["tier"] == "B"
        np.testing.assert_allclose(np.asarray(r["scores"]), ref2[9],
                                   rtol=1e-5, atol=1e-5)
    finally:
        core.close()


# ----------------------------------------------------------------------------
# (c) batching invariance: alone == inside a full bucket
# ----------------------------------------------------------------------------

@pytest.mark.parametrize("model,use_pp,heads", MODELS, ids=MODEL_IDS)
def test_batching_invariance_bitwise(model, use_pp, heads):
    g, spec, params, state, core = _core(model, use_pp, heads)
    try:
        target = 42
        alone = core.scorer.score(core.graph, params, state, [target])
        full = core.scorer.score(core.graph, params, state,
                                 [target] + list(range(16)))
        assert np.array_equal(alone[target][1], full[target][1])
        assert np.array_equal(alone[target][0], full[target][0])
    finally:
        core.close()


def test_predict_many_coalesces_tier_b_into_bucket_steps():
    """A batch request's tier-B set must run as whole-bucket steps (never
    one step per node) and agree with the per-node tier-B path."""
    g, spec, params, state, core = _core("gcn", False, 1)
    try:
        core.add_edges([(3, 17)])
        dirty_pick = sorted(core.dirty)[:10]
        clean_pick = [n for n in range(g.n_nodes)
                      if n not in core.dirty][:2]
        nodes = dirty_pick + clean_pick
        solo = {n: core.scorer.score(core.graph, params, state, [n])[n][1]
                for n in dirty_pick}
        before = core.snapshot_stats()["refreshed_nodes"]
        out = core.predict_many(nodes)
        tiers = {r["node"]: r for r in out}
        n_b = sum(1 for r in out if r["tier"] == "B")
        assert n_b == len(dirty_pick) and len(out) == len(nodes)
        for n, ref in solo.items():
            assert np.array_equal(np.asarray(tiers[n]["scores"],
                                             ref.dtype), ref)
        # the whole tier-B set fit one serve_max_batch bucket step, which
        # also refreshed those rows (they were dirty)
        assert core.snapshot_stats()["refreshed_nodes"] == before + n_b
        assert all(tiers[n]["tier"] == "A" for n in clean_pick)
    finally:
        core.close()


def test_concurrent_requests_coalesce_into_buckets():
    """Concurrent tier-B submissions share batcher steps AND each equals its
    solo score — the batching path itself is invariant, not just the
    scorer."""
    g, spec, params, state, core = _core("graphsage", True, 1)
    try:
        targets = list(range(12))
        solo = {t: core.scorer.score(core.graph, params, state, [t])[t][1]
                for t in targets}
        results = {}

        def one(t):
            results[t] = np.asarray(core.predict(t, tier="B")["scores"])

        threads = [threading.Thread(target=one, args=(t,)) for t in targets]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for t in targets:
            assert np.array_equal(results[t], solo[t]), f"node {t}"
        stats = core.snapshot_stats()
        assert stats["batches"] <= len(targets)   # at least some coalescing
        assert stats["batched_requests"] == len(targets)
    finally:
        core.close()


# ----------------------------------------------------------------------------
# (d) delta ingestion: exactly the <= L-hop dirty set, nothing else
# ----------------------------------------------------------------------------

@pytest.mark.parametrize("model,use_pp,heads", MODELS, ids=MODEL_IDS)
def test_delta_refreshes_exactly_the_dirty_set(model, use_pp, heads):
    g, spec, params, state, core = _core(model, use_pp, heads)
    try:
        edges = [(3, 17)]
        core.add_edges(edges)
        g2 = _appended(g, edges)
        expected = _fwd_closure(g2.src, g2.dst, {3, 17}, core.hops)
        assert core.dirty == expected
        before_logits = core.logits.copy()
        before_hidden = core.hidden.copy()
        refreshed = core.flush()
        assert refreshed == len(expected)
        assert core.snapshot_stats()["refreshed_nodes"] == len(expected)
        assert not core.dirty
        clean = np.setdiff1d(np.arange(g.n_nodes), sorted(expected))
        # nothing else: clean rows bitwise untouched
        assert np.array_equal(core.logits[clean], before_logits[clean])
        assert np.array_equal(core.hidden[clean], before_hidden[clean])
        # the dirty set: refreshed to the scratch recompute
        ref2 = full_graph_logits(params, state, spec, g2)
        ids = sorted(expected)
        np.testing.assert_allclose(core.logits[ids], ref2[ids],
                                   rtol=1e-5, atol=1e-5)
        # and tier A serves the refreshed rows again
        r = core.predict(17)
        assert r["tier"] == "A"
    finally:
        core.close()


def test_dirty_mark_survives_concurrent_delta_mid_refresh():
    """A delta landing while a refresh step is in flight must not have its
    fresh dirty mark cleared by the step's (now stale) result — and claimed
    nodes are never double-picked by a concurrent refresh."""
    g, spec, params, state, core = _core("gcn", False, 1)
    try:
        core.add_edges([(3, 17)])
        orig_run = core.scorer.run_arrays

        def run_then_mutate(*a, **kw):
            out = orig_run(*a, **kw)
            # lands between the step's snapshot and its write-back; also
            # proves the claim: node 17 is in _refreshing, not dirty, so
            # refresh_some here must not double-pick it
            assert 17 in core._refreshing
            assert 17 not in core.dirty
            core.add_edges([(1, 17)])
            return out

        core.scorer.run_arrays = run_then_mutate
        try:
            core._score_batch([17])
        finally:
            core.scorer.run_arrays = orig_run
        assert 17 in core.dirty          # stale result did not clear it
        assert 17 not in core._refreshing
        # and tier routing still treats it as dirty
        assert core.predict(17)["tier"] == "B"
        core.flush()
        assert not core.dirty and not core._refreshing
    finally:
        core.close()


# ----------------------------------------------------------------------------
# checkpoint selection + embedding artifact (satellites)
# ----------------------------------------------------------------------------

def _ckpt_cfg(tmp_path):
    g, cfg, spec, params, state = _setup("graphsage", True, 1)
    cfg = cfg.replace(ckpt_path=str(tmp_path),
                      graph_name=cfg.derive_graph_name())
    return g, cfg, spec, params, state


def test_serving_checkpoint_prefers_final_then_walks_chain(tmp_path):
    g, cfg, spec, params, state = _ckpt_cfg(tmp_path)
    ckpt.save_checkpoint(ckpt.periodic_path(cfg, 3), params=params,
                         bn_state=state, epoch=3, seed=1)
    assert ckpt.serving_checkpoint(cfg)[0] == ckpt.periodic_path(cfg, 3)
    ckpt.save_checkpoint(ckpt.final_path(cfg), params=params,
                         bn_state=state, epoch=9, best_acc=0.7, seed=1)
    path, payload = ckpt.serving_checkpoint(cfg)
    assert path == ckpt.final_path(cfg) and payload["epoch"] == 9
    # torn final -> fall back to the newest valid periodic, loudly
    from bnsgcn_tpu.resilience import corrupt_file
    corrupt_file(ckpt.final_path(cfg))
    logged = []
    path, payload = ckpt.serving_checkpoint(cfg, log=logged.append)
    assert path == ckpt.periodic_path(cfg, 3) and payload["epoch"] == 3
    assert any("final checkpoint unusable" in s for s in logged)
    # everything torn -> None (serve exits 2 with a named error, never
    # loads garbage)
    corrupt_file(ckpt.periodic_path(cfg, 3))
    assert ckpt.serving_checkpoint(cfg, log=logged.append) is None


def test_embedding_table_roundtrip_and_integrity(tmp_path):
    g, cfg, spec, params, state = _setup("gcn", False, 1)
    hidden, logits = full_graph_embeddings(params, state, spec, g)
    path = str(tmp_path / "emb.tbl")
    serve.save_table(path, hidden, logits, meta={"graph_name": "x",
                                                 "n_nodes": g.n_nodes})
    h2, l2, meta = serve.load_table(path)
    assert np.array_equal(h2, hidden) and np.array_equal(l2, logits)
    assert meta["n_nodes"] == g.n_nodes
    from bnsgcn_tpu.resilience import corrupt_file
    corrupt_file(path)
    with pytest.raises(ckpt.CheckpointCorrupt):
        serve.load_table(path)
    # a wrong-sized artifact is a named config error, not a silent mismatch
    with pytest.raises(ConfigError):
        serve.ServeCore(cfg, spec, serve.DynamicGraph(g), params, state,
                        hidden[:10], logits[:10], log=lambda *a: None)


def test_cold_start_from_table_matches_precompute():
    """build_core(hidden=..., logits=...) — the --embeddings cold start —
    serves bitwise what a fresh precompute serves."""
    g, cfg, spec, params, state = _setup("gcn", False, 1)
    hidden, logits = full_graph_embeddings(params, state, spec, g)
    core = serve.build_core(cfg, g, params, state, log=lambda *a: None,
                            hidden=hidden, logits=logits)
    try:
        ref = full_graph_logits(params, state, spec, g)
        r = core.predict(33)
        assert np.array_equal(np.asarray(r["scores"], ref.dtype), ref[33])
    finally:
        core.close()


def test_dump_embeddings_flag_writes_loadable_table(tmp_path):
    """--dump-embeddings on the eval path: run_training writes the
    integrity-headed all-node table an external serve cold-starts from."""
    from bnsgcn_tpu.run import run_training
    out = str(tmp_path / "emb.tbl")
    cfg = Config(dataset="sbm", partition_method="random", n_partitions=2,
                 model="graphsage", n_layers=2, n_hidden=8, use_pp=True,
                 sampling_rate=1.0, n_epochs=4, log_every=2, fix_seed=True,
                 seed=5, part_path=str(tmp_path / "parts"),
                 ckpt_path=str(tmp_path / "ckpt"),
                 results_path=str(tmp_path / "res"),
                 comm_trace=False, dump_embeddings=out)
    run_training(cfg, verbose=False)
    hidden, logits, meta = serve.load_table(out)
    assert hidden.shape[0] == logits.shape[0] == 2000
    assert hidden.shape[1] == 8 and meta["model"] == "graphsage"
    assert np.isfinite(hidden).all() and np.isfinite(logits).all()


# ----------------------------------------------------------------------------
# DynamicGraph units
# ----------------------------------------------------------------------------

def test_dynamic_graph_neighbors_and_degrees_track_deltas():
    g = sbm_graph(n_nodes=100, n_class=4, n_feat=4, seed=2)
    dg = serve.DynamicGraph(g)
    in_before = list(dg.in_nbrs(5))
    od_u, id_v = dg.out_deg[9], dg.in_deg[5]
    dg.add_edges([(9, 5), (9, 5)])
    assert dg.in_nbrs(5) == in_before + [9, 9]
    assert dg.out_deg[9] == od_u + 2 and dg.in_deg[5] == id_v + 2
    with pytest.raises(ValueError):
        dg.add_edges([(0, 100)])
    with pytest.raises(ValueError):
        dg.set_feat(0, np.zeros(3, np.float32))


def test_in_closure_depths_cover_the_computation_subgraph():
    g = sbm_graph(n_nodes=100, n_class=4, n_feat=4, seed=2)
    dg = serve.DynamicGraph(g)
    depth = dg.in_closure([7], 2)
    assert depth[7] == 0
    for u in dg.in_nbrs(7):
        assert depth[u] <= 1
        for w in dg.in_nbrs(u):
            assert w in depth
    # every node at depth <= hops-1 has its FULL in-neighborhood present
    for v, d in depth.items():
        if d <= 1:
            assert all(u in depth for u in dg.in_nbrs(v))


def test_bucket_ladder_is_static_shapes():
    assert serve._bucket(1, 32) == 32
    assert serve._bucket(32, 32) == 32
    assert serve._bucket(33, 32) == 64
    assert serve._bucket(1000, 128) == 1024


# ----------------------------------------------------------------------------
# (e) e2e: subprocess server + client round trip; SIGTERM drain contract
# ----------------------------------------------------------------------------

def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("", 0))
        return s.getsockname()[1]


def _env():
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu")
    return env


def _write_serving_ckpt(tmp_path):
    """A loadable (random-init) checkpoint + the flag set serve launches
    with — serving correctness does not depend on trained weights."""
    cfg = Config(dataset="sbm", model="graphsage", n_layers=2, n_hidden=8,
                 use_pp=True, seed=3, sampling_rate=1.0,
                 ckpt_path=str(tmp_path / "ckpt"))
    cfg = cfg.replace(graph_name=cfg.derive_graph_name())
    from bnsgcn_tpu.data.datasets import load_data
    g, _, _ = load_data(cfg)
    cfg2 = cfg.replace(n_feat=g.n_feat, n_class=g.n_class, n_train=g.n_train)
    params, state = init_params(jax.random.key(3),
                                spec_from_config(cfg2))
    ckpt.save_checkpoint(ckpt.final_path(cfg2), params=params,
                         bn_state=state, epoch=7, best_acc=0.5, seed=3)
    return ["--dataset", "sbm", "--model", "graphsage", "--n-layers", "2",
            "--n-hidden", "8", "--use-pp", "--fix-seed", "--seed", "3",
            "--ckpt-path", str(tmp_path / "ckpt")]


def _launch(args, port):
    cmd = ([sys.executable, "-m", "bnsgcn_tpu.main", "serve"] + args
           + ["--serve-port", str(port)])
    p = subprocess.Popen(cmd, env=_env(), cwd=REPO, stdout=subprocess.PIPE,
                         stderr=subprocess.STDOUT, text=True)
    deadline = time.monotonic() + 120
    while time.monotonic() < deadline:
        if p.poll() is not None:
            raise AssertionError(f"server died rc={p.returncode}:\n"
                                 f"{p.stdout.read()[-2000:]}")
        try:
            if serve.request(port, {"op": "ping"}, timeout_s=1.0).get("ok"):
                return p
        except Exception:
            pass
        time.sleep(0.2)
    p.kill()
    raise AssertionError("server never became ready")


@pytest.mark.quickgate
def test_e2e_subprocess_server_roundtrip(tmp_path):
    args = _write_serving_ckpt(tmp_path)
    port = _free_port()
    p = _launch(args, port)
    try:
        r = serve.request(port, {"op": "predict", "node": 11})
        assert r["ok"] and r["tier"] == "A" and len(r["scores"]) == 8
        r = serve.request(port, {"op": "add_edges", "edges": [[4, 11]]})
        assert r["ok"] and r["dirty_total"] > 0
        r = serve.request(port, {"op": "predict", "node": 11})
        assert r["ok"] and r["tier"] == "B"
        r = serve.request(port, {"op": "predict_many",
                                 "nodes": [1, 2, 3]})
        assert r["ok"] and len(r["results"]) == 3
        assert serve.request(port, {"op": "nope"})["ok"] is False
        stats = serve.request(port, {"op": "stats"})
        # nodes 1-3 may or may not sit in the appended edge's dirty
        # frontier, so only the totals are pinned, not the tier split
        assert stats["requests"] >= 5
        assert stats["tier_a"] >= 1 and stats["tier_b"] >= 1
        serve.request(port, {"op": "shutdown"})
        assert p.wait(timeout=60) == 0
    finally:
        if p.poll() is None:
            p.kill()


def test_e2e_sigterm_drains_flushes_delta_log_exit_75(tmp_path):
    """The serving half of the PR-4 preemption contract: SIGTERM -> drain,
    delta log flushed, exit 75; a relaunch replays the log (the ingested
    delta — and its dirty frontier — survives the restart)."""
    args = _write_serving_ckpt(tmp_path)
    serve_dir = str(tmp_path / "servedir")
    args += ["--serve-dir", serve_dir]
    port = _free_port()
    p = _launch(args, port)
    try:
        serve.request(port, {"op": "add_edges", "edges": [[4, 11], [7, 2]]})
        p.send_signal(15)
        rc = p.wait(timeout=60)
        out = p.stdout.read()
        assert rc == 75, (rc, out[-2000:])
        assert "delta(s) flushed" in out
        log_path = os.path.join(serve_dir, serve.DELTA_LOG)
        assert os.path.exists(log_path)
        lines = [json.loads(l) for l in open(log_path) if l.strip()]
        assert lines == [{"op": "add_edges", "edges": [[4, 11], [7, 2]]}]
    finally:
        if p.poll() is None:
            p.kill()
    # relaunch: the delta (and its dirty frontier) must be live again
    p2 = _launch(args, port)
    try:
        stats = serve.request(port, {"op": "stats"})
        assert stats["deltas"] == 1
        r = serve.request(port, {"op": "flush"})
        assert r["ok"]
        assert serve.request(port, {"op": "dirty"})["count"] == 0
        serve.request(port, {"op": "shutdown"})
        assert p2.wait(timeout=60) == 0
    finally:
        if p2.poll() is None:
            p2.kill()
