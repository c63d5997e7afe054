"""--reorder exactness (data/reorder.py) + the layout fast-path pins.

The tentpole invariant: the reorder pass permutes each part's REAL inner
rows once, at load time, and is invisible at every user-visible edge —
gather_parts maps results back through the permuted global_nid, so the
global-order logits of a `--reorder cluster` run are BITWISE equal to
`--reorder off` for the pure-ELL and segment SpMMs (per-row sums see the
same sources in the same slot order) and reassociation-close for the
hybrid (the dense/residual split moves with the row order). Pinned here
across all three halo strategies at rate 1.0, composed with --overlap
split and a replicas x parts x feat mesh, plus:

* apply_reorder invariants: per-part bijection, identity on padding rows,
  global-id edge multiset exactly preserved, shapes/n_b/degree multisets
  unchanged, ValueError on multi-host partial artifacts;
* the permutation disk cache: memoized on second load, keyed on tile so
  t256/t512 orders never alias, stale shapes rebuilt, no path w/o
  --cache-dir;
* layout-cache key audit: hybrid/ell/gat keys over tile x overlap x
  reorder are pairwise distinct (the t256-vs-t512 aliasing regression);
* coverage really rises where it should: a community SBM whose node ids
  were scrambled recovers >= +15 points of dense-tile coverage;
* the bincount/packed-sort layout builders (BNSGCN_LAYOUT_FASTPATH=1,
  the default) are bitwise identical to the legacy np.unique/argsort
  passes on all three layout families, raw and reordered;
* e2e through the real CLI: `--reorder cluster --halo-refresh 2` runs the
  header/obs plumbing ('+ro' halo label, reorder + layout_build events),
  and the default pipeline is bitwise `--reorder off`.
"""

import dataclasses
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bnsgcn_tpu.config import Config
from bnsgcn_tpu.data.artifacts import build_artifacts
from bnsgcn_tpu.data.graph import Graph, sbm_graph, synthetic_graph
from bnsgcn_tpu.data.partitioner import partition_graph
from bnsgcn_tpu.data.reorder import (REORDER_ALGO, apply_reorder,
                                     artifact_coverage, compute_orders,
                                     maybe_reorder, reorder_cache_path)
from bnsgcn_tpu.evaluate import gather_parts
from bnsgcn_tpu.models.gnn import ModelSpec, init_params
from bnsgcn_tpu.ops.block_spmm import effective_occupancy
from bnsgcn_tpu.parallel import feat as feat_mod
from bnsgcn_tpu.parallel.mesh import make_parts_mesh
from bnsgcn_tpu.parallel.replicas import make_mesh
from bnsgcn_tpu.trainer import (build_block_arrays, build_step_fns,
                                ell_layout_key, gat_layout_key,
                                hybrid_layout_key, init_training,
                                place_blocks, place_replicated)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ----------------------------------------------------------------------------
# fixtures: a skew-partitioned graph and its reordered twin
# ----------------------------------------------------------------------------

@pytest.fixture(scope="module")
def ro4():
    """4-part skewed partition + the same artifacts reordered with a small
    tile_r (32) so the LPA clustering + FFD packing path really runs at
    this size instead of degenerating to one degree-sorted cluster."""
    g = synthetic_graph(n_nodes=160, avg_degree=7, n_feat=6, seed=43,
                        power_law=True)
    pid = np.zeros(g.n_nodes, dtype=np.int32)
    pid[80:120] = 1
    pid[120:144] = 2
    pid[144:] = 3
    art = build_artifacts(g, pid)
    orders = compute_orders(art, tile_r=32)
    # the permutation must be non-trivial or every test below is vacuous
    assert any((orders[p] != np.arange(art.pad_inner)).any() for p in range(4))
    return g, art, apply_reorder(art, orders), orders, make_parts_mesh(4)


def _train(g, art, mesh, reorder, *, spmm="ell", strategy="padded",
           overlap="off", epochs=2):
    """Forward logits (global node order, via gather_parts) + train losses
    for one (artifact, resolved-reorder) pair. rate 1.0 and dropout 0.0:
    BNS sampling and dropout draws are row-position-keyed, so any rate < 1
    would select different nodes under the permutation by design."""
    cfg = Config(model="graphsage", dropout=0.0, use_pp=False, norm="layer",
                 n_train=g.n_train, lr=0.01, sampling_rate=1.0, spmm=spmm,
                 halo_exchange=strategy, overlap=overlap, reorder=reorder,
                 n_partitions=mesh.devices.size, n_feat=g.n_feat,
                 n_class=g.n_class)
    spec = ModelSpec("graphsage", (g.n_feat, 16, g.n_class), norm="layer",
                     dropout=0.0, train_size=g.n_train)
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
    _, _, opt = init_training(cfg, spec, mesh)
    logits = fns.forward(params, state, jnp.uint32(2), blk, tb,
                         jax.random.key(0))
    losses = []
    for e in range(epochs):
        params, state, opt, loss = fns.train_step(
            params, state, opt, jnp.uint32(e), blk, tb,
            jax.random.key(0), jax.random.key(1))
        losses.append(float(loss))
    return gather_parts(art, np.asarray(logits)), losses, fns.overlap


# ----------------------------------------------------------------------------
# round-trip exactness: permuted-space run == off after the inverse map
# ----------------------------------------------------------------------------

@pytest.mark.quickgate
@pytest.mark.parametrize("strategy", ["padded", "shift", "ragged"])
def test_ell_logits_bitwise_under_reorder(ro4, strategy):
    """The acceptance pin: per-row ELL sums see the same sources in the
    same slot order (stable dst grouping of the same edge sequence), so
    global-order logits are bitwise invariant under the permutation for
    EVERY halo strategy."""
    g, art, art_ro, _, mesh = ro4
    lo, losses_o, _ = _train(g, art, mesh, "off", strategy=strategy)
    lr, losses_r, _ = _train(g, art_ro, mesh, "cluster", strategy=strategy)
    assert np.array_equal(lo, lr), strategy
    for a, b in zip(losses_o, losses_r):
        assert abs(a - b) <= 1e-6 * max(abs(a), 1.0), (strategy, losses_o,
                                                       losses_r)


def test_segment_logits_bitwise_under_reorder(ro4):
    g, art, art_ro, _, mesh = ro4
    lo, _, _ = _train(g, art, mesh, "off", spmm="segment")
    lr, _, _ = _train(g, art_ro, mesh, "cluster", spmm="segment")
    assert np.array_equal(lo, lr)


def test_hybrid_logits_allclose_under_reorder(ro4):
    """The hybrid's dense/residual split moves with the row order (that's
    the point), so per-row sums reassociate: allclose, not bitwise."""
    g, art, art_ro, _, mesh = ro4
    lo, losses_o, _ = _train(g, art, mesh, "off", spmm="hybrid")
    lr, losses_r, _ = _train(g, art_ro, mesh, "cluster", spmm="hybrid")
    scale = np.abs(lo).max() + 1e-9
    assert np.abs(lr - lo).max() / scale < 1e-5
    for a, b in zip(losses_o, losses_r):
        assert abs(a - b) <= 1e-4 * max(abs(a), 1.0)


def test_composes_with_overlap_split(ro4):
    """--overlap split re-derives interior/frontier membership from the
    permuted artifacts; frontier-ness is a per-row property that travels
    with its row, so the split path stays bitwise too."""
    g, art, art_ro, _, mesh = ro4
    lo, losses_o, ov_o = _train(g, art, mesh, "off", overlap="split")
    lr, losses_r, ov_r = _train(g, art_ro, mesh, "cluster", overlap="split")
    assert ov_o == ov_r == "split"      # both really ran the split path
    assert np.array_equal(lo, lr)
    for a, b in zip(losses_o, losses_r):
        assert abs(a - b) <= 1e-6 * max(abs(a), 1.0)


def test_composes_with_replicas_and_feat_mesh():
    """2 x 2 x 2 replicas x parts x feat: the fused loss/grad on permuted
    artifacts matches the raw-artifact run — the reorder changes no
    estimator on any mesh shape."""
    g = synthetic_graph(n_nodes=120, avg_degree=6, n_feat=6, seed=44,
                        power_law=True)
    pid = (np.arange(g.n_nodes) >= 70).astype(np.int32)
    art = build_artifacts(g, pid)
    art_ro = apply_reorder(art, compute_orders(art, tile_r=32))
    mesh = make_mesh(2, 2, 2)

    def run(a, reorder):
        cfg = Config(model="graphsage", dropout=0.0, use_pp=False,
                     norm="layer", n_train=g.n_train, lr=0.01,
                     sampling_rate=1.0, spmm="ell", reorder=reorder,
                     replicas=2, feat=2, n_partitions=2, n_feat=g.n_feat,
                     n_class=g.n_class)
        spec = ModelSpec("graphsage", (g.n_feat, 16, g.n_class),
                         norm="layer", dropout=0.0, train_size=g.n_train)
        fns, _, tables, _ = build_step_fns(cfg, spec, a, mesh)
        assert fns.n_replicas == 2 and fns.n_feat == 2
        blk_np = build_block_arrays(a, "graphsage")
        blk_np.update(fns.extra_blk)
        blk = place_blocks(blk_np, mesh)
        tb = place_replicated(tables, mesh)
        params, state = init_params(jax.random.key(5), spec)
        params_np = jax.tree.map(np.asarray, params)
        p = feat_mod.place_params(params_np, mesh, spec)
        s = place_replicated(state, mesh)
        loss, grads = fns.loss_and_grad(p, s, jnp.uint32(0), blk, tb,
                                        jax.random.key(0), jax.random.key(1))
        return float(loss), jax.tree.map(
            lambda x: np.asarray(jax.device_get(x)), grads)

    lo, go = run(art, "off")
    lr, gr = run(art_ro, "cluster")
    assert abs(lr - lo) <= 1e-5 * max(abs(lo), 1.0)
    for a, b in zip(jax.tree.leaves(go), jax.tree.leaves(gr)):
        s = np.abs(a).max() + 1e-9
        assert np.abs(b - a).max() / s < 1e-4


# ----------------------------------------------------------------------------
# apply_reorder invariants
# ----------------------------------------------------------------------------

def _global_edge_keys(a, p):
    """Edge multiset of part p in GLOBAL ids: inner endpoints through the
    (permuted) global_nid, halo sources by their (untouched) slot id, the
    pad_inner trash row as -1. Sorted => order-free comparison."""
    gn = a.global_nid[p].astype(np.int64)
    s = a.src[p].astype(np.int64)
    d = a.dst[p].astype(np.int64)
    gs = np.where(s < a.pad_inner, gn[np.minimum(s, a.pad_inner - 1)],
                  10**9 + s)
    gd = np.where(d < a.pad_inner, gn[np.minimum(d, a.pad_inner - 1)], -1)
    return np.sort((gs + 2) * np.int64(10**10) + (gd + 2))


def test_apply_reorder_invariants(ro4):
    g, art, art_ro, orders, _ = ro4
    P = art.feat.shape[0]
    for p in range(P):
        n_i = int(art.n_inner[p])
        # bijection on the inner rows, identity on padding rows
        assert np.array_equal(np.sort(orders[p][:n_i]), np.arange(n_i))
        assert np.array_equal(orders[p][n_i:],
                              np.arange(n_i, art.pad_inner))
    # geometry unchanged: shapes, pads, boundary counts, degree multisets
    assert art_ro.pad_inner == art.pad_inner
    assert art_ro.pad_boundary == art.pad_boundary
    assert np.array_equal(art_ro.n_b, art.n_b)
    assert np.array_equal(art_ro.n_inner, art.n_inner)
    for p in range(P):
        assert np.array_equal(np.sort(art_ro.in_deg[p]),
                              np.sort(art.in_deg[p]))
        # every (node, label) pair travels with its row
        a = dict(zip(art.global_nid[p][art.inner_mask[p]].tolist(),
                     art.label[p][art.inner_mask[p]].tolist()))
        b = dict(zip(art_ro.global_nid[p][art_ro.inner_mask[p]].tolist(),
                     art_ro.label[p][art_ro.inner_mask[p]].tolist()))
        assert a == b
        # the edge multiset in global ids is exactly preserved
        assert np.array_equal(_global_edge_keys(art, p),
                              _global_edge_keys(art_ro, p))
    # multi-host partial loads must be refused, not silently half-permuted
    partial = dataclasses.replace(art, feat=art.feat[:1])
    with pytest.raises(ValueError, match="full artifacts"):
        apply_reorder(partial, orders[:1])


# ----------------------------------------------------------------------------
# permutation disk cache + layout-cache key audit
# ----------------------------------------------------------------------------

def test_reorder_cache_memoizes_and_keys_on_tile(ro4, tmp_path):
    _, art, _, _, _ = ro4
    cfg = Config(reorder="cluster", cache_dir=str(tmp_path),
                 graph_name="rotest")
    p512 = reorder_cache_path(cfg, art, 512)
    p256 = reorder_cache_path(cfg, art, 256)
    assert p512 != p256, "t256 and t512 orders must never alias"
    assert REORDER_ALGO in p512 and p512.endswith("_t512.pkl")
    assert reorder_cache_path(cfg.replace(cache_dir=""), art, 512) is None

    quiet = lambda *a: None                                   # noqa: E731
    a1, r1, i1 = maybe_reorder(cfg, art, log=quiet)
    assert r1 == "cluster" and i1["cached"] is False
    assert os.path.exists(p512)
    a2, _, i2 = maybe_reorder(cfg, art, log=quiet)
    assert i2["cached"] is True
    np.testing.assert_array_equal(a1.global_nid, a2.global_nid)
    np.testing.assert_array_equal(a1.dst, a2.dst)
    # a stale (wrong-shape) cached order is rebuilt, never half-applied
    from bnsgcn_tpu.utils.diskcache import atomic_dump
    atomic_dump(np.zeros((2, 3), np.int64), p512)
    a3, _, i3 = maybe_reorder(cfg, art, log=quiet)
    assert i3["cached"] is False
    np.testing.assert_array_equal(a3.global_nid, a1.global_nid)
    # off is the untouched pre-PR pipeline: same object, no work, no event
    a4, r4, i4 = maybe_reorder(cfg.replace(reorder="off"), art, log=quiet)
    assert a4 is art and r4 == "off" and i4 == {}


def test_layout_keys_never_alias():
    """The satellite key audit: every (tile, overlap, reorder) combination
    gets its own hybrid/ell/gat layout-cache key — a t256 layout can never
    be served a t512 pickle, nor a reordered build a raw one."""
    keys, n = set(), 0
    for tile in (512, 256):
        for overlap in ("off", "split"):
            for ro in ("off", "cluster"):
                keys.add(hybrid_layout_key(Config(
                    block_tile=tile, overlap=overlap, reorder=ro)))
                n += 1
    for overlap in ("off", "split"):
        for ro in ("off", "cluster"):
            keys.add(ell_layout_key(Config(overlap=overlap, reorder=ro)))
            n += 1
    for ro in ("off", "cluster"):
        keys.add(gat_layout_key(Config(reorder=ro)))
        n += 1
    assert len(keys) == n, sorted(keys)
    # auto occupancy and its resolved explicit value still share one entry
    occ = effective_occupancy(0, 512, 512)
    assert (hybrid_layout_key(Config(block_occupancy=0))
            == hybrid_layout_key(Config(block_occupancy=occ)))


# ----------------------------------------------------------------------------
# coverage really rises: scrambled community SBM
# ----------------------------------------------------------------------------

@pytest.fixture(scope="module")
def scrambled_sbm():
    """32-community SBM whose node ids were randomly relabeled — the
    worst case the reorder pass exists for: structure present, order
    destroyed (identity t256 coverage ~18%)."""
    gs = sbm_graph(n_nodes=8192, n_class=32, n_feat=8, p_in=0.008,
                   p_out=0.0001, seed=3)
    rng = np.random.default_rng(0)
    perm = rng.permutation(gs.n_nodes)
    inv = np.argsort(perm)
    g2 = Graph(gs.n_nodes, perm[gs.src], perm[gs.dst], gs.feat[inv],
               gs.label[inv], gs.train_mask[inv], gs.val_mask[inv],
               gs.test_mask[inv])
    return build_artifacts(g2, partition_graph(g2, 1, method="random",
                                               seed=0))


def test_reorder_recovers_scrambled_communities(scrambled_sbm):
    art = scrambled_sbm
    occ = effective_occupancy(0, 256, 256)
    budget = 2048 << 20
    before = artifact_coverage(art, occ, budget, 256)
    art_ro = apply_reorder(art, compute_orders(art, tile_r=256))
    after = artifact_coverage(art_ro, occ, budget, 256)
    # measured 0.18 -> 0.45; pin a generous floor, not the exact number
    assert after >= before + 0.15, (before, after)


def test_auto_declines_when_ldg_baseline_wins(scrambled_sbm):
    """auto's baseline is what --reorder off ACTUALLY builds with — the
    hybrid's per-build LDG cluster_order — not the raw load order. On the
    scrambled SBM the LDG recovers the communities better than the LPA
    pass (measured 0.59 vs 0.45), so auto must keep the off path."""
    cfg = Config(reorder="auto", block_tile=256)
    art2, resolved, info = maybe_reorder(cfg, scrambled_sbm,
                                         log=lambda *a: None)
    assert resolved == "off"
    assert info["coverage_after"] <= info["coverage_before"]
    assert art2 is scrambled_sbm
    # cluster mode applies unconditionally — the A/B lever stays available
    art3, r3, _ = maybe_reorder(cfg.replace(reorder="cluster"),
                                scrambled_sbm, log=lambda *a: None)
    assert r3 == "cluster" and art3 is not scrambled_sbm


def test_auto_applies_in_the_skew_only_regime():
    """Structure-free power-law (the uniform bench regime, where LDG
    scrambles the one exploitable signal — popularity skew): auto applies
    (measured t256 coverage 0.50 LDG -> 0.56 reorder at this size)."""
    g = synthetic_graph(n_nodes=8192, avg_degree=12, n_feat=8, seed=7,
                        power_law=True)
    art = build_artifacts(g, partition_graph(g, 1, method="random", seed=0))
    cfg = Config(reorder="auto", block_tile=256)
    art2, resolved, info = maybe_reorder(cfg, art, log=lambda *a: None)
    assert resolved == "cluster"
    assert info["coverage_after"] > info["coverage_before"]
    assert art2 is not art


# ----------------------------------------------------------------------------
# layout fast paths: bitwise == the legacy np.unique/argsort builders
# ----------------------------------------------------------------------------

def _assert_same(a, b, path=""):
    if isinstance(a, np.ndarray):
        assert np.array_equal(a, np.asarray(b)), path
    elif isinstance(a, dict):
        assert sorted(a) == sorted(b), path
        for k in a:
            _assert_same(a[k], b[k], f"{path}.{k}")
    elif isinstance(a, (list, tuple)):
        assert len(a) == len(b), path
        for i, (x, y) in enumerate(zip(a, b)):
            _assert_same(x, y, f"{path}[{i}]")
    elif dataclasses.is_dataclass(a) and not isinstance(a, type):
        for f in dataclasses.fields(a):
            _assert_same(getattr(a, f.name), getattr(b, f.name),
                         f"{path}.{f.name}")
    else:
        assert a == b, path


def test_grouped_order_matches_stable_argsort(monkeypatch):
    from bnsgcn_tpu.ops.ell import grouped_order
    rng = np.random.default_rng(0)
    cases = [
        (np.zeros(0, np.int64), 4),
        (np.zeros(1, np.int64), 1),
        (rng.integers(0, 7, 5000).astype(np.int64), 7),       # heavy ties
        (np.repeat(np.arange(50), 100).astype(np.int64), 50),  # all runs
        (rng.permutation(4096).astype(np.int64), 4096),        # no ties
    ]
    for keys, n_keys in cases:
        monkeypatch.setenv("BNSGCN_LAYOUT_FASTPATH", "1")
        fast = grouped_order(keys, n_keys)
        monkeypatch.setenv("BNSGCN_LAYOUT_FASTPATH", "0")
        legacy = grouped_order(keys, n_keys)
        np.testing.assert_array_equal(fast, legacy)
        np.testing.assert_array_equal(legacy,
                                      np.argsort(keys, kind="stable"))


def test_fastpath_builders_bitwise_equal_legacy(ro4, monkeypatch):
    """All three layout families (pure ELL, split ELL, hybrid) + the
    coverage estimator, built on raw AND reordered artifacts, with the
    fast paths on vs. the legacy passes: every array bitwise equal."""
    from bnsgcn_tpu.ops import block_spmm as bs
    from bnsgcn_tpu.ops import ell as ell_mod
    _, art, art_ro, _, _ = ro4
    P = art.src.shape[0]
    results = {}
    for env in ("1", "0"):
        monkeypatch.setenv("BNSGCN_LAYOUT_FASTPATH", env)
        for name, a in (("raw", art), ("ro", art_ro)):
            pi = np.stack([bs.cluster_order(a.src[p], a.dst[p], a.pad_inner,
                                            a.n_ext)[0] for p in range(P)])
            pe = np.concatenate(
                [pi, np.tile(np.arange(a.pad_inner, a.n_ext), (P, 1))],
                axis=1)
            results[env, name, "ell"] = ell_mod.build_layouts(
                a.src, a.dst, a.pad_inner, a.n_ext)
            results[env, name, "split"] = ell_mod.build_split_layouts(
                a.src, a.dst, a.pad_inner, a.n_ext)
            results[env, name, "hyb"] = bs.build_block_layouts(
                a.src, a.dst, a.pad_inner, a.n_ext, pi, pe,
                occupancy_min=16, tile_r=64, tile_c=64)
            real = a.dst[0] < a.pad_inner
            results[env, name, "cov"] = bs.estimate_coverage(
                pi[0], pe[0], a.pad_inner, a.n_ext, a.dst[0][real],
                a.src[0][real], occupancy_min=16,
                tile_budget_bytes=2048 << 20, tile_r=64, tile_c=64)
    for name in ("raw", "ro"):
        for fam in ("ell", "split", "hyb", "cov"):
            _assert_same(results["1", name, fam], results["0", name, fam],
                         f"{name}/{fam}")


# ----------------------------------------------------------------------------
# e2e through the real CLI
# ----------------------------------------------------------------------------

E2E_ARGS = [
    "--dataset", "sbm", "--partition-method", "random", "--n-partitions",
    "2", "--model", "graphsage", "--n-layers", "2", "--n-hidden", "8",
    "--sampling-rate", "1.0", "--n-epochs", "6", "--log-every", "2",
    "--no-eval", "--no-comm-trace", "--fix-seed", "--seed", "11",
]


def _run_main(tmp_path, extra=()):
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               PYTHONPATH=REPO)
    cmd = ([sys.executable, "-m", "bnsgcn_tpu.main"] + E2E_ARGS
           + ["--part-path", str(tmp_path / "parts"),
              "--results-path", str(tmp_path / "res")] + list(extra))
    return subprocess.run(cmd, capture_output=True, text=True, timeout=300,
                          cwd=REPO, env=env)


def _final_loss(out: str) -> str:
    m = re.search(r"RESULT final_loss=(\S+)", out)
    assert m, f"no RESULT line in output:\n{out[-2000:]}"
    return m.group(1)       # string compare == bitwise pin


@pytest.mark.quickgate
def test_e2e_cluster_run_header_and_obs(tmp_path):
    """`--reorder cluster --halo-refresh 2` through the real CLI: the run
    header carries the resolved mode and the '+ro' halo label, and the obs
    log carries the reorder lifecycle event plus per-stage layout_build
    timings (the satellite obs plumbing, end to end)."""
    log = str(tmp_path / "obs.jsonl")
    r = _run_main(tmp_path, ["--reorder", "cluster", "--halo-refresh", "2",
                             "--obs-log", log])
    assert r.returncode == 0, r.stdout + r.stderr
    assert re.search(r"reorder: cluster -> cluster \[lpa-ffd, t512\]",
                     r.stdout), r.stdout[-3000:]
    assert "+ro" in r.stdout            # halo label, e.g. padded+hr2+ro

    from bnsgcn_tpu.obs import load_events
    evs = load_events(log)
    hdr = [e for e in evs if e["kind"] == "run_header"]
    assert hdr and hdr[0]["config"]["reorder"] == "cluster"
    assert "+ro" in hdr[0]["halo"]
    ro = [e for e in evs if e["kind"] == "reorder"]
    assert len(ro) == 1 and ro[0]["resolved"] == "cluster"
    assert ro[0]["algorithm"] == REORDER_ALGO and ro[0]["tile"] == 512
    lb = [e for e in evs if e["kind"] == "layout_build"]
    assert lb and all("stage" in e and e["ms"] >= 0 for e in lb)


def test_e2e_default_is_bitwise_reorder_off(tmp_path):
    """--reorder off is the pre-PR pipeline, pinned bitwise: an untouched
    default run and an explicit `--reorder off` run produce the same final
    loss string, and neither prints a reorder line."""
    a = _run_main(tmp_path)
    assert a.returncode == 0, a.stdout + a.stderr
    b = _run_main(tmp_path, ["--reorder", "off"])
    assert b.returncode == 0, b.stdout + b.stderr
    assert _final_loss(a.stdout) == _final_loss(b.stdout)
    assert "reorder:" not in a.stdout and "reorder:" not in b.stdout
