"""Staleness-bounded halo communication: --halo-refresh K + --halo-mode.

  * make_refresh_spec partitions every boundary list into K residue-class
    chunks whose counts sum back to the full tables, and its steady-state
    wire bytes drop ~K x under every strategy;
  * at K=1 the refresh plan applies bit-identically to the historical plan
    across the strategy x wire matrix (quantized wires within per-block
    scale tolerance — the send pad differs by the lane rounding the partial
    geometry deliberately drops);
  * at rate 1.0 the K staggered chunk exchanges, merged through
    refresh_row_mask, reconstruct the exact full exchange bitwise — the
    "staleness is the ONLY approximation" invariant;
  * the full-refresh train step is bitwise the historical step; the cached
    step's staleness bias at rate 1.0 stays within epsilon of the exact
    trajectory for K in {2, 4}; grad-only still learns the SBM task;
  * the CLI path: `+hrK` run label, run_header peak/steady wire MB,
    duty-cycled per-epoch wire_mb, halo_refresh lifecycle events, and
    bitwise-deterministic rollback (cache invalidation -> full-refresh
    epoch) and resume.

No reference equivalent: the reference (like BNS-GCN) exchanges halos every
epoch; bounded-staleness reuse is a capability upgrade for DCN-crossing
meshes where the per-epoch exchange dominates the step.
"""

import json
import os
import re
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from bnsgcn_tpu.config import Config, ConfigError, parse_config
from bnsgcn_tpu.data.artifacts import build_artifacts
from bnsgcn_tpu.data.graph import sbm_graph, synthetic_graph
from bnsgcn_tpu.data.partitioner import partition_graph
from bnsgcn_tpu.models.gnn import ModelSpec, init_params
from bnsgcn_tpu.parallel.halo import (halo_apply, make_halo_plan,
                                      make_halo_spec, make_halo_plan_refresh,
                                      make_refresh_spec, refresh_row_mask,
                                      wire_bytes)
from jax import shard_map
from bnsgcn_tpu.parallel.mesh import make_parts_mesh
from bnsgcn_tpu.trainer import (build_block_arrays, build_step_fns,
                                init_training, place_blocks, place_replicated)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ----------------------------------------------------------------------------
# geometry units: chunk tables and steady-state bytes
# ----------------------------------------------------------------------------

def _skew_nb():
    rng = np.random.default_rng(7)
    n_b = rng.integers(100, 400, size=(4, 4)).astype(np.int64)
    np.fill_diagonal(n_b, 0)
    return n_b


@pytest.mark.quickgate
def test_refresh_spec_chunk_counts_and_steady_bytes():
    """Per-chunk boundary counts must sum back to the full counts (every
    boundary position lives in exactly one chunk), sends stay nonzero
    wherever the full exchange sends (no permanently-silent pair = no bias),
    and the steady-state wire bytes drop ~K x under every strategy — the
    >= 40% @ K=2 acceptance bar of the PR."""
    n_b = _skew_nb()
    for strategy in ("padded", "shift", "ragged"):
        sp_full, tb_full = make_halo_spec(n_b, 0, 512, 0.5, strategy=strategy)
        full_bytes = wire_bytes(sp_full, 64, 2)
        for K, cap in ((2, 0.60), (4, 0.35)):
            sp_r, tb_r = make_refresh_spec(n_b, 0, 512, 0.5, K,
                                           strategy=strategy)
            nbc = np.asarray(tb_r["n_b"], np.int64)
            assert nbc.shape == (K, 4, 4)
            np.testing.assert_array_equal(nbc.sum(axis=0), n_b)
            s_c = np.asarray(tb_r["send_size"], np.int64)
            full_send = np.asarray(tb_full["send_size"], np.int64)
            # a pair the full exchange serves sends in EVERY chunk with rows
            assert np.all((s_c > 0) == ((nbc > 0) & (full_send[None] > 0)))
            assert sp_r.pad_boundary == sp_full.pad_boundary  # cache layout
            rb = wire_bytes(sp_r, 64, 2)
            assert rb <= cap * full_bytes, (strategy, K, rb, full_bytes)


def test_refresh_spec_exact_rate_sends_whole_chunk():
    n_b = _skew_nb()
    for K in (2, 3):
        _, tb = make_refresh_spec(n_b, 0, 512, 1.0, K)
        np.testing.assert_array_equal(np.asarray(tb["send_size"]),
                                      np.asarray(tb["n_b"]))


def test_refresh_row_mask_partitions_halo_slots():
    sp, _ = make_refresh_spec(_skew_nb(), 0, 512, 0.5, 3)
    masks = [np.asarray(refresh_row_mask(sp, 3, jnp.uint32(e)))
             for e in range(3)]
    assert not (masks[0] & masks[1]).any()          # pairwise disjoint
    assert np.all(masks[0] | masks[1] | masks[2])   # and exhaustive
    # period K: epoch e and e+K refresh the same slots
    np.testing.assert_array_equal(
        masks[1], np.asarray(refresh_row_mask(sp, 3, jnp.uint32(4))))


# ----------------------------------------------------------------------------
# plan equivalence on the real 4-part skewed partition
# ----------------------------------------------------------------------------

def _skewed_art():
    g = synthetic_graph(n_nodes=120, avg_degree=7, n_feat=6, seed=41,
                        power_law=True)
    pid = np.zeros(g.n_nodes, dtype=np.int32)
    pid[60:90] = 1
    pid[90:110] = 2
    pid[110:] = 3
    return build_artifacts(g, pid)


def _apply_plans(art, mesh, feat, make_plan_fns, epoch=3):
    """halo_apply each plan builder inside ONE shard_map; returns the list
    of (h_ext, d_feat) numpy pairs for a sum-of-squares cotangent."""
    base = jax.random.key(42)

    def local(blk, *tables_list):
        b = {k: v[0] for k, v in blk.items()}
        outs = []
        for mk, tb in zip(make_plan_fns, tables_list):
            plan = mk[1](mk[0], tb, b["bnd"], jnp.uint32(epoch), base)

            def loss_fn(h, spec=mk[0], plan=plan):
                hx = halo_apply(spec, plan, h)
                return jnp.sum(hx.astype(jnp.float32) ** 2), hx

            (_, hx), g = jax.value_and_grad(loss_fn, has_aux=True)(b["feat"])
            outs.extend([hx[None], g[None]])
        return tuple(outs)

    n = len(make_plan_fns)
    f = jax.jit(shard_map(local, mesh=mesh,
                          in_specs=(P("parts"),) + (P(),) * n,
                          out_specs=(P("parts"),) * (2 * n)))
    blk = place_blocks({"feat": feat, "bnd": art.bnd}, mesh)
    res = f(blk, *[place_replicated(tb, mesh) for _, _, tb in make_plan_fns])
    return [(np.asarray(res[2 * i]), np.asarray(res[2 * i + 1]))
            for i in range(n)]


@pytest.mark.parametrize("wire", ["native", "bf16", "int8", "fp8"])
@pytest.mark.parametrize("strategy", ["padded", "shift", "ragged"])
def test_k1_refresh_plan_matches_full_plan(strategy, wire):
    """K=1 has a single chunk covering every boundary position: the partial
    plan must reproduce the historical exchange. Native/bf16 wires are
    bitwise (positionwise codecs); int8/fp8 per-block scales see a
    differently-padded send block (the refresh geometry drops the x8 lane
    rounding), so they match within quantization tolerance."""
    art = _skewed_art()
    mesh = make_parts_mesh(4)
    feat = art.feat.astype(np.float32)
    sp_f, tb_f = make_halo_spec(art.n_b, art.pad_inner, art.pad_boundary,
                                0.5, strategy=strategy, wire=wire)
    sp_r, tb_r = make_refresh_spec(art.n_b, art.pad_inner, art.pad_boundary,
                                   0.5, 1, strategy=strategy, wire=wire)

    def plan_r(spec, tb, bnd, epoch, key):
        return make_halo_plan_refresh(spec, tb, bnd, epoch, key, 1)

    (hx_f, g_f), (hx_r, g_r) = _apply_plans(
        art, mesh, feat,
        [(sp_f, make_halo_plan, tb_f), (sp_r, plan_r, tb_r)])
    if wire in ("native", "bf16"):
        np.testing.assert_array_equal(hx_r, hx_f)
        np.testing.assert_array_equal(g_r, g_f)
    else:
        scale = np.abs(hx_f).max() + 1e-9
        assert np.abs(hx_r - hx_f).max() / scale < 0.05, (strategy, wire)
        gscale = np.abs(g_f).max() + 1e-9
        assert np.abs(g_r - g_f).max() / gscale < 0.05, (strategy, wire)


@pytest.mark.quickgate
def test_staggered_chunks_reconstruct_exact_exchange():
    """rate 1.0, K=3: running the partial exchange for epochs 0..K-1 and
    merging each result through its refresh_row_mask must reconstruct the
    full exact exchange bitwise — proof that a warm steady-state cache
    differs from per-epoch exchange ONLY through staleness."""
    art = _skewed_art()
    mesh = make_parts_mesh(4)
    feat = art.feat.astype(np.float32)
    K = 3
    sp_f, tb_f = make_halo_spec(art.n_b, art.pad_inner, art.pad_boundary, 1.0)
    sp_r, tb_r = make_refresh_spec(art.n_b, art.pad_inner, art.pad_boundary,
                                   1.0, K)
    base = jax.random.key(42)

    def local(blk, tb_f, tb_r):
        b = {k: v[0] for k, v in blk.items()}
        plan_f = make_halo_plan(sp_f, tb_f, b["bnd"], jnp.uint32(0), base)
        full_tail = halo_apply(sp_f, plan_f, b["feat"])[sp_f.pad_inner:]
        merged = jnp.zeros_like(full_tail)
        for e in range(K):
            plan_e = make_halo_plan_refresh(sp_r, tb_r, b["bnd"],
                                            jnp.uint32(e), base, K)
            tail_e = halo_apply(sp_r, plan_e, b["feat"])[sp_r.pad_inner:]
            mask = refresh_row_mask(sp_r, K, jnp.uint32(e))
            merged = jnp.where(mask[:, None], tail_e, merged)
        return full_tail[None], merged[None]

    f = jax.jit(shard_map(local, mesh=mesh, in_specs=(P("parts"), P(), P()),
                          out_specs=(P("parts"), P("parts"))))
    blk = place_blocks({"feat": feat, "bnd": art.bnd}, mesh)
    full_tail, merged = f(blk, place_replicated(tb_f, mesh),
                          place_replicated(tb_r, mesh))
    np.testing.assert_array_equal(np.asarray(merged), np.asarray(full_tail))


# ----------------------------------------------------------------------------
# train-step level: full-refresh bitwise, staleness bias bound, grad-only
# ----------------------------------------------------------------------------

def _train(g, epochs, force_full_each_epoch=False, n_parts=4, **cfg_kw):
    """run.py's step dispatch in miniature: full-refresh step when the cache
    is cold, cached step after. Returns the per-epoch loss trajectory."""
    kw = dict(model="graphsage", dropout=0.0, use_pp=True, norm="layer",
              n_train=g.n_train, lr=0.01, sampling_rate=0.5)
    kw.update(cfg_kw)
    cfg = Config(**kw)
    spec = ModelSpec("graphsage", (8, 16, 4), norm="layer", dropout=0.0,
                     use_pp=True, train_size=g.n_train)
    mesh = make_parts_mesh(n_parts)
    art = build_artifacts(g, partition_graph(g, n_parts, method="random",
                                             seed=2))
    fns, hspec, tables, tables_full = build_step_fns(cfg, spec, art, mesh)
    blk_np = build_block_arrays(art, "graphsage")
    blk_np.update(fns.extra_blk)
    for k in fns.drop_blk_keys:
        blk_np.pop(k, None)
    blk = place_blocks(blk_np, mesh)
    tb = place_replicated(tables, mesh)
    blk["feat"] = fns.precompute(blk, place_replicated(tables_full, mesh))
    params, state = init_params(jax.random.key(5), spec)
    params = place_replicated(params, mesh)
    state = place_replicated(state, mesh)
    _, _, opt = init_training(cfg, spec, mesh)
    tb_r = (place_replicated(fns.tables_refresh, mesh)
            if fns.tables_refresh is not None else None)
    cache, traj = None, []
    for e in range(epochs):
        if fns.train_step_full is not None:
            if cache is None or force_full_each_epoch:
                params, state, opt, loss, cache = fns.train_step_full(
                    params, state, opt, jnp.uint32(e), blk, tb,
                    jax.random.key(0), jax.random.key(1))
            else:
                params, state, opt, loss, cache = fns.train_step_cached(
                    params, state, opt, jnp.uint32(e), blk, tb_r, cache,
                    jax.random.key(0), jax.random.key(1))
        else:
            params, state, opt, loss = fns.train_step(
                params, state, opt, jnp.uint32(e), blk, tb,
                jax.random.key(0), jax.random.key(1))
        traj.append(float(loss))
    return traj


@pytest.fixture(scope="module")
def sbm4():
    return sbm_graph(n_nodes=240, n_class=4, n_feat=8, p_in=0.08,
                     p_out=0.004, seed=44)


@pytest.mark.quickgate
def test_full_refresh_step_is_bitwise_the_historical_step(sbm4):
    """train_step_full replays the historical exchange geometry (it only
    ADDS cache recording): forced full-refresh every epoch must trace the
    exact historical trajectory bitwise."""
    ref = _train(sbm4, 5)
    full = _train(sbm4, 5, halo_refresh=2, force_full_each_epoch=True)
    assert full == ref, (ref, full)


def test_staleness_bias_bounded_at_exact_rate(sbm4):
    """rate 1.0: staleness is the ONLY approximation K introduces (pinned
    bitwise above/in the merge test), so the K in {2, 4} trajectories must
    land within a small epsilon of the exact run — the PR's stated
    accuracy-within-epsilon acceptance criterion, on the loss it trains."""
    exact = _train(sbm4, 40, sampling_rate=1.0)
    eps = 0.05 * abs(exact[0])
    for K in (2, 4):
        stale = _train(sbm4, 40, sampling_rate=1.0, halo_refresh=K)
        assert stale[-1] < 0.5 * stale[0], f"K={K} did not learn"
        assert abs(stale[-1] - exact[-1]) < eps, (K, exact[-1], stale[-1])


def test_grad_only_converges(sbm4):
    """--halo-mode grad-only drops the activation exchange entirely; the
    gradient all-reduce (the loss psum transpose) alone must still learn
    the SBM task, if to a worse loss than the exchanging run."""
    traj = _train(sbm4, 40, halo_mode="grad-only")
    assert traj[-1] < 0.5 * traj[0], traj[-1]


@pytest.mark.parametrize("overlap", ["off", "split"])
def test_narrow_side_through_the_halo_cache(monkeypatch, sbm4, overlap):
    """The 16 -> 4 layer projects the rows the cached step merges (stored
    halo rows and the fresh chunk alike) after the exchange: at P=2, rate
    0.1, K=2, the trajectory is the wide order's."""
    from bnsgcn_tpu.models import gnn
    kw = dict(n_parts=2, sampling_rate=0.1, halo_refresh=2, overlap=overlap)
    narrow = _train(sbm4, 4, **kw)
    with monkeypatch.context() as m:
        m.setattr(gnn, "projects_first", lambda spec, i: False)
        wide = _train(sbm4, 4, **kw)
    assert np.allclose(narrow, wide, rtol=1e-5, atol=0), (narrow, wide)


# ----------------------------------------------------------------------------
# flags + StepFns surface
# ----------------------------------------------------------------------------

def test_config_flags_and_step_fns_surface(sbm4):
    cfg = parse_config(["--halo-refresh", "4", "--halo-mode", "grad-only"])
    assert cfg.halo_refresh == 4 and cfg.halo_mode == "grad-only"
    assert parse_config([]).halo_refresh == 1
    assert parse_config([]).halo_mode == "exchange"

    g = sbm4
    spec = ModelSpec("graphsage", (8, 16, 4), norm="layer", dropout=0.0,
                     use_pp=True, train_size=g.n_train)
    art = build_artifacts(g, partition_graph(g, 4, method="random", seed=2))
    mesh = make_parts_mesh(4)

    def build(**kw):
        c = Config(model="graphsage", dropout=0.0, use_pp=True, norm="layer",
                   n_train=g.n_train, sampling_rate=0.5, **kw)
        return build_step_fns(c, spec, art, mesh)[0]

    with pytest.raises(ConfigError, match="halo-refresh"):
        build(halo_refresh=0)
    with pytest.raises(ConfigError, match="halo-mode"):
        build(halo_mode="nope")
    assert build().train_step_full is None              # K=1: nothing built
    fns = build(halo_refresh=2)
    assert fns.train_step_full is not None
    assert fns.train_step_cached is not None
    assert fns.tables_refresh is not None and fns.halo_refresh == 2
    # grad-only ignores the refresh period (warned): no refresh machinery
    fns = build(halo_refresh=4, halo_mode="grad-only")
    assert fns.halo_mode == "grad-only" and fns.train_step_full is None


# ----------------------------------------------------------------------------
# e2e through the CLI: label, header, duty-cycled wire_mb, determinism
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


def _run(tmp_path, extra_args=(), timeout=240, base=BASE_ARGS):
    cmd = ([sys.executable, "-m", "bnsgcn_tpu.main"] + list(base)
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


@pytest.mark.quickgate
def test_cli_e2e_header_label_and_duty_cycled_wire(tmp_path):
    """--halo-refresh 2 end to end: the run labels itself +hr2, the header
    carries both peak and steady-state MB (steady <= 60% of peak — the
    >= 40% acceptance bar), a halo_refresh lifecycle event marks the cold
    full-refresh epoch, and every steady epoch's wire_mb record ships the
    reduced figure."""
    log = str(tmp_path / "obs.jsonl")
    # default --comm-trace, long enough for the post-warm-up window to open
    # and close: a K>1 run launches train_step_full/_cached, and the window
    # must attribute them (it is fatal when it cannot)
    args = [a for a in BASE_ARGS if a != "--no-comm-trace"]
    args[args.index("--n-epochs") + 1] = "12"
    r = _run(tmp_path, ["--halo-refresh", "2", "--obs-log", log], base=args)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "+hr2" in r.stdout, r.stdout[-3000:]
    assert "[traced]" in r.stdout, r.stdout[-3000:]
    assert "halo cache: full refresh at epoch 0 (start)" in r.stdout
    evs = _load_events(log)
    hdr = next(e for e in evs if e["kind"] == "run_header")
    assert hdr["halo_refresh"] == 2 and hdr["halo_mode"] == "exchange"
    tr = next(e for e in evs if e["kind"] == "trace")
    assert tr["exchanges"] is True and tr["comm_s"] > 0
    peak, steady = hdr["wire_mb_per_exchange"], hdr["wire_mb_steady"]
    assert steady <= 0.6 * peak, (steady, peak)
    assert any(e["kind"] == "halo_refresh" and e["reason"] == "start"
               for e in evs)
    ep = [e for e in evs if e["kind"] == "epoch"]
    assert ep, "no epoch records"
    # epoch 0 rebuilt the cache at peak cost; the rest ride the steady rate
    by_epoch = {int(e["epoch"]): e["wire_mb"] for e in ep}
    assert by_epoch[0] == pytest.approx(peak, rel=1e-3)
    for e, mb in by_epoch.items():
        if e > 0:
            assert mb <= 0.6 * peak, (e, mb, peak)
    # the report tool renders it (wire column + lifecycle line)
    rep = subprocess.run([sys.executable, "tools/obs_report.py", log],
                         capture_output=True, text=True, timeout=60,
                         cwd=REPO, env=_env())
    assert rep.returncode == 0, rep.stdout + rep.stderr
    assert "halo refresh: K=2" in rep.stdout
    assert "halo_refresh" in rep.stdout and "wire_mb" in rep.stdout


@pytest.mark.quickgate
def test_rollback_invalidates_cache_and_stays_deterministic(tmp_path):
    """nan@E5 under an active K=2 cache: the rollback must invalidate the
    cache (a full-refresh epoch replays at the restart point — the resumed
    state was saved WITHOUT the cache) and the whole recovery is
    deterministic: two identical runs land bitwise-equal final losses."""
    losses = []
    for i in (0, 1):
        log = str(tmp_path / f"obs{i}.jsonl")
        r = _run(tmp_path, ["--halo-refresh", "2", "--inject", "nan@E5",
                            "--ckpt-path", str(tmp_path / f"ck{i}"),
                            "--obs-log", log])
        assert r.returncode == 0, r.stdout + r.stderr
        assert "rolled back" in r.stdout or "rollback" in r.stdout.lower()
        kinds = [e["kind"] for e in _load_events(log)]
        assert "rollback" in kinds
        # two halo_refresh events: the cold start AND the post-rollback
        # invalidation
        ref = [e for e in _load_events(log) if e["kind"] == "halo_refresh"]
        assert {e["reason"] for e in ref} == {"start", "rollback"}, ref
        losses.append(_final_loss(r.stdout))
    assert losses[0] == losses[1], losses


@pytest.mark.slow
def test_resume_forces_full_refresh_and_is_deterministic(tmp_path):
    """sigterm@E3 under K=2, then --resume twice from copies of the same
    checkpoint: the cache is never checkpointed, so each resume must replay
    a full-refresh epoch (reason=resume) and the two resumed runs must land
    bitwise-identical final losses."""
    interrupted = _run(tmp_path, ["--halo-refresh", "2",
                                  "--inject", "sigterm@E3"])
    assert interrupted.returncode == 75, (
        interrupted.returncode, interrupted.stderr[-2000:])
    losses = []
    for i in (0, 1):
        ck = str(tmp_path / f"ck_resume{i}")
        shutil.copytree(str(tmp_path / "ckpt"), ck)
        r = _run(tmp_path, ["--halo-refresh", "2", "--resume",
                            "--skip-partition", "--ckpt-path", ck])
        assert r.returncode == 0, r.stdout + r.stderr
        assert "Resumed from" in r.stdout
        m = re.search(r"full refresh at epoch (\d+) \(resume\)", r.stdout)
        assert m, r.stdout[-3000:]
        losses.append(_final_loss(r.stdout))
    assert losses[0] == losses[1], losses
