"""Distributed-runtime correctness on a virtual CPU mesh (SURVEY §4):

  * P=4, rate=1.0 training forward/loss/step ≡ P=1 (the reference's own
    exactness ground truth: sampling_rate 1 == exact full-graph training);
  * BNS unbiasedness: E[sampled halo aggregation] == full aggregation;
  * presence mask semantics for GAT.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bnsgcn_tpu.config import Config
from bnsgcn_tpu.data.artifacts import build_artifacts
from bnsgcn_tpu.data.graph import sbm_graph, synthetic_graph
from bnsgcn_tpu.data.partitioner import partition_graph
from bnsgcn_tpu.models.gnn import ModelSpec, init_params
from bnsgcn_tpu.parallel.halo import halo_apply, make_halo_plan, make_halo_spec
from jax import shard_map
from bnsgcn_tpu.parallel.mesh import make_parts_mesh
from bnsgcn_tpu.ops.spmm import agg_sum
from bnsgcn_tpu.trainer import (build_block_arrays, build_step_fns, init_training,
                                place_blocks, place_replicated)
from jax.sharding import PartitionSpec as P


def _setup(g, n_parts, cfg, spec, mesh, rate=None):
    pid = partition_graph(g, n_parts, method="random", seed=3)
    art = build_artifacts(g, pid)
    fns, hspec, tables, tables_full = build_step_fns(cfg, spec, art, mesh, rate=rate)
    blk_np = build_block_arrays(art, spec.model)
    blk_np.update(fns.extra_blk)
    blk = place_blocks(blk_np, mesh)
    tables = place_replicated(tables, mesh)
    tables_full = place_replicated(tables_full, mesh)
    if spec.use_pp:
        out = fns.precompute(blk, tables_full)
        if spec.model == "gat":
            blk["feat0_ext"] = out
        else:
            blk["feat"] = out
    return art, fns, blk, tables


def _gather_logits(art, logits):
    """[P, pad_inner, C] device logits -> [N, C] global order."""
    logits = np.asarray(logits)
    n_class = logits.shape[-1]
    n = int(art.n_inner.sum())
    out = np.zeros((n, n_class), dtype=logits.dtype)
    for p in range(art.n_parts):
        ids = art.global_nid[p][art.inner_mask[p]]
        out[ids] = logits[p][art.inner_mask[p]]
    return out


MODELS = [
    ("gcn", False, "layer"),
    ("gcn", True, "layer"),
    ("graphsage", False, "layer"),
    ("graphsage", True, "layer"),
    ("graphsage", False, "batch"),
    ("gat", True, "layer"),
]


@pytest.mark.parametrize("model,use_pp,norm", MODELS)
def test_p4_rate1_forward_equals_p1(model, use_pp, norm):
    g = synthetic_graph(n_nodes=90, avg_degree=6, n_feat=6, n_class=4, seed=31)
    cfg = Config(model=model, dropout=0.0, use_pp=use_pp, norm=norm,
                 n_train=g.n_train, lr=0.01, sampling_rate=1.0)
    spec = ModelSpec(model, (6, 8, 4), norm=norm, dropout=0.0, use_pp=use_pp,
                     train_size=g.n_train, heads=2 if model == "gat" else 1)
    params, state = init_params(jax.random.key(7), spec)

    mesh4 = make_parts_mesh(4)
    mesh1 = make_parts_mesh(1)
    key = jax.random.key(0)
    ep = jnp.uint32(0)

    art4, fns4, blk4, tb4 = _setup(g, 4, cfg, spec, mesh4)
    art1, fns1, blk1, tb1 = _setup(g, 1, cfg, spec, mesh1)
    p4 = place_replicated(params, mesh4)
    s4 = place_replicated(state, mesh4)
    p1 = place_replicated(params, mesh1)
    s1 = place_replicated(state, mesh1)

    l4 = _gather_logits(art4, fns4.forward(p4, s4, ep, blk4, tb4, key))
    l1 = _gather_logits(art1, fns1.forward(p1, s1, ep, blk1, tb1, key))
    np.testing.assert_allclose(l4, l1, rtol=2e-4, atol=2e-4)


@pytest.mark.quickgate
@pytest.mark.parametrize("model,use_pp,halo",
                         [("gcn", True, "padded"), ("graphsage", True, "padded"),
                          ("graphsage", False, "padded"),
                          # rate-1.0 'ragged' must reproduce exact full-graph
                          # training like the padded path (ISSUE 1 acceptance)
                          ("graphsage", True, "ragged"),
                          ("graphsage", False, "ragged")])
def test_p4_rate1_train_step_equals_p1(model, use_pp, halo):
    g = synthetic_graph(n_nodes=80, avg_degree=5, n_feat=5, n_class=3, seed=32)
    cfg = Config(model=model, dropout=0.0, use_pp=use_pp, norm="layer",
                 n_train=g.n_train, lr=0.01, sampling_rate=1.0,
                 halo_exchange=halo)
    spec = ModelSpec(model, (5, 8, 3), norm="layer", dropout=0.0, use_pp=use_pp,
                     train_size=g.n_train)
    params, state = init_params(jax.random.key(9), spec)
    # host copies: train_step donates its inputs, so place fresh per mesh
    params_np = jax.tree.map(np.asarray, params)
    state_np = jax.tree.map(np.asarray, state)
    key = jax.random.key(0)
    dkey = jax.random.key(1)

    results = {}
    for np_, meshn in [(4, make_parts_mesh(4)), (1, make_parts_mesh(1))]:
        art, fns, blk, tb = _setup(g, np_, cfg, spec, meshn)
        pp = place_replicated(params_np, meshn)
        ss = place_replicated(state_np, meshn)
        _, _, opt = init_training(cfg, spec, meshn)
        losses = []
        for e in range(3):
            pp, ss, opt, loss = fns.train_step(pp, ss, opt, jnp.uint32(e), blk, tb, key, dkey)
            losses.append(float(loss))
        results[np_] = (losses, jax.tree.map(np.asarray, jax.device_get(pp)))

    np.testing.assert_allclose(results[4][0], results[1][0], rtol=1e-4, atol=1e-5)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(a, b, rtol=5e-4, atol=1e-5),
                 results[4][1], results[1][1])


@pytest.mark.quickgate
def test_bns_unbiasedness():
    """E over epochs of (sampled, 1/ratio-scaled) halo aggregation equals the
    full-rate aggregation (SURVEY §4: unbiasedness of BNS)."""
    g = synthetic_graph(n_nodes=60, avg_degree=6, n_feat=4, seed=33)
    pid = partition_graph(g, 4, method="random", seed=5)
    art = build_artifacts(g, pid)
    mesh = make_parts_mesh(4)

    hspec, tables = make_halo_spec(art.n_b, art.pad_inner, art.pad_boundary, 0.5)
    hfull, tfull = make_halo_spec(art.n_b, art.pad_inner, art.pad_boundary, 1.0)
    blk = place_blocks({"feat": art.feat.astype(np.float32),
                        "bnd": art.bnd, "src": art.src, "dst": art.dst}, mesh)
    base = jax.random.key(42)

    def make_agg(spec):
        def local(blk, tables, epoch):
            b = {k: v[0] for k, v in blk.items()}
            plan = make_halo_plan(spec, tables, b["bnd"], epoch, base)
            hx = halo_apply(spec, plan, b["feat"])
            return agg_sum(hx, b["src"], b["dst"], spec.pad_inner)[None]
        return jax.jit(shard_map(
            local, mesh=mesh, in_specs=(P("parts"), P(), P()),
            out_specs=P("parts")))

    full = np.asarray(make_agg(hfull)(blk, place_replicated(tfull, mesh), jnp.uint32(0)))
    n_ep = 300
    acc = np.zeros_like(full)
    tb = place_replicated(tables, mesh)
    agg = make_agg(hspec)
    for e in range(n_ep):
        acc += np.asarray(agg(blk, tb, jnp.uint32(e)))
    mean = acc / n_ep
    # inner-edge contribution is identical; compare totals with MC tolerance
    err = np.abs(mean - full)
    scale = np.abs(full).mean() + 1e-6
    assert err.mean() / scale < 0.05, f"biased? mean rel err {err.mean() / scale}"


def test_sampling_rate_reduces_payload_not_shapes():
    g = synthetic_graph(n_nodes=60, avg_degree=6, n_feat=4, seed=34)
    pid = partition_graph(g, 4, method="random", seed=5)
    art = build_artifacts(g, pid)
    h_low, t_low = make_halo_spec(art.n_b, art.pad_inner, art.pad_boundary, 0.1)
    h_hi, t_hi = make_halo_spec(art.n_b, art.pad_inner, art.pad_boundary, 1.0)
    assert h_low.pad_send <= h_hi.pad_send
    ss_low = np.asarray(t_low["send_size"])
    nb = np.asarray(t_low["n_b"])
    assert np.all(ss_low == (0.1 * nb).astype(np.int64))


def test_training_improves_accuracy_sbm():
    """End-to-end: distributed BNS training on an SBM graph learns (accuracy
    over 60 epochs clearly above chance)."""
    g = sbm_graph(n_nodes=240, n_class=4, n_feat=8, p_in=0.08, p_out=0.004, seed=35)
    cfg = Config(model="graphsage", dropout=0.1, use_pp=True, norm="layer",
                 n_train=g.n_train, lr=0.01, sampling_rate=0.5)
    spec = ModelSpec("graphsage", (8, 16, 4), norm="layer", dropout=0.1,
                     use_pp=True, train_size=g.n_train)
    mesh = make_parts_mesh(4)
    art, fns, blk, tb = _setup(g, 4, cfg, spec, mesh)
    params, state = init_params(jax.random.key(11), spec)
    params = place_replicated(params, mesh)
    state = place_replicated(state, mesh)
    _, _, opt = init_training(cfg, spec, mesh)
    key, dkey = jax.random.key(0), jax.random.key(1)
    first = None
    for e in range(60):
        params, state, opt, loss = fns.train_step(
            params, state, opt, jnp.uint32(e), blk, tb, key, dkey)
        if first is None:
            first = float(loss)
    assert float(loss) < first * 0.7, (first, float(loss))
    logits = _gather_logits(art, fns.forward(params, state, jnp.uint32(0), blk, tb, key))
    acc = float((logits.argmax(1) == g.label)[g.train_mask].mean())
    assert acc > 0.6, acc


def test_remat_matches_no_remat():
    """jax.checkpoint per layer changes memory, not math: losses and updated
    params identical with and without --remat."""
    g = synthetic_graph(n_nodes=80, avg_degree=5, n_feat=5, n_class=3, seed=90)
    spec = ModelSpec("graphsage", (5, 8, 8, 3), norm="layer", dropout=0.2,
                     use_pp=True, train_size=g.n_train)
    params0, state0 = init_params(jax.random.key(9), spec)
    params_np = jax.tree.map(np.asarray, params0)
    mesh = make_parts_mesh(4)
    results = {}
    for remat in (False, True):
        cfg = Config(model="graphsage", dropout=0.2, use_pp=True, norm="layer",
                     n_train=g.n_train, lr=0.01, sampling_rate=0.5, remat=remat)
        art, fns, blk, tb = _setup(g, 4, cfg, spec, mesh)
        p = place_replicated(params_np, mesh)
        s = place_replicated(state0, mesh)
        _, _, opt = init_training(cfg, spec, mesh)
        for e in range(3):
            p, s, opt, loss = fns.train_step(p, s, opt, jnp.uint32(e), blk, tb,
                                             jax.random.key(0), jax.random.key(1))
        results[remat] = (float(loss), jax.tree.map(np.asarray, jax.device_get(p)))
    assert abs(results[True][0] - results[False][0]) < 1e-5
    jax.tree.map(lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6),
                 results[True][1], results[False][1])
