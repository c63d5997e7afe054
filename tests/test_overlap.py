"""--overlap split exactness: interior/frontier split aggregation.

The tentpole invariant: splitting each layer's aggregation into an interior
part (rows with no halo in-neighbor — aggregated while the collective is in
flight) and a frontier part (rows needing the exchange), then recombining
through the merge permutation, is numerically identical (allclose, forward
AND backward) to the fused exchange-then-aggregate path for EVERY halo
strategy x wire codec combination, at rate 1.0 and a sampled rate, on the
8-device CPU mesh. Both paths send the exact same wire payloads (halo_apply
IS halo_start + halo_finish), so even quantized wires must agree to float
reassociation tolerance.

Also pinned: degenerate partitions (a part with zero interior rows, a part
with zero frontier rows, and the P=1 no-cross-edges case) build and train
identically to --overlap off.

Reference context: DistGNN (arXiv:2104.06700) overlaps remote-aggregate
communication with local aggregation; the reference BNS-GCN serializes
exchange-then-aggregate (train.py:256-281 after the buffer update).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from bnsgcn_tpu.config import Config
from bnsgcn_tpu.data.artifacts import build_artifacts
from bnsgcn_tpu.data.graph import Graph, synthetic_graph
from bnsgcn_tpu.data.partitioner import partition_graph
from bnsgcn_tpu.models.gnn import ModelSpec, init_params
from bnsgcn_tpu.ops.ell import build_layouts, build_split_layouts, make_ell_spmm
from bnsgcn_tpu.ops.spmm import frontier_mask
from bnsgcn_tpu.parallel.halo import (halo_apply, halo_finish, halo_start,
                                      make_halo_plan, make_halo_spec)
from jax import shard_map
from bnsgcn_tpu.parallel.mesh import make_parts_mesh
from bnsgcn_tpu.trainer import (build_block_arrays, build_step_fns,
                                init_training, place_blocks, place_replicated)


# ----------------------------------------------------------------------------
# seam-level matrix: halo_start/finish + split ELL layouts vs halo_apply +
# fused ELL layout, forward and grad, for every strategy x wire x rate
# ----------------------------------------------------------------------------

@pytest.fixture(scope="module")
def split8():
    """8-part skewed partition + fused and split ELL SpMMs over the same
    edges, shared across the matrix cases."""
    g = synthetic_graph(n_nodes=240, avg_degree=7, n_feat=6, seed=46,
                        power_law=True)
    sizes = [90, 50, 30, 20, 16, 14, 12, 8]
    pid = np.repeat(np.arange(8), sizes).astype(np.int32)
    art = build_artifacts(g, pid)
    mesh = make_parts_mesh(8)
    fwd, bwd, f_arrays = build_layouts(art.src, art.dst, art.pad_inner,
                                       art.n_ext)
    fused = make_ell_spmm(fwd, bwd, len(fwd.widths), len(bwd.widths))
    (i_f, i_b), (r_f, r_b), s_arrays, _, _ = build_split_layouts(
        art.src, art.dst, art.pad_inner, art.n_ext)
    int_spmm = make_ell_spmm(i_f, i_b, len(i_f.widths), len(i_b.widths))
    fro_spmm = make_ell_spmm(r_f, r_b, len(r_f.widths), len(r_b.widths))
    blk_np = {"feat": art.feat.astype(np.float32), "bnd": art.bnd}
    f_keys = tuple(f_arrays)
    s_keys = tuple(s_arrays)
    blk_np.update(f_arrays)
    blk_np.update(s_arrays)
    blk = place_blocks(blk_np, mesh)
    return art, mesh, blk, fused, (int_spmm, fro_spmm), f_keys, s_keys


@pytest.mark.parametrize("rate", [1.0, 0.5])
@pytest.mark.parametrize("wire", ["native", "bf16", "int8", "fp8"])
@pytest.mark.parametrize("strategy", ["padded", "shift", "ragged"])
def test_split_matches_fused_matrix(split8, strategy, wire, rate):
    art, mesh, blk, fused, (int_spmm, fro_spmm), f_keys, s_keys = split8
    hspec, tables = make_halo_spec(art.n_b, art.pad_inner, art.pad_boundary,
                                   rate, strategy=strategy, wire=wire)
    base = jax.random.key(42)

    def local(blk, tables):
        b = {k: v[0] for k, v in blk.items()}
        plan = make_halo_plan(hspec, tables, b["bnd"], jnp.uint32(3), base)
        a_fused = {k: b[k] for k in f_keys}
        a_int = {k[4:]: b[k] for k in s_keys if k.startswith("int_")}
        a_fro = {k[4:]: b[k] for k in s_keys if k.startswith("fro_")}

        def loss_fused(h):
            out = fused(a_fused, halo_apply(hspec, plan, h))
            return jnp.sum(out.astype(jnp.float32) ** 2), out

        def loss_split(h):
            recv = halo_start(hspec, plan, h)
            o_i = int_spmm(a_int, h)
            buf = halo_finish(hspec, plan, recv, h)
            o_f = fro_spmm(a_fro, jnp.concatenate([h, buf], 0))
            out = jnp.concatenate([o_i, o_f], 0)[b["merge_perm"]]
            return jnp.sum(out.astype(jnp.float32) ** 2), out

        (_, of), gf = jax.value_and_grad(loss_fused, has_aux=True)(b["feat"])
        (_, os_), gs = jax.value_and_grad(loss_split, has_aux=True)(b["feat"])
        return of[None], gf[None], os_[None], gs[None]

    f = jax.jit(shard_map(local, mesh=mesh, in_specs=(P("parts"), P()),
                          out_specs=(P("parts"),) * 4))
    of, gf, os_, gs = f(blk, place_replicated(tables, mesh))
    of, gf, os_, gs = map(np.asarray, (of, gf, os_, gs))
    # same wire payloads on both sides: only float reassociation differs
    scale = np.abs(of).max() + 1e-9
    assert np.abs(os_ - of).max() / scale < 1e-5, (strategy, wire, rate, "fwd")
    gscale = np.abs(gf).max() + 1e-9
    assert np.abs(gs - gf).max() / gscale < 1e-5, (strategy, wire, rate, "bwd")


# ----------------------------------------------------------------------------
# end-to-end: build_step_fns(--overlap split) == (--overlap off) — forward
# logits, train losses and updated params after real train steps
# ----------------------------------------------------------------------------

def _run_training(g, art, mesh, overlap, *, model="graphsage", spmm="ell",
                  strategy="padded", wire="native", rate=0.5, epochs=3):
    n_parts = mesh.devices.size
    cfg = Config(model=model, dropout=0.0, use_pp=False, norm="layer",
                 n_train=g.n_train, lr=0.01, sampling_rate=rate, spmm=spmm,
                 halo_exchange=strategy, halo_wire=wire, overlap=overlap,
                 n_partitions=n_parts, n_feat=g.n_feat, n_class=g.n_class)
    spec = ModelSpec(model, (g.n_feat, 16, g.n_class), norm="layer",
                     dropout=0.0, train_size=g.n_train)
    fns, hspec, tables, tables_full = build_step_fns(cfg, spec, art, mesh)
    blk_np = build_block_arrays(art, model)
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
    return np.asarray(logits), losses, jax.device_get(params), fns.overlap


def _assert_off_equals_split(g, art, mesh, **kw):
    lo, lso, po, _ = _run_training(g, art, mesh, "off", **kw)
    ls, lss, ps, resolved = _run_training(g, art, mesh, "split", **kw)
    assert resolved == "split"          # really ran the split path
    scale = np.abs(lo).max() + 1e-9
    assert np.abs(ls - lo).max() / scale < 1e-4, kw
    for a, b in zip(lso, lss):
        assert abs(a - b) <= 1e-5 * max(abs(a), 1.0), (kw, lso, lss)
    for a, b in zip(jax.tree.leaves(po), jax.tree.leaves(ps)):
        a, b = np.asarray(a), np.asarray(b)
        s = np.abs(a).max() + 1e-9
        assert np.abs(b - a).max() / s < 1e-4, kw


@pytest.fixture(scope="module")
def skew4():
    g = synthetic_graph(n_nodes=120, avg_degree=7, n_feat=6, seed=41,
                        power_law=True)
    pid = np.zeros(g.n_nodes, dtype=np.int32)
    pid[60:90] = 1
    pid[90:110] = 2
    pid[110:] = 3
    return g, build_artifacts(g, pid), make_parts_mesh(4)


@pytest.mark.quickgate
def test_e2e_split_equals_off_ell(skew4):
    g, art, mesh = skew4
    _assert_off_equals_split(g, art, mesh, spmm="ell", rate=0.5)


def test_e2e_split_equals_off_hybrid_ragged_int8(skew4):
    g, art, mesh = skew4
    _assert_off_equals_split(g, art, mesh, model="gcn", spmm="hybrid",
                             strategy="ragged", wire="int8", rate=1.0)


def test_e2e_split_equals_off_segment_shift(skew4):
    g, art, mesh = skew4
    _assert_off_equals_split(g, art, mesh, spmm="segment", strategy="shift",
                             wire="bf16", rate=0.5)


def test_gat_falls_back_to_off(skew4):
    """GAT aggregates through the masked edge softmax — --overlap split must
    resolve to 'off' (logged), not crash or silently mis-aggregate."""
    g, art, mesh = skew4
    cfg = Config(model="gat", use_pp=True, n_train=g.n_train,
                 overlap="split", n_feat=g.n_feat, n_class=g.n_class)
    spec = ModelSpec("gat", (g.n_feat, 8, g.n_class), dropout=0.0,
                     use_pp=True, heads=2, train_size=g.n_train)
    fns, _, _, _ = build_step_fns(cfg, spec, art, mesh)
    assert fns.overlap == "off"


# ----------------------------------------------------------------------------
# degenerate partitions: zero interior rows / zero frontier rows
# ----------------------------------------------------------------------------

def _degenerate_graph():
    """16 nodes, 2 parts of 8 (pad_inner == 8, NO padded rows — padding
    would count as interior and un-degenerate part 0): every part-0 row has
    a cross in-edge (zero interior), part 1 receives no cross edges (zero
    frontier)."""
    n = 16
    rng = np.random.default_rng(7)
    src = list(range(n))                       # self-loops (canonical form)
    dst = list(range(n))
    for i in range(8):                         # 8+i -> i : part0 all-frontier
        src.append(8 + i)
        dst.append(i)
    for i in range(7):                         # local chain inside part 1
        src.append(8 + i)
        dst.append(9 + i)
    label = rng.integers(0, 3, size=n)
    feat = rng.normal(size=(n, 5)).astype(np.float32)
    ones = np.ones(n, dtype=bool)
    g = Graph(n, np.asarray(src, np.int64), np.asarray(dst, np.int64),
              feat, label.astype(np.int64), ones, ones, ones)
    pid = np.repeat(np.arange(2), 8).astype(np.int32)
    return g, pid


@pytest.mark.quickgate
def test_degenerate_zero_interior_and_zero_frontier():
    g, pid = _degenerate_graph()
    art = build_artifacts(g, pid)
    assert art.pad_inner == 8 and art.n_inner.tolist() == [8, 8]
    fm0 = frontier_mask(art.src[0], art.dst[0], art.pad_inner)
    fm1 = frontier_mask(art.src[1], art.dst[1], art.pad_inner)
    assert fm0.all(), "part 0 must have zero interior rows"
    assert not fm1.any(), "part 1 must have zero frontier rows"
    mesh = make_parts_mesh(2)
    _assert_off_equals_split(g, art, mesh, spmm="ell", rate=1.0)
    _assert_off_equals_split(g, art, mesh, spmm="hybrid", rate=0.5)


@pytest.mark.quickgate
def test_degenerate_single_part_no_frontier_anywhere():
    """P=1 (the bench preflight shape): no cross edges at all — the
    frontier side is all-padding everywhere and split must still equal
    off."""
    g = synthetic_graph(n_nodes=64, avg_degree=5, n_feat=6, seed=9)
    art = build_artifacts(g, partition_graph(g, 1, method="random", seed=0))
    mesh = make_parts_mesh(1)
    _assert_off_equals_split(g, art, mesh, spmm="ell", rate=1.0)


# ----------------------------------------------------------------------------
# a narrowing layer projects before it aggregates, on both source sides of
# the split and behind the sampled exchange: the wide order's loss, logits
# and gradients at P=2, boundary sampling at rate 0.1
# ----------------------------------------------------------------------------

@pytest.fixture(scope="module")
def wide2():
    g = synthetic_graph(n_nodes=120, avg_degree=7, n_feat=40, n_class=3,
                        seed=43, power_law=True)
    art = build_artifacts(g, partition_graph(g, 2, method="random", seed=3))
    return g, art, make_parts_mesh(2)


def _loss_grads(g, art, mesh, overlap, model, sizes, rate):
    use_pp = model == "graphsage"
    cfg = Config(model=model, dropout=0.0, use_pp=use_pp, norm="layer",
                 n_train=g.n_train, lr=0.01, sampling_rate=rate, spmm="ell",
                 overlap=overlap, n_partitions=mesh.devices.size,
                 n_feat=g.n_feat, n_class=g.n_class)
    spec = ModelSpec(model, sizes, norm="layer", dropout=0.0, use_pp=use_pp,
                     train_size=g.n_train)
    fns, _, tables, tables_full = build_step_fns(cfg, spec, art, mesh)
    assert fns.overlap == overlap
    blk_np = build_block_arrays(art, model)
    blk_np.update(fns.extra_blk)
    for k in fns.drop_blk_keys:
        blk_np.pop(k, None)
    blk = place_blocks(blk_np, mesh)
    tb = place_replicated(tables, mesh)
    if use_pp:
        blk["feat"] = fns.precompute(blk, place_replicated(tables_full, mesh))
    params, state = init_params(jax.random.key(5), spec)
    params = place_replicated(params, mesh)
    state = place_replicated(state, mesh)
    logits = fns.forward(params, state, jnp.uint32(2), blk, tb,
                         jax.random.key(0))
    loss, grads = fns.loss_and_grad(params, state, jnp.uint32(3), blk, tb,
                                    jax.random.key(0), jax.random.key(1))
    return (np.asarray(logits), float(loss),
            jax.tree.map(np.asarray, jax.device_get(grads)))


@pytest.mark.parametrize("overlap", ["off", "split"])
@pytest.mark.parametrize("model,sizes,narrow", [
    ("gcn", (40, 8, 8, 3), (0, 2)),
    ("graphsage", (40, 16, 16, 3), (2,))])
def test_narrow_side_matches_wide_order_p2(monkeypatch, wide2, overlap,
                                           model, sizes, narrow):
    from bnsgcn_tpu.models import gnn
    g, art, mesh = wide2
    spec = ModelSpec(model, sizes, use_pp=model == "graphsage")
    assert tuple(i for i in range(spec.n_layers)
                 if gnn.projects_first(spec, i)) == narrow
    lo, loss, grads = _loss_grads(g, art, mesh, overlap, model, sizes, 0.1)
    with monkeypatch.context() as m:
        m.setattr(gnn, "projects_first", lambda spec, i: False)
        lo_w, loss_w, grads_w = _loss_grads(g, art, mesh, overlap, model,
                                            sizes, 0.1)
    assert np.abs(lo - lo_w).max() <= 1e-5 * np.abs(lo_w).max()
    assert abs(loss - loss_w) <= 1e-5 * abs(loss_w)
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(grads_w)):
        assert np.abs(a - b).max() <= 1e-5 * (np.abs(b).max() + 1e-12)
