"""Hybrid block-dense + ELL SpMM == plain ELL SpMM == dense oracle
(forward and gradients), on clustered and uniform graphs."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bnsgcn_tpu.data.artifacts import build_artifacts
from bnsgcn_tpu.data.graph import sbm_graph, synthetic_graph
from bnsgcn_tpu.data.partitioner import partition_graph
from bnsgcn_tpu.ops.block_spmm import (build_block_layouts, cluster_order,
                                       dense_edge_count, make_block_spmm)
from bnsgcn_tpu.ops.ell import build_layouts, make_ell_spmm


def _hybrid_for(art, occupancy_min, tile=512):
    P = art.n_parts
    perms_i, perms_e = [], []
    for p in range(P):
        pi, pe = cluster_order(art.src[p], art.dst[p], art.pad_inner,
                               art.n_ext, target=min(tile, 64))
        perms_i.append(pi)
        perms_e.append(pe)
    fwd, bwd, ell_pair, arrays = build_block_layouts(
        art.src, art.dst, art.pad_inner, art.n_ext,
        np.stack(perms_i), np.stack(perms_e), occupancy_min=occupancy_min,
        tile_r=tile, tile_c=tile)
    return fwd, bwd, ell_pair, arrays


def _dense_oracle(art, p, h_ext):
    out = np.zeros((art.pad_inner, h_ext.shape[1]))
    real = art.dst[p] < art.pad_inner
    np.add.at(out, art.dst[p][real], np.asarray(h_ext)[art.src[p][real]])
    return out


def _assert_oracle_and_grads(art, spmm, arrays, H=7, seed=0):
    """Forward == dense oracle and d/dh == A^T cot on every part."""
    rng = np.random.default_rng(seed)
    for p in range(art.n_parts):
        h = jnp.asarray(rng.normal(size=(art.n_ext, H)), jnp.float32)
        arr_p = {k: jnp.asarray(v[p]) for k, v in arrays.items()}
        out = np.asarray(spmm(arr_p, h))
        np.testing.assert_allclose(out, _dense_oracle(art, p, h),
                                   rtol=1e-4, atol=1e-4)
        cot = rng.normal(size=out.shape).astype(np.float32)
        gfn = jax.grad(lambda hh: jnp.sum(spmm(arr_p, hh) * cot))
        d_h = np.asarray(gfn(h))
        d_ref = np.zeros((art.n_ext, H))
        real = art.dst[p] < art.pad_inner
        np.add.at(d_ref, art.src[p][real], cot[art.dst[p][real]])
        np.testing.assert_allclose(d_h, d_ref, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("graph,occ", [("sbm", 4), ("uniform", 4),
                                       ("sbm", 10**9)])
def test_hybrid_matches_oracle_and_grads(graph, occ):
    """occ=4: most edges densify on the clustered graph; occ=huge: pure-ELL
    degeneration — all must equal the dense oracle exactly."""
    if graph == "sbm":
        g = sbm_graph(n_nodes=300, n_class=5, n_feat=6, p_in=0.15,
                      p_out=0.003, seed=61)
    else:
        g = synthetic_graph(n_nodes=300, avg_degree=8, n_feat=6, seed=62)
    art = build_artifacts(g, partition_graph(g, 2, method="random", seed=3))
    fwd, bwd, ell_pair, arrays = _hybrid_for(art, occ)
    spmm = make_block_spmm(fwd, bwd, ell_pair)
    if graph == "sbm" and occ == 4:
        assert dense_edge_count(arrays, 0) > 0, "no tiles densified"
    _assert_oracle_and_grads(art, spmm, arrays)


@pytest.mark.parametrize("tile", [32, 64])
def test_hybrid_tile_size_matches_oracle(tile):
    """Non-default tile geometry (the bench's +t256 class, scaled to test
    size): multiple row/col blocks per part, forward and VJP exact."""
    g = sbm_graph(n_nodes=300, n_class=5, n_feat=6, p_in=0.15, p_out=0.003,
                  seed=61)
    art = build_artifacts(g, partition_graph(g, 2, method="random", seed=3))
    fwd, bwd, ell_pair, arrays = _hybrid_for(art, 4, tile=tile)
    assert fwd.row_tile == tile and fwd.n_row_blocks > 1
    assert dense_edge_count(arrays, 0) > 0, "no tiles densified"
    spmm = make_block_spmm(fwd, bwd, ell_pair)
    _assert_oracle_and_grads(art, spmm, arrays)


def test_hybrid_equals_pure_ell():
    g = sbm_graph(n_nodes=240, n_class=4, n_feat=6, p_in=0.12, p_out=0.004,
                  seed=63)
    art = build_artifacts(g, partition_graph(g, 2, method="random", seed=4))
    fwd_h, bwd_h, ell_pair, arrays_h = _hybrid_for(art, 4)
    hybrid = make_block_spmm(fwd_h, bwd_h, ell_pair)
    f_spec, b_spec, ell_arrays = build_layouts(art.src, art.dst,
                                               art.pad_inner, art.n_ext)
    ell = make_ell_spmm(f_spec, b_spec, len(f_spec.widths),
                        len(b_spec.widths))
    rng = np.random.default_rng(1)
    h = jnp.asarray(rng.normal(size=(art.n_ext, 5)), jnp.float32)
    a_h = {k: jnp.asarray(v[0]) for k, v in arrays_h.items()}
    a_e = {k: jnp.asarray(v[0]) for k, v in ell_arrays.items()}
    np.testing.assert_allclose(np.asarray(hybrid(a_h, h)),
                               np.asarray(ell(a_e, h)), rtol=1e-4, atol=1e-4)


def test_multiplicity_overflow_rides_residual():
    """>127 duplicate edges of one (u,v) pair exceed int8 tile headroom; the
    excess must ride the ELL residual so hybrid == oracle exactly."""
    g = sbm_graph(n_nodes=200, n_class=3, n_feat=5, p_in=0.2, p_out=0.01,
                  seed=65)
    g.src = np.concatenate([g.src, np.full(300, 7, dtype=np.int64)])
    g.dst = np.concatenate([g.dst, np.full(300, 9, dtype=np.int64)])
    art = build_artifacts(g, np.zeros(g.n_nodes, dtype=np.int32))
    fwd, bwd, ell_pair, arrays = _hybrid_for(art, 4)
    assert int(arrays["blk_tiles_fwd"].max()) == 127, "no tile saturated"
    spmm = make_block_spmm(fwd, bwd, ell_pair)
    rng = np.random.default_rng(2)
    h = jnp.asarray(rng.normal(size=(art.n_ext, 5)), jnp.float32)
    arr0 = {k: jnp.asarray(v[0]) for k, v in arrays.items()}
    np.testing.assert_allclose(np.asarray(spmm(arr0, h)),
                               _dense_oracle(art, 0, h), rtol=1e-4, atol=1e-4)
    cot = rng.normal(size=(art.pad_inner, 5)).astype(np.float32)
    d_h = np.asarray(jax.grad(lambda hh: jnp.sum(spmm(arr0, hh) * cot))(h))
    d_ref = np.zeros((art.n_ext, 5))
    real = art.dst[0] < art.pad_inner
    np.add.at(d_ref, art.src[0][real], cot[art.dst[0][real]])
    np.testing.assert_allclose(d_h, d_ref, rtol=1e-4, atol=1e-4)


def test_hybrid_train_step_matches_ell():
    """--spmm hybrid inside the sharded train step (custom VJP under
    shard_map's varying-axes checks) == --spmm ell, losses and params."""
    import jax.numpy as jnp
    from bnsgcn_tpu.config import Config
    from bnsgcn_tpu.models.gnn import ModelSpec, init_params
    from bnsgcn_tpu.parallel.mesh import make_parts_mesh
    from bnsgcn_tpu.trainer import (build_block_arrays, build_step_fns,
                                    init_training, place_blocks,
                                    place_replicated)

    g = sbm_graph(n_nodes=240, n_class=4, n_feat=8, p_in=0.1, p_out=0.005,
                  seed=66)
    spec = ModelSpec("graphsage", (8, 16, 4), norm="layer", dropout=0.0,
                     use_pp=True, train_size=g.n_train)
    params0, state0 = init_params(jax.random.key(6), spec)
    params_np = jax.tree.map(np.asarray, params0)
    mesh = make_parts_mesh(4)
    art = build_artifacts(g, partition_graph(g, 4, method="random", seed=7))
    results = {}
    for spmm in ("hybrid", "ell"):
        cfg = Config(model="graphsage", dropout=0.0, use_pp=True,
                     norm="layer", n_train=g.n_train, lr=0.01,
                     sampling_rate=0.5, spmm=spmm)
        fns, hspec, tables, tables_full = build_step_fns(cfg, spec, art, mesh)
        # the run header's counters: the XLA twin off the TPU, no tiles
        # at all on the pure ELL path
        via = "xla" if spmm == "hybrid" else "none"
        assert (fns.spmm_counts["dense_path_fwd"],
                fns.spmm_counts["dense_path_bwd"]) == (via, via)
        assert (f"via {via}," in fns.spmm_desc) == (spmm == "hybrid")
        blk_np = build_block_arrays(art, "graphsage")
        blk_np.update(fns.extra_blk)
        for k in fns.drop_blk_keys:
            blk_np.pop(k, None)
        blk = place_blocks(blk_np, mesh)
        tb = place_replicated(tables, mesh)
        blk["feat"] = fns.precompute(blk, place_replicated(tables_full, mesh))
        p = place_replicated(params_np, mesh)
        s = place_replicated(state0, mesh)
        _, _, opt = init_training(cfg, spec, mesh)
        for e in range(3):
            p, s, opt, loss = fns.train_step(p, s, opt, jnp.uint32(e), blk, tb,
                                             jax.random.key(0), jax.random.key(1))
        results[spmm] = (float(loss), jax.tree.map(np.asarray, jax.device_get(p)))
    assert abs(results["hybrid"][0] - results["ell"][0]) < 1e-5
    jax.tree.map(lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-4,
                                                         atol=1e-5),
                 results["hybrid"][1], results["ell"][1])


def _tile_stack(tile, H, slab, seed=0):
    """A stacked dense-tile layout as the builder lays it out, with what a
    real one can hold: 4 row blocks x 3 column blocks, every pair but row
    block 1's (UNVISITED: the kernel never writes it, the caller's mask
    must), then two pad-only tiles (rowb == n_row_blocks, all zero)."""
    from bnsgcn_tpu.ops.block_spmm import BlockSpec
    rng = np.random.default_rng(seed)
    n_rb, n_cb = 4, 3
    rb, cb = np.meshgrid(np.arange(n_rb), np.arange(n_cb), indexing="ij")
    keep = rb.ravel() != 1
    rowb = np.concatenate([rb.ravel()[keep], [n_rb, n_rb]]).astype(np.int32)
    colb = np.concatenate([cb.ravel()[keep], [0, 0]]).astype(np.int32)
    B = len(rowb)
    tiles = ((rng.random((B, tile, tile)) < 0.02)
             * rng.integers(1, 4, (B, tile, tile))).astype(np.int8)
    tiles[-2:] = 0
    spec = BlockSpec(n_rows=n_rb * tile, n_src=n_cb * tile, row_tile=tile,
                     col_tile=tile, n_blocks=B, n_row_blocks=n_rb,
                     max_row_dense=int(tiles.sum(axis=2).max()))
    perm_src = rng.permutation(spec.n_src).astype(np.int32)
    perm_out = rng.permutation(spec.n_rows).astype(np.int32)
    h = jnp.asarray(rng.normal(size=(spec.n_src, H)), slab)
    unvisited = (perm_out >= tile) & (perm_out < 2 * tile)
    return spec, tuple(map(jnp.asarray, (tiles, rowb, colb, perm_src,
                                         perm_out))), h, unvisited


# (layout, dense_dtype, slab dtype, H, tile): the sbm graph's own layout,
# and stacks at the widths and tiles a TPU run reaches (H = 41, 256 and
# 602: the last layer, the hidden width, the use_pp precompute), in both
# slab dtypes the recipes state
TILE_CASES = [("sbm", "native", "float32", 7, 512),
              ("sbm", "int8", "float32", 7, 512),
              ("stack", "native", "float32", 41, 512),
              ("stack", "native", "float32", 256, 256),
              ("stack", "native", "float32", 602, 512),
              ("stack", "native", "bfloat16", 41, 256),
              ("stack", "native", "bfloat16", 256, 512),
              ("stack", "native", "bfloat16", 602, 256),
              ("stack", "int8", "float32", 256, 512),
              ("stack", "int8", "bfloat16", 41, 256)]


@pytest.mark.parametrize("layout,dense_dtype,slab,H,tile", TILE_CASES)
def test_pallas_tile_matmul_matches_xla(layout, dense_dtype, slab, H, tile):
    """The fused Pallas grouped-matmul (interpret mode off-TPU) == the XLA
    dense-tile path; the int8 variant quantizes with one per-call scale so
    it gets the quantization tolerance against the NATIVE reference. A
    row block no tile maps to reads zero."""
    from bnsgcn_tpu.ops.block_spmm import _dense_apply
    from bnsgcn_tpu.ops.pallas_block import dense_apply_pallas

    slab = jnp.dtype(slab)
    if layout == "sbm":
        g = sbm_graph(n_nodes=300, n_class=5, n_feat=6, p_in=0.15,
                      p_out=0.003, seed=67)
        art = build_artifacts(g, partition_graph(g, 2, method="random",
                                                 seed=3))
        fwd, bwd, ell_pair, arrays = _hybrid_for(art, 4, tile=tile)
        assert dense_edge_count(arrays, 0) > 0
        a = {k: jnp.asarray(v[0]) for k, v in arrays.items()}
        ops = (a["blk_tiles_fwd"], a["blk_rowb_fwd"], a["blk_colb_fwd"],
               a["blk_perm_ext"], a["blk_perm_inner"])
        h = jnp.asarray(np.random.default_rng(3).normal(
            size=(art.n_ext, H)), slab)
        unvisited = None
    else:
        fwd, ops, h, unvisited = _tile_stack(tile, H, slab, seed=tile + H)
    ref = np.asarray(_dense_apply(fwd, *ops, h), np.float32)
    got = np.asarray(dense_apply_pallas(fwd, *ops, h, dense_dtype=dense_dtype,
                                        interpret=True), np.float32)
    amax = float(np.abs(ref).max())
    if dense_dtype == "int8":
        tol = dict(atol=0.05 * amax)
    elif slab == jnp.float32:
        tol = dict(rtol=1e-4, atol=1e-4 * amax)
    else:
        # both accumulate in f32 and round to bf16 once: they differ by the
        # f32 summation order, at most one bf16 ulp
        tol = dict(rtol=2.0 ** -7, atol=1e-3 * amax)
    np.testing.assert_allclose(got, ref, **tol)
    if unvisited is not None:
        assert unvisited.any() and not got[unvisited].any()
    if dense_dtype == "int8":
        assert not np.allclose(got, ref)  # quantized


@pytest.mark.parametrize("backend,dense_dtype,row_dense,want", [
    ("tpu", "native", 0, "pallas"),
    ("tpu", "native", 10**6, "pallas"),
    ("tpu", "int8", 1000, "pallas"),
    ("tpu", "int8", 10**6, "xla"),       # past the int32 accumulator bound
    ("cpu", "native", 0, "xla"),
    ("cpu", "int8", 1000, "xla")])
def test_dense_path_reads_only_what_it_observes(monkeypatch, backend,
                                                dense_dtype, row_dense, want):
    """The kernel on every TPU run, with no flag; the XLA twin where Mosaic
    does not lower (the CPU) or an int8 row could wrap the kernel's int32
    accumulator. The run header's counters read the same choice."""
    from bnsgcn_tpu.ops import block_spmm as bs
    from bnsgcn_tpu.trainer import dense_paths
    assert (row_dense > bs._I8_ROW_CAP) == (row_dense == 10**6)
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    spec = bs.BlockSpec(n_rows=512, n_src=512, row_tile=512, col_tile=512,
                        n_blocks=1, n_row_blocks=1, max_row_dense=row_dense)
    small = bs.BlockSpec(n_rows=512, n_src=512, row_tile=512, col_tile=512,
                         n_blocks=1, n_row_blocks=1, max_row_dense=1)
    assert bs.dense_path(spec, dense_dtype) == want
    assert dense_paths({"": (spec, spec)}, dense_dtype) == {"fwd": want,
                                                            "bwd": want}
    # --overlap split: the set over its spec pairs
    other = bs.dense_path(small, dense_dtype)
    split = dense_paths({"int_": (spec, small), "fro_": (small, small)},
                        dense_dtype)
    assert split == {"fwd": "+".join(sorted({want, other})), "bwd": other}
    assert dense_paths(None, dense_dtype) == {"fwd": "none", "bwd": "none"}


@pytest.mark.parametrize("at", ["first", "last"])
def test_pallas_flag_is_a_noop(at):
    """--use-pallas still parses (the benchmark's whole.p1 cell passes it)
    and changes nothing: the dense-tile path is dense_path's alone."""
    import dataclasses
    from bnsgcn_tpu.config import parse_config
    base = ["--spmm", "hybrid", "--dtype", "bfloat16"]
    argv = (["--use-pallas"] + base if at == "first"
            else base + ["--use-pallas"])
    cfg = parse_config(argv)
    assert cfg == parse_config(base)
    assert not any("pallas" in f.name for f in dataclasses.fields(cfg))


def test_cluster_order_is_permutation():
    g = sbm_graph(n_nodes=200, n_class=4, n_feat=4, seed=64)
    art = build_artifacts(g, partition_graph(g, 2, method="random", seed=5))
    pi, pe = cluster_order(art.src[0], art.dst[0], art.pad_inner, art.n_ext)
    assert sorted(pi.tolist()) == list(range(art.pad_inner))
    assert sorted(pe.tolist()) == list(range(art.n_ext))
    np.testing.assert_array_equal(pe[:art.pad_inner], pi)


def test_int8_dense_path_close_to_native():
    """dense_dtype='int8' (quantized slabs, int8 x int8 MXU tiles) tracks
    the exact path within quantization tolerance, forward and gradient."""
    g = sbm_graph(n_nodes=300, n_class=5, n_feat=6, p_in=0.15, p_out=0.003,
                  seed=68)
    art = build_artifacts(g, partition_graph(g, 2, method="random", seed=3))
    fwd, bwd, ell_pair, arrays = _hybrid_for(art, 4)
    assert dense_edge_count(arrays, 0) > 0
    exact = make_block_spmm(fwd, bwd, ell_pair)
    quant = make_block_spmm(fwd, bwd, ell_pair, dense_dtype="int8")
    rng = np.random.default_rng(5)
    h = jnp.asarray(rng.normal(size=(art.n_ext, 7)), jnp.float32)
    a = {k: jnp.asarray(v[0]) for k, v in arrays.items()}
    ref = np.asarray(exact(a, h))
    got = np.asarray(quant(a, h))
    scale = np.abs(ref).max()
    np.testing.assert_allclose(got, ref, atol=0.05 * scale)
    cot = rng.normal(size=ref.shape).astype(np.float32)
    d_ref = np.asarray(jax.grad(
        lambda hh: jnp.sum(exact(a, hh) * cot))(h))
    d_got = np.asarray(jax.grad(
        lambda hh: jnp.sum(quant(a, hh) * cot))(h))
    np.testing.assert_allclose(d_got, d_ref,
                               atol=0.05 * np.abs(d_ref).max())


@pytest.mark.parametrize("chunked", [True, False])
@pytest.mark.parametrize("dense_dtype", ["native", "int8"])
def test_chunked_dense_path_matches_oracle(dense_dtype, chunked, monkeypatch):
    """The lax.scan tile accumulation (keeps HLO temps flat in B — the
    jit(precompute) OOM fix) must stay exact, forward and gradient, both
    multi-chunk (incl. B % C != 0 zero-tile padding) and single-chunk
    (B <= C), on a multi-block geometry where rowb != colb — a wrong
    slab-gather index (colb vs rowb) only shows up off the diagonal."""
    import bnsgcn_tpu.ops.block_spmm as bs
    if chunked:
        monkeypatch.setattr(bs, "_tile_chunk_for", lambda *a, **k: 4)
    g = sbm_graph(n_nodes=300, n_class=5, n_feat=6, p_in=0.15, p_out=0.003,
                  seed=61)
    art = build_artifacts(g, partition_graph(g, 2, method="random", seed=3))
    fwd, bwd, ell_pair, arrays = _hybrid_for(art, 4, tile=64)
    assert np.any(arrays["blk_rowb_fwd"][0][:fwd.n_blocks]
                  != arrays["blk_colb_fwd"][0][:fwd.n_blocks]), \
        "all tiles on the diagonal — wrong-slab-index bug invisible"
    if chunked:
        assert fwd.n_blocks > 4 and fwd.n_blocks % 4 != 0, \
            "chunking path (incl. padding) not exercised"
    else:
        assert fwd.n_blocks <= bs._tile_chunk_for(
            fwd.n_blocks, fwd.row_tile, 7), "expected single-chunk case"
    spmm = make_block_spmm(fwd, bwd, ell_pair, dense_dtype=dense_dtype)
    rng = np.random.default_rng(9)
    h = jnp.asarray(rng.normal(size=(art.n_ext, 7)), jnp.float32)
    arr0 = {k: jnp.asarray(v[0]) for k, v in arrays.items()}
    ref = _dense_oracle(art, 0, h)
    tol = dict(rtol=1e-4, atol=1e-4) if dense_dtype == "native" else \
        dict(atol=0.05 * np.abs(ref).max())
    np.testing.assert_allclose(np.asarray(spmm(arr0, h)), ref, **tol)
    cot = rng.normal(size=ref.shape).astype(np.float32)
    d_h = np.asarray(jax.grad(lambda hh: jnp.sum(spmm(arr0, hh) * cot))(h))
    d_ref = np.zeros((art.n_ext, 7))
    real = art.dst[0] < art.pad_inner
    np.add.at(d_ref, art.src[0][real], cot[art.dst[0][real]])
    d_tol = tol if dense_dtype == "native" else \
        dict(atol=0.05 * np.abs(d_ref).max())
    np.testing.assert_allclose(d_h, d_ref, **d_tol)


def test_estimate_coverage_matches_build():
    """The --spmm auto estimator equals the dense-edge fraction the real
    layout build produces (same _select_dense rule, no materialization)."""
    from bnsgcn_tpu.ops.block_spmm import estimate_coverage
    g = sbm_graph(n_nodes=300, n_class=5, n_feat=6, p_in=0.15,
                  p_out=0.003, seed=61)
    art = build_artifacts(g, partition_graph(g, 2, method="random", seed=3))
    for occ in (4, 64, 10**9):
        fwd, bwd, ell_pair, arrays = _hybrid_for(art, occ)
        for p in range(art.n_parts):
            pi, pe = cluster_order(art.src[p], art.dst[p], art.pad_inner,
                                   art.n_ext, target=64)
            real = art.dst[p] < art.pad_inner
            d, s = art.dst[p][real], art.src[p][real]
            est = estimate_coverage(pi, pe, art.pad_inner, art.n_ext, d, s,
                                    occupancy_min=occ)
            frac = dense_edge_count(arrays, p) / max(len(d), 1)
            assert abs(est - frac) < 1e-9, (occ, p, est, frac)


def test_spmm_auto_resolution():
    """cfg.spmm='auto' picks hybrid on a clustered graph at low occupancy
    and ell when no tile can reach occupancy; both train."""
    from bnsgcn_tpu.config import Config
    from bnsgcn_tpu.models.gnn import ModelSpec, init_params
    from bnsgcn_tpu.parallel.mesh import make_parts_mesh
    from bnsgcn_tpu.trainer import (build_block_arrays, build_step_fns,
                                    init_training, place_blocks,
                                    place_replicated)
    g = sbm_graph(n_nodes=300, n_class=5, n_feat=6, p_in=0.15,
                  p_out=0.003, seed=61)
    art = build_artifacts(g, partition_graph(g, 2, method="random", seed=3))
    mesh = make_parts_mesh(2)
    for occ, expect_dense in ((4, True), (10**9, False)):
        cfg = Config(model="graphsage", n_layers=2, n_hidden=8, spmm="auto",
                     block_occupancy=occ, sampling_rate=1.0,
                     n_feat=art.n_feat, n_class=art.n_class,
                     n_train=art.n_train)
        spec = ModelSpec("graphsage", (art.n_feat, 8, art.n_class),
                         train_size=art.n_train)
        fns, hspec, tables, _ = build_step_fns(cfg, spec, art, mesh)
        has_tiles = any("tiles" in k for k in fns.extra_blk)
        assert has_tiles == expect_dense, (occ, sorted(fns.extra_blk))
        blk_np = build_block_arrays(art, spec.model)
        blk_np.update(fns.extra_blk)
        for k in fns.drop_blk_keys:
            blk_np.pop(k, None)
        blk = place_blocks(blk_np, mesh)
        params, state, opt = init_training(cfg, spec, mesh)
        params, state, opt, loss = fns.train_step(
            params, state, opt, jnp.uint32(0), blk,
            place_replicated(tables, mesh),
            jax.random.key(0), jax.random.key(1))
        assert np.isfinite(float(loss))


def test_max_row_dense_repair_matches_build(monkeypatch):
    """The int8 Pallas overflow guard reads max_row_dense in both
    directions. The build fills both from the RLE bincount (fast path) or
    from a scan of the stack (legacy path); with one stored stack, the
    backward's value must equal the column sums of the forward tiles, and
    the backward's view (tiles taken through blk_order_bwd, grouped by
    blk_rowb_bwd) must count the same rows."""
    from bnsgcn_tpu.ops.block_spmm import _row_dense_maxima
    g = synthetic_graph(n_nodes=120, avg_degree=8, n_feat=4, seed=9)
    art = build_artifacts(g, partition_graph(g, 2, method="random", seed=1))
    tile = 32
    fwd, bwd, _, arrays = _hybrid_for(art, occupancy_min=4, tile=tile)
    assert "blk_tiles_bwd" not in arrays
    assert fwd.max_row_dense > 0 and bwd.max_row_dense > 0
    tiles = arrays["blk_tiles_fwd"]
    scan_f = scan_b = view_b = 0
    for p in range(art.n_parts):
        m_f, m_b = _row_dense_maxima(
            tiles[p], arrays["blk_rowb_fwd"][p], arrays["blk_colb_fwd"][p],
            art.pad_inner, art.n_ext, tile, tile)
        scan_f, scan_b = max(scan_f, m_f), max(scan_b, m_b)
        # backward as the kernel reads it: tile j is fwd tile order[j],
        # its output rows are the forward tile's columns
        per_row = np.zeros((bwd.n_row_blocks + 1, tile), np.int64)
        np.add.at(per_row, arrays["blk_rowb_bwd"][p],
                  tiles[p][arrays["blk_order_bwd"][p]].sum(
                      axis=1, dtype=np.int64))
        view_b = max(view_b, int(per_row.max()))
    assert fwd.max_row_dense == scan_f
    assert bwd.max_row_dense == scan_b == view_b
    # the legacy (scan) path builds the same guard
    monkeypatch.setenv("BNSGCN_LAYOUT_FASTPATH", "0")
    lf, lb, _, _ = _hybrid_for(art, occupancy_min=4, tile=tile)
    assert (lf.max_row_dense, lb.max_row_dense) == (scan_f, scan_b)


def test_dense_edge_count_split_and_missing_keys():
    """dense_edge_count across all three layout shapes (bench preflight
    regression: the hybrid+rag+ovl candidate KeyError'd on the split
    layout's int_/fro_-prefixed tile stacks and fell back to ell, so +ovl
    never got measured).

    * unified layout: bare blk_tiles_fwd
    * split-overlap layout: int_blk_tiles_fwd + fro_blk_tiles_fwd
    * fully-ELL layout (occupancy filter kept nothing): no tiles keys -> 0
    """
    from bnsgcn_tpu.ops.block_spmm import build_split_block_layouts

    g = sbm_graph(n_nodes=240, n_class=4, n_feat=5, p_in=0.2, p_out=0.01,
                  seed=17)
    art = build_artifacts(g, partition_graph(g, 2, method="random", seed=2))
    # unified layout counts == per-part tile sums (sanity baseline)
    _, _, _, uni = _hybrid_for(art, occupancy_min=4, tile=32)
    assert "blk_tiles_fwd" in uni
    for p in range(art.n_parts):
        assert dense_edge_count(uni, p) == int(
            uni["blk_tiles_fwd"][p].astype(np.int64).sum())

    # split layout: keys are int_/fro_-prefixed; the old implementation
    # raised KeyError here
    perms_i = np.stack([cluster_order(art.src[p], art.dst[p], art.pad_inner,
                                      art.n_ext, target=32)[0]
                        for p in range(art.n_parts)])
    perms_e = np.stack([cluster_order(art.src[p], art.dst[p], art.pad_inner,
                                      art.n_ext, target=32)[1]
                        for p in range(art.n_parts)])
    _, _, split_arrays, _, _ = build_split_block_layouts(
        art.src, art.dst, art.pad_inner, art.n_ext, perms_i, perms_e,
        occupancy_min=4, tile_r=32, tile_c=32)
    assert "blk_tiles_fwd" not in split_arrays
    for p in range(art.n_parts):
        want = sum(int(split_arrays[k][p].astype(np.int64).sum())
                   for k in ("int_blk_tiles_fwd", "fro_blk_tiles_fwd")
                   if k in split_arrays)
        got = dense_edge_count(split_arrays, p)
        assert got == want and got >= 0

    # impossible occupancy keeps only a placeholder tile carrying 0 edges
    _, _, _, empty = _hybrid_for(art, occupancy_min=10**9, tile=32)
    assert dense_edge_count(empty) == 0
    # arrays with no tiles keys at all (the auto path drops empty stacks
    # from extra_blk, test_spmm_auto_resolution) -> 0, not KeyError
    assert dense_edge_count({"merge_perm": np.arange(4)}) == 0


# ---------------------------------------------------------------------------
# one int8 stack for both directions: the backward reads the forward tiles
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def stack_builds():
    """(layout, tile) -> _stack_build's result, built once a module run."""
    return {}


def _stack_build(cache, layout, tile):
    """Every tile stack one build lays out, as (name, bwd BlockSpec, one
    part's arrays): the fused layout at P=1 and P=2, and the --overlap
    split layout's int_ / fro_ stacks (P=2), on a clustered graph with
    several row blocks a part at both tile sizes."""
    from bnsgcn_tpu.ops.block_spmm import build_split_block_layouts
    if (layout, tile) in cache:
        return cache[layout, tile]
    g = sbm_graph(n_nodes=1400, n_class=4, n_feat=4, p_in=0.03,
                  p_out=0.0005, seed=70)
    n_parts = 1 if layout == "p1" else 2
    art = build_artifacts(g, partition_graph(g, n_parts))
    perms = [cluster_order(art.src[p], art.dst[p], art.pad_inner, art.n_ext,
                           target=64) for p in range(n_parts)]
    pi = np.stack([a for a, _ in perms])
    pe = np.stack([b for _, b in perms])
    if layout == "split":
        (_, int_b, _), (_, fro_b, _), arrays, _, _ = \
            build_split_block_layouts(art.src, art.dst, art.pad_inner,
                                      art.n_ext, pi, pe, occupancy_min=4,
                                      tile_r=tile, tile_c=tile)
        pairs = (("int_", int_b), ("fro_", fro_b))
    else:
        _, bwd, _, arrays = build_block_layouts(
            art.src, art.dst, art.pad_inner, art.n_ext, pi, pe,
            occupancy_min=4, tile_r=tile, tile_c=tile)
        pairs = (("", bwd),)
    out = []
    for pre, bwd in pairs:
        for p in range(n_parts):
            a = {k[len(pre):]: np.asarray(v[p]) for k, v in arrays.items()
                 if k.startswith(pre)}
            out.append((f"{pre}p{p}", bwd, a))
    assert any((a["blk_rowb_bwd"] < b.n_row_blocks).sum() > 1
               for _, b, a in out), "no stack with two real tiles"
    cache[layout, tile] = out
    return out


@pytest.mark.parametrize("layout", ["p1", "p2", "split"])
@pytest.mark.parametrize("H,tile", [(256, 512), (41, 512), (602, 512),
                                    (256, 256), (41, 256), (602, 256)])
def test_backward_over_forward_stack_matches_transposed_stack(
        stack_builds, layout, H, tile):
    """The backward reading the forward stack through blk_order_bwd equals
    the backward over the transposed stack the layout used to hold (tiles
    [order] transposed, the same rowb / colb): the kernel (interpret mode)
    for a float32 slab to 1e-6 and for an int8 slab bitwise, and the XLA
    twin for a float32 slab to 1e-6."""
    from bnsgcn_tpu.ops.block_spmm import _dense_apply
    from bnsgcn_tpu.ops.pallas_block import pallas_tile_matmul
    rng = np.random.default_rng(H + tile)
    for name, bwd, a in _stack_build(stack_builds, layout, tile):
        tiles, order = a["blk_tiles_fwd"], a["blk_order_bwd"]
        rowb, colb = a["blk_rowb_bwd"], a["blk_colb_bwd"]
        n_rb = bwd.n_row_blocks
        assert sorted(order.tolist()) == list(range(len(order)))
        assert (np.diff(rowb) >= 0).all()
        transposed = np.ascontiguousarray(tiles[order].transpose(0, 2, 1))
        n_cb = -(-bwd.n_src // tile)
        visited = np.zeros(n_rb + 1, bool)
        visited[rowb] = True
        x32 = rng.normal(size=(n_cb, tile, H)).astype(np.float32)
        x8 = rng.integers(-127, 128, (n_cb, tile, H)).astype(np.int8)
        for x in (x32, x8):
            ops = tuple(map(jnp.asarray, (rowb, colb, x)))
            ref = np.asarray(pallas_tile_matmul(
                jnp.asarray(transposed), *ops, n_rb, interpret=True))
            got = np.asarray(pallas_tile_matmul(
                jnp.asarray(tiles), *ops, n_rb, order=jnp.asarray(order),
                interpret=True))
            assert got.shape == ref.shape == (n_rb + 1, tile, H)
            if x.dtype == np.int8:
                assert got.dtype == np.int32, name
                np.testing.assert_array_equal(got[visited], ref[visited],
                                              err_msg=name)
            else:
                np.testing.assert_allclose(
                    got[visited], ref[visited], rtol=1e-6,
                    atol=1e-6 * float(np.abs(ref[visited]).max()),
                    err_msg=name)
        g = jnp.asarray(rng.normal(size=(bwd.n_src, H)), jnp.float32)
        perms = tuple(map(jnp.asarray, (rowb, colb, a["blk_perm_inner"],
                                        a["blk_perm_ext"])))
        ref = np.asarray(_dense_apply(bwd, jnp.asarray(transposed), *perms, g))
        got = np.asarray(_dense_apply(bwd, jnp.asarray(tiles), *perms, g,
                                      order=jnp.asarray(order)))
        np.testing.assert_allclose(got, ref, rtol=1e-6,
                                   atol=1e-6 * float(np.abs(ref).max()),
                                   err_msg=name)


@pytest.mark.parametrize("path", ["xla", "pallas"])
def test_backward_vjp_is_dense_transpose(monkeypatch, path):
    """jax.vjp of the hybrid SpMM is A^T g against the dense oracle on
    every part, through the XLA twin and through the kernel (interpret
    mode), with the backward reading the forward stack."""
    from functools import partial

    from bnsgcn_tpu.ops import block_spmm as bs
    from bnsgcn_tpu.ops import pallas_block
    if path == "pallas":
        monkeypatch.setattr(bs, "dense_path", lambda *a: "pallas")
        monkeypatch.setattr(pallas_block, "dense_apply_pallas",
                            partial(pallas_block.dense_apply_pallas,
                                    interpret=True))
    g = sbm_graph(n_nodes=300, n_class=5, n_feat=6, p_in=0.15, p_out=0.003,
                  seed=71)
    art = build_artifacts(g, partition_graph(g, 2, method="random", seed=3))
    fwd, bwd, ell_pair, arrays = _hybrid_for(art, 4, tile=64)
    assert fwd.n_row_blocks > 1 and dense_edge_count(arrays, 0) > 0
    assert "blk_tiles_bwd" not in arrays
    _assert_oracle_and_grads(art, make_block_spmm(fwd, bwd, ell_pair),
                             arrays)


def test_layout_holds_one_stack_of_the_budgeted_bytes():
    """No transposed stack: one int8 stack a part, whose device bytes the
    budget bounds (it binds here), read by both directions through
    blk_order_bwd, a col_block-sorted permutation of the stored tiles; the
    split layout's two builds likewise. The run header counts the same
    tiles both ways, the stack's bytes and the dense share of all edges."""
    from bnsgcn_tpu.models.gnn import ModelSpec
    from bnsgcn_tpu.ops.block_spmm import build_split_block_layouts
    from bnsgcn_tpu.trainer import spmm_counts
    g = sbm_graph(n_nodes=300, n_class=5, n_feat=6, p_in=0.15, p_out=0.003,
                  seed=61)
    art = build_artifacts(g, partition_graph(g, 2, method="random", seed=3))
    tile = 32
    spec_free, _, _, free = _hybrid_for(art, 4, tile=tile)
    n_free = int((free["blk_rowb_fwd"] < spec_free.n_row_blocks).sum(
        axis=1).max())
    budget = (n_free // 2) * tile * tile
    perms = [cluster_order(art.src[p], art.dst[p], art.pad_inner, art.n_ext,
                           target=tile) for p in range(2)]
    pi = np.stack([a for a, _ in perms])
    pe = np.stack([b for _, b in perms])
    fwd, bwd, _, arrays = build_block_layouts(
        art.src, art.dst, art.pad_inner, art.n_ext, pi, pe, occupancy_min=4,
        tile_budget_bytes=budget, tile_r=tile, tile_c=tile)
    assert not any(k.endswith("tiles_bwd") for k in arrays)
    assert arrays["blk_order_bwd"].dtype == np.int32
    stack = arrays["blk_tiles_fwd"]
    assert stack[0].nbytes <= budget
    real = (arrays["blk_rowb_fwd"] < fwd.n_row_blocks).sum(axis=1)
    assert real.max() == budget // (tile * tile) == n_free // 2
    for p in range(2):
        order = arrays["blk_order_bwd"][p]
        assert sorted(order.tolist()) == list(range(stack.shape[1]))
        np.testing.assert_array_equal(arrays["blk_rowb_bwd"][p][:real[p]],
                                      arrays["blk_colb_fwd"][p][order][
                                          :real[p]])
        assert (np.diff(arrays["blk_rowb_bwd"][p]) >= 0).all()
    dense = [dense_edge_count(arrays, p) for p in range(2)]
    spec = ModelSpec("graphsage", (6, 8, 5), train_size=art.n_train)
    counts = spmm_counts("hybrid", spec, arrays, 2,
                         spec_pairs={"": (fwd, bwd)}, dense_per_part=dense)
    assert counts["tiles_fwd"] == counts["tiles_bwd"] == int(real.max())
    assert counts["tile_stack_bytes"] == stack[0].nbytes
    n_edges = int((art.dst < art.pad_inner).sum())
    assert counts["dense_share"] == pytest.approx(sum(dense) / n_edges)
    _, _, split, _, _ = build_split_block_layouts(
        art.src, art.dst, art.pad_inner, art.n_ext, pi, pe, occupancy_min=4,
        tile_r=tile, tile_c=tile)
    assert not any(k.endswith("tiles_bwd") for k in split)
    assert {"int_blk_order_bwd", "fro_blk_order_bwd"} <= set(split)


def test_new_defaults_densify_twice_the_tiles():
    """At the defaults (time break-even, one stack in the device bytes both
    stacks used to take) _select_dense picks twice the 512 x 512 tiles of
    the old ones (byte break-even, the same bytes a direction) on a
    clustered stand-in whose tiles thin out from 2,000 edges to 242, the
    shape of the benchmark graph's ranking; every tile it adds carries
    more edges than the time break-even."""
    from bnsgcn_tpu.config import Config
    from bnsgcn_tpu.ops.block_spmm import _select_dense, effective_occupancy
    tile_bytes = 512 * 512
    counts = (242 + 1758 * np.exp(-np.arange(24576) / 6000.0)).astype(
        np.int64)
    tile_id = np.repeat(np.arange(len(counts), dtype=np.int32), counts)
    occ = effective_occupancy(Config().block_occupancy, 512, 512)
    assert occ == 256
    new = _select_dense(tile_id, occ, Config().block_tile_budget_mb << 20,
                        tile_bytes, need_inverse=False,
                        n_tiles=len(counts))[3]
    old = _select_dense(tile_id, 512, 2048 << 20, tile_bytes,
                        need_inverse=False, n_tiles=len(counts))[3]
    assert int(old.sum()) == 8192
    assert int(new.sum()) == 2 * int(old.sum()) == 16384
    assert (counts[new & ~old] >= occ).all()


@pytest.mark.parametrize("n_parts", [1, 2])
def test_place_blocks_copies_a_large_shard_in_chunks(monkeypatch, n_parts):
    """A tile stack whose per-device shard reaches the slow-transfer size is
    placed in pieces along its tile axis into one donated buffer: the same
    values and sharding as one device_put, the last piece short; smaller
    arrays go through device_put as they are."""
    from bnsgcn_tpu import trainer
    from bnsgcn_tpu.parallel.mesh import make_parts_mesh
    monkeypatch.setattr(trainer, "_PUT_SLOW_BYTES", 1 << 12)
    monkeypatch.setattr(trainer, "_PUT_CHUNK_BYTES", 1 << 10)
    puts = []
    real_put = jax.device_put
    monkeypatch.setattr(jax, "device_put",
                        lambda x, *a, **k: puts.append(np.shape(x))
                        or real_put(x, *a, **k))
    mesh = make_parts_mesh(n_parts)
    rng = np.random.default_rng(5)
    tiles = rng.integers(-127, 128, (n_parts, 37, 8, 16)).astype(np.int8)
    rowb = np.arange(n_parts * 37, dtype=np.int32).reshape(n_parts, 37)
    out = trainer.place_blocks({"blk_tiles_fwd": tiles, "blk_rowb_fwd": rowb},
                               mesh)
    np.testing.assert_array_equal(np.asarray(out["blk_tiles_fwd"]), tiles)
    np.testing.assert_array_equal(np.asarray(out["blk_rowb_fwd"]), rowb)
    assert out["blk_tiles_fwd"].sharding == out["blk_rowb_fwd"].sharding
    # 37 tiles of 128 B a shard, 1 KiB pieces: 8 + 8 + 8 + 8 + 5
    assert puts == [(n_parts, 8, 8, 16)] * 4 + [(n_parts, 5, 8, 16),
                                                 (n_parts, 37)]
