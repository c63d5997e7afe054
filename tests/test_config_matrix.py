"""Config-matrix smoke: one train step compiles and yields a finite loss for
every supported flag combination (models x pp x norm x spmm x dtype x remat
x n_linear x edge_chunk). Locks rarely-hit paths against regressions."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bnsgcn_tpu.config import Config
from bnsgcn_tpu.data.artifacts import build_artifacts
from bnsgcn_tpu.data.graph import synthetic_graph
from bnsgcn_tpu.data.partitioner import partition_graph
from bnsgcn_tpu.models.gnn import ModelSpec, init_params
from bnsgcn_tpu.parallel.mesh import make_parts_mesh
from bnsgcn_tpu.trainer import (build_block_arrays, build_step_fns,
                                init_training, place_blocks, place_replicated)

CASES = [
    # (model, use_pp, norm, spmm, dtype, remat, n_linear, edge_chunk)
    ("gcn",       False, "layer", "ell",     "float32",  False, 0, 0),
    ("gcn",       True,  None,    "segment", "float32",  False, 0, 64),
    ("gcn",       True,  "batch", "ell",     "bfloat16", True,  0, 0),
    ("graphsage", False, "batch", "segment", "float32",  False, 0, 0),
    ("graphsage", True,  "layer", "ell",     "bfloat16", False, 1, 0),
    ("graphsage", False, "layer", "ell",     "float32",  True,  0, 0),
    ("graphsage", True,  None,    "segment", "float32",  False, 2, 128),
    ("gat",       True,  "layer", "ell",     "float32",  False, 0, 0),
    ("gat",       True,  "batch", "segment", "float32",  True,  1, 0),
    ("gat",       True,  "layer", "ell",     "bfloat16", False, 0, 0),
]


@pytest.fixture(scope="module")
def graph():
    return synthetic_graph(n_nodes=64, avg_degree=5, n_feat=6, n_class=3,
                           seed=99)


@pytest.mark.parametrize("model,use_pp,norm,spmm,dtype,remat,n_linear,edge_chunk",
                         CASES)
@pytest.mark.quickgate
def test_one_step_finite(graph, model, use_pp, norm, spmm, dtype, remat,
                         n_linear, edge_chunk):
    g = graph
    n_layers = 3
    cfg = Config(model=model, dropout=0.2, use_pp=use_pp, norm=norm, spmm=spmm,
                 dtype=dtype, remat=remat, n_linear=n_linear,
                 edge_chunk=edge_chunk, n_train=g.n_train, lr=0.01,
                 sampling_rate=0.5, heads=2)
    sizes = (6,) + (8,) * (n_layers - 1) + (3,)
    spec = ModelSpec(model, sizes, n_linear=n_linear, norm=norm, dropout=0.2,
                     use_pp=(True if model == "gat" else use_pp), heads=2,
                     train_size=g.n_train)
    mesh = make_parts_mesh(4)
    art = build_artifacts(g, partition_graph(g, 4, method="random", seed=7),
                          edge_mult=max(edge_chunk, 8))
    fns, hspec, tables, tables_full = build_step_fns(cfg, spec, art, mesh)
    blk_np = build_block_arrays(art, model)
    blk_np.update(fns.extra_blk)
    for k in fns.drop_blk_keys:
        blk_np.pop(k, None)
    blk = place_blocks(blk_np, mesh)
    jdtype = jnp.bfloat16 if dtype == "bfloat16" else jnp.float32
    if dtype == "bfloat16":
        blk["feat"] = blk["feat"].astype(jdtype)
    tb = place_replicated(tables, mesh)
    if spec.use_pp:
        out = fns.precompute(blk, place_replicated(tables_full, mesh)).astype(
            jdtype if dtype == "bfloat16" else out_dtype_default(blk))
        if model == "gat":
            blk["feat0_ext"] = out
        else:
            blk["feat"] = out
    params, state = init_params(jax.random.key(0), spec, dtype=jdtype)
    params = place_replicated(params, mesh)
    state = place_replicated(state, mesh)
    _, _, opt = init_training(cfg, spec, mesh, dtype=jdtype)
    params, state, opt, loss = fns.train_step(
        params, state, opt, jnp.uint32(0), blk, tb,
        jax.random.key(0), jax.random.key(1))
    assert np.isfinite(float(loss)), (model, use_pp, norm, spmm, dtype)


def out_dtype_default(blk):
    return blk["feat"].dtype


HALO_CASES = [
    # (model, spmm, halo_exchange, halo_wire, dtype)
    ("graphsage", "hybrid", "padded", "native", "float32"),
    ("gcn",       "hybrid", "shift",  "fp8",    "bfloat16"),
    ("graphsage", "ell",    "shift",  "bf16",   "float32"),
    ("gat",       "ell",    "shift",  "fp8",    "float32"),
    ("graphsage", "hybrid", "shift",  "fp8",    "bfloat16"),
    # exact-bytes ragged exchange x models x wires, and the auto selector
    # resolving inside build_step_fns
    ("graphsage", "ell",    "ragged", "int8",   "float32"),
    ("gcn",       "hybrid", "ragged", "bf16",   "bfloat16"),
    ("gat",       "ell",    "ragged", "fp8",    "float32"),
    ("graphsage", "hybrid", "auto",   "native", "float32"),
]


@pytest.mark.parametrize("model,spmm,halo_exchange,halo_wire,dtype", HALO_CASES)
def test_one_step_finite_halo_variants(graph, model, spmm, halo_exchange,
                                       halo_wire, dtype):
    """New round-2 flags: hybrid SpMM x shift exchange x fp8/bf16 wire."""
    g = graph
    cfg = Config(model=model, dropout=0.2, use_pp=True, norm="layer",
                 spmm=spmm, dtype=dtype, halo_exchange=halo_exchange,
                 halo_wire=halo_wire, n_train=g.n_train, lr=0.01,
                 sampling_rate=0.5, heads=2)
    sizes = (6, 8, 8, 3)
    spec = ModelSpec(model, sizes, norm="layer", dropout=0.2, use_pp=True,
                     heads=2, train_size=g.n_train)
    mesh = make_parts_mesh(4)
    art = build_artifacts(g, partition_graph(g, 4, method="random", seed=7))
    fns, hspec, tables, tables_full = build_step_fns(cfg, spec, art, mesh)
    blk_np = build_block_arrays(art, model)
    blk_np.update(fns.extra_blk)
    for k in fns.drop_blk_keys:
        blk_np.pop(k, None)
    blk = place_blocks(blk_np, mesh)
    jdtype = jnp.bfloat16 if dtype == "bfloat16" else jnp.float32
    if dtype == "bfloat16":
        blk["feat"] = blk["feat"].astype(jdtype)
    tb = place_replicated(tables, mesh)
    out = fns.precompute(blk, place_replicated(tables_full, mesh)).astype(jdtype)
    if model == "gat":
        blk["feat0_ext"] = out
    else:
        blk["feat"] = out
    params, state = init_params(jax.random.key(0), spec, dtype=jdtype)
    params = place_replicated(params, mesh)
    state = place_replicated(state, mesh)
    _, _, opt = init_training(cfg, spec, mesh, dtype=jdtype)
    for e in range(2):
        params, state, opt, loss = fns.train_step(
            params, state, opt, jnp.uint32(e), blk, tb,
            jax.random.key(0), jax.random.key(1))
    assert np.isfinite(float(loss)), (model, spmm, halo_exchange, halo_wire)


def test_one_step_finite_all_int8_recipe(graph):
    """The all-int8 TPU recipe: hybrid SpMM with int8 residual gathers +
    int8 MXU dense tiles + int8 halo wire + shift exchange, bf16 compute —
    the preferred narrow-format stack on v5e (e4m3 decode is emulated and
    measured slower on a v5e, 2026-07-29: 3.07 vs 1.67 s/epoch)."""
    g = graph
    cfg = Config(model="graphsage", dropout=0.2, use_pp=True, norm="layer",
                 spmm="hybrid", dtype="bfloat16", halo_exchange="shift",
                 halo_wire="int8", spmm_gather="int8", spmm_dense="int8",
                 n_train=g.n_train, lr=0.01, sampling_rate=0.5)
    sizes = (6, 8, 8, 3)
    spec = ModelSpec("graphsage", sizes, norm="layer", dropout=0.2,
                     use_pp=True, train_size=g.n_train)
    mesh = make_parts_mesh(4)
    art = build_artifacts(g, partition_graph(g, 4, method="random", seed=7))
    fns, hspec, tables, tables_full = build_step_fns(cfg, spec, art, mesh)
    blk_np = build_block_arrays(art, "graphsage")
    blk_np.update(fns.extra_blk)
    for k in fns.drop_blk_keys:
        blk_np.pop(k, None)
    blk = place_blocks(blk_np, mesh)
    blk["feat"] = blk["feat"].astype(jnp.bfloat16)
    tb = place_replicated(tables, mesh)
    blk["feat"] = fns.precompute(
        blk, place_replicated(tables_full, mesh)).astype(jnp.bfloat16)
    params, state = init_params(jax.random.key(0), spec, dtype=jnp.bfloat16)
    params = place_replicated(params, mesh)
    state = place_replicated(state, mesh)
    _, _, opt = init_training(cfg, spec, mesh, dtype=jnp.bfloat16)
    for e in range(2):
        params, state, opt, loss = fns.train_step(
            params, state, opt, jnp.uint32(e), blk, tb,
            jax.random.key(0), jax.random.key(1))
    assert np.isfinite(float(loss))
