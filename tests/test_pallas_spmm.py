"""Pallas ELL bucket kernel vs jnp reference (interpret mode on CPU).

The kernel is a STUDY ARTIFACT living in tools/pallas_spmm.py (round 5: the
unrolled column-chain accumulation beat it on hardware and the dispatch was
retired); its interpreter checks are kept but slow-marked, out of the
default (tier-1) run. test_ell_accum_modes_agree pins the LIVE ops/ell
accumulation paths and stays in the default tier."""

import jax.numpy as jnp
import numpy as np
import pytest

from bnsgcn_tpu.data.artifacts import build_artifacts
from bnsgcn_tpu.data.graph import synthetic_graph
from bnsgcn_tpu.data.partitioner import partition_graph
from bnsgcn_tpu.ops.ell import build_layouts
from tools.pallas_spmm import pallas_bucket_sum, pallas_ell_apply
from bnsgcn_tpu.ops.spmm import agg_sum


@pytest.mark.slow
def test_bucket_sum_matches_gather():
    rng = np.random.default_rng(0)
    n, h_dim, r, w = 50, 8, 16, 4
    hp = jnp.asarray(rng.normal(size=(n + 1, h_dim)).astype(np.float32))
    hp = hp.at[n].set(0.0)
    idx = jnp.asarray(rng.integers(0, n + 1, size=(r, w)).astype(np.int32))
    out = pallas_bucket_sum(hp, idx, interpret=True)
    expect = np.asarray(hp)[np.asarray(idx)].sum(axis=1)
    np.testing.assert_allclose(np.asarray(out), expect, rtol=1e-5, atol=1e-5)


@pytest.mark.slow
def test_pallas_ell_apply_matches_segment():
    g = synthetic_graph(n_nodes=60, avg_degree=6, n_feat=5, seed=2,
                        power_law=True)
    art = build_artifacts(g, partition_graph(g, 1))
    fs, bs, arrays = build_layouts(art.src, art.dst, art.pad_inner, art.n_ext)
    idx_list = [jnp.asarray(arrays[f"fwd_idx_{k}"][0])
                for k in range(len(fs.widths))]
    perm = jnp.asarray(arrays["fwd_perm"][0])
    rng = np.random.default_rng(3)
    h = jnp.asarray(rng.normal(size=(art.n_ext, 5)).astype(np.float32))
    out = pallas_ell_apply(fs, idx_list, perm, h, interpret=True)
    expect = agg_sum(h, jnp.asarray(art.src[0]), jnp.asarray(art.dst[0]),
                     art.pad_inner)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.slow
def test_pallas_bucket_reduce_matches_sum():
    rng = np.random.default_rng(5)
    g = jnp.asarray(rng.normal(size=(24, 8, 16)).astype(np.float32))
    from tools.pallas_spmm import pallas_bucket_reduce
    out = pallas_bucket_reduce(g, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(g.sum(1)),
                               rtol=1e-5, atol=1e-5)


def test_ell_accum_modes_agree():
    """The ELL accumulation strategies must be numerically interchangeable:
    'unroll' (the TPU/headline path, forced here via accum) vs 'reduce'
    (the fp8/off-TPU materializing path). Replaces the retired
    Pallas-vs-jnp comparison, which became vacuous once the
    pallas_bucket_reduce dispatch was removed from _bucket_sum (round 5)."""
    g = synthetic_graph(n_nodes=40, avg_degree=5, n_feat=4, seed=7)
    art = build_artifacts(g, partition_graph(g, 1))
    fs, bs, arrays = build_layouts(art.src, art.dst, art.pad_inner, art.n_ext)
    from bnsgcn_tpu.ops.ell import make_ell_spmm
    spmm_u = make_ell_spmm(fs, bs, len(fs.widths), len(bs.widths),
                           accum="unroll")
    spmm_r = make_ell_spmm(fs, bs, len(fs.widths), len(bs.widths),
                           accum="reduce")
    a0 = {k: jnp.asarray(v[0]) for k, v in arrays.items()}
    h = jnp.asarray(np.random.default_rng(8).normal(
        size=(art.n_ext, 4)).astype(np.float32))
    np.testing.assert_allclose(np.asarray(spmm_u(a0, h)),
                               np.asarray(spmm_r(a0, h)),
                               rtol=1e-5, atol=1e-5)
