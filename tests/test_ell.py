"""ELL bucketed SpMM == segment_sum SpMM, forward and backward."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bnsgcn_tpu.data.artifacts import build_artifacts
from bnsgcn_tpu.data.graph import synthetic_graph
from bnsgcn_tpu.data.partitioner import partition_graph
from bnsgcn_tpu.ops.ell import (ELL_BLOCK, ELL_SPLIT_CAP, GeoAccum,
                                _choose_widths, build_ell_numpy,
                                build_layouts, compute_geometry,
                                make_ell_spmm)
from bnsgcn_tpu.ops.spmm import agg_sum


@pytest.mark.parametrize("seed", [0, 1])
def test_ell_single_part_matches_segment(seed):
    g = synthetic_graph(n_nodes=70, avg_degree=7, n_feat=5, seed=seed,
                        power_law=True)
    art = build_artifacts(g, partition_graph(g, 1))
    n_ext = art.pad_inner + art.n_parts * art.pad_boundary
    fwd_spec, bwd_spec, arrays = build_layouts(art.src, art.dst,
                                               art.pad_inner, n_ext)
    spmm = make_ell_spmm(fwd_spec, bwd_spec,
                         len(fwd_spec.widths), len(bwd_spec.widths))
    arrays0 = {k: jnp.asarray(v[0]) for k, v in arrays.items()}
    h = jnp.asarray(np.random.default_rng(seed).normal(
        size=(n_ext, 5)).astype(np.float32))
    out_ell = spmm(arrays0, h)
    out_seg = agg_sum(h, jnp.asarray(art.src[0]), jnp.asarray(art.dst[0]),
                      art.pad_inner)
    np.testing.assert_allclose(np.asarray(out_ell), np.asarray(out_seg),
                               rtol=1e-5, atol=1e-5)


def test_ell_gradient_matches_segment():
    g = synthetic_graph(n_nodes=50, avg_degree=6, n_feat=4, seed=3,
                        power_law=True)
    art = build_artifacts(g, partition_graph(g, 1))
    n_ext = art.pad_inner + art.n_parts * art.pad_boundary
    fwd_spec, bwd_spec, arrays = build_layouts(art.src, art.dst,
                                               art.pad_inner, n_ext)
    spmm = make_ell_spmm(fwd_spec, bwd_spec,
                         len(fwd_spec.widths), len(bwd_spec.widths))
    arrays0 = {k: jnp.asarray(v[0]) for k, v in arrays.items()}
    src, dst = jnp.asarray(art.src[0]), jnp.asarray(art.dst[0])
    h = jnp.asarray(np.random.default_rng(4).normal(
        size=(n_ext, 4)).astype(np.float32))
    w = jnp.asarray(np.random.default_rng(5).normal(
        size=(art.pad_inner, 4)).astype(np.float32))

    g_ell = jax.grad(lambda h: jnp.sum(spmm(arrays0, h) * w))(h)
    g_seg = jax.grad(lambda h: jnp.sum(agg_sum(h, src, dst, art.pad_inner) * w))(h)
    np.testing.assert_allclose(np.asarray(g_ell), np.asarray(g_seg),
                               rtol=1e-5, atol=1e-5)


def test_ell_multi_part_layouts_cover_halo_rows():
    g = synthetic_graph(n_nodes=90, avg_degree=6, n_feat=4, seed=6)
    art = build_artifacts(g, partition_graph(g, 4, method="random", seed=1))
    n_ext = art.pad_inner + art.n_parts * art.pad_boundary
    fwd_spec, bwd_spec, arrays = build_layouts(art.src, art.dst,
                                               art.pad_inner, n_ext)
    spmm = make_ell_spmm(fwd_spec, bwd_spec,
                         len(fwd_spec.widths), len(bwd_spec.widths))
    rng = np.random.default_rng(7)
    for p in range(art.n_parts):
        arrays_p = {k: jnp.asarray(v[p]) for k, v in arrays.items()}
        h = jnp.asarray(rng.normal(size=(n_ext, 4)).astype(np.float32))
        out_ell = spmm(arrays_p, h)
        out_seg = agg_sum(h, jnp.asarray(art.src[p]), jnp.asarray(art.dst[p]),
                          art.pad_inner)
        np.testing.assert_allclose(np.asarray(out_ell), np.asarray(out_seg),
                                   rtol=1e-5, atol=1e-5)
        # backward covers extended (halo) rows too
        ge = jax.grad(lambda h: jnp.sum(spmm(arrays_p, h) ** 2))(h)
        gs = jax.grad(lambda h: jnp.sum(agg_sum(
            h, jnp.asarray(art.src[p]), jnp.asarray(art.dst[p]),
            art.pad_inner) ** 2))(h)
        np.testing.assert_allclose(np.asarray(ge), np.asarray(gs),
                                   rtol=1e-5, atol=1e-5)


def test_split_rows_hub_node_matches_segment():
    """A hub with degree >> ELL_SPLIT_CAP exercises the split-row combine."""
    rng = np.random.default_rng(9)
    n, hub_deg = 400, 1000
    src = np.concatenate([rng.integers(0, n, 800),
                          rng.integers(0, n, hub_deg)]).astype(np.int64)
    dst = np.concatenate([rng.integers(1, n, 800),
                          np.zeros(hub_deg, np.int64)]).astype(np.int64)
    src_a, dst_a = src[None], dst[None]
    fs, bs, arrays = build_layouts(src_a, dst_a, n, n)
    assert fs.n_split > 0 and fs.n_chunks >= hub_deg // 128
    spmm = make_ell_spmm(fs, bs, len(fs.widths), len(bs.widths))
    a0 = {k: jnp.asarray(v[0]) for k, v in arrays.items()}
    h = jnp.asarray(rng.normal(size=(n, 6)).astype(np.float32))
    out = spmm(a0, h)
    expect = agg_sum(h, jnp.asarray(src, jnp.int32), jnp.asarray(dst, jnp.int32), n)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-5, atol=1e-4)
    # gradient through the split path
    ge = jax.grad(lambda h: jnp.sum(spmm(a0, h) ** 2))(h)
    gs = jax.grad(lambda h: jnp.sum(agg_sum(
        h, jnp.asarray(src, jnp.int32), jnp.asarray(dst, jnp.int32), n) ** 2))(h)
    np.testing.assert_allclose(np.asarray(ge), np.asarray(gs), rtol=1e-5, atol=1e-4)


def test_build_ell_numpy_basics():
    src = np.array([0, 1, 2, 3, 4, 5, 0])
    dst = np.array([0, 0, 0, 1, 1, 2, 3])
    widths, rows, idx, perm, _, _, _ = build_ell_numpy(src, dst, n_rows=5, n_src=6)
    # row 4 has degree 0 -> routed to the trailing zero row
    total = sum(rows)
    assert perm[4] == total
    h = np.eye(6, dtype=np.float32)
    # manual check via dense
    a = np.zeros((5, 6))
    np.add.at(a, (dst, src), 1.0)
    from bnsgcn_tpu.ops.ell import EllSpec, _ell_apply
    import jax.numpy as jnp
    spec = EllSpec(widths=widths, rows=rows, n_rows=5, n_src=6)
    out = _ell_apply(spec, [jnp.asarray(i) for i in idx], jnp.asarray(perm),
                     jnp.asarray(h))
    np.testing.assert_allclose(np.asarray(out), a @ h, atol=1e-6)


import pytest


@pytest.mark.parametrize("qmode", ["fp8", "int8"])
def test_quantized_gather_close_to_native(qmode):
    """gather_dtype='fp8'/'int8' ELL SpMM is within quantization tolerance
    of native, forward and backward, and is not a silent no-op. int8 is the
    v5e-native 1-byte wire (fp8 decode is emulated and measured slower than
    bf16 on hardware); its bucket sums run exactly in int32."""
    import jax
    import jax.numpy as jnp
    from bnsgcn_tpu.data.artifacts import build_artifacts
    from bnsgcn_tpu.data.graph import synthetic_graph
    from bnsgcn_tpu.data.partitioner import partition_graph
    from bnsgcn_tpu.ops.ell import build_layouts, make_ell_spmm

    g = synthetic_graph(n_nodes=200, avg_degree=8, n_feat=4, seed=71,
                        power_law=True)
    art = build_artifacts(g, partition_graph(g, 2, method="random", seed=1))
    f_spec, b_spec, arrays = build_layouts(art.src, art.dst, art.pad_inner,
                                           art.n_ext)
    rng = np.random.default_rng(0)
    h = jnp.asarray(rng.normal(size=(art.n_ext, 16)), jnp.float32)
    cot = jnp.asarray(rng.normal(size=(art.pad_inner, 16)), jnp.float32)
    a0 = {k: jnp.asarray(v[0]) for k, v in arrays.items()}
    outs, grads = {}, {}
    for mode in ("native", qmode):
        spmm = make_ell_spmm(f_spec, b_spec, len(f_spec.widths),
                             len(b_spec.widths), gather_dtype=mode)
        outs[mode] = np.asarray(spmm(a0, h))
        grads[mode] = np.asarray(jax.grad(
            lambda hh: jnp.sum(spmm(a0, hh) * cot))(h))
    scale = np.abs(outs["native"]).max() + 1e-9
    assert np.abs(outs[qmode] - outs["native"]).max() / scale < 0.05
    assert not np.allclose(outs[qmode], outs["native"])   # really quantized
    gscale = np.abs(grads["native"]).max() + 1e-9
    assert np.abs(grads[qmode] - grads["native"]).max() / gscale < 0.05


def test_bucket_sum_int8_unroll_exact():
    """int8 rows unroll in int32 chains == the reduce path's int32 sums,
    bit-exact (both are exact integer sums of |q|<=127 over <=128 rows)."""
    import jax.numpy as jnp
    from bnsgcn_tpu.ops.ell import _bucket_sum
    rng = np.random.default_rng(6)
    for w in (2, 16, 32, 128):
        hp = jnp.asarray(rng.integers(-127, 128, size=(400, 16)), jnp.int8)
        idx = jnp.asarray(rng.integers(0, 400, size=(53, w)).astype(np.int32))
        a = np.asarray(_bucket_sum(hp, idx, w, accum="unroll"))
        b = np.asarray(_bucket_sum(hp, idx, w, accum="reduce"))
        assert a.dtype == np.int32 and b.dtype == np.int32
        np.testing.assert_array_equal(a, b)


def test_bucket_sum_fp8_unroll_raises():
    import jax.numpy as jnp
    import pytest as _pytest
    from bnsgcn_tpu.ops.ell import _bucket_sum
    hp = jnp.zeros((8, 4), jnp.float8_e4m3fn)
    idx = jnp.zeros((3, 4), jnp.int32)
    with _pytest.raises(ValueError):
        _bucket_sum(hp, idx, 4, accum="unroll")


@pytest.mark.parametrize("dtype,h_dim", [
    ("float32", 16), ("bfloat16", 41), ("bfloat16", 256), ("int8", 41),
    ("int8", 256)])
def test_bucket_sum_unroll_matches_reduce(dtype, h_dim):
    """The TPU-default unrolled f32-chain accumulation equals the
    materialize-then-reduce path (f32 chains vs the reduce path's sum:
    compare in the reduce path's own precision envelope; int8 rows sum
    exactly in int32 both ways), at the widths a narrowing layer and a
    hidden layer aggregate. Its gathers carry no compare or select on the
    index vectors (jnp's wrap of negative indices): the only compare left
    is a scan's scalar loop counter."""
    import re
    import jax.numpy as jnp
    from bnsgcn_tpu.ops.ell import _bucket_sum, _unroll_sum
    rng = np.random.default_rng(5)
    # 16 = largest single unrolled chain, 32 = smallest 2-block scan
    for w in (2, 4, 8, 16, 32, 128):
        x = rng.normal(size=(500, h_dim))
        hp = (jnp.asarray(np.clip(np.round(40 * x), -127, 127), jnp.int8)
              if dtype == "int8" else jnp.asarray(x, dtype))
        idx = jnp.asarray(rng.integers(0, 500, size=(37, w)).astype(np.int32))
        a = np.asarray(_bucket_sum(hp, idx, w, accum="unroll"))
        b = np.asarray(_bucket_sum(hp, idx, w, accum="reduce"))
        assert a.dtype == b.dtype
        if dtype == "int8":
            np.testing.assert_array_equal(a, b)
        elif dtype == "bfloat16":
            # one rounding to bfloat16 of two f32 sums: a unit in the last
            # place apart at most
            np.testing.assert_allclose(a.astype(np.float32),
                                       b.astype(np.float32),
                                       rtol=2.0 ** -7, atol=2e-5)
        else:
            np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5)
        text = _unroll_sum.lower(hp, idx).as_text()
        for line in text.splitlines():
            if re.search(r"stablehlo\.(compare|select)", line):
                sig = line.rsplit(":", 1)[1]
                assert all("x" not in t for t in
                           re.findall(r"tensor<([^>]*)>", sig)), line


# ----------------------------------------------------------------------------
# the width ladder and the split rows' tails (PR 28)
# ----------------------------------------------------------------------------

def _slots(g):
    return sum(r * w for r, w in zip(g["rows"], g["widths"]))


@pytest.mark.parametrize("max_deg,cap,last", [
    (1, 128, 4), (4, 128, 4), (5, 128, 8), (17, 128, 32), (33, 128, 48),
    (100, 128, 112), (128, 128, 128), (129, 128, 128), (9539, 128, 128),
    (50, None, 64), (300, None, 320), (9539, None, 10240), (40, 32, 32)])
def test_width_ladder(max_deg, cap, last):
    """4, 8, 16, then steps of _bucket_sum's block (growing to an eighth of
    the width past 256): every width stays on the unroll path, the ladder is
    ascending and ends at the first width that holds min(max degree, cap):
    the cap itself when rows split."""
    w = _choose_widths(max_deg, cap=cap)
    assert w[:3] == (4, 8, 16)[:len(w)] and w[-1] == last
    assert all(x <= ELL_BLOCK or x % ELL_BLOCK == 0 for x in w)
    assert all(a < b for a, b in zip(w, w[1:]))
    assert all(b - a <= max(ELL_BLOCK, a // 8) for a, b in zip(w[2:], w[3:]))
    top = min(max_deg, cap) if cap else max_deg
    assert w[-1] >= top and (len(w) == 1 or w[-2] < top)
    if cap and max_deg > cap:
        assert w[-1] == cap
    if cap == ELL_SPLIT_CAP and max_deg >= 113:
        assert w == (4, 8, 16, 32, 48, 64, 80, 96, 112, 128)


def test_ladder_block_is_the_unroll_block():
    """A ladder width never leaves `_bucket_sum`'s unroll path: the scan
    over 16-column blocks takes it whole (the reduce path it would fall to
    materialises the gathered rows)."""
    import jax
    from bnsgcn_tpu.ops.ell import _bucket_sum
    hp = jnp.zeros((9, 4), jnp.float32)
    for w in _choose_widths(1000, cap=ELL_SPLIT_CAP)[3:]:
        idx = jnp.zeros((5, w), jnp.int32)
        text = str(jax.make_jaxpr(
            lambda h, i: _bucket_sum(h, i, w, accum="unroll"))(hp, idx))
        assert "scan" in text and f"5,{w},4" not in text.replace(" ", "")


@pytest.mark.parametrize("case", ["power_law", "hand_made"])
def test_slots_per_edge(case):
    if case == "power_law":
        # mean about 90 with a tail over the cap, as the benchmark's residual
        rng = np.random.default_rng(28)
        deg = np.minimum((rng.pareto(1.6, 20000) + 1.0) * 34.0,
                         9000.0).astype(np.int64)
        assert 80 < deg.mean() < 100 and (deg > ELL_SPLIT_CAP).mean() > 0.1
        acc = GeoAccum(ELL_SPLIT_CAP)
        acc.add_part(deg)
        assert _slots(acc.finish()) / deg.sum() < 1.12
        return
    # rows of degree 1, 4, 5, 16, 17, 33, 100, 128, 129 (tail 1), 256 (no
    # tail), 300 (tail 44) and 383 (tail 127): rows per bucket padded to 8
    deg = np.asarray([1, 4, 5, 16, 17, 33, 100, 128, 129, 256, 300, 383, 0])
    acc = GeoAccum(ELL_SPLIT_CAP)
    acc.add_part(deg)
    g = acc.finish()
    assert g["widths"] == [4, 8, 16, 32, 48, 64, 80, 96, 112, 128]
    # 4: {1, 4, tail 1}; 8: {5}; 16: {16}; 32: {17}; 48: {33, tail 44};
    # 112: {100}; 128: {128, tail 127} + 1 + 2 + 2 + 2 cap-wide chunks
    assert g["rows"] == [8, 8, 8, 8, 8, 0, 0, 0, 8, 16]
    assert (g["split"], g["chunks"], g["cap"]) == (8, 16, 128)
    assert _slots(g) == 8 * (4 + 8 + 16 + 32 + 48 + 112) + 16 * 128


def _one_row_graph(deg, n=700, seed=0):
    """Row 0 has in-degree `deg` (distinct sources), the other rows a few
    edges each; source 1 has a large out-degree for the transposed layout."""
    rng = np.random.default_rng(seed)
    hub_src = rng.permutation(np.arange(2, n))[:deg]
    src = np.concatenate([hub_src, rng.integers(2, n, 900),
                          np.ones(deg, np.int64)])
    dst = np.concatenate([np.zeros(deg, np.int64), rng.integers(1, n, 900),
                          rng.permutation(np.arange(1, n))[:deg]])
    return src.astype(np.int64), dst.astype(np.int64), n


@pytest.mark.parametrize("with_geometry", [False, True])
@pytest.mark.parametrize("deg", [
    ELL_SPLIT_CAP, ELL_SPLIT_CAP + 1, 2 * ELL_SPLIT_CAP,
    2 * ELL_SPLIT_CAP + 1, 3 * ELL_SPLIT_CAP - 1])
def test_split_row_tails_match_dense(deg, with_geometry):
    """Rows at and around multiples of the cap (tail absent, of length 1, of
    length cap - 1): forward and VJP equal the dense product, with the
    tables' own row counts and with `row_pad` from a geometry."""
    src, dst, n = _one_row_graph(deg)
    geometry = None
    if with_geometry:
        # pads of a larger graph of the same ladder: every bucket gets room
        geometry = compute_geometry(src[None], dst[None], n, n)
        for g in geometry.values():
            g["rows"] = [r + 8 for r in g["rows"]]
            g["split"] += 8 * bool(g["split"])
            g["chunks"] += 8 * bool(g["chunks"])
    fs, bs, arrays = build_layouts(src[None], dst[None], n, n,
                                   geometry=geometry)
    split = deg > ELL_SPLIT_CAP
    assert bool(fs.n_split) == split and bool(bs.n_split) == split
    if split:
        # the tail sits in the bucket that fits it, not in the cap's
        tail = deg % ELL_SPLIT_CAP
        cap_rows = (arrays[f"fwd_idx_{len(fs.widths) - 1}"][0, :, 0]
                    < n).sum()
        assert cap_rows == deg // ELL_SPLIT_CAP + (tail > 112)
    a = np.zeros((n, n), np.float32)
    np.add.at(a, (dst, src), 1.0)
    spmm = make_ell_spmm(fs, bs, len(fs.widths), len(bs.widths))
    a0 = {k: jnp.asarray(v[0]) for k, v in arrays.items()}
    rng = np.random.default_rng(1)
    h = jnp.asarray(rng.normal(size=(n, 5)).astype(np.float32))
    cot = rng.normal(size=(n, 5)).astype(np.float32)
    np.testing.assert_allclose(np.asarray(spmm(a0, h)), a @ np.asarray(h),
                               rtol=1e-5, atol=1e-4)
    d_h = jax.grad(lambda x: jnp.sum(spmm(a0, x) * cot))(h)
    np.testing.assert_allclose(np.asarray(d_h), a.T @ cot,
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("cap", [ELL_SPLIT_CAP, 8, None])
def test_geoaccum_merged_equals_compute_geometry(cap):
    """Per-part accumulators merged through their state vectors (the
    multi-host agreement) give the geometry `compute_geometry` gives on the
    stacked parts, tails and cap-wide chunks included, and `build_layouts`
    fills it without a row to spare in some bucket of some part."""
    rng = np.random.default_rng(4)
    n, P, E = 300, 3, 6000
    src = rng.integers(0, n, (P, E))
    dst = np.where(rng.random((P, E)) < 0.5, rng.integers(0, 4, (P, E)),
                   rng.integers(0, n, (P, E)))
    dst[:, -50:] = n                                    # padded edges
    geo = compute_geometry(src, dst, n, n, cap=cap)
    for d, rows_of in (("fwd", dst), ("bwd", src)):
        merged = GeoAccum(cap)
        for p in range(P):
            part = GeoAccum(cap)
            real = dst[p] < n
            part.add_part(np.bincount(rows_of[p][real], minlength=n))
            merged.merge_state(part.state())
        assert merged.finish() == geo[d]
    assert (geo["fwd"]["cap"] is not None) == (cap is not None)
    fs, bs, arrays = build_layouts(src, dst, n, n, cap=cap, geometry=geo)
    for k, (r, w) in enumerate(zip(fs.rows, fs.widths)):
        used = (arrays[f"fwd_idx_{k}"][:, :, 0] < n).sum(axis=1)
        assert r == 0 or 0 <= r - used.max() < 8


def test_stale_geometry_is_refused_with_a_message():
    """A geometry computed under the power-of-two ladder (a meta.json of an
    earlier version) is refused by name, and one whose pads do not hold the
    graph likewise: never an assert inside the builder."""
    src, dst, n = _one_row_graph(300)
    geo = compute_geometry(src[None], dst[None], n, n)
    old = {d: dict(g, widths=[4, 8, 16, 32, 64, 128], rows=g["rows"][:6])
           for d, g in geo.items()}
    with pytest.raises(ValueError, match="re-partition"):
        build_layouts(src[None], dst[None], n, n, geometry=old)
    tight = {d: dict(g, rows=[0] + g["rows"][1:]) for d, g in geo.items()}
    with pytest.raises(ValueError, match="re-partition"):
        build_layouts(src[None], dst[None], n, n, geometry=tight)
    # a small graph's geometry is the same under both ladders and builds
    small = compute_geometry(src[None, -40:], dst[None, -40:], n, n)
    assert small["fwd"]["widths"] in ([4], [4, 8], [4, 8, 16])
    build_layouts(src[None, -40:], dst[None, -40:], n, n, geometry=small)
