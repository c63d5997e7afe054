"""The plain reference at tiny size: its blocked segment sum against a dense
adjacency, its transpose, and the control and the planted fault of `correct`
(both have to come out as not correct)."""

import numpy as np
import pytest

import bench_tiny
from benchmarks import graphgen
from benchmarks.reference import check, sage

MODEL = {"model": "graphsage", "n_layers": 4, "n_hidden": 32, "n_linear": 0,
         "dropout": 0.5, "lr": 0.01, "use_pp": True, "dtype": "bfloat16",
         "n_class": 5, "multilabel": False}
LAYOUT = sage.one_part_layout(2000, 2000)


def hub_graph(n=300, e=6000, hub_in=1500, seed=0):
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n, e + hub_in)
    dst = np.concatenate([rng.integers(0, n, e), np.full(hub_in, 7)])
    return src, dst


def test_segment_sum_equals_dense_adjacency():
    n = 300
    src, dst = hub_graph(n)
    levels, seg_row = sage.build_seg_levels(dst, src, n, n)
    assert len(levels) >= 3 and levels[-1].shape[0] == n
    assert seg_row.shape[0] == levels[0].shape[0]
    x = np.random.default_rng(1).normal(size=(n, 9)).astype(np.float32)
    dense = np.zeros((n, n))
    np.add.at(dense, (dst, src), 1.0)
    got = np.asarray(sage.seg_sum([sage.slot_major(t) for t in levels], x))
    np.testing.assert_allclose(got, dense @ x, rtol=1e-5, atol=1e-4)


def test_aggregate_backward_is_the_transposed_sum():
    import jax
    import jax.numpy as jnp
    n = 200
    src, dst = hub_graph(n, 3000, 100)
    t = sage.build_graph_tables(src, dst, n)
    fwd, bwd = sage.split_tables(t)
    fwd = [sage.slot_major(a) for a in fwd]
    bwd = [sage.slot_major(a) for a in bwd]
    rng = np.random.default_rng(2)
    h = jnp.asarray(rng.normal(size=(n, 4)), jnp.float32)
    c = rng.normal(size=(n, 4)).astype(np.float32)
    g = jax.grad(lambda x: jnp.sum(sage.aggregate(x, fwd, bwd) * c))(h)
    dense = np.zeros((n, n))
    np.add.at(dense, (dst, src), 1.0)
    np.testing.assert_allclose(np.asarray(g), dense.T @ c, rtol=1e-5,
                               atol=1e-4)
    np.testing.assert_array_equal(t["in_deg"], np.bincount(dst, minlength=n))


def test_quantizer_rounds_to_the_lower_type():
    import jax.numpy as jnp
    q = sage.quantizer("float8_e4m3fn")
    x = jnp.asarray([1.0, 1.01, 1.07, 1000.0, -0.3], jnp.float32)
    got = np.asarray(q(x))
    assert got[0] == 1.0 and got[1] == 1.0 and got[2] == 1.125
    assert got[3] == 448.0                     # clipped, never NaN
    assert sage.quantizer(None)(x) is x


@pytest.fixture(scope="module")
def tiny():
    params = {"n_nodes": 3000, "avg_degree": 30, "n_feat": 24, "n_class": 5,
              "n_comm": 5, "n_train": 2000, "n_val": 300}
    g = graphgen.training_graph(graphgen.make_graph(params, 7), True)
    tables = sage.build_graph_tables(g["src"], g["dst"], g["n_nodes"])
    return g, tables


@pytest.fixture(scope="module")
def want(tiny):
    g, tables = tiny
    return {s: sage.run_steps(g, tables, MODEL, s, LAYOUT, 3) for s in (1, 2, 3)}


def verdict(got, want):
    nums = check.compare(got, want, bench_tiny.TINY_LIMITS)
    return all(v <= lim for v, lim in nums.values()), nums


def test_reference_repeats_itself(tiny, want):
    g, tables = tiny
    again = sage.run_steps(g, tables, MODEL, 1, LAYOUT, 3)
    ok, nums = verdict(again, want[1])
    assert ok and all(v == 0.0 for v, _ in nums.values())
    assert want[1]["losses"] != want[2]["losses"]
    assert set(want[1]["grad1"]) == set(want[1]["dparam"])
    assert len(want[1]["grad1"]) == 20


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_in_fp8_is_not_correct(tiny, want, seed):
    g, tables = tiny
    got = sage.run_steps(g, tables, MODEL, seed, LAYOUT, 3,
                         quant="float8_e4m3fn")
    ok, nums = verdict(got, want[seed])
    assert not ok, nums


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_half_of_the_batch_left_out_is_not_correct(tiny, want, seed):
    g, tables = tiny
    half = np.ones(g["n_nodes"], np.float32)
    half[g["n_nodes"] // 2:] = 0.0
    got = sage.run_steps(g, tables, MODEL, seed, LAYOUT, 3, row_weight=half)
    ok, nums = verdict(got, want[seed])
    assert not ok, nums


def test_dead_leaves_are_left_out_by_rule_not_by_name():
    want = {"a": 1.0, "b": 2.0, "c": 1e-5, "d": 3.0}
    assert sorted(check.live_leaves(want)) == ["a", "b", "d"]
    prog = {"losses": [1.0, 1.0, 1.0], "grad1": dict(want),
            "dparam": {"a": 1.0, "b": 2.0, "c": 50.0, "d": 3.0}}
    ref = {"losses": [1.0, 1.0, 1.0], "grad1": want,
           "dparam": {"a": 1.0, "b": 2.0, "c": 1.0, "d": 3.0}}
    nums = check.compare(prog, ref, bench_tiny.TINY_LIMITS)
    assert nums["dparam_gap"][0] == 0.0


def test_gap_is_between_norms_against_leaf_or_median():
    want = {"a": 1.0, "b": 2.0, "c": 1e-6}
    prog = {"a": 1.1, "b": 2.0, "c": 0.1}
    gap, at = check.worst_leaf_gap(prog, want)
    assert at == "a" and gap == pytest.approx(0.1)     # c: 0.1 / median 1.0
    gap, at = check.worst_leaf_gap({"a": 1.0, "b": 0.0, "c": 1e-6}, want)
    assert at == "b" and gap == pytest.approx(1.0)     # a leaf that stood still
    with pytest.raises(ValueError):
        check.worst_leaf_gap({"a": 1.0}, want)


def test_recomputing_each_layer_changes_nothing(tiny, want, monkeypatch):
    """Large graphs recompute each layer in the backward pass (so that the
    reference fits beside them); the numbers are the same."""
    g, tables = tiny
    monkeypatch.setattr(sage, "REMAT_BYTES", 0)
    got = sage.run_steps(g, tables, MODEL, 2, LAYOUT, 3)
    ok, nums = verdict(got, want[2])
    assert ok and all(v < 1e-5 for v, _ in nums.values())
