"""A four-part cell on the virtual CPU mesh: the harness end to end (boundary
sampling, halo exchange and gradient all-reduce replayed by the whole-graph
reference), and `correct` false with the exchange left out."""

import json
import os

import numpy as np
import pytest

import bench_tiny
from benchmarks.reference import sage


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_tiny.make_root(str(tmp_path_factory.mktemp("bench_parts")))


@pytest.fixture(scope="module")
def first_run(root):
    return bench_tiny.run_cell(root, seed=31, cell="tiny.p4")


def test_four_parts_agree_with_the_whole_graph_reference(first_run):
    rc, res, _ = first_run
    assert rc == 0 and res["correct"] is True
    assert res["device"]["count"] == 4
    # float32 against float32 at `highest`: rounding only
    assert all(v < 1e-5 for v, _ in res["compared"].values())


def test_another_seed_draws_another_boundary_sample(root, first_run):
    rc, res, _ = bench_tiny.run_cell(root, seed=32, cell="tiny.p4")
    assert rc == 0 and res["correct"] is True
    assert res["compared"] != first_run[1]["compared"]


def test_the_exchange_between_chips_left_out(root, first_run):
    """The program's own path without the activation exchange
    (--halo-mode grad-only) in the cell's place: not correct."""
    with open(os.path.join(root, "workloads", "tiny.p4.json")) as f:
        wl = json.load(f)
    wl["flags"] += ["--halo-mode", "grad-only"]
    with open(os.path.join(root, "workloads", "tiny-noex.p4.json"), "w") as f:
        json.dump(wl, f)
    rc, res, _ = bench_tiny.run_cell(root, seed=31, cell="tiny-noex.p4")
    assert rc == 0 and res["correct"] is False
    assert any(v > 1e-3 for v, _ in res["compared"].values())


def _layout_inputs(root):
    from benchmarks import harness
    wl = harness.load_workload("tiny.p4", root)
    cfg = harness.load_config("yelp-tiny", root)
    dirs = bench_tiny.cell_dirs(root, "tiny.p4")
    graph, _ = harness.load_reference_inputs(dirs, edges=True)
    return harness, wl, cfg, dirs, graph


def test_layout_is_read_from_the_partition_on_disk(root, first_run):
    harness, wl, cfg, dirs, graph = _layout_inputs(root)
    lay = harness.read_layout(wl, cfg, dirs, graph)
    assert lay["n_parts"] == 4 and lay["rate"] == 0.25
    assert sorted(np.bincount(lay["part_of"]))[0] > 400
    # a node sits in one row of one part
    key = lay["part_of"].astype(np.int64) * lay["pad_inner"] + lay["row_of"]
    assert len(np.unique(key)) == 2250
    # the in-degrees it is held against are counted from the benchmark's edges
    np.testing.assert_array_equal(
        graph["in_deg"], np.bincount(graph["dst"], minlength=2250))


def _rewrite_part(dirs, part, change):
    path = os.path.join(os.path.dirname(dirs.meta), f"part{part}.npz")
    with np.load(path) as f:
        arrays = {k: f[k] for k in f.files}
    change(arrays)
    np.savez_compressed(path, **arrays)


def _swap_two_ids(a):
    rows = np.nonzero(a["global_nid"] >= 0)[0]
    a["global_nid"][rows[[0, 1]]] = a["global_nid"][rows[[1, 0]]]


def _name_a_node_twice(a):
    rows = np.nonzero(a["global_nid"] >= 0)[0]
    for k in ("global_nid", "feat", "label", "train_mask", "in_deg"):
        a[k][rows[0]] = a[k][rows[1]]


def _lose_an_edge_of_the_degree(a):
    a["in_deg"][np.nonzero(a["global_nid"] >= 0)[0][3]] += 1


@pytest.mark.parametrize("change, says", [
    (_swap_two_ids, "holds other"),
    (_name_a_node_twice, "exactly once"),
    (_lose_an_edge_of_the_degree, "in-degrees"),
])
def test_a_layout_that_departs_from_the_benchmarks_graph_is_refused(
        root, first_run, tmp_path, change, says):
    """The reference replays dropout and sampling by the program's rows: rows
    that do not carry the nodes they name are a fault, not a layout."""
    import shutil
    harness, wl, cfg, dirs, graph = _layout_inputs(root)
    copy = str(tmp_path / "parts")
    shutil.copytree(dirs.parts, copy)
    dirs.meta = os.path.join(copy, dirs.graph_name, "meta.json")
    harness.read_layout(wl, cfg, dirs, graph)
    _rewrite_part(dirs, 2, change)
    with pytest.raises(harness.BenchError, match=says):
        harness.read_layout(wl, cfg, dirs, graph)


def test_a_boundary_larger_than_the_partition_pads_is_refused():
    src = np.array([0, 1, 2, 3])
    dst = np.array([3, 3, 3, 0])
    with pytest.raises(ValueError, match="boundary nodes"):
        sage.boundary_lists(src, dst, np.array([0, 0, 0, 1]), 2, 2, 0.5)


def test_boundary_lists_and_send_sizes():
    src = np.array([0, 1, 2, 3, 4, 5, 0, 2])
    dst = np.array([3, 3, 4, 0, 1, 5, 1, 5])
    part = np.array([0, 0, 0, 1, 1, 1])
    bl = sage.boundary_lists(src, dst, part, 2, 8, 0.5)
    assert bl["nodes"][0, 1, :3].tolist() == [0, 1, 2]      # in id order
    assert bl["nodes"][1, 0, :2].tolist() == [3, 4]
    assert bl["count"].tolist() == [[0, 3], [2, 0]]
    assert bl["sent"].tolist() == [[0, 1], [1, 0]]           # int(rate * n)
    assert bl["scale"].tolist() == [[0.0, 3.0], [2.0, 0.0]]  # count / sent
    assert bl["nodes"][0, 1, 3] == 6                         # padded with n


def test_sampled_weights_scale_what_is_sent():
    src = np.array([0, 1, 2, 3, 4, 5, 0, 2])
    dst = np.array([3, 3, 4, 0, 1, 5, 1, 5])
    part = np.array([0, 0, 0, 1, 1, 1])
    bl = sage.boundary_lists(src, dst, part, 2, 8, 0.5)
    import jax.numpy as jnp
    lay = {"n_parts": 2, "part_of": jnp.asarray(part)}
    seen = set()
    for step in range(12):
        w = np.asarray(sage.sample_weights(3, jnp.uint32(step), lay, bl)
                       ).reshape(2, 7)
        assert w[0, :3].tolist() == [1, 1, 1] and w[1, 3:6].tolist() == [1, 1, 1]
        assert sorted(w[1, :3].tolist()) == [0.0, 0.0, 3.0]  # one of three sent
        assert sorted(w[0, 3:6].tolist()) == [0.0, 0.0, 2.0]
        seen.add(int(np.argmax(w[1, :3])))
    assert len(seen) > 1                                     # redrawn each step
