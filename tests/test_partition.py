"""Partitioner + artifact invariants (SURVEY §4 implication (a)):
every node exactly one owner; boundary symmetry; edge conservation."""

import numpy as np
import pytest

from bnsgcn_tpu.data.artifacts import build_artifacts, load_artifacts, save_artifacts
from bnsgcn_tpu.data.graph import synthetic_graph
from bnsgcn_tpu.data.partitioner import (comm_volume, edge_cut,
                                         partition_graph, random_partition)


@pytest.fixture(scope="module")
def g():
    return synthetic_graph(n_nodes=120, avg_degree=6, n_feat=7, n_class=4, seed=20)


@pytest.mark.parametrize("method", ["random", "metis"])
def test_every_node_exactly_one_owner(g, method):
    pid = partition_graph(g, 4, method=method, seed=0)
    assert pid.shape == (g.n_nodes,)
    assert pid.min() >= 0 and pid.max() < 4
    # balanced within ceil
    counts = np.bincount(pid, minlength=4)
    assert counts.max() - counts.min() <= max(2, g.n_nodes // 10)


def test_quality_metrics_consistent(g):
    pid = random_partition(g, 3, 0)
    assert comm_volume(g, pid) <= edge_cut(g, pid)


def _artifacts(g, P=4):
    pid = partition_graph(g, P, method="random", seed=1)
    return pid, build_artifacts(g, pid)


def test_artifact_inner_partition_of_nodes(g):
    pid, art = _artifacts(g)
    assert art.n_inner.sum() == g.n_nodes
    all_gnid = art.global_nid[art.inner_mask]
    assert sorted(all_gnid.tolist()) == list(range(g.n_nodes))
    # inner rows hold the right per-node data
    for p in range(art.n_parts):
        ids = art.global_nid[p][art.inner_mask[p]]
        np.testing.assert_array_equal(art.feat[p][art.inner_mask[p]], g.feat[ids])
        np.testing.assert_array_equal(art.train_mask[p][art.inner_mask[p]], g.train_mask[ids])
        np.testing.assert_array_equal(art.in_deg[p][art.inner_mask[p]],
                                      g.in_degrees()[ids].astype(np.float32))


def test_artifact_edge_conservation(g):
    """Each global edge appears exactly once: inner edges in the owner of dst,
    cross edges as halo edges of the dst part."""
    pid, art = _artifacts(g)
    total = 0
    for p in range(art.n_parts):
        real = art.dst[p] < art.pad_inner
        total += int(real.sum())
    assert total == g.n_edges


def test_artifact_boundary_symmetry_and_slots(g):
    """bnd[p, j] lists exactly the p-owned sources of cross edges into j, and
    halo edge slots decode back to the correct global node."""
    pid, art = _artifacts(g)
    P, B = art.n_parts, art.pad_boundary
    cross = pid[g.src] != pid[g.dst]
    for p in range(P):
        for j in range(P):
            if p == j:
                assert art.n_b[p, j] == 0
                continue
            m = cross & (pid[g.src] == p) & (pid[g.dst] == j)
            expect = np.unique(g.src[m])
            got = art.global_nid[p][art.bnd[p, j, :art.n_b[p, j]]]
            np.testing.assert_array_equal(np.sort(got), expect)
    # halo edges: reconstruct each edge's global (src, dst) and compare multisets
    for j in range(P):
        real = art.dst[j] < art.pad_inner
        s, d = art.src[j][real], art.dst[j][real]
        halo = s >= art.pad_inner
        q = (s[halo] - art.pad_inner) // B
        k = (s[halo] - art.pad_inner) % B
        src_gl = art.global_nid[q, art.bnd[q, j, k]]
        dst_gl = art.global_nid[j][d[halo]]
        m = cross & (pid[g.dst] == j)
        expect = np.stack([g.src[m], g.dst[m]], 1)
        got = np.stack([src_gl, dst_gl], 1)
        assert sorted(map(tuple, got)) == sorted(map(tuple, expect))
        # inner edges
        inner_s = art.global_nid[j][s[~halo]]
        inner_d = art.global_nid[j][d[~halo]]
        m2 = (pid[g.src] == j) & (pid[g.dst] == j)
        assert sorted(zip(inner_s, inner_d)) == sorted(zip(g.src[m2], g.dst[m2]))


def test_artifact_out_deg_ext(g):
    pid, art = _artifacts(g)
    out_deg = g.out_degrees().astype(np.float32)
    for p in range(art.n_parts):
        np.testing.assert_array_equal(art.out_deg_ext[p, :art.n_inner[p]],
                                      out_deg[art.global_nid[p, :art.n_inner[p]]])
        for q in range(art.n_parts):
            nb = art.n_b[q, p]
            base = art.pad_inner + q * art.pad_boundary
            ids = art.global_nid[q, art.bnd[q, p, :nb]]
            np.testing.assert_array_equal(art.out_deg_ext[p, base:base + nb], out_deg[ids])


def test_artifact_roundtrip(tmp_path, g):
    pid, art = _artifacts(g, P=3)
    save_artifacts(art, str(tmp_path / "parts"))
    art2 = load_artifacts(str(tmp_path / "parts"))
    for k in ["feat", "label", "src", "dst", "bnd", "n_b", "in_deg",
              "out_deg_ext", "global_nid"]:
        np.testing.assert_array_equal(getattr(art, k), getattr(art2, k))
    assert art2.n_train == g.n_train and art2.n_class == g.n_class


def test_single_partition_degenerate(g):
    pid = partition_graph(g, 1)
    art = build_artifacts(g, pid)
    assert art.n_parts == 1
    assert art.n_b.sum() == 0
    real = art.dst[0] < art.pad_inner
    assert int(real.sum()) == g.n_edges


def test_load_artifacts_partial_parts(tmp_path, g):
    pid, art = _artifacts(g, P=4)
    save_artifacts(art, str(tmp_path / "pp"))
    sub = load_artifacts(str(tmp_path / "pp"), parts=[2, 0])
    assert sub.n_parts == 4                       # meta stays global
    np.testing.assert_array_equal(sub.feat[0], art.feat[2])
    np.testing.assert_array_equal(sub.feat[1], art.feat[0])
    np.testing.assert_array_equal(sub.bnd[0], art.bnd[2])
    np.testing.assert_array_equal(sub.n_b, art.n_b)


def test_place_blocks_local_single_host_equivalent(g):
    import jax
    from bnsgcn_tpu.parallel.mesh import make_parts_mesh
    from bnsgcn_tpu.trainer import (local_part_ids, place_blocks,
                                    place_blocks_local)
    pid, art = _artifacts(g, P=4)
    mesh = make_parts_mesh(4)
    assert local_part_ids(mesh) == [0, 1, 2, 3]   # single process hosts all
    blk = {"feat": art.feat, "bnd": art.bnd}
    a = place_blocks(blk, mesh)
    b = place_blocks_local(blk, mesh)
    for k in blk:
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]))
        assert a[k].sharding == b[k].sharding
