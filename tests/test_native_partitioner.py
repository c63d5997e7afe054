"""Native C++ partitioner: build, invariants, quality, determinism."""

import numpy as np
import pytest

from bnsgcn_tpu.data.graph import sbm_graph, synthetic_graph
from bnsgcn_tpu.data.partitioner import edge_cut, random_partition
from bnsgcn_tpu.native import native_partition


@pytest.fixture(scope="module")
def g():
    return sbm_graph(n_nodes=600, n_class=6, n_feat=4, p_in=0.06, p_out=0.002,
                     seed=42)


def test_every_node_assigned_and_balanced(g):
    pid = native_partition(g, 4, obj="cut", seed=0)
    assert pid is not None and pid.shape == (g.n_nodes,)
    assert pid.min() >= 0 and pid.max() < 4
    counts = np.bincount(pid, minlength=4)
    cap = -(-g.n_nodes // 4)
    assert counts.max() <= int(cap * 1.02) + 1
    assert counts.min() > 0


@pytest.mark.parametrize("obj", ["cut", "vol"])
def test_beats_random_partition(g, obj):
    pid_n = native_partition(g, 4, obj=obj, seed=0)
    pid_r = random_partition(g, 4, seed=0)
    # an SBM has community structure: locality partitioner must do much better
    assert edge_cut(g, pid_n) < 0.7 * edge_cut(g, pid_r), (
        edge_cut(g, pid_n), edge_cut(g, pid_r))


def test_deterministic_by_seed(g):
    a = native_partition(g, 3, seed=7)
    b = native_partition(g, 3, seed=7)
    c = native_partition(g, 3, seed=8)
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)


def test_single_part_and_power_law():
    g2 = synthetic_graph(n_nodes=300, avg_degree=10, n_feat=4, seed=1,
                         power_law=True)
    pid1 = native_partition(g2, 1)
    assert np.all(pid1 == 0)
    pid8 = native_partition(g2, 8, seed=3)
    assert np.bincount(pid8, minlength=8).min() > 0


def test_vol_objective_beats_cut_on_comm_volume():
    """The 'vol' refinement optimizes the TRUE directed comm volume (own +
    neighbor halo-set deltas), so it must beat the 'cut' objective on
    comm_volume — and 'cut' must beat 'vol' on edge_cut (differentiated
    objectives, reference objtype vol|cut, helper/utils.py:94-95)."""
    from bnsgcn_tpu.data.partitioner import comm_volume
    g2 = synthetic_graph(n_nodes=2000, avg_degree=16, n_feat=4, seed=2,
                         power_law=True)
    for P in (4, 8):
        pid_v = native_partition(g2, P, obj="vol", seed=0)
        pid_c = native_partition(g2, P, obj="cut", seed=0)
        assert comm_volume(g2, pid_v) < comm_volume(g2, pid_c), P
        assert edge_cut(g2, pid_c) < edge_cut(g2, pid_v), P


def test_native_comm_volume_matches_python(g):
    from bnsgcn_tpu.data.partitioner import comm_volume
    from bnsgcn_tpu.native import native_comm_volume
    pid = native_partition(g, 4, obj="vol", seed=1)
    assert native_comm_volume(g, pid, 4) == comm_volume(g, pid)


def test_multi_seed_never_worse():
    """Best-of-n_seeds is monotone: the 3-seed result's objective equals the
    min over its three candidates — in multilevel mode that pool is
    [ml(seed0), ml(seed1), flat(seed2)] (the last slot keeps a flat
    candidate so structure-free graphs never regress; seeds advance by the
    golden-ratio stride, matching partitioner.cpp)."""
    from bnsgcn_tpu.data.partitioner import comm_volume
    g2 = synthetic_graph(n_nodes=800, avg_degree=10, n_feat=4, seed=5,
                         power_law=True)
    best = comm_volume(g2, native_partition(g2, 4, obj="vol", seed=0, n_seeds=3))
    stride = 0x9E3779B97F4A7C15
    singles = [comm_volume(g2, native_partition(
        g2, 4, obj="vol", seed=(i * stride) % 2**64, n_seeds=1,
        multilevel=(i < 2)))
        for i in range(3)]
    assert best <= min(singles), (best, singles)
    assert best == min(singles)      # best-of picks one of the candidates


def test_partition_graph_uses_native():
    from bnsgcn_tpu.data.partitioner import partition_graph
    g2 = sbm_graph(n_nodes=400, n_class=4, n_feat=4, seed=9)
    pid = partition_graph(g2, 4, method="metis", obj="cut", seed=0)
    pid_native = native_partition(g2, 4, obj="cut", seed=0)
    np.testing.assert_array_equal(pid, pid_native)
