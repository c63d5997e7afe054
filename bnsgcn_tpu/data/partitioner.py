"""Graph partitioning (offline, CPU).

Replaces `dgl.distributed.partition_graph` + METIS (reference
helper/utils.py:94-95). Methods:

  * 'random'  — balanced random assignment (reference part_method='random').
  * 'metis'   — locality-minimizing partition by the native C++ partitioner
    (bnsgcn_tpu/native: multilevel coarsening + greedy linear-deterministic
    + boundary refinement, vol/cut objectives). The library builds on first
    use; a failed build is an error, never another partitioner.

Both return `part_id: [N] int32` with every node assigned to exactly one part;
partition *artifacts* (halo metadata etc.) are built by `artifacts.py`.
"""

from __future__ import annotations

import os
import re

import numpy as np

from bnsgcn_tpu.data.graph import Graph


def random_partition(g: Graph, n_parts: int, seed: int = 0) -> np.ndarray:
    """Balanced random assignment: shuffle nodes, deal them out round-robin."""
    rng = np.random.default_rng(seed)
    perm = rng.permutation(g.n_nodes)
    part_id = np.empty(g.n_nodes, dtype=np.int32)
    part_id[perm] = np.arange(g.n_nodes, dtype=np.int32) % n_parts
    return part_id


def partition_graph(g: Graph, n_parts: int, method: str = "metis",
                    obj: str = "vol", seed: int = 0) -> np.ndarray:
    if n_parts == 1:
        return np.zeros(g.n_nodes, dtype=np.int32)
    if method == "random":
        return random_partition(g, n_parts, seed)
    if method == "metis":
        from bnsgcn_tpu.native import native_partition
        return native_partition(g, n_parts, obj, seed)
    raise ValueError(f"unknown partition method {method!r}")


def degree_tables(src: np.ndarray, dst: np.ndarray,
                  n_nodes: int) -> tuple[np.ndarray, np.ndarray]:
    """Pure degree recompute from COO edges: (in_deg, out_deg), [N] int64.

    Shared by the offline artifact builder and the incremental delta path
    (data/incremental.py), which calls it on just the delta edges and adds
    the result to the degrees recovered from the existing artifact."""
    src = np.asarray(src)
    dst = np.asarray(dst)
    in_deg = np.bincount(dst, minlength=n_nodes).astype(np.int64)
    out_deg = np.bincount(src, minlength=n_nodes).astype(np.int64)
    return in_deg, out_deg


def degree_norm_row(deg_g: np.ndarray, ids: np.ndarray, pad: int) -> np.ndarray:
    """One part's padded degree/norm row: global degrees gathered at `ids`
    (the part's sorted inner node ids) with padding rows pinned to 1 so the
    normalization divide is a no-op on them. f32, matching the artifact
    contract (artifacts.py layout invariants)."""
    row = np.ones(pad, dtype=np.float32)
    row[:len(ids)] = deg_g[ids]
    return row


def validate_artifact_dir(path: str, n_parts: int,
                          parts: "list[int] | None" = None) -> None:
    """Check that the part files on disk match meta.json's part count.

    Historically a mismatch (stale meta.json next to a re-partitioned dir,
    or a pruned multi-host dir loaded single-host) surfaced as a downstream
    shape error deep in np.stack; raise a named ConfigError here instead.
    `parts` restricts the check to a partial load's requested part ids."""
    from bnsgcn_tpu.config import ConfigError
    present = set()
    for fn in os.listdir(path):
        m = re.fullmatch(r"part(\d+)\.npz", fn)
        if m:
            present.add(int(m.group(1)))
    want = set(range(n_parts)) if parts is None else set(parts)
    missing = sorted(want - present)
    extra = sorted(p for p in present if p >= n_parts)
    if missing:
        raise ConfigError(
            f"artifact dir {path}: meta.json says n_parts={n_parts} but part "
            f"files {missing} are missing (have {sorted(present)}); "
            f"re-run partitioning or pass --force-partition")
    if extra:
        raise ConfigError(
            f"artifact dir {path}: meta.json says n_parts={n_parts} but extra "
            f"part files {extra} exist — stale meta.json next to a "
            f"re-partitioned dir; re-run partitioning or remove the dir")


def edge_cut(g: Graph, part_id: np.ndarray) -> int:
    """Number of edges crossing partitions (quality metric, obj='cut')."""
    return int(np.sum(part_id[g.src] != part_id[g.dst]))


def comm_volume(g: Graph, part_id: np.ndarray) -> int:
    """Total boundary-set size: sum over (node u, part j!=part(u)) of whether u
    has an out-edge into j — the payload of one full-rate halo exchange
    (obj='vol', what BNS actually compresses)."""
    cross = part_id[g.src] != part_id[g.dst]
    # unique (node, dst-part) pairs via a packed 1-D key: half the memory
    # and no structured axis=0 sort — matters at 1e9-edge scale proofs
    P = int(part_id.max()) + 1
    key = g.src[cross] * np.int64(P) + part_id[g.dst[cross]].astype(np.int64)
    return int(np.unique(key).shape[0])
