"""Graph reordering as a first-class artifact pass (--reorder).

The hybrid SpMM's whole economics hinge on dense-tile coverage: with rows
ordered for locality, edge mass concentrates into a small set of [tile x
tile] adjacency cells that aggregate on the MXU instead of the gather unit
(ops/block_spmm.py). Historically that ordering was recomputed per layout
build by cluster_order's LDG pass — and on structure-free graphs (uniform
synthetic: ~21% coverage, the 4.7x regime) LDG actually SCRAMBLES the one
exploitable signal, the power-law popularity skew.

This module makes the ordering an explicit, cached artifact transform:

  * `cluster_reorder` computes a per-part permutation of the REAL inner
    rows — degree-anchored label propagation (Rabbit-style community
    ordering, pure numpy) + greedy first-fit-decreasing packing of the
    clusters into tile_r-row bins, degree-descending within each cluster.
    On clustered graphs the LPA recovers the communities; on skew-only
    graphs it degenerates to global degree order, which concentrates the
    popularity hyperbola into the top-left tiles.
  * `apply_reorder` permutes the artifacts ONCE, in place of nothing:
    every downstream consumer (halo plans, BNS sampling, --halo-refresh
    chunk tables, --overlap split, all three layout builders) sees
    permuted row ids consistently, and the permutation is inverted only
    at the user-visible edges — evaluate.gather_parts maps results back
    through the permuted `global_nid`, so eval logits, --dump-embeddings
    tables and serve lookups stay in global id order with no extra code.
  * `maybe_reorder` resolves --reorder {auto,cluster,off} for a run,
    memoizes the permutation on disk next to the layout caches
    (utils/diskcache; key = pre-permutation partition digest + algorithm
    + tile), and emits the `reorder` obs event (coverage before/after,
    build ms).

Permutation contract (the part every consumer relies on): per part p only
rows [0, n_inner[p]) move; padding rows and halo slots keep their
positions. `order[p][new] = old`; positions `pos[old] = new`. Row-indexed
arrays gather by `order`, edge endpoints and boundary-list VALUES remap by
`pos` (halo slot ids and the pad_inner trash row are untouched), and every
padded shape, boundary count (n_b) and degree multiset — hence
ell_geometry — is unchanged. `--reorder off` never constructs any of this:
bit-identical to the pre-reorder pipeline, pinned by tests/test_reorder.py.
"""

from __future__ import annotations

import dataclasses
import os
import time

import numpy as np

from bnsgcn_tpu.config import Config, ConfigError
from bnsgcn_tpu.data.artifacts import PartitionArtifacts

REORDER_ALGO = "lpa-ffd"      # versions the disk cache: bump on any change
                              # to cluster_reorder's output for fixed input

# label-propagation sweeps: 3 reaches ~3-hop neighborhoods of the anchors,
# after which bench-scale labelings are stationary to within <0.5% of rows
LPA_SWEEPS = 3
# deterministic edge stride cap for the LPA vote (NOT for degrees/coverage):
# community votes saturate long before bench-scale edge counts, so huge
# parts subsample instead of sorting 10^8 vote keys per sweep
LPA_MAX_EDGES = 8_000_000
# first-fit bin scan window: FFD checks at most this many open bins per
# cluster, keeping packing O(n_clusters * window) at papers100M part counts
FFD_WINDOW = 128


def _majority_vote(u, v, labels, n_labels):
    """One LPA sweep: for every node u with >=1 labeled neighbor v, adopt
    the most frequent neighbor label (ties -> smallest label). Vectorized
    as a radix sort + run-length encode over (node, label) keys."""
    lv = labels[v]
    has = lv >= 0
    if not has.any():
        return
    keys = u[has] * np.int64(n_labels) + lv[has]
    keys.sort(kind="stable")                       # radix for ints
    starts = np.concatenate([[0], np.flatnonzero(np.diff(keys)) + 1])
    uk = keys[starts]
    cnt = np.diff(np.concatenate([starts, [len(keys)]]))
    node = uk // n_labels
    lab = uk % n_labels
    # per node: max count wins, ties -> smallest label (lexsort is stable,
    # so equal (node, cnt) entries keep label-ascending order from uk)
    o = np.lexsort((lab, -cnt, node))
    node_o, lab_o = node[o], lab[o]
    first = np.concatenate([[True], node_o[1:] != node_o[:-1]])
    labels[node_o[first]] = lab_o[first]


def cluster_reorder(src, dst, pad_inner: int, n_inner: int,
                    tile_r: int = 512, sweeps: int = LPA_SWEEPS
                    ) -> np.ndarray:
    """Row permutation of ONE part's inner space: `order[new] = old`,
    identity on the padding rows [n_inner, pad_inner).

    Degree-anchored label propagation over the part's inner-inner edges
    (anchors = the ceil(n_inner/tile_r) highest-degree rows, pinned so
    clusters stay anchored), then clusters packed first-fit-decreasing by
    degree mass into tile_r-row bins, rows degree-descending inside each
    cluster. Pure numpy, deterministic."""
    order = np.arange(pad_inner, dtype=np.int64)
    if n_inner <= 1:
        return order
    s = np.asarray(src).astype(np.int64, copy=False)
    d = np.asarray(dst).astype(np.int64, copy=False)
    m = (s < n_inner) & (d < n_inner)
    s, d = s[m], d[m]
    deg = (np.bincount(d, minlength=n_inner)
           + np.bincount(s, minlength=n_inner)).astype(np.int64)
    n_clusters = max(int(np.ceil(n_inner / max(tile_r, 1))), 1)
    labels = np.full(n_inner, -1, dtype=np.int64)
    if n_clusters > 1 and len(s):
        if len(s) > LPA_MAX_EDGES:
            step = (len(s) + LPA_MAX_EDGES - 1) // LPA_MAX_EDGES
            s, d = s[::step], d[::step]
        u = np.concatenate([d, s])
        v = np.concatenate([s, d])
        anchors = np.argsort(-deg, kind="stable")[:n_clusters]
        anchor_labels = np.arange(n_clusters, dtype=np.int64)
        labels[anchors] = anchor_labels
        for _ in range(max(sweeps, 1)):
            _majority_vote(u, v, labels, n_clusters)
            labels[anchors] = anchor_labels        # anchors stay pinned
    # unlabeled rows (isolated / unreached) form one trailing cluster
    lab = np.where(labels >= 0, labels, n_clusters)
    n_lab = n_clusters + 1
    sizes = np.bincount(lab, minlength=n_lab)
    mass = np.bincount(lab, weights=deg.astype(np.float64), minlength=n_lab)
    # FFD tile packing: clusters by mass descending (ties -> smaller label)
    # into tile_r-row bins so small clusters share a row block instead of
    # each wasting most of one
    by_mass = np.lexsort((np.arange(n_lab), -mass))
    bins: list[list[int]] = []
    room: list[int] = []
    for c in by_mass:
        sz = int(sizes[c])
        if sz == 0:
            continue
        placed = False
        if sz < tile_r:
            lo = max(len(bins) - FFD_WINDOW, 0)
            for b in range(lo, len(bins)):
                if room[b] >= sz:
                    bins[b].append(int(c))
                    room[b] -= sz
                    placed = True
                    break
        if not placed:
            bins.append([int(c)])
            room.append(max(tile_r - sz, 0))
    cluster_pos = np.zeros(n_lab, dtype=np.int64)
    k = 0
    for b in bins:
        for c in b:
            cluster_pos[c] = k
            k += 1
    # final row order: packed-cluster sequence, degree-descending within a
    # cluster (full ties keep ascending original id — lexsort is stable)
    order[:n_inner] = np.lexsort((-deg, cluster_pos[lab]))
    return order


def compute_orders(art: PartitionArtifacts, tile_r: int = 512) -> np.ndarray:
    """Stacked per-part permutations [P, pad_inner] (order[p][new] = old)."""
    P = art.feat.shape[0]
    return np.stack([
        cluster_reorder(art.src[p], art.dst[p], art.pad_inner,
                        int(art.n_inner[p]), tile_r=tile_r)
        for p in range(P)])


def apply_reorder(art: PartitionArtifacts, orders: np.ndarray
                  ) -> PartitionArtifacts:
    """New artifacts with each part's inner rows permuted by `orders`.

    Row-indexed arrays gather by order; src/dst/bnd VALUES remap through
    the inverse positions (halo slot ids >= pad_inner and the pad_inner
    trash-row dst are untouched; bnd pad entries stay 0). Shapes, n_b,
    pads and ell_geometry are unchanged. Full artifacts only: a multi-host
    partial load's local row p is not global part p, so its n_b rows
    cannot be matched to bnd rows here (maybe_reorder gates that case)."""
    P = art.feat.shape[0]
    if art.n_b.shape[0] != P:
        raise ValueError(
            f"apply_reorder needs full artifacts (all {art.n_b.shape[0]} "
            f"parts); got a partial load with {P} part rows")
    pad_inner = art.pad_inner
    feat = np.stack([art.feat[p][orders[p]] for p in range(P)])
    label = np.stack([art.label[p][orders[p]] for p in range(P)])
    train_mask = np.stack([art.train_mask[p][orders[p]] for p in range(P)])
    val_mask = np.stack([art.val_mask[p][orders[p]] for p in range(P)])
    test_mask = np.stack([art.test_mask[p][orders[p]] for p in range(P)])
    inner_mask = np.stack([art.inner_mask[p][orders[p]] for p in range(P)])
    in_deg = np.stack([art.in_deg[p][orders[p]] for p in range(P)])
    global_nid = np.stack([art.global_nid[p][orders[p]] for p in range(P)])
    out_deg_ext = art.out_deg_ext.copy()
    src = art.src.copy()
    dst = np.empty_like(art.dst)
    bnd = art.bnd.copy()
    for p in range(P):
        pos = np.empty(pad_inner, dtype=np.int64)
        pos[orders[p]] = np.arange(pad_inner)
        out_deg_ext[p, :pad_inner] = out_deg_ext[p, :pad_inner][orders[p]]
        sp = src[p]
        inner_src = sp < pad_inner
        sp[inner_src] = pos[sp[inner_src]].astype(sp.dtype)
        # dst includes the pad_inner trash row: extend pos with a fixpoint
        pos_ext = np.concatenate([pos, [pad_inner]])
        dst[p] = pos_ext[art.dst[p]].astype(art.dst.dtype)
        for j in range(art.bnd.shape[1]):
            k = int(art.n_b[p, j])
            if k:
                bnd[p, j, :k] = pos[art.bnd[p, j, :k]].astype(bnd.dtype)
    return dataclasses.replace(
        art, feat=feat, label=label, train_mask=train_mask,
        val_mask=val_mask, test_mask=test_mask, inner_mask=inner_mask,
        in_deg=in_deg, out_deg_ext=out_deg_ext, src=src, dst=dst, bnd=bnd,
        global_nid=global_nid)


def artifact_coverage(art: PartitionArtifacts, occupancy_min: int,
                      tile_budget_bytes: int, tile: int,
                      perms=None) -> float:
    """Edge-weighted dense-tile coverage of the artifacts under `perms`
    (stacked per-part row [P, pad_inner] / col [P, n_ext] permutations;
    None = identity, the order a reordered artifact's layout build sees).
    One O(E) histogram per part (estimate_coverage)."""
    from bnsgcn_tpu.ops.block_spmm import estimate_coverage
    ident_i = np.arange(art.pad_inner, dtype=np.int64)
    ident_e = np.arange(art.n_ext, dtype=np.int64)
    dense = total = 0.0
    for p in range(art.feat.shape[0]):
        pi = ident_i if perms is None else perms[0][p]
        pe = ident_e if perms is None else perms[1][p]
        real = art.dst[p] < art.pad_inner
        d, s = art.dst[p][real], art.src[p][real]
        cov = estimate_coverage(pi, pe, art.pad_inner, art.n_ext,
                                d, s, occupancy_min=occupancy_min,
                                tile_budget_bytes=tile_budget_bytes,
                                tile_r=tile, tile_c=tile)
        dense += cov * len(d)
        total += len(d)
    return dense / max(total, 1.0)


def reorder_cache_path(cfg: Config, art: PartitionArtifacts,
                       tile: int) -> str | None:
    """Disk location of the memoized permutation; None without --cache-dir.

    Content-addressed by the PRE-permutation partition (same sha1 recipe as
    run.py's layout digest, which hashes POST-permutation arrays — the two
    namespaces can never collide) and versioned with the reorder config
    (algorithm + tile), so a knob change can never read a stale order."""
    if not cfg.cache_dir:
        return None
    import hashlib
    dg = hashlib.sha1()
    for a in (art.n_b, art.src, art.dst):
        dg.update(np.ascontiguousarray(a))
    gname = cfg.graph_name or cfg.derive_graph_name()
    return os.path.join(
        cfg.cache_dir,
        f"reorder_{gname}_{dg.hexdigest()[:12]}_{REORDER_ALGO}_t{tile}.pkl")


def maybe_reorder(cfg: Config, art: PartitionArtifacts, log=print, obs=None
                  ) -> tuple[PartitionArtifacts, str, dict]:
    """Resolve --reorder for this run: (artifacts, resolved, info).

    'off' returns the input untouched (bit-identical pipeline). 'cluster'
    always applies the permutation; 'auto' measures tile coverage and
    applies only on improvement — against the baseline the off path
    ACTUALLY builds with (the hybrid's per-build LDG cluster_order perms,
    not the raw load order: on the uniform bench graph the raw order
    scores 50.6% while the LDG build it would feed gets 27.0%, so an
    identity baseline would decline exactly where the pass pays most).
    Multi-host partial loads force 'off': each process sees only its local
    parts, and an order derived from them would desync the shared-name
    layout caches. Emits the `reorder` obs event when a bus is given."""
    mode = getattr(cfg, "reorder", "off") or "off"
    if mode == "off":
        return art, "off", {}
    if mode not in ("auto", "cluster"):
        raise ConfigError(
            f"--reorder must be 'auto', 'cluster' or 'off', got {mode!r}")
    import jax
    if jax.process_count() > 1:
        log("reorder: multi-host partial loads keep the on-disk row order "
            "(--reorder forced off)")
        return art, "off", {}
    from bnsgcn_tpu.ops.block_spmm import cluster_order, effective_occupancy
    tile = int(getattr(cfg, "block_tile", 512) or 512)
    occ = effective_occupancy(int(getattr(cfg, "block_occupancy", 0) or 0),
                              tile, tile)
    budget = int(getattr(cfg, "block_tile_budget_mb", 4096)) << 20
    t0 = time.perf_counter()
    P = art.feat.shape[0]
    base_i = np.stack([cluster_order(art.src[p], art.dst[p], art.pad_inner,
                                     art.n_ext)[0] for p in range(P)])
    base_e = np.concatenate(
        [base_i, np.tile(np.arange(art.pad_inner, art.n_ext), (P, 1))],
        axis=1)
    cov_before = artifact_coverage(art, occ, budget, tile,
                                   perms=(base_i, base_e))
    orders, cached = None, False
    path = reorder_cache_path(cfg, art, tile)
    if path is not None:
        from bnsgcn_tpu.utils.diskcache import try_load
        orders = try_load(path, log)
        cached = orders is not None
        if cached and orders.shape != (art.feat.shape[0], art.pad_inner):
            orders, cached = None, False       # stale shape: rebuild
    if orders is None:
        orders = compute_orders(art, tile_r=tile)
        if path is not None:
            from bnsgcn_tpu.utils.diskcache import atomic_dump
            os.makedirs(cfg.cache_dir, exist_ok=True)
            atomic_dump(orders, path)
    art2 = apply_reorder(art, orders)
    cov_after = artifact_coverage(art2, occ, budget, tile)
    build_ms = (time.perf_counter() - t0) * 1e3
    applied = mode == "cluster" or cov_after > cov_before + 1e-9
    resolved = "cluster" if applied else "off"
    info = {"algorithm": REORDER_ALGO, "mode": mode, "resolved": resolved,
            "tile": tile, "coverage_before": round(cov_before, 4),
            "coverage_after": round(cov_after, 4),
            "build_ms": round(build_ms, 1), "cached": bool(cached)}
    log(f"reorder: {mode} -> {resolved} [{REORDER_ALGO}, t{tile}] tile "
        f"coverage {cov_before:.1%} -> {cov_after:.1%} "
        f"({build_ms:.0f} ms{', order cached' if cached else ''})")
    if obs is not None:
        obs.emit("reorder", **info)
    return (art2 if applied else art), resolved, info
