"""Hybrid block-dense + ELL sparse aggregation — the MXU-path SpMM.

The pure-ELL SpMM (ops/ell.py) is bound by the TPU gather unit (~110 GB/s of
512B rows measured on a v5e — far below HBM stream). Real graphs in this
workload's class (Reddit: 41 communities, strong homophily; METIS partitions
of anything) are CLUSTERED: with rows reordered by locality, much of the
edge mass falls into a small set of dense adjacency tiles. Those tiles can
be aggregated on the MXU instead of the gather unit:

  offline (numpy, per part):
    * cluster-order the local node space (cluster_order: native-partitioner
      LDG clustering; halo slots keep their per-peer grouping);
    * tile the (dst x src) adjacency into [TR x TC] blocks; blocks with
      >= occupancy_min edges become DENSE int8 tiles (edge multiplicities)
      with (row_block, col_block) ids sorted by row_block; every remaining
      edge goes to the usual bucketed-ELL residual;
    * the backward reads the SAME int8 stack, transposed as it is read:
      `blk_order_bwd` visits the tiles col_block-sorted, with (row_block,
      col_block) ids swapped — same edges, so the VJP is exact, and one
      stack holds every dense tile; the ELL residual already builds its
      own fwd+bwd pair over the SAME edges.
  on device, per pass:
    * X_perm = X[inv perm] (one cheap permutation gather) sliced into
      [n_col_blocks, TC, H] slabs; slab gather by col_block id (contiguous
      TC*H*2-byte reads — byte-efficient even on the gather unit);
    * int8 tiles cast to the compute dtype and ONE batched matmul
      [B, TR, TC] @ [B, TC, H] (MXU);
    * sorted segment-sum over row_block ids, inverse permutation, plus the
      ELL residual output.

On graphs with no locality (uniform synthetic), no tile clears the
occupancy threshold and the operator degenerates to the ELL SpMM — the
hybrid never loses. Replaces: reference DGL SpMM update_all(copy_u, sum)
(module/layer.py:35-37,88-90).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bnsgcn_tpu.ops.ell import (ELL_SPLIT_CAP, GeoAccum, build_layouts,
                                layout_fastpath, make_ell_spmm, run_parallel)
from bnsgcn_tpu.utils import traceparse as tp

TR = 512          # default dst rows per dense tile (square: transposes keep
TC = 512          # shape, and per-edge slab/output overhead beats narrow
                  # tiles). Finer tiles (256) capture more edge mass per tile
                  # byte on clustered graphs — same budget, less ELL residual
                  # — at the cost of ~2x slab-gather traffic per tile byte;
                  # selectable per run (config --block-tile, bench +t256).


@dataclass(frozen=True)
class BlockSpec:
    """Static geometry of one direction's dense-tile layout."""
    n_rows: int                    # output rows (original id space)
    n_src: int                     # gatherable rows (original id space)
    row_tile: int
    col_tile: int
    n_blocks: int                  # padded dense-tile count
    n_row_blocks: int              # ceil(n_rows / row_tile)
    max_row_dense: int = 0         # max dense edges on any output row (over
                                   # parts; 0 = unknown, e.g. a layout cached
                                   # before this field existed). Bounds the
                                   # int8 Pallas path's int32 accumulator:
                                   # |row sum| <= 127*127*max_row_dense.


def effective_occupancy(occupancy: int, tile_r: int = TR,
                        tile_c: int = TC) -> int:
    """Resolve the occupancy knob: 0 = auto, the TIME break-even of a
    dense tile against the residual gather, half the tile's edge in edges
    (256 at the default 512x512 tile, 128 at 256x256). On a v5e a residual
    edge costs about 22.6 ns a step; the kernel's six calls a step (forward
    and backward at widths 256, 256, 41) cost 5.94 us a 512x512 tile and
    2.97 us a 256x256 one (over 4 GiB of tiles): a grid step's fixed cost
    keeps the smaller tile at half the larger's time, not a quarter, so the
    break-even follows the edge, not the area. Measured at those two sizes
    only. Explicit values are absolute edge counts. Centralized so trainer
    CLI runs, bench variants, and tools all resolve the threshold
    identically."""
    return (occupancy if occupancy > 0
            else max(math.isqrt(tile_r * tile_c) // 2, 16))


def _select_dense(tile_id, occupancy_min, tile_budget_bytes,
                  tile_bytes=TR * TC, need_inverse=True, n_tiles=None):
    """Which tiles densify: >= occupancy_min edges, highest-count tiles win
    under the HBM budget, the device bytes of the one int8 stack both
    directions read (ties trimmed last). Shared by the real layout
    build and the O(E) coverage estimator behind --spmm auto (which skips
    the len(E) int64 inverse array — need_inverse=False).

    With `n_tiles` (the dense tile-grid extent) the unique pass runs as one
    O(E + n_tiles) bincount + rank LUT instead of np.unique's O(E log E)
    sort — bitwise-identical output (bincount indices are ascending, the
    same order np.unique emits; ~24x at 20M edges). The sort fallback
    covers grids too large to histogram and BNSGCN_LAYOUT_FASTPATH=0."""
    if (n_tiles is not None and layout_fastpath()
            and n_tiles <= (1 << 26)):
        cf = np.bincount(tile_id, minlength=n_tiles)
        uniq = np.flatnonzero(cf)
        counts = cf[uniq]
        if need_inverse:
            lut = np.zeros(n_tiles, dtype=np.int64)
            lut[uniq] = np.arange(len(uniq))
            inv = lut[tile_id]
        else:
            inv = None
    elif need_inverse:
        uniq, inv, counts = np.unique(tile_id, return_inverse=True,
                                      return_counts=True)
    else:
        uniq, counts = np.unique(tile_id, return_counts=True)
        inv = None
    max_tiles = max(int(tile_budget_bytes // tile_bytes), 1)
    dense_sel = counts >= occupancy_min
    if int(dense_sel.sum()) > max_tiles:
        # keep every tile strictly above the cut, trim only among ties
        thresh = np.sort(counts[dense_sel])[-max_tiles]
        above = counts > thresh
        ties = np.nonzero(dense_sel & (counts == thresh))[0]
        dense_sel = above
        dense_sel[ties[:max_tiles - int(above.sum())]] = True
    return uniq, inv, counts, dense_sel


def estimate_coverage(perm_rows, perm_cols, n_rows, n_src, rows, cols,
                      occupancy_min=256, tile_budget_bytes=4 << 30,
                      tile_r=TR, tile_c=TC) -> float:
    """Fraction of edges that would land on dense MXU tiles under the
    given cluster order — the decision statistic for --spmm auto. One
    O(E) histogram pass over exactly _build_tiles' selection rule; no
    tile stacks or residual tables are materialized.

    Known bias: edges beyond 127 per-(tile,row,col) multiplicity count as
    dense here, but _build_tiles pushes that excess back to the ELL
    residual — so on high-multiplicity multigraphs the estimate can
    overstate coverage and flip --spmm auto toward hybrid near the
    decision threshold. Negligible on simple graphs (every bench/reference
    dataset); clamping would need the per-cell histogram this estimator
    exists to avoid."""
    if len(rows) == 0:
        return 0.0
    n_cb = (n_src + tile_c - 1) // tile_c
    tile_id = (perm_rows[rows] // tile_r).astype(np.int64) * n_cb \
        + perm_cols[cols] // tile_c
    n_rb = (n_rows + tile_r - 1) // tile_r
    _, _, counts, dense_sel = _select_dense(tile_id, occupancy_min,
                                            tile_budget_bytes,
                                            tile_bytes=tile_r * tile_c,
                                            need_inverse=False,
                                            n_tiles=n_rb * n_cb)
    return float(counts[dense_sel].sum()) / float(len(rows))


def _build_tiles(perm_rows, perm_cols, n_rows, n_src, rows, cols,
                 occupancy_min, tile_budget_bytes=4 << 30,
                 tile_r=TR, tile_c=TC):
    """Dense tiles over cluster-ordered (rows x cols); fully vectorized.

    A tile densifies only if it carries >= occupancy_min edges (the
    time break-even against the residual gather, effective_occupancy) AND
    the one stack both directions read stays under tile_budget_bytes of
    device memory (highest-count tiles win; ties trimmed last).
    Returns (tiles int8 [B,tile_r,tile_c] sorted by row_blk, row_blk,
    col_blk, residual_edge_mask, extra_rows, extra_cols, rle) — the extras
    are >127 multiplicity overflow in PERMUTED coordinates. Tiles fill by a
    cell-id sort + run-length encode (writes only occupied cells); peak
    transient memory is O(E), not O(tiles). `rle` is the occupied-cell
    encoding (cell ids, clamped int8 counts) on the fast path (None on
    legacy) — it lets the caller take the per-row dense maxima by an
    O(occupied) bincount instead of two more passes over the multi-GB
    stack."""
    n_cb = (n_src + tile_c - 1) // tile_c
    pr = perm_rows[rows]
    pc = perm_cols[cols]
    tile_id = (pr // tile_r).astype(np.int64) * n_cb + pc // tile_c
    n_rb = (n_rows + tile_r - 1) // tile_r
    uniq, inv, counts, dense_sel = _select_dense(tile_id, occupancy_min,
                                                 tile_budget_bytes,
                                                 tile_bytes=tile_r * tile_c,
                                                 n_tiles=n_rb * n_cb)
    B = int(dense_sel.sum())
    if B == 0:
        return (np.zeros((0, tile_r, tile_c), np.int8),
                np.zeros(0, np.int32),
                np.zeros(0, np.int32), np.ones(len(rows), dtype=bool),
                np.zeros(0, np.int64), np.zeros(0, np.int64), None)

    rank = np.full(len(uniq), -1, dtype=np.int64)
    rank[np.nonzero(dense_sel)[0]] = np.arange(B)        # uniq sorted => rb-major
    e_rank = rank[inv]
    m = e_rank >= 0
    resid_mask = ~m
    sel_ids = uniq[dense_sel]
    row_blk = (sel_ids // n_cb).astype(np.int32)
    col_blk = (sel_ids % n_cb).astype(np.int32)

    # fill by run-length encoding instead of a dense int accumulator: sort
    # the dense edges by exact cell id (tile-major), count runs, and write
    # only the OCCUPIED cells straight into the int8 stack. Replaces the
    # chunked np.add.at histogram + full-stack >127 scan + int32->int8
    # cast — each a pass over B*tile_r*tile_c elements — with one O(E log E)
    # sort plus O(E) writes (2.1x on the scale-0.1 dcsbm build where edges
    # fill ~2% of the selected tiles' cells; CPU-container measurement, PR 2).
    area = tile_r * tile_c
    tiles8 = np.zeros((B, tile_r, tile_c), dtype=np.int8)
    cell = (e_rank[m] * area + (pr[m] % tile_r) * tile_c
            + (pc[m] % tile_c))
    cell.sort()
    starts = np.concatenate(
        [[0], np.flatnonzero(np.diff(cell)) + 1]).astype(np.int64)
    uc = cell[starts]                                    # occupied cells
    cnt = np.diff(np.concatenate([starts, [len(cell)]]))
    cnt8 = np.minimum(cnt, 127).astype(np.int8)
    tiles8.reshape(-1)[uc] = cnt8
    over = cnt > 127                                     # int8 overflow:
    if over.any():                                       # excess -> residual
        rep = cnt[over] - 127
        ob = uc[over] // area
        orr = (uc[over] % area) // tile_c
        occ = uc[over] % tile_c
        extra_rows = np.repeat(orr + row_blk[ob].astype(np.int64) * tile_r,
                               rep)
        extra_cols = np.repeat(occ + col_blk[ob].astype(np.int64) * tile_c,
                               rep)
    else:
        extra_rows = extra_cols = np.zeros(0, np.int64)
    rle = (uc, cnt8) if layout_fastpath() else None
    return tiles8, row_blk, col_blk, resid_mask, extra_rows, extra_cols, rle


def _row_dense_maxima(tiles, rb, cb, n_dst, n_src_ext, tile_r, tile_c):
    """(max fwd-row, max bwd-row) dense edge counts for one part's tile
    stack. sum(dtype=int64) — NOT astype — so no 8x copy of the (up to
    multi-GB) int8 stack is ever materialized."""
    # +1 row block: stacked cached layouts pad unused tile slots with
    # row_blk == n_row_blocks (their tiles are all-zero, so the extra row
    # accumulates nothing and is simply not read)
    per_row = np.zeros(((n_dst + tile_r - 1) // tile_r + 1, tile_r),
                       np.int64)
    np.add.at(per_row, rb, tiles.sum(axis=2, dtype=np.int64))
    per_col = np.zeros(((n_src_ext + tile_c - 1) // tile_c + 1, tile_c),
                       np.int64)
    np.add.at(per_col, cb, tiles.sum(axis=1, dtype=np.int64))
    return int(per_row.max()), int(per_col.max())


def build_block_layouts(src_all, dst_all, n_dst, n_src_ext, perm_inner,
                        perm_ext, occupancy_min=256,
                        tile_budget_bytes=4 << 30, agree=None,
                        tile_r=TR, tile_c=TC):
    """Hybrid layout for all local parts. perm_inner [P, n_dst] /
    perm_ext [P, n_src_ext]: cluster position per original row (the inner
    prefix of perm_ext must equal perm_inner).

    `agree`: optional callable (dict of int arrays) -> elementwise-maxed
    dict, used on multi-host runs so every process builds identically-shaped
    tile stacks and residual ELL tables from its LOCAL parts alone (the
    trainer wires jax process_allgather through it).

    Returns (fwd BlockSpec, bwd BlockSpec, ell pair (spec, spec, buckets),
    arrays dict stacked on parts)."""
    P = src_all.shape[0]

    def one_part(p):
        real = dst_all[p] < n_dst
        s, d = src_all[p][real], dst_all[p][real]
        tiles, rb, cb, resid, xr, xc, rle = _build_tiles(
            perm_inner[p], perm_ext[p], n_dst, n_src_ext, d, s, occupancy_min,
            tile_budget_bytes, tile_r=tile_r, tile_c=tile_c)
        # excess-multiplicity edges come back in PERMUTED coordinates —
        # map to original ids for the residual ELL. perm_* are true
        # permutations, so the inverse is a single scatter (~8x vs the
        # legacy argsort; same values).
        if layout_fastpath():
            orig_inner = np.empty(n_dst, dtype=np.intp)
            orig_inner[perm_inner[p]] = np.arange(n_dst)
            orig_ext = np.empty(n_src_ext, dtype=np.intp)
            orig_ext[perm_ext[p]] = np.arange(n_src_ext)
        else:
            orig_inner = np.argsort(perm_inner[p], kind="stable")
            orig_ext = np.argsort(perm_ext[p], kind="stable")
        return ((tiles, rb, cb, rle),
                np.concatenate([s[resid], orig_ext[xc]]),
                np.concatenate([d[resid], orig_inner[xr]]))

    # parts build concurrently (ell.build_workers pool; results in part
    # order, so stacked layouts are bit-identical to the serial build)
    results = run_parallel([partial(one_part, p) for p in range(P)])
    per_part = [r[0] for r in results]
    res_src = [r[1] for r in results]
    res_dst = [r[2] for r in results]

    B = max(max(e[0].shape[0] for e in per_part), 1)
    # max dense edges on any single output row, per direction (the spmm
    # runs per part under shard_map, so the per-part max is the bound):
    # caps the int8 Pallas accumulator at 127*127*max_row_dense
    mrd_f = mrd_b = 0
    area = tile_r * tile_c
    for p, (tiles, rb, cb, rle) in enumerate(per_part):
        if tiles.shape[0] == 0:
            continue
        if rle is not None:
            # O(occupied cells) bincount over the RLE — same clamped int8
            # counts the stack stores, grouped by the same (block, lane)
            # keys _row_dense_maxima sums, so the maxima are identical
            # without two more full passes over the multi-GB stack
            uc, c8 = rle
            t = uc // area
            r = (uc % area) // tile_c
            c = uc % tile_c
            m_f = int(np.bincount(rb[t].astype(np.int64) * tile_r + r,
                                  weights=c8).max())
            m_b = int(np.bincount(cb[t].astype(np.int64) * tile_c + c,
                                  weights=c8).max())
        else:
            m_f, m_b = _row_dense_maxima(tiles, rb, cb, n_dst, n_src_ext,
                                         tile_r, tile_c)
        mrd_f, mrd_b = max(mrd_f, m_f), max(mrd_b, m_b)
    # residual geometry stats (mergeable across hosts)
    acc_f, acc_b = GeoAccum(ELL_SPLIT_CAP), GeoAccum(ELL_SPLIT_CAP)
    for p in range(P):
        acc_f.add_part(np.bincount(res_dst[p], minlength=n_dst))
        acc_b.add_part(np.bincount(res_src[p], minlength=n_src_ext))
    if agree is not None:
        merged = agree({"B": np.asarray([B], np.int64),
                        "mrd": np.asarray([mrd_f, mrd_b], np.int64),
                        "geo_f": acc_f.state(), "geo_b": acc_b.state()})
        B = int(merged["B"][0])
        mrd_f, mrd_b = int(merged["mrd"][0]), int(merged["mrd"][1])
        acc_f.merge_state(merged["geo_f"])
        acc_b.merge_state(merged["geo_b"])
    res_geometry = {"fwd": acc_f.finish(), "bwd": acc_b.finish()}
    n_rb_f = (n_dst + tile_r - 1) // tile_r
    n_rb_b = (n_src_ext + tile_c - 1) // tile_c

    def build_residual():
        # residual ELL over the leftover edges (shared fwd+bwd edge set)
        e_max = max(max((len(s) for s in res_src), default=0), 8)
        e_max = ((e_max + 7) // 8) * 8
        r_src = np.zeros((P, e_max), dtype=np.int32)
        r_dst = np.full((P, e_max), n_dst, dtype=np.int32)
        for p in range(P):
            k = len(res_src[p])
            r_src[p, :k] = res_src[p]
            r_dst[p, :k] = res_dst[p]
            res_src[p] = res_dst[p] = None
        return build_layouts(r_src, r_dst, n_dst, n_src_ext,
                             geometry=res_geometry)

    def build_stacks():
        nonlocal tiles_f
        if P == 1 and per_part[0][0].shape[0] == B:
            # single local part fills the stack exactly: alias instead of
            # a second 2+ GB copy (the fwd stack IS the part's tile stack)
            tiles_f = per_part[0][0][None]
        else:
            tiles_f = np.zeros((P, B, tile_r, tile_c), dtype=np.int8)
        for p in range(P):
            tiles, rb, cb, _ = per_part[p]
            bp = tiles.shape[0]
            if bp:
                if tiles_f.base is not tiles:
                    tiles_f[p, :bp] = tiles
                rowb_f[p, :bp] = rb
                colb_f[p, :bp] = cb
                # the backward visits the same tiles transposed, cb-sorted:
                # bwd tile j is fwd tile order[j] read as (cb, rb)^T
                o = np.argsort(cb, kind="stable")
                order_b[p, :bp] = o
                rowb_b[p, :bp] = cb[o]
                colb_b[p, :bp] = rb[o]
            # release this part's stack as soon as it's copied (the P==1
            # alias survives through tiles_f.base)
            per_part[p] = None

    tiles_f = None
    rowb_f = np.full((P, B), n_rb_f, dtype=np.int32)
    colb_f = np.zeros((P, B), dtype=np.int32)
    # pad slots visit their own (all-zero) tile into the trash row block
    order_b = np.tile(np.arange(B, dtype=np.int32), (P, 1))
    rowb_b = np.full((P, B), n_rb_b, dtype=np.int32)
    colb_b = np.zeros((P, B), dtype=np.int32)
    if layout_fastpath():
        # residual ELL FIRST, while the per-part stacks are the only live
        # multi-GB objects: with the assembled stack also resident the
        # same build measures ~5x slower on a 1-vCPU host (page-table /
        # TLB pressure from the extra GBs dominates its random gathers)
        ell_fwd, ell_bwd, ell_arrays = build_residual()
        build_stacks()
    else:
        build_stacks()
        ell_fwd, ell_bwd, ell_arrays = build_residual()

    arrays = {
        "blk_tiles_fwd": tiles_f, "blk_rowb_fwd": rowb_f,
        "blk_colb_fwd": colb_f,
        "blk_order_bwd": order_b, "blk_rowb_bwd": rowb_b,
        "blk_colb_bwd": colb_b,
        "blk_perm_ext": perm_ext.astype(np.int32),
        "blk_perm_inner": perm_inner.astype(np.int32),
    }
    for k, v in ell_arrays.items():
        arrays[f"res_{k}"] = v

    fwd = BlockSpec(n_rows=n_dst, n_src=n_src_ext, row_tile=tile_r,
                    col_tile=tile_c, n_blocks=B, n_row_blocks=n_rb_f,
                    max_row_dense=mrd_f)
    bwd = BlockSpec(n_rows=n_src_ext, n_src=n_dst, row_tile=tile_c,
                    col_tile=tile_r, n_blocks=B, n_row_blocks=n_rb_b,
                    max_row_dense=mrd_b)
    return fwd, bwd, (ell_fwd, ell_bwd), arrays


def _compact_rank_perm(perm_full: np.ndarray, mask: np.ndarray,
                       n_pad: int) -> np.ndarray:
    """Cluster positions for a compact row subset: compact row c (the c-th
    True of `mask` in ascending original id) takes the RANK of its full
    cluster position among the subset — the split layouts inherit the full
    build's locality without re-clustering. Padded compact slots fill the
    remaining positions (each position used exactly once)."""
    rows = np.nonzero(mask)[0]
    vals = perm_full[rows]
    if layout_fastpath():
        # rank of each subset value = count of smaller subset values: one
        # presence mask + cumsum over the full space, O(N) vs the argsort's
        # O(S log S) — identical ranks (the values are distinct)
        present = np.zeros(len(perm_full), dtype=bool)
        present[vals] = True
        rank = (np.cumsum(present) - 1)[vals]
    else:
        order = np.argsort(vals, kind="stable")
        rank = np.empty(len(rows), dtype=np.int64)
        rank[order] = np.arange(len(rows))
    out = np.empty(n_pad, dtype=np.int64)
    out[:len(rows)] = rank
    out[len(rows):] = np.arange(len(rows), n_pad)
    return out


def build_split_block_layouts(src_all, dst_all, n_dst, n_src_ext, perm_inner,
                              perm_ext, occupancy_min=256,
                              tile_budget_bytes=4 << 30,
                              tile_r=TR, tile_c=TC):
    """Interior/frontier row-partitioned hybrid layouts (--overlap split).

    Same row split as ops/ell.build_split_layouts — interior rows (no halo
    in-neighbor) aggregate from the owned rows alone, frontier rows from the
    extended space — realized as two complete hybrid builds (dense MXU tiles
    + ELL residual each): the interior build's dense tiles are what the XLA
    scheduler overlaps with the halo collective. Dense-tile coverage is
    preserved because the compact row orders keep the full build's cluster
    locality (_compact_rank_perm).

    Returns ((int_fwd, int_bwd, int_ell_pair), (fro_fwd, fro_bwd,
    fro_ell_pair), arrays, n_int_pad, n_fro_pad); arrays holds the two
    builds' tables under 'int_*'/'fro_*' prefixes plus 'merge_perm'
    [P, n_dst] int32 (recombination back to original row order)."""
    from bnsgcn_tpu.ops.spmm import split_row_partition
    P = src_all.shape[0]
    masks, merge_perm, (si, di, n_int_pad), (sf, df, n_fro_pad) = \
        split_row_partition(src_all, dst_all, n_dst)
    pi_int = np.stack([_compact_rank_perm(perm_inner[p], ~masks[p],
                                          n_int_pad) for p in range(P)])
    pi_fro = np.stack([_compact_rank_perm(perm_inner[p], masks[p],
                                          n_fro_pad) for p in range(P)])
    # interior gathers from the owned row space (cols perm = the full inner
    # cluster order); frontier gathers from the full extended space
    (int_build, fro_build) = run_parallel([
        partial(build_block_layouts, si, di, n_int_pad, n_dst,
                pi_int, perm_inner, occupancy_min=occupancy_min,
                tile_budget_bytes=tile_budget_bytes,
                tile_r=tile_r, tile_c=tile_c),
        partial(build_block_layouts, sf, df, n_fro_pad, n_src_ext,
                pi_fro, perm_ext, occupancy_min=occupancy_min,
                tile_budget_bytes=tile_budget_bytes,
                tile_r=tile_r, tile_c=tile_c)])
    int_f, int_b, int_pair, int_arr = int_build
    fro_f, fro_b, fro_pair, fro_arr = fro_build
    arrays = {"merge_perm": merge_perm}
    arrays.update({f"int_{k}": v for k, v in int_arr.items()})
    arrays.update({f"fro_{k}": v for k, v in fro_arr.items()})
    return ((int_f, int_b, int_pair), (fro_f, fro_b, fro_pair),
            arrays, n_int_pad, n_fro_pad)


def dense_edge_count(arrays, part: int = 0) -> int:
    """Diagnostic: number of edges carried by the dense tiles of one part.

    Layout-shape agnostic: the unified layout stores a bare
    `blk_tiles_fwd`; the split-overlap layout prefixes its two stacks
    (`int_blk_tiles_fwd` + `fro_blk_tiles_fwd`); and a side whose
    occupancy filter kept zero dense tiles omits its key entirely.
    Summing whichever keys exist covers all three (a fully-ELL layout
    counts 0 dense edges)."""
    total = 0
    for key in ("blk_tiles_fwd", "int_blk_tiles_fwd", "fro_blk_tiles_fwd"):
        tiles = arrays.get(key)
        if tiles is not None:
            # per-tile int32 sums (a 512x512 tile holds at most 2^25), then
            # int64: no 8x copy of the (multi-GB) int8 stack, and half the
            # set-up time of one int64 reduction over it
            total += int(np.asarray(tiles[part]).sum(
                axis=(1, 2), dtype=np.int32).sum(dtype=np.int64))
    return total


def build_x_slabs(spec: BlockSpec, perm_src, h):
    """X in cluster order, sliced into [n_cb, col_tile, H] slabs — shared by
    the XLA and Pallas dense paths so pad/permutation handling cannot drift."""
    H = h.shape[1]
    n_cb = (spec.n_src + spec.col_tile - 1) // spec.col_tile
    pad_src = n_cb * spec.col_tile
    # inv_src[pos] = original id at cluster position pos (pad -> zero row)
    inv_src = jnp.full((pad_src,), spec.n_src, jnp.int32).at[perm_src].set(
        jnp.arange(spec.n_src, dtype=jnp.int32))
    hp = jnp.concatenate([h, jnp.zeros((1, H), h.dtype)], 0)
    return hp[inv_src].reshape(n_cb, spec.col_tile, H)


def _tile_chunk_for(n_blocks: int, row_tile: int, width: int,
                    budget_bytes: int = 768 << 20,
                    col_tile: int = 0) -> int:
    """Tiles per scan chunk so the f32 per-tile partial product stays under
    `budget_bytes`. Without chunking, [B, TR, H] f32 partials at bench scale
    (B=8192, H=602 in the use_pp precompute) are 9.5 GB of HLO temp — over
    a v5e's 16 GB HBM (observed OOM at jit(precompute)). The budget trades
    peak temp against accumulator re-traffic: each scan iteration re-reads
    and re-writes the [n_row_blocks+1, TR, H] carry (~120 MB at H=256), so
    fewer/larger chunks cost less HBM bandwidth — 768 MB keeps the
    width-602 precompute near 2 GB of live temps and the H=256 train step
    at ~6 chunks (~1.4 GB of carry traffic per pass instead of ~3.8 GB)."""
    per_tile = row_tile * width * 4
    # the int8 path (col_tile > 0) adds per-chunk quantization temps on top
    # of the f32 partial: xc [C, TC, H] f32 + qc [C, TC, H] int8 — without
    # this the budget understates int8 peak temps ~3x (round-4 OOM class)
    if col_tile:
        per_tile += col_tile * width * 5
    c = max(64, budget_bytes // per_tile)
    return int(min(n_blocks, c))


def _dense_apply(spec: BlockSpec, tiles, rowb, colb, perm_src, perm_out, h,
                 dense_dtype: str = "native", order=None):
    """Dense-tile aggregation; returns [n_rows, H] in ORIGINAL row order.

    With `order` (the backward) `tiles` is the forward stack and tile j of
    this direction is tiles[order[j]] transposed: the scan runs over the
    stack as stored, each tile contracted over its rows, and segment-sums
    into the row block it lands in (unsorted in stack order).

    dense_dtype='int8' quantizes each [TC, H] activation slab to int8 with
    one scale (symmetric, amax/127) and runs the tile matmul fully in int8
    (the tiles are int8 edge multiplicities already): the v5e MXU moves
    int8 at ~2x the bf16 rate, the bf16 tile conversion disappears, and
    slab HBM traffic halves. The per-slab scale is finer than the fp8
    gather path's per-call scale; sums over ~10^2-edge rows average the
    rounding error out. Guarded end-to-end by the bench loss gates.

    The tile stack is processed in `lax.scan` chunks (bounded [C, TR, H]
    partials + one [n_row_blocks+1, TR, H] accumulator) instead of one
    [B, TR, H] einsum, keeping HLO temps flat in B."""
    H = h.shape[1]
    B = tiles.shape[0]
    sorted_ids = order is None
    prod = "brc,bch->brh"
    if order is not None:
        # this direction's ids, in the order the stack stores its tiles
        at = jnp.zeros_like(order).at[order].set(
            jnp.arange(B, dtype=order.dtype))
        rowb, colb, prod = rowb[at], colb[at], "bcr,bch->brh"
    x_perm = build_x_slabs(spec, perm_src, h)
    if dense_dtype == "int8":
        # per-slab scales from the input-dtype amax (bf16 values are exact
        # in f32, so this equals the old full-f32 amax); quantization runs
        # chunk-wise inside the scan body — the old whole-stack
        # `x_perm.astype(f32)` copy OOM'd the v5e HBM at the width-602
        # use_pp precompute (round-4 measured RESOURCE_EXHAUSTED)
        scale = jnp.maximum(
            jnp.max(jnp.abs(x_perm), axis=(1, 2)).astype(jnp.float32) / 127.0,
            1e-30)                                         # [n_cb]

        def chunk_prod(tiles_c, colb_c):
            xc = x_perm[colb_c].astype(jnp.float32)
            qc = jnp.clip(jnp.round(xc / scale[colb_c][:, None, None]),
                          -127, 127).astype(jnp.int8)
            p = jnp.einsum(prod, tiles_c, qc,
                           preferred_element_type=jnp.int32)
            return p.astype(jnp.float32) * scale[colb_c][:, None, None]
    else:
        def chunk_prod(tiles_c, colb_c):
            return jnp.einsum(prod, tiles_c.astype(h.dtype),
                              x_perm[colb_c],
                              preferred_element_type=jnp.float32)

    n_seg = spec.n_row_blocks + 1
    C = _tile_chunk_for(B, spec.row_tile, H,
                        col_tile=(spec.col_tile
                                  if dense_dtype == "int8" else 0))
    n_full = B // C                       # >= 1: C = min(B, ...) above
    rem = B - n_full * C

    def body(acc, x):
        tiles_c, rowb_c, colb_c = x
        s = jax.ops.segment_sum(chunk_prod(tiles_c, colb_c), rowb_c,
                                num_segments=n_seg,
                                indices_are_sorted=sorted_ids)
        return acc + s, None

    # full chunks go through the scan as a prefix-slice + reshape (both
    # copy-free in XLA); the B%C remainder runs as ONE extra, smaller
    # segment-sum below instead of zero-padding the whole tile stack —
    # the old pad-concatenate materialized a transient copy of the stack
    # (~2 GB at bench scale) inside jit whenever B wasn't a chunk multiple
    xs = (tiles[:n_full * C].reshape(n_full, C, *tiles.shape[1:]),
          rowb[:n_full * C].reshape(n_full, C),
          colb[:n_full * C].reshape(n_full, C))

    # derive the init carry from the input so it carries the same varying
    # manual axes as the body output under shard_map (scan rejects an
    # unvarying zeros init against a parts-varying accumulator); the empty
    # slice reads no data, so a non-finite activation cannot leak NaN here
    acc0 = jnp.zeros((n_seg, spec.row_tile, H), jnp.float32) \
        + jnp.sum(x_perm[:0]).astype(jnp.float32)
    seg, _ = jax.lax.scan(body, acc0, xs)
    if rem:
        seg = seg + jax.ops.segment_sum(
            chunk_prod(tiles[n_full * C:], colb[n_full * C:]),
            rowb[n_full * C:], num_segments=n_seg,
            indices_are_sorted=sorted_ids)
    seg = seg[:spec.n_row_blocks]
    flat = seg.reshape(spec.n_row_blocks * spec.row_tile, H).astype(h.dtype)
    return flat[perm_out]                                  # original row order


# int8 Pallas accumulator bound: the fused kernel keeps exact int32 row sums
# of |q|<=127 x |mult|<=127 products, so a row with more than
# int32_max/(127*127) ~= 133k dense edges could silently wrap. The max
# per-row dense edge count is static in the layout (max_row_dense; getattr
# for layouts cached before the field existed -> 0 = unknown, guard
# skipped). Overflow-risk rows route to the XLA path, whose int8
# formulation rescales to f32 per chunk (no wrap possible).
_I8_ROW_CAP = (2**31 - 1) // (127 * 127)


def dense_path(spec_d: BlockSpec, dense_dtype: str) -> str:
    """Which implementation runs one direction's dense tiles: 'pallas' (the
    fused Mosaic kernel, ops/pallas_block) on a TPU backend, else 'xla'
    (_dense_apply: a backend Mosaic does not lower to, or an int8 layout
    past the kernel's int32 accumulator bound). It reads only what it can
    observe: the backend, the slab dtype and the layout's max_row_dense.
    The ONE place the choice is made: the compiled step and the run header
    (trainer.dense_paths) both read it, so a log always shows what ran."""
    if (jax.default_backend() == "tpu"
            and (dense_dtype != "int8"
                 or getattr(spec_d, "max_row_dense", 0) <= _I8_ROW_CAP)):
        return "pallas"
    return "xla"


def make_block_spmm(fwd: BlockSpec, bwd: BlockSpec, ell_pair,
                    gather_dtype: str = "native",
                    dense_dtype: str = "native", accum: str = "auto"):
    """Returns spmm(arrays, h_ext) -> [n_dst, H]: dense tiles on the MXU +
    ELL residual, custom VJP reading the same tiles transposed.
    dense_dtype='int8': quantized int8 MXU tile path — per-slab scales on
    the XLA formulation (_dense_apply), one per-call scale on the fused
    Pallas kernel (pallas_block.dense_apply_pallas).
    accum: residual-ELL accumulation strategy (ops/ell._bucket_sum)."""
    ell_fwd, ell_bwd = ell_pair
    ell = make_ell_spmm(ell_fwd, ell_bwd, len(ell_fwd.widths),
                        len(ell_bwd.widths), gather_dtype=gather_dtype,
                        accum=accum)
    # transposed residual operator for the backward: same tables with the
    # fwd/bwd roles swapped (a nested vjp at a dummy point would record an
    # unvarying primal and trip shard_map's varying-axes check)
    ell_t = make_ell_spmm(ell_bwd, ell_fwd, len(ell_bwd.widths),
                          len(ell_fwd.widths), gather_dtype=gather_dtype,
                          accum=accum)

    def _res_arrays(arrays):
        return {k[len("res_"):]: v for k, v in arrays.items()
                if k.startswith("res_")}

    @jax.named_scope(tp.AGG_TILES)
    def _dense(spec_d, arrays, d, h):
        # both directions read the one forward stack; the backward visits
        # it through blk_order_bwd, transposed
        perm_src, perm_out = (("blk_perm_ext", "blk_perm_inner") if d == "fwd"
                              else ("blk_perm_inner", "blk_perm_ext"))
        ops = (spec_d, arrays["blk_tiles_fwd"], arrays[f"blk_rowb_{d}"],
               arrays[f"blk_colb_{d}"], arrays[perm_src], arrays[perm_out], h)
        order = arrays["blk_order_bwd"] if d == "bwd" else None
        if dense_path(spec_d, dense_dtype) == "pallas":
            from bnsgcn_tpu.ops.pallas_block import dense_apply_pallas
            return dense_apply_pallas(*ops, order=order,
                                      dense_dtype=dense_dtype)
        return _dense_apply(*ops, dense_dtype=dense_dtype, order=order)

    def _swap_dirs(arrays):
        out = {}
        for k, v in arrays.items():
            if k.startswith("fwd_"):
                out["bwd_" + k[4:]] = v
            elif k.startswith("bwd_"):
                out["fwd_" + k[4:]] = v
            else:
                out[k] = v
        return out

    @jax.custom_vjp
    def spmm(arrays, h_ext):
        dense = _dense(fwd, arrays, "fwd", h_ext)
        return dense + ell(_res_arrays(arrays), h_ext)

    def fwd_rule(arrays, h_ext):
        return spmm(arrays, h_ext), (arrays,)

    def bwd_rule(res, g):
        (arrays,) = res
        d_dense = _dense(bwd, arrays, "bwd", g)
        d_res = ell_t(_swap_dirs(_res_arrays(arrays)), g)
        return None, (d_dense + d_res).astype(g.dtype)

    spmm.defvjp(fwd_rule, bwd_rule)
    return spmm


def cluster_order(src, dst, n_rows, n_ext, target=TC
                  ) -> tuple[np.ndarray, np.ndarray]:
    """Locality permutation of the (inner, extended) row spaces.

    Inner rows: clustered by the native partitioner (LDG streaming + light
    refinement) into ~n_rows/target balanced groups, ordered group-major —
    structural clustering, no labels involved. Halo rows keep their slot
    order (already grouped by owning peer). Returns (perm_inner [n_rows],
    perm_ext [n_ext]): each row's position in cluster order; the inner
    prefix of perm_ext equals perm_inner."""
    n_clusters = max(int(np.ceil(n_rows / max(target, 1))), 1)
    order = None
    src = np.asarray(src)
    dst = np.asarray(dst)
    inner = (src < n_rows) & (dst < n_rows)
    if n_clusters > 1 and inner.any():
        from bnsgcn_tpu.native import native_partition

        class _G:                           # minimal adapter for the binding
            pass

        gg = _G()
        gg.src = src[inner].astype(np.int64)
        gg.dst = dst[inner].astype(np.int64)
        gg.n_nodes = n_rows
        # a failed native build raises here: an unclustered order would
        # silently build a layout with other tile coverage
        cid = native_partition(gg, n_clusters, obj="cut",
                               seed=0, refine_passes=2, n_seeds=1)
        order = np.argsort(cid, kind="stable")
    if order is None:
        order = np.arange(n_rows)
    perm_inner = np.empty(n_rows, dtype=np.int64)
    perm_inner[order] = np.arange(n_rows)
    perm_ext = np.concatenate([perm_inner,
                               np.arange(n_rows, n_ext, dtype=np.int64)])
    return perm_inner, perm_ext
