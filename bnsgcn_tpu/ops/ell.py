"""Bucketed-ELLPACK sparse aggregation — the TPU-shaped SpMM.

`jax.ops.segment_sum` lowers to an XLA scatter-add, which serializes on TPU
(~120 GB/s effective on a v5e where HBM does ~800). This module reformulates
the same aggregation (reference DGL SpMM, module/layer.py:35-37,88-90) as
dense, scatter-free work:

  * offline (numpy, per part): group destination rows by in-degree into
    power-of-two buckets; within a bucket store src indices as a dense
    [rows, width] ELL table padded with a dummy index;
  * on device: per bucket, `h[idx]` (a batched row gather — fast on TPU) and
    a dense sum over the width axis; results land via one unique-index
    row permutation (a gather, not a scatter);
  * backward uses a second, transposed layout (rows = source nodes, grouped
    by out-degree) through `jax.custom_vjp`, so the gradient is the same
    scatter-free shape: d_h[u] = sum over out-edges of g[dst].

Bucket widths are powers of two, so ELL padding wastes < 2x gathers; rows
with degree 0 (structural padding) are skipped entirely.

Layouts stack across partition parts (shared bucket shapes = max over parts)
and ride through shard_map as ordinary sharded int arrays.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bnsgcn_tpu.utils import traceparse as tp


def build_workers(n_tasks: int, cap: int = 8) -> int:
    """Host-parallelism width for offline layout builds (ROADMAP open item:
    the hybrid build was ~980 s of single-threaded numpy at bench scale).
    The heavy kernels (sorts, bincounts, fancy indexing) run per part /
    per direction in a ThreadPoolExecutor — no pickling of the multi-GB
    inputs. BNSGCN_BUILD_WORKERS=1 restores strictly serial builds (or any
    explicit width caps the pool)."""
    env = os.environ.get("BNSGCN_BUILD_WORKERS")
    if env:
        return max(1, min(int(env), max(n_tasks, 1)))
    return max(1, min(cap, os.cpu_count() or 1, max(n_tasks, 1)))


def run_parallel(fns):
    """Run thunks via ThreadPoolExecutor (results in order); serial when the
    worker budget is 1 so BNSGCN_BUILD_WORKERS=1 gives bit-identical
    single-threaded behavior."""
    w = build_workers(len(fns))
    if w <= 1 or len(fns) <= 1:
        return [f() for f in fns]
    with ThreadPoolExecutor(max_workers=w) as ex:
        futs = [ex.submit(f) for f in fns]
        return [f.result() for f in futs]


ELL_SPLIT_CAP = 128   # rows with degree > cap are split into cap-wide chunks


def layout_fastpath() -> bool:
    """BNSGCN_LAYOUT_FASTPATH=0 pins the legacy np.unique/argsort layout
    passes. Both paths are bitwise-identical by construction; the toggle
    exists so tests can assert that and bisects can isolate the builders."""
    return os.environ.get("BNSGCN_LAYOUT_FASTPATH", "1") != "0"


def grouped_order(keys: np.ndarray, n_keys: int) -> np.ndarray:
    """Stable argsort of small-int `keys` — the layout builders' dominant
    pass (edges sorted by destination row). Fast path packs (key, index)
    into one int64 and runs numpy's SIMD quicksort: the packed keys are
    distinct, so the unstable sort reproduces the kind='stable' order
    exactly (~7x on 20M edges, numpy 2.0). Falls back to stable argsort
    when the packed key would overflow int64 or the fast path is off."""
    n = len(keys)
    bits = max(int(n - 1).bit_length(), 1)
    if n and layout_fastpath() and (int(n_keys) << bits) < 2**63:
        packed = (keys.astype(np.int64) << bits) \
            | np.arange(n, dtype=np.int64)
        packed.sort()
        return packed & ((1 << bits) - 1)
    return np.argsort(keys, kind="stable")


@dataclass(frozen=True)
class EllSpec:
    """Static bucket geometry (identical across parts)."""
    widths: tuple[int, ...]            # bucket ELL widths, ascending powers of 2
    rows: tuple[int, ...]              # padded row count per bucket
    n_rows: int                        # output rows (n_dst for fwd, n_src_ext for bwd)
    n_src: int                         # gatherable rows (n_src_ext for fwd, n_dst for bwd)
    n_split: int = 0                   # padded count of split (degree > cap) rows
    n_chunks: int = 0                  # padded count of their cap-wide chunks


def _bucketize(deg: np.ndarray, widths: Sequence[int]) -> np.ndarray:
    """bucket index per row; deg 0 -> -1 (skipped)."""
    b = np.full(deg.shape, -1, dtype=np.int32)
    lo = 0
    for k, w in enumerate(widths):
        b[(deg > lo) & (deg <= w)] = k
        lo = w
    return b


def build_ell_numpy(src: np.ndarray, dst: np.ndarray, n_rows: int, n_src: int,
                    widths: Sequence[int] | None = None,
                    row_pad: Sequence[int] | None = None,
                    cap: int | None = None,
                    split_pad: int = 0, chunk_pad: int = 0):
    """Build one part's ELL tables for `out[r] = sum_{e: dst_e == r} h[src_e]`.

    Padded edges must already point at dst == n_rows (they are dropped).
    Returns (widths, rows_per_bucket, idx_arrays, perm, chunk_pos, chunk_seg).

    Split-row scheme (`cap`): rows with degree > cap become ceil(deg/cap)
    cap-wide pseudo-rows appended to the cap bucket (cutting the power-law
    padding waste from ~1.5x to ~1.15x of E); their partial sums are combined
    by a tiny sorted segment-sum over `chunk_pos`/`chunk_seg`. Table layout:
    [bucket rows 0..T-1 ; combine results T..T+split_pad-1 ; zero row].
    `perm[r]` points a normal row at its bucket position, a split row at its
    combine slot, and a degree-0 row at the zero row.
    """
    if cap is not None and (cap < 4 or cap & (cap - 1)):
        raise ValueError(f"split cap must be a power of two >= 4, got {cap}")
    real = dst < n_rows
    src, dst = src[real], dst[real]
    deg = np.bincount(dst, minlength=n_rows)
    split_mask = (deg > cap) if cap else np.zeros(n_rows, dtype=bool)
    deg_b = np.where(split_mask, 0, deg)
    if widths is None:
        # ladder from the FULL degree distribution so it reaches cap whenever
        # any row splits (deg_b alone would stop short of cap)
        widths = _choose_widths(deg, cap=cap)
    if cap and split_mask.any() and widths[-1] != cap:
        raise ValueError(f"width ladder {widths} must end at cap={cap} "
                         f"when split rows exist")
    bucket = _bucketize(deg_b, widths)

    order = grouped_order(dst, n_rows)
    src_sorted = src[order]
    dst_sorted = dst[order]
    indptr = np.zeros(n_rows + 1, dtype=np.int64)
    np.cumsum(deg, out=indptr[1:])

    # split bookkeeping: pseudo-row base per split row, chunk segments
    split_rows = np.nonzero(split_mask)[0]
    n_split = len(split_rows)
    chunks_per = np.ceil(deg[split_rows] / cap).astype(np.int64) if n_split else         np.zeros(0, np.int64)
    n_pseudo = int(chunks_per.sum())
    assert n_split <= max(split_pad, 0) or split_pad == 0
    pseudo_base = np.zeros(n_rows, dtype=np.int64)
    if n_split:
        pseudo_base[split_rows] = np.concatenate([[0], np.cumsum(chunks_per)[:-1]])

    # fully vectorized fill: for each edge, its (bucket, row-within-bucket,
    # slot-within-row) — no per-row python loop (matters at 100M edges)
    rpos = np.zeros(n_rows, dtype=np.int64)
    within = np.arange(len(dst_sorted), dtype=np.int64) - indptr[dst_sorted]
    e_bucket = bucket[dst_sorted]
    e_split = split_mask[dst_sorted]

    rows_per_bucket = []
    perm = np.zeros(n_rows, dtype=np.int32)
    offset = 0
    cap_k = len(widths) - 1
    # bucket geometry in one cheap row-level pass, shared by both fill paths
    flat_base = np.zeros(len(widths) + 1, dtype=np.int64)
    cap_offset = cap_normal = 0
    for k, w in enumerate(widths):
        rows_k = np.nonzero(bucket == k)[0]
        n_k = len(rows_k)
        extra = n_pseudo if (cap and k == cap_k) else 0
        pad_rows = row_pad[k] if row_pad is not None else n_k + extra
        assert pad_rows >= n_k + extra
        rpos[rows_k] = np.arange(n_k)
        perm[rows_k] = offset + np.arange(n_k, dtype=np.int32)
        if cap and k == cap_k:
            cap_offset, cap_normal = offset, n_k
        rows_per_bucket.append(pad_rows)
        offset += pad_rows
        flat_base[k + 1] = flat_base[k] + pad_rows * w
    total = offset                                 # table rows T

    if layout_fastpath():
        # one flat table + one collision-free scatter for ALL buckets —
        # each edge owns a distinct (row, slot), so a single fancy-index
        # write replaces the per-bucket O(E x buckets) full-edge masks
        idx_flat = np.full(int(flat_base[-1]), n_src, dtype=np.int32)
        w_arr = np.asarray(widths, dtype=np.int64)
        ns = ~e_split
        eb = e_bucket[ns]
        idx_flat[flat_base[eb] + rpos[dst_sorted[ns]] * w_arr[eb]
                 + within[ns]] = src_sorted[ns]
        if n_pseudo:
            es = e_split
            pr = cap_normal + pseudo_base[dst_sorted[es]] + within[es] // cap
            idx_flat[flat_base[cap_k] + pr * w_arr[cap_k]
                     + within[es] % cap] = src_sorted[es]
        idx_arrays = [idx_flat[flat_base[k]:flat_base[k + 1]]
                      .reshape(rows_per_bucket[k], w)
                      for k, w in enumerate(widths)]
    else:
        idx_arrays = []
        for k, w in enumerate(widths):
            idx = np.full((rows_per_bucket[k] * w,), n_src, dtype=np.int32)
            sel = (e_bucket == k) & ~e_split
            idx[rpos[dst_sorted[sel]] * w + within[sel]] = src_sorted[sel]
            if cap and k == cap_k and n_pseudo:
                sel = e_split
                pr = (cap_normal + pseudo_base[dst_sorted[sel]]
                      + within[sel] // cap)
                idx[pr * w + within[sel] % cap] = src_sorted[sel]
            idx_arrays.append(idx.reshape(rows_per_bucket[k], w))

    sp = split_pad if split_pad else ((n_split + 7) // 8 * 8 if n_split else 0)
    cp = chunk_pad if chunk_pad else ((n_pseudo + 7) // 8 * 8 if n_pseudo else 0)
    # chunk_pos indexes the CAP BUCKET's rows (plus one appended zero row at
    # rows_per_bucket[-1]) — not the whole table — so the combine gathers from
    # the cap bucket output directly without re-materializing the table
    cap_rows = rows_per_bucket[-1] if rows_per_bucket else 0
    chunk_pos = np.full(cp, cap_rows, dtype=np.int32)   # pad -> appended zero row
    chunk_seg = np.full(cp, sp, dtype=np.int32)         # pad -> dropped segment
    # row_of[table_pos] = the output row this table row computes (split
    # pseudo-rows map to their split source; padding -> n_rows). Consumers
    # that need per-table-row context (GAT attention broadcasts el/z by row)
    # index with this.
    row_of = np.full(total, n_rows, dtype=np.int32)
    normal = (bucket >= 0)
    rws = np.nonzero(normal)[0]
    row_of[perm[rws]] = rws
    if n_split:
        chunk_pos[:n_pseudo] = cap_normal + np.arange(n_pseudo)
        chunk_seg[:n_pseudo] = np.repeat(np.arange(n_split), chunks_per)
        perm[split_rows] = total + np.arange(n_split, dtype=np.int32)
        row_of[cap_offset + cap_normal + np.arange(n_pseudo)] = \
            np.repeat(split_rows, chunks_per)
    perm[(bucket == -1) & ~split_mask] = total + sp     # zero row
    return (tuple(widths), tuple(rows_per_bucket), idx_arrays, perm,
            chunk_pos, chunk_seg, row_of)


def _choose_widths(deg: np.ndarray, cap: int | None = None) -> tuple[int, ...]:
    """Power-of-2 bucket-width ladder from 4 up to min(max degree, cap).

    (An edge-mass-quantile scheme was tried and measured *slower* on a v5e
    despite ~25% fewer padded gathers — wide low-row-count buckets hurt the
    gather/reduce pipeline more than padding does. Keep the ladder; the
    split-row cap handles the power-law tail instead.)
    """
    deg = deg[deg > 0]
    max_deg = int(deg.max()) if deg.size else 1
    if cap:
        max_deg = min(max_deg, cap)
    widths, w = [], 4
    while True:
        widths.append(w)
        if w >= max(max_deg, 1):
            break
        w *= 2
    return tuple(widths)


def _part_edges(src, dst, n_dst, direction):
    """Real edges of one part, oriented for the requested layout direction."""
    real = dst < n_dst
    if direction == "fwd":             # rows = dst, gather = src
        return src[real], dst[real]
    return dst[real], src[real]        # rows = src(ext), gather = dst


def compute_geometry(src_all: np.ndarray, dst_all: np.ndarray, n_dst: int,
                     n_src_ext: int, cap: int = ELL_SPLIT_CAP,
                     directions: tuple = ("fwd", "bwd")) -> dict:
    """Global ELL geometry (widths, padded rows, split/chunk pads) for both
    directions — a pure graph property needing the FULL set of parts.
    JSON-serializable so the offline partitioner can store it in meta.json,
    letting multi-host processes build their ELL tables from local parts
    alone (data/artifacts.py)."""
    P = src_all.shape[0]
    geo = {}
    for direction in directions:
        n_rows = n_dst if direction == "fwd" else n_src_ext
        degs = []
        for p in range(P):
            _, d = _part_edges(src_all[p], dst_all[p], n_dst, direction)
            degs.append(np.bincount(d, minlength=n_rows))
        all_deg = np.concatenate(degs)
        widths = _choose_widths(all_deg, cap=cap)
        eff_cap = cap if (cap and all_deg.max() > cap) else None
        rows_max = [0] * len(widths)
        split_max = chunk_max = 0
        for d in degs:
            split = (d > eff_cap) if eff_cap else np.zeros_like(d, dtype=bool)
            b = _bucketize(np.where(split, 0, d), widths)
            for k in range(len(widths)):
                rows_max[k] = max(rows_max[k], int(np.sum(b == k)))
            if eff_cap:
                split_max = max(split_max, int(split.sum()))
                chunk_max = max(chunk_max, int(np.ceil(d[split] / eff_cap).sum()))
        if eff_cap:
            rows_max[-1] += chunk_max          # pseudo-rows live in the cap bucket
        pad8 = lambda r: ((r + 7) // 8) * 8 if r else 0
        geo[direction] = {
            "widths": [int(w) for w in widths],
            "rows": [pad8(r) for r in rows_max],
            "split": pad8(split_max), "chunks": pad8(chunk_max),
            "cap": eff_cap,
        }
    return geo


def build_layouts(src_all: np.ndarray, dst_all: np.ndarray, n_dst: int,
                  n_src_ext: int, cap: int = ELL_SPLIT_CAP,
                  geometry: dict | None = None
                  ) -> tuple[EllSpec, EllSpec, dict]:
    """Build stacked fwd (rows = dst) and bwd (rows = src_ext) ELL layouts.

    src_all/dst_all: [P_local, E] artifact edge arrays — may be a subset of
    parts when `geometry` (from compute_geometry, possibly via meta.json)
    provides the global pads. Returns (fwd_spec, bwd_spec, arrays) with
    arrays = {'{dir}_idx_k', '{dir}_perm', '{dir}_chunk_pos',
    '{dir}_chunk_seg'} stacked on the leading local-part axis.
    """
    P = src_all.shape[0]
    if geometry is None:
        geometry = compute_geometry(src_all, dst_all, n_dst, n_src_ext, cap)

    def build_all(direction):
        n_rows = n_dst if direction == "fwd" else n_src_ext
        n_src = n_src_ext if direction == "fwd" else n_dst
        g = geometry[direction]
        widths = tuple(g["widths"])
        rows_max = tuple(g["rows"])
        split_max, chunk_max, eff_cap = g["split"], g["chunks"], g["cap"]

        def build_one(p):
            s, d = _part_edges(src_all[p], dst_all[p], n_dst, direction)
            _, _, idx, perm, cp, cs, _ = build_ell_numpy(
                s, d, n_rows, n_src, widths=widths, row_pad=rows_max,
                cap=eff_cap, split_pad=split_max, chunk_pad=chunk_max)
            return idx, perm, cp, cs

        results = run_parallel([partial(build_one, p) for p in range(P)])
        idx_stacked = [[r[0][k] for r in results] for k in range(len(widths))]
        perms = [r[1] for r in results]
        cpos = [r[2] for r in results]
        csegs = [r[3] for r in results]
        spec = EllSpec(widths=widths, rows=rows_max, n_rows=n_rows,
                       n_src=n_src, n_split=split_max, n_chunks=chunk_max)
        return (spec, [np.stack(x) for x in idx_stacked], np.stack(perms),
                np.stack(cpos), np.stack(csegs))

    (fwd_spec, fwd_idx, fwd_perm, fwd_cp, fwd_cs), \
        (bwd_spec, bwd_idx, bwd_perm, bwd_cp, bwd_cs) = run_parallel(
            [partial(build_all, "fwd"), partial(build_all, "bwd")])
    arrays = {"fwd_perm": fwd_perm, "bwd_perm": bwd_perm}
    if fwd_spec.n_split:
        arrays["fwd_chunk_pos"], arrays["fwd_chunk_seg"] = fwd_cp, fwd_cs
    if bwd_spec.n_split:
        arrays["bwd_chunk_pos"], arrays["bwd_chunk_seg"] = bwd_cp, bwd_cs
    for k in range(len(fwd_spec.widths)):
        arrays[f"fwd_idx_{k}"] = fwd_idx[k]
    for k in range(len(bwd_spec.widths)):
        arrays[f"bwd_idx_{k}"] = bwd_idx[k]
    return fwd_spec, bwd_spec, arrays


def build_split_layouts(src_all: np.ndarray, dst_all: np.ndarray, n_dst: int,
                        n_src_ext: int, cap: int = ELL_SPLIT_CAP):
    """Interior/frontier row-partitioned ELL layouts (--overlap split).

    Each part's destination rows are split by ops/spmm.frontier_mask and
    remapped to two compact row spaces (compact ids ascend with original
    id), so one layer's aggregation becomes

        interior_spmm(h)             # gathers ONLY owned rows — no halo dep
        frontier_spmm([h ; halo])    # rows that need the exchange
        out = concat(int_out, fro_out)[merge_perm]

    with `merge_perm` the recombination permutation back to original row
    order. Row-exact vs the fused layout: every output row's complete edge
    set lands on exactly one side (a frontier row's LOCAL in-edges aggregate
    on the frontier side with it). Degree-0/padded rows are interior.

    The interior pair gathers from the owned space (n_src = n_dst), so its
    backward emits d_h directly; the frontier pair gathers from the full
    extended space and its backward emits d_h_ext (the halo slice of which
    transposes through the backward exchange).

    Returns ((int_fwd, int_bwd), (fro_fwd, fro_bwd), arrays, n_int_pad,
    n_fro_pad); arrays = 'int_*'/'fro_*'-prefixed build_layouts tables plus
    'merge_perm' [P, n_dst] int32."""
    from bnsgcn_tpu.ops.spmm import split_row_partition
    _, merge_perm, (si, di, n_int_pad), (sf, df, n_fro_pad) = \
        split_row_partition(src_all, dst_all, n_dst)
    (int_f, int_b, int_arr), (fro_f, fro_b, fro_arr) = run_parallel([
        partial(build_layouts, si, di, n_int_pad, n_dst, cap=cap),
        partial(build_layouts, sf, df, n_fro_pad, n_src_ext, cap=cap)])
    arrays = {"merge_perm": merge_perm}
    arrays.update({f"int_{k}": v for k, v in int_arr.items()})
    arrays.update({f"fro_{k}": v for k, v in fro_arr.items()})
    return (int_f, int_b), (fro_f, fro_b), arrays, n_int_pad, n_fro_pad


def _bucket_sum(hp, idx, w, chunk_gathers: int = 4_000_000,
                use_pallas: bool = False, accum: str = "auto"):
    """sum over ELL width for one bucket.

    accum='unroll' (the TPU default for native-dtype rows): per-column
    accumulation `acc += hp[idx[:, j]]` in 16-column unrolled f32 chains,
    scanned over column blocks for w > 16 — no [rows, w, H] gathered
    intermediate is ever materialized, so the bucket runs near the gather
    unit's row rate instead of paying an extra HBM round-trip.
    v5e-measured on the bench cap bucket ([150k, 128] idx, H=256):
    block-scan 81.5 ms (16-col) / 79.4 ms (32-col) vs 154.4 ms for the
    chunked reduce — 1.9x; a fully-unrolled 128-chain also wins (90.5 ms)
    but blows the remote compiler up at full train-step scale, and pure
    fori/scan per column loses it all to carry re-traffic (145.7 ms).
    f32 chains also accumulate more precisely than the bf16 tree reduce.

    int8 rows unroll too: exact int32 chains (the int8->int32 convert is
    v5e-native), the caller's one per-call scale multiplies back after the
    combine — bit-identical to the reduce path's int32 sums at ~2x the
    row rate (256B rows move ~519M rows/s vs 268M at 512B).

    accum='reduce': the materialize-then-sum path, row-chunked so the
    gathered intermediate never exceeds ~chunk_gathers * H elements; it
    serves fp8 gathers (their convert must happen on the gathered block;
    e4m3 decode is VPU-emulated and loses anyway) and non-TPU backends
    (unrolled gathers lower poorly there).

    use_pallas no longer affects this function (round 5): the
    pallas_bucket_reduce dispatch was retired — superseded by the unroll,
    never hardware-validated; the kernel remains in tools/pallas_spmm as a
    study artifact. The parameter stays for signature stability with
    make_ell_spmm/make_block_spmm, whose use_pallas switches the fused
    dense-tile kernel (ops/pallas_block), which IS hardware-validated."""
    if accum not in ("auto", "unroll", "reduce"):
        raise ValueError(f"unknown accum mode {accum!r}")
    r = idx.shape[0]
    h_dim = hp.shape[1]
    if accum == "auto":
        # unroll beats BOTH the jnp chunked reduce and pallas_bucket_reduce
        # (which only fuses the reduction, not the gather materialization),
        # so use_pallas does not disable it — pass accum='reduce' explicitly
        # to study the materializing paths. int8 rows unroll too (exact
        # int32 chains, v5e-native converts); fp8 stays on reduce — e4m3
        # decode is emulated on the VPU and measured 1.8x slower than bf16.
        from bnsgcn_tpu.utils.platform import tpu_codepaths
        accum = ("unroll" if hp.dtype != jnp.float8_e4m3fn
                 and tpu_codepaths() else "reduce")
    BS = 16
    if accum == "unroll" and hp.dtype == jnp.float8_e4m3fn:
        raise ValueError("accum='unroll' supports native and int8 rows; "
                         "fp8 gathers take accum='reduce'")
    if (accum == "unroll" and r > 0 and w > 1
            and (w <= BS or w % BS == 0)):
        # int8 rows accumulate in int32 (exact, like the reduce path's
        # int32 sums — the caller's one per-call scale multiplies back
        # after the combine); native rows in f32 chains
        acc_dt = jnp.int32 if hp.dtype == jnp.int8 else jnp.float32
        out_dt = jnp.int32 if hp.dtype == jnp.int8 else hp.dtype

        def chain(cb, n):
            a = hp[cb[0]].astype(acc_dt)
            for j in range(1, n):
                a = a + hp[cb[j]].astype(acc_dt)
            return a

        if w <= BS:
            return chain(idx.T, w).astype(out_dt)
        cols = idx.T.reshape(w // BS, BS, r)
        # derive the init from the input so the carry has the same varying
        # manual axes as the body output under shard_map (same contract as
        # block_spmm._dense_apply's acc0); the empty slice reads no data
        acc0 = jnp.zeros((r, h_dim), acc_dt) \
            + jnp.sum(hp[:0]).astype(acc_dt)
        out, _ = jax.lax.scan(lambda acc, cb: (acc + chain(cb, BS), None),
                              acc0, cols)
        return out.astype(out_dt)
    rows_per_chunk = max(1, chunk_gathers // max(w, 1))
    # (round 5) pallas_bucket_reduce is no longer dispatched here: the
    # unrolled chains beat it end-to-end on the v5e (it fuses only the
    # reduction, not the gather materialization — its own docstring), its
    # hardware validation slot never materialized across two windows, and
    # keeping a non-winning TPU-only branch inside the accumulation
    # hot-path risks exactly the untested-on-hardware escapes the CPU
    # preflight exists to prevent. The kernel survives in tools/pallas_spmm
    # as a study artifact with its interpret-mode test.

    def reduce_tile(g):
        if g.dtype == jnp.float8_e4m3fn:
            # fp8 gather mode: rows travel at 1 byte/element through the
            # gather unit; the reduction must leave fp8 immediately
            return g.astype(jnp.float32).sum(axis=1)
        if g.dtype == jnp.int8:
            # int8 gather mode: same 1-byte wire, but the int8->int32
            # convert is v5e-native (fp8 decode is emulated and measured
            # 1.8x SLOWER than bf16 end to end); int32 sums of <=1024
            # rows of |q|<=127 are exact
            return g.astype(jnp.int32).sum(axis=1)
        return g.sum(axis=1)

    if r <= rows_per_chunk:
        return reduce_tile(hp[idx.reshape(-1)].reshape(r, w, h_dim))
    n_chunks = -(-r // rows_per_chunk)
    pad = n_chunks * rows_per_chunk - r
    idx_p = jnp.pad(idx, ((0, pad), (0, 0)), constant_values=hp.shape[0] - 1)
    idx_c = idx_p.reshape(n_chunks, rows_per_chunk, w)

    def body(_, ix):
        g = hp[ix.reshape(-1)].reshape(rows_per_chunk, w, h_dim)
        return None, reduce_tile(g)

    _, out = jax.lax.scan(body, None, idx_c)
    return out.reshape(n_chunks * rows_per_chunk, h_dim)[:r]


def ell_combine(spec: EllSpec, outs, perm, chunk_pos=None, chunk_seg=None):
    """Per-bucket outputs [R_k, ...] -> [n_rows, ...] via the split-row chunk
    combine (tiny sorted segment-sum) + one permutation gather. Shared by the
    SpMM and any other bucketed row computation (GAT attention backward)."""
    trailing = outs[0].shape[1:]
    zero = jnp.zeros((1,) + trailing, outs[0].dtype)
    if spec.n_split:
        # combine split-row chunks straight from the cap bucket's output
        # (chunk_pos is cap-bucket-relative; its pad points at the zero row)
        cap_z = jnp.concatenate([outs[-1], zero], axis=0)
        gathered = cap_z[chunk_pos]                    # [n_chunks, ...]
        comb = jax.ops.segment_sum(gathered, chunk_seg,
                                   num_segments=spec.n_split + 1,
                                   indices_are_sorted=True)[:spec.n_split]
        full = jnp.concatenate(list(outs) + [comb, zero], axis=0)
    else:
        full = jnp.concatenate(list(outs) + [zero], axis=0)
    return full[perm]


@jax.named_scope(tp.AGG_RESIDUAL)
def _ell_apply(spec: EllSpec, idx_list, perm, h, use_pallas: bool = False,
               chunk_pos=None, chunk_seg=None, gather_dtype: str = "native",
               accum: str = "auto"):
    """Bucketed gather+sum (+ split-row combine), then one permutation gather.
    The only scatter is the tiny sorted segment-sum over split-row chunks.

    gather_dtype='fp8': rows are quantized (one per-call e4m3 scale) BEFORE
    the gather, halving wire bytes vs bf16 — the gather unit is row-rate
    bound below 512B rows, so 256-feature bf16 rows gain ~1.5x (measured);
    the reduction runs in f32 and the single scale multiplies back after the
    combine (linear, exact). Quantization noise is ~2-3 significant digits
    per element, the same class as the fp8 halo wire."""
    scale = None
    if gather_dtype == "fp8":
        # NOTE: fp8 rows take the jnp f32 reduce — the Pallas bucket kernel
        # is bypassed for them (reduce_tile) until f8 loads are validated
        # in Mosaic on hardware
        from bnsgcn_tpu.utils.quant import f8_quant
        hq, scale = f8_quant(h)
        hp = jnp.concatenate([hq, jnp.zeros((1, h.shape[1]), hq.dtype)], 0)
    elif gather_dtype == "int8":
        # native 1-byte wire: int32 bucket sums stay exact; one per-call
        # scale multiplies back after the combine (linear, exact)
        from bnsgcn_tpu.utils.quant import i8_quant
        hq, scale = i8_quant(h)
        hp = jnp.concatenate([hq, jnp.zeros((1, h.shape[1]), hq.dtype)], 0)
    else:
        hp = jnp.concatenate([h, jnp.zeros((1, h.shape[1]), h.dtype)], 0)
    outs = []
    for k, w in enumerate(spec.widths):
        outs.append(_bucket_sum(hp, idx_list[k], w, use_pallas=use_pallas,
                                accum=accum))
    out = ell_combine(spec, outs, perm, chunk_pos, chunk_seg)
    if scale is not None:
        out = (out.astype(jnp.float32) * scale).astype(h.dtype)
    return out


def make_ell_spmm(fwd_spec: EllSpec, bwd_spec: EllSpec, n_buckets_fwd: int,
                  n_buckets_bwd: int, use_pallas: bool = False,
                  gather_dtype: str = "native", accum: str = "auto"):
    """Returns spmm(arrays, h_ext) -> [n_dst, H] with a custom VJP that runs
    the transposed layout (also scatter-free) on the backward pass. The
    backward quantizes the cotangent with its OWN fp8 scale when
    gather_dtype='fp8' (gradient magnitudes differ from activations)."""

    @jax.custom_vjp
    def spmm(arrays, h_ext):
        idx = [arrays[f"fwd_idx_{k}"] for k in range(n_buckets_fwd)]
        return _ell_apply(fwd_spec, idx, arrays["fwd_perm"], h_ext, use_pallas,
                          arrays.get("fwd_chunk_pos"), arrays.get("fwd_chunk_seg"),
                          gather_dtype=gather_dtype, accum=accum)

    def fwd(arrays, h_ext):
        return spmm(arrays, h_ext), (arrays,)

    def bwd(res, g):
        (arrays,) = res
        idx = [arrays[f"bwd_idx_{k}"] for k in range(n_buckets_bwd)]
        d_h = _ell_apply(bwd_spec, idx, arrays["bwd_perm"], g, use_pallas,
                         arrays.get("bwd_chunk_pos"), arrays.get("bwd_chunk_seg"),
                         gather_dtype=gather_dtype, accum=accum)
        return None, d_h

    spmm.defvjp(fwd, bwd)
    return spmm


def _pow2_bucket(deg: np.ndarray) -> np.ndarray:
    """Ladder bucket index of each positive degree for widths (4, 8, 16, ...):
    deg in (0,4] -> 0, (4,8] -> 1, (2^j, 2^(j+1)] -> j-1 (matches
    ops/ell._bucketize against ops/ell._choose_widths ladders exactly)."""
    d = np.maximum(deg, 1)
    return np.maximum(np.ceil(np.log2(d)).astype(np.int64), 2) - 2


class GeoAccum:
    """Accumulates per-part degree statistics into the compute_geometry dict
    without holding any stacked arrays: per-part pow2-bucket counts (below the
    cap), split-row counts and chunk sums (above it), and the global max."""

    def __init__(self, cap):
        self.cap = cap
        self.rows_max = np.zeros(64, dtype=np.int64)
        self.split_max = 0
        self.chunk_max = 0
        self.max_deg = 0

    def add_part(self, deg: np.ndarray):
        deg = deg[deg > 0]
        if deg.size == 0:
            return
        self.max_deg = max(self.max_deg, int(deg.max()))
        if self.cap:
            over = deg > self.cap
            n_split = int(over.sum())
            if n_split:
                self.split_max = max(self.split_max, n_split)
                self.chunk_max = max(self.chunk_max, int(
                    np.ceil(deg[over] / self.cap).sum()))
                deg = deg[~over]
        if deg.size:
            b = np.bincount(_pow2_bucket(deg), minlength=64)
            self.rows_max = np.maximum(self.rows_max, b)

    def state(self) -> "np.ndarray":
        """Fixed-size mergeable stats vector (for cross-host agreement):
        [rows_max[64], split_max, chunk_max, max_deg]."""
        return np.concatenate([self.rows_max,
                               [self.split_max, self.chunk_max, self.max_deg]]
                              ).astype(np.int64)

    def merge_state(self, state: "np.ndarray"):
        """Elementwise-max another accumulator's state() into this one."""
        self.rows_max = np.maximum(self.rows_max, state[:64])
        self.split_max = max(self.split_max, int(state[64]))
        self.chunk_max = max(self.chunk_max, int(state[65]))
        self.max_deg = max(self.max_deg, int(state[66]))

    def finish(self) -> dict:
        if self.max_deg == 0:
            return {"widths": [4], "rows": [0], "split": 0, "chunks": 0,
                    "cap": None}
        fake = np.asarray([self.max_deg])
        widths = _choose_widths(fake, cap=self.cap)
        eff_cap = self.cap if (self.cap and self.max_deg > self.cap) else None
        rows = [int(r) for r in self.rows_max[:len(widths)]]
        pad8 = lambda r: ((r + 7) // 8) * 8 if r else 0
        split = chunks = 0
        if eff_cap:
            split, chunks = pad8(self.split_max), pad8(self.chunk_max)
            rows[-1] += self.chunk_max
        return {"widths": [int(w) for w in widths], "rows": [pad8(r) for r in rows],
                "split": split, "chunks": chunks, "cap": eff_cap}
