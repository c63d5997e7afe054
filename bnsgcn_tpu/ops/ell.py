"""Bucketed-ELLPACK sparse aggregation — the TPU-shaped SpMM.

`jax.ops.segment_sum` lowers to an XLA scatter-add, which serializes on TPU
(~120 GB/s effective on a v5e where HBM does ~800). This module reformulates
the same aggregation (reference DGL SpMM, module/layer.py:35-37,88-90) as
dense, scatter-free work:

  * offline (numpy, per part): group destination rows by in-degree into
    the buckets of one width ladder (`LADDER`: 4, 8, 16, then steps of 16);
    within a bucket store src indices as a dense [rows, width] ELL table
    padded with a dummy index;
  * on device: per bucket, `h[idx]` (a batched row gather — fast on TPU) and
    a dense sum over the width axis; results land via one unique-index
    row permutation (a gather, not a scatter);
  * backward uses a second, transposed layout (rows = source nodes, grouped
    by out-degree) through `jax.custom_vjp`, so the gradient is the same
    scatter-free shape: d_h[u] = sum over out-edges of g[dst].

A padded slot gathers a row like any other (v5e, PR 28: an all-padding
bucket runs at the rate of a random one), so the ladder is as fine as the
device code's unrolled 16-column chains allow; rows with degree 0
(structural padding) are skipped entirely.

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
ELL_BLOCK = 16        # columns one unrolled chain of _bucket_sum sums; every
                      # ladder width over it is a multiple, so no bucket
                      # leaves the unroll path


def _ladder(top: int) -> np.ndarray:
    """Bucket widths 4, 8, 16, 32, 48, 64, ... up to the first one >= top:
    steps of ELL_BLOCK, which grow to an eighth of the width past 256 so an
    uncapped ladder (GAT's forward) stays a few dozen widths long while a
    row is still padded by under an eighth."""
    ws = [4, 8, ELL_BLOCK]
    while ws[-1] < top:
        ws.append(ws[-1] + max(ELL_BLOCK, 1 << (ws[-1].bit_length() - 4)))
    return np.asarray(ws, dtype=np.int64)


LADDER = _ladder(2 ** 31)   # every width a bucket can have (202 of them)


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
    widths: tuple[int, ...]            # bucket ELL widths, a prefix of LADDER
    rows: tuple[int, ...]              # padded row count per bucket
    n_rows: int                        # output rows (n_dst for fwd, n_src_ext for bwd)
    n_src: int                         # gatherable rows (n_src_ext for fwd, n_dst for bwd)
    n_split: int = 0                   # padded count of split (degree > cap) rows
    n_chunks: int = 0                  # padded count of their chunks (tails too)


def _bucketize(deg: np.ndarray, widths: Sequence[int]) -> np.ndarray:
    """Index of the narrowest bucket that holds each row; deg 0 -> -1
    (skipped). The one place a degree meets the ladder: the builder, the
    offline geometry and the accumulated geometry all bucket through it."""
    b = np.searchsorted(np.asarray(widths), deg, side="left").astype(np.int32)
    b[deg <= 0] = -1
    return b


def _split_rows(deg: np.ndarray, cap: int | None):
    """Rows over the cap and how each is laid down, per row of `deg`: (is
    split, its cap-wide chunks, the length of its tail chunk), 0 where not
    split."""
    mask = (deg > cap) if cap else np.zeros(deg.shape, dtype=bool)
    d = np.where(mask, deg, 0).astype(np.int64)
    return mask, d // (cap or 1), d % (cap or 1)


def _run_ranks(counts: np.ndarray) -> np.ndarray:
    """0..c-1 for each run length c in `counts`, laid end to end."""
    return np.arange(int(counts.sum())) - np.repeat(
        np.cumsum(counts) - counts, counts)


def _pad8(r: int) -> int:
    return (int(r) + 7) // 8 * 8


_STALE = ("the stored ELL geometry does not hold this graph under the width "
          "ladder of this version ({what}): re-partition (or delete "
          "`ell_geometry` from meta.json on a single host)")


def check_geometry(g: dict):
    """A geometry entry (compute_geometry schema, possibly from meta.json)
    must name this version's ladder: one computed under another ladder lays
    split rows down differently and cannot be built from."""
    w = [int(x) for x in g["widths"]]
    if w != LADDER[:len(w)].tolist():
        raise ValueError(_STALE.format(what=f"widths {w}"))


def build_ell_numpy(src: np.ndarray, dst: np.ndarray, n_rows: int, n_src: int,
                    widths: Sequence[int] | None = None,
                    row_pad: Sequence[int] | None = None,
                    cap: int | None = None,
                    split_pad: int = 0, chunk_pad: int = 0):
    """Build one part's ELL tables for `out[r] = sum_{e: dst_e == r} h[src_e]`.

    Padded edges must already point at dst == n_rows (they are dropped).
    Returns (widths, rows_per_bucket, idx_arrays, perm, chunk_pos, chunk_seg,
    row_of).

    Split-row scheme (`cap`): a row of degree d > cap becomes d // cap
    cap-wide pseudo-rows in the cap bucket and, where d % cap > 0, one
    pseudo-row of that length in the ladder bucket that fits it; their
    partial sums are combined by a tiny sorted segment-sum over
    `chunk_pos`/`chunk_seg`. With the 16-step ladder this lays down 1.08
    slots an edge on the benchmark's residual (13,998,063 edges, PR 28) where
    power-of-two widths with the last chunk padded to the cap laid 1.379.
    Table layout: [bucket rows 0..T-1 ; zero row T ; combine results
    T+1..T+split_pad]. `perm[r]` points a normal row at its bucket position,
    a split row at its combine slot, and a degree-0 row at the zero row;
    `chunk_pos` addresses rows 0..T of the same table (pad -> the zero row).
    """
    if cap is not None and (cap < 4 or cap & (cap - 1)):
        raise ValueError(f"split cap must be a power of two >= 4, got {cap}")
    real = dst < n_rows
    src, dst = src[real], dst[real]
    deg = np.bincount(dst, minlength=n_rows)
    split_mask, full, tail = _split_rows(deg, cap)
    if widths is None:
        # ladder from the FULL degree distribution so it reaches cap whenever
        # any row splits (the unsplit rows alone would stop short of cap)
        widths = _choose_widths(int(deg.max(initial=0)), cap=cap)
    widths = tuple(int(w) for w in widths)
    K = len(widths)
    split_rows = np.nonzero(split_mask)[0]
    n_split = len(split_rows)
    if n_split and widths[-1] != cap:
        raise ValueError(f"width ladder {widths} must end at cap={cap} "
                         f"when split rows exist")

    # the table rows in edge order (edges sorted by row, a split row's chunks
    # one after the other, its tail last): owner row, chunk number, length
    per_row = np.where(split_mask, full + (tail > 0), deg > 0)
    owner = np.repeat(np.arange(n_rows), per_row)
    chunk = _run_ranks(per_row)
    pseudo = split_mask[owner]
    n_pseudo = int(pseudo.sum())
    length = np.where(pseudo, np.where(chunk < full[owner], cap or 0,
                                       tail[owner]), deg[owner])
    # one bucketing for whole rows, cap-wide chunks and tails alike; within a
    # bucket, rows keep the edge order
    t_bucket = _bucketize(length, widths)
    used_k = np.bincount(t_bucket, minlength=K)
    t_row = np.empty(len(owner), dtype=np.int64)
    t_row[np.argsort(t_bucket, kind="stable")] = _run_ranks(used_k)

    sp = split_pad or _pad8(n_split)
    cp = chunk_pad or _pad8(n_pseudo)
    rows_per_bucket = (tuple(int(r) for r in row_pad) if row_pad is not None
                       else tuple(int(r) for r in used_k))
    if (len(rows_per_bucket) != K or (used_k > rows_per_bucket).any()
            or n_split > sp or n_pseudo > cp):
        raise ValueError(_STALE.format(
            what=f"rows {list(rows_per_bucket)} / split {sp} / chunks {cp} "
                 f"for {used_k.tolist()} / {n_split} / {n_pseudo}"))
    offset = np.zeros(K + 1, dtype=np.int64)            # table row of bucket k
    np.cumsum(rows_per_bucket, out=offset[1:])
    total = int(offset[-1])                             # table rows T
    w_arr = np.asarray(widths, dtype=np.int64)
    flat_base = np.zeros(K + 1, dtype=np.int64)
    np.cumsum(np.asarray(rows_per_bucket, np.int64) * w_arr, out=flat_base[1:])
    pos = offset[t_bucket] + t_row                      # table row of each

    # the fill, vectorized over edges (matters at 100M edges): edges sorted
    # by row are the table rows' slots laid end to end, so an edge's slot in
    # the flat table is its rank plus its table row's shift
    order = grouped_order(dst, n_rows)
    src_sorted = src[order]
    shift = (flat_base[t_bucket] + t_row * w_arr[t_bucket]
             - (np.cumsum(length) - length))
    flat = np.repeat(shift, length)
    flat += np.arange(len(flat), dtype=np.int64)
    if layout_fastpath():
        # one flat table + one collision-free scatter for ALL buckets —
        # each edge owns a distinct (row, slot), so a single fancy-index
        # write replaces the per-bucket O(E x buckets) full-edge masks
        idx_flat = np.full(int(flat_base[-1]), n_src, dtype=np.int32)
        idx_flat[flat] = src_sorted
        idx_arrays = [idx_flat[flat_base[k]:flat_base[k + 1]]
                      .reshape(rows_per_bucket[k], w)
                      for k, w in enumerate(widths)]
    else:
        e_bucket = np.repeat(t_bucket, length)
        idx_arrays = []
        for k, w in enumerate(widths):
            idx = np.full((rows_per_bucket[k] * w,), n_src, dtype=np.int32)
            sel = e_bucket == k
            idx[flat[sel] - flat_base[k]] = src_sorted[sel]
            idx_arrays.append(idx.reshape(rows_per_bucket[k], w))

    # row_of[table_pos] = the output row this table row computes (split
    # pseudo-rows, tails among them, map to their split row; padding ->
    # n_rows). Consumers that need per-table-row context (GAT attention
    # broadcasts el/z by row) index with this.
    row_of = np.full(total, n_rows, dtype=np.int32)
    row_of[pos] = owner
    perm = np.full(n_rows, total, dtype=np.int32)       # degree 0: zero row
    perm[owner[~pseudo]] = pos[~pseudo]
    perm[split_rows] = total + 1 + np.arange(n_split, dtype=np.int32)
    chunk_pos = np.full(cp, total, dtype=np.int32)      # pad -> the zero row
    chunk_seg = np.full(cp, sp, dtype=np.int32)         # pad -> dropped segment
    chunk_pos[:n_pseudo] = pos[pseudo]
    chunk_seg[:n_pseudo] = np.cumsum(split_mask)[owner[pseudo]] - 1
    return (widths, rows_per_bucket, idx_arrays, perm, chunk_pos, chunk_seg,
            row_of)


def _choose_widths(max_deg: int, cap: int | None = None) -> tuple[int, ...]:
    """The ladder's widths from 4 up to the first that holds
    min(max degree, cap): the cap itself when rows split.

    What the ladder rests on, measured on a v5e (`tools/ell_bucket_probe.py`,
    bf16 rows of 256, unroll path; PR 28): a padded slot costs what an edge
    costs (a 117,728 x 128 bucket takes 65.2 ms with random indices, 65.0 ms
    with half of each row padding, 65.0 ms with nothing but padding), and
    seconds follow slots down to the buckets a 16-step ladder makes: the
    benchmark's residual as 10 buckets (rows 1,576 to 42,824) runs 15.12M
    slots in 60.9 ms, as the 4 occupied buckets of a power-of-two ladder
    19.31M in 82.1 ms (248 against 235 Mslot/s in one program). An earlier
    note here said an edge-mass-quantile ladder had measured slower; no record
    of that run survives, and its widths were no multiples of the block, so
    they left the unroll path for the materialising reduce.
    """
    top = max(min(max_deg, cap) if cap else max_deg, 1)
    return tuple(int(w) for w in
                 LADDER[:int(np.searchsorted(LADDER, top, side="left")) + 1])


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
    alone (data/artifacts.py). The same GeoAccum that streams over parts
    there counts here, so the two cannot drift."""
    geo = {}
    for direction in directions:
        n_rows = n_dst if direction == "fwd" else n_src_ext
        acc = GeoAccum(cap)
        for p in range(src_all.shape[0]):
            _, d = _part_edges(src_all[p], dst_all[p], n_dst, direction)
            acc.add_part(np.bincount(d, minlength=n_rows))
        geo[direction] = acc.finish()
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
        check_geometry(g)
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


# hp[i] for an index vector i [r, 1]: one whole row of hp per index
_ROW_GATHER = jax.lax.GatherDimensionNumbers(
    offset_dims=(1,), collapsed_slice_dims=(0,), start_index_map=(0,))


def _gather_rows(hp, col):
    """The gather jnp's `hp[i]` lowers to, without the compare/add/select
    that wraps negative indices: a layout's indices lie in [0, n_src] by
    construction (row n_src is the zero row `_ell_apply` appends), and
    half the cost of tracing the jnp spelling is that wrap."""
    return jax.lax.gather(
        hp, col, _ROW_GATHER, (1, hp.shape[1]),
        mode=jax.lax.GatherScatterMode.PROMISE_IN_BOUNDS)


@jax.jit
def _unroll_sum(hp, idx):
    """_bucket_sum's unroll path. Jitted so that the step traces a bucket
    shape once and not once a call (a step holds every bucket's chains at
    each aggregation width, forward and backward), and spelled in
    `lax.gather` because jnp's indexing is slow to trace (about 11 ms a
    gather on the v5e's host). XLA inlines the calls: the program is
    the one the inline code gave, each copy under its caller's scope names."""
    (r, w), h_dim, BS = idx.shape, hp.shape[1], ELL_BLOCK
    # int8 rows accumulate in int32 (exact, like the reduce path's
    # int32 sums — the caller's one per-call scale multiplies back
    # after the combine); native rows in f32 chains
    acc_dt = jnp.int32 if hp.dtype == jnp.int8 else jnp.float32
    out_dt = jnp.int32 if hp.dtype == jnp.int8 else hp.dtype

    def chain(cb, n):
        # cb [n, r, 1]: one index column a gather
        a = _gather_rows(hp, jax.lax.index_in_dim(cb, 0, keepdims=False)
                         ).astype(acc_dt)
        for j in range(1, n):
            a = a + _gather_rows(
                hp, jax.lax.index_in_dim(cb, j, keepdims=False)
            ).astype(acc_dt)
        return a

    if w <= BS:
        return chain(idx.T[:, :, None], w).astype(out_dt)
    cols = idx.T.reshape(w // BS, BS, r, 1)
    # derive the init from the input so the carry has the same varying
    # manual axes as the body output under shard_map (same contract as
    # block_spmm._dense_apply's acc0); the empty slice reads no data
    acc0 = jnp.zeros((r, h_dim), acc_dt) \
        + jnp.sum(hp[:0]).astype(acc_dt)
    out, _ = jax.lax.scan(lambda acc, cb: (acc + chain(cb, BS), None),
                          acc0, cols)
    return out.astype(out_dt)


def _bucket_sum(hp, idx, w, chunk_gathers: int = 4_000_000,
                accum: str = "auto"):
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
    (unrolled gathers lower poorly there)."""
    if accum not in ("auto", "unroll", "reduce"):
        raise ValueError(f"unknown accum mode {accum!r}")
    r = idx.shape[0]
    h_dim = hp.shape[1]
    if accum == "auto":
        # unroll beats BOTH the jnp chunked reduce and pallas_bucket_reduce
        # (which only fuses the reduction, not the gather materialization);
        # pass accum='reduce' explicitly to study the materializing paths.
        # int8 rows unroll too (exact int32 chains, v5e-native converts);
        # fp8 stays on reduce — e4m3 decode is emulated on the VPU and
        # measured 1.8x slower than bf16.
        from bnsgcn_tpu.utils.platform import tpu_codepaths
        accum = ("unroll" if hp.dtype != jnp.float8_e4m3fn
                 and tpu_codepaths() else "reduce")
    if accum == "unroll" and hp.dtype == jnp.float8_e4m3fn:
        raise ValueError("accum='unroll' supports native and int8 rows; "
                         "fp8 gathers take accum='reduce'")
    if (accum == "unroll" and r > 0 and w > 1
            and (w <= ELL_BLOCK or w % ELL_BLOCK == 0)):
        return _unroll_sum(hp, idx)
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
    # [bucket rows ; zero row ; combine slots]: one table serves the chunk
    # gather (a split row's chunks sit in the cap bucket, its tail in the
    # bucket that fits it; chunk_pos pads point at the zero row) and, with
    # the combined rows written into its last slots, the permutation
    tail = jnp.zeros((1 + spec.n_split,) + trailing, outs[0].dtype)
    full = jnp.concatenate(list(outs) + [tail], axis=0)
    if spec.n_split:
        comb = jax.ops.segment_sum(full[chunk_pos], chunk_seg,
                                   num_segments=spec.n_split + 1,
                                   indices_are_sorted=True)[:spec.n_split]
        full = jax.lax.dynamic_update_slice_in_dim(
            full, comb, sum(spec.rows) + 1, axis=0)
    return full[perm]


@jax.named_scope(tp.AGG_RESIDUAL)
def _ell_apply(spec: EllSpec, idx_list, perm, h, chunk_pos=None,
               chunk_seg=None, gather_dtype: str = "native",
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
        outs.append(_bucket_sum(hp, idx_list[k], w, accum=accum))
    out = ell_combine(spec, outs, perm, chunk_pos, chunk_seg)
    if scale is not None:
        out = (out.astype(jnp.float32) * scale).astype(h.dtype)
    return out


def make_ell_spmm(fwd_spec: EllSpec, bwd_spec: EllSpec, n_buckets_fwd: int,
                  n_buckets_bwd: int, gather_dtype: str = "native",
                  accum: str = "auto"):
    """Returns spmm(arrays, h_ext) -> [n_dst, H] with a custom VJP that runs
    the transposed layout (also scatter-free) on the backward pass. The
    backward quantizes the cotangent with its OWN fp8 scale when
    gather_dtype='fp8' (gradient magnitudes differ from activations)."""

    @jax.custom_vjp
    def spmm(arrays, h_ext):
        idx = [arrays[f"fwd_idx_{k}"] for k in range(n_buckets_fwd)]
        return _ell_apply(fwd_spec, idx, arrays["fwd_perm"], h_ext,
                          arrays.get("fwd_chunk_pos"), arrays.get("fwd_chunk_seg"),
                          gather_dtype=gather_dtype, accum=accum)

    def fwd(arrays, h_ext):
        return spmm(arrays, h_ext), (arrays,)

    def bwd(res, g):
        (arrays,) = res
        idx = [arrays[f"bwd_idx_{k}"] for k in range(n_buckets_bwd)]
        d_h = _ell_apply(bwd_spec, idx, arrays["bwd_perm"], g,
                         arrays.get("bwd_chunk_pos"), arrays.get("bwd_chunk_seg"),
                         gather_dtype=gather_dtype, accum=accum)
        return None, d_h

    spmm.defvjp(fwd, bwd)
    return spmm


class GeoAccum:
    """Accumulates per-part degree statistics into the compute_geometry dict
    without holding any stacked arrays: per-part counts of the table rows
    each ladder bucket receives (whole rows, the cap-wide chunks of split
    rows, their tails), split-row and chunk counts, and the global max."""

    def __init__(self, cap):
        self.cap = cap
        self.rows_max = np.zeros(len(LADDER), dtype=np.int64)
        self.split_max = 0
        self.chunk_max = 0
        self.max_deg = 0

    def add_part(self, deg: np.ndarray):
        deg = deg[deg > 0]
        if deg.size == 0:
            return
        self.max_deg = max(self.max_deg, int(deg.max()))
        over, full, tail = _split_rows(deg, self.cap)
        tail = tail[tail > 0]
        rows = np.bincount(
            _bucketize(np.concatenate([deg[~over], tail]), LADDER),
            minlength=len(LADDER))
        if over.any():
            rows[np.searchsorted(LADDER, self.cap)] += full.sum()
            self.split_max = max(self.split_max, int(over.sum()))
            self.chunk_max = max(self.chunk_max,
                                 int(full.sum()) + int(tail.size))
        self.rows_max = np.maximum(self.rows_max, rows)

    def state(self) -> "np.ndarray":
        """Fixed-size mergeable stats vector (for cross-host agreement):
        [rows_max[len(LADDER)], split_max, chunk_max, max_deg]."""
        return np.concatenate([self.rows_max,
                               [self.split_max, self.chunk_max, self.max_deg]]
                              ).astype(np.int64)

    def merge_state(self, state: "np.ndarray"):
        """Elementwise-max another accumulator's state() into this one."""
        n = len(LADDER)
        self.rows_max = np.maximum(self.rows_max, state[:n])
        self.split_max = max(self.split_max, int(state[n]))
        self.chunk_max = max(self.chunk_max, int(state[n + 1]))
        self.max_deg = max(self.max_deg, int(state[n + 2]))

    def finish(self) -> dict:
        if self.max_deg == 0:
            return {"widths": [4], "rows": [0], "split": 0, "chunks": 0,
                    "cap": None}
        widths = _choose_widths(self.max_deg, cap=self.cap)
        eff_cap = self.cap if (self.cap and self.max_deg > self.cap) else None
        return {"widths": list(widths),
                "rows": [_pad8(r) for r in self.rows_max[:len(widths)]],
                "split": _pad8(self.split_max), "chunks": _pad8(self.chunk_max),
                "cap": eff_cap}
