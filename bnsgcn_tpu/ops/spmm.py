"""Sparse neighbor aggregation — the TPU replacement for DGL's C++/CUDA SpMM.

The reference's hottest compute is `update_all(copy_u('h'), sum('h'))`
(reference module/layer.py:35-37,88-90): for every edge (u -> v), gather h[u]
and segment-sum into v. Here that is a gather + `segment_sum` in static shape,
optionally chunked over the edge axis with `lax.scan` so the [E, H] gathered
intermediate never exceeds `edge_chunk * H` (HBM bound for 100M-edge graphs).

Padded-edge convention (shared with the partition artifacts): `dst == n_dst`
(one trash row, sliced off) and `src == 0` (value irrelevant). This module is
the pure-XLA reference implementation; the Pallas kernel runs the hybrid
layout's dense tiles on a TPU (ops/block_spmm.dense_path).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bnsgcn_tpu.utils import traceparse as tp


# ----------------------------------------------------------------------------
# interior / frontier row split (offline numpy) — the --overlap split
# foundation shared by every SpMM layout family. A destination row is
# FRONTIER when at least one of its in-edges arrives from a halo slot
# (src >= n_dst in the extended index space) and INTERIOR otherwise; an
# interior row's whole aggregation is independent of the halo exchange, so
# the per-layer collective can run concurrently with it (DistGNN's
# local/remote-aggregate overlap, arXiv:2104.06700).
# ----------------------------------------------------------------------------

def frontier_mask(src: np.ndarray, dst: np.ndarray, n_dst: int) -> np.ndarray:
    """[n_dst] bool: rows with >= 1 in-edge from a halo slot. Computed from
    the FULL static edge list — BNS sampling only zeroes halo values, never
    removes edges, so the split is epoch-invariant."""
    src = np.asarray(src)
    dst = np.asarray(dst)
    m = np.zeros(n_dst, dtype=bool)
    halo = (dst < n_dst) & (src >= n_dst)
    m[dst[halo]] = True
    return m


def _pad8(n: int) -> int:
    return max(8, ((n + 7) // 8) * 8)


def _classify_edges(s: np.ndarray, d: np.ndarray, fm: np.ndarray,
                    n_dst: int):
    """(interior_edge_mask, frontier_edge_mask) for one part's padded COO
    edges under frontier row mask `fm` (trash edges d == n_dst in neither)."""
    fmx = np.append(fm, False)
    real = d < n_dst
    is_f = real & fmx[d]
    return real & ~fmx[d], is_f


def _pack_edge_sets(sets, trash: int):
    """Stack per-part (src, dst) edge lists to [P, E_pad] int32 with the
    trash convention dst == `trash`, src == 0 — the one padding
    implementation every split family shares."""
    P = len(sets)
    e_max = _pad8(max((len(s) for s, _ in sets), default=0))
    sa = np.zeros((P, e_max), dtype=np.int32)
    da = np.full((P, e_max), trash, dtype=np.int32)
    for p, (s, d) in enumerate(sets):
        sa[p, :len(s)] = s
        da[p, :len(d)] = d
    return sa, da


def split_row_partition(src_all: np.ndarray, dst_all: np.ndarray, n_dst: int):
    """The shared interior/frontier row split consumed by every split layout
    family (ops/ell.build_split_layouts, ops/block_spmm
    .build_split_block_layouts) — one implementation so the compact-id,
    padding and merge conventions cannot drift between them.

    Per part, destination rows are remapped to two compact row spaces
    (compact ids ascend with original id; degree-0/padded rows are
    interior). Returns (masks, merge_perm, (src_int, dst_int, n_int_pad),
    (src_fro, dst_fro, n_fro_pad)):

      * masks: per-part frontier bool [n_dst] arrays;
      * merge_perm [P, n_dst] int32: out[r] = concat(int_out [n_int_pad],
        fro_out [n_fro_pad])[merge_perm[r]] — the recombination back to
        original row order;
      * edge arrays [P, E_pad] int32 in the compact row spaces, padded to a
        common length with the trash convention dst == n_X_pad, src == 0.
        Both row spaces are floored at 8 rows so degenerate parts (zero
        interior or zero frontier anywhere) build ordinary all-padded
        tables instead of zero-size special cases.
    """
    P = src_all.shape[0]
    masks = [frontier_mask(src_all[p], dst_all[p], n_dst) for p in range(P)]
    n_int_pad = _pad8(max(int((~m).sum()) for m in masks))
    n_fro_pad = _pad8(max(int(m.sum()) for m in masks))
    merge_perm = np.zeros((P, n_dst), dtype=np.int32)
    e_int, e_fro = [], []
    for p in range(P):
        fm = masks[p]
        int_id = (np.cumsum(~fm) - 1).astype(np.int64)
        fro_id = (np.cumsum(fm) - 1).astype(np.int64)
        merge_perm[p] = np.where(fm, n_int_pad + fro_id, int_id)
        s = np.asarray(src_all[p])
        d = np.asarray(dst_all[p])
        is_i, is_f = _classify_edges(s, d, fm, n_dst)
        e_int.append((s[is_i], int_id[d[is_i]]))
        e_fro.append((s[is_f], fro_id[d[is_f]]))
    si, di = _pack_edge_sets(e_int, n_int_pad)
    sf, df = _pack_edge_sets(e_fro, n_fro_pad)
    return (masks, merge_perm, (si, di, n_int_pad), (sf, df, n_fro_pad))


def split_coo(src_all: np.ndarray, dst_all: np.ndarray, n_dst: int
              ) -> dict[str, np.ndarray]:
    """Row-partition each part's COO edges into the interior set (edges whose
    dst row has no halo in-neighbor — all such edges have src < n_dst) and
    the frontier set (ALL edges of rows with >= 1 halo in-neighbor, local
    sources included). Padded per set to a common length across parts with
    the usual trash convention (dst == n_dst, src == 0).

    Returns {'seg_int_src','seg_int_dst','seg_fro_src','seg_fro_dst'}
    stacked [P, E_pad]. Because the two sets cover disjoint OUTPUT rows, the
    recombination is an exact elementwise add of the two aggregations (dst
    ids stay in the ORIGINAL row space — no compaction, no merge perm)."""
    P = src_all.shape[0]
    ints, fros = [], []
    for p in range(P):
        s = np.asarray(src_all[p])
        d = np.asarray(dst_all[p])
        is_i, is_f = _classify_edges(s, d, frontier_mask(s, d, n_dst), n_dst)
        ints.append((s[is_i], d[is_i]))
        fros.append((s[is_f], d[is_f]))
    out = {}
    for name, sets in (("int", ints), ("fro", fros)):
        sa, da = _pack_edge_sets(sets, n_dst)
        out[f"seg_{name}_src"] = sa
        out[f"seg_{name}_dst"] = da
    return out


@jax.named_scope(tp.AGG_COO)
def gather_scatter_sum(h_src: jax.Array, src: jax.Array, dst: jax.Array,
                       n_dst: int, edge_chunk: int = 0) -> jax.Array:
    """sum_{e:(src_e -> dst_e)} h_src[src_e]  ->  [n_dst, H].

    `dst` may contain the value `n_dst` for padded edges; those land in a trash
    row that is dropped.

    edge_chunk > 0 bounds peak memory: edges are processed in chunks of that
    size via `lax.scan` (E must be divisible by edge_chunk; artifacts pad E
    accordingly).
    """
    n_out = n_dst + 1
    if edge_chunk and src.shape[0] > edge_chunk:
        e = src.shape[0]
        assert e % edge_chunk == 0, f"E={e} not divisible by edge_chunk={edge_chunk}"
        n_chunks = e // edge_chunk
        src_c = src.reshape(n_chunks, edge_chunk)
        dst_c = dst.reshape(n_chunks, edge_chunk)

        def body(acc, sd):
            s, d = sd
            msg = h_src[s]
            acc = acc.at[d].add(msg, mode="drop")
            return acc, None

        # derive init from h_src so it carries the same shard_map varying axes
        # (a plain jnp.zeros is 'unvarying' and trips the scan VMA check)
        init = jnp.zeros((n_out, h_src.shape[1]), dtype=h_src.dtype) + h_src[0] * 0
        out, _ = jax.lax.scan(body, init, (src_c, dst_c))
    else:
        out = jax.ops.segment_sum(h_src[src], dst, num_segments=n_out)
    return out[:n_dst]


def agg_sum(h_src, src, dst, n_dst, edge_chunk: int = 0):
    """Plain copy_u/sum aggregation (GCN/GraphSAGE numerator)."""
    return gather_scatter_sum(h_src, src, dst, n_dst, edge_chunk)


def agg_mean(h_src, src, dst, n_dst, in_deg, edge_chunk: int = 0):
    """Sum aggregation divided by a caller-provided in-degree.

    The reference's GraphSAGE mean uses the *global* in-degree stored as ndata
    before partitioning (reference helper/utils.py:92-93, train.py:380,
    module/layer.py:85-91) — NOT the degree of the sampled subgraph; that is
    what makes BNS unbiased for the mean aggregator.
    """
    s = gather_scatter_sum(h_src, src, dst, n_dst, edge_chunk)
    return s / in_deg[:, None]


def segment_softmax(scores: jax.Array, dst: jax.Array, n_dst: int,
                    mask: jax.Array | None = None) -> jax.Array:
    """Numerically-stable softmax over edges grouped by destination.

    Replaces DGL's C++ edge_softmax used by GATConv (reference
    module/model.py:102). `scores`: [E, heads]; `mask`: [E] bool — masked
    edges (absent sampled halos, padding) get zero weight.
    """
    n_out = n_dst + 1
    neg = jnp.asarray(-1e30, dtype=scores.dtype)
    s = scores if mask is None else jnp.where(mask[:, None], scores, neg)
    smax = jax.ops.segment_max(s, dst, num_segments=n_out)
    smax = jnp.where(jnp.isfinite(smax), smax, 0.0)
    ex = jnp.exp(s - smax[dst])
    if mask is not None:
        ex = jnp.where(mask[:, None], ex, 0.0)
    denom = jax.ops.segment_sum(ex, dst, num_segments=n_out)
    denom = jnp.maximum(denom, jnp.asarray(1e-16, dtype=scores.dtype))
    return ex / denom[dst]
