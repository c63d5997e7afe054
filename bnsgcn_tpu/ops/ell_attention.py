"""GAT attention over the ELL layout — dense per-row edge softmax with a
transposed-layout custom VJP.

The segment-softmax GAT path (ops/spmm.segment_softmax + segment sums) runs
three scatter-shaped passes over the edge list. With destination rows in ELL
form (ops/ell.py, built WITHOUT the split cap so every dst row is one table
row), the edge softmax becomes a dense masked softmax over the row width and
the weighted sum a dense einsum — the DGL edge-softmax replacement (SURVEY
§2.4; reference module/model.py:102) in the same scatter-free shape as the
SpMM.

Backward (jax.custom_vjp — the GAT analog of ops/ell.make_ell_spmm's
transposed layout):
  * pass A on the FORWARD layout (rows = dst v): recompute alpha from saved
    per-row softmax stats (max, denom), form q = <g[v], z[u]> per edge, and
    produce d_er plus the per-row sum s_v = sum_u alpha*q~ — all dense;
  * pass B on the TRANSPOSED layout (rows = src u, degree-capped with
    split-row chunks like the SpMM backward): d_z[u] = sum_v alpha~ * g[v]
    and d_el[u] = sum_v alpha*(q~ - s_v)*leaky' — gathers only, partial
    sums combined by ops/ell.ell_combine.
No scatter touches [n_ext, heads, F'] anywhere.

Attention dropout (the reference passes dropout as GATConv attn_drop,
module/model.py:102) is EDGE-DETERMINISTIC: the keep decision is a stateless
integer hash of (src id, dst id, head, key-derived seed), so the forward and
the transposed backward reproduce the identical mask without storing it.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bnsgcn_tpu.ops.ell import (ELL_SPLIT_CAP, EllSpec, build_ell_numpy,
                                check_geometry, compute_geometry,
                                ell_combine)
from bnsgcn_tpu.utils import traceparse as tp


@dataclass(frozen=True)
class GatEllSpec:
    widths: tuple[int, ...]
    rows: tuple[int, ...]
    n_rows: int                        # dst rows (pad_inner)
    n_src: int                         # extended rows
    bwd: EllSpec = None                # transposed (src-major, capped) layout


def gat_geometry(src_all: np.ndarray, dst_all: np.ndarray, n_dst: int,
                 n_src_ext: int) -> dict:
    """Uncapped fwd geometry (whole rows — the softmax can't span split
    chunks); same schema as compute_geometry entries, JSON-serializable."""
    return compute_geometry(src_all, dst_all, n_dst, n_src_ext, cap=None,
                            directions=("fwd",))["fwd"]


def build_gat_layouts(src_all: np.ndarray, dst_all: np.ndarray, n_dst: int,
                      n_src_ext: int, geometry: dict | None = None,
                      geometry_bwd: dict | None = None
                      ) -> tuple[GatEllSpec, dict]:
    """Dst-major uncapped ELL layout (forward) + src-major capped layout
    (backward), with per-table-position row ids for both.

    `geometry`/`geometry_bwd` may come from meta.json ('gat_fwd' and 'bwd'
    entries — multi-host partial parts). Returns (spec, arrays):
    {'gat_idx_k', 'gat_rows', 'gat_perm',
     'gat_bwd_idx_k', 'gat_bwd_rows', 'gat_bwd_perm'
     [, 'gat_bwd_chunk_pos', 'gat_bwd_chunk_seg']}, stacked on parts."""
    P = src_all.shape[0]
    if geometry is None:
        geometry = gat_geometry(src_all, dst_all, n_dst, n_src_ext)
    if geometry_bwd is None:
        geometry_bwd = compute_geometry(src_all, dst_all, n_dst, n_src_ext,
                                        cap=ELL_SPLIT_CAP,
                                        directions=("bwd",))["bwd"]
    check_geometry(geometry)
    check_geometry(geometry_bwd)
    widths = tuple(geometry["widths"])
    rows_max = tuple(geometry["rows"])

    arrays = {}
    # ---- forward layout (rows = dst, uncapped) ----
    idx_stacked = [[] for _ in widths]
    perms, rows_ids = [], []
    for p in range(P):
        _, _, idx, perm, _, _, row_of = build_ell_numpy(
            src_all[p], dst_all[p], n_dst, n_src_ext,
            widths=widths, row_pad=rows_max, cap=None)
        for k in range(len(widths)):
            idx_stacked[k].append(idx[k])
        perms.append(perm)
        rows_ids.append(row_of)
    arrays["gat_perm"] = np.stack(perms)
    arrays["gat_rows"] = np.stack(rows_ids)
    for k in range(len(widths)):
        arrays[f"gat_idx_{k}"] = np.stack(idx_stacked[k])

    # ---- transposed layout (rows = src_ext, capped like the SpMM bwd) ----
    bw = tuple(geometry_bwd["widths"])
    br = tuple(geometry_bwd["rows"])
    b_cap = geometry_bwd["cap"]
    b_split, b_chunks = geometry_bwd["split"], geometry_bwd["chunks"]
    bidx_stacked = [[] for _ in bw]
    bperms, brows, bcp, bcs = [], [], [], []
    for p in range(P):
        real = dst_all[p] < n_dst
        _, _, idx, perm, cp, cs, row_of = build_ell_numpy(
            dst_all[p][real], src_all[p][real], n_src_ext, n_dst,
            widths=bw, row_pad=br, cap=b_cap,
            split_pad=b_split, chunk_pad=b_chunks)
        for k in range(len(bw)):
            bidx_stacked[k].append(idx[k])
        bperms.append(perm)
        brows.append(row_of)
        bcp.append(cp)
        bcs.append(cs)
    arrays["gat_bwd_perm"] = np.stack(bperms)
    arrays["gat_bwd_rows"] = np.stack(brows)
    if b_split:
        arrays["gat_bwd_chunk_pos"] = np.stack(bcp)
        arrays["gat_bwd_chunk_seg"] = np.stack(bcs)
    for k in range(len(bw)):
        arrays[f"gat_bwd_idx_{k}"] = np.stack(bidx_stacked[k])

    bwd_spec = EllSpec(widths=bw, rows=br, n_rows=n_src_ext, n_src=n_dst,
                       n_split=b_split, n_chunks=b_chunks)
    spec = GatEllSpec(widths=widths, rows=rows_max, n_rows=n_dst,
                      n_src=n_src_ext, bwd=bwd_spec)
    return spec, arrays


# ----------------------------------------------------------------------------
# edge-deterministic dropout: keep(u, v, h) from an integer hash — identical
# on the forward (dst-major) and transposed (src-major) layouts.
# ----------------------------------------------------------------------------

def _hash_keep(u32, v32, h_idx, seed0, seed1, keep_prob):
    """u32/v32: broadcast-compatible uint32 arrays of src/dst ids; h_idx [H].
    Returns bool [..., H]: murmur3-finalized hash of (u, v, h, seeds)."""
    x = (u32 * np.uint32(2654435761)) ^ (v32 * np.uint32(2246822519)) ^ seed0
    x = x[..., None] ^ (h_idx * np.uint32(3266489917)) ^ seed1
    x = x ^ (x >> 16)
    x = x * np.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * np.uint32(0xC2B2AE35)
    x = x ^ (x >> 16)
    unit = x.astype(jnp.float32) * np.float32(1.0 / 4294967296.0)
    return unit < keep_prob


def _row_chunked(tile, r, rows_per_chunk, pads, *arrs):
    """scan `tile` over row chunks of the leading axis; `pads` gives the
    pad value per array. Outputs (array or tuple) are row-concatenated."""
    if r <= rows_per_chunk:
        return tile(*arrs)
    n_chunks = -(-r // rows_per_chunk)
    pad = n_chunks * rows_per_chunk - r
    padded = []
    for a, pv in zip(arrs, pads):
        cfg = [(0, pad)] + [(0, 0)] * (a.ndim - 1)
        padded.append(jnp.pad(a, cfg, constant_values=pv)
                      .reshape((n_chunks, rows_per_chunk) + a.shape[1:]))

    def body(_, chunk):
        return None, tile(*chunk)

    _, out = jax.lax.scan(body, None, tuple(padded))
    if isinstance(out, tuple):
        return tuple(o.reshape((n_chunks * rows_per_chunk,) + o.shape[2:])[:r]
                     for o in out)
    return out.reshape((n_chunks * rows_per_chunk,) + out.shape[2:])[:r]


def _leaky(x, slope):
    return jnp.where(x > 0, x, x * slope)


def _pad_rows(x, value=0.0):
    pad = jnp.full((1,) + x.shape[1:], value, x.dtype)
    return jnp.concatenate([x, pad], axis=0)


def _seeds_of(attn_rng, training, drop):
    if attn_rng is None or not training or drop <= 0.0:
        return jnp.zeros((2,), jnp.uint32)
    return jax.random.key_data(attn_rng).astype(jnp.uint32).reshape(-1)[:2]


def _head_idx(heads, head_off):
    """Global head ids of this call's head block. `head_off` (feat-sharded
    GAT, parallel/feat.py) offsets the dropout hash so shard f's masks are
    exactly heads [off, off+heads) of the feat=1 masks; None = heads 0..H."""
    hidx = jnp.arange(heads, dtype=jnp.uint32)
    if head_off is not None:
        hidx = hidx + jnp.asarray(head_off).astype(jnp.uint32)
    return hidx


def _fwd_buckets(spec, arrays, zp, elp, erp, pres, drop, training,
                 slope, seeds, head_off=None, chunk_gathers=2_000_000):
    """Forward over the dst-major layout. Returns per-bucket weighted sums
    and per-bucket softmax stats (m', denom), all in table-row order."""
    heads = zp.shape[1]
    hidx = _head_idx(heads, head_off)
    outs, ms, ds = [], [], []
    offset = 0
    for k, w in enumerate(spec.widths):
        idx = arrays[f"gat_idx_{k}"]
        r = idx.shape[0]
        rows = jax.lax.dynamic_slice_in_dim(arrays["gat_rows"], offset, r)
        offset += r

        def tile(idx_t, rows_t):
            mask = (idx_t != spec.n_src) & (rows_t != spec.n_rows)[:, None]
            if pres is not None:
                mask = mask & pres[idx_t]
            e = _leaky((elp[idx_t] + erp[rows_t][:, None, :])
                       .astype(jnp.float32), slope)
            e = jnp.where(mask[:, :, None], e, -1e30)
            m = jnp.maximum(jnp.max(e, axis=1), -1e29)          # [r, H]
            ex = jnp.where(mask[:, :, None], jnp.exp(e - m[:, None, :]), 0.0)
            denom = jnp.maximum(ex.sum(axis=1), 1e-16)          # [r, H]
            alpha = (ex / denom[:, None, :])
            if training and drop > 0.0:
                keep = _hash_keep(idx_t.astype(jnp.uint32),
                                  rows_t.astype(jnp.uint32)[:, None],
                                  hidx, seeds[0], seeds[1], 1.0 - drop)
                alpha = jnp.where(keep, alpha / (1.0 - drop), 0.0)
            return (jnp.einsum("rwh,rwhf->rhf", alpha.astype(zp.dtype),
                               zp[idx_t]), m, denom)

        rpc = max(1, chunk_gathers // max(w, 1))
        o, m, d = _row_chunked(tile, r, rpc, (spec.n_src, spec.n_rows),
                               idx, rows)
        outs.append(o)
        ms.append(m)
        ds.append(d)
    return outs, ms, ds


@jax.named_scope(tp.ATTENTION)
def _gat_fwd_impl(spec, arrays, z, el, er, presence, attn_rng, head_off,
                  attn_dropout, training, negative_slope):
    heads, fdim = z.shape[1], z.shape[2]
    zp = _pad_rows(z)
    elp = _pad_rows(el)
    erp = _pad_rows(er)
    pres = _pad_rows(presence, False) if presence is not None else None
    seeds = _seeds_of(attn_rng, training, attn_dropout)
    outs, ms, ds = _fwd_buckets(spec, arrays, zp, elp, erp, pres,
                                attn_dropout, training, negative_slope, seeds,
                                head_off=head_off)
    zero = jnp.zeros((1, heads, fdim), z.dtype)
    out = jnp.concatenate(outs + [zero], axis=0)[arrays["gat_perm"]]
    # per-dst stats for the transposed backward (degree-0 rows hit the
    # appended neutral row: m=-1e29, denom=1)
    m_tab = jnp.concatenate(ms + [jnp.full((1, heads), -1e29, jnp.float32)], 0)
    d_tab = jnp.concatenate(ds + [jnp.ones((1, heads), jnp.float32)], 0)
    return out, (m_tab[arrays["gat_perm"]], d_tab[arrays["gat_perm"]], seeds)


@partial(jax.custom_vjp, nondiff_argnums=(0, 8, 9, 10))
def gat_ell_attention(spec: GatEllSpec, arrays: dict, z: jax.Array,
                      el: jax.Array, er: jax.Array,
                      presence, attn_rng, head_off,
                      attn_dropout: float, training: bool,
                      negative_slope: float = 0.2) -> jax.Array:
    """out[v] = sum_u softmax_u(leaky(el[u] + er[v])) * z[u] over v's ELL row.

    z: [n_ext, heads, F'], el: [n_ext, heads], er: [n_dst, heads].
    Returns [n_dst, heads, F']. Padded slots and absent (unsampled) halos are
    masked out of the softmax (the reference's sampled-subgraph semantics,
    train.py:256-281). `head_off` (None = 0) shifts the dropout hash's head
    ids for feat-sharded head blocks (parallel/feat.py).
    """
    out, _ = _gat_fwd_impl(spec, arrays, z, el, er, presence, attn_rng,
                           head_off, attn_dropout, training, negative_slope)
    return out


def _gat_fwd_rule(spec, arrays, z, el, er, presence, attn_rng, head_off,
                  attn_dropout, training, negative_slope):
    out, (m_v, denom_v, seeds) = _gat_fwd_impl(
        spec, arrays, z, el, er, presence, attn_rng, head_off, attn_dropout,
        training, negative_slope)
    return out, (arrays, z, el, er, presence, head_off, m_v, denom_v, seeds)


@jax.named_scope(tp.ATTENTION)
def _gat_bwd_rule(spec, attn_dropout, training, negative_slope, res, g):
    arrays, z, el, er, presence, head_off, m_v, denom_v, seeds = res
    heads = z.shape[1]
    hidx = _head_idx(heads, head_off)
    drop = attn_dropout if training else 0.0
    keep_p = 1.0 - drop

    zp = _pad_rows(z)
    elp = _pad_rows(el)
    erp = _pad_rows(er)
    pres = _pad_rows(presence, False) if presence is not None else None
    gp = _pad_rows(g.astype(jnp.float32))
    m_p = _pad_rows(m_v, -1e29)
    den_p = _pad_rows(denom_v, 1.0)

    # ---- pass A: forward layout — d_er and s_v = sum_u alpha * q~ ----
    der_list, s_list = [], []
    offset = 0
    for k, w in enumerate(spec.widths):
        idx = arrays[f"gat_idx_{k}"]
        r = idx.shape[0]
        rows = jax.lax.dynamic_slice_in_dim(arrays["gat_rows"], offset, r)
        offset += r

        def tileA(idx_t, rows_t):
            mask = (idx_t != spec.n_src) & (rows_t != spec.n_rows)[:, None]
            if pres is not None:
                mask = mask & pres[idx_t]
            e_pre = (elp[idx_t] + erp[rows_t][:, None, :]).astype(jnp.float32)
            e = _leaky(e_pre, negative_slope)
            alpha = jnp.where(
                mask[:, :, None],
                jnp.exp(e - m_p[rows_t][:, None, :]) / den_p[rows_t][:, None, :],
                0.0)                                            # [r, W, H]
            q = jnp.einsum("rwhf,rhf->rwh", zp[idx_t].astype(jnp.float32),
                           gp[rows_t])
            if drop > 0.0:
                keep = _hash_keep(idx_t.astype(jnp.uint32),
                                  rows_t.astype(jnp.uint32)[:, None],
                                  hidx, seeds[0], seeds[1], keep_p)
                q = jnp.where(keep, q / keep_p, 0.0)
            s_row = jnp.einsum("rwh,rwh->rh", alpha, q)          # [r, H]
            d_e = alpha * (q - s_row[:, None, :])
            d_pre = d_e * jnp.where(e_pre > 0, 1.0, negative_slope)
            return d_pre.sum(axis=1), s_row

        rpc = max(1, 2_000_000 // max(w, 1))
        der_k, s_k = _row_chunked(tileA, r, rpc, (spec.n_src, spec.n_rows),
                                  idx, rows)
        der_list.append(der_k)
        s_list.append(s_k)
    zeroH = jnp.zeros((1, heads), jnp.float32)
    d_er = jnp.concatenate(der_list + [zeroH], 0)[arrays["gat_perm"]]
    s_v = jnp.concatenate(s_list + [zeroH], 0)[arrays["gat_perm"]]
    s_p = _pad_rows(s_v)

    # ---- pass B: transposed layout — d_z and d_el (gathers only) ----
    bspec = spec.bwd
    dz_outs, del_outs = [], []
    offset = 0
    for k, w in enumerate(bspec.widths):
        idx = arrays[f"gat_bwd_idx_{k}"]                         # [R, W] dst ids
        r = idx.shape[0]
        rows = jax.lax.dynamic_slice_in_dim(arrays["gat_bwd_rows"], offset, r)
        offset += r

        def tileB(idx_t, rows_t):
            # rows_t: src ext ids (split pseudo-rows share their source id)
            mask = idx_t != bspec.n_src                          # pad dst slot
            if pres is not None:
                mask = mask & pres[rows_t][:, None]
            e_pre = (elp[rows_t][:, None, :] + erp[idx_t]).astype(jnp.float32)
            e = _leaky(e_pre, negative_slope)
            alpha = jnp.where(mask[:, :, None],
                              jnp.exp(e - m_p[idx_t]) / den_p[idx_t], 0.0)
            g_t = gp[idx_t]                                      # [r, W, H, F]
            q = jnp.einsum("rwhf,rhf->rwh", g_t,
                           zp[rows_t].astype(jnp.float32))
            alpha_d = alpha
            if drop > 0.0:
                # hash args must match pass A: u = src id, v = dst id
                keep = _hash_keep(rows_t.astype(jnp.uint32)[:, None],
                                  idx_t.astype(jnp.uint32),
                                  hidx, seeds[0], seeds[1], keep_p)
                alpha_d = jnp.where(keep, alpha / keep_p, 0.0)
                q = jnp.where(keep, q / keep_p, 0.0)
            d_z_row = jnp.einsum("rwh,rwhf->rhf", alpha_d, g_t)
            d_e = alpha * (q - s_p[idx_t])
            d_pre = d_e * jnp.where(e_pre > 0, 1.0, negative_slope)
            return d_z_row, d_pre.sum(axis=1)

        rpc = max(1, 2_000_000 // max(w, 1))
        dz_k, del_k = _row_chunked(tileB, r, rpc,
                                   (bspec.n_src, bspec.n_rows), idx, rows)
        dz_outs.append(dz_k)
        del_outs.append(del_k)

    cp = arrays.get("gat_bwd_chunk_pos")
    cs = arrays.get("gat_bwd_chunk_seg")
    d_z = ell_combine(bspec, dz_outs, arrays["gat_bwd_perm"], cp, cs)
    d_el = ell_combine(bspec, del_outs, arrays["gat_bwd_perm"], cp, cs)
    return (None, d_z.astype(z.dtype), d_el.astype(el.dtype),
            d_er.astype(er.dtype), None, None, None)


gat_ell_attention.defvjp(_gat_fwd_rule, _gat_bwd_rule)
