"""Pallas grouped-matmul kernel for the hybrid SpMM's dense tiles.

The XLA formulation (ops/block_spmm._dense_apply) materializes the slab
gather [B, TC, H] and the per-tile partial products [B, TR, H] f32 in HBM
before the segment-sum. This kernel fuses all three: a standard block
pipeline (no manual DMA) over grid=(B,) where

  * the adjacency tile [TR, TC] int8 streams in per step,
  * the X slab block index comes from the scalar-prefetched colb table
    (PrefetchScalarGridSpec — the megablocks/gmm pattern),
  * the output block index comes from rowb; tiles are rowb-sorted, so
    revisited output blocks stay resident and accumulate in VMEM, zeroed on
    first visit.

The backward reads the SAME stack: a third scalar-prefetched table, the
order, picks which stored tile step b reads, and the tile is contracted
over its rows (A^T x), so one int8 stack serves both directions.

Per pass this reads tiles once + one slab per tile at pipeline DMA rates and
writes each output row-block once — no [B, TR, H] partials, no segment-sum.

Correctness is pinned against the XLA path in tests (interpret mode off-TPU).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# the pallas_call's name: what the Mosaic custom call carries in the
# compiled step's HLO and what a device trace lists the kernel under
KERNEL_NAME = "bns_tile_matmul"


def _first_visit(rowb_ref):
    b = pl.program_id(0)
    return jnp.logical_or(b == 0,
                          rowb_ref[b] != rowb_ref[jnp.maximum(b, 1) - 1])


def _kernel(rowb_ref, colb_ref, a_ref, x_ref, o_ref):
    @pl.when(_first_visit(rowb_ref))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    # int8 slabs: int8 multiplicity tiles x int8 activations -> int32 on
    # the MXU (~2x the bf16 rate, exact integer accumulation across tiles;
    # the caller's one per-call scale multiplies back outside). Float
    # slabs: tiles convert to the slab dtype, f32 accumulation.
    a = a_ref[0].astype(x_ref.dtype)
    o_ref[...] += jax.lax.dot_general(
        a, x_ref[0], (((1,), (0,)), ((), ())),
        preferred_element_type=o_ref.dtype)[None]


def _kernel_t(rowb_ref, colb_ref, order_ref, a_ref, x_ref, o_ref, acc_ref):
    """The backward: a_ref holds forward tile order[b] [TR, TC]. The output
    block [TC, H] is held feature-major in acc_ref [H, TC], which takes
    x^T A, and is written back transposed on the block's last visit. On a
    v5e this costs 1.178 / 0.708 us a 512x512 tile at H = 256 / 41 against
    1.160 / 0.850 for A^T x into the output block (the forward over a
    transposed stack: 1.044 / 0.758): the faster over a step's backward
    widths."""
    b = pl.program_id(0)
    last_b = pl.num_programs(0) - 1

    @pl.when(_first_visit(rowb_ref))
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[0].astype(x_ref.dtype)
    acc_ref[...] += jax.lax.dot_general(
        x_ref[0], a, (((0,), (0,)), ((), ())),
        preferred_element_type=acc_ref.dtype)

    @pl.when(jnp.logical_or(
        b == last_b, rowb_ref[jnp.minimum(b + 1, last_b)] != rowb_ref[b]))
    def _():
        o_ref[0] = acc_ref[...].T


def pallas_tile_matmul(tiles: jax.Array, rowb: jax.Array, colb: jax.Array,
                       x_slabs: jax.Array, n_row_blocks: int,
                       order: jax.Array | None = None,
                       interpret: bool = False) -> jax.Array:
    """tiles [B, TR, TC] int8, rowb/colb [B] int32 (rowb sorted ascending,
    pads = n_row_blocks), x_slabs [n_cb, TC, H] -> out [n_row_blocks+1, TR, H]
    (f32 for float slabs; RAW int32 accumulator for int8 slabs — the caller
    owns the dequant scale; last block is the pad-tile trash; caller
    slices it off).

    With `order` [B] int32 (the backward over the forward stack) step b
    reads tiles[order[b]] transposed: x_slabs [n_cb, TR, H] -> out
    [n_row_blocks+1, TC, H].

    Row blocks NO tile maps to are never written by the kernel — on hardware
    Pallas out buffers are uninitialized, so the CALLER must mask them
    (dense_apply_pallas does, via the statically-known visited set)."""
    B, TR, TC = tiles.shape
    H = x_slabs.shape[-1]
    out_dtype = jnp.int32 if x_slabs.dtype == jnp.int8 else jnp.float32
    if order is None:
        kernel, scalars, out_rows, scratch = _kernel, (rowb, colb), TR, []
        tile_map = lambda b, rowb, colb: (b, 0, 0)
    else:
        kernel, scalars, out_rows = _kernel_t, (rowb, colb, order), TC
        scratch = [pltpu.VMEM((H, TC), out_dtype)]
        tile_map = lambda b, rowb, colb, order: (order[b], 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, TR, TC), tile_map),
            pl.BlockSpec((1, x_slabs.shape[1], H), lambda b, rowb, colb, *_:
                         (colb[b], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, out_rows, H), lambda b, rowb, colb, *_:
                               (rowb[b], 0, 0)),
        scratch_shapes=scratch,
    )
    # under shard_map's check_vma the out aval must carry the same
    # varying-mesh-axes set as the input
    out_shape = jax.ShapeDtypeStruct((n_row_blocks + 1, out_rows, H),
                                     out_dtype, vma=jax.typeof(x_slabs).vma)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
        name=KERNEL_NAME,
    )(*scalars, tiles, x_slabs)


def dense_apply_pallas(spec, tiles, rowb, colb, perm_src, perm_out, h,
                       dense_dtype: str = "native", order=None,
                       interpret: bool = False):
    """Drop-in for ops/block_spmm._dense_apply running the fused kernel
    (`order`: the backward over the forward stack, as there).

    dense_dtype='int8': slabs quantize to int8 with ONE per-call symmetric
    scale (amax/127) and the kernel runs int8 x int8 -> int32 on the MXU —
    exact integer accumulation across tiles, so only the quantization
    itself loses precision; the scale multiplies back here (linear,
    exact). Coarser than the XLA path's per-slab scales but scale-free
    inside the kernel. Overflow bound: |row sum| <= 127 * 127 * row's
    dense-tile degree — safe below ~1.3e5 (the bench graph's hubs are
    well under; a multiplicity-127 hub at that degree is pathological).

    Unvisited output row-blocks hold uninitialized memory on hardware; they
    are zeroed here with a mask derived from rowb (visited row-blocks), which
    is cheap and fuses into the final permutation gather."""
    from bnsgcn_tpu.ops.block_spmm import build_x_slabs
    H = h.shape[1]
    x_slabs = build_x_slabs(spec, perm_src, h)
    scale = None
    if dense_dtype == "int8":
        scale = jnp.maximum(
            jnp.max(jnp.abs(x_slabs)).astype(jnp.float32) / 127.0, 1e-30)
        x_slabs = jnp.clip(
            jnp.round(x_slabs.astype(jnp.float32) / scale),
            -127, 127).astype(jnp.int8)
    out = pallas_tile_matmul(tiles, rowb, colb, x_slabs, spec.n_row_blocks,
                             order=order, interpret=interpret)
    visited = jnp.zeros((spec.n_row_blocks + 1,), bool).at[rowb].set(True)
    out = jnp.where(visited[:, None, None], out, 0)
    if scale is not None:
        out = out.astype(jnp.float32) * scale
    flat = out[:spec.n_row_blocks].reshape(
        spec.n_row_blocks * spec.row_tile, H).astype(h.dtype)
    return flat[perm_out]
