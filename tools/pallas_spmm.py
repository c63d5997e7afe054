"""Pallas TPU kernel: ELL-bucket sparse gather-sum (experimental).

The DGL-CUDA-SpMM replacement slot from SURVEY §2.4 / §7-step-5: a hand-rolled
kernel for `out[r] = sum_w h[idx[r, w]]` over one ELL bucket
(ops/ell.py layout), with per-row HBM->VMEM DMAs double-buffered against the
accumulation.

Status: STUDY ARTIFACT (round 5) — correct under the Pallas interpreter
(tests/test_pallas_spmm.py, slow tier) but wired into no training path; it
lives in tools/ (not the importable bnsgcn_tpu package) so the default test
tier and the training import graph never pay for it. The unrolled
column-chain accumulation (ops/ell._bucket_sum accum='unroll') beat the
materializing reduce this kernel fuses by 1.9x on the v5e cap bucket and
set the 0.573 s/epoch headline, so the dispatch to `pallas_bucket_reduce`
was retired; the one Pallas kernel on the training path is the fused
dense-tile kernel (ops/pallas_block), which is hardware-validated.
Kept for two findings a later session may build on: (a) the remote
compiler of the July 2026 sessions rejected *any* manual-DMA kernel (even a
minimal fixed-row `make_async_copy` one) - that compiler is gone and the
kernel has not been retried on a directly attached chip; (b) the XLA gather
engine on a v5e sustains ~145M rows/s independent of index locality, so a
DMA-per-row pipeline must coalesce sorted index runs into multi-row
extents to win.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _bucket_kernel(idx_ref, h_hbm, out_ref, *, tile_rows, width):
    """One grid step: accumulate `width` gathered rows for `tile_rows` outputs."""

    def body(scratch, sem):
        n = tile_rows * width
        h_dim = h_hbm.shape[1]

        def get_dma(slot, flat):
            r = flat // width
            w = flat % width
            return pltpu.make_async_copy(
                h_hbm.at[pl.ds(idx_ref[r, w], 1), :],
                scratch.at[slot], sem.at[slot])

        get_dma(0, 0).start()

        def loop_row(r, _):
            # per-row accumulator lives in vector registers; one dynamic row
            # store per output row (TPU Pallas has no dynamic scatter-add)
            def loop_w(w, acc):
                flat = r * width + w
                slot = jax.lax.rem(flat, 2)

                @pl.when(flat + 1 < n)
                def _():
                    get_dma(jax.lax.rem(flat + 1, 2), flat + 1).start()

                get_dma(slot, flat).wait()
                return acc + scratch[slot].astype(jnp.float32)

            acc = jax.lax.fori_loop(0, width, loop_w,
                                    jnp.zeros((1, h_dim), jnp.float32))
            out_ref[pl.ds(r, 1), :] = acc.astype(out_ref.dtype)
            return _

        jax.lax.fori_loop(0, tile_rows, loop_row, None)

    pl.run_scoped(
        body,
        scratch=pltpu.VMEM((2, 1, h_hbm.shape[1]), h_hbm.dtype),
        sem=pltpu.SemaphoreType.DMA((2,)),
    )


@functools.partial(jax.jit, static_argnames=("tile_rows", "interpret"))
def pallas_bucket_sum(hp: jax.Array, idx: jax.Array, tile_rows: int = 8,
                      interpret: bool = False) -> jax.Array:
    """out[r] = sum_w hp[idx[r, w]] for one ELL bucket.

    hp: [N+1, H] (row N is the zero pad row); idx: [R, W] int32 with pad = N.
    R must be a multiple of tile_rows (ops/ell.py pads rows to x8).
    """
    r, w = idx.shape
    assert r % tile_rows == 0, (r, tile_rows)
    kernel = functools.partial(_bucket_kernel, tile_rows=tile_rows, width=w)
    return pl.pallas_call(
        kernel,
        grid=(r // tile_rows,),
        in_specs=[
            pl.BlockSpec((tile_rows, w), lambda i: (i, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pl.ANY),             # h stays in HBM
        ],
        out_specs=pl.BlockSpec((tile_rows, hp.shape[1]), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, hp.shape[1]), hp.dtype),
        interpret=interpret,
    )(idx, hp)


def _reduce_kernel(g_ref, out_ref):
    out_ref[:, :] = jnp.sum(g_ref[:, :, :].astype(jnp.float32),
                            axis=1).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tile_rows", "interpret"))
def pallas_bucket_reduce(gathered: jax.Array, tile_rows: int = 8,
                         interpret: bool = False) -> jax.Array:
    """[R, W, H] -> [R, H] width-axis reduction as a standard-pipeline Pallas
    kernel (compiles on hardware; the gather stays on the XLA gather engine)."""
    r, w, h = gathered.shape
    assert r % tile_rows == 0
    try:
        # under shard_map with check_vma the out aval must carry the same
        # varying-mesh-axes set as the input
        out_shape = jax.ShapeDtypeStruct((r, h), gathered.dtype,
                                         vma=jax.typeof(gathered).vma)
    except (AttributeError, TypeError):
        out_shape = jax.ShapeDtypeStruct((r, h), gathered.dtype)
    return pl.pallas_call(
        _reduce_kernel,
        grid=(r // tile_rows,),
        in_specs=[pl.BlockSpec((tile_rows, w, h), lambda i: (i, 0, 0))],
        out_specs=pl.BlockSpec((tile_rows, h), lambda i: (i, 0)),
        out_shape=out_shape,
        interpret=interpret,
    )(gathered)


def pallas_ell_apply(spec, idx_list, perm, h, interpret: bool = False):
    """Drop-in for ops.ell._ell_apply using the Pallas bucket kernel for
    buckets the kernel supports (W <= 1024, SMEM block bound); jnp fallback
    for the rest."""
    from bnsgcn_tpu.ops.ell import _bucket_sum

    hp = jnp.concatenate([h, jnp.zeros((1, h.shape[1]), h.dtype)], 0)
    outs = []
    for k, w in enumerate(spec.widths):
        idx = idx_list[k]
        if 0 < idx.shape[0] and w <= 1024:
            outs.append(pallas_bucket_sum(hp, idx, interpret=interpret))
        else:
            outs.append(_bucket_sum(hp, idx, w))
    outs.append(jnp.zeros((1, h.shape[1]), h.dtype))
    table = jnp.concatenate(outs, axis=0)
    return table[perm]
