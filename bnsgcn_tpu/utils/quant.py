"""Shared narrow-wire quantization codecs (e4m3 + int8).

One implementation of the symmetric (amax -> scale -> cast) rule used by
the halo wire format (parallel/halo.py, per (sender, peer) block scales)
and the quantized SpMM gather modes (ops/ell.py, one scale per call).
Gradients always get their OWN scales at their own call sites — activation
scales under/overflow gradient magnitudes, the standard narrow-format
pitfall.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F8 = jnp.float8_e4m3fn
F8_MAX = 448.0
I8_MAX = 127.0
_AMAX_FLOOR = 1e-30


def _sym_scale(x: jax.Array, qmax: float, axes, keepdims: bool):
    """(x as f32, scale) for symmetric quantization into [-qmax, qmax].
    `axes=None`: one scalar scale for the whole tensor; otherwise per-slice
    over the given axes."""
    xf = x.astype(jnp.float32)
    amax = (jnp.max(jnp.abs(xf)) if axes is None
            else jnp.max(jnp.abs(xf), axis=axes, keepdims=keepdims))
    return xf, jnp.maximum(amax, _AMAX_FLOOR) / qmax


def f8_quant(x: jax.Array, axes=None, keepdims: bool = True):
    """Returns (payload e4m3, scale f32)."""
    xf, scale = _sym_scale(x, F8_MAX, axes, keepdims)
    return (xf / scale).astype(F8), scale


def f8_dequant(payload: jax.Array, scale, dtype):
    return (payload.astype(jnp.float32) * scale).astype(dtype)


def i8_quant(x: jax.Array, axes=None, keepdims: bool = True):
    """Returns (payload int8, scale f32). int8 is the v5e's NATIVE narrow
    format (MXU and VPU convert it in hardware), unlike e4m3 whose decode
    is emulated bit-twiddling — measured on a v5e (2026-07-29), the fp8 SpMM
    gather mode LOST 1.8x to bf16 because the dequant in the gather-reduce
    inner loop cost more than the byte halving saved; int8 keeps the
    1-byte wire without that tax."""
    xf, scale = _sym_scale(x, I8_MAX, axes, keepdims)
    return jnp.clip(jnp.round(xf / scale),
                    -I8_MAX, I8_MAX).astype(jnp.int8), scale
