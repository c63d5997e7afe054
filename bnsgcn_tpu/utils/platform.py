"""Process-level JAX set-up shared by the entry points: which code paths a
backend gets, and where compiled programs persist.

The platform itself is chosen from outside and nowhere in code:
`JAX_PLATFORMS=cpu` (plus `XLA_FLAGS=--xla_force_host_platform_device_count=8`
for a virtual mesh) runs on the CPU, no variable runs on the accelerator.
"""

from __future__ import annotations

import os

# the checkout root (this file is <root>/bnsgcn_tpu/utils/platform.py)
_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# the one in-checkout compile-cache directory (git-ignored)
COMPILE_CACHE_DIR = os.path.join(_ROOT, ".jax_cache")


def tpu_codepaths() -> bool:
    """True when TPU-only code-path decisions should be taken anyway.

    The ELL accumulation auto-choice keys off this instead of
    ``jax.default_backend() == "tpu"`` directly (ops/ell._bucket_sum picks
    the unrolled chains on TPU, the materializing reduce elsewhere). Under
    BNSGCN_BENCH_PREFLIGHT=1 a CPU run takes the TPU decision, so a CPU
    test can compile and train through the accumulation path the chip
    runs (tests/test_accuracy_anchor.py; the round-4 scan-carry bug cost
    three hardware launches because no CPU test did). Pallas kernel BODIES
    still run as their XLA twins off-TPU: Mosaic doesn't lower elsewhere,
    and the interpreter doesn't compose with shard_map's varying-axes
    checks; their logic is pinned by the interpret-mode unit tests."""
    import jax

    return (jax.default_backend() == "tpu"
            or bool(os.environ.get("BNSGCN_BENCH_PREFLIGHT")))


def place_compile_cache() -> str:
    """Where this process keeps JAX's persistent compilation cache — the one
    rule main.py, bench.py and chip_smoke.py share. Where
    JAX_COMPILATION_CACHE_DIR is set JAX already reads it and nothing is
    touched; otherwise the cache is COMPILE_CACHE_DIR, one fixed directory
    in the checkout. Fixed because the directory is part of the cache key:
    a path that moves between processes (a tempdir, an output dir) never
    hits. Call before the first compile. Returns the directory in effect."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return COMPILE_CACHE_DIR
