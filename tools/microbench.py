"""TPU microbenchmark for the ops that bound bnsgcn_tpu's hot path.

Measures XLA gather (rows/s and GB/s vs row width), the ELL access pattern,
narrow-N bf16 matmul (the block-dense SpMM shape), and HBM stream bandwidth.

Methodology (a dispatch carries a fixed host round-trip, and XLA hoists
loop-invariant bodies out of fori_loop):
  * every case runs inside ONE jit with a *dynamic* trip count (single
    compile, no unroll) and a real data dependency between iterations;
  * per-iter time = (t(2K) - t(K)) / K — the slope cancels dispatch latency,
    compile residue, and the final host read.

Usage: python tools/microbench.py [--quick] [--emit-calibration out.json]

--emit-calibration writes the measured rates as a graftperf calibration
table (analysis/perf/calibration.py schema) keyed by the live backend:
gather rows/s per row-byte class, dense_tile_us from the narrow-N matmul
rate, link_GBps from the HBM stream proxy. The emitted table is marked
calibrated:false (machine-local, no ladder records yet) — merge it into
tools/perf_calibration.json once bench runs have populated records and
`python -m bnsgcn_tpu.analysis perf` holds the drift band.
"""

from __future__ import annotations

import argparse
import time

import numpy as np


def total_time(g, iters, *args):
    import jax.numpy as jnp
    t0 = time.perf_counter()
    out = g(jnp.int32(iters), *args)
    _ = float(np.asarray(out).reshape(-1)[0])
    return time.perf_counter() - t0


def slope(fn, *args, K=20):
    import jax
    g = jax.jit(fn)
    _ = total_time(g, 2, *args)                      # compile + warm
    tA = min(total_time(g, K, *args) for _ in range(2))
    tB = min(total_time(g, 2 * K, *args) for _ in range(2))
    return max((tB - tA) / K, 1e-9)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--tiny", action="store_true",
                    help="smoke sizes: validates the measurement pipeline "
                         "and --emit-calibration off-TPU in seconds (the "
                         "emitted rates are shape-correct but meaningless)")
    ap.add_argument("--emit-calibration", type=str, default="",
                    metavar="OUT.json",
                    help="write measured rates as a graftperf calibration "
                         "table (analysis/perf schema, calibrated:false)")
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    print("devices:", jax.devices())

    rng = np.random.default_rng(0)
    N = 8192 if args.tiny else 131072
    M = (100_000 if args.tiny
         else 4_000_000 if args.quick else 8_000_000)
    idx = jnp.asarray(rng.integers(0, N, size=M, dtype=np.int32))

    def gather_dep(iters, h, ix):
        def body(i, carry):
            acc, off = carry
            s = h[(ix + off) % h.shape[0]].sum(axis=0)
            return (acc + s.astype(jnp.float32), off + 1)
        acc, _ = jax.lax.fori_loop(
            0, iters, body, (jnp.zeros((h.shape[1],), jnp.float32), jnp.int32(0)))
        return acc

    cal_gather = {}
    for W in [128, 256, 512]:
        h = jnp.asarray(rng.normal(size=(N, W)), dtype=jnp.bfloat16)
        dt = slope(gather_dep, h, idx, K=8)
        cal_gather[str(W * 2)] = round(M / dt, 1)
        print(f"gather W={W:4d} ({W*2:5d}B/row): {M/dt/1e6:8.1f}M rows/s "
              f"{M*W*2/dt/1e9:7.1f} GB/s", flush=True)

    # ELL pattern: [rows, w] index table, gather + width reduce
    h = jnp.asarray(rng.normal(size=(N, 256)), dtype=jnp.bfloat16)

    def ell_dep(iters, h, ix):
        r, w = ix.shape
        def body(i, carry):
            acc, off = carry
            g2 = h[((ix + off) % h.shape[0]).reshape(-1)].reshape(r, w, 256)
            return (acc + g2.sum(axis=1).sum(axis=0).astype(jnp.float32), off + 1)
        acc, _ = jax.lax.fori_loop(
            0, iters, body, (jnp.zeros((256,), jnp.float32), jnp.int32(0)))
        return acc

    for w in [16, 128]:
        r = M // w
        dt = slope(ell_dep, h, idx[:r * w].reshape(r, w), K=8)
        print(f"ell w={w:4d}: {(r*w)/dt/1e6:8.1f}M rows/s "
              f"{(r*w)*512/dt/1e9:7.1f} GB/s", flush=True)

    # narrow-N bf16 matmul (block-dense SpMM shape): b evolves each iter
    def mm_dep(iters, a, b0):
        K2 = b0.shape[0]
        def body(i, b):
            c = a @ b
            return (c[:K2] * jnp.bfloat16(0.001)).astype(jnp.bfloat16) + b0
        return jax.lax.fori_loop(0, iters, body, b0)

    best_flops = 0.0
    mm_shapes = ([(1024, 1024, 256), (1024, 1024, 512)] if args.tiny else
                 [(16384, 16384, 256), (32768, 8192, 256),
                  (16384, 16384, 512)])
    for B, K2, Nn in mm_shapes:
        a = jnp.asarray(rng.normal(size=(B, K2)), dtype=jnp.bfloat16)
        b = jnp.asarray(rng.normal(size=(K2, Nn)), dtype=jnp.bfloat16)
        dt = slope(mm_dep, a, b, K=20)
        if Nn == 256:
            best_flops = max(best_flops, 2 * B * K2 * Nn / dt)
        print(f"matmul [{B},{K2}]@[{K2},{Nn}]: {2*B*K2*Nn/dt/1e12:6.1f} TFLOP/s "
              f"({dt*1e3:.3f} ms/iter)", flush=True)

    x = jnp.asarray(rng.normal(
        size=((4 if args.tiny else 64) * 1024 * 1024,)), dtype=jnp.bfloat16)

    def stream_dep(iters, x):
        def body(i, x):
            return x * jnp.bfloat16(1.0000001)
        return jax.lax.fori_loop(0, iters, body, x)

    dt = slope(stream_dep, x, K=20)
    stream_gbps = 2 * x.size * 2 / dt / 1e9
    print(f"stream {x.size * 2 // (1024 * 1024)}MB r+w: "
          f"{stream_gbps:7.1f} GB/s", flush=True)

    if args.emit_calibration:
        from bnsgcn_tpu.analysis.perf import calibration as pcal
        backend = jax.default_backend()
        if backend == "tpu":
            kind = jax.devices()[0].device_kind.lower().replace(" ", "-")
            backend = kind if kind.startswith("tpu") else f"tpu-{kind}"
        # us per 512x512xH=256 dense tile from the best narrow-N matmul
        # rate (the block-dense SpMM's exact inner shape)
        tile_us = 2 * 512 * 512 * 256 / max(best_flops, 1.0) * 1e6
        table = {
            "device_kind": jax.devices()[0].device_kind,
            "gather_rows_per_s": cal_gather,
            "gather_materialize_factor": 1.0,
            "dense_tile_us": {"512": round(tile_us, 3)},
            "dense_xla_factor": 1.0,
            # a 1-chip microbench cannot time the interconnect; HBM
            # stream / 16 approximates the v5e HBM:ICI ratio — replace
            # with a measured all-to-all once a pod window is available
            "link_GBps": round(max(stream_gbps / 16.0, 0.1), 2),
            "fixed_step_s": 0.0,
            "calib_scale": 1.0,
            # machine-local raw rates, no ladder records behind them:
            # gate 4 will not gate drift on this table until a human
            # merges it into tools/perf_calibration.json with records
            # and flips calibrated on
            "calibrated": False,
        }
        calib = {pcal.SCHEMA_KEY: pcal.SCHEMA_VERSION,
                 "backends": {backend: table}, "records": []}
        probs = pcal.validate_calibration(calib)
        if probs:
            raise SystemExit("calibration self-check failed: "
                             + "; ".join(probs))
        pcal.save_calibration(calib, args.emit_calibration)
        print(f"calibration table for backend {backend!r} -> "
              f"{args.emit_calibration}", flush=True)


if __name__ == "__main__":
    main()
