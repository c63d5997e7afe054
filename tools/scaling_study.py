"""Multi-chip scaling evidence within single-chip env limits.

Physical multi-chip hardware is not reachable from this environment, so the
scaling story is built from the two things that ARE measurable here:

  1. real partitions of the bench graph at P in {2,4,8,16}: per-chip edge
     share and real (skewed) boundary sizes -> exact halo wire bytes per
     strategy/dtype at the reference's rate 0.1;
  2. measured single-chip constants (tools/microbench.py on the v5e:
     ELL gather throughput; bench.py epoch time), combined with an analytic
     ICI model: T(P) = T_spmm(E/P) + 2 * L_ex * wire_bytes(P) / BW_ici.

BW_ici defaults to 90 GB/s usable per-chip all-to-all bandwidth (v5e ICI,
conservative vs the 1.6 Tbps aggregate spec); it is an ASSUMPTION to be
replaced by a measurement when a pod is available — the table records the
inputs so the model is auditable.

The P>1 *correctness* of the very code being modeled is exercised on the
virtual CPU mesh by tests/ (exactness at rate 1.0, multi-host runs) and by
the driver's dryrun_multichip.

Usage: python tools/scaling_study.py [--scale 0.5] [--rate 0.1] [--seeds 1]
"""

from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=0.5,
                    help="fraction of Reddit nodes (0.5 == the bench graph)")
    ap.add_argument("--rate", type=float, default=0.1)
    ap.add_argument("--hidden", type=int, default=256)
    ap.add_argument("--layers", type=int, default=4,
                    help="n_layers; graph-layer exchanges = layers-1 with pp")
    ap.add_argument("--seeds", type=int, default=1)
    ap.add_argument("--bw-ici", type=float, default=90e9,
                    help="assumed usable per-chip all-to-all B/s")
    ap.add_argument("--ell-rate", type=float, default=230e6,
                    help="measured ELL gather rows/s per chip (microbench)")
    ap.add_argument("--ell-waste", type=float, default=1.14,
                    help="measured ELL padding factor (gathers per edge)")
    ap.add_argument("--spmm-passes", type=int, default=6,
                    help="SpMM passes per epoch (3 graph layers x fwd+bwd)")
    ap.add_argument("--graph", choices=["dcsbm", "uniform"], default="dcsbm")
    ap.add_argument("--cache-dir", type=str, default="./bench_cache")
    args = ap.parse_args()

    sys.path.insert(0, os.getcwd())
    from bench import _cached_graph
    from bnsgcn_tpu.parallel.halo import make_halo_spec, wire_bytes

    n_nodes = max(int(232_965 * args.scale), 2000)
    log = lambda *a: print(*a, file=sys.stderr, flush=True)
    g = _cached_graph(n_nodes, 492, args.cache_dir, log, kind=args.graph)
    n_ex = args.layers - 2  # hidden-width exchanges per fwd pass (pp drops L0)

    print("| P | edges/chip | max boundary/pair | wire MB/epoch/chip "
          "(padded bf16) | (shift bf16) | (shift fp8) | T_spmm (s) | "
          "T_comm (s) | T_epoch model (s) | speedup |")
    print("|---|---|---|---|---|---|---|---|---|---|")
    base_t = None
    for P in (1, 2, 4, 8, 16):
        t0 = time.time()
        if P == 1:
            pid = np.zeros(g.n_nodes, dtype=np.int32)
        else:
            from bnsgcn_tpu.native import native_partition
            pid = native_partition(g, P, obj="vol", seed=0,
                                   refine_passes=4, n_seeds=args.seeds)
        # boundary sizes n_b[p, j]
        src_o, dst_o = pid[g.src], pid[g.dst]
        cross = src_o != dst_o
        key = g.src[cross].astype(np.int64) * P + dst_o[cross]
        ukey = np.unique(key)
        bp = pid[(ukey // P)]
        bj = ukey % P
        n_b = np.zeros((P, P), dtype=np.int64)
        np.add.at(n_b, (bp, bj.astype(np.int64)), 1)
        e_per = np.bincount(dst_o, minlength=P).max()
        pad_b = max(int(n_b.max()), 8)

        variants = {}
        for strat, wire in [("padded", "bf16"), ("shift", "bf16"),
                            ("shift", "fp8")]:
            spec, _ = make_halo_spec(n_b, 0, pad_b, args.rate,
                                     strategy=strat, wire=wire)
            # bytes per epoch per chip: fwd+bwd per hidden exchange.
            # wire_bytes' padded accounting counts the full P-block buffer
            # (hw-probe parity); this table models CROSS-CHIP ICI payload,
            # so drop the chip-local self block
            wb = wire_bytes(spec, args.hidden, 2)
            if strat == "padded":
                wb = wb * (P - 1) // P
            variants[(strat, wire)] = 2 * n_ex * wb

        t_spmm = (e_per * args.ell_waste * args.spmm_passes) / args.ell_rate
        t_comm = variants[("shift", "fp8")] / args.bw_ici
        t_epoch = t_spmm + t_comm
        if base_t is None:
            base_t = t_epoch
        print(f"| {P} | {e_per/1e6:.1f}M | {n_b.max()} "
              f"| {variants[('padded','bf16')]/1e6:.1f} "
              f"| {variants[('shift','bf16')]/1e6:.1f} "
              f"| {variants[('shift','fp8')]/1e6:.1f} "
              f"| {t_spmm:.3f} | {t_comm:.4f} | {t_epoch:.3f} "
              f"| {base_t/t_epoch:.2f}x |")
        log(f"P={P} done in {time.time()-t0:.0f}s")


if __name__ == "__main__":
    main()
