"""Calibrate the accuracy-anchor graph so exact training plateaus ~97%.

Searches feat_snr x label_noise on the reddit_like_graph generator, printing
exact (P=1 rate=1.0), BNS (P=4 rate=0.1), and the two mutations' accuracies.
The goal configuration makes
  * exact land in [0.94, 0.99]  (NOT saturated at 1.0),
  * BNS stay within 0.5% of exact,
  * break_rescale / biased_sampler drop VISIBLY below that band.
Run on the virtual CPU mesh:
  JAX_PLATFORMS=cpu \
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  python tools/calibrate_anchor.py [--grid | --snr S --noise N]
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bnsgcn_tpu.data.graph import reddit_like_graph
from tools.anchor_harness import train_eval

GRAPH = dict(n_nodes=8192, avg_degree=96, n_class=16, n_feat=32, seed=11)


def run_point(snr, noise, epochs, mutations=False, norm=None):
    g = reddit_like_graph(feat_snr=snr, label_noise=noise, **GRAPH)
    t0 = time.time()
    acc_e = train_eval(g, P=1, rate=1.0, epochs=epochs, norm=norm)
    acc_b = train_eval(g, P=4, rate=0.1, epochs=epochs, norm=norm)
    row = {"snr": snr, "noise": noise, "exact": acc_e, "bns": acc_b}
    if mutations:
        row["broken_rescale"] = train_eval(g, P=4, rate=0.1, epochs=epochs,
                                           break_rescale=True, norm=norm)
        row["biased_sampler"] = train_eval(g, P=4, rate=0.1, epochs=epochs,
                                           biased_sampler=True, norm=norm)
    row["t"] = round(time.time() - t0, 1)
    print(" ".join(f"{k}={v:.4f}" if isinstance(v, float) else f"{k}={v}"
                   for k, v in row.items()), flush=True)
    return row


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--grid", action="store_true")
    ap.add_argument("--snr", type=float, default=0.12)
    ap.add_argument("--noise", type=float, default=0.03)
    ap.add_argument("--epochs", type=int, default=120)
    ap.add_argument("--mutations", action="store_true")
    ap.add_argument("--norm", type=str, default="none",
                    choices=["none", "layer"])
    args = ap.parse_args()
    norm = None if args.norm == "none" else args.norm
    if args.grid:
        for noise in (0.0, 0.03):
            for snr in (0.06, 0.09, 0.12, 0.18, 0.25):
                run_point(snr, noise, args.epochs, norm=norm)
    else:
        run_point(args.snr, args.noise, args.epochs,
                  mutations=args.mutations, norm=norm)


if __name__ == "__main__":
    sys.exit(main())
