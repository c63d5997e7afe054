"""papers100M on a 64-chip pod, priced by the graftperf roofline model.

A PREDICTION, not a measurement: the analysis/perf model (whose v5e table
is four July 2026 single-chip records, tools/perf_calibration.json)
applied to one chip's share of papers100M. No chip has run this cell.

Workload geometry (one rank's share of Reddit P=2, 57.4M edges/chip,
GraphSAGE H=256, 6 SpMM applications/step) and the clustered-graph hybrid
tile coverage are the constants the calibration records were taken at.

Usage:
    python tools/perf_rank.py [--backend tpu-v5e] [--calibration FILE]
"""

from __future__ import annotations

import argparse
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from bnsgcn_tpu.analysis.perf import calibration as pcal           # noqa: E402
from bnsgcn_tpu.analysis.perf import model as pmod                 # noqa: E402

# the calibration workload: 57.4M edges/chip, ELL bucket fill 0.74, 6 SpMM
# apps, 8192 t512 tiles behind 75.8% dense-tile coverage (dcsbm graph)
EDGES = 57.4e6
FILL = 0.74
N_APPS = 6
COVERAGE = 0.758
TILES_AT_DCSBM = 8192.0


def pod_projection(table):
    """papers100M (111M nodes / 1.615B edges) on a 64-chip pod, the
    round-4 recipe (hybrid+pallas+i8g, SAGE 3x256, METIS-ish partition:
    ~30% boundary rows, BNS rate 0.5, bf16 wire)."""
    chips, n_nodes, n_edges = 64, 111.06e6, 1.615e9
    epc = n_edges / chips
    cov = COVERAGE                     # clustered-graph coverage
    slots = epc * (1.0 - cov) / FILL
    tiles = TILES_AT_DCSBM * (epc * cov) / (EDGES * COVERAGE)
    boundary = 0.30 * n_nodes / chips
    wire_mb = boundary * 0.5 * 256 * 2 / 1e6    # rows x rate x H x bf16
    n_exchanges = 2 * (3 - 1)                   # 3 layers, fwd+bwd
    feat = pmod.StepFeatures(
        n_apps=N_APPS, gather_slots=slots, row_bytes=256,
        gather_path="unroll", dense_tiles=int(tiles), tile=512,
        dense_path="pallas", wire_mb=wire_mb * n_exchanges)
    parts = pmod.predict_parts(feat, table)
    return {"chips": chips, "edges_per_chip_M": round(epc / 1e6, 1),
            "residual_slots_M": round(slots / 1e6, 1),
            "dense_tiles": int(tiles),
            "wire_mb_per_exchange": round(wire_mb, 1),
            "gather_s": round(parts["gather_s"], 4),
            "dense_s": round(parts["dense_s"], 4),
            "wire_s": round(parts["wire_s"], 4),
            "epoch_s": round(parts["step_s"], 4),
            "chip_s_per_epoch": round(parts["step_s"] * chips, 2)}


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="papers100M 64-chip projection (a model prediction)")
    ap.add_argument("--calibration", default="",
                    help="calibration json (default: bundled)")
    ap.add_argument("--backend", default="tpu-v5e",
                    help="calibration table to price with")
    args = ap.parse_args(argv)

    calib = pcal.load_calibration(args.calibration or None, root=REPO)
    proj = pod_projection(pcal.backend_table(calib, args.backend))
    print("papers100M on a 64-chip pod (hybrid+pallas+i8g, SAGE "
          "3x256, rate 0.5, bf16 wire, ~30% boundary) - predicted, "
          "not measured:")
    for k, v in proj.items():
        print(f"  {k}: {v}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
