#!/bin/bash
# Flagship Reddit recipe (reference scripts/reddit.sh): GraphSAGE 4x256,
# P-partition BNS at rate 0.1, precompute, inductive. Requires the real
# Reddit dataset (dgl) — use sbm_demo.sh for an offline smoke run.
# TPU perf knobs (the recipe chip_smoke.py runs; see PERF.md): append
#   --dtype bfloat16 --spmm auto --halo-wire int8
# (auto picks the hybrid MXU-tile SpMM on clustered graphs, whose dense
#  tiles run the Pallas kernel on a TPU; --block-tile 256 / --spmm-gather
#  int8 are the finer-tile / 1-byte-residual knobs).
python -m bnsgcn_tpu.main \
  --dataset reddit \
  --dropout 0.5 \
  --lr 0.01 \
  --n-partitions ${P:-8} \
  --n-epochs 3000 \
  --model graphsage \
  --sampling-rate 0.1 \
  --n-layers 4 \
  --n-hidden 256 \
  --log-every 10 \
  --use-pp \
  --inductive \
  "$@"
