#!/bin/bash
# Offline smoke run on a virtual 8-device CPU mesh (no dataset download).
JAX_PLATFORMS=cpu \
XLA_FLAGS=--xla_force_host_platform_device_count=8 \
python -m bnsgcn_tpu.main \
  --dataset sbm --n-partitions 8 --model graphsage \
  --n-layers 3 --n-hidden 32 --n-epochs 50 --log-every 10 \
  --sampling-rate 0.5 --use-pp --fix-seed "$@"
