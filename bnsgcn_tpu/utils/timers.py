"""Timing + device-memory observability.

The reference wraps wall-clock spans around every transfer
(helper/timer/comm_timer.py). Under XLA a span inside a jitted step is
meaningless; instead the trainer measures (a) whole-epoch wall time after
block_until_ready and (b) communication time from the profiler window's
device collective spans (utils/traceparse.py). This module provides the
per-epoch bookkeeping plus peak-HBM reporting equivalent to print_memory
(helper/utils.py:244-250). Host spans of the loop live in obs.span.
"""

from __future__ import annotations

import jax
import numpy as np


class EpochTimer:
    """Per-epoch Time/Comm/Reduce accumulators with warmup exclusion
    (reference train.py:366,415-423: first `warmup` epochs dropped)."""

    def __init__(self, warmup: int = 5):
        self.warmup = warmup
        self.train_dur: list[float] = []
        self.comm_dur: list[float] = []
        self.reduce_dur: list[float] = []
        # per-step phase buckets (--overlap split observability): trace-
        # derived 'exchange_ms' / 'interior_ms' / 'frontier_ms' /
        # 'hidden_ms' device-time attributions (utils/traceparse
        # .overlap_report); empty for fused runs
        self.buckets: dict[str, list[float]] = {}

    def record(self, epoch: int, train_t: float, comm_t: float = 0.0, reduce_t: float = 0.0):
        if epoch >= self.warmup:
            self.train_dur.append(train_t)
            self.comm_dur.append(comm_t)
            self.reduce_dur.append(reduce_t)

    def record_bucket(self, name: str, value_ms: float):
        self.buckets.setdefault(name, []).append(float(value_ms))

    def bucket_means(self) -> dict[str, float]:
        return {k: float(np.mean(v)) for k, v in self.buckets.items() if v}

    def means(self) -> tuple[float, float, float]:
        m = lambda xs: float(np.mean(xs)) if xs else 0.0
        return m(self.train_dur), m(self.comm_dur), m(self.reduce_dur)


def device_memory_stats() -> dict:
    """Peak/current HBM per device (reference print_memory equivalent)."""
    out = {}
    for d in jax.local_devices():       # stats exist for addressable devices
        s = d.memory_stats()            # None where the backend keeps none
        if s:
            out[str(d)] = {
                "bytes_in_use": s.get("bytes_in_use", 0),
                "peak_bytes_in_use": s.get("peak_bytes_in_use", 0),
                "bytes_limit": s.get("bytes_limit", 0),
            }
    return out


def format_memory_stats() -> str:
    lines = []
    for dev, s in device_memory_stats().items():
        lines.append(
            f"{dev}: current {s['bytes_in_use'] / 2**20:.2f} MB, "
            f"peak {s['peak_bytes_in_use'] / 2**20:.2f} MB, "
            f"limit {s['bytes_limit'] / 2**20:.2f} MB")
    return "\n".join(lines) if lines else "(no device memory stats available)"
