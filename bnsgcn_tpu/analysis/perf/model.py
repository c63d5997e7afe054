"""graftperf cost model: predicted step/wire time from layout geometry.

A calibrated roofline over the three terms every variant of the training
step decomposes into (the round-4 'layout-derived cost model'):

  step_s = fixed + calib_scale * (n_apps * (gather_s + dense_s) + wire_s)

  gather_s = gather_slots / gather_rows_per_s(row_bytes)
             [* gather_materialize_factor on the materialize path]
  dense_s  = dense_tiles * dense_tile_us(tile) * 1e-6
             [* dense_xla_factor off the pallas path]
  wire_s   = wire_mb * 1e6 / (link_GBps * 1e9)

The per-backend constants live in a calibration table (see
`calibration.py`; persisted by `tools/microbench.py --emit-calibration`).

Everything here is numpy-only ON PURPOSE: lint gate 4 (`python -m
bnsgcn_tpu.analysis perf`) must run in seconds with zero devices, so the
halo wire geometry is MIRRORED from `parallel/halo.py` (which imports
jax at module level) instead of imported. The mirror is pinned
bit-equal to `make_halo_spec` / `make_refresh_spec` / `wire_bytes` by
tests/test_perf_model.py — edit those together or the pin fails.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, replace

import numpy as np

__all__ = [
    "StepFeatures", "exchange_geometry", "refresh_geometry",
    "geometry_wire_bytes", "steady_wire_mb", "hybrid_features",
    "gather_rows_per_s", "dense_tile_us", "predict_parts",
    "predict_step_s", "predict_wire_s", "drift", "fit_scale",
    "model_prior", "ell_geometry_slots",
]


# ---------------------------------------------------------------------------
# halo wire-geometry mirror (parallel/halo.py, jax-free)
# ---------------------------------------------------------------------------

def _round8(x: int) -> int:
    return ((x + 7) // 8) * 8


def exchange_geometry(n_b, pad_boundary: int, rate: float) -> dict:
    """Mirror of `halo.make_halo_spec`'s static geometry: the
    (pad_send, shift_pads, pair_send) triple `wire_bytes` prices, from the
    [P, P] boundary-count table alone."""
    n_b = np.asarray(n_b, dtype=np.int64)
    P = int(n_b.shape[0])
    exact = rate >= 1.0
    send = n_b if exact else (rate * n_b).astype(np.int64)
    pad_send = max(1, int(send.max())) if send.size else 1
    pad_send = min(_round8(pad_send), pad_boundary)
    shift_pads = []
    for k in range(1, P):
        m = int(max(send[p, (p + k) % P] for p in range(P)))
        shift_pads.append(0 if m == 0 else min(_round8(m), pad_send))
    return {"n_parts": P, "pad_send": pad_send,
            "shift_pads": tuple(shift_pads),
            "pair_send": tuple(map(tuple, send.tolist()))}


def refresh_geometry(n_b, pad_boundary: int, rate: float,
                     refresh: int) -> dict:
    """Mirror of `halo.make_refresh_spec`'s steady-state geometry (chunk
    sends sized to the worst chunk; NO x8 lane rounding — see the comment
    there on why rounding would erase the ~K x saving)."""
    K = int(refresh)
    assert K >= 1, f"halo refresh period must be >= 1, got {K}"
    n_b = np.asarray(n_b, dtype=np.int64)
    P = int(n_b.shape[0])
    exact = rate >= 1.0
    c_idx = np.arange(K, dtype=np.int64).reshape(K, 1, 1)
    n_bc = (np.maximum(n_b[None] - c_idx, 0) + K - 1) // K
    if exact:
        s_c = n_bc
    else:
        full_send = (rate * n_b).astype(np.int64)
        s_c = np.where((n_bc > 0) & (full_send[None] > 0),
                       np.maximum((rate * n_bc).astype(np.int64), 1), 0)
    pair_send = s_c.max(axis=0)
    pad_b_chunk = (pad_boundary + K - 1) // K
    pad_send = max(1, int(pair_send.max())) if pair_send.size else 1
    pad_send = min(pad_send, max(pad_b_chunk, 1))
    shift_pads = []
    for k in range(1, P):
        m = int(max(pair_send[p, (p + k) % P] for p in range(P)))
        shift_pads.append(0 if m == 0 else min(m, pad_send))
    return {"n_parts": P, "pad_send": pad_send,
            "shift_pads": tuple(shift_pads),
            "pair_send": tuple(map(tuple, pair_send.tolist()))}


def geometry_wire_bytes(geom: dict, strategy: str, wire: str, width: int,
                        native_bytes: int = 4) -> int:
    """Mirror of `halo.wire_bytes` over a mirror geometry dict: per-device
    payload bytes of ONE exchange (padded full buffer / shift diagonal
    pads / ragged bottleneck exact off-diagonal rows)."""
    b = {"native": native_bytes, "bf16": 2, "fp8": 1, "int8": 1}[wire]
    if strategy == "shift":
        return sum(geom["shift_pads"]) * width * b
    if strategy == "ragged":
        S = np.asarray(geom["pair_send"], dtype=np.int64).copy()
        np.fill_diagonal(S, 0)
        rows = int(S.sum(axis=1).max()) if S.size else 0
        return rows * width * b
    return geom["n_parts"] * geom["pad_send"] * width * b


def steady_wire_mb(n_b, pad_boundary: int, rate: float, *, strategy: str,
                   wire: str, refresh: int = 1, mode: str = "exchange",
                   width: int, native_bytes: int = 4) -> float:
    """Steady-state MB one exchange ships under the full lever state —
    run.py's `steady_wire_mb` (0 under grad-only, the ~1/K partial
    geometry under --halo-refresh K, the full geometry otherwise)."""
    if mode == "grad-only":
        return 0.0
    geom = (refresh_geometry(n_b, pad_boundary, rate, refresh)
            if refresh > 1 else exchange_geometry(n_b, pad_boundary, rate))
    return geometry_wire_bytes(geom, strategy, wire, width,
                               native_bytes) / 1e6


# ---------------------------------------------------------------------------
# step-time features + prediction
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class StepFeatures:
    """What one training step looks like to the roofline — every field a
    pure layout/geometry property, no timing in here.

    `wire_mb` is the TOTAL payload per step per device (all exchanges,
    fwd+bwd), not the per-exchange figure run.py logs."""
    n_apps: int = 0              # SpMM applications/step (graph layers x fwd+bwd)
    gather_slots: float = 0.0    # padded ELL slots per application
    row_bytes: int = 0           # gathered row payload (width x dtype bytes)
    gather_path: str = "materialize"   # 'materialize' | 'unroll' | 'none'
    dense_tiles: int = 0         # MXU tiles per application (hybrid)
    tile: int = 512              # dense tile edge length
    dense_path: str = "none"     # 'pallas' | 'xla' | 'none'
    wire_mb: float = 0.0         # total MB on the wire per step per device


def hybrid_features(*, n_edges: float, coverage: float, fill: float,
                    dense_tiles: int, tile: int = 512, row_bytes: int,
                    n_apps: int, gather_path: str = "materialize",
                    dense_path: str = "xla",
                    wire_mb: float = 0.0) -> StepFeatures:
    """Features of a hybrid (dense tiles + ELL residual) layout from the
    tiling_check statistics: `coverage` is the dense edge fraction,
    `fill` the residual ELL bucket fill — coverage enters the model ONLY
    by shrinking the residual (tile count is a budget, not a function of
    coverage), which is what makes 'higher coverage => less time' a
    theorem rather than a hope."""
    residual_edges = float(n_edges) * max(1.0 - coverage, 0.0)
    slots = residual_edges / max(fill, 1e-9)
    return StepFeatures(
        n_apps=n_apps, gather_slots=slots, row_bytes=row_bytes,
        gather_path=(gather_path if slots > 0 else "none"),
        dense_tiles=dense_tiles, tile=tile,
        dense_path=(dense_path if dense_tiles > 0 else "none"),
        wire_mb=wire_mb)


def gather_rows_per_s(table: dict, row_bytes: int) -> float:
    """Gather throughput at the given row payload, log-log interpolated
    between the measured widths. Below the smallest measured row the rate
    saturates (latency/issue-bound — clamp); above the largest it decays
    1/bytes (bandwidth-bound)."""
    pts = sorted((int(k), float(v))
                 for k, v in table["gather_rows_per_s"].items())
    if not pts:
        raise ValueError("gather_rows_per_s table is empty")
    rb = max(int(row_bytes), 1)
    if rb <= pts[0][0]:
        return pts[0][1]
    if rb >= pts[-1][0]:
        k, v = pts[-1]
        return v * k / rb
    for (k0, v0), (k1, v1) in zip(pts, pts[1:]):
        if k0 <= rb <= k1:
            t = (math.log(rb) - math.log(k0)) / (math.log(k1) - math.log(k0))
            return math.exp(math.log(v0) * (1 - t) + math.log(v1) * t)
    raise AssertionError("unreachable")


def dense_tile_us(table: dict, tile: int) -> float:
    """Per-tile MXU cost at the given tile edge: nearest measured tile,
    scaled by (tile/measured)^2 — a [t, t] @ [t, H] tile is 2*t*t*H FLOPs,
    quadratic in the edge at fixed H."""
    pts = sorted((int(k), float(v)) for k, v in table["dense_tile_us"].items())
    if not pts:
        raise ValueError("dense_tile_us table is empty")
    k, v = min(pts, key=lambda kv: abs(math.log(tile) - math.log(kv[0])))
    return v * (tile / k) ** 2


def predict_parts(feat: StepFeatures, table: dict) -> dict:
    """The per-term breakdown behind `predict_step_s` — what bench.py's
    residual line and obs_report's prediction section print."""
    gather_s = 0.0
    if feat.gather_path != "none" and feat.gather_slots > 0:
        gather_s = feat.gather_slots / gather_rows_per_s(table,
                                                         feat.row_bytes)
        if feat.gather_path == "materialize":
            gather_s *= float(table.get("gather_materialize_factor", 1.0))
    dense_s = 0.0
    if feat.dense_path != "none" and feat.dense_tiles > 0:
        dense_s = feat.dense_tiles * dense_tile_us(table, feat.tile) * 1e-6
        if feat.dense_path == "xla":
            dense_s *= float(table.get("dense_xla_factor", 1.0))
    wire_s = feat.wire_mb * 1e6 / (float(table["link_GBps"]) * 1e9)
    scale = float(table.get("calib_scale", 1.0))
    fixed = float(table.get("fixed_step_s", 0.0))
    step = fixed + scale * (feat.n_apps * (gather_s + dense_s) + wire_s)
    return {"gather_s": gather_s, "dense_s": dense_s, "wire_s": wire_s,
            "fixed_s": fixed, "scale": scale, "step_s": step}


def predict_step_s(feat: StepFeatures, table: dict) -> float:
    return predict_parts(feat, table)["step_s"]


def predict_wire_s(feat: StepFeatures, table: dict) -> float:
    return predict_parts(feat, table)["wire_s"]


def drift(predicted: float, measured: float) -> float:
    """Signed relative drift of a prediction; +0.25 == 25% over."""
    return predicted / max(measured, 1e-12) - 1.0


def fit_scale(pairs, table: dict) -> dict:
    """One-parameter calibration: returns a copy of `table` whose
    `calib_scale` is the median measured/raw-predicted ratio over
    `pairs` = [(StepFeatures, measured_s), ...]. Median, not mean — a
    single compile-tail epoch must not drag the whole model. This is the
    round-trip `load -> fit -> predict` the CPU obs-history test drives."""
    base = dict(table)
    base["calib_scale"] = 1.0
    base["fixed_step_s"] = 0.0
    ratios = []
    for feat, measured in pairs:
        raw = predict_step_s(feat, base)
        if raw > 0 and measured > 0:
            ratios.append(measured / raw)
    if not ratios:
        raise ValueError("fit_scale: no usable (features, measured) pairs")
    out = dict(table)
    out["calib_scale"] = float(np.median(ratios))
    out["fixed_step_s"] = 0.0
    return out


# ---------------------------------------------------------------------------
# layout helpers + the --tune-prior model decision
# ---------------------------------------------------------------------------

def ell_geometry_slots(geometry: dict, direction: str = "fwd") -> int:
    """Padded ELL slots of one direction from `art.ell_geometry`
    (ops/ell.compute_geometry schema): sum of width x padded-rows over
    the buckets (the cap bucket's rows already include the split-row
    chunk overflow — compute_geometry folds it in before padding)."""
    g = geometry[direction]
    slots = sum(int(w) * int(r) for w, r in zip(g["widths"], g["rows"]))
    return int(slots)


def run_features(cfg, art, *, strategy: str,
                 width: int | None = None) -> StepFeatures:
    """StepFeatures of the run `run.py` is about to launch, from the
    partition artifacts + config alone (pre-build — this feeds the
    --tune-prior model decision, which must land BEFORE the first
    compile). ELL slots come from art.ell_geometry when the partitioner
    stored it, else the padded edge count stands in; the wire term is
    the K=1 full-exchange payload across the per-step halo hops
    (fwd+bwd per graph-layer boundary). Deliberately width-approximate
    (feat-axis sharding and the layer-0 feature hop are ignored): the
    prior consumes a comm FRACTION, not absolute seconds."""
    nb = 2 if cfg.dtype == "bfloat16" else 4
    width = int(cfg.n_hidden) if width is None else int(width)
    geom = exchange_geometry(art.n_b, art.pad_boundary, cfg.sampling_rate)
    per_ex_mb = geometry_wire_bytes(geom, strategy, cfg.halo_wire,
                                    width, nb) / 1e6
    layers = max(int(cfg.n_layers), 1)
    n_exchanges = 2 * max(layers - 1, 1)
    if getattr(art, "ell_geometry", None):
        slots = 0.5 * (ell_geometry_slots(art.ell_geometry, "fwd")
                       + ell_geometry_slots(art.ell_geometry, "bwd"))
    else:
        slots = float(art.pad_edges)
    return StepFeatures(
        n_apps=2 * layers, gather_slots=slots, row_bytes=width * nb,
        gather_path="materialize",
        wire_mb=per_ex_mb * n_exchanges)


def model_prior(feat: StepFeatures, table: dict,
                comm_frac: float = 0.30) -> dict:
    """The `--tune auto --tune-prior model` startup decision: predict the
    comm fraction at the FRESHEST lever state (K=1) and pick the coarsest
    staleness rung the model says still matters.

      * comm-bound (predicted wire >= `comm_frac` of the step): the wire
        is the bottleneck — start at K=4, exactly the default ladder's
        coarse launch point;
      * compute-bound: coarse staleness buys predicted-immaterial time,
        so skip the K=4 rung and start at K=2 — one local refinement
        (K=2 -> K=1 when the loss goes flat) instead of two.

    Returns {"halo_refresh", "comm_frac", "wire_s", "step_s", "why"};
    tune.startup_changes folds it without ever loosening a state the
    user launched coarser than the pick."""
    parts = predict_parts(feat, table)
    step = max(parts["step_s"], 1e-12)
    c = parts["wire_s"] * parts["scale"] / step
    if c >= comm_frac:
        pick, tag = 4, "comm-bound"
    else:
        pick, tag = 2, "compute-bound"
    why = (f"model-prior: predicted comm {c:.1%} of step "
           f"({tag} vs {comm_frac:.0%} threshold) -> start K={pick}")
    return {"halo_refresh": pick, "comm_frac": c,
            "wire_s": parts["wire_s"], "step_s": parts["step_s"],
            "why": why}


def scaled_features(feat: StepFeatures, *, wire_mb: float) -> StepFeatures:
    """Same step, different wire payload — the monotonicity probes and the
    prior's per-rung sweep both re-price wire without touching compute."""
    return replace(feat, wire_mb=wire_mb)
