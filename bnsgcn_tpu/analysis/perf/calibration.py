"""graftperf calibration tables: per-backend cost constants + the
measured records the model is pinned against.

Schema (`tools/perf_calibration.json`, written by
`tools/microbench.py --emit-calibration` on a fresh backend):

    {"perf_calibration": 1,
     "backends": {
       "<name>": {"device_kind": "<jax.devices()[0].device_kind>",
                  "gather_rows_per_s": {"<row bytes>": rows/s, ...},
                  "gather_materialize_factor": f,   # materialize-path tax
                  "dense_tile_us": {"<tile edge>": us, ...},
                  "dense_xla_factor": f,            # XLA dense vs pallas
                  "link_GBps": f,                   # per-device wire BW
                  "fixed_step_s": f, "calib_scale": f,
                  "calibrated": true|false},        # false => drift not gated
       ...},
     "records": [{"name", "backend", "measured_s",
                  "features": {StepFeatures fields}}, ...]}

The bundled v5e table is transcribed from the 2026-07-29..31 v5e
microbenches (390/267/106 M rows/s at 256/512/1024 B rows,
~4.3 us per 512x512 int8 tile at H=256, XLA dense path 1.961x pallas,
materialize gather 1.088x the pure-rate slope) and the bundled records
are the round-4 per-chip ladder — gate 4 re-derives the ladder from the
table on every lint run and fails if model and history drift apart.

The bundled cpu table is a rough shape prior (`calibrated: false`):
absolute CPU step time varies machine to machine, so CPU users fit
`calib_scale` from their own obs epoch history via `model.fit_scale`
(the tests do exactly this) instead of trusting bundled constants.
"""

from __future__ import annotations

import copy
import json
import os

from bnsgcn_tpu.analysis.perf.model import StepFeatures

SCHEMA_KEY = "perf_calibration"
SCHEMA_VERSION = 1
DEFAULT_RELPATH = os.path.join("tools", "perf_calibration.json")

_TABLE_REQUIRED = ("gather_rows_per_s", "dense_tile_us", "link_GBps")
_FEATURE_FIELDS = ("n_apps", "gather_slots", "row_bytes", "gather_path",
                   "dense_tiles", "tile", "dense_path", "wire_mb")


def default_calibration() -> dict:
    """The bundled tables + round-4 ladder records (single source of truth;
    tools/perf_calibration.json is this, serialized)."""
    v5e = {
        "device_kind": "TPU v5 lite",
        "gather_rows_per_s": {"256": 390e6, "512": 267e6, "1024": 106e6},
        "gather_materialize_factor": 1.088,
        "dense_tile_us": {"512": 4.3},
        "dense_xla_factor": 1.961,
        # v5e ICI: 1.6 Tbps bidirectional across links -> ~45 GB/s usable
        # per direction per device on the 2D torus (order-of-magnitude;
        # the round-4 epochs are compute-bound so this term is small)
        "link_GBps": 45.0,
        "fixed_step_s": 0.0,
        "calib_scale": 1.0,
        "calibrated": True,
    }
    cpu = {
        "device_kind": "cpu",
        "gather_rows_per_s": {"32": 60e6, "256": 40e6, "1024": 15e6},
        "gather_materialize_factor": 1.0,
        "dense_tile_us": {"512": 2000.0},
        "dense_xla_factor": 1.0,
        # CPU mesh 'wire' is a memcpy through host RAM
        "link_GBps": 10.0,
        "fixed_step_s": 0.0,
        "calib_scale": 1.0,
        "calibrated": False,
    }
    # round-4 per-chip ladder (ogbn-products, P=4, H=256, rate 1.0,
    # use_pp: 3 graph layers x fwd+bwd = 6 SpMM applications/step).
    # wire_mb 0: those epochs are compute-bound (by the July 2026 microbench
    # arithmetic the residual gather alone is ~75% of the 0.5715 s epoch)
    # and the probe timed the
    # exchange separately — the wire term is exercised by the CPU e2e and
    # the monotonicity tests instead.
    base = {"n_apps": 6, "row_bytes": 512, "tile": 512, "wire_mb": 0.0}
    ell_slots = 77.6e6        # 57.4M residual-free ELL edges / 0.74 fill
    hyb_slots = 18.74e6       # fwd residual slots after 8192 dense tiles
    records = [
        {"name": "r4-ell", "backend": "tpu-v5e", "measured_s": 1.672,
         "features": {**base, "gather_slots": ell_slots,
                      "gather_path": "materialize",
                      "dense_tiles": 0, "dense_path": "none"}},
        {"name": "r4-hybrid", "backend": "tpu-v5e", "measured_s": 0.87,
         "features": {**base, "gather_slots": hyb_slots,
                      "gather_path": "materialize",
                      "dense_tiles": 8192, "dense_path": "xla"}},
        {"name": "r4-hybrid-pallas", "backend": "tpu-v5e",
         "measured_s": 0.667,
         "features": {**base, "gather_slots": hyb_slots,
                      "gather_path": "materialize",
                      "dense_tiles": 8192, "dense_path": "pallas"}},
        {"name": "r4-hybrid-pallas-unroll", "backend": "tpu-v5e",
         "measured_s": 0.5715,
         "features": {**base, "gather_slots": hyb_slots,
                      "gather_path": "unroll",
                      "dense_tiles": 8192, "dense_path": "pallas"}},
    ]
    return {SCHEMA_KEY: SCHEMA_VERSION,
            "backends": {"tpu-v5e": v5e, "cpu": cpu},
            "records": records}


def validate_calibration(calib: dict) -> list:
    """Schema + physics sanity; returns human-readable problem strings
    (gate 4 turns each into a perf-calibration-invalid finding)."""
    probs = []
    if not isinstance(calib, dict) or calib.get(SCHEMA_KEY) != SCHEMA_VERSION:
        return [f"missing/unknown {SCHEMA_KEY} schema marker "
                f"(want {SCHEMA_VERSION})"]
    backends = calib.get("backends")
    if not isinstance(backends, dict) or not backends:
        probs.append("no 'backends' tables")
        backends = {}
    for name, tb in backends.items():
        for key in _TABLE_REQUIRED:
            if key not in tb:
                probs.append(f"backend {name!r}: missing {key!r}")
        for key in ("gather_rows_per_s", "dense_tile_us"):
            for k, v in (tb.get(key) or {}).items():
                try:
                    ok = int(k) > 0 and float(v) > 0
                except (TypeError, ValueError):
                    ok = False
                if not ok:
                    probs.append(f"backend {name!r}: {key}[{k!r}] must be a "
                                 f"positive number at a positive int key")
        for key in ("link_GBps", "calib_scale"):
            if key in tb and not float(tb[key]) > 0:
                probs.append(f"backend {name!r}: {key} must be > 0")
    for i, rec in enumerate(calib.get("records") or []):
        tag = rec.get("name") or f"records[{i}]"
        if rec.get("backend") not in backends:
            probs.append(f"record {tag}: unknown backend "
                         f"{rec.get('backend')!r}")
        if not (isinstance(rec.get("measured_s"), (int, float))
                and rec["measured_s"] > 0):
            probs.append(f"record {tag}: measured_s must be > 0")
        feats = rec.get("features")
        if not isinstance(feats, dict):
            probs.append(f"record {tag}: missing features")
        else:
            unknown = set(feats) - set(_FEATURE_FIELDS)
            if unknown:
                probs.append(f"record {tag}: unknown feature field(s) "
                             f"{sorted(unknown)}")
    return probs


def record_features(rec: dict) -> StepFeatures:
    return StepFeatures(**rec["features"])


def calibration_path(root: str | None = None) -> str:
    from bnsgcn_tpu.analysis.core import resolve_root
    return os.path.join(resolve_root(root), DEFAULT_RELPATH)


def load_calibration(source=None, root: str | None = None) -> dict:
    """`source` may be a dict (tests inject miscalibrations directly), a
    path, or None for the bundled tools/perf_calibration.json."""
    if isinstance(source, dict):
        return copy.deepcopy(source)
    path = source or calibration_path(root)
    with open(path) as f:
        return json.load(f)


def save_calibration(calib: dict, path: str) -> None:
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(calib, f, indent=2, sort_keys=True)
        f.write("\n")
    os.replace(tmp, path)


def backend_table(calib: dict, device_kind: str) -> dict:
    """The calibration table for a device, keyed on what the device says it
    is: `jax.devices()[0].device_kind` ('TPU v5 lite', 'cpu') against each
    table's 'device_kind' field. A table's own name matches too, for tools
    that name one (`perf_rank.py --backend tpu-v5e`). A kind no table
    claims is a KeyError: constants measured on one TPU generation are
    never handed to another."""
    backends = calib["backends"]
    if device_kind in backends:
        return backends[device_kind]
    for table in backends.values():
        if table.get("device_kind") == device_kind:
            return table
    raise KeyError(
        f"no calibration table for device kind {device_kind!r} (have "
        + ", ".join(f"{n} [{t.get('device_kind', '?')}]"
                    for n, t in sorted(backends.items())) + ")")
