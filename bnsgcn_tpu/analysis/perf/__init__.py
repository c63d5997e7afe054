"""graftperf: predictive roofline audit of the variant matrix (gate 4).

Gates 1-3 prove hazards absent from the source, the compiled programs,
and the coordination protocol; this tier checks the repo's PERFORMANCE
STORY stays coherent: the calibrated cost model (`model.py`,
`calibration.py`) must keep reproducing the measurements the repo's
decisions were justified by. Per lint run it verifies:

1. **calibration schema** — tools/perf_calibration.json parses and
   passes physics sanity (positive rates, known backends/features);
2. **recorded-measurement drift** — every bundled record (the round-4
   per-chip ladder) re-predicts within ``DRIFT_BAND`` of its measured
   value from the CURRENT tables; a table or feature edit that breaks
   the history fails the gate, not a later hardware window;
3. **monotonicity** — more wire costs more predicted time, higher dense
   coverage costs less, gather throughput never rises with row bytes,
   coarser --halo-refresh never ships more steady-state bytes;
4. **variant sweep** — every tune-reachable lever state (the gate-2
   variant matrix) prices to finite wire/step predictions on a fixed
   synthetic geometry, with int8 <= bf16 <= native byte ordering,
   ragged <= padded, and grad-only == 0;
5. **obs consistency** (``--check-obs LOG``) — each epoch record's
   wire_mb matches a wire figure its run_header/tune_decision events
   declared (peak, steady, or grad-only zero).

Everything is host arithmetic over persisted JSON + mirrored numpy
geometry — no jax tracing, no devices, seconds per run.

Entry points: ``run_perf_audit`` (library), ``python -m
bnsgcn_tpu.analysis perf`` (CLI, see __main__), `tools/lint.sh` gate 4.
"""

from __future__ import annotations

import json
import math
import os
import time

import numpy as np

from bnsgcn_tpu.analysis.perf import calibration as C
from bnsgcn_tpu.analysis.perf import model as M

DRIFT_BAND = 0.25      # |predicted/measured - 1| beyond this is a finding

# The sweep geometry: same spirit as the gate-2 audit graph — small,
# deterministic, skewed enough that padded/shift/ragged and every refresh
# rung produce DIFFERENT byte counts (a balanced matrix would let a
# broken ragged mirror hide behind padded's numbers).
AUDIT_RATE = 0.5
AUDIT_WIDTH = 8
AUDIT_N_B = np.array([[0, 40, 11, 3],
                      [40, 0, 25, 7],
                      [11, 25, 0, 18],
                      [3, 7, 18, 0]], dtype=np.int64)
AUDIT_PAD_BOUNDARY = 48        # round8(max n_b) + one spare lane row

_CODEC_BYTES = (("int8", 1), ("fp8", 1), ("bf16", 2), ("native", 4))


def _finding(file, rule, message):
    from bnsgcn_tpu.analysis.core import Finding
    return Finding(file=file, line=0, col=0, rule=rule, message=message)


def _nominal_features(wire_mb: float = 0.0) -> M.StepFeatures:
    """A mid-size hybrid step used by the monotonicity and variant-sweep
    probes — absolute numbers don't matter, orderings do."""
    return M.hybrid_features(
        n_edges=50e6, coverage=0.6, fill=0.74, dense_tiles=4096,
        tile=512, row_bytes=512, n_apps=6, dense_path="xla",
        wire_mb=wire_mb)


def check_records(calib: dict, drift_band: float):
    """Contract 2: the bundled measurements re-predict from the current
    tables. Uncalibrated tables (cpu shape prior) are exempt — their
    records would gate on machine noise, not model quality."""
    findings, rows = [], []
    for rec in calib.get("records") or []:
        name = rec.get("name", "?")
        table = calib["backends"][rec["backend"]]
        feat = C.record_features(rec)
        pred = M.predict_step_s(feat, table)
        d = M.drift(pred, rec["measured_s"])
        rows.append({"name": name, "backend": rec["backend"],
                     "measured_s": rec["measured_s"],
                     "predicted_s": round(pred, 4), "drift": round(d, 4)})
        if table.get("calibrated", True) and abs(d) > drift_band:
            findings.append(_finding(
                f"perf://record/{name}", "perf-model-drift",
                f"predicted {pred:.4f}s vs measured "
                f"{rec['measured_s']:.4f}s ({d:+.1%}, band "
                f"±{drift_band:.0%})"))
    return findings, rows


def check_monotone(calib: dict):
    """Contract 3: the physical orderings every roofline must satisfy."""
    findings = []
    for name, table in sorted(calib["backends"].items()):
        where = f"perf://monotone/{name}"
        lo = M.predict_step_s(_nominal_features(wire_mb=10.0), table)
        hi = M.predict_step_s(_nominal_features(wire_mb=20.0), table)
        if not hi > lo:
            findings.append(_finding(
                where, "perf-model-nonmonotone",
                f"2x wire did not cost more time ({hi:.4f} <= {lo:.4f})"))
        f_lo = M.hybrid_features(n_edges=50e6, coverage=0.4, fill=0.74,
                                 dense_tiles=4096, row_bytes=512, n_apps=6)
        f_hi = M.hybrid_features(n_edges=50e6, coverage=0.8, fill=0.74,
                                 dense_tiles=4096, row_bytes=512, n_apps=6)
        if not M.predict_step_s(f_hi, table) < M.predict_step_s(f_lo, table):
            findings.append(_finding(
                where, "perf-model-nonmonotone",
                "higher dense coverage did not cost less time"))
        rates = [M.gather_rows_per_s(table, rb)
                 for rb in (32, 64, 128, 256, 512, 1024, 2048, 4096)]
        if any(b > a * (1 + 1e-9) for a, b in zip(rates, rates[1:])):
            findings.append(_finding(
                where, "perf-model-nonmonotone",
                "gather rows/s increased with row bytes"))
    mbs = [M.steady_wire_mb(AUDIT_N_B, AUDIT_PAD_BOUNDARY, AUDIT_RATE,
                            strategy="padded", wire="native", refresh=k,
                            width=AUDIT_WIDTH) for k in (1, 2, 4)]
    if any(b > a * (1 + 1e-9) for a, b in zip(mbs, mbs[1:])):
        findings.append(_finding(
            "perf://monotone/refresh", "perf-model-nonmonotone",
            f"coarser --halo-refresh shipped more steady bytes ({mbs})"))
    return findings


def check_variants(calib: dict, tune_schedule=None, progress=None):
    """Contract 4: price every tune-reachable lever state on the audit
    geometry; orderings that don't hold would mean the tuner's wire
    accounting and the model's have diverged."""
    from bnsgcn_tpu.analysis.ir.variants import enumerate_variants
    try:
        table = C.backend_table(calib, "tpu-v5e")
    except KeyError:                     # an injected calibration without it
        table = next(iter(calib["backends"].values()))
    variants = enumerate_variants(tune_schedule=tune_schedule)
    findings, rows, errors = [], [], []
    for i, v in enumerate(variants):
        if progress is not None:
            progress(f"[perf] {i + 1}/{len(variants)} {v.key} ({v.source})")
        where = f"perf://{v.key}"
        try:
            mb = M.steady_wire_mb(
                AUDIT_N_B, AUDIT_PAD_BOUNDARY, AUDIT_RATE,
                strategy=v.strategy, wire=v.wire, refresh=v.refresh,
                mode=v.mode, width=AUDIT_WIDTH)
            step = M.predict_step_s(_nominal_features(wire_mb=2 * mb), table)
            if not (math.isfinite(mb) and mb >= 0 and math.isfinite(step)
                    and step > 0):
                findings.append(_finding(
                    where, "perf-model-nonmonotone",
                    f"non-finite prediction (wire {mb}, step {step})"))
            if v.mode == "grad-only" and mb != 0.0:
                findings.append(_finding(
                    where, "perf-model-nonmonotone",
                    f"grad-only predicted {mb} MB of halo wire"))
            if v.mode != "grad-only":
                by_codec = {w: M.steady_wire_mb(
                    AUDIT_N_B, AUDIT_PAD_BOUNDARY, AUDIT_RATE,
                    strategy=v.strategy, wire=w, refresh=v.refresh,
                    mode=v.mode, width=AUDIT_WIDTH)
                    for w, _ in _CODEC_BYTES}
                order = [by_codec[w] for w, _ in _CODEC_BYTES]
                if any(b < a for a, b in zip(order, order[1:])):
                    findings.append(_finding(
                        where, "perf-model-nonmonotone",
                        f"wire codec byte ordering violated: {by_codec}"))
                if v.strategy == "ragged":
                    padded = M.steady_wire_mb(
                        AUDIT_N_B, AUDIT_PAD_BOUNDARY, AUDIT_RATE,
                        strategy="padded", wire=v.wire, refresh=v.refresh,
                        mode=v.mode, width=AUDIT_WIDTH)
                    if mb > padded * (1 + 1e-9):
                        findings.append(_finding(
                            where, "perf-model-nonmonotone",
                            f"ragged priced above padded "
                            f"({mb:.6f} > {padded:.6f} MB)"))
            rows.append({"key": v.key, "source": v.source,
                         "wire_mb": round(mb, 6),
                         "predicted_step_s": round(step, 4)})
        except Exception as ex:   # attribute, keep auditing other cells
            errors.append(f"{v.key}: {type(ex).__name__}: {ex}")
            findings.append(_finding(
                where, "perf-audit-error",
                f"variant failed to price: {type(ex).__name__}: {ex}"))
    return findings, rows, errors


def check_obs_log(path: str, tol: float = 0.05):
    """Contract 5: every epoch record's wire_mb is a figure some
    run_header/tune_decision on the same log declared (full-refresh peak,
    steady partial, or grad-only zero). Catches the accounting and the
    recording drifting apart — the lie gate 4 exists to prevent."""
    findings = []
    declared = {0.0}
    checked = mismatched = 0
    with open(path) as f:
        for lineno, line in enumerate(f, 1):
            line = line.strip()
            if not line:
                continue
            try:
                ev = json.loads(line)
            except ValueError:
                continue
            kind = ev.get("kind")
            if kind == "run_header":
                for key in ("wire_mb_per_exchange", "wire_mb_steady"):
                    if isinstance(ev.get(key), (int, float)):
                        declared.add(round(float(ev[key]), 4))
            elif kind == "tune_decision":
                # a retune re-declares both figures: steady for the
                # cache-hit epochs, peak for the forced full refresh
                # its geometry change triggers
                for key in ("wire_mb_steady", "wire_mb_peak"):
                    if isinstance(ev.get(key), (int, float)):
                        declared.add(round(float(ev[key]), 4))
            elif kind == "epoch" and isinstance(ev.get("wire_mb"),
                                               (int, float)):
                checked += 1
                w = float(ev["wire_mb"])
                if not any(abs(w - d) <= tol * max(d, 1e-9) + 1e-3
                           for d in declared):
                    mismatched += 1
                    if mismatched <= 5:   # first few carry the signal
                        findings.append(_finding(
                            f"perf://obs/{os.path.basename(path)}:{lineno}",
                            "perf-obs-drift",
                            f"epoch {ev.get('epoch')} wire_mb {w} matches "
                            f"no declared figure {sorted(declared)}"))
    if mismatched > 5:
        findings.append(_finding(
            f"perf://obs/{os.path.basename(path)}", "perf-obs-drift",
            f"... and {mismatched - 5} more mismatched epoch(s) "
            f"of {checked}"))
    return findings, {"epochs_checked": checked, "mismatched": mismatched}


def run_perf_audit(root=None, calibration=None, tune_schedule=None,
                   check_obs=None, obs_log=None, progress=None,
                   drift_band: float = DRIFT_BAND) -> dict:
    """All five contracts; returns the JSON-able gate-4 report (same
    shape/exit conventions as the gate-2/3 reports)."""
    from bnsgcn_tpu.analysis.core import resolve_root
    root = resolve_root(root)
    t0 = time.time()
    findings, errors = [], []
    rec_rows, var_rows = [], []
    obs_stats = None

    try:
        calib = C.load_calibration(calibration, root=root)
    except (OSError, ValueError) as ex:
        calib = None
        findings.append(_finding(
            "perf://calibration", "perf-calibration-invalid",
            f"cannot load calibration: {type(ex).__name__}: {ex}"))
    if calib is not None:
        for prob in C.validate_calibration(calib):
            findings.append(_finding("perf://calibration",
                                     "perf-calibration-invalid", prob))
    if calib is not None and not any(
            f.rule == "perf-calibration-invalid" for f in findings):
        f2, rec_rows = check_records(calib, drift_band)
        findings += f2
        findings += check_monotone(calib)
        f4, var_rows, errors = check_variants(
            calib, tune_schedule=tune_schedule, progress=progress)
        findings += f4
    if check_obs:
        try:
            f5, obs_stats = check_obs_log(check_obs)
            findings += f5
        except OSError as ex:
            errors.append(f"check-obs: {ex}")
            findings.append(_finding(
                "perf://obs", "perf-audit-error",
                f"cannot read obs log {check_obs!r}: {ex}"))

    counts: dict = {}
    for f in findings:
        counts[f.rule] = counts.get(f.rule, 0) + 1
    report = {
        "graftperf": 1,
        "root": root,
        "drift_band": drift_band,
        "n_records": len(rec_rows),
        "n_variants": len(var_rows),
        "elapsed_s": round(time.time() - t0, 2),
        "ok": not findings,
        "records": rec_rows,
        "variants": var_rows,
        "obs": obs_stats,
        "findings": [f.as_dict() for f in findings],
        "counts": counts,
        "errors": errors,
    }
    _emit_event(report, obs_log)
    return report


def _emit_event(report: dict, obs_log):
    """Land a `perf_audit` event on the telemetry bus when a log is
    configured (--obs-log or $BNSGCN_OBS_LOG) — same convention as the
    ir/proto audits, so a window's preflight verdicts sit together."""
    path = obs_log or os.environ.get("BNSGCN_OBS_LOG", "")
    if not path:
        return
    from bnsgcn_tpu.obs import EventLog
    EventLog(path).emit(
        "perf_audit", ok=report["ok"], n_records=report["n_records"],
        n_variants=report["n_variants"],
        n_findings=len(report["findings"]), counts=report["counts"],
        elapsed_s=report["elapsed_s"], errors=len(report["errors"]))
