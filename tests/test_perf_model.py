"""graftperf (analysis/perf): roofline model, calibration, gate 4, prior.

  * the numpy halo-geometry mirror is pinned BIT-EQUAL to
    parallel/halo.make_halo_spec / make_refresh_spec / wire_bytes across
    partitions x rates x strategies x codecs x refresh rungs — the one
    contract that lets gate 4 price wire with zero devices;
  * physical orderings (more wire / less coverage / wider rows / coarser
    refresh can never be predicted faster) and the calibration file
    round-trip + one-parameter fit;
  * the bundled v5e table re-predicts the committed round-4 ladder
    within the ±25% gate band, and an injected 2x gather miscalibration
    is CAUGHT by `run_perf_audit` (the gate actually gates);
  * gate 4 runs clean at HEAD in seconds on CPU;
  * `--tune-prior model`: the prior picks the comm-/compute-bound rung,
    `startup_changes` folds it without ever loosening, validation
    rejects the flag outside --tune auto, and the 20-epoch CPU e2e
    reaches a frontier lever state (K <= 2) in strictly fewer retune
    windows than the default ladder — with `--tune auto` (no prior)
    left bitwise on the historical startup path.
"""

import copy
import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

from bnsgcn_tpu.analysis.perf import (AUDIT_N_B, AUDIT_PAD_BOUNDARY,
                                      AUDIT_RATE, AUDIT_WIDTH, DRIFT_BAND,
                                      check_obs_log, run_perf_audit)
from bnsgcn_tpu.analysis.perf import calibration as C
from bnsgcn_tpu.analysis.perf import model as M
from bnsgcn_tpu.config import Config, ConfigError
from bnsgcn_tpu.tune import startup_changes, validate_mode

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# skewed, zero-diagonal boundary tables (the audit matrix + a 2-part and
# an odd 5-part one so padded/shift/ragged all diverge)
N_B_CASES = {
    "p2": np.array([[0, 37], [11, 0]], dtype=np.int64),
    "p4-audit": AUDIT_N_B,
    "p5": np.array([[0, 3, 0, 7, 30],
                    [3, 0, 12, 0, 5],
                    [0, 12, 0, 9, 1],
                    [7, 0, 9, 0, 16],
                    [30, 5, 1, 16, 0]], dtype=np.int64),
}


# ----------------------------------------------------------------------------
# the halo-geometry mirror is bit-equal to parallel/halo.py
# ----------------------------------------------------------------------------

@pytest.mark.quickgate
@pytest.mark.parametrize("case", sorted(N_B_CASES))
@pytest.mark.parametrize("rate", [0.5, 1.0])
def test_exchange_mirror_matches_halo_spec(case, rate):
    from bnsgcn_tpu.parallel import halo
    n_b = N_B_CASES[case]
    pad_b = int(((n_b.max() + 7) // 8) * 8 + 8)
    geom = M.exchange_geometry(n_b, pad_b, rate)
    for strategy in ("padded", "shift", "ragged"):
        spec, _ = halo.make_halo_spec(n_b, 64, pad_b, rate,
                                      strategy=strategy)
        assert geom["n_parts"] == spec.n_parts
        assert geom["pad_send"] == spec.pad_send
        assert geom["shift_pads"] == tuple(spec.shift_pads)
        assert geom["pair_send"] == tuple(map(tuple, spec.pair_send))
        for wire, nb in (("native", 4), ("native", 2), ("bf16", 4),
                         ("int8", 4), ("fp8", 4)):
            spec_w, _ = halo.make_halo_spec(n_b, 64, pad_b, rate,
                                            strategy=strategy, wire=wire)
            assert M.geometry_wire_bytes(geom, strategy, wire, AUDIT_WIDTH,
                                         native_bytes=nb) \
                == halo.wire_bytes(spec_w, AUDIT_WIDTH, native_bytes=nb), \
                (case, rate, strategy, wire, nb)


@pytest.mark.quickgate
@pytest.mark.parametrize("case", sorted(N_B_CASES))
@pytest.mark.parametrize("rate", [0.5, 1.0])
@pytest.mark.parametrize("K", [2, 3, 4])
def test_refresh_mirror_matches_refresh_spec(case, rate, K):
    from bnsgcn_tpu.parallel import halo
    n_b = N_B_CASES[case]
    pad_b = int(((n_b.max() + 7) // 8) * 8 + 8)
    geom = M.refresh_geometry(n_b, pad_b, rate, K)
    for strategy in ("padded", "shift", "ragged"):
        spec, _ = halo.make_refresh_spec(n_b, 64, pad_b, rate, K,
                                         strategy=strategy)
        assert geom["pad_send"] == spec.pad_send, (case, rate, K, strategy)
        assert geom["shift_pads"] == tuple(spec.shift_pads)
        assert geom["pair_send"] == tuple(map(tuple, spec.pair_send))
        assert M.geometry_wire_bytes(geom, strategy, "native", AUDIT_WIDTH) \
            == halo.wire_bytes(spec, AUDIT_WIDTH)


def test_steady_wire_modes():
    kw = dict(strategy="padded", wire="native", width=AUDIT_WIDTH)
    full = M.steady_wire_mb(AUDIT_N_B, AUDIT_PAD_BOUNDARY, AUDIT_RATE, **kw)
    assert M.steady_wire_mb(AUDIT_N_B, AUDIT_PAD_BOUNDARY, AUDIT_RATE,
                            mode="grad-only", **kw) == 0.0
    # K=1 steady state IS the full exchange
    assert M.steady_wire_mb(AUDIT_N_B, AUDIT_PAD_BOUNDARY, AUDIT_RATE,
                            refresh=1, **kw) == full
    assert 0 < M.steady_wire_mb(AUDIT_N_B, AUDIT_PAD_BOUNDARY, AUDIT_RATE,
                                refresh=4, **kw) < full


# ----------------------------------------------------------------------------
# physical orderings
# ----------------------------------------------------------------------------

def _table():
    return C.backend_table(C.default_calibration(), "tpu-v5e")


def _feat(**kw):
    base = dict(n_edges=50e6, coverage=0.6, fill=0.74, dense_tiles=4096,
                row_bytes=512, n_apps=6)
    base.update(kw)
    return M.hybrid_features(**base)


def test_monotone_wire_coverage_rows():
    t = _table()
    assert M.predict_step_s(_feat(wire_mb=20.0), t) \
        > M.predict_step_s(_feat(wire_mb=10.0), t)
    assert M.predict_step_s(_feat(coverage=0.8), t) \
        < M.predict_step_s(_feat(coverage=0.4), t)
    rates = [M.gather_rows_per_s(t, rb)
             for rb in (16, 32, 64, 128, 256, 384, 512, 1024, 2048, 8192)]
    assert all(b <= a * (1 + 1e-9) for a, b in zip(rates, rates[1:]))
    # interpolation pins the measured points exactly
    for k, v in t["gather_rows_per_s"].items():
        assert M.gather_rows_per_s(t, int(k)) == pytest.approx(float(v))


def test_monotone_refresh_and_codecs():
    mbs = [M.steady_wire_mb(AUDIT_N_B, AUDIT_PAD_BOUNDARY, AUDIT_RATE,
                            strategy="padded", wire="native", refresh=k,
                            width=AUDIT_WIDTH) for k in (1, 2, 3, 4, 8)]
    assert all(b <= a * (1 + 1e-9) for a, b in zip(mbs, mbs[1:]))
    for strategy in ("padded", "shift", "ragged"):
        by = {w: M.steady_wire_mb(AUDIT_N_B, AUDIT_PAD_BOUNDARY, AUDIT_RATE,
                                  strategy=strategy, wire=w,
                                  width=AUDIT_WIDTH)
              for w in ("int8", "fp8", "bf16", "native")}
        assert by["int8"] == by["fp8"] <= by["bf16"] <= by["native"]
        # ragged ships exact rows; padded ships the padded buffer
        assert M.steady_wire_mb(
            AUDIT_N_B, AUDIT_PAD_BOUNDARY, AUDIT_RATE, strategy="ragged",
            wire="native", width=AUDIT_WIDTH) <= M.steady_wire_mb(
            AUDIT_N_B, AUDIT_PAD_BOUNDARY, AUDIT_RATE, strategy="padded",
            wire="native", width=AUDIT_WIDTH)


# ----------------------------------------------------------------------------
# calibration: round-trip, fit, ladder pin, miscalibration caught
# ----------------------------------------------------------------------------

def test_calibration_roundtrip_and_bundled_file(tmp_path):
    calib = C.default_calibration()
    assert C.validate_calibration(calib) == []
    p = str(tmp_path / "cal.json")
    C.save_calibration(calib, p)
    assert C.load_calibration(p) == json.loads(json.dumps(calib))
    # the committed file IS the bundled default, serialized
    committed = C.load_calibration(root=REPO)
    assert committed == json.loads(json.dumps(calib)), \
        "tools/perf_calibration.json drifted from default_calibration()"
    # dict sources are deep-copied: mutating the load must not leak back
    src = C.default_calibration()
    loaded = C.load_calibration(src)
    loaded["backends"]["tpu-v5e"]["link_GBps"] = 1.0
    assert src["backends"]["tpu-v5e"]["link_GBps"] != 1.0


def test_backend_table_keys_on_device_kind():
    """A device gets the table calibrated for ITS kind (the string
    `jax.devices()[0].device_kind` reports) — never the first tpu-* table
    because its backend is 'tpu'. An unknown kind is a KeyError."""
    calib = C.default_calibration()
    assert C.backend_table(calib, "TPU v5 lite") is calib["backends"]["tpu-v5e"]
    assert C.backend_table(calib, "cpu") is calib["backends"]["cpu"]
    assert C.backend_table(calib, "tpu-v5e") is calib["backends"]["tpu-v5e"]
    for kind in ("tpu", "TPU v4", "TPU v5", "TPU v6 lite"):
        with pytest.raises(KeyError, match="no calibration table"):
            C.backend_table(calib, kind)


def test_validate_calibration_flags_problems():
    calib = C.default_calibration()
    calib["backends"]["tpu-v5e"]["gather_rows_per_s"]["-4"] = 1e6
    calib["records"][0]["backend"] = "nonexistent"
    calib["records"][1]["measured_s"] = 0.0
    probs = C.validate_calibration(calib)
    assert len(probs) >= 3
    assert C.validate_calibration({"nope": 1})


def test_fit_scale_median():
    t = _table()
    feat = _feat()
    raw = M.predict_step_s(feat, dict(t, calib_scale=1.0, fixed_step_s=0.0))
    fitted = M.fit_scale([(feat, 2.0 * raw), (feat, 2.2 * raw),
                          (feat, 50.0 * raw)], t)
    # median, not mean: the 50x compile-tail outlier must not drag it
    assert fitted["calib_scale"] == pytest.approx(2.2)
    assert M.predict_step_s(feat, fitted) == pytest.approx(2.2 * raw)
    with pytest.raises(ValueError):
        M.fit_scale([], t)


@pytest.mark.quickgate
def test_bundled_ladder_within_band():
    """The v5e table re-predicts the committed round-4 ladder
    (1.672 / 0.87 / 0.667 / 0.5715 s/epoch) within the gate band."""
    calib = C.default_calibration()
    assert len(calib["records"]) == 4
    for rec in calib["records"]:
        table = calib["backends"][rec["backend"]]
        pred = M.predict_step_s(C.record_features(rec), table)
        d = M.drift(pred, rec["measured_s"])
        assert abs(d) <= DRIFT_BAND, \
            f"{rec['name']}: predicted {pred:.4f} vs {rec['measured_s']} " \
            f"({d:+.1%} outside ±{DRIFT_BAND:.0%})"


def test_injected_miscalibration_is_caught():
    """Double the v5e gather rates: every record's prediction halves its
    gather term and the ladder re-prediction leaves the band — gate 4
    must FAIL, not shrug."""
    calib = C.default_calibration()
    bad = copy.deepcopy(calib)
    tb = bad["backends"]["tpu-v5e"]
    tb["gather_rows_per_s"] = {k: 2.0 * float(v)
                               for k, v in tb["gather_rows_per_s"].items()}
    report = run_perf_audit(root=REPO, calibration=bad)
    drifted = [f for f in report["findings"]
               if f["rule"] == "perf-model-drift"]
    assert drifted and not report["ok"]
    # the gather-dominated cells name the drift direction
    assert any("-" in f["message"] for f in drifted)
    # sanity: the unmutated tables pass the same audit
    assert run_perf_audit(root=REPO, calibration=calib)["ok"]


# ----------------------------------------------------------------------------
# gate 4 at HEAD
# ----------------------------------------------------------------------------

@pytest.mark.quickgate
def test_gate4_clean_at_head():
    report = run_perf_audit(root=REPO)
    assert report["ok"], report["findings"]
    assert report["errors"] == []
    assert report["n_records"] == 4
    assert report["n_variants"] > 40
    assert report["elapsed_s"] < 30.0       # "seconds, zero devices"


def test_gate4_cli_subprocess(tmp_path):
    out = str(tmp_path / "perf_report.json")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    r = subprocess.run(
        [sys.executable, "-m", "bnsgcn_tpu.analysis", "perf", "-q",
         "--json", out], capture_output=True, text=True, timeout=300,
        cwd=REPO, env=env)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "graftperf: clean" in r.stderr
    rep = json.load(open(out))
    assert rep["ok"] and rep["graftperf"] == 1


def test_check_obs_log_drift(tmp_path):
    p = str(tmp_path / "obs.jsonl")
    with open(p, "w") as f:
        f.write(json.dumps({"kind": "run_header",
                            "wire_mb_per_exchange": 1.5,
                            "wire_mb_steady": 0.75}) + "\n")
        f.write(json.dumps({"kind": "epoch", "epoch": 0, "loss": 1.0,
                            "wire_mb": 1.5}) + "\n")
        f.write(json.dumps({"kind": "epoch", "epoch": 1, "loss": 0.9,
                            "wire_mb": 0.75}) + "\n")
        f.write(json.dumps({"kind": "epoch", "epoch": 2, "loss": 0.8,
                            "wire_mb": 0.0}) + "\n")
    findings, stats = check_obs_log(p)
    assert findings == [] and stats["epochs_checked"] == 3
    with open(p, "a") as f:
        f.write(json.dumps({"kind": "epoch", "epoch": 3, "loss": 0.7,
                            "wire_mb": 0.33}) + "\n")
    findings, stats = check_obs_log(p)
    assert [f.rule for f in findings] == ["perf-obs-drift"]
    assert stats["mismatched"] == 1


# ----------------------------------------------------------------------------
# --tune-prior model: prior units + config surface
# ----------------------------------------------------------------------------

def test_model_prior_picks_rung_by_comm_fraction():
    t = _table()
    compute_bound = M.model_prior(_feat(wire_mb=0.01), t)
    assert compute_bound["halo_refresh"] == 2
    assert compute_bound["comm_frac"] < 0.30
    assert "compute-bound" in compute_bound["why"]
    # wire the step until the model calls it comm-bound
    comm_bound = M.model_prior(_feat(wire_mb=1e5), t)
    assert comm_bound["halo_refresh"] == 4
    assert comm_bound["comm_frac"] >= 0.30
    assert "comm-bound" in comm_bound["why"]
    # scaled_features changes only the wire term
    a, b = _feat(wire_mb=1.0), M.scaled_features(_feat(wire_mb=1.0),
                                                 wire_mb=2.0)
    pa, pb = M.predict_parts(a, t), M.predict_parts(b, t)
    assert pb["wire_s"] == pytest.approx(2 * pa["wire_s"])
    assert pb["gather_s"] == pa["gather_s"] and pb["dense_s"] == pa["dense_s"]


def test_startup_changes_folds_prior_and_never_loosens():
    prior = {"halo_refresh": 2, "why": "model-prior: test"}
    cfg = Config(tune="auto")
    ch, why = startup_changes(cfg, prior=prior)
    assert ch == {"halo_refresh": 2} and "model-prior" in why
    # positional/backward-compatible default: the ladder K=4 start
    ch, why = startup_changes(cfg)
    assert ch == {"halo_refresh": 4} and "coarse staleness" in why
    # never loosens: a user who launched at K=4 keeps it against a K=2 pick
    ch, _ = startup_changes(Config(tune="auto", halo_refresh=4), prior=prior)
    assert ch == {}
    # grad-only launches are left alone entirely
    ch, _ = startup_changes(Config(tune="auto", halo_mode="grad-only"),
                            prior=prior)
    assert ch == {}


def test_validate_mode_tune_prior_surface():
    validate_mode(Config(tune="auto", tune_prior="model"))
    validate_mode(Config(tune="auto", tune_prior="ladder"))
    validate_mode(Config(tune="off", tune_prior="ladder"))
    with pytest.raises(ConfigError):
        validate_mode(Config(tune="off", tune_prior="model"))
    with pytest.raises(ConfigError):
        validate_mode(Config(tune="schedule", tune_schedule="K=2@3",
                             tune_prior="model"))
    with pytest.raises(ConfigError):
        validate_mode(Config(tune="auto", tune_prior="oracle"))


def test_run_features_from_artifacts():
    """run_features prices a run from (cfg, art) alone — numpy stand-in
    artifact, no partition build needed."""
    class Art:
        n_b = AUDIT_N_B
        pad_boundary = AUDIT_PAD_BOUNDARY
        pad_edges = 12345
        ell_geometry = {"fwd": {"widths": [4, 16], "rows": [100, 10]},
                        "bwd": {"widths": [4, 16], "rows": [120, 8]}}
    cfg = Config(n_layers=2, n_hidden=8, sampling_rate=0.5, dtype="float32")
    feat = M.run_features(cfg, Art(), strategy="padded")
    assert feat.n_apps == 4 and feat.row_bytes == 32
    fwd = 4 * 100 + 16 * 10
    bwd = 4 * 120 + 16 * 8
    assert feat.gather_slots == pytest.approx(0.5 * (fwd + bwd))
    geom = M.exchange_geometry(AUDIT_N_B, AUDIT_PAD_BOUNDARY, 0.5)
    per_ex = M.geometry_wire_bytes(geom, "padded", "native", 8, 4) / 1e6
    assert feat.wire_mb == pytest.approx(per_ex * 2)   # 2*(L-1) exchanges
    # without stored geometry the padded edge count stands in
    class Bare(Art):
        ell_geometry = None
    assert M.run_features(cfg, Bare(), strategy="padded").gather_slots \
        == 12345


# ----------------------------------------------------------------------------
# e2e: --tune-prior model beats the ladder to the frontier rung (CPU)
# ----------------------------------------------------------------------------

BASE_ARGS = [
    "--dataset", "sbm", "--partition-method", "random", "--n-partitions", "2",
    "--model", "graphsage", "--n-layers", "2", "--n-hidden", "8",
    "--sampling-rate", "0.5", "--use-pp", "--n-epochs", "20",
    "--log-every", "2", "--no-eval", "--no-comm-trace",
    "--fix-seed", "--seed", "11",
]


def _env():
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               BNSGCN_RETRY_BACKOFF_S="0", PYTHONPATH=REPO)
    return env


def _run(tmp_path, tag, extra_args=(), timeout=420):
    cmd = ([sys.executable, "-m", "bnsgcn_tpu.main"] + BASE_ARGS
           + ["--part-path", str(tmp_path / f"parts_{tag}"),
              "--ckpt-path", str(tmp_path / f"ckpt_{tag}"),
              "--results-path", str(tmp_path / f"res_{tag}"),
              "--obs-log", str(tmp_path / f"obs_{tag}.jsonl")]
           + list(extra_args))
    r = subprocess.run(cmd, capture_output=True, text=True, timeout=timeout,
                       cwd=REPO, env=_env())
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-2000:])
    return r


def _tune_trail(path):
    from bnsgcn_tpu.obs import load_events
    return [(int(e["epoch"]), dict(e.get("changes") or {}), e["reason"])
            for e in load_events(str(path)) if e["kind"] == "tune_decision"]


def _windows_to_frontier(trail):
    """Retune windows (post-startup applied decisions) until the lever
    state first sits at halo_refresh <= 2. The startup fold is window 0;
    never reaching the frontier counts every window plus one."""
    k = 1
    for i, (_, changes, _) in enumerate(trail):
        k = int(changes.get("halo_refresh", k))
        if k <= 2:
            return max(i, 0)        # i==0: the startup fold itself
    return len(trail) + 1


@pytest.mark.quickgate
def test_e2e_model_prior_beats_ladder_to_frontier(tmp_path):
    r_model = _run(tmp_path, "model",
                   ["--tune", "auto", "--tune-prior", "model"])
    r_ladder = _run(tmp_path, "ladder", ["--tune", "auto"])

    # the model run logged its prediction before the first compile
    assert "[tune] prior: predicted step" in r_model.stdout + r_model.stderr

    tm = _tune_trail(tmp_path / "obs_model.jsonl")
    tl = _tune_trail(tmp_path / "obs_ladder.jsonl")
    assert tm and tm[0][0] == 0 and "model-prior" in tm[0][2]
    assert tm[0][1].get("halo_refresh") == 2, tm
    assert tl and tl[0][0] == 0 and tl[0][1].get("halo_refresh") == 4, tl

    wm, wl = _windows_to_frontier(tm), _windows_to_frontier(tl)
    assert wm == 0, tm
    assert wm < wl, (tm, tl)

    # gate 4's obs contract holds on both live logs: every epoch wire_mb
    # is a declared figure
    for tag in ("model", "ladder"):
        findings, stats = check_obs_log(str(tmp_path / f"obs_{tag}.jsonl"))
        assert findings == [] and stats["epochs_checked"] > 0, (tag, findings)


@pytest.mark.quickgate
def test_e2e_auto_without_prior_unchanged(tmp_path):
    """`--tune auto` with the default --tune-prior walks the historical
    ladder startup — same fold, same reason string — so the pinned
    no-prior trajectory is untouched by this PR."""
    r = _run(tmp_path, "plain", ["--tune", "auto", "--n-epochs", "4"],
             timeout=300)
    trail = _tune_trail(tmp_path / "obs_plain.jsonl")
    assert trail and trail[0][0] == 0
    assert trail[0][1] == {"halo_refresh": 4}
    assert "coarse staleness" in trail[0][2]
    assert "[tune] prior:" not in r.stdout + r.stderr


def test_cpu_obs_history_self_calibration(tmp_path):
    """The calibration workflow the cpu table's `calibrated: false`
    points at: fit `calib_scale` from a live run's obs epoch records,
    then the fitted table re-predicts those records inside the gate
    band (median residual 0 by construction of the median fit; the
    band absorbs epoch-to-epoch CPU noise)."""
    _run(tmp_path, "cal", ["--halo-refresh", "2"], timeout=300)
    from bnsgcn_tpu.obs import load_events
    evs = load_events(str(tmp_path / "obs_cal.jsonl"))
    epochs = [e for e in evs if e["kind"] == "epoch"
              and isinstance(e.get("step_s"), (int, float))]
    assert len(epochs) >= 3
    steady = epochs[1:]                    # epoch 0 carries the compile
    table = C.backend_table(C.default_calibration(), "cpu")
    feat = M.StepFeatures(n_apps=4, gather_slots=2e4, row_bytes=32,
                          gather_path="materialize",
                          wire_mb=float(np.median(
                              [e.get("wire_mb", 0.0) for e in steady])))
    pairs = [(feat, float(e["step_s"])) for e in steady]
    fitted = M.fit_scale(pairs, table)
    resids = [M.drift(M.predict_step_s(feat, fitted), m) for _, m in pairs]
    assert float(np.median(np.abs(resids))) <= DRIFT_BAND
    # and at least the median epoch is matched essentially exactly
    assert min(abs(r) for r in resids) <= 0.05
