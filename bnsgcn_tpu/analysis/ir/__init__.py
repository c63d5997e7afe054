"""graftlint-ir: jaxpr-level contract verification of the real programs.

The AST tier (`bnsgcn_tpu.analysis` rules_*) proves source-level hazards
absent; this tier abstractly TRACES the actual step/eval/exchange
programs — `build_step_fns` under a host-only ``AbstractMesh`` (no
devices, no FLOPs, no data) — and verifies, for every cell of the
strategy x wire x overlap x refresh x tune-target matrix:

1. **rank symmetry** — the ordered collective schedule contains no
   ``axis_index_groups`` sub-grouping and no collective under a
   rank-predicated branch; tune-reachable states also retrace
   deterministically (the schedule is a pure function of the lever state,
   so a mid-run retune lands every rank in the same program);
2. **donation** — every ``donate_argnums`` buffer aliases an output in
   the lowered StableHLO (no dead donations), plus a peak-live-bytes
   estimate per program;
3. **wire bytes** — the payload the traced exchange collectives move
   equals `halo.traced_wire_bytes`'s claim (the run-header / tuner
   number); grad-only steps trace zero forward-halo payload;
4. **transfers** — no `strict.TRANSFER_PRIMITIVES` device<->host
   primitive inside any traced program.

Entry points: ``run_ir_audit`` (library), ``python -m
bnsgcn_tpu.analysis ir`` (CLI, see __main__), `tools/lint.sh` gate 2.
"""

from __future__ import annotations

import os
import time

from bnsgcn_tpu.analysis.ir.variants import Variant, enumerate_variants

# The audit geometry: small enough to trace a ~60-cell matrix in ~1 min,
# large enough that every strategy pads/shifts/packs non-trivially.
AUDIT_PARTS = 4
AUDIT_NODES = 96
AUDIT_FEAT = 6
AUDIT_HIDDEN = 8
AUDIT_RATE = 0.5


def _aval(v):
    import jax
    import numpy as np
    v = np.asarray(v)
    return jax.ShapeDtypeStruct(v.shape, v.dtype)


def build_audit_inputs():
    """The one tiny synthetic graph + partition every variant traces."""
    from bnsgcn_tpu.data.artifacts import build_artifacts
    from bnsgcn_tpu.data.graph import synthetic_graph
    from bnsgcn_tpu.data.partitioner import partition_graph
    g = synthetic_graph(n_nodes=AUDIT_NODES, avg_degree=5,
                        n_feat=AUDIT_FEAT, seed=3)
    pid = partition_graph(g, AUDIT_PARTS, method="random", seed=0)
    return g, build_artifacts(g, pid)


def audit_config(g, variant: Variant):
    from bnsgcn_tpu.config import Config
    return Config(model="graphsage", dropout=0.0, use_pp=False,
                  norm="layer", n_train=g.n_train, lr=0.01,
                  sampling_rate=AUDIT_RATE, spmm="ell",
                  n_hidden=AUDIT_HIDDEN,
                  halo_exchange=variant.strategy, halo_wire=variant.wire,
                  halo_refresh=variant.refresh, halo_mode=variant.mode,
                  overlap=variant.overlap,
                  n_partitions=AUDIT_PARTS, n_feat=g.n_feat,
                  n_class=g.n_class)


def trace_variant(variant: Variant, g, art, full_set: bool = False,
                  slot_map=None) -> dict:
    """Trace one variant cell. Returns {program name -> TracedProgram}
    plus '_oracle' entries the wire contract compares against. With
    `full_set`, also traces the lever-independent eval/forward/precompute
    programs (done for one cell only — they do not vary with the halo
    levers). `slot_map` threads an elastic part -> slot hosting map into
    the HaloSpec (the slot-invariance audit re-traces under it)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import AbstractMesh

    from bnsgcn_tpu.analysis.ir import trace as T
    from bnsgcn_tpu.models.gnn import ModelSpec
    from bnsgcn_tpu.parallel.halo import make_refresh_spec, traced_wire_bytes
    from bnsgcn_tpu.trainer import abstract_step_inputs, build_step_fns

    cfg = audit_config(g, variant)
    spec = ModelSpec(cfg.model, (g.n_feat, AUDIT_HIDDEN, g.n_class),
                     norm="layer", dropout=0.0, train_size=g.n_train)
    mesh = AbstractMesh((AUDIT_PARTS,), ("parts",))
    fns, hspec, tables, tables_full = build_step_fns(cfg, spec, art, mesh,
                                                     slot_map=slot_map)
    inp = abstract_step_inputs(cfg, spec, art, fns, tables)
    p, s, o = inp["params"], inp["state"], inp["opt_state"]
    e, blk, tb, key = inp["epoch"], inp["blk"], inp["tables"], inp["key"]

    width = AUDIT_HIDDEN          # hid_w at feat=1 (run.py's wire width)
    nb = 2 if cfg.dtype == "bfloat16" else 4
    out: dict = {}
    out["train_step"] = T.trace_jitted(
        "train_step", fns.train_step, p, s, o, e, blk, tb, key, key)
    if variant.mode != "grad-only":
        out["exchange_only"] = T.trace_program(
            "exchange_only",
            lambda b, t, ep, k: fns.exchange_only(b, t, ep, k, width=width),
            blk, tb, e, key)
        out["_oracle:exchange_only"] = traced_wire_bytes(hspec, width, nb)

    if fns.train_step_full is not None:
        tbr = {k: _aval(v) for k, v in fns.tables_refresh.items()}
        out["train_step_full"] = T.trace_jitted(
            "train_step_full", fns.train_step_full,
            p, s, o, e, blk, tb, key, key)
        cache = jax.eval_shape(fns.train_step_full,
                               p, s, o, e, blk, tb, key, key)[4]
        out["train_step_cached"] = T.trace_jitted(
            "train_step_cached", fns.train_step_cached,
            p, s, o, e, blk, tbr, cache, key, key)
        out["exchange_only_refresh"] = T.trace_program(
            "exchange_only_refresh",
            lambda b, t, ep, k: fns.exchange_only_refresh(
                b, t, ep, k, width=width),
            blk, tbr, e, key)
        hspec_r, _ = make_refresh_spec(
            art.n_b, art.pad_inner, art.pad_boundary, cfg.sampling_rate,
            variant.refresh, strategy=variant.strategy, wire=variant.wire)
        out["_oracle:exchange_only_refresh"] = traced_wire_bytes(
            hspec_r, width, nb)

    if full_set:
        out["forward"] = T.trace_program(
            "forward", fns.forward, p, s, e, blk, tb, key, key)
        tbf = {k: _aval(v) for k, v in tables_full.items()}
        out["eval_forward"] = T.trace_program(
            "eval_forward", fns.eval_forward, p, s, blk, tbf)
        out["precompute"] = T.trace_program(
            "precompute", fns.precompute, blk, tbf)
    out["_width"] = width
    return out


def check_variant(variant: Variant, traced: dict) -> list:
    """All four contracts over one traced cell."""
    from bnsgcn_tpu.analysis.ir import contracts as C
    width = traced["_width"]
    findings = []
    for name, tp in traced.items():
        if name.startswith("_"):
            continue
        where = f"ir://{variant.key}#{name}"
        findings += C.check_rank_symmetry(tp, where)
        findings += C.check_transfers(tp, where)
        findings += C.check_donation(tp, where)
        oracle = traced.get(f"_oracle:{name}")
        if oracle is not None:
            findings += C.check_wire(tp, width, oracle, where)
    if variant.mode == "grad-only":
        where = f"ir://{variant.key}#train_step"
        findings += C.check_no_payload(traced["train_step"], width, where)
    return findings


def run_ir_audit(root: str | None = None, tune_schedule: str | None = None,
                 max_variants: int | None = None, obs_log: str | None = None,
                 progress=None) -> dict:
    """Trace + check the full variant matrix; returns the JSON-able report
    (schema documented in README 'Static analysis & strict execution').

    Tune-sourced variants are additionally traced TWICE and their
    collective schedules compared — the retune determinism half of
    contract 1 (`contracts.check_schedule_match`)."""
    from bnsgcn_tpu.analysis.core import resolve_root
    from bnsgcn_tpu.analysis.ir import contracts as C

    root = resolve_root(root)
    t0 = time.time()
    variants = enumerate_variants(tune_schedule=tune_schedule)
    dropped = 0
    if max_variants is not None and len(variants) > max_variants:
        dropped = len(variants) - max_variants
        variants = variants[:max_variants]
    g, art = build_audit_inputs()

    findings: list = []
    rows: list = []
    errors: list = []
    for i, v in enumerate(variants):
        if progress is not None:
            progress(f"[ir] {i + 1}/{len(variants)} {v.key} ({v.source})")
        try:
            traced = trace_variant(v, g, art, full_set=(i == 0))
            vf = check_variant(v, traced)
            if v.source == "tune":
                again = trace_variant(v, g, art)
                for name in ("train_step",):
                    if name in traced and name in again:
                        vf += C.check_schedule_match(
                            traced[name], again[name],
                            f"ir://{v.key}#{name}", what="tune retrace")
            findings += vf
            rows.append(_row(v, traced, vf))
        except Exception as ex:  # attribute, keep auditing other cells
            from bnsgcn_tpu.analysis.core import Finding
            errors.append(f"{v.key}: {type(ex).__name__}: {ex}")
            findings.append(Finding(
                file=f"ir://{v.key}", line=0, col=0, rule="ir-trace-error",
                message=f"variant failed to trace: "
                        f"{type(ex).__name__}: {ex}"))

    # ---- elastic slot-map invariance (run.py --elastic on): a RESIZE
    # re-hosts whole parts onto fewer workers via mesh.plan_slots, but the
    # traced step program keeps the full P-wide 'parts' axis regardless —
    # HaloSpec.slot_map is host-side metadata only. Re-trace the baseline
    # cell under the part -> slot maps of two world sizes and prove (a)
    # the collective schedule is IDENTICAL to the unmapped program and
    # (b) the mapped program is itself rank-symmetric — together: every
    # survivor of a resize compiles the same schedule it always ran. ----
    slot_rows: list = []
    if variants:
        from bnsgcn_tpu.parallel.mesh import plan_slots
        try:
            base_v = variants[0]
            base = trace_variant(base_v, g, art)
            for world in (2, AUDIT_PARTS):
                if progress is not None:
                    progress(f"[ir] slot map W={world} {base_v.key}")
                sm = plan_slots(AUDIT_PARTS, world)
                mapped = trace_variant(base_v, g, art, slot_map=sm)
                where = f"ir://{base_v.key}#slot-w{world}"
                sf = C.check_schedule_match(
                    mapped["train_step"], base["train_step"], where,
                    what=f"slot-map W={world} retrace")
                sf += C.check_rank_symmetry(mapped["train_step"], where)
                findings += sf
                slot_rows.append({
                    "world": world, "slot_map": list(sm),
                    "findings": len(sf),
                    "collectives": len(mapped["train_step"].collectives)})
        except Exception as ex:
            from bnsgcn_tpu.analysis.core import Finding
            errors.append(f"slot-map: {type(ex).__name__}: {ex}")
            findings.append(Finding(
                file="ir://slot-map", line=0, col=0, rule="ir-trace-error",
                message=f"slot-map retrace failed: "
                        f"{type(ex).__name__}: {ex}"))

    counts: dict = {}
    for f in findings:
        counts[f.rule] = counts.get(f.rule, 0) + 1
    report = {
        "graftlint_ir": 1,
        "root": root,
        "n_parts": AUDIT_PARTS,
        "n_variants": len(variants),
        "variants_dropped": dropped,
        "elapsed_s": round(time.time() - t0, 2),
        "ok": not findings,
        "slot_worlds": slot_rows,
        "variants": rows,
        "findings": [f.as_dict() for f in findings],
        "counts": counts,
        "errors": errors,
    }
    _emit_event(report, obs_log)
    return report


def _row(v: Variant, traced: dict, vf: list) -> dict:
    from bnsgcn_tpu.analysis.ir.trace import payload_wire_bytes
    width = traced["_width"]
    programs = {}
    for name, tp in traced.items():
        if name.startswith("_"):
            continue
        d = {
            "collectives": len(tp.collectives),
            "peak_live_bytes": tp.peak_live_bytes,
        }
        if tp.donation is not None:
            d["donated"] = list(tp.donation.donated)
            d["dead_donations"] = list(tp.donation.dead)
        oracle = traced.get(f"_oracle:{name}")
        if oracle is not None:
            d["wire_bytes"] = {"traced": payload_wire_bytes(tp, width),
                               "oracle": oracle}
        programs[name] = d
    return {"key": v.key, "source": v.source, "findings": len(vf),
            "programs": programs}


def _emit_event(report: dict, obs_log: str | None):
    """Land an `ir_audit` event on the telemetry bus when a log is
    configured (--obs-log or $BNSGCN_OBS_LOG) — a pod run's preflight
    verdict then sits next to the run it gated."""
    path = obs_log or os.environ.get("BNSGCN_OBS_LOG", "")
    if not path:
        return
    from bnsgcn_tpu.obs import EventLog
    EventLog(path).emit(
        "ir_audit", ok=report["ok"], n_variants=report["n_variants"],
        n_findings=len(report["findings"]), counts=report["counts"],
        elapsed_s=report["elapsed_s"], errors=len(report["errors"]))
