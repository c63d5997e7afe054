"""graftlint-ir (bnsgcn_tpu/analysis/ir/): jaxpr-level contract audit.

Seeded-violation fixtures per contract — each checker MUST fire on a
hand-built program carrying exactly that violation (rank-asymmetric
collective, dead donation, wire-byte mismatch, hidden transfer), fed
through the same trace_program/trace_jitted entry points the real
variant runner uses — plus unit coverage for the variant enumeration,
`tune.reachable_lever_states`, `run.step_variants`,
`halo.traced_wire_bytes`, the repo-level checks (tune-schedule grammar
lint, README knob-table drift, suppression staleness), and the
quickgate clean-at-HEAD gate: `python -m bnsgcn_tpu.analysis ir` over
the full strategy x wire x overlap x refresh x tune-target matrix on
CPU with zero findings.
"""

import json
import os
import subprocess
import sys
import textwrap
from functools import partial
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from bnsgcn_tpu.analysis.ir import contracts as C
from bnsgcn_tpu.analysis.ir import trace as T
from bnsgcn_tpu.analysis.ir.variants import enumerate_variants
from jax import shard_map

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

MESH = AbstractMesh((4,), ("parts",))
AVAL = jax.ShapeDtypeStruct((4, 8), jnp.float32)


def _rules(findings):
    return sorted(f.rule for f in findings)


# ----------------------------------------------------------------------------
# contract 1: rank symmetry (seeded violations)
# ----------------------------------------------------------------------------

def test_rank_branched_collective_fires():
    def local(x):
        r = jax.lax.axis_index("parts")

        def yes(v):
            # cast back to varying: cond branches must agree on vma
            return jax.lax.pcast(jax.lax.psum(v, "parts"), "parts",
                                 to="varying")

        return jax.lax.cond(r == 0, yes, lambda v: v, x)

    f = shard_map(local, mesh=MESH, in_specs=P("parts"),
                  out_specs=P("parts"))
    tp = T.trace_program("fix", f, AVAL)
    found = C.check_rank_symmetry(tp, "ir://fix#prog")
    assert "ir-rank-asymmetry" in _rules(found)
    assert any("cond/switch" in f.message for f in found)
    assert all(f.file == "ir://fix#prog" for f in found)


def test_axis_index_groups_fires():
    def local(x):
        return jax.lax.all_gather(x, "parts",
                                  axis_index_groups=[[0, 1], [2, 3]])

    f = shard_map(local, mesh=MESH, in_specs=P("parts"),
                  out_specs=P(None, "parts"))
    tp = T.trace_program("fix", f, AVAL)
    found = C.check_rank_symmetry(tp, "ir://fix#prog")
    assert "ir-rank-asymmetry" in _rules(found)
    assert any("axis_index_groups" in f.message for f in found)


def test_symmetric_collective_is_clean():
    def local(x):
        return jax.lax.psum(x, "parts")

    f = shard_map(local, mesh=MESH, in_specs=P("parts"),
                  out_specs=P("parts"))
    tp = T.trace_program("ok", f, AVAL)
    assert C.check_rank_symmetry(tp, "ir://ok#prog") == []
    assert len(tp.collectives) >= 1
    assert tp.collectives[0].axes == ("parts",)


def test_schedule_match_flags_divergence():
    def mk(name, shapes):
        return T.TracedProgram(name=name, collectives=[
            T.Collective("all_to_all", ("parts",), s, "float32", False,
                         (), False) for s in shapes])

    a = mk("launch", [(16, 8), (4, 8)])
    b = mk("retuned", [(16, 8), (8, 8)])
    found = C.check_schedule_match(a, b, "ir://x#train_step")
    assert _rules(found) == ["ir-rank-asymmetry"]
    assert "divergence at collective #1" in found[0].message
    assert C.check_schedule_match(a, mk("again", [(16, 8), (4, 8)]),
                                  "ir://x#train_step") == []


# ----------------------------------------------------------------------------
# contract 2: donation (seeded violation)
# ----------------------------------------------------------------------------

def test_dead_donation_fires():
    @partial(jax.jit, donate_argnums=(0, 1))
    def f(a, b):
        return a + 1.0      # b donated but unused: pruned, never aliased

    tp = T.trace_jitted("fix", f, AVAL, AVAL)
    found = C.check_donation(tp, "ir://fix#prog")
    assert _rules(found) == ["ir-dead-donation"]
    assert tp.donation.dead == (1,)
    assert 0 in tp.donation.aliased     # the live donation still aliases


def test_live_donation_is_clean():
    @partial(jax.jit, donate_argnums=(0,))
    def f(a, b):
        return a + b

    tp = T.trace_jitted("ok", f, AVAL, AVAL)
    assert C.check_donation(tp, "ir://ok#prog") == []
    assert tp.donation.donated == (0,) and tp.donation.dead == ()


def test_peak_live_bytes_positive():
    tp = T.trace_program("p", lambda a, b: a @ b.T + 1.0, AVAL, AVAL)
    # two 4x8 f32 inputs live at once -> at least 256 B
    assert tp.peak_live_bytes >= 2 * 4 * 8 * 4


# ----------------------------------------------------------------------------
# contract 3: wire bytes (seeded mismatch + oracle unit)
# ----------------------------------------------------------------------------

def _exchange_tp(width=8):
    def local(x):
        return jax.lax.all_to_all(x, "parts", 0, 0, tiled=True)

    f = shard_map(local, mesh=MESH, in_specs=P("parts"), out_specs=P("parts"))
    return T.trace_program("exch", f,
                           jax.ShapeDtypeStruct((16, width), jnp.float32))


def test_wire_drift_fires_on_mismatched_oracle():
    tp = _exchange_tp()
    traced = T.payload_wire_bytes(tp, 8)
    assert traced == 4 * 8 * 4
    found = C.check_wire(tp, 8, traced + 64, "ir://fix#exchange_only")
    assert _rules(found) == ["ir-wire-drift"]
    assert str(traced) in found[0].message
    assert C.check_wire(tp, 8, traced, "ir://fix#exchange_only") == []


def test_no_payload_fires_on_forward_exchange():
    tp = _exchange_tp()
    found = C.check_no_payload(tp, 8, "ir://fix#train_step")
    assert _rules(found) == ["ir-wire-drift"]
    assert "grad-only" in found[0].message


def test_payload_excludes_scale_hops():
    # a [4,1] scale all_to_all (last dim 1) must not count toward the
    # width-8 payload — the quantized-wire accounting convention
    def local(x, s):
        a = jax.lax.all_to_all(x, "parts", 0, 0, tiled=True)
        b = jax.lax.all_to_all(s, "parts", 0, 0, tiled=True)
        return a, b

    f = shard_map(local, mesh=MESH, in_specs=(P("parts"), P("parts")),
                  out_specs=(P("parts"), P("parts")))
    tp = T.trace_program("q", f, jax.ShapeDtypeStruct((16, 8), jnp.int8),
                         jax.ShapeDtypeStruct((16, 1), jnp.float32))
    assert T.payload_wire_bytes(tp, 8) == 4 * 8 * 1      # int8 payload only


def test_traced_wire_bytes_oracle():
    from bnsgcn_tpu.parallel.halo import (make_halo_spec, traced_wire_bytes,
                                          wire_bytes)
    n_b = np.array([[0, 3, 2, 1], [3, 0, 1, 1], [2, 1, 0, 2], [1, 1, 2, 0]])
    for strat in ("padded", "shift"):
        spec, _ = make_halo_spec(n_b, 32, 8, 0.5, strategy=strat)
        assert traced_wire_bytes(spec, 8) == wire_bytes(spec, 8)
    spec, _ = make_halo_spec(n_b, 32, 8, 0.5, strategy="ragged")
    # CPU emulation routes over the padded all_to_all: padded accounting,
    # NOT the exact-rows number wire_bytes reports for ragged
    assert (traced_wire_bytes(spec, 8, ragged_native=False)
            == spec.n_parts * spec.pad_send * 8 * 4)
    assert (traced_wire_bytes(spec, 8, ragged_native=True)
            != traced_wire_bytes(spec, 8, ragged_native=False))


# ----------------------------------------------------------------------------
# contract 4: hidden transfers (seeded violation)
# ----------------------------------------------------------------------------

def test_hidden_transfer_fires():
    def f(x):
        y = jax.pure_callback(
            lambda v: v, jax.ShapeDtypeStruct(x.shape, x.dtype), x)
        return y + 1.0

    tp = T.trace_program("fix", jax.jit(f), AVAL)
    found = C.check_transfers(tp, "ir://fix#prog")
    assert _rules(found) == ["ir-hidden-transfer"]
    assert "pure_callback" in found[0].message


def test_clean_program_has_no_transfers():
    tp = T.trace_program("ok", jax.jit(lambda x: x * 2.0), AVAL)
    assert tp.transfers == []
    assert C.check_transfers(tp, "ir://ok#prog") == []


# ----------------------------------------------------------------------------
# variant enumeration + seams
# ----------------------------------------------------------------------------

def test_enumerate_variants_covers_matrix_and_tune():
    vs = enumerate_variants()
    keys = {(v.strategy, v.wire, v.overlap, v.refresh, v.mode) for v in vs}
    assert len(keys) == len(vs)                       # deduplicated
    for strat in ("padded", "shift", "ragged"):
        for wire in ("native", "bf16", "fp8", "int8"):
            for ovl in ("off", "split"):
                for k in (1, 2):
                    assert (strat, wire, ovl, k, "exchange") in keys
        assert (strat, "native", "off", 1, "grad-only") in keys
    # the auto controller's coarse-staleness rung reaches K=4
    assert any(v.refresh == 4 and v.source == "tune" for v in vs)
    assert not any(v.strategy == "auto" for v in vs)


def test_enumerate_variants_with_schedule():
    # K=8 is outside the static matrix, so the schedule-reached state must
    # survive dedup as a tune-sourced extra cell
    vs = enumerate_variants(tune_schedule="K=8@5,wire=int8@9")
    assert any(v.refresh == 8 and v.source == "tune" for v in vs)
    assert any(v.refresh == 8 and v.wire == "int8" for v in vs)


def test_reachable_lever_states_schedule():
    from bnsgcn_tpu.config import Config
    from bnsgcn_tpu.tune import reachable_lever_states
    cfg = Config(tune="schedule",
                 tune_schedule="K=2@3,wire=bf16@7,mode=grad-only@9")
    states = reachable_lever_states(cfg)
    assert states[0] == {"halo_exchange": "padded", "halo_wire": "native",
                         "halo_refresh": 1, "halo_mode": "exchange"}
    assert {"halo_exchange": "padded", "halo_wire": "bf16",
            "halo_refresh": 2, "halo_mode": "exchange"} in states
    assert any(s["halo_mode"] == "grad-only" for s in states)
    # off: only the launch state
    assert len(reachable_lever_states(Config(tune="off"))) == 1


def test_step_variants():
    from bnsgcn_tpu.run import step_variants
    assert step_variants(SimpleNamespace(train_step_full=None)) == ("step",)
    assert step_variants(
        SimpleNamespace(train_step_full=object())) == ("full", "cached")


def test_transfer_primitives_registry():
    from bnsgcn_tpu.strict import TRANSFER_PRIMITIVES
    assert "device_put" in TRANSFER_PRIMITIVES
    assert "pure_callback" in TRANSFER_PRIMITIVES


# ----------------------------------------------------------------------------
# repo-level checks: tune-schedule lint, knob-table drift, stale suppressions
# ----------------------------------------------------------------------------

def _lint(root, paths=None):
    from bnsgcn_tpu.analysis import lint_paths
    return lint_paths(paths, root=str(root))


def test_tune_schedule_lint_fires(tmp_path):
    (tmp_path / "scripts").mkdir()
    (tmp_path / "scripts" / "run.sh").write_text(
        '#!/bin/bash\npython -m bnsgcn_tpu --tune schedule '
        '--tune-schedule wire=bf16@3\n'
        'python -m bnsgcn_tpu --tune schedule '
        '--tune-schedule "K=banana@5"\n'
        'python -m bnsgcn_tpu --tune schedule --tune-schedule=nope=1@9\n')
    active, _, _ = _lint(tmp_path)
    assert _rules(active) == ["tune-schedule-invalid",
                              "tune-schedule-invalid"]
    assert [f.file for f in active] == [os.path.join("scripts", "run.sh")] * 2
    assert sorted(f.line for f in active) == [3, 4]   # not the valid line 2


def test_tune_schedule_lint_python_argv(tmp_path):
    (tmp_path / "bench.py").write_text(textwrap.dedent("""\
        cmd = ["prog", "--tune-schedule", "K=2@4"]
        bad = ["prog", "--tune-schedule", "K=zero@4"]
        kw = dict(tune_schedule="wire=fp8@7")
    """))
    active, _, _ = _lint(tmp_path)
    assert _rules(active) == ["tune-schedule-invalid"]
    assert active[0].line == 2


def test_config_doc_drift_fires_and_clean(tmp_path):
    from bnsgcn_tpu.analysis.repo_checks import (KNOB_BEGIN, KNOB_END,
                                                 check_config_docs,
                                                 render_knob_table)
    # missing marker block
    (tmp_path / "README.md").write_text("# hi\n")
    assert _rules(check_config_docs(str(tmp_path))) == ["config-doc-drift"]
    # stale table (a knob row the parser doesn't have)
    (tmp_path / "README.md").write_text(
        f"# hi\n{KNOB_BEGIN}\n| knob | default | choices |\n|---|---|---|\n"
        f"| `--no-such-flag` | `1` |  |\n{KNOB_END}\n")
    found = check_config_docs(str(tmp_path))
    assert _rules(found) == ["config-doc-drift"]
    assert "drifted" in found[0].message
    # generated table verbatim -> clean
    (tmp_path / "README.md").write_text("# hi\n" + render_knob_table())
    assert check_config_docs(str(tmp_path)) == []


def test_knob_table_clean_at_head():
    """README knob table matches the live parser — the drift gate the
    full lint run enforces, asserted directly for a fast signal."""
    from bnsgcn_tpu.analysis.repo_checks import check_config_docs
    assert check_config_docs(REPO) == []


def test_suppression_stale_fires(tmp_path):
    (tmp_path / "fix.py").write_text(textwrap.dedent("""\
        import jax
        # graftlint: disable=prng-literal-key(was needed before a refactor)
        x = 1 + 1
    """))
    active, _, _ = _lint(tmp_path, [str(tmp_path)])
    assert _rules(active) == ["suppression-stale"]
    assert "prng-literal-key" in active[0].message
    assert active[0].line == 2


def test_suppression_used_not_stale(tmp_path):
    (tmp_path / "fix.py").write_text(textwrap.dedent("""\
        import jax
        # graftlint: disable=prng-literal-key(fixture: literal key on purpose)
        k = jax.random.PRNGKey(0)
    """))
    active, suppressed, _ = _lint(tmp_path, [str(tmp_path)])
    assert _rules(active) == []
    assert _rules(suppressed) == ["prng-literal-key"]


def test_suppression_multi_rule_partially_used_not_stale(tmp_path):
    # line-level semantics: one firing rule keeps the whole comment
    # load-bearing, even if the other listed rule no longer matches
    (tmp_path / "fix.py").write_text(textwrap.dedent("""\
        import jax
        # graftlint: disable=prng-key-reuse(fixture A),prng-literal-key(B)
        k = jax.random.PRNGKey(0)
    """))
    active, suppressed, _ = _lint(tmp_path, [str(tmp_path)])
    assert _rules(active) == []
    assert _rules(suppressed) == ["prng-literal-key"]


def test_suppression_stale_skipped_under_select(tmp_path):
    from bnsgcn_tpu.analysis import lint_paths
    (tmp_path / "fix.py").write_text(
        "# graftlint: disable=prng-literal-key(covered elsewhere)\nx = 1\n")
    active, _, _ = lint_paths([str(tmp_path)], root=str(tmp_path),
                              select={"prng-literal-key"})
    assert _rules(active) == []     # select runs can't judge staleness


# ----------------------------------------------------------------------------
# CLI + clean-at-HEAD gate
# ----------------------------------------------------------------------------

def _env():
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO)
    return env


def test_ir_cli_smoke_subset(tmp_path):
    """One --max-variants run covers the CLI surface: JSON report schema,
    wire-byte rows, and the ir_audit obs event (a single subprocess — the
    jax import dominates, so don't pay it twice)."""
    rep = tmp_path / "ir.json"
    log = tmp_path / "events.jsonl"
    r = subprocess.run(
        [sys.executable, "-m", "bnsgcn_tpu.analysis", "ir", "-q",
         "--max-variants", "2", "--json", str(rep), "--obs-log", str(log)],
        capture_output=True, text=True, timeout=300, cwd=REPO, env=_env())
    assert r.returncode == 0, r.stdout + r.stderr
    data = json.loads(rep.read_text())
    assert data["graftlint_ir"] == 1 and data["ok"] is True
    assert data["n_variants"] == 2 and data["variants_dropped"] > 0
    progs = data["variants"][0]["programs"]
    assert "train_step" in progs and "exchange_only" in progs
    assert progs["exchange_only"]["wire_bytes"]["traced"] == \
        progs["exchange_only"]["wire_bytes"]["oracle"]
    events = [json.loads(l) for l in log.read_text().splitlines()]
    ev = [e for e in events if e["kind"] == "ir_audit"]
    assert len(ev) == 1 and ev[0]["ok"] is True and ev[0]["n_variants"] == 2
    # elastic slot-map invariance rides every audit: the first variant is
    # re-traced under part -> slot maps for two world sizes and must keep
    # the identical (and rank-symmetric) collective schedule
    sw = data["slot_worlds"]
    assert [r["world"] for r in sw] == [2, 4]
    assert all(r["findings"] == 0 for r in sw)
    assert len({r["collectives"] for r in sw}) == 1


@pytest.mark.quickgate
def test_ir_audit_clean_at_head(tmp_path):
    """The gate: the FULL variant matrix (strategies x wires x overlap x
    refresh x tune targets) traces clean at HEAD on CPU with no devices —
    rank-symmetric schedules, no dead donations, wire bytes matching the
    plan oracle, no hidden transfers, zero trace errors."""
    rep = tmp_path / "ir.json"
    r = subprocess.run(
        [sys.executable, "-m", "bnsgcn_tpu.analysis", "ir", "-q",
         "--json", str(rep)],
        capture_output=True, text=True, timeout=540, cwd=REPO, env=_env())
    assert r.returncode == 0, r.stdout + r.stderr
    data = json.loads(rep.read_text())
    assert data["ok"] is True and data["findings"] == []
    assert data["errors"] == [] and data["variants_dropped"] == 0
    assert data["n_variants"] >= 40
    keys = {v["key"] for v in data["variants"]}
    assert "padded/native/ovl-off/K1/exchange" in keys
    assert any(k.endswith("grad-only") for k in keys)
    assert any("/K4/" in k for k in keys)             # tune-reachable rung
    # RESIZE survivors recompile through the same layout cache: the
    # slot-mapped retraces (W=2 shrink and W=4 identity) must already be
    # schedule-identical at HEAD, or an elastic verdict would silently
    # change the program a survivor runs
    assert [r["world"] for r in data["slot_worlds"]] == [2, 4]
    assert all(r["findings"] == 0 for r in data["slot_worlds"])
    # every exchange program's traced payload matched its oracle
    for row in data["variants"]:
        for name, prog in row["programs"].items():
            wb = prog.get("wire_bytes")
            if wb is not None:
                assert wb["traced"] == wb["oracle"], (row["key"], name)
