"""The step names itself from inside: every layer boundary of `train_step` is
one `jax.named_scope` of the table in utils/traceparse.py, and the epoch
loop's host time is accounted by obs.span.

(a) the lowered train step of each (SpMM path x model family) carries every
scope that applies to it in its `op_name` metadata, and the bucket loops of
the ELL gather sit under `agg_residual`; (c) a tiny `run_training`: the
`epoch` events' host account adds up, the set-up `span` events form one tree,
and the `run_header` counts equal what the layout arrays hold.
"""

import re

import jax
import numpy as np
import pytest

from bnsgcn_tpu import obs as obs_mod
from bnsgcn_tpu.config import Config
from bnsgcn_tpu.data.artifacts import build_artifacts
from bnsgcn_tpu.data.graph import sbm_graph, synthetic_graph
from bnsgcn_tpu.data.partitioner import partition_graph
from bnsgcn_tpu.models.gnn import spec_from_config
from bnsgcn_tpu.parallel.mesh import make_parts_mesh
from bnsgcn_tpu.trainer import (abstract_step_inputs, agg_widths,
                                build_step_fns)
from bnsgcn_tpu.utils import traceparse as tp

# tests/benchmarks/bench_tiny.py's sizes: 24 features, 32 hidden, 5 classes
N_FEAT, N_HIDDEN = 24, 32
ALWAYS = {tp.BNS_SAMPLE, tp.HALO_EXCHANGE, tp.NORM, tp.DROPOUT, tp.LOSS,
          tp.OPTIMIZER, tp.LAYER}
AGG = {"hybrid": {tp.AGG_TILES, tp.AGG_RESIDUAL}, "ell": {tp.AGG_RESIDUAL},
       "segment": {tp.AGG_COO}}


@pytest.mark.parametrize("op_name, scope", [
    ("jit(train_step)/jvp()/layer_1/agg_residual/gather", tp.AGG_RESIDUAL),
    ("jit(train_step)/transpose(jvp())/layer_2/transpose(jvp(linear))/dot_general",
     tp.LINEAR),
    ("jit(train_step)/jvp()/layer_3/mul", tp.LAYER),
    ("jit(train_step)/jvp()/layer_0/dropout/jit(_bernoulli)/jit(_uniform)/slice",
     tp.DROPOUT),
    ("jit(train_step)/jvp()/layer_1/attention/dropout/select_n", tp.DROPOUT),
    ("jit(train_step)/optimizer/jit(norm)/sqrt", tp.OPTIMIZER),
    ("jit(param_global_norm)/jit(norm)/reduce_sum", None),
    ("jit(train_step)/jvp()/jit(_threefry_fold_in)/slice", None),
    ("jit(train_step)/jvp()/linear_3/add", None),
])
def test_innermost_scope(op_name, scope):
    assert tp.innermost_scope(op_name) == scope


@pytest.fixture(scope="module")
def tiny_art():
    g = synthetic_graph(n_nodes=600, avg_degree=30, n_feat=N_FEAT, seed=3,
                        power_law=True)
    return build_artifacts(g, partition_graph(g, 1, method="random", seed=0))


@pytest.mark.parametrize("model, spmm", [
    ("graphsage", "hybrid"), ("graphsage", "ell"), ("gcn", "ell"),
    ("graphsage", "segment"), ("gat", "ell")])
def test_lowered_step_carries_every_scope(tiny_art, monkeypatch, model, spmm):
    # the accumulation path the chip takes: bucket loops over column blocks
    monkeypatch.setenv("BNSGCN_BENCH_PREFLIGHT", "1")
    art = tiny_art
    cfg = Config(model=model, n_layers=3, n_hidden=N_HIDDEN, n_partitions=1,
                 use_pp=True, spmm=spmm, sampling_rate=0.5, heads=2,
                 n_feat=art.n_feat, n_class=art.n_class, n_train=art.n_train)
    spec = spec_from_config(cfg)
    fns, _, tables, _ = build_step_fns(cfg, spec, art, make_parts_mesh(1))
    a = abstract_step_inputs(cfg, spec, art, fns, tables)
    feat = a["blk"]["feat"]
    if model == "gat":          # use_pp: the cached extended raw features
        a["blk"]["feat0_ext"] = jax.ShapeDtypeStruct(
            (feat.shape[0], art.n_ext, feat.shape[2]), feat.dtype)
    elif model == "graphsage":  # use_pp: [feat, mean_nbr]
        a["blk"]["feat"] = jax.ShapeDtypeStruct(
            feat.shape[:2] + (2 * feat.shape[2],), feat.dtype)
    lowered = fns.train_step.lower(
        a["params"], a["state"], a["opt_state"], a["epoch"], a["blk"],
        a["tables"], a["key"], a["key"])
    text = lowered.as_text(debug_info=True)
    names = re.findall(r'loc\("(jit\(train_step\)[^"]*)"', text)
    found = {tp.innermost_scope(n) for n in names}
    want = ALWAYS | ({tp.ATTENTION} if model == "gat"
                     else AGG[spmm] | {tp.LINEAR})
    assert want <= found, sorted(want - found)
    assert found - {None} <= set(tp.SCOPES)
    # forward and backward of one layer sum under one token: the custom_vjp
    # backward rules are traced under the same scope as their forward
    for scope in want & {tp.AGG_TILES, tp.AGG_RESIDUAL, tp.ATTENTION}:
        paths = [n for n in names if tp.innermost_scope(n) == scope]
        assert any("transpose(" in n for n in paths), scope
        assert any("transpose(" not in n for n in paths), scope
    # and the loops of a jitted helper (ell._unroll_sum, traced once for all
    # its calls and lowered apart) as the compiled program names them: XLA
    # inlines the helper under each caller's path
    loops = [n for n in names if n.endswith("/while")] + re.findall(
        r' while\(.*op_name="(jit\(train_step\)[^"]*'
        r'jit\(_unroll_sum\)/while)"', lowered.compile().as_text())
    if spmm == "ell" and model != "gat":
        # ell._bucket_sum's scan over column blocks
        assert any(tp.innermost_scope(n) == tp.AGG_RESIDUAL for n in loops)
    if spmm == "hybrid":
        # the XLA twin's scan over tile chunks (this graph's residual is too
        # narrow to loop)
        assert any(tp.innermost_scope(n) == tp.AGG_TILES for n in loops)
    assert all(tp.innermost_scope(n) in (tp.AGG_RESIDUAL, tp.AGG_TILES,
                                         tp.AGG_COO, tp.ATTENTION)
               for n in loops), loops


def test_agg_calls_counts_layers_that_aggregate():
    def calls(**kw):
        fwd, bwd, _ = agg_widths(spec_from_config(
            Config(n_feat=8, n_class=3, **kw)))
        return len(fwd), len(bwd)
    # sage-reddit: 4 layers, the precomputed layer 0 is a pure matmul
    assert calls(model="graphsage", n_layers=4, use_pp=True) == (3, 3)
    # without use_pp layer 0 aggregates, and its input holds no parameter
    assert calls(model="gcn", n_layers=3, use_pp=False) == (3, 2)
    assert calls(model="graphsage", n_layers=4, n_linear=2,
                 use_pp=True) == (1, 1)
    assert calls(model="gat", n_layers=3) == (0, 0)


# ----------------------------------------------------------------------------
# (c) the loop's host account on a tiny run
# ----------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_run(tmp_path_factory):
    from bnsgcn_tpu.run import run_training
    tmp = tmp_path_factory.mktemp("scoped_run")
    g = sbm_graph(n_nodes=240, n_class=3, n_feat=8, p_in=0.12, p_out=0.01,
                  seed=3)
    cfg = Config(dataset="sbm", model="graphsage", n_partitions=1, n_layers=3,
                 n_hidden=8, sampling_rate=0.5, use_pp=True, eval=False,
                 n_epochs=14, log_every=5, seed=7, comm_trace=False,
                 spmm="ell", part_path=str(tmp / "parts"),
                 ckpt_path=str(tmp / "ckpt"), results_path=str(tmp / "res"),
                 obs_log=str(tmp / "obs.jsonl"))
    captured = {}
    import bnsgcn_tpu.run as run_mod
    real = run_mod.build_step_fns

    def build(*a, **k):
        out = real(*a, **k)
        captured["fns"] = out[0]
        return out

    run_mod.build_step_fns = build
    try:
        run_training(cfg, g=g, verbose=False)
    finally:
        run_mod.build_step_fns = real
    return obs_mod.load_events(str(tmp / "obs.jsonl")), captured["fns"], cfg


def test_epoch_events_account_for_the_window(tiny_run):
    events, _, cfg = tiny_run
    ep = [e for e in events if e["kind"] == "epoch"]
    assert [e["epoch"] for e in ep] == list(range(cfg.n_epochs))
    for e in ep:
        assert abs(e["dispatch_s"] + e["wait_s"] - e["step_s"]) <= 2e-6
        assert {"cpu_s", "nivcsw", "majflt"} <= set(e)
        assert e["cpu_s"] >= 0 and e["nivcsw"] >= 0 and e["majflt"] >= 0
    assert "boundary_s" not in ep[0]        # nothing before the first step
    for e in ep[1:]:
        assert set(e["boundary"]) <= set(obs_mod.PHASES)
        assert "pre" in e["boundary"]
        assert not {"dispatch", "wait"} & set(e["boundary"])
        # the parts are exclusive: they never exceed the wall they divide
        assert sum(e["boundary"].values()) <= e["boundary_s"] + 1e-5
    # steps and boundaries tile the wall between the first and last event
    win = ep[6:]
    wall = win[-1]["ts"] - ep[5]["ts"]
    told = sum(e["step_s"] + e["boundary_s"] for e in win)
    assert abs(wall - told) <= 0.002 * len(win)
    # the epoch after a checkpoint epoch holds the write in its boundary
    wrote = {e["epoch"] for e in ep if "checkpoint" in e.get("boundary", {})}
    assert wrote == {5, 10}
    assert all(ep[i]["boundary"]["checkpoint"] > 0 for i in wrote)
    # the norm probe rides the guard on the same epochs, as its child
    assert all("norm_probe" in ep[i]["boundary"] for i in wrote)


def test_setup_spans_form_one_tree(tiny_run):
    events, _, _ = tiny_run
    spans = [e for e in events if e["kind"] == "span"]
    # the boot stamps ran before any Obs existed: written at make_obs, under
    # their own parent, ahead of the tree
    boot = [e for e in spans if e["parent"] == obs_mod.BOOT_PARENT]
    assert {e["name"] for e in boot} == {"import", "backend_init"}
    assert spans[:len(boot)] == boot
    spans = spans[len(boot):]
    by = {e["name"]: e for e in spans}
    root = obs_mod.SETUP_SPANS[0]
    assert by[root]["parent"] is None
    setup = [e for e in spans
             if not e["name"].startswith(obs_mod.FIRST_CALL)]
    assert {e["name"] for e in setup} <= set(obs_mod.SETUP_SPANS)
    assert {"build_step_fns", "place", "init_training", "pp_precompute",
            "comm_bench_compile"} <= set(by)
    for e in spans:
        if e["name"] != root:
            assert e["parent"] == root, e
            assert e["dur_s"] >= 0 and e["t0"] >= by[root]["t0"] - 1e-3
    for e in setup:
        assert e["t0"] + e["dur_s"] <= (by[root]["t0"] + by[root]["dur_s"]
                                        + 1e-3)
    assert sum(e["dur_s"] for e in setup if e["name"] != root) <= (
        by[root]["dur_s"] + 1e-3)
    assert all(e["parent"] == "build_step_fns" for e in events
               if e["kind"] == "layout_build")
    # what compiling the step cost: its first call less the later ones
    first = by[obs_mod.FIRST_CALL + "train_step"]
    assert first["calls"] == 4 and first["dur_s"] > 0
    assert by[obs_mod.FIRST_CALL + "param_global_norm"]["calls"] == 2
    kinds = [e["kind"] for e in events]
    assert kinds[-1] == "run_end"           # nothing is written after it
    assert set(kinds) <= set(obs_mod.EVENT_KINDS)


def test_run_header_counts_equal_the_layout_arrays(tiny_run):
    events, fns, _ = tiny_run
    head = next(e for e in events if e["kind"] == "run_header")["spmm"]
    assert head["path"] == "ell" and head["tiles_fwd"] == 0
    assert (head["dense_path_fwd"], head["dense_path_bwd"]) == ("none",
                                                                "none")
    # a padded slot holds the index of the zero row appended to what the
    # table gathers from: the extended rows forward, the owned rows backward
    zero_row = {"fwd": fns.extra_blk["bwd_perm"].shape[1],
                "bwd": fns.extra_blk["fwd_perm"].shape[1]}
    for d in ("fwd", "bwd"):
        tables = [np.asarray(v) for k, v in fns.extra_blk.items()
                  if re.fullmatch(rf"{d}_idx_\d+", k)]
        slots = sum(int(np.prod(v.shape[1:])) for v in tables)
        assert slots > 0 and head[f"residual_slots_{d}"] == slots
        edges = sum(int((v < zero_row[d]).sum()) for v in tables)
        assert 0 < edges <= slots and head[f"residual_edges_{d}"] == edges
    # --spmm ell on one part: both directions carry the graph's every edge
    assert head["residual_edges_fwd"] == head["residual_edges_bwd"]
    assert (head["agg_calls_fwd"], head["agg_calls_bwd"],
            head["agg_calls_per_step"]) == (2, 2, 4)
    # 8 -> 8 -> 8 -> 3 with use_pp: layer 1 keeps the wide order, layer 2
    # narrows and aggregates its 3 projected columns
    assert head["agg_width_fwd"] == head["agg_width_bwd"] == [8, 3]
    assert head["narrow_layers"] == [{"layer": 2, "fin": 8, "fout": 3}]
