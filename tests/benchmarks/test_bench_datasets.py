"""The data seam: a configuration names its data module (`datasets/<name>.py`)
and the harness finds it by that name. The graph cells keep their data key,
their argv and their step_mfu; a configuration that names no data module, or
a missing one, is refused; and a cell whose workload has no `graph` runs
through the harness on a data module that a test drops in as a file."""

import json
import math
import os
import shutil

import pytest

import bench_tiny
from benchmarks import harness, obsread
from benchmarks.reference import sage

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
KEY = "sage-reddit-48fa241439"      # both cells' data key, as it was named
CELLS = ["sage-reddit.whole-xla.p1", "sage-reddit.whole.p1"]
CONFIG_FLAGS = ["--model", "graphsage", "--n-layers", "4", "--n-hidden",
                "256", "--dropout", "0.5", "--lr", "0.01", "--log-every",
                "10", "--use-pp", "--inductive", "--dtype", "bfloat16",
                "--spmm", "hybrid", "--n-partitions", "1",
                "--sampling-rate", "0.1"]


def argv_before_the_seam(cell, tag, trace):
    """The argv the harness built before configurations named their data
    module, written out flag by flag."""
    data = os.path.join(bench_tiny.BENCH, "cache", "data", KEY)
    run = os.path.join(bench_tiny.BENCH, "cache", "cells", cell, "run", tag)
    step = ["--use-pallas"] if cell == "sage-reddit.whole.p1" else []
    return (CONFIG_FLAGS + step
            + ["--dataset", "reddit", "--graph-name", KEY,
               "--part-path", os.path.join(data, "parts"),
               "--cache-dir", os.path.join(data, "layouts"),
               "--ckpt-path", os.path.join(run, "ckpt"),
               "--results-path", os.path.join(run, "results"),
               "--obs-log", os.path.join(run, "obs.jsonl"),
               "--seed", "123", "--fix-seed", "--no-eval",
               "--skip-partition", "--n-epochs", "40"]
            + (["--profile-dir", os.path.join(run, "trace")] if trace
               else ["--no-comm-trace"]))


@pytest.mark.parametrize("cell", CELLS)
def test_the_graph_cells_keep_their_data_key(cell):
    wl = harness.load_workload(cell)
    config = harness.load_config(wl["config"])
    assert config["data"] == "graph"
    assert harness.data_key(wl, config) == KEY
    dirs = harness.CellDirs(cell, wl, config)
    assert dirs.key == KEY
    assert dirs.data == os.path.join(bench_tiny.BENCH, "cache", "data", KEY)


@pytest.mark.parametrize("tag, trace", [("calibrate", False),
                                        ("timed", False), ("timed", True)])
@pytest.mark.parametrize("cell", CELLS)
def test_the_graph_cells_pass_the_same_argv(cell, tag, trace):
    wl = harness.load_workload(cell)
    config = harness.load_config(wl["config"])
    dirs = harness.CellDirs(cell, wl, config)
    assert harness.build_argv(config, wl, dirs, 123, 40, trace, tag) == (
        argv_before_the_seam(cell, tag, trace))


def test_step_mfu_reads_the_sizes_the_done_record_names():
    """On the recorded chip run's obs log, with the cell's training-graph
    sizes as the graph module's `done` record holds them: the value the
    reducer gave when it took n_nodes and n_edges by position. A family
    whose counter takes other sizes gets them by name."""
    events = obsread.read_events(os.path.join(DATA, "v5e_p1_scoped.obs.jsonl"))
    ctx = {"reference": sage, "config": harness.load_config("sage-reddit"),
           "ref_info": {"n_nodes": 153431, "n_edges": 50079171},
           "events": events, "first_epoch": 11, "chips": 1,
           "device": {"kind": "TPU v5 lite"}}
    step_mfu = harness.load_reducer("step_mfu")
    assert step_mfu(ctx) == pytest.approx(13.452115754135466, rel=1e-12)

    class Tokens:
        @staticmethod
        def step_flops(model, n_tokens, vocab_rows):
            return 6 * n_tokens * vocab_rows

    ctx.update(reference=Tokens, ref_info={"n_tokens": 4096,
                                           "vocab_rows": 12544})
    want = 100 * 6 * 4096 * 12544 / (obsread.step_median_s(events, 11)
                                      * 197e12)
    assert step_mfu(ctx) == pytest.approx(want, rel=1e-12)


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return bench_tiny.make_root(str(tmp_path_factory.mktemp("bench_data")))


def _write(root, kind, name, obj):
    with open(os.path.join(root, kind, name + ".json"), "w") as f:
        json.dump(obj, f)


def test_a_configuration_without_a_data_module_is_refused(tiny_root):
    cfg = harness.load_config("sage-tiny", tiny_root)
    del cfg["data"]
    with pytest.raises(harness.BenchError, match="names no data module"):
        harness.load_data(cfg, tiny_root)
    cfg["data"] = "nowhere"
    with pytest.raises(harness.BenchError, match="no nowhere.py"):
        harness.load_data(cfg, tiny_root)


@pytest.mark.parametrize("data, says", [(None, "names no data module"),
                                        ("nowhere", "no nowhere.py")])
def test_a_run_on_such_a_configuration_stops_before_it_starts(
        tiny_root, data, says):
    cfg = harness.load_config("sage-tiny", tiny_root)
    cfg.pop("data")
    if data is not None:
        cfg["data"] = data
    name = f"nodata-{data}"
    _write(tiny_root, "configs", name, cfg)
    wl = harness.load_workload("tiny.p1", tiny_root)
    wl.pop("name")
    wl["config"] = name
    _write(tiny_root, "workloads", name + ".p1", wl)
    with pytest.raises(harness.BenchError, match=says):
        harness.run(name + ".p1", 1, 0.2, False, root=tiny_root,
                    require_tpu=False)
    assert not os.path.exists(os.path.join(tiny_root, "cache", "cells",
                                           name + ".p1"))


# ----------------------------------------------------------------------------
# a data module and a cell without a graph, added as files only
# ----------------------------------------------------------------------------

@pytest.fixture(scope="module")
def grid_root(tmp_path_factory):
    """A tiny root that gains `datasets/grid.py` (a seeded lattice), a
    configuration that names it and a cell whose file has no `graph`."""
    root = bench_tiny.make_root(str(tmp_path_factory.mktemp("bench_grid")))
    os.makedirs(os.path.join(root, "datasets"))
    shutil.copy(os.path.join(DATA, "grid.py"),
                os.path.join(root, "datasets", "grid.py"))
    cfg = harness.load_config("sage-tiny", root)
    cfg.update(name="grid-tiny", data="grid", dataset="grid")
    cfg["flags"].remove("--inductive")
    # float32, so that the program and the reference differ by rounding
    # only: gaps of 1e-7 on most seeds, at most 1.0e-5 on a loss and 1.6e-4
    # on a parameter's change over six seeds (on a CPU)
    cfg["model"]["dtype"] = "float32"
    cfg["flags"][cfg["flags"].index("--dtype") + 1] = "float32"
    _write(root, "configs", "grid-tiny", cfg)
    _write(root, "workloads", "grid.p1", {
        "config": "grid-tiny", "chips": 1, "why": "test size",
        "lattice_seed": 2**31 + 5,
        "lattice": {"side": 40, "n_feat": 24, "n_class": 5,
                    "train_share": 0.6},
        "flags": ["--n-partitions", "1", "--sampling-rate", "0.1"],
        "limits": {"loss1_gap": 1e-4, "loss2_gap": 1e-4, "loss3_gap": 1e-4,
                   "grad1_gap": 1e-4, "dparam_gap": 2e-3}})
    return root


@pytest.fixture(scope="module")
def grid_runs(grid_root):
    first = bench_tiny.run_cell(grid_root, seed=2**31 + 77, cell="grid.p1")
    traced = bench_tiny.run_cell(grid_root, seed=19, trace=True,
                                 cell="grid.p1")
    return first, traced


def test_a_cell_on_its_own_data_module_is_correct(grid_root, grid_runs):
    wl = harness.load_workload("grid.p1", grid_root)
    assert "graph" not in wl and "graph_seed" not in wl
    for rc, res, err in grid_runs:
        assert rc == 0 and res["correct"] is True and res["failed"] == 0
        for name, (value, limit) in res["compared"].items():
            assert value <= limit, name
    (_, first, err1), (_, traced, err2) = grid_runs
    assert "lattice stored" in err1 and "lattice stored" not in err2
    assert "graph generated" not in err1
    assert set(first["metrics"]) == set(harness.metrics_for(
        "grid.p1", "end_to_end", grid_root))
    assert first["metrics"]["epoch_s"]["value"] > 0


def test_every_metric_without_a_cell_list_reads_or_is_left_out(grid_root,
                                                              grid_runs):
    (_, first, _), (_, traced, _) = grid_runs
    for res, kind in ((first, "end_to_end"), (traced, "per_layer")):
        listed = harness.metrics_for("grid.p1", kind, grid_root)
        assert set(res["metrics"]) <= set(listed)
        for name, m in listed.items():
            if "workloads" not in m and name in res["metrics"]:
                assert math.isfinite(res["metrics"][name]["value"]), name
    assert {"loop.host_gap_pct", "step.device_s"} <= set(traced["metrics"])


def test_the_grid_cell_names_its_own_data(grid_root, grid_runs):
    grid = bench_tiny.cell_dirs(grid_root, "grid.p1")
    assert grid.key.startswith("grid-tiny-")
    assert grid.key != bench_tiny.cell_dirs(grid_root, "tiny.p1").key
    with open(os.path.join(grid.ref, "done")) as f:
        assert json.load(f) == {"n_nodes": 1600,
                                "n_edges": 4 * 40 * 39 + 1600}
