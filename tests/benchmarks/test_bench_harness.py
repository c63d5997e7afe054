"""The harness end to end on a tiny graph through the real run_training, and
a configuration, a cell and a per-layer metric added as files only."""

import json
import os

import pytest

import bench_tiny
from benchmarks.datasets import graph


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_tiny.make_root(str(tmp_path_factory.mktemp("bench_root")))


@pytest.fixture(scope="module")
def first_run(root):
    return bench_tiny.run_cell(root, seed=2**31 + 11)


def test_result_line_keys(first_run):
    rc, res, err = first_run
    assert rc == 0
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(res)[-1] == "compared"
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 2
    assert set(res["metrics"]) == {"epoch_s", "peak_hbm_gib", "setup_s"}
    for m in res["metrics"].values():
        assert set(m) == {"value", "unit"}
    assert res["metrics"]["epoch_s"]["value"] > 0
    assert res["metrics"]["setup_s"]["value"] > 0
    assert set(res["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    for name, (value, limit) in res["compared"].items():
        assert value <= limit, name
    assert "[bench] compared loss1_gap" in err
    assert err.strip().splitlines()[-1].startswith("[bench] correct = True")


def test_first_run_builds_and_second_run_only_loads(root, first_run):
    _, _, err1 = first_run
    assert "graph generated" in err1 and "calibrating call" in err1
    rc, res, err2 = bench_tiny.run_cell(root, seed=12, trace=True)
    assert rc == 0 and res["correct"] is True
    assert "graph generated" not in err2 and "calibrating call" not in err2
    assert res["metrics"]["setup.layout_build_s"]["value"] == 0.0
    # a traced run reports the per-layer metrics that found something to read
    assert {"loop.host_gap_pct", "loop.epoch_p95_s",
            "step.device_s"} <= set(res["metrics"])
    assert "epoch_s" not in res["metrics"]
    # a CPU trace has no device lanes: those readers return nothing
    assert "device.idle_pct" not in res["metrics"]
    assert "breakdown" in res


def test_seed_is_the_training_run_not_the_graph(root, first_run):
    """Same cell, another --seed: same partition on disk, other losses."""
    meta = graph.meta_path(bench_tiny.cell_dirs(root, "tiny.p1"))
    before = os.path.getmtime(meta)
    rc, res, _ = bench_tiny.run_cell(root, seed=99)
    assert rc == 0 and res["correct"] is True
    assert os.path.getmtime(meta) == before
    assert res["compared"] != first_run[1]["compared"]


STUB_REFERENCE = '''"""A model family's reference and FLOP counter, added as a file: here the
GraphSAGE one under another name, with a counter of its own."""
from benchmarks.reference import sage

ADAM_B1 = sage.ADAM_B1
build_graph_tables = sage.build_graph_tables
CALLS = []


def run_steps(graph, tables, model, seed, layout, n_steps, **how):
    CALLS.append(seed)
    return sage.run_steps(graph, tables, model, seed, layout, n_steps, **how)


def step_flops(model, n_nodes, n_edges):
    return 7 * n_nodes * model["n_hidden"]
'''


def test_new_config_cell_metric_and_reference_as_files_only(root, first_run):
    """A later PR adds a configuration, a cell, a per-layer metric and a model
    family's reference with its FLOP counter as new files and edits none that
    is there: the harness lists and runs them."""
    from benchmarks import harness
    listed = {k: harness.list_names(k, root)
              for k in ("configs", "workloads", "metrics")}
    with open(os.path.join(root, "reference", "stubfamily.py"), "w") as f:
        f.write(STUB_REFERENCE)
    with open(os.path.join(root, "configs", "sage-tiny.json")) as f:
        cfg = json.load(f)
    cfg["name"] = "sage-tiny-wide"
    cfg["reference"] = "stubfamily"
    cfg["model"]["n_hidden"] = 48
    cfg["flags"][cfg["flags"].index("--n-hidden") + 1] = "48"
    with open(os.path.join(root, "configs", "sage-tiny-wide.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(root, "workloads", "tiny.p1.json")) as f:
        wl = json.load(f)
    wl["config"] = "sage-tiny-wide"
    with open(os.path.join(root, "workloads", "tiny-wide.p1.json"), "w") as f:
        json.dump(wl, f)
    with open(os.path.join(root, "metrics", "loop.first_wall_s.json"),
              "w") as f:
        json.dump({"kind": "per_layer", "unit": "s", "better": "lower",
                   "source": "program_span",
                   "layer": "entry: run.run_training", "moves": "epoch_s",
                   "reducer": "first_wall", "args": {"scale": 1.0},
                   "workloads": ["tiny-wide.p1"]}, f)
    with open(os.path.join(root, "reducers", "first_wall.py"), "w") as f:
        f.write("from benchmarks import obsread\n\n\n"
                "def reduce(ctx, scale):\n"
                "    return scale * obsread.epoch_walls(\n"
                "        ctx['events'], ctx['first_epoch'])[0]\n")
    # the family's FLOP count as a metric a CPU can read (step_mfu needs a
    # chip's peak): the reducer calls what the configuration's reference holds
    with open(os.path.join(root, "metrics", "step.flops.json"), "w") as f:
        json.dump({"kind": "per_layer", "unit": "flop", "better": "lower",
                   "source": "program_counter",
                   "layer": "step: trainer train_step", "moves": "epoch_s",
                   "reducer": "family_flops",
                   "workloads": ["tiny-wide.p1"]}, f)
    with open(os.path.join(root, "reducers", "family_flops.py"), "w") as f:
        f.write("def reduce(ctx):\n"
                "    return ctx['reference'].step_flops(\n"
                "        ctx['config']['model'], ctx['ref_info']['n_nodes'],\n"
                "        ctx['ref_info']['n_edges'])\n")
    assert harness.list_names("configs", root) == sorted(
        listed["configs"] + ["sage-tiny-wide"])
    assert harness.list_names("workloads", root) == sorted(
        listed["workloads"] + ["tiny-wide.p1"])
    assert "loop.first_wall_s" in harness.metrics_for(
        "tiny-wide.p1", "per_layer", root)
    assert "loop.first_wall_s" not in harness.metrics_for(
        "tiny.p1", "per_layer", root)
    rc, res, _ = bench_tiny.run_cell(root, seed=3, trace=True,
                                     cell="tiny-wide.p1")
    assert rc == 0 and res["correct"] is True
    assert res["metrics"]["loop.first_wall_s"]["value"] > 0
    assert res["metrics"]["step.flops"]["value"] == 7 * 2000 * 48
    # another configuration is another dataset to the program: its own cache
    assert (graph.meta_path(bench_tiny.cell_dirs(root, "tiny-wide.p1"))
            != graph.meta_path(bench_tiny.cell_dirs(root, "tiny.p1")))


def test_a_configuration_without_a_reference_is_refused(root):
    from benchmarks import harness
    with open(os.path.join(root, "configs", "sage-tiny.json")) as f:
        cfg = json.load(f)
    del cfg["reference"]
    with pytest.raises(harness.BenchError, match="names no reference"):
        harness.load_reference(cfg, root)
    cfg["reference"] = "nowhere"
    with pytest.raises(harness.BenchError, match="no nowhere.py"):
        harness.load_reference(cfg, root)


def test_cells_that_differ_in_step_flags_share_one_set_up(root, first_run):
    """A cell whose file differs only in `step_flags` loads the partition,
    layouts and reference inputs its sibling built; only the epoch wall that
    sizes its runs is its own."""
    with open(os.path.join(root, "workloads", "tiny.p1.json")) as f:
        wl = json.load(f)
    wl["step_flags"] = ["--use-pallas"]
    with open(os.path.join(root, "workloads", "tiny-pallas.p1.json"),
              "w") as f:
        json.dump(wl, f)
    a = bench_tiny.cell_dirs(root, "tiny.p1")
    b = bench_tiny.cell_dirs(root, "tiny-pallas.p1")
    assert (a.key, a.data, a.ref) == (b.key, b.data, b.ref)
    assert a.run != b.run and a.calibration != b.calibration
    before = os.path.getmtime(graph.meta_path(a))
    rc, res, err = bench_tiny.run_cell(root, seed=41, cell="tiny-pallas.p1")
    assert rc == 0 and res["correct"] is True
    assert "graph generated" not in err and "calibrating call" in err
    assert os.path.getmtime(graph.meta_path(a)) == before
    from benchmarks import harness
    cfg = harness.load_config(wl["config"], root)
    assert "--use-pallas" in harness.build_argv(cfg, wl, b, 1, 20, False, "t")
    # a flag among `flags` is another deployment: nothing is shared
    wl["flags"] = wl["flags"] + ["--partition-obj", "cut"]
    with open(os.path.join(root, "workloads", "tiny-cut.p1.json"), "w") as f:
        json.dump(wl, f)
    assert bench_tiny.cell_dirs(root, "tiny-cut.p1").data != a.data


def test_refuses_without_a_tpu(root):
    from benchmarks import harness
    with pytest.raises(harness.BenchError, match="TPU only"):
        harness.run("tiny.p1", 1, 0.2, False, root=root)


def test_cli_prints_no_result_without_a_tpu():
    import subprocess
    import sys
    p = subprocess.run(
        [sys.executable, os.path.join(bench_tiny.BENCH, "run_cell.py"),
         "--workload", "sage-reddit.whole.p1", "--seed", "1", "--seconds",
         "1", "--trace", "0"], capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU only" in p.stderr
