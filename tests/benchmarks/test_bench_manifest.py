"""BENCHMARK.json against the files the harness finds by name, and against the
limits of the manifest's own contract."""

import json
import os
import re

import pytest

import bench_tiny
from benchmarks import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(bench_tiny.REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def test_top_level_keys(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert manifest["command"] == ["python3", "benchmarks/run_cell.py"]
    assert manifest["paths"] == ["benchmarks", "tests/benchmarks"]
    assert 1 <= manifest["run_seconds"] <= 51


def test_cells_and_configs_have_their_files(manifest):
    cfg_names = [c["name"] for c in manifest["configs"]]
    assert sorted(cfg_names) == harness.list_names("configs")
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        on_disk = harness.load_config(c["name"])
        assert c["file"] == f"benchmarks/configs/{c['name']}.json"
        assert on_disk["source"] == c["source"]
        assert on_disk["reduced"] == c["reduced"]
        ref = harness.load_reference(on_disk)   # named in the file, found
        harness.load_data(on_disk)              # named in the file, found
        for name in ("run_steps", "step_flops", "ADAM_B1"):
            assert hasattr(ref, name), name
        assert NAME.match(c["name"]) and len(c["why"]) <= 200
    cells = [w["name"] for w in manifest["workloads"]]
    assert sorted(cells) == harness.list_names("workloads")
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        on_disk = harness.load_workload(w["name"])
        assert on_disk["config"] == w["config"] in cfg_names
        assert w["name"] == f"{w['config']}.{w['traffic']}"
        assert on_disk["chips"] == w["chips"] in (1, 4)
        assert on_disk["why"] == w["why"] and len(w["why"]) <= 200
        assert set(on_disk["limits"]) == {"loss1_gap", "loss2_gap",
                                          "loss3_gap", "grad1_gap",
                                          "dparam_gap"}
    four = sum(w["chips"] == 4 for w in manifest["workloads"])
    assert four <= max(1, len(cells) // 4)


def test_metrics_agree_with_their_files(manifest):
    files = harness.load_metrics()
    listed = {m["name"]: m for m in
              manifest["end_to_end"] + manifest["per_layer"]}
    assert set(listed) == set(files)
    cells = {w["name"] for w in manifest["workloads"]}
    e2e = {m["name"] for m in manifest["end_to_end"]}
    assert "setup_s" in e2e
    for m in manifest["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert files[m["name"]]["kind"] == "end_to_end"
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in manifest["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        f = files[m["name"]]
        assert f["kind"] == "per_layer" and m["moves"] in e2e
        assert m["layer"] == f["layer"] and f.get("workloads") == m.get(
            "workloads")
        assert set(m.get("workloads", [])) <= cells
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for name, m in listed.items():
        f = files[name]
        assert NAME.match(name) and UNIT.match(m["unit"])
        assert (m["unit"], m["better"], m["source"]) == (
            f["unit"], f["better"], f["source"])
        assert m["better"] in ("lower", "higher")
        harness.load_reducer(f["reducer"])      # exists and imports


def test_every_cell_reports_enough(manifest):
    for w in manifest["workloads"]:
        e2e = harness.metrics_for(w["name"], "end_to_end")
        assert "setup_s" in e2e and len(e2e) >= 2
        assert harness.metrics_for(w["name"], "per_layer")


def test_config_flags_and_model_block_agree():
    """Each configuration's data module holds it to what its family needs
    (for `graph`: the flags against the model block, the reference's
    `build_graph_tables`, each cell's graph against the model's widths)."""
    wls = [harness.load_workload(w) for w in harness.list_names("workloads")]
    for name in harness.list_names("configs"):
        c = harness.load_config(name)
        harness.load_data(c).check_config(
            c, [wl for wl in wls if wl["config"] == name],
            harness.load_reference(c))


def _no_tables(c, wl):
    return c, wl, object()


def _flag(flag, value):
    def change(c, wl):
        flags = c["flags"]
        if flag in flags:
            flags[flags.index(flag) + 1] = value
        else:
            flags += [flag, value]
        return c, wl, None
    return change


def _graph(key):
    def change(c, wl):
        wl["graph"][key] += 1
        return c, wl, None
    return change


@pytest.mark.parametrize("change, says", [
    (_no_tables, "build_graph_tables"),
    (_flag("--n-hidden", "128"), "n_hidden"),
    (_flag("--n-layers", "3"), "n_layers"),
    (_flag("--dropout", "0.4"), "dropout"),
    (_flag("--lr", "0.02"), "lr"),
    (_flag("--dtype", "float32"), "dtype"),
    (_flag("--norm", "batch"), "norm"),
    (_flag("--weight-decay", "0.001"), "weight_decay"),
    (_graph("n_feat"), "n_feat"),
    (_graph("n_class"), "n_class"),
], ids=["tables", "n_hidden", "n_layers", "dropout", "lr", "dtype", "norm",
        "weight_decay", "n_feat", "n_class"])
def test_graph_data_refuses_a_configuration_that_disagrees(change, says):
    """Every check the graph data module makes bites on the real files."""
    c = harness.load_config("sage-reddit")
    wl = harness.load_workload("sage-reddit.whole.p1")
    c, wl, ref = change(c, wl)
    data = harness.load_data(c)
    with pytest.raises(harness.BenchError, match=says):
        data.check_config(c, [wl], ref or harness.load_reference(c))


def test_paths_hold_only_plain_names():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for base in ("benchmarks", "tests/benchmarks"):
        for d, dirs, names in os.walk(os.path.join(bench_tiny.REPO, base)):
            dirs[:] = [x for x in dirs if x not in ("cache", "__pycache__")]
            for n in names:
                rel = os.path.relpath(os.path.join(d, n), bench_tiny.REPO)
                assert ok.match(rel), rel
