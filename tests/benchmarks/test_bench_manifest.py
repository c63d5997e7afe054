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
        for name in ("build_graph_tables", "run_steps", "step_flops",
                     "ADAM_B1"):
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
    from bnsgcn_tpu.config import parse_config
    for name in harness.list_names("configs"):
        c = harness.load_config(name)
        cfg = parse_config(c["flags"] + ["--dataset", c["dataset"]])
        m = c["model"]
        assert (cfg.model, cfg.n_layers, cfg.n_hidden, cfg.n_linear) == (
            m["model"], m["n_layers"], m["n_hidden"], m["n_linear"])
        assert (cfg.dropout, cfg.lr, cfg.use_pp, cfg.dtype) == (
            m["dropout"], m["lr"], m["use_pp"], m["dtype"])
        assert cfg.norm == "layer" and cfg.weight_decay == 0.0
        for w in harness.list_names("workloads"):
            wl = harness.load_workload(w)
            if wl["config"] == name:
                assert wl["graph"]["n_feat"] == m["n_feat"]
                assert wl["graph"]["n_class"] == m["n_class"]


def test_paths_hold_only_plain_names():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for base in ("benchmarks", "tests/benchmarks"):
        for d, dirs, names in os.walk(os.path.join(bench_tiny.REPO, base)):
            dirs[:] = [x for x in dirs if x not in ("cache", "__pycache__")]
            for n in names:
                rel = os.path.relpath(os.path.join(d, n), bench_tiny.REPO)
                assert ok.match(rel), rel
