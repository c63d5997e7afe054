"""Shared by the benchmark's tests: a tiny benchmark root built from files
only (the size override that only tests pass), and one harness run in it."""

import io
import json
import os
import shutil
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)
BENCH = os.path.join(REPO, "benchmarks")

TINY_LIMITS = {"loss1_gap": 0.003, "loss2_gap": 0.003, "loss3_gap": 0.003,
               "grad1_gap": 0.04, "dparam_gap": 0.12}


def make_root(root: str) -> str:
    """A benchmark root holding the real metric files, and a tiny
    configuration and cell dropped in as new files."""
    for d in ("configs", "workloads", "reducers", "metrics", "reference"):
        os.makedirs(os.path.join(root, d))
    for name in os.listdir(os.path.join(BENCH, "metrics")):
        # a share of a chip's peak has no reading on a CPU: the peaks table
        # knows no such device kind, and an unknown kind is an error
        if name not in ("step_mfu.json", "bns_tile_matmul_roofline.json"):
            shutil.copy(os.path.join(BENCH, "metrics", name),
                        os.path.join(root, "metrics", name))
    with open(os.path.join(BENCH, "configs", "sage-reddit.json")) as f:
        cfg = json.load(f)
    cfg["name"] = "sage-tiny"
    cfg["model"].update(n_hidden=32, n_feat=24, n_class=5)
    flags = cfg["flags"]
    flags[flags.index("--n-hidden") + 1] = "32"
    with open(os.path.join(root, "configs", "sage-tiny.json"), "w") as f:
        json.dump(cfg, f)
    wl = {"config": "sage-tiny", "chips": 1, "why": "test size",
          "graph_seed": 7,
          "graph": {"n_nodes": 3000, "avg_degree": 30, "n_feat": 24,
                    "n_class": 5, "n_comm": 5, "n_train": 2000,
                    "n_val": 300},
          "flags": ["--n-partitions", "1", "--sampling-rate", "0.1"],
          "limits": TINY_LIMITS}
    with open(os.path.join(root, "workloads", "tiny.p1.json"), "w") as f:
        json.dump(wl, f)
    # a second tiny configuration and a four-part cell: the Yelp recipe's
    # shape (float32, two dense tail layers, multilabel) at BNS rate 0.25
    cfg = {"name": "yelp-tiny", "source": "test", "reduced": [],
           "data": "graph", "dataset": "yelp", "reference": "sage",
           "model": {"model": "graphsage", "n_layers": 4, "n_hidden": 32,
                     "n_linear": 2, "dropout": 0.1, "lr": 0.001,
                     "use_pp": True, "dtype": "float32", "n_feat": 24,
                     "n_class": 10},
           "flags": ["--model", "graphsage", "--n-layers", "4", "--n-linear",
                     "2", "--n-hidden", "32", "--dropout", "0.1", "--lr",
                     "0.001", "--log-every", "10", "--use-pp", "--inductive",
                     "--dtype", "float32", "--spmm", "ell"]}
    with open(os.path.join(root, "configs", "yelp-tiny.json"), "w") as f:
        json.dump(cfg, f)
    wl = {"config": "yelp-tiny", "chips": 4, "why": "test size",
          "graph_seed": 8,
          "graph": {"n_nodes": 3000, "avg_degree": 19.5, "n_feat": 24,
                    "n_class": 10, "n_comm": 5, "n_train": 2250,
                    "n_val": 300, "multilabel": True},
          "flags": ["--n-partitions", "4", "--sampling-rate", "0.25"],
          "limits": {k: 1e-4 for k in TINY_LIMITS}}
    with open(os.path.join(root, "workloads", "tiny.p4.json"), "w") as f:
        json.dump(wl, f)
    return root


def cell_dirs(root: str, cell: str):
    from benchmarks import harness
    wl = harness.load_workload(cell, root)
    return harness.CellDirs(cell, wl, harness.load_config(wl["config"], root),
                            root)


def run_cell(root: str, seed: int = 5, seconds: float = 0.3,
             trace: bool = False, cell: str = "tiny.p1"):
    """(exit code, result object, stderr text) of one harness run that skips
    only the look for a chip."""
    from benchmarks import harness
    out, err = io.StringIO(), io.StringIO()
    real = sys.stdout
    sys.stdout = err
    try:
        rc = harness.run(cell, seed, seconds, trace, root=root,
                         require_tpu=False, out=out, err=err)
    finally:
        sys.stdout = real
    lines = out.getvalue().strip().splitlines()
    return rc, json.loads(lines[-1]), err.getvalue()
