"""Graph datasets: a seeded graph at a published dataset's shape, partitioned
by the program, and the plain reference's inputs from the same graph.

A configuration names this module with `"data": "graph"`. Its cells carry
`graph` (the parameters of `graphgen.make_graph`) and `graph_seed`. The
program is handed the partition on disk (`--part-path`, `--graph-name`) and
a directory for its layouts (`--cache-dir`); the reference gets the
benchmark's own split of the same graph and, from the program's files, only
the layout (`layout`). Cache, under `cache/data/<key>/`:

    parts/<key>/meta.json, part<p>.npz   the program's partition
    layouts/                             the program's SpMM layouts
    reference/                           features, labels, flags, in-degrees,
                                         edges and the reference's tables;
                                         `done` holds the graph's sizes
"""

from __future__ import annotations

import glob
import json
import os
import shutil
import time

import numpy as np

from benchmarks.harness import (BenchError, build_argv, cell_flags,
                                flag_value, load_json)


def parts_dir(dirs) -> str:
    return os.path.join(dirs.data, "parts")


def meta_path(dirs) -> str:
    return os.path.join(parts_dir(dirs), dirs.key, "meta.json")


def is_inductive(config: dict) -> bool:
    return "--inductive" in config["flags"]


def n_partitions(wl: dict, config: dict) -> int:
    return int(flag_value(cell_flags(wl, config), "--n-partitions", 1))


# ---------------------------------------------------------------------------
# the data module's interface (benchmarks/README.md)
# ---------------------------------------------------------------------------

def key_fields(wl: dict, config: dict) -> dict:
    """What names the dataset besides the harness's own fields: the graph,
    its seed and the program's dataset name."""
    return {"graph": wl["graph"], "graph_seed": wl["graph_seed"],
            "dataset": config["dataset"]}


def program_flags(config: dict, wl: dict, dirs) -> list:
    """Where the program finds the partition and keeps its layouts."""
    return ["--dataset", config["dataset"], "--graph-name", dirs.key,
            "--part-path", parts_dir(dirs),
            "--cache-dir", os.path.join(dirs.data, "layouts")]


def is_stored(dirs) -> bool:
    return os.path.exists(meta_path(dirs)) and os.path.exists(
        os.path.join(dirs.ref, "done"))


def ensure(wl: dict, config: dict, dirs, ref, log) -> bool:
    """Generate, partition and store the cell's dataset unless the cache
    holds it. Returns True when it was built now."""
    if is_stored(dirs):
        return False
    from benchmarks import graphgen
    t0 = time.time()
    full = graphgen.make_graph(wl["graph"], int(wl["graph_seed"]))
    log(f"[bench] graph generated in {time.time() - t0:.1f}s: "
        f"{full['n_nodes']} nodes, {full['src'].shape[0]} edges")
    store(full, wl, config, dirs, ref, log, int(wl["graph_seed"]))
    return True


def store(full: dict, wl: dict, config: dict, dirs, ref, log,
          seed: int) -> None:
    """Let the program partition the graph `full` (a dict of arrays in
    `graphgen.make_graph`'s form; emptied here, so its arrays go as soon as
    they are used) and store the reference's inputs from the same graph."""
    from benchmarks import graphgen
    from bnsgcn_tpu.config import parse_config
    from bnsgcn_tpu.data.graph import Graph
    from bnsgcn_tpu.run import prepare_partition

    inductive = is_inductive(config)
    # the program gets the dataset and takes its own inductive split, the
    # lines main.py -> prepare_partition run when they load a dataset
    g = Graph(n_nodes=full["n_nodes"], src=full["src"], dst=full["dst"],
              feat=full["feat"], label=full["label"],
              train_mask=full["train_mask"], val_mask=full["val_mask"],
              test_mask=full["test_mask"], multilabel=full["multilabel"])
    if inductive:
        g = g.subgraph(g.train_mask)
    shutil.rmtree(parts_dir(dirs), ignore_errors=True)
    argv = build_argv(config, wl, dirs, seed, 1, False, "partition")
    argv.remove("--skip-partition")
    t0 = time.time()
    prepare_partition(parse_config(argv), g, load=False)
    log(f"[bench] partition + artifacts in {time.time() - t0:.1f}s")
    del g
    # the reference's inputs, from the benchmark's own split of the graph
    t0 = time.time()
    tg = graphgen.training_graph(full, inductive)
    full.clear()
    shutil.rmtree(dirs.ref, ignore_errors=True)
    os.makedirs(dirs.ref)
    for k in ("src", "dst"):
        np.save(os.path.join(dirs.ref, f"{k}.npy"), tg[k].astype(np.int32))
    for k in ("feat", "label", "train_mask"):
        np.save(os.path.join(dirs.ref, f"{k}.npy"), tg[k])
    np.save(os.path.join(dirs.ref, "in_deg.npy"),
            np.bincount(tg["dst"], minlength=tg["n_nodes"]).astype(np.float32))
    tables = ref.build_graph_tables(tg["src"], tg["dst"], tg["n_nodes"])
    for k, v in tables.items():
        np.save(os.path.join(dirs.ref, f"table_{k}.npy"), v)
    with open(os.path.join(dirs.ref, "done"), "w") as f:
        json.dump({"n_nodes": tg["n_nodes"],
                   "n_edges": int(tg["src"].shape[0])}, f)
    log(f"[bench] reference inputs in {time.time() - t0:.1f}s")


def reference_inputs(wl: dict, config: dict, dirs):
    """(graph, tables) for the reference: the edges only where the layout
    has more than one part (the reference then replays boundary sampling)."""
    info = load_json(os.path.join(dirs.ref, "done"))
    graph = {"n_nodes": info["n_nodes"], "n_edges": info["n_edges"]}
    edges = ("src", "dst") if n_partitions(wl, config) > 1 else ()
    for k in ("feat", "label", "train_mask", "in_deg") + edges:
        graph[k] = np.load(os.path.join(dirs.ref, f"{k}.npy"))
    tables = {}
    for p in glob.glob(os.path.join(dirs.ref, "table_*.npy")):
        tables[os.path.basename(p)[len("table_"):-len(".npy")]] = np.load(p)
    return graph, tables


def reference_model(config: dict, graph: dict) -> dict:
    model = dict(config["model"])
    lab = graph["label"]
    model["multilabel"] = bool(lab.ndim == 2)
    if lab.ndim == 1:
        model["n_class"] = int(lab.max()) + 1
    return model


def layout(wl: dict, config: dict, dirs, graph: dict) -> dict:
    """The deployment's layout, read from the partition on disk: which part
    holds each node of the training graph and in which row, the padded row
    and boundary counts, and the cell's sampling rate. It is all the
    reference takes from the program's files: it needs it to replay dropout
    and boundary sampling, which are drawn by part and row.

    What the replay rests on is first held against the benchmark's own graph
    (`graph`: its features, labels, training flags and the in-degrees counted
    from its own edges): every node sits in exactly one row, under `pad_inner`,
    and that row carries this node's own features, label, training flag and
    in-degree. A row order or padding that departs from the node ids the
    files declare is an error here, and is not mirrored into the reference.
    Boundary sets are the reference's own, from the benchmark's edges
    (`boundary_lists`), held against `pad_boundary` there."""
    meta = load_json(meta_path(dirs))
    n = int(graph["n_nodes"])
    n_parts, pad_inner = int(meta["n_parts"]), int(meta["pad_inner"])
    part_of = np.full(n, -1, np.int32)
    row_of = np.zeros(n, np.int32)
    seen = np.zeros(n, np.int64)

    def fault(what):
        raise BenchError(f"the partition on disk {what}")

    for p in range(n_parts):
        with np.load(os.path.join(os.path.dirname(meta_path(dirs)),
                                  f"part{p}.npz")) as f:
            nid = f["global_nid"]
            rows = np.nonzero(nid >= 0)[0]
            ids = nid[rows]
            if rows.size and (rows.max() >= pad_inner or ids.max() >= n):
                fault(f"holds a row over pad_inner or a node over {n} in "
                      f"part {p}")
            feat = f["feat"]
            if meta.get("feat_dtype", "float32") == "bfloat16":
                import ml_dtypes
                feat = feat.view(ml_dtypes.bfloat16)
            held = {"features": np.allclose(
                        feat[rows].astype(np.float32), graph["feat"][ids],
                        rtol=2.0 ** -7, atol=1e-6),
                    "labels": np.array_equal(f["label"][rows],
                                             graph["label"][ids]),
                    "training flags": np.array_equal(
                        f["train_mask"][rows], graph["train_mask"][ids]),
                    "in-degrees": np.array_equal(f["in_deg"][rows],
                                                 graph["in_deg"][ids])}
        for what, ok in held.items():
            if not ok:
                fault(f"holds other {what} in part {p} than the nodes its "
                      f"rows name")
        seen += np.bincount(ids, minlength=n)
        part_of[ids] = p
        row_of[ids] = rows
    if (seen != 1).any():
        fault("does not hold every node of the training graph exactly once")
    return {"n_parts": n_parts, "pad_inner": pad_inner,
            "pad_boundary": int(meta["pad_boundary"]),
            "rate": float(flag_value(cell_flags(wl, config),
                                     "--sampling-rate", 1.0)),
            "part_of": part_of, "row_of": row_of}


def check_model(config: dict, ref) -> None:
    """A GNN configuration's flags (what the program runs) against its model
    block (what the reference and the FLOP counter take), and its reference
    against what this module calls."""
    from bnsgcn_tpu.config import parse_config
    name = config.get("name")
    if not hasattr(ref, "build_graph_tables"):
        raise BenchError(f"configuration {name!r}: its reference has no "
                         f"build_graph_tables")
    cfg = parse_config(config["flags"] + ["--dataset", config["dataset"]])
    m = config["model"]
    held = {
        "model, n_layers, n_hidden, n_linear":
            (cfg.model, cfg.n_layers, cfg.n_hidden, cfg.n_linear)
            == (m["model"], m["n_layers"], m["n_hidden"], m["n_linear"]),
        "dropout, lr, use_pp, dtype":
            (cfg.dropout, cfg.lr, cfg.use_pp, cfg.dtype)
            == (m["dropout"], m["lr"], m["use_pp"], m["dtype"]),
        "norm layer (the reference's LayerNorm)": cfg.norm == "layer",
        "weight_decay 0 (the reference's Adam)": cfg.weight_decay == 0.0,
    }
    for what, ok in held.items():
        if not ok:
            raise BenchError(f"configuration {name!r}: its flags and its "
                             f"model block disagree on {what}")


def check_config(config: dict, workloads: list, ref) -> None:
    """`check_model`, and each cell's graph against the model's widths."""
    check_model(config, ref)
    m = config["model"]
    for wl in workloads:
        for k in ("n_feat", "n_class"):
            if wl["graph"][k] != m[k]:
                raise BenchError(
                    f"cell {wl.get('name')!r}: its graph's {k} "
                    f"{wl['graph'][k]} is not the model's {m[k]}")
