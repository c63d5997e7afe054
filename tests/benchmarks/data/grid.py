"""A data module that tests drop into a benchmark root as `datasets/grid.py`:
a seeded 2-D lattice, generated here and not by `graphgen`, whose cells carry
`lattice` and `lattice_seed` and no `graph`. It stores the lattice the way
the graph module stores a graph, and the rest of the interface is that
module's."""

import numpy as np

from benchmarks.datasets import graph
from benchmarks.harness import BenchError

program_flags = graph.program_flags
reference_inputs = graph.reference_inputs
reference_model = graph.reference_model
layout = graph.layout


def key_fields(wl, config):
    return {"lattice": wl["lattice"], "lattice_seed": wl["lattice_seed"],
            "dataset": config["dataset"]}


def make_lattice(p: dict, seed: int) -> dict:
    """side x side nodes, an edge each way between grid neighbours and a
    self-loop a node; classes are vertical stripes, features the stripe's
    centre plus noise; a random `train_share` of the nodes trains."""
    side, n_feat, n_class = int(p["side"]), int(p["n_feat"]), int(p["n_class"])
    n = side * side
    rng = np.random.default_rng(seed)
    ids = np.arange(n, dtype=np.int64).reshape(side, side)
    pairs = [(ids[:, :-1], ids[:, 1:]), (ids[:-1, :], ids[1:, :])]
    a = np.concatenate([u.ravel() for u, _ in pairs])
    b = np.concatenate([v.ravel() for _, v in pairs])
    loops = np.arange(n, dtype=np.int64)
    label = (np.arange(n) % side) * n_class // side
    centres = rng.normal(size=(n_class, n_feat)).astype(np.float32)
    feat = centres[label] + rng.normal(size=(n, n_feat)).astype(np.float32)
    perm = rng.permutation(n)
    n_train = int(float(p["train_share"]) * n)
    masks = [np.zeros(n, dtype=bool) for _ in range(3)]
    masks[0][perm[:n_train]] = True
    masks[1][perm[n_train:]] = True
    return {"n_nodes": n, "src": np.concatenate([a, b, loops]),
            "dst": np.concatenate([b, a, loops]), "feat": feat,
            "label": label.astype(np.int64), "train_mask": masks[0],
            "val_mask": masks[1], "test_mask": masks[2], "multilabel": False}


def ensure(wl, config, dirs, ref, log):
    if graph.is_stored(dirs):
        return False
    seed = int(wl["lattice_seed"])
    graph.store(make_lattice(wl["lattice"], seed), wl, config, dirs, ref, log,
                seed)
    log("[bench] lattice stored")
    return True


def check_config(config, workloads, ref):
    graph.check_model(config, ref)
    for wl in workloads:
        for k in ("n_feat", "n_class"):
            if wl["lattice"][k] != config["model"][k]:
                raise BenchError(f"cell {wl.get('name')!r}: its lattice's "
                                 f"{k} is not the model's")
