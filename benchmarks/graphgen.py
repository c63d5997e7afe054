"""The benchmark's datasets: seeded graphs at a published dataset's shape.

This is the benchmark's own copy of the program's `reddit_like_graph`
(bnsgcn_tpu/data/graph.py at PR 22), extended only so that the split sizes,
feature width, class count and multilabel targets are arguments. Later PRs may
change the program's generator; they may not change the dataset, so nothing
here imports the program. Plain numpy in, plain numpy out.

Model: a degree-corrected stochastic block model. Community sizes ~ Zipf;
per-node popularity ~ (local rank)^-0.5 (power-law degrees); every edge draws
its source from the global popularity law and, with probability `homophily`,
its destination from the source's community, else from the global law.
Features are community-centred Gaussians. Single-label targets are the
communities; multilabel targets are `n_class` binary columns whose
per-community rates are seeded (so labels correlate with the communities).
"""

from __future__ import annotations

import numpy as np

# every key a workload file's "graph" object may carry, with its default
GRAPH_DEFAULTS = {
    "n_nodes": 232_965, "avg_degree": 492, "n_feat": 602, "n_class": 41,
    "n_comm": 41, "homophily": 0.78, "feat_snr": 1.0, "multilabel": False,
    "n_train": None, "n_val": None,
}


def _canonicalize(n, src, dst):
    """Self-loops removed, then one added per node (the reference's dataset
    canonical form): every node has in- and out-degree >= 1."""
    keep = src != dst
    loops = np.arange(n, dtype=src.dtype)
    return (np.concatenate([src[keep], loops]),
            np.concatenate([dst[keep], loops]))


def make_graph(params: dict, seed: int) -> dict:
    """Full graph as a dict of arrays: n_nodes, src, dst (int64), feat (f32),
    label (int64 [N] or f32 [N, C]), train/val/test masks, multilabel."""
    unknown = set(params) - set(GRAPH_DEFAULTS)
    if unknown:
        raise ValueError(f"unknown graph parameter(s): {sorted(unknown)}")
    p = {**GRAPH_DEFAULTS, **params}
    n, n_feat = int(p["n_nodes"]), int(p["n_feat"])
    rng = np.random.default_rng(seed)
    n_comm = max(min(int(p["n_comm"]), n // 64), 1)
    raw = 1.0 / np.arange(1, n_comm + 1) ** 0.9
    sizes = np.maximum((raw / raw.sum() * n).astype(np.int64), 32)
    while sizes.sum() > n:
        sizes[0] -= min(sizes[0] - 32, sizes.sum() - n)
        if sizes[0] <= 32 and sizes.sum() > n:
            sizes = sizes[:-1]
    sizes[0] += n - sizes.sum()
    n_comm = len(sizes)
    off = np.concatenate([[0], np.cumsum(sizes)])
    comm = np.repeat(np.arange(n_comm, dtype=np.int64), sizes)

    n_edges = int(round(n * float(p["avg_degree"])))
    mass = 2.0 * np.sqrt(sizes.astype(np.float64))
    cdf = np.cumsum(mass / mass.sum())

    def global_draw(k):
        c = np.minimum(np.searchsorted(cdf, rng.random(k)), n_comm - 1)
        return off[c] + (sizes[c] * rng.random(k) ** 2).astype(np.int64)

    src = global_draw(n_edges)
    intra = rng.random(n_edges) < p["homophily"]
    c_src = comm[src]
    dst = np.empty(n_edges, dtype=np.int64)
    n_in = int(intra.sum())
    dst[intra] = off[c_src[intra]] + (
        sizes[c_src[intra]] * rng.random(n_in) ** 2).astype(np.int64)
    dst[~intra] = global_draw(n_edges - n_in)
    del intra, c_src

    centers = rng.normal(size=(n_comm, n_feat)).astype(np.float32)
    feat = centers[comm] * np.float32(p["feat_snr"])
    feat += rng.normal(size=(n, n_feat)).astype(np.float32)

    n_class = int(p["n_class"])
    if p["multilabel"]:
        rates = rng.beta(0.5, 2.0, size=(n_comm, n_class))
        label = (rng.random((n, n_class)) < rates[comm]).astype(np.float32)
    else:
        label = comm % n_class

    n_train = int(0.6 * n) if p["n_train"] is None else int(p["n_train"])
    n_val = int(0.2 * n) if p["n_val"] is None else int(p["n_val"])
    if n_train + n_val > n:
        raise ValueError("n_train + n_val exceeds n_nodes")
    perm = rng.permutation(n)
    masks = [np.zeros(n, dtype=bool) for _ in range(3)]
    masks[0][perm[:n_train]] = True
    masks[1][perm[n_train:n_train + n_val]] = True
    masks[2][perm[n_train + n_val:]] = True
    src, dst = _canonicalize(n, src, dst)
    return {"n_nodes": n, "src": src, "dst": dst, "feat": feat,
            "label": label, "train_mask": masks[0], "val_mask": masks[1],
            "test_mask": masks[2], "multilabel": bool(p["multilabel"])}


def train_subgraph(g: dict) -> dict:
    """Node-induced subgraph of the training nodes, ids relabelled in order
    (the inductive setting trains on this graph alone)."""
    keep = g["train_mask"]
    new_id = np.full(g["n_nodes"], -1, dtype=np.int64)
    kept = np.nonzero(keep)[0]
    new_id[kept] = np.arange(kept.shape[0])
    ekeep = keep[g["src"]] & keep[g["dst"]]
    return {"n_nodes": int(kept.shape[0]),
            "src": new_id[g["src"][ekeep]], "dst": new_id[g["dst"][ekeep]],
            "feat": g["feat"][kept], "label": g["label"][kept],
            "train_mask": np.ones(kept.shape[0], dtype=bool),
            "multilabel": g["multilabel"]}


def training_graph(g: dict, inductive: bool) -> dict:
    """The graph the step trains on: the training subgraph when inductive,
    else the whole graph with its train mask."""
    if inductive:
        return train_subgraph(g)
    return {k: g[k] for k in ("n_nodes", "src", "dst", "feat", "label",
                              "train_mask", "multilabel")}
