"""Full-graph evaluation (reference train.py:22-61,427-456).

The reference evaluates on the whole undistributed graph on CPU in a
background thread. Here the eval forward is the same `apply_model` in eval
mode (norms recomputed from the eval graph's degrees, module/layer.py:39-45).
The full-graph forwards here compute on the caller's default device unless
given a `device`. run.py owns `--eval-device`: under `host` it passes
`host_device()` (the CPU backend), so the eval runs in a host thread that
overlaps training exactly like the reference and never on the accelerator the
mesh trains on — the full graph does not fit beside a part's blocks there.
`--eval-device mesh` (`evaluate_mesh`) is the on-accelerator,
partition-parallel alternative. Serving and continual training call the same
forwards with no device: their tables are computed where they run.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from bnsgcn_tpu.config import ConfigError
from bnsgcn_tpu.data.graph import Graph
from bnsgcn_tpu.models.gnn import GraphEnv, ModelSpec, apply_model
from bnsgcn_tpu.utils.metrics import calc_acc


def _identity_exchange(i, h):
    return h, None


def host_device():
    """The device `--eval-device host` computes on: the CPU backend's first
    device, whatever the default backend is."""
    try:
        return jax.devices("cpu")[0]
    except RuntimeError as ex:
        raise ConfigError(
            "--eval-device host runs the full-graph eval on the CPU backend, "
            f"which this process did not initialize (JAX_PLATFORMS="
            f"{jax.config.jax_platforms!r}): add cpu to JAX_PLATFORMS, or "
            f"use --eval-device mesh") from ex


def build_eval_env(g: Graph, spec: ModelSpec, edge_chunk: int = 0) -> GraphEnv:
    """Eval-path env: norms from the eval graph's own degrees
    (module/layer.py:40-41,94)."""
    in_deg = g.in_degrees().astype(np.float32)
    out_deg = g.out_degrees().astype(np.float32)
    if spec.model == "gcn":
        in_norm = np.sqrt(in_deg)
        out_norm = np.sqrt(out_deg)
    else:
        in_norm = in_deg
        out_norm = out_deg  # unused by SAGE/GAT but harmless
    return GraphEnv(
        src=jnp.asarray(g.src, jnp.int32),
        dst=jnp.asarray(g.dst, jnp.int32),
        n_dst=g.n_nodes,
        in_norm=jnp.asarray(in_norm),
        out_norm=jnp.asarray(out_norm),
        exchange=_identity_exchange,
        training=False,
        edge_chunk=edge_chunk,
    )


def full_graph_logits(params, state, spec: ModelSpec, g: Graph,
                      edge_chunk: int = 0, device=None) -> np.ndarray:
    """Eval-mode logits of every node of `g`, computed on `device` (None =
    the caller's default device)."""
    with jax.default_device(device):
        env = build_eval_env(g, spec, edge_chunk)
        feat = jnp.asarray(g.feat)
        logits, _ = apply_model(params, state, spec, feat, env)
    return np.asarray(jax.device_get(logits))


def full_graph_embeddings(params, state, spec: ModelSpec, g: Graph,
                          edge_chunk: int = 0,
                          device=None) -> tuple[np.ndarray, np.ndarray]:
    """(hidden [N, H], logits [N, C]): the all-node embedding table the
    serving subsystem (serve.py) and `--dump-embeddings` precompute — the
    penultimate activations (final layer's input) plus the final-layer
    scores, through the SAME eval forward as `full_graph_logits`, so served
    tier-A scores are bitwise the full-eval logits."""
    with jax.default_device(device):
        env = build_eval_env(g, spec, edge_chunk)
        feat = jnp.asarray(g.feat)
        logits, _, hidden = apply_model(params, state, spec, feat, env,
                                        return_hidden=True)
    return (np.asarray(jax.device_get(hidden)),
            np.asarray(jax.device_get(logits)))


def evaluate_trans(name: str, params, state, spec: ModelSpec, g: Graph,
                   result_file: Optional[str] = None,
                   edge_chunk: int = 0, device=None) -> tuple[float, float]:
    """Transductive: val+test in one pass (reference train.py:44-61)."""
    logits = full_graph_logits(params, state, spec, g, edge_chunk, device)
    val_acc = calc_acc(logits[g.val_mask], np.asarray(g.label)[g.val_mask])
    test_acc = calc_acc(logits[g.test_mask], np.asarray(g.label)[g.test_mask])
    buf = "{:s} | Validation Accuracy {:.2%} | Test Accuracy {:.2%}".format(name, val_acc, test_acc)
    _emit(buf, result_file)
    return val_acc, test_acc


def evaluate_induc(name: str, params, state, spec: ModelSpec, g: Graph,
                   mode: str, result_file: Optional[str] = None,
                   edge_chunk: int = 0, device=None) -> float:
    """Inductive: evaluate `mode` ('val'|'test') mask on subgraph g
    (reference train.py:22-41)."""
    logits = full_graph_logits(params, state, spec, g, edge_chunk, device)
    mask = g.val_mask if mode == "val" else g.test_mask
    acc = calc_acc(logits[mask], np.asarray(g.label)[mask])
    buf = "{:s} | Accuracy {:.2%}".format(name, acc)
    _emit(buf, result_file)
    return acc


def gather_parts(art, stacked) -> np.ndarray:
    """[P, pad_inner, ...] stacked per-part rows -> [N, ...] in global node
    order (drops padding via inner_mask, places via global_nid)."""
    stacked = np.asarray(stacked)
    out = np.zeros((int(art.n_inner.sum()),) + stacked.shape[2:], stacked.dtype)
    for p in range(art.n_parts):
        ids = art.global_nid[p][art.inner_mask[p]]
        out[ids] = stacked[p][art.inner_mask[p]]
    return out


# back-compat alias used by tests/benchmarks
def gather_part_logits(art, logits) -> np.ndarray:
    return gather_parts(art, logits)


def _local_part_rows(arr) -> np.ndarray:
    """This process's rows of a parts-sharded [P, R, ...] array, in mesh order."""
    shards = sorted(arr.addressable_shards, key=lambda s: s.index[0].start or 0)
    return np.concatenate([np.asarray(jax.device_get(s.data)) for s in shards], 0)


def _metric_stats(logits, labels, mask, multilabel) -> np.ndarray:
    """Sufficient statistics for accuracy / micro-F1 as a summable vector."""
    lg, lb = logits[mask], labels[mask]
    if multilabel:
        pred = lg > 0
        pos = lb.astype(bool)
        return np.array([np.sum(pos & pred), np.sum(~pos & pred),
                         np.sum(pos & ~pred)], dtype=np.int64)
    correct = np.sum(np.argmax(lg, 1) == lb) if lg.size else 0
    return np.array([correct, lb.shape[0], 0], dtype=np.int64)


def _stats_to_acc(s, multilabel) -> float:
    if multilabel:
        denom = 2 * s[0] + s[1] + s[2]
        return float(2 * s[0] / denom) if denom else 0.0
    return float(s[0] / s[1]) if s[1] else 0.0


def evaluate_mesh(name: str, eval_forward, params, state, blk_eval, tables_full,
                  art_eval, modes: tuple[str, ...],
                  result_file: Optional[str] = None) -> dict[str, float]:
    """Mesh-distributed evaluation: full-rate eval forward over the parts
    mesh, metrics on host. `modes` from {'val','test'}; returns accuracies.
    Capability upgrade over the reference's single-process CPU eval
    (train.py:313-319,427-441). Multi-host: each process computes metric
    statistics from its addressable shards; tiny allgather-sum combines them
    (art_eval then holds only this process's part rows)."""
    out = eval_forward(params, state, blk_eval, tables_full)
    masks = {"val": art_eval.val_mask, "test": art_eval.test_mask}
    accs = {}
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils
        logits_l = _local_part_rows(out)                  # [P_local, R, C]
        for mode in modes:
            s = np.zeros(3, dtype=np.int64)
            for i in range(logits_l.shape[0]):
                m = masks[mode][i] & art_eval.inner_mask[i]
                s += _metric_stats(logits_l[i], art_eval.label[i], m,
                                   art_eval.multilabel)
            total = np.asarray(multihost_utils.process_allgather(s)).sum(0)
            accs[mode] = _stats_to_acc(total, art_eval.multilabel)
    else:
        logits = gather_parts(art_eval, out)
        labels = gather_parts(art_eval, art_eval.label)
        for mode in modes:
            m = gather_parts(art_eval, masks[mode])
            accs[mode] = calc_acc(logits[m], labels[m])
    if "test" in accs and "val" in accs:
        buf = "{:s} | Validation Accuracy {:.2%} | Test Accuracy {:.2%}".format(
            name, accs["val"], accs["test"])
    else:
        buf = "{:s} | Accuracy {:.2%}".format(name, list(accs.values())[0])
    if jax.process_index() == 0:
        _emit(buf, result_file)
    return accs


def _emit(buf: str, result_file: Optional[str]):
    print(buf)
    if result_file is not None:
        with open(result_file, "a+") as f:
            f.write(buf + "\n")
