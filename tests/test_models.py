"""Model-semantics tests: eval-path forward vs hand-rolled dense numpy math."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bnsgcn_tpu.data.graph import synthetic_graph
from bnsgcn_tpu.evaluate import build_eval_env, full_graph_logits
from bnsgcn_tpu.models.gnn import ModelSpec, apply_model, init_params


def _dense_gcn(g, params, spec):
    """Eval-path GCN in numpy: h/sqrt(out_deg) -> A @ . -> /sqrt(in_deg) -> W."""
    a = g.dense_adj()
    in_n = np.sqrt(g.in_degrees())[:, None]
    out_n = np.sqrt(g.out_degrees())[:, None]
    h = np.asarray(g.feat, np.float64)
    for i in range(spec.n_layers):
        p = jax.tree.map(lambda x: np.asarray(x, np.float64), params[f"layer_{i}"])
        if i < spec.n_graph_layers:
            h = (a @ (h / out_n)) / in_n @ p["w"] + p["b"]
        else:
            h = h @ p["w"] + p["b"]
        if i < spec.n_layers - 1:
            if spec.norm == "layer":
                q = params[f"norm_{i}"]
                mu = h.mean(-1, keepdims=True)
                var = ((h - mu) ** 2).mean(-1, keepdims=True)
                h = (h - mu) / np.sqrt(var + 1e-5) * np.asarray(q["scale"]) + np.asarray(q["bias"])
            h = np.maximum(h, 0)
    return h


def _dense_sage(g, params, spec):
    a = g.dense_adj()
    deg = g.in_degrees().astype(np.float64)[:, None]
    h = np.asarray(g.feat, np.float64)
    for i in range(spec.n_layers):
        pr = params[f"layer_{i}"]
        if i < spec.n_graph_layers:
            ah = (a @ h) / deg
            if spec.use_pp and i == 0:
                p = jax.tree.map(np.asarray, pr)
                h = np.concatenate([h, ah], 1) @ p["w"] + p["b"]
            else:
                p1 = jax.tree.map(np.asarray, pr["linear1"])
                p2 = jax.tree.map(np.asarray, pr["linear2"])
                h = h @ p1["w"] + p1["b"] + ah @ p2["w"] + p2["b"]
        else:
            p = jax.tree.map(np.asarray, pr)
            h = h @ p["w"] + p["b"]
        if i < spec.n_layers - 1:
            if spec.norm == "layer":
                q = params[f"norm_{i}"]
                mu = h.mean(-1, keepdims=True)
                var = ((h - mu) ** 2).mean(-1, keepdims=True)
                h = (h - mu) / np.sqrt(var + 1e-5) * np.asarray(q["scale"]) + np.asarray(q["bias"])
            h = np.maximum(h, 0)
    return h


@pytest.mark.parametrize("norm", ["layer", None])
def test_gcn_eval_matches_dense(norm):
    g = synthetic_graph(n_nodes=40, avg_degree=5, n_feat=6, n_class=3, seed=7)
    spec = ModelSpec("gcn", (6, 8, 3), norm=norm, dropout=0.0)
    params, state = init_params(jax.random.key(0), spec)
    logits = full_graph_logits(params, state, spec, g)
    expect = _dense_gcn(g, params, spec)
    np.testing.assert_allclose(logits, expect, rtol=1e-4, atol=1e-4)


@pytest.mark.quickgate
@pytest.mark.parametrize("use_pp", [False, True])
def test_sage_eval_matches_dense(use_pp):
    g = synthetic_graph(n_nodes=35, avg_degree=4, n_feat=5, n_class=4, seed=8)
    spec = ModelSpec("graphsage", (5, 8, 4), norm="layer", dropout=0.0, use_pp=use_pp)
    params, state = init_params(jax.random.key(1), spec)
    logits = full_graph_logits(params, state, spec, g)
    expect = _dense_sage(g, params, spec)
    np.testing.assert_allclose(logits, expect, rtol=1e-4, atol=1e-4)


def test_sage_n_linear_tail():
    g = synthetic_graph(n_nodes=30, avg_degree=4, n_feat=5, n_class=3, seed=9)
    spec = ModelSpec("graphsage", (5, 8, 8, 3), n_linear=2, norm="layer", dropout=0.0)
    params, state = init_params(jax.random.key(2), spec)
    logits = full_graph_logits(params, state, spec, g)
    expect = _dense_sage(g, params, spec)
    np.testing.assert_allclose(logits, expect, rtol=1e-4, atol=1e-4)
    # tail layers must be plain {'w','b'} linears
    assert set(params["layer_2"].keys()) == {"w", "b"}


def test_gat_eval_shapes_and_softmax():
    g = synthetic_graph(n_nodes=20, avg_degree=4, n_feat=5, n_class=3, seed=10)
    spec = ModelSpec("gat", (5, 8, 3), norm="layer", dropout=0.0, heads=2, use_pp=True)
    params, state = init_params(jax.random.key(3), spec)
    logits = full_graph_logits(params, state, spec, g)
    assert logits.shape == (g.n_nodes, 3)
    assert np.all(np.isfinite(logits))


def test_dropout_off_in_eval_and_deterministic():
    g = synthetic_graph(n_nodes=25, avg_degree=4, n_feat=5, n_class=3, seed=11)
    spec = ModelSpec("graphsage", (5, 8, 3), norm="layer", dropout=0.5)
    params, state = init_params(jax.random.key(4), spec)
    a = full_graph_logits(params, state, spec, g)
    b = full_graph_logits(params, state, spec, g)
    np.testing.assert_array_equal(a, b)


# ----------------------------------------------------------------------------
# aggregating on the narrow side: agg(h W) / norm + b == (agg(h) / norm) W + b
# ----------------------------------------------------------------------------

def _train_env(n=30, n_edges=90, model="gcn", seed=12):
    """A training-mode env on one part (identity exchange) over a random
    graph without self-loops in which nodes 0-3 have no in-neighbour, and
    the aggregation widths each call gathered."""
    from bnsgcn_tpu.models.gnn import GraphEnv
    from bnsgcn_tpu.ops.spmm import agg_sum
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n, n_edges)
    dst = rng.integers(4, n, n_edges)
    in_deg = np.maximum(np.bincount(dst, minlength=n), 1).astype(np.float32)
    out_deg = np.maximum(np.bincount(src, minlength=n), 1).astype(np.float32)
    gcn = model == "gcn"
    src_j, dst_j = jnp.asarray(src, jnp.int32), jnp.asarray(dst, jnp.int32)
    widths = []

    def aggregate(h_ext):
        widths.append(h_ext.shape[1])
        return agg_sum(h_ext, src_j, dst_j, n)

    env = GraphEnv(src=src_j, dst=dst_j, n_dst=n,
                   in_norm=jnp.asarray(np.sqrt(in_deg) if gcn else in_deg),
                   out_norm=jnp.asarray(np.sqrt(out_deg)),
                   exchange=lambda i, h: (h, None), aggregate=aggregate)
    return env, widths


@pytest.mark.parametrize("model,sizes,narrow", [
    # layer 0 without use_pp narrows past half its width: it projects
    # first and gains a backward aggregation; the last layer narrows
    ("gcn", (40, 8, 8, 3), (0, 2)),
    # layer 0 narrows by less than half: the wide order is cheaper there
    ("gcn", (12, 8, 8, 3), (2,)),
    # the Reddit recipe's shape: pp layer 0, a 16 -> 16 layer, 16 -> 5
    ("graphsage", (12, 16, 16, 5), (2,)),
    # nothing narrows: every layer keeps the wide order
    ("graphsage", (12, 16, 16, 16), ()),
])
def test_narrow_side_matches_wide_order(monkeypatch, model, sizes, narrow):
    """A layer that narrows projects before it aggregates and matches the
    wide order, forward and every gradient, float32; rows with no
    in-neighbour read the bias; the widths each aggregation gathered are the
    ones `agg_widths` counts for the run header."""
    from bnsgcn_tpu.models import gnn
    from bnsgcn_tpu.trainer import agg_widths
    spec = ModelSpec(model, sizes, norm="layer", dropout=0.0,
                     use_pp=model == "graphsage")
    assert tuple(i for i in range(spec.n_layers)
                 if gnn.projects_first(spec, i)) == narrow
    params, state = init_params(jax.random.key(6), spec)
    n = 30
    feat = jnp.asarray(np.random.default_rng(13).normal(
        size=(n, 2 * sizes[0] if spec.use_pp else sizes[0])), jnp.float32)
    cot = jnp.asarray(np.random.default_rng(14).normal(size=(n, sizes[-1])),
                      jnp.float32)

    def run():
        env, widths = _train_env(n=n, model=model)

        def loss(p):
            out, _ = apply_model(p, state, spec, feat, env)
            return jnp.sum(out * cot), out
        (_, out), grads = jax.value_and_grad(loss, has_aux=True)(params)
        return np.asarray(out), jax.tree.map(np.asarray, grads), widths

    out, grads, widths = run()
    fwd, bwd, narrow_layers = agg_widths(spec)
    layers = range(int(spec.use_pp), spec.n_graph_layers)
    assert widths == fwd == [sizes[i + 1] if i in narrow else sizes[i]
                             for i in layers]
    # backward: every layer whose aggregated rows hold a parameter
    assert bwd == [w for i, w in zip(layers, fwd) if i > 0 or i in narrow]
    assert narrow_layers == [{"layer": i, "fin": sizes[i],
                              "fout": sizes[i + 1]} for i in narrow]
    with monkeypatch.context() as m:
        m.setattr(gnn, "projects_first", lambda spec, i: False)
        out_w, grads_w, widths_w = run()
    assert widths_w == [sizes[i] for i in layers]
    scale = np.abs(out_w).max()
    assert np.abs(out - out_w).max() <= 1e-5 * scale
    for (path, g), gw in zip(jax.tree_util.tree_leaves_with_path(grads),
                             jax.tree.leaves(grads_w)):
        assert np.abs(g - gw).max() <= 1e-5 * (np.abs(gw).max() + 1e-12), \
            path
    if model == "gcn" and spec.n_layers - 1 in narrow:
        # the last layer has no norm after it: an isolated row is its bias
        b = np.asarray(params[f"layer_{spec.n_layers - 1}"]["b"])
        np.testing.assert_allclose(out[:4], np.broadcast_to(b, (4, b.size)),
                                   rtol=0, atol=1e-6)
