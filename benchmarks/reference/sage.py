"""Plain float32 reference of the GraphSAGE full-graph training step.

Straight `jax.numpy`, float32, matmuls at `highest` precision, no kernels, no
layouts of the program's, nothing imported from the program. It follows the
published recipe (BNS-GCN, GraphSAGE with mean aggregation over the global
in-degree, `use_pp` precompute of layer 0, LayerNorm + ReLU between layers,
dropout on every layer's input, summed loss over the training rows divided by
their number, Adam) and replays the run's random streams from `--seed`:

  * parameters: torch.nn.Linear's default, uniform(+-1/sqrt(fan_in)), drawn in
    the configuration's storage type from `key(seed)` split per layer;
  * dropout: one mask per layer and step, Bernoulli(1 - rate) over the step's
    padded row block, from `key(seed + 1)` folded with the step and the part.

Aggregation is a whole-graph segment sum, computed in blocks: each node's
neighbour list is cut into fixed-width segments that are gathered and summed,
and the partial sums are summed the same way until one row a node is left. Its
transpose (the backward pass) is the same sum over the reversed edges.

Across parts (`--n-partitions P > 1`) the reference stays one whole-graph
computation. It takes the deployment's layout (which part holds a node, and in
which row) and replays boundary-node sampling from the same keys: for every
ordered pair of parts the sender's boundary nodes (sources of an edge into the
receiver, in id order) get a uniform score from `key(seed)` folded with the
step, the sender and the receiver; the `int(rate * count)` lowest scores are
sent, scaled by count / sent. An edge that crosses parts then carries that
weight (0 if its source was not sampled) in the forward sum and in its
transpose; the `use_pp` precompute exchanges at full rate.

`quant` puts the reference in a lower precision (the control of `correct`):
every matmul and aggregation operand and the stored parameters are rounded to
that type; the arithmetic stays float32. `store` rounds only the parameters,
after every optimizer step, to a storage type (a look at what a program that
keeps them in that type must read; no run of the benchmark uses it).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import counters
from benchmarks.reference.check import leaf_norms

HIGHEST = jax.lax.Precision.HIGHEST
SEG_WIDTHS = (64, 16)          # level 1, then every later level
GATHER_TEMP_ELEMS = 1 << 25    # gathered block of at most 128 MiB of f32
REMAT_BYTES = 1 << 29          # activations above 512 MiB: recompute per layer
ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8


# ---------------------------------------------------------------------------
# segment-sum tables (host, numpy)
# ---------------------------------------------------------------------------

def build_seg_levels(rows: np.ndarray, items: np.ndarray, n_rows: int,
                     n_items: int, widths=SEG_WIDTHS) -> list[np.ndarray]:
    """Tables for out[r] = sum of x[items[e]] over e with rows[e] == r.

    Level k is an int32 table [n_seg_k, width]; entry values index the rows of
    that level's input, and the value `input row count` addresses an appended
    zero row (padding). The last level has exactly n_rows rows, in row order.
    Also returns, for each segment of level 0, the row it belongs to.
    """
    order = np.argsort(rows, kind="stable")
    r = np.asarray(rows)[order].astype(np.int64)
    it = np.asarray(items)[order].astype(np.int64)
    pad_id = n_items
    levels = []
    while True:
        w = widths[min(len(levels), len(widths) - 1)]
        counts = np.bincount(r, minlength=n_rows)
        nseg_row = np.maximum((counts + w - 1) // w, 1)
        seg_off = np.concatenate([[0], np.cumsum(nseg_row)])
        n_seg = int(seg_off[-1])
        start = np.concatenate([[0], np.cumsum(counts)])[:-1]
        pos = np.arange(r.shape[0], dtype=np.int64) - start[r]
        table = np.full((n_seg, w), pad_id, dtype=np.int32)
        table[seg_off[r] + pos // w, pos % w] = it
        levels.append(table)
        seg_row = np.repeat(np.arange(n_rows, dtype=np.int64), nseg_row)
        if len(levels) == 1:
            seg_row0 = seg_row.astype(np.int32)
        if n_seg == n_rows:
            return levels, seg_row0
        r = seg_row
        it = np.arange(n_seg, dtype=np.int64)
        pad_id = n_seg


def build_graph_tables(src: np.ndarray, dst: np.ndarray, n: int) -> dict:
    """Forward (sum over in-edges) and transposed (sum over out-edges) tables
    plus the in-degree, for a graph on n nodes; `*_seg_row` is the node each
    first-level segment sums into."""
    fwd, fwd_row = build_seg_levels(dst, src, n, n)
    bwd, bwd_row = build_seg_levels(src, dst, n, n)
    out = {f"fwd_{k}": t for k, t in enumerate(fwd)}
    out.update({f"bwd_{k}": t for k, t in enumerate(bwd)})
    out["fwd_seg_row"], out["bwd_seg_row"] = fwd_row, bwd_row
    out["in_deg"] = np.bincount(dst, minlength=n).astype(np.float32)
    return out


def split_tables(tables: dict):
    def levels(pre):
        out = []
        while f"{pre}_{len(out)}" in tables:
            out.append(tables[f"{pre}_{len(out)}"])
        return out
    return levels("fwd"), levels("bwd")


def edge_weight_index(tables: dict, part_of: np.ndarray):
    """For every slot of the two first-level tables, where its edge's weight
    sits in a [P, n + 1] matrix W[receiving part, source node] laid flat:
    forward slots (row = destination v, entry = source u) read
    W[part(v), u]; transposed slots (row = u, entry = v) read W[part(v), u].
    Padding slots address a zero row of the summand, so their index is free."""
    n = part_of.shape[0]
    stride = n + 1
    part_pad = np.concatenate([part_of, [0]]).astype(np.int64)
    fwd = (part_pad[tables["fwd_seg_row"]][:, None] * stride
           + tables["fwd_0"].astype(np.int64))
    bwd = (part_pad[tables["bwd_0"]] * stride
           + tables["bwd_seg_row"].astype(np.int64)[:, None])
    return fwd.astype(np.int32), bwd.astype(np.int32)


# ---------------------------------------------------------------------------
# aggregation
# ---------------------------------------------------------------------------

def slot_major(table: np.ndarray):
    """A host table [rows, width] as the device wants it, [width, rows]: the
    long axis last, so that the chip's tiled layout pads nothing (a [rows, 16]
    int32 array is padded eightfold there)."""
    return jnp.asarray(np.ascontiguousarray(table.T))


def _gather_sum(x, table, weight=None):
    """out[r] = sum_k x[table[k, r]] (* wflat[widx[k, r]] with `weight` =
    (wflat, widx)); tables are slot-major, [width, rows]."""
    w, m = table.shape
    d = x.shape[1]
    xp = jnp.concatenate([x, jnp.zeros((1, d), x.dtype)], 0)
    chunk = int(max(1, min(m, GATHER_TEMP_ELEMS // (w * d))))
    n_chunks = -(-m // chunk)
    pad = ((0, 0), (0, n_chunks * chunk - m))

    def blocks(t, fill):
        t = jnp.pad(t, pad, constant_values=fill)
        return t.reshape(w, n_chunks, chunk).transpose(1, 0, 2)

    t = blocks(table, x.shape[0])
    if weight is None:
        out = jax.lax.map(lambda tb: xp[tb].sum(0), t)
    else:
        wflat, widx = weight
        out = jax.lax.map(
            lambda a: (xp[a[0]] * wflat[a[1]][..., None]).sum(0),
            (t, blocks(widx, 0)))
    return out.reshape(n_chunks * chunk, d)[:m]


def seg_sum(levels, x, weight=None):
    for k, table in enumerate(levels):
        x = _gather_sum(x, table, weight if k == 0 else None)
    return x


def _float0_like(tree):
    return jax.tree.map(
        lambda a: np.zeros(a.shape, dtype=jax.dtypes.float0), tree)


def _weights(ew, which):
    return None if ew is None else (ew["wflat"], ew[which])


@jax.custom_vjp
def aggregate(h, fwd, bwd, ew=None):
    """Sum of h[u] over every edge u -> v, at v. With `ew` = {wflat,
    fwd_widx, bwd_widx} every edge's term is scaled by its weight."""
    return seg_sum(fwd, h, _weights(ew, "fwd_widx"))


def _aggregate_fwd(h, fwd, bwd, ew):
    return aggregate(h, fwd, bwd, ew), (fwd, bwd, ew)


def _aggregate_bwd(res, g):
    fwd, bwd, ew = res
    ct_ew = None if ew is None else {
        "wflat": jnp.zeros_like(ew["wflat"]),
        "fwd_widx": _float0_like(ew["fwd_widx"]),
        "bwd_widx": _float0_like(ew["bwd_widx"])}
    return (seg_sum(bwd, g, _weights(ew, "bwd_widx")), _float0_like(fwd),
            _float0_like(bwd), ct_ew)


aggregate.defvjp(_aggregate_fwd, _aggregate_bwd)


# ---------------------------------------------------------------------------
# model
# ---------------------------------------------------------------------------

def _storage_dtype(name: str):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[name]


def quantizer(quant):
    if quant is None:
        return lambda x: x
    qt = jnp.dtype(quant)
    lim = float(jnp.finfo(qt).max)
    return lambda x: jnp.clip(x, -lim, lim).astype(qt).astype(jnp.float32)


def storage_rounder(store):
    """Rounds float32 values to the storage type `store` and back. Through
    `reduce_precision`, which the compiler keeps: it may drop a pair of
    converts as excess precision."""
    if store is None:
        return lambda x: x
    info = jnp.finfo(jnp.dtype(store))
    return lambda x: jax.lax.reduce_precision(x, info.nexp, info.nmant)


def layer_sizes(model: dict, n_feat: int, n_class: int) -> list[int]:
    return ([n_feat] + [model["n_hidden"]] * (model["n_layers"] - 1)
            + [n_class])


def step_flops(model: dict, n_nodes: int, n_edges: int) -> int:
    """FLOPs one training step of this model family needs on a graph of
    `n_nodes` and `n_edges` (for `step_mfu`): counted from the shapes,
    whatever implements them."""
    return counters.sage_step_flops(
        n_nodes, n_edges,
        layer_sizes(model, model["n_feat"], model["n_class"]),
        model.get("n_linear", 0), bool(model.get("use_pp")))


def _linear_init(key, fan_in, fan_out, dtype):
    kw, kb = jax.random.split(key)
    bound = 1.0 / (fan_in ** 0.5)
    w = jax.random.uniform(kw, (fan_in, fan_out), dtype, -bound, bound)
    b = jax.random.uniform(kb, (fan_out,), dtype, -bound, bound)
    return {"w": w.astype(jnp.float32), "b": b.astype(jnp.float32)}


def init_params(model: dict, sizes: list[int], seed: int) -> dict:
    """The recipe's initial parameters, drawn in the storage type and held in
    float32."""
    if not model.get("use_pp"):
        raise NotImplementedError("the reference covers use_pp recipes")
    dtype = _storage_dtype(model["dtype"])
    n_layers = len(sizes) - 1
    n_graph = n_layers - model.get("n_linear", 0)
    keys = jax.random.split(jax.random.key(seed), n_layers)
    params = {}
    for i in range(n_layers):
        fin, fout = sizes[i], sizes[i + 1]
        if i >= n_graph:
            params[f"layer_{i}"] = _linear_init(keys[i], fin, fout, dtype)
        elif i == 0:
            params[f"layer_{i}"] = _linear_init(keys[i], 2 * fin, fout, dtype)
        else:
            k1, k2 = jax.random.split(keys[i])
            params[f"layer_{i}"] = {
                "linear1": _linear_init(k1, fin, fout, dtype),
                "linear2": _linear_init(k2, fin, fout, dtype)}
        if i < n_layers - 1:
            params[f"norm_{i}"] = {"scale": jnp.ones((fout,), jnp.float32),
                                   "bias": jnp.zeros((fout,), jnp.float32)}
    return params


def _linear(p, h, q):
    return jnp.dot(q(h), q(p["w"]), precision=HIGHEST) + p["b"]


def _layer_norm(p, h, eps=1e-5):
    mu = h.mean(-1, keepdims=True)
    var = ((h - mu) ** 2).mean(-1, keepdims=True)
    return (h - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def precompute(feat, fwd, bwd, in_deg, q):
    """use_pp: layer 0's input is [x, mean of the neighbours' x]."""
    mean = aggregate(q(feat), fwd, bwd) / in_deg[:, None]
    return jnp.concatenate([feat, mean], axis=1)


def dropout_keys(seed: int, step, n_parts: int, n_layers: int):
    """keys[part][layer] of the step's dropout masks."""
    out = []
    for part in range(n_parts):
        key = jax.random.fold_in(
            jax.random.fold_in(jax.random.key(seed + 1), step), part)
        out.append(jax.random.split(key, n_layers))
    return out


def dropout_mask(keys, layer: int, layout: dict, rate: float, width: int):
    """One layer's keep-mask by node: every part draws its mask over its own
    padded row block, and a node takes its row's."""
    per_part = jnp.stack([
        jax.random.bernoulli(keys[p][layer], 1.0 - rate,
                             (layout["pad_inner"], width))
        for p in range(layout["n_parts"])])
    return per_part[layout["part_of"], layout["row_of"]]


def boundary_lists(src: np.ndarray, dst: np.ndarray, part_of: np.ndarray,
                   n_parts: int, pad_boundary: int, rate: float) -> dict:
    """Per ordered pair (sender p, receiver j): the sender's boundary nodes in
    id order (padded with n), their count, how many are sent each step and
    the scale count / sent (reference train.py:107-131)."""
    n = part_of.shape[0]
    ps, pd = part_of[src], part_of[dst]
    nodes = np.full((n_parts, n_parts, pad_boundary), n, dtype=np.int32)
    count = np.zeros((n_parts, n_parts), dtype=np.int64)
    for p in range(n_parts):
        for j in range(n_parts):
            if p != j:
                b = np.unique(src[(ps == p) & (pd == j)])
                if len(b) > pad_boundary:
                    raise ValueError(
                        f"part {p} has {len(b)} boundary nodes towards part "
                        f"{j} by the benchmark's own edges; the partition "
                        f"pads to {pad_boundary}")
                nodes[p, j, :len(b)] = b
                count[p, j] = len(b)
    sent = count if rate >= 1.0 else (rate * count).astype(np.int64)
    ratio = np.where(count > 0, sent / np.maximum(count, 1), 0.0)
    scale = np.where(ratio > 0, 1.0 / np.maximum(ratio, 1e-30), 0.0)
    pad_send = max(1, int(sent.max()))
    pad_send = min(((pad_send + 7) // 8) * 8, pad_boundary)
    return {"nodes": nodes, "count": count, "sent": sent,
            "scale": scale.astype(np.float32), "pad_send": pad_send,
            "exact": rate >= 1.0}


def sample_weights(seed: int, step, layout: dict, bl: dict):
    """W[receiving part, source node] of this step, flat with stride n + 1:
    1 for a node of the part itself, count / sent for a sampled boundary node
    of another part, 0 otherwise."""
    n_parts, n = layout["n_parts"], layout["part_of"].shape[0]
    own = (layout["part_of"][None, :] == jnp.arange(n_parts)[:, None])
    w = jnp.concatenate([own.astype(jnp.float32),
                         jnp.zeros((n_parts, 1), jnp.float32)], axis=1)
    pad_b = bl["nodes"].shape[2]
    base = jax.random.fold_in(jax.random.key(seed), step)
    for p in range(n_parts):
        for j in range(n_parts):
            sent = int(bl["sent"][p, j])
            if p == j or sent == 0:
                continue
            key = jax.random.fold_in(jax.random.fold_in(base, p), j)
            scores = jax.random.uniform(key, (pad_b,))
            scores = jnp.where(jnp.arange(pad_b) < int(bl["count"][p, j]),
                               scores, 2.0)
            _, idx = jax.lax.top_k(-scores, bl["pad_send"])
            if bl["exact"]:             # full rate: the list as it stands
                idx = jnp.arange(sent)
            chosen = jnp.asarray(bl["nodes"][p, j])[idx[:sent]]
            w = w.at[j, chosen].set(float(bl["scale"][p, j]))
    return w.reshape(-1)


def forward(params, x0, fwd, bwd, in_deg, drop, model: dict, q, ew=None):
    """`drop` is None (no dropout) or (keys, layout): the masks are drawn
    inside each layer, so that only one is alive at a time."""
    n_layers = sum(k.startswith("layer_") for k in params)
    n_graph = n_layers - model.get("n_linear", 0)
    keep = 1.0 - model["dropout"]
    keys, layout = drop if drop is not None else (None, None)

    def layer(i, h, p, norm, keys):
        if keys is not None:
            mask = dropout_mask(keys, i, layout, model["dropout"], h.shape[1])
            h = jnp.where(mask, h / keep, 0.0)
        if i == 0 or i >= n_graph:
            h = _linear(p, h, q)
        else:
            mean = aggregate(q(h), fwd, bwd, ew) / in_deg[:, None]
            h = _linear(p["linear1"], h, q) + _linear(p["linear2"], mean, q)
        if norm is not None:
            h = jax.nn.relu(_layer_norm(norm, h))
        return h

    # on a large graph (an activation over REMAT_BYTES) the backward pass
    # keeps each layer's input only and recomputes the rest, so that it fits;
    # a small one pays no recomputation
    big = x0.shape[0] * model["n_hidden"] * 4 > REMAT_BYTES
    run_layer = jax.checkpoint(layer, static_argnums=0) if big else layer
    h = x0
    for i in range(n_layers):
        h = run_layer(i, h, params[f"layer_{i}"], params.get(f"norm_{i}"),
                      keys)
    return h


def loss_fn(params, x0, label, train_mask, fwd, bwd, in_deg, drop,
            model: dict, q, weight=None, ew=None):
    """Summed loss over the training rows over their number. `weight`, a
    per-row factor, is for fault tests only."""
    logits = forward(params, x0, fwd, bwd, in_deg, drop, model, q, ew)
    if model.get("multilabel"):
        per = (jnp.maximum(logits, 0) - logits * label
               + jnp.log1p(jnp.exp(-jnp.abs(logits)))).sum(-1)
    else:
        logp = jax.nn.log_softmax(logits, axis=-1)
        per = -jnp.take_along_axis(logp, label[:, None], axis=-1)[:, 0]
    w = train_mask.astype(jnp.float32)
    if weight is not None:
        w = w * weight
    return jnp.sum(per * w) / jnp.sum(w)


def adam_update(params, grads, mu, nu, count, lr, q):
    count = count + 1
    mu = jax.tree.map(lambda m, g: ADAM_B1 * m + (1 - ADAM_B1) * g, mu, grads)
    nu = jax.tree.map(lambda v, g: ADAM_B2 * v + (1 - ADAM_B2) * g * g,
                      nu, grads)
    c1 = 1 - ADAM_B1 ** count
    c2 = 1 - ADAM_B2 ** count
    params = jax.tree.map(
        lambda p, m, v: q(p - lr * (m / c1) / (jnp.sqrt(v / c2) + ADAM_EPS)),
        params, mu, nu)
    return params, mu, nu, count


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def build_step(model: dict, seed: int, lay: dict, bl, n_layers: int, q,
               q_store=lambda x: x):
    """One training step as a function of arrays only (to be jitted)."""
    def step(params, mu, nu, count, step_idx, x0, label, train_mask,
             fwd, bwd, in_deg, weight, widx):
        drop = None
        if model["dropout"] > 0.0:
            drop = (dropout_keys(seed, step_idx, lay["n_parts"], n_layers),
                    lay)
        ew = None
        if bl is not None:
            ew = {"wflat": sample_weights(seed, step_idx, lay, bl),
                  "fwd_widx": widx[0], "bwd_widx": widx[1]}
        loss, grads = jax.value_and_grad(loss_fn)(
            params, x0, label, train_mask, fwd, bwd, in_deg, drop,
            model, q, weight, ew)
        params, mu, nu, count = adam_update(params, grads, mu, nu, count,
                                            model["lr"],
                                            lambda x: q(q_store(x)))
        return params, mu, nu, count, loss, grads
    return step


def one_part_layout(n: int, pad_rows: int) -> dict:
    return {"n_parts": 1, "pad_inner": pad_rows, "pad_boundary": 8,
            "rate": 1.0, "part_of": np.zeros(n, np.int32),
            "row_of": np.arange(n, dtype=np.int32)}


def run_steps(graph: dict, tables: dict, model: dict, seed: int,
              layout: dict, n_steps: int = 3, quant=None, row_weight=None,
              exchange: bool = True, store=None) -> dict:
    """Train `n_steps` steps from the seed on the whole training graph laid
    out as `layout` says ({n_parts, pad_inner, pad_boundary, rate, part_of,
    row_of}). Returns the step losses and the per-leaf norms of the first
    gradient and of the parameters' change over the steps. `row_weight` (a
    per-row factor on the loss) and `exchange=False` (no halo rows at all)
    plant faults for tests."""
    q = quantizer(quant)
    fwd_np, bwd_np = split_tables(tables)
    fwd = [slot_major(t) for t in fwd_np]
    bwd = [slot_major(t) for t in bwd_np]
    in_deg = jnp.asarray(tables["in_deg"])
    feat = jnp.asarray(graph["feat"], jnp.float32)
    multilabel = bool(model.get("multilabel"))
    label = jnp.asarray(graph["label"],
                        jnp.float32 if multilabel else jnp.int32)
    train_mask = jnp.asarray(graph["train_mask"])
    n_class = int(label.shape[1]) if multilabel else int(model["n_class"])
    sizes = layer_sizes(model, int(feat.shape[1]), n_class)
    n_layers = len(sizes) - 1
    lay = {**layout, "part_of": jnp.asarray(layout["part_of"], jnp.int32),
           "row_of": jnp.asarray(layout["row_of"], jnp.int32)}
    n_parts = int(layout["n_parts"])
    bl = widx = None
    if n_parts > 1:
        part_np = np.asarray(layout["part_of"])
        bl = boundary_lists(graph["src"], graph["dst"], part_np, n_parts,
                            int(layout["pad_boundary"]),
                            float(layout["rate"]) if exchange else 0.0)
        widx = tuple(slot_major(a)
                     for a in edge_weight_index(tables, part_np))

    with jax.default_matmul_precision("highest"):
        x0 = jax.jit(lambda f, a, b, d: precompute(f, a, b, d, q))(
            feat, fwd, bwd, in_deg)
        del feat
        params = jax.tree.map(q, init_params(model, sizes, seed))
        p0 = params
        mu = jax.tree.map(jnp.zeros_like, params)
        nu = jax.tree.map(jnp.zeros_like, params)
        count = jnp.zeros((), jnp.float32)
        weight = None if row_weight is None else jnp.asarray(row_weight)

        step = jax.jit(build_step(model, seed, lay, bl, n_layers, q,
                                  storage_rounder(store)))
        losses, g1 = [], None
        for k in range(n_steps):
            params, mu, nu, count, loss, grads = step(
                params, mu, nu, count, jnp.uint32(k), x0, label, train_mask,
                fwd, bwd, in_deg, weight, widx)
            losses.append(float(loss))
            if k == 0:
                g1 = leaf_norms(grads)
            del grads
        dp = leaf_norms(jax.tree.map(lambda a, b: a - b, params, p0))
    return {"losses": losses, "grad1": g1, "dparam": dp}
