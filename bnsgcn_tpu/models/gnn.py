"""GCN / GraphSAGE / GAT as pure functions over explicit parameter pytrees.

Semantics mirror the reference model layer-for-layer (module/model.py,
module/layer.py, module/sync_bn.py) but the implementation is JAX-native:
aggregation is gather+segment_sum (ops/spmm.py), the halo exchange is injected
via `GraphEnv.exchange` (a shard_map collective in distributed training, the
identity on a single device), and cross-partition BatchNorm moments travel by
`lax.psum` instead of a custom autograd.Function.

Reference math preserved exactly:
  * GCN train: h/out_norm -> copy_u/sum -> /in_norm -> linear
    (module/layer.py:26-46); eval recomputes norms as sqrt(graph degrees).
  * GraphSAGE: linear1(h_self) + linear2(sum(h_nbr)/in_deg) with the *global*
    in-degree (module/layer.py:79-103, train.py:380); use_pp layer 0 is a
    single Linear(2*in, out) over the precomputed [feat, mean_nbr] concat.
  * A GCN / GraphSAGE layer that narrows (`projects_first`) runs its
    linear's matmul before the aggregation instead of after: the same sum,
    fewer columns gathered; the bias still comes after.
  * GAT: DGL-GATConv equivalent (shared fc, additive attention, leaky_relu 0.2,
    edge softmax, feat/attn dropout, bias), mean over heads
    (module/model.py:102,111-132). Absent sampled halos are removed from the
    softmax by an edge mask — the static-shape replacement for the reference's
    per-epoch bipartite graph rebuild (train.py:256-281).
  * layer stack: dropout -> exchange -> layer -> norm -> activation with
    `n_linear` dense tail layers (module/model.py:42-58).
  * SyncBatchNorm: moments summed over all real local rows, psum'd across
    parts, normalized by whole_size = global n_train (module/sync_bn.py:15-22).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from bnsgcn_tpu.ops.spmm import agg_sum, segment_softmax
from bnsgcn_tpu.config import Config
from bnsgcn_tpu.parallel.feat import feat_shardable
from bnsgcn_tpu.utils import traceparse as tp


@dataclass(frozen=True)
class ModelSpec:
    model: str                         # 'gcn' | 'graphsage' | 'gat'
    layer_sizes: tuple[int, ...]       # (n_feat, hidden, ..., n_class)
    n_linear: int = 0
    norm: Optional[str] = "layer"
    dropout: float = 0.5
    use_pp: bool = False
    heads: int = 1
    train_size: int = 0                # global n_train, for SyncBN whole_size

    @property
    def n_layers(self) -> int:
        return len(self.layer_sizes) - 1

    @property
    def n_graph_layers(self) -> int:
        return self.n_layers - self.n_linear


def spec_from_config(cfg: Config) -> ModelSpec:
    # GAT is always use_pp in the reference trainer (train.py:222)
    use_pp = True if cfg.model == "gat" else cfg.use_pp
    return ModelSpec(
        model=cfg.model,
        layer_sizes=tuple(cfg.layer_sizes()),
        n_linear=cfg.n_linear,
        norm=cfg.norm,
        dropout=cfg.dropout,
        use_pp=use_pp,
        heads=cfg.heads,
        train_size=cfg.n_train,
    )


@dataclass
class GraphEnv:
    """Everything a forward pass needs to know about the (local) graph.

    Index space: edge endpoints index the *extended* node array
    [inner nodes ; halo slots]; `dst` always lands in [0, n_dst] where n_dst is
    the inner count (dst == n_dst is the padded-edge trash row).
    """
    src: Optional[jax.Array]           # [E] int32, extended index space (None when the
    dst: Optional[jax.Array]           # ELL aggregate owns the graph structure)
    n_dst: int
    in_norm: jax.Array                 # [n_dst] float — GCN: sqrt(in_deg); SAGE: in_deg
    out_norm: Optional[jax.Array]      # [n_src_ext] float — GCN: sqrt(out_deg) incl. halos
    exchange: Callable[[int, jax.Array], tuple[jax.Array, Optional[jax.Array]]]
    # exchange(layer, h[n_dst, d]) -> (h_ext [n_src_ext, d], presence [n_src_ext] bool|None)
    #
    # Contract: the halo tail of h_ext need NOT come from a live collective
    # this step — it only has to be zero wherever presence is False, so
    # sum-aggregation skips absent slots and the GAT softmax masks them.
    # Besides the per-epoch halo_apply, trainer.py injects: the
    # --halo-refresh cached step (this epoch's refreshed chunk live, every
    # other row a stop-gradient cached block from an earlier epoch, presence
    # merged accordingly) and --halo-mode grad-only (all-zero halo tail,
    # presence False on every halo slot — aggregation over local rows only).
    gat_feat0: Optional[tuple[jax.Array, Optional[jax.Array]]] = None
    training: bool = True
    rng: Optional[jax.Array] = None
    edge_chunk: int = 0
    axis_name: Optional[str] = None    # mesh axis for SyncBN psum
    inner_mask: Optional[jax.Array] = None  # [n_dst] bool, real (non-padded) rows
    aggregate: Optional[Callable] = None
    # aggregate(h_ext [n_src_ext, d]) -> [n_dst, d]: scatter-free ELL SpMM
    # (ops/ell.py) when set; falls back to segment_sum otherwise
    gat_ell: Optional[tuple] = None
    # (GatEllSpec, arrays dict): dense per-row GAT attention over the ELL
    # layout (ops/ell_attention.py) when set; segment softmax otherwise
    remat: bool = False                # jax.checkpoint each layer (HBM for FLOPs+comm)
    replica_axis: Optional[str] = None # 2-D ('replicas','parts') mesh: SyncBN
    n_replicas: int = 1                # moments mean over replicas too (one
                                       # fused psum over both axes, divided by
                                       # whole_size * n_replicas — each replica
                                       # sees the whole graph). None/1 = the
                                       # historical parts-only reduction.
    agg_exchange: Optional[Callable] = None
    # agg_exchange(layer, h [n_dst, d], scale_out_norm, w) -> [n_dst, d']:
    # (w as in env_agg_exchange: None, or the projection a narrowing layer
    # applies to both source sides after the exchange)
    # fused exchange + sum-aggregation override (--overlap split re-threads
    # the layer body as start-exchange -> interior-agg -> finish-exchange ->
    # frontier-agg -> merge through this seam). None = the historical
    # exchange-then-aggregate path. Under --halo-refresh the cached step
    # threads the same split body through the ~K-x-smaller partial-refresh
    # exchange and merges stored halo rows after halo_finish — a cache-hit
    # epoch's "collective" is tiny, so the split is near-pure compute.
    feat_axis: Optional[str] = None    # 3-D ('replicas','parts','feat') mesh
    n_feat_shards: int = 1             # (parallel/feat.py): shardable layers
                                       # run exchange+SpMM on an H/T column
                                       # slice and psum the weight-shard
                                       # partials over 'feat' (one collective
                                       # per layer). None/1 = the historical
                                       # full-width bodies, bit-identical.


def env_agg_sum(env: "GraphEnv", h_ext: jax.Array) -> jax.Array:
    """sum_{e:(u->v)} h_ext[u] at v via the env's preferred SpMM backend."""
    if env.aggregate is not None:
        return env.aggregate(h_ext)
    return agg_sum(h_ext, env.src, env.dst, env.n_dst, env.edge_chunk)


@jax.named_scope(tp.LINEAR)
def project(h: jax.Array, w: jax.Array) -> jax.Array:
    """h @ w on the rows a narrowing layer aggregates (see projects_first)."""
    return h @ w


def env_agg_exchange(env: "GraphEnv", i: int, h: jax.Array,
                     scale_out_norm: bool = False,
                     w: Optional[jax.Array] = None) -> jax.Array:
    """One layer's exchange + sum-aggregation: h [n_dst, d] -> [n_dst, d]
    ([n_dst, w.shape[1]] with `w`).

    `w` projects the extended rows AFTER the exchange and before the
    aggregation, so the halo sees what it sees without it.
    `scale_out_norm` divides the extended rows by env.out_norm BEFORE
    aggregating (the GCN symmetric norm, module/layer.py:26-46). Default
    path is the historical fused exchange-then-aggregate, op for op; when
    `env.agg_exchange` is set (--overlap split), it runs the interior/
    frontier split so the collective overlaps interior compute."""
    if env.agg_exchange is not None:
        return env.agg_exchange(i, h, scale_out_norm, w)
    with jax.named_scope(tp.HALO_EXCHANGE):
        h_ext, _ = env.exchange(i, h)
    if w is not None:
        h_ext = project(h_ext, w)
    if scale_out_norm:
        h_ext = (h_ext / env.out_norm[:, None]).astype(h_ext.dtype)
    return env_agg_sum(env, h_ext)


# ----------------------------------------------------------------------------
# init
# ----------------------------------------------------------------------------

def _uniform(key, shape, bound, dtype=jnp.float32):
    return jax.random.uniform(key, shape, dtype, minval=-bound, maxval=bound)


def _linear_init(key, fan_in, fan_out, dtype=jnp.float32):
    """uniform(-1/sqrt(fan_in), +1/sqrt(fan_in)) for W and b — the reference's
    reset_parameters (module/layer.py:20-24) and torch.nn.Linear default."""
    kw, kb = jax.random.split(key)
    bound = 1.0 / (fan_in ** 0.5)
    return {"w": _uniform(kw, (fan_in, fan_out), bound, dtype),
            "b": _uniform(kb, (fan_out,), bound, dtype)}


def _xavier_normal(key, shape, fan_in, fan_out, gain, dtype=jnp.float32):
    std = gain * (2.0 / (fan_in + fan_out)) ** 0.5
    return std * jax.random.normal(key, shape, dtype)


def init_params(key: jax.Array, spec: ModelSpec, dtype=jnp.float32):
    """Returns (params, state). `state` holds SyncBN running stats."""
    params: dict[str, Any] = {}
    state: dict[str, Any] = {}
    keys = jax.random.split(key, spec.n_layers)
    for i in range(spec.n_layers):
        fin, fout = spec.layer_sizes[i], spec.layer_sizes[i + 1]
        name = f"layer_{i}"
        if i >= spec.n_graph_layers:                    # dense tail
            params[name] = _linear_init(keys[i], fin, fout, dtype)
        elif spec.model == "gcn":
            params[name] = _linear_init(keys[i], fin, fout, dtype)
        elif spec.model == "graphsage":
            if spec.use_pp and i == 0:
                # precompute doubles layer-0 input width (module/layer.py:59)
                params[name] = _linear_init(keys[i], 2 * fin, fout, dtype)
            else:
                k1, k2 = jax.random.split(keys[i])
                params[name] = {"linear1": _linear_init(k1, fin, fout, dtype),
                                "linear2": _linear_init(k2, fin, fout, dtype)}
        elif spec.model == "gat":
            kf, kl, kr = jax.random.split(keys[i], 3)
            h = spec.heads
            params[name] = {
                "w": _xavier_normal(kf, (fin, h * fout), fin, h * fout, 2.0 ** 0.5, dtype),
                "attn_l": _xavier_normal(kl, (h, fout), fout, 1, 2.0 ** 0.5, dtype),
                "attn_r": _xavier_normal(kr, (h, fout), fout, 1, 2.0 ** 0.5, dtype),
                "bias": jnp.zeros((h * fout,), dtype),
            }
        else:
            raise ValueError(spec.model)
        if i < spec.n_layers - 1 and spec.norm is not None:
            if spec.norm == "layer":
                params[f"norm_{i}"] = {"scale": jnp.ones((fout,), dtype),
                                       "bias": jnp.zeros((fout,), dtype)}
            elif spec.norm == "batch":
                params[f"norm_{i}"] = {"scale": jnp.ones((fout,), dtype),
                                       "bias": jnp.zeros((fout,), dtype)}
                state[f"norm_{i}"] = {"mean": jnp.zeros((fout,), jnp.float32),
                                      "var": jnp.ones((fout,), jnp.float32)}
    return params, state


# ----------------------------------------------------------------------------
# building blocks
# ----------------------------------------------------------------------------

@jax.named_scope(tp.DROPOUT)
def _dropout(h, rate, rng, training):
    if not training or rate <= 0.0 or rng is None:
        return h
    keep = 1.0 - rate
    mask = jax.random.bernoulli(rng, keep, h.shape)
    return jnp.where(mask, h / keep, 0.0).astype(h.dtype)


@jax.named_scope(tp.DROPOUT)
def _dropout_heads(a, rate, rng, training, n_total, off):
    """Last-dim (head) dropout whose mask is drawn at the FULL width
    `n_total` and sliced at `off` — a feat-sharded GAT layer therefore
    reproduces exactly the feat=1 run's per-head masks (the exactness tests
    compare feat=T against feat=1 with dropout on). off=None with
    n_total == a.shape[-1] is bit-identical to `_dropout`."""
    if not training or rate <= 0.0 or rng is None:
        return a
    keep = 1.0 - rate
    mask = jax.random.bernoulli(rng, keep, a.shape[:-1] + (n_total,))
    if off is not None:
        mask = jax.lax.dynamic_slice_in_dim(mask, off, a.shape[-1], a.ndim - 1)
    return jnp.where(mask, a / keep, 0.0).astype(a.dtype)


# ----------------------------------------------------------------------------
# feat-axis (tensor-parallel) layer body — parallel/feat.py's contract:
# slice the input activations to this shard's H/T columns, run the (sliced)
# exchange + SpMM and the local weight-row-shard matmul, then ONE psum over
# 'feat' where the layer transitions shards. Dropout always fires on the
# FULL pre-slice activations (identical masks to feat=1); biases are
# replicated and added once, after the psum.
# ----------------------------------------------------------------------------

def _feat_slice(env: "GraphEnv", h: jax.Array) -> jax.Array:
    """This feat shard's column slice h[:, f*k:(f+1)*k], k = width/T."""
    k = h.shape[-1] // env.n_feat_shards
    f = jax.lax.axis_index(env.feat_axis)
    return jax.lax.dynamic_slice_in_dim(h, f * k, k, h.ndim - 1)


def _feat_psum(env: "GraphEnv", x: jax.Array) -> jax.Array:
    return jax.lax.psum(x, env.feat_axis)


def _feat_layer(p, i, h, env: "GraphEnv", spec: "ModelSpec") -> jax.Array:
    """One feat-sharded GCN / GraphSAGE / dense layer (h arrives full-width,
    already dropped out; returns the full-width psummed output). The halo
    exchange inside rides the H/T slice — its wire bytes drop T x."""
    is_graph = i < spec.n_graph_layers
    if not is_graph or (env.training and spec.use_pp and i == 0):
        # pure dense matmul: the linear tail and the precomputed layer 0
        with jax.named_scope(tp.LINEAR):
            part = _feat_slice(env, h) @ p["w"]
            return _feat_psum(env, part) + p["b"]
    if spec.model == "gcn":
        s = env_agg_exchange(env, i, _feat_slice(env, h), scale_out_norm=True)
        with jax.named_scope(tp.LINEAR):
            part = (s / env.in_norm[:, None]).astype(h.dtype) @ p["w"]
            return _feat_psum(env, part) + p["b"]
    if (not env.training) and spec.use_pp and i == 0:
        # eval pp layer 0: cat(feat, mean) @ W — the concat consumes the
        # full-width mean, so only the linear shards (full-rate eval runs
        # once per log_every; the training exchange is what the axis thins)
        ah = env_agg_exchange(env, i, h) / env.in_norm[:, None]
        with jax.named_scope(tp.LINEAR):
            part = _feat_slice(
                env, jnp.concatenate([h[:env.n_dst], ah], 1)) @ p["w"]
            return _feat_psum(env, part) + p["b"]
    hs = _feat_slice(env, h)
    ah = (env_agg_exchange(env, i, hs) / env.in_norm[:, None]).astype(h.dtype)
    with jax.named_scope(tp.LINEAR):
        part = hs[:env.n_dst] @ p["linear1"]["w"] + ah @ p["linear2"]["w"]
        return _feat_psum(env, part) + p["linear1"]["b"] + p["linear2"]["b"]


@jax.named_scope(tp.NORM)
def _layer_norm(p, h, eps=1e-5):
    # stats in f32 (bf16 activations would lose the variance), output in h.dtype
    hf = h.astype(jnp.float32)
    mu = hf.mean(-1, keepdims=True)
    var = ((hf - mu) ** 2).mean(-1, keepdims=True)
    out = (hf - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]
    return out.astype(h.dtype)


@jax.named_scope(tp.NORM)
def _sync_batch_norm(p, st, h, env: GraphEnv, whole_size, momentum=0.1, eps=1e-5):
    """module/sync_bn.py:10-28 — moments over all real rows of all parts,
    normalized by whole_size (= global n_train in the reference trainer)."""
    if env.training:
        if whole_size <= 0:
            raise ValueError("SyncBatchNorm requires train_size (global n_train) > 0; "
                             "is n_train missing from the partition meta?")
        hm = h if env.inner_mask is None else jnp.where(env.inner_mask[:, None], h, 0.0)
        sum_x = hm.sum(0)
        sum_x2 = (hm * hm).sum(0)
        if env.axis_name is not None:
            # replica-axis meshes fold the cross-replica moment mean into
            # the same psum (one collective over both axes; whole_size
            # scales by n_replicas below because each replica holds the
            # full graph, not a shard of it). The feat axis rides the same
            # psum the same way: its moments are identical per shard
            # (computed on the full post-psum activations), so summing
            # them and scaling whole_size by n_feat_shards keeps the value
            # exact with still ONE collective.
            if env.replica_axis is None and env.feat_axis is None:
                axes = env.axis_name
            else:
                axes = tuple(a for a in (env.replica_axis, env.axis_name,
                                         env.feat_axis) if a is not None)
            sum_x = jax.lax.psum(sum_x, axes)
            sum_x2 = jax.lax.psum(sum_x2, axes)
        whole_size = (whole_size * max(env.n_replicas, 1)
                      * max(env.n_feat_shards, 1))
        mean = sum_x / whole_size
        # the reference's estimator (module/sync_bn.py:19-20) sums over ALL
        # local rows but divides by whole_size = n_train; when n_train < the
        # summed row count the quirky formula can go negative (where the
        # reference would silently sqrt(NaN)) — clamp at 0, a no-op whenever
        # the estimate is a valid variance
        var = jnp.maximum((sum_x2 - mean * sum_x) / whole_size, 0.0)
        new_st = {"mean": (1 - momentum) * st["mean"] + momentum * jax.lax.stop_gradient(mean),
                  "var": (1 - momentum) * st["var"] + momentum * jax.lax.stop_gradient(var)}
    else:
        mean, var = st["mean"], st["var"]
        new_st = st
    x_hat = (h - mean) / jnp.sqrt(var + eps)
    return x_hat * p["scale"] + p["bias"], new_st


@jax.named_scope(tp.LINEAR)
def _linear(p, h):
    return h @ p["w"] + p["b"]


def projects_first(spec: ModelSpec, i: int) -> bool:
    """Whether GCN / GraphSAGE graph layer i aggregates on its narrow side:
    agg(h W) / norm + b in place of (agg(h) / norm) W + b, the same sum in
    the other order, gathering fout columns a row instead of fin.

    A layer whose input holds a parameter aggregates once forward and once
    backward in a step in either order, so it projects first where
    fout < fin. Layer 0 without use_pp aggregates the features, which hold
    none: its wide order needs no backward aggregation and projecting first
    adds one at fout, so it projects first only where 2 fout < fin. The
    use_pp layer 0 (a matmul in training, a concat in eval), GAT and the
    dense tail never do."""
    if (spec.model not in ("gcn", "graphsage") or i >= spec.n_graph_layers
            or (spec.use_pp and i == 0)):
        return False
    fin, fout = spec.layer_sizes[i], spec.layer_sizes[i + 1]
    return 2 * fout < fin if i == 0 else fout < fin


def _linear_after_agg(p, s, projected: bool):
    """The layer's linear on the aggregated rows `s`: only its bias where
    the rows were projected before the aggregation (a row with no
    in-neighbour reads b either way)."""
    if not projected:
        return _linear(p, s)
    with jax.named_scope(tp.LINEAR):
        return s + p["b"]


def _gcn_layer(p, i, h, env: GraphEnv, narrow: bool):
    """Symmetric-norm SpMM then linear (module/layer.py:26-46); with
    `narrow` (projects_first) the linear's matmul goes before the SpMM.

    Degree norms are f32; divisions happen in f32 but the result is cast back
    to the activation dtype so the (bytes-bound) gather stays bf16 in bf16 runs.
    The exchange rides inside env_agg_exchange so --overlap split can run the
    collective concurrently with the interior rows' aggregation.
    """
    s = env_agg_exchange(env, i, h, scale_out_norm=True,
                         w=p["w"] if narrow else None)
    return _linear_after_agg(p, (s / env.in_norm[:, None]).astype(h.dtype),
                             narrow)


def _sage_layer(p, i, h, env: GraphEnv, narrow: bool):
    """linear1(self) + linear2(sum(nbrs)/in_deg) (module/layer.py:79-92);
    with `narrow` (projects_first) linear2's matmul goes before the sum."""
    p2 = p["linear2"]
    ah = (env_agg_exchange(env, i, h, w=p2["w"] if narrow else None)
          / env.in_norm[:, None]).astype(h.dtype)
    return (_linear(p["linear1"], h[:env.n_dst])
            + _linear_after_agg(p2, ah, narrow))


@jax.named_scope(tp.ATTENTION)
def _gat_layer(p, h_dst, h_ext, presence, env: GraphEnv, heads, out_feats,
               rng, dropout, training, negative_slope=0.2,
               total_heads=None, head_off=None):
    """DGL-GATConv equivalent over the extended (inner+halo) node space.

    `presence` masks softmax contributions of halo slots that were not sampled
    this epoch (and of padded edges) — reference semantics where unsampled
    halos simply don't appear in the constructed graph (train.py:256-281).

    Feat-sharded GAT (parallel/feat.py): `heads` is this shard's local head
    count, `p` its head-sliced params; `total_heads`/`head_off` make the
    attention-dropout masks the exact head slice of the feat=1 masks
    (defaults keep the historical full-head behavior bit-identical).
    """
    if total_heads is None:
        total_heads = heads
    r1 = r2 = r3 = None
    if training and rng is not None:
        r1, r2, r3 = jax.random.split(rng, 3)
    h_ext = _dropout(h_ext, dropout, r1, training)       # feat_drop
    z = h_ext @ p["w"]                                    # [n_ext, heads*out]
    z = z.reshape(z.shape[0], heads, out_feats)
    el = (z * p["attn_l"][None]).sum(-1)                  # [n_ext, heads]
    if training and r2 is not None:
        # dst projections from independently dropped-out dst features
        h_d = _dropout(h_dst, dropout, r2, training)
        zd = (h_d @ p["w"]).reshape(h_dst.shape[0], heads, out_feats)
    else:
        # eval: h_dst is a prefix of h_ext and dropout is off — reuse z
        zd = z[:h_dst.shape[0]]
    er = (zd * p["attn_r"][None]).sum(-1)                 # [n_dst, heads]
    if env.gat_ell is not None:
        # dense per-row attention over the ELL layout — no COO edge arrays
        from bnsgcn_tpu.ops.ell_attention import gat_ell_attention
        spec_e, arrays_e = env.gat_ell
        out = gat_ell_attention(spec_e, arrays_e, z, el, er, presence,
                                r3, head_off, dropout, training,
                                negative_slope)
        return out + p["bias"].reshape(1, heads, out_feats)
    er_pad = jnp.concatenate([er, jnp.zeros((1, heads), er.dtype)], 0)
    e = el[env.src] + er_pad[jnp.minimum(env.dst, env.n_dst)]
    e = jax.nn.leaky_relu(e, negative_slope)
    edge_mask = None
    if presence is not None:
        edge_mask = presence[env.src]
    alpha = segment_softmax(e, env.dst, env.n_dst, mask=edge_mask)
    alpha = _dropout_heads(alpha, dropout, r3, training,  # attn_drop
                           total_heads, head_off)
    msg = z[env.src] * alpha[:, :, None]                  # [E, heads, out]
    out = jax.ops.segment_sum(msg.reshape(msg.shape[0], heads * out_feats),
                              env.dst, num_segments=env.n_dst + 1)[:env.n_dst]
    out = out + p["bias"]
    return out.reshape(env.n_dst, heads, out_feats)


# ----------------------------------------------------------------------------
# full forward
# ----------------------------------------------------------------------------

def apply_model(params, state, spec: ModelSpec, feat, env: GraphEnv,
                return_hidden: bool = False):
    """Forward pass. Returns (logits [n_dst, n_class], new_state).

    In training mode `feat` is the (possibly precomputed) per-partition inner
    feature block; in eval mode it is the raw full-graph features and
    `env.exchange` is the identity.

    `return_hidden=True` additionally returns the penultimate activations
    (the final layer's input, post norm/relu) as a third element — the
    embedding-table export seam the serving subsystem (serve.py,
    `--dump-embeddings`) precomputes from. Default calls are unchanged.
    """
    h = feat
    hidden = None
    new_state = dict(state)
    rngs = [None] * spec.n_layers
    if env.training and env.rng is not None:
        rngs = list(jax.random.split(env.rng, spec.n_layers))

    for i in range(spec.n_layers):
        if i == spec.n_layers - 1:
            hidden = h
        body = partial(_layer_forward, i=i, params=params, state=state,
                       spec=spec, env=env, rng=rngs[i])
        with jax.named_scope(tp.layer_scope(i)):
            if env.remat and env.training:
                # rematerialize per layer: activations (incl. the halo-
                # extended block) are recomputed in the backward instead of
                # stored — HBM-for-FLOPs/comm, jax.checkpoint per TPU guidance
                h, st_i = jax.checkpoint(body)(h)
            else:
                h, st_i = body(h)
        if st_i is not None:
            new_state[f"norm_{i}"] = st_i

    if return_hidden:
        return h, new_state, hidden
    return h, new_state


def _layer_forward(h, *, i, params, state, spec: ModelSpec, env: GraphEnv, rng):
    """One layer of the stack: returns (h, bn_state_or_None). Extracted so
    apply_model can wrap it in jax.checkpoint (remat)."""
    name = f"layer_{i}"
    p = params[name]
    is_graph_layer = i < spec.n_graph_layers
    # feat-axis tensor parallelism (parallel/feat.py): layers whose width
    # tiles the axis run the sharded body; the rest keep the historical one
    # (their params matched the replicated catch-all rule)
    fshard = (env.feat_axis is not None
              and feat_shardable(spec, i, env.n_feat_shards))

    if spec.model in ("gcn", "graphsage"):
        # dropout -> (exchange) -> layer   (module/model.py:44-51,79-86);
        # dropout fires on the FULL width even when the layer shards — the
        # feat=T masks are exactly the feat=1 masks
        h = _dropout(h, spec.dropout, rng, env.training)
        if fshard:
            h = _feat_layer(p, i, h, env, spec)
        elif not is_graph_layer:
            h = _linear(p, h)
        elif env.training and spec.use_pp and i == 0:
            # precomputed layer 0: pure dense matmul (module/layer.py:29-30,83-84)
            h = _linear(p, h)
        elif spec.model == "gcn":
            h = _gcn_layer(p, i, h, env, projects_first(spec, i))
        elif (not env.training) and spec.use_pp and i == 0:
            # eval pp layer 0: cat(feat, mean) @ W  (module/layer.py:99-100)
            ah = env_agg_exchange(env, i, h) / env.in_norm[:, None]
            h = _linear(p, jnp.concatenate([h[:env.n_dst], ah], 1))
        else:
            h = _sage_layer(p, i, h, env, projects_first(spec, i))
    elif spec.model == "gat":
        out_feats = spec.layer_sizes[i + 1]
        if is_graph_layer:
            # feat-sharded GAT: each shard owns heads/T heads (params are
            # head-sliced by the partition rules); the exchange stays
            # full-width and the head mean becomes local-sum -> one psum
            heads_l = (spec.heads // env.n_feat_shards if fshard
                       else spec.heads)
            head_off = (jax.lax.axis_index(env.feat_axis) * heads_l
                        if fshard else None)
            if env.training:
                if i == 0 and spec.use_pp:
                    assert env.gat_feat0 is not None
                    h_ext, presence = env.gat_feat0
                    h_d = h[:env.n_dst] if h.shape[0] > env.n_dst else h
                else:
                    with jax.named_scope(tp.HALO_EXCHANGE):
                        h_ext, presence = env.exchange(i, h)
                    h_d = h
            else:
                # eval: exchange is the identity on a single device and a
                # full-rate halo exchange under mesh-distributed eval
                with jax.named_scope(tp.HALO_EXCHANGE):
                    h_ext, presence = env.exchange(i, h)
                h_d = h
            h = _gat_layer(p, h_d, h_ext, presence, env, heads_l, out_feats,
                           rng, spec.dropout, env.training,
                           total_heads=spec.heads, head_off=head_off)
            if fshard:
                # mean over ALL heads = psum of local head sums / H
                h = _feat_psum(env, h.sum(1)) / spec.heads
            else:
                h = h.mean(1)          # mean over heads (module/model.py:124)
        elif fshard:
            h = _dropout(h, spec.dropout, rng, env.training)
            h = _feat_layer(p, i, h, env, spec)
        else:
            h = _dropout(h, spec.dropout, rng, env.training)
            h = _linear(p, h)
    else:
        raise ValueError(spec.model)

    st_i = None
    if i < spec.n_layers - 1:
        if spec.norm == "layer":
            h = _layer_norm(params[f"norm_{i}"], h)
        elif spec.norm == "batch":
            h, st_i = _sync_batch_norm(
                params[f"norm_{i}"], state[f"norm_{i}"], h, env, spec.train_size)
        h = jax.nn.relu(h)
    return h, st_i
