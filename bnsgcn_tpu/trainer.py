"""Distributed trainer: one compiled train step over a ('parts',) mesh.

Replaces the reference's `run()` epoch loop body (train.py:385-425). Per
epoch the host feeds only an epoch index — BNS resampling, halo exchange,
forward, backward (with its transposed exchange), gradient all-reduce and the
Adam update are all inside a single jitted step:

  reference                                   here
  ---------                                   ----
  select_node + index data_transfer            shared-PRNG pair_sample (in-step)
  construct_graph per epoch (train.py:392)     static padded edges (offline)
  ctx.buffer.update per layer                  halo_apply (lax.all_to_all)
  grad hooks + Reducer all_reduce/synchronize  AD transpose auto-psum of
  (helper/reducer.py)                          replicated params
  optimizer.step()                             optax adam (in-step)

Gradient semantics preserved: sum-loss / global n_train + SUM-reduce
== full-graph mean-loss gradient (train.py:359-361, helper/reducer.py:34).
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
import re
import sys
import time
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bnsgcn_tpu.config import Config, ConfigError
from bnsgcn_tpu.data.artifacts import PartitionArtifacts
from bnsgcn_tpu.models.gnn import (GraphEnv, ModelSpec, apply_model,
                                   init_params, project, projects_first)
from bnsgcn_tpu.ops.spmm import agg_sum
from bnsgcn_tpu.parallel.halo import (HaloSpec, full_rate_spec, halo_apply,
                                      halo_finish, halo_start,
                                      make_halo_plan, make_halo_plan_refresh,
                                      make_halo_spec, make_refresh_spec,
                                      precompute_exchange, refresh_row_mask)
from bnsgcn_tpu.parallel.mesh import (make_parts_mesh, parts_sharding,
                                       replicated_sharding)
from bnsgcn_tpu.parallel import feat as feat_mod
from bnsgcn_tpu.parallel.reducer import grad_reduce_axes
from bnsgcn_tpu.parallel.replicas import (dedup_replica0, stacked_spec,
                                          n_replicas as mesh_n_replicas,
                                          replica_axis as mesh_replica_axis)
from bnsgcn_tpu.utils import traceparse as tp

# --spmm auto picks the dense-tile hybrid when at least this fraction of
# edges would densify onto MXU tiles (v5e measured: hybrid wins at 78.5%
# coverage — 0.87 vs 1.67 s/epoch — and the marginal-tile cost model puts
# break-even near half coverage; below it the gathers-only ELL is safer)
AUTO_HYBRID_MIN_COVERAGE = 0.5

# configurations already warned about non-feat-shardable layers (the note
# fires once per config, not once per build_step_fns call)
_warned_unshardable: set = set()

# per-stage layout-build timings of the MOST RECENT build_step_fns call:
# [{'stage', 'ms', 'cached'}, ...]. Mutated in place (cleared on entry) so
# run.py can read it right after the call and emit one `layout_build` obs
# event per stage; purely informational, never branched on.
LAST_BUILD_TIMINGS: list = []


def _record_build(stage: str, t0: float, cached: bool):
    LAST_BUILD_TIMINGS.append(
        {"stage": stage, "ms": round((time.perf_counter() - t0) * 1e3, 1),
         "cached": bool(cached)})


# ----------------------------------------------------------------------------
# losses (reference train.py:358-361: reduction='sum' over local train rows)
# ----------------------------------------------------------------------------

def ce_sum(logits, labels, mask):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logp, labels[:, None].astype(jnp.int32), axis=-1)[:, 0]
    return -jnp.sum(jnp.where(mask, ll, 0.0))


def bce_sum(logits, labels, mask):
    """BCEWithLogits summed over train rows x classes (yelp multi-label)."""
    per = optax.sigmoid_binary_cross_entropy(logits.astype(jnp.float32), labels)
    return jnp.sum(jnp.where(mask[:, None], per, 0.0))


def _psum_loss(ls, axes):
    """The ONE fused loss psum over `axes` (reducer.grad_reduce_axes). The
    per-device loss is invariant over a mesh axis it never read an index of
    (always 'feat': each layer already psummed its partials), and psum
    refuses an operand that is varying over some of its axes and invariant
    over others — so the invariant ones are cast to varying first. The
    n-fold sum this adds over such an axis is what loss_denom's
    n_rep * n_fe factor already divides out."""
    if isinstance(axes, tuple):
        have = jax.typeof(ls).vma
        missing = tuple(a for a in axes if a not in have)
        if missing:
            ls = jax.lax.pcast(ls, missing, to="varying")
    return jax.lax.psum(ls, axes)


# ----------------------------------------------------------------------------
# device data
# ----------------------------------------------------------------------------

def build_block_arrays(art: PartitionArtifacts, model: str,
                       dtype=np.float32) -> dict[str, np.ndarray]:
    """Stacked [P, ...] numpy arrays the train step consumes (sharded on parts)."""
    if model == "gcn":
        in_norm = np.sqrt(art.in_deg).astype(dtype)
        out_norm = np.sqrt(art.out_deg_ext).astype(dtype)
    else:
        in_norm = art.in_deg.astype(dtype)
        out_norm = np.ones_like(art.out_deg_ext, dtype=dtype)
    blk = {
        "feat": art.feat.astype(dtype),
        "label": art.label,
        "train_mask": art.train_mask,
        "inner_mask": art.inner_mask,
        "src": art.src, "dst": art.dst, "bnd": art.bnd,
        "in_norm": in_norm, "out_norm": out_norm,
    }
    return blk


# A v5e moves one host-to-device transfer of 2**32 bytes or more about seven
# times slower than the same bytes in smaller pieces (one 4 GiB int8 array
# 5.3-7.2 s; 4 GiB less 256 KiB, or two of 2 GiB, 0.56-1.05 s).
_PUT_SLOW_BYTES = 1 << 32
_PUT_CHUNK_BYTES = 256 << 20


@partial(jax.jit, donate_argnums=0)
def _put_chunk(buf, chunk, start):
    return jax.lax.dynamic_update_slice_in_dim(buf, chunk, start, 1)


def _device_put_parts(v, sh: NamedSharding):
    """device_put, except that an array whose per-device shard reaches
    _PUT_SLOW_BYTES (the hybrid layout's dense-tile stack) is copied in
    _PUT_CHUNK_BYTES pieces along axis 1 into one donated device buffer,
    one piece on the device at a time: the transfer then runs at the fast
    rate, at one piece of transient device memory."""
    shard_bytes = (int(np.prod(sh.shard_shape(np.shape(v))))
                   * np.dtype(v.dtype).itemsize)
    if shard_bytes < _PUT_SLOW_BYTES or np.ndim(v) < 2:
        return jax.device_put(v, sh)
    step = max(1, v.shape[1] * _PUT_CHUNK_BYTES // shard_bytes)
    buf = jax.jit(partial(jnp.zeros, v.shape, v.dtype), out_shardings=sh)()
    for start in range(0, v.shape[1], step):
        chunk = jax.device_put(np.ascontiguousarray(v[:, start:start + step]),
                               sh)
        buf = _put_chunk(buf, chunk, start)
        buf.block_until_ready()
        del chunk
    return buf


def place_blocks(blk: dict, mesh: Mesh) -> dict:
    """Host [P, ...] arrays -> parts-sharded device arrays. device_put takes
    the host array itself, so each part's rows go to their own device and
    nowhere else (through jnp.asarray the whole stack would sit on device 0
    first and be re-sharded from there)."""
    sh = parts_sharding(mesh)
    return {k: _device_put_parts(v, sh) for k, v in blk.items()}


def place_replicated(tree, mesh: Mesh):
    sh = replicated_sharding(mesh)
    if jax.process_count() > 1:
        # multi-host: every process contributes its full copy
        return jax.tree.map(
            lambda v: jax.make_array_from_process_local_data(sh, np.asarray(v)),
            tree)
    return jax.tree.map(lambda v: jax.device_put(v, sh), tree)


def local_part_ids(mesh: Mesh) -> list[int]:
    """Mesh slots (== partition ids) hosted by this process, in mesh order.
    The multi-host analog of the reference's rank -> partition mapping
    (main.py:42-48)."""
    me = jax.process_index()
    return [p for p, d in enumerate(mesh.devices.flat) if d.process_index == me]


def place_blocks_local(blk_local: dict, mesh: Mesh) -> dict:
    """Build globally-sharded block arrays from process-local rows.

    `blk_local` arrays carry only this process's parts on the leading axis
    (rows in `local_part_ids(mesh)` order, from
    `load_artifacts(..., parts=local_part_ids(mesh))`)."""
    sh = parts_sharding(mesh)
    n_global = len(mesh.devices.flat)
    out = {}
    for k, v in blk_local.items():
        v = np.asarray(v)
        out[k] = jax.make_array_from_process_local_data(
            sh, v, (n_global,) + v.shape[1:])
    return out


# ----------------------------------------------------------------------------
# step builder
# ----------------------------------------------------------------------------

@dataclass
class StepFns:
    train_step: Callable      # (params, state, opt_state, epoch, blk, tables, keys) -> (...)
    forward: Callable         # (params, state, epoch, blk, tables, keys) -> logits [P, pad_inner, C]
    precompute: Callable      # (blk, tables_full) -> new feat [P, pad_inner, F'] (or gat cache)
    exchange_only: Callable   # comm-isolating microbench for Comm(s) reporting
    extra_blk: dict           # extra per-part arrays (ELL layouts) to merge into the block dict
    drop_blk_keys: tuple      # block keys the compiled step does not read (drop to save HBM)
    eval_forward: Callable = None  # mesh-distributed eval-mode forward (full rate)
    embed_forward: Callable = None  # mesh-distributed embedding export: the
                              # eval forward returning (hidden, logits) per
                              # part — hidden is the final layer's input, the
                              # all-node embedding table serve.py and
                              # --dump-embeddings assemble via gather_parts
    overlap: str = "off"      # RESOLVED --overlap mode ('split' only when the
                              # train step really runs the interior/frontier
                              # split; run.py labels the header from this)
    loss_and_grad: Callable = None  # (params, state, epoch, blk, tables, keys)
                              # -> (loss, grads): the train step's fused-mean
                              # gradient without the optimizer update —
                              # exactness tests compare replica-mesh grads
                              # against means of 1-D runs through this
    n_replicas: int = 1       # replica-axis size of the mesh the fns compiled
                              # for (parallel/replicas.py; 1 = historical 1-D)
    n_feat: int = 1           # feat-axis size (parallel/feat.py): shardable
                              # layers run on H/T activation slices with
                              # feat-sharded weights; 1 = historical paths
    param_spec: Any = None    # PartitionSpec pytree the params enter the
                              # shard_map'd loss with (P() when n_feat == 1) —
                              # run.py/tests place params and optimizer state
                              # with it so checkpoints stay feat-invariant
    train_step_full: Callable = None  # --halo-refresh K>1 only: the
                              # full-refresh step — the historical exchange
                              # geometry, additionally RETURNING the
                              # per-layer halo cache. Runs at epoch 0 and
                              # after every rollback/resume (the cache is
                              # never checkpointed)
    train_step_cached: Callable = None  # the steady-state step: refreshes
                              # chunk epoch%K of every boundary set through
                              # the ~K-x-smaller partial exchange, reuses
                              # the cached (stop-gradient) rows everywhere
                              # else, returns the updated cache
    exchange_only_refresh: Callable = None  # Comm(s) microbench on the
                              # partial-refresh geometry — the steady-state
                              # wire cost run.py reports for K>1 epochs
    tables_refresh: dict = None  # [K, P, P] chunk-major tables for the
                              # cached step / microbench (host copy; run.py
                              # places them replicated). None at K == 1
    halo_refresh: int = 1     # resolved --halo-refresh period K
    halo_mode: str = "exchange"  # resolved --halo-mode
    halo_strategy: str = "padded"  # RESOLVED exchange strategy (the concrete
                              # pick under --halo-exchange auto) — the --tune
                              # controller's lever baseline; run.py/bench.py
                              # label from it without re-deriving the auto
                              # selection
    spmm_counts: dict = None  # counts where the aggregation's work is
                              # defined (see spmm_counts): run.py writes them
                              # into the obs run_header under `spmm`
    spmm_desc: str = ""       # what aggregation the step was BUILT with, for
                              # the run header: resolved spmm kind and, for
                              # hybrid, the dense-tile count, their edge
                              # share and the dense path that really runs
                              # (dense_paths: pallas | xla)


def _local_env(spec: ModelSpec, hspec: HaloSpec, blk: dict, plan,
               rng, edge_chunk: int, training: bool, aggregate=None,
               gat_ell=None, remat: bool = False,
               agg_exchange=None, n_replicas: int = 1,
               feat_axis=None, n_feat: int = 1,
               exchange=None, presence=None) -> GraphEnv:
    # `exchange`/`presence` override the per-epoch fused exchange and its
    # presence mask — the --halo-refresh cached step (fresh chunk + stored
    # rows) and --halo-mode grad-only (zero halo block) ride this seam;
    # None = the historical halo_apply, bit-identical
    if presence is None:
        presence = plan.presence
    return GraphEnv(
        src=blk.get("src"), dst=blk.get("dst"), n_dst=hspec.pad_inner,
        in_norm=blk["in_norm"], out_norm=blk["out_norm"],
        exchange=(exchange if exchange is not None
                  else (lambda i, h: (halo_apply(hspec, plan, h), presence))),
        gat_feat0=((blk["feat0_ext"], presence)
                   if spec.model == "gat" and "feat0_ext" in blk else None),
        training=training, rng=rng, edge_chunk=edge_chunk,
        axis_name=hspec.axis_name, inner_mask=blk["inner_mask"],
        aggregate=aggregate, gat_ell=gat_ell, remat=remat,
        replica_axis=hspec.replica_axis, n_replicas=n_replicas,
        agg_exchange=agg_exchange,
        feat_axis=feat_axis, n_feat_shards=n_feat,
    )


def make_tx(cfg: Config) -> optax.GradientTransformation:
    """torch.optim.Adam(lr, weight_decay) semantics: L2 added to the grad
    before the Adam moments (reference train.py:362-364)."""
    return optax.chain(
        optax.add_decayed_weights(cfg.weight_decay) if cfg.weight_decay else optax.identity(),
        optax.adam(cfg.lr))


def hybrid_tiling(cfg: Config) -> tuple[int, int, int]:
    """(effective_occupancy, tile, budget_mb) for cfg's hybrid SpMM knobs."""
    from bnsgcn_tpu.ops.block_spmm import effective_occupancy
    return (effective_occupancy(cfg.block_occupancy, cfg.block_tile,
                                cfg.block_tile),
            cfg.block_tile, cfg.block_tile_budget_mb)


def reorder_active(cfg: Config) -> bool:
    """True when the artifacts this build sees are --reorder permuted (the
    RESOLVED value: run.py/bench resolve 'auto' and apply the permutation
    before building). Both the cluster perms and every layout-cache key
    branch on this: reordered artifacts take IDENTITY perms (the artifact
    order IS the cluster order — data/reorder.py packed it for tiles), and
    keys gain a ':ro' namespace so a layout built from reordered rows can
    never alias one built from the on-disk order."""
    return getattr(cfg, "reorder", "off") not in (None, "", "off")


def hybrid_layout_key(cfg: Config) -> str:
    """layout_cache key for the hybrid SpMM under cfg's tiling knobs —
    shared with bench.py's on-disk layout pickles so they cannot drift.
    Uses the EFFECTIVE occupancy, so auto (0) and an equal explicit value
    share one cache entry. ':1stack' names the format in which both
    directions read one tile stack (blk_order_bwd, no blk_tiles_bwd): a
    layout cached with a transposed stack under the same knobs is never
    loaded into a program that reads one. --overlap split builds a
    differently-shaped (interior/frontier row-partitioned) layout and gets
    its own ':ovl' namespace; an applied --reorder builds from permuted
    rows and gets ':ro'."""
    occ, tile, budget = hybrid_tiling(cfg)
    key = f"hybrid:{occ}:{budget}:1stack"
    if tile != 512:
        key += f":t{tile}"
    if cfg.overlap == "split":
        key += ":ovl"
    if reorder_active(cfg):
        key += ":ro"
    return key


def ell_layout_key(cfg: Config) -> str:
    """layout_cache key for the pure-ELL SpMM ('ell', or 'ell:ovl' for the
    --overlap split interior/frontier pair; ':ro' under an applied
    --reorder — same degree multiset, different index tables)."""
    key = "ell:ovl" if cfg.overlap == "split" else "ell"
    if reorder_active(cfg):
        key += ":ro"
    return key


def gat_layout_key(cfg: Config) -> str:
    """layout_cache key for the GAT ELL-attention layout ('gat'; ':ro'
    under an applied --reorder — geometry is order-invariant, the index
    tables are not)."""
    return "gat:ro" if reorder_active(cfg) else "gat"


def _identity_perms(art: PartitionArtifacts):
    pi = np.tile(np.arange(art.pad_inner, dtype=np.int64),
                 (art.feat.shape[0], 1))
    pe = np.tile(np.arange(art.n_ext, dtype=np.int64),
                 (art.feat.shape[0], 1))
    return pi, pe


def _cluster_perms(art: PartitionArtifacts, cfg: Config):
    """Per-part cluster orders for the hybrid layout (shared by the fused
    and --overlap split builds). Under an applied --reorder the rows
    already sit in tile-packed cluster order, so the perms are identity
    and the per-build LDG re-clustering pass (and its wall clock)
    disappears."""
    if reorder_active(cfg):
        return _identity_perms(art)
    from bnsgcn_tpu.ops.block_spmm import cluster_order
    n_local = art.feat.shape[0]
    perms_i, perms_e = [], []
    for p in range(n_local):
        pi, pe = cluster_order(art.src[p], art.dst[p], art.pad_inner,
                               art.n_ext, target=cfg.block_tile)
        perms_i.append(pi)
        perms_e.append(pe)
    return np.stack(perms_i), np.stack(perms_e)


# an ELL index table of a layout dict: [parts, rows, width], under the bare
# name (--spmm ell), 'res_' (the hybrid's residual) and the --overlap split
# prefixes; groups: the prefix, the direction
_ELL_IDX_KEY = re.compile(r"^((?:int_|fro_)?(?:res_)?)(fwd|bwd)_idx_\d+$")


def agg_widths(spec: ModelSpec, n_feat: int = 1
               ) -> tuple[list[int], list[int], list[dict]]:
    """The columns each sum-aggregation of one train step gathers, forward
    and backward, in layer order, and the layers that project before they
    aggregate ({layer, fin, fout}). One aggregation per GCN / GraphSAGE
    graph layer that aggregates (the precomputed layer 0 of use_pp is a
    pure matmul), at its output width where it projects first
    (models/gnn.projects_first), else at its input width (a feat-sharded
    layer's slice of it, always in the wide order); backward, one per such
    layer whose aggregated rows depend on a parameter (layer 0's features
    do not, unless it projects first). GAT aggregates inside its
    attention."""
    fwd, bwd, narrow_layers = [], [], []
    if spec.model not in ("gcn", "graphsage"):
        return fwd, bwd, narrow_layers
    for i in range(1 if spec.use_pp else 0, spec.n_graph_layers):
        fin, fout = spec.layer_sizes[i], spec.layer_sizes[i + 1]
        narrow = False
        if feat_mod.feat_shardable(spec, i, n_feat):
            width = fin // n_feat
        else:
            narrow = projects_first(spec, i)
            width = fout if narrow else fin
        if narrow:
            narrow_layers.append({"layer": i, "fin": fin, "fout": fout})
        fwd.append(width)
        if i > 0 or narrow:
            bwd.append(width)
    return fwd, bwd, narrow_layers


def dense_paths(spec_pairs: Optional[dict], dense_dtype: str) -> dict:
    """The dense-tile implementation each direction of a hybrid step runs
    (block_spmm.dense_path, the choice the compiled step makes): 'pallas'
    or 'xla', or both joined by '+' where an --overlap split layout's spec
    pairs differ; 'none' without dense tiles. `spec_pairs` as in
    _hybrid_desc."""
    from bnsgcn_tpu.ops.block_spmm import dense_path
    out = {}
    for i, d in enumerate(("fwd", "bwd")):
        paths = {dense_path(pair[i], dense_dtype)
                 for pair in (spec_pairs or {}).values()}
        out[d] = "+".join(sorted(paths)) or "none"
    return out


def spmm_counts(kind: str, spec: ModelSpec, arrays: dict, n_local: int,
                spec_pairs: Optional[dict] = None,
                dense_per_part=(), dense_dtype: str = "native",
                n_feat: int = 1) -> dict:
    """Counts at the boundaries where the aggregation's work is defined, per
    part maxima: dense tiles and the edges they carry (hybrid;
    `dense_per_part` from block_spmm.dense_edge_count) and the
    implementation that runs them (`dense_paths`), the slots the residual
    ELL gathers (rows x width summed over buckets, padding included: what
    ell._bucket_sum reads) and the real edges among them (slots over edges
    is what the bucket geometry costs), the aggregations per step and the
    columns each gathers (`agg_widths`), and the layers that project before
    they aggregate (`narrow_layers`: layer, fin, fout), the device bytes
    of the tile stack both directions read (`tile_stack_bytes`) and the
    share of the local parts' edges the tiles carry (`dense_share`).
    `spec_pairs` as in _hybrid_desc."""
    out = {"path": kind}
    for d, p in dense_paths(spec_pairs, dense_dtype).items():
        out[f"dense_path_{d}"] = p
    stack_bytes = 0
    tiles_pp = np.zeros(n_local, np.int64)
    for pre, (f, _) in (spec_pairs or {}).items():
        t = arrays.get(f"{pre}blk_tiles_fwd")
        if t is not None:
            stack_bytes += t[0].nbytes
            # pad slots carry rowb == n_row_blocks; the backward visits
            # the same tiles through blk_order_bwd
            tiles_pp += (np.asarray(arrays[f"{pre}blk_rowb_fwd"])
                         < f.n_row_blocks).sum(axis=1)
    out["tiles_fwd"] = out["tiles_bwd"] = int(tiles_pp.max(initial=0))
    out["tile_stack_bytes"] = int(stack_bytes)
    for d, other in (("fwd", "bwd"), ("bwd", "fwd")):
        # a padded slot holds the index of the appended zero row: the rows
        # the table gathers from, which the other direction's perm counts
        slots, edges = 0, np.zeros(n_local, np.int64)
        for k, v in arrays.items():
            m = _ELL_IDX_KEY.match(k)
            if m and m.group(2) == d:
                v = np.asarray(v)
                slots += v.shape[1] * v.shape[2]
                n_src = arrays[f"{m.group(1)}{other}_perm"].shape[1]
                edges += (v < n_src).sum(axis=(1, 2))
        out[f"residual_slots_{d}"] = int(slots)
        out[f"residual_edges_{d}"] = int(edges.max(initial=0))
        if d == "fwd":
            # every edge is in a tile or in the residual (a tile cell's
            # multiplicity past 127 rides the residual)
            dense = int(sum(dense_per_part))
            out["dense_share"] = dense / max(dense + int(edges.sum()), 1)
    out["dense_edges"] = int(max(dense_per_part, default=0))
    fwd, bwd, narrow_layers = agg_widths(spec, n_feat)
    out.update(agg_calls_fwd=len(fwd), agg_calls_bwd=len(bwd),
               agg_calls_per_step=len(fwd) + len(bwd),
               agg_width_fwd=fwd, agg_width_bwd=bwd,
               narrow_layers=narrow_layers)
    return out


def _hybrid_desc(cfg: Config, art: PartitionArtifacts, arrays: dict,
                 spec_pairs: dict, dense: int) -> str:
    """Run-header text for a built hybrid layout. `spec_pairs` maps each
    array-key prefix ('' fused; 'int_'/'fro_' --overlap split) to its
    (fwd, bwd) BlockSpecs; `dense` is the edges the local tiles carry."""
    n_local = art.feat.shape[0]
    tiles = 0
    for pre, (f, _) in spec_pairs.items():
        rb = arrays.get(pre + "blk_rowb_fwd")
        if rb is not None:                # pad slots carry rowb == n_row_blocks
            tiles += int((np.asarray(rb) < f.n_row_blocks).sum())
    edges = max(int((art.dst < art.pad_inner).sum()), 1)
    paths = dense_paths(spec_pairs, cfg.spmm_dense)
    via = (paths["fwd"] if paths["fwd"] == paths["bwd"]
           else f"{paths['fwd']} fwd / {paths['bwd']} bwd")
    return (f"hybrid, {tiles} dense {cfg.block_tile}x{cfg.block_tile} tiles "
            f"on {n_local} local part(s) carry {dense / edges:.1%} of "
            f"{edges} edges via {via}"
            + (f" [{cfg.spmm_dense} slabs]" if cfg.spmm_dense != "native"
               else "") + ", ell residual")


def _compose_split(spmms, pad_inner: int):
    """Fused-equivalent aggregation from an (interior, frontier) SpMM pair:
    int rows gather from the owned prefix, frontier rows from the full
    extended block, one recombination gather back to row order. Serves the
    eval/precompute call sites of a --overlap split run so only ONE layout
    family is ever built (row-exact vs the fused layout)."""
    int_spmm, fro_spmm = spmms

    def spmm(arrays, h_ext):
        a_i = {k[4:]: v for k, v in arrays.items() if k.startswith("int_")}
        a_f = {k[4:]: v for k, v in arrays.items() if k.startswith("fro_")}
        o_i = int_spmm(a_i, h_ext[:pad_inner])
        o_f = fro_spmm(a_f, h_ext)
        return jnp.concatenate([o_i, o_f], 0)[arrays["merge_perm"]]

    return spmm


def build_step_fns(cfg: Config, spec: ModelSpec, art: PartitionArtifacts,
                   mesh: Mesh, rate: Optional[float] = None,
                   layout_cache: Optional[dict] = None,
                   slot_map=None
                   ) -> tuple[StepFns, HaloSpec, dict, dict]:
    """Returns (fns, hspec, tables, tables_full); the tables dicts must be
    passed (replicated) to every call. When cfg.spmm == 'ell', merge
    fns.extra_blk into the build_block_arrays dict before place_blocks
    (run.run_training does this automatically).

    `layout_cache`: optional dict shared across calls on the SAME artifacts
    — SpMM layout construction (minutes at bench scale) is memoized under
    the spmm kind, so e.g. bench's ell and ell+f8g candidates build once.

    `slot_map`: elastic part -> worker-slot hosting (mesh.plan_slots), stamped
    onto the HaloSpecs as host-side addressing metadata. Never read inside
    traced code, so a resize rebuild reuses the layout cache AND compiles the
    exact same step program — graftlint-ir's slot-map section pins this."""
    rate = cfg.sampling_rate if rate is None else rate
    del LAST_BUILD_TIMINGS[:]           # this call's stage timings
    halo_strategy = cfg.halo_exchange
    if halo_strategy == "auto":
        # byte estimate + hop tiebreak over the GLOBAL n_b table, so every
        # host of a multi-host run resolves to the same strategy; eligibility
        # keeps a TPU without the native ragged collective off the emulation
        # (which ships padded bytes)
        from bnsgcn_tpu.parallel.halo import (ragged_auto_eligible,
                                              select_halo_strategy)
        halo_strategy, why = select_halo_strategy(
            art.n_b, art.pad_inner, art.pad_boundary, rate,
            wire=cfg.halo_wire, allow_ragged=ragged_auto_eligible())
        if jax.process_index() == 0:
            print(f"halo-exchange=auto: {why} -> {halo_strategy}",
                  file=sys.stderr)
    # 2-D ('replicas', 'parts') mesh (parallel/replicas.py): each replica row
    # runs its own parts-axis halo exchange with an independently-folded BNS
    # sample; the gradient mean over replicas is fused into the loss psum.
    # A 1-D mesh leaves every value below at its historical default —
    # bit-identical code path.
    n_rep = mesh_n_replicas(mesh)
    rep_axis = mesh_replica_axis(mesh)
    # 3-D mesh feat axis (parallel/feat.py): shardable layers slice their
    # activations to H/T columns (the halo exchange ships H/T-width payloads)
    # and psum weight-shard partials over 'feat' once per layer; the BNS
    # sampling keys never fold the feat index — every shard of a (replica,
    # part) must draw the SAME boundary sample.
    n_fe = feat_mod.n_feat(mesh)
    fe_axis = feat_mod.feat_axis(mesh)
    if (n_rep > 1 or n_fe > 1) and jax.process_count() > 1:
        raise ValueError(
            "replica/feat-axis meshes are single-host for now: multi-host "
            "partial artifact loading maps processes to parts slots only "
            "(use --replicas 1 --feat 1 across hosts)")
    hspec, tables = make_halo_spec(art.n_b, art.pad_inner, art.pad_boundary, rate,
                                   strategy=halo_strategy, wire=cfg.halo_wire,
                                   replica_axis=rep_axis, slot_map=slot_map)
    hspec_full, tables_full = full_rate_spec(art.n_b, art.pad_inner, art.pad_boundary)
    # staleness-bounded halo communication (--halo-refresh K / --halo-mode):
    # K > 1 builds a second, ~K-x-smaller exchange geometry for the
    # steady-state cached step; grad-only skips activation exchange entirely.
    # Validated here (not in config post-init) so directly-constructed
    # Configs in tests hit the same guard as the CLI.
    refresh_k = getattr(cfg, "halo_refresh", 1)
    refresh_k = 1 if refresh_k is None else int(refresh_k)
    if refresh_k < 1:
        raise ConfigError(f"--halo-refresh must be >= 1, got {refresh_k}")
    halo_mode = getattr(cfg, "halo_mode", "exchange")
    if halo_mode not in ("exchange", "grad-only"):
        raise ConfigError(
            f"--halo-mode must be 'exchange' or 'grad-only', got {halo_mode!r}")
    grad_only = halo_mode == "grad-only"
    if grad_only and refresh_k > 1:
        if jax.process_index() == 0:
            print("halo-mode=grad-only never exchanges activations; "
                  "--halo-refresh has no effect", file=sys.stderr)
        refresh_k = 1
    hspec_r, tables_refresh = None, None
    if refresh_k > 1:
        hspec_r, tables_refresh = make_refresh_spec(
            art.n_b, art.pad_inner, art.pad_boundary, rate, refresh_k,
            strategy=halo_strategy, wire=cfg.halo_wire, replica_axis=rep_axis,
            slot_map=slot_map)
    n_train = max(art.n_train, 1)
    multilabel = art.multilabel
    axis = hspec.axis_name
    # ONE fused psum spanning every mesh axis: /n_rep (gradient mean over
    # replicas) and /n_fe (feat shards hold identical post-psum losses)
    # both ride the existing /n_train scale — never a second collective
    loss_axes = grad_reduce_axes(axis, rep_axis, fe_axis)
    loss_denom = n_train * n_rep * n_fe
    blk_spec = P("parts")                          # replicated over replicas+feat
    stacked = stacked_spec(mesh)                   # per-replica-varying outs
    rep = P()
    # params enter the shard_map'd loss feat-sharded where the regex rules
    # say so (weights row/head-sharded, biases and norms replicated); P()
    # everywhere at n_fe == 1 — the historical replicated in_spec verbatim
    param_spec = rep
    if n_fe > 1:
        param_spec = feat_mod.param_specs_for(spec, n_fe)
        skipped = [i for i, ok in enumerate(
            feat_mod.shardable_layers(spec, n_fe)) if not ok]
        warn_key = (spec.model, spec.layer_sizes, spec.heads, n_fe)
        if (skipped and jax.process_index() == 0
                and warn_key not in _warned_unshardable):
            # once per configuration: run_training rebuilds step fns for
            # every eval resource and bench per variant — the diagnostic is
            # about the config, not the build
            _warned_unshardable.add(warn_key)
            print(f"feat={n_fe}: layer(s) {skipped} keep full width (input "
                  f"width/heads not divisible by {n_fe}); their params stay "
                  f"replicated", file=sys.stderr)

    # scatter-free SpMM layouts (GCN/SAGE aggregation path): 'ell' (bucketed
    # gathers) or 'hybrid' (dense int8 adjacency tiles on the MXU + ELL
    # residual — ops/block_spmm.py). Multi-host partial loads agree on the
    # tile-stack and residual-table shapes via a host-side allgather so every
    # process compiles the identical program from its local parts.
    ell_spmm, ell_keys, ell_arrays = None, (), {}
    ell_spmm_pre = None
    spmm_desc = ""                      # hybrid builds fill it; else below
    hybrid_pairs, dense_pp = None, ()   # hybrid builds: BlockSpec pairs by
                                        # array-key prefix, dense edges a part
    spmm_kind = cfg.spmm
    auto_perms = None
    if spmm_kind == "auto":
        # pick the SpMM backend from the graph itself: cluster-order the
        # local parts and estimate the MXU-densifiable edge fraction in one
        # O(E) histogram (ops/block_spmm.estimate_coverage). Clustered
        # graphs (78.5% coverage on the reddit-like bench graph) run the
        # dense-tile hybrid; structure-free ones stay on ELL gathers. The
        # perms are reused by the hybrid build, so auto costs nothing extra
        # when hybrid is picked. Multi-host processes agree on GLOBAL
        # coverage so every rank compiles the same program.
        if spec.model in ("gcn", "graphsage"):
            from bnsgcn_tpu.ops.block_spmm import (cluster_order,
                                                   estimate_coverage)
            t0_auto = time.perf_counter()
            # an applied --reorder already packed rows for tiles: estimate
            # coverage of the artifact order itself (identity perms) and
            # skip the per-part LDG pass entirely
            ro_active = reorder_active(cfg)
            n_local = art.feat.shape[0]
            perms_i, perms_e = [], []
            dense_e, total_e = 0.0, 0.0
            for p in range(n_local):
                if ro_active:
                    pi = np.arange(art.pad_inner, dtype=np.int64)
                    pe = np.arange(art.n_ext, dtype=np.int64)
                else:
                    pi, pe = cluster_order(art.src[p], art.dst[p],
                                           art.pad_inner, art.n_ext,
                                           target=cfg.block_tile)
                perms_i.append(pi)
                perms_e.append(pe)
                real = art.dst[p] < art.pad_inner
                d, s = art.dst[p][real], art.src[p][real]
                occ_eff = hybrid_tiling(cfg)[0]
                cov = estimate_coverage(
                    pi, pe, art.pad_inner, art.n_ext, d, s,
                    occupancy_min=occ_eff,
                    tile_budget_bytes=cfg.block_tile_budget_mb << 20,
                    tile_r=cfg.block_tile, tile_c=cfg.block_tile)
                dense_e += cov * len(d)
                total_e += len(d)
            if jax.process_count() > 1:
                from jax.experimental import multihost_utils
                both = np.asarray(multihost_utils.process_allgather(
                    np.array([dense_e, total_e]))).sum(axis=0)
                dense_e, total_e = float(both[0]), float(both[1])
            frac = dense_e / max(total_e, 1.0)
            spmm_kind = ("hybrid" if frac >= AUTO_HYBRID_MIN_COVERAGE
                         else "ell")
            auto_perms = ((np.stack(perms_i), np.stack(perms_e))
                          if spmm_kind == "hybrid" else None)
            _record_build("auto_coverage", t0_auto, cached=False)
            if jax.process_index() == 0:
                print(f"spmm=auto: {frac:.1%} of edges densify onto MXU "
                      f"tiles -> {spmm_kind}", file=sys.stderr)
        else:
            spmm_kind = "ell"

    # --overlap split: interior/frontier row-split aggregation so the halo
    # collective runs concurrently with the interior SpMM (DistGNN-style
    # local/remote overlap, arXiv:2104.06700). Resolved HERE so the layout
    # build below emits the row-partitioned pair instead of the fused tables.
    overlap = cfg.overlap
    if overlap == "split":
        reason = None
        if grad_only:
            reason = ("halo-mode=grad-only skips the activation exchange "
                      "entirely — there is no collective to overlap")
        elif spec.model not in ("gcn", "graphsage"):
            reason = (f"model={spec.model!r} aggregates through the masked "
                      f"edge softmax, which consumes the whole halo block")
        elif jax.process_count() > 1:
            reason = ("multi-host partial loads cannot derive the global "
                      "interior/frontier row split from local parts yet")
        if reason is not None:
            if jax.process_index() == 0:
                print(f"overlap=split unavailable ({reason}); falling back "
                      f"to --overlap off", file=sys.stderr)
            overlap = "off"
    key_cfg = cfg if overlap == cfg.overlap else cfg.replace(overlap=overlap)
    split_spmms = None                  # (interior, frontier) train instances
    split_kind = None

    want_hybrid = (spmm_kind == "hybrid"
                   and spec.model in ("gcn", "graphsage"))
    if want_hybrid and overlap == "split":
        from bnsgcn_tpu.ops.block_spmm import (build_split_block_layouts,
                                               make_block_spmm)
        hyb_key = hybrid_layout_key(key_cfg)            # 'hybrid:...:ovl'
        t0_b = time.perf_counter()
        hyb_cached = layout_cache is not None and hyb_key in layout_cache
        if hyb_cached:
            sb = layout_cache[hyb_key]
        else:
            perms_i, perms_e = (auto_perms if auto_perms is not None
                                else _cluster_perms(art, cfg))
            sb = build_split_block_layouts(
                art.src, art.dst, art.pad_inner, art.n_ext, perms_i, perms_e,
                occupancy_min=hybrid_tiling(cfg)[0],
                tile_budget_bytes=cfg.block_tile_budget_mb << 20,
                tile_r=cfg.block_tile, tile_c=cfg.block_tile)
            if layout_cache is not None:
                layout_cache[hyb_key] = sb
        _record_build("hybrid_split", t0_b, hyb_cached)
        (int_f, int_b, int_pair), (fro_f, fro_b, fro_pair), s_arrays, _, _ = sb
        split_spmms = tuple(
            make_block_spmm(f, b, pair, gather_dtype=cfg.spmm_gather,
                            dense_dtype=cfg.spmm_dense)
            for f, b, pair in ((int_f, int_b, int_pair),
                               (fro_f, fro_b, fro_pair)))
        split_pre = (make_block_spmm(int_f, int_b, int_pair, accum="reduce"),
                     make_block_spmm(fro_f, fro_b, fro_pair, accum="reduce"))
        ell_arrays = dict(s_arrays)
        ell_spmm = _compose_split(split_spmms, art.pad_inner)
        ell_spmm_pre = _compose_split(split_pre, art.pad_inner)
        ell_keys = tuple(ell_arrays.keys())
        split_kind = "hybrid"
        hybrid_pairs = {"int_": (int_f, int_b), "fro_": (fro_f, fro_b)}
    elif want_hybrid:
        from bnsgcn_tpu.ops.block_spmm import (build_block_layouts,
                                               make_block_spmm)
        hyb_key = hybrid_layout_key(key_cfg)
        t0_b = time.perf_counter()
        hyb_cached = layout_cache is not None and hyb_key in layout_cache
        if hyb_cached:
            # every layout under a ':1stack' key was built with
            # BlockSpec.max_row_dense filled in
            fwd_b, bwd_b, ell_pair, ell_arrays = layout_cache[hyb_key]
        else:
            agree = None
            if jax.process_count() > 1:
                from jax.experimental import multihost_utils

                def agree(stats):
                    return {k: np.asarray(
                        multihost_utils.process_allgather(np.asarray(v))
                    ).max(axis=0) for k, v in stats.items()}

            perms_i, perms_e = (auto_perms if auto_perms is not None
                                else _cluster_perms(art, cfg))
            fwd_b, bwd_b, ell_pair, ell_arrays = build_block_layouts(
                art.src, art.dst, art.pad_inner, art.n_ext,
                perms_i, perms_e, agree=agree,
                occupancy_min=hybrid_tiling(cfg)[0],
                tile_budget_bytes=cfg.block_tile_budget_mb << 20,
                tile_r=cfg.block_tile, tile_c=cfg.block_tile)
            if layout_cache is not None:
                layout_cache[hyb_key] = (fwd_b, bwd_b, ell_pair,
                                         dict(ell_arrays))
        _record_build("hybrid", t0_b, hyb_cached)
        ell_arrays = dict(ell_arrays)   # never alias the cache (extra_blk is
        ell_spmm = make_block_spmm(fwd_b, bwd_b, ell_pair,  # caller-mutable)
                                   gather_dtype=cfg.spmm_gather,
                                   dense_dtype=cfg.spmm_dense)
        # the one-time use_pp precompute always aggregates with NATIVE
        # codecs: quantized gathers/tiles are per-epoch throughput knobs,
        # and the int8 dense path's extra per-chunk intermediates OOM the
        # v5e HBM at the raw-feature width (602) the precompute runs at
        # (round-4 measured RESOURCE_EXHAUSTED; H=256 train steps fit)
        ell_spmm_pre = make_block_spmm(fwd_b, bwd_b, ell_pair,
                                       accum="reduce")
        ell_keys = tuple(ell_arrays.keys())
        hybrid_pairs = {"": (fwd_b, bwd_b)}
    elif (spmm_kind == "ell" and spec.model in ("gcn", "graphsage")
          and overlap == "split"):
        from bnsgcn_tpu.ops.ell import build_split_layouts, make_ell_spmm
        skey = ell_layout_key(key_cfg)                  # 'ell:ovl'
        t0_b = time.perf_counter()
        ell_cached = layout_cache is not None and skey in layout_cache
        if ell_cached:
            sb = layout_cache[skey]
        else:
            sb = build_split_layouts(art.src, art.dst, art.pad_inner,
                                     art.n_ext)
            if layout_cache is not None:
                layout_cache[skey] = sb
        _record_build("ell_split", t0_b, ell_cached)
        (int_f, int_b), (fro_f, fro_b), s_arrays, _, _ = sb

        def mke(f, b, **kw):
            return make_ell_spmm(f, b, len(f.widths), len(b.widths), **kw)

        split_spmms = (mke(int_f, int_b, gather_dtype=cfg.spmm_gather),
                       mke(fro_f, fro_b, gather_dtype=cfg.spmm_gather))
        split_pre = (mke(int_f, int_b, accum="reduce"),
                     mke(fro_f, fro_b, accum="reduce"))
        ell_arrays = dict(s_arrays)
        ell_spmm = _compose_split(split_spmms, art.pad_inner)
        ell_spmm_pre = _compose_split(split_pre, art.pad_inner)
        ell_keys = tuple(ell_arrays.keys())
        split_kind = "ell"
    elif spmm_kind == "ell" and spec.model in ("gcn", "graphsage"):
        from bnsgcn_tpu.ops.ell import build_layouts, make_ell_spmm
        ekey = ell_layout_key(key_cfg)                  # 'ell' / 'ell:ro'
        t0_b = time.perf_counter()
        ell_cached = layout_cache is not None and ekey in layout_cache
        if ell_cached:
            fwd_spec, bwd_spec, ell_arrays = layout_cache[ekey]
        else:
            fwd_spec, bwd_spec, ell_arrays = build_layouts(
                art.src, art.dst, art.pad_inner, art.n_ext,
                geometry=art.ell_geometry)
            if layout_cache is not None:
                layout_cache[ekey] = (fwd_spec, bwd_spec, dict(ell_arrays))
        _record_build("ell", t0_b, ell_cached)
        ell_arrays = dict(ell_arrays)   # never alias the cache
        ell_spmm = make_ell_spmm(fwd_spec, bwd_spec,
                                 len(fwd_spec.widths), len(bwd_spec.widths),
                                 gather_dtype=cfg.spmm_gather)
        ell_spmm_pre = make_ell_spmm(fwd_spec, bwd_spec,
                                     len(fwd_spec.widths),
                                     len(bwd_spec.widths),
                                     accum="reduce")
        ell_keys = tuple(ell_arrays.keys())
    elif overlap == "split" and spec.model in ("gcn", "graphsage"):
        # 'segment' COO path: the row split is just two edge lists (no
        # layout build); recombination is an exact add of disjoint rows
        from bnsgcn_tpu.ops.spmm import split_coo
        t0_b = time.perf_counter()
        ell_arrays = dict(split_coo(art.src, art.dst, art.pad_inner))
        _record_build("segment_split", t0_b, cached=False)
        split_kind = "segment"

    # dense per-row GAT attention over an (uncapped) ELL layout; geometry
    # comes from meta.json ('gat_fwd') or is computed when all parts are local
    gat_spec, gat_keys = None, ()
    if spmm_kind in ("ell", "hybrid") and spec.model == "gat":
        geo = (art.ell_geometry or {}).get("gat_fwd")
        if geo is not None or art.feat.shape[0] == art.n_parts:
            gkey = gat_layout_key(cfg)                  # 'gat' / 'gat:ro'
            t0_b = time.perf_counter()
            gat_cached = layout_cache is not None and gkey in layout_cache
            if gat_cached:
                gat_spec, gat_arrays = layout_cache[gkey]
            else:
                from bnsgcn_tpu.ops.ell_attention import build_gat_layouts
                gat_spec, gat_arrays = build_gat_layouts(
                    art.src, art.dst, art.pad_inner, art.n_ext, geometry=geo,
                    geometry_bwd=(art.ell_geometry or {}).get("bwd"))
                if layout_cache is not None:
                    # minutes of host numpy at bench scale — cacheable like
                    # the ell/hybrid layouts (geometry depends only on the
                    # artifacts, not on heads/hidden/dtype)
                    layout_cache[gkey] = (gat_spec, dict(gat_arrays))
            _record_build("gat", t0_b, gat_cached)
            ell_arrays.update(gat_arrays)
            gat_keys = tuple(gat_arrays.keys())

    if hybrid_pairs is not None:
        from bnsgcn_tpu.ops.block_spmm import dense_edge_count
        dense_pp = [dense_edge_count(ell_arrays, p)
                    for p in range(art.feat.shape[0])]
        spmm_desc = _hybrid_desc(cfg, art, ell_arrays, hybrid_pairs,
                                 sum(dense_pp))
    if not spmm_desc:
        spmm_desc = ("ell gathers" if ell_spmm is not None
                     else "gat ell-attention" if gat_spec is not None
                     else "segment-sum over coo edges")
    if cfg.spmm_gather != "native" and ell_spmm is None and jax.process_index() == 0:
        print(f"spmm_gather={cfg.spmm_gather} has no effect for spmm={spmm_kind!r} / "
              f"model={spec.model!r} (only the ell/hybrid GCN/GraphSAGE "
              f"aggregation paths quantize gathers)", file=sys.stderr)

    def _agg_for(spmm, blk):
        if spmm is None:
            return None
        arrays = {k: blk[k] for k in ell_keys}
        return lambda h_ext: spmm(arrays, h_ext)

    def _aggregate_for(blk):
        return _agg_for(ell_spmm, blk)

    def _aggregate_pre_for(blk):
        """Native-codec aggregation for the one-time precompute."""
        return _agg_for(ell_spmm_pre, blk)

    def _gat_ell_for(blk):
        if gat_spec is None:
            return None
        return (gat_spec, {k: blk[k] for k in gat_keys})

    def _split_agg_for(blk, plan, spec_h=None, combine=None):
        """--overlap split layer body: start-exchange -> interior-agg ->
        finish-exchange -> frontier-agg -> merge. The interior aggregation
        has NO data dependency on the collective, so the XLA latency-hiding
        scheduler can run the exchange while it computes. Returned callable
        becomes GraphEnv.agg_exchange; None keeps the fused layer body.

        `spec_h`/`combine` serve the --halo-refresh cached step: the plan's
        exchange runs on the partial-refresh geometry (same pad_inner /
        n_halo, ~K-x-smaller sends — a near-pure-compute epoch) and
        `combine(i, buf)` merges the fresh chunk into the stored rows before
        the frontier aggregation. Defaults are the historical fused-geometry
        path, bit-identical."""
        if overlap != "split":
            return None
        spec_h = hspec if spec_h is None else spec_h
        out_norm = blk["out_norm"]
        ni = spec_h.pad_inner

        def scale(x, norm):
            # the GCN symmetric norm, applied piecewise: elementwise
            # identical to the fused path's single h_ext / out_norm
            return (x / norm[:, None]).astype(x.dtype)

        def source(x, norm, scale_out_norm, w):
            # a narrowing layer's projection, then the norm: the fused
            # path's order on each side's rows
            x = x if w is None else project(x, w)
            return scale(x, norm) if scale_out_norm else x

        if split_kind == "segment":
            def agg(i, h, scale_out_norm, w=None):
                with jax.named_scope(tp.HALO_START):
                    recv = halo_start(spec_h, plan, h)
                h_in = source(h, out_norm[:ni], scale_out_norm, w)
                with jax.named_scope(tp.INTERIOR_AGG):
                    o_i = agg_sum(h_in, blk["seg_int_src"],
                                  blk["seg_int_dst"], ni, cfg.edge_chunk)
                with jax.named_scope(tp.HALO_FINISH):
                    buf = halo_finish(spec_h, plan, recv, h)
                if combine is not None:
                    buf = combine(i, buf)
                h_halo = source(buf, out_norm[ni:], scale_out_norm, w)
                with jax.named_scope(tp.FRONTIER_AGG):
                    o_f = agg_sum(jnp.concatenate([h_in, h_halo], 0),
                                  blk["seg_fro_src"], blk["seg_fro_dst"],
                                  ni, cfg.edge_chunk)
                return o_i + o_f            # disjoint rows: exact recombine
            return agg

        int_spmm, fro_spmm = split_spmms
        a_i = {k[4:]: blk[k] for k in ell_keys if k.startswith("int_")}
        a_f = {k[4:]: blk[k] for k in ell_keys if k.startswith("fro_")}
        mp = blk["merge_perm"]

        def agg(i, h, scale_out_norm, w=None):
            with jax.named_scope(tp.HALO_START):
                recv = halo_start(spec_h, plan, h)
            h_in = source(h, out_norm[:ni], scale_out_norm, w)
            with jax.named_scope(tp.INTERIOR_AGG):
                o_i = int_spmm(a_i, h_in)
            with jax.named_scope(tp.HALO_FINISH):
                buf = halo_finish(spec_h, plan, recv, h)
            if combine is not None:
                buf = combine(i, buf)
            h_halo = source(buf, out_norm[ni:], scale_out_norm, w)
            with jax.named_scope(tp.FRONTIER_AGG):
                o_f = fro_spmm(a_f, jnp.concatenate([h_in, h_halo], 0))
            return jnp.concatenate([o_i, o_f], 0)[mp]
        return agg

    def _replica_fold(key):
        """Fold the replica index into a host-fed PRNG key so each replica's
        dropout stream is independent — folded FIRST, mirroring
        sampling.pair_key's replica fold, so replica r of a 2-D run equals a
        1-D run fed fold_in(key, r). 1-D meshes fold nothing."""
        if rep_axis is None:
            return key
        return jax.random.fold_in(key, jax.lax.axis_index(rep_axis))

    def _grad_only_override():
        """--halo-mode grad-only (the Grappa extreme): NO activation
        collective at all — the halo block is zero (aggregation sees local
        rows plus zero-initialized halo state) and presence masks every halo
        slot, so GAT's masked edge softmax excludes them identically. The
        loss psum's AD transpose still all-reduces the gradients — the one
        per-step collective the mode keeps. Returns (None, None) outside
        grad-only so default paths stay structurally untouched."""
        if not grad_only:
            return None, None
        presence = jnp.concatenate(
            [jnp.ones(hspec.pad_inner, dtype=bool),
             jnp.zeros(hspec.n_halo, dtype=bool)])

        def exchange(i, h):
            pad = jnp.zeros((hspec.n_halo, h.shape[-1]), h.dtype)
            return jnp.concatenate([h, pad], 0), presence
        return exchange, presence

    @jax.named_scope(tp.LOSS)
    def _train_loss(logits, blk):
        if multilabel:
            ls = bce_sum(logits, blk["label"], blk["train_mask"])
        else:
            ls = ce_sum(logits, blk["label"], blk["train_mask"])
        # the cross-replica mean is FUSED here: one psum over both mesh axes,
        # rescaled by n_replicas — the AD transpose of the replicated params
        # therefore emits one gradient all-reduce over the whole mesh, whose
        # result is exactly mean-over-replicas of the per-replica gradients
        return _psum_loss(ls / loss_denom, loss_axes)

    def local_loss(params, state, blk, tables, epoch, sample_key, drop_key):
        blk = {k: v[0] for k, v in blk.items()}
        with jax.named_scope(tp.BNS_SAMPLE):
            plan = make_halo_plan(hspec, tables, blk["bnd"], epoch, sample_key)
        me = jax.lax.axis_index(axis)
        rng = jax.random.fold_in(
            jax.random.fold_in(_replica_fold(drop_key), epoch), me)
        exch, pres = _grad_only_override()
        env = _local_env(spec, hspec, blk, plan, rng, cfg.edge_chunk, True,
                         aggregate=_aggregate_for(blk), gat_ell=_gat_ell_for(blk),
                         remat=cfg.remat, agg_exchange=_split_agg_for(blk, plan),
                         n_replicas=n_rep, feat_axis=fe_axis, n_feat=n_fe,
                         exchange=exch, presence=pres)
        logits, new_state = apply_model(params, state, spec, blk["feat"], env)
        return _train_loss(logits, blk), new_state

    sharded_loss = jax.shard_map(
        local_loss, mesh=mesh,
        in_specs=(param_spec, rep, blk_spec, rep, rep, rep, rep),
        out_specs=(rep, rep))

    def global_loss(params, state, blk, tables, epoch, sample_key, drop_key):
        return sharded_loss(params, state, blk, tables, epoch, sample_key, drop_key)

    tx = make_tx(cfg)

    @jax.named_scope(tp.OPTIMIZER)
    def apply_grads(grads, opt_state, params):
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state

    @partial(jax.jit, donate_argnums=(0, 1, 2))
    def train_step(params, state, opt_state, epoch, blk, tables, sample_key, drop_key):
        (loss, new_state), grads = jax.value_and_grad(global_loss, has_aux=True)(
            params, state, blk, tables, epoch, sample_key, drop_key)
        params, opt_state = apply_grads(grads, opt_state, params)
        return params, new_state, opt_state, loss

    @jax.jit
    def loss_and_grad(params, state, epoch, blk, tables, sample_key, drop_key):
        """The step's loss + fused-mean gradient, optimizer untouched —
        what tests compare across mesh shapes (replica-mean exactness)."""
        (loss, _), grads = jax.value_and_grad(global_loss, has_aux=True)(
            params, state, blk, tables, epoch, sample_key, drop_key)
        return loss, grads

    # ---- --halo-refresh K > 1: the staleness-bounded step pair. Built only
    # then — at K == 1 nothing below traces and the historical step above is
    # the one and only training path (structural bit-identity). ----
    refresh_fns = {}
    if refresh_k > 1:
        if cfg.remat and jax.process_index() == 0:
            print("halo-refresh>1: the refresh steps return per-layer halo "
                  "buffers as step outputs, which cannot escape a "
                  "jax.checkpoint region — --remat is ignored for them "
                  "(numerics unchanged; memory savings lost)",
                  file=sys.stderr)

        def _make_refresh_loss(cached: bool):
            """local_loss variant that additionally maintains the halo cache
            {'presence': [n_halo] bool, 'layer_i': [n_halo, d_i]}.

            cached=False — the FULL-refresh step: the historical exchange
            (bit-identical math to local_loss) that records every layer's
            received halo buffer + presence into the cache it returns. Runs
            at epoch 0 and whenever rollback/resume invalidated the cache.

            cached=True — the steady-state step: chunk epoch%K of each
            boundary set is redrawn through the ~K-x-smaller partial
            exchange (same pair_key streams — deterministic per epoch/
            replica/nonce); every other halo row comes from the cache under
            stop_gradient. Gradients stay exact w.r.t. the forward actually
            computed: stale rows are constants, fresh rows back-prop through
            the wire codec's custom VJPs as always — so the backward
            collective also runs on the refresh geometry."""
            spec_h = hspec_r if cached else hspec

            def body(params, state, blk, tables_, cache, epoch, sample_key,
                     drop_key):
                blk = {k: v[0] for k, v in blk.items()}
                ni = hspec.pad_inner
                if cached:
                    cache_l = {k: v[0] for k, v in cache.items()}
                    with jax.named_scope(tp.BNS_SAMPLE):
                        plan = make_halo_plan_refresh(
                            spec_h, tables_, blk["bnd"], epoch, sample_key,
                            refresh_k)
                    mask = refresh_row_mask(spec_h, refresh_k, epoch)
                    # a refreshed chunk's presence replaces its stored bits;
                    # stale chunks keep the presence of the epoch that last
                    # drew them (their rows ARE that epoch's sample)
                    presence_h = jnp.where(mask, plan.presence[ni:],
                                           cache_l["presence"])
                else:
                    with jax.named_scope(tp.BNS_SAMPLE):
                        plan = make_halo_plan(hspec, tables_, blk["bnd"],
                                              epoch, sample_key)
                    mask = None
                    presence_h = plan.presence[ni:]
                presence = jnp.concatenate(
                    [jnp.ones(ni, dtype=bool), presence_h])
                cache_out = {
                    "presence": jax.lax.stop_gradient(presence_h)[None]}

                def combine(i, fresh):
                    if cached:
                        old = jax.lax.stop_gradient(
                            cache_l[f"layer_{i}"]).astype(fresh.dtype)
                        fresh = jnp.where(mask[:, None], fresh, old)
                    cache_out[f"layer_{i}"] = jax.lax.stop_gradient(
                        fresh)[None]
                    return fresh

                def exchange(i, h):
                    recv = halo_start(spec_h, plan, h)
                    buf = combine(i, halo_finish(spec_h, plan, recv, h))
                    return jnp.concatenate([h, buf], 0), presence

                me = jax.lax.axis_index(axis)
                rng = jax.random.fold_in(
                    jax.random.fold_in(_replica_fold(drop_key), epoch), me)
                env = _local_env(
                    spec, spec_h, blk, plan, rng, cfg.edge_chunk, True,
                    aggregate=_aggregate_for(blk), gat_ell=_gat_ell_for(blk),
                    agg_exchange=_split_agg_for(blk, plan, spec_h=spec_h,
                                                combine=combine),
                    n_replicas=n_rep, feat_axis=fe_axis, n_feat=n_fe,
                    exchange=exchange, presence=presence)
                logits, new_state = apply_model(params, state, spec,
                                                blk["feat"], env)
                return _train_loss(logits, blk), (new_state, cache_out)

            if cached:
                return body
            # the full-refresh step takes no cache input
            return (lambda params, state, blk, tables_, epoch, sample_key,
                    drop_key: body(params, state, blk, tables_, None, epoch,
                                   sample_key, drop_key))

        # the cache travels as a stacked (per-(replica,part)-varying) pytree:
        # each mesh slot keeps its own blocks — replicas drew independent
        # samples, feat shards hold H/T-wide slices
        sharded_full = jax.shard_map(
            _make_refresh_loss(False), mesh=mesh,
            in_specs=(param_spec, rep, blk_spec, rep, rep, rep, rep),
            out_specs=(rep, (rep, stacked)))
        sharded_cached = jax.shard_map(
            _make_refresh_loss(True), mesh=mesh,
            in_specs=(param_spec, rep, blk_spec, rep, stacked, rep, rep, rep),
            out_specs=(rep, (rep, stacked)))

        @partial(jax.jit, donate_argnums=(0, 1, 2))
        def train_step_full(params, state, opt_state, epoch, blk, tables,
                            sample_key, drop_key):
            (loss, (new_state, cache)), grads = jax.value_and_grad(
                sharded_full, has_aux=True)(
                    params, state, blk, tables, epoch, sample_key, drop_key)
            params, opt_state = apply_grads(grads, opt_state, params)
            return params, new_state, opt_state, loss, cache

        @partial(jax.jit, donate_argnums=(0, 1, 2, 6))
        def train_step_cached(params, state, opt_state, epoch, blk, tables_r,
                              cache, sample_key, drop_key):
            (loss, (new_state, new_cache)), grads = jax.value_and_grad(
                sharded_cached, has_aux=True)(
                    params, state, blk, tables_r, cache, epoch, sample_key,
                    drop_key)
            params, opt_state = apply_grads(grads, opt_state, params)
            return params, new_state, opt_state, loss, new_cache

        def local_exchange_only_refresh(blk, tables_r, epoch, sample_key,
                                        width):
            blk = {k: v[0] for k, v in blk.items()}
            plan = make_halo_plan_refresh(hspec_r, tables_r, blk["bnd"],
                                          epoch, sample_key, refresh_k)
            comm_dtype = (jnp.bfloat16 if cfg.dtype == "bfloat16"
                          else jnp.float32)
            h = jnp.zeros((hspec_r.pad_inner, width), dtype=comm_dtype)
            out = halo_finish(hspec_r, plan, halo_start(hspec_r, plan, h), h)
            return jnp.sum(out)[None]

        def exchange_only_refresh(blk, tables_r, epoch, sample_key, width):
            """Comm(s) microbench on the partial-refresh geometry — what a
            steady-state (cache-hit) epoch actually puts on the wire."""
            f = jax.shard_map(
                partial(local_exchange_only_refresh, width=width), mesh=mesh,
                in_specs=(blk_spec, rep, rep, rep), out_specs=stacked)
            return f(blk, tables_r, epoch, sample_key)

        refresh_fns = dict(
            train_step_full=train_step_full,
            train_step_cached=train_step_cached,
            exchange_only_refresh=jax.jit(exchange_only_refresh,
                                          static_argnames="width"),
            tables_refresh=tables_refresh)

    def local_forward(params, state, blk, tables, epoch, sample_key, drop_key):
        blk = {k: v[0] for k, v in blk.items()}
        plan = make_halo_plan(hspec, tables, blk["bnd"], epoch, sample_key)
        me = jax.lax.axis_index(axis)
        rng = None
        if drop_key is not None:
            rng = jax.random.fold_in(
                jax.random.fold_in(_replica_fold(drop_key), epoch), me)
        exch, pres = _grad_only_override()
        env = _local_env(spec, hspec, blk, plan, rng, cfg.edge_chunk, True,
                         aggregate=_aggregate_for(blk), gat_ell=_gat_ell_for(blk),
                         agg_exchange=_split_agg_for(blk, plan),
                         n_replicas=n_rep, feat_axis=fe_axis, n_feat=n_fe,
                         exchange=exch, presence=pres)
        logits, _ = apply_model(params, state, spec, blk["feat"], env)
        return logits[None]

    @jax.jit
    def forward(params, state, epoch, blk, tables, sample_key, drop_key=None):
        """Training-mode forward (per-epoch sampling active), logits per part.
        Replica meshes de-duplicate the report to replica 0's draw so the
        host-side consumers keep the [P, pad_inner, C] shape."""
        f = jax.shard_map(
            partial(local_forward),
            mesh=mesh,
            in_specs=(param_spec, rep, blk_spec, rep, rep, rep, rep),
            out_specs=stacked)
        out = f(params, state, blk, tables, epoch, sample_key, drop_key)
        return dedup_replica0(out, mesh, hspec.n_parts)

    def local_embed(params, state, blk, tables_full):
        """Mesh-distributed full-rate eval forward returning (hidden,
        logits) — hidden is the final layer's input, the embedding-export
        seam (--dump-embeddings / serve cold-start). Eval-path semantics:
        no dropout, all halos present, BN running stats; the caller
        supplies eval-graph artifacts so norms are the eval graph's own
        degrees (module/layer.py:39-45,93-102). local_eval below is its
        logits half, so the two can never drift."""
        blk = {k: v[0] for k, v in blk.items()}
        zero = jnp.zeros((), jnp.uint32)
        plan = make_halo_plan(hspec_full, tables_full, blk["bnd"], zero,
                              # graftlint: disable=prng-literal-key(eval path is deterministic by design: exact plan ignores the key)
                              jax.random.key(0))
        env = _local_env(spec, hspec_full, blk, plan, None, cfg.edge_chunk,
                         False, aggregate=_aggregate_for(blk),
                         gat_ell=_gat_ell_for(blk),
                         n_replicas=n_rep, feat_axis=fe_axis, n_feat=n_fe)
        logits, _, hidden = apply_model(params, state, spec, blk["feat"],
                                        env, return_hidden=True)
        return hidden[None], logits[None]

    def local_eval(params, state, blk, tables_full):
        # the eval forward IS local_embed's logits output (XLA dead-code-
        # eliminates the unused hidden half under jit)
        return local_embed(params, state, blk, tables_full)[1]

    @jax.jit
    def eval_forward(params, state, blk, tables_full):
        # full-rate eval is deterministic, so every replica computes the
        # same logits; metrics de-duplicate to replica 0's copy
        f = jax.shard_map(local_eval, mesh=mesh,
                          in_specs=(param_spec, rep, blk_spec, rep),
                          out_specs=stacked)
        return dedup_replica0(f(params, state, blk, tables_full),
                              mesh, hspec.n_parts)

    @jax.jit
    def embed_forward(params, state, blk, tables_full):
        f = jax.shard_map(local_embed, mesh=mesh,
                          in_specs=(param_spec, rep, blk_spec, rep),
                          out_specs=(stacked, stacked))
        hid, lg = f(params, state, blk, tables_full)
        return (dedup_replica0(hid, mesh, hspec.n_parts),
                dedup_replica0(lg, mesh, hspec.n_parts))

    @jax.named_scope(tp.PP_PRECOMPUTE)
    def local_precompute(blk, tables_full):
        blk = {k: v[0] for k, v in blk.items()}
        agg = _aggregate_pre_for(blk) or (lambda h: agg_sum(
            h, blk["src"], blk["dst"], hspec.pad_inner, cfg.edge_chunk))
        feat_ext = precompute_exchange(hspec_full, tables_full, blk["bnd"], blk["feat"])
        if spec.model == "gcn":
            # (Σ feat_u / sqrt(out_deg_u)) / sqrt(in_deg_v)  (train.py:190-199)
            out = agg(feat_ext / blk["out_norm"][:, None]) / blk["in_norm"][:, None]
        elif spec.model == "graphsage":
            # concat[feat, mean_nbr]  (train.py:200-207); note reference uses
            # fn.mean over the constructed graph == sum / global in_deg here
            ah = agg(feat_ext) / blk["in_norm"][:, None]
            out = jnp.concatenate([blk["feat"], ah], axis=1)
        elif spec.model == "gat":
            out = feat_ext                                   # cached raw halo feats
        else:
            raise ValueError(spec.model)
        return out[None]

    @jax.jit
    def precompute(blk, tables_full):
        # one-time, full-rate, key-free — replicas compute identical copies;
        # de-dup to replica 0 so the result drops back into the P('parts')
        # block dict (re-replicated over the replica axis on placement)
        f = jax.shard_map(local_precompute, mesh=mesh,
                          in_specs=(blk_spec, rep), out_specs=stacked)
        return dedup_replica0(f(blk, tables_full), mesh, hspec.n_parts)

    def local_exchange_only(blk, tables, epoch, sample_key, width):
        blk = {k: v[0] for k, v in blk.items()}
        plan = make_halo_plan(hspec, tables, blk["bnd"], epoch, sample_key)
        # the payload must be the TRAINING compute dtype: with
        # --dtype bfloat16 --halo-wire native the wire ships bf16, and an
        # f32 microbench payload would report 2x the training step's bytes
        comm_dtype = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
        h = jnp.zeros((hspec.pad_inner, width), dtype=comm_dtype)
        out = halo_apply(hspec, plan, h)
        return jnp.sum(out)[None]

    def exchange_only(blk, tables, epoch, sample_key, width):
        """Isolated halo exchange x n_graph_layers — the Comm(s) microbench.
        Per-replica sums differ (independent draws): stacked out spec."""
        f = jax.shard_map(partial(local_exchange_only, width=width),
                          mesh=mesh,
                          in_specs=(blk_spec, rep, rep, rep), out_specs=stacked)
        return f(blk, tables, epoch, sample_key)

    fns = StepFns(train_step=train_step, forward=forward,
                  precompute=precompute, exchange_only=jax.jit(
                      exchange_only, static_argnames="width"),
                  eval_forward=eval_forward,
                  embed_forward=embed_forward,
                  extra_blk=ell_arrays,
                  drop_blk_keys=(("src", "dst")
                                 if (ell_spmm is not None or gat_spec is not None)
                                 else ()),
                  overlap=overlap,
                  loss_and_grad=loss_and_grad,
                  n_replicas=n_rep,
                  n_feat=n_fe,
                  param_spec=param_spec,
                  halo_refresh=refresh_k,
                  halo_mode=halo_mode,
                  halo_strategy=halo_strategy,
                  spmm_counts=spmm_counts(
                      "hybrid" if hybrid_pairs is not None
                      else "ell" if ell_spmm is not None
                      else "gat-ell" if gat_spec is not None else "segment",
                      spec, ell_arrays, art.feat.shape[0], hybrid_pairs,
                      dense_pp, cfg.spmm_dense, n_fe),
                  spmm_desc=spmm_desc,
                  **refresh_fns)
    return fns, hspec, tables, tables_full


@jax.jit
def param_global_norm(params) -> jax.Array:
    """Global L2 norm over every param leaf (f32 accumulation).

    The resilience divergence guard's cheap probe: a non-finite result means
    some leaf went NaN/Inf even when the masked loss still reads finite.
    Replicated inputs -> replicated scalar; one tiny fused reduction, run
    host-side every `log_every` epochs only."""
    leaves = [l for l in jax.tree.leaves(params) if hasattr(l, "dtype")]
    if not leaves:
        return jnp.zeros(())
    return jnp.sqrt(sum(jnp.sum(jnp.square(l.astype(jnp.float32)))
                        for l in leaves))


def init_training(cfg: Config, spec: ModelSpec, mesh: Mesh, seed: int = 0,
                  dtype=jnp.float32):
    """Replicated params / state / optimizer state (reference train.py:331-338).
    The optimizer is the same make_tx(cfg) the train step uses.

    Feat-axis meshes (parallel/feat.py) place weight leaves SHARDED over
    'feat' per the regex partition rules, with the Adam moments adopting
    their weight's sharding; init still happens on the full host tree, so a
    feat=T run initializes bit-identically to feat=1 and checkpoints stay
    feat-invariant."""
    params, state = init_params(jax.random.key(seed), spec, dtype)
    opt_state = make_tx(cfg).init(params)
    if feat_mod.n_feat(mesh) > 1:
        params = feat_mod.place_params(params, mesh, spec)
        state = place_replicated(state, mesh)
        opt_state = feat_mod.place_state_like(opt_state, params, mesh)
    else:
        params = place_replicated(params, mesh)
        state = place_replicated(state, mesh)
        opt_state = place_replicated(opt_state, mesh)
    return params, state, opt_state


def warm_start_state(cfg: Config, params, state, log=print):
    """Continual-cycle warm start: adopt params + BN state from the
    checkpoint blob at cfg.warm_start, keeping the freshly-initialized
    optimizer (the fine-tune starts its own Adam moments — stale moments
    from a different graph/epoch horizon are noise, not signal). Returns
    HOST trees restored into the given templates; the caller re-places
    them on the mesh exactly like a resume would."""
    from bnsgcn_tpu import checkpoint as ckpt
    payload, err = ckpt.load_or_error(cfg.warm_start)
    if payload is None:
        raise ConfigError(f"--warm-start checkpoint unusable: {err}")
    p, _, s = ckpt.restore_into(payload, jax.device_get(params), None,
                                jax.device_get(state))
    log(f"Warm start from {cfg.warm_start} (epoch "
        f"{int(payload.get('epoch', 0))}, fresh optimizer)")
    return p, s


def abstract_step_inputs(cfg: Config, spec: ModelSpec, art, fns: StepFns,
                         tables: dict) -> dict:
    """ShapeDtypeStruct pytrees matching every argument of the compiled
    step/eval/exchange programs — the traceable twin of `init_training` +
    `build_block_arrays` + `place_*` that touches NO device: params/state
    come from `jax.eval_shape` of the real initializer, the block dict from
    the real host-side array builder, so `jax.make_jaxpr(fns.train_step)`
    over these avals yields exactly the program a run would compile
    (analysis/ir traces it on a host-only AbstractMesh, CI-safe).

    Returns {params, state, opt_state, epoch, blk, tables, key}: `key` is
    a typed-PRNG-key aval usable for both sample_key and drop_key; `blk`
    already folds `fns.extra_blk` / `fns.drop_blk_keys` and the bfloat16
    feature cast the run applies after placement."""
    aval = lambda v: jax.ShapeDtypeStruct(np.asarray(v).shape,
                                          np.asarray(v).dtype)
    blk_np = build_block_arrays(art, spec.model, dtype=np.float32)
    blk_np.update(fns.extra_blk)
    for k in fns.drop_blk_keys:
        blk_np.pop(k, None)
    blk = {k: aval(v) for k, v in blk_np.items()}
    if cfg.dtype == "bfloat16":
        blk["feat"] = jax.ShapeDtypeStruct(blk["feat"].shape, jnp.bfloat16)
    dtype = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    key = jax.eval_shape(jax.random.key, 0)
    params, state = jax.eval_shape(
        lambda k: init_params(k, spec, dtype), key)
    opt_state = jax.eval_shape(make_tx(cfg).init, params)
    return {
        "params": params, "state": state, "opt_state": opt_state,
        "epoch": jax.ShapeDtypeStruct((), jnp.uint32),
        "blk": blk,
        "tables": {k: aval(v) for k, v in tables.items()},
        "key": key,
    }
