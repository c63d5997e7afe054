"""End-to-end training orchestration (reference train.py:300-456 `run()`).

One Python process drives the whole mesh (SPMD replaces the reference's
process-per-partition fork, main.py:35-50): load or build partition
artifacts, place sharded device data, precompute, then the epoch loop — a
single jitted step per epoch plus host-side timing, logging, background
evaluation, checkpointing and a results file in the reference's format.

On the Reduce(s) column: the reference overlaps its gradient all-reduce with
the backward pass via hooks and side streams and reports the residual
synchronize time (train.py:410-412). Here the reduction is *inside* the
compiled step where XLA overlaps it with backward compute — there is no
separable host-visible reduce phase, so Reduce(s) reports 0; Comm(s) is
measured by a compiled exchange-only microbench on identical inputs.
"""

from __future__ import annotations

# the `import` boot stamp spans this module's body: the program's modules,
# flax and optax, and whatever of jax was not loaded before (obs imports
# nothing but the standard library)
from bnsgcn_tpu import obs as obs_mod

obs_mod.boot_begin("import")

import contextlib
import math
import os
import shutil
import sys
import tempfile
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bnsgcn_tpu import checkpoint as ckpt
from bnsgcn_tpu import resilience
from bnsgcn_tpu import strict as strict_mod
from bnsgcn_tpu import tune as tune_mod
from bnsgcn_tpu.config import Config, ConfigError
from bnsgcn_tpu.data.artifacts import (PartitionArtifacts, build_artifacts,
                                       load_artifacts, save_artifacts)
from bnsgcn_tpu.data.datasets import inductive_split, load_data
from bnsgcn_tpu.data.graph import Graph
from bnsgcn_tpu.data.partitioner import partition_graph
from bnsgcn_tpu.data.reorder import maybe_reorder
from bnsgcn_tpu.evaluate import evaluate_induc, evaluate_mesh, evaluate_trans
from bnsgcn_tpu.models.gnn import ModelSpec, spec_from_config
from bnsgcn_tpu.parallel import coord as coord_mod
from bnsgcn_tpu.parallel import feat as feat_mod
from bnsgcn_tpu.parallel.mesh import replicated_sharding
from bnsgcn_tpu.parallel.replicas import make_mesh, mesh_desc, slot_desc
from bnsgcn_tpu.trainer import (LAST_BUILD_TIMINGS, build_block_arrays,
                                build_step_fns, init_training,
                                local_part_ids, param_global_norm, place_blocks,
                                place_blocks_local, place_replicated,
                                warm_start_state)
from bnsgcn_tpu.utils import traceparse
from bnsgcn_tpu.utils.timers import EpochTimer, format_memory_stats


def artifacts_dir(cfg: Config) -> str:
    name = cfg.graph_name or cfg.derive_graph_name()
    return os.path.join(cfg.part_path, name)


def artifact_digest(art) -> str:
    """Content address of the partition: sha1 over (n_b, src, dst), the
    same recipe the layout and reorder caches key by. The continual cycle
    records it in promotion lineage / run_header so a promoted model is
    traceable to the exact mutated artifact it was fine-tuned on."""
    import hashlib
    dg = hashlib.sha1()
    for a in (art.n_b, art.src, art.dst):
        # buffer protocol, not .tobytes(): no transient copy of the
        # (papers100M-scale: multi-GB) edge arrays just to hash them
        dg.update(np.ascontiguousarray(a))
    return dg.hexdigest()[:12]


def prepare_partition(cfg: Config, g: Optional[Graph] = None,
                      force: bool = False, load: bool = True
                      ) -> Optional[PartitionArtifacts]:
    """Offline partitioning step (reference graph_partition, helper/utils.py:73-98):
    skipped when the artifact dir already exists, like the reference's config-
    JSON existence check (:87).

    Large graphs route through the streaming builder (one part resident at a
    time, vectorized passes — the papers100M-scale path; cfg.streaming_artifacts
    'auto' switches at 30M edges). `load=False` (offline partition_cli) writes
    the artifacts without stacking them back into host memory."""
    path = artifacts_dir(cfg)
    if not force and os.path.exists(os.path.join(path, "meta.json")):
        return load_artifacts(path) if load else None
    if g is None:
        g, _, _ = load_data(cfg)
        if cfg.inductive:
            g = g.subgraph(g.train_mask)        # helper/utils.py:76-77
    pid = partition_graph(g, cfg.n_partitions, method=cfg.partition_method,
                          obj=cfg.partition_obj, seed=cfg.seed)
    streaming = (cfg.streaming_artifacts == "always" or
                 (cfg.streaming_artifacts == "auto" and g.n_edges > 30_000_000))
    if streaming:
        from bnsgcn_tpu.data.artifacts import build_artifacts_streaming
        build_artifacts_streaming(g, pid, path, feat_dtype=cfg.feat_storage,
                                  log=print)
        return load_artifacts(path) if load else None
    art = build_artifacts(g, pid)
    save_artifacts(art, path)
    return art


# best-params recovery contract: consolidated in checkpoint.py (PR 7) so the
# serving loader shares the exact same selection/validation entry points
_final_best_payload = ckpt.final_best_payload


# the jitted program behind each strict-exec step variant (step_variants)
STEP_PROGRAMS = {"step": "train_step", "full": "train_step_full",
                 "cached": "train_step_cached"}


def step_variants(fns) -> tuple:
    """Strict-exec step-program variant names the epoch loop can execute
    with these step fns: the `--halo-refresh` pair ('full' at epoch 0 and
    after every cache invalidation, 'cached' in steady state) when the
    cached program exists, else the single 'step' program. The loop below
    derives the per-epoch pick from the cache state; this is the static
    vocabulary — what `--strict-exec` arms per variant and what the
    analysis/ir preflight traces per lever state."""
    return (("full", "cached") if fns.train_step_full is not None
            else ("step",))


def check_mesh_budget(cfg: Config, devices=None) -> None:
    """ONE named config error when R x P x T exceeds the device budget,
    raised before any mesh/axis-specific constructor can fail with its own
    partial message (previously only the replicas path raised, from inside
    make_mesh). Lists which axis to shrink; main.py maps ConfigError to
    exit 2."""
    have = len(devices if devices is not None else jax.devices())
    R, P_, T = max(cfg.replicas, 1), max(cfg.n_partitions, 1), max(cfg.feat, 1)
    need = R * P_ * T
    if need <= have:
        return
    fixes = []
    if T > 1 and R * P_ <= have:
        fixes.append(f"--feat to <= {have // (R * P_)}")
    if R > 1 and P_ * T <= have:
        fixes.append(f"--replicas to <= {have // (P_ * T)}")
    if P_ > have:
        fixes.append(f"--n-partitions to <= {have} (re-partition the graph)")
    if not fixes:
        fixes.append(f"some axis so replicas*parts*feat <= {have}")
    raise ConfigError(
        f"mesh does not fit: --replicas {R} x --n-partitions {P_} x "
        f"--feat {T} needs {need} devices, have {have}; shrink "
        + " or ".join(fixes)
        + (f", or use a CPU mesh via XLA_FLAGS="
           f"--xla_force_host_platform_device_count={need}"))


@dataclass
class RunResult:
    best_val_acc: float = 0.0
    test_acc: float = 0.0
    epoch_time: float = 0.0
    comm_time: float = 0.0
    reduce_time: float = 0.0
    final_loss: float = 0.0
    losses: list = field(default_factory=list)
    memory: str = ""
    overlap_buckets: dict = field(default_factory=dict)
    # --overlap split: trace-derived per-step exchange/interior/frontier/
    # hidden ms means (EpochTimer.bucket_means); empty for fused runs
    rollbacks: list = field(default_factory=list)
    # divergence recoveries this run performed: [{'epoch', 'restart',
    # 'source', 'nonce'}, ...] (resilience.ResilienceManager.rollbacks)


def run_training(cfg: Config, g: Optional[Graph] = None,
                 art: Optional[PartitionArtifacts] = None,
                 devices=None, verbose: bool = True) -> RunResult:
    log = print if verbose else (lambda *a, **k: None)

    # the program's first touch of the backend: the chip's start-up, unless
    # the caller started it (then this stamp reads about 0)
    obs_mod.boot_begin("backend_init")
    multi_host = jax.process_count() > 1
    obs_mod.boot_end("backend_init")
    is_rank0 = jax.process_index() == 0

    # ---- out-of-band rank coordination (multi-host resilience) ----
    # parallel/coord.py: failure verdicts travel OUTSIDE the XLA collectives
    # so a faulting rank can tell its peers instead of hanging them. None
    # under --coord off / single-rank runs — those paths are bit-identical
    # to the uncoordinated loop. --coord-rank/--coord-world run the same
    # layer without jax.distributed (each process a full single-host
    # trainer, coupled only through the coordinator): the subprocess fault
    # harness the CPU container can actually execute.
    coordinator, coord_rank = None, jax.process_index()
    if cfg.resilience == "on" and cfg.coord != "off":
        coordinator, coord_rank, _ = coord_mod.make_coordinator(cfg, log)
        if coordinator is not None and not multi_host:
            # external-rank harness mode: coordination rank 0 owns the
            # checkpoint dir (and host eval), exactly like jax rank 0 does
            # in a real multi-host run
            is_rank0 = coord_rank == 0

    # ---- elastic world size (--elastic on): a heartbeat-detected rank
    # loss becomes a coordinated RESIZE verdict (re-map the P parts onto
    # the survivors via mesh.plan_slots, rebuild step fns, resume from the
    # agreed checkpoint) instead of a CoordTimeout exit. Harness-mode only:
    # a real jax.distributed pod cannot reshape its process grid in place.
    # `joiner` marks a process relaunched AFTER a shrink verdict — it must
    # not replay the pre-loop collectives (those seq-space keys are retired
    # on the survivors) and instead re-enters through the rejoin handshake
    # below the resume block.
    joiner = False
    if cfg.elastic == "on":
        if coordinator is None:
            raise ConfigError(
                "--elastic on needs the rank coordinator: run with "
                "--resilience on and --coord tcp|file (got --coord "
                f"{cfg.coord}, --resilience {cfg.resilience})")
        if multi_host:
            raise ConfigError(
                "--elastic on is harness-mode only (--coord-world/"
                "--coord-rank): a jax.distributed process grid cannot be "
                "resized in place")
        coordinator.enable_elastic(cfg.elastic_min_world)
        if coord_rank != 0:
            joiner = coordinator.detect_rejoin()
            if joiner:
                log(f"[elastic] rank {coord_rank}: rejoining a resized "
                    f"world (lost-rank beacon found)")

    # ---- telemetry bus (obs.py): rank-tagged structured event log +
    # metrics registry. None under --obs off — every emit below is guarded,
    # so off constructs nothing and stays bit-identical (pinned). ----
    obs = obs_mod.make_obs(cfg, rank=coord_rank, log=log)
    span = partial(obs_mod.span, obs)           # loop phases (obs.PHASES)
    setup_span = partial(span, emit=True)       # set-up: one `span` event each
    setup_root = setup_span(obs_mod.SETUP_SPANS[0]).begin()

    # ---- data + eval graphs (train.py:313-319) ----
    # multi-host: only rank 0 ever needs the full undistributed graph (host
    # eval); the other ranks read just their partition artifacts
    val_g = test_g = None
    # transductive mesh eval runs entirely from partition artifacts — the
    # full undistributed graph is only needed for host eval / inductive splits
    trans_mesh_eval = (cfg.eval and cfg.eval_device == "mesh"
                       and not cfg.inductive)
    need_graph_eval = (cfg.eval and not trans_mesh_eval
                       and (is_rank0 or not multi_host))
    need_graph_partition = art is None and not (multi_host or cfg.skip_partition)
    if g is None and (need_graph_eval or need_graph_partition):
        with setup_span("load_graph"):
            g, _, _ = load_data(cfg)
    if cfg.eval and g is not None:
        if cfg.inductive:
            _, val_g, test_g = inductive_split(g)
        else:
            val_g = test_g = g
    train_g = g.subgraph(g.train_mask) if (cfg.inductive and g is not None) else g

    # ---- mesh + partition artifacts ----
    # --replicas N > 1: each replica row trains the same partitioned graph
    # under an independent BNS draw, gradients are the fused cross-replica
    # mean (parallel/replicas.py). --feat T > 1: the innermost mesh axis
    # shards hidden dimensions T-ways — zero boundary nodes on that axis,
    # halo payloads H/T wide, one feat psum per layer (parallel/feat.py).
    if (cfg.replicas > 1 or cfg.feat > 1) and multi_host:
        raise ValueError(
            "--replicas/--feat > 1 are single-host for now (multi-host "
            "processes map to parts slots only); run with --replicas 1 "
            "--feat 1 across hosts")
    check_mesh_budget(cfg, devices)
    mesh = make_mesh(cfg.n_partitions, cfg.replicas, cfg.feat, devices)
    if multi_host and art is not None:
        n_local = len(local_part_ids(mesh))
        if art.feat.shape[0] != n_local:
            raise ValueError(
                f"multi-host run_training(art=...) needs artifacts holding "
                f"only this process's {n_local} parts "
                f"(load_artifacts(parts=local_part_ids(mesh))), got "
                f"{art.feat.shape[0]} part rows")
    if art is None:
        art_span = setup_span("load_artifacts" if multi_host
                              or cfg.skip_partition
                              else "prepare_partition").begin()
        if multi_host:
            # each process loads only the parts whose mesh slots it hosts
            # (main.py already partitioned on rank 0 behind a barrier)
            mine = local_part_ids(mesh)
            if not mine:
                raise ValueError(
                    f"process {jax.process_index()} hosts no partition: use "
                    f"n_partitions >= {jax.process_count()} x local device "
                    f"count (mesh takes the first n_partitions global devices)")
            art = load_artifacts(artifacts_dir(cfg), parts=mine)
        elif cfg.skip_partition:
            art = load_artifacts(artifacts_dir(cfg))
        elif coordinator is not None:
            # harness mode without --skip-partition: only rank 0 builds;
            # peers wait at a coordinator barrier, then load the finished
            # artifacts — two concurrent builders would tear the shared dir
            # (real multi-host has main.py's XLA barrier for this)
            if coord_rank == 0:
                art = prepare_partition(cfg, train_g)
                coordinator.broadcast("parts-ready", {"ok": 1})
            elif joiner:
                # rejoining rank: the parts-ready broadcast key was retired
                # long ago on the survivors; the artifacts are already on
                # disk from the original build, so load them directly
                art = prepare_partition(cfg, train_g)
            else:
                coordinator.broadcast("parts-ready")
                art = prepare_partition(cfg, train_g)
        else:
            art = prepare_partition(cfg, train_g)
        art_span.end()
    cfg = cfg.replace(n_feat=art.n_feat, n_class=art.n_class, n_train=art.n_train)
    if (multi_host and cfg.spmm in ("ell", "auto")
            and art.ell_geometry is None):
        # pre-v2 artifacts lack the global ELL geometry a partial load needs
        # (hybrid gcn/graphsage is exempt: its shapes agree via a host-side
        # allgather, no meta.json geometry required — but 'auto' may resolve
        # to ell, which would build per-host tables of different shapes, so
        # it falls back too; GAT on hybrid still needs gat_fwd geometry and
        # falls back to segment attention inside the trainer)
        log("multi-host: artifacts carry no ELL geometry (old format); "
            "falling back to --spmm segment")
        cfg = cfg.replace(spmm="segment")
    # ---- reorder pass (before the layout digest: the digest below hashes
    # the POST-perm edge arrays, so permuted and raw layouts can never
    # alias each other in the cache) ----
    art, ro_resolved, _ro_info = maybe_reorder(cfg, art, log=log, obs=obs)
    cfg = cfg.replace(reorder=ro_resolved)

    # ---- closed-loop comm auto-tuner (--tune, tune.py): fold the launch
    # point of the schedule/anneal into cfg BEFORE the first build so a
    # coarse start (K=4, grad-only) never pays a throwaway compile ----
    _tune_start = None
    tune_mod.validate_mode(cfg, multi_host=multi_host,
                           coordinated=coordinator is not None)
    if cfg.tune != "off":
        _prior = None
        if cfg.tune == "auto" and cfg.tune_prior == "model":
            # --tune-prior model: the graftperf roofline (analysis/perf)
            # predicts the comm fraction from the partition geometry +
            # calibration tables and picks the launch rung, skipping
            # ladder rungs whose wire saving it prices as immaterial. The
            # table is the one calibrated for THIS device kind; a device
            # nobody calibrated has no prior — say so and start the ladder
            # at its default rung.
            from bnsgcn_tpu.analysis.perf import calibration as _pcal
            from bnsgcn_tpu.analysis.perf import model as _pmod
            try:
                _table = _pcal.backend_table(_pcal.load_calibration(),
                                             jax.devices()[0].device_kind)
            except KeyError as ex:
                _table = None
                log(f"[tune] model prior unavailable ({ex.args[0]}); "
                    f"using ladder start")
            if _table is not None:
                _strat = (cfg.halo_exchange if cfg.halo_exchange in
                          ("padded", "shift", "ragged") else "padded")
                _feat = _pmod.run_features(cfg, art, strategy=_strat)
                _prior = _pmod.model_prior(_feat, _table,
                                           comm_frac=tune_mod.AUTO_COMM_FRAC)
                log(f"[tune] prior: predicted step "
                    f"{_prior['step_s'] * 1e3:.1f} ms, wire "
                    f"{_prior['wire_s'] * 1e3:.2f} ms "
                    f"(comm {_prior['comm_frac']:.1%})")
        _ch0, _why0 = tune_mod.startup_changes(cfg, prior=_prior)
        if _ch0:
            cfg = cfg.replace(**_ch0)
            _tune_start = (_ch0, _why0)
            log(f"[tune] {_why0}: " + ", ".join(
                f"{k}={v}" for k, v in sorted(_ch0.items())))

    # ---- step functions + device data ----
    spec = spec_from_config(cfg)
    # --cache-dir / $BNSGCN_CACHE_DIR: persist SpMM layout builds (~980 s at
    # bench scale for hybrid) across container wipes. Files are addressed by
    # (graph name, trainer.hybrid_layout_key), the same content keys bench.py
    # uses, so knob changes can never read a stale geometry.
    layout_cache = lc_loaded = None
    if cfg.cache_dir:
        from bnsgcn_tpu.trainer import (ell_layout_key, gat_layout_key,
                                        hybrid_layout_key)
        from bnsgcn_tpu.utils.diskcache import (atomic_dump, sweep_stale_tmp,
                                                try_load)
        os.makedirs(cfg.cache_dir, exist_ok=True)
        # a crashed/preempted writer mid-atomic_dump leaves a torn *.tmp —
        # sweep them on open so the dir can't accumulate garbage
        sweep_stale_tmp(cfg.cache_dir, log)
        gname = cfg.graph_name or cfg.derive_graph_name()
        # content-address the PARTITION, not just its name: layouts are a
        # pure function of (src, dst) — a re-partition under the same graph
        # name (changed seed, random method) or another host's partial-load
        # rows must never read each other's files
        digest = artifact_digest(art)

        def _lc_path(key):
            return os.path.join(
                cfg.cache_dir,
                f"layouts_{gname}_{digest}_{key.replace(':', '-')}.pkl")

        # preload both the fused and (under --overlap split) the ':ovl'
        # split-layout namespaces — build_step_fns may fall back to off,
        # and a downgraded run must still find its fused tables
        keys = {ell_layout_key(cfg.replace(overlap="off")),
                gat_layout_key(cfg),
                hybrid_layout_key(cfg.replace(overlap="off"))}
        if cfg.overlap == "split":
            keys |= {ell_layout_key(cfg), hybrid_layout_key(cfg)}
        layout_cache, lc_loaded = {}, {}
        with setup_span("layout_cache_load"):
            for key in sorted(keys):
                obj = try_load(_lc_path(key), log)
                if obj is not None:
                    layout_cache[key] = obj
                    lc_loaded[key] = id(obj)
    elif cfg.tune != "off":
        # no disk cache, but the --tune controller may rebuild the step fns
        # mid-run: an in-memory layout cache makes those rebuilds hit the
        # already-built SpMM layouts (the layout keys do not depend on any
        # tuned lever), so a retune never pays the layout build twice
        layout_cache, lc_loaded = {}, {}
    with setup_span("build_step_fns") as build_span:
        fns, hspec, tables, tables_full = build_step_fns(
            cfg, spec, art, mesh, layout_cache=layout_cache)
    if obs is not None:
        for _st in LAST_BUILD_TIMINGS:
            obs.emit("layout_build", parent=build_span.name, **_st)
    if cfg.cache_dir and layout_cache is not None:
        for key, obj in layout_cache.items():
            # new or repaired-in-place entries (id changed) get persisted
            if lc_loaded.get(key) != id(obj):
                atomic_dump(obj, _lc_path(key))
                log(f"  layout cache: wrote {_lc_path(key)}")
    place_span = setup_span("place").begin()
    np_dtype = np.float32  # norms/feat host dtype; bf16 cast happens on device
    blk_np = build_block_arrays(art, spec.model, dtype=np_dtype)
    blk_np.update(fns.extra_blk)        # ELL SpMM layouts, if enabled
    for k in fns.drop_blk_keys:         # COO unused under ELL: save the HBM
        blk_np.pop(k, None)
    blk = place_blocks_local(blk_np, mesh) if multi_host else place_blocks(blk_np, mesh)
    if cfg.dtype == "bfloat16":
        blk["feat"] = blk["feat"].astype(jnp.bfloat16)
    tables = place_replicated(tables, mesh)
    tables_full_d = place_replicated(tables_full, mesh)
    tables_refresh_d = (place_replicated(fns.tables_refresh, mesh)
                        if fns.tables_refresh is not None else None)
    place_span.end()
    if spec.use_pp:
        # like `place`, the host's side of it: the transfers and the
        # precompute run on while the host goes on (waiting here cost the
        # flagship 3 s of set-up, PR 27); the first step's wait holds what
        # is left of them
        with setup_span("pp_precompute"):
            out = fns.precompute(blk, tables_full_d)
            if cfg.dtype == "bfloat16":
                out = out.astype(jnp.bfloat16)
        if spec.model == "gat":
            blk["feat0_ext"] = out
        else:
            blk["feat"] = out
    from bnsgcn_tpu.parallel.halo import ragged_native_ok, wire_bytes
    nb = 2 if cfg.dtype == "bfloat16" else 4
    # Comm column context: the halo label is the RESOLVED strategy (under
    # --halo-exchange auto the pick was logged by build_step_fns; 'auto->'
    # here keeps the per-run record self-describing). --overlap split tags
    # the label '+ovl' the same way; the EXCHANGE itself is unchanged by the
    # split (same spec, same per-layer bytes, still one forward + one
    # backward hop per layer), so wire_bytes below is reported exactly once
    # — the interior/frontier split must never double-count it.
    halo_label = (f"auto->{hspec.strategy}"
                  if cfg.halo_exchange == "auto" else hspec.strategy)
    if fns.overlap == "split":
        halo_label += "+ovl"
    if fns.n_replicas > 1:
        halo_label += f"+rep{fns.n_replicas}"
    if fns.n_feat > 1:
        halo_label += f"+feat{fns.n_feat}"
    use_refresh = fns.train_step_full is not None   # --halo-refresh K > 1
    grad_only = fns.halo_mode == "grad-only"
    if grad_only:
        halo_label += "+go"
    elif use_refresh:
        halo_label += f"+hr{fns.halo_refresh}"
    if cfg.reorder != "off":
        halo_label += "+ro"
    # wire bytes are PER REPLICA per device (each replica row runs its own
    # parts-axis exchange) and reported exactly once — the replica axis adds
    # one fused gradient all-reduce per step, never more halo traffic. The
    # feat axis SHRINKS the parts-axis payload instead: a feat-sharded
    # layer's exchange ships its H/T activation slice, so the per-axis
    # numbers below drop ~T x vs feat=1 (GAT exchanges stay full-width —
    # that model shards heads, not the exchanged input).
    T_fe = fns.n_feat

    def _wire_w(fin):
        # GAT exchanges its full-width input (it shards heads, not the
        # exchanged activations); GCN/SAGE ship the H/T slice
        return feat_mod.shard_width(fin, T_fe,
                                    spec.model in ("gcn", "graphsage"))

    per_rep = "/replica" if fns.n_replicas > 1 else ""
    hid_w = _wire_w(cfg.n_hidden)
    feat_note = (f" (H/T={hid_w} of {cfg.n_hidden} on the parts wire: "
                 f"~{cfg.n_hidden // max(hid_w, 1)}x less than feat=1)"
                 if hid_w != cfg.n_hidden else "")
    log(f"Mesh: {mesh_desc(mesh)} | pad_inner={art.pad_inner} "
        f"pad_boundary={art.pad_boundary} pad_send={hspec.pad_send} "
        f"edges/part={art.pad_edges} | halo {halo_label}/{hspec.wire}: "
        f"{wire_bytes(hspec, hid_w, nb) / 1e6:.2f} MB/exchange/device{per_rep} "
        f"at hidden width {cfg.n_hidden}" + feat_note
        + ("" if spec.use_pp or spec.model == "gat" else
           f" ({wire_bytes(hspec, _wire_w(max(cfg.n_feat, 1)), nb) / 1e6:.2f}"
           f" MB at layer-0 feature width {cfg.n_feat})"))

    # what the step was BUILT with, as opposed to what the flags asked for:
    # the dense-tile path that really runs (the XLA twin off-TPU) and
    # whether 'ragged' is the native collective or its all_to_all
    # emulation — so a log always shows what ran
    log(f"Step: spmm {fns.spmm_desc} | halo exchange {hspec.strategy}"
        + ("" if hspec.strategy != "ragged" else
           " (native ragged_all_to_all)" if ragged_native_ok() else
           " (emulated over the padded all_to_all: no native lowering on "
           f"{jax.default_backend()})"))

    # one machine-readable run header: everything the per-run log line above
    # says, plus the config the run is actually executing — the record
    # obs_report joins epochs/lifecycle events against
    halo_wire_mb = wire_bytes(hspec, hid_w, nb) / 1e6
    # --halo-refresh K: halo_wire_mb above is the PEAK (full-refresh-epoch)
    # cost; steady-state (cache-hit) epochs ship only the ~1/K partial
    # exchange. Both numbers go to the log and the run_header — reporting
    # just the peak was the old header's lie for duty-cycled runs.
    # grad-only ships nothing per step at all.
    steady_wire_mb = halo_wire_mb
    if grad_only:
        steady_wire_mb = 0.0
        log("  halo grad-only: 0.00 MB/exchange steady-state (no activation "
            "exchange; the gradient all-reduce is the only collective)")
    elif use_refresh:
        from bnsgcn_tpu.parallel.halo import make_refresh_spec
        hspec_r, _ = make_refresh_spec(
            art.n_b, art.pad_inner, art.pad_boundary, cfg.sampling_rate,
            fns.halo_refresh, strategy=hspec.strategy, wire=hspec.wire)
        steady_wire_mb = wire_bytes(hspec_r, hid_w, nb) / 1e6
        log(f"  halo refresh K={fns.halo_refresh}: peak {halo_wire_mb:.2f} "
            f"MB/exchange (full-refresh epochs), steady-state "
            f"{steady_wire_mb:.2f} MB "
            f"({steady_wire_mb / max(halo_wire_mb, 1e-12):.0%} of peak)")
    if obs is not None:
        # continual-cycle provenance: only attached when a cycle is live, so
        # every pre-continual run_header stays byte-identical
        continual_hdr = ({"warm_start": cfg.warm_start,
                          "cycle_nonce": int(cfg.cycle_nonce),
                          "artifact_digest": artifact_digest(art)}
                         if (cfg.warm_start or cfg.cycle_nonce) else None)
        obs.emit(
            "run_header", mesh=mesh_desc(mesh),
            **({"continual": continual_hdr} if continual_hdr else {}),
            replicas=int(fns.n_replicas), parts=int(cfg.n_partitions),
            feat=int(fns.n_feat), halo=halo_label, wire=hspec.wire,
            wire_mb_per_exchange=round(halo_wire_mb, 4),
            wire_mb_steady=round(steady_wire_mb, 4),
            halo_refresh=int(fns.halo_refresh), halo_mode=fns.halo_mode,
            spmm=fns.spmm_counts,
            partition={"pad_inner": int(art.pad_inner),
                       "pad_boundary": int(art.pad_boundary),
                       "pad_send": int(hspec.pad_send),
                       "edges_per_part": int(art.pad_edges)},
            config={k: getattr(cfg, k) for k in (
                "dataset", "graph_name", "model", "n_layers", "n_hidden",
                "heads", "sampling_rate", "lr", "dtype", "spmm",
                "spmm_gather", "spmm_dense", "halo_exchange",
                "halo_wire", "halo_refresh", "halo_mode", "overlap",
                "reorder", "tune", "tune_schedule", "tune_prior",
                "n_epochs", "log_every", "seed",
                "inductive", "use_pp", "resilience", "coord")})

    # ---- --tune controller, bound to the RESOLVED levers (post
    # startup fold, post `--halo-exchange auto` pick): the base every
    # later rewind/restore diffs against ----
    tuner = None
    if cfg.tune != "off":
        tuner = tune_mod.Tuner(cfg, levers={
            "halo_refresh": int(fns.halo_refresh),
            "halo_mode": fns.halo_mode,
            "halo_exchange": fns.halo_strategy,
            "halo_wire": hspec.wire,
        }, log=log)
        if _tune_start is not None:
            _ent0 = tuner.record_startup(*_tune_start)
            if obs is not None:
                obs.emit("tune_decision", **_ent0)
        if cfg.tune == "auto":
            from bnsgcn_tpu.parallel.halo import retune_strategy
            # precompute the byte-estimate strategy re-pick once — the
            # partition geometry it reads never changes mid-run
            tuner.strategy_alt = retune_strategy(
                art.n_b, art.pad_inner, art.pad_boundary, cfg.sampling_rate,
                current=fns.halo_strategy, wire=hspec.wire)

    # ---- mesh-distributed eval resources (--eval-device mesh) ----
    mesh_eval = cfg.eval and cfg.eval_device == "mesh"
    host_dev = None
    if (is_rank0 and not mesh_eval
            and (cfg.eval or cfg.dump_embeddings)):
        # --eval-device host: this run's full-graph forwards (eval, and the
        # --dump-embeddings table) compute on the CPU backend, never on a
        # training chip. Resolved now so a process without one fails before
        # epoch 0.
        from bnsgcn_tpu.evaluate import host_device
        host_dev = host_device()
    eval_val = None                    # (fns, blk, tables_full_d, art)

    def _eval_resources(graph, name_suffix):
        if not cfg.inductive:
            # same graph as training: share every placed training array and
            # swap only 'feat' for the raw (non-precomputed, f32) features
            b = dict(blk)
            raw = {"feat": build_block_arrays(art, spec.model)["feat"]}
            if multi_host:
                b["feat"] = place_blocks_local(raw, mesh)["feat"]
            else:
                b["feat"] = jax.device_put(raw["feat"],
                                           blk["inner_mask"].sharding)
            return fns, b, tables_full_d, art
        base = cfg.graph_name or cfg.derive_graph_name()
        cfg_e = cfg.replace(graph_name=base + name_suffix)
        if multi_host:
            # rank 0 (which holds the eval subgraph) partitions it; everyone
            # else waits at the barrier, then loads only its own parts
            from jax.experimental import multihost_utils
            if is_rank0 and not os.path.exists(
                    os.path.join(artifacts_dir(cfg_e), "meta.json")):
                prepare_partition(cfg_e, graph, load=False)  # build+save only when missing
            multihost_utils.sync_global_devices(f"bnsgcn_eval_parts{name_suffix}")
            # agree across ranks so EVERY process fails fast (a rank that has
            # the files must not sail into the next collective alone)
            have = int(os.path.exists(
                os.path.join(artifacts_dir(cfg_e), "meta.json")))
            all_have = np.asarray(
                multihost_utils.process_allgather(np.int64(have))).min()
            if not all_have:
                raise FileNotFoundError(
                    f"eval partition artifacts missing at {artifacts_dir(cfg_e)} "
                    f"on at least one host: part_path must be a shared "
                    f"filesystem, or pre-distribute the eval artifact dirs "
                    f"(partition_cli --inductive --eval-device mesh builds "
                    f"them), or use --eval-device host")
            art_e = load_artifacts(artifacts_dir(cfg_e),
                                   parts=local_part_ids(mesh))
        else:
            art_e = prepare_partition(cfg_e, graph)
        # the training cfg already carries the RESOLVED reorder mode, so the
        # eval subgraph gets the same treatment (its own perm — row ids are
        # per-artifact) and gather_parts' global_nid indexing undoes it
        art_e, _, _ = maybe_reorder(cfg_e.replace(reorder=cfg.reorder),
                                    art_e, log=log)
        fns_e, _, _, tf = build_step_fns(cfg, spec, art_e, mesh)
        b = build_block_arrays(art_e, spec.model)
        b.update(fns_e.extra_blk)
        for k in fns_e.drop_blk_keys:
            b.pop(k, None)
        placed = place_blocks_local(b, mesh) if multi_host else place_blocks(b, mesh)
        return fns_e, placed, place_replicated(tf, mesh), art_e

    if mesh_eval:
        eval_val = _eval_resources(val_g, "-val")

    # ---- model / optimizer init, optionally resumed ----
    seed = cfg.seed
    if joiner:
        # rejoining rank: the seed broadcast key is long retired on the
        # survivors; rank 0's bootstrap facts live under the never-retired
        # el/boot key exactly so late joiners can adopt the run seed
        seed = int(coordinator.boot_info()["seed"])
    elif coordinator is not None and not multi_host:
        # harness-mode analogue of main.py's XLA seed broadcast: every rank
        # must adopt rank 0's (possibly randomized) seed or the shared-PRNG
        # sampling/dropout/init streams desync across ranks
        seed = int(coordinator.broadcast(
            "seed", {"seed": seed} if coord_rank == 0 else None)["seed"])
    if cfg.elastic == "on" and coord_rank == 0:
        coordinator.publish_boot({"seed": seed})
    dtype = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    with setup_span("init_training"):
        params, state, opt_state = init_training(cfg, spec, mesh, seed=seed,
                                                 dtype=dtype)
    # every resume/rollback below restores HOST trees back onto the mesh;
    # feat-sharded meshes re-place them under the captured template
    # shardings (weights + Adam moments sharded over 'feat' — checkpoints
    # themselves are always saved unsharded via jax.device_get, so they
    # stay feat-invariant); feat=1 keeps the historical replicated
    # placement verbatim, including the multi-host local-data path
    if cfg.feat > 1:
        _p_sh = jax.tree.map(lambda x: x.sharding, params)
        _o_sh = jax.tree.map(lambda x: x.sharding, opt_state)

        def place_p(h):
            return feat_mod.place_like(h, _p_sh)

        def place_o(h):
            return feat_mod.place_like(h, _o_sh)
    else:
        def place_p(h):
            return place_replicated(h, mesh)
        place_o = place_p
    start_epoch, best_acc, best_params = 0, 0.0, None
    retry_nonce = 0     # cumulative divergence-rollback count: folds the
                        # sampling/dropout key streams (resilience.py) and
                        # round-trips through checkpoint extra so a resumed
                        # run continues the post-rollback streams bit-for-bit
    resize_nonce = 0    # cumulative elastic-shrink count (--elastic on):
                        # folds the same streams under a disjoint high-bit
                        # domain so a resized world resamples its boundary
                        # sets; 0 (never shrunk) is bit-identical. Grows
                        # never change it — rejoin replays deterministically.
    tune_state = None   # --tune controller history from checkpoint extra:
                        # only the single-host path reads it (auto is
                        # single-process; a multi-rank schedule run
                        # reconstructs the same history from the schedule
                        # text, which every rank already has)
    resume_span = (setup_span("resume") if cfg.resume
                   else obs_mod.NO_SPAN).begin()
    if cfg.resume and coordinator is not None and not joiner:
        # ---- rank-consistent recovery: rank 0 WALKS the chain, everyone
        # else loads exactly rank 0's choice. Two ranks walking
        # independently can pick DIFFERENT files (one rank's newest local
        # copy torn, the other's fine) and silently desync the epoch
        # schedule; and the uncoordinated multi-host path broadcast rank
        # 0's epoch without ever checking the peers could load it. Every
        # rank acks loadability through the coordinator BEFORE any state is
        # adopted — a torn local file aborts the resume loudly on ALL
        # ranks (exit 78), not mid-epoch. ----
        choice = None
        if coord_rank == 0:
            found = ckpt.latest_valid_checkpoint(cfg, log=log)
            if found:
                path0, payload0 = found
                _rx0 = ckpt.resilience_extra(payload0)
                choice = {"have": 1, "file": os.path.basename(path0),
                          "epoch": int(payload0["epoch"]) + 1,
                          "seed": int(payload0.get("seed", seed)),
                          "nonce": _rx0["retry_nonce"],
                          "rnonce": _rx0["resize_nonce"],
                          "best_acc": float(payload0["best_acc"])}
            else:
                choice = {"have": 0}
        choice = coordinator.broadcast("resume-choice", choice)
        if choice["have"]:
            cpath = os.path.join(cfg.ckpt_path, choice["file"])
            # one load per rank, reused for the restore below: rank 0's walk
            # above already read + checksummed its payload (multi-GB at
            # papers100M scale — never read the same file twice); each peer
            # loads its local copy once, and the load IS the ack
            payload1, err = (payload0, None) if coord_rank == 0 else (None, None)
            if coord_rank != 0:
                payload1, err = ckpt.load_or_error(cpath)
                if err is not None and multi_host and not os.path.exists(cpath):
                    # no local copy at all: fine on a real pod — the state
                    # arrives via the rank-0 XLA broadcast below. A PRESENT
                    # but torn copy is never fine: this rank's disk lies.
                    err = None
            all_ok, fails = coordinator.gather_ok("resume", err is None,
                                                  err or "")
            if not all_ok:
                raise coord_mod.CoordAbort(
                    "resume aborted by agreement: rank(s) cannot load the "
                    f"chosen checkpoint {choice['file']!r}: "
                    + "; ".join(f"rank {r}: {d}"
                                for r, d in sorted(fails.items())))
            seed = int(choice["seed"])
            retry_nonce = int(choice["nonce"])
            resize_nonce = int(choice.get("rnonce", 0))
            start_epoch = int(choice["epoch"])
            best_acc = float(choice["best_acc"])
            if multi_host:
                # state still travels the proven XLA broadcast: rank 0
                # restores its validated payload, peers receive the trees
                from jax.experimental import multihost_utils
                host = (ckpt.restore_into(payload1, jax.device_get(params),
                                          jax.device_get(opt_state),
                                          jax.device_get(state))
                        if is_rank0 else
                        (jax.device_get(params), jax.device_get(opt_state),
                         jax.device_get(state)))
                host = multihost_utils.broadcast_one_to_all(host)
            else:
                host = ckpt.restore_into(payload1, jax.device_get(params),
                                         jax.device_get(opt_state),
                                         jax.device_get(state))
            params = place_p(host[0])
            opt_state = place_o(host[1])
            state = place_replicated(host[2], mesh)
            log(f"Resumed (agreed via coordinator) from {choice['file']} at "
                f"epoch {start_epoch}")
            if best_acc > 0:
                # best-params recovery, same contract as the uncoordinated
                # paths: the final ckpt must carry the matching best_acc or
                # best tracking restarts. One load per participating rank
                # (multi-host: rank 0 only — peers receive the XLA
                # broadcast; harness mode: every rank restores its local
                # copy), reused for both the best_acc probe and the
                # restore. The ranks AGREE before adopting anything, so a
                # torn/stale local copy on one harness rank degrades best
                # tracking on ALL ranks instead of crashing that rank or
                # desyncing the final eval.
                payf = (_final_best_payload(cfg, best_acc, log)
                        if coord_rank == 0 or not multi_host else None)
                if multi_host:
                    rec = coordinator.broadcast(
                        "resume-best",
                        {"recovered": int(payf is not None)}
                        if coord_rank == 0 else None)
                    recovered = bool(rec["recovered"])
                else:
                    recovered, _ = coordinator.gather_ok(
                        "resume-best", payf is not None)
                if recovered and multi_host:
                    from jax.experimental import multihost_utils
                    bp = (ckpt.restore_into(payf, jax.device_get(params))[0]
                          if is_rank0 else jax.device_get(params))
                    best_params = multihost_utils.broadcast_one_to_all(bp)
                elif recovered:
                    best_params = ckpt.restore_into(
                        payf, jax.device_get(params))[0]
                else:
                    best_acc = 0.0
    elif cfg.resume and multi_host:
        # rank 0 reads (and integrity-validates) the checkpoint; everything
        # restored must be broadcast so all processes drive the SPMD loop
        # over the same epoch range
        from jax.experimental import multihost_utils
        payload = None
        if is_rank0:
            found = ckpt.latest_valid_checkpoint(cfg, log=log)
            if found:
                payload = found[1]
        # broadcast [next_epoch, saved_seed, retry_nonce, resize_nonce]
        # together: the resumed run must continue the checkpoint's
        # BNS-sampling/dropout streams, and every process must agree on
        # them (shared-PRNG invariant)
        _rx = ckpt.resilience_extra(payload) if payload is not None else {
            "retry_nonce": 0, "resize_nonce": 0}
        have, saved_seed, saved_nonce, saved_rnonce = (
            int(x) for x in multihost_utils.broadcast_one_to_all(
                np.asarray(
                    [0 if payload is None else int(payload["epoch"]) + 1,
                     seed if payload is None else int(payload.get("seed", seed)),
                     _rx["retry_nonce"], _rx["resize_nonce"]],
                    dtype=np.int64)))
        if int(have) > 0:
            seed = saved_seed
            retry_nonce = saved_nonce
            resize_nonce = saved_rnonce
            host = ckpt.restore_into(payload, jax.device_get(params),
                                     jax.device_get(opt_state),
                                     jax.device_get(state)) if is_rank0 else (
                jax.device_get(params), jax.device_get(opt_state),
                jax.device_get(state))
            host = multihost_utils.broadcast_one_to_all(host)
            params = place_p(host[0])
            opt_state = place_o(host[1])
            state = place_replicated(host[2], mesh)
            start_epoch = int(have)
            best_acc = float(multihost_utils.broadcast_one_to_all(np.float64(
                payload["best_acc"] if payload else 0.0)))
            # recover best params (rank 0 reads the matching final ckpt, all
            # ranks receive them — the final mesh test eval is a collective);
            # no match -> restart best tracking, same as single-host
            fp = (_final_best_payload(cfg, best_acc, log)
                  if is_rank0 and best_acc > 0 else None)
            recovered = int(multihost_utils.broadcast_one_to_all(
                np.int64(fp is not None)))
            if best_acc > 0 and recovered:
                bp = (ckpt.restore_into(fp, jax.device_get(params))[0]
                      if is_rank0 else jax.device_get(params))
                best_params = multihost_utils.broadcast_one_to_all(bp)
            elif best_acc > 0:
                best_acc = 0.0
            log(f"Resumed (broadcast from rank 0) at epoch {start_epoch}")
    elif cfg.resume:
        # latest_valid_checkpoint walks past corrupt/torn files: a bad
        # newest checkpoint costs the epochs since the previous periodic
        # save instead of crashing the resume
        found = ckpt.latest_valid_checkpoint(cfg, log=log)
        if found:
            latest, payload = found
            p, o, s = ckpt.restore_into(payload, jax.device_get(params),
                                        jax.device_get(opt_state),
                                        jax.device_get(state))
            params = place_p(p)
            opt_state = place_o(o)
            state = place_replicated(s, mesh)
            start_epoch = int(payload["epoch"]) + 1
            best_acc = float(payload["best_acc"])
            # adopt the checkpoint's seed: main.py re-randomizes cfg.seed per
            # launch, but a resumed run must continue the saved sampling and
            # dropout streams (checkpoint.py's round-trip contract)
            seed = int(payload.get("seed", seed))
            _rx = ckpt.resilience_extra(payload)
            retry_nonce = _rx["retry_nonce"]
            resize_nonce = _rx["resize_nonce"]
            tune_state = (payload.get("extra") or {}).get("tune")
            log(f"Resumed from {latest} at epoch {start_epoch}")
            # recover the best-so-far params (final ckpt) so a resumed run that
            # never beats the old best still saves/evaluates a best model
            # (_final_best_payload owns the matching-best_acc contract)
            fp = (_final_best_payload(cfg, best_acc, log)
                  if best_acc > 0 else None)
            if fp is not None:
                best_params = ckpt.restore_into(fp, jax.device_get(params))[0]
            elif best_acc > 0:
                best_acc = 0.0      # no matching best params: restart tracking
    resume_span.end()

    if cfg.warm_start:
        # continual-cycle fine-tune entry: params + BN state come from the
        # serving checkpoint, the optimizer stays fresh, the epoch counter
        # starts at 0 — a different contract from --resume (which continues
        # one run's own history), so combining them is a named config error
        # rather than a silent winner
        if cfg.resume:
            raise ConfigError(
                "--warm-start and --resume are mutually exclusive: resume "
                "continues a run's own optimizer/epoch history, warm start "
                "re-initializes both from another run's weights")
        p, s = warm_start_state(cfg, params, state, log=log)
        params = place_p(p)
        state = place_replicated(s, mesh)

    # Both keys derive from cfg.seed: every process of a multi-host run MUST
    # agree on the sampling key or the shared-PRNG BNS exchange desyncs
    # (main.py broadcasts the randomized seed from process 0).
    base_sample_key = jax.random.key(seed)
    base_drop_key = jax.random.key(seed + 1)
    if cfg.cycle_nonce:
        # continual-cycle refold (the retry-nonce pattern one level up):
        # each fine-tune cycle draws fresh BNS/dropout streams instead of
        # replaying cycle 0's schedule on a mutated graph. The high-bit
        # offset keeps the cycle fold domain disjoint from the small
        # positive divergence-retry folds applied on top (fold_in data is
        # uint32); nonce 0 is bit-identical.
        cyc = (1 << 31) | (int(cfg.cycle_nonce) & 0x7FFFFFFF)
        base_sample_key = jax.random.fold_in(base_sample_key, cyc)
        base_drop_key = jax.random.fold_in(base_drop_key, cyc)

    def _fold_keys(nonce: int, rnonce: int = 0):
        """Retry-nonce fold of the sampling/dropout streams: after the n-th
        divergence rollback every subsequent epoch draws from fold_in(base,
        n), so the retried epoch resamples its BNS boundary sets (PAPER §3:
        a diverged epoch is cheap to retry under a fresh fold) instead of
        deterministically re-diverging. nonce 0 — every run that never
        rolled back — is the historical keys, bit-identical.

        `rnonce` is the elastic resize nonce, folded on top under the
        (1 << 30) high-bit domain — disjoint from both the small-int retry
        folds and the (1 << 31) continual-cycle folds — so a shrunk world
        draws fresh boundary sets instead of replaying the schedule that
        straddled the loss; rnonce 0 (and every grow, which keeps the
        nonce) stays on the unfolded streams."""
        sk, dk = base_sample_key, base_drop_key
        if rnonce:
            rdom = (1 << 30) | (int(rnonce) & 0x3FFFFFFF)
            sk = jax.random.fold_in(sk, rdom)
            dk = jax.random.fold_in(dk, rdom)
        if nonce:
            sk, dk = (jax.random.fold_in(sk, nonce),
                      jax.random.fold_in(dk, nonce))
        if cfg.strict_exec and jax.process_count() == 1:
            # --strict-exec: commit the keys to the mesh up front. The
            # transfer guard treats the lazy first-use resharding of an
            # uncommitted host-born array as an implicit transfer, so the
            # one-time placement happens here, outside any guarded step.
            sh = replicated_sharding(mesh)
            sk, dk = jax.device_put(sk, sh), jax.device_put(dk, sh)
        return sk, dk

    sample_key, drop_key = _fold_keys(retry_nonce, resize_nonce)

    # ---- resilience subsystem (divergence rollback, preemption-safe
    # shutdown, hung-step watchdog, fault injection) ----
    resil = None
    if cfg.resilience == "on" and (not multi_host or coordinator is not None):
        resil = resilience.ResilienceManager(cfg, log, start_epoch=start_epoch,
                                             retry_nonce=retry_nonce,
                                             resize_nonce=resize_nonce,
                                             coord=coordinator, obs=obs)
        # host snapshot of the fresh/resumed state: the rollback target
        # until the first periodic checkpoint exists (under coordination,
        # every rank keeps one — the '<initial state>' source restores it
        # rank-locally, params being replicated)
        resil.set_initial_snapshot(jax.device_get(params),
                                   jax.device_get(opt_state),
                                   jax.device_get(state))
        resil.start()
    elif cfg.resilience == "on":
        log("[resilience] multi-host run with --coord off: in-process "
            "divergence rollback/watchdog disabled (agreed abort/rollback "
            "needs the rank coordinator — drop --coord off); the "
            "checkpoint integrity chain still protects rank-0 resume")
    if resil is None and (cfg.inject or os.environ.get("BNSGCN_FAULT")):
        log("[resilience] WARNING: --inject is armed but the resilience "
            "loop is disabled here — no fault will fire")

    os.makedirs(cfg.ckpt_path, exist_ok=True)
    os.makedirs(cfg.results_path, exist_ok=True)
    result_file = os.path.join(
        cfg.results_path,
        "%s_n%d_p%.2f.txt" % (cfg.dataset, cfg.n_partitions, cfg.sampling_rate))

    timer = EpochTimer(warmup=5)
    pool = ThreadPoolExecutor(max_workers=1)     # async eval (train.py:370,437-441)
    pending = None
    comm_t = 0.0
    res = RunResult()
    # widths of the per-layer exchanges: hidden-wide for layers >= 1, and a
    # raw-feature-wide layer-0 exchange when use_pp is off; feat-sharded
    # layers ship their H/T slice, so the microbench must too
    exch_widths = [_wire_w(cfg.n_hidden)] * max(spec.n_graph_layers - 1, 0)
    if not spec.use_pp and spec.model != "gat" and spec.n_graph_layers > 0:
        exch_widths.append(_wire_w(max(cfg.n_feat, 1)))
    if grad_only:
        # no per-step activation exchange exists: an exchange microbench
        # would report a collective the training step never runs
        exch_widths = []

    def _comm_bench(w):
        """One exchange-microbench call at width w — the partial-refresh
        geometry when the run is in steady state (K > 1), else the full
        exchange. This is the sampled Comm(s) twin of what the step on the
        wire actually does."""
        if use_refresh:
            return fns.exchange_only_refresh(blk, tables_refresh_d,
                                             jnp.uint32(epoch), sample_key,
                                             width=w)
        return fns.exchange_only(blk, tables, jnp.uint32(epoch), sample_key,
                                 width=w)

    # compile the comm microbenches outside the timed region
    epoch = 0
    with setup_span("comm_bench_compile"):
        for w in set(exch_widths):
            _comm_bench(w).block_until_ready()

    # profiler window (SURVEY §5.1 upgrade: the reference's wall-clock comm
    # spans are meaningless under XLA; named traces are the TPU equivalent),
    # clamped into the epochs this run actually executes
    # +2 past start_epoch: a resumed run compiles on its first executed
    # epoch, and a step that compiles INSIDE the trace window records no
    # device ops on XLA:CPU (observed: 1 launch, 0 collective events) —
    # the window must hold only post-compile steps
    prof_start = max(timer.warmup + 1, start_epoch + 2)
    prof_stop = min(prof_start + 3, cfg.n_epochs - 1)
    tracing = False
    # The Comm(s) microbench overstates the real in-step collective cost by
    # 1.5-26x (2026-07-30 cross-check on an 8-device host-platform mesh,
    # hw_logs/trace_comm_table.log: host dispatch dominates for small
    # quantized payloads — the int8 wire's microbench reads 26x its traced
    # in-step exchange). The reference's
    # column is a direct in-step measurement (helper/timer/comm_timer.py:
    # 21-25), so ours must be too: trace a short window (the user's
    # --profile-dir if given, else an auto temp dir on rank 0) and derive
    # per-epoch in-step exchange/reduce from the device collective spans
    # (utils/traceparse.step_comm_per_epoch). Until the window closes the
    # microbench prints, tagged [sampled]; after it, [traced] numbers.
    # Single-process only: the trace stop/serialize/parse stalls THIS rank
    # between epochs while its peers run ahead into the next collective —
    # XLA:CPU's rendezvous watchdog (default ~40 s) then terminates them
    # (observed as test_multihost subprocess timeouts). Multi-host runs
    # keep the [sampled] microbench column; --profile-dir is still honored
    # there for explicit traced sessions.
    auto_trace_dir = None
    trace_dir = cfg.profile_dir
    if (not trace_dir and cfg.comm_trace and not multi_host
            and prof_stop > prof_start):
        auto_trace_dir = tempfile.mkdtemp(prefix="bnsgcn_commtrace_")
        trace_dir = auto_trace_dir
    comm_traced = reduce_traced = None

    def _drain_eval(fut):
        """(params, acc) from a finished host-eval future. A raise inside
        the eval thread re-raises here and fails the run: a run whose eval
        phase failed must not go on to print a result."""
        e, out = fut.result()
        if obs is not None:
            obs.emit("eval", epoch=e, val_acc=round(float(out[1]), 6))
        return out

    loss = jnp.zeros(())
    loss_f = 0.0
    trace_done = False          # one trace window per run, even across rollbacks
    # on-demand profiling (SIGUSR1, obs on): a bounded profiler window into
    # the post-mortem dir, captured WITHOUT stopping training
    usr1_tracing, usr1_stop, usr1_dir = False, -1, None
    loss_base = start_epoch     # epoch of res.losses[0]: a rollback behind the
                                # resume point (newer ckpts all corrupt) rebases
                                # the list instead of corrupting its indexing
    epoch = start_epoch
    # --strict-exec: runtime proof the steady-state step is clean — a
    # transfer guard around every step (implicit host transfer = error)
    # plus a compile listener (recompile after a variant's first guarded
    # step = error). The loss fetch goes through strict.fetch (audited
    # explicit device_get); the per-epoch uint32 upload is hoisted before
    # the guard below.
    strict = strict_mod.StrictExec(obs=obs, log=log) if cfg.strict_exec \
        else None
    # --halo-refresh cache state: None means the next step runs the
    # full-refresh geometry and rebuilds the cache. Starts invalid (fresh run
    # OR resume — checkpoints never hold the cache) and is re-invalidated at
    # every rollback, which is what keeps --resume/rollback deterministic.
    halo_cache = None
    cache_reason = "resume" if start_epoch > 0 else "start"
    # --elastic on: the part -> hosting-slot map agreed at the last RESIZE
    # verdict (mesh.plan_slots over the survivors). None until a shrink;
    # threaded into build_step_fns so rebuilt HaloSpecs carry the layout
    # (host-side metadata only — the traced program is slot-invariant).
    slot_map = None

    def _ckpt_extra():
        """Checkpoint `extra` payload: retry nonce + (under --tune) the
        controller's sticky decision history, so a resumed run replays the
        same schedule deterministically. The elastic resize nonce rides
        along only when it could matter (--elastic on, or a nonzero count
        inherited through resume) so pre-elastic checkpoints stay
        byte-identical."""
        ex = {"retry_nonce": retry_nonce}
        if cfg.elastic == "on" or resize_nonce:
            ex["resize_nonce"] = resize_nonce
        if tuner is not None:
            ex["tune"] = tuner.state_dict()
        return ex

    # ---- --tune actuation: rebuild the comm stack at an epoch boundary.
    # build_step_fns hits the shared layout cache (the SpMM layout keys do
    # not depend on any tuned lever), the halo cache is invalidated so the
    # next epoch is a logged full refresh, strict-exec's per-variant compile
    # allowance is re-armed (a retune is the one sanctioned recompile), and
    # the comm microbench is recompiled HERE, outside the timed region. ----
    retune_cool = -1    # epochs <= this carry retune compiles in dt: excluded
                        # from the timer/histogram like warmup epochs

    def _apply_tune(changes, reason, trigger, at_epoch):
        nonlocal cfg, fns, hspec, tables, tables_full_d, tables_refresh_d
        nonlocal halo_label, halo_wire_mb, steady_wire_mb
        nonlocal use_refresh, grad_only, exch_widths
        nonlocal halo_cache, cache_reason, retune_cool
        from bnsgcn_tpu.parallel.halo import make_refresh_spec, wire_bytes
        cfg = cfg.replace(**changes)
        fns, hspec, tb, tbf = build_step_fns(cfg, spec, art, mesh,
                                             layout_cache=layout_cache,
                                             slot_map=slot_map)
        tables = place_replicated(tb, mesh)
        tables_full_d = place_replicated(tbf, mesh)
        tables_refresh_d = (place_replicated(fns.tables_refresh, mesh)
                            if fns.tables_refresh is not None else None)
        use_refresh = fns.train_step_full is not None
        grad_only = fns.halo_mode == "grad-only"
        halo_label = hspec.strategy
        if fns.overlap == "split":
            halo_label += "+ovl"
        if fns.n_replicas > 1:
            halo_label += f"+rep{fns.n_replicas}"
        if fns.n_feat > 1:
            halo_label += f"+feat{fns.n_feat}"
        if grad_only:
            halo_label += "+go"
        elif use_refresh:
            halo_label += f"+hr{fns.halo_refresh}"
        if cfg.reorder != "off":
            halo_label += "+ro"
        halo_wire_mb = wire_bytes(hspec, hid_w, nb) / 1e6
        steady_wire_mb = halo_wire_mb
        if grad_only:
            steady_wire_mb = 0.0
        elif use_refresh:
            hspec_r, _ = make_refresh_spec(
                art.n_b, art.pad_inner, art.pad_boundary, cfg.sampling_rate,
                fns.halo_refresh, strategy=hspec.strategy, wire=hspec.wire)
            steady_wire_mb = wire_bytes(hspec_r, hid_w, nb) / 1e6
        # the old cache was built by the OLD exchange geometry: the next
        # epoch must be a full refresh under the new one. resume/rollback/
        # resize keep their own lifecycle reason; fresh decisions log as
        # 'retune'
        halo_cache = None
        cache_reason = (reason if reason in ("resume", "rollback", "resize")
                        else "retune")
        if strict is not None and strict.steps:
            # new compiled programs: each variant's next step legitimately
            # compiles once more (before the first step nothing is armed)
            strict.rearm(reason)
        exch_widths = ([_wire_w(cfg.n_hidden)]
                       * max(spec.n_graph_layers - 1, 0))
        if not spec.use_pp and spec.model != "gat" and spec.n_graph_layers > 0:
            exch_widths.append(_wire_w(max(cfg.n_feat, 1)))
        if grad_only:
            exch_widths = []
        for w in set(exch_widths):
            _comm_bench(w).block_until_ready()
        retune_cool = at_epoch + 1
        if resil is not None:
            resil.watchdog.touch()      # rebuild+compile is boundary work
        log(f"[tune] epoch {at_epoch}: {reason} -> " + ", ".join(
            f"{k}={v}" for k, v in sorted(changes.items()))
            + f" (halo {halo_label}/{hspec.wire}, steady "
              f"{steady_wire_mb:.2f} MB/exchange)")
        if obs is not None:
            # peak rides along with steady: the forced full-refresh epoch
            # right after a retune pays the NEW geometry's peak figure,
            # and gate 4's obs contract checks epochs against DECLARED
            # numbers only
            obs.emit("tune_decision", epoch=int(at_epoch), reason=reason,
                     changes=dict(changes), trigger=dict(trigger or {}),
                     halo=halo_label, wire=hspec.wire,
                     wire_mb_steady=round(steady_wire_mb, 4),
                     wire_mb_peak=round(halo_wire_mb, 4))

    if joiner:
        # ---- rejoin handshake (--elastic on): this process replaces a
        # rank the survivors already voted out of the world. It cannot
        # replay the retired pre-loop collectives; instead it posts a
        # rejoin request against the lost-rank beacon, rank 0 folds the
        # grow verdict into its next agree boundary, and the grant carries
        # everything needed to fall into lockstep — the agreed restore
        # point, both nonces, the part -> rank map, and the survivors'
        # seq/agree-call position. The first collective this rank joins is
        # the grow restore ack, shoulder to shoulder with the survivors'
        # own resize-arm restore. ----
        token = f"{os.getpid():x}-{os.urandom(4).hex()}"
        log(f"[elastic] rank {coord_rank}: requesting rejoin "
            f"(token {token})")
        grant = coordinator.request_rejoin(token)
        coordinator.adopt_grant(grant)
        restart = int(grant["restart"])
        retry_nonce = int(grant["retry_nonce"])
        resize_nonce = int(grant["nonce"])
        slot_map = (tuple(int(s) for s in grant["slots"])
                    if grant.get("slots") else None)
        resil.nonce = retry_nonce
        resil.resize_nonce = resize_nonce
        templates = (jax.device_get(params), jax.device_get(opt_state),
                     jax.device_get(state))
        p_h, o_h, s_h = resil.coord_restore(grant, *templates,
                                            ack_name="resize")
        params = place_p(p_h)
        opt_state = place_o(o_h)
        state = place_replicated(s_h, mesh)
        sample_key, drop_key = _fold_keys(retry_nonce, resize_nonce)
        start_epoch = epoch = loss_base = restart
        _apply_tune({}, "resize",
                    {"world": grant.get("world"), "trigger": "rejoin"},
                    restart)
        resil._emit("resize", epoch=int(grant["epoch"]),
                    old_world=int(grant["old_world"]),
                    world=int(grant["world"]),
                    members=[int(r) for r in grant["members"]],
                    lost=[], slots=[int(s) for s in grant.get("slots", [])],
                    trigger="rejoin", nonce=int(resize_nonce),
                    restart=int(restart), source=str(grant["source"]))
        log(f"[elastic] rank {coord_rank}: rejoined world "
            f"{grant.get('world')} (members {grant.get('members')}); "
            f"parts now "
            + slot_desc(slot_map, grant.get("members") or [])
            + f"; replaying from epoch {restart} in lockstep")
    if tuner is not None and start_epoch > 0 and not joiner:
        # resumed run: reconstruct/adopt the controller history and actuate
        # the levers that were live at the resume point BEFORE the first
        # step — the healed run replays the same schedule deterministically
        _tdiff = tuner.restore(start_epoch, tune_state)
        if _tdiff:
            _apply_tune(_tdiff, "resume", {}, start_epoch)
    # The loop is a `while` so the divergence guard can move `epoch`
    # BACKWARD (rollback to the last good checkpoint, resilience.py); with
    # --resilience off no hook below fires and the schedule is exactly the
    # historical `for epoch in range(start_epoch, n_epochs)`.
    # $BNSGCN_EPOCH_THROTTLE_S: minimum wall time per epoch (sleep before
    # the timed region). A test/demo knob — the elastic e2e harness uses it
    # to keep a fast CPU run alive long enough for a relaunched rank to pay
    # its startup cost and rejoin; 0 (default) sleeps nothing.
    epoch_throttle = float(os.environ.get("BNSGCN_EPOCH_THROTTLE_S", 0) or 0)
    setup_root.end()
    # host account of the loop (obs on): the wall between one epoch's loss
    # ready and the next one's dispatch is `boundary_s`, its parts by phase
    # are `boundary`; the process counters are deltas over the epoch
    t_ready = None
    trace_start_wall = None
    if obs is not None:
        obs.take_phases()
        obs.rusage_delta()
    try:
        while epoch < cfg.n_epochs:
            if obs is not None:
                obs.epoch_begin(epoch)
            pre_span = span("pre").begin()
            if epoch_throttle > 0:
                time.sleep(epoch_throttle)
            if resil is not None:
                resil.watchdog.beat(epoch)
                # deterministic fault injection at the step boundary
                # (--inject / $BNSGCN_FAULT); 'nan' poisons the params so
                # the divergence shows up through the REAL loss path
                if resil.fire_injections(epoch)["nan"]:
                    params = jax.tree.map(
                        lambda x: x * jnp.nan
                        if jnp.issubdtype(x.dtype, jnp.floating) else x,
                        params)
                # ---- on-demand profiling: SIGUSR1 was received; capture
                # stacks + registry snapshot NOW and trace a bounded window
                # of the next epochs into the post-mortem dir — training
                # never stops ----
                if obs is not None and resil.take_profile_request():
                    pm = resil.postmortem_dir or obs_mod.postmortem_dir(cfg)
                    snap = obs_mod.write_postmortem(
                        pm, f"sigusr1_E{epoch}",
                        text=f"SIGUSR1 snapshot at epoch {epoch}",
                        registry=obs.registry) or None
                    if snap:
                        log(f"[obs] SIGUSR1: stacks + metrics snapshot -> "
                            f"{snap}")
                    else:
                        log("[obs] SIGUSR1: post-mortem snapshot write "
                            "FAILED (disk?); still arming the trace window")
                    if not tracing and not usr1_tracing:
                        usr1_dir = os.path.join(pm, f"trace_E{epoch}")
                        try:
                            jax.profiler.start_trace(usr1_dir)
                            usr1_tracing, usr1_stop = True, epoch + 2
                            log(f"[obs] SIGUSR1: profiling epochs {epoch}.."
                                f"{usr1_stop} -> {usr1_dir}")
                        except Exception as ex:
                            log(f"[obs] SIGUSR1 trace failed to start: {ex}")
                            usr1_dir = None
                    obs.emit("profile_request", epoch=epoch, snapshot=snap,
                             trace_dir=usr1_dir if usr1_tracing else None)
                    resil.watchdog.touch()      # capture is boundary work
            # >= (not ==): a SIGUSR1 window covering prof_start must only
            # DELAY the one-shot comm-trace start, never cancel it
            if (trace_dir and prof_start <= epoch < prof_stop
                    and not tracing and not trace_done and not usr1_tracing):
                trace_start_wall = time.time()
                jax.profiler.start_trace(trace_dir)
                tracing = True
            pre_span.end()
            # step_s = dispatch_s + wait_s: `dispatch` holds the epoch_dev
            # upload and the step call returning, `wait` the blocking wait
            # for the loss
            t0 = time.perf_counter()
            if obs is not None:
                boundary = obs.take_phases()
                boundary_s = None if t_ready is None else t0 - t_ready
            dispatch_span = span("dispatch").begin()
            # --halo-refresh K: an invalidated cache (run start, resume,
            # rollback) forces one full-refresh epoch at peak wire cost;
            # every other epoch runs the ~1/K partial exchange against
            # the cache. The cache is never checkpointed — it is
            # host-held device state only, rebuilt by the next
            # full-refresh epoch after any restore. full/cached are two
            # distinct compiled programs, so each is its own strict-exec
            # variant.
            refresh_full = use_refresh and halo_cache is None
            variant = (("full" if refresh_full else "cached")
                       if use_refresh else "step")
            # the one deliberate per-epoch host->device upload, hoisted
            # BEFORE the strict guard: everything else the step consumes
            # is already device-resident. Under strict the scalar is also
            # committed to the mesh's replicated sharding here — otherwise
            # its first use inside the guarded step reshards it and the
            # guard flags that device-to-device move.
            epoch_dev = jnp.uint32(epoch)
            if strict is not None and jax.process_count() == 1:
                epoch_dev = jax.device_put(epoch_dev,
                                           replicated_sharding(mesh))
            with (strict.step(variant) if strict is not None
                  else contextlib.nullcontext()):
                if use_refresh:
                    if refresh_full:
                        params, state, opt_state, loss, halo_cache = (
                            fns.train_step_full(
                                params, state, opt_state, epoch_dev, blk,
                                tables, sample_key, drop_key))
                    else:
                        params, state, opt_state, loss, halo_cache = (
                            fns.train_step_cached(
                                params, state, opt_state, epoch_dev, blk,
                                tables_refresh_d, halo_cache, sample_key,
                                drop_key))
                else:
                    params, state, opt_state, loss = fns.train_step(
                        params, state, opt_state, epoch_dev, blk, tables,
                        sample_key, drop_key)
                dispatch_span.end()
                t_dispatched = time.perf_counter()
                with span("wait"):
                    loss.block_until_ready()
            t_ready = time.perf_counter()
            dt = t_ready - t0
            if obs is not None:
                obs.phase_s.clear()     # dispatch, wait: reported as fields
                obs.note_call(STEP_PROGRAMS[variant], dt)
            # identical float either way; under strict the fetch is the
            # audited explicit path (counted in the end-of-run summary)
            with span("loss_fetch"):
                loss_f = (float(strict.fetch(loss)) if strict is not None
                          else float(loss))
            usr1_in_step = usr1_tracing     # profiler overhead rides dt
            if use_refresh and refresh_full:
                # lifecycle marker: this epoch rebuilt the halo cache at peak
                # wire cost (obs_report surfaces these against the
                # duty-cycled steady-state epochs)
                if obs is not None:
                    obs.emit("halo_refresh", epoch=epoch,
                             k=int(fns.halo_refresh), reason=cache_reason)
                log(f"  halo cache: full refresh at epoch {epoch} "
                    f"({cache_reason}); next {fns.halo_refresh - 1}+ epochs "
                    f"reuse cached blocks")

            # ---- divergence guard: free loss check every step (the loop
            # fetched it for res.losses anyway) + param-norm probe every
            # log_every; rollback BEFORE the checkpoint write below so a
            # non-finite state can never become "last good" ----
            bad = resil is not None and not math.isfinite(loss_f)
            pnorm = None        # the probe's value, reused by the obs epoch
                                # record — never an extra device op
            if (resil is not None and not bad
                    and (epoch + 1) % cfg.log_every == 0):
                with span("guard"), span("norm_probe") as probe_span:
                    pnorm = float(param_global_norm(params))
                if obs is not None:
                    obs.note_call("param_global_norm", probe_span.dur_s)
                bad = not math.isfinite(pnorm)
            if resil is not None and resil.coord is not None:
                # ---- multi-host agreed verdict: every rank contributes
                # its local state out-of-band, rank 0 reduces worst-wins,
                # and ALL ranks act on the one decision — a SIGTERM or NaN
                # on a single rank can no longer strand its peers inside
                # the next collective ----
                local = ("diverged" if bad
                         else "preempted" if resil.preempt_requested
                         else "ok")
                # piggyback this rank's epoch telemetry on the verdict the
                # exchange already carries: rank 0 merges every rank's
                # summary into ONE epoch_ranks record, no new collective
                summary = ({"loss": round(loss_f, 6),
                            "step_ms": round(dt * 1e3, 3)}
                           if obs is not None else None)
                with span("agree"):
                    decision = resil.agree_step(
                        epoch, local, loss_f, summary=summary,
                        final=epoch + 1 >= cfg.n_epochs)
                act = decision["decision"]
                if act == "abort":
                    resil.raise_abort(decision)
                if act == "preempt":
                    # agreed all-rank resumable shutdown: rank 0 writes the
                    # checkpoint (the agree() confirm phase already
                    # guaranteed every rank has read the verdict)
                    ppath = ckpt.periodic_path(cfg, epoch)
                    if is_rank0:
                        ckpt.save_checkpoint(ppath, params=params,
                                             opt_state=opt_state,
                                             bn_state=state, epoch=epoch,
                                             best_acc=best_acc, seed=seed,
                                             extra=_ckpt_extra())
                        ckpt.prune_checkpoints(cfg, cfg.keep_ckpt)
                    log(f"[resilience] agreed preemption (requested by "
                        f"rank(s) {decision.get('ranks')}) at the epoch-"
                        f"{epoch} step boundary: resumable checkpoint at "
                        f"{ppath}")
                    if obs is not None:
                        obs.emit("preempt", epoch=epoch, ckpt=ppath,
                                 agreed=True, ranks=decision.get("ranks"))
                    raise resilience.PreemptedError(epoch, ppath)
                if act == "rollback":
                    templates = (jax.device_get(params),
                                 jax.device_get(opt_state),
                                 jax.device_get(state))
                    if multi_host:
                        # real pod: rank 0 restores its validated payload
                        # and the trees travel the proven XLA broadcast.
                        # Every rank joins the restore ack FIRST — a rank-0
                        # restore failure must abort all ranks (78) before
                        # anyone blocks inside the XLA collective
                        from jax.experimental import multihost_utils
                        host = resil.coord_restore(decision, *templates,
                                                   restore_local=is_rank0)
                        p_h, o_h, s_h = multihost_utils.broadcast_one_to_all(
                            host)
                    else:
                        # harness mode: each rank restores the agreed source
                        # from its own checkpoint dir and acks — a torn
                        # local copy aborts ALL ranks loudly (exit 78)
                        p_h, o_h, s_h = resil.coord_restore(decision,
                                                            *templates)
                    restart = int(decision["restart"])
                    retry_nonce = int(decision["nonce"])
                    params = place_p(p_h)
                    opt_state = place_o(o_h)
                    state = place_replicated(s_h, mesh)
                    sample_key, drop_key = _fold_keys(retry_nonce, resize_nonce)
                    if restart < loss_base:
                        res.losses.clear()
                        loss_base = restart
                    else:
                        del res.losses[restart - loss_base:]
                    # the halo cache was built by epochs past the restore
                    # point — rolled-back training must not see them (the
                    # replayed epoch re-runs full-refresh, bitwise like a
                    # fresh run from that checkpoint)
                    halo_cache, cache_reason = None, "rollback"
                    if tuner is not None:
                        # revert to the levers live when `restart` first ran;
                        # the kept history REPLAYS from there (deterministic)
                        _td = tuner.rewind(restart)
                        if _td:
                            _apply_tune(_td, "rollback", {}, restart)
                    resil.watchdog.touch()      # restore+ack was boundary
                    epoch = restart             # work, not step time
                    continue
                if act == "resize":
                    # ---- elastic RESIZE verdict (--elastic on): shrink
                    # after a heartbeat-detected rank loss, or grow when a
                    # lost rank rejoins. Every surviving rank re-maps the P
                    # parts onto the new membership (decision['slots'],
                    # mesh.plan_slots — no METIS rerun), restores the
                    # agreed checkpoint, rebuilds the step fns through the
                    # shared layout cache like a retune, refolds the
                    # sampling/dropout streams under the resize nonce, and
                    # keeps training. ----
                    coordinator.apply_resize(decision)
                    templates = (jax.device_get(params),
                                 jax.device_get(opt_state),
                                 jax.device_get(state))
                    p_h, o_h, s_h = resil.coord_restore(decision, *templates,
                                                        ack_name="resize")
                    restart = int(decision["restart"])
                    retry_nonce = int(decision["retry_nonce"])
                    resize_nonce = int(decision["nonce"])
                    slot_map = (tuple(int(s) for s in decision["slots"])
                                if decision.get("slots") else None)
                    params = place_p(p_h)
                    opt_state = place_o(o_h)
                    state = place_replicated(s_h, mesh)
                    sample_key, drop_key = _fold_keys(retry_nonce,
                                                      resize_nonce)
                    if restart < loss_base:
                        res.losses.clear()
                        loss_base = restart
                    else:
                        del res.losses[restart - loss_base:]
                    # rebuild unconditionally: the halo spec must adopt the
                    # new slot map even when no tune lever moved (rewind
                    # returns {} then); _apply_tune invalidates the halo
                    # cache, re-arms strict-exec, and touches the watchdog
                    _td = tuner.rewind(restart) if tuner is not None else {}
                    _apply_tune(_td or {}, "resize",
                                {"world": decision.get("world"),
                                 "trigger": decision.get("trigger")},
                                restart)
                    log(f"[elastic] epoch {epoch}: "
                        f"{decision.get('trigger')} resize to world "
                        f"{decision.get('world')} "
                        f"(members {decision.get('members')}); parts now "
                        + slot_desc(slot_map, decision.get("members") or [])
                        + f"; resuming from epoch {restart}")
                    epoch = restart
                    continue
            elif bad:
                p_h, o_h, s_h, restart, retry_nonce = resil.rollback(
                    epoch, loss_f, jax.device_get(params),
                    jax.device_get(opt_state), jax.device_get(state))
                params = place_p(p_h)
                opt_state = place_o(o_h)
                state = place_replicated(s_h, mesh)
                sample_key, drop_key = _fold_keys(retry_nonce, resize_nonce)
                # retried epochs get re-recorded on the healthy pass
                if restart < loss_base:
                    res.losses.clear()
                    loss_base = restart
                else:
                    del res.losses[restart - loss_base:]
                # stale halo cache from the diverged timeline: invalidate so
                # the replayed epoch rebuilds it (full-refresh, deterministic)
                halo_cache, cache_reason = None, "rollback"
                if tuner is not None:
                    # revert to the levers live when `restart` first ran; the
                    # kept history REPLAYS from there (deterministic heal)
                    _td = tuner.rewind(restart)
                    if _td:
                        _apply_tune(_td, "rollback", {}, restart)
                resil.watchdog.touch()      # restore+backoff was boundary
                epoch = restart             # work, not step time
                continue

            if tracing and epoch >= prof_stop:
                trace_span = span("trace_io").begin()
                jax.profiler.stop_trace()
                tracing = False
                trace_done = True
                if cfg.profile_dir:
                    log(f"profiler trace written to {cfg.profile_dir}")
                # load the trace ONCE; both the Comm/Reduce attribution and
                # the overlap report parse the same event list. A window
                # that cannot be read or attributed raises (TraceError names
                # why): the run asked for traced columns, and carrying on
                # under the [sampled] tag would hide that it has none.
                # --no-comm-trace is the way to run without the window.
                trace_events, _ = traceparse.load_trace_events(trace_dir)
                # a 1-part program, or grad-only, exchanges nothing: no
                # exchange span is then the truth (0 s), not a lost trace
                exchanges = cfg.n_partitions > 1 and not grad_only
                comm_traced, reduce_traced, _ = (
                    traceparse.step_comm_from_events(trace_events, exchanges))
                if obs is not None:
                    # the comm-vs-compute split obs_report renders:
                    # trace-derived in-step collective seconds per epoch
                    # (`exchanges` lets an offline re-parse of the same
                    # trace apply the same rule)
                    obs.emit("trace", epoch=epoch,
                             comm_s=round(comm_traced, 6),
                             reduce_s=round(reduce_traced, 6),
                             exchanges=exchanges,
                             trace_dir=cfg.profile_dir or None,
                             start_wall=round(trace_start_wall, 6))
                # drop the microbench samples recorded so far so the
                # printed means are purely the traced in-step numbers;
                # seed one sample immediately — the window-closing epoch
                # itself is excluded from record(), and a log line firing
                # on it would otherwise print an empty (0.0) mean
                timer.comm_dur.clear()
                timer.reduce_dur.clear()
                timer.comm_dur.append(comm_traced)
                timer.reduce_dur.append(reduce_traced)
                if fns.overlap == "split":
                    # --overlap split observability: per-step phase buckets +
                    # whether the collective ran under interior compute
                    rep = traceparse.overlap_from_events(trace_events)
                    if rep is not None:
                        for k in ("exchange_ms", "interior_ms", "frontier_ms",
                                  "hidden_ms"):
                            timer.record_bucket(k, rep[k])
                        if obs is not None:
                            obs.emit("overlap", epoch=epoch,
                                     **{k: round(float(rep[k]), 4)
                                        for k in ("exchange_ms",
                                                  "interior_ms",
                                                  "frontier_ms", "hidden_ms")},
                                     overlapped=bool(rep["overlapped"]))
                        log("overlap[traced]: exchange {exchange_ms:.3f} ms | "
                            "interior {interior_ms:.3f} ms | frontier "
                            "{frontier_ms:.3f} ms | hidden {hidden_ms:.3f} ms "
                            "per step — collective overlapped interior "
                            "compute: {verdict}".format(
                                verdict="YES" if rep["overlapped"] else "NO",
                                **{k: rep[k] for k in rep}))
                    else:
                        log("overlap[traced]: no interior/frontier scope "
                            "spans in the trace window (tools/trace_comm.py "
                            "--overlap-check <dir> on a --profile-dir trace "
                            "gives the full report)")
                if auto_trace_dir:
                    shutil.rmtree(auto_trace_dir, ignore_errors=True)
                trace_span.end()

            # ---- SIGUSR1 bounded window closes here; training continues ----
            if usr1_tracing and epoch >= usr1_stop:
                try:
                    jax.profiler.stop_trace()
                except Exception:
                    pass
                usr1_tracing = False
                log(f"[obs] SIGUSR1 profiler window written to {usr1_dir}")
                if obs is not None:
                    obs.emit("profile", epoch=epoch, trace_dir=usr1_dir)
                if trace_dir and not trace_done and epoch >= prof_start:
                    # the SIGUSR1 window swallowed (part of) the one-shot
                    # comm-trace window: re-arm it right after — delayed,
                    # never cancelled
                    prof_start = epoch + 1
                    prof_stop = min(prof_start + 3, cfg.n_epochs - 1)

            if comm_traced is not None:
                comm_t = comm_traced
            elif exch_widths and (epoch == timer.warmup
                                  or (epoch + 1) % cfg.log_every == 0):
                # comm microbench: exchange-only programs at each real layer
                # width, x2 for the backward (transposed) exchange. Under
                # --halo-refresh _comm_bench runs the partial-refresh
                # geometry — the steady-state cost, matching what all but
                # the 1-in-K full-refresh epochs put on the wire
                comm_t = 0.0
                with span("comm_bench"):
                    for w in exch_widths:
                        t1 = time.perf_counter()
                        _comm_bench(w).block_until_ready()
                        comm_t += (time.perf_counter() - t1) * 2
            # epochs inside the trace window carry profiler-collection
            # overhead in dt — exclude them from the reported means like
            # warmup epochs (same rule as bench.py, whose traced runs are
            # tagged profiled-diagnostic)
            # retune epochs compile the rebuilt step programs inside dt —
            # excluded from the reported means exactly like warmup epochs
            clean_step = (not (trace_dir and prof_start <= epoch <= prof_stop)
                          and not usr1_in_step and epoch > retune_cool)
            if clean_step:
                timer.record(epoch, dt, comm_t,
                             reduce_traced if reduce_traced is not None else 0.0)
            res.losses.append(loss_f)
            # wire_mb is THIS epoch's actual exchange cost: duty-cycled under
            # --halo-refresh (peak on full-refresh epochs, the ~1/K steady
            # cost otherwise), 0 under grad-only — the per-epoch evidence for
            # the K-vs-bytes regression, and the --tune controller's wire
            # trigger
            epoch_wire_mb = (halo_wire_mb if (not use_refresh and
                                              not grad_only)
                             else halo_wire_mb if refresh_full
                             else steady_wire_mb)

            if obs is not None:
                # the per-epoch record everything downstream joins on; the
                # registry histogram gives p50/p99 step time without storing
                # samples (the snapshot rides post-mortem dumps). Same
                # exclusions as timer.record — compile/warmup and profiled
                # epochs must not report as p99 step time
                with span("obs_emit"):
                    if clean_step and epoch >= timer.warmup:
                        obs.registry.histogram("train/step_s").observe(dt)
                    rec = {"epoch": epoch, "loss": round(loss_f, 6),
                           "step_s": round(dt, 6),
                           "wire_mb": round(epoch_wire_mb, 4)}
                    if pnorm is not None:
                        rec["param_norm"] = round(pnorm, 6)
                    if comm_t:
                        rec["comm_s"] = round(comm_t, 6)
                        rec["comm_tag"] = ("traced" if comm_traced is not None
                                           else "sampled")
                    # where the host's time went: the step's two halves, the
                    # boundary before it (the previous epoch's phases after
                    # its loss was ready, and this epoch's `pre`), and the
                    # process counters over the epoch
                    rec["dispatch_s"] = round(t_dispatched - t0, 6)
                    rec["wait_s"] = round(t_ready - t_dispatched, 6)
                    if boundary_s is not None:
                        rec["boundary_s"] = round(boundary_s, 6)
                        rec["boundary"] = boundary
                    rec.update(obs.rusage_delta())
                    # only an epoch in which jax compiled (or loaded) a
                    # program carries its account, naming the programs
                    compiled = obs.take_compiles()
                    if compiled:
                        rec["compile"] = compiled
                    obs.emit("epoch", **rec)

            # ---- --tune decision point: the epoch's measured metrics feed
            # the controller AFTER the epoch record lands on the bus; a
            # decision retunes the comm stack now and takes effect from the
            # next epoch (the rebuild/compile happens here, at the boundary,
            # never inside a timed step) ----
            if tuner is not None:
                with span("tune"):
                    _dec = tuner.on_epoch_end(epoch, {
                        "loss": loss_f, "step_s": dt,
                        "comm_s": comm_t if comm_t else None,
                        "wire_mb": epoch_wire_mb})
                    if _dec is not None and _dec["changes"]:
                        _apply_tune(_dec["changes"], _dec["reason"],
                                    _dec.get("trigger") or {}, epoch + 1)

            if (epoch + 1) % cfg.log_every == 0:
                log_span = span("log").begin()
                mt, mc, mr = timer.means()
                # [traced]: per-epoch in-step collective time attributed from
                # the profiler window (the reference's comm_timer equivalent).
                # [sampled]: the exchange-only microbench at the training
                # compute dtype, which overstates quantized wires (dispatch-
                # dominated; measured up to 26x for int8) — printed only
                # until the trace window closes or under --no-comm-trace.
                tag = "[traced]" if comm_traced is not None else "[sampled]"
                log("Process 000 | Epoch {:05d} | Time(s) {:.4f} | Comm(s) "
                    "{:.4f} {} | Reduce(s) {:.4f} | Loss {:.4f}".format(
                        epoch, mt, mc, tag, mr, loss_f))
                log_span.end()

            wrote_ckpt = False
            if (epoch + 1) % cfg.log_every == 0 and is_rank0 and not bad:
                # periodic checkpoint regardless of eval, so --no-eval runs
                # resume too; rank 0 only (reference train.py:427-428).
                # `not bad` is vacuous at the default verdict cadence (a
                # diverged epoch rolled back above before reaching here)
                # but load-bearing under $BNSGCN_COORD_AGREE_EVERY > 1:
                # a latched-not-yet-agreed NaN state must never become
                # the newest "last good" checkpoint
                with span("checkpoint"):
                    ckpt.save_checkpoint(ckpt.periodic_path(cfg, epoch),
                                         params=params, opt_state=opt_state,
                                         bn_state=state, epoch=epoch,
                                         best_acc=best_acc, seed=seed,
                                         extra=_ckpt_extra())
                    ckpt.prune_checkpoints(cfg, cfg.keep_ckpt)
                wrote_ckpt = True
            eval_span = (span("eval") if cfg.eval
                         and (epoch + 1) % cfg.log_every == 0
                         else obs_mod.NO_SPAN).begin()
            if mesh_eval and (epoch + 1) % cfg.log_every == 0:
                fns_e, blk_e, tf_e, art_e = eval_val
                modes = ("val",) if cfg.inductive else ("val", "test")
                accs = evaluate_mesh("Epoch %05d" % epoch, fns_e.eval_forward,
                                     params, state, blk_e, tf_e, art_e, modes,
                                     result_file)
                if obs is not None:
                    obs.emit("eval", epoch=epoch,
                             **{f"{m}_acc": round(float(accs[m]), 6)
                                for m in modes})
                if accs["val"] > best_acc:
                    best_acc, best_params = accs["val"], jax.device_get(params)
            elif cfg.eval and is_rank0 and (epoch + 1) % cfg.log_every == 0:
                if pending is not None:
                    done = _drain_eval(pending)
                    if done[1] > best_acc:
                        best_acc, best_params = done[1], done[0]
                p_host = jax.device_get(params)
                s_host = jax.device_get(state)
                # bind the epoch label like the params: the thread may run
                # after the loop has advanced, and a late-bound `epoch`
                # mislabels the eval line (observed as an "Epoch 00020" eval
                # in a log_every=10 run)
                if cfg.inductive:
                    pending = pool.submit(
                        lambda p=p_host, s=s_host, e=epoch: (e, (
                            p, evaluate_induc(
                                "Epoch %05d" % e, p, s, spec, val_g, "val",
                                result_file, device=host_dev))))
                else:
                    pending = pool.submit(
                        lambda p=p_host, s=s_host, e=epoch: (e, (
                            p, evaluate_trans(
                                "Epoch %05d" % e, p, s, spec, val_g,
                                result_file, device=host_dev)[0])))
            eval_span.end()

            if resil is not None and (epoch + 1) % cfg.log_every == 0:
                if wrote_ckpt:
                    # a guard-verified checkpoint strictly past the last
                    # rollback heals the divergence retry budget
                    resil.note_progress(epoch)
                # checkpoint fsync + (mesh) eval — incl. the eval compile on
                # its first call — are epoch-boundary work: reset the
                # liveness clock so they never eat into the next step's
                # watchdog deadline
                resil.watchdog.touch()

            # ---- preemption-safe shutdown: the SIGTERM/SIGINT flag is read
            # at the step boundary only — mid-step device state is never
            # torn. The resumable checkpoint carries seed + retry nonce, so
            # --resume continues the exact sampling/dropout streams. Under
            # coordination the flag already went through the agreed-verdict
            # exchange above (a signal landing after it waits one epoch). ----
            if (resil is not None and resil.coord is None
                    and resil.preempt_requested):
                ppath = ckpt.periodic_path(cfg, epoch)
                if is_rank0 and not wrote_ckpt:
                    ckpt.save_checkpoint(ppath, params=params,
                                         opt_state=opt_state, bn_state=state,
                                         epoch=epoch, best_acc=best_acc,
                                         seed=seed,
                                         extra=_ckpt_extra())
                    ckpt.prune_checkpoints(cfg, cfg.keep_ckpt)
                log(f"[resilience] {resil.preempt_requested} honored at the "
                    f"epoch-{epoch} step boundary: resumable checkpoint at "
                    f"{ppath}")
                if obs is not None:
                    obs.emit("preempt", epoch=epoch, ckpt=ppath,
                             signal=resil.preempt_requested)
                raise resilience.PreemptedError(epoch, ppath)
            epoch += 1
    finally:
        if obs is not None:
            obs.epoch_end()
            obs.flush_first_calls()
        # trace-window leak fix: a crash/preemption anywhere in the loop
        # (including the normal shorter-than-prof_stop ending) must not
        # leave a dangling profiler session or the auto temp dir behind
        if tracing or usr1_tracing:
            try:
                jax.profiler.stop_trace()
            except Exception:
                pass
            if usr1_tracing:
                # the open trace was the SIGUSR1 one (the two never overlap)
                # — announce ITS location and complete its event pair, not
                # the comm-trace's profile_dir
                log(f"[obs] SIGUSR1 profiler window written to {usr1_dir}")
                if obs is not None:
                    obs.emit("profile", epoch=epoch, trace_dir=usr1_dir,
                             at_exit=True)
            elif cfg.profile_dir:
                log(f"profiler trace written to {cfg.profile_dir}")
            tracing = usr1_tracing = False
        if auto_trace_dir:
            shutil.rmtree(auto_trace_dir, ignore_errors=True)
        if resil is not None:
            res.rollbacks = list(resil.rollbacks)
            resil.close()
        if strict is not None:
            # the audit summary must land (log + obs event) on EVERY exit
            # path — an interrupted strict run still proves what it proved
            strict.finish()
        if obs is not None and sys.exc_info()[0] is not None:
            # an interrupted run (preempt 75, divergence 76, abort 78 —
            # anything raising out of the loop) still ends its log with a
            # terminal record; the normal path emits a richer run_end below
            mt_i, _, _ = timer.means()
            obs.emit("run_end", interrupted=sys.exc_info()[0].__name__,
                     epochs_done=len(res.losses), final_loss=loss_f,
                     epoch_time_s=round(mt_i, 6))
            obs.close()
        if coordinator is not None:
            # terminal decisions (preempt/abort) were already confirmed by
            # every peer inside agree(); a NORMAL completion still needs a
            # barrier, or rank 0 could tear the server down while a peer —
            # up to one step boundary behind — is fetching its last verdict
            if sys.exc_info()[0] is None:
                coordinator.finish()
            coordinator.close()
        if sys.exc_info()[0] is not None:
            # propagate without waiting on a queued eval. An in-flight eval
            # still runs in its (non-daemon) worker; the CLI preemption path
            # therefore ends with os._exit in main.py so the exit-75
            # contract can't be stalled past the platform's grace window
            pool.shutdown(wait=False, cancel_futures=True)
    if pending is not None:
        done = _drain_eval(pending)
        if done[1] > best_acc:
            best_acc, best_params = done[1], done[0]
    pool.shutdown(wait=True)

    res.epoch_time, res.comm_time, res.reduce_time = timer.means()
    res.overlap_buckets = timer.bucket_means()
    res.final_loss = float(loss)
    res.memory = format_memory_stats()
    log(res.memory)

    if cfg.eval and best_params is not None:
        # checkpoint/log I/O is rank-0-only, but the mesh test eval is a
        # COLLECTIVE — every process must join it or the mesh deadlocks
        if is_rank0:
            ckpt.save_checkpoint(ckpt.final_path(cfg), params=best_params,
                                 bn_state=jax.device_get(state),
                                 epoch=cfg.n_epochs - 1, best_acc=best_acc,
                                 seed=seed)
            log("model saved")
            log("Max Validation Accuracy {:.2%}".format(best_acc))
        res.best_val_acc = best_acc
        if mesh_eval:
            # test resources built lazily (inductive test graph = full graph;
            # no reason to pin it in HBM during training)
            fns_e, blk_e, tf_e, art_e = (
                _eval_resources(test_g, "-test") if cfg.inductive else eval_val)
            pb = place_p(best_params)
            res.test_acc = evaluate_mesh("Test Result", fns_e.eval_forward,
                                         pb, state, blk_e, tf_e, art_e,
                                         ("test",))["test"]
        elif is_rank0:
            res.test_acc = evaluate_induc("Test Result", best_params,
                                          jax.device_get(state), spec, test_g,
                                          "test", device=host_dev)

    # ---- embedding-table export (--dump-embeddings): the all-node
    # penultimate activations + final-layer logits, written under the
    # checkpoint integrity header so serve.py can cold-start from the
    # artifact instead of recomputing. Uses the best-val params when
    # available (what serving should score with), else the final params —
    # so `--resume --n-epochs 0 --dump-embeddings PATH` is a standalone
    # embedding-export tool over a finished run. ----
    if cfg.dump_embeddings and is_rank0:
        from bnsgcn_tpu import serve as serve_mod
        from bnsgcn_tpu.evaluate import full_graph_embeddings, gather_parts
        dump_params = (best_params if best_params is not None
                       else jax.device_get(params))
        t0 = time.time()
        hidden = logits = None
        if multi_host:
            log("[serve] --dump-embeddings skipped: multi-host export needs "
                "a gather of remote part rows (single-host only for now)")
        elif mesh_eval and not cfg.inductive:
            # mesh seam: the eval forward returning (hidden, logits) per
            # part (trainer.embed_forward), assembled to global node order
            fns_e, blk_e, tf_e, art_e = eval_val
            hid, lg = fns_e.embed_forward(place_p(dump_params), state,
                                          blk_e, tf_e)
            hidden = gather_parts(art_e, hid)
            logits = gather_parts(art_e, lg)
        elif test_g is not None or g is not None:
            graph = test_g if test_g is not None else g
            hidden, logits = full_graph_embeddings(
                dump_params, jax.device_get(state), spec, graph,
                cfg.edge_chunk, device=host_dev)
        else:
            log("[serve] --dump-embeddings skipped: no eval graph loaded "
                "(run with --eval, or --eval-device mesh transductive)")
        if hidden is not None:
            serve_mod.save_table(cfg.dump_embeddings, hidden, logits, meta={
                "graph_name": cfg.graph_name or cfg.derive_graph_name(),
                "model": cfg.model, "n_nodes": int(hidden.shape[0]),
                "epoch": cfg.n_epochs - 1,
                "best_acc": float(best_acc)})
            log(f"[serve] embedding table [{hidden.shape[0]} x "
                f"{hidden.shape[1]}] + logits [{logits.shape[1]} classes] "
                f"-> {cfg.dump_embeddings} ({time.time() - t0:.1f}s)")
    if obs is not None:
        obs.emit("run_end", final_loss=res.final_loss,
                 epoch_time_s=round(res.epoch_time, 6),
                 comm_time_s=round(res.comm_time, 6),
                 reduce_time_s=round(res.reduce_time, 6),
                 best_val_acc=round(res.best_val_acc, 6),
                 test_acc=round(res.test_acc, 6),
                 rollbacks=len(res.rollbacks),
                 step_hist=obs.registry.histogram("train/step_s").snapshot())
        obs.close()
    return res


obs_mod.boot_end("import", proc_start=obs_mod.proc_start_wall())
