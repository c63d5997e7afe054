"""Typed run configuration.

Flag-compatible with the reference CLI (reference helper/parser.py:4-61): every
reference flag has a field of the same name here, plus TPU-specific knobs. The
reference threads a raw argparse namespace through every module; here the
config is a frozen dataclass created once and passed explicitly.
"""

from __future__ import annotations

import argparse
import dataclasses
import os
from dataclasses import dataclass
from typing import Optional


class ConfigError(ValueError):
    """A named configuration error: main.py prints it and exits 2 (the
    deterministic-argument-error code a requeue wrapper never relaunches),
    instead of a stack trace from deep inside mesh construction."""


@dataclass(frozen=True)
class Config:
    # --- data / partitioning (reference helper/parser.py:6-13,37-41) ---
    dataset: str = "reddit"
    data_path: str = "./dataset/"
    part_path: str = "./partition/"
    graph_name: str = ""
    n_partitions: int = 2
    partition_obj: str = "vol"          # 'vol' | 'cut'
    partition_method: str = "metis"     # 'metis' | 'random'  (metis → native partitioner)
    inductive: bool = False
    skip_partition: bool = False

    # --- model (reference helper/parser.py:14-31,42-46) ---
    model: str = "graphsage"            # 'gcn' | 'graphsage' | 'gat'
    n_layers: int = 2
    n_hidden: int = 16
    n_linear: int = 0
    heads: int = 1
    norm: Optional[str] = "layer"       # 'layer' | 'batch' | None
    dropout: float = 0.5
    use_pp: bool = False

    # --- optimization (reference helper/parser.py:16-19,32-34) ---
    lr: float = 1e-2
    weight_decay: float = 0.0
    n_epochs: int = 200
    sampling_rate: float = 1.0

    # --- bookkeeping ---
    log_every: int = 10
    eval: bool = True
    fix_seed: bool = False
    seed: int = 0
    ckpt_path: str = "./checkpoint/"
    results_path: str = "./results/"
    resume: bool = False                # capability upgrade: reference is save-only (train.py:428)
    keep_ckpt: int = 5                  # retain the newest N periodic checkpoints (0 = keep all;
                                        # the reference keeps every snapshot, train.py:428)

    # --- distributed / launcher (reference helper/parser.py:47-56) ---
    backend: str = "xla"                # XLA collectives; 'gloo'/'mpi' accepted as aliases
    port: int = 18118
    master_addr: str = "127.0.0.1"
    node_rank: int = 0
    parts_per_node: int = 10
    n_nodes: int = 1                    # multi-host: number of processes (jax.distributed)

    # --- TPU-specific knobs (no reference equivalent) ---
    replicas: int = 1                   # replica-axis size of the 2-D
                                        # ('replicas','parts') mesh: each of N
                                        # full graph replicas draws an
                                        # independent BNS boundary sample and
                                        # the gradient is the fused cross-
                                        # replica mean (~1/N sampling variance
                                        # at constant epoch math/replica).
                                        # Needs replicas*n_partitions devices;
                                        # 1 = the historical 1-D parts mesh,
                                        # bit-identical
    feat: int = 1                       # feat-axis size of the 3-D
                                        # ('replicas','parts','feat') mesh:
                                        # shard hidden dimensions T-ways —
                                        # perfectly load-balanced (no boundary
                                        # nodes on this axis), halo wire bytes
                                        # drop ~T x, weight/optimizer HBM and
                                        # matmul FLOPs /T; one feat psum per
                                        # layer. Needs replicas*parts*feat
                                        # devices; 1 = no axis, bit-identical
    dtype: str = "float32"              # compute dtype: 'float32' | 'bfloat16'
    edge_chunk: int = 0                 # >0: aggregate edges in chunks of this size (bounds HBM)
    spmm: str = "ell"                   # 'ell' (scatter-free bucketed) | 'hybrid'
                                        # (dense int8 MXU tiles + ELL residual) | 'auto'
                                        # (estimate tile coverage, pick hybrid/ell) | 'segment'
    spmm_gather: str = "native"         # 'native' | 'fp8' | 'int8': quantize SpMM gather rows to
                                        # e4m3 (+1 scale per call) — the gather unit is
                                        # row-rate bound, so 256B rows move ~1.5x faster
    spmm_dense: str = "native"          # hybrid SpMM dense-tile matmul dtype: 'native'
                                        # (compute dtype) | 'int8' (quantized slabs,
                                        # int8x int8 MXU at ~2x bf16 rate)
    block_occupancy: int = 0            # hybrid SpMM: min edges for a tile to densify.
                                        # 0 = auto: the time break-even against the
                                        # residual gather, half the tile's edge (256
                                        # at 512x512, 128 at 256x256: on a v5e a
                                        # residual edge costs ~22.6 ns a step, a tile
                                        # 5.94 / 2.97 us; measured at those two sizes
                                        # only); explicit values are absolute
    block_tile_budget_mb: int = 4096    # hybrid SpMM: device bytes of one build's tile
                                        # stack, the one int8 stack both directions
                                        # read (16384 tiles at 512x512); --overlap
                                        # split builds two, interior and frontier
                                        # rows, each under this budget. Until the
                                        # stacks were merged it counted each
                                        # direction: an old explicit value now buys
                                        # half the tiles it did
    block_tile: int = 512               # hybrid SpMM: square tile edge (512 default;
                                        # 256 = 4x more tiles per budget byte, finer
                                        # edge capture on clustered graphs at ~2x the
                                        # slab-gather traffic per tile byte)
    reorder: str = "off"                # graph-reordering artifact pass
                                        # (data/reorder.py): 'cluster' permutes
                                        # each part's inner rows ONCE at load
                                        # (degree-anchored label propagation +
                                        # FFD tile packing) so edge mass
                                        # concentrates into dense MXU tiles;
                                        # 'auto' applies it only when measured
                                        # tile coverage improves; 'off' is the
                                        # bit-identical pre-reorder pipeline.
                                        # Results stay in global id order (the
                                        # permuted global_nid inverts at every
                                        # user-visible edge); the order is
                                        # cached like layouts under --cache-dir
    profile_dir: str = ""               # write a jax.profiler trace of a few epochs here
    comm_trace: bool = True             # auto-trace a short post-warmup window and report
                                        # trace-derived in-step Comm/Reduce columns
                                        # ([traced]); --no-comm-trace keeps the
                                        # exchange-only microbench ([sampled])
    remat: bool = False                 # rematerialize each layer in backward (saves HBM,
                                        # recomputes activations incl. the halo exchange)
    eval_device: str = "host"           # 'host' (background thread, full graph) |
                                        # 'mesh' (distributed full-rate eval on the parts mesh)
    halo_exchange: str = "padded"       # 'padded' (one all_to_all, uniform pad) |
                                        # 'shift' (P-1 ppermute rounds, per-shift pads —
                                        #  wire bytes track skewed boundary sizes) |
                                        # 'ragged' (one lax.ragged_all_to_all, exact
                                        #  per-pair bytes; emulated off-TPU) |
                                        # 'auto' (pick per run from wire_bytes() +
                                        #  hop-count tiebreak; logged at startup)
    halo_wire: str = "native"           # interconnect payload dtype for the training halo
                                        # exchange: 'native' | 'bf16' | 'fp8' (e4m3 + scales)
    halo_refresh: int = 1               # staleness-bounded halo cache: reuse each
                                        # layer's received halo block for up to K
                                        # epochs, refreshing ~1/K of every boundary
                                        # set per epoch (round-robin over position
                                        # chunks) so steady-state wire bytes drop
                                        # ~K x without a synchronized staleness
                                        # cliff. Gradients stop at stale cached
                                        # rows (exact w.r.t. the forward actually
                                        # computed). 1 = the historical per-epoch
                                        # exchange, bit-identical. The cache is
                                        # never checkpointed: rollback/--resume
                                        # invalidate it and force one full-refresh
                                        # (peak-wire) epoch
    halo_mode: str = "exchange"         # 'exchange' (activations cross the wire as
                                        # configured above) | 'grad-only' (the
                                        # Grappa extreme: skip the activation
                                        # exchange entirely and aggregate from
                                        # local rows only — zero halo block,
                                        # presence-masked out of GAT softmax;
                                        # the per-step gradient all-reduce is the
                                        # only collective left)
    tune: str = "off"                   # closed-loop comm auto-tuner (tune.py):
                                        # 'off' (launch levers frozen, bit-
                                        # identical pre-tune loop) | 'schedule'
                                        # (declarative per-epoch lever schedule,
                                        # --tune-schedule) | 'auto' (feedback
                                        # anneal on the obs bus: staleness
                                        # tightens as loss flattens, strategy/
                                        # codec re-picked from MEASURED comm
                                        # share; single-process only). Every
                                        # move is a tune_decision event and a
                                        # full-refresh rebuild of the step fns
    tune_schedule: str = ""             # --tune schedule grammar: comma-
                                        # separated lever=value@epoch, levers
                                        # K/mode/strategy/wire (e.g.
                                        # 'K=4@0,K=2@30,K=1@60,wire=bf16@30')
    tune_prior: str = "ladder"          # --tune auto launch point: 'ladder'
                                        # (coarse K=4 start, tighten rung by
                                        # rung — the historical controller,
                                        # bit-identical default) | 'model'
                                        # (the graftperf cost model
                                        # (analysis/perf) predicts the comm
                                        # fraction and picks the starting
                                        # rung, then auto refines locally)
    overlap: str = "off"                # 'off' (fused exchange-then-aggregate; the
                                        # historical step graph) | 'split' (interior/
                                        # frontier row-split aggregation: the halo
                                        # collective is dispatched first and the
                                        # interior SpMM — rows with no halo
                                        # in-neighbor — runs while it is in flight;
                                        # numerically row-exact vs 'off')
    streaming_artifacts: str = "auto"   # 'auto' (> 30M edges) | 'always' | 'never':
                                        # build partition artifacts one part at a time
    feat_storage: str = "float32"       # on-disk feature dtype for streamed artifacts
                                        # ('bfloat16' halves papers100M-scale feature IO)
    resilience: str = "on"              # 'on' (divergence rollback + preemption-
                                        # safe shutdown + hung-step watchdog,
                                        # resilience.py) | 'off' (bit-identical
                                        # pre-resilience loop: no checks, no
                                        # threads, no signal handlers)
    inject: str = ""                    # deterministic fault injection:
                                        # 'kind@E<epoch>,...' with kinds
                                        # nan|sigterm|hang|ckpt-corrupt|
                                        # ranklost (env $BNSGCN_FAULT); CI
                                        # proves every recovery path with it.
                                        # ranklost requires :r<rank> — losing
                                        # every rank is not a resize. Serving
                                        # kinds fire on the Nth routed data-
                                        # path request inside one backend:
                                        # servekill@<N>:p<P>.r<R> (hard exit)
                                        # | servehang@<N>:p<P>.r<R> (wedge) |
                                        # servedrop@<N>[:p<P>.r<R>] (torn
                                        # connection, no response)
    elastic: str = "off"                # 'on': a heartbeat-detected rank
                                        # loss becomes an agreed RESIZE
                                        # verdict (survivors re-host the P
                                        # parts via mesh.plan_slots and keep
                                        # training; a rejoining replacement
                                        # grows the world back) instead of
                                        # CoordTimeout -> exit 77. 'off'
                                        # (default): the exact pre-elastic
                                        # protocol, bit-identical, exit-code
                                        # table unchanged. Requires the
                                        # coordinator (--coord tcp|file)
    elastic_min_world: int = 1          # smallest world a RESIZE may shrink
                                        # to; fewer survivors -> agreed abort
                                        # (78) instead of overloaded workers
    resil_retries: int = 3              # divergence rollbacks (exponential
                                        # backoff) before aborting with a
                                        # diagnostic report
    coord: str = "auto"                 # multi-host rank coordination channel
                                        # (parallel/coord.py): 'auto' (tcp
                                        # when >1 rank, else off) | 'tcp'
                                        # (rank 0 serves --coord-port) |
                                        # 'file' (shared --coord-dir) |
                                        # 'off' (bit-identical PR-4 paths:
                                        # no agreed verdicts, multi-host
                                        # resilience downgraded)
    coord_addr: str = ""                # coordinator host (default
                                        # master_addr) for --coord tcp
    coord_port: int = 18119             # rank 0's KV-server port (tcp)
    coord_dir: str = ""                 # shared dir for --coord file
                                        # (default {ckpt_path}/.coord)
    coord_rank: int = -1                # this process's coordination rank;
                                        # -1 = jax.process_index(). Explicit
                                        # values enable the no-XLA-collective
                                        # subprocess harness (each process a
                                        # full single-host trainer, coupled
                                        # only through the coordinator)
    coord_world: int = 0                # total coordination ranks; 0 =
                                        # jax.process_count()
    # --- online inference serving (serve.py; `python -m bnsgcn_tpu.main
    # serve ...` or `python -m bnsgcn_tpu.serve ...`) ---
    serve_port: int = 18120             # line-JSON TCP port the node-
                                        # prediction server listens on
                                        # (same wire protocol/framing as the
                                        # rank coordinator's KV server)
    serve_addr: str = ""                # bind address (server) / connect
                                        # address (clients); default all
                                        # interfaces / 127.0.0.1
    serve_dir: str = ""                 # serving state dir (resumable delta
                                        # log flushed on SIGTERM drain);
                                        # default {ckpt_path}/serve
    serve_max_batch: int = 64           # max tier-B requests coalesced into
                                        # one padded-SpMM bucket step
    serve_refresh_s: float = 0.2        # background dirty-embedding refresh
                                        # cadence (0 = refresh only on
                                        # demand / 'flush')
    embeddings: str = ""                # embedding-table artifact
                                        # (--dump-embeddings output) to
                                        # cold-start serving from instead of
                                        # recomputing the all-node table
    dump_embeddings: str = ""           # eval path: write the all-node
                                        # embedding table (penultimate
                                        # activations + final-layer logits,
                                        # checkpoint integrity header) here
    serve_compact_deltas: int = 0       # delta-log compaction threshold: at
                                        # >= N logged deltas, snapshot the
                                        # mutated graph + tables (write_blob
                                        # integrity header) and truncate the
                                        # log to a tail so relaunch replay is
                                        # O(snapshot + tail); 0 = never
                                        # compact (full replay, PR-7 exact)
    # --- partition-sharded distributed serving (serve_router.py /
    # serve_backend.py; `serve-router` + `serve-backend` subcommands) ---
    parts: int = 0                      # serving fleet width: number of
                                        # partition shards the router expects
                                        # backends for; 0 = read it from the
                                        # partition artifacts' meta.json
    part_replicas: int = 1              # read replicas per part behind the
                                        # router (deltas broadcast to all,
                                        # reads round-robined)
    serve_part: int = -1                # which partition shard THIS backend
                                        # process owns (serve-backend only)
    serve_replica: int = 0              # this backend's replica ordinal
                                        # within its part (serve-backend)
    serve_backend_port: int = 0         # backend listen port (serve-backend;
                                        # 0 = ephemeral, reported to the
                                        # router at registration)
    serve_router: str = ""              # router address a backend registers
                                        # with / clients connect to, as
                                        # 'host:port' (default
                                        # 127.0.0.1:{serve_port})
    serve_degraded: str = "off"         # router answer when a part has no
                                        # live backend: 'off' = named
                                        # RouteError (PR-16 protocol),
                                        # 'partial' = per-node
                                        # status:"unavailable" rows, the rest
                                        # answered; 'stale-ok' = additionally
                                        # serve tier-A from a non-up replica,
                                        # tagged status:"stale"
    serve_probe_s: float = 0.0          # router health-probe cadence in
                                        # seconds (up/suspect/down states,
                                        # breaker quarantine, WAL replay on
                                        # recovery); 0 = probes off —
                                        # evict-on-error exactly as PR 16.
                                        # Thresholds are env knobs:
                                        # BNSGCN_SERVE_{SUSPECT_AFTER,
                                        # DOWN_AFTER,READMIT,BREAKER_FLAPS,
                                        # BREAKER_WINDOW_S,BREAKER_HOLD_S,
                                        # PROBE_TIMEOUT_S}
    serve_hedge: str = "off"            # 'on' = hedge tier-A reads: fire a
                                        # second replica after a p99-derived
                                        # delay, first answer wins, loser
                                        # cancelled (reads only — writes stay
                                        # at-most-once)
    serve_wal_cap: int = 256            # router-side WAL bound: queued
                                        # delta writes per DOWN part before
                                        # new writes fail loudly (replayed in
                                        # order on recovery; only active with
                                        # --serve-degraded != off)
    # --- continual training on an evolving graph (continual.py +
    # data/incremental.py; `python -m bnsgcn_tpu.main continual ...`).
    # All defaults are inert: a run that never passes --warm-start /
    # --cycle-nonce and never invokes the subcommand is bit-identical. ---
    cycle_epochs: int = 5               # fine-tune epochs per continual cycle
    cycles: int = 1                     # continual cycles to run (looped
                                        # train->promote; 1 = one-shot)
    continual_source: str = "auto"      # where cycle deltas come from:
                                        # 'server' (live export_deltas RPC
                                        # handshake), 'log' (flushed delta-log
                                        # files in serve_dir), 'auto' = server
                                        # if reachable else log
    continual_cut_growth: float = 1.5   # staleness budget: re-partition from
                                        # scratch when edge-cut grows past
                                        # baseline*factor (else incremental
                                        # update at the pinned assignment)
    continual_imbalance: float = 2.0    # staleness budget: re-partition when
                                        # max/mean per-part edge load exceeds
                                        # this factor
    continual_acc_drop: float = 0.02    # promotion gate: refuse to promote
                                        # (keep serving the prior weights)
    #                                     when the fine-tuned val accuracy
    #                                     drops more than this below the old
    #                                     weights' accuracy on the SAME
    #                                     mutated graph
    warm_start: str = ""                # checkpoint blob to warm-start
                                        # params/BN state from (optimizer
                                        # starts fresh; mutually exclusive
                                        # with --resume)
    cycle_nonce: int = 0                # continual-cycle fold of the
                                        # sampling/dropout streams (the
                                        # retry-nonce pattern, high-bit fold
                                        # domain); 0 = historical streams,
                                        # bit-identical

    # --- observability (obs.py: unified telemetry bus) ---
    obs: str = "on"                     # 'on' (process-wide metrics registry +
                                        # structured event log + post-mortem
                                        # capture, obs.py) | 'off' (constructs
                                        # none of it: bit-identical loop,
                                        # pinned by tests/test_obs.py)
    obs_log: str = ""                   # rank-tagged JSONL event log path
                                        # (default $BNSGCN_OBS_LOG; ranks > 0
                                        # write PATH.r<rank>); size-bounded
                                        # with rotation ($BNSGCN_OBS_MAX_MB).
                                        # Empty = registry only, no file
    obs_dir: str = ""                   # post-mortem dir (watchdog/divergence
                                        # dumps, SIGUSR1 stack+metrics+trace
                                        # snapshots); default
                                        # {ckpt_path}/postmortem
    strict_exec: bool = False           # strict-execution runtime guard
                                        # (strict.py): jax.transfer_guard
                                        # around the hot-loop step (implicit
                                        # host transfer = error) + a compile
                                        # listener (recompile after a step
                                        # variant's first epoch = error).
                                        # Proof-of-cleanliness for pod runs;
                                        # the static half is graftlint
                                        # (python -m bnsgcn_tpu.analysis)

    cache_dir: str = ""                 # persistent dir for SpMM layout pickles
                                        # (content-addressed by hybrid_layout_key);
                                        # default from $BNSGCN_CACHE_DIR — point it at
                                        # a persistent volume and the ~980 s hybrid
                                        # layout build survives container wipes.
                                        # Empty = rebuild every run.

    # fields injected from partition meta.json at load time
    # (reference helper/utils.py:134-138)
    n_feat: int = 0
    n_class: int = 0
    n_train: int = 0

    def replace(self, **kw) -> "Config":
        return dataclasses.replace(self, **kw)

    @property
    def multilabel(self) -> bool:
        return self.dataset == "yelp"

    def layer_sizes(self) -> list[int]:
        """[n_feat, hidden, ..., hidden, n_class] — reference helper/utils.py:233-241."""
        assert self.n_layers >= 1
        return [self.n_feat] + [self.n_hidden] * (self.n_layers - 1) + [self.n_class]

    def derive_graph_name(self) -> str:
        """Reference main.py:18-24."""
        mode = "induc" if self.inductive else "trans"
        return (f"{self.dataset}-{self.n_partitions}-{self.partition_method}-"
                f"{self.partition_obj}-{mode}")


def create_parser() -> argparse.ArgumentParser:
    """Argparse front-end accepting the reference's flags (helper/parser.py:4-61)."""
    p = argparse.ArgumentParser(description="bnsgcn_tpu — TPU-native BNS-GCN-capability framework")

    def both(name, **kw):
        p.add_argument(f"--{name}", f"--{name.replace('-', '_')}", **kw)

    p.add_argument("--dataset", type=str, default="reddit")
    both("data-path", type=str, default="./dataset/")
    both("part-path", type=str, default="./partition/")
    both("graph-name", type=str, default="")
    p.add_argument("--model", type=str, default="graphsage",
                   choices=["gcn", "graphsage", "gat"])
    p.add_argument("--dropout", type=float, default=0.5)
    p.add_argument("--lr", type=float, default=1e-2)
    both("sampling-rate", type=float, default=1.0)
    p.add_argument("--heads", type=int, default=1)
    both("n-epochs", type=int, default=200)
    both("n-partitions", type=int, default=2)
    both("n-hidden", type=int, default=16)
    both("n-layers", type=int, default=2)
    both("log-every", type=int, default=10)
    both("weight-decay", type=float, default=0.0)
    p.add_argument("--norm", choices=["layer", "batch", "none"], default="layer")
    both("partition-obj", choices=["vol", "cut"], default="vol")
    both("partition-method", choices=["metis", "random"], default="metis")
    both("n-linear", type=int, default=0)
    both("use-pp", action="store_true", default=False)
    p.add_argument("--inductive", action="store_true")
    both("fix-seed", action="store_true", default=False)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--backend", type=str, default="xla")
    p.add_argument("--port", type=int, default=18118)
    both("master-addr", type=str, default="127.0.0.1")
    both("node-rank", type=int, default=0)
    both("parts-per-node", type=int, default=10)
    p.add_argument("--skip-partition", action="store_true")
    p.add_argument("--eval", action="store_true", dest="eval")
    p.add_argument("--no-eval", action="store_false", dest="eval")
    p.set_defaults(eval=True)
    # TPU-specific
    p.add_argument("--replicas", type=int, default=1,
                   help="replica-axis size: train N independently-BNS-sampled "
                        "graph replicas on a ('replicas','parts') mesh and "
                        "average gradients (needs N*n_partitions devices; "
                        "use when devices > partitions)")
    p.add_argument("--feat", type=int, default=1,
                   help="feat/tensor-axis size: shard hidden dimensions "
                        "T-ways on the innermost mesh axis (zero boundary "
                        "nodes on this axis; halo wire bytes and matmul "
                        "FLOPs drop ~T x; one psum per layer) — wins on "
                        "wide-hidden runs; needs replicas*parts*feat devices")
    p.add_argument("--dtype", type=str, default="float32", choices=["float32", "bfloat16"])
    p.add_argument("--spmm", type=str, default="ell",
                   choices=["ell", "hybrid", "auto", "segment"])
    both("profile-dir", type=str, default="")
    p.add_argument("--no-comm-trace", action="store_false", dest="comm_trace",
                   help="disable the auto-traced in-step Comm/Reduce columns")
    p.set_defaults(comm_trace=True)
    p.add_argument("--remat", action="store_true")
    both("eval-device", type=str, default="host", choices=["host", "mesh"])
    both("halo-exchange", type=str, default="padded",
         choices=["padded", "shift", "ragged", "auto"])
    both("halo-wire", type=str, default="native", choices=["native", "bf16", "fp8", "int8"])
    both("halo-refresh", type=int, default=1,
         help="reuse each layer's received halo block for up to K epochs, "
              "refreshing ~1/K of every boundary set per epoch round-robin "
              "(steady-state wire bytes drop ~K x; 1 = exchange every epoch, "
              "bit-identical to the pre-cache path)")
    both("halo-mode", type=str, default="exchange",
         choices=["exchange", "grad-only"],
         help="'grad-only' skips the activation exchange entirely "
              "(local-only aggregation; the per-step gradient all-reduce is "
              "the only collective left)")
    p.add_argument("--tune", type=str, default="off",
                   choices=["off", "schedule", "auto"],
                   help="closed-loop comm auto-tuner (tune.py): retune "
                        "staleness/strategy/codec at epoch boundaries from "
                        "the obs-bus metrics ('auto', single-process) or a "
                        "declarative --tune-schedule ('schedule'); every "
                        "move is an audited tune_decision event")
    both("tune-schedule", type=str, default="",
         help="--tune schedule grammar: comma-separated lever=value@epoch "
              "with levers K/mode/strategy/wire, e.g. "
              "'K=4@0,K=2@30,K=1@60,wire=bf16@30'")
    both("tune-prior", type=str, default="ladder",
         choices=["ladder", "model"],
         help="--tune auto launch point: 'ladder' starts coarse (K=4) and "
              "tightens rung by rung; 'model' asks the graftperf cost model "
              "(analysis/perf) for the predicted-optimal starting rung from "
              "the partition geometry + calibration tables, then refines "
              "locally — fewer retune windows when the model is right")
    p.add_argument("--overlap", type=str, default="off", choices=["off", "split"])
    both("streaming-artifacts", type=str, default="auto",
         choices=["auto", "always", "never"])
    both("feat-storage", type=str, default="float32",
         choices=["float32", "bfloat16"])
    p.add_argument("--resilience", type=str, default="on",
                   choices=["on", "off"],
                   help="divergence rollback, preemption-safe checkpointing "
                        "and the hung-step watchdog (off = the exact "
                        "pre-resilience loop)")
    p.add_argument("--inject", type=str,
                   default=os.environ.get("BNSGCN_FAULT", ""),
                   help="deterministic fault injection, e.g. "
                        "'nan@E12,sigterm@E20,hang@E8,ckpt-corrupt@E10,"
                        "ranklost@E6:r1'")
    both("resil-retries", type=int, default=3)
    p.add_argument("--elastic", type=str, default="off",
                   choices=["off", "on"],
                   help="elastic world size: agree a coordinated RESIZE on "
                        "heartbeat-detected rank loss (survivors re-host all "
                        "parts and keep training; a rejoin grows back) "
                        "instead of exiting 77 (off = the exact pre-elastic "
                        "protocol, bit-identical)")
    both("elastic-min-world", type=int, default=1,
         help="smallest world --elastic may shrink to before an agreed "
              "abort (exit 78)")
    p.add_argument("--coord", type=str, default="auto",
                   choices=["auto", "tcp", "file", "off"],
                   help="multi-host rank-coordination channel for agreed "
                        "abort/rollback (off = the uncoordinated PR-4 "
                        "behavior, bit-identical)")
    both("coord-addr", type=str, default="")
    both("coord-port", type=int, default=18119)
    both("coord-dir", type=str, default="")
    both("coord-rank", type=int, default=-1,
         help="explicit coordination rank (with --coord-world: run the "
              "coordinator without jax.distributed — the subprocess fault "
              "harness)")
    both("coord-world", type=int, default=0)
    # online inference serving (serve.py)
    both("serve-port", type=int, default=18120)
    both("serve-addr", type=str, default="")
    both("serve-dir", type=str, default="")
    both("serve-max-batch", type=int, default=64)
    both("serve-refresh-s", type=float, default=0.2)
    p.add_argument("--embeddings", type=str, default="",
                   help="embedding-table artifact (--dump-embeddings "
                        "output) to cold-start serving from")
    both("dump-embeddings", type=str, default="",
         help="write the all-node embedding table (+ integrity header) "
              "here after eval — serve.py cold-starts from it")
    both("serve-compact-deltas", type=int, default=0,
         help="compact the serving delta log past N entries: integrity-"
              "headed snapshot + truncated tail, so relaunch replay is "
              "O(snapshot + tail) instead of O(all deltas ever); 0 = off")
    # partition-sharded distributed serving (serve_router/serve_backend)
    p.add_argument("--parts", type=int, default=0,
                   help="serving fleet width (number of partition shards "
                        "the router fronts); 0 = read it from the partition "
                        "artifacts' meta.json")
    both("part-replicas", type=int, default=1,
         help="read replicas per part behind the serving router (deltas "
              "broadcast, reads round-robined)")
    both("serve-part", type=int, default=-1,
         help="partition shard this serve-backend owns")
    both("serve-replica", type=int, default=0,
         help="replica ordinal of this serve-backend within its part")
    both("serve-backend-port", type=int, default=0,
         help="serve-backend listen port (0 = ephemeral; reported to the "
              "router at registration)")
    both("serve-router", type=str, default="",
         help="router 'host:port' a serve-backend registers with (default "
              "127.0.0.1:{serve-port})")
    both("serve-degraded", type=str, default="off",
         choices=["off", "partial", "stale-ok"],
         help="router behavior for a part with no live backend: 'off' = "
              "named RouteError (PR-16), 'partial' = per-node "
              "status:'unavailable' rows while the rest answer, 'stale-ok' "
              "= also serve possibly-stale tier-A from a non-up replica, "
              "tagged status:'stale'")
    both("serve-probe-s", type=float, default=0.0,
         help="router health-probe cadence in seconds (up/suspect/down, "
              "breaker quarantine, rejoin warm-up + WAL replay); 0 = "
              "probes off, evict-on-error exactly as PR 16 "
              "(thresholds: BNSGCN_SERVE_* env knobs)")
    both("serve-hedge", type=str, default="off", choices=["off", "on"],
         help="hedge tier-A fleet reads: fire a second replica after a "
              "p99-derived delay, first answer wins, loser cancelled")
    both("serve-wal-cap", type=int, default=256,
         help="bounded router-side WAL: queued delta writes per down part "
              "before writes fail loudly (replayed in order on recovery)")
    # continual training (continual.py; `continual` subcommand)
    both("cycle-epochs", type=int, default=5,
         help="fine-tune epochs per continual cycle")
    p.add_argument("--cycles", type=int, default=1,
                   help="continual cycles to run (looped train->promote; "
                        "1 = one-shot)")
    both("continual-source", type=str, default="auto",
         choices=["auto", "server", "log"],
         help="cycle delta source: live export_deltas RPC ('server'), "
              "flushed delta-log files ('log'), or 'auto'")
    both("continual-cut-growth", type=float, default=1.5,
         help="re-partition from scratch when edge-cut exceeds "
              "baseline*factor; below it the cycle updates artifacts "
              "incrementally at the pinned assignment")
    both("continual-imbalance", type=float, default=2.0,
         help="re-partition when max/mean per-part edge load exceeds this")
    both("continual-acc-drop", type=float, default=0.02,
         help="refuse to promote when fine-tuned val accuracy drops more "
              "than this below the old weights on the same mutated graph")
    both("warm-start", type=str, default="",
         help="checkpoint blob to warm-start params/BN state from (fresh "
              "optimizer; mutually exclusive with --resume)")
    both("cycle-nonce", type=int, default=0,
         help="continual-cycle sampling/dropout stream fold (0 = "
              "bit-identical historical streams)")
    # observability (obs.py)
    p.add_argument("--obs", type=str, default="on", choices=["on", "off"],
                   help="unified telemetry bus: metrics registry + "
                        "structured JSONL event log + post-mortem capture "
                        "(off = the exact pre-obs loop, bit-identical)")
    both("obs-log", type=str, default=os.environ.get("BNSGCN_OBS_LOG", ""),
         help="structured JSONL event log path (rank-tagged; ranks > 0 "
              "write PATH.r<rank>; size-bounded, $BNSGCN_OBS_MAX_MB)")
    both("obs-dir", type=str, default="",
         help="post-mortem dir for watchdog/divergence dumps and SIGUSR1 "
              "snapshots (default {ckpt_path}/postmortem)")
    both("strict-exec", action="store_true", default=False,
         help="strict-execution runtime guard: transfer_guard('disallow') "
              "around every hot-loop step plus a compile listener — any "
              "implicit host transfer in the step, or any recompile after "
              "a step variant's first epoch, aborts the run "
              "(StrictExecError). Pairs with the graftlint static gate")
    both("cache-dir", type=str,
         default=os.environ.get("BNSGCN_CACHE_DIR", ""))
    both("edge-chunk", type=int, default=0)
    # no Config field: config_from_args drops it
    both("use-pallas", action="store_true", default=False,
         help="no-op: the dense tiles run the Pallas kernel on every TPU "
              "run (ops/block_spmm.dense_path); accepted until the "
              "benchmark stops passing it")
    both("spmm-gather", type=str, default="native", choices=["native", "fp8", "int8"])
    both("spmm-dense", type=str, default="native", choices=["native", "int8"])
    both("block-occupancy", type=int, default=0)
    both("block-tile-budget-mb", type=int, default=4096)
    both("block-tile", type=int, default=512)
    p.add_argument("--reorder", type=str, default="off",
                   choices=["auto", "cluster", "off"],
                   help="graph-reordering artifact pass: permute each "
                        "part's rows once at load to concentrate edge mass "
                        "into dense MXU tiles (order cached like layouts; "
                        "outputs stay in global id order; 'auto' applies "
                        "only on measured coverage improvement; 'off' is "
                        "bit-identical)")
    both("ckpt-path", type=str, default="./checkpoint/")
    both("results-path", type=str, default="./results/")
    p.add_argument("--resume", action="store_true")
    both("keep-ckpt", type=int, default=5)
    both("n-nodes", type=int, default=1)
    return p


def config_from_args(args: argparse.Namespace) -> Config:
    d = vars(args).copy()
    if d.get("norm") == "none":
        d["norm"] = None
    valid = {f.name for f in dataclasses.fields(Config)}
    d = {k: v for k, v in d.items() if k in valid}
    return Config(**d)


def parse_config(argv=None) -> Config:
    return config_from_args(create_parser().parse_args(argv))
