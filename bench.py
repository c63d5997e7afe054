"""Flagship benchmark — prints JSON lines for the driver; LAST line wins.

One process, on the chip: `python bench.py` measures on a TPU backend or
exits non-zero before doing any work. It carries no number forward from
an earlier run, starts no probe or worker subprocess, and every result
line is stamped with the device it ran on (`platform`, `device_kind`,
`device_count`). A candidate that fails to build fails the run.

A provisional best-so-far JSON line is emitted as each SpMM candidate is
measured, so an outer timeout that kills the process mid-matrix still leaves
a valid, freshly measured result on stdout; consumers must parse the LAST
JSON line.

Workload: one rank's share of the reference's headline config (BASELINE.md /
reference scripts/reddit.sh: Reddit — 232,965 nodes, ~114.6M directed edges
(mean degree ~492), 602 features, 41 classes — GraphSAGE 4-layer hidden=256,
use_pp, BNS rate 0.1, P=2, 0.3578 s/epoch/rank on 2x NVIDIA >=11GB GPUs,
README.md:94-95). The real dataset is not downloadable here (zero egress), so
a synthetic power-law graph with the same shape statistics stands in:
scale x 232,965 nodes at the true ~492 mean degree (scale 0.5 = the P=2
per-rank node share, ~57M local edges).

vs_baseline = 0.3578 / measured_epoch_time (>1 == faster per chip than the
reference per GPU). Compute dtype defaults to bf16 — the TPU-native choice.
The v5e gather unit moves 512B rows at ~110 GB/s (the pure-ELL bound); the
hybrid block-dense SpMM routes clustered edge mass through the MXU instead,
and scale-out (BNS partition parallelism over the 'parts' mesh axis)
divides the rest.

Usage: python bench.py [--epochs N] [--scale S] [--avg-degree D]
                       [--dtype bf16|f32] [--json-only]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

BASELINE_EPOCH_S = 0.3578   # reference README.md:94 (rank 0, Reddit P=2 rate=0.1)

# versioned-pickle cache helpers shared with the trainer's --cache-dir
# layout persistence (bnsgcn_tpu/utils/diskcache.py)
from bnsgcn_tpu.utils.diskcache import (atomic_dump as _atomic_dump,
                                        disk_cached as _disk_cached,
                                        try_load as _try_load)


def _workload_tag(args) -> str:
    tag = f"{args.graph}_{args.scale:g}_{args.avg_degree}"
    # non-flagship models get their own tag (a GAT epoch time must never be
    # read as a GraphSAGE one in results_log.jsonl / the obs log)
    if args.model != "graphsage":
        tag += f"_{args.model}"
    return tag


def _metric_name(args) -> str:
    """Driver-parsed metric id. The flagship GraphSAGE workload keeps the
    historical name; other models get their own. vs_baseline is only emitted for the flagship — the reference
    publishes no in-repo GAT epoch time to normalize against
    (README.md:94-95 is the GraphSAGE run)."""
    if args.model == "graphsage":
        return "reddit_rank_share_epoch_time_per_chip"
    return f"reddit_{args.model}_rank_share_epoch_time_per_chip"


def _vhalo(v):
    """Halo-exchange strategy of a variant tuple. Variants grew a 5th field
    for the ragged exchange; 4-tuples (every pre-existing name) mean
    'padded', so every older name stays valid."""
    return v[4] if len(v) > 4 else "padded"


def _vovl(v):
    """Overlap mode of a variant tuple (6th field, PR 2's interior/frontier
    split aggregation); shorter tuples mean 'off' — pre-existing names
    stay valid."""
    return v[5] if len(v) > 5 else "off"


def _vrep(v):
    """Replica-axis size of a variant tuple (7th field: the 2-D
    ('replicas','parts') mesh of parallel/replicas.py — N independently-
    BNS-sampled graph replicas, fused cross-replica gradient mean); shorter
    tuples mean 1 — pre-existing names stay valid."""
    return v[6] if len(v) > 6 else 1


def _vfeat(v):
    """Feat-axis size of a variant tuple (8th field: parallel/feat.py's
    tensor axis — hidden dimensions sharded T-ways, H/T halo payloads, one
    feat psum per layer); shorter tuples mean 1 — pre-existing names
    stay valid."""
    return v[7] if len(v) > 7 else 1


def _vhr(v):
    """Halo-refresh period K of a variant tuple (9th field: the staleness-
    bounded cached-halo reuse of parallel/halo.py — epoch 0 pays the full
    exchange, steady-state epochs redraw only chunk epoch%K, ~1/K the wire
    bytes); shorter tuples mean 1 — pre-existing names stay
    valid."""
    return v[8] if len(v) > 8 else 1


def _vro(v):
    """Reorder mode of a variant tuple (10th field: the data/reorder
    LPA+FFD artifact permutation, --reorder; 'cluster' bakes the
    tile-coverage-maximizing row order into the artifact before layouts
    build); shorter tuples mean 'off' — pre-existing names
    stay valid."""
    return v[9] if len(v) > 9 else "off"


def _vat(v):
    """Auto-tune flag of a variant tuple (11th field: 'sched' runs the
    fixed coarse->fine staleness anneal of tune.bench_schedule — K=4 from
    epoch 0, K=2 at 40%, K=1 at 70% — with each retune's rebuild + compile
    epochs excluded from the mean, the bench twin of run.py's `--tune`);
    shorter tuples mean 'off' — pre-existing names stay
    valid."""
    return v[10] if len(v) > 10 else "off"


def _vname(v):
    """Candidate display/CLI name for a (spmm, gather_dtype, dense_dtype,
    tile[, halo[, overlap[, replicas[, feat[, refresh[,
    reorder[, autotune]]]]]]]) variant tuple — the vocabulary --candidates
    is written in."""
    return (v[0] + ({"fp8": "+f8g", "int8": "+i8g"}.get(v[1], ""))
            + ("+i8d" if v[2] == "int8" else "")
            + (f"+t{v[3]}" if v[3] != 512 else "")
            + ({"ragged": "+rag", "shift": "+shift"}.get(_vhalo(v), ""))
            + ("+ovl" if _vovl(v) == "split" else "")
            + (f"+rep{_vrep(v)}" if _vrep(v) != 1 else "")
            + (f"+feat{_vfeat(v)}" if _vfeat(v) != 1 else "")
            + (f"+hr{_vhr(v)}" if _vhr(v) != 1 else "")
            + ("+ro" if _vro(v) != "off" else "")
            + ("+at" if _vat(v) != "off" else ""))


def _device_stamp() -> dict:
    """The device the numbers were taken on, as JAX reports it — merged into
    every result line so no number can be read without its chip."""
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "device_kind": devs[0].device_kind,
            "device_count": len(devs)}


def _features(label: np.ndarray, n_feat=602, n_class=41) -> np.ndarray:
    """Label-correlated features from a dedicated RNG stream — identical on
    cold and warm runs (the cache stores only edges/labels/masks)."""
    rng = np.random.default_rng(1234)
    centers = rng.normal(size=(n_class, n_feat)).astype(np.float32)
    return (centers[label] + rng.normal(
        scale=1.0, size=(label.shape[0], n_feat))).astype(np.float32)


def _cached_graph(n_nodes: int, avg_degree: int, cache_dir: str, log,
                  kind: str = "uniform"):
    """Synthetic graph with npz edge cache (generation dominates cold runs).

    kind='dcsbm': Reddit-calibrated degree-corrected SBM (41 communities,
    power-law degrees, edge homophily 0.78 — see
    data/graph.reddit_like_graph); 'uniform': the structure-free power-law
    graph (round-1 stand-in, kept as the no-locality worst case);
    'dcsbm-mid': the same SBM at homophily 0.45 — calibrated to put hybrid
    tile coverage in the 30-50%% band where --spmm auto's 0.5 threshold
    decides, so the flip point gets a measured point between the clustered
    (78.5%%) and uniform (21%%) extremes."""
    from bnsgcn_tpu.data.graph import Graph, reddit_like_graph, synthetic_graph
    os.makedirs(cache_dir, exist_ok=True)
    tag = {"uniform": "synth", "dcsbm": "dcsbm",
           "dcsbm-mid": "dcsbmmid"}[kind]
    path = os.path.join(cache_dir, f"{tag}_{n_nodes}_{avg_degree}.npz")
    if os.path.exists(path):
        log(f"loading cached graph {path}")
        z = np.load(path)
        label = z["label"].astype(np.int64)
        return Graph(n_nodes, z["src"].astype(np.int64), z["dst"].astype(np.int64),
                     _features(label), label, z["train"], z["val"], z["test"])
    t0 = time.time()
    if kind == "uniform":
        g = synthetic_graph(n_nodes=n_nodes, avg_degree=avg_degree, n_feat=602,
                            n_class=41, seed=0, power_law=True)
    elif kind == "dcsbm-mid":
        g = reddit_like_graph(n_nodes=n_nodes, avg_degree=avg_degree,
                              n_feat=8, seed=0, homophily=0.45)
    else:
        g = reddit_like_graph(n_nodes=n_nodes, avg_degree=avg_degree,
                              n_feat=8, seed=0)
    g.feat = _features(g.label)
    log(f"  graph generated in {time.time() - t0:.1f}s: {g.n_edges} edges")
    np.savez(path, src=g.src.astype(np.int32), dst=g.dst.astype(np.int32),
             label=g.label.astype(np.int32),
             train=g.train_mask, val=g.val_mask, test=g.test_mask)
    return g


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--epochs", type=int, default=8)
    ap.add_argument("--scale", type=float, default=0.5,
                    help="fraction of Reddit's 232,965 nodes per chip (0.5 = rank share at P=2)")
    ap.add_argument("--avg-degree", type=int, default=492,
                    help="mean degree (Reddit: 114.6M edges / 233k nodes ~= 492)")
    ap.add_argument("--hidden", type=int, default=256)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--dtype", choices=["f32", "bf16"], default="bf16")
    ap.add_argument("--graph", choices=["dcsbm", "uniform", "dcsbm-mid"],
                    default="dcsbm",
                    help="dcsbm: Reddit-calibrated clustered stand-in "
                         "(default); uniform: structure-free worst case")
    ap.add_argument("--spmm", choices=["hybrid", "ell"], default="hybrid")
    ap.add_argument("--model", choices=["graphsage", "gat"],
                    default="graphsage",
                    help="gat: 2-head ELL-attention GAT on the same graph "
                         "(reference module/model.py:102-132; measures the "
                         "edge-softmax hot loop, which no SpMM variant "
                         "touches — candidates collapse to the anchor)")
    ap.add_argument("--occupancy", type=int, default=0,
                    help="hybrid: min edges per tile to densify "
                         "(0 = auto: the tile's time break-even, half "
                         "the tile's edge — 256 for 512x512, 128 for +t256)")
    ap.add_argument("--tile-budget-mb", type=int, default=4096,
                    help="hybrid: device bytes of the one int8 dense-tile "
                         "stack both directions read")
    ap.add_argument("--cache-dir", type=str,
                    default=os.environ.get("BNSGCN_CACHE_DIR")
                    or "./bench_cache",
                    help="graph/artifact/layout cache dir (default "
                         "$BNSGCN_CACHE_DIR or ./bench_cache). Not the XLA "
                         "compile cache: that is placed by "
                         "utils/platform.place_compile_cache")
    ap.add_argument("--profile-dir", type=str, default="",
                    help="diagnostic: write a jax.profiler trace of each "
                         "measured candidate's first epoch chunk to "
                         "<dir>/<candidate>/ (parse with tools/trace_comm.py "
                         "--parse --breakdown). Traced result lines carry "
                         "status=profiled-diagnostic")
    ap.add_argument("--json-only", action="store_true")
    ap.add_argument("--prep-only", action="store_true",
                    help="build + disk-cache artifacts and SpMM layouts, "
                         "then exit without measuring or printing a result "
                         "(host numpy only: run it under JAX_PLATFORMS=cpu "
                         "to keep it off the chip)")
    ap.add_argument("--budget-s", type=float, default=1500.0,
                    help="soft wall-clock budget: skip remaining SpMM "
                         "candidates once exceeded (the JSON line always "
                         "reports the best measured so far)")
    ap.add_argument("--candidates", type=str, default="",
                    help="comma list restricting/ordering the SpMM variants "
                         "to measure after the ell anchor (names as logged: "
                         "hybrid, hybrid+i8g, hybrid+i8d, hybrid+i8g+i8d, "
                         "hybrid+f8g+i8d, hybrid+f8g, ell+i8g, ell+f8g; "
                         "a +rag suffix runs the same recipe under the "
                         "exact-bytes ragged halo exchange: hybrid+rag, "
                         "ell+rag; a +ovl suffix runs it with --overlap "
                         "split interior/frontier aggregation: hybrid+ovl, "
                         "ell+ovl, hybrid+rag+ovl; a +repN suffix runs it "
                         "on an (N, 1) replica mesh — N independently-"
                         "BNS-sampled replicas, fused cross-replica "
                         "gradient mean, needs N devices: hybrid+rep2, "
                         "ell+rep2, hybrid+rag+ovl+rep2; a +featT suffix "
                         "shards hidden dims T-ways on the innermost feat "
                         "axis — H/T halo payloads, one psum per layer, "
                         "needs T devices: hybrid+feat2, ell+feat2, "
                         "hybrid+rag+ovl+feat2; a +hrK suffix reuses "
                         "cached halos for up to K epochs (--halo-refresh "
                         "K staleness-bounded refresh, ~1/K steady-state "
                         "wire bytes): hybrid+hr2, hybrid+hr4, "
                         "hybrid+rag+ovl+hr4; a +ro suffix bakes the "
                         "--reorder cluster LPA+FFD row permutation into "
                         "the artifact before layouts build — higher "
                         "dense-tile coverage on low-locality graphs: "
                         "hybrid+ro, hybrid+t256+ro; a +at suffix runs the "
                         "closed-loop staleness anneal (tune.bench_schedule"
                         ": K=4 from epoch 0, K=2 at 40%%, K=1 at 70%%, "
                         "retune rebuilds untimed): hybrid+at; the dense "
                         "tiles run the Pallas kernel on a TPU, and an "
                         "older name's +pallas is dropped)"
                         ". An unknown name, or one that needs more "
                         "devices than the host has, is an error (exit 2) "
                         "before any work")
    ap.add_argument("--obs-log", type=str,
                    default=os.environ.get("BNSGCN_OBS_LOG", ""),
                    help="obs telemetry JSONL (bnsgcn_tpu/obs.py): one "
                         "bench header + one bench_variant event per gated "
                         "measurement, and every result JSON carries the "
                         "log's path — two runs compare with "
                         "tools/obs_report.py --compare")
    args = ap.parse_args()
    t_start = time.time()

    import jax

    from bnsgcn_tpu.utils.platform import place_compile_cache

    if not args.prep_only and jax.default_backend() != "tpu":
        # no chip, no number: a CPU epoch time under this metric's name
        # would be read as a device measurement
        print(f"bench.py measures on a TPU and found none: JAX's default "
              f"backend here is {jax.default_backend()!r} "
              f"({jax.devices()[0].device_kind}). No result printed.",
              file=sys.stderr)
        sys.exit(1)
    cc_dir = place_compile_cache()
    import jax.numpy as jnp

    from bnsgcn_tpu.config import Config
    from bnsgcn_tpu.data.artifacts import build_artifacts
    from bnsgcn_tpu.data.partitioner import partition_graph
    from bnsgcn_tpu.models.gnn import ModelSpec, init_params
    from bnsgcn_tpu.parallel.mesh import make_parts_mesh
    from bnsgcn_tpu.parallel.replicas import make_mesh
    from bnsgcn_tpu.trainer import (build_block_arrays, build_step_fns,
                                    init_training, place_blocks, place_replicated)

    log = (lambda *a: None) if args.json_only else (lambda *a: print(*a, file=sys.stderr))
    log(f"compile cache: {cc_dir}")

    # ell runs FIRST as the trusted reference; other variants must agree
    # with its FIRST-step loss (guards a silently-miscompiling kernel from
    # ever winning the headline; step-0 comparison keeps legitimately-lossy
    # variants like fp8 gathers from accumulating drift over --epochs)
    # main contenders first so a tight budget still measures them; the
    # universe is independent of --spmm so --candidates can always select
    # from the full documented name set. Candidate validation runs HERE,
    # before graph generation + artifact build, so a --candidates typo
    # exits in seconds instead of burning minutes of cold prep first.
    # variant = (spmm, gather_dtype, dense_dtype, tile).
    # MEASURED WINNERS FIRST (v5e 2026-07-30: hybrid+pallas 0.573 s/epoch,
    # hybrid 0.87, ell 1.67, i8g/f8g reduce-path variants lose) so a
    # budget-starved run still measures the best known before exploring.
    universe = [("hybrid", "native", "native", 512),
                 # finer tiles: 4x tiles/budget-byte, less ELL residual
                 ("hybrid", "native", "native", 256),
                 # fused Pallas dense + 1-byte int8-unroll residual rows
                 ("hybrid", "int8", "native", 512),
                 ("hybrid", "int8", "native", 256),
                 # int8 slabs inside the fused kernel (int8 MXU, one
                 # per-call scale) — alone and with int8 residual rows
                 ("hybrid", "native", "int8", 512),
                 ("hybrid", "int8", "int8", 512),
                 # the full-lever endgame: finer tiles + int8 residual
                 # rows + int8 slabs
                 ("hybrid", "int8", "int8", 256)]
    # exact-bytes ragged halo exchange under the headline recipe: on the
    # single bench chip this measures the ragged collective's dispatch
    # cost inside the real train step (cross-chip bytes need a pod);
    # ragged_all_to_all lowered on a v5e at axis size 1 (2026-07-30)
    universe += [("hybrid", "native", "native", 512, "ragged"),
                 # interior/frontier split aggregation (--overlap split):
                 # a single bench chip measures the split-layout overhead
                 # (P=1 has zero frontier rows); the latency hiding
                 # itself needs several chips
                 ("hybrid", "native", "native", 512, "padded",
                  "split"),
                 ("hybrid", "native", "native", 512, "ragged",
                  "split"),
                 # replica-axis hybrid parallelism: 2 independently-
                 # BNS-sampled graph replicas on a (2, 1) mesh with the
                 # fused cross-replica gradient mean — needs >= 2 chips
                 # (left out of a default run on a 1-chip host); measures
                 # the variance-reduction recipe's wall-clock cost
                 ("hybrid", "native", "native", 512, "padded",
                  "off", 2),
                 ("hybrid", "native", "native", 512, "ragged",
                  "split", 2),
                 # feat/tensor axis (parallel/feat.py): hidden dims
                 # sharded 2-ways on a (1, 1, 2) mesh — measures the
                 # per-layer feat-psum + sliced-SpMM recipe on 2 chips
                 # (the T x halo-byte win itself needs a multi-part pod);
                 # wide-hidden (--hidden 512) is where it should win
                 ("hybrid", "native", "native", 512, "padded",
                  "off", 1, 2),
                 ("hybrid", "native", "native", 512, "ragged",
                  "split", 1, 2),
                 # staleness-bounded halo refresh (--halo-refresh K):
                 # steady-state epochs redraw only chunk epoch%K of each
                 # boundary set and reuse the cached rows elsewhere. On
                 # the single bench chip this measures the cached step's
                 # compute cost (plan + where-combine overhead); the
                 # ~K x wire-byte win itself needs a multi-part pod
                 ("hybrid", "native", "native", 512, "padded",
                  "off", 1, 1, 2),
                 ("hybrid", "native", "native", 512, "padded",
                  "off", 1, 1, 4),
                 ("hybrid", "native", "native", 512, "ragged",
                  "split", 1, 1, 4),
                 # graph reordering (--reorder cluster): the LPA+FFD
                 # artifact permutation raises dense-tile coverage
                 # before layouts build — the uniform/dcsbm-mid graphs
                 # are the headline targets
                 ("hybrid", "native", "native", 512, "padded",
                  "off", 1, 1, 1, "cluster"),
                 ("hybrid", "native", "native", 256, "padded",
                  "off", 1, 1, 1, "cluster"),
                 # closed-loop staleness anneal (--tune / tune.py): the
                 # fixed coarse->fine schedule K=4 -> 2 -> 1 with each
                 # retune's rebuild+compile epochs untimed — measures
                 # what a tuned run's STEADY epochs cost vs the static
                 # +hrK points on either side of the anneal
                 ("hybrid", "native", "native", 512, "padded",
                  "off", 1, 1, 1, "off", "sched")]
    # the dense tiles run the Pallas kernel on every TPU run (PR 40:
    # block_spmm.dense_path), so the XLA-dense twins of the entries above
    # are gone; what is left has no kernel-path sibling
    universe += [("hybrid", "fp8", "int8", 512),
                 ("hybrid", "fp8", "native", 512),
                 ("ell", "int8", "native", 512),
                 ("ell", "fp8", "native", 512),
                 ("ell", "native", "native", 512, "ragged"),
                 ("ell", "native", "native", 512, "padded", "split"),
                 ("ell", "native", "native", 512, "padded", "off", 2),
                 ("ell", "native", "native", 512, "padded",
                  "off", 1, 2)]
    anchor = ("ell", "native", "native", 512)
    n_dev = len(jax.devices())

    def fits(v):
        """+repN/+featT candidates compile onto an (N, 1, T) mesh."""
        return _vrep(v) * _vfeat(v) <= n_dev

    if args.candidates:
        # a NAMED candidate runs or the run fails: an unknown name, or one
        # this host has too few devices for, is an argument error (exit 2)
        # before graph generation — never a silently shorter run
        by_name = {_vname(v): v for v in universe}
        # a '+pallas' in an older name is dropped: every TPU hybrid run
        # takes the kernel now (PR 40), so the name it kept apart is gone
        names = [nm.strip().replace("+pallas", "")
                 for nm in args.candidates.split(",") if nm.strip()]
        unknown = [nm for nm in names if nm not in by_name]
        too_wide = [nm for nm in names
                    if nm in by_name and not fits(by_name[nm])]
        if unknown or too_wide or not names:
            print(f"  --candidates {args.candidates!r}: "
                  + (f"unknown {unknown} (known: {sorted(by_name)}) "
                     if unknown or not names else "")
                  + (f"need more than this host's {n_dev} device(s): "
                     f"{too_wide}" if too_wide else ""), file=sys.stderr)
            sys.exit(2)
        candidates = [anchor] + [by_name[nm] for nm in names]
    elif args.spmm == "hybrid":
        # the default matrix adapts to the device count it can observe
        wide = [_vname(v) for v in universe if not fits(v)]
        if wide:
            log(f"  {n_dev} device(s): leaving out {wide}")
        candidates = [anchor] + [v for v in universe if fits(v)]
    else:
        candidates = [(args.spmm, "native", "native", 512)]

    if args.model == "gat":
        # GAT's hot loop is the dense per-row ELL attention (edge softmax +
        # weighted combine), which no SpMM candidate touches — the matrix
        # collapses to the single anchor-shaped run and the measurement IS
        # the GAT epoch time (reference module/model.py:102-132; BNS note
        # train.py:117: GAT halos ride ratio=1)
        if args.candidates:
            log("  --model gat ignores --candidates (SpMM variants do not "
                "apply to the attention path)")
        candidates = [anchor]
    n_nodes = max(int(232_965 * args.scale), 2000)
    model_desc = ("GAT(2 heads)" if args.model == "gat" else "GraphSAGE")
    log(f"workload: {n_nodes} nodes x mean degree {args.avg_degree} "
        f"(~{n_nodes * args.avg_degree / 1e6:.1f}M edges/chip), "
        f"{model_desc} {args.layers}x{args.hidden}, pp, dtype={args.dtype}, "
        f"graph={args.graph}, spmm={args.spmm}")
    g = _cached_graph(n_nodes, args.avg_degree, args.cache_dir, log,
                      kind=args.graph)

    t0 = time.time()
    tag = f"{args.graph}_{n_nodes}_{args.avg_degree}"
    art = _disk_cached(
        os.path.join(args.cache_dir, f"art_{tag}.pkl"),
        lambda: build_artifacts(g, partition_graph(g, 1)), log)
    log(f"  artifacts in {time.time() - t0:.1f}s")
    sizes = (art.n_feat,) + (args.hidden,) * (args.layers - 1) + (art.n_class,)
    spec = ModelSpec(args.model, sizes, norm="layer", dropout=0.5,
                     use_pp=True, train_size=art.n_train,
                     heads=2 if args.model == "gat" else 1)
    mesh = make_parts_mesh(1)
    dtype = jnp.bfloat16 if args.dtype == "bf16" else jnp.float32
    # graftlint: disable=prng-literal-key(fixed bench keys: every variant times the same sample stream)
    skey, dkey = jax.random.key(0), jax.random.key(1)

    def make_cfg(variant):
        spmm, gather, dense, tile = variant[:4]
        cfg = Config(model=args.model,
                      halo_exchange=_vhalo(variant),
                      overlap=_vovl(variant),
                      replicas=_vrep(variant),
                      feat=_vfeat(variant),
                      halo_refresh=_vhr(variant),
                      reorder=_vro(variant),
                      heads=2 if args.model == "gat" else 1,
                      n_layers=args.layers,
                      n_hidden=args.hidden, use_pp=True, dropout=0.5,
                      lr=0.01, sampling_rate=0.1, spmm=spmm,
                      spmm_gather=gather,
                      spmm_dense=dense,
                      block_occupancy=args.occupancy,
                      block_tile_budget_mb=args.tile_budget_mb,
                      block_tile=tile,
                      n_feat=art.n_feat, n_class=art.n_class,
                      n_train=art.n_train)
        if _vat(variant) != "off":
            # +at starts at the anneal's epoch-0 point (K=4) so the first
            # compile already targets the coarse geometry — never a
            # throwaway build, exactly run.py's startup fold
            from bnsgcn_tpu import tune as tune_mod
            for _ep, _ch in tune_mod.bench_schedule(args.epochs):
                if _ep == 0:
                    cfg = cfg.replace(**_ch)
        return cfg

    # graftperf (analysis/perf) predictions per candidate name — filled by
    # setup_and_compile from the BUILT layout, joined to the measurement
    # in the gated loop below so every bench record doubles as
    # calibration data (predicted_step_s / predicted_wire_mb + the
    # residual log line)
    perf_pred = {}

    # +ro candidates run on the PERMUTED artifact (what run.py's
    # maybe_reorder produces) — the perm depends on the tile size, so
    # memoize one reordered artifact per tile value across candidates
    ro_arts = {}

    def art_for(variant):
        if _vro(variant) == "off":
            return art
        tile = variant[3]
        if tile not in ro_arts:
            from bnsgcn_tpu.data.reorder import apply_reorder, compute_orders
            t0 = time.time()
            ro_arts[tile] = apply_reorder(art, compute_orders(art,
                                                              tile_r=tile))
            log(f"  reorder: t{tile} order built in {time.time() - t0:.1f}s")
        return ro_arts[tile]

    def setup_and_compile(variant):
        """Layouts + device data + the first (compiling) train step. A
        failure here — a kernel that does not compile, an OOM — raises and
        fails the run."""
        t0 = time.time()
        spmm = variant[0]
        cfg = make_cfg(variant)
        v_art = art_for(variant)
        # +repN/+featT candidates compile onto their own (N, 1, T) mesh; the
        # layout cache is mesh-independent so the stacks are still shared
        mesh = make_mesh(1, _vrep(variant), _vfeat(variant))
        fns, hspec, tables, tables_full = build_step_fns(
            cfg, spec, v_art, mesh, layout_cache=layout_cache)
        log(f"  spmm {fns.spmm_desc}")
        log(f"  {spmm} layouts in {time.time() - t0:.1f}s")
        # roofline prediction from the layout that actually built (tile
        # stacks, ELL geometry, halo geometry), priced with the table
        # calibrated for THIS device kind; a kind nobody calibrated gets
        # no prediction (and the log says so), never another chip's
        from bnsgcn_tpu.analysis.perf import calibration as pcal
        from bnsgcn_tpu.analysis.perf import model as pmod
        try:
            table = pcal.backend_table(pcal.load_calibration(),
                                       jax.devices()[0].device_kind)
        except KeyError as ex:
            table = None
            log(f"  [perf] no prediction for {_vname(variant)}: {ex.args[0]}")
        if table is not None:
            nbytes = 2 if cfg.dtype == "bfloat16" else 4
            gb = {"int8": 1, "fp8": 1}.get(variant[1], nbytes)
            slots_full = 0.0
            tiles = 0
            if v_art.ell_geometry:
                slots_full = 0.5 * (
                    pmod.ell_geometry_slots(v_art.ell_geometry, "fwd")
                    + pmod.ell_geometry_slots(v_art.ell_geometry, "bwd"))
            fill = (v_art.pad_edges / slots_full) if slots_full else 0.74
            if spmm == "hybrid":
                from bnsgcn_tpu.ops.block_spmm import dense_edge_count
                dcov = dense_edge_count(fns.extra_blk) / max(g.n_edges, 1)
                for tkey in ("blk_tiles_fwd", "int_blk_tiles_fwd",
                             "fro_blk_tiles_fwd"):
                    t_arr = fns.extra_blk.get(tkey)
                    if t_arr is not None:
                        tiles += int(np.asarray(t_arr).shape[1])
                slots = (v_art.pad_edges * max(1.0 - dcov, 0.0)
                         / max(fill, 1e-9))
            else:
                slots = slots_full or float(v_art.pad_edges)
            width = max(cfg.n_hidden // max(_vfeat(variant), 1), 1)
            wire_mb = pmod.steady_wire_mb(
                v_art.n_b, v_art.pad_boundary, cfg.sampling_rate,
                strategy=_vhalo(variant), wire="native",
                refresh=_vhr(variant), width=width,
                native_bytes=nbytes) * 2 * max(cfg.n_layers - 1, 1)
            feat_p = pmod.StepFeatures(
                n_apps=2 * int(cfg.n_layers), gather_slots=float(slots),
                row_bytes=int(cfg.n_hidden) * gb,
                gather_path="materialize",
                dense_tiles=tiles, tile=int(variant[3]),
                dense_path=(fns.spmm_counts["dense_path_fwd"]
                            if tiles else "none"),
                wire_mb=wire_mb)
            perf_pred[_vname(variant)] = {
                "predicted_step_s": round(
                    pmod.predict_step_s(feat_p, table), 4),
                "predicted_wire_mb": round(wire_mb, 4)}
        blk_np = build_block_arrays(v_art, spec.model)
        blk_np.update(fns.extra_blk)
        for k in fns.drop_blk_keys:
            blk_np.pop(k, None)
        blk = place_blocks(blk_np, mesh)
        tables_d = place_replicated(tables, mesh)
        pp_out = fns.precompute(
            blk, place_replicated(tables_full, mesh)).astype(dtype)
        if args.model == "gat":
            # GAT keeps raw features (cast to the compute dtype like
            # run.py:173 — an f32 blk['feat'] would silently measure 2x
            # the layer-0 feature HBM) and caches the full-rate extended
            # feature slab for the attention source side (run.py:177-181)
            blk["feat"] = blk["feat"].astype(dtype)
            blk["feat0_ext"] = pp_out
        else:
            blk["feat"] = pp_out
        # graftlint: disable=prng-literal-key(fixed seed: bench variants must share identical params)
        params, state = init_params(jax.random.key(0), spec, dtype=dtype)
        if _vfeat(variant) > 1:
            # feat-sharded weights (parallel/feat.py regex rules); the init
            # itself is the same host tree, so losses stay gate-comparable
            from bnsgcn_tpu.parallel import feat as feat_mod
            params = feat_mod.place_params(params, mesh, spec)
        else:
            params = place_replicated(params, mesh)
        state = place_replicated(state, mesh)
        _, _, opt = init_training(cfg, spec, mesh)
        log("compiling + warmup...")
        t0 = time.time()
        cache, tables_r_d = None, None
        if fns.train_step_full is not None:
            # +hrK: epoch 0 is the full-refresh step (historical exchange
            # geometry, same loss as fns.train_step — the step-0 gate below
            # stays meaningful) and seeds the halo cache the measured
            # steady-state epochs reuse
            tables_r_d = place_replicated(fns.tables_refresh, mesh)
            params, state, opt, loss, cache = fns.train_step_full(
                params, state, opt, jnp.uint32(0), blk, tables_d, skey, dkey)
        else:
            params, state, opt, loss = fns.train_step(
                params, state, opt, jnp.uint32(0), blk, tables_d, skey, dkey)
        log(f"  first step (compile) {time.time() - t0:.1f}s, "
            f"loss={float(loss):.4f}")
        ctx = {"cfg": cfg}

        def rebuild(changes):
            """+at retune: rebuild the step fns under changed comm levers.
            The shared layout cache absorbs the SpMM layout work (its keys
            do not depend on any tuned lever), so a retune costs one
            build + one compile — the same contract as run.py's --tune."""
            ctx["cfg"] = ctx["cfg"].replace(**changes)
            f2, _h2, tb2, tbf2 = build_step_fns(
                ctx["cfg"], spec, v_art, mesh, layout_cache=layout_cache)
            tr2 = (place_replicated(f2.tables_refresh, mesh)
                   if f2.tables_refresh is not None else None)
            return f2, place_replicated(tb2, mesh), tr2

        return (fns, blk, tables_d, params, state, opt, loss, cache,
                tables_r_d, rebuild)

    def measure(built, name="run", at_sched=None):
        """Timed epochs; chains CHUNK epochs between host syncs (matches the
        reference's free-running epoch loop). Under --profile-dir the FIRST
        chunk is traced (device-lane op breakdown); its timing includes
        profiler overhead, which is why traced result lines are tagged
        profiled-diagnostic.
        `at_sched` ({epoch: lever changes}, +at candidates only) retunes
        the comm stack mid-run: the rebuild and its compile epochs are
        REAL training steps (the loss trajectory continues through them)
        but run untimed, the same compile-exclusion every other candidate
        gets for its first step."""
        (fns, blk, tables_d, params, state, opt, loss, cache,
         tables_r, rebuild) = built
        use_refresh = cache is not None
        at_sched = dict(at_sched or {})
        CHUNK = 4
        total_t, min_t = 0.0, float("inf")
        timed_n = 0
        e = 1

        def _untimed_cached():
            # the steady-state (cached) step compiles on ITS first call —
            # run it once untimed so +hrK/+at candidates get the same
            # compile-excluded treatment as everyone else (whose only
            # compile happened in setup_and_compile)
            nonlocal params, state, opt, loss, cache, e
            params, state, opt, loss, cache = fns.train_step_cached(
                params, state, opt, jnp.uint32(e), blk, tables_r, cache,
                skey, dkey)
            _ = float(loss)
            e += 1

        if use_refresh:
            _untimed_cached()
        tracing = False
        if args.profile_dir:
            jax.profiler.start_trace(os.path.join(
                args.profile_dir, name.replace("+", "_")))
            tracing = True
        try:
            while e <= args.epochs:
                due = sorted(ep for ep in at_sched if ep <= e)
                if due:
                    # +at retune boundary: fold every due entry, rebuild,
                    # and pay the full-refresh + compile epochs untimed
                    changes = {}
                    for ep in due:
                        changes.update(at_sched.pop(ep))
                    log("  at: epoch %d retune -> %s" % (e, " ".join(
                        f"{k}={v}" for k, v in sorted(changes.items()))))
                    fns, tables_d, tables_r = rebuild(changes)
                    use_refresh = fns.train_step_full is not None
                    if use_refresh:
                        params, state, opt, loss, cache = fns.train_step_full(
                            params, state, opt, jnp.uint32(e), blk, tables_d,
                            skey, dkey)
                        _ = float(loss)
                        e += 1
                        if e <= args.epochs:
                            _untimed_cached()
                    else:
                        cache = None
                        params, state, opt, loss = fns.train_step(
                            params, state, opt, jnp.uint32(e), blk, tables_d,
                            skey, dkey)
                        _ = float(loss)
                        e += 1
                    continue
                n = min(CHUNK, args.epochs - e + 1)
                nxt = min((ep for ep in at_sched), default=None)
                if nxt is not None and nxt > e:
                    # never time across a retune boundary
                    n = min(n, nxt - e)
                t0 = time.perf_counter()
                for _ in range(n):
                    if use_refresh:
                        params, state, opt, loss, cache = \
                            fns.train_step_cached(
                                params, state, opt, jnp.uint32(e), blk,
                                tables_r, cache, skey, dkey)
                    else:
                        params, state, opt, loss = fns.train_step(
                            params, state, opt, jnp.uint32(e), blk, tables_d,
                            skey, dkey)
                    e += 1
                _ = float(loss)   # force device sync through the host read
                dt = time.perf_counter() - t0
                if tracing:
                    # after dt: trace serialization must not inflate the
                    # first chunk's timing (round-4 advisor finding)
                    jax.profiler.stop_trace()
                    tracing = False
                total_t += dt
                timed_n += n
                min_t = min(min_t, dt / n)
        finally:
            if tracing:           # exception mid-measure: never leak the
                jax.profiler.stop_trace()   # trace into the next candidate
        if timed_n == 0:
            # a tiny-epoch +at run (e.g. the preflight's --epochs 2
            # override) can spend EVERY epoch on retune/compile
            # boundaries; time one extra epoch so the result line always
            # carries a real measurement instead of dividing by zero
            t0 = time.perf_counter()
            if use_refresh:
                params, state, opt, loss, cache = fns.train_step_cached(
                    params, state, opt, jnp.uint32(e), blk, tables_r,
                    cache, skey, dkey)
            else:
                params, state, opt, loss = fns.train_step(
                    params, state, opt, jnp.uint32(e), blk, tables_d,
                    skey, dkey)
            _ = float(loss)
            total_t, timed_n = time.perf_counter() - t0, 1
        if min_t == float("inf"):     # --epochs 1 +hrK: warmup ate the run
            min_t = total_t / max(timed_n, 1)
        return total_t / max(timed_n, 1), min_t, loss

    best, ref_loss, ref_final = None, None, None
    # step-0 / final losses of the NATIVE (unquantized) run of each SpMM
    # base: quantized variants gate against their native twin at 5% — far
    # tighter than the old blanket 10%-vs-ell gate, which was wide enough
    # to let a miscompiled int8 kernel win the headline (round-2 advisor)
    native_l0, native_lf = {}, {}
    # share built layouts across candidates AND across runs (disk): keys
    # come from trainer.hybrid_layout_key so they cannot drift. The ell
    # layouts don't depend on the hybrid tuning knobs, so they get their
    # own file and survive occupancy/budget/tile sweeps; each hybrid
    # tiling geometry gets its own file (multi-GB stacks — one file per
    # key avoids rewriting every stack when one is added).
    from bnsgcn_tpu.trainer import ell_layout_key, hybrid_layout_key

    def variant_key(variant):
        return (ell_layout_key(make_cfg(variant))
                if variant[0] != "hybrid"
                else hybrid_layout_key(make_cfg(variant)))

    def hyb_path_for(variant):
        # the file is named by the key: tiling, layout format, ':ovl' and
        # ':ro' each get their own multi-GB file
        key = variant_key(variant).replace(":", "-")
        return os.path.join(args.cache_dir, f"layouts_{tag}_{key}.pkl")

    hyb_variants = {variant_key(v): v for v in candidates
                    if v[0] == "hybrid"}
    ell_path = os.path.join(args.cache_dir, f"layouts_ell_{tag}.pkl")
    ell_ovl_path = os.path.join(args.cache_dir, f"layouts_ell_ovl_{tag}.pkl")
    gat_path = os.path.join(args.cache_dir, f"layouts_gat_{tag}.pkl")
    layout_cache = _try_load(ell_path, log) or {}
    if any(variant_key(v) == "ell:ovl" for v in candidates):
        layout_cache.update(_try_load(ell_ovl_path, log) or {})
    if args.model == "gat":
        layout_cache.update(_try_load(gat_path, log) or {})
    for v in hyb_variants.values():
        layout_cache.update(_try_load(hyb_path_for(v), log) or {})
    if layout_cache:
        log(f"  layout cache: {sorted(layout_cache)}")
    lc_keys0 = set(layout_cache)

    def persist_layouts():
        nonlocal lc_keys0
        for key in set(layout_cache) - lc_keys0:
            path = (ell_path if key == "ell"
                    else ell_ovl_path if key == "ell:ovl"
                    else gat_path if key == "gat"
                    else hyb_path_for(hyb_variants[key]))
            _atomic_dump({key: layout_cache[key]}, path)
        lc_keys0 = set(layout_cache)
    if args.prep_only:
        for variant in candidates:
            # a GAT run caches under 'gat' (trainer's ELL-SpMM branch is
            # gcn/graphsage-only, so variant_key's 'ell' never appears)
            key = "gat" if args.model == "gat" else variant_key(variant)
            if key in layout_cache:     # fp8 twins share the same layouts
                continue
            t0 = time.time()
            build_step_fns(make_cfg(variant), spec, art_for(variant), mesh,
                           layout_cache=layout_cache)
            persist_layouts()
            log(f"  prep {_vname(variant)}: {time.time() - t0:.1f}s")
        log(f"prep-only done: {sorted(layout_cache)}")
        return

    # obs telemetry (bnsgcn_tpu/obs.py): one bench_header + one
    # bench_variant event per gated measurement — the trajectory record
    # tools/obs_report.py --compare diffs across hardware windows
    obs_ev = None
    # the audit pointer every result JSON carries — ONE definition so the
    # per-variant history and both RESULT lines can never disagree
    obs_extra = ({"obs_log": os.path.abspath(args.obs_log)}
                 if args.obs_log else {})
    if args.obs_log:
        from bnsgcn_tpu.obs import EventLog
        obs_ev = EventLog(args.obs_log)
        obs_ev.emit("bench_header", workload=_workload_tag(args),
                    model=args.model, epochs=args.epochs,
                    hidden=args.hidden, layers=args.layers,
                    dtype=args.dtype, graph=args.graph,
                    candidates=[_vname(v) for v in candidates])

    stamp = _device_stamp()

    def result_line(epoch_s):
        """The driver-parsed JSON object: value + the device it was taken
        on. A traced run's first chunk pays profiler overhead, so it is
        tagged and never reads as a clean measurement."""
        return {"metric": _metric_name(args),
                **({"status": "profiled-diagnostic"} if args.profile_dir
                   else {}),
                "value": round(epoch_s, 4), "unit": "s/epoch",
                **({"vs_baseline": round(BASELINE_EPOCH_S / epoch_s, 3)}
                   if args.model == "graphsage" else {}),
                **stamp, **obs_extra}

    for variant in candidates:
        name = _vname(variant)
        if best is not None and time.time() - t_start > args.budget_s:
            log(f"  budget {args.budget_s:.0f}s exceeded; skipping {name}")
            continue
        # no except around the build/measure: a candidate that fails to
        # compile or run fails the whole bench, it never drops out quietly
        try:
            built = setup_and_compile(variant)
        finally:
            persist_layouts()     # keep layouts even if compile failed
        l0 = float(built[6])      # first-step (forward-dominated) loss
        quantized = variant[1] != "native" or variant[2] == "int8"
        # multi-device variants (+repN replica mean, +featT psum-order
        # drift) are gated wider and must never become native twins —
        # 'base' strips their suffixes, so without this exclusion a
        # feat2 run's loss would silently gate its quantized siblings
        multi_dev = _vrep(variant) > 1 or _vfeat(variant) > 1
        # +hrK reuses up-to-(K-1)-epoch-stale halos BY DESIGN: its
        # trajectory legitimately drifts from the exact exchange, so it
        # rides the widened gate and never becomes a native twin either
        stale = _vhr(variant) > 1
        # +ro permutes rows: the forward is the same aggregation at
        # round-off distance, but the row-position-keyed dropout draws
        # land on different nodes — a differently-seeded sample of the
        # same estimator, exactly the +repN situation — so it rides the
        # widened gate and never becomes the native twin its raw-order
        # siblings gate against
        ro = _vro(variant) != "off"
        # +at anneals K mid-run: its trajectory carries the staleness
        # drift of every rung it visits, so it rides the widened gate
        # like +hrK and never becomes a native twin
        at = _vat(variant) != "off"
        base = variant[0]
        # quantized variants gate against their NATIVE TWIN (same SpMM
        # base, native gathers/tiles) at 5%: the twin isolates exactly
        # the quantizers' legitimate loss. Only when the twin wasn't
        # measured (a --candidates pick) fall back to the ell anchor,
        # slightly widened for the ell-vs-hybrid tiling difference.
        # +repN losses are the MEAN over N independent BNS/dropout draws
        # — a different (lower-variance, but differently-seeded) sample
        # of the same estimator — so they get the widened gate too
        # (+featT only reorders float sums, but shares the exclusion).
        if quantized and base in native_l0:
            gate0, tol0, gsrc = native_l0[base], 0.05, f"native {base}"
        elif quantized or multi_dev or stale or ro or at:
            gate0, tol0, gsrc = ref_loss, 0.07, "ell anchor"
        else:
            gate0, tol0, gsrc = ref_loss, 0.02, "ell anchor"
        if (gate0 is not None
                and not (abs(l0 - gate0) <= tol0 * abs(gate0) + 1e-3)):
            log(f"  spmm={name} step-0 loss {l0:.4f} != {gsrc} "
                f"{gate0:.4f} (tol {tol0:.0%}); DISCARDED")
            continue
        at_sched = None
        if at:
            from bnsgcn_tpu import tune as tune_mod
            at_sched = {ep: ch for ep, ch in
                        tune_mod.bench_schedule(args.epochs) if ep > 0}
        et, mt, loss = measure(built, name, at_sched)
        lf = float(loss)
        if ref_loss is None:
            ref_loss, ref_final = l0, lf
        # end-of-run gate exercises the BACKWARD too (a miscompiled gradient
        # diverges the trajectory); same twin-first gating as step 0
        if quantized and base in native_lf:
            gate_f, tol, gsrc = native_lf[base], 0.05, f"native {base}"
        elif quantized or multi_dev or stale or ro or at:
            gate_f, tol, gsrc = ref_final, 0.07, "ell anchor"
        else:
            gate_f, tol, gsrc = ref_final, 0.02, "ell anchor"
        if not (abs(lf - gate_f) <= tol * abs(gate_f) + 1e-3):
            log(f"  spmm={name} final loss {lf:.4f} != {gsrc} "
                f"{gate_f:.4f} (tol {tol:.0%}); DISCARDED")
            continue
        if not quantized and not multi_dev and not stale and not ro \
                and not at:
            # record the twin reference only for a native run that passed
            # BOTH gates — a diverged native run must never become the
            # gate its quantized twins are judged against
            native_l0[base], native_lf[base] = l0, lf
        log(f"  spmm={name}: {et:.4f}s/epoch loss={lf:.4f}")
        pred = perf_pred.get(name) or {}
        if pred:
            # the residual line: the perf trajectory doubles as
            # calibration data from here on (gate 4 audits the drift)
            log(f"  [perf] {name}: predicted "
                f"{pred['predicted_step_s']:.4f}s/epoch "
                f"({(pred['predicted_step_s'] - et) / max(et, 1e-9):+.1%} "
                f"residual), steady wire "
                f"{pred['predicted_wire_mb']:.2f} MB/epoch")
        if obs_ev is not None:
            obs_ev.emit("bench_variant", name=name, epoch_s=round(et, 4),
                        min_epoch_s=round(mt, 4), loss=round(lf, 4),
                        **stamp,
                        profiled=bool(args.profile_dir), **pred)
        # structured per-candidate history (append-only) — the winner JSON
        # line only carries the best, but comparing runs needs every gated
        # measurement with its context and its device
        with open(os.path.join(args.cache_dir, "results_log.jsonl"),
                  "a") as f:
            f.write(json.dumps({
                "ts": time.strftime("%Y-%m-%d %H:%M:%S"),
                "workload": _workload_tag(args), "spmm": name,
                "epoch_s": round(et, 4), "min_epoch_s": round(mt, 4),
                "loss": round(lf, 4), **stamp,
                "profiled": bool(args.profile_dir),
                **pred, **obs_extra}) + "\n")
        if best is None or et < best[0]:
            best = (et, mt, loss, name)
            # provisional line: if an outer timeout kills the process before
            # all candidates run, the LAST printed JSON is still a valid
            # best-so-far result (the driver parses from the tail)
            print(json.dumps(result_line(et)), flush=True)
        del built
    if best is None:
        raise SystemExit("no candidate survived its loss gates: no result")
    epoch_t, min_t, loss, spmm_used = best
    log(f"winner: spmm={spmm_used}")
    eps = g.n_edges / epoch_t
    log(f"epoch time mean={epoch_t:.4f}s min={min_t:.4f}s "
        f"({eps / 1e6:.1f}M edges/s/chip; baseline {BASELINE_EPOCH_S}s/rank) "
        f"loss={float(loss):.4f} spmm={spmm_used}")

    print(json.dumps(result_line(epoch_t)))
    if obs_ev is not None:
        obs_ev.emit("bench_end", winner=spmm_used,
                    epoch_s=round(epoch_t, 4), min_epoch_s=round(min_t, 4))
        obs_ev.close()


if __name__ == "__main__":
    main()
