"""papers100M-scale pipeline proof: streaming artifact build + one training
epoch on a >=1e8-edge synthetic graph, on this host, without OOM.

Reports wall times + peak RSS. (The reference loads papers100M through DGL on
a 120 GB host, README.md:32; this exercises the same scale class for OUR
pipeline: vectorized streaming build, bf16 feature storage, partial loads.)

Usage:
  JAX_PLATFORMS=cpu \
  XLA_FLAGS="--xla_force_host_platform_device_count=8 \
    --xla_cpu_collective_call_warn_stuck_timeout_seconds=600 \
    --xla_cpu_collective_call_terminate_timeout_seconds=3600" \
  python tools/scale_proof.py [--nodes 12500000] [--deg 8] [--parts 8]

The collective-timeout flags matter: XLA:CPU's rendezvous defaults to a 40s
hard kill, and 8 virtual devices serialized on few cores legitimately take
longer than that per step at this scale.
"""

from __future__ import annotations

import argparse
import os
import resource
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def rss_gb():
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1e6


class VirtualFeat:
    """Deterministic id->feature generator standing in for a dataset's
    on-disk feature matrix. The real papers100M flow reads raw features
    from the dataset's own memmap (no extra copy); this host's free disk
    cannot hold a 57 GB raw f32 memmap (111M x 128) AND the built
    artifacts, so the rehearsal synthesizes rows on demand instead — same
    access pattern (fancy indexing by global id, one part at a time), zero
    resident or on-disk footprint. splitmix64-style hash of (id, column)
    -> uniform floats in [-0.5, 0.5)."""

    def __init__(self, n, n_feat, seed=0):
        self.shape = (n, n_feat)
        self.ndim = 2
        self.dtype = np.dtype(np.float32)
        # mask to 64 bits BEFORE np.uint64: the Python-int product overflows
        # the C-long conversion for any seed >= 1 otherwise
        self._seed = np.uint64(
            (seed * 0x9E3779B97F4A7C15 + 0xD1B54A32D192ED03)
            & 0xFFFFFFFFFFFFFFFF)

    def __getitem__(self, ids):
        ids = np.asarray(ids).astype(np.uint64, copy=False)
        F = self.shape[1]
        out = np.empty((len(ids), F), np.float32)
        cols = (np.arange(F, dtype=np.uint64)
                * np.uint64(0xBF58476D1CE4E5B9))[None, :]
        chunk = max(1, (1 << 27) // max(F, 1))          # ~1 GB u64 temps
        for i in range(0, len(ids), chunk):
            x = (ids[i:i + chunk, None] * np.uint64(0x9E3779B97F4A7C15)
                 + cols + self._seed)
            x ^= x >> np.uint64(30)
            x *= np.uint64(0xBF58476D1CE4E5B9)
            x ^= x >> np.uint64(27)
            out[i:i + chunk] = (x >> np.uint64(40)).astype(np.float32) \
                / np.float32(2 ** 24) - np.float32(0.5)
        return out


def make_graph(n, deg, n_feat, n_class, seed=0, feat_path=None,
               feat_virtual=False):
    """Power-law-ish graph via inverse-transform sampling (w ~ i^-0.5):
    node = floor(N * u^2) — O(E) with no per-draw search.

    feat_path: write features to an on-disk .npy memmap instead of RAM —
    the papers100M-class flow (feat is the biggest array and the
    partitioner never reads it; the streaming artifact build slices it
    per part, which pages in from disk). The 1.125B-edge rehearsal with
    feat resident was OOM-killed at ~112 GB RSS during multilevel
    coarsening on this 125 GB host; memmapped it fits."""
    from bnsgcn_tpu.data.graph import Graph
    rng = np.random.default_rng(seed)
    e = n * deg
    # int32 ids whenever n fits (always for papers100M's 111M): halves the
    # dominant edge arrays AND their canonicalize/build transients —
    # int64 promotion was ~27 GB of the 1.6B-edge peak on this 125 GB host
    idt = np.int32 if n < 2**31 else np.int64
    src = (n * rng.random(e) ** 2).astype(idt)
    dst = (n * rng.random(e) ** 2).astype(idt)
    label = rng.integers(0, n_class, size=n, dtype=np.int64)
    if feat_virtual:
        feat = VirtualFeat(n, n_feat, seed=seed)
    elif feat_path:
        feat = np.lib.format.open_memmap(
            feat_path, mode="w+", dtype=np.float32, shape=(n, n_feat))
        chunk = max(1, (1 << 28) // (n_feat * 4))        # ~256 MB slices
        for i in range(0, n, chunk):
            feat[i:i + chunk] = rng.standard_normal(
                (min(chunk, n - i), n_feat), dtype=np.float32)
        feat.flush()
    else:
        feat = rng.standard_normal((n, n_feat), dtype=np.float32)
    train = rng.random(n) < 0.6
    val = ~train & (rng.random(n) < 0.5)
    test = ~train & ~val
    g = Graph(n, src, dst, feat, label, train, val, test)
    return g.canonicalize()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=12_500_000)
    ap.add_argument("--deg", type=int, default=8)
    ap.add_argument("--parts", type=int, default=8)
    ap.add_argument("--hidden", type=int, default=64)
    ap.add_argument("--feat", type=int, default=64)
    ap.add_argument("--workdir", type=str, default="/tmp/scale_proof")
    ap.add_argument("--method", type=str, default="random",
                    choices=["random", "native"])
    ap.add_argument("--refine-passes", type=int, default=1)
    ap.add_argument("--n-seeds", type=int, default=1)
    ap.add_argument("--flat", action="store_true",
                    help="disable multilevel coarsening in the native run")
    ap.add_argument("--metrics", action="store_true",
                    help="report comm-volume/edge-cut vs a random baseline "
                         "(O(E log E) host sort — minutes and ~8 B/cross-edge "
                         "of transient memory at 1e9 edges, and it inflates "
                         "the later peak-RSS prints)")
    ap.add_argument("--allow-small", action="store_true",
                    help="skip the >=1e8-edge bar (smoke-testing the tool)")
    ap.add_argument("--feat-on-disk", action="store_true",
                    help="generate features into a workdir .npy memmap "
                         "(papers100M-class RAM relief: the partitioner "
                         "never reads feat; the streaming build pages it)")
    ap.add_argument("--feat-virtual", action="store_true",
                    help="synthesize feature rows on demand (VirtualFeat): "
                         "the true-shape 1.6B x 128 rehearsal on a host "
                         "whose free disk can't hold a raw 57 GB memmap "
                         "next to the built artifacts")
    ap.add_argument("--reuse-pid", action="store_true",
                    help="load the partition saved by a previous native run "
                         "from workdir/pid.npy instead of re-partitioning")
    ap.add_argument("--prune-parts", action="store_true",
                    help="measure then delete every part file except part 0 "
                         "as it is written: the multi-host disk story (each "
                         "host stores only ITS parts) for single-host "
                         "rehearsals whose disk cannot hold all P at once")
    ap.add_argument("--partition-only", action="store_true",
                    help="stop after the partition (+ optional --metrics): "
                         "isolates a partitioner variant's scale/memory "
                         "behavior without re-paying the artifact build")
    ap.add_argument("--no-train", action="store_true",
                    help="stop after a partial (one-part) artifact load: the "
                         "billion-edge rehearsal — XLA:CPU's 8 virtual "
                         "devices can't hold the training buffers this host "
                         "fits on real per-chip HBM (measured 124.7 GB RSS "
                         "already at 112.5M edges)")
    args = ap.parse_args()

    t0 = time.time()
    feat_path = None
    if args.feat_on_disk:
        os.makedirs(args.workdir, exist_ok=True)
        feat_path = os.path.join(args.workdir, "feat_raw.npy")
        try:                      # tmpfs pages count AGAINST memory — the
            fstype = None         # flag would silently provide no relief
            dev = os.stat(args.workdir).st_dev
            for line in open("/proc/mounts"):
                f = line.split()
                if os.path.exists(f[1]) and os.stat(f[1]).st_dev == dev:
                    fstype = f[2]
            if fstype in ("tmpfs", "ramfs"):
                print(f"WARNING: --workdir {args.workdir} is {fstype} "
                      f"(RAM-backed); --feat-on-disk gives no OOM relief "
                      f"there — point --workdir at a real filesystem",
                      file=sys.stderr, flush=True)
        except Exception:
            pass
    g = make_graph(args.nodes, args.deg, args.feat, 16, feat_path=feat_path,
                   feat_virtual=args.feat_virtual)
    fmode = ("feat virtual" if args.feat_virtual
             else "feat on disk" if feat_path else "feat resident")
    print(f"[{time.time()-t0:7.1f}s] graph: {g.n_nodes} nodes, {g.n_edges} edges "
          f"({fmode}, ids {g.src.dtype.name}, "
          f"rss {rss_gb():.1f} GB)", flush=True)
    assert args.allow_small or g.n_edges >= 100_000_000

    if args.prune_parts and not (args.no_train or args.partition_only):
        # the default path full-loads every part AFTER the build — pruning
        # would make a billion-edge rehearsal crash hours in
        sys.exit("--prune-parts requires --no-train (or --partition-only): "
                 "the training path loads all parts")
    pid_path = os.path.join(args.workdir, "pid.npy")
    if args.reuse_pid and not os.path.exists(pid_path):
        sys.exit(f"--reuse-pid: {pid_path} not found (wrong --workdir, or "
                 f"the previous native run died before saving) — refusing "
                 f"to silently re-partition")
    if args.reuse_pid:
        # a billion-edge partition is ~1-3.5k s on this host: reuse the
        # saved one when a later phase (e.g. a disk-full artifact build)
        # needs a retry
        pid = np.load(pid_path)
        assert pid.shape[0] == g.n_nodes
        assert int(pid.max()) + 1 == args.parts, (
            f"pid.npy was saved for P={int(pid.max()) + 1}, run asks "
            f"--parts {args.parts}")
        print(f"[{time.time()-t0:7.1f}s] partition reused from {pid_path}",
              flush=True)
    elif args.method == "native":
        # the METIS-role partitioner at papers100M scale (SURVEY §7 hard
        # part d: the reference needs a 120 GB host for DGL/METIS here)
        from bnsgcn_tpu.native import native_partition
        t1 = time.time()
        pid = native_partition(g, args.parts, obj="vol", seed=0,
                               refine_passes=args.refine_passes,
                               n_seeds=args.n_seeds,
                               multilevel=not args.flat)
        print(f"[{time.time()-t0:7.1f}s] partitioned (native vol "
              f"{'flat' if args.flat else 'multilevel'}, P={args.parts}, "
              f"{args.refine_passes} refine, {args.n_seeds} seeds) in "
              f"{time.time()-t1:.1f}s (rss {rss_gb():.1f} GB)", flush=True)
        os.makedirs(args.workdir, exist_ok=True)
        np.save(pid_path, pid)
    else:
        from bnsgcn_tpu.data.partitioner import random_partition
        pid = random_partition(g, args.parts, seed=0)
        print(f"[{time.time()-t0:7.1f}s] partitioned (random, P={args.parts})", flush=True)

    if args.metrics:
        from bnsgcn_tpu.data.partitioner import random_partition

        def vol_cut(p):
            # one pass over the edges for both metrics: the mask gathers
            # alone are ~8 GB/call at the 1e9-edge scale this flag targets
            cross = p[g.src] != p[g.dst]
            c = int(np.sum(cross))
            Pn = int(p.max()) + 1
            key = g.src[cross] * np.int64(Pn) + p[g.dst[cross]].astype(np.int64)
            return int(np.unique(key).shape[0]), c

        t1 = time.time()
        v, c = vol_cut(pid)
        rv, rc = vol_cut(random_partition(g, args.parts, seed=1))
        bal = np.bincount(pid, minlength=args.parts)
        print(f"[{time.time()-t0:7.1f}s] quality ({time.time()-t1:.1f}s): "
              f"comm volume {v} ({v/max(rv,1):.2f}x random), edge cut {c} "
              f"({c/max(rc,1):.2f}x random), part sizes "
              f"{bal.min()}..{bal.max()} "
              f"(imbalance {bal.max()/bal.mean():.2f})", flush=True)

    if args.partition_only:
        print("SCALE PROOF OK (partition-only)")
        return

    from bnsgcn_tpu.data.artifacts import build_artifacts_streaming
    path = os.path.join(args.workdir, "artifacts")
    t1 = time.time()
    pruned_bytes = [0]

    def on_part(fpath, p):
        if args.prune_parts and p > 0:
            pruned_bytes[0] += os.path.getsize(fpath)
            os.remove(fpath)

    build_artifacts_streaming(g, pid, path, feat_dtype="bfloat16",
                              with_gat=False, log=None, on_part_written=on_part)
    build_t = time.time() - t1
    du = sum(os.path.getsize(os.path.join(path, f)) for f in os.listdir(path))
    print(f"[{time.time()-t0:7.1f}s] streaming build: {build_t:.1f}s, "
          f"{(du + pruned_bytes[0])/1e9:.2f} GB written "
          f"({du/1e9:.2f} GB retained"
          + (f", parts 1..{args.parts-1} measured then pruned"
             if args.prune_parts else "")
          + f") (rss {rss_gb():.1f} GB)", flush=True)

    # free the raw graph before training (keep masks/labels scale honest);
    # the raw f32 feat memmap has no consumer past the streaming build —
    # drop it so it can't triple the run's disk footprint at scale
    del g
    import gc
    gc.collect()
    if feat_path:
        try:
            os.remove(feat_path)
        except OSError:
            pass

    if args.no_train:
        # the per-host flow at papers100M scale: each process reads ONLY its
        # parts (reference per-rank read, helper/utils.py:101-140)
        from bnsgcn_tpu.data.artifacts import load_artifacts
        t1 = time.time()
        art = load_artifacts(path, parts=[0])
        print(f"[{time.time()-t0:7.1f}s] partial load (1 of {args.parts} "
              f"parts) in {time.time()-t1:.1f}s: {art.pad_inner} inner-node "
              f"slots, feat {art.feat.shape} {art.feat.dtype} "
              f"(rss {rss_gb():.1f} GB)", flush=True)
        print("SCALE PROOF OK (build+partial-load rehearsal)")
        return

    import jax
    import jax.numpy as jnp
    from bnsgcn_tpu.config import Config
    from bnsgcn_tpu.data.artifacts import load_artifacts
    from bnsgcn_tpu.models.gnn import ModelSpec, init_params
    from bnsgcn_tpu.parallel.mesh import make_parts_mesh
    from bnsgcn_tpu.trainer import (build_block_arrays, build_step_fns,
                                    init_training, place_blocks,
                                    place_replicated)

    t1 = time.time()
    art = load_artifacts(path)
    print(f"[{time.time()-t0:7.1f}s] loaded artifacts in {time.time()-t1:.1f}s "
          f"(rss {rss_gb():.1f} GB)", flush=True)

    cfg = Config(model="graphsage", n_layers=3, n_hidden=args.hidden,
                 use_pp=True, dropout=0.5, lr=0.01, sampling_rate=0.1,
                 n_feat=art.n_feat, n_class=art.n_class, n_train=art.n_train,
                 dtype="bfloat16", halo_exchange="padded", halo_wire="fp8")
    spec = ModelSpec("graphsage", (art.n_feat, args.hidden, args.hidden,
                                   art.n_class), norm="layer", dropout=0.5,
                     use_pp=True, train_size=art.n_train)
    mesh = make_parts_mesh(args.parts)
    fns, hspec, tables, tables_full = build_step_fns(cfg, spec, art, mesh)
    blk_np = build_block_arrays(art, spec.model)
    blk_np.update(fns.extra_blk)
    for k in fns.drop_blk_keys:
        blk_np.pop(k, None)
    blk = place_blocks(blk_np, mesh)
    del blk_np, art
    gc.collect()
    tables_d = place_replicated(tables, mesh)
    blk["feat"] = fns.precompute(
        blk, place_replicated(tables_full, mesh)).astype(jnp.bfloat16)
    print(f"[{time.time()-t0:7.1f}s] device data + precompute done "
          f"(rss {rss_gb():.1f} GB)", flush=True)

    # graftlint: disable=prng-literal-key(fixed seed: scale proof must be reproducible across pod windows)
    params, state = init_params(jax.random.key(0), spec, dtype=jnp.bfloat16)
    params = place_replicated(params, mesh)
    state = place_replicated(state, mesh)
    _, _, opt = init_training(cfg, spec, mesh)
    t1 = time.time()
    params, state, opt, loss = fns.train_step(
        params, state, opt, jnp.uint32(0), blk, tables_d,
        # graftlint: disable=prng-literal-key(scale proof times fixed streams; independence is irrelevant)
        jax.random.key(0), jax.random.key(1))
    l0 = float(loss)
    print(f"[{time.time()-t0:7.1f}s] epoch 0 (incl compile): "
          f"{time.time()-t1:.1f}s loss={l0:.4f} (rss {rss_gb():.1f} GB)", flush=True)
    t1 = time.time()
    params, state, opt, loss = fns.train_step(
        params, state, opt, jnp.uint32(1), blk, tables_d,
        # graftlint: disable=prng-literal-key(scale proof times fixed streams; independence is irrelevant)
        jax.random.key(0), jax.random.key(1))
    l1 = float(loss)
    print(f"[{time.time()-t0:7.1f}s] epoch 1 (steady): {time.time()-t1:.1f}s "
          f"loss={l1:.4f} (rss {rss_gb():.1f} GB)", flush=True)
    assert np.isfinite(l0) and np.isfinite(l1)
    print("SCALE PROOF OK")


if __name__ == "__main__":
    main()
