"""The benchmark harness: one cell, one run, through the product's own loop.

Everything that belongs to one configuration, one cell or one per-layer metric
is a file the harness finds by name under a benchmark root (this directory):

    configs/<config>.json      flags for `parse_config`, the model's sizes,
                               the name of its plain reference
    workloads/<cell>.json      config, graph parameters, graph_seed, chips
    metrics/<metric>.json      layer, unit, moves, reducer + arguments
    reducers/<reducer>.py      `reduce(ctx, **args) -> number or None`
    reference/<name>.py        the model family's plain reference and FLOP
                               counter: `build_graph_tables`, `run_steps`,
                               `step_flops`, `ADAM_B1`

A run is: set-up (first run in a checkout: generate the graph from
`graph_seed`, partition it, build layouts, compile; later runs: load all of
that from `cache/` and `.jax_cache/`), then ONE `run_training` call whose
epochs 0-9 warm up (through the loop's first checkpoint and norm probe) and
whose later epochs are the timed window, then the comparison with the plain
reference that decides `correct`.
"""

from __future__ import annotations

import gc
import glob
import hashlib
import importlib.util
import json
import math
import os
import shutil
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
TIMER_WARMUP = 5           # the product's own EpochTimer(warmup=5)
CALIBRATION_EPOCHS = 5     # epochs 5-9 of the calibrating call are read
# The window opens after epoch 9: at `--log-every 10` that epoch holds the
# loop's first checkpoint and the norm probe's first call (a program of its
# own, loaded from the compile cache), which are warm-up, not steady state.
WINDOW_FIRST = 10
TRACE_LAST_EPOCH = 9       # the product traces epochs 6-9 of a run; epoch 10
                           # of a traced run writes the trace out
REF_STEPS = 3


class BenchError(RuntimeError):
    """The run cannot produce a result; no result line is printed."""


# ---------------------------------------------------------------------------
# files found by name
# ---------------------------------------------------------------------------

def _load_json(path):
    with open(path) as f:
        return json.load(f)


def load_workload(name: str, root: str = HERE) -> dict:
    path = os.path.join(root, "workloads", name + ".json")
    if not os.path.exists(path):
        raise BenchError(f"no workload file {path}")
    wl = _load_json(path)
    wl["name"] = name
    return wl


def load_config(name: str, root: str = HERE) -> dict:
    path = os.path.join(root, "configs", name + ".json")
    if not os.path.exists(path):
        raise BenchError(f"no configuration file {path}")
    return _load_json(path)


def list_names(kind: str, root: str = HERE) -> list:
    return sorted(os.path.splitext(os.path.basename(p))[0]
                  for p in glob.glob(os.path.join(root, kind, "*.json")))


def load_metrics(root: str = HERE) -> dict:
    return {n: _load_json(os.path.join(root, "metrics", n + ".json"))
            for n in list_names("metrics", root)}


def metrics_for(cell: str, kind: str, root: str = HERE) -> dict:
    """The metrics of `kind` ('end_to_end' | 'per_layer') this cell reports."""
    return {n: m for n, m in load_metrics(root).items()
            if m["kind"] == kind
            and ("workloads" not in m or cell in m["workloads"])}


def load_module(kind: str, name: str, root: str = HERE):
    """The module `<kind>/<name>.py` of the benchmark root, or of this
    directory where the root holds none."""
    for base in (root, HERE):
        path = os.path.join(base, kind, name + ".py")
        if os.path.exists(path):
            spec = importlib.util.spec_from_file_location(
                f"bench_{kind}_{name}", path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod
    raise BenchError(f"no {name}.py under {root}/{kind}")


def load_reducer(name: str, root: str = HERE):
    return load_module("reducers", name, root).reduce


def load_reference(config: dict, root: str = HERE):
    """The plain reference the configuration's file names."""
    if "reference" not in config:
        raise BenchError(f"configuration {config.get('name')!r} names no "
                         f"reference module")
    return load_module("reference", config["reference"], root)


def flag_value(flags: list, flag: str, default=None):
    return flags[flags.index(flag) + 1] if flag in flags else default


# ---------------------------------------------------------------------------
# the cell's directories and the program's argv
# ---------------------------------------------------------------------------

def data_key(wl: dict, config: dict) -> str:
    """Names the dataset as the program sees it: the graph and its seed, the
    configuration's flags and the cell's `flags`. Cells of one configuration
    that differ only in `step_flags` (flags that touch neither the partition
    nor its artifacts, such as --use-pallas) share one set-up."""
    what = {"graph": wl["graph"], "graph_seed": wl["graph_seed"],
            "dataset": config["dataset"], "reference": config["reference"],
            "config_flags": config["flags"], "flags": wl.get("flags", [])}
    digest = hashlib.sha1(json.dumps(what, sort_keys=True).encode())
    return f"{wl['config']}-{digest.hexdigest()[:10]}"


class CellDirs:
    """`cache/data/<key>/` holds what cells on one dataset share (partition,
    layouts, the reference's inputs); `cache/cells/<cell>/` what is the
    cell's own (its runs' directories and the epoch wall that sizes them)."""

    def __init__(self, cell: str, wl: dict, config: dict, root: str = HERE):
        self.graph_name = data_key(wl, config)
        data = os.path.join(root, "cache", "data", self.graph_name)
        self.parts = os.path.join(data, "parts")
        self.layouts = os.path.join(data, "layouts")
        self.ref = os.path.join(data, "reference")
        self.meta = os.path.join(self.parts, self.graph_name, "meta.json")
        own = os.path.join(root, "cache", "cells", cell)
        self.run = os.path.join(own, "run")
        self.calibration = os.path.join(own, "calibration.json")

    def fresh_run_dir(self):
        shutil.rmtree(self.run, ignore_errors=True)
        os.makedirs(self.run)


def build_argv(config: dict, wl: dict, dirs: CellDirs, seed: int,
               n_epochs: int, trace: bool, tag: str) -> list:
    """The product's flags for one `run_training` call. Everything about the
    loop stays at the product's defaults except what the timing protocol
    fixes: --no-eval, --fix-seed, and the profiler off unless traced."""
    argv = cell_flags(wl, config)
    argv += ["--dataset", config["dataset"], "--graph-name", dirs.graph_name,
             "--part-path", dirs.parts, "--cache-dir", dirs.layouts,
             "--ckpt-path", os.path.join(dirs.run, tag, "ckpt"),
             "--results-path", os.path.join(dirs.run, tag, "results"),
             "--obs-log", os.path.join(dirs.run, tag, "obs.jsonl"),
             "--seed", str(seed), "--fix-seed", "--no-eval",
             "--skip-partition", "--n-epochs", str(n_epochs)]
    if trace:
        argv += ["--profile-dir", os.path.join(dirs.run, tag, "trace")]
    else:
        argv += ["--no-comm-trace"]
    return argv


# ---------------------------------------------------------------------------
# tap on the timed call's own step: the state the comparison needs
# ---------------------------------------------------------------------------

def _host_f32(tree):
    import jax
    return jax.tree.map(lambda x: np.asarray(jax.device_get(x), np.float32),
                        tree)


def _adam_mu(opt_state):
    import jax
    found = [s for s in jax.tree.leaves(
        opt_state, is_leaf=lambda x: hasattr(x, "mu") and hasattr(x, "nu"))
        if hasattr(s, "mu")]
    if len(found) != 1:
        raise BenchError("the optimizer state holds no single Adam moment "
                         "tree")
    return found[0].mu


class StepTap:
    """Wraps the `train_step` that `run_training` builds, for one call of
    `run_training`: copies to the host the parameters before step 1, Adam's
    first moment after step 1 and the parameters after step `REF_STEPS`, all
    inside the warm-up epochs; later steps pass straight through."""

    def __init__(self, run_mod):
        self.run_mod = run_mod
        self.calls = 0
        self.p0 = self.mu1 = self.p_end = None
        self._orig = None

    def __enter__(self):
        self._orig = self.run_mod.build_step_fns

        def build(*a, **k):
            out = self._orig(*a, **k)
            fns = out[0]
            if fns.train_step_full is not None:
                raise BenchError("--halo-refresh K > 1 builds a step pair "
                                 "the tap does not follow")
            inner = fns.train_step

            def train_step(params, state, opt_state, *rest):
                k_call = self.calls
                self.calls += 1
                if k_call == 0:
                    self.p0 = _host_f32(params)
                res = inner(params, state, opt_state, *rest)
                if k_call == 0:
                    self.mu1 = _host_f32(_adam_mu(res[2]))
                if k_call == REF_STEPS - 1:
                    self.p_end = _host_f32(res[0])
                return res

            fns.train_step = train_step
            return out

        self.run_mod.build_step_fns = build
        return self

    def __exit__(self, *exc):
        self.run_mod.build_step_fns = self._orig
        return False


# ---------------------------------------------------------------------------
# set-up of a cell's cache (first run in a checkout)
# ---------------------------------------------------------------------------

def is_inductive(config: dict) -> bool:
    return "--inductive" in config["flags"]


def cell_flags(wl: dict, config: dict) -> list:
    """The configuration's flags, then the cell's own."""
    return (list(config["flags"]) + list(wl.get("flags", []))
            + list(wl.get("step_flags", [])))


def n_partitions(wl: dict, config: dict) -> int:
    return int(flag_value(cell_flags(wl, config), "--n-partitions", 1))


def ensure_dataset(wl: dict, config: dict, dirs: CellDirs, ref, log) -> bool:
    """Generate, partition and store the cell's dataset unless the cache
    holds it. Returns True when it was built now."""
    if os.path.exists(dirs.meta) and os.path.exists(
            os.path.join(dirs.ref, "done")):
        return False
    from benchmarks import graphgen
    from bnsgcn_tpu.config import parse_config
    from bnsgcn_tpu.data.graph import Graph
    from bnsgcn_tpu.run import prepare_partition

    t0 = time.time()
    full = graphgen.make_graph(wl["graph"], int(wl["graph_seed"]))
    log(f"[bench] graph generated in {time.time() - t0:.1f}s: "
        f"{full['n_nodes']} nodes, {full['src'].shape[0]} edges")
    inductive = is_inductive(config)
    # the program gets the dataset and takes its own inductive split, the
    # lines main.py -> prepare_partition run when they load a dataset
    g = Graph(n_nodes=full["n_nodes"], src=full["src"], dst=full["dst"],
              feat=full["feat"], label=full["label"],
              train_mask=full["train_mask"], val_mask=full["val_mask"],
              test_mask=full["test_mask"], multilabel=full["multilabel"])
    if inductive:
        g = g.subgraph(g.train_mask)
    shutil.rmtree(dirs.parts, ignore_errors=True)
    argv = build_argv(config, wl, dirs, int(wl["graph_seed"]), 1, False,
                      "partition")
    argv.remove("--skip-partition")
    t0 = time.time()
    prepare_partition(parse_config(argv), g, load=False)
    log(f"[bench] partition + artifacts in {time.time() - t0:.1f}s")
    del g
    # the reference's inputs, from the benchmark's own split of the graph
    t0 = time.time()
    tg = graphgen.training_graph(full, inductive)
    del full
    shutil.rmtree(dirs.ref, ignore_errors=True)
    os.makedirs(dirs.ref)
    for k in ("src", "dst"):
        np.save(os.path.join(dirs.ref, f"{k}.npy"), tg[k].astype(np.int32))
    for k in ("feat", "label", "train_mask"):
        np.save(os.path.join(dirs.ref, f"{k}.npy"), tg[k])
    np.save(os.path.join(dirs.ref, "in_deg.npy"),
            np.bincount(tg["dst"], minlength=tg["n_nodes"]).astype(np.float32))
    tables = ref.build_graph_tables(tg["src"], tg["dst"], tg["n_nodes"])
    for k, v in tables.items():
        np.save(os.path.join(dirs.ref, f"table_{k}.npy"), v)
    with open(os.path.join(dirs.ref, "done"), "w") as f:
        json.dump({"n_nodes": tg["n_nodes"],
                   "n_edges": int(tg["src"].shape[0])}, f)
    log(f"[bench] reference inputs in {time.time() - t0:.1f}s")
    return True


def load_reference_inputs(dirs: CellDirs, edges: bool = False):
    info = _load_json(os.path.join(dirs.ref, "done"))
    graph = {"n_nodes": info["n_nodes"], "n_edges": info["n_edges"]}
    for k in ("feat", "label", "train_mask", "in_deg") + (
            ("src", "dst") if edges else ()):
        graph[k] = np.load(os.path.join(dirs.ref, f"{k}.npy"))
    tables = {}
    for p in glob.glob(os.path.join(dirs.ref, "table_*.npy")):
        tables[os.path.basename(p)[len("table_"):-len(".npy")]] = np.load(p)
    return graph, tables


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def device_stamp(chips: int, require_tpu: bool) -> dict:
    import jax
    devs = jax.devices()
    platform = devs[0].platform
    if require_tpu and platform != "tpu":
        raise BenchError(f"the benchmark runs on a TPU only; JAX found "
                         f"{platform!r}")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chip(s); JAX found "
                         f"{len(devs)}")
    return {"platform": platform, "kind": devs[0].device_kind,
            "count": chips}


def memory_peak_bytes(chips: int) -> int:
    import jax
    peak = 0
    for d in jax.devices()[:chips]:
        s = d.memory_stats()
        if s:
            peak = max(peak, int(s.get("peak_bytes_in_use", 0)))
    return peak


def _train(argv, tap=None):
    """One `run_training` call on the parsed flags; no Graph is handed over
    (the partition is on disk), as in a `--skip-partition` launch."""
    import contextlib
    from bnsgcn_tpu import run as run_mod
    from bnsgcn_tpu.config import parse_config
    with tap if tap is not None else contextlib.nullcontext():
        return run_mod.run_training(parse_config(argv))


def calibrated_epoch_s(wl, config, dirs, seed, log) -> float:
    """Epoch wall that sizes --n-epochs, from the cell's cache or, on a
    cell's first run in a checkout, from a short calibrating call (which also
    builds the layout cache and compiles)."""
    from benchmarks import obsread
    if os.path.exists(dirs.calibration):
        return float(_load_json(dirs.calibration)["epoch_s"])
    n = TIMER_WARMUP + CALIBRATION_EPOCHS
    argv = build_argv(config, wl, dirs, seed, n, False, "calibrate")
    t0 = time.time()
    _train(argv)
    ev = obsread.read_events(os.path.join(dirs.run, "calibrate", "obs.jsonl"))
    ep = obsread.epoch_s(ev, TIMER_WARMUP)
    log(f"[bench] calibrating call {time.time() - t0:.1f}s, "
        f"{ep:.4f} s/epoch")
    write_calibration(dirs, ep)
    return ep


def write_calibration(dirs, epoch_s):
    os.makedirs(os.path.dirname(dirs.calibration), exist_ok=True)
    tmp = dirs.calibration + ".tmp"
    with open(tmp, "w") as f:
        json.dump({"epoch_s": epoch_s}, f)
    os.replace(tmp, dirs.calibration)


def program_numbers(tap: StepTap, losses: list, adam_b1: float) -> dict:
    """What the timed call's own first steps produced, as the comparison
    wants it: step losses, per-leaf norms of the first gradient as Adam got
    it (its first moment after one step over 1 - b1) and of the parameters'
    change over the first REF_STEPS steps."""
    import jax
    from benchmarks.reference.check import leaf_norms
    if tap.p0 is None or tap.mu1 is None or tap.p_end is None:
        raise BenchError("the timed call ran fewer steps than the "
                         "comparison follows")
    g1 = jax.tree.map(lambda m: m / (1.0 - adam_b1), tap.mu1)
    dp = jax.tree.map(lambda a, b: a - b, tap.p_end, tap.p0)
    return {"losses": [float(x) for x in losses[:REF_STEPS]],
            "grad1": leaf_norms(g1), "dparam": leaf_norms(dp)}


def read_layout(wl: dict, config: dict, dirs: CellDirs, graph: dict) -> dict:
    """The deployment's layout, read from the partition on disk: which part
    holds each node of the training graph and in which row, the padded row
    and boundary counts, and the cell's sampling rate. It is all the
    reference takes from the program's files: it needs it to replay dropout
    and boundary sampling, which are drawn by part and row.

    What the replay rests on is first held against the benchmark's own graph
    (`graph`: its features, labels, training flags and the in-degrees counted
    from its own edges): every node sits in exactly one row, under `pad_inner`,
    and that row carries this node's own features, label, training flag and
    in-degree. A row order or padding that departs from the node ids the
    files declare is an error here, and is not mirrored into the reference.
    Boundary sets are the reference's own, from the benchmark's edges
    (`boundary_lists`), held against `pad_boundary` there."""
    meta = _load_json(dirs.meta)
    n = int(graph["n_nodes"])
    n_parts, pad_inner = int(meta["n_parts"]), int(meta["pad_inner"])
    part_of = np.full(n, -1, np.int32)
    row_of = np.zeros(n, np.int32)
    seen = np.zeros(n, np.int64)

    def fault(what):
        raise BenchError(f"the partition on disk {what}")

    for p in range(n_parts):
        with np.load(os.path.join(os.path.dirname(dirs.meta),
                                  f"part{p}.npz")) as f:
            nid = f["global_nid"]
            rows = np.nonzero(nid >= 0)[0]
            ids = nid[rows]
            if rows.size and (rows.max() >= pad_inner or ids.max() >= n):
                fault(f"holds a row over pad_inner or a node over {n} in "
                      f"part {p}")
            feat = f["feat"]
            if meta.get("feat_dtype", "float32") == "bfloat16":
                import ml_dtypes
                feat = feat.view(ml_dtypes.bfloat16)
            held = {"features": np.allclose(
                        feat[rows].astype(np.float32), graph["feat"][ids],
                        rtol=2.0 ** -7, atol=1e-6),
                    "labels": np.array_equal(f["label"][rows],
                                             graph["label"][ids]),
                    "training flags": np.array_equal(
                        f["train_mask"][rows], graph["train_mask"][ids]),
                    "in-degrees": np.array_equal(f["in_deg"][rows],
                                                 graph["in_deg"][ids])}
        for what, ok in held.items():
            if not ok:
                fault(f"holds other {what} in part {p} than the nodes its "
                      f"rows name")
        seen += np.bincount(ids, minlength=n)
        part_of[ids] = p
        row_of[ids] = rows
    if (seen != 1).any():
        fault("does not hold every node of the training graph exactly once")
    return {"n_parts": n_parts, "pad_inner": pad_inner,
            "pad_boundary": int(meta["pad_boundary"]),
            "rate": float(flag_value(cell_flags(wl, config),
                                     "--sampling-rate", 1.0)),
            "part_of": part_of, "row_of": row_of}


def reference_model(config: dict, graph: dict) -> dict:
    model = dict(config["model"])
    lab = graph["label"]
    model["multilabel"] = bool(lab.ndim == 2)
    if lab.ndim == 1:
        model["n_class"] = int(lab.max()) + 1
    return model


def run(workload: str, seed: int, seconds: float, trace: bool,
        root: str = HERE, require_tpu: bool = True, out=sys.stdout,
        err=sys.stderr) -> int:
    """One run of one cell. Prints the result object as the last line of
    `out`; returns the exit code."""
    t_start = time.time()

    def log(msg):
        print(msg, file=err, flush=True)

    wl = load_workload(workload, root)
    config = load_config(wl["config"], root)
    chips = int(wl["chips"])
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    try:
        import bnsgcn_tpu  # noqa: F401
    except ImportError as ex:
        raise BenchError(f"the program is not beside the benchmark: {ex}")
    device = device_stamp(chips, require_tpu)
    import jax
    from benchmarks import obsread
    from benchmarks.reference import check as ref_check
    from bnsgcn_tpu import run as run_mod
    from bnsgcn_tpu.utils.platform import place_compile_cache
    cache_dir = place_compile_cache()
    # every program goes to the cache, also the small ones (the norm probe,
    # first called at epoch 9, is loaded from it in the warm-up)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    log(f"[bench] {workload} seed={seed} seconds={seconds} trace={int(trace)}"
        f" on {device['count']} x {device['kind']}; compile cache "
        f"{cache_dir}")

    ref = load_reference(config, root)
    dirs = CellDirs(workload, wl, config, root)
    dirs.fresh_run_dir()
    built = ensure_dataset(wl, config, dirs, ref, log)
    ep_cal = calibrated_epoch_s(wl, config, dirs, seed, log)
    first = (TRACE_LAST_EPOCH + 2) if trace else WINDOW_FIRST
    n_epochs = first + max(int(math.ceil(seconds / ep_cal)), 2)

    # ---- the timed call: one uninterrupted run_training ----
    argv = build_argv(config, wl, dirs, seed, n_epochs, trace, "timed")
    tap = StepTap(run_mod)
    res = _train(argv, tap=tap)
    peak = memory_peak_bytes(chips)
    events = obsread.read_events(os.path.join(dirs.run, "timed", "obs.jsonl"))
    win, t0 = obsread.window(events, first)
    setup_s = t0 - t_start
    epoch_s = obsread.epoch_s(events, first)
    if abs(epoch_s - ep_cal) > 0.02 * ep_cal:
        # the calibrating call's few epochs read a little high (one in five
        # writes a checkpoint); the window's own reading sizes the next run
        write_calibration(dirs, epoch_s)
    failed = sum(1 for e in win if not math.isfinite(float(e["loss"])))
    log(f"[bench] window: {len(win)} epochs, {epoch_s:.5f} s/epoch, set-up "
        f"{setup_s:.1f}s{' (built the cell cache)' if built else ''}")

    ctx = {"workload": wl, "config": config, "chips": chips,
           "device": device, "events": events, "first_epoch": first,
           "epoch_s": epoch_s, "setup_s": setup_s, "peak_bytes": peak,
           "trace_events": None, "reference": ref,
           "ref_info": _load_json(os.path.join(dirs.ref, "done")),
           "breakdown_notes": {}}

    # ---- correct: the timed call's first steps against the reference ----
    prog = program_numbers(tap, res.losses, ref.ADAM_B1)
    del tap, res
    gc.collect()
    jax.clear_caches()
    t_ref = time.time()
    graph, tables = load_reference_inputs(
        dirs, edges=n_partitions(wl, config) > 1)
    model = reference_model(config, graph)
    layout = read_layout(wl, config, dirs, graph)
    want = ref.run_steps(graph, tables, model, seed, layout, REF_STEPS)
    del graph, tables
    numbers = ref_check.compare(prog, want, wl["limits"])
    correct = bool(failed == 0 and all(v <= lim for v, lim
                                       in numbers.values()))
    log(f"[bench] reference {time.time() - t_ref:.1f}s")

    # ---- metrics ----
    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    if trace:
        from benchmarks import tracelib
        ctx["trace_events"], _ = tracelib.load_trace_events(
            os.path.join(dirs.run, "timed", "trace"))
    for name, m in sorted(metrics_for(workload, kind, root).items()):
        value = load_reducer(m["reducer"], root)(ctx, **m.get("args", {}))
        if value is not None:
            metrics[name] = {"value": float(value), "unit": m["unit"]}
    device["memory_peak_bytes"] = peak
    result = {"correct": correct, "attempted": len(win), "failed": failed,
              "metrics": metrics, "device": device}
    if trace:
        busy, window_s = tracelib.device_busy(ctx["trace_events"])
        if not busy and require_tpu:
            raise BenchError("the traced window holds no device operation")
        device["busy_s"] = sum(busy.values()) / max(len(busy), 1)
        device["window_s"] = window_s
        result["breakdown"] = {
            "device_ops": tracelib.top_device_ops(ctx["trace_events"]),
            "idle_gaps": tracelib.idle_gaps(ctx["trace_events"])}
        if ctx["breakdown_notes"]:
            result["notes"] = ctx["breakdown_notes"]
    result["compared"] = {k: [v, lim] for k, (v, lim) in numbers.items()}
    for k, (v, lim) in numbers.items():
        log(f"[bench] compared {k} = {v:.6g} (limit {lim:.6g})")
    log(f"[bench] correct = {correct}")
    print(json.dumps(result), file=out, flush=True)
    return 0
