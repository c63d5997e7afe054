"""The benchmark harness: one cell, one run, through the product's own loop.

Everything that belongs to one configuration, one cell or one per-layer metric
is a file the harness finds by name under a benchmark root (this directory):

    configs/<config>.json      flags for `parse_config`, the model's sizes,
                               the names of its data module and its plain
                               reference
    datasets/<data>.py         the configuration's data: what names it, the
                               program's data flags, the cache it builds at
                               set-up, the reference's inputs and the layout
                               the reference replays (benchmarks/README.md)
    workloads/<cell>.json      config, chips, flags, limits, and what the
                               data module reads (a graph and its seed)
    metrics/<metric>.json      layer, unit, moves, reducer + arguments
    reducers/<reducer>.py      `reduce(ctx, **args) -> number or None`
    reference/<name>.py        the model family's plain reference and FLOP
                               counter: `run_steps`, `step_flops`, `ADAM_B1`

A run is: set-up (first run in a checkout: the data module builds the cell's
data cache, the program builds its layouts and compiles; later runs: load all
of that from `cache/` and `.jax_cache/`), then ONE `run_training` call whose
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

def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_workload(name: str, root: str = HERE) -> dict:
    path = os.path.join(root, "workloads", name + ".json")
    if not os.path.exists(path):
        raise BenchError(f"no workload file {path}")
    wl = load_json(path)
    wl["name"] = name
    return wl


def load_config(name: str, root: str = HERE) -> dict:
    path = os.path.join(root, "configs", name + ".json")
    if not os.path.exists(path):
        raise BenchError(f"no configuration file {path}")
    return load_json(path)


def list_names(kind: str, root: str = HERE) -> list:
    return sorted(os.path.splitext(os.path.basename(p))[0]
                  for p in glob.glob(os.path.join(root, kind, "*.json")))


def load_metrics(root: str = HERE) -> dict:
    return {n: load_json(os.path.join(root, "metrics", n + ".json"))
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


def load_data(config: dict, root: str = HERE):
    """The data module the configuration's file names."""
    if "data" not in config:
        raise BenchError(f"configuration {config.get('name')!r} names no "
                         f"data module")
    return load_module("datasets", config["data"], root)


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

def data_key(wl: dict, config: dict, root: str = HERE) -> str:
    """Names the dataset as the program sees it: what the data module names
    it by (a graph and its seed), the reference, the configuration's flags
    and the cell's `flags`. Cells of one configuration that differ only in
    `step_flags` (flags that touch neither the data nor its artifacts, such
    as --use-pallas) share one set-up."""
    what = {**load_data(config, root).key_fields(wl, config),
            "reference": config["reference"],
            "config_flags": config["flags"], "flags": wl.get("flags", [])}
    digest = hashlib.sha1(json.dumps(what, sort_keys=True).encode())
    return f"{wl['config']}-{digest.hexdigest()[:10]}"


class CellDirs:
    """`cache/data/<key>/` holds what cells on one dataset share (whatever
    the data module builds there, and under `reference/` the reference's
    inputs with `done`, the sizes `step_flops` takes); `cache/cells/<cell>/`
    what is the cell's own (its runs' directories and the epoch wall that
    sizes them)."""

    def __init__(self, cell: str, wl: dict, config: dict, root: str = HERE):
        self.root = root
        self.key = data_key(wl, config, root)
        self.data = os.path.join(root, "cache", "data", self.key)
        self.ref = os.path.join(self.data, "reference")
        own = os.path.join(root, "cache", "cells", cell)
        self.run = os.path.join(own, "run")
        self.calibration = os.path.join(own, "calibration.json")

    def fresh_run_dir(self):
        shutil.rmtree(self.run, ignore_errors=True)
        os.makedirs(self.run)


def cell_flags(wl: dict, config: dict) -> list:
    """The configuration's flags, then the cell's own."""
    return (list(config["flags"]) + list(wl.get("flags", []))
            + list(wl.get("step_flags", [])))


def build_argv(config: dict, wl: dict, dirs: CellDirs, seed: int,
               n_epochs: int, trace: bool, tag: str) -> list:
    """The product's flags for one `run_training` call: the cell's, the data
    module's, then what the timing protocol fixes. Everything about the loop
    stays at the product's defaults except --no-eval, --fix-seed, the
    profiler off unless traced, and --skip-partition: the data module's
    `ensure` prepared the program's data at set-up, and a call only loads
    it."""
    argv = cell_flags(wl, config)
    argv += load_data(config, dirs.root).program_flags(config, wl, dirs)
    argv += ["--ckpt-path", os.path.join(dirs.run, tag, "ckpt"),
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
        return float(load_json(dirs.calibration)["epoch_s"])
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
    data = load_data(config, root)
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
    data.check_config(config, [wl], ref)
    dirs = CellDirs(workload, wl, config, root)
    dirs.fresh_run_dir()
    built = data.ensure(wl, config, dirs, ref, log)
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
           "ref_info": load_json(os.path.join(dirs.ref, "done")),
           "breakdown_notes": {}}

    # ---- correct: the timed call's first steps against the reference ----
    prog = program_numbers(tap, res.losses, ref.ADAM_B1)
    del tap, res
    gc.collect()
    jax.clear_caches()
    t_ref = time.time()
    inputs, tables = data.reference_inputs(wl, config, dirs)
    model = data.reference_model(config, inputs)
    layout = data.layout(wl, config, dirs, inputs)
    want = ref.run_steps(inputs, tables, model, seed, layout, REF_STEPS)
    del inputs, tables
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
