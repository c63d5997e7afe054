"""Readings that the limits of `correct` are set from, in one process.

    python benchmarks/limits_sweep.py --workload <cell> --seeds 1,2,3 \
        --control-seeds 1,2,3 [--out chiprun_out/limits.json]

For every seed in --seeds: the program's first steps through `run_training`
(the same tap the benchmark's runs use) against the plain reference: the lower
readings. For every seed in --control-seeds: the control (the reference in the
nearest precision below the configuration's, put in the program's place) and
the planted fault "half of the batch left out, the mean taken over the rest",
each against the reference: the upper readings. TPU only, like the benchmark.

A look at a gap, for PERF.md and not for any limit: `--store-seeds` holds the
same program runs against the reference with its parameters rounded to the
configuration's storage type after every step.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONTROL_QUANT = {"bfloat16": "float8_e4m3fn", "float32": "bfloat16"}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--store-seeds", default="")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    from benchmarks import harness
    from benchmarks.reference import check
    real_out, sys.stdout = sys.stdout, sys.stderr

    wl = harness.load_workload(args.workload)
    config = harness.load_config(wl["config"])
    harness.device_stamp(int(wl["chips"]), require_tpu=True)
    import jax
    import numpy as np
    from bnsgcn_tpu import run as run_mod
    from bnsgcn_tpu.utils.platform import place_compile_cache
    place_compile_cache()
    ref = harness.load_reference(config)
    dirs = harness.CellDirs(args.workload, wl, config)
    dirs.fresh_run_dir()
    log = lambda m: print(m, file=sys.stderr, flush=True)
    harness.ensure_dataset(wl, config, dirs, ref, log)
    ints = lambda text: [int(s) for s in text.split(",") if s]
    seeds, cseeds = ints(args.seeds), ints(args.control_seeds)
    sseeds = ints(args.store_seeds)
    if not os.path.exists(dirs.calibration):
        harness.calibrated_epoch_s(
            wl, config, dirs, (seeds + cseeds + sseeds)[0], log)
    n_parts = harness.n_partitions(wl, config)
    graph, tables = harness.load_reference_inputs(dirs, edges=n_parts > 1)
    model = harness.reference_model(config, graph)
    layout = harness.read_layout(wl, config, dirs, graph)
    free = {k: 1.0 for k in ("loss1_gap", "loss2_gap", "loss3_gap",
                             "grad1_gap", "dparam_gap")}
    refs, progs, rows = {}, {}, []

    def reference(seed, **how):
        key = (seed, tuple(sorted(how.items())))
        if key not in refs:
            t0 = time.time()
            refs[key] = ref.run_steps(graph, tables, model, seed, layout,
                                      harness.REF_STEPS, **how)
            log(f"[sweep] reference seed {seed} {how}: "
                f"{time.time() - t0:.1f}s")
        return refs[key]

    def program(seed):
        if seed not in progs:
            argv_p = harness.build_argv(config, wl, dirs, seed,
                                        harness.REF_STEPS + 1, False,
                                        f"s{seed}")
            tap = harness.StepTap(run_mod)
            t0 = time.time()
            res = harness._train(argv_p, tap=tap)
            progs[seed] = harness.program_numbers(tap, res.losses,
                                                 ref.ADAM_B1)
            del tap, res
            gc.collect()
            jax.clear_caches()
            log(f"[sweep] program seed {seed}: {time.time() - t0:.1f}s")
        return progs[seed]

    def record(kind, seed, got, want):
        nums = {k: v for k, (v, _) in check.compare(got, want, free).items()}
        live = check.live_leaves(want["grad1"])
        nums["grad1_at"] = check.worst_leaf_gap(got["grad1"], want["grad1"])[1]
        nums["dparam_at"] = check.worst_leaf_gap(got["dparam"],
                                                 want["dparam"], live)[1]
        rows.append({"kind": kind, "seed": seed, **nums})
        log(f"[sweep] {kind} seed {seed}: " + json.dumps(nums))

    for seed in seeds:
        record("program", seed, program(seed), reference(seed))
    dtype = config["model"]["dtype"]
    for seed in sseeds:
        record(f"program_vs_store:{dtype}", seed, program(seed),
               reference(seed, store=dtype))
    quant = CONTROL_QUANT[dtype]
    half = np.ones(graph["n_nodes"], np.float32)
    half[graph["n_nodes"] // 2:] = 0.0
    for seed in cseeds:
        record(f"control:{quant}", seed, reference(seed, quant=quant),
               reference(seed))
        half_run = ref.run_steps(graph, tables, model, seed, layout,
                                 harness.REF_STEPS, row_weight=half)
        record("fault:half_batch", seed, half_run, reference(seed))
        if n_parts > 1:
            record("fault:no_exchange", seed,
                   reference(seed, exchange=False), reference(seed))
    sys.stdout = real_out
    text = json.dumps(rows, indent=1)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            f.write(text + "\n")
    print(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
