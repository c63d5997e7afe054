"""The quickest proof that the training path still starts on the chip.

    python chip_smoke.py        # on a machine with a TPU; ~2-3 min cold

One process, no arguments, no JAX_PLATFORMS set here: it runs on whatever
JAX finds and REFUSES (non-zero exit, no result line) unless that is a TPU.
It drives the product's own entry point, `bnsgcn_tpu.main.main(argv)` ->
`run_training`, at the flagship widths (GraphSAGE 602 -> 4 x 256 -> 41,
scripts/reddit.sh) with the on-chip recipe (bf16, hybrid SpMM, whose dense
tiles run the Pallas kernel on a TPU, BNS rate 0.1, use_pp), a dozen epochs,
host eval every --log-every, checkpoints written. Widths are not cut; the graph is
(`synth-reddit:0.1`: 23,296 nodes, 2.3M edges - large enough that the
hybrid layout selects dense tiles, small enough that a cold run fits the
chip tool's limit). Weights are random, from --fix-seed.

Phases (every one runs; any failure fails the script):

  kernel    ops/pallas_block.dense_apply_pallas against its XLA twin
            ops/block_spmm._dense_apply and a float64 host reference, on the
            chip, at tile 512 and 256, H = 41, 256 and 602, bf16, float32
            and int8 slabs (KERNEL_CASES), forward and backward (the same
            stack read transposed), and on the two tile stacks of an
            --overlap split layout.
  P=4       the recipe at --n-partitions 4, when the host has >= 4 chips
            (first, so each chip's peak memory is this run's alone).
  P=1       the recipe at --n-partitions 1.

Each training phase checks what the log shows (loss finite and falling,
Time(s) > 0, the Comm(s) column traced, eval and test accuracy produced,
checkpoints on disk, a device-reported memory peak) and what it cannot: the
train step each chip EXECUTED holds the Mosaic custom call (the Pallas kernel
really compiled, interpret=False) and, at P>1, the all-to-all and the
all-reduce. That is read from the run's own profiler window (--profile-dir
keeps the trace run_training parses for its Comm(s) column), so it is the
program that ran, not a second build of it.

Starts from a clean tree: its work directory (smoke_work/, git-ignored) and
any built native library are removed first, and nothing else a checkout can
hold (partition/, checkpoint/, bench_cache/) is read. The XLA compile cache
is the one thing kept between runs, where utils/platform.place_compile_cache
puts it - a second run reports its hits.

Last stdout line on success, and only then:
    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
"""

from __future__ import annotations

import contextlib
import glob
import io
import json
import os
import re
import shutil
import sys
import time
import traceback

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(ROOT, "smoke_work")

RECIPE = ["--dataset", "synth-reddit:0.1", "--model", "graphsage",
          "--n-layers", "4", "--n-hidden", "256", "--use-pp",
          "--sampling-rate", "0.1", "--dtype", "bfloat16",
          "--spmm", "hybrid",
          "--n-epochs", "12", "--log-every", "4", "--fix-seed"]

# kernel-agreement tolerances (phase `kernel`).
# bf16 slabs: both paths accumulate bf16 x int8 products in f32 and round the
# result to bf16 once; they differ by f32 summation order, so by at most one
# bf16 ulp (2^-8 relative, 2^-7 across a power of two) plus an absolute term
# for rows whose terms cancel.
NATIVE_RTOL, NATIVE_ATOL = 2.0 ** -7, 2e-2
# int8 slabs: the two paths quantize differently BY DESIGN (one per-call
# scale in the kernel, one per slab in XLA), so each is held to the
# quantizer's own bounds against the exact result instead of to the other.
# Hard: a row's error is at most (its dense edge count) x scale/2 with
# scale = amax/127, plus the output's bf16 rounding. Statistical: rounding
# errors are uniform in +-scale/2 and independent, multiplicities are <= 3,
# so the rms error over all outputs is at most scale/2 x sqrt(mean dense
# edges per row) (+ bf16 rounding); 1.5x that is allowed.
INT8_SLACK, INT8_RMS_SLACK = 1.05, 1.5
# float32 slabs: each path is held to the float64 result within the rounding
# of a one-pass bf16 MXU product (unit roundoff 2^-9 a term, so 2^-8 of the
# sum of the terms' magnitudes) - the coarsest precision either path may
# use for a float32 dot on the chip - and to each other by the same bound.
F32_RTOL, F32_ATOL = 2.0 ** -8, 1e-5


def fail(msg: str, code: int = 1):
    print(f"chip_smoke: {msg}", file=sys.stderr)
    sys.exit(code)


class _Tee(io.TextIOBase):
    def __init__(self, *streams):
        self.streams = streams

    def write(self, text):
        for st in self.streams:
            st.write(text)
        return len(text)

    def flush(self):
        for st in self.streams:
            st.flush()


# ---------------------------------------------------------------------------
# phase: kernel agreement
# ---------------------------------------------------------------------------

# (tile, H, slab dtype, dense dtypes): the widths a TPU run reaches (41 the
# last layer, 256 the hidden width, 602 the use_pp precompute) in both slab
# dtypes the recipes state (bf16: sage-reddit; float32: the products recipe)
KERNEL_CASES = [(512, 256, "bfloat16", ("native", "int8")),
                (256, 256, "bfloat16", ("native", "int8")),
                (512, 256, "float32", ("native",)),
                (256, 256, "float32", ("native",)),
                (512, 602, "float32", ("native",)),
                (256, 602, "bfloat16", ("native",)),
                (512, 41, "bfloat16", ("native",)),
                (256, 41, "float32", ("native",))]


def _exact(tiles, rowb, colb, perm_src, perm_out, h64, tile, n_rb, n_cb):
    """float64 host reference of one direction's dense-tile contraction, and
    the same with every term's magnitude (what a rounding bound scales
    with)."""
    H = h64.shape[1]
    x_cl = np.zeros((n_cb * tile, H))
    x_cl[perm_src] = h64
    flat = np.zeros(((n_rb + 1) * tile, H))
    absflat = np.zeros_like(flat)
    for b in range(len(rowb)):
        t = tiles[b].astype(np.float64)
        xs = x_cl[colb[b] * tile:(colb[b] + 1) * tile]
        flat[rowb[b] * tile:(rowb[b] + 1) * tile] += t @ xs
        absflat[rowb[b] * tile:(rowb[b] + 1) * tile] += t @ np.abs(xs)
    return flat[perm_out], absflat[perm_out]


def _agree(name, spec, args, dense, exact, absdot, deg, amax, out,
           zero_rows=None, order=None):
    """Run one direction's dense tiles through the kernel and its XLA twin
    on the chip and hold both to the float64 reference (`order`: the
    backward, reading the forward stack in `args` transposed)."""
    import jax
    import jax.numpy as jnp

    from bnsgcn_tpu.ops.block_spmm import _dense_apply
    from bnsgcn_tpu.ops.pallas_block import dense_apply_pallas

    pal = jax.jit(lambda *a: dense_apply_pallas(spec, *a, dense_dtype=dense,
                                                order=order))
    xla = jax.jit(lambda *a: _dense_apply(spec, *a, dense_dtype=dense,
                                          order=order))
    if "tpu_custom_call" not in pal.lower(*args).compile().as_text():
        raise AssertionError(f"{name}: the Pallas path compiled without a "
                             f"Mosaic custom call")
    got_p = np.asarray(pal(*args).astype(jnp.float32), np.float64)
    got_x = np.asarray(xla(*args).astype(jnp.float32), np.float64)
    if not (np.isfinite(got_p).all() and np.isfinite(got_x).all()):
        raise AssertionError(f"{name}: non-finite output")
    if zero_rows is not None and np.abs(got_p[zero_rows]).max() != 0:
        raise AssertionError(
            f"{name}: rows of the unvisited row block are not zero "
            f"(uninitialized kernel output leaked)")
    if dense == "int8":
        bound = (INT8_SLACK * deg * amax / 127.0 / 2.0
                 + 2.0 ** -7 * np.abs(exact) + NATIVE_ATOL)
        rms_bound = (INT8_RMS_SLACK * amax / 127.0 / 2.0
                     * np.sqrt(deg.mean())
                     + 2.0 ** -8 * np.sqrt((exact ** 2).mean()))
        errs = {"pallas_vs_exact": np.abs(got_p - exact) - bound,
                "xla_vs_exact": np.abs(got_x - exact) - bound,
                "pallas_rms": np.sqrt(((got_p - exact) ** 2).mean())
                - rms_bound,
                "xla_rms": np.sqrt(((got_x - exact) ** 2).mean())
                - rms_bound}
    else:
        if args[-1].dtype == jnp.float32:
            bound_px = F32_RTOL * absdot + F32_ATOL
            bound_ex = bound_px
        else:
            bound_px = NATIVE_RTOL * np.abs(got_x) + NATIVE_ATOL
            bound_ex = NATIVE_RTOL * np.abs(exact) + NATIVE_ATOL
        errs = {"pallas_vs_xla": np.abs(got_p - got_x) - bound_px,
                "pallas_vs_exact": np.abs(got_p - exact) - bound_ex,
                "xla_vs_exact": np.abs(got_x - exact) - bound_ex}
    worst = {k: float(np.max(v)) for k, v in errs.items()}
    bad = {k: v for k, v in worst.items() if v > 0}
    if bad:
        raise AssertionError(f"{name}: outside tolerance by {bad}")
    scale = float(absdot.max()) or 1.0
    row = out[name] = {
        "max_abs_pallas_vs_xla": float(np.abs(got_p - got_x).max()),
        "max_abs_pallas_vs_exact": float(np.abs(got_p - exact).max()),
        "max_abs_xla_vs_exact": float(np.abs(got_x - exact).max()),
        "max_abs_terms": scale,
        "rms_pallas_vs_exact": float(np.sqrt(((got_p - exact) ** 2).mean())),
        "out_rms": float(np.sqrt((exact ** 2).mean()))}
    print(f"[smoke] kernel {name}: pallas vs xla max "
          f"{row['max_abs_pallas_vs_xla']:.4g}, vs exact max pallas "
          f"{row['max_abs_pallas_vs_exact']:.4g} / xla "
          f"{row['max_abs_xla_vs_exact']:.4g} (largest sum of |terms| "
          f"{scale:.4g}, output rms {row['out_rms']:.3g})")


def kernel_phase(report: dict):
    import jax.numpy as jnp

    from bnsgcn_tpu.ops.block_spmm import BlockSpec

    n_rows, n_src = 2048, 3072
    out = {}
    for tile, H, slab, denses in KERNEL_CASES:
        rng = np.random.default_rng(tile + H)
        n_rb, n_cb = n_rows // tile, n_src // tile
        # every (row block, col block) pair except row block 1, which stays
        # UNVISITED (the kernel never writes it: the caller's mask must),
        # then two pad tiles (rowb == n_rb, all-zero) as stacked layouts have
        rb, cb = np.meshgrid(np.arange(n_rb), np.arange(n_cb), indexing="ij")
        keep = rb.ravel() != 1
        rowb = np.concatenate([rb.ravel()[keep], [n_rb, n_rb]]).astype(np.int32)
        colb = np.concatenate([cb.ravel()[keep], [0, 0]]).astype(np.int32)
        B = len(rowb)
        tiles = ((rng.random((B, tile, tile)) < 0.02)
                 * rng.integers(1, 4, (B, tile, tile))).astype(np.int8)
        tiles[-2:] = 0
        perm_src = rng.permutation(n_src).astype(np.int32)
        perm_out = rng.permutation(n_rows).astype(np.int32)
        h = jnp.asarray(rng.normal(size=(n_src, H)), jnp.dtype(slab))
        row_deg = np.zeros((n_rb + 1) * tile)
        np.add.at(row_deg.reshape(n_rb + 1, tile), rowb,
                  tiles.sum(axis=2, dtype=np.int64))
        spec = BlockSpec(n_rows=n_rows, n_src=n_src, row_tile=tile,
                         col_tile=tile, n_blocks=B, n_row_blocks=n_rb,
                         max_row_dense=int(row_deg.max()))
        h64 = np.asarray(h.astype(jnp.float32), np.float64)
        exact, absdot = _exact(tiles, rowb, colb, perm_src, perm_out, h64,
                               tile, n_rb, n_cb)
        args = tuple(jnp.asarray(a) for a in
                     (tiles, rowb, colb, perm_src, perm_out)) + (h,)
        # the backward over the same stack, as build_block_layouts lays it out:
        # col_block-sorted, ids swapped, the pads last into the trash block
        o = np.argsort(colb[:-2], kind="stable")
        order = np.concatenate([o, [B - 2, B - 1]]).astype(np.int32)
        rowb_t = np.concatenate([colb[o], [n_cb, n_cb]]).astype(np.int32)
        colb_t = np.concatenate([rowb[o], [0, 0]]).astype(np.int32)
        col_deg = np.zeros((n_cb + 1) * tile)
        np.add.at(col_deg.reshape(n_cb + 1, tile), colb,
                  tiles.sum(axis=1, dtype=np.int64))
        spec_t = BlockSpec(n_rows=n_src, n_src=n_rows, row_tile=tile,
                           col_tile=tile, n_blocks=B, n_row_blocks=n_cb,
                           max_row_dense=int(col_deg.max()))
        g = jnp.asarray(rng.normal(size=(n_rows, H)), jnp.dtype(slab))
        g64 = np.asarray(g.astype(jnp.float32), np.float64)
        exact_t, absdot_t = _exact(tiles[order].transpose(0, 2, 1), rowb_t,
                                   colb_t, perm_out, perm_src, g64, tile,
                                   n_cb, n_rb)
        args_t = tuple(jnp.asarray(a) for a in
                       (tiles, rowb_t, colb_t, perm_out, perm_src)) + (g,)
        for dense in denses:
            name = f"t{tile}/H{H}/{slab}" + ("/int8" if dense == "int8"
                                             else "")
            _agree(name, spec, args, dense, exact, absdot,
                   row_deg[perm_out][:, None], float(np.abs(h64).max()),
                   out, zero_rows=perm_out // tile == 1)
            _agree(name + "/bwd", spec_t, args_t, dense, exact_t, absdot_t,
                   col_deg[perm_src][:, None], float(np.abs(g64).max()),
                   out, order=jnp.asarray(order))
    split_layout_check(out)
    report["kernel"] = out


def split_layout_check(out: dict):
    """The kernel against its twin on the two tile stacks an --overlap
    split build lays out (interior and frontier rows), each read forward
    and transposed, from a two-part partition of a clustered graph."""
    import jax.numpy as jnp

    from bnsgcn_tpu.data.artifacts import build_artifacts
    from bnsgcn_tpu.data.graph import sbm_graph
    from bnsgcn_tpu.data.partitioner import partition_graph
    from bnsgcn_tpu.ops.block_spmm import (build_split_block_layouts,
                                           cluster_order)

    H, tile = 256, 512
    g = sbm_graph(n_nodes=4096, n_class=4, n_feat=8, p_in=0.03,
                  p_out=0.0005, seed=40)
    art = build_artifacts(g, partition_graph(g, 2))
    perms = [cluster_order(art.src[p], art.dst[p], art.pad_inner, art.n_ext,
                           target=tile) for p in range(art.n_parts)]
    (int_f, int_b, _), (fro_f, fro_b, _), arrays, _, _ = \
        build_split_block_layouts(
            art.src, art.dst, art.pad_inner, art.n_ext,
            np.stack([pi for pi, _ in perms]),
            np.stack([pe for _, pe in perms]), occupancy_min=8,
            tile_r=tile, tile_c=tile)
    rng = np.random.default_rng(41)
    for pre, pair in (("int_", (int_f, int_b)), ("fro_", (fro_f, fro_b))):
        for d, spec in zip(("fwd", "bwd"), pair):
            a = {k[len(pre):]: np.asarray(v[0]) for k, v in arrays.items()
                 if k.startswith(pre)}
            src_key, out_key = (("blk_perm_ext", "blk_perm_inner")
                                if d == "fwd" else
                                ("blk_perm_inner", "blk_perm_ext"))
            ops = (a["blk_tiles_fwd"], a[f"blk_rowb_{d}"],
                   a[f"blk_colb_{d}"], a[src_key], a[out_key])
            order = a.get(f"blk_order_{d}")
            real = ops[1] < spec.n_row_blocks
            if not real.any():
                raise AssertionError(f"split {pre}{d}: no dense tiles")
            h = jnp.asarray(rng.normal(size=(spec.n_src, H)), jnp.bfloat16)
            h64 = np.asarray(h.astype(jnp.float32), np.float64)
            n_cb = -(-spec.n_src // tile)
            tiles_d = (ops[0] if order is None
                       else ops[0][order].transpose(0, 2, 1))
            exact, absdot = _exact(tiles_d, ops[1], ops[2], ops[3], ops[4],
                                   h64, tile, spec.n_row_blocks, n_cb)
            _agree(f"split/{pre}{d}/t{tile}/H{H}/bfloat16", spec,
                   tuple(jnp.asarray(x) for x in ops) + (h,), "native",
                   exact, absdot, None, float(np.abs(h64).max()), out,
                   order=None if order is None else jnp.asarray(order))
            out[f"split/{pre}{d}/t{tile}/H{H}/bfloat16"]["tiles"] = int(
                real.sum())


# ---------------------------------------------------------------------------
# phase: the recipe through main.py
# ---------------------------------------------------------------------------

def executed_step_ops(trace_dir: str):
    """What the run's own trace window shows its train step executing:
    (train_step launches, {device: [HLO text of each Pallas kernel span]},
    devices that ran an exchange collective, devices that ran a reduce). A
    v5e trace lists the Mosaic custom call on each `/device:TPU:k` process
    under the pallas_call's name (`bns_tile_matmul.N`) with the instruction
    text in args.long_name (seen on four v5e chips, PR 22); an interpreted
    kernel is plain XLA ops and leaves no such span."""
    from bnsgcn_tpu.ops.pallas_block import KERNEL_NAME
    from bnsgcn_tpu.utils import traceparse

    events, _ = traceparse.load_trace_events(trace_dir)
    attr = traceparse.attribute(events)["train_step"]
    procs = {ev["pid"]: ev["args"].get("name", "") for ev in events
             if ev.get("ph") == "M" and ev.get("name") == "process_name"}
    kernel_pat = re.compile(re.escape(KERNEL_NAME) + r"(\.\d+)?$")
    kernels = {}
    for ev in events:
        dev = procs.get(ev.get("pid"), "")
        if (ev.get("ph") == "X" and dev.startswith("/device:")
                and kernel_pat.match(ev.get("name", ""))):
            kernels.setdefault(dev, []).append(
                (ev.get("args") or {}).get("long_name", ""))
    ex_devs, rd_devs = ({procs.get(pid, pid) for pid, _ in attr[cat]}
                        for cat in ("exchange", "reduce"))
    return attr["launches"], kernels, ex_devs, rd_devs


def train_phase(n_parts: int, report: dict):
    from bnsgcn_tpu.main import main
    from bnsgcn_tpu.utils.timers import device_memory_stats

    tag = f"p{n_parts}"
    base = os.path.join(WORK, tag)
    argv = RECIPE + ["--n-partitions", str(n_parts),
                     "--part-path", os.path.join(base, "parts"),
                     "--ckpt-path", os.path.join(base, "ckpt"),
                     "--results-path", os.path.join(base, "results"),
                     "--profile-dir", os.path.join(base, "trace")]
    print(f"[smoke] {tag}: python -m bnsgcn_tpu.main {' '.join(argv)}")
    buf = io.StringIO()
    t0 = time.time()
    with contextlib.redirect_stdout(_Tee(sys.stdout, buf)):
        res = main(argv)
    wall = time.time() - t0
    log = buf.getvalue()
    checks = []

    def check(ok, what):
        checks.append((bool(ok), what))

    losses = np.asarray(res.losses, np.float64)
    check(len(losses) == 12 and np.isfinite(losses).all(),
          f"12 finite losses (got {len(losses)}: {losses.round(3).tolist()})")
    check(losses[-3:].mean() < losses[:3].mean(),
          f"loss falling ({losses[:3].mean():.3f} -> {losses[-3:].mean():.3f})")
    check(res.epoch_time > 0, f"Time(s) > 0 after warm-up ({res.epoch_time})")
    procs = [ln for ln in log.splitlines() if ln.startswith("Process 000")]
    check(procs and "[traced]" in procs[-1],
          f"Comm(s) column traced in the last epoch line ({procs[-1:]})")
    m = re.search(r"Step: spmm hybrid, (\d+) dense 512x512 tiles .* via "
                  r"pallas,", log)
    check(m and int(m.group(1)) > 0,
          "run header: hybrid layout with > 0 dense tiles via pallas "
          f"({[ln for ln in log.splitlines() if ln.startswith('Step:')]})")
    check(res.best_val_acc > 0 and res.test_acc > 0
          and log.count("Validation Accuracy") >= 3,
          f"eval every --log-every + final test (val {res.best_val_acc:.3f}, "
          f"test {res.test_acc:.3f})")
    ckpts = sorted(os.listdir(os.path.join(base, "ckpt")))
    check(len(ckpts) >= 2, f"periodic + final checkpoints written ({ckpts})")
    check("RESULT final_loss=" in log, "main.py printed its RESULT line")

    mem = device_memory_stats()
    peaks = [v["peak_bytes_in_use"] for v in list(mem.values())[:n_parts]]
    check(len(peaks) == n_parts and min(peaks, default=0) > 0,
          f"device.memory_stats() reports a peak on each of the {n_parts} "
          f"chip(s) used ({[round(p / 2**20) for p in peaks]} MB)")
    if n_parts > 1:
        # each chip holds its own part and nothing else: padded blocks are
        # one shape per part, so the peaks are one size. Blocks staged
        # through chip 0 would show there as a multiple.
        lo, hi = min(peaks, default=0), max(peaks, default=0)
        check(0 < hi <= 1.25 * lo,
              f"per-chip peak memory of one size (max/min "
              f"{hi / max(lo, 1):.3f})")

    steps, kernels, ex_devs, rd_devs = executed_step_ops(
        os.path.join(base, "trace"))
    texts = [t for spans in kernels.values() for t in spans]
    targets = set(re.findall(r'custom_call_target="([^"]+)"', " ".join(texts)))
    check(steps >= 1 and len(kernels) == n_parts
          and all(len(spans) >= steps for spans in kernels.values())
          and all("custom-call(" in t for t in texts)
          and targets <= {"tpu_custom_call"},
          f"each of the {n_parts} chip(s) executed the Mosaic custom call in "
          f"every traced train step ({steps} steps; kernel spans per device "
          f"{ {d: len(v) for d, v in sorted(kernels.items())} }, targets "
          f"{sorted(targets)}, e.g. {texts[0][:160] if texts else None!r})")
    if n_parts > 1:
        check(len(ex_devs) == n_parts,
              f"the train step ran its all-to-all on each chip "
              f"({sorted(ex_devs)})")
        check(len(rd_devs) == n_parts,
              f"the train step ran its all-reduce on each chip "
              f"({sorted(rd_devs)})")

    for ok, what in checks:
        print(f"[smoke] {tag}: {'ok  ' if ok else 'FAIL'} {what}")
    report[tag] = {"epoch_time_s": round(res.epoch_time, 5),
                   "comm_time_s": round(res.comm_time, 6),
                   "final_loss": round(res.final_loss, 4),
                   "test_acc": round(res.test_acc, 4),
                   "wall_s": round(wall, 1),
                   "peak_mb": [round(p / 2**20, 1) for p in peaks]}
    failed = [what for ok, what in checks if not ok]
    if failed:
        raise AssertionError(f"{tag}: " + "; ".join(failed))


# ---------------------------------------------------------------------------

def run():
    try:
        import bnsgcn_tpu
    except ImportError as ex:
        fail(f"the repository is not beside this script ({ex})", 2)
    if os.path.dirname(os.path.dirname(os.path.abspath(
            bnsgcn_tpu.__file__))) != ROOT:
        fail(f"bnsgcn_tpu imports from {bnsgcn_tpu.__file__}, not from "
             f"beside this script ({ROOT})", 2)

    import jax
    try:
        backend = jax.default_backend()
    except RuntimeError as ex:
        fail(f"no chip: JAX could not start its backend ({ex})")
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    print(f"[smoke] jax {jax.__version__}, devices: {json.dumps(device)}, "
          f"JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}")
    if backend != "tpu":
        fail(f"no chip: JAX's default backend here is {backend!r} "
             f"({device['kind']} x {device['count']}); this script proves "
             f"the TPU path and prints no result without one")

    # clean tree: nothing left over from an earlier run or another host
    shutil.rmtree(WORK, ignore_errors=True)
    for so in glob.glob(os.path.join(ROOT, "bnsgcn_tpu", "native", "*.so")):
        os.remove(so)

    from jax import monitoring

    from bnsgcn_tpu.utils.platform import place_compile_cache
    cache = {"dir": place_compile_cache(), "hits": 0, "misses": 0,
             "compile_s": 0.0}

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            cache["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            cache["misses"] += 1

    def on_duration(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            cache["compile_s"] += secs

    monitoring.register_event_listener(on_event)
    monitoring.register_event_duration_secs_listener(on_duration)

    report = {"device": device}
    phases = [("kernel", lambda: kernel_phase(report))]
    if device["count"] >= 4:
        phases.append(("p4", lambda: train_phase(4, report)))
    phases.append(("p1", lambda: train_phase(1, report)))
    failed = []
    for name, phase in phases:
        t0 = time.time()
        try:
            phase()
            print(f"[smoke] phase {name}: passed in {time.time() - t0:.1f}s")
        except (Exception, SystemExit):     # main() exits on config errors
            failed.append(name)
            traceback.print_exc()
            print(f"[smoke] phase {name}: FAILED after "
                  f"{time.time() - t0:.1f}s", file=sys.stderr)
    cache["compile_s"] = round(cache["compile_s"], 1)
    report["compile_cache"] = cache
    if device["count"] < 4:
        report["p4"] = f"not run: this host offers {device['count']} chip(s)"
    print("[smoke] report " + json.dumps(report))
    if failed:
        fail(f"phase(s) failed: {failed}; no result")
    shutil.rmtree(WORK, ignore_errors=True)
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    run()
