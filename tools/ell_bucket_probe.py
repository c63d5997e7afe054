"""Time `ell._bucket_sum` by bucket shape on the chip (PR 28, step 0).

Two questions the residual ELL's geometry rests on:

  1. does a padded slot cost what an edge costs? One bucket shape, once with
     random indices and once with every index at the appended zero row;
  2. do seconds follow rows x width across bucket shapes, down to the small
     buckets a finer width ladder makes? Two sets of shapes, each shape alone
     and each set in one program.

  python tools/ell_bucket_probe.py                  # the whole.p1 residual
  python tools/ell_bucket_probe.py --old 1000x4,500x128 --new 800x4,300x96

Unroll path, bf16 rows of width --hidden, TPU only. One JSON line per
reading on stdout; the last line holds the summary.
"""
import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# forward residual of sage-reddit.whole.p1 (seed 26): rows x width per bucket
OLD = "56x16,8920x32,61744x64,117728x128"
NEW = ("1576x4,1480x8,2824x16,13464x32,33792x48,34304x64,28288x80,"
       "17992x96,11920x112,42824x128")


def shapes(text):
    return [tuple(int(x) for x in s.split("x")) for s in text.split(",")]


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--old", default=OLD)
    ap.add_argument("--new", default=NEW)
    ap.add_argument("--pad-shape", default="117728x128")
    ap.add_argument("--n-src", type=int, default=153440)
    ap.add_argument("--hidden", type=int, default=256)
    ap.add_argument("--reps", type=int, default=10)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from bnsgcn_tpu.ops.ell import _bucket_sum
    if jax.default_backend() != "tpu":
        print(f"the probe times the TPU's gather; JAX found "
              f"{jax.default_backend()!r}", file=sys.stderr)
        return 1
    rng = np.random.default_rng(0)
    n_src = args.n_src
    hp = jnp.asarray(rng.normal(size=(n_src + 1, args.hidden)), jnp.bfloat16)
    hp = hp.at[n_src].set(0)

    def table(r, w, pad_share=0.0):
        idx = rng.integers(0, n_src, size=(r, w)).astype(np.int32)
        if pad_share >= 1.0:
            idx[:] = n_src
        elif pad_share > 0.0:
            # padding as the builder lays it: the tail of a row
            real = np.ceil(w * (1.0 - pad_share * 2 * rng.random(r)))
            idx[np.arange(w)[None, :] >= real[:, None]] = n_src
        return jnp.asarray(idx)

    def timed(fn, *a):
        """Median seconds of `reps` calls, each ended by a host read."""
        float(jnp.sum(fn(*a)[:1].astype(jnp.float32)))     # compile + warm up
        ts = []
        for _ in range(args.reps):
            t0 = time.perf_counter()
            float(jnp.sum(fn(*a)[:1].astype(jnp.float32)))
            ts.append(time.perf_counter() - t0)
        return float(np.median(ts))

    def one(w):
        return jax.jit(lambda h, i: _bucket_sum(h, i, w, accum="unroll"))

    def emit(**kw):
        print(json.dumps(kw), flush=True)

    summary = {"device_kind": jax.devices()[0].device_kind,
               "hidden": args.hidden, "n_src": n_src}
    # 1. padding against random indices
    r, w = shapes(args.pad_shape)[0]
    for name, share in (("random", 0.0), ("half_padded", 0.5),
                        ("all_padding", 1.0)):
        s = timed(one(w), hp, table(r, w, share))
        emit(probe="padding", indices=name, rows=r, width=w, seconds=s,
             mslot_per_s=r * w / s / 1e6)
        summary[f"pad_{name}_s"] = s
    # 2. seconds against rows x width, shape by shape and set by set
    for label, text in (("old", args.old), ("new", args.new)):
        shp = shapes(text)
        tabs = [table(r, w) for r, w in shp]
        alone = 0.0
        for (r, w), t in zip(shp, tabs):
            s = timed(one(w), hp, t)
            alone += s
            emit(probe="shape", set=label, rows=r, width=w, slots=r * w,
                 seconds=s, mslot_per_s=r * w / s / 1e6)

        @jax.jit
        def whole(h, ts, shp=shp):
            return jnp.concatenate(
                [_bucket_sum(h, t, w, accum="unroll")
                 for t, (_, w) in zip(ts, shp)], axis=0)

        s = timed(whole, hp, tabs)
        slots = sum(r * w for r, w in shp)
        emit(probe="set", set=label, buckets=len(shp), slots=slots,
             seconds=s, sum_of_shapes_s=alone, mslot_per_s=slots / s / 1e6)
        summary[f"{label}_slots"] = slots
        summary[f"{label}_s"] = s
        summary[f"{label}_sum_of_shapes_s"] = alone
    summary["new_over_old_slots"] = summary["new_slots"] / summary["old_slots"]
    summary["new_over_old_s"] = summary["new_s"] / summary["old_s"]
    emit(probe="summary", **summary)
    return 0


if __name__ == "__main__":
    sys.exit(main())
