"""python benchmarks/run_cell.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One run of one benchmark cell on the TPU this process finds. No CPU fallback:
without a TPU, or with fewer chips than the cell asks for, or without the
program beside the benchmark, it exits non-zero and prints no result. The last
line of standard output is the result object. See benchmarks/README.md.
"""

from __future__ import annotations

import argparse
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    args = ap.parse_args(argv)
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    from benchmarks import harness
    try:
        # the program logs to stdout; the result line must be the last there
        real_out = sys.stdout
        sys.stdout = sys.stderr
        try:
            return harness.run(args.workload, args.seed, args.seconds,
                               bool(args.trace), out=real_out)
        finally:
            sys.stdout = real_out
    except harness.BenchError as ex:
        print(f"[bench] {ex}", file=sys.stderr)
        return 1


if __name__ == "__main__":
    sys.exit(main())
