"""The comparison that decides `correct` for a training cell.

Compared, each against a limit of its own from the cell's file:

  loss1_gap .. loss3_gap   |program - reference| / |reference| of each of the
                           first three steps' losses
  grad1_gap                the first gradient as the optimizer got it, by the
                           worst leaf: the gap between the program's norm and
                           the reference's, against the reference's norm of
                           that leaf or of the median leaf, whichever is larger
  dparam_gap               the parameters' change over the three steps, by the
                           worst leaf, measured the same way

Leaves whose gradient is nought to rounding in the reference (under a
thousandth of the median leaf's) move under Adam by round-off alone and are
left out of `dparam_gap`, by that rule and not by name.
"""

from __future__ import annotations

import statistics

DEAD_LEAF_SHARE = 1e-3


def leaf_norms(tree) -> dict:
    """{path: L2 norm} over the leaves of a parameter tree."""
    import jax
    import jax.numpy as jnp
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {"/".join(str(getattr(k, "key", k)) for k in path):
            float(jnp.sqrt(jnp.sum(jnp.square(jnp.asarray(leaf, jnp.float32)))))
            for path, leaf in flat}


def worst_leaf_gap(prog: dict, want: dict, leaves=None):
    """(largest gap, its leaf) of |prog - want| / max(want, median want)."""
    if set(prog) != set(want):
        raise ValueError(f"parameter leaves differ: {sorted(set(prog) ^ set(want))}")
    names = sorted(want) if leaves is None else sorted(leaves)
    floor = statistics.median(want[k] for k in names)
    worst, at = 0.0, ""
    for k in names:
        gap = abs(prog[k] - want[k]) / max(want[k], floor, 1e-30)
        if not gap <= worst:            # NaN counts as the worst
            worst, at = gap, k
    return worst, at


def live_leaves(want_grad: dict) -> list:
    med = statistics.median(want_grad.values())
    return [k for k, v in want_grad.items() if v >= DEAD_LEAF_SHARE * med]


def compare(prog: dict, want: dict, limits: dict) -> dict:
    """{name: (value, limit)} for every number the cell's limits name."""
    numbers = {}
    for i, (a, b) in enumerate(zip(prog["losses"], want["losses"]), 1):
        numbers[f"loss{i}_gap"] = abs(a - b) / max(abs(b), 1e-30)
    numbers["grad1_gap"], _ = worst_leaf_gap(prog["grad1"], want["grad1"])
    numbers["dparam_gap"], _ = worst_leaf_gap(
        prog["dparam"], want["dparam"], live_leaves(want["grad1"]))
    missing = set(limits) - set(numbers)
    if missing:
        raise ValueError(f"limits name numbers that are not compared: "
                         f"{sorted(missing)}")
    return {k: (float(numbers[k]), float(limits[k])) for k in sorted(limits)}
