"""Operations and bytes the algorithm needs, computed from shapes, and the
table of device peaks. Kept with the benchmark: it counts the same work
whatever implements it.
"""

from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def device_peaks(device_kind: str) -> dict:
    """Peaks of one chip by JAX's `device_kind`. An unknown kind is an error,
    never a default."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"benchmarks/peaks.json")
    return table[device_kind]


def sage_layer_flops(rows: int, nnz: int, fan_in: int, fan_out: int,
                     kind: str) -> int:
    """Forward + backward FLOPs of one layer over `rows` nodes.

    kind 'pp'     layer 0 under use_pp: one linear over [x, mean(x)] (width
                  2 * fan_in); its input is data, so the backward needs the
                  weight gradient only: 2NKM x 2.
    kind 'sage'   linear1(h) + linear2(mean of neighbours): two linears,
                  2NKM x 3 each (forward, input gradient, weight gradient),
                  and the aggregation 2 * nnz * fan_in forward and as much
                  backward.
    kind 'linear' a dense tail layer: 2NKM x 3.
    """
    if kind == "pp":
        return 2 * rows * (2 * fan_in) * fan_out * 2
    if kind == "linear":
        return 2 * rows * fan_in * fan_out * 3
    if kind == "sage":
        return 2 * (2 * rows * fan_in * fan_out * 3) + 2 * (2 * nnz * fan_in)
    raise ValueError(kind)


def sage_step_flops(rows: int, nnz: int, sizes: list, n_linear: int,
                    use_pp: bool) -> int:
    """FLOPs one training step needs (recomputation not counted)."""
    n_layers = len(sizes) - 1
    n_graph = n_layers - n_linear
    total = 0
    for i in range(n_layers):
        kind = ("linear" if i >= n_graph
                else "pp" if (i == 0 and use_pp) else "sage")
        total += sage_layer_flops(rows, nnz, sizes[i], sizes[i + 1], kind)
    return total


def tile_matmul_flops(n_tiles: int, tile_rows: int, tile_cols: int,
                      width: int) -> int:
    """One pass of the dense-tile kernel: n_tiles products [TR, TC] x [TC, H]."""
    return 2 * n_tiles * tile_rows * tile_cols * width


def tile_matmul_bytes(n_tiles: int, tile_rows: int, tile_cols: int,
                      width: int, n_row_blocks: int, tile_itemsize: int = 1,
                      slab_itemsize: int = 2, out_itemsize: int = 4) -> int:
    """Bytes one pass must move: every tile once, one activation slab per
    tile in, every output row block once out."""
    return (n_tiles * tile_rows * tile_cols * tile_itemsize
            + n_tiles * tile_cols * width * slab_itemsize
            + n_row_blocks * tile_rows * width * out_itemsize)


def roofline_seconds(flops: float, nbytes: float, peaks: dict):
    """(least seconds the chip could take, which bound: 'flops' or 'bytes')."""
    t_f = flops / peaks["bf16_flops"]
    t_b = nbytes / peaks["hbm_bytes_per_s"]
    return (t_f, "flops") if t_f >= t_b else (t_b, "bytes")
