"""The FLOP and byte counters against hand counts, and the peaks table."""

import pytest

import bench_tiny  # noqa: F401
from benchmarks import counters


def test_sage_layer_by_hand():
    n, nnz, k, m = 1000, 30000, 256, 256
    # two linears, each forward + input gradient + weight gradient
    linears = 2 * 3 * (2 * n * k * m)
    # one multiply-add per edge and feature, forward and backward
    agg = 2 * (2 * nnz * k)
    assert counters.sage_layer_flops(n, nnz, k, m, "sage") == linears + agg
    assert counters.sage_layer_flops(n, nnz, k, m, "linear") == 3 * 2 * n * k * m
    # use_pp layer 0: input [x, mean x] is data, no input gradient
    assert counters.sage_layer_flops(n, nnz, 602, 256, "pp") == \
        2 * (2 * n * 1204 * 256)


def test_step_is_the_sum_of_its_layers():
    n, nnz = 153431, 49_700_000
    sizes = [602, 256, 256, 256, 41]
    want = (counters.sage_layer_flops(n, nnz, 602, 256, "pp")
            + 2 * counters.sage_layer_flops(n, nnz, 256, 256, "sage")
            + counters.sage_layer_flops(n, nnz, 256, 41, "sage"))
    assert counters.sage_step_flops(n, nnz, sizes, 0, True) == want
    tail = counters.sage_step_flops(n, nnz, sizes, 2, True)
    assert tail == (counters.sage_layer_flops(n, nnz, 602, 256, "pp")
                    + counters.sage_layer_flops(n, nnz, 256, 256, "sage")
                    + counters.sage_layer_flops(n, nnz, 256, 256, "linear")
                    + counters.sage_layer_flops(n, nnz, 256, 41, "linear"))


def test_tile_kernel_by_hand():
    assert counters.tile_matmul_flops(10, 512, 512, 256) == \
        10 * 2 * 512 * 512 * 256
    assert counters.tile_matmul_bytes(10, 512, 512, 256, 4) == \
        10 * 512 * 512 + 10 * 512 * 256 * 2 + 4 * 512 * 256 * 4


def test_roofline_names_its_bound():
    peaks = counters.device_peaks("TPU v5 lite")
    assert peaks["bf16_flops"] == 197e12 and peaks["hbm_bytes_per_s"] == 819e9
    t, bound = counters.roofline_seconds(197e12, 1.0, peaks)
    assert (t, bound) == (pytest.approx(1.0), "flops")
    t, bound = counters.roofline_seconds(1.0, 819e9, peaks)
    assert (t, bound) == (pytest.approx(1.0), "bytes")


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "_source"])
def test_unknown_device_kind_is_an_error(kind):
    with pytest.raises(KeyError):
        counters.device_peaks(kind)


def test_step_mfu_reducer_on_a_known_chip():
    from benchmarks import harness
    from benchmarks.reference import sage
    ctx = {"reference": sage,
           "config": {"model": {"n_feat": 602, "n_hidden": 256, "n_layers": 4,
                                "n_class": 41, "n_linear": 0,
                                "use_pp": True}},
           "ref_info": {"n_nodes": 153431, "n_edges": 49_700_000},
           "events": [{"kind": "epoch", "epoch": e, "ts": 10.0 + e,
                       "step_s": 0.5, "loss": 1.0} for e in range(4, 9)],
           "first_epoch": 5, "chips": 1, "device": {"kind": "TPU v5 lite"}}
    flops = counters.sage_step_flops(153431, 49_700_000,
                                     [602, 256, 256, 256, 41], 0, True)
    assert sage.step_flops(ctx["config"]["model"], 153431, 49_700_000) == flops
    got = harness.load_reducer("step_mfu")(ctx)
    assert got == pytest.approx(100 * flops / (0.5 * 197e12))
    assert 0 < got < 5
    # a family that brings no counter has nothing to read, never a 0
    ctx["reference"] = object()
    assert harness.load_reducer("step_mfu")(ctx) is None


def test_kernel_roofline_reads_the_spans_own_shapes():
    from benchmarks import harness
    ln = ("%bns_tile_matmul.3 = f32[301,512,256]{2,1,0} custom-call("
          "s32[7435]{0} %a, s32[7435]{0} %b, s8[7435,512,512]{2,1,0} %t, "
          "bf16[300,512,256]{2,1,0} %x), custom_call_target=\"tpu_custom_call\"")
    ev = [{"ph": "M", "pid": 3, "name": "process_name",
           "args": {"name": "/device:TPU:0"}},
          {"ph": "X", "pid": 3, "tid": 3, "ts": 0.0, "dur": 20000.0,
           "name": "bns_tile_matmul.3", "args": {"long_name": ln}}]
    ctx = {"trace_events": ev, "breakdown_notes": {},
           "device": {"kind": "TPU v5 lite"}}
    flops = counters.tile_matmul_flops(7435, 512, 512, 256)
    nbytes = counters.tile_matmul_bytes(7435, 512, 512, 256, 301, 1, 2, 4)
    least = max(flops / 197e12, nbytes / 819e9)
    got = harness.load_reducer("kernel_roofline")(
        ctx, kernel="bns_tile_matmul")
    assert got == pytest.approx(100 * least / 0.02)
    assert ctx["breakdown_notes"]["bns_tile_matmul_bound"] in ("flops",
                                                               "bytes")
