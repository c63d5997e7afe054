"""The set-up reducer (`reducers/obs_setup.py`) on a synthetic obs log: the
three top-level parts add up to `setup_s`, the compile account is read from
the root and the warm-up epochs only, and a log without boot spans or
`compile` fields (a program that does not write them) reads None there. The
benchmark does not list the five metrics that read it yet (the manifest's
per-layer list is pinned to end with the fourteen scope metrics); a root that
lists them, as a traced CPU run of the tiny cell shows, puts all five on its
line."""

import json
import os

import pytest

import bench_tiny  # puts the repo on sys.path
from benchmarks import harness, obsread

HARNESS_START = 1000.0
FIRST = 10


def acct(trace, lower, comp, hits=0, misses=0):
    return {"trace_s": trace, "lower_s": lower, "compile_s": comp,
            "hits": hits, "misses": misses}


def write_log(path, with_new_fields=True):
    lines = [
        {"ts": 1003.2, "kind": "span", "rank": 0, "name": "import",
         "parent": "process", "t0": 1001.1, "dur_s": 2.1,
         "proc_start": 999.5},
        {"ts": 1004.0, "kind": "span", "rank": 0, "name": "backend_init",
         "parent": "process", "t0": 1003.9, "dur_s": 0.000012},
        # a child's account is inside its root's and must not count twice
        {"ts": 1012.0, "kind": "span", "rank": 0, "name": "init_training",
         "parent": "run_training_setup", "t0": 1010.5, "dur_s": 1.5,
         "compile": acct(0.2, 0.3, 0.9, hits=3)},
        {"ts": 1026.1, "kind": "span", "rank": 0,
         "name": "run_training_setup", "parent": None, "t0": 1011.123456,
         "dur_s": 15.0, "compile": acct(0.25, 0.5, 1.25, hits=4)},
    ]
    t = 1026.2
    for e in range(FIRST + 5):
        t = round(t + (8.0 if e == 0 else 0.4), 3)
        ev = {"ts": t, "kind": "epoch", "rank": 0, "epoch": e, "loss": 1.0,
              "step_s": 0.39}
        if e == 0:
            ev["compile"] = dict(acct(3.5, 2.1, 2.5, hits=1),
                                 programs=["jit(train_step)"], t0=1026.3)
        elif e == FIRST - 1:
            ev["compile"] = dict(acct(0.01, 0.02, 0.06, hits=1),
                                 programs=["jit(param_global_norm)"],
                                 t0=t - 0.1)
        elif e == FIRST + 2:
            # inside the window: not set-up, and not read
            ev["compile"] = dict(acct(9.0, 9.0, 9.0, misses=1),
                                 programs=["jit(x)"], t0=t - 0.1)
        lines.append(ev)
    if not with_new_fields:
        lines = [{k: v for k, v in ev.items() if k != "compile"}
                 for ev in lines if ev.get("parent") != "process"]
    with open(path, "w") as f:
        f.write("\n".join(json.dumps(x) for x in lines) + "\n")
    events = obsread.read_events(path)
    _, t_window = obsread.window(events, FIRST)
    return {"events": events, "first_epoch": FIRST,
            "setup_s": t_window - HARNESS_START, "breakdown_notes": {}}


def read(ctx, part):
    return harness.load_reducer("obs_setup")(ctx, part=part)


def run_training_s(ctx):
    return harness.load_reducer("obs_span")(ctx, names=["run_training_setup"])


@pytest.mark.parametrize("with_new_fields", [True, False])
def test_the_three_parts_add_up_to_setup_s(tmp_path, with_new_fields):
    ctx = write_log(tmp_path / "obs.jsonl", with_new_fields)
    pre, warm = read(ctx, "pre_run"), read(ctx, "warmup")
    assert pre == pytest.approx(11.123456, abs=1e-9)
    assert warm == pytest.approx(ctx["setup_s"] - 11.123456 - 15.0)
    assert abs(pre + run_training_s(ctx) + warm - ctx["setup_s"]) < 1e-6


def test_compile_account_of_the_root_and_the_warmup_epochs(tmp_path):
    ctx = write_log(tmp_path / "obs.jsonl")
    assert read(ctx, "import") == 2.1
    # root 0.25 + 0.5, epoch 0 3.5 + 2.1, epoch 9 0.01 + 0.02
    assert read(ctx, "trace") == pytest.approx(6.38)
    assert read(ctx, "compile") == pytest.approx(1.25 + 2.5 + 0.06)
    assert ctx["breakdown_notes"]["setup_cache"] == "hits 6 misses 0"


def test_a_log_without_the_new_fields_reads_none(tmp_path):
    ctx = write_log(tmp_path / "obs.jsonl", with_new_fields=False)
    for part in ("import", "trace", "compile"):
        assert read(ctx, part) is None, part
    assert ctx["breakdown_notes"] == {}
    with pytest.raises(ValueError, match="no set-up part"):
        read(ctx, "imports")


# ----------------------------------------------------------------------------
# the five metrics, as a benchmark root would list them, through the harness
# ----------------------------------------------------------------------------

PARTS = ("pre_run", "warmup", "import", "trace", "compile")
SETUP = [f"setup.{p}_s" for p in PARTS]


def metric_file(part, cells):
    """The metric file that reads one part of set-up."""
    return {"kind": "per_layer", "unit": "s", "better": "lower",
            "source": "program_span", "layer": "set-up: data + layout builders",
            "moves": "setup_s", "reducer": "obs_setup", "args": {"part": part},
            "workloads": cells}


def test_the_five_metrics_read_obs_setup_in_both_cells():
    """Each part's metric file fits the manifest beside the set-up metrics
    it already lists: same layer, same end-to-end metric, both cells, a
    reducer that loads."""
    with open(os.path.join(bench_tiny.REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    listed = {m["name"]: m for m in manifest["per_layer"]}
    e2e = {m["name"] for m in manifest["end_to_end"]}
    cells = sorted(w["name"] for w in manifest["workloads"])
    beside = listed["setup.run_training_s"]
    for part, name in zip(PARTS, SETUP):
        f = metric_file(part, cells)
        assert (f["layer"], f["source"], f["unit"]) == (
            beside["layer"], beside["source"], beside["unit"])
        assert f["moves"] in e2e and f["workloads"] == beside["workloads"]
        assert harness.load_reducer(f["reducer"]) is not None, name


def test_harness_reports_the_five_on_a_cpu_run(tmp_path):
    """A traced run of the tiny cell through the real run_training: the
    five are on the line, and the three top-level parts hold
    `setup.run_training_s` between them."""
    root = bench_tiny.make_root(str(tmp_path / "root"))
    for part, name in zip(PARTS, SETUP):
        with open(os.path.join(root, "metrics", name + ".json"), "w") as f:
            json.dump(metric_file(part, ["tiny.p1"]), f)
    path = os.path.join(root, "metrics", "setup.run_training_s.json")
    with open(path) as f:
        m = json.load(f)
    m["workloads"] = ["tiny.p1"]
    with open(path, "w") as f:
        json.dump(m, f)
    rc, res, _ = bench_tiny.run_cell(root, seed=2**31 + 11, seconds=1.0,
                                     trace=True)
    assert rc == 0 and res["correct"] is True
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert set(SETUP) <= set(m), sorted(set(SETUP) - set(m))
    assert m["setup.pre_run_s"] > 0 and m["setup.warmup_s"] > 0
    assert m["setup.import_s"] > 0
    assert m["setup.trace_s"] > 0 and m["setup.compile_s"] > 0
    assert res["notes"]["setup_cache"].startswith("hits ")
