"""epoch_s, loop.epoch_p95_s and loop.host_gap_pct on a synthetic obs log."""

import json

import pytest

import bench_tiny  # noqa: F401  (puts the repo on sys.path)
from benchmarks import obsread


def write_log(path, walls, step_s=0.08, layout=()):
    t = 1000.0
    lines = [{"ts": t, "kind": "run_header", "rank": 0}]
    for st in layout:
        lines.append({"ts": t, "kind": "layout_build", **st})
    for i, w in enumerate(walls):
        t = round(t + w, 3)
        lines.append({"ts": t, "kind": "epoch", "rank": 0, "epoch": i,
                      "loss": 1.0, "step_s": step_s})
    with open(path, "w") as f:
        f.write("\n".join(json.dumps(x) for x in lines) + "\n")
    return obsread.read_events(path)


def test_window_starts_at_the_last_warmup_epoch(tmp_path):
    ev = write_log(tmp_path / "obs.jsonl", [5.0] * 5 + [0.1] * 20)
    assert obsread.epoch_s(ev, 5) == pytest.approx(0.1)
    assert len(obsread.epoch_walls(ev, 5)) == 20


def test_a_stalled_epoch_moves_epoch_s(tmp_path):
    steady = write_log(tmp_path / "a.jsonl", [1.0] * 5 + [0.1] * 20)
    walls = [1.0] * 5 + [0.1] * 20
    walls[12] = 2.1                       # one stall of two seconds
    stalled = write_log(tmp_path / "b.jsonl", walls)
    assert obsread.epoch_s(steady, 5) == pytest.approx(0.1)
    assert obsread.epoch_s(stalled, 5) == pytest.approx(0.2)
    # the median step does not see it; the gap share and the tail do
    assert obsread.step_median_s(stalled, 5) == pytest.approx(0.08)
    assert obsread.host_gap_share(steady, 5) == pytest.approx(0.2)
    assert obsread.host_gap_share(stalled, 5) == pytest.approx(0.6)
    assert obsread.epoch_p95_s(steady, 5) == pytest.approx(0.1)
    assert obsread.epoch_p95_s(stalled, 5) > 0.1


def test_p95_of_checkpoint_epochs(tmp_path):
    walls = [1.0] * 5 + [0.5 if i % 10 == 9 else 0.1 for i in range(5, 105)]
    ev = write_log(tmp_path / "c.jsonl", walls)
    assert obsread.epoch_p95_s(ev, 5) == pytest.approx(0.5)
    assert obsread.epoch_s(ev, 5) == pytest.approx(0.14)


def test_layout_build_counts_only_misses(tmp_path):
    ev = write_log(tmp_path / "d.jsonl", [1.0] * 7, layout=[
        {"stage": "hybrid", "ms": 2500.0, "cached": False},
        {"stage": "ell", "ms": 40.0, "cached": True}])
    assert obsread.layout_build_s(ev) == pytest.approx(2.5)


def test_window_needs_the_epoch_before_it(tmp_path):
    ev = write_log(tmp_path / "e.jsonl", [1.0] * 3)
    with pytest.raises(ValueError):
        obsread.epoch_s(ev, 5)
