"""A whole run of the harness with the timed path broken underneath: `correct`
has to come out false. The look for a chip is the only thing skipped."""

import numpy as np
import pytest

import bench_tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = bench_tiny.make_root(str(tmp_path_factory.mktemp("bench_faults")))
    rc, res, _ = bench_tiny.run_cell(root, seed=21)
    assert rc == 0 and res["correct"] is True
    return root


def test_a_step_that_returns_its_state_unchanged(root, monkeypatch):
    import jax
    import jax.numpy as jnp
    from bnsgcn_tpu import run as run_mod
    real = run_mod.build_step_fns

    def build(*a, **k):
        out = real(*a, **k)
        fns = out[0]
        inner = fns.train_step

        def frozen(params, state, opt_state, *rest):
            copies = jax.tree.map(jnp.copy, (params, state, opt_state))
            loss = inner(*copies, *rest)[3]
            return params, state, opt_state, loss

        fns.train_step = frozen
        return out

    monkeypatch.setattr(run_mod, "build_step_fns", build)
    rc, res, _ = bench_tiny.run_cell(root, seed=22)
    assert rc == 0 and res["correct"] is False
    assert res["compared"]["grad1_gap"][0] == pytest.approx(1.0)
    assert res["compared"]["dparam_gap"][0] == pytest.approx(1.0)


def test_half_of_the_batch_left_out(root, monkeypatch):
    from bnsgcn_tpu import run as run_mod
    real = run_mod.load_artifacts

    def load(*a, **k):
        art = real(*a, **k)
        rows = art.train_mask.shape[1]
        art.train_mask = art.train_mask.copy()
        art.train_mask[:, rows // 2:] = False
        art.n_train = int(np.asarray(art.train_mask).sum())
        return art

    monkeypatch.setattr(run_mod, "load_artifacts", load)
    rc, res, _ = bench_tiny.run_cell(root, seed=23)
    assert rc == 0 and res["correct"] is False
    over = [k for k, (v, lim) in res["compared"].items() if v > lim]
    assert over


def test_a_non_finite_loss_in_the_window_is_not_correct(root, monkeypatch):
    from benchmarks import obsread
    real = obsread.read_events

    def poisoned(path):
        ev = real(path)
        for e in ev:
            if e.get("kind") == "epoch" and e["epoch"] == 11:
                e["loss"] = float("nan")
        return ev

    monkeypatch.setattr(obsread, "read_events", poisoned)
    rc, res, _ = bench_tiny.run_cell(root, seed=24)
    assert rc == 0 and res["correct"] is False and res["failed"] == 1
