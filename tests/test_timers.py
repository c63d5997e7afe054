"""Timer/metrics utilities (reference helper/timer parity)."""

import numpy as np

from bnsgcn_tpu.utils.metrics import calc_acc, micro_f1, standard_scale
from bnsgcn_tpu.utils.timers import EpochTimer


def test_epoch_timer_warmup_exclusion():
    t = EpochTimer(warmup=5)
    for e in range(10):
        t.record(e, 1.0 if e >= 5 else 100.0, 0.5, 0.1)
    mt, mc, mr = t.means()
    assert mt == 1.0 and mc == 0.5 and abs(mr - 0.1) < 1e-12


def test_micro_f1_and_acc():
    labels = np.array([[1, 0], [0, 1], [1, 1]])
    preds = np.array([[1, 0], [0, 0], [1, 1]])
    assert abs(micro_f1(labels, preds) - 2 * 3 / (2 * 3 + 0 + 1)) < 1e-9
    logits = np.array([[0.9, 0.1], [0.2, 0.8]])
    assert calc_acc(logits, np.array([0, 1])) == 1.0


def test_standard_scale_train_fit():
    rng = np.random.default_rng(0)
    x = rng.normal(loc=5.0, scale=3.0, size=(100, 4)).astype(np.float32)
    mask = np.zeros(100, dtype=bool)
    mask[:60] = True
    y = standard_scale(x, mask)
    np.testing.assert_allclose(y[mask].mean(0), 0.0, atol=1e-5)
    np.testing.assert_allclose(y[mask].std(0), 1.0, atol=1e-4)
