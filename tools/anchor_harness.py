"""Train/eval harness for the calibrated accuracy anchor.

Shared by tools/calibrate_anchor.py (parameter search) and
tests/test_accuracy_anchor.py (the gate + mutation tests). Trains on the
P-part CPU mesh exactly like tests/test_convergence.py, but evaluates with
the full-rate eval-mode forward (the reference evaluates on the full graph,
train.py:300-308) so a sampling mutation shows up as damage to the LEARNED
WEIGHTS, not as eval-time noise.

Mutations (each reproduces a specific way the BNS math can silently break):
  * break_rescale — drop the 1/ratio sender rescale (reference
    feature_buffer.py scales sampled boundary activations by 1/ratio; losing
    it shrinks every remote contribution by ~rate)
  * biased_sampler — replace the uniform without-replacement pair sample
    with "always the first s positions": a deterministic, biased subset
    (the estimator no longer has the full aggregate as its expectation)
"""

from __future__ import annotations

from contextlib import contextmanager

import jax
import jax.numpy as jnp
import numpy as np

from bnsgcn_tpu.config import Config
from bnsgcn_tpu.data.artifacts import build_artifacts
from bnsgcn_tpu.data.partitioner import partition_graph
from bnsgcn_tpu.evaluate import gather_parts
from bnsgcn_tpu.models.gnn import ModelSpec, init_params
from bnsgcn_tpu.parallel.mesh import make_parts_mesh
from bnsgcn_tpu.trainer import (build_block_arrays, build_step_fns,
                                init_training, place_blocks, place_replicated)
from bnsgcn_tpu.utils.metrics import calc_acc


@contextmanager
def _biased_pair_sample():
    """Swap halo's pair_sample for a first-k (non-uniform) selection."""
    import bnsgcn_tpu.parallel.halo as halo

    def biased(key, n_valid, s_valid, pad_b, pad_s):
        pos = jnp.arange(pad_s, dtype=jnp.int32)
        return pos, jnp.arange(pad_s) < s_valid

    orig = halo.pair_sample
    halo.pair_sample = biased
    try:
        yield
    finally:
        halo.pair_sample = orig


def train_eval(g, P, rate, epochs=120, n_hidden=32, n_layers=3, seed=5,
               break_rescale=False, biased_sampler=False, lr=0.01,
               norm=None, use_pp=False, spmm="ell", spmm_gather="native",
               spmm_dense="native", halo_wire="native"):
    """Train a GraphSAGE on graph g over a P-part mesh at BNS `rate`;
    return full-rate eval-mode validation accuracy.

    norm=None (no normalization) on purpose: a broken 1/ratio rescale is a
    SCALE bug, and LayerNorm is scale-invariant — under it the mutation is
    learnable-around (measured: 96.8% vs the 96.7% exact anchor) and the
    gate could never trip. Without normalization the train-time shrink of
    remote contributions mismatches the full-rate eval aggregates and the
    damage is visible. use_pp=False for the same reason: with the
    layer-0 aggregation precomputed exactly, a rescale mutation touches
    only hidden-layer refinements and measured as BENIGN shrinkage
    (96.8% vs 96.7% exact); without pp every layer — including the raw
    feature aggregation carrying most of the signal — rides the sampled
    exchange."""
    cfg = Config(model="graphsage", dropout=0.1, use_pp=use_pp,
                 norm=norm or "none",
                 n_train=g.n_train, lr=lr, sampling_rate=rate,
                 n_feat=g.n_feat, n_hidden=n_hidden, n_layers=n_layers,
                 n_class=g.n_class, spmm=spmm, spmm_gather=spmm_gather,
                 spmm_dense=spmm_dense, halo_wire=halo_wire)
    sizes = (g.n_feat,) + (n_hidden,) * (n_layers - 1) + (g.n_class,)
    spec = ModelSpec("graphsage", sizes, norm=norm, dropout=0.1,
                     use_pp=use_pp, train_size=g.n_train)
    mesh = make_parts_mesh(P)
    art = build_artifacts(g, partition_graph(g, P, method="random", seed=2))

    import contextlib
    ctx = _biased_pair_sample() if biased_sampler else contextlib.nullcontext()
    with ctx:
        fns, hspec, tables, tables_full = build_step_fns(cfg, spec, art, mesh)
        if break_rescale:
            # "forgot the 1/ratio": sampled remote activations arrive
            # unscaled, shrinking every remote contribution by ~rate
            tables = dict(tables)
            tables["inv_ratio"] = jnp.where(
                tables["inv_ratio"] > 0, 1.0, 0.0).astype(jnp.float32)
        blk_np = build_block_arrays(art, "graphsage")
        blk_np.update(fns.extra_blk)
        for k in fns.drop_blk_keys:
            blk_np.pop(k, None)
        blk = place_blocks(blk_np, mesh)
        tb = place_replicated(tables, mesh)
        tbf = place_replicated(tables_full, mesh)
        blk_eval = dict(blk)          # eval re-aggregates RAW features
        if use_pp:                    # run.py:171-178 gates this on use_pp
            blk["feat"] = fns.precompute(blk, tbf)
        params, state = init_params(jax.random.key(seed), spec)
        params = place_replicated(params, mesh)
        state = place_replicated(state, mesh)
        _, _, opt = init_training(cfg, spec, mesh)
        for e in range(epochs):
            params, state, opt, loss = fns.train_step(
                params, state, opt, jnp.uint32(e), blk, tb,
                # graftlint: disable=prng-literal-key(anchor runs pin keys so loss curves are comparable across commits)
                jax.random.key(0), jax.random.key(1))
        out = fns.eval_forward(params, state, blk_eval, tbf)
    logits = gather_parts(art, out)
    labels = gather_parts(art, art.label)
    mask = gather_parts(art, art.val_mask)
    return float(calc_acc(logits[mask], labels[mask]))
