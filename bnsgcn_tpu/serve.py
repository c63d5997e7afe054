"""Online inference serving: two-tier node prediction over a trained model.

The training repro becomes a system users hit: load a trained checkpoint
through the integrity chain (checkpoint.serving_checkpoint — never a torn
file), precompute the all-node embedding table through the SAME eval forward
the trainer reports accuracy with (evaluate.full_graph_embeddings), then
answer `score node v now` over a tiny line-JSON TCP protocol (the rank
coordinator's transport machinery, parallel/coord.LineJsonServer — one wire
framing for the whole repo).

Two serving tiers:

* **Tier A — table lookup.** The one-time precompute runs the embedding
  pass AND the final-layer scoring for every node; serving a clean node is
  a two-array row lookup (microseconds). Because the table IS the full-eval
  forward's output, tier-A scores are bitwise the full-eval logits
  (pinned by tests/test_serve.py).
* **Tier B — fresh L-hop re-aggregation.** A node whose neighborhood
  changed since the precompute is scored exactly: build the L-hop
  in-neighborhood closure (L = n_graph_layers), run the eval forward on
  that subgraph with GLOBAL degree norms. Concurrent requests are coalesced
  by a batcher thread into ONE padded step per bucket: node/edge counts pad
  to a power-of-two ladder, so there is one compiled program per bucket —
  the same static-shape padded-SpMM discipline as ELL training ("Fast
  Training of Sparse GNNs on Dense Hardware", PAPERS.md) — and a request
  scored alone equals the same request scored inside a full bucket.

**Delta ingestion** (DistGNN-style cached-embedding reuse, PAPERS.md):
`add_edges` / `update_feat` mutate the serving graph, mark the <= L-hop
FORWARD closure of the touched nodes dirty (every node whose logits can
have changed), and a background thread incrementally re-scores the dirty
set through the tier-B engine, writing fresh rows back into the table —
stale-but-bounded embeddings between refreshes, exact after.

**Graceful shutdown**: SIGTERM/SIGINT (resilience.PreemptSignals — the PR-4
handler) drains in-flight requests, flushes every ingested delta to a
resumable JSONL log under --serve-dir, and exits 75 (EXIT_PREEMPTED); a
relaunch replays the log so no accepted delta is ever lost.

CLI:  python -m bnsgcn_tpu.main serve --dataset ... --model ... \
          --ckpt-path ... --serve-port 18120
      (or python -m bnsgcn_tpu.serve ...)
Bench: tools/serve_bench.py — p50/p99 latency + QPS/chip per tier.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
from typing import Iterable, Optional

import numpy as np

from bnsgcn_tpu import checkpoint as ckpt
from bnsgcn_tpu import obs as obs_mod
from bnsgcn_tpu import resilience
from bnsgcn_tpu.config import Config, ConfigError, parse_config
from bnsgcn_tpu.data.graph import Graph
from bnsgcn_tpu.evaluate import _identity_exchange, full_graph_embeddings
from bnsgcn_tpu.models.gnn import (GraphEnv, ModelSpec, apply_model,
                                   init_params, spec_from_config)
from bnsgcn_tpu.parallel import coord as coord_mod

DELTA_LOG = "delta_log.jsonl"
SNAPSHOT = "serve_snapshot.blob"


class HaloCacheMiss(RuntimeError):
    """A tier-B subgraph build touched a remote halo row that is not (or no
    longer) in the local cache. Raised only under the core lock by the
    partition backend's graph (serve_backend.PartGraph): the fetch itself
    must happen OUTSIDE the lock (graph.prefetch) so a remote round trip
    can never stall concurrent predicts — the caller un-claims, re-runs
    prefetch, and retries the build."""


# ----------------------------------------------------------------------------
# embedding-table artifact (--dump-embeddings / cold start)
# ----------------------------------------------------------------------------

def save_table(path: str, hidden, logits, meta: Optional[dict] = None):
    """Write the all-node embedding table (penultimate activations +
    final-layer logits) under the checkpoint integrity header (magic +
    sha256, fsync-before-rename — checkpoint.write_blob), so a torn export
    can never cold-start a server with silently-wrong scores."""
    ckpt.write_blob(path, {
        "hidden": np.asarray(hidden),
        "logits": np.asarray(logits),
        "meta": meta or {},
    })


def load_table(path: str) -> tuple[np.ndarray, np.ndarray, dict]:
    """(hidden, logits, meta) — raises checkpoint.CheckpointCorrupt on a
    torn/zero-byte/checksum-failing artifact."""
    payload = ckpt.read_blob(path)
    return (np.asarray(payload["hidden"]), np.asarray(payload["logits"]),
            dict(payload.get("meta") or {}))


def promotion_admissible(cycle: int, adopted: int):
    """The monotonic adoption rule for continual-training promotions:
    (ok, reason). A cycle at or below the last adopted one is stale —
    adopting it would replay an older trainer's weights over newer ones
    (the split-brain the graftcheck-proto promotion-handshake scenario
    explores). One rule, shared by every adoption site, so the model
    checker and the server cannot drift apart."""
    if int(cycle) <= int(adopted):
        return False, f"stale cycle {int(cycle)} <= adopted {int(adopted)}"
    return True, ""


# ----------------------------------------------------------------------------
# the serving graph: base CSR + appended deltas
# ----------------------------------------------------------------------------

class DynamicGraph:
    """The server's mutable view of the (canonicalized) full graph: the base
    edges in two CSR indexes (in-neighbors for tier-B closures, out-
    neighbors for dirty-frontier marking) plus per-node append lists for
    ingested edges, and a mutable feature matrix. Degrees update with every
    delta, so tier-B norms are always the CURRENT global degrees."""

    def __init__(self, g: Graph):
        self.n_nodes = g.n_nodes
        self.feat = np.array(g.feat, dtype=np.float32, copy=True)
        self.in_deg = g.in_degrees().astype(np.int64).copy()
        self.out_deg = g.out_degrees().astype(np.int64).copy()
        src = np.asarray(g.src)
        dst = np.asarray(g.dst)
        order = np.argsort(dst, kind="stable")
        self._in_src = src[order].astype(np.int64)
        self._in_ptr = np.searchsorted(dst[order], np.arange(self.n_nodes + 1))
        order = np.argsort(src, kind="stable")
        self._out_dst = dst[order].astype(np.int64)
        self._out_ptr = np.searchsorted(src[order], np.arange(self.n_nodes + 1))
        self._extra_in: dict[int, list[int]] = {}
        self._extra_out: dict[int, list[int]] = {}

    def _check(self, *nodes: int):
        for v in nodes:
            if not 0 <= v < self.n_nodes:
                raise ValueError(f"node {v} out of range [0, {self.n_nodes})")

    # -- the scorer-facing graph protocol (shared with the partition
    # backend's PartGraph, which answers the same calls from a local shard
    # plus a remote-halo cache) --

    @property
    def n_feat(self) -> int:
        return self.feat.shape[1]

    def feat_rows(self, ids: np.ndarray) -> np.ndarray:
        return self.feat[ids]

    def in_deg_of(self, ids: np.ndarray) -> np.ndarray:
        return self.in_deg[ids]

    def out_deg_of(self, ids: np.ndarray) -> np.ndarray:
        return self.out_deg[ids]

    def prefetch(self, targets: Iterable[int], hops: int):
        """Single-host graph: every row is local — nothing to fetch."""

    def in_nbrs(self, v: int) -> list[int]:
        base = self._in_src[self._in_ptr[v]:self._in_ptr[v + 1]]
        extra = self._extra_in.get(v)
        return base.tolist() + extra if extra else base.tolist()

    def out_nbrs(self, v: int) -> list[int]:
        base = self._out_dst[self._out_ptr[v]:self._out_ptr[v + 1]]
        extra = self._extra_out.get(v)
        return base.tolist() + extra if extra else base.tolist()

    def add_edges(self, edges: Iterable[tuple[int, int]]) -> set[int]:
        """Append directed edges (u -> v); returns the touched node set the
        dirty marking expands from. u is touched even though only its OUT
        edge changed: its out-degree moves every existing out-neighbor's
        GCN out-norm, and the forward closure from u covers exactly them."""
        touched: set[int] = set()
        for u, v in edges:
            u, v = int(u), int(v)
            self._check(u, v)
            self._extra_out.setdefault(u, []).append(v)
            self._extra_in.setdefault(v, []).append(u)
            self.out_deg[u] += 1
            self.in_deg[v] += 1
            touched.add(u)
            touched.add(v)
        return touched

    def set_feat(self, v: int, vec) -> set[int]:
        self._check(int(v))
        vec = np.asarray(vec, dtype=np.float32)
        if vec.shape != self.feat[int(v)].shape:
            raise ValueError(f"feature length {vec.shape} != "
                             f"{self.feat[int(v)].shape}")
        self.feat[int(v)] = vec
        return {int(v)}

    def forward_closure(self, seeds: Iterable[int], hops: int) -> set[int]:
        """Nodes within `hops` out-edge steps of `seeds` (seeds included):
        the set of nodes whose final-layer output can depend on a change at
        the seeds — the <= L-hop dirty frontier."""
        seen = set(int(s) for s in seeds)
        frontier = list(seen)
        for _ in range(hops):
            nxt = []
            for v in frontier:
                for w in self.out_nbrs(v):
                    if w not in seen:
                        seen.add(w)
                        nxt.append(w)
            frontier = nxt
        return seen

    def in_closure(self, targets: Iterable[int], hops: int) -> dict[int, int]:
        """{node: depth} of the `hops`-hop in-neighborhood closure of
        `targets` (depth 0) — the exact computation subgraph of an L-layer
        forward at the targets: layer-l activations of a depth-d node are
        exact whenever d <= hops - l, which covers every value the targets'
        outputs consume."""
        depth = {int(t): 0 for t in targets}
        frontier = list(depth)
        for d in range(1, hops + 1):
            nxt = []
            for v in frontier:
                for u in self.in_nbrs(v):
                    if u not in depth:
                        depth[u] = d
                        nxt.append(u)
            frontier = nxt
        return depth

    # -- compaction support: the mutated state as a msgpack-able pytree --

    def mutation_state(self) -> dict:
        """Everything a relaunch needs to reconstruct this graph's mutations
        on top of the base CSR (which is rebuilt from the dataset): the
        current features/degrees plus the appended edges in per-node
        insertion order — in_nbrs()/out_nbrs() order (and thus tier-B
        accumulation order) survives the round trip exactly."""
        ein_v, ein_u = [], []
        for v in sorted(self._extra_in):
            for u in self._extra_in[v]:
                ein_v.append(v)
                ein_u.append(u)
        eout_u, eout_v = [], []
        for u in sorted(self._extra_out):
            for v in self._extra_out[u]:
                eout_u.append(u)
                eout_v.append(v)
        return {
            "feat": self.feat.copy(),
            "in_deg": self.in_deg.copy(),
            "out_deg": self.out_deg.copy(),
            "ein_v": np.asarray(ein_v, dtype=np.int64),
            "ein_u": np.asarray(ein_u, dtype=np.int64),
            "eout_u": np.asarray(eout_u, dtype=np.int64),
            "eout_v": np.asarray(eout_v, dtype=np.int64),
        }

    def restore_mutations(self, state: dict):
        """Inverse of mutation_state(), applied over a freshly-built base
        graph (degrees/features are restored wholesale, not re-derived)."""
        self.feat = np.array(state["feat"], dtype=np.float32, copy=True)
        self.in_deg = np.array(state["in_deg"], dtype=np.int64, copy=True)
        self.out_deg = np.array(state["out_deg"], dtype=np.int64, copy=True)
        self._extra_in = {}
        self._extra_out = {}
        for v, u in zip(np.asarray(state["ein_v"]).tolist(),
                        np.asarray(state["ein_u"]).tolist()):
            self._extra_in.setdefault(int(v), []).append(int(u))
        for u, v in zip(np.asarray(state["eout_u"]).tolist(),
                        np.asarray(state["eout_v"]).tolist()):
            self._extra_out.setdefault(int(u), []).append(int(v))


# ----------------------------------------------------------------------------
# tier-B engine: bucketed fresh-subgraph scoring
# ----------------------------------------------------------------------------

def _bucket(n: int, floor: int) -> int:
    b = floor
    while b < n:
        b <<= 1
    return b


class SubgraphScorer:
    """Exact L-hop re-scoring with static shapes: the closure subgraph pads
    to a (node, edge) bucket from a power-of-two ladder and runs ONE
    compiled eval forward per bucket — the training repo's padded-SpMM
    bucketing discipline applied to request batching. Padded edges use the
    repo-wide trash convention (dst == n_dst, src == 0); padded node rows
    get unit norms so no NaN can appear near real rows."""

    NODE_FLOOR = 32
    EDGE_FLOOR = 128

    def __init__(self, spec: ModelSpec, edge_chunk: int = 0):
        self.spec = spec
        self.hops = spec.n_graph_layers
        self.edge_chunk = edge_chunk
        self._fns: dict[tuple[int, int], callable] = {}

    def _fn(self, nb: int, eb: int):
        hit = self._fns.get((nb, eb))
        if hit is not None:
            return hit
        import jax

        spec, edge_chunk = self.spec, self.edge_chunk

        def run(params, state, feat, src, dst, in_norm, out_norm):
            env = GraphEnv(src=src, dst=dst, n_dst=nb, in_norm=in_norm,
                           out_norm=out_norm, exchange=_identity_exchange,
                           training=False, edge_chunk=edge_chunk)
            logits, _, hidden = apply_model(params, state, spec, feat, env,
                                            return_hidden=True)
            return hidden, logits

        fn = jax.jit(run)
        self._fns[(nb, eb)] = fn
        return fn

    def build_arrays(self, graph: DynamicGraph, targets: list[int]):
        """(nodes, feat, src, dst, in_norm, out_norm) — the padded closure
        subgraph of `targets`. Edges are grouped by destination in ascending
        global-id order with each destination's in-edges in stable CSR(+
        append) order, so a node's per-row accumulation order — and thus its
        score — is invariant to which other requests share the bucket."""
        depth = graph.in_closure(targets, self.hops)
        nodes = sorted(depth)
        local = {g: i for i, g in enumerate(nodes)}
        src_l: list[int] = []
        dst_l: list[int] = []
        inner = self.hops - 1
        for v in nodes:
            if depth[v] <= inner:
                lv = local[v]
                for u in graph.in_nbrs(v):
                    src_l.append(local[u])
                    dst_l.append(lv)
        nb = _bucket(len(nodes), self.NODE_FLOOR)
        eb = _bucket(max(len(src_l), 1), self.EDGE_FLOOR)
        ids = np.asarray(nodes, dtype=np.int64)
        feat = np.zeros((nb, graph.n_feat), dtype=np.float32)
        feat[:len(nodes)] = graph.feat_rows(ids)
        src = np.zeros(eb, dtype=np.int32)
        dst = np.full(eb, nb, dtype=np.int32)          # trash row
        src[:len(src_l)] = src_l
        dst[:len(dst_l)] = dst_l
        in_norm = np.ones(nb, dtype=np.float32)
        out_norm = np.ones(nb, dtype=np.float32)
        ind = graph.in_deg_of(ids).astype(np.float32)
        outd = graph.out_deg_of(ids).astype(np.float32)
        if self.spec.model == "gcn":
            in_norm[:len(nodes)] = np.sqrt(ind)
            out_norm[:len(nodes)] = np.sqrt(outd)
        else:
            in_norm[:len(nodes)] = ind
            out_norm[:len(nodes)] = outd               # unused by SAGE/GAT
        return nodes, feat, src, dst, in_norm, out_norm

    def run_arrays(self, params, state, targets: list[int], arrays
                   ) -> dict[int, tuple[np.ndarray, np.ndarray]]:
        """The compiled half: score pre-built subgraph arrays (the caller
        may have snapshotted them under its graph lock; the jit dispatch
        itself needs no lock)."""
        nodes, feat, src, dst, in_norm, out_norm = arrays
        fn = self._fn(feat.shape[0], src.shape[0])
        hidden, logits = fn(params, state, feat, src, dst, in_norm, out_norm)
        hidden = np.asarray(hidden)
        logits = np.asarray(logits)
        local = {g: i for i, g in enumerate(nodes)}
        return {t: (hidden[local[int(t)]], logits[local[int(t)]])
                for t in targets}

    def score(self, graph: DynamicGraph, params, state, targets: list[int]
              ) -> dict[int, tuple[np.ndarray, np.ndarray]]:
        """{node: (hidden_row, logits_row)} — exact under the graph's
        current edges/features/degrees."""
        arrays = self.build_arrays(graph, targets)
        return self.run_arrays(params, state, targets, arrays)


class _TierBBatcher:
    """Coalesces concurrent tier-B requests into one bucket step: handler
    threads enqueue and wait; one worker thread drains up to `max_batch`
    targets per step after a short accumulation window. One compiled
    program per bucket shape serves every request that shares it."""

    def __init__(self, score_fn, max_batch: int, window_s: float = 0.002):
        self._score_fn = score_fn
        self.max_batch = max(int(max_batch), 1)
        self.window_s = window_s
        self._pending: list[tuple[int, dict, threading.Event]] = []  # guarded-by: self._cv
        self._cv = threading.Condition()
        self._stop = False      # guarded-by: self._cv
        self.batches = 0
        self.batched_requests = 0
        self._thread = threading.Thread(target=self._run,
                                        name="bnsgcn-serve-batcher",
                                        daemon=True)
        self._thread.start()

    def submit(self, node: int, timeout_s: float = 120.0):
        box: dict = {}
        ev = threading.Event()
        with self._cv:
            if self._stop:
                raise RuntimeError("server draining")
            self._pending.append((int(node), box, ev))
            self._cv.notify()
        if not ev.wait(timeout_s):
            raise TimeoutError(f"tier-B scoring of node {node} timed out")
        if "err" in box:
            raise RuntimeError(box["err"])
        return box["r"]

    def _run(self):
        while True:
            with self._cv:
                while not self._pending and not self._stop:
                    self._cv.wait(0.1)
                if self._stop and not self._pending:
                    return
            if self.window_s > 0:
                time.sleep(self.window_s)       # let concurrent arrivals pool
            with self._cv:
                batch = self._pending[:self.max_batch]
                del self._pending[:len(batch)]
            if not batch:
                continue
            targets = sorted({n for n, _, _ in batch})
            try:
                results = self._score_fn(targets)
                self.batches += 1
                self.batched_requests += len(batch)
                for node, box, ev in batch:
                    box["r"] = results[node]
                    ev.set()
            except Exception as ex:             # noqa: BLE001 — answer, don't die
                for _, box, ev in batch:
                    box["err"] = f"{type(ex).__name__}: {ex}"
                    ev.set()

    def drain(self, timeout_s: float = 30.0):
        """Stop accepting, finish what is queued, join the worker."""
        with self._cv:
            self._stop = True
            self._cv.notify()
        self._thread.join(timeout=timeout_s)


# ----------------------------------------------------------------------------
# the serving core: table + dirty frontier + delta ingestion
# ----------------------------------------------------------------------------

class ServeCore:
    """Protocol-independent serving state machine (the TCP layer below is a
    thin dispatcher over it; tests drive it directly). Owns the embedding/
    score table, the dirty set, the ingested-delta journal and the tier-B
    batcher. All public methods are thread-safe."""

    def __init__(self, cfg: Config, spec: ModelSpec, graph: DynamicGraph,
                 params, state, hidden: np.ndarray, logits: np.ndarray,
                 log=print, obs: Optional[obs_mod.Obs] = None):
        self.cfg = cfg
        self.spec = spec
        self.graph = graph
        self._check_table(hidden, logits)
        self.params = params
        self.state = state
        self.hidden = hidden
        self.logits = logits
        self.hops = spec.n_graph_layers
        self.log = log
        # registry-backed serving metrics (obs.py): per-tier latency
        # histograms (p50/p99 without sample storage), refresh-lag, queue
        # depth. The registry exists even without an event log — `stats`
        # and the `metrics` op serve it over the wire either way.
        self.obs = obs
        self.registry = obs.registry if obs is not None else obs_mod.Registry()
        self._lat = {t: self.registry.histogram(f"serve/latency_ms/{t}")
                     for t in ("A", "B")}
        self._lag_hist = self.registry.histogram("serve/refresh_lag_s")
        # node -> first dirty ts        # guarded-by: self._lock
        self._dirty_since: dict[int, float] = {}
        self.scorer = SubgraphScorer(spec, edge_chunk=cfg.edge_chunk)
        self.dirty: set[int] = set()        # guarded-by: self._lock
        self._refreshing: set[int] = set()  # guarded-by: self._lock
                                        # claimed by an in-flight refresh
                                        # step: still stale for tier routing,
                                        # but never double-picked (the
                                        # background refresher and a client
                                        # 'flush' must not score the same
                                        # nodes twice)
        self.deltas: list[dict] = []        # guarded-by: self._lock
        self._lock = threading.RLock()
        # guarded-by: self._lock
        self.stats = {"requests": 0, "tier_a": 0, "tier_b": 0,
                      "refreshed_nodes": 0, "deltas": 0}
        # delta-log compaction (--serve-compact-deltas): where the snapshot
        # and tail log live (set by the CLI entry point; "" disables), the
        # per-core artifact names (backends shard them per part/replica),
        # the deltas-folded-into-snapshot count, and an overlap guard
        self.serve_dir = ""
        self._delta_log_name = DELTA_LOG
        self._snapshot_name = SNAPSHOT
        self._folded = 0            # guarded-by: self._lock
        self._compacting = False    # guarded-by: self._lock
        # continual-training promotion: last adopted lineage cycle — the
        # monotonic check that rejects stale/double promotes (split-brain
        # guard: two backends can never end up on different cycles because
        # a replayed older promote is refused, not re-adopted)
        self._promoted_cycle = 0    # guarded-by: self._lock
        self.stats["exported_to"] = 0
        self.stats["promotions"] = 0
        self.batcher = _TierBBatcher(self._score_batch, cfg.serve_max_batch)

    def _check_table(self, hidden: np.ndarray, logits: np.ndarray):
        """Table rows must cover this core's graph — overridden by the
        partition backend, whose table is a shard (n_own rows), not the
        full node set."""
        if (hidden.shape[0] != self.graph.n_nodes
                or logits.shape[0] != self.graph.n_nodes):
            raise ConfigError(
                f"embedding table rows ({hidden.shape[0]}/{logits.shape[0]}) "
                f"!= graph nodes ({self.graph.n_nodes}) — wrong --embeddings "
                f"artifact for this dataset?")

    def _row(self, node: int) -> int:
        """Table row index for a global node id (identity here; the
        partition backend maps global id -> local shard row)."""
        return node

    # -- scoring --

    def _score_batch(self, targets: list[int]):
        """One bucket step for `targets`: claim the dirty ones (no
        concurrent step may double-score them), snapshot the subgraph
        arrays UNDER the lock (a delta landing mid-build can never tear the
        snapshot), run the compiled step outside it, then write fresh
        (hidden, logits) back for every claimed node that was NOT
        re-dirtied while the step ran — a newer delta's mark always wins
        over a stale result. Clean targets are never written back: the
        table row stays the precompute's full-eval output (tier A's
        bitwise contract).

        The halo dance (partition backends only; no-ops on a single-host
        graph): remote rows the closure needs are fetched OUTSIDE the lock
        (graph.prefetch — peer round trips must never stall concurrent
        predicts, and peers answer `resolve` under only their own short
        lock, so no distributed lock cycle can form). The locked build is
        then cache-only; a delta invalidating a cached row between
        prefetch and build raises HaloCacheMiss and the claim/prefetch/
        build is retried."""
        for attempt in range(4):
            self.graph.prefetch(targets, self.hops)
            with self._lock:
                was_dirty = [t for t in targets if t in self.dirty]
                self.dirty.difference_update(was_dirty)
                self._refreshing.update(was_dirty)
                try:
                    arrays = self.scorer.build_arrays(self.graph, targets)
                except HaloCacheMiss:
                    self._refreshing.difference_update(was_dirty)
                    self.dirty.update(was_dirty)
                    if attempt == 3:
                        raise
                    continue
            break
        try:
            results = self.scorer.run_arrays(self.params, self.state,
                                             targets, arrays)
        except Exception:
            with self._lock:                # a failed step re-queues its claim
                self._refreshing.difference_update(was_dirty)
                self.dirty.update(was_dirty)
            raise
        with self._lock:
            now = time.monotonic()
            self._refreshing.difference_update(was_dirty)
            for t in was_dirty:
                if t in self.dirty:         # re-dirtied mid-step: stale, skip
                    continue
                hid, lg = results[t]
                self.hidden[self._row(t)] = hid
                self.logits[self._row(t)] = lg
                self.stats["refreshed_nodes"] += 1
                since = self._dirty_since.pop(t, None)
                if since is not None:
                    # refresh lag: how stale this row got before the fresh
                    # score landed (the bounded-staleness figure the delta
                    # pipeline promises)
                    self._lag_hist.observe(now - since)
        return results

    def predict(self, node: int, tier: Optional[str] = None) -> dict:
        t_in = time.perf_counter()
        node = int(node)
        self.graph._check(node)
        with self._lock:
            self.stats["requests"] += 1
            # a node claimed by an in-flight refresh step is still stale in
            # the table — route it tier B like any other dirty node
            is_dirty = node in self.dirty or node in self._refreshing
        if tier == "A" or (tier is None and not is_dirty):
            with self._lock:
                self.stats["tier_a"] += 1
                scores = self.logits[self._row(node)].copy()
            out = {"ok": True, "node": node, "tier": "A",
                   "scores": scores.tolist()}
            if is_dirty:
                out["stale"] = True     # forced tier A on a dirty node
        else:
            _, lg = self.batcher.submit(node)
            with self._lock:
                self.stats["tier_b"] += 1
            out = {"ok": True, "node": node, "tier": "B",
                   "scores": np.asarray(lg).tolist()}
        if not self.cfg.multilabel:
            out["pred"] = int(np.argmax(out["scores"]))
        self._lat[out["tier"]].observe((time.perf_counter() - t_in) * 1e3)
        return out

    def predict_many(self, nodes, tier: Optional[str] = None) -> list[dict]:
        """Batch predict: the whole request's tier-B set runs as coalesced
        bucket steps directly (the caller already holds the full target
        list — routing each node through the batcher one-by-one would
        serialize what this subsystem exists to coalesce)."""
        t_in = time.perf_counter()
        nodes = [int(n) for n in nodes]
        for n in nodes:
            self.graph._check(n)
        with self._lock:
            self.stats["requests"] += len(nodes)
            stale = {n for n in nodes
                     if n in self.dirty or n in self._refreshing}
        fresh = sorted({n for n in nodes if tier == "B" or n in stale})
        scored: dict[int, tuple] = {}
        t_b0 = time.perf_counter()
        for i in range(0, len(fresh), self.cfg.serve_max_batch):
            scored.update(self._score_batch(
                fresh[i:i + self.cfg.serve_max_batch]))
        t_b = time.perf_counter() - t_b0
        # per-tier attribution: the bucket-step time belongs to the tier-B
        # nodes only — smearing the whole call over both tiers would inflate
        # the tier-A percentiles ~1000x (a row lookup vs a compiled forward)
        n_b = sum(1 for n in nodes if n in scored)
        n_a = len(nodes) - n_b
        per_b_ms = t_b * 1e3 / max(n_b, 1)
        per_a_ms = ((time.perf_counter() - t_in - t_b) * 1e3 / max(n_a, 1))
        out = []
        for n in nodes:
            if n in scored:
                r = {"ok": True, "node": n, "tier": "B",
                     "scores": np.asarray(scored[n][1]).tolist()}
                with self._lock:
                    self.stats["tier_b"] += 1
            else:
                with self._lock:
                    self.stats["tier_a"] += 1
                    scores = self.logits[self._row(n)].copy()
                r = {"ok": True, "node": n, "tier": "A",
                     "scores": scores.tolist()}
                if n in stale:
                    r["stale"] = True       # forced tier A on a dirty node
            if not self.cfg.multilabel:
                r["pred"] = int(np.argmax(r["scores"]))
            self._lat[r["tier"]].observe(per_b_ms if r["tier"] == "B"
                                         else per_a_ms)
            out.append(r)
        return out

    # -- delta ingestion --

    def _mark_dirty_stamps_locked(self, new_dirty: set):
        """First-dirty timestamps for the refresh-lag figure (setdefault:
        a node already waiting keeps its ORIGINAL staleness clock)."""
        now = time.monotonic()
        for n in new_dirty:
            self._dirty_since.setdefault(n, now)

    def add_edges(self, edges: list) -> dict:
        pairs = [(int(u), int(v)) for u, v in edges]
        with self._lock:
            touched = self.graph.add_edges(pairs)
            new_dirty = self.graph.forward_closure(touched, self.hops)
            added = new_dirty - self.dirty
            self.dirty |= new_dirty
            self._mark_dirty_stamps_locked(new_dirty)
            self.deltas.append({"op": "add_edges",
                                "edges": [[u, v] for u, v in pairs]})
            self.stats["deltas"] += 1
            out = {"ok": True, "dirty_new": len(added),
                   "dirty_total": len(self.dirty)}
        if self.obs is not None:
            # OUTSIDE the core lock: a stalled telemetry write (slow/NFS
            # log disk) must never block concurrent predicts behind a delta
            self.obs.emit("delta", op="add_edges", edges=len(pairs),
                          dirty_new=out["dirty_new"],
                          dirty_total=out["dirty_total"])
        return out

    def update_feat(self, node: int, vec) -> dict:
        with self._lock:
            touched = self.graph.set_feat(int(node), vec)
            new_dirty = self.graph.forward_closure(touched, self.hops)
            added = new_dirty - self.dirty
            self.dirty |= new_dirty
            self._mark_dirty_stamps_locked(new_dirty)
            self.deltas.append({"op": "update_feat", "node": int(node),
                                "feat": np.asarray(
                                    vec, dtype=np.float32).tolist()})
            self.stats["deltas"] += 1
            out = {"ok": True, "dirty_new": len(added),
                   "dirty_total": len(self.dirty)}
        if self.obs is not None:
            self.obs.emit("delta", op="update_feat", node=int(node),
                          dirty_new=out["dirty_new"],
                          dirty_total=out["dirty_total"])
        return out

    # -- incremental refresh --

    def refresh_some(self, limit: Optional[int] = None) -> int:
        """Re-score up to `limit` dirty nodes (ascending id — deterministic)
        through the tier-B engine and fold the fresh rows back into the
        table. Returns how many nodes were picked."""
        limit = limit if limit is not None else self.cfg.serve_max_batch
        with self._lock:
            pick = sorted(self.dirty)[:max(int(limit), 1)]
        if not pick:
            return 0
        self._score_batch(pick)
        return len(pick)

    def flush(self, timeout_s: float = 600.0) -> int:
        """Drain the whole dirty set synchronously (including claims held
        by a concurrent refresh step); returns nodes this call picked."""
        total = 0
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            total += self.refresh_some()
            with self._lock:
                if not self.dirty and not self._refreshing:
                    return total
                busy = not self.dirty       # only claims in flight elsewhere
            if busy:
                time.sleep(0.005)           # let the owning step finish
        # graftlint: disable=lock-unguarded-access(best-effort count in a timeout message; a torn read costs nothing)
        raise TimeoutError(f"flush: {len(self.dirty)} nodes still dirty")

    # -- resumable delta log --

    def flush_delta_log(self, serve_dir: str) -> str:
        """Atomically persist every un-compacted delta as JSONL (snapshot +
        this log resumes the server's exact state on relaunch; with
        compaction off the log alone is the full history)."""
        os.makedirs(serve_dir, exist_ok=True)
        path = os.path.join(serve_dir, self._delta_log_name)
        with self._lock:
            lines = [json.dumps(d) for d in self.deltas]
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            f.write("\n".join(lines) + ("\n" if lines else ""))
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
        return path

    def _apply_logged(self, d: dict):
        """Re-ingest one logged delta (the partition backend extends the
        op set with its fan-out entries)."""
        if d["op"] == "add_edges":
            self.add_edges(d["edges"])
        elif d["op"] == "update_feat":
            self.update_feat(d["node"], d["feat"])

    def replay_delta_log(self, serve_dir: str) -> int:
        """Re-ingest a previous run's flushed deltas (marks the dirty
        frontier again; the background refresh re-scores it). Returns the
        number of deltas replayed."""
        path = os.path.join(serve_dir, self._delta_log_name)
        if not os.path.exists(path):
            return 0
        n = 0
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                self._apply_logged(json.loads(line))
                n += 1
        return n

    # -- delta-log compaction (--serve-compact-deltas) --

    def maybe_compact(self):
        """Fold the delta log into an integrity-headed snapshot once it
        crosses the configured threshold, so a relaunch replays only the
        tail instead of every delta ever ingested. Called on the delta
        ingestion path (the ingesting client pays the snapshot write;
        concurrent predicts keep running — the blob write happens outside
        the core lock)."""
        if self.cfg.serve_compact_deltas <= 0 or not self.serve_dir:
            return
        with self._lock:
            if (self._compacting
                    or len(self.deltas) < self.cfg.serve_compact_deltas):
                return
            self._compacting = True
        try:
            self.compact(self.serve_dir)
        finally:
            with self._lock:
                self._compacting = False

    def compact(self, serve_dir: str) -> dict:
        """Snapshot the mutated graph + tables + dirty frontier (write_blob:
        magic + sha256, fsync-before-rename) and truncate the log to the
        deltas that arrived after the snapshot point."""
        os.makedirs(serve_dir, exist_ok=True)
        with self._lock:
            k = len(self.deltas)
            payload = self.graph.mutation_state()
            payload["hidden"] = self.hidden.copy()
            payload["logits"] = self.logits.copy()
            payload["dirty"] = np.asarray(
                sorted(self.dirty | self._refreshing), dtype=np.int64)
            payload["n_deltas"] = int(self._folded + k)
        ckpt.write_blob(os.path.join(serve_dir, self._snapshot_name), payload)
        with self._lock:
            # deltas that landed while the blob was writing stay in the tail
            # (graph state + first-k deltas were captured under one lock
            # hold, so snapshot + tail is exactly the full history)
            del self.deltas[:k]
            self._folded += k
            tail = len(self.deltas)
        self.flush_delta_log(serve_dir)
        out = {"folded": k, "tail": tail}
        name = self._snapshot_name
        if self.obs is not None:
            self.obs.emit("serve_compact", **out)
        self.log(f"[serve] compacted delta log: {k} delta(s) folded into "
                 f"{name}, {tail} left in the tail")
        return out

    def load_serving_state(self, serve_dir: str) -> dict:
        """Relaunch path: adopt the compaction snapshot if one exists
        (mutated graph + tables + dirty frontier — O(snapshot)), then
        replay the tail log. A corrupt snapshot raises CheckpointCorrupt
        loudly: the log is only a tail, so silently skipping the snapshot
        would resume from a hole in history."""
        snap = os.path.join(serve_dir, self._snapshot_name)
        folded = 0
        if os.path.exists(snap):
            payload = ckpt.read_blob(snap)
            hidden = np.array(payload["hidden"], copy=True)
            logits = np.array(payload["logits"], copy=True)
            self.graph.restore_mutations(payload)
            self._check_table(hidden, logits)
            folded = int(payload["n_deltas"])
            with self._lock:
                self.hidden = hidden
                self.logits = logits
                dirty = set(np.asarray(payload["dirty"]).tolist())
                self.dirty |= dirty
                self._mark_dirty_stamps_locked(dirty)
                self._folded = folded
                self.stats["deltas"] += folded
            self.log(f"[serve] snapshot {self._snapshot_name}: "
                     f"{folded} folded delta(s), {len(dirty)} node(s) dirty")
        return {"folded": folded,
                "replayed": self.replay_delta_log(serve_dir)}

    # -- continual training: delta export handshake + promotion adoption --

    def export_deltas(self, cursor: int = 0) -> dict:
        """Atomically hand the journal tail past `cursor` (an absolute delta
        count the continual trainer owns) to a continual cycle, and mark the
        handoff point. One lock hold snapshots (folded, tail) together, so a
        delta landing mid-export gets an absolute position >= the returned
        `total` and is picked up by the next cycle — never double-consumed,
        never dropped. When compaction already folded deltas past `cursor`
        the individual entries are gone; `snapshot_required` tells the
        trainer to resync from the snapshot blob + tail instead."""
        cursor = int(cursor)
        with self._lock:
            folded = self._folded
            total = folded + len(self.deltas)
            if cursor > total:
                return {"ok": False,
                        "err": f"export cursor {cursor} ahead of journal "
                               f"total {total}"}
            if cursor < folded:
                out = {"ok": True, "snapshot_required": True,
                       "folded": folded, "total": total, "from": cursor,
                       "deltas": []}
            else:
                out = {"ok": True, "snapshot_required": False,
                       "folded": folded, "total": total, "from": cursor,
                       "deltas": [dict(d) for d in
                                  self.deltas[cursor - folded:]]}
            self.stats["exported_to"] = total
        if self.obs is not None:
            self.obs.emit("delta", op="export", start=cursor, total=total,
                          handed=len(out["deltas"]),
                          snapshot_required=bool(out["snapshot_required"]))
        return out

    def _adopt_table_locked(self, hidden: np.ndarray, logits: np.ndarray):
        """Swap in a promoted full-graph table (the partition backend
        overrides this to slice its own shard rows)."""
        self._check_table(hidden, logits)
        self.hidden = hidden
        self.logits = logits

    def _tail_redirty_locked(self, tail: list) -> set:
        """Dirty set owed to journal entries the promoted table has not
        seen: the forward closure of their touched nodes. The partition
        backend overrides this (its journal speaks the fan-out op set and
        its graph walks closures shard-locally)."""
        touched: set = set()
        for d in tail:
            if d.get("op") == "add_edges":
                for u, v in d["edges"]:
                    touched.add(int(u))
                    touched.add(int(v))
            elif d.get("op") == "update_feat":
                touched.add(int(d["node"]))
        return (self.graph.forward_closure(touched, self.hops)
                if touched else set())

    def promote(self, path: str) -> dict:
        """Adopt a refreshed promotion blob (checkpoint.write_promotion) at
        a drain boundary: the swap happens under one core-lock hold, atomic
        with respect to every concurrent predict/delta. Rollback semantics:
        a blob that fails the integrity chain, carries a stale (non-
        monotonic) cycle, or mismatches the table shape is rejected and the
        prior params/table stay live.

        Consistency after adoption: the promoted table is the full-graph
        eval of the mutated graph at the trainer's consumed-delta cursor.
        Nodes outside the forward closure of the deltas past that cursor
        have identical L-hop neighborhoods in both graphs, so their rows
        are exact; everything inside the closure is re-marked dirty (and
        in-flight refresh claims are re-dirtied so a stale old-params
        result can never land in the new table)."""
        def _reject(reason: str, rolled_back: bool = True) -> dict:
            self.log(f"[serve] promotion rejected ({reason}); "
                     f"keeping prior table")
            if self.obs is not None:
                self.obs.emit("promote", status="rejected", reason=reason,
                              path=path)
            return {"ok": False, "err": f"promotion rejected: {reason}",
                    "rolled_back": rolled_back}

        try:
            payload = ckpt.read_promotion(path)
        except (ckpt.CheckpointCorrupt, OSError) as ex:
            return _reject(str(ex))
        from flax import serialization
        lin = payload["lineage"]
        cycle = int(lin["cycle"])
        consumed = int(lin.get("consumed", 0))
        hidden = np.array(payload["hidden"], copy=True)
        logits = np.array(payload["logits"], copy=True)
        with self._lock:
            ok, stale = promotion_admissible(cycle, self._promoted_cycle)
        if not ok:
            return _reject(stale, rolled_back=False)
        try:
            params = serialization.from_state_dict(self.params,
                                                   payload["params"])
            state = (serialization.from_state_dict(self.state,
                                                   payload["bn_state"])
                     if payload.get("bn_state") else self.state)
        except (KeyError, ValueError, TypeError) as ex:
            return _reject(f"params do not restore into the serving model "
                           f"({type(ex).__name__}: {ex})")
        with self._lock:
            # re-check under the final lock: raced another promote
            ok, stale = promotion_admissible(cycle, self._promoted_cycle)
            stale = None if ok else stale
            if stale is None:
                try:
                    self._adopt_table_locked(hidden, logits)
                except ConfigError as ex:
                    stale = str(ex)
            if stale is None:
                self.params = params
                self.state = state
                self._promoted_cycle = cycle
                tail = self.deltas[max(consumed - self._folded, 0):]
                redirty = self._tail_redirty_locked(tail)
                redirty |= set(self._refreshing)
                self.dirty = set(redirty)
                self._dirty_since = {n: t for n, t
                                     in self._dirty_since.items()
                                     if n in redirty}
                self._mark_dirty_stamps_locked(redirty)
                self.stats["promotions"] += 1
                n_dirty = len(self.dirty)
                n_tail = len(tail)
        if stale is not None:
            return _reject(stale, rolled_back=False)
        self.log(f"[serve] promoted cycle {cycle}: refreshed table adopted "
                 f"({n_tail} unconsumed delta(s) re-marked, {n_dirty} "
                 f"node(s) dirty)")
        if self.obs is not None:
            self.obs.emit("promote", status="adopted", cycle=cycle,
                          consumed=consumed, tail=n_tail, dirty=n_dirty,
                          path=path)
        return {"ok": True, "cycle": cycle, "tail": n_tail,
                "dirty": n_dirty}

    def snapshot_stats(self) -> dict:
        with self._lock:
            out = dict(self.stats)
            out["dirty"] = len(self.dirty) + len(self._refreshing)
            out["n_nodes"] = self.graph.n_nodes
            out["batches"] = self.batcher.batches
            out["batched_requests"] = self.batcher.batched_requests
            # registry-backed figures (previously: counters only) — the
            # per-tier latency percentiles serve_bench cross-checks its
            # client-side numbers against, the current refresh lag (age of
            # the stalest dirty row), and the batcher queue depth
            now = time.monotonic()
            out["refresh_lag_s"] = round(
                now - min(self._dirty_since.values()), 6) \
                if self._dirty_since else 0.0
            out["queue_depth"] = len(self.batcher._pending)
        for t in ("A", "B"):
            snap = self._lat[t].snapshot()
            out[f"tier_{t.lower()}_p50_ms"] = snap["p50"]
            out[f"tier_{t.lower()}_p99_ms"] = snap["p99"]
        lag = self._lag_hist.snapshot()
        out["refresh_lag_p50_s"] = lag["p50"]
        out["refresh_lag_p99_s"] = lag["p99"]
        # mirror the headline gauges into the registry so the `metrics` op
        # (full snapshot) always reports current depth/lag too. The gauge
        # name differs from the 'serve/refresh_lag_s' HISTOGRAM on purpose:
        # the gauge is the age of the stalest currently-dirty row, the
        # histogram the per-row dirty->refreshed latency distribution
        self.registry.gauge("serve/queue_depth").set(out["queue_depth"])
        self.registry.gauge("serve/stalest_dirty_age_s").set(
            out["refresh_lag_s"])
        self.registry.gauge("serve/dirty").set(out["dirty"])
        return out

    def close(self):
        self.batcher.drain()


# ----------------------------------------------------------------------------
# TCP front end (parallel/coord.py's line-JSON transport)
# ----------------------------------------------------------------------------

class ServeServer:
    """Thin line-JSON dispatcher over a ServeCore on the coordinator's
    LineJsonServer (one JSON request line per connection, one JSON response
    line — the exact framing tests and tools already speak)."""

    def __init__(self, core: ServeCore, port: int, addr: str = "",
                 log=print):
        self.core = core
        self.log = log
        self._inflight = 0      # guarded-by: self._lock
        self._draining = False  # guarded-by: self._lock
        self._lock = threading.Lock()
        self.shutdown_requested = threading.Event()
        self.server = coord_mod.LineJsonServer(port, self._handle,
                                               addr=addr).start()

    @property
    def port(self) -> int:
        return self.server.port

    def _handle(self, req: dict) -> dict:
        op = req.get("op")
        with self._lock:
            if self._draining and op not in ("ping", "stats", "metrics"):
                return {"ok": False, "err": "draining"}
            self._inflight += 1
        try:
            return self._dispatch(op, req)
        except (KeyError, ValueError, TypeError) as ex:
            return {"ok": False, "err": f"{type(ex).__name__}: {ex}"}
        finally:
            with self._lock:
                self._inflight -= 1

    def _dispatch(self, op: Optional[str], req: dict) -> dict:
        """One op -> one response dict (subclassed by the partition
        backend's server, which extends the op set)."""
        if op == "ping":
            return {"ok": True}
        if op == "predict":
            return self.core.predict(req["node"], tier=req.get("tier"))
        if op == "predict_many":
            return {"ok": True,
                    "results": self.core.predict_many(
                        req["nodes"], tier=req.get("tier"))}
        if op == "add_edges":
            out = self.core.add_edges(req["edges"])
            self.core.maybe_compact()
            return out
        if op == "update_feat":
            out = self.core.update_feat(req["node"], req["feat"])
            self.core.maybe_compact()
            return out
        if op == "export_deltas":
            out = self.core.export_deltas(req.get("cursor", 0))
            if out.get("ok") and self.core.serve_dir:
                # mirror the handoff point on disk: a trainer reading the
                # journal file after a crash sees exactly the exported tail
                self.core.flush_delta_log(self.core.serve_dir)
            return out
        if op == "promote":
            return self.core.promote(req["path"])
        if op == "dirty":
            # include in-flight refresh claims: a claimed node is still
            # stale in the table (same accounting as snapshot_stats) —
            # dirty == 0 must mean "every row is fresh", not "the
            # background refresher happens to hold the last few"
            with self.core._lock:
                n = len(self.core.dirty) + len(self.core._refreshing)
            return {"ok": True, "count": n}
        if op == "flush":
            return {"ok": True, "refreshed": self.core.flush()}
        if op == "stats":
            return {"ok": True, **self.core.snapshot_stats()}
        if op == "metrics":
            # the full registry snapshot (counters, gauges, histograms
            # incl. per-tier p50/p90/p99) — the machine-readable twin
            # of 'stats' for dashboards/scrapers
            self.core.snapshot_stats()      # refresh the gauges first
            return {"ok": True, "metrics": self.core.registry.snapshot()}
        if op == "shutdown":
            self.shutdown_requested.set()
            return {"ok": True}
        return {"ok": False, "err": f"unknown op {op!r}"}

    def drain(self, timeout_s: float = 30.0):
        """Stop accepting new work, wait for in-flight handlers, stop the
        listener — the graceful half of the SIGTERM exit."""
        with self._lock:
            self._draining = True
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            with self._lock:
                if self._inflight == 0:
                    break
            time.sleep(0.01)
        self.server.stop()


def request(port: int, payload: dict, addr: str = "127.0.0.1",
            timeout_s: float = 30.0) -> dict:
    """One client round trip against a running serve server (shared by
    tools/serve_bench.py and the tests). At-most-once: serve ops mutate
    (add_edges, update_feat) or are expensive to double-start (flush), so
    a sent request is never silently re-sent — connect failures still
    retry until the deadline, and the response wait spans the whole
    deadline (a long flush must not be abandoned at a 10 s read cap)."""
    return coord_mod.rpc_line_json(addr or "127.0.0.1", port, payload,
                                   time.monotonic() + timeout_s,
                                   what="serve server", retry_sent=False)


# ----------------------------------------------------------------------------
# construction + CLI
# ----------------------------------------------------------------------------

def build_core(cfg: Config, g: Graph, params, state, log=print,
               hidden: Optional[np.ndarray] = None,
               logits: Optional[np.ndarray] = None,
               obs: Optional[obs_mod.Obs] = None) -> ServeCore:
    """ServeCore over graph `g` with a precomputed (or supplied) table."""
    cfg = cfg.replace(n_feat=g.n_feat, n_class=g.n_class, n_train=g.n_train)
    spec = spec_from_config(cfg)
    if hidden is None or logits is None:
        t0 = time.perf_counter()
        hidden, logits = full_graph_embeddings(params, state, spec, g,
                                               cfg.edge_chunk)
        log(f"[serve] precomputed {hidden.shape[0]}-node embedding table "
            f"(hidden {hidden.shape[1]}, classes {logits.shape[1]}) in "
            f"{time.perf_counter() - t0:.1f}s")
    return ServeCore(cfg, spec, DynamicGraph(g), params, state,
                     np.array(hidden, copy=True), np.array(logits, copy=True),
                     log=log, obs=obs)


def _load_model(cfg: Config, log) -> tuple:
    """(params, state, payload, path) through the integrity chain — the
    same selection entry point as resume (checkpoint.serving_checkpoint),
    so serve can never adopt a torn file."""
    found = ckpt.serving_checkpoint(cfg, log=log)
    if found is None:
        raise ConfigError(
            f"no loadable checkpoint for graph {cfg.graph_name!r} rate "
            f"{cfg.sampling_rate:.2f} under {cfg.ckpt_path} — train first, "
            f"or point --ckpt-path at a finished run")
    path, payload = found
    import jax
    spec = spec_from_config(cfg)
    params_t, state_t = init_params(jax.random.key(
        int(payload.get("seed", 0))), spec)
    params, _, state = ckpt.restore_into(payload, params_t, None, state_t)
    log(f"[serve] checkpoint {path}: epoch {int(payload.get('epoch', -1))}, "
        f"best_acc {float(payload.get('best_acc', 0.0)):.4f}")
    return params, state, payload, path


def serve_main(argv=None) -> int:
    """`python -m bnsgcn_tpu.main serve ...` / `python -m bnsgcn_tpu.serve`.

    Exit codes: 0 clean shutdown (client 'shutdown' op), 75 graceful
    SIGTERM/SIGINT drain (deltas flushed, resumable), 2 config error."""
    cfg = parse_config(argv)
    if not cfg.graph_name:
        cfg = cfg.replace(graph_name=cfg.derive_graph_name())
    log = print
    obs = obs_mod.make_obs(cfg, rank=0, log=log)
    try:
        from bnsgcn_tpu.data.datasets import load_data
        g, _, _ = load_data(cfg)
        cfg = cfg.replace(n_feat=g.n_feat, n_class=g.n_class,
                          n_train=g.n_train)
        params, state, payload, cpath = _load_model(cfg, log)
        hidden = logits = None
        if cfg.embeddings:
            hidden, logits, meta = load_table(cfg.embeddings)
            log(f"[serve] cold start from embedding table {cfg.embeddings} "
                f"({hidden.shape[0]} nodes"
                + (f", exported at epoch {meta.get('epoch')}" if meta else "")
                + ")")
        core = build_core(cfg, g, params, state, log=log,
                          hidden=hidden, logits=logits, obs=obs)
    except ConfigError as ex:
        print(f"[config] {ex}", file=sys.stderr)
        sys.exit(2)
    except ckpt.CheckpointCorrupt as ex:
        print(f"[config] embedding artifact unusable: {ex}", file=sys.stderr)
        sys.exit(2)

    serve_dir = cfg.serve_dir or os.path.join(cfg.ckpt_path, "serve")
    core.serve_dir = serve_dir
    try:
        counts = core.load_serving_state(serve_dir)
    except ckpt.CheckpointCorrupt as ex:
        print(f"[config] serving snapshot unusable: {ex} — the delta log is "
              f"only a tail past a snapshot; refusing to resume from a hole "
              f"in history", file=sys.stderr)
        sys.exit(2)
    replayed = counts["replayed"]
    if replayed or counts["folded"]:
        log(f"[serve] resumed: {counts['folded']} delta(s) from the "
            f"snapshot + {replayed} replayed from the tail log "
            f"({len(core.dirty)} nodes dirty, refreshing in background)")
    # adopt a promotion published while no server was running (the offline
    # continual flow: trainer writes the blob, the next serve start picks it
    # up through the same monotonic/rollback checks as the live op)
    promo = ckpt.promotion_path(serve_dir)
    if os.path.exists(promo):
        adopted = core.promote(promo)
        if adopted.get("ok"):
            log(f"[serve] adopted promotion cycle {adopted['cycle']} "
                f"at startup")

    signals = resilience.PreemptSignals(
        action="drain in-flight requests and flush the delta log",
        boundary="request boundary")
    signals.install()
    server = ServeServer(core, cfg.serve_port, cfg.serve_addr, log=log)
    stop_refresh = threading.Event()

    def _refresher():
        while not stop_refresh.wait(cfg.serve_refresh_s):
            try:
                core.refresh_some()
            except Exception as ex:             # noqa: BLE001 — keep serving
                log(f"[serve] background refresh failed: "
                    f"{type(ex).__name__}: {ex}")

    refresher = None
    if cfg.serve_refresh_s > 0:
        refresher = threading.Thread(target=_refresher,
                                     name="bnsgcn-serve-refresh", daemon=True)
        refresher.start()

    log(f"[serve] ready on port {server.port}: tier A table lookup + tier B "
        f"{core.hops}-hop re-aggregation (max batch {cfg.serve_max_batch}), "
        f"delta log at {os.path.join(serve_dir, DELTA_LOG)}")
    if obs is not None:
        obs.emit("serve_header", port=server.port, n_nodes=core.graph.n_nodes,
                 model=cfg.model, hops=core.hops,
                 max_batch=cfg.serve_max_batch, replayed=replayed,
                 folded=counts["folded"])
    try:
        while signals.requested is None:
            if server.shutdown_requested.wait(0.05):
                break
    finally:
        stop_refresh.set()
        if refresher is not None:
            # a refresh still inside XLA when the interpreter tears down
            # aborts the process (SIGABRT, seen as exit -6 after a clean
            # shutdown op): wait it out, as serve_backend does
            refresher.join(timeout=120.0)
        server.drain()
        core.close()
        path = core.flush_delta_log(serve_dir)
        stats = core.snapshot_stats()
        log(f"[serve] drained: {stats['requests']} requests served "
            f"(A {stats['tier_a']} / B {stats['tier_b']}), "
            f"{stats['deltas']} delta(s) flushed to {path}, "
            f"{stats['dirty']} node(s) left dirty for the next run")
        log(f"[serve] latency: tier A p50 {stats['tier_a_p50_ms']:.3f} ms / "
            f"p99 {stats['tier_a_p99_ms']:.3f} ms | tier B p50 "
            f"{stats['tier_b_p50_ms']:.3f} ms / p99 "
            f"{stats['tier_b_p99_ms']:.3f} ms | refresh lag p50 "
            f"{stats['refresh_lag_p50_s']:.3f} s")
        if obs is not None:
            obs.emit("serve_drain", **{k: stats[k] for k in sorted(stats)})
            obs.close()
        signals.restore()
    if signals.requested is not None:
        log(f"[serve] {signals.requested} honored: resumable delta log "
            f"flushed — relaunch continues ingestion exactly here")
        sys.exit(resilience.EXIT_PREEMPTED)
    return 0


if __name__ == "__main__":
    serve_main()
