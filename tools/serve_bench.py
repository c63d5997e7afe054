"""Load generator for the online inference server (bnsgcn_tpu/serve.py).

Self-hosted by default: builds a synthetic graph, a randomly-initialized
model (latency does not depend on trained weights), precomputes the
embedding table, starts a real ServeServer on a free port, then fires
requests from concurrent client threads over the real line-JSON TCP wire —
every measured microsecond includes the socket round trip a production
client would pay. Point --port/--addr at an already-running server to bench
it instead.

Reports, as JSON lines in the SERVE_METRICS vocabulary below (last line
wins, like bench.py's epoch-time lines):

  serve_p50_ms / serve_p99_ms   per-request latency, per tier
                                (A = table lookup, B = fresh L-hop
                                re-aggregation in padded-SpMM buckets)
  serve_qps                     sustained throughput / accelerator chip

Tier-B bucket-program compiles are paid by a warmup pass run at the SAME
concurrency as the measured pass (coalesced batches land in larger buckets
than solo requests) — a latency percentile should reflect steady-state
serving, not one-time XLA compiles. A previously-unseen bucket shape can
still appear mid-measurement (closure sizes vary); raise --warmup if tier-B
p99 looks compile-shaped.

Fleet mode (--fleet N): self-hosts a partition-sharded serving fleet
instead — N per-part backends (random N-way owner map over the same
synthetic graph) behind a real serve-router, all over real TCP — and fires
the same workload at the ROUTER. Responses carry their shard tags, so the
percentiles additionally split per part/backend, the server-side
cross-check runs per backend against the router's aggregated `stats`, and
a direct-at-the-backend tier-A pass measures the router's forwarding
overhead (routed p50 / direct p50 — flagged when it exceeds 2x). --variant
tags every emitted metric line (default: serve1 single-host, serve{N}p
fleet) so the two topologies are never compared as one.

The serving tier is host numpy plus a one-shot table precompute: this tool
is the serving bench's only entry point and runs wherever JAX_PLATFORMS
points it. bench.py (training epoch time, TPU only) does not dispatch to it.

Usage: python tools/serve_bench.py [--requests 400] [--concurrency 4]
           [--dataset synthetic] [--model graphsage] [--fleet 2]
           [--json-only]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax  # noqa: E402

from bnsgcn_tpu import serve  # noqa: E402
from bnsgcn_tpu.config import Config  # noqa: E402
from bnsgcn_tpu.data.datasets import load_data  # noqa: E402
from bnsgcn_tpu.models.gnn import init_params, spec_from_config  # noqa: E402


# Metric vocabulary. Names are load-bearing: a rename silently orphans every
# recorded line, so the emitter below refuses anything else.
SERVE_METRICS = {
    "serve_p50_ms": "ms",          # per-request latency median, per tier
    "serve_p99_ms": "ms",          # per-request latency 99th pct, per tier
    "serve_qps": "req/s/chip",     # sustained throughput per accelerator chip
}


def emit_serve_metric(name: str, value: float, tier: str | None = None,
                      **extra):
    """One JSON metric line on stdout (last line wins)."""
    if name not in SERVE_METRICS:
        raise ValueError(f"unknown serve metric {name!r} "
                         f"(vocabulary: {sorted(SERVE_METRICS)})")
    line = {"metric": name, "value": round(float(value), 4),
            "unit": SERVE_METRICS[name]}
    if tier is not None:
        line["tier"] = tier
    line.update(extra)
    print(json.dumps(line), flush=True)


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--dataset", default="synthetic",
                   help="synthetic | sbm | synth-reddit[:scale] | ...")
    p.add_argument("--model", default="graphsage",
                   choices=["gcn", "graphsage", "gat"])
    p.add_argument("--layers", type=int, default=2)
    p.add_argument("--hidden", type=int, default=32)
    p.add_argument("--requests", type=int, default=400,
                   help="measured requests per tier")
    p.add_argument("--concurrency", type=int, default=4,
                   help="client threads per tier (tier-B concurrency is "
                        "what the batcher coalesces into buckets)")
    p.add_argument("--max-batch", type=int, default=64)
    p.add_argument("--warmup", type=int, default=8,
                   help="unmeasured warmup requests per tier (compiles the "
                        "tier-B bucket programs)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--addr", default="",
                   help="bench an external server instead of self-hosting")
    p.add_argument("--port", type=int, default=0,
                   help="external server port (with --addr); 0 self-hosts "
                        "on a free port")
    p.add_argument("--fleet", type=int, default=0,
                   help="self-host a partition-sharded fleet: N per-part "
                        "backends behind a serve-router, bench the router; "
                        "0 = single-host ServeServer")
    p.add_argument("--variant", default="",
                   help="topology tag on every emitted metric line "
                        "(default: serve1, or serve{N}p with --fleet)")
    p.add_argument("--chaos", action="store_true",
                   help="with --fleet: self-host 2 replicas per part with "
                        "health tracking + '--serve-degraded partial', tear "
                        "down backend p0.r0 mid-load and rejoin it; reports "
                        "the ok/degraded/failed availability split, "
                        "failover p99 and the recovery wall clock as one "
                        "JSON summary line instead of the latency metrics")
    p.add_argument("--json-only", action="store_true")
    args = p.parse_args(argv)
    if args.chaos and not args.fleet:
        p.error("--chaos needs --fleet N (it kills one replica of a "
                "partition-sharded fleet)")
    if args.chaos and args.addr:
        p.error("--chaos self-hosts its victim fleet; drop --addr")
    return args


def _self_host(args, log):
    """(server, core): a real ServeServer over a fresh synthetic workload."""
    cfg = Config(dataset=args.dataset, model=args.model,
                 n_layers=args.layers, n_hidden=args.hidden,
                 seed=args.seed, serve_max_batch=args.max_batch,
                 use_pp=args.model == "graphsage")
    g, _, _ = load_data(cfg)
    cfg = cfg.replace(n_feat=g.n_feat, n_class=g.n_class, n_train=g.n_train)
    spec = spec_from_config(cfg)
    params, state = init_params(jax.random.key(args.seed), spec)
    log(f"graph: {g.n_nodes} nodes, {g.n_edges} edges | model {args.model} "
        f"L={args.layers} H={args.hidden}")
    core = serve.build_core(cfg, g, params, state, log=log)
    server = serve.ServeServer(core, port=0, log=log)
    return server, core


def _self_host_fleet(args, log):
    """(router_server, close_fn, n_nodes, owned): a real serve-router
    fronting --fleet in-process per-part backends (random owner map, one
    full-table precompute sliced into shards), all over real TCP. `owned`
    maps backend id -> (direct port, owned node ids) for the direct
    overhead pass."""
    from bnsgcn_tpu import serve_backend as sb
    from bnsgcn_tpu import serve_router as sr
    from bnsgcn_tpu.evaluate import full_graph_embeddings
    cfg = Config(dataset=args.dataset, model=args.model,
                 n_layers=args.layers, n_hidden=args.hidden,
                 seed=args.seed, serve_max_batch=args.max_batch,
                 use_pp=args.model == "graphsage")
    g, _, _ = load_data(cfg)
    cfg = cfg.replace(n_feat=g.n_feat, n_class=g.n_class, n_train=g.n_train)
    spec = spec_from_config(cfg)
    params, state = init_params(jax.random.key(args.seed), spec)
    log(f"graph: {g.n_nodes} nodes, {g.n_edges} edges | model {args.model} "
        f"L={args.layers} H={args.hidden} | fleet of {args.fleet} part(s)")
    t0 = time.perf_counter()
    hidden, logits = full_graph_embeddings(params, state, spec, g,
                                           cfg.edge_chunk)
    hidden, logits = np.asarray(hidden), np.asarray(logits)
    log(f"full table precomputed once in {time.perf_counter() - t0:.1f}s; "
        f"sliced into {args.fleet} shards")
    rng = np.random.default_rng(args.seed)
    owner = rng.integers(0, args.fleet, size=g.n_nodes).astype(np.int32)
    owner[:args.fleet] = np.arange(args.fleet)      # every part non-empty
    rcore = sr.RouterCore(owner, args.fleet, hops=spec.n_graph_layers,
                          log=log)
    router = sr.RouterServer(rcore, 0, log=log)
    cores, servers, resolvers, owned = [], [], [], {}
    for part in range(args.fleet):
        c = sb.build_backend_core(cfg.replace(serve_part=part), g, owner,
                                  params, state, log=lambda *a, **k: None,
                                  hidden=hidden, logits=logits)
        s = sb.BackendServer(c, 0, log=log)
        res = sb.PeerResolver("127.0.0.1", router.port)
        c.graph.resolver = res
        rcore.fleet.register(part, 0, "127.0.0.1", s.port)
        cores.append(c)
        servers.append(s)
        resolvers.append(res)
        owned[f"p{part}.r0"] = (s.port, np.flatnonzero(owner == part))

    def close():
        for s in servers:
            s.drain(timeout_s=5.0)
        for c in cores:
            c.close()
        for r in resolvers:
            r.close()
        router.drain(timeout_s=5.0)
        rcore.close()

    return router, close, g.n_nodes, owned


def run_chaos(args, log) -> int:
    """Self-hosted failover drill: a --fleet-part fleet with TWO replicas
    per part behind a health-tracking router in degraded 'partial' mode.
    Mid-load, backend p0.r0 is torn down (listener stopped + every
    in-flight connection dropped — to the router it is a dead process),
    a delta lands while it is gone (so the WAL queues for it), then it
    restarts under a fresh incarnation and must rejoin through WAL replay
    + the bitwise warm-up gate. Exit 0 iff zero client requests FAILED
    (degraded answers are fine — that is the contract under test) and the
    victim recovered to 'up'."""
    from bnsgcn_tpu import serve_backend as sb
    from bnsgcn_tpu import serve_router as sr
    from bnsgcn_tpu.evaluate import full_graph_embeddings
    cfg = Config(dataset=args.dataset, model=args.model,
                 n_layers=args.layers, n_hidden=args.hidden,
                 seed=args.seed, serve_max_batch=args.max_batch,
                 use_pp=args.model == "graphsage")
    g, _, _ = load_data(cfg)
    cfg = cfg.replace(n_feat=g.n_feat, n_class=g.n_class, n_train=g.n_train)
    spec = spec_from_config(cfg)
    params, state = init_params(jax.random.key(args.seed), spec)
    hidden, logits = full_graph_embeddings(params, state, spec, g,
                                           cfg.edge_chunk)
    hidden, logits = np.asarray(hidden), np.asarray(logits)
    rng = np.random.default_rng(args.seed)
    owner = rng.integers(0, args.fleet, size=g.n_nodes).astype(np.int32)
    owner[:args.fleet] = np.arange(args.fleet)
    os.environ.setdefault("BNSGCN_SERVE_DOWN_AFTER", "2")
    rcore = sr.RouterCore(owner, args.fleet, replicas=2,
                          hops=spec.n_graph_layers, log=log,
                          health=sr.HealthPolicy(probe_s=0.15),
                          degraded="partial")
    router = sr.RouterServer(rcore, 0, log=log)
    servers, cores, resolvers = {}, {}, []
    for part in range(args.fleet):
        for r in range(2):
            c = sb.build_backend_core(
                cfg.replace(serve_part=part, serve_replica=r), g, owner,
                params, state, log=lambda *a, **k: None,
                hidden=hidden, logits=logits)
            s = sb.BackendServer(c, 0, log=log)
            res = sb.PeerResolver("127.0.0.1", router.port)
            c.graph.resolver = res
            rcore.register_backend(part, r, "127.0.0.1", s.port,
                                   incarnation=f"chaos-p{part}.r{r}#0")
            servers[(part, r)] = s
            cores[(part, r)] = c
            resolvers.append(res)
    rcore.start_probes()
    log(f"chaos fleet up: {args.fleet} part(s) x 2 replicas behind router "
        f"port {router.port}, probes every 0.15s, degraded=partial")

    counts: dict[str, int] = {"ok": 0, "stale": 0, "unavailable": 0,
                              "failed": 0}
    fail_errs: list[str] = []
    lock = threading.Lock()
    stop = threading.Event()

    def _load(tid: int):
        r = np.random.default_rng(args.seed + 100 + tid)
        while not stop.is_set():
            node = int(r.integers(0, g.n_nodes))
            try:
                resp = serve.request(router.port, {"op": "predict",
                                                   "node": node},
                                     timeout_s=30.0)
            except Exception as ex:             # noqa: BLE001 — a failed
                resp = {"ok": False,            # request is a data point
                        "err": f"{type(ex).__name__}: {ex}"}
            key = ((resp.get("status") or "ok") if resp.get("ok")
                   else "failed")
            with lock:
                counts[key] = counts.get(key, 0) + 1
                if key == "failed" and len(fail_errs) < 3:
                    fail_errs.append(str(resp.get("err", "?")))
            time.sleep(0.002)

    threads = [threading.Thread(target=_load, args=(i,))
               for i in range(args.concurrency)]
    for t in threads:
        t.start()
    try:
        time.sleep(1.0)
        log("[chaos] tearing down backend p0.r0 mid-load")
        t_kill = time.perf_counter()
        victim = servers[(0, 0)]
        # dead-process simulation: drop every in-flight connection without
        # a response AND refuse new ones
        victim.server.handle_fn = lambda req: None
        victim.server.stop()
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline and rcore.health_snapshot().get(
                "p0.r0") not in ("down", "quarantined"):
            time.sleep(0.05)
        log(f"[chaos] router sees p0.r0 "
            f"{rcore.health_snapshot().get('p0.r0')!r}; landing a delta "
            f"while it is gone (WAL must queue it)")
        serve.request(router.port, {"op": "add_edges", "edges": [[0, 1]]},
                      timeout_s=120.0)
        time.sleep(0.4)
        log("[chaos] restarting p0.r0 under a fresh incarnation")
        s2 = sb.BackendServer(cores[(0, 0)], 0, log=log)
        servers[(0, 0)] = s2
        rcore.register_backend(0, 0, "127.0.0.1", s2.port,
                               incarnation="chaos-p0.r0#1")
        deadline = time.monotonic() + 15.0
        while time.monotonic() < deadline and \
                rcore.health_snapshot().get("p0.r0") != "up":
            time.sleep(0.05)
        recovered = rcore.health_snapshot().get("p0.r0") == "up"
        recovery_wall_s = time.perf_counter() - t_kill
        time.sleep(1.0)                 # post-recovery steady state
    finally:
        stop.set()
        for t in threads:
            t.join()
    avail = rcore.availability()
    with rcore._lock:
        wal_replayed = rcore.stats["wal_replayed"]
    summary = {"chaos": True, "fleet": args.fleet, "replicas": 2,
               "client_requests": sum(counts.values()),
               "client_ok": counts["ok"], "client_stale": counts["stale"],
               "client_unavailable": counts["unavailable"],
               "client_failed": counts["failed"],
               "availability": avail["availability"],
               "failovers": avail["failovers"],
               "failover_p99_ms": avail["failover_p99_ms"],
               "recoveries": avail["recoveries"],
               "recovery_s": avail["recovery_s"],
               "recovery_wall_s": round(recovery_wall_s, 3),
               "wal_replayed": wal_replayed,
               "recovered": recovered,
               "first_failures": fail_errs}
    print(json.dumps(summary, sort_keys=True))
    for s in servers.values():
        try:
            s.drain(timeout_s=2.0)
        except OSError:
            pass                        # the victim's first listener is gone
    for c in cores.values():
        c.close()
    for res in resolvers:
        res.close()
    router.drain(timeout_s=2.0)
    rcore.close()
    return 0 if recovered and counts["failed"] == 0 else 1


def _fire(args, port, addr, tier, nodes, latencies, errors):
    for n in nodes:
        req = {"op": "predict", "node": int(n)}
        if tier == "B":
            req["tier"] = "B"
        t0 = time.perf_counter()
        resp = serve.request(port, req, addr=addr or "127.0.0.1",
                             timeout_s=120.0)
        dt = (time.perf_counter() - t0) * 1e3
        if not resp.get("ok"):
            errors.append(resp.get("err", "?"))
        else:
            # a routed response carries its shard tag — the fleet split
            latencies.append((dt, resp.get("backend")))


def _burst(args, port, addr, tier, rng, n_nodes, per, lat, errors):
    """One measured-shape pass: --concurrency threads x `per` requests."""
    threads = []
    for _ in range(args.concurrency):
        nodes = rng.integers(0, n_nodes, size=per)
        t = threading.Thread(target=_fire,
                             args=(args, port, addr, tier, nodes, lat,
                                   errors))
        t.start()
        threads.append(t)
    for t in threads:
        t.join()


def bench_tier(args, port, addr, tier, n_nodes, log):
    """(p50_ms, p99_ms, qps) for one tier at --concurrency client threads."""
    rng = np.random.default_rng(args.seed + (1 if tier == "B" else 0))
    # warmup at the SAME concurrency as the measured pass: coalesced
    # multi-target batches land in larger (node, edge) buckets than solo
    # requests, and their one-time XLA compiles must be paid here, not
    # inside the measured percentiles
    _burst(args, port, addr, tier, rng, n_nodes,
           max(args.warmup // args.concurrency, 1), [], [])
    per = max(args.requests // args.concurrency, 1)
    lat: list[tuple] = []
    errors: list[str] = []
    t0 = time.perf_counter()
    _burst(args, port, addr, tier, rng, n_nodes, per, lat, errors)
    wall = time.perf_counter() - t0
    if errors:
        raise RuntimeError(f"tier {tier}: {len(errors)} failed requests "
                           f"(first: {errors[0]})")
    qps = len(lat) / wall / max(jax.device_count(), 1)
    p50, p99 = np.percentile([d for d, _ in lat], [50, 99])
    log(f"tier {tier}: {len(lat)} requests in {wall:.2f}s | p50 "
        f"{p50:.3f} ms p99 {p99:.3f} ms | {qps:.1f} req/s/chip")
    # per-part/backend split (routed responses only): where the time goes
    # when one shard runs hotter than the rest
    by_backend: dict[str, list[float]] = {}
    for d, bid in lat:
        if bid:
            by_backend.setdefault(bid, []).append(d)
    split = {}
    for bid in sorted(by_backend):
        bp50, bp99 = np.percentile(by_backend[bid], [50, 99])
        split[bid] = (float(bp50), float(bp99), len(by_backend[bid]))
        log(f"  tier {tier} @ {bid}: n={len(by_backend[bid])} p50 "
            f"{bp50:.3f} ms p99 {bp99:.3f} ms")
    return float(p50), float(p99), float(qps), split


def _direct_overhead(args, routed_a_p50, owned, log):
    """Routed-vs-direct tier-A overhead: fire at ONE backend directly (its
    owned nodes — anything else is a mis-route by construction) and compare
    medians. The router adds one hop + one line-JSON re-encode; more than
    2x on the tier-A median means the routing layer, not the model, owns
    the latency budget."""
    bid, (bport, bnodes) = sorted(owned.items())[0]
    rng = np.random.default_rng(args.seed + 7)
    lat: list[tuple] = []
    errors: list[str] = []
    per = max(args.requests // args.concurrency, 8)
    threads = []        # SAME concurrency as the routed pass — queueing
    for _ in range(args.concurrency):       # must hit both sides equally
        picks = bnodes[rng.integers(0, len(bnodes), size=per)]
        t = threading.Thread(target=_fire, args=(args, bport, "127.0.0.1",
                                                 "A", picks, lat, errors))
        t.start()
        threads.append(t)
    for t in threads:
        t.join()
    if errors:
        raise RuntimeError(f"direct pass at {bid}: {len(errors)} failed "
                           f"(first: {errors[0]})")
    direct_p50 = float(np.percentile([d for d, _ in lat], 50))
    ratio = routed_a_p50 / max(direct_p50, 1e-9)
    log(f"router overhead: tier A p50 routed {routed_a_p50:.3f} ms vs "
        f"direct @ {bid} {direct_p50:.3f} ms -> {ratio:.2f}x")
    if ratio > 2.0:
        log(f"  WARNING: routed tier-A p50 is {ratio:.2f}x the direct-"
            f"backend p50 (budget: 2x) — the router hop dominates")
    return direct_p50, ratio


def main(argv=None):
    args = parse_args(argv)
    log = (lambda *a, **k: None) if args.json_only else print
    if args.chaos:
        return run_chaos(args, log)
    variant = args.variant or (f"serve{args.fleet}p" if args.fleet
                               else "serve1")
    dev = jax.devices()[0]
    # every line says where it was taken: the unit reads req/s/chip on a CPU
    # too, and only the stamp tells such a line from a chip number
    tags = {"variant": variant, "backends": args.fleet or 1,
            "platform": dev.platform, "device_kind": dev.device_kind,
            "device_count": jax.device_count()}
    server = core = close_fleet = None
    owned: dict = {}
    if args.addr:
        port, addr = args.port, args.addr
        n_nodes = int(serve.request(port, {"op": "stats"},
                                    addr=addr)["n_nodes"])
    elif args.fleet:
        t0 = time.perf_counter()
        router, close_fleet, n_nodes, owned = _self_host_fleet(args, log)
        port, addr = router.port, "127.0.0.1"
        log(f"self-hosted fleet up behind router port {port} "
            f"({time.perf_counter() - t0:.1f}s incl. table precompute)")
    else:
        t0 = time.perf_counter()
        server, core = _self_host(args, log)
        port, addr = server.port, "127.0.0.1"
        n_nodes = core.graph.n_nodes
        log(f"self-hosted server up on port {port} "
            f"({time.perf_counter() - t0:.1f}s incl. table precompute)")
    try:
        results = {}
        for tier in ("A", "B"):
            results[tier] = bench_tier(args, port, addr, tier, n_nodes, log)
        # cross-check against the SERVER-side registry percentiles (the
        # obs-backed `stats` figures): server p50 measures the handler only,
        # so it must not exceed the client p50 (which adds the socket round
        # trip) by more than scheduling noise — a bigger gap means the two
        # clocks disagree about where the time goes. p50 ONLY: the server's
        # histogram also holds the warmup pass (its one-time bucket compiles
        # dominate a tail quantile but cannot move the median), so its p99
        # is printed for context, not compared. Against a router, the same
        # keys hold the ROUTE-level percentiles, and the nested `backends`
        # stats run the check once per backend against its client-side
        # split.
        stats = serve.request(port, {"op": "stats"}, addr=addr or "127.0.0.1")
        for tier in ("A", "B"):
            sp50 = stats.get(f"tier_{tier.lower()}_p50_ms", 0.0)
            sp99 = stats.get(f"tier_{tier.lower()}_p99_ms", 0.0)
            cp50 = results[tier][0]
            log(f"tier {tier} server-side: p50 {sp50:.3f} ms (client-side "
                f"p50 {cp50:.3f} ms; delta = socket + queueing) | p99 "
                f"{sp99:.3f} ms incl. warmup compiles — not comparable")
            if sp50 > cp50 * 1.5 + 0.5:
                log(f"  WARNING: tier {tier} server p50 exceeds client p50 "
                    f"— registry/clock skew, treat percentiles as suspect")
            for be in stats.get("backends", []):
                bid = be.get("backend", "?")
                bsp50 = be.get(f"tier_{tier.lower()}_p50_ms", 0.0)
                bcp50 = results[tier][3].get(bid, (0.0,))[0]
                log(f"  tier {tier} @ {bid} server-side p50 {bsp50:.3f} ms "
                    f"(client-side {bcp50:.3f} ms)")
                if bcp50 and bsp50 > bcp50 * 1.5 + 0.5:
                    log(f"  WARNING: tier {tier} @ {bid} server p50 exceeds "
                        f"its client p50 — registry/clock skew, treat "
                        f"percentiles as suspect")
        if owned:
            _, ratio = _direct_overhead(args, results["A"][0], owned, log)
            tags["router_overhead_x"] = round(ratio, 3)
        for tier in ("A", "B"):
            p50, p99, qps, _ = results[tier]
            emit_serve_metric("serve_p50_ms", p50, tier=tier, **tags)
            emit_serve_metric("serve_p99_ms", p99, tier=tier, **tags)
            emit_serve_metric("serve_qps", qps, tier=tier, **tags)
        # last line wins for the driver: the mixed-fleet headline is tier-A
        # throughput (the tier a production cache-hit path serves)
        emit_serve_metric("serve_qps", results["A"][2], tier="A",
                          requests=args.requests,
                          concurrency=args.concurrency, **tags)
        assert set(SERVE_METRICS) == {"serve_p50_ms", "serve_p99_ms",
                                      "serve_qps"}
    finally:
        if server is not None:
            server.drain(timeout_s=5.0)
            core.close()
        if close_fleet is not None:
            close_fleet()
    return 0


if __name__ == "__main__":
    sys.exit(main())
