"""Unified telemetry bus: structured run/serve metrics + post-mortem trail.

Every subsystem built since PR 1 emitted its own ad-hoc signals — EpochTimer
buckets and wire-bytes header lines in run.py, liveness dumps in
parallel/coord.py, bare counters in serve.py's `stats` op, stderr stack dumps
from the watchdog — and none of it survived a run as a machine-readable
artifact. The ROADMAP's standing campaigns (real-pod validation, chip
measurements taken on a machine that is thrown away afterwards,
papers100M epoch timing) all hinge on answering "where did the time/bytes
go, on which rank, in which epoch" from a log AFTER the run. This module
is the one place such signals land:

* **Registry** — process-wide counters, gauges and fixed-log-bucket
  streaming histograms (p50/p99 without sample storage: values land in
  geometrically-spaced buckets, a quantile is the geometric midpoint of the
  bucket holding it — bounded relative error, O(buckets) memory forever).
* **EventLog** — a rank-tagged structured JSONL event log (`--obs-log PATH`
  / `$BNSGCN_OBS_LOG`; ranks > 0 write `PATH.r<rank>`), size-bounded with
  one-deep rotation (`PATH.1`) so a multi-day run can never fill a disk.
  Every write is line-flushed: the log survives os._exit (the watchdog's
  exit 77) with the triggering event on disk.
* **Post-mortem capture** — `write_postmortem` drops all-thread stacks plus
  a registry snapshot into `--obs-dir` (default `{ckpt_path}/postmortem`),
  used by the watchdog/divergence dumps and the on-demand SIGUSR1 profile
  window (resilience.PreemptSignals + run.py) so exits 75/76/77/78 leave
  files, not just stderr.

`--obs off` constructs none of this (make_obs returns None; every call site
guards) and is pinned bitwise against `on` by tests/test_obs.py — the bus
only ever reads host-side values the loop already fetched, never adds a
device op. tools/obs_report.py renders a log (per-epoch table, comm-vs-
compute split, serving percentiles, multi-rank merge, --compare).

Set-up is named from inside too: boot stamps (`boot_begin`/`boot_end`)
time what runs before an Obs exists, and the process's one
`jax.monitoring` registration (`subscribe_compiles`) feeds each Obs a
compile account (trace, lower, compile or cache load, cache hits and
misses) that set-up spans and compiling epochs carry as `compile`.
"""

from __future__ import annotations

import faulthandler
import json
import math
import os
import sys
import threading
import time
import weakref
from collections import deque
from typing import Optional

__all__ = [
    "Counter", "Gauge", "Histogram", "Registry", "EventLog", "Obs",
    "make_obs", "postmortem_dir", "write_postmortem", "load_events",
    "rank_log_path", "EVENT_KINDS", "PHASES", "SETUP_SPANS", "FIRST_CALL",
    "SPAN_PREFIX", "EPOCH_MARK", "Span", "NO_SPAN", "span", "BOOT_PARENT",
    "boot_begin", "boot_end", "proc_start_wall",
    "subscribe_compiles", "unsubscribe_compiles", "compile_account",
]


# The closed vocabulary of event kinds the bus carries. tools/obs_report.py
# renders from this registry, and graftlint's obs-unregistered-event rule
# rejects any emit() kind literal not listed here — adding an event means
# registering it first, which is what keeps the log and every reader in
# sync. Grouped by emitter.
EVENT_KINDS = (
    # training lifecycle (run.py)
    "run_header", "epoch", "epoch_ranks", "eval", "trace", "overlap",
    "halo_refresh", "reorder", "layout_build", "tune_decision", "run_end",
    # host spans (`span` below): one event per set-up phase of run_training
    # and per `first_call:<program>`: name, parent, t0 (wall clock), dur_s,
    # and `compile` where jax compiled inside it; boot stamps (`import`,
    # `backend_init`) under parent BOOT_PARENT
    "span",
    # resilience (resilience.py: injections, rollback consensus, exits;
    # 'resize' = the elastic shrink/grow verdict: old/new world, part->slot
    # map, trigger, resize nonce)
    "inject", "rollback", "divergence_abort", "coord_decision",
    "watchdog_fire", "preempt", "resize", "profile_request", "profile",
    # serving (serve.py; serve_router.py / serve_backend.py for the
    # partition-sharded fleet)
    "serve_header", "serve_drain", "delta", "serve_fleet", "serve_compact",
    # serving-fleet self-healing (serve_router.py): 'serve_health' = one
    # backend's up/suspect/down/quarantined transition with the probe
    # evidence; 'failover' = a read answered by a non-primary replica, a
    # degraded answer, or a WAL replay — the router's recovery actions
    "serve_health", "failover",
    # continual training on an evolving graph (continual.py ingestion/
    # promotion cycle; serve.py emits 'promote' at the adoption boundary)
    "continual_cycle", "artifact_update", "promote",
    # benchmarking (bench.py)
    "bench_header", "bench_variant", "bench_end",
    # strict-execution guard (strict.py, --strict-exec)
    "strict_exec",
    # jaxpr-level static preflight (analysis/ir, `-m bnsgcn_tpu.analysis ir`)
    "ir_audit",
    # protocol model-checking preflight (analysis/proto,
    # `-m bnsgcn_tpu.analysis proto`)
    "proto_audit",
    # predictive cost-model audit (analysis/perf, `-m bnsgcn_tpu.analysis
    # perf`)
    "perf_audit",
)


# Host phases of one epoch of run.run_training's loop, in loop order; each is
# one `span`. `norm_probe` is the child of `guard`. The `epoch` event's
# `boundary` is keyed by these names.
PHASES = ("pre", "dispatch", "wait", "loss_fetch", "guard", "norm_probe",
          "agree", "trace_io", "comm_bench", "obs_emit", "tune", "log",
          "checkpoint", "eval")
# Set-up phases of run_training, each emitted as one `span` event under the
# root SETUP_SPANS[0]; `first_call:<program>` events join them from the loop.
SETUP_SPANS = ("run_training_setup", "load_graph", "load_artifacts",
               "prepare_partition", "layout_cache_load", "build_step_fns",
               "place", "init_training", "resume", "pp_precompute",
               "comm_bench_compile")
FIRST_CALL = "first_call:"
# every span is also a jax.profiler.TraceAnnotation under this prefix, so a
# profiler window carries it on the Python thread's lane, on the device
# lanes' clock; the epoch body runs under StepTraceAnnotation(EPOCH_MARK)
SPAN_PREFIX = "bns:"
EPOCH_MARK = SPAN_PREFIX + "epoch"
# the parent of the boot stamps: set-up that runs before any Obs exists
BOOT_PARENT = "process"


# ----------------------------------------------------------------------------
# metrics: counters, gauges, streaming histograms
# ----------------------------------------------------------------------------

class Counter:
    """Monotonic count; thread-safe via the owning Registry's lock discipline
    (increments are a single int add under the GIL — atomic enough for
    telemetry; the registry snapshot takes the lock for consistency)."""

    __slots__ = ("value",)

    def __init__(self):
        self.value = 0

    def inc(self, n: int = 1):
        self.value += int(n)


class Gauge:
    """Last-write-wins instantaneous value."""

    __slots__ = ("value",)

    def __init__(self):
        self.value = 0.0

    def set(self, v: float):
        self.value = float(v)


class Histogram:
    """Fixed-log-bucket streaming histogram: p50/p99 without sample storage.

    Bucket i holds values in [lo * growth^(i-1), lo * growth^i); bucket 0 is
    the underflow (< lo, including 0/negatives), the last the overflow. A
    quantile is the geometric midpoint of the bucket the target count falls
    in, so the relative error is bounded by sqrt(growth) - 1 (~4.4% at the
    default growth 2^(1/8)) — tests/test_obs.py pins known-quantile inputs.
    Memory is the bucket array, constant for the life of the run."""

    __slots__ = ("lo", "growth", "_log_g", "n", "counts", "count", "total",
                 "vmin", "vmax")

    def __init__(self, lo: float = 1e-4, growth: float = 2 ** 0.125,
                 n_buckets: int = 256):
        self.lo = float(lo)
        self.growth = float(growth)
        self._log_g = math.log(growth)
        self.n = int(n_buckets)
        self.counts = [0] * (self.n + 2)    # [underflow, n buckets, overflow]
        self.count = 0
        self.total = 0.0
        self.vmin = math.inf
        self.vmax = -math.inf

    def _idx(self, v: float) -> int:
        if v < self.lo:
            return 0
        i = 1 + int(math.log(v / self.lo) / self._log_g)
        return min(i, self.n + 1)

    def observe(self, v: float):
        v = float(v)
        if not math.isfinite(v):
            return      # a NaN/inf measurement is dropped, never a crash —
                        # the bus's contract is that telemetry cannot kill
                        # the subsystem feeding it (int(nan) would raise)
        self.counts[self._idx(v)] += 1
        self.count += 1
        self.total += v
        if v < self.vmin:
            self.vmin = v
        if v > self.vmax:
            self.vmax = v

    def _bucket_mid(self, i: int) -> float:
        if i <= 0:
            return min(self.lo, self.vmin)
        if i >= self.n + 1:
            return max(self.lo * self.growth ** self.n, self.vmax)
        # geometric midpoint of [lo*g^(i-1), lo*g^i)
        return self.lo * self.growth ** (i - 0.5)

    def percentile(self, q: float) -> float:
        """Value at quantile q in [0, 100]; 0.0 for an empty histogram."""
        if self.count == 0:
            return 0.0
        target = max(q / 100.0 * self.count, 1.0)
        seen = 0
        for i, c in enumerate(self.counts):
            seen += c
            if seen >= target:
                # clamp into the observed range: a single-bucket histogram
                # must not report a midpoint outside [vmin, vmax]
                return float(min(max(self._bucket_mid(i), self.vmin),
                                 self.vmax))
        return float(self.vmax)

    def snapshot(self) -> dict:
        if self.count == 0:
            return {"count": 0, "sum": 0.0, "min": 0.0, "max": 0.0,
                    "p50": 0.0, "p90": 0.0, "p99": 0.0}
        return {"count": self.count, "sum": round(self.total, 6),
                "min": round(self.vmin, 6), "max": round(self.vmax, 6),
                "p50": round(self.percentile(50), 6),
                "p90": round(self.percentile(90), 6),
                "p99": round(self.percentile(99), 6)}


class Registry:
    """Process-wide named metrics. Names are '/'-joined paths (e.g.
    'serve/latency_ms/A'); creation is idempotent and thread-safe, so any
    subsystem can grab its instruments without coordination."""

    def __init__(self):
        self._lock = threading.Lock()
        self._counters: dict[str, Counter] = {}   # guarded-by: self._lock
        self._gauges: dict[str, Gauge] = {}       # guarded-by: self._lock
        self._hists: dict[str, Histogram] = {}    # guarded-by: self._lock

    def counter(self, name: str) -> Counter:
        with self._lock:
            return self._counters.setdefault(name, Counter())

    def gauge(self, name: str) -> Gauge:
        with self._lock:
            return self._gauges.setdefault(name, Gauge())

    def histogram(self, name: str, **kw) -> Histogram:
        with self._lock:
            hit = self._hists.get(name)
            if hit is None:
                hit = self._hists[name] = Histogram(**kw)
            return hit

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "counters": {k: c.value for k, c in self._counters.items()},
                "gauges": {k: round(g.value, 6)
                           for k, g in self._gauges.items()},
                "histograms": {k: h.snapshot()
                               for k, h in self._hists.items()},
            }


# ----------------------------------------------------------------------------
# the structured JSONL event log
# ----------------------------------------------------------------------------

def _sanitize(v):
    """Strict-JSON-safe copy: non-finite floats (the NaN loss a rollback
    event exists to record) become their string form instead of the bare
    `NaN` token Python's json would emit — every line must parse under a
    strict reader, not just under json.loads."""
    if isinstance(v, float) and not math.isfinite(v):
        return str(v)
    if isinstance(v, dict):
        return {k: _sanitize(x) for k, x in v.items()}
    if isinstance(v, (list, tuple)):
        return [_sanitize(x) for x in v]
    return v

class EventLog:
    """Rank-tagged JSONL writer, size-bounded with one-deep rotation.

    Each `emit` appends one line `{"ts", "kind", "rank", ...fields}` and
    flushes — the log must survive os._exit (watchdog 77) with the
    triggering event on disk. When the file would exceed `max_bytes`
    (default $BNSGCN_OBS_MAX_MB = 64 MB) it rotates to `<path>.1`
    (overwriting the previous rotation), bounding total disk at ~2x the
    limit for the run's lifetime. Write failures disable the log with one
    stderr note — telemetry must never kill the run it observes."""

    def __init__(self, path: str, rank: int = 0,
                 max_bytes: Optional[int] = None):
        self.path = path
        self.rank = int(rank)
        if max_bytes is None:
            try:
                max_bytes = float(os.environ.get("BNSGCN_OBS_MAX_MB",
                                                 64)) * 2 ** 20
            except ValueError:
                # a typo'd env var must degrade, not crash-loop the run the
                # bus exists to observe (same contract as the open guard)
                sys.stderr.write("[obs] bad $BNSGCN_OBS_MAX_MB "
                                 f"{os.environ['BNSGCN_OBS_MAX_MB']!r}; "
                                 "using 64\n")
                max_bytes = 64 * 2 ** 20
        self.max_bytes = int(max_bytes)
        self._lock = threading.Lock()
        self._f = None          # guarded-by: self._lock
        self._size = 0          # guarded-by: self._lock
        self._dead = False      # guarded-by: self._lock
        try:
            self._open_locked()
        except OSError as ex:
            # an unwritable $BNSGCN_OBS_LOG must degrade to a no-log run,
            # not crash-loop every requeued relaunch before training starts
            self._dead = True
            sys.stderr.write(f"[obs] cannot open event log {path}: "
                             f"{type(ex).__name__}: {ex}; telemetry log "
                             f"disabled for this run\n")

    def _open_locked(self):
        d = os.path.dirname(os.path.abspath(self.path))
        os.makedirs(d, exist_ok=True)
        self._f = open(self.path, "a")
        self._size = self._f.tell()

    def emit(self, kind: str, **fields) -> Optional[dict]:
        rec = {"ts": round(time.time(), 3), "kind": kind, "rank": self.rank}
        rec.update(fields)
        rec = _sanitize(rec)
        line = json.dumps(rec, default=str) + "\n"
        with self._lock:
            self._write_locked(line)
        return rec

    def emit_bounded(self, kind: str, timeout_s: float = 2.0, **fields):
        """Best-effort emit that gives up when the writer lock cannot be
        acquired within `timeout_s`. For exit paths — the watchdog's 77
        fires exactly when a wedged disk may have the MAIN thread stalled
        inside emit() holding the lock; a blocking acquire here would
        deadlock the escape hatch it is reporting."""
        rec = _sanitize({"ts": round(time.time(), 3), "kind": kind,
                         "rank": self.rank, **fields})
        line = json.dumps(rec, default=str) + "\n"
        if not self._lock.acquire(timeout=timeout_s):
            return
        try:
            self._write_locked(line)
        finally:
            self._lock.release()

    def _write_locked(self, line: str):
        if self._dead:
            return
        try:
            if self._size + len(line) > self.max_bytes and self._size > 0:
                self._f.close()
                os.replace(self.path, self.path + ".1")
                self._open_locked()
            self._f.write(line)
            self._f.flush()
            self._size += len(line)
        except (OSError, ValueError) as ex:
            self._dead = True
            sys.stderr.write(f"[obs] event log {self.path} disabled: "
                             f"{type(ex).__name__}: {ex}\n")

    def close(self):
        with self._lock:
            if self._f is not None and not self._dead:
                try:
                    self._f.close()
                except OSError:
                    pass
            self._f = None
            self._dead = True


def load_events(path: str, rotated: bool = True) -> list[dict]:
    """Parse a JSONL event log (optionally prepending its `.1` rotation),
    skipping torn lines — a reader must work on the log of a crashed run."""
    out: list[dict] = []
    paths = ([path + ".1"] if rotated and os.path.exists(path + ".1")
             else []) + [path]
    for p in paths:
        try:
            with open(p) as f:
                for line in f:
                    line = line.strip()
                    if not line:
                        continue
                    try:
                        out.append(json.loads(line))
                    except ValueError:
                        continue        # torn final line of a killed run
        except OSError:
            continue
    return out


# ----------------------------------------------------------------------------
# the facade run.py / serve.py / resilience.py thread through
# ----------------------------------------------------------------------------

class Obs:
    """One per run: a registry plus an optional event log. Without a log
    path the registry still works (serve's `stats`/`metrics` ops) and
    `emit` is a no-op — so default runs pay nothing but a dict lookup."""

    def __init__(self, path: str = "", rank: int = 0):
        self.rank = int(rank)
        self.registry = Registry()
        self.log_path = path or ""
        self.events = EventLog(path, rank=rank) if path else None
        # host spans: the main thread's open spans, the seconds each name
        # took itself (children taken out) since the last take_phases(),
        # the open epoch mark, the last rusage reading and the per-program
        # call times `first_call:` spans are made from
        self._spans: list = []
        self.phase_s: dict = {}
        self._epoch_mark = None
        self._rusage = None
        self._calls: dict = {}
        # jax.monitoring records (kind, start, end, fun_name) not yet taken
        # by an epoch: set-up spans read theirs by time, the loop takes the
        # rest each epoch. Bounded: a process that never takes them (a
        # server) keeps the newest. Fed only while subscribed (make_obs),
        # from whichever thread compiles (the host eval thread too), so
        # read and cleared under the lock.
        self._compiles: deque = deque(maxlen=COMPILES_KEPT)
        self._compiles_lock = threading.Lock()

    def emit(self, kind: str, **fields):
        if self.events is not None:
            self.events.emit(kind, **fields)

    def emit_bounded(self, kind: str, **fields):
        """Never-blocking variant for exit paths (watchdog): skips the
        event rather than wait on a lock a stalled writer may hold."""
        if self.events is not None:
            self.events.emit_bounded(kind, **fields)

    def snapshot(self) -> dict:
        return self.registry.snapshot()

    def take_phases(self) -> dict:
        """{span name: seconds inside it, its children taken out} since the
        last call; starts the next account."""
        out = {k: round(v, 6) for k, v in self.phase_s.items()}
        self.phase_s = {}
        return out

    def epoch_begin(self, epoch: int):
        """Closes the previous epoch's StepTraceAnnotation and opens this
        one's: the mark spans the whole loop body, `continue` paths too."""
        self.epoch_end()
        from jax.profiler import StepTraceAnnotation
        self._epoch_mark = StepTraceAnnotation(EPOCH_MARK, step_num=int(epoch))
        self._epoch_mark.__enter__()

    def epoch_end(self):
        if self._epoch_mark is not None:
            self._epoch_mark.__exit__(None, None, None)
            self._epoch_mark = None

    def rusage_delta(self) -> dict:
        """This process's CPU seconds (user + system), involuntary context
        switches and major page faults since the last call: one getrusage.
        A long `wait_s` with `nivcsw` up and `cpu_s` flat is a descheduled
        host; with `cpu_s` up by as much it is the process's own threads."""
        import resource
        ru = resource.getrusage(resource.RUSAGE_SELF)
        now = (ru.ru_utime + ru.ru_stime, ru.ru_nivcsw, ru.ru_majflt)
        was = self._rusage or now
        self._rusage = now
        return {"cpu_s": round(now[0] - was[0], 6),
                "nivcsw": int(now[1] - was[1]),
                "majflt": int(now[2] - was[2])}

    def note_call(self, program: str, seconds: float):
        """One call of a jitted program of the loop took `seconds` (dispatch
        to result). After the fourth, its `first_call:<program>` span says
        what compiling or loading it cost: the first call less the median of
        the next three."""
        rec = self._calls.setdefault(program, [time.time() - seconds])
        if rec is not None:
            rec.append(float(seconds))
            if len(rec) == 5:
                self._emit_first_call(program)

    def _emit_first_call(self, program: str):
        t0, first, *later = self._calls[program]
        self._calls[program] = None
        if later:
            later.sort()
            self.emit("span", name=FIRST_CALL + program,
                      parent=SETUP_SPANS[0], t0=round(t0, 6),
                      dur_s=round(first - later[len(later) // 2], 6),
                      calls=1 + len(later))

    def flush_first_calls(self):
        """At the loop's end: a run too short for four calls of a program
        still says what its first call cost, against the calls it made."""
        for program, rec in list(self._calls.items()):
            if rec is not None:
                self._emit_first_call(program)

    def on_compile(self, kind: str, start: float, end: float,
                   fun_name: str):
        """One compile record from the dispatcher (any thread)."""
        with self._compiles_lock:
            self._compiles.append((kind, start, end, fun_name))

    def _read_compiles(self, clear: bool) -> list:
        with self._compiles_lock:
            recs = list(self._compiles)
            if clear:
                self._compiles.clear()
        return recs

    def take_compiles(self) -> dict:
        """The compile account of what jax did since the last call (or since
        the set-up root closed), with the outermost compiles' `programs` and
        the first record's wall `t0`; {} when nothing compiled. The loop's
        one check an epoch: a record that lands after it is the next
        epoch's."""
        if not self._compiles:
            return {}
        return compile_account(self._read_compiles(clear=True),
                               programs=True)

    def close(self):
        self.epoch_end()
        unsubscribe_compiles(self.on_compile)
        if self.events is not None:
            self.events.close()


class _NullSpan:
    """What `span` hands out under --obs off: enters, exits, begins and ends
    nothing, and is one object for the life of the process."""
    __slots__ = ()
    dur_s = 0.0

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    begin = __enter__

    def end(self):
        pass


NO_SPAN = _NullSpan()


class Span:
    """One named stretch of host time on the main thread. While it is open a
    jax.profiler.TraceAnnotation(SPAN_PREFIX + name) is too, so a profiler
    window shows it on the device lanes' clock; when it closes, its seconds
    (less its children's) join `obs.phase_s[name]`, and `emit=True` writes a
    `span` event. `with span(...)`, or begin()/end() around code that cannot
    be indented under one block."""

    __slots__ = ("obs", "name", "emit", "parent", "t0_wall", "t0", "dur_s",
                 "_mark", "_child_s")

    def __init__(self, obs: Obs, name: str, emit: bool = False,
                 parent: Optional[str] = None):
        self.obs, self.name, self.emit, self.parent = obs, name, emit, parent
        self.dur_s = 0.0
        self._child_s = 0.0

    def begin(self):
        from jax.profiler import TraceAnnotation
        stack = self.obs._spans
        if self.parent is None and stack:
            self.parent = stack[-1].name
        stack.append(self)
        self.t0_wall = time.time()
        self._mark = TraceAnnotation(SPAN_PREFIX + self.name)
        self._mark.__enter__()
        self.t0 = time.perf_counter()
        return self

    def end(self):
        self.dur_s = time.perf_counter() - self.t0
        self._mark.__exit__(None, None, None)
        stack = self.obs._spans
        while stack and stack.pop() is not self:
            pass                    # a span left open by a raise inside it
        if stack:
            stack[-1]._child_s += self.dur_s
        acc = self.obs.phase_s
        acc[self.name] = acc.get(self.name, 0.0) + self.dur_s - self._child_s
        if self.emit:
            fields = {}
            if self.obs._compiles:
                # the outermost set-up span reads its records and clears
                # them: what the loop takes from here on is its own
                recs = self.obs._read_compiles(
                    clear=not any(s.emit for s in stack))
                compiled = compile_account(recs, self.t0_wall, time.time())
                if compiled:
                    fields["compile"] = compiled
            self.obs.emit("span", name=self.name, parent=self.parent,
                          t0=round(self.t0_wall, 6),
                          dur_s=round(self.dur_s, 6), **fields)

    __enter__ = begin

    def __exit__(self, *exc):
        self.end()
        return False


def span(obs: Optional[Obs], name: str, emit: bool = False,
         parent: Optional[str] = None):
    """The one host-span primitive (see Span). `obs=None` (--obs off) gets
    the shared null span: nothing is constructed, timed or annotated."""
    return NO_SPAN if obs is None else Span(obs, name, emit, parent)


# ----------------------------------------------------------------------------
# boot stamps: set-up that runs before an Obs exists
# ----------------------------------------------------------------------------

# {name: (wall t0, seconds, extra fields)}; a name keeps its first stamp, so
# a second run in one process writes the process's own import and backend
# start again, at their own t0. A stamp imports no jax and needs no Obs.
_BOOT: dict = {}
_BOOT_OPEN: dict = {}


def boot_begin(name: str):
    _BOOT_OPEN[name] = time.time()


def boot_end(name: str, **fields):
    t0 = _BOOT_OPEN.pop(name, None)
    if t0 is not None and name not in _BOOT:
        _BOOT[name] = (t0, time.time() - t0, fields)


def proc_start_wall() -> Optional[float]:
    """Wall time at which the OS started this process: /proc/self/stat
    field 22 (clock ticks after boot) plus /proc/stat `btime` (whole
    seconds, so within a second). None where these cannot be read."""
    try:
        with open("/proc/self/stat") as f:
            # fields after the parenthesised command name start at field 3
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/stat") as f:
            btime = next(int(ln.split()[1]) for ln in f
                         if ln.startswith("btime "))
        return round(btime + ticks / os.sysconf("SC_CLK_TCK"), 3)
    except (OSError, ValueError, IndexError, StopIteration):
        return None


# ----------------------------------------------------------------------------
# the compile account: the process's one jax.monitoring registration
# ----------------------------------------------------------------------------

# jax.monitoring's time spans (wall clock, `fun_name` in their metadata) and
# counted events, by the kind the account books them under. In jax 0.9
# `compile` wraps compile_or_get_cached, so a persistent-cache hit's load is
# inside it; `misses` counts the executables written to that cache.
COMPILE_SPANS = {"/jax/core/compile/jaxpr_trace_duration": "trace",
                 "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
                 "/jax/core/compile/backend_compile_duration": "compile"}
CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "hits",
                "/jax/compilation_cache/cache_misses": "misses"}
COMPILES_KEPT = 1 << 16

# weak references to fn(kind, start, end, fun_name): an Obs or StrictExec
# dropped without closing (a run that raised in set-up) stops hearing. A
# tuple replaced whole under the lock, so the listeners read it without one.
_subscribers: tuple = ()
_sub_lock = threading.Lock()


def _on_time_span(event: str, start_time: float, end_time: float, **kw):
    kind = COMPILE_SPANS.get(event)
    if kind is not None:
        _dispatch(kind, start_time, end_time, kw.get("fun_name", ""))


def _on_event(event: str, **kw):
    kind = CACHE_EVENTS.get(event)
    if kind is not None:
        t = time.time()
        _dispatch(kind, t, t, "")


def _dispatch(kind, start, end, fun_name):
    for ref in _subscribers:
        fn = ref()
        if fn is not None:
            fn(kind, start, end, fun_name)


def subscribe_compiles(fn):
    """Hear every trace, lowering, compile (or cache load), cache hit and
    miss of the process as fn(kind, start, end, fun_name), on the thread
    that compiled. The first subscriber registers the dispatcher with
    jax.monitoring; the last to leave unregisters it."""
    global _subscribers
    ref = weakref.WeakMethod(fn) if hasattr(fn, "__self__") \
        else weakref.ref(fn)
    with _sub_lock:
        live = tuple(r for r in _subscribers if r() is not None)
        if not _subscribers:
            from jax import monitoring
            monitoring.register_event_time_span_listener(_on_time_span)
            monitoring.register_event_listener(_on_event)
        _subscribers = live + (ref,)


def unsubscribe_compiles(fn):
    global _subscribers
    with _sub_lock:
        if not _subscribers:
            return
        _subscribers = tuple(r for r in _subscribers
                             if r() is not None and r() != fn)
        if not _subscribers:
            from jax import monitoring
            monitoring.unregister_event_time_span_listener(_on_time_span)
            monitoring.unregister_event_listener(_on_event)


def _union_s(spans: list) -> float:
    total, hi = 0.0, -math.inf
    for a, b in sorted(spans):
        if b > hi:
            total += b - max(a, hi)
            hi = b
    return total


def compile_account(records, lo: Optional[float] = None,
                    hi: Optional[float] = None,
                    programs: bool = False) -> dict:
    """{trace_s, lower_s, compile_s, hits, misses} of (kind, start, end,
    fun_name) records, clipped to [lo, hi] where given. A kind's seconds are
    the union of its spans, not their sum: a jit traced inside another's
    trace reports its own span inside the outer one. `programs` adds the
    fun_names of the outermost compiles and the first record's wall `t0`.
    {} when no record falls inside."""
    spans = {"trace": [], "lower": [], "compile": []}
    counts = {"hits": 0, "misses": 0}
    kept = []
    for kind, a, b, name in records:
        if lo is not None:
            a, b = max(a, lo), min(b, hi)
            if a > b:
                continue
        kept.append((a, b))
        if kind in spans:
            spans[kind].append((a, b, name))
        else:
            counts[kind] += 1
    if not kept:
        return {}
    out = {f"{k}_s": round(_union_s([(a, b) for a, b, _ in v]), 6)
           for k, v in spans.items()}
    out.update(counts)
    if programs:
        names, reach = [], -math.inf
        for a, b, name in sorted(spans["compile"],
                                 key=lambda r: (r[0], -r[1])):
            if b > reach:
                reach = b
                if name not in names:
                    names.append(name)
        out["programs"] = names
        out["t0"] = round(min(a for a, _ in kept), 6)
    return out


def rank_log_path(path: str, rank: int) -> str:
    """Per-rank event-log file: rank 0 owns the bare path, every other rank
    writes `<path>.r<rank>` — two coordinated processes handed the same
    --obs-log must never interleave writes into one file."""
    return path if rank == 0 or not path else f"{path}.r{rank}"


def make_obs(cfg, rank: int = 0, log=print) -> Optional[Obs]:
    """Obs for this run, or None under `--obs off` (every call site guards —
    off constructs nothing: no registry, no file, no signal handler)."""
    if getattr(cfg, "obs", "on") != "on":
        return None
    path = cfg.obs_log or os.environ.get("BNSGCN_OBS_LOG", "")
    path = rank_log_path(path, rank)
    obs = Obs(path, rank=rank)
    if path:
        log(f"[obs] event log -> {path}")
    for name, (t0, dur_s, fields) in sorted(_BOOT.items(),
                                            key=lambda kv: kv[1][0]):
        obs.emit("span", name=name, parent=BOOT_PARENT, t0=round(t0, 6),
                 dur_s=round(dur_s, 6), **fields)
    subscribe_compiles(obs.on_compile)
    return obs


# ----------------------------------------------------------------------------
# post-mortem capture (watchdog 77, divergence 76, SIGUSR1 snapshots)
# ----------------------------------------------------------------------------

def postmortem_dir(cfg) -> str:
    """Where exits 75/76/77/78 leave their files: `--obs-dir`, default
    `{ckpt_path}/postmortem`."""
    return getattr(cfg, "obs_dir", "") or os.path.join(cfg.ckpt_path,
                                                       "postmortem")


def write_postmortem(dirpath: str, tag: str, text: str = "",
                     registry: Optional[Registry] = None,
                     stacks: bool = True) -> str:
    """Write `<tag>_<pid>.txt` (free text + all-thread stacks) and, when a
    registry is given, `<tag>_<pid>_metrics.json` (its snapshot) under
    `dirpath`. Returns the text file's path, or "" when the write failed
    (disk full — the exact condition post-mortems target): callers must
    not advertise a breadcrumb that does not exist. Never raises; the
    degraded fallback is the stderr dump the caller already made."""
    try:
        os.makedirs(dirpath, exist_ok=True)
        base = os.path.join(dirpath, f"{tag}_{os.getpid()}")
        path = base + ".txt"
        with open(path, "w") as f:
            if text:
                f.write(text.rstrip("\n") + "\n")
            if stacks:
                f.write("\n--- all-thread stacks ---\n")
                f.flush()
                faulthandler.dump_traceback(file=f, all_threads=True)
            f.flush()
            os.fsync(f.fileno())
    except OSError:
        return ""
    if registry is not None:
        try:
            with open(base + "_metrics.json", "w") as f:
                json.dump(registry.snapshot(), f, indent=1)
        except OSError:
            pass
    return path
