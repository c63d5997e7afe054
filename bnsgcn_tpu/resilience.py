"""Self-healing training loop: the in-process resilience subsystem.

At multi-hour full-graph scale (ROADMAP north star; Plexus, arXiv:2505.04083)
preemption and divergence — not throughput — bound a run. Before this module
the epoch loop had zero failure handling: a NaN loss trained to garbage
silently, a SIGTERM (TPU maintenance / spot preemption) lost everything since
the last periodic checkpoint, a torn `.ckpt` crashed `--resume`, and a hung
collective was only caught by shell scripts polling from OUTSIDE the
process. This module brings all four recoveries in-process:

* **Divergence guard + rollback** — `run_training` checks the already-host-
  fetched loss every step (free: the loop fetched it for `res.losses` anyway)
  and a param-global-norm probe every `log_every`. On NaN/Inf it rolls
  params/opt/BN state back to the newest VALID checkpoint (or the initial
  state), re-folds the sampling/dropout key streams with a retry nonce —
  BNS resamples per epoch (PAPER §3), so a diverged epoch is cheap to retry
  under a fresh fold of the shared PRNG — and retries with exponential
  backoff, aborting with a diagnostic report after `--resil-retries`.
* **Preemption-safe shutdown** — SIGTERM/SIGINT set a flag the loop reads at
  the step boundary; the loop writes a final resumable checkpoint, closes any
  open profiler trace, and `main.py` exits with EXIT_PREEMPTED so a requeue
  wrapper can relaunch with `--resume` and continue bit-for-bit.
* **Hung-step watchdog** — a monitor thread with a deadline derived from the
  rolling epoch-time mean; on expiry it dumps all-thread stacks and live-
  array state to stderr and exits EXIT_WATCHDOG, replacing the shell
  watchdogs' liveness probe for the training process itself.
* **Deterministic fault injection** — `--inject nan@E12,sigterm@E20,hang@E8,
  ckpt-corrupt@E10` (env $BNSGCN_FAULT) fires each fault at the named epoch's
  step boundary, so every recovery path above is provable in CI on the CPU
  mesh (tests/test_resilience*.py, tools/fault_matrix.sh), not just on
  hardware.

`--resilience off` constructs none of this: the loop is bit-identical to the
pre-resilience code path (no extra device ops, no threads, no handlers).

**Multi-host** (this PR): with a rank coordinator (`parallel/coord.py`,
`--coord`) the manager runs on EVERY rank and the verdicts travel out-of-
band from the XLA collectives. At each step boundary `agree_step` contributes
the rank's local {ok, diverged, preempted} state; rank 0 reduces worst-wins
and all ranks act on the one agreed decision — a SIGTERM on a single rank
becomes a clean all-rank resumable exit 75, a NaN on any rank becomes a
coordinated rollback where rank 0 selects the checkpoint and broadcasts the
(restart epoch, retry nonce) every rank restores with, and a rank that
cannot restore fails the post-restore ack so everyone aborts loudly instead
of desyncing. The watchdog additionally dumps per-rank heartbeat liveness
before exit 77, naming the rank that stalled a hung collective. Multi-host
with `--coord off` keeps the PR-4 downgrade (rank-0 integrity chain only).

Timing knobs are env vars, not flags, so CI can shrink them without widening
the CLI surface:
  BNSGCN_WATCHDOG_GRACE_S   deadline before the first step completes (600)
  BNSGCN_WATCHDOG_FACTOR    deadline = max(MIN, FACTOR * rolling mean) (20)
  BNSGCN_WATCHDOG_MIN_S     deadline floor after the first step (300)
  BNSGCN_RETRY_BACKOFF_S    rollback backoff base, doubled per retry (1.0)
  BNSGCN_COORD_TIMEOUT_S    per-exchange coordinator deadline (120)
  BNSGCN_COORD_AGREE_EVERY  agree every K step boundaries, latching local
                            verdicts in between (1)
  BNSGCN_ELASTIC_DEAD_S     alive-beat age that proves a peer dead (6)
  BNSGCN_ELASTIC_MAX_RESIZES  resize budget per run before abort (8)
"""

from __future__ import annotations

import faulthandler
import os
import re
import signal
import sys
import threading
import time
from dataclasses import dataclass, field
from typing import Optional

from bnsgcn_tpu import checkpoint as ckpt
from bnsgcn_tpu import obs as obs_mod
from bnsgcn_tpu.config import ConfigError
from bnsgcn_tpu.parallel.coord import CoordAbort

# Distinct exit codes so a requeue wrapper can tell retryable states apart:
EXIT_PREEMPTED = 75   # EX_TEMPFAIL: resumable checkpoint written; relaunch
                      # with --resume continues bit-for-bit
EXIT_DIVERGED = 76    # rollback retries exhausted; diagnostic report printed
EXIT_WATCHDOG = 77    # hung step: stacks + live arrays dumped to stderr
                      # (multi-host: also a coordinator exchange timeout,
                      # after the peer-liveness dump named the stalled rank)
EXIT_COORD_ABORT = 78  # ranks agreed to abort: a peer cannot restore the
                       # chosen checkpoint (rollback or resume ack) — needs
                       # triage, not a blind requeue

FAULT_KINDS = ("nan", "sigterm", "hang", "ckpt-corrupt", "ranklost")

# serving-fleet faults ride the same --inject spec but fire on request
# COUNTS, not epochs: `servekill@N:pP.rR` / `servehang@N:pP.rR` kill or
# wedge backend (part P, replica R) after its Nth routed request;
# `servedrop@N` tears the connection of every backend's Nth request
# (a transient network blip — the router's retry path must absorb it)
SERVE_FAULT_KINDS = ("servekill", "servehang", "servedrop")


class PreemptedError(Exception):
    """Raised by run_training at a step boundary after SIGTERM/SIGINT: the
    final resumable checkpoint is already on disk at `.ckpt_path`."""

    def __init__(self, epoch: int, ckpt_path: str = ""):
        self.epoch = epoch
        self.ckpt_path = ckpt_path
        super().__init__(
            f"preempted at epoch {epoch}; resumable checkpoint at "
            f"{ckpt_path or '<none>'} — relaunch with --resume")


class DivergenceError(Exception):
    """Raised when divergence rollback retries are exhausted; the message is
    the full diagnostic report (also written next to the checkpoints)."""


class CheckpointUnavailable(Exception):
    """A rank could not obtain the agreed restore source (no usable file,
    no snapshot). Internal to coord_restore: it is reported through the
    coordinator ack so all ranks abort together, never raised past it."""


class RankLostExit(Exception):
    """Raised by fire_injections when this rank's scheduled `ranklost`
    fault fires: the process unwinds WITHOUT the orderly coordinator
    goodbye (no fin barrier, no final agree) and main.py exits 0 — to its
    peers it is indistinguishable from a preempted worker whose alive-beats
    stopped, which is exactly the heartbeat-silence path the elastic
    RESIZE detection must prove."""

    def __init__(self, epoch: int):
        self.epoch = epoch
        super().__init__(f"rank lost (injected) at epoch {epoch}")


# ----------------------------------------------------------------------------
# preemption signals — the PR-4 SIGTERM/SIGINT contract, reusable
# ----------------------------------------------------------------------------

class PreemptSignals:
    """SIGTERM/SIGINT -> a flag the owner polls at its own safe boundary;
    a SECOND signal restores default handling and re-raises (the operator,
    or the platform's kill escalation, wants out NOW). Extracted from
    ResilienceManager so the online inference server (serve.py) drains with
    the exact same handler semantics the training loop checkpoints with.

    `action` is the one-line promise printed on the first signal — what the
    owner will do at its `boundary` before exiting EXIT_PREEMPTED.

    `profile=True` additionally claims SIGUSR1 as the ON-DEMAND PROFILING
    signal (the obs telemetry bus): the handler only sets a flag; the owner
    polls `take_profile_request()` at its boundary and captures a bounded
    jax.profiler trace window + all-thread stacks + registry snapshot into
    the post-mortem dir WITHOUT stopping training (run.py's loop)."""

    def __init__(self, action: str = "checkpoint",
                 boundary: str = "step boundary", profile: bool = False):
        self.action = action
        self.boundary = boundary
        self.profile = profile
        self._requested: Optional[str] = None
        self._profile_requested = False
        self._old_handlers: dict = {}

    def install(self):
        """Main thread only — a worker-thread owner just skips them."""
        if threading.current_thread() is threading.main_thread():
            sigs = [signal.SIGTERM, signal.SIGINT]
            if self.profile and hasattr(signal, "SIGUSR1"):
                sigs.append(signal.SIGUSR1)
            for sig in sigs:
                try:
                    handler = (self._on_profile
                               if self.profile and hasattr(signal, "SIGUSR1")
                               and sig == signal.SIGUSR1 else self._on_signal)
                    self._old_handlers[sig] = signal.signal(sig, handler)
                except (ValueError, OSError):
                    pass
        return self

    def restore(self):
        for sig, old in self._old_handlers.items():
            try:
                signal.signal(sig, old)
            except (ValueError, OSError):
                pass
        self._old_handlers.clear()

    def _on_signal(self, signum, frame):
        name = signal.Signals(signum).name
        if self._requested is not None:
            signal.signal(signum, signal.SIG_DFL)
            signal.raise_signal(signum)
            return
        self._requested = name
        # async-signal-safe enough: one line, flushed by the owner's boundary
        sys.stderr.write(
            f"\n[resilience] {name} received: will {self.action} and exit "
            f"{EXIT_PREEMPTED} at the next {self.boundary} (send again to "
            f"kill immediately)\n")

    def _on_profile(self, signum, frame):
        # flag only — the owner's boundary does the capture (a signal
        # handler must never touch jax/profiler state mid-step)
        self._profile_requested = True
        sys.stderr.write(
            "\n[obs] SIGUSR1 received: will capture stacks + metrics + a "
            "bounded profiler window at the next step boundary\n")

    def take_profile_request(self) -> bool:
        """True exactly once per SIGUSR1 — the owner consumes the flag."""
        if self._profile_requested:
            self._profile_requested = False
            return True
        return False

    @property
    def requested(self) -> Optional[str]:
        return self._requested


# ----------------------------------------------------------------------------
# fault-injection plan
# ----------------------------------------------------------------------------

@dataclass
class FaultPlan:
    """Parsed `--inject` spec: kind -> sorted epochs, each fired once."""

    faults: dict = field(default_factory=dict)   # kind -> set of epochs

    @staticmethod
    def parse(spec: str, rank: int = 0) -> "FaultPlan":
        """Grammar: comma-separated `kind@E<epoch>[:r<rank>]` terms, e.g.
        `nan@E12,sigterm@E20:r1,hang@E8,ckpt-corrupt@E10`. The rank suffix
        targets one rank of a multi-host run (partial faults — the whole
        point of the coordinated-abort tests); the rank-less form keeps its
        historical meaning, "fire on all ranks". Every term is validated
        even when targeted elsewhere — a typo'd injection silently not
        firing would make a CI fault run vacuously green."""
        plan = FaultPlan()
        for term in filter(None, (t.strip() for t in spec.split(","))):
            kind = term.partition("@")[0]
            if kind in SERVE_FAULT_KINDS:
                # serving-fleet faults share the spec string but fire on
                # request counts inside backend processes — validate here
                # (a typo'd term must fail in EVERY consumer) and skip
                _parse_serve_term(term)
                continue
            kind, sep, rest = term.partition("@")
            ep, rsep, rk = rest.partition(":")
            if (not sep or not ep.startswith("E")
                    or not ep[1:].isdigit()
                    or (rsep and not (rk.startswith("r")
                                      and rk[1:].isdigit()))):
                raise ValueError(
                    f"bad --inject term {term!r}: expected "
                    f"kind@E<epoch>[:r<rank>] "
                    f"(kinds: {', '.join(FAULT_KINDS)})")
            if kind not in FAULT_KINDS:
                raise ValueError(
                    f"unknown --inject fault {kind!r} "
                    f"(kinds: {', '.join(FAULT_KINDS)})")
            if kind == "ranklost" and not rsep:
                # rank-less faults mean "fire on every rank" — losing ALL
                # ranks is not a resize, so the grammar refuses it up front
                raise ConfigError(
                    f"--inject term {term!r}: ranklost needs an explicit "
                    f":r<rank> target (losing every rank is not a resize); "
                    f"use ranklost@E<epoch>:r<rank>")
            if rsep and int(rk[1:]) != rank:
                continue                # valid term, targets another rank
            plan.faults.setdefault(kind, set()).add(int(ep[1:]))
        return plan

    def pop(self, kind: str, epoch: int) -> bool:
        """True exactly once when `kind` is scheduled at `epoch`."""
        eps = self.faults.get(kind)
        if eps and epoch in eps:
            eps.discard(epoch)
            return True
        return False

    def empty(self) -> bool:
        return not any(self.faults.values())


def _parse_serve_term(term: str) -> tuple[str, int, Optional[tuple]]:
    """Validate one serving-fault term; returns (kind, nth, target) where
    target is (part, replica) or None. Grammar: `kind@<N>[:p<P>.r<R>]`.
    Target validation mirrors `ranklost`: servekill/servehang require an
    explicit backend target (killing EVERY backend is not a failover
    test), while servedrop is transient and may stay fleet-wide."""
    kind, sep, rest = term.partition("@")
    nth, tsep, tgt = rest.partition(":")
    if not sep or not nth.isdigit():
        raise ValueError(
            f"bad --inject term {term!r}: expected "
            f"kind@<N>[:p<part>.r<replica>] "
            f"(serve kinds: {', '.join(SERVE_FAULT_KINDS)})")
    target = None
    if tsep:
        m = re.fullmatch(r"p(\d+)\.r(\d+)", tgt)
        if not m:
            raise ValueError(
                f"bad --inject term {term!r}: backend target must be "
                f"p<part>.r<replica> (e.g. servekill@5:p0.r1)")
        target = (int(m.group(1)), int(m.group(2)))
    if kind in ("servekill", "servehang") and target is None:
        raise ConfigError(
            f"--inject term {term!r}: {kind} needs an explicit "
            f":p<part>.r<replica> target (wedging every backend is not a "
            f"failover test); use {kind}@<N>:p<part>.r<replica>")
    return kind, int(nth), target


@dataclass
class ServeFaultPlan:
    """Parsed serving-fault terms of an `--inject` spec, scoped to ONE
    backend (part, replica): kind -> set of request ordinals, each fired
    once. The training twin is `FaultPlan`; both parsers validate every
    term of a mixed spec so a typo fails loudly in whichever process
    sees it first."""

    faults: dict = field(default_factory=dict)   # kind -> set of ordinals

    @staticmethod
    def parse(spec: str, part: int = -1, replica: int = 0) -> "ServeFaultPlan":
        plan = ServeFaultPlan()
        for term in filter(None, (t.strip() for t in spec.split(","))):
            if term.partition("@")[0] not in SERVE_FAULT_KINDS:
                continue                # a training term; FaultPlan's beat
            kind, nth, target = _parse_serve_term(term)
            if target is not None and target != (part, replica):
                continue                # valid term, targets another backend
            plan.faults.setdefault(kind, set()).add(nth)
        return plan

    def pop(self, kind: str, count: int) -> bool:
        """True exactly once when `kind` is scheduled at request `count`."""
        ns = self.faults.get(kind)
        if ns and count in ns:
            ns.discard(count)
            return True
        return False

    def empty(self) -> bool:
        return not any(self.faults.values())


# ----------------------------------------------------------------------------
# hung-step watchdog
# ----------------------------------------------------------------------------

class _Watchdog(threading.Thread):
    """Monitor thread: the loop calls `beat()` at each step boundary; if no
    beat lands within the deadline (rolling-mean-derived once steps flow,
    a grace period before that), dump all-thread stacks + live-array state
    and exit EXIT_WATCHDOG. Daemon: never blocks normal interpreter exit."""

    POLL_S = 0.25
    ROLLING = 20
    ALIVE_BEAT_S = 2.0      # coord: watchdog-thread heartbeat period, so
                            # peers can tell "process dead" from "step slow"

    def __init__(self, log=print, coord=None, postmortem_dir=None, obs=None):
        super().__init__(name="bnsgcn-watchdog", daemon=True)
        self.log = log
        self.coord = coord
        self.postmortem_dir = postmortem_dir    # obs on: the stack dump is
        self.obs = obs                          # also a FILE, not just stderr
        self.grace_s = float(os.environ.get("BNSGCN_WATCHDOG_GRACE_S", 600))
        self.factor = float(os.environ.get("BNSGCN_WATCHDOG_FACTOR", 20))
        # floor of 300 s: epoch-boundary work that is slow-but-legit (a
        # first-call eval compile, a multi-GB checkpoint fsync) must clear
        # it — the quarry is hung collectives, which are minutes-to-forever
        self.min_s = float(os.environ.get("BNSGCN_WATCHDOG_MIN_S", 300))
        self._durs: list[float] = []            # guarded-by: self._lock
        self._last_beat = time.monotonic()      # guarded-by: self._lock
        self._epoch = -1                        # guarded-by: self._lock
        self._halt = threading.Event()
        self._lock = threading.Lock()

    def beat(self, epoch: int):
        now = time.monotonic()
        with self._lock:
            if self._epoch >= 0:
                self._durs.append(now - self._last_beat)
                del self._durs[:-self.ROLLING]
            self._epoch = epoch
            self._last_beat = now

    def touch(self):
        """Reset the liveness clock WITHOUT recording a duration sample.

        Called after legitimate long epoch-boundary work (mesh eval incl.
        its first-call compile, checkpoint fsync, a rollback restore +
        backoff) so that time never eats into the next step's deadline —
        and so the rolling mean stays a pure step-time signal."""
        with self._lock:
            self._last_beat = time.monotonic()

    def deadline_s(self) -> float:
        with self._lock:
            if not self._durs:
                return self.grace_s
            mean = sum(self._durs) / len(self._durs)
        return max(self.min_s, self.factor * mean)

    def stop(self):
        self._halt.set()

    def run(self):
        last_alive = 0.0
        while not self._halt.wait(self.POLL_S):
            # one consistent snapshot per poll; beat()/touch() write these
            # from the main thread under the same lock
            with self._lock:
                epoch = self._epoch
                last_beat = self._last_beat
            if self.coord is not None:
                # alive-beat from THIS thread: proves the process is up even
                # while the main thread is stuck inside a collective —
                # exactly what the peers' liveness dump needs to separate
                # "rank died" from "rank hung"
                now = time.monotonic()
                if now - last_alive >= self.ALIVE_BEAT_S:
                    last_alive = now
                    try:
                        self.coord.heartbeat(epoch, self.coord.ALIVE_KEY)
                    except Exception:
                        pass        # best-effort; never kills the watchdog
            idle = time.monotonic() - last_beat
            deadline = self.deadline_s()
            if idle <= deadline:
                continue
            # the dump runs in its OWN daemon thread with a bounded join:
            # the 77 exit fires exactly when a wedged disk/NFS may block
            # any file write (or the obs writer lock) forever, and the
            # escape hatch must stay reachable regardless. The epoch rides
            # along as an argument — the dump thread must not need the lock.
            t = threading.Thread(target=self._dump,
                                 args=(idle, deadline, epoch),
                                 name="bnsgcn-watchdog-dump", daemon=True)
            t.start()
            t.join(timeout=30.0)
            if t.is_alive():
                sys.stderr.write("[watchdog] dump stalled (wedged "
                                 "filesystem?); exiting without it\n")
            os._exit(EXIT_WATCHDOG)

    def _dump(self, idle: float, deadline: float, epoch: int):
        try:
            sys.stderr.write(
                "\n[watchdog] step hung: no step-boundary heartbeat for "
                f"{idle:.1f}s (deadline {deadline:.1f}s, last epoch "
                f"{epoch}); dumping stacks and exiting "
                f"{EXIT_WATCHDOG}\n")
            faulthandler.dump_traceback(file=sys.stderr, all_threads=True)
            try:
                import jax
                arrs = jax.live_arrays()
                total = sum(getattr(a, "nbytes", 0) for a in arrs)
                sys.stderr.write(
                    f"[watchdog] {len(arrs)} live arrays, "
                    f"{total / 2**20:.1f} MB on device\n")
                for a in arrs[:8]:
                    sys.stderr.write(
                        f"[watchdog]   {a.dtype} {tuple(a.shape)}\n")
            except Exception:
                pass
            if self.coord is not None:
                # a hung collective should name the rank that stalled it:
                # dump every peer's last step-boundary heartbeat (epoch +
                # age) before dying
                try:
                    self.coord.log_liveness(
                        write=lambda s: sys.stderr.write(s + "\n"))
                except Exception:
                    pass
            dump_path = ""
            if self.postmortem_dir:
                # exit 77 must leave a post-mortem FILE a requeue wrapper
                # can point triage at after the machine is gone —
                # stderr alone dies with the terminal scrollback. "" =
                # write failed (disk full): no breadcrumb to a ghost file
                dump_path = obs_mod.write_postmortem(
                    self.postmortem_dir, f"watchdog_E{epoch}",
                    text=(f"watchdog: no step-boundary heartbeat for "
                          f"{idle:.1f}s (deadline {deadline:.1f}s, last "
                          f"epoch {epoch}); exiting "
                          f"{EXIT_WATCHDOG}"),
                    registry=(self.obs.registry
                              if self.obs is not None else None))
                if dump_path:
                    sys.stderr.write(
                        f"[watchdog] post-mortem dump: {dump_path}\n")
            if self.obs is not None:
                # bounded, own try: neither an unwritable post-mortem dir
                # nor a writer lock held by a disk-stalled main thread may
                # cost (or deadlock) the exit this event reports
                try:
                    self.obs.emit_bounded("watchdog_fire", epoch=epoch,
                                          idle_s=round(idle, 1),
                                          deadline_s=round(deadline, 1),
                                          dump=dump_path or None)
                except Exception:
                    pass
            sys.stderr.flush()
        except Exception:
            pass    # dumping must never mask the exit itself


# ----------------------------------------------------------------------------
# the manager run_training threads its loop through
# ----------------------------------------------------------------------------

class ResilienceManager:
    """One per run_training call (`--resilience on`). Owns the signal
    handlers, the watchdog, the fault plan, and the rollback state;
    `close()` restores the process to its pre-run state so sequential
    run_training calls (tests, bench sweeps) never leak handlers/threads.

    Single-host: `coord` is None and the manager behaves exactly as in
    PR 4. Multi-host (`--coord`): one manager per rank, every local verdict
    routed through `agree_step` so all ranks act together."""

    def __init__(self, cfg, log=print, start_epoch: int = 0,
                 retry_nonce: int = 0, coord=None, obs=None,
                 resize_nonce: int = 0):
        self.cfg = cfg
        self.log = log
        self.start_epoch = start_epoch
        self.coord = coord
        self.obs = obs          # telemetry bus (obs.py): every recovery
                                # below leaves a structured lifecycle event
                                # so exits 75/76/77/78 have a post-mortem
                                # trail; None under --obs off (no event, no
                                # file — the pre-obs paths verbatim)
        self.postmortem_dir = (obs_mod.postmortem_dir(cfg)
                               if obs is not None else None)
        self.rank = coord.rank if coord is not None else 0
        self.plan = FaultPlan.parse(
            cfg.inject or os.environ.get("BNSGCN_FAULT", ""), rank=self.rank)
        if not self.plan.empty():
            log(f"[resilience] fault plan armed (rank {self.rank}): "
                + ",".join(f"{k}@E{e}" for k, eps in
                           sorted(self.plan.faults.items())
                           for e in sorted(eps)))
        self.retries = 0
        self.nonce = retry_nonce        # cumulative rollback count; folds the
                                        # sampling/dropout streams (persisted
                                        # in ckpt extra so resume re-applies)
        self.backoff_base = float(os.environ.get("BNSGCN_RETRY_BACKOFF_S", 1.0))
        self.backoff_cap = 30.0
        self.rollbacks: list[dict] = []     # surfaced on RunResult
        # elastic world size (--elastic on + a coordinator): rank loss
        # becomes a RESIZE verdict instead of CoordTimeout->77
        self.elastic = (getattr(cfg, "elastic", "off") == "on"
                        and coord is not None)
        self.resize_nonce = resize_nonce    # restore-carrying resizes so
                                            # far; folds the key streams
                                            # under a domain disjoint from
                                            # the retry nonce's (persisted
                                            # in ckpt extra like it)
        self.resizes = 0
        self.max_resizes = int(os.environ.get(
            "BNSGCN_ELASTIC_MAX_RESIZES", 8))
        self._signals = PreemptSignals(action="checkpoint",
                                       profile=obs is not None)
        # decide/ack seam: the rollback paths reach checkpoint I/O and the
        # backoff sleep ONLY through these attributes, so the protocol
        # checker (analysis/proto) can drive the real plan_rollback /
        # coord_restore logic against fake payloads under a virtual clock.
        # Production constructs nothing extra — these ARE the real functions.
        self._find_ckpt = ckpt.latest_valid_checkpoint
        self._load_ckpt = ckpt.load_checkpoint
        self._restore_into = ckpt.restore_into
        self._sleep = time.sleep
        self._snapshot = None
        self._pending_payload = None    # rank 0: the checkpoint payload
                                        # plan_rollback just validated, so
                                        # coord_restore never re-reads it
        self.watchdog = _Watchdog(log, coord=coord,
                                  postmortem_dir=self.postmortem_dir,
                                  obs=obs)

    # -- lifecycle --

    def start(self):
        """Install signal handlers (main thread only — a worker-thread
        run_training just skips them) and start the watchdog."""
        self._signals.install()
        self.watchdog.start()
        return self

    def close(self):
        self.watchdog.stop()
        self.watchdog.join(timeout=2.0)
        self._signals.restore()

    # -- preemption / on-demand profiling --

    @property
    def preempt_requested(self) -> Optional[str]:
        return self._signals.requested

    def take_profile_request(self) -> bool:
        """True once per SIGUSR1 (--obs on only): run.py's loop answers it
        with a post-mortem snapshot + a bounded profiler trace window."""
        return self._signals.take_profile_request()

    def _emit(self, kind: str, **fields):
        if self.obs is not None:
            self.obs.emit(kind, **fields)

    # -- divergence rollback --

    def set_initial_snapshot(self, params_host, opt_host, state_host):
        """Host copies of the fresh (or resumed) training state: the rollback
        target when no valid checkpoint exists yet."""
        self._snapshot = (params_host, opt_host, state_host)

    def note_progress(self, epoch: int):
        """A guard-verified periodic checkpoint landed at `epoch`, strictly
        past the last rollback: that divergence is healed, so the retry /
        backoff budget resets — a multi-day run surviving N independent
        transients must not abort on the (N+1)th just because the counter
        never forgot. The key-fold nonce is NOT reset: it must stay
        monotonic for stream distinctness."""
        if (self.retries and self.rollbacks
                and epoch > self.rollbacks[-1]["epoch"]):
            self.retries = 0

    def rollback(self, epoch: int, loss_f: float, params_t, opt_t, state_t):
        """Restore the last good state after a non-finite loss/param probe.

        TWIN of plan_rollback/coord_restore (the coordinated split of the
        same policy): retry budget, checkpoint selection, nonce and backoff
        MUST stay in lockstep — change one, change both. Kept separate
        because this single-host path is behavior-pinned bitwise by the
        PR-4 tests (sleep-before-restore ordering, log wording) and the
        coordinated path must publish its decision BEFORE sleeping.

        Returns (params_host, opt_host, state_host, restart_epoch, nonce):
        host trees bitwise-equal the checkpoint they restore (pinned by
        tests/test_resilience.py), the epoch to resume the loop at, and the
        new retry nonce to re-fold the sampling/dropout keys with. Raises
        DivergenceError with a diagnostic report once retries are exhausted.
        """
        self.retries += 1
        limit = max(int(self.cfg.resil_retries), 0)
        found = self._find_ckpt(self.cfg, log=self.log, before_epoch=epoch)
        if self.retries > limit:
            raise DivergenceError(self._report(epoch, loss_f, found))
        backoff = min(self.backoff_cap,
                      self.backoff_base * (2 ** (self.retries - 1)))
        if backoff > 0:
            self.log(f"[resilience] backing off {backoff:.1f}s before retry "
                     f"{self.retries}/{limit}")
            self._sleep(backoff)
        if found is not None:
            path, payload = found
            p, o, s = self._restore_into(payload, params_t, opt_t, state_t)
            restart = int(payload["epoch"]) + 1
            src = os.path.basename(path)
        else:
            if self._snapshot is None:
                raise DivergenceError(self._report(epoch, loss_f, None))
            p, o, s = self._snapshot
            restart = self.start_epoch
            src = "<initial state>"
        self.nonce += 1
        self.rollbacks.append({"epoch": epoch, "restart": restart,
                               "source": src, "nonce": self.nonce})
        self._emit("rollback", epoch=int(epoch), restart=int(restart),
                   source=src, nonce=int(self.nonce), loss=float(loss_f),
                   retry=self.retries, limit=limit)
        self.log(
            f"[resilience] non-finite training state at epoch {epoch} "
            f"(loss={loss_f}): rolled back to {src}, restarting at epoch "
            f"{restart} with retry-nonce {self.nonce} folded into the "
            f"sampling/dropout keys (retry {self.retries}/{limit})")
        return p, o, s, restart, self.nonce

    def _report(self, epoch: int, loss_f: float, found) -> str:
        lines = [
            f"divergence unrecovered after {self.retries - 1} rollback "
            f"retr{'y' if self.retries == 2 else 'ies'} "
            f"(--resil-retries {self.cfg.resil_retries}):",
            f"  epoch {epoch}: loss={loss_f}",
            f"  last valid checkpoint: "
            f"{found[0] if found else '<none found>'}",
            f"  rollback history: {self.rollbacks or '<none>'}",
            "  likely causes: lr too high for this sampling rate, bad input "
            "features, or fp8/int8 wire overflow — see README 'Fault "
            "tolerance'",
        ]
        report = "\n".join(lines)
        try:
            os.makedirs(self.cfg.ckpt_path, exist_ok=True)
            rp = os.path.join(self.cfg.ckpt_path,
                              f"divergence_report_E{epoch}.txt")
            with open(rp, "w") as f:
                f.write(report + "\n")
            report += f"\n  report written to {rp}"
        except OSError:
            pass
        pm = ""
        if self.postmortem_dir:
            # exit 76 leaves the same diagnostic (plus stacks + metrics) in
            # the post-mortem dir, next to the watchdog's exit-77 dumps —
            # one place a requeue wrapper can point triage at ("" = write
            # failed; no breadcrumb to a file that does not exist)
            pm = obs_mod.write_postmortem(
                self.postmortem_dir, f"divergence_E{epoch}", text=report,
                registry=self.obs.registry if self.obs else None)
            if pm:
                report += f"\n  post-mortem dump: {pm}"
        # emitted regardless of the dump outcome: a failed post-mortem
        # write must not cost the lifecycle event (_emit no-ops without obs)
        self._emit("divergence_abort", epoch=int(epoch),
                   loss=float(loss_f), retries=self.retries - 1,
                   dump=pm or None)
        return report

    # -- multi-host agreed verdicts (coord != None) --

    def agree_step(self, epoch: int, state: str, loss_f: float = 0.0,
                   summary: Optional[dict] = None,
                   final: bool = False) -> dict:
        """One step-boundary verdict exchange: contribute this rank's local
        state ('ok' | 'diverged' | 'preempted'), return the agreed decision
        every rank acts on. Rank 0 owns the reduce and — for 'rollback' —
        the checkpoint selection, restart epoch, retry nonce and backoff;
        non-0 ranks record the rollback from the decision so their
        RunResult.rollbacks and nonce stay rank-consistent.

        `summary` (obs on only) piggybacks this rank's epoch telemetry
        (loss, step ms) on the verdict value the exchange already carries;
        rank 0 merges every rank's summary into ONE `epoch_ranks` event —
        cross-rank per-epoch accounting with zero extra collectives.

        `final` marks the run's last step boundary: the coordinator's agree
        cadence ($BNSGCN_COORD_AGREE_EVERY) always exchanges there, so a
        latched verdict can never die with the run.

        Elastic mode additionally resolves an imputed 'lost' peer into a
        RESIZE decision (plan_resize), and — at a clean boundary — answers
        a pending rejoin request with a grow RESIZE (plan_grow)."""
        decide = None
        if self.coord.rank == 0:
            def decide(name, states):
                if name == "resize":
                    return self.plan_resize(epoch, states, loss_f)
                if name == "rollback":
                    return self.plan_rollback(epoch, loss_f, states)
                if name == "preempt":
                    who = [r for r, s in states.items() if s == "preempted"]
                    return {"decision": "preempt", "ranks": who}
                if name == "abort":
                    return {"decision": "abort", "why": "peer",
                            "report": f"a rank reported abort: {states}"}
                if self.elastic:
                    # a clean boundary is the only admission point: the
                    # joiner steps into the NEXT collective, so the member
                    # set must change exactly here, through the same
                    # agree/confirm machinery every other verdict uses
                    for r, tok in self.coord.poll_rejoin():
                        return self.plan_grow(epoch, r, tok)
                return {"decision": "ok"}
        decision = self.coord.agree(epoch, state, decide, info=summary,
                                    final=final)
        if (self.obs is not None and self.coord.rank == 0
                and not decision.get("deferred")
                and self.coord.last_infos):
            self.obs.emit("epoch_ranks", epoch=int(epoch),
                          decision=decision.get("decision", "ok"),
                          ranks={str(r): i for r, i in
                                 sorted(self.coord.last_infos.items())})
        if (decision.get("decision", "ok") != "ok"
                and not decision.get("deferred")):
            self._emit("coord_decision", epoch=int(epoch),
                       decision=decision["decision"], local_state=state)
        if decision["decision"] == "resize":
            if self.coord.rank in [int(r) for r in decision.get("lost", [])]:
                raise CoordAbort(
                    f"rank {self.coord.rank} was declared lost by the "
                    f"resize verdict while still alive — its alive-beats "
                    f"stalled past {self.coord.dead_after_s:.1f}s (raise "
                    f"$BNSGCN_ELASTIC_DEAD_S if the host is just slow)")
            self.resize_nonce = int(decision.get("nonce", self.resize_nonce))
            if self.coord.rank != 0:
                self.log(
                    f"[resilience] agreed resize (decided by rank 0): world "
                    f"{decision['old_world']} -> {decision['world']} "
                    f"({decision['trigger']}), restart "
                    f"{decision['restart']} from {decision['source']}, "
                    f"resize-nonce {self.resize_nonce}")
            self._emit("resize", epoch=int(decision["epoch"]),
                       old_world=int(decision["old_world"]),
                       world=int(decision["world"]),
                       members=[int(r) for r in decision["members"]],
                       lost=[int(r) for r in decision.get("lost", [])],
                       slots=[int(s) for s in decision.get("slots", [])],
                       trigger=str(decision["trigger"]),
                       nonce=int(decision.get("nonce", 0)),
                       restart=int(decision["restart"]),
                       source=str(decision["source"]))
        if decision["decision"] == "rollback" and self.coord.rank != 0:
            self.nonce = int(decision["nonce"])
            self.rollbacks.append({
                "epoch": int(decision["epoch"]),
                "restart": int(decision["restart"]),
                "source": decision["source"], "nonce": self.nonce})
            self._emit("rollback", epoch=int(decision["epoch"]),
                       restart=int(decision["restart"]),
                       source=decision["source"], nonce=int(self.nonce),
                       agreed=True)
            self.log(
                f"[resilience] agreed rollback (decided by rank 0): epoch "
                f"{decision['epoch']} -> restart {decision['restart']} from "
                f"{decision['source']}, retry-nonce {self.nonce}")
        return decision

    def plan_rollback(self, epoch: int, loss_f: float,
                      states: Optional[dict] = None) -> dict:
        """Rank 0's half of a coordinated rollback: pick the newest valid
        checkpoint (or the initial snapshot), advance the retry/nonce
        accounting, and return the decision payload every rank restores
        with. Retry exhaustion returns an 'abort' decision carrying the
        diagnostic report instead — all ranks then raise DivergenceError,
        so the whole job exits 76 consistently. The backoff is NOT slept
        here (the decision must publish before peers' exchange deadline);
        each rank sleeps `backoff_s` locally before restoring.

        TWIN of the single-host rollback() — same retry/selection/nonce/
        backoff policy, split at the publish point; keep them in lockstep
        (see rollback's docstring for why they are not one function)."""
        self.retries += 1
        limit = max(int(self.cfg.resil_retries), 0)
        found = self._find_ckpt(self.cfg, log=self.log, before_epoch=epoch)
        if self.retries > limit:
            return {"decision": "abort", "why": "divergence",
                    "report": self._report(epoch, loss_f, found)}
        if found is not None:
            path, self._pending_payload = found
            restart = int(self._pending_payload["epoch"]) + 1
            src = os.path.basename(path)
        else:
            if self._snapshot is None:
                return {"decision": "abort", "why": "divergence",
                        "report": self._report(epoch, loss_f, None)}
            self._pending_payload = None
            restart = self.start_epoch
            src = "<initial state>"
        self.nonce += 1
        self.rollbacks.append({"epoch": epoch, "restart": restart,
                               "source": src, "nonce": self.nonce})
        self._emit("rollback", epoch=int(epoch), restart=int(restart),
                   source=src, nonce=int(self.nonce), loss=float(loss_f),
                   retry=self.retries, limit=limit, agreed=True)
        diverged = sorted(r for r, s in (states or {}).items()
                          if s == "diverged")
        self.log(
            f"[resilience] non-finite training state at epoch {epoch} on "
            f"rank(s) {diverged or [self.rank]} (loss={loss_f}): agreed "
            f"rollback to {src}, restarting all ranks at epoch {restart} "
            f"with retry-nonce {self.nonce} (retry {self.retries}/{limit})")
        return {"decision": "rollback", "epoch": int(epoch),
                "restart": int(restart), "nonce": int(self.nonce),
                "source": src, "retry": self.retries, "limit": limit,
                "backoff_s": min(self.backoff_cap,
                                 self.backoff_base * (2 ** (self.retries - 1)))}

    def _pick_restore(self, epoch: int) -> tuple[int, str]:
        """Newest valid checkpoint strictly before `epoch`'s boundary (or
        the initial snapshot): the restore target a RESIZE carries. Sets
        `_pending_payload` exactly like plan_rollback so rank 0's
        coord_restore never re-reads the file it just validated."""
        found = self._find_ckpt(self.cfg, log=self.log, before_epoch=epoch)
        if found is not None:
            path, self._pending_payload = found
            return int(self._pending_payload["epoch"]) + 1, \
                os.path.basename(path)
        self._pending_payload = None
        return self.start_epoch, "<initial state>"

    def plan_resize(self, epoch: int, states: dict,
                    loss_f: float = 0.0) -> dict:
        """Rank 0's shrink verdict: peers imputed 'lost' are dropped from
        the member set, every survivor restores the newest valid checkpoint
        (or the initial snapshot) and refolds its key streams under a fresh
        resize nonce, and the P parts are re-mapped onto the survivor slots
        (contiguous balanced blocks — no METIS rerun). Falls back to an
        agreed abort when the survivors cannot cover the minimum world or
        the resize budget is exhausted — a flapping pod must fail loudly,
        not thrash forever."""
        from bnsgcn_tpu.parallel.mesh import plan_slots
        lost = sorted(int(r) for r, s in states.items() if s == "lost")
        survivors = [r for r in self.coord.members if r not in lost]
        self.resizes += 1
        if self.resizes > self.max_resizes:
            return {"decision": "abort", "why": "peer",
                    "report": f"resize budget exhausted "
                              f"({self.max_resizes} per run, "
                              f"$BNSGCN_ELASTIC_MAX_RESIZES): rank(s) "
                              f"{lost} lost at epoch {epoch}"}
        if len(survivors) < max(self.coord.min_world, 1):
            return {"decision": "abort", "why": "peer",
                    "report": f"rank(s) {lost} lost at epoch {epoch} but "
                              f"only {len(survivors)} survivor(s) remain "
                              f"(--elastic-min-world "
                              f"{self.coord.min_world})"}
        restart, src = self._pick_restore(epoch)
        self.resize_nonce += 1
        n_parts = int(getattr(self.cfg, "n_partitions", len(survivors)))
        slots = [survivors[s] for s in plan_slots(n_parts, len(survivors))]
        self.log(
            f"[resilience] rank(s) {lost} lost at epoch {epoch}: agreed "
            f"resize, world {len(self.coord.members)} -> {len(survivors)} "
            f"(survivors {survivors}), all survivors restart at epoch "
            f"{restart} from {src} with resize-nonce {self.resize_nonce} "
            f"folded into the sampling/dropout keys")
        return {"decision": "resize", "trigger": "ranklost",
                "epoch": int(epoch),
                "old_world": len(self.coord.members),
                "world": len(survivors), "members": survivors,
                "lost": lost, "slots": slots, "restart": int(restart),
                "source": src, "retry_nonce": int(self.nonce),
                "nonce": int(self.resize_nonce), "backoff_s": 0.0}

    def plan_grow(self, epoch: int, rank: int, token: str) -> dict:
        """Rank 0's grow verdict: admit `rank`'s replacement back into the
        member set. Every member (the joiner included — its grant names the
        same source) restores the newest valid checkpoint and replays from
        it; the folds are untouched (NO new resize nonce), so the replay
        deterministically lands back on the survivors' own trajectory and
        the final loss is independent of when the rejoin happened. The
        grant additionally carries the seq / agree-call position so the
        joiner's next collective is already in lockstep."""
        from bnsgcn_tpu.parallel.mesh import plan_slots
        members = sorted(set(self.coord.members) | {int(rank)})
        restart, src = self._pick_restore(epoch)
        n_parts = int(getattr(self.cfg, "n_partitions", len(members)))
        slots = [members[s] for s in plan_slots(n_parts, len(members))]
        decision = {"decision": "resize", "trigger": "rejoin",
                    "epoch": int(epoch),
                    "old_world": len(self.coord.members),
                    "world": len(members), "members": members,
                    "lost": [], "joined": [int(rank)], "slots": slots,
                    "restart": int(restart), "source": src,
                    "retry_nonce": int(self.nonce),
                    "nonce": int(self.resize_nonce), "backoff_s": 0.0}
        grant = dict(decision)
        # the joiner's schedule position: agree() already advanced both
        # counters for THIS exchange, so the values here are exactly where
        # every survivor will stand when it acts on the decision
        grant["seq"] = self.coord._seq
        grant["agree_calls"] = self.coord._agree_calls
        self.coord.grant_rejoin(int(rank), token, grant)
        self.log(
            f"[resilience] rank {rank} rejoined at epoch {epoch}: agreed "
            f"resize, world {len(self.coord.members)} -> {len(members)}, "
            f"all members restart at epoch {restart} from {src} (folds "
            f"unchanged — the replay rejoins the same trajectory)")
        return decision

    def coord_restore(self, decision: dict, params_t, opt_t, state_t,
                      restore_local: bool = True,
                      ack_name: str = "rollback"):
        """Every rank's half of a coordinated rollback: sleep the agreed
        backoff, restore the decision's source from the local checkpoint
        dir (rank 0 reuses the payload plan_rollback already validated; the
        initial-snapshot source restores each rank's own host snapshot —
        replicated params, so identical), then ack. A rank whose restore
        fails fails the ack and EVERY rank raises CoordAbort: a loud agreed
        abort, never a silent epoch desync. `restore_local=False` (the
        real-multi-host peers, whose state arrives via the rank-0 XLA
        broadcast) skips the local load but STILL joins the ack — a rank-0
        restore failure must surface as the agreed exit 78 on all ranks
        BEFORE anyone blocks inside the XLA collective, not as rank 0
        aborting alone while its peers hang to the watchdog (77)."""
        backoff = float(decision.get("backoff_s", 0.0))
        if backoff > 0:
            self.log(f"[resilience] backing off {backoff:.1f}s before "
                     f"agreed retry {decision.get('retry')}"
                     f"/{decision.get('limit')}")
            self._sleep(backoff)
        src = decision["source"]
        ok, err, out = True, "", (params_t, opt_t, state_t)
        if restore_local:
            try:
                if src == "<initial state>":
                    if self._snapshot is None:
                        raise CheckpointUnavailable("no initial snapshot")
                    out = self._snapshot
                else:
                    payload = self._pending_payload
                    if payload is None:
                        payload = self._load_ckpt(
                            os.path.join(self.cfg.ckpt_path, src))
                    out = self._restore_into(payload, params_t, opt_t,
                                             state_t)
            except (ckpt.CheckpointCorrupt, CheckpointUnavailable,
                    OSError) as ex:
                ok, err = False, f"{type(ex).__name__}: {ex}"
                self.log(f"[resilience] rank {self.rank} cannot restore "
                         f"{src}: {err}")
            finally:
                self._pending_payload = None
        all_ok, fails = self.coord.gather_ok(ack_name, ok, err)
        if not all_ok:
            raise CoordAbort(
                f"coordinated {ack_name} failed — rank(s) could not restore "
                f"{src!r}: "
                + "; ".join(f"rank {r}: {d}" for r, d in sorted(fails.items())))
        return out

    @staticmethod
    def raise_abort(decision: dict):
        """Map an agreed 'abort' decision to the exception (and thus exit
        code) it belongs to, identically on every rank."""
        if decision.get("why") == "divergence":
            raise DivergenceError(decision.get("report",
                                               "divergence abort (agreed)"))
        raise CoordAbort(decision.get("report",
                                      "coordinated abort (agreed)"))

    # -- fault injection --

    def fire_injections(self, epoch: int) -> dict:
        """Apply this epoch's scheduled faults at the step boundary.

        Returns {'nan': bool} — NaN poisoning is applied by the caller (it
        owns the device params); the other kinds act here: `sigterm` raises
        the real signal through the installed handler, `hang` blocks the main
        thread so the watchdog path fires for real, and `ckpt-corrupt` tears
        the newest periodic checkpoint to prove the fallback chain."""
        out = {"nan": self.plan.pop("nan", epoch)}
        if self.plan.pop("sigterm", epoch):
            self.log(f"[inject] sigterm@E{epoch}")
            self._emit("inject", kind_injected="sigterm", epoch=int(epoch))
            signal.raise_signal(signal.SIGTERM)
        if self.plan.pop("ckpt-corrupt", epoch):
            latest = ckpt.latest_checkpoint(self.cfg)
            if latest:
                corrupt_file(latest)
                self.log(f"[inject] ckpt-corrupt@E{epoch}: tore {latest}")
            else:
                self.log(f"[inject] ckpt-corrupt@E{epoch}: no checkpoint yet")
        if self.plan.pop("ranklost", epoch):
            self.log(f"[inject] ranklost@E{epoch}: dropping this rank with "
                     f"no coordinator goodbye — peers must detect the "
                     f"heartbeat silence")
            self._emit("inject", kind_injected="ranklost", epoch=int(epoch))
            raise RankLostExit(epoch)
        if self.plan.pop("hang", epoch):
            self.log(f"[inject] hang@E{epoch}: blocking the step (watchdog "
                     f"deadline {self.watchdog.deadline_s():.1f}s)")
            while True:                 # the watchdog ends the process
                time.sleep(3600)
        if out["nan"]:
            self.log(f"[inject] nan@E{epoch}: poisoning params")
            self._emit("inject", kind_injected="nan", epoch=int(epoch))
        return out


def corrupt_file(path: str, keep_bytes: int = 64):
    """Simulate a torn write: truncate to the first `keep_bytes` bytes and
    flip them — the checkpoint keeps its checksum header but fails
    verification, exactly the state a preemption mid-`os.replace`-era write
    (or disk corruption) leaves behind."""
    with open(path, "r+b") as f:
        head = bytearray(f.read(keep_bytes))
        for i in range(len(head)):
            head[i] ^= 0xFF
        f.seek(0)
        f.write(head)
        f.truncate(len(head))
