"""Strict-execution runtime guards: prove the steady-state step is clean.

graftlint (bnsgcn_tpu/analysis/) proves host-sync and recompile hazards
absent from the SOURCE; `--strict-exec` proves them absent from the RUN.
Two mechanisms wrap the hot-loop step region in run.py:

* **Transfer guard** — `jax.transfer_guard("disallow")` around the step
  makes any implicit host<->device transfer inside the guarded region an
  error instead of a silent sync. The per-epoch `jnp.uint32(epoch)`
  upload is hoisted OUTSIDE the guard by run.py (one deliberate scalar
  H2D per epoch); the loss fetch goes through the audited
  `StrictExec.fetch` (an explicit, counted `jax.device_get`). Everything
  else that would transfer inside the step is a bug this mode turns
  fatal. (On the CPU backend device<->host is zero-copy and the guard
  cannot observe D2H at all — the H2D side and the compile listener
  still make the CPU quickgate a real test; on TPU the guard sees both
  directions.)

* **Compile listener** — `jax.monitoring` delivers a
  `.../backend_compile_duration` span on every XLA compilation (or
  persistent-cache load), including recompiles, and nothing on calls the
  in-memory cache serves. Each
  step VARIANT (`full`/`cached`/`step` — the `--halo-refresh` pair is
  two distinct programs) is allowed to compile during its first guarded
  step; a compile in any later step of an armed variant is a
  steady-state recompile (donation-shape drift, a host value leaking
  into the trace) and raises StrictExecError. Each StrictExec subscribes
  to the process's one registration, which obs owns
  (`obs.subscribe_compiles`), directly and not through an Obs: under
  `--obs off` there is none.

`finish()` logs a one-line audit summary and lands a `strict_exec` event
on the telemetry bus (obs.EVENT_KINDS), so a pod run's log carries the
proof: zero violations, zero steady-state recompiles, N audited fetches.
"""

from __future__ import annotations

import contextlib
from typing import Optional

import jax

from bnsgcn_tpu import obs as obs_mod

__all__ = ["StrictExec", "StrictExecError", "TRANSFER_PRIMITIVES"]

# jaxpr primitives that move data across the device<->host boundary (or
# re-place it) from INSIDE a traced program — the static face of the same
# contract the transfer guard enforces at runtime. analysis/ir scans every
# traced step/eval/exchange program for these: a hit is a hidden transfer
# the runtime guard would only catch on hardware (CPU cannot observe D2H),
# so the static audit is the proof that needs no pod window. `device_put`
# inside a traced scope re-commits placement mid-program (a sync or a
# cross-mesh copy); the callback family round-trips through the host by
# definition; infeed/outfeed are the raw host-transfer channels.
TRANSFER_PRIMITIVES = frozenset({
    "device_put", "infeed", "outfeed",
    "pure_callback", "io_callback", "debug_callback", "callback",
})


class StrictExecError(RuntimeError):
    """A strict-execution invariant failed: an implicit transfer inside
    the guarded step region, or a recompile after the variant's first
    step. The message names the variant and the fix direction."""


class StrictExec:
    """Per-run strict-execution auditor. run.py creates one when
    `--strict-exec` is set and wraps every hot-loop step in `step()`."""

    def __init__(self, obs=None, log=print):
        self.obs = obs
        self.log = log
        self._armed: set[str] = set()       # variants past their first step
        self._in_step: Optional[str] = None
        self._step_compiles = 0
        self.steps: dict[str, int] = {}
        self.first_compiles: dict[str, int] = {}
        self.fetches = 0
        self.violations = 0
        self.rearms = 0
        obs_mod.subscribe_compiles(self._saw_compile)

    # listener path (same thread: XLA compiles synchronously under trace)
    def _saw_compile(self, kind: str, start: float, end: float,
                     fun_name: str):
        if kind == "compile" and self._in_step is not None:
            self._step_compiles += 1

    @contextlib.contextmanager
    def step(self, variant: str):
        """Guard one hot-loop step of the named program variant."""
        self._in_step = variant
        self._step_compiles = 0
        try:
            with jax.transfer_guard("disallow"):
                yield
        except Exception as ex:
            if "transfer" in str(ex).lower():
                self.violations += 1
                raise StrictExecError(
                    f"implicit host transfer inside the guarded "
                    f"'{variant}' step: {ex}\nEvery host value the step "
                    f"consumes must be uploaded before the guard (the "
                    f"jnp.uint32(epoch) pattern) and every result fetched "
                    f"through strict.fetch() after it.") from ex
            raise
        finally:
            self._in_step = None
        n = self._step_compiles
        self.steps[variant] = self.steps.get(variant, 0) + 1
        if variant in self._armed:
            if n:
                self.violations += 1
                raise StrictExecError(
                    f"{n} steady-state recompile(s) in step variant "
                    f"'{variant}' (step {self.steps[variant]}): a shape, "
                    f"dtype or Python-hashable argument changed after the "
                    f"first epoch — hoist it to a device value or a stable "
                    f"static arg.")
        else:
            self.first_compiles[variant] = \
                self.first_compiles.get(variant, 0) + n
            self._armed.add(variant)

    def rearm(self, reason: str = "retune"):
        """Re-arm every variant's first-compile allowance: the `--tune`
        controller rebuilt the step fns (new compiled programs), so their
        next guarded step legitimately compiles ONCE more. Counted in the
        audit — a clean tuned run shows exactly `rearms` sanctioned
        recompile rounds and still zero violations."""
        self.rearms += 1
        self._armed.clear()
        self.log(f"[strict] compile allowance re-armed ({reason}): the next "
                 f"step of each variant may compile once")

    def fetch(self, x):
        """Audited explicit device->host fetch (the loss read). Explicit
        transfers pass the guard by design; counting them keeps the
        summary honest about how much the loop pulls per epoch."""
        self.fetches += 1
        return jax.device_get(x)

    def summary(self) -> dict:
        return {
            "variants": sorted(self.steps),
            "steps": dict(self.steps),
            "first_compiles": dict(self.first_compiles),
            "fetches": self.fetches,
            "violations": self.violations,
            "rearms": self.rearms,
        }

    def finish(self):
        obs_mod.unsubscribe_compiles(self._saw_compile)
        s = self.summary()
        total_steps = sum(s["steps"].values())
        self.log(
            f"[strict] exec audit: {total_steps} guarded steps across "
            f"{len(s['variants'])} variant(s) {s['variants']}, "
            f"first-step compiles {s['first_compiles']}, "
            f"{s['fetches']} audited fetches, "
            f"{s['rearms']} retune re-arm(s), "
            f"{s['violations']} violation(s)")
        if self.obs is not None:
            self.obs.emit("strict_exec", **s)
        return s
