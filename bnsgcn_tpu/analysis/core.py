"""graftlint framework: file walking, suppressions, findings, JSON report.

The rule families live in sibling ``rules_*`` modules; each exposes
``check(module, ctx) -> list[Finding]`` plus an optional
``collect(module, ctx)`` pre-pass that contributes cross-module context
(the mesh axis vocabulary, the donated-callable registry, the obs event
registry) before any rule runs. Rules see only parsed ASTs + comment
tokens — no imports of the scanned code, so a file with a missing
optional dependency still lints.
"""

from __future__ import annotations

import ast
import io
import json
import os
import re
import tokenize
from dataclasses import dataclass, field

# rule id -> (one-line description, fix hint). The single source the CLI
# table, README table and tests enumerate. Family prefix groups ids.
RULE_DOCS = {
    # -- family 1: SPMD collective discipline --
    "spmd-unbound-axis": (
        "collective names a mesh axis outside the repo's axis vocabulary "
        "(HaloSpec axis fields + make_mesh literals)",
        "use an axis bound by the enclosing shard_map mesh — the "
        "vocabulary is built from parallel/halo.py HaloSpec defaults and "
        "make_mesh axis-name literals"),
    "spmd-rank-branch": (
        "collective under rank-dependent Python control flow "
        "(axis_index/process_index in the branch condition)",
        "hoist the collective out of the branch: a collective only some "
        "ranks enter deadlocks the mesh"),
    # -- family 2: PRNG key discipline --
    "prng-literal-key": (
        "literal PRNGKey/key constant outside tests",
        "derive the key from the run seed via fold_in/split (see "
        "sampling.pair_key); literal keys correlate streams across "
        "call sites"),
    "prng-key-reuse": (
        "same PRNG key consumed by multiple random draws without an "
        "intervening split/fold_in",
        "split the key (k1, k2 = jax.random.split(key)) or fold a "
        "distinct id per draw — reused keys make 'independent' draws "
        "identical"),
    "prng-replica-fold-order": (
        "replica id folded after other stream ids (replica-fold-FIRST "
        "is the sampling.pair_key contract)",
        "fold the replica index before epoch/pair ids so replica r of a "
        "2-D run equals a 1-D run with the folded base key"),
    # -- family 3: host-sync / recompile hazards in jitted scopes --
    "host-sync-item": (
        ".item() inside a jitted scope forces a device sync",
        "keep the value on device; fetch at the epoch boundary with an "
        "explicit jax.device_get outside the jitted scope"),
    "host-sync-cast": (
        "float()/int()/bool() of a non-static value inside a jitted "
        "scope concretizes a tracer",
        "use jnp casts on device, or move the host cast outside the "
        "jitted scope"),
    "host-sync-numpy": (
        "np.asarray/np.array on a traced value inside a jitted scope",
        "use jnp.* on device; host numpy on a tracer is a sync (or a "
        "trace error on the TPU path)"),
    "host-sync-device-get": (
        "jax.device_get/block_until_ready inside a jitted scope",
        "device fetches belong outside jit; inside a traced function "
        "they sync or fail at trace time"),
    "host-sync-traced-branch": (
        "Python if/while on a traced value inside a jitted scope",
        "use jnp.where / lax.cond — a Python branch on a tracer "
        "concretizes it (recompile per value, or trace error)"),
    # -- family 4: donation safety --
    "donate-use-after": (
        "buffer read after being passed through a donated argument",
        "donated buffers are invalidated by the call (donate_argnums); "
        "rebind the variable from the call's result or copy before "
        "donating"),
    # -- family 5: lock discipline --
    "lock-unguarded-access": (
        "field annotated '# guarded-by: <lock>' accessed outside "
        "'with <lock>:'",
        "wrap the access in the annotated lock (or suppress with a "
        "reason if the access is provably pre-thread/single-threaded)"),
    # -- family 6: contract lints --
    "obs-unregistered-event": (
        "emitted obs event kind missing from obs.EVENT_KINDS",
        "add the kind to bnsgcn_tpu/obs.py EVENT_KINDS so "
        "tools/obs_report.py renders it and downstream joins see it"),
    "exit-code-literal": (
        "sys.exit/os._exit with a literal lifecycle exit code "
        "(75/76/77/78)",
        "use the named constants (resilience.EXIT_PREEMPTED/"
        "EXIT_DIVERGED/EXIT_WATCHDOG/EXIT_COORD_ABORT) so the exit-code "
        "contract is greppable"),
    # -- family 7: repo contract checks (analysis/repo_checks.py) --
    "tune-schedule-invalid": (
        "--tune-schedule string literal does not parse under the real "
        "tune.py grammar",
        "fix the schedule spelling (epoch:lever=value, comma-separated; "
        "levers K/mode/strategy/wire) — the run would die at startup with "
        "the same error this lint reports early"),
    "config-doc-drift": (
        "config.py flag vocabulary and the README knob table disagree "
        "(undocumented flag, stale flag, or stale choices)",
        "update the README 'Config knobs' table to match "
        "config.create_parser() — the table is contract, not prose"),
    # -- family 8: jaxpr-level contracts (analysis/ir, `ir` subcommand) --
    "ir-rank-asymmetry": (
        "traced collective schedule is not rank-symmetric "
        "(axis_index_groups, rank-predicated branch, or a retrace "
        "divergence between tune-equivalent states)",
        "make every collective unconditional and sub-group-free inside "
        "shard_map, and keep the schedule a pure function of the lever "
        "state — asymmetric schedules deadlock the mesh at scale"),
    "ir-dead-donation": (
        "donate_argnums buffer has no aliased output in the lowered "
        "module (donation buys nothing, buffer still invalidated)",
        "drop the argument from donate_argnums or return an output with "
        "the same shape/dtype so XLA can alias it"),
    "ir-wire-drift": (
        "payload bytes in the traced exchange differ from the "
        "halo.traced_wire_bytes plan oracle (the run-header/tuner claim)",
        "the compiled exchange and the reported bytes must agree: check "
        "the wire-codec cast points and the spec geometry "
        "(pad_send/shift_pads/pair_send) for the strategy"),
    "ir-hidden-transfer": (
        "device<->host primitive (strict.TRANSFER_PRIMITIVES) inside a "
        "traced step/eval/exchange program",
        "hoist the host interaction outside the jitted program — inside, "
        "it is a per-step sync the CPU transfer guard cannot even see"),
    "ir-trace-error": (
        "a variant-matrix cell failed to trace at all",
        "the build/trace path for this lever combination is broken — "
        "reproduce with `python -m bnsgcn_tpu.analysis ir` and fix the "
        "exception before trusting any run that can retune into it"),
    # -- family 9: lock-order discipline (rules_lockorder.py) --
    "lock-order-cycle": (
        "lock-acquisition graph has a cycle: two locks are taken in "
        "opposite nesting orders (or a non-reentrant lock re-enters "
        "itself) — a potential deadlock between the threaded subsystems",
        "pick ONE global order for the locks involved and restructure the "
        "nested `with` blocks so every code path acquires them in that "
        "order (or copy the needed state out and release first)"),
    "lock-held-blocking-call": (
        "blocking call (thread join, sleep, fsync, socket I/O, "
        "coordinator RPC) inside a `with <lock>:` block",
        "move the blocking call outside the lock: snapshot the guarded "
        "state under the lock, release, then block — a stalled disk or "
        "peer otherwise wedges every thread contending for that lock"),
    # -- family 10: protocol model checking (analysis/proto, `proto`
    #    subcommand). Findings attribute to proto://<scenario>#<hash>
    #    with a replayable schedule trace in the message. --
    "proto-agreement": (
        "two ranks completed the same exchange with different results "
        "(verdict / decision / checkpoint / restart epoch / broadcast "
        "payload) under an explored schedule",
        "the protocol let ranks adopt divergent outcomes for one seq — "
        "replay the schedule trace with `python -m bnsgcn_tpu.analysis "
        "proto --replay <spec>` and fix coord.py's publish/confirm "
        "ordering before trusting any coordinated run"),
    "proto-split-brain": (
        "a rank adopted a stale run's namespace/payload across run "
        "tokens (FileTransport relaunch race)",
        "the .boot token pin/refuse logic regressed: a peer must reject "
        "dead same-host tokens and only pin a token after a successful "
        "get — replay the schedule to reproduce"),
    "proto-reduce-order": (
        "agreed decision contradicts the worst-wins state reduction "
        "(e.g. a diverged rank lost to a preempted one)",
        "STATE_PRIORITY/_DECISION_OF drifted from the documented order "
        "ok < preempted < diverged < abort — a preempt checkpoint "
        "written from NaN state would poison the resume"),
    "proto-retired-live-key": (
        "key retirement deleted a message a lagging rank had not yet "
        "read, inside its legal in-window lag",
        "PRUNE_HORIZON (or _retire's bookkeeping) regressed: a spent "
        "exchange's keys must survive the maximum legal peer lag — "
        "replay the schedule trace to see the put/delete/timeout order"),
    "proto-exit-code": (
        "a terminal path ended in an undocumented way (an exception "
        "outside the CoordTimeout/CoordAbort/DivergenceError/"
        "PreemptedError -> {77,78,76,75} contract, or a disallowed exit "
        "for the scenario's fault)",
        "map the failure onto exactly one documented exit code "
        "(resilience.py EXIT_* constants) — requeue wrappers triage on "
        "these codes"),
    "proto-hang": (
        "a schedule did not terminate within the modeled deadline "
        "budget (silent hang: every wait must be deadline-bounded)",
        "some wait path lacks a deadline (or sleeps past its own): "
        "bound it with Coordinator._deadline so the worst case is a "
        "named CoordTimeout, never a stuck rank"),
    "proto-explore-error": (
        "a proto scenario crashed the explorer itself (harness error, "
        "not a protocol verdict)",
        "reproduce with `python -m bnsgcn_tpu.analysis proto --scenario "
        "<name>` and fix the exception before trusting the audit"),
    # -- family 11: predictive cost model (analysis/perf, `perf`
    #    subcommand). Findings attribute to perf://<record|variant|probe>. --
    "perf-calibration-invalid": (
        "the perf calibration table fails schema/physics validation "
        "(missing backend constants, non-positive rates, records "
        "referencing unknown backends or feature fields)",
        "fix tools/perf_calibration.json by hand or regenerate the "
        "backend table with `python tools/microbench.py "
        "--emit-calibration out.json` on the target backend"),
    "perf-model-drift": (
        "cost-model prediction off a recorded measurement beyond the "
        "drift band — the model no longer explains the repo's own "
        "perf history",
        "recalibrate the backend table (microbench --emit-calibration, "
        "or model.fit_scale over fresh obs epochs) or fix the record's "
        "layout features; never widen the band to make it pass"),
    "perf-model-nonmonotone": (
        "the cost model violated a physical ordering (more wire or less "
        "dense coverage predicted faster, gather sped up with row "
        "bytes, coarser refresh shipped more steady bytes, or a lever "
        "state priced non-finite)",
        "the roofline terms in analysis/perf/model.py regressed — a "
        "model that can rank backwards will mistune --tune-prior and "
        "misrank the watch queue; fix the term, don't gate it off"),
    "perf-obs-drift": (
        "an obs epoch record's wire_mb matches no figure its "
        "run_header/tune_decision events declared",
        "run.py's per-epoch wire accounting and its header/tune "
        "declarations diverged — align epoch_wire_mb with "
        "halo.wire_bytes over the live spec before trusting the "
        "K-vs-bytes history"),
    "perf-audit-error": (
        "a perf-audit cell failed to evaluate at all (harness error, "
        "not a model verdict)",
        "reproduce with `python -m bnsgcn_tpu.analysis perf` and fix "
        "the exception before trusting the gate"),
    # -- framework --
    "suppression-stale": (
        "graftlint: disable= comment whose line no longer triggers any "
        "of its suppressed rules",
        "delete the stale suppression — it would silently swallow a "
        "future regression at that line"),
    "suppression-missing-reason": (
        "graftlint: disable= without a (reason)",
        "every suppression must say why: "
        "# graftlint: disable=rule-id(the reason)"),
    "suppression-unknown-rule": (
        "graftlint: disable= names an unknown rule id",
        "use a rule id from --list-rules"),
}


@dataclass
class Finding:
    file: str               # path relative to the lint root
    line: int
    col: int
    rule: str
    message: str
    suppressed: bool = False
    reason: str = ""        # the suppression reason, when suppressed

    @property
    def hint(self) -> str:
        return RULE_DOCS.get(self.rule, ("", ""))[1]

    def fmt(self) -> str:
        return f"{self.file}:{self.line}:{self.col}: [{self.rule}] {self.message}"

    def as_dict(self) -> dict:
        d = {"file": self.file, "line": self.line, "col": self.col,
             "rule": self.rule, "message": self.message, "hint": self.hint}
        if self.suppressed:
            d["suppressed"] = True
            d["reason"] = self.reason
        return d


# Matches the inline marker (hash, 'graftlint:', 'disable=', then a
# comma list of rule-id(reason) items). Spelled via concatenation so
# this file's own comments never match the marker.
_SUPPRESS_RE = re.compile(r"#\s*graft" r"lint:\s*disable=(.*)$")
_ITEM_RE = re.compile(r"\s*([\w-]+)\s*(?:\(([^)]*)\))?\s*(?:,|$)")


@dataclass
class Suppression:
    line: int               # line the comment is on
    rule: str
    reason: str
    standalone: bool        # comment-only line: also covers the next line
    used: bool = False


@dataclass
class Module:
    """One parsed source file plus its comment-derived suppressions."""
    path: str
    relpath: str
    tree: ast.AST
    source: str
    suppressions: list = field(default_factory=list)
    is_test: bool = False

    def covered(self, line: int, rule: str):
        """The suppression covering (line, rule), if any. A suppression
        covers its own line; a standalone comment also covers the line
        below it (put it directly above the flagged statement)."""
        for s in self.suppressions:
            if s.rule != rule:
                continue
            if s.line == line or (s.standalone and s.line + 1 == line):
                return s
        return None


@dataclass
class Context:
    """Cross-module facts collected in the pre-pass, read by every rule."""
    axis_vocab: set = field(default_factory=set)      # mesh axis names
    donated: dict = field(default_factory=dict)       # fn name -> (positions)
    event_kinds: set = field(default_factory=set)     # obs.EVENT_KINDS
    have_event_registry: bool = False
    lock_edges: list = field(default_factory=list)    # cross-module lock-
                        # acquisition graph: (held, acquired, relpath, line)
    lock_kinds: dict = field(default_factory=dict)    # lock name -> Lock/
                        # RLock/Condition (from threading.* assignments)


def parse_module(path: str, root: str) -> Module | None:
    """Parse one file into a Module; None on a syntax error (reported by
    the caller as a lint run error, not a crash)."""
    with open(path, "rb") as f:
        raw = f.read()
    try:
        source = raw.decode("utf-8")
        tree = ast.parse(source, filename=path)
    except (SyntaxError, UnicodeDecodeError):
        return None
    rel = os.path.relpath(path, root)
    mod = Module(path=path, relpath=rel, tree=tree, source=source,
                 is_test=("tests" + os.sep) in rel or
                         os.path.basename(rel).startswith("test_"))
    _collect_suppressions(mod, raw)
    return mod


def _collect_suppressions(mod: Module, raw: bytes):
    try:
        toks = list(tokenize.tokenize(io.BytesIO(raw).readline))
    except tokenize.TokenError:
        return
    lines = mod.source.splitlines()
    for tok in toks:
        if tok.type != tokenize.COMMENT:
            continue
        m = _SUPPRESS_RE.search(tok.string)
        if not m:
            continue
        line = tok.start[0]
        before = lines[line - 1][:tok.start[1]] if line <= len(lines) else ""
        standalone = not before.strip()
        for item in _ITEM_RE.finditer(m.group(1)):
            rule, reason = item.group(1), (item.group(2) or "").strip()
            if not rule:
                continue
            mod.suppressions.append(Suppression(
                line=line, rule=rule, reason=reason, standalone=standalone))


def _suppression_findings(mod: Module) -> list[Finding]:
    out = []
    for s in mod.suppressions:
        if s.rule not in RULE_DOCS:
            out.append(Finding(mod.relpath, s.line, 0,
                               "suppression-unknown-rule",
                               f"disable= names unknown rule {s.rule!r}"))
        elif not s.reason:
            out.append(Finding(mod.relpath, s.line, 0,
                               "suppression-missing-reason",
                               f"disable={s.rule} has no (reason) — "
                               f"suppressions must say why"))
    return out


# Directories never scanned (vendored/related/caches), relative names.
_SKIP_DIRS = {".git", "__pycache__", ".pytest_cache", "node_modules",
              ".claude"}

# The repo's default lint surface: the package, the tools, and the
# top-level entry points. Tests are deliberately excluded (they use
# literal keys and host syncs by design); fixtures under tests/ are
# linted explicitly by tests/test_analysis.py.
DEFAULT_TARGETS = ("bnsgcn_tpu", "tools", "bench.py", "chip_smoke.py",
                   "__graft_entry__.py")


def iter_py_files(paths: list[str], root: str) -> list[str]:
    out = []
    for p in paths:
        ap = p if os.path.isabs(p) else os.path.join(root, p)
        if os.path.isfile(ap) and ap.endswith(".py"):
            out.append(ap)
        elif os.path.isdir(ap):
            for dirpath, dirnames, filenames in os.walk(ap):
                dirnames[:] = sorted(d for d in dirnames
                                     if d not in _SKIP_DIRS)
                for fn in sorted(filenames):
                    if fn.endswith(".py"):
                        out.append(os.path.join(dirpath, fn))
    return sorted(dict.fromkeys(out))


def _rule_modules():
    from bnsgcn_tpu.analysis import (rules_contract, rules_donation,
                                     rules_hostsync, rules_lockorder,
                                     rules_locks, rules_prng, rules_spmd)
    return [rules_spmd, rules_prng, rules_hostsync, rules_donation,
            rules_locks, rules_lockorder, rules_contract]


def resolve_root(root: str | None = None) -> str:
    """The repo root: explicit, or three levels up from this file."""
    if root is not None:
        return os.path.abspath(root)
    return os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))


def resolve_paths(paths: list[str] | None, root: str) -> list[str]:
    if paths:
        return list(paths)
    return [p for p in DEFAULT_TARGETS
            if os.path.exists(os.path.join(root, p))]


def lint_paths(paths: list[str] | None = None, root: str | None = None,
               select: set | None = None
               ) -> tuple[list[Finding], list[Finding], list[str]]:
    """Lint `paths` (files/dirs, default DEFAULT_TARGETS under `root`).

    Returns (active_findings, suppressed_findings, errors):
    active findings are what gate CI; suppressed ones carry their reason
    into the JSON report so intentional hazards stay auditable; errors
    are unparseable files (relative paths).
    """
    root = resolve_root(root)
    paths = resolve_paths(paths, root)
    files = iter_py_files(list(paths), root)
    modules, errors = [], []
    for fp in files:
        mod = parse_module(fp, root)
        if mod is None:
            errors.append(os.path.relpath(fp, root))
        else:
            modules.append(mod)

    ctx = Context()
    rule_mods = _rule_modules()
    for rm in rule_mods:
        collect = getattr(rm, "collect", None)
        if collect is not None:
            for mod in modules:
                collect(mod, ctx)

    raw: list[Finding] = []
    for rm in rule_mods:
        for mod in modules:
            raw.extend(rm.check(mod, ctx))
    for mod in modules:
        raw.extend(_suppression_findings(mod))

    # repo-level contract checks (non-Python surfaces: shell scripts, the
    # watch queue, the README knob table) ride the default full-surface
    # run — linting an explicit file subset stays file-scoped
    if sorted(paths) == sorted(resolve_paths(None, root)):
        from bnsgcn_tpu.analysis import repo_checks
        raw.extend(repo_checks.check_repo(root))

    if select:
        raw = [f for f in raw
               if f.rule in select or f.rule.startswith("suppression-")]

    active, suppressed = [], []
    by_path = {m.relpath: m for m in modules}
    for f in sorted(raw, key=lambda f: (f.file, f.line, f.col, f.rule)):
        mod = by_path.get(f.file)
        sup = mod.covered(f.line, f.rule) if mod is not None else None
        if sup is not None and sup.reason:
            sup.used = True
            f.suppressed, f.reason = True, sup.reason
            suppressed.append(f)
        else:
            active.append(f)

    # staleness audit: a suppression comment whose line no longer
    # triggers ANY of its listed rules is itself a finding — left
    # behind, it would silently swallow the NEXT regression at that
    # line. Line-level, not per-rule: a multi-rule list where one rule
    # still fires is load-bearing and stays. Only meaningful on
    # unfiltered runs (under --select, unselected rules never get the
    # chance to mark their suppressions used). Reasonless suppressions
    # are already flagged suppression-missing-reason and skipped here.
    if select is None:
        for mod in modules:
            used_lines = {s.line for s in mod.suppressions if s.used}
            for s in mod.suppressions:
                if (s.line in used_lines or not s.reason
                        or s.rule not in RULE_DOCS):
                    continue
                active.append(Finding(
                    mod.relpath, s.line, 0, "suppression-stale",
                    f"disable={s.rule} no longer matches a finding on its "
                    f"line (reason was: {s.reason!r}) — delete it"))
        active.sort(key=lambda f: (f.file, f.line, f.col, f.rule))
    return active, suppressed, errors


def report_json(active: list[Finding], suppressed: list[Finding],
                errors: list[str], root: str, n_files: int) -> dict:
    counts: dict[str, int] = {}
    for f in active:
        counts[f.rule] = counts.get(f.rule, 0) + 1
    return {
        "graftlint": 1,
        "root": root,
        "files_scanned": n_files,
        "ok": not active and not errors,
        "findings": [f.as_dict() for f in active],
        "suppressed": [f.as_dict() for f in suppressed],
        "counts": counts,
        "errors": errors,
    }


def write_report(report: dict, path: str):
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(report, f, indent=1)
        f.write("\n")
    os.replace(tmp, path)
