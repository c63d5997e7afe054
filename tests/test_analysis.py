"""graftlint (bnsgcn_tpu/analysis/) + --strict-exec runtime guards.

Fixture matrix: every rule family gets seeded-violation fixtures (the
rule MUST fire) and clean fixtures (it MUST NOT), written into tmp dirs
and linted with --root pointed there so each fixture set is
self-contained — the axis vocabulary, donation registry and event
registry are collected from the fixture files themselves.

Framework coverage: suppression grammar (reasoned suppressions move
findings to the suppressed list, reasonless ones are themselves
findings, unknown rule ids are flagged), the JSON report schema, CLI
exit codes, `tools/lint.sh` clean at HEAD (the repo lints itself), and
the `--strict-exec` end-to-end proof: a CLI training run under the
transfer guard + compile listener finishes with zero violations and
lands the audit on the telemetry bus.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from bnsgcn_tpu.analysis import RULE_DOCS, lint_paths, report_json
from bnsgcn_tpu.analysis.core import iter_py_files, resolve_root

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# mesh-vocabulary preamble shared by SPMD fixtures: collect() reads the
# axis names out of this make_mesh literal
MESH_PREAMBLE = """\
import jax
from jax import lax
mesh = make_mesh((2,), ('parts',))
"""


def lint_dir(tmp_path, files, select=None):
    """Write {name: source} fixtures and lint the dir as its own root."""
    tmp_path.mkdir(parents=True, exist_ok=True)
    for name, src in files.items():
        (tmp_path / name).write_text(textwrap.dedent(src))
    return lint_paths([str(tmp_path)], root=str(tmp_path), select=select)


def rules(findings):
    return sorted(f.rule for f in findings)


# ----------------------------------------------------------------------------
# family 1: SPMD collective discipline
# ----------------------------------------------------------------------------

def test_spmd_unbound_axis_fires(tmp_path):
    active, _, _ = lint_dir(tmp_path, {"fix_a.py": MESH_PREAMBLE + """\
def f(x):
    return lax.psum(x, 'bogus_axis')
"""})
    assert rules(active) == ["spmd-unbound-axis"]
    assert "bogus_axis" in active[0].message


def test_spmd_unbound_axis_tuple_and_kw(tmp_path):
    active, _, _ = lint_dir(tmp_path, {"fix_a.py": MESH_PREAMBLE + """\
def f(x):
    a = lax.all_gather(x, axis_name=('parts', 'nope'))
    b = lax.axis_index('also_nope')
    return a, b
"""})
    assert rules(active) == ["spmd-unbound-axis", "spmd-unbound-axis"]


def test_spmd_rank_branch_fires(tmp_path):
    active, _, _ = lint_dir(tmp_path, {"fix_a.py": MESH_PREAMBLE + """\
def f(x):
    r = lax.axis_index('parts')
    if r == 0:
        x = lax.psum(x, 'parts')
    return x
"""})
    assert "spmd-rank-branch" in rules(active)


def test_spmd_clean_and_inactive_without_vocab(tmp_path):
    # bound axis + collective outside any rank branch: clean
    active, _, _ = lint_dir(tmp_path, {"fix_a.py": MESH_PREAMBLE + """\
def f(x):
    return lax.psum(x, 'parts')
"""})
    assert active == []
    # no mesh constructor in the target set -> empty vocabulary -> the
    # axis rule stays silent rather than flagging every axis it can't see
    active, _, _ = lint_dir(tmp_path / "sub",
                            {"fix_b.py": """\
from jax import lax
def f(x):
    return lax.psum(x, 'unknowable')
"""})
    assert active == []


# ----------------------------------------------------------------------------
# family 2: PRNG key discipline
# ----------------------------------------------------------------------------

def test_prng_literal_key_fires(tmp_path):
    active, _, _ = lint_dir(tmp_path, {"fix_k.py": """\
import jax
k1 = jax.random.PRNGKey(0)
k2 = jax.random.key(42)
"""})
    assert rules(active) == ["prng-literal-key", "prng-literal-key"]


def test_prng_literal_key_exempt_in_tests(tmp_path):
    active, _, _ = lint_dir(tmp_path, {"test_fix.py": """\
import jax
k = jax.random.PRNGKey(0)
"""})
    assert active == []


def test_prng_key_reuse_fires(tmp_path):
    active, _, _ = lint_dir(tmp_path, {"fix_k.py": """\
import jax
def draw(key):
    a = jax.random.uniform(key)
    b = jax.random.normal(key)
    return a, b
"""})
    assert rules(active) == ["prng-key-reuse"]
    assert active[0].line == 4


def test_prng_key_reuse_clean_after_split(tmp_path):
    active, _, _ = lint_dir(tmp_path, {"fix_k.py": """\
import jax
def draw(key):
    k1, k2 = jax.random.split(key)
    a = jax.random.uniform(k1)
    b = jax.random.normal(k2)
    return a, b

def refold(key, i):
    a = jax.random.uniform(key)
    key = jax.random.fold_in(key, i)
    b = jax.random.uniform(key)
    return a, b
"""})
    assert active == []


def test_prng_replica_fold_order_fires(tmp_path):
    active, _, _ = lint_dir(tmp_path, {"fix_k.py": """\
import jax
def pair(base, epoch, replica_id):
    k = jax.random.fold_in(base, epoch)
    k = jax.random.fold_in(k, replica_id)
    return k
"""})
    assert rules(active) == ["prng-replica-fold-order"]
    assert active[0].line == 4


def test_prng_replica_fold_first_clean(tmp_path):
    active, _, _ = lint_dir(tmp_path, {"fix_k.py": """\
import jax
def pair(base, epoch, replica_id):
    k = jax.random.fold_in(base, replica_id)
    k = jax.random.fold_in(k, epoch)
    return k
"""})
    assert active == []


# ----------------------------------------------------------------------------
# family 3: host-sync hazards in jitted scopes
# ----------------------------------------------------------------------------

def test_hostsync_item_and_cast_fire(tmp_path):
    active, _, _ = lint_dir(tmp_path, {"fix_h.py": """\
import jax
import jax.numpy as jnp

@jax.jit
def step(x):
    s = jnp.sum(x)
    bad = s.item()
    worse = float(s)
    return bad + worse
"""})
    assert rules(active) == ["host-sync-cast", "host-sync-item"]


def test_hostsync_traced_branch_and_numpy_fire(tmp_path):
    active, _, _ = lint_dir(tmp_path, {"fix_h.py": """\
import jax
import jax.numpy as jnp
import numpy as np

def _loss(x):
    y = jnp.sum(x)
    if y > 0:
        y = -y
    h = np.asarray(y)
    return h

loss_fn = jax.jit(_loss)
"""})
    assert rules(active) == ["host-sync-numpy", "host-sync-traced-branch"]


def test_hostsync_silent_outside_jit_and_on_none_checks(tmp_path):
    active, _, _ = lint_dir(tmp_path, {"fix_h.py": """\
import jax
import jax.numpy as jnp

def host_side(x):
    # not a jit scope: host casts are fine here
    return float(jnp.sum(x).item())

@jax.jit
def step(x, y):
    if y is None:
        return x
    return x + y
"""})
    assert active == []


# ----------------------------------------------------------------------------
# family 4: donation safety
# ----------------------------------------------------------------------------

def test_donate_use_after_assign_form(tmp_path):
    active, _, _ = lint_dir(tmp_path, {"fix_d.py": """\
import jax

def _step(params, x):
    return params

step = jax.jit(_step, donate_argnums=(0,))

def loop(params, xs):
    out = step(params, xs)
    norm = params.sum()
    return out, norm
"""})
    assert rules(active) == ["donate-use-after"]
    assert active[0].line == 10


def test_donate_use_after_decorator_form(tmp_path):
    active, _, _ = lint_dir(tmp_path, {"fix_d.py": """\
import jax
from functools import partial

@partial(jax.jit, donate_argnums=(0, 2))
def train(p, x, cache):
    return p, cache

def drive(p, x, cache):
    p2, c2 = train(p, x, cache)
    return cache
"""})
    assert rules(active) == ["donate-use-after"]


def test_donate_same_statement_rebind_clean(tmp_path):
    active, _, _ = lint_dir(tmp_path, {"fix_d.py": """\
import jax

def _step(params, x, cache):
    return params, cache

step = jax.jit(_step, donate_argnums=(0, 2))

def loop(params, xs, cache):
    for x in xs:
        params, cache = step(params, x, cache)
    return params, cache
"""})
    assert active == []


# ----------------------------------------------------------------------------
# family 5: lock discipline (# guarded-by:)
# ----------------------------------------------------------------------------

def test_lock_unguarded_access_fires(tmp_path):
    active, _, _ = lint_dir(tmp_path, {"fix_l.py": """\
import threading

class Box:
    def __init__(self):
        self._lock = threading.Lock()
        self._items = []        # guarded-by: self._lock

    def add(self, x):
        self._items.append(x)
"""})
    assert rules(active) == ["lock-unguarded-access"]
    assert "_items" in active[0].message


def test_lock_standalone_annotation_and_wrong_lock(tmp_path):
    active, _, _ = lint_dir(tmp_path, {"fix_l.py": """\
import threading

class Box:
    def __init__(self):
        self._lock = threading.Lock()
        self._other = threading.Lock()
        # guarded-by: self._lock
        self._n = 0

    def bump(self):
        with self._other:
            self._n += 1
"""})
    assert rules(active) == ["lock-unguarded-access"]


def test_lock_clean_inside_with_and_locked_helpers(tmp_path):
    active, _, _ = lint_dir(tmp_path, {"fix_l.py": """\
import threading

class Box:
    def __init__(self):
        self._lock = threading.Lock()
        self._items = []        # guarded-by: self._lock

    def add(self, x):
        with self._lock:
            self._append_locked(x)

    def _append_locked(self, x):
        self._items.append(x)
"""})
    assert active == []


# ----------------------------------------------------------------------------
# family 9: lock-order discipline
# ----------------------------------------------------------------------------

_LOCKORDER = {"lock-order-cycle", "lock-held-blocking-call"}


def test_lockorder_abba_cycle_fires(tmp_path):
    active, _, _ = lint_dir(tmp_path, {"fix_lo.py": """\
import threading

class Box:
    def __init__(self):
        self._lock_a = threading.Lock()
        self._lock_b = threading.Lock()

    def fwd(self):
        with self._lock_a:
            with self._lock_b:
                pass

    def rev(self):
        with self._lock_b:
            with self._lock_a:
                pass
"""}, select=_LOCKORDER)
    # both edges of the ABBA pair are on the cycle — one finding each
    assert rules(active) == ["lock-order-cycle", "lock-order-cycle"]
    assert "Box._lock_a" in (active[0].message + active[1].message)
    assert "reverse order" in active[0].message


def test_lockorder_self_nest_lock_fires_rlock_clean(tmp_path):
    active, _, _ = lint_dir(tmp_path, {"fix_lo.py": """\
import threading

class Plain:
    def __init__(self):
        self._lock = threading.Lock()

    def f(self):
        with self._lock:
            with self._lock:
                pass

class Reentrant:
    def __init__(self):
        self._lock = threading.RLock()

    def f(self):
        with self._lock:
            with self._lock:
                pass
"""}, select=_LOCKORDER)
    assert rules(active) == ["lock-order-cycle"]
    assert "Plain._lock" in active[0].message


def test_lockorder_consistent_order_clean(tmp_path):
    active, _, _ = lint_dir(tmp_path, {"fix_lo.py": """\
import threading

class Box:
    def __init__(self):
        self._lock_a = threading.Lock()
        self._lock_b = threading.Lock()

    def f(self):
        with self._lock_a:
            with self._lock_b:
                pass

    def g(self):
        with self._lock_a, self._lock_b:
            pass
"""}, select=_LOCKORDER)
    assert active == []


def test_lockorder_blocking_call_under_lock(tmp_path):
    active, _, _ = lint_dir(tmp_path, {"fix_lo.py": """\
import os
import threading

class Box:
    def __init__(self):
        self._lock = threading.Lock()
        self._cv = threading.Condition(self._lock)

    def flush(self, fd, t):
        with self._lock:
            os.fsync(fd)        # blocks every contender on a slow disk
            t.join()            # thread join: unbounded

    def fine(self, xs):
        with self._lock:
            s = ",".join(xs)    # string join: not a thread join
        with self._cv:
            self._cv.wait()     # releases the lock while waiting
        return s
"""}, select=_LOCKORDER)
    assert rules(active) == ["lock-held-blocking-call",
                             "lock-held-blocking-call"]
    assert any("fsync" in f.message for f in active)
    assert any("join" in f.message for f in active)


# ----------------------------------------------------------------------------
# family 6: contract lints (obs registry, exit codes)
# ----------------------------------------------------------------------------

def test_obs_unregistered_event_fires(tmp_path):
    active, _, _ = lint_dir(tmp_path, {
        "obs.py": 'EVENT_KINDS = ("epoch", "run_end")\n',
        "fix_c.py": """\
def report(obs):
    obs.emit("epoch", n=1)
    obs.emit("totally_new_kind", n=2)
"""})
    assert rules(active) == ["obs-unregistered-event"]
    assert "totally_new_kind" in active[0].message


def test_obs_rule_inactive_without_registry(tmp_path):
    active, _, _ = lint_dir(tmp_path, {"fix_c.py": """\
def report(obs):
    obs.emit("anything_goes", n=1)
"""})
    assert active == []


def test_exit_code_literal_fires_and_named_clean(tmp_path):
    active, _, _ = lint_dir(tmp_path, {"fix_c.py": """\
import os
import sys
EXIT_DIVERGED = 76

def die(kind):
    if kind == "preempt":
        sys.exit(75)
    if kind == "watchdog":
        os._exit(77)
    sys.exit(EXIT_DIVERGED)     # named constant: fine
    sys.exit(1)                 # outside the lifecycle range: fine
"""})
    assert rules(active) == ["exit-code-literal", "exit-code-literal"]
    assert "EXIT_PREEMPTED" in active[0].message


# ----------------------------------------------------------------------------
# suppressions
# ----------------------------------------------------------------------------

def test_reasoned_suppression_moves_finding(tmp_path):
    active, suppressed, _ = lint_dir(tmp_path, {"fix_s.py": """\
import jax
# graftlint: disable=prng-literal-key(fixture: the reason travels)
k = jax.random.PRNGKey(0)
"""})
    assert active == []
    assert rules(suppressed) == ["prng-literal-key"]
    assert suppressed[0].reason == "fixture: the reason travels"


def test_trailing_suppression_covers_own_line(tmp_path):
    active, suppressed, _ = lint_dir(tmp_path, {"fix_s.py": (
        "import jax\n"
        "k = jax.random.PRNGKey(0)  "
        "# graftlint: disable=prng-literal-key(same line)\n")})
    assert active == [] and rules(suppressed) == ["prng-literal-key"]


def test_reasonless_suppression_is_a_finding(tmp_path):
    active, suppressed, _ = lint_dir(tmp_path, {"fix_s.py": """\
import jax
# graftlint: disable=prng-literal-key
k = jax.random.PRNGKey(0)
"""})
    # the reasonless marker does NOT suppress, and is itself flagged
    assert rules(active) == ["prng-literal-key", "suppression-missing-reason"]
    assert suppressed == []


def test_unknown_rule_suppression_is_a_finding(tmp_path):
    active, _, _ = lint_dir(tmp_path, {"fix_s.py": """\
x = 1  # graftlint: disable=not-a-rule(whatever)
"""})
    assert rules(active) == ["suppression-unknown-rule"]


def test_multi_rule_suppression_list(tmp_path):
    active, suppressed, _ = lint_dir(tmp_path, {"fix_s.py": """\
import jax
def draw(key):
    # graftlint: disable=prng-key-reuse(fixture A),prng-literal-key(fixture B)
    a = jax.random.uniform(jax.random.key(7))
    return a
"""})
    assert active == []
    assert rules(suppressed) == ["prng-literal-key"]


# ----------------------------------------------------------------------------
# report schema + select + parse errors
# ----------------------------------------------------------------------------

def test_report_json_schema(tmp_path):
    active, suppressed, errors = lint_dir(tmp_path, {
        "fix_r.py": "import jax\nk = jax.random.PRNGKey(3)\n",
        "broken.py": "def oops(:\n"})
    assert errors == ["broken.py"]
    rep = report_json(active, suppressed, errors, root=str(tmp_path),
                      n_files=2)
    assert rep["graftlint"] == 1 and rep["files_scanned"] == 2
    assert rep["ok"] is False
    assert rep["counts"] == {"prng-literal-key": 1}
    f = rep["findings"][0]
    assert set(f) == {"file", "line", "col", "rule", "message", "hint"}
    assert f["hint"] == RULE_DOCS["prng-literal-key"][1]
    json.dumps(rep)     # serializable end to end


def test_select_filters_but_keeps_suppression_rules(tmp_path):
    files = {"fix_r.py": """\
import sys
import jax
k = jax.random.PRNGKey(3)  # graftlint: disable=no-such-rule
def die():
    sys.exit(76)
"""}
    active, _, _ = lint_dir(tmp_path, files,
                            select={"exit-code-literal"})
    # selected rule + the framework's suppression lints always run
    # (an unknown rule id is one finding — it can't also be reasonless)
    assert rules(active) == ["exit-code-literal",
                             "suppression-unknown-rule"]


def test_every_rule_family_documented():
    fams = {"spmd-", "prng-", "host-sync-", "donate-", "lock-", "obs-"}
    for fam in fams:
        assert any(r.startswith(fam) for r in RULE_DOCS), fam
    for rule, (desc, hint) in RULE_DOCS.items():
        assert desc and hint, rule


# ----------------------------------------------------------------------------
# CLI + lint.sh
# ----------------------------------------------------------------------------

def _env():
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO)
    return env


def _cli(args, cwd=REPO):
    return subprocess.run([sys.executable, "-m", "bnsgcn_tpu.analysis"]
                          + list(args), capture_output=True, text=True,
                          timeout=300, cwd=cwd, env=_env())


def test_cli_seeded_violations_exit_nonzero(tmp_path):
    (tmp_path / "fix_v.py").write_text(
        "import jax\nk = jax.random.PRNGKey(1)\n")
    rep = tmp_path / "report.json"
    r = _cli(["--root", str(tmp_path), "--json", str(rep), str(tmp_path)])
    assert r.returncode == 1, r.stdout + r.stderr
    assert "prng-literal-key" in r.stdout and "fix:" in r.stdout
    data = json.loads(rep.read_text())
    assert data["ok"] is False and data["counts"]["prng-literal-key"] == 1


def test_cli_unknown_select_and_list_rules(tmp_path):
    r = _cli(["--select", "no-such-rule", str(tmp_path)])
    assert r.returncode == 2 and "unknown rule" in r.stderr
    r = _cli(["--list-rules"])
    assert r.returncode == 0
    for rule in RULE_DOCS:
        assert rule in r.stdout


@pytest.mark.quickgate
def test_lint_sh_clean_at_head(tmp_path):
    """The repo lints itself: tools/lint.sh exits 0 at HEAD (the CI gate
    fault_matrix.sh and the quickgate tier both invoke)."""
    env = _env()
    env["LINT_REPORT"] = str(tmp_path / "lint_report.json")
    # gate 1 only: the IR and proto tiers' clean-at-HEAD runs are their
    # own quickgates (test_analysis_ir.test_ir_audit_clean_at_head,
    # test_analysis_proto.test_proto_audit_clean_at_head) — no doubling
    env["LINT_SKIP_IR"] = "1"
    env["LINT_SKIP_PROTO"] = "1"
    r = subprocess.run(["bash", "tools/lint.sh"], capture_output=True,
                       text=True, timeout=300, cwd=REPO, env=env)
    assert r.returncode == 0, r.stdout + r.stderr
    data = json.loads((tmp_path / "lint_report.json").read_text())
    assert data["ok"] is True and data["findings"] == []
    assert data["files_scanned"] >= 50
    # every checked-in suppression carries its reason into the report
    assert all(s["reason"] for s in data["suppressed"])


def test_default_targets_exclude_tests():
    files = iter_py_files(["bnsgcn_tpu", "tools"], resolve_root(REPO))
    assert not any(os.sep + "tests" + os.sep in f for f in files)


# ----------------------------------------------------------------------------
# --strict-exec end to end
# ----------------------------------------------------------------------------

BASE_ARGS = [
    "--dataset", "sbm", "--partition-method", "random", "--n-partitions", "2",
    "--model", "graphsage", "--n-layers", "2", "--n-hidden", "8",
    "--sampling-rate", "0.5", "--use-pp", "--n-epochs", "6",
    "--log-every", "2", "--no-eval", "--no-comm-trace",
    "--fix-seed", "--seed", "11",
]


@pytest.mark.quickgate
def test_strict_exec_e2e_clean_run(tmp_path):
    """--strict-exec on a real CLI run: the transfer guard + compile
    listener wrap every hot-loop step; --halo-refresh 2 exercises BOTH
    compiled step programs (full + cached) as separate variants. The run
    must finish rc=0 with zero violations, each variant compiling exactly
    once (its first guarded step), and the audit landing on the obs bus."""
    log = str(tmp_path / "obs.jsonl")
    cmd = ([sys.executable, "-m", "bnsgcn_tpu.main"] + BASE_ARGS
           + ["--part-path", str(tmp_path / "parts"),
              "--ckpt-path", str(tmp_path / "ckpt"),
              "--results-path", str(tmp_path / "res"),
              "--halo-refresh", "2", "--strict-exec", "--obs-log", log])
    env = _env()
    env.update(XLA_FLAGS="--xla_force_host_platform_device_count=2",
               BNSGCN_RETRY_BACKOFF_S="0")
    r = subprocess.run(cmd, capture_output=True, text=True, timeout=300,
                       cwd=REPO, env=env)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "[strict] exec audit:" in r.stdout
    assert "0 violation(s)" in r.stdout
    from bnsgcn_tpu.obs import load_events
    evs = load_events(log)
    se = [e for e in evs if e["kind"] == "strict_exec"]
    assert len(se) == 1, se
    s = se[0]
    assert s["violations"] == 0
    assert sorted(s["variants"]) == ["cached", "full"]
    # each program compiles exactly once, in its first guarded step
    assert s["first_compiles"] == {"full": 1, "cached": 1}
    assert sum(s["steps"].values()) == 6 and s["fetches"] == 6


def test_strict_exec_unit_recompile_and_fetch():
    """StrictExec unit semantics: a compile during a variant's first step
    arms it; a compile in any later step raises StrictExecError; fetch()
    counts; finish() emits the summary through a provided obs."""
    import jax
    import jax.numpy as jnp

    from bnsgcn_tpu.strict import StrictExec, StrictExecError

    emitted = []

    class FakeObs:
        def emit(self, kind, **kw):
            emitted.append((kind, kw))

    lines = []
    st = StrictExec(obs=FakeObs(), log=lines.append)

    @jax.jit
    def f(x):
        return x * 2

    x = jnp.arange(4.0)
    with st.step("v"):
        f(x)                    # first step: compiling is legal
    assert st.first_compiles["v"] >= 1
    with st.step("v"):
        f(x)                    # cached: no compile, still clean
    with pytest.raises(StrictExecError, match="recompile"):
        with st.step("v"):
            f(jnp.arange(8.0))  # new shape -> steady-state recompile
    assert float(st.fetch(jnp.float32(3.0))) == 3.0 and st.fetches == 1
    s = st.finish()
    # 3 steps entered (the raising one still counts), 1 violation recorded
    assert s["violations"] == 1 and s["steps"]["v"] == 3
    assert emitted and emitted[0][0] == "strict_exec"
    assert any("[strict] exec audit:" in ln for ln in lines)


@pytest.mark.parametrize("obs_on", [True, False])
def test_strict_exec_recompile_raises_through_the_one_registration(
        tmp_path, obs_on):
    """StrictExec hears compiles through obs's one jax.monitoring
    registration, beside a run's Obs (obs on) or alone (obs off: no Obs
    exists): a forced recompile inside a later step still raises, and
    finish() leaves it subscribed no more."""
    import jax
    import jax.numpy as jnp

    from bnsgcn_tpu import obs as obs_mod
    from bnsgcn_tpu.config import Config
    from bnsgcn_tpu.strict import StrictExec, StrictExecError

    ob = obs_mod.make_obs(
        Config(obs="on" if obs_on else "off",
               obs_log=str(tmp_path / "o.jsonl")), log=lambda *a: None)
    assert (ob is not None) == obs_on
    st = StrictExec(obs=ob, log=lambda *a: None)

    @jax.jit
    def f(x):
        return x + 1

    with st.step("v"):
        f(jnp.arange(3.0))
    with pytest.raises(StrictExecError, match="recompile"):
        with st.step("v"):
            f(jnp.arange(5.0))
    s = st.finish()
    assert s["violations"] == 1 and s["first_compiles"]["v"] >= 1
    assert all(r() != st._saw_compile for r in obs_mod._subscribers)
    if ob is not None:
        assert "jit(f)" in ob.take_compiles()["programs"]
        ob.close()
