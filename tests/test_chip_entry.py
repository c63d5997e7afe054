"""The chip entry points refuse to run without the chip, and the compile
cache is placed by one rule.

chip_smoke.py's pass line is what the driver trusts as "the training path
ran on a TPU"; on a machine with no accelerator (this suite's), or in a
directory holding the script and nothing else of the repo, it must exit
non-zero, name what is missing and never print that line. The compile-cache
helper (utils/platform.place_compile_cache) is shared by main.py, bench.py
and chip_smoke.py: an outside JAX_COMPILATION_CACHE_DIR is left alone,
otherwise the cache is one fixed in-checkout path every process agrees on.
"""

import os
import shutil
import subprocess
import sys

import jax

from bnsgcn_tpu.utils import platform as plat

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script, cwd, **env):
    base = {k: v for k, v in os.environ.items()
            if k not in ("PYTHONPATH", "XLA_FLAGS",
                         "JAX_COMPILATION_CACHE_DIR")}
    return subprocess.run([sys.executable, script], cwd=cwd,
                          env=dict(base, JAX_PLATFORMS="cpu", **env),
                          capture_output=True, text=True, timeout=300)


def _no_pass_line(r):
    assert r.returncode != 0, r.stdout + r.stderr
    assert '"ok"' not in r.stdout, r.stdout


def test_chip_smoke_refuses_without_a_chip():
    r = _run(os.path.join(REPO, "chip_smoke.py"), REPO)
    _no_pass_line(r)
    assert "no chip" in r.stderr and "'cpu'" in r.stderr, r.stderr


def test_chip_smoke_refuses_without_the_repo(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    r = _run("chip_smoke.py", str(tmp_path))
    _no_pass_line(r)
    assert "repository is not beside this script" in r.stderr, r.stderr
    # ... also when another checkout is importable from the path
    r = _run("chip_smoke.py", str(tmp_path), PYTHONPATH=REPO)
    _no_pass_line(r)
    assert "not from beside this script" in r.stderr, r.stderr


def test_bench_refuses_without_a_chip():
    r = _run(os.path.join(REPO, "bench.py"), REPO)
    assert r.returncode != 0
    assert r.stdout.strip() == "", r.stdout        # no result line at all
    assert "found none" in r.stderr, r.stderr


def test_compile_cache_left_alone_when_placed_from_outside(monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")

    def no_update(*a, **k):
        raise AssertionError(f"jax.config.update{a} with the variable set")

    monkeypatch.setattr(jax.config, "update", no_update)
    assert plat.place_compile_cache() == "/somewhere/else"


def test_compile_cache_fixed_in_checkout_path(monkeypatch, tmp_path):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: calls.append((k, v)))
    here = plat.place_compile_cache()
    assert here == os.path.join(REPO, ".jax_cache")
    assert calls == [("jax_compilation_cache_dir", here)]
    # the path is part of the cache key: a second process, started
    # elsewhere, must land on the very same directory (and have it in
    # effect in its own jax config)
    probe = tmp_path / "probe.py"
    probe.write_text(
        "import sys; sys.path.insert(0, sys.argv[1])\n"
        "import jax\n"
        "from bnsgcn_tpu.utils.platform import place_compile_cache\n"
        "assert place_compile_cache() == jax.config.jax_compilation_cache_dir\n"
        "print(jax.config.jax_compilation_cache_dir)\n")
    r = subprocess.run([sys.executable, str(probe), REPO], cwd=str(tmp_path),
                       env={k: v for k, v in os.environ.items()
                            if k != "JAX_COMPILATION_CACHE_DIR"},
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    assert r.stdout.strip() == here
    # git would not commit it
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_chip_smoke_reads_the_executed_step_from_the_runs_trace(tmp_path):
    """chip_smoke checks the program that RAN: the Mosaic custom call and
    the collectives are read from the run's own --profile-dir window. The
    recorded v5e P=4 window (tests/data/) carries the collectives; the
    kernel spans are added in the shape that chip run listed them
    (`bns_tile_matmul.N` on every /device:TPU:k process)."""
    import gzip
    import json

    sys.path.insert(0, REPO)
    import chip_smoke
    with gzip.open(os.path.join(REPO, "tests", "data",
                                "v5e_p4_step_comm.trace.json.gz"), "rt") as f:
        events = json.load(f)["traceEvents"]
    steps = sorted({round(e["ts"]) for e in events
                    if e.get("name") == "PjitFunction(train_step)"})
    assert len(steps) == 4
    for pid in (3, 9, 15, 21):
        for ts in steps:
            events.append({
                "ph": "X", "pid": pid, "tid": 3, "ts": ts + 500.0, "dur": 150.0,
                "name": "bns_tile_matmul.9", "args": {"long_name":
                    "%bns_tile_matmul.9 = bf16[6144,256]{1,0} custom-call("
                    "s32[14]{0} %a, s8[14,512,512]{2,1,0} %b), "
                    'custom_call_target="tpu_custom_call"'}})
    d = tmp_path / "plugins" / "profile" / "run1"
    d.mkdir(parents=True)
    with gzip.open(d / "vm.trace.json.gz", "wt") as f:
        json.dump({"traceEvents": events}, f)
    n, kernels, ex, rd = chip_smoke.executed_step_ops(str(tmp_path))
    chips = [f"/device:TPU:{k}" for k in range(4)]
    assert n == 4 and sorted(ex) == chips and sorted(rd) == chips
    assert sorted(kernels) == chips
    assert all(len(v) == 4 and "custom-call(" in v[0]
               for v in kernels.values())
