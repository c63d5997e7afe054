"""CPU preflight of the serving load generator (tools/serve_bench.py).

The ACTUAL tool runs as a subprocess at tiny scale on CPU and must emit
every metric in its SERVE_METRICS vocabulary, for both tiers, as parseable
JSON lines.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

from serve_bench import SERVE_METRICS  # noqa: E402


def _env():
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu")
    # the suite conftest forces an 8-device CPU mesh; the serving bench
    # needs no mesh — drop the forced device count for the subprocess
    flags = env.get("XLA_FLAGS", "").replace(
        "--xla_force_host_platform_device_count=8", "").strip()
    if flags:
        env["XLA_FLAGS"] = flags
    else:
        env.pop("XLA_FLAGS", None)
    return env


@pytest.mark.slow
def test_serve_bench_emits_full_metric_vocabulary():
    cmd = [sys.executable, os.path.join(REPO, "tools", "serve_bench.py"),
           "--requests", "40", "--concurrency", "2", "--warmup", "4",
           "--hidden", "8", "--json-only"]
    r = subprocess.run(cmd, capture_output=True, text=True, timeout=600,
                       cwd=REPO, env=_env())
    tail = "\n".join((r.stdout + "\n" + r.stderr).splitlines()[-25:])
    assert r.returncode == 0, f"serve_bench failed preflight:\n{tail}"
    lines = [json.loads(ln) for ln in r.stdout.splitlines()
             if ln.startswith("{")]
    assert lines, f"no JSON metric lines:\n{tail}"
    seen = {(ln["metric"], ln.get("tier")) for ln in lines}
    for metric in SERVE_METRICS:
        for tier in ("A", "B"):
            assert (metric, tier) in seen, f"missing {metric}/{tier}:\n{tail}"
    for ln in lines:
        assert ln["metric"] in SERVE_METRICS, f"off-vocabulary: {ln}"
        assert ln["unit"] == SERVE_METRICS[ln["metric"]]
        assert ln["value"] > 0, f"non-positive metric: {ln}"
        # the default is the single-host variant, tagged so that its numbers
        # are never compared against a fleet's, and stamped with the device
        # they were taken on (the unit says req/s/chip on a CPU too)
        assert ln["variant"] == "serve1" and ln["backends"] == 1, ln
        assert ln["platform"] == "cpu" and ln["device_count"] >= 1, ln
    # last line wins for the driver: it must be a valid vocabulary metric
    last = lines[-1]
    assert last["metric"] == "serve_qps" and last["tier"] == "A"


@pytest.mark.slow
def test_fleet_variant_tags_backend_counts():
    """--fleet 2: every metric line of the router-fronted fleet carries the
    variant/backend-count tags that keep a serve1 number from ever being
    compared against a serve2p one, plus its measured router overhead."""
    cmd = [sys.executable, os.path.join(REPO, "tools", "serve_bench.py"),
           "--json-only", "--requests", "24", "--concurrency", "2",
           "--variant", "serve2p", "--fleet", "2"]
    r = subprocess.run(cmd, capture_output=True, text=True, timeout=600,
                       cwd=REPO, env=_env())
    tail = "\n".join((r.stdout + "\n" + r.stderr).splitlines()[-25:])
    assert r.returncode == 0, f"serve_bench --fleet failed preflight:\n{tail}"
    lines = [json.loads(ln) for ln in r.stdout.splitlines()
             if ln.startswith("{")]
    assert lines, f"no JSON metric lines:\n{tail}"
    for ln in lines:
        assert ln["metric"] in SERVE_METRICS, f"off-vocabulary: {ln}"
        assert ln["variant"] == "serve2p" and ln["backends"] == 2, ln
        # the routed fleet measured its own router tax vs a direct backend
        assert ln["router_overhead_x"] > 0
    seen = {(ln["metric"], ln.get("tier")) for ln in lines}
    for metric in SERVE_METRICS:
        for tier in ("A", "B"):
            assert (metric, tier) in seen, f"missing {metric}/{tier}:\n{tail}"
    last = lines[-1]
    assert last["metric"] == "serve_qps" and last["tier"] == "A"
