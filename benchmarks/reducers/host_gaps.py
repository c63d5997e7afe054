"""Share of the busiest device's idle time inside the traced window that a
host span of the program names (`scopelib.named_gaps`). The seconds by phase
and the largest gap's phase go to the result's `notes`."""
from benchmarks import scopelib


def reduce(ctx):
    gaps = scopelib.named_gaps(ctx["trace_events"])
    if not gaps:
        return None
    by = {}
    for name, s in gaps:
        by[name] = by.get(name, 0.0) + s
    idle = sum(by.values())
    order = sorted(by.items(), key=lambda kv: -kv[1])
    ctx["breakdown_notes"]["idle_by_phase"] = ", ".join(
        f"{k} {v:.4g}" for k, v in order[:8])
    name, s = max(gaps, key=lambda g: g[1])
    ctx["breakdown_notes"]["largest_idle_gap"] = f"{name} {s:.4g}"
    return 100.0 * (idle - by.get(scopelib.UNNAMED, 0.0)) / idle
