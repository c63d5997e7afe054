"""From the program's obs event log (JSON lines) to the window's numbers.

The product's loop emits one `epoch` event per epoch after the step and the
loss read, before that epoch's checkpoint: `ts` (wall clock, ms resolution),
`epoch`, `step_s` (dispatch to loss ready), `loss`. The window of a run is the
epochs from `first` on: it starts at the `ts` of epoch `first - 1` and ends at
the `ts` of the last epoch, so every stall between two epochs counts.
"""

from __future__ import annotations

import json
import statistics


def read_events(path: str) -> list:
    out = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                out.append(json.loads(line))
    return out


def epoch_events(events: list) -> list:
    ep = [e for e in events if e.get("kind") == "epoch"]
    ep.sort(key=lambda e: e["epoch"])
    return ep


def window(events: list, first: int):
    """(epoch events of the window, start ts). Needs epoch `first - 1`."""
    ep = epoch_events(events)
    by = {e["epoch"]: e for e in ep}
    if first - 1 not in by:
        raise ValueError(f"obs log has no epoch {first - 1} to start the "
                         f"window from")
    win = [e for e in ep if e["epoch"] >= first]
    if not win:
        raise ValueError(f"obs log has no epoch from {first} on")
    return win, float(by[first - 1]["ts"])


def epoch_s(events: list, first: int) -> float:
    """Wall of the whole window over all its epochs."""
    win, t0 = window(events, first)
    return (float(win[-1]["ts"]) - t0) / len(win)


def epoch_walls(events: list, first: int) -> list:
    win, t0 = window(events, first)
    ts = [t0] + [float(e["ts"]) for e in win]
    return [b - a for a, b in zip(ts, ts[1:])]


def epoch_p95_s(events: list, first: int) -> float:
    walls = sorted(epoch_walls(events, first))
    if len(walls) < 2:
        return walls[0]
    return statistics.quantiles(walls, n=20, method="inclusive")[18]


def host_gap_share(events: list, first: int) -> float:
    """1 - sum(step_s) / window."""
    win, t0 = window(events, first)
    wall = float(win[-1]["ts"]) - t0
    return 1.0 - sum(float(e["step_s"]) for e in win) / wall


def step_median_s(events: list, first: int) -> float:
    win, _ = window(events, first)
    return statistics.median(float(e["step_s"]) for e in win)


def layout_build_s(events: list) -> float:
    """Seconds of layout building that was not a cache hit."""
    return sum(float(e.get("ms", 0.0)) for e in events
               if e.get("kind") == "layout_build"
               and not e.get("cached")) / 1e3
