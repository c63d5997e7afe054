"""The window's largest host stall inside a step's blocking wait: the largest
`wait_s` less the median `wait_s`. The epoch that held it and its process
counters (involuntary context switches, major faults, CPU seconds) go to the
result's `notes`: `nivcsw` up with `cpu_s` flat is a descheduled host."""
import statistics

from benchmarks import obsread


def reduce(ctx):
    win, _ = obsread.window(ctx["events"], ctx["first_epoch"])
    win = [e for e in win if "wait_s" in e]
    if not win:
        return None
    median = statistics.median(float(e["wait_s"]) for e in win)
    worst = max(win, key=lambda e: float(e["wait_s"]))
    ctx["breakdown_notes"]["stall_max"] = (
        "epoch {epoch} wait_s {wait_s} nivcsw {nivcsw} majflt {majflt} "
        "cpu_s {cpu_s}".format(**{k: worst.get(k) for k in (
            "epoch", "wait_s", "nivcsw", "majflt", "cpu_s")}))
    return float(worst["wait_s"]) - median
