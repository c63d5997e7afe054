"""Median over the window's `epoch` events of one field the loop's host spans
write (`dispatch_s`, `boundary_s`), or of one phase of `boundary` (`part`).
`checkpoint`: true keeps the epochs whose boundary holds a checkpoint write
(the one of the epoch before), false those without. None where no event of
the window has the field (a program without host spans)."""
import statistics

from benchmarks import obsread


def reduce(ctx, field, part=None, checkpoint=None):
    win, _ = obsread.window(ctx["events"], ctx["first_epoch"])
    values = []
    for e in win:
        if field not in e:
            continue
        wrote = "checkpoint" in e.get("boundary", {})
        if checkpoint is not None and wrote != checkpoint:
            continue
        v = e[field] if part is None else e[field].get(part)
        if v is not None:
            values.append(float(v))
    return statistics.median(values) if values else None
