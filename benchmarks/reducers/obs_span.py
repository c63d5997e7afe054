"""Seconds of the program's set-up `span` events of the given names, summed
(a name counts once: its first event). None where the log holds none."""


def reduce(ctx, names):
    found = {}
    for e in ctx["events"]:
        if e.get("kind") == "span" and e.get("name") in names:
            found.setdefault(e["name"], float(e["dur_s"]))
    return sum(found.values()) if found else None
