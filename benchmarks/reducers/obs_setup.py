"""Set-up as the program names it, on the harness's clock. The harness started
at the window's start less `setup_s`; the program's root set-up span
`run_training_setup` splits what follows into three parts that add up to
`setup_s`:

* `pre_run`: harness start to the root's `t0` (imports, the chip's start-up,
  the harness's own checks);
* `warmup`: the root's end to the window's start (the first call and the
  epochs before the window; in a traced run the profiled epochs and the
  trace write too).

What is inside them, where the program carries it (None where it does not):

* `import`: the `import` boot span (the body of `bnsgcn_tpu/run.py`);
* `trace`: tracing and lowering seconds of the root's `compile` account
  (its children's are inside it) and of every epoch before the window;
* `compile`: compile or persistent-cache load seconds, the same way; the
  cache's hits and misses go to the result's `notes`."""
from benchmarks import obsread

ROOT = "run_training_setup"
PARTS = ("pre_run", "warmup", "import", "trace", "compile")


def _span(events, name, parent=None):
    return next((e for e in events if e.get("kind") == "span"
                 and e.get("name") == name
                 and (parent is None or e.get("parent") == parent)), None)


def reduce(ctx, part):
    if part not in PARTS:
        raise ValueError(f"no set-up part {part!r}; one of {PARTS}")
    events = ctx["events"]
    first = ctx["first_epoch"]
    root = _span(events, ROOT)
    if part == "import":
        imp = _span(events, "import", parent="process")
        return None if imp is None else float(imp["dur_s"])
    if root is None:
        return None
    if part in ("pre_run", "warmup"):
        _, t_window = obsread.window(events, first)
        if part == "pre_run":
            return float(root["t0"]) - (t_window - ctx["setup_s"])
        return t_window - (float(root["t0"]) + float(root["dur_s"]))
    accounts = [root.get("compile")] + [
        e.get("compile") for e in obsread.epoch_events(events)
        if e["epoch"] < first]
    accounts = [a for a in accounts if a]
    if not accounts:
        return None
    if part == "trace":
        return sum(float(a["trace_s"]) + float(a["lower_s"])
                   for a in accounts)
    ctx["breakdown_notes"]["setup_cache"] = "hits {} misses {}".format(
        sum(int(a["hits"]) for a in accounts),
        sum(int(a["misses"]) for a in accounts))
    return sum(float(a["compile_s"]) for a in accounts)
