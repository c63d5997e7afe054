"""Residual gather rate: ELL slots the step's aggregations read (the
`run_header` event's `spmm` counts: slots a call, padding included, times
the forward and backward calls a step) over the seconds a step spends under
`scopes` (`scopelib.scope_seconds_of`), in millions a second."""
from benchmarks import scopelib


def reduce(ctx, scopes):
    head = next((e for e in ctx["events"] if e.get("kind") == "run_header"),
                {}).get("spmm")
    read = scopelib.scope_seconds_of(ctx)
    if not head or read is None:
        return None
    seconds = sum(read[0].get(s, 0.0) for s in scopes)
    slots = (head["residual_slots_fwd"] * head["agg_calls_fwd"]
             + head["residual_slots_bwd"] * head["agg_calls_bwd"])
    return slots / seconds / 1e6 if slots and seconds else None
