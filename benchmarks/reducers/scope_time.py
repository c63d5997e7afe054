"""Seconds per traced step on the busiest device under the named scopes of
the program's table (`scopes`), or the share of the busy time under none of
them and no collective (`unscoped`). Reads `scopelib.scope_seconds_of`."""
from benchmarks import scopelib


def reduce(ctx, scopes=(), unscoped=False):
    read = scopelib.scope_seconds_of(ctx)
    if read is None:
        return None
    took, _ = read
    unknown = set(scopes) - set(scopelib.program_scopes()[0])
    if unknown:
        raise ValueError(f"not in the program's scope table: {sorted(unknown)}")
    if unscoped:
        busy = sum(took.values())
        return 100.0 * took.get(scopelib.UNSCOPED, 0.0) / busy if busy else None
    found = [took[s] for s in scopes if s in took]
    return sum(found) if found else None
