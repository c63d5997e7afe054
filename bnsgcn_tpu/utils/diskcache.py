"""Versioned-pickle disk cache for expensive host-side builds.

One shared implementation for bench.py's artifact/layout caches and the
trainer's `--cache-dir` / $BNSGCN_CACHE_DIR layout persistence (the hybrid
SpMM layout build is ~980 s at bench scale — pointing the cache at a
persistent volume makes it survive container wipes). Keys are the caller's
content-addressed names (trainer.hybrid_layout_key), so entries cannot
drift across the two users.
"""

from __future__ import annotations

import os
import pickle
import time

CACHE_VER = 2               # bump when artifact/layout formats change
                            # (2: the ELL width ladder and split-row tails,
                            # whose tables the combine of 1 cannot read)


def try_load(path: str, log=print):
    """Versioned-pickle read; None on missing/stale/corrupt (a bad cache
    must never kill the caller)."""
    if not os.path.exists(path):
        return None
    t0 = time.time()
    try:
        with open(path, "rb") as f:
            ver, obj = pickle.load(f)
        if ver != CACHE_VER:
            log(f"  stale cache version {ver} at {path}; ignoring")
            return None
        log(f"  loaded {os.path.basename(path)} in {time.time() - t0:.1f}s")
        return obj
    except Exception as ex:
        log(f"  cache read failed at {path} ({type(ex).__name__})")
        return None


def _pid_alive(pid: int) -> bool:
    try:
        os.kill(pid, 0)
    except ProcessLookupError:
        return False
    except PermissionError:
        return True                 # exists, owned by someone else
    except OSError:
        return False
    return True


def sweep_stale_tmp(cache_dir: str, log=print, max_age_s: float = 3600.0,
                    grace_s: float = 600.0) -> int:
    """Remove `*.tmp` files a crashed/preempted writer left mid-atomic_dump.

    atomic_dump tmp names embed the writer PID (`{name}.{pid}.tmp`): a dead
    PID suggests the dump never reached its os.replace and the bytes are
    garbage — but on a SHARED cache volume (the documented multi-container
    use) another host's live writer has a PID that looks dead in this
    namespace, so the PID check alone never deletes anything: a dead-looking
    PID must also be `grace_s` past its last write (pickle.dump refreshes
    mtime continuously, so an in-progress dump always looks fresh), and
    live-looking PIDs (recycled, or genuinely mid-dump) fall back to the
    long `max_age_s` bound — no real dump takes an hour between writes.
    Returns the number removed; called on cache-dir open (run.py) so the
    dir can't accumulate torn files."""
    removed = 0
    try:
        names = os.listdir(cache_dir)
    except OSError:
        return 0
    now = time.time()
    for fn in names:
        if not fn.endswith(".tmp"):
            continue
        path = os.path.join(cache_dir, fn)
        try:
            age = now - os.path.getmtime(path)
        except OSError:
            continue                # vanished under us (concurrent replace)
        stem = fn[:-len(".tmp")].rsplit(".", 1)
        pid_dead = len(stem) == 2 and stem[1].isdigit() and \
            not _pid_alive(int(stem[1]))
        if (pid_dead and age > grace_s) or age > max_age_s:
            try:
                os.remove(path)
                removed += 1
            except OSError:
                pass
    if removed:
        log(f"  cache sweep: removed {removed} stale .tmp file(s) "
            f"from {cache_dir}")
    return removed


def atomic_dump(obj, path: str):
    tmp = f"{path}.{os.getpid()}.tmp"   # per-PID: prep-only and a watchdog
    with open(tmp, "wb") as f:          # bench may write concurrently
        pickle.dump((CACHE_VER, obj), f, protocol=4)
    os.replace(tmp, path)


def disk_cached(path: str, build, log=print):
    """Pickle-backed build cache (artifacts + SpMM layouts are minutes of
    numpy at bench scale — pre-buildable on CPU while the TPU idles)."""
    obj = try_load(path, log)
    if obj is None:
        obj = build()
        atomic_dump(obj, path)
    return obj
