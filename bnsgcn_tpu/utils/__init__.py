"""Utility re-exports, resolved lazily (PEP 562): `utils.diskcache` and
`utils.platform` stay importable without dragging in jax (timers imports
it)."""

_EXPORTS = {
    "calc_acc": "bnsgcn_tpu.utils.metrics",
    "micro_f1": "bnsgcn_tpu.utils.metrics",
    "EpochTimer": "bnsgcn_tpu.utils.timers",
    "device_memory_stats": "bnsgcn_tpu.utils.timers",
}

__all__ = list(_EXPORTS)


def __getattr__(name):
    if name in _EXPORTS:
        import importlib
        return getattr(importlib.import_module(_EXPORTS[name]), name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
