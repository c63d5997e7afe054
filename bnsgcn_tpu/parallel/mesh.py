"""Device mesh construction for partition parallelism.

The reference maps partition rank -> process -> GPU (main.py:35-50, mpirun
path :51-62). Here partitions map onto a 1-D ``('parts',)`` axis of a
`jax.sharding.Mesh`; on a pod slice the axis rides ICI, and a multi-host
papers100M-scale run lays parts over (DCN, ICI) transparently via
`jax.distributed` + `jax.make_mesh`.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def make_parts_mesh(n_parts: int, devices=None) -> Mesh:
    """1-D mesh with one mesh slot per partition.

    n_parts must divide (or equal) the available device count; with fewer
    devices than parts the caller should re-partition (no oversubscription —
    SPMD shard_map owns the axis)."""
    if devices is None:
        devices = jax.devices()
    if len(devices) < n_parts:
        raise ValueError(
            f"need >= {n_parts} devices for {n_parts} partitions, have {len(devices)}; "
            f"re-partition the graph or use a CPU mesh via "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n_parts}")
    return Mesh(np.asarray(devices[:n_parts]), ("parts",))


def parts_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P("parts"))


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def plan_slots(n_parts: int, n_slots: int) -> tuple[int, ...]:
    """Part -> slot assignment for an elastic world of `n_slots` workers
    hosting `n_parts` METIS parts: contiguous balanced blocks (the first
    `n_parts % n_slots` slots take one extra part), so a RESIZE never
    re-partitions the graph — it only re-hosts whole parts. Contiguity
    matters: METIS orders parts so neighbors tend to be adjacent, and a
    contiguous block keeps the heaviest halo pairs intra-slot (free on the
    resized worker) rather than cross-slot wire. Pure host-side metadata —
    the traced step programs keep the full P-wide 'parts' axis regardless
    (see halo.HaloSpec.slot_map).

    plan_slots(4, 2) -> (0, 0, 1, 1); plan_slots(5, 2) -> (0, 0, 0, 1, 1);
    plan_slots(P, P) is the identity (worker == part, today's layout)."""
    if n_slots < 1:
        raise ValueError(f"plan_slots needs >= 1 slot, got {n_slots}")
    if n_parts < n_slots:
        raise ValueError(
            f"cannot spread {n_parts} parts over {n_slots} slots without "
            f"empty workers; shrink the world to <= {n_parts}")
    base, extra = divmod(n_parts, n_slots)
    out = []
    for slot in range(n_slots):
        out.extend([slot] * (base + (1 if slot < extra else 0)))
    return tuple(out)


def slot_members(slot_map: tuple[int, ...]) -> dict[int, list[int]]:
    """{slot: [part ids it hosts]} — the inverse view of `plan_slots`,
    used for logging/obs and the cross-slot wire accounting."""
    out: dict[int, list[int]] = {}
    for part, slot in enumerate(slot_map):
        out.setdefault(int(slot), []).append(part)
    return out
