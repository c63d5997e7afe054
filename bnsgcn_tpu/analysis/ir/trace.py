"""Abstract program tracing for the graftlint-ir tier.

Everything here runs on a host-only ``jax.sharding.AbstractMesh`` — no
devices, no FLOPs, no data: `jax.make_jaxpr` over ShapeDtypeStructs yields
the exact program a run would compile (shard_map accepts an abstract mesh
at trace time), and `jit(...).lower()` of the same avals yields the
StableHLO whose ``tf.aliasing_output`` attributes prove each donated
buffer is consumed. The contract checkers (``contracts.py``) consume only
the ``TracedProgram`` summaries built here, so seeded-violation tests can
feed them hand-built fixture programs through the same entry points.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import jax
import jax.extend.core as jex_core
import numpy as np

# Communication primitives whose ordered sequence IS the collective
# schedule. Inside shard_map `lax.psum` traces as `psum_invariant` (the
# varying -> invariant reduce of the vma type system); `pvary`, its
# inverse cast, moves no data and is deliberately absent.
COLLECTIVE_PRIMS = frozenset({
    "psum", "psum_invariant", "pmax", "pmin", "pmean",
    "all_to_all", "all_gather", "all_gather_invariant",
    "ppermute", "pshuffle", "ragged_all_to_all",
    "psum_scatter", "reduce_scatter", "pbroadcast",
})

# Control-flow primitives whose branch selection can diverge per rank when
# the predicate derives from axis_index — a collective under one is only
# executed by the ranks that take that branch: the canonical SPMD hang.
_BRANCHY_PRIMS = frozenset({"cond", "switch"})

# Primitives that mint a rank identity; anything data-dependent on one is
# rank-varying (taint source for the branch check).
_RANK_PRIMS = frozenset({"axis_index", "axis_size"})


@dataclass(frozen=True)
class Collective:
    """One communication eqn in traced order."""
    prim: str
    axes: tuple            # normalized axis-name tuple
    shape: tuple           # operand shape (per-shard, inside shard_map)
    dtype: str
    groups: bool           # axis_index_groups was not None
    stack: tuple           # enclosing higher-order primitive names
    rank_branched: bool    # under a cond/switch whose predicate is
                           # data-dependent on axis_index

    @property
    def sig(self) -> tuple:
        """Schedule signature: what must be identical across ranks and
        across every retune into the same lever state."""
        return (self.prim, self.axes, self.shape, self.dtype)


@dataclass
class DonationInfo:
    donated: tuple = ()    # flat arg indices marked donated
    aliased: tuple = ()    # flat arg indices with tf.aliasing_output
    paths: dict = field(default_factory=dict)   # flat index -> tree path str

    @property
    def dead(self) -> tuple:
        """Donated-but-never-aliased buffers: the donation silently buys
        nothing and the 'saved' HBM is still live."""
        return tuple(i for i in self.donated if i not in set(self.aliased))


@dataclass
class TracedProgram:
    """Contract-checker view of one traced program."""
    name: str
    collectives: list = field(default_factory=list)
    transfers: list = field(default_factory=list)   # (prim, stack) hits
    donation: DonationInfo | None = None
    peak_live_bytes: int = 0

    def schedule(self) -> tuple:
        return tuple(c.sig for c in self.collectives)


# ----------------------------------------------------------------------------
# jaxpr walking
# ----------------------------------------------------------------------------

def _subjaxprs(eqn):
    """Inner jaxprs of a higher-order eqn, wherever its params keep them
    (pjit: 'jaxpr'; shard_map/scan/while: 'jaxpr'/'body_jaxpr'/...;
    cond/switch: 'branches'; custom_vjp: 'fun_jaxpr'). Scanning every param
    value generically survives primitive-specific param renames."""
    for v in eqn.params.values():
        for j in _as_jaxprs(v):
            yield j


def _as_jaxprs(v):
    if isinstance(v, jex_core.ClosedJaxpr):
        yield v.jaxpr
    elif isinstance(v, jex_core.Jaxpr):
        yield v
    elif isinstance(v, (tuple, list)):
        for item in v:
            yield from _as_jaxprs(item)


def _aval_bytes(aval) -> int:
    try:
        return int(np.prod(aval.shape)) * aval.dtype.itemsize
    except Exception:
        return 0


def _axes_of(eqn) -> tuple:
    p = eqn.params
    ax = p.get("axes", p.get("axis_name", p.get("axis_names", ())))
    if ax is None:
        ax = ()
    if not isinstance(ax, (tuple, list)):
        ax = (ax,)
    return tuple(str(a) for a in ax)


def _collect(jaxpr, stack: tuple, tainted: set, out_coll: list,
             out_xfer: list, force_branched: bool):
    """One recursive pass: collectives + transfers + axis_index taint.

    `tainted` holds vars of THIS jaxpr known rank-varying (seeded by the
    caller through invar positions, extended by local axis_index eqns and
    dataflow). `force_branched` marks every collective below a
    rank-predicated cond that was entered higher up."""
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        eqn_tainted = any(v in tainted for v in eqn.invars
                          if not isinstance(v, jex_core.Literal))
        if prim in COLLECTIVE_PRIMS and (eqn.invars or eqn.outvars):
            # operand-less eqns (pbroadcast replication annotations) move
            # nothing and are not part of the wire schedule — skipped
            v0 = (eqn.invars or eqn.outvars)[0]
            aval = getattr(v0, "aval", None)
            out_coll.append(Collective(
                prim=prim, axes=_axes_of(eqn),
                shape=tuple(getattr(aval, "shape", ())),
                dtype=str(getattr(aval, "dtype", "")),
                groups=eqn.params.get("axis_index_groups") is not None,
                stack=stack,
                rank_branched=force_branched,
            ))
        if prim in _TRANSFER_PRIMS():
            out_xfer.append((prim, stack))

        branch_forces = force_branched
        if prim in _BRANCHY_PRIMS:
            # flag only when the PREDICATE (invar 0) is rank-varying —
            # everything inside the branches then executes on a subset of
            # ranks; a tainted payload operand alone cannot steer control
            pred = eqn.invars[0]
            if (not isinstance(pred, jex_core.Literal)) and pred in tainted:
                branch_forces = True

        for sub in _subjaxprs(eqn):
            # positional invar taint hand-off where arities line up (cond
            # branches bind eqn.invars[1:], pjit/shard_map bind 1:1; when
            # they don't line up, start clean — the local axis_index seeds
            # below still catch the common same-jaxpr pattern)
            sub_taint = set()
            outer_ins = list(eqn.invars)
            if prim in _BRANCHY_PRIMS:
                outer_ins = outer_ins[1:]
            if len(outer_ins) == len(sub.invars):
                for ov, iv in zip(outer_ins, sub.invars):
                    if not isinstance(ov, jex_core.Literal) and ov in tainted:
                        sub_taint.add(iv)
            _collect(sub, stack + (prim,), sub_taint, out_coll, out_xfer,
                     branch_forces)

        if prim in _RANK_PRIMS or eqn_tainted:
            for ov in eqn.outvars:
                tainted.add(ov)


def _TRANSFER_PRIMS():
    from bnsgcn_tpu.strict import TRANSFER_PRIMITIVES
    return TRANSFER_PRIMITIVES


def peak_live_bytes(closed_jaxpr) -> int:
    """Linear-scan liveness estimate over the top-level jaxpr: the max of
    (sum of live value bytes) after each eqn. Global (unsharded) shapes,
    no donation aliasing credit — an upper-bound ESTIMATE for the HBM
    budget report, not an XLA allocator model."""
    jx = closed_jaxpr.jaxpr
    last_use: dict = {}
    for i, eqn in enumerate(jx.eqns):
        for v in eqn.invars:
            if not isinstance(v, jex_core.Literal):
                last_use[v] = i
    n = len(jx.eqns)
    for v in jx.outvars:
        if not isinstance(v, jex_core.Literal):
            last_use[v] = n
    live = 0
    for v in list(jx.invars) + list(jx.constvars):
        live += _aval_bytes(v.aval)
    peak = live
    for i, eqn in enumerate(jx.eqns):
        for v in eqn.outvars:
            live += _aval_bytes(v.aval)
        peak = max(peak, live)
        seen = set()
        for v in list(eqn.invars) + list(eqn.outvars):
            # Literal is unhashable — skip before deduplicating
            if isinstance(v, jex_core.Literal) or v in seen:
                continue
            seen.add(v)
            if last_use.get(v, -1) <= i:
                live -= _aval_bytes(v.aval)
    return peak


# ----------------------------------------------------------------------------
# program-level entry points
# ----------------------------------------------------------------------------

def trace_program(name: str, fn, *args, **kwargs) -> TracedProgram:
    """make_jaxpr `fn` over avals and summarize its collective schedule,
    transfer hits and peak-live estimate (no lowering, no donation info —
    use `trace_jitted` for that)."""
    closed = jax.make_jaxpr(fn)(*args, **kwargs)
    return summarize(name, closed)


def summarize(name: str, closed_jaxpr) -> TracedProgram:
    coll: list = []
    xfer: list = []
    _collect(closed_jaxpr.jaxpr, (), set(), coll, xfer, False)
    return TracedProgram(name=name, collectives=coll, transfers=xfer,
                         peak_live_bytes=peak_live_bytes(closed_jaxpr))


def trace_jitted(name: str, jitted, *args, **kwargs) -> TracedProgram:
    """Trace a `jax.jit`-wrapped callable (donate_argnums respected) and
    attach the donation audit from its lowered StableHLO."""
    tp = trace_program(name, jitted, *args, **kwargs)
    lowered = jitted.lower(*args, **kwargs)
    tp.donation = donation_info(lowered)
    return tp


def donation_info(lowered) -> DonationInfo:
    """Which flat args are donated, and which actually alias an output in
    the lowered module. `args_info` leaves line up with ``%argN`` of the
    StableHLO ``@main`` by flattening order; a donated arg with no
    ``tf.aliasing_output`` attribute was dropped by XLA — a dead donation
    (the caller invalidated a buffer and got nothing back for it)."""
    paths = {}
    donated = []
    leaves = jax.tree_util.tree_flatten_with_path(lowered.args_info)[0]
    for i, (path, info) in enumerate(leaves):
        paths[i] = jax.tree_util.keystr(path)
        if getattr(info, "donated", False):
            donated.append(i)
    # jit prunes unused args from the lowered signature (keep_unused
    # defaults False), so %argN numbers the KEPT args; kept_var_idx maps
    # them back to args_info's flat indices. Fall back to identity when a
    # jax upgrade moves the field — worst case the audit over-reports and
    # someone lands here.
    try:
        kept = sorted(lowered._lowering.compile_args["kept_var_idx"])
    except (AttributeError, KeyError, TypeError):
        kept = list(range(len(leaves)))
    aliased = [kept[i] if i < len(kept) else i
               for i in _aliased_args(str(lowered.compiler_ir("stablehlo")))]
    return DonationInfo(donated=tuple(donated), aliased=tuple(aliased),
                        paths=paths)


def _aliased_args(shlo: str) -> list:
    """Flat arg indices carrying ``tf.aliasing_output`` in @main's
    signature. Parses the balanced-paren argument list, splitting on
    depth-0 commas (attr dicts and tensor<> types nest commas)."""
    marker = "@main("
    start = shlo.find(marker)
    if start < 0:
        return []
    i = start + len(marker) - 1       # at the '('
    depth = 0
    j = i
    while j < len(shlo):
        c = shlo[j]
        if c in "(<{[":
            depth += 1
        elif c in ")>}]":
            depth -= 1
            if depth == 0:
                break
        j += 1
    arglist = shlo[i + 1:j]
    out = []
    depth = 0
    piece_start = 0
    pieces = []
    for k, c in enumerate(arglist):
        if c in "(<{[":
            depth += 1
        elif c in ")>}]":
            depth -= 1
        elif c == "," and depth == 0:
            pieces.append(arglist[piece_start:k])
            piece_start = k + 1
    pieces.append(arglist[piece_start:])
    import re
    for piece in pieces:
        m = re.search(r"%arg(\d+)", piece)
        if m and "tf.aliasing_output" in piece:
            out.append(int(m.group(1)))
    return out


def payload_wire_bytes(tp: TracedProgram, width: int) -> int:
    """Per-device payload bytes the traced program's halo collectives move:
    the sum of operand bytes over the point-to-point exchange primitives
    (all_to_all / ppermute / ragged_all_to_all) whose operand feature
    width equals `width` — the [P] scale hops of the quantized wires have
    feature width 1 and are excluded, matching the `wire_bytes` /
    `traced_wire_bytes` accounting convention."""
    total = 0
    for c in tp.collectives:
        if c.prim not in ("all_to_all", "ppermute", "ragged_all_to_all"):
            continue
        if not c.shape or c.shape[-1] != width:
            continue
        n = int(np.prod(c.shape))
        total += n * np.dtype(c.dtype).itemsize
    return total
