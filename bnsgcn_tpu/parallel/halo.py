"""The halo (boundary-activation) exchange — the heart of partition parallelism.

TPU-native redesign of the reference feature buffer (helper/feature_buffer.py):

  * one static-shape tiled `lax.all_to_all` over the 'parts' mesh axis
    replaces the gloo irecv/isend ring + pinned staging + deferred-send queues
    (helper/feature_buffer.py:102-129) and the MPI all_to_all (:132-153);
  * the BNS sample for the epoch is computed once per step on *both* endpoints
    from a shared key (`parallel/sampling.py`), replacing the per-epoch index
    exchange (reference train.py:389);
  * sampled activations are scaled by 1/ratio on the sender
    (helper/feature_buffer.py:117,143) and scattered into fixed per-peer halo
    slot blocks; unsampled slots stay zero, which under sum-aggregation over
    the *full* static halo edge list reproduces exactly the reference's
    aggregation over the per-epoch sampled subgraph (train.py:256-281) — no
    graph reconstruction, ever;
  * the backward pass needs no grad hooks (helper/feature_buffer.py:97-98,
    169-182): JAX AD transposes gather -> all_to_all -> scatter-add into
    scatter-add -> all_to_all -> gather, which is precisely the reference's
    gloo backward including the 1/ratio rescale (:129).

Slot layout (see data/artifacts.py): extended row `pad_inner + q*pad_b + k`
on part j holds the k-th entry of q's boundary list toward j.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np

from bnsgcn_tpu.parallel.sampling import (chunk_identity_sample, chunk_sample,
                                          identity_sample, pair_key,
                                          pair_sample)


@dataclass(frozen=True)
class HaloSpec:
    """Static exchange geometry (python ints only — safe to close over in jit).

    The replicated device tables (n_b, send_size, inv_ratio) travel separately
    as a `tables` dict argument through shard_map with spec P().

    `strategy` picks the collective decomposition:
      * 'padded' — one tiled `lax.all_to_all`, every pair padded to the global
        max send size (round-1 behavior; best when partitions are balanced);
      * 'shift'  — P-1 `ppermute` rounds, round k padded only to
        max_p send_size[p, (p+k)%P]: wire bytes track the *actual* skewed
        boundary sizes, the TPU analog of the reference's exact per-pair
        isend sizes (helper/feature_buffer.py:111-121);
      * 'ragged' — ONE `lax.ragged_all_to_all` carrying each (sender, peer)
        pair's exact send_size[p, j] rows: shift's exact bytes without its
        P-1 serialized hops. Offsets/sizes are trace-time constants
        (`pair_send`). Native on TPU; on XLA:CPU, where the collective
        does not lower, a numerically identical emulation
        routes the same rows over the padded all_to_all through the same
        pack/unpack geometry, so the strategy is CPU-mesh-testable.
    `wire` picks the payload dtype on the interconnect:
      * 'native' — h.dtype as-is;
      * 'bf16'   — cast to bfloat16 on the wire;
      * 'int8'   — 1-byte symmetric int8 with per-(sender,peer)-block scales
        (v5e-native convert — preferred over fp8 on hardware);
      * 'fp8'    — float8_e4m3fn with one f32 scale per (sender, peer) block;
        backward gradients are re-quantized with their own scales (a fresh
        amax), not the activation scales — see `_a2a_wire`/`_ppermute_wire`.
    """
    n_parts: int
    pad_inner: int
    pad_boundary: int                  # B_pad: per-pair boundary padding
    pad_send: int                      # S_pad: per-pair send padding (<= B_pad)
    axis_name: str = "parts"
    exact: bool = False                # rate == 1.0: identity ordering, no top_k
    strategy: str = "padded"           # 'padded' | 'shift' | 'ragged'
    wire: str = "native"               # 'native' | 'bf16' | 'fp8' | 'int8'
    shift_pads: tuple = ()             # [P-1] per-shift send widths (strategy='shift')
    pair_send: tuple = ()              # [P][P] exact per-pair send sizes (python
                                       # ints — the ragged geometry is static)
    replica_axis: str | None = None    # 2-D ('replicas','parts') meshes: fold
                                       # axis_index(replica_axis) into the BNS
                                       # keys so each replica draws an
                                       # INDEPENDENT boundary sample. None
                                       # (1-D path) folds nothing —
                                       # bit-identical historical keys. Every
                                       # collective here stays scoped to
                                       # axis_name='parts' either way: inside
                                       # shard_map over a 2-D mesh a
                                       # parts-axis collective acts within
                                       # each replica's own sub-group.
    slot_map: tuple = ()               # [P] part -> hosting worker slot for
                                       # elastic worlds (mesh.plan_slots).
                                       # Host-side addressing metadata ONLY:
                                       # the traced programs keep the full
                                       # P-wide 'parts' axis regardless, and
                                       # nothing inside traced code reads
                                       # this field, so the compiled schedule
                                       # is slot-invariant (pinned by the
                                       # graftlint-ir slot-map section).
                                       # () = identity (worker == part).

    @property
    def n_halo(self) -> int:
        return self.n_parts * self.pad_boundary


def make_halo_spec(n_b: np.ndarray, pad_inner: int, pad_boundary: int,
                   rate: float, axis_name: str = "parts",
                   strategy: str = "padded", wire: str = "native",
                   replica_axis: str | None = None,
                   slot_map=None
                   ) -> tuple[HaloSpec, dict]:
    """Derive fixed send sizes and ratios from boundary sizes + sampling rate
    (reference get_send_size/get_recv_size, train.py:107-131).

    Returns (spec, tables): `tables` = {n_b, send_size, inv_ratio} device
    arrays, replicated across the mesh."""
    n_b = np.asarray(n_b, dtype=np.int64)
    P = n_b.shape[0]
    exact = rate >= 1.0
    send_size = n_b if exact else (rate * n_b).astype(np.int64)
    ratio = np.where(n_b > 0, send_size / np.maximum(n_b, 1), 0.0)
    inv_ratio = np.where(ratio > 0, 1.0 / np.maximum(ratio, 1e-30), 0.0)
    # S_pad: one uniform per-pair send width; multiple of 8 for lane friendliness
    pad_send = max(1, int(send_size.max())) if send_size.size else 1
    pad_send = min(((pad_send + 7) // 8) * 8, pad_boundary)
    # per-shift widths: round k only carries the (p -> p+k) pairs, so its pad
    # is that diagonal's max — zero-size shifts are skipped entirely at trace
    # time (static), making sparse peer topologies cost nothing
    shift_pads = []
    for k in range(1, P):
        m = int(max(send_size[p, (p + k) % P] for p in range(P)))
        shift_pads.append(0 if m == 0 else min(((m + 7) // 8) * 8, pad_send))
    assert strategy in ("padded", "shift", "ragged"), (
        f"unresolved halo strategy {strategy!r} (resolve 'auto' via "
        f"select_halo_strategy before make_halo_spec)")
    spec = HaloSpec(
        n_parts=P, pad_inner=pad_inner, pad_boundary=pad_boundary,
        pad_send=pad_send, axis_name=axis_name, exact=exact,
        strategy=strategy, wire=wire, shift_pads=tuple(shift_pads),
        pair_send=tuple(map(tuple, send_size.tolist())),
        replica_axis=replica_axis,
        slot_map=tuple(int(s) for s in (slot_map or ())),
    )
    tables = {"n_b": jnp.asarray(n_b, jnp.int32),
              "send_size": jnp.asarray(send_size, jnp.int32),
              "inv_ratio": jnp.asarray(inv_ratio, jnp.float32)}
    return spec, tables


def _ragged_exact_rows(pair_send, n_parts: int) -> int:
    """Bottleneck device's exact off-diagonal send rows — what the ragged
    collective puts on the wire (matches the hw-probe accounting,
    2026-07-30 v5e probe: `send.sum(1).max()` with a zero diagonal)."""
    S = np.asarray(pair_send, dtype=np.int64).reshape(n_parts, n_parts).copy()
    np.fill_diagonal(S, 0)
    return int(S.sum(axis=1).max()) if S.size else 0


def wire_bytes(spec: HaloSpec, width: int, native_bytes: int = 4) -> int:
    """Per-device payload bytes of ONE forward exchange at the given feature
    width (excluding the [P] f32 scales, which are negligible). The backward
    exchange costs the same.

    Accounting matches the 2026-07-30 v5e hardware probe:
    'padded' counts the full P-block tiled all_to_all buffer (the self block
    rides the same payload even though its hop is chip-local); 'shift' counts
    its per-diagonal pads; 'ragged' counts the bottleneck device's exact
    off-diagonal rows."""
    b = {"native": native_bytes, "bf16": 2, "fp8": 1, "int8": 1}[spec.wire]
    if spec.strategy == "shift":
        return sum(spec.shift_pads) * width * b
    if spec.strategy == "ragged":
        return _ragged_exact_rows(spec.pair_send, spec.n_parts) * width * b
    return spec.n_parts * spec.pad_send * width * b


def traced_wire_bytes(spec: HaloSpec, width: int, native_bytes: int = 4,
                      ragged_native: Optional[bool] = None) -> int:
    """Per-device payload bytes the COMPILED exchange program actually moves
    — the analysis/ir wire-byte contract's oracle, cross-checked against the
    collective operands extracted from the traced jaxpr.

    Equals `wire_bytes()` for 'padded' and 'shift' (their traced operands
    ARE the accounting). 'ragged' differs by construction: the native
    collective ships the lane-aligned [T_pad, d] operand (the bottleneck
    device's exact rows INCLUDING the self chunk, rounded up to 8), while
    the emulated path (XLA:CPU, `ragged_native_ok()` False)
    routes the same rows over the padded all_to_all — padded accounting,
    the documented emulation slack `wire_bytes()` deliberately ignores.
    The [P] f32 scale hop of the quantized wires is excluded on both sides
    (same convention as `wire_bytes`)."""
    b = {"native": native_bytes, "bf16": 2, "fp8": 1, "int8": 1}[spec.wire]
    if spec.strategy == "ragged":
        if ragged_native is None:
            ragged_native = ragged_native_ok()
        if ragged_native:
            t_pad = _ragged_geometry(spec.pair_send)[3]
            return t_pad * width * b
        return spec.n_parts * spec.pad_send * width * b
    return wire_bytes(spec, width, native_bytes)


def cross_slot_wire_bytes(spec: HaloSpec, width: int,
                          native_bytes: int = 4) -> int:
    """Per-device halo bytes that actually cross WORKER boundaries under an
    elastic part->slot mapping: pairs hosted on the same slot move through
    that worker's own HBM, not the interconnect. Exact pair_send rows (no
    padding — this is the planning/obs view of a resized world's wire cost,
    not the traced operand size). With an empty slot_map (identity, worker
    == part) only the self pair is intra-slot, matching `_ragged_exact_rows`
    accounting. Returns the bottleneck slot's worst part, summed over its
    cross-slot peers."""
    b = {"native": native_bytes, "bf16": 2, "fp8": 1, "int8": 1}[spec.wire]
    P = spec.n_parts
    slots = spec.slot_map or tuple(range(P))
    S = np.asarray(spec.pair_send, dtype=np.int64).reshape(P, P)
    rows = np.zeros(P, dtype=np.int64)
    for p in range(P):
        rows[p] = sum(int(S[p, q]) for q in range(P) if slots[q] != slots[p])
    return int(rows.max()) * width * b if P else 0


# auto-selection thresholds: ragged must save >=5% of padded's cross-chip
# bytes to be worth leaving the best-tuned dense collective; shift pays P-1
# serialized hop latencies for the same bytes as ragged, so it is only
# picked when ragged is unavailable AND the skew saving is large (>=25%).
RAGGED_MIN_SAVING = 0.05
SHIFT_MIN_SAVING = 0.25


def ragged_native_ok() -> bool:
    """True when `lax.ragged_all_to_all` will lower natively here: the
    backend is TPU (the op is UNIMPLEMENTED on XLA:CPU).
    BNSGCN_RAGGED_EMULATE=1 forces the emulation path for debugging."""
    if os.environ.get("BNSGCN_RAGGED_EMULATE"):
        return False
    return jax.default_backend() == "tpu"


def ragged_auto_eligible() -> bool:
    """Whether `--halo-exchange auto` may pick 'ragged'. The emulated path is
    numerically exact everywhere but ships padded bytes PLUS pack/unpack
    gathers — strictly worse than 'padded' on any real accelerator — so auto
    only picks ragged where the native collective lowers, or on the CPU test
    mesh (bytes are fictional there and the strategy must stay selectable
    for the tier-1 suite). An explicit --halo-exchange ragged still runs the
    emulation anywhere."""
    return ragged_native_ok() or jax.default_backend() == "cpu"


def select_halo_strategy(n_b: np.ndarray, pad_inner: int, pad_boundary: int,
                         rate: float, wire: str = "native",
                         allow_ragged: bool = True) -> tuple[str, str]:
    """Resolve `--halo-exchange auto`: pick padded/shift/ragged from the
    `wire_bytes()` estimate (width/dtype cancel, so the pick is width-free)
    plus the hop-count tiebreak documented above. Returns (strategy, reason).

    Byte comparison is against padded's CROSS-CHIP rows (P-1 blocks; the
    self block never leaves the chip), not its full buffer accounting —
    otherwise ragged would "win" 1/P even on perfectly balanced partitions.
    Deterministic in the (global) n_b table: every host of a multi-host run
    resolves identically."""
    # one spec carries all three strategies' geometry (pad_send, shift_pads
    # and pair_send are derived unconditionally)
    spec = make_halo_spec(n_b, pad_inner, pad_boundary, rate, wire=wire)[0]
    P = spec.n_parts
    padded_rows = (P - 1) * spec.pad_send
    shift_rows = sum(spec.shift_pads)
    ragged_rows = _ragged_exact_rows(spec.pair_send, P)
    if P <= 1 or padded_rows == 0:
        return "padded", "single partition / empty halo"
    if allow_ragged and ragged_rows < (1.0 - RAGGED_MIN_SAVING) * padded_rows:
        return "ragged", (
            f"exact {ragged_rows} rows vs padded {padded_rows} "
            f"({ragged_rows / padded_rows:.0%}), one collective")
    if shift_rows < (1.0 - SHIFT_MIN_SAVING) * padded_rows:
        return "shift", (
            f"per-diagonal {shift_rows} rows vs padded {padded_rows} "
            f"({shift_rows / padded_rows:.0%}), worth P-1 serialized hops"
            + ("" if allow_ragged else "; ragged collective unavailable"))
    if allow_ragged:
        return "padded", (
            f"balanced boundaries (ragged {ragged_rows}/{padded_rows} rows "
            f"saves <{RAGGED_MIN_SAVING:.0%}); one dense collective")
    return "padded", (
        f"ragged collective unavailable and shift {shift_rows}/{padded_rows} "
        f"rows saves <{SHIFT_MIN_SAVING:.0%} (not worth P-1 serialized hops)")


def retune_strategy(n_b: np.ndarray, pad_inner: int, pad_boundary: int,
                    rate: float, current: str, wire: str = "native",
                    allow_ragged: Optional[bool] = None) -> Optional[tuple]:
    """The `--tune` controller's strategy re-pick: the same wire-bytes
    estimate `--halo-exchange auto` runs at launch, re-framed as "is there a
    better strategy than the one this run is EXECUTING". Returns
    ``(strategy, why)`` when the estimate prefers a different strategy, else
    None. The caller (tune.decide) only acts on it when the MEASURED epoch
    comm share is high — the estimate proposes, the measurement disposes,
    which is the difference from the launch-time pick that has nothing but
    the estimate to go on."""
    if allow_ragged is None:
        allow_ragged = ragged_auto_eligible()
    best, why = select_halo_strategy(n_b, pad_inner, pad_boundary, rate,
                                     wire=wire, allow_ragged=allow_ragged)
    if best == current:
        return None
    return best, why


@dataclass
class HaloPlan:
    """Per-epoch sampling decisions, shared by every layer's exchange
    (the reference samples once per epoch, train.py:388-390)."""
    sel: jax.Array                     # [P, S] my boundary positions to send to each peer
    weight: jax.Array                  # [P, S] f32: valid/ratio sender scaling
    slots: jax.Array                   # [P, S] int32: halo slots for received rows (trash = n_halo)
    presence: jax.Array                # [pad_inner + n_halo] bool: inner + sampled halos


def make_halo_plan(spec: HaloSpec, tables: dict, bnd: jax.Array,
                   epoch: jax.Array, base_key: jax.Array) -> HaloPlan:
    """Compute this epoch's send selection and receive scatter plan.

    `bnd`: [P, B_pad] — this device's boundary lists toward each peer
    (sharded row of artifacts.bnd). Runs inside shard_map.
    """
    P, Bp, Sp = spec.n_parts, spec.pad_boundary, spec.pad_send
    me = jax.lax.axis_index(spec.axis_name)
    peers = jnp.arange(P)

    n_send = tables["n_b"][me]                 # [P]
    s_send = tables["send_size"][me]
    n_recv = tables["n_b"][:, me]
    s_recv = tables["send_size"][:, me]

    if spec.exact:
        pos, valid = jax.vmap(lambda n: identity_sample(n, Sp))(n_send)
        rpos, rvalid = jax.vmap(lambda n: identity_sample(n, Sp))(n_recv)
    else:
        # replica-axis meshes: each replica folds its own index into the
        # pair keys, drawing an independent BNS sample from the one shared
        # base seed (both endpoints of a pair live in the same replica row,
        # so the zero-communication shared-PRNG contract is unchanged)
        rep = (jax.lax.axis_index(spec.replica_axis)
               if spec.replica_axis is not None else None)
        send_keys = jax.vmap(
            lambda j: pair_key(base_key, epoch, me, j, replica=rep))(peers)
        recv_keys = jax.vmap(
            lambda q: pair_key(base_key, epoch, q, me, replica=rep))(peers)
        pos, valid = jax.vmap(
            lambda k, n, s: pair_sample(k, n, s, Bp, Sp))(send_keys, n_send, s_send)
        rpos, rvalid = jax.vmap(
            lambda k, n, s: pair_sample(k, n, s, Bp, Sp))(recv_keys, n_recv, s_recv)

    sel = jnp.take_along_axis(bnd, pos.astype(bnd.dtype), axis=1)          # [P, S]
    weight = jnp.where(valid, tables["inv_ratio"][me][:, None], 0.0)       # [P, S]
    slots = jnp.where(rvalid, peers[:, None] * Bp + rpos, spec.n_halo)     # [P, S]

    presence = jnp.zeros(spec.n_halo + 1, dtype=bool).at[slots.reshape(-1)].set(True)
    presence = jnp.concatenate(
        [jnp.ones(spec.pad_inner, dtype=bool), presence[:-1]])
    return HaloPlan(sel=sel, weight=weight, slots=slots, presence=presence)


# ----------------------------------------------------------------------------
# staleness-bounded refresh (--halo-refresh K): epoch e re-exchanges only the
# boundary positions {k : k % K == e % K} of every pair ("chunk" e % K), so
# the per-epoch wire bytes drop ~K x while every halo row is at most K-1
# epochs stale, with staleness staggered across rows instead of cliffing all
# at once. The partial exchange reuses halo_start/halo_finish UNCHANGED: only
# the spec geometry (sized to the largest chunk) and the plan (chunk-domain
# draws mapped back to full boundary positions) differ, so all three
# strategies x four wire codecs compose for free.
# ----------------------------------------------------------------------------

def make_refresh_spec(n_b: np.ndarray, pad_inner: int, pad_boundary: int,
                      rate: float, refresh: int, axis_name: str = "parts",
                      strategy: str = "padded", wire: str = "native",
                      replica_axis: str | None = None,
                      slot_map=None
                      ) -> tuple[HaloSpec, dict]:
    """Geometry + tables for the --halo-refresh K partial exchange.

    The spec keeps the FULL pad_boundary (halo slot layout — and therefore
    n_halo and the cache buffer shape — identical to the full exchange's),
    but pad_send / shift_pads / pair_send are sized to the largest chunk, so
    `wire_bytes(spec)` reports the true steady-state cost. Tables are
    [K, P, P] chunk-major device arrays; the plan builder dynamically
    indexes them with the traced chunk e % K.

    Per-chunk inv_ratio = n_bc / s_c keeps each refreshed chunk an unbiased
    estimate of ITS slice of the boundary sum; mixed with cached rows drawn
    under earlier epochs' keys, the steady-state halo buffer remains an
    unbiased (stale) estimate of the full boundary aggregation — and exact
    at rate 1.0, where K > 1 differs from the per-epoch exchange only
    through staleness. At K=1 the tables and geometry reduce bit-identically
    to `make_halo_spec`'s."""
    K = int(refresh)
    assert K >= 1, f"halo refresh period must be >= 1, got {K}"
    n_b = np.asarray(n_b, dtype=np.int64)
    P = n_b.shape[0]
    exact = rate >= 1.0
    c_idx = np.arange(K, dtype=np.int64).reshape(K, 1, 1)
    # |{k in [0, n_b) : k % K == c}| — per-chunk boundary counts [K, P, P]
    n_bc = (np.maximum(n_b[None] - c_idx, 0) + K - 1) // K
    if exact:
        s_c = n_bc
    else:
        # floor(rate * chunk) like the full path, but never 0 for a pair the
        # full exchange serves: a permanently silent chunk would bias the
        # steady-state aggregation instead of merely adding variance
        full_send = (rate * n_b).astype(np.int64)
        s_c = np.where((n_bc > 0) & (full_send[None] > 0),
                       np.maximum((rate * n_bc).astype(np.int64), 1), 0)
    ratio_c = np.where(n_bc > 0, s_c / np.maximum(n_bc, 1), 0.0)
    inv_ratio_c = np.where(ratio_c > 0, 1.0 / np.maximum(ratio_c, 1e-30), 0.0)
    pair_send = s_c.max(axis=0)                    # [P, P] worst chunk per pair
    pad_b_chunk = (pad_boundary + K - 1) // K      # chunk-domain boundary pad
    # NO x8 lane rounding here, unlike make_halo_spec: chunk sends are small
    # and rounding up would erase exactly the ~K x byte saving the refresh
    # mode exists for (round8(ceil(s/K)) == round8(s) for modest s)
    pad_send = max(1, int(pair_send.max())) if pair_send.size else 1
    pad_send = min(pad_send, max(pad_b_chunk, 1))
    shift_pads = []
    for k in range(1, P):
        m = int(max(pair_send[p, (p + k) % P] for p in range(P)))
        shift_pads.append(0 if m == 0 else min(m, pad_send))
    assert strategy in ("padded", "shift", "ragged"), (
        f"unresolved halo strategy {strategy!r} (resolve 'auto' via "
        f"select_halo_strategy before make_refresh_spec)")
    spec = HaloSpec(
        n_parts=P, pad_inner=pad_inner, pad_boundary=pad_boundary,
        pad_send=pad_send, axis_name=axis_name, exact=exact,
        strategy=strategy, wire=wire, shift_pads=tuple(shift_pads),
        pair_send=tuple(map(tuple, pair_send.tolist())),
        replica_axis=replica_axis,
        slot_map=tuple(int(s) for s in (slot_map or ())),
    )
    tables = {"n_b": jnp.asarray(n_bc, jnp.int32),
              "send_size": jnp.asarray(s_c, jnp.int32),
              "inv_ratio": jnp.asarray(inv_ratio_c, jnp.float32)}
    return spec, tables


def make_halo_plan_refresh(spec: HaloSpec, tables: dict, bnd: jax.Array,
                           epoch: jax.Array, base_key: jax.Array,
                           refresh: int) -> HaloPlan:
    """This epoch's PARTIAL send/scatter plan under --halo-refresh K.

    Chunk c = epoch % K of every boundary list is redrawn through the SAME
    `pair_key` stream as the full plan — deterministic per (epoch, pair,
    replica, nonce) with zero index communication, exactly like BNS.
    `spec`/`tables` come from `make_refresh_spec`; slots and presence live
    in the FULL pad_boundary slot layout, so `halo_finish`'s buffer drops
    straight into the cache and this plan's presence covers ONLY the
    refreshed chunk's halo rows (the caller merges it with the cached
    presence). Runs inside shard_map, like `make_halo_plan`."""
    K = int(refresh)
    P, Bp, Sp = spec.n_parts, spec.pad_boundary, spec.pad_send
    Bp_c = (Bp + K - 1) // K
    c = jax.lax.rem(epoch.astype(jnp.uint32), jnp.uint32(K)).astype(jnp.int32)
    me = jax.lax.axis_index(spec.axis_name)
    peers = jnp.arange(P)

    n_b_c = tables["n_b"][c]                   # [P, P] this chunk's counts
    s_c = tables["send_size"][c]
    n_send, s_send = n_b_c[me], s_c[me]
    n_recv, s_recv = n_b_c[:, me], s_c[:, me]

    if spec.exact:
        pos, valid = jax.vmap(
            lambda n: chunk_identity_sample(n, c, K, Sp))(n_send)
        rpos, rvalid = jax.vmap(
            lambda n: chunk_identity_sample(n, c, K, Sp))(n_recv)
    else:
        rep = (jax.lax.axis_index(spec.replica_axis)
               if spec.replica_axis is not None else None)
        send_keys = jax.vmap(
            lambda j: pair_key(base_key, epoch, me, j, replica=rep))(peers)
        recv_keys = jax.vmap(
            lambda q: pair_key(base_key, epoch, q, me, replica=rep))(peers)
        pos, valid = jax.vmap(
            lambda k, n, s: chunk_sample(k, n, s, c, K, Bp_c, Sp))(
                send_keys, n_send, s_send)
        rpos, rvalid = jax.vmap(
            lambda k, n, s: chunk_sample(k, n, s, c, K, Bp_c, Sp))(
                recv_keys, n_recv, s_recv)

    # invalid rows carry chunk-domain padding positions that can map past
    # Bp; clamp them into range — their weight is 0 and their slot is trash,
    # so the clamped gather/scatter targets are never observed
    pos = jnp.minimum(pos, Bp - 1)
    rpos = jnp.minimum(rpos, Bp - 1)
    sel = jnp.take_along_axis(bnd, pos.astype(bnd.dtype), axis=1)          # [P, S]
    weight = jnp.where(valid, tables["inv_ratio"][c][me][:, None], 0.0)    # [P, S]
    slots = jnp.where(rvalid, peers[:, None] * Bp + rpos, spec.n_halo)     # [P, S]

    presence = jnp.zeros(spec.n_halo + 1, dtype=bool).at[slots.reshape(-1)].set(True)
    presence = jnp.concatenate(
        [jnp.ones(spec.pad_inner, dtype=bool), presence[:-1]])
    return HaloPlan(sel=sel, weight=weight, slots=slots, presence=presence)


def refresh_row_mask(spec: HaloSpec, refresh: int, epoch: jax.Array) -> jax.Array:
    """[n_halo] bool: halo slots whose boundary position belongs to this
    epoch's refresh chunk. Slot q*pad_boundary + k refreshes iff
    k % K == epoch % K; the cached step keeps every other slot's stored
    (stop-gradient) rows."""
    K = jnp.uint32(refresh)
    c = jax.lax.rem(epoch.astype(jnp.uint32), K)
    k = jnp.arange(spec.n_halo, dtype=jnp.uint32) % jnp.uint32(spec.pad_boundary)
    return (k % K) == c


# ----------------------------------------------------------------------------
# wire codec: quantize per (sender, peer) block for the interconnect hop only.
# fp8 rides float8_e4m3fn with one f32 scale per block; gradients on the
# backward hop get their OWN scales (activation scales would under/overflow
# gradient magnitudes — the standard fp8-comm pitfall).
# ----------------------------------------------------------------------------

def _quant(x: jax.Array, wire: str):
    """x [..., S, d] -> (payload, scales or None); scales over the last two axes."""
    if wire == "bf16":
        return x.astype(jnp.bfloat16), None
    if wire == "int8":
        # v5e-native 1-byte wire: the convert is hardware, unlike e4m3
        # decode (emulated; measured slower than bf16 in the SpMM gather)
        from bnsgcn_tpu.utils.quant import i8_quant
        return i8_quant(x, axes=(-2, -1))
    from bnsgcn_tpu.utils.quant import f8_quant
    return f8_quant(x, axes=(-2, -1))


def _dequant(payload: jax.Array, scale, dtype):
    if scale is None:
        return payload.astype(dtype)
    from bnsgcn_tpu.utils.quant import f8_dequant
    return f8_dequant(payload, scale, dtype)


def _a2a_wire_impl(spec: HaloSpec, send: jax.Array) -> jax.Array:
    P, S, d = send.shape
    payload, scale = _quant(send, spec.wire)
    recv = jax.lax.all_to_all(payload.reshape(P * S, d), spec.axis_name,
                              0, 0, tiled=True).reshape(P, S, d)
    rscale = None
    if scale is not None:
        rscale = jax.lax.all_to_all(scale.reshape(P, 1), spec.axis_name,
                                    0, 0, tiled=True).reshape(P, 1, 1)
    return _dequant(recv, rscale, send.dtype)


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _a2a_wire(spec: HaloSpec, send: jax.Array) -> jax.Array:
    return _a2a_wire_impl(spec, send)


def _a2a_wire_fwd(spec, send):
    return _a2a_wire_impl(spec, send), None


def _a2a_wire_bwd(spec, _, g):
    # tiled all_to_all is an involution: the same call routes each received
    # block's cotangent back to its sender, re-quantized with g's own scales
    return (_a2a_wire_impl(spec, g),)


_a2a_wire.defvjp(_a2a_wire_fwd, _a2a_wire_bwd)


def _ppermute_wire_impl(spec: HaloSpec, k: int, send: jax.Array) -> jax.Array:
    P = spec.n_parts
    perm = [(i, (i + k) % P) for i in range(P)]
    payload, scale = _quant(send, spec.wire)
    recv = jax.lax.ppermute(payload, spec.axis_name, perm)
    rscale = None
    if scale is not None:
        rscale = jax.lax.ppermute(scale, spec.axis_name, perm)
    return _dequant(recv, rscale, send.dtype)


@partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _ppermute_wire(spec: HaloSpec, k: int, send: jax.Array) -> jax.Array:
    return _ppermute_wire_impl(spec, k, send)


def _ppermute_wire_fwd(spec, k, send):
    return _ppermute_wire_impl(spec, k, send), None


def _ppermute_wire_bwd(spec, k, _, g):
    return (_ppermute_wire_impl(spec, spec.n_parts - k, g),)


_ppermute_wire.defvjp(_ppermute_wire_fwd, _ppermute_wire_bwd)


# ----------------------------------------------------------------------------
# 'ragged' strategy: ONE collective carrying each pair's exact send_size[p,j]
# rows. All geometry (offsets, sizes, buffer bounds) is derived from the
# static pair_send table, so the per-device offset vectors are plain gathers
# of trace-time constants by axis_index — exactly the static-shape discipline
# the padded path established, minus its padding bytes.
# ----------------------------------------------------------------------------

@lru_cache(maxsize=None)
def _ragged_geometry(sizes: tuple):
    """(S, in_off, recv_off, T_pad, R_pad) for a [P][P] pair-size tuple.

    S[p][j]     rows p sends j;
    in_off[p]   exclusive row-cumsum of S[p] — chunk offsets in p's operand;
    recv_off[p] exclusive row-cumsum of S[:,p] — chunk offsets in p's output;
    T_pad/R_pad lane-aligned uniform operand/output bounds (SPMD shapes must
    agree across devices; the ragged sizes say how much of each is real)."""
    S = np.asarray(sizes, dtype=np.int64)
    in_off = np.zeros_like(S)
    in_off[:, 1:] = np.cumsum(S, axis=1)[:, :-1]
    R = np.ascontiguousarray(S.T)
    recv_off = np.zeros_like(R)
    recv_off[:, 1:] = np.cumsum(R, axis=1)[:, :-1]
    pad8 = lambda n: max(8, ((int(n) + 7) // 8) * 8)
    return (S, in_off, recv_off,
            pad8(S.sum(axis=1).max()), pad8(R.sum(axis=1).max()))


def _transpose_sizes(sizes: tuple) -> tuple:
    return tuple(zip(*sizes))


def _ragged_pack(off_row, size_row, n_pad: int, blocks: jax.Array) -> jax.Array:
    """[P, S, d] per-peer blocks -> [n_pad, d] ragged buffer: chunk j's rows
    land contiguously at off_row[j]; slack rows are zero."""
    P, S, d = blocks.shape
    t = jnp.arange(n_pad)
    j = jnp.clip(jnp.searchsorted(off_row, t, side="right") - 1, 0, P - 1)
    i = t - off_row[j]
    src = jnp.where(i < size_row[j], j * S + i, P * S)
    flat = jnp.concatenate(
        [blocks.reshape(P * S, d), jnp.zeros((1, d), blocks.dtype)])
    return flat[src]


def _ragged_unpack(off_row, size_row, S: int, buf: jax.Array) -> jax.Array:
    """Inverse of `_ragged_pack`: [n_pad, d] -> [P, S, d]; rows beyond each
    chunk's ragged size come back zero."""
    n_pad, d = buf.shape
    P = off_row.shape[0]
    i = jnp.arange(S)
    idx = jnp.where(i[None, :] < size_row[:, None],
                    off_row[:, None] + i[None, :], n_pad)
    flat = jnp.concatenate([buf, jnp.zeros((1, d), buf.dtype)])
    return flat[idx.reshape(-1)].reshape(P, S, d)


def _ragged_a2a(spec: HaloSpec, sizes: tuple, payload: jax.Array) -> jax.Array:
    """The ragged collective with a block interface: [P, S, d] per-peer send
    blocks -> [P, S, d] per-sender recv blocks (rows >= sizes[q][me] zero).

    Native path: pack to the ragged operand and issue ONE
    `lax.ragged_all_to_all` (lowered on a v5e at axis size 1, 2026-07-30).
    Emulated path (XLA:CPU): the same pack/unpack geometry wrapped
    around a padded all_to_all — identical numerics, so the CPU mesh tests
    exercise the real offset math even where the op cannot lower."""
    P, S, d = payload.shape
    S_mat, in_off, recv_off, T_pad, R_pad = _ragged_geometry(sizes)
    me = jax.lax.axis_index(spec.axis_name)
    in_off_d = jnp.asarray(in_off, jnp.int32)[me]          # [P]
    send_d = jnp.asarray(S_mat, jnp.int32)[me]             # [P] rows to peer j
    recv_off_d = jnp.asarray(recv_off, jnp.int32)[me]      # [P]
    recv_d = jnp.asarray(S_mat.T, jnp.int32)[me]           # [P] rows from q
    operand = _ragged_pack(in_off_d, send_d, T_pad, payload)
    if ragged_native_ok():
        # output_offsets[j] = where MY chunk lands on receiver j
        out_off_d = jnp.asarray(recv_off.T, jnp.int32)[me]
        output = jnp.zeros((R_pad, d), payload.dtype)
        out = jax.lax.ragged_all_to_all(
            operand, output, in_off_d, send_d, out_off_d, recv_d,
            axis_name=spec.axis_name)
    else:
        blocks = _ragged_unpack(in_off_d, send_d, S, operand)
        recvb = jax.lax.all_to_all(blocks.reshape(P * S, d), spec.axis_name,
                                   0, 0, tiled=True).reshape(P, S, d)
        out = _ragged_pack(recv_off_d, recv_d, R_pad, recvb)
    return _ragged_unpack(recv_off_d, recv_d, S, out)


def _ragged_wire_impl(spec: HaloSpec, sizes: tuple, send: jax.Array) -> jax.Array:
    P = send.shape[0]
    if spec.wire == "native":
        payload, scale = send, None
    else:
        payload, scale = _quant(send, spec.wire)
    recv = _ragged_a2a(spec, sizes, payload)
    rscale = None
    if scale is not None:
        # per-(sender, peer) block scales ride a tiny dense all_to_all, as
        # on the padded path (P floats vs megabytes of rows)
        rscale = jax.lax.all_to_all(scale.reshape(P, 1), spec.axis_name,
                                    0, 0, tiled=True).reshape(P, 1, 1)
    return _dequant(recv, rscale, send.dtype)


@partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _ragged_wire(spec: HaloSpec, sizes: tuple, send: jax.Array) -> jax.Array:
    return _ragged_wire_impl(spec, sizes, send)


def _ragged_wire_fwd(spec, sizes, send):
    return _ragged_wire_impl(spec, sizes, send), None


def _ragged_wire_bwd(spec, sizes, _, g):
    # the transpose of a ragged all_to_all is the ragged all_to_all with the
    # pair-size matrix transposed: the cotangent of what I received from q
    # (S[q][me] rows) routes back to q. Quantized wires re-quantize g with
    # its OWN scales, never the activations' (the fp8-comm pitfall).
    return (_ragged_wire_impl(spec, _transpose_sizes(sizes), g),)


_ragged_wire.defvjp(_ragged_wire_fwd, _ragged_wire_bwd)


def halo_start(spec: HaloSpec, plan: HaloPlan, h: jax.Array):
    """Dispatch one layer's halo exchange WITHOUT consuming its result.

    Returns the in-flight received payload (a pytree of arrays: one
    [P*S_pad, d] buffer for 'padded'/'ragged', a tuple of per-round blocks
    for 'shift') to be scattered into halo slots by `halo_finish`. Nothing
    here depends on any aggregation output, and nothing downstream of the
    caller's independent (interior) compute depends on this value — that
    dependence gap is what lets the XLA latency-hiding scheduler run the
    collective concurrently with interior SpMM work (`--overlap split`).

    Composes with all three strategies and all four wire codecs; AD through
    start+finish is exactly halo_apply's transpose (the custom-vjp wire hops
    sit inside), so gradients re-quantize with their own scales as before.
    """
    P, Sp, d = spec.n_parts, spec.pad_send, h.shape[-1]
    if spec.strategy == "shift" and P > 1:
        me = jax.lax.axis_index(spec.axis_name)
        recvs = []
        for k in range(1, P):
            Sk = spec.shift_pads[k - 1]
            if Sk == 0:
                continue                       # no pair on this diagonal sends
            to = (me + k) % P                  # peer I send to this round
            sel_k = jax.lax.dynamic_index_in_dim(plan.sel, to, 0, False)[:Sk]
            w_k = jax.lax.dynamic_index_in_dim(plan.weight, to, 0, False)[:Sk]
            send = (h[sel_k] * w_k[:, None]).astype(h.dtype)       # [Sk, d]
            if spec.wire == "native":
                perm = [(i, (i + k) % P) for i in range(P)]
                recv = jax.lax.ppermute(send, spec.axis_name, perm)
            else:
                recv = _ppermute_wire(spec, k, send)
            recvs.append(recv)
        return tuple(recvs)

    # keep the payload in h's dtype: weight is f32, and bf16*f32 would promote
    # (doubling the wire bytes and tripping the bf16 scatter in halo_finish)
    send = (h[plan.sel] * plan.weight[..., None]).astype(h.dtype)  # [P, S, d]
    if spec.strategy == "ragged":
        # exact per-pair rows in ONE collective (runs even at P=1 so a
        # single-chip bench measures the real dispatch cost); the valid
        # sample rows are the FIRST send_size[me, j] of each S_pad block
        # (sampling.pair_sample contract), which is what makes the ragged
        # chunks contiguous prefixes
        return _ragged_wire(spec, spec.pair_send, send).reshape(P * Sp, d)
    # padded: one tiled all_to_all, uniform S_pad per pair
    if spec.wire == "native":
        return jax.lax.all_to_all(send.reshape(P * Sp, d), spec.axis_name,
                                  0, 0, tiled=True)             # [P*S, d]
    return _a2a_wire(spec, send).reshape(P * Sp, d)


def halo_finish(spec: HaloSpec, plan: HaloPlan, recv, like: jax.Array
                ) -> jax.Array:
    """Scatter `halo_start`'s received payload into the fixed per-peer halo
    slot blocks. Returns the halo buffer [n_halo, d] (NOT concatenated with
    the inner rows — the overlap-split caller scales/concatenates itself).
    `like` supplies only the static feature width and dtype; no data
    dependency on it is introduced."""
    P = spec.n_parts
    buf = jnp.zeros((spec.n_halo + 1, like.shape[-1]), dtype=like.dtype)
    if spec.strategy == "shift" and P > 1:
        me = jax.lax.axis_index(spec.axis_name)
        i = 0
        for k in range(1, P):
            Sk = spec.shift_pads[k - 1]
            if Sk == 0:
                continue                       # matches halo_start's rounds
            frm = (me - k) % P                 # peer I receive from
            slots_k = jax.lax.dynamic_index_in_dim(plan.slots, frm, 0, False)[:Sk]
            buf = buf.at[slots_k].add(recv[i])
            i += 1
        return buf[:-1]
    buf = buf.at[plan.slots.reshape(-1)].add(recv)
    return buf[:-1]


def halo_apply(spec: HaloSpec, plan: HaloPlan, h: jax.Array) -> jax.Array:
    """One layer's halo exchange: h [pad_inner, d] -> h_ext [pad_inner + n_halo, d].

    Fully differentiable; the AD transpose is the reference's backward
    all-to-all with scatter-add x (1/ratio) (helper/feature_buffer.py:119-129).
    The wire codec hops carry custom VJPs so fp8/bf16 compression applies to
    both directions with direction-appropriate scales.

    Implemented as halo_start + halo_finish (the `--overlap split` seam) so
    the fused and split paths share one collective implementation and cannot
    drift numerically.
    """
    recv = halo_start(spec, plan, h)
    return jnp.concatenate([h, halo_finish(spec, plan, recv, h)], axis=0)


def sampled_presence(spec: HaloSpec, plan: HaloPlan) -> jax.Array:
    """[pad_inner + n_halo] bool — which extended rows are live this epoch
    (GAT masks absent halos out of its edge softmax with this)."""
    return plan.presence


def full_rate_spec(n_b: np.ndarray, pad_inner: int, pad_boundary: int,
                   axis_name: str = "parts") -> tuple[HaloSpec, dict]:
    """rate-1.0 (spec, tables) used by the precompute exchange (train.py:170-189)."""
    return make_halo_spec(n_b, pad_inner, pad_boundary, 1.0, axis_name)


def precompute_exchange(spec_full: HaloSpec, tables_full: dict,
                        bnd: jax.Array, feat: jax.Array) -> jax.Array:
    """One full-rate exchange of raw input features at setup (`use_pp`,
    reference precompute train.py:170-189). Returns feat_ext
    [pad_inner + n_halo, F]; aggregation per model is done by the caller."""
    zero = jnp.zeros((), dtype=jnp.uint32)
    plan = make_halo_plan(spec_full, tables_full, bnd, zero,
                          # graftlint: disable=prng-literal-key(exact plan: key is a dead argument)
                          jax.random.key(0))  # exact => key unused
    return halo_apply(spec_full, plan, feat)
