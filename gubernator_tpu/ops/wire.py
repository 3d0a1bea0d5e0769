"""Compact host↔device wire codec for the serving hot path.

BENCH_r05 attributed the whole remaining sharded-dispatch gap (~2.3 s wall
vs ~10 ms device) to host↔device transport: every dispatch ships a
(12, B) int64 ingress grid (96 B/row) and fetches a (B+2, 4) int64 output
(32 B/row) over a link where bytes are the budget. This module shrinks both
directions with an in-trace-decoded packed layout:

**Ingress — 5 int32 lanes (20 B/row) + one trailing base column:**

  lane 0  fp_lo          low 32 bits of the fingerprint
  lane 1  fp_hi          high 32 bits (fp == 0 ⇒ inactive row — the packing
                         invariant every serving path already maintains)
  lane 2  limit          full int32 (front-door validated to int32)
  lane 3  duration[0:27] | algo << 27 (3 bits) | cascade_level << 30 (2 bits)
  lane 4  hits[0:18] | (created_delta + 512) << 18 | priority << 28
          | RESET << 30 | DRAIN << 31

  column B (the +1): cells [0, B], [1, B] carry the batch's created_at BASE
  (lo/hi int32) — every other per-row timestamp decodes as base-relative.

The decode (decode_wire_block) reconstructs the full 12-column int64 ingress
array INSIDE the kernel's jit, where the redundant fields are recomputed
instead of shipped: created_at = base + delta, expire_new = created +
duration, duration_eff = duration, greg_interval = 0, burst = limit for
leaky and GCRA rows (the burst==0→limit defaulting both algorithms' packs
apply), 0 otherwise (no other algorithm reads burst — ops/math.py).
Behavior ships as exactly the two bits the decision math consumes
(RESET_REMAINING, DRAIN_OVER_LIMIT) plus the 2-bit cascade level (levels
above CASCADE_WIRE_MAX_LEVEL ride full-width) and the 2-bit priority tier
(types.PRIORITY_SHIFT — the overload plane's QoS field, echoed back in the
egress flags); kernel-inert bits (NO_BATCHING, GLOBAL, MULTI_REGION) are
dropped on the wire.

The algo field grew from 2 to 3 bits (the five in-kernel algorithms) and
the cascade level took the remaining 2, paid for by narrowing the duration
budget from 2^30 to 2^27 ms (~37 hours — daily quotas still fit; multi-day
windows fall back to full-width, exactly like weekly ones always did).
The priority tier was paid for the same way: the created-at delta budget
narrowed from ±2047 to ±511 ms of the batch base — serving batches stamp
one ingress `now` over the whole batch (delta 0), so only client-supplied
created_at beyond half a second of skew falls back to full-width.

**Egress — (B+2, 4) int32 (16 B/row), same row layout as kernel2.pack_outputs:**

  row i < B   [limit, remaining (saturating i32), reset_delta, flags]
  row B       [cache_hits, cache_misses, over_limit, evicted]  (counts ≤ B)
  row B+1     [dropped, base_lo, base_hi, 0]

reset_delta = reset_time - base, with -2^31 reserved as the "reset==0"
sentinel so inactive/removed rows round-trip exactly; the base rides in the
spare stats cells, making the fetched array self-describing (unpack_outputs
dispatches on dtype alone). Host-side decode is vectorized numpy.

**Fallback contract.** Not every batch is representable (Gregorian
durations, hits ≥ 2^18, durations ≥ 2^30 ms, created_at skew beyond
±511 ms of the batch base, negative limits, explicit leaky bursts).
`wire_encodable` checks a batch host-side in a handful of vectorized
passes; non-encodable dispatches take the full-width path — identical
semantics, more bytes — and `GUBER_WIRE_COMPACT=0` forces full-width
everywhere, which is the parity oracle every compact test and bench smoke
compares against row-for-row.
"""

from __future__ import annotations

import functools
import os
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from gubernator_tpu.ops.batch import HostBatch
from gubernator_tpu.ops.kernel2 import (
    FLAG_DROPPED,
    FLAG_HIT,
    FLAG_STATUS,
    _hi32,
    _join64,
    _lo32,
    decide2_packed_cols_impl,
    decide2_packed_dedup_impl,
)

i32 = jnp.int32
i64 = jnp.int64

WIRE_LANES = 5  # ingress int32 lanes per row (20 B) — + 1 base column/grid
WIRE_EGRESS_ROW_BYTES = 16  # (·, 4) int32 egress rows
DUR_BITS = 27  # duration < 2^27 ms (~37 hours); beyond → full-width
ALGO_BITS = 3  # five in-kernel algorithms (types.Algorithm)
LEVEL_SHIFT = DUR_BITS + ALGO_BITS  # cascade level, 2 bits (30, 31)
LEVEL_MAX = 3  # types.CASCADE_WIRE_MAX_LEVEL — deeper cascades → full-width
HITS_BITS = 18  # hits in [0, 2^18) — covers host-aggregated 131K-row carriers
DELTA_BITS = 10  # created_at - base in [-512, 511] ms
DELTA_BIAS = 1 << (DELTA_BITS - 1)
PRIO_SHIFT = HITS_BITS + DELTA_BITS  # priority tier, 2 bits (28, 29)
_DUR_MASK = (1 << DUR_BITS) - 1
_ALGO_MASK = (1 << ALGO_BITS) - 1
_HITS_MASK = (1 << HITS_BITS) - 1
_DELTA_MASK = (1 << DELTA_BITS) - 1
RESET_SENTINEL = -(2**31)  # egress reset_delta value for reset_time == 0
# behavior-word cascade level field (types.CASCADE_LEVEL_SHIFT)
_BEH_LEVEL_SHIFT = 8
# behavior-word priority tier field (types.PRIORITY_SHIFT)
_BEH_PRIO_SHIFT = 6
_MAX_ALGO = 4  # types.MAX_ALGORITHM — wire-encodable algorithm range

# Behavior bits (gubernator_tpu.types.Behavior values, frozen by the proto)
_RESET = 8  # RESET_REMAINING — consumed by the decision math
_DRAIN = 32  # DRAIN_OVER_LIMIT — consumed by the decision math
_GREG = 4  # DURATION_IS_GREGORIAN — host-resolved; forces full-width
# bits the kernel never reads (ops/math.py) — safe to drop on the wire
_INERT = 1 | 2 | 16  # NO_BATCHING | GLOBAL | MULTI_REGION
_PRIO_BEH = 0x3 << _BEH_PRIO_SHIFT  # priority tier — carried in lane 4
_ENCODABLE_BEHAVIOR = _RESET | _DRAIN | _INERT | _PRIO_BEH

I32_MAX = 2**31 - 1


def default_wire_mode() -> str:
    """Compact wire grids on real TPU (where host↔device bytes are the
    serving bottleneck), full-width elsewhere (CPU test meshes keep the
    seed suite's exact transfer shapes). GUBER_WIRE_COMPACT=1/0 forces
    either mode; per-engine `wire=` overrides both."""
    env = os.environ.get("GUBER_WIRE_COMPACT")
    if env is not None:
        return "compact" if env not in ("0", "false", "off") else "full"
    return "compact" if jax.default_backend() == "tpu" else "full"


# ------------------------------------------------------------- host encode


def pick_base(b: HostBatch) -> int:
    """The batch's created_at base: the first active row's stamp. Serving
    batches stamp every unset created_at with one ingress `now`
    (ops/batch.pack_columns), so per-row deltas are 0; rows skewed beyond
    the delta budget fail wire_encodable and take the full-width path."""
    act = np.asarray(b.active)
    if not act.any():
        return 0
    return int(b.created_at[int(np.argmax(act))])


def wire_encodable(b: HostBatch, base: int) -> bool:
    """Can this batch ride the compact wire exactly? A handful of
    vectorized passes over the active rows — cheap against the pack it
    gates. Every check guards a field the compact layout narrows or
    recomputes; failing any one falls the dispatch back to full-width
    (same semantics, more bytes), so this is a perf decision, never a
    correctness one."""
    act = np.asarray(b.active)
    if not act.any():
        return True
    fp = b.fp[act]
    if (fp == 0).any():
        return False  # active ⟺ fp != 0 is the decode's activity rule
    beh = b.behavior[act].astype(np.int64)
    # bits 0..7 are behavior flags, 8..15 the cascade level (compact lane
    # carries 2 level bits); anything above is unknown → full-width
    if (beh & ~np.int64((0xFF << _BEH_LEVEL_SHIFT) | _ENCODABLE_BEHAVIOR)).any():
        return False  # Gregorian (host-resolved calendar fields) or unknown
    lvl = (beh >> _BEH_LEVEL_SHIFT) & 0xFF
    if (lvl > LEVEL_MAX).any():
        return False  # cascade deeper than the 2-bit lane budget
    if (b.greg_interval[act] != 0).any():
        return False
    dur = b.duration[act]
    if ((dur < 0) | (dur > _DUR_MASK)).any():
        return False
    if (b.duration_eff[act] != dur).any():
        return False
    created = b.created_at[act]
    if (b.expire_new[act] != created + dur).any():
        return False  # expire recomputes in-trace only for the linear rule
    delta = created - base
    if ((delta < -DELTA_BIAS) | (delta > DELTA_BIAS - 1)).any():
        return False
    hits = b.hits[act]
    if ((hits < 0) | (hits > _HITS_MASK)).any():
        return False  # negative hits (lease releases) ride full-width
    limit = b.limit[act]
    if ((limit < 0) | (limit > I32_MAX)).any():
        return False  # negative limits keep the full-width path's exact
        # (pathological) arithmetic; positive is the serving domain
    algo = b.algo[act]
    if ((algo < 0) | (algo > _MAX_ALGO)).any():
        return False
    burst = b.burst[act]
    bursty = (algo == 1) | (algo == 2)  # leaky / GCRA: burst lane-derived
    if bursty.any() and (burst[bursty] != limit[bursty]).any():
        return False  # burst defaults to limit (pack rule); explicit
        # bursts are rare enough to ship full-width
    nob = (algo == 3) | (algo == 4)  # window / lease: burst unused, keep 0
    if nob.any() and (burst[nob] != 0).any():
        return False
    return True


def pack_wire_rows(
    b: HostBatch, base: int, out: "np.ndarray | None" = None
) -> np.ndarray:
    """Pack a (wire_encodable) HostBatch into (5, n) int32 data lanes.
    Inactive rows encode as all-zero columns (fp == 0 ⇒ inactive on
    decode). `out` packs straight into pooled staging memory."""
    n = b.fp.shape[0]
    if out is None:
        arr = np.empty((WIRE_LANES, n), dtype=np.int32)
    else:
        assert out.shape == (WIRE_LANES, n) and out.dtype == np.int32
        arr = out
    act = b.active
    fp = np.where(act, b.fp, 0)
    arr[0] = fp.astype(np.int64).astype(np.int32)  # low 32, wrap cast
    arr[1] = (fp >> 32).astype(np.int32)
    arr[2] = np.where(act, b.limit, 0).astype(np.int32)
    lvl = (b.behavior.astype(np.int64) >> _BEH_LEVEL_SHIFT) & 0xFF
    l3 = (
        (b.duration & _DUR_MASK)
        | (b.algo.astype(np.int64) << DUR_BITS)
        | (lvl << LEVEL_SHIFT)
    )
    arr[3] = np.where(act, l3, 0).astype(np.int64).astype(np.int32)
    reset = (b.behavior & _RESET) != 0
    drain = (b.behavior & _DRAIN) != 0
    prio = (b.behavior.astype(np.int64) >> _BEH_PRIO_SHIFT) & 0x3
    l4 = (
        (b.hits & _HITS_MASK)
        | (((b.created_at - base + DELTA_BIAS) & _DELTA_MASK) << HITS_BITS)
        | (prio << PRIO_SHIFT)
        | (reset.astype(np.int64) << 30)
        | (drain.astype(np.int64) << 31)
    )
    arr[4] = np.where(act, l4, 0).astype(np.int64).astype(np.int32)
    return arr


def pack_wire_full(
    b: HostBatch, base: int, out: "np.ndarray | None" = None
) -> np.ndarray:
    """(5, n+1) int32: data lanes plus the trailing base column — the
    single-device / single-block ingress form (mesh engines scatter
    pack_wire_rows into their own grids and stamp the base per block)."""
    n = b.fp.shape[0]
    if out is None:
        arr = np.zeros((WIRE_LANES, n + 1), dtype=np.int32)
    else:
        assert out.shape == (WIRE_LANES, n + 1) and out.dtype == np.int32
        arr = out
        arr[:, n] = 0
    pack_wire_rows(b, base, out=arr[:, :n])
    stamp_base(arr, base)
    return arr


def assemble_wire_grid(
    lane_parts: "list[np.ndarray]",
    created: np.ndarray,
    base: int,
    pad: int,
    active: np.ndarray,
) -> np.ndarray:
    """Fused front-door staging: scatter pre-packed per-request lane blocks
    (the native parser's (5, n_i) int32 images, created-delta bits zero)
    into ONE padded (5, pad+1) ingress grid, OR the batch-relative created
    deltas into lane 4, and stamp the base column. This single scatter IS
    the staging — no RequestColumns concat, no 12-column HostBatch pack, no
    second wire pack; the request bytes were traversed exactly once, by the
    parser. `created` holds the stamped absolute created_at over the
    concatenated rows; callers verify the delta budget (±511 ms of `base`)
    before assembling."""
    grid = np.zeros((WIRE_LANES, pad + 1), dtype=np.int32)
    off = sum(lanes.shape[1] for lanes in lane_parts)
    np.concatenate(lane_parts, axis=1, out=grid[:, :off])
    delta32 = (
        ((created - base + DELTA_BIAS) & _DELTA_MASK) << HITS_BITS
    ).astype(np.int32)
    grid[4, :off] |= np.where(active, delta32, np.int32(0))
    stamp_base(grid, base)
    return grid


def grid_math_mode(grid: np.ndarray, n: int) -> str:
    """Static kernel math variant for an assembled wire grid — the
    lane-level twin of engine._math_mode: all-token → the token-only
    graph, a leaky row → the mixed (f64) graph, any other algorithm →
    the all-integer graph."""
    algo = (grid[3, :n].astype(np.int64) >> DUR_BITS) & _ALGO_MASK
    if (algo == 1).any():
        return "mixed"
    if not algo.any():
        return "token"
    # active rows are fp != 0 (lanes 0/1); inactive lanes are all-zero
    act = algo[(grid[0, :n] != 0) | (grid[1, :n] != 0)]
    if act.size and (act == 2).all():
        return "gcra"
    return "int"


def grid_has_cascade(grid: np.ndarray, n: int) -> bool:
    """Whether an assembled wire grid carries cascade level bits (lane 3
    bits 30-31) — the engine then compiles the in-trace verdict fold into
    the dispatch (kernel2.fold_cascade_packed)."""
    return bool(((grid[3, :n].astype(np.int64) >> LEVEL_SHIFT) & 3).any())


def stamp_base(block: np.ndarray, base: int) -> None:
    """Write the base into a wire block's trailing column (cells [0, -1]
    and [1, -1]) — shared by every grid builder so the cell assignment can
    never diverge from decode_wire_block's."""
    block[0, -1] = np.int64(base).astype(np.int32)
    block[1, -1] = np.int64(base >> 32).astype(np.int32)


def block_base(block: np.ndarray) -> int:
    """The base a wire block's trailing column carries (`stamp_base`)."""
    return (int(block[0, -1]) & 0xFFFFFFFF) | (int(block[1, -1]) << 32)


# ------------------------------------------------------------ trace decode


def decode_wire_block(blk: jnp.ndarray):
    """In-trace decode of one (5, W+1) int32 wire block back to the full
    (12, W) int64 ingress array (kernel2.req_from_arr layout) plus the
    base scalar. Pure casts/shifts — fuses into the decision kernel, so
    the narrow wire costs a few vector ops instead of 76 B/row of
    host→device traffic."""
    W = blk.shape[1] - 1
    base = _join64(blk[0, W], blk[1, W])
    l0, l1, l2, l3, l4 = (blk[i, :W] for i in range(WIRE_LANES))
    fp = _join64(l0, l1)
    limit = l2.astype(i64)
    dur = (l3 & _DUR_MASK).astype(i64)
    algo = (l3 >> DUR_BITS) & _ALGO_MASK
    level = (l3 >> LEVEL_SHIFT) & 3
    hits = (l4 & _HITS_MASK).astype(i64)
    delta = (((l4 >> HITS_BITS) & _DELTA_MASK) - DELTA_BIAS).astype(i64)
    behavior = (
        ((l4 >> 30) & 1) * _RESET
        | ((l4 >> 31) & 1) * _DRAIN
        | (((l4 >> PRIO_SHIFT) & 3) << _BEH_PRIO_SHIFT)
        | (level << _BEH_LEVEL_SHIFT)
    )
    created = base + delta
    active = fp != 0
    # burst reconstructs to limit for the tolerance-shaped algorithms
    # (leaky, GCRA — the pack-side defaulting), 0 otherwise
    burst = jnp.where((algo == 1) | (algo == 2), limit, i64(0))
    arr12 = jnp.stack(
        [
            fp,
            algo.astype(i64),
            behavior.astype(i64),
            hits,
            limit,
            burst,
            dur,
            created,
            created + dur,  # expire_new (non-Gregorian by encodability)
            jnp.zeros_like(fp),  # greg_interval
            dur,  # duration_eff
            active.astype(i64),
        ]
    )
    return arr12, base


def encode_wire_out(packed: jnp.ndarray, base) -> jnp.ndarray:
    """In-trace egress narrowing: the (B+2, 4) int64 pack_outputs array →
    int32, reset as a base-relative delta (RESET_SENTINEL preserves
    reset==0 exactly), remaining/limit saturating-clamped to int32 (both
    are int32-bounded for every validated config — the clamp only moves
    values pathological configs could not re-read anyway), and the base
    stamped into the spare stats cells so the fetched array is
    self-describing."""
    B = packed.shape[0] - 2
    rows = packed[:B]
    sat = lambda x: jnp.clip(x, -(2**31), 2**31 - 1).astype(i32)
    reset = rows[:, 2]
    enc = jnp.where(
        reset == 0,
        jnp.int32(RESET_SENTINEL),
        jnp.clip(reset - base, -(2**31) + 1, 2**31 - 1).astype(i32),
    )
    body = jnp.stack([sat(rows[:, 0]), sat(rows[:, 1]), enc, sat(rows[:, 3])], axis=1)
    stats = jnp.clip(packed[B:], -(2**31), 2**31 - 1).astype(i32)
    stats = stats.at[1, 1].set(_lo32(base)).at[1, 2].set(_hi32(base))
    return jnp.concatenate([body, stats], axis=0)


# -------------------------------------------------------------- host decode


def decode_wire_host(lanes: np.ndarray, base: int) -> dict:
    """Vectorized HOST decode of a (5, n) int32 lane image (pack_wire_rows
    layout) back to full-width int64 columns — the receive half of the
    inter-slice GLOBAL sync codec (service/global_manager.py ships pending
    hits as one lane image instead of n proto messages; the owner daemon
    decodes them here before applying). The in-trace twin is
    decode_wire_block; the two must agree field-for-field, which
    tests/test_pod_mesh.py pins by round-tripping through both."""
    lanes = np.asarray(lanes, dtype=np.int32)
    l0, l1, l2, l3, l4 = (lanes[i].astype(np.int64) for i in range(WIRE_LANES))
    fp = (l0 & 0xFFFFFFFF) | (l1 << 32)
    dur = l3 & _DUR_MASK
    algo = (l3 >> DUR_BITS) & _ALGO_MASK
    level = (l3 >> LEVEL_SHIFT) & 3
    hits = l4 & _HITS_MASK
    delta = ((l4 >> HITS_BITS) & _DELTA_MASK) - DELTA_BIAS
    behavior = (
        ((l4 >> 30) & 1) * _RESET
        | ((l4 >> 31) & 1) * _DRAIN
        | (((l4 >> PRIO_SHIFT) & 3) << _BEH_PRIO_SHIFT)
        | (level << _BEH_LEVEL_SHIFT)
    )
    created = base + delta
    return {
        "fp": fp,
        "algo": algo.astype(np.int32),
        "behavior": behavior.astype(np.int32),
        "hits": hits,
        "limit": l2,
        "duration": dur,
        "created_at": created,
        "active": fp != 0,
    }


def wire_out_base(arr: np.ndarray) -> int:
    """The base stamped into a fetched compact egress array."""
    return (int(arr[-1, 1]) & 0xFFFFFFFF) | (int(arr[-1, 2]) << 32)


def decode_wire_rows(per: np.ndarray, base: int) -> np.ndarray:
    """Vectorized host decode of compact egress response rows ((n, 4)
    int32 → int64, absolute reset_time). Returns a fresh writable array
    (retry fix-ups mutate responses in place)."""
    out = per.astype(np.int64)
    d = out[:, 2]
    out[:, 2] = np.where(d == RESET_SENTINEL, 0, base + d)
    return out


def unpack_wire_out(arr: np.ndarray, n: int):
    """Compact counterpart of kernel2.unpack_outputs (same return shape);
    kernel2.unpack_outputs dispatches here on dtype, so every caller
    decodes both wire formats through one entry."""
    base = wire_out_base(arr)
    st = (int(arr[-2, 0]), int(arr[-2, 1]), int(arr[-2, 2]), int(arr[-2, 3]))
    per = decode_wire_rows(arr[:n], base)
    status = (per[:, 3] & FLAG_STATUS).astype(np.int32)
    hit = (per[:, 3] & FLAG_HIT) != 0
    dropped = (per[:, 3] & FLAG_DROPPED) != 0
    return (status, per[:, 0], per[:, 1], per[:, 2], dropped, hit), st


# --------------------------------------------------- single-device entries


def decide2_wire_cols_impl(
    table, carr, *, write="sweep", math="mixed", cascade=False,
    evictees=False,
):
    """Compact single-transfer serving entry: (5, B+1) int32 wire block in,
    (B+2, 4) int32 compact outputs out — the narrow-wire twin of
    kernel2.decide2_packed_cols_impl. `cascade=True` folds cascade verdicts
    in-trace on the wide packed array BEFORE the egress narrowing.
    `evictees=True` appends the raw int32 evictee sidecar AFTER the
    narrowing (slot fields are bit patterns, never clamped —
    kernel2.attach_evictees_wire)."""
    arr12, base = decode_wire_block(carr)
    if evictees is True:
        from gubernator_tpu.ops.kernel2 import (
            attach_evictees_wire,
            decide2_packed_impl,
            fold_cascade_packed,
            req_from_arr,
        )

        table, packed, ev16 = decide2_packed_impl(
            table, req_from_arr(arr12), write=write, math=math, evictees=True
        )
        if cascade:
            packed = fold_cascade_packed(packed, arr12)
        return table, attach_evictees_wire(encode_wire_out(packed, base), ev16)
    # False, or "defer": the tiered table's hits-only program, no sidecar
    table, packed = decide2_packed_cols_impl(
        table, arr12, write=write, math=math, cascade=cascade,
        evictees=evictees,
    )
    return table, encode_wire_out(packed, base)


def decide2_wire_dedup_impl(
    table, carr, *, write="sweep", math="mixed", cascade=False
):
    """Compact entry with in-trace duplicate aggregation (the mesh
    engines' dedup="device" program built on the narrow wire)."""
    arr12, base = decode_wire_block(carr)
    table, packed = decide2_packed_dedup_impl(
        table, arr12, write=write, math=math, cascade=cascade
    )
    return table, encode_wire_out(packed, base)


decide2_wire_cols = functools.partial(
    jax.jit, donate_argnums=(0,),
    static_argnames=("write", "math", "cascade", "evictees"),
)(decide2_wire_cols_impl)


# ------------------------------------------------- passes behind a grid


def gather_wire_block(
    lanes: np.ndarray, rows: np.ndarray, pad: int, members=None, starts=None
) -> "np.ndarray | None":
    """One pass behind a fused grid as a gather of the chunk's own lanes:
    columns `rows` of `lanes` (an assembled grid, every copy of a key still
    in it) in a zeroed (5, pad+1) block under the same base column. The
    planner's aggregate names its `members` too, group after group from
    `starts` on, a group's newest member being its row: lane 4 then takes
    the group's summed hits and RESET_REMAINING OR-ed over the group — None
    when a sum passes the lane's 18 bits."""
    n = rows.size
    block = np.zeros((WIRE_LANES, pad + 1), dtype=np.int32)
    block[:, -1] = lanes[:, -1]
    block[:, :n] = lanes[:, rows]
    if members is not None:
        l4 = lanes[4, members]
        hits = np.add.reduceat((l4 & _HITS_MASK).astype(np.int64), starts)
        if hits.max() > _HITS_MASK:
            return None
        reset = np.bitwise_or.reduceat(l4 & np.int32(1 << 30), starts)
        block[4, :n] = (
            (block[4, :n] & ~np.int32(_HITS_MASK)) | hits.astype(np.int32) | reset
        )
    return block


# ------------------------------------------------ a fused chunk, staged

# grid_math_mode's answers, indexed as native stage_wire_chunk names them
MATH_MODES = ("token", "mixed", "gcra", "int")


class StagedPass(NamedTuple):
    """One pass behind a fused grid: its `rows` of the chunk and its
    (5, pad+1) `block` (gather_wire_block) with the mode it selects, or
    `block` None where the lanes cannot carry the pass and the caller packs
    it as columns. The aggregate also names its `members`, group after
    group from `starts` on, `member_counts` to a group (ops/plan.Pass)."""

    rows: np.ndarray
    block: "np.ndarray | None"
    pad: int
    math: "str | None"
    members: "np.ndarray | None" = None
    starts: "np.ndarray | None" = None
    member_counts: "np.ndarray | None" = None


class StagedChunk(NamedTuple):
    """The host staging of one fused chunk (ops/engine._assemble_wire_parts):
    the pass-0 `grid` with its `math` mode and cascade flag, a copy of the
    rows' `err` the finish half owns, every active fingerprint, the rows
    with a live lane in the grid (`first`), the clamped stamps counted, how
    many rows are `later` copies of a key, and the passes those make."""

    grid: np.ndarray
    err: np.ndarray
    act_fp: np.ndarray
    first: np.ndarray
    clamped: int
    math: str
    casc: bool
    later: int
    passes: "list[StagedPass]"


def stage_wire_chunk(
    mod, parts, now: int, tol: int, pad: int, max_exact: int,
    pad_floor: int, keep_copies: bool = False,
) -> "StagedChunk | None":
    """`StagedChunk` of the WireBatch pieces `parts` from ONE call into the
    native module `mod` (native/guberhost.cpp stage_wire_chunk), which runs
    without the GIL from the first row to the last; what comes back is
    wrapped, not computed. None: the chunk cannot fuse. The NumPy staging
    of ops/engine.py is the same function of the same arguments, and what
    the tests hold this one to byte for byte. `keep_copies`: the program
    folds the copies of a key itself (the mesh's in-trace dedup), so each
    keeps its lane in the grid and no pass follows it."""
    out = mod.stage_wire_chunk(
        [(p.lanes, p.cols.fp, p.cols.err, p.cols.created_at) for p in parts],
        now, tol, pad, max_exact, pad_floor, keep_copies,
    )
    if out is None:
        return None
    grid, err, act_fp, first, clamped, math, casc, later, passes = out
    return StagedChunk(
        _as_block(grid, pad), np.frombuffer(err, np.int8),
        _as_rows(act_fp), np.frombuffer(first, np.bool_),
        clamped, MATH_MODES[math], casc, later,
        [
            StagedPass(
                _as_rows(r), _as_block(b, p), p,
                None if b is None else MATH_MODES[m],
                _as_rows(mem), _as_rows(st), _as_rows(cnt),
            )
            for r, b, p, m, mem, st, cnt in passes
        ],
    )


def _as_block(buf, pad: int) -> "np.ndarray | None":
    """A (5, pad+1) int32 wire block over the native call's bytes."""
    if buf is None:
        return None
    return np.frombuffer(buf, np.int32).reshape(WIRE_LANES, pad + 1)


def _as_rows(buf) -> "np.ndarray | None":
    return None if buf is None else np.frombuffer(buf, np.int64)


# ---------------------------------------------- a fused dispatch, finished


class WireFinish(NamedTuple):
    """What `finish_wire_chunk` found while it wrote a dispatch's response
    columns: the passes' `stats` summed (cache_hits, cache_misses,
    over_limit, evicted_unexpired), the rows answered behind the first pass
    and of those the aggregate's members, a mesh's unprocessed rows
    (`overflow`), and `dropped`: one (pass, row of the pass, the row's
    flags) for every row whose FLAG_DROPPED is set, in pass and row order."""

    stats: "tuple[int, int, int, int]"
    later_rows: int
    aggregate_rows: int
    overflow: int
    dropped: np.ndarray  # (k, 3) int64


def finish_wire_chunk(mod, passes, cols) -> "WireFinish | None":
    """The finish half of one fused dispatch from ONE call into the native
    module `mod` (native/guberhost.cpp finish_wire_chunk), which runs
    without the GIL from the first row to the last: every pass's fetched
    compact egress block decoded and scattered to request order, in place,
    into `cols` (status int32; limit, remaining, reset_time int64).
    `passes`: a (block, n, rows, members, member_counts, base, lanes) each —
    `rows`, or `members` with `member_counts` for the aggregate, as
    ops/plan.Pass holds them; `base` and `lanes` a mesh's grid names, None
    for a block that carries its own. None: a block is not int32 and the
    NumPy finish (ops/engine._finish_numpy, the same function of the same
    arrays, and what the tests hold this one to byte for byte) is the path."""
    out = mod.finish_wire_chunk(passes, *cols)
    if out is None:
        return None
    return WireFinish(
        out[:4], out[4], out[5], out[6],
        np.frombuffer(out[7], np.int64).reshape(-1, 3),
    )
