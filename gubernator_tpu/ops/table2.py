"""Packed-row HBM table (v2): one bucket per TPU lane row.

Layout chosen from measured v5e memory-op costs (exp/README.md, exp_mem*):

* XLA scatters serialize (~8 ns/element regardless of layout) — the v1 design's
  15 plane scatters cost ~16 ms per 131K-row dispatch;
* row gathers are fast (~1.3 ms for (131K, 128) int32), and a full streaming
  sweep of a 1 GB table through VMEM costs ~3.3 ms with int8 one-hot matmuls
  (the scatter-as-MXU-work trick) essentially free behind the DMA.

Hence the v2 layout: ``rows`` is an (NB, 128) int32 array — NB buckets, each
row = K=8 slots x 16 int32 fields, slot-major. A bucket row is exactly one TPU
vector lane row (128 lanes), so:

* probe+apply = ONE row gather of the request's whole bucket (every slot's
  full state arrives in one fetch — no separate probe plane);
* write = the Pallas sweep kernel (ops/kernel2.py) composing slot-granular
  updates into bucket rows via int8 one-hot matmuls on the MXU.

Per-slot field order (16 int32 lanes): fp_lo, fp_hi, limit, burst, rem_i,
flags(algo | status<<8), dur_lo, dur_hi, stamp_lo, stamp_hi, exp_lo, exp_hi,
remf_hi(f32 bits), remf_lo(f32 bits), touch (tiered tables; else 0),
reserved. Semantics mirror
TokenBucketItem/LeakyBucketItem (reference store.go:29-43) + CacheItem.ExpireAt
(reference cache.go:29-41); the leaky float64 remainder is double-single
(two f32, ~48-bit mantissa). fp == 0 marks an empty slot. Eviction is
expiry-stamp based exactly as in v1 (ops/table.py docstring; reference
lrucache.go:111-149).
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

K = 8  # slots per bucket
F = 16  # int32 fields per slot in the canonical FULL layout
ROW = K * F  # 128 int32 lanes per full-layout bucket row

# field indices within a FULL-layout slot (packed layouts unpack to this
# order — ops/layout.py is the single conversion authority)
FP_LO, FP_HI, LIMIT, BURST, REM_I, FLAGS = 0, 1, 2, 3, 4, 5
DUR_LO, DUR_HI, STAMP_LO, STAMP_HI, EXP_LO, EXP_HI = 6, 7, 8, 9, 10, 11
REMF_HI, REMF_LO = 12, 13
# the first reserved lane: with a shadow tier attached the decide and merge
# programs write the time of the row's last use here (`touch_tick`), which
# is what their victim rule reads (ops/kernel2._probe_claim2, victim="lru").
# Untiered programs write 0 and never read it; the 32 B layouts drop it.
TOUCH = 14


class Table2:
    """One HBM table: a rows array plus the SlotLayout addressing it.

    ``rows`` is (NB, K·layout.F) int32 — one bucket per row, K slots of
    layout.F fields each (128 lanes for the full layout, 64 for the packed
    ones). The layout travels as pytree AUX data (static), so jitted
    programs key their compilation on it and shard_map/tree transforms
    preserve it for free; ``Table2(rows=...)`` without a layout infers the
    full layout from the 128-lane width (the pre-layout constructor every
    existing call site uses), while packed tables pass theirs explicitly."""

    __slots__ = ("rows", "layout")

    def __init__(self, rows, layout=None):
        if layout is None:
            from gubernator_tpu.ops.layout import layout_for_row

            layout = layout_for_row(int(rows.shape[-1]))
        self.rows = rows
        self.layout = layout

    @property
    def n_buckets(self) -> int:
        return self.rows.shape[-2]

    @property
    def capacity(self) -> int:
        return self.rows.shape[-2] * K

    def __repr__(self) -> str:  # pragma: no cover - debugging nicety
        return f"Table2(rows={getattr(self.rows, 'shape', None)}, " \
               f"layout={self.layout.name})"


def _t2_flatten(t: Table2):
    return (t.rows,), t.layout


def _t2_unflatten(layout, children):
    obj = object.__new__(Table2)
    obj.rows = children[0]
    obj.layout = layout
    return obj


jax.tree_util.register_pytree_node(Table2, _t2_flatten, _t2_unflatten)


def n_buckets_for(capacity: int) -> int:
    """Bucket count for a requested slot capacity: rounded up so the Pallas
    sweep's block partitioning divides evenly (power of two below 2048 blocks,
    multiple of 2048 above)."""
    if capacity <= 0:
        raise ValueError("capacity must be positive")
    nb = -(-capacity // K)
    if nb <= 2048:
        p = 1
        while p < nb:
            p *= 2
        return p
    return -(-nb // 2048) * 2048


def new_table2(capacity: int, layout=None) -> Table2:
    """Fresh empty table (the CacheSize analog, reference config.go:151).
    Keep load factor <= ~0.6 for healthy buckets. `layout` defaults to the
    canonical full layout (bit-compatible with every earlier PR)."""
    if layout is None:
        from gubernator_tpu.ops.layout import FULL as layout
    return Table2(
        rows=jnp.zeros((n_buckets_for(capacity), layout.row), dtype=jnp.int32),
        layout=layout,
    )


def _live_slots(xp, rows, now_ms, lay):
    """Mask of live slots over rows seen as (buckets, K, F), on the host
    (xp=np) or traced (xp=jnp): one predicate for both."""
    rows = rows.reshape(-1, K, lay.F)
    lo = rows[:, :, FP_LO]
    hi = rows[:, :, FP_HI]
    exp = (rows[:, :, lay.exp_lo_i].astype(xp.int64) & 0xFFFFFFFF) | (
        rows[:, :, lay.exp_hi_i].astype(xp.int64) << 32
    )
    return ((lo != 0) | (hi != 0)) & (exp >= now_ms)


# buckets per step of the device count: seeing rows as (.., K, F) makes a TPU
# copy them into another tiling, so the table goes through that in pieces of
# 8 MiB instead of whole (1.2 GiB of scratch beside a 1 GiB table)
_COUNT_CHUNK = 16384


def live_count_rows(rows: jnp.ndarray, now_ms, layout) -> jnp.ndarray:
    """Traced: the live slots of one device's (buckets, row) array."""
    nb = rows.shape[0]
    chunk = math.gcd(nb, _COUNT_CHUNK)  # n_buckets_for: 2^k, or a multiple of 2048

    def step(i, acc):
        part = jax.lax.dynamic_slice_in_dim(rows, i * chunk, chunk)
        return acc + _live_slots(jnp, part, now_ms, layout).sum(dtype=jnp.int64)

    return jax.lax.fori_loop(0, nb // chunk, step, jnp.int64(0))


live_count_device = functools.partial(jax.jit, static_argnames=("layout",))(
    live_count_rows
)


def live_count2(table: Table2, now_ms: int) -> int:
    """Live (non-empty, unexpired) slots — reference cache Size()
    (lrucache.go:152-157). A device-resident table is counted where it
    lives and one integer comes back; rows already on the host (a
    checkpoint's snapshot) are counted in NumPy."""
    if isinstance(table.rows, np.ndarray):
        return int(_live_slots(np, table.rows, now_ms, table.layout).sum())
    return int(live_count_device(table.rows, jnp.int64(now_ms), table.layout))


def decode_live_slots(rows: np.ndarray, now_ms: int, layout=None):
    """Flatten a rows array into live slot records: (slot_fields (N, F_layout)
    i32, fp (N,) i64, exp (N,) i64) for slots that are non-empty and
    unexpired at now_ms. Slots come back in the TABLE's own layout — convert
    with layout.unpack when full-width fields are needed."""
    if layout is None:
        from gubernator_tpu.ops.layout import layout_for_row

        layout = layout_for_row(int(rows.shape[-1]))
    slots = rows.reshape(-1, layout.F)
    lo = slots[:, FP_LO].astype(np.int64) & 0xFFFFFFFF
    hi = slots[:, FP_HI].astype(np.int64)
    fp = (hi << 32) | lo
    exp = (slots[:, layout.exp_lo_i].astype(np.int64) & 0xFFFFFFFF) | (
        slots[:, layout.exp_hi_i].astype(np.int64) << 32
    )
    live = (fp != 0) & (exp >= now_ms)
    return slots[live], fp[live], exp[live]


# ------------------------------------------------------------- handoff ops
#
# Topology-change survivability (docs/robustness.md "Topology change &
# drain"): when ring ownership moves, the owner's live rows must follow.
# The DEVICE pays for partitioning millions of live slots — a full-table
# filter+pack runs as one fused program and the host fetches only the live
# prefix (batch-proportional transfer), mirroring the sparse-write /
# packed-single-fetch idioms of the serving path.


@functools.partial(jax.jit, static_argnames=("layout",))
def _extract_sorted(rows: jnp.ndarray, now_ms: jnp.ndarray, *, layout):
    """Device filter+pack: all live slots sorted to the front. Accepts any
    (..., ROW_layout) rows array (single-device (NB, ·) or sharded
    (D, NB, ·) — the flatten makes the shard axis fold in). Returns
    (slots_packed (N, F_layout), fp_packed (N,), live_count) with live
    entries occupying the first `live_count` positions; slots stay in the
    table's own layout (the handoff/checkpoint wire format)."""
    slots = rows.reshape(-1, layout.F)
    lo = slots[:, FP_LO].astype(jnp.int64) & 0xFFFFFFFF
    hi = slots[:, FP_HI].astype(jnp.int64)
    fp = (hi << 32) | lo
    exp = (slots[:, layout.exp_lo_i].astype(jnp.int64) & 0xFFFFFFFF) | (
        slots[:, layout.exp_hi_i].astype(jnp.int64) << 32
    )
    live = (fp != 0) & (exp >= now_ms)
    order = jnp.argsort(jnp.where(live, 0, 1).astype(jnp.int32))
    return slots[order], fp[order], live.sum()


def extract_live_rows(rows, now_ms: int, layout=None):
    """Extract every live slot from a device-resident rows array:
    (fps (N,) i64, slots (N, F_layout) i32) host copies. The filter + pack
    runs on-device (_extract_sorted); the host fetches only the live prefix,
    padded to a power of two so the number of compiled slice shapes stays
    logarithmic in table capacity."""
    if layout is None:
        from gubernator_tpu.ops.layout import layout_for_row

        layout = layout_for_row(int(rows.shape[-1]))
    slots_s, fp_s, cnt = _extract_sorted(rows, np.int64(now_ms), layout=layout)
    n = int(cnt)
    if n == 0:
        return (
            np.empty(0, dtype=np.int64),
            np.empty((0, layout.F), dtype=np.int32),
        )
    pad = 256
    while pad < n:
        pad *= 2
    pad = min(pad, int(fp_s.shape[0]))
    return (
        np.asarray(fp_s[:pad])[:n].copy(),
        np.asarray(slots_s[:pad])[:n].copy(),
    )


def _extract_idle_core(rows2d, now_ms, idle_ms, layout):
    """Traced core of the tiering idle sweep (gubernator_tpu/tier/):
    live slots whose last-activity reference (layout.idle_ref — stamp, or
    exp-duration for layouts that drop it) is at least `idle_ms` behind
    `now_ms`, sorted to the front. `rows2d` is (T, ROW_layout); returns
    (slots (T·K, F_layout) idle-first, fp (T·K,), idle_count) — slots stay
    in the table's own layout, the demote path unpacks only the fetched
    prefix. Shared by the single-array jit below and the per-shard
    shard_map body (parallel/sharded.make_sharded_extract_idle)."""
    slots = rows2d.reshape(-1, layout.F)
    lo = slots[:, FP_LO].astype(jnp.int64) & 0xFFFFFFFF
    hi = slots[:, FP_HI].astype(jnp.int64)
    fp = (hi << 32) | lo
    exp = (slots[:, layout.exp_lo_i].astype(jnp.int64) & 0xFFFFFFFF) | (
        slots[:, layout.exp_hi_i].astype(jnp.int64) << 32
    )
    live = (fp != 0) & (exp >= now_ms)
    idle = live & ((now_ms - layout.idle_ref(slots)) >= idle_ms)
    order = jnp.argsort(jnp.where(idle, 0, 1).astype(jnp.int32))
    return slots[order], fp[order], idle.sum()


@functools.partial(jax.jit, static_argnames=("layout",))
def _extract_idle_sorted(rows, now_ms, idle_ms, *, layout):
    """Single-array entry: any (..., ROW_layout) rows array (the flatten
    folds a shard axis in, like _extract_sorted)."""
    return _extract_idle_core(
        rows.reshape(-1, layout.row), now_ms, idle_ms, layout
    )


_IDLE_CHUNK = 16_384  # buckets the idle sweep looks at in one step


def _idle_first_impl(rows, now_ms, idle_ms, *, layout, max_rows):
    """The first `max_rows` idle live slots of a rows array, in table order:
    (slots (max_rows, F_layout), fp (max_rows,), idle count). Which slots
    are idle is decided `_IDLE_CHUNK` buckets at a time (seeing rows as
    (.., K, F) makes a TPU copy them into another tiling: done whole, the
    sorted extract below wants 9.7 GiB of scratch beside a 1 GiB table, as
    the telemetry scan did before ops/telemetry._scan_chunked), and only the
    rows of the slots taken are gathered. Entries past the count repeat
    slot 0 and are the caller's to drop."""
    rows = rows.reshape(-1, layout.row)
    nb = rows.shape[0]
    chunk = math.gcd(nb, _IDLE_CHUNK)

    def mask_of(part):
        slots = part.reshape(-1, layout.F)
        fp = (slots[:, FP_HI].astype(jnp.int64) << 32) | (
            slots[:, FP_LO].astype(jnp.int64) & 0xFFFFFFFF
        )
        exp = (slots[:, layout.exp_lo_i].astype(jnp.int64) & 0xFFFFFFFF) | (
            slots[:, layout.exp_hi_i].astype(jnp.int64) << 32
        )
        live = (fp != 0) & (exp >= now_ms)
        return live & ((now_ms - layout.idle_ref(slots)) >= idle_ms)

    def step(i, mask):
        part = jax.lax.dynamic_slice_in_dim(rows, i * chunk, chunk)
        return jax.lax.dynamic_update_slice_in_dim(
            mask, mask_of(part), i * chunk * K, 0
        )

    mask = jax.lax.fori_loop(
        0, nb // chunk, step, jnp.zeros(nb * K, dtype=bool)
    )
    take = min(max_rows, nb * K)
    (idx,) = jnp.nonzero(mask, size=take, fill_value=0)
    lanes = (idx % K)[:, None] * layout.F + jnp.arange(layout.F)[None, :]
    slots = jnp.take_along_axis(rows[idx // K], lanes, axis=1)
    fp = (slots[:, FP_HI].astype(jnp.int64) << 32) | (
        slots[:, FP_LO].astype(jnp.int64) & 0xFFFFFFFF
    )
    return slots, fp, mask.sum()


_extract_idle_first = functools.partial(
    jax.jit, static_argnames=("layout", "max_rows")
)(_idle_first_impl)


def extract_idle_rows(rows, now_ms: int, idle_ms: int, layout=None,
                      max_rows: int = 1 << 16):
    """Idle-past-the-horizon live slots of a device-resident rows array:
    (fps (N,) i64, slots (N, F_layout) i32) host copies, N ≤ max_rows (the
    per-sweep demote cap — bounds the engine-thread job; the remainder
    stays for the next sweep). The filter and the gather run on-device
    (`_extract_idle_first`); the host fetches `max_rows` rows at most."""
    if layout is None:
        from gubernator_tpu.ops.layout import layout_for_row

        layout = layout_for_row(int(rows.shape[-1]))
    slots, fp, cnt = _extract_idle_first(
        rows, jnp.asarray(np.int64(now_ms)), jnp.asarray(np.int64(idle_ms)),
        layout=layout, max_rows=int(max_rows),
    )
    n = min(int(cnt), int(fp.shape[0]))
    if n == 0:
        return (
            np.empty(0, dtype=np.int64),
            np.empty((0, layout.F), dtype=np.int32),
        )
    return np.asarray(fp)[:n].copy(), np.asarray(slots)[:n].copy()


def gather_slots_impl(rows: jnp.ndarray, fp: jnp.ndarray,
                      active: jnp.ndarray, layout=None):
    """Read the slots holding each fingerprint WITHOUT mutating anything:
    one bucket-row gather + lane match, unpacked to canonical full-width
    fields. Returns (full_slots (B, 16) i32, found (B,) bool) — the
    stored-state read the GLOBAL broadcast plane uses to ship
    sliding-window aux with owner updates (service/global_manager.py).
    Unexpired-ness is NOT checked here; callers filter on the expiry pair
    if they need liveness."""
    if layout is None:
        from gubernator_tpu.ops.layout import layout_for_row

        layout = layout_for_row(int(rows.shape[-1]))
    NB = rows.shape[0]
    B = fp.shape[0]
    bucket = (fp % NB).astype(jnp.int32)
    full = layout.unpack(rows[bucket].reshape(B, K, layout.F))  # (B, K, 16)
    my_lo = fp.astype(jnp.int32)
    my_hi = (fp >> 32).astype(jnp.int32)
    s_lo = full[:, :, FP_LO]
    s_hi = full[:, :, FP_HI]
    empty = (s_lo == 0) & (s_hi == 0)
    match = (
        (s_lo == my_lo[:, None]) & (s_hi == my_hi[:, None]) & ~empty
        & active[:, None]
    )
    found = match.any(axis=1)
    lane = jnp.argmax(match, axis=1).astype(jnp.int32)
    lane16 = jnp.take_along_axis(full, lane[:, None, None], axis=1)[:, 0, :]
    return jnp.where(found[:, None], lane16, 0), found


gather_slots = functools.partial(jax.jit, static_argnames=("layout",))(
    gather_slots_impl
)


def tombstone_rows_impl(rows: jnp.ndarray, fp: jnp.ndarray, active: jnp.ndarray):
    """Zero the slot holding each fingerprint (handoff source side: rows are
    tombstoned only AFTER the destination acked their transfer). Missing
    fingerprints are no-ops — a kill mask over matched slots only, so a
    retried tombstone can never evict an unrelated live entry. Returns
    (rows', found_mask). Layout-agnostic by construction: only the
    fingerprint pair (fields 0/1 in every layout) and the row geometry
    (F = lanes // K) are read."""
    NB = rows.shape[0]
    B = fp.shape[0]
    F_l = rows.shape[-1] // K
    bucket = (fp % NB).astype(jnp.int32)
    b_rows = rows[bucket].reshape(B, K, F_l)
    my_lo = fp.astype(jnp.int32)
    my_hi = (fp >> 32).astype(jnp.int32)
    s_lo = b_rows[:, :, FP_LO]
    s_hi = b_rows[:, :, FP_HI]
    empty = (s_lo == 0) & (s_hi == 0)
    match = (
        (s_lo == my_lo[:, None]) & (s_hi == my_hi[:, None]) & ~empty
        & active[:, None]
    )
    lane = jnp.argmax(match, axis=1).astype(jnp.int32)
    found = match.any(axis=1)
    NBK = NB * K
    tgt = jnp.where(found, bucket * K + lane, NBK)
    kill = jnp.zeros(NBK + 1, dtype=bool).at[tgt].set(True)[:NBK]
    flat = rows.reshape(NBK, F_l)
    out = jnp.where(kill[:, None], 0, flat).reshape(NB, K * F_l)
    return out, found


tombstone_rows = functools.partial(jax.jit, donate_argnums=(0,))(
    tombstone_rows_impl
)


def rehash_rows(
    rows: np.ndarray, new_n_buckets: int, now_ms: int, layout=None
) -> "tuple[np.ndarray, int]":
    """Re-place every live slot into a table with `new_n_buckets` buckets —
    the host side of a resize (SURVEY §7 hard-parts: table growth is
    host-orchestrated; the kernel's placement rule is bucket = fp % NB).
    Buckets receiving more than K live entries keep the K latest-expiring and
    drop the rest (the same preference order as in-kernel eviction). Returns
    (new rows array, dropped count) in the same slot layout as the input."""
    if layout is None:
        from gubernator_tpu.ops.layout import layout_for_row

        layout = layout_for_row(int(rows.shape[-1]))
    slots, fp, exp = decode_live_slots(rows, now_ms, layout=layout)
    out = np.zeros((new_n_buckets, layout.row), dtype=np.int32)
    if fp.shape[0] == 0:
        return out, 0
    bucket = fp % new_n_buckets
    # rank entries within their new bucket, latest-expiring first
    order = np.lexsort((-exp, bucket))
    b_sorted = bucket[order]
    first = np.concatenate([[True], b_sorted[1:] != b_sorted[:-1]])
    pos = np.arange(b_sorted.shape[0])
    start = np.maximum.accumulate(np.where(first, pos, -1))
    lane = (pos - start).astype(np.int64)
    keep = lane < K
    dropped = int((~keep).sum())
    tgt = b_sorted[keep] * K + lane[keep]
    out.reshape(-1, layout.F)[tgt] = slots[order[keep]]
    return out, dropped
