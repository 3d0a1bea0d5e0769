"""Decision kernel v2: packed-row table, sort-based claim, Pallas sweep write.

Replaces the v1 kernel's memory strategy (ops/kernel.py — 15 f32-carrier plane
scatters + 12 flat gathers + a multi-round scatter-max claim auction, ~74 ms
per 131K-row dispatch on v5e) with the design measured fastest on real TPU
(exp/README.md, exp_mem*):

  1. **fetch** — ONE (B, 128) row gather brings each request's whole bucket
     (all 8 slots, full state) into registers: ~1.3 ms.
  2. **claim** — pure vector math, no device auction: requests are sorted by
     bucket (lax.sort of int32 operands, ~0.1 ms); each inserting row takes a
     rank among its bucket's inserters via segmented prefix sums, and rank r
     picks the r-th lane in (vacant-first, then soonest-expiring) order.
     Insert-vs-owner lane collisions are resolved by a second sort over target
     slots (owners win; losers are answered but flagged dropped → the engine
     retries them, cf. v1's auction losers).
  3. **apply** — the shared branchless decision table (ops/math.py) on the
     chosen lane's state.
  4. **write** — the update set becomes (payload, lane-mask) rows composed into
     bucket rows by a **Pallas sweep**: the table streams through VMEM in
     (BLK, 128) blocks while int8 one-hot matmuls on the MXU scatter each
     block's updates into place; blocks whose update run fits their first
     u-window skip the second half's matmuls via a scalar-prefetched
     predicate (~4.2 ms for a 1 GiB table at headline batch,
     exp/README.md, exp_sweep5). `write="sparse"` launches the SAME sweep grid only
     over the batch's dirty blocks via scalar-prefetched block indices
     (_write_sparse) — write cost ∝ batch, not table size — and resolves
     back to the full sweep past a coverage crossover (resolve_write /
     GUBER_WRITE_SPARSE_CROSSOVER). XLA scatter fallback (`write="xla"`)
     keeps identical semantics for CPU meshes/tests.

Dispatches are additionally specialized host-side by
`math="token"|"int"|"mixed"` (engine._math_mode): all-token batches — the
common case — compile a decision graph with ONLY the token lanes; batches
mixing in GCRA / sliding-window / concurrency-lease rows compile the
all-integer graph; only a leaky row forces the emulated-float64 lanes
(see ops/math.bucket_math).

Same decision semantics as v1 (reference algorithms.go:37-492 via
ops/math.py). Documented divergence from v1: slot-vacancy uses the exact
millisecond expiry (the whole bucket is already in registers) instead of v1's
conservative coarse-expiry probe plane, and a burst of inserts into one full
bucket may evict several soonest-expiring lanes at once (v1 evicted at most
one per dispatch round; the reference's LRU evicts as many as needed,
lrucache.go:138-149). The leaky remainder is stored as a double-single f32
pair (REMF_HI/LO, ~48-bit mantissa) vs the reference's float64 (store.go:32):
exact for every integer remainder in the accepted config range — limits and
bursts are validated to int32 (pack_columns ERR_LIMIT_I32/ERR_BURST_I32), so
integer parts are ≤ 2^31 ≪ 2^48 — with fractional-refill resolution ≥ 2^-17
tokens at the i32 extreme (measured worst roundtrip error 2^-19; bounds
asserted in tests/test_leaky_bucket.py). Configs beyond i32, which COULD
quantize, are rejected rather than served imprecisely; in-kernel math is
float64 throughout (ops/math.py).
"""

from __future__ import annotations

import contextlib
import functools
import os
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from gubernator_tpu.ops.batch import BatchStats, ReqBatch, RespBatch
from gubernator_tpu.ops.math import StoredState, bucket_math
from gubernator_tpu.ops.table2 import (
    BURST,
    DUR_HI,
    DUR_LO,
    EXP_HI,
    EXP_LO,
    F,
    FLAGS,
    FP_HI,
    FP_LO,
    K,
    LIMIT,
    REM_I,
    REMF_HI,
    REMF_LO,
    ROW,
    STAMP_HI,
    STAMP_LO,
    TOUCH,
    Table2,
)
from gubernator_tpu.types import Status

i64 = jnp.int64
i32 = jnp.int32
f64 = jnp.float64
f32 = jnp.float32

def _sweep_x64_ctx(interpret: bool):
    """Trace the sweep/sparse pallas_call with x64 OFF on real TPU (Mosaic
    rejects the x64-promoted scalars the surrounding graph traces with) but
    leave the config ALONE under the CPU interpreter, whose grid loop would
    otherwise mix i32 and i64 scalar helpers inside one trace."""
    return contextlib.nullcontext() if interpret else jax.enable_x64(False)


def _lo32(x):
    return (x & 0xFFFFFFFF).astype(i32)


def _hi32(x):
    return (x >> 32).astype(i32)


def _join64(lo32, hi32):
    return (hi32.astype(i64) << 32) | (lo32.astype(i64) & 0xFFFFFFFF)


def _biased(x_i32):
    """Map int32 bit patterns to an order-preserving signed key for the
    unsigned value (flip the sign bit): sorting the result as int32 sorts the
    original as uint32."""
    return x_i32 ^ jnp.int32(-0x80000000)


def _cummax(x):
    return jax.lax.cummax(x, axis=0)


def sweep_geometry(n_buckets: int, batch: int) -> Tuple[int, int]:
    """(BLK bucket-rows per Pallas block, U update window per block).

    U covers the expected per-block update count plus a ~5-sigma Poisson tail
    (overflow rows are dropped → engine retry, so the tail bound is a perf
    knob, not correctness). BLK stays as LARGE as VMEM allows: the sweep's
    cost is dominated by per-block pipeline overhead, not the one-hot MXU
    work — exp/README.md, exp_sweep5 measured 4.20 ms at (2048, 256) vs 6.34 ms at
    (1024, 128) for the same headline update set, even though the smaller
    window runs half the matmul MACs. BLK shrinks only until the (BLK, U)
    one-hot operand fits VMEM comfortably."""
    blk = min(2048, n_buckets)
    if n_buckets % blk:
        # tables built by new_table2 are always conforming (power-of-two below
        # 2048 buckets, multiple of 2048 above); a hand-built table with a
        # non-dividing bucket count would leave tail rows outside the Pallas
        # grid with undefined content under input_output_aliasing
        raise ValueError(
            f"n_buckets={n_buckets} not divisible by sweep block {blk}; "
            "build tables with new_table2()"
        )
    while True:
        nblk = n_buckets // blk
        mean = batch / nblk
        u = int(mean + 5.0 * mean**0.5) + 64
        p = 64  # power of two so the window count divides the (pow2) batch —
        # the sweep's dynamic index maps address u-aligned payload blocks
        while p < u:
            p *= 2
        u = min(p, batch)
        # VMEM stack bound: the two-half kernel holds ~6 (blk,128) i32
        # temps + 2 (blk,u) onehots; blk*u ≤ 2^19 keeps the scoped
        # allocation under the 16 MiB limit (measured: u=512 × blk=2048
        # overflows at 21.4 MiB)
        if blk * u <= (1 << 19) or blk <= 256:
            # the blk floor must not re-open the VMEM bound (a small table
            # under a huge batch otherwise walks to blk=256, u=batch): cap u
            # hard — window-overflow rows just drop to the engine's retry
            u = min(u, max(64, (1 << 19) // blk))
            return blk, u
        blk //= 2


def _sparse_blk() -> int:
    """Block rows per sparse-write grid step (GUBER_WRITE_SPARSE_BLK).

    Small on purpose: the sparse path's HBM traffic is (dirty blocks) × BLK
    rows, and dirty blocks ≈ min(batch, n_buckets/BLK) for hash-spread
    targets — so BLK is the knob trading per-step pipeline overhead against
    bytes touched per dirty block. Read per trace (host-side), so tuning
    runs can flip it between compiles without a restart."""
    return int(os.environ.get("GUBER_WRITE_SPARSE_BLK", "64"))


def sparse_write_crossover() -> float:
    """Coverage bound gating the sparse write (GUBER_WRITE_SPARSE_CROSSOVER):
    `write="sparse"` resolves to the full sweep unless the sparse grid's
    worst-case coverage (grid steps × BLK bucket rows) times this factor
    still fits under n_buckets — i.e. sparse only runs when it provably
    touches ≤ 1/crossover of the table, where its batch-proportional cost
    beats the table-streaming sweep."""
    return float(os.environ.get("GUBER_WRITE_SPARSE_CROSSOVER", "4"))


def sparse_geometry(n_buckets: int, batch: int) -> Tuple[int, int, int]:
    """(BLK bucket-rows per sparse block, U update window, G grid steps).

    Unlike the dense sweep (BLK as large as VMEM allows — per-block overhead
    amortizes over the whole-table stream), the sparse grid visits only
    dirty blocks, so BLK stays SMALL: each of the ≤ min(batch, n_buckets/BLK)
    dirty blocks costs BLK·512 B of HBM traffic regardless of how many
    updates it holds. U follows the same Poisson-tail policy as
    sweep_geometry (overflow rows drop to the engine's retry), and the VMEM
    stack bound blk·u ≤ 2^19 is inherited unchanged."""
    blk = min(_sparse_blk(), n_buckets)
    while blk > 1 and n_buckets % blk:
        # conforming tables (new_table2) are pow2 below 2048 buckets or a
        # multiple of 2048 above — some pow2 ≤ blk always divides
        blk //= 2
    nblk = n_buckets // blk
    mean = batch / nblk
    u = int(mean + 5.0 * mean**0.5) + 64
    p = 64
    while p < u:
        p *= 2
    u = min(p, batch)
    u = min(u, max(64, (1 << 19) // blk))
    return blk, u, min(nblk, batch)


def resolve_write(write: str, n_buckets: int, batch: int, layout=None) -> str:
    """Per-dispatch (static-shape) write-mode resolution. `"sparse"` falls
    back to the full sweep when the worst-case dirty coverage crosses
    GUBER_WRITE_SPARSE_CROSSOVER — a 131K-row headline dispatch on a 1 GiB
    table resolves to the sweep, a 4K serving dispatch to the sparse grid.
    Runs host-side at trace time (batch and table shapes are static), so the
    jit cache key (the `write` string) stays stable per call site.

    The crossover is BYTE-denominated, not row-denominated: the sweep's
    cost is the table's bytes streamed through VMEM while the sparse grid's
    dominant cost is per-block pipeline overhead (byte-count-independent at
    its small BLK). A packed 32 B layout halves the bytes both sides touch
    per row but not the sparse grid's per-block overhead, so the coverage
    fraction where sparse still wins DOUBLES — the worst-case dirty
    coverage is scaled by layout.F / 16 before the crossover compare, i.e.
    the knob's value keeps meaning "sparse must touch ≤ 1/crossover of a
    FULL-layout table's bytes". `layout=None` (or full) preserves the
    pre-layout behavior bit-for-bit."""
    if write not in ("sweep", "sparse", "xla"):
        raise ValueError(
            f"unknown write mode {write!r}; expected 'sweep', 'sparse' or 'xla'"
        )
    if write != "sparse":
        return write
    if layout is None:
        from gubernator_tpu.ops.layout import FULL as layout
    blk, _u, g = sparse_geometry(n_buckets, batch)
    coverage_bytes_scaled = g * blk * (layout.F / float(F))
    if coverage_bytes_scaled * sparse_write_crossover() >= n_buckets:
        return "sweep"
    return "sparse"


class Claim2(NamedTuple):
    bucket: jnp.ndarray  # (B,) i32
    chosen: jnp.ndarray  # (B,) i32 lane in [0, K)
    got: jnp.ndarray  # (B,) bool — row has a lane (pre-dedup)
    owns: jnp.ndarray  # (B,) bool — lane holds this row's fp
    written: jnp.ndarray  # (B,) bool — row survives dedup (+ window overflow)
    evict_live: jnp.ndarray  # (B,) bool — claimed lane held a live item
    slots: jnp.ndarray  # (B, K, F) i32 — the gathered bucket contents
    # sweep-write routing (sorted-by-target domain)
    order: jnp.ndarray  # (B,) i32 original index at each sorted position
    tgt_sorted: jnp.ndarray  # (B,) i32 target slot at each sorted position
    written_sorted: jnp.ndarray  # (B,) bool — written flag at sorted position


def _probe_claim2(
    rows_tbl: jnp.ndarray, fp, now, active, blk: int, u: int, layout=None,
    victim: str = "expiry", claim: bool = True,
) -> Claim2:
    """Probe + claim. `layout` (ops/layout.py) is the table's slot layout:
    the row gather fetches layout.row lanes per bucket — HALF the HBM
    bytes for the 32 B packed layouts — and the packed fields unpack to
    the canonical 16-field slots in registers, so every consumer below
    (claim ordering, decision math, merge rules) stays layout-blind.

    The two static switches are the tiered programs' (a shadow attached,
    gubernator_tpu/tier/). `victim="lru"` orders a full bucket's live
    lanes by their TOUCH lane, least recently used first (upstream's
    lrucache.go), expiry breaking ties, where the default takes the
    soonest-expiring. `claim=False` gives no row a lane it does not own:
    a key the table does not hold comes back dropped and unwritten, for
    the engine's miss path to fault in (ops/engine.LocalEngine)."""
    if layout is None:
        from gubernator_tpu.ops.layout import FULL as layout
    NB = rows_tbl.shape[0]
    B = fp.shape[0]
    if NB * K * 2 >= 2**31:
        raise ValueError("table too large for int32 slot ids")

    bucket = (fp % NB).astype(i32)
    my_lo = _lo32(fp)
    my_hi = _hi32(fp)

    rows = rows_tbl[bucket]  # (B, ROW_layout) row gather — the only table read
    slots = layout.unpack(rows.reshape(B, K, layout.F))  # (B, K, 16) canonical
    s_fp_lo = slots[:, :, FP_LO]
    s_fp_hi = slots[:, :, FP_HI]

    empty = (s_fp_lo == 0) & (s_fp_hi == 0)
    match = (s_fp_lo == my_lo[:, None]) & (s_fp_hi == my_hi[:, None]) & ~empty
    match = match & active[:, None]
    owns = match.any(axis=1)
    own_j = jnp.argmax(match, axis=1).astype(i32)

    # exact lazy expiry (reference lrucache.go:111-128): expired slots are
    # reclaimable by any key probing the bucket. Compared in the split
    # (hi, lo-as-unsigned) domain — int64 on TPU is emulated, and this is
    # the kernel's only (B, K)-shaped 64-bit computation
    exp_lo = slots[:, :, EXP_LO]
    exp_hi = slots[:, :, EXP_HI]
    now_hi = _hi32(now)
    now_lo_b = _biased(_lo32(now))
    dead = ~empty & (
        (exp_hi < now_hi[:, None])
        | ((exp_hi == now_hi[:, None]) & (_biased(exp_lo) < now_lo_b[:, None]))
    )
    vacant = empty | dead
    live = ~vacant

    # ---- rank among inserting rows of the same bucket (sorted domain)
    need = active & ~owns
    NBs = jnp.int32(NB)
    bkey = jnp.where(active, bucket, NBs)
    idx = jnp.arange(B, dtype=i32)
    bkey_s, need_s, idx_s1 = jax.lax.sort(
        (bkey, need.astype(i32), idx), num_keys=1
    )
    first = jnp.concatenate(
        [jnp.ones((1,), dtype=bool), bkey_s[1:] != bkey_s[:-1]]
    )
    csum = jnp.cumsum(need_s)
    c_excl = csum - need_s
    seg_base = _cummax(jnp.where(first, c_excl, -1))
    rank_s = (c_excl - seg_base).astype(i32)
    # un-sort: rank back to original row order
    _, rank = jax.lax.sort((idx_s1, rank_s), num_keys=1)

    # ---- candidate lane order: vacant lanes first (by index), then live
    # lanes by soonest expiry — expiry-stamp eviction, v1 semantics
    lane_iota = jnp.broadcast_to(jnp.arange(K, dtype=i32), (B, K))
    exp_hi_k = slots[:, :, EXP_HI]
    exp_lo_k = _biased(slots[:, :, EXP_LO])
    keys = (live.astype(i32), exp_hi_k, exp_lo_k)
    if victim == "lru":
        keys = (keys[0], slots[:, :, TOUCH], *keys[1:])
    cand = jax.lax.sort(
        (*keys, lane_iota), num_keys=len(keys), dimension=1
    )[-1]
    rank_c = jnp.clip(rank, 0, K - 1)
    ins_lane = jnp.take_along_axis(cand, rank_c[:, None], axis=1)[:, 0]
    claim_ok = need & (rank < K)
    if not claim:
        claim_ok = jnp.zeros_like(need)

    chosen = jnp.where(owns, own_j, ins_lane)
    got = active & (owns | claim_ok)
    lane_live = jnp.take_along_axis(live, chosen[:, None], axis=1)[:, 0]
    evict_live = claim_ok & lane_live

    # ---- conflict dedup + sweep window assignment over target slots
    NBK = jnp.int32(NB * K)
    target = jnp.where(got, bucket * K + chosen, NBK)
    # owners sort ahead of inserters on equal targets, so dedup keeps them
    skey = target * 2 + jnp.where(owns, 0, 1).astype(i32)
    skey_s, idx_s2 = jax.lax.sort((skey, idx), num_keys=1)
    tgt_s = skey_s >> 1
    dup = jnp.concatenate([jnp.zeros((1,), dtype=bool), tgt_s[1:] == tgt_s[:-1]])

    # window overflow: position within the target's sweep block run
    pos_i = jnp.arange(B, dtype=i32)
    blk_of = tgt_s // jnp.int32(K * blk)
    first_blk = jnp.concatenate(
        [jnp.ones((1,), dtype=bool), blk_of[1:] != blk_of[:-1]]
    )
    blk_start = _cummax(jnp.where(first_blk, pos_i, -1))
    overflow = (pos_i - blk_start) >= u

    written_s = (tgt_s < NBK) & ~dup & ~overflow
    _, written_i = jax.lax.sort((idx_s2, written_s.astype(i32)), num_keys=1)
    written = written_i.astype(bool)

    return Claim2(
        bucket=bucket,
        chosen=chosen,
        got=got,
        owns=owns,
        written=written,
        evict_live=evict_live & written,
        slots=slots,
        order=idx_s2,
        tgt_sorted=tgt_s,
        written_sorted=written_s,
    )


# --------------------------------------------------------------------- write


def _make_sweep_kernel(nwin: int, blk: int, u: int, fl: int = F,
                       sparse: bool = False):
    """Kernel factory for the scalar-prefetch sweep (closes over geometry).

    Windowing lives IN the kernel: updates stay in target-sorted order; the
    grid's dynamic block index maps (PrefetchScalarGridSpec) DMA the two
    u-aligned payload blocks covering this table block's update run, and
    slot/lane-mask/liveness derive from the raw sorted targets. Each half is
    composed into the block rows via int8 one-hot matmuls (MXU — the
    scatter-as-matmul trick); unique targets (claim dedup) mean the sums
    place, never add. A run never extends past start+u (the probe's window
    overflow marks the tail dropped), so two aligned u-blocks always cover
    it; the second half is masked off when its block index clamps (window at
    the array end).

    The previous design materialized (nblk·u) host-side window gathers —
    measured 8 ms of the 16 ms write at headline scale; in-kernel windowing
    plus one payload gather runs the same sweep in ~3.3 ms (≈600 GB/s through
    a 1 GiB table). The second half's matmuls only run when this block's
    update run actually crosses its first window boundary (`need2`, scalar-
    prefetched per block) — runs are ~mean-length and windows u-aligned, so
    most blocks take the single-half branch and the MXU work per sweep drops
    by roughly the non-straddle fraction.

    `sparse=True` builds the block-sparse variant (_write_sparse): grid step
    i composes the dirty block named by the scalar-prefetched `db_ref[i]`
    instead of block i — same body, data-dependent block base. `fl` is the
    table layout's fields-per-slot (ops/layout.py): payload rows are
    (u, fl) and table blocks (blk, K·fl) — the packed layouts stream half
    the bytes per block through VMEM."""
    KBLK = K * blk
    ROW_L = K * fl

    def body(i, blk_base, n2_ref, p1, p2, t1, t2, tbl_in, tbl_out):
        dot = functools.partial(
            jax.lax.dot_general,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=i32,
        )

        def half(pay_ref, tgt_ref):
            pay = pay_ref[:]  # (u, fl) i32 payload, sorted-by-target
            tgt = tgt_ref[:]  # (u, 1) i32 global slot target (sentinel NBK)
            rel = tgt - blk_base
            live = (rel >= 0) & (rel < KBLK)
            slot = jnp.where(live, rel % K, -1)  # (u, 1)
            lb = jnp.where(live, rel // K, -1)  # (u, 1)
            # lane l of a bucket row belongs to slot l//fl, field l%fl
            lane_slot = jax.lax.broadcasted_iota(i32, (u, ROW_L), 1) // fl
            upd = jnp.concatenate([pay] * K, axis=1)  # (u, K·fl)
            msk = (lane_slot == slot).astype(jnp.int8)
            iot = jax.lax.broadcasted_iota(i32, (blk, u), 0)
            onehot = (iot == lb[:, 0][None, :]).astype(jnp.int8)
            w = dot(onehot, msk)
            acc = None
            for s in range(4):
                plane = (((upd >> (8 * s)) & 0xFF) * msk.astype(i32)).astype(
                    jnp.int8
                )
                p = dot(onehot, plane)
                # one (sign-extended) byte per hit — re-mask, then place
                p = (p & 0xFF) << (8 * s)
                acc = p if acc is None else acc | p
            return acc, w

        # need2 ⇒ s+1 ≤ nwin-1 (a run never extends past the batch end), so
        # the second window's block index is always in range on this branch
        @pl.when(n2_ref[i] != 0)
        def _():
            acc1, w1 = half(p1, t1)
            acc2, w2 = half(p2, t2)
            tbl_out[:] = jnp.where(w1 + w2 > 0, acc1 | acc2, tbl_in[:])

        @pl.when(n2_ref[i] == 0)
        def _():
            acc1, w1 = half(p1, t1)
            tbl_out[:] = jnp.where(w1 > 0, acc1, tbl_in[:])

    if sparse:

        def kern_sparse(db_ref, s_ref, n2_ref, p1, p2, t1, t2, tbl_in, tbl_out):
            i = pl.program_id(0)
            body(i, db_ref[i] * KBLK, n2_ref, p1, p2, t1, t2, tbl_in, tbl_out)

        return kern_sparse

    def kern(s_ref, n2_ref, p1, p2, t1, t2, tbl_in, tbl_out):
        i = pl.program_id(0)
        body(i, i * KBLK, n2_ref, p1, p2, t1, t2, tbl_in, tbl_out)

    return kern


def _write_sweep(rows_tbl, new16, c: Claim2, blk: int, u: int, layout=None):
    """Pallas sweep write: stream the table through VMEM once, composing the
    target-sorted update run of each block in-kernel (see _make_sweep_kernel).
    Payload rows pack to the table's slot layout before the gather, so a
    packed table's sweep streams layout.row lanes per bucket — half the
    bytes for the 32 B layouts."""
    if layout is None:
        from gubernator_tpu.ops.layout import FULL as layout
    fl, rowl = layout.F, layout.row
    NB = rows_tbl.shape[0]
    B = new16.shape[0]
    nblk = NB // blk
    nwin = B // u
    assert nwin * u == B, f"batch {B} not divisible by window {u}"

    new_pk = layout.pack(new16)  # (B, fl)
    pay_s = new_pk[c.order]  # the ONE payload gather: original → sorted order
    tgt_eff = jnp.where(
        c.written_sorted, c.tgt_sorted, jnp.int32(NB * K)
    ).astype(i32)[:, None]
    starts = jnp.searchsorted(
        c.tgt_sorted, (jnp.arange(nblk, dtype=i32) * (K * blk)).astype(i32)
    ).astype(i32)
    ends = jnp.concatenate([starts[1:], jnp.full((1,), B, dtype=i32)])
    s_blk = jnp.clip(starts // u, 0, nwin - 1)
    # does block i's update run cross its first window's end? (ends ≤ B, so
    # need2 ⇒ s_blk+1 ≤ nwin-1; blocks whose run fits one window skip the
    # second half's matmuls entirely)
    need2 = (ends > (s_blk + 1) * u).astype(i32)

    second = lambda i, s, n2: (jnp.minimum(s[i] + 1, nwin - 1), 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nblk,),
        in_specs=[
            pl.BlockSpec((u, fl), lambda i, s, n2: (s[i], 0)),
            pl.BlockSpec((u, fl), second),
            pl.BlockSpec((u, 1), lambda i, s, n2: (s[i], 0)),
            pl.BlockSpec((u, 1), second),
            pl.BlockSpec((blk, rowl), lambda i, s, n2: (i, 0)),
        ],
        out_specs=pl.BlockSpec((blk, rowl), lambda i, s, n2: (i, 0)),
    )
    interpret = jax.default_backend() == "cpu"
    with _sweep_x64_ctx(interpret):
        out = pl.pallas_call(
            _make_sweep_kernel(nwin, blk, u, fl),
            interpret=interpret,
            out_shape=jax.ShapeDtypeStruct(rows_tbl.shape, rows_tbl.dtype),
            grid_spec=grid_spec,
            input_output_aliases={6: 0},
        )(s_blk, need2, pay_s, pay_s, tgt_eff, tgt_eff, rows_tbl)
    return out


def _write_sparse(rows_tbl, new16, c: Claim2, blk: int, u: int, g: int,
                  layout=None):
    """Block-sparse Pallas write: launch the sweep grid ONLY over dirty
    blocks, so the write's HBM traffic scales with the batch, not the table.

    The dirty-block set — the ≤ min(batch, nblk) unique `target // (K·blk)`
    values over WRITTEN rows — is computed in-trace (sort + unique, a few µs
    of vector work against the ms-scale sweep it replaces) and handed to the
    kernel as a scalar-prefetched block-index vector: grid step i DMAs block
    `db[i]` in and out, composing its update run exactly like the dense
    sweep. Unvisited blocks are untouched — `input_output_aliases` makes the
    output buffer the donated input, so their rows simply persist.

    Grid padding (g is static, the dirty count dynamic): padded steps target
    a provably-CLEAN block — the smallest block id absent from the dirty set
    (first index where the sorted unique list skips a value) — and compose an
    empty run, i.e. rewrite that block's unchanged content. Padding steps all
    name the SAME block and sit contiguously at the end of the sorted list,
    so Pallas' revisit rule (consecutive equal block indices share one VMEM
    buffer, fetched and flushed once) makes them write identical bytes — no
    read-after-write hazard, unlike duplicate DIRTY blocks, which is why the
    real entries are deduplicated rather than clamped."""
    if layout is None:
        from gubernator_tpu.ops.layout import FULL as layout
    fl, rowl = layout.F, layout.row
    NB = rows_tbl.shape[0]
    B = new16.shape[0]
    nblk = NB // blk
    KBLK = K * blk
    nwin = B // u
    assert nwin * u == B, f"batch {B} not divisible by window {u}"
    assert g >= 1

    new_pk = layout.pack(new16)  # (B, fl)
    pay_s = new_pk[c.order]  # the ONE payload gather: original → sorted order
    tgt_eff = jnp.where(
        c.written_sorted, c.tgt_sorted, jnp.int32(NB * K)
    ).astype(i32)[:, None]
    NBLK = jnp.int32(nblk)
    # dirty block per written row; sentinel nblk otherwise (merges with the
    # unique fill value — both mean "padding step")
    blk_w = jnp.where(c.written_sorted, c.tgt_sorted // jnp.int32(KBLK), NBLK)
    du = jnp.unique(blk_w, size=g, fill_value=nblk).astype(i32)
    # free (clean) block for padding steps: du is sorted unique, so the
    # first index i with du[i] > i is a block id absent from the dirty set
    # (padding entries du[i] = nblk > i always qualify, so when any padding
    # exists the min is < nblk; with zero written rows it degrades to 0)
    idxg = jnp.arange(g, dtype=i32)
    free = jnp.min(jnp.where(du > idxg, idxg, NBLK))
    db = jnp.where(du >= NBLK, free, du)

    starts = jnp.searchsorted(c.tgt_sorted, db * jnp.int32(KBLK)).astype(i32)
    ends = jnp.searchsorted(
        c.tgt_sorted, (db + 1) * jnp.int32(KBLK)
    ).astype(i32)
    s_blk = jnp.clip(starts // u, 0, nwin - 1)
    need2 = (ends > (s_blk + 1) * u).astype(i32)

    second = lambda i, db_, s, n2: (jnp.minimum(s[i] + 1, nwin - 1), 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(g,),
        in_specs=[
            pl.BlockSpec((u, fl), lambda i, db_, s, n2: (s[i], 0)),
            pl.BlockSpec((u, fl), second),
            pl.BlockSpec((u, 1), lambda i, db_, s, n2: (s[i], 0)),
            pl.BlockSpec((u, 1), second),
            pl.BlockSpec((blk, rowl), lambda i, db_, s, n2: (db_[i], 0)),
        ],
        out_specs=pl.BlockSpec((blk, rowl), lambda i, db_, s, n2: (db_[i], 0)),
    )
    interpret = jax.default_backend() == "cpu"
    with _sweep_x64_ctx(interpret):
        out = pl.pallas_call(
            _make_sweep_kernel(nwin, blk, u, fl, sparse=True),
            interpret=interpret,
            out_shape=jax.ShapeDtypeStruct(rows_tbl.shape, rows_tbl.dtype),
            grid_spec=grid_spec,
            input_output_aliases={7: 0},
        )(db, s_blk, need2, pay_s, pay_s, tgt_eff, tgt_eff, rows_tbl)
    return out


def _write_xla(rows_tbl, new16, c: Claim2, layout=None):
    """Semantically identical scatter write for backends without the Pallas
    TPU pipeline (CPU test meshes). Slot-granular, drop-mode."""
    if layout is None:
        from gubernator_tpu.ops.layout import FULL as layout
    NB = rows_tbl.shape[0]
    slot_view = rows_tbl.reshape(NB * K, layout.F)
    tgt = jnp.where(c.written, c.bucket * K + c.chosen, NB * K)
    out = slot_view.at[tgt].set(layout.pack(new16), mode="drop")
    return out.reshape(NB, layout.row)


# -------------------------------------------------------------------- decide


def touch_tick(now):
    """What the tiered programs write into a row's TOUCH lane: the row's
    clock in units of 1,024 ms, which an int32 holds until 2039. Lanes
    touched within one unit tie, and the victim rule then falls back on
    their expiry."""
    return (now >> 10).astype(i32)


def decide_payload(lane16, req: ReqBatch, owns, *, math: str):
    """The per-row DECIDE stage: the chosen lane's canonical (B, 16) stored
    fields + the request rows → (exists, Decision, canonical (B, 16)
    write-payload rows) — algorithm math and payload packing, everything
    downstream of the claim except the response assembly."""
    now = req.created_at
    B = req.fp.shape[0]
    g = lambda f: lane16[:, f]
    s_exp = _join64(g(EXP_LO), g(EXP_HI))
    exists = owns & (s_exp >= now)
    s_flags = g(FLAGS)
    stored = StoredState(
        limit=g(LIMIT).astype(i64),
        burst=g(BURST).astype(i64),
        rem_i=g(REM_I).astype(i64),
        algo=s_flags & 0xFF,
        status=s_flags >> 8,
        duration=_join64(g(DUR_LO), g(DUR_HI)),
        stamp=_join64(g(STAMP_LO), g(STAMP_HI)),
        exp=s_exp,
        rem_f=jax.lax.bitcast_convert_type(g(REMF_HI), f32).astype(f64)
        + jax.lax.bitcast_convert_type(g(REMF_LO), f32).astype(f64),
        # the SAME lane pair reinterpreted as a raw int64 — GCRA's TAT and
        # the sliding window's previous count live here (ops/math.py
        # storage convention); dead code (DCE'd) under math="token"
        aux=_join64(g(REMF_LO), g(REMF_HI)),
    )
    d = bucket_math(stored, req, exists, mode=math)

    # ---- build update payload rows
    sat32 = lambda x: jnp.clip(x, -(2**31), 2**31 - 1).astype(i32)
    # REMF lane pair by algorithm family: zeros for token-only batches,
    # the raw aux int64 (GCRA TAT / window prev) for int batches, and the
    # leaky f64 split merged in per row for mixed ones
    if math == "token":
        remf_hi_i = jnp.zeros(B, dtype=i32)
        remf_lo_i = jnp.zeros(B, dtype=i32)
    elif math in ("gcra", "int"):
        remf_hi_i = _hi32(d.aux_out)
        remf_lo_i = _lo32(d.aux_out)
    else:
        f_hi = d.rem_f_out.astype(f32)
        f_lo = (d.rem_f_out - f_hi.astype(f64)).astype(f32)
        is_leaky = req.algo == 1
        remf_hi_i = jnp.where(
            is_leaky, jax.lax.bitcast_convert_type(f_hi, i32), _hi32(d.aux_out)
        )
        remf_lo_i = jnp.where(
            is_leaky, jax.lax.bitcast_convert_type(f_lo, i32), _lo32(d.aux_out)
        )
    my_lo = _lo32(req.fp)
    my_hi = _hi32(req.fp)
    zero = jnp.zeros_like(my_lo)
    new16 = jnp.stack(
        [
            jnp.where(d.remove, 0, my_lo),
            jnp.where(d.remove, 0, my_hi),
            sat32(req.limit),
            sat32(d.burst_out),
            sat32(d.rem_i_out),
            d.flags_out,
            _lo32(d.dur_out),
            _hi32(d.dur_out),
            _lo32(d.stamp_out),
            _hi32(d.stamp_out),
            jnp.where(d.remove, 0, _lo32(d.exp_out)),
            jnp.where(d.remove, 0, _hi32(d.exp_out)),
            remf_hi_i,
            remf_lo_i,
            zero,
            zero,
        ],
        axis=1,
    )  # (B, F)
    return exists, d, new16


def assemble_resp(req: ReqBatch, d, exists, written, evict_live):
    """Response + stats assembly: the Decision rows plus the claim outcome
    flags → (RespBatch, BatchStats)."""
    active = req.active
    OVER = jnp.int32(int(Status.OVER_LIMIT))
    UNDER = jnp.int32(int(Status.UNDER_LIMIT))
    dropped = active & ~written
    resp = RespBatch(
        status=jnp.where(active, d.resp_status, UNDER),
        limit=jnp.where(active, req.limit, i64(0)),
        remaining=jnp.where(active, d.resp_rem, i64(0)),
        reset_time=jnp.where(active, d.resp_reset, i64(0)),
        cache_hit=exists,
        dropped=dropped,
        # stored-state echoes for full-fidelity GLOBAL broadcasts
        # (parallel/global_sync._sync_core): the raw aux (GCRA TAT /
        # sliding-window previous count) and the remaining-STYLE integer
        # lane. DCE'd in every serving graph (pack_outputs ignores them).
        aux=d.aux_out,
        rem_store=d.rem_i_out,
    )
    stats = BatchStats(
        cache_hits=exists.sum(dtype=i64),
        cache_misses=(active & ~exists).sum(dtype=i64),
        over_limit=(active & (resp.status == OVER)).sum(dtype=i64),
        evicted_unexpired=evict_live.sum(dtype=i64),
        dropped=dropped.sum(dtype=i64),
    )
    return resp, stats


def decide2_impl(
    table: Table2, req: ReqBatch, *, write: str = "sweep", math: str = "mixed",
    evictees: bool = False,
) -> Tuple[Table2, RespBatch, BatchStats]:
    """Un-jitted v2 kernel body — call through `decide2` / `decide2_xla`.

    `math="token"` compiles the token-only decision graph (no emulated-f64
    leaky lanes — see ops/math.bucket_math); the engine selects it per
    dispatch after a host-side check that the batch carries no leaky row.
    `write="sparse"` resolves per dispatch shape (resolve_write): the
    block-sparse grid when its coverage is a small fraction of the table,
    the full sweep otherwise. The table's slot layout (ops/layout.py)
    threads through the probe gather and the write composition; packed
    layouts only serve their own math mode — the engine migrates a packed
    table to full before dispatching off-family traffic, so this guard
    firing means a caller skipped the engine layer.

    `evictees=True` (static — compiled only when a shadow tier is attached,
    gubernator_tpu/tier/) additionally returns the EVICTEE SIDECAR: a
    (B, 16) int32 array of the canonical full-width rows the claim
    displaced (`evict_live` rows' pre-dispatch lane state, zero rows
    elsewhere) — the state today's eviction silently discards, captured so
    the engine can demote it to the host-RAM shadow instead. The return
    grows a 4th element; `evictees=False` keeps the historic 3-tuple and
    a bit-identical trace. `evictees="defer"` is the tiered table's
    pipelined program: it decides the rows whose key the table holds and
    gives no other row a lane (`claim=False`), so it creates nothing,
    evicts nothing and carries no sidecar; a deferred row comes back
    dropped, in no count of the stats rows, and the engine's miss path
    decides it (with `evictees=True`) once its state is where it belongs.
    Both tiered programs stamp the TOUCH lane of every row they write and
    take a full bucket's least recently touched lane as the victim.
    """
    layout = table.layout
    if not layout.supports_math(math):
        raise ValueError(
            f"table layout {layout.name!r} cannot serve math={math!r}; "
            "migrate the table to the full layout first (engine does this "
            "automatically)"
        )
    B = req.fp.shape[0]
    NB = table.rows.shape[0]
    write = resolve_write(write, NB, B, layout)
    if write == "sparse":
        blk, u, gsteps = sparse_geometry(NB, B)
    else:
        blk, u = sweep_geometry(NB, B)
    now = req.created_at
    active = req.active

    c = _probe_claim2(
        table.rows, req.fp, now, active, blk, u, layout,
        victim="lru" if evictees else "expiry", claim=evictees != "defer",
    )

    # ---- apply: chosen lane's stored state (shared decide stage)
    lane16 = jnp.take_along_axis(c.slots, c.chosen[:, None, None], axis=1)[
        :, 0, :
    ]  # (B, F)
    exists, d, new16 = decide_payload(lane16, req, c.owns, math=math)
    if evictees:
        new16 = new16.at[:, TOUCH].set(touch_tick(now))

    if write == "sweep":
        rows_out = _write_sweep(table.rows, new16, c, blk, u, layout)
    elif write == "sparse":
        rows_out = _write_sparse(table.rows, new16, c, blk, u, gsteps, layout)
    else:
        rows_out = _write_xla(table.rows, new16, c, layout)

    if evictees == "defer":
        # a row with no lane of its own was not decided here: dropped for
        # the engine to see, and counted by the program that decides it
        resp, stats = assemble_resp(
            req._replace(active=active & c.owns), d, exists, c.written,
            c.evict_live,
        )
        resp = resp._replace(dropped=active & ~c.written)
        return Table2(rows=rows_out, layout=layout), resp, stats
    resp, stats = assemble_resp(req, d, exists, c.written, c.evict_live)
    if evictees:
        ev16 = jnp.where(c.evict_live[:, None], lane16, 0).astype(i32)
        return Table2(rows=rows_out, layout=layout), resp, stats, ev16
    return Table2(rows=rows_out, layout=layout), resp, stats


decide2 = functools.partial(
    jax.jit, donate_argnums=(0,),
    static_argnames=("write", "math", "evictees"),
)(decide2_impl)


def pack_outputs(
    resp: RespBatch, stats: BatchStats, behavior=None
) -> jnp.ndarray:
    """Pack responses + stats into ONE (B+2, 4) i64 array.

    The serving engine reads kernel results with a single device→host
    transfer: each fetched array is its own host sync, and one DMA beats
    six.
    Layout: row i < B = [limit, remaining, reset_time, flags] with
    flags = status | cache_hit<<1 | dropped<<2; row B = [cache_hits,
    cache_misses, over_limit, evicted_unexpired]; row B+1 = [dropped, 0, 0, 0].

    `behavior` (the request batch's behavior words, optional) echoes each
    row's priority tier (types.PRIORITY_SHIFT) into flags bits 5-6
    (FLAG_TIER_SHIFT) — the decision's QoS tier rides the same fetched
    array, so the batcher and the metrics plane read it without a
    host-side side table.
    """
    flags = (
        resp.status.astype(i64)
        | (resp.cache_hit.astype(i64) << 1)
        | (resp.dropped.astype(i64) << 2)
    )
    if behavior is not None:
        tier = (jnp.asarray(behavior).astype(i64) >> _BEH_PRIO_SHIFT) & 3
        flags = flags | (tier << FLAG_TIER_SHIFT)
    rows = jnp.stack([resp.limit, resp.remaining, resp.reset_time, flags], axis=1)
    z = jnp.zeros((), dtype=i64)
    srow0 = jnp.stack(
        [stats.cache_hits, stats.cache_misses, stats.over_limit,
         stats.evicted_unexpired]
    )[None, :]
    srow1 = jnp.stack([stats.dropped, z, z, z])[None, :]
    return jnp.concatenate([rows, srow0, srow1], axis=0)


# ------------------------------------------------------- evictee sidecar
#
# Hot-set tiering (gubernator_tpu/tier/, docs/tiering.md): when a shadow
# table is attached the decide dispatch also returns the canonical rows it
# evicted, riding the SAME fetched array as the responses and stats. The
# sidecar rows are inserted BETWEEN the response rows and the two stats
# rows, so every existing decoder (`arr[:n]` responses, `arr[-2]` stats)
# keeps working unchanged; only unpack_evictees knows the middle exists.
#   int64 packed outputs: each (16,) i32 row rides as 8 int64 lanes
#     ((hi<<32)|lo over adjacent field pairs) → 2 extra rows of 4 per
#     request → (3B+2, 4).
#   int32 compact-wire outputs: raw fields, 4 extra rows of 4 per request
#     → (5B+2, 4) (slot fields must NOT ride the clamped response
#     narrowing — they are raw bit patterns).


def attach_evictees(packed: jnp.ndarray, ev16: jnp.ndarray) -> jnp.ndarray:
    """Insert a (B, 16) i32 evictee sidecar into a full-width (B+2, 4)
    int64 pack_outputs array → (3B+2, 4)."""
    B = ev16.shape[0]
    ev64 = _join64(ev16[:, 0::2], ev16[:, 1::2]).reshape(2 * B, 4)
    return jnp.concatenate([packed[:B], ev64, packed[B:]], axis=0)


def attach_evictees_wire(enc: jnp.ndarray, ev16: jnp.ndarray) -> jnp.ndarray:
    """Insert a (B, 16) i32 evictee sidecar into a compact (B+2, 4) int32
    egress array → (5B+2, 4) (raw fields, dtype already int32)."""
    B = ev16.shape[0]
    return jnp.concatenate(
        [enc[:B], ev16.reshape(4 * B, 4), enc[B:]], axis=0
    )


def unpack_evictees(arr: np.ndarray):
    """Host-side sidecar decode: fetched output array (either wire format,
    evictees attached) → (fps (E,) i64, rows (E, 16) i32 canonical
    full-width) for the E nonzero-fingerprint evictee rows. The caller
    must KNOW the dispatch ran with evictees=True — a sidecar-less array
    is not self-distinguishing (a (3B+2)-row sidecar array and a plain
    (B'+2)-row array can share a shape)."""
    arr = np.asarray(arr)
    if arr.dtype == np.int32:
        B = (arr.shape[0] - 2) // 5
        ev = np.ascontiguousarray(arr[B:5 * B]).reshape(B, 16)
    else:
        B = (arr.shape[0] - 2) // 3
        ev64 = np.ascontiguousarray(arr[B:3 * B]).reshape(B, 8)
        lo_u = ev64 & 0xFFFFFFFF
        lo = np.where(lo_u >= (1 << 31), lo_u - (1 << 32), lo_u).astype(
            np.int32
        )
        hi = (ev64 >> 32).astype(np.int32)
        ev = np.empty((B, 16), dtype=np.int32)
        ev[:, 0::2] = lo
        ev[:, 1::2] = hi
    lo_f = ev[:, 0].astype(np.int64) & 0xFFFFFFFF
    hi_f = ev[:, 1].astype(np.int64)
    fps = (hi_f << 32) | lo_f
    keep = fps != 0
    return fps[keep], ev[keep]


# flag bits of pack_outputs' 4th column — the single source of truth for
# every host-side decoder (engine unpack, sharded un-route)
FLAG_STATUS = 1
FLAG_HIT = 2
FLAG_DROPPED = 4
# set ALONGSIDE FLAG_DROPPED for rows that never reached the kernel at all
# (a2a exchange-capacity overflow, parallel/a2a.py): such rows appear in no
# kernel stats row, so the engine counts their hit/miss/over outcome at the
# retry that finally processes them
FLAG_UNPROCESSED = 8
# set on rows whose response was fanned out from a same-key aggregation
# carrier by the in-trace dedup (dedup_packed_cols): such rows were merged
# INTO the carrier before the kernel ran, so host-side hit/miss/over
# accounting must skip them — exactly like the host planner's member rows,
# which serve_columns answers from the aggregate without counting
FLAG_MEMBER = 16
# bits 5-6: the row's priority tier (types.PRIORITY_SHIFT field of the
# request behavior word), echoed by pack_outputs so overload accounting
# reads the tier straight off the fetched array
FLAG_TIER_SHIFT = 5
FLAG_TIER_MASK = 0x3
# behavior-word priority field position (types.PRIORITY_SHIFT)
_BEH_PRIO_SHIFT = 6


def unpack_tiers(arr: np.ndarray, n: int) -> np.ndarray:
    """Per-row priority tiers from a fetched pack_outputs array (either
    wire format — the flags column layout is shared)."""
    return (
        (np.asarray(arr[:n, 3]).astype(np.int64) >> FLAG_TIER_SHIFT)
        & FLAG_TIER_MASK
    ).astype(np.int32)


def unpack_outputs(arr, n: int):
    """Decode a fetched pack_outputs array (host-side): (B+2, 4) i64 →
    ((status, limit, remaining, reset_time, dropped, hit), (cache_hits,
    cache_misses, over_limit, evicted_unexpired)). Response arrays are
    writable copies (retry fix-ups mutate them in place). Compact-wire
    outputs (int32, base-relative reset — ops/wire.py) are self-describing
    by dtype and decode through the wire module's twin."""
    if arr.dtype == np.int32:
        from gubernator_tpu.ops.wire import unpack_wire_out

        return unpack_wire_out(arr, n)
    st = (int(arr[-2, 0]), int(arr[-2, 1]), int(arr[-2, 2]), int(arr[-2, 3]))
    limit = arr[:n, 0].copy()
    remaining = arr[:n, 1].copy()
    reset = arr[:n, 2].copy()
    status = (arr[:n, 3] & FLAG_STATUS).astype(np.int32)
    hit = (arr[:n, 3] & FLAG_HIT) != 0
    dropped = (arr[:n, 3] & FLAG_DROPPED) != 0
    return (status, limit, remaining, reset, dropped, hit), st


def decide2_packed_impl(
    table: Table2, req: ReqBatch, *, write: str = "sweep", math: str = "mixed",
    evictees: bool = False,
):
    """(table', packed (B+2, 4) i64[, evictee sidecar (B, 16) i32]) — the
    sidecar element exists only under evictees=True (see decide2_impl)."""
    if evictees is True:
        table, resp, stats, ev16 = decide2_impl(
            table, req, write=write, math=math, evictees=True
        )
        return table, pack_outputs(resp, stats, req.behavior), ev16
    # False, or "defer": the tiered table's hits-only program, no sidecar
    table, resp, stats = decide2_impl(
        table, req, write=write, math=math, evictees=evictees
    )
    return table, pack_outputs(resp, stats, req.behavior)


def req_from_arr(arr: jnp.ndarray) -> ReqBatch:
    """Rebuild the ReqBatch from the single packed (12, B) int64 ingress
    array (batch.pack_host_batch) — traced inside the kernel jit so the
    casts fuse with the kernel instead of costing separate transfers."""
    return ReqBatch(
        fp=arr[0],
        algo=arr[1].astype(i32),
        behavior=arr[2].astype(i32),
        hits=arr[3],
        limit=arr[4],
        burst=arr[5],
        duration=arr[6],
        created_at=arr[7],
        expire_new=arr[8],
        greg_interval=arr[9],
        duration_eff=arr[10],
        active=arr[11] != 0,
    )


def decide2_packed_cols_impl(
    table: Table2, arr: jnp.ndarray, *, write: str = "sweep",
    math: str = "mixed", cascade: bool = False, evictees: bool = False,
) -> Tuple[Table2, jnp.ndarray]:
    """Single-transfer serving entry: packed ingress array in, packed
    output array out — one host→device put and one device→host fetch per
    dispatch regardless of column count. `cascade=True` folds cascade
    groups' combined verdicts into their carrier rows in-trace (set by the
    engine for order-preserving single-device dispatches whose batch
    carries level bits — see fold_cascade_packed). `evictees=True`
    rides the evictee sidecar home in the same fetched array
    (attach_evictees; decoded host-side by unpack_evictees)."""
    if evictees is True:
        table, packed, ev16 = decide2_packed_impl(
            table, req_from_arr(arr), write=write, math=math, evictees=True
        )
        if cascade:
            packed = fold_cascade_packed(packed, arr)
        return table, attach_evictees(packed, ev16)
    table, packed = decide2_packed_impl(
        table, req_from_arr(arr), write=write, math=math, evictees=evictees
    )
    if cascade:
        packed = fold_cascade_packed(packed, arr)
    return table, packed


decide2_packed_cols = functools.partial(
    jax.jit, donate_argnums=(0,),
    static_argnames=("write", "math", "cascade", "evictees"),
)(decide2_packed_cols_impl)


# --------------------------------------------------------- in-trace dedup
#
# The kernel's unique-fingerprint contract used to be discharged on the HOST:
# plan_passes runs an O(n log n) numpy group-by over every batch before any
# dispatch (ops/plan.py). On the mesh serving path that group-by sits on a
# single Python process's critical path while D devices idle — the staging
# bottleneck BENCH_r05 measured at 230× the device time. These helpers move
# the duplicate-key aggregation INTO the traced program (sort + segment-sum,
# the same vector recipe the GLOBAL collective already uses for cross-device
# hit merging, parallel/global_sync._sync_core), so the host ships raw
# arrival-order batches with zero planning work.
#
# Semantics: ALL duplicates aggregate — hits summed, RESET_REMAINING OR-ed,
# newest request's config wins, and every member row is answered with the
# aggregate's response (flagged FLAG_MEMBER). That is plan_passes'
# aggregated-tail rule applied from occurrence 0, i.e. the reference's own
# hot-key aggregation on the GLOBAL async path (global.go:109-123). The host
# planner's exact per-occurrence sequential passes remain available as the
# fallback and test oracle (ShardedEngine dedup="host" ≍ plan_passes;
# dedup="device" ≍ plan_passes(max_exact=1)).

RESET_REMAINING_BIT = 8  # Behavior.RESET_REMAINING (shared with ops/plan.py)
# cascade level field of the behavior word (types.CASCADE_LEVEL_SHIFT): the
# discriminator that keeps two LEVELS of one cascade from aggregating even
# when their keys collide on a fingerprint — dedup groups on (fp, level),
# and same-(fp, level) rows across different cascades still aggregate
# (tenant/global levels of many users' cascades collapse to one kernel row)
CASCADE_LEVEL_SHIFT = 8


def dedup_packed_cols(arr: jnp.ndarray):
    """Aggregate duplicate (fingerprint, cascade-level) groups of a packed
    (12, n) ingress array in-trace. Returns (deduped arr, carrier, member):

    * deduped arr — same shape/order; each group's CARRIER row (its newest
      member, plan_passes' config rule) stays active carrying the summed
      hits and OR-ed RESET_REMAINING bit; all other duplicates are
      deactivated (fp→0) so the kernel sees unique fingerprints;
    * carrier — (n,) i32, each row's carrier index (itself when unique);
    * member — (n,) bool, active rows whose response must be fanned out
      from their carrier (fanout_packed).

    Keying on (fp, level) instead of fp alone is what keeps the cascade
    machinery sound under key collisions: a user-level key that collides
    with a tenant-level key of the SAME cascade stays two kernel rows (they
    then conflict in the claim and the loser retries — sequential
    semantics), instead of silently merging two different limit configs.
    """
    fp = arr[0]
    active = arr[11] != 0
    n = fp.shape[0]
    idx = jnp.arange(n, dtype=i32)
    # inactive rows key to 0 (below every real fp, hashing.py keeps fps ≥ 1):
    # they sort into one leading segment that no active row can join
    key = jnp.where(active, fp, i64(0))
    lvl = jnp.where(
        active, (arr[2] >> CASCADE_LEVEL_SHIFT) & 0xFF, i64(0)
    ).astype(i32)
    key_s, lvl_s, idx_s = jax.lax.sort((key, lvl, idx), num_keys=2)
    first = jnp.concatenate(
        [
            jnp.ones((1,), dtype=bool),
            (key_s[1:] != key_s[:-1]) | (lvl_s[1:] != lvl_s[:-1]),
        ]
    )
    seg = jnp.cumsum(first.astype(i32)) - 1
    act_s = active[idx_s]
    hits_s = jnp.where(act_s, arr[3][idx_s], i64(0))
    seg_hits = jax.ops.segment_sum(hits_s, seg, num_segments=n)
    reset_s = jnp.where(
        act_s, arr[2][idx_s] & i64(RESET_REMAINING_BIT), i64(0)
    )
    seg_reset = jax.ops.segment_max(reset_s, seg, num_segments=n)
    # carrier = newest member = max original index (plan.py: "newest member
    # of each group carries the config")
    seg_carrier = jax.ops.segment_max(
        jnp.where(act_s, idx_s, i32(-1)), seg, num_segments=n
    )
    # un-sort each row's segment id back to original order
    _, seg_u = jax.lax.sort((idx_s, seg), num_keys=1)
    carrier = jnp.clip(seg_carrier[seg_u], 0, n - 1).astype(i32)
    is_carrier = active & (carrier == idx)
    member = active & ~is_carrier
    ded = jnp.concatenate(
        [
            jnp.where(is_carrier, fp, i64(0))[None],
            arr[1:2],
            (arr[2] | seg_reset[seg_u])[None],
            jnp.where(is_carrier, seg_hits[seg_u], i64(0))[None],
            arr[4:11],
            is_carrier.astype(i64)[None],
        ],
        axis=0,
    )
    return ded, carrier, member


def fanout_packed(
    packed: jnp.ndarray, carrier: jnp.ndarray, member: jnp.ndarray, n: int
) -> jnp.ndarray:
    """Fan each member row's response out from its aggregation carrier in
    the packed (n+2, 4) output array, marking it FLAG_MEMBER so host-side
    accounting skips it (the carrier already represents the whole group in
    the kernel's stats rows)."""
    rows = packed[:n]
    fan = rows[carrier]
    fan = fan.at[:, 3].set(fan[:, 3] | i64(FLAG_MEMBER))
    rows = jnp.where(member[:, None], fan, rows)
    return jnp.concatenate([rows, packed[n:]], axis=0)


# ------------------------------------------------------- cascade fold
#
# A CASCADE request expands into one row per limit level at the front door
# (level 0 = the carrier, levels ≥ 1 = member rows immediately following it
# — types.CASCADE_LEVEL_SHIFT). Every level is evaluated independently by
# the kernel in the SAME launch; the fold below then computes the combined
# verdict in-trace: the carrier row's status becomes OVER if ANY level
# denied, its remaining the minimum across levels, and its reset the
# latest reset among denying levels — while member rows keep their own
# per-level response (the "per-level remaining/reset" the response
# surfaces). This is the dedup/FLAG_MEMBER carrier machinery run in the
# opposite direction: members fold INTO their carrier's verdict instead of
# reading from it.
#
# The fold requires rows in ORIGINAL batch order (carrier adjacency), so it
# runs only in order-preserving traces — the single-device entries below
# with cascade=True, staged by the engine when the batch carries level bits.
# Mesh programs (routed/exchanged row order) skip it; the engine's shared
# response assembly applies the same fold host-side there
# (ops/engine._fold_cascades_host), and that host fold is idempotent over
# an already-folded carrier, so the two layers compose.


def cascade_groups(arr: jnp.ndarray):
    """(carrier, member) from a packed ingress array's behavior level bits.
    member rows are level > 0 regardless of activity (an errored member
    must not break its group's adjacency chain); carrier[i] is the nearest
    preceding level-0 row (itself for carriers/standalone rows)."""
    level = (arr[2] >> CASCADE_LEVEL_SHIFT) & 0xFF
    n = arr.shape[1]
    idx = jnp.arange(n, dtype=i32)
    member = level > 0
    carrier = _cummax(jnp.where(~member, idx, i32(-1)))
    # a leading orphan member (malformed input) folds onto itself
    carrier = jnp.where(carrier < 0, idx, carrier).astype(i32)
    return carrier, member


def fold_cascade_packed(packed: jnp.ndarray, arr: jnp.ndarray) -> jnp.ndarray:
    """Fold each cascade group's per-level verdicts into its carrier row of
    the packed (n+2, 4) output array: status OR (deny-if-any), remaining
    min, reset = latest reset among denying levels (the retry-after bound)
    when any level denies. Inactive rows (validation errors) are excluded
    from the reductions; member rows are untouched."""
    n = arr.shape[1]
    carrier, member = cascade_groups(arr)
    active = arr[11] != 0
    rows = packed[:n]
    flags = rows[:, 3]
    status = jnp.where(active, flags & i64(FLAG_STATUS), i64(0))
    over = jax.ops.segment_max(status, carrier, num_segments=n)
    big = jnp.int64(2**62)
    rem = jnp.where(active, rows[:, 1], big)
    rem_min = jax.ops.segment_min(rem, carrier, num_segments=n)
    deny_reset = jnp.where(active & (status != 0), rows[:, 2], i64(0))
    reset_max = jax.ops.segment_max(deny_reset, carrier, num_segments=n)
    is_carrier = ~member & active
    new_flags = jnp.where(is_carrier, flags | over, flags)
    new_rem = jnp.where(
        is_carrier & (rem_min < big), jnp.minimum(rows[:, 1], rem_min), rows[:, 1]
    )
    new_reset = jnp.where(
        is_carrier & (over != 0), jnp.maximum(rows[:, 2], reset_max), rows[:, 2]
    )
    rows = jnp.stack([rows[:, 0], new_rem, new_reset, new_flags], axis=1)
    return jnp.concatenate([rows, packed[n:]], axis=0)


def decide2_packed_dedup_impl(
    table: Table2, arr: jnp.ndarray, *, write: str = "sweep",
    math: str = "mixed", cascade: bool = False,
) -> Tuple[Table2, jnp.ndarray]:
    """Single-transfer serving entry with IN-TRACE duplicate aggregation:
    raw (possibly duplicate-keyed) packed ingress in, packed outputs out
    with member rows answered from their aggregation carrier. The mesh
    engines build their per-device programs on this when dedup="device"
    (parallel/sharded.py, parallel/a2a.py), which lets the host skip
    plan_passes entirely (ops/plan.single_pass). `cascade=True`
    additionally folds cascade groups' verdicts into their carriers
    (order-preserving traces only — see fold_cascade_packed)."""
    ded, carrier, member = dedup_packed_cols(arr)
    table, packed = decide2_packed_cols_impl(
        table, ded, write=write, math=math
    )
    packed = fanout_packed(packed, carrier, member, arr.shape[1])
    if cascade:
        packed = fold_cascade_packed(packed, arr)
    return table, packed


# -------------------------------------------------------------------- install


def install_payload16(inst) -> jnp.ndarray:
    """The per-row INSTALL payload stage: InstallBatch columns → canonical
    (B, 16) i32 slot rows. A pure function of the incoming batch — it never
    reads table state."""
    from gubernator_tpu.types import Algorithm

    B = inst.fp.shape[0]
    is_token = inst.algo == int(Algorithm.TOKEN_BUCKET)
    is_leaky = inst.algo == int(Algorithm.LEAKY_BUCKET)
    is_gcra = inst.algo == int(Algorithm.GCRA)
    is_win = inst.algo == int(Algorithm.SLIDING_WINDOW)
    # full-fidelity window state when the broadcast carries it (the
    # PR-11 GLOBAL fidelity fix): `aux` = previous-window count,
    # `rem_store` = the stored-style remaining (limit - current count).
    # Legacy broadcasts (None) degrade to the CONSERVATIVE weighted
    # rebuild below.
    has_aux = inst.aux is not None
    inst_aux = inst.aux if has_aux else jnp.zeros_like(inst.remaining)
    inst_rem = inst.rem_store if inst.rem_store is not None else inst.remaining
    # REM_I is remaining-style for every integer algorithm (ops/math.py
    # storage convention), so the wire rebuild installs `remaining`
    # verbatim for token and lease rows; sliding windows take the
    # stored-style remaining when the wire carries it (else the weighted
    # client remaining — conservative: interpolated usage counts as
    # current); only leaky keeps its float lane and GCRA its TAT.
    rem_i = jnp.where(
        is_leaky | is_gcra, i64(0), jnp.where(is_win, inst_rem, inst.remaining)
    )
    rem_f = jnp.where(is_leaky, inst.remaining.astype(f64), f64(0.0))
    # GCRA: with the wire rebuild's burst == limit, reset_time IS the
    # authoritative TAT (tau = limit·T ⇒ reset = tat, ops/math.py) — the
    # owner's verdict rebuilds exactly. Sliding window: the previous-window
    # count rides the broadcast aux when present (replicas then interpolate
    # the same `used` as the owner); absent, 0 — the legacy permissive
    # rebuild, tightened by the next owner broadcast.
    aux = jnp.where(is_gcra, inst.reset_time, jnp.where(is_win, inst_aux, i64(0)))
    burst = jnp.where(is_token | is_win, i64(0), inst.burst)
    # expiry: token items expire at their authoritative reset (ExpireAt =
    # CreatedAt + Duration = reset, store.go:29-35); leaky items at
    # stamp + duration (UpdatedAt basis, cache.go:35-40) — NOT reset_time,
    # whose leaky meaning (createdAt + (limit-rem)*rate) can lie in the past
    # for a near-full bucket and would expire the install on arrival. GCRA
    # state self-expires at its TAT (= reset); window/lease keep the
    # stamp + duration rule (lease reset_time == expiry by construction).
    # Sliding windows store the WINDOW START as their stamp (the
    # interpolation key, ops/math.py w_same) and expire one full window
    # past the current one — matching the owner's own writeback.
    w_dur = jnp.maximum(inst.duration, i64(1))
    w_ws = inst.now - inst.now % w_dur
    exp = jnp.where(
        is_token | is_gcra,
        inst.reset_time,
        jnp.where(is_win, w_ws + 2 * w_dur, inst.stamp + inst.duration),
    )
    flags = inst.algo | (inst.status << 8)
    sat32 = lambda x: jnp.clip(x, -(2**31), 2**31 - 1).astype(i32)
    remf_hi_f = rem_f.astype(f32)
    remf_lo_f = (rem_f - remf_hi_f.astype(f64)).astype(f32)
    remf_hi = jnp.where(
        is_leaky, jax.lax.bitcast_convert_type(remf_hi_f, i32), _hi32(aux)
    )
    remf_lo = jnp.where(
        is_leaky, jax.lax.bitcast_convert_type(remf_lo_f, i32), _lo32(aux)
    )
    stamp_eff = jnp.where(is_win, w_ws, inst.stamp)
    zero = jnp.zeros((B,), dtype=i32)
    new16 = jnp.stack(
        [
            _lo32(inst.fp),
            _hi32(inst.fp),
            sat32(inst.limit),
            sat32(burst),
            sat32(rem_i),
            flags,
            _lo32(inst.duration),
            _hi32(inst.duration),
            _lo32(stamp_eff),
            _hi32(stamp_eff),
            _lo32(exp),
            _hi32(exp),
            remf_hi,
            remf_lo,
            zero,
            zero,
        ],
        axis=1,
    )
    return new16


def install2_impl(
    table: Table2, inst, *, write: str = "xla"
) -> Tuple[Table2, jnp.ndarray]:
    """v2 analog of kernel.install_impl — install owner-authoritative GLOBAL
    statuses as fresh items (reference UpdatePeerGlobals, gubernator.go:434-474).
    Returns (table', installed_mask)."""
    layout = table.layout
    B = inst.fp.shape[0]
    NB = table.rows.shape[0]
    write = resolve_write(write, NB, B, layout)
    if write == "sparse":
        blk, u, g = sparse_geometry(NB, B)
    else:
        blk, u = sweep_geometry(NB, B)
    c = _probe_claim2(table.rows, inst.fp, inst.now, inst.active, blk, u,
                      layout)
    new16 = install_payload16(inst)
    if write == "sweep":
        rows_out = _write_sweep(table.rows, new16, c, blk, u, layout)
    elif write == "sparse":
        rows_out = _write_sparse(table.rows, new16, c, blk, u, g, layout)
    else:
        rows_out = _write_xla(table.rows, new16, c, layout)
    return Table2(rows=rows_out, layout=layout), inst.active & c.written


install2 = functools.partial(
    jax.jit, donate_argnums=(0,), static_argnames=("write",)
)(install2_impl)


# ------------------------------------------------------- conservative merge


def merge_payload16(fp, slots, lane16, owns, now):
    """The per-row MERGE payload stage: (incoming canonical slot, chosen
    stored lane, ownership mask, receiver clock) → (exists_mask, merged
    (B, 16) i32 slot rows). Implements every conservatism rule documented
    on merge2_impl — remaining=min, raw aux=max, expiry=max, OVER sticks,
    newest-stamp config."""
    g_i = lambda f: slots[:, f]
    g_s = lambda f: lane16[:, f]
    i_exp = _join64(g_i(EXP_LO), g_i(EXP_HI))
    s_exp = _join64(g_s(EXP_LO), g_s(EXP_HI))
    exists = owns & (s_exp >= now)

    i_stamp = _join64(g_i(STAMP_LO), g_i(STAMP_HI))
    s_stamp = _join64(g_s(STAMP_LO), g_s(STAMP_HI))
    i_flags = g_i(FLAGS)
    s_flags = g_s(FLAGS)
    # config carrier: the newer stamp's limit/burst/duration/algo
    keep_stored = exists & (s_stamp > i_stamp)
    pick32 = lambda i_f, s_f: jnp.where(keep_stored, s_f, i_f)
    limit = pick32(g_i(LIMIT), g_s(LIMIT))
    burst = pick32(g_i(BURST), g_s(BURST))
    algo = pick32(i_flags & 0xFF, s_flags & 0xFF)
    dur = jnp.where(
        keep_stored,
        _join64(g_s(DUR_LO), g_s(DUR_HI)),
        _join64(g_i(DUR_LO), g_i(DUR_HI)),
    )
    status = jnp.where(
        exists, jnp.maximum(i_flags >> 8, s_flags >> 8), i_flags >> 8
    )
    # REM_I is remaining-style for EVERY integer algorithm (ops/math.py
    # storage convention: token remaining, limit-current for sliding
    # windows, limit-inflight for leases), so min is uniformly the
    # tightening direction here
    rem_i = jnp.where(exists, jnp.minimum(g_i(REM_I), g_s(REM_I)), g_i(REM_I))
    to_f64 = lambda g: (
        jax.lax.bitcast_convert_type(g(REMF_HI), f32).astype(f64)
        + jax.lax.bitcast_convert_type(g(REMF_LO), f32).astype(f64)
    )
    rem_f = jnp.where(exists, jnp.minimum(to_f64(g_i), to_f64(g_s)), to_f64(g_i))
    # the raw-int REMF pair (GCRA TAT / sliding-window previous count): the
    # tightening direction is MAX — a later theoretical arrival time or a
    # larger previous-window count can only deny more. Replaying a STALE
    # checkpoint frame (smaller TAT) therefore under-grants, never over.
    # When the two sides disagree on the algorithm the config winner's raw
    # value is kept verbatim (cross-algorithm arithmetic is meaningless);
    # the float lane keeps its historical unconditional min, which for a
    # same-algo leaky pair is the conservative direction and for an algo
    # flip is "legitimately tighter than serving" (docs/durability.md).
    to_aux = lambda g: _join64(g(REMF_LO), g(REMF_HI))
    s_aux, i_aux = to_aux(g_s), to_aux(g_i)
    same_algo = exists & ((s_flags & 0xFF) == (i_flags & 0xFF))
    aux = jnp.where(
        same_algo,
        jnp.maximum(s_aux, i_aux),
        jnp.where(keep_stored, s_aux, i_aux),
    )
    exp = jnp.where(exists, jnp.maximum(s_exp, i_exp), i_exp)
    stamp = jnp.where(exists, jnp.maximum(s_stamp, i_stamp), i_stamp)

    remf_hi_f = rem_f.astype(f32)
    remf_lo_f = (rem_f - remf_hi_f.astype(f64)).astype(f32)
    from gubernator_tpu.types import Algorithm as _Algo

    aux_algo = (algo == int(_Algo.GCRA)) | (algo == int(_Algo.SLIDING_WINDOW))
    remf_hi = jnp.where(
        aux_algo, _hi32(aux), jax.lax.bitcast_convert_type(remf_hi_f, i32)
    )
    remf_lo = jnp.where(
        aux_algo, _lo32(aux), jax.lax.bitcast_convert_type(remf_lo_f, i32)
    )
    zero = jnp.zeros(fp.shape, dtype=i32)
    new16 = jnp.stack(
        [
            _lo32(fp),
            _hi32(fp),
            limit,
            burst,
            rem_i,
            algo | (status << 8),
            _lo32(dur),
            _hi32(dur),
            _lo32(stamp),
            _hi32(stamp),
            _lo32(exp),
            _hi32(exp),
            remf_hi,
            remf_lo,
            zero,
            zero,
        ],
        axis=1,
    )
    return exists, new16


def merge2_impl(
    table: Table2, fp, slots, now, active, *, write: str = "xla",
    evictees: bool = False,
):
    """Conservative merge of transferred table slots (the TransferState
    receive path, docs/robustness.md "Topology change & drain").

    Incoming rows arrive in the CANONICAL full-width slot layout ((B, 16)
    i32): extract wires carry the sender's own layout, and the receiving
    host unpacks them through ops/layout before this kernel — the one
    full-width round-trip that keeps the conservatism rules below
    layout-independent. Against an existing live entry the merge can only
    ever TIGHTEN admission — the invariant that makes a retried,
    duplicated, or crossed transfer unable to grant extra capacity:

      * remaining  = min(stored, incoming)   (integer and leaky-float lanes;
        REM_I is remaining-style for every integer algorithm, so min
        uniformly tightens)
      * raw aux lane (GCRA TAT / sliding-window prev count) = max — a later
        TAT or larger previous count can only deny more
      * expiry     = max(stored, incoming)   (state lives at least as long)
      * OVER_LIMIT sticks (status = max)
      * config (limit/burst/duration/algo) — newest stamp wins

    Absent keys install the incoming slot verbatim (claim/evict machinery
    shared with install2). Incoming rows already expired at the receiver's
    clock are dropped — stale state must not resurrect. Returns
    (table', merged_mask).

    `evictees=True` (static — the tiering promote path) additionally
    returns the (B, 16) i32 canonical rows of LIVE entries this merge's
    installs displaced, so a shadow fault-back that lands in a full
    bucket demotes the victim instead of silently destroying it — the
    invariant that makes HBM + shadow a closed state set. It is a tiered
    program like the decide's: the victim is the least recently touched
    lane and every row it writes is touched at `now`."""
    g_i = lambda f: slots[:, f]
    i_exp = _join64(g_i(EXP_LO), g_i(EXP_HI))
    active = active & (i_exp >= now)

    layout = table.layout
    B = fp.shape[0]
    NB = table.rows.shape[0]
    write = resolve_write(write, NB, B, layout)
    if write == "sparse":
        blk, u, gsteps = sparse_geometry(NB, B)
    else:
        blk, u = sweep_geometry(NB, B)

    c = _probe_claim2(
        table.rows, fp, now, active, blk, u, layout,
        victim="lru" if evictees else "expiry",
    )
    lane16 = jnp.take_along_axis(c.slots, c.chosen[:, None, None], axis=1)[
        :, 0, :
    ]
    exists, new16 = merge_payload16(fp, slots, lane16, c.owns, now)
    if evictees:
        # a promote is a use: the row comes back as the bucket's newest
        new16 = new16.at[:, TOUCH].set(touch_tick(now))
    if write == "sweep":
        rows_out = _write_sweep(table.rows, new16, c, blk, u, layout)
    elif write == "sparse":
        rows_out = _write_sparse(table.rows, new16, c, blk, u, gsteps, layout)
    else:
        rows_out = _write_xla(table.rows, new16, c, layout)
    if evictees:
        ev16 = jnp.where(c.evict_live[:, None], lane16, 0).astype(i32)
        return Table2(rows=rows_out, layout=layout), active & c.written, ev16
    return Table2(rows=rows_out, layout=layout), active & c.written


merge2 = functools.partial(
    jax.jit, donate_argnums=(0,), static_argnames=("write", "evictees")
)(merge2_impl)
