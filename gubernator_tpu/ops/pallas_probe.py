"""Fused Pallas probe→decide→write megakernel: the decide path as ONE
table-walking kernel instead of an XLA gather plus a separate write pass.

BENCH_r05 pinned the 100M-key scaling wall on HBM: the XLA decide graph
pays one uncoalesced row-gather round trip (`kernel2._probe_claim2`'s
``rows = rows_tbl[bucket]``) and a second full round trip in the
sweep/sparse write, with zero overlap between fetch and compute — at 100M
live keys the chip starves (13.4M → 9.8M decisions/s). This module runs
the whole decide path — bucket-row fetch, layout unpack, probe/claim,
algorithm math and dirty-row write-back — inside one Pallas kernel that
streams exactly the touched bucket rows through VMEM:

* the batch is **bucket-sorted** in a cheap XLA prologue (the same rank
  sort `_probe_claim2` already pays), so same-bucket requests coalesce
  into ONE fetched row slot per block — one DMA descriptor in, one out,
  however many requests share the bucket;
* the grid walks the sorted batch in blocks of ``GUBER_PROBE_BLK``
  requests with **double-buffered async row copies**: while block *i* is
  being decided, block *i+1*'s bucket rows are already in flight
  (`pltpu.make_async_copy` into the alternate VMEM slot — the pattern
  the PR-8 remote-DMA ring uses), and only rows a decision
  actually dirtied are copied back;
* a bucket whose request run straddles a block boundary is **carried**:
  its lane updates accumulate in VMEM scratch across steps and the row is
  written once, when the run ends — no block ever re-reads a row another
  block wrote, so every request observes the pre-dispatch table exactly
  like the XLA gather does.

Bit-identity contract: the claim machinery below reproduces
`_probe_claim2` decision-for-decision (owner match, exact lazy expiry,
insert rank over vacant-then-soonest-expiring lanes, owner-wins dedup,
multi-evict) and the decide/payload/response stages are literally shared
code (`kernel2.decide_payload` / `kernel2.assemble_resp`), pinned by
tests/test_pallas_probe.py across layouts × algorithms × the eviction/
dedup/reclaim corners and on the 8-device mesh. The ONE intentional
divergence: the sweep write's u-window overflow drop (`_probe_claim2`'s
``overflow``) does not exist here — the megakernel has no payload window,
so rows the XLA path would window-drop (pathological same-sweep-block
concentration past the 5-sigma Poisson bound) are simply served. The
Pallas path can only drop FEWER rows, never different decisions.

Execution: CPU backends run the kernel in interpret mode (the
`_sweep_x64_ctx` pattern) — that is what CI exercises (`probe_smoke`,
the oracle-parity suite). On TPU the kernel compiles through Mosaic; the
claim sort and the 64-bit decide lanes are the known lowering-risk spots,
which is why `GUBER_PROBE_KERNEL` defaults to ``xla`` and the bench
`probe` phase records the Pallas path per kernel × layout on the next
device run before any default flips.

Beyond decide, the same probe→payload→write structure serves the OTHER
two table walks (`walk2_pallas_impl`): GLOBAL installs (`install2`) and
conservative merges (`merge2` — region sync, handoff, tiering promotes)
run as fused probe→install/merge→write walks, sharing the claim/carry/
write machinery verbatim. Their payload stages are the factored
`kernel2.install_payload16` / `kernel2.merge_payload16` — the same
shared-stage contract that makes decide bit-identical. Selection rides
`GUBER_WALK_KERNEL` (ops/plan.py), independent of the decide knob.

Write-side overlap: dirty-row write-backs no longer serialize against
the next block. Block *g* only STARTS its write DMAs; block *g+1* waits
them (`wdirty` parity scratch) just before reusing the buffer half —
in the HBM-bound steady state stores fly concurrently with the next
block's compute and fetch-waits instead of stalling the inner loop.
The data-movement layer is its own knob (`GUBER_PROBE_MOVEMENT`) so the
deferred-wait DMA protocol is testable on CPU through the interpret
emulation, not just on device.
"""

from __future__ import annotations

import functools
import os
from types import SimpleNamespace
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from gubernator_tpu.ops.batch import BatchStats, ReqBatch, RespBatch
from gubernator_tpu.ops.kernel2 import (
    _biased,
    _hi32,
    _join64,
    _lo32,
    _sweep_x64_ctx,
    assemble_resp,
    decide_payload,
    merge_payload16,
    resolve_write,
    sparse_geometry,
)
from gubernator_tpu.ops.table2 import (
    EXP_HI,
    EXP_LO,
    FP_HI,
    FP_LO,
    K,
    Table2,
)

i64 = jnp.int64
i32 = jnp.int32

# out_resp columns (sorted-domain, un-sorted by the epilogue)
_OC_STATUS, _OC_REM, _OC_RESET, _OC_EXISTS = 0, 1, 2, 3
_OC_WRITTEN, _OC_EVICT, _OC_AUX, _OC_REMSTORE = 4, 5, 6, 7
_OUTW = 8
# with evictees=True the out rows widen by 8 int64 lanes carrying the
# CANDIDATE victim row (the claimed lane's pre-dispatch canonical 16
# fields as (hi<<32)|lo pairs). Deferred inserters' candidates ride the
# carry machinery untouched (the patch only flips _OC_WRITTEN/_OC_EVICT),
# and the epilogue masks candidates by the FINAL _OC_EVICT verdict — so
# a carried inserter killed by a later owner emits no victim row.
_OUTW_EV = 16


def probe_blk(batch: int) -> int:
    """Requests per megakernel grid step (GUBER_PROBE_BLK). The block is
    the double-buffering unit: VMEM holds 2 × BLK fetched bucket rows
    (2 × 256 × 512 B = 256 KiB at the TPU default on the full layout)
    plus the decide stage's per-row temporaries. Bigger blocks amortize
    per-step pipeline overhead; smaller ones cut the VMEM footprint and
    shorten the pipeline's fill/drain. "auto" = 256 on TPU; the whole
    batch (one grid step, no carries) on CPU interpret, where per-step
    machinery is pure overhead. Read per trace (host-side), so tuning
    runs can flip it between compiles without a restart — like
    GUBER_WRITE_SPARSE_BLK, an already-compiled dispatch shape keeps its
    traced geometry."""
    v = os.environ.get("GUBER_PROBE_BLK", "auto")
    if v == "auto":
        blk = batch if jax.default_backend() == "cpu" else 256
    else:
        blk = int(v)
    blk = max(1, min(blk, batch))
    while blk > 1 and batch % blk:
        blk //= 2
    return blk


def probe_movement(interpret: bool) -> str:
    """GUBER_PROBE_MOVEMENT: auto | interp | dma — which data-movement
    layer the megakernel traces (_make_probe_kernel docstring). "auto" is
    the measured-best pairing: the vectorized-gather + epilogue-scatter
    variant on CPU interpret backends, real async-DMA descriptors on
    device. "dma" on a CPU backend forces the DMA protocol through the
    interpret emulation — ~12× slower per dispatch, but it is the only
    host-side way to exercise the deferred write-back waits and semaphore
    accounting, which is what the movement-parity tests pin. "interp" on
    a real device is meaningless (the gather variant's epilogue scatter
    defeats the fusion) and rejected."""
    v = os.environ.get("GUBER_PROBE_MOVEMENT", "auto")
    if v not in ("auto", "interp", "dma"):
        raise ValueError(
            f"GUBER_PROBE_MOVEMENT must be auto, interp or dma, got {v!r}"
        )
    if v == "auto":
        return "interp" if interpret else "dma"
    if v == "interp" and not interpret:
        raise ValueError(
            "GUBER_PROBE_MOVEMENT=interp is CPU-interpret-only; device "
            "backends must run the DMA movement"
        )
    return v


def hbm_bytes_per_decision(
    layout, batch: int, n_buckets: int, write: str, probe: str = "xla"
) -> float:
    """Roofline model: HBM bytes the table walk moves per decision, from
    the layout's row width, the dispatch geometry and the write mode —
    the denominator of the "is the chip HBM-bound?" argument
    (docs/kernel.md "Probe pipeline"). A model: the measured share is
    the benchmark's `decide_roofline`, from the trace.

    Per decision the PROBE reads one bucket row (`layout.row` i32 lanes).
    The write side depends on the mode: the dense sweep streams the whole
    table through VMEM and back (2 · NB · row_bytes amortized over the
    batch); the sparse grid touches its dirty blocks both ways; the XLA
    scatter writes one slot. The fused Pallas kernel reads one row and
    writes back only dirty rows — worst case one full row per decision,
    with same-bucket coalescing only lowering it. The model is the
    WORST case (every request a distinct bucket, every row dirtied): real
    batches with duplicate buckets or read-only rows move fewer bytes."""
    row_b = float(layout.row * 4)
    read = row_b
    if probe == "pallas":
        return read + row_b
    w = resolve_write(write, n_buckets, batch, layout)
    if w == "sweep":
        write_b = 2.0 * n_buckets * row_b / max(batch, 1)
    elif w == "sparse":
        blk, _u, g = sparse_geometry(n_buckets, batch)
        write_b = 2.0 * min(g * blk, n_buckets) * row_b / max(batch, 1)
    else:  # xla scatter: slot-granular write
        write_b = float(layout.slot_bytes)
    return read + write_b


# --------------------------------------------------------------- prologue


def _req_lanes(req: ReqBatch) -> jnp.ndarray:
    """The decide stage's (12, B) i64 kernel ingress (req_from_arr
    layout); ONE gather in _sorted_schedule permutes every column at
    once."""
    return jnp.stack(
        [
            req.fp,
            req.algo.astype(i64),
            req.behavior.astype(i64),
            req.hits,
            req.limit,
            req.burst,
            req.duration,
            req.created_at,
            req.expire_new,
            req.greg_interval,
            req.duration_eff,
            req.active.astype(i64),
        ]
    )


def _sorted_schedule(fp, active, arrN, NB: int, rblk: int):
    """Bucket-sort the batch and derive the megakernel's block schedule.

    `arrN` is the stage's (N, B) i64 ingress lane stack — the 12 decide
    request columns (_req_lanes) or the 11 walk lanes (fp, now, active,
    8 payload pairs; walk2_pallas_impl). Returns (idx_s, arr_s, meta, sb,
    bkf, G):
      * idx_s    — (B,) i32 original index at each sorted position (the
                   epilogue's un-sort key);
      * arr_s    — (N, B) i64 sorted ingress lanes (the kernel's blocked
                   ingress);
      * meta     — (3, B) i32 [sort key, VMEM row slot, fetch bucket];
      * sb       — (G·rblk,) i32 per-(block, slot) bucket to fetch,
                   sentinel NB for unused slots (the DMA index vector);
      * bkf      — (G,) i32 first sort key of each block (the carry's
                   continuation test).

    The sort key is the bucket for active rows and NB (past every real
    bucket) for inactive ones — the exact `bkey` `_probe_claim2` ranks
    with, so segment-local rank/dedup below reproduce the sorted-domain
    machinery. Fetches use the REAL bucket (fp % NB) for every row,
    matching the XLA gather byte-for-byte (inactive rows gather their
    bucket too; their decide outputs are masked identically).

    Slot assignment dedups buckets GLOBALLY within each block (not just
    consecutive runs): every distinct bucket a block touches — including
    an inactive row whose bucket another row already fetches — maps to
    one VMEM slot, so it costs one DMA descriptor each way and the
    write-back scatter never carries duplicate row indices."""
    B = fp.shape[0]
    G = B // rblk
    bucket = (fp % NB).astype(i32)
    bkey = jnp.where(active, bucket, i32(NB))
    idx = jnp.arange(B, dtype=i32)
    bkey_s, idx_s = jax.lax.sort((bkey, idx), num_keys=1)
    fbucket_s = bucket[idx_s]

    arr_s = arrN[:, idx_s]

    pos = jnp.arange(B, dtype=i32)
    blk_id = pos // i32(rblk)
    # dense rank of distinct (block, bucket) pairs within each block: sort
    # by the pair key, count firsts, subtract the count at the block start
    key = blk_id.astype(i64) * i64(NB + 1) + fbucket_s.astype(i64)
    key_s2, pos_s2 = jax.lax.sort((key, pos), num_keys=1)
    kfirst = jnp.concatenate(
        [jnp.ones((1,), dtype=bool), key_s2[1:] != key_s2[:-1]]
    )
    bo = (key_s2 // i64(NB + 1)).astype(i32)
    bstart = jnp.concatenate(
        [jnp.ones((1,), dtype=bool), bo[1:] != bo[:-1]]
    )
    cs = jnp.cumsum(kfirst.astype(i32))
    base = jax.lax.cummax(jnp.where(bstart, cs - 1, -1))
    slot_s2 = (cs - 1 - base).astype(i32)
    rs = jnp.zeros((B,), dtype=i32).at[pos_s2].set(slot_s2)

    sb = jnp.full((B,), NB, dtype=i32).at[blk_id * i32(rblk) + rs].set(
        fbucket_s
    )
    bkf = bkey_s[:: rblk]
    meta = jnp.stack([bkey_s, rs, fbucket_s])
    return idx_s, arr_s, meta, sb, bkf, G


# --------------------------------------------------------------- kernel


def _make_probe_kernel(layout, rblk: int, NB: int, G: int, math: str,
                       interp: bool, evictees: bool = False,
                       stage: str = "decide"):
    """Kernel factory (closes over static geometry + layout + math mode).

    `stage` (static) picks the payload computed between probe and write —
    "decide" (kernel2.decide_payload, the request path), "install"
    (prologue-precomputed install_payload16 rows ride the ingress lanes;
    the kernel just unjoins them) or "merge" (kernel2.merge_payload16
    against the VMEM-resident claimed lane). Claim, carry, compose and
    write machinery are IDENTICAL across stages — that is the point: the
    fused walk inherits the decide kernel's proven coalescing and
    carry-correctness wholesale.

    Scratch protocol (persists across grid steps):
      fbuf  (2, rblk, rowl)  double-buffered fetched bucket rows
      wdirty (2, rblk)       per-parity dirty masks of IN-FLIGHT write-
                             backs: block g only STARTS its dirty-row
                             copies; the step that reuses that buffer
                             half (g+1, before refilling it) waits them —
                             write-backs overlap the next block's compute
                             instead of stalling the inner loop
      obuf  (rblk, _OUTW)    per-block response staging (DMA'd per step)
      cstage (1, rowl)       carry-flush row staging
      pstage (K, _OUTW)      deferred-response patch staging
      crow  (1, rowl)        carried bucket's ORIGINAL fetched row
      cop/cip (K, F)         carried owner / inserter lane payloads
      cmask (2, K)           carried owner / inserter lane counts
      cdo   (K, _OUTW)       deferred inserter responses (indexed by RANK —
                             ranks are unique across the whole carried
                             segment, so slots never collide)
      cdmeta (4, K)          deferred rowid / lane / valid / evictable
      cscal SMEM (8,)        [carry_valid, carry_sort_key, carry_rank,
                              carry_fetch_bucket, …]

    Carry invariant: a bucket's row is fetched by every block whose
    requests probe it (all read the pre-dispatch bytes — no block writes
    a row a later block fetches) and written by exactly the step where its
    sorted run ends, composed as owner-payload > inserter-payload >
    original per lane. Inserters of a still-open run are DEFERRED: their
    written/evicted verdict depends on owners later in the run, so their
    response rows are patched at flush time from the accumulated owner
    mask (at most K per run — ranks ≥ K are dropped regardless).

    `interp` (static, = CPU backend) swaps the DATA-MOVEMENT layer only:
    fetches become one vectorized ref gather per block, and instead of
    writing table rows in-kernel the composed dirty rows + their target
    buckets leave through dedicated outputs that the entry's XLA epilogue
    scatters into the DONATED table once (`_write_xla`'s own in-place
    pattern). Both alternatives were measured and rejected: the interpret
    emulation walks per-row DMA descriptors one dynamic-update-slice at a
    time (~12× the whole XLA path per dispatch), and an in-kernel ref
    SCATTER on the aliased table state forces the discharge machinery
    into a full-table copy per call (~30 ms at 128 MiB — the state is
    both read and swapped in one jaxpr). Claim, decide, compose and
    carry logic are shared byte-for-byte between the variants; the
    oracle-parity suite runs the interp movement, the bench `probe`
    phase exercises the DMA movement on device."""
    from gubernator_tpu.ops.math import StoredState  # noqa: F401 (doc link)

    Fl = layout.F
    rowl = layout.row

    def kern(sb_ref, bkf_ref, arr_ref, meta_ref, sbv_ref, tbl_ref, *rest):
        if interp:
            # slot-payload staging outputs + the epilogue-scatter protocol
            # (factory docstring); the table is a read-only input here
            (ptgt_out, pay_out, ctgt_out, crows_out, resp_out) = rest[:5]
            (fbuf, wdirty, obuf, cstage, pstage, crow, cop, cip, cmask,
             cdo, cdmeta, cscal, fsem, wsem, osem, psem) = rest[5:]
            rows_out = None
        else:
            rows_out, resp_out = rest[:2]
            (fbuf, wdirty, obuf, cstage, pstage, crow, cop, cip, cmask,
             cdo, cdmeta, cscal, fsem, wsem, osem, psem) = rest[2:]
        NBc = i32(NB)
        lane_iota_k = jax.lax.broadcasted_iota(i32, (rblk, K), 1)
        g = pl.program_id(0)
        p = jax.lax.rem(g, i32(2))

        @pl.when(g == i32(0))
        def _():
            cscal[0] = i32(0)  # no carry before the first block

        # ---------------- fetch wait + prefetch (double buffer) ----------
        def fetch_copy(blk_i32, parity, n):
            b = sb_ref[blk_i32 * i32(rblk) + n]
            return pltpu.make_async_copy(
                tbl_ref.at[b], fbuf.at[parity, n], fsem
            )

        sbb = sbv_ref[0, :]  # (rblk,) this block's slot→bucket vector
        if interp:
            fb = None  # per-request gather below — no slot indirection
        else:
            @pl.when(g == i32(0))
            def _():
                def issue0(n, c):
                    @pl.when(sb_ref[n] < NBc)
                    def _():
                        fetch_copy(i32(0), i32(0), n).start()
                    return c
                jax.lax.fori_loop(0, rblk, issue0, 0)

            def wait_cur(n, c):
                @pl.when(sb_ref[g * i32(rblk) + n] < NBc)
                def _():
                    fetch_copy(g, p, n).wait()
                return c
            jax.lax.fori_loop(0, rblk, wait_cur, 0)

            # retire block g-1's still-in-flight write-backs BEFORE the
            # next fetch refills their source buffer half (fbuf[1-p]) —
            # the only ordering the deferred-write protocol needs.
            # Bucket-sorted runs guarantee no later block FETCHES a row
            # an earlier block writes (the carry owns straddlers), so
            # the stores can fly concurrently with this block's fetch
            # waits and compute.
            def write_copy(blk_i32, parity, n):
                return pltpu.make_async_copy(
                    fbuf.at[parity, n],
                    rows_out.at[sb_ref[blk_i32 * i32(rblk) + n]],
                    wsem,
                )

            @pl.when(g > i32(0))
            def _():
                def wait_prev(n, c):
                    dn = jax.lax.dynamic_index_in_dim(
                        wdirty[i32(1) - p], n, keepdims=False
                    )
                    @pl.when(
                        (sb_ref[(g - i32(1)) * i32(rblk) + n] < NBc)
                        & (dn != 0)
                    )
                    def _():
                        write_copy(g - i32(1), i32(1) - p, n).wait()
                    return c
                jax.lax.fori_loop(0, rblk, wait_prev, 0)

            @pl.when(g + i32(1) < i32(G))
            def _():
                def issue_next(n, c):
                    @pl.when(sb_ref[(g + i32(1)) * i32(rblk) + n] < NBc)
                    def _():
                        fetch_copy(g + i32(1), i32(1) - p, n).start()
                    return c
                jax.lax.fori_loop(0, rblk, issue_next, 0)
            fb = fbuf[p]

        # ---------------- probe + claim (block-local `_probe_claim2`) ----
        arr = arr_ref[...]  # (NL, rblk) i64 sorted ingress lanes
        if stage == "decide":
            reqb = ReqBatch(
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
            fpv = reqb.fp
            active = reqb.active
            now = reqb.created_at
            in16 = None
        else:
            # walk ingress (walk2_pallas_impl): [fp, now, active,
            # 8 × (hi<<32)|lo payload pairs] — the incoming canonical
            # (rblk, 16) i32 rows, unjoined losslessly in-register
            fpv = arr[0]
            now = arr[1]
            active = arr[2] != 0
            pairs_t = arr[3:11].T  # (rblk, 8)
            in16 = jnp.stack(
                [_lo32(pairs_t), _hi32(pairs_t)], axis=-1
            ).reshape(rblk, 16)
        bk = meta_ref[0, :]  # (rblk,) sort keys
        rs = meta_ref[1, :]  # VMEM row slot per request

        # rows_r: (rblk, rowl) each request's bucket row — pre-dispatch
        # bytes in both movement variants (no block ever reads a row
        # another block wrote). The interp gather goes per request (the
        # XLA oracle's own access pattern, one gather op); the DMA path
        # reads each distinct bucket's row once from its VMEM slot.
        if interp:
            rows_r = tbl_ref[meta_ref[2, :]]
        else:
            rows_r = jnp.take(fb, rs, axis=0)
        slots = layout.unpack(rows_r.reshape(rblk, K, Fl))  # (rblk, K, 16)

        my_lo = _lo32(fpv)
        my_hi = _hi32(fpv)
        s_fp_lo = slots[:, :, FP_LO]
        s_fp_hi = slots[:, :, FP_HI]
        empty = (s_fp_lo == 0) & (s_fp_hi == 0)
        match = (
            (s_fp_lo == my_lo[:, None]) & (s_fp_hi == my_hi[:, None])
            & ~empty & active[:, None]
        )
        owns = match.any(axis=1)
        own_j = jnp.argmax(match, axis=1).astype(i32)

        exp_lo_k = slots[:, :, EXP_LO]
        exp_hi_k = slots[:, :, EXP_HI]
        now_hi = _hi32(now)
        now_lo_b = _biased(_lo32(now))
        dead = ~empty & (
            (exp_hi_k < now_hi[:, None])
            | ((exp_hi_k == now_hi[:, None])
               & (_biased(exp_lo_k) < now_lo_b[:, None]))
        )
        vacant = empty | dead
        live = ~vacant

        # segments over the sort key; the first segment may continue the
        # carried run from the previous block
        first = jnp.concatenate(
            [jnp.ones((1,), dtype=bool), bk[1:] != bk[:-1]]
        )
        seg = jnp.cumsum(first.astype(i32)) - 1
        in_seg0 = seg == 0
        if G > 1:
            cvalid = cscal[0]
            cont = (cvalid != i32(0)) & (bk[0] == cscal[1])
            crank = cscal[2]
            carry_om = cmask[0, :]  # (K,) carried owner counts
        else:
            # single-block grid: no run can straddle, the whole carry
            # plane (and its scratch traffic) drops out of the trace
            cont = jnp.bool_(False)
            crank = i32(0)
            carry_om = jnp.zeros((K,), dtype=i32)

        need = active & ~owns
        csum = jnp.cumsum(need.astype(i32))
        c_excl = csum - need
        seg_base = jax.lax.cummax(jnp.where(first, c_excl, -1))
        rank = (c_excl - seg_base).astype(i32) + jnp.where(
            cont & in_seg0, crank, i32(0)
        )

        # owner lane occupancy over the WHOLE segment (carry included):
        # the dedup authority — an inserter whose chosen lane any owner of
        # its bucket holds is dropped (owner wins, `_probe_claim2`'s
        # sorted-dup rule)
        ownerhot = (
            (lane_iota_k == own_j[:, None]) & owns[:, None]
        ).astype(i32)
        seg_own = jax.ops.segment_sum(ownerhot, seg, num_segments=rblk)
        om = (jnp.take(seg_own, seg, axis=0) > 0) | (
            (cont & in_seg0)[:, None] & (carry_om > 0)[None, :]
        )
        # earlier-owner counts (duplicate-fp robustness: first owner wins)
        pre_own = jnp.cumsum(ownerhot, axis=0) - ownerhot
        seg_base_own = jax.lax.cummax(
            jnp.where(first[:, None], pre_own, -1), axis=0
        )
        earlier = pre_own - seg_base_own + jnp.where(
            (cont & in_seg0)[:, None], carry_om[None, :], 0
        )
        own_earlier = jnp.take_along_axis(earlier, own_j[:, None], axis=1)[
            :, 0
        ]
        owner_killed = owns & (own_earlier > 0)

        # candidate lane order: the EXACT `_probe_claim2` sort — vacant
        # lanes first (by index), then live lanes by soonest expiry
        _, _, _, cand = jax.lax.sort(
            (live.astype(i32), exp_hi_k, _biased(exp_lo_k), lane_iota_k),
            num_keys=3, dimension=1,
        )
        rank_c = jnp.clip(rank, 0, K - 1)
        ins_lane = jnp.take_along_axis(cand, rank_c[:, None], axis=1)[:, 0]
        chosen = jnp.where(owns, own_j, ins_lane).astype(i32)
        claim_ok = need & (rank < K)
        got = active & (owns | claim_ok)
        lane_live = jnp.take_along_axis(live, chosen[:, None], axis=1)[:, 0]
        killed_ins = claim_ok & jnp.take_along_axis(
            om, chosen[:, None], axis=1
        )[:, 0]
        written = got & ~killed_ins & ~owner_killed

        # ---------------- payload (shared stage, bit-identical) ----------
        lane16 = jnp.take_along_axis(
            slots, chosen[:, None, None], axis=1
        )[:, 0, :]
        if stage == "decide":
            exists, d, new16 = decide_payload(lane16, reqb, owns, math=math)
        elif stage == "install":
            # install rows are a pure function of the batch — precomputed
            # by the entry's install_payload16 prologue, they ride the
            # ingress lanes; owners overwrite their lane unconditionally
            # (install2's own rule), so exists is bookkeeping only
            d = None
            exists = owns
            new16 = in16
        else:  # merge
            d = None
            exists, new16 = merge_payload16(fpv, in16, lane16, owns, now)
        pay = layout.pack(new16)  # (rblk, Fl)

        # ---------------- segment classification -------------------------
        nseg = seg[rblk - 1] + 1
        last_seg = seg == (nseg - 1)
        if G > 1:
            nxt_key = bkf_ref[jnp.minimum(g + i32(1), i32(G - 1))]
            cont_next = (g + i32(1) < i32(G)) & (nxt_key == bk[rblk - 1])
        else:
            cont_next = jnp.bool_(False)
        in_carry = (cont & in_seg0) | (cont_next & last_seg)

        # ---------------- in-block compose + dirty-row write-back --------
        wr_now = written & ~in_carry
        if interp:
            # stage each WRITTEN row's packed payload + its global slot
            # target for the entry's epilogue scatter (unwritten/carried
            # rows redirect to the out-of-bounds sentinel and drop) —
            # `_write_xla`'s own slot-granular pattern, one scatter per
            # dispatch instead of per-row copies
            ptgt_out[0, pl.ds(g * i32(rblk), rblk)] = jnp.where(
                wr_now, meta_ref[2, :] * i32(K) + chosen, i32(NB * K)
            )
            pay_out[pl.ds(g * i32(rblk), rblk)] = pay
        else:
            tgt = jnp.where(wr_now, rs * i32(K) + chosen, i32(rblk * K))
            fb_new = (
                fb.reshape(rblk * K, Fl)
                .at[tgt].set(pay, mode="drop")
                .reshape(rblk, rowl)
            )
            dirty = (
                jnp.zeros(rblk * K + 1, dtype=bool)
                .at[tgt].set(True, mode="drop")[: rblk * K]
                .reshape(rblk, K)
                .any(axis=1)
            )
            fbuf[p] = fb_new
            wdirty[p] = dirty.astype(i32)

            # START the dirty-row copies only — the step that next reuses
            # this buffer half waits them (wait_prev above), overlapping
            # the stores with block g+1's compute; the final grid step
            # retires its own writes before the kernel exits
            def write_row(n, c):
                dn = jax.lax.dynamic_index_in_dim(
                    wdirty[p], n, keepdims=False
                )
                @pl.when((sb_ref[g * i32(rblk) + n] < NBc) & (dn != 0))
                def _():
                    write_copy(g, p, n).start()
                return c
            jax.lax.fori_loop(0, rblk, write_row, 0)

            @pl.when(g == i32(G - 1))
            def _():
                def wait_last(n, c):
                    dn = jax.lax.dynamic_index_in_dim(
                        wdirty[p], n, keepdims=False
                    )
                    @pl.when((sb_ref[g * i32(rblk) + n] < NBc) & (dn != 0))
                    def _():
                        write_copy(g, p, n).wait()
                    return c
                jax.lax.fori_loop(0, rblk, wait_last, 0)

        # ---------------- per-block responses -----------------------------
        evict = claim_ok & lane_live & written
        if stage == "decide":
            outb = jnp.stack(
                [
                    d.resp_status.astype(i64),
                    d.resp_rem,
                    d.resp_reset,
                    exists.astype(i64),
                    written.astype(i64),
                    evict.astype(i64),
                    d.aux_out,
                    d.rem_i_out,
                ],
                axis=1,
            )  # (rblk, _OUTW)
        else:
            # walks answer only the masks; the response columns keep the
            # decide width so the carry patch machinery (cdo rows, the
            # _OC_WRITTEN/_OC_EVICT flips) is shared untouched
            z = jnp.zeros((rblk,), dtype=i64)
            outb = jnp.stack(
                [
                    z, z, z,
                    exists.astype(i64),
                    written.astype(i64),
                    evict.astype(i64),
                    z, z,
                ],
                axis=1,
            )  # (rblk, _OUTW)
        if evictees:
            # candidate victim row (pre-dispatch claimed-lane state); the
            # FINAL verdict is the patched _OC_EVICT — epilogue masks
            ev16 = jnp.where(
                (claim_ok & lane_live)[:, None], lane16, 0
            ).astype(i32)
            outb = jnp.concatenate(
                [outb, _join64(ev16[:, 0::2], ev16[:, 1::2])], axis=1
            )  # (rblk, _OUTW_EV)
        if interp:
            resp_out[pl.ds(g * i32(rblk), rblk)] = outb
        else:
            obuf[...] = outb
            oc = pltpu.make_async_copy(
                obuf, resp_out.at[pl.ds(g * i32(rblk), rblk)], osem
            )
            oc.start()
            oc.wait()

        # ---------------- carry resolution --------------------------------
        if G == 1:
            # single-block grid: no run can straddle a boundary, so the
            # whole carry plane below never traces
            return
        jpos = jax.lax.broadcasted_iota(i32, (rblk,), 0)
        if interp:
            # default: this step flushes nothing (the epilogue drops the
            # sentinel target); at most ONE flush can happen per step —
            # the old-carry and run-ends-here cases are mutually exclusive
            ctgt_out[0, g] = NBc

        def flush_carry():
            """Write the carried bucket's composed row + patch deferred
            responses from the FINAL owner mask."""
            com = cmask[0, :] > 0
            cim = cmask[1, :] > 0
            crow_slots = crow[0].reshape(K, Fl)
            final = jnp.where(
                com[:, None], cop[...],
                jnp.where((cim & ~com)[:, None], cip[...], crow_slots),
            )
            @pl.when((com | cim).any() & (cscal[3] < NBc))
            def _():
                if interp:
                    ctgt_out[0, g] = cscal[3]
                    crows_out[pl.ds(g, 1)] = final.reshape(1, rowl)
                else:
                    cstage[0] = final.reshape(rowl)
                    fc = pltpu.make_async_copy(
                        cstage.at[0], rows_out.at[cscal[3]], psem
                    )
                    fc.start()
                    fc.wait()

            def patch(k, c):
                @pl.when(cdmeta[2, k] != i32(0))
                def _():
                    lane = cdmeta[1, k]
                    killed = (
                        jax.lax.dynamic_index_in_dim(
                            cmask[0, :], lane, keepdims=False
                        ) > 0
                    )
                    wr = jnp.where(killed, i64(0), i64(1))
                    row = cdo[k]
                    row = row.at[_OC_WRITTEN].set(wr)
                    row = row.at[_OC_EVICT].set(row[_OC_EVICT] * wr)
                    if interp:
                        resp_out[cdmeta[0, k]] = row
                    else:
                        pstage[k] = row
                        pc = pltpu.make_async_copy(
                            pstage.at[k], resp_out.at[cdmeta[0, k]], psem
                        )
                        pc.start()
                        pc.wait()
                return c
            jax.lax.fori_loop(0, K, patch, 0)
            cscal[0] = i32(0)

        def clear_carry():
            cmask[...] = jnp.zeros((2, K), dtype=i32)
            cop[...] = jnp.zeros((K, Fl), dtype=i32)
            cip[...] = jnp.zeros((K, Fl), dtype=i32)
            cdmeta[...] = jnp.zeros((4, K), dtype=i32)

        def accumulate(sel):
            """Fold this block's rows of segment `sel` into the carry:
            rank offset, owner/inserter lane payloads + counts, deferred
            inserter responses (slot = rank, unique across the run)."""
            cscal[2] = cscal[2] + jnp.sum(
                (need & sel).astype(i32), dtype=i32
            )
            own_sel = sel & owns & got & ~owner_killed
            o_hot = ownerhot * own_sel[:, None].astype(i32)  # (rblk, K)
            cmask[0, :] = cmask[0, :] + o_hot.sum(axis=0).astype(i32)
            cop[...] = cop[...] + jnp.einsum(
                "rk,rf->kf", o_hot, pay
            ).astype(i32)
            ins_sel = sel & claim_ok
            i_hot = (
                (lane_iota_k == chosen[:, None]) & ins_sel[:, None]
            ).astype(i32)
            cmask[1, :] = cmask[1, :] + i_hot.sum(axis=0).astype(i32)
            cip[...] = cip[...] + jnp.einsum(
                "rk,rf->kf", i_hot, pay
            ).astype(i32)
            # deferred responses, keyed by rank (< K for every ins_sel row)
            rk = jnp.where(ins_sel, rank, i32(K))
            cdo[...] = cdo[...].at[rk].set(outb, mode="drop")
            cdmeta[0, :] = cdmeta[0, :].at[rk].set(
                g * i32(rblk) + jpos, mode="drop"
            )
            cdmeta[1, :] = cdmeta[1, :].at[rk].set(chosen, mode="drop")
            cdmeta[2, :] = cdmeta[2, :].at[rk].set(
                jnp.ones((rblk,), dtype=i32), mode="drop"
            )
            cdmeta[3, :] = cdmeta[3, :].at[rk].set(
                (claim_ok & lane_live).astype(i32), mode="drop"
            )

        # A: a carried run that did NOT continue ended at the last block
        @pl.when((cvalid != i32(0)) & ~cont)
        def _():
            flush_carry()

        # B: continuing run — fold this block's head segment in; flush if
        # the run ends inside this block (or the grid ends)
        @pl.when(cont)
        def _():
            accumulate(in_seg0)
        @pl.when(cont & ((nseg > 1) | ~cont_next))
        def _():
            flush_carry()

        # C: a run that straddles INTO the next block opens a new carry
        @pl.when(cont_next & ~(cont & (nseg == 1)))
        def _():
            clear_carry()
            cscal[0] = i32(1)
            cscal[1] = bk[rblk - 1]
            cscal[2] = i32(0)
            cscal[3] = meta_ref[2, rblk - 1]  # real fetch bucket
            crow[0] = rows_r[rblk - 1]
            accumulate(last_seg)

    return kern


# --------------------------------------------------------------- entry


def _launch_walk(table: Table2, arr_s, meta, sb, bkf, G: int, rblk: int, *,
                 math: str, evictees: bool, stage: str):
    """Shared pallas_call scaffolding for every stage (decide + the
    install/merge walks): block the sorted ingress lanes, wire the
    scratch protocol, run the kernel, and — interp movement — apply the
    staged slot/carry writes to the DONATED table in one epilogue
    scatter. Returns (rows_out, resp_s), responses still in sorted
    order."""
    layout = table.layout
    NB = table.rows.shape[0]
    nl, B = arr_s.shape
    outw = _OUTW_EV if evictees else _OUTW

    interpret = jax.default_backend() == "cpu"
    interp = probe_movement(interpret) == "interp"
    if interp:
        # slot-payload staging outputs; the table stays a read-only input
        # and the donated-scatter epilogue below applies the writes in
        # place (_make_probe_kernel docstring: an in-kernel ref scatter on
        # the aliased state costs a full-table copy under the discharge)
        out_shape = (
            jax.ShapeDtypeStruct((1, B), jnp.int32),  # ptgt (slot ids)
            jax.ShapeDtypeStruct((B, layout.F), jnp.int32),  # pay
            jax.ShapeDtypeStruct((1, G), jnp.int32),  # ctgt
            jax.ShapeDtypeStruct((G, layout.row), jnp.int32),  # crows
            jax.ShapeDtypeStruct((B, outw), jnp.int64),  # resp
        )
        out_specs = [pl.BlockSpec(memory_space=pl.ANY)] * 5
        aliases = {}
    else:
        out_shape = (
            jax.ShapeDtypeStruct(table.rows.shape, table.rows.dtype),
            jax.ShapeDtypeStruct((B, outw), jnp.int64),
        )
        out_specs = [pl.BlockSpec(memory_space=pl.ANY)] * 2
        aliases = {5: 0}
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(G,),
        in_specs=[
            pl.BlockSpec((nl, rblk), lambda g, sb, bkf: (0, g)),
            pl.BlockSpec((3, rblk), lambda g, sb, bkf: (0, g)),
            pl.BlockSpec((1, rblk), lambda g, sb, bkf: (0, g)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((2, rblk, layout.row), jnp.int32),  # fbuf
            pltpu.VMEM((2, rblk), jnp.int32),  # wdirty
            pltpu.VMEM((rblk, outw), jnp.int64),  # obuf
            pltpu.VMEM((1, layout.row), jnp.int32),  # cstage
            pltpu.VMEM((K, outw), jnp.int64),  # pstage
            pltpu.VMEM((1, layout.row), jnp.int32),  # crow
            pltpu.VMEM((K, layout.F), jnp.int32),  # cop
            pltpu.VMEM((K, layout.F), jnp.int32),  # cip
            pltpu.VMEM((2, K), jnp.int32),  # cmask
            pltpu.VMEM((K, outw), jnp.int64),  # cdo
            pltpu.VMEM((4, K), jnp.int32),  # cdmeta
            pltpu.SMEM((8,), jnp.int32),  # cscal
            pltpu.SemaphoreType.DMA,  # fsem
            pltpu.SemaphoreType.DMA,  # wsem
            pltpu.SemaphoreType.DMA,  # osem
            pltpu.SemaphoreType.DMA,  # psem
        ],
    )
    with _sweep_x64_ctx(interpret):
        outs = pl.pallas_call(
            _make_probe_kernel(layout, rblk, NB, G, math, interp,
                               evictees, stage),
            interpret=interpret,
            out_shape=out_shape,
            grid_spec=grid_spec,
            input_output_aliases=aliases,
        )(sb, bkf, arr_s, meta, sb.reshape(1, G * rblk), table.rows)
    if interp:
        ptgt, pay_s, ctgt, crows, resp_s = outs
        # the table write: one slot-granular scatter of the written rows'
        # payloads (`_write_xla`'s own pattern), then the carried buckets'
        # composed rows (disjoint target sets — a carried bucket is never
        # composed in-block); sentinel targets drop
        slot_view = table.rows.reshape(NB * K, layout.F)
        rows_out = (
            slot_view.at[ptgt[0]].set(pay_s, mode="drop")
            .reshape(NB, layout.row)
        )
        if G > 1:  # single-block grids carry (and flush) nothing
            rows_out = rows_out.at[ctgt[0]].set(crows, mode="drop")
    else:
        rows_out, resp_s = outs
    return rows_out, resp_s


def decide2_pallas_impl(
    table: Table2, req: ReqBatch, *, math: str = "mixed",
    evictees: bool = False,
):
    """Fused-megakernel twin of `kernel2.decide2_impl` (reached through its
    ``probe="pallas"`` switch — call sites never import this directly).
    Same signature contract: (table', RespBatch, BatchStats), decision-
    bit-identical modulo the sweep-window divergence documented above.
    ``evictees=True`` (static) widens the out rows by the candidate-victim
    lanes (_OUTW_EV) and returns a 4th element: the (B, 16) i32 evictee
    sidecar, victim rows where the final evict verdict holds."""
    layout = table.layout
    NB = table.rows.shape[0]
    B = req.fp.shape[0]
    rblk = probe_blk(B)
    idx_s, arr_s, meta, sb, bkf, G = _sorted_schedule(
        req.fp, req.active, _req_lanes(req), NB, rblk
    )
    rows_out, resp_s = _launch_walk(
        table, arr_s, meta, sb, bkf, G, rblk,
        math=math, evictees=evictees, stage="decide",
    )
    outw = _OUTW_EV if evictees else _OUTW

    # un-sort the response rows back to batch order
    out = jnp.zeros((B, outw), dtype=i64).at[idx_s].set(resp_s)
    d_like = SimpleNamespace(
        resp_status=out[:, _OC_STATUS].astype(i32),
        resp_rem=out[:, _OC_REM],
        resp_reset=out[:, _OC_RESET],
        aux_out=out[:, _OC_AUX],
        rem_i_out=out[:, _OC_REMSTORE],
    )
    exists = out[:, _OC_EXISTS] != 0
    written = out[:, _OC_WRITTEN] != 0
    evict_live = out[:, _OC_EVICT] != 0
    resp, stats = assemble_resp(req, d_like, exists, written, evict_live)
    if evictees:
        evcols = out[:, _OUTW:]  # (B, 8) i64 candidate victim pairs
        ev16 = jnp.stack(
            [_lo32(evcols), _hi32(evcols)], axis=-1
        ).reshape(B, 16)
        ev16 = jnp.where(evict_live[:, None], ev16, 0)
        return Table2(rows=rows_out, layout=layout), resp, stats, ev16
    return Table2(rows=rows_out, layout=layout), resp, stats


decide2_pallas = functools.partial(
    jax.jit, donate_argnums=(0,), static_argnames=("math", "evictees")
)(decide2_pallas_impl)


def walk2_pallas_impl(
    table: Table2, fp, pay16, now, active, *, stage: str,
    evictees: bool = False,
):
    """Fused-megakernel twin of `install2` / `merge2`: the probe→
    install/merge→write walk, reached through their ``probe="pallas"``
    switches — call sites never import this directly.

    `pay16` is the (B, 16) i32 canonical ingress: for ``stage="install"``
    the precomputed `kernel2.install_payload16` rows (the install payload
    never reads table state, so it rides the ingress lanes and the kernel
    just unjoins it), for ``stage="merge"`` the raw incoming slot rows
    (`kernel2.merge_payload16` runs in-kernel against the claimed VMEM
    lane). `now` broadcasts to per-row like the XLA path's (B,) clock.
    The caller applies merge's expired-incoming filter to `active` BEFORE
    this entry (merge2_impl does) — the walk itself treats `active` as
    the claim mask, exactly like `_probe_claim2`.

    Returns ``(table', active & written_mask)``, plus the (B, 16) i32
    evictee sidecar when ``evictees=True`` — the install2/merge2 return
    contracts exactly, bit-identical modulo the documented sweep-window
    divergence (the walk can only drop FEWER rows)."""
    if stage not in ("install", "merge"):
        raise ValueError(f"stage must be install or merge, got {stage!r}")
    layout = table.layout
    NB = table.rows.shape[0]
    B = fp.shape[0]
    rblk = probe_blk(B)
    now = jnp.broadcast_to(jnp.asarray(now, dtype=i64), fp.shape)
    pay16 = jnp.asarray(pay16, dtype=i32)
    pairs = _join64(pay16[:, 0::2], pay16[:, 1::2])  # (B, 8) lossless
    arr11 = jnp.concatenate(
        [fp[None, :], now[None, :], active.astype(i64)[None, :], pairs.T],
        axis=0,
    )
    idx_s, arr_s, meta, sb, bkf, G = _sorted_schedule(
        fp, active, arr11, NB, rblk
    )
    rows_out, resp_s = _launch_walk(
        table, arr_s, meta, sb, bkf, G, rblk,
        math="mixed", evictees=evictees, stage=stage,
    )
    outw = _OUTW_EV if evictees else _OUTW
    out = jnp.zeros((B, outw), dtype=i64).at[idx_s].set(resp_s)
    written = out[:, _OC_WRITTEN] != 0
    tbl = Table2(rows=rows_out, layout=layout)
    if evictees:
        evict_live = out[:, _OC_EVICT] != 0
        evcols = out[:, _OUTW:]  # (B, 8) i64 candidate victim pairs
        ev16 = jnp.stack(
            [_lo32(evcols), _hi32(evcols)], axis=-1
        ).reshape(B, 16)
        ev16 = jnp.where(evict_live[:, None], ev16, 0)
        return tbl, active & written, ev16
    return tbl, active & written


__all__ = [
    "decide2_pallas",
    "decide2_pallas_impl",
    "hbm_bytes_per_decision",
    "probe_blk",
    "probe_movement",
    "walk2_pallas_impl",
]
