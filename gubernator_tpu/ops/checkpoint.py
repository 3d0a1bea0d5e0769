"""Incremental device-side checkpointing: epoch tracker + dirty-block extract.

The full-snapshot Loader (store.py) pays table-size cost on every save — a
100M-key table is ~6 GiB of DMA + compression per checkpoint, which is why
the seed only snapshots at graceful shutdown (and a `kill -9` loses every
counter since the last clean stop). This module makes checkpoint cost
proportional to the WRITE RATE instead:

* **EpochTracker** — a host-side dirty-block bitmap at the same granularity
  family as the kernel2 sparse write (`bucket // CKPT_BLK`, cf. the sweep's
  scalar-prefetched `target // (K·BLK)` dirty-block indices). Every table
  mutation marks the touched fingerprints' blocks ON THE ENGINE THREAD,
  strictly before (or in the same engine-thread job as) the mutation's
  launch; `take()` runs on the engine thread too, immediately before the
  extract launch, so the mark→mutate / take→extract pairs interleave FIFO
  and a dirtied block can never fall between epochs.
* **extract pass** — only the dirty blocks leave the device. On one device
  (`extract_begin` / `finish_extract`): a gather of the dirty blocks'
  bucket rows in grids of a fixed width (EXTRACT_GRIDS: two compiled
  programs, warmed before the door opens), each slot's liveness computed
  beside it, a fetch of each grid whole, and the dead slots dropped on the
  host. Mesh engines gather, filter and pack (live slots sorted to the
  front) per shard under shard_map (`_extract_blocks_core`,
  parallel/sharded.make_sharded_extract_dirty), so no slot row ever crosses
  a device boundary, and fetch the live prefix. Cost ∝ dirty blocks, never
  table size.

The extracted rows ride the table's own packed slot-field layout ((N, F)
int32 — the same wire format TransferState chunks use), which is exactly
what `kernel2.merge2` consumes on replay: a stale or duplicated frame can
only tighten admission (remaining=min, expiry=max, OVER sticks), never
over-grant. Framing/CRC/replay live in store.py + service/checkpoint.py.

Granularity note: a dirty block's extract carries EVERY live row of its
buckets, not just the written one — the amplification is bounded by
CKPT_BLK × K × (live density), the price of block-granular tracking. The
default CKPT_BLK=1 (bucket granularity) holds amplification at the
bucket-occupancy floor; GUBER_CHECKPOINT_BLK trades bitmap size against
frame amplification for tables where n_buckets bools of host memory
matter.
"""

from __future__ import annotations

import functools
import os
import threading
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from gubernator_tpu import tracing
from gubernator_tpu.ops.table2 import FP_HI, FP_LO, K


def ckpt_blk() -> int:
    """Buckets per checkpoint dirty block (GUBER_CHECKPOINT_BLK). Bucket
    granularity (1) by default: extract cost is dirty blocks × blk × K
    slots, so unlike the sparse WRITE block (DMA-efficiency bound, default
    64) the tracking block hugs the placement granularity — measured 11×
    cheaper extracts at blk=1 vs blk=8 under a random-key write load.
    Raising it shrinks the bitmap (n_buckets/blk bools) at the price of
    extract amplification; even blk=1 is 12.5 MB of host bitmap at 100M
    keys."""
    return int(os.environ.get("GUBER_CHECKPOINT_BLK", "1"))


class EpochTracker:
    """Host-side dirty-block accumulator between checkpoint epochs.

    One bitmap bit per (shard, block); `mark()` is a vectorized setitem on
    the serving path (engine thread), `take()` snapshots-and-clears for one
    checkpoint epoch, `remark()` re-arms a taken set whose save failed so a
    full disk never silently drops dirt. Thread-safe: marks come from the
    engine thread, takes from the checkpoint manager (which routes them to
    the engine thread anyway — see module docstring), and status reads from
    the debug plane."""

    def __init__(
        self,
        n_buckets: int,
        n_shards: int = 1,
        blk: Optional[int] = None,
        start_epoch: int = 0,
    ):
        if n_buckets <= 0:
            raise ValueError("n_buckets must be positive")
        b = blk or ckpt_blk()
        b = min(b, n_buckets)
        # conforming tables (new_table2) are pow2 below 2048 buckets or a
        # multiple of 2048 above — some pow2 ≤ b always divides
        while b > 1 and n_buckets % b:
            b //= 2
        self.blk = b
        self.n_buckets = n_buckets
        self.n_shards = n_shards
        self.nblk = n_buckets // b  # blocks per shard
        self._dirty = np.zeros(n_shards * self.nblk, dtype=bool)
        # completed checkpoint epochs; take() hands out epoch+1 and advances
        self.epoch = start_epoch
        self.marked_fps = 0  # cumulative fps marked (status surface)
        self._lock = threading.Lock()

    def _block_ids(self, fps: np.ndarray) -> np.ndarray:
        fps = np.asarray(fps, dtype=np.int64)
        fps = fps[fps != 0]  # padding/inactive rows carry fp == 0
        if fps.size == 0:
            return fps
        blkid = fps % self.n_buckets
        if self.blk > 1:  # every array call on the serving path waits for the GIL
            blkid //= self.blk
        if self.n_shards > 1:
            from gubernator_tpu.parallel.mesh import shard_of

            blkid = shard_of(fps, self.n_shards) * self.nblk + blkid
        return blkid

    def mark(self, fps: np.ndarray) -> None:
        """Mark the blocks holding `fps` dirty (fp == 0 entries ignored)."""
        # a part of the dispatch's `issue` stage when one is open on this
        # thread (the served path marks inside the issue job), else a
        # profiler span and a clock
        with tracing.stage.within("ckpt_mark"):
            blkid = self._block_ids(fps)
            if blkid.size == 0:
                return
            with self._lock:
                self._dirty[blkid] = True
                self.marked_fps += int(blkid.size)

    def mark_all(self) -> None:
        """Everything is dirty (restore/resize of unknown provenance): the
        next epoch extracts the whole live set — expensive once, never
        lossy."""
        with self._lock:
            self._dirty[:] = True

    def take(self) -> Tuple[int, np.ndarray]:
        """Snapshot-and-clear the dirty set for one checkpoint epoch.
        Returns (epoch_id, sorted global block ids); the epoch counter
        advances even on an empty take so frame ids stay monotone."""
        with self._lock:
            gids = np.nonzero(self._dirty)[0].astype(np.int64)
            self._dirty[:] = False
            self.epoch += 1
            return self.epoch, gids

    def remark(self, gids: np.ndarray) -> None:
        """Re-arm a taken block set whose frame could not be persisted
        (disk full, unwritable path): the dirt survives to the next epoch
        instead of silently vanishing from every future checkpoint."""
        if gids.size == 0:
            return
        with self._lock:
            self._dirty[np.asarray(gids, dtype=np.int64)] = True

    @property
    def dirty_blocks(self) -> int:
        with self._lock:
            return int(self._dirty.sum())

    def rebuild(self, n_buckets: int) -> "EpochTracker":
        """Tracker for a resized table: same epoch lineage, everything
        dirty (block ids do not survive a geometry change)."""
        t = EpochTracker(
            n_buckets, n_shards=self.n_shards, blk=self.blk,
            start_epoch=self.epoch,
        )
        t.mark_all()
        return t


# ------------------------------------------------------------- extract pass


def _extract_blocks_core(rows2d, bidx, now, blk: int, layout=None):
    """Traced core of the per-shard shard_map body (parallel/sharded.py):
    gather the dirty blocks' bucket rows, filter live slots, pack them to
    the front.

    `rows2d` is (T, ROW_layout); `bidx` (g,) block ids with out-of-range
    sentinels for padding (jnp.take mode="fill" zero-fills them — fp == 0
    rows are never live). Returns (slots (g·blk·K, F_layout) live-first,
    fp (g·blk·K,), live_count) — slots stay in the table's own layout, so
    packed tables' delta frames carry HALF the bytes per row."""
    if layout is None:
        from gubernator_tpu.ops.layout import FULL as layout
    g = bidx.shape[0]
    rowidx = (
        bidx[:, None].astype(jnp.int32) * blk
        + jnp.arange(blk, dtype=jnp.int32)[None, :]
    ).reshape(-1)
    blocks = jnp.take(rows2d, rowidx, axis=0, mode="fill", fill_value=0)
    slots = blocks.reshape(g * blk * K, layout.F)
    lo = slots[:, FP_LO].astype(jnp.int64) & 0xFFFFFFFF
    hi = slots[:, FP_HI].astype(jnp.int64)
    fp = (hi << 32) | lo
    exp = (slots[:, layout.exp_lo_i].astype(jnp.int64) & 0xFFFFFFFF) | (
        slots[:, layout.exp_hi_i].astype(jnp.int64) << 32
    )
    live = (fp != 0) & (exp >= now)
    order = jnp.argsort(jnp.where(live, 0, 1).astype(jnp.int32))
    return slots[order], fp[order], live.sum()


# Grid widths (dirty blocks an execution) of the single-device extract: a
# quiet epoch runs the small grid once, a busy one the large grid as often
# as its dirty set needs. Two compiled programs whatever the write rate —
# `EngineRunner.checkpoint_warm` compiles both before the door opens, so no
# epoch compiles under load (a pow2 pad per dirty-set size compiled its steady shape
# inside the first busy second) — and the padding of an epoch is at most
# one grid.
EXTRACT_GRIDS = (4096, 65536)


@functools.partial(jax.jit, static_argnames=("blk", "layout"))
def _extract_blocks_grid(rows, bidx, now, *, blk: int, layout):
    """One grid of the single-device extract: the dirty blocks' bucket rows
    as they lie in the table ((g·blk, ROW_layout): a bucket row is one lane
    row, which the chip gathers at a few ns a row and hands to the host
    untiled) and, flat beside them, each slot's fingerprint, 0 where the
    slot is empty or expired. `rows` is any (..., ROW_layout) array ((NB, ·)
    local, or (D, NB, ·) with the shard axis folded in and block ids then
    global, shard-major); `bidx` (g,) block ids, padded with an
    out-of-range sentinel (`jnp.take` mode="fill" zero-fills it, and
    fp == 0 is never live). Packing live slots to the front on the device
    would add a sort over every slot and a gather of 64-byte rows (282 ms
    an epoch of 364K buckets against 10, PERF.md section 6, PR 34), so the
    host drops the dead slots as it copies the rows out of the fetch buffer
    (`finish_extract`)."""
    g = bidx.shape[0]
    rowidx = (
        bidx[:, None].astype(jnp.int32) * blk
        + jnp.arange(blk, dtype=jnp.int32)[None, :]
    ).reshape(-1)
    blocks = jnp.take(
        rows.reshape(-1, layout.row), rowidx, axis=0, mode="fill",
        fill_value=0,
    )
    slots = blocks.reshape(g * blk, K, layout.F)
    lo = slots[:, :, FP_LO].astype(jnp.int64) & 0xFFFFFFFF
    hi = slots[:, :, FP_HI].astype(jnp.int64)
    exp = (slots[:, :, layout.exp_lo_i].astype(jnp.int64) & 0xFFFFFFFF) | (
        slots[:, :, layout.exp_hi_i].astype(jnp.int64) << 32
    )
    return blocks, jnp.where(exp >= now, (hi << 32) | lo, 0).reshape(-1)


def _grid_for(n: int) -> int:
    for g in EXTRACT_GRIDS:
        if n <= g:
            return g
    return EXTRACT_GRIDS[-1]


def extract_begin(rows, gids: np.ndarray, blk: int, now_ms: int, layout=None):
    """LAUNCH half of a dirty-block extract (engine thread — must read a
    coherent table, costs only the enqueues): cuts the dirty-block list
    into grids of one of EXTRACT_GRIDS' widths, pads the last with an
    out-of-range sentinel and launches one gather per grid. Returns a
    pending handle for finish_extract. `layout` is the table's slot layout
    (full when omitted — the legacy geometry)."""
    if layout is None:
        from gubernator_tpu.ops.layout import layout_for_row

        layout = layout_for_row(int(rows.shape[-1]))
    # sentinel: one past the last valid block id in the flattened layout
    sentinel = int(np.prod(rows.shape[:-1])) // blk
    g = int(gids.shape[0])
    width = _grid_for(g)
    now = jnp.asarray(np.int64(now_ms))
    pending = []
    for at in range(0, max(g, 1), width):
        bidx = np.full(width, sentinel, dtype=np.int32)
        part = gids[at:at + width]
        bidx[:part.shape[0]] = part
        pending.append(_extract_blocks_grid(
            rows, jnp.asarray(bidx), now, blk=blk, layout=layout,
        ))
    return pending


def finish_extract(pending):
    """FETCH half (any thread): fetch each grid whole — one fixed shape, so
    no slice program is compiled per extract size — and copy its live
    slots out. Returns (fps (N,) i64, slots (N, F_layout) i32), slots in
    the table's own layout and in table order."""
    fp = [np.asarray(fp_d) for _blocks, fp_d in pending]
    live = [f != 0 for f in fp]
    ends = np.cumsum([int(m.sum()) for m in live])
    F = int(pending[0][0].shape[1]) // K
    fps = np.empty(int(ends[-1]), dtype=np.int64)
    slots = np.empty((int(ends[-1]), F), dtype=np.int32)
    at = 0
    for (blocks_d, _fp_d), f, m, end in zip(pending, fp, live, ends.tolist()):
        # each grid's live rows land where the frame wants them: no
        # concatenate of a hundred megabytes afterwards
        np.compress(m, f, out=fps[at:end])
        np.compress(m, np.asarray(blocks_d).reshape(-1, F), axis=0,
                    out=slots[at:end])
        at = end
    return fps, slots
