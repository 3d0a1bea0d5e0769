"""Host-RAM shadow table: fp-keyed canonical 64 B rows + optional spill.

The shadow is the demotion target for rows leaving HBM (evictee sidecar,
idle sweep) and the fault-back source of the engine's miss path. The RAM
set is columnar (`ShadowTable`: one row array, one sequence column, one
open-addressing index; every call handles a batch). Design points:

* **Canonical rows.** Entries are always the 16-field full-width slot row
  (ops/layout.py conversion contract): demotes unpack the table's own
  layout at the boundary, promotes re-enter through `merge_rows` which
  packs back — so a row that lived in a packed table round-trips
  bit-exactly and cross-layout restarts stay sound.
* **Byte bound.** `max_bytes` bounds the RAM set at the nominal
  ROW_BYTES (64) per row — the state bytes themselves. Over-budget entries
  shed oldest-demoted-first (by demote/refresh time): to the spill file when one is
  configured (lossless), else dropped and counted — exactly today's
  eviction loss, never worse.
* **Conservative conflicts.** A demote for a fingerprint already
  shadowed merges host-side with the merge2 rules (remaining=min,
  expiry=max, aux=max-same-algo, OVER sticks, newest-stamp config) —
  a duplicated or reordered demote can only tighten.
* **Spill file.** DeltaLog frame format (store.py — CRC-framed raw-LE
  full-layout rows), append-only with an in-memory fp → byte-offset
  index for O(1) single-row fault-back reads; compacts when garbage
  dominates. Spill writes are BATCHED (`flush()`, sweep cadence) so the
  serving-path evict capture never pays an fsync. Promote REMOVALS are
  RAM-only: after a restart a promoted row may be re-promoted stale,
  which the conservative merge renders harmless (under-grant only).
"""

from __future__ import annotations

import logging
import os
import struct
import tempfile
import threading
import zlib
from typing import Optional, Tuple

import numpy as np

from gubernator_tpu import native
from gubernator_tpu.ops.table2 import (
    BURST,
    DUR_HI,
    DUR_LO,
    EXP_HI,
    EXP_LO,
    F,
    FLAGS,
    LIMIT,
    REM_I,
    REMF_HI,
    REMF_LO,
    STAMP_HI,
    STAMP_LO,
)
from gubernator_tpu.store import (
    DELTA_LOG_MAGIC,
    _FRAME_HEADER,
    encode_delta_frame,
    read_delta_frames,
)

log = logging.getLogger("gubernator_tpu.tier")

ROW_BYTES = F * 4  # canonical full-width slot row: the shadow's unit cost


def _join(slots: np.ndarray, lo: int, hi: int) -> np.ndarray:
    return (slots[:, hi].astype(np.int64) << 32) | (
        slots[:, lo].astype(np.int64) & 0xFFFFFFFF
    )


def _split(vals: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    lo_u = vals & 0xFFFFFFFF
    lo = np.where(lo_u >= (1 << 31), lo_u - (1 << 32), lo_u).astype(np.int32)
    return lo, (vals >> 32).astype(np.int32)


def _remf_f64(slots: np.ndarray) -> np.ndarray:
    return (
        slots[:, REMF_HI].view(np.float32).astype(np.float64)
        + slots[:, REMF_LO].view(np.float32).astype(np.float64)
    )


def merge_canonical_rows(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Host-side conservative merge of same-fingerprint canonical rows —
    the numpy twin of kernel2.merge2's exists-branch (remaining=min,
    expiry=max, aux=max when algorithms agree else config winner's,
    OVER sticks, newest-stamp config wins). (n, 16) × (n, 16) → (n, 16);
    used for shadow offer conflicts and spill-load dedup, so a duplicated
    demote can only tighten what a later promote installs."""
    a = np.ascontiguousarray(a, dtype=np.int32)
    b = np.ascontiguousarray(b, dtype=np.int32)
    out = a.copy()
    st_a, st_b = _join(a, STAMP_LO, STAMP_HI), _join(b, STAMP_LO, STAMP_HI)
    keep_a = st_a > st_b  # config carrier: the newer stamp's side
    for f_ in (LIMIT, BURST, DUR_LO, DUR_HI):
        out[:, f_] = np.where(keep_a, a[:, f_], b[:, f_])
    algo = np.where(keep_a, a[:, FLAGS] & 0xFF, b[:, FLAGS] & 0xFF)
    status = np.maximum(a[:, FLAGS] >> 8, b[:, FLAGS] >> 8)
    out[:, FLAGS] = algo | (status << 8)
    out[:, REM_I] = np.minimum(a[:, REM_I], b[:, REM_I])
    exp = np.maximum(_join(a, EXP_LO, EXP_HI), _join(b, EXP_LO, EXP_HI))
    out[:, EXP_LO], out[:, EXP_HI] = _split(exp)
    stamp = np.maximum(st_a, st_b)
    out[:, STAMP_LO], out[:, STAMP_HI] = _split(stamp)
    # raw aux pair (GCRA TAT / window prev): max tightens when the two
    # sides agree on the algorithm, else the config winner's raw value;
    # the float lane keeps its unconditional min (merge2's own rule)
    aux_a, aux_b = _join(a, REMF_LO, REMF_HI), _join(b, REMF_LO, REMF_HI)
    same = (a[:, FLAGS] & 0xFF) == (b[:, FLAGS] & 0xFF)
    aux = np.where(
        same, np.maximum(aux_a, aux_b), np.where(keep_a, aux_a, aux_b)
    )
    rem_f = np.minimum(_remf_f64(a), _remf_f64(b))
    f_hi = rem_f.astype(np.float32)
    f_lo = (rem_f - f_hi.astype(np.float64)).astype(np.float32)
    aux_lo, aux_hi = _split(aux)
    is_aux = (algo == 2) | (algo == 3)  # GCRA | sliding window
    out[:, REMF_HI] = np.where(is_aux, aux_hi, f_hi.view(np.int32))
    out[:, REMF_LO] = np.where(is_aux, aux_lo, f_lo.view(np.int32))
    return out


class _SpillFile:
    """Append-only DeltaLog-format spill with an fp → byte-offset index.

    One frame per flush; each indexed row is read back with a single
    seek + 64 B read. Compaction rewrites the live rows into a fresh
    file (atomic replace) when garbage dominates. NOT thread-safe on its
    own — the owning ShadowTable's lock serializes every call."""

    COMPACT_MIN_BYTES = 1 << 22  # don't bother below 4 MiB
    _ROW = ROW_BYTES

    def __init__(self, path: str):
        self.path = path
        self.index: dict = {}  # fp -> absolute byte offset of the row
        self.payload_bytes = 0  # all row bytes ever appended (garbage incl.)
        self.read_errors = 0
        self.loaded_rows = 0

    # ------------------------------------------------------------- loading
    def load(self) -> int:
        """Rebuild the index from an existing spill file (boot). Later
        frames supersede earlier ones; a torn tail is ignored (the clean
        prefix is what the scan yields). Returns indexed rows."""
        scan = read_delta_frames(self.path)
        if scan.error:
            log.warning("tier spill %s: %s — keeping the clean prefix",
                        self.path, scan.error)
        off = len(DELTA_LOG_MAGIC)
        for _epoch, _now, slots, layout in scan.frames:
            payload_off = off + _FRAME_HEADER.size
            n = slots.shape[0]
            width = slots.shape[1] * 4
            if getattr(layout, "F", None) == F:
                fps = (slots[:, 1].astype(np.int64) << 32) | (
                    slots[:, 0].astype(np.int64) & 0xFFFFFFFF
                )
                for i in range(n):
                    if fps[i] != 0:
                        self.index[int(fps[i])] = payload_off + i * self._ROW
            off = payload_off + n * width
        self.payload_bytes = max(0, off - len(DELTA_LOG_MAGIC))
        self.loaded_rows = len(self.index)
        return self.loaded_rows

    # ------------------------------------------------------------ appending
    def append(self, fps: np.ndarray, rows: np.ndarray, now_ms: int) -> None:
        """Append one frame of canonical rows; index every row."""
        n = int(fps.shape[0])
        if n == 0:
            return
        frame = encode_delta_frame(0, now_ms, rows.astype(np.int32))
        d = os.path.dirname(os.path.abspath(self.path)) or "."
        os.makedirs(d, exist_ok=True)
        fresh = not os.path.exists(self.path) or os.path.getsize(
            self.path
        ) == 0
        with open(self.path, "ab") as f:
            if fresh:
                f.write(DELTA_LOG_MAGIC)
            base = f.tell() + _FRAME_HEADER.size
            f.write(frame)
            f.flush()
            os.fsync(f.fileno())
        for i in range(n):
            self.index[int(fps[i])] = base + i * self._ROW
        self.payload_bytes += n * self._ROW

    # -------------------------------------------------------------- reading
    def read(self, fp: int) -> Optional[np.ndarray]:
        """One indexed row ((16,) int32) or None. Validates the stored
        fingerprint — a mismatch (torn/foreign file) drops the entry."""
        off = self.index.get(fp)
        if off is None:
            return None
        try:
            with open(self.path, "rb") as f:
                f.seek(off)
                buf = f.read(self._ROW)
        except OSError:
            self.read_errors += 1
            self.index.pop(fp, None)
            return None
        if len(buf) < self._ROW:
            self.read_errors += 1
            self.index.pop(fp, None)
            return None
        row = np.frombuffer(buf, dtype="<i4").astype(np.int32)
        got = (int(row[1]) << 32) | (int(row[0]) & 0xFFFFFFFF)
        if got != fp:
            self.read_errors += 1
            self.index.pop(fp, None)
            return None
        return row

    def discard(self, fp: int) -> None:
        self.index.pop(fp, None)

    # ----------------------------------------------------------- compaction
    def maybe_compact(self, now_ms: int) -> bool:
        """Rewrite live rows into a fresh file when garbage dominates
        (> half the payload) and the file is worth the I/O."""
        live = len(self.index) * self._ROW
        if self.payload_bytes < self.COMPACT_MIN_BYTES:
            return False
        if live * 2 > self.payload_bytes:
            return False
        fps = np.fromiter(self.index.keys(), dtype=np.int64,
                          count=len(self.index))
        rows = np.zeros((fps.shape[0], F), dtype=np.int32)
        keep = np.zeros(fps.shape[0], dtype=bool)
        for i, fp in enumerate(fps):
            row = self.read(int(fp))
            if row is not None:
                rows[i] = row
                keep[i] = True
        fps, rows = fps[keep], rows[keep]
        d = os.path.dirname(os.path.abspath(self.path)) or "."
        fd, tmp = tempfile.mkstemp(dir=d, prefix=".gubtpu-spill-")
        try:
            with os.fdopen(fd, "wb") as f:
                f.write(DELTA_LOG_MAGIC)
                base = f.tell() + _FRAME_HEADER.size
                if fps.shape[0]:
                    f.write(encode_delta_frame(0, now_ms, rows))
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, self.path)
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise
        self.index = {
            int(fps[i]): base + i * self._ROW for i in range(fps.shape[0])
        }
        self.payload_bytes = fps.shape[0] * self._ROW
        return True

    def size_bytes(self) -> int:
        try:
            return os.path.getsize(self.path)
        except OSError:
            return 0


class _FpIndex:
    """fingerprint → row id: one open-addressing table in two flat arrays,
    probed and filled a batch at a time (no Python object per key).

    `keys` holds the fingerprint (0 = never used, −1 = removed: a
    fingerprint is a positive 63-bit number), `vals` the row id. Linear
    probing from a multiplicative hash; the table doubles, and drops its
    removed marks, once used + removed slots pass 0.7 of it, so a chain
    stays a handful of slots. A probe or a fill of n fingerprints is one
    call into the native module with the GIL released (`fp_index_find`,
    `fp_index_place`: the engine thread does this inside the miss path, and
    on a loaded host each of the NumPy twin's few hundred small array calls
    queues for the GIL again); where the module is not loaded the twin is a
    few array passes over a shrinking remainder. 12 B a slot: 17–34 B a
    row."""

    _EMPTY, _GONE = 0, -1
    _MULT = np.uint64(0x9E3779B97F4A7C15)

    def __init__(self, bits: int = 12):
        self._alloc(bits)

    def _alloc(self, bits: int) -> None:
        self.bits = bits
        self.keys = np.zeros(1 << bits, dtype=np.int64)
        self.vals = np.zeros(1 << bits, dtype=np.int32)
        self.used = 0  # slots holding a fingerprint
        self.gone = 0  # slots holding a removed mark

    @property
    def nbytes(self) -> int:
        return self.keys.nbytes + self.vals.nbytes

    def _home(self, fps: np.ndarray) -> np.ndarray:
        with np.errstate(over="ignore"):
            h = fps.view(np.uint64) * self._MULT
        return (h >> np.uint64(64 - self.bits)).astype(np.int64)

    def find(self, fps: np.ndarray) -> np.ndarray:
        """Slot of each fingerprint, −1 where the table does not hold it."""
        fps = np.ascontiguousarray(fps, dtype=np.int64)
        out = np.full(fps.shape[0], -1, dtype=np.int64)
        mod = native.load()
        if mod is not None:
            # one GIL-free call (native/guberhost.cpp); below, its twin
            mod.fp_index_find(self.keys, self.bits, fps, out)
            return out
        return self._find_numpy(fps, out)

    def _find_numpy(self, fps: np.ndarray, out: np.ndarray) -> np.ndarray:
        mask = (1 << self.bits) - 1
        rest, cur, want = np.arange(fps.shape[0]), self._home(fps), fps
        while rest.size:
            k = self.keys[cur]
            hit = k == want
            out[rest[hit]] = cur[hit]
            go = ~hit & (k != self._EMPTY)
            rest, cur, want = rest[go], (cur[go] + 1) & mask, want[go]
        return out

    def insert(self, fps: np.ndarray, ids: np.ndarray) -> None:
        """Add fingerprints the table does not hold, each once."""
        n = int(fps.shape[0])
        if n == 0:
            return
        if (self.used + self.gone + n) * 10 > (7 << self.bits):
            self._grow(n)
        self._place(np.ascontiguousarray(fps, dtype=np.int64),
                    np.asarray(ids, dtype=np.int32))

    def _place(self, fps: np.ndarray, ids: np.ndarray) -> None:
        mod = native.load()
        if mod is not None:
            ids = np.ascontiguousarray(ids, dtype=np.int32)
            self.gone -= mod.fp_index_place(self.keys, self.vals, self.bits, fps, ids)
            self.used += int(fps.shape[0])
            return
        self._place_numpy(fps, ids)

    def _place_numpy(self, fps: np.ndarray, ids: np.ndarray) -> None:
        mask = (1 << self.bits) - 1
        cur = self._home(fps)
        while fps.size:
            k = self.keys[cur]
            free = (k == self._EMPTY) | (k == self._GONE)
            # two of the batch may want one free slot: all write, the slot
            # keeps one of them, and who reads its own fingerprint back won
            self.keys[cur[free]] = fps[free]
            won = free & (self.keys[cur] == fps)
            self.vals[cur[won]] = ids[won]
            self.used += int(won.sum())
            self.gone -= int((k[won] == self._GONE).sum())
            go = ~won
            fps, ids, cur = fps[go], ids[go], (cur[go] + 1) & mask

    def remove(self, slots: np.ndarray) -> None:
        self.keys[slots] = self._GONE
        self.used -= int(slots.shape[0])
        self.gone += int(slots.shape[0])

    def _grow(self, more: int) -> None:
        live = self.keys > 0
        fps, ids = self.keys[live], self.vals[live]
        bits = self.bits
        while (fps.shape[0] + more) * 100 > (35 << bits):
            bits += 1  # refilled to at most 0.35 of the new table
        self._alloc(bits)
        self._place(fps, ids)


class ShadowTable:
    """The host-side tier: fp → canonical 64 B row, byte-bounded RAM set
    with oldest-first shed-to-spill (or shed-and-count), batched durable
    spill, and exact-match fault-back probes.

    The RAM set is columnar: the rows lie in one (capacity, 16) int32
    array, grown in place, beside an 8 B demote sequence a row (what
    "oldest" means) and `_FpIndex`, the fingerprint's way to its row.
    `offer`, `take` and `contains` each handle a batch with array
    operations, so that 23M shadowed rows cost 64 B + 8 B + the index
    (17–34 B) each and no Python object. Thread-safe (one lock): the
    engine thread offers and takes, the sweep flushes."""

    def __init__(self, max_bytes: int, spill_path: Optional[str] = None):
        if max_bytes <= 0:
            raise ValueError("shadow max_bytes must be positive")
        self.max_bytes = int(max_bytes)
        self._index = _FpIndex()
        self._rows = np.zeros((1 << 10, F), dtype=np.int32)
        self._seq = np.zeros(1 << 10, dtype=np.int64)
        self._top = 0  # rows [0, _top) have been handed out at least once
        self._free = np.empty(1 << 10, dtype=np.int32)  # stack of freed ids
        self._n_free = 0
        self._clock = 0  # demote sequence: larger = demoted later
        # oldest-first candidates (row id, its sequence then) for the byte
        # bound, refilled by one partition of the sequence column when it
        # runs dry: a shed costs O(1) amortised, not a scan each
        self._old_ids = np.empty(0, dtype=np.int64)
        self._old_seq = np.empty(0, dtype=np.int64)
        self._old_at = 0
        self._unspilled = None  # (capacity,) bool: RAM-newer than the file
        self.spill = _SpillFile(spill_path) if spill_path else None
        if self.spill is not None:
            self._unspilled = np.zeros(1 << 10, dtype=bool)
        self._lock = threading.Lock()
        # counters (cumulative; the metrics layer diffs them)
        self.demoted_evict = 0
        self.demoted_idle = 0
        self.promoted = 0
        # promote rows handed BACK (claim dropped: > K same-bucket
        # promotes in one batch); their decide waits for the next round
        self.promote_returned = 0
        self.shed = 0  # rows dropped with no spill — state lost
        self.probes = 0
        self.probe_hits = 0
        self.expired_dropped = 0
        self.conflicts_merged = 0

    # ------------------------------------------------------------- geometry
    @property
    def ram_rows(self) -> int:
        return self._index.used

    @property
    def nominal_bytes(self) -> int:
        """RAM set cost at ROW_BYTES per row — the bounded figure."""
        return self._index.used * ROW_BYTES

    @property
    def resident_bytes(self) -> int:
        """What the RAM set's arrays hold: rows handed out so far, their
        sequence numbers, the free stack and the index."""
        n = self._top
        return (
            n * ROW_BYTES + n * 8 + self._free.nbytes + self._index.nbytes
            + (n if self._unspilled is not None else 0)
        )

    @property
    def tracked_rows(self) -> int:
        """Rows reachable for fault-back: RAM ∪ spill-only."""
        with self._lock:
            n = self._index.used
            if self.spill is not None and self.spill.index:
                fps = np.fromiter(self.spill.index.keys(), dtype=np.int64,
                                  count=len(self.spill.index))
                n += int((self._index.find(fps) < 0).sum())
            return n

    # ------------------------------------------------------------ row store
    def _row_fps(self, ids: np.ndarray) -> np.ndarray:
        r = self._rows
        return (r[ids, 1].astype(np.int64) << 32) | (
            r[ids, 0].astype(np.int64) & 0xFFFFFFFF
        )

    def _new_ids(self, n: int) -> np.ndarray:
        take = min(n, self._n_free)
        ids = np.empty(n, dtype=np.int32)
        if take:
            ids[:take] = self._free[self._n_free - take:self._n_free]
            self._n_free -= take
        fresh = n - take
        if fresh:
            cap = self._rows.shape[0]
            if self._top + fresh > cap:
                while cap < self._top + fresh:
                    cap += max(cap >> 2, 1 << 10)
                # grown where they lie (realloc): no second copy of 1.5 GB
                self._rows.resize((cap, F), refcheck=False)
                self._seq.resize(cap, refcheck=False)
                if self._unspilled is not None:
                    self._unspilled.resize(cap, refcheck=False)
            ids[take:] = np.arange(self._top, self._top + fresh, dtype=np.int32)
            self._top += fresh
        return ids

    def _release(self, slots: np.ndarray, ids: np.ndarray) -> None:
        """Rows leave the RAM set (lock held): index, fp lanes, free stack."""
        self._index.remove(slots)
        self._rows[ids, 0] = 0
        self._rows[ids, 1] = 0
        if self._unspilled is not None:
            self._unspilled[ids] = False
        n = int(ids.shape[0])
        if self._n_free + n > self._free.shape[0]:
            self._free.resize(
                max(2 * self._free.shape[0], self._n_free + n), refcheck=False
            )
        self._free[self._n_free:self._n_free + n] = ids
        self._n_free += n

    # --------------------------------------------------------------- demote
    def offer(self, fps: np.ndarray, rows: np.ndarray, now_ms: int,
              reason: str = "evict") -> int:
        """Accept a demote batch of canonical rows. Expired rows are
        dropped (dead state must not resurrect); conflicts merge
        conservatively; the RAM byte bound is enforced after insert
        (shed-to-spill, else shed-and-count). Returns rows accepted."""
        n = int(fps.shape[0])
        if n == 0:
            return 0
        fps = np.ascontiguousarray(fps, dtype=np.int64)
        rows = np.ascontiguousarray(rows, dtype=np.int32)
        live = _join(rows, EXP_LO, EXP_HI) >= now_ms
        keep = live & (fps != 0)
        with self._lock:
            self.expired_dropped += int((~live).sum())
            accepted = self._offer_locked(fps[keep], rows[keep])
            if reason == "idle":
                self.demoted_idle += accepted
            elif reason == "return":
                self.promote_returned += accepted
            else:
                self.demoted_evict += accepted
            self._enforce_bound(now_ms)
        return accepted

    def _offer_locked(self, fps: np.ndarray, rows: np.ndarray) -> int:
        n = int(fps.shape[0])
        if n == 0:
            return 0
        _, first = np.unique(fps, return_index=True)
        if first.shape[0] != n:
            # one fingerprint twice in a batch (no caller does): the first
            # copies now, the rest after them, so that they merge in order
            rest = np.ones(n, dtype=bool)
            rest[first] = False
            first.sort()
            return self._offer_locked(fps[first], rows[first]) + (
                self._offer_locked(fps[rest], rows[rest])
            )
        slots = self._index.find(fps)
        had = slots >= 0
        ids = np.empty(n, dtype=np.int32)
        if had.any():
            ids[had] = cur = self._index.vals[slots[had]]
            rows = rows.copy()
            rows[had] = merge_canonical_rows(rows[had], self._rows[cur])
            self.conflicts_merged += int(had.sum())
        new = ~had
        if new.any():
            ids[new] = fresh = self._new_ids(int(new.sum()))
            self._index.insert(fps[new], fresh)
        self._rows[ids] = rows
        self._seq[ids] = np.arange(self._clock, self._clock + n)
        self._clock += n
        if self._unspilled is not None:
            self._unspilled[ids] = True
        return n

    def _oldest(self, want: int) -> np.ndarray:
        """Row ids of the `want` longest-shadowed rows (lock held), oldest
        first. Candidates come from one partition of the sequence column,
        a sixteenth of the rows at a time; one that was taken or offered
        again since (its sequence moved, or its row was freed) is skipped."""
        out = np.empty(want, dtype=np.int64)
        got = 0
        while got < want:
            if self._old_at >= self._old_ids.shape[0]:
                top = self._top
                held = np.flatnonzero(self._rows[:top, 0] | self._rows[:top, 1])
                k = min(held.shape[0], max(want - got, held.shape[0] >> 4, 1024))
                seq = self._seq[held]
                if k < held.shape[0]:
                    part = np.argpartition(seq, k - 1)[:k]
                    held, seq = held[part], seq[part]
                order = np.argsort(seq, kind="stable")
                self._old_ids, self._old_seq = held[order], seq[order]
                self._old_at = 0
            i, at = self._old_ids, self._old_at
            end = min(i.shape[0], at + want - got)
            ids = i[at:end]
            ok = (self._seq[ids] == self._old_seq[at:end]) & (
                (self._rows[ids, 0] | self._rows[ids, 1]) != 0
            )
            ids = ids[ok]
            out[got:got + ids.shape[0]] = ids
            got += ids.shape[0]
            self._old_at = end
        return out[:got]

    def _enforce_bound(self, now_ms: int) -> None:
        """Drop the oldest RAM rows past the byte budget (lock held). With a
        spill the dropped rows are appended there first (lossless); without
        one they are shed — counted state loss, identical to the
        pre-tiering eviction behavior."""
        over = self._index.used - self.max_bytes // ROW_BYTES
        if over <= 0:
            return
        ids = self._oldest(over)
        fps = self._row_fps(ids)
        if self.spill is not None:
            self.spill.append(fps, self._rows[ids], now_ms)
        else:
            self.shed += over
        self._release(self._index.find(fps), ids.astype(np.int32))

    def flush(self, now_ms: int) -> int:
        """Write RAM entries newer than the spill file out to it (sweep
        cadence / shutdown). No-op without a spill. Returns rows written."""
        if self.spill is None:
            return 0
        with self._lock:
            ids = np.flatnonzero(self._unspilled[:self._top])
            if ids.shape[0] == 0:
                return 0
            self.spill.append(self._row_fps(ids), self._rows[ids], now_ms)
            self._unspilled[ids] = False
            self.spill.maybe_compact(now_ms)
            return int(ids.shape[0])

    def load(self) -> int:
        """Boot: index an existing spill file (rows stay on disk; they
        fault back lazily). Returns indexed rows."""
        if self.spill is None:
            return 0
        with self._lock:
            return self.spill.load()

    # ------------------------------------------------------------ fault-back
    def take(self, fps: np.ndarray, now_ms: int):
        """Exact-match probe-and-REMOVE for a batch of fingerprints:
        (found_fps (m,) i64, rows (m, 16) i32), each found fingerprint
        once. A miss costs its probe of the index (and one dictionary
        lookup where a spill file is configured). Expired entries are
        dropped, not promoted."""
        n = int(fps.shape[0])
        none = np.empty(0, dtype=np.int64), np.empty((0, F), np.int32)
        if n == 0:
            return none
        fps = np.unique(np.ascontiguousarray(fps, dtype=np.int64))
        fps = fps[fps != 0]
        with self._lock:
            self.probes += n
            slots = self._index.find(fps)
            had = slots >= 0
            out_fps, hit = fps[had], slots[had]
            ids = self._index.vals[hit]
            out_rows = self._rows[ids]  # a copy: fancy indexing
            if hit.shape[0]:
                self._release(hit, ids)
            if self.spill is not None and self.spill.index:
                if out_fps.shape[0]:
                    for fp in out_fps.tolist():
                        self.spill.discard(fp)
                more_f, more_r = [], []
                for fp in fps[~had].tolist():
                    row = self.spill.read(fp)
                    if row is not None:
                        self.spill.discard(fp)
                        more_f.append(fp)
                        more_r.append(row)
                if more_f:
                    out_fps = np.concatenate(
                        [out_fps, np.asarray(more_f, dtype=np.int64)]
                    )
                    out_rows = np.concatenate(
                        [out_rows, np.stack(more_r).astype(np.int32)]
                    )
            if out_fps.shape[0]:
                live = _join(out_rows, EXP_LO, EXP_HI) >= now_ms
                if not live.all():
                    self.expired_dropped += int((~live).sum())
                    out_fps, out_rows = out_fps[live], out_rows[live]
            self.probe_hits += int(out_fps.shape[0])
            # taken rows ARE promoted by contract: the caller installs
            # them before the decide that needs them
            self.promoted += int(out_fps.shape[0])
        if out_fps.shape[0] == 0:
            return none
        return out_fps, out_rows

    def contains(self, fps: np.ndarray) -> np.ndarray:
        """Non-destructive membership mask (RAM ∪ spill index)."""
        fps = np.ascontiguousarray(fps, dtype=np.int64)
        with self._lock:
            out = (self._index.find(fps) >= 0) & (fps != 0)
            if self.spill is not None and self.spill.index:
                idx = self.spill.index
                for i in np.flatnonzero(~out & (fps != 0)).tolist():
                    out[i] = int(fps[i]) in idx
        return out

    # ---------------------------------------------------------------- status
    def stats(self) -> dict:
        with self._lock:
            out = {
                "ram_rows": self._index.used,
                "nominal_bytes": self._index.used * ROW_BYTES,
                "resident_bytes": self.resident_bytes,
                "max_bytes": self.max_bytes,
                "demoted_evict": self.demoted_evict,
                "demoted_idle": self.demoted_idle,
                "promoted": self.promoted,
                "promote_returned": self.promote_returned,
                "shed": self.shed,
                "probes": self.probes,
                "probe_hits": self.probe_hits,
                "expired_dropped": self.expired_dropped,
                "conflicts_merged": self.conflicts_merged,
            }
            if self.spill is not None:
                out["spill"] = {
                    "path": self.spill.path,
                    "indexed_rows": len(self.spill.index),
                    "file_bytes": self.spill.size_bytes(),
                    "read_errors": self.spill.read_errors,
                }
            return out
