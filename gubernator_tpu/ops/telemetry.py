"""Device-side table telemetry: one fused scan → the glass-box counters.

The reference observes its LRU cache through per-operation counters
(lrucache.go:48-59); a 10M–100M-key HBM-resident hash-slotted table needs
*structural* health signals those counters cannot express: how full the
buckets actually are (collision pressure predicts `unexpired_evictions`
BEFORE it fires), how the TTL horizon is distributed (what fraction of the
table frees itself in the next minute), how much admission headroom remains,
and what fraction of live keys sit OVER limit.

One jitted scan computes all of it in a single pass over the rows array —
the same streaming-sweep cost model as the write kernel (ops/table2.py
docstring: a full table stream through VMEM is ~ms at 1 GiB). The scan runs
on a BACKGROUND cadence from EngineRunner.table_telemetry (issue on the
engine thread, fetch off it — it overlaps serving dispatches and never sits
on the serving path). Output is one small int64 stats vector; the host
decodes it into a `TableSnapshot` that feeds the `gubernator_tpu_table_*`
Prometheus families and the `/v1/debug/table` endpoint.

`host_telemetry` is the numpy oracle the parity tests (and skeptical
operators) check the device scan against.
"""

from __future__ import annotations

import functools
import math
import time
from dataclasses import dataclass, field
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from gubernator_tpu.ops.table2 import (
    EXP_HI,
    EXP_LO,
    F,
    FLAGS,
    FP_HI,
    FP_LO,
    K,
    LIMIT,
    REM_I,
    _COUNT_CHUNK,
)

# TTL-horizon bucket edges (ms since `now`): live keys expiring within each
# horizon, cumulative — ≤1s, ≤10s, ≤1m, ≤10m, ≤1h, ≤1d, +Inf
TTL_EDGES_MS = (1_000, 10_000, 60_000, 600_000, 3_600_000, 86_400_000)
# remaining-capacity edges (remaining / limit, cumulative ≤): keys at ≤1% of
# their limit are one burst from OVER; ≥90% are idle
REMAIN_EDGES = (0.01, 0.1, 0.25, 0.5, 0.9)
# buckets per occupancy block: the sweep kernel's default block width
# (kernel2 sparse geometry), so block-fill deciles line up with the write
# kernel's launch granularity
BLOCK_BUCKETS = 64

# stats-vector layout (int64): decoders below and the shard_map variant in
# parallel/telemetry.py share it — keep in sync
_N_SCALAR = 3  # live, occupied, over
VEC_LEN = _N_SCALAR + (K + 1) + len(TTL_EDGES_MS) + len(REMAIN_EDGES) + 10


@dataclass
class TableSnapshot:
    """One decoded telemetry scan (host side)."""

    now_ms: int
    capacity: int  # total slots
    n_buckets: int
    live_keys: int
    occupied_slots: int  # fp != 0, including expired-not-yet-evicted
    over_keys: int  # live slots whose stored status is OVER_LIMIT
    # count of buckets holding exactly j live slots, j = 0..K
    bucket_occupancy: List[int] = field(default_factory=list)
    # cumulative live keys with (expire - now) <= TTL_EDGES_MS[i]
    ttl_horizon: List[int] = field(default_factory=list)
    # cumulative live keys with remaining/limit <= REMAIN_EDGES[i]
    remaining_frac: List[int] = field(default_factory=list)
    # sweep-block fill-fraction histogram, 10 decile bins
    block_fill: List[int] = field(default_factory=list)
    scan_ms: float = 0.0
    per_shard_live: Optional[List[int]] = None  # mesh engines only

    @property
    def load_factor(self) -> float:
        return self.live_keys / max(self.capacity, 1)

    @property
    def over_fraction(self) -> float:
        return self.over_keys / max(self.live_keys, 1)

    @property
    def probe_depth(self) -> List[int]:
        """Live keys by their bucket's occupancy (a lookup gathers the whole
        bucket row, so depth == how contended the key's bucket is):
        depth_hist[j] = j * bucket_occupancy[j], j = 1..K."""
        return [j * self.bucket_occupancy[j] for j in range(1, K + 1)]

    def to_dict(self) -> dict:
        d = {
            "now_ms": self.now_ms,
            "capacity": self.capacity,
            "n_buckets": self.n_buckets,
            "live_keys": self.live_keys,
            "occupied_slots": self.occupied_slots,
            "expired_slots": self.occupied_slots - self.live_keys,
            "over_keys": self.over_keys,
            "over_fraction": round(self.over_fraction, 6),
            "load_factor": round(self.load_factor, 6),
            "bucket_occupancy": self.bucket_occupancy,
            "probe_depth": self.probe_depth,
            "ttl_horizon_ms": dict(
                zip([str(e) for e in TTL_EDGES_MS] + ["+Inf"],
                    self.ttl_horizon + [self.live_keys])
            ),
            "remaining_frac": dict(
                zip([str(e) for e in REMAIN_EDGES] + ["+Inf"],
                    self.remaining_frac + [self.live_keys])
            ),
            "block_fill_deciles": self.block_fill,
            "scan_ms": round(self.scan_ms, 3),
        }
        if self.per_shard_live is not None:
            d["per_shard_live"] = self.per_shard_live
        return d


def _scan_body(rows: jnp.ndarray, now: jnp.ndarray, blk: int,
               layout=None) -> jnp.ndarray:
    """Traceable scan body over an (..., NB, ROW_layout) rows array →
    (VEC_LEN,) int64 stats vector. Every entry is additive across disjoint
    row sets, so the sharded variant sums per-device vectors. `blk`
    (static) is the occupancy-block width in buckets; `layout` the table's
    slot layout — packed fields unpack to the canonical 16 in registers,
    so the statistics themselves stay layout-blind while the scan streams
    half the HBM bytes on 32 B tables."""
    if layout is None:
        from gubernator_tpu.ops.layout import FULL as layout
    slots = layout.unpack(
        rows.reshape(-1, K, layout.F)
    )  # (M buckets, K slots, 16 canonical fields)
    lo = slots[:, :, FP_LO].astype(jnp.int64) & 0xFFFFFFFF
    hi = slots[:, :, FP_HI].astype(jnp.int64)
    fp = (hi << 32) | lo
    exp = (slots[:, :, EXP_LO].astype(jnp.int64) & 0xFFFFFFFF) | (
        slots[:, :, EXP_HI].astype(jnp.int64) << 32
    )
    occupied = fp != 0
    live = occupied & (exp >= now)
    status = slots[:, :, FLAGS] >> 8  # FLAGS = algo | status<<8
    over = live & (status == 1)

    live_count = live.sum(dtype=jnp.int64)
    parts = [
        live_count[None],
        occupied.sum(dtype=jnp.int64)[None],
        over.sum(dtype=jnp.int64)[None],
    ]
    # bucket occupancy histogram: buckets holding exactly j live slots
    bucket_occ = live.sum(axis=1).astype(jnp.int32)  # (M,)
    occ_hist = (
        (bucket_occ[:, None] == jnp.arange(K + 1, dtype=jnp.int32)[None, :])
        .sum(axis=0, dtype=jnp.int64)
    )
    parts.append(occ_hist)
    # TTL horizon (cumulative over live slots)
    rel = exp - now
    parts.append(
        jnp.stack(
            [(live & (rel <= e)).sum(dtype=jnp.int64) for e in TTL_EDGES_MS]
        )
    )
    # remaining-capacity fraction (cumulative): rem_i / limit per live slot
    rem = jnp.maximum(slots[:, :, REM_I], 0).astype(jnp.float32)
    lim = jnp.maximum(slots[:, :, LIMIT], 1).astype(jnp.float32)
    frac = rem / lim
    parts.append(
        jnp.stack(
            [(live & (frac <= e)).sum(dtype=jnp.int64) for e in REMAIN_EDGES]
        )
    )
    # sweep-block fill deciles
    block_live = bucket_occ.reshape(-1, blk).sum(axis=1)  # (M/blk,)
    fill = block_live.astype(jnp.float32) / float(blk * K)
    decile = jnp.clip((fill * 10).astype(jnp.int32), 0, 9)
    parts.append(
        (decile[:, None] == jnp.arange(10, dtype=jnp.int32)[None, :]).sum(
            axis=0, dtype=jnp.int64
        )
    )
    return jnp.concatenate(parts)


def block_width(n_buckets: int) -> int:
    """Occupancy-block width for a table geometry: the sweep's 64-bucket
    block when it divides, the whole (tiny) table otherwise."""
    return BLOCK_BUCKETS if n_buckets % BLOCK_BUCKETS == 0 else n_buckets


def scan_chunk(n_buckets: int, blk: Optional[int] = None) -> int:
    """Buckets the single-device scan takes a step: `_COUNT_CHUNK` where it
    divides the table, and never less than an occupancy block."""
    blk = block_width(n_buckets) if blk is None else blk
    chunk = math.gcd(n_buckets, _COUNT_CHUNK)
    return chunk if chunk % blk == 0 else n_buckets


def _scan_chunked(rows: jnp.ndarray, now: jnp.ndarray, blk: int,
                  layout=None) -> jnp.ndarray:
    """`_scan_body` over one device's rows in pieces of `_COUNT_CHUNK`
    buckets, summed (every entry of the vector is additive over disjoint
    rows). Seeing rows as (.., K, F) makes a TPU copy them into another
    tiling — scratch of 1.25x the table's bytes when the table goes through
    whole, which no chip has left beside a table of half its memory (the
    compiler refuses the program at 8 GiB of 16) — so, as the live count
    does (ops/table2.live_count_rows), it goes through 8 MiB at a time."""
    rows = rows.reshape(-1, rows.shape[-1])
    nb = rows.shape[0]
    chunk = scan_chunk(nb, blk)

    def step(i, acc):
        part = jax.lax.dynamic_slice_in_dim(rows, i * chunk, chunk)
        return acc + _scan_body(part, now, blk, layout)

    return jax.lax.fori_loop(
        0, nb // chunk, step, jnp.zeros(VEC_LEN, dtype=jnp.int64)
    )


_scan = functools.partial(jax.jit, static_argnames=("blk", "layout"))(
    _scan_chunked
)


class PendingScan:
    """An ISSUED telemetry scan: the device computes while serving continues;
    `finish_scan` materializes the stats vector. Carries the geometry the
    decoder needs."""

    __slots__ = ("vec", "now_ms", "capacity", "n_buckets", "t0", "per_shard")

    def __init__(self, vec, now_ms, capacity, n_buckets, per_shard=False):
        self.vec = vec
        self.now_ms = now_ms
        self.capacity = capacity
        self.n_buckets = n_buckets
        self.t0 = time.perf_counter()
        self.per_shard = per_shard


def scan_begin(rows, now_ms: int, layout=None) -> PendingScan:
    """Launch the telemetry scan over a single-device rows array WITHOUT
    fetching (the engine-thread half — cheap enqueue, the serving pipeline
    keeps dispatching while the device streams the table)."""
    if layout is None:
        from gubernator_tpu.ops.layout import layout_for_row

        layout = layout_for_row(int(rows.shape[-1]))
    nb = int(rows.shape[-2])
    vec = _scan(rows, jnp.int64(now_ms), blk=block_width(nb), layout=layout)
    total_buckets = int(np.prod(rows.shape[:-1]))
    return PendingScan(vec, now_ms, total_buckets * K, total_buckets)


def decode_vec(vec: np.ndarray) -> dict:
    """Split one (VEC_LEN,) stats vector into named pieces."""
    i = _N_SCALAR
    out = {
        "live": int(vec[0]),
        "occupied": int(vec[1]),
        "over": int(vec[2]),
    }
    out["occ_hist"] = [int(x) for x in vec[i : i + K + 1]]
    i += K + 1
    out["ttl"] = [int(x) for x in vec[i : i + len(TTL_EDGES_MS)]]
    i += len(TTL_EDGES_MS)
    out["remain"] = [int(x) for x in vec[i : i + len(REMAIN_EDGES)]]
    i += len(REMAIN_EDGES)
    out["blocks"] = [int(x) for x in vec[i : i + 10]]
    return out


def finish_scan(pending: PendingScan) -> TableSnapshot:
    """Fetch + decode an issued scan (the off-engine-thread half)."""
    vech = np.asarray(pending.vec)
    per_shard = None
    if pending.per_shard:
        per_shard = [int(x) for x in vech[:, 0]]
        vech = vech.sum(axis=0)
    d = decode_vec(vech)
    return TableSnapshot(
        now_ms=pending.now_ms,
        capacity=pending.capacity,
        n_buckets=pending.n_buckets,
        live_keys=d["live"],
        occupied_slots=d["occupied"],
        over_keys=d["over"],
        bucket_occupancy=d["occ_hist"],
        ttl_horizon=d["ttl"],
        remaining_frac=d["remain"],
        block_fill=d["blocks"],
        scan_ms=(time.perf_counter() - pending.t0) * 1e3,
        per_shard_live=per_shard,
    )


def host_telemetry(rows: np.ndarray, now_ms: int, layout=None) -> TableSnapshot:
    """Numpy oracle: the same statistics computed host-side from a table
    snapshot — the parity reference for the device scan (tests) and the
    escape hatch for post-mortem analysis of a checkpoint file."""
    if layout is None:
        from gubernator_tpu.ops.layout import layout_for_row

        layout = layout_for_row(int(rows.shape[-1]))
    nb = int(rows.shape[-2])
    blk = block_width(nb)
    slots = np.asarray(layout.unpack(rows.reshape(-1, K, layout.F)))
    lo = slots[:, :, FP_LO].astype(np.int64) & 0xFFFFFFFF
    hi = slots[:, :, FP_HI].astype(np.int64)
    fp = (hi << 32) | lo
    exp = (slots[:, :, EXP_LO].astype(np.int64) & 0xFFFFFFFF) | (
        slots[:, :, EXP_HI].astype(np.int64) << 32
    )
    occupied = fp != 0
    live = occupied & (exp >= now_ms)
    status = slots[:, :, FLAGS] >> 8
    bucket_occ = live.sum(axis=1)
    rel = exp - now_ms
    rem = np.maximum(slots[:, :, REM_I], 0).astype(np.float32)
    lim = np.maximum(slots[:, :, LIMIT], 1).astype(np.float32)
    frac = rem / lim
    block_live = bucket_occ.reshape(-1, blk).sum(axis=1)
    decile = np.clip((block_live.astype(np.float32) / (blk * K) * 10).astype(
        np.int32), 0, 9)
    total_buckets = slots.shape[0]
    return TableSnapshot(
        now_ms=now_ms,
        capacity=total_buckets * K,
        n_buckets=total_buckets,
        live_keys=int(live.sum()),
        occupied_slots=int(occupied.sum()),
        over_keys=int((live & (status == 1)).sum()),
        bucket_occupancy=[int((bucket_occ == j).sum()) for j in range(K + 1)],
        ttl_horizon=[int((live & (rel <= e)).sum()) for e in TTL_EDGES_MS],
        remaining_frac=[int((live & (frac <= e)).sum()) for e in REMAIN_EDGES],
        block_fill=[int((decile == j).sum()) for j in range(10)],
    )
