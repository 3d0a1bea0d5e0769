"""Device-side batch layout + host-side packing.

A `ReqBatch` is the SoA form of a slice of RateLimitRequests after host-side
resolution: strings → fingerprints, Gregorian durations → absolute expiries and
interval lengths, leaky burst defaulting (burst==0 → limit, reference
algorithms.go:259-261). The kernel (ops/kernel2.py) requires all fingerprints
within one batch to be distinct — the pass planner (ops/plan.py) guarantees
that, reproducing the reference's per-key sequential semantics (the worker
hash-ring serializes same-key requests, reference workers.go:185-189).
"""

from __future__ import annotations

from typing import List, NamedTuple, Optional, Sequence

import jax.numpy as jnp
import numpy as np

from gubernator_tpu import gregorian
from gubernator_tpu.hashing import fingerprint
from gubernator_tpu.types import Algorithm, Behavior, RateLimitRequest, has_behavior

# front-door bound on limit/burst: stored table fields are int32 carriers
# (ops/table.py docstring); larger values get a per-request error instead of
# silently saturating device state.
INT32_MAX = 2**31 - 1
# client-supplied created_at is accepted (reference gubernator.go:225-227 only
# stamps when unset) but clamped to ingress now ± tolerance: the reference
# checks item expiry against the *server* clock (lrucache.go GetItem), so an
# arbitrarily skewed client timestamp must not be able to renew or expire live
# buckets. Frozen-time tests pass an explicit now_ms and matching created_at,
# which never clamps.
CREATED_AT_TOLERANCE_MS = 5 * 60 * 1000
_created_at_tolerance_ms = CREATED_AT_TOLERANCE_MS


def _max_algorithm() -> int:
    from gubernator_tpu.types import MAX_ALGORITHM

    return MAX_ALGORITHM


def set_created_at_tolerance_ms(ms: int) -> None:
    """Configure the accepted client clock skew (GUBER_CREATED_AT_TOLERANCE).
    Replayed/queued traffic with legitimately old timestamps can raise it."""
    global _created_at_tolerance_ms
    if ms <= 0:
        raise ValueError("created_at tolerance must be positive")
    _created_at_tolerance_ms = int(ms)


def created_at_tolerance_ms() -> int:
    return _created_at_tolerance_ms


class ReqBatch(NamedTuple):
    """All arrays shape (B,). Fingerprints must be unique among active rows."""

    fp: jnp.ndarray  # int64 (63-bit fingerprint; 0 reserved)
    algo: jnp.ndarray  # int32
    behavior: jnp.ndarray  # int32 bitflags
    hits: jnp.ndarray  # int64
    limit: jnp.ndarray  # int64
    burst: jnp.ndarray  # int64 (resolved: 0 → limit)
    duration: jnp.ndarray  # int64 raw request duration (ms, or Gregorian enum)
    created_at: jnp.ndarray  # int64 epoch ms ("now" for this request)
    expire_new: jnp.ndarray  # int64 absolute expiry for new/renewed token items
    greg_interval: jnp.ndarray  # int64 full Gregorian interval ms (0 ⇒ not gregorian)
    duration_eff: jnp.ndarray  # int64 effective duration for leaky expiry updates
    active: jnp.ndarray  # bool padding mask

    @property
    def size(self) -> int:
        return self.fp.shape[0]


class RespBatch(NamedTuple):
    """Kernel outputs, shape (B,), in the same row order as the ReqBatch."""

    status: jnp.ndarray  # int32
    limit: jnp.ndarray  # int64
    remaining: jnp.ndarray  # int64
    reset_time: jnp.ndarray  # int64
    cache_hit: jnp.ndarray  # bool — row found a live matching slot
    dropped: jnp.ndarray  # bool — no slot could be claimed (decision not persisted)
    # stored-state echoes for full-fidelity GLOBAL broadcasts
    # (kernel2.decide2_impl → global_sync._sync_core): the raw aux lane
    # writeback (GCRA TAT / sliding-window previous count) and the
    # remaining-STYLE integer lane (limit - current for windows). None on
    # legacy constructors (the v1 oracle kernel); DCE'd by every serving
    # graph (pack_outputs reads neither).
    aux: jnp.ndarray = None  # int64 | None
    rem_store: jnp.ndarray = None  # int64 | None


class BatchStats(NamedTuple):
    """Per-dispatch scalar counters feeding the Prometheus layer
    (reference lrucache.go:48-59, gubernator.go:76-80)."""

    cache_hits: jnp.ndarray  # int64
    cache_misses: jnp.ndarray  # int64
    over_limit: jnp.ndarray  # int64 — rows answered OVER_LIMIT
    evicted_unexpired: jnp.ndarray  # int64 — live slots evicted for new keys
    dropped: jnp.ndarray  # int64 — rows that failed slot claiming


class InstallBatch(NamedTuple):
    """SoA of authoritative global statuses (one owner-broadcast entry per
    row): what UpdatePeerGlobalsReq.Globals carries (reference peers.proto:50-73)."""

    fp: jnp.ndarray  # int64
    algo: jnp.ndarray  # int32
    status: jnp.ndarray  # int32
    limit: jnp.ndarray  # int64
    remaining: jnp.ndarray  # int64
    reset_time: jnp.ndarray  # int64
    duration: jnp.ndarray  # int64
    now: jnp.ndarray  # int64 (B,)
    active: jnp.ndarray  # bool
    # full-fidelity state (Store rehydrate): the leaky burst and the item's
    # UpdatedAt/CreatedAt stamp. The UpdatePeerGlobals wire path has neither
    # (reference rebuilds with Burst=Limit, CreatedAt=now,
    # gubernator.go:434-474) — its callers pass burst=limit, stamp=now.
    burst: jnp.ndarray  # int64
    stamp: jnp.ndarray  # int64
    # sliding-window broadcast fidelity (PR 11): the previous-window count
    # (raw aux lane) and the stored-style remaining (limit - current
    # count). None on legacy wire paths — install2 then falls back to the
    # conservative weighted rebuild (docs/algorithms.md "Sliding window").
    aux: jnp.ndarray = None  # int64 | None
    rem_store: jnp.ndarray = None  # int64 | None


class HostBatch(NamedTuple):
    """numpy staging form, built by pack_requests, before device transfer."""

    fp: np.ndarray
    algo: np.ndarray
    behavior: np.ndarray
    hits: np.ndarray
    limit: np.ndarray
    burst: np.ndarray
    duration: np.ndarray
    created_at: np.ndarray
    expire_new: np.ndarray
    greg_interval: np.ndarray
    duration_eff: np.ndarray
    active: np.ndarray


# ---------------------------------------------------------------- columns path
#
# The serving hot path (service/ front door, bench e2e) avoids per-request
# Python objects entirely: requests arrive as parallel columns (numpy arrays +
# one fingerprint pass over the key strings) and resolution/validation is
# vectorized. The object API (pack_requests below) is a thin wrapper kept for
# tests and embedding use.

ERR_OK = 0
ERR_EMPTY_KEY = 1
ERR_EMPTY_NAME = 2
ERR_LIMIT_I32 = 3
ERR_BURST_I32 = 4
ERR_GREGORIAN = 5
ERR_DROPPED = 6
# forward-compat: an `algorithm` enum value this build doesn't speak (a
# NEWER peer's request in a mixed-version cluster) is a per-item error row,
# never a failed batch — the reference isolates invalid items the same way
# (gubernator.go:215-237) and its algorithm switch rejects unknown values
# with this wording
ERR_ALGORITHM = 7
# a cascade request carrying more levels than GUBER_CASCADE_MAX_LEVELS —
# the daemon parameterizes the message with the configured cap
# (service/wire.cascade_too_deep_error); this entry is the generic default
ERR_CASCADE_DEEP = 8
# shed by the overload plane before reaching the engine (service/batcher.py
# deadline/priority shedding — docs/robustness.md "Overload & QoS"): the
# answer rides a fast per-item OVER_LIMIT-style row whose reset_time is the
# suggested retry instant, never an RPC failure
ERR_OVERLOAD = 9

# wording parity with the reference where it has fixed strings
# (gubernator.go:215-224); ERR_DROPPED is this design's own failure mode
ERROR_STRINGS = {
    ERR_OK: "",
    ERR_EMPTY_KEY: "field 'unique_key' cannot be empty",
    ERR_EMPTY_NAME: "field 'namespace' cannot be empty",
    ERR_LIMIT_I32: "field 'limit' must fit int32",
    ERR_BURST_I32: "field 'burst' must fit int32",
    ERR_GREGORIAN: "invalid gregorian duration",
    ERR_DROPPED: "rate limit state could not be persisted (contended table); retry",
    ERR_ALGORITHM: "invalid rate limit algorithm",
    ERR_CASCADE_DEEP: "cascade levels list too large",
    ERR_OVERLOAD: "request shed under overload; retry after reset_time",
}


class RequestColumns(NamedTuple):
    """Column-oriented request batch (pre-fingerprinted). `created_at == 0`
    means unset (stamped with ingress now, reference gubernator.go:225-227);
    `err` carries fingerprint-stage validation codes."""

    fp: np.ndarray  # int64; 0 where err != 0
    algo: np.ndarray  # int32
    behavior: np.ndarray  # int32
    hits: np.ndarray  # int64
    limit: np.ndarray  # int64
    burst: np.ndarray  # int64 (raw; 0 → limit resolved for leaky in pack)
    duration: np.ndarray  # int64
    created_at: np.ndarray  # int64; 0 = unset
    err: np.ndarray  # int8 error codes (ERR_*)


def concat_columns(parts: Sequence[RequestColumns]) -> RequestColumns:
    """One RequestColumns over a chunk's pieces, in arrival order."""
    if len(parts) == 1:
        return parts[0]
    return RequestColumns(
        *[np.concatenate([p[k] for p in parts]) for k in range(len(parts[0]))]
    )


def fingerprint_columns(names, keys) -> "tuple[np.ndarray, np.ndarray]":
    """Fingerprint parallel name/key string sequences; returns (fp, err).
    The per-item xxhash call is the one irreducible Python loop on the ingress
    path (native/ replaces it with a C pass when built)."""
    n = len(names)
    fp = np.zeros(n, dtype=np.int64)
    err = np.zeros(n, dtype=np.int8)
    for i in range(n):
        k = keys[i]
        nm = names[i]
        if k == "":
            err[i] = ERR_EMPTY_KEY
        elif nm == "":
            err[i] = ERR_EMPTY_NAME
        else:
            fp[i] = fingerprint(nm, k)
    return fp, err


def pack_columns(
    cols: RequestColumns, now_ms: int, tolerance_ms: Optional[int] = None
) -> "tuple[HostBatch, np.ndarray]":
    """Vectorized resolution of a RequestColumns batch into a HostBatch.
    Mirrors pack_requests() semantics exactly (validation, created_at
    clamping, leaky burst defaulting, Gregorian resolution); returns
    (batch, err_codes). `tolerance_ms` overrides the process-default clock
    skew bound (engines thread their own configured value)."""
    tol = _created_at_tolerance_ms if tolerance_ms is None else tolerance_ms
    n = cols.fp.shape[0]
    err = cols.err.copy()
    ok = err == ERR_OK
    bad_limit = ok & ((cols.limit > INT32_MAX) | (cols.limit < -INT32_MAX))
    err[bad_limit] = ERR_LIMIT_I32
    bad_burst = (err == ERR_OK) & (
        (cols.burst > INT32_MAX) | (cols.burst < -INT32_MAX)
    )
    err[bad_burst] = ERR_BURST_I32
    # forward-compat: unknown algorithm enum values (a newer peer's traffic)
    # become per-item "invalid rate limit algorithm" rows, never a failed
    # batch and never a silent fall-through into some other algorithm's math
    from gubernator_tpu.types import MAX_ALGORITHM

    bad_algo = (err == ERR_OK) & (
        (cols.algo < 0) | (cols.algo > MAX_ALGORITHM)
    )
    err[bad_algo] = ERR_ALGORITHM

    created = np.where(cols.created_at == 0, now_ms, cols.created_at)
    created = np.clip(created, now_ms - tol, now_ms + tol)
    # burst defaults to limit for the tolerance-shaped algorithms: leaky
    # (reference algorithms.go:259-261) and GCRA, whose delay-variation
    # tolerance tau = T·burst degenerates to "deny everything" at burst 0
    bursty = (cols.algo == int(Algorithm.LEAKY_BUCKET)) | (
        cols.algo == int(Algorithm.GCRA)
    )
    burst = np.where(bursty & (cols.burst == 0), cols.limit, cols.burst)

    expire_new = created + cols.duration
    greg_interval = np.zeros(n, dtype=np.int64)
    duration_eff = cols.duration.astype(np.int64).copy()
    greg_rows = (cols.behavior & int(Behavior.DURATION_IS_GREGORIAN)) != 0
    if greg_rows.any():
        # Gregorian durations are an enum (≤6 distinct values) and the whole
        # batch shares one `now` — resolve once per distinct enum value
        for val in np.unique(cols.duration[greg_rows]):
            rows = greg_rows & (cols.duration == val)
            try:
                expire = gregorian.gregorian_expiration(now_ms, int(val))
                interval = gregorian.gregorian_duration(now_ms, int(val))
            except gregorian.GregorianError:
                err[rows & (err == ERR_OK)] = ERR_GREGORIAN
                continue
            expire_new[rows] = expire
            greg_interval[rows] = interval
            duration_eff[rows] = expire - now_ms

    active = err == ERR_OK
    b = HostBatch(
        fp=np.where(active, cols.fp, 0),
        algo=cols.algo.astype(np.int32),
        behavior=cols.behavior.astype(np.int32),
        hits=cols.hits.astype(np.int64),
        limit=cols.limit.astype(np.int64),
        burst=burst.astype(np.int64),
        duration=cols.duration.astype(np.int64),
        created_at=created.astype(np.int64),
        expire_new=expire_new.astype(np.int64),
        greg_interval=greg_interval,
        duration_eff=duration_eff,
        active=active,
    )
    return b, err


class ResponseColumns(NamedTuple):
    """Column-oriented responses, request order. `err` uses ERR_* codes;
    ERROR_STRINGS maps them to the wire strings."""

    status: np.ndarray  # int32
    limit: np.ndarray  # int64
    remaining: np.ndarray  # int64
    reset_time: np.ndarray  # int64
    err: np.ndarray  # int8


def columns_from_requests(
    requests: Sequence[RateLimitRequest],
) -> RequestColumns:
    """Object → columns edge conversion (per-item loop lives here only)."""
    n = len(requests)
    fp = np.zeros(n, dtype=np.int64)
    err = np.zeros(n, dtype=np.int8)
    algo = np.zeros(n, dtype=np.int32)
    behavior = np.zeros(n, dtype=np.int32)
    hits = np.zeros(n, dtype=np.int64)
    limit = np.zeros(n, dtype=np.int64)
    burst = np.zeros(n, dtype=np.int64)
    duration = np.zeros(n, dtype=np.int64)
    created_at = np.zeros(n, dtype=np.int64)
    for i, r in enumerate(requests):
        if r.unique_key == "":
            err[i] = ERR_EMPTY_KEY
            continue
        if r.name == "":
            err[i] = ERR_EMPTY_NAME
            continue
        fp[i] = fingerprint(r.name, r.unique_key)
        algo[i] = int(r.algorithm)
        behavior[i] = int(r.behavior)
        hits[i] = r.hits
        limit[i] = min(max(r.limit, -(2**62)), 2**62)  # pre-clip to avoid int64 overflow
        burst[i] = min(max(r.burst, -(2**62)), 2**62)
        duration[i] = r.duration
        created_at[i] = r.created_at if r.created_at else 0
    return RequestColumns(
        fp=fp, algo=algo, behavior=behavior, hits=hits, limit=limit,
        burst=burst, duration=duration, created_at=created_at, err=err,
    )


def pack_requests(
    requests: Sequence[RateLimitRequest],
    now_ms: int,
    pad_to: Optional[int] = None,
    tolerance_ms: Optional[int] = None,
) -> "tuple[HostBatch, List[Optional[str]]]":
    """Resolve and pack requests into numpy SoA (host hot path).

    Returns (batch, errors): errors[i] is a per-request error string — the row
    is left inactive and must be answered with RateLimitResponse.error, exactly
    as the reference isolates invalid items instead of failing the batch
    (reference gubernator.go:215-237).

    Resolution performed here, mirroring host-side work in the reference:
    * validation: empty unique_key / name rejected (reference gubernator.go:215-224,
      including its quirky "field 'namespace' cannot be empty" wording)
    * created_at stamped with `now_ms` when unset (reference gubernator.go:225-227)
    * leaky burst==0 → limit (reference algorithms.go:259-261)
    * Gregorian: expire_new = end-of-interval, greg_interval = interval length,
      duration_eff = expire_new - now (reference algorithms.go:337-353,440-449);
      invalid Gregorian durations become per-request errors
    * non-Gregorian: expire_new = created_at + duration, duration_eff = duration
    """
    n = len(requests)
    size = pad_to if pad_to is not None else n
    if size < n:
        raise ValueError("pad_to smaller than batch")
    errors: List[Optional[str]] = [None] * n
    b = HostBatch(
        fp=np.zeros(size, dtype=np.int64),
        algo=np.zeros(size, dtype=np.int32),
        behavior=np.zeros(size, dtype=np.int32),
        hits=np.zeros(size, dtype=np.int64),
        limit=np.zeros(size, dtype=np.int64),
        burst=np.zeros(size, dtype=np.int64),
        duration=np.zeros(size, dtype=np.int64),
        created_at=np.zeros(size, dtype=np.int64),
        expire_new=np.zeros(size, dtype=np.int64),
        greg_interval=np.zeros(size, dtype=np.int64),
        duration_eff=np.zeros(size, dtype=np.int64),
        active=np.zeros(size, dtype=bool),
    )
    tol = _created_at_tolerance_ms if tolerance_ms is None else tolerance_ms
    for i, r in enumerate(requests):
        if r.unique_key == "":
            errors[i] = "field 'unique_key' cannot be empty"
            continue
        if r.name == "":
            errors[i] = "field 'namespace' cannot be empty"
            continue
        if not (-INT32_MAX <= r.limit <= INT32_MAX):
            errors[i] = "field 'limit' must fit int32"
            continue
        if not (-INT32_MAX <= r.burst <= INT32_MAX):
            errors[i] = "field 'burst' must fit int32"
            continue
        if not (0 <= int(r.algorithm) <= _max_algorithm()):
            errors[i] = ERROR_STRINGS[ERR_ALGORITHM]
            continue
        created = r.created_at if r.created_at is not None and r.created_at != 0 else now_ms
        if created > now_ms + tol:
            created = now_ms + tol
        elif created < now_ms - tol:
            created = now_ms - tol
        b.fp[i] = fingerprint(r.name, r.unique_key)
        b.algo[i] = int(r.algorithm)
        b.behavior[i] = int(r.behavior)
        b.hits[i] = r.hits
        b.limit[i] = r.limit
        b.duration[i] = r.duration
        b.created_at[i] = created
        if (
            int(r.algorithm) in (Algorithm.LEAKY_BUCKET, Algorithm.GCRA)
            and r.burst == 0
        ):
            b.burst[i] = r.limit
        else:
            b.burst[i] = r.burst
        if has_behavior(r.behavior, Behavior.DURATION_IS_GREGORIAN):
            try:
                expire = gregorian.gregorian_expiration(now_ms, r.duration)
                b.greg_interval[i] = gregorian.gregorian_duration(now_ms, r.duration)
            except gregorian.GregorianError as e:
                errors[i] = str(e)
                b.fp[i] = 0
                continue
            b.expire_new[i] = expire
            b.duration_eff[i] = expire - now_ms
        else:
            b.expire_new[i] = created + r.duration
            b.greg_interval[i] = 0
            b.duration_eff[i] = r.duration
        b.active[i] = True
    return b, errors


def pad_batch(b: HostBatch, to_size: int) -> HostBatch:
    """Zero-pad every field to `to_size` rows (inactive padding)."""
    n = b.fp.shape[0]
    if n == to_size:
        return b
    if n > to_size:
        raise ValueError("cannot pad smaller")
    return HostBatch(
        *[np.concatenate([f, np.zeros(to_size - n, dtype=f.dtype)]) for f in b]
    )


def pack_host_batch(b: HostBatch, out: Optional[np.ndarray] = None) -> np.ndarray:
    """Pack a HostBatch into ONE (12, B) int64 array for a single host→
    device transfer — the ingress mirror of kernel2.pack_outputs' single-
    fetch egress. Every device_put is a separate host→device transfer with
    its own fixed cost, so one put replaces 12 per-column ones (the saving
    is not measured on a co-located host).
    The device side reconstructs the ReqBatch inside the kernel's jit
    (kernel2.req_from_arr), costing a few casts that fuse into the kernel.

    `out` lets the mesh engines pack straight into a persistent staging
    buffer (parallel/sharded._StagingPool) — may be a strided view into the
    pooled (D, 12, c) ingress grid, so no fresh (12, B) allocation and no
    second scatter per dispatch."""
    n = b.fp.shape[0]
    if out is None:
        arr = np.empty((12, n), dtype=np.int64)
    else:
        assert out.shape == (12, n) and out.dtype == np.int64, out.shape
        arr = out
    arr[0] = b.fp
    arr[1] = b.algo
    arr[2] = b.behavior
    arr[3] = b.hits
    arr[4] = b.limit
    arr[5] = b.burst
    arr[6] = b.duration
    arr[7] = b.created_at
    arr[8] = b.expire_new
    arr[9] = b.greg_interval
    arr[10] = b.duration_eff
    arr[11] = b.active
    return arr


def to_device(b: HostBatch) -> ReqBatch:
    return ReqBatch(
        fp=jnp.asarray(b.fp),
        algo=jnp.asarray(b.algo),
        behavior=jnp.asarray(b.behavior),
        hits=jnp.asarray(b.hits),
        limit=jnp.asarray(b.limit),
        burst=jnp.asarray(b.burst),
        duration=jnp.asarray(b.duration),
        created_at=jnp.asarray(b.created_at),
        expire_new=jnp.asarray(b.expire_new),
        greg_interval=jnp.asarray(b.greg_interval),
        duration_eff=jnp.asarray(b.duration_eff),
        active=jnp.asarray(b.active),
    )
