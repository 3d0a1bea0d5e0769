"""Single-device engine: the TPU analog of the reference WorkerPool.

Owns one HBM table and turns lists of RateLimitRequests into responses by
packing → pass-planning → dispatching the decision kernel. Replaces the
reference's WorkerPool.GetRateLimit channel machinery (workers.go:266-330);
"worker goroutines" collapse into SIMD lanes of one kernel call.

Batches are padded to bucketed static shapes so jit caches a handful of
compiled kernels instead of one per batch size.

The fused front door (`prepare_check_wire`) packs nothing: the lanes the
native parser wrote are the ingress grid of pass 0, and the passes behind it
(the later copies of a key sent more than once in the chunk) are gathers of
the same lanes, split by the planner's rule (ops/plan.py).
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Callable, List, NamedTuple, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from gubernator_tpu import tracing
from gubernator_tpu.ops.batch import (
    ERR_DROPPED,
    InstallBatch,
    ERROR_STRINGS,
    HostBatch,
    RequestColumns,
    ResponseColumns,
    columns_from_requests,
    concat_columns,
    pack_columns,
    pack_host_batch,
    pad_batch,
    to_device,
)
from gubernator_tpu.ops.kernel2 import (
    FLAG_UNPROCESSED,
    decide2_packed_cols,
    install2,
    pack_outputs,
    unpack_outputs,
)
from gubernator_tpu.ops.plan import (
    Pass,
    aggregate_pass,
    occurrence_rank,
    plan_passes,
    runs,
    split_rows,
)
from gubernator_tpu.ops.table2 import Table2, new_table2
from gubernator_tpu.types import RateLimitRequest, RateLimitResponse

# Error surfaced for rows whose decision could never be persisted (claim
# dropped after every retry). The reference never silently skips the cache
# write; returning the computed answer without persisting it would hand out
# free decisions under pathological contention.
ERR_NOT_PERSISTED = "rate limit state could not be persisted (contended table); retry"


def default_write_mode() -> str:
    """Block-sparse Pallas write on real TPU — write cost ∝ batch, not table
    size; kernel2.resolve_write falls each dispatch shape back to the full
    table-streaming sweep when the sparse grid's coverage crosses
    GUBER_WRITE_SPARSE_CROSSOVER (e.g. 131K-row bench batches). XLA scatter
    everywhere else (CPU test meshes, and any backend without the TPU Pallas
    pipeline — e.g. GPU, where the sweep kernel has never been lowered)."""
    return "sparse" if jax.default_backend() == "tpu" else "xla"


def ms_now() -> int:
    # reference store.go MillisecondNow()
    return time.time_ns() // 1_000_000


def _pad_size(n: int, floor: int = 16) -> int:
    size = floor
    while size < n:
        size *= 2
    return size


def _math_mode(hb: HostBatch) -> str:
    """Static kernel specialization chosen host-side per dispatch
    (ops/math.bucket_math): an all-token batch (the common case — token is
    the reference's default algorithm) compiles ONLY the token lanes;
    GCRA / sliding-window / lease rows add the all-integer lanes; only a
    leaky row forces the emulated-f64 graph. Padding rows carry algo=0
    (token)."""
    algo = hb.algo
    if not algo.any():
        return "token"
    if (algo == 1).any():
        return "mixed"
    # all-GCRA specialization (headline single-algorithm traffic): only
    # the TAT lanes compile. Padding rows carry algo=0, so the check masks
    # to ACTIVE rows — inactive rows ride the gcra lanes harmlessly.
    act = algo[np.asarray(hb.active)]
    if act.size and (act == 2).all():
        return "gcra"
    return "int"


def batch_needs_full_layout(layout, math: str, hb=None) -> bool:
    """Host-side: can `layout` serve this batch? Shared by the local and
    mesh engines. Computed from the BATCH alone — migration only ever goes
    packed → full, so a prep-thread race reads at worst a stale packed
    layout and the engine-thread migrate call no-ops."""
    from gubernator_tpu.ops.layout import FULL

    if layout is FULL:
        return False
    if not layout.supports_math(math):
        return True
    if hb is not None and isinstance(hb, HostBatch):
        if not layout.greg_ok and (np.asarray(hb.greg_interval) != 0).any():
            return True
        if not layout.supports_algos(hb.algo, hb.active):
            return True
    return False


def effective_math(layout, hb) -> str:
    """The dispatch's math mode, layout-adjusted: an all-padding batch
    (warm-ups, all-error rows) defaults to "token" in _math_mode, which a
    packed non-token table cannot serve — padding rows ride ANY
    algorithm's lanes harmlessly (ops/math.py), so such batches take the
    layout's own mode instead of forcing a spurious migration."""
    math = _math_mode(hb)
    if not layout.supports_math(math) and not np.asarray(hb.active).any():
        return layout.modes[0]
    return math


def _has_cascade(hb) -> bool:
    """Whether a packed batch carries cascade level bits (behavior bits
    8-15, types.CASCADE_LEVEL_SHIFT)."""
    return bool((hb.behavior & np.int32(0xFF00)).any())


def _fold_cascades_host(
    behavior: np.ndarray,
    status: np.ndarray,
    remaining: np.ndarray,
    reset: np.ndarray,
    err: np.ndarray,
) -> None:
    """Host-side cascade verdict fold over assembled response columns:
    each carrier row (level 0) takes deny-if-any status, min remaining and
    the latest reset among denying levels of its group (members = the
    level>0 rows immediately following it). IDEMPOTENT over an already
    in-trace-folded carrier (kernel2.fold_cascade_packed), which is what
    lets it run unconditionally as the authoritative fold — it completes
    partial folds left by multi-pass plans, dropped-row retries and the
    mesh programs (whose routed/exchanged row order cannot fold in-trace).
    Rows with validation errors are excluded from the reductions; arrays
    mutate in place."""
    lvl = (behavior.astype(np.int64) >> 8) & 0xFF
    if not lvl.any():
        return
    n = lvl.shape[0]
    member = lvl > 0
    idx = np.arange(n)
    carrier = np.maximum.accumulate(np.where(~member, idx, -1))
    carrier = np.where(carrier < 0, idx, carrier)
    ok = err == 0
    mrows = np.nonzero(member & ok)[0]
    if mrows.size == 0:
        return
    c = carrier[mrows]
    np.maximum.at(status, c, status[mrows])
    np.minimum.at(remaining, c, remaining[mrows])
    deny = mrows[status[mrows] != 0]
    if deny.size:
        deny_reset = np.zeros(n, dtype=reset.dtype)
        np.maximum.at(deny_reset, carrier[deny], reset[deny])
        crows = np.nonzero(~member & ok & (status != 0))[0]
        reset[crows] = np.maximum(reset[crows], deny_reset[crows])


@dataclass
class EngineStats:
    """Host-side accumulation of kernel BatchStats (→ Prometheus layer)."""

    cache_hits: int = 0
    cache_misses: int = 0
    over_limit: int = 0
    evicted_unexpired: int = 0
    dropped: int = 0
    checks: int = 0
    dispatches: int = 0
    created_at_clamped: int = 0  # client timestamps outside the skew tolerance
    # rows that exhausted retries WITHOUT ever reaching the kernel (a2a
    # exchange-capacity drops, parallel/a2a.py): they appear in no
    # hit/miss/over counter, so without this the identity hits+misses ≈
    # checks would drift silently under sustained hot-shard overflow
    unprocessed_dropped: int = 0
    # packed-layout tables migrated to the full layout because off-family
    # traffic arrived (ops/layout.py selection contract) — a nonzero count
    # on a single-algorithm fleet means GUBER_SLOT_LAYOUT is misconfigured
    layout_migrations: int = 0
    # rows of a pipelined chunk that were a later copy of a key sent earlier
    # in it and were decided in the passes behind the first; of those, the
    # members of the aggregate pass (copy max_exact−1 and up of their key),
    # and the rows whose pass the fused path staged from the parser's lanes
    # (`_later_passes`; a member of a lane-staged aggregate counts)
    later_rows: int = 0
    aggregate_rows: int = 0
    later_lane_rows: int = 0
    # fused dispatches whose host staging was the one native call
    # (ops/wire.stage_wire_chunk), not the NumPy staging
    native_staged: int = 0
    # fused dispatches whose response columns the one native call wrote
    # (ops/wire.finish_wire_chunk), not the NumPy finish
    native_finished: int = 0
    # live rows a decide displaced whose state went to the host-RAM shadow
    # (gubernator_tpu/tier/), each also in `evicted_unexpired`, the
    # kernel's count of displaced live rows: the difference is state LOST
    # (`lost_live`), all of `evicted_unexpired` where no shadow is attached
    demoted_live: int = 0

    @property
    def lost_live(self) -> int:
        return self.evicted_unexpired - self.demoted_live

    def accumulate(self, stats, count_dropped: bool = True) -> None:
        self.cache_hits += int(stats.cache_hits)
        self.cache_misses += int(stats.cache_misses)
        self.over_limit += int(stats.over_limit)
        self.evicted_unexpired += int(stats.evicted_unexpired)
        if count_dropped:
            self.dropped += int(stats.dropped)

    def merge(self, d: "EngineStats") -> None:
        """Fold a pipelined check's stats delta in (applied on the engine
        thread so counter updates never race the dispatch path)."""
        self.cache_hits += d.cache_hits
        self.cache_misses += d.cache_misses
        self.over_limit += d.over_limit
        self.evicted_unexpired += d.evicted_unexpired
        self.dropped += d.dropped
        self.checks += d.checks
        self.dispatches += d.dispatches
        self.created_at_clamped += d.created_at_clamped
        self.unprocessed_dropped += d.unprocessed_dropped
        self.layout_migrations += d.layout_migrations
        self.later_rows += d.later_rows
        self.aggregate_rows += d.aggregate_rows
        self.later_lane_rows += d.later_lane_rows
        self.native_staged += d.native_staged
        self.native_finished += d.native_finished
        self.demoted_live += d.demoted_live


def _plan(engine, hb):
    """One batch's pass plan: the engine's `plan` hook when it has one
    (mesh engines aggregate duplicates in-trace and plan O(1) —
    parallel/sharded.ShardedEngine.plan), else the host group-by planner."""
    plan = getattr(engine, "plan", None)
    if plan is not None:
        return plan(hb)
    return plan_passes(hb, max_exact=engine.max_exact_passes)


def shadow_probe(engine, fps: np.ndarray, now_ms: int):
    """Fault-back probe (hot-set tiering, gubernator_tpu/tier/): exact-
    match the batch's fingerprints against the host-RAM shadow and REMOVE
    the hits — (fps, canonical rows) or None. Misses cost one dict lookup
    per unique fp, the off-hot-path contract; hits must be installed
    through the conservative merge BEFORE the batch's decide dispatch
    (promote_rows / PendingCheck.promote)."""
    shadow = getattr(engine, "shadow", None)
    if shadow is None or getattr(engine, "_evictees", False):
        # LocalEngine decides a shadowed key in its own miss path
        # (`_decide_faulting`), on the engine thread, where the shadow and
        # the table are one state; a probe ahead of the dispatch is not
        return None
    pf, rows = shadow.take(fps, now_ms)
    if pf.shape[0] == 0:
        return None
    return pf, rows


def promote_rows(engine, promote, now_ms: int):
    """Install a shadow_probe result into HBM through kernel2.merge2
    (engine thread — mutates the table). The merge's conservatism is the
    tiering soundness argument: a stale, duplicated, or raced promote can
    only UNDER-grant (docs/tiering.md). The closed-state-set discipline:
    live rows the installs displace demote onward to the shadow
    (merge2's evictee sidecar), and promote rows whose claim dropped
    (> K same-bucket inserters in one batch) retry and finally RETURN to
    the shadow instead of vanishing. Returns (installed_count,
    putback_fps) — the fingerprints this promote handed BACK to the
    shadow (returned leftovers + promote-displaced evictees): exactly
    the rows whose decide this batch may run against absent state, i.e.
    the miss re-check's eligibility set (_shadow_rehydrate). Rows the
    DECIDE dispatch itself later evicts are NOT eligible — their decide
    already served correctly from pre-evict state, and re-dispatching
    them would apply their hits twice."""
    if promote is None:
        return 0, np.empty(0, dtype=np.int64)
    from gubernator_tpu.ops.layout import FULL

    pf, rows = promote
    shadow = getattr(engine, "shadow", None)
    total = 0
    putback = []
    for _ in range(max(1, getattr(engine, "max_claim_retries", 3))):
        n, mask, ev_fps, ev_rows = engine.merge_rows(
            pf, rows, now_ms=now_ms, layout=FULL, collect=True
        )
        total += n
        if shadow is not None and ev_fps.shape[0]:
            shadow.offer(ev_fps, ev_rows, now_ms=0, reason="evict")
            putback.append(ev_fps)
        if mask.all():
            pf = pf[:0]
            break
        pf, rows = pf[~mask], rows[~mask]
    if shadow is not None and pf.shape[0]:
        shadow.offer(pf, rows, now_ms=0, reason="return")
        putback.append(pf)
    if not putback:
        return total, np.empty(0, dtype=np.int64)
    return total, np.concatenate(putback)


def _fit(mask: np.ndarray, like) -> np.ndarray:
    """`mask` over a batch's first rows, False-padded to its padded width."""
    out = np.zeros(np.asarray(like).shape[0], dtype=bool)
    out[: mask.shape[0]] = mask
    return out


def _batch_fps(batch, n: int) -> np.ndarray:
    """Output-row-aligned fingerprints of a pass batch (HostBatch or the
    fused front door's lazy wire batch — cheap column view, no pack)."""
    if isinstance(batch, HostBatch):
        return np.asarray(batch.fp[:n])
    return batch.fp_view()[:n]


def _shadow_rehydrate(engine, batch, n, outs, active, now, redispatch,
                      eligible):
    """Tiering miss re-check (the Store `_rehydrate_misses` pattern):
    device-reported misses whose state the PROMOTE stage handed back to
    the shadow (`eligible` = promote_rows' putback fps — returned
    leftovers and promote-displaced evictees under > K-same-bucket
    pressure) are promoted through the conservative merge and
    RE-DISPATCHED, overwriting their phase-1 fresh-grant responses. The
    phase-1 slot merges with the shadow row (remaining = min), so the
    corrected response is exact when the shadow state is tighter and
    conservative otherwise. Eligibility is strictly the promote putback
    set: a row the DECIDE dispatch itself evicted was served correctly
    from pre-evict state before landing in the shadow, and re-dispatching
    it would double-apply its hits. Single-shot: a residual miss (a
    second >K collision within the re-dispatch itself) keeps its fresh
    grant, the state stays shadowed for the next batch, and the incident
    is bounded by one limit (docs/tiering.md). `redispatch(fn)` runs fn
    on the engine thread and returns its result. Returns
    (outs, changed)."""
    shadow = getattr(engine, "shadow", None)
    if shadow is None or eligible is None or eligible.shape[0] == 0:
        return outs, False
    s, l, r, t, dropped, hit = outs
    miss = ~hit[:n] & active
    if not miss.any():
        return outs, False
    rows = np.nonzero(miss)[0]
    fps = _batch_fps(batch, n)[rows]
    has = np.isin(fps, eligible) & shadow.contains(fps)
    if not has.any():
        return outs, False
    # unique-fp contract for the re-dispatch: duplicate-fp rows (mesh
    # member fan-outs) keep their phase-1 response; the first occurrence
    # carries the correction
    sel = np.nonzero(has)[0]
    _, first = np.unique(fps[sel], return_index=True)
    fr = rows[sel[np.sort(first)]]
    sub_fps = _batch_fps(batch, n)[fr]

    def run():
        promote_rows(engine, shadow_probe(engine, sub_fps, now), now)
        sub = HostBatch(*[f[fr] for f in batch])
        return engine._redispatch_rows(sub, len(fr))

    s2, l2, r2, t2, d2, h2 = redispatch(run)
    m = len(fr)
    s[fr], l[fr], r[fr], t[fr] = s2[:m], l2[:m], r2[:m], t2[:m]
    dropped[fr] = d2[:m]
    hit[fr] = h2[:m]
    return (s, l, r, t, dropped, hit), True


def serve_columns(engine, cols, now_ms, dispatch) -> ResponseColumns:
    """The shared columns-in/columns-out serving loop: pack + clamp-count,
    plan same-key passes, dispatch each (member-row fan-out, ERR_DROPPED for
    unpersisted rows), fold cascade verdicts, fire the Store hooks.
    `dispatch(pass_batch, n_rows, cascade=False)` returns (status, limit,
    remaining, reset, dropped, cache_hit) over the pass rows — the only
    thing that differs between the single-device and mesh engines;
    `cascade` asks for the in-trace verdict fold (single-device engines
    honor it, mesh engines ignore it and lean on the host fold)."""
    now = now_ms if now_ms is not None else ms_now()
    hb, err = pack_columns(cols, now, tolerance_ms=engine.created_at_tolerance_ms)
    engine.stats.created_at_clamped += int(
        ((cols.created_at != 0) & (hb.created_at != cols.created_at)).sum()
    )
    # fault-back (tiering): shadowed keys re-enter HBM through the
    # conservative merge BEFORE their decide dispatch — this serial path
    # already runs on the engine thread, so probe + promote inline. The
    # putback fps feed the miss re-check's eligibility below.
    _, promote_putback = promote_rows(
        engine, shadow_probe(engine, hb.fp, now), now
    )
    n = hb.fp.shape[0]
    status = np.zeros(n, dtype=np.int32)
    limit_o = np.zeros(n, dtype=np.int64)
    remaining = np.zeros(n, dtype=np.int64)
    reset = np.zeros(n, dtype=np.int64)
    passes = _plan(engine, hb)
    has_casc = _has_cascade(hb)
    # the in-trace cascade fold needs the whole batch in one dispatch
    # (carrier adjacency) AND an engine whose program preserves row order;
    # multi-pass plans and mesh engines rely on the idempotent host fold
    # below instead
    casc_intrace = (
        has_casc and len(passes) == 1
        and getattr(engine, "supports_cascade_intrace", False)
    )
    for pi, p in enumerate(passes):
        np_ = len(p.rows)
        outs = dispatch(p.batch, np_, cascade=casc_intrace)
        if pi == 0 and engine.store is not None:
            # cache miss → consult the store and re-apply against hydrated
            # state (reference algorithms.go:45-51). Only pass 0 can miss:
            # later passes hit what pass 0 created.
            outs = _rehydrate_misses(engine, p.batch, np_, outs, now, dispatch)
        if pi == 0 and getattr(engine, "shadow", None) is not None:
            # tiering miss re-check (serial path runs on the engine
            # thread already — redispatch inline)
            outs, _ = _shadow_rehydrate(
                engine, p.batch, np_,
                outs, np.asarray(p.batch.active[:np_]), now,
                lambda fn: fn(), promote_putback,
            )
        s, l, r, t, dropped, _hit = outs
        if p.members is not None:
            # fan the aggregate's response out to every member row
            members = p.members
            src = np.repeat(np.arange(np_), p.member_counts)
            status[members] = s[src]
            limit_o[members] = l[src]
            remaining[members] = r[src]
            reset[members] = t[src]
            err[members[dropped[src]]] = ERR_DROPPED
        else:
            rows = p.rows
            status[rows] = s[:np_]
            limit_o[rows] = l[:np_]
            remaining[rows] = r[:np_]
            reset[rows] = t[:np_]
            err[rows[dropped[:np_]]] = ERR_DROPPED
    engine.stats.checks += n
    if engine.store is not None:
        ok = (err == 0) & (hb.fp != 0)
        if ok.any():
            from gubernator_tpu.store import ChangeSet

            idx = np.nonzero(ok)[0]
            # one row per unique fp, last occurrence wins — the changeset is
            # a STATE delta, not a request log (reference OnChange carries
            # the stored item, store.go:66-70)
            rev = idx[::-1]
            _, pos = np.unique(hb.fp[rev], return_index=True)
            keep = rev[pos]
            engine.store.on_change(
                ChangeSet(
                    fps=hb.fp[keep],
                    created_at=now,
                    algo=hb.algo[keep],
                    status=status[keep].astype(np.int32),
                    limit=limit_o[keep],
                    remaining=remaining[keep],
                    reset_time=reset[keep],
                    duration=hb.duration[keep],
                    burst=hb.burst[keep],
                    stamp=hb.created_at[keep],
                )
            )
    if has_casc:
        # authoritative fold AFTER the Store hook (the store records each
        # KEY's own state; only the carrier's RESPONSE takes the verdict)
        _fold_cascades_host(hb.behavior, status, remaining, reset, err)
    return ResponseColumns(
        status=status, limit=limit_o, remaining=remaining,
        reset_time=reset, err=err,
    )


def _rehydrate_misses(engine, batch, n: int, outs, now: int, dispatch):
    """Re-hydrate device cache misses from the Store: install found rows and
    re-dispatch just those requests against the stored state, overwriting
    their phase-1 (fresh-create) responses. The phase-1 slot is overwritten
    by the install, so hits apply exactly once — against the hydrated item."""
    s, l, r, t, dropped, hit = outs
    active = np.asarray(batch.active[:n])
    miss = ~hit[:n] & active
    if not miss.any():
        return outs
    rows = np.nonzero(miss)[0]
    res = engine.store.get_many(np.asarray(batch.fp[rows]), now)
    if res is None:
        return outs
    found = np.asarray(res["found"])
    if not found.any():
        return outs
    fr = rows[found]
    engine.install_columns(
        fp=np.asarray(batch.fp[fr]),
        algo=np.asarray(res["algo"])[found],
        status=np.asarray(res["status"])[found],
        limit=np.asarray(res["limit"])[found],
        remaining=np.asarray(res["remaining"])[found],
        reset_time=np.asarray(res["reset_time"])[found],
        duration=np.asarray(res["duration"])[found],
        now_ms=now,
        burst=np.asarray(res["burst"])[found],
        stamp=np.asarray(res["stamp"])[found],
    )
    sub = HostBatch(*[f[fr] for f in batch])
    m = len(fr)
    prev_status = s[fr].copy()
    prev_dropped = dropped[fr].copy()
    s2, l2, r2, t2, d2, h2 = dispatch(sub, m)
    for dst, src in ((s, s2), (l, l2), (r, r2), (t, t2), (dropped, d2), (hit, h2)):
        dst[fr] = src[:m]
    # a rehydrated row is ONE miss-then-warm, not a miss plus a hit — undo
    # the re-dispatch's double counting (reference counts Store.Get warms as
    # plain misses); likewise drop phase-1 over_limit/dropped for rows the
    # hydrated re-run superseded
    engine.stats.cache_hits -= int(h2[:m].sum())
    engine.stats.cache_misses -= int((~h2[:m]).sum())
    engine.stats.over_limit -= int((prev_status == 1).sum())
    engine.stats.dropped -= int((prev_dropped & ~d2[:m]).sum())
    return s, l, r, t, dropped, hit


class PendingCheck:
    """In-flight pipelined check: every pass's kernel dispatch has been
    ISSUED (device arrays pending) but nothing fetched yet. Produced on the
    engine thread by `issue_check_columns` (after `prepare_check_columns`
    staged the single-transfer ingress arrays off-thread), consumed on a
    fetch thread by `finish_check_columns` — the split that lets host pack +
    transfer of dispatch N+1 overlap device execution and fetch of N."""

    __slots__ = (
        "hb", "err", "now", "passes", "clamped", "rows", "mark",
        "casc", "casc_intrace", "promote", "promote_putback", "native",
    )

    def __init__(
        self, hb, err, now, passes, clamped, rows=None, mark=None,
        casc=False, casc_intrace=False, promote=None, native=False,
    ):
        self.hb = hb
        self.err = err
        self.now = now
        self.passes = passes  # [(Pass, n_rows, padded HostBatch, dev arr)]
        self.clamped = clamped
        # total request rows (fused wire batches carry no eager HostBatch)
        self.rows = rows if rows is not None else int(hb.fp.shape[0])
        # fingerprints this batch will touch — recorded into the checkpoint
        # epoch tracker at ISSUE time (engine thread), in the same job as
        # the launches, so a dirtied block can never fall between epochs
        # (ops/checkpoint.py ordering contract)
        self.mark = mark
        # cascade bookkeeping: `casc` = the batch carries level bits;
        # `casc_intrace` = the dispatches fold verdicts in-trace (single
        # pass), so the finish half only re-folds host-side after a
        # dropped-row retry invalidated a carrier
        self.casc = casc
        self.casc_intrace = casc_intrace
        # shadow fault-back rows (tiering): (fps, canonical rows) probed
        # OUT of the shadow on the prep thread, merged into HBM by
        # issue_check_columns on the engine thread BEFORE the launches —
        # the promote-stage ordering that keeps a promoted row's state
        # ahead of the decide that needs it (races stay conservative)
        self.promote = promote
        # fps the promote handed back to the shadow (the miss re-check's
        # eligibility set — set by issue_check_columns)
        self.promote_putback = None
        # a fused chunk staged by the native call (EngineStats.native_staged)
        self.native = native


class _LazyWireBatch:
    """Padded HostBatch materialized ONLY if the rare dropped-claim retry
    needs it — the fused wire path stages pre-packed lanes directly and
    skips pack_columns entirely on the common path. Duck-types the
    HostBatch uses inside the pipelined finish half: field iteration
    (`HostBatch(*[f[rows] for f in batch])`), the padded row count, and
    `active` — the rows the staged grid holds live lanes for (no error, and
    the first occurrence of their key in the chunk). A pass behind the grid
    is the chunk's rows `pick` (all active), folded into the planner's
    aggregate where it has `groups` (ops/plan.runs over `pick`)."""

    __slots__ = (
        "_parts", "_now", "_tol", "rows", "active", "_pick", "_groups", "_hb",
    )

    def __init__(
        self, parts, now, tol, rows, active=None, pick=None, groups=None
    ):
        self._parts = parts  # RequestColumns pieces, concat on demand
        self._now = now
        self._tol = tol
        self.rows = rows  # padded dispatch rows
        self.active = active  # (n,) bool, unpadded; the grid's batch only
        self._pick = pick
        self._groups = groups
        self._hb = None

    def _materialize(self) -> HostBatch:
        if self._hb is None:
            cols = concat_columns(self._parts)
            if self._pick is not None:
                cols = RequestColumns(*[f[self._pick] for f in cols])
            hb, _ = pack_columns(cols, self._now, tolerance_ms=self._tol)
            if self._pick is None:
                # as staged: a later copy of a key has no lane in this grid
                hb = hb._replace(
                    fp=np.where(self.active, hb.fp, 0), active=self.active
                )
            elif self._groups is not None:
                members = np.arange(self._pick.size)
                hb = aggregate_pass(hb, members, *self._groups).batch
            self._hb = pad_batch(hb, self.rows)
        return self._hb

    def __iter__(self):
        return iter(self._materialize())

    def select(self, rows: np.ndarray) -> HostBatch:
        """The HostBatch of `rows` of this pass alone (a retry's, the tiered
        table's deferred rows): packed from those rows' columns, not cut
        from the whole chunk's HostBatch, unless that exists already or the
        pass is an aggregate (whose rows are sums over the chunk)."""
        if self._hb is not None or self._groups is not None:
            return HostBatch(*[f[rows] for f in self._materialize()])
        cols = concat_columns(self._parts)
        idx = rows if self._pick is None else self._pick[rows]
        hb, _ = pack_columns(
            RequestColumns(*[f[idx] for f in cols]), self._now,
            tolerance_ms=self._tol,
        )
        if self._pick is None:  # as staged: see `_materialize`
            live = self.active[rows]
            hb = hb._replace(fp=np.where(live, hb.fp, 0), active=live)
        return hb

    def fp_view(self) -> np.ndarray:
        """Fingerprint column without materializing the HostBatch (the
        tiering miss re-check's cheap gate)."""
        if self._hb is not None:
            return np.asarray(self._hb.fp)
        if len(self._parts) == 1:
            return self._parts[0].fp
        return np.concatenate([p.fp for p in self._parts])


def _padded_rows(batch) -> int:
    """Padded dispatch rows of a pass batch (HostBatch or lazy wire batch)."""
    if isinstance(batch, HostBatch):
        return int(batch.fp.shape[0])
    return batch.rows


class _WireAssembly(NamedTuple):
    """What `_assemble_wire_parts` hands the two fused stagings."""

    chunk: "wire_mod.StagedChunk"  # the grid, its masks, the passes behind it
    cols_list: list  # the parts' RequestColumns
    now: int
    n: int
    tol: int
    pad: int
    native: bool  # staged by the native call, not by NumPy


def _assemble_wire_parts(engine, parts, now_ms=None):
    """Gating + host staging of the fused wire path: the parts' pre-packed
    native lanes become ONE padded compact ingress grid. Returns None when
    the batch needs the general columns path (engine not wire-capable,
    non-encodable rows, created_at skew beyond the ±511 ms delta budget,
    Store attached), else a `_WireAssembly`.

    Copies of one key need the planner's sequential passes, and the grid is
    its pass 0: occurrence 0 of every key rides its parser lane, and every
    later copy has its lane zeroed (fp == 0, inactive on decode, as an error
    row) and is a row of one of the passes behind the grid, each a gather of
    the chunk's own lanes (`StagedChunk.passes`). A repeated key next to
    cascade level bits means None (the in-trace fold needs a single pass).

    An engine whose program folds the copies itself (`folds_copies`: the
    mesh's in-trace dedup, the property that makes its `plan` one pass)
    gets the same staging with every copy left in the grid and nothing
    behind it. Such an engine says how wide its grid is (`wire_pad`: D
    device blocks of c rows), and declines on the parts themselves what the
    lanes cannot tell it: a behavior bit the wire drops as inert that it
    acts on (`wire_columns_behavior`: GLOBAL rows fork in `prepare_columns`)
    and level bits where it folds cascades on the host.

    The staging is one call into the native module, GIL-free from the first
    row to the last (ops/wire.stage_wire_chunk): on a loaded host every
    array call queues for the GIL again, and the NumPy staging is 45 to 155
    of them. Where the module is not loaded (`native.load()` is None: no
    toolchain) `_stage_chunk_numpy` is that staging, byte for byte."""
    if not getattr(engine, "supports_wire_ingress", False):
        return None
    if engine.store is not None or not engine.supports_pipeline:
        return None
    if not all(p.all_encodable for p in parts):
        return None
    acts_on = getattr(engine, "wire_columns_behavior", 0)
    if acts_on and any(_behavior_or(p) & acts_on for p in parts):
        return None
    wire_pad = getattr(engine, "wire_pad", None)
    cols_list = [p.cols for p in parts]
    n = sum(c.fp.shape[0] for c in cols_list)
    if n == 0:
        return None
    from gubernator_tpu import native
    from gubernator_tpu.ops import wire as wire_mod
    from gubernator_tpu.ops.batch import created_at_tolerance_ms

    now = now_ms if now_ms is not None else ms_now()
    tol = engine.created_at_tolerance_ms
    if tol is None:
        tol = created_at_tolerance_ms()
    pad = (wire_pad or _pad_size)(n)
    args = (parts, now, tol, pad, engine.max_exact_passes)
    keep_copies = bool(getattr(engine, "folds_copies", False))
    mod = native.load()
    with tracing.stage.within("wire_pack"):
        if mod is None:
            chunk = _stage_chunk_numpy(*args, keep_copies)
        else:
            chunk = wire_mod.stage_wire_chunk(
                mod, *args, _pad_size(0), keep_copies
            )
    if chunk is None:
        return None
    if chunk.casc and not getattr(engine, "supports_cascade_intrace", False):
        return None
    return _WireAssembly(chunk, cols_list, now, n, tol, pad, mod is not None)


def _behavior_or(part) -> int:
    """OR of a parsed piece's behavior words: the parser's own reduction
    where the piece still carries it, else a scan of the column."""
    if part.summary is not None:
        return part.summary.behavior_or
    return int(np.bitwise_or.reduce(part.cols.behavior, initial=0))


def _stage_chunk_numpy(parts, now, tol, pad, max_exact, keep_copies=False):
    """ops/wire.stage_wire_chunk in NumPy, of the same arguments: what a
    host with no toolchain runs, and what the tests hold the native staging
    to byte for byte. None: all-error chunk (the columns path produces it),
    a first copy's stamp outside the delta budget, a repeated key where
    there is no exact pass for the grid to be, or beside cascade level bits.
    With `keep_copies` no key is looked for twice: every active row keeps
    its lane."""
    from gubernator_tpu.ops import wire as wire_mod

    cols_list = [p.cols for p in parts]
    one = len(cols_list) == 1
    fp = cols_list[0].fp if one else np.concatenate([c.fp for c in cols_list])
    err = (
        cols_list[0].err.copy()
        if one
        else np.concatenate([c.err for c in cols_list])
    )
    n = fp.shape[0]
    active = err == 0
    if not active.any():
        return None
    act_fp = fp[active]
    # unique-fingerprint kernel contract: the grid takes the first of a
    # key's copies, the rest follow it as the planner's later passes
    first, later = active, None
    order, rank = (None, None) if keep_copies else occurrence_rank(fp)
    if rank is not None:
        rank[~active] = 0  # error rows (fp 0) are no copies of one another
        later = np.nonzero(rank)[0]
    if later is None or later.size == 0:
        later = None
    elif max_exact < 2:
        # with max_exact 1 the planner aggregates from occurrence 0
        return None
    else:
        first = active.copy()
        first[later] = False
    created = (
        cols_list[0].created_at
        if one
        else np.concatenate([c.created_at for c in cols_list])
    )
    stamped = np.where(created == 0, now, created)
    clipped = np.clip(stamped, now - tol, now + tol)
    clamped = int((clipped != stamped).sum())
    base = int(clipped[int(np.argmax(active))])
    delta = clipped - base
    fits = (delta >= -wire_mod.DELTA_BIAS) & (delta < wire_mod.DELTA_BIAS)
    if not fits[first].all():
        return None
    grid = wire_mod.assemble_wire_grid(
        [p.lanes for p in parts], clipped, base, pad, active
    )
    # cascade batches normally take the pb path (the native parser routes
    # them there), but an engine-level caller may assemble level-bit lanes
    # directly — a single pass, so the in-trace fold is sound here
    casc = wire_mod.grid_has_cascade(grid, n)
    passes = []
    if later is not None:
        if casc:
            return None
        lanes, grid = grid, grid.copy()
        grid[:, later] = 0
        passes = _later_blocks(
            lanes, order, rank, later, None if fits[later].all() else fits,
            max_exact,
        )
    return wire_mod.StagedChunk(
        grid, err, act_fp, first, clamped, wire_mod.grid_math_mode(grid, n),
        casc, 0 if later is None else int(later.size), passes,
    )


def _later_blocks(lanes, order, rank, later, fits, max_exact) -> list:
    """The passes behind a fused grid (`_stage_chunk_numpy`), each a gather
    of `lanes`, the grid as it was with every copy in it, so that no
    HostBatch is built. Exact pass r holds the rows of occurrence rank r in
    arrival order, r = 1…max_exact−2; the aggregate every row of rank
    max_exact−1 and up, where `plan_passes` puts them for the whole chunk.
    What the lanes cannot carry — a stamp beyond ±511 ms of the grid's base
    (`fits`; None: all fit), an aggregate's hits past 18 bits — is a pass
    with no block."""
    from gubernator_tpu.ops import wire as wire_mod

    exact, tail = split_rows(order, rank, max_exact)
    plan = [(rows, None, (None, None)) for rows in exact[1:]]
    if tail is not None:  # answered by each group's newest member
        starts, counts = runs(lanes[0, tail], lanes[1, tail])
        plan.append((tail[starts + counts - 1], tail, (starts, counts)))
    # every row of these passes is live: where all later copies name one
    # algorithm, all passes select the mode the first does
    algo = lanes[3, later] >> wire_mod.DUR_BITS
    same, mode = int(algo.min()) == int(algo.max()), None
    passes = []
    for rows, members, (starts, counts) in plan:
        pad, block, math = _pad_size(rows.size), None, None
        if fits is None or fits[rows if members is None else members].all():
            block = wire_mod.gather_wire_block(lanes, rows, pad, members, starts)
        if block is not None:
            if mode is None or not same:
                mode = wire_mod.grid_math_mode(block, pad)
            math = mode
        passes.append(
            wire_mod.StagedPass(rows, block, pad, math, members, starts, counts)
        )
    return passes


def _later_passes(engine, a: _WireAssembly) -> "tuple[list, int]":
    """The passes that follow a fused grid as `issue_check_columns` takes
    them: the staged blocks (`StagedChunk.passes`) put on the device in one
    transfer call, each behind a lazy batch that only a retry materializes.
    A pass the lanes could not carry is that pass alone packed and staged
    as columns. Returns the passes and how many rode the lanes."""
    passes, blocks, maths = [], [], []
    for sp in a.chunk.passes:
        p = Pass(sp.rows, None, sp.members, sp.member_counts)
        n = sp.rows.size
        p.batch = lazy = _LazyWireBatch(
            a.cols_list, a.now, a.tol, sp.pad,
            pick=sp.rows if sp.members is None else sp.members,
            groups=None if sp.members is None else (sp.starts, sp.member_counts),
        )
        if sp.block is None:
            p.batch, staged = engine.stage_pass(lazy._materialize(), n)
            passes.append([p, n, p.batch, staged])
        else:
            blocks.append(sp.block)
            maths.append(sp.math)
            passes.append([p, n, lazy, None])
    staged = iter(engine.stage_wire_blocks(blocks, maths))
    for entry in passes:
        if entry[3] is None:
            entry[3] = next(staged)
    return passes, len(blocks)


def _wire_pending(engine, a: _WireAssembly, staged):
    """PendingCheck over one assembled wire grid. Later copies of a key
    (`chunk.later`) are its passes after the grid's."""
    c = a.chunk
    lazy = _LazyWireBatch(a.cols_list, a.now, a.tol, a.pad, c.first)
    p = Pass(rows=np.arange(a.n), batch=lazy)
    pending = PendingCheck(
        hb=lazy, err=c.err, now=a.now, passes=[[p, a.n, lazy, staged]],
        clamped=c.clamped, rows=a.n, mark=c.act_fp, casc=c.casc,
        casc_intrace=c.casc, promote=shadow_probe(engine, c.act_fp, a.now),
        native=a.native,
    )
    if c.later:
        with tracing.stage.within("later_stage", rows=c.later) as st:
            later, lanes = _later_passes(engine, a)
            st.note(passes=len(later), lane_passes=lanes)
        pending.passes += later
    return pending


def prepare_check_wire(engine, parts, now_ms=None) -> "PendingCheck | None":
    """Fused front-door preparation: pre-packed native wire lanes
    (service/wire.WireBatch pieces) become ONE staged compact ingress grid —
    the request bytes were traversed once by the parser, and one native
    call over its lanes (`_assemble_wire_parts`) is the only further touch:
    what is left of `put` is that call, one `device_put` of the grid, one
    more of every block behind it, and the objects around them. A key sent
    more than once in the chunk keeps the grid for its first copy; the later
    ones are gathers of the same lanes in the passes behind it
    (`_later_passes`); a mesh engine that folds copies in-trace keeps them
    all in the grid, which its `stage_wire` lays out a block a device.
    Returns a PendingCheck for the standard issue/finish
    halves, or None when the batch needs the general columns path — the
    fallback is semantically identical, it just pays the full pack."""
    a = _assemble_wire_parts(engine, parts, now_ms=now_ms)
    if a is None:
        return None
    staged = engine.stage_wire(a.chunk.grid, a.chunk.math, cascade=a.chunk.casc)
    return _wire_pending(engine, a, staged)


def prepare_check_columns(engine, cols, now_ms=None) -> PendingCheck:
    """Preparation half of the pipelined serving path (any thread — touches
    no engine state): pack, clamp, plan same-key passes, and stage each
    pass's SINGLE packed ingress transfer on-device via the engine's
    `stage_pass` (LocalEngine: (12, B) array; ShardedEngine: routed
    (D, 12, b_local) grid).

    Engines with batch shapes the generic split cannot express (the
    mesh-global engine's replica/owner fork) provide `prepare_columns`,
    returning their own pending object — or None to fall through to the
    generic path for batches without the special rows."""
    hook = getattr(engine, "prepare_columns", None)
    if hook is not None:
        alt = hook(cols, now_ms=now_ms)
        if alt is not None:
            return alt
    now = now_ms if now_ms is not None else ms_now()
    hb, err = pack_columns(cols, now, tolerance_ms=engine.created_at_tolerance_ms)
    clamped = int(
        ((cols.created_at != 0) & (hb.created_at != cols.created_at)).sum()
    )
    plan = _plan(engine, hb)
    casc = _has_cascade(hb)
    casc_intrace = (
        casc and len(plan) == 1
        and getattr(engine, "supports_cascade_intrace", False)
    )
    passes = []
    for p in plan:
        n = len(p.rows)
        batch, staged = engine.stage_pass(p.batch, n, cascade=casc_intrace)
        passes.append([p, n, batch, staged])
    return PendingCheck(
        hb=hb, err=err, now=now, passes=passes, clamped=clamped, mark=hb.fp,
        casc=casc, casc_intrace=casc_intrace,
        promote=shadow_probe(engine, hb.fp, now),
    )


def issue_check_columns(engine, pending: PendingCheck) -> PendingCheck:
    """Engine-thread half: launch every staged pass WITHOUT fetching.
    Later passes depend only on device state, not fetched outputs, so the
    whole chain enqueues back-to-back; each entry's staged ingress is
    replaced by its pending (un-fetched) output handle."""
    if not isinstance(pending, PendingCheck):  # engine-specific pending
        return engine.issue_pending(pending)
    if pending.promote is not None:
        # shadow fault-back lands through the conservative merge BEFORE
        # this batch's launches (engine thread — merge_rows marks the
        # checkpoint tracker itself)
        _, pending.promote_putback = promote_rows(
            engine, pending.promote, pending.now
        )
        pending.promote = None
    elif getattr(engine, "_evictees", False):
        # a tiered table faults the grid's shadowed keys back ahead of the
        # launches, in this job, and fetches nothing (`fault_ahead`)
        _p, n0, batch, _staged = pending.passes[0]
        live = np.asarray(batch.active[:n0])
        engine.fault_ahead(_batch_fps(batch, n0)[live], pending.now)
    if pending.mark is not None and getattr(engine, "ckpt", None) is not None:
        # dirty-block marking for incremental checkpoints: same engine-
        # thread job as the launches below (ops/checkpoint.py contract)
        engine.ckpt.mark(pending.mark)
    for entry in pending.passes:
        _p, _n, batch, staged = entry
        entry[3] = engine.issue_staged(staged, _padded_rows(batch))
    return pending


# Per-pass pending handles differ by engine: LocalEngine issues a bare
# output array, ShardedEngine a (staged, out) tuple. These two helpers are
# the only place that distinction exists.
def _pending_out(pend):
    return pend[1] if isinstance(pend, tuple) else pend


def _pending_with_out(pend, out):
    return (pend[0], out) if isinstance(pend, tuple) else out


def fetch_passes(engine, passes) -> None:
    """ONE fetch of every pass's pending output (`jax.device_get` of the
    list: every copy to the host is started before the first is waited
    for), each entry's device handle replaced by its host array, which is
    what `finish_staged` and `finish_wire` take. A mesh engine banks the
    fetched device arrays as its next egress buffers (`_recycle_egress`)."""
    outs = [_pending_out(entry[3]) for entry in passes]
    fetched = jax.device_get(outs)
    recycle = getattr(engine, "_recycle_egress", None)
    for entry, dev, host in zip(passes, outs, fetched):
        if recycle is not None:
            recycle(dev)
        entry[3] = _pending_with_out(entry[3], host)


def finish_check_columns(
    engine, pending: PendingCheck, fixup
) -> "tuple[ResponseColumns, EngineStats]":
    """Fetch-thread half: materialize every pass's packed output in one
    fetch and assemble the response. The rare feedback path — claim drops
    needing a re-dispatch — runs through `fixup(fn)`, which executes fn ON
    THE ENGINE THREAD and returns its result (table mutations stay
    single-writer). Returns the response plus a stats delta for the caller
    to apply on the engine thread. Store-configured engines never take
    this path (EngineRunner routes them to the serial one): the Store
    contract needs rehydrates and write-throughs ordered against every
    same-key dispatch, which a pipeline with interleaved chunks cannot
    guarantee.

    A fused dispatch's passes are decoded and scattered to request order
    by one native call, GIL-free from the first row to the last
    (`_finish_native`): on a loaded host every array call queues for the
    GIL again, and the NumPy finish is about thirty of them a pass. Where
    the module is not loaded, or the dispatch is not one the call reads,
    `_finish_numpy` is that finish, byte for byte."""
    if not isinstance(pending, PendingCheck):  # engine-specific pending
        return engine.finish_pending(pending, fixup)
    fetch_passes(engine, pending.passes)
    n = pending.rows
    cols = (
        np.zeros(n, dtype=np.int32), np.zeros(n, dtype=np.int64),
        np.zeros(n, dtype=np.int64), np.zeros(n, dtype=np.int64),
    )
    delta = EngineStats(
        created_at_clamped=pending.clamped, checks=n,
        native_staged=int(pending.native),
    )
    retried = _finish_native(engine, pending, cols, delta, fixup)
    if retried is None:
        retried = _finish_numpy(engine, pending, cols, delta, fixup)
    status, limit_o, remaining, reset = cols
    if pending.casc and (retried or not pending.casc_intrace):
        # the in-trace fold (when it ran) predates any dropped-row retry;
        # the idempotent host fold makes the carriers authoritative again.
        # Fused wire batches materialize their HostBatch only on this rare
        # path (cascade batch AND a claim drop).
        hbm = pending.hb
        if not isinstance(hbm, HostBatch):
            hbm = hbm._materialize()
        _fold_cascades_host(hbm.behavior, status, remaining, reset, pending.err)
    rc = ResponseColumns(
        status=status, limit=limit_o, remaining=remaining,
        reset_time=reset, err=pending.err,
    )
    return rc, delta


def _finish_native(engine, pending, cols, delta, fixup) -> "bool | None":
    """The finish of a fused dispatch in one native call: every fetched
    block decoded into `cols` at its rows' places (the engine names its
    blocks: `finish_wire`), the summed stats into `delta`. Returns whether
    a row was retried, or None where the NumPy finish has to run: no
    native module, a pass that was not staged from the lanes (packed as
    columns, its output maybe full-width), or a promote that handed rows
    back to the shadow, whose re-check reads pass 0 decoded
    (`_shadow_rehydrate`). The rows that came back dropped are retried as
    the NumPy finish retries them, pass after pass, and patched into the
    columns the call wrote: a dropped row is a live one (kernel2: `active &
    ~written`), and a live row of the grid is answered by no pass behind
    it, so one pass's patches never meet another's rows."""
    from gubernator_tpu import native

    mod = native.load()
    finish = getattr(engine, "finish_wire", None)
    putback = pending.promote_putback
    if (
        mod is None or finish is None
        or (putback is not None and putback.shape[0])
        or not all(isinstance(e[2], _LazyWireBatch) for e in pending.passes)
    ):
        return None
    done = finish(mod, pending.passes, cols)
    if done is None:
        return None
    delta.native_finished = 1
    delta.cache_hits, delta.cache_misses, delta.over_limit, \
        delta.evicted_unexpired = done.stats
    delta.dispatches = len(pending.passes)
    delta.later_rows = delta.later_lane_rows = done.later_rows
    delta.aggregate_rows = done.aggregate_rows
    if not len(done.dropped):
        return False
    status, limit_o, remaining, reset = cols
    for pi in np.unique(done.dropped[:, 0]):
        p, _n, batch, _pend = pending.passes[pi]
        mine = done.dropped[done.dropped[:, 0] == pi]
        rows = mine[:, 1]
        sub = batch.select(rows)
        # rows the pass never processed (a2a capacity drops) have their
        # outcome counted by the retry
        unc = (mine[:, 2] & FLAG_UNPROCESSED) != 0
        s2, l2, r2, t2, d2, _h2 = fixup(
            lambda: engine._redispatch_rows(sub, len(rows), uncounted=unc)
        )
        if p.members is None:
            at = p.rows[rows]
        else:  # a retried row of the aggregate answers its group's members
            ends = np.cumsum(p.member_counts)
            at = np.concatenate(
                [p.members[ends[g] - p.member_counts[g]:ends[g]] for g in rows]
            )
            fan = np.repeat(np.arange(len(rows)), p.member_counts[rows])
            s2, l2, r2, t2, d2 = s2[fan], l2[fan], r2[fan], t2[fan], d2[fan]
        status[at], limit_o[at], remaining[at], reset[at] = s2, l2, r2, t2
        pending.err[at[d2]] = ERR_DROPPED
    return True


def _finish_numpy(engine, pending, cols, delta, fixup) -> bool:
    """The finish in NumPy, pass after pass: what a host with no toolchain
    runs, what every dispatch staged as columns runs, and what the tests
    hold `_finish_native` to byte for byte. Returns whether a row was
    retried."""
    status, limit_o, remaining, reset = cols
    err = pending.err
    retried_any = False
    for pi, (p, np_, batch, pend) in enumerate(pending.passes):
        (s, l, r, t, dropped, hit), st, uncounted = engine.finish_staged(
            pend, np_
        )
        delta.cache_hits += st[0]
        delta.cache_misses += st[1]
        delta.over_limit += st[2]
        delta.evicted_unexpired += st[3]
        delta.dispatches += 1
        if dropped.any():
            # contended-claim retries mutate the table → engine thread;
            # _redispatch_rows counts dispatches/evictions only, exactly
            # like the sync path's retry loop
            retried_any = True
            rows = np.nonzero(dropped)[0]
            # the rows' batch is made here, on the fetch thread: with a
            # shadow tier every key the table does not hold comes this
            # way, and the engine thread has the table to itself
            if isinstance(batch, HostBatch):
                sub = HostBatch(*[f[rows] for f in batch])
            else:
                sub = batch.select(rows)

            def retry(rows=rows, sub=sub, uncounted=uncounted):
                # padding conventions are the engine's own (LocalEngine pads
                # to _pad_size; ShardedEngine needs no row padding). Rows the
                # phase-1 pass never processed (a2a capacity drops) have
                # their outcome counted by the retry.
                unc = uncounted[rows] if uncounted is not None else None
                return engine._redispatch_rows(sub, len(rows), uncounted=unc)

            s2, l2, r2, t2, d2, h2 = fixup(retry)
            s[rows], l[rows], r[rows], t[rows] = s2, l2, r2, t2
            dropped[rows] = d2
            hit[rows] = h2
        if pi == 0 and getattr(engine, "shadow", None) is not None:
            # tiering miss re-check: promote + re-dispatch run on the
            # engine thread through the same fixup the dropped-claim
            # retries use. A fused wire batch's mask is the rows its grid
            # holds live: no error row, no later copy of a key.
            (s, l, r, t, dropped, hit), changed = _shadow_rehydrate(
                engine, batch, np_, (s, l, r, t, dropped, hit),
                np.asarray(batch.active[:np_]), pending.now, fixup,
                pending.promote_putback,
            )
            retried_any = retried_any or changed
        if p.members is not None:
            rows = p.members
            src = np.repeat(np.arange(np_), p.member_counts)
            s, l, r, t, dropped = s[src], l[src], r[src], t[src], dropped[src]
            if pi:
                delta.aggregate_rows += len(rows)
        else:
            rows = p.rows
            s, l, r, t, dropped = s[:np_], l[:np_], r[:np_], t[:np_], dropped[:np_]
        status[rows] = s
        limit_o[rows] = l
        remaining[rows] = r
        reset[rows] = t
        err[rows[dropped]] = ERR_DROPPED
        if pi:
            delta.later_rows += len(rows)
            if not isinstance(batch, HostBatch):
                delta.later_lane_rows += len(rows)
    return retried_any


class LocalEngine:
    """One device-resident rate-limit table + its dispatch loop.

    `decide_fn`/`table` injection exists for the differential test oracle
    (tests/oracle/ keeps the v1 plane kernel); production always runs the v2
    packed-row kernel (ops/kernel2.py).
    """

    supports_grow = True  # resize()/maybe_grow() are real (cf. ShardedEngine)
    supports_pipeline = True  # prepare/issue/finish split

    def __init__(
        self,
        capacity: int = 50_000,
        max_exact_passes: int = 8,
        write_mode: Optional[str] = None,
        decide_fn: Optional[Callable] = None,
        table=None,
        created_at_tolerance_ms: Optional[int] = None,
        store=None,
        wire: Optional[str] = None,
        layout: Optional[str] = None,
    ):
        from gubernator_tpu.ops.layout import resolve_layout
        from gubernator_tpu.ops.wire import default_wire_mode

        # slot layout (ops/layout.py): "full" (bit-compatible default),
        # "gcra32"/"token32" (32 B packed rows for single-algorithm
        # tables), or "auto"/"packed" policies; None reads
        # GUBER_SLOT_LAYOUT. Off-family traffic migrates a packed table to
        # full in place (one unpack) rather than erroring.
        if table is None:
            self._layout = resolve_layout(layout)
        else:
            # injected tables carry their own layout; the v1 oracle's
            # legacy Table has none (its plane layout predates descriptors)
            from gubernator_tpu.ops.layout import FULL

            self._layout = getattr(table, "layout", FULL)
        self.table = (
            table if table is not None
            else new_table2(capacity, layout=self._layout)
        )
        # host↔device wire format: "compact" ships 5-lane int32 ingress +
        # int32 egress (ops/wire.py, the TPU default — GUBER_WIRE_COMPACT),
        # "full" the 12-lane int64 grids (the parity oracle). Per-dispatch
        # encodability still falls compact batches back to full-width.
        if wire is not None and wire not in ("compact", "full"):
            raise ValueError(f"wire must be 'compact' or 'full', got {wire!r}")
        self.wire = wire or default_wire_mode()
        # one write mode for every dispatch: the block-sparse Pallas write
        # on TPU (kernel2.resolve_write falls big-batch shapes back to the
        # full sweep), XLA scatter on CPU meshes. A batch-size crossover to
        # the SCATTER used to exist on a "scatter costs ∝ batch" assumption
        # — measured FALSE
        # at scale (exp/README.md, exp_crossover, v5e, 1 GiB table: scatter ≈ 58 ms
        # at EVERY batch size 2K-16K vs sweep 4.1-4.9 ms), so it picked a
        # 13× slower path exactly where latency mattered.
        self.write_mode = write_mode or default_write_mode()
        self._decide_fn = decide_fn
        # oracle engines return unpacked outputs; the begin/finish split
        # assumes the packed single-fetch layout
        self.supports_pipeline = decide_fn is None
        self.max_exact_passes = max_exact_passes
        self.max_claim_retries = 3
        # per-engine clock-skew bound; None = the ops.batch process default
        self.created_at_tolerance_ms = created_at_tolerance_ms
        # optional write-through hook (gubernator_tpu.store.Store): fires a
        # ChangeSet of persisted fingerprints after every check — the
        # Store.OnChange analog (reference store.go:63-78, algorithms.go:148)
        self.store = store
        # incremental-checkpoint epoch tracker (ops/checkpoint.EpochTracker),
        # attached by service/checkpoint.CheckpointManager when the daemon
        # runs with GUBER_CHECKPOINT_INTERVAL_MS > 0; None = zero marking
        # cost on the serving path
        self.ckpt = None
        # hot-set tiering (gubernator_tpu/tier/): host-RAM ShadowTable
        # attached by the daemon's TierManager (or tests). Non-None flips
        # the dispatch entries' static `evictees` flag — victim rows ride
        # the fetched outputs home and demote instead of vanishing — and
        # arms the fault-back probe in the serving paths. None = zero
        # cost, bit-identical dispatch graphs.
        self.shadow = None
        self._tier_metrics = None
        self._tier = dict.fromkeys(
            ("probed", "promoted", "promoted_ahead", "returned",
             "merge_launches", "rehydrate_dispatches"), 0,
        )
        # merges an issue job launched and left unfetched (`fault_ahead`),
        # oldest first: (merged mask, evictee block, the fps and rows the
        # launch was given); `drain_sidecars` empties it
        self._sidecars: list = []
        self.stats = EngineStats()
        # device passes launched, by padded batch size (_issue_from_dev):
        # the shapes a resize compiles again, and what `passes_by_write`
        # counts
        self._pad_passes: dict = {}
        # reason string when a failed donated launch left device state
        # suspect (see GlobalShardedEngine._requeue_popped); surfaces as
        # health_check "unhealthy". Never set on the single-device path
        # today, but the daemon reads it engine-agnostically.
        self.poisoned: Optional[str] = None

    def _mark_dirty(self, fps) -> None:
        """Checkpoint hook: record the touched fingerprints' blocks in the
        epoch tracker (ops/checkpoint.py). Called on the engine thread in
        the same job as the mutation it precedes, so marks and takes
        interleave FIFO and no dirtied block falls between epochs."""
        if self.ckpt is not None:
            self.ckpt.mark(np.asarray(fps))

    # --------------------------------------------------------------- tiering
    #
    # With a shadow attached the table is upstream's bounded LRU and the
    # shadow the Store behind it, and every answer is what an unbounded
    # table would give. What makes that exact is where a key's state may
    # be: in the table, in the shadow, or in exactly one unfetched sidecar
    # (`_sidecars`: the outputs of a `merge2` an issue job launched ahead
    # of its passes, `fault_ahead`) whose launch precedes, on the device,
    # every program launched after it. The shadow's only host reader is
    # `take`, always behind a drain of those sidecars (`drain_sidecars`),
    # and the only programs that create a key are the claiming decide
    # (launched by `_decide_faulting` alone, behind a drain and a take) and
    # `merge2`, which installs only rows a take returned. The pipelined
    # launches (`issue_staged`) run the hits-only program
    # (evictees="defer"): a key the table does not hold, one whose state
    # is in a sidecar among them, is neither created nor answered there,
    # no row is evicted, and its row comes back dropped for the finish
    # half's fixup, which drains before it looks. So no key is granted
    # afresh while its count is on its way, and a key is never in two
    # places (docs/tiering.md).

    @property
    def _evictees(self) -> bool:
        """Whether dispatches compile the tiered programs (a shadow tier
        is attached; the v1 oracle's unpacked outputs carry no sidecar).
        The serving halves then leave the fault-back to the dispatch
        itself (`shadow_probe` and what follows it stay the mesh engine's,
        whose decide carries no sidecar)."""
        return self.shadow is not None and self._decide_fn is None

    def attach_shadow(self, shadow, metrics=None) -> None:
        """Arm hot-set tiering: evict capture + fault-back from `shadow`
        (tier.ShadowTable). Call before serving — flipping it mid-flight
        only costs recompiles. `metrics` (the daemon's) takes the tier's
        stage samples (tier_probe, tier_promote, tier_harvest). What the
        shadow attached until now is still owed goes to it first (engine
        thread): the warm-up's scratch shadow leaks no sidecar into the
        real one."""
        self.drain_sidecars()
        self.shadow = shadow
        self._tier_metrics = metrics
        self._tier = dict.fromkeys(self._tier, 0)

    def tier_counts(self) -> dict:
        """The fault-back's counts since start (engine thread writes):
        `promoted` and `merge_launches` count every promote and every
        `merge2` launch, `promoted_ahead` the promotes an issue job's merge
        installed (`fault_ahead`; counted when its sidecar is drained)."""
        return dict(self._tier)

    def _harvest_evictees(self, host_arr: np.ndarray) -> None:
        """Demote-on-evict: decode the dispatch's evictee sidecar and
        append the victim rows to the shadow. `host_arr`
        must come from a dispatch issued with evictees=True, fetched on
        the engine thread in the job that launched it. Expiry filtering is
        left to promote time (`take` drops dead rows against the request
        timeline; wall clock here could disagree with a test's synthetic
        clock)."""
        if self.shadow is None:
            return
        # the stats row's evicted_unexpired cell gates the decode: the
        # common hot-set dispatch evicts nothing and pays ONE cell read
        if int(host_arr[-2, 3]) == 0:
            return
        from gubernator_tpu.ops.kernel2 import unpack_evictees

        with tracing.stage("tier_harvest", self._tier_metrics) as st:
            fps, rows = unpack_evictees(host_arr)
            st.note(rows=int(fps.shape[0]))
            if fps.shape[0]:
                self.shadow.offer(fps, rows, now_ms=0, reason="evict")
        self.stats.demoted_live += int(fps.shape[0])

    def drain_sidecars(self) -> None:
        """Fetch every sidecar an earlier engine-thread job left unfetched
        (engine thread; the head of every job that reads or writes the
        shadow): each a finished `merge2`'s merged mask and evictee block.
        The victims go to the shadow, the promotes whose install found no
        lane (more than K keys of one bucket in the launch) back to it.
        Stage `tier_harvest`, one sample a sidecar."""
        t = self._tier
        while self._sidecars:
            merged, ev, pf, rows = self._sidecars[0]
            with tracing.stage("tier_harvest", self._tier_metrics) as st:
                mask, ev_fps, ev_rows = self._merge_collect(
                    merged, ev, pf.shape[0]
                )
                # fetched: the record goes (a fetch that raises keeps it)
                del self._sidecars[0]
                st.note(rows=int(ev_fps.shape[0]), returned=int((~mask).sum()))
                landed = int(mask.sum())
                t["promoted"] += landed
                t["promoted_ahead"] += landed
                if landed < pf.shape[0]:
                    t["returned"] += int(pf.shape[0]) - landed
                    self.shadow.offer(
                        pf[~mask], rows[~mask], now_ms=0, reason="return"
                    )
                if ev_fps.shape[0]:
                    self.shadow.offer(ev_fps, ev_rows, now_ms=0, reason="evict")

    def _take_shadowed(self, fps: np.ndarray, now: int):
        """`shadow.take` of `fps`, timed and counted (stage `tier_probe`)."""
        with tracing.stage("tier_probe", self._tier_metrics) as st:
            pf, rows = self.shadow.take(fps, now)
            st.note(rows=int(fps.shape[0]), hits=int(pf.shape[0]))
        self._tier["probed"] += int(fps.shape[0])
        return pf, rows

    def fault_ahead(self, fps: np.ndarray, now: int) -> None:
        """The fault-back of a pipelined dispatch, at the head of its issue
        job (engine thread): the shadowed ones of `fps` (the keys the
        dispatch's grid holds live) are taken out of the shadow and
        installed by ONE `merge2` launched ahead of the dispatch's passes
        and NOT fetched: the device runs programs in launch order, so the
        hits-only passes behind it see the installs, and the launch's
        outputs wait in `_sidecars` for the next job that touches the
        shadow. If the launch raises, the taken rows go back first."""
        self.drain_sidecars()
        t = self._tier
        pf, rows = self._take_shadowed(fps, now)
        if pf.shape[0] == 0:
            return
        with tracing.stage(
            "tier_promote", self._tier_metrics, rows=int(pf.shape[0]), launches=1
        ):
            try:
                merged, ev = self._merge_launch(pf, rows, now, True)
            except BaseException:
                self.shadow.offer(pf, rows, now_ms=0, reason="return")
                raise
        t["merge_launches"] += 1
        # the outputs' way to the host starts here, behind the merge and
        # ahead of the dispatch's passes, and is waited for by no one: the
        # drain a job later finds them on the host
        merged.copy_to_host_async()
        ev.copy_to_host_async()
        self._sidecars.append((merged, ev, pf, rows))

    def _fault_in(self, fps: np.ndarray, now: int) -> np.ndarray:
        """Bring the shadowed ones of `fps` back into the table before
        their decide (the miss path, engine thread): taken out of the
        shadow and installed by ONE `merge2` launch, fetched here, whose
        own victims go to the shadow before this returns. Returns the
        fingerprints whose state is in the shadow as this returns, whose
        decide therefore has to wait for the next round: those whose
        install found no lane (more than K keys of one bucket in the
        batch; their rows are back in the shadow) and the merge's own
        victims, one of which may be a key of this batch."""
        from gubernator_tpu.ops.layout import FULL

        t = self._tier
        pf, rows = self._take_shadowed(fps, now)
        if pf.shape[0] == 0:
            return pf
        with tracing.stage(
            "tier_promote", self._tier_metrics, rows=int(pf.shape[0]), launches=1
        ):
            _n, mask, ev_fps, ev_rows = self.merge_rows(
                pf, rows, now_ms=now, layout=FULL, collect=True
            )
            t["merge_launches"] += 1
            t["promoted"] += int(mask.sum())
            held = pf[~mask]
            if held.shape[0]:
                self.shadow.offer(held, rows[~mask], now_ms=0, reason="return")
                t["returned"] += int(held.shape[0])
        if ev_fps.shape[0]:
            # the merge's victims: its sidecar's way into the shadow, as
            # the decide's (`_harvest_evictees`)
            with tracing.stage(
                "tier_harvest", self._tier_metrics, rows=int(ev_fps.shape[0])
            ):
                self.shadow.offer(ev_fps, ev_rows, now_ms=0, reason="evict")
        return np.concatenate([held, ev_fps])

    def _decide_faulting(self, batch, n: int, cascade: bool = False):
        """One unique-fp pass over a tiered table, exactly (engine thread):
        what `_dispatch_with_retry` is to a table without a shadow. Each
        round takes the rows' shadowed state back into the table
        (`_fault_in`), decides the rows whose state is now in it with the
        claiming program (evictees=True), and puts that program's victims
        into the shadow before anything else is launched; a row whose
        claim was contended, or whose promote found no lane, is the next
        round's. Counts every row it decides (the hits-only program counts
        none of the rows it deferred). What an issue job's merge left
        unfetched is in the shadow before the first take."""
        self.drain_sidecars()
        status = np.zeros(n, dtype=np.int32)
        limit = np.zeros(n, dtype=np.int64)
        remaining = np.zeros(n, dtype=np.int64)
        reset = np.zeros(n, dtype=np.int64)
        hit = np.zeros(n, dtype=bool)
        rows, sub = np.arange(n), batch
        dropped = np.zeros(n, dtype=bool)
        for attempt in range(self.max_claim_retries + 1):
            m = len(rows)
            live = np.asarray(sub.active[:m]) & (np.asarray(sub.fp[:m]) != 0)
            wait = np.zeros(m, dtype=bool)
            if live.any():
                fps = np.asarray(sub.fp[:m])
                now = int(np.asarray(sub.created_at[:m])[live].max())
                held = self._fault_in(fps[live], now)
                if held.shape[0]:
                    wait = live & np.isin(fps, held)
                    out = _fit(wait, sub.fp)
                    sub = sub._replace(
                        fp=np.where(out, 0, sub.fp),
                        active=np.asarray(sub.active) & ~out,
                    )
            (s2, l2, r2, t2, d2, h2), st = unpack_outputs(
                self._decide_packed(sub, cascade and attempt == 0), m
            )
            self._tier["rehydrate_dispatches"] += 1
            self.stats.cache_hits += st[0]
            self.stats.cache_misses += st[1]
            self.stats.over_limit += st[2]
            self.stats.evicted_unexpired += st[3]
            self.stats.dispatches += 1
            status[rows], limit[rows], remaining[rows], reset[rows] = s2, l2, r2, t2
            hit[rows] = h2
            again = d2 | wait
            dropped[:] = False
            dropped[rows[again]] = True
            if not again.any():
                break
            rows = rows[again]
            sub = pad_batch(
                HostBatch(*[f[:n][rows] for f in batch]), _pad_size(len(rows))
            )
        return status, limit, remaining, reset, dropped, hit

    def extract_idle(self, now_ms: int, idle_ms: int,
                     max_rows: int = 1 << 16):
        """Live rows idle past `idle_ms`: (fps (N,) i64, slots (N,
        F_layout) i32), N ≤ max_rows — the demote-on-idle sweep's read
        half (EngineRunner.tier_demote_idle pairs it with tombstone_fps
        in ONE engine-thread job so no decide interleaves)."""
        from gubernator_tpu.ops.table2 import extract_idle_rows

        return extract_idle_rows(
            self.table.rows, now_ms, idle_ms, layout=self.table.layout,
            max_rows=max_rows,
        )

    # ---------------------------------------------------------- slot layout

    def _batch_needs_full(self, math: str, hb=None) -> bool:
        return batch_needs_full_layout(self.table.layout, math, hb)

    def _effective_math(self, hb: HostBatch) -> str:
        return effective_math(self.table.layout, hb)

    def migrate_layout_full(self, reason: str = "off-family traffic") -> bool:
        """Migrate a packed table to the canonical full layout in place —
        one jitted row unpack, engine thread only. Returns True when a
        migration actually happened. The one-way direction is deliberate:
        packed layouts are a boot-time bet on single-algorithm traffic,
        and losing the bet must degrade to correct-and-bigger, never to
        wrong bytes."""
        from gubernator_tpu.ops.layout import FULL

        lay = self.table.layout
        if lay is FULL:
            return False
        import logging

        logging.getLogger("gubernator_tpu.engine").warning(
            "migrating table layout %s -> full (%s)", lay.name, reason
        )
        rows_full = jax.jit(lay.unpack_rows)(self.table.rows)
        self.table = Table2(rows=rows_full, layout=FULL)
        self._layout = FULL
        self.stats.layout_migrations += 1
        return True

    def _decide_packed(self, hb: HostBatch, cascade: bool = False) -> np.ndarray:
        """One dispatch → ONE host transfer each way: compact 5-lane int32
        wire block (or full packed (12, B) ingress) in, compact int32 (or
        packed (B+2, 4) i64) output fetched. Updates self.table; returns
        the host array (unpack_outputs dispatches on its dtype). `cascade`
        compiles the in-trace verdict fold into the dispatch (single-pass
        batches with level bits only — the fold needs carrier adjacency)."""
        self._mark_dirty(hb.fp)
        if self._decide_fn is not None:
            # oracle engines return unpacked outputs; pack on device for the
            # same downstream shape
            self.table, resp, stats = self._decide_fn(self.table, to_device(hb))
            return np.asarray(pack_outputs(resp, stats, hb.behavior))
        math = self._effective_math(hb)
        if self._batch_needs_full(math, hb):
            self.migrate_layout_full()
        dev, wired = self._stage_ingress(hb)
        out = np.asarray(
            self._issue_from_dev(
                dev, int(hb.fp.shape[0]), math, wired, cascade
            )
        )
        if self._evictees:
            # serial path: the fetch happened right here — demote the
            # victims before the caller decodes responses
            self._harvest_evictees(out)
        return out

    def _stage_ingress(self, batch: HostBatch):
        """Stage ONE ingress array for a padded batch: the compact wire
        block when the engine is in compact mode and the batch is
        representable (ops/wire.wire_encodable — Gregorian rows, oversize
        hits/durations, skewed created_at fall back), else the full-width
        grid. Returns (device array, compact?)."""
        import jax

        if self.wire == "compact":
            from gubernator_tpu.ops import wire as wire_mod

            base = wire_mod.pick_base(batch)
            if wire_mod.wire_encodable(batch, base):
                return (
                    jax.device_put(wire_mod.pack_wire_full(batch, base)),
                    True,
                )
        return jax.device_put(pack_host_batch(batch)), False

    def _issue_from_dev(
        self, dev_arr, batch_rows: int, math: str, wired: bool = False,
        cascade: bool = False, defer: bool = False,
    ) -> "jax.Array":
        """Launch one pass from a staged ingress array WITHOUT fetching (the
        output is fetched later, off this thread); counted by its pad. On a
        tiered table `defer` selects the hits-only program (a pipelined
        launch), else the claiming one whose victims ride home."""
        self._pad_passes[batch_rows] = self._pad_passes.get(batch_rows, 0) + 1
        ev = self._evictees and ("defer" if defer else True)
        if wired:
            from gubernator_tpu.ops.wire import decide2_wire_cols

            self.table, packed = decide2_wire_cols(
                self.table, dev_arr, write=self.write_mode, math=math,
                cascade=cascade, evictees=ev,
            )
            return packed
        self.table, packed = decide2_packed_cols(
            self.table, dev_arr, write=self.write_mode, math=math,
            cascade=cascade, evictees=ev,
        )
        return packed

    # ------------------------------------------------- pipelined protocol
    # stage_pass (any thread) → issue_staged (engine thread) → finish_staged
    # (fetch thread); the packed single-transfer layout stays private to the
    # engine so mesh engines can substitute routed grids (parallel/sharded.py).

    def stage_pass(self, pass_batch: HostBatch, n: int, cascade: bool = False):
        """(padded batch, staged ingress array + static math/wire/cascade
        modes + layout-mismatch flag) for one unique-fp pass."""
        batch = pad_batch(pass_batch, _pad_size(n))
        math = self._effective_math(batch)
        dev, wired = self._stage_ingress(batch)
        return batch, (
            dev, math, wired, cascade, self._batch_needs_full(math, batch)
        )

    @property
    def supports_cascade_intrace(self) -> bool:
        """Single-device dispatches preserve batch row order, so the
        kernel-side cascade fold (fold_cascade_packed) is sound here; mesh
        engines route/exchange rows and leave the fold to the host
        (_fold_cascades_host). Oracle engines (decide_fn) predate the
        packed entries and never fold in-trace."""
        return self._decide_fn is None

    @property
    def supports_wire_ingress(self) -> bool:
        """Whether the fused front-door path (prepare_check_wire: native
        parser lanes staged straight into a compact grid) may target this
        engine. Compact-wire engines only — full-width mode stays the
        byte-for-byte parity oracle. A mesh engine answers for itself
        (parallel/sharded.ShardedEngine.supports_wire_ingress: the
        arrival-order grid of the device route is the lanes in D blocks; a
        host-routed per-shard grid is not)."""
        return self.wire == "compact" and self._decide_fn is None

    def stage_wire(self, grid: np.ndarray, math: str, cascade: bool = False):
        """Stage a fused front-door grid (ops/wire.assemble_wire_grid
        output) — same staged tuple as stage_pass's, issued by
        issue_staged unchanged."""
        return self.stage_wire_blocks([grid], [math], cascade)[0]

    def stage_wire_blocks(self, blocks, maths, cascade: bool = False):
        """`stage_wire` of several wire blocks (a grid; the passes behind
        one, ops/wire.gather_wire_block) in one transfer call. Wire blocks
        carry no Gregorian rows (wire_encodable excludes them) and their
        algorithm family is implied by the math mode, so the layout check
        needs no batch."""
        return [
            (dev, math, True, cascade, self._batch_needs_full(math))
            for dev, math in zip(jax.device_put(blocks), maths)
        ]

    def issue_staged(self, staged, batch_rows: int):
        dev, math, wired, cascade, needs_full = staged
        if needs_full:
            # engine thread — the only thread allowed to swap the table
            self.migrate_layout_full()
        return self._issue_from_dev(
            dev, batch_rows, math, wired, cascade, defer=True
        )

    def finish_staged(self, pending, n: int):
        """Decode one pass's fetched packed output → ((s, l, r, t, dropped,
        hit), (hits, misses, over, evicted), uncounted). The single-device
        kernel probes every row, so `uncounted` is always None here (cf.
        ShardedEngine's a2a capacity drops). With a shadow attached this
        is the hits-only program's output: the rows it deferred come back
        dropped and go the retry's way, `_redispatch_rows`."""
        outs, st = unpack_outputs(pending, n)
        return outs, st, None

    def finish_wire(self, mod, passes, cols):
        """The native finish of a fused dispatch's `passes` (fetched) into
        `cols` (ops/wire.finish_wire_chunk; fetch thread): each pass's
        compact egress array is its block, base and stats rows in it.
        None where a pass came back full-width."""
        from gubernator_tpu.ops.wire import finish_wire_chunk

        return finish_wire_chunk(
            mod,
            [
                (out, n, p.rows, p.members, p.member_counts, None, None)
                for p, n, _batch, out in passes
            ],
            cols,
        )

    def _redispatch_rows(self, batch, n: int, uncounted=None):
        """Re-dispatch rows whose phase-1 claim dropped (pipelined retry):
        accounts dispatches/evictions/final drops only — hits/misses/over
        were already counted by the dropped phase-1 pass, exactly like the
        sync path's retry loop. `uncounted` is a mesh-engine concern
        (ShardedEngine): ignored here. On a tiered table this is where a
        key the table did not hold is decided (`_decide_faulting`)."""
        batch = pad_batch(batch, _pad_size(n))
        if self._evictees:
            outs = self._decide_faulting(batch, n)
            self.stats.dropped += int(outs[4].sum())
            return outs
        (status, limit, remaining, reset, dropped, hit), st = unpack_outputs(
            self._decide_packed(batch), n
        )
        self.stats.dispatches += 1
        self.stats.evicted_unexpired += st[3]
        # this first dispatch already IS retry #1 of the dropped phase-1
        # rows, so the loop allows max_claim_retries-1 more — same total
        # attempt budget as the sync path
        dropped = self._retry_dropped(
            batch, n, status, limit, remaining, reset, dropped, hit, retries=1
        )
        self.stats.dropped += int(dropped.sum())
        return status, limit, remaining, reset, dropped, hit

    def _retry_dropped(
        self, batch, n, status, limit, remaining, reset, dropped, hit, retries
    ):
        """Shared claim-drop retry loop: re-dispatch dropped rows (evictions +
        dispatches counted only) until persisted or the attempt budget runs
        out. Mutates the response arrays in place; returns the final dropped
        mask."""
        while dropped.any() and retries < self.max_claim_retries:
            rows = np.nonzero(dropped)[0]
            sub = HostBatch(*[f[:n][rows] for f in batch])
            sub = pad_batch(sub, _pad_size(len(rows)))
            m = len(rows)
            (s2, l2, r2, t2, d2, h2), st = unpack_outputs(
                self._decide_packed(sub), m
            )
            self.stats.dispatches += 1
            self.stats.evicted_unexpired += st[3]
            status[rows], limit[rows], remaining[rows], reset[rows] = s2, l2, r2, t2
            hit[rows] = h2
            nd = np.zeros(n, dtype=bool)
            nd[rows] = d2
            dropped = nd
            retries += 1
        return dropped

    def check(
        self,
        requests: Sequence[RateLimitRequest],
        now_ms: Optional[int] = None,
    ) -> List[RateLimitResponse]:
        """Apply a batch; responses come back in request order (the API
        contract, reference gubernator.proto:58-61). Object-API wrapper over
        the columns fast path."""
        if not requests:
            return []
        from gubernator_tpu.types import retry_after_ms

        now = now_ms if now_ms is not None else ms_now()
        cols = columns_from_requests(requests)
        rc = self.check_columns(cols, now_ms=now)
        return [
            RateLimitResponse(
                status=int(rc.status[i]),
                limit=int(rc.limit[i]),
                remaining=int(rc.remaining[i]),
                reset_time=int(rc.reset_time[i]),
                error=ERROR_STRINGS[int(rc.err[i])],
                retry_after_ms=retry_after_ms(
                    int(rc.status[i]), int(rc.reset_time[i]), now
                ),
            )
            for i in range(len(requests))
        ]

    def check_columns(
        self,
        cols: RequestColumns,
        now_ms: Optional[int] = None,
    ) -> ResponseColumns:
        """Vectorized serving path: columns in, columns out (request order).
        Per-request validation errors come back as ERR_* codes instead of
        failing the batch (reference gubernator.go:215-237)."""

        def dispatch(pass_batch, n_rows: int, cascade: bool = False):
            batch = pad_batch(pass_batch, _pad_size(n_rows))
            return self._dispatch_with_retry(batch, n_rows, cascade)

        self.drain_sidecars()
        return serve_columns(self, cols, now_ms, dispatch)

    def _dispatch_with_retry(self, batch, n: int, cascade: bool = False):
        """Run one unique-fp pass; rows the claim auction dropped (contended
        bucket within a single dispatch) are re-dispatched — the decision is
        only authoritative once persisted. Rows still unpersisted after
        `max_claim_retries` surface a per-item error (`ERR_NOT_PERSISTED`)."""
        if self._evictees:
            outs = self._decide_faulting(batch, n, cascade)
            self.stats.dropped += int(outs[4].sum())
            return outs
        (status, limit, remaining, reset, dropped, hit), st = unpack_outputs(
            self._decide_packed(batch, cascade), n
        )
        self.stats.cache_hits += st[0]
        self.stats.cache_misses += st[1]
        self.stats.over_limit += st[2]
        self.stats.evicted_unexpired += st[3]
        self.stats.dispatches += 1
        dropped = self._retry_dropped(
            batch, n, status, limit, remaining, reset, dropped, hit, retries=0
        )
        # only rows still unpersisted after retries count as dropped
        self.stats.dropped += int(dropped.sum())
        return status, limit, remaining, reset, dropped, hit

    # ------------------------------------------------------------ peer plane

    def install_columns(
        self,
        fp: np.ndarray,
        algo: np.ndarray,
        status: np.ndarray,
        limit: np.ndarray,
        remaining: np.ndarray,
        reset_time: np.ndarray,
        duration: np.ndarray,
        now_ms: Optional[int] = None,
        burst: Optional[np.ndarray] = None,
        stamp: Optional[np.ndarray] = None,
        aux: Optional[np.ndarray] = None,
        rem_store: Optional[np.ndarray] = None,
    ) -> int:
        """Install owner-authoritative GLOBAL statuses as fresh items — the
        UpdatePeerGlobals receive path (reference gubernator.go:434-474).
        Returns the number installed. `burst`/`stamp` default to the wire
        path's lossy rebuild (Burst=Limit, stamp=now — exactly the
        reference's, gubernator.go:434-474); the Store rehydrate path passes
        the stored values for full fidelity. `aux`/`rem_store` carry
        sliding-window broadcast fidelity (previous-window count and the
        stored-style remaining) when the wire provides them."""
        if self._decide_fn is not None:
            raise RuntimeError("install_columns unsupported on the v1 oracle engine")
        now = now_ms if now_ms is not None else ms_now()
        n = fp.shape[0]
        if n == 0:
            return 0
        if burst is None:
            burst = np.asarray(limit, dtype=np.int64)
        if stamp is None:
            stamp = np.full(n, now, dtype=np.int64)
        if not self.table.layout.supports_algos(algo):
            self.migrate_layout_full("install of off-family algorithms")
        self._mark_dirty(fp)
        size = _pad_size(n)

        def pad(a, dtype):
            out = np.zeros(size, dtype=dtype)
            out[:n] = a
            return out

        import jax.numpy as jnp

        inst = InstallBatch(
            fp=jnp.asarray(pad(fp, np.int64)),
            algo=jnp.asarray(pad(algo, np.int32)),
            status=jnp.asarray(pad(status, np.int32)),
            limit=jnp.asarray(pad(limit, np.int64)),
            remaining=jnp.asarray(pad(remaining, np.int64)),
            reset_time=jnp.asarray(pad(reset_time, np.int64)),
            duration=jnp.asarray(pad(duration, np.int64)),
            now=jnp.asarray(pad(np.full(n, now, dtype=np.int64), np.int64)),
            active=jnp.asarray(pad(np.ones(n, dtype=bool), bool)),
            burst=jnp.asarray(pad(burst, np.int64)),
            stamp=jnp.asarray(pad(stamp, np.int64)),
            aux=None if aux is None else jnp.asarray(pad(aux, np.int64)),
            rem_store=(
                None if rem_store is None
                else jnp.asarray(pad(rem_store, np.int64))
            ),
        )
        self.table, installed = install2(
            self.table, inst, write=self.write_mode
        )
        self.stats.dispatches += 1
        return int(np.asarray(installed).sum())

    # ------------------------------------------------------------- handoff
    # Topology-change survivability (docs/robustness.md): extract packs every
    # live slot on-device, merge applies transferred slots conservatively
    # (kernel2.merge2 — a retried/duplicated transfer can never grant extra
    # capacity), tombstone zeroes acked rows so they are neither re-served
    # nor re-snapshotted by the source.

    def extract_live(self, now_ms: Optional[int] = None):
        """All live slots as (fps (N,) i64, slots (N, F_layout) i32) host
        arrays — the device pays for the full-table filter+pack, the host
        fetches only the live prefix (ops/table2.extract_live_rows). Slots
        ride the table's own layout; the TransferState wire tags them with
        the layout code so a receiver on a different layout converts
        through the canonical full row."""
        from gubernator_tpu.ops.table2 import extract_live_rows

        now = now_ms if now_ms is not None else ms_now()
        return extract_live_rows(
            self.table.rows, now, layout=self.table.layout
        )

    def _slots_to_full(self, slots: np.ndarray, layout=None) -> np.ndarray:
        """Normalize incoming slot rows to the canonical full layout — the
        one cross-layout conversion point (ops/layout.py contract). With no
        explicit layout, a 16-field row is full and an 8-field row is
        assumed to be this table's own packed layout (same-fleet
        transfers); cross-layout senders always say theirs."""
        from gubernator_tpu.ops import layout as layout_mod

        if layout is None:
            if slots.shape[1] == layout_mod.FULL.F:
                layout = layout_mod.FULL
            elif slots.shape[1] == self.table.layout.F:
                layout = self.table.layout
            else:
                raise ValueError(
                    f"cannot infer slot layout for width {slots.shape[1]}"
                )
        return np.asarray(layout.unpack(slots))

    def merge_rows(
        self, fps: np.ndarray, slots: np.ndarray,
        now_ms: Optional[int] = None, layout=None, collect: bool = False,
    ):
        """Conservatively merge transferred slot rows (TransferState receive
        path): remaining=min, expiry=max, newest config wins. Returns the
        number of rows merged/installed. `slots` may arrive in any sender
        layout (`layout`; inferred for full-width / same-layout rows) — the
        merge itself always runs on canonical full rows, so the
        conservatism is layout-independent. Duplicate fingerprints within
        one call merge as sequential passes — the claim machinery's
        unique-fp contract, same as the serving planner's (a chunk from one
        extract is always unique, but crossed transfers may not be).

        `collect=True` (the tiering promote path — unique fps only)
        instead returns (count, merged_mask (n,), evictee_fps, evictee
        canonical rows): the mask says which incoming rows actually
        landed (a claim-dropped promote must return to the shadow, not
        vanish) and the evictees are LIVE rows the installs displaced
        (demoted onward instead of destroyed). It is the launch half and
        the collect half in a row (`_merge_launch`, `_merge_collect`); the
        issue job's fault-back launches and leaves the collecting to a
        later job (`fault_ahead`, `drain_sidecars`)."""
        n = fps.shape[0]
        if n == 0:
            if collect:
                return 0, np.zeros(0, dtype=bool), np.empty(
                    0, dtype=np.int64
                ), np.empty((0, 16), dtype=np.int32)
            return 0
        slots = self._slots_to_full(slots, layout)
        _order, rank = occurrence_rank(fps)
        if rank is not None:
            if collect:
                raise ValueError(
                    "merge_rows(collect=True) requires unique fingerprints"
                )
            return sum(
                self.merge_rows(fps[rank == r], slots[rank == r], now_ms)
                for r in range(int(rank.max()) + 1)
            )
        merged, ev = self._merge_launch(fps, slots, now_ms, collect)
        if collect:
            mask, ev_fps, ev_rows = self._merge_collect(merged, ev, n)
            return int(mask.sum()), mask, ev_fps, ev_rows
        return int(np.asarray(merged).sum())

    def _merge_launch(self, fps, slots, now_ms, evictees: bool):
        """Launch half of `merge_rows`: ONE `merge2` over unique
        fingerprints and their canonical full rows, padded to the pow2
        shapes the warm-up compiles; the table is the new one as this
        returns and nothing is fetched. Returns the un-fetched (merged
        mask, evictee block or None)."""
        from gubernator_tpu.ops.kernel2 import merge2
        from gubernator_tpu.ops.table2 import FLAGS

        n = fps.shape[0]
        if not self.table.layout.supports_algos(slots[:, FLAGS] & 0xFF):
            self.migrate_layout_full("merge of off-family rows")
        now = now_ms if now_ms is not None else ms_now()
        self._mark_dirty(fps)
        size = _pad_size(n)
        fp_p = np.zeros(size, dtype=np.int64)
        fp_p[:n] = fps
        slots_p = np.zeros((size, slots.shape[1]), dtype=np.int32)
        slots_p[:n] = slots
        active = np.zeros(size, dtype=bool)
        active[:n] = True
        # one transfer call for the four: on a loaded host every call that
        # gives the GIL away queues for it again
        args = jax.device_put(
            [fp_p, slots_p, np.full(size, now, dtype=np.int64), active]
        )
        ev = None
        if evictees:
            self.table, merged, ev = merge2(
                self.table, *args, write=self.write_mode, evictees=True
            )
        else:
            self.table, merged = merge2(
                self.table, *args, write=self.write_mode
            )
        self.stats.dispatches += 1
        return merged, ev

    @staticmethod
    def _merge_collect(merged, ev, n: int):
        """Collect half of `merge_rows(collect=True)`: fetch a launch's
        outputs → (merged mask (n,), evictee fps, evictee canonical rows)."""
        mask = np.asarray(merged)[:n].copy()
        ev_h = np.asarray(ev)
        ev_lo = ev_h[:, 0].astype(np.int64) & 0xFFFFFFFF
        ev_fp = (ev_h[:, 1].astype(np.int64) << 32) | ev_lo
        keep = ev_fp != 0
        return mask, ev_fp[keep], ev_h[keep].copy()

    def read_state(self, fps: np.ndarray, raw: bool = False):
        """Read the full-width stored slots for `fps` without mutating
        anything: (found (n,) bool, slots (n, 16) i32 canonical fields).
        One device bucket gather — the GLOBAL broadcast plane uses this to
        attach sliding-window aux (prev count, stored remaining) to owner
        updates (service/global_manager._broadcast). `raw=True` returns
        the rows re-packed into THIS table's own slot layout ((n,
        layout.F) — exact for in-family rows, ops/layout.py) so the
        region-sync sender ships its stored rows at the table's native
        width and the receiver converts through the canonical full row."""
        import jax.numpy as jnp

        from gubernator_tpu.ops.table2 import F as F_FULL, gather_slots

        n = fps.shape[0]
        if n == 0:
            width = self.table.layout.F if raw else F_FULL
            return (
                np.zeros(0, dtype=bool), np.zeros((0, width), dtype=np.int32)
            )
        size = _pad_size(n)
        fp_p = np.zeros(size, dtype=np.int64)
        fp_p[:n] = fps
        active = np.zeros(size, dtype=bool)
        active[:n] = True
        slots, found = gather_slots(
            self.table.rows, jnp.asarray(fp_p), jnp.asarray(active),
            layout=self.table.layout,
        )
        out = np.asarray(slots)[:n].copy()
        if raw:
            out = np.asarray(self.table.layout.pack(out))
        return np.asarray(found)[:n].copy(), out

    def tombstone_fps(self, fps: np.ndarray) -> int:
        """Zero the slots holding `fps` (post-ack handoff cleanup). Missing
        fingerprints are no-ops; returns the number actually removed."""
        import jax.numpy as jnp

        from gubernator_tpu.ops.table2 import Table2, tombstone_rows

        n = fps.shape[0]
        if n == 0:
            return 0
        self._mark_dirty(fps)
        size = _pad_size(n)
        fp_p = np.zeros(size, dtype=np.int64)
        fp_p[:n] = fps
        active = np.zeros(size, dtype=bool)
        active[:n] = True
        rows, found = tombstone_rows(
            self.table.rows, jnp.asarray(fp_p), jnp.asarray(active)
        )
        self.table = Table2(rows=rows, layout=self.table.layout)
        self.stats.dispatches += 1
        return int(np.asarray(found).sum())

    # ------------------------------------------------------------- telemetry

    def telemetry_begin(self, now_ms: Optional[int] = None):
        """Launch the fused table-telemetry scan (ops/telemetry.py) without
        fetching — called on the engine thread so it reads a coherent table,
        finished off-thread so the device scan overlaps serving dispatches
        (EngineRunner.table_telemetry)."""
        from gubernator_tpu.ops.telemetry import scan_begin

        return scan_begin(
            self.table.rows, now_ms if now_ms is not None else ms_now(),
            layout=self.table.layout,
        )

    # ---------------------------------------------------------- checkpointing

    def snapshot(self) -> np.ndarray:
        """Device→host copy of the whole table (the Loader.Save analog,
        reference store.go:49-60 / workers.go:457-540)."""
        return np.asarray(self.table.rows)

    def restore(self, rows: np.ndarray, layout=None) -> None:
        """Host→device restore of a snapshot taken by `snapshot()` (the
        Loader.Load analog, reference workers.go:335-419). A snapshot
        written under a DIFFERENT slot layout (`layout` — recorded in the
        snapshot file) converts through the canonical full row on the host
        when the bucket geometry matches; the engine's own layout is kept."""
        import jax
        import jax.numpy as jnp

        lay = self.table.layout
        if layout is not None and layout is not lay:
            from gubernator_tpu.ops.table2 import FLAGS, F as F_FULL

            if rows.shape[:-1] != tuple(self.table.rows.shape[:-1]):
                raise ValueError(
                    f"snapshot geometry {rows.shape} incompatible with "
                    f"table {tuple(self.table.rows.shape)}"
                )
            full = np.asarray(layout.unpack_rows(rows))
            slots = full.reshape(-1, F_FULL)
            occupied = (slots[:, 0] != 0) | (slots[:, 1] != 0)
            if not lay.supports_algos((slots[:, FLAGS] & 0xFF)[occupied]):
                # the snapshot holds rows this packed layout cannot store:
                # degrade the ENGINE to full rather than corrupt state
                self.migrate_layout_full("restore of off-family snapshot")
                lay = self.table.layout
            rows = np.asarray(lay.pack_rows(full))
        if rows.shape != tuple(self.table.rows.shape):
            raise ValueError(
                f"snapshot shape {rows.shape} != table {tuple(self.table.rows.shape)}"
            )
        self.table = Table2(
            rows=jax.device_put(jnp.asarray(rows, dtype=jnp.int32)),
            layout=lay,
        )
        if self.ckpt is not None:
            # a mid-life restore replaces state of unknown provenance: the
            # next delta epoch must capture everything live, not just what
            # was marked before (boot-time restores run with no tracker
            # attached, so the warm path never pays this)
            self.ckpt.mark_all()

    def checkpoint_begin(self, gids: np.ndarray, now_ms: Optional[int] = None):
        """LAUNCH half of a dirty-block checkpoint extract (engine thread —
        reads a coherent table, costs only the enqueue); finish with
        `checkpoint_finish` on any thread while serving keeps dispatching
        (the telemetry_begin overlap pattern)."""
        from gubernator_tpu.ops.checkpoint import extract_begin

        now = now_ms if now_ms is not None else ms_now()
        return extract_begin(
            self.table.rows, gids, self.ckpt.blk, now,
            layout=self.table.layout,
        )

    def checkpoint_finish(self, pending):
        """FETCH half: (fps (N,) i64, slots (N, F) i32) — only the live
        prefix of the dirty blocks crosses the device→host boundary."""
        from gubernator_tpu.ops.checkpoint import finish_extract

        return finish_extract(pending)

    def live_count(self, now_ms: Optional[int] = None) -> int:
        from gubernator_tpu.ops.table2 import live_count2

        return live_count2(self.table, now_ms if now_ms is not None else ms_now())

    # -------------------------------------------------------------- resizing

    def resize(self, new_capacity: int, now_ms: Optional[int] = None) -> int:
        """Grow (or shrink) the table to `new_capacity` slots, re-placing
        every live entry (host-orchestrated rehash — SURVEY §7 hard-parts).
        The reference's LRU never resizes (CacheSize is fixed, config.go:151);
        here growth is cheap enough to expose: one device→host snapshot, a
        vectorized host rehash, one host→device put. Every previously-compiled
        batch shape is re-warmed against the new bucket count BEFORE serving
        resumes (a new (NB, ·) geometry means fresh XLA compiles — paying them
        inside resize() keeps them out of the request path, the same incident
        Daemon.warm_up prevents at startup). Returns the number of live
        entries dropped by per-bucket overflow in the new geometry (counted as
        unexpired evictions)."""
        import jax
        import jax.numpy as jnp

        from gubernator_tpu.ops.batch import HostBatch
        from gubernator_tpu.ops.table2 import n_buckets_for, rehash_rows

        now = now_ms if now_ms is not None else ms_now()
        lay = self.table.layout
        new_rows, dropped = rehash_rows(
            self.snapshot(), n_buckets_for(new_capacity), now, layout=lay
        )
        self.table = Table2(
            rows=jax.device_put(jnp.asarray(new_rows)), layout=lay
        )
        self.stats.evicted_unexpired += dropped
        if self.ckpt is not None:
            # block ids do not survive a geometry change: fresh tracker,
            # same epoch lineage, everything dirty (the next delta carries
            # the rehashed live set once)
            self.ckpt = self.ckpt.rebuild(self.table.rows.shape[0])
        # warm compiles for the new geometry with all-inactive dummy batches
        # (no state mutation — _decide_packed counts nothing itself, and all
        # rows are inactive). Three static math variants warm: algo=0 rows
        # compile the token graph, a GCRA-marked row the all-integer one, a
        # leaky row the mixed one (_math_mode; the all-GCRA "gcra" variant
        # needs an ACTIVE row, so a rare pure-GCRA batch right after a
        # resize pays its own compile).
        from gubernator_tpu.ops.layout import FULL as _FULL

        # packed layouts warm only their own math graph (off-family probe
        # rows would trigger a spurious migration)
        probe_algos = (0, 2, 1) if lay is _FULL else (lay.algos[0],)
        for size in sorted(self._pad_passes):
            z64 = np.zeros(size, dtype=np.int64)
            for probe_algo in probe_algos:
                algo = np.zeros(size, dtype=np.int32)
                algo[0] = probe_algo
                dummy = HostBatch(
                    fp=z64, algo=algo,
                    behavior=np.zeros(size, dtype=np.int32), hits=z64,
                    limit=np.ones(size, dtype=np.int64), burst=z64,
                    duration=np.ones(size, dtype=np.int64), created_at=z64,
                    expire_new=z64, greg_interval=z64,
                    duration_eff=np.ones(size, dtype=np.int64),
                    active=np.zeros(size, dtype=bool),
                )
                self._decide_packed(dummy)
        return dropped

    def maybe_grow(
        self,
        threshold: float = 0.6,
        factor: int = 2,
        max_capacity: Optional[int] = None,
        now_ms: Optional[int] = None,
    ) -> bool:
        """Auto-grow policy: double the table when live slots exceed
        `threshold` of capacity (open-addressed buckets degrade past ~0.6
        load). Call from a maintenance tick. Returns True if resized.
        `max_capacity` bounds the REALIZED capacity: bucket counts round up to
        a valid sweep geometry (n_buckets_for), so the clamp picks the largest
        conforming geometry that stays under the ceiling."""
        from gubernator_tpu.ops.table2 import K, n_buckets_for

        cap = self.table.capacity
        if self.live_count(now_ms) <= threshold * cap:
            return False
        new_cap = cap * factor
        if max_capacity is not None:
            while new_cap > cap and n_buckets_for(new_cap) * K > max_capacity:
                new_cap //= factor
            if new_cap <= cap:
                return False
        self.resize(new_cap, now_ms)
        return True

    # ------------------------------------------------------------ pass counts

    def passes_by_write(self) -> dict:
        """Device passes launched since warm-up, by the write their padded
        shape resolves to on this table (kernel2.resolve_write over the
        counts by pad: `sparse`, `sweep`, or `xla` off the TPU). Read from
        any thread; the engine thread alone counts."""
        from gubernator_tpu.ops.kernel2 import resolve_write

        nb = int(self.table.rows.shape[-2])
        lay = getattr(self.table, "layout", None)
        out = {"sparse": 0, "sweep": 0, "xla": 0}
        for pad, n in list(self._pad_passes.items()):
            out[resolve_write(self.write_mode, nb, pad, lay)] += n
        return out

    def forget_passes(self) -> None:
        """Zero the pass counts and keep their shapes (warm-up is no traffic,
        and a resize still has to compile what it compiled)."""
        self._pad_passes = dict.fromkeys(self._pad_passes, 0)
