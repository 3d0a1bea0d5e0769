"""GLOBAL behavior as mesh collectives — the reference's globalManager
(reference global.go:31-307) re-designed for the TPU interconnect.

In the reference, a GLOBAL rate limit has one owning node; every other node
answers from a local read-replica immediately and asynchronously ships its
accumulated hits to the owner (runAsyncHits, 100 ms cadence), which applies
them with DRAIN_OVER_LIMIT forced and broadcasts the authoritative status to
every peer (runBroadcasts → UpdatePeerGlobals). Worst case 3+N gRPC messages
per hit, amortized by two batching stages (docs/architecture.md:84-105).

Here the mesh replaces the peer group: every device keeps
* its authoritative table shard (ShardedEngine), and
* a **replica table** holding installed statuses of remote-owned GLOBAL keys,
* a host-side pending-hit accumulator per device (sum hits, OR RESET_REMAINING
  — exactly the reference aggregation, global.go:109-123).

`sync()` is ONE jitted collective step (the 3+N message dance collapses into
two all_gathers over ICI):
 1. all_gather every device's outbox of aggregated hits;
 2. each device filters entries it owns, segment-aggregates duplicates from
    different devices, applies them through the decision kernel with
    DRAIN_OVER_LIMIT forced (reference gubernator.go:526-532);
 3. all_gather the resulting authoritative statuses; every device installs
    entries it does NOT own into its replica table (install kernel =
    UpdatePeerGlobals semantics, reference gubernator.go:434-474).

GLOBAL requests are answered from the home device's replica table immediately
("process like we own it" with GLOBAL stripped and NO_BATCHING forced,
reference gubernator.go:401-429) — eventual consistency bounded by the sync
cadence, identical to the reference's contract.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding

from gubernator_tpu.ops.batch import (
    ERR_DROPPED,
    HostBatch,
    InstallBatch,
    ReqBatch,
    RequestColumns,
    ResponseColumns,
    pack_columns,
    pack_requests,
    pad_batch,
)
from gubernator_tpu.ops.kernel2 import decide2_impl, install2_impl
from gubernator_tpu.ops.plan import _subset
from gubernator_tpu.ops.table2 import Table2
from gubernator_tpu.parallel.mesh import shard_axes, shard_of, shard_spec
from gubernator_tpu.parallel.sharded import ShardedEngine, new_sharded_table
from gubernator_tpu.types import (
    Behavior,
    RateLimitRequest,
    RateLimitResponse,
    has_behavior,
)
from gubernator_tpu.ops.engine import ERR_NOT_PERSISTED, _pad_size, default_write_mode, ms_now


class PendingHits:
    """Columnar per-home accumulator of GLOBAL hits awaiting the sync tick.

    The merge is the reference's async-hit aggregation (global.go:109-123:
    sum Hits, OR RESET_REMAINING, newest request's config wins) as ONE numpy
    group-by per batch instead of a Python dict update per row — at 131K-row
    batches the per-row loop was µs-per-row host work against a ms-per-batch
    kernel. Entry order only affects which sync round an entry rides in
    (sync() drains fully every tick), never the reconciled result."""

    __slots__ = ("hb", "hits", "reset", "oldest_ts")

    def __init__(self):
        self.hb: Optional[HostBatch] = None  # unique-fp config carrier rows
        self.hits: Optional[np.ndarray] = None  # (n,) i64 accumulated hits
        self.reset: Optional[np.ndarray] = None  # (n,) i32 RESET bits OR-ed
        # monotonic ts of the oldest entry still in the accumulator: set
        # when the first entry lands in an empty queue, cleared only on a
        # FULL drain (a partial take keeps it — the remainder is no newer,
        # so staleness stays an upper bound). Feeds the
        # gubernator_global_sync_staleness_seconds gauge.
        self.oldest_ts: Optional[float] = None

    def age_s(self) -> float:
        """Seconds the oldest pending entry has waited (0 when empty)."""
        if self.oldest_ts is None or self.hb is None:
            return 0.0
        import time as _time

        return max(0.0, _time.monotonic() - self.oldest_ts)

    def __len__(self) -> int:
        # single read of self.hb: has_pending() is called from the event-loop
        # thread while the engine thread's take() may set hb=None — two reads
        # (check then use) would race
        hb = self.hb
        return 0 if hb is None else int(hb.fp.shape[0])

    def merge(
        self, hb: HostBatch, rows: np.ndarray, hits: np.ndarray,
        reset: np.ndarray,
    ) -> None:
        """Fold batch rows `rows` of `hb` in (hits pre-zeroed for owner-side
        rows that only mark a broadcast)."""
        if self.hb is None:
            import time as _time

            self.oldest_ts = _time.monotonic()
        new = _subset(hb, rows)
        if self.hb is not None:
            new = HostBatch(
                *[np.concatenate([a, b]) for a, b in zip(self.hb, new)]
            )
            hits = np.concatenate([self.hits, hits])
            reset = np.concatenate([self.reset, reset])
        uniq, inv = np.unique(new.fp, return_inverse=True)
        m = uniq.size
        h = np.zeros(m, dtype=np.int64)
        np.add.at(h, inv, hits)
        r = np.zeros(m, dtype=np.int32)
        np.bitwise_or.at(r, inv, reset.astype(np.int32))
        # newest config wins: highest concatenated position per key (existing
        # entries precede the new batch's rows, which are in request order)
        pos = np.full(m, -1, dtype=np.int64)
        np.maximum.at(pos, inv, np.arange(new.fp.shape[0]))
        self.hb = _subset(new, pos)
        self.hits, self.reset = h, r

    def take(self, k: int):
        """Pop up to k entries → (config rows, hits, reset) columns.

        The POPPED columns are copies: the outbox builder stamps
        hits/behavior/created_at into them in place, and a popped box that
        shared storage with the accumulator would write through into
        whatever still aliases the same base buffer. The REMAINDER stays a
        slice view — a sync tick drains a deep queue in Q/k rounds, and
        copying the remainder each round would make the drain O(Q²) in
        queue depth (copying the popped k is O(Q) total)."""
        n = len(self)
        k = min(k, n)
        out = (
            HostBatch(*[f[:k].copy() for f in self.hb]),
            self.hits[:k].copy(),
            self.reset[:k].copy(),
        )
        if k == n:
            self.hb = self.hits = self.reset = None
            self.oldest_ts = None
        else:
            self.hb = HostBatch(*[f[k:] for f in self.hb])
            self.hits = self.hits[k:]
            self.reset = self.reset[k:]
        return out

    def clear(self) -> None:
        """Drop every pending entry (test reset — modeling a steady state
        where the sync tick keeps the accumulator drained)."""
        self.hb = self.hits = self.reset = None
        self.oldest_ts = None


@dataclass
class _QueuedHits:
    """Queue-merge inputs computed at PREPARE time, applied at ISSUE time —
    the accumulator mutation must stay on the engine thread (single-writer),
    while prepare runs on the pipeline's prep pool."""

    hb: HostBatch  # the GLOBAL sub-batch (config carrier rows)
    rows: np.ndarray  # rows to queue (active, nonzero hits)
    hits: np.ndarray  # per-row hits (0 for owner-side broadcast markers)
    reset: np.ndarray  # RESET_REMAINING bits
    home: int  # the batch's rotating home device
    n_remote: int  # non-owner rows (hits_queued metric delta)


@dataclass
class GlobalPending:
    """In-flight pipelined GLOBAL check (the mesh-global engine's analog of
    ops/engine.PendingCheck): staged replica/owner/plain dispatches plus the
    deferred hit-queue merge."""

    hb: HostBatch
    err: np.ndarray
    now: int
    queue: _QueuedHits
    # [Pass, n_rows, batch, staged→(staged, out), table_attr, home_pin, rowmap]
    passes: list
    clamped: int


@dataclass
class GlobalStats:
    """Counters mirroring the reference's global-behavior metric family
    (global.go:53-79) — load-bearing for convergence tests (§4 SURVEY.md)."""

    hits_queued: int = 0
    sync_rounds: int = 0
    broadcasts_applied: int = 0  # entries applied+broadcast as owner
    updates_installed: int = 0  # entries installed into replica tables
    send_queue_length: int = 0


def _sync_core(primary, replica, outbox: ReqBatch, me, D: int, write: str,
               axes="shard"):
    """One collective sync round, per-device body (shared by the
    single-round and fused multi-round steps): exchange outboxes, owner
    applies aggregated hits, broadcast + replica install. Returns
    (primary', replica', counters(2,) i64, bc InstallBatch)."""
    # sentinel OUTSIDE the fingerprint domain (real fps are in [1, 2^63-1],
    # hashing.py): non-owned/inactive outbox rows sort into their own leading
    # segment and can never merge with a real key's aggregation
    DROP_FP = jnp.int64(-1)
    RESET = int(Behavior.RESET_REMAINING)
    DRAIN = int(Behavior.DRAIN_OVER_LIMIT)

    # ---- stage 1: exchange hit outboxes (runAsyncHits → sendHits analog)
    gath = jax.lax.all_gather(outbox, axes)  # leaves (D, OUT)
    flat = jax.tree.map(lambda x: x.reshape((-1,) + x.shape[2:]), gath)
    N = flat.fp.shape[0]
    owner = ((flat.fp >> 32) % D).astype(jnp.int32)
    mine = flat.active & (owner == me)

    # ---- stage 2: aggregate same-key hits from different devices
    key = jnp.where(mine, flat.fp, DROP_FP)
    order = jnp.argsort(key)
    sfp = key[order]
    first = jnp.concatenate(
        [jnp.ones((1,), dtype=bool), sfp[1:] != sfp[:-1]]
    )
    seg = jnp.cumsum(first) - 1
    hits = jax.ops.segment_sum(flat.hits[order], seg, num_segments=N)
    reset_bit = jax.ops.segment_max(
        (flat.behavior[order] & RESET), seg, num_segments=N
    )
    pos = jnp.arange(N)
    # config carrier = newest contributing entry of the segment
    carrier_pos = jax.ops.segment_max(
        jnp.where(mine[order], pos, -1), seg, num_segments=N
    )
    valid = carrier_pos >= 0
    carrier = order[jnp.clip(carrier_pos, 0, N - 1)]
    cfg = jax.tree.map(lambda x: x[carrier], flat)
    agg = cfg._replace(
        hits=hits,
        # owner applies accumulated global hits with DRAIN forced
        # (reference gubernator.go:526-532) and RESET OR-ed in
        behavior=cfg.behavior | DRAIN | reset_bit,
        active=valid,
    )
    primary, resp, stats = decide2_impl(primary, agg, write=write)

    # ---- stage 3: broadcast authoritative statuses (runBroadcasts analog)
    bc = InstallBatch(
        fp=jnp.where(valid, agg.fp, jnp.int64(0)),
        algo=agg.algo,
        status=resp.status,
        limit=resp.limit,
        remaining=resp.remaining,
        reset_time=resp.reset_time,
        duration=agg.duration,
        now=agg.created_at,
        active=valid,
        burst=agg.burst,  # real config burst — richer than the wire
        stamp=agg.created_at,  # path's Burst=Limit rebuild
        # sliding-window fidelity (PR 11): the owner's previous-window
        # count and stored-style remaining ride the broadcast so replicas
        # interpolate the SAME `used` as the owner instead of the
        # permissive aux=0 rebuild
        aux=resp.aux,
        rem_store=resp.rem_store,
    )
    bc_all = jax.lax.all_gather(bc, axes)
    bc_flat = jax.tree.map(lambda x: x.reshape((-1,) + x.shape[2:]), bc_all)
    bc_owner = ((bc_flat.fp >> 32) % D).astype(jnp.int32)
    theirs = bc_flat.active & (bc_owner != me)
    inst = bc_flat._replace(active=theirs)
    replica, installed = install2_impl(replica, inst, write=write)

    counters = jnp.stack(
        [
            valid.sum(dtype=jnp.int64),  # broadcasts applied as owner
            installed.sum(dtype=jnp.int64),  # replica installs
        ]
    )
    return primary, replica, counters, bc


def _mk_sync_step(
    mesh, n_shards: int, out_size: int, write: Optional[str] = None,
    wire: bool = False,
):
    """Build the jitted single-round collective sync step. `wire=True`
    takes the outbox as ONE compact (D, 5, OUT+1) int32 wire grid
    (ops/wire.py) decoded in-trace instead of a 12-leaf HostBatch pytree —
    one device put per round instead of twelve, at 20 B/entry instead of
    96 (PendingHits rounds were put-bound: BENCH_r05 measured 110 ms per
    16K-entry round against ~16 ms of compute)."""
    D = n_shards
    write = write or default_write_mode()
    axes = shard_axes(mesh)

    def per_device(primary, replica, outbox):
        primary = jax.tree.map(lambda x: x[0], primary)
        replica = jax.tree.map(lambda x: x[0], replica)
        if wire:
            from gubernator_tpu.ops.kernel2 import req_from_arr
            from gubernator_tpu.ops.wire import decode_wire_block

            arr12, _base = decode_wire_block(outbox[0])
            outbox = req_from_arr(arr12)
        else:
            outbox = jax.tree.map(lambda x: x[0], outbox)
        me = jax.lax.axis_index(axes)
        primary, replica, counters, bc = _sync_core(
            primary, replica, outbox, me, D, write, axes=axes
        )
        expand = lambda t: jax.tree.map(lambda x: x[None], t)
        # bc (this device's owner-applied rows) returns to the host so a
        # configured Store can write the reconciled state through — the
        # reference's OnChange fires on owner-side GLOBAL applies too
        return expand(primary), expand(replica), counters[None], expand(bc)

    spec = shard_spec(mesh)
    fn = jax.shard_map(
        per_device,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=(spec, spec, spec, spec),
        # check_vma=False: the Pallas sweep's out_shape carries no vma
        # annotation, which the checker (jax>=0.9) rejects inside shard_map
        check_vma=False,
    )
    return jax.jit(fn, donate_argnums=(0, 1))


def _mk_sync_step_multi(
    mesh, n_shards: int, rounds: int, write: Optional[str] = None,
    wire: bool = False,
):
    """Fused R-round sync step: a fori_loop over R stacked outboxes inside
    ONE launch. A deep drain (sync() after a burst) otherwise pays the
    put + launch + fetch host cost per round (its share of a round is
    not measured on a co-located host). Rounds with all-inactive outboxes
    are no-ops, so
    the host pads the round count to a fixed R and one compile serves
    every backlog ≤ R. Store-configured engines never use this step: the
    per-round bc must reach the Store write-through, so they stay on the
    single-round path."""
    D = n_shards
    write = write or default_write_mode()
    axes = shard_axes(mesh)

    def per_device(primary, replica, outboxes):
        primary = jax.tree.map(lambda x: x[0], primary)
        replica = jax.tree.map(lambda x: x[0], replica)
        # pytree: leaves (R, OUT); wire: ONE (R, 5, OUT+1) int32 grid
        outboxes = jax.tree.map(lambda x: x[0], outboxes)
        me = jax.lax.axis_index(axes)

        def body(i, carry):
            primary, replica, counters = carry
            outbox = jax.tree.map(
                lambda x: jax.lax.dynamic_index_in_dim(x, i, keepdims=False),
                outboxes,
            )
            if wire:
                from gubernator_tpu.ops.kernel2 import req_from_arr
                from gubernator_tpu.ops.wire import decode_wire_block

                arr12, _base = decode_wire_block(outbox)
                outbox = req_from_arr(arr12)
            primary, replica, c, _bc = _sync_core(
                primary, replica, outbox, me, D, write, axes=axes
            )
            return primary, replica, counters + c

        primary, replica, counters = jax.lax.fori_loop(
            0, rounds, body,
            (primary, replica, jnp.zeros((2,), dtype=jnp.int64)),
        )
        expand = lambda t: jax.tree.map(lambda x: x[None], t)
        return expand(primary), expand(replica), counters[None]

    spec = shard_spec(mesh)
    fn = jax.shard_map(
        per_device,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=(spec, spec, spec),
        check_vma=False,
    )
    return jax.jit(fn, donate_argnums=(0, 1))


class GlobalShardedEngine(ShardedEngine):
    """ShardedEngine + GLOBAL-behavior replicas and collective sync.

    `home_shard` models which node a client connected to (the reference's
    non-owner): GLOBAL requests are answered from that device's replica table
    and their hits accumulate until the next sync tick (GlobalSyncWait analog,
    default 100 ms, reference config.go:142-146).

    The daemon serving surface (`check_columns`) assigns each GLOBAL batch a
    rotating home device: successive front-door dispatches land on successive
    devices, modeling clients spread over the peer group — the replica plane
    absorbs the reads/hits and the collective sync reconciles them, which is
    the BASELINE #3 topology (8-peer cluster ↦ v5e-8 mesh over ICI)."""

    mesh_global = True  # daemon marker: this engine serves the GLOBAL
    # behavior through replica tables + collective sync

    def __init__(
        self,
        mesh,
        capacity_per_shard: int = 50_000,
        max_exact_passes: int = 8,
        sync_out: int = 256,
        created_at_tolerance_ms=None,
        store=None,
        route: Optional[str] = None,
        write_mode: Optional[str] = None,
        dedup: Optional[str] = None,
        wire: Optional[str] = None,
        layout: Optional[str] = None,
    ):
        super().__init__(
            mesh,
            capacity_per_shard=capacity_per_shard,
            max_exact_passes=max_exact_passes,
            created_at_tolerance_ms=created_at_tolerance_ms,
            store=store,
            route=route,
            write_mode=write_mode,
            dedup=dedup,
            wire=wire,
            layout=layout,
        )
        # the replica table + collective step materialize on first GLOBAL
        # use: clustered daemons route GLOBAL over the host peer plane and
        # must not pay a second table's HBM or the sync-step compile
        self._capacity_per_shard = capacity_per_shard
        self.replica: Optional[Table2] = None
        self._sync_step = None
        self._sync_step_wire = None  # compact-outbox single-round step
        self._sync_multi = {}  # fused-drain steps, keyed by (rounds R, wire)
        self.sync_out = sync_out
        self.pending: List[PendingHits] = [
            PendingHits() for _ in range(self.n_shards)
        ]
        self.global_stats = GlobalStats()
        self._rr = 0  # rotating home-device assignment for served batches
        # the home counter is the one piece of engine state the pipelined
        # PREPARE stage touches (prep threads run concurrently)
        self._rr_lock = threading.Lock()

    def _ensure_global_plane(self) -> None:
        # the collective reconcile runs the mixed decision graph over
        # whatever algorithms GLOBAL keys use — a packed single-algorithm
        # primary cannot serve it; replicas are always full for the same
        # reason (installs carry arbitrary algos)
        self.migrate_layout_full("GLOBAL collective sync needs mixed math")
        if self.replica is None:
            self.replica = new_sharded_table(self.mesh, self._capacity_per_shard)
        if self._sync_step is None:
            self._sync_step = _mk_sync_step(
                self.mesh, self.n_shards, self.sync_out, write=self.write_mode
            )

    def _next_home(self) -> int:
        with self._rr_lock:
            h = self._rr % self.n_shards
            self._rr += 1
            return h

    def has_pending(self) -> bool:
        return any(len(p) for p in self.pending)

    def oldest_pending_age_s(self) -> float:
        """Age of the oldest un-synced mesh-GLOBAL hit across every home
        device's outbox (the in-mesh half of the staleness gauge)."""
        return max((p.age_s() for p in self.pending), default=0.0)

    # ------------------------------------------------------------------ check
    def check(
        self,
        requests: Sequence[RateLimitRequest],
        now_ms: Optional[int] = None,
        home_shard: int = 0,
    ) -> List[RateLimitResponse]:
        now = now_ms if now_ms is not None else ms_now()
        glob = [
            i
            for i, r in enumerate(requests)
            if has_behavior(r.behavior, Behavior.GLOBAL)
        ]
        if not glob:
            return super().check(requests, now_ms=now)
        rest = [i for i in range(len(requests)) if i not in set(glob)]
        out: List[Optional[RateLimitResponse]] = [None] * len(requests)
        if rest:
            sub = super().check([requests[i] for i in rest], now_ms=now)
            for i, r in zip(rest, sub):
                out[i] = r
        gsub = self._check_global([requests[i] for i in glob], now, home_shard)
        for i, r in zip(glob, gsub):
            out[i] = r
        return out  # type: ignore[return-value]

    def _check_global(
        self, requests: Sequence[RateLimitRequest], now: int, home: int
    ) -> List[RateLimitResponse]:
        """GLOBAL dispatch (object API). Array core shared with the daemon's
        columns path (`check_columns`)."""
        hb, errors = pack_requests(requests, now, tolerance_ms=self.created_at_tolerance_ms)
        out: List[Optional[RateLimitResponse]] = [None] * len(requests)
        for i, err in enumerate(errors):
            if err is not None:
                out[i] = RateLimitResponse(error=err)
        status, limit, remaining, reset, dropped = self._global_hb(hb, home, now)
        for i in range(len(requests)):
            if out[i] is None:
                out[i] = RateLimitResponse(
                    status=int(status[i]),
                    limit=int(limit[i]),
                    remaining=int(remaining[i]),
                    reset_time=int(reset[i]),
                    error=ERR_NOT_PERSISTED if dropped[i] else "",
                )
        self.stats.checks += len(requests)
        return out  # type: ignore[return-value]

    # ------------------------------------------------ daemon serving surface
    def check_columns(
        self, cols: RequestColumns, now_ms: Optional[int] = None
    ) -> ResponseColumns:
        """Columns-in/columns-out with the GLOBAL behavior honored on-mesh:
        GLOBAL rows are answered from a rotating home device's replica table
        (non-owner semantics, reference gubernator.go:401-429) with their hits
        accumulated for the collective sync tick; everything else takes the
        ownership-routed authoritative path. Store write-through/rehydrate
        fires on the authoritative paths (non-GLOBAL, GLOBAL owner rows, and
        the collective sync's reconciled state) — never on replica answers,
        which are transient by contract and would write stale state over the
        owner's."""
        gmask = (np.asarray(cols.behavior) & np.int32(Behavior.GLOBAL)) != 0
        if not gmask.any():
            return super().check_columns(cols, now_ms=now_ms)
        now = now_ms if now_ms is not None else ms_now()
        n = cols.fp.shape[0]
        status = np.zeros(n, dtype=np.int32)
        limit = np.zeros(n, dtype=np.int64)
        remaining = np.zeros(n, dtype=np.int64)
        reset = np.zeros(n, dtype=np.int64)
        err = np.zeros(n, dtype=np.int8)
        rest = np.nonzero(~gmask)[0]
        if rest.size:
            rc = super().check_columns(
                RequestColumns(*[f[rest] for f in cols]), now_ms=now
            )
            status[rest] = rc.status
            limit[rest] = rc.limit
            remaining[rest] = rc.remaining
            reset[rest] = rc.reset_time
            err[rest] = rc.err
        g = np.nonzero(gmask)[0]
        hb, perr = pack_columns(
            RequestColumns(*[f[g] for f in cols]),
            now,
            tolerance_ms=self.created_at_tolerance_ms,
        )
        err[g] = perr
        g_created = cols.created_at[g]
        self.stats.created_at_clamped += int(
            ((g_created != 0) & (hb.created_at != g_created)).sum()
        )
        s, l, r, t, dropped = self._global_hb(hb, self._next_home(), now)
        status[g] = s
        limit[g] = l
        remaining[g] = r
        reset[g] = t
        err[g[dropped]] = ERR_DROPPED
        self.stats.checks += int(g.size)
        from gubernator_tpu.ops.engine import _fold_cascades_host

        # host fold over the REASSEMBLED batch order (the GLOBAL/local
        # split above preserves original row positions)
        _fold_cascades_host(
            np.asarray(cols.behavior), status, remaining, reset, err
        )
        return ResponseColumns(
            status=status, limit=limit, remaining=remaining,
            reset_time=reset, err=err,
        )

    # ------------------------------------------------- pipelined GLOBAL path
    # The generic prepare/issue/finish split (ops/engine.py) can't express
    # the GLOBAL fork (replica answers + owner applies + hit queueing), so
    # this engine provides its own pending type through the
    # `prepare_columns`/`issue_pending`/`finish_pending` hooks — GLOBAL
    # batches ride the SAME pipeline as everything else instead of
    # serializing the front door (the round-4 `can_pipeline` veto): the prep
    # thread stages replica+owner+plain dispatches, the engine thread merges
    # queued hits and launches all of them back-to-back, and the fetch
    # thread materializes the outputs while the engine thread stages the
    # next batch. Store-configured engines never reach these hooks
    # (EngineRunner serializes them for write-through ordering).

    def _global_fork(self, hb: HostBatch, home: int):
        """Shared construction of the GLOBAL fork — the ONE place the queue
        rules live (serial `_global_hb` and pipelined `prepare_columns` both
        call it): zero-hit requests are never queued (global.go:85-95),
        owner-side hits queue as hits=0 broadcast markers (QueueUpdate →
        runBroadcasts), non-owner hits accumulate for the owner; non-owner
        rows answer from the home replica with GLOBAL stripped and
        NO_BATCHING forced (reference gubernator.go:416-422), owner rows run
        the authoritative table."""
        owner = shard_of(hb.fp, self.n_shards)
        is_owner_here = (owner == home) & hb.active
        q = np.nonzero(hb.active & (hb.hits != 0))[0]
        queue = _QueuedHits(
            hb=hb,
            rows=q,
            hits=np.where(is_owner_here[q], 0, hb.hits[q]).astype(np.int64),
            reset=hb.behavior[q] & np.int32(Behavior.RESET_REMAINING),
            home=home,
            n_remote=int((~is_owner_here[q]).sum()),
        )
        hb_replica = hb._replace(
            behavior=(hb.behavior & ~np.int32(Behavior.GLOBAL))
            | np.int32(Behavior.NO_BATCHING),
            active=hb.active & ~is_owner_here,
        )
        hb_owner = hb._replace(active=is_owner_here)
        return is_owner_here, queue, hb_replica, hb_owner

    def _apply_queue(self, qu: "_QueuedHits") -> None:
        """Fold prepared queue-merge inputs into the sync accumulator
        (engine thread only — single-writer)."""
        if qu.rows.size:
            self.pending[qu.home].merge(qu.hb, qu.rows, qu.hits, qu.reset)
            self.global_stats.hits_queued += qu.n_remote
        self.global_stats.send_queue_length = sum(len(p) for p in self.pending)

    def prepare_columns(self, cols: RequestColumns, now_ms=None):
        """Prepare hook (any thread): returns a GlobalPending for batches
        carrying GLOBAL rows, or None to route pure-local batches through
        the generic pipelined path."""
        gmask = (np.asarray(cols.behavior) & np.int32(Behavior.GLOBAL)) != 0
        if not gmask.any():
            return None
        now = now_ms if now_ms is not None else ms_now()
        hb, err = pack_columns(
            cols, now, tolerance_ms=self.created_at_tolerance_ms
        )
        clamped = int(
            ((cols.created_at != 0) & (hb.created_at != cols.created_at)).sum()
        )
        home = self._next_home()
        passes = []

        def plan_into(batch, table_attr, home_pin, rowmap):
            if not batch.active.any():
                return
            for p in self.plan(batch):
                if len(p.rows) == 0:
                    continue
                shard = (
                    np.full(p.batch.fp.shape[0], home_pin, dtype=np.int64)
                    if home_pin is not None
                    else None
                )
                staged = self._stage(p.batch, shard)
                passes.append(
                    [p, len(p.rows), p.batch, staged, table_attr, home_pin,
                     rowmap]
                )

        rest = np.nonzero(~gmask)[0]
        if rest.size:
            plan_into(_subset(hb, rest), "table", None, rest)
        g = np.nonzero(gmask)[0]
        hbg = _subset(hb, g)
        _owner_here, queue, hb_replica, hb_owner = self._global_fork(hbg, home)
        plan_into(hb_replica, "replica", home, g)
        # owner rows all belong to shard `home`: pin them there (host
        # grid). Routed by the a2a exchange they would all target ONE
        # destination and overflow a pair capacity sized for hash-spread
        # rows — every overflow row costs up to two extra dispatches.
        plan_into(hb_owner, "table", home, g)
        return GlobalPending(
            hb=hb, err=err, now=now, queue=queue, passes=passes,
            clamped=clamped,
        )

    def issue_pending(self, pending: "GlobalPending") -> "GlobalPending":
        """Issue hook (engine thread): fold the queued hits into the sync
        accumulator, then launch every staged dispatch without fetching."""
        self._ensure_global_plane()
        # checkpoint marking for the pipelined GLOBAL fork: replica-pinned
        # rows are a harmless superset (dirty blocks only cost extract
        # bytes), and marking here — the engine-thread job that launches —
        # keeps the mark→mutate / take→extract FIFO contract
        self._mark_dirty(pending.hb.fp)
        self._apply_queue(pending.queue)
        for entry in pending.passes:
            staged, table_attr = entry[3], entry[4]
            table, out = self._decide(getattr(self, table_attr), staged)
            setattr(self, table_attr, table)
            entry[3] = (staged, out)
        return pending

    def finish_pending(self, pending: "GlobalPending", fixup):
        """Finish hook (fetch thread): materialize every pass's output and
        assemble the full response; claim-drop retries run on the engine
        thread via `fixup` against the same table (replica pins preserved)."""
        from gubernator_tpu.ops.engine import EngineStats, fetch_passes

        # ONE fetch for every pass's output (cf. finish_check_columns)
        fetch_passes(self, pending.passes)
        hb, err = pending.hb, pending.err
        n = hb.fp.shape[0]
        status = np.zeros(n, dtype=np.int32)
        limit_o = np.zeros(n, dtype=np.int64)
        remaining = np.zeros(n, dtype=np.int64)
        reset = np.zeros(n, dtype=np.int64)
        delta = EngineStats(created_at_clamped=pending.clamped, checks=n)
        for p, np_, batch, pend, table_attr, home_pin, rowmap in pending.passes:
            (s, l, r, t, dropped, hit), st, uncounted = self.finish_staged(
                pend, np_
            )
            delta.cache_hits += st[0]
            delta.cache_misses += st[1]
            delta.over_limit += st[2]
            delta.evicted_unexpired += st[3]
            delta.dispatches += 1
            if dropped.any():
                rows = np.nonzero(dropped)[0]

                def retry(rows=rows, batch=batch, uncounted=uncounted,
                          table_attr=table_attr, home_pin=home_pin):
                    sub = _subset(batch, rows)
                    shard = (
                        np.full(rows.size, home_pin, dtype=np.int64)
                        if home_pin is not None
                        else None
                    )
                    _, vals = self._dispatch(
                        sub, depth=1, shard=shard, table_attr=table_attr,
                        count=uncounted[rows] if uncounted is not None else None,
                    )
                    return vals

                s2, l2, r2, t2, d2, h2 = fixup(retry)
                s[rows], l[rows], r[rows], t[rows] = s2, l2, r2, t2
                dropped[rows] = d2
                hit[rows] = h2
            if p.members is not None:
                members = rowmap[p.members]
                src = np.repeat(np.arange(np_), p.member_counts)
                status[members] = s[src]
                limit_o[members] = l[src]
                remaining[members] = r[src]
                reset[members] = t[src]
                err[members[dropped[src]]] = ERR_DROPPED
            else:
                rows_f = rowmap[p.rows]
                status[rows_f] = s[:np_]
                limit_o[rows_f] = l[:np_]
                remaining[rows_f] = r[:np_]
                reset[rows_f] = t[:np_]
                err[rows_f[dropped[:np_]]] = ERR_DROPPED
        from gubernator_tpu.ops.engine import _fold_cascades_host

        # cascade verdicts fold host-side on the mesh-global path (the
        # replica/owner fork re-orders rows, so no in-trace fold ran)
        _fold_cascades_host(hb.behavior, status, remaining, reset, err)
        rc = ResponseColumns(
            status=status, limit=limit_o, remaining=remaining,
            reset_time=reset, err=err,
        )
        return rc, delta

    def _global_hb(self, hb: HostBatch, home: int, now: Optional[int] = None):
        """The GLOBAL core over a packed batch: requests whose owner shard IS
        the home device run the owner path against the authoritative table and
        queue a broadcast (reference getLocalRateLimit + QueueUpdate,
        gubernator.go:653-690); everything else is answered from the home
        replica and its hits are queued for the owner (getGlobalRateLimit,
        gubernator.go:401-429). Returns per-row response arrays."""
        self._ensure_global_plane()
        n = hb.fp.shape[0]
        is_owner_here, queue, hb2, hb3 = self._global_fork(hb, home)
        self._apply_queue(queue)

        status = np.zeros(n, dtype=np.int32)
        limit = np.zeros(n, dtype=np.int64)
        remaining = np.zeros(n, dtype=np.int64)
        reset = np.zeros(n, dtype=np.int64)
        dropped = np.zeros(n, dtype=bool)
        self._global_passes(hb2, status, limit, remaining, reset, dropped,
                            table_attr="replica", home=home)
        # owner rows run the authoritative path on the primary shard — with
        # the Store contract honored there (write-through + miss rehydrate,
        # like the reference's owner-side getLocalRateLimit)
        self._global_passes(hb3, status, limit, remaining, reset, dropped,
                            table_attr="table", home=home, now=now)
        if self.store is not None and now is not None:
            own = np.nonzero(is_owner_here & ~dropped)[0]
            if own.size:
                from gubernator_tpu.store import ChangeSet

                rev = own[::-1]
                _, pos = np.unique(hb.fp[rev], return_index=True)
                keep = rev[pos]
                self.store.on_change(
                    ChangeSet(
                        fps=hb.fp[keep],
                        created_at=now,
                        algo=hb.algo[keep],
                        status=status[keep].astype(np.int32),
                        limit=limit[keep],
                        remaining=remaining[keep],
                        reset_time=reset[keep],
                        duration=hb.duration[keep],
                        burst=hb.burst[keep],
                        stamp=hb.created_at[keep],
                    )
                )
        return status, limit, remaining, reset, dropped

    def _global_passes(
        self, hb: HostBatch, status, limit, remaining, reset, dropped,
        table_attr: str, home, now: Optional[int] = None,
    ) -> None:
        if not hb.active.any():
            return
        use_store = (
            table_attr == "table"
            and self.store is not None and now is not None
        )
        for pi, p in enumerate(self.plan(hb)):
            nrows = len(p.rows)
            batch = pad_batch(p.batch, _pad_size(nrows))
            shard = (
                np.full(batch.fp.shape[0], home, dtype=np.int64)
                if home is not None
                else None
            )
            _, (s, l, r, t, d, _h) = self._dispatch(
                batch, shard=shard, table_attr=table_attr
            )
            if pi == 0 and use_store:
                from gubernator_tpu.ops.engine import _rehydrate_misses

                def disp(b, nb):
                    _, vals = self._dispatch(
                        pad_batch(b, _pad_size(nb)), table_attr="table"
                    )
                    return vals

                s, l, r, t, d, _h = _rehydrate_misses(
                    self, p.batch, nrows, (s, l, r, t, d, _h), now, disp
                )
            if p.members is not None:
                members = p.members
                src = np.repeat(np.arange(nrows), p.member_counts)
                status[members] = s[src]
                limit[members] = l[src]
                remaining[members] = r[src]
                reset[members] = t[src]
                dropped[members] = d[src]
            else:
                rows = p.rows
                status[rows] = s[:nrows]
                limit[rows] = l[:nrows]
                remaining[rows] = r[:nrows]
                reset[rows] = t[:nrows]
                dropped[rows] = d[:nrows]

    # ------------------------------------------------------------------- sync
    def sync(self, now_ms: Optional[int] = None) -> None:
        """One sync tick: drain ALL pending hits, in as many collective
        rounds as the fixed outbox size requires. The reference flushes its
        queue on batch-limit OR timer and never leaves a backlog behind a tick
        (global.go:125-151); a fixed one-round outbox would silently backlog
        hot global keys beyond `sync_out`.

        Deep backlogs drain through the FUSED multi-round step (one launch
        runs R rounds on-device, `_mk_sync_step_multi`) where it can run
        (`_fuse_rounds`); otherwise round by round."""
        first = True
        while first or self.has_pending():
            first = False
            rounds = max(
                (len(p) + self.sync_out - 1) // self.sync_out
                for p in self.pending
            )
            if rounds <= 1 or not self._fuse_rounds:
                self._sync_round(now_ms)
            else:
                self._sync_rounds_fused(rounds, now_ms)

    _SYNC_FUSE_CAP = 64  # max rounds per fused launch (bounds put size)

    @property
    def _fuse_rounds(self) -> bool:
        """Whether deep backlogs may take the fused multi-round step. Not
        with a Store (its write-through needs each round's bc on the host),
        and not on a TPU: XLA (libtpu 0.0.34, TPU v5 lite, PR 21) refuses
        the step — inside the fori_loop body the claim's int64 cummax, a
        two-operand u32 reduce-window after the x64 rewrite, is allocated on
        the scoped-VMEM stack: "RESOURCE_EXHAUSTED: Ran out of memory in
        memory space vmem ... Scoped allocation with size 19.07M and limit
        16.00M exceeded scoped vmem limit". The single-round step compiles;
        what a round costs on a co-located host is not measured."""
        return self.store is None and jax.default_backend() != "tpu"

    def _build_box(self, d: int, now: int):
        """Pop ≤ sync_out entries of home `d` into one padded outbox.
        Returns (box, popped) — `popped` is the raw (cfg, hits, reset)
        columns removed from the accumulator (None when empty), kept so a
        failed collective launch can re-merge them instead of losing the
        hits (take() hands back copies, so the box's in-place stamping
        below never writes through into them)."""
        OUT = self.sync_out
        k = min(len(self.pending[d]), OUT)
        if k:
            popped = self.pending[d].take(OUT)
            cfg, hits, reset = popped
            # collective sync mutates owner shards (and replicas) for these
            # keys — mark before the launch (engine thread, sync job)
            self._mark_dirty(cfg.fp)
            box = pad_batch(cfg, OUT)
            box.hits[:k] = hits
            box.behavior[:k] |= reset
            box.created_at[:k] = now
            # re-anchor non-Gregorian expiries to the applied-at stamp the
            # rows were just given (created + duration — the linear rule the
            # compact wire decode reconstructs in-trace; Gregorian rows keep
            # their host-resolved calendar expiry and force the full-width
            # outbox). Under frozen-clock tests created == now already, so
            # this is identity there; live, it anchors a new item's expiry
            # at apply time instead of up to one sync cadence earlier.
            ng = box.greg_interval[:k] == 0
            box.expire_new[:k] = np.where(
                ng, now + box.duration[:k], box.expire_new[:k]
            )
        else:
            popped = None
            box = pad_batch(
                HostBatch(
                    *[np.zeros(0, dtype=f.dtype)
                      for f in pack_requests([], now)[0]]
                ),
                OUT,
            )
        return box, popped

    def _requeue_popped(self, popped, exc: BaseException) -> None:
        """A collective sync launch failed AFTER the accumulators were
        popped and the tables donated into the dead computation: re-merge
        every popped box (`popped`: (home, (cfg, hits, reset)) pairs) so the
        hits survive (the reference requeues failed owner sends rather than
        dropping; service/global_manager.py does the same on the peer
        plane), and poison the engine — the donated table/replica buffers
        may be invalid, so serving must surface unhealthy (daemon
        health_check) instead of answering from them."""
        for d, (cfg, hits, reset) in popped:
            self.pending[d].merge(
                cfg, np.arange(cfg.fp.shape[0]), hits, reset
            )
        self.global_stats.send_queue_length = sum(len(p) for p in self.pending)
        self.poisoned = f"GLOBAL collective sync launch failed: {exc}"

    def _wire_boxes(self, boxes, now: int) -> bool:
        """Can this round's outboxes ride the compact wire? All-or-nothing
        per launch: one grid dtype/shape per compiled step. Accumulated
        hot-key hits ≥ 2^18 or Gregorian configs fall the round back to the
        full-width pytree put (same semantics, 12 puts instead of one)."""
        if self.wire != "compact":
            return False
        from gubernator_tpu.ops.wire import wire_encodable

        return all(wire_encodable(b, now) for b in boxes)

    def _sync_rounds_fused(self, rounds_needed: int, now_ms: Optional[int]) -> None:
        """Drain up to R rounds in ONE launch: stack R outboxes per device,
        run the fused step. R pads to a power of two so one compile serves
        every backlog ≤ R (padded rounds carry all-inactive outboxes and
        apply nothing)."""
        self._ensure_global_plane()
        now = now_ms if now_ms is not None else ms_now()
        R = 2
        while R < rounds_needed and R < self._SYNC_FUSE_CAP:
            R *= 2
        # padded rounds all carry the same all-inactive outbox — build it
        # once (np.stack copies on assembly, so sharing the object is safe)
        empty_box = None
        popped = []  # (home, cfg/hits/reset) columns popped this drain

        def box(d: int) -> HostBatch:
            nonlocal empty_box
            if len(self.pending[d]) == 0:
                if empty_box is None:
                    empty_box, _ = self._build_box(d, now)
                return empty_box
            b, p = self._build_box(d, now)
            if p is not None:
                popped.append((d, p))
            return b

        boxes = [[box(d) for d in range(self.n_shards)] for _r in range(R)]
        wire = self._wire_boxes(
            [boxes[r][d] for r in range(R) for d in range(self.n_shards)], now
        )
        step = self._sync_multi.get((R, wire))
        if step is None:
            step = self._sync_multi[(R, wire)] = _mk_sync_step_multi(
                self.mesh, self.n_shards, R, write=self.write_mode, wire=wire
            )
        try:
            if wire:
                from gubernator_tpu.ops import wire as wire_mod

                OUT = self.sync_out
                grid = np.zeros(
                    (self.n_shards, R, wire_mod.WIRE_LANES, OUT + 1),
                    dtype=np.int32,
                )
                for r in range(R):
                    for d in range(self.n_shards):
                        b = boxes[r][d]
                        if b is empty_box:  # zeros already; base only
                            wire_mod.stamp_base(grid[d, r], now)
                        else:
                            wire_mod.pack_wire_full(b, now, out=grid[d, r])
                dev = jax.device_put(grid, self._batch_sharding)
            else:
                stacked = HostBatch(
                    *[
                        np.stack(
                            [
                                np.stack([boxes[r][d][k] for r in range(R)])
                                for d in range(self.n_shards)
                            ]
                        )
                        for k in range(len(boxes[0][0]))
                    ]
                )  # leaves (D, R, OUT)
                dev = jax.tree.map(
                    lambda x: jax.device_put(
                        jnp.asarray(x), self._batch_sharding
                    ),
                    stacked,
                )
            self.table, self.replica, counters = step(
                self.table, self.replica, dev
            )
        except Exception as exc:
            self._requeue_popped(popped, exc)
            raise
        c = np.asarray(counters)
        # count the rounds that carried work, not the pow2 padding — the
        # gubernator_mesh_sync_rounds series must read the same for
        # identical traffic whichever drain path ran
        self.global_stats.sync_rounds += min(rounds_needed, R)
        self.global_stats.broadcasts_applied += int(c[:, 0].sum())
        self.global_stats.updates_installed += int(c[:, 1].sum())
        self.global_stats.send_queue_length = sum(len(p) for p in self.pending)

    def warm_sync_steps(self, now_ms: Optional[int] = None) -> None:
        """Pre-trace the collective sync steps — the single-round step plus
        every fused R variant — with empty outboxes (all-inactive rounds
        apply nothing; only the compile caches change). Without this the
        first deep backlog compiles a fused variant ON the engine thread
        mid-tick, stalling all serving behind a cold XLA compile. Engine
        thread only (mutates the donated tables through no-op steps). The
        caller should reset global_stats afterwards — warm rounds are not
        traffic. Compact-wire engines warm BOTH outbox formats: a round
        whose accumulated hits overflow the narrow layout falls back to
        the pytree step, and that compile must not land mid-tick either."""
        self._ensure_global_plane()
        modes = ("compact", "full") if self.wire == "compact" else (self.wire,)
        saved = self.wire
        try:
            for mode in modes:
                self.wire = mode
                self._sync_round(now_ms)
                R = 2
                while self._fuse_rounds and R <= self._SYNC_FUSE_CAP:
                    self._sync_rounds_fused(R, now_ms)
                    R *= 2
        finally:
            self.wire = saved

    def _sync_round(self, now_ms: Optional[int] = None) -> None:
        """One collective hit-sync + broadcast round. The outbox ships as
        ONE compact int32 wire grid when every box is representable
        (ops/wire.py — one put instead of twelve at ~a fifth the bytes),
        falling back to the HostBatch pytree put otherwise."""
        self._ensure_global_plane()
        now = now_ms if now_ms is not None else ms_now()
        built = [self._build_box(d, now) for d in range(self.n_shards)]
        boxes = [b for b, _p in built]
        popped = [(d, p) for d, (_b, p) in enumerate(built) if p is not None]
        wire = self._wire_boxes(boxes, now)
        if wire and self._sync_step_wire is None:
            self._sync_step_wire = _mk_sync_step(
                self.mesh, self.n_shards, self.sync_out,
                write=self.write_mode, wire=True,
            )
        try:
            if wire:
                from gubernator_tpu.ops import wire as wire_mod

                grid = np.zeros(
                    (self.n_shards, wire_mod.WIRE_LANES, self.sync_out + 1),
                    dtype=np.int32,
                )
                for d, b in enumerate(boxes):
                    wire_mod.pack_wire_full(b, now, out=grid[d])
                dev_box = jax.device_put(grid, self._batch_sharding)
                self.table, self.replica, counters, bc = self._sync_step_wire(
                    self.table, self.replica, dev_box
                )
            else:
                stacked = HostBatch(
                    *[np.stack([b[k] for b in boxes]) for k in range(len(boxes[0]))]
                )
                dev_box = jax.tree.map(
                    lambda x: jax.device_put(jnp.asarray(x), self._batch_sharding),
                    stacked,
                )
                self.table, self.replica, counters, bc = self._sync_step(
                    self.table, self.replica, dev_box
                )
        except Exception as exc:
            # the popped hit boxes must survive a failed launch:
            # re-merge them and mark the engine unhealthy — the donated
            # tables went into the dead computation
            self._requeue_popped(popped, exc)
            raise
        c = np.asarray(counters)
        self.global_stats.sync_rounds += 1
        self.global_stats.broadcasts_applied += int(c[:, 0].sum())
        self.global_stats.updates_installed += int(c[:, 1].sum())
        self.global_stats.send_queue_length = sum(len(p) for p in self.pending)
        if self.store is not None:
            # owner-reconciled GLOBAL state writes through (reference fires
            # OnChange inside the owner's getLocalRateLimit on the GLOBAL
            # apply path too); bc is lazy — only materialized here
            from gubernator_tpu.store import ChangeSet

            flat = lambda x: np.asarray(x).reshape(-1)
            active = flat(bc.active)
            rows = np.nonzero(active)[0]
            if rows.size:
                self.store.on_change(
                    ChangeSet(
                        fps=flat(bc.fp)[rows],
                        created_at=now,
                        algo=flat(bc.algo)[rows],
                        status=flat(bc.status)[rows].astype(np.int32),
                        limit=flat(bc.limit)[rows],
                        remaining=flat(bc.remaining)[rows],
                        reset_time=flat(bc.reset_time)[rows],
                        duration=flat(bc.duration)[rows],
                        burst=flat(bc.burst)[rows],
                        stamp=flat(bc.stamp)[rows],
                    )
                )
