"""Sharded execution: the key table distributed over the TPU mesh.

The reference spreads keys across cluster nodes with a consistent-hash ring and
forwards requests to owners over gRPC (replicated_hash.go, peer_client.go).
Here the same ownership axis maps onto the device mesh: every device holds a
shard of the HBM table, the host routes each request's fingerprint to its
owning shard, and one shard_map dispatch executes the decision kernel on all
shards simultaneously — no forwarding hop, no N×N connection mesh; ICI does
what gRPC did.

Layout: the Table2 leaves gain a leading (D,) device axis sharded with
PartitionSpec("shard"); request batches travel as ONE (D, 12, b_local) packed
i64 ingress grid and come back as ONE (D, b_local+2, 4) packed output grid
(single put + single fetch per mesh dispatch, cf. batch.pack_host_batch /
kernel2.pack_outputs). Inside shard_map each device sees its (1, …) block and
runs the decision kernel on its local slice independently — embarrassingly
parallel, exactly like the reference's share-nothing workers (workers.go:19-37)
but across chips. Because dispatches route UNIQUE fingerprints (the pass
planner aggregates same-key duplicates first, ops/plan.py), the hash spread
over shards stays near-multinomial even under Zipf-skewed traffic — per-shard
padding is counts.max() over a balanced draw, not the hot key's count.

Two routing modes (ShardedEngine(route=...), GUBER_SHARD_ROUTE):
* "host": the host sorts rows into the ownership grid — simple and fast on
  a single-host mesh, and the exact-sequential-semantics fallback;
* "device" (TPU default): the host ships rows in ARRIVAL order and the mesh
  itself routes them with a capacity-bounded all_to_all exchange
  (parallel/a2a.py) — zero per-dispatch host routing work, the path that
  scales to multi-host slices where each host only feeds its local devices.

Two dedup modes (ShardedEngine(dedup=...), GUBER_SHARD_DEDUP) decide WHERE
the kernel's unique-fingerprint contract is discharged:
* "host": the pass planner's numpy group-by (ops/plan.plan_passes) — exact
  per-occurrence sequential semantics, O(n log n) single-process work on
  every dispatch's critical path;
* "device" (TPU default): duplicate keys aggregate IN-TRACE
  (kernel2.dedup_packed_cols — hits summed, RESET_REMAINING OR-ed, newest
  config wins, members answered from the carrier) and the host plans O(1)
  (ops/plan.single_pass). Same semantics as plan_passes(max_exact=1), i.e.
  the reference's GLOBAL hot-key aggregation applied from occurrence 0.

With both on the device and the compact wire (the TPU's resolution of every
`auto`), the engine is wire-capable (`supports_wire_ingress`): a chunk the
native parser already holds as compact lanes is staged from them by one
GIL-free native call with every copy of a key kept (`stage_wire`,
ops/engine.prepare_check_wire) — the same grid, byte for byte, that
`_stage_a2a` builds from the same rows as columns, so the same programs.

Ingress/egress staging is persistent: packed grids build in a ring of
reusable host buffers (_StagingPool), ship once, and are DONATED into the
mesh step; the packed output allocation aliases a recycled egress buffer
from an earlier dispatch (_take_egress). Steady-state serving therefore
allocates no fresh host or device staging memory, and the prepare/issue/
finish runner split double-buffers the ring: pack(N+1) fills one buffer
while N's transfer drains another.
"""

from __future__ import annotations

import os
import threading
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from gubernator_tpu.ops.batch import (
    ERROR_STRINGS,
    HostBatch,
    InstallBatch,
    RequestColumns,
    ResponseColumns,
    pack_host_batch,
)
from gubernator_tpu.ops.kernel2 import (
    FLAG_DROPPED,
    FLAG_HIT,
    FLAG_MEMBER,
    FLAG_STATUS,
    FLAG_UNPROCESSED,
    decide2_packed_cols_impl,
    decide2_packed_dedup_impl,
    install2_impl,
)
from gubernator_tpu.ops.engine import (
    EngineStats,
    _math_mode,
    _pad_size,
    batch_needs_full_layout,
    default_write_mode,
    effective_math,
    ms_now,
)
from gubernator_tpu.ops.plan import _subset, plan_passes, single_pass
from gubernator_tpu.ops.table2 import Table2, n_buckets_for
from gubernator_tpu.parallel.mesh import (
    devices_per_host,
    mesh_hosts,
    shard_axes,
    shard_of,
    shard_spec,
)
from gubernator_tpu import tracing
from gubernator_tpu.types import Behavior, RateLimitRequest, RateLimitResponse


def _staging_donate() -> tuple:
    """donate_argnums for the (table, ingress grid, egress buffer) mesh
    steps: everything on TPU — the ingress grid's HBM frees at launch, the
    egress buffer aliases the output allocation — but table-only on CPU,
    where device_put zero-copies aligned host numpy buffers and donating
    memory XLA doesn't own corrupts or crashes the process."""
    return (0, 1, 2) if jax.default_backend() == "tpu" else (0,)


def default_shard_route() -> str:
    """On-device routing (the a2a exchange) on real TPU meshes — zero host
    routing work per dispatch, ICI does what the host argsort did; the host
    ownership grid everywhere else (CPU test meshes keep the simple path
    and the seed tests' exact shapes)."""
    return "device" if jax.default_backend() == "tpu" else "host"


def default_shard_dedup() -> str:
    """In-trace duplicate aggregation on real TPU meshes — the host group-by
    (plan_passes' np.unique) leaves the dispatch critical path; host
    planning elsewhere, preserving exact sequential same-key semantics on
    the CPU test meshes. Overridable per engine (dedup=) or daemon-wide
    (GUBER_SHARD_DEDUP) — a TPU deployment that needs per-occurrence
    sequential responses for duplicate keys within one batch sets "host"."""
    return "device" if jax.default_backend() == "tpu" else "host"


def make_sharded_decide(
    mesh: Mesh, math: str = "mixed", write: Optional[str] = None,
    dedup: bool = False, wire: bool = False,
):
    """Build the jitted all-shards decision step over the SINGLE-TRANSFER
    packed layout: (Table2[D,·], (D, 12, b) i64 ingress grid, (D, b+2, 4)
    recycled egress buffer) → (Table2', (D, b+2, 4) i64 packed outputs).
    Each device unpacks its ingress block in-kernel (kernel2.req_from_arr)
    and packs responses+stats on-device (kernel2.pack_outputs) — one host→
    device put and ONE device→host fetch per mesh dispatch, however many
    shards (the per-column transfer layout cost 12 puts + 6 grid fetches
    per dispatch). All inputs are DONATED: the ingress grid's HBM frees at
    launch and the egress buffer (a previous dispatch's fetched output,
    ShardedEngine._take_egress) aliases this dispatch's output allocation.
    Write mode defaults to the backend's (block-sparse Pallas on TPU with
    per-shape sweep fallback, XLA scatter on CPU test meshes) and is
    overridable for parity tests; `math` picks the token-only or mixed
    decision graph (engine._math_mode); `dedup` aggregates duplicate keys
    in-trace (kernel2.decide2_packed_dedup_impl — duplicates share a
    fingerprint, so the host grid colocates them on the owning device);
    `wire` takes the compact 5-lane int32 ingress grid (trailing base
    column per device block) and returns int32 compact outputs — the
    decode/encode fuse into the kernel (ops/wire.py), so the narrow wire
    costs vector ops instead of 76 B/row of transport."""
    write = write or default_write_mode()

    def per_device(table: Table2, arr: jnp.ndarray, out_buf: jnp.ndarray):
        from gubernator_tpu.ops.wire import decode_wire_block, encode_wire_out

        table = jax.tree.map(lambda x: x[0], table)
        impl = decide2_packed_dedup_impl if dedup else decide2_packed_cols_impl
        if wire:
            arr12, base = decode_wire_block(arr[0])
            table, packed = impl(table, arr12, write=write, math=math)
            packed = encode_wire_out(packed, base)
        else:
            table, packed = impl(table, arr[0], write=write, math=math)
        expand = lambda t: jax.tree.map(lambda x: x[None], t)
        return expand(table), packed[None]

    spec = shard_spec(mesh)
    fn = jax.shard_map(
        per_device, mesh=mesh, in_specs=(spec, spec, spec),
        # check_vma=False: the Pallas sweep's out_shape carries no vma
        # annotation, which the checker (jax>=0.9) rejects inside shard_map
        out_specs=(spec, spec), check_vma=False
    )
    # keep_unused: out_buf exists only to donate its allocation into the
    # same-shape output (XLA aliases donated inputs to matching outputs);
    # jit would otherwise prune the unused arg and the aliasing with it.
    # Staging donation is TPU-only: XLA:CPU zero-copies host numpy buffers
    # into device arrays, and donating memory the process still owns
    # segfaults / corrupts advanced tables (CPU meshes donate the table
    # alone, the seed behavior).
    return jax.jit(fn, donate_argnums=_staging_donate(), keep_unused=True)


def make_sharded_install(mesh: Mesh, write: Optional[str] = None):
    """All-shards install step for owner-authoritative GLOBAL statuses —
    the UpdatePeerGlobals receive path on a sharded daemon."""
    write = write or default_write_mode()

    def per_device(table: Table2, inst: InstallBatch):
        table = jax.tree.map(lambda x: x[0], table)
        inst = jax.tree.map(lambda x: x[0], inst)
        table, installed = install2_impl(table, inst, write=write)
        expand = lambda t: jax.tree.map(lambda x: x[None], t)
        return expand(table), expand(installed)

    spec = shard_spec(mesh)
    fn = jax.shard_map(
        per_device, mesh=mesh, in_specs=(spec, spec),
        # check_vma=False: the Pallas sweep's out_shape carries no vma
        # annotation, which the checker (jax>=0.9) rejects inside shard_map
        out_specs=(spec, spec), check_vma=False
    )
    return jax.jit(fn, donate_argnums=(0,))


def make_sharded_merge(mesh: Mesh, write: Optional[str] = None,
                       evictees: bool = False):
    """All-shards conservative-merge step (kernel2.merge2_impl) — the
    TransferState receive path on a sharded daemon: transferred slot rows
    are routed to their owning shard and merged with remaining=min /
    expiry=max / newest-config-wins semantics per device. `evictees=True`
    (the tiering promote path) additionally yields each shard's displaced
    live rows as canonical (b, 16) grids."""
    write = write or default_write_mode()

    def per_device(table: Table2, fp, slots, now, active):
        from gubernator_tpu.ops.kernel2 import merge2_impl

        table = jax.tree.map(lambda x: x[0], table)
        expand = lambda t: jax.tree.map(lambda x: x[None], t)
        if evictees:
            table, merged, ev = merge2_impl(
                table, fp[0], slots[0], now[0], active[0], write=write,
                evictees=True,
            )
            return expand(table), expand(merged), expand(ev)
        table, merged = merge2_impl(
            table, fp[0], slots[0], now[0], active[0], write=write
        )
        return expand(table), expand(merged)

    spec = shard_spec(mesh)
    n_out = 3 if evictees else 2
    fn = jax.shard_map(
        per_device, mesh=mesh, in_specs=(spec, spec, spec, spec, spec),
        out_specs=(spec,) * n_out, check_vma=False
    )
    return jax.jit(fn, donate_argnums=(0,))


def make_sharded_extract_dirty(mesh: Mesh, blk: int, layout=None):
    """All-shards dirty-block extract step (incremental checkpointing,
    ops/checkpoint.py): each device gathers ITS dirty blocks' bucket rows,
    filters live slots and packs them to the front — no slot row ever
    crosses a device boundary; the host fetches only per-shard live
    prefixes (ShardedEngine.checkpoint_finish). `bidx` is a (D, G) grid of
    per-shard LOCAL block ids padded with the out-of-range sentinel
    nblk_local (jnp.take mode="fill" zero-fills, and fp == 0 rows are
    never live)."""

    def per_device(rows, bidx, now):
        from gubernator_tpu.ops.checkpoint import _extract_blocks_core

        slots, fp, cnt = _extract_blocks_core(
            rows[0], bidx[0], now[0], blk, layout
        )
        return slots[None], fp[None], cnt[None]

    spec = shard_spec(mesh)
    fn = jax.shard_map(
        per_device, mesh=mesh, in_specs=(spec, spec, spec),
        out_specs=(spec, spec, spec), check_vma=False
    )
    return jax.jit(fn)


def make_sharded_extract_idle(mesh: Mesh, layout=None):
    """All-shards idle-row extract step (hot-set tiering,
    gubernator_tpu/tier/): each device filters ITS shard's live slots
    whose last-activity reference is idle past the horizon and packs them
    to the front (table2._extract_idle_core) — no slot row crosses a
    device boundary; the host fetches only per-shard idle prefixes
    (ShardedEngine.extract_idle)."""

    def per_device(rows, now, idle):
        from gubernator_tpu.ops.table2 import _extract_idle_core

        slots, fp, cnt = _extract_idle_core(
            rows[0], now[0], idle[0], layout
        )
        return slots[None], fp[None], cnt[None]

    spec = shard_spec(mesh)
    fn = jax.shard_map(
        per_device, mesh=mesh, in_specs=(spec, spec, spec),
        out_specs=(spec, spec, spec), check_vma=False
    )
    return jax.jit(fn)


def make_sharded_gather(mesh: Mesh, layout=None):
    """All-shards stored-state read (table2.gather_slots_impl): full-width
    slots for routed fingerprints, no mutation (nothing donated)."""

    def per_device(rows, fp, active):
        from gubernator_tpu.ops.table2 import gather_slots_impl

        slots, found = gather_slots_impl(rows[0], fp[0], active[0], layout)
        return slots[None], found[None]

    spec = shard_spec(mesh)
    fn = jax.shard_map(
        per_device, mesh=mesh, in_specs=(spec, spec, spec),
        out_specs=(spec, spec), check_vma=False
    )
    return jax.jit(fn)


def make_sharded_tombstone(mesh: Mesh):
    """All-shards tombstone step (table2.tombstone_rows_impl): zero the
    slots holding acked handed-off fingerprints, routed per owning shard."""

    def per_device(table: Table2, fp, active):
        from gubernator_tpu.ops.table2 import tombstone_rows_impl

        rows = table.rows[0]
        rows, found = tombstone_rows_impl(rows, fp[0], active[0])
        return Table2(rows=rows[None], layout=table.layout), found[None]

    spec = shard_spec(mesh)
    fn = jax.shard_map(
        per_device, mesh=mesh, in_specs=(spec, spec, spec),
        out_specs=(spec, spec), check_vma=False
    )
    return jax.jit(fn, donate_argnums=(0,))


class _StagingPool:
    """Ring of persistent host-side staging buffers, keyed by shape.

    Per-dispatch ingress staging used to allocate (and zero) a fresh
    (D, 12, b) grid — 12+ MB of alloc + fault-in on every 131K-row mesh
    dispatch. The pool hands out the same `depth` buffers round-robin per
    shape instead: pages stay warm, the allocator never churns, and callers
    only rewrite the bytes the batch actually covers. `depth` must cover
    the pipeline's in-flight bound (a buffer is only rewritten after the
    dispatch that device_put it has been issued `depth` dispatches ago —
    the same staging-lifetime assumption the runner's double-buffered
    prepare/issue/finish split already makes)."""

    def __init__(self, depth: int = 6):
        self.depth = depth
        self._rings: Dict[tuple, list] = {}
        self._lock = threading.Lock()  # stage_pass runs on concurrent prep threads

    def get(self, shape: tuple, zero: bool = False, dtype=np.int64) -> np.ndarray:
        key = (shape, np.dtype(dtype).str)
        with self._lock:
            ring = self._rings.get(key)
            if ring is None:
                ring = self._rings[key] = [[], 0]
            bufs, idx = ring
            if len(bufs) < self.depth:
                buf = np.zeros(shape, dtype=dtype)  # fresh → already zero
                bufs.append(buf)
                return buf
            ring[1] = idx + 1
            buf = bufs[idx % self.depth]
        if zero:
            buf.fill(0)
        return buf


def new_sharded_table(mesh: Mesh, capacity_per_shard: int, layout=None) -> Table2:
    """A (D, n_buckets, ROW_layout) packed-row table placed shard-per-device
    (the slot layout travels as Table2 pytree aux through every tree.map)."""
    if layout is None:
        from gubernator_tpu.ops.layout import FULL as layout
    # allocated sharded from the start: each device zero-fills its own
    # shard (a stacked copy built on device 0 first would hold D shards
    # there — 4 GiB on one chip for a 4 × 1 GiB table)
    rows = jnp.zeros(
        (int(mesh.devices.size), n_buckets_for(capacity_per_shard), layout.row),
        dtype=jnp.int32, device=NamedSharding(mesh, shard_spec(mesh)),
    )
    return Table2(rows=rows, layout=layout)


class ShardedEngine:
    """Multi-device analog of LocalEngine: one table shard per mesh device.

    Host-side routing (fingerprint → shard) replaces the reference's
    GetPeer/asyncRequest forwarding (gubernator.go:243-263); since every shard
    participates in every dispatch, "forwarding" costs nothing extra.
    """

    def __init__(
        self,
        mesh: Mesh,
        capacity_per_shard: int = 50_000,
        max_exact_passes: int = 8,
        created_at_tolerance_ms=None,
        store=None,
        route: Optional[str] = None,
        write_mode: Optional[str] = None,
        dedup: Optional[str] = None,
        wire: Optional[str] = None,
        layout: Optional[str] = None,
    ):
        from gubernator_tpu.ops.layout import resolve_layout
        from gubernator_tpu.ops.wire import default_wire_mode

        route = route or default_shard_route()
        if route not in ("host", "device"):
            raise ValueError(f"route must be 'host' or 'device', got {route!r}")
        dedup = dedup or default_shard_dedup()
        if dedup not in ("host", "device"):
            raise ValueError(f"dedup must be 'host' or 'device', got {dedup!r}")
        if wire is not None and wire not in ("compact", "full"):
            raise ValueError(f"wire must be 'compact' or 'full', got {wire!r}")
        self.mesh = mesh
        # per-engine clock-skew bound; None = the ops.batch process default
        self.created_at_tolerance_ms = created_at_tolerance_ms
        self.n_shards = int(mesh.devices.size)
        # pod topology: host rows × devices per host (1 × D on single-host
        # meshes) — introspection for the debug plane and the bench; the
        # shard id ↔ (host, device) mapping itself is mesh.py's host-major
        # linearization, so no routing code below reads these
        self.n_hosts = mesh_hosts(mesh)
        self.devices_per_host = devices_per_host(mesh)
        self._live_count_fn = None  # (layout, compiled count), live_count()
        # slot layout (ops/layout.py): full by default, packed 32 B rows
        # for single-algorithm fleets (GUBER_SLOT_LAYOUT / layout=); off-
        # family traffic migrates the shards to full in place
        self._layout = resolve_layout(layout)
        self.table = new_sharded_table(
            mesh, capacity_per_shard, layout=self._layout
        )
        # routing mode: "host" sorts rows into an ownership grid on the host;
        # "device" ships arrival-order rows and routes on-mesh with an
        # all_to_all exchange (parallel/a2a.py) — zero host routing work,
        # the multi-host-scale path (default on TPU backends)
        self.route = route
        # dedup mode: where the kernel's unique-fingerprint contract is
        # discharged — "host" = plan_passes group-by (exact sequential
        # same-key semantics), "device" = in-trace aggregation + O(1) host
        # planning (module docstring; default on TPU backends)
        self.dedup = dedup
        # one write mode for every mesh step (decide, install, GLOBAL sync);
        # None = the backend default (kernel2.resolve_write still falls the
        # sparse mode back to the full sweep per dispatch shape)
        self.write_mode = write_mode or default_write_mode()
        # host↔device wire format for decide dispatches and the GLOBAL sync
        # outbox: "compact" ships 5-lane int32 ingress grids + int32 egress
        # (ops/wire.py — the TPU default, GUBER_WIRE_COMPACT), "full" the
        # 12-lane int64 grids (the parity oracle). Per-dispatch
        # encodability still falls compact batches back to full-width.
        self.wire = wire or default_wire_mode()
        # (kind, …, math) → jitted mesh step (lazy); an a2a step with its
        # exchange_traffic
        self._decide_fns = {}
        self._install = make_sharded_install(mesh, write=self.write_mode)
        # handoff mesh steps, built lazily (most engines never rebalance)
        self._merge_fn = None
        self._tombstone_fn = None
        # incremental-checkpoint plane (ops/checkpoint.py): epoch tracker
        # attached by the daemon's CheckpointManager (None = zero marking
        # cost), per-shard extract step built lazily on first checkpoint
        self.ckpt = None
        self._extract_dirty_fn = None
        # hot-set tiering (gubernator_tpu/tier/): host-RAM shadow attached
        # by the daemon's TierManager. Mesh engines participate through
        # the idle sweep (extract_idle below) and the fault-back merge;
        # the per-request evictee sidecar is a single-device surface today
        # (the routed per-shard programs don't thread the flag — demote-
        # on-evict on meshes is a documented follow-up, docs/tiering.md)
        self.shadow = None
        self._extract_idle_fn = None
        self._batch_sharding = NamedSharding(mesh, shard_spec(mesh))
        self.max_exact_passes = max_exact_passes
        self.store = store  # write-through hook (gubernator_tpu.store.Store)
        self.stats = EngineStats()
        # persistent ingress staging (module docstring). CPU backends MUST
        # NOT pool: XLA:CPU zero-copies an aligned numpy buffer into the
        # device array, so with donation the advanced TABLE can end up
        # aliased into pool memory a later dispatch rewrites (observed as
        # corrupted remaining counts on the 8-device test mesh). TPU
        # host→HBM transfers always copy, which is what makes buffer reuse
        # sound there — exactly where the alloc+zero cost matters.
        self._pool: Optional[_StagingPool] = (
            _StagingPool() if jax.default_backend() != "cpu" else None
        )
        # recycled egress buffers per output shape: finish hands fetched
        # output arrays back, _take_egress donates them into the next
        # same-shape dispatch where XLA aliases the output allocation
        self._egress: Dict[tuple, list] = {}
        self._egress_lock = threading.Lock()
        # what the mesh steps were traced with, growing per pass (engine
        # thread, _decide; /v1/debug/pipeline "engine"): `mesh_lanes` the
        # lanes the decide kernel ran over all shards (D x b_local on the
        # host grid, D x D*C after the exchange), to set beside
        # stats.checks; `exchange_rows`/`exchange_bytes` the row slots and
        # bytes ONE chip sends plus receives over ICI in both legs of the
        # a2a exchange (parallel/a2a.exchange_traffic; 0 for a host-routed
        # pass). From shapes, not measured.
        self.mesh_lanes = 0
        self.exchange_rows = 0
        self.exchange_bytes = 0
        self._stage_lock = threading.Lock()
        # bytes actually crossing the host↔device boundary on the decide
        # path (the gubernator_tpu_wire_bytes_total series): ingress grid
        # nbytes at stage time, fetched output nbytes at finish time —
        # counted whichever wire format ran, so bytes/decision is
        # scrapeable rather than bench-computed
        self.wire_bytes = {"put": 0, "fetch": 0}
        self._wire_taken = dict(self.wire_bytes)
        # rows the a2a exchange capacity-dropped before they reached the
        # kernel (FLAG_UNPROCESSED on a device-routed dispatch) — the
        # per-engine source of gubernator_tpu_a2a_overflow_total.
        # Counted at every depth: a row that overflows twice was twice a
        # symptom of undersized pair capacity (GUBER_A2A_CAPACITY_SIGMA)
        self.a2a_overflow = 0
        self._a2a_overflow_taken = 0
        # per-shard ingress transfers issued concurrently (TPU: each
        # shard's device_put is its own host→device transfer; overlapping
        # them makes the put cost max-of-shards, not sum-of-shards — not
        # measured on a co-located host). CPU keeps the single zero-copy
        # put.
        self._put_pool: Optional[ThreadPoolExecutor] = None
        put_env = os.environ.get("GUBER_SHARD_PUT", "auto")
        if put_env not in ("auto", "single", "concurrent"):
            raise ValueError(
                f"GUBER_SHARD_PUT must be auto, single or concurrent, "
                f"got {put_env!r}"
            )
        self._put_concurrent = (
            put_env == "concurrent"
            or (put_env == "auto" and jax.default_backend() == "tpu")
        ) and self.n_shards > 1
        # set (with a reason) when a donated collective launch failed after
        # state was popped/donated: the tables may be poisoned, serving must
        # surface unhealthy (daemon health_check reads this)
        self.poisoned: Optional[str] = None

    def check(
        self,
        requests: Sequence[RateLimitRequest],
        now_ms: Optional[int] = None,
    ) -> List[RateLimitResponse]:
        """Object-API wrapper over the columns fast path (same shape as
        LocalEngine.check) so the Store write-through/rehydrate contract
        holds on BOTH serving surfaces."""
        if not requests:
            return []
        from gubernator_tpu.ops.batch import columns_from_requests

        cols = columns_from_requests(requests)
        rc = self.check_columns(cols, now_ms=now_ms)
        return [
            RateLimitResponse(
                status=int(rc.status[i]),
                limit=int(rc.limit[i]),
                remaining=int(rc.remaining[i]),
                reset_time=int(rc.reset_time[i]),
                error=ERROR_STRINGS[int(rc.err[i])],
            )
            for i in range(len(requests))
        ]

    # ----------------------------------------------- daemon serving surface
    # The same columns-in/columns-out API as LocalEngine, so the daemon's
    # Batcher/EngineRunner serve a whole mesh through one engine object
    # (GUBER_ENGINE=sharded).

    def check_columns(
        self, cols: RequestColumns, now_ms: Optional[int] = None
    ) -> ResponseColumns:
        from gubernator_tpu.ops.engine import serve_columns

        def dispatch(pass_batch, n_rows: int, cascade: bool = False):
            # mesh programs never fold cascades in-trace (routed/exchanged
            # row order breaks carrier adjacency); serve_columns' host fold
            # computes the combined verdicts instead
            _, vals = self._dispatch(pass_batch)
            return vals

        return serve_columns(self, cols, now_ms, dispatch)

    @property
    def folds_copies(self) -> bool:
        """The decide program folds the copies of a key itself
        (kernel2.decide2_packed_dedup_impl): a batch is one pass whatever it
        repeats, as columns (`plan`) and as the parser's lanes
        (ops/engine._assemble_wire_parts) alike."""
        return self.dedup == "device"

    def plan(self, hb: HostBatch):
        """Pass plan for one packed batch (serve_columns/prepare hook):
        O(1) when duplicates aggregate in-trace, the host group-by planner
        otherwise (exact sequential same-key semantics — fallback/oracle)."""
        if self.folds_copies:
            return single_pass(hb)
        return plan_passes(hb, max_exact=self.max_exact_passes)

    def _mark_dirty(self, fps) -> None:
        """Checkpoint hook: record touched fingerprints' (shard, block)
        pairs in the epoch tracker — engine thread, same job as the
        mutation (ops/checkpoint.py ordering contract)."""
        if self.ckpt is not None:
            self.ckpt.mark(np.asarray(fps))

    # ------------------------------------------------ boundary accounting
    # (the host stages themselves are tracing.stage parts of the runner's
    # put/fetch: shard_route, shard_pack | wire_pack, shard_put in _stage*,
    # shard_unroute with wire_decode inside it in _unroute; on the fused
    # path wire_pack is the native staging call, ops/engine.py, and
    # shard_put follows it in stage_wire)

    def _wire_count(self, direction: str, nbytes: int) -> None:
        with self._stage_lock:
            self.wire_bytes[direction] += int(nbytes)

    def take_wire_deltas(self) -> Dict[str, int]:
        """Bytes over the host↔device boundary per direction since the
        last take (EngineRunner feeds the wire_bytes_total counter)."""
        with self._stage_lock:
            d = {
                k: self.wire_bytes[k] - self._wire_taken[k]
                for k in self.wire_bytes
            }
            self._wire_taken = dict(self.wire_bytes)
        return d

    def take_a2a_overflow_delta(self) -> int:
        """Overflow rows since the last take — EngineRunner feeds
        gubernator_tpu_a2a_overflow_total so capacity pressure is
        scrapeable instead of test-only."""
        with self._stage_lock:
            d = self.a2a_overflow - self._a2a_overflow_taken
            self._a2a_overflow_taken = self.a2a_overflow
        return d

    # ------------------------------------------------ egress buffer recycling

    def _take_egress(self, shape: tuple, dtype=np.int64):
        """A donated egress buffer for one mesh dispatch: a previously
        fetched output array of the same shape/dtype when one is banked
        (its allocation will alias the new output), else a fresh zeroed
        grid (first dispatches of a shape, before the ring primes). Keyed
        by dtype too: compact-wire dispatches fetch int32 grids and full-
        width ones int64, and XLA only aliases exact matches."""
        key = (shape, np.dtype(dtype).str)
        with self._egress_lock:
            bank = self._egress.get(key)
            if bank:
                return bank.pop()
        return jax.device_put(
            np.zeros(shape, dtype=dtype), self._batch_sharding
        )

    def _recycle_egress(self, out) -> None:
        """Bank a fetched output array for reuse as a donated egress buffer
        (the serial dispatch after its fetch; the pipelined finish after
        its one fetch of every pass, engine.fetch_passes)."""
        with self._egress_lock:
            bank = self._egress.setdefault((out.shape, out.dtype.str), [])
            if len(bank) < 8:
                bank.append(out)

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
        """Install owner-authoritative GLOBAL statuses, routed to each
        fingerprint's owning shard (UpdatePeerGlobals receive path).
        `burst`/`stamp` default to the wire path's lossy rebuild;
        `aux`/`rem_store` carry sliding-window broadcast fidelity (cf.
        LocalEngine.install_columns)."""
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
        D = self.n_shards
        routed = shard_of(fp, D)
        order, rs, offset, b_local = _route_plan(routed, D)

        def grid(field, dtype):
            return jnp.asarray(
                _to_grid(field[order].astype(dtype), rs, offset, D, b_local)
            )

        inst = InstallBatch(
            fp=grid(fp, np.int64),
            algo=grid(algo, np.int32),
            status=grid(status, np.int32),
            limit=grid(limit, np.int64),
            remaining=grid(remaining, np.int64),
            reset_time=grid(reset_time, np.int64),
            duration=grid(duration, np.int64),
            now=grid(np.full(n, now, dtype=np.int64), np.int64),
            active=grid(np.ones(n, dtype=bool), bool),
            burst=grid(burst, np.int64),
            stamp=grid(stamp, np.int64),
            aux=None if aux is None else grid(aux, np.int64),
            rem_store=(
                None if rem_store is None else grid(rem_store, np.int64)
            ),
        )
        inst = jax.tree.map(
            lambda x: jax.device_put(x, self._batch_sharding), inst
        )
        self.table, installed = self._install(self.table, inst)
        self.stats.dispatches += 1
        return int(np.asarray(installed).sum())

    # ------------------------------------------------- maintenance surface

    def snapshot(self) -> np.ndarray:
        """(D, NB, 128) device→host copy of every shard (Loader.Save analog)."""
        return np.asarray(self.table.rows)

    def restore(self, rows: np.ndarray, layout=None) -> None:
        lay = self.table.layout
        if layout is not None and layout is not lay:
            if rows.shape[:-1] != tuple(self.table.rows.shape[:-1]):
                raise ValueError(
                    f"snapshot geometry {rows.shape} incompatible with "
                    f"table {tuple(self.table.rows.shape)}"
                )
            rows = np.asarray(lay.pack_rows(layout.unpack_rows(rows)))
        if rows.shape != tuple(self.table.rows.shape):
            raise ValueError(
                f"snapshot shape {rows.shape} != table {tuple(self.table.rows.shape)}"
            )
        sharding = NamedSharding(self.mesh, shard_spec(self.mesh))
        self.table = Table2(
            rows=jax.device_put(jnp.asarray(rows, dtype=jnp.int32), sharding),
            layout=lay,
        )
        if self.ckpt is not None:
            # mid-life restore: state of unknown provenance — next delta
            # epoch captures the whole live set (cf. LocalEngine.restore)
            self.ckpt.mark_all()

    def live_count(self, now_ms: Optional[int] = None) -> int:
        """Every shard counts its own rows where they live, the counts meet
        in one psum, and one integer comes back (cf. table2.live_count2)."""
        from gubernator_tpu.ops.table2 import live_count_rows

        lay = self.table.layout
        if self._live_count_fn is None or self._live_count_fn[0] is not lay:
            axes = shard_axes(self.mesh)
            self._live_count_fn = lay, jax.jit(jax.shard_map(
                lambda rows, now: jax.lax.psum(
                    live_count_rows(rows[0], now, lay), axes
                ),
                mesh=self.mesh, in_specs=(shard_spec(self.mesh), P()),
                out_specs=P(), check_vma=False,
            ))
        now = now_ms if now_ms is not None else ms_now()
        return int(self._live_count_fn[1](self.table.rows, jnp.int64(now)))

    # ----------------------------------------------------------- handoff
    # Same surface as LocalEngine (extract_live / merge_rows /
    # tombstone_fps): the mesh pays for the full-table partition pass, the
    # host stages only the transferred rows — batch-proportional, like the
    # install path.

    def extract_live(self, now_ms: Optional[int] = None):
        from gubernator_tpu.ops.table2 import extract_live_rows

        now = now_ms if now_ms is not None else ms_now()
        return extract_live_rows(
            self.table.rows, now, layout=self.table.layout
        )

    def _slots_to_full(self, slots: np.ndarray, layout=None) -> np.ndarray:
        """Normalize incoming slot rows to the canonical full layout (cf.
        LocalEngine._slots_to_full — same inference rules)."""
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
        n = fps.shape[0]
        if n == 0:
            if collect:
                return 0, np.zeros(0, dtype=bool), np.empty(
                    0, dtype=np.int64
                ), np.empty((0, 16), dtype=np.int32)
            return 0
        from gubernator_tpu.ops.plan import occurrence_rank
        from gubernator_tpu.ops.table2 import FLAGS

        slots = self._slots_to_full(slots, layout)
        _order, rank = occurrence_rank(fps)
        if rank is not None:  # unique-fp contract (cf. LocalEngine.merge_rows)
            if collect:
                raise ValueError(
                    "merge_rows(collect=True) requires unique fingerprints"
                )
            return sum(
                self.merge_rows(fps[rank == r], slots[rank == r], now_ms)
                for r in range(int(rank.max()) + 1)
            )
        if not self.table.layout.supports_algos(slots[:, FLAGS] & 0xFF):
            self.migrate_layout_full("merge of off-family rows")
        now = now_ms if now_ms is not None else ms_now()
        self._mark_dirty(fps)
        D = self.n_shards
        routed = shard_of(fps, D)
        order, rs, offset, b_local = _route_plan(routed, D)
        fp_g = _to_grid(fps[order].astype(np.int64), rs, offset, D, b_local)
        now_g = np.full((D, b_local), now, dtype=np.int64)
        act_g = _to_grid(np.ones(n, dtype=bool), rs, offset, D, b_local)
        slots_g = np.zeros((D, b_local, slots.shape[1]), dtype=np.int32)
        slots_g[rs, offset] = slots[order]
        put = lambda x: jax.device_put(x, self._batch_sharding)
        if collect:
            fn = getattr(self, "_merge_ev_fn", None)
            if fn is None:
                fn = self._merge_ev_fn = make_sharded_merge(
                    self.mesh, write=self.write_mode, evictees=True
                )
            self.table, merged, ev = fn(
                self.table, put(fp_g), put(slots_g), put(now_g), put(act_g)
            )
            self.stats.dispatches += 1
            merged_h = np.asarray(merged)
            mask = np.zeros(n, dtype=bool)
            mask[order] = merged_h[rs, offset]
            ev_h = np.asarray(ev).reshape(-1, 16)
            ev_lo = ev_h[:, 0].astype(np.int64) & 0xFFFFFFFF
            ev_fp = (ev_h[:, 1].astype(np.int64) << 32) | ev_lo
            keep = ev_fp != 0
            return int(mask.sum()), mask, ev_fp[keep], ev_h[keep].copy()
        if self._merge_fn is None:
            self._merge_fn = make_sharded_merge(
                self.mesh, write=self.write_mode
            )
        self.table, merged = self._merge_fn(
            self.table, put(fp_g), put(slots_g), put(now_g), put(act_g)
        )
        self.stats.dispatches += 1
        return int(np.asarray(merged).sum())

    def read_state(self, fps: np.ndarray, raw: bool = False):
        """(found, full-width slots) for `fps` — the ShardedEngine analog
        of LocalEngine.read_state (routed shard_map gather, no mutation).
        `raw=True` re-packs the gathered rows into the table's own slot
        layout (the region-sync staging form, cf. LocalEngine)."""
        from gubernator_tpu.ops.table2 import F as F_FULL

        n = fps.shape[0]
        if n == 0:
            width = self.table.layout.F if raw else F_FULL
            return (
                np.zeros(0, dtype=bool), np.zeros((0, width), dtype=np.int32)
            )
        D = self.n_shards
        routed = shard_of(fps, D)
        order, rs, offset, b_local = _route_plan(routed, D)
        fp_g = _to_grid(fps[order].astype(np.int64), rs, offset, D, b_local)
        act_g = _to_grid(np.ones(n, dtype=bool), rs, offset, D, b_local)
        fn = getattr(self, "_gather_fn", None)
        if fn is None or getattr(self, "_gather_layout", None) is not (
            self.table.layout
        ):
            fn = self._gather_fn = make_sharded_gather(
                self.mesh, layout=self.table.layout
            )
            self._gather_layout = self.table.layout
        put = lambda x: jax.device_put(x, self._batch_sharding)
        slots_g, found_g = fn(self.table.rows, put(fp_g), put(act_g))
        slots_h = np.asarray(slots_g)
        found_h = np.asarray(found_g)
        slots = np.zeros((n, F_FULL), dtype=np.int32)
        found = np.zeros(n, dtype=bool)
        slots[order] = slots_h[rs, offset]
        found[order] = found_h[rs, offset]
        if raw:
            slots = np.asarray(self.table.layout.pack(slots))
        return found, slots

    def tombstone_fps(self, fps: np.ndarray) -> int:
        n = fps.shape[0]
        if n == 0:
            return 0
        self._mark_dirty(fps)
        D = self.n_shards
        routed = shard_of(fps, D)
        order, rs, offset, b_local = _route_plan(routed, D)
        fp_g = _to_grid(fps[order].astype(np.int64), rs, offset, D, b_local)
        act_g = _to_grid(np.ones(n, dtype=bool), rs, offset, D, b_local)
        put = lambda x: jax.device_put(x, self._batch_sharding)
        if self._tombstone_fn is None:
            self._tombstone_fn = make_sharded_tombstone(self.mesh)
        self.table, found = self._tombstone_fn(self.table, put(fp_g), put(act_g))
        self.stats.dispatches += 1
        return int(np.asarray(found).sum())

    # ------------------------------------------------------- checkpointing
    # Same begin/finish split as LocalEngine (launch on the engine thread,
    # fetch off it), but the extract runs PER SHARD under shard_map so no
    # slot row crosses a device boundary; the tracker's global block ids
    # (shard-major: gid = shard · nblk_local + local_block) regroup into a
    # per-shard local-block grid here.

    def checkpoint_begin(self, gids: np.ndarray, now_ms: Optional[int] = None):
        now = now_ms if now_ms is not None else ms_now()
        blk, nblk = self.ckpt.blk, self.ckpt.nblk
        D = self.n_shards
        shard = gids // nblk
        local = gids % nblk
        counts = np.bincount(shard, minlength=D)
        G = _pad_size(int(max(counts.max(), 1)), floor=8)
        bidx = np.full((D, G), nblk, dtype=np.int64)  # sentinel: zero-fill
        order = np.argsort(shard, kind="stable")
        rs = shard[order]
        offset = np.arange(gids.shape[0]) - np.searchsorted(rs, rs)
        bidx[rs, offset] = local[order]
        if self._extract_dirty_fn is None:
            self._extract_dirty_fn = make_sharded_extract_dirty(
                self.mesh, blk, layout=self.table.layout
            )
        put = lambda x: jax.device_put(x, self._batch_sharding)
        return self._extract_dirty_fn(
            self.table.rows, put(bidx),
            put(np.full(D, now, dtype=np.int64)),
        )

    def checkpoint_finish(self, pending):
        """Fetch per-shard live prefixes (pow2-padded — the
        extract_live_rows fetch rule, per shard) and concatenate."""
        F = self.table.layout.F

        slots_g, fp_g, cnt_g = pending
        counts = np.asarray(cnt_g)
        width = int(fp_g.shape[1])
        fps_l, slots_l = [], []
        for d in range(self.n_shards):
            n = int(counts[d])
            if n == 0:
                continue
            pad = 256
            while pad < n:
                pad *= 2
            pad = min(pad, width)
            fps_l.append(np.asarray(fp_g[d, :pad])[:n])
            slots_l.append(np.asarray(slots_g[d, :pad])[:n])
        if not fps_l:
            return (
                np.empty(0, dtype=np.int64),
                np.empty((0, F), dtype=np.int32),
            )
        return np.concatenate(fps_l), np.concatenate(slots_l)

    # ------------------------------------------------------------- tiering

    def extract_idle(self, now_ms: int, idle_ms: int,
                     max_rows: int = 1 << 16):
        """Live rows idle past `idle_ms` across every shard: (fps (N,)
        i64, slots (N, F_layout) i32), N ≤ max_rows. The filter + pack
        runs PER SHARD under shard_map (make_sharded_extract_idle — no
        slot row crosses a device boundary); the host fetches only
        per-shard idle prefixes, the checkpoint_finish fetch rule. The
        cap slices shard-major — the remainder stays for the next
        sweep."""
        fn = self._extract_idle_fn
        if fn is None or getattr(self, "_extract_idle_layout", None) is not (
            self.table.layout
        ):
            fn = self._extract_idle_fn = make_sharded_extract_idle(
                self.mesh, layout=self.table.layout
            )
            self._extract_idle_layout = self.table.layout
        D = self.n_shards
        put = lambda x: jax.device_put(x, self._batch_sharding)
        slots_g, fp_g, cnt_g = fn(
            self.table.rows,
            put(np.full(D, now_ms, dtype=np.int64)),
            put(np.full(D, idle_ms, dtype=np.int64)),
        )
        counts = np.asarray(cnt_g)
        width = int(fp_g.shape[1])
        F_l = self.table.layout.F
        fps_l, slots_l = [], []
        left = int(max_rows)
        for d in range(D):
            n = min(int(counts[d]), left)
            if n <= 0:
                continue
            pad = 256
            while pad < n:
                pad *= 2
            pad = min(pad, width)
            fps_l.append(np.asarray(fp_g[d, :pad])[:n].copy())
            slots_l.append(np.asarray(slots_g[d, :pad])[:n].copy())
            left -= n
        if not fps_l:
            return (
                np.empty(0, dtype=np.int64),
                np.empty((0, F_l), dtype=np.int32),
            )
        return np.concatenate(fps_l), np.concatenate(slots_l)

    # ----------------------------------------------------------- telemetry

    def telemetry_begin(self, now_ms: Optional[int] = None):
        """Launch the per-shard telemetry scan (parallel/telemetry.py)
        without fetching; additionally yields per-shard live counts so hot
        shards are observable (cf. LocalEngine.telemetry_begin)."""
        from gubernator_tpu.parallel.telemetry import sharded_scan_begin

        return sharded_scan_begin(
            self, now_ms if now_ms is not None else ms_now()
        )

    supports_grow = False  # the daemon must not start an auto-grow loop

    def maybe_grow(self, **kw) -> bool:
        """Sharded tables are sized at mesh construction; growth means a mesh
        re-plan (host-orchestrated, like the reference's fixed CacheSize per
        node). Not auto-grown."""
        return False

    # ------------------------------------------------------ pipelined surface
    # The same prepare/issue/finish protocol as LocalEngine (ops/engine.py):
    # stage_pass routes + packs + stages the ingress grid on ANY thread,
    # issue_staged advances the sharded table on the engine thread without
    # fetching, finish_staged materializes the ONE packed output grid on a
    # fetch thread — so a sharded daemon's front door overlaps host routing
    # of dispatch N+1 with mesh execution of N exactly like the local one.

    supports_pipeline = True

    def stage_pass(self, pass_batch: HostBatch, n: int, cascade: bool = False):
        """(padded batch, staged route) for one unique-fp pass. No row
        padding is needed: the compiled shape depends only on the pow2
        per-shard width b_local, not on n. `cascade` is accepted for
        protocol parity and ignored — mesh programs rely on the host-side
        verdict fold (engine._fold_cascades_host)."""
        staged = self._stage(pass_batch, None)
        return pass_batch, staged

    # The fused front door (ops/engine.prepare_check_wire): a chunk the
    # native parser already holds as compact lanes is staged from them by
    # the one native staging call the local engine uses, laid out here as
    # `_stage_a2a` lays out the same rows from columns, byte for byte.

    # behavior bits the compact lanes drop as inert (ops/wire._INERT) that a
    # mesh engine acts on: GLOBAL rows fork to the replica plane in
    # `prepare_columns`, which reads them off the columns
    wire_columns_behavior = int(Behavior.GLOBAL | Behavior.MULTI_REGION)

    @property
    def supports_wire_ingress(self) -> bool:
        """Whether a chunk's lanes may be staged for this engine as they
        are: the arrival-order compact grid with the copies of a key folded
        in-trace is the only layout that needs nothing of the host but the
        rows in order. A host-routed grid sorts rows by owner, host dedup
        plans passes over a HostBatch, a Store orders write-throughs on
        the serial path: those engines take the parser's columns."""
        return (
            self.wire == "compact" and self.route == "device"
            and self.folds_copies and self.store is None
        )

    def _a2a_rows(self, n: int) -> int:
        """Rows per device block of an arrival-order grid of `n` rows."""
        return _pad_size(max(1, -(-n // self.n_shards)), floor=8)

    def wire_pad(self, n: int) -> int:
        """Data columns of the fused grid of `n` rows: D blocks of c."""
        return self.n_shards * self._a2a_rows(n)

    def stage_wire(self, grid: np.ndarray, math: str, cascade: bool = False):
        """Stage a fused front-door grid: the chunk's (5, D*c + 1) lanes in
        arrival order (ops/wire.stage_wire_chunk with every copy kept, its
        base in the trailing column) become the (D, 5, c+1) ingress grid of
        `_stage_a2a` — row i on device i // c, the base column after every
        block — and the `_StagedA2A` that `issue_staged` takes. `cascade`
        as in `stage_pass`; a chunk with level bits does not come here."""
        from gubernator_tpu.ops.wire import block_base

        c = (grid.shape[1] - 1) // self.n_shards
        blocks = self._wire_blocks(grid[:, :-1], c)
        blocks[:, :, c] = grid[:, -1]  # the base, and zeros under it
        with tracing.stage.within("shard_put"):
            dev = self._put_grid(blocks)
        self._wire_count("put", blocks.nbytes)
        return _StagedA2A(
            c=c, dev=dev, math=math, wire=True, base=block_base(grid),
            needs_full=batch_needs_full_layout(self.table.layout, math),
            lanes=grid,
        )

    def _wire_blocks(self, flat: np.ndarray, c: int) -> np.ndarray:
        """The pooled (D, 5, c+1) compact ingress grid with the (5, D*c)
        arrival-order lanes `flat` in its data columns: one strided copy."""
        D, L = self.n_shards, flat.shape[0]
        shape = (D, L, c + 1)
        if self._pool is not None:
            grid = self._pool.get(shape, dtype=np.int32)
        else:
            grid = np.empty(shape, dtype=np.int32)
        np.copyto(grid[:, :, :c], flat.reshape(L, D, c).transpose(1, 0, 2))
        return grid

    def migrate_layout_full(self, reason: str = "off-family traffic") -> bool:
        """Migrate the authoritative shards to the canonical full layout in
        place (engine thread only; cf. LocalEngine.migrate_layout_full).
        One jitted per-shard row unpack — the shard axis is untouched, so
        the sharding survives the conversion."""
        from gubernator_tpu.ops.layout import FULL

        if self.table.layout is FULL:
            return False
        self.table = self._table_to_full(self.table)
        self._layout = FULL
        return True

    def _table_to_full(self, table: Table2) -> Table2:
        from gubernator_tpu.ops.layout import FULL

        import logging

        logging.getLogger("gubernator_tpu.engine").warning(
            "migrating sharded table layout %s -> full", table.layout.name
        )
        rows_full = jax.jit(table.layout.unpack_rows)(table.rows)
        rows_full = jax.device_put(rows_full, self._batch_sharding)
        self.stats.layout_migrations += 1
        return Table2(rows=rows_full, layout=FULL)

    def _decide(self, table: Table2, staged):
        from gubernator_tpu.ops.layout import FULL

        if getattr(staged, "needs_full", False) and table.layout is not FULL:
            # engine thread (_decide only runs from issue/dispatch): convert
            # whichever table this dispatch targets before launching
            table = self._table_to_full(table)
        dedup = self.dedup == "device"
        if isinstance(staged, _StagedA2A):
            from gubernator_tpu.parallel.a2a import (
                exchange_traffic,
                make_a2a_decide,
            )

            # the built step is kept with what it was traced with: the
            # exchange's geometry is read when the step is built
            key = ("a2a", staged.c, staged.math, staged.wire)
            built = self._decide_fns.get(key)
            if built is None:
                built = self._decide_fns[key] = make_a2a_decide(
                    self.mesh, staged.c, math=staged.math,
                    write=self.write_mode, dedup=dedup, wire=staged.wire,
                ), exchange_traffic(staged.c, self.n_shards)
            fn, (lanes, ex_rows, ex_bytes) = built
            rows = staged.c
            self.exchange_rows += ex_rows
            self.exchange_bytes += ex_bytes
        else:
            key = ("host", staged.math, staged.wire)
            fn = self._decide_fns.get(key)
            if fn is None:
                fn = self._decide_fns[key] = make_sharded_decide(
                    self.mesh, math=staged.math, write=self.write_mode,
                    dedup=dedup, wire=staged.wire,
                )
            rows = lanes = staged.b_local
        self.mesh_lanes += self.n_shards * lanes
        out_buf = self._take_egress(
            (self.n_shards, rows + 2, 4),
            np.int32 if staged.wire else np.int64,
        )
        return fn(table, staged.dev, out_buf)

    def issue_staged(self, staged: "_Staged", batch_rows: int):
        # dispatch count is folded in via the finish delta (engine thread)
        table, out = self._decide(self.table, staged)
        self.table = table
        return staged, out

    def finish_staged(self, pending, n: int):
        staged, outh = pending  # fetched (engine.fetch_passes)
        s, l, r, t, dropped, hit, unproc, member, evicted = self._unroute(
            staged, outh, n
        )
        # per-row accounting over the rows the kernel actually processed
        # (pass rows are all active; a2a capacity drops count at their
        # retry; dedup member rows are represented by their carrier)
        counted = ~unproc & ~member
        lanes = getattr(staged, "lanes", None)
        if lanes is not None:
            # a fused grid holds the chunk's error rows in place, as zeroed
            # lanes the kernel never saw: fp == 0 is the decode's rule
            counted &= (lanes[0, :n] != 0) | (lanes[1, :n] != 0)
        st = (
            int(hit[counted].sum()),
            int((~hit[counted]).sum()),
            int((s[counted] == 1).sum()),
            evicted,
        )
        return (s, l, r, t, dropped, hit), st, unproc

    def finish_wire(self, mod, passes, cols):
        """The native finish of a fused dispatch's `passes` (fetched) into
        `cols` (ops/wire.finish_wire_chunk; fetch thread): `_unroute` and
        `finish_staged`'s accounting for an arrival-order compact grid, in
        the one routine the local engine's finish is — the (D, c+2, 4)
        egress grid is the block, the staged base its base, and the
        chunk's lanes say which rows the kernel never saw. Timed as
        `shard_unroute`, like the NumPy decode. None where a pass is not
        such a grid (host-routed, full-width)."""
        from gubernator_tpu.ops.wire import finish_wire_chunk

        blocks = []
        for p, n, _batch, (staged, outh) in passes:
            if not (isinstance(staged, _StagedA2A) and staged.wire):
                return None
            blocks.append((
                outh, n, p.rows, p.members, p.member_counts, staged.base,
                staged.lanes,
            ))
        with tracing.stage.within("shard_unroute"):
            done = finish_wire_chunk(mod, blocks, cols)
        if done is None:
            return None
        self._wire_count("fetch", sum(b[0].nbytes for b in blocks))
        if done.overflow:
            with self._stage_lock:
                self.a2a_overflow += done.overflow
        return done

    def _redispatch_rows(self, batch: HostBatch, n: int, uncounted=None):
        """Pipelined-retry hook (engine thread): depth=1 counts evictions and
        dispatches, plus the hit/miss/over outcome of `uncounted` rows —
        those the phase-1 pass never processed (a2a capacity drops); rows
        the phase-1 kernel DID probe were already counted there (cf.
        LocalEngine._redispatch_rows)."""
        _, (s, l, r, t, d, h) = self._dispatch(batch, depth=1, count=uncounted)
        return s[:n], l[:n], r[:n], t[:n], d[:n], h[:n]

    # ------------------------------------------------------- dispatch core

    def _stage(self, batch: HostBatch, shard: Optional[np.ndarray]):
        """Host half of one mesh dispatch. route="host": sort rows by owning
        shard and scatter the packed (12, n) columns into ONE (D, 12,
        b_local) ownership grid. route="device": NO routing work — rows ship
        in arrival order and the mesh exchanges them over ICI
        (parallel/a2a.py). Explicit `shard` pins (the GLOBAL replica path)
        always take the host grid: a2a routes by ownership hash only.
        Grids build in the persistent staging ring (_StagingPool); each
        phase is a part of the runner's `put` stage (shard_route,
        shard_pack | wire_pack, shard_put; the compact wire's plan, which
        decides the pack's name, is `put`'s own time)."""
        if self.route == "device" and shard is None:
            return self._stage_a2a(batch)
        D = self.n_shards
        with tracing.stage.within("shard_route"):
            routed = shard if shard is not None else shard_of(batch.fp, D)
            order, rs, offset, b_local = _route_plan(routed, D)
        wired, base = self._wire_plan(batch)
        with tracing.stage.within("wire_pack" if wired else "shard_pack"):
            if wired:
                from gubernator_tpu.ops import wire as wire_mod

                # compact grid: one trailing column per device block carries
                # the base (decode_wire_block reads cells [0, -1], [1, -1])
                shape = (D, wire_mod.WIRE_LANES, b_local + 1)
                grid = (
                    self._pool.get(shape, zero=True, dtype=np.int32)
                    if self._pool is not None
                    else np.zeros(shape, dtype=np.int32)
                )
                packed = wire_mod.pack_wire_rows(batch, base)
                grid[rs, :, offset] = packed[:, order].T
                for d in range(D):
                    wire_mod.stamp_base(grid[d], base)
            else:
                packed = pack_host_batch(batch)  # (12, n)
                shape = (D, 12, b_local)
                grid = (
                    self._pool.get(shape, zero=True)
                    if self._pool is not None
                    else np.zeros(shape, dtype=np.int64)
                )
                grid[rs, :, offset] = packed[:, order].T
        with tracing.stage.within("shard_put"):
            dev = self._put_grid(grid)
        self._wire_count("put", grid.nbytes)
        math = effective_math(self.table.layout, batch)
        return _Staged(
            order=order, rs=rs, offset=offset, b_local=b_local, dev=dev,
            math=math, wire=wired, base=base,
            needs_full=batch_needs_full_layout(self.table.layout, math, batch),
        )

    def _wire_plan(self, batch: HostBatch) -> "tuple[bool, int]":
        """Per-dispatch wire decision: (compact?, base). Compact only when
        the engine is in compact mode AND the batch is representable in the
        narrow layout (ops/wire.wire_encodable) — otherwise the dispatch
        ships full-width with identical semantics."""
        if self.wire != "compact":
            return False, 0
        from gubernator_tpu.ops import wire as wire_mod

        base = wire_mod.pick_base(batch)
        return wire_mod.wire_encodable(batch, base), base

    def _put_grid(self, grid: np.ndarray):
        """One staged ingress grid → sharded device array. On TPU meshes
        per-shard puts issue CONCURRENTLY and assemble with
        make_array_from_single_device_arrays — put cost becomes
        max-of-shards instead of sum-of-shards. CPU meshes keep the single
        zero-copy put (GUBER_SHARD_PUT overrides either way)."""
        if not self._put_concurrent:
            return jax.device_put(grid, self._batch_sharding)
        if self._put_pool is None:
            self._put_pool = ThreadPoolExecutor(
                max_workers=min(self.n_shards, 8), thread_name_prefix="put"
            )
        devs = list(self.mesh.devices.flat)
        futs = [
            self._put_pool.submit(jax.device_put, grid[d : d + 1], devs[d])
            for d in range(self.n_shards)
        ]
        return jax.make_array_from_single_device_arrays(
            grid.shape, self._batch_sharding, [f.result() for f in futs]
        )

    def _stage_a2a(self, batch: HostBatch) -> "_StagedA2A":
        """Arrival-order staging: pack the columns straight into a pooled
        flat buffer and strided-copy it into the pooled ingress grid — row
        i lands on device i // c. O(1) routing work on the host, zero
        fresh allocations in steady state. Compact-wire dispatches build
        the 5-lane int32 grid with one trailing base column per device
        (20 B/row on the put vs the full layout's 96)."""
        D = self.n_shards
        n = batch.fp.shape[0]
        c = self._a2a_rows(n)
        wired, base = self._wire_plan(batch)
        with tracing.stage.within("wire_pack" if wired else "shard_pack"):
            if wired:
                from gubernator_tpu.ops import wire as wire_mod

                L = wire_mod.WIRE_LANES
                if self._pool is not None:
                    flat = self._pool.get((L, D * c), dtype=np.int32)
                    flat[:, n:] = 0  # stale tail from the buffer's last use
                else:
                    flat = np.zeros((L, D * c), dtype=np.int32)
                wire_mod.pack_wire_rows(batch, base, out=flat[:, :n])
                grid = self._wire_blocks(flat, c)
                grid[:, :, c] = 0
                for d in range(D):
                    wire_mod.stamp_base(grid[d], base)
            else:
                if self._pool is not None:
                    flat = self._pool.get((12, D * c))
                    flat[:, n:] = 0  # stale tail from the buffer's last use
                    grid = self._pool.get((D, 12, c))
                else:
                    flat = np.zeros((12, D * c), dtype=np.int64)
                    grid = np.empty((D, 12, c), dtype=np.int64)
                pack_host_batch(batch, out=flat[:, : n])
                # one strided copy rearranges (12, D·c) → (D, 12, c); every
                # grid byte is overwritten, so the pooled buffer needs no
                # zeroing
                np.copyto(grid, flat.reshape(12, D, c).transpose(1, 0, 2))
        with tracing.stage.within("shard_put"):
            dev = self._put_grid(grid)
        self._wire_count("put", grid.nbytes)
        math = effective_math(self.table.layout, batch)
        return _StagedA2A(
            c=c, dev=dev, math=math, wire=wired, base=base,
            needs_full=batch_needs_full_layout(self.table.layout, math, batch),
        )

    def _unroute(self, staged, outh: np.ndarray, n: int):
        """Decode the fetched (D, rows+2, 4) packed output grid back to
        pass-row order: per-row responses, the `unprocessed` mask (rows the
        a2a exchange capacity-dropped before they reached the kernel), the
        `member` mask (rows answered from an in-trace dedup carrier —
        excluded from per-row accounting), and the summed per-device
        evicted_unexpired (the only stat that cannot be derived per row).
        Flag bits shared with the single-device decoder
        (kernel2.FLAG_*/unpack_outputs). Compact-wire outputs (int32 —
        ops/wire.py) decode here with vectorized numpy: the reset lane is
        base-relative, everything else widens to int64. A part of the
        runner's `fetch` stage (shard_unroute, wire_decode inside it)."""
        self._wire_count("fetch", outh.nbytes)
        with tracing.stage.within("shard_unroute"):
            if isinstance(staged, _StagedA2A):
                st = outh[:, staged.c, :].astype(np.int64).sum(axis=0)
                per = outh[:, : staged.c, :].reshape(-1, 4)[:n]
                per = per.copy() if per.dtype == np.int64 else per
            else:
                st = outh[:, staged.b_local, :].astype(np.int64).sum(axis=0)
                per = np.empty((n, 4), dtype=outh.dtype)
                per[staged.order] = outh[staged.rs, staged.offset]
            if staged.wire:
                from gubernator_tpu.ops.wire import decode_wire_rows

                with tracing.stage.within("wire_decode"):
                    per = decode_wire_rows(per, staged.base)
            status = (per[:, 3] & FLAG_STATUS).astype(np.int32)
            hit = (per[:, 3] & FLAG_HIT) != 0
            dropped = (per[:, 3] & FLAG_DROPPED) != 0
            unproc = (per[:, 3] & FLAG_UNPROCESSED) != 0
            member = (per[:, 3] & FLAG_MEMBER) != 0
            if isinstance(staged, _StagedA2A):
                # capacity overflow: exchanged rows that never reached a
                # kernel this dispatch (members inherit their carrier's
                # flags without having been exchanged — not counted)
                over = int((unproc & ~member).sum())
                if over:
                    with self._stage_lock:
                        self.a2a_overflow += over
            return (
                status, per[:, 0], per[:, 1], per[:, 2], dropped, hit, unproc,
                member, int(st[3]),
            )

    def _dispatch(
        self,
        batch: HostBatch,
        depth: int = 0,
        shard: Optional[np.ndarray] = None,
        table_attr: str = "table",
        count: Optional[np.ndarray] = None,
    ):
        """Route one unique-fp pass across shards, run, and un-route responses
        back to pass-row order. Rows dropped by the claim auction are
        re-dispatched (cf. LocalEngine._dispatch_with_retry).

        `shard` overrides ownership routing (used by the GLOBAL path to pin
        requests to their home device's replica table); `table_attr` picks the
        state table ("table" = authoritative shards, "replica" = GLOBAL
        read-replicas). `count` masks the rows whose hit/miss/over outcome
        this call should account (None = all active at depth 0, none at
        retry depths): each row is counted exactly once, at the dispatch
        that first PROCESSES it — claim-dropped rows were probed and count
        immediately; a2a capacity-dropped rows (never probed, FLAG_UNPROCESSED)
        count at the retry that finally reaches the kernel. Rows that
        exhaust retries without ever being probed are not counted, matching
        the host path where such rows cannot exist."""
        n = batch.fp.shape[0]
        self._mark_dirty(batch.fp)
        staged = self._stage(batch, shard)
        table, out = self._decide(getattr(self, table_attr), staged)
        setattr(self, table_attr, table)
        self.stats.dispatches += 1
        outh = np.asarray(out)
        self._recycle_egress(out)
        status, limit, remaining, reset, dropped, hit, unproc, member, evicted = (
            self._unroute(staged, outh, n)
        )
        if count is None:
            count = np.asarray(batch.active) if depth == 0 else np.zeros(n, bool)
        counted = count & ~unproc & ~member
        self.stats.cache_hits += int(hit[counted].sum())
        self.stats.cache_misses += int((~hit[counted]).sum())
        self.stats.over_limit += int((status[counted] == 1).sum())
        self.stats.evicted_unexpired += evicted
        if dropped.any() and depth < 3:
            rows = np.nonzero(dropped)[0]
            sub_shard = shard[rows] if shard is not None else None
            if sub_shard is None and self.route == "device" and depth == 2:
                # FINAL retry falls back to host ownership routing: the
                # reference never rejects a valid request on internal
                # capacity, and the a2a exchange's bounded capacity must not
                # either — the host grid has no capacity to exceed, so
                # residual rows can only fail on (rare) claim contention
                sub_shard = shard_of(batch.fp[rows], self.n_shards)
            _, (s2, l2, r2, t2, d2, h2) = self._dispatch(
                _subset(batch, rows),
                depth=depth + 1,
                shard=sub_shard,
                table_attr=table_attr,
                count=(count & unproc)[rows],
            )
            status = status.copy(); limit = limit.copy()
            remaining = remaining.copy(); reset = reset.copy()
            dropped = dropped.copy(); hit = hit.copy()
            status[rows], limit[rows], remaining[rows], reset[rows] = s2, l2, r2, t2
            dropped[rows] = d2
            hit[rows] = h2
        elif dropped.any():
            # exhausted retries: decision was never persisted — callers
            # surface ERR_NOT_PERSISTED per item instead of failing open.
            # Rows that ALSO never reached a kernel (still FLAG_UNPROCESSED
            # at terminal failure) are counted separately: they are absent
            # from hits/misses/over, and this counter is what keeps that
            # absence observable instead of silent drift
            self.stats.dropped += int(dropped.sum())
            self.stats.unprocessed_dropped += int((dropped & unproc).sum())
        return np.arange(n), (status, limit, remaining, reset, dropped, hit)


class _Staged(NamedTuple):
    """One staged mesh dispatch: the routing plan + the on-device ingress
    grid. Carried from stage (any thread) to issue (engine thread) to finish
    (fetch thread) on the pipelined path."""

    order: np.ndarray  # (n,) original row index at each sorted position
    rs: np.ndarray  # (n,) owning shard, sorted
    offset: np.ndarray  # (n,) position within the shard's grid row
    b_local: int  # padded per-shard width
    dev: object  # (D, 12, b_local) i64 — or compact (D, 5, b_local+1) i32
    math: str  # static decision-graph mode ("token" | "mixed")
    wire: bool = False  # compact 5-lane int32 wire grids (ops/wire.py)
    base: int = 0  # created_at base of the compact encoding
    needs_full: bool = False  # batch unservable by a packed table layout


class _StagedA2A(NamedTuple):
    """One staged device-routed dispatch (parallel/a2a.py): arrival-order
    grid; the mesh does the ownership exchange (capacity derives from c and
    the mesh size inside make_a2a_decide)."""

    c: int  # rows per device (pow2)
    dev: object  # (D, 12, c) i64 — or compact (D, 5, c+1) i32, arrival order
    math: str  # static decision-graph mode ("token" | "mixed")
    wire: bool = False  # compact 5-lane int32 wire grids (ops/wire.py)
    base: int = 0  # created_at base of the compact encoding
    needs_full: bool = False  # batch unservable by a packed table layout
    # a fused dispatch (stage_wire): the chunk's (5, D*c + 1) lanes it was
    # laid out from, error rows zeroed in place; None for a pass of columns
    lanes: Optional[np.ndarray] = None


def _route_plan(routed: np.ndarray, D: int):
    """Shared shard-routing plan: rows grouped by shard, each row's position
    within its shard, and the padded per-shard width. Used by both the decide
    and install paths so their grid geometry can never diverge."""
    n = routed.shape[0]
    order = np.argsort(routed, kind="stable")
    counts = np.bincount(routed, minlength=D)
    b_local = _pad_size(int(counts.max()))
    rs = routed[order]
    offset = np.arange(n) - np.searchsorted(rs, rs)
    return order, rs, offset, b_local


def _to_grid(field: np.ndarray, shard_sorted, offset, D: int, b_local: int) -> np.ndarray:
    grid = np.zeros((D, b_local), dtype=field.dtype)
    grid[shard_sorted, offset] = field
    return grid
