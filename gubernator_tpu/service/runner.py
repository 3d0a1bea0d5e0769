"""EngineRunner: the single-writer dispatch thread.

The device table has exactly one owner — the kernel — and the host side
funnels every mutation through ONE thread, the TPU analog of the reference's
"each worker owns its cache, no mutexes" rule (reference workers.go:19-37).
asyncio handlers await engine work through this runner; ordering of submitted
jobs is FIFO, which is what makes the front-door batcher's request-order
contract hold.
"""

from __future__ import annotations

import asyncio
from concurrent.futures import ThreadPoolExecutor
from typing import Optional

import numpy as np

from gubernator_tpu import tracing
from gubernator_tpu.ops.batch import RequestColumns, ResponseColumns
from gubernator_tpu.ops.engine import LocalEngine
from gubernator_tpu.service.wire import concat_columns


# gubernator_tpu_decisions_total label values (types.Algorithm order)
_ALGO_LABELS = (
    "token_bucket", "leaky_bucket", "gcra", "sliding_window",
    "concurrency_lease", "invalid",
)


def _label_counts(algo_col) -> list:
    """Rows of an `algo` column by decision label (`_ALGO_LABELS`' order, an
    out-of-range value under the last): what the native parser counts for a
    summarised batch (wire.RowSummary.algo_counts)."""
    a = np.asarray(algo_col)
    last = len(_ALGO_LABELS) - 1
    lab = np.where((a >= 0) & (a < last), a, last)
    return np.bincount(lab, minlength=len(_ALGO_LABELS)).tolist()


def _drain_sidecars(engine) -> None:
    """`LocalEngine.drain_sidecars` where the engine has one (engine
    thread): the head of every job here that touches the shadow."""
    drain = getattr(engine, "drain_sidecars", None)
    if drain is not None:
        drain()


class EngineRunner:
    """Serializes engine table access onto one thread; async façade.

    The pipelined path (`check`) splits each request batch into an ISSUE
    half on the engine thread (pack + enqueue kernel dispatches, no fetch)
    and a FINISH half on a small fetch pool (materialize outputs) — so the
    engine thread packs dispatch N+1 while N executes on-device and N-1's
    results stream back. Rare feedback (claim drops, Store rehydrates) runs
    back on the engine thread via the `fixup` hook; stats deltas are folded
    in on the engine thread too, keeping every engine mutation single-
    writer."""

    def __init__(self, engine: LocalEngine, metrics=None, fetch_workers: int = 4):
        self.engine = engine
        self.metrics = metrics
        self._exec = ThreadPoolExecutor(max_workers=1, thread_name_prefix="engine")
        # sized to the configured pipeline depth: fewer fetch workers than
        # in-flight dispatches would silently cap the pipeline
        self._fetch = ThreadPoolExecutor(
            max_workers=max(1, fetch_workers), thread_name_prefix="fetch"
        )
        # preparation pool separate from the fetch pool: finish() blocks a
        # worker for a device round trip, and a prepare stuck behind blocked
        # fetchers would stall the whole pipeline's intake
        self._prep = ThreadPoolExecutor(
            max_workers=max(2, fetch_workers // 2), thread_name_prefix="prep"
        )
        # background telemetry fetches get their OWN single thread (lazy):
        # a table scan parked on a fetch worker would steal a pipeline slot
        self._telemetry: Optional[ThreadPoolExecutor] = None
        # checkpoint-extract fetches likewise (lazy): the dirty-block
        # fetch overlaps serving dispatches, never competes with them
        self._ckpt: Optional[ThreadPoolExecutor] = None
        # cumulative per-algorithm decision counts (the debug-plane mirror
        # of gubernator_tpu_decisions_total; /v1/debug/pipeline)
        self.algo_counts = {k: 0 for k in _ALGO_LABELS}
        # EWMA of the issue stage (seconds) — the device-launch half of a
        # dispatch. The batcher's auto overload deadline
        # (GUBER_OVERLOAD_DEADLINE_MS=auto) is derived from this: a queue
        # estimate denominated in what a launch actually costs on THIS
        # deployment, not a hand-tuned wall-clock guess.
        self.issue_ewma = 0.0
        # event-loop callbacks run on behalf of runner dispatches: one per
        # dispatch, its completion (_run_chain; /v1/debug/pipeline "runner")
        self.loop_trips = 0

    def _count_decisions(self, parts) -> None:
        """Per-algorithm decision accounting of one dispatch (the
        gubernator_tpu_decisions_total{algorithm} family and its mirror,
        `algo_counts`): one update an algorithm a dispatch, never per row
        nor per RPC. `parts` are the chunk's pieces. One that carries the
        parser's summary (a plain RPC's WireBatch) adds the integers the
        parser counted on the door thread; any other (columns, rows
        selected from a batch) is counted here from its `algo` column.
        Cascade member rows carry their own algorithm, so every level
        counts as one decision."""
        total = [0] * len(_ALGO_LABELS)
        for part in parts:
            summary = getattr(part, "summary", None)
            if summary is not None:
                counts = summary.algo_counts
            else:
                counts = _label_counts(getattr(part, "cols", part).algo)
            for v, c in enumerate(counts):
                total[v] += c
        for label, c in zip(_ALGO_LABELS, total):
            if c:
                self.algo_counts[label] += c
                if self.metrics is not None:
                    self.metrics.decisions_total.labels(algorithm=label).inc(c)

    def _run_chain(self, links, parts, done, fused=lambda: 0):
        """One dispatch's way through the worker threads: it leaves the
        event loop once and comes back once. `links` is a sequence of
        (executor, fn): the first link runs fn(None), each later one the
        result of the link before it, and the thread that finished a link
        submits the next one itself — nothing returns to the loop between
        stages. The thread that ran the last link makes the dispatch's one
        `call_soon_threadsafe`. What that runs on the loop thread counts the
        trip (`loop_trips`) and the decisions of `parts` (the chunk's
        pieces, once a dispatch: `_count_decisions`; `algo_counts` is a
        plain dict, so here and on no worker),
        calls `done(rc, exc, fused())` if there is one — where the batcher
        answers its callers, before any coroutine is resumed — and resolves
        the returned future. An exception in any link (a shut-down executor
        included) skips the links after it and arrives the same way."""
        loop = asyncio.get_running_loop()
        fut = loop.create_future()

        def land(rc, exc):
            self.loop_trips += 1
            try:
                if exc is None:
                    self._count_decisions(parts)
                if done is not None:
                    done(rc, exc, fused())
            finally:
                if fut.cancelled():  # its awaiter was; nobody is left to tell
                    pass
                elif exc is not None:
                    fut.set_exception(exc)
                else:
                    fut.set_result(rc)

        def step(i, value):
            try:
                value = links[i][1](value)
                if i + 1 < len(links):
                    links[i + 1][0].submit(step, i + 1, value)
                    return
            except BaseException as exc:  # all an executor's future carries
                loop.call_soon_threadsafe(land, None, exc)
                return
            loop.call_soon_threadsafe(land, value, None)

        try:
            links[0][0].submit(step, 0, None)
        except RuntimeError as exc:  # the first executor is shut down
            land(None, exc)
        return fut

    def _stage_columns(self, parts, now_ms, disp):
        """The `put` stage of a chunk staged as columns (a prep thread)."""
        from gubernator_tpu.ops.engine import prepare_check_columns

        with tracing.stage("put", self.metrics, disp=disp):
            prepared = prepare_check_columns(
                self.engine, concat_columns(parts), now_ms=now_ms
            )
        if self.metrics is not None:
            self._observe_shard_stages()
        return prepared

    async def check(
        self, cols, now_ms: Optional[int] = None, disp=None,
        done=None, counted=None,
    ) -> ResponseColumns:
        """Pipelined check when the engine supports the prepare/issue/finish
        split, else the serial path. `cols` is one RequestColumns or a list
        of them that the prep job concatenates (a coalesced chunk). Store-
        configured engines stay serial: write-through ordering and miss-
        rehydrates must serialize against every same-key dispatch, which
        interleaved pipelined chunks cannot guarantee — durability trades
        pipeline throughput. Engines whose batches need a custom split (the
        mesh-global engine's replica/owner fork) provide their own pending
        type through the prepare_columns/issue_pending/finish_pending hooks.

        `disp` is the batcher's tracing.Dispatch: each pipeline stage is a
        tracing.stage under it (histogram sample, `gub:<stage>` profiler
        span carrying its `dispatch` number, child span under its trace), so
        a coalesced flush decomposes per stage in every view.

        `done(rc, exc, fused)`, when given, is called on the loop thread by
        the dispatch's one crossing back (`_run_chain`), before this
        coroutine is resumed: the batcher answers its callers there.
        `fused` counts the passes the fused wire staging issued for the
        chunk (`check_wire`); 0 here, where every chunk is staged as
        columns. `counted` is what the dispatch's decisions are counted
        from where that is not `cols`: the parsed pieces these columns came
        in (`check_wire`, for an engine that takes no lanes), whose
        summaries hold the counts."""
        parts = [cols] if isinstance(cols, RequestColumns) else cols
        if (
            not getattr(self.engine, "supports_pipeline", False)
            or getattr(self.engine, "store", None) is not None
        ):
            return await self.check_columns(
                concat_columns(parts), now_ms=now_ms,
                done=done, disp=disp, counted=counted,
            )
        return await self._run_chain(
            ((self._prep, lambda _: self._stage_columns(parts, now_ms, disp)),
             *self._issue_and_finish(disp)),
            counted or parts, done,
        )

    async def check_wire(
        self, parts, now_ms=None, disp=None, done=None,
    ) -> ResponseColumns:
        """Fused front-door check: pre-parsed WireBatch pieces
        (service/wire.py — native-parser lanes) staged straight into ONE
        compact ingress grid, no column concat and no HostBatch pack. A
        key sent more than once keeps the grid for its first copy, and the
        later copies follow as passes of the same dispatch. A chunk that
        cannot ride the fused path (a non-encodable row, `created_at` skew
        beyond the wire's budget) is staged again as columns by the same
        prep job that found it out, which is semantically identical; an
        engine that is not wire-capable (a mesh engine with a host route or
        a host plan), or has a Store, takes `check` from here. A wire-capable
        mesh engine keeps every copy of a key in its one grid and declines,
        on the same terms, what its lanes cannot tell it (a GLOBAL or
        MULTI_REGION row, cascade level bits). `done` as in `check`: its
        `fused` is the number of passes the fused staging issued, 0 when
        the columns staging served the chunk."""
        engine = self.engine
        cols = [p.cols for p in parts]
        if (
            not getattr(engine, "supports_wire_ingress", False)
            or getattr(engine, "store", None) is not None
        ):
            return await self.check(
                cols, now_ms=now_ms, disp=disp, done=done, counted=parts,
            )
        from gubernator_tpu.ops.engine import prepare_check_wire

        fused = 0

        def prepare(_):
            nonlocal fused
            with tracing.stage("put", self.metrics, disp=disp) as st:
                prepared = prepare_check_wire(engine, parts, now_ms=now_ms)
                if prepared is None:
                    # the chunk cannot fuse and is staged again below:
                    # wasted work under a label of its own, so that `put`
                    # stays the staging that was used
                    st.name = "put_miss"
            if prepared is None:
                prepared = self._stage_columns(cols, now_ms, disp)
            else:
                fused = len(prepared.passes)
                if self.metrics is not None:
                    self._observe_shard_stages()
            return prepared

        return await self._run_chain(
            ((self._prep, prepare),
             *self._issue_and_finish(disp)),
            parts, done, lambda: fused,
        )

    def _note_issue(self, dt: float) -> None:
        self.issue_ewma = (
            dt if self.issue_ewma == 0.0 else 0.9 * self.issue_ewma + 0.1 * dt
        )

    def _issue_and_finish(self, disp=None):
        """The issue and finish links of a pipelined dispatch (`_run_chain`
        runs them after the prepare link): ISSUE on the engine thread
        (enqueue kernel launches, no fetch), FINISH on a fetch worker
        (materialize outputs, rare fixups back on the engine thread, then
        the dispatch's `tail`), stats folded in on the engine thread."""
        from gubernator_tpu.ops.engine import (
            finish_check_columns,
            issue_check_columns,
        )

        def issue(prepared):
            with tracing.stage("issue", self.metrics, disp=disp) as st:
                pending = issue_check_columns(self.engine, prepared)
            self._note_issue(st.dt)
            if self.metrics is not None:
                self.metrics.dispatch_launches.labels(path="xla").inc()
            return pending

        def fixup(fn):
            # executes fn on the engine thread; called FROM a fetch thread
            # (never from the engine thread — that would deadlock the
            # single-worker executor)
            return self._exec.submit(fn).result()

        def finish(pending):
            with tracing.stage("fetch", self.metrics, disp=disp) as st:
                rc, delta = finish_check_columns(self.engine, pending, fixup)
                st.note(
                    passes=delta.dispatches, rows=delta.checks,
                    native=delta.native_finished,
                )
            # fire-and-forget, engine thread
            self._exec.submit(self._apply, [delta], disp)
            return rc if disp is None or disp.tail is None else disp.tail(rc)

        return (self._exec, issue), (self._fetch, finish)

    def _apply(self, deltas, disp=None) -> None:
        """Engine-thread tail of a dispatch: fold its stats deltas into the
        engine's (single writer) and refresh the gauges that mirror them.
        Not in the dispatch's budget: its caller has already been answered."""
        with tracing.stage(
            "apply", self.metrics, dispatch=disp.seq if disp else 0
        ):
            for delta in deltas:
                self.engine.stats.merge(delta)
            # a tiered dispatch's merge (ops/engine.fault_ahead) finished
            # before the passes this dispatch has just fetched: its sidecar
            # goes to the shadow here unless a later job took it first, so
            # an engine gone quiet holds none
            _drain_sidecars(self.engine)
            if self.metrics is not None:
                self.metrics.observe_engine(self.engine.stats)
                # GLOBAL batches ride the pipeline too: without this the
                # queue-length gauge would only ever be observed post-
                # drain (sync_global) and read 0 forever
                gs = getattr(self.engine, "global_stats", None)
                if gs is not None:
                    self.metrics.observe_global(gs)

    def _observe_shard_stages(self) -> None:
        """Fold what the mesh engine counted since the last call into the
        daemon's counters: the bytes it moved across the host↔device
        boundary (gubernator_tpu_wire_bytes_total, so bytes/decision is
        scrapeable rather than bench-computed) and the rows its exchange
        capacity-dropped. The host stages themselves (shard_route,
        shard_pack | wire_pack, shard_put, shard_unroute, wire_decode) are
        tracing.stage parts of `put` and `fetch`, timed where the work
        happens (parallel/sharded.py)."""
        wtake = getattr(self.engine, "take_wire_deltas", None)
        if wtake is not None:
            for direction, nbytes in wtake().items():
                if nbytes > 0:
                    self.metrics.wire_bytes.labels(direction=direction).inc(
                        nbytes
                    )
        otake = getattr(self.engine, "take_a2a_overflow_delta", None)
        if otake is not None:
            rows = otake()
            if rows > 0:
                self.metrics.a2a_overflow.inc(rows)

    async def check_columns(
        self, cols: RequestColumns, now_ms: Optional[int] = None,
        done=None, disp=None, counted=None,
    ) -> ResponseColumns:
        """The serial path: the whole check, and the dispatch's `tail`, is one
        engine-thread job, a chain of one link. `done` and `counted` as in
        `check`."""

        def run(_):
            rc = self.engine.check_columns(cols, now_ms=now_ms)
            if self.metrics is not None:
                self.metrics.dispatch_launches.labels(path="xla").inc()
                self._observe_shard_stages()
                self.metrics.observe_engine(self.engine.stats)
                gs = getattr(self.engine, "global_stats", None)
                if gs is not None:
                    self.metrics.observe_global(gs)
            return rc if disp is None or disp.tail is None else disp.tail(rc)

        return await self._run_chain(
            ((self._exec, run),), counted or [cols], done
        )

    async def install_columns(self, **kw) -> int:
        loop = asyncio.get_running_loop()

        def run():
            n = self.engine.install_columns(**kw)
            if self.metrics is not None:
                self.metrics.observe_engine(self.engine.stats)
            return n

        return await loop.run_in_executor(self._exec, run)

    async def sync_global(self) -> None:
        """One collective GLOBAL sync (mesh engines): drain pending hits
        through the all_gather/aggregate/install step, serialized onto the
        engine thread like every other table mutation. Metric observation
        happens HERE (on the engine thread) so observe_global's read-modify-
        write of its delta baseline is never concurrent with the dispatch
        path's."""
        loop = asyncio.get_running_loop()

        def run():
            with tracing.stage("global_sync", self.metrics) as st:
                self.engine.sync()
            if self.metrics is not None:
                self.metrics.global_send_duration.observe(st.dt)
                self.metrics.observe_global(self.engine.global_stats)

        await loop.run_in_executor(self._exec, run)

    async def live_count(self) -> int:
        """Table live-key count, serialized onto the engine thread — reading
        engine.table from another thread races the donated-buffer dispatch.
        The count is made on the device; one integer comes back."""
        loop = asyncio.get_running_loop()

        def run():
            with tracing.stage("live_count", self.metrics):
                return self.engine.live_count()

        return await loop.run_in_executor(self._exec, run)

    async def table_telemetry(self, now_ms: Optional[int] = None):
        """One background table-telemetry scan (ops/telemetry.py), split
        like a serving dispatch: the LAUNCH runs on the engine thread (the
        scan must read a coherent table — every mutation is single-writer
        there, and the enqueue costs microseconds), the FETCH runs on a
        dedicated telemetry thread so the device streams the table WHILE
        the engine thread keeps issuing serving dispatches. The scan is
        never on the serving path; its only engine-thread cost is the
        launch."""
        from gubernator_tpu.ops.telemetry import finish_scan

        loop = asyncio.get_running_loop()
        if self._telemetry is None:
            self._telemetry = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="telemetry"
            )

        # the bytes the scan streams ride on both spans: what its time is
        # set against (the whole table goes through the device once)
        nbytes = int(self.engine.table.rows.nbytes)

        def launch():
            with tracing.stage("scan_launch", self.metrics, table_bytes=nbytes):
                return self.engine.telemetry_begin(now_ms)

        def fetch(pending):
            with tracing.stage("scan_fetch", self.metrics, table_bytes=nbytes):
                return finish_scan(pending)

        pending = await loop.run_in_executor(self._exec, launch)
        return await loop.run_in_executor(self._telemetry, fetch, pending)

    async def snapshot(self) -> np.ndarray:
        loop = asyncio.get_running_loop()
        return await loop.run_in_executor(self._exec, self.engine.snapshot)

    # ------------------------------------------------------------- tiering

    async def tier_demote_idle(
        self, idle_ms: int, max_rows: int = 1 << 16, now_ms=None, sink=None
    ):
        """One demote-on-idle sweep (gubernator_tpu/tier/): extract rows
        idle past the horizon, tombstone them out of HBM AND hand them to
        `sink(fps, rows, now)` (the shadow's `offer`) in ONE engine-thread
        job, stage `tier_sweep` — no decide can interleave between the
        read, the removal and the shadow's append, so the demoted copy is
        exactly the state that left the table and a miss path that runs
        next finds it. Returns (now_ms, fps, canonical full rows). Crash
        ordering: a death after the tombstone loses nothing the delta log
        doesn't still hold — restart replays the row back (no tombstone
        frame was written yet), which is the conservative direction."""
        loop = asyncio.get_running_loop()

        def run():
            from gubernator_tpu.ops.engine import ms_now

            eng = self.engine
            now = now_ms if now_ms is not None else ms_now()
            _drain_sidecars(eng)
            with tracing.stage("tier_sweep", self.metrics) as st:
                fps, slots = eng.extract_idle(now, idle_ms, max_rows)
                st.note(rows=int(fps.shape[0]))
                if fps.shape[0] == 0:
                    return now, fps, np.empty((0, 16), dtype=np.int32)
                eng.tombstone_fps(fps)
                # canonical rows at the shadow boundary (the one
                # cross-layout conversion point, ops/layout.py)
                full = np.asarray(eng.table.layout.unpack(slots))
                if sink is not None:
                    sink(fps, full, now)
            return now, fps, full

        return await loop.run_in_executor(self._exec, run)

    def tier_drain_sync(self) -> None:
        """Run the tiered engine's `drain_sidecars` as an engine-thread job
        and wait for it (any thread but the engine's and the loop's): what
        closes the shadow flushes behind it (`TierManager.close`)."""
        self._exec.submit(_drain_sidecars, self.engine).result()

    # ------------------------------------------------- incremental checkpoint
    # (service/checkpoint.py) — split like telemetry: take+launch atomically
    # on the engine thread, fetch on a dedicated lazy thread so the extract
    # streams off-device WHILE serving dispatches keep issuing.

    # extract programs launched for checkpoint epochs (/v1/debug/pipeline
    # "checkpoint"); a default on the class, so that no line above the
    # dispatch chain's functions moves (a moved line re-keys every decide
    # program in the compile cache: PERF.md, PR 33)
    ckpt_extracts = 0

    async def checkpoint_extract(self, now_ms: Optional[int] = None):
        """One checkpoint epoch's dirty-block extract: (epoch, gids, fps,
        slots). The tracker take() and the extract LAUNCH run in one
        engine-thread job — the ordering contract that makes every
        mark→mutate pair land wholly inside one epoch (ops/checkpoint.py)."""
        loop = asyncio.get_running_loop()

        def begin():
            with tracing.stage("ckpt_launch", self.metrics) as st:
                tracker = self.engine.ckpt
                epoch, gids = tracker.take()
                st.note(blocks=int(gids.shape[0]))
                if gids.shape[0] == 0:
                    return epoch, gids, None
                return epoch, gids, self.engine.checkpoint_begin(gids, now_ms)

        def fetch(pending):
            with tracing.stage("ckpt_fetch", self.metrics):
                return self.engine.checkpoint_finish(pending)

        epoch, gids, pending = await loop.run_in_executor(self._exec, begin)
        if pending is None:
            width = self.engine.table.layout.F
            return (
                epoch, gids,
                np.empty(0, dtype=np.int64),
                np.empty((0, width), dtype=np.int32),
            )
        # device programs launched: the local engine's pending is one entry
        # a grid (ops/checkpoint.extract_begin), a mesh engine's one step
        self.ckpt_extracts += len(pending) if isinstance(pending, list) else 1
        fps, slots = await loop.run_in_executor(
            self._ckpt_thread(), fetch, pending
        )
        return epoch, gids, fps, slots

    def _ckpt_thread(self) -> ThreadPoolExecutor:
        if self._ckpt is None:
            self._ckpt = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="ckpt"
            )
        return self._ckpt

    async def checkpoint_write(self, stage_name: str, fn):
        """Run a checkpoint's disk work (`ckpt_append`: a frame's CRC, write
        and fsync; `ckpt_base`: a compaction's snapshot write and log
        reset) on the checkpoint thread, timed as that stage: the thread
        that fetched the rows writes them, and neither the event loop nor
        the default executor's workers wait on a disk."""
        def run():
            with tracing.stage(stage_name, self.metrics):
                return fn()

        return await asyncio.get_running_loop().run_in_executor(
            self._ckpt_thread(), run
        )

    async def checkpoint_warm(self) -> None:
        """Compile the extract's programs before the door opens (engine
        thread: it reads the table): every grid the tracker's geometry can
        fill, through the engine's own begin/finish, so that a checkpoint
        epoch under load compiles nothing."""
        from gubernator_tpu.ops.checkpoint import EXTRACT_GRIDS

        def run():
            tracker = self.engine.ckpt
            total = tracker.n_shards * tracker.nblk
            for width in EXTRACT_GRIDS:
                n = min(width, total)
                self.engine.checkpoint_finish(self.engine.checkpoint_begin(
                    np.arange(n, dtype=np.int64), 0
                ))
                if n == total:
                    break  # a table this small never fills a wider grid

        await asyncio.get_running_loop().run_in_executor(self._exec, run)

    async def checkpoint_snapshot(self):
        """(full table rows, epoch, slot layout) read atomically on the
        engine thread — the compaction input (rows coherent with the epoch
        counter AND the layout those bytes are in)."""
        loop = asyncio.get_running_loop()

        def run():
            tracker = self.engine.ckpt
            return (
                self.engine.snapshot(),
                tracker.epoch if tracker is not None else 0,
                self.engine.table.layout,
            )

        return await loop.run_in_executor(self._exec, run)

    # ---------------------------------------------------------- handoff ops
    # All three mutate (or scan state coherent with) the device table, so
    # they serialize onto the engine thread like every dispatch.

    async def extract_live(self, now_ms: Optional[int] = None):
        loop = asyncio.get_running_loop()
        return await loop.run_in_executor(
            self._exec, lambda: self.engine.extract_live(now_ms)
        )

    async def merge_rows(
        self, fps: np.ndarray, slots: np.ndarray,
        now_ms: Optional[int] = None, layout=None,
    ) -> int:
        loop = asyncio.get_running_loop()
        return await loop.run_in_executor(
            self._exec,
            lambda: self.engine.merge_rows(fps, slots, now_ms, layout=layout),
        )

    async def tombstone_fps(self, fps: np.ndarray) -> int:
        loop = asyncio.get_running_loop()
        return await loop.run_in_executor(
            self._exec, lambda: self.engine.tombstone_fps(fps)
        )

    async def read_state(self, fps: np.ndarray):
        """(found, full-width slots) stored-state read — engine thread for
        a coherent table view (the GLOBAL broadcast aux source)."""
        loop = asyncio.get_running_loop()
        return await loop.run_in_executor(
            self._exec, lambda: self.engine.read_state(fps)
        )

    async def read_state_raw(self, fps: np.ndarray):
        """(found, slots, layout) stored-state read in the table's OWN slot
        layout — the region-sync sender's staging read. The layout is
        captured inside the same engine-thread job as the gather, so a
        concurrent layout migration can never mis-tag the rows."""
        loop = asyncio.get_running_loop()

        def run():
            found, slots = self.engine.read_state(fps, raw=True)
            return found, slots, self.engine.table.layout

        return await loop.run_in_executor(self._exec, run)

    async def apply_region(
        self, fps: np.ndarray, deltas: np.ndarray, cfg: dict,
        sender_slots, sender_layout,
    ) -> int:
        """Apply one received cross-region delta batch through the
        conservative merge (ops/reconcile.apply_region_sync). ONE engine
        job, so the read→reconcile→merge triplet is atomic with respect to
        serving dispatches — no concurrent hit slips between the stored-
        state read and the merge."""
        from gubernator_tpu.ops.reconcile import apply_region_sync

        loop = asyncio.get_running_loop()
        return await loop.run_in_executor(
            self._exec,
            lambda: apply_region_sync(
                self.engine, fps, deltas, cfg, sender_slots, sender_layout
            ),
        )

    async def maybe_grow(self, **kw) -> bool:
        """The maintenance tick's engine-thread job (it counts live keys)."""
        loop = asyncio.get_running_loop()

        def run():
            with tracing.stage("maintenance", self.metrics):
                return self.engine.maybe_grow(**kw)

        return await loop.run_in_executor(self._exec, run)

    def snapshot_sync(self) -> np.ndarray:
        """Synchronous snapshot for shutdown paths with no running loop."""
        return self._exec.submit(self.engine.snapshot).result()

    def close(self) -> None:
        if self._ckpt is not None:
            self._ckpt.shutdown(wait=True)
        if self._telemetry is not None:
            self._telemetry.shutdown(wait=True)
        self._prep.shutdown(wait=True)
        self._fetch.shutdown(wait=True)
        self._exec.shutdown(wait=True)
