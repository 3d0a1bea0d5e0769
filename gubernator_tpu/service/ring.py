"""Device-resident request ring — the always-on serving loop's front end.

The dispatch round-trip is the last fixed cost the serving plane pays per
flush: every coalesced batch walks the full host dispatch machinery
(executor hop → XLA launch → fetch) even when the device is idle and the
next batch is already parsed. On a real TPU the fix is a PERSISTENT serving
kernel fed by a fixed ring of compact wire-grid slots in device memory: the
host DMAs a packed (5, B+1) ingress grid into slot `t % S`, publishes a
sequence word, and the always-running kernel picks the slot up without any
launch round-trip; results come back through a per-slot egress fence the
host polls. This module is the FUNCTIONAL EMULATION of that protocol on
the CPU build — it drives the exact same runner surface
(`EngineRunner.check_wire`) the direct path drives, so responses are
byte-identical by construction, while exercising the full ring protocol:

* **slot claim / publish ordering** — a submitter claims ticket `t`
  (slot `t % S`) under the submit lock, stages the payload into the slot,
  and only THEN publishes `seq_in[slot] = t + 1` — the store fence that
  makes a published slot's payload visible before its sequence word, the
  ordering a device ring needs for the kernel's poll to be race-free;
* **sequence-number fencing** — the consumer checks `seq_in[slot] == t+1`
  before touching a slot and publishes `seq_out[slot] = t + 1` only after
  the result is materialized; a submitter's result wait is exactly the
  egress-fence poll;
* **bounded backpressure** — when all S slots hold published-but-unconsumed
  batches, submit WAITS (no drops, FIFO ticket order preserved) until the
  consumer retires the oldest slot;
* **drain on shutdown** — `drain()` stops intake, lets every published
  ticket complete in order, and only then parks the serving loop (zero
  loss; tests/test_request_ring.py pins the contract).

Consumption is strictly in ticket order, but the finish half of each
dispatch overlaps the next ticket's issue through the runner's own
prepare/issue/finish pipeline —
the ring serializes LAUNCH ORDER, not completion latency.

The CONSUME side has two tiers behind GUBER_RING_ISSUE (docs/latency.md
"Launch budget"):

* **host** — the original loop: one runner dispatch (one XLA launch) per
  published slot. The CPU default and the byte-parity oracle.
* **fused** — the device-resident drain (ops/ring_drain.py): slots and
  fence words live in device buffers, and ONE jitted while_loop launch
  decides up to GUBER_RING_DRAIN_K consecutively published slots with the
  donated table in the carry, amortizing the launch round-trip K×. Slots
  the fused path can't take (duplicate keys, non-encodable rows, chunks
  wider than the slot) ride the per-slot host path in ticket order —
  byte-identical either way. The TPU default.

Knobs: GUBER_RING_ENABLE turns the plane on (service/daemon.py routes
all-wire flushes here), GUBER_RING_SLOTS sizes the ring, GUBER_RING_ISSUE
picks the consume tier, GUBER_RING_DRAIN_K bounds slots per fused launch,
GUBER_RING_SLOT_WIDTH fixes the device slot width (0 = auto-size to the
first fused chunk). Metrics:
gubernator_tpu_dispatch_launches_total{path="ring"|"fused"|"xla"} splits
launch counts by feed path, gubernator_tpu_ring_drain_slots records
published slots retired per fused launch (the scrapeable amortization
factor), gubernator_tpu_ring_occupancy gauges published-but-unconsumed
slots, and the ring_put / ring_poll stage_duration labels time the
submit-side staging and the egress-fence wait.
"""

from __future__ import annotations

import asyncio
import time
from typing import List, Optional, Tuple

import numpy as np

from gubernator_tpu import tracing
from gubernator_tpu.ops.batch import ResponseColumns


class RingClosed(RuntimeError):
    """Raised to a submitter racing drain(): the caller (Batcher._dispatch)
    falls back to the direct dispatch path — no request is lost."""


class RequestRing:
    """Fixed ring of S request slots with sequence-number fencing.

    `seq_in` / `seq_out` are the ingress/egress fence words — int64 arrays
    indexed by slot, exactly the layout the device ring keeps resident in
    HBM (docs/latency.md "Dispatch budget"). Slot `t % S` carries ticket
    `t`; fence value `t + 1` (never 0, so an unused slot is unambiguous).
    """

    def __init__(self, runner, slots: int = 64, metrics=None,
                 issue_mode: str = "host", drain_k: int = 8,
                 slot_width: int = 0):
        if slots < 2:
            raise ValueError("RequestRing needs at least 2 slots")
        if issue_mode not in ("host", "fused"):
            raise ValueError(
                f"GUBER_RING_ISSUE must be host|fused, got {issue_mode!r}"
            )
        if drain_k < 1:
            raise ValueError("GUBER_RING_DRAIN_K must be >= 1")
        self.runner = runner
        self.slots = int(slots)
        self.metrics = metrics
        self.issue_mode = issue_mode
        self.drain_k = int(min(drain_k, slots))
        # fixed device slot width (rows); 0 = auto-size to the first fused
        # chunk's padded size (wider chunks then ride the host path)
        self.slot_width = int(slot_width)
        self._dring = None  # ops/ring_drain.DeviceRing, fused tiers only
        self.seq_in = np.zeros(self.slots, dtype=np.int64)
        self.seq_out = np.zeros(self.slots, dtype=np.int64)
        # slot payload staging (the emulation's stand-in for the DMA'd
        # wire grids): (parts, disp) per slot, cleared on consume
        self._staged: List[Optional[Tuple[list, object]]] = (
            [None] * self.slots
        )
        self._head = 0  # next ticket to claim (== tickets published)
        self._consumed = 0  # tickets fully retired (seq_out published)
        self._done = {}  # ticket -> result future (the egress poll)
        self._lock: Optional[asyncio.Lock] = None
        self._published: Optional[asyncio.Event] = None
        self._space: Optional[asyncio.Event] = None
        self._drained: Optional[asyncio.Event] = None
        self._issue_task: Optional[asyncio.Task] = None
        self._finish_task: Optional[asyncio.Task] = None
        self._inorder: Optional[asyncio.Queue] = None
        self._closed = False
        # introspection counters (/v1/debug/pipeline)
        self.launches = 0  # tickets retired through the ring
        self.fallbacks = 0  # non-fusable slots that rode the columns path
        self.backpressure_waits = 0  # submits that found the ring full
        self.max_occupancy = 0
        # fused-tier counters (/v1/debug/pipeline)
        self.drain_launches = 0  # fused drain launches (XLA launches)
        self.drained_slots = 0  # tickets retired by fused drains
        self.host_slots = 0  # fused-ineligible tickets (per-slot path)

    # ------------------------------------------------------------ lifecycle
    def _ensure_started(self) -> None:
        if self._lock is not None:
            return
        loop = asyncio.get_running_loop()
        self._lock = asyncio.Lock()
        self._published = asyncio.Event()
        self._space = asyncio.Event()
        self._drained = asyncio.Event()
        self._inorder = asyncio.Queue()
        consume = (
            self._issue_loop if self.issue_mode == "host"
            else self._issue_loop_fused
        )
        self._issue_task = loop.create_task(consume(), name="ring-issue")
        self._finish_task = loop.create_task(self._finish_loop(),
                                             name="ring-finish")

    def _set_occupancy(self) -> None:
        occ = self._head - self._consumed
        if occ > self.max_occupancy:
            self.max_occupancy = occ
        if self.metrics is not None:
            self.metrics.ring_occupancy.set(occ)

    # -------------------------------------------------------------- submit
    async def submit(self, parts, disp=None) -> ResponseColumns:
        """Claim a ticket, stage the payload, publish the ingress fence,
        and poll the egress fence for the coalesced response. `parts` is
        the all-WireBatch chunk the batcher formed — the same value the
        direct path hands `runner.check_wire`, which is what makes the two
        paths byte-identical."""
        self._ensure_started()
        if self._closed:
            raise RingClosed("request ring is draining")
        t0 = time.perf_counter()
        async with self._lock:
            ticket = self._head
            # bounded backpressure: every slot published-but-unconsumed →
            # wait for the serving loop to retire the oldest (FIFO under
            # the lock: later submitters queue behind this one)
            while not self._closed and (
                ticket - self._consumed >= self.slots
            ):
                self.backpressure_waits += 1
                self._space.clear()
                await self._space.wait()
            if self._closed:
                raise RingClosed("request ring is draining")
            self._head = ticket + 1
            slot = ticket % self.slots
            fut = asyncio.get_running_loop().create_future()
            self._done[ticket] = fut
            # STAGE before PUBLISH — the store-fence ordering: the payload
            # must be slot-resident before seq_in makes it claimable
            self._staged[slot] = (parts, disp)
            self.seq_in[slot] = ticket + 1
            self._published.set()
        self._set_occupancy()
        # both ring legs are waits (for the lock and a slot, then for the
        # serving loop), not work: samples and child spans only
        span = disp.span if disp is not None else None
        t1 = time.perf_counter()
        tracing.observe("ring_put", self.metrics, t1 - t0, span)
        # egress-fence poll: resolve when the serving loop publishes
        # seq_out[slot] == ticket + 1
        try:
            rc = await fut
        finally:
            self._done.pop(ticket, None)
        tracing.observe("ring_poll", self.metrics, time.perf_counter() - t1, span)
        return rc

    # ------------------------------------------------------- serving loop
    async def _dispatch(self, parts, disp):
        """One slot's dispatch: the exact runner surface the direct path
        drives. Non-fusable chunks (a non-encodable row, created_at skew)
        are staged as columns inside the same runner dispatch, same as for
        Batcher._dispatch; `fallbacks` counts them."""

        def note(_rc, _exc, fused):
            if not fused:
                self.fallbacks += 1

        return await self.runner.check_wire(
            parts, disp=disp, launch_path="ring", done=note
        )

    async def _issue_loop(self) -> None:
        """Walk tickets strictly in order (the device ring's slot
        walk): check the ingress fence, lift the payload, and start its
        dispatch. Completion ordering is the finish loop's job."""
        t = 0
        loop = asyncio.get_running_loop()
        while True:
            while t >= self._head:
                if self._closed:
                    await self._inorder.put(None)  # finish-loop sentinel
                    return
                self._published.clear()
                if t < self._head:  # raced a publish
                    break
                await self._published.wait()
            slot = t % self.slots
            # ingress fence: the slot must carry exactly this ticket
            assert int(self.seq_in[slot]) == t + 1, (
                f"ring fence violation: slot {slot} has seq "
                f"{int(self.seq_in[slot])}, expected {t + 1}"
            )
            parts, disp = self._staged[slot]
            self._staged[slot] = None
            await self._inorder.put(
                ([t], loop.create_task(self._dispatch(parts, disp)))
            )
            t += 1

    # ------------------------------------------------- fused consume tier
    def _prepare_slot(self, parts, disp):
        """Prep-pool half of one fused slot: assemble the fixed-width wire
        grid + PendingCheck (ops/engine.prepare_ring_slot). None routes
        the chunk to the per-slot host path. Auto-sizes the device ring on
        the first fusable chunk when GUBER_RING_SLOT_WIDTH=0."""
        from gubernator_tpu.ops.engine import _pad_size, prepare_ring_slot

        engine = self.runner.engine
        if self._dring is None and self.slot_width == 0:
            # first fused chunk sizes the slots: wide enough for its own
            # padded dispatch, floored so ordinary coalesced flushes fit
            n = sum(p.cols.fp.shape[0] for p in parts)
            self.slot_width = max(64, _pad_size(n))
        with tracing.stage("put", self.metrics, disp=disp) as st:
            prep = prepare_ring_slot(engine, parts, self.slot_width)
            if prep is None:
                st.name = "put_miss"  # as in EngineRunner.check_wire
        if prep is not None:
            self.runner._count_decisions(parts)
        return prep

    def _ensure_dring(self):
        if self._dring is None:
            from gubernator_tpu.ops.ring_drain import DeviceRing

            engine = self.runner.engine
            self._dring = DeviceRing(
                self.slots, self.slot_width, self.drain_k,
                evictees=bool(getattr(engine, "_evictees", False)),
            )
        return self._dring

    async def _fail(self, exc):
        raise exc

    async def _issue_loop_fused(self) -> None:
        """Fused consume loop (GUBER_RING_ISSUE=fused): walk
        tickets strictly in order, group consecutively published fusable
        slots that share the drain graph's static modes (math, cascade),
        and retire each group with ONE device drain launch
        (ops/ring_drain.drain_ring). Launch order across groups — and
        across the interleaved per-slot host dispatches — stays strict
        ticket order, the byte-parity contract; each group's finish
        overlaps the next group's prepare/issue through the runner's fetch
        pool, same as the host tier."""
        t = 0
        loop = asyncio.get_running_loop()
        while True:
            while t >= self._head:
                if self._closed:
                    await self._inorder.put(None)  # finish-loop sentinel
                    return
                self._published.clear()
                if t < self._head:  # raced a publish
                    break
                await self._published.wait()
            # lift every currently published ticket, up to one drain's worth
            todo = []
            while t < self._head and len(todo) < self.drain_k:
                slot = t % self.slots
                assert int(self.seq_in[slot]) == t + 1, (
                    f"ring fence violation: slot {slot} has seq "
                    f"{int(self.seq_in[slot])}, expected {t + 1}"
                )
                parts, disp = self._staged[slot]
                self._staged[slot] = None
                todo.append((t, parts, disp))
                t += 1
            preps = await asyncio.gather(*(
                loop.run_in_executor(
                    self.runner._prep, self._prepare_slot, parts, disp
                )
                for _t, parts, disp in todo
            ))
            i = 0
            while i < len(todo):
                if preps[i] is None:
                    # fused-ineligible: per-slot host dispatch. Awaited in
                    # full (not pipelined) so a following fused drain can
                    # never launch before this earlier ticket's dispatch —
                    # strict launch order is what byte-parity rests on.
                    tk, parts, disp = todo[i]
                    self.host_slots += 1
                    task = loop.create_task(self._dispatch(parts, disp))
                    await asyncio.wait({task})
                    await self._inorder.put(([tk], task))
                    i += 1
                    continue
                j = i + 1
                while (
                    j < len(todo)
                    and preps[j] is not None
                    and preps[j].math == preps[i].math
                    and preps[j].cascade == preps[i].cascade
                    # a slot with shadow fault-backs must HEAD its group:
                    # its promote-merge precedes the whole launch, so any
                    # earlier slot in the same drain would decide against
                    # post-merge state the per-slot path never saw
                    and preps[j].pending.promote is None
                ):
                    j += 1
                group = [preps[x] for x in range(i, j)]
                tickets = [todo[x][0] for x in range(i, j)]
                disp = todo[i][2]  # the group's launch rides its head's
                try:
                    bank, n = await self.runner.drain_ring_issue(
                        self._ensure_dring(), group, tickets[0], disp=disp
                    )
                except Exception as exc:
                    await self._inorder.put(
                        (tickets, loop.create_task(self._fail(exc)))
                    )
                    i = j
                    continue
                self.drain_launches += 1
                self.drained_slots += len(group)
                task = loop.create_task(
                    self.runner.drain_ring_finish(group, bank, n, disp=disp)
                )
                await self._inorder.put((tickets, task))
                i = j

    async def _finish_loop(self) -> None:
        """Retire tickets in order: await each dispatch (one ticket on the
        host/fallback path, a whole drain group on the fused path),
        publish the egress fences, resolve the submitters' polls, free the
        slots."""
        while True:
            item = await self._inorder.get()
            if item is None:
                self._drained.set()
                return
            tickets, task = item
            try:
                rc = await task
            except Exception as exc:  # pragma: no cover - defensive
                results = [exc] * len(tickets)
            else:
                results = rc if isinstance(rc, list) else [rc]
            for t, res in zip(tickets, results):
                slot = t % self.slots
                fut = self._done.get(t)
                if fut is not None and not fut.done():
                    if isinstance(res, Exception):
                        fut.set_exception(res)
                    else:
                        fut.set_result(res)
                self.launches += 1
                # egress fence AFTER the result is materialized — the
                # order the submitter's poll relies on
                self.seq_out[slot] = t + 1
                self._consumed = t + 1
            self._set_occupancy()
            self._space.set()

    # --------------------------------------------------------------- drain
    async def drain(self) -> None:
        """Stop intake and retire every published ticket in order before
        parking the serving loop — zero-loss shutdown. Safe to call with
        nothing ever submitted."""
        self._closed = True
        if self._lock is None:
            return  # never started
        self._published.set()  # wake the issue loop to emit its sentinel
        self._space.set()  # release submitters blocked on backpressure
        await self._drained.wait()
        for task in (self._issue_task, self._finish_task):
            if task is not None and not task.done():
                task.cancel()
                try:
                    await task
                except asyncio.CancelledError:
                    pass

    def debug(self) -> dict:
        """Ring-plane state for /v1/debug/pipeline."""
        return {
            "slots": self.slots,
            "occupancy": self._head - self._consumed,
            "published": self._head,
            "consumed": self._consumed,
            "launches": self.launches,
            "fallbacks": self.fallbacks,
            "backpressure_waits": self.backpressure_waits,
            "max_occupancy": self.max_occupancy,
            "closed": self._closed,
            "issue_mode": self.issue_mode,
            "drain_k": self.drain_k,
            "slot_width": self.slot_width,
            "drain_launches": self.drain_launches,
            "drained_slots": self.drained_slots,
            "host_slots": self.host_slots,
        }
