"""The raw handler's plain path (service/daemon.py:_serve_plain): an RPC whose
rows are all valid, all local and free of GLOBAL/MULTI_REGION goes parser →
batcher → its dispatch's encoder with no per-row work on the event-loop
thread, on the strength of the summary the native parser reduced over the rows.

Contract: the plain path is a pure perf change. The same bodies answered by
the general path (forced by each thing that disqualifies an RPC) give the
same bytes; the summary equals numpy reductions of the columns; a selection
of rows drops it; the enqueue reads tier, cost and stamp from it exactly as
its scans would."""

import asyncio
import functools
import sys
import threading

import numpy as np
import pytest

from gubernator_tpu import native
from gubernator_tpu.ops.engine import LocalEngine, ms_now
from gubernator_tpu.proto import gubernator_pb2 as pb
from gubernator_tpu.service import batcher as batcher_mod
from gubernator_tpu.service import daemon as daemon_mod
from gubernator_tpu.service.batcher import Batcher
from gubernator_tpu.service.daemon import Daemon
from gubernator_tpu.service.runner import _label_counts
from gubernator_tpu.service.wire import (
    RowSummary,
    WireBatch,
    subset_wire,
    wire_batch_from_wire,
)
from gubernator_tpu.types import (
    PRIORITY_MASK,
    PRIORITY_SHIFT,
    Algorithm,
    Behavior,
    PeerInfo,
)

from tests.cluster import daemon_config

pytestmark = pytest.mark.skipif(
    native.load() is None, reason="native toolchain unavailable"
)

NOW = ms_now()  # every clock the answers can see is pinned to this


def async_test(fn):
    @functools.wraps(fn)
    def wrapper(*a, **k):
        asyncio.run(fn(*a, **k))

    return wrapper


def req(i: int, **kw) -> "pb.RateLimitReq":
    d = dict(name="pl", unique_key=f"k{i}", hits=1, limit=3 + i,
             duration=60_000, created_at=NOW)
    d.update(kw)
    return pb.RateLimitReq(**d)


def body(items) -> bytes:
    return pb.GetRateLimitsReq(requests=items).SerializeToString()


# ------------------------------------------------------------------ parity
# what sends an RPC down the general path: rows appended to the plain body
# (answered after it, so the plain rows' bytes are a prefix of the answer),
# or the daemon's own state
ERROR_ROW = pb.RateLimitReq(name="pl", hits=1, limit=1)  # no unique_key
TRIGGERS = {
    "error_row": ([ERROR_ROW], None),
    "global_row": ([req(900, behavior=int(Behavior.GLOBAL))], None),
    "multi_region_row": ([req(901, behavior=int(Behavior.MULTI_REGION))], None),
    "force_global": ([], lambda d: setattr(d.conf.behaviors, "force_global", True)),
    "peer_set_owner_self": (
        [], lambda d: d.set_peers([PeerInfo(grpc_address=d.conf.advertise_address)])
    ),
}


def plain_rounds(rc_err_row: bool):
    """Three rounds over the same keys: under the limit, at it, over it
    (OVER_LIMIT rows carry retry_after_ms and feed the counter); stamped and
    unstamped created_at; a leaky row, a priority tier, a lease, a reset.
    `rc_err_row` adds a row the parser passes and the engine refuses (limit
    beyond int32): an error the plain path has to fold in itself."""
    rows = [
        req(0), req(1, hits=2), req(2, created_at=0), req(3, algorithm=1),
        req(4, behavior=2 << PRIORITY_SHIFT),
        req(5, behavior=int(Behavior.DRAIN_OVER_LIMIT), hits=2),
        req(6, algorithm=int(Algorithm.CONCURRENCY_LEASE)),
    ]
    if rc_err_row:
        rows.append(req(7, limit=1 << 40))
    return [rows, rows, rows + [req(8, behavior=int(Behavior.RESET_REMAINING))]]


async def _spawn(window_ms: float = 0.0):
    """`window_ms`: a fixed batch window in place of the adaptive one, so
    that RPCs sent together are one chunk whatever the host's load."""
    conf = daemon_config(http_address="")
    # the owner's async GLOBAL update decides its rows again and counts an
    # OVER_LIMIT one a second time (force_global): not while a test reads
    # the counter, 50 ms after the hits were queued
    conf.behaviors.global_sync_wait_ms = 60_000.0
    if window_ms:
        conf.behaviors.adaptive_batch = False
        conf.behaviors.batch_wait_ms = window_ms
    d = await Daemon.spawn(
        conf, engine=LocalEngine(capacity=8192, wire="compact"),
    )
    d.now_ms = lambda: NOW + 7  # retry_after_ms basis (the general path's)
    return d


async def _over_limit_count(d) -> float:
    """The daemon's OVER_LIMIT counter once the engine thread has folded in
    what it owes (a dispatch's stats follow its answer)."""
    await d.runner.live_count()
    return d.metrics.over_limit_counter._value.get()


@pytest.mark.parametrize("rc_err_row", [False, True], ids=["", "rc_err_row"])
@pytest.mark.parametrize("trigger", sorted(TRIGGERS))
@async_test
async def test_plain_path_bytes_equal_general_path(trigger, rc_err_row, monkeypatch):
    """The same rounds through a daemon that serves them on the plain path
    and through one that is forced onto the general path: the plain rows'
    response bytes are equal, and the counters say which path each took."""
    # the enqueue's stamp, and the plain path's retry_after_ms basis
    monkeypatch.setattr(batcher_mod, "ms_now", lambda: NOW + 7)
    hops = []  # the general path's encode hops: the plain path takes none

    def spy(*a, encode=daemon_mod._encode_counted):
        hops.append(a)
        return encode(*a)

    monkeypatch.setattr(daemon_mod, "_encode_counted", spy)
    extra, arrange = TRIGGERS[trigger]
    d_plain, d_gen = await _spawn(), await _spawn()
    try:
        if arrange is not None:
            arrange(d_gen)
        rounds = plain_rounds(rc_err_row)
        for k, rows in enumerate(rounds):
            got_plain = await d_plain.get_rate_limits_raw(body(rows))
            got_gen = await d_gen.get_rate_limits_raw(body(rows + extra))
            assert got_gen[: len(got_plain)] == got_plain, (trigger, k)
            tail = pb.GetRateLimitsResp.FromString(got_gen[len(got_plain):])
            assert len(tail.responses) == len(extra)
            answers = pb.GetRateLimitsResp.FromString(got_plain).responses
            assert len(answers) == len(rows)
            if rc_err_row:
                assert answers[7].error and not answers[0].error
        assert answers[1].status == pb.OVER_LIMIT  # 3 × 2 hits of limit 4
        assert answers[1].metadata["retry_after_ms"] == str(60_000 - 7)
        assert (d_plain.raw_rpcs, d_plain.plain_rpcs) == (3, 3)
        assert (d_gen.raw_rpcs, d_gen.plain_rpcs) == (3, 0)
        assert len(hops) == 3  # d_gen's
        over = await _over_limit_count(d_plain)
        assert over == await _over_limit_count(d_gen) and over > 0
        pipe = d_plain.debug_pipeline()["daemon"]
        assert pipe == {
            "raw_rpcs": 3, "plain_rpcs": 3, "dispatch_encoded_rpcs": 3,
            "summary_entry_rpcs": 3,
        }
        assert d_gen.debug_pipeline()["daemon"]["summary_entry_rpcs"] == 0
        assert d_gen.debug_pipeline()["daemon"]["dispatch_encoded_rpcs"] == 0
    finally:
        await d_plain.close()
        await d_gen.close()


@async_test
async def test_rpcs_that_are_not_plain_are_counted_so():
    """An empty RPC and a cascade RPC (which leaves for the pb path before
    the raw handler) are not plain; the second is not raw either."""
    d = await Daemon.spawn(
        daemon_config(http_address=""),
        engine=LocalEngine(capacity=8192, wire="compact"),
    )
    try:
        assert await d.get_rate_limits_raw(body([])) == b""
        assert (d.raw_rpcs, d.plain_rpcs) == (1, 0)
        casc = req(1)
        casc.cascade.add(name="pl", unique_key="tenant", limit=10, duration=60_000)
        out = pb.GetRateLimitsResp.FromString(
            await d.get_rate_limits_raw(body([req(0), casc]))
        )
        assert len(out.responses) == 2 and len(out.responses[1].cascade) == 1
        assert (d.raw_rpcs, d.plain_rpcs) == (1, 0)
        await d.get_rate_limits_raw(body([req(0)]))
        assert (d.raw_rpcs, d.plain_rpcs) == (2, 1)
    finally:
        await d.close()


# ----------------------------------------------------------------- summary


def random_items(rng, n: int):
    """An item mix that moves every field of the summary."""
    items = []
    for i in range(n):
        behavior = int(rng.choice([0, 0, 0, 1, 2, 8, 16, 32, 4]))
        behavior |= int(rng.integers(0, 4)) << PRIORITY_SHIFT
        if rng.random() < 0.1:
            behavior |= 1 << 9  # a forged cascade level: masked at ingress
        it = pb.RateLimitReq(
            name="" if rng.random() < 0.05 else "sm",
            unique_key="" if rng.random() < 0.05 else f"k{i}",
            hits=int(rng.choice([0, 1, 5, 1 << 19])),
            limit=int(rng.choice([10, 1 << 20, 1 << 40])),
            duration=60_000,
            algorithm=int(rng.integers(0, 5)),
            behavior=behavior,
        )
        if rng.random() < 0.5:
            it.created_at = NOW + int(rng.integers(0, 100))
        items.append(it)
    return items


def reduced(wb: WireBatch, now_ms: int = 0, sent_unstamped=None) -> RowSummary:
    """The summary as numpy reductions of the parsed columns. `now_ms` is
    the clock the parser was handed and `sent_unstamped` the rows the client
    left unstamped, which a stamped column no longer shows."""
    c = wb.cols
    return RowSummary(
        errors=int((c.err != 0).sum()),
        behavior_or=int(np.bitwise_or.reduce(c.behavior, initial=0)),
        leases=int((c.algo == int(Algorithm.CONCURRENCY_LEASE)).sum()),
        unstamped=(
            int((c.created_at == 0).sum()) if sent_unstamped is None
            else sent_unstamped
        ),
        encodable=int(wb.encodable.sum()),
        max_tier=int(((c.behavior >> PRIORITY_SHIFT) & PRIORITY_MASK).max(initial=0)),
        cascades=0,
        stamp_lo=int(c.created_at.min()) if wb.rows else now_ms,
        stamp_hi=int(c.created_at.max()) if wb.rows else now_ms,
        first_fp=int(c.fp[0]) if wb.rows else 0,
        algo_counts=tuple(_label_counts(c.algo)),
    )


@pytest.mark.parametrize("n", [0, 1, 2, 37, 600])
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_summary_equals_numpy_reductions(seed, n):
    rng = np.random.default_rng(seed * 1000 + n)
    items = random_items(rng, n)
    wb = wire_batch_from_wire(body(items))[0]
    assert wb.rows == n
    assert wb.summary == reduced(wb)
    # handed a clock, the parser stamps the rows and reduces what it serves
    sent_unstamped = sum(not it.created_at for it in items)
    wb = wire_batch_from_wire(body(items), NOW + 5)[0]
    assert wb.summary == reduced(wb, NOW + 5, sent_unstamped)
    assert wb.summary.stamped and (wb.cols.created_at != 0).all()
    assert wb.all_encodable == bool(wb.encodable.all())
    assert wb.summary.behavior_or < 256  # client-facing bits only


def test_summary_edges_all_and_none():
    """All rows unstamped / none; the cascade count sends a batch to the pb
    path; the raw parser's tuple ends with the summary."""
    stamped = wire_batch_from_wire(body([req(i) for i in range(5)]))[0]
    assert stamped.summary == RowSummary(
        0, 0, 0, 0, 5, 0, 0, NOW, NOW, int(stamped.cols.fp[0]), (5, 0, 0, 0, 0, 0)
    )
    bare = wire_batch_from_wire(body([req(i, created_at=0) for i in range(5)]))[0]
    assert bare.summary.unstamped == 5 and not bare.summary.stamped
    casc = req(1)
    casc.cascade.add(name="pl", unique_key="t", limit=10, duration=60_000)
    data = body([req(0), casc])
    assert wire_batch_from_wire(data) is None
    raw = native.load().parse_get_rate_limits(data)
    assert RowSummary(*raw[-1]).cascades == 1


def test_subset_wire_drops_the_summary():
    rng = np.random.default_rng(7)
    wb = wire_batch_from_wire(body(random_items(rng, 20)))[0]
    assert wb.summary is not None
    sub = subset_wire(wb, np.array([1, 3, 5]))
    assert sub.summary is None and sub.rows == 3
    assert sub.all_encodable == bool(wb.encodable[[1, 3, 5]].all())


# ----------------------------------------------------------------- enqueue


class EchoRunner:
    """Answers every chunk at once; keeps the payloads it was handed."""

    def __init__(self):
        self.payloads = []

    async def check_wire(self, payloads, now_ms=None, disp=None, done=None):
        from gubernator_tpu.service.wire import empty_response_columns

        self.payloads.extend(payloads)
        done(empty_response_columns(sum(p.rows for p in payloads)), None, False)


@pytest.mark.parametrize("stamps", ["none", "some", "all"])
@async_test
async def test_enqueue_reads_the_summary_as_it_scans(stamps, monkeypatch):
    """Tier, cost and the created_at stamp of an enqueue: from the summary
    and from the scans of a summary-less payload, the same."""
    entries = []

    class Spy(batcher_mod._Entry):
        def __init__(self, *a):
            super().__init__(*a)
            entries.append(self)

    monkeypatch.setattr(batcher_mod, "_Entry", Spy)
    rng = np.random.default_rng(11)
    items = [it for it in random_items(rng, 600) if it.name and it.unique_key]
    for i, it in enumerate(items):
        if stamps == "none" or (stamps == "some" and i % 3):
            it.ClearField("created_at")
        else:
            it.created_at = NOW + i % 50
    wb = wire_batch_from_wire(body(items), NOW + 3)[0]  # stamped by the parser
    bare = wire_batch_from_wire(body(items))[0]  # parsed with no clock
    assert (wb.summary.unstamped == 0) == (stamps == "all")
    assert (wb.summary.unstamped == wb.rows) == (stamps == "none")
    assert wb.summary.stamped and bare.summary.stamped == (stamps == "all")
    runner = EchoRunner()
    b = Batcher(runner, batch_wait_ms=0.0, workers=1)
    try:
        await b.check(wb, now_ms=NOW + 999)  # nothing is left to stamp
        await b.check(bare._replace(summary=None), now_ms=NOW + 3)
        await b.check(bare, now_ms=NOW + 3)
    finally:
        await b.drain()
    assert b.summary_entries == 1 + (stamps == "all")
    with_summary, scanned, late = entries
    for e in (scanned, late):
        assert (
            with_summary.tier, with_summary.cost, with_summary.rows,
            with_summary.bucket, with_summary.stamp_lo, with_summary.stamp_hi,
        ) == (e.tier, e.cost, e.rows, e.bucket, e.stamp_lo, e.stamp_hi)
    assert with_summary.cost > with_summary.rows  # leases cost 2
    a, c, l = (p.cols.created_at for p in runner.payloads)
    np.testing.assert_array_equal(a, c)
    np.testing.assert_array_equal(a, l)
    assert (a != 0).all()
    # the parser's batch goes to its dispatch as it is: nothing replaced
    assert runner.payloads[0] is wb
    # a batch parsed with no clock is stamped by the enqueue, as columns
    # are, and loses the summary with the column that was rewritten
    assert (runner.payloads[2].summary is None) == (stamps != "all")


# ------------------------------------------- the loop thread's array calls


class ArraySpy:
    """Which threads made the array calls that an enqueue and a dispatch's
    decision count used to make on the event-loop thread: `numpy.full`,
    `numpy.where`, `numpy.bincount` (patched where every module looks them
    up) and `ndarray.min` / `.max` (a method of a built-in type cannot be
    patched: the interpreter's profile hook of the watched thread sees the
    call)."""

    def __init__(self, monkeypatch):
        self.calls = []  # (function, thread id)
        for name in ("full", "where", "bincount"):
            monkeypatch.setattr(np, name, self._wrap(name, getattr(np, name)))

    def _wrap(self, name, real):
        def spy(*a, **k):
            self.calls.append((name, threading.get_ident()))
            return real(*a, **k)

        return spy

    def profile(self, frame, event, arg):
        if event == "c_call" and getattr(arg, "__name__", "") in ("min", "max"):
            owner = getattr(arg, "__self__", None)
            if isinstance(owner, np.ndarray) or getattr(
                arg, "__objclass__", None
            ) is np.ndarray:
                self.calls.append((arg.__name__, threading.get_ident()))

    def on(self, thread_id: int) -> set:
        return {name for name, t in self.calls if t == thread_id}


@pytest.mark.parametrize("rows", [3, 600], ids=["inline_parse", "door_hop"])
@async_test
async def test_a_plain_rpc_makes_no_array_call_on_the_loop_thread(rows, monkeypatch):
    """Plain RPCs, stamped by their client or not, parsed inline or on the
    door pool, with algorithms of every label: between the request bytes
    and the response bytes the loop thread calls none of the array
    functions that the stamp, its range and the decision count used to
    cost it, and every entry was made from the summary. An RPC with an
    error row takes the general path, whose scans the same spy sees."""
    d = await _spawn()
    me = threading.get_ident()
    plain = [
        body([req(i + 1000 * r, created_at=0 if (i + r) % 2 else NOW,
                  algorithm=(i + r) % 5) for i in range(rows)])
        for r in range(6)
    ]
    assert (len(plain[0]) >= d.DOOR_OFFLOAD_BYTES) == (rows == 600)
    try:
        await d.get_rate_limits_raw(plain[0])  # programs compiled, pools up
        spy = ArraySpy(monkeypatch)
        before = dict(d.runner.algo_counts)
        sys.setprofile(spy.profile)
        try:
            outs = await asyncio.gather(
                *(d.get_rate_limits_raw(b) for b in plain[1:])
            )
            on_plain = spy.on(me)
            await d.get_rate_limits_raw(body([req(1), ERROR_ROW]))
            on_general = spy.on(me) - on_plain
        finally:
            sys.setprofile(None)
        assert on_plain == set(), on_plain
        assert {"where", "min", "max", "bincount"} <= on_general, on_general
        for out in outs:
            assert len(pb.GetRateLimitsResp.FromString(out).responses) == rows
        pipe = d.debug_pipeline()
        assert pipe["daemon"]["plain_rpcs"] == 6
        assert pipe["daemon"]["summary_entry_rpcs"] == 6
        assert pipe["daemon"]["raw_rpcs"] == 7
        # every decision of the five RPCs, by label, and the general RPC's
        # one valid row (its error row reaches no dispatch)
        grown = {k: v - before[k] for k, v in pipe["runner"]["algo_counts"].items()}
        assert grown == {
            "token_bucket": rows + 1, "leaky_bucket": rows, "gcra": rows,
            "sliding_window": rows, "concurrency_lease": rows, "invalid": 0,
        }
    finally:
        await d.close()


@pytest.mark.parametrize("off_ms,dispatches", [(400, 1), (600, 2), (-600, 2)])
@async_test
async def test_a_chunk_is_cut_on_the_summarys_stamp_range(off_ms, dispatches):
    """`_form_chunk` keeps a chunk's stamps inside the compact wire's ±511
    ms from the entries' `stamp_lo` / `stamp_hi`, which for a plain RPC are
    the summary's: an RPC the parser stamped at request entry, then one
    whose client stamped it up to `off_ms` away. An entry that would take
    the chunk's stamps 512 ms apart starts the next chunk, and both ride
    the fused staging."""
    d = await _spawn(window_ms=200.0)  # its clock: NOW + 7
    try:
        await d.get_rate_limits_raw(body([req(0, created_at=0)]))
        b0 = d.batcher.debug()
        rpcs = [
            body([req(10 + i, created_at=0) for i in range(4)]),
            body([req(20, created_at=NOW + 7 + off_ms),
                  req(21, created_at=NOW + 7 + off_ms * 7 // 8)]),
        ]
        entries = []
        real = batcher_mod._Entry

        class Spy(real):
            def __init__(self, *a):
                super().__init__(*a)
                entries.append((self.stamp_lo, self.stamp_hi))

        batcher_mod._Entry = Spy
        try:
            tasks = []
            for data in rpcs:  # enqueued in this order, in one window
                n0 = d.plain_rpcs
                tasks.append(asyncio.ensure_future(d.get_rate_limits_raw(data)))
                while d.plain_rpcs == n0:
                    await asyncio.sleep(0)
            outs = await asyncio.gather(*tasks)
        finally:
            batcher_mod._Entry = real
        lo, hi = sorted((NOW + 7 + off_ms, NOW + 7 + off_ms * 7 // 8))
        assert entries == [(NOW + 7, NOW + 7), (lo, hi)]
        b1 = d.batcher.debug()
        grew = {k: b1[k] - b0[k] for k in (
            "dispatches", "fused_dispatches", "wire_fallbacks", "column_dispatches"
        )}
        assert grew == {
            "dispatches": dispatches, "fused_dispatches": dispatches,
            "wire_fallbacks": 0, "column_dispatches": 0,
        }
        for out, n in zip(outs, (4, 2)):
            got = pb.GetRateLimitsResp.FromString(out).responses
            assert len(got) == n and not any(r.error for r in got)
    finally:
        await d.close()
