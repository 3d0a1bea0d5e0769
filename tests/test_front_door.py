"""Serving-plane tests: fused wire→grid parse parity, multi-worker front
door ordering, adaptive batching, and the bounded ring.

Parity contract: the raw byte path (native parse → fused lane staging →
native encode) must be BYTE-IDENTICAL to the pb path (message parse →
columns → pack → dispatch → message encode) for every routing shape —
that's what makes the fused path a pure perf change. GUBER_WIRE_COMPACT=0
(full-width) remains the deeper oracle below both."""

import asyncio
import functools
import time

import numpy as np
import pytest

from gubernator_tpu import native
from gubernator_tpu.ops.batch import RequestColumns, ResponseColumns
from gubernator_tpu.ops.engine import LocalEngine, ms_now
from gubernator_tpu.proto import gubernator_pb2 as pb
from gubernator_tpu.service.batcher import Batcher
from gubernator_tpu.service.daemon import Daemon
from gubernator_tpu.service.wire import (
    WireBatch,
    concat_columns,
    wire_batch_from_wire,
)
from gubernator_tpu.types import Behavior

from tests.cluster import daemon_config

nat = native.load()
pytestmark = pytest.mark.skipif(nat is None, reason="native toolchain unavailable")


def async_test(fn):
    @functools.wraps(fn)
    def wrapper(*a, **k):
        asyncio.run(fn(*a, **k))

    return wrapper


def req(i: int, now: int, **kw) -> "pb.RateLimitReq":
    d = dict(
        name="fd", unique_key=f"k{i}", hits=1, limit=100 + i,
        duration=60_000, created_at=now,
    )
    d.update(kw)
    return pb.RateLimitReq(**d)


def mixed_corpus(now: int):
    """Every fused-path edge in one batch sequence: plain encodable rows,
    error rows, duplicates (unique-fp fallback), and each non-wire-encodable
    field (hits overflow, Gregorian, explicit leaky burst, oversized
    limit)."""
    return [
        # all-encodable, all-unique — the fused fast path
        [req(i, now) for i in range(8)],
        # error rows isolated, batch still served
        [req(0, now), pb.RateLimitReq(unique_key="nn", hits=1, limit=1),
         pb.RateLimitReq(name="nk", hits=1, limit=1), req(3, now)],
        # duplicate keys → host pass planner (sequential same-key semantics)
        [req(7, now), req(7, now), req(9, now)],
        # hits beyond the 18-bit lane budget → full-width fallback
        [req(11, now, hits=1 << 19, limit=1 << 24)],
        # Gregorian duration (behavior bit) → full-width fallback
        [req(12, now, behavior=int(Behavior.DURATION_IS_GREGORIAN),
             duration=4)],  # GregorianDays: end-of-day is call-stable
        # explicit leaky burst → full-width fallback
        [req(13, now, algorithm=1, burst=7, limit=50)],
        # limit beyond int32 → per-item validation error via the fallback
        [req(14, now, limit=1 << 40), req(15, now)],
        # DRAIN/RESET bits ride the wire; GLOBAL/NO_BATCHING are inert
        [req(16, now, behavior=int(Behavior.RESET_REMAINING)),
         req(17, now, behavior=int(Behavior.DRAIN_OVER_LIMIT), hits=0),
         req(18, now, behavior=int(Behavior.NO_BATCHING))],
    ]


async def _global_settled(d, timeout_s: float = 30.0) -> None:
    """On a mesh engine, the GLOBAL hits queued so far have been synced: a
    replica answers from what the last sync tick left it, so two daemons
    given the same RPCs agree only between ticks that both have run (on a
    loaded host one daemon's tick came after the next RPC, the other's
    before)."""
    if not getattr(d.engine, "mesh_global", False):
        return
    deadline = time.monotonic() + timeout_s
    while d.debug_global()["mesh"]["pending"] and time.monotonic() < deadline:
        await asyncio.sleep(0.01)


async def _parity_daemons(corpus, raw_conf, pb_conf, raw_engine=None,
                          pb_engine=None, reset_tol_ms: int = 0):
    """Drive the SAME request sequence through a raw-bytes daemon and a
    pb-path daemon; every response must be byte-identical. `reset_tol_ms`
    relaxes ONLY reset_time (mesh-GLOBAL replica answers re-anchor at each
    daemon's serve clock, so two daemons differ by wall-clock ms — a
    cross-daemon nondeterminism, not a raw/pb divergence; every other field
    still compares exactly)."""
    d_raw = await Daemon.spawn(raw_conf, engine=raw_engine)
    d_pb = await Daemon.spawn(pb_conf, engine=pb_engine)
    try:
        for items in corpus:
            data = pb.GetRateLimitsReq(
                requests=items
            ).SerializeToString()
            raw_bytes = await d_raw.get_rate_limits_raw(data)
            resps = await d_pb.get_rate_limits(list(items))
            await _global_settled(d_raw)
            await _global_settled(d_pb)
            pb_bytes = pb.GetRateLimitsResp(
                responses=resps
            ).SerializeToString()
            if raw_bytes == pb_bytes:
                continue
            raw_msg = pb.GetRateLimitsResp.FromString(raw_bytes)
            diag = (
                f"raw/pb divergence for {items}:\n"
                f"raw={raw_msg}\npb={pb.GetRateLimitsResp(responses=resps)}"
            )
            assert reset_tol_ms > 0, diag
            assert len(raw_msg.responses) == len(resps), diag
            for a, b in zip(raw_msg.responses, resps):
                assert abs(a.reset_time - b.reset_time) <= reset_tol_ms, diag
                a.reset_time = b.reset_time = 0
                assert a == b, diag
        return d_raw, d_pb
    finally:
        await d_raw.close()
        await d_pb.close()


@async_test
async def test_fused_parity_local_compact():
    """Byte-for-byte parity on the compact-wire local engine — the fused
    lane path against the pb path, across encodable, error, duplicate,
    non-encodable and behavior-bit batches."""
    now = ms_now()
    conf = lambda: daemon_config(http_address="")
    d_raw, _ = await _parity_daemons(
        mixed_corpus(now),
        conf(), conf(),
        raw_engine=LocalEngine(capacity=8192, wire="compact"),
        pb_engine=LocalEngine(capacity=8192, wire="compact"),
    )
    # the plain batches actually rode the fused path; the exotic ones fell
    # back — both must have happened for this parity run to mean anything
    assert d_raw.batcher.fused_dispatches > 0
    assert d_raw.batcher.column_dispatches + d_raw.batcher.wire_fallbacks > 0


@async_test
async def test_fused_parity_full_width_oracle():
    """Same corpus with GUBER_WIRE_COMPACT semantics OFF (full-width
    engines): the raw path must still match the pb path byte-for-byte —
    the fused path simply never engages."""
    now = ms_now()
    conf = lambda: daemon_config(http_address="")
    d_raw, _ = await _parity_daemons(
        mixed_corpus(now),
        conf(), conf(),
        raw_engine=LocalEngine(capacity=8192, wire="full"),
        pb_engine=LocalEngine(capacity=8192, wire="full"),
    )
    assert d_raw.batcher.fused_dispatches == 0


@async_test
async def test_fused_parity_sharded_engine():
    """Raw/pb parity through the mesh engine (8-dev virtual CPU mesh,
    GLOBAL served by the collective replica plane standalone): the fused
    path declines mesh engines, and the fallback must stay byte-identical
    — including GLOBAL-behavior rows."""
    now = ms_now()
    corpus = [
        [req(i, now) for i in range(4)],
        [req(5, now, behavior=int(Behavior.GLOBAL)),
         req(6, now), pb.RateLimitReq(name="nk", hits=1, limit=1)],
        [req(5, now, behavior=int(Behavior.GLOBAL), hits=2)],
    ]
    await _parity_daemons(
        corpus,
        daemon_config(engine="sharded", cache_size=4096, http_address=""),
        daemon_config(engine="sharded", cache_size=4096, http_address=""),
        reset_tol_ms=5_000,
    )


@async_test
async def test_fused_parity_force_global():
    """GUBER_FORCE_GLOBAL flips every request to GLOBAL before routing; the
    raw path applies it to the columns only (GLOBAL is kernel-inert, the
    parser lanes stay valid) and must still match the pb path exactly."""
    now = ms_now()

    def conf():
        c = daemon_config(http_address="")
        c.behaviors.force_global = True
        return c

    d_raw, _ = await _parity_daemons(
        [[req(i, now) for i in range(6)], [req(2, now, hits=3)]],
        conf(), conf(),
        raw_engine=LocalEngine(capacity=8192, wire="compact"),
        pb_engine=LocalEngine(capacity=8192, wire="compact"),
    )
    assert d_raw.batcher.fused_dispatches > 0


@async_test
async def test_multi_worker_slicing_order():
    """N front-door workers + concurrent raw requests: every request's
    slice of the coalesced response must line up with ITS items (the limit
    field echoes the request, so a mis-slice is visible immediately)."""
    conf = daemon_config(http_address="")
    conf.behaviors.front_workers = 4
    conf.behaviors.batch_wait_ms = 2.0
    d = await Daemon.spawn(
        conf, engine=LocalEngine(capacity=1 << 15, wire="compact")
    )
    try:
        now = ms_now()
        R, B = 24, 64

        async def one(r: int):
            items = [
                pb.RateLimitReq(
                    name="ord", unique_key=f"r{r}b{i}", hits=1,
                    limit=1000 + r * B + i, duration=60_000, created_at=now,
                )
                for i in range(B)
            ]
            data = pb.GetRateLimitsReq(requests=items).SerializeToString()
            out = pb.GetRateLimitsResp.FromString(
                await d.get_rate_limits_raw(data)
            )
            assert len(out.responses) == B
            for i, resp in enumerate(out.responses):
                assert resp.limit == 1000 + r * B + i, (r, i)
                assert resp.remaining == 1000 + r * B + i - 1, (r, i)

        await asyncio.gather(*(one(r) for r in range(R)))
        # distinct keys, all encodable: the whole run rides the fused path
        assert d.batcher.fused_dispatches > 0
        assert d.batcher.wire_fallbacks == 0
    finally:
        await d.close()


# --------------------------------------------------------- batcher units


def _cols(rows: int, base: int = 0) -> RequestColumns:
    n = rows
    return RequestColumns(
        fp=np.arange(base + 1, base + n + 1, dtype=np.int64),
        algo=np.zeros(n, dtype=np.int32),
        behavior=np.zeros(n, dtype=np.int32),
        hits=np.ones(n, dtype=np.int64),
        limit=np.full(n, 100, dtype=np.int64),
        burst=np.zeros(n, dtype=np.int64),
        duration=np.full(n, 60_000, dtype=np.int64),
        created_at=np.full(n, 1_700_000_000_000, dtype=np.int64),
        err=np.zeros(n, dtype=np.int8),
    )


class StubRunner:
    """Echo runner: gates the FIRST dispatch on an event (simulating a busy
    engine) and records per-dispatch row counts."""

    def __init__(self):
        self.gate: "asyncio.Event | None" = None
        self.dispatch_rows = []

    async def check(self, cols, now_ms=None, disp=None, done=None):
        cols = concat_columns(cols)
        self.dispatch_rows.append(cols.fp.shape[0])
        if self.gate is not None and len(self.dispatch_rows) == 1:
            await self.gate.wait()
        n = cols.fp.shape[0]
        rc = ResponseColumns(
            status=np.zeros(n, dtype=np.int32),
            limit=cols.limit.copy(),
            remaining=cols.limit - cols.hits,
            reset_time=np.zeros(n, dtype=np.int64),
            err=np.zeros(n, dtype=np.int8),
        )
        if done is not None:
            done(rc, None, False)
        return rc


@async_test
async def test_adaptive_window_closes_on_rows():
    """With the engine busy, the adaptive window must close on accumulated
    rows — NOT ride out the (deliberately huge) wall-clock window."""
    runner = StubRunner()
    runner.gate = asyncio.Event()
    b = Batcher(
        runner, batch_wait_ms=2_000.0, coalesce_limit=4096,
        workers=1, adaptive=True, close_rows=128,
    )
    t0 = time.perf_counter()
    first = asyncio.ensure_future(b.check(_cols(16)))
    await asyncio.sleep(0.05)  # worker picked it up and is gated
    rest = [asyncio.ensure_future(b.check(_cols(16, base=100 * (i + 1))))
            for i in range(8)]  # 128 pending rows ≥ close_rows
    await asyncio.sleep(0.05)
    runner.gate.set()
    await asyncio.gather(first, *rest)
    elapsed = time.perf_counter() - t0
    assert elapsed < 1.0, f"window did not close on rows ({elapsed:.2f}s)"
    assert b.adaptive_closes >= 1
    # the 8 backlogged enqueues coalesced rather than dispatching singly
    assert max(runner.dispatch_rows) >= 128
    await b.drain()


@async_test
async def test_adaptive_idle_engine_skips_window():
    """Light load: with no dispatch in flight the window closes
    immediately — a lone request must not pay the batch window."""
    runner = StubRunner()
    b = Batcher(runner, batch_wait_ms=500.0, workers=2, adaptive=True)
    t0 = time.perf_counter()
    await b.check(_cols(4))
    assert time.perf_counter() - t0 < 0.3
    assert b.adaptive_closes >= 1 and b.window_expires == 0
    await b.drain()


@async_test
async def test_bounded_ring_backpressure():
    """Enqueues past max_queue_rows wait for drain progress instead of
    growing the queue without limit."""
    runner = StubRunner()
    runner.gate = asyncio.Event()
    b = Batcher(
        runner, batch_wait_ms=0.1, coalesce_limit=64, workers=1,
        adaptive=True, max_queue_rows=32,
    )
    first = asyncio.ensure_future(b.check(_cols(16)))
    await asyncio.sleep(0.05)  # in flight, engine gated
    second = asyncio.ensure_future(b.check(_cols(32, base=100)))
    await asyncio.sleep(0.02)
    third = asyncio.ensure_future(b.check(_cols(16, base=200)))
    await asyncio.sleep(0.1)
    assert not third.done(), "third enqueue should be backpressured"
    assert b._pending_rows == 32  # only the admitted batch pends
    runner.gate.set()
    await asyncio.gather(first, second, third)
    await b.drain()


@async_test
async def test_queue_gauge_set_once_per_flush():
    """The queue_length gauge is observed per FLUSH, not per enqueue —
    hot-path metric churn at request rates (PR-3 follow-through)."""

    class GaugeSpy:
        def __init__(self):
            self.sets = 0

        def set(self, v):
            self.sets += 1

    class MetricsSpy:
        def __init__(self):
            self.queue_length = GaugeSpy()

        def __getattr__(self, name):
            class _Noop:
                def labels(self, **kw):
                    return self

                def __call__(self, *a):  # metrics.stage_child(stage)
                    return self

                def observe(self, v, exemplar=None):
                    pass

                def inc(self, v=1):
                    pass

            return _Noop()

    runner = StubRunner()
    spy = MetricsSpy()
    b = Batcher(runner, batch_wait_ms=50.0, workers=1, adaptive=True,
                close_rows=1 << 20, metrics=spy)
    futs = [asyncio.ensure_future(b.check(_cols(4, base=10 * i)))
            for i in range(16)]
    await asyncio.gather(*futs)
    await b.drain()
    # 16 enqueues; far fewer flushes — and the gauge only moved per flush
    assert spy.queue_length.sets <= len(runner.dispatch_rows)


@async_test
async def test_runner_check_wire_matches_columns():
    """Engine-level fused parity: runner.check_wire over native parser
    lanes == runner.check over the equivalent columns, field for field."""
    from gubernator_tpu.service.runner import EngineRunner

    now = ms_now()
    items = [req(i, now) for i in range(32)]
    data = pb.GetRateLimitsReq(requests=items).SerializeToString()
    wb, _, _, _ = wire_batch_from_wire(data)
    assert wb.encodable.all()

    r_wire = EngineRunner(LocalEngine(capacity=4096, wire="compact"))
    r_cols = EngineRunner(LocalEngine(capacity=4096, wire="compact"))
    try:
        rc1 = await r_wire.check_wire([wb], now_ms=now)
        assert rc1 is not None, "fused path should engage"
        rc2 = await r_cols.check(wb.cols, now_ms=now)
        for f in ResponseColumns._fields:
            np.testing.assert_array_equal(
                getattr(rc1, f), getattr(rc2, f), err_msg=f
            )
        # a full-width engine takes the columns path, and says so
        r_full = EngineRunner(LocalEngine(capacity=4096, wire="full"))
        paths = []
        rc3 = await r_full.check_wire(
            [wb], now_ms=now, done=lambda _rc, _exc, fused: paths.append(fused)
        )
        assert paths == [False]
        for f in ResponseColumns._fields:
            np.testing.assert_array_equal(
                getattr(rc3, f), getattr(rc2, f), err_msg=f
            )
        r_full.close()
    finally:
        r_wire.close()
        r_cols.close()
