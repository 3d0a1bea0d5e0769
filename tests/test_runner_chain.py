"""A runner dispatch leaves the event loop once and comes back once.

`EngineRunner._run_chain` hands a dispatch from the prep pool to the engine
thread to the fetch pool without a stop on the loop in between; the one
crossing back answers the batcher's callers. These tests hold it to that:
the loop-trip counter, which thread runs which stage, where an exception in
any link ends up, what a chunk the fused staging refuses is answered (and
that a repeated key is not such a chunk), and the stage identity
`dispatch` = put + put_miss + issue + fetch + `dispatch_wait`.
"""

import asyncio
import functools
import threading

import numpy as np
import pytest

from gubernator_tpu import native
from gubernator_tpu.ops import engine as engine_mod
from gubernator_tpu.ops.batch import ResponseColumns
from gubernator_tpu.ops.engine import LocalEngine, ms_now
from gubernator_tpu.proto import gubernator_pb2 as pb
from gubernator_tpu.service.batcher import Batcher
from gubernator_tpu.service.metrics import DaemonMetrics
from gubernator_tpu.service.runner import _ALGO_LABELS, EngineRunner, _label_counts
from gubernator_tpu.service.wire import (
    concat_columns,
    subset_wire,
    wire_batch_from_wire,
)

from tests.test_observability import _stage_sums

pytestmark = pytest.mark.skipif(
    native.load() is None, reason="native toolchain unavailable"
)

STAGES = ("prepare", "issue", "finish")
# the engine functions each stage of the chain calls (runner.py resolves
# them at call time, so a test can stand in front of them)
STAGE_FNS = {
    "prepare": ("prepare_check_wire", "prepare_check_columns"),
    "issue": ("issue_check_columns",),
    "finish": ("finish_check_columns",),
}


def async_test(fn):
    @functools.wraps(fn)
    def wrapper(*a, **k):
        asyncio.run(fn(*a, **k))

    return wrapper


def wire_batch(keys, now, tag="rc", gregorian=()):
    """One parsed RPC of one hit a key. Keys in `gregorian` ask for a
    calendar day (DURATION_IS_GREGORIAN, GregorianDays): rows the compact
    wire cannot carry, so their chunk cannot ride the fused staging."""
    data = pb.GetRateLimitsReq(requests=[
        pb.RateLimitReq(
            name=tag, unique_key=f"k{k}", hits=1, limit=10, created_at=now,
            **({"behavior": 4, "duration": 4} if k in gregorian
               else {"duration": 60_000}),
        )
        for k in keys
    ]).SerializeToString()
    wb = wire_batch_from_wire(data)[0]
    assert wb.encodable.all() == (not gregorian)
    return wb


def new_runner(metrics=None, kind="local"):
    """A runner over the engine a cell runs: `mesh` is four devices as a TPU
    resolves them (cell 3: it takes the parser's lanes and folds the copies
    of a key in its program), `tiered` a table with a shadow behind it
    (cell 7)."""
    if kind == "mesh":
        from gubernator_tpu.parallel import ShardedEngine, make_mesh

        eng = ShardedEngine(
            make_mesh(4), capacity_per_shard=4096, route="device",
            dedup="device", wire="compact",
        )
        assert eng.supports_wire_ingress and eng.folds_copies
        return EngineRunner(eng, metrics)
    eng = LocalEngine(capacity=4096, wire="compact")
    if kind == "tiered":
        from gubernator_tpu.tier import ShadowTable

        eng.attach_shadow(ShadowTable(max_bytes=1 << 22))
    return EngineRunner(eng, metrics)


async def dispatch(runner, path, wb, now, **kw):
    if path == "wire":
        return await runner.check_wire([wb], now_ms=now, **kw)
    return await runner.check(wb.cols, now_ms=now, **kw)


def assert_same(a: ResponseColumns, b: ResponseColumns):
    for f in ResponseColumns._fields:
        got, want = getattr(a, f), getattr(b, f)
        assert got.dtype == want.dtype and got.tobytes() == want.tobytes(), f


@pytest.mark.parametrize(
    "path", ["wire", "wire_split", "columns", "wire_miss", "serial", "mesh_wire"]
)
@async_test
async def test_a_dispatch_is_one_loop_trip(path):
    """Whatever staging serves it, a dispatch's completion is the one
    callback the loop runs for it, and `done` hears which staging it was
    (the passes the fused one issued, 0 for columns) before the awaiting
    coroutine goes on. A mesh keeps a repeated key in its one grid."""
    now = ms_now()
    runner = new_runner(kind="mesh" if path == "mesh_wire" else "local")
    order = []
    try:
        keys = [1, 2, 2, 3] if path in ("wire_split", "mesh_wire") else list(range(8))
        wb = wire_batch(keys, now, gregorian=[3] if path == "wire_miss" else ())

        def done(rc, exc, fused):
            order.append(("done", exc, fused))

        before = runner.loop_trips
        if path == "serial":
            rc = await runner.check_columns(wb.cols, now_ms=now, done=done)
        else:
            rc = await dispatch(
                runner, "columns" if path == "columns" else "wire", wb, now,
                done=done,
            )
        order.append(("resumed", None, None))
        assert runner.loop_trips - before == 1
        fused = {"wire": 1, "wire_split": 2, "mesh_wire": 1}.get(path, 0)
        assert order == [("done", None, fused), ("resumed", None, None)]
        assert rc.status.shape == (len(keys),)
        assert sum(runner.algo_counts.values()) == len(keys)
    finally:
        runner.close()


@pytest.mark.parametrize("path", ["wire", "columns"])
@async_test
async def test_each_stage_runs_on_its_own_pool(path, monkeypatch):
    """prepare on a prep thread, issue on THE engine thread, finish on a
    fetch thread — and the completion on the loop's thread."""
    seen = {}

    def watch(stage, name):
        real = getattr(engine_mod, name)

        def fn(*a, **k):
            seen.setdefault(stage, set()).add(threading.current_thread().name)
            return real(*a, **k)

        monkeypatch.setattr(engine_mod, name, fn)

    for stage, names in STAGE_FNS.items():
        for name in names:
            watch(stage, name)
    now = ms_now()
    runner = new_runner()
    try:
        for i in range(6):
            await dispatch(
                runner, path, wire_batch(range(8 * i, 8 * i + 8), now), now,
                done=lambda *_: seen.setdefault("done", set()).add(
                    threading.current_thread().name
                ),
            )
    finally:
        runner.close()
    assert all(n.startswith("prep") for n in seen["prepare"]), seen
    assert seen["issue"] == {"engine_0"}, seen
    assert all(n.startswith("fetch") for n in seen["finish"]), seen
    assert seen["done"] == {threading.current_thread().name}, seen


@pytest.mark.parametrize("path", ["wire", "columns", "mesh_wire"])
@pytest.mark.parametrize("stage", STAGES)
@async_test
async def test_an_exception_in_any_link_reaches_the_caller(stage, path, monkeypatch):
    """Raised on a worker thread, delivered to every caller of the chunk by
    the dispatch's crossing back; the batcher's slot is freed and the next
    dispatch is served."""
    now = ms_now()
    runner = new_runner(kind="mesh" if path == "mesh_wire" else "local")
    b = Batcher(runner, batch_wait_ms=0.5, workers=2)
    payload = (lambda wb: wb.cols) if path == "columns" else (lambda wb: wb)
    try:
        with monkeypatch.context() as m:
            for name in STAGE_FNS[stage]:
                def boom(*a, _name=name, **k):
                    raise ValueError(f"{_name} failed")

                m.setattr(engine_mod, name, boom)
            trips = runner.loop_trips
            outs = await asyncio.gather(
                b.check(payload(wire_batch(range(4), now))),
                b.check(payload(wire_batch(range(4, 8), now))),
                return_exceptions=True,
            )
            assert [type(o) for o in outs] == [ValueError, ValueError], outs
            assert "failed" in str(outs[0])
            assert b._inflight == 0
            assert runner.loop_trips - trips == b.dispatches
        rc = await b.check(payload(wire_batch(range(100, 104), now)))
        assert (rc.status == 0).all() and (rc.remaining == 9).all()
        assert b._inflight == 0
    finally:
        await b.drain()
        runner.close()


@pytest.mark.parametrize("pool", ["_prep", "_exec", "_fetch"])
@async_test
async def test_a_shut_down_executor_reaches_the_caller(pool):
    """The first link's submit fails on the loop, a later link's on the
    worker that finished the link before it: either way the awaiting caller
    gets the error, and nothing is left in flight."""
    now = ms_now()
    runner = new_runner()
    b = Batcher(runner, batch_wait_ms=0.5, workers=1)
    try:
        await b.check(wire_batch(range(4), now))  # compiled, pools started
        getattr(runner, pool).shutdown(wait=True)
        with pytest.raises(RuntimeError, match="shutdown"):
            await asyncio.wait_for(b.check(wire_batch(range(4, 8), now)), 30)
        assert b._inflight == 0
    finally:
        await b.drain()
        runner.close()


@pytest.mark.parametrize("chunk", ["gregorian_row", "repeated_key"])
@async_test
async def test_a_wire_miss_is_restaged_where_it_was_found(chunk):
    """A chunk with a row the compact wire cannot carry cannot fuse. The
    prep job that finds that out stages it as columns itself: the answer is
    byte for byte what `check` gives on the concatenated columns, the first
    staging is one `put_miss` sample and the second one `put`, and the
    batcher counts one wire fallback. A key sent more than once is no such
    chunk: it fuses, in one `put`, as a split dispatch."""
    now = ms_now()
    metrics = DaemonMetrics()
    r_wire, r_cols = new_runner(metrics), new_runner()
    b = Batcher(r_wire, batch_wait_ms=0.5, workers=1, metrics=metrics)
    missed = chunk == "gregorian_row"
    try:
        # warm both engines with the same history, so both answer from it
        first = wire_batch(range(6), now)
        assert_same(
            await r_wire.check_wire([first], now_ms=now),
            await r_cols.check(first.cols, now_ms=now),
        )
        parts = [
            wire_batch([1, 2, 3], now),
            wire_batch([3, 4, 1, 9], now, gregorian=[9] if missed else ()),
        ]
        s0, trips = _stage_sums(metrics), r_wire.loop_trips
        got = await asyncio.gather(*(b.check(p, now_ms=now) for p in parts))
        s1 = _stage_sums(metrics)
        want = await r_cols.check(
            concat_columns([p.cols for p in parts]), now_ms=now
        )
        assert b.dispatches == 1, "the two RPCs were meant to coalesce"
        assert_same(
            ResponseColumns(*(np.concatenate(f) for f in zip(*got))), want
        )
        assert (want.remaining == [8, 8, 8, 7, 8, 7, 9]).all()
        assert (
            b.wire_fallbacks, b.column_dispatches, b.fused_dispatches,
            b.split_dispatches,
        ) == ((1, 1, 0, 0) if missed else (0, 0, 1, 1))
        assert b.debug()["split_dispatches"] == b.split_dispatches
        assert r_wire.loop_trips - trips == 1

        def delta(stage, k):
            return s1.get(stage, (0, 0))[k] - s0.get(stage, (0, 0))[k]

        assert {s: delta(s, 1) for s in ("put_miss", "put", "issue", "fetch")} == {
            "put_miss": int(missed), "put": 1, "issue": 1, "fetch": 1,
        }
    finally:
        await b.drain()
        r_wire.close()
        r_cols.close()


@pytest.mark.parametrize(
    "path", ["fused", "split", "miss", "columns", "mesh", "tiered"]
)
@async_test
async def test_dispatch_is_its_stages_plus_its_self_time(path):
    """`dispatch` = put + put_miss + issue + fetch + `dispatch_wait`, to the
    float: every stage of the chain is timed under the dispatch, on the
    thread that runs it, and the batcher states the rest. So on a mesh (its
    shard stages are parts of `put` and `fetch`), and with a shadow behind
    the table when every key of the chunk comes back from it ahead of the
    launch (the probe is part of `put`, the merge of `issue`)."""
    now = ms_now()
    metrics = DaemonMetrics()
    runner = new_runner(metrics, path if path in ("mesh", "tiered") else "local")
    b = Batcher(runner, batch_wait_ms=0.5, workers=1, metrics=metrics)
    shadow = getattr(runner.engine, "shadow", None)
    try:
        for i in range(5):
            keys = range(8) if path == "tiered" else range(8 * i, 8 * i + 8)
            wb = wire_batch(
                [7, 7, 8] if path == "split" else keys, now,
                gregorian=[8 * i] if path == "miss" else (),
            )
            if path == "tiered" and i:  # the rows of the dispatch before
                _now, fps, _rows = await runner.tier_demote_idle(
                    1, now_ms=now + 10,
                    sink=lambda f, r, t: shadow.offer(f, r, t, reason="idle"),
                )
                assert fps.size == 8 and shadow.contains(wb.cols.fp).all()
            s0 = _stage_sums(metrics)
            rc = await b.check(wb.cols if path == "columns" else wb, now_ms=now)
            s1 = _stage_sums(metrics)
            if path == "tiered":  # one count through five trips to the shadow
                assert (rc.remaining == 9 - i).all()

            def delta(stage, k=0):
                return s1.get(stage, (0, 0))[k] - s0.get(stage, (0, 0))[k]

            assert delta("dispatch", 1) == delta("dispatch_wait", 1) == 1
            assert delta("put_miss", 1) == (path == "miss")
            parts = [delta(s) for s in
                     ("put", "put_miss", "issue", "fetch", "dispatch_wait")]
            assert all(p >= 0 for p in parts) and delta("dispatch_wait") > 0
            assert sum(parts) == pytest.approx(delta("dispatch"), rel=1e-9)
    finally:
        await b.drain()
        runner.close()


@async_test
async def test_many_dispatches_under_a_short_switch_interval():
    """More dispatches in flight than cores, the interpreter switching
    threads every 10 µs: every caller is answered with its own rows, every
    dispatch is one loop trip, and the decision counts (a plain dict, kept
    on the loop thread) lose no update."""
    import sys

    now = ms_now()
    runner = new_runner()
    b = Batcher(runner, batch_wait_ms=0.2, workers=8)
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        waves = [
            [wire_batch(range(1000 * w + 10 * j, 1000 * w + 10 * j + 1 + j % 7), now)
             for j in range(40)]
            for w in range(6)
        ]
        trips, rows = runner.loop_trips, 0
        for wave in waves:
            outs = await asyncio.wait_for(
                asyncio.gather(*(b.check(wb, now_ms=now) for wb in wave)), 60
            )
            for wb, rc in zip(wave, outs):
                assert rc.status.shape == (wb.rows,)
                assert (rc.remaining == 9).all() and (rc.err == 0).all()
                rows += wb.rows
        assert b._inflight == 0 and b.requests == 240
        assert runner.loop_trips - trips == b.dispatches
        assert sum(runner.algo_counts.values()) == rows
    finally:
        sys.setswitchinterval(old)
        await b.drain()
        runner.close()


# ------------------------------------------------- a dispatch's decision counts
def algo_batch(algos, now, tag, gregorian=False):
    """One parsed RPC, stamped by the parser, a row an entry of `algos`."""
    data = pb.GetRateLimitsReq(requests=[
        pb.RateLimitReq(
            name=tag, unique_key=f"k{i}", hits=1, limit=10, algorithm=a,
            **({"behavior": 4, "duration": 4} if gregorian and i == 0
               else {"duration": 60_000}),
        )
        for i, a in enumerate(algos)
    ]).SerializeToString()
    wb = wire_batch_from_wire(data, now)[0]
    assert wb.summary.stamped and wb.summary.unstamped == len(algos)
    return wb


def decision_samples(metrics) -> dict:
    return {
        label: metrics.decisions_total.labels(algorithm=label)._value.get()
        for label in _ALGO_LABELS
    }


DECISION_KINDS = [
    "fused", "wire_miss", "columns", "mixed", "no_lanes", "serial", "mesh_fused",
]


@pytest.mark.parametrize("kind", DECISION_KINDS)
@async_test
async def test_a_dispatch_counts_its_decisions_once(kind, monkeypatch):
    """`decisions_total{algorithm}` and `runner.algo_counts` after a dispatch
    are the counts of its rows by algorithm, however the chunk was staged
    and whether its pieces carry the parser's summary (their integers are
    added up), do not (their column is counted), or both; the counter is
    touched once an algorithm a dispatch, not once an RPC."""
    now = ms_now()
    metrics = DaemonMetrics()
    runner = new_runner(metrics, "mesh" if kind == "mesh_fused" else "local")
    if kind == "no_lanes":  # as a CPU mesh engine: it takes the parser's columns
        runner.close()
        runner = EngineRunner(LocalEngine(capacity=4096, wire="full"), metrics)
        assert not runner.engine.supports_wire_ingress
    incs = []
    labels = metrics.decisions_total.labels
    monkeypatch.setattr(
        metrics.decisions_total, "labels",
        lambda **kw: incs.append(kw["algorithm"]) or labels(**kw),
    )
    counted = []  # the columns that were counted by array calls
    import gubernator_tpu.service.runner as runner_mod

    monkeypatch.setattr(
        runner_mod, "_label_counts",
        lambda col: counted.append(len(col)) or _label_counts(col),
    )
    parts = [
        algo_batch([0, 0, 1, 0], now, "a", gregorian=kind == "wire_miss"),
        algo_batch([1, 2, 3, 0, 0], now, "b"),
        algo_batch([0, 1, 1], now, "c"),
    ]
    if kind == "mixed":  # rows selected from a batch: no summary
        parts[1] = subset_wire(parts[1], np.array([0, 1, 3]))
        assert parts[1].summary is None
    want = dict(zip(_ALGO_LABELS, _label_counts(
        np.concatenate([p.cols.algo for p in parts])
    )))
    fused = []
    try:
        done = lambda rc, exc, n: fused.append(n)  # noqa: E731
        if kind == "columns":
            await runner.check([p.cols for p in parts], now_ms=now, done=done)
        elif kind == "serial":
            await runner.check_columns(
                concat_columns([p.cols for p in parts]), now_ms=now, done=done
            )
        else:
            await runner.check_wire(parts, now_ms=now, done=done)
        assert bool(fused[0]) == (kind in ("fused", "mixed", "mesh_fused"))
        assert runner.algo_counts == want
        assert sorted(incs) == sorted(k for k, v in want.items() if v)
        assert decision_samples(metrics) == want
        assert counted == {
            "columns": [4, 5, 3], "serial": [12], "mixed": [3],
        }.get(kind, [])
    finally:
        runner.close()


@pytest.mark.parametrize("stage", STAGES)
@async_test
async def test_a_dispatch_that_fails_counts_no_decision(stage, monkeypatch):
    now = ms_now()
    metrics = DaemonMetrics()
    runner = new_runner(metrics)
    try:
        for name in STAGE_FNS[stage]:
            def boom(*a, _name=name, **k):
                raise ValueError(f"{_name} failed")

            monkeypatch.setattr(engine_mod, name, boom)
        with pytest.raises(ValueError, match="failed"):
            await runner.check_wire([algo_batch([0, 1, 4], now, "x")], now_ms=now)
        assert not any(runner.algo_counts.values())
        assert not any(decision_samples(metrics).values())
    finally:
        runner.close()
