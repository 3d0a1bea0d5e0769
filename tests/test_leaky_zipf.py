"""The deployment `bench/configs/leaky1m-zipf.json` defines — a leaky bucket
over Zipf(0.99) keys, 1,000 checks an RPC — at a size the CPU holds, through
the normal path: raw gRPC handler → native parser → batcher → fused wire
staging → engine.

Under that skew every chunk repeats its hot keys: the grid carries the first
copy of each key and `ops/engine._later_passes` stages the rest as gathers of
the same lanes, copies 1–6 one pass each and copies 7 and up as one aggregate
(`ops/plan.py`, `max_exact_passes` 8). These tests hold every answer of such chunks to the
plain leaky oracle (`tests/oracle/algos.py`) applied in the plan's order,
the counters `later_rows` / `aggregate_rows` to what the chunk holds, the
read-back to the benchmark's rule, and the `later_stage` part of `put` to
its place in `/metrics` and in a profiler trace.
"""

import asyncio
import json
import dataclasses
import os

import numpy as np
import pytest

from gubernator_tpu import native
from gubernator_tpu.ops.engine import LocalEngine, ms_now
from gubernator_tpu.proto import gubernator_pb2 as pb
from gubernator_tpu.service.daemon import Daemon
from gubernator_tpu.service.wire import wire_batch_from_wire

from tests.cluster import daemon_config
from tests.oracle.algos import LeakyOracle
from tests.test_mesh4_deployment import _spans
from tests.test_observability import _stage_sums
from tests.test_runner_chain import assert_same, async_test
from tests.test_wire_split import pair, rpc, wire_against_columns

pytestmark = pytest.mark.skipif(
    native.load() is None, reason="native toolchain unavailable"
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "bench", "configs", "leaky1m-zipf.json")) as _f:
    CONFIG = json.load(_f)
with open(os.path.join(ROOT, "bench", "traffic", "bulk1000-zipf-closed64.json")) as _f:
    TRAFFIC = json.load(_f)
DURATION = int(CONFIG["keyspace"]["duration_ms"])  # 1,340 s a token at limit 100: no leak lands
RPC_ITEMS = int(TRAFFIC["items_per_rpc"]["fixed"])
THETA = float(TRAFFIC["keys"]["theta"])
KEYS = 2000
LIMIT = 20  # the deployment's is 100: at this size the hot keys pass 20 in one chunk
PROBE_LIMIT = 500  # the probe keys': room for 400 copies
MAX_EXACT = 8


_CDF = np.cumsum(np.arange(1, KEYS + 1, dtype=np.float64) ** -THETA)
_CDF /= _CDF[-1]


def zipf_ranks(rng, count: int) -> np.ndarray:
    """bench/loadgen.py's sampler: the exact inverse CDF over KEYS ranks."""
    return np.minimum(np.searchsorted(_CDF, rng.random(count)), KEYS - 1)


def body(rows, now: int) -> bytes:
    """One RPC. A row is (key, hits, limit)."""
    return pb.GetRateLimitsReq(requests=[
        pb.RateLimitReq(
            name="lz", unique_key=f"k{key}", hits=hits, limit=limit,
            duration=DURATION, algorithm=pb.LEAKY_BUCKET, created_at=now,
        )
        for key, hits, limit in rows
    ]).SerializeToString()


def answers(data: bytes):
    return [(r.status, r.remaining, r.reset_time, r.limit, r.error)
            for r in pb.GetRateLimitsResp.FromString(data).responses]


async def spawn() -> Daemon:
    """One peer with the deployment's engine selectors as a TPU resolves
    them (compact wire), and a fixed 400 ms window in place of the adaptive
    500 µs one, so that RPCs sent together are one chunk whatever the host's
    load."""
    conf = daemon_config(http_address="")
    conf.behaviors.adaptive_batch = False
    conf.behaviors.batch_wait_ms = 400.0
    return await Daemon.spawn(conf, engine=LocalEngine(capacity=65536, wire="compact"))


async def one_chunk(d: Daemon, bodies) -> list:
    """`bodies` through the raw handler as ONE chunk in the order given: each
    RPC is started once the one before it is parsed and enqueued (the parse
    hops to the door pool, whose workers may finish out of order). Returns
    the answers, one list an RPC."""
    b0 = d.batcher.debug()["dispatches"]
    tasks = []
    for data in bodies:
        n0 = d.raw_rpcs
        tasks.append(asyncio.ensure_future(d.get_rate_limits_raw(data)))
        while d.raw_rpcs == n0:
            await asyncio.sleep(0)
    out = [answers(x) for x in await asyncio.gather(*tasks)]
    assert d.batcher.debug()["dispatches"] - b0 == 1, "the RPCs were not one chunk"
    return out


def engine_block(d: Daemon) -> dict:
    # a dispatch's stats delta lands on the engine thread after its answer
    d.runner._exec.submit(lambda: None).result()
    return d.debug_pipeline()["engine"]


def oracle_in_plan_order(oracle: LeakyOracle, rows, now) -> list:
    """What the chunk `rows` (in arrival order) must answer: a key's copies
    0–6 one after another, its copies 7 and up as one check of their summed
    hits, stamped as its newest member, whose answer every member shares;
    keys do not interact. `now`: the rows' created_at, one or one a row."""
    stamp = now if isinstance(now, list) else [now] * len(rows)
    copies: dict = {}
    for i, (key, _hits, _limit) in enumerate(rows):
        copies.setdefault(key, []).append(i)
    want = [None] * len(rows)
    for key, at in copies.items():
        limit = rows[at[0]][2]
        for i in at[:MAX_EXACT - 1]:
            want[i] = oracle.check(key, stamp[i], rows[i][1], limit, DURATION)
        tail = at[MAX_EXACT - 1:]
        if tail:
            agg = oracle.check(
                key, stamp[tail[-1]], sum(rows[i][1] for i in tail), limit, DURATION)
            for i in tail:
                want[i] = agg
    return want


def held_to(got_rpcs, want, rows) -> None:
    got = [a for rpc in got_rpcs for a in rpc]
    assert len(got) == len(want) == len(rows)
    for i, (g, w) in enumerate(zip(got, want)):
        assert g == (*w, rows[i][2], ""), (i, rows[i], g, w)


@pytest.mark.parametrize("left", ["below", "at", "above"])
@pytest.mark.parametrize("copies", [2, 7, 8, 9, 400])
@async_test
async def test_a_zipf_chunk_answers_as_the_oracle_in_plan_order(copies, left):
    """Three 1,000-item RPCs of Zipf(0.99) leaky keys, one chunk, twice over
    (near the limit, then past it); among them a probe key sent `copies`
    times whose bucket holds one token fewer than, as many as, or one more
    than its copies. Every answer is the oracle's, eight passes are issued,
    and the counters grow by exactly what the chunk holds."""
    rng = np.random.default_rng(32_000 + copies)
    now = ms_now()
    probe = KEYS + copies  # not a Zipf key
    room = copies + {"below": -1, "at": 0, "above": 1}[left]
    oracle = LeakyOracle()
    d = await spawn()
    try:
        # the probe's history: one check that leaves `room` tokens
        history = [(probe, PROBE_LIMIT - room, PROBE_LIMIT)]
        held_to(await one_chunk(d, [body(history, now)]),
                oracle_in_plan_order(oracle, history, now), history)
        for round_ in range(2):
            rows = [(int(k), 1, LIMIT) for k in zipf_ranks(rng, 3 * RPC_ITEMS - copies)]
            if round_ == 0:
                for at in np.sort(rng.choice(len(rows) + 1, size=copies)):
                    rows.insert(int(at), (probe, 1, PROBE_LIMIT))
            else:
                rows += [(int(k), 1, LIMIT) for k in zipf_ranks(rng, copies)]
            _keys, counts = np.unique([r[0] for r in rows], return_counts=True)
            assert counts.max() > MAX_EXACT  # rank 0 alone is a ninth of the rows
            e0, b0 = engine_block(d), d.batcher.debug()
            got = await one_chunk(
                d, [body(rows[lo:lo + RPC_ITEMS], now) for lo in range(0, len(rows), RPC_ITEMS)]
            )
            held_to(got, oracle_in_plan_order(oracle, rows, now), rows)
            e1, b1 = engine_block(d), d.batcher.debug()
            grew = {k: e1[k] - e0[k] for k in ("checks", "dispatches", "later_rows",
                                               "aggregate_rows", "over_limit")}
            assert grew["checks"] == len(rows) and grew["dispatches"] == MAX_EXACT
            assert grew["later_rows"] == len(rows) - len(counts)
            assert grew["aggregate_rows"] == int(np.maximum(counts - (MAX_EXACT - 1), 0).sum())
            # the kernel's count: decided rows, an aggregate once
            flat = [a for rpc in got for a in rpc]
            assert 0 < grew["over_limit"] <= sum(a[0] == pb.OVER_LIMIT for a in flat)
            for k in ("fused_dispatches", "split_dispatches"):
                assert b1[k] - b0[k] == 1, k
            assert b1["wire_fallbacks"] == b0["wire_fallbacks"]
        if copies >= MAX_EXACT and left == "below":
            # the aggregate was refused whole: the probe keeps what the exact
            # passes left it, where one check after another would drain it
            status, remaining, _reset = oracle.check(probe, now, 0, PROBE_LIMIT, DURATION)
            assert (status, remaining) == (0, room - (MAX_EXACT - 1))
    finally:
        await d.close()


@async_test
async def test_a_chunk_of_the_deployments_size_rides_the_lanes_as_it_rode_columns():
    """Eight 1,000-item RPCs of Zipf(0.99) leaky keys as one chunk, twice
    (the second past the hot keys' limit), through the fused staging on one
    engine and as plain columns on another: eight passes, every answer, every
    stored row and the stats delta equal, and every later copy — 4 in 10 of
    the rows, the aggregate's hundreds of members among them — staged from
    the parser's lanes."""
    rng = np.random.default_rng(33_001)
    now = ms_now()
    r_wire, r_cols = pair(capacity=65536)
    try:
        for _ in range(2):
            ranks = zipf_ranks(rng, 8 * RPC_ITEMS)
            parts = [
                wire_batch_from_wire(
                    body([(int(k), 1, LIMIT) for k in ranks[lo:lo + RPC_ITEMS]], now))[0]
                for lo in range(0, ranks.size, RPC_ITEMS)
            ]
            n_fused, got, delta = await wire_against_columns(r_wire, r_cols, parts, now)
            counts = np.unique(ranks, return_counts=True)[1]
            assert n_fused == MAX_EXACT and not got.err.any()
            assert delta["checks"] == ranks.size and delta["dispatches"] == MAX_EXACT
            assert delta["later_rows"] == ranks.size - counts.size > 0.4 * ranks.size
            assert delta["aggregate_rows"] == int(np.maximum(counts - (MAX_EXACT - 1), 0).sum())
            assert delta["aggregate_rows"] > 0.2 * ranks.size
            assert delta["later_lane_rows"] == delta["later_rows"]
        assert (got.status == pb.OVER_LIMIT).sum() > 0.2 * ranks.size
    finally:
        r_wire.close()
        r_cols.close()


def no_native_module(monkeypatch) -> None:
    """A host with no toolchain, from here to the test's end: `native.load()`
    is None, and the fused staging is NumPy's (ops/engine._stage_chunk_numpy).
    Parse every RPC before this."""
    monkeypatch.setattr(native, "load", lambda: None)


def staged_natively(runner) -> int:
    runner._exec.submit(lambda: None).result()  # the stats delta is in
    return runner.engine.stats.native_staged


@pytest.mark.parametrize("rpcs", [1, 8])
@async_test
async def test_a_zipf_chunk_answers_the_same_staged_natively_or_by_numpy(rpcs, monkeypatch):
    """The deployment's chunk, twice (the second past the hot keys' limit),
    on two equal engines: one staged by the native call, one with
    `native.load()` patched to None. Every answer and every stored row are
    the same bytes, both dispatches issue the same passes and count the
    same stats, and `engine.native_staged` and `engine.native_finished`
    count the dispatches of the first alone: the staging and the finish
    (ops/wire.finish_wire_chunk against ops/engine._finish_numpy)."""
    rng = np.random.default_rng(39_001)
    now = ms_now()
    chunks = []
    for _ in range(2):
        ranks = zipf_ranks(rng, rpcs * RPC_ITEMS)
        chunks.append([
            wire_batch_from_wire(
                body([(int(k), 1, LIMIT) for k in ranks[lo:lo + RPC_ITEMS]], now))[0]
            for lo in range(0, ranks.size, RPC_ITEMS)
        ])
    r_native, r_numpy = pair(capacity=65536)
    try:
        fused, got = [], []
        for parts in chunks:
            got.append(await r_native.check_wire(
                parts, now_ms=now, done=lambda _rc, _exc, f: fused.append(f)))
        assert staged_natively(r_native) == 2
        no_native_module(monkeypatch)
        for parts, native_answer in zip(chunks, got):
            assert_same(
                await r_numpy.check_wire(
                    parts, now_ms=now, done=lambda _rc, _exc, f: fused.append(f)),
                native_answer,
            )
        assert staged_natively(r_numpy) == 0 and staged_natively(r_native) == 2
        assert fused == [MAX_EXACT] * 4
        assert (got[1].status == pb.OVER_LIMIT).sum() > 0.2 * ranks.size
        fps = np.unique(np.concatenate([p.cols.fp for p in chunks[1]]))
        (found_a, rows_a), (found_b, rows_b) = (
            r.engine.read_state(fps) for r in (r_native, r_numpy)
        )
        assert found_a.all() and found_b.all() and (rows_a == rows_b).all()
        for field in ("later_rows", "aggregate_rows", "later_lane_rows", "dispatches"):
            assert getattr(r_native.engine.stats, field) == getattr(r_numpy.engine.stats, field) > 0
        a, b = (dataclasses.asdict(r.engine.stats) for r in (r_native, r_numpy))
        assert [a.pop(k) for k in ("native_staged", "native_finished")] == [2, 2]
        assert [b.pop(k) for k in ("native_staged", "native_finished")] == [0, 0]
        assert a == b
    finally:
        r_native.close()
        r_numpy.close()


@async_test
async def test_chunks_staged_on_two_threads_at_once_answer_as_one_at_a_time(monkeypatch):
    """240 Zipf chunks, each over keys of its own, in flight together through
    one runner at a 10 µs switch interval: its prep threads run the native
    staging side by side, which holds no state. Every chunk is answered as
    the same chunk served alone from NumPy's staging on another engine."""
    import sys

    rng = np.random.default_rng(39_002)
    now = ms_now()
    chunks = []
    for c in range(240):
        keys = 100 * c + np.minimum(zipf_ranks(rng, 2 * 40), 11)
        # a later copy in 50 is stamped 700 ms late and takes its pass off
        # the lanes (a first copy so late would take the chunk off the wire)
        rows, seen = [], set()
        for k in map(int, keys):
            late = k in seen and rng.random() < 0.02
            rows.append((k, 700 if late else int(rng.integers(-200, 200)), 0))
            seen.add(k)
        chunks.append([rpc(rows[:40], now), rpc(rows[40:], now)])
    r_native, r_numpy = pair(capacity=65536)
    interval = sys.getswitchinterval()
    try:
        sys.setswitchinterval(1e-5)
        try:
            got = await asyncio.gather(
                *(r_native.check_wire(parts, now_ms=now) for parts in chunks))
        finally:
            sys.setswitchinterval(interval)
        assert staged_natively(r_native) == 240
        no_native_module(monkeypatch)
        for parts, native_answer in zip(chunks, got):
            assert_same(await r_numpy.check_wire(parts, now_ms=now), native_answer)
        assert staged_natively(r_numpy) == 0
        a, b = r_native.engine.stats, r_numpy.engine.stats
        assert a.later_rows == b.later_rows > 240 * 40
        assert a.aggregate_rows == b.aggregate_rows > 0
        assert 0 < a.later_lane_rows == b.later_lane_rows < a.later_rows
        # finished on four fetch threads side by side by the call that holds
        # no state either, but the chunks with a pass off the lanes
        assert 0 < a.native_finished < 240 and b.native_finished == 0
    finally:
        r_native.close()
        r_numpy.close()


@async_test
async def test_after_enough_dispatches_the_read_back_rule_holds():
    """The benchmark's read-back (bench/checker.py, a leaky keyspace): after
    the traffic a `hits=0` peek of every key answers UNDER_LIMIT, `remaining`
    max(limit − sent, 0) — 0 for every key sent more than its limit, though
    aggregates of it were refused whole on the way — and reset_time = the
    peek's stamp + (limit − remaining) × int(duration/limit)."""
    rng = np.random.default_rng(32_099)
    now = ms_now()
    sent = np.zeros(KEYS, dtype=np.int64)
    oracle = LeakyOracle()
    d = await spawn()
    try:
        for _ in range(10):
            ranks = zipf_ranks(rng, 2 * RPC_ITEMS)
            rows = [(int(k), 1, LIMIT) for k in ranks]
            got = await one_chunk(
                d, [body(rows[lo:lo + RPC_ITEMS], now) for lo in (0, RPC_ITEMS)])
            held_to(got, oracle_in_plan_order(oracle, rows, now), rows)
            np.add.at(sent, ranks, 1)
        assert (sent > LIMIT).sum() > 50 and (sent == 0).sum() > 50
        stamp = now + 250
        peeks = [(k, 0, LIMIT) for k in range(KEYS)]
        got = await one_chunk(
            d, [body(peeks[lo:lo + RPC_ITEMS], stamp) for lo in (0, RPC_ITEMS)])
        irate = DURATION // LIMIT
        for k, (status, remaining, reset, limit, error) in enumerate(
                a for rpc in got for a in rpc):
            want = max(LIMIT - int(sent[k]), 0)
            assert (status, remaining, limit, error) == (pb.UNDER_LIMIT, want, LIMIT, ""), k
            assert reset == stamp + (LIMIT - want) * irate, k
        assert (await d.debug_table())["evicted_live_total"] == 0
    finally:
        await d.close()


@async_test
async def test_later_stage_is_a_part_of_put_in_metrics_and_in_the_trace(tmp_path):
    """The staging of a chunk's later copies is timed where it happens, as a
    part of the dispatch's `put`: one `later_stage` sample a split chunk in
    `/metrics`, inside `put`'s time; and, with a jax.profiler trace running
    (the benchmark launcher's options), one `gub:later_stage` span inside the
    `gub:put` span of the same `dispatch`, with its passes and rows. The
    trace is started before the chunks are sent and stopped after they have
    answered, in this process: no window for the traffic to miss."""
    import jax

    rng = np.random.default_rng(32_101)
    now = ms_now()
    d = await spawn()
    try:
        unique = [(KEYS + 1 + i, 1, LIMIT) for i in range(64)]
        await one_chunk(d, [body(unique, now)])  # compiles; no key repeats
        s0 = _stage_sums(d.metrics)
        assert "later_stage" not in s0 and s0["put"][1] >= 1
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            chunks = []
            for _ in range(3):
                rows = [(int(k), 1, LIMIT) for k in zipf_ranks(rng, RPC_ITEMS)]
                chunks.append(rows)
                await one_chunk(d, [body(rows, now)])
            await one_chunk(d, [body(unique, now)])
        finally:
            jax.profiler.stop_trace()
        s1 = _stage_sums(d.metrics)
    finally:
        await d.close()
    later = [len(rows) - len({r[0] for r in rows}) for rows in chunks]
    assert s1["later_stage"][1] == 3 and s1["put"][1] - s0["put"][1] == 4
    assert 0.0 < s1["later_stage"][0] <= s1["put"][0] - s0["put"][0]

    spans = _spans(str(tmp_path))
    puts = {st["dispatch"]: (a, b) for st, a, b in spans["put"]}
    assert len(puts) == 4 and len(spans["later_stage"]) == 3
    for (st, a, b), n_later in zip(sorted(spans["later_stage"], key=lambda s: s[1]), later):
        lo, hi = puts[st["dispatch"]]
        assert lo <= a and b <= hi, st
        assert st["rows"] == n_later and st["passes"] == MAX_EXACT - 1, st


@async_test
async def test_a_chunk_is_cut_where_its_stamps_would_leave_the_compact_wire():
    """Rows are stamped when they are enqueued (or by the client), and the
    compact wire carries a stamp as −512…511 ms from the chunk's first. RPCs
    queued across a stall of half a second used to make one chunk that left
    the wire for the full-width format — on a TPU a leaky program nobody
    had warmed, over a minute of compiling with every RPC of the deployment
    behind it. The chunk now ends before the RPC that would take its stamps
    past that span: both parts ride the fused staging, in arrival order."""
    rng = np.random.default_rng(32_103)
    now = ms_now()
    oracle = LeakyOracle()
    d = await spawn()
    try:
        # (the last RPC has keys of its own: two chunks in flight are not
        # ordered against each other)
        rpcs = [[(int(k) + KEYS * (j == 3), 1, LIMIT) for k in zipf_ranks(rng, 200)]
                for j in range(4)]
        stamps = [now, now + 300, now + 511, now + 512]
        b0 = d.batcher.debug()
        tasks = []
        for rows, at in zip(rpcs, stamps):
            n0 = d.raw_rpcs
            tasks.append(asyncio.ensure_future(d.get_rate_limits_raw(body(rows, at))))
            while d.raw_rpcs == n0:
                await asyncio.sleep(0)
        got = [answers(x) for x in await asyncio.gather(*tasks)]
        b1 = d.batcher.debug()
        grew = {k: b1[k] - b0[k] for k in ("dispatches", "fused_dispatches",
                                           "wire_fallbacks", "column_dispatches")}
        assert grew == {"dispatches": 2, "fused_dispatches": 2, "wire_fallbacks": 0,
                        "column_dispatches": 0}
        # the first three RPCs are one chunk, the fourth the next
        for part, at_ in ((rpcs[:3], stamps[:3]), (rpcs[3:], stamps[3:])):
            rows = [r for rpc in part for r in rpc]
            at = [t for rpc, t in zip(part, at_) for _ in rpc]
            held_to(got[:len(part)], oracle_in_plan_order(oracle, rows, at), rows)
            got = got[len(part):]
    finally:
        await d.close()
