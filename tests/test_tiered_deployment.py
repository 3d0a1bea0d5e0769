"""The tiered deployment (`token40m-tiered`: 40M tracked token buckets behind
a 16,777,216-slot table, the host-RAM shadow as upstream's Store) at a size a
CPU holds: `LocalEngine` with a shadow attached, served through the fused wire
path the daemon uses, held dispatch by dispatch to the plain reference
`tests/oracle/stored_table.py` — every answer (an unbounded table's), which
keys are resident and which stored, the demote / promote / return counts, and
nothing lost; then every key read back with `hits=0`.

The sizes: 64 buckets of 8 lanes, about four tracked keys a slot, 1,000-row
chunks. The fill goes in rank order, as the benchmark's does, so it leaves the
hottest ranks in the store; the traffic is Zipf(0.99) with its duplicates, so
the passes behind the grid and the aggregate run. Dispatches are 2,048 ms
apart: each is another unit of the lanes' touch clock.
"""

import asyncio

import numpy as np
import pytest

from gubernator_tpu.hashing import fingerprint
from gubernator_tpu.ops.engine import LocalEngine
from gubernator_tpu.proto import gubernator_pb2 as pb
from gubernator_tpu.service.runner import EngineRunner
from gubernator_tpu.service.wire import wire_batch_from_wire
from gubernator_tpu.tier import ShadowTable

from tests.oracle.stored_table import K, StoredTable

NOW = 1_700_000_000_000
STEP_MS = 2_048
LIMIT, DURATION = 100, 3_600_000
SLOTS, N_KEYS, RPC_ITEMS = 512, 2_000, 1_000
TAG = "tiered"


def fp_of(k: int) -> int:
    return fingerprint(TAG, f"k{k}")


def rpc(keys, now: int, hits: int = 1):
    wb = wire_batch_from_wire(pb.GetRateLimitsReq(requests=[
        pb.RateLimitReq(name=TAG, unique_key=f"k{k}", hits=hits, limit=LIMIT,
                        duration=DURATION, created_at=now)
        for k in keys
    ]).SerializeToString())[0]
    assert wb.all_encodable
    return wb


def zipf_ranks(rng, n: int, size: int, theta: float = 0.99) -> np.ndarray:
    p = 1.0 / np.arange(1, n + 1) ** theta
    return rng.choice(n, size=size, p=p / p.sum())


def fill_chunks(n_keys: int):
    return [np.arange(lo, min(lo + RPC_ITEMS, n_keys)) for lo in range(0, n_keys, RPC_ITEMS)]


def same_bucket(n_buckets: int, want: int, among: int) -> np.ndarray:
    """`want` key indices below `among` whose fingerprints share a bucket."""
    by = {}
    for k in range(among):
        by.setdefault(fp_of(k) % n_buckets, []).append(k)
    keys = max(by.values(), key=len)
    assert len(keys) >= want, "no bucket that crowded: raise `among`"
    return np.asarray(keys[:want])


def case_chunks(case: str, n_buckets: int):
    rng = np.random.default_rng(42)
    fill = fill_chunks(N_KEYS)
    if case == "zipf":
        return fill + [zipf_ranks(rng, N_KEYS, RPC_ITEMS) for _ in range(10)]
    if case == "promote_and_hit":
        # the hottest ranks lie in the store after the fill: each comes back
        # and is hit three times more in the dispatch that brought it back
        hot = np.arange(40)
        return fill + [np.concatenate([hot, hot, hot, hot]), hot]
    if case == "returned_promote":
        # more stored keys of one bucket than it has lanes, in one dispatch
        crowd = same_bucket(n_buckets, K + 3, 800)
        rest = np.setdiff1d(np.arange(300), crowd)[:200]
        return fill + [np.concatenate([crowd, rest]), crowd]
    if case == "redispatch":
        # keys the table holds beside keys it does not, each dispatch: the
        # pipelined launch decides the first, the miss path the others
        tail = np.arange(N_KEYS - 300, N_KEYS)
        return fill + [np.concatenate([tail[:150], np.arange(i * 100, i * 100 + 100), tail[150:]])
                       for i in range(6)]
    raise AssertionError(case)


async def served(chunks, slots: int = SLOTS):
    eng = LocalEngine(capacity=slots, wire="compact")
    eng.attach_shadow(ShadowTable(max_bytes=1 << 24))
    runner = EngineRunner(eng)
    answers = []
    try:
        for i, keys in enumerate(chunks):
            now = NOW + i * STEP_MS
            fused = []
            got = await runner.check_wire(
                [rpc(keys, now)], now_ms=now,
                done=lambda _rc, _exc, n_fused: fused.append(n_fused),
            )
            assert fused and fused[0] >= 1, "the chunk left the fused wire path"
            assert not got.err.any()
            answers.append(np.stack([got.status, got.remaining, got.reset_time], axis=1))
        state = snapshot(eng)
        # read every key back, hits = 0: what it holds, wherever it lay
        now = NOW + len(chunks) * STEP_MS
        back = []
        for keys in fill_chunks(N_KEYS):
            got = await runner.check_wire([rpc(keys, now, hits=0)], now_ms=now)
            assert not got.err.any()
            back.append(np.stack([got.status, got.remaining, got.reset_time], axis=1))
    finally:
        runner.close()
    return np.concatenate(answers), np.concatenate(back), state, eng


def snapshot(eng) -> dict:
    fps = np.asarray([fp_of(k) for k in range(N_KEYS)], dtype=np.int64)
    found, _rows = eng.read_state(fps)
    t = eng.tier_counts()
    return {
        "resident": np.asarray(found),
        "stored": eng.shadow.contains(fps),
        "demoted": eng.shadow.demoted_evict,
        "promoted": t["promoted"],
        "returned": t["returned"],
        "rehydrate_dispatches": t["rehydrate_dispatches"],
        "lost": eng.stats.lost_live + eng.shadow.shed,
        "dropped": eng.stats.dropped,
    }


def reference(n_buckets: int, chunks):
    table = StoredTable(n_buckets)
    answers = []
    for i, keys in enumerate(chunks):
        answers += table.check_together(
            [fp_of(k) for k in keys], NOW + i * STEP_MS, 1, LIMIT, DURATION
        )
    state = {
        "resident": np.asarray([table.holds(fp_of(k)) for k in range(N_KEYS)]),
        "stored": np.asarray([fp_of(k) in table.store for k in range(N_KEYS)]),
        "demoted": table.demoted, "promoted": table.promoted,
        "returned": table.returned, "lost": table.lost,
    }
    now = NOW + len(chunks) * STEP_MS
    back = [table.peek(fp_of(k), now, LIMIT, DURATION) for k in range(N_KEYS)]
    return np.asarray(answers, dtype=np.int64), np.asarray(back, dtype=np.int64), state


CASES = ["zipf", "promote_and_hit", "returned_promote", "redispatch"]


@pytest.fixture(scope="module")
def runs():
    cache = {}

    def get(case: str):
        if case not in cache:
            n_buckets = SLOTS // K
            chunks = case_chunks(case, n_buckets)
            got, back, state, eng = asyncio.run(served(chunks))
            assert eng.table.n_buckets == n_buckets
            cache[case] = (chunks, got, back, state, *reference(n_buckets, chunks))
        return cache[case]

    return get


@pytest.mark.parametrize("case", CASES)
def test_every_answer_is_the_unbounded_tables(case, runs):
    _chunks, got, _back, state, want, _wb, _ws = runs(case)
    assert got.shape == want.shape and np.array_equal(got, want), (
        f"{int((got != want).any(axis=1).sum())} of {len(want)} answers differ"
    )
    assert state["dropped"] == 0


@pytest.mark.parametrize("case", CASES)
def test_every_key_reads_back_what_it_was_sent(case, runs):
    chunks, _got, back, _state, _want, want_back, _ws = runs(case)
    assert np.array_equal(back, want_back), (
        f"{int((back != want_back).any(axis=1).sum())} of {N_KEYS} keys read back wrong"
    )
    # and that is limit - sent, the reset_time its first check gave it
    sent = np.bincount(np.concatenate(chunks), minlength=N_KEYS)
    exact = sent <= LIMIT  # past its limit a key may keep part of an aggregate
    assert np.array_equal(back[exact, 1], LIMIT - sent[exact])
    first = {}
    for i, keys in enumerate(chunks):
        for k in keys:
            first.setdefault(int(k), NOW + i * STEP_MS + DURATION)
    assert np.array_equal(back[:, 2], [first[k] for k in range(N_KEYS)])


@pytest.mark.parametrize("case", CASES)
def test_the_counts_and_the_residents_are_the_references(case, runs):
    _chunks, _got, _back, state, _want, _wb, ref = runs(case)
    assert state["lost"] == ref["lost"] == 0
    for name in ("demoted", "promoted", "returned"):
        assert state[name] == ref[name], name
    assert np.array_equal(state["resident"], ref["resident"])
    assert np.array_equal(state["stored"], ref["stored"])
    # a key is in one place
    assert not (state["resident"] & state["stored"]).any()
    assert (state["resident"] | state["stored"]).all()
    assert state["demoted"] > 0 and state["promoted"] > 0


def test_what_each_case_is_there_for(runs):
    assert runs("returned_promote")[3]["returned"] > 0
    assert runs("zipf")[3]["returned"] == runs("zipf")[6]["returned"]
    # the miss path ran beside the pipelined launches, hits applied once
    # (the answers and the read-back above are the proof of "once")
    assert runs("redispatch")[3]["rehydrate_dispatches"] >= 6
    chunks, got = runs("promote_and_hit")[:2]
    hot = got[len(np.concatenate(chunks[:2])):][:160, 1].reshape(4, 40)
    # one hit in the fill, then four in one dispatch, one after another
    assert np.array_equal(hot, LIMIT - 2 - np.arange(4)[:, None] + np.zeros(40, dtype=int))


# ------------------------------------------------ the daemon's own counts


async def _overflowed(**conf):
    """One daemon with a 64-slot table, 256 keys of two hits each through
    its front door: (`/v1/debug/table`, `/v1/debug/pipeline`, the stage
    samples of `/metrics`)."""
    from gubernator_tpu.service.metrics import parse_metrics
    from tests.cluster import Cluster

    c = await Cluster.start(1, cache_size=64, telemetry_interval_ms=60_000.0, **conf)
    d = c.daemons[0]
    try:
        for w in range(8):
            reqs = [pb.RateLimitReq(name="t", unique_key=f"k{w}.{i}", hits=2, limit=10,
                                    duration=600_000) for i in range(32)]
            for r in await d.get_rate_limits(reqs):
                assert not r.error
        # the first wave again: keys that left and come back
        reqs = [pb.RateLimitReq(name="t", unique_key=f"k0.{i}", hits=0, limit=10,
                                duration=600_000) for i in range(32)]
        remaining = [r.remaining for r in await d.get_rate_limits(reqs)]
        fams = parse_metrics(d.metrics.render().decode())
        return await d.debug_table(), d.debug_pipeline(), fams, remaining
    finally:
        await c.stop()


TIER_COUNTS = ("probed", "promoted", "promoted_ahead", "demoted_evict", "demoted_idle", "returned",
               "rehydrate_dispatches", "merge_launches", "lost", "shadow_rows",
               "shadow_bytes")


def test_a_tiered_overflow_demotes_and_loses_nothing():
    table, pipe, fams, remaining = asyncio.run(_overflowed(
        tier_enabled=True, tier_shadow_bytes=1 << 20,
    ))
    assert table["evicted_live_total"] == 0 and table["demoted_live_total"] > 0
    eng = pipe["engine"]
    assert eng["tiering"] == "shadow"
    assert eng["evicted_live_total"] == 0 and eng["demoted_live_total"] > 0
    tier = pipe["tier"]
    assert set(tier) == set(TIER_COUNTS)
    assert tier["lost"] == 0 and tier["promoted"] > 0 and tier["merge_launches"] > 0
    # the last wave's keys came back ahead of their dispatch's launch
    assert 0 < tier["promoted_ahead"] <= tier["promoted"]
    assert tier["demoted_evict"] == table["demoted_live_total"]
    assert tier["rehydrate_dispatches"] > 0 and tier["shadow_rows"] > 0
    assert remaining == [8] * 32  # every count kept
    count = fams["gubernator_tpu_stage_duration_count"]
    for stage in ("tier_probe", "tier_promote", "tier_harvest"):
        assert count[(("stage", stage),)] > 0, stage


def test_only_a_shadow_that_sheds_loses_state():
    table, pipe, _fams, remaining = asyncio.run(_overflowed(
        tier_enabled=True, tier_shadow_bytes=64 * 64,  # 64 rows: it sheds
    ))
    assert table["evicted_live_total"] > 0
    assert pipe["tier"]["lost"] == table["evicted_live_total"]
    assert table["demoted_live_total"] > table["evicted_live_total"]
    assert any(r == 10 for r in remaining)  # a shed key starts anew


def test_without_the_tier_nothing_says_tiering():
    table, pipe, _fams, remaining = asyncio.run(_overflowed())
    assert "tiering" not in pipe["engine"] and "demoted_live_total" not in pipe["engine"]
    assert pipe["tier"] is None and "demoted_live_total" not in table
    assert table["evicted_live_total"] > 0  # an untiered table forgets
    assert any(r == 10 for r in remaining)
