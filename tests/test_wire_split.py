"""A chunk with a key sent more than once keeps the fused wire staging.

`prepare_check_wire` stages the first occurrence of every key from the
parser's lanes (the planner's pass 0) and the later copies as gathers of the
same lanes, in the passes behind it; a pass the lanes cannot carry (a stamp
off the grid's base, an aggregate's hits past 18 bits) is alone staged as
columns. These tests hold the split to the columns path on the same rows:
answers byte for byte, the table row for row, the `EngineStats` delta, the
programs selected, through the dropped-claim retry and the shadow's miss
re-check; and hold the cascade fold, which needs a single pass, to its
refusal.
"""

import dataclasses

import numpy as np
import pytest

from gubernator_tpu import native
from gubernator_tpu.ops import wire as wire_mod
from gubernator_tpu.ops import engine as engine_mod
from gubernator_tpu.ops.engine import (
    LocalEngine,
    ms_now,
    prepare_check_columns,
    prepare_check_wire,
)
from gubernator_tpu.proto import gubernator_pb2 as pb
from gubernator_tpu.service.runner import EngineRunner
from gubernator_tpu.service.wire import concat_columns, wire_batch_from_wire
from gubernator_tpu.tier import ShadowTable

from tests.test_runner_chain import assert_same, async_test

pytestmark = pytest.mark.skipif(
    native.load() is None, reason="native toolchain unavailable"
)

RESET = 8  # Behavior.RESET_REMAINING
EMPTY_KEY, EMPTY_NAME = object(), object()  # rows the parser marks as errors


def rpc(rows, now):
    """One parsed RPC. A row is a key, or (key, created_at offset in ms,
    behavior[, hits[, algorithm]]); EMPTY_KEY / EMPTY_NAME make the two
    validation errors."""
    reqs = []
    for row in rows:
        row = row if isinstance(row, tuple) else (row,)
        key, off, beh, hits, algo = (
            *row, *(0, 0, 1, pb.TOKEN_BUCKET)[len(row) - 1:]
        )
        reqs.append(pb.RateLimitReq(
            name="" if key is EMPTY_NAME else "split",
            unique_key="" if key is EMPTY_KEY else f"k{key}",
            hits=hits, limit=10, duration=60_000, created_at=now + off,
            behavior=beh, algorithm=algo,
        ))
    wb = wire_batch_from_wire(
        pb.GetRateLimitsReq(requests=reqs).SerializeToString()
    )[0]
    assert wb.all_encodable
    return wb


def pair(capacity=4096, shadow=False):
    """Two runners over equal engines: one for the wire, one for columns."""
    runners = []
    for _ in range(2):
        eng = LocalEngine(capacity=capacity, wire="compact")
        if shadow:
            eng.attach_shadow(ShadowTable(max_bytes=1 << 22))
        runners.append(EngineRunner(eng))
    return runners


async def wire_against_columns(r_wire, r_cols, parts, now):
    """Serve `parts` as one chunk through the wire on one engine and as
    concatenated columns on the other; hold answers, stats delta and every
    touched key's stored row to equality. Returns (passes the fused staging
    issued, the answer, the stats delta)."""
    def stats():
        # a dispatch's stats delta lands on the engine thread after its
        # answer: a no-op job behind it on that one thread says it has
        for r in (r_wire, r_cols):
            r._exec.submit(lambda: None).result()
        return [dataclasses.asdict(r.engine.stats) for r in (r_wire, r_cols)]

    before = stats()
    fused = []
    got = await r_wire.check_wire(
        parts, now_ms=now, done=lambda _rc, _exc, f: fused.append(f)
    )
    cols = concat_columns([p.cols for p in parts])
    want = await r_cols.check(cols, now_ms=now)
    assert_same(got, want)
    deltas = [
        {k: a[k] - b[k] for k in a} for a, b in zip(stats(), before)
    ]
    # the counters only the wire can move: rows staged from its lanes, the
    # dispatch that the native call staged, and finished unless a pass fell
    # to columns
    wire_only = {
        k: deltas[0].pop(k)
        for k in ("later_lane_rows", "native_staged", "native_finished")
    }
    assert not any(deltas[1].pop(k) for k in wire_only)
    assert deltas[0] == deltas[1] and wire_only["native_staged"] == 1
    assert wire_only["native_finished"] == (
        wire_only["later_lane_rows"] == deltas[0]["later_rows"]
    )
    deltas[0].update(wire_only)
    fps = np.unique(cols.fp[cols.err == 0])
    (found_w, rows_w), (found_c, rows_c) = (
        r.engine.read_state(fps) for r in (r_wire, r_cols)
    )
    assert (found_w == found_c).all() and (rows_w == rows_c).all()
    (n_fused,) = fused
    return n_fused, got, deltas[0]


def programs(pending):
    """What each pass of a prepared check selects: the staged ingress's
    shape and dtype, the math mode, wire or not, cascade, layout flag."""
    return [(st[0].shape, st[0].dtype, *st[1:]) for *_, st in pending.passes]


LEAKY = pb.LEAKY_BUCKET

# parts of one chunk; passes the split must issue; remaining of the rows
# named, after a history in which keys 0..5 were hit once; and, where not 0,
# the later rows of passes the lanes cannot carry
SHAPES = {
    "pair_inside_one_rpc": ([[1, 2, 1, 7]], 2, {0: 8, 2: 7, 3: 9}),
    "pair_across_two_parts": ([[1, 2, 3], [3, 4, 1, 9]], 2, {2: 8, 3: 7, 5: 7}),
    "one_key_3_times": ([[6, 7, 6], [6, 8]], 3, {0: 9, 2: 8, 3: 7}),
    # max_exact 8: occurrences 0..6 exact, 7 and up one aggregate
    "one_key_8_times": ([[7] * 5 + [9], [7] * 3], 8, {4: 5, 6: 4, 8: 2}),
    "one_key_9_times": ([[7] * 9], 8, {6: 3, 7: 1, 8: 1}),
    # 13 copies left for 3 remaining: the aggregate is refused whole
    "one_key_20_times": ([[7] * 12, [8] + [7] * 8], 8, {6: 3, 7: 3, 20: 3}),
    "two_keys_past_the_aggregate": (
        [[7, 8] * 9, [7] * 2], 8, {12: 3, 13: 3, 17: 1, 19: 3},
    ),
    "next_to_error_rows": (
        [[EMPTY_KEY, 1, EMPTY_NAME, 1], [EMPTY_KEY, 2, 1]], 3,
        {1: 8, 3: 7, 5: 8, 6: 6},
    ),
    # the wire's delta budget is ±511 ms of the first active row's stamp;
    # a later copy is its own pass with its own base
    "stamps_at_the_edge_of_the_budget": (
        [[1, (2, 511, 0), (3, -512, 0)], [(1, 511, 0), (2, -512, 0), (1, 900, 0)]],
        3, {3: 7, 4: 7, 5: 6}, 1,
    ),
    "reset_remaining_in_the_tail": (
        [[7] * 9 + [(7, 0, RESET)] + [7]], 8, {6: 3, 8: 10, 9: 10, 10: 10},
    ),
    "reset_remaining_in_an_exact_pass": (
        [[1, 1, (1, 0, RESET), 1]], 4, {0: 8, 1: 7, 2: 10, 3: 9},
    ),
    # the bit is OR-ed over its own group, from a member that is not the
    # newest, and over no other group
    "reset_remaining_on_one_group_of_two": (
        [[7, 8] * 8, [(7, 0, RESET), 8, 7, 7], [8]], 8,
        {12: 3, 13: 3, 14: 10, 15: 0, 16: 10, 17: 0, 18: 10, 19: 10, 20: 0},
    ),
    # three members of 2^17 hits: their sum does not fit lane 4's 18 bits,
    # so the aggregate alone is staged as columns (and refused whole)
    "an_aggregate_whose_hits_pass_the_lane": (
        [[7] * 7 + [(7, 0, 0, 1 << 17)] * 3], 8, {5: 4, 6: 3, 7: 3, 9: 3}, 3,
    ),
    # the token key's fourth copy is a pass of its own with no leaky row
    "two_algorithms_in_one_chunk": (
        [[6, (7, 0, 0, 1, LEAKY), 6], [(7, 0, 0, 1, LEAKY), 6, (7, 0, 0, 1, LEAKY), 6]],
        4, {0: 9, 1: 9, 4: 7, 5: 7, 6: 6},
    ),
    "error_rows_between_copies_past_the_aggregate": (
        [[7] * 7 + [EMPTY_KEY, 7], [EMPTY_NAME, 7, EMPTY_KEY, EMPTY_KEY, 7]], 8,
        {6: 3, 8: 0, 10: 0, 13: 0},
    ),
}


@pytest.mark.parametrize("shape", SHAPES)
@async_test
async def test_a_split_chunk_is_the_columns_path_byte_for_byte(shape):
    parts, passes, remaining, *off_lanes = SHAPES[shape]
    now = ms_now()
    r_wire, r_cols = pair()
    try:
        # both engines answer from the same history
        first = rpc(range(6), now)
        assert_same(
            await r_wire.check_wire([first], now_ms=now),
            await r_cols.check(first.cols, now_ms=now),
        )
        n_fused, got, delta = await wire_against_columns(
            r_wire, r_cols, [rpc(p, now) for p in parts], now
        )
        assert n_fused == passes
        assert {i: int(got.remaining[i]) for i in remaining} == remaining
        assert delta["checks"] == sum(len(p) for p in parts)
        assert delta["dispatches"] == passes
        keys = [r[0] if isinstance(r, tuple) else r for p in parts for r in p]
        counts = np.unique(
            [k for k in keys if k not in (EMPTY_KEY, EMPTY_NAME)], return_counts=True
        )[1]
        assert delta["later_rows"] == int((counts - 1).sum())
        assert delta["aggregate_rows"] == int(np.maximum(counts - 7, 0).sum())
        assert delta["later_lane_rows"] == delta["later_rows"] - sum(off_lanes)
        # and no program the columns path would not have selected
        chunk = [rpc(p, now) for p in parts]
        cols = concat_columns([p.cols for p in chunk])
        grid, *behind = programs(prepare_check_wire(r_wire.engine, chunk, now_ms=now))
        first, *later = programs(prepare_check_columns(r_cols.engine, cols, now_ms=now))
        # (the grid is padded for the whole chunk, pass 0 for its first
        # copies; a pass whose stamps fit the grid's base and not its own
        # first row's keeps the compact program where columns go full-width)
        assert grid[1:] == first[1:] and len(behind) == len(later)
        for lanes, columns in zip(behind, later):
            wired = columns[3]
            assert lanes == columns or (lanes[3] and not wired and lanes[2] == columns[2])
    finally:
        r_wire.close()
        r_cols.close()


@async_test
async def test_the_common_path_packs_no_batch(monkeypatch):
    """Nine copies each of two keys: the grid, six exact passes and the
    aggregate are all staged from the parser's lanes. `pack_columns`, which
    every HostBatch of the serving path comes from, is not called."""
    def packed(*_a, **_k):
        raise AssertionError("the fused staging packed a HostBatch")

    monkeypatch.setattr(engine_mod, "pack_columns", packed)
    now = ms_now()
    r_wire = EngineRunner(LocalEngine(capacity=4096, wire="compact"))
    try:
        fused = []
        got = await r_wire.check_wire(
            [rpc([7, 8] * 5, now), rpc([8, 7] * 4, now)], now_ms=now,
            done=lambda _rc, _exc, f: fused.append(f),
        )
        r_wire._exec.submit(lambda: None).result()  # the stats delta is in
        assert fused == [8] and not got.err.any() and not got.status.any()
        # copies 0–6 one after another, copies 7 and 8 as one check of 2 hits
        assert got.remaining.tolist() == (
            [r for r in range(9, 4, -1) for _ in range(2)]
            + [4, 4, 3, 3, 1, 1, 1, 1]
        )
        stats = r_wire.engine.stats
        assert (stats.later_rows, stats.aggregate_rows, stats.later_lane_rows) == (16, 4, 16)
    finally:
        r_wire.close()


@async_test
async def test_a_clamped_stamp_is_counted_once_on_a_split_chunk():
    """A client stamp beyond the engine's skew tolerance is clamped and
    counted, on the first copy of a key and on a later one, once each."""
    now = ms_now()
    r_wire, r_cols = pair()
    for r in (r_wire, r_cols):
        r.engine.created_at_tolerance_ms = 300
    try:
        n_fused, _got, delta = await wire_against_columns(
            r_wire, r_cols, [rpc([1, (2, 400, 0), (1, -400, 0), 2], now)], now
        )
        assert n_fused == 2 and delta["created_at_clamped"] == 2
    finally:
        r_wire.close()
        r_cols.close()


@pytest.mark.parametrize("shadow", [False, True], ids=["retry", "shadow"])
@async_test
async def test_a_split_chunk_through_the_feedback_paths(shadow, monkeypatch):
    """One bucket of eight slots and 24 keys: pass 0's claims drop and are
    retried on the engine thread; with a shadow attached (ported in PR 42:
    the pipelined launch decides only the keys the table holds; in PR 44:
    the issue job first brings the chunk's shadowed keys back, as many as
    the bucket has lanes) a pass hands the rows of the keys it still does
    not hold to the same retry, which faults them in
    (`LocalEngine._decide_faulting`). Both select rows of a pass by its
    mask, which leaves out the later copies of a key: a copy taken for a
    miss would be applied twice."""
    calls = []
    redispatch = LocalEngine._redispatch_rows
    monkeypatch.setattr(
        LocalEngine, "_redispatch_rows",
        lambda self, *a, **k: calls.append(self) or redispatch(self, *a, **k),
    )
    now = ms_now()
    r_wire, r_cols = pair(capacity=8, shadow=shadow)
    try:
        for lo in (0, 8, 16):
            fill = rpc(range(lo, lo + 8), now)
            assert_same(
                await r_wire.check(fill.cols, now_ms=now),
                await r_cols.check(fill.cols, now_ms=now),
            )
        calls.clear()
        parts = [
            rpc(list(range(12)) + [3, 3, 20], now + 5),
            rpc(list(range(12, 24)) + [20, 3, 1], now + 5),
        ]
        n_fused, got, _delta = await wire_against_columns(
            r_wire, r_cols, parts, now + 5
        )
        assert n_fused == 4 and not got.err.any()
        # the retry; with a shadow, the residue of the fault-back ahead of
        # the launch: keys 0-15 lie in the shadow and the issue job's merge
        # brings eight of them back (key 3 among them: the bucket's eight
        # lanes), pushing keys 16-23 out. Pass 0 defers the sixteen keys
        # that are not resident; passes 1 and 2 (keys 3, 20, 1 and 3, 20)
        # defer key 20, which was pushed out before they ran; pass 3 is key
        # 3 alone and hits. Three calls, where every pass made one (four)
        # while nothing came back before the launch.
        want_calls = 3 if shadow else 1
        assert calls.count(r_wire.engine) == want_calls
        assert calls.count(r_cols.engine) == want_calls
    finally:
        r_wire.close()
        r_cols.close()


@pytest.mark.parametrize("seed", range(4))
def test_a_retrys_rows_are_packed_as_the_whole_chunk_would_be(seed):
    """The batch a retry (or a tiered table's miss path) re-dispatches is
    packed from the selected rows' columns alone (`_LazyWireBatch.select`,
    on the fetch thread): for every pass of a split chunk it is, row for
    row, what cutting the whole chunk's HostBatch gives — the later copies
    the grid left out, clamped stamps and the aggregate included."""
    rng = np.random.default_rng(seed)
    now = engine_mod.ms_now()
    eng = LocalEngine(capacity=4096, wire="compact")
    eng.created_at_tolerance_ms = 150
    rows = lambda n: [
        (int(k), int(rng.integers(-200, 200)), 0, int(rng.integers(0, 4)))
        for k in rng.integers(0, 40, size=n)
    ]
    pending = prepare_check_wire(eng, [rpc(rows(100), now), rpc(rows(80), now)], now_ms=now)
    assert pending is not None and len(pending.passes) == 8
    for _p, n, batch, _staged in pending.passes:
        pick = np.sort(rng.choice(n, size=min(n, 9), replace=False))
        cut = engine_mod.HostBatch(*[f[pick] for f in batch._materialize()])
        batch._hb = None  # `select` would cut the same HostBatch otherwise
        for name, a, b in zip(cut._fields, batch.select(pick), cut):
            assert np.array_equal(np.asarray(a), np.asarray(b)), name


def test_what_needs_a_single_pass_still_refuses_a_repeated_key():
    """The in-trace cascade fold is one pass: with a repeated key in the
    chunk the staging is refused whole, as before; without one, it is
    served."""
    now = ms_now()
    eng = LocalEngine(capacity=4096, wire="compact")
    unique, repeated = [rpc([1, 2, 3], now)], [rpc([1, 2], now), rpc([3, 1], now)]
    split = prepare_check_wire(eng, repeated, now_ms=now)
    assert [n for _p, n, _b, _s in split.passes] == [4, 1]
    assert split.hb.active.tolist() == [True, True, True, False]

    def with_level_bits(parts):
        # what an engine-level caller assembles: level 1 on the second row
        wb = parts[0]
        lanes, behavior = wb.lanes.copy(), wb.cols.behavior.copy()
        lanes[3, 1] |= np.int32(1 << wire_mod.LEVEL_SHIFT)
        behavior[1] |= 1 << 8
        return [
            wb._replace(lanes=lanes, cols=wb.cols._replace(behavior=behavior)),
            *parts[1:],
        ]

    cascade = prepare_check_wire(eng, with_level_bits(unique), now_ms=now)
    assert cascade.casc and cascade.casc_intrace and len(cascade.passes) == 1
    assert prepare_check_wire(eng, with_level_bits(repeated), now_ms=now) is None
    # and an engine with no exact pass for the grid to be
    eng.max_exact_passes = 1
    assert prepare_check_wire(eng, [rpc([1, 1], now)], now_ms=now) is None
