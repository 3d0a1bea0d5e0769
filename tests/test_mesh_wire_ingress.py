"""The mesh engine takes the parser's lanes.

A `ShardedEngine` as a TPU resolves it (route=device, dedup=device, compact
wire, no Store) is wire-capable: `prepare_check_wire` stages a chunk's
pre-packed lanes through the one native staging call the local engine uses,
every copy of a key kept for the in-trace fold, and `stage_wire` lays the
result out as `_stage_a2a` lays out the same rows from columns. These tests
hold the grid to that byte for byte (native call and NumPy twin alike, over
chunk sizes that hit several widths, repeated keys, error rows), the answers
and the stats to the columns path's through `EngineRunner.check_wire`, every
chunk the mesh must decline to the columns staging, and an exchange
overflow on a fused chunk to its retry. CPU meshes of 4 and 8 devices.
"""

import dataclasses

import numpy as np
import pytest

import jax

from gubernator_tpu import native, tracing
from gubernator_tpu.ops import engine as engine_mod
from gubernator_tpu.ops.batch import pack_columns
from gubernator_tpu.ops.engine import ms_now, prepare_check_wire
from gubernator_tpu.parallel import ShardedEngine, make_mesh
from gubernator_tpu.parallel.global_sync import GlobalShardedEngine
from gubernator_tpu.parallel.mesh import shard_of
from gubernator_tpu.service.metrics import DaemonMetrics
from gubernator_tpu.service.runner import EngineRunner
from gubernator_tpu.service.wire import concat_columns
from gubernator_tpu.store import RecordingStore

from tests.test_native import _blank_columns, _level_bit
from tests.test_observability import _stage_sums
from tests.test_runner_chain import assert_same, async_test, wire_batch
from tests.test_wire_split import EMPTY_KEY, EMPTY_NAME, rpc

pytestmark = pytest.mark.skipif(
    native.load() is None, reason="native toolchain unavailable"
)

TPU = {"route": "device", "dedup": "device", "wire": "compact"}
GLOBAL, MULTI_REGION = 2, 16  # types.Behavior


@pytest.fixture(scope="module", params=[4, 8])
def mesh(request):
    assert len(jax.devices()) == 8, "tests require the 8-device CPU mesh"
    return make_mesh(request.param)


def new_engine(mesh, cls=ShardedEngine, **kw):
    return cls(mesh, **{"capacity_per_shard": 4096, **TPU, **kw})


def chunk(n, now, repeats, per_rpc=1000):
    """`n` rows in RPCs of `per_rpc`: distinct keys, or with `repeats` keys
    drawn from a third as many; one row in 53 of a larger chunk is an error
    row (an empty key or an empty name), the first of them not the first."""
    rng = np.random.default_rng(n)
    keys = rng.integers(0, max(2, n // 3), n) if repeats else np.arange(n)
    rows = [
        (EMPTY_KEY, EMPTY_NAME)[i % 2] if n > 8 and i % 53 == 5 else int(k)
        for i, k in enumerate(keys)
    ]
    return [rpc(rows[i:i + per_rpc], now) for i in range(0, n, per_rpc)]


# ------------------------------------------------------------- (a) the grid


@pytest.mark.parametrize("staging", ["native", "numpy"])
@pytest.mark.parametrize("repeats", [False, True], ids=["distinct", "repeats"])
@pytest.mark.parametrize("n", [1, 7, 8, 1000, 2977, 12000])
def test_the_fused_grid_is_the_columns_grid_byte_for_byte(
    mesh, n, repeats, staging, monkeypatch
):
    """Row i on device i // c, c the bucketed share of a device, the base in
    every block's trailing column: what `_stage_a2a` builds from the packed
    columns, the native call and the NumPy twin both build from the lanes."""
    now = ms_now()
    eng = new_engine(mesh)
    assert eng.supports_wire_ingress and eng.folds_copies
    parts = chunk(n, now, repeats)
    if staging == "numpy":  # a host with no toolchain (the parts are parsed)
        monkeypatch.setattr(native, "load", lambda: None)
    a = engine_mod._assemble_wire_parts(eng, parts, now_ms=now)
    assert a is not None and a.native == (staging == "native")
    D = eng.n_shards
    c = max(8, 1 << (-(-n // D) - 1).bit_length())
    assert a.pad == eng.wire_pad(n) == D * c and a.chunk.grid.shape == (5, D * c + 1)
    # every copy kept, nothing behind the grid
    assert a.chunk.later == 0 and a.chunk.passes == []
    cols = concat_columns([p.cols for p in parts])
    assert (a.chunk.first == (cols.err == 0)).all()
    got = eng.stage_wire(a.chunk.grid, a.chunk.math)
    hb, _err = pack_columns(cols, now, tolerance_ms=eng.created_at_tolerance_ms)
    want = eng._stage_a2a(hb)
    g, w = np.asarray(got.dev), np.asarray(want.dev)
    assert g.shape == w.shape == (D, 5, c + 1) and g.dtype == w.dtype == np.int32
    assert g.tobytes() == w.tobytes()
    assert got[:1] + got[2:6] == want[:1] + want[2:6]  # c, math, wire, base, needs_full
    assert want.wire and want.lanes is None and got.lanes is a.chunk.grid
    assert eng.wire_bytes["put"] == 2 * g.nbytes


def test_a_pooled_grid_carries_nothing_over_from_its_last_use(mesh):
    """On a TPU the grid is built in a ring of reused host buffers
    (`_StagingPool`; a CPU engine has none): a smaller chunk staged into the
    buffer a larger one of the same width left is still the columns' grid."""
    from gubernator_tpu.parallel.sharded import _StagingPool

    now = ms_now()
    eng, plain = new_engine(mesh), new_engine(mesh)
    eng._pool = _StagingPool(depth=1)
    for n, repeats in ((1000, False), (700, True), (520, False)):  # one c
        parts = chunk(n, now, repeats, per_rpc=300)
        a = engine_mod._assemble_wire_parts(eng, parts, now_ms=now)
        got = np.asarray(eng.stage_wire(a.chunk.grid, a.chunk.math).dev).copy()
        hb, _err = pack_columns(
            concat_columns([p.cols for p in parts]), now,
            tolerance_ms=eng.created_at_tolerance_ms,
        )
        assert got.tobytes() == np.asarray(plain._stage_a2a(hb).dev).tobytes()
        assert got.tobytes() == np.asarray(eng._stage_a2a(hb).dev).tobytes()


def test_the_numpy_twin_is_the_native_call_with_every_copy_kept():
    """`keep_copies` is one argument of one staging: both implementations
    take it, and agree on every field of what they return."""
    from gubernator_tpu.ops import wire as wire_mod

    from tests.test_native import STAGE_NOW, _same_staging

    for rows in ([[1, 2, 1, 7, 1]], [[7] * 9, [EMPTY_KEY, 7, 8]],
                 [[(1, 200, 0), (1, -200, 0), 2], [(3, 9_000, 0)]]):
        parts = [rpc(r, STAGE_NOW) for r in rows]
        args = (parts, STAGE_NOW, 300, 64, 8)
        got = wire_mod.stage_wire_chunk(native.load(), *args, 16, True)
        want = engine_mod._stage_chunk_numpy(*args, True)
        _same_staging(got, want)
        assert want.later == 0 and want.passes == []
        # the same rows split for a local engine: the default
        split = engine_mod._stage_chunk_numpy(*args)
        _same_staging(wire_mod.stage_wire_chunk(native.load(), *args, 16), split)
        assert split.later > 0


# ----------------------------------------------- (b) answers through the runner


def pair(mesh, cls=ShardedEngine, metrics=None, **kw):
    """Two runners over equal engines: one for the wire, one for columns."""
    return [
        EngineRunner(new_engine(mesh, cls, **kw), metrics if i == 0 else None)
        for i in range(2)
    ]


def close(*runners):
    for r in runners:
        r.close()


async def wire_against_columns(r_wire, r_cols, parts, now):
    """`parts` as one chunk through `check_wire` on one engine and as
    concatenated columns through `check` on its twin: answers, the stats
    delta and every touched key's stored row are equal. Returns (passes the
    fused staging issued, the answer, the wire engine's stats delta)."""
    def stats():
        for r in (r_wire, r_cols):  # behind the dispatch's `_apply`
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
    deltas = [{k: a[k] - b[k] for k in a} for a, b in zip(stats(), before)]
    staged, finished = (
        deltas[0].pop(k) for k in ("native_staged", "native_finished")
    )
    assert not deltas[1].pop("native_staged") + deltas[1].pop("native_finished")
    assert deltas[0] == deltas[1] and finished == staged
    fps = np.unique(cols.fp[cols.err == 0])
    (found_w, rows_w), (found_c, rows_c) = (
        r.engine.read_state(fps) for r in (r_wire, r_cols)
    )
    assert found_w.all() and found_c.all() and (rows_w == rows_c).all()
    (n_fused,) = fused
    assert staged == (n_fused > 0)
    return n_fused, got, deltas[0]


@pytest.mark.parametrize("cls", [ShardedEngine, GlobalShardedEngine])
@async_test
async def test_copies_of_a_key_are_one_aggregate_on_either_staging(mesh, cls):
    """A chunk that repeats keys, with error rows in it, is ONE fused pass
    on the mesh (the program folds the copies): every copy answers the
    aggregate from occurrence 0, as the columns staging answers it, and the
    dispatch counts as fused and natively staged."""
    now = ms_now()
    r_wire, r_cols = pair(mesh, cls)
    try:
        parts = [
            rpc([1, 2, 1, EMPTY_KEY, 7, 1], now), rpc([2, EMPTY_NAME, 3], now),
            rpc([1] * 9 + [4], now),
        ]
        fused, got, delta = await wire_against_columns(r_wire, r_cols, parts, now)
        assert fused == 1 and delta["dispatches"] == 1 and delta["checks"] == 19
        assert r_wire.engine.stats.native_staged == 1
        key = np.array([1, 2, 1, 0, 7, 1, 2, 0, 3] + [1] * 9 + [4])
        ok = got.err == 0
        assert (~ok).sum() == 2
        # limit 10: key 1 came 12 times and is refused whole, as an aggregate
        assert (got.status[ok & (key == 1)] == 1).all()
        assert (got.remaining[ok & (key == 1)] == 10).all()
        assert (got.remaining[ok & (key == 2)] == 8).all()
        assert (got.remaining[ok & np.isin(key, (3, 4, 7))] == 9).all()
        assert delta["cache_misses"] == 5 and delta["over_limit"] == 1
        # the next chunk finds the rows the first left
        fused, got, delta = await wire_against_columns(
            r_wire, r_cols, [rpc([2, 3, 9], now + 1)], now + 1
        )
        assert fused == 1 and got.remaining.tolist() == [7, 8, 9]
        assert (delta["cache_hits"], delta["cache_misses"]) == (2, 1)
    finally:
        close(r_wire, r_cols)


@async_test
async def test_a_batcher_counts_the_mesh_dispatch_as_fused(mesh):
    """`batcher.fused_dispatches` and `engine.native_staged`, which the
    benchmark's `fused_dispatch_share` and `/v1/debug/pipeline` read."""
    from gubernator_tpu.service.batcher import Batcher

    now = ms_now()
    runner = EngineRunner(new_engine(mesh, GlobalShardedEngine))
    batcher = Batcher(runner, batch_wait_ms=0.5, workers=1)
    try:
        rc = await batcher.check(rpc([1, 2, 1], now))
        assert rc.remaining.tolist() == [8, 9, 8] and not rc.err.any()
        runner._exec.submit(lambda: None).result()
        seen = batcher.debug()
        assert (seen["dispatches"], seen["fused_dispatches"]) == (1, 1)
        assert seen["split_dispatches"] == seen["column_dispatches"] == 0
        assert runner.engine.stats.native_staged == 1
    finally:
        await batcher.drain()
        runner.close()


# ------------------------------------------------------------- (c) declines


def _global_row(now):
    return [rpc([1, (2, 0, GLOBAL), 3], now)]


DECLINES = {
    # chunk → what the wire-capable mesh cannot stage from its lanes
    "global_row": _global_row,
    "multi_region_row": lambda now: [rpc([1, 2], now), rpc([(3, 0, MULTI_REGION)], now)],
    "global_row_without_a_summary": lambda now: [
        p._replace(summary=None) for p in _global_row(now)
    ],
    "cascade_bits": lambda now: _level_bit([rpc([1, 2, 3], now)]),
    "stamp_out_of_budget": lambda now: [rpc([1, (2, 600, 0)], now)],
    "non_encodable_row": lambda now: [wire_batch([1, 2, 3, 4], now, gregorian=[3])],
}


@pytest.mark.parametrize("case", DECLINES)
@async_test
async def test_what_cannot_ride_is_staged_as_columns(mesh, case, monkeypatch):
    """Each chunk the lanes cannot speak for lands on the columns staging of
    the same prep job (`put_miss`, then `put`) with the answers of the
    columns path; a GLOBAL row reaches `prepare_columns`, where the replica
    fork reads it."""
    now = ms_now()
    metrics = DaemonMetrics()
    r_wire, r_cols = pair(mesh, GlobalShardedEngine, metrics)
    forked = []
    hook = GlobalShardedEngine.prepare_columns
    monkeypatch.setattr(
        GlobalShardedEngine, "prepare_columns",
        lambda self, cols, now_ms=None: forked.append(
            (self, hook(self, cols, now_ms=now_ms))
        ) or forked[-1][1],
    )
    try:
        parts = DECLINES[case](now)
        assert r_wire.engine.supports_wire_ingress
        assert prepare_check_wire(r_wire.engine, parts, now_ms=now) is None
        fused, got, _delta = await wire_against_columns(r_wire, r_cols, parts, now)
        assert fused == 0 and not got.err.any()
        counts = {s: int(c) for s, (_sum, c) in _stage_sums(metrics).items()}
        assert (counts["put_miss"], counts["put"]) == (1, 1)
        mine = [p for eng, p in forked if eng is r_wire.engine]
        assert len(mine) == 1 and (mine[0] is not None) == case.startswith("global")
        # a chunk beside it with nothing to decline still fuses
        fused, _got, _delta = await wire_against_columns(
            r_wire, r_cols, [rpc([11, 12], now)], now
        )
        assert fused == 1
    finally:
        close(r_wire, r_cols)


@pytest.mark.parametrize("how", ["route_host", "dedup_host", "wire_full", "store"])
@async_test
async def test_an_engine_that_is_not_wire_capable_takes_columns(mesh, how):
    """What the engine can observe of itself decides: a host-routed grid, a
    host plan, the full-width wire and a Store each take the parser's
    columns (every CPU mesh by default), answered as ever."""
    now = ms_now()
    kw = {
        "route_host": {"route": "host"}, "dedup_host": {"dedup": "host"},
        "wire_full": {"wire": "full"}, "store": {"store": None},
    }[how]
    metrics = DaemonMetrics()
    runners = pair(mesh, ShardedEngine, metrics, **kw)
    if how == "store":
        for r in runners:
            r.engine.store = RecordingStore()
    try:
        eng = runners[0].engine
        assert not eng.supports_wire_ingress
        parts = [rpc([1, 2, 1, 3], now)]
        assert prepare_check_wire(eng, parts, now_ms=now) is None
        fused, got, _delta = await wire_against_columns(*runners, parts, now)
        assert fused == 0 and not got.err.any()
        # exact passes under host dedup, one aggregate otherwise
        assert got.remaining.tolist() == (
            [9, 9, 8, 9] if how == "dedup_host" else [8, 9, 8, 9]
        )
        assert "put_miss" not in _stage_sums(metrics)
    finally:
        close(*runners)


# ------------------------------------------------------------- (d) overflow


@async_test
async def test_an_exchange_overflow_on_a_fused_chunk_is_retried(mesh, monkeypatch):
    """2,048 keys of one owner shard overflow that pair's exchange capacity:
    the rows the exchange dropped come back unprocessed, the fused chunk's
    lazy batch packs just those rows from the parser's columns, and the
    retry on the engine thread answers them; counted once, like columns."""
    now = ms_now()
    r_wire, r_cols = pair(mesh, capacity_per_shard=1 << 16)  # no bucket fills
    D = r_wire.engine.n_shards
    selects = []
    select = engine_mod._LazyWireBatch.select
    monkeypatch.setattr(
        engine_mod._LazyWireBatch, "select",
        lambda self, rows: selects.append(len(rows)) or select(self, rows),
    )
    try:
        probe = rpc(list(range(24_000)), now)
        owner = shard_of(probe.cols.fp, D)
        picked = [int(k) for k in np.nonzero(owner == owner[0])[0][:2048]]
        assert len(picked) == 2048
        parts = [rpc(picked[:1000], now), rpc(picked[1000:], now)]
        fused, got, delta = await wire_against_columns(r_wire, r_cols, parts, now)
        assert fused == 1 and not got.err.any()
        assert (got.remaining == 9).all() and (got.status == 0).all()
        assert r_wire.engine.a2a_overflow == r_cols.engine.a2a_overflow > 0
        assert selects and 0 < selects[0] <= r_wire.engine.a2a_overflow
        assert delta["cache_misses"] == 2048 and delta["dispatches"] > 1
    finally:
        close(r_wire, r_cols)


# ---------------------------------------------------------- (e) the finish


@pytest.mark.parametrize("n", [7, 1000, 2977])
def test_the_native_finish_is_the_numpy_unroute_byte_for_byte(mesh, n):
    """A fused mesh dispatch finished by the one native call
    (`ShardedEngine.finish_wire` over the (D, c+2, 4) egress grid) and by
    `_unroute` + `finish_staged` + the NumPy scatter, both handed one
    made-up grid in which every flag turns up — a dropped row, an
    unprocessed one, a member of an in-trace aggregate, the reset sentinel —
    on live rows and on the error rows' zeroed lanes: the same columns, the
    same `err`, the same stats delta and overflow count, the same rows
    retried with the same `uncounted` mask."""
    now = ms_now()
    parts = chunk(n, now, repeats=True)
    rng = np.random.default_rng(n)
    grid, out = None, []
    for finish in (engine_mod._finish_native, engine_mod._finish_numpy):
        eng = new_engine(mesh)
        pending = prepare_check_wire(eng, parts, now_ms=now)
        (p, n_rows, lazy, staged), = pending.passes
        if grid is None:
            D, c = eng.n_shards, staged.c
            grid = rng.integers(-(2**31), 2**31, (D, c + 2, 4)).astype(np.int32)
            flags = rng.integers(0, 4, (D, c))  # status, hit
            live = np.zeros(D * c, bool)
            live[:n_rows] = lazy.active
            lost = live.reshape(D, c) & (rng.random((D, c)) < 0.1)
            unproc = lost & (rng.random((D, c)) < 0.5)
            member = rng.random((D, c)) < 0.2
            grid[:, :c, 3] = flags | lost << 2 | unproc << 3 | member << 4
            grid[:, :c, 2][rng.random((D, c)) < 0.2] = -(2**31)
            grid[:, c] = rng.integers(0, 1000, (D, 4))
            assert lost.any() and unproc.any() and (member & ~live.reshape(D, c)).any()
        pending.passes[0][3] = (staged, grid.copy())
        cols = _blank_columns(n_rows)
        delta, calls = engine_mod.EngineStats(), []

        def redispatch(sub, m, uncounted=None):
            fp = np.asarray(sub.fp[:m])
            calls.append((fp.copy(), uncounted.copy()))
            return (
                (fp % 2).astype(np.int32), fp % 1009, fp % 997, fp % 991 + 1,
                fp % 3 == 0, fp % 5 == 0,
            )

        eng._redispatch_rows = redispatch
        assert finish(eng, pending, cols, delta, lambda fn: fn()) is True
        out.append((
            cols, bytes(pending.err), dataclasses.asdict(delta), calls,
            eng.a2a_overflow, eng.wire_bytes["fetch"],
        ))
    got, want = out
    for g, w in zip(got[0], want[0]):
        assert g.dtype == w.dtype and g.tobytes() == w.tobytes()
    assert got[1] == want[1] and got[4] == want[4] > 0 and got[5] == want[5] > 0
    assert got[2].pop("native_finished") == 1 and not want[2].pop("native_finished")
    assert got[2] == want[2] and got[2]["cache_hits"] > 0 < got[2]["over_limit"]
    (fp_g, unc_g), = got[3]
    (fp_w, unc_w), = want[3]
    assert (fp_g == fp_w).all() and (unc_g == unc_w).all() and unc_g.any()


@async_test
async def test_a_mesh_chunk_answers_the_same_finished_natively_or_by_numpy(
    mesh, monkeypatch
):
    """One chunk with repeats and error rows through `check_wire` on two
    equal mesh engines, the second with `native.load()` patched to None:
    same bytes, same stats, `native_finished` 1 and 0, and the decode is a
    `shard_unroute` sample on both."""
    now = ms_now()
    parts = chunk(2977, now, repeats=True)
    metrics = [DaemonMetrics(), DaemonMetrics()]
    r_native, r_numpy = (
        EngineRunner(new_engine(mesh), metrics[i]) for i in range(2)
    )
    try:
        disp = [tracing.Dispatch(seq=i, rows=2977) for i in range(2)]
        got = await r_native.check_wire(parts, now_ms=now, disp=disp[0])
        monkeypatch.setattr(native, "load", lambda: None)
        assert_same(await r_numpy.check_wire(parts, now_ms=now, disp=disp[1]), got)
        for r in (r_native, r_numpy):
            r._exec.submit(lambda: None).result()
        a, b = (dataclasses.asdict(r.engine.stats) for r in (r_native, r_numpy))
        assert [a.pop(k) for k in ("native_staged", "native_finished")] == [1, 1]
        assert [b.pop(k) for k in ("native_staged", "native_finished")] == [0, 0]
        assert a == b and a["checks"] == 2977
        for m in metrics:
            assert _stage_sums(m)["shard_unroute"][1] == 1
        assert r_native.engine.wire_bytes == r_numpy.engine.wire_bytes
    finally:
        close(r_native, r_numpy)
