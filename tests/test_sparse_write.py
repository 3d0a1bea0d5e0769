"""Block-sparse write parity suite (ops/kernel2._write_sparse).

`write="sparse"` must be bit-identical to `write="xla"` (and the dense
sweep) in BOTH table state and responses: the sparse grid only changes
which blocks the Pallas pipeline streams, never what lands in them.
Exercised on the CPU interpret lowering (the XLA-emulated path tier-1
runs): random token/leaky/mixed traffic, conflict-heavy same-bucket
batches, block-boundary slots (bucket 0, bucket BLK-1, the last block),
the sharded mesh path, and the GLOBAL collective-sync install path on the
virtual 8-device mesh.

Every parity config asserts `resolve_write` actually resolved "sparse" —
a table too small for the coverage crossover would silently fall back to
the sweep and test nothing.
"""

import numpy as np
import pytest

from gubernator_tpu.ops.batch import RequestColumns
from gubernator_tpu.ops.engine import LocalEngine
from gubernator_tpu.ops.kernel2 import (
    resolve_write,
    sparse_geometry,
    sweep_geometry,
)
from gubernator_tpu.parallel import make_mesh
from gubernator_tpu.parallel.global_sync import GlobalShardedEngine
from gubernator_tpu.parallel.sharded import ShardedEngine
from gubernator_tpu.types import Algorithm, Behavior, RateLimitRequest, SECOND

NOW = 1_700_000_000_000
# 2^15 buckets: large enough that a ≤64-row pass stays under the sparse
# coverage crossover (64 steps × 64 rows × 4 ≪ 32768), small enough for CPU
CAP = 1 << 18


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(8)


def _engines(**kw):
    return {
        w: LocalEngine(capacity=CAP, write_mode=w, **kw)
        for w in ("xla", "sweep", "sparse")
    }


def _assert_parity(engines, reqs, now):
    outs = {w: e.check(reqs, now_ms=now) for w, e in engines.items()}
    for w in ("sweep", "sparse"):
        for i, (a, b) in enumerate(zip(outs["xla"], outs[w])):
            assert (a.status, a.limit, a.remaining, a.reset_time, a.error) == (
                b.status, b.limit, b.remaining, b.reset_time, b.error,
            ), f"write={w} row {i}"


def _assert_tables_equal(engines):
    base = np.asarray(engines["xla"].table.rows)
    for w in ("sweep", "sparse"):
        assert np.array_equal(base, np.asarray(engines[w].table.rows)), w


def _random_requests(rng, n, keyspace, now, algo=None):
    reqs = []
    for _ in range(n):
        a = algo
        if a is None:
            a = (
                Algorithm.TOKEN_BUCKET
                if rng.random() < 0.5
                else Algorithm.LEAKY_BUCKET
            )
        behavior = 0
        r = rng.random()
        if r < 0.15:
            behavior |= Behavior.RESET_REMAINING
        if 0.15 <= r < 0.3:
            behavior |= Behavior.DRAIN_OVER_LIMIT
        reqs.append(
            RateLimitRequest(
                name="sp",
                unique_key=f"k{rng.integers(keyspace)}",
                hits=int(rng.integers(0, 4)),
                limit=int(rng.integers(1, 20)),
                duration=int(rng.integers(1, 5)) * SECOND,
                algorithm=a,
                behavior=behavior,
                created_at=now,
            )
        )
    return reqs


def test_sparse_resolves_sparse_at_parity_geometry():
    """Tripwire: if this fails, every parity test below is testing the
    dense sweep twice instead of the sparse grid."""
    eng = LocalEngine(capacity=CAP)
    nb = eng.table.rows.shape[0]
    # engine pads ≤64-row passes to 64
    assert resolve_write("sparse", nb, 64) == "sparse"


@pytest.mark.parametrize("algo", [None, Algorithm.TOKEN_BUCKET,
                                  Algorithm.LEAKY_BUCKET])
def test_sparse_parity_random_traffic(algo):
    """token-only / leaky-only / mixed random streams: responses and final
    table state bit-identical across all three write modes."""
    rng = np.random.default_rng(3 if algo is None else int(algo))
    engines = _engines()
    now = NOW
    for _ in range(4):
        reqs = _random_requests(rng, 48, keyspace=70, now=now, algo=algo)
        _assert_parity(engines, reqs, now)
        now += int(rng.integers(0, 2500))
    _assert_tables_equal(engines)
    ex = engines["xla"].stats
    for w in ("sweep", "sparse"):
        s = engines[w].stats
        assert (s.cache_hits, s.cache_misses, s.over_limit) == (
            ex.cache_hits, ex.cache_misses, ex.over_limit,
        ), w


def _cols(fps, now, hits=1):
    n = fps.shape[0]
    return RequestColumns(
        fp=np.asarray(fps, dtype=np.int64),
        algo=np.zeros(n, dtype=np.int32),
        behavior=np.zeros(n, dtype=np.int32),
        hits=np.full(n, hits, dtype=np.int64),
        limit=np.full(n, 100, dtype=np.int64),
        burst=np.zeros(n, dtype=np.int64),
        duration=np.full(n, 60_000, dtype=np.int64),
        created_at=np.full(n, now, dtype=np.int64),
        err=np.zeros(n, dtype=np.int8),
    )


def _cols_parity(engines, fps, now):
    outs = {
        w: e.check_columns(_cols(fps, now), now_ms=now)
        for w, e in engines.items()
    }
    for w in ("sweep", "sparse"):
        for f in outs["xla"]._fields:
            np.testing.assert_array_equal(
                getattr(outs["xla"], f), getattr(outs[w], f),
                err_msg=f"write={w} col {f}",
            )


def test_sparse_parity_conflict_heavy_same_bucket():
    """12 distinct keys forced into ONE bucket (direct fp injection:
    bucket = fp % NB): inserts overflow the 8 lanes, the claim dedup and
    retry/eviction machinery fires, and every write mode must persist the
    same survivors."""
    engines = _engines()
    nb = engines["xla"].table.rows.shape[0]
    target_bucket = 7
    fps = np.array([target_bucket + nb * k for k in range(1, 13)],
                   dtype=np.int64)
    now = NOW
    for step in range(3):
        _cols_parity(engines, fps, now)
        now += 1000
    _assert_tables_equal(engines)


def test_sparse_parity_block_boundary_slots():
    """Targets pinned to sparse-block edges: bucket 0 (slot 0), bucket
    BLK-1 (last bucket of block 0), the first bucket of the last block, and
    bucket NB-1 (the table's final row) — the off-by-one surface of the
    dirty-block index math."""
    engines = _engines()
    nb = engines["xla"].table.rows.shape[0]
    blk, _u, _g = sparse_geometry(nb, 64)
    buckets = [0, blk - 1, nb - blk, nb - 1]
    fps = []
    for b in buckets:
        for k in range(1, 4):  # several keys per boundary bucket
            fps.append((b + nb * k) or nb)  # fp 0 is the empty sentinel
    fps = np.array(fps, dtype=np.int64)
    now = NOW
    for step in range(3):
        _cols_parity(engines, fps, now)
        now += 1000
    _assert_tables_equal(engines)


def test_sparse_parity_sharded_mesh(mesh):
    """The sharded path (one table shard per device, shard_map dispatch)
    with write_mode="sparse" matches "xla" row-for-row on the virtual
    8-device CPU mesh."""
    kw = dict(capacity_per_shard=CAP)
    ex = ShardedEngine(mesh, write_mode="xla", **kw)
    es = ShardedEngine(mesh, write_mode="sparse", **kw)
    rng = np.random.default_rng(11)
    now = NOW
    for _ in range(3):
        reqs = _random_requests(rng, 64, keyspace=90, now=now)
        rx = ex.check(reqs, now_ms=now)
        rs = es.check(reqs, now_ms=now)
        for i, (a, b) in enumerate(zip(rx, rs)):
            assert (a.status, a.remaining, a.reset_time, a.error) == (
                b.status, b.remaining, b.reset_time, b.error,
            ), f"row {i}"
        now += 1500
    assert np.array_equal(ex.snapshot(), es.snapshot())


def test_sparse_parity_global_install(mesh):
    """The GLOBAL plane end-to-end with write_mode="sparse": replica
    answers, owner applies, and the collective sync's broadcast INSTALL all
    run the sparse write and must converge to the same authoritative and
    replica state as "xla"."""
    kw = dict(capacity_per_shard=CAP, sync_out=64)
    ex = GlobalShardedEngine(mesh, write_mode="xla", **kw)
    es = GlobalShardedEngine(mesh, write_mode="sparse", **kw)
    now = NOW
    reqs = [
        RateLimitRequest(
            name="g", unique_key=f"gk{i}", hits=1, limit=10,
            duration=60_000, behavior=Behavior.GLOBAL, created_at=now,
        )
        for i in range(24)
    ]
    for eng in (ex, es):
        for home in (0, 3):
            eng.check(reqs, now_ms=now, home_shard=home)
        eng.sync(now_ms=now)
    # post-sync: answers come from replica installs written sparse vs xla
    rx = ex.check(reqs, now_ms=now + 10, home_shard=5)
    rs = es.check(reqs, now_ms=now + 10, home_shard=5)
    for i, (a, b) in enumerate(zip(rx, rs)):
        assert (a.status, a.remaining, a.reset_time) == (
            b.status, b.remaining, b.reset_time,
        ), f"row {i}"
    ex.sync(now_ms=now + 10)
    es.sync(now_ms=now + 10)
    assert np.array_equal(ex.snapshot(), es.snapshot())
    assert np.array_equal(
        np.asarray(ex.replica.rows), np.asarray(es.replica.rows)
    )
    gx, gs = ex.global_stats, es.global_stats
    assert (gx.broadcasts_applied, gx.updates_installed) == (
        gs.broadcasts_applied, gs.updates_installed,
    )


def test_sparse_geometry_bounds():
    for nb, batch in [(1 << 15, 64), (1 << 18, 4096), (1 << 21, 16384),
                      (512, 16), (2048 * 3, 1024)]:
        blk, u, g = sparse_geometry(nb, batch)
        assert nb % blk == 0, (nb, batch)
        assert blk * u <= 1 << 19
        assert u & (u - 1) == 0 or u == batch
        assert g == min(nb // blk, batch)
        if batch >= u:
            assert batch % u == 0


def test_resolve_write_crossover(monkeypatch):
    # big batch over a small table → worst-case coverage crosses → sweep
    assert resolve_write("sparse", 1 << 11, 1 << 17) == "sweep"
    # serving shape over a big table → sparse
    assert resolve_write("sparse", 1 << 21, 4096) == "sparse"
    # other modes pass through untouched
    assert resolve_write("sweep", 1 << 11, 1 << 17) == "sweep"
    assert resolve_write("xla", 1 << 21, 64) == "xla"
    with pytest.raises(ValueError):
        resolve_write("bogus", 1 << 21, 64)
    # the crossover knob moves the boundary: an absurdly strict factor
    # pushes even the serving shape back to the sweep
    monkeypatch.setenv("GUBER_WRITE_SPARSE_CROSSOVER", "1e9")
    assert resolve_write("sparse", 1 << 21, 4096) == "sweep"
    monkeypatch.setenv("GUBER_WRITE_SPARSE_CROSSOVER", "1")
    assert resolve_write("sparse", 1 << 21, 16384) == "sparse"


def test_sparse_geometry_matches_probe_window_contract():
    """The probe marks window overflow with the SAME (blk, u) the write
    uses; sanity-pin that sparse geometry never hands the probe a window
    smaller than the dense floor (64) for pow2 batches ≥ 64."""
    for nb in (1 << 15, 1 << 18, 1 << 21):
        for batch in (64, 1024, 4096):
            _blk, u, _g = sparse_geometry(nb, batch)
            assert u >= min(64, batch)
            _dblk, du = sweep_geometry(nb, batch)
            assert du >= min(64, batch)


def test_sparse_crossover_is_layout_aware(monkeypatch):
    """The crossover is byte-denominated: a geometry whose worst-case dirty
    coverage sits just past the FULL-layout bound still resolves sparse on
    a 32 B packed layout (half the bytes per row → twice the row budget)."""
    from gubernator_tpu.ops.layout import FULL, GCRA32, TOKEN32

    monkeypatch.setenv("GUBER_WRITE_SPARSE_BLK", "64")
    monkeypatch.setenv("GUBER_WRITE_SPARSE_CROSSOVER", "4")
    # batch 128 → g = 128 grid steps × blk = 64 rows = 8192 rows worst-case
    # dirty coverage. With crossover 4 the sweep fallback fires when
    # scaled_coverage·4 ≥ NB: full scales ×1 → fires for NB ≤ 32768; packed
    # ×0.5 → fires only for NB ≤ 16384. NB = 24576 (12 × 2048) sits in the
    # boundary band where the two layouts DECIDE DIFFERENTLY.
    nb, batch = 12 * 2048, 128
    assert resolve_write("sparse", nb, batch, FULL) == "sweep"
    assert resolve_write("sparse", nb, batch, GCRA32) == "sparse"
    assert resolve_write("sparse", nb, batch, TOKEN32) == "sparse"
    # defaulted layout keeps the pre-layout behavior bit-for-bit
    assert resolve_write("sparse", nb, batch) == "sweep"
    # far side of the boundary: both layouts agree again
    assert resolve_write("sparse", 1 << 21, 128, FULL) == "sparse"
    assert resolve_write("sparse", 1 << 11, 1 << 17, GCRA32) == "sweep"
