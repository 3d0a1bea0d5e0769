"""Compact-wire parity suite (ISSUE 5): the 5-lane int32 ingress / int32
egress codec (ops/wire.py) against the full-width oracle, row-for-row.

The compact path must be an ENCODING, never a semantics change: every
engine surface that can ship it (LocalEngine, ShardedEngine host-grid and
a2a routes, both dedup modes, the GLOBAL owner/replica fork and collective
sync outbox) is compared against the same engine forced to wire="full".
Batches that the narrow layout cannot represent (created_at skew beyond the
delta budget, hits ≥ 2^18, Gregorian durations) must fall back to
full-width transparently — checked by byte accounting, not just absence of
error. Egress saturation edges (int32 clamps, the reset==0 sentinel) are
pinned directly against the codec.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from gubernator_tpu.ops import wire
from gubernator_tpu.ops.batch import RequestColumns, pack_columns, pack_host_batch
from gubernator_tpu.ops.engine import LocalEngine
from gubernator_tpu.parallel import ShardedEngine, make_mesh
from gubernator_tpu.parallel.global_sync import GlobalShardedEngine
from gubernator_tpu.types import Behavior

NOW = 1_700_000_000_000
RESET = int(Behavior.RESET_REMAINING)
DRAIN = int(Behavior.DRAIN_OVER_LIMIT)
GLOBAL = int(Behavior.GLOBAL)
GREG = int(Behavior.DURATION_IS_GREGORIAN)


@pytest.fixture(scope="module")
def mesh():
    assert len(jax.devices()) == 8, "tests require the 8-device CPU mesh"
    return make_mesh(8)


def mk_cols(
    n,
    rng,
    dup=False,
    leaky_frac=0.5,
    limit=100,
    duration=60_000,
    behavior_pool=(0, RESET, DRAIN),
    created_at=NOW,
    hits_hi=4,
):
    fp = rng.integers(1, (1 << 63) - 1, size=n, dtype=np.int64)
    if dup:
        fp[n // 2 :] = fp[: n - n // 2]
    return RequestColumns(
        fp=fp,
        algo=(rng.random(n) < leaky_frac).astype(np.int32),
        behavior=rng.choice(behavior_pool, size=n).astype(np.int32),
        hits=rng.integers(0, hits_hi, n).astype(np.int64),
        limit=np.full(n, limit, dtype=np.int64),
        burst=np.zeros(n, dtype=np.int64),
        duration=np.full(n, duration, dtype=np.int64),
        created_at=np.full(n, created_at, dtype=np.int64),
        err=np.zeros(n, dtype=np.int8),
    )


def assert_rc_equal(a, b, ctx=""):
    for f in ("status", "limit", "remaining", "reset_time", "err"):
        np.testing.assert_array_equal(
            getattr(a, f), getattr(b, f), err_msg=f"{ctx}: {f} diverged"
        )


# ------------------------------------------------------------------- codec


def test_codec_roundtrip_exact():
    """pack → in-trace decode reproduces the full 12-column ingress array
    exactly, modulo the documented narrowing (behavior keeps only the two
    math-visible bits; leaky burst reconstructs as limit, token as 0)."""
    rng = np.random.default_rng(3)
    cols = mk_cols(64, rng)
    cols.created_at[5] = NOW - 512  # delta floor
    cols.created_at[6] = NOW + 511  # delta ceiling
    hb, err = pack_columns(cols, NOW)
    assert not err.any()
    base = wire.pick_base(hb)
    assert wire.wire_encodable(hb, base)
    arr12 = np.asarray(wire.decode_wire_block(
        jnp.asarray(wire.pack_wire_full(hb, base)))[0])
    ref = pack_host_batch(hb)
    ref[2] = ref[2] & (RESET | DRAIN)  # behavior narrows to the math bits
    ref[5] = np.where(ref[1] == 1, ref[4], 0)  # burst: leaky=limit, token=0
    np.testing.assert_array_equal(arr12, ref)


def test_encodable_rejections():
    rng = np.random.default_rng(4)

    def hb_of(**kw):
        cols = mk_cols(16, rng, **kw)
        return pack_columns(cols, NOW)[0]

    base = NOW
    assert wire.wire_encodable(hb_of(), base)
    # created_at outside the ±512 ms delta window
    assert not wire.wire_encodable(hb_of(created_at=NOW + 512), base)
    assert not wire.wire_encodable(hb_of(created_at=NOW - 513), base)
    # hits beyond 18 bits
    hb = hb_of()
    hb.hits[0] = 1 << 18
    assert not wire.wire_encodable(hb, base)
    hb.hits[0] = -1
    assert not wire.wire_encodable(hb, base)
    # duration beyond 30 bits
    hb = hb_of()
    hb.duration[3] = 1 << 30
    assert not wire.wire_encodable(hb, base)
    # negative limit (kept on the full-width path's exact arithmetic)
    hb = hb_of()
    hb.limit[0] = -5
    assert not wire.wire_encodable(hb, base)
    # explicit leaky burst != limit
    hb = hb_of(leaky_frac=1.0)
    hb.burst[0] = hb.limit[0] + 1
    assert not wire.wire_encodable(hb, base)
    # token burst is math-inert → still encodable
    hb = hb_of(leaky_frac=0.0)
    hb.burst[0] = 7
    assert wire.wire_encodable(hb, base)
    # Gregorian rows carry host-resolved calendar fields
    hb = hb_of()
    hb.greg_interval[2] = 1000
    assert not wire.wire_encodable(hb, base)
    # all-inactive batches are trivially encodable (zero columns)
    hb = hb_of()
    hb.active[:] = False
    assert wire.wire_encodable(hb, base)


def test_egress_saturation_and_sentinel():
    """int32 saturation edges: remaining/limit ≥ 2^31 clamp, negative
    remaining survives down to -2^31, reset_time==0 round-trips through
    the sentinel, and far-future resets clamp instead of wrapping."""
    base = NOW
    n = 6
    packed = np.zeros((n + 2, 4), dtype=np.int64)
    packed[:n, 0] = [2**31 + 7, 5, 5, 5, 5, 5]  # limit lane
    packed[:n, 1] = [3, 2**31 + 9, -(2**31) - 9, -17, 0, 1]  # remaining
    packed[:n, 2] = [NOW + 1, NOW + 2, NOW + 3, 0, NOW + 2**40, NOW - 5]
    packed[:n, 3] = [1, 5, 4, 0, 2, 0]  # flags
    packed[n] = [4, 2, 1, 0]
    packed[n + 1] = [1, 0, 0, 0]
    enc = np.asarray(wire.encode_wire_out(jnp.asarray(packed), jnp.int64(base)))
    assert enc.dtype == np.int32
    (status, limit, rem, reset, dropped, hit), st = wire.unpack_wire_out(enc, n)
    assert limit[0] == 2**31 - 1  # saturated, not wrapped
    assert rem[1] == 2**31 - 1 and rem[2] == -(2**31)
    assert rem[3] == -17  # in-range negatives exact
    assert reset[3] == 0  # sentinel round-trip
    assert reset[5] == NOW - 5  # small negative delta exact
    # far-future reset clamps to base + (2^31 - 1), never wraps negative
    assert reset[4] == base + 2**31 - 1
    assert st == (4, 2, 1, 0)
    assert bool(hit[4]) and bool(dropped[2]) and not bool(hit[0])


def test_a_mixed_dispatch_is_fetched_once_and_keeps_each_dtype():
    """One fetch of a dispatch whose passes mix compact (int32) and
    full-width (int64) outputs hands each pass its own array, dtype and
    shape as launched: the dtype is the tag the host decoder dispatches
    on, so nothing may widen on the way."""
    from gubernator_tpu.ops.engine import fetch_passes

    outs = [
        jnp.arange(24, dtype=jnp.int64).reshape(6, 4),
        jnp.arange(72, dtype=jnp.int32).reshape(18, 4),
        jnp.ones((6, 4), dtype=jnp.int32),
    ]
    passes = [[None, 4, None, out] for out in outs]
    fetch_passes(object(), passes)
    for (*_, host), dev in zip(passes, outs):
        assert isinstance(host, np.ndarray)
        assert host.dtype == dev.dtype and host.shape == dev.shape
        assert (host == np.asarray(dev)).all()


# ----------------------------------------------------------- local engine


def test_local_engine_parity_and_state():
    rng = np.random.default_rng(11)
    ec = LocalEngine(capacity=1 << 12, write_mode="xla", wire="compact")
    ef = LocalEngine(capacity=1 << 12, write_mode="xla", wire="full")
    state = rng.bit_generator.state
    got = []
    for eng in (ec, ef):
        rng.bit_generator.state = state
        for step in range(4):
            cols = mk_cols(200, rng, dup=(step % 2 == 1))
            rc = eng.check_columns(cols, now_ms=NOW + step * 1000)
            if eng is ec:
                got.append(rc)
            else:
                assert_rc_equal(got[step], rc, f"local step {step}")
    # identical responses AND identical device state, slot for slot
    np.testing.assert_array_equal(
        np.asarray(ec.table.rows), np.asarray(ef.table.rows)
    )
    assert ec.stats == ef.stats


def test_local_engine_parity_per_step():
    """Same as above but comparing per step (responses in lockstep)."""
    rng = np.random.default_rng(12)
    ec = LocalEngine(capacity=1 << 12, write_mode="xla", wire="compact")
    ef = LocalEngine(capacity=1 << 12, write_mode="xla", wire="full")
    for step in range(3):
        cols = mk_cols(128, rng, dup=(step == 2))
        assert_rc_equal(
            ec.check_columns(cols, now_ms=NOW + step),
            ef.check_columns(cols, now_ms=NOW + step),
            f"step {step}",
        )


def test_limit_i32_error_parity():
    """limit ≥ 2^31 is a front-door validation error on both paths — the
    row never reaches a kernel, compact or full."""
    rng = np.random.default_rng(13)
    cols = mk_cols(8, rng)
    cols.limit[3] = 2**31
    ec = LocalEngine(capacity=1 << 10, write_mode="xla", wire="compact")
    ef = LocalEngine(capacity=1 << 10, write_mode="xla", wire="full")
    a = ec.check_columns(cols, now_ms=NOW)
    b = ef.check_columns(cols, now_ms=NOW)
    assert a.err[3] != 0
    assert_rc_equal(a, b)


# ------------------------------------------------------------ sharded mesh


@pytest.mark.parametrize("route", ["host", "device"])
@pytest.mark.parametrize("dedup", ["host", "device"])
def test_sharded_parity(mesh, route, dedup):
    rng = np.random.default_rng(21)
    kw = dict(capacity_per_shard=1 << 10, write_mode="xla",
              route=route, dedup=dedup)
    ec = ShardedEngine(mesh, wire="compact", **kw)
    ef = ShardedEngine(mesh, wire="full", **kw)
    for step in range(3):
        cols = mk_cols(300, rng, dup=(step == 1))
        assert_rc_equal(
            ec.check_columns(cols, now_ms=NOW + step * 1000),
            ef.check_columns(cols, now_ms=NOW + step * 1000),
            f"{route}/{dedup} step {step}",
        )
    w, wf = ec.take_wire_deltas(), ef.take_wire_deltas()
    assert 0 < w["put"] < wf["put"] and 0 < w["fetch"] < wf["fetch"]


@pytest.mark.parametrize("fmt,put_row,fetch_row", [
    ("compact", 20, 16),  # 5 int32 lanes in, 4 int32 lanes out
    ("full", 96, 32),  # 12 int64 lanes in, 4 int64 lanes out
])
def test_wire_bytes_a_row_and_one_dispatch_a_batch(mesh, fmt, put_row,
                                                   fetch_row):
    """What one more row costs across the host↔device boundary, exactly,
    from the engine's own byte accounting (the wire_bytes_total source):
    the margin between a 512-row and a 4,096-row dispatch. A batch of
    distinct keys is ONE engine dispatch at either size."""
    rng = np.random.default_rng(31)
    eng = ShardedEngine(mesh, capacity_per_shard=1 << 12, write_mode="xla",
                        route="device", dedup="device", wire=fmt)

    def one(n):
        eng.take_wire_deltas()
        before = eng.stats.dispatches
        eng.check_columns(
            mk_cols(n, rng, leaky_frac=0.0, behavior_pool=(0,)), now_ms=NOW
        )
        assert eng.stats.dispatches == before + 1
        return eng.take_wire_deltas()

    small, big = one(512), one(4096)
    assert (big["put"] - small["put"]) / (4096 - 512) == put_row
    assert (big["fetch"] - small["fetch"]) / (4096 - 512) == fetch_row


def test_sharded_fallback_on_skew(mesh):
    """A batch with created_at beyond the delta budget ships full-width
    (byte-counted) and still matches the oracle row-for-row."""
    rng = np.random.default_rng(22)
    kw = dict(capacity_per_shard=1 << 10, write_mode="xla", route="host")
    ec = ShardedEngine(mesh, wire="compact", **kw)
    ef = ShardedEngine(mesh, wire="full", **kw)
    cols = mk_cols(64, rng)
    cols.created_at[7] = NOW + 60_000  # within clamp tolerance, over budget
    ec.take_wire_deltas()
    ef.take_wire_deltas()
    assert_rc_equal(
        ec.check_columns(cols, now_ms=NOW),
        ef.check_columns(cols, now_ms=NOW),
        "skew fallback",
    )
    # identical byte footprint ⇒ the compact engine took the wide path
    assert ec.take_wire_deltas() == ef.take_wire_deltas()


def test_sharded_fallback_on_hits_overflow(mesh):
    rng = np.random.default_rng(23)
    kw = dict(capacity_per_shard=1 << 10, write_mode="xla", route="host")
    ec = ShardedEngine(mesh, wire="compact", **kw)
    ef = ShardedEngine(mesh, wire="full", **kw)
    cols = mk_cols(64, rng, hits_hi=2)
    cols.hits[0] = 1 << 20  # beyond the 18-bit wire budget
    cols.limit[:] = 1 << 30
    ec.take_wire_deltas()
    ef.take_wire_deltas()
    assert_rc_equal(
        ec.check_columns(cols, now_ms=NOW),
        ef.check_columns(cols, now_ms=NOW),
        "hits fallback",
    )
    assert ec.take_wire_deltas() == ef.take_wire_deltas()


def test_concurrent_put_parity(mesh):
    """GUBER_SHARD_PUT=concurrent (per-shard transfers assembled with
    make_array_from_single_device_arrays) is a transport strategy, not a
    semantics change."""
    rng = np.random.default_rng(24)
    kw = dict(capacity_per_shard=1 << 10, write_mode="xla")
    ea = ShardedEngine(mesh, wire="compact", **kw)
    eb = ShardedEngine(mesh, wire="compact", **kw)
    ea._put_concurrent = True
    eb._put_concurrent = False
    cols = mk_cols(500, rng)
    assert_rc_equal(
        ea.check_columns(cols, now_ms=NOW),
        eb.check_columns(cols, now_ms=NOW),
        "concurrent put",
    )


# ------------------------------------------------------------------ GLOBAL


def test_global_parity_with_sync(mesh):
    """The GLOBAL owner/replica fork + collective sync (compact outbox)
    against the full-width engine: responses, replica-served reads after
    sync, and the global counters all match."""
    rng = np.random.default_rng(31)
    kw = dict(capacity_per_shard=1 << 10, write_mode="xla", sync_out=128)
    ec = GlobalShardedEngine(mesh, wire="compact", **kw)
    ef = GlobalShardedEngine(mesh, wire="full", **kw)
    state = rng.bit_generator.state
    outs = {}
    for name, eng in (("c", ec), ("f", ef)):
        rng.bit_generator.state = state
        last = None
        for step in range(3):
            cols = mk_cols(200, rng, behavior_pool=(GLOBAL,), limit=50)
            last = eng.check_columns(cols, now_ms=NOW + step * 100)
            eng.sync(now_ms=NOW + step * 100)
        # replica re-read after the last reconcile
        rng.bit_generator.state = state
        cols = mk_cols(200, rng, behavior_pool=(GLOBAL,), limit=50)
        outs[name] = (last, eng.check_columns(cols, now_ms=NOW + 300))
    assert_rc_equal(outs["c"][0], outs["f"][0], "GLOBAL serve")
    assert_rc_equal(outs["c"][1], outs["f"][1], "GLOBAL replica re-read")
    assert ec.global_stats == ef.global_stats


def test_global_sync_outbox_falls_back_on_big_hits(mesh):
    """Accumulated hot-key hits beyond the 18-bit wire budget push the
    sync round onto the full-width pytree outbox — reconciliation must be
    identical either way."""
    rng = np.random.default_rng(32)
    kw = dict(capacity_per_shard=1 << 10, write_mode="xla", sync_out=64)
    ec = GlobalShardedEngine(mesh, wire="compact", **kw)
    ef = GlobalShardedEngine(mesh, wire="full", **kw)
    cols = mk_cols(16, rng, behavior_pool=(GLOBAL,), limit=1 << 30,
                   leaky_frac=0.0)
    cols = cols._replace(hits=np.full(16, (1 << 18) + 5, dtype=np.int64))
    for eng in (ec, ef):
        eng.check_columns(cols, now_ms=NOW)
        eng.sync(now_ms=NOW)
        # the compact engine must have taken the fallback (no wire step
        # compiled) — and both reconcile the same totals
    assert ec._sync_step_wire is None
    assert ec.global_stats == ef.global_stats
    probe = mk_cols(16, rng, behavior_pool=(GLOBAL,), limit=1 << 30)
    probe = probe._replace(fp=cols.fp, hits=np.zeros(16, dtype=np.int64),
                           algo=cols.algo)
    assert_rc_equal(
        ec.check_columns(probe, now_ms=NOW + 1),
        ef.check_columns(probe, now_ms=NOW + 1),
        "post-sync probe",
    )


# ------------------------------------------------------------------- knobs


def test_default_wire_mode_env(monkeypatch):
    monkeypatch.setenv("GUBER_WIRE_COMPACT", "1")
    assert wire.default_wire_mode() == "compact"
    monkeypatch.setenv("GUBER_WIRE_COMPACT", "0")
    assert wire.default_wire_mode() == "full"
    monkeypatch.delenv("GUBER_WIRE_COMPACT")
    # CPU backend default is full-width (TPU defaults compact)
    assert wire.default_wire_mode() == (
        "compact" if jax.default_backend() == "tpu" else "full"
    )


def test_wire_param_validation(mesh):
    with pytest.raises(ValueError):
        LocalEngine(capacity=1 << 10, wire="tight")
    with pytest.raises(ValueError):
        ShardedEngine(mesh, capacity_per_shard=1 << 10, wire="tight")
