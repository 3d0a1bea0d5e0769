"""Native ingress/egress (gubernator_tpu/native) parity tests: wire parsing,
hashing, and response encoding must match the pure-Python pb path exactly."""

import asyncio
import functools
import random

import numpy as np
import pytest

from gubernator_tpu import native
from gubernator_tpu.proto import gubernator_pb2 as pb

from tests.test_wire_split import EMPTY_KEY, EMPTY_NAME, LEAKY, RESET, SHAPES, rpc

m = native.load()
pytestmark = pytest.mark.skipif(m is None, reason="native toolchain unavailable")


def async_test(fn):
    @functools.wraps(fn)
    def wrapper(*a, **k):
        asyncio.run(fn(*a, **k))

    return wrapper


def random_req(rng, i):
    r = pb.RateLimitReq(
        name=rng.choice(["svc", "üñïçødé-svc", "a" * 40, "x"]),
        unique_key=f"key-{i}-{rng.randrange(1000)}",
        hits=rng.choice([0, 1, 5, -3, 1 << 40]),
        limit=rng.choice([0, 10, 1 << 31, -7]),
        duration=rng.choice([1000, 60_000, 3]),  # 3 = a Gregorian enum value
        algorithm=rng.choice([0, 1]),
        behavior=rng.choice([0, 1, 2, 8, 32, 34]),
        burst=rng.choice([0, 5]),
    )
    if rng.random() < 0.5:
        r.created_at = rng.randrange(1, 1 << 45)
    if rng.random() < 0.3:
        r.metadata["traceparent"] = "00-" + "ab" * 16 + "-" + "cd" * 8 + "-01"
        r.metadata["other"] = "värde"
    return r


def test_parse_matches_pb_path():
    from gubernator_tpu.hashing import fingerprint
    from gubernator_tpu.peers.hash_ring import fnv1a_32
    from gubernator_tpu.service.wire import columns_from_pb, columns_from_wire

    rng = random.Random(7)
    items = [random_req(rng, i) for i in range(200)]
    items.append(pb.RateLimitReq(name="no-key"))  # ERR_EMPTY_KEY
    items.append(pb.RateLimitReq(unique_key="no-name"))  # ERR_EMPTY_NAME
    data = pb.GetRateLimitsReq(requests=items).SerializeToString()

    got = columns_from_wire(data)
    assert got is not None
    cols, ring, spans, traceparent = got
    # at least one random item carried the traceparent metadata
    assert traceparent == "00-" + "ab" * 16 + "-" + "cd" * 8 + "-01"
    ref_cols, hash_keys = columns_from_pb(items)

    for field in ("fp", "algo", "behavior", "hits", "burst", "created_at", "err"):
        np.testing.assert_array_equal(
            getattr(cols, field), getattr(ref_cols, field), err_msg=field
        )
    # limit/duration are clipped by columns_from_pb only beyond ±2^62 —
    # unclipped here, so compare raw
    np.testing.assert_array_equal(cols.limit, [it.limit for it in items])
    np.testing.assert_array_equal(cols.duration, [it.duration for it in items])
    # ring points match the python ring hash of the hash key
    for i, hk in enumerate(hash_keys):
        if hk:
            assert int(ring[i]) == fnv1a_32(hk.encode()), hk
    # spans re-materialize the exact item
    from gubernator_tpu.service.wire import item_from_span

    for i in (0, 57, 199):
        assert item_from_span(data, spans[i]) == items[i]


def test_encode_matches_pb():
    from gubernator_tpu.service.wire import encode_response_columns

    n = 50
    rng = np.random.default_rng(3)
    status = rng.integers(0, 2, n).astype(np.int64)
    limit = rng.integers(0, 1 << 40, n)
    remaining = rng.integers(0, 1 << 40, n)
    reset = rng.integers(0, 1 << 45, n)
    errors = {0: "boom", 17: "fält-fel: üñï"}
    data = encode_response_columns(status, limit, remaining, reset, errors)
    resp = pb.GetRateLimitsResp.FromString(data)
    assert len(resp.responses) == n
    for i, r in enumerate(resp.responses):
        assert r.status == status[i]
        assert r.limit == limit[i]
        assert r.remaining == remaining[i]
        assert r.reset_time == reset[i]
        assert r.error == errors.get(i, "")


def test_malformed_wire_raises():
    with pytest.raises(ValueError):
        m.parse_get_rate_limits(b"\x0a\xff\xff\xff\xff\xff")  # truncated len


@async_test
async def test_raw_path_serves_cluster_traffic():
    """The raw gRPC path end-to-end on a 3-daemon cluster: local, forwarded,
    and GLOBAL items all answered from the native ingress."""
    from gubernator_tpu.client import V1Client
    from gubernator_tpu.types import Behavior

    from tests.cluster import Cluster, wait_for

    c = await Cluster.start(3)
    try:
        non_owner = c.non_owning_daemons("nat", "k1")[0]
        owner = c.find_owning_daemon("nat", "k1")
        client = V1Client(non_owner.conf.grpc_address)
        try:
            resp = await client.get_rate_limits(
                [
                    dict(name="nat", unique_key="k1", hits=2, limit=10, duration=60_000),
                    dict(name="nat", unique_key="k2", hits=1, limit=10, duration=60_000),
                    dict(name="", unique_key="bad", hits=1, limit=1, duration=1000),
                    dict(
                        name="nat", unique_key="g1", hits=3, limit=10,
                        duration=60_000, behavior=int(Behavior.GLOBAL),
                    ),
                ]
            )
            r = resp.responses
            assert r[0].error == "" and r[0].remaining == 8
            assert r[1].error == "" and r[1].remaining == 9
            assert "namespace" in r[2].error
            assert r[3].error == "" and r[3].remaining == 7

            # the GLOBAL hit reaches the owner asynchronously
            async def owner_saw_hits():
                ro = await owner.get_rate_limits(
                    [pb.RateLimitReq(name="nat", unique_key="g1", hits=0,
                                     limit=10, duration=60_000)]
                )
                return ro[0].remaining == 7

            await wait_for(owner_saw_hits, timeout_s=15)
        finally:
            await client.close()
    finally:
        await c.stop()


@async_test
async def test_raw_path_force_global():
    """GUBER_FORCE_GLOBAL on the native raw path: requests flip to GLOBAL,
    serve locally, and the owner broadcast still fires (the forced bit must
    survive lazy pb materialization)."""
    from gubernator_tpu.client import V1Client

    from tests.cluster import Cluster, daemon_config, metric_value, scrape, wait_for

    from gubernator_tpu.config import BehaviorConfig

    behaviors = BehaviorConfig(
        batch_wait_ms=1.0, global_sync_wait_ms=50.0,
        batch_timeout_ms=5000.0, global_timeout_ms=5000.0, force_global=True,
    )
    c = await Cluster.start(2, behaviors=behaviors)
    try:
        owner = c.find_owning_daemon("fg", "k1")
        client = V1Client(owner.conf.grpc_address)
        try:
            resp = await client.get_rate_limits(
                [dict(name="fg", unique_key="k1", hits=2, limit=10, duration=60_000)]
            )
            assert resp.responses[0].error == ""
            assert resp.responses[0].remaining == 8
        finally:
            await client.close()

        # forced-GLOBAL owner hits must broadcast to the peer
        async def broadcasted():
            s = await scrape(owner)
            return metric_value(
                s, "gubernator_broadcast_counter_total", condition="broadcast"
            )

        await wait_for(broadcasted, timeout_s=15)
        other = c.non_owning_daemons("fg", "k1")[0]

        async def installed():
            s = await scrape(other)
            return metric_value(
                s, "gubernator_update_peer_globals_installed_total"
            )

        await wait_for(installed, timeout_s=15)
    finally:
        await c.stop()


# ------------------------------------------------ encode_responses_many
# A dispatch's answers in one call (service/batcher.py's encode link):
# byte for byte what encode_response_columns gives for each entry's slice
# with the error strings of its `err` codes, and the entry's OVER_LIMIT rows.


def _response_columns(n, seed, wide=True, over=0.0, err=0.0, leaky=False):
    """A chunk's response columns as an engine hands them over: status
    int32, err int8, the rest int64 (`wide`) or all of them int32."""
    from gubernator_tpu.ops.batch import ERROR_STRINGS, ResponseColumns

    rng = np.random.default_rng(seed)
    big = np.int64 if wide else np.int32
    top = 1 << (45 if wide else 30)
    reset = rng.integers(1, top, n)
    if leaky:  # a leaky bucket's reset_time: the next token, often past
        reset = MANY_NOW + rng.integers(-5_000, 5_000, n)
    return ResponseColumns(
        status=(rng.random(n) < over).astype(np.int32),
        limit=rng.integers(0, top, n).astype(big),
        remaining=rng.integers(0, top, n).astype(big),
        reset_time=reset.astype(big),
        err=np.where(
            rng.random(n) < err, rng.integers(1, len(ERROR_STRINGS), n), 0
        ).astype(np.int8),
    )


MANY_NOW = 1_700_000_000_000
MANY_CASES = {
    # name: (columns' arguments, offsets, now_ms)
    "one_entry": (dict(n=40, seed=1), [0, 40], None),
    "many_entries": (dict(n=3000, seed=2), [0, 1000, 1001, 2000, 3000], None),
    "empty_entry": (dict(n=10, seed=3), [0, 4, 4, 10], None),
    "no_entry": (dict(n=10, seed=3), [], None),
    "int32_columns": (dict(n=64, seed=4, wide=False, over=0.3), [0, 1, 64], 77),
    "over_limit_without_now": (dict(n=200, seed=5, over=0.5), [0, 120, 200], None),
    "over_limit_with_now": (dict(n=200, seed=5, over=0.5), [0, 120, 200], 1 << 44),
    "err_codes": (dict(n=300, seed=6, over=0.2, err=0.3), [0, 7, 150, 300], 9),
    "leaky_reset_below_now": (
        dict(n=500, seed=7, over=0.5, leaky=True), [0, 250, 500], MANY_NOW
    ),
    "offsets_not_from_zero": (dict(n=100, seed=8, over=0.4), [17, 30, 90], 5),
}


@pytest.mark.parametrize("case", sorted(MANY_CASES))
def test_encode_many_matches_encode_of_each_slice(case):
    from gubernator_tpu.ops.batch import ERROR_STRINGS
    from gubernator_tpu.service.wire import (
        encode_response_columns,
        encode_responses_many,
    )

    kw, offsets, now = MANY_CASES[case]
    rc = _response_columns(**kw)
    bodies, over = encode_responses_many(rc, offsets, now)
    assert len(bodies) == len(over) == max(len(offsets) - 1, 0)
    for k, (lo, hi) in enumerate(zip(offsets, offsets[1:])):
        errors = {
            int(i): ERROR_STRINGS[int(rc.err[lo + i])]
            for i in np.flatnonzero(rc.err[lo:hi])
        }
        want = encode_response_columns(
            rc.status[lo:hi], rc.limit[lo:hi], rc.remaining[lo:hi],
            rc.reset_time[lo:hi], errors, now,
        )
        assert bodies[k] == want, (case, k)
        assert over[k] == int((rc.status[lo:hi] == pb.OVER_LIMIT).sum())
        assert len(pb.GetRateLimitsResp.FromString(bodies[k]).responses) == hi - lo
    if now is not None and kw.get("over"):
        assert b"retry_after_ms" in b"".join(bodies)
    if kw.get("leaky"):  # a reset_time behind the clock waits 0 ms
        waits = {
            r.metadata["retry_after_ms"]
            for b in bodies
            for r in pb.GetRateLimitsResp.FromString(b).responses
            if r.status == pb.OVER_LIMIT
        }
        assert "0" in waits and len(waits) > 1


def test_encode_many_takes_strided_columns_and_refuses_bad_input():
    """A column that is a view with a stride is read where it lies; offsets
    that leave the columns or descend, columns of unlike length, a code
    with no error string and a column that holds no integers are refused."""
    from gubernator_tpu.service.wire import encode_responses_many

    rc = _response_columns(n=64, seed=9, over=0.5, err=0.2)
    strided = type(rc)(*(np.repeat(c, 2)[::2] for c in rc))
    assert not strided.limit.flags.c_contiguous
    assert encode_responses_many(strided, [0, 30, 64], 5) == (
        encode_responses_many(rc, [0, 30, 64], 5)
    )
    for bad in ([0, 65], [10, 5], [-1, 3]):
        with pytest.raises(ValueError):
            encode_responses_many(rc, bad)
    with pytest.raises(ValueError):
        encode_responses_many(rc._replace(limit=rc.limit[:-1]), [0, 1])
    with pytest.raises(ValueError):
        encode_responses_many(rc._replace(err=np.full(64, 99, np.int8)), [0, 64])
    with pytest.raises(TypeError):
        encode_responses_many(rc._replace(limit=rc.limit.astype(float)), [0, 1])


# ------------------------------------------------------ stage_wire_chunk
# (the host staging of a fused chunk, one GIL-free call) against the NumPy
# staging of ops/engine.py, byte for byte

STAGE_NOW = 1_759_000_000_000
GCRA, WINDOW = pb.GCRA, pb.SLIDING_WINDOW
UNSTAMPED = -STAGE_NOW  # the offset that leaves created_at 0


def _level_bit(parts):
    """Cascade level 1 on the second row, as an engine-level caller packs."""
    lanes = parts[0].lanes.copy()
    lanes[3, 1] |= np.int32(1 << 30)
    return [parts[0]._replace(lanes=lanes), *parts[1:]]


def _every_other_row(parts):
    """A selection of a parsed batch's rows: its lanes are not contiguous."""
    from gubernator_tpu.service.wire import subset_wire

    return [subset_wire(p, np.arange(0, p.rows, 2)) for p in parts]


def _error_row_with_a_fingerprint(parts):
    """An error row between two copies of the key whose fingerprint it is
    given: the parser never writes one, the rank rule is held all the same
    (the key's second copy is rank 2 and exact pass 1 is empty)."""
    cols = parts[0].cols
    fp = cols.fp.copy()
    fp[1] = fp[0]
    return [parts[0]._replace(cols=cols._replace(fp=fp))]


def _zipf_chunk(seed, rpcs=3, rows=1000, keys=400, late=0.0):
    """Leaky rows over Zipf(0.99) keys, stamped within 200 ms of the ingress
    instant; a later copy of a key, one in 1/`late`, 700 ms after it."""
    rng = np.random.default_rng(seed)
    cdf = np.cumsum(np.arange(1, keys + 1, dtype=np.float64) ** -0.99)
    ranks = np.searchsorted(cdf / cdf[-1], rng.random((rpcs, rows)))
    seen, parts = set(), []
    for part in np.minimum(ranks, keys - 1):
        parts.append([])
        for k in map(int, part):
            off = 700 if k in seen and rng.random() < late else int(rng.integers(-200, 200))
            parts[-1].append((k, off, 0, 1, LEAKY))
            seen.add(k)
    return parts


# parts (rows as tests/test_wire_split.rpc takes them), then what differs
# from: tolerance 5,000 ms, the bucketed pad, max_exact 8, lanes as parsed
STAGINGS = {
    **{name: (shape[0], {}) for name, shape in SHAPES.items()},
    "one_part_no_repeat": ([[1, 2, 3]], {}),
    "many_parts_no_repeat": ([[1, 2, 3], [4], [5, 6]], {}),
    "unstamped_and_clamped_stamps": (
        [[(1, UNSTAMPED, 0), (2, 400, 0), (1, -400, 0), 2, (1, UNSTAMPED, 0)],
         [(3, 9_000, 0), (2, -9_000, 0)]],
        {"tol": 300},
    ),
    "a_first_copy_outside_the_budget": ([[1, (2, 600, 0)]], {}),
    "the_first_later_pass_outside_the_budget": ([[1, (1, 900, 0), 1, 2, 2]], {}),
    "every_row_an_error": ([[EMPTY_KEY, EMPTY_NAME], [EMPTY_KEY]], {}),
    "cascade_bits_and_no_repeat": ([[1, 2, 3]], {"edit": _level_bit}),
    "cascade_bits_beside_a_repeat": ([[1, 2], [3, 1]], {"edit": _level_bit}),
    "a_chunk_that_fills_its_pad_to_the_last_row": (
        [list(range(1, 9)), list(range(9, 17))], {},
    ),
    "no_exact_pass_for_the_grid": ([[1, 1]], {"max_exact": 1}),
    "every_later_copy_in_the_aggregate": ([[7, 8, 7, 7], [8, 9]], {"max_exact": 2}),
    "one_exact_pass_then_the_aggregate": ([[7] * 5, [8, 7, 8]], {"max_exact": 3}),
    "lanes_that_are_not_contiguous": ([[7, 1, 7, 2, 7, 3, 8, 4, 8]], {"edit": _every_other_row}),
    "an_error_row_under_a_keys_fingerprint": (
        [[1, EMPTY_KEY, 1, 2]], {"edit": _error_row_with_a_fingerprint},
    ),
    "all_gcra": ([[(1, 0, 0, 1, GCRA), (1, 0, 0, 1, GCRA), (2, 0, 0, 1, GCRA)]], {}),
    "gcra_beside_a_window": (
        [[(1, 0, 0, 1, GCRA), (2, 0, 0, 1, WINDOW), (1, 0, 0, 1, GCRA), (2, 0, 0, 1, WINDOW)]],
        {},
    ),
    # the later copies name two algorithms and the first pass behind the
    # grid holds only the token one: each pass selects its own mode
    "a_token_pass_before_a_leaky_one": (
        [[6, (7, 0, 0, 1, LEAKY), 6, (7, 0, 0, 1, LEAKY), (7, 0, 0, 1, LEAKY)]], {},
    ),
    "reset_remaining_beside_priority_bits": (
        [[(7, 0, 64)] * 8 + [(7, 0, RESET | 128), (7, 0, 64), (8, 0, 192), (8, 0, RESET)]],
        {},
    ),
    "zipf_3000_rows": (_zipf_chunk(11), {}),
    "zipf_3000_rows_some_stamps_late": (_zipf_chunk(12, late=0.002), {}),
    "zipf_3000_rows_clamped_into_the_budget": (_zipf_chunk(13, late=0.1), {"tol": 300}),
}


def _same_bytes(got, want, what):
    if want is None or isinstance(want, (bool, int, str)):
        assert got == want and type(got) is type(want), what
        return
    assert got.dtype == want.dtype and got.shape == want.shape, what
    assert got.tobytes() == want.tobytes(), what


def _same_staging(got, want):
    assert (got is None) == (want is None)
    if want is None:
        return
    for field in want._fields[:-1]:
        _same_bytes(getattr(got, field), getattr(want, field), field)
    assert len(got.passes) == len(want.passes)
    for i, (g, w) in enumerate(zip(got.passes, want.passes)):
        for field in w._fields:
            _same_bytes(getattr(g, field), getattr(w, field), (i, field))


def _stage_both(parts, tol=5_000, max_exact=8, edit=None):
    from gubernator_tpu.ops import engine, wire

    parts = [rpc(p, STAGE_NOW) for p in parts]
    if edit is not None:
        parts = edit(parts)
    pad = engine._pad_size(sum(p.rows for p in parts))
    args = (parts, STAGE_NOW, tol, pad, max_exact)
    return (
        wire.stage_wire_chunk(m, *args, engine._pad_size(0)),
        engine._stage_chunk_numpy(*args),
    )


@pytest.mark.parametrize("case", STAGINGS)
def test_stage_wire_chunk_is_the_numpy_staging_byte_for_byte(case):
    parts, how = STAGINGS[case]
    got, want = _stage_both(parts, **how)
    _same_staging(got, want)
    # what each case is there for
    refused = case in (
        "a_first_copy_outside_the_budget", "every_row_an_error",
        "cascade_bits_beside_a_repeat", "no_exact_pass_for_the_grid",
    )
    assert (want is None) == refused
    if refused:
        return
    n = sum(len(p) for p in parts) if "edit" not in how else want.first.size
    assert want.grid.shape == (5, max(16, 1 << (n - 1).bit_length()) + 1)
    if case == "a_chunk_that_fills_its_pad_to_the_last_row":
        assert n == 16 and want.grid.shape == (5, 17) and want.first.all()
    assert want.later == sum(p.rows.size if p.members is None else p.members.size for p in want.passes)
    assert not want.grid[:, :n][:, ~want.first].any()
    off_lanes = {
        "stamps_at_the_edge_of_the_budget": [False, True],
        "the_first_later_pass_outside_the_budget": [True, False],
        "an_aggregate_whose_hits_pass_the_lane": [False] * 6 + [True],
    }.get(case)
    if off_lanes is not None:
        assert [p.block is None for p in want.passes] == off_lanes
    elif case == "zipf_3000_rows_some_stamps_late":
        assert 0 < sum(p.block is None for p in want.passes) < len(want.passes) == 7
    else:
        assert all(p.block is not None for p in want.passes)
    if case == "unstamped_and_clamped_stamps":
        assert want.clamped == 4
    if case == "cascade_bits_and_no_repeat":
        assert want.casc
    if case == "an_error_row_under_a_keys_fingerprint":
        assert [p.rows.size for p in want.passes] == [0, 1]
    if case == "a_token_pass_before_a_leaky_one":
        assert [p.math for p in want.passes] == ["mixed", "mixed"]
        assert want.math == "mixed"
    if case == "two_algorithms_in_one_chunk":
        assert [p.math for p in want.passes] == ["mixed", "mixed", "token"]
    if case in ("all_gcra", "gcra_beside_a_window"):
        assert (want.math, want.passes[0].math) == (
            ("gcra", "gcra") if case == "all_gcra" else ("int", "int")
        )


def test_stage_wire_chunk_refuses_what_it_cannot_read():
    from gubernator_tpu.ops import wire

    wb = rpc([1, 2, 3], STAGE_NOW)
    cols = wb.cols
    good = (wb.lanes, cols.fp, cols.err, cols.created_at)
    for bad, exc in (
        ((wb.lanes.astype(np.int64), *good[1:]), TypeError),  # not int32
        ((wb.lanes[:4], *good[1:]), TypeError),  # four lanes
        ((wb.lanes, cols.fp[:2], *good[2:]), ValueError),  # a short column
        ((wb.lanes, cols.fp.astype(np.float64), *good[2:]), TypeError),
        (good[:3], TypeError),
    ):
        with pytest.raises(exc):
            m.stage_wire_chunk([bad], STAGE_NOW, 5_000, 16, 8, 16)
    with pytest.raises(ValueError):  # a pad below the rows
        m.stage_wire_chunk([good], STAGE_NOW, 5_000, 2, 8, 16)
    assert wire.stage_wire_chunk(m, [wb], STAGE_NOW, 5_000, 16, 8, 16) is not None


def test_stage_wire_chunk_holds_no_state_between_threads():
    """240 Zipf chunks staged from eight threads at a 10 µs switch interval,
    each twice: every one is the staging NumPy makes of it alone."""
    import sys
    from concurrent.futures import ThreadPoolExecutor

    from gubernator_tpu.ops import engine, wire

    chunks = []
    for seed in range(240):
        rows = _zipf_chunk(seed, rpcs=2, rows=150, keys=40, late=0.01)
        args = ([rpc(p, STAGE_NOW) for p in rows], STAGE_NOW, 5_000, 512, 8)
        chunks.append((args, engine._stage_chunk_numpy(*args)))
    assert sum(len(want.passes) == 7 for _a, want in chunks) > 100

    def stage(chunk):
        args, want = chunk
        for _ in range(2):
            _same_staging(wire.stage_wire_chunk(m, *args, 16), want)
        return True

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        with ThreadPoolExecutor(8) as pool:
            done = list(pool.map(stage, chunks, timeout=120))
    finally:
        sys.setswitchinterval(interval)
    assert done == [True] * 240


# ------------------------------------------------------ finish_wire_chunk
# (the finish half of a fused dispatch, one GIL-free call) against the NumPy
# finish of ops/engine.py, byte for byte: the same prepared dispatch twice,
# both handed the same "fetched" egress blocks — made up here, so that every
# flag, the reset sentinel and a dropped row turn up in every pass — and a
# retry that answers from the rows it is asked for


def _egress_block(rng, pad, n, base, dropped=0.0, sidecar=False, strided=False,
                  live=None):
    """A compact egress block as a pass of `n` rows padded to `pad` would
    fetch it: rows, (a tiered program's 4*pad sidecar rows,) stats, base.
    Only a `live` row comes back dropped (kernel2: active & ~written)."""
    height = (5 * pad if sidecar else pad) + 2
    big = rng.integers(-(2**31), 2**31, (2 * height, 8), dtype=np.int64).astype(np.int32)
    block = big[::2, ::2] if strided else np.ascontiguousarray(big[:height, :4])
    block[:pad, 3] = rng.integers(0, 4, pad)  # status and hit
    lost = rng.random(pad) < dropped
    if live is not None:
        lost[:n] &= live
    block[:pad, 3] |= lost.astype(np.int32) << 2
    block[:pad, 2][rng.random(pad) < 0.2] = -(2**31)  # reset_time == 0
    block[-2] = rng.integers(0, 1000, 4)
    block[-1] = [0, np.int64(base & 0xFFFFFFFF).astype(np.int32), base >> 32, 0]
    assert block.flags.c_contiguous != strided
    return block


def _blank_columns(n):
    """A dispatch's four response columns before its finish."""
    return (
        np.zeros(n, np.int32), np.zeros(n, np.int64),
        np.zeros(n, np.int64), np.zeros(n, np.int64),
    )


def _retry_from_the_rows(calls):
    """`_redispatch_rows` as a function of the rows alone: what each answers
    is read off its fingerprint, one in three stays dropped."""
    def redispatch(sub, n, uncounted=None):
        fp = np.asarray(sub.fp[:n])
        assert uncounted is None or not uncounted.any()
        calls.append(fp.copy())
        return (
            (fp % 2).astype(np.int32), fp % 1009, fp % 997, fp % 991 + 1,
            fp % 3 == 0, fp % 5 == 0,
        )
    return redispatch


def _finish_both(parts, blocks, tol=5_000, max_exact=8, edit=None):
    """The chunk prepared twice on one engine, each pending handed its own
    copy of `blocks(pads, base)` as fetched; finished by the native call
    and by NumPy. Returns both (columns, err, stats delta, retried rows)."""
    import dataclasses

    from gubernator_tpu.ops import engine as eng_mod
    from gubernator_tpu.ops.wire import block_base

    parts = [rpc(p, STAGE_NOW) for p in parts]
    if edit is not None:
        parts = edit(parts)
    engine = eng_mod.LocalEngine(
        capacity=1024, wire="compact", max_exact_passes=max_exact,
        created_at_tolerance_ms=tol,
    )
    out = []
    for finish in (eng_mod._finish_native, eng_mod._finish_numpy):
        pending = eng_mod.prepare_check_wire(engine, parts, now_ms=STAGE_NOW)
        if pending is None:
            return None
        staged = [entry[3][0] for entry in pending.passes]
        made = blocks(
            [
                (eng_mod._padded_rows(e[2]), e[1], e[2].active if i == 0 else None)
                for i, e in enumerate(pending.passes)
            ],
            block_base(np.asarray(staged[0])),
        )
        for entry, block in zip(pending.passes, made):
            entry[3] = block.copy() if block.flags.c_contiguous else block
        cols = _blank_columns(pending.rows)
        delta, calls = eng_mod.EngineStats(), []
        engine._redispatch_rows = _retry_from_the_rows(calls)
        retried = finish(engine, pending, cols, delta, lambda fn: fn())
        if retried is None:
            out.append(None)
            continue
        out.append((cols, pending.err, dataclasses.asdict(delta), calls, retried))
    return out


def _same_finish(got, want):
    (cols_g, err_g, delta_g, calls_g, retried_g) = got
    (cols_w, err_w, delta_w, calls_w, retried_w) = want
    for g, w, name in zip(cols_g, cols_w, ("status", "limit", "remaining", "reset")):
        _same_bytes(g, w, name)
    assert bytes(err_g) == bytes(err_w)
    assert delta_g.pop("native_finished") == 1 and not delta_w.pop("native_finished")
    assert delta_g == delta_w and retried_g == retried_w
    assert len(calls_g) == len(calls_w)
    for g, w in zip(calls_g, calls_w):
        _same_bytes(g, w, "the rows a retry was asked for")


# a staging shape, then how its passes' blocks come back
FINISHES = {
    **{
        name: (parts, how, {})
        for name, (parts, how) in STAGINGS.items()
        if name in (
            "one_part_no_repeat", "many_parts_no_repeat", "pair_inside_one_rpc",
            "one_key_3_times", "one_key_8_times", "one_key_9_times",
            "one_key_20_times", "two_keys_past_the_aggregate",
            "next_to_error_rows", "error_rows_between_copies_past_the_aggregate",
            "every_later_copy_in_the_aggregate", "one_exact_pass_then_the_aggregate",
            "lanes_that_are_not_contiguous", "an_error_row_under_a_keys_fingerprint",
            "cascade_bits_and_no_repeat", "reset_remaining_beside_priority_bits",
            "a_chunk_that_fills_its_pad_to_the_last_row", "zipf_3000_rows",
            # a pass the lanes cannot carry is packed as columns: NumPy's
            "an_aggregate_whose_hits_pass_the_lane", "zipf_3000_rows_some_stamps_late",
        )
    },
    "dropped_rows_in_the_grid_and_in_later_passes": (
        STAGINGS["zipf_3000_rows"][0], {}, {"dropped": 0.05},
    ),
    "dropped_members_of_the_aggregate": (
        [[7] * 12 + [8] * 9 + [9, 7, 8]], {"max_exact": 3}, {"dropped": 1.0},
    ),
    "a_dropped_cascade_row_folds_again_on_the_host": (
        [[1, 2, 3]], {"edit": _level_bit}, {"dropped": 0.7},
    ),
    "blocks_that_are_not_contiguous": (
        STAGINGS["zipf_3000_rows"][0], {}, {"dropped": 0.01, "strided": True},
    ),
    "a_tiered_block_with_its_sidecar": (
        [[1, 2, 1, 3, 1]], {}, {"dropped": 0.9, "sidecar": True},
    ),
}


def test_the_finish_cases_name_stagings_that_exist():
    assert len(FINISHES) == 25  # a name that SHAPES lost would drop its case


@pytest.mark.parametrize("case", FINISHES)
def test_finish_wire_chunk_is_the_numpy_finish_byte_for_byte(case):
    parts, how, back = FINISHES[case]

    def blocks(pads, base):  # the same for both finishes
        rng = np.random.default_rng(49)
        return [
            _egress_block(rng, pad, n, base, live=live, **back)
            for pad, n, live in pads
        ]

    got, want = _finish_both(parts, blocks, **how)
    if case in ("an_aggregate_whose_hits_pass_the_lane", "zipf_3000_rows_some_stamps_late"):
        assert got is None and want[2]["later_lane_rows"] < want[2]["later_rows"]
        return
    _same_finish(got, want)
    cols, _err, delta, calls, retried = want
    assert delta["dispatches"] >= 1 and retried == bool(calls)
    assert retried == ("dropped" in back)
    if case == "zipf_3000_rows":
        assert delta["dispatches"] == 8 and delta["aggregate_rows"] > 100
        assert delta["later_lane_rows"] == delta["later_rows"] > 1000
        assert (cols[3] == 0).any() and (cols[0] == 1).any()
    if case == "dropped_rows_in_the_grid_and_in_later_passes":
        assert len(calls) == 8  # every pass had a row to retry
    if case == "dropped_members_of_the_aggregate":
        assert delta["dispatches"] == 3 and delta["aggregate_rows"] == 19
        assert [c.size for c in calls] == [3, 2, 2]  # both groups retried


def test_finish_wire_chunk_refuses_what_it_cannot_read():
    """A full-width (int64) block among the passes is the Python finish's
    (None, nothing written); what is no block, or names rows outside the
    columns, raises."""
    from gubernator_tpu.ops import wire

    rng = np.random.default_rng(5)
    block = _egress_block(rng, 16, 3, STAGE_NOW)
    rows = np.arange(3)
    cols = lambda: _blank_columns(3)
    one = lambda b=block, n=3, r=rows, mem=None, cnt=None: [(b, n, r, mem, cnt, None, None)]
    done = wire.finish_wire_chunk(m, one(), cols())
    assert done.stats == tuple(block[-2]) and done.later_rows == 0
    out = cols()
    assert wire.finish_wire_chunk(m, one(block.astype(np.int64)), out) is None
    assert wire.finish_wire_chunk(m, one() + one(block.astype(np.int64)), cols()) is None
    assert not any(c.any() for c in out)
    for bad, exc in (
        (one(block[:, :3]), TypeError),  # three cells a row
        (one(b"x" * 8), TypeError),  # no block at all
        (one(n=17), ValueError),  # more rows than the block holds
        (one(r=rows[:2]), ValueError),  # a row without a place
        (one(r=np.array([0, 1, 3])), ValueError),  # a place outside the columns
        (one(mem=np.arange(3), cnt=np.array([1, 1, 2])), ValueError),
        (one(mem=np.arange(3), cnt=np.array([1, 1, 0])), ValueError),
        ([(block.reshape(1, 18, 4), 3, rows, None, None, None, None)], ValueError),  # a grid names its base
        ([(block, 3, rows)], TypeError),
    ):
        with pytest.raises(exc):
            wire.finish_wire_chunk(m, bad, cols())
    with pytest.raises((TypeError, ValueError, BufferError)):  # columns of the wrong width
        m.finish_wire_chunk(one(), *[c.astype(np.int64) for c in cols()])
    with pytest.raises(ValueError):
        m.finish_wire_chunk(one(), *cols()[:3], np.zeros(2, np.int64))


def test_finish_wire_chunk_holds_no_state_between_threads():
    """240 dispatches' blocks finished from eight threads at a 10 µs switch
    interval, each twice: every one is the finish NumPy makes of it alone."""
    import sys
    from concurrent.futures import ThreadPoolExecutor

    from gubernator_tpu.ops import wire
    from gubernator_tpu.ops.kernel2 import unpack_outputs

    jobs = []
    for seed in range(240):
        rng = np.random.default_rng(seed)
        n = int(rng.integers(40, 400))
        cut = sorted(rng.choice(np.arange(1, n), 3, replace=False))
        order = rng.permutation(n)
        passes, want = [], [np.zeros(n, np.int64) for _ in range(4)]
        stats = np.zeros(4, np.int64)
        for rows in np.split(order, cut)[:3]:
            block = _egress_block(rng, 512, rows.size, STAGE_NOW + seed, dropped=0.1)
            passes.append((block, rows.size, rows, None, None, None, None))
            (s, l, r, t, _d, _h), st = unpack_outputs(block, rows.size)
            for col, val in zip(want, (s, l, r, t)):
                col[rows] = val
            stats += st
        # the aggregate: the last rows in groups of one to four
        tail = np.split(order, cut)[3]
        counts = []
        while sum(counts) < tail.size:
            counts.append(min(int(rng.integers(1, 5)), tail.size - sum(counts)))
        counts = np.asarray(counts)
        block = _egress_block(rng, 512, counts.size, STAGE_NOW + seed)
        passes.append((block, counts.size, None, tail, counts, None, None))
        (s, l, r, t, _d, _h), st = unpack_outputs(block, counts.size)
        src = np.repeat(np.arange(counts.size), counts)
        for col, val in zip(want, (s, l, r, t)):
            col[tail] = val[src]
        jobs.append((passes, want, tuple(stats + st), n))

    def finish(job):
        passes, want, stats, n = job
        for _ in range(2):
            cols = _blank_columns(n)
            done = wire.finish_wire_chunk(m, passes, cols)
            assert done.stats == stats
            assert done.later_rows == n - passes[0][1]
            for got, w in zip(cols, want):
                assert (got == w).all()
            flagged = sum(int((p[0][: p[1], 3] & 4 != 0).sum()) for p in passes)
            assert len(done.dropped) == flagged
        return True

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        with ThreadPoolExecutor(8) as pool:
            done = list(pool.map(finish, jobs, timeout=120))
    finally:
        sys.setswitchinterval(interval)
    assert done == [True] * 240


# ------------------------------------------- the parser's stamp and its sums
STAMP_NOW = 1_790_000_000_123


def _stamp_items(case):
    """Item mixes for the clocked parse: which rows their client stamped,
    and an algorithm value that is none of the five."""
    rng = random.Random(41)
    n = {"empty": 0}.get(case, 300)
    items = []
    for i in range(n):
        r = pb.RateLimitReq(
            name="st", unique_key=f"k{i}", hits=1, limit=10, duration=60_000,
            algorithm=rng.choice([0, 0, 1, 2, 3, 4]),
        )
        if case == "out_of_range_algorithm" and i % 7 == 0:
            r.algorithm = rng.choice([5, 9, 1 << 20, -1])
        stamped = {
            "all_unstamped": False, "all_stamped": True,
        }.get(case, rng.random() < 0.5)
        if stamped:
            r.created_at = STAMP_NOW + rng.randrange(-700, 700)
        items.append(r)
    return items


@pytest.mark.parametrize("case", [
    "all_unstamped", "all_stamped", "mixed", "empty", "out_of_range_algorithm",
])
def test_parse_with_a_clock_stamps_and_reduces_as_numpy_would(case):
    """Handed the handler's clock, the parser writes it where the client
    sent no stamp and reduces the stamps it serves, the rows by decision
    label and the rows it stamped: each against its NumPy form, the column
    byte for byte, and every other buffer as the parse without a clock."""
    from gubernator_tpu.service.runner import _ALGO_LABELS, _label_counts
    from gubernator_tpu.service.wire import RowSummary

    items = _stamp_items(case)
    data = pb.GetRateLimitsReq(requests=items).SerializeToString()
    bare = m.parse_get_rate_limits(data)
    got = m.parse_get_rate_limits(data, STAMP_NOW)
    n = got[0]
    assert n == len(items)
    sent = np.frombuffer(bare[8], np.int64)
    want = np.where(sent == 0, STAMP_NOW, sent)
    assert got[8] == want.tobytes()  # created_at as served
    for k in range(15):  # every other buffer, and the traceparent
        if k != 8:
            assert got[k] == bare[k], k
    summary, bare_summary = RowSummary(*got[15]), RowSummary(*bare[15])
    assert summary.unstamped == bare_summary.unstamped == int((sent == 0).sum())
    assert summary.unstamped == {
        "all_unstamped": n, "all_stamped": 0, "empty": 0,
    }.get(case, summary.unstamped)
    if n:
        assert (summary.stamp_lo, summary.stamp_hi) == (want.min(), want.max())
        assert (bare_summary.stamp_lo, bare_summary.stamp_hi) == (sent.min(), sent.max())
        assert summary.first_fp == np.frombuffer(got[1], np.int64)[0]
    else:
        assert (summary.stamp_lo, summary.stamp_hi) == (STAMP_NOW, STAMP_NOW)
        assert (bare_summary.stamp_lo, bare_summary.stamp_hi) == (0, 0)
        assert summary.first_fp == 0
    assert summary.stamped and bare_summary.stamped == (n > 0 and bool((sent != 0).all()))
    algo = np.frombuffer(got[2], np.int32)
    assert list(summary.algo_counts) == _label_counts(algo)
    assert len(summary.algo_counts) == len(_ALGO_LABELS) and sum(summary.algo_counts) == n
    if case == "out_of_range_algorithm":
        assert summary.algo_counts[-1] == sum(
            not 0 <= it.algorithm < len(_ALGO_LABELS) - 1 for it in items
        ) > 0
    # what the summary had before is what it was
    assert summary[:7] == bare_summary[:7]
    assert bare_summary.algo_counts == summary.algo_counts
