"""The table at the size a deployment holds (deployment `token100m`: 100M keys
in 134,217,728 slots, 8 GiB of a chip's 16), as far as a CPU can say.

What no test here can allocate it still holds to account: the engine against
the plain bounded table (`tests/oracle/bounded_table.py`) at the published
load of 0.745 keys a slot in two small tables, every answer, the eviction
count and which keys went, through the fused wire path; the same answers at
both sizes for keys neither table evicted; the write mode every warm pad
resolves to at 1 GiB and at 8 GiB, and the pass counts behind
`sweep_pass_share`; every warm program lowered at the published shape (no
allocation) and, where the chip's compiler can be described here, compiled
for a v5e with its scratch beside the 8 GiB table counted; the index
arithmetic at that shape's last bucket; and the stages and counters the size
added, in `/v1/debug/pipeline` and the Prometheus text.
"""

import asyncio
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gubernator_tpu import native
from gubernator_tpu.hashing import fingerprint
from gubernator_tpu.ops import kernel2, telemetry
from gubernator_tpu.ops import wire as wire_mod
from gubernator_tpu.ops.engine import LocalEngine, _pad_size
from gubernator_tpu.ops.layout import FULL
from gubernator_tpu.ops.table2 import K, ROW, Table2, live_count_device
from gubernator_tpu.proto import gubernator_pb2 as pb
from gubernator_tpu.service.daemon import Daemon
from gubernator_tpu.service.metrics import parse_metrics
from gubernator_tpu.service.runner import EngineRunner
from gubernator_tpu.service.wire import wire_batch_from_wire
from gubernator_tpu.types import RateLimitRequest

from tests.cluster import daemon_config
from tests.oracle.bounded_table import BoundedTable

needs_native = pytest.mark.skipif(
    native.load() is None, reason="native toolchain unavailable"
)

LOAD = 0.745  # keys a slot: 100,000,000 in 134,217,728
NOW = 1_700_000_000_000
LIMIT, DURATION = 5, 3_600_000
RPC_ITEMS = 1_000
PUBLISHED_BUCKETS = 134_217_728 // K  # 16,777,216 rows of 128 lanes
GIB1_BUCKETS = 16_777_216 // K
WARM_PADS = [16 << i for i in range(11)]  # GUBER_WARM_SHAPES=pow2: 16 .. 16,384


def async_test(fn):
    @functools.wraps(fn)
    def wrapper(*a, **k):
        asyncio.run(fn(*a, **k))

    return wrapper


# ------------------------------------------ the engine and the plain table


def key_sequence(n_keys: int, seed: int):
    """The checks a run sends, as chunks of key indices: every key once (the
    fill), then twice as many drawn uniformly, so that keys come back after
    their neighbours may have pushed them out and some pass their limit. No
    chunk holds a key twice (copies of a key in one dispatch are another
    rule, tests/test_wire_split.py)."""
    rng = np.random.default_rng(seed)
    chunks = [np.arange(lo, min(lo + RPC_ITEMS, n_keys)) for lo in range(0, n_keys, RPC_ITEMS)]
    for _ in range(2 * len(chunks)):
        chunks.append(rng.choice(n_keys, size=min(RPC_ITEMS, n_keys), replace=False))
    return chunks


def rpc(tag: str, keys, now: int):
    wb = wire_batch_from_wire(pb.GetRateLimitsReq(requests=[
        pb.RateLimitReq(name=tag, unique_key=f"k{k}", hits=1, limit=LIMIT,
                        duration=DURATION, created_at=now)
        for k in keys
    ]).SerializeToString())[0]
    assert wb.all_encodable
    return wb


async def served(slots: int, tag: str, chunks):
    """`chunks` through the fused wire path of a fresh engine of `slots`
    slots, one dispatch a chunk, a millisecond apart: every answer in order,
    the engine, and the keys that still have a lane at the end."""
    eng = LocalEngine(capacity=slots, wire="compact")
    runner = EngineRunner(eng)
    answers = []
    try:
        for i, keys in enumerate(chunks):
            fused = []
            got = await runner.check_wire(
                [rpc(tag, keys, NOW + i)], now_ms=NOW + i,
                done=lambda _rc, _exc, n_fused: fused.append(n_fused),
            )
            assert fused and fused[0] >= 1, "the chunk left the fused wire path"
            answers.append(np.stack([got.status, got.remaining, got.reset_time], axis=1))
    finally:
        runner.close()
    return np.concatenate(answers), eng


def reference(n_buckets: int, tag: str, chunks):
    table = BoundedTable(n_buckets)
    answers = []
    for i, keys in enumerate(chunks):
        fps = [fingerprint(tag, f"k{k}") for k in keys]
        answers += table.check_together(fps, NOW + i, 1, LIMIT, DURATION)
    return np.asarray(answers, dtype=np.int64), table


@pytest.fixture(scope="module")
def runs():
    """Each (slots, keys) pair served once and referenced once."""
    cache = {}

    def get(slots: int, n_keys: int):
        if (slots, n_keys) not in cache:
            chunks = key_sequence(n_keys, seed=slots ^ n_keys)
            got, eng = asyncio.run(served(slots, "scale", chunks))
            want, table = reference(eng.table.n_buckets, "scale", chunks)
            cache[slots, n_keys] = (chunks, got, eng, want, table)
        return cache[slots, n_keys]

    return get


@needs_native
@pytest.mark.parametrize("slots", [1 << 13, 1 << 16], ids=["8Ki-slots", "64Ki-slots"])
def test_the_engine_is_the_plain_bounded_table_at_the_published_load(slots, runs):
    n_keys = int(LOAD * slots)
    chunks, got, eng, want, table = runs(slots, n_keys)
    assert eng.table.n_buckets == slots // K
    # every answer: status, remaining, reset_time
    assert got.shape == want.shape and np.array_equal(got, want), (
        f"{int((got != want).any(axis=1).sum())} of {len(want)} answers differ"
    )
    # the count, and it is the published load's: some keys did lose their lane
    assert eng.stats.evicted_unexpired == table.evicted_live_total > 0
    assert eng.stats.dropped == 0
    # which keys went: the keys that hold a lane at the end are the same
    fps = np.asarray([fingerprint("scale", f"k{k}") for k in range(n_keys)], dtype=np.int64)
    found, _state = eng.read_state(fps)
    held = np.asarray([table.holds(int(fp)) for fp in fps])
    assert np.array_equal(np.asarray(found), held)
    assert set(fps[~held].tolist()) <= set(table.evicted)
    assert eng.live_count(NOW + len(chunks)) == int(held.sum())


@needs_native
def test_the_tables_size_changes_no_answer(runs):
    """The same checks into a table of 8Ki slots (0.745 keys a slot) and one
    of 64Ki (an eighth of that load): every check of a key that neither
    table ever evicted is answered the same."""
    n_keys = int(LOAD * (1 << 13))
    chunks, small, _e1, _w1, t_small = runs(1 << 13, n_keys)
    # the larger table is served with the smaller one's sequence
    large, eng_large = asyncio.run(served(1 << 16, "scale", chunks))
    _want, t_large = reference(eng_large.table.n_buckets, "scale", chunks)
    keys = np.concatenate(chunks)
    evicted = {*t_small.evicted, *t_large.evicted}
    kept = np.asarray([fingerprint("scale", f"k{k}") not in evicted for k in range(n_keys)])
    rows = kept[keys]
    assert t_small.evicted_live_total > 0 and rows.sum() > len(keys) // 2
    assert small[rows].tobytes() == large[rows].tobytes()
    # and the evicted ones are where the two differ, if anywhere
    assert not np.array_equal(small, large)


# ------------------------------------------------- the write mode by size


MODES = {
    # (buckets, pad) -> what "sparse" resolves to: a 1 GiB table streams
    # whole from 8K rows up, the published 8 GiB never below 64K
    **{(GIB1_BUCKETS, pad): "sparse" for pad in WARM_PADS if pad <= 4096},
    (GIB1_BUCKETS, 8192): "sweep",
    (GIB1_BUCKETS, 16384): "sweep",
    **{(PUBLISHED_BUCKETS, pad): "sparse" for pad in WARM_PADS},
    (PUBLISHED_BUCKETS, 32768): "sparse",
    (PUBLISHED_BUCKETS, 65536): "sweep",
}


@pytest.mark.parametrize("buckets,pad", sorted(MODES), ids=lambda v: str(v))
def test_the_write_a_warm_pad_resolves_to(buckets, pad):
    assert kernel2.resolve_write("sparse", buckets, pad) == MODES[buckets, pad]
    assert kernel2.resolve_write("sweep", buckets, pad) == "sweep"
    assert kernel2.resolve_write("xla", buckets, pad) == "xla"


def test_passes_are_counted_by_the_write_that_ran():
    """64Ki slots: a 16-row pad is sparse, a 32-row pad sweeps (interpreted
    Pallas on the CPU). Each pass counts once, under what its shape resolved
    to; a warm-up's passes are forgotten, its shapes kept."""
    eng = LocalEngine(capacity=1 << 16, write_mode="sparse")
    nb = eng.table.n_buckets
    assert kernel2.resolve_write("sparse", nb, 16) == "sparse"
    assert kernel2.resolve_write("sparse", nb, 32) == "sweep"

    def check(n, tag):
        out = eng.check([
            RateLimitRequest(name=tag, unique_key=f"k{i}", hits=1, limit=3, duration=60_000)
            for i in range(n)
        ], now_ms=NOW)
        assert all(r.remaining == 2 and not r.error for r in out)

    assert eng.passes_by_write() == {"sparse": 0, "sweep": 0, "xla": 0}
    check(10, "a")
    assert eng.passes_by_write() == {"sparse": 1, "sweep": 0, "xla": 0}
    check(20, "b")
    check(30, "c")
    assert eng.passes_by_write() == {"sparse": 1, "sweep": 2, "xla": 0}
    assert eng.stats.dispatches == 3
    eng.forget_passes()
    assert eng.passes_by_write() == {"sparse": 0, "sweep": 0, "xla": 0}
    assert sorted(eng._pad_passes) == [16, 32]  # what a resize compiles again
    # off the TPU the default write is the scatter, counted as such
    plain = LocalEngine(capacity=1 << 13)
    plain.check([RateLimitRequest(name="t", unique_key="k", hits=1, limit=3, duration=60_000)])
    assert plain.passes_by_write() == {"sparse": 0, "sweep": 0, "xla": 1}


# ------------------------------------- every program at the published shape


def published(sharding=None):
    rows = jax.ShapeDtypeStruct((PUBLISHED_BUCKETS, ROW), jnp.int32, sharding=sharding)
    scalar = jax.ShapeDtypeStruct((), jnp.int64, sharding=sharding)
    return rows, Table2(rows=rows, layout=FULL), scalar


def programs(sharding=None, pads=WARM_PADS, maths=("token", "gcra", "int", "mixed")):
    """name -> a thunk that lowers one program `daemon.warm_up` compiles (and
    the 5 s scan, which it does not), over the published table shape."""
    rows, table, scalar = published(sharding)
    spec = lambda *shape, dtype=jnp.int32: jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    out = {}
    for pad in pads:
        out[f"decide2_wire_cols token pad={pad}"] = functools.partial(
            wire_mod.decide2_wire_cols.lower, table, spec(5, pad + 1),
            write="sparse", math="token", cascade=False, evictees=False)
    for math in maths[1:]:
        out[f"decide2_wire_cols {math} pad=16"] = functools.partial(
            wire_mod.decide2_wire_cols.lower, table, spec(5, 17),
            write="sparse", math=math, cascade=False, evictees=False)
    out["decide2_packed_cols token pad=16"] = functools.partial(
        kernel2.decide2_packed_cols.lower, table, spec(12, 16, dtype=jnp.int64),
        write="sparse", math="token", cascade=False, evictees=False)
    out["telemetry scan"] = functools.partial(
        telemetry._scan.lower, rows, scalar,
        blk=telemetry.block_width(PUBLISHED_BUCKETS), layout=FULL)
    out["live count"] = functools.partial(live_count_device.lower, rows, scalar, FULL)
    return out


@pytest.mark.parametrize("name", sorted(programs()))
def test_a_warm_program_lowers_at_the_published_shape(name):
    """Traced and lowered over `ShapeDtypeStruct`s, nothing allocated: a
    guard of the kernel that the shape trips (`_probe_claim2`'s int32 slot
    ids), a reshape that cannot be, an index that a static shape overflows,
    all raise here. The TPU's write (`sparse`: the Pallas grid, interpreted
    off the chip) is what is lowered."""
    lowered = programs()[name]()
    out_shapes = [tuple(s.shape) for s in jax.tree_util.tree_leaves(lowered.out_info)]
    if name.startswith("decide2"):
        assert out_shapes[0] == (PUBLISHED_BUCKETS, ROW)  # the table, donated through
    else:
        assert (PUBLISHED_BUCKETS, ROW) not in out_shapes


@pytest.mark.parametrize("pad", WARM_PADS + [32768, 65536])
def test_index_arithmetic_at_the_last_bucket_stays_inside_int32(pad):
    """The table holds 2^31 int32 elements, one more than an int32 counts,
    so nothing may index it by element. What the kernel does index by: slot
    ids (bucket * K + lane, the sentinel one past the last), their sort key
    (slot * 2 + 1), bucket rows, and blocks of rows in the Pallas write."""
    i32 = np.iinfo(np.int32).max
    nb = PUBLISHED_BUCKETS
    assert nb * ROW == 2**31 > i32
    last_slot = (nb - 1) * K + (K - 1)
    sentinel = nb * K
    assert sentinel * 2 + 1 <= i32 and last_slot < sentinel
    # numpy's int32 raises on a wrap where Python's int would not
    with np.errstate(over="raise"):
        assert int(np.int32(nb - 1) * np.int32(K) + np.int32(K - 1)) == last_slot
        assert int(np.int32(sentinel) * np.int32(2) + np.int32(1)) == 2 * sentinel + 1
    if kernel2.resolve_write("sparse", nb, pad) == "sparse":
        blk, u, grid = kernel2.sparse_geometry(nb, pad)
        assert grid == min(nb // blk, pad) and blk == 64
    else:
        blk, u = kernel2.sweep_geometry(nb, pad)
    assert nb % blk == 0 and pad % u == 0
    # a block's first slot, and one past the last block's: `db * KBLK`,
    # `(db + 1) * KBLK` of _write_sparse; a block index times its rows
    assert (nb // blk) * K * blk == sentinel <= i32
    assert (nb // blk - 1) * blk + blk - 1 == nb - 1


@pytest.fixture(scope="module")
def one_v5e():
    """A described v5e chip to compile for (no chip is attached, nothing can
    run): skips where the TPU's compiler cannot be loaded here."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or another process holds its lock
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("name", [
    "telemetry scan", "live count",
    "decide2_wire_cols token pad=16", "decide2_wire_cols token pad=8192",
])
def test_the_chips_compiler_takes_the_program_beside_an_8_gib_table(
    name, one_v5e, monkeypatch
):
    """Compiled for the chip (Mosaic for the Pallas write), with what the
    program wants beside its arguments counted: the table is 8 GiB of the
    chip's 15.75, so a program that copies it whole is refused (the scan did,
    into another tiling: 10 GiB of scratch) and one that holds it twice
    does not fit either. A pass that compiles is no chip run: no answer, no
    time."""
    from jax.experimental.compilation_cache import compilation_cache

    # the kernels ask `jax.default_backend()` for their TPU branch; a trace
    # of the same program made without this (the lowering tests above) must
    # not be found again, nor this one by a later test
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    jax.clear_caches()
    try:
        compiled = programs(one_v5e, pads=[16, 8192])[name]().compile()
    finally:
        jax.clear_caches()
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    mem = compiled.memory_analysis()
    table_bytes = PUBLISHED_BUCKETS * ROW * 4
    assert mem.argument_size_in_bytes >= table_bytes
    assert mem.temp_size_in_bytes < 256 << 20, "scratch beside the table"
    if name.startswith("decide2"):
        assert mem.alias_size_in_bytes >= table_bytes, "the table is not updated in place"


# ------------------------------ the tiered deployment's programs (PR 42)


def tiered_programs(sharding=None, pads=(16, 8192)):
    """name -> a thunk that lowers one program of a TIERED table
    (`token40m-tiered`: a shadow attached, docs/tiering.md) at that
    deployment's 1 GiB table: the pipelined hits-only decide, the miss
    path's claiming decide with its evictee sidecar, the promote's merge
    with its own, and the idle sweep's extract."""
    from gubernator_tpu.ops.table2 import _extract_idle_first

    spec = lambda *shape, dtype=jnp.int32: jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    rows = spec(GIB1_BUCKETS, ROW)
    table = Table2(rows=rows, layout=FULL)
    out = {}
    for pad in pads:
        for name, ev in (("hits-only decide", "defer"), ("claiming decide", True)):
            out[f"tiered {name} pad={pad}"] = functools.partial(
                wire_mod.decide2_wire_cols.lower, table, spec(5, pad + 1),
                write="sparse", math="token", cascade=False, evictees=ev)
        out[f"tiered merge2 pad={pad}"] = functools.partial(
            kernel2.merge2.lower, table, spec(pad, dtype=jnp.int64), spec(pad, 16),
            spec(pad, dtype=jnp.int64), spec(pad, dtype=jnp.bool_),
            write="sparse", evictees=True)
    out["tiered idle extract"] = functools.partial(
        _extract_idle_first.lower, rows, spec(dtype=jnp.int64), spec(dtype=jnp.int64),
        layout=FULL, max_rows=1 << 16)
    return out


@pytest.mark.parametrize("name", sorted(tiered_programs()))
def test_a_tiered_program_lowers_at_the_deployments_shape(name):
    lowered = tiered_programs()[name]()
    shapes = [tuple(s.shape) for s in jax.tree_util.tree_leaves(lowered.out_info)]
    pad = int(name.rsplit("=", 1)[1]) if "=" in name else None
    if "extract" in name:
        assert (GIB1_BUCKETS, ROW) not in shapes and (1 << 16, 16) in shapes
        return
    assert shapes[0] == (GIB1_BUCKETS, ROW)  # the table, donated through
    if "hits-only" in name:
        assert shapes[1] == (pad + 2, 4)  # no sidecar: it evicts nothing
    elif "claiming" in name:
        assert shapes[1] == (5 * pad + 2, 4)  # answers, 64 B a row of sidecar, stats
    else:
        assert shapes[1:] == [(pad,), (pad, 16)]  # landed mask, the merge's victims


@pytest.mark.parametrize("name", [
    "tiered hits-only decide pad=8192", "tiered claiming decide pad=8192",
    "tiered merge2 pad=8192", "tiered idle extract",
])
def test_the_chips_compiler_takes_a_tiered_program(name, one_v5e, monkeypatch):
    """Compiled for the chip beside the deployment's 1 GiB table: the table
    is updated in place by the three programs that write it, and the idle
    extract, which once sorted the whole table into another tiling (9.7 GiB
    of scratch), stays under 1 GiB."""
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    jax.clear_caches()
    try:
        compiled = tiered_programs(one_v5e, pads=[8192])[name]().compile()
    finally:
        jax.clear_caches()
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    mem = compiled.memory_analysis()
    table_bytes = GIB1_BUCKETS * ROW * 4
    assert mem.argument_size_in_bytes >= table_bytes
    assert mem.temp_size_in_bytes < 1 << 30, "scratch beside the table"
    if "extract" not in name:
        assert mem.alias_size_in_bytes >= table_bytes, "the table is not updated in place"
        assert mem.temp_size_in_bytes < 256 << 20


# ----------------------------------------- what the size added to be seen


@async_test
async def test_the_sizes_stages_and_counters_are_served():
    d = await Daemon.spawn(daemon_config(cache_size=1 << 13))
    try:
        cols_keys = [f"k{i}" for i in range(40)]
        out = d.engine.check([
            RateLimitRequest(name="seen", unique_key=k, hits=1, limit=3, duration=60_000)
            for k in cols_keys
        ])
        assert all(r.remaining == 2 for r in out)
        eng = d.debug_pipeline()["engine"]
        # before the first scan there is no load to report
        assert eng["table_load"] is None
        await d.collect_telemetry()
        eng = d.debug_pipeline()["engine"]
        assert eng["table_load"] == pytest.approx(40 / (1 << 13))
        assert eng["evicted_live_total"] == 0
        # the CPU scatters: the one check above is a pass of neither kind
        assert (eng["passes_total"], eng["passes_sparse"], eng["passes_sweep"]) == (1, 0, 0)
        assert telemetry.scan_chunk(PUBLISHED_BUCKETS) == telemetry.scan_chunk(GIB1_BUCKETS) == 16384
        n_dev = eng["device_count"]
        assert len(eng["device_peak_bytes"]) == len(eng["device_bytes_limit"]) == n_dev
        text = d.metrics.render().decode()
        fams = parse_metrics(text)
        passes = fams["gubernator_tpu_device_passes_total"]
        assert passes[(("write", "xla"),)] == 1.0  # the check above; warm-up forgotten
        assert passes[(("write", "sparse"),)] == passes[(("write", "sweep"),)] == 0.0
        counts = fams["gubernator_tpu_stage_duration_count"]
        for stage in ("table_alloc", "warm_up", "scan_launch", "scan_fetch"):
            assert counts[(("stage", stage),)] == 1.0, stage
        assert fams["gubernator_tpu_table_load_factor"][()] == pytest.approx(40 / (1 << 13))
    finally:
        await d.close()


def test_a_scan_in_pieces_is_the_scan():
    """The chunked scan adds up what the whole-table body gives, at a table
    of several chunks and at one smaller than an occupancy block."""
    rng = np.random.default_rng(7)
    for n_buckets in (1 << 16, 32):
        eng = LocalEngine(capacity=n_buckets * K)
        n = n_buckets * 2
        eng.check([
            RateLimitRequest(name="scan", unique_key=f"k{i}", hits=int(h), limit=4,
                             duration=int(dur))
            for i, (h, dur) in enumerate(zip(rng.integers(0, 6, n), rng.choice([50, 90_000], n)))
        ], now_ms=NOW)
        rows, now = eng.table.rows, jnp.int64(NOW + 1_000)
        blk = telemetry.block_width(n_buckets)
        assert telemetry.scan_chunk(n_buckets) == min(n_buckets, 16384)
        whole = telemetry._scan_body(rows, now, blk, FULL)
        pieces = telemetry._scan(rows, now, blk=blk, layout=FULL)
        assert np.array_equal(np.asarray(whole), np.asarray(pieces))
        assert int(pieces[0]) > 0
