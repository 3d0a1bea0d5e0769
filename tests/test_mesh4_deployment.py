"""The deployment `bench/configs/mesh4-sharded.json` defines — the cluster as
the four shards of one host, GUBER_ENGINE=sharded — at a size the CPU holds:
4,096 slots a shard on four virtual devices, the selectors a TPU resolves
`auto` to forced (route=device, dedup=device, compact wire).

The served half runs the benchmark's own server child (`bench/launcher.py`:
`python -m gubernator_tpu` plus a control thread for the profiler) with the
configuration file's `server_env`, and goes through the gRPC door. tier-1's
conftest gives this process eight devices and a daemon takes every local
device, so the child gets `--xla_force_host_platform_device_count=4`.
"""

import asyncio
import functools
import glob
import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.request

import numpy as np
import pytest

from gubernator_tpu.client import V1Client
from gubernator_tpu.ops.batch import RequestColumns
from gubernator_tpu.ops.engine import LocalEngine
from gubernator_tpu.parallel import ShardedEngine, make_mesh
from gubernator_tpu.parallel.a2a import exchange_traffic
from gubernator_tpu.parallel.mesh import shard_of
from gubernator_tpu.types import RateLimitRequest

from tests.oracle.algos import TokenOracle

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "bench", "configs", "mesh4-sharded.json")) as _f:
    CONFIG = json.load(_f)
SHARDS = int(CONFIG["chips"])
SLOTS_PER_SHARD = 4096
# what `auto` resolves to on a TPU (a CPU backend takes the host paths)
TPU_SELECTORS = {
    "GUBER_SHARD_ROUTE": "device", "GUBER_SHARD_DEDUP": "device",
    "GUBER_WIRE_COMPACT": "1",
}
LIMIT = int(CONFIG["keyspace"]["limit"])
DURATION = int(CONFIG["keyspace"]["duration_ms"])
RPC_ITEMS = 1000  # GUBER_BATCH_LIMIT, upstream's cap
KEYS = 3000


def async_test(fn):
    @functools.wraps(fn)
    def wrapper(*a, **k):
        asyncio.run(fn(*a, **k))

    return wrapper


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Child:
    """bench/launcher.py as a child, driven as bench/doors.py drives it: one
    JSON command a line on stdin, one answer a line on BENCH_REPLY_FD."""

    def __init__(self, log_path: str):
        self.grpc = f"127.0.0.1:{_free_port()}"
        self.http = f"127.0.0.1:{_free_port()}"
        env = dict(CONFIG["server_env"])
        env["GUBER_CACHE_SIZE"] = str(SLOTS_PER_SHARD * SHARDS)
        env.pop("GUBER_WARM_SHAPES")  # compile on first use instead
        reply_r, reply_w = os.pipe()
        self._log = open(log_path, "wb")
        self.proc = subprocess.Popen(
            [sys.executable, os.path.join(ROOT, "bench", "launcher.py")],
            env={
                **os.environ, **env, **TPU_SELECTORS,
                "PYTHONPATH": ROOT + os.pathsep + os.environ.get("PYTHONPATH", ""),
                "JAX_PLATFORMS": "cpu",
                "XLA_FLAGS": f"--xla_force_host_platform_device_count={SHARDS}",
                "GUBER_GRPC_ADDRESS": self.grpc, "GUBER_HTTP_ADDRESS": self.http,
                "BENCH_REPLY_FD": str(reply_w),
            },
            cwd=ROOT, stdin=subprocess.PIPE, stdout=self._log,
            stderr=subprocess.STDOUT, pass_fds=(reply_w,),
        )
        os.close(reply_w)
        self._reply = os.fdopen(reply_r, "r")

    def get(self, path: str) -> dict:
        with urllib.request.urlopen(f"http://{self.http}{path}", timeout=30) as r:
            return json.loads(r.read())

    def wait_healthy(self, timeout_s: float = 300.0) -> None:
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            assert self.proc.poll() is None, "the server died while starting"
            try:
                if self.get("/v1/HealthCheck").get("status") == "healthy":
                    return
            except OSError:
                pass
            time.sleep(0.25)
        raise TimeoutError("the server did not come up")

    def command(self, **msg) -> dict:
        self.proc.stdin.write((json.dumps(msg) + "\n").encode())
        self.proc.stdin.flush()
        res = json.loads(self._reply.readline())
        assert res.pop("ok"), res
        return res

    def sigterm(self, timeout_s: float) -> "tuple[int, float]":
        """(exit code, seconds it took) as bench/doors.py Server.stop asks:
        SIGTERM, then `timeout_s` to be gone."""
        t0 = time.monotonic()
        self.proc.send_signal(signal.SIGTERM)
        rc = self.proc.wait(timeout=timeout_s)
        return rc, time.monotonic() - t0

    def close(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()
        for f in (self.proc.stdin, self._reply, self._log):
            try:
                f.close()
            except OSError:
                pass


@pytest.fixture(scope="module")
def server(tmp_path_factory):
    child = Child(str(tmp_path_factory.mktemp("mesh4") / "server.log"))
    try:
        child.wait_healthy()
        yield child
    finally:
        child.close()


def _req(key: str, hits: int, at: int) -> RateLimitRequest:
    return RateLimitRequest(
        name="mesh4", unique_key=key, hits=hits, limit=LIMIT,
        duration=DURATION, created_at=at,
    )


def _now_ms() -> int:
    return int(time.time() * 1000)


@async_test
async def test_the_deployment_answers_as_the_oracle_through_the_door(server):
    """Seeded 1,000-item RPCs of uniform keys, one after another, then one
    RPC that holds a key three times (one aggregate decision from occurrence
    0, every copy answered with it), then every key read back with hits=0:
    each answer is what the plain token bucket gives."""
    eng = server.get("/v1/debug/pipeline")["engine"]
    want = {k: v for k, v in CONFIG["expect_engine"].items()
            if k in ("n_shards", "route", "dedup", "a2a_impl", "wire")}
    assert {k: eng[k] for k in want} == want
    assert eng["kind"] == "GlobalShardedEngine" and eng["device_count"] == SHARDS

    rng = np.random.default_rng(2_654_435_761)
    oracle = TokenOracle()
    client = V1Client(server.grpc, timeout_s=120.0)
    compared = 0

    async def rpc(keys, hits, at):
        """`keys` may hold a key more than once: its copies are one
        aggregate of their hits, answered to each."""
        nonlocal compared
        got = await client.check([_req(f"k{k}", hits, at) for k in keys])
        assert len(got) == len(keys)
        uniq, counts = np.unique(keys, return_counts=True)
        exp = {
            int(k): oracle.check(int(k), at, hits * int(c), LIMIT, DURATION)
            for k, c in zip(uniq, counts)
        }
        for k, r in zip(keys, got):
            assert not r.error
            assert (r.status, r.remaining, r.reset_time, r.limit) == (
                *exp[int(k)], LIMIT), (k, r)
            compared += 1

    try:
        for _ in range(6):
            await rpc(rng.choice(KEYS, size=RPC_ITEMS, replace=False), 1, _now_ms())
        dup = rng.choice(KEYS, size=RPC_ITEMS - 2, replace=False)
        await rpc(np.concatenate([dup[:1], dup, dup[:1]]), 1, _now_ms())
        at = _now_ms()
        for lo in range(0, KEYS, RPC_ITEMS):
            await rpc(np.arange(lo, min(lo + RPC_ITEMS, KEYS)), 0, at)
    finally:
        await client.close()
    assert compared == 7 * RPC_ITEMS + KEYS
    table = server.get("/v1/debug/table")
    assert table["evicted_live_total"] == 0
    assert sum(table["per_shard_live"]) == table["live_keys"] == len(oracle.state)
    end = server.get("/v1/debug/pipeline")["engine"]
    assert end["a2a_overflow"] == 0 and end["dropped"] == 0 and not end["poisoned"]


def _spans(trace_dir: str) -> dict:
    """{stage: [(stats, start_ns, end_ns)]} of the gub: host spans."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    out: dict = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("gub:"):
                    out.setdefault(ev.name[4:], []).append(
                        (dict(ev.stats), ev.start_ns, ev.start_ns + ev.duration_ns))
    return out


@async_test
async def test_traced_under_load_then_sigterm_exits_0(server, tmp_path):
    """What a traced run of the cell does to the server: a jax.profiler
    trace started and stopped under load, then SIGTERM. Every RPC answers,
    the counters grow by what the shapes say, the trace holds the mesh
    engine's host stages as parts of their dispatch's put and fetch, and the
    server leaves with code 0 inside the 60 s the benchmark allows."""
    client = V1Client(server.grpc, timeout_s=120.0, channels=2)
    rng = np.random.default_rng(40_503)
    answered = 0

    stop = asyncio.Event()

    async def worker() -> None:
        nonlocal answered
        while not stop.is_set():
            keys = rng.choice(KEYS, size=RPC_ITEMS, replace=False)
            got = await client.check([_req(f"k{k}", 0, _now_ms()) for k in keys])
            assert len(got) == RPC_ITEMS and not any(r.error for r in got)
            answered += 1

    try:
        # one dispatch's worth of rows at a time first: the counters' growth
        # is then a known number of passes of a known shape
        e0 = server.get("/v1/debug/pipeline")["engine"]
        for _ in range(3):
            got = await client.check(
                [_req(f"k{k}", 0, _now_ms()) for k in range(RPC_ITEMS)])
            assert len(got) == RPC_ITEMS
        e1 = server.get("/v1/debug/pipeline")["engine"]
        passes = e1["dispatches"] - e0["dispatches"]
        assert passes == 3 and e1["checks"] - e0["checks"] == 3 * RPC_ITEMS
        c = 256  # 1,000 rows over four devices, padded to a power of two
        lanes, rows, nbytes = exchange_traffic(c, SHARDS)
        assert e1["mesh_lanes"] - e0["mesh_lanes"] == passes * SHARDS * lanes
        assert e1["exchange_rows"] - e0["exchange_rows"] == passes * rows
        assert e1["exchange_bytes"] - e0["exchange_bytes"] == passes * nbytes
        assert 0 < 3 * RPC_ITEMS < passes * SHARDS * lanes

        tdir = str(tmp_path / "trace")
        load = asyncio.gather(*(worker() for _ in range(4)))
        await asyncio.sleep(0.5)
        loop = asyncio.get_running_loop()

        async def dispatches() -> int:
            pipe = await loop.run_in_executor(None, server.get, "/v1/debug/pipeline")
            return pipe["engine"]["dispatches"]

        await loop.run_in_executor(
            None, functools.partial(server.command, cmd="trace_start", dir=tdir))
        # The load runs on through trace_stop, and the trace stays open until
        # dispatches have begun and ended inside it: on a loaded host
        # trace_start alone took longer than a load of fixed length, and the
        # trace then held no span.
        d0, deadline = await dispatches(), time.monotonic() + 60.0
        while await dispatches() < d0 + 6 and time.monotonic() < deadline:
            await asyncio.sleep(0.25)
        t0 = time.monotonic()
        await loop.run_in_executor(
            None, functools.partial(server.command, cmd="trace_stop"))
        t_stop = time.monotonic() - t0
        stop.set()
        await load
    finally:
        stop.set()
        await client.close()
    assert answered >= 5
    assert server.get("/v1/HealthCheck")["status"] == "healthy"

    spans = _spans(tdir)
    assert {"put", "fetch", "shard_put", "shard_unroute"} <= set(spans), sorted(spans)
    assert {"wire_pack", "shard_pack"} & set(spans)
    puts = {st["dispatch"]: (a, b) for st, a, b in spans["put"]}
    fetches = {st["dispatch"]: (a, b) for st, a, b in spans["fetch"]}
    inside = {"shard_put": puts, "wire_pack": puts, "shard_pack": puts,
              "shard_unroute": fetches, "wire_decode": fetches}
    seen = 0
    for name, outer in inside.items():
        for st, a, b in spans.get(name, []):
            assert "dispatch" in st and "rows" in st, (name, st)
            if st["dispatch"] in outer:  # its outer span began inside the trace
                lo, hi = outer[st["dispatch"]]
                assert lo <= a and b <= hi, (name, st)
                seen += 1
    assert seen >= 4

    rc, took = server.sigterm(60.0)
    assert rc == 0, f"exit code {rc} after {took:.1f} s (trace_stop {t_stop:.1f} s)"
    assert took < 60.0


def _columns(fps: np.ndarray, now: int) -> RequestColumns:
    n = fps.shape[0]
    return RequestColumns(
        fp=fps, algo=np.zeros(n, dtype=np.int32),
        behavior=np.zeros(n, dtype=np.int32), hits=np.ones(n, dtype=np.int64),
        limit=np.full(n, LIMIT, dtype=np.int64), burst=np.zeros(n, dtype=np.int64),
        duration=np.full(n, DURATION, dtype=np.int64),
        created_at=np.full(n, now, dtype=np.int64), err=np.zeros(n, dtype=np.int8),
    )


@pytest.mark.parametrize("shards", [2, 4])
def test_the_shards_add_up_to_the_whole(shards, frozen_now):
    """The same seeded stream of unique keys into one table and into
    `shards` shards (the TPU's selectors forced) gives identical answers;
    the shards' live keys sum to the live keys; and each key is live on
    `shard_of(fp, D)` and nowhere else."""
    from gubernator_tpu.ops.table2 import decode_live_slots
    from gubernator_tpu.ops.telemetry import finish_scan

    now = frozen_now
    rng = np.random.default_rng(97)
    fps = np.unique(rng.integers(1, (1 << 63) - 1, size=1400, dtype=np.int64))[:1200]
    rng.shuffle(fps)
    whole = LocalEngine(capacity=SLOTS_PER_SHARD * shards)
    mesh = ShardedEngine(
        make_mesh(shards), capacity_per_shard=SLOTS_PER_SHARD,
        route="device", dedup="device", wire="compact",
    )
    # three rounds over overlapping halves: installs, then hits on live keys
    for r in range(3):
        part = fps[(r * 300):(r * 300) + 600]
        a = whole.check_columns(_columns(part, now + r), now_ms=now + r)
        b = mesh.check_columns(_columns(part, now + r), now_ms=now + r)
        for f in ("status", "limit", "remaining", "reset_time", "err"):
            np.testing.assert_array_equal(getattr(a, f), getattr(b, f), err_msg=f)
    assert mesh.a2a_overflow == 0 and mesh.stats.dropped == 0
    # no bucket overflowed, so no live key has left either table
    assert mesh.stats.evicted_unexpired == 0 == whole.stats.evicted_unexpired
    snap = finish_scan(mesh.telemetry_begin(now + 3))
    assert len(snap.per_shard_live) == shards
    assert sum(snap.per_shard_live) == snap.live_keys == whole.live_count(now + 3) == 1200
    rows = mesh.snapshot()  # (D, buckets, 128)
    seen = []
    for d in range(shards):
        _slots, live_fp, _exp = decode_live_slots(rows[d], now + 3)
        assert len(live_fp) == snap.per_shard_live[d]
        assert (shard_of(live_fp, shards) == d).all()
        seen.append(live_fp)
    assert np.array_equal(np.sort(np.concatenate(seen)), np.sort(fps))
