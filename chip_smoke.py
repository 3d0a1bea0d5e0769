#!/usr/bin/env python3
"""chip_smoke.py — start the server on the TPU and check that it answers right.

Drives the served path once, end to end, the way a user would: the normal
binary (`python -m gubernator_tpu`) with every path selector left at its
default and a 1 GiB table per chip, 10M distinct keys loaded through the
gRPC door in 1,000-item `GetRateLimits` RPCs, every answer compared with a
plain oracle (tests/oracle/algos.py, written after upstream's algorithms.go),
and what the server says about itself read back from its HTTP debug plane.
One chip runs the local engine; four or more run the sharded engine over all
of them with a share of GLOBAL keys. No speed is claimed: the walls printed
are observations.

Process rules (one process per chip): THIS process never initialises a JAX
backend. It imports `gubernator_tpu.proto`, which imports `jax` through the
package's `__init__` (tolerable: importing jax touches no device), and it
never calls `jax.devices()` or builds an array — `main()` asserts that at the
end. The chip is touched only by children started with `JAX_PLATFORMS=tpu`,
one at a time: a short probe that reports the devices and exits, then the
server. With no TPU JAX itself raises in the probe and the script exits
non-zero without printing a result.

Stdout is two JSON lines. The last is the verdict, these keys and no others:
  {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}
with the device as JAX reports it (`ok` is false, and the exit code 1, when a
device was found and the run then failed). The line before it is the report:
resolved paths, table bytes, keys loaded, checks compared, mismatches,
evicted-live, versions, walls and the compile cache.
"""

from __future__ import annotations

import argparse
import asyncio
import importlib.metadata
import json
import os
import signal
import socket
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

# imports jax (never a backend) via gubernator_tpu/__init__.py — see above
from gubernator_tpu.proto import gubernator_pb2 as pb  # noqa: E402
from tests.oracle.algos import LeakyOracle, TokenOracle  # noqa: E402

SLOTS_PER_CHIP = 16_777_216  # 1 GiB full-layout table (r05 headline geometry)
KEYS = 10_000_000  # the north star's live-key count (BASELINE.md)
RPC_ITEMS = 1_000  # upstream's batch cap (BASELINE.md)
BULK_LIMIT = 100
BULK_DURATION_MS = 3_600_000  # outlives the run; < 2^27 ms stays compact-encodable
FRESH_DURATION_MS = 2 * BULK_DURATION_MS  # fresh keys evict bulk keys, never each other
GLOBAL_EVERY = 500  # sharded leg: every 500th bulk RPC carries Behavior.GLOBAL
# a replica may run one hit ahead of its owner on a row it had to retry
# (peek_bulk); a share of its answers above this is a wrong count, not a race
REPLICA_AHEAD_MAX = 0.01
INFLIGHT = 64  # bounded client concurrency (RPCs)
# no RPC is retried; a cold compile on the request path (a batch shape or
# math variant GUBER_WARM_SHAPES=pow2 did not cover) may take a minute
RPC_TIMEOUT_S = 600.0
HEALTH_WAIT_S = 900.0
TOTAL_BUDGET_S = 1150.0  # the contract gives 1200 s, compilation included
METHOD = "/pb.gubernator.V1/GetRateLimits"
# what every `auto` path selector resolves to on a tpu backend: a run that
# silently served from another path (or a default that moved without this
# table) fails
TPU_AUTO = {"write_mode": "sparse", "wire": "compact", "probe_kernel": "xla"}
TPU_AUTO_MESH = {"route": "device", "dedup": "device", "a2a_impl": "collective"}

RESET_REMAINING = int(pb.RESET_REMAINING)
DRAIN_OVER_LIMIT = int(pb.DRAIN_OVER_LIMIT)
GLOBAL = int(pb.GLOBAL)
TOKEN, LEAKY = int(pb.TOKEN_BUCKET), int(pb.LEAKY_BUCKET)


class SmokeFailure(Exception):
    """The run is wrong; the message says why."""


class Wrong:
    """Answers that differ from the oracle: all are counted, a few kept."""

    def __init__(self):
        self.n = 0
        self.examples: list = []

    def add(self, what: str) -> None:
        self.n += 1
        if len(self.examples) < 5:
            self.examples.append(what)

    def check(self, where: str, compared: int) -> None:
        if self.n:
            raise SmokeFailure(
                f"{self.n} of {compared} answers differ from the oracle in "
                f"{where}, e.g. " + "; ".join(self.examples)
            )


def now_ms() -> int:
    return time.time_ns() // 1_000_000


# ------------------------------------------------------------ request bytes


def _varint(v: int) -> bytes:
    out = bytearray()
    while True:
        b = v & 0x7F
        v >>= 7
        if v:
            out.append(b | 0x80)
        else:
            out.append(b)
            return bytes(out)


def bulk_key_ids(seed: int, idx: np.ndarray) -> np.ndarray:
    """64-bit ids of the bulk keys with indices `idx`: an odd multiplier is
    a bijection mod 2^64, so distinct indices never collide."""
    return idx.astype(np.uint64) * np.uint64(0x9E3779B97F4A7C15) + np.uint64(
        seed * 0xD1B54A32D192ED03 % 2**64
    )


_HEX = np.frombuffer(b"0123456789abcdef", dtype=np.uint8)
_SHIFTS = np.arange(60, -4, -4, dtype=np.uint64)


def bulk_request_bytes(
    ids: np.ndarray, hits: int, created_at: int, behavior: int = 0
) -> bytes:
    """Serialized GetRateLimitsReq for token-bucket checks on the bulk keys
    `ids` (unique_key = 16 hex digits of the id), built as one fixed-width
    byte matrix instead of n Python messages — byte-identical to what
    protobuf serializes for the same items (tests/test_chip_smoke.py)."""
    head = b"\x0a\x04bulk\x12\x10"  # name="bulk", unique_key (16 bytes) follows
    tail = b""
    if hits:
        tail += b"\x18" + _varint(hits)
    tail += b"\x20" + _varint(BULK_LIMIT) + b"\x28" + _varint(BULK_DURATION_MS)
    if behavior:
        tail += b"\x38" + _varint(behavior)
    tail += b"\x50" + _varint(created_at)
    item_len = len(head) + 16 + len(tail)
    frame = b"\x0a" + _varint(item_len)
    row = np.frombuffer(frame + head + bytes(16) + tail, dtype=np.uint8)
    mat = np.tile(row, (ids.shape[0], 1))
    k0 = len(frame) + len(head)
    mat[:, k0 : k0 + 16] = _HEX[((ids[:, None] >> _SHIFTS) & np.uint64(0xF)).astype(np.intp)]
    return mat.tobytes()


def expected_bulk_response_bytes(n: int, remaining: int, reset_time: int) -> bytes:
    """What n identical UNDER_LIMIT answers serialize to (proto3 skips the
    zero status): the load's fast path compares bytes and only parses a
    response that differs."""
    item = (
        b"\x10" + _varint(BULK_LIMIT) + b"\x18" + _varint(remaining)
        + b"\x20" + _varint(reset_time)
    )
    return (b"\x0a" + _varint(len(item)) + item) * n


# ------------------------------------------------------------------ client


class Door:
    """The two doors a user has: gRPC for checks, HTTP for status."""

    def __init__(self, grpc_addr: str, http_addr: str, channels: int = 4):
        import aiohttp
        import grpc

        self._chans = [
            grpc.aio.insecure_channel(
                grpc_addr,
                options=[
                    ("grpc.use_local_subchannel_pool", 1),
                    ("grpc.max_receive_message_length", 16 << 20),
                ],
            )
            for _ in range(channels)
        ]
        self._calls = [
            c.unary_unary(
                METHOD, request_serializer=None, response_deserializer=None
            )
            for c in self._chans
        ]
        self._n = 0
        self._http = aiohttp.ClientSession()
        self._base = f"http://{http_addr}"

    async def check_raw(self, body: bytes) -> bytes:
        self._n += 1
        return await self._calls[self._n % len(self._calls)](
            body, timeout=RPC_TIMEOUT_S
        )

    async def check(self, reqs) -> list:
        data = await self.check_raw(
            pb.GetRateLimitsReq(requests=reqs).SerializeToString()
        )
        return list(pb.GetRateLimitsResp.FromString(data).responses)

    async def get(self, path: str) -> dict:
        async with self._http.get(self._base + path) as r:
            if r.status != 200:
                raise SmokeFailure(f"GET {path} -> HTTP {r.status}: {await r.text()}")
            return await r.json()

    async def close(self) -> None:
        await self._http.close()
        for c in self._chans:
            await c.close()


# ------------------------------------------------------------- load (bulk)


async def load_bulk(door: Door, seed: int, n_keys: int, global_every: int) -> dict:
    """One hits=1 token-bucket check per bulk key, created_at pinned per
    RPC. Every answer must be UNDER_LIMIT with remaining == limit-1 and
    reset_time == created_at + duration. A GLOBAL RPC is answered by a
    replica, which the system promises only eventual consistency (bounded by
    the sync cadence): its reset_time may be the owner's, and `remaining` may
    run one hit ahead (see `peek_bulk`); the owner is held to the exact
    answer there."""
    n_rpcs = -(-n_keys // RPC_ITEMS)
    created = np.zeros(n_rpcs, dtype=np.int64)
    bad = Wrong()
    fast = ahead = from_replicas = 0
    sem = asyncio.Semaphore(INFLIGHT)

    async def one(r: int) -> None:
        nonlocal fast, ahead, from_replicas
        async with sem:
            lo = r * RPC_ITEMS
            n = min(RPC_ITEMS, n_keys - lo)
            is_global = bool(global_every) and r % global_every == 0
            created[r] = t = now_ms()
            body = bulk_request_bytes(
                bulk_key_ids(seed, np.arange(lo, lo + n)), 1, t,
                GLOBAL if is_global else 0,
            )
            data = await door.check_raw(body)
            reset = t + BULK_DURATION_MS
            if not is_global and data == expected_bulk_response_bytes(
                n, BULK_LIMIT - 1, reset
            ):
                fast += 1
                return
            resps = pb.GetRateLimitsResp.FromString(data).responses
            if len(resps) != n:
                raise SmokeFailure(f"{len(resps)} responses for {n} requests")
            from_replicas += n * is_global
            for j, x in enumerate(resps):
                ok = not x.error and x.status == pb.UNDER_LIMIT and x.limit == BULK_LIMIT
                if is_global and ok and x.remaining == BULK_LIMIT - 2:
                    ahead += 1
                elif not (
                    ok and x.remaining == BULK_LIMIT - 1
                    and (is_global or x.reset_time == reset)
                ):
                    bad.add(f"key {lo + j}: {_brief(x)}")

    t0 = time.monotonic()
    await _gather_all(one(r) for r in range(n_rpcs))
    wall = time.monotonic() - t0
    print(f"chip_smoke: loaded {n_keys} keys in {wall:.1f} s", file=sys.stderr)
    bad.check("the bulk load", n_keys)
    return {
        "keys": n_keys, "rpcs": n_rpcs, "created": created,
        "byte_identical_rpcs": fast, "replica_answers": from_replicas,
        "replica_ahead": ahead, "wall_s": wall,
    }


async def _gather_all(coros) -> None:
    """Await every task; the first failure cancels the rest and raises."""
    tasks = [asyncio.ensure_future(c) for c in coros]
    try:
        await asyncio.gather(*tasks)
    finally:
        for t in tasks:
            t.cancel()


def _brief(x) -> str:
    return (
        f"status={x.status} limit={x.limit} remaining={x.remaining} "
        f"reset_time={x.reset_time} error={x.error!r}"
    )


# ---------------------------------------------------- fresh keys vs oracle


def fresh_scenarios(seed: int, n_per: int, t0: int, dup_aggregates: bool):
    """Scripts over fresh keys: a list of steps, each a list of
    (RateLimitReq, expected (status, remaining, reset_time)) that goes out
    as ONE RPC, steps strictly one after another. Expectations come from
    the plain oracles. `dup_aggregates`: what the same key several times in
    one batch means on the engine under test — the local engine (and
    dedup=host) decides them one after another; the mesh's in-trace dedup
    aggregates them into one decision whose answer every copy gets
    (upstream's own GLOBAL hot-key rule, docs/architecture.md)."""
    tok, leak = TokenOracle(), LeakyOracle()
    D = FRESH_DURATION_MS

    def req(name, k, hits, limit, algo=TOKEN, behavior=0, at=t0, duration=D):
        return pb.RateLimitReq(
            name=name, unique_key=f"s{seed}-{k}", hits=hits, limit=limit,
            duration=duration, algorithm=algo, behavior=behavior, created_at=at,
        )

    def tstep(name, script):
        """script: [(hits, limit, behavior)] applied to n_per token keys."""
        steps = []
        for s, (hits, limit, beh) in enumerate(script):
            step = []
            for k in range(n_per):
                exp = tok.check(
                    (name, k), t0 + s, hits, limit, D,
                    reset=bool(beh & RESET_REMAINING),
                    drain=bool(beh & DRAIN_OVER_LIMIT),
                )
                step.append((req(name, k, hits, limit, behavior=beh, at=t0 + s), exp))
            steps.append(step)
        return steps

    groups = [
        # down to OVER_LIMIT and past it; the at-limit status is sticky
        tstep("drain", [(2, 5, 0), (2, 5, 0), (2, 5, 0), (1, 5, 0), (1, 5, 0), (0, 5, 0)]),
        tstep("reset", [(3, 5, 0), (1, 5, RESET_REMAINING), (1, 5, 0)]),
        tstep("drainover", [(3, 5, 0), (4, 5, DRAIN_OVER_LIMIT), (0, 5, 0), (1, 5, 0)]),
        tstep("peek", [(0, 7, 0), (1, 7, 0), (0, 7, 0)]),
        # hits >= 2^18 cannot ride the compact wire: the full-width
        # fallback of ops/wire.py decides these
        tstep("wide", [(1 << 18, 1 << 20, 0), (1 << 18, 1 << 20, 0), (0, 1 << 20, 0)]),
    ]
    # leaky bucket across a pinned created_at step: rate = 60000/10 = 6000
    # ms per token, so +15000 ms leaks 2.5 tokens (never a borderline value)
    lk = []
    for s, (hits, dt, beh) in enumerate(
        [(4, 0, 0), (7, 0, 0), (3, 15_000, 0), (0, 15_000, 0),
         (9, 15_000, DRAIN_OVER_LIMIT), (1, 27_000, 0)]
    ):
        step = []
        for k in range(n_per):
            exp = leak.check(
                ("leak", k), t0 + dt, hits, 10, 60_000,
                drain=bool(beh & DRAIN_OVER_LIMIT),
            )
            step.append((
                req("leak", k, hits, 10, algo=LEAKY, behavior=beh, at=t0 + dt,
                    duration=60_000), exp,
            ))
        lk.append(step)
    groups.append(lk)
    # the same key three times in one RPC
    step = []
    for k in range(n_per):
        if dup_aggregates:
            exp3 = [tok.check(("dup", k), t0, 6, 10, D)] * 3
        else:
            exp3 = [tok.check(("dup", k), t0, 2, 10, D) for _ in range(3)]
        step += [(req("dup", k, 2, 10), e) for e in exp3]
    groups.append([step])
    return groups


async def run_fresh(door: Door, groups) -> int:
    """Returns the number of checks compared (all equal, or it raised)."""
    compared, bad = 0, Wrong()
    for steps in groups:
        for step in steps:
            for lo in range(0, len(step), RPC_ITEMS):
                part = step[lo : lo + RPC_ITEMS]
                resps = await door.check([r for r, _ in part])
                if len(resps) != len(part):
                    raise SmokeFailure(
                        f"{len(resps)} responses for {len(part)} requests"
                    )
                for (r, exp), x in zip(part, resps):
                    compared += 1
                    got = (int(x.status), int(x.remaining), int(x.reset_time))
                    if x.error or x.limit != r.limit or got != exp:
                        bad.add(
                            f"{r.name}/{r.unique_key} hits={r.hits} "
                            f"behavior={r.behavior}: {_brief(x)} expected {exp}"
                        )
    bad.check("the fresh-key scripts", compared)
    return compared


# ------------------------------------------------------ peek at bulk keys


async def peek_bulk(
    door: Door, seed: int, load: dict, n_sample: int, global_every: int
) -> dict:
    """hits=0 on a seeded sample of bulk keys. A key answers what the
    oracle says (one hit taken at its RPC's created_at) or, if the table
    evicted it while live, as a fresh key; anything else is a mismatch.

    A key loaded through a GLOBAL RPC is peeked twice. Without the GLOBAL
    flag the check goes to the owner's authoritative table, which must hold
    exactly the one hit (the caller waits for the sync to drain first); its
    reset_time is bounded, not exact, because the owner stamps a synced hit
    at its sync tick. With the flag a replica answers, eventually consistent:
    the one hit, or one more — a row that lost the replica's claim auction is
    retried after the batch's hits were queued, and when a sync tick lands in
    between, the replica applies the hit on top of the owner's broadcast that
    already counts it (until the key's next broadcast)."""
    rng = np.random.default_rng(seed)
    n_keys = load["keys"]
    idx = np.sort(rng.choice(n_keys, size=min(n_sample, n_keys), replace=False))
    t = now_ms()
    t_load0 = int(load["created"].min())
    out = {"compared": 0, "evicted": 0, "global": 0, "replica_ahead": 0}
    bad = Wrong()

    async def one(part: np.ndarray, loaded_global: bool, ask_replica: bool) -> None:
        body = bulk_request_bytes(
            bulk_key_ids(seed, part), 0, t, GLOBAL if ask_replica else 0
        )
        resps = pb.GetRateLimitsResp.FromString(await door.check_raw(body)).responses
        if len(resps) != len(part):
            raise SmokeFailure(f"{len(resps)} responses for {len(part)} requests")
        for i, x in zip(part, resps):
            out["compared"] += 1
            c = int(load["created"][i // RPC_ITEMS])
            ok = not x.error and x.status == pb.UNDER_LIMIT and x.limit == BULK_LIMIT
            if loaded_global:
                out["global"] += not ask_replica
                if ok and t_load0 <= x.reset_time - BULK_DURATION_MS <= t:
                    if x.remaining == BULK_LIMIT - 1:
                        continue
                    if ask_replica and x.remaining == BULK_LIMIT - 2:
                        out["replica_ahead"] += 1
                        continue
            elif ok and (x.remaining, x.reset_time) == (
                BULK_LIMIT - 1, c + BULK_DURATION_MS
            ):
                continue
            if ok and (x.remaining, x.reset_time) == (
                BULK_LIMIT, t + BULK_DURATION_MS
            ):
                out["evicted"] += 1
                continue
            where = "replica" if ask_replica else "owner"
            bad.add(f"bulk key {int(i)} ({where}): {_brief(x)}")

    gmask = (
        (idx // RPC_ITEMS) % global_every == 0 if global_every
        else np.zeros(idx.shape, dtype=bool)
    )
    jobs = []
    for sel, kind in (
        (idx[~gmask], (False, False)), (idx[gmask], (True, False)),
        (idx[gmask], (True, True)),
    ):
        for lo in range(0, len(sel), RPC_ITEMS):
            jobs.append(one(sel[lo : lo + RPC_ITEMS], *kind))
    await _gather_all(jobs)
    bad.check("the bulk-key peek", out["compared"])
    return out


# ------------------------------------------------- the run against a server


async def drive(
    grpc_addr: str, http_addr: str, *, seed: int, n_keys: int, n_fresh: int,
    n_sample: int, sharded: bool,
) -> dict:
    """Load → oracle compare → what the server says about itself. Raises
    SmokeFailure on the first wrong thing; returns the record otherwise.
    (tests/test_chip_smoke.py runs exactly this against a CPU daemon.)"""
    door = Door(grpc_addr, http_addr)
    try:
        pipe = await door.get("/v1/debug/pipeline")
        eng = pipe["engine"]
        if pipe.get("native_parser") not in ("built", "reused"):
            raise SmokeFailure(
                "the native request parser is not live (native_parser="
                f"{pipe.get('native_parser')!r}): the door fell back to Python"
            )
        global_every = GLOBAL_EVERY if sharded else 0

        load = await load_bulk(door, seed, n_keys, global_every)
        fresh_compared = await run_fresh(
            door,
            fresh_scenarios(
                seed, n_fresh, now_ms(), dup_aggregates=eng.get("dedup") == "device"
            ),
        )
        # the owners must have every GLOBAL hit before they are asked
        global_sync = await global_drained(door) if sharded else None
        peek = await peek_bulk(door, seed, load, n_sample, global_every)

        # the table scan behind /v1/debug/table runs on a cadence: wait for
        # one taken after the last write
        t_done = now_ms()
        deadline = time.monotonic() + 60
        while True:
            table = await door.get("/v1/debug/table")
            if table["now_ms"] >= t_done:
                break
            if time.monotonic() > deadline:
                raise SmokeFailure("no table telemetry scan after the load")
            await asyncio.sleep(0.5)
        evicted_live = int(table["evicted_live_total"])
        if peek["evicted"] > evicted_live:
            raise SmokeFailure(
                f"{peek['evicted']} sampled bulk keys answered as fresh but the "
                f"server counts only {evicted_live} live evictions"
            )

        pipe = await door.get("/v1/debug/pipeline")
        eng = pipe["engine"]
        health = await door.get("/v1/HealthCheck")
        if health.get("status") != "healthy":
            raise SmokeFailure(f"HealthCheck at the end: {health}")
        if eng.get("poisoned"):
            raise SmokeFailure(f"engine poisoned: {eng['poisoned']}")
        if eng.get("dropped"):
            raise SmokeFailure(f"{eng['dropped']} decisions were never persisted")
        rec = {
            "engine": eng,
            "native_parser": pipe["native_parser"],
            "keys_loaded": load["keys"],
            "load_rpcs": load["rpcs"],
            "load_byte_identical_rpcs": load["byte_identical_rpcs"],
            "load_wall_s": round(load["wall_s"], 3),
            "fresh_checks_compared": fresh_compared,
            "bulk_peeks_compared": peek["compared"],
            "bulk_peeks_evicted": peek["evicted"],
            "mismatches": 0,
            "evicted_live_total": evicted_live,
            "live_keys": int(table["live_keys"]),
        }
        if sharded:
            check_mesh(eng, table, peek)
            # as many replica peeks as owner peeks: the same keys, asked twice
            answers = load["replica_answers"] + peek["global"]
            ahead = load["replica_ahead"] + peek["replica_ahead"]
            if ahead > REPLICA_AHEAD_MAX * answers:
                raise SmokeFailure(
                    f"{ahead} of {answers} replica answers count one hit twice: "
                    "more than a retry now and then explains"
                )
            rec.update({
                "per_shard_live": table["per_shard_live"],
                "global_sync": global_sync,
                "global_peeks_compared": peek["global"],
                "replica_answers": answers,
                "replica_answers_ahead": ahead,
            })
        return rec
    finally:
        await door.close()


async def global_drained(door: Door) -> dict:
    """Wait until the collective GLOBAL sync has run and holds no pending
    hit; returns what /v1/debug/global says of the mesh plane."""
    deadline = time.monotonic() + 60
    while True:
        g = (await door.get("/v1/debug/global")).get("mesh") or {}
        if g.get("sync_rounds", 0) > 0 and g.get("pending", 1) == 0:
            return g
        if time.monotonic() > deadline:
            raise SmokeFailure(f"GLOBAL sync did not drain: {g}")
        await asyncio.sleep(0.5)


def check_mesh(eng: dict, table: dict, peek: dict) -> None:
    """The four-chip leg's own assertions: the mesh really is the device
    count, every chip holds its share, the exchange lost nothing, and GLOBAL
    keys were compared."""
    n = eng["device_count"]
    if eng["n_shards"] != n:
        raise SmokeFailure(f"n_shards={eng['n_shards']} on {n} devices")
    if eng.get("a2a_overflow"):
        raise SmokeFailure(f"a2a_overflow={eng['a2a_overflow']}")
    per = table.get("per_shard_live")
    if not per or len(per) != n:
        raise SmokeFailure(f"per_shard_live={per!r} on {n} devices")
    share = sum(per) / n
    if min(per) < 0.9 * share or max(per) > 1.1 * share:
        raise SmokeFailure(f"live keys are not spread over the chips: {per}")
    if not peek["global"]:
        raise SmokeFailure("the bulk-key sample held no GLOBAL key")


# --------------------------------------------------------------- children


def probe_device(platform: str) -> dict:
    """Ask a short-lived child what JAX sees. It exits before the server
    starts, so the chip is free again; with no device of that platform JAX
    raises in the child and its message is ours."""
    code = (
        "import json, jax, jaxlib; d = jax.devices(); "
        "print(json.dumps({'platform': d[0].platform, 'kind': d[0].device_kind, "
        "'count': len(d), 'jax': jax.__version__, 'jaxlib': jaxlib.__version__}))"
    )
    p = subprocess.run(
        [sys.executable, "-c", code], env={**os.environ, "JAX_PLATFORMS": platform},
        capture_output=True, text=True, timeout=300,
    )
    if p.returncode != 0:
        raise SmokeFailure(
            f"JAX found no {platform} device: " + _tail(p.stderr, 1500)
        )
    return json.loads(p.stdout.strip().splitlines()[-1])


def _tail(text: str, n: int) -> str:
    return text.strip()[-n:]


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _cache_state() -> dict:
    """Where the children's compile cache lives and whether it had entries
    before this run (the parent only lists the directory)."""
    d = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(ROOT, ".jax_cache")
    try:
        n = len(os.listdir(d))
    except OSError:
        n = 0
    return {"dir": d, "entries_before": n, "warm": n > 0}


class Server:
    """The normal binary as a child that holds the chip."""

    def __init__(self, platform: str, engine: str, cache_size: int, log_path: str):
        self.grpc = f"127.0.0.1:{_free_port()}"
        self.http = f"127.0.0.1:{_free_port()}"
        env = {
            **os.environ,
            "JAX_PLATFORMS": platform,
            "PYTHONPATH": ROOT + os.pathsep + os.environ.get("PYTHONPATH", ""),
            "GUBER_GRPC_ADDRESS": self.grpc,
            "GUBER_HTTP_ADDRESS": self.http,
            "GUBER_ENGINE": engine,
            "GUBER_CACHE_SIZE": str(cache_size),
            # every pow2 token batch shape compiles before the door opens;
            # pow2-mixed would add ~50 s of leaky-graph compile per shape
            "GUBER_WARM_SHAPES": "pow2",
        }
        self.log_path = log_path
        self._log = open(log_path, "wb")
        self.t0 = time.monotonic()
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "gubernator_tpu"], env=env, cwd=ROOT,
            stdout=self._log, stderr=subprocess.STDOUT,
        )

    def log_tail(self, n: int = 3000) -> str:
        with open(self.log_path, "r", errors="replace") as f:
            return _tail(f.read(), n)

    async def wait_healthy(self) -> float:
        import aiohttp

        async with aiohttp.ClientSession() as s:
            while True:
                if self.proc.poll() is not None:
                    raise SmokeFailure(
                        f"the server exited with code {self.proc.returncode} "
                        "before it was healthy:\n" + self.log_tail()
                    )
                if time.monotonic() - self.t0 > HEALTH_WAIT_S:
                    raise SmokeFailure(
                        f"the server was not healthy after {HEALTH_WAIT_S:.0f} s:\n"
                        + self.log_tail()
                    )
                try:
                    async with s.get(f"http://{self.http}/v1/HealthCheck") as r:
                        if r.status == 200 and (await r.json()).get("status") == "healthy":
                            return time.monotonic() - self.t0
                except aiohttp.ClientError:
                    pass
                await asyncio.sleep(0.5)

    def stop(self) -> int:
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=60)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        self._log.close()
        return self.proc.returncode


async def smoke(dev: dict, seed: int, n_keys: int, slots_per_chip: int) -> dict:
    """Start the server for what the probe found, drive it, stop it; returns
    the report."""
    t_start = time.monotonic()
    platform = dev["platform"]
    sharded = dev["count"] >= 4
    cache = _cache_state()
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    server = Server(
        platform, "sharded" if sharded else "local",
        slots_per_chip * (dev["count"] if sharded else 1),
        os.path.join(ROOT, "chiprun_out", "chip_smoke_server.log"),
    )
    try:
        startup_s = await server.wait_healthy()
        rec = await drive(
            server.grpc, server.http, seed=seed, n_keys=n_keys, n_fresh=200,
            n_sample=20_000, sharded=sharded,
        )
        eng = rec["engine"]
        want = {"platform": dev["platform"], "device_kind": dev["kind"],
                "device_count": dev["count"]}
        if platform == "tpu":
            want.update(TPU_AUTO)
            if sharded:
                want.update(TPU_AUTO_MESH)
        for k, v in want.items():
            if eng[k] != v:
                raise SmokeFailure(f"the server reports {k}={eng[k]!r}, expected {v!r}")
        want_bytes = slots_per_chip * 64 * (dev["count"] if sharded else 1)
        if eng["table_bytes"] != want_bytes:
            raise SmokeFailure(
                f"table_bytes={eng['table_bytes']}, expected {want_bytes}"
            )
    except BaseException:
        sys.stderr.write("---- server log tail ----\n" + server.log_tail() + "\n")
        raise
    finally:
        rc = server.stop()
    if rc != 0:
        raise SmokeFailure(f"the server exited with code {rc} on SIGTERM")
    rec.update({
        "leg": "sharded" if sharded else "local",
        "jax": dev["jax"], "jaxlib": dev["jaxlib"],
        "libtpu": _version("libtpu"),
        "seed": seed,
        "startup_wall_s": round(startup_s, 3),
        "total_wall_s": round(time.monotonic() - t_start, 3),
        "compile_cache": cache,
    })
    if n_keys < KEYS:
        rec["reduced"] = {"keys": {"from": KEYS, "to": n_keys}}
    return rec


def _version(dist: str):
    try:
        return importlib.metadata.version(dist)
    except importlib.metadata.PackageNotFoundError:
        return None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--keys", type=int, default=KEYS,
                    help="distinct bulk keys to load (a cut is printed under 'reduced')")
    args = ap.parse_args(argv)
    try:
        dev = probe_device("tpu")
        if dev["platform"] != "tpu":
            raise SmokeFailure(f"asked JAX for tpu, it reports {dev}")
    except SmokeFailure as exc:
        # no accelerator: no result line at all
        print(f"chip_smoke: FAILED: {exc}", file=sys.stderr)
        return 1
    why = None
    try:
        report = asyncio.run(asyncio.wait_for(
            smoke(dev, args.seed, args.keys, SLOTS_PER_CHIP), TOTAL_BUDGET_S
        ))
    except asyncio.TimeoutError:
        why = f"not done after {TOTAL_BUDGET_S:.0f} s"
    except SmokeFailure as exc:
        why = str(exc)
    # the parent must have stayed off the device (module docstring)
    import jax._src.xla_bridge as xb

    if why is None and xb._backends:
        why = f"the parent initialised JAX backends {list(xb._backends)}"
    if why is None:
        print(json.dumps(report))
    else:
        print(f"chip_smoke: FAILED: {why}", file=sys.stderr)
    print(verdict_line(why is None, dev), flush=True)
    return 0 if why is None else 1


def verdict_line(ok: bool, dev: dict) -> str:
    """The last line of stdout: exactly `ok` and `device`, and `device`
    exactly platform, kind and count as the probe child's JAX reported them."""
    return json.dumps({
        "ok": bool(ok),
        "device": {"platform": str(dev["platform"]), "kind": str(dev["kind"]),
                   "count": int(dev["count"])},
    })


if __name__ == "__main__":
    sys.exit(main())
