"""One general load generator, driven by a traffic file.

A traffic mix is a JSON file of parameters under bench/traffic/:

  loop            "closed" (a fixed number of RPCs in flight, each worker
                  sends its next when the last is answered) or "open"
                  (arrivals on a schedule, whatever the server does)
  inflight        closed loop: RPCs in flight
  rate_rpc_per_s  open loop: the fixed offered rate, Poisson arrivals
  max_outstanding open loop: RPCs beyond this many unanswered are refused by
                  the generator and count as failed
  items_per_rpc   {"fixed": n} or {"mix": [[share, lo, hi], ...]} (uniform
                  inside each range)
  keys            {"dist": "uniform"} or {"dist": "zipf", "theta": t} over
                  the configuration's live keys (rank r is key index r; the
                  key's id hashes the index with the seed)
  behavior        absent: every RPC carries the configuration's `keyspace.
                  behavior`. {"mix": [[share, [names]], ...]}: every RPC
                  carries one entry's behavior in all its rows (upstream's
                  names, gubernator.proto; [] is none), drawn with these
                  shares. The check has a rule for "GLOBAL" alone and refuses
                  any other name (bench/checker.py). The ledger keeps which
                  behavior each RPC carried
  warm_seconds    the same traffic sent before the window opens (set-up)
  channels        gRPC channels of the client
  rpc_timeout_s   after which an RPC has failed

An open loop's gaps, sizes and key ranks are drawn once, from SHAPE_SEED, and
only re-ordered by --seed: every seed offers the same multiset of work, so
that runs differ by the order of the work and not by its amount. A behavior
mix is drawn the same way, after them (a file without one draws what it
always drew): one entry per RPC of an open loop, and for a closed loop,
whose number of RPCs is the server's to decide, a cycle of BEHAVIOR_CYCLE
entries that its RPCs take in the order they start.

The traffic runs without a break through warm-up and window; what falls in
which is decided afterwards from the clock. Everything sent is kept in the
`Ledger`, which is what the checks and the metrics read.
"""

from __future__ import annotations

import asyncio
import gc
import time

import numpy as np

import wirefmt

SHAPE_SEED = 20260927
BEHAVIOR_CYCLE = 1000


class Ledger:
    """Every RPC of one stretch of traffic: the key indices it carried, when
    it was due, sent and answered (seconds from the stretch's start), and
    the answer's bytes (None: failed or refused)."""

    def __init__(self, warm_s: float, seconds: float):
        self.warm_s, self.seconds = warm_s, seconds
        self.idx: list = []
        self.behavior: list = []  # the wire number every row of the RPC carried
        self.due: list = []
        self.sent: list = []
        self.done: list = []
        self.resp: list = []
        self.errors: list = []  # a few messages, for the context line
        self.cpu_cores = None  # the generator process's CPU while it offered this

    def note(self, message: str) -> None:
        if len(self.errors) < 5:
            self.errors.append(message)

    @property
    def t1(self) -> float:
        return self.warm_s + self.seconds

    def arrays(self):
        n = len(self.idx)
        return (
            np.asarray(self.due, dtype=np.float64),
            np.asarray(self.sent, dtype=np.float64),
            np.asarray(self.done, dtype=np.float64),
            np.fromiter((r is not None for r in self.resp), dtype=bool, count=n),
            np.fromiter((len(i) for i in self.idx), dtype=np.int64, count=n),
        )


def behavior_mix(spec: dict):
    """A traffic file's `behavior` as (shares, wire numbers), one entry of
    each per entry of its mix; None where the file has no such key."""
    mix = spec.get("behavior")
    if mix is None:
        return None
    return (np.asarray([m[0] for m in mix["mix"]], dtype=np.float64),
            [wirefmt.behavior_bits(m[1]) for m in mix["mix"]])


def size_sampler(spec: dict):
    if "fixed" in spec:
        n = int(spec["fixed"])
        return lambda rng, count: np.full(count, n, dtype=np.int64)
    mix = spec["mix"]
    shares = np.asarray([m[0] for m in mix], dtype=np.float64)
    shares /= shares.sum()

    def draw(rng, count):
        which = rng.choice(len(mix), size=count, p=shares)
        lo = np.asarray([m[1] for m in mix])[which]
        hi = np.asarray([m[2] for m in mix])[which]
        return rng.integers(lo, hi + 1)

    return draw


def key_sampler(spec: dict, n_keys: int):
    if spec["dist"] == "uniform":
        return lambda rng, count: rng.integers(0, n_keys, size=count)
    if spec["dist"] == "zipf":
        # exact inverse CDF of P(rank r) ~ 1/(r+1)^theta over n_keys ranks
        w = np.arange(1, n_keys + 1, dtype=np.float64) ** -float(spec["theta"])
        cdf = np.cumsum(w)
        cdf /= cdf[-1]
        return lambda rng, count: np.minimum(
            np.searchsorted(cdf, rng.random(count)), n_keys - 1
        )
    raise ValueError(f"unknown key distribution {spec['dist']!r}")


class Traffic:
    """The generator for one cell: `prepare()` does the host work that needs
    no server, `run(door)` offers the load and returns the ledger."""

    def __init__(self, spec: dict, keyspec: dict, seed: int, seconds: float,
                 key_seed: int | None = None):
        # `seed` orders the work; `key_seed` names the keys, and is the seed
        # the table was filled with (a second stretch takes another order
        # over the same keys)
        self.spec, self.seed, self.seconds = spec, seed, float(seconds)
        self.key_seed = seed if key_seed is None else key_seed
        self.n_keys = int(keyspec["keys"])
        self.limit = int(keyspec["limit"])
        self.duration = int(keyspec["duration_ms"])
        self.hits = int(keyspec["hits"])
        self.algorithm = wirefmt.keyspec_algorithm(keyspec)
        self.behavior = wirefmt.keyspec_behavior(keyspec)
        self._mix = behavior_mix(spec)
        self._cycle = None  # a closed loop's behaviors, where the file has a mix
        self.warm_s = float(spec.get("warm_seconds", 0))
        self.timeout_s = float(spec.get("rpc_timeout_s", 60))
        self._sizes = size_sampler(spec["items_per_rpc"])
        self._plan = None
        self.closed = spec["loop"] == "closed"
        if not self.closed and spec["loop"] != "open":
            raise ValueError(f"unknown loop kind {spec['loop']!r}")

    def _body(self, idx: np.ndarray, behavior: int | None = None) -> bytes:
        return wirefmt.request_bytes(
            wirefmt.key_ids(self.key_seed, idx), self.hits, self.limit, self.duration,
            algorithm=self.algorithm,
            behavior=self.behavior if behavior is None else behavior,
        )

    def _behaviors(self, shape, n: int) -> np.ndarray:
        """The behavior of each of `n` RPCs: the mix drawn from `shape` (the
        SHAPE_SEED generator, after everything else it gives) and re-ordered
        by the seed, through a generator of its own so that the seed's other
        draws are what they are without a mix."""
        shares, bits = self._mix
        drawn = np.asarray(bits)[shape.choice(len(bits), size=n, p=shares / shares.sum())]
        return np.random.default_rng((self.seed, BEHAVIOR_CYCLE)).permutation(drawn)

    def prepare(self) -> None:
        self._keys = key_sampler(self.spec["keys"], self.n_keys)
        if self.closed:
            if self._mix is not None:
                self._cycle = self._behaviors(
                    np.random.default_rng(SHAPE_SEED), BEHAVIOR_CYCLE).tolist()
            return
        total = self.warm_s + self.seconds
        n = int(round(float(self.spec["rate_rpc_per_s"]) * total))
        shape = np.random.default_rng(SHAPE_SEED)
        gaps = shape.exponential(size=n)
        sizes = self._sizes(shape, n)
        ranks = self._keys(shape, int(sizes.sum()))
        order = np.random.default_rng(self.seed)
        gaps, sizes, ranks = (order.permutation(a) for a in (gaps, sizes, ranks))
        due = np.cumsum(gaps)
        due *= total / due[-1]
        offsets = np.concatenate([[0], np.cumsum(sizes)])
        behaviors = (np.full(n, self.behavior) if self._mix is None
                     else self._behaviors(shape, n))
        # every body in one pass: items are fixed-width, so an RPC's bytes
        # are a slice of one long buffer (one buffer a behavior of the mix:
        # the flag is two bytes of every row)
        of_item, step = np.repeat(behaviors, sizes), 200_000
        blobs, first = {}, np.zeros(n, dtype=np.int64)  # an RPC's first row in its blob
        for b in np.unique(behaviors).tolist():
            mine = ranks[of_item == b]
            blob = b"".join(self._body(mine[lo : lo + step], b)
                            for lo in range(0, len(mine), step))
            blobs[b] = (blob, len(blob) // max(len(mine), 1))
            rpcs = behaviors == b
            first[rpcs] = np.cumsum(sizes[rpcs]) - sizes[rpcs]
        self._plan = (due, offsets, ranks, blobs, behaviors.tolist(), first)

    def _planned_body(self, i: int) -> bytes:
        """The bytes of the open loop's i-th RPC."""
        _due, offsets, _ranks, blobs, behaviors, first = self._plan
        blob, width = blobs[behaviors[i]]
        lo = first[i] * width
        return blob[lo : lo + (offsets[i + 1] - offsets[i]) * width]

    async def run(self, door) -> Ledger:
        led = Ledger(self.warm_s, self.seconds)
        # The generator's own garbage collector is held off while it offers
        # load: a full collection stalled it for 110-120 ms once a run, which
        # at 360 RPC/s put some 200 RPCs late or behind a burst, more than lie
        # beyond the 99th percentile (PERF.md, section 6). The server child
        # is another process and keeps its collector.
        gc.collect()
        gc.disable()
        try:
            t0 = time.perf_counter()
            led.t0_monotonic = time.monotonic()
            cpu0 = time.process_time()
            if self.closed:
                await self._run_closed(door, led, t0)
            else:
                await self._run_open(door, led, t0)
            # every thread of this process (the loop, gRPC's pollers), in cores:
            # how far the generator is from being what a run measures
            led.cpu_cores = (time.process_time() - cpu0) / (time.perf_counter() - t0)
        finally:
            gc.enable()
        return led

    # -------------------------------------------------------------- closed

    async def _run_closed(self, door, led: Ledger, t0: float) -> None:
        import grpc

        rng = np.random.default_rng(self.seed)
        t_end = led.t1
        cycle = self._cycle

        async def worker() -> None:
            while True:
                now = time.perf_counter() - t0
                if now >= t_end:
                    return
                idx = self._keys(rng, int(self._sizes(rng, 1)[0]))
                i = len(led.idx)
                behavior = cycle[i % len(cycle)] if cycle else self.behavior
                body = self._body(idx, behavior)
                led.idx.append(idx)
                led.behavior.append(behavior)
                led.due.append(now)
                led.sent.append(time.perf_counter() - t0)
                led.done.append(np.nan)
                led.resp.append(None)
                try:
                    data = await door.start(body)
                except grpc.aio.AioRpcError as exc:
                    led.done[i] = time.perf_counter() - t0
                    led.note(f"{exc.code()}: {exc.details()}")
                    continue
                led.done[i] = time.perf_counter() - t0
                led.resp[i] = data

        door.timeout_s = self.timeout_s
        await asyncio.gather(*(worker() for _ in range(int(self.spec["inflight"]))))

    # ---------------------------------------------------------------- open

    async def _run_open(self, door, led: Ledger, t0: float) -> None:
        import grpc

        due, offsets, ranks, _blobs, behaviors, _first = self._plan
        n = len(due)
        cap = int(self.spec.get("max_outstanding", 1 << 30))
        sent = np.full(n, np.nan)
        done = np.full(n, np.nan)
        calls: list = [None] * n
        state = {"out": 0}

        def finished(i, _call) -> None:
            done[i] = time.perf_counter() - t0
            state["out"] -= 1

        door.timeout_s = self.timeout_s
        clock = time.perf_counter
        i = 0
        while i < n:
            now = clock() - t0
            wait = due[i] - now
            if wait > 0:
                # the loop's timer rounds to a millisecond: spin the last one
                await asyncio.sleep(wait - 0.001 if wait > 0.002 else 0)
                continue
            stop = min(n, i + 64)
            while i < stop and due[i] <= now:
                if state["out"] < cap:
                    call = door.start(self._planned_body(i))
                    call.add_done_callback(
                        lambda c, i=i: finished(i, c)
                    )
                    calls[i] = call
                    state["out"] += 1
                    sent[i] = clock() - t0
                i += 1
            await asyncio.sleep(0)
        # every RPC answers or times out; then read each call's outcome
        deadline = clock() + self.timeout_s + 5
        while state["out"] > 0 and clock() < deadline:
            await asyncio.sleep(0.01)
        for j in range(n):
            led.idx.append(ranks[offsets[j] : offsets[j + 1]])
            call, data = calls[j], None
            if call is None:
                led.note("refused by the generator: too many unanswered")
            else:
                try:
                    data = await call
                except grpc.aio.AioRpcError as exc:
                    led.note(f"{exc.code()}: {exc.details()}")
            led.resp.append(data)
        led.behavior = behaviors
        led.due, led.sent, led.done = due.tolist(), sent.tolist(), done.tolist()
