"""Device-resident request ring suite (service/ring.py) + the warm_up
zero-compiles gate.

The acceptance surface of the always-on-chip tentpole's serving half:

* the ring protocol is correct on its own terms — slot claim/publish
  ordering (stage before the ingress fence), sequence-number fencing
  (`seq_in`/`seq_out` carry ticket+1, launches walk tickets strictly in
  order), bounded backpressure (never more than S outstanding, no drops,
  no reordering), zero-loss drain, RingClosed to racing submitters;
* the daemon integration is byte-identical to the direct dispatch path
  (same runner surface by construction) and feeds the
  `dispatch_launches_total{path="ring"}` / `ring_occupancy` telemetry;
* `Daemon.warm_up` leaves ZERO compiles for the warmed shapes, verified
  through jax.monitoring compile events, so no production
  dispatch of a warmed shape ever pays a trace on the request path.
"""

import asyncio
import os
import time

import numpy as np
import pytest

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

from gubernator_tpu.service.ring import RequestRing, RingClosed

# one fresh XLA compile fires exactly one of these events; cached
# executions fire none
COMPILE_EVENT = "/jax/compilation_cache/compile_requests_use_cache"


class StubRunner:
    """The minimal runner surface the ring drives: check_wire (which stages
    a chunk that cannot fuse as columns itself, and tells `done` so).
    Echoes the submitted payload so reordering is detectable, and tracks concurrent in-flight dispatches so the occupancy bound is
    assertable."""

    def __init__(self, delay=0.0, fail_on=None, fuse=True):
        self.delay = delay
        self.fail_on = fail_on  # payload value that raises
        self.fuse = fuse  # False => the chunk rides the columns path
        self.launch_order = []
        self.active = 0
        self.max_active = 0
        self.check_calls = 0

    async def check_wire(self, parts, now_ms=None, disp=None,
                         launch_path="xla", done=None):
        assert launch_path == "ring"
        if not self.fuse:
            rc = await self.check(parts[0].cols, launch_path=launch_path)
            done(rc, None, False)
            return rc
        self.active += 1
        self.max_active = max(self.max_active, self.active)
        self.launch_order.append(parts[0])
        try:
            if self.delay:
                await asyncio.sleep(self.delay)
            if self.fail_on is not None and parts[0] == self.fail_on:
                raise RuntimeError(f"boom on {parts[0]}")
            return ("rc", parts[0])
        finally:
            self.active -= 1

    async def check(self, cols, now_ms=None, disp=None, launch_path="xla"):
        assert launch_path == "ring"
        self.check_calls += 1
        return ("cols-rc", cols)


# ------------------------------------------------------------ ring protocol


def test_ring_rejects_degenerate_sizes():
    with pytest.raises(ValueError):
        RequestRing(StubRunner(), slots=1)
    with pytest.raises(ValueError):
        RequestRing(StubRunner(), slots=0)


def test_ring_orders_launches_and_echoes_results():
    """Launch order is strictly ticket order even under racing submitters,
    and every submitter gets ITS chunk's response back."""
    async def go():
        r = StubRunner(delay=0.001)
        ring = RequestRing(r, slots=4)
        outs = await asyncio.gather(*(ring.submit([i]) for i in range(24)))
        return r, ring, outs

    r, ring, outs = asyncio.run(go())
    assert r.launch_order == sorted(r.launch_order)  # ticket order
    assert [o[1] for o in outs] == list(range(24))  # no cross-wiring
    d = ring.debug()
    assert d["published"] == d["consumed"] == d["launches"] == 24
    assert d["occupancy"] == 0


def test_ring_backpressure_bounds_occupancy_without_drops():
    """More submitters than slots: submits WAIT (no drops), in-flight
    dispatches never exceed S, FIFO order is preserved."""
    async def go():
        r = StubRunner(delay=0.002)
        ring = RequestRing(r, slots=3)
        outs = await asyncio.gather(*(ring.submit([i]) for i in range(32)))
        return r, ring, outs

    r, ring, outs = asyncio.run(go())
    assert r.max_active <= 3  # occupancy bound held
    assert ring.max_occupancy <= 3
    assert ring.backpressure_waits > 0  # the bound actually engaged
    assert [o[1] for o in outs] == list(range(32))  # nothing dropped/reordered
    assert ring.debug()["launches"] == 32


def test_ring_sequence_fences():
    """seq_in/seq_out carry ticket+1 per slot (never 0 for a used slot),
    and after full retirement the egress fence has caught the ingress."""
    async def go():
        ring = RequestRing(StubRunner(), slots=4)
        await asyncio.gather(*(ring.submit([i]) for i in range(11)))
        return ring

    ring = asyncio.run(go())
    # 11 tickets over 4 slots: slot s last carried the highest ticket
    # t ≡ s (mod 4) below 11, fence word t+1
    for s in range(4):
        last = max(t for t in range(11) if t % 4 == s)
        assert int(ring.seq_in[s]) == last + 1
        assert int(ring.seq_out[s]) == last + 1


def test_ring_drain_is_zero_loss_and_closes_intake():
    """drain() retires every published ticket before parking the loop; a
    submitter racing the drain gets RingClosed (the batcher's cue to fall
    back to the direct path — no request lost either way)."""
    async def go():
        r = StubRunner(delay=0.005)
        ring = RequestRing(r, slots=4)
        pending = [asyncio.create_task(ring.submit([i])) for i in range(8)]
        await asyncio.sleep(0.006)  # some in flight, some queued
        await ring.drain()
        outs = await asyncio.gather(*pending, return_exceptions=True)
        late = None
        try:
            await ring.submit(["late"])
        except RingClosed as exc:
            late = exc
        return ring, outs, late

    ring, outs, late = asyncio.run(go())
    ok = [o for o in outs if not isinstance(o, Exception)]
    closed = [o for o in outs if isinstance(o, RingClosed)]
    assert len(ok) + len(closed) == 8  # every submit resolved, one way
    assert len(ok) == ring.debug()["launches"]  # published == launched
    assert [o[1] for o in ok] == sorted(o[1] for o in ok)  # order kept
    assert isinstance(late, RingClosed)
    assert ring.debug()["closed"]


def test_ring_drain_without_traffic():
    async def go():
        ring = RequestRing(StubRunner(), slots=2)
        await ring.drain()  # never started: must not hang
        with pytest.raises(RingClosed):
            await ring.submit(["x"])
        return ring

    ring = asyncio.run(go())
    assert ring.debug()["published"] == 0


def test_ring_nonfusable_chunk_falls_back_to_columns_path():
    """A chunk the fused staging refuses rides the columns path INSIDE the
    same runner dispatch — same as for Batcher._dispatch — and the ring
    counts it."""
    async def go():
        r = StubRunner(fuse=False)
        ring = RequestRing(r, slots=2)

        class P:
            cols = "c0"

        out = await ring.submit([P()])
        return r, ring, out

    r, ring, out = asyncio.run(go())
    assert r.check_calls == 1
    assert out == ("cols-rc", "c0")
    assert ring.fallbacks == 1


def test_ring_dispatch_error_propagates_to_submitter():
    """A failing dispatch resolves ONLY its own submitter's poll with the
    error; later tickets still retire cleanly."""
    async def go():
        r = StubRunner(delay=0.001, fail_on=2)
        ring = RequestRing(r, slots=4)
        outs = await asyncio.gather(
            *(ring.submit([i]) for i in range(6)), return_exceptions=True
        )
        return ring, outs

    ring, outs = asyncio.run(go())
    assert isinstance(outs[2], RuntimeError)
    good = [o for i, o in enumerate(outs) if i != 2]
    assert [o[1] for o in good] == [0, 1, 3, 4, 5]
    assert ring.debug()["consumed"] == 6  # the failed slot still retired


# ------------------------------------------------------- daemon integration


NOW = None  # wall clock at corpus build: inside created_at tolerance


def _corpus(reqs, rows, tag):
    from gubernator_tpu.proto import gubernator_pb2 as pb

    now = int(time.time() * 1000)
    return [
        pb.GetRateLimitsReq(
            requests=[
                pb.RateLimitReq(
                    name="ring", unique_key=f"{tag}r{r}i{i}", hits=1,
                    limit=1 << 20, duration=3_600_000, created_at=now,
                )
                for i in range(rows)
            ]
        ).SerializeToString()
        for r in range(reqs)
    ]


def _conf(**beh):
    from gubernator_tpu.config import BehaviorConfig, DaemonConfig

    beh.setdefault("batch_wait_ms", 1.0)
    beh.setdefault("front_workers", 4)
    return DaemonConfig(
        grpc_address="127.0.0.1:0", http_address="", cache_size=1 << 14,
        behaviors=BehaviorConfig(**beh),
    )


def test_daemon_ring_byte_identity(monkeypatch):
    """The whole point: a ring-fed daemon serves byte-identical responses
    to a direct-dispatch daemon over the same corpus, while the launch
    counter splits by path and the drain retires everything."""
    monkeypatch.setenv("GUBER_WIRE_COMPACT", "1")
    from gubernator_tpu.service.daemon import Daemon
    from gubernator_tpu.service.metrics import parse_metrics

    async def go():
        dr = await Daemon.spawn(_conf(ring_enable=True, ring_slots=4))
        dd = await Daemon.spawn(_conf())
        datas = _corpus(16, 48, "x")
        r1 = await asyncio.gather(*(dr.get_rate_limits_raw(x) for x in datas))
        r2 = await asyncio.gather(*(dd.get_rate_limits_raw(x) for x in datas))
        scrape = parse_metrics(dr.metrics.render().decode())
        ringdbg = dr.ring.debug()
        nring = dr.batcher.ring_dispatches
        await dr.close()
        await dd.close()
        return r1, r2, scrape, ringdbg, nring, dr.ring.debug()

    r1, r2, scrape, dbg, nring, post = asyncio.run(go())
    assert r1 == r2  # byte-identical, request by request
    assert nring > 0 and dbg["launches"] == nring
    assert dbg["occupancy"] == 0  # everything retired before close
    launches = scrape["gubernator_tpu_dispatch_launches_total"]
    assert launches[(("path", "ring"),)] == nring
    assert (("path", "xla"),) in launches  # warm_up rode the direct path
    stages = scrape["gubernator_tpu_stage_duration_count"]
    assert stages[(("stage", "ring_put"),)] >= nring
    assert stages[(("stage", "ring_poll"),)] >= nring
    assert post["closed"]  # daemon.close drained the ring


def test_ring_config_env_plumbing():
    from gubernator_tpu.config import setup_daemon_config

    conf = setup_daemon_config(env={
        "GUBER_GRPC_ADDRESS": "127.0.0.1:0", "GUBER_HTTP_ADDRESS": "",
        "GUBER_RING_ENABLE": "1", "GUBER_RING_SLOTS": "8",
    })
    assert conf.behaviors.ring_enable is True
    assert conf.behaviors.ring_slots == 8


def test_ring_config_validation_and_engine_block_constants():
    """GUBER_RING_ISSUE is auto|host|fused — `persistent` named a kernel
    the chip refused and went with it — a 1-slot ring is refused, and
    /v1/debug/pipeline still reports the two engine keys the benchmark's
    configurations and chip_smoke.py compare, as constants."""
    from gubernator_tpu.config import (
        BehaviorConfig,
        ConfigError,
        DaemonConfig,
        setup_daemon_config,
    )
    from gubernator_tpu.service.daemon import Daemon

    for issue in ("auto", "host", "fused"):
        DaemonConfig(behaviors=BehaviorConfig(
            ring_enable=True, ring_slots=2, ring_issue=issue
        )).validate()
    with pytest.raises(ConfigError, match="GUBER_RING_ISSUE"):
        setup_daemon_config(env={
            "GUBER_GRPC_ADDRESS": "127.0.0.1:0", "GUBER_HTTP_ADDRESS": "",
            "GUBER_RING_ENABLE": "1", "GUBER_RING_ISSUE": "persistent",
        })
    with pytest.raises(ValueError, match="GUBER_RING_ISSUE"):
        RequestRing(StubRunner(), issue_mode="persistent")
    with pytest.raises(ConfigError):
        DaemonConfig(behaviors=BehaviorConfig(ring_slots=1)).validate()

    async def go():
        d = await Daemon.spawn(_conf())
        try:
            return d.debug_pipeline()
        finally:
            await d.close()

    dbg = asyncio.run(go())
    assert dbg["engine"]["probe_kernel"] == "xla"
    assert dbg["engine"]["a2a_impl"] is None  # "collective" on a mesh engine


# ------------------------------------------------------ warm_up zero compiles


def _warm_shapes_again(d):
    """Re-drive the exact dispatch surface warm_up traced, with DIFFERENT
    values (shape-cache, not value-cache): the decide variants and the
    1-row install."""
    from gubernator_tpu.ops.batch import RequestColumns

    async def go():
        for algos in ([0], [2], [2, 3], [1]):
            n = len(algos)
            await d.runner.check_columns(RequestColumns(
                fp=np.arange(7, 7 + n, dtype=np.int64),
                algo=np.asarray(algos, dtype=np.int32),
                behavior=np.zeros(n, dtype=np.int32),
                hits=np.ones(n, dtype=np.int64),
                limit=np.full(n, 5, dtype=np.int64),
                burst=np.zeros(n, dtype=np.int64),
                duration=np.full(n, 1000, dtype=np.int64),
                created_at=np.zeros(n, dtype=np.int64),
                err=np.zeros(n, dtype=np.int8),
            ))
        await d.runner.install_columns(
            fp=np.asarray([9], dtype=np.int64),
            algo=np.zeros(1, dtype=np.int32),
            status=np.zeros(1, dtype=np.int32),
            limit=np.full(1, 3, dtype=np.int64),
            remaining=np.ones(1, dtype=np.int64),
            reset_time=np.full(1, 2, dtype=np.int64),
            duration=np.full(1, 2, dtype=np.int64),
            now_ms=2,
        )

    return go()


def test_warm_up_leaves_zero_compiles():
    """After Daemon.spawn (which runs warm_up), re-dispatching every warmed
    shape triggers ZERO fresh XLA compiles (the always-on contract: no
    production dispatch of a warmed shape ever traces on the request
    path)."""
    import jax.monitoring as jm

    from gubernator_tpu.service.daemon import Daemon

    compiles = []
    armed = [False]

    def listener(event, **kw):
        if armed[0] and event == COMPILE_EVENT:
            compiles.append(event)

    async def go():
        import jax
        import jax.numpy as jnp

        d = await Daemon.spawn(_conf())
        jm.register_event_listener(listener)
        armed[0] = True
        try:
            await _warm_shapes_again(d)
            warm_compiles = list(compiles)
            # positive control: a fresh jitted function MUST fire the
            # compile event — proves the listener actually observes
            # compiles, so the empty assertion above means something
            jax.jit(lambda x: x * 3 + 1)(jnp.arange(8)).block_until_ready()
            canary_fired = len(compiles) > len(warm_compiles)
        finally:
            armed[0] = False
        await d.close()
        return warm_compiles, canary_fired

    try:
        warm_compiles, canary_fired = asyncio.run(go())
    finally:
        armed[0] = False
    assert canary_fired, "compile-event canary did not fire"
    assert warm_compiles == [], (
        f"warm_up left {len(warm_compiles)} shapes compiling on the "
        "request path"
    )
