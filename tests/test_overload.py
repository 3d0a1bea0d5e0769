"""Overload plane (docs/robustness.md "Overload & QoS").

Four contracts under test:

* **zero priority inversions** — when capacity pressure forces sheds, the
  rows that fall are always the lowest queued tier (preempt-before-shed);
  the batcher's `priority_inversions` counter is the runtime proof and
  must stay exactly 0;
* **fair admission** — once the bounded ring is under pressure, one
  tenant (fingerprint bucket) cannot hold more than its share of the
  window; the abuser sheds with a fast per-item overload row, the
  victims keep being admitted;
* **deadline honesty** — an item whose enqueue deadline passes while it
  waits is shed, never served (the answer would arrive after the caller
  stopped listening);
* **lease QoS** — with GUBER_PRIORITY_LEASE_SCALING on, grants scale with
  the requester's tier, pressured keys push shrink_to hints, and the
  edge LocalLimiter honors a hint by clamping its grant target and
  returning the excess ahead of the TTL.
"""

import asyncio
import functools
import time

import numpy as np

from gubernator_tpu.client import V1Client
from gubernator_tpu.edge import LocalLimiter
from gubernator_tpu.ops.batch import ERR_OVERLOAD, RequestColumns, ResponseColumns
from gubernator_tpu.ops.engine import ms_now
from gubernator_tpu.proto import gubernator_pb2 as pb
from gubernator_tpu.service import deadline as deadline_mod
from gubernator_tpu.service.batcher import Batcher
from gubernator_tpu.service.daemon import Daemon
from gubernator_tpu.service.wire import concat_columns
from gubernator_tpu.types import priority_tier, with_cascade_level, with_priority

from tests.cluster import daemon_config

NOW = ms_now()


def async_test(fn):
    @functools.wraps(fn)
    def wrapper(*a, **k):
        asyncio.run(fn(*a, **k))

    return wrapper


def _cols(rows: int, tier: int = 0, base: int = 0, fp0: int = 0) -> RequestColumns:
    """A column batch at one priority tier; fp0 pins the leading
    fingerprint (= the batcher's tenant bucket) when nonzero."""
    fp = np.arange(base + 1, base + rows + 1, dtype=np.int64)
    if fp0:
        fp[0] = fp0
    return RequestColumns(
        fp=fp,
        algo=np.zeros(rows, dtype=np.int32),
        behavior=np.full(rows, with_priority(0, tier), dtype=np.int32),
        hits=np.ones(rows, dtype=np.int64),
        limit=np.full(rows, 100, dtype=np.int64),
        burst=np.zeros(rows, dtype=np.int64),
        duration=np.full(rows, 60_000, dtype=np.int64),
        created_at=np.full(rows, NOW, dtype=np.int64),
        err=np.zeros(rows, dtype=np.int8),
    )


class GatedRunner:
    """Echo runner that blocks the FIRST dispatch on an event — the
    saturated-engine stand-in the overload tests queue behind."""

    def __init__(self):
        self.gate = asyncio.Event()
        self.dispatch_rows = []
        self.dispatch_tiers = []  # leading row's tier, per dispatch

    async def check(self, cols, now_ms=None, disp=None, done=None):
        cols = concat_columns(cols)
        self.dispatch_rows.append(cols.fp.shape[0])
        self.dispatch_tiers.append(priority_tier(int(cols.behavior[0])))
        if len(self.dispatch_rows) == 1:
            await self.gate.wait()
        n = cols.fp.shape[0]
        rc = ResponseColumns(
            status=np.zeros(n, dtype=np.int32),
            limit=cols.limit.copy(),
            remaining=cols.limit - cols.hits,
            reset_time=np.zeros(n, dtype=np.int64),
            err=np.zeros(n, dtype=np.int8),
        )
        if done is not None:
            done(rc, None, False)
        return rc


def _shed_all(rc: ResponseColumns) -> bool:
    return bool(
        (np.asarray(rc.err) == ERR_OVERLOAD).all()
        and (np.asarray(rc.status) == 1).all()
    )


def _served_all(rc: ResponseColumns) -> bool:
    return bool((np.asarray(rc.err) == 0).all())


@async_test
async def test_preemption_sheds_lowest_tier_zero_inversions():
    """A saturated ring + a high-tier arrival: the queued tier-0 batch is
    preempted (fast overload answer), the tier-3 batch is admitted and
    served, and the inversion counter stays 0."""
    runner = GatedRunner()
    b = Batcher(
        runner, batch_wait_ms=0.5, coalesce_limit=64, workers=1,
        adaptive=True, max_queue_rows=64, overload_deadline_ms=2_000.0,
    )
    first = asyncio.ensure_future(b.check(_cols(16)))
    await asyncio.sleep(0.05)  # worker picked it up; engine gated
    low = asyncio.ensure_future(b.check(_cols(64, tier=0, base=100)))
    await asyncio.sleep(0.05)  # fills the ring
    high = asyncio.ensure_future(b.check(_cols(32, tier=3, base=300)))
    await asyncio.sleep(0.05)
    runner.gate.set()
    r_first, r_low, r_high = await asyncio.gather(first, low, high)
    assert _served_all(r_first)
    assert _shed_all(r_low), "queued tier-0 rows must be preempted"
    assert _served_all(r_high), "tier-3 arrival must be served"
    assert b.shed_rows["preempted"] == 64
    assert b.shed_by_tier[0] == 64 and b.shed_by_tier[3] == 0
    assert b.priority_inversions == 0
    # preempted rows never reached the engine
    assert sum(runner.dispatch_rows) == 16 + 32
    # shed responses carry a retry hint in reset_time
    assert (np.asarray(r_low.reset_time) > 0).all()
    await b.drain()


@async_test
async def test_fair_admission_caps_abusive_tenant():
    """Under queue pressure one tenant bucket may hold at most
    tenant_share of the ring: the abuser's second batch sheds with
    reason="fairness", a different tenant is still admitted."""
    runner = GatedRunner()
    b = Batcher(
        runner, batch_wait_ms=0.5, coalesce_limit=128, workers=1,
        adaptive=True, max_queue_rows=128, overload_deadline_ms=5_000.0,
        tenant_share=0.25, tenant_buckets=64,
    )
    first = asyncio.ensure_future(b.check(_cols(16)))
    await asyncio.sleep(0.05)
    # abuser bucket: leading fp pinned to 5 → bucket 5 for every batch
    abuse1 = asyncio.ensure_future(b.check(_cols(64, base=1_000, fp0=5)))
    await asyncio.sleep(0.05)  # 64 pending = half the ring → pressured
    abuse2 = asyncio.ensure_future(b.check(_cols(32, base=2_000, fp0=5)))
    victim = asyncio.ensure_future(b.check(_cols(16, base=3_000, fp0=7)))
    await asyncio.sleep(0.05)
    runner.gate.set()
    r1, ra1, ra2, rv = await asyncio.gather(first, abuse1, abuse2, victim)
    assert _served_all(r1) and _served_all(ra1)
    assert _shed_all(ra2), "abuser beyond its share must shed"
    assert _served_all(rv), "other tenants must keep being admitted"
    assert b.shed_rows["fairness"] == 32
    assert b.priority_inversions == 0
    await b.drain()


@async_test
async def test_deadline_expired_items_shed_not_served():
    """An item whose deadline passes while queued behind a stalled engine
    is answered with the overload row and NEVER dispatched."""
    runner = GatedRunner()
    b = Batcher(
        runner, batch_wait_ms=0.5, coalesce_limit=64, workers=1,
        adaptive=True, max_queue_rows=1024, overload_deadline_ms=100.0,
    )
    first = asyncio.ensure_future(b.check(_cols(16)))
    await asyncio.sleep(0.05)
    stale = asyncio.ensure_future(b.check(_cols(32, base=100)))
    await asyncio.sleep(0.3)  # stale's 100 ms deadline passes in-queue
    runner.gate.set()
    r_first, r_stale = await asyncio.gather(first, stale)
    assert _served_all(r_first)
    assert _shed_all(r_stale), "expired work must be shed, not served"
    assert b.shed_rows["deadline"] == 32
    assert runner.dispatch_rows == [16], "expired rows must not dispatch"
    await b.drain()


@async_test
async def test_inbound_grpc_deadline_bounds_queue_wait():
    """Without the overload knob, a caller's inbound gRPC deadline alone
    bounds the queue wait (service/deadline.py contextvar)."""
    runner = GatedRunner()
    b = Batcher(
        runner, batch_wait_ms=0.5, coalesce_limit=64, workers=1,
        adaptive=True, max_queue_rows=1024,
    )
    assert not b.armed  # knob off: legacy door + inbound bounding only
    first = asyncio.ensure_future(b.check(_cols(16)))
    await asyncio.sleep(0.05)
    deadline_mod.set_inbound_deadline(0.1)
    stale = asyncio.ensure_future(b.check(_cols(8, base=100)))
    deadline_mod.set_inbound_deadline(None)
    await asyncio.sleep(0.3)
    runner.gate.set()
    _, r_stale = await asyncio.gather(first, stale)
    assert _shed_all(r_stale)
    assert b.shed_rows["deadline"] == 8
    await b.drain()


@async_test
async def test_tier_rides_wire_and_dispatch_order():
    """Priority bits survive the behavior word round trip and armed
    dispatch order is tier-major, FIFO within a tier."""
    assert priority_tier(with_priority(0, 3)) == 3
    assert priority_tier(with_priority(8, 2)) == 2  # RESET preserved below
    runner = GatedRunner()
    b = Batcher(
        runner, batch_wait_ms=0.5, coalesce_limit=16, workers=1,
        adaptive=True, max_queue_rows=1024, overload_deadline_ms=5_000.0,
    )
    first = asyncio.ensure_future(b.check(_cols(16)))
    await asyncio.sleep(0.05)
    lo = asyncio.ensure_future(b.check(_cols(16, tier=0, base=100)))
    hi = asyncio.ensure_future(b.check(_cols(16, tier=2, base=200)))
    await asyncio.sleep(0.05)
    runner.gate.set()
    await asyncio.gather(first, lo, hi)
    # coalesce_limit 16 → one chunk per entry; tier 2 dispatched before 0
    # even though it enqueued after
    assert runner.dispatch_rows == [16, 16, 16]
    assert runner.dispatch_tiers == [0, 2, 0]
    assert b.admitted_by_tier[2] == 16 and b.admitted_by_tier[0] == 32
    assert b.priority_inversions == 0
    await b.drain()


def _cascade_cols(rows: int, level: int, base: int = 0, fp0: int = 0) -> RequestColumns:
    """A column batch whose rows each carry `level` cascade levels — the
    expensive traffic the cost-weighted door must account at more than one
    unit per row."""
    c = _cols(rows, base=base, fp0=fp0)
    return c._replace(
        behavior=np.full(rows, with_cascade_level(0, level), dtype=np.int32)
    )


@async_test
async def test_cost_weighted_fairness_stops_cascade_starvation():
    """Equal ROW budgets, unequal device cost: a cascade-heavy tenant
    (level-3 rows ≈ 4 kernel rows each) exhausts its fairness share by
    COST and sheds, while the cheap single-row tenant keeps being
    admitted. The control run — identical row counts, no cascades — never
    pressures the door, proving it was the cost weighting (not the row
    counts) that capped the abuser."""
    runner = GatedRunner()
    b = Batcher(
        runner, batch_wait_ms=0.5, coalesce_limit=128, workers=1,
        adaptive=True, max_queue_rows=128, overload_deadline_ms=5_000.0,
        tenant_share=0.25, tenant_buckets=64,
    )
    first = asyncio.ensure_future(b.check(_cols(16)))
    await asyncio.sleep(0.05)  # worker picked it up; engine gated
    # cascade tenant (bucket 5): 16 rows × (1 + 3 levels) = 64 cost units
    # — only 16 ROWS, an eighth of the ring, but half its cost capacity
    casc1 = asyncio.ensure_future(b.check(_cascade_cols(16, 3, base=1_000, fp0=5)))
    await asyncio.sleep(0.05)  # 64 pending cost = half the ring → pressured
    # 8 more cascade rows = 32 cost: bucket 5 would hold 96 > share (32)
    casc2 = asyncio.ensure_future(b.check(_cascade_cols(8, 3, base=2_000, fp0=5)))
    # the cheap tenant (bucket 7) stays admissible under the same pressure
    victim = asyncio.ensure_future(b.check(_cols(16, base=3_000, fp0=7)))
    await asyncio.sleep(0.05)
    runner.gate.set()
    r1, rc1, rc2, rv = await asyncio.gather(first, casc1, casc2, victim)
    assert _served_all(r1) and _served_all(rc1)
    assert _shed_all(rc2), "cascade tenant beyond its COST share must shed"
    assert _served_all(rv), "cheap single-row traffic must not starve"
    assert b.shed_rows["fairness"] == 8
    assert b.priority_inversions == 0
    await b.drain()

    # control: the SAME row counts without cascade levels never even
    # pressure the door (16+8 rows ≪ the 64-row pressure point) — under
    # the old row-weighted accounting the abuser above was this invisible
    runner2 = GatedRunner()
    b2 = Batcher(
        runner2, batch_wait_ms=0.5, coalesce_limit=128, workers=1,
        adaptive=True, max_queue_rows=128, overload_deadline_ms=5_000.0,
        tenant_share=0.25, tenant_buckets=64,
    )
    first2 = asyncio.ensure_future(b2.check(_cols(16)))
    await asyncio.sleep(0.05)
    p1 = asyncio.ensure_future(b2.check(_cols(16, base=1_000, fp0=5)))
    await asyncio.sleep(0.05)
    p2 = asyncio.ensure_future(b2.check(_cols(8, base=2_000, fp0=5)))
    await asyncio.sleep(0.05)
    runner2.gate.set()
    rf, rp1, rp2 = await asyncio.gather(first2, p1, p2)
    assert _served_all(rf) and _served_all(rp1) and _served_all(rp2)
    assert b2.shed_rows["fairness"] == 0
    await b2.drain()


@async_test
async def test_auto_deadline_tracks_issue_ewma():
    """GUBER_OVERLOAD_DEADLINE_MS=auto arms the door with a deadline
    derived from the runner's issue-stage EWMA
    (OVERLOAD_AUTO_DEADLINE_MULT × issue_ewma, floored at shed_retry_ms)
    — re-evaluated per enqueue as the EWMA moves."""
    from gubernator_tpu.service.batcher import OVERLOAD_AUTO_DEADLINE_MULT

    runner = GatedRunner()
    b = Batcher(
        runner, batch_wait_ms=0.5, coalesce_limit=64, workers=1,
        adaptive=True, max_queue_rows=1024, overload_deadline_auto=True,
        shed_retry_ms=25,
    )
    assert b.armed  # auto arms the full overload plane
    # no EWMA yet (cold runner): the shed_retry floor keeps the door sane
    d0 = b._item_deadline()
    assert d0 is not None
    assert abs((d0 - time.monotonic()) - 0.025) < 0.01
    # a measured issue stage moves the deadline with it
    runner.issue_ewma = 0.002
    d1 = b._item_deadline()
    want = OVERLOAD_AUTO_DEADLINE_MULT * 0.002
    assert abs((d1 - time.monotonic()) - want) < 0.05
    await b.drain()


# ------------------------------------------------------------- lease QoS


@async_test
async def test_lease_grants_scale_with_tier():
    """GUBER_PRIORITY_LEASE_SCALING: same ask, tier 3 gets the full slice,
    tier 0 a quarter; pressured keys push shrink_to at low tiers."""
    conf = daemon_config()
    conf.lease_priority_scaling = True
    conf.lease_max_fraction = 0.5  # cap = 500 of the 1 000 limit
    d = await Daemon.spawn(conf)
    try:
        def req(key, tokens, tier, lease_id=""):
            return pb.LeaseQuotaReq(
                name="qos", unique_key=key, tokens=tokens, limit=1_000,
                duration=60_000, ttl_ms=2_000, lease_id=lease_id,
                behavior=with_priority(0, tier),
            )

        r3 = await d.lease_quota(req("k-hi", 400, 3))
        r0 = await d.lease_quota(req("k-lo", 400, 0))
        assert r3.granted == 400  # tier 3: full ask (≤ cap 500)
        assert r0.granted == 100  # tier 0: a quarter of the ask
        assert r3.shrink_to == 0 and r0.shrink_to == 0  # no pressure yet

        # pressure k-lo past 80% of its 500-token cap, then renew at tier 0:
        # the response must carry a shrink hint below the outstanding
        ra = await d.lease_quota(req("k-lo", 1_000, 3))
        assert ra.granted > 0
        rb = await d.lease_quota(req("k-lo", 4, 0, lease_id=r0.lease_id))
        assert rb.shrink_to > 0, "pressured low-tier lease must be asked to shrink"
        assert rb.shrink_to < 100 + rb.granted
        # tier 3 under the same pressure is never asked to shrink
        rc = await d.lease_quota(req("k-lo", 4, 3, lease_id=ra.lease_id))
        assert rc.shrink_to == 0
    finally:
        await d.close()


class _ShrinkClient(V1Client):
    """Stub lease endpoint: grants normally, then starts pushing a
    shrink_to hint — no network, the LocalLimiter drives this directly."""

    def __init__(self):
        super().__init__("127.0.0.1:1")  # lazy channel: never connected
        self.calls = 0
        self.shrink_to = 0
        self.returned = 0

    async def lease_quota(self, req, timeout_s=None):
        self.calls += 1
        self.returned += int(req.return_tokens)
        return pb.LeaseQuotaResp(
            lease_id="L1", granted=int(req.tokens),
            expires_at=ms_now() + 60_000, limit=req.limit,
            remaining=req.limit, shrink_to=self.shrink_to,
        )


@async_test
async def test_local_limiter_honors_push_shrink_hint():
    """A shrink_to hint clamps the edge's grant target and the next
    renewal returns the excess budget instead of holding it to the TTL."""
    client = _ShrinkClient()
    # waste_fraction=10: disable adaptive halving so any giveback in this
    # test is attributable to the shrink hint alone
    lim = LocalLimiter(
        client, "edge", "u1", limit=1_000, duration=60_000,
        ttl_ms=60_000, initial_grant=64, waste_fraction=10.0,
    )
    await lim.start()
    assert lim.budget == 64 and lim.stats.shrinks == 0
    client.shrink_to = 8
    await lim._renew_once()  # hint arrives with this renewal's response
    assert lim.stats.shrinks == 1
    assert lim._grant <= 8, "grant target must clamp to the hint"

    async def excess_returned():
        deadline = time.perf_counter() + 5
        while time.perf_counter() < deadline:
            if client.returned > 0:
                return True
            await asyncio.sleep(0.05)
        return False

    assert await excess_returned(), "excess budget must return early"
    await lim.close()
    await client.close()
