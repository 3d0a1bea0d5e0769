"""Fused multi-slot ring drain suite (ops/ring_drain.py + service/ring.py
fused issue loop) — the kill-the-launch-tax tentpole's acceptance surface.

* **Byte parity.** A fused-drain daemon (GUBER_RING_ISSUE=fused) serves
  byte-identical responses to a direct-dispatch daemon over the same corpus
  under heavy submitter concurrency — the fused while_loop walks the same
  decide2_wire_cols graph per slot, in the same ticket order, so the bytes
  cannot differ.
* **Amortization.** The launch counter proves the point of the PR: strictly
  fewer drain launches than retired slots (`dispatch_launches_total{path=
  "fused"}` + ring drain counters).
* **Zero-loss drain.** drain() racing live fused launches loses nothing:
  every submitter resolves (served or RingClosed→direct fallback).
* **Backpressure.** K < occupancy just means more drains per window — the
  slot-count bound still holds, nothing drops or reorders.
* **Fence protocol.** `DeviceRing.drain` claims published tickets in
  order, stops at the first unpublished one, takes at most k, wraps the
  ring and publishes `seq_out` for exactly what it claimed.
"""

import asyncio
import os
import time

import numpy as np
import pytest

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")


def _corpus(reqs, rows, tag):
    from gubernator_tpu.proto import gubernator_pb2 as pb

    now = int(time.time() * 1000)
    return [
        pb.GetRateLimitsReq(
            requests=[
                pb.RateLimitReq(
                    name="drain", unique_key=f"{tag}r{r}i{i}", hits=1,
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


# ------------------------------------------------------------- byte parity


def test_fused_drain_byte_identity_under_concurrency(monkeypatch):
    """24 concurrent 64-row submitters through the fused-drain ring vs the
    direct path: responses byte-identical request by request, multiple
    slots retired per launch (the launch tax actually amortized), and the
    fused launch counter exported."""
    monkeypatch.setenv("GUBER_WIRE_COMPACT", "1")
    from gubernator_tpu.service.daemon import Daemon
    from gubernator_tpu.service.metrics import parse_metrics

    async def go():
        # coalesce_limit == the per-request row count: every request is its
        # own ring slot, so concurrent submitters actually FILL slots and
        # the drain has groups to retire (one giant coalesced chunk would
        # trivially be a single launch either way)
        df = await Daemon.spawn(_conf(
            ring_enable=True, ring_slots=8, ring_issue="fused",
            ring_drain_k=8, coalesce_limit=64, front_workers=8,
        ))
        dd = await Daemon.spawn(_conf())
        datas = _corpus(24, 64, "p")
        r1 = await asyncio.gather(*(df.get_rate_limits_raw(x) for x in datas))
        r2 = await asyncio.gather(*(dd.get_rate_limits_raw(x) for x in datas))
        scrape = parse_metrics(df.metrics.render().decode())
        dbg = df.ring.debug()
        await df.close()
        await dd.close()
        return r1, r2, scrape, dbg

    r1, r2, scrape, dbg = asyncio.run(go())
    assert r1 == r2  # byte-identical, request by request
    assert dbg["issue_mode"] == "fused"
    assert dbg["drained_slots"] >= 2
    assert dbg["launches"] == dbg["published"] == dbg["consumed"]
    # the tentpole: strictly fewer launches than retired slots
    assert dbg["drain_launches"] < dbg["drained_slots"]
    launches = scrape["gubernator_tpu_dispatch_launches_total"]
    assert launches[(("path", "fused"),)] == dbg["drain_launches"]
    slots = scrape["gubernator_tpu_ring_drain_slots_sum"]
    assert slots[()] == dbg["drained_slots"]
    assert dbg["occupancy"] == 0


# ---------------------------------------------------------- zero-loss drain


def test_drain_zero_loss_through_midflight_fused_launch(monkeypatch):
    """drain() called while fused launches are in flight: every submitter
    resolves with a real verdict (ring-served or direct fallback after
    RingClosed) — no request is lost, and the ring parks closed."""
    monkeypatch.setenv("GUBER_WIRE_COMPACT", "1")
    from gubernator_tpu.service.daemon import Daemon

    async def go():
        d = await Daemon.spawn(_conf(
            ring_enable=True, ring_slots=4, ring_issue="fused",
            ring_drain_k=4,
        ))
        datas = _corpus(16, 32, "z")
        pending = [
            asyncio.create_task(d.get_rate_limits_raw(x)) for x in datas
        ]
        await asyncio.sleep(0.01)  # some fused launches in flight
        await d.ring.drain()
        outs = await asyncio.gather(*pending)
        dbg = d.ring.debug()
        await d.close()
        return outs, dbg

    outs, dbg = asyncio.run(go())
    assert len(outs) == 16 and all(isinstance(o, bytes) for o in outs)
    assert dbg["closed"]
    assert dbg["occupancy"] == 0  # nothing stranded in a slot
    assert dbg["published"] == dbg["consumed"]


# ------------------------------------------------------------- backpressure


def test_backpressure_when_drain_k_below_occupancy(monkeypatch):
    """drain_k=2 against 8 slots and 32 submitters: each launch retires at
    most K slots, so retirement takes multiple drains — but the occupancy
    bound, FIFO ticket order, and byte results are all unaffected."""
    monkeypatch.setenv("GUBER_WIRE_COMPACT", "1")
    from gubernator_tpu.service.daemon import Daemon

    async def go():
        df = await Daemon.spawn(_conf(
            ring_enable=True, ring_slots=8, ring_issue="fused",
            ring_drain_k=2, coalesce_limit=16, front_workers=8,
        ))
        dd = await Daemon.spawn(_conf())
        datas = _corpus(32, 16, "b")
        r1 = await asyncio.gather(*(df.get_rate_limits_raw(x) for x in datas))
        r2 = await asyncio.gather(*(dd.get_rate_limits_raw(x) for x in datas))
        dbg = df.ring.debug()
        maxocc = df.ring.max_occupancy
        await df.close()
        await dd.close()
        return r1, r2, dbg, maxocc

    r1, r2, dbg, maxocc = asyncio.run(go())
    assert r1 == r2  # nothing dropped, nothing reordered
    assert dbg["drain_k"] == 2
    assert maxocc <= 8  # the slot bound held while K throttled retirement
    assert dbg["launches"] == dbg["published"] == dbg["consumed"]
    if dbg["drained_slots"] > 2:
        # K bounds the group: more drains than slots/K is impossible
        assert dbg["drain_launches"] >= dbg["drained_slots"] / 2


# ----------------------------------------------------------- fence protocol


@pytest.mark.parametrize(
    "case",
    [
        # (slots, published tickets, start, k) — contiguous, gap, wrap, k-bound
        (4, [0, 1, 2], 0, 4),
        (4, [0, 2, 3], 0, 4),          # gap at ticket 1: claim stops at 1
        (4, [4, 5, 6, 7], 4, 4),       # second lap of the ring
        (8, list(range(6)), 0, 2),     # k < published: claim exactly k
        (4, [], 0, 4),                 # nothing published: claim nothing
        (4, [1, 2], 0, 4),             # head not published: claim nothing
    ],
)
def test_device_ring_drain_fence_protocol(case):
    """The fused drain's in-trace claim loop: tickets claimed contiguously
    from `start` (a gap or k stops it), each claimed slot decided exactly
    like a direct compact-wire dispatch in ticket order, `seq_out` bumped
    for the claimed slots and no other."""
    import jax

    from gubernator_tpu.ops import wire
    from gubernator_tpu.ops.batch import RequestColumns, pack_columns, pad_batch
    from gubernator_tpu.ops.engine import LocalEngine
    from gubernator_tpu.ops.ring_drain import DeviceRing

    slots, tickets, start, k = case
    width, now = 8, 1_700_000_000_000
    rng = np.random.default_rng(42 + slots + len(tickets))
    eng = LocalEngine(capacity=1 << 10, wire="compact")
    ref = LocalEngine(capacity=1 << 10, wire="compact")
    ring = DeviceRing(slots, width, drain_k=slots)
    grids = {}
    for t in tickets:
        n = width - 2  # two padding rows a slot
        hb, _ = pack_columns(RequestColumns(
            fp=rng.integers(1, 1 << 62, size=n, dtype=np.int64),
            algo=np.zeros(n, np.int32), behavior=np.zeros(n, np.int32),
            hits=rng.integers(0, 4, size=n).astype(np.int64),
            limit=np.full(n, 10, np.int64), burst=np.zeros(n, np.int64),
            duration=np.full(n, 60_000, np.int64),
            created_at=np.zeros(n, np.int64), err=np.zeros(n, np.int8),
        ), now)
        grids[t] = wire.pack_wire_full(pad_batch(hb, width), now)
        ring.stage(t % slots, grids[t], t)

    claimed = []  # the protocol, in plain Python
    while len(claimed) < k and start + len(claimed) in grids:
        claimed.append(start + len(claimed))

    bank, n = ring.drain(eng, start, k, "token", False)
    assert int(n) == len(claimed)
    want_out = np.zeros(slots, dtype=np.int64)
    for i, t in enumerate(claimed):
        want_out[t % slots] = t + 1
        ref.table, out = wire.decide2_wire_cols(
            ref.table, jax.device_put(grids[t]), write=ref.write_mode,
            math="token", cascade=False, evictees=False,
        )
        np.testing.assert_array_equal(np.asarray(bank)[i], np.asarray(out))
    np.testing.assert_array_equal(np.asarray(ring.seq_out), want_out)
    np.testing.assert_array_equal(
        np.asarray(eng.table.rows), np.asarray(ref.table.rows)
    )


def test_fused_config_env_plumbing():
    from gubernator_tpu.config import setup_daemon_config

    conf = setup_daemon_config(env={
        "GUBER_GRPC_ADDRESS": "127.0.0.1:0", "GUBER_HTTP_ADDRESS": "",
        "GUBER_RING_ENABLE": "1", "GUBER_RING_ISSUE": "fused",
        "GUBER_RING_DRAIN_K": "4", "GUBER_RING_SLOT_WIDTH": "128",
        "GUBER_OVERLOAD_DEADLINE_MS": "auto",
    })
    assert conf.behaviors.ring_issue == "fused"
    assert conf.behaviors.ring_drain_k == 4
    assert conf.behaviors.ring_slot_width == 128
    assert conf.behaviors.overload_deadline_auto is True
    assert conf.behaviors.overload_deadline_ms == 0.0
