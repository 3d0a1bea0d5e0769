"""Small deterministic CPU benchmark for the CI regression gate.

The reference gates PRs on a relative benchmark regression (±200% vs master,
reference .github/workflows/on-pull-request.yml:47-80). CI runners have no
TPU, so the gate measures the XLA-CPU lowering of the same serving path
(LocalEngine.check_columns → decision kernel, scatter write): base and PR
trees run in the SAME job and only their ratio matters — machine speed
cancels out.

Also runs a sharded-dispatch ingress smoke on a virtual 8-device mesh
(route="device" + in-trace dedup — the TPU serving default): regressions
that re-grow the host staging cost with batch size (a reintroduced host
group-by or argsort on the dispatch path) fail fast here, gated by
bench_guard.check_dropped so a drop-storm can't masquerade as fast staging.

Prints one JSON line: {"decisions_per_sec": N, "sharded_smoke": {...}}.
"""

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# the ingress smoke needs a multi-device mesh; must be set before jax init
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import gubernator_tpu  # noqa: F401,E402  (x64 on)
from gubernator_tpu.bench_guard import StageTotals, check_dropped
from gubernator_tpu.ops.batch import RequestColumns
from gubernator_tpu.ops.engine import LocalEngine

NOW = 1_700_000_000_000
B = 4096


def cols(fp: np.ndarray) -> RequestColumns:
    n = fp.shape[0]
    return RequestColumns(
        fp=fp,
        algo=(np.arange(n) % 2).astype(np.int32),
        behavior=np.zeros(n, dtype=np.int32),
        hits=np.ones(n, dtype=np.int64),
        limit=np.full(n, 1 << 20, dtype=np.int64),
        burst=np.zeros(n, dtype=np.int64),
        duration=np.full(n, 3_600_000, dtype=np.int64),
        created_at=np.full(n, NOW, dtype=np.int64),
        err=np.zeros(n, dtype=np.int8),
    )


def sharded_smoke() -> dict:
    """Ingress-path regression gate: host-stage ms per dispatch through the
    device-routed, in-trace-dedup mesh path must stay batch-proportional.
    Staging at 8× the rows may cost up to 8× (proportional) times slack —
    a reintroduced keyspace-bound or super-linear host step (np.unique,
    argsort routing, per-dispatch grid realloc at table scale) blows the
    bound; flat-to-linear passes. check_dropped rejects a run that 'wins'
    by shedding rows into terminal drops."""
    from gubernator_tpu.parallel import make_mesh
    from gubernator_tpu.parallel.sharded import ShardedEngine

    mesh = make_mesh(8)
    eng = ShardedEngine(
        mesh, capacity_per_shard=1 << 12, write_mode="xla",
        route="device", dedup="device",
    )
    rng = np.random.default_rng(1)
    big, small = 4096, 512
    fps = rng.integers(1, (1 << 63) - 1, size=big * 4, dtype=np.int64)
    batches = {
        n: [fps[i * n : (i + 1) * n] for i in range(4)] for n in (big, small)
    }
    for n in (small, big):  # compile + seed
        for f in batches[n]:
            eng.check_columns(cols(f), now_ms=NOW)

    def stage_ms_per_dispatch(n: int, k: int = 12) -> float:
        totals = StageTotals()
        with totals.watch():
            for i in range(k):
                eng.check_columns(cols(batches[n][i % 4]), now_ms=NOW)
        return sum(totals.stage_ms.values()) / max(1, totals.stage_dispatches)

    small_ms = min(stage_ms_per_dispatch(small) for _ in range(3))
    big_ms = min(stage_ms_per_dispatch(big) for _ in range(3))
    rows_ratio = big / small
    SLACK = 4.0
    ok = big_ms <= rows_ratio * SLACK * max(small_ms, 1e-4)
    guard = check_dropped(eng.stats.dropped, max(1, eng.stats.checks))
    out = {
        "host_stage_small_ms": round(small_ms, 4),
        "host_stage_big_ms": round(big_ms, 4),
        "rows_ratio": rows_ratio,
        "proportional": bool(ok),
        "dropped_guard": guard or "ok",
    }
    if not ok:
        print(json.dumps({"error": "sharded ingress host-stage cost is "
                          "super-linear in batch rows", **out}))
        sys.exit(1)
    if guard:
        print(json.dumps({"error": f"sharded smoke drop storm: {guard}", **out}))
        sys.exit(1)
    return out


def wire_smoke() -> dict:
    """Compact-wire regression gate (ISSUE 5): on the 8-device mesh with
    the TPU serving defaults forced (device route/dedup, compact wire),

      * responses must match the full-width oracle row-for-row over
        token/leaky/duplicate-key/flagged traffic;
      * marginal bytes/row across two batch sizes must stay within the
        wire budget — put ≤ 24 B/row and fetch ≤ 16 B/row (marginal cost
        is the honest transport-proportionality metric: it cancels the
        fixed per-dispatch base column and stats rows) — and beat the
        full-width layout ≥3× on put, ≥2× on fetch;
      * double-buffered dispatch wall time must stay batch-proportional
        (the sharded_smoke bound, driven through the depth-2 pipelined
        issue/finish split this time);
      * the transport gate must not reject the window for claiming bytes
        it could not have moved (impossible-bandwidth side only — CI
        runners are legitimately slow, so the drift side is reported,
        not fatal).
    """
    import time as _time

    from gubernator_tpu.bench_guard import check_transport
    from gubernator_tpu.ops.engine import (
        finish_check_columns,
        issue_check_columns,
        prepare_check_columns,
    )
    from gubernator_tpu.parallel import make_mesh
    from gubernator_tpu.parallel.sharded import ShardedEngine

    mesh = make_mesh(8)
    kw = dict(
        capacity_per_shard=1 << 12, write_mode="xla",
        route="device", dedup="device",
    )
    ec = ShardedEngine(mesh, wire="compact", **kw)
    ef = ShardedEngine(mesh, wire="full", **kw)
    rng = np.random.default_rng(7)
    big, small = 4096, 512

    def mixed_cols(fp):
        n = fp.shape[0]
        c = cols(fp)
        return c._replace(
            behavior=rng.choice([0, 8, 32], size=n).astype(np.int32),
            hits=rng.integers(0, 4, n).astype(np.int64),
        )

    state = rng.bit_generator.state
    for eng in (ec, ef):
        rng.bit_generator.state = state
        for step in range(4):
            n = big if step % 2 else small
            fp = rng.integers(1, (1 << 63) - 1, size=n, dtype=np.int64)
            if step == 3:
                fp[n // 2 :] = fp[: n - n // 2]  # duplicate keys
            rc = eng.check_columns(mixed_cols(fp), now_ms=NOW)
            if eng is ec:
                saved = getattr(ec, "_smoke", [])
                saved.append(rc)
                ec._smoke = saved
            else:
                want = ec._smoke[step]
                for f in ("status", "limit", "remaining", "reset_time", "err"):
                    if not np.array_equal(getattr(rc, f), getattr(want, f)):
                        print(json.dumps({
                            "error": f"wire smoke: compact/full mismatch in "
                                     f"{f} at step {step}"}))
                        sys.exit(1)

    def bytes_per_dispatch(eng, n, k=6):
        eng.take_wire_deltas()
        fps = rng.integers(1, (1 << 63) - 1, size=(k, n), dtype=np.int64)
        for i in range(k):
            eng.check_columns(cols(fps[i]), now_ms=NOW)
        w = eng.take_wire_deltas()
        return w["put"] / k, w["fetch"] / k

    marg = {}
    for label, eng in (("compact", ec), ("full", ef)):
        put_s, fetch_s = bytes_per_dispatch(eng, small)
        put_b, fetch_b = bytes_per_dispatch(eng, big)
        marg[label] = (
            (put_b - put_s) / (big - small),
            (fetch_b - fetch_s) / (big - small),
        )
    put_row, fetch_row = marg["compact"]
    put_ratio = marg["full"][0] / max(put_row, 1e-9)
    fetch_ratio = marg["full"][1] / max(fetch_row, 1e-9)
    out = {
        "put_bytes_per_row": round(put_row, 2),
        "fetch_bytes_per_row": round(fetch_row, 2),
        "put_reduction_vs_full": round(put_ratio, 2),
        "fetch_reduction_vs_full": round(fetch_ratio, 2),
    }
    if put_row > 24 or fetch_row > 16:
        print(json.dumps({"error": "compact wire over budget (put ≤ 24, "
                          "fetch ≤ 16 B/row)", **out}))
        sys.exit(1)
    if put_ratio < 3.0 or fetch_ratio < 2.0:
        print(json.dumps({"error": "compact wire reduction under the "
                          "acceptance floor (≥3x put, ≥2x fetch)", **out}))
        sys.exit(1)

    # double-buffered wall-time proportionality through the depth-2 split
    def piped_wall(n, k=12):
        fps = rng.integers(1, (1 << 63) - 1, size=(k, n), dtype=np.int64)
        fixup = lambda fn: fn()
        t0 = _time.perf_counter()
        pend = []
        for i in range(k):
            pend.append(issue_check_columns(
                ec, prepare_check_columns(ec, cols(fps[i]), now_ms=NOW)
            ))
            if len(pend) > 2:
                _rc, delta = finish_check_columns(ec, pend.pop(0), fixup)
                ec.stats.merge(delta)
        while pend:
            _rc, delta = finish_check_columns(ec, pend.pop(0), fixup)
            ec.stats.merge(delta)
        return _time.perf_counter() - t0

    piped_wall(small, k=2)  # warm
    piped_wall(big, k=2)
    small_s = min(piped_wall(small) for _ in range(3))
    big_s = min(piped_wall(big) for _ in range(3))
    SLACK = 4.0
    ok = big_s <= (big / small) * SLACK * max(small_s, 1e-4)
    out["piped_small_s"] = round(small_s, 4)
    out["piped_big_s"] = round(big_s, 4)
    out["piped_proportional"] = bool(ok)
    if not ok:
        print(json.dumps({"error": "double-buffered sharded dispatch wall "
                          "time is super-linear in batch rows", **out}))
        sys.exit(1)

    # transport gate: only the impossible-bandwidth side is fatal on CI
    ec.take_wire_deltas()
    totals = StageTotals()
    with totals.watch():
        for i in range(6):
            ec.check_columns(cols(rng.integers(1, (1 << 63) - 1, size=big,
                                               dtype=np.int64)), now_ms=NOW)
    w = ec.take_wire_deltas()
    put_ms = totals.stage_ms["put"]
    guard = check_transport(put_ms / 1e3, w["put"], min_bandwidth=0.0)
    out["transport_guard"] = guard or "ok"
    if guard:
        print(json.dumps({"error": f"wire smoke transport gate: {guard}",
                          **out}))
        sys.exit(1)
    return out


def handoff_smoke() -> dict:
    """Topology-handoff regression gate: extract + conservative-merge of
    ~100k live rows across an 8-device mesh must be batch-proportional on
    the host (no full-table host loop — the device does the partition pass)
    and lose zero rows in the no-fault case (row parity src extract → dst
    merge). Host cost is measured as wall time of the merge path at 1× vs
    8× the rows: super-linear growth (a reintroduced per-row Python loop or
    keyspace-bound staging) blows the bound."""
    from gubernator_tpu.parallel import make_mesh
    from gubernator_tpu.parallel.sharded import ShardedEngine

    mesh = make_mesh(8)
    cap = 1 << 15  # 256K slots across the mesh — rows ≪ table
    src = ShardedEngine(mesh, capacity_per_shard=cap, write_mode="xla")
    rng = np.random.default_rng(3)
    n = 100_000
    fps_in = np.unique(rng.integers(1, (1 << 63) - 1, size=n + n // 8,
                                    dtype=np.int64))[:n]
    ones = np.ones(n, dtype=np.int64)
    installed = src.install_columns(
        fp=fps_in,
        algo=np.zeros(n, dtype=np.int32),
        status=np.zeros(n, dtype=np.int32),
        limit=ones * 100,
        remaining=ones * 37,
        reset_time=ones * (NOW + 3_600_000),
        duration=ones * 3_600_000,
        now_ms=NOW,
    )
    # a few installs drop to per-bucket overflow (the claim auction's
    # documented behavior at this load) — parity is against what LANDED
    t0 = time.perf_counter()
    fps, slots = src.extract_live(NOW)
    t_extract = time.perf_counter() - t0
    if fps.shape[0] != installed:
        print(json.dumps({"error": "handoff smoke: extract lost rows",
                          "extracted": int(fps.shape[0]),
                          "expected": installed}))
        sys.exit(1)

    n_live = int(fps.shape[0])

    def merge_time(rows: int) -> float:
        dst = ShardedEngine(mesh, capacity_per_shard=cap, write_mode="xla")
        dst.merge_rows(fps[:rows], slots[:rows], now_ms=NOW)  # compile+seed
        t0 = time.perf_counter()
        merged = dst.merge_rows(fps[:rows], slots[:rows], now_ms=NOW)
        dt = time.perf_counter() - t0
        if merged != rows:  # idempotent replay must re-ack every row
            print(json.dumps({"error": "handoff smoke: merge lost rows",
                              "merged": merged, "expected": rows}))
            sys.exit(1)
        return dt

    small, big = n_live // 8, n_live
    small_s = min(merge_time(small) for _ in range(3))
    big_s = min(merge_time(big) for _ in range(3))
    SLACK = 4.0
    ok = big_s <= (big / small) * SLACK * max(small_s, 1e-4)
    out = {
        "rows": n,
        "extract_s": round(t_extract, 4),
        "merge_small_s": round(small_s, 4),
        "merge_big_s": round(big_s, 4),
        "proportional": bool(ok),
    }
    if not ok:
        print(json.dumps({"error": "handoff merge cost is super-linear in "
                          "rows", **out}))
        sys.exit(1)
    return out


def serving_smoke() -> dict:
    """Serving-plane regression gate (loopback daemon, CPU backend):

    (a) **parse once, stage once** — an encodable distinct-key corpus must
        ride the fused wire→grid path (no column re-pack on any dispatch),
        and the native parse must stay ∝ bytes (a reintroduced per-item
        Python stage shows up as a super-linear ratio);
    (b) **front-door workers** — serving the same concurrent load with 4
        flush workers must not be slower than with 1 (the multi-worker door
        exists to overlap chunk form/dispatch/fan-out; losing that overlap
        is the regression this gates);
    (c) **adaptive window** — under synthetic backlog the coalesce window
        must close on accumulated ROWS, not ride out a (deliberately huge)
        wall-clock window.
    """
    import asyncio

    from gubernator_tpu.config import BehaviorConfig, DaemonConfig
    from gubernator_tpu.proto import gubernator_pb2 as pb
    from gubernator_tpu.service.daemon import Daemon
    from gubernator_tpu.service.wire import wire_batch_from_wire

    os.environ["GUBER_WIRE_COMPACT"] = "1"  # fused path needs compact wire

    def corpus(reqs: int, rows: int, tag: str):
        return [
            pb.GetRateLimitsReq(
                requests=[
                    pb.RateLimitReq(
                        name="smoke", unique_key=f"{tag}r{r}i{i}", hits=1,
                        limit=1 << 20, duration=3_600_000, created_at=NOW,
                    )
                    for i in range(rows)
                ]
            ).SerializeToString()
            for r in range(reqs)
        ]

    # ---- (a) parse cost ∝ bytes (native parser, one traversal)
    small = corpus(1, 250, "s")[0]
    big = corpus(1, 1000, "b")[0]
    wire_batch_from_wire(small), wire_batch_from_wire(big)  # warm
    K = 50

    def parse_ms(data: bytes) -> float:
        t0 = time.perf_counter()
        for _ in range(K):
            wire_batch_from_wire(data)
        return (time.perf_counter() - t0) / K * 1e3

    p_small, p_big = parse_ms(small), parse_ms(big)
    bytes_ratio = len(big) / len(small)
    parse_ratio = p_big / max(p_small, 1e-9)
    out: dict = {
        "parse_ms_250": round(p_small, 4),
        "parse_ms_1000": round(p_big, 4),
        "parse_bytes_ratio": round(bytes_ratio, 2),
        "parse_time_ratio": round(parse_ratio, 2),
    }
    if parse_ratio > bytes_ratio * 2.5:
        print(json.dumps({"error": "serving smoke: parse cost super-linear "
                          "in bytes", **out}))
        sys.exit(1)

    def conf(**beh) -> DaemonConfig:
        beh.setdefault("batch_wait_ms", 1.0)
        return DaemonConfig(
            grpc_address="127.0.0.1:0", http_address="",
            cache_size=1 << 15,
            behaviors=BehaviorConfig(**beh),
        )

    async def drive(d: Daemon, datas) -> float:
        t0 = time.perf_counter()
        await asyncio.gather(*(d.get_rate_limits_raw(x) for x in datas))
        return time.perf_counter() - t0

    # ---- (a) fused path engaged, zero re-packs; (b) worker scaling
    async def fused_and_workers():
        res = {}
        for label, workers in (("w1", 1), ("w4", 4)):
            d = await Daemon.spawn(conf(front_workers=workers))
            datas = corpus(64, 64, label)
            await drive(d, datas)  # shape warm
            best = min([await drive(d, datas) for _ in range(3)])
            res[label] = best
            if label == "w4":
                res["fused"] = d.batcher.fused_dispatches
                res["fallbacks"] = d.batcher.wire_fallbacks
                res["columns"] = d.batcher.column_dispatches
            await d.close()
        return res

    r = asyncio.run(fused_and_workers())
    out["serve_s_workers1"] = round(r["w1"], 4)
    out["serve_s_workers4"] = round(r["w4"], 4)
    out["worker_speedup"] = round(r["w1"] / max(r["w4"], 1e-9), 3)
    out["fused_dispatches"] = r["fused"]
    out["wire_fallbacks"] = r["fallbacks"]
    if r["fused"] == 0 or r["fallbacks"] > 0:
        print(json.dumps({"error": "serving smoke: encodable corpus did not "
                          "ride the fused parse path", **out}))
        sys.exit(1)
    # CI machines are noisy: gate on "multi-worker must not LOSE the
    # overlap", not on a specific speedup
    if r["w4"] > r["w1"] * 1.5:
        print(json.dumps({"error": "serving smoke: 4 front-door workers "
                          "slower than 1", **out}))
        sys.exit(1)

    # ---- (c) adaptive window closes on rows under backlog
    async def adaptive():
        d = await Daemon.spawn(conf(
            front_workers=2, batch_wait_ms=300.0, adaptive_batch=True,
            batch_close_rows=2048,
        ))
        datas = corpus(64, 64, "a")
        await drive(d, datas)  # shape warm
        wall = await drive(d, corpus(64, 64, "a2"))
        closes, expires = d.batcher.adaptive_closes, d.batcher.window_expires
        await d.close()
        return wall, closes, expires

    wall, closes, expires = asyncio.run(adaptive())
    out["adaptive_wall_s"] = round(wall, 4)
    out["adaptive_closes"] = closes
    out["window_expires"] = expires
    # riding the 300 ms wall-clock window even once per flush cycle would
    # put the wall well past a second for this backlog
    if closes < 1 or wall > 2.0:
        print(json.dumps({"error": "serving smoke: adaptive window did not "
                          "close on rows under backlog", **out}))
        sys.exit(1)
    return out


def ring_smoke() -> dict:
    """Device-resident request-ring regression gate (always-on-chip PR,
    loopback daemon, CPU backend — the functional emulation of the
    persistent-kernel ring protocol):

    (a) **byte-identity** — a ring-fed daemon must serve byte-identical
        responses to a direct-dispatch daemon over the same distinct-key
        corpus under 4-worker concurrency (the ring drives the exact
        runner surface the direct path drives, so any divergence is a
        protocol bug: misordered slot consumption, crossed futures,
        stale staging);
    (b) **bounded backpressure, zero loss** — with a deliberately tiny
        ring (GUBER_RING_SLOTS=2) and per-request chunks, submits must
        WAIT rather than drop: every published ticket launches exactly
        once, in ticket order, and every response comes back;
    (c) **zero-loss drain** — daemon close retires every published slot
        before parking the loop (published == consumed, occupancy 0);
    (d) **bounded host overhead** — the ring protocol's per-dispatch host
        cost (claim/stage/fence/poll) must stay within 2.5× the direct
        path's dispatch wall at small batches. (On CPU the emulation can
        only ADD overhead — the round-trip it deletes is priced by
        bench.py's `dispatch` phase on a real TPU, where the persistent
        kernel skips the launch entirely.)
    """
    import asyncio

    from gubernator_tpu.config import BehaviorConfig, DaemonConfig
    from gubernator_tpu.service.daemon import Daemon

    os.environ["GUBER_WIRE_COMPACT"] = "1"  # fused path needs compact wire
    now = int(time.time() * 1000)  # honored by created_at tolerance →
    # reset_time is corpus-determined, so responses are byte-comparable
    # across daemons serving seconds apart

    def corpus(reqs: int, rows: int, tag: str):
        from gubernator_tpu.proto import gubernator_pb2 as pb

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

    def conf(**beh) -> DaemonConfig:
        beh.setdefault("batch_wait_ms", 1.0)
        beh.setdefault("front_workers", 4)
        # per-request chunks: 64-row requests + a 64-row coalesce cap mean
        # every request is its own ring ticket — the protocol stress shape
        beh.setdefault("coalesce_limit", 64)
        return DaemonConfig(
            grpc_address="127.0.0.1:0", http_address="",
            cache_size=1 << 15, behaviors=BehaviorConfig(**beh),
        )

    async def drive(d: Daemon, datas):
        t0 = time.perf_counter()
        rs = await asyncio.gather(*(d.get_rate_limits_raw(x) for x in datas))
        return time.perf_counter() - t0, rs

    async def run():
        out: dict = {}
        dr = await Daemon.spawn(conf(ring_enable=True, ring_slots=2))
        dd = await Daemon.spawn(conf())
        await drive(dr, corpus(8, 64, "w"))  # shape warm
        await drive(dd, corpus(8, 64, "w"))
        datas = corpus(64, 64, "x")
        t_ring, r1 = await drive(dr, datas)
        t_direct, r2 = await drive(dd, datas)
        dbg = dr.ring.debug()
        out["identical"] = r1 == r2
        out["ring_dispatches"] = dr.batcher.ring_dispatches
        out["ring_launches"] = dbg["launches"]
        out["ring_published"] = dbg["published"]
        out["backpressure_waits"] = dbg["backpressure_waits"]
        out["max_occupancy"] = dbg["max_occupancy"]
        out["fallbacks"] = dbg["fallbacks"]
        out["serve_s_ring"] = round(t_ring, 4)
        out["serve_s_direct"] = round(t_direct, 4)
        out["ring_overhead_ratio"] = round(t_ring / max(t_direct, 1e-9), 3)
        await dr.close()
        await dd.close()
        post = dr.ring.debug()
        out["drained_clean"] = (
            post["closed"] and post["occupancy"] == 0
            and post["published"] == post["consumed"]
        )
        return out

    out = asyncio.run(run())
    if not out["identical"]:
        print(json.dumps({"error": "ring smoke: ring-fed responses diverge "
                          "from the direct dispatch path", **out}))
        sys.exit(1)
    if out["ring_dispatches"] == 0 or out["ring_launches"] == 0:
        print(json.dumps({"error": "ring smoke: ring plane never engaged",
                          **out}))
        sys.exit(1)
    if out["ring_launches"] != out["ring_published"]:
        print(json.dumps({"error": "ring smoke: published tickets were "
                          "dropped (launch/publish mismatch)", **out}))
        sys.exit(1)
    if out["max_occupancy"] > 2:
        print(json.dumps({"error": "ring smoke: occupancy exceeded the "
                          "slot bound", **out}))
        sys.exit(1)
    if out["backpressure_waits"] == 0:
        print(json.dumps({"error": "ring smoke: 64 per-request tickets "
                          "through a 2-slot ring never hit backpressure — "
                          "the bound is not being exercised", **out}))
        sys.exit(1)
    if not out["drained_clean"]:
        print(json.dumps({"error": "ring smoke: drain left unconsumed "
                          "slots", **out}))
        sys.exit(1)
    if out["ring_overhead_ratio"] > 2.5:
        print(json.dumps({"error": "ring smoke: ring protocol host "
                          "overhead exceeds 2.5x the direct path", **out}))
        sys.exit(1)
    return out


def ring_drain_smoke() -> dict:
    """Fused multi-slot drain regression gate (kill-the-launch-tax PR,
    ops/ring_drain.py — the jitted while_loop consumer behind
    GUBER_RING_ISSUE=fused):

    (a) **byte parity at ~1M keys** — a fused-drain daemon must serve
        byte-identical responses to a direct-dispatch daemon over a
        distinct-key corpus of 64×16384 = 1 048 576 keys (the fused graph
        walks the same decide2_wire_cols per slot, in ticket order — any
        divergence is a drain-protocol bug: misgrouped slots, stale bank
        rows, fence skew);
    (b) **launches/decision strictly decreasing in K** — the whole point
        of the PR: over the same concurrent corpus, raising
        GUBER_RING_DRAIN_K must strictly reduce drain launches (K=1 is
        one-launch-per-slot; K=8 retires groups);
    (c) **zero-loss drain** — drain() racing live fused launches strands
        nothing: every submitter resolves, published == consumed,
        occupancy 0.
    """
    import asyncio

    from gubernator_tpu.config import BehaviorConfig, DaemonConfig
    from gubernator_tpu.service.daemon import Daemon

    os.environ["GUBER_WIRE_COMPACT"] = "1"  # fused path needs compact wire
    now = int(time.time() * 1000)

    def corpus(reqs: int, rows: int, tag: str):
        from gubernator_tpu.proto import gubernator_pb2 as pb

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

    def conf(**beh) -> DaemonConfig:
        beh.setdefault("batch_wait_ms", 1.0)
        beh.setdefault("front_workers", 8)
        return DaemonConfig(
            grpc_address="127.0.0.1:0", http_address="",
            cache_size=1 << 21, max_batch_size=4096,
            behaviors=BehaviorConfig(**beh),
        )

    async def drive(d: Daemon, datas):
        t0 = time.perf_counter()
        rs = await asyncio.gather(*(d.get_rate_limits_raw(x) for x in datas))
        return time.perf_counter() - t0, rs

    async def parity():
        out: dict = {}
        # one 4096-row request per ring slot: ~1M distinct keys total
        df = await Daemon.spawn(conf(
            ring_enable=True, ring_issue="fused", ring_slots=8,
            ring_drain_k=8, coalesce_limit=4096,
        ))
        dd = await Daemon.spawn(conf(coalesce_limit=4096))
        await drive(df, corpus(4, 4096, "w"))  # shape warm
        await drive(dd, corpus(4, 4096, "w"))
        datas = corpus(256, 4096, "m")
        t_fused, r1 = await drive(df, datas)
        t_direct, r2 = await drive(dd, datas)
        dbg = df.ring.debug()
        out["identical"] = r1 == r2
        out["keys"] = 256 * 4096
        out["drain_launches"] = dbg["drain_launches"]
        out["drained_slots"] = dbg["drained_slots"]
        out["host_slots"] = dbg["host_slots"]
        out["serve_s_fused"] = round(t_fused, 4)
        out["serve_s_direct"] = round(t_direct, 4)
        await df.close()
        await dd.close()
        return out

    async def k_sweep():
        # same concurrent corpus per K: drain launches must strictly fall
        launches = {}
        for k in (1, 2, 8):
            d = await Daemon.spawn(conf(
                ring_enable=True, ring_issue="fused", ring_slots=8,
                ring_drain_k=k, coalesce_limit=64,
            ))
            await drive(d, corpus(8, 64, f"w{k}"))  # shape warm
            await drive(d, corpus(64, 64, f"s{k}"))
            dbg = d.ring.debug()
            launches[k] = dbg["drain_launches"] + dbg["host_slots"]
            await d.close()
        return launches

    async def zero_loss():
        d = await Daemon.spawn(conf(
            ring_enable=True, ring_issue="fused", ring_slots=4,
            ring_drain_k=4, coalesce_limit=64,
        ))
        pending = [
            asyncio.create_task(d.get_rate_limits_raw(x))
            for x in corpus(32, 64, "z")
        ]
        await asyncio.sleep(0.02)  # fused launches in flight
        await d.ring.drain()
        outs = await asyncio.gather(*pending)
        dbg = d.ring.debug()
        await d.close()
        return (
            all(isinstance(o, bytes) for o in outs)
            and dbg["closed"] and dbg["occupancy"] == 0
            and dbg["published"] == dbg["consumed"]
        )

    out = asyncio.run(parity())
    out["launches_by_k"] = asyncio.run(k_sweep())
    out["drain_zero_loss"] = asyncio.run(zero_loss())
    if not out["identical"]:
        print(json.dumps({"error": "ring drain smoke: fused-drain "
                          "responses diverge from the direct path at 1M "
                          "keys", **out}))
        sys.exit(1)
    if out["drain_launches"] == 0 or out["drained_slots"] == 0:
        print(json.dumps({"error": "ring drain smoke: fused drain never "
                          "engaged", **out}))
        sys.exit(1)
    lk = out["launches_by_k"]
    if not (lk[1] > lk[2] > lk[8]):
        print(json.dumps({"error": "ring drain smoke: launches/decision "
                          "not strictly decreasing in K — the drain is "
                          "not amortizing the launch tax", **out}))
        sys.exit(1)
    if not out["drain_zero_loss"]:
        print(json.dumps({"error": "ring drain smoke: drain through live "
                          "fused launches lost or stranded work", **out}))
        sys.exit(1)
    return out


def telemetry_smoke() -> dict:
    """Table-telemetry regression gate (observability PR) at a 1M-key
    population:

    (a) **parity** — the fused device scan must match the numpy host oracle
        field-for-field on the seeded table (and on an 8-dev mesh slice);
    (b) **off the serving path** — the scan's only engine-thread cost is
        its LAUNCH (begin ≪ total: the device streams the table while
        serving keeps dispatching). Gated: launch ≤ 25% of scan wall and
        under 10 ms;
    (c) **<5% throughput cost at the shipped cadence** — the MARGINAL wall
        cost of one scan overlapped with serving (measured, not assumed:
        XLA-CPU shares one intra-op pool, so 'it runs on another thread'
        is exactly the claim that must be priced) divided by the default
        GUBER_TELEMETRY_INTERVAL_MS duty cycle must stay under 5%.
    """
    import queue
    import threading

    from gubernator_tpu.ops.telemetry import finish_scan, host_telemetry

    eng = LocalEngine(capacity=1 << 21, write_mode="xla")
    rng = np.random.default_rng(5)
    n = 1 << 20
    fps = np.unique(
        rng.integers(1, (1 << 63) - 1, size=n + (n >> 3), dtype=np.int64)
    )[:n]
    for i in range(0, n, 1 << 17):
        sl = fps[i : i + (1 << 17)]
        m = sl.shape[0]
        o = np.ones(m, dtype=np.int64)
        eng.install_columns(
            fp=sl, algo=np.zeros(m, np.int32), status=np.zeros(m, np.int32),
            limit=o * 100, remaining=o * 37,
            reset_time=o * (NOW + 3_600_000), duration=o * 3_600_000,
            now_ms=NOW,
        )

    # ---- (a) parity vs the host oracle (local + mesh slice)
    snap = finish_scan(eng.telemetry_begin(NOW))
    oracle = host_telemetry(np.asarray(eng.table.rows), NOW)
    for f in ("live_keys", "occupied_slots", "over_keys", "bucket_occupancy",
              "ttl_horizon", "remaining_frac", "block_fill"):
        if getattr(snap, f) != getattr(oracle, f):
            print(json.dumps({"error": f"telemetry smoke: device scan != "
                              f"host oracle in {f}"}))
            sys.exit(1)
    from gubernator_tpu.parallel import make_mesh
    from gubernator_tpu.parallel.sharded import ShardedEngine

    mesh_eng = ShardedEngine(make_mesh(8), capacity_per_shard=1 << 12,
                             write_mode="xla")
    m = 1 << 14
    o = np.ones(m, dtype=np.int64)
    mesh_eng.install_columns(
        fp=fps[:m], algo=np.zeros(m, np.int32), status=np.zeros(m, np.int32),
        limit=o * 100, remaining=o * 37, reset_time=o * (NOW + 3_600_000),
        duration=o * 3_600_000, now_ms=NOW,
    )
    msnap = finish_scan(mesh_eng.telemetry_begin(NOW))
    morcl = host_telemetry(np.asarray(mesh_eng.table.rows), NOW)
    if (msnap.live_keys != morcl.live_keys
            or msnap.bucket_occupancy != morcl.bucket_occupancy
            or sum(msnap.per_shard_live) != msnap.live_keys):
        print(json.dumps({"error": "telemetry smoke: mesh scan parity "
                          "failed"}))
        sys.exit(1)

    # ---- (b) launch ≪ total (the begin/finish split actually overlaps)
    t0 = time.perf_counter()
    pend = eng.telemetry_begin(NOW)
    t_launch = time.perf_counter() - t0
    finish_scan(pend)
    t_total = time.perf_counter() - t0
    out = {
        "live_keys": snap.live_keys,
        "scan_launch_ms": round(t_launch * 1e3, 3),
        "scan_total_ms": round(t_total * 1e3, 3),
    }
    if t_launch > 0.010 or t_launch > 0.25 * t_total:
        print(json.dumps({"error": "telemetry smoke: scan launch blocks the "
                          "engine thread (begin must enqueue, not compute)",
                          **out}))
        sys.exit(1)

    # ---- (c) marginal overlapped-scan cost vs the shipped duty cycle
    B_ = 4096
    batches = [fps[i * B_ : (i + 1) * B_] for i in range(4)]
    for f in batches:
        eng.check_columns(cols(f), now_ms=NOW)
    K = 64
    SCAN_EVERY = 8

    def window(q=None):
        t0 = time.perf_counter()
        for i in range(K):
            if q is not None and i % SCAN_EVERY == 0:
                # launch inline (the engine thread's real cost), finish on
                # the background worker — the runner's exact split
                q.put(eng.telemetry_begin(NOW))
            eng.check_columns(cols(batches[i % 4]), now_ms=NOW)
        return time.perf_counter() - t0

    base = min(window() for _ in range(3))

    def with_scans():
        q: "queue.Queue" = queue.Queue()
        done = [0]

        def worker():
            while True:
                p = q.get()
                if p is None:
                    return
                finish_scan(p)
                done[0] += 1

        t = threading.Thread(target=worker)
        t.start()
        dt = window(q)
        q.put(None)
        t.join()
        return dt, done[0]

    runs = [with_scans() for _ in range(3)]
    wt = min(r[0] for r in runs)
    n_scans = K // SCAN_EVERY
    marginal_s = max(0.0, (wt - base)) / n_scans
    # duty cycle at the shipped default cadence (config.py: 5000 ms)
    duty = marginal_s / 5.0
    out.update({
        "serve_base_s": round(base, 4),
        "serve_with_scans_s": round(wt, 4),
        "scan_marginal_ms": round(marginal_s * 1e3, 2),
        "cost_at_default_cadence": round(duty, 4),
    })
    if duty >= 0.05:
        print(json.dumps({"error": "telemetry smoke: background scan costs "
                          ">=5% of serving throughput at the default "
                          "cadence", **out}))
        sys.exit(1)
    return out


def mesh_smoke() -> dict:
    """Pod-scale mesh regression gate on a SIMULATED 2-host mesh (the 8
    forced-host-platform devices folded into 2 × 4 (host, device) rows):

    (a) **ring/collective parity** — the hand-rolled ring schedule
        (parallel/ring.py) must be byte-identical to the lax.all_to_all
        oracle through real engine traffic (responses AND canonical table
        state), duplicates included;
    (b) **batch-proportional host staging** — the 2-D topology must not
        re-grow per-dispatch host routing work (same bound as
        sharded_smoke, driven on the (host, device) mesh through the ring
        exchange);
    (c) **hierarchical GLOBAL sync convergence** — replica answers + the
        collective reconcile on the 2-host mesh must converge to the exact
        per-key totals, and the inter-slice compact codec must round-trip
        exactly (send half of the SyncGlobalsWire path)."""
    from gubernator_tpu.parallel import make_mesh
    from gubernator_tpu.parallel.global_sync import GlobalShardedEngine
    from gubernator_tpu.parallel.sharded import ShardedEngine

    mesh = make_mesh(8, hosts=2)
    out: dict = {"axes": list(mesh.axis_names)}

    # ---- (a) ring vs collective engine parity (byte-for-byte)
    kw = dict(capacity_per_shard=1 << 12, write_mode="xla",
              route="device", dedup="device")
    ring = ShardedEngine(mesh, a2a="ring", **kw)
    coll = ShardedEngine(mesh, a2a="collective", **kw)
    rng = np.random.default_rng(11)
    for step in range(3):
        n = 1024
        fp = rng.integers(1, (1 << 63) - 1, size=n, dtype=np.int64)
        if step == 2:
            fp[n // 2:] = fp[: n - n // 2]  # duplicate keys
        c = cols(fp)
        want = coll.check_columns(c, now_ms=NOW)
        got = ring.check_columns(c, now_ms=NOW)
        for f in ("status", "limit", "remaining", "reset_time", "err"):
            if not np.array_equal(getattr(want, f), getattr(got, f)):
                print(json.dumps({"error": f"mesh smoke: ring/collective "
                                  f"mismatch in {f} at step {step}"}))
                sys.exit(1)
    if not np.array_equal(np.asarray(ring.table.rows),
                          np.asarray(coll.table.rows)):
        # identical dispatch order ⇒ even slot order must agree
        print(json.dumps({"error": "mesh smoke: ring/collective table "
                          "state diverged"}))
        sys.exit(1)
    out["ring_parity"] = True

    # ---- (b) batch-proportional host staging on the 2-D topology
    big, small = 4096, 512
    fps = rng.integers(1, (1 << 63) - 1, size=big * 4, dtype=np.int64)
    batches = {
        n: [fps[i * n: (i + 1) * n] for i in range(4)] for n in (big, small)
    }
    for n in (small, big):  # compile + seed
        for f in batches[n]:
            ring.check_columns(cols(f), now_ms=NOW)

    def stage_ms_per_dispatch(n: int, k: int = 12) -> float:
        totals = StageTotals()
        with totals.watch():
            for i in range(k):
                ring.check_columns(cols(batches[n][i % 4]), now_ms=NOW)
        return sum(totals.stage_ms.values()) / max(1, totals.stage_dispatches)

    small_ms = min(stage_ms_per_dispatch(small) for _ in range(3))
    big_ms = min(stage_ms_per_dispatch(big) for _ in range(3))
    SLACK = 4.0
    ok = big_ms <= (big / small) * SLACK * max(small_ms, 1e-4)
    out["host_stage_small_ms"] = round(small_ms, 4)
    out["host_stage_big_ms"] = round(big_ms, 4)
    out["proportional"] = bool(ok)
    if not ok:
        print(json.dumps({"error": "mesh smoke: 2-host staging cost is "
                          "super-linear in batch rows", **out}))
        sys.exit(1)
    guard = check_dropped(ring.stats.dropped, max(1, ring.stats.checks))
    if guard:
        print(json.dumps({"error": f"mesh smoke drop storm: {guard}", **out}))
        sys.exit(1)

    # ---- (c) hierarchical GLOBAL sync convergence on the 2-host mesh
    geng = GlobalShardedEngine(mesh, a2a="ring", sync_out=64, **kw)
    m = 96
    gfp = rng.integers(1, (1 << 63) - 1, size=m, dtype=np.int64)
    hits_total = np.zeros(m, dtype=np.int64)
    for step in range(4):  # rotating homes: hits land on several replicas
        h = rng.integers(1, 4, size=m).astype(np.int64)
        hits_total += h
        c = cols(gfp)._replace(
            hits=h, behavior=np.full(m, 2, dtype=np.int32)  # GLOBAL
        )
        rc = geng.check_columns(c, now_ms=NOW)
        if (rc.err != 0).any():
            print(json.dumps({"error": "mesh smoke: GLOBAL serve error",
                              **out}))
            sys.exit(1)
    geng.sync(now_ms=NOW)
    if geng.has_pending():
        print(json.dumps({"error": "mesh smoke: sync left pending hits",
                          **out}))
        sys.exit(1)
    probe = cols(gfp)._replace(
        hits=np.zeros(m, dtype=np.int64),
        behavior=np.full(m, 2, dtype=np.int32),
    )
    # every rotating home's replica must answer the reconciled total
    for _ in range(3):
        rc = geng.check_columns(probe, now_ms=NOW)
        want = (1 << 20) - hits_total
        if not np.array_equal(np.asarray(rc.remaining), want):
            print(json.dumps({"error": "mesh smoke: hierarchical GLOBAL "
                              "sync did not converge", **out}))
            sys.exit(1)
    out["global_sync_rounds"] = geng.global_stats.sync_rounds
    out["global_converged"] = True

    # inter-slice codec half: lane pack → item decode must be exact
    from gubernator_tpu.proto import gubernator_pb2 as pb
    from gubernator_tpu.service.wire import sync_wire_items, sync_wire_pb

    pairs = [
        (f"ms_k{i}", pb.RateLimitReq(
            name="ms", unique_key=f"k{i}", hits=(1 << 19) + i, limit=100,
            duration=60_000, algorithm=i % 2, behavior=2, created_at=NOW,
            burst=100 if i % 2 else 0,
        ))
        for i in range(8)
    ]
    req = sync_wire_pb(pairs, "ci")
    if req is None:
        print(json.dumps({"error": "mesh smoke: sync codec refused an "
                          "encodable batch", **out}))
        sys.exit(1)
    back = sync_wire_items(req)
    for (_k, a), b in zip(pairs, back):
        if (a.name, a.unique_key, a.hits, a.limit, a.duration, a.algorithm,
                a.created_at) != (b.name, b.unique_key, b.hits, b.limit,
                                  b.duration, b.algorithm, b.created_at):
            print(json.dumps({"error": "mesh smoke: sync codec roundtrip "
                              "mismatch", **out}))
            sys.exit(1)
    out["wire_sync_codec"] = True
    return out


def durability_smoke() -> dict:
    """Incremental-checkpoint regression gate (docs/durability.md):

    (a) **delta cost ∝ dirty rows, not table size** — the same fixed write
        rate into a 1M-key and a 10M-key table must produce delta frames
        within 2× of each other (bytes AND rows), each ≥3× smaller than
        the full base snapshot at 10M keys (in practice ~100×);
    (b) **warm-restart replay parity** — base + delta frames replayed
        through the conservative merge reconstruct the source's live rows
        byte-for-byte (and replay wall is reported against re-seeding);
    (c) **background loop < 5% of serving** — the MARGINAL wall cost of
        one overlapped take→extract→append cycle (the runner's exact
        engine-thread-launch / off-thread-fetch split, measured against
        the same serving window without it) divided by a 1 s reference
        cadence must stay under 5% (telemetry-smoke methodology).
    """
    import queue
    import tempfile
    import threading

    from gubernator_tpu.ops.checkpoint import (
        EpochTracker, extract_begin, finish_extract,
    )
    from gubernator_tpu.store import (
        DeltaLog, encode_delta_frame, fps_from_slots,
    )
    from gubernator_tpu.ops.table2 import decode_live_slots

    rng = np.random.default_rng(13)
    WRITE_KEYS = 1 << 14  # fixed write rate: 16K distinct keys per window
    fps = np.unique(rng.integers(1, (1 << 63) - 1, size=WRITE_KEYS * 2,
                                 dtype=np.int64))[:WRITE_KEYS]

    def dcols(fp: np.ndarray, hits: int = 1) -> RequestColumns:
        # algo keyed off the FP (not batch position, like the shared
        # cols()): a real key keeps one algorithm across waves, and an
        # algo flip would make merge2's cross-semantics min legitimately
        # tighter than the serving path — conservative, but not parity
        return cols(fp)._replace(
            algo=(fp & 1).astype(np.int32),
            hits=np.full(fp.shape[0], hits, dtype=np.int64),
        )

    # ---- (a) fixed write rate into 1M vs 10M-key tables
    out: dict = {}
    deltas = {}
    engines = {}
    for label, cap in (("1M", 1_000_000), ("10M", 10_000_000)):
        eng = LocalEngine(capacity=cap, write_mode="xla")
        eng.ckpt = EpochTracker(eng.table.rows.shape[0])
        for i in range(4):
            eng.check_columns(dcols(fps[i::4]), now_ms=NOW)
        _, gids = eng.ckpt.take()
        t0 = time.perf_counter()
        e_fps, e_slots = finish_extract(
            extract_begin(eng.table.rows, gids, eng.ckpt.blk, NOW)
        )
        extract_s = time.perf_counter() - t0
        frame = encode_delta_frame(1, NOW, e_slots)
        full = int(np.asarray(eng.table.rows).nbytes)
        deltas[label] = dict(
            dirty_blocks=int(gids.shape[0]), rows=int(e_fps.shape[0]),
            delta_bytes=len(frame), full_bytes=full,
            extract_s=round(extract_s, 4),
            reduction=round(full / len(frame), 1),
        )
        engines[label] = (eng, e_fps, e_slots)
    out["delta"] = deltas
    ratio = deltas["10M"]["delta_bytes"] / max(deltas["1M"]["delta_bytes"], 1)
    out["delta_bytes_ratio_10M_vs_1M"] = round(ratio, 3)
    if ratio > 2.0:
        print(json.dumps({"error": "durability smoke: delta bytes grew "
                          "with table size at a fixed write rate", **out}))
        sys.exit(1)
    if deltas["10M"]["reduction"] < 3.0:
        print(json.dumps({"error": "durability smoke: delta frame is not "
                          ">=3x smaller than the 10M full snapshot", **out}))
        sys.exit(1)

    # ---- (b) warm-restart replay parity (1M table)
    src, e_fps, e_slots = engines["1M"]
    base = src.snapshot()
    src.check_columns(dcols(fps[: 1 << 12], hits=3), now_ms=NOW + 5)
    _, gids = src.ckpt.take()
    d_fps, d_slots = finish_extract(
        extract_begin(src.table.rows, gids, src.ckpt.blk, NOW + 5)
    )
    dst = LocalEngine(capacity=1_000_000, write_mode="xla")
    t0 = time.perf_counter()
    dst.restore(base)
    dst.merge_rows(d_fps, d_slots, now_ms=NOW + 5)
    replay_s = time.perf_counter() - t0

    def live_map(eng):
        slots, fp, _ = decode_live_slots(np.asarray(eng.table.rows), NOW + 5)
        return {int(f): s.tobytes() for f, s in zip(fp, slots)}

    if live_map(dst) != live_map(src):
        print(json.dumps({"error": "durability smoke: base+delta replay "
                          "did not reconstruct the live rows", **out}))
        sys.exit(1)
    if fps_from_slots(d_slots).shape[0] != d_fps.shape[0]:
        print(json.dumps({"error": "durability smoke: frame fps decode "
                          "mismatch", **out}))
        sys.exit(1)
    out["replay_s"] = round(replay_s, 4)
    out["replay_rows"] = int(d_fps.shape[0]) + WRITE_KEYS

    # ---- (c) marginal overlapped checkpoint cost vs a 1 s cadence
    eng = engines["1M"][0]
    tmp = tempfile.mkdtemp()
    log = DeltaLog(os.path.join(tmp, "smoke.delta"))
    B_ = 4096
    batches = [fps[i * B_: (i + 1) * B_] for i in range(4)]
    for f in batches:
        eng.check_columns(dcols(f), now_ms=NOW)
    K = 48
    SCAN_EVERY = 8

    def window(q=None):
        t0 = time.perf_counter()
        for i in range(K):
            if q is not None and i % SCAN_EVERY == 0:
                # take+launch inline (the engine thread's real cost),
                # fetch+append on the background worker — the runner's
                # exact split (EngineRunner.checkpoint_extract)
                epoch, gids = eng.ckpt.take()
                q.put((epoch, extract_begin(
                    eng.table.rows, gids, eng.ckpt.blk, NOW)))
            eng.check_columns(dcols(batches[i % 4]), now_ms=NOW)
        return time.perf_counter() - t0

    base_s = min(window() for _ in range(3))

    def with_ckpt():
        q: "queue.Queue" = queue.Queue()

        def worker():
            while True:
                item = q.get()
                if item is None:
                    return
                epoch, pend = item
                _f, slots = finish_extract(pend)
                log.append(epoch, NOW, slots)

        t = threading.Thread(target=worker)
        t.start()
        dt = window(q)
        q.put(None)
        t.join()
        return dt

    wt = min(with_ckpt() for _ in range(3))
    marginal_s = max(0.0, wt - base_s) / (K // SCAN_EVERY)
    duty = marginal_s / 1.0  # 1 s reference cadence (docs/durability.md)
    out.update({
        "serve_base_s": round(base_s, 4),
        "serve_with_ckpt_s": round(wt, 4),
        "ckpt_marginal_ms": round(marginal_s * 1e3, 2),
        "cost_at_1s_cadence": round(duty, 4),
    })
    if duty >= 0.05:
        print(json.dumps({"error": "durability smoke: background "
                          "checkpointing costs >=5% of serving at a 1 s "
                          "cadence", **out}))
        sys.exit(1)
    return out


def algo_smoke() -> dict:
    """Scenario-breadth regression gate (ISSUE 10):

    (a) **per-algorithm oracle parity at 1M live keys** — GCRA, sliding
        window and concurrency leases must match the pure-Python oracles
        decision-for-decision against a table already holding ~1M live
        rows (the headline-geometry analog CI can afford);
    (b) **cascade single-dispatch engaged** — an encodable 3-level cascade
        batch rides the compact wire in ONE engine dispatch (zero
        full-width fallbacks, in-trace verdict fold);
    (c) **cascade-vs-sequential e2e ratio** — through a loopback daemon, N
        3-level cascade checks (one RPC, one dispatch each) must clear
        ≥ 2.5× the checks/s of the same N checks issued as three DEPENDENT
        single-level round trips (the deployment pattern cascades replace).
    """
    import asyncio

    from tests.oracle.algos import GcraOracle, LeaseOracle, SlidingWindowOracle

    from gubernator_tpu.hashing import fingerprint
    from gubernator_tpu.ops import wire as wire_mod
    from gubernator_tpu.ops.batch import pack_columns
    from gubernator_tpu.types import Algorithm

    out: dict = {}
    rng = np.random.default_rng(31)

    def acols(fps, algo, hits, limit, dur, levels=None, now=NOW):
        n = fps.shape[0]
        return RequestColumns(
            fp=fps.astype(np.int64),
            algo=np.asarray(algo, dtype=np.int32) if np.ndim(algo) else
            np.full(n, algo, dtype=np.int32),
            behavior=np.array(
                [lvl << 8 for lvl in (levels or [0] * n)], dtype=np.int32
            ),
            hits=np.asarray(hits, dtype=np.int64) if np.ndim(hits) else
            np.full(n, hits, dtype=np.int64),
            limit=np.full(n, limit, dtype=np.int64),
            burst=np.zeros(n, dtype=np.int64),
            duration=np.full(n, dur, dtype=np.int64),
            created_at=np.full(n, now, dtype=np.int64),
            err=np.zeros(n, dtype=np.int8),
        )

    # ---- (a) parity at ~1M live keys
    eng = LocalEngine(capacity=1 << 20, write_mode="xla", wire="compact")
    seed_fps = []
    seed_b = 1 << 16
    for i in range(16):  # ~1M distinct live rows, algorithm-striped
        fps = rng.integers(1, (1 << 63) - 1, size=seed_b, dtype=np.int64)
        seed_fps.append(fps)
        algos = (np.arange(seed_b) % 4).astype(np.int32)
        algos[algos == 1] = 4  # token/gcra/window/lease stripes (no leaky f64)
        eng.check_columns(
            acols(fps, 0, 1, 1 << 20, 3_600_000)._replace(algo=algos),
            now_ms=NOW,
        )
    live = eng.live_count(now_ms=NOW)
    out["seeded_live_keys"] = int(live)

    oracles = {
        int(Algorithm.GCRA): GcraOracle(),
        int(Algorithm.SLIDING_WINDOW): SlidingWindowOracle(),
        int(Algorithm.CONCURRENCY_LEASE): LeaseOracle(),
    }
    mismatches = 0
    t = NOW
    # parity keys from UNCONTESTED buckets: the near-capacity seed makes
    # some buckets overflow their 8 slots, and GCRA/lease parity keys (exp
    # near now by design) would be the soonest-expiring eviction victims —
    # eviction behavior is the claim layer's contract (tests/test_kernel2),
    # this gate pins the ALGORITHM math against the 1M-live geometry
    NB = int(eng.table.rows.shape[0])
    bucket_load = np.bincount(
        (np.concatenate(seed_fps) % NB).astype(np.int64), minlength=NB
    )

    def calm_keys(a, want=512):
        picked, i = [], 0
        while len(picked) < want:
            fp = fingerprint("algsm", f"{a}k{i}")
            if bucket_load[fp % NB] <= 4:
                picked.append(fp)
            i += 1
        return np.array(picked, dtype=np.int64)

    keys = {a: calm_keys(a) for a in oracles}
    for step in range(6):
        t += int(rng.integers(100, 2_000))
        for a, oracle in oracles.items():
            hits = rng.integers(-2 if a == 4 else 0, 4, size=512)
            rc = eng.check_columns(
                acols(keys[a], a, hits, 16, 8_000, now=t), now_ms=t
            )
            for j in range(512):
                st, rem, reset = oracle.check(
                    int(keys[a][j]), t, int(hits[j]), 16, 8_000
                )
                if (int(rc.status[j]), int(rc.remaining[j]),
                        int(rc.reset_time[j])) != (st, rem, reset):
                    mismatches += 1
    out["parity_mismatches"] = mismatches
    if mismatches:
        print(json.dumps({"error": "algo smoke: device/oracle parity "
                          "mismatch at 1M keys", **out}))
        sys.exit(1)

    # ---- (b) cascade single-dispatch, compact wire, zero fallbacks
    def cascade_batch(n_casc, now, tag="c"):
        # distinct keys per level: the single-device engine host-plans
        # duplicate (fp, level) groups into sequential passes for exact
        # semantics — shared tenant/global keys aggregate to one dispatch
        # on the mesh engines' in-trace dedup path (tests/test_algorithms
        # test_same_level_cascade_rows_aggregate)
        rows = []
        for i in range(n_casc):
            rows.extend([
                (fingerprint("casc", f"{tag}u{i}"), 0, 0, 100),
                (fingerprint("casc", f"{tag}t{i}"), int(Algorithm.SLIDING_WINDOW), 1, 10_000),
                (fingerprint("casc", f"{tag}g{i}"), int(Algorithm.GCRA), 2, 1 << 20),
            ])
        n = len(rows)
        return RequestColumns(
            fp=np.array([r[0] for r in rows], dtype=np.int64),
            algo=np.array([r[1] for r in rows], dtype=np.int32),
            behavior=np.array([r[2] << 8 for r in rows], dtype=np.int32),
            hits=np.ones(n, dtype=np.int64),
            limit=np.array([r[3] for r in rows], dtype=np.int64),
            burst=np.zeros(n, dtype=np.int64),
            duration=np.full(n, 60_000, dtype=np.int64),
            created_at=np.full(n, now, dtype=np.int64),
            err=np.zeros(n, dtype=np.int8),
        )

    ceng = LocalEngine(capacity=1 << 15, write_mode="xla", wire="compact")
    cb = cascade_batch(64, NOW)
    hb, errs = pack_columns(cb, NOW)
    enc = wire_mod.wire_encodable(hb, wire_mod.pick_base(hb))
    d0 = ceng.stats.dispatches
    rc = ceng.check_columns(cb, now_ms=NOW)
    out["cascade_encodable"] = bool(enc)
    out["cascade_dispatches"] = int(ceng.stats.dispatches - d0)
    if not enc or ceng.stats.dispatches - d0 != 1 or rc.err.any():
        print(json.dumps({"error": "algo smoke: encodable 3-level cascade "
                          "did not resolve in one compact dispatch", **out}))
        sys.exit(1)

    # ---- (c) cascade vs three dependent sequential checks, e2e loopback
    from gubernator_tpu.config import BehaviorConfig, DaemonConfig
    from gubernator_tpu.proto import gubernator_pb2 as pb
    from gubernator_tpu.service.daemon import Daemon

    def creq(i, now):
        r = pb.RateLimitReq(name="cas", unique_key=f"u{i}", hits=1,
                            limit=1 << 20, duration=60_000, created_at=now)
        r.cascade.add(name="cas_t", unique_key=f"t{i % 8}", limit=1 << 20,
                      duration=60_000, algorithm=pb.SLIDING_WINDOW)
        r.cascade.add(name="cas_g", unique_key="all", limit=1 << 20,
                      duration=60_000, algorithm=pb.GCRA)
        return r

    def sreqs(i, now):
        return [
            pb.RateLimitReq(name="cas", unique_key=f"u{i}", hits=1,
                            limit=1 << 20, duration=60_000, created_at=now),
            pb.RateLimitReq(name="cas_t", unique_key=f"t{i % 8}", hits=1,
                            limit=1 << 20, duration=60_000, created_at=now,
                            algorithm=pb.SLIDING_WINDOW),
            pb.RateLimitReq(name="cas_g", unique_key="all", hits=1,
                            limit=1 << 20, duration=60_000, created_at=now,
                            algorithm=pb.GCRA),
        ]

    N_CHECKS, WORKERS = 256, 32

    async def run_e2e():
        d = await Daemon.spawn(DaemonConfig(
            grpc_address="127.0.0.1:0", http_address="",
            cache_size=1 << 15,
            behaviors=BehaviorConfig(batch_wait_ms=0.5),
        ))

        async def casc_worker(w, now):
            for i in range(w, N_CHECKS, WORKERS):
                data = pb.GetRateLimitsReq(
                    requests=[creq(i, now)]
                ).SerializeToString()
                await d.get_rate_limits_raw(data)

        async def seq_worker(w, now):
            for i in range(w, N_CHECKS, WORKERS):
                # three DEPENDENT round trips — each level waits for the
                # previous verdict, the pattern a cascade replaces
                for r in sreqs(i, now):
                    data = pb.GetRateLimitsReq(
                        requests=[r]
                    ).SerializeToString()
                    await d.get_rate_limits_raw(data)

        async def wall(worker, now) -> float:
            t0 = time.perf_counter()
            await asyncio.gather(*(worker(w, now) for w in range(WORKERS)))
            return time.perf_counter() - t0

        # warm both shapes, then best-of-3 each
        await wall(casc_worker, NOW)
        await wall(seq_worker, NOW)
        casc = min([await wall(casc_worker, NOW + 1 + k) for k in range(3)])
        seq = min([await wall(seq_worker, NOW + 10 + k) for k in range(3)])
        await d.close()
        return casc, seq

    casc_s, seq_s = asyncio.run(run_e2e())
    ratio = seq_s / max(casc_s, 1e-9)
    out["cascade_wall_s"] = round(casc_s, 4)
    out["sequential_wall_s"] = round(seq_s, 4)
    out["cascade_speedup"] = round(ratio, 2)
    if ratio < 2.5:
        print(json.dumps({"error": "algo smoke: 3-level cascade under 2.5x "
                          "the checks/s of three sequential round trips",
                          **out}))
        sys.exit(1)
    return out


def layout_smoke() -> dict:
    """Packed slot-layout regression gate (PR 11):

    (a) **bytes/slot** — the packed layouts must hold ≥1.8× fewer bytes
        per slot than the full layout (measured on the actual table
        arrays, not the descriptor constants), i.e. bytes/slot ≤ 0.55×;
    (b) **decision parity at scale** — a gcra32 (and token32) table must
        match the full-layout oracle decision-for-decision over ~1M-key
        traffic with duplicates and time steps (the CPU-CI proxy for the
        TPU 100M-key acceptance run);
    (c) **checkpoint/delta bytes shrink proportionally** — the same dirty
        set's delta frame under the packed layout must be ≤ 0.6× the
        full-layout frame's bytes;
    (d) **full stays bit-identical** — layout="full" and the pre-layout
        default produce byte-equal tables for identical traffic.
    """
    from gubernator_tpu.ops.checkpoint import (
        EpochTracker, extract_begin, finish_extract,
    )
    from gubernator_tpu.store import encode_delta_frame

    rng = np.random.default_rng(17)
    out: dict = {}

    # ---- (d) full byte-identity pin
    fp0 = rng.integers(1, (1 << 63) - 1, size=B, dtype=np.int64)
    e_full = LocalEngine(capacity=1 << 14, write_mode="xla", layout="full")
    e_def = LocalEngine(capacity=1 << 14, write_mode="xla")
    for t in (NOW, NOW + 1000):
        e_full.check_columns(cols(fp0), now_ms=t)
        e_def.check_columns(cols(fp0), now_ms=t)
    if not np.array_equal(np.asarray(e_full.table.rows),
                          np.asarray(e_def.table.rows)):
        print(json.dumps({"error": "layout smoke: layout=full diverged "
                          "from the pre-layout default table bytes"}))
        sys.exit(1)
    out["full_bit_identical"] = True

    # ---- (a)+(b) packed parity over a ~1M-key population
    def pcols(fp, algo, hits, t):
        n = fp.shape[0]
        return cols(fp)._replace(
            algo=np.full(n, algo, dtype=np.int32),
            hits=np.asarray(hits, dtype=np.int64),
            limit=np.full(n, 64, dtype=np.int64),
            duration=np.full(n, 60_000, dtype=np.int64),
            created_at=np.full(n, t, dtype=np.int64),
        )

    n_seed = 1 << 20
    seed_fps = np.unique(rng.integers(
        1, (1 << 63) - 1, size=n_seed + (n_seed >> 3), dtype=np.int64
    ))[:n_seed]
    for lay, algo in (("gcra32", 2), ("token32", 0)):
        full_e = LocalEngine(capacity=1 << 21, write_mode="xla",
                             layout="full")
        pack_e = LocalEngine(capacity=1 << 21, write_mode="xla", layout=lay)
        bytes_full = np.asarray(full_e.table.rows).nbytes
        bytes_pack = np.asarray(pack_e.table.rows).nbytes
        ratio = bytes_pack / bytes_full
        out[f"{lay}_bytes_per_slot_ratio"] = round(ratio, 3)
        out[f"{lay}_live_keys_per_gb_gain"] = round(1.0 / ratio, 2)
        if ratio > 0.55:
            print(json.dumps({"error": f"layout smoke: {lay} bytes/slot "
                              f"ratio {ratio:.3f} above the 0.55 floor",
                              **out}))
            sys.exit(1)
        t = NOW
        bsz = 1 << 16
        for i in range(0, n_seed, bsz):  # seed ~1M live keys
            sl = seed_fps[i:i + bsz]
            h = np.ones(sl.shape[0], dtype=np.int64)
            full_e.check_columns(pcols(sl, algo, h, t), now_ms=t)
            pack_e.check_columns(pcols(sl, algo, h, t), now_ms=t)
        mism = 0
        for step in range(4):  # re-hit a slice, duplicates included
            t += int(rng.integers(100, 5_000))
            sel = seed_fps[rng.integers(0, n_seed, size=4096)]
            h = rng.integers(0, 4, size=4096)
            a = full_e.check_columns(pcols(sel, algo, h, t), now_ms=t)
            b = pack_e.check_columns(pcols(sel, algo, h, t), now_ms=t)
            for f in ("status", "remaining", "reset_time", "err"):
                mism += int((np.asarray(getattr(a, f))
                             != np.asarray(getattr(b, f))).sum())
        out[f"{lay}_parity_mismatches"] = mism
        out[f"{lay}_live"] = pack_e.live_count(t)
        if mism or pack_e.stats.layout_migrations:
            print(json.dumps({"error": f"layout smoke: {lay} parity vs the "
                              "full-layout oracle failed", **out}))
            sys.exit(1)
        if pack_e.live_count(t) != full_e.live_count(t):
            print(json.dumps({"error": f"layout smoke: {lay} live-key count "
                              "diverged from full", **out}))
            sys.exit(1)

        # ---- (c) checkpoint bytes shrink with the layout
        if lay == "gcra32":
            for e, label in ((full_e, "full"), (pack_e, "packed")):
                e.ckpt = EpochTracker(e.table.rows.shape[0])
                e.check_columns(
                    pcols(seed_fps[: 1 << 14],
                          algo, np.ones(1 << 14, dtype=np.int64), t),
                    now_ms=t,
                )
                _, gids = e.ckpt.take()
                _f, slots = finish_extract(extract_begin(
                    e.table.rows, gids, e.ckpt.blk, t, layout=e.table.layout
                ))
                frame = encode_delta_frame(1, t, slots, layout=e.table.layout)
                out[f"delta_bytes_{label}"] = len(frame)
            dratio = out["delta_bytes_packed"] / max(out["delta_bytes_full"], 1)
            out["delta_bytes_ratio"] = round(dratio, 3)
            if dratio > 0.6:
                print(json.dumps({"error": "layout smoke: packed delta "
                                  "frame not proportionally smaller", **out}))
                sys.exit(1)
    return out


def region_smoke() -> dict:
    """Multi-region active-active regression gate (docs/robustness.md
    "Multi-region active-active"; ISSUE 12 acceptance):

    (a) **exact convergence** — a two-region loopback cluster with
        concurrent hits on K keys in BOTH regions converges every key to
        the exact union of hits, within a bounded number of sync
        intervals;
    (b) **bounded partition over-admission** — with the inter-region link
        blackholed under live traffic, each region keeps serving locally
        with zero request errors, total admissions stay ≤ Σ per-region
        limits, and the over-admission beyond one region's limit stays ≤
        the sum of unreplicated deltas (the documented bound); after heal
        both regions reconverge;
    (c) **compact-wire engagement** — encodable replication traffic rides
        the SyncRegionsWire merge codec with ZERO proto fallbacks.
    """
    import asyncio

    from gubernator_tpu.config import BehaviorConfig
    from gubernator_tpu.proto import gubernator_pb2 as pb
    from gubernator_tpu.types import Behavior
    from tests.cluster import Cluster, wait_for

    MR = int(Behavior.MULTI_REGION)
    SYNC_S = 0.025
    out: dict = {}

    def mr(key, hits, limit=100):
        return pb.RateLimitReq(
            name="rs", unique_key=key, hits=hits, limit=limit,
            duration=600_000, behavior=MR,
        )

    async def run():
        beh = BehaviorConfig(
            batch_wait_ms=1.0,
            global_sync_wait_ms=SYNC_S * 1e3,
            batch_timeout_ms=5000.0,
            global_timeout_ms=300.0,
            region_requeue_retries=100_000,  # ride out the partition
            peer_breaker_errors=3,
            peer_breaker_backoff_base_ms=200.0,
            peer_breaker_backoff_cap_ms=1_000.0,
        )
        c = await Cluster.start(
            2, dcs=["dc-a", "dc-b"], chaos=True, behaviors=beh
        )
        a, b = c.daemons
        try:
            # ---- (a) exact per-key convergence of totals
            rng = np.random.default_rng(7)
            K = 64
            ha = rng.integers(1, 30, size=K)
            hb = rng.integers(1, 30, size=K)
            ra = await a.get_rate_limits(
                [mr(f"k{i}", int(ha[i])) for i in range(K)]
            )
            rb = await b.get_rate_limits(
                [mr(f"k{i}", int(hb[i])) for i in range(K)]
            )
            if any(r.error for r in ra + rb):
                print(json.dumps({"error": "region smoke: serve error",
                                  **out}))
                sys.exit(1)
            want = [100 - int(ha[i] + hb[i]) for i in range(K)]
            t0 = time.perf_counter()

            async def conv():
                xa = await a.get_rate_limits(
                    [mr(f"k{i}", 0) for i in range(K)]
                )
                xb = await b.get_rate_limits(
                    [mr(f"k{i}", 0) for i in range(K)]
                )
                return all(
                    xa[i].remaining == xb[i].remaining == want[i]
                    for i in range(K)
                )

            try:
                await wait_for(conv, timeout_s=20)
            except TimeoutError:
                print(json.dumps({"error": "region smoke: two-region "
                                  "totals did not converge to the exact "
                                  "union", **out}))
                sys.exit(1)
            wall = time.perf_counter() - t0
            out["converged_keys"] = K
            out["convergence_wall_s"] = round(wall, 3)
            out["convergence_sync_intervals"] = round(wall / SYNC_S, 1)

            # ---- (c) compact-wire engagement, zero fallbacks
            out["wire_sent"] = (
                a.region_manager.wire_sent + b.region_manager.wire_sent
            )
            out["wire_fallback"] = (
                a.region_manager.wire_fallback
                + b.region_manager.wire_fallback
            )
            out["rows_merged"] = (
                a.region_manager.rows_merged + b.region_manager.rows_merged
            )
            if out["wire_sent"] == 0 or out["wire_fallback"] != 0:
                print(json.dumps({"error": "region smoke: encodable "
                                  "traffic did not ride the compact merge "
                                  "codec", **out}))
                sys.exit(1)
            # steady-state replication entries (strings + slots only on a
            # key's FIRST batch) must stay a fixed 40 B/row (32 B lane+hits
            # + 8 B cumulative dedup counter) — smaller than the classic
            # proto fallback for the same items
            from gubernator_tpu.proto import peers_pb2 as peers_pb
            from gubernator_tpu.service.wire import (
                split_region_encodable, sync_regions_pb,
            )

            bp = [(f"rs_b{i}", pb.RateLimitReq(
                name="rs", unique_key=f"tenant-{i:03d}/user-{i:08d}",
                hits=3, limit=100, duration=600_000, behavior=MR,
                created_at=a.now_ms(),
            )) for i in range(256)]
            e2, f2 = split_region_encodable(bp)
            steady = sync_regions_pb(
                e2, "ci", "dc-a",
                detail_rows=np.zeros(len(e2), dtype=bool),
                cums=np.arange(1, len(e2) + 1, dtype=np.int64) * 1000,
            ).ByteSize() / len(e2)
            proto_b = peers_pb.GetPeerRateLimitsReq(
                requests=[it for _k, it in bp]
            ).ByteSize() / len(bp)
            out["steady_state_bytes_per_row"] = round(steady, 1)
            out["proto_bytes_per_row"] = round(proto_b, 1)
            if f2 or steady > 44 or steady >= proto_b:
                print(json.dumps({"error": "region smoke: steady-state "
                                  "codec rows are not proportionally "
                                  "smaller than the proto fallback",
                                  **out}))
                sys.exit(1)

            # ---- (b) partition: degraded-local + bounded over-admission
            LIMIT = 50

            def pk(hits):
                return pb.RateLimitReq(
                    name="rs", unique_key="part", hits=hits, limit=LIMIT,
                    duration=600_000, behavior=MR,
                )

            for p in c.proxies:
                p.set_mode("blackhole")
            t0 = time.monotonic()
            admitted = errors = 0
            while time.monotonic() - t0 < 1.0:  # ≥ 40 sync intervals
                for d in (a, b):
                    r = (await d.get_rate_limits([pk(1)]))[0]
                    if r.error:
                        errors += 1
                    elif r.status == pb.UNDER_LIMIT:
                        admitted += 1
                await asyncio.sleep(0.005)
            out["partition_admitted"] = admitted
            out["partition_errors"] = errors
            if errors:
                print(json.dumps({"error": "region smoke: request errors "
                                  "during the partition", **out}))
                sys.exit(1)
            if admitted > 2 * LIMIT:
                print(json.dumps({"error": "region smoke: partition "
                                  "admissions exceeded Σ per-region "
                                  "limits", **out}))
                sys.exit(1)
            unreplicated = 0
            for d in (a, b):
                for pend in d.region_manager._pending.values():
                    it = pend.get("rs_part")
                    if it is not None:
                        unreplicated += it.hits
            over = max(0, admitted - LIMIT)
            out["partition_over_admission"] = over
            out["partition_unreplicated_deltas"] = int(unreplicated)
            if over > unreplicated:
                print(json.dumps({"error": "region smoke: over-admission "
                                  "exceeded the documented Σ-unreplicated-"
                                  "deltas bound", **out}))
                sys.exit(1)

            # ---- heal: backlog drains through the merge, reconverge
            for p in c.proxies:
                p.heal()

            async def healed():
                xa = (await a.get_rate_limits([pk(0)]))[0].remaining
                xb = (await b.get_rate_limits([pk(0)]))[0].remaining
                return xa == xb == max(0, LIMIT - admitted)

            try:
                await wait_for(healed, timeout_s=20, interval_s=0.1)
            except TimeoutError:
                print(json.dumps({"error": "region smoke: regions did not "
                                  "reconverge after heal", **out}))
                sys.exit(1)
            out["healed"] = True

            async def drained():
                return max(
                    a.region_manager.oldest_delta_age_s(),
                    b.region_manager.oldest_delta_age_s(),
                ) == 0.0

            try:
                await wait_for(drained, timeout_s=10, interval_s=0.1)
            except TimeoutError:
                print(json.dumps({"error": "region smoke: staleness did "
                                  "not drain to 0 after heal", **out}))
                sys.exit(1)
            out["staleness_drained"] = True
        finally:
            await c.stop()

    asyncio.run(run())
    return out


def lease_smoke() -> dict:
    """Edge quota-lease regression gate (ISSUE 13 acceptance):

    (a) **fan-in cut ≥50×** — a LocalLimiter under LEASE CHURN (short
        TTL, adaptive grants, live renew/return traffic) must serve
        client-side admissions at ≥50× the e2e per-check RPC rate
        through the same loopback daemon;
    (b) **over-admission bound** — total admissions ≤ limit + Σ
        outstanding leases, asserted exactly, INCLUDING across a daemon
        kill -9 + checkpoint-backed warm restart (the restarted daemon
        remembers leased consumption; the edge keeps only its
        outstanding slice);
    (c) **TTL reclamation** — an unrenewed lease's ledger tokens flow
        back by TTL eviction alone (fresh acquires regain the full cap)
        while the real-limit consumption stays (conservative).
    """
    import asyncio
    import tempfile

    from gubernator_tpu.client import V1Client
    from gubernator_tpu.edge import LocalLimiter
    from gubernator_tpu.proto import gubernator_pb2 as pb
    from tests.cluster import Cluster, wait_for

    MINUTE = 60_000
    out: dict = {}

    async def run():
        tmp = tempfile.mkdtemp()
        c = await Cluster.start(
            1,
            checkpoint_path=os.path.join(tmp, "ckpt.bin"),
            checkpoint_interval_ms=25.0,
        )
        d = c.daemons[0]
        try:
            cl = V1Client(d.conf.grpc_address)

            # ---- per-check RPC baseline: 8 concurrent single-item
            # checkers through the full front door (the fan-in every
            # check pays without leases)
            rpc_n = 0

            async def rpc_worker(i, deadline):
                nonlocal rpc_n
                while time.perf_counter() < deadline:
                    r = (await cl.get_rate_limits([pb.RateLimitReq(
                        name="rpcrate", unique_key=f"u{i}", hits=1,
                        limit=1 << 30, duration=MINUTE,
                    )])).responses[0]
                    assert not r.error
                    rpc_n += 1

            t0 = time.perf_counter()
            deadline = t0 + 0.4
            await asyncio.gather(*(rpc_worker(i, deadline)
                                   for i in range(8)))
            rpc_rate = rpc_n / (time.perf_counter() - t0)
            out["per_check_rpc_per_sec"] = round(rpc_rate, 1)

            # ---- client-side admission rate under lease churn: short
            # TTL + modest initial grant force live renew/return traffic
            # while 2 threads hammer the local budget
            lim = LocalLimiter(
                d.conf.grpc_address, "edge", "hot", limit=1 << 24,
                duration=MINUTE, ttl_ms=200, initial_grant=4096,
            )
            await lim.start()
            stop = [False]
            counts = [0, 0]

            def admit_worker(i):
                while not stop[0]:
                    if lim.allow():
                        counts[i] += 1
                    else:
                        time.sleep(0.0005)

            loop = asyncio.get_running_loop()
            t0 = time.perf_counter()
            futs = [loop.run_in_executor(None, admit_worker, i)
                    for i in range(2)]
            await asyncio.sleep(0.6)
            stop[0] = True
            await asyncio.gather(*futs)
            wall = time.perf_counter() - t0
            local_rate = sum(counts) / wall
            out["client_admissions_per_sec"] = round(local_rate, 1)
            out["lease_renewals"] = lim.stats.grants
            out["grant_sizes"] = lim.stats.grant_sizes[:12]
            out["fanin_cut_x"] = round(local_rate / max(rpc_rate, 1), 1)
            if lim.stats.grants < 2:
                print(json.dumps({"error": "lease smoke: no lease churn "
                                  "(renewals did not fire)", **out}))
                sys.exit(1)
            if local_rate < 50 * rpc_rate:
                print(json.dumps({"error": "lease smoke: client-side "
                                  "admission rate under lease churn is "
                                  "below 50x the per-check RPC rate",
                                  **out}))
                sys.exit(1)
            # no-crash over-admission: grants pre-consume, so admissions
            # can never exceed server-side consumption
            await lim.close()
            srv = (await cl.get_rate_limits([pb.RateLimitReq(
                name="edge", unique_key="hot", hits=0, limit=1 << 24,
                duration=MINUTE,
            )])).responses[0]
            consumed = (1 << 24) - srv.remaining
            out["admitted_total"] = lim.stats.local_admits
            out["consumed_server_side"] = int(consumed)
            if lim.stats.local_admits > consumed:
                print(json.dumps({"error": "lease smoke: admissions "
                                  "exceeded server-side consumption",
                                  **out}))
                sys.exit(1)

            # ---- kill -9 / warm restart: admissions ≤ limit + Σ
            # outstanding-at-crash
            LIMIT = 200
            lim2 = LocalLimiter(
                d.conf.grpc_address, "boom", "k", limit=LIMIT,
                duration=10 * MINUTE, ttl_ms=20_000, initial_grant=60,
            )
            await lim2.start()
            for _ in range(20):
                assert lim2.allow()
            outstanding = lim2.budget
            await asyncio.sleep(0.3)  # checkpoint covers the grant writes
            await c.crash_restart(0)
            d2 = c.daemons[0]
            while lim2.allow():
                pass
            for _ in range(3 * LIMIT):
                await lim2.check()
            total = lim2.stats.local_admits + lim2.stats.rpc_admits
            out["restart_outstanding_at_crash"] = outstanding
            out["restart_admitted_total"] = total
            out["restart_bound"] = LIMIT + outstanding
            if total > LIMIT + outstanding:
                print(json.dumps({"error": "lease smoke: admissions "
                                  "across kill/restart exceeded limit + "
                                  "outstanding-at-crash", **out}))
                sys.exit(1)
            if total < outstanding:
                print(json.dumps({"error": "lease smoke: the restarted "
                                  "plane served nothing", **out}))
                sys.exit(1)
            await lim2.close()

            # ---- TTL reclamation without any scan
            cl2 = V1Client(d2.conf.grpc_address)
            r1 = await cl2.lease_quota(pb.LeaseQuotaReq(
                name="ttl", unique_key="k", tokens=50, limit=100,
                duration=10 * MINUTE, ttl_ms=150,
            ))
            assert r1.granted == 50, r1

            async def reclaimed():
                r = await cl2.lease_quota(pb.LeaseQuotaReq(
                    name="ttl", unique_key="k", tokens=50, limit=100,
                    duration=10 * MINUTE, ttl_ms=150,
                ))
                return r.granted == 50

            await wait_for(reclaimed, timeout_s=5)
            srv = (await cl2.get_rate_limits([pb.RateLimitReq(
                name="ttl", unique_key="k", hits=0, limit=100,
                duration=10 * MINUTE,
            )])).responses[0]
            out["ttl_reclaimed"] = True
            if srv.remaining != 0:
                print(json.dumps({"error": "lease smoke: expiry refunded "
                                  "real-limit consumption (must stay "
                                  "conservative)", **out}))
                sys.exit(1)
            await cl.close()
            await cl2.close()
        finally:
            await c.stop()

    asyncio.run(run())
    return out


def probe_smoke() -> dict:
    """Fused Pallas probe-kernel gate (ops/pallas_probe.py, interpret
    mode — the same lowering CPU CI's oracle suite runs):

    * BIT-IDENTITY: both kernels drive the same seeded ~1M-live-key table
      through the same mixed-algorithm batch sequence; any output-row or
      table-byte divergence fails the build;
    * WALL-TIME: the Pallas path must stay within 10% of the XLA path per
      dispatch at the 1M-key config (interleaved best-of-3, so machine
      weather cancels) — the interpret movement layer discharges to the
      same gather/scatter XLA runs, and a regression here means someone
      re-introduced a per-row loop or a full-table copy into it.
    """
    import jax.numpy as jnp

    from gubernator_tpu.ops.kernel2 import decide2_packed_cols
    from gubernator_tpu.ops.table2 import Table2, new_table2

    B_P = 4096
    CAP = 1 << 21  # ~1M live keys at ~0.5 load
    LIVE = 1_000_000
    rng = np.random.default_rng(23)
    keys = np.unique(rng.integers(1, (1 << 62), size=LIVE + (LIVE >> 3),
                                  dtype=np.int64))[:LIVE]

    def arr12(fp, algo, hits, now):
        n = fp.shape[0]
        z = np.zeros(n, dtype=np.int64)
        a = np.stack([
            fp, algo.astype(np.int64), z, hits,
            np.full(n, 1 << 16, dtype=np.int64), z,
            np.full(n, 3_600_000, dtype=np.int64),
            np.full(n, now, dtype=np.int64),
            np.full(n, now + 3_600_000, dtype=np.int64), z,
            np.full(n, 3_600_000, dtype=np.int64),
            np.ones(n, dtype=np.int64),
        ])
        return jnp.asarray(a)

    def batch(i, now, algos=False):
        fp = keys[(i * B_P) % LIVE:][:B_P]
        if fp.shape[0] < B_P:
            fp = keys[:B_P]
        algo = (
            np.array([(0, 2, 3, 4)[j % 4] for j in range(B_P)],
                     dtype=np.int64)
            if algos else np.zeros(B_P, dtype=np.int64)
        )
        hits = rng.integers(0, 3, size=B_P).astype(np.int64)
        return arr12(fp, algo, hits, now)

    # seed ONCE through the XLA kernel, then hand both kernels identical
    # table bytes (seeding twice would double the smoke's wall time)
    t_seed = new_table2(CAP)
    for i in range(LIVE // B_P):
        t_seed, out = decide2_packed_cols(
            t_seed, batch(i, NOW), write="xla", math="token"
        )
        if i % 32 == 31:
            np.asarray(out)
    rows_np = np.asarray(t_seed.rows)
    tx = Table2(rows=jnp.asarray(rows_np))
    tp = Table2(rows=jnp.asarray(rows_np.copy()))

    # ---- parity drive: mixed algorithms over the seeded keyspace
    mismatches = 0
    for i in range(24):
        b = batch(7 * i, NOW + 1_000 * i, algos=True)
        tx, ox = decide2_packed_cols(tx, b, write="xla", math="int")
        tp, op = decide2_packed_cols(
            tp, b, write="xla", math="int", probe="pallas"
        )
        if not np.array_equal(np.asarray(ox), np.asarray(op)):
            mismatches += 1
    byte_equal = bool(np.array_equal(np.asarray(tx.rows), np.asarray(tp.rows)))
    out = {"parity_dispatches": 24, "mismatched_dispatches": mismatches,
           "table_bytes_equal": byte_equal}
    if mismatches or not byte_equal:
        print(json.dumps({"error": "probe smoke: pallas/xla divergence",
                          **out}))
        sys.exit(1)

    # ---- wall-time: staged batches, reps interleaved so machine weather
    # hits both kernels alike; best-of-3 per kernel
    timed = [batch(3 * i, NOW) for i in range(16)]
    tables = {
        p: Table2(rows=jnp.asarray(rows_np.copy())) for p in ("xla", "pallas")
    }
    walls = {"xla": float("inf"), "pallas": float("inf")}
    for p in walls:  # compile + warm
        tables[p], o = decide2_packed_cols(
            tables[p], timed[0], write="xla", math="token", probe=p
        )
        np.asarray(o)
    for _ in range(3):
        for p in walls:
            t = tables[p]
            t0 = time.perf_counter()
            for b in timed:
                t, o = decide2_packed_cols(
                    t, b, write="xla", math="token", probe=p
                )
            np.asarray(o)
            walls[p] = min(walls[p], time.perf_counter() - t0)
            tables[p] = t

    xla_ms = walls["xla"] / 16 * 1e3
    pallas_ms = walls["pallas"] / 16 * 1e3
    ratio = pallas_ms / xla_ms
    from gubernator_tpu.ops.layout import FULL
    from gubernator_tpu.ops.pallas_probe import hbm_bytes_per_decision

    out.update({
        "xla_ms_per_dispatch": round(xla_ms, 2),
        "pallas_ms_per_dispatch": round(pallas_ms, 2),
        "pallas_over_xla": round(ratio, 3),
        "hbm_bytes_per_decision": {
            p: round(hbm_bytes_per_decision(FULL, B_P, CAP >> 3, "xla", p), 1)
            for p in ("xla", "pallas")
        },
    })
    if ratio > 1.10:
        print(json.dumps({"error": "probe smoke: pallas interpret path "
                          ">10% over the XLA path", **out}))
        sys.exit(1)
    return out


def tier_smoke() -> dict:
    """Hot-set tiering gate (ISSUE 15 acceptance, docs/tiering.md):

    (a) **capacity**: ≥4× tracked keys beyond table capacity with ZERO
        over-grants vs the token-bucket oracle (non-refilling window ⇒
        per-key admissions ≤ limit) — eviction is a tiering event, not a
        permissive re-grant. A control run without tiering must
        over-grant, or the scenario stopped exercising eviction;
    (b) **hot-set throughput**: Zipf traffic whose hot set lives in HBM
        must stay within 15% of the no-tiering engine on the SAME
        batches (interleaved best-of-5). The CPU proxy's serial python
        front end exaggerates the sidecar/probe overhead a TPU pipeline
        overlaps — run-to-run machine noise alone swings this ratio
        ±5%, so the CPU gate carries margin and the ≥0.9× acceptance
        bit is recorded by the bench `tiering` phase on the device run
        (the same split as the layout/probe TPU claims);
    (c) **byte bound**: the shadow's RAM set stays within
        GUBER_TIER_SHADOW_BYTES with LRU shedding counted.
    """
    from gubernator_tpu.tier import ROW_BYTES, ShadowTable

    rng = np.random.default_rng(31)
    CAP = 1 << 12          # 4096 slots (512 buckets)
    TRACKED = 4 * CAP      # the ≥4× capacity claim
    LIMIT = 10
    keys = np.unique(
        rng.integers(1, 1 << 62, size=TRACKED + 256, dtype=np.int64)
    )[:TRACKED]

    def mkcols(fp, now, hits):
        n = fp.shape[0]
        return RequestColumns(
            fp=fp, algo=np.zeros(n, dtype=np.int32),
            behavior=np.zeros(n, dtype=np.int32),
            hits=np.full(n, hits, dtype=np.int64),
            limit=np.full(n, LIMIT, dtype=np.int64),
            burst=np.zeros(n, dtype=np.int64),
            duration=np.full(n, 3_600_000, dtype=np.int64),
            created_at=np.full(n, now, dtype=np.int64),
            err=np.zeros(n, dtype=np.int8),
        )

    def drive(eng):
        adm = np.zeros(TRACKED, dtype=np.int64)
        t = NOW
        for _ in range(4):
            for i in range(0, TRACKED, 2048):
                rc = eng.check_columns(mkcols(keys[i:i + 2048], t, 3),
                                       now_ms=t)
                ok = (rc.status == 0) & (rc.err == 0)
                adm[i:i + 2048][ok] += 3
                t += 7
        return adm

    eng = LocalEngine(capacity=CAP, write_mode="xla")
    eng.attach_shadow(ShadowTable(max_bytes=TRACKED * ROW_BYTES))
    adm = drive(eng)
    over = int((adm > LIMIT).sum())
    st = eng.shadow.stats()
    out = {
        "capacity_slots": CAP,
        "tracked_keys": TRACKED,
        "tracked_x_capacity": TRACKED / CAP,
        "over_granted_keys": over,
        "demoted_evict": st["demoted_evict"],
        "promoted": st["promoted"],
    }
    if over:
        print(json.dumps({"error": "tier smoke: over-grants with tiering "
                          "on (eviction lost state)", **out}))
        sys.exit(1)
    if st["demoted_evict"] == 0:
        print(json.dumps({"error": "tier smoke: no demotions — the drive "
                          "no longer exercises eviction", **out}))
        sys.exit(1)
    ctrl = LocalEngine(capacity=CAP, write_mode="xla")
    adm_ctrl = drive(ctrl)
    out["control_over_granted_keys"] = int((adm_ctrl > LIMIT).sum())
    if out["control_over_granted_keys"] == 0:
        print(json.dumps({"error": "tier smoke: the no-tiering control "
                          "did not over-grant", **out}))
        sys.exit(1)

    # ---- (b) hot-set throughput, interleaved best-of-3. The claim under
    # test: the tiering MACHINERY (sidecar fetch, shadow probes) costs
    # the HBM-resident hot set ≤ 10% — so the gate times Zipf-shaped
    # HOT-SET batches on an engine tracking 4× capacity (cold majority
    # demoted by the sweep, the TierManager operating point) against the
    # all-HBM no-tiering baseline. The mixed 90/10 stream — where ~10% of
    # rows FAULT BACK through the merge, work the baseline skips by
    # over-granting — is measured and REPORTED (mixed_rate_*), not
    # gated: paging the tail is the new capability, not overhead.
    # the hot set is the LAST-seeded slice: the idle reference is the
    # stored stamp (a token row's window creation — docs/tiering.md
    # "idle detection"), so the sweep separates hot from cold by
    # creation order here. Collision-capped at ≤6 keys per bucket so no
    # bucket hosts > K hot keys (a bucket that does thrashes by
    # GEOMETRY, tiering or not — the >K pathology docs/tiering.md
    # bounds); Zipf-shaped draws at the serving plane's coalesced batch
    # size (unique ~1.7K rows/dispatch).
    NBUCK = CAP // 8
    tail = keys[TRACKED - CAP // 2:]
    per = {}
    hot_sel = []
    for k in tail.tolist():
        b = k % NBUCK
        if per.get(b, 0) < 6:
            per[b] = per.get(b, 0) + 1
            hot_sel.append(k)
    hot = np.asarray(hot_sel, dtype=np.int64)
    HOT = hot.shape[0]
    zr = np.minimum(rng.zipf(1.05, size=80 * 2048) - 1, HOT - 1)
    t = NOW + 10_000_000
    hot_batches = []
    for i in range(16):
        fp = np.unique(hot[zr[i * 3072:(i + 1) * 3072]])
        hot_batches.append((fp, t))
        t += 13
    mixed_batches = []
    for i in range(8):
        h = hot[zr[(16 + i) * 3072:(16 + i) * 3072 + 1844]]
        cold_draw = keys[:TRACKED - HOT][
            rng.integers(0, TRACKED - HOT, size=204)
        ]
        fp = np.unique(np.concatenate([h, cold_draw]))
        mixed_batches.append((fp, t))
        t += 13
    engines = {}
    for tag in ("tiering", "baseline"):
        e = LocalEngine(capacity=CAP, write_mode="xla")
        tt = NOW + 9_000_000
        if tag == "tiering":
            e.attach_shadow(ShadowTable(max_bytes=TRACKED * ROW_BYTES))
            # seed the COLD majority, then the hot set a beat later —
            # the idle sweep keys off the stored stamp (a token row's
            # window creation, docs/tiering.md), so the age gap is what
            # separates the tiers here
            cold_keys = keys[:TRACKED - HOT]
            for i in range(0, cold_keys.shape[0], 2048):
                e.check_columns(mkcols(cold_keys[i:i + 2048], tt, 1),
                                now_ms=tt)
                tt += 7
            tt += 2_000
            e.check_columns(mkcols(hot, tt, 1), now_ms=tt)
            # the cadence sweep a live daemon runs (TierManager):
            # demotes the cold seed waves, keeps the fresher hot set
            fps, slots = e.extract_idle(tt + 100, 1_000, max_rows=TRACKED)
            if fps.shape[0]:
                e.tombstone_fps(fps)
                e.shadow.offer(
                    fps, np.asarray(e.table.layout.unpack(slots)), tt + 100,
                    reason="idle",
                )
        else:
            e.check_columns(mkcols(hot, tt, 1), now_ms=tt)
        # warm every compiled shape before timing
        for fp, bt in hot_batches[:4]:
            e.check_columns(mkcols(fp, bt, 1), now_ms=bt)
        engines[tag] = e
    walls = {"tiering": float("inf"), "baseline": float("inf")}
    rows_total = sum(b[0].shape[0] for b in hot_batches[4:])
    for _ in range(5):  # interleaved best-of-5: CI-runner weather cancels
        for tag, e in engines.items():
            t0 = time.perf_counter()
            for fp, bt in hot_batches[4:]:
                e.check_columns(mkcols(fp, bt, 1), now_ms=bt)
            walls[tag] = min(walls[tag], time.perf_counter() - t0)
    rate = {k: rows_total / v for k, v in walls.items()}
    ratio = rate["tiering"] / rate["baseline"]
    # mixed 90/10 stream with live fault-backs — reported, not gated
    # (best-of-3; early reps eat the promote/rehydrate compiles)
    mixed_rows = sum(b[0].shape[0] for b in mixed_batches)
    mixed = {}
    for tag, e in engines.items():
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            for fp, bt in mixed_batches:
                e.check_columns(mkcols(fp, bt, 1), now_ms=bt)
            best = min(best, time.perf_counter() - t0)
        mixed[tag] = mixed_rows / best
    out["mixed_rate_tiering"] = round(mixed["tiering"], 1)
    out["mixed_rate_baseline"] = round(mixed["baseline"], 1)
    out["mixed_ratio"] = round(mixed["tiering"] / mixed["baseline"], 3)
    out.update({
        "hot_set_rate_tiering": round(rate["tiering"], 1),
        "hot_set_rate_baseline": round(rate["baseline"], 1),
        "hot_set_ratio": round(ratio, 3),
    })
    if ratio < 0.85:
        print(json.dumps({"error": "tier smoke: hot-set rate with "
                          "tiering fell below 0.85x the no-tiering "
                          "baseline (CPU-proxy gate; the 0.9x claim is "
                          "the device bench's)", **out}))
        sys.exit(1)

    # ---- (c) byte bound + LRU shed accounting
    sh = ShadowTable(max_bytes=64 * ROW_BYTES)
    fps = np.arange(1, 257, dtype=np.int64)
    rows = np.zeros((256, 16), dtype=np.int32)
    rows[:, 0] = fps.astype(np.int32)
    rows[:, 10] = 1
    sh.offer(fps, rows, 0)
    out["shadow_bound_bytes"] = sh.max_bytes
    out["shadow_nominal_bytes"] = sh.nominal_bytes
    out["shadow_shed"] = sh.shed
    if sh.nominal_bytes > sh.max_bytes or sh.shed != 256 - 64:
        print(json.dumps({"error": "tier smoke: shadow byte bound or "
                          "shed accounting broken", **out}))
        sys.exit(1)
    return out


def overload_smoke() -> dict:
    """Overload-plane regression gate (docs/robustness.md "Overload &
    QoS"): a 10× flash crowd through a loopback daemon with the overload
    plane armed (bounded ring, 75 ms enqueue deadline, tier-major
    dispatch). Gated:

    (a) **zero priority inversions** — a request must never be shed for
        capacity while strictly-lower-tier rows sit admitted (the
        preempt-before-shed rule's runtime proof, counted in the batcher);
    (b) **the plane engages** — the flash step must actually shed (an
        overload gate that never sheds is gating nothing);
    (c) **goodput floor** — during the 10× step the door must keep serving:
        goodput ≥ 25% of the offered flood AND ≥ 80% of the pre-flash
        goodput (the anti-collapse bound — shedding is for the excess, not
        the base load);
    (d) **bounded top-tier p99** — tier-3 requests must clear the flash
        step under a fixed wall (generous for CI weather; the disarmed
        door's queue grows without bound here, so ANY fixed bound
        separates armed from unarmed).
    """
    from bench import drive_overload_scenario

    res = drive_overload_scenario(
        "flash_crowd", seconds_per_step=1.5, base_workers=4,
        rows_per_req=128, keys=1 << 14, coalesce_limit=1024,
        batch_queue_rows=2048, overload_deadline_ms=75.0,
    )
    steps = {s["step"]: s for s in res["curve"]}
    pre, flash = steps["pre"], steps["flash"]
    shed_total = sum(flash["sheds"].values())
    tier3_p99 = flash["request_p99_ms_by_tier"].get("3", 0.0)
    out = {
        "offered_flash_rows_per_s": flash["offered_rows_per_s"],
        "goodput_flash_rows_per_s": flash["goodput_rows_per_s"],
        "goodput_pre_rows_per_s": pre["goodput_rows_per_s"],
        "flash_sheds": flash["sheds"],
        "tier3_flash_p99_ms": tier3_p99,
        "priority_inversions": res["priority_inversions"],
        "shed_by_tier": res["shed_by_tier"],
    }
    if res["priority_inversions"]:
        print(json.dumps({"error": "overload smoke: priority inversions "
                          "under the saturated ring", **out}))
        sys.exit(1)
    if shed_total == 0:
        print(json.dumps({"error": "overload smoke: the 10x flash crowd "
                          "never shed — the overload plane did not engage",
                          **out}))
        sys.exit(1)
    if (flash["goodput_rows_per_s"] < 0.25 * flash["offered_rows_per_s"]
            or flash["goodput_rows_per_s"]
            < 0.8 * pre["goodput_rows_per_s"]):
        print(json.dumps({"error": "overload smoke: goodput collapsed "
                          "under the flash crowd", **out}))
        sys.exit(1)
    if tier3_p99 > 2_000.0:
        print(json.dumps({"error": "overload smoke: top-tier p99 unbounded "
                          "under the flash crowd", **out}))
        sys.exit(1)
    return out


def main() -> None:
    eng = LocalEngine(capacity=1 << 15, write_mode="xla")
    rng = np.random.default_rng(0)
    fps = [
        rng.integers(1, (1 << 63) - 1, size=B, dtype=np.int64) for _ in range(4)
    ]
    for f in fps:  # compile + seed
        eng.check_columns(cols(f), now_ms=NOW)
    best = 0.0
    for _ in range(3):
        t0 = time.perf_counter()
        n_disp = 64
        for i in range(n_disp):
            eng.check_columns(cols(fps[i % 4]), now_ms=NOW)
        dt = time.perf_counter() - t0
        best = max(best, n_disp * B / dt)
    print(json.dumps({
        "decisions_per_sec": round(best, 1),
        "sharded_smoke": sharded_smoke(),
        "wire_smoke": wire_smoke(),
        "handoff_smoke": handoff_smoke(),
        "serving_smoke": serving_smoke(),
        "telemetry_smoke": telemetry_smoke(),
        "mesh_smoke": mesh_smoke(),
        "durability_smoke": durability_smoke(),
        "algo_smoke": algo_smoke(),
        "layout_smoke": layout_smoke(),
        "probe_smoke": probe_smoke(),
        "region_smoke": region_smoke(),
        "lease_smoke": lease_smoke(),
        "tier_smoke": tier_smoke(),
        "ring_smoke": ring_smoke(),
        "ring_drain_smoke": ring_drain_smoke(),
        "overload_smoke": overload_smoke(),
    }))


if __name__ == "__main__":
    main()
