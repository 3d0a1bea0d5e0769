"""Observability-layer tests (ISSUE 7): device-side table telemetry parity
vs the host oracle (local + 8-dev CPU mesh), OpenMetrics exemplars whose
trace_ids resolve to dispatch spans, span links across a coalesced flush,
the /v1/debug/* JSON plane, and GLOBAL sync-staleness monotonicity."""

import asyncio
import functools

import numpy as np
import pytest

from gubernator_tpu import tracing
from gubernator_tpu.client import V1Client
from gubernator_tpu.config import BehaviorConfig
from gubernator_tpu.ops.batch import RequestColumns
from gubernator_tpu.ops.engine import LocalEngine
from gubernator_tpu.ops.telemetry import (
    REMAIN_EDGES,
    TTL_EDGES_MS,
    finish_scan,
    host_telemetry,
)
from gubernator_tpu.types import RateLimitRequest

from tests.cluster import daemon_config

NOW = 1_700_000_000_000

PARITY_FIELDS = (
    "live_keys", "occupied_slots", "over_keys", "bucket_occupancy",
    "ttl_horizon", "remaining_frac", "block_fill",
)


def async_test(fn):
    @functools.wraps(fn)
    def wrapper(*a, **k):
        asyncio.run(fn(*a, **k))

    return wrapper


def _mixed_cols(rng, n):
    """Traffic that exercises every telemetry dimension: token+leaky, tight
    limits (depleted + OVER keys), short durations (expired slots at a later
    scan now), and spread TTL horizons."""
    fp = np.unique(rng.integers(1, (1 << 63) - 1, size=2 * n,
                                dtype=np.int64))[:n]
    return RequestColumns(
        fp=fp,
        algo=(np.arange(n) % 2).astype(np.int32),
        behavior=np.zeros(n, dtype=np.int32),
        hits=rng.integers(0, 5, n).astype(np.int64),
        limit=rng.integers(1, 10, n).astype(np.int64),
        burst=np.zeros(n, dtype=np.int64),
        duration=rng.choice(
            [500, 30_000, 120_000, 7_200_000, 172_800_000], n
        ).astype(np.int64),
        created_at=np.full(n, NOW, dtype=np.int64),
        err=np.zeros(n, dtype=np.int8),
    )


class StubExporter:
    """In-memory tracing exporter: records what the OTLP one would POST."""

    def __init__(self):
        self.spans = []
        self.exported = 3
        self.dropped = 1
        self.export_errors = 0

    def record(self, name, span, parent_span_id, start_ns, end_ns,
               attributes=None, links=(), kind=2):
        self.spans.append({
            "name": name, "trace_id": span.trace_id, "span_id": span.span_id,
            "parent": parent_span_id, "start": start_ns, "end": end_ns,
            "attributes": dict(attributes or {}), "links": list(links),
            "kind": kind,
        })

    def flush(self):
        pass


# ---------------------------------------------------------------- telemetry


def test_telemetry_scan_matches_host_oracle_local():
    eng = LocalEngine(capacity=4096, write_mode="xla")
    rng = np.random.default_rng(11)
    eng.check_columns(_mixed_cols(rng, 3000), now_ms=NOW)
    # drive a couple of keys to exact depletion so stored OVER status exists
    hot = RequestColumns(
        fp=np.asarray([12345], dtype=np.int64),
        algo=np.zeros(1, np.int32), behavior=np.zeros(1, np.int32),
        hits=np.asarray([3], np.int64), limit=np.asarray([3], np.int64),
        burst=np.zeros(1, np.int64), duration=np.asarray([60_000], np.int64),
        created_at=np.full(1, NOW, np.int64), err=np.zeros(1, np.int8),
    )
    eng.check_columns(hot, now_ms=NOW)  # depletes to remaining=0
    # a hit against a depleted key is what sticks stored status = OVER
    eng.check_columns(hot._replace(hits=np.asarray([1], np.int64)),
                      now_ms=NOW)
    later = NOW + 2_000  # the 500 ms-duration cohort is expired by now
    snap = finish_scan(eng.telemetry_begin(later))
    oracle = host_telemetry(np.asarray(eng.table.rows), later)
    for f in PARITY_FIELDS:
        assert getattr(snap, f) == getattr(oracle, f), f
    # structural invariants the dashboards rely on
    assert snap.over_keys >= 1  # the depleted key
    assert snap.occupied_slots > snap.live_keys  # expired cohort visible
    assert sum(snap.bucket_occupancy) == snap.n_buckets
    assert sum(snap.probe_depth) == snap.live_keys
    assert sum(snap.block_fill) == snap.n_buckets // min(64, snap.n_buckets) \
        or sum(snap.block_fill) > 0
    assert snap.ttl_horizon == sorted(snap.ttl_horizon)  # cumulative
    assert snap.remaining_frac == sorted(snap.remaining_frac)
    assert snap.ttl_horizon[-1] <= snap.live_keys
    assert len(snap.ttl_horizon) == len(TTL_EDGES_MS)
    assert len(snap.remaining_frac) == len(REMAIN_EDGES)


def test_telemetry_scan_matches_host_oracle_sharded():
    from gubernator_tpu.parallel import make_mesh
    from gubernator_tpu.parallel.sharded import ShardedEngine

    eng = ShardedEngine(make_mesh(8), capacity_per_shard=1 << 10,
                        write_mode="xla")
    rng = np.random.default_rng(13)
    eng.check_columns(_mixed_cols(rng, 4000), now_ms=NOW)
    later = NOW + 2_000
    snap = finish_scan(eng.telemetry_begin(later))
    oracle = host_telemetry(np.asarray(eng.table.rows), later)
    for f in PARITY_FIELDS:
        assert getattr(snap, f) == getattr(oracle, f), f
    # the mesh variant additionally reports per-shard live counts
    assert snap.per_shard_live is not None and len(snap.per_shard_live) == 8
    assert sum(snap.per_shard_live) == snap.live_keys
    assert snap.capacity == 8 * (1 << 10)


@async_test
async def test_daemon_telemetry_loop_populates_metrics():
    from gubernator_tpu.service.daemon import Daemon
    from gubernator_tpu.service.metrics import parse_metrics

    conf = daemon_config(telemetry_interval_ms=100.0)
    d = await Daemon.spawn(conf)
    client = V1Client(d.conf.grpc_address)
    try:
        await client.get_rate_limits([
            RateLimitRequest(name="tm", unique_key=f"k{i}", hits=1,
                             limit=100, duration=60_000)
            for i in range(64)
        ])
        for _ in range(50):
            await asyncio.sleep(0.1)
            if d._table_telemetry is not None:
                break
        assert d._table_telemetry is not None, "telemetry loop never ticked"
        scraped = parse_metrics(d.metrics.render().decode())
        assert scraped["gubernator_tpu_table_live_keys"][()] == 64
        assert scraped["gubernator_tpu_table_capacity"][()] >= 8192
        occ = scraped["gubernator_tpu_table_bucket_occupancy"]
        assert sum(occ.values()) == d._table_telemetry.n_buckets
        # snapshot histograms carry an explicit +Inf bound = live keys
        assert scraped["gubernator_tpu_table_ttl_horizon"][
            (("le", "+Inf"),)
        ] == 64
        assert scraped["gubernator_tpu_table_scan_duration_count"][()] >= 1
        # the exporter-health satellites render (zeros without an exporter)
        assert "gubernator_otel_spans_exported_total" in scraped
        assert "gubernator_global_sync_staleness_seconds" in scraped
    finally:
        await client.close()
        await d.close()


# ------------------------------------------------- exemplars + span links


@async_test
async def test_stage_exemplars_resolve_to_dispatch_spans():
    """A scraped stage_duration bucket must carry an OpenMetrics exemplar
    whose trace_id resolves to a recorded `dispatch` span holding ≥1 request
    span link (the acceptance criterion's exact chain)."""
    from prometheus_client.openmetrics.parser import (
        text_string_to_metric_families,
    )

    from gubernator_tpu.service.daemon import Daemon

    exp = StubExporter()
    old = tracing.exporter
    tracing.set_exporter(exp)
    d = await Daemon.spawn(daemon_config())
    client = V1Client(d.conf.grpc_address)
    try:
        reqs = [
            RateLimitRequest(name="ex", unique_key=f"k{i}", hits=1,
                             limit=100, duration=60_000)
            for i in range(32)
        ]
        await asyncio.gather(*(client.get_rate_limits(reqs)
                               for _ in range(4)))
        text = d.metrics.render(openmetrics=True).decode()
        exemplars = {}  # metric name -> [trace_id]
        for fam in text_string_to_metric_families(text):
            for s in fam.samples:
                if s.exemplar is not None:
                    exemplars.setdefault(s.name, []).append(
                        s.exemplar.labels["trace_id"]
                    )
        # stage buckets AND the (Summary→Histogram satellite) request plane
        assert any(k.startswith("gubernator_tpu_stage_duration_bucket")
                   for k in exemplars), exemplars.keys()
        assert any(
            k.startswith("gubernator_grpc_request_duration_bucket")
            for k in exemplars
        ), exemplars.keys()
        for tid in {t for v in exemplars.values() for t in v}:
            assert len(tid) == 32 and int(tid, 16)  # valid W3C trace id
        dispatches = {s["trace_id"]: s for s in exp.spans
                      if s["name"] == "dispatch"}
        assert dispatches, "no dispatch spans recorded"
        stage_tids = [
            t for k, v in exemplars.items()
            if k.startswith("gubernator_tpu_stage_duration_bucket")
            for t in v
        ]
        resolved = [dispatches[t] for t in stage_tids if t in dispatches]
        assert resolved, (stage_tids, list(dispatches))
        assert any(len(sp["links"]) >= 1 for sp in resolved)
        assert resolved[0]["attributes"]["batch.rows"] >= 32
        # stage child spans hang under the dispatch span
        stages = {s["name"] for s in exp.spans
                  if s["parent"] and s["trace_id"] in dispatches}
        assert {"queue", "put", "issue", "fetch"} <= stages
    finally:
        tracing.set_exporter(old)
        await client.close()
        await d.close()


@async_test
async def test_request_spans_link_to_shared_dispatch_span():
    """Requests coalesced into ONE flush each carry a link to the SAME
    dispatch span — the causality edge batching otherwise erases."""
    from gubernator_tpu.service.daemon import Daemon

    exp = StubExporter()
    old = tracing.exporter
    tracing.set_exporter(exp)
    # non-adaptive 50 ms window: concurrent requests land in one flush
    conf = daemon_config()
    conf.behaviors = BehaviorConfig(
        batch_wait_ms=50.0, adaptive_batch=False,
        batch_timeout_ms=5000.0, global_timeout_ms=5000.0,
    )
    d = await Daemon.spawn(conf)
    try:
        async def one(i):
            trace = f"{i:02d}" * 16
            await d.get_rate_limits([
                __import__("gubernator_tpu.proto.gubernator_pb2",
                           fromlist=["x"]).RateLimitReq(
                    name="ln", unique_key=f"k{i}", hits=1, limit=100,
                    duration=60_000,
                    metadata={"traceparent": f"00-{trace}-{'ab' * 8}-01"},
                )
            ])
            return trace

        traces = await asyncio.gather(*(one(i) for i in range(1, 5)))
        req_spans = [s for s in exp.spans if s["name"] == "GetRateLimits"
                     and s["trace_id"] in traces]
        assert len(req_spans) == 4
        linked_dispatches = [s["links"][0].span_id for s in req_spans
                             if s["links"]]
        assert linked_dispatches, "no request span carried a dispatch link"
        # at least two requests shared one flush → same dispatch span id
        assert any(linked_dispatches.count(x) >= 2
                   for x in set(linked_dispatches)), linked_dispatches
        # and the dispatch span links back to its member request spans
        disp = {s["span_id"]: s for s in exp.spans if s["name"] == "dispatch"}
        shared = max(set(linked_dispatches), key=linked_dispatches.count)
        assert len(disp[shared]["links"]) >= 2
    finally:
        tracing.set_exporter(old)
        await d.close()


def _is_id(s, n):
    return len(s) == n and s == s.lower() and int(s, 16) != 0


@async_test
async def test_ids_with_exporter_inbound_traceparent_and_forwarded_row():
    """With a reader of the ids (an exporter, an inbound traceparent, a row
    forwarded to its owner) they are where they always were: the ingress
    span continues the client's trace under the client's span, its stage
    spans hang under it, the owner's span hangs under the ingress span
    through the forwarded row's metadata and links to the dispatch that
    served it, and the gRPC duration bucket carries the trace as exemplar."""
    from prometheus_client.openmetrics.parser import (
        text_string_to_metric_families,
    )

    from tests.cluster import Cluster, wait_for

    exp = StubExporter()
    old = tracing.exporter
    tracing.set_exporter(exp)
    c = await Cluster.start(2)
    trace, client_span = "5e" * 16, "c1" * 8
    try:
        ingress = c.non_owning_daemons("ids", "fwd")[0]
        client = V1Client(ingress.conf.grpc_address)
        try:
            resp = await client.get_rate_limits([
                RateLimitRequest(
                    name="ids", unique_key="fwd", hits=1, limit=10,
                    duration=60_000,
                    metadata={"traceparent": f"00-{trace}-{client_span}-01"},
                )
            ])
            assert resp.responses[0].error == ""
        finally:
            await client.close()

        def spans(name):
            return [s for s in exp.spans
                    if s["name"] == name and s["trace_id"] == trace]

        await wait_for(lambda: asyncio.sleep(0, spans("GetPeerRateLimits")))
        (req_span,) = spans("GetRateLimits")
        assert req_span["parent"] == client_span
        assert _is_id(req_span["span_id"], 16)
        assert req_span["span_id"] != client_span
        # the ingress handler's stage spans are children of its span
        kids = [s for s in exp.spans if s["parent"] == req_span["span_id"]]
        assert {"parse", "request"} <= {s["name"] for s in kids}
        assert all(s["trace_id"] == trace and _is_id(s["span_id"], 16)
                   for s in kids)
        # the forwarded row carried the ingress span to the owner
        (peer_span,) = spans("GetPeerRateLimits")
        assert peer_span["parent"] == req_span["span_id"]
        # and the owner's span links to the dispatch that served the row,
        # a trace of its own, which links back
        assert len(peer_span["links"]) == 1
        link = peer_span["links"][0]
        assert _is_id(link.trace_id, 32) and link.trace_id != trace
        (disp,) = [s for s in exp.spans if s["name"] == "dispatch"
                   and s["span_id"] == link.span_id]
        assert peer_span["span_id"] in {l.span_id for l in disp["links"]}
        # the request-duration bucket of the ingress door names the trace
        tids = [
            smp.exemplar.labels["trace_id"]
            for fam in text_string_to_metric_families(
                ingress.metrics.render(openmetrics=True).decode()
            )
            for smp in fam.samples
            if smp.exemplar is not None
            and smp.name == "gubernator_grpc_request_duration_bucket"
            and smp.labels.get("method") == "/v1.GetRateLimits"
        ]
        assert tids == [trace]
    finally:
        tracing.set_exporter(old)
        await c.stop()


@async_test
async def test_rpc_without_a_reader_of_its_ids_calls_no_urandom(monkeypatch):
    """No exporter, no inbound traceparent, no hook: nothing will read an
    RPC's ids, and serving it makes no `os.urandom` system call (each one
    drops the GIL on the event-loop thread); with an exporter the duration
    bucket gets its exemplar, without one it does not."""
    import os
    import random

    from gubernator_tpu.service.daemon import Daemon

    calls = []
    real = os.urandom

    def counted(n):
        calls.append(n)
        return real(n)

    assert tracing.exporter is None and tracing.span_hook is None
    d = await Daemon.spawn(daemon_config())
    client = V1Client(d.conf.grpc_address)
    try:
        reqs = lambda tag, n: [
            RateLimitRequest(name="nou", unique_key=f"{tag}{i}", hits=1,
                             limit=10, duration=60_000)
            for i in range(n)
        ]
        await client.get_rate_limits(reqs("w", 300))  # connect, compile
        await client.get_rate_limits(reqs("w", 3))
        monkeypatch.setattr(os, "urandom", counted)
        monkeypatch.setattr(random, "_urandom", counted)  # what `secrets` calls
        for k in range(5):
            big = await client.get_rate_limits(reqs(f"b{k}", 300))  # door pool
            small = await client.get_rate_limits(reqs(f"s{k}", 3))  # inline
            assert len(big.responses) == 300 and len(small.responses) == 3
        await d.get_rate_limits_raw(_raw_request("raw", 200))
        assert calls == []
        span = tracing.new_span()
        assert _is_id(span.trace_id, 32) and _is_id(span.span_id, 16)
        child = tracing.new_span(span)
        assert child.trace_id == span.trace_id and child.span_id != span.span_id
        assert calls == []
        assert "trace_id" not in d.metrics.render(openmetrics=True).decode()
    finally:
        await client.close()
        await d.close()


# ------------------------------------------- one primitive, budgets, profiler


def _stage_sums(metrics):
    """{stage: (sum seconds, count)} of gubernator_tpu_stage_duration."""
    from prometheus_client.parser import text_string_to_metric_families

    out = {}
    for fam in text_string_to_metric_families(metrics.render().decode()):
        for smp in fam.samples:
            if smp.name.startswith("gubernator_tpu_stage_duration_"):
                kind = smp.name.rsplit("_", 1)[1]
                if kind in ("sum", "count"):
                    st = out.setdefault(smp.labels["stage"], [0.0, 0.0])
                    st[kind == "count"] = smp.value
    return out


def _raw_request(tag, n):
    from gubernator_tpu.proto import gubernator_pb2 as pb

    return pb.GetRateLimitsReq(requests=[
        pb.RateLimitReq(name="bud", unique_key=f"{tag}-{i}", hits=1,
                        limit=1000, duration=60_000)
        for i in range(n)
    ]).SerializeToString()


def test_stage_primitive_feeds_histogram_dispatch_and_exporter():
    """One interval, three readers: the histogram sample (through a metrics
    object), the Dispatch's work sum, the exporter's child span; a renamed
    stage lands under its new label; without any of them it is a clock."""
    from gubernator_tpu.service.metrics import DaemonMetrics

    m, exp, old = DaemonMetrics(), StubExporter(), tracing.exporter
    tracing.set_exporter(exp)
    try:
        disp = tracing.Dispatch(seq=7, rows=3, span=tracing.new_span())
        with tracing.stage("put", m, disp=disp) as st:
            pass
        with tracing.stage("put", m, disp=disp) as miss:
            miss.name = "put_miss"
        assert disp.work_s == pytest.approx(st.dt + miss.dt)
        req = tracing.new_span()
        tracing.observe("door_wait", m, 0.25, req)
    finally:
        tracing.set_exporter(old)
    with tracing.stage("bare") as bare:  # no metrics, no parent, no exporter
        pass
    assert bare.dt >= 0.0
    fams = {f.name: f for f in m.registry.collect()}
    counts = {
        s.labels["stage"]: s.value
        for s in fams["gubernator_tpu_stage_duration"].samples
        if s.name.endswith("_count")
    }
    assert counts == {"put": 1, "put_miss": 1, "door_wait": 1}
    by_name = {s["name"]: s for s in exp.spans}
    assert set(by_name) == {"put", "put_miss", "door_wait"}
    assert by_name["put"]["parent"] == disp.span.span_id
    assert by_name["put"]["trace_id"] == disp.span.trace_id
    assert by_name["door_wait"]["parent"] == req.span_id
    assert by_name["door_wait"]["end"] - by_name["door_wait"]["start"] == 250_000_000


@async_test
async def test_request_and_dispatch_budgets_close():
    """The stages account for the time they claim to: a plain raw RPC's
    lines (parse, route, batch_wait, respond) cover >= 90% of `request` and
    never more than it, `door_wait` is a part of parse, and a dispatch is
    exactly its work stages (the encode of its callers' answers among them)
    plus its self time."""
    from gubernator_tpu.service.daemon import Daemon

    d = await Daemon.spawn(daemon_config())
    try:
        # small RPCs parse inline, 200-item RPCs cross the door pool once
        assert len(_raw_request("x", 200)) >= d.DOOR_OFFLOAD_BYTES
        for n in (1, 200, 7, 200):  # compile the shapes outside the count
            await d.get_rate_limits_raw(_raw_request(f"w{n}", n))
        s0 = _stage_sums(d.metrics)
        b0 = d.batcher.debug()
        for wave in range(30):
            await asyncio.gather(*(
                d.get_rate_limits_raw(
                    _raw_request(f"{wave}-{j}", 200 if j % 4 == 0 else 1 + j)
                )
                for j in range(10)
            ))
        s1 = _stage_sums(d.metrics)
        b1 = d.batcher.debug()
    finally:
        await d.close()

    def delta(stage, k=0):
        return s1.get(stage, (0, 0))[k] - s0.get(stage, (0, 0))[k]

    assert delta("request", 1) == 300
    assert delta("door_wait", 1) == 300 and delta("route", 1) == 300
    request = delta("request")
    lines = sum(delta(x) for x in ("parse", "route", "batch_wait", "respond"))
    assert 0.9 * request <= lines <= request
    assert delta("respond", 1) == 300
    assert 0.0 < delta("door_wait") <= delta("parse")
    n_disp = b1["dispatches"] - b0["dispatches"]
    assert n_disp >= 30 and b1["requests"] - b0["requests"] == 300
    assert delta("dispatch", 1) == delta("dispatch_wait", 1) == n_disp
    assert delta("encode", 1) == n_disp  # one a dispatch, inside batch_wait
    work = sum(delta(x) for x in
               ("put", "put_miss", "issue", "fetch", "encode"))
    assert work + delta("dispatch_wait") == pytest.approx(
        delta("dispatch"), rel=0.01
    )
    assert 0.0 < delta("dispatch_wait") < delta("dispatch")
    assert delta("close", 1) >= n_disp


@async_test
async def test_profiler_trace_holds_stage_spans_joined_by_dispatch(tmp_path):
    """With a jax.profiler trace running (the benchmark launcher's options)
    the stages are host spans on the profiler's clock: put, issue and fetch
    of one flush carry the same `dispatch` stat, on three threads, and the
    window's close says what closed it and how long its oldest entry waited."""
    import glob

    import jax
    from jax.profiler import ProfileData

    from gubernator_tpu.service.daemon import Daemon

    d = await Daemon.spawn(daemon_config())
    try:
        await d.get_rate_limits_raw(_raw_request("warm", 16))
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            for wave in range(4):
                await asyncio.gather(*(
                    d.get_rate_limits_raw(_raw_request(f"p{wave}-{j}", 16))
                    for j in range(4)
                ))
        finally:
            jax.profiler.stop_trace()
    finally:
        await d.close()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    by_stage = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("gub:"):
                    by_stage.setdefault(ev.name[4:], []).append(
                        (dict(ev.stats), ev.start_ns, ev.duration_ns, line.name)
                    )
    assert {"close", "put", "issue", "fetch", "apply", "parse",
            "encode"} <= set(by_stage), sorted(by_stage)
    seqs = {
        name: {st["dispatch"]: (t0, t0 + dur) for st, t0, dur, _ln in by_stage[name]}
        for name in ("put", "issue", "fetch")
    }
    joined = set(seqs["put"]) & set(seqs["issue"]) & set(seqs["fetch"])
    assert joined, seqs
    for seq in joined:  # one flush: put ends before issue ends before fetch
        assert seqs["put"][seq][1] <= seqs["issue"][seq][1] <= seqs["fetch"][seq][1]
    assert all(st["rows"] >= 16 for st, *_ in by_stage["put"])
    close = [st for st, *_ in by_stage["close"]]
    assert all(c["reason"] in ("rows", "bytes", "idle", "slot", "expire")
               for c in close)
    assert any(c.get("waited_us", -1) >= 0 and c.get("rows", 0) >= 16
               for c in close)


@async_test
async def test_metrics_scrape_counts_live_keys_on_the_device(monkeypatch):
    """GET /metrics no longer moves the table: the live-key count is one
    device program (compiled in warm-up) whose integer is fetched, and it
    is still exact, after inserts and after expiry. The three series that
    repeated others are gone."""
    import aiohttp

    from gubernator_tpu.ops import table2
    from gubernator_tpu.service.daemon import Daemon

    calls = {"device": 0, "host": 0}
    dev, slots = table2.live_count_device, table2._live_slots

    def count_device(*a, **k):
        calls["device"] += 1
        return dev(*a, **k)

    def count_slots(xp, *a, **k):
        calls["host"] += xp is np
        return slots(xp, *a, **k)

    d = await Daemon.spawn(daemon_config(telemetry_interval_ms=0.0))
    monkeypatch.setattr(table2, "live_count_device", count_device)
    monkeypatch.setattr(table2, "_live_slots", count_slots)
    client = V1Client(d.conf.grpc_address)

    async def scrape(session):
        async with session.get(f"http://{d.conf.http_address}/metrics") as r:
            assert r.status == 200
            text = await r.text()
        (line,) = [ln for ln in text.splitlines()
                   if ln.startswith("gubernator_cache_size ")]
        return text, float(line.split()[1])

    try:
        await client.get_rate_limits(
            [RateLimitRequest(name="lc", unique_key=f"long{i}", hits=1,
                              limit=10, duration=60_000) for i in range(20)]
            + [RateLimitRequest(name="lc", unique_key=f"short{i}", hits=1,
                                limit=10, duration=1_000) for i in range(12)]
        )
        async with aiohttp.ClientSession() as s:
            text, live = await scrape(s)
            assert live == 32 and calls == {"device": 1, "host": 0}
            await asyncio.sleep(1.2)
            _text, live = await scrape(s)
            assert live == 20 and calls == {"device": 2, "host": 0}
        assert 'gubernator_tpu_stage_duration_count{stage="live_count"}' in text
        for gone in ("gubernator_tpu_queue_wait_seconds",
                     "gubernator_tpu_dispatch_duration",
                     "gubernator_table_hbm_bytes_per_decision"):
            assert gone not in text
    finally:
        await client.close()
        await d.close()


def test_live_count_on_device_matches_the_host_count_sharded():
    """The same predicate on both sides: the device count over a mesh's
    sharded table equals the NumPy count over a host copy of its rows."""
    from gubernator_tpu.ops.table2 import Table2, live_count2
    from gubernator_tpu.parallel import make_mesh
    from gubernator_tpu.parallel.sharded import ShardedEngine

    rng = np.random.default_rng(5)
    eng = ShardedEngine(make_mesh(8), capacity_per_shard=1 << 10,
                        write_mode="xla")
    eng.check_columns(_mixed_cols(rng, 4000), now_ms=NOW)
    for now in (NOW, NOW + 1_000, NOW + 200_000):
        host = live_count2(
            Table2(rows=np.asarray(eng.table.rows), layout=eng.table.layout), now
        )
        assert eng.live_count(now) == host
    assert eng.live_count(NOW) > eng.live_count(NOW + 200_000) > 0


# --------------------------------------------------------------- debug plane


@async_test
async def test_debug_endpoints_schema():
    import aiohttp

    from gubernator_tpu.service.daemon import Daemon

    d = await Daemon.spawn(daemon_config(telemetry_interval_ms=0.0))
    client = V1Client(d.conf.grpc_address)
    try:
        await client.get_rate_limits([
            RateLimitRequest(name="dbg", unique_key=f"k{i}", hits=1,
                             limit=10, duration=60_000)
            for i in range(8)
        ])
        base = f"http://{d.conf.http_address}"
        async with aiohttp.ClientSession() as s:
            async with s.get(f"{base}/v1/debug/table") as r:
                assert r.status == 200
                table = await r.json()
            async with s.get(f"{base}/v1/debug/pipeline") as r:
                pipeline = await r.json()
            async with s.get(f"{base}/v1/debug/peers") as r:
                peers = await r.json()
            async with s.get(f"{base}/v1/debug/global") as r:
                glob = await r.json()
            async with s.get(f"{base}/v1/debug/bogus") as r:
                assert r.status == 404
        # table: scans on demand when the loop is disabled
        assert table["live_keys"] == 8
        assert set(table) >= {
            "capacity", "load_factor", "bucket_occupancy", "probe_depth",
            "ttl_horizon_ms", "remaining_frac", "block_fill_deciles",
            "over_fraction", "scan_ms",
        }
        b = pipeline["batcher"]
        assert set(b) >= {
            "pending_rows", "workers", "workers_alive", "inflight",
            "fused_dispatches", "column_dispatches", "adaptive_closes",
            "close_reasons", "dispatches", "requests",
        }
        # every _dispatch and every entry it carried, beside the engine's
        # passes; the modelled bytes-per-decision field is gone
        assert b["dispatches"] >= 1 and b["requests"] >= b["dispatches"]
        assert pipeline["engine"]["dispatches"] >= b["dispatches"]
        assert "hbm_bytes_per_decision" not in pipeline["engine"]
        assert set(b["close_reasons"]) == {"rows", "bytes", "idle", "slot"}
        assert pipeline["engine"]["kind"] == "LocalEngine"
        assert peers["self"] == d.conf.advertise_address
        assert set(peers["handoff"]) >= {"enabled", "active", "rounds"}
        assert "staleness_s" in glob and "manager" in glob
        assert set(glob["manager"]) >= {
            "pending_hits", "oldest_hit_age_s", "unsynced_keys",
        }
    finally:
        await client.close()
        await d.close()


@async_test
async def test_debug_endpoints_disabled_by_config():
    import aiohttp

    from gubernator_tpu.service.daemon import Daemon

    d = await Daemon.spawn(daemon_config(debug_endpoints=False))
    try:
        async with aiohttp.ClientSession() as s:
            async with s.get(
                f"http://{d.conf.http_address}/v1/debug/table"
            ) as r:
                assert r.status == 404
    finally:
        await d.close()


# ---------------------------------------------------------------- staleness


def test_pending_hits_age_monotonic_and_cleared():
    import time as _time

    from gubernator_tpu.ops.batch import HostBatch, pack_columns
    from gubernator_tpu.parallel.global_sync import PendingHits

    rng = np.random.default_rng(3)
    cols = _mixed_cols(rng, 8)
    hb, _err = pack_columns(cols, NOW)
    p = PendingHits()
    assert p.age_s() == 0.0
    p.merge(hb, np.arange(8), np.ones(8, dtype=np.int64),
            np.zeros(8, dtype=np.int32))
    a1 = p.age_s()
    _time.sleep(0.02)
    a2 = p.age_s()
    assert a2 > a1 >= 0.0  # monotonic while un-drained
    p.take(3)  # partial drain keeps the (conservative) age
    assert p.age_s() >= a2
    p.take(100)  # full drain clears it
    assert p.age_s() == 0.0
    p.merge(hb, np.arange(8), np.ones(8, dtype=np.int64),
            np.zeros(8, dtype=np.int32))
    assert p.age_s() < a2  # re-anchored at the new first entry
    p.clear()
    assert p.age_s() == 0.0


@async_test
async def test_global_staleness_gauge_under_paused_sync():
    """With the sync loop effectively paused (huge GlobalSyncWait), queued
    GLOBAL hits age monotonically and the gauge reports it; a drained queue
    reads 0."""
    from gubernator_tpu.proto import gubernator_pb2 as pb
    from gubernator_tpu.service.daemon import Daemon
    from gubernator_tpu.service.metrics import parse_metrics

    conf = daemon_config()
    conf.behaviors = BehaviorConfig(
        global_sync_wait_ms=600_000.0,  # paused for this test's lifetime
        batch_timeout_ms=5000.0, global_timeout_ms=5000.0,
    )
    d = await Daemon.spawn(conf)
    try:
        assert d.global_sync_staleness_s() == 0.0
        item = pb.RateLimitReq(name="gs", unique_key="k", hits=2, limit=10,
                               duration=60_000)
        d.global_manager.queue_hit("gs_k", item)
        a1 = d.global_sync_staleness_s()
        await asyncio.sleep(0.05)
        a2 = d.global_sync_staleness_s()
        assert a2 > a1 >= 0.0
        # more hits on the SAME key do not reset the age
        d.global_manager.queue_hit("gs_k", item)
        assert d.global_sync_staleness_s() >= a2
        # the /metrics render refreshes the gauge
        import aiohttp

        async with aiohttp.ClientSession() as s:
            async with s.get(f"http://{d.conf.http_address}/metrics") as r:
                scraped = parse_metrics(await r.text())
        assert scraped["gubernator_global_sync_staleness_seconds"][()] >= a2
        # a successful drain (no peers → keys dropped) zeroes it
        await d.global_manager._send_hits()
        assert d.global_sync_staleness_s() == 0.0
    finally:
        await d.close()


# ------------------------------------------------------------ otel satellites


def test_exporter_from_env_resource_attributes():
    from gubernator_tpu.otel import exporter_from_env

    exp = exporter_from_env({
        "OTEL_EXPORTER_OTLP_ENDPOINT": "http://127.0.0.1:1",
        "OTEL_SERVICE_NAME": "svc-a",
        "OTEL_RESOURCE_ATTRIBUTES":
            "service.name=ignored,host.name=node-3,region=us%2Deast,bad",
    })
    try:
        assert exp.service_name == "svc-a"  # OTEL_SERVICE_NAME wins
        assert exp.resource_attributes == {
            "host.name": "node-3", "region": "us-east",
        }
        payload = exp._payload([{"traceId": "0" * 32, "spanId": "1" * 16,
                                 "name": "x", "kind": 2,
                                 "startTimeUnixNano": "1",
                                 "endTimeUnixNano": "2"}])
        import json

        attrs = json.loads(payload)["resourceSpans"][0]["resource"][
            "attributes"
        ]
        by_key = {a["key"]: a["value"] for a in attrs}
        assert by_key["service.name"] == {"stringValue": "svc-a"}
        assert by_key["host.name"] == {"stringValue": "node-3"}
        assert by_key["region"] == {"stringValue": "us-east"}
    finally:
        exp.close()

    # service.name from the resource attrs when OTEL_SERVICE_NAME is unset
    exp2 = exporter_from_env({
        "OTEL_EXPORTER_OTLP_ENDPOINT": "http://127.0.0.1:1",
        "OTEL_RESOURCE_ATTRIBUTES": "service.name=from-attrs",
    })
    try:
        assert exp2.service_name == "from-attrs"
        assert "service.name" not in exp2.resource_attributes
    finally:
        exp2.close()


def test_otel_span_counters_reflect_exporter():
    from gubernator_tpu.service.metrics import DaemonMetrics, parse_metrics

    exp = StubExporter()  # exported=3, dropped=1, export_errors=0
    old = tracing.exporter
    tracing.set_exporter(exp)
    try:
        m = DaemonMetrics()
        scraped = parse_metrics(m.render().decode())
        assert scraped["gubernator_otel_spans_exported_total"][()] == 3
        assert scraped["gubernator_otel_spans_dropped_total"][()] == 1
        assert scraped["gubernator_otel_spans_export_errors_total"][()] == 0
    finally:
        tracing.set_exporter(old)


def test_otlp_record_carries_attributes_and_links():
    from gubernator_tpu.otel import OTLPJsonExporter

    exp = OTLPJsonExporter("http://127.0.0.1:1")
    try:
        parent = tracing.new_span()
        link = tracing.new_span()
        exp.record("dispatch", parent, "", 1, 2,
                   attributes={"batch.rows": 42, "batch.fused": True,
                               "note": "x"},
                   links=[link], kind=1)
        entry = exp._buf[-1]
        assert entry["kind"] == 1
        by_key = {a["key"]: a["value"] for a in entry["attributes"]}
        assert by_key["batch.rows"] == {"intValue": "42"}
        assert by_key["batch.fused"] == {"boolValue": True}
        assert by_key["note"] == {"stringValue": "x"}
        assert entry["links"] == [
            {"traceId": link.trace_id, "spanId": link.span_id}
        ]
    finally:
        exp.close()


def test_pending_link_registry_bounded_and_popped():
    a, b = tracing.new_span(), tracing.new_span()
    tracing.add_span_link(a, b)
    tracing.add_span_link(a, b)
    assert len(tracing.take_span_links(a.span_id)) == 2
    assert tracing.take_span_links(a.span_id) == []  # popped
    tracing.add_span_link(None, b)  # no-ops never register
    tracing.add_span_link(a, None)
    assert tracing.take_span_links(a.span_id) == []
