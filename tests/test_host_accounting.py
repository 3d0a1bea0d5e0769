"""What the host was doing, counted inside the program (ISSUE 38): thread
CPU clocks by pool, event-loop lag, collector pauses, the span stats that
join a dispatch to how its chunk came to be dispatched, and the request span
that is minted only where somebody can read it."""

import asyncio
import functools
import gc
import glob
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import pytest

from gubernator_tpu import tracing
from gubernator_tpu.proto import gubernator_pb2 as pb
from gubernator_tpu.service.metrics import DaemonMetrics, parse_metrics

from tests.cluster import daemon_config


def async_test(fn):
    @functools.wraps(fn)
    def wrapper(*a, **k):
        asyncio.run(fn(*a, **k))

    return wrapper


def _raw_request(tag, n):
    return pb.GetRateLimitsReq(requests=[
        pb.RateLimitReq(name="host", unique_key=f"{tag}-{i}", hits=1,
                        limit=1000, duration=60_000)
        for i in range(n)
    ]).SerializeToString()


def _burn(seconds):
    """Spend `seconds` of this thread's CPU clock."""
    end = time.thread_time() + seconds
    while time.thread_time() < end:
        pass


def _stage(metrics, stage):
    """(sum seconds, count) of one gubernator_tpu_stage_duration child."""
    series = parse_metrics(metrics.render().decode())
    key = (("stage", stage),)
    return (
        series.get("gubernator_tpu_stage_duration_sum", {}).get(key, 0.0),
        series.get("gubernator_tpu_stage_duration_count", {}).get(key, 0.0),
    )


# ------------------------------------------------------------ thread clocks


def test_thread_clock_id_is_the_one_glibc_builds():
    """`_thread_cpu_s` reads the clock `pthread_getcpuclockid` names, from
    the kernel's thread id, and a thread that has exited is an OSError."""
    me = threading.current_thread()
    _burn(0.01)
    theirs = time.clock_gettime(time.pthread_getcpuclockid(me.ident))
    ours = tracing._thread_cpu_s(me.native_id)
    assert theirs <= ours <= theirs + 0.005
    t = threading.Thread(target=lambda: None)
    t.start()
    tid = t.native_id
    t.join()  # the interpreter's end of it; the kernel's follows at once
    deadline = time.monotonic() + 2.0
    with pytest.raises(OSError):
        while time.monotonic() < deadline:
            tracing._thread_cpu_s(tid)
            time.sleep(0.005)


@async_test
async def test_threads_block_names_pools_and_puts_cpu_where_it_was_burned():
    """A pool thread that burns 50 ms of CPU shows it in its pool and not in
    the loop's; every number is monotone across two snapshots, pools + other
    = process, and a pool whose thread has exited keeps its sum."""
    host = tracing.HostClocks()
    host.start()
    pool = ThreadPoolExecutor(max_workers=2, thread_name_prefix="prep")
    try:
        loop = asyncio.get_running_loop()
        await loop.run_in_executor(pool, _burn, 0.001)  # the thread exists
        a = host.snapshot()
        await loop.run_in_executor(pool, _burn, 0.05)
        b = host.snapshot()
    finally:
        pool.shutdown(wait=True)
        host.stop()
    assert a["loop"]["threads"] == 1 and b["prep"]["threads"] >= 1
    assert 40.0 <= b["prep"]["cpu_ms"] - a["prep"]["cpu_ms"] <= 80.0
    assert 0.0 <= b["loop"]["cpu_ms"] - a["loop"]["cpu_ms"] < 5.0
    for snap in (a, b):
        assert snap["pools_cpu_ms"] == pytest.approx(
            sum(v["cpu_ms"] for v in snap.values() if isinstance(v, dict))
        )
        assert snap["pools_cpu_ms"] + snap["other_cpu_ms"] == pytest.approx(
            snap["process_cpu_ms"]
        )
        assert snap["other_cpu_ms"] >= 0.0
    for key in ("wall_ms", "process_cpu_ms", "pools_cpu_ms"):
        assert b[key] >= a[key]
    assert 50.0 <= b["wall_ms"] - a["wall_ms"] < 5_000.0
    c = host.snapshot()  # the pool is shut down: its threads are gone
    assert c["prep"]["threads"] == 0
    assert c["prep"]["cpu_ms"] >= b["prep"]["cpu_ms"]


@async_test
async def test_daemon_reports_threads_in_pipeline_and_metrics():
    """`/v1/debug/pipeline` carries the block with the loop and every pool
    that has started, and /metrics the same numbers as one counter family."""
    from gubernator_tpu.service.daemon import Daemon

    d = await Daemon.spawn(daemon_config())
    try:
        big = _raw_request("big", 300)
        assert len(big) >= d.DOOR_OFFLOAD_BYTES
        for k in range(3):
            await d.get_rate_limits_raw(big)
        a = d.debug_pipeline()["threads"]
        for k in range(5):
            await d.get_rate_limits_raw(_raw_request(f"r{k}", 300))
        b = d.debug_pipeline()["threads"]
        text = d.metrics.render().decode()
    finally:
        await d.close()
    for pool in ("loop", "door", "prep", "engine", "fetch"):
        assert b[pool]["threads"] >= 1, pool
        assert b[pool]["cpu_ms"] >= a[pool]["cpu_ms"] > 0.0
    assert b["pools_cpu_ms"] + b["other_cpu_ms"] == pytest.approx(b["process_cpu_ms"])
    assert b["wall_ms"] > a["wall_ms"]
    fam = parse_metrics(text)["gubernator_tpu_thread_cpu_seconds_total"]
    by_thread = {dict(k)["thread"]: v for k, v in fam.items()}
    assert {"loop", "door", "prep", "engine", "fetch", "other"} <= set(by_thread)
    assert by_thread["loop"] >= b["loop"]["cpu_ms"] / 1e3 > 0.0


# ----------------------------------------------------------------- loop lag


def _lag_buckets(m):
    """(count, sum, le(edge)) of the loop_lag histogram."""
    series = parse_metrics(m.render().decode())
    key = (("stage", "loop_lag"),)
    b = series["gubernator_tpu_stage_duration_bucket"]
    return (
        series["gubernator_tpu_stage_duration_count"][key],
        series["gubernator_tpu_stage_duration_sum"][key],
        lambda edge: b[(("le", edge), ("stage", "loop_lag"))],
    )


@async_test
async def test_loop_lag_samples_a_blocked_loop_and_stays_low_on_an_idle_one():
    """50 samples a second whatever the load; an idle loop's stay under 5 ms
    (on a test host loaded by other workers: most of them), a loop blocked for
    100 ms yields the lateness of the tick that was due and of the ticks it
    missed behind it. The histogram is read with the ticker stopped: the
    reading itself holds the loop."""
    idle = DaemonMetrics()
    host = tracing.HostClocks(idle)
    host.start()
    await asyncio.sleep(0.3)
    host.stop()
    n, _s, le = _lag_buckets(idle)
    assert 10 <= n <= 16  # one sample every 20 ms
    assert le("0.005") >= n - 2 or le("0.005") >= n // 2 and le("0.05") >= n - 1
    await asyncio.sleep(0.06)
    assert _lag_buckets(idle)[0] == n  # the ticker is gone

    blocked = DaemonMetrics()
    host = tracing.HostClocks(blocked)
    host.start()
    asyncio.get_running_loop().call_soon(time.sleep, 0.1)
    await asyncio.sleep(0.25)
    host.stop()
    n, s, le = _lag_buckets(blocked)
    assert 8 <= n <= 16
    assert le("0.25") - le("0.05") >= 1  # the tick that was due: ~100 ms late
    assert 0.08 <= s <= 1.0  # ~ 100 + 80 + 60 + 40 + 20 ms


def test_loop_lag_of_a_blocked_tick_is_its_lateness():
    """One tick, by hand: due at t, run 100 ms late, it samples 100 ms and
    the next is due one period after the first, not after now."""
    class Loop:
        now = 10.0
        calls = []

        def time(self):
            return self.now

        def call_at(self, when, fn):
            self.calls.append(when)

    m = DaemonMetrics()
    host = tracing.HostClocks(m)
    host._loop, host._due = Loop(), 10.0
    Loop.now = 10.1
    host._on_tick()
    s, n = _stage(m, "loop_lag")
    assert n == 1 and 0.08 <= s <= 0.13
    assert Loop.calls == [pytest.approx(10.0 + tracing.LOOP_LAG_PERIOD_S)]


# ----------------------------------------------------------- collector pauses


@async_test
async def test_gc_pause_is_sampled_counted_and_removed_at_shutdown():
    """A forced collection is one `gc_pause` sample and moves the block's
    pause and count; the callback goes with the daemon, so a second daemon
    in the process counts each collection once."""
    from gubernator_tpu.service.daemon import Daemon

    n_callbacks = len(gc.callbacks)
    d = await Daemon.spawn(daemon_config())
    try:
        assert len(gc.callbacks) == n_callbacks + 1
        a = d.debug_pipeline()["threads"]
        _s, n0 = _stage(d.metrics, "gc_pause")
        gc.collect()
        b = d.debug_pipeline()["threads"]
        _s, n1 = _stage(d.metrics, "gc_pause")
        assert n1 - n0 >= 1  # an automatic collection may have joined it
        gen2 = lambda t: t["gc_generations"][2]
        assert gen2(b)["collections"] - gen2(a)["collections"] >= 1
        assert gen2(b)["pause_ms"] > gen2(a)["pause_ms"]
        assert b["gc_pause_ms"] > a["gc_pause_ms"]
        assert b["gc_collections"] > a["gc_collections"]
        assert b["gc_pause_ms"] == pytest.approx(
            sum(g["pause_ms"] for g in b["gc_generations"])
        )
    finally:
        await d.close()
    assert len(gc.callbacks) == n_callbacks
    d2 = await Daemon.spawn(daemon_config())
    try:
        assert len(gc.callbacks) == n_callbacks + 1
        before = d2.host.gc_collections[2]
        _s, n0 = _stage(d2.metrics, "gc_pause")
        was = gc.isenabled()
        gc.disable()  # nothing but the forced collection between the reads
        try:
            gc.collect()
        finally:
            if was:
                gc.enable()
        assert d2.host.gc_collections[2] - before == 1
        assert _stage(d2.metrics, "gc_pause")[1] - n0 == 1
        stale = list(d.host.gc_collections)
        gc.collect()
        assert d.host.gc_collections == stale
    finally:
        await d2.close()
    assert len(gc.callbacks) == n_callbacks


def test_collector_callback_asks_for_no_lock_the_interrupted_code_may_hold():
    """A collection can start inside `labels()`, under the stage family's
    lock; the callback must not want that lock (it deadlocked the thread:
    the children of both stages exist before the first sample)."""
    m = DaemonMetrics()
    host = tracing.HostClocks(m)
    assert {"loop_lag", "gc_pause"} <= set(m._stage_children)
    done = threading.Event()

    def collect_under_the_lock():
        with m.stage_duration._lock:
            host._on_gc("start", {"generation": 2})
            host._on_gc("stop", {"generation": 2})
        done.set()

    t = threading.Thread(target=collect_under_the_lock, daemon=True)
    t.start()
    assert done.wait(5.0), "the callback waits for the family's lock"
    assert _stage(m, "gc_pause")[1] == 1


def test_generation_zero_is_counted_and_not_sampled():
    m = DaemonMetrics()
    host = tracing.HostClocks(m)
    for gen in (0, 0, 1, 2):
        host._on_gc("start", {"generation": gen})
        host._on_gc("stop", {"generation": gen, "collected": 0, "uncollectable": 0})
    assert host.gc_collections == [2, 1, 1]
    assert all(s >= 0.0 for s in host.gc_pause_s)
    assert _stage(m, "gc_pause")[1] == 2


# ------------------------------------------------- the idle reader's two joins


def test_origin_stats_lay_slot_window_and_closed_end_to_end():
    """The three stats of a dispatch's first span, worked by hand: enqueued
    at 1.000, the worker free at 1.010, closed at 1.012, the span starting
    at 1.015: 10 ms for a slot, 2 ms of window, 3 ms closed. A worker that
    was idle before the entry came waited for no slot."""
    st = tracing._origin_stats((1.000, 1.010, 1.012), 1.015)
    assert st == {"closed_us": 3000, "window_us": 2000, "slot_us": 10000}
    st = tracing._origin_stats((1.000, 0.200, 1.0004), 1.001)
    assert st["slot_us"] == 0 and st["window_us"] == 400
    assert 590 <= st["closed_us"] <= 610


def test_stage_builds_no_origin_stats_without_a_profile(monkeypatch):
    """With no profile running a stage reads no clock for the join and
    builds no stats dict: the origin stays on the dispatch, untouched."""
    called = []
    monkeypatch.setattr(tracing, "_origin_stats", lambda *a: called.append(a) or {})
    assert not tracing.TraceAnnotation.is_enabled()
    disp = tracing.Dispatch(seq=1, rows=2, origin=(1.0, 2.0, 3.0))
    with tracing.stage("put", None, disp=disp) as st:
        pass
    assert called == [] and disp.origin == (1.0, 2.0, 3.0)
    assert st._ann is None and st.stats == {}


@async_test
async def test_first_span_of_a_dispatch_carries_its_origin_and_programs_follow_issue(tmp_path):
    """Under a CPU profile every dispatch's first span (gub:put) carries
    `closed_us`, `window_us` and `slot_us`, once; and the join the idle
    reader makes holds in the rehearsal's trace: the first execution of the
    decide program at or after a dispatch's gub:issue start begins inside
    that dispatch's issue -> fetch interval (on the CPU the program's call,
    `PjitFunction(decide2...)`, stands in for the chip's "XLA Modules"
    event, which only a device plane has)."""
    import jax
    from jax.profiler import ProfileData

    from gubernator_tpu.service.daemon import Daemon

    d = await Daemon.spawn(daemon_config())
    try:
        await d.get_rate_limits_raw(_raw_request("warm", 16))
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1  # the benchmark launcher's
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
    spans, programs = {}, []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("gub:"):
                    spans.setdefault(ev.name[4:], []).append(
                        (dict(ev.stats), ev.start_ns, ev.start_ns + ev.duration_ns)
                    )
                elif ev.name.startswith("PjitFunction(decide2"):
                    programs.append(ev.start_ns)  # the CPU's stand-in: the call
    puts = spans["put"]
    assert puts and all(
        {"closed_us", "window_us", "slot_us", "dispatch", "rows"} <= set(st)
        for st, *_ in puts
    )
    assert all(st["closed_us"] >= 0 and st["slot_us"] >= 0 for st, *_ in puts)
    for later in ("issue", "fetch", "encode"):
        assert all("closed_us" not in st for st, *_ in spans[later]), later
    fetch_end = {st["dispatch"]: e for st, _s, e in spans["fetch"]}
    programs.sort()
    assert programs, "the trace holds no execution of the decide program"
    joined = 0
    for st, s, _e in spans["issue"]:
        first = next((p for p in programs if p >= s), None)
        if first is not None and st["dispatch"] in fetch_end:
            assert first <= fetch_end[st["dispatch"]]
            joined += 1
    assert joined >= 4


# --------------------------------------------- the span nobody reads is not made


@async_test
async def test_request_span_is_minted_only_where_somebody_can_read_it(monkeypatch):
    """A lone daemon with no exporter, no hook and no inbound traceparent
    opens no scope for a raw RPC; a hook, an exporter or the client's
    traceparent each bring it back."""
    from gubernator_tpu.service.daemon import Daemon

    scopes = []
    real = tracing.start_scope

    def counted(name, parent=None):
        scopes.append((name, parent))
        return real(name, parent)

    monkeypatch.setattr(tracing, "start_scope", counted)
    assert tracing.exporter is None and tracing.span_hook is None
    d = await Daemon.spawn(daemon_config())
    try:
        out = await d.get_rate_limits_raw(_raw_request("a", 5))
        assert len(pb.GetRateLimitsResp.FromString(out).responses) == 5
        assert scopes == [] and tracing.current_span() is None
        seen = []
        monkeypatch.setattr(tracing, "span_hook", lambda n, s: seen.append(n))
        await d.get_rate_limits_raw(_raw_request("b", 5))
        assert [n for n, _p in scopes] == ["GetRateLimits"] == seen
        monkeypatch.setattr(tracing, "span_hook", None)
        parent = tracing.new_span()
        req = pb.GetRateLimitsReq(requests=[pb.RateLimitReq(
            name="host", unique_key="c", hits=1, limit=10, duration=60_000,
            metadata={"traceparent": parent.to_traceparent()},
        )])
        await d.get_rate_limits_raw(req.SerializeToString())
        assert len(scopes) == 2 and scopes[1][1].trace_id == parent.trace_id
        await d.get_rate_limits_raw(_raw_request("d", 5))
        assert len(scopes) == 2
    finally:
        await d.close()
