"""Trace propagation across the peer mesh — the MetadataCarrier analog.

The reference injects W3C TraceContext into `RateLimitReq.Metadata` on the
forwarding side and extracts it on the owner so one client request is a single
distributed trace across daemons (reference metadata_carrier.go:19-40,
peer_client.go:140-142, gubernator.go:522-524). OTEL itself is not a baked-in
dependency here, so this module implements the W3C `traceparent` header format
directly (https://www.w3.org/TR/trace-context/) over a contextvar, plus an
optional span-event hook embedders can point at their own tracer.
"""

from __future__ import annotations

import contextvars
import gc
import random
import threading
import time
from dataclasses import dataclass
from typing import Callable, Dict, List, Mapping, Optional

from jax.profiler import TraceAnnotation

TRACEPARENT_KEY = "traceparent"
_FLAG_SAMPLED = 0x01


@dataclass(frozen=True)
class SpanContext:
    trace_id: str  # 32 lowercase hex chars
    span_id: str  # 16 lowercase hex chars
    flags: int = _FLAG_SAMPLED

    def to_traceparent(self) -> str:
        return f"00-{self.trace_id}-{self.span_id}-{self.flags:02x}"


_current: contextvars.ContextVar[Optional[SpanContext]] = contextvars.ContextVar(
    "gubernator_tpu_span", default=None
)

# the most recently ENDED scope's span in this context: transport-layer
# metrics (grpc_request_duration) observe AFTER the handler's scope closed,
# so this is how a request-duration bucket gets the request's trace_id as
# its OpenMetrics exemplar
_last_ended: contextvars.ContextVar[Optional[SpanContext]] = (
    contextvars.ContextVar("gubernator_tpu_last_span", default=None)
)


def last_ended_span() -> Optional[SpanContext]:
    return _last_ended.get()

# embedder hook: called with (name, SpanContext) whenever a scope starts;
# wire this to a real tracer (OTEL etc.) if you have one
span_hook: Optional[Callable[[str, SpanContext], None]] = None

# optional exporter: an object with record(name, span, parent_span_id,
# start_ns, end_ns, *, attributes=None, links=(), kind=...); end_scope and
# record_span feed it finished spans. Wired by the daemon from the standard
# OTEL_* envs (gubernator_tpu.otel.OTLPJsonExporter).
exporter = None


def set_exporter(exp) -> None:
    global exporter
    exporter = exp


@dataclass
class Scope:
    """One open scope (returned by start_scope, consumed by end_scope):
    carries what the exporter needs to emit a finished span."""

    token: object
    name: str
    span: SpanContext
    parent_span_id: str
    start_ns: int
    attributes: Optional[dict] = None


# ---------------------------------------------------------------- span links
# Batching breaks parent-child causality: a request span cannot parent the
# dispatch span that served it (one dispatch serves many requests, and it
# outlives none of them cleanly). OTLP span LINKS restore the edge — the
# batcher registers "request span → dispatch span" links here while the
# request scope is still open, and end_scope attaches them to the finished
# span. Bounded: an abandoned scope (exceptions, exporter off) must not leak.
_links_lock = threading.Lock()
_pending_links: "Dict[str, List[SpanContext]]" = {}
_MAX_LINK_SPANS = 4096  # open spans tracked
_MAX_LINKS_PER_SPAN = 16  # a request split across local/global/forward rows


def add_span_link(span: Optional[SpanContext], target: Optional[SpanContext]) -> None:
    """Register a link from `span` (whose scope is still open — e.g. the
    request scope awaiting its batch slice) to `target` (e.g. the dispatch
    span that served it). Attached when the span's scope ends."""
    if span is None or target is None:
        return
    with _links_lock:
        lst = _pending_links.setdefault(span.span_id, [])
        if len(lst) < _MAX_LINKS_PER_SPAN:
            lst.append(target)
        while len(_pending_links) > _MAX_LINK_SPANS:
            _pending_links.pop(next(iter(_pending_links)))


def take_span_links(span_id: str) -> List[SpanContext]:
    with _links_lock:
        return _pending_links.pop(span_id, [])


def record_span(
    name: str,
    span: SpanContext,
    parent_span_id: str,
    start_ns: int,
    end_ns: int,
    attributes: Optional[dict] = None,
    links=(),
    kind: int = 1,
) -> None:
    """Emit one already-finished span straight to the exporter — the scope
    machinery (contextvar set/reset) is wrong for spans whose lifetime
    crosses threads and requests, like a batcher flush and its pipeline
    stage children. No-op without an exporter or when sampled out."""
    if exporter is not None and span.flags & 0x01:
        exporter.record(
            name, span, parent_span_id, start_ns, end_ns,
            attributes=attributes, links=links, kind=kind,
        )


# ------------------------------------------------------------ stage timing
# ONE way to time a stage of the serving path (docs/tracing.md "Spans on the
# profiler's clock"). A `stage` block takes one perf_counter interval on the
# thread that does the work and hands it to three readers: the
# gubernator_tpu_stage_duration{stage} histogram (exemplar = the parent
# trace), the JAX profiler (a `gub:<stage>` host span on the device trace's
# clock, when a profile is being taken) and the OTLP exporter (a child span,
# when one is set). Nothing switches it: with no profile running and no
# exporter it costs the clock pair, one flag test and the histogram sample.


@dataclass
class Dispatch:
    """One batcher dispatch as its stages see it: `seq` and `rows` ride on
    every profiler span under it (`dispatch=<seq>` joins gub:put/issue/
    fetch of one flush across threads), `span` parents the exported child
    spans (None without an exporter), and `work_s` adds up the intervals
    timed under it, so the batcher can state the dispatch's self time
    (`dispatch_wait`: the hand-offs from thread to thread and the one
    crossing back onto the loop). `tail`, where the batcher sets one, is
    what the worker thread that holds the dispatch's answer does with it
    before that crossing (`tail(rc)` gives what crosses): the batcher's
    `encode` stage. `origin` is how the chunk came to be dispatched, three
    clocks the batcher had read anyway (`perf_counter`: its oldest entry's
    enqueue, the instant the flush worker that took it came free, the
    dispatch's start in the callback that closed the chunk): the dispatch's
    first profiler span turns them into its `slot_us`, `window_us` and
    `closed_us` stats (`stage.__enter__`); with no profile running nobody
    reads them."""

    seq: int
    rows: int
    span: Optional[SpanContext] = None
    work_s: float = 0.0
    tail: Optional[Callable] = None
    origin: Optional[tuple] = None


def _origin_stats(origin: tuple, now: float) -> dict:
    """How a dispatch got to its first stage, as that span's stats, each in
    us and one after another back from the span's start: `closed_us`, the
    chunk was closed and this stage had not begun (the dispatch's start on
    the loop, the hop onto the stage's pool, the wait for one of its
    threads); `window_us` before that, a flush worker was free and held the
    window open; `slot_us` before that, the chunk's oldest entry lay in the
    queue and every flush worker was in a dispatch of its own (0 for a
    worker that was idle when the entry came)."""
    t_enq, t_free, t_closed = origin
    t_open = min(max(t_enq, t_free), t_closed)
    return {
        "closed_us": round((now - t_closed) * 1e6),
        "window_us": round((t_closed - t_open) * 1e6),
        "slot_us": round((t_open - min(t_enq, t_open)) * 1e6),
    }


# The dispatch stage (put, put_miss, issue, fetch) that is open on this
# thread. Those run from start to end on one worker thread, so code they
# call (the mesh engine's staging and un-routing) can time its own parts
# under them without the dispatch being handed down through every call.
_open = threading.local()


def observe(stage_name: str, metrics, dt_s: float,
            span: Optional[SpanContext] = None) -> None:
    """Record an interval that has already been measured: the histogram
    sample and, when an exporter is set, the parent's trace_id as the
    sample's OpenMetrics exemplar and a child span under `span` (wall-clock
    ns derived from the same interval). Without an exporter the trace_id
    would resolve to nothing, and a dozen exemplars an RPC are not free on
    the event loop. Direct callers are the stages that are waits or
    differences (queue, door_wait, dispatch_wait, ...), which wrap no work
    and so get no profiler span."""
    traced = span is not None and exporter is not None
    if metrics is not None:
        child = metrics.stage_child(stage_name)
        if traced:
            child.observe(dt_s, exemplar={"trace_id": span.trace_id})
        else:
            child.observe(dt_s)
    if traced and dt_s > 0:
        # (an inline parse's door_wait is 0 by definition: a sample, no span)
        end_ns = time.time_ns()
        record_span(
            stage_name, new_span(span), span.span_id,
            end_ns - int(dt_s * 1e9), end_ns,
        )


class stage:
    """`with tracing.stage("put", metrics, disp=disp): ...` around work, on
    the thread that does it. `span` (a request's) or `disp` (a dispatch's
    context) names the parent; keyword `stats` and later `note()`s become
    the profiler span's stats. `dt` holds the interval after the block.
    With `metrics=None` the block is a profiler span and a clock only.

    `stage.within(name)` opens a part of the dispatch stage that is open on
    this thread (`gub:shard_route` inside `gub:put`): it samples into the
    same metrics and carries the same `dispatch=<seq>`, and its interval is
    already in the outer stage's, so it adds nothing to the dispatch's
    `work_s`. Under no dispatch stage it is a profiler span and a clock."""

    __slots__ = ("name", "metrics", "span", "disp", "stats", "dt", "_t0",
                 "_ann", "_part", "_outer")

    def __init__(self, name: str, metrics=None, span: Optional[SpanContext] = None,
                 disp: Optional[Dispatch] = None, **stats):
        self.name, self.metrics, self.span, self.disp = name, metrics, span, disp
        self.stats = stats
        self.dt = 0.0
        self._ann = None
        self._part = False

    @classmethod
    def within(cls, name: str, **stats) -> "stage":
        outer = getattr(_open, "stage", None)
        if outer is None:
            st = cls(name, **stats)
        else:
            st = cls(name, outer.metrics, disp=outer.disp, **stats)
        st._part = True
        return st

    def __enter__(self) -> "stage":
        if self.disp is not None and not self._part:
            self._outer = getattr(_open, "stage", None)
            _open.stage = self
        if TraceAnnotation.is_enabled():
            stats, disp = self.stats, self.disp
            if disp is not None:
                # a part may state the rows it handles itself
                stats = {"dispatch": disp.seq, "rows": disp.rows, **stats}
                if disp.origin is not None:
                    # the dispatch's first span under a profile says how
                    # its chunk came here (the idle reader's join)
                    stats.update(_origin_stats(disp.origin, time.perf_counter()))
                    disp.origin = None
            self._ann = TraceAnnotation("gub:" + self.name, **stats)
            self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def note(self, **stats) -> None:
        """Stats known only once the work is under way (a window's rows)."""
        if self._ann is not None:
            self._ann.set_metadata(**stats)

    def __exit__(self, *exc) -> bool:
        self.dt = dt = time.perf_counter() - self._t0
        if self._ann is not None:
            self._ann.__exit__(*exc)
        disp = self.disp
        if disp is not None and not self._part:
            _open.stage = self._outer
            disp.work_s += dt
        observe(self.name, self.metrics, dt,
                disp.span if disp is not None else self.span)
        return False


# ids for spans: one generator of this module's own, so that a caller who
# seeds the `random` module does not make two daemons mint the same ids
_id_bits = random.Random().getrandbits


def current_span() -> Optional[SpanContext]:
    return _current.get()


def new_span(parent: Optional[SpanContext] = None) -> SpanContext:
    """A child of `parent` (same trace), or a fresh root. A W3C id has to be
    unique, not unguessable: the ids come from the process's Mersenne
    Twister, seeded from the system once, and not from `os.urandom`, a
    system call that drops the GIL twice for every RPC on the event loop."""
    return SpanContext(
        trace_id=parent.trace_id if parent else f"{_id_bits(128):032x}",
        span_id=f"{_id_bits(64):016x}",
        flags=parent.flags if parent else _FLAG_SAMPLED,
    )


def start_scope(name: str, parent: Optional[SpanContext] = None):
    """Begin a scope: set the current span (child of parent or of the ambient
    span) and return a Scope to pass to end_scope. The
    tracing.StartNamedScope analog."""
    eff_parent = parent if parent is not None else _current.get()
    span = new_span(eff_parent)
    if span_hook is not None:
        span_hook(name, span)
    token = _current.set(span)
    return Scope(
        token=token,
        name=name,
        span=span,
        parent_span_id=eff_parent.span_id if eff_parent else "",
        start_ns=time.time_ns(),
    )


def end_scope(scope) -> None:
    if isinstance(scope, Scope):
        _current.reset(scope.token)
        _last_ended.set(scope.span)
        # pop pending links unconditionally — an unsampled or unexported
        # scope must not strand registry entries
        links = take_span_links(scope.span.span_id)
        # honor the W3C sampled flag: traces sampled out upstream
        # (traceparent ...-00) must not produce orphan partial traces here
        if exporter is not None and scope.span.flags & 0x01:
            exporter.record(
                scope.name, scope.span, scope.parent_span_id,
                scope.start_ns, time.time_ns(),
                attributes=scope.attributes, links=links,
            )
    else:  # raw contextvars token (embedders on the old surface)
        _current.reset(scope)


def parse_traceparent(value: str) -> Optional[SpanContext]:
    """Parse a W3C traceparent header; None on anything malformed (invalid
    inbound context must not break serving)."""
    parts = value.strip().lower().split("-")
    if len(parts) != 4:
        return None
    version, trace_id, span_id, flags = parts
    if len(version) != 2 or len(trace_id) != 32 or len(span_id) != 16:
        return None
    if trace_id == "0" * 32 or span_id == "0" * 16:
        return None
    try:
        int(version, 16), int(trace_id, 16), int(span_id, 16)
        f = int(flags, 16)
    except ValueError:
        return None
    return SpanContext(trace_id=trace_id, span_id=span_id, flags=f)


def inject(metadata) -> None:
    """Write the current span into a RateLimitReq.metadata map (the carrier's
    Set side, metadata_carrier.go:33-36). No-op when there is no active span."""
    span = _current.get()
    if span is not None:
        metadata[TRACEPARENT_KEY] = span.to_traceparent()


def extract(metadata: Mapping[str, str]) -> Optional[SpanContext]:
    """Read a span from a RateLimitReq.metadata map (the carrier's Get side,
    metadata_carrier.go:24-31)."""
    raw = metadata.get(TRACEPARENT_KEY, "")
    return parse_traceparent(raw) if raw else None


# ------------------------------------------------------- what the host did
# Three things a stage's wall interval cannot say (docs/observability.md,
# line 1 of the budget walk): which thread was on a CPU, how long a ready
# callback waits for the event loop, and how long the collector held the
# GIL. All three are counted here and cost nothing on the path of an RPC or
# of a dispatch: the threads' own CPU clocks are read when somebody asks
# (`/v1/debug/pipeline`, `/metrics`), the loop's lag is one callback every
# 20 ms, the collector's pauses two callbacks a collection.

# thread name -> pool: the event loop by its ident, executor threads by
# their `thread_name_prefix` ("door_0"), the exporter's worker by its name
POOLS = ("loop", "door", "prep", "engine", "fetch", "ckpt", "telemetry",
         "put", "otel-export")
LOOP_LAG_PERIOD_S = 0.02


def _thread_cpu_s(native_id: int) -> float:
    """CPU seconds of one live thread of this process from its own clock:
    the id glibc's `pthread_getcpuclockid` builds (Linux: (~tid << 3) | 6,
    the per-thread scheduler clock), from the kernel's thread id. Not
    `time.pthread_getcpuclockid(ident)` itself, which reads freed memory
    for a thread that has just exited; here the kernel looks the id up and
    a thread that is gone is an OSError (tests hold the two to agree)."""
    return time.clock_gettime((~native_id << 3) | 6)


class HostClocks:
    """One daemon's account of the host side: `snapshot()` is the `threads`
    block of `/v1/debug/pipeline` (and what the
    gubernator_tpu_thread_cpu_seconds_total collector renders), `start()`
    arms the loop-lag ticker and the collector callback on the running
    loop, `stop()` takes both away. The clocks are the process's, so two
    daemons in one process read the same threads; each samples `loop_lag`
    and `gc_pause` into its own metrics."""

    def __init__(self, metrics=None):
        self.metrics = metrics
        if metrics is not None:
            # the two stages' histogram children exist before the first
            # sample: the collector's callback runs wherever an allocation
            # tripped it, inside the family's `labels()` lock too, and may
            # not ask for that lock itself (observe() then finds the child)
            metrics.stage_child("loop_lag")
            metrics.stage_child("gc_pause")
        self._loop = None
        self._loop_thread: Optional[int] = None  # native id
        self._tick = None  # the ticker's pending TimerHandle
        self._due = 0.0
        # live threads: native id -> [pool, last reading]; a pool's threads
        # that have exited keep their last reading in `_retired`, so that no
        # pool's sum ever falls
        self._live: Dict[int, list] = {}
        self._retired: Dict[str, float] = {}
        self.gc_pause_s = [0.0, 0.0, 0.0]
        self.gc_collections = [0, 0, 0]
        self._gc_t0 = 0.0

    # ---- arming
    def start(self) -> None:
        """On the event-loop thread, once the loop runs."""
        import asyncio

        self._loop = loop = asyncio.get_running_loop()
        self._loop_thread = threading.get_native_id()
        self._due = loop.time() + LOOP_LAG_PERIOD_S
        self._tick = loop.call_at(self._due, self._on_tick)
        gc.callbacks.append(self._on_gc)

    def stop(self) -> None:
        if self._tick is not None:
            self._tick.cancel()
            self._tick = None
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)

    def _on_tick(self) -> None:
        """`loop_lag`: how late the loop ran a callback that was due at a
        fixed instant, which is what any ready callback waits (an RPC meets
        it at every crossing: bytes read -> handler started, door worker
        done -> coroutine resumed, dispatch done -> answer, bytes written).
        The due time advances by the period whatever the lag, so a loop
        blocked for 100 ms yields the samples 100, 80, 60, ... of the ticks
        it missed: 50 samples a second, their mean the wait of a callback
        that becomes ready at a random instant."""
        loop = self._loop
        observe("loop_lag", self.metrics, max(0.0, loop.time() - self._due))
        self._due += LOOP_LAG_PERIOD_S
        self._tick = loop.call_at(self._due, self._on_tick)

    def _on_gc(self, phase: str, info: dict) -> None:
        """The collector's start/stop pair, on whichever thread tripped it,
        with the GIL held from one to the other: every thread's stall.
        Generations 1 and 2 are `gc_pause` samples; generation 0 can run
        hundreds of times a second and is counted and summed only."""
        if phase == "start":
            self._gc_t0 = time.perf_counter()
            return
        dt = time.perf_counter() - self._gc_t0
        gen = info["generation"]
        self.gc_pause_s[gen] += dt
        self.gc_collections[gen] += 1
        if gen:
            observe("gc_pause", self.metrics, dt)

    # ---- reading
    def _pool_of(self, thread: threading.Thread) -> Optional[str]:
        if thread.native_id == self._loop_thread:
            return "loop"
        pool = thread.name.rpartition("_")[0] or thread.name
        return pool if pool in POOLS[1:] else None

    def snapshot(self) -> dict:
        """{"wall_ms", "process_cpu_ms", "pools_cpu_ms", "other_cpu_ms",
        <pool>: {"cpu_ms", "threads"} for the loop and every pool that has
        started, "gc_pause_ms", "gc_collections", "gc_generations"}: all
        monotone, ms. `process_cpu_ms` is every thread of the process (gRPC's
        pollers, XLA's and the device runtime's among them) and is read
        last, so that it is never less than the pools' sum; `other_cpu_ms`
        is the difference. Called on the event-loop thread (the HTTP
        handlers of both endpoints run there)."""
        seen = set()
        for th in threading.enumerate():
            tid = th.native_id
            pool = self._pool_of(th) if tid is not None else None
            if pool is None:
                continue
            try:
                cpu = _thread_cpu_s(tid)
            except OSError:  # it exited since enumerate() saw it
                continue
            seen.add(tid)
            last = self._live.get(tid)
            if last is not None and (last[0] != pool or cpu < last[1]):
                self._retire(tid)  # the kernel gave the id to a new thread
                last = None
            if last is None:
                self._live[tid] = [pool, cpu]
            else:
                last[1] = cpu
        for tid in [t for t in self._live if t not in seen]:
            self._retire(tid)
        pools: Dict[str, dict] = {}
        for pool, cpu in self._retired.items():
            pools[pool] = {"cpu_ms": cpu * 1e3, "threads": 0}
        for pool, cpu in self._live.values():
            acc = pools.setdefault(pool, {"cpu_ms": 0.0, "threads": 0})
            acc["cpu_ms"] += cpu * 1e3
            acc["threads"] += 1
        in_pools = sum(p["cpu_ms"] for p in pools.values())
        process = max(time.process_time() * 1e3, in_pools)
        return {
            "wall_ms": time.monotonic() * 1e3,
            "process_cpu_ms": process,
            "pools_cpu_ms": in_pools,
            "other_cpu_ms": process - in_pools,
            **{p: pools[p] for p in POOLS if p in pools},
            "gc_pause_ms": sum(self.gc_pause_s) * 1e3,
            "gc_collections": sum(self.gc_collections),
            "gc_generations": [
                {"pause_ms": s * 1e3, "collections": n}
                for s, n in zip(self.gc_pause_s, self.gc_collections)
            ],
        }

    def _retire(self, tid: int) -> None:
        pool, cpu = self._live.pop(tid)
        self._retired[pool] = self._retired.get(pool, 0.0) + cpu
