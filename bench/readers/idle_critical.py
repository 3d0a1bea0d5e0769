"""Which stage of its own dispatch the chip was waiting for, gap by gap.

`host_spans` says how much of the busiest chip's idle time falls inside
*some* dispatch's host interval; with four dispatches in flight that is
nearly all of it, always. This reader gives every idle gap to ONE stage of
ONE dispatch: the dispatch whose program ended the gap, at the stage that
dispatch was in at each instant of the gap. All from the one `.xplane.pb` of
the traced sub-window: the chips' busy intervals and the program's `gub:`
spans (`host_spans.load`), and the executions of the device programs
(`xplane.load`, line "XLA Modules").

The rule. An idle gap of the busiest chip ends where a device program
starts. Two things come from the runtime's own launch events (PR 38's first
chip trace: every execution on "XLA Modules" carries a `run_id`, and so does
the host event `DoEnqueueProgram` that handed it to the device queue):

  the clock  the device plane's timestamps lay 2.0 ms BEFORE the host
             plane's in that trace (a program "started" 2 ms before it was
             enqueued), which is a fifth of a gap. No program starts before
             its enqueue, so the largest (enqueue - start) over the trace's
             programs is the offset (to within the shortest launch), and the
             chip's intervals are moved onto the host's clock by it.
  the join   a dispatch program (`match`, the decide step) was launched
             under the `gub:issue` span that began last before the
             program's ENQUEUE (host clock, exact by run_id; the program's
             own start where the trace holds no enqueue for it), and a
             dispatch keeps the programs that follow until the next
             dispatch, whose issue has started, has taken one: the runtime
             may hold a launch (a donated buffer still in use) past the
             next issue's start.

Walking back from the gap's end along the owning dispatch N's own
timeline, each part of the gap goes to what N was in:

  issue     N's gub:issue start -> the gap's end: the launch on the engine
            thread, and the time between two passes of one dispatch
  handoff   N waited for a thread: its gub:put end -> its gub:issue start
            (the engine thread ran another dispatch's issue, an apply, a
            checkpoint mark, or stood in the GIL's queue), and `closed_us`
            before its gub:put start (the chunk was closed, the dispatch had
            begun on the loop, no prep thread had taken it up yet)
  put       N's gub:put span(s), first start -> last end: staging, with
            gub:later_stage and the mesh engine's parts inside it
  slot      `slot_us`, ending `closed_us` + `window_us` before the gub:put
            start: N's oldest entry lay queued while every flush worker
            (= dispatch slot, `max_inflight`) was in a dispatch of its own:
            an earlier dispatch's fetch, encode and crossing back held it
  upstream  everything else: the `window_us` a free worker held the window
            open, and all time before N's oldest entry was enqueued (the
            door, the loop, gRPC, the client); and a gap ended by a program
            that is no dispatch's (a scan, an extract, a conversion), unless
            the dispatch programs on either side of it are one dispatch's,
            which makes it time between two passes: issue

`closed_us`, `window_us`, `slot_us` are stats of a dispatch's first span
(gubernator_tpu/tracing.py `_origin_stats`; docs/tracing.md). Each share is a
% of the idle time of the gaps so read (every idle interval of the window
but the last, which no program ends); the five sum to 100. A trace in which
no span carries `closed_us` (a program from before PR 38), or without a
dispatch program, or without a single enqueue event to set the clock by,
reads None. params: what (one of the five), match (regex over "XLA
Modules"). Unit: %.

Like `host_spans`, `read` runs this file in a child of its own
(`python bench/readers/idle_critical.py <trace dir> <platform> <match>`
prints the five shares) once per run.
"""

from __future__ import annotations

import bisect
import json
import os
import re
import subprocess
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH_DIR)
sys.path.insert(0, os.path.join(BENCH_DIR, "readers"))

import host_spans  # noqa: E402
import xplane  # noqa: E402

STAGES = ("slot", "put", "handoff", "issue", "upstream")
INF = float("inf")


ENQUEUE = "DoEnqueueProgram"  # the runtime's host event, stats run_id, device_ordinal


def load(path: str, platform: str) -> dict:
    """What `host_spans.load` returns, and "programs": {chip plane: [[name,
    start_ns, end_ns, run_id], ...]}, the executions on the chip's module
    line, and "enqueued": {device ordinal: {run_id: host start_ns}}."""
    from jax.profiler import ProfileData

    loaded = host_spans.load(path, platform)
    device_plane = re.compile(xplane.DEVICE_PLANES[platform])
    programs: dict = {}
    enqueued: dict = {}
    for plane in ProfileData.from_file(path).planes:
        if device_plane.match(plane.name):
            for line in plane.lines:
                if line.name == xplane.MODULE_LINE:
                    programs[plane.name] = [
                        [xplane.short_name(ev.name), float(ev.start_ns),
                         float(ev.start_ns) + float(ev.duration_ns),
                         dict(ev.stats).get("run_id")]
                        for ev in line.events
                    ]
        elif plane.name == host_spans.HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == ENQUEUE:
                        st = dict(ev.stats)
                        if "run_id" in st:
                            enqueued.setdefault(str(st.get("device_ordinal", 0)), {})[
                                str(st["run_id"])] = float(ev.start_ns)
    loaded["programs"], loaded["enqueued"] = programs, enqueued
    return loaded


def timelines(spans: list) -> dict:
    """{dispatch: [[from_ns, stage], ...]} sorted by time: the stage the
    dispatch is in from each instant on (before the first: upstream)."""
    by_seq: dict = {}
    for name, s, e, stats in spans:
        if name not in ("gub:put", "gub:issue") or "dispatch" not in stats:
            continue
        d = by_seq.setdefault(stats["dispatch"], {})
        iv = d.setdefault(name, [s, e])
        iv[0], iv[1] = min(iv[0], s), max(iv[1], e)
        if "closed_us" in stats:
            d["origin"] = (s, stats)
    out = {}
    for seq, d in by_seq.items():
        if "gub:issue" not in d:
            continue
        issue = d["gub:issue"][0]
        line = [[issue, 5, "issue"]]
        if "gub:put" in d:
            put0, put1 = d["gub:put"][0], min(d["gub:put"][1], issue)
            line += [[put1, 4, "handoff"], [put0, 3, "put"]]
        if "origin" in d:
            at, st = d["origin"]
            closed = at - 1e3 * float(st["closed_us"])
            opened = closed - 1e3 * float(st.get("window_us", 0))
            line += [[closed, 2, "handoff"], [opened, 1, "upstream"],
                     [opened - 1e3 * float(st["slot_us"]), 0, "slot"]]
        # by time and, at one instant, in the order a dispatch goes through
        out[seq] = [[t, stage] for t, _rank, stage in sorted(line)]
    return out


def owners(programs: list, issues: list, rx) -> list:
    """Per program [name, launched_ns, ...] (sorted by that instant, on the
    host's clock), the dispatch it belongs to or None. `issues` is [[issue
    start, issue end, dispatch]] sorted by start."""
    out, i, got = [], -1, 0
    for name, s, *_ in programs:
        if not rx.search(name):
            out.append(None)
            continue
        # on to the next dispatch once its issue has started, if this one
        # has a program already (or the next one's whole issue is over: this
        # one never launched)
        while i + 1 < len(issues) and issues[i + 1][0] <= s and (
            i < 0 or got > 0 or issues[i + 1][1] <= s
        ):
            i, got = i + 1, 0
        out.append(issues[i][2] if i >= 0 else None)
        got += i >= 0
    return out


def shares(loaded: dict, match: str) -> dict:
    """{stage: % of the read gaps' idle time} for the five stages, or every
    one None when there is nothing to read."""
    none = dict.fromkeys(STAGES)
    spans = loaded.get("spans") or []
    if not spans or not loaded.get("chips"):
        return none
    if not any("closed_us" in st for _n, _s, _e, st in spans):
        return none
    chip = max(loaded["chips"], key=lambda c: host_spans._length(loaded["chips"][c]))
    rx = re.compile(match)
    enq = loaded.get("enqueued", {}).get(chip.rsplit(":", 1)[-1], {})
    programs = [
        [name, s, e, enq.get(str(rid))]
        for name, s, e, rid in loaded.get("programs", {}).get(chip, [])
    ]
    offsets = [at - s for _n, s, _e, at in programs if at is not None]
    if not offsets or not any(rx.search(p[0]) for p in programs):
        return none
    # the chip's clock onto the host's: no program starts before its enqueue
    skew = max(offsets)
    busy = [[s + skew, e + skew] for s, e in loaded["chips"][chip]]
    # [name, launched (its enqueue; else its start), start] by start
    programs = sorted(
        ([n, at if at is not None else s + skew, s + skew] for n, s, _e, at in programs),
        key=lambda p: p[2],
    )
    lines = timelines(spans)
    issues = sorted(
        [s, e, st["dispatch"]] for n, s, e, st in spans
        if n == "gub:issue" and st.get("dispatch") in lines
    )
    by_launch = sorted(range(len(programs)), key=lambda k: programs[k][1])
    owner = [None] * len(programs)
    for k, o in zip(by_launch, owners([programs[k] for k in by_launch], issues, rx)):
        owner[k] = o
    starts = [p[2] for p in programs]
    # the dispatch of the nearest dispatch program before and after each
    before, after, last = [], [None] * len(owner), None
    for o in owner:
        before.append(last)
        last = o if o is not None else last
    last = None
    for k in range(len(owner) - 1, -1, -1):
        after[k] = last
        last = owner[k] if owner[k] is not None else last

    lo, _hi = loaded["span_ns"]
    total = dict.fromkeys(STAGES, 0.0)
    at = lo
    for s1, e1 in busy:
        g0, g1, at = at, s1, max(at, e1)
        if g1 <= g0:
            continue
        # the program that began in or right after the gap; where none did,
        # the one the gap lies inside (between two of its ops)
        k = bisect.bisect_right(starts, g1 + 1) - 1
        seq = owner[k] if k >= 0 else None
        if k >= 0 and seq is None and before[k] is not None and before[k] == after[k]:
            total["issue"] += g1 - g0  # between two passes of one dispatch
            continue
        if seq is None:
            total["upstream"] += g1 - g0
            continue
        stage, frm = "upstream", -INF
        for t, nxt in lines[seq] + [[INF, None]]:
            a, b = max(g0, frm), min(g1, t)
            if b > a:
                total[stage] += b - a
            stage, frm = nxt, t
    idle = sum(total.values())
    if idle <= 0:
        return none
    return {k: 100.0 * v / idle for k, v in total.items()}


def read(ctx, what, match):
    if ctx.get("trace") is None:
        return None
    if "_idle_critical" not in ctx:
        p = subprocess.run(
            [sys.executable, os.path.abspath(__file__), host_spans.TRACE_DIR,
             ctx["device"]["platform"], match],
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
            capture_output=True, text=True, timeout=300,
        )
        if p.returncode != 0:
            raise RuntimeError("reading the idle gaps failed: " + p.stderr.strip()[-1500:])
        ctx["_idle_critical"] = json.loads(p.stdout.strip().splitlines()[-1])
    return ctx["_idle_critical"][what]


if __name__ == "__main__":
    print(json.dumps(shares(
        load(xplane.find_xplane(sys.argv[1]), sys.argv[2]), sys.argv[3]
    )))
