"""Where the device's idle time falls on the host's side of the pipeline:
the program's own `gub:<stage>` spans (jax.profiler.TraceAnnotation, in the
`/host:CPU` plane) laid over the busy intervals of the busiest chip, both
from the one `.xplane.pb` of the traced sub-window, so on one clock.

  what="dispatch"  share (%) of that chip's idle time inside the host
                   interval of some dispatch: first gub:put start to last
                   gub:fetch end among the spans that carry one `dispatch`
                   number (put, issue, fetch run on three threads)
  what="window"    share (%) of idle time outside every dispatch interval
                   but inside a batch window: from `waited_us` before a
                   gub:close span's end (when its oldest entry arrived) to
                   that end
What is left, 100 - both, is idle time in which the pipeline had been
handed nothing: the door, gRPC, or the client. A trace without any gub:
span (a program that has none) reads None, not 0.

Reading the file needs jax.profiler.ProfileData, and the benchmark's parent
stays off JAX, so `read` runs this file in a child of its own
(`python bench/readers/host_spans.py <trace dir> <platform>` prints what
`load` returns; that is also how bench/tests/data/host_spans_small.json.gz
was made) once per run and keeps the answer in the readers' context.
"""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH_DIR)

import xplane  # noqa: E402

TRACE_DIR = os.path.join(BENCH_DIR, ".out", "trace")  # harness._watch_window's
HOST_PLANE = "/host:CPU"
WORK = ("gub:put", "gub:issue", "gub:fetch")


def load(path: str, platform: str) -> dict:
    """{"span_ns": [first start, last end] over every event, "chips": {plane:
    merged busy intervals, as xplane.reduce takes them}, "spans": [[name,
    start_ns, end_ns, stats], ...] of the program's gub: spans}."""
    from jax.profiler import ProfileData

    device_plane = re.compile(xplane.DEVICE_PLANES[platform])
    chips, spans, lo, hi = {}, [], None, None
    for plane in ProfileData.from_file(path).planes:
        is_chip = device_plane.match(plane.name) is not None
        lines = {}
        for line in plane.lines:
            evs = []
            for ev in line.events:
                s, e = float(ev.start_ns), float(ev.start_ns) + float(ev.duration_ns)
                lo = s if lo is None or s < lo else lo
                hi = e if hi is None or e > hi else hi
                if plane.name == HOST_PLANE and ev.name.startswith("gub:"):
                    spans.append([ev.name, s, e, {k: v for k, v in ev.stats}])
                elif is_chip:  # the CPU rehearsal: host threads, less our own spans
                    evs.append([s, e])
            if evs:
                lines[line.name] = evs
        if is_chip and lines:
            ops = lines.get(xplane.OP_LINE) or lines.get(xplane.MODULE_LINE) or [
                iv for evs in lines.values() for iv in evs
            ]
            chips[plane.name] = xplane._union(ops)
    return {"span_ns": [lo or 0.0, hi or 0.0], "chips": chips, "spans": spans}


def _length(intervals: list) -> float:
    return sum(e - s for s, e in intervals)


def _intersect(a: list, b: list) -> list:
    """Of two sorted lists of disjoint intervals."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append([s, e])
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _complement(a: list, lo: float, hi: float) -> list:
    out, at = [], lo
    for s, e in a:
        if s > at:
            out.append([at, min(s, hi)])
        at = max(at, e)
    if at < hi:
        out.append([at, hi])
    return [iv for iv in out if iv[0] < iv[1]]


def shares(loaded: dict) -> dict:
    """{"dispatch": %, "window": %} of the busiest chip's idle time, or both
    None when the trace holds no gub: span or no idle time."""
    none = {"dispatch": None, "window": None}
    if not loaded["spans"] or not loaded["chips"]:
        return none
    lo, hi = loaded["span_ns"]
    busy = max(loaded["chips"].values(), key=_length)
    idle = _complement(busy, lo, hi)
    if _length(idle) <= 0:
        return none
    by_seq: dict = {}
    windows = []
    for name, s, e, stats in loaded["spans"]:
        if name in WORK and "dispatch" in stats:
            iv = by_seq.setdefault(stats["dispatch"], [s, e])
            iv[0], iv[1] = min(iv[0], s), max(iv[1], e)
        elif name == "gub:close" and "waited_us" in stats:
            windows.append([e - 1e3 * float(stats["waited_us"]), e])
    dispatch = xplane._union(list(by_seq.values()))
    window_only = _intersect(
        xplane._union(windows), _complement(dispatch, lo, hi)
    )
    total = _length(idle)
    return {
        "dispatch": 100.0 * _length(_intersect(idle, dispatch)) / total,
        "window": 100.0 * _length(_intersect(idle, window_only)) / total,
    }


def read(ctx, what):
    if ctx.get("trace") is None:
        return None
    if "_host_span_shares" not in ctx:
        p = subprocess.run(
            [sys.executable, os.path.abspath(__file__), TRACE_DIR, ctx["device"]["platform"]],
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
            capture_output=True, text=True, timeout=300,
        )
        if p.returncode != 0:
            raise RuntimeError("reading the host spans failed: " + p.stderr.strip()[-1500:])
        ctx["_host_span_shares"] = shares(json.loads(p.stdout.strip().splitlines()[-1]))
    return ctx["_host_span_shares"][what]


if __name__ == "__main__":
    print(json.dumps(load(xplane.find_xplane(sys.argv[1]), sys.argv[2])))
