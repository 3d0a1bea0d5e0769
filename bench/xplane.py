"""From the profiler's `.xplane.pb` to numbers: busy and idle time of each
chip, time per XLA module and per op, the longest idle gaps; and the table of
peaks and the bytes a decide dispatch needs, for the roofline share.

`load()` needs `jax.profiler.ProfileData` and therefore runs in a short-lived
child of its own (`python bench/xplane.py <trace dir>`), after the server has
stopped: the benchmark's parent never imports JAX. `reduce()` works on the
plain structure `load()` returns, which is also what the recorded trace under
bench/tests/data/ holds, so the reduction is tested without a chip.

A device plane (`/device:TPU:n`) carries a line "XLA Modules" (one event per
executed program, named after the jitted function) and a line "XLA Ops" (one
event per HLO op or kernel inside it). Busy time is the union of the op
events' intervals; where a plane has no op line the module line stands in.
"""

from __future__ import annotations

import bisect
import glob
import json
import os
import re
import sys

MODULE_LINE, OP_LINE = "XLA Modules", "XLA Ops"
# which planes are chips, by the platform the run asked for; "cpu" is the
# tests' rehearsal, where the host's threads stand in for a device
DEVICE_PLANES = {"tpu": r"^/device:TPU:\d+$", "cpu": r"^/host:CPU$"}

# Peaks by `device_kind` as JAX reports it. Source: Google Cloud
# documentation, "TPU v5e" (197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s).
# A device that is not here is an error, never a default.
PEAKS = {
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
    "TPU v5e": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
}


def peak(device_kind: str, what: str) -> float:
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks recorded for device kind {device_kind!r}")
    return PEAKS[device_kind][what]


def decide_needed_bytes(live_rows: float, table: dict) -> float:
    """HBM bytes the token-bucket decision itself needs for `live_rows`
    checks on distinct keys: each reads its key's bucket row (all slots of
    one bucket, to find the key or a free slot) and writes one slot back.
    Sorting, padding lanes, the request and response lanes and whatever
    else the kernel moves are what the share is there to expose."""
    bucket_row = int(table["slot_bytes"]) * int(table["slots_per_bucket"])
    return float(live_rows) * (bucket_row + int(table["slot_bytes"]))


def roofline_share_pct(needed_bytes: float, seconds: float, device_kind: str) -> float:
    """needed bytes / peak bandwidth / measured time, in percent. Above 100
    the bytes are counted too high or the time leaves out work: that is a
    fault of the count, so it raises and is never clipped."""
    if seconds <= 0:
        raise ValueError("no kernel time to take a roofline share of")
    pct = 100.0 * needed_bytes / peak(device_kind, "hbm_bytes_per_s") / seconds
    if pct > 100.0:
        raise ValueError(
            f"roofline share {pct:.1f}% > 100%: {needed_bytes:.0f} B in "
            f"{seconds * 1e6:.1f} us on {device_kind}"
        )
    return pct


def short_name(name: str) -> str:
    """An op event is named by its whole HLO text; what comes before " = "
    is the instruction's name ("%fusion.12"), which is what is kept."""
    return name.split(" = ", 1)[0].lstrip("%")[:120]


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str, platform: str = "tpu") -> dict:
    """{plane name: {line name: [[event name, start_ns, duration_ns], ...]}}
    for the device planes, plus "_span_ns": [first start, last end] over
    every event of every plane (host threads included)."""
    from jax.profiler import ProfileData

    device_plane = re.compile(DEVICE_PLANES[platform])
    out: dict = {}
    lo, hi = None, None
    for plane in ProfileData.from_file(path).planes:
        keep = device_plane.match(plane.name) is not None
        lines = {}
        for line in plane.lines:
            evs = []
            for ev in line.events:
                s, d = float(ev.start_ns), float(ev.duration_ns)
                lo = s if lo is None or s < lo else lo
                hi = s + d if hi is None or s + d > hi else hi
                if keep:
                    evs.append([short_name(ev.name), s, d])
            if keep and evs:
                lines[line.name] = evs
        if keep and lines:
            out[plane.name] = lines
    out["_span_ns"] = [lo or 0.0, hi or 0.0]
    return out


def _union(intervals: list) -> list:
    """Merged [start, end] intervals, sorted."""
    merged: list = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return merged


def reduce(planes: dict) -> dict:
    """Busy time, idle share, per-module and per-op sums for every chip. The
    window is the span of the trace's own events, host threads included, so
    busy time and window are on one clock."""
    span = planes.get("_span_ns", [0.0, 0.0])
    window_s = (span[1] - span[0]) / 1e9
    chips = {}
    for name, lines in planes.items():
        if name.startswith("_"):
            continue
        ops = lines.get(OP_LINE) or lines.get(MODULE_LINE) or [
            ev for evs in lines.values() for ev in evs  # a plane of plain threads
        ]
        merged = _union([[s, s + d] for _n, s, d in ops])
        sums = {}
        for key, line in (("modules", MODULE_LINE), ("ops", OP_LINE)):
            acc: dict = {}
            for n, _s, d in lines.get(line, []):
                c = acc.setdefault(n, [0, 0.0])
                c[0] += 1
                c[1] += d / 1e9
            sums[key] = acc
        # a gap is named after the program that ended it: all that can be
        # said until the program has host spans of its own
        starts = sorted((s, n) for n, s, _d in lines.get(MODULE_LINE, []))
        keys = [s for s, _n in starts]
        gaps: dict = {}
        for (_s0, e0), (s1, _e1) in zip(merged, merged[1:]):
            # the program that began in or right after the gap ended it; a
            # gap no program began in lies between two ops of one program
            k = bisect.bisect_right(keys, s1 + 1) - 1
            nxt = starts[k][1] if k >= 0 and starts[k][0] >= e0 - 1 else "inside a program"
            g = gaps.setdefault(nxt, [0, 0.0, 0.0])
            g[0] += 1
            g[1] += (s1 - e0) / 1e9
            g[2] = max(g[2], (s1 - e0) / 1e9)
        chips[name] = {
            "busy_s": sum(e - s for s, e in merged) / 1e9, "gaps": gaps, **sums,
        }
    if not chips:
        raise ValueError("the trace holds no device plane: no operation ran on a device")
    busy = [c["busy_s"] for c in chips.values()]
    if window_s <= 0 or max(busy) <= 0:
        raise ValueError("the trace holds no device operation")
    if max(busy) > window_s:
        raise ValueError(f"a chip was busy {max(busy):.3f} s of a {window_s:.3f} s window")
    return {
        "window_s": window_s,
        "busy_s": sum(busy) / len(busy),
        "idle_share_worst": 1.0 - min(busy) / window_s,
        "chips": chips,
    }


def summed(red: dict, line: str, pattern: str) -> tuple:
    """(events, seconds) of the modules or ops whose name matches, averaged
    over the chips (each chip of a mesh runs every dispatch's program)."""
    rx = re.compile(pattern)
    key = "modules" if line == MODULE_LINE else "ops"
    n = t = 0.0
    for chip in red["chips"].values():
        for name, (count, secs) in chip[key].items():
            if rx.search(name):
                n += count
                t += secs
    k = len(red["chips"])
    return n / k, t / k


def breakdown(red: dict, top: int = 10) -> dict:
    """The contract's `breakdown`, from the busiest chip: the device ops
    that took most time, and where the idle time went (summed by the
    program that ran next)."""
    chip = max(red["chips"].values(), key=lambda c: c["busy_s"])
    src = chip["ops"] or chip["modules"]
    ops = sorted(([n, s] for n, (_c, s) in src.items()), key=lambda x: -x[1])[:top]
    by_program: dict = {}
    for n, g in chip["gaps"].items():
        key = "idle_before:" + re.sub(r"\(.*", "", n)[:80]
        by_program[key] = by_program.get(key, 0.0) + g[1]
    idle = sorted(([k, v] for k, v in by_program.items()), key=lambda x: -x[1])[:top]
    return {"device_ops": ops, "idle_gaps": idle}


def module_table(red: dict) -> dict:
    """{module: [executions, seconds]} averaged over the chips, for the
    context line."""
    out: dict = {}
    k = len(red["chips"])
    for chip in red["chips"].values():
        for n, (c, secs) in chip["modules"].items():
            acc = out.setdefault(n, [0.0, 0.0])
            acc[0] += c / k
            acc[1] += secs / k
    return out


def main(argv) -> int:
    """`xplane.py <trace dir> --reduce <platform>` prints the reduction;
    `--dump <platform>` prints what `load()` read (how the recorded trace
    under bench/tests/data/ was made)."""
    planes = load(find_xplane(argv[1]), argv[3])
    print(json.dumps(planes if argv[2] == "--dump" else reduce(planes)))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
