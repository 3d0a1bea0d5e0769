"""Shares of a peak on a mesh, where one pass's rows are split over the
shards and every chip runs the step (`decide_roofline` takes a dispatch's
rows against one chip's time and would read n_shards times too high).

  what="decide"    the mesh step's share of one chip's HBM roofline: the
                   bytes the decisions of ONE shard need (a pass's live
                   rows, growth of `engine.checks` over `engine.dispatches`,
                   divided by `engine.n_shards`; xplane.decide_needed_bytes)
                   over the chip's peak bandwidth, over the step's mean
                   device time on a chip. params: match (regex over
                   "XLA Modules").
  what="exchange"  the exchange's share of one chip's ICI peak: the bytes
                   one chip sends plus receives per pass (growth of
                   `engine.exchange_bytes`, which the program computes from
                   the shapes the step is traced with, over
                   `engine.dispatches`) over ICI_BYTES_PER_S, over the
                   collective ops' device time per execution of the step
                   (a chip's mean). params: match (the step, "XLA
                   Modules"), ops (regex over "XLA Ops").
  what="lane_fill" share of the lanes the mesh step ran that held a live
                   row: growth of `engine.checks` over `engine.mesh_lanes`.
                   A count, not a share of a peak; it may not pass 100
                   either.

Unit: %. A share above 100 means the bytes are counted too high or the time
leaves out work: it raises and is never clipped (xplane.roofline_share_pct).
A program without the counters (before PR 26), or a trace without the step
or without a collective op, reads None: nothing to read.
"""

import xplane

# Google Cloud documentation, "TPU v5e": 1,600 Gbit/s of chip-to-chip
# interconnect a chip (bench/configs/mesh4-sharded.json `assumed`).
ICI_BYTES_PER_S = {"TPU v5 lite": 1600e9 / 8, "TPU v5e": 1600e9 / 8}


def _growth(ctx, key):
    a, b = ctx["pipeline_before"]["engine"], ctx["pipeline_after"]["engine"]
    if key not in a or key not in b:
        return None
    return float(b[key]) - float(a[key])


def share_pct(needed_bytes: float, seconds: float, peak_bytes_per_s: float) -> float:
    if seconds <= 0:
        raise ValueError("no device time to take a share of a peak of")
    pct = 100.0 * needed_bytes / peak_bytes_per_s / seconds
    if pct > 100.0:
        raise ValueError(
            f"share of the peak {pct:.1f}% > 100%: {needed_bytes:.0f} B in "
            f"{seconds * 1e6:.1f} us against {peak_bytes_per_s:.3g} B/s"
        )
    return pct


def read(ctx, what, match=None, ops=None):
    passes = _growth(ctx, "dispatches")
    if not passes or passes <= 0:
        return None
    if what == "lane_fill":
        lanes, rows = _growth(ctx, "mesh_lanes"), _growth(ctx, "checks")
        if not lanes or lanes <= 0:
            return None
        pct = 100.0 * rows / lanes
        if pct > 100.0:
            raise ValueError(f"{rows:.0f} live rows in {lanes:.0f} lanes")
        return pct
    red = ctx.get("trace")
    if red is None:
        return None
    steps, step_s = xplane.summed(red, xplane.MODULE_LINE, match)
    if steps <= 0:
        return None
    kind = ctx["device"]["kind"]
    if what == "decide":
        shards = int(ctx["pipeline_after"]["engine"].get("n_shards") or 1)
        rows = _growth(ctx, "checks") / passes / shards
        needed = xplane.decide_needed_bytes(rows, ctx["config"]["table"])
        return share_pct(needed, step_s / steps, xplane.peak(kind, "hbm_bytes_per_s"))
    if what == "exchange":
        moved = _growth(ctx, "exchange_bytes")
        events, op_s = xplane.summed(red, xplane.OP_LINE, ops)
        if not moved or moved <= 0 or events <= 0:
            return None
        if kind not in ICI_BYTES_PER_S:
            raise KeyError(f"no ICI peak recorded for device kind {kind!r}")
        return share_pct(moved / passes, op_s / steps, ICI_BYTES_PER_S[kind])
    raise ValueError(f"unknown what={what!r}")
