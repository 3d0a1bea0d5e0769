"""The checkpoint extract's share of the HBM roofline: the bytes one
execution of the extract program needs, over the peak bandwidth of the
device kind, over the program's mean device time in the trace.

The count of bytes is this file's own, from the table's shapes in the
configuration file and the growth of three `/v1/debug/pipeline` counters over
the run's traffic: every dirty block's bucket rows are read once
(`checkpoint.dirty_blocks` x buckets a block x the bucket's bytes) and every
live row they hold is written once (`checkpoint.rows` x the slot's bytes),
spread over the executions the epochs launched (`checkpoint.extracts`).
What else the program moves (the dead slots it carries to the host, the
padding of an epoch's last grid, the liveness column) is what the share is
there to expose. A program without the counters, or a trace without the
program, reads None. params: match (regex over "XLA Modules"). Unit: %."""

import xplane


def needed_bytes(dirty_blocks: float, rows: float, table: dict, blk: float = 1) -> float:
    bucket = int(table["slot_bytes"]) * int(table["slots_per_bucket"])
    return float(dirty_blocks) * float(blk) * bucket + float(rows) * int(table["slot_bytes"])


def read(ctx, match):
    red = ctx.get("trace")
    a, b = ctx["pipeline_before"], ctx["pipeline_after"]
    ca, cb = a.get("checkpoint"), b.get("checkpoint")
    if red is None or not ca or not cb or "extracts" not in cb:
        return None
    runs = cb["extracts"] - ca["extracts"]
    events, seconds = xplane.summed(red, xplane.MODULE_LINE, match)
    if events <= 0 or runs <= 0:
        return None
    needed = needed_bytes(
        cb["dirty_blocks"] - ca["dirty_blocks"], cb["rows"] - ca["rows"],
        ctx["config"]["table"], b["engine"].get("ckpt_blk") or 1,
    ) / runs
    return xplane.roofline_share_pct(needed, seconds / events, ctx["device"]["kind"])
