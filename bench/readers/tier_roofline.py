"""The promote's merge program's share of the HBM roofline: the bytes one
execution of it needs, over the peak bandwidth of the device kind, over the
program's mean device time in the trace.

The count of bytes is this file's own and is of the work, not of the
implementation: a promoted row needs its bucket read once and one slot
written (`slots_per_bucket` x `slot_bytes` + `slot_bytes` of the
configuration's `table`: 576 B), and an execution promotes
(growth of `tier.promoted`) / (growth of `tier.merge_launches`) rows, both
from `/v1/debug/pipeline` over the run's traffic. It reads the same whatever
launches the merge. What else the program moves (the padding of its batch,
the evictee sidecar, the sorts of its claim) is what the share is there to
expose. A program without the counters, or a trace without the program,
reads None. params: match (regex over "XLA Modules"). Unit: %."""

import xplane


def needed_bytes(rows: float, table: dict) -> float:
    slot = int(table["slot_bytes"])
    return float(rows) * (slot * int(table["slots_per_bucket"]) + slot)


def read(ctx, match):
    red = ctx.get("trace")
    a, b = ctx.get("pipeline_before") or {}, ctx.get("pipeline_after") or {}
    ta, tb = a.get("tier"), b.get("tier")
    if red is None or not ta or not tb or "merge_launches" not in tb:
        return None
    launches = tb["merge_launches"] - ta["merge_launches"]
    events, seconds = xplane.summed(red, xplane.MODULE_LINE, match)
    if events <= 0 or launches <= 0:
        return None
    needed = needed_bytes(tb["promoted"] - ta["promoted"], ctx["config"]["table"]) / launches
    return xplane.roofline_share_pct(needed, seconds / events, ctx["device"]["kind"])
