"""The decide program's share of the HBM roofline: the bytes the decisions
of one dispatch need (bench/xplane.py `decide_needed_bytes`: the table's
shapes from the configuration file, the live rows per dispatch from the
growth of `engine.checks` over `engine.dispatches`) over the peak bandwidth
of the device kind, over the program's mean device time in the trace.
params: match (regex over "XLA Modules"). Unit: %."""

import xplane


def read(ctx, match):
    red = ctx.get("trace")
    if red is None:
        return None
    a, b = ctx["pipeline_before"]["engine"], ctx["pipeline_after"]["engine"]
    dispatches = b["dispatches"] - a["dispatches"]
    events, seconds = xplane.summed(red, xplane.MODULE_LINE, match)
    if events <= 0 or dispatches <= 0:
        return None
    rows = (b["checks"] - a["checks"]) / dispatches
    needed = xplane.decide_needed_bytes(rows, ctx["config"]["table"])
    return xplane.roofline_share_pct(needed, seconds / events, ctx["device"]["kind"])
