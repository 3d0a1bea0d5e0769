"""Device time of the modules or ops whose name matches, per execution of a
marker program (the one that runs once per dispatch), both counted in the
profiler's trace of the sub-window. params: line ("XLA Modules" | "XLA Ops")
and match (regex) for what is summed; per_match (regex over "XLA Modules")
for the marker. Unit: ms."""

import xplane


def read(ctx, line, match, per_match):
    red = ctx.get("trace")
    if red is None:
        return None
    events, seconds = xplane.summed(red, line, match)
    marks, _ = xplane.summed(red, xplane.MODULE_LINE, per_match)
    if marks <= 0 or events <= 0:
        return None
    return 1e3 * seconds / marks
