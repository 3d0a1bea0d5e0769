"""Ratio of two `/v1/debug/pipeline` counters' growth over the run's
traffic. params: num, den (dotted paths into the snapshot)."""


def _at(snap, path):
    for part in path.split("."):
        snap = snap[part]
    return float(snap)


def read(ctx, num, den):
    before, after = ctx.get("pipeline_before"), ctx.get("pipeline_after")
    if before is None or after is None:
        return None
    d = _at(after, den) - _at(before, den)
    if d <= 0:
        return None
    return (_at(after, num) - _at(before, num)) / d
