"""Ratio of two `/v1/debug/pipeline` counters' growth over the run's
traffic, like `pipeline_delta`, for counters that a program may not have:
a path that is missing from either snapshot reads None (nothing to read),
where `pipeline_delta` raises. params: num, den (dotted paths)."""


def _at(snap, path):
    for part in path.split("."):
        if not isinstance(snap, dict) or part not in snap:
            return None
        snap = snap[part]
    return float(snap)


def read(ctx, num, den):
    before, after = ctx.get("pipeline_before"), ctx.get("pipeline_after")
    if before is None or after is None:
        return None
    ends = [_at(s, p) for p in (num, den) for s in (before, after)]
    if None in ends:
        return None
    n0, n1, d0, d1 = ends
    if d1 - d0 <= 0:
        return None
    return (n1 - n0) / (d1 - d0)
