"""One number of `/v1/debug/pipeline` as it stands after the run's traffic,
or its growth over the traffic (`delta`): a gauge the program keeps and
resets itself (read once, after the window), or a count that has no
denominator. A path that is missing from a snapshot, or holds null, reads
None: the program has no such block, or it is switched off.
params: path (dotted), delta (default false)."""


def _at(snap, path):
    for part in path.split("."):
        if not isinstance(snap, dict) or snap.get(part) is None:
            return None
        snap = snap[part]
    return float(snap)


def read(ctx, path, delta=False):
    after = _at(ctx.get("pipeline_after"), path)
    if after is None or not delta:
        return after
    before = _at(ctx.get("pipeline_before"), path)
    return None if before is None else after - before
