"""One number of `/v1/debug/pipeline` as it stands after the run's traffic,
over a number of the configuration file (a count the server kept since its
warm-up, per key the run loaded). A path missing from the snapshot, or
holding null, reads None. params: path (dotted, into the snapshot), per
(dotted, into the configuration)."""


def _at(snap, path):
    for part in path.split("."):
        if not isinstance(snap, dict) or snap.get(part) is None:
            return None
        snap = snap[part]
    return float(snap)


def read(ctx, path, per):
    value, over = _at(ctx.get("pipeline_after"), path), _at(ctx.get("config"), per)
    if value is None or not over:
        return None
    return value / over
