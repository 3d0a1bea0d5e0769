"""A number the load generator took of itself. params: what (a key of the
generator's report, e.g. late_p99_ms: the 99th percentile of actual send
time minus due time over the window's RPCs)."""


def read(ctx, what):
    return ctx["generator"].get(what)
