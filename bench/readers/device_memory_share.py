"""The fullest device's peak bytes in use since the server started, over the
most its runtime hands out (`bytes_limit`), in percent: both as the server
reads them from its devices' `memory_stats()` after the run's traffic
(`engine.device_peak_bytes`, `engine.device_bytes_limit` of
`/v1/debug/pipeline`, one entry a device). A program that does not report
them, or a backend without memory statistics, reads None. Unit: %."""


def read(ctx):
    eng = (ctx.get("pipeline_after") or {}).get("engine", {})
    peaks, limits = eng.get("device_peak_bytes"), eng.get("device_bytes_limit")
    if not peaks or not limits:
        return None
    shares = [p / l for p, l in zip(peaks, limits) if p is not None and l]
    return 100.0 * max(shares) if shares else None
