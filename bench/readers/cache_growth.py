"""New entries in the compile-cache directory between the window's start
and its end: programs compiled inside the measured window. Must read 0 (the
server's cache threshold is set to 0 s, so every compile leaves an entry)."""


def read(ctx):
    a, b = ctx.get("cache_at_window_start"), ctx.get("cache_at_window_end")
    if a is None or b is None:
        return None
    return float(b - a)
