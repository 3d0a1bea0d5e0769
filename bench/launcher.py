"""The server child: the program's normal entry point, plus a side thread
that can trace the process that holds the chip.

Runs `gubernator_tpu.cmd.server.main` (what `python -m gubernator_tpu` runs)
on the main thread, unchanged, in traced and untraced runs alike. The side
thread reads one JSON command per line from stdin and answers one JSON line
on the file descriptor named by BENCH_REPLY_FD:

  {"cmd": "device"}              -> platform, kind, count, peak bytes per device
  {"cmd": "trace_start", "dir"}  -> jax.profiler.start_trace(dir)
  {"cmd": "trace_stop"}          -> jax.profiler.stop_trace()

Only the process that holds the chip can trace it, and nothing in the
program calls the profiler; this thread is the whole of the benchmark's
presence in the server. It does nothing between commands.
"""

from __future__ import annotations

import json
import os
import sys
import threading


def _device() -> dict:
    import jax

    devs = jax.local_devices()
    stats = [d.memory_stats() or {} for d in devs]
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
        "peak_bytes": [int(s.get("peak_bytes_in_use", 0)) for s in stats],
        "bytes_in_use": [int(s.get("bytes_in_use", 0)) for s in stats],
        "jax": jax.__version__,
    }


def _trace_start(log_dir: str) -> dict:
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # the device planes are what is read
    opts.host_tracer_level = 1
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    return {}


def _trace_stop() -> dict:
    import jax

    jax.profiler.stop_trace()
    return {}


def _serve_commands(reply_fd: int) -> None:
    with os.fdopen(reply_fd, "w") as out:
        for line in sys.stdin:
            try:
                msg = json.loads(line)
                cmd = msg["cmd"]
                if cmd == "device":
                    res = _device()
                elif cmd == "trace_start":
                    res = _trace_start(msg["dir"])
                elif cmd == "trace_stop":
                    res = _trace_stop()
                else:
                    raise ValueError(f"unknown command {cmd!r}")
                res["ok"] = True
            except Exception as exc:  # the parent decides what a failure means
                res = {"ok": False, "error": f"{type(exc).__name__}: {exc}"}
            out.write(json.dumps(res) + "\n")
            out.flush()


def main() -> int:
    # the benchmark's own modules must not shadow anything the program imports
    here = os.path.dirname(os.path.abspath(__file__))
    sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != here]
    threading.Thread(
        target=_serve_commands, args=(int(os.environ["BENCH_REPLY_FD"]),),
        name="bench-control", daemon=True,
    ).start()
    from gubernator_tpu.cmd.server import main as server_main

    return server_main([])


if __name__ == "__main__":
    sys.exit(main())
