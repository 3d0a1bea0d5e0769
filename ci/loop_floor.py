"""What grpc.aio alone costs the event-loop thread for one RPC of cell 1's
size: the floor under `loop_cpu_ms_per_rpc`.

    python3 ci/loop_floor.py [--seconds 10] [--inflight 64] [--channels 4]
        [--items 1000] [--hold-ms 0]

A bare `grpc.aio` server in a child process, with the daemon's server options
and one raw-bytes generic handler on GetRateLimits' method that returns a
constant: the GetRateLimitsResp of `--items` answers, to a GetRateLimitsReq
of `--items` rows as `bench/wirefmt.py` writes cell 1's (35,000 B for 1,000).
No parser, no batcher, no engine, no JAX: what is left on the loop thread is
grpc.aio's own callbacks and the handler's coroutine. With `--hold-ms` the
handler sleeps that long before it answers, as an RPC of the cell stays in
the server for its dispatch (64 in flight held 115 ms are cell 1's 550 RPC/s:
the floor at the cell's rate, one timer an RPC included; without it the
client saturates the loop and the callbacks of several RPCs share one turn
of it). This process is the client: `--inflight` RPCs in a closed loop over
`--channels` channels (cell 1's 64 over 4), the server's counters read
through a second method on either side of the traffic. One JSON line on
stdout and appended to `chiprun_out/loop_floor.jsonl`: RPC/s, the loop
thread's CPU ms per RPC (its own clock, `time.thread_time`, as
`tracing.HostClocks` reads the daemon's), its share of the wall, and the
process's CPU beside it. Run it on the machine whose `loop_cpu_ms_per_rpc` it
is set beside: a CPU clock reads another machine's speed. It needs no chip
and holds none.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT = os.path.join(ROOT, "chiprun_out", "loop_floor.jsonl")
METHOD = "/pb.gubernator.V1/GetRateLimits"
STATS = "/floor.V1/Stats"
RECV_CAP = 1024 * 1024  # service/server.start_servers' receive cap


def _raw(fn):
    import grpc

    return grpc.unary_unary_rpc_method_handler(
        fn, request_deserializer=lambda b: b, response_serializer=lambda b: b
    )


async def serve(answer: bytes, hold_s: float) -> None:
    """The child: serve until stdin closes; the port goes out on stdout."""
    import grpc

    rpcs = 0

    async def get_rate_limits(data: bytes, context) -> bytes:
        nonlocal rpcs
        rpcs += 1
        if hold_s:
            await asyncio.sleep(hold_s)
        return answer

    async def stats(data: bytes, context) -> bytes:
        # read on the loop thread: its own CPU clock
        return json.dumps({
            "rpcs": rpcs, "loop_cpu_s": time.thread_time(),
            "process_cpu_s": time.process_time(), "wall_s": time.monotonic(),
        }).encode()

    server = grpc.aio.server(
        options=[("grpc.max_receive_message_length", RECV_CAP)]
    )
    for service, name, fn in (
        (METHOD, "GetRateLimits", get_rate_limits), (STATS, "Stats", stats),
    ):
        server.add_generic_rpc_handlers((grpc.method_handlers_generic_handler(
            service.rsplit("/", 1)[0].lstrip("/"), {name: _raw(fn)}
        ),))
    port = server.add_insecure_port("127.0.0.1:0")
    await server.start()
    print(port, flush=True)
    await asyncio.get_running_loop().run_in_executor(None, sys.stdin.read)
    await server.stop(0)


async def drive(port: int, request: bytes, args) -> dict:
    import grpc

    channels = [
        grpc.aio.insecure_channel(
            f"127.0.0.1:{port}",
            options=[("grpc.max_receive_message_length", RECV_CAP),
                     ("grpc.use_local_subchannel_pool", 1)],
        )
        for _ in range(args.channels)
    ]
    calls = [c.unary_unary(METHOD) for c in channels]
    read_stats = channels[0].unary_unary(STATS)
    stop = False

    async def worker(call) -> None:
        while not stop:
            await call(request)

    workers = [
        asyncio.ensure_future(worker(calls[i % len(calls)]))
        for i in range(args.inflight)
    ]
    await asyncio.sleep(args.warm_seconds)
    before = json.loads(await read_stats(b""))
    await asyncio.sleep(args.seconds)
    after = json.loads(await read_stats(b""))
    stop = True
    await asyncio.gather(*workers)
    for c in channels:
        await c.close()
    d = {k: after[k] - before[k] for k in before}
    return {
        "rpcs": d["rpcs"], "rpc_per_s": d["rpcs"] / d["wall_s"],
        "loop_cpu_ms_per_rpc": 1e3 * d["loop_cpu_s"] / d["rpcs"],
        "loop_cpu_share": d["loop_cpu_s"] / d["wall_s"],
        "server_cpu_cores": d["process_cpu_s"] / d["wall_s"],
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--warm-seconds", type=float, default=2.0)
    ap.add_argument("--inflight", type=int, default=64)
    ap.add_argument("--channels", type=int, default=4)
    ap.add_argument("--items", type=int, default=1000)
    ap.add_argument("--hold-ms", type=float, default=0.0)
    ap.add_argument("--serve", action="store_true", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(ROOT, "bench"))
    import numpy as np
    import wirefmt

    # cell 1's rows (bench/configs/token10m.json's keyspace) and the answer
    # a token bucket gives them
    ids = wirefmt.key_ids(1, np.arange(args.items))
    request = wirefmt.request_bytes(ids, hits=1, limit=100, duration=3_600_000)
    answer = wirefmt.response_bytes(
        [(wirefmt.UNDER, 100, 99, 1_790_003_600_123)] * args.items
    )
    if args.serve:
        asyncio.run(serve(answer, args.hold_ms / 1e3))
        return 0
    child = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--serve",
         "--items", str(args.items), "--hold-ms", str(args.hold_ms)],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE,
    )
    try:
        port = int(child.stdout.readline())
        out = asyncio.run(drive(port, request, args))
    finally:
        child.stdin.close()
        child.wait(30)
    out.update(
        request_bytes=len(request), response_bytes=len(answer),
        inflight=args.inflight, channels=args.channels, seconds=args.seconds,
        hold_ms=args.hold_ms,
    )
    line = json.dumps(out)
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    with open(OUT, "a") as f:
        f.write(line + "\n")
    print(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
