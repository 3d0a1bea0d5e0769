"""The server as a child process, and the two doors a user has into it.

`Server` and `Door` are copied from `chip_smoke.py` (commit 846d0923) and
changed in three ways: the child is `bench/launcher.py` (the same entry point
plus a control thread for the profiler), the server's environment comes from
the configuration file, and the gRPC client hands back raw bytes only.
"""

from __future__ import annotations

import asyncio
import json
import os
import signal
import socket
import subprocess
import sys
import time

import wirefmt

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
# JAX's persistent compile cache, at one fixed path inside the checkout
CACHE_DIR = os.path.join(BENCH_DIR, ".jax_cache")
HEALTH_WAIT_S = 1100.0  # a cold four-chip start compiled for 362 s (PR 21)


class BenchFailure(Exception):
    """The run cannot give a result; the message says why."""


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def cache_entries() -> int:
    try:
        return len(os.listdir(CACHE_DIR))
    except OSError:
        return 0


class Server:
    """The normal binary as a child that holds the chip."""

    def __init__(self, platform: str, server_env: dict, log_path: str,
                 extra_env: dict | None = None):
        self.grpc = f"127.0.0.1:{_free_port()}"
        self.http = f"127.0.0.1:{_free_port()}"
        reply_r, reply_w = os.pipe()
        env = {
            **os.environ,
            "PYTHONPATH": ROOT + os.pathsep + os.environ.get("PYTHONPATH", ""),
            **{k: str(v) for k, v in server_env.items()},
            **(extra_env or {}),
            "JAX_PLATFORMS": platform,
            # libtpu would otherwise log under /tmp, outside the checkout (with
            # "disabled" a four-chip trace came back empty, PERF.md section 6)
            "TPU_LOG_DIR": os.path.join(os.path.dirname(log_path), "tpu_logs"),
            "JAX_COMPILATION_CACHE_DIR": CACHE_DIR,
            "GUBER_GRPC_ADDRESS": self.grpc,
            "GUBER_HTTP_ADDRESS": self.http,
            "BENCH_REPLY_FD": str(reply_w),
        }
        self.log_path = log_path
        os.makedirs(os.path.dirname(log_path), exist_ok=True)
        os.makedirs(CACHE_DIR, exist_ok=True)
        self._log = open(log_path, "wb")
        self.t0 = time.monotonic()
        self.proc = subprocess.Popen(
            [sys.executable, os.path.join(BENCH_DIR, "launcher.py")],
            env=env, cwd=ROOT, stdin=subprocess.PIPE, stdout=self._log,
            stderr=subprocess.STDOUT, pass_fds=(reply_w,),
        )
        os.close(reply_w)
        self._reply = os.fdopen(reply_r, "r")

    def log_tail(self, n: int = 3000) -> str:
        with open(self.log_path, "r", errors="replace") as f:
            return f.read().strip()[-n:]

    async def wait_healthy(self) -> float:
        import aiohttp

        async with aiohttp.ClientSession() as s:
            while True:
                if self.proc.poll() is not None:
                    raise BenchFailure(
                        f"the server exited with code {self.proc.returncode} "
                        "before it was healthy:\n" + self.log_tail()
                    )
                if time.monotonic() - self.t0 > HEALTH_WAIT_S:
                    raise BenchFailure(
                        f"the server was not healthy after {HEALTH_WAIT_S:.0f} s:\n"
                        + self.log_tail()
                    )
                try:
                    async with s.get(f"http://{self.http}/v1/HealthCheck") as r:
                        if r.status == 200 and (await r.json()).get("status") == "healthy":
                            return time.monotonic() - self.t0
                except aiohttp.ClientError:
                    pass
                await asyncio.sleep(0.25)

    def _command(self, msg: dict) -> dict:
        self.proc.stdin.write((json.dumps(msg) + "\n").encode())
        self.proc.stdin.flush()
        line = self._reply.readline()
        if not line:
            raise BenchFailure("the server child closed its control channel")
        res = json.loads(line)
        if not res.pop("ok"):
            raise BenchFailure(f"control command {msg['cmd']}: {res['error']}")
        return res

    async def command(self, **msg) -> dict:
        """One command to the launcher's control thread (blocking pipe I/O,
        kept off the event loop)."""
        return await asyncio.get_running_loop().run_in_executor(
            None, self._command, msg
        )

    def stop(self) -> int:
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=60)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        for f in (self.proc.stdin, self._reply, self._log):
            try:
                f.close()
            except OSError:
                pass
        return self.proc.returncode


class Door:
    """gRPC for checks (raw bytes both ways), HTTP for status."""

    def __init__(self, grpc_addr: str, http_addr: str, channels: int = 4,
                 timeout_s: float = 600.0):
        import aiohttp
        import grpc

        self._chans = [
            grpc.aio.insecure_channel(
                grpc_addr,
                options=[
                    ("grpc.use_local_subchannel_pool", 1),
                    ("grpc.max_receive_message_length", 16 << 20),
                ],
            )
            for _ in range(channels)
        ]
        self.calls = [
            c.unary_unary(
                wirefmt.METHOD, request_serializer=None, response_deserializer=None
            )
            for c in self._chans
        ]
        self.timeout_s = timeout_s
        self._n = 0
        self._http = aiohttp.ClientSession()
        self._base = f"http://{http_addr}"

    def start(self, body: bytes):
        """Begin one RPC; the returned call is awaitable and takes done
        callbacks (the open loop's way in: no task per RPC)."""
        self._n += 1
        return self.calls[self._n % len(self.calls)](body, timeout=self.timeout_s)

    async def check_raw(self, body: bytes) -> bytes:
        return await self.start(body)

    async def get(self, path: str, as_json: bool = True):
        async with self._http.get(self._base + path) as r:
            if r.status != 200:
                raise BenchFailure(f"GET {path} -> HTTP {r.status}: {await r.text()}")
            return await (r.json() if as_json else r.text())

    async def close(self) -> None:
        await self._http.close()
        for c in self._chans:
            await c.close()
