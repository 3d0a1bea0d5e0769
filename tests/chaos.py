"""Fault-injection harness — an in-process chaos TCP proxy.

Each test-cluster daemon can be fronted by one ChaosProxy: peers dial the
proxy's port (the daemon advertises it), the proxy pipes bytes to the real
gRPC listener, and tests toggle failure modes per-peer at runtime — so the
fault-tolerance layer (service/breaker.py, degraded-local fallback, GLOBAL
requeue) is exercised against *real* failing RPCs, not mocks.

Modes
-----
* "pass"      — transparent byte pipe (default)
* "delay"     — transparent, but each chunk is delayed by `delay_s`
* "drop"      — new connections are accepted and immediately closed
                (connection-refused-like fast failures)
* "error"     — connections establish, then reset on the first client bytes
                (mid-stream RPC failures)
* "blackhole" — connections establish but nothing is ever forwarded or
                answered (the slow timeout failures breakers exist for)

Switching modes severs existing connections, so a long-lived HTTP/2 channel
can't tunnel through a freshly injected fault — nor stay wedged on a
blackholed socket after a heal.
"""

from __future__ import annotations

import asyncio
import contextlib
from typing import Optional, Set

MODES = ("pass", "delay", "drop", "error", "blackhole")


class ChaosProxy:
    def __init__(self):
        self.mode = "pass"
        self.delay_s = 0.0
        self.port: Optional[int] = None
        self.target_host: Optional[str] = None
        self.target_port: Optional[int] = None
        self._server: Optional[asyncio.base_events.Server] = None
        self._conns: Set[asyncio.Task] = set()
        self._writers: Set[asyncio.StreamWriter] = set()
        self._holes: Set[asyncio.Event] = set()

    @property
    def address(self) -> str:
        return f"127.0.0.1:{self.port}"

    async def start(self) -> "ChaosProxy":
        self._server = await asyncio.start_server(
            self._handle, "127.0.0.1", 0
        )
        self.port = self._server.sockets[0].getsockname()[1]
        return self

    def set_target(self, host: str, port: int) -> None:
        self.target_host, self.target_port = host, port

    def set_mode(self, mode: str, delay_s: float = 0.0) -> None:
        """Switch the failure mode at runtime. Every switch severs live
        connections so the new mode applies immediately: a gRPC channel
        would otherwise keep its established HTTP/2 stream through a fresh
        fault — or, on heal, stay wedged on a blackholed socket."""
        assert mode in MODES, f"unknown chaos mode {mode!r}"
        self.mode = mode
        self.delay_s = delay_s
        self.sever()

    def heal(self) -> None:
        self.set_mode("pass")

    def sever(self) -> None:
        """Kill every live connection (blackholed ones included)."""
        for ev in list(self._holes):
            ev.set()
        for w in list(self._writers):
            with contextlib.suppress(Exception):
                w.transport.abort()

    async def stop(self) -> None:
        if self._server is not None:
            self._server.close()
        # sever BEFORE waiting: since Python 3.12 wait_closed() also waits
        # for every accepted connection, and a blackholed one never ends
        # by itself (the suite hung here, in the first test that stops a
        # proxy still in blackhole mode)
        self.sever()
        for t in list(self._conns):
            t.cancel()
        await asyncio.gather(*self._conns, return_exceptions=True)
        if self._server is not None:
            await self._server.wait_closed()

    # ------------------------------------------------------------- internals
    async def _handle(
        self, reader: asyncio.StreamReader, writer: asyncio.StreamWriter
    ) -> None:
        task = asyncio.current_task()
        self._conns.add(task)
        self._writers.add(writer)
        try:
            await self._serve_conn(reader, writer)
        except (asyncio.CancelledError, ConnectionError, OSError):
            pass
        finally:
            self._writers.discard(writer)
            self._conns.discard(task)
            with contextlib.suppress(Exception):
                writer.close()

    async def _serve_conn(self, reader, writer) -> None:
        mode = self.mode  # the mode at accept time governs this connection
        if mode == "drop":
            writer.transport.abort()
            return
        if mode == "blackhole":
            # swallow inbound bytes, answer nothing, hold the socket open
            # until severed/healed — the caller is left waiting on its RPC
            # deadline, exactly like a dead host behind a silent LB
            hole = asyncio.Event()
            self._holes.add(hole)
            drain = asyncio.create_task(self._drain_forever(reader))
            try:
                await hole.wait()
            finally:
                self._holes.discard(hole)
                drain.cancel()
                with contextlib.suppress(asyncio.CancelledError):
                    await drain
            writer.transport.abort()
            return
        if mode == "error":
            # let the connection establish, reset on first client bytes
            with contextlib.suppress(Exception):
                await reader.read(1)
            writer.transport.abort()
            return
        # pass / delay: full duplex pipe to the real listener
        assert self.target_port is not None, "chaos proxy has no target"
        try:
            up_r, up_w = await asyncio.open_connection(
                self.target_host, self.target_port
            )
        except OSError:
            writer.transport.abort()
            return
        self._writers.add(up_w)
        try:
            await asyncio.gather(
                self._pipe(reader, up_w),
                self._pipe(up_r, writer),
            )
        finally:
            self._writers.discard(up_w)
            with contextlib.suppress(Exception):
                up_w.close()

    async def _drain_forever(self, reader) -> None:
        with contextlib.suppress(Exception):
            while await reader.read(65536):
                pass

    async def _pipe(self, reader, writer) -> None:
        try:
            while True:
                chunk = await reader.read(65536)
                if not chunk:
                    break
                if self.mode == "delay" and self.delay_s > 0:
                    await asyncio.sleep(self.delay_s)
                writer.write(chunk)
                await writer.drain()
        except (ConnectionError, OSError, asyncio.CancelledError):
            pass
        finally:
            with contextlib.suppress(Exception):
                writer.write_eof()
