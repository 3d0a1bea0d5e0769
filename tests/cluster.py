"""In-process multi-daemon test cluster — the reference's central fixture.

Boots N real daemons in one process on 127.0.0.1 ephemeral ports with
discovery "none" and explicit set_peers, short batch/global cadences for test
speed (reference cluster/cluster.go:123-201; the functional suite's TestMain
boots 10 daemons the same way, functional_test.go:2465-2491). Helpers locate
the consistent-hash owner of a key so tests target owner vs non-owner
deterministically (cluster/cluster.go:72-110).
"""

from __future__ import annotations

import asyncio
from typing import List, Optional

from gubernator_tpu.config import BehaviorConfig, DaemonConfig
from gubernator_tpu.service.daemon import Daemon
from gubernator_tpu.types import PeerInfo


def daemon_config(dc: str = "", **overrides) -> DaemonConfig:
    conf = DaemonConfig(
        grpc_address="127.0.0.1:0",
        http_address="127.0.0.1:0",
        data_center=dc,
        cache_size=8192,
        behaviors=BehaviorConfig(
            batch_wait_ms=1.0,
            global_sync_wait_ms=50.0,  # reference cluster uses 50ms sync
            batch_timeout_ms=5000.0,  # CPU-jit compiles can stall first calls
            global_timeout_ms=5000.0,
        ),
    )
    for k, v in overrides.items():
        setattr(conf, k, v)
    return conf


class Cluster:
    def __init__(self, daemons: List[Daemon], proxies: Optional[list] = None):
        self.daemons = daemons
        # chaos=True: proxies[i] fronts daemons[i]'s peer traffic
        self.proxies = proxies or [None] * len(daemons)

    @classmethod
    async def start(
        cls,
        n: int,
        dcs: Optional[List[str]] = None,
        chaos: bool = False,
        **overrides,
    ):
        """Start n daemons (optionally with per-daemon datacenter labels) and
        wire them together with explicit set_peers.

        chaos=True fronts each daemon's PEER plane with a ChaosProxy
        (tests/chaos.py): the daemon advertises the proxy's port, so every
        other daemon's forwards/hit-syncs/broadcasts flow through it and
        tests inject faults per-peer at runtime. Direct client traffic
        (V1Client at conf.grpc_address) bypasses the proxy."""
        dcs = dcs or [""] * n
        proxies = [None] * n
        daemons = []
        for i in range(n):
            conf_kw = dict(overrides)
            if chaos:
                from tests.chaos import ChaosProxy

                proxies[i] = await ChaosProxy().start()
                # advertise the proxy: ring identity and peer dialing both
                # key on advertise_address, so ownership stays consistent
                # across daemons while the transport detours via the proxy
                conf_kw["advertise_address"] = proxies[i].address
            daemons.append(
                await Daemon.spawn(daemon_config(dc=dcs[i], **conf_kw))
            )
            if chaos:
                host, _, port = daemons[i].conf.grpc_address.rpartition(":")
                proxies[i].set_target(host, int(port))
        peers = [d.peer_info() for d in daemons]
        for d in daemons:
            # fresh PeerInfo copies: set_peers mutates is_owner per daemon
            d.set_peers([PeerInfo(**vars(p)) for p in peers])
        return cls(daemons, proxies)

    def proxy_for(self, daemon: Daemon):
        """The ChaosProxy fronting `daemon`'s peer traffic."""
        return self.proxies[self.daemons.index(daemon)]

    def find_owning_daemon(self, name: str, key: str) -> Daemon:
        """reference cluster.FindOwningDaemon (cluster/cluster.go:81-110)."""
        hk = name + "_" + key
        owner = self.daemons[0].get_peer(hk)
        for d in self.daemons:
            if d.conf.advertise_address == owner.grpc_address:
                return d
        raise AssertionError(f"no daemon owns {hk}")

    def non_owning_daemons(self, name: str, key: str) -> List[Daemon]:
        owner = self.find_owning_daemon(name, key)
        return [d for d in self.daemons if d is not owner]

    async def restart(self, i: int) -> Daemon:
        """Stop and respawn daemon i with the same config (reference
        cluster.Restart, cluster/cluster.go:139-148)."""
        old = self.daemons[i]
        conf = old.conf
        await old.close()
        new = await Daemon.spawn(conf)
        self.daemons[i] = new
        peers = [d.peer_info() for d in self.daemons]
        for d in self.daemons:
            d.set_peers([PeerInfo(**vars(p)) for p in peers])
        return new

    async def crash_restart(self, i: int) -> Daemon:
        """kill -9 analog (docs/durability.md): daemon i dies UNCLEANLY —
        no drain, no GLOBAL flush, no shutdown checkpoint (Daemon.abort)
        — and a replacement spawns on the same config, recovering only
        what the incremental checkpoint plane already persisted. The
        durability chaos tests bound over-admission across this edge."""
        old = self.daemons[i]
        conf = old.conf
        await old.abort()
        new = await Daemon.spawn(conf)
        self.daemons[i] = new
        peers = [d.peer_info() for d in self.daemons]
        for d in self.daemons:
            d.set_peers([PeerInfo(**vars(p)) for p in peers])
        return new

    async def drain_restart(self, i: int, mid_handoff=None) -> Daemon:
        """Rolling-restart step with graceful state handoff (the reference
        has no analog — docs/robustness.md "Topology change & drain"):

        1. the surviving daemons drop daemon i from their peer set (the
           discovery/LB view once its health flips to "leaving");
        2. daemon i drains — flushes GLOBAL queues, hands every owned live
           row to its ring successor, snapshots the unacked remainder —
           then closes;
        3. a replacement spawns on the same config and every daemon re-adds
           it: the survivors' rebalance diff hands the moved rows BACK.

        `mid_handoff` (async callable) runs between de-registration and the
        drain — the hook chaos tests use to inject faults mid-handoff."""
        old = self.daemons[i]
        survivors = [d for j, d in enumerate(self.daemons) if j != i]
        peers_without = [d.peer_info() for d in survivors]
        for d in survivors:
            d.set_peers([PeerInfo(**vars(p)) for p in peers_without])
        if mid_handoff is not None:
            await mid_handoff()
        await old.stop(drain=True)
        new = await Daemon.spawn(old.conf)
        self.daemons[i] = new
        peers = [d.peer_info() for d in self.daemons]
        for d in self.daemons:
            d.set_peers([PeerInfo(**vars(p)) for p in peers])
        await self.settle_handoffs()
        return new

    async def settle_handoffs(self) -> None:
        """Wait for every daemon's in-flight rebalance handoff tasks (the
        set_peers diff launches them fire-and-forget). Only tasks still
        running are waited for: once in ≈50 runs under load a finished task
        is still in `_handoff_tasks` (parent and change alike; cause not
        found), and waiting on the set itself then never ends."""
        for d in self.daemons:
            while running := [t for t in d._handoff_tasks if not t.done()]:
                await asyncio.gather(*running, return_exceptions=True)

    async def stop(self) -> None:
        await asyncio.gather(*(d.close() for d in self.daemons))
        await asyncio.gather(
            *(p.stop() for p in self.proxies if p is not None)
        )


async def scrape(daemon: Daemon) -> dict:
    """GET the daemon's real /metrics endpoint and parse it — convergence
    assertions go through the wire, exactly like the reference's
    getMetrics/expfmt technique (functional_test.go:2245-2267)."""
    import aiohttp

    from gubernator_tpu.service.metrics import parse_metrics

    url = f"http://{daemon.conf.http_address}/metrics"
    async with aiohttp.ClientSession() as s:
        async with s.get(url) as resp:
            assert resp.status == 200
            return parse_metrics(await resp.text())


def metric_value(scraped: dict, name: str, **labels) -> float:
    fam = scraped.get(name, {})
    want = tuple(sorted(labels.items()))
    for labelset, value in fam.items():
        if all(kv in labelset for kv in want):
            return value
    return 0.0


async def wait_for(predicate, timeout_s: float = 5.0, interval_s: float = 0.05):
    """Poll an async predicate until truthy (waitForBroadcast analog,
    functional_test.go:2328-2385)."""
    deadline = asyncio.get_running_loop().time() + timeout_s
    while True:
        val = await predicate()
        if val:
            return val
        if asyncio.get_running_loop().time() > deadline:
            raise TimeoutError("condition not met before timeout")
        await asyncio.sleep(interval_s)
