"""Discovery pool tests without real infrastructure — the reference's own
technique (fake DNS server dns_test.go:81-294; pure k8s extraction functions
kubernetes_internal_test.go:52)."""

import asyncio
import base64
import functools
import json

import pytest
from aiohttp import web

from gubernator_tpu.types import PeerInfo


def async_test(fn):
    @functools.wraps(fn)
    def wrapper(*a, **k):
        asyncio.run(fn(*a, **k))

    return wrapper


async def wait_until(pred, timeout_s=10.0, interval_s=0.05):
    deadline = asyncio.get_running_loop().time() + timeout_s
    while True:
        if pred():
            return
        if asyncio.get_running_loop().time() > deadline:
            raise TimeoutError("condition not met")
        await asyncio.sleep(interval_s)


# ------------------------------------------------------------------ memberlist


@async_test
async def test_memberlist_three_nodes_converge_and_leave():
    from gubernator_tpu.discovery.memberlist import MemberlistPool

    seen = {}

    def updater(name):
        def cb(peers):
            seen[name] = sorted(p.grpc_address for p in peers)

        return cb

    pools = []
    # node 0 is the seed
    p0 = MemberlistPool(
        bind_address="127.0.0.1:0",
        known_nodes=[],
        on_update=updater("n0"),
        peer_info=PeerInfo(grpc_address="10.0.0.1:1051", data_center="dc-a"),
        gossip_interval_ms=50.0,
    )
    await p0.start()
    pools.append(p0)
    seed = p0.advertise_address
    for i, name in enumerate(["n1", "n2"], start=1):
        p = MemberlistPool(
            bind_address="127.0.0.1:0",
            known_nodes=[seed],
            on_update=updater(name),
            peer_info=PeerInfo(grpc_address=f"10.0.0.{i + 1}:1051"),
            gossip_interval_ms=50.0,
        )
        await p.start()
        pools.append(p)

    want = ["10.0.0.1:1051", "10.0.0.2:1051", "10.0.0.3:1051"]
    try:
        await wait_until(
            lambda: all(seen.get(n) == want for n in ("n0", "n1", "n2"))
        )
        # graceful leave propagates as a tombstone
        await pools[2].close()
        await wait_until(
            lambda: seen["n0"] == want[:2] and seen["n1"] == want[:2]
        )
    finally:
        for p in pools[:2]:
            await p.close()


@async_test
async def test_memberlist_detects_dead_peer_by_heartbeat_timeout():
    from gubernator_tpu.discovery.memberlist import MemberlistPool

    seen = {}
    p0 = MemberlistPool(
        bind_address="127.0.0.1:0",
        known_nodes=[],
        on_update=lambda ps: seen.__setitem__(
            "n0", sorted(p.grpc_address for p in ps)
        ),
        peer_info=PeerInfo(grpc_address="10.0.0.1:1051"),
        gossip_interval_ms=50.0,
        suspect_ticks=4,
    )
    await p0.start()
    p1 = MemberlistPool(
        bind_address="127.0.0.1:0",
        known_nodes=[p0.advertise_address],
        on_update=lambda ps: None,
        peer_info=PeerInfo(grpc_address="10.0.0.2:1051"),
        gossip_interval_ms=50.0,
    )
    await p1.start()
    try:
        await wait_until(
            lambda: seen.get("n0") == ["10.0.0.1:1051", "10.0.0.2:1051"]
        )
        # hard-kill node 1 (no tombstone): cancel its loop + server
        p1._closed = True
        p1._task.cancel()
        p1._server.close()
        await wait_until(lambda: seen.get("n0") == ["10.0.0.1:1051"], timeout_s=15)
    finally:
        await p0.close()
        try:
            await p1.close()
        except Exception:
            pass


# ----------------------------------------------------------------------- etcd


class FakeEtcd:
    """Minimal in-process etcd v3 HTTP JSON gateway: kv put/range/deleterange,
    lease grant/keepalive/revoke with TTL expiry."""

    def __init__(self):
        self.kv = {}  # key(str) -> (value str, lease id)
        self.leases = {}  # id -> expires_at (loop time)
        self.next_lease = 7000
        self.watchers = []  # asyncio.Queue per open watch stream
        self.app = web.Application()
        self.app.router.add_post("/v3/kv/put", self.put)
        self.app.router.add_post("/v3/kv/range", self.range)
        self.app.router.add_post("/v3/kv/deleterange", self.deleterange)
        self.app.router.add_post("/v3/lease/grant", self.grant)
        self.app.router.add_post("/v3/lease/keepalive", self.keepalive)
        self.app.router.add_post("/v3/lease/revoke", self.revoke)
        self.app.router.add_post("/v3/watch", self.watch)
        self.runner = None
        self.url = ""

    def _notify(self):
        for q in list(self.watchers):
            q.put_nowait({"type": "PUT"})

    async def watch(self, req):
        resp = web.StreamResponse()
        await resp.prepare(req)
        q = asyncio.Queue()
        self.watchers.append(q)
        await resp.write(b'{"result":{"created":true}}\n')
        try:
            while True:
                ev = await q.get()
                await resp.write(
                    json.dumps({"result": {"events": [ev]}}).encode() + b"\n"
                )
        except (asyncio.CancelledError, ConnectionResetError):
            pass
        finally:
            self.watchers.remove(q)
        return resp

    def _gc(self):
        now = asyncio.get_running_loop().time()
        dead = {lid for lid, exp in self.leases.items() if exp < now}
        for lid in dead:
            del self.leases[lid]
        self.kv = {
            k: (v, lid)
            for k, (v, lid) in self.kv.items()
            if lid is None or lid in self.leases
        }

    async def put(self, req):
        b = await req.json()
        key = base64.b64decode(b["key"]).decode()
        val = base64.b64decode(b["value"]).decode()
        self.kv[key] = (val, b.get("lease"))
        self._notify()
        return web.json_response({})

    async def range(self, req):
        self._gc()
        b = await req.json()
        key = base64.b64decode(b["key"]).decode()
        end = base64.b64decode(b.get("range_end", b["key"])).decode()
        kvs = [
            {
                "key": base64.b64encode(k.encode()).decode(),
                "value": base64.b64encode(v.encode()).decode(),
            }
            for k, (v, _) in sorted(self.kv.items())
            if key <= k < end
        ]
        return web.json_response({"kvs": kvs, "count": str(len(kvs))})

    async def deleterange(self, req):
        b = await req.json()
        key = base64.b64decode(b["key"]).decode()
        self.kv.pop(key, None)
        self._notify()
        return web.json_response({})

    async def grant(self, req):
        b = await req.json()
        lid = self.next_lease
        self.next_lease += 1
        self.leases[lid] = asyncio.get_running_loop().time() + float(b["TTL"])
        return web.json_response({"ID": str(lid), "TTL": str(b["TTL"])})

    async def keepalive(self, req):
        b = await req.json()
        lid = int(b["ID"])
        if lid not in self.leases:
            return web.json_response({"result": {"TTL": "0"}})
        self.leases[lid] = asyncio.get_running_loop().time() + 30.0
        return web.json_response({"result": {"ID": str(lid), "TTL": "30"}})

    async def revoke(self, req):
        b = await req.json()
        self.leases.pop(int(b["ID"]), None)
        self._gc()
        self._notify()
        return web.json_response({})

    async def start(self):
        self.runner = web.AppRunner(self.app)
        await self.runner.setup()
        site = web.TCPSite(self.runner, "127.0.0.1", 0)
        await site.start()
        port = self.runner.addresses[0][1]
        self.url = f"http://127.0.0.1:{port}"

    async def stop(self):
        await self.runner.cleanup()


@async_test
async def test_etcd_pool_register_discover_deregister():
    from gubernator_tpu.discovery.etcd import EtcdPool

    fake = FakeEtcd()
    await fake.start()
    seen = {}

    def updater(name):
        def cb(peers):
            seen[name] = sorted(p.grpc_address for p in peers)

        return cb

    a = EtcdPool(
        fake.url, updater("a"),
        PeerInfo(grpc_address="10.0.0.1:1051", data_center="dc-a"),
        poll_ms=50.0,
    )
    b = EtcdPool(
        fake.url, updater("b"), PeerInfo(grpc_address="10.0.0.2:1051"),
        poll_ms=50.0,
    )
    try:
        await a.start()
        await b.start()
        want = ["10.0.0.1:1051", "10.0.0.2:1051"]
        await wait_until(lambda: seen.get("a") == want and seen.get("b") == want)
        # self-markers + DC survive the JSON roundtrip
        assert "/gubernator/peers/10.0.0.1:1051" in fake.kv
        stored = json.loads(fake.kv["/gubernator/peers/10.0.0.1:1051"][0])
        assert stored["data_center"] == "dc-a"
        # close → key deleted → the other pool converges on one peer
        await b.close()
        await wait_until(lambda: seen["a"] == ["10.0.0.1:1051"])
    finally:
        await a.close()
        await fake.stop()


@async_test
async def test_etcd_pool_lease_expiry_drops_dead_peer():
    """A crashed node's key must disappear when its lease expires (the
    keepalive stops; reference etcd.go:30s lease)."""
    from gubernator_tpu.discovery.etcd import EtcdPool

    fake = FakeEtcd()
    await fake.start()
    seen = {}
    a = EtcdPool(
        fake.url,
        lambda ps: seen.__setitem__("a", sorted(p.grpc_address for p in ps)),
        PeerInfo(grpc_address="10.0.0.1:1051"),
        poll_ms=50.0,
        lease_ttl_s=1,
    )
    b = EtcdPool(
        fake.url, lambda ps: None, PeerInfo(grpc_address="10.0.0.2:1051"),
        poll_ms=50.0, lease_ttl_s=1,
    )
    try:
        await a.start()
        await b.start()
        await wait_until(
            lambda: seen.get("a") == ["10.0.0.1:1051", "10.0.0.2:1051"]
        )
        # hard-kill b: cancel its tasks without deregistering
        b._closed = True
        for t in b._tasks:
            t.cancel()
        await wait_until(lambda: seen["a"] == ["10.0.0.1:1051"], timeout_s=15)
    finally:
        await a.close()
        await b._session.close()
        await fake.stop()


@async_test
async def test_etcd_watch_propagates_membership_sub_poll():
    """Membership changes ride the watch stream, not the poll cadence
    (reference etcd.go:173-219): with polling effectively disabled, a
    register and a deregister both propagate in well under the poll
    interval."""
    from gubernator_tpu.discovery.etcd import EtcdPool

    fake = FakeEtcd()
    await fake.start()
    seen = {}

    def cb(peers):
        seen["p"] = sorted(p.grpc_address for p in peers)

    pool = EtcdPool(
        fake.url,
        on_update=cb,
        peer_info=PeerInfo(grpc_address="127.0.0.1:1"),
        poll_ms=60_000.0,  # the poller cannot be the one propagating
    )
    pool2 = EtcdPool(
        fake.url,
        on_update=lambda ps: None,
        peer_info=PeerInfo(grpc_address="127.0.0.1:2"),
        poll_ms=60_000.0,
    )
    try:
        await pool.start()
        await wait_until(lambda: seen.get("p") == ["127.0.0.1:1"], timeout_s=5)
        loop = asyncio.get_running_loop()
        t0 = loop.time()
        await pool2.start()  # registers → watch event → re-range
        await wait_until(
            lambda: seen.get("p") == ["127.0.0.1:1", "127.0.0.1:2"],
            timeout_s=5,
            interval_s=0.005,
        )
        assert loop.time() - t0 < 2.0  # event latency, not the 60 s poll
        t0 = loop.time()
        await pool2.close()  # deletes its key → watch event
        await wait_until(
            lambda: seen.get("p") == ["127.0.0.1:1"],
            timeout_s=5,
            interval_s=0.005,
        )
        assert loop.time() - t0 < 2.0
    finally:
        await pool.close()
        await fake.stop()


# ------------------------------------------------------------------------ k8s


def _slice(endpoints, address_type="IPv4"):
    return {"addressType": address_type, "endpoints": endpoints}


def test_extract_peers_from_endpoint_slices():
    from gubernator_tpu.discovery.kubernetes import (
        extract_peers_from_endpoint_slices,
    )

    slices = [
        _slice(
            [
                {"addresses": ["10.0.0.1"], "conditions": {"ready": True}},
                {"addresses": ["10.0.0.2"], "conditions": {"ready": False}},
                {"addresses": ["10.0.0.3"]},  # no conditions → ready
                {"addresses": []},  # ignored
            ]
        ),
        _slice([{"addresses": ["fe80::1"]}], address_type="IPv6"),  # ignored
        # duplicate of .1 in a second slice must not duplicate the peer
        _slice([{"addresses": ["10.0.0.1"], "conditions": {"ready": True}}]),
    ]
    peers = extract_peers_from_endpoint_slices(slices, "10.0.0.9", "1051")
    assert sorted(p.grpc_address for p in peers) == [
        "10.0.0.1:1051",
        "10.0.0.3:1051",
    ]
    # a NOT-ready self must still be included (kubernetes.go:281-289)
    peers = extract_peers_from_endpoint_slices(slices, "10.0.0.2", "1051")
    got = {p.grpc_address: p.is_owner for p in peers}
    assert got == {
        "10.0.0.1:1051": False,
        "10.0.0.2:1051": True,
        "10.0.0.3:1051": False,
    }


def test_extract_peers_from_pods():
    from gubernator_tpu.discovery.kubernetes import extract_peers_from_pods

    pods = [
        {
            "status": {
                "podIP": "10.0.0.1",
                "phase": "Running",
                "conditions": [{"type": "Ready", "status": "True"}],
            }
        },
        {
            "status": {
                "podIP": "10.0.0.2",
                "phase": "Pending",
                "conditions": [],
            }
        },
        {"status": {}},  # no IP yet
    ]
    peers = extract_peers_from_pods(pods, "10.0.0.9", "1051")
    assert [p.grpc_address for p in peers] == ["10.0.0.1:1051"]
    # self included even when not ready
    peers = extract_peers_from_pods(pods, "10.0.0.2", "1051")
    assert sorted(p.grpc_address for p in peers) == [
        "10.0.0.1:1051",
        "10.0.0.2:1051",
    ]


async def _fake_k8s_api(state):
    """In-process API server: list + watch on endpointslices. Returns
    (url, runner, notify) — notify() pushes a watch event to open streams."""
    state.setdefault("watchers", [])
    state.setdefault("rv", 7)
    app = web.Application()

    async def endpointslices(req):
        assert req.headers.get("Authorization") == "Bearer test-token"
        assert req.query.get("labelSelector") == "app=gubernator"
        if req.query.get("watch"):
            resp = web.StreamResponse()
            await resp.prepare(req)
            q = asyncio.Queue()
            state["watchers"].append(q)
            try:
                while True:
                    ev = await q.get()
                    await resp.write(json.dumps(ev).encode() + b"\n")
            except (asyncio.CancelledError, ConnectionResetError):
                pass
            finally:
                state["watchers"].remove(q)
            return resp
        return web.json_response(
            {"items": state["items"],
             "metadata": {"resourceVersion": str(state["rv"])}}
        )

    app.router.add_get(
        "/apis/discovery.k8s.io/v1/namespaces/default/endpointslices",
        endpointslices,
    )
    # a watch handler blocked on its queue never ends by itself: without a
    # short shutdown_timeout, cleanup() waits aiohttp's default 60 s for it
    runner = web.AppRunner(app, shutdown_timeout=0.5)
    await runner.setup()
    site = web.TCPSite(runner, "127.0.0.1", 0)
    await site.start()
    url = f"http://127.0.0.1:{runner.addresses[0][1]}"

    def notify():
        state["rv"] += 1
        for q in list(state["watchers"]):
            q.put_nowait(
                {"type": "MODIFIED",
                 "object": {"metadata": {"resourceVersion": str(state["rv"])}}}
            )

    return url, runner, notify


@async_test
async def test_k8s_pool_against_fake_api():
    from gubernator_tpu.discovery.kubernetes import K8sPool

    state = {
        "items": [
            _slice([{"addresses": ["10.0.0.1"], "conditions": {"ready": True}}])
        ]
    }
    url, runner, _notify = await _fake_k8s_api(state)

    seen = {}
    pool = K8sPool(
        on_update=lambda ps: seen.__setitem__(
            "p", sorted(p.grpc_address for p in ps)
        ),
        pod_ip="10.0.0.1",
        pod_port="1051",
        selector="app=gubernator",
        api_url=url,
        token="test-token",
        poll_ms=50.0,
    )
    try:
        await pool.start()
        await wait_until(lambda: seen.get("p") == ["10.0.0.1:1051"])
        # a new ready endpoint appears → next poll picks it up
        state["items"][0]["endpoints"].append(
            {"addresses": ["10.0.0.2"], "conditions": {"ready": True}}
        )
        await wait_until(
            lambda: seen.get("p") == ["10.0.0.1:1051", "10.0.0.2:1051"]
        )
    finally:
        await pool.close()
        await runner.cleanup()


@async_test
async def test_k8s_watch_propagates_membership_sub_poll():
    """Membership changes ride the list+watch stream, not the resync poll
    (reference kubernetes.go:79-114 informer): with polling effectively
    disabled, an endpoint change propagates at event latency."""
    from gubernator_tpu.discovery.kubernetes import K8sPool

    state = {
        "items": [
            _slice([{"addresses": ["10.0.0.1"], "conditions": {"ready": True}}])
        ]
    }
    url, runner, notify = await _fake_k8s_api(state)
    seen = {}
    pool = K8sPool(
        on_update=lambda ps: seen.__setitem__(
            "p", sorted(p.grpc_address for p in ps)
        ),
        pod_ip="10.0.0.1",
        pod_port="1051",
        selector="app=gubernator",
        api_url=url,
        token="test-token",
        poll_ms=60_000.0,  # the resync poll cannot be the one propagating
    )
    try:
        await pool.start()
        await wait_until(lambda: seen.get("p") == ["10.0.0.1:1051"])
        await wait_until(lambda: state["watchers"], timeout_s=5)
        loop = asyncio.get_running_loop()
        t0 = loop.time()
        state["items"][0]["endpoints"].append(
            {"addresses": ["10.0.0.2"], "conditions": {"ready": True}}
        )
        notify()  # watch event → list+extract
        await wait_until(
            lambda: seen.get("p") == ["10.0.0.1:1051", "10.0.0.2:1051"],
            timeout_s=5,
            interval_s=0.005,
        )
        assert loop.time() - t0 < 2.0  # event latency, not the 60 s resync
    finally:
        await pool.close()
        await runner.cleanup()


def test_config_validates_discovery_requirements():
    from gubernator_tpu.config import ConfigError, DaemonConfig

    with pytest.raises(ConfigError):
        DaemonConfig(peer_discovery_type="etcd").validate()
    with pytest.raises(ConfigError):
        DaemonConfig(peer_discovery_type="member-list").validate()
    with pytest.raises(ConfigError):
        DaemonConfig(peer_discovery_type="bogus").validate()
    DaemonConfig(
        peer_discovery_type="etcd", etcd_endpoint="http://127.0.0.1:2379"
    ).validate()
    DaemonConfig(
        peer_discovery_type="member-list", memberlist_address="127.0.0.1:7946"
    ).validate()
    # k8s requires a pod IP (self-recognition) and a selector — without one
    # the pool would join every workload in the namespace into the peer ring
    with pytest.raises(ConfigError):
        DaemonConfig(peer_discovery_type="k8s", k8s_selector="a=b").validate()
    with pytest.raises(ConfigError):
        DaemonConfig(peer_discovery_type="k8s", k8s_pod_ip="10.0.0.1").validate()
    DaemonConfig(
        peer_discovery_type="k8s", k8s_pod_ip="10.0.0.1",
        k8s_selector="app=gubernator",
    ).validate()


@async_test
async def test_daemons_discover_each_other_via_memberlist():
    """Full path: two daemons boot with member-list discovery and converge on
    a shared peer ring without any explicit set_peers."""
    from tests.cluster import daemon_config

    from gubernator_tpu.service.daemon import Daemon

    d0 = await Daemon.spawn(
        daemon_config(
            peer_discovery_type="member-list",
            memberlist_address="127.0.0.1:0",
            memberlist_gossip_interval_ms=50.0,
        )
    )
    seed = d0._pool.advertise_address
    d1 = await Daemon.spawn(
        daemon_config(
            peer_discovery_type="member-list",
            memberlist_address="127.0.0.1:0",
            memberlist_known_nodes=seed,
            memberlist_gossip_interval_ms=50.0,
        )
    )
    try:
        want = sorted(
            [d0.conf.advertise_address, d1.conf.advertise_address]
        )
        await wait_until(
            lambda: sorted(p.grpc_address for p in d0.local_peers()) == want
            and sorted(p.grpc_address for p in d1.local_peers()) == want,
            timeout_s=15,
        )
        # the ring agrees on ownership across both daemons
        owner0 = d0.get_peer("some_key").grpc_address
        owner1 = d1.get_peer("some_key").grpc_address
        assert owner0 == owner1
    finally:
        await d1.close()
        await d0.close()


@async_test
async def test_memberlist_aes_gcm_keyring():
    """Gossip encryption (reference SecretKey/keyring, memberlist.go:149-167):
    nodes sharing a key converge; a keyless or wrong-key node can neither
    read nor inject state; an old-keyring node still interops during
    rotation (new key first, old key still accepted)."""
    import os

    from gubernator_tpu.discovery.memberlist import MemberlistPool

    key_a = os.urandom(32)
    key_b = os.urandom(32)
    seen = {}

    def updater(name):
        return lambda ps: seen.__setitem__(
            name, sorted(p.grpc_address for p in ps)
        )

    p0 = MemberlistPool(
        bind_address="127.0.0.1:0", known_nodes=[],
        on_update=updater("n0"),
        peer_info=PeerInfo(grpc_address="10.1.0.1:1051"),
        gossip_interval_ms=50.0, secret_keys=[key_a],
    )
    await p0.start()
    seed = p0.advertise_address
    p1 = MemberlistPool(
        bind_address="127.0.0.1:0", known_nodes=[seed],
        on_update=updater("n1"),
        peer_info=PeerInfo(grpc_address="10.1.0.2:1051"),
        gossip_interval_ms=50.0, secret_keys=[key_a],
    )
    await p1.start()
    # rotation: node 2 sends with key_b but still accepts key_a
    p2 = MemberlistPool(
        bind_address="127.0.0.1:0", known_nodes=[seed],
        on_update=updater("n2"),
        peer_info=PeerInfo(grpc_address="10.1.0.3:1051"),
        gossip_interval_ms=50.0, secret_keys=[key_b, key_a],
    )
    # ... so the cluster must also accept key_b for p2's sends to land
    p0.secret_keys.append(key_b)
    p1.secret_keys.append(key_b)
    await p2.start()
    # intruders: plaintext and wrong-key nodes must stay invisible
    evil_plain = MemberlistPool(
        bind_address="127.0.0.1:0", known_nodes=[seed],
        on_update=updater("evil_plain"),
        peer_info=PeerInfo(grpc_address="10.66.0.1:1051"),
        gossip_interval_ms=50.0,
    )
    await evil_plain.start()
    evil_key = MemberlistPool(
        bind_address="127.0.0.1:0", known_nodes=[seed],
        on_update=updater("evil_key"),
        peer_info=PeerInfo(grpc_address="10.66.0.2:1051"),
        gossip_interval_ms=50.0, secret_keys=[os.urandom(32)],
    )
    await evil_key.start()
    want = ["10.1.0.1:1051", "10.1.0.2:1051", "10.1.0.3:1051"]
    try:
        await wait_until(
            lambda: all(seen.get(n) == want for n in ("n0", "n1", "n2"))
        )
        # the intruders never learned the cluster, the cluster never saw them
        assert seen.get("evil_plain", ["10.66.0.1:1051"]) == ["10.66.0.1:1051"]
        assert seen.get("evil_key", ["10.66.0.2:1051"]) == ["10.66.0.2:1051"]
        assert seen["n0"] == want
    finally:
        for p in (p0, p1, p2, evil_plain, evil_key):
            await p.close()


def test_memberlist_secret_key_validation():
    import base64
    import os

    import pytest as _pytest

    from gubernator_tpu.config import ConfigError, DaemonConfig
    from gubernator_tpu.discovery.memberlist import MemberlistPool

    with _pytest.raises(ValueError, match="16, 24 or 32"):
        MemberlistPool(
            bind_address="127.0.0.1:0", known_nodes=[],
            on_update=lambda ps: None,
            peer_info=PeerInfo(grpc_address="x:1"),
            secret_keys=[b"short"],
        )
    good = base64.b64encode(os.urandom(32)).decode()
    DaemonConfig(memberlist_secret_keys=good).validate()
    with _pytest.raises(ConfigError, match="base64"):
        DaemonConfig(memberlist_secret_keys="!!notb64!!").validate()
    with _pytest.raises(ConfigError, match="16, 24 or 32"):
        DaemonConfig(
            memberlist_secret_keys=base64.b64encode(b"tooshort").decode()
        ).validate()
