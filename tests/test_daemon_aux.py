"""Config system, DNS discovery, checkpoint/resume, TLS — the daemon's
auxiliary subsystems (reference config.go / dns.go / store.go / tls.go)."""

import asyncio
import functools
import os

import pytest

from gubernator_tpu.client import V1Client
from gubernator_tpu.config import (
    ConfigError,
    DaemonConfig,
    load_config_file,
    setup_daemon_config,
)
from gubernator_tpu.types import RateLimitRequest

from tests.cluster import daemon_config


def async_test(fn):
    @functools.wraps(fn)
    def wrapper(*a, **k):
        asyncio.run(fn(*a, **k))

    return wrapper


def req(key, hits=1, limit=5):
    return RateLimitRequest(
        name="aux", unique_key=key, hits=hits, limit=limit, duration=60_000
    )


# -------------------------------------------------------------------- config


def test_config_from_env():
    env = {
        "GUBER_GRPC_ADDRESS": "127.0.0.1:9999",
        "GUBER_HTTP_ADDRESS": "127.0.0.1:9998",
        "GUBER_CACHE_SIZE": "12345",
        "GUBER_BATCH_WAIT": "2ms",
        "GUBER_GLOBAL_SYNC_WAIT": "1s",
        "GUBER_BATCH_LIMIT": "500",
        "GUBER_DATA_CENTER": "dc-west",
        "GUBER_FORCE_GLOBAL": "true",
    }
    conf = setup_daemon_config(env=env)
    assert conf.grpc_address == "127.0.0.1:9999"
    assert conf.cache_size == 12345
    assert conf.behaviors.batch_wait_ms == 2.0
    assert conf.behaviors.global_sync_wait_ms == 1000.0
    assert conf.behaviors.batch_limit == 500
    assert conf.data_center == "dc-west"
    assert conf.behaviors.force_global is True
    assert conf.advertise_address == "127.0.0.1:9999"


def test_config_file_seeds_env_but_real_env_wins(tmp_path):
    f = tmp_path / "guber.conf"
    f.write_text(
        "# comment\n\nGUBER_CACHE_SIZE=777\nGUBER_DATA_CENTER = dc-file\n"
    )
    env = {"GUBER_DATA_CENTER": "dc-env"}
    conf = setup_daemon_config(config_file=str(f), env=env)
    assert conf.cache_size == 777  # from file
    assert conf.data_center == "dc-env"  # real env wins (config.go:703-726)


def test_config_validation_errors():
    with pytest.raises(ConfigError, match="GUBER_PEER_DISCOVERY_TYPE"):
        setup_daemon_config(env={"GUBER_PEER_DISCOVERY_TYPE": "etcd"})
    with pytest.raises(ConfigError, match="GUBER_DNS_FQDN"):
        setup_daemon_config(env={"GUBER_PEER_DISCOVERY_TYPE": "dns"})
    with pytest.raises(ConfigError, match="GUBER_BATCH_LIMIT"):
        setup_daemon_config(env={"GUBER_BATCH_LIMIT": "5000"})
    with pytest.raises(ConfigError, match="integer"):
        setup_daemon_config(env={"GUBER_CACHE_SIZE": "lots"})
    with pytest.raises(ConfigError, match="key=value"):
        import tempfile

        with tempfile.NamedTemporaryFile("w", suffix=".conf", delete=False) as f:
            f.write("not-a-pair\n")
        try:
            load_config_file(f.name, {})
        finally:
            os.unlink(f.name)


@pytest.mark.parametrize("name, value", [
    ("GUBER_RING_ENABLE", "1"), ("GUBER_RING_SLOTS", "8"),
    ("GUBER_RING_ISSUE", "fused"), ("GUBER_RING_DRAIN_K", "4"),
    ("GUBER_RING_SLOT_WIDTH", "4096"),
])
def test_a_retired_ring_setting_refuses_to_start(name, value, tmp_path):
    """The request ring's settings were documented (docs/latency.md,
    example.conf) until the ring went: an operator who still exports one,
    or keeps it in the config file, is told by name that it is gone, and is
    not left believing the ring is on."""
    with pytest.raises(ConfigError, match=f"{name} is set.*gone"):
        setup_daemon_config(env={"GUBER_GRPC_ADDRESS": "127.0.0.1:0", name: value})
    f = tmp_path / "guber.conf"
    f.write_text(f"GUBER_CACHE_SIZE=777\n{name}={value}\n")
    with pytest.raises(ConfigError, match=name):
        setup_daemon_config(config_file=str(f), env={})
    # unset, or set to nothing: the daemon starts
    assert setup_daemon_config(env={name: ""}).cache_size == 50_000


# ----------------------------------------------------------------- discovery


@async_test
async def test_dns_pool_with_fake_resolver():
    """DNS pool against an injected resolver (reference dns_test.go:81-294):
    peer set follows record changes; empty answers never clear the list
    (dns.go:253-264)."""
    from gubernator_tpu.discovery.dns import DNSPool

    answers = {"cluster.test": ["10.0.0.1", "10.0.0.2"]}
    calls = []

    def resolver(fqdn, port):
        calls.append(fqdn)
        return [f"{ip}:{port}" for ip in answers.get(fqdn, [])]

    seen = []
    pool = DNSPool(
        fqdn="cluster.test",
        poll_ms=20.0,
        on_update=lambda peers: seen.append([p.grpc_address for p in peers]),
        self_address="10.0.0.1:1051",
        resolver=resolver,
    )
    await pool.start()
    try:
        assert seen == [["10.0.0.1:1051", "10.0.0.2:1051"]]
        # a record appears → update fires once with the new set
        answers["cluster.test"] = ["10.0.0.1", "10.0.0.2", "10.0.0.3"]
        await asyncio.sleep(0.08)
        assert seen[-1] == ["10.0.0.1:1051", "10.0.0.2:1051", "10.0.0.3:1051"]
        n_updates = len(seen)
        # resolver failure → stale list kept, no update fired
        answers["cluster.test"] = []
        await asyncio.sleep(0.08)
        assert len(seen) == n_updates
    finally:
        await pool.close()


@async_test
async def test_daemon_boots_from_env_with_dns():
    """Daemon boots from env alone (discovery=dns, fake-resolved to self)."""
    from unittest import mock

    from gubernator_tpu.discovery import dns as dns_mod
    from gubernator_tpu.service.daemon import Daemon

    conf = setup_daemon_config(
        env={
            "GUBER_GRPC_ADDRESS": "127.0.0.1:0",
            "GUBER_HTTP_ADDRESS": "127.0.0.1:0",
            "GUBER_PEER_DISCOVERY_TYPE": "dns",
            "GUBER_DNS_FQDN": "self.test",
            "GUBER_DNS_POLL": "50ms",
            "GUBER_CACHE_SIZE": "4096",
        }
    )

    def resolver(fqdn, port):
        return [f"127.0.0.1:{port}"]

    with mock.patch.object(dns_mod, "system_resolver", resolver):
        d = await Daemon.spawn(conf)
    try:
        # resolver returned self → single-peer cluster, serving locally
        client = V1Client(d.conf.grpc_address, timeout_s=15.0)
        resp = await client.get_rate_limits([req("dns1")])
        assert resp.responses[0].remaining == 4
        assert d.local_peers()[0].is_owner
        await client.close()
    finally:
        await d.close()


# ---------------------------------------------------------------- checkpoint


@async_test
async def test_checkpoint_survives_restart(tmp_path):
    """Kill/restart a daemon with GUBER_CHECKPOINT_PATH: remaining counts
    survive (reference TestLoader, store_test.go:76)."""
    from gubernator_tpu.service.daemon import Daemon

    snap = str(tmp_path / "table.ckpt")
    conf = daemon_config()
    conf.checkpoint_path = snap
    d = await Daemon.spawn(conf)
    client = V1Client(d.conf.grpc_address, timeout_s=15.0)
    resp = await client.get_rate_limits([req("ck1", hits=3, limit=10)])
    assert resp.responses[0].remaining == 7
    await client.close()
    await d.close()  # checkpoint written on graceful shutdown
    assert os.path.exists(snap)

    d2 = await Daemon.spawn(conf)  # restores on boot
    client = V1Client(d2.conf.grpc_address, timeout_s=15.0)
    try:
        resp = await client.get_rate_limits([req("ck1", hits=1, limit=10)])
        assert resp.responses[0].remaining == 6  # 10 - 3 (restored) - 1
    finally:
        await client.close()
        await d2.close()


def test_snapshot_rejects_garbage(tmp_path):
    import numpy as np

    from gubernator_tpu.store import load_snapshot, save_snapshot

    p = tmp_path / "x.ckpt"
    np.savez(p, magic=np.frombuffer(b"NOTGUB!", dtype=np.uint8), rows=np.zeros(3))
    with pytest.raises(ValueError, match="not a gubernator-tpu snapshot"):
        load_snapshot(str(p) + ".npz")  # np.savez appends .npz
    save_snapshot(str(p), np.arange(12, dtype=np.int32).reshape(3, 4))
    assert load_snapshot(str(p)).tolist()[1] == [4, 5, 6, 7]


# ----------------------------------------------------------------------- tls


@async_test
async def test_auto_tls_daemon():
    """AutoTLS: self-signed CA + cert generated at boot; a client presenting
    that CA connects; the gRPC listener speaks TLS (reference tls_test.go)."""
    import grpc

    from gubernator_tpu.service.daemon import Daemon
    from gubernator_tpu.service.tls import bundle_from_config

    conf = daemon_config()
    conf.tls_auto = True
    conf.http_address = ""  # gRPC-only for this test
    d = await Daemon.spawn(conf)
    try:
        bundle = bundle_from_config(d.conf)
        creds = grpc.ssl_channel_credentials(root_certificates=bundle.ca_pem)
        client = V1Client(d.conf.grpc_address, credentials=creds, timeout_s=15.0)
        resp = await client.get_rate_limits([req("tls1")])
        assert resp.responses[0].remaining == 4
        await client.close()
        # plaintext client must NOT work against the TLS port
        plain = V1Client(d.conf.grpc_address, timeout_s=2.0)
        with pytest.raises(grpc.aio.AioRpcError):
            await plain.get_rate_limits([req("tls2")])
        await plain.close()
    finally:
        await d.close()


@async_test
async def test_tls_http_gateway_and_status_listener(tmp_path):
    """With TLS on, the HTTP gateway serves HTTPS under the daemon's
    client-auth mode, and the separate status listener serves health +
    /metrics over TLS WITHOUT client certs (reference
    HTTPStatusListenAddress, daemon.go:150-155, 324-352) — previously /v1
    JSON and /metrics left the host in the clear while gRPC was mTLS."""
    import ssl

    import aiohttp

    from gubernator_tpu.service.daemon import Daemon
    from gubernator_tpu.service.tls import generate_self_signed

    bundle = generate_self_signed(("127.0.0.1",))
    ca = tmp_path / "ca.pem"; ca.write_bytes(bundle.ca_pem)
    crt = tmp_path / "crt.pem"; crt.write_bytes(bundle.cert_pem)
    key = tmp_path / "key.pem"; key.write_bytes(bundle.key_pem)

    conf = daemon_config(
        tls_ca_file=str(ca), tls_cert_file=str(crt), tls_key_file=str(key),
        tls_client_auth="verify", status_http_address="127.0.0.1:0",
    )
    d = await Daemon.spawn(conf)
    try:
        gw = f"https://{d.conf.http_address}"
        status = f"https://{d.conf.status_http_address}"
        trust = ssl.create_default_context(cadata=bundle.ca_pem.decode())
        trust.check_hostname = False
        mtls = ssl.create_default_context(cadata=bundle.ca_pem.decode())
        mtls.check_hostname = False
        mtls.load_cert_chain(str(crt), str(key))

        async with aiohttp.ClientSession() as s:
            # status listener: CA-trust only, no client cert → works
            async with s.get(f"{status}/metrics", ssl=trust) as r:
                assert r.status == 200
                assert b"gubernator_" in await r.read()
            async with s.get(f"{status}/v1/HealthCheck", ssl=trust) as r:
                assert r.status == 200
            # the status listener has NO rate-limit surface
            async with s.post(
                f"{status}/v1/GetRateLimits", json={"requests": []}, ssl=trust
            ) as r:
                assert r.status == 404
            # main gateway: requires a client certificate
            with pytest.raises(aiohttp.ClientError):
                async with s.get(f"{gw}/metrics", ssl=trust) as r:
                    await r.read()
            # with the client cert, the full JSON surface works over TLS
            async with s.post(
                f"{gw}/v1/GetRateLimits",
                json={"requests": [{"name": "t", "unique_key": "h",
                                    "hits": 1, "limit": 5,
                                    "duration": 60000}]},
                ssl=mtls,
            ) as r:
                assert r.status == 200
                body = await r.json()
                assert body["responses"][0]["remaining"] == "4"
            # plaintext against the TLS gateway fails
            with pytest.raises(aiohttp.ClientError):
                async with s.get(
                    f"http://{d.conf.http_address}/metrics"
                ) as r:
                    await r.read()
    finally:
        await d.close()


@async_test
async def test_mtls_cluster_forwards_between_peers(tmp_path):
    """mTLS (client_auth=verify): two daemons share a CA-signed cert from
    files; forwarding works peer-to-peer over mutual TLS, and a client
    WITHOUT a cert is rejected (reference tls_test.go:238 mTLS cluster)."""
    import grpc

    from gubernator_tpu.service.daemon import Daemon
    from gubernator_tpu.service.tls import generate_self_signed
    from gubernator_tpu.types import PeerInfo

    bundle = generate_self_signed(("127.0.0.1",))
    ca = tmp_path / "ca.pem"; ca.write_bytes(bundle.ca_pem)
    crt = tmp_path / "crt.pem"; crt.write_bytes(bundle.cert_pem)
    key = tmp_path / "key.pem"; key.write_bytes(bundle.key_pem)

    daemons = []
    for _ in range(2):
        conf = daemon_config(
            tls_ca_file=str(ca), tls_cert_file=str(crt), tls_key_file=str(key),
            tls_client_auth="verify", http_address="",
        )
        daemons.append(await Daemon.spawn(conf))
    peers = [d.peer_info() for d in daemons]
    for d in daemons:
        d.set_peers([PeerInfo(**vars(p)) for p in peers])
    try:
        creds = grpc.ssl_channel_credentials(
            root_certificates=bundle.ca_pem,
            private_key=bundle.key_pem,
            certificate_chain=bundle.cert_pem,
        )
        # find a key owned by daemon 1 and send it to daemon 0 → forwarded
        # over the mTLS peer channel
        for i in range(50):
            k = f"mtls-{i}"
            owner = daemons[0].get_peer("t_" + k)
            if owner.grpc_address == daemons[1].conf.advertise_address:
                break
        client = V1Client(daemons[0].conf.grpc_address, credentials=creds, timeout_s=15.0)
        try:
            resp = await client.get_rate_limits(
                [dict(name="t", unique_key=k, hits=1, limit=5, duration=60_000)]
            )
            assert resp.responses[0].error == ""
            assert resp.responses[0].remaining == 4
        finally:
            await client.close()
        # a client with the CA but NO client cert must be rejected
        noauth = V1Client(
            daemons[0].conf.grpc_address,
            credentials=grpc.ssl_channel_credentials(root_certificates=bundle.ca_pem),
            timeout_s=3.0,
        )
        with pytest.raises(grpc.aio.AioRpcError):
            await noauth.get_rate_limits([req("x")])
        await noauth.close()
    finally:
        for d in daemons:
            await d.close()


@async_test
async def test_tls_hot_cert_reload(tmp_path):
    """Rotating the PEM files on disk takes effect without a restart: new
    handshakes serve the new certificate (reference keypairReloader,
    tls.go:295-362)."""
    import os

    import grpc

    from gubernator_tpu.service.daemon import Daemon
    from gubernator_tpu.service.tls import generate_self_signed

    b1 = generate_self_signed(("127.0.0.1",))
    crt = tmp_path / "crt.pem"; crt.write_bytes(b1.cert_pem)
    key = tmp_path / "key.pem"; key.write_bytes(b1.key_pem)
    conf = daemon_config(
        tls_cert_file=str(crt), tls_key_file=str(key), http_address="",
    )
    d = await Daemon.spawn(conf)
    try:
        c1 = V1Client(
            d.conf.grpc_address,
            credentials=grpc.ssl_channel_credentials(root_certificates=b1.ca_pem),
            timeout_s=15.0,
        )
        assert (await c1.get_rate_limits([req("r1")])).responses[0].remaining == 4
        await c1.close()

        # rotate: a DIFFERENT CA signs the new pair
        b2 = generate_self_signed(("127.0.0.1",))
        crt.write_bytes(b2.cert_pem)
        key.write_bytes(b2.key_pem)
        future = __import__("time").time() + 2
        os.utime(crt, (future, future))
        os.utime(key, (future, future))

        # a client trusting ONLY the new CA now connects...
        c2 = V1Client(
            d.conf.grpc_address,
            credentials=grpc.ssl_channel_credentials(root_certificates=b2.ca_pem),
            timeout_s=15.0,
        )
        assert (await c2.get_rate_limits([req("r2")])).responses[0].remaining == 4
        await c2.close()
        # ...and one trusting only the OLD CA is refused
        c3 = V1Client(
            d.conf.grpc_address,
            credentials=grpc.ssl_channel_credentials(root_certificates=b1.ca_pem),
            timeout_s=3.0,
        )
        with pytest.raises(grpc.aio.AioRpcError):
            await c3.get_rate_limits([req("r3")])
        await c3.close()
    finally:
        await d.close()


@async_test
async def test_mtls_rotation_rewires_peer_channels(tmp_path, monkeypatch):
    """Rotating the CA+cert of a verify-mode cluster: the watcher rebuilds
    peer-client credentials and re-dials, so forwarding keeps working after
    the old CA stops being trusted."""
    import os
    import time as _time

    import grpc

    from gubernator_tpu.service.daemon import Daemon
    from gubernator_tpu.service.tls import generate_self_signed
    from gubernator_tpu.types import PeerInfo

    monkeypatch.setattr(Daemon, "cert_watch_interval_s", 0.1)
    b1 = generate_self_signed(("127.0.0.1",))
    ca = tmp_path / "ca.pem"; ca.write_bytes(b1.ca_pem)
    crt = tmp_path / "crt.pem"; crt.write_bytes(b1.cert_pem)
    key = tmp_path / "key.pem"; key.write_bytes(b1.key_pem)

    daemons = []
    for _ in range(2):
        conf = daemon_config(
            tls_ca_file=str(ca), tls_cert_file=str(crt), tls_key_file=str(key),
            tls_client_auth="verify", http_address="",
        )
        daemons.append(await Daemon.spawn(conf))
    peers = [d.peer_info() for d in daemons]
    for d in daemons:
        d.set_peers([PeerInfo(**vars(p)) for p in peers])
    try:
        # rotate everything to a fresh CA
        b2 = generate_self_signed(("127.0.0.1",))
        future = _time.time() + 2
        for p, data in [(ca, b2.ca_pem), (crt, b2.cert_pem), (key, b2.key_pem)]:
            p.write_bytes(data)
            os.utime(p, (future, future))
        await asyncio.sleep(0.5)  # a few watcher ticks

        creds = grpc.ssl_channel_credentials(
            root_certificates=b2.ca_pem,
            private_key=b2.key_pem,
            certificate_chain=b2.cert_pem,
        )
        for i in range(50):
            k = f"rot-{i}"
            if (
                daemons[0].get_peer("t_" + k).grpc_address
                == daemons[1].conf.advertise_address
            ):
                break
        client = V1Client(
            daemons[0].conf.grpc_address, credentials=creds, timeout_s=15.0
        )
        try:
            resp = await client.get_rate_limits(
                [dict(name="t", unique_key=k, hits=1, limit=5, duration=60_000)]
            )
            # the forwarded hop succeeded over the ROTATED mTLS pair
            assert resp.responses[0].error == ""
            assert resp.responses[0].remaining == 4
        finally:
            await client.close()
    finally:
        for d in daemons:
            await d.close()


@async_test
async def test_graceful_termination_delay_keeps_serving():
    """GUBER_GRACEFUL_TERMINATION_DELAY: liveness fails immediately on close
    while requests still serve during the delay window (reference
    daemon.go:389-391 LB de-registration)."""
    from gubernator_tpu.service.daemon import Daemon

    conf = daemon_config()
    conf.graceful_termination_delay_s = 0.6
    d = await Daemon.spawn(conf)
    client = V1Client(d.conf.grpc_address, timeout_s=15.0)
    try:
        await client.get_rate_limits([req("gt")])
        t0 = asyncio.get_running_loop().time()
        closer = asyncio.create_task(d.close())
        await asyncio.sleep(0.1)
        # liveness already failing (LBs de-register)...
        with pytest.raises(RuntimeError):
            d.live_check()
        # ...but traffic still serves inside the delay window
        r = await client.get_rate_limits([req("gt")])
        assert r.responses[0].error == ""
        await closer
        assert asyncio.get_running_loop().time() - t0 >= 0.6
    finally:
        await client.close()


@async_test
async def test_memory_loader_and_recording_store_hooks():
    """Custom Loader/Store hooks through the daemon lifecycle — the
    reference's embedding pattern (TestLoader/TestStore, store_test.go:76,127
    over in-tree MockLoader/MockStore)."""
    from gubernator_tpu.hashing import fingerprint
    from gubernator_tpu.service.daemon import Daemon
    from gubernator_tpu.store import MemoryLoader, RecordingStore

    loader = MemoryLoader()
    store = RecordingStore()
    d = await Daemon.spawn(daemon_config(), store=store, loader=loader)
    client = V1Client(d.conf.grpc_address)
    try:
        await client.get_rate_limits(
            [dict(name="ld", unique_key="k1", hits=3, limit=9, duration=60_000)]
        )
    finally:
        await client.close()
        await d.close()
    assert loader.load_called == 1
    assert loader.save_called == 1  # shutdown snapshot landed in memory
    assert fingerprint("ld", "k1") in store.touched_fps

    # a fresh daemon restoring from the SAME loader continues the counts
    d2 = await Daemon.spawn(daemon_config(), loader=loader)
    client = V1Client(d2.conf.grpc_address)
    try:
        r = await client.get_rate_limits(
            [dict(name="ld", unique_key="k1", hits=0, limit=9, duration=60_000)]
        )
        assert r.responses[0].remaining == 6  # 9 - 3 survived via MemoryLoader
    finally:
        await client.close()
        await d2.close()


def test_example_conf_parses_and_validates():
    """example.conf documents every knob; loading it must parse cleanly and
    produce a valid config (all entries are commented defaults, and any
    uncommented sample must round-trip)."""
    import os

    path = os.path.join(os.path.dirname(__file__), "..", "example.conf")
    env = {}
    load_config_file(path, env)
    conf = setup_daemon_config(env=env)
    conf.validate()


def test_coalesce_limit_env_reaches_the_batcher():
    from gubernator_tpu.service.daemon import Daemon

    conf = setup_daemon_config(
        env={
            "GUBER_GRPC_ADDRESS": "127.0.0.1:0",
            "GUBER_HTTP_ADDRESS": "",
            "GUBER_BATCH_COALESCE_LIMIT": "4096",
            "GUBER_CACHE_SIZE": "4096",
        }
    )
    d = Daemon(conf)  # batcher wiring happens in __init__, no spawn needed
    assert d.batcher.coalesce_limit == 4096
    d.runner.close()
    with pytest.raises(ConfigError):
        setup_daemon_config(env={"GUBER_BATCH_COALESCE_LIMIT": "0"})


@async_test
async def test_coalesce_limit_caps_dispatch_size():
    """The limit is a real per-dispatch cap: concurrent enqueues exceeding it
    split into multiple kernel dispatches of whole sub-batches."""
    from gubernator_tpu.ops.batch import columns_from_requests
    from gubernator_tpu.ops.engine import LocalEngine
    from gubernator_tpu.service.batcher import Batcher
    from gubernator_tpu.service.runner import EngineRunner

    engine = LocalEngine(capacity=4096)
    runner = EngineRunner(engine)
    sizes = []
    orig = runner.check  # the batcher's (pipelined) entry point

    async def spy(cols, **kw):  # a chunk: the list of its entries' columns
        sizes.append(sum(c.fp.shape[0] for c in cols))
        return await orig(cols, **kw)

    runner.check = spy
    b = Batcher(runner, batch_wait_ms=5.0, coalesce_limit=32)
    reqs = lambda tag, n: columns_from_requests(
        [
            RateLimitRequest(
                name="cl", unique_key=f"{tag}-{i}", hits=1, limit=100,
                duration=60_000,
            )
            for i in range(n)
        ]
    )
    outs = await asyncio.gather(
        b.check(reqs("a", 20)), b.check(reqs("b", 20)), b.check(reqs("c", 20))
    )
    assert [o.status.shape[0] for o in outs] == [20, 20, 20]
    assert all(o.err.max() == 0 for o in outs)
    assert max(sizes) <= 32  # whole sub-batches, never past the cap
    assert len(sizes) >= 2  # really split
    await b.drain()
    runner.close()


def test_metric_flags_collectors():
    """GUBER_METRIC_FLAGS opts into process/runtime collector families
    (reference flags.go:19-57 FlagOSMetrics/FlagGolangMetrics wired at
    daemon.go:293-306) — the flag must actually grow /metrics, not just
    parse."""
    from gubernator_tpu.service.metrics import DaemonMetrics

    base = DaemonMetrics().render().decode()
    assert "process_open_fds" not in base
    assert "python_gc_objects_collected" not in base

    both = DaemonMetrics(metric_flags="os,python").render().decode()
    assert "gubernator_process_open_fds" in both
    assert "gubernator_process_resident_memory_bytes" in both
    assert "python_gc_objects_collected_total" in both
    assert "python_info" in both

    # "golang" is accepted as an alias for the runtime collectors, and
    # unknown flags are ignored (logged), matching getEnvMetricFlags
    alias = DaemonMetrics(metric_flags="golang,bogus").render().decode()
    assert "python_gc_objects_collected_total" in alias
    assert "gubernator_process_open_fds" not in alias


@async_test
async def test_warm_shapes_pow2():
    """GUBER_WARM_SHAPES=pow2 pre-compiles every pow2 coalesce geometry at
    spawn so no production batch shape compiles on the request path; warm-up
    traffic must not leak into stats, and real requests still serve."""
    from gubernator_tpu.service.daemon import Daemon

    conf = daemon_config()
    conf.behaviors.warm_shapes = "pow2"
    conf.behaviors.coalesce_limit = 64  # 16..64 → 3 shapes, keeps CI fast
    d = await Daemon.spawn(conf)
    client = V1Client(d.conf.grpc_address)
    try:
        assert d.engine.stats.checks == 0  # warm-up is not traffic
        rs = await client.get_rate_limits(
            [req(f"w{i}") for i in range(40)]  # coalesces into a pow2 shape
        )
        assert len(rs.responses) == 40
        assert all(r.error == "" for r in rs.responses)
        # the pipelined door applies the stats delta fire-and-forget on the
        # engine thread AFTER replying — flush it before asserting
        await asyncio.get_running_loop().run_in_executor(
            d.runner._exec, lambda: None
        )
        assert d.engine.stats.checks == 40
    finally:
        await client.close()
        await d.close()


# ------------------------------------------------------ warm_up zero compiles
# one fresh XLA compile fires exactly one of these events; cached
# executions fire none
COMPILE_EVENT = "/jax/compilation_cache/compile_requests_use_cache"


def _warm_shapes_again(d):
    """Re-drive the exact dispatch surface warm_up traced, with DIFFERENT
    values (shape-cache, not value-cache): the decide variants and the
    1-row install."""
    import numpy as np

    from gubernator_tpu.ops.batch import RequestColumns

    async def go():
        for algos in ([0], [2], [2, 3], [1]):
            n = len(algos)
            await d.runner.check_columns(RequestColumns(
                fp=np.arange(7, 7 + n, dtype=np.int64),
                algo=np.asarray(algos, dtype=np.int32),
                behavior=np.zeros(n, dtype=np.int32),
                hits=np.ones(n, dtype=np.int64),
                limit=np.full(n, 5, dtype=np.int64),
                burst=np.zeros(n, dtype=np.int64),
                duration=np.full(n, 1000, dtype=np.int64),
                created_at=np.zeros(n, dtype=np.int64),
                err=np.zeros(n, dtype=np.int8),
            ))
        await d.runner.install_columns(
            fp=np.asarray([9], dtype=np.int64),
            algo=np.zeros(1, dtype=np.int32),
            status=np.zeros(1, dtype=np.int32),
            limit=np.full(1, 3, dtype=np.int64),
            remaining=np.ones(1, dtype=np.int64),
            reset_time=np.full(1, 2, dtype=np.int64),
            duration=np.full(1, 2, dtype=np.int64),
            now_ms=2,
        )

    return go()


@pytest.mark.parametrize("kind", ["local", "tiered", "durable"])
def test_warm_up_leaves_zero_compiles(kind, tmp_path):
    """After Daemon.spawn (which runs warm_up), re-dispatching every warmed
    shape triggers ZERO fresh XLA compiles (the always-on contract: no
    production dispatch of a warmed shape ever traces on the request
    path). With the tiering plane armed the warmed programs are the tiered
    ones, and with the checkpoint plane armed every dispatch marks its
    blocks: the guard under the benchmark's `window_compiles` in cells 7
    and 5."""
    import jax.monitoring as jm

    from gubernator_tpu.service.daemon import Daemon

    compiles = []
    armed = [False]

    def listener(event, **kw):
        if armed[0] and event == COMPILE_EVENT:
            compiles.append(event)

    conf = daemon_config(http_address="", cache_size=1 << 14)
    if kind == "tiered":
        conf.tier_enabled, conf.tier_shadow_bytes = True, 1 << 20
    if kind == "durable":
        conf.checkpoint_path = str(tmp_path / "base.npz")
        conf.checkpoint_interval_ms = 60_000.0  # no epoch of the loop's own

    async def go():
        import jax
        import jax.numpy as jnp

        d = await Daemon.spawn(conf)
        assert (d.engine.shadow is not None) == (kind == "tiered")
        assert (d.engine.ckpt is not None) == (kind == "durable")
        jm.register_event_listener(listener)
        armed[0] = True
        try:
            await _warm_shapes_again(d)
            if kind == "durable":  # and the epoch that takes their blocks
                assert (await d.checkpointer.checkpoint_once())["rows"] > 0
            warm_compiles = list(compiles)
            # positive control: a fresh jitted function MUST fire the
            # compile event — proves the listener actually observes
            # compiles, so the empty assertion above means something
            jax.jit(lambda x: x * 3 + 1)(jnp.arange(8)).block_until_ready()
            canary_fired = len(compiles) > len(warm_compiles)
        finally:
            armed[0] = False
        await d.close()
        return warm_compiles, canary_fired

    try:
        warm_compiles, canary_fired = asyncio.run(go())
    finally:
        armed[0] = False
    assert canary_fired, "compile-event canary did not fire"
    assert warm_compiles == [], (
        f"warm_up left {len(warm_compiles)} shapes compiling on the "
        "request path"
    )


@async_test
async def test_the_engine_block_reports_its_two_constants():
    """/v1/debug/pipeline still reports the two engine keys the benchmark's
    configurations and chip_smoke.py compare, as constants."""
    from gubernator_tpu.service.daemon import Daemon

    d = await Daemon.spawn(daemon_config(http_address=""))
    try:
        dbg = d.debug_pipeline()
    finally:
        await d.close()
    assert dbg["engine"]["probe_kernel"] == "xla"
    assert dbg["engine"]["a2a_impl"] is None  # "collective" on a mesh engine
