"""chip_smoke.py between chip runs: its load → oracle-compare body against a
CPU daemon, its refusal to pass without a chip, and where the compile cache
goes (the three things about starting the program that only break on the
machine nobody is looking at)."""

import asyncio
import functools
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import chip_smoke as cs
from gubernator_tpu.config import DaemonConfig
from gubernator_tpu.service.daemon import Daemon

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def async_test(fn):
    @functools.wraps(fn)
    def wrapper(*a, **k):
        asyncio.run(fn(*a, **k))

    return wrapper


def test_bulk_request_bytes_are_what_protobuf_serializes():
    ids = cs.bulk_key_ids(3, np.arange(998, 1003))
    assert len(set(ids.tolist())) == 5
    at = 1_790_000_000_123
    for hits, behavior in ((1, 0), (0, 0), (1, cs.GLOBAL)):
        want = cs.pb.GetRateLimitsReq(requests=[
            cs.pb.RateLimitReq(
                name="bulk", unique_key=f"{int(i):016x}", hits=hits,
                limit=cs.BULK_LIMIT, duration=cs.BULK_DURATION_MS,
                behavior=behavior, created_at=at,
            )
            for i in ids
        ])
        assert cs.bulk_request_bytes(ids, hits, at, behavior) == (
            want.SerializeToString()
        )
    want = cs.pb.GetRateLimitsResp(responses=[
        cs.pb.RateLimitResp(limit=cs.BULK_LIMIT, remaining=99, reset_time=1_790_003_600_123)
    ] * 3)
    assert cs.expected_bulk_response_bytes(3, 99, 1_790_003_600_123) == (
        want.SerializeToString()
    )


async def _spawn(**kw) -> Daemon:
    return await Daemon.spawn(DaemonConfig(
        grpc_address="127.0.0.1:0", http_address="127.0.0.1:0",
        telemetry_interval_ms=200.0, **kw,
    ))


@async_test
async def test_drive_against_a_cpu_daemon():
    """3,000 keys into a 4,096-slot table: the load and every scripted
    fresh-key check agree with the plain oracle, and bulk keys the table
    evicted are explained by the server's own counter."""
    d = await _spawn(cache_size=4096)
    try:
        rec = await cs.drive(
            d.conf.grpc_address, d.conf.http_address, seed=5, n_keys=3000,
            n_fresh=20, n_sample=1500, sharded=False,
        )
    finally:
        await d.close()
    assert rec["mismatches"] == 0
    assert rec["keys_loaded"] == 3000
    # the encoder's bytes are the canonical ones: the fast path is the path
    assert rec["load_byte_identical_rpcs"] == rec["load_rpcs"] == 3
    assert rec["fresh_checks_compared"] == 20 * 28
    assert rec["bulk_peeks_compared"] == 1500
    assert 0 < rec["bulk_peeks_evicted"] <= rec["evicted_live_total"]
    assert rec["native_parser"] in ("built", "reused")
    eng = rec["engine"]
    assert (eng["platform"], eng["device_count"]) == ("cpu", 8)
    assert eng["table_bytes"] == 4096 * 64


@async_test
async def test_drive_catches_a_wrong_answer():
    """A bulk key somebody already hit answers remaining == limit-2: the
    run fails and names the key."""
    d = await _spawn(cache_size=4096)
    door = cs.Door(d.conf.grpc_address, d.conf.http_address)
    try:
        await door.check_raw(
            cs.bulk_request_bytes(cs.bulk_key_ids(5, np.array([7])), 1, cs.now_ms())
        )
        with pytest.raises(cs.SmokeFailure, match=r"1 of 1000 .*bulk load.*key 7: .*remaining=98"):
            await cs.drive(
                d.conf.grpc_address, d.conf.http_address, seed=5, n_keys=1000,
                n_fresh=5, n_sample=100, sharded=False,
            )
    finally:
        await door.close()
        await d.close()


@async_test
async def test_drive_refuses_the_python_door(monkeypatch):
    from gubernator_tpu import native

    d = await _spawn(cache_size=4096)
    monkeypatch.setattr(native, "state", None)
    try:
        with pytest.raises(cs.SmokeFailure, match="native request parser is not live"):
            await cs.drive(
                d.conf.grpc_address, d.conf.http_address, seed=5, n_keys=1000,
                n_fresh=5, n_sample=100, sharded=False,
            )
    finally:
        await d.close()


@async_test
async def test_drive_on_the_mesh_engine(monkeypatch):
    """The four-chip leg's assertions on the 8-device CPU mesh, with the
    selectors a TPU resolves `auto` to (device routing, in-trace dedup,
    compact wire): GLOBAL sync runs, nothing overflows the exchange, every
    shard holds its share, every sampled GLOBAL key's owner holds exactly
    its one hit, and the replicas answer within their bound."""
    monkeypatch.setenv("GUBER_WIRE_COMPACT", "1")
    monkeypatch.setattr(cs, "GLOBAL_EVERY", 2)
    d = await _spawn(
        cache_size=8 * 8192, engine="sharded", shard_route="device",
        shard_dedup="device",
    )
    try:
        rec = await cs.drive(
            d.conf.grpc_address, d.conf.http_address, seed=9, n_keys=8000,
            n_fresh=20, n_sample=4000, sharded=True,
        )
    finally:
        await d.close()
    eng = rec["engine"]
    assert (eng["n_shards"], eng["route"], eng["dedup"], eng["wire"]) == (
        8, "device", "device", "compact"
    )
    assert eng["a2a_impl"] == "collective" and eng["a2a_overflow"] == 0
    assert rec["global_sync"]["sync_rounds"] > 0
    # half the RPCs were GLOBAL: 4,000 load answers from replicas, and each
    # sampled GLOBAL key peeked at its owner and again at a replica
    n_global = rec["global_peeks_compared"]
    assert 1900 < n_global < 2100
    assert rec["bulk_peeks_compared"] == 4000 + n_global
    assert rec["replica_answers"] == 4000 + n_global
    assert rec["replica_answers_ahead"] <= cs.REPLICA_AHEAD_MAX * rec["replica_answers"]
    assert len(rec["per_shard_live"]) == 8


def test_main_fails_without_a_chip():
    """Run as a script where JAX has no TPU: non-zero, says why, no result."""
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py"), "--keys", "1000"],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=ROOT,
        capture_output=True, text=True, timeout=300,
    )
    assert p.returncode != 0
    assert "JAX found no tpu device" in p.stderr
    assert p.stdout.strip() == ""


def test_main_ends_with_the_verdict_and_only_the_verdict(monkeypatch, capsys):
    """The last stdout line holds exactly `ok` and `device` (platform, kind,
    count); everything else the run learned is on the line before it."""
    import jax._src.xla_bridge as xb

    dev = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
           "jax": "0.9.0", "jaxlib": "0.9.0"}
    monkeypatch.setattr(cs, "probe_device", lambda platform: dict(dev))

    async def passes(dev, seed, n_keys, slots):
        return {"keys_loaded": n_keys, "mismatches": 0}

    async def fails(dev, seed, n_keys, slots):
        raise cs.SmokeFailure("3 of 10 answers differ")

    verdict = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    monkeypatch.setattr(cs, "smoke", passes)
    monkeypatch.setattr(xb, "_backends", {})  # pytest's own process holds cpu
    assert cs.main(["--keys", "7"]) == 0
    report, last = map(json.loads, capsys.readouterr().out.splitlines())
    assert last == {"ok": True, "device": verdict}
    assert report == {"keys_loaded": 7, "mismatches": 0}

    monkeypatch.setattr(cs, "smoke", fails)
    assert cs.main([]) == 1
    cap = capsys.readouterr()
    assert [json.loads(x) for x in cap.out.splitlines()] == [
        {"ok": False, "device": verdict}
    ]
    assert "3 of 10 answers differ" in cap.err

    # a parent that touched a backend is itself a failure
    monkeypatch.setattr(cs, "smoke", passes)
    monkeypatch.setattr(xb, "_backends", {"cpu": object()})
    assert cs.main([]) == 1
    assert json.loads(capsys.readouterr().out.splitlines()[-1])["ok"] is False


def _cache_dir(env: dict, cwd: str) -> str:
    code = (
        "import gubernator_tpu, jax, json; "
        "print(json.dumps(jax.config.jax_compilation_cache_dir))"
    )
    base = {k: v for k, v in os.environ.items()
            if k not in ("JAX_COMPILATION_CACHE_DIR", "HOME", "XDG_CACHE_HOME")}
    p = subprocess.run(
        [sys.executable, "-c", code], cwd=cwd, text=True, capture_output=True,
        env={**base, "PYTHONPATH": ROOT, "JAX_PLATFORMS": "cpu", **env},
        timeout=120,
    )
    assert p.returncode == 0, p.stderr
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_compile_cache_placement(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: the program sets nothing. Unset: one
    fixed directory inside the checkout, whatever the cwd and $HOME."""
    assert _cache_dir({"JAX_COMPILATION_CACHE_DIR": "/x", "HOME": "/root"}, ROOT) == "/x"
    home_a, home_b = tmp_path / "a", tmp_path / "b"
    home_a.mkdir(), home_b.mkdir()
    got = {
        _cache_dir({"HOME": str(home_a)}, ROOT),
        _cache_dir({"HOME": str(home_b)}, str(tmp_path)),
    }
    assert got == {os.path.join(ROOT, ".jax_cache")}
