"""The harness end to end on the CPU at a 4,096-slot table: every cell that
BENCHMARK.json lists, traced and untraced (a cell that asks for four chips
runs on four virtual devices), the scratch cells of small.py (a leaky
keyspace), and the control: the timed path broken underneath must come out
as not correct.

These start real server children and take a few minutes; the first run of a
checkout also compiles. `python -m pytest bench/tests/test_end_to_end.py -q`.
"""

import asyncio
import json
import os
import subprocess
import sys

import pytest

import control
import harness
import small
import wirefmt
from doors import BENCH_DIR, ROOT, Door

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    CELLS = [w["name"] for w in json.load(_f)["workloads"]]
ALL_CELLS = CELLS + list(small.SCRATCH)


def is_leaky(workload) -> bool:
    return small.full_spec(workload)["config"]["keyspace"].get("algorithm") == "leaky"


def is_global(workload) -> bool:
    return "GLOBAL" in small.full_spec(workload)["config"]["keyspace"].get("behavior", ())


class CountingDoor(Door):
    """Counts at the door what a run asks of the server: every GET by its
    path, in order, and every check RPC by what its first row is (a fill
    row pins `created_at` and carries the hit, a read-back row carries none,
    a script's row has a name of its own, the drain's sentinel is `drain`)."""

    seen = None  # the newest door, for the test to read once the run is over

    def __init__(self, *args, **kw):
        super().__init__(*args, **kw)
        self.gets, self.rpcs = [], {}
        type(self).seen = self

    async def get(self, path, as_json=True):
        self.gets.append(path)
        return await super().get(path, as_json)

    def start(self, body: bytes):
        n = body[3]
        name, tail = body[4 : 4 + n], body[4 + n :]
        tail = tail[2 + tail[1] :]  # past the key
        if name != b"bulk":
            kind = "sentinel" if name == b"drain" and len(body) < 64 else "script"
        elif tail[0] != 0x18:
            kind = "read_back"
        else:
            kind = "fill" if b"\x50" in tail[: body[1] - 24] else "traffic"
        self.rpcs[kind] = self.rpcs.get(kind, 0) + 1
        return super().start(body)


# what a run asked of the server over HTTP at e445c33, in order
GETS_BEFORE = {
    False: ["/v1/debug/pipeline"] * 3 + ["/v1/debug/table", "/v1/debug/pipeline",
                                          "/v1/HealthCheck"],
    True: ["/v1/debug/pipeline", "/metrics", "/v1/debug/pipeline", "/v1/debug/pipeline",
           "/metrics", "/v1/debug/table", "/v1/debug/pipeline", "/v1/HealthCheck"],
}


def run(workload, seed, trace, seconds=3.0, **kw):
    return asyncio.run(harness.run_cell(
        workload, seed, seconds, trace, platform="cpu",
        spec=small.small_spec(workload), **kw,
    ))


@pytest.fixture(scope="module")
def warmed():
    """Each cell run once, unjudged, so that the judged runs find their
    programs in the compile cache (bench/.jax_cache): a first run of a
    checkout stalls on compiles, and a stalled server cuts dispatches with
    many copies of one hot key, which the engine answers as one aggregate."""
    for workload in ALL_CELLS:
        for _ in range(3):  # until a run finds every program it uses in the cache
            cache = run(workload, 3_000_000_011, False)["context"]["cache_entries"]
            if cache["after"] == cache["before"]:
                break


@pytest.mark.parametrize("trace", [False, True], ids=["untraced", "traced"])
@pytest.mark.parametrize("workload", ALL_CELLS)
def test_cell_runs_and_is_correct(workload, trace, warmed):
    out = run(workload, 3_000_000_011, trace, door_cls=CountingDoor)
    res, spec = out["result"], small.full_spec(workload)
    assert out["context"]["algorithm"] == spec["config"]["keyspace"].get("algorithm", "token")
    door, compared = CountingDoor.seen, out["context"]["compared"]
    names = [c["name"] for c in compared]
    read_back = -(-next(c["of"] for c in compared if c["name"] == "counters_below_expected") // 1000)
    if is_global(workload):
        # drained after the fill, after each of the 16 scripted steps and
        # before the read-back, one sentinel a drain; four readings a key
        drains = len(out["context"]["drain_ms"])
        assert drains == 18 and door.rpcs["sentinel"] == drains
        assert door.gets.count("/v1/debug/global") >= 2 * drains + 2
        assert [g for g in door.gets if g != "/v1/debug/global"] == GETS_BEFORE[trace]
        assert door.rpcs["script"] == 16 and door.rpcs["read_back"] == 4 * read_back
        assert names[10:] == ["global_undrained", "global_over_admitted", "replica_disagreements"]
        assert out["context"]["scripts_left_out"] == ["dup", "leak", "reset"]
    else:
        # no request, no wait and no compared name that the parent's harness
        # did not have
        assert door.gets == GETS_BEFORE[trace]
        assert door.rpcs == {"fill": 2, "script": 32 if is_leaky(workload) else 26,
                             "traffic": door.rpcs["traffic"], "read_back": read_back}
        assert len(names) == 10 and out["context"]["scripts_left_out"] == []
    assert names[:10] == [
        "fill_mismatches", "scenario_mismatches", "window_answer_violations",
        "counters_below_expected", "counters_above_expected_not_evicted",
        "counters_status_wrong", "counters_fields_wrong", "counters_evicted_in_sample",
        "server_decisions_dropped", "server_unhealthy"]
    assert all(c["limit"] == 0 for c in compared if c["name"] != "counters_evicted_in_sample")
    cache = out["context"]["cache_entries"]
    # one run, no retry: a compile inside the window is a failure to count
    assert cache["at_window_end"] == cache["before"], "a program compiled inside the window"
    assert res["correct"], out["context"]["compared"]
    assert set(res) == {"correct", "attempted", "failed", "metrics", "device", "compared"} | (
        {"breakdown"} if trace else set())
    assert list(res)[-1] == "compared" and all(v <= lim for v, lim in res["compared"].values())
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["device"]["count"] == spec["cell"]["chips"]
    if trace:
        assert res["device"]["busy_s"] > 0 and res["device"]["window_s"] > 0
        assert set(res["metrics"]) <= {m["name"] for m in spec["per_layer"]}
        assert any(n.startswith("window_compiles") for n in res["metrics"])
    else:
        assert set(res["metrics"]) == {m["name"] for m in spec["end_to_end"]}
        assert all(v["value"] > 0 for v in res["metrics"].values())


@pytest.mark.parametrize("seed", [3_000_000_012, 3_000_000_013])
def test_the_global_scratch_cell_is_correct_on_two_more_seeds(seed, warmed):
    out = run("mesh4-global-scratch.bulk1000-closed64", seed, False)
    assert out["result"]["correct"], (out["context"]["compared"], out["context"]["examples"])
    assert max(out["context"]["drain_ms"]) < 5000.0


class FailingDoor(Door):
    """The first read-back RPC fails as gRPC reports it."""

    async def check_raw(self, body: bytes) -> bytes:
        import grpc

        if body[2:8] == b"\x0a\x04bulk" and body[26] != 0x18:
            raise grpc.aio.AioRpcError(
                grpc.StatusCode.UNAVAILABLE, grpc.aio.Metadata(), grpc.aio.Metadata(),
                details="the door closed")
        return await super().check_raw(body)


def test_an_rpc_error_outside_the_traffic_ends_the_run_as_a_bench_failure():
    from doors import BenchFailure

    with pytest.raises(BenchFailure, match="read-back failed: UNAVAILABLE: the door closed"):
        run(CELLS[0], 3_000_000_041, False, seconds=1.0, door_cls=FailingDoor)


class AlteringDoor(Door):
    """One answer of the read-back is altered where it is produced: in the
    first hits=0 RPC, the `remaining` of one key that was never evicted (its
    reset_time is the one most of its fill RPC share) goes down by one. In
    a leaky keyspace any key will do, and the answer's reset_time moves with
    it (`per_token`), as it does where the server counts a hit twice."""

    altered = 0
    per_token = 0

    async def check_raw(self, body: bytes) -> bytes:
        data = await super().check_raw(body)
        if b"\x12\x10" in body[:12] and b"\x18" not in body[24:28] and not self.altered:
            rows = wirefmt.decode_response_slow(data)
            resets = [r[3] for r in rows]
            usual = max(set(resets), key=resets.count)
            for r in rows:
                if (r[3] == usual or self.per_token) and r[2] > 0:
                    r[2] -= 1
                    r[3] += self.per_token
                    type(self).altered += 1
                    return wirefmt.response_bytes([r[:4] for r in rows])
        return data


@pytest.mark.parametrize("workload", ["token10m.bulk1000-closed64", *small.SCRATCH])
def test_one_sampled_counter_altered_by_one_is_not_correct(workload, monkeypatch):
    keyspec = small.small_spec(workload)["config"]["keyspace"]
    monkeypatch.setattr(AlteringDoor, "altered", 0)
    monkeypatch.setattr(AlteringDoor, "per_token",
                        keyspec["duration_ms"] // keyspec["limit"] if is_leaky(workload) else 0)
    out = run(workload, 3_000_000_021, False, door_cls=AlteringDoor)
    assert AlteringDoor.altered == 1
    cmp = {c["name"]: c["value"] for c in out["context"]["compared"]}
    assert not out["result"]["correct"]
    assert cmp["counters_below_expected"] == 1


@pytest.mark.parametrize("workload", ALL_CELLS)
def test_the_control_is_not_correct(workload, monkeypatch):
    monkeypatch.setattr(control, "EVERY", 20)
    # three stretches over one table: enough keys that most are still under
    # their limit at the end (a hit lost on a key past its limit cannot be seen)
    keys = 8000 if workload in small.SCRATCH else 2000
    rows = asyncio.run(control.control(
        workload, 3_000_000_031, 3.0, platform="cpu", spec=small.small_spec(workload, keys=keys)))
    sound, replay, lost = rows
    assert sound["correct"], sound
    assert not replay["correct"] and replay["compared"]["counters_below_expected"][0] > 0
    assert not lost["correct"]
    if is_leaky(workload):
        # no key of a leaky keyspace can be shown never evicted: a key above
        # counts as evicted, and the server counted no eviction
        value, limit = lost["compared"]["counters_evicted_in_sample"]
        assert value > 0 and limit == 0
    else:
        assert lost["compared"]["counters_above_expected_not_evicted"][0] > 0


def test_without_a_tpu_the_command_prints_no_result():
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH_DIR, "run.py"), "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300,
        env={k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"},
    )
    assert p.returncode != 0
    assert p.stdout.strip() == ""
