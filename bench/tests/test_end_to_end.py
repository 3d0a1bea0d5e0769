"""The harness end to end on the CPU at a 4,096-slot table: every cell that
BENCHMARK.json lists, traced and untraced (a cell that asks for four chips
runs on four virtual devices), and the control: the timed path broken
underneath must come out as not correct.

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
    for workload in CELLS:
        run(workload, 3_000_000_011, False)


@pytest.mark.parametrize("trace", [False, True], ids=["untraced", "traced"])
@pytest.mark.parametrize("workload", CELLS)
def test_cell_runs_and_is_correct(workload, trace, warmed):
    out = run(workload, 3_000_000_011, trace)
    res, spec = out["result"], harness.load_cell(workload)
    cache = out["context"]["cache_entries"]
    # one run, no retry: a compile inside the window is a failure to count
    assert cache["at_window_end"] == cache["before"], "a program compiled inside the window"
    assert res["correct"], out["context"]["compared"]
    assert set(res) == {"correct", "attempted", "failed", "metrics", "device"} | (
        {"breakdown"} if trace else set())
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["device"]["count"] == spec["cell"]["chips"]
    if trace:
        assert res["device"]["busy_s"] > 0 and res["device"]["window_s"] > 0
        assert set(res["metrics"]) <= {m["name"] for m in spec["per_layer"]}
        assert any(n.startswith("window_compiles") for n in res["metrics"])
    else:
        assert set(res["metrics"]) == {m["name"] for m in spec["end_to_end"]}
        assert all(v["value"] > 0 for v in res["metrics"].values())


class AlteringDoor(Door):
    """One answer of the read-back is altered where it is produced: in the
    first hits=0 RPC, the `remaining` of one key that was never evicted (its
    reset_time is the one most of its fill RPC share) goes down by one."""

    altered = 0

    async def check_raw(self, body: bytes) -> bytes:
        data = await super().check_raw(body)
        if b"\x12\x10" in body[:12] and b"\x18" not in body[24:28] and not self.altered:
            rows = wirefmt.decode_response_slow(data)
            resets = [r[3] for r in rows]
            usual = max(set(resets), key=resets.count)
            for r in rows:
                if r[3] == usual and r[2] > 0:
                    r[2] -= 1
                    type(self).altered += 1
                    return wirefmt.response_bytes([r[:4] for r in rows])
        return data


def test_one_sampled_counter_altered_by_one_is_not_correct():
    AlteringDoor.altered = 0
    out = run("token10m.bulk1000-closed64", 3_000_000_021, False, door_cls=AlteringDoor)
    assert AlteringDoor.altered == 1
    cmp = {c["name"]: c["value"] for c in out["context"]["compared"]}
    assert not out["result"]["correct"]
    assert cmp["counters_below_expected"] == 1


@pytest.mark.parametrize("workload", CELLS)
def test_the_control_is_not_correct(workload, monkeypatch):
    monkeypatch.setattr(control, "EVERY", 20)
    rows = asyncio.run(control.control(
        workload, 3_000_000_031, 3.0, platform="cpu", spec=small.small_spec(workload)))
    sound, replay, lost = rows
    assert sound["correct"], sound
    assert not replay["correct"] and replay["compared"]["counters_below_expected"][0] > 0
    assert not lost["correct"]
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
