#!/usr/bin/env python3
"""The deployment `token10m-durable` against the plain durable reference, at
size, on the chip: what a run of the benchmark cannot show, because the
harness cannot kill and restart a server (PERF.md section 7, `recover_s`).

    chiprun --chips 1 --timeout 1800 -- python3 ci/chip_durable.py [--seed N]

One server child, the cell's own (`bench/configs/token10m-durable.json`
through `bench/harness.Session`: start, `expect_engine`, the fill of 10M keys,
the scripted scenarios), then the cell's traffic. `--kill-at` seconds into
the window the server is killed with SIGKILL. The delta log it leaves is
read (headers only): the last complete frame's stamp is when the state a
restart will find was taken. A second server starts from `bench/.out/ckpt`
— the seconds from its start to healthy are the restore of the base (none
here: the first life never compacted or stopped) and the replay of every
frame — and the harness's read-back asks it for 200,000 seeded keys.

Two `tests/oracle/durable.py` references are fed the generator's ledger for
those keys: the fill, every RPC answered before the last frame's stamp, a
`checkpoint()`, then every other RPC that was sent before the kill (answered
or not: the server may have applied it). One `crash()`es. A key must not hold
less than the one that never crashed says (a hit counted twice) nor, if it
was never evicted, more than the crashed one says (a hit the last completed
epoch should have held); the distance between the two is the hits the key
was sent after that epoch. Keys evicted live (a later reset_time) may hold
more and are counted beside the harness's allowance.

Then the second life is stopped with SIGTERM: the wall to its exit, what it
leaves under bench/.out. One JSON line on stdout and
chiprun_out/chip_durable.json; exit code 0 when no key is outside its bounds
and the restart replayed the log.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import signal
import struct
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "bench"))
sys.path.insert(1, ROOT)

import numpy as np  # noqa: E402

import checker  # noqa: E402
import harness  # noqa: E402
import loadgen  # noqa: E402
from doors import Door, Server  # noqa: E402
from tests.oracle.durable import DurableOracle  # noqa: E402

CELL = "token10m-durable.bulk1000-closed64"
# store.py's framing, restated: the reference reads the log with no code of
# the program (magic u32, version u32, rows u32, epoch i64, now_ms i64, crc u32)
LOG_MAGIC, FRAME_MAGIC = b"GUBTPUDL", 0x46445547
FRAME_HEADER = struct.Struct("<IIIqqI")
ROW_BYTES = {1: 64}  # version 1: the full layout's 16 int32 fields


def complete_frames(path: str) -> list:
    """(epoch, now_ms, rows) of every frame that lies whole in the file."""
    out, size = [], os.path.getsize(path)
    with open(path, "rb") as f:
        if f.read(len(LOG_MAGIC)) != LOG_MAGIC:
            return out
        while True:
            hdr = f.read(FRAME_HEADER.size)
            if len(hdr) < FRAME_HEADER.size:
                return out
            magic, version, rows, epoch, now_ms, _crc = FRAME_HEADER.unpack(hdr)
            end = f.tell() + rows * ROW_BYTES.get(version, 1 << 62)
            if magic != FRAME_MAGIC or end > size:
                return out
            out.append((epoch, now_ms, rows))
            f.seek(end)


def tree_bytes(path: str) -> dict:
    return {
        os.path.relpath(os.path.join(d, f), path): os.path.getsize(os.path.join(d, f))
        for d, _, files in os.walk(path) for f in files
    }


async def main_async(args) -> dict:
    platform = "cpu" if args.rehearse else "tpu"
    if args.rehearse:
        sys.path.insert(0, os.path.join(ROOT, "bench", "tests"))
        import small

        spec = small.small_spec(CELL)
    else:
        spec = harness.load_cell(CELL)
    cfg, keyspec = spec["config"], spec["config"]["keyspace"]
    n_keys, limit = int(keyspec["keys"]), int(keyspec["limit"])
    hits, dur = int(keyspec["hits"]), int(keyspec["duration_ms"])
    base = os.path.join(ROOT, cfg["server_env"]["GUBER_CHECKPOINT_PATH"])
    out: dict = {"cell": CELL, "seed": args.seed, "kill_at_s": args.kill_at}

    ses = harness.Session(spec, args.seed, platform)
    traffic = loadgen.Traffic(spec["traffic"], keyspec, args.seed, args.kill_at + 0.5)
    await ses.open(while_starting=traffic.prepare)
    out["first_life"] = {"healthy_s": ses.startup_s, "fill_s": ses.fill_out["wall_s"],
                         "fill_mismatches": ses.fill_out["mismatches"],
                         "scenario_mismatches": ses.scen["mismatches"]}
    evicted_live = int((await ses.door.get("/v1/debug/table"))["evicted_live_total"])
    wall_minus_mono = time.time() - time.monotonic()
    killed = {}

    async def kill() -> None:
        await asyncio.sleep(traffic.warm_s + args.kill_at)
        killed["pipe"] = await ses.door.get("/v1/debug/pipeline")
        killed["mono"] = time.monotonic()
        os.kill(ses.server.proc.pid, signal.SIGKILL)

    killer = asyncio.ensure_future(kill())
    led = await traffic.run(ses.door)
    await killer
    ses.server.proc.wait()
    await ses.door.close()
    checker.settle(led)
    t_kill_ms = (killed["mono"] + wall_minus_mono) * 1e3
    t0_ms = (led.t0_monotonic + wall_minus_mono) * 1e3
    ck = killed["pipe"]["checkpoint"]
    out["first_life"].update(
        epochs=ck["epochs"], bases=ck["bases"], rows=ck["rows"], log_bytes=ck["bytes"],
        period_ms=ck["period_ms"], epoch_age_ms_max=ck["epoch_age_ms_max"],
        checks=killed["pipe"]["engine"]["checks"], evicted_live_total=evicted_live,
    )

    frames = complete_frames(base + ".delta")
    if not frames:
        raise SystemExit("the first life left no complete frame")
    last_epoch, t_epoch_ms, _ = frames[-1]
    out["log"] = {"frames": len(frames), "rows": sum(f[2] for f in frames),
                  "bytes": os.path.getsize(base + ".delta"), "last_epoch": last_epoch,
                  "age_at_kill_ms": t_kill_ms - t_epoch_ms,
                  "base_exists": os.path.exists(base)}

    # ---- the second life: restore, read back
    server = Server(platform, cfg["server_env"], os.path.join(harness.OUT_DIR, "server2.log"),
                    extra_env=spec.get("extra_env"))
    healthy_s = await server.wait_healthy()
    door = Door(server.grpc, server.http, channels=4)
    dura = await door.get("/v1/debug/durability")
    out["restart"] = {"healthy_s": healthy_s, **{k: dura[k] for k in (
        "restored", "base_epoch", "last_epoch", "replayed_frames", "replayed_rows",
        "replay_unmerged")}}
    idx = checker.draw_sample(args.seed, n_keys, np.ones(n_keys, dtype=bool), cfg["check"])
    t_peek = checker.now_ms()
    ans = await checker.read_back(door, args.seed, idx, keyspec, t_peek)
    table = await door.get("/v1/debug/table")

    # ---- the references, fed the ledger for the sampled keys
    never, crashed = DurableOracle(), DurableOracle()
    sampled = np.zeros(n_keys, dtype=bool)
    sampled[idx] = True
    created = ses.fill_out["created"]
    for k in idx.tolist():
        t = int(created[k // checker.FILL_RPC_ITEMS])
        never.check(k, t, hits, limit, dur), crashed.check(k, t, hits, limit, dur)
    order = np.argsort(np.nan_to_num(np.asarray(led.done), nan=np.inf), kind="stable")
    durable, at_risk, not_sent = [], [], 0
    for i in order.tolist():
        sent_ms = t0_ms + led.sent[i] * 1e3
        if sent_ms >= t_kill_ms:
            not_sent += 1  # it met a dead server
            continue
        answered = led.resp[i] is not None
        before = answered and t0_ms + led.done[i] * 1e3 < t_epoch_ms
        (durable if before else at_risk).append(led.idx[i][sampled[led.idx[i]]])
    for part, mark in ((durable, True), (at_risk, False)):
        for keys in part:
            for k in keys.tolist():
                never.check(k, t_peek, hits, limit, dur), crashed.check(k, t_peek, hits, limit, dur)
        if mark:
            never.checkpoint(), crashed.checkpoint()
    since = dict(crashed.since)
    crashed.crash()
    lo = np.fromiter((never.check(k, t_peek, 0, limit, dur)[1] for k in idx.tolist()),
                     dtype=np.int64, count=len(idx))
    hi = np.fromiter((crashed.check(k, t_peek, 0, limit, dur)[1] for k in idx.tolist()),
                     dtype=np.int64, count=len(idx))
    born = created[idx // checker.FILL_RPC_ITEMS] + dur
    kept = ans.reset_time == born
    below = ans.remaining < lo
    above = kept & (ans.remaining > hi)
    n = len(idx)
    out["read_back"] = {
        "sample": n, "below_uncrashed": int(below.sum()), "above_crashed": int(above.sum()),
        "evicted_in_sample": int((~kept).sum()),
        "evicted_allowance": checker.eviction_bound(keyspec, n, evicted_live, n_keys),
        "keys_at_risk": len(since), "hits_at_risk": int(sum(since.values())),
        "hits_granted_again": int((ans.remaining - lo)[kept].sum()),
        "rpcs": {"durable": len(durable), "at_risk": len(at_risk), "met_a_dead_server": not_sent},
        "examples": [
            f"key {int(idx[j])}: holds {int(ans.remaining[j])}, bounds {int(lo[j])}..{int(hi[j])}"
            for j in np.flatnonzero(below | above)[:5]
        ],
        "live_keys_after_restart": table.get("live_keys"),
    }

    # ---- a graceful stop of the restored server
    await door.close()
    t = time.monotonic()
    rc = server.stop()
    left = tree_bytes(os.path.dirname(base))
    out["stop"] = {"rc": rc, "sigterm_to_exit_s": time.monotonic() - t, "left": left,
                   "left_bytes": sum(left.values())}
    rb = out["read_back"]
    out["ok"] = bool(
        rb["below_uncrashed"] == 0 and rb["above_crashed"] == 0
        and rb["evicted_in_sample"] <= rb["evicted_allowance"]
        and out["restart"]["replayed_frames"] > 0 and rc == 0
    )
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=2971215073)
    ap.add_argument("--kill-at", type=float, default=10.0,
                    help="seconds into the window at which the server is killed")
    ap.add_argument("--rehearse", action="store_true",
                    help="on the CPU, the cell cut by bench/tests/small.py: finds faults, times nothing")
    args = ap.parse_args(argv)
    out = asyncio.run(main_async(args))
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "chip_durable.json"), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out), flush=True)
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
