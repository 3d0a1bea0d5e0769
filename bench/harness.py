"""One run of one cell: start the server, fill the table, check the scripted
scenarios, offer the cell's traffic through warm-up and window, read the
counters back, and reduce what was seen to the cell's metrics.

Everything that belongs to one configuration, traffic mix or per-layer
metric is found by its name in BENCHMARK.json:
  bench/configs/<config>.json    the deployment (server environment, sizes)
  bench/traffic/<traffic>.json   the mix (bench/loadgen.py reads it)
  bench/layers/<quantity>.json   {"reader": name, ...parameters}; the quantity
                                 is the metric's name up to its first "."
                                 (rows_per_dispatch.open reads what
                                 rows_per_dispatch reads, in another cell)
  bench/readers/<reader>.py      read(ctx, **parameters) -> number or None
so a later PR adds a cell or a metric with new files and new entries only.
"""

from __future__ import annotations

import asyncio
import importlib.util
import json
import os
import re
import shutil
import subprocess
import sys
import time

import numpy as np

import checker
import loadgen
import xplane
from doors import BENCH_DIR, ROOT, BenchFailure, Door, Server, cache_entries

OUT_DIR = os.path.join(BENCH_DIR, ".out")  # server log and traces of the last run
RUN_BUDGET_S = 1150.0  # a first run may take 1200 s, compilation included
_STAGE_LINE = re.compile(
    r'^gubernator_tpu_stage_duration_(sum|count)\{stage="([^"]+)"\} (\S+)$', re.M
)


def load_json(*parts: str) -> dict:
    with open(os.path.join(BENCH_DIR, *parts)) as f:
        return json.load(f)


def load_cell(workload: str) -> dict:
    """The cell, its configuration, its traffic and its metrics, by name."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bm = json.load(f)
    cells = {w["name"]: w for w in bm["workloads"]}
    if workload not in cells:
        raise BenchFailure(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]

    def mine(metrics):
        return [m for m in metrics if workload in m.get("workloads", [workload])]

    e2e = mine(bm["end_to_end"])
    names = {m["name"] for m in e2e}
    return {
        "cell": cell,
        "config": load_json("configs", cell["config"] + ".json"),
        "traffic": load_json("traffic", cell["traffic"] + ".json"),
        "end_to_end": e2e,
        "per_layer": [m for m in mine(bm["per_layer"]) if m["moves"] in names],
    }


def read_layer(name: str, ctx: dict):
    """One per-layer metric through its reader; None when there was nothing
    to read."""
    spec = dict(load_json("layers", name.split(".")[0] + ".json"))
    reader = spec.pop("reader")
    spec.pop("about", None)
    path = os.path.join(BENCH_DIR, "readers", reader + ".py")
    mod_spec = importlib.util.spec_from_file_location(f"bench_reader_{reader}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read(ctx, **spec)


def parse_stages(text: str) -> dict:
    """{stage: (sum seconds, count)} from a /metrics exposition."""
    out: dict = {}
    for kind, stage, val in _STAGE_LINE.findall(text):
        s, c = out.get(stage, (0.0, 0.0))
        out[stage] = (float(val), c) if kind == "sum" else (s, float(val))
    return out


def generator_report(led: loadgen.Ledger) -> dict:
    """What the generator says of its own window: counts, rates, lateness."""
    due, sent, done, ok, items = led.arrays()
    w0, w1 = led.warm_s, led.t1
    in_win = (due >= w0) & (due < w1)
    was_sent = ~np.isnan(sent)
    late = (sent - due)[in_win & was_sent]
    answered_in = ok & (done >= w0) & (done < w1)
    # latency from the due time, over every RPC due in the window; one that
    # failed or was refused is given the window's length
    lat = np.where(ok, done - due, led.seconds)[in_win]
    return {
        "rpc_p50_ms": float(np.percentile(lat, 50) * 1e3) if lat.size else None,
        "rpc_p90_ms": float(np.percentile(lat, 90) * 1e3) if lat.size else None,
        "rpc_p99_ms": float(np.percentile(lat, 99) * 1e3) if lat.size else None,
        "rpcs_beyond_p99": int(lat.size // 100),
        "rpcs_due_in_window": int(in_win.sum()),
        "rpcs_answered_in_window": int(answered_in.sum()),
        "items_answered_in_window": int(items[answered_in].sum()),
        "rpcs_failed_in_window": int((in_win & ~ok).sum()),
        "rpcs_refused_by_generator": int((in_win & ~was_sent).sum()),
        "offered_rpc_per_s": float(in_win.sum() / led.seconds),
        "achieved_rpc_per_s": float(answered_in.sum() / led.seconds),
        "late_p50_ms": float(np.percentile(late, 50) * 1e3) if late.size else None,
        "late_p99_ms": float(np.percentile(late, 99) * 1e3) if late.size else None,
        "late_max_ms": float(late.max() * 1e3) if late.size else None,
        "unanswered_at_window_end": int(
            (was_sent & (sent < w1) & ~(done < w1)).sum()
        ),
        "cpu_cores": led.cpu_cores,
        "errors": led.errors,
    }


def end_to_end_values(led: loadgen.Ledger, gen: dict) -> dict:
    """Every end-to-end number the ledger can give; the cell reports the
    ones BENCHMARK.json lists for it."""
    return {
        "checks_per_s": gen["items_answered_in_window"] / led.seconds,
        "p50_ms": gen["rpc_p50_ms"],
    }


def _reduce_trace(trace_dir: str, platform: str) -> dict:
    """bench/xplane.py in a child of its own: it needs JAX's ProfileData,
    and the parent stays off JAX. The server has stopped by now."""
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH_DIR, "xplane.py"), trace_dir, "--reduce", platform],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=300,
    )
    if p.returncode != 0:
        raise BenchFailure("reducing the trace failed: " + p.stderr.strip()[-1500:])
    return json.loads(p.stdout.strip().splitlines()[-1])


class Session:
    """A started server with a filled table and the scenarios checked: what
    a run, the rate sweep and the control all begin with."""

    def __init__(self, spec: dict, seed: int, platform: str = "tpu", door_cls=Door):
        self.spec, self.seed, self.platform = spec, seed, platform
        self.cfg = spec["config"]
        self.keyspec = self.cfg["keyspace"]
        checker.refuse_keyspec(self.keyspec, RUN_BUDGET_S, spec.get("traffic"))
        # a row of this run can carry GLOBAL (then the cluster is drained
        # before anything eventually consistent is judged), and the keyspace
        # itself is GLOBAL (then scenarios and read-back are judged by
        # GLOBAL's contract: checker.py's docstring)
        self.drains_global = checker.carries_global(self.keyspec, spec.get("traffic"))
        self.global_keys = checker.is_global(self.keyspec)
        self.drains: list = []  # every drain of the run, as checker.drain returned it
        self.door_cls = door_cls
        self.server = self.door = None
        self.ledgers: list = []

    async def drain(self) -> None:
        self.drains.append(await checker.drain(self.door, self.seed))

    async def open(self, while_starting=None) -> None:
        chips = int(self.spec["cell"]["chips"])
        shutil.rmtree(OUT_DIR, ignore_errors=True)
        os.makedirs(OUT_DIR, exist_ok=True)
        self.cache0 = cache_entries()
        self.server = Server(
            self.platform, self.cfg["server_env"], os.path.join(OUT_DIR, "server.log"),
            extra_env=self.spec.get("extra_env"),
        )
        if while_starting is not None:
            while_starting()  # host work, while the server starts
        self.startup_s = await self.server.wait_healthy()
        self.dev = dev = await self.server.command(cmd="device")
        if dev["platform"] != self.platform or dev["count"] < chips:
            raise BenchFailure(
                f"the cell needs {chips} {self.platform} chip(s); JAX reports "
                f"{dev['count']} x {dev['platform']} ({dev['kind']})"
            )
        self.door = door = self.door_cls(
            self.server.grpc, self.server.http,
            channels=int(self.spec["traffic"].get("channels", 4)),
        )
        self.pipe = pipe = await door.get("/v1/debug/pipeline")
        self.eng = eng = pipe["engine"]
        for k, v in self.cfg.get("expect_batcher", {}).items():
            if pipe["batcher"].get(k) != v:
                raise BenchFailure(
                    f"the server's batcher reports {k}={pipe['batcher'].get(k)!r}, expected {v!r}")
        if self.platform == "tpu":
            want = dict(self.cfg.get("expect_engine", {}), platform="tpu",
                        device_count=dev["count"])
            for k, v in want.items():
                if eng.get(k) != v:
                    raise BenchFailure(f"the server reports {k}={eng.get(k)!r}, expected {v!r}")
            if pipe.get("native_parser") not in ("built", "reused"):
                raise BenchFailure(f"native_parser={pipe.get('native_parser')!r}")
        self.t_fill0_ms = checker.now_ms()
        self.fill_out = await checker.fill(door, self.seed, self.keyspec)
        if self.drains_global:
            await self.drain()
        self.t_filled_ms = checker.now_ms()
        t = time.monotonic()
        self.scen = await checker.run_scenarios(door, checker.fresh_scenarios(
            self.seed, checker.FRESH_KEYS_PER_SCRIPT, checker.now_ms(),
            dup_aggregates=eng.get("dedup") == "device", keyspec=self.keyspec,
        ), drain=self.drain if self.global_keys else None)
        self.t_scen = time.monotonic() - t

    async def offer(self, traffic: loadgen.Traffic, trace: bool) -> dict:
        """The traffic through warm-up and window, with what is read on either
        side of it. Returns the readers' context; the ledger joins
        `self.ledgers`."""
        door, ctx = self.door, {"config": self.cfg, "device": self.dev}
        t = time.monotonic()
        if trace:
            ctx["stages_before"] = parse_stages(await door.get("/metrics", as_json=False))
        ctx["pipeline_before"] = await door.get("/v1/debug/pipeline")
        if self.drains_global:
            ctx["global_before"] = (await door.get("/v1/debug/global"))["mesh"]
        ctx["t_scrape"] = time.monotonic() - t
        watch = asyncio.ensure_future(
            _watch_window(self.server, ctx, traffic.warm_s, traffic.seconds, trace)
        )
        try:
            led = await traffic.run(door)
        finally:
            await watch
        checker.settle(led)
        self.ledgers.append(led)
        ctx["ledger"] = led
        ctx["generator"] = generator_report(led)
        ctx["pipeline_after"] = await door.get("/v1/debug/pipeline")
        if self.drains_global:
            ctx["global_after"] = (await door.get("/v1/debug/global"))["mesh"]
        if trace:
            ctx["stages_after"] = parse_stages(await door.get("/metrics", as_json=False))
        return ctx

    async def check(self) -> dict:
        """Every number that decides `correct`, beside its limit; after every
        RPC has answered and tracing has stopped."""
        t0 = time.monotonic()
        door, keyspec, fill_out = self.door, self.keyspec, self.fill_out
        n_keys = int(keyspec["keys"])
        counts, known = checker.hit_counts(n_keys, self.ledgers)
        inv = checker.window_invariants(
            self.ledgers, counts, keyspec, self.t_fill0_ms, checker.now_ms()
        )
        if self.drains_global:
            await self.drain()
        idx = checker.draw_sample(self.seed, n_keys, known, self.cfg["check"])
        t_peek = checker.now_ms()
        if self.global_keys:
            ans = await checker.read_back(
                door, self.seed, idx, keyspec, t_peek, readings=int(self.cfg["peers"]))
            judged = checker.judge_global_counters(
                idx, ans, counts, fill_out["created"], keyspec, t_peek, self.t_filled_ms)
        else:
            ans = await checker.read_back(door, self.seed, idx, keyspec, t_peek)
            judged = checker.judge_counters(
                idx, ans, counts, fill_out["created"], keyspec, t_peek)
        table = await door.get("/v1/debug/table")
        eng_end = (await door.get("/v1/debug/pipeline"))["engine"]
        health = await door.get("/v1/HealthCheck")
        evicted_live = int(table["evicted_live_total"])
        bound = checker.eviction_bound(keyspec, judged["sample"], evicted_live, n_keys)
        C, scen, n = checker.Compared, self.scen, judged["sample"]
        compared = [
            C("fill_mismatches", fill_out["mismatches"], 0, fill_out["keys"]),
            C("scenario_mismatches", scen["mismatches"], 0, scen["compared"]),
            C("window_answer_violations", inv["violations"], 0, inv["answers"]),
            C("counters_below_expected", judged["below_expected"], 0, n),
            C("counters_above_expected_not_evicted", judged["above_expected_not_evicted"], 0, n),
            C("counters_status_wrong", judged["status_wrong"], 0, n),
            C("counters_fields_wrong", judged["fields_wrong"], 0, n),
            C("counters_evicted_in_sample", judged["evicted"], bound, n),
            C("server_decisions_dropped", int(eng_end.get("dropped") or 0), 0),
            C("server_unhealthy",
              int(health.get("status") != "healthy" or bool(eng_end.get("poisoned"))), 0),
        ]
        extra = {}
        if self.drains_global:
            over = checker.over_admission(self.ledgers, n_keys, keyspec, int(self.cfg["peers"]))
            compared += [
                C("global_undrained", max(d["undrained"] for d in self.drains), 0,
                  len(self.drains)),
                C("global_over_admitted", over["keys"], 0, n_keys),
            ]
            extra = {"drain_ms": [round(d["ms"], 1) for d in self.drains],
                     "global_excess_hits": over["excess_hits"]}
        if self.global_keys:
            compared.append(C("replica_disagreements", judged["replica_disagreements"], 0, n))
            extra.update(readings_per_key=judged["readings"],
                         evicted_at_every_peer=judged["evicted_at_every_peer"])
        return {
            "correct": all(c.ok for c in compared),
            "compared": [c.to_dict() for c in compared],
            **extra,
            "examples": (fill_out["examples"] + scen["examples"] + inv["examples"]
                         + judged["examples"])[:8],
            "live_keys": table.get("live_keys"),
            "per_shard_live": table.get("per_shard_live"),
            "evicted_live_total": evicted_live,
            "above_on_keys_past_limit": judged["above_on_keys_past_limit"],
            "a2a_overflow": eng_end.get("a2a_overflow"),
            "wall_s": time.monotonic() - t0,
        }

    async def close(self, failed: bool = False) -> None:
        if failed and self.server is not None:
            sys.stderr.write("---- server log tail ----\n" + self.server.log_tail() + "\n")
        if self.door is not None:
            await self.door.close()
        if self.server is not None:
            rc = self.server.stop()
            if rc != 0 and not failed:
                raise BenchFailure(f"the server exited with code {rc} on SIGTERM")


async def run_cell(
    workload: str, seed: int, seconds: float, trace: bool, *,
    platform: str = "tpu", spec: dict | None = None, t_process0: float | None = None,
    door_cls=Door,
) -> dict:
    """Drive one run. Returns {"result": the contract's last line, "context":
    the line before it}. `platform`, `spec` and `door_cls` are the tests' way
    in (bench/tests); the command line has no switch for them."""
    import grpc  # here, not at the top: doors.py keeps it out of a start that needs none

    t_process0 = t_process0 if t_process0 is not None else time.monotonic()
    spec = spec or load_cell(workload)
    ses = Session(spec, seed, platform, door_cls)
    traffic = loadgen.Traffic(spec["traffic"], ses.keyspec, seed, seconds)
    try:
        await ses.open(while_starting=traffic.prepare)
        ctx = await ses.offer(traffic, trace)
        dev_end = await ses.server.command(cmd="device")
        verdict = await ses.check()
    except grpc.aio.AioRpcError as exc:
        # the traffic's own RPCs may fail and are counted; one of the fill,
        # the scenarios, a drain or the read-back cannot, and ends the run
        await ses.close(failed=True)
        raise BenchFailure(
            "an RPC of the fill, the scenarios, a drain or the read-back failed: "
            f"{exc.code().name}: {exc.details()}") from exc
    except BaseException:
        await ses.close(failed=True)
        raise
    await ses.close()

    led, gen, dev, eng = ctx["ledger"], ctx["generator"], ses.dev, ses.eng
    metrics: dict = {}
    if trace:
        tw = ctx.pop("trace_window", None)
        if tw is None:
            raise BenchFailure("the traced sub-window never opened")
        ctx["trace"] = red = _reduce_trace(tw["dir"], platform)
        for m in spec["per_layer"]:
            v = read_layer(m["name"], ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        values = end_to_end_values(led, gen)
        # set-up: process start to the window's opening, less the scenario
        # check (a reference's time is not set-up)
        values["setup_s"] = (
            led.t0_monotonic + led.warm_s - t_process0 - ses.t_scen - ctx["t_scrape"]
        )
        for m in spec["end_to_end"]:
            metrics[m["name"]] = {"value": float(values[m["name"]]), "unit": m["unit"]}

    device = {
        "platform": dev["platform"], "kind": dev["kind"], "count": dev["count"],
        "memory_peak_bytes": max(dev_end["peak_bytes"]),
    }
    result = {
        "correct": verdict.pop("correct"),
        "attempted": gen["rpcs_due_in_window"],
        "failed": gen["rpcs_failed_in_window"],
        "metrics": metrics,
        "device": device,
    }
    b0, b1 = ctx["pipeline_before"]["batcher"], ctx["pipeline_after"]["batcher"]
    context = {
        "workload": workload, "seed": seed, "seconds": seconds, "trace": bool(trace),
        "engine": {k: eng.get(k) for k in (
            "kind", "platform", "device_kind", "device_count", "table_bytes", "wire",
            "write_mode", "probe_kernel", "n_shards", "route", "dedup", "a2a_impl")},
        "native_parser": ses.pipe.get("native_parser"),
        "jax": dev.get("jax"),
        "keys_loaded": ses.fill_out["keys"],
        "algorithm": ses.keyspec.get("algorithm", "token"),
        "behavior": ses.keyspec.get("behavior", []),
        "scripts_left_out": sorted(checker.scripts_left_out(ses.keyspec)),
        "fill_byte_identical_rpcs": ses.fill_out["byte_identical_rpcs"],
        **verdict,
        "close_reasons": {
            k: b1["close_reasons"].get(k, 0) - b0["close_reasons"].get(k, 0)
            for k in b1.get("close_reasons", {})
        },
        "dispatches": {k: b1.get(k, 0) - b0.get(k, 0) for k in (
            "fused_dispatches", "split_dispatches", "column_dispatches", "wire_fallbacks")},
        "generator": gen,
        "walls_s": {"server_healthy": ses.startup_s, "fill": ses.fill_out["wall_s"],
                    "scenarios": ses.t_scen, "scrape_before": ctx["t_scrape"],
                    "total": time.monotonic() - t_process0},
        "cache_entries": {"before": ses.cache0, "after": cache_entries(),
                          "at_window_start": ctx.get("cache_at_window_start"),
                          "at_window_end": ctx.get("cache_at_window_end")},
        "device_bytes_in_use": dev_end["bytes_in_use"],
    }
    if ses.drains_global:
        g0, g1 = ctx["global_before"], ctx["global_after"]
        context["global_in_traffic"] = {
            k: g1[k] - g0[k]
            for k in ("sync_rounds", "hits_queued", "broadcasts_applied", "updates_installed")}
        context["rpcs_by_behavior"] = {
            str(b): led.behavior.count(b) for b in sorted(set(led.behavior))}
    if trace:
        device["busy_s"], device["window_s"] = red["busy_s"], red["window_s"]
        result["breakdown"] = xplane.breakdown(red)
        context["trace"] = {
            "window_s": red["window_s"], "host_timed_s": tw["seconds"],
            "idle_share_worst_chip": red["idle_share_worst"],
            "busy_s_per_chip": {n: c["busy_s"] for n, c in red["chips"].items()},
            "modules": xplane.module_table(red),
        }
    # last in the line: every number that decided `correct`, beside its limit
    result["compared"] = {c["name"]: [c["value"], c["limit"]] for c in context["compared"]}
    return {"result": result, "context": context}


async def _watch_window(server, ctx, warm_s, seconds, trace) -> None:
    """Beside the traffic: count the compile cache's entries as the window
    opens and closes and, in a traced run, trace a sub-window in its middle
    (what the trace covers is read from the trace's own clock)."""
    t0 = time.monotonic()

    async def until(t):
        await asyncio.sleep(max(0.0, t0 + t - time.monotonic()))

    await until(warm_s)
    ctx["cache_at_window_start"] = cache_entries()
    if trace:
        # two seconds: on four chips they hold 65K op events a chip, and
        # stop_trace then takes half a minute to hand them over
        span = max(1.0, min(2.0, 0.1 * seconds))
        await until(warm_s + 0.35 * seconds)
        tdir = os.path.join(OUT_DIR, "trace")
        await server.command(cmd="trace_start", dir=tdir)
        ts = time.monotonic()
        await asyncio.sleep(span)
        te = time.monotonic()
        await server.command(cmd="trace_stop")
        ctx["trace_window"] = {"dir": tdir, "seconds": te - ts}
    await until(warm_s + seconds)
    ctx["cache_at_window_end"] = cache_entries()
