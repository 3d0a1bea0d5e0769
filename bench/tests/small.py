"""The cells at a size a CPU can hold: the same files, a 4,096-slot table per
device, a few thousand keys, a few seconds. For the tests and for rehearsing
a chip call; a number timed this way is never a device number."""

import copy
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import harness  # noqa: E402

CPU_MESH_ENV = {
    "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
    # what `auto` resolves to on a TPU (a CPU backend takes the host paths)
    "GUBER_SHARD_ROUTE": "device",
    "GUBER_SHARD_DEDUP": "device",
    "GUBER_WIRE_COMPACT": "1",
}


# Cells that BENCHMARK.json does not list: a listed cell's traffic and metrics
# over a configuration kept in bench/tests/data (PR 30 ran this one at size
# on the chip to prove `keyspace.algorithm`: PERF.md, section 6).
SCRATCH = {
    "leaky1m-scratch.bulk1000-closed64": ("token10m.bulk1000-closed64", "leaky1m-scratch.json"),
    # PR 46 ran this one at size on four chips to prove `keyspace.behavior`
    "mesh4-global-scratch.bulk1000-closed64": (
        "mesh4-sharded.bulk1000-closed64", "mesh4-global-scratch.json"),
}


def full_spec(workload: str) -> dict:
    """`harness.load_cell` for a listed cell; a scratch cell at its full size."""
    if workload not in SCRATCH:
        return harness.load_cell(workload)
    like, config_file = SCRATCH[workload]
    spec = copy.deepcopy(harness.load_cell(like))
    spec["config"] = harness.load_json("tests", "data", config_file)
    spec["cell"] = dict(spec["cell"], name=workload, config=spec["config"]["name"])
    return spec


def small_spec(workload: str, keys: int = 2000, rate: float = 100.0) -> dict:
    spec = copy.deepcopy(full_spec(workload))
    cfg = spec["config"]
    chips = int(spec["cell"]["chips"])
    cfg["server_env"]["GUBER_CACHE_SIZE"] = str(4096 * chips)
    cfg["server_env"].pop("GUBER_WARM_SHAPES")  # compile on first use instead
    cfg["keyspace"]["keys"] = keys
    cfg["check"] = {"sample_uniform": keys // 2,
                    "sample_hot_ranks": min(50, cfg["check"]["sample_hot_ranks"])}
    tr = spec["traffic"]
    tr["warm_seconds"] = 1
    # The engine answers more than seven copies of one key in one dispatch
    # as one aggregate, which near the key's limit is not what one check
    # after another gives. At full size no key comes near its limit with
    # that many copies in a dispatch (PERF.md, section 7); at this size the
    # load is kept low enough that none does either.
    if tr["loop"] == "open":
        tr["rate_rpc_per_s"] = rate
        tr["items_per_rpc"] = {"mix": [[0.7, 1, 1], [0.3, 2, 6]]}
        cfg["keyspace"]["limit"] = 20  # so that the hot keys still go over it
    else:
        tr["inflight"] = 4
        tr["items_per_rpc"] = {"fixed": 50}
        if cfg["keyspace"].get("algorithm") == "leaky":
            cfg["keyspace"]["limit"] = 50  # so that some keys end the run over it
            # as at full size, a table that holds every key: a leaky key
            # evicted live cannot be told from a lost hit (bench/checker.py)
            cfg["server_env"]["GUBER_CACHE_SIZE"] = str(65536)
    if "GLOBAL" in cfg["keyspace"].get("behavior", ()):
        # Sixteen slots a key on every device, where the full-size file has
        # 1.7 (every device also holds, in its replica table, a copy of every
        # key it does not own). What has to match the full size is not the
        # table's load but a dispatch's: `kernel2._probe_claim2` drops the
        # ninth new key of one dispatch in one 8-lane bucket, the engine
        # retries the row, and a row of a replica table retried after a sync
        # tick counts its hit twice (ROADMAP C2). The fill's dispatches hold
        # up to `keys` new rows here, 0.003 a bucket at full size; at 2 and
        # at 0.5 slots a key they put nine in a bucket somewhere, and 2 of 4
        # and 2 of 2 small runs then read fill_mismatches and
        # counters_below_expected above 0 (PERF.md section 6, PR 46).
        per_device = 4096
        while per_device < 16 * keys:
            per_device *= 2
        cfg["server_env"]["GUBER_CACHE_SIZE"] = str(per_device * chips)
    spec["extra_env"] = dict(CPU_MESH_ENV) if chips > 1 else {"GUBER_WIRE_COMPACT": "1"}
    return spec
