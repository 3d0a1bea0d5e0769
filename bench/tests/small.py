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


def small_spec(workload: str, keys: int = 2000, rate: float = 100.0) -> dict:
    spec = copy.deepcopy(harness.load_cell(workload))
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
    spec["extra_env"] = dict(CPU_MESH_ENV) if chips > 1 else {"GUBER_WIRE_COMPACT": "1"}
    return spec
