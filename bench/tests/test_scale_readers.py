"""The metrics that came with the deployment `token100m`, through their layer
files (the new readers device_memory_share and per_config; pipeline_ratio and
stage_delta over the new counters and stages), on hand-made contexts whose
answers are known, and on the context a program without the counters leaves
(the parent's): nothing to read is None, never a raise."""

import importlib.util
import json
import os

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GIB = 1 << 30


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(BENCH, "readers", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _layer(name):
    with open(os.path.join(BENCH, "layers", name + ".json")) as f:
        spec = json.load(f)
    spec.pop("about")
    return _reader(spec.pop("reader")), spec


def _ctx(engine_before, engine_after, stages=None, platform="tpu", keys=100_000_000):
    ctx = {
        "pipeline_before": {"engine": engine_before},
        "pipeline_after": {"engine": engine_after},
        "config": {"keyspace": {"keys": keys}},
        "device": {"platform": platform, "kind": "TPU v5 lite" if platform == "tpu" else "cpu"},
    }
    if stages is not None:
        ctx["stages_before"], ctx["stages_after"] = stages
    return ctx


PARENT = _ctx({"checks": 0, "table_bytes": GIB}, {"checks": 9, "table_bytes": GIB})


def _passes(sparse, sweep):
    return {"passes_total": sparse + sweep, "passes_sparse": sparse, "passes_sweep": sweep}


@pytest.mark.parametrize("sparse,sweep,want", [
    ((100, 5_100), (7, 7), 0.0),          # 8 GiB: no pad sweeps
    ((100, 4_100), (0, 1_000), 0.2),      # 1 GiB: one pass in five padded to 8K
    ((50, 50), (3, 11), 1.0),
    ((50, 50), (3, 3), None),             # no pass at all between the scrapes
])
def test_sweep_pass_share(sparse, sweep, want):
    reader, params = _layer("sweep_pass_share")
    ctx = _ctx(_passes(sparse[0], sweep[0]), _passes(sparse[1], sweep[1]))
    assert reader.read(ctx, **params) == want


def test_sweep_pass_share_reads_nothing_without_the_counters():
    """The parent's program, and a mesh engine: neither has the keys."""
    reader, params = _layer("sweep_pass_share")
    assert reader.read(PARENT, **params) is None


def _scans(n, launch_s, fetch_s):
    """Stage sums and counts on either side of `n` scans."""
    before = {"scan_launch": (1.0, 4.0), "scan_fetch": (2.0, 4.0), "put": (9.0, 99.0)}
    after = {"scan_launch": (1.0 + n * launch_s, 4.0 + n), "scan_fetch": (2.0 + n * fetch_s, 4.0 + n),
             "put": (19.0, 199.0)}
    return before, after


def test_scan_ms_from_fixed_stage_times():
    reader, params = _layer("scan_ms")
    # six scans of 0.4 ms of launch and 39.6 ms to the host: 40 ms each
    ctx = _ctx({}, {"table_bytes": 8 * GIB}, stages=_scans(6, 0.0004, 0.0396))
    assert reader.read(ctx, **params) == pytest.approx(40.0)
    assert reader.read(_ctx({}, {}, stages=_scans(0, 0, 0)), **params) is None  # no scan between the scrapes


def test_hbm_peak_share():
    reader, params = _layer("hbm_peak_share")
    limit = 16_909_336_576
    one = _ctx({}, {"device_peak_bytes": [8_800_000_000], "device_bytes_limit": [limit]})
    assert reader.read(one, **params) == pytest.approx(100 * 8.8e9 / limit)
    # the fullest of a mesh's chips
    four = _ctx({}, {"device_peak_bytes": [2.2e9, 2.3e9, 2.1e9, 2.2e9], "device_bytes_limit": [limit] * 4})
    assert reader.read(four, **params) == pytest.approx(100 * 2.3e9 / limit)
    assert reader.read(PARENT, **params) is None
    # a backend without memory statistics (the CPU) reports nulls
    assert reader.read(_ctx({}, {"device_peak_bytes": [None], "device_bytes_limit": [None]}), **params) is None


def test_evicted_live_share():
    reader, params = _layer("evicted_live_share")
    ctx = _ctx({"evicted_live_total": 5_000_000}, {"evicted_live_total": 5_700_000})
    assert reader.read(ctx, **params) == pytest.approx(0.057)  # since warm-up, the fill's included
    assert reader.read(_ctx({}, {"evicted_live_total": 0}), **params) == 0.0
    assert reader.read(PARENT, **params) is None
