"""bench/readers/mesh_roofline.py on a hand-made four-chip trace and two
hand-made `/v1/debug/pipeline` snapshots whose answers are known."""

import importlib.util
import os

import pytest

import xplane

_path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "readers", "mesh_roofline.py")
_spec = importlib.util.spec_from_file_location("mesh_roofline", _path)
mesh_roofline = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(mesh_roofline)

MS = 1e6  # ns
STEP = "jit_(decide2|per_device)"
COLL = "all[-_]to[-_]all|all[-_]gather|all[-_]reduce|collective[-_]permute"
TABLE = {"slot_bytes": 64, "slots_per_bucket": 8}


def _ctx(step_ms=2.0, coll_ms=0.02, passes=10, rows=2000, shards=4,
         exchange_bytes=393216, lanes=8192, counters=True):
    """`passes` executions of the mesh step on every one of four chips, two
    collective ops in each; the counters grew by one pass's worth a pass."""
    planes = {"_span_ns": [0.0, passes * 10 * MS]}
    for chip in range(4):
        mods, ops = [], []
        for k in range(passes):
            t = k * 10 * MS
            mods.append(["jit_per_device(7)", t, step_ms * MS])
            ops += [["all-to-all.1", t, coll_ms / 2 * MS],
                    ["fusion.2", t + 0.1 * MS, (step_ms - 0.2) * MS],
                    ["all-to-all.3", t + (step_ms - 0.05) * MS, coll_ms / 2 * MS]]
        planes[f"/device:TPU:{chip}"] = {"XLA Modules": mods, "XLA Ops": ops}
    before = {"checks": 1000, "dispatches": 5, "n_shards": shards}
    after = {"checks": 1000 + passes * rows, "dispatches": 5 + passes,
             "n_shards": shards}
    if counters:
        before.update(exchange_bytes=7, mesh_lanes=11)
        after.update(exchange_bytes=7 + passes * exchange_bytes,
                     mesh_lanes=11 + passes * lanes)
    return {
        "trace": xplane.reduce(planes),
        "pipeline_before": {"engine": before}, "pipeline_after": {"engine": after},
        "config": {"table": TABLE}, "device": {"kind": "TPU v5 lite"},
    }


def test_a_shard_decides_its_share_of_the_rows():
    # 2,000 rows a pass over four shards: 500 rows x 576 B in 2 ms on a chip
    got = mesh_roofline.read(_ctx(), "decide", match=STEP)
    assert got == pytest.approx(100 * 500 * 576 / 819e9 / 2e-3)
    one = mesh_roofline.read(_ctx(shards=1), "decide", match=STEP)
    assert one == pytest.approx(4 * got)


def test_the_exchange_is_held_against_the_ici_peak():
    # 393,216 B a pass in 0.02 ms of collective ops a pass, against 200 GB/s
    got = mesh_roofline.read(_ctx(), "exchange", match=STEP, ops=COLL)
    assert got == pytest.approx(100 * 393216 / 200e9 / 0.02e-3)


def test_lane_fill_is_live_rows_over_lanes():
    assert mesh_roofline.read(_ctx(), "lane_fill") == pytest.approx(100 * 2000 / 8192)


@pytest.mark.parametrize("what,kw", [
    ("decide", dict(step_ms=0.001)),       # 288 KB in 1 us
    ("exchange", dict(coll_ms=0.001)),     # 393 KB in 1 us
    ("lane_fill", dict(lanes=1000)),       # 2,000 rows in 1,000 lanes
])
def test_a_share_above_100_raises_and_is_never_clipped(what, kw):
    with pytest.raises(ValueError):
        mesh_roofline.read(_ctx(**kw), what, match=STEP, ops=COLL)


def test_nothing_to_read_is_none_not_zero():
    # a program without the counters (the parent of PR 26)
    ctx = _ctx(counters=False)
    assert mesh_roofline.read(ctx, "exchange", match=STEP, ops=COLL) is None
    assert mesh_roofline.read(ctx, "lane_fill") is None
    # a trace in which the step never ran, or no collective did
    assert mesh_roofline.read(_ctx(), "decide", match="jit_nothing") is None
    assert mesh_roofline.read(_ctx(), "exchange", match=STEP, ops="nothing") is None
    # an untraced run
    ctx = _ctx()
    ctx.pop("trace")
    assert mesh_roofline.read(ctx, "decide", match=STEP) is None
    # no pass at all
    ctx = _ctx()
    ctx["pipeline_after"] = ctx["pipeline_before"]
    assert mesh_roofline.read(ctx, "lane_fill") is None


def test_an_unknown_device_has_no_peak():
    ctx = _ctx()
    ctx["device"]["kind"] = "TPU v9"
    with pytest.raises(KeyError):
        mesh_roofline.read(ctx, "exchange", match=STEP, ops=COLL)
    with pytest.raises(KeyError):
        mesh_roofline.read(ctx, "decide", match=STEP)
