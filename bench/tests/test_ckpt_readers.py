"""bench/readers/ckpt_roofline.py and pipeline_value.py on a hand-made trace
and two hand-made `/v1/debug/pipeline` snapshots whose answers are known."""

import importlib.util
import os

import pytest

import xplane

MS = 1e6  # ns
TABLE = {"slot_bytes": 64, "slots_per_bucket": 8}
MATCH = "jit__extract_blocks"


def _reader(name):
    path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                        "readers", name + ".py")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ckpt_roofline, pipeline_value = _reader("ckpt_roofline"), _reader("pipeline_value")


def _ctx(epochs=2, grids=6, grid_ms=0.5, dirty=364_000, rows=1_740_000, blk=1,
         block=True, program="jit__extract_blocks_grid(12)"):
    """`epochs` epochs of `grids` executions each in the traced sub-window;
    the counters grew by as many epochs of `dirty` blocks and `rows` rows."""
    mods = [["jit_decide2_wire_impl(3)", k * 5 * MS, 2 * MS] for k in range(40)]
    for e in range(epochs):
        for g in range(grids):
            mods.append([program, (e * 100 + g) * MS + 3 * MS, grid_ms * MS])
    planes = {"_span_ns": [0.0, 200 * MS],
              "/device:TPU:0": {"XLA Modules": mods, "XLA Ops": []}}
    before = {"engine": {"checks": 0, "ckpt_blk": blk}}
    after = {"engine": {"checks": 800_000, "ckpt_blk": blk}}
    if block:
        before["checkpoint"] = {"epochs": 30, "extracts": 100, "dirty_blocks": 5, "rows": 7,
                                "bases": 0, "epoch_age_ms_max": 1900.0}
        after["checkpoint"] = {"epochs": 30 + epochs, "extracts": 100 + epochs * grids,
                               "dirty_blocks": 5 + epochs * dirty, "rows": 7 + epochs * rows,
                               "bases": 1, "epoch_age_ms_max": 1432.5}
    return {"trace": xplane.reduce(planes), "pipeline_before": before, "pipeline_after": after,
            "config": {"table": TABLE}, "device": {"kind": "TPU v5 lite"}}


def test_the_bytes_an_execution_needs():
    # a dirty bucket is read whole (512 B), a live row written once (64 B)
    assert ckpt_roofline.needed_bytes(364_000, 1_740_000, TABLE) == 364_000 * 512 + 1_740_000 * 64
    assert ckpt_roofline.needed_bytes(10, 0, TABLE, blk=8) == 10 * 8 * 512


def test_the_share_is_needed_bytes_over_peak_over_device_time():
    # 297.7 MB an epoch over six executions of 0.5 ms each
    got = ckpt_roofline.read(_ctx(), MATCH)
    assert got == pytest.approx(100 * (364_000 * 512 + 1_740_000 * 64) / 6 / 819e9 / 0.5e-3)
    assert 0 < got < 100
    # the same bytes in one execution six times as long: the same share
    one = ckpt_roofline.read(_ctx(grids=1, grid_ms=3.0), MATCH)
    assert one == pytest.approx(got)
    # the program as the parent names it is found by the same pattern
    assert ckpt_roofline.read(_ctx(program="jit__extract_blocks_sorted(4)"), MATCH) == pytest.approx(got)


def test_a_share_over_100_is_a_fault_and_raises():
    with pytest.raises(ValueError, match="roofline share"):
        ckpt_roofline.read(_ctx(grid_ms=0.005), MATCH)


def test_nothing_to_read_reads_nothing():
    assert ckpt_roofline.read(_ctx(block=False), MATCH) is None  # the plane is off
    assert ckpt_roofline.read(_ctx(epochs=0), MATCH) is None  # no epoch in the trace
    ctx = _ctx()
    ctx["pipeline_after"]["checkpoint"] = None
    assert ckpt_roofline.read(ctx, MATCH) is None
    del ctx["trace"]
    assert ckpt_roofline.read(ctx, MATCH) is None


def test_a_gauge_is_read_after_the_window_and_a_count_as_its_growth():
    ctx = _ctx()
    assert pipeline_value.read(ctx, "checkpoint.epoch_age_ms_max") == 1432.5
    assert pipeline_value.read(ctx, "checkpoint.bases", delta=True) == 1.0
    assert pipeline_value.read(ctx, "checkpoint.epochs", delta=True) == 2.0
    off = _ctx(block=False)
    assert pipeline_value.read(off, "checkpoint.bases", delta=True) is None
    off["pipeline_after"]["checkpoint"] = None  # the program has the block, the plane is off
    assert pipeline_value.read(off, "checkpoint.epoch_age_ms_max") is None
    assert pipeline_value.read({}, "checkpoint.bases") is None
