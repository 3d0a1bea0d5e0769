"""The reduction from trace to numbers: on a hand-made trace whose answers
are known, and on a small trace recorded on the chip (data/trace_small.json.gz,
made by `bench/xplane.py <dir> --dump tpu` and cut to its first events)."""

import gzip
import json
import os

import pytest

import xplane

MS = 1e6  # ns


def _plane(busy_shift=0.0):
    """Two dispatches of one program: ops a,b,c with b overlapping a."""
    mods, ops = [], []
    for k in range(2):
        t = k * 10 * MS + busy_shift
        mods.append(["jit_decide2_wire_cols_impl(123)", t, 4 * MS])
        ops += [["fusion.a", t, 2 * MS], ["sort.b", t + 1 * MS, 2 * MS],
                ["all-to-all.c", t + 3.5 * MS, 0.5 * MS]]
    return {"XLA Modules": mods, "XLA Ops": ops}


def _trace():
    return {"/device:TPU:0": _plane(), "/device:TPU:1": _plane(1 * MS),
            "_span_ns": [0.0, 20 * MS]}


def test_busy_is_the_union_of_op_intervals_and_idle_the_rest():
    red = xplane.reduce(_trace())
    # per dispatch: [0,3] and [3.5,4] ms busy = 3.5 ms; two dispatches
    assert red["window_s"] == pytest.approx(0.020)
    assert red["busy_s"] == pytest.approx(0.007)
    assert red["idle_share_worst"] == pytest.approx(1 - 0.007 / 0.020)
    for chip in red["chips"].values():
        assert chip["ops"]["sort.b"] == [2, pytest.approx(0.004)]
        assert chip["modules"]["jit_decide2_wire_cols_impl(123)"][0] == 2


def test_sums_by_name_are_averaged_over_the_chips():
    red = xplane.reduce(_trace())
    assert xplane.summed(red, "XLA Modules", "jit_decide2") == (2, pytest.approx(0.008))
    assert xplane.summed(red, "XLA Ops", "all-to-all") == (2, pytest.approx(0.001))
    assert xplane.summed(red, "XLA Ops", "nothing") == (0, 0)


def test_gaps_are_named_after_the_program_that_ended_them():
    bd = xplane.breakdown(xplane.reduce(_trace()))
    assert bd["device_ops"][0][0] in ("fusion.a", "sort.b")
    gaps = dict(bd["idle_gaps"])
    assert gaps["idle_before:jit_decide2_wire_cols_impl"] == pytest.approx(0.006)
    assert gaps["idle_before:inside a program"] == pytest.approx(0.001)


def test_a_trace_without_a_device_plane_is_refused():
    with pytest.raises(ValueError):
        xplane.reduce({"_span_ns": [0.0, 1e9]})


def test_roofline_share_and_its_refusals():
    table = {"slot_bytes": 64, "slots_per_bucket": 8}
    needed = xplane.decide_needed_bytes(2600, table)
    assert needed == 2600 * 576
    pct = xplane.roofline_share_pct(needed, 250e-6, "TPU v5 lite")
    assert pct == pytest.approx(100 * needed / 819e9 / 250e-6)
    with pytest.raises(ValueError):  # over 100%: the count is wrong, never clipped
        xplane.roofline_share_pct(needed, 1e-6, "TPU v5 lite")
    with pytest.raises(KeyError):  # an unknown device has no peak
        xplane.roofline_share_pct(needed, 250e-6, "TPU v9")


RECORDED = os.path.join(os.path.dirname(__file__), "data", "trace_small.json.gz")


@pytest.mark.skipif(not os.path.exists(RECORDED), reason="no recorded trace")
def test_the_recorded_chip_trace_reduces():
    with gzip.open(RECORDED, "rt") as f:
        planes = json.load(f)
    red = xplane.reduce(planes)
    assert list(red["chips"]) == ["/device:TPU:0"]
    assert 0 < red["busy_s"] < red["window_s"]
    events, seconds = xplane.summed(red, "XLA Modules", "jit_decide2")
    assert events > 0 and red["busy_s"] * 0.9 < seconds < red["window_s"]
    bd = xplane.breakdown(red)
    assert 0 < len(bd["device_ops"]) <= 10 and 0 < len(bd["idle_gaps"]) <= 10
