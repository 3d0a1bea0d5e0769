"""The readers that came with the program's own spans: idle time laid over
host spans (on hand-built traces whose shares follow by arithmetic, and on a
small trace recorded on the chip), the tolerant counter ratio, and the time
outside the handler."""

import gzip
import importlib.util
import json
import os

import numpy as np
import pytest

import loadgen
from doors import BENCH_DIR

MS = 1e6  # ns


def reader(name):
    spec = importlib.util.spec_from_file_location(
        f"bench_reader_{name}", os.path.join(BENCH_DIR, "readers", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


host_spans = reader("host_spans")


def _trace(spans):
    """One chip, busy [10,20] and [50,60] ms of a [0,100] ms trace: idle
    [0,10], [20,50], [60,100] = 80 ms. A second, lazier chip is ignored."""
    return {
        "span_ns": [0.0, 100 * MS],
        "chips": {
            "/device:TPU:0": [[10 * MS, 20 * MS], [50 * MS, 60 * MS]],
            "/device:TPU:1": [[10 * MS, 12 * MS]],
        },
        "spans": spans,
    }


def _dispatch(seq, put, issue, fetch):
    return [
        [name, s * MS, e * MS, {"dispatch": seq, "rows": 5}]
        for name, (s, e) in (("gub:put", put), ("gub:issue", issue), ("gub:fetch", fetch))
    ]


def test_gaps_in_a_dispatch_in_a_window_and_in_neither():
    spans = (
        # dispatch 1: host interval [5, 25] ms -> idle [5,10] + [20,25] = 10 ms
        _dispatch(1, (5, 6), (8, 9), (9, 25))
        # dispatch 2: [45, 70] -> idle [45,50] + [60,70] = 15 ms
        + _dispatch(2, (45, 46), (47, 48), (48, 70))
        # a window that closed at 45 ms after its oldest entry waited 15 ms:
        # [30, 45], all idle, outside every dispatch -> 15 ms
        + [["gub:close", 44.9 * MS, 45 * MS, {"reason": "slot", "rows": 5, "waited_us": 15000}]]
        # a window [62, 72] that overlaps dispatch 2: only [70, 72] counts -> 2 ms
        + [["gub:close", 71.9 * MS, 72 * MS, {"reason": "idle", "rows": 1, "waited_us": 10000}]]
        # a close that formed no chunk carries no waited_us and is no window
        + [["gub:close", 90 * MS, 90.1 * MS, {"reason": "slot"}]]
        # work spans of other kinds are no dispatch
        + [["gub:apply", 80 * MS, 81 * MS, {"dispatch": 2}]]
    )
    got = host_spans.shares(_trace(spans))
    assert got["dispatch"] == pytest.approx(100 * 25 / 80)
    assert got["window"] == pytest.approx(100 * 17 / 80)
    assert got["dispatch"] + got["window"] <= 100


def test_a_dispatch_seen_only_in_part_counts_for_what_is_seen():
    # the trace began while dispatch 7 was in its fetch: [0, 4] ms, all idle
    spans = [["gub:fetch", 0.0, 4 * MS, {"dispatch": 7, "rows": 2}]]
    got = host_spans.shares(_trace(spans))
    assert got["dispatch"] == pytest.approx(100 * 4 / 80)
    assert got["window"] == 0.0


def test_without_a_gub_span_there_is_nothing_to_read():
    assert host_spans.shares(_trace([])) == {"dispatch": None, "window": None}
    # and the reader hands that on, without a child process, from its cache
    ctx = {"trace": {}, "_host_span_shares": host_spans.shares(_trace([]))}
    assert host_spans.read(ctx, "dispatch") is None
    assert host_spans.read({"trace": None}, "window") is None  # an untraced run


def test_a_chip_that_never_idles_has_no_share():
    t = _trace(_dispatch(1, (5, 6), (8, 9), (9, 25)))
    t["chips"] = {"/device:TPU:0": [[0.0, 100 * MS]]}
    assert host_spans.shares(t) == {"dispatch": None, "window": None}


RECORDED = os.path.join(os.path.dirname(__file__), "data", "host_spans_small.json.gz")


@pytest.mark.skipif(not os.path.exists(RECORDED), reason="no recorded trace")
def test_the_recorded_chip_trace_gives_shares():
    with gzip.open(RECORDED, "rt") as f:
        loaded = json.load(f)
    assert list(loaded["chips"]) == ["/device:TPU:0"]
    names = {s[0] for s in loaded["spans"]}
    assert {"gub:close", "gub:put", "gub:issue", "gub:fetch"} <= names
    got = host_spans.shares(loaded)
    assert 0 < got["dispatch"] < 100 and 0 <= got["window"] < 100
    assert got["dispatch"] + got["window"] <= 100


def test_a_counter_the_program_lacks_reads_none_not_an_error():
    ratio = reader("pipeline_ratio")
    before = {"engine": {"dispatches": 10}, "batcher": {"dispatches": 4}}
    after = {"engine": {"dispatches": 40}, "batcher": {"dispatches": 16}}
    ctx = {"pipeline_before": before, "pipeline_after": after}
    assert ratio.read(ctx, "engine.dispatches", "batcher.dispatches") == 2.5
    old = {"pipeline_before": {"engine": {"dispatches": 10}, "batcher": {}},
           "pipeline_after": {"engine": {"dispatches": 40}, "batcher": {}}}
    assert ratio.read(old, "engine.dispatches", "batcher.dispatches") is None
    assert ratio.read({**ctx, "pipeline_after": before},
                      "engine.dispatches", "batcher.dispatches") is None
    assert ratio.read({}, "engine.dispatches", "batcher.dispatches") is None


def test_time_outside_the_handler_is_client_mean_less_handler_mean():
    outside = reader("outside_handler")
    led = loadgen.Ledger(warm_s=1.0, seconds=2.0)
    # three answered RPCs of 10, 20 and 30 ms; one that failed; one refused
    for sent, done, resp in ((0.5, 0.51, b"x"), (1.5, 1.52, b"x"), (2.5, 2.53, b"x"),
                             (2.6, 2.9, None), (np.nan, np.nan, None)):
        led.idx.append([0])
        led.due.append(0.0)
        led.sent.append(sent)
        led.done.append(done)
        led.resp.append(resp)
    ctx = {"ledger": led, "stages_before": {"request": (1.0, 10.0)},
           "stages_after": {"request": (1.024, 13.0)}}  # 8 ms a request
    assert outside.read(ctx, "request") == pytest.approx(20.0 - 8.0)
    ctx["stages_after"] = ctx["stages_before"] = {}  # a program without the stage
    assert outside.read(ctx, "request") is None
