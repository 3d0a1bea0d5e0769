"""`idle_critical` on hand-made traces whose shares follow by arithmetic (the
chip's plane 2 ms before the host's, as the first chip trace had it): one
dispatch at a time, dispatches that overlap (a program that starts after the
next dispatch's issue has), a dispatch of several passes with programs of
nobody's in between; the five shares sum to 100; a trace of a program from
before the span stats reads nothing."""

import json
import os
import re

import pytest

from doors import BENCH_DIR, ROOT
from test_host_spans import MS, reader

idle_critical = reader("idle_critical")
MATCH = "jit_(decide2|per_device)"
DECIDE = "jit_decide2_wire_cols_impl(123)"


def _dispatch(seq, put, issue, origin=None):
    """gub:put and gub:issue of one dispatch (ms); `origin` is (closed_us,
    window_us, slot_us) on the put span, as the program writes them."""
    stats = {"dispatch": seq, "rows": 5}
    first = dict(stats)
    if origin is not None:
        first.update(zip(("closed_us", "window_us", "slot_us"), origin))
    return [
        ["gub:put", put[0] * MS, put[1] * MS, first],
        ["gub:issue", issue[0] * MS, issue[1] * MS, stats],
        ["gub:fetch", issue[1] * MS, (issue[1] + 1) * MS, stats],
    ]


SKEW = 2.0  # ms the device plane's clock lies before the host's, as on the chip


def _trace(end, programs, spans, busy=None, enqueues=True):
    """`programs` (name, start, end[, held]) and `busy` on the HOST's clock,
    ms; the trace holds them as the chip's plane would, SKEW earlier, with a
    run id each and the host's enqueue event `held` ms before the start (0:
    the chip was idle and took the program at once)."""
    progs, enq = [], {}
    for rid, (name, s, e, *held) in enumerate(programs, start=100):
        progs.append([name, (s - SKEW) * MS, (e - SKEW) * MS, rid])
        enq[str(rid)] = (s - (held[0] if held else 0.0)) * MS
    on_chip = busy or [[s, e] for _n, s, e, *_ in programs]
    return {
        "span_ns": [0.0, end * MS],
        "chips": {
            "/device:TPU:0": [[(s - SKEW) * MS, (e - SKEW) * MS] for s, e in on_chip],
            "/device:TPU:1": [[1 * MS, 2 * MS]],  # a lazier chip is ignored
        },
        "programs": {"/device:TPU:0": progs, "/device:TPU:1": []},
        "enqueued": {"0": enq} if enqueues else {},
        "spans": spans,
    }


def _check(got, want_ms):
    total = sum(want_ms.values())
    assert sum(got.values()) == pytest.approx(100.0, abs=0.1)
    for stage, ms in want_ms.items():
        assert got[stage] == pytest.approx(100.0 * ms / total), stage


def test_one_dispatch_at_a_time():
    # idle [0,40] ends with dispatch 1's program, [50,90] with dispatch 2's;
    # [95,100] ends with no program and is not read
    spans = (
        # closed at 18, window open from 15, queued for a slot from 10
        _dispatch(1, (20, 30), (35, 38), origin=(2000, 3000, 5000))
        # closed at 59 by a worker that had just come free, queued from 55
        + _dispatch(2, (60, 75), (80, 84), origin=(1000, 0, 4000))
        + [["gub:close", 17.9 * MS, 18 * MS, {"reason": "slot", "waited_us": 8000}]]
    )
    got = idle_critical.shares(
        _trace(100, [(DECIDE, 40, 50), (DECIDE, 90, 95)], spans), MATCH
    )
    _check(got, {
        "upstream": 10 + 3 + 5,   # [0,10] + the window [15,18]; [50,55]
        "slot": 5 + 4,            # [10,15]; [55,59]
        "handoff": 2 + 5 + 1 + 5,  # [18,20] + [30,35]; [59,60] + [75,80]
        "put": 10 + 15,
        "issue": 5 + 10,          # [35,40]; [80,90]
    })


def test_four_overlapping_dispatches_and_a_program_behind_the_next_issue():
    spans = (
        _dispatch(1, (5, 15), (20, 28), origin=(1000, 1000, 0))
        + _dispatch(2, (6, 18), (28.2, 34), origin=(2000, 0, 0))
        + _dispatch(3, (16, 26), (41, 44.5), origin=(1000, 0, 9000))
        + _dispatch(4, (27, 37), (50.5, 54), origin=(500, 500, 10000))
        + _dispatch(5, (58, 62), (63, 64), origin=(500, 0, 6000))
    )
    programs = [
        # dispatch 1's, though it was enqueued at 28.3 and 2's issue began at 28.2
        (DECIDE, 28.5, 33, 0.2),
        (DECIDE, 34.5, 39), (DECIDE, 45, 50), (DECIDE, 55, 56),
        (DECIDE, 64.5, 66),
    ]
    launched = [[n, (s - (held[0] if held else 0)) * MS] for n, s, _e, *held in programs]
    issues = sorted([s, e, st["dispatch"]] for n, s, e, st in spans if n == "gub:issue")
    assert idle_critical.owners(launched, issues, re.compile(MATCH)) == [1, 2, 3, 4, 5]
    got = idle_critical.shares(_trace(70, programs, spans), MATCH)
    _check(got, {
        # gap [0,28.5], dispatch 1: before its window and in it [0,4]
        "upstream": 4,
        # gap [56,64.5], dispatch 5: queued for a slot from 51.5 to 57.5
        "slot": 1.5,
        # d1 [4,5] + [15,20]; d3 [39,41]; d4 [50,50.5]; d5 [57.5,58] + [62,63]
        "handoff": 1 + 5 + 2 + 0.5 + 0.5 + 1,
        "put": 10 + 4,        # d1 [5,15]; d5 [58,62]
        # d1 [20,28.5]; d2 [33,34.5]; d3 [41,45]; d4 [50.5,55]; d5 [63,64.5]
        "issue": 8.5 + 1.5 + 4 + 4.5 + 1.5,
    })


def test_passes_of_one_dispatch_and_programs_that_are_no_dispatchs():
    spans = (
        _dispatch(1, (2, 10), (12, 20), origin=(1000, 1000, 0))
        + _dispatch(2, (30, 45), (46, 48), origin=(500, 0, 0))
    )
    programs = [
        (DECIDE, 14, 18), ("jit_convert_element_type(7)", 19, 19.5),
        (DECIDE, 21, 24), (DECIDE, 24, 26),   # three passes of dispatch 1
        ("jit__scan_body(9)", 40, 42),
        (DECIDE, 49, 52),
    ]
    busy = [(14, 18), (19, 19.5), (21, 26), (40, 42), (49, 50), (51, 52)]
    got = idle_critical.shares(_trace(60, programs, spans, busy), MATCH)
    _check(got, {
        # [0,1] before and in dispatch 1's window; [26,40] ended by the scan,
        # with dispatch 1 behind it and dispatch 2 ahead
        "upstream": 1 + 14,
        "slot": 0,
        "handoff": 1 + 2 + 1,   # d1 [1,2] + [10,12]; d2 [45,46]
        "put": 8 + 3,           # d1 [2,10]; d2 [42,45]
        # d1 [12,14]; [18,19] ended by the conversion between two of d1's
        # passes; [19.5,21]; d2 [46,49] and the hole [50,51] in its program
        "issue": 2 + 1 + 1.5 + 3 + 1,
    })


def test_a_trace_without_the_span_stats_or_the_program_reads_nothing():
    programs = [(DECIDE, 40, 50), (DECIDE, 90, 95)]
    parent = _dispatch(1, (20, 30), (35, 38)) + _dispatch(2, (60, 75), (80, 84))
    none = dict.fromkeys(idle_critical.STAGES)
    assert idle_critical.shares(_trace(100, programs, parent), MATCH) == none
    ours = _dispatch(1, (20, 30), (35, 38), origin=(2000, 3000, 5000))
    assert idle_critical.shares(_trace(100, programs, []), MATCH) == none
    assert idle_critical.shares(
        _trace(100, [("jit__scan_body(1)", 40, 50)], ours), MATCH
    ) == none
    # nothing to set the chip's clock by: no enqueue event
    assert idle_critical.shares(_trace(100, programs, ours, enqueues=False), MATCH) == none
    assert idle_critical.read({"trace": None}, "slot", MATCH) is None
    assert idle_critical.shares(_trace(100, programs, ours), MATCH)["slot"] is not None


def test_every_listed_metric_names_a_layer_file_a_reader_and_cells_that_report_what_it_moves():
    """BENCHMARK.json's per-layer entries against the files they name: the
    quantity's layer file, its reader, and cells that report the end-to-end
    metric the entry says it moves; the five idle_crit_* are listed together."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bm = json.load(f)
    cells = {w["name"] for w in bm["workloads"]}
    e2e = {m["name"]: set(m.get("workloads", cells)) for m in bm["end_to_end"]}
    crit: dict = {}
    for m in bm["per_layer"]:
        with open(os.path.join(BENCH_DIR, "layers", m["name"].split(".")[0] + ".json")) as f:
            spec = json.load(f)
        assert os.path.exists(os.path.join(BENCH_DIR, "readers", spec["reader"] + ".py")), m["name"]
        assert set(m["workloads"]) <= e2e[m["moves"]], m["name"]
        if m["name"].startswith("idle_crit_"):
            for w in m["workloads"]:
                crit.setdefault(w, set()).add(spec["what"])
    assert crit == {w: set(idle_critical.STAGES) for w in cells}
