"""`tier_ahead_share` (PR 44) on the tiered cell at the size
`test_tiered_cell.py` gives it: promotes installed by an issue job's merge,
ahead of the dispatch's passes (`tier.promoted_ahead`), per promote of any kind
(`tier.promoted`), read from `/v1/debug/pipeline` by `pipeline_ratio`.

Two things are shown: a traced run of the program that has the counter reads
the metric, a share in (0, 1]; a program whose `tier` block lacks the key (as
the parent's does) leaves the metric out of the result line and its run is
sound all the same.

`python -m pytest bench/tests/test_tier_ahead_share.py -q` (by hand, with the
rest of bench/tests; a first run of a checkout also compiles).
"""

import os
import textwrap

import pytest

import harness
from doors import ROOT
from test_tiered_cell import CELL, run

METRIC = "tier_ahead_share"


@pytest.fixture(scope="module")
def warmed():
    for _ in range(3):  # until a run finds every program it uses in the cache
        cache = run(3_000_000_051, False)["context"]["cache_entries"]
        if cache["after"] == cache["before"]:
            break


def test_the_metric_is_the_tiered_cells_alone():
    entry = [m for m in harness.load_json("..", "BENCHMARK.json")["per_layer"]
             if m["name"] == METRIC]
    assert len(entry) == 1
    assert entry[0]["workloads"] == [CELL] and entry[0]["layer"] == "tier"
    assert entry[0]["moves"] == "checks_per_s" and entry[0]["source"] == "program_counter"
    layer = harness.load_json("layers", METRIC + ".json")
    assert layer["reader"] == "pipeline_ratio"
    assert (layer["num"], layer["den"]) == ("tier.promoted_ahead", "tier.promoted")


def test_a_traced_run_reads_the_share(warmed):
    out = run(3_000_000_053, True)
    res, ctx = out["result"], out["context"]
    assert res["correct"], ctx["compared"]
    assert ctx["evicted_live_total"] == 0
    share = res["metrics"][METRIC]["value"]
    # the window's misses are shadowed keys (the fill made every key): most
    # come back ahead of the launch; the rest are the miss path's (a promote
    # that found no lane, a key a merge of its own dispatch pushed out)
    assert 0.5 < share <= 1.0
    assert res["metrics"]["tier_promoted_share"]["value"] > 0


KEYLESS = textwrap.dedent('''
    """Test control: the `tier` block as the parent's program reports it, with
    no `promoted_ahead` (sitecustomize of the server child,
    bench/tests/test_tier_ahead_share.py)."""
    import gubernator_tpu.tier.manager as manager

    _pipeline = manager.TierManager.pipeline


    def pipeline(self):
        out = _pipeline(self)
        if out is not None:
            out.pop("promoted_ahead", None)
        return out


    manager.TierManager.pipeline = pipeline
''')


def test_a_program_without_the_counter_reads_nothing(tmp_path, warmed):
    (tmp_path / "sitecustomize.py").write_text(KEYLESS)
    out = run(3_000_000_057, True,
              extra_env={"PYTHONPATH": str(tmp_path) + os.pathsep + ROOT})
    res, ctx = out["result"], out["context"]
    assert res["correct"], ctx["compared"]
    assert METRIC not in res["metrics"]
    assert "tier_promoted_share" in res["metrics"]
