"""The tiered cell (`token40m-tiered.bulk1000-zipf-closed64`) cut to a size
the CPU holds, with its shape kept: 2.4 tracked keys a slot (9,830 keys over a
4,096-slot table), the fill in rank order, Zipf(0.99) traffic. `small.py` on
its own gives every cell 2,000 keys, fewer than the table's slots, which for
this cell is the tier at rest; test_end_to_end.py runs it so with the others.

Two things are shown here: the cell is served exactly (no count lost, the
counts of the tier reported, its metrics read), and the control of the
deployment's own guarantee — a shadow that drops rows without counting them
must come out as not correct.

`python -m pytest bench/tests/test_tiered_cell.py -q` (by hand, with the rest
of bench/tests; a first run of a checkout also compiles).
"""

import asyncio
import os
import textwrap

import pytest

import harness
import small
from doors import ROOT

CELL = "token40m-tiered.bulk1000-zipf-closed64"
KEYS = 9_830  # 2.4 a slot of 4,096


def spec(extra_env=None):
    s = small.small_spec(CELL, keys=KEYS)
    s["config"]["check"] = {"sample_uniform": KEYS // 2, "sample_hot_ranks": 50}
    s["extra_env"] = {**s["extra_env"], **(extra_env or {})}
    return s


def run(seed, trace, extra_env=None, seconds=3.0):
    return asyncio.run(harness.run_cell(
        CELL, seed, seconds, trace, platform="cpu", spec=spec(extra_env)))


@pytest.fixture(scope="module")
def warmed():
    for _ in range(3):  # until a run finds every program it uses in the cache
        cache = run(3_000_000_041, False)["context"]["cache_entries"]
        if cache["after"] == cache["before"]:
            break


def test_the_population_is_larger_than_the_table():
    cfg = spec()["config"]
    assert int(cfg["keyspace"]["keys"]) > int(cfg["server_env"]["GUBER_CACHE_SIZE"])
    assert cfg["server_env"]["GUBER_TIER_ENABLED"] == "true"
    assert cfg["expect_engine"]["tiering"] == "shadow"


@pytest.mark.parametrize("trace", [False, True], ids=["untraced", "traced"])
def test_the_tiered_cell_loses_no_count(trace, warmed):
    out = run(3_000_000_043, trace)
    res, ctx = out["result"], out["context"]
    assert res["correct"], ctx["compared"]
    assert res["failed"] == 0 and res["attempted"] > 0
    # state lost: none, so the harness allows ten keys evicted in the sample
    assert ctx["evicted_live_total"] == 0
    assert res["compared"]["counters_evicted_in_sample"][1] == 10
    assert res["compared"]["counters_evicted_in_sample"][0] == 0
    if trace:
        names = set(res["metrics"])
        for name in ("tier_promoted_share", "tier_demoted_share", "tier_rehydrate_share",
                     "tier_probe_ms", "tier_promote_ms", "tier_harvest_ms",
                     "evicted_live_share"):
            assert name in names, name
        assert res["metrics"]["tier_promoted_share"]["value"] > 0
        assert res["metrics"]["evicted_live_share"]["value"] == 0


LOSSY = textwrap.dedent('''
    """Test control: the shadow forgets every fourth demoted row and counts
    nothing (sitecustomize of the server child, bench/tests/test_tiered_cell.py)."""
    import gubernator_tpu.tier.shadow as shadow

    _offer = shadow.ShadowTable.offer


    def offer(self, fps, rows, now_ms, reason="evict"):
        keep = (fps % 4) != 0
        return _offer(self, fps[keep], rows[keep], now_ms, reason)


    shadow.ShadowTable.offer = offer
''')


def test_a_shadow_that_drops_rows_uncounted_is_not_correct(tmp_path, warmed):
    (tmp_path / "sitecustomize.py").write_text(LOSSY)
    out = run(3_000_000_047, False,
              extra_env={"PYTHONPATH": str(tmp_path) + os.pathsep + ROOT})
    res, ctx = out["result"], out["context"]
    assert ctx["evicted_live_total"] == 0  # the server counted no loss
    value, limit = res["compared"]["counters_evicted_in_sample"]
    assert limit == 10 and value > limit  # keys came back with a fresh quota
    assert not res["correct"]
