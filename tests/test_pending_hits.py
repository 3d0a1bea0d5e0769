"""PendingHits unit tests — the columnar GLOBAL hit accumulator
(parallel/global_sync.py). The reference semantics it must reproduce are
the async-hit aggregation of global.go:109-123: sum Hits, OR
RESET_REMAINING, newest request's config wins; plus the take() pop used by
the sync outbox builder."""

import numpy as np

from gubernator_tpu.ops.batch import pack_requests
from gubernator_tpu.parallel.global_sync import PendingHits
from gubernator_tpu.types import Behavior, RateLimitRequest

NOW = 1_700_000_000_000


def hb_for(specs):
    """specs: list of (key, hits, limit, behavior)."""
    reqs = [
        RateLimitRequest(
            name="p", unique_key=k, hits=h, limit=lim, duration=60_000,
            behavior=b, created_at=NOW,
        )
        for (k, h, lim, b) in specs
    ]
    hb, errs = pack_requests(reqs, NOW)
    assert all(e is None for e in errs)
    return hb


def test_merge_aggregates_within_batch():
    p = PendingHits()
    hb = hb_for([("a", 2, 10, 0), ("b", 1, 10, 0), ("a", 3, 99, 0)])
    p.merge(hb, np.arange(3), hb.hits.copy(),
            hb.behavior & np.int32(Behavior.RESET_REMAINING))
    assert len(p) == 2
    by_fp = dict(zip(p.hb.fp.tolist(), p.hits.tolist()))
    # same-key hits summed; newest config (limit=99) carried
    fa = hb.fp[0]
    assert by_fp[int(fa)] == 5
    carrier_limit = int(p.hb.limit[p.hb.fp.tolist().index(int(fa))])
    assert carrier_limit == 99


def test_merge_across_batches_sums_and_ors():
    p = PendingHits()
    hb1 = hb_for([("k", 1, 10, Behavior.RESET_REMAINING)])
    p.merge(hb1, np.array([0]), np.array([1], dtype=np.int64),
            hb1.behavior & np.int32(Behavior.RESET_REMAINING))
    hb2 = hb_for([("k", 4, 77, 0)])
    p.merge(hb2, np.array([0]), np.array([4], dtype=np.int64),
            hb2.behavior & np.int32(Behavior.RESET_REMAINING))
    assert len(p) == 1
    assert int(p.hits[0]) == 5
    assert int(p.reset[0]) == int(Behavior.RESET_REMAINING)  # OR survives
    assert int(p.hb.limit[0]) == 77  # newest config wins


def test_take_pops_disjoint_and_drains():
    p = PendingHits()
    hb = hb_for([(f"k{i}", 1, 10, 0) for i in range(10)])
    p.merge(hb, np.arange(10), hb.hits.copy(), np.zeros(10, dtype=np.int32))
    cfg1, hits1, _ = p.take(4)
    assert cfg1.fp.shape[0] == 4 and len(p) == 6
    cfg2, hits2, _ = p.take(100)  # over-ask drains the rest
    assert cfg2.fp.shape[0] == 6 and len(p) == 0
    assert p.hb is None
    # popped sets are disjoint and cover everything
    assert set(cfg1.fp.tolist()) | set(cfg2.fp.tolist()) == set(hb.fp.tolist())
    assert not set(cfg1.fp.tolist()) & set(cfg2.fp.tolist())


def test_take_views_do_not_alias_remainder():
    """Mutating a popped box (the outbox builder stamps hits/behavior/
    created_at in place) must never corrupt the entries still queued."""
    p = PendingHits()
    hb = hb_for([(f"k{i}", 1, 10, 0) for i in range(8)])
    p.merge(hb, np.arange(8), hb.hits.copy(), np.zeros(8, dtype=np.int32))
    cfg, hits, reset = p.take(4)
    remainder_before = p.hb.hits.copy()
    cfg.hits[:] = 999  # outbox-builder-style in-place stamp
    cfg.behavior[:] |= 0x7F
    np.testing.assert_array_equal(p.hb.hits, remainder_before)
    assert not (p.hb.behavior & 0x40).any()


def test_empty_accumulator():
    p = PendingHits()
    assert len(p) == 0
    # merging zero rows is a no-op that keeps the accumulator well-formed
    hb = hb_for([("x", 1, 10, 0)])
    p.merge(hb, np.arange(0), np.zeros(0, dtype=np.int64),
            np.zeros(0, dtype=np.int32))
    assert len(p) == 0


def test_clear_drops_everything():
    """clear() is the reset entry point for a test that needs an empty
    queue — no reaching into __slots__ private fields."""
    p = PendingHits()
    hb = hb_for([(f"k{i}", 1, 10, 0) for i in range(5)])
    p.merge(hb, np.arange(5), hb.hits.copy(), np.zeros(5, dtype=np.int32))
    assert len(p) == 5
    p.clear()
    assert len(p) == 0
    assert p.hb is None and p.hits is None and p.reset is None
    # cleared accumulator accepts fresh merges
    p.merge(hb, np.arange(5), hb.hits.copy(), np.zeros(5, dtype=np.int32))
    assert len(p) == 5


def test_take_popped_columns_are_copies():
    """The POPPED box must not share storage with the accumulator either
    (the de-alias guarantee take() now makes): stamping the popped columns
    in place — exactly what _build_box does — must never write through
    into entries still queued, in either drain order."""
    p = PendingHits()
    hb = hb_for([(f"k{i}", 1, 10, 0) for i in range(8)])
    p.merge(hb, np.arange(8), hb.hits.copy(), np.zeros(8, dtype=np.int32))
    cfg, hits, reset = p.take(4)
    assert not np.shares_memory(cfg.hits, p.hb.hits)
    assert not np.shares_memory(hits, p.hits)
    assert not np.shares_memory(reset, p.reset)
    # full-drain pop of the remainder is also a copy (accumulator nulls out)
    cfg2, hits2, _ = p.take(100)
    cfg2.hits[:] = 123  # must be dead storage now
    assert len(p) == 0


def test_owner_marker_zero_hits_entry_kept():
    """Owner-side rows queue with hits=0 (broadcast markers) and must
    survive aggregation as entries — the sync round broadcasts them even
    though they contribute no hits."""
    p = PendingHits()
    hb = hb_for([("own", 3, 10, 0)])
    p.merge(hb, np.array([0]), np.array([0], dtype=np.int64),
            np.zeros(1, dtype=np.int32))
    assert len(p) == 1
    assert int(p.hits[0]) == 0
