"""Batch pass planning: same-key sequential semantics on a parallel device.

The reference serializes same-key requests through a per-key worker goroutine
(workers.go:185-189), so two hits on one key within a batch window apply one
after the other. The decision kernel instead requires unique fingerprints per
dispatch. The planner restores sequential semantics by splitting a batch into
passes:

* occurrence 0 of every key → pass 0, occurrence 1 → pass 1, … (exact
  sequential semantics for up to `max_exact` occurrences);
* occurrences ≥ max_exact-1 for a key are *aggregated* into the final pass —
  hits summed, RESET_REMAINING OR-ed, config taken from the newest request, and
  the aggregate's response shared by all members. This mirrors the reference's own
  hot-key aggregation on the GLOBAL async path (global.go:109-123: sum Hits,
  OR RESET_REMAINING) and bounds worst-case passes under Zipf-skewed traffic.

For the common all-unique batch this is a single pass with zero copies.

A plan is one stable sort of the fingerprints (`occurrence_rank`), the rows
of each rank (`split_rows`) and the aggregate's groups as runs of the sorted
tail (`runs`). `plan_passes` builds a `HostBatch` for every pass from them;
the fused front door (ops/engine.prepare_check_wire) takes the same rows as
gathers of the lanes the parser already packed (pass 0 is its grid) and
builds no batch at all.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional

import numpy as np

from gubernator_tpu.ops.batch import HostBatch


@dataclass
class Pass:
    rows: np.ndarray  # original row indices whose response comes from this pass
    batch: HostBatch
    # The aggregated final pass fans its responses back out: `members` lists,
    # group after group, every original row that shares a batch row's
    # response, and `member_counts[i]` how many share batch row i's. None on
    # an exact pass.
    members: Optional[np.ndarray] = None
    member_counts: Optional[np.ndarray] = None


def _subset(b: HostBatch, rows: np.ndarray) -> HostBatch:
    return HostBatch(*[f[rows] for f in b])


def single_pass(b: HostBatch) -> List[Pass]:
    """O(1) plan for engines that aggregate duplicate keys IN-TRACE
    (kernel2.dedup_packed_cols, ShardedEngine dedup="device"): one pass, the
    raw batch, no host group-by. The sort below is the host-side cost the
    mesh path eliminates — on a 131K-row dispatch it alone is milliseconds
    of single-process work while every device idles. Member fan-out happens
    on-device too (kernel2.fanout_packed), so `members` stays None and each
    row comes back with its own (aggregate) response."""
    act = np.nonzero(b.active)[0]
    if act.size == b.fp.shape[0]:
        return [Pass(rows=act, batch=b)]
    return [Pass(rows=act, batch=_subset(b, act))]


def occurrence_rank(fps: np.ndarray):
    """(order, rank): the stable argsort of `fps` — a key's copies stand
    together in arrival order — and each row's occurrence index among the
    rows of its fingerprint (0 for the first, 1 for the second, …). `rank`
    is None when no fingerprint repeats."""
    order = np.argsort(fps, kind="stable")
    s = fps[order]
    new = np.ones(s.shape[0], dtype=bool)
    new[1:] = s[1:] != s[:-1]
    if new.all():
        return order, None
    idx = np.arange(s.shape[0])
    rank = np.empty(s.shape[0], dtype=np.int64)
    rank[order] = idx - np.maximum.accumulate(np.where(new, idx, 0))
    return order, rank


def split_rows(order: np.ndarray, rank: np.ndarray, max_exact: int):
    """(exact, tail) of an `occurrence_rank`: the rows of rank r, in arrival
    order, for each r below max_exact−1 that occurs; and the rows of every
    rank from max_exact−1 up — the aggregate's members — key after key, in
    arrival order within a key (None when no key has that many copies)."""
    top = int(rank.max())
    exact = [np.nonzero(rank == r)[0] for r in range(min(top + 1, max_exact - 1))]
    if top < max_exact - 1:
        return exact, None
    return exact, order[rank[order] >= max_exact - 1]


def runs(*keys: np.ndarray):
    """(starts, counts) of the runs of rows over which every one of `keys`
    (parallel columns, sorted so that equal rows stand together) is equal."""
    n = keys[0].shape[0]
    new = np.zeros(n, dtype=bool)
    new[0] = True
    for k in keys:
        new[1:] |= k[1:] != k[:-1]
    starts = np.nonzero(new)[0]
    return starts, np.diff(starts, append=n)


def aggregate_pass(b: HostBatch, tail, starts, counts) -> Pass:
    """The aggregated final pass over `tail`, rows of `b` in the groups
    `runs` found: the newest member of each group carries the config
    (clients send the full config with every request; latest wins) with the
    group's summed hits. Only RESET_REMAINING survives the merge (reference
    global.go:117-121); OR-ing other flags would desynchronize the carrier
    row's pre-resolved fields (e.g. Gregorian rate inputs)."""
    last = tail[starts + counts - 1]
    agg = _subset(b, last)
    reset = np.bitwise_or.reduceat(b.behavior[tail] & 8, starts)
    agg = agg._replace(
        hits=np.add.reduceat(b.hits[tail], starts), behavior=agg.behavior | reset
    )
    return Pass(rows=last, batch=agg, members=tail, member_counts=counts)


def plan_passes(b: HostBatch, max_exact: int = 8) -> List[Pass]:
    """Split a packed batch into unique-fingerprint passes. Rows with
    active=False (padding or per-request validation errors) are skipped."""
    act = np.nonzero(b.active)[0]
    fp = b.fp[act]
    order, rank = occurrence_rank(fp)
    if rank is None:
        if act.size == b.fp.shape[0]:
            return [Pass(rows=act, batch=b)]
        return [Pass(rows=act, batch=_subset(b, act))]

    exact, tail = split_rows(order, rank, max_exact)
    passes = [Pass(rows=rows, batch=_subset(b, rows)) for rows in map(act.take, exact)]
    if tail is not None:
        # aggregation groups key on (fp, cascade level) — two LEVELS of one
        # cascade whose keys collide on a fingerprint carry different limit
        # configs and must not merge (kernel2.dedup_packed_cols applies the
        # same discriminator in-trace)
        key, tail = fp[tail], act[tail]
        lvl = (b.behavior[tail] >> 8) & 0xFF
        if lvl.any():
            by_level = np.lexsort((lvl, key))  # stable: arrival order kept
            key, lvl, tail = key[by_level], lvl[by_level], tail[by_level]
        passes.append(aggregate_pass(b, tail, *runs(key, lvl)))
    return passes
