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

The fused front door (ops/engine.prepare_check_wire) takes pass 0 — occurrence
0 of every key — from the lanes the parser already packed, and plans only the
later copies here, with `max_exact` one lower, so that the exact passes and
the aggregate hold the occurrences they would hold in a plan of the whole
chunk.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import List

import numpy as np

from gubernator_tpu.ops.batch import HostBatch


@dataclass
class Pass:
    rows: np.ndarray  # original row indices whose response comes from this pass
    batch: HostBatch
    # For the aggregated final pass, responses fan back out: member_rows[i]
    # lists every original row sharing batch row i's response.
    member_rows: List[np.ndarray]


def _subset(b: HostBatch, rows: np.ndarray) -> HostBatch:
    return HostBatch(*[f[rows] for f in b])


def single_pass(b: HostBatch) -> List[Pass]:
    """O(1) plan for engines that aggregate duplicate keys IN-TRACE
    (kernel2.dedup_packed_cols, ShardedEngine dedup="device"): one pass, the
    raw batch, no host group-by. The np.unique sweep below is the host-side
    cost the mesh path eliminates — on a 131K-row dispatch the sort alone is
    milliseconds of single-process work while every device idles. Member
    fan-out happens on-device too (kernel2.fanout_packed), so member_rows
    stays empty and each row comes back with its own (aggregate) response."""
    act = np.nonzero(b.active)[0]
    if act.size == b.fp.shape[0]:
        return [Pass(rows=act, batch=b, member_rows=[])]
    return [Pass(rows=act, batch=_subset(b, act), member_rows=[])]


def plan_passes(b: HostBatch, max_exact: int = 8) -> List[Pass]:
    """Split a packed batch into unique-fingerprint passes. Rows with
    active=False (padding or per-request validation errors) are skipped."""
    act = np.nonzero(b.active)[0]
    fp = b.fp[act]
    uniq, inv, counts = np.unique(fp, return_inverse=True, return_counts=True)
    if counts.max(initial=0) <= 1:
        if act.size == b.fp.shape[0]:
            return [Pass(rows=act, batch=b, member_rows=[])]
        return [Pass(rows=act, batch=_subset(b, act), member_rows=[])]

    order = np.argsort(inv, kind="stable")
    sorted_inv = inv[order]
    group_start = np.searchsorted(sorted_inv, sorted_inv)
    occ = np.empty(act.size, dtype=np.int64)
    occ[order] = np.arange(act.size) - group_start

    passes: List[Pass] = []
    for r in range(min(int(occ.max()) + 1, max_exact - 1)):
        rows = act[np.nonzero(occ == r)[0]]
        if rows.size == 0:
            break
        passes.append(Pass(rows=rows, batch=_subset(b, rows), member_rows=[]))

    tail_pos = np.nonzero(occ >= max_exact - 1)[0]
    if tail_pos.size:
        tail = act[tail_pos]
        # aggregation groups key on (fp, cascade level) — two LEVELS of one
        # cascade whose keys collide on a fingerprint carry different limit
        # configs and must not merge (kernel2.dedup_packed_cols applies the
        # same discriminator in-trace). `inv` indexes unique fps; pairing it
        # with the level keeps the group id dense enough for np.unique.
        tail_lvl = (b.behavior[tail].astype(np.int64) >> 8) & 0xFF
        tail_key = inv[tail_pos].astype(np.int64) * 256 + tail_lvl
        tuniq, tinv = np.unique(tail_key, return_inverse=True)
        # newest member of each group carries the config (clients send the full
        # config with every request; latest wins)
        last_rows = np.zeros(tuniq.size, dtype=np.int64)
        np.maximum.at(last_rows, tinv, tail)
        agg = _subset(b, last_rows)
        hits = np.zeros(tuniq.size, dtype=np.int64)
        np.add.at(hits, tinv, b.hits[tail])
        # Only RESET_REMAINING survives the merge (reference global.go:117-121);
        # OR-ing other flags would desynchronize the carrier row's pre-resolved
        # fields (e.g. Gregorian rate inputs).
        reset_bit = np.zeros(tuniq.size, dtype=np.int32)
        np.bitwise_or.at(reset_bit, tinv, b.behavior[tail] & 8)  # RESET_REMAINING
        agg = agg._replace(hits=hits, behavior=agg.behavior | reset_bit)
        member_rows = [tail[tinv == g] for g in range(tuniq.size)]
        passes.append(Pass(rows=last_rows, batch=agg, member_rows=member_rows))
    return passes
