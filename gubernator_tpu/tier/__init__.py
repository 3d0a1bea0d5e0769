"""Hot-set tiering: the host-RAM shadow table behind the HBM hot set.

HBM is the capacity ceiling the ROADMAP's elastic-fleet item names ("1B
tracked keys per pod with HBM holding the hot ~10%"): the decide path's
state array must fit HBM, so today a table sized for the hot set silently
DISCARDS the displaced row's state on every live eviction — a permissive
re-grant the next time that key shows up. This package turns eviction
into a tiering event instead:

* **demote-on-evict** — the decide kernels return the evicted rows as a
  sidecar riding the response fetch (kernel2 `evictees=`)
  and the engine appends them to the shadow;
* **demote-on-idle** — a background sweep (tier/manager.py, telemetry
  cadence) pulls rows idle past GUBER_TIER_IDLE_MS out of HBM
  (table2.extract_idle_rows + tombstone) into the shadow;
* **fault-back** — host staging probes the shadow for the batch's
  fingerprints (exact dict hit, off the hot path for misses); hits are
  removed from the shadow and installed through the conservative merge
  (kernel2.merge2) BEFORE the decide dispatch, so a promoted stale row
  can only UNDER-grant — the same pinned conservatism as checkpoint
  replay, handoff, and region sync.

Capacity now scales with TRACKED keys (host RAM + optional spill file)
while decisions/s tracks the HOT set (HBM). Losing the shadow (no spill,
kill -9) degrades exactly to today's eviction behavior — state loss, and
over-admission bounded by the per-key limits — never worse.

See docs/tiering.md.
"""

from gubernator_tpu.tier.shadow import ROW_BYTES, ShadowTable  # noqa: F401
