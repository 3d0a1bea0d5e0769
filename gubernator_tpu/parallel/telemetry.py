"""Sharded table telemetry: the per-device scan as one mesh collective.

Same fused statistics pass as ops/telemetry.py, run per shard under
shard_map so (a) each device streams only its own (NB, 128) table slice —
no cross-device gather of 100M-key state just to count it — and (b) the
per-device vectors come back stacked (D, VEC_LEN), which is what makes
shard *imbalance* observable: a Zipf-hot shard shows up as one row's live
count diverging long before its buckets start evicting live keys.

Every stats-vector entry is additive over disjoint row sets (ops/telemetry
layout contract), so the host sums the D rows for table-wide totals and
keeps column 0 (per-shard live counts) for the debug plane.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from jax.sharding import Mesh

from gubernator_tpu.ops.telemetry import (
    PendingScan,
    _scan_body,
    block_width,
)
from gubernator_tpu.ops.table2 import K
from gubernator_tpu.parallel.mesh import shard_spec


def make_sharded_scan(mesh: Mesh, n_buckets: int, layout=None):
    """Jitted all-shards telemetry step: (D, NB, ROW_layout) rows →
    (D, VEC_LEN) per-shard stats vectors. The table is NOT donated — the
    scan is a pure read racing nothing (it runs issued from the engine
    thread like every other table access)."""
    blk = block_width(n_buckets)

    def per_device(rows: jnp.ndarray, now: jnp.ndarray):
        return _scan_body(rows[0], now[0, 0], blk, layout)[None]

    spec = shard_spec(mesh)
    fn = jax.shard_map(
        per_device, mesh=mesh, in_specs=(spec, spec), out_specs=spec,
        check_vma=False,
    )
    return jax.jit(fn)


def sharded_scan_begin(engine, now_ms: int) -> PendingScan:
    """Launch the mesh telemetry scan over a ShardedEngine's table without
    fetching (engine-thread half; finish with ops.telemetry.finish_scan).
    The compiled step is cached on the engine — the geometry never changes
    between scans."""
    rows = engine.table.rows
    D, nb = int(rows.shape[0]), int(rows.shape[1])
    fn = getattr(engine, "_telemetry_fn", None)
    if fn is None or getattr(engine, "_telemetry_layout", None) is not (
        engine.table.layout
    ):
        fn = engine._telemetry_fn = make_sharded_scan(
            engine.mesh, nb, layout=engine.table.layout
        )
        engine._telemetry_layout = engine.table.layout
    now = jax.device_put(
        jnp.full((D, 1), now_ms, dtype=jnp.int64), engine._batch_sharding
    )
    vec = fn(rows, now)
    return PendingScan(
        vec, now_ms, capacity=D * nb * K, n_buckets=D * nb, per_shard=True
    )
