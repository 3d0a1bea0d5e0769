"""Device-resident ring consumer: drain K published slots per XLA launch.

The request ring (service/ring.py) removed the per-batch *enqueue* cost
from the serving plane, but its host issue loop still paid one full XLA
launch round-trip per published slot — steady-state serving throughput was
launch-bound, not kernel-bound. This module moves the CONSUME side onto the
device as a fused multi-slot drain (`drain_ring`, live on every backend).
The whole ring of compact wire-grid slots plus the `seq_in`/`seq_out`
fence words stays device-resident (`DeviceRing`), and one jitted bounded
`lax.while_loop` launch reads the ingress fences IN-TRACE, decodes and
decides up to `k` published slots through the existing `decide2_wire_cols`
walk (the donated table threaded through the carry), writes each slot's
compact egress bank, and publishes `seq_out`. The launch round-trip
amortizes k× and the per-launch cost is ∝ published work: an unpublished
slot is a fence compare and a no-op branch (the loop exits). `k` and the
start ticket are *traced* scalars, so one compile per (ring geometry × math
mode) serves every group size.

Threading contract: every `DeviceRing` mutation (slot staging, fence
publish, drain launch) happens on the ENGINE THREAD — the buffers are
donated through jitted in-place updates, and a second writer would race
the donation. The host mirrors in service/ring.py remain the submitters'
view; this module is the device's.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

from gubernator_tpu.ops.wire import WIRE_LANES, decide2_wire_cols_impl

i32 = jnp.int32
i64 = jnp.int64


def default_ring_issue() -> str:
    """Backend default for GUBER_RING_ISSUE: the fused drain on real TPU
    (launch round-trips are the cost it exists to amortize), the host
    issue loop on CPU builds (byte-parity oracle; per-launch overhead is
    microseconds there, and the host loop keeps the per-slot pad sizing)."""
    return "fused" if jax.default_backend() == "tpu" else "host"


def egress_rows(width: int, evictees) -> int:
    """Rows of one slot's compact egress bank: the (W+2, 4) encode_wire_out
    image, or (5W+2, 4) with the raw evictee sidecar rows interleaved
    (kernel2.attach_evictees_wire, `evictees=True`). A tiered engine's
    drain runs the hits-only program ("defer"), which carries none."""
    return 5 * width + 2 if evictees is True else width + 2


def _drain_impl(
    table, grids, seq_in, seq_out, start, k, *,
    k_max, write, math, cascade, evictees,
):
    """One fused drain launch: walk tickets from `start`, decide every
    published slot (≤ k ≤ k_max), publish egress fences. Returns
    (table', seq_out', bank, drained) where bank[i] is the i-th drained
    ticket's egress image and `drained` is the in-trace claim count — the
    host asserts it equals the group it published (fence-protocol proof,
    not a recovery path)."""
    S = grids.shape[0]
    E = egress_rows(grids.shape[2] - 1, evictees)
    start = jnp.asarray(start, dtype=i64)
    k = jnp.minimum(jnp.asarray(k, dtype=i64), i64(k_max))
    bank0 = jnp.zeros((k_max, E, 4), dtype=i32)

    def cond(carry):
        _table, _seq_out, _bank, t, n = carry
        # ingress fence, read in-trace: slot t%S must carry exactly
        # ticket t (fence word t+1 — never 0, so an unused slot can't
        # alias). An unpublished slot ends the drain: cost ∝ published
        # work, not slot count.
        return (n < k) & (seq_in[jax.lax.rem(t, S)] == t + 1)

    def body(carry):
        table, seq_out, bank, t, n = carry
        slot = jax.lax.rem(t, S)
        grid = jax.lax.dynamic_index_in_dim(grids, slot, 0, keepdims=False)
        table, out = decide2_wire_cols_impl(
            table, grid, write=write, math=math, cascade=cascade,
            evictees=evictees,
        )
        # dense egress bank indexed by drain POSITION, not slot: one fetch
        # covers the whole launch.
        bank = jax.lax.dynamic_update_index_in_dim(bank, out, n, 0)
        # egress fence AFTER the slot's outputs exist in the bank — same
        # store ordering the host finish loop keeps
        seq_out = seq_out.at[slot].set(t + 1)
        return table, seq_out, bank, t + 1, n + 1

    table, seq_out, bank, _t, n = jax.lax.while_loop(
        cond, body, (table, seq_out, bank0, start, i64(0))
    )
    return table, seq_out, bank, n


# table and seq_out are donated (in-place across launches); grids/seq_in
# are read-only residents the staging updates below replace. The bank is a
# FRESH output each launch — donating it would let launch j+1 reuse the
# buffer a fetch thread is still reading from launch j.
drain_ring = functools.partial(
    jax.jit, donate_argnums=(0, 3),
    static_argnames=("k_max", "write", "math", "cascade", "evictees"),
)(_drain_impl)


@functools.partial(jax.jit, donate_argnums=(0,))
def _store_slot(grids, grid, slot):
    """In-place slot refresh (donated): the emulation's stand-in for the
    host→HBM DMA into slot `slot`. `slot` is traced — one compile serves
    the whole ring."""
    return jax.lax.dynamic_update_index_in_dim(grids, grid, slot, 0)


@functools.partial(jax.jit, donate_argnums=(0,))
def _publish_fence(seq, slot, val):
    return seq.at[slot].set(val)


class DeviceRing:
    """The device-resident half of the request ring: S wire-grid slots of
    one FIXED width plus the seq_in/seq_out fence words, mutated only on
    the engine thread (donated buffers). Chunks wider than `width` keep
    riding the host per-slot path — the fixed width is what makes the
    drain graph a single compile (docs/latency.md "Launch budget")."""

    def __init__(self, slots: int, width: int, drain_k: int,
                 evictees: bool = False):
        if slots < 2 or drain_k < 1 or width < 1:
            raise ValueError("DeviceRing needs slots>=2, drain_k>=1, width>=1")
        self.slots = int(slots)
        self.width = int(width)
        self.drain_k = int(min(drain_k, slots))
        self.evictees = bool(evictees)
        self.grids = jnp.zeros(
            (self.slots, WIRE_LANES, self.width + 1), dtype=i32
        )
        self.seq_in = jnp.zeros((self.slots,), dtype=i64)
        self.seq_out = jnp.zeros((self.slots,), dtype=i64)

    def stage(self, slot: int, grid: np.ndarray, ticket: int) -> None:
        """ENGINE THREAD. Stage one slot's (5, width+1) grid and publish
        its ingress fence — STAGE before PUBLISH, the same store ordering
        the host mirror keeps (a device consumer polling seq_in must never
        observe the fence before the payload)."""
        self.grids = _store_slot(
            self.grids, jnp.asarray(grid, dtype=i32), np.int32(slot)
        )
        self.seq_in = _publish_fence(
            self.seq_in, np.int32(slot), np.int64(ticket + 1)
        )

    def drain(self, engine, start: int, k: int, math: str, cascade: bool):
        """ENGINE THREAD. One fused drain launch over tickets
        [start, start+k): threads the engine's donated table through the
        while_loop carry and advances the device egress fences. Returns
        (bank, drained) un-fetched device handles — the finish half
        materializes them on a fetch thread."""
        table, self.seq_out, bank, n = drain_ring(
            engine.table, self.grids, self.seq_in, self.seq_out,
            np.int64(start), np.int64(k),
            k_max=self.drain_k, write=engine.write_mode, math=math,
            # a drain is a pipelined launch: on a tiered table the
            # hits-only program, whose deferred rows the finish half hands
            # to the engine's miss path (ops/engine.LocalEngine)
            cascade=cascade, evictees="defer" if engine._evictees else False,
        )
        engine.table = table
        return bank, n
