"""Device-resident ring consumer: drain K published slots per XLA launch.

The request ring (service/ring.py) removed the per-batch *enqueue* cost
from the serving plane, but its host issue loop still paid one full XLA
launch round-trip per published slot — steady-state serving throughput was
launch-bound, not kernel-bound. This module moves the CONSUME side onto the
device in two tiers:

**Tier A — fused multi-slot drain (this file's `drain_ring`, live on every
backend).** The whole ring of compact wire-grid slots plus the
`seq_in`/`seq_out` fence words stays device-resident (`DeviceRing`), and
one jitted bounded `lax.while_loop` launch reads the ingress fences
IN-TRACE, decodes and decides up to `k` published slots through the
existing `decide2_wire_cols` walk (the donated table threaded through the
carry), writes each slot's compact egress bank, and publishes `seq_out` —
exactly the pattern ops/loop.py proved for the bench harness, applied to
the serving path. The launch round-trip amortizes k× and the per-launch
cost is ∝ published work: an unpublished slot is a fence compare and a
no-op branch (the loop exits). `k` and the start ticket are *traced*
scalars, so one compile per (ring geometry × math mode) serves every
group size.

**Tier B — persistent issue kernel (`fence_claim`, staged for the TPU
run).** A Pallas kernel that polls `seq_in` and claims published slots
with the async-copy/DMA-semaphore pattern — the device-side half of the
protocol that makes steady state pay ZERO XLA launches (the kernel never
exits; the host only stages grids and polls egress fences). The CPU build
validates the fence protocol in interpreter mode
(tests/test_ring_drain.py) against `fence_claim_ref`; the service keeps
`GUBER_RING_ISSUE=persistent` on the fused drain launches until the
device run validates the resident loop (watchdog re-launch on preemption
is the service's job — service/ring.py counts `watchdog_relaunches`).

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


def egress_rows(width: int, evictees: bool) -> int:
    """Rows of one slot's compact egress bank: the (W+2, 4) encode_wire_out
    image, or (5W+2, 4) with the raw evictee sidecar rows interleaved
    (kernel2.attach_evictees_wire — static per engine config)."""
    return 5 * width + 2 if evictees else width + 2


def _drain_impl(
    table, grids, seq_in, seq_out, start, k, *,
    k_max, write, math, cascade, probe, evictees,
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
            probe=probe, evictees=evictees,
        )
        # dense egress bank indexed by drain POSITION, not slot: one fetch
        # covers the whole launch. (The true device ring / persistent tier
        # writes per-slot banks the host polls individually; the dense
        # bank is the pipelined-fetch shape the CPU-provable tier wants.)
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
    static_argnames=("k_max", "write", "math", "cascade", "probe",
                     "evictees"),
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
            cascade=cascade, probe=engine.probe_mode,
            evictees=bool(engine._evictees),
        )
        engine.table = table
        return bank, n


# --------------------------------------------------------------------------
# Tier B: persistent issue kernel (staged for the TPU run)
# --------------------------------------------------------------------------


def _fence_claim_kernel(seq_in_ref, _seq_out_in, grids_ref, ctl_ref,
                        seq_out_ref, bank_ref, n_ref, sem):
    """Pallas fence-claim loop: the persistent issue kernel's inner step.

    Walks tickets from ctl[0], and for each CONTIGUOUSLY published slot
    (seq_in[t%S] == t+1 — a gap stops the claim, preserving strict ticket
    order) async-copies the slot's wire grid into the claim bank and bumps
    the egress-side fence, up to ctl[1] claims. This is the Pallas
    async-copy/DMA-semaphore recipe applied to slot claiming; the resident
    production loop wraps this step in an outer poll that never exits.
    Fence words are int32 here (tickets wrap at 2^31 — years of uptime at
    serving rates; the host remaps before wrap)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    start = ctl_ref[0]
    k = ctl_ref[1]
    S = seq_in_ref.shape[0]

    def body(i, n):
        t = start + i
        # i32(S): a bare python int promotes to i64 under jax_enable_x64,
        # and lax.rem refuses mixed-width operands
        slot = jax.lax.rem(t, i32(S))
        published = seq_in_ref[slot] == t + 1
        live = (i < k) & (i == n) & published

        @pl.when(live)
        def _claim():
            cp = pltpu.make_async_copy(
                grids_ref.at[slot], bank_ref.at[i], sem
            )
            cp.start()
            cp.wait()
            # egress fence AFTER the DMA completed — the claim ordering
            # the host's result poll relies on
            seq_out_ref[slot] = t + 1

        return n + live.astype(i32)

    n = jax.lax.fori_loop(0, bank_ref.shape[0], body, i32(0))
    n_ref[0] = n


def make_fence_claim(slots: int, width: int, k_max: int, *,
                     interpret: bool = False):
    """Build the fence-claim pallas_call for one ring geometry. Returns
    fn(seq_in i32 (S,), seq_out i32 (S,), grids i32 (S, 5, W+1),
    ctl i32 (2,)=[start, k]) → (seq_out', bank (k_max, 5, W+1), n (1,)).
    `interpret=True` runs the CPU interpreter — the parity surface
    tests/test_ring_drain.py pins against `fence_claim_ref`."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    out_shape = (
        jax.ShapeDtypeStruct((slots,), jnp.int32),
        jax.ShapeDtypeStruct((k_max, WIRE_LANES, width + 1), jnp.int32),
        jax.ShapeDtypeStruct((1,), jnp.int32),
    )
    return pl.pallas_call(
        _fence_claim_kernel,
        out_shape=out_shape,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # seq_in
            pl.BlockSpec(memory_space=pltpu.SMEM),  # seq_out (aliased)
            pl.BlockSpec(memory_space=pl.ANY),      # grids (HBM)
            pl.BlockSpec(memory_space=pltpu.SMEM),  # ctl [start, k]
        ],
        out_specs=(
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ),
        scratch_shapes=[pltpu.SemaphoreType.DMA(())],
        input_output_aliases={1: 0},
        interpret=interpret,
    )


def fence_claim_ref(seq_in: np.ndarray, seq_out: np.ndarray,
                    grids: np.ndarray, start: int, k: int):
    """Numpy reference of the fence-claim protocol — the oracle the
    interpreter-mode kernel test compares against. Claims contiguously
    published tickets from `start` (a gap or k stops it), copies each
    claimed slot's grid, bumps its egress fence."""
    S = seq_in.shape[0]
    seq_out = seq_out.copy()
    claimed = []
    n = 0
    while n < k:
        t = start + n
        slot = t % S
        if int(seq_in[slot]) != t + 1:
            break
        claimed.append(grids[slot].copy())
        seq_out[slot] = t + 1
        n += 1
    bank = (
        np.stack(claimed)
        if claimed
        else np.zeros((0,) + grids.shape[1:], dtype=grids.dtype)
    )
    return n, bank, seq_out
