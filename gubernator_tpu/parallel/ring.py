"""Ownership exchange over the mesh: collective vs hand-rolled ring.

`a2a.make_a2a_decide` needs one primitive: every device holds a (D, …)
send buffer whose block d is destined for device d; deliver each block and
hand back a (D, …) recv buffer whose block s came from device s. The seed
paid ONE monolithic `lax.all_to_all` per direction — correct, but opaque to
XLA's scheduler: the whole exchange serializes before any owner-side work
can start, and on multi-host meshes the single collective's cost is set by
the slowest (DCN) edge.

This module adds a hand-rolled RING schedule for the same primitive
(GUBER_A2A_IMPL=ring|collective|auto):

* hop k (k = 1..D-1): device d sends block (d+k) mod D directly to device
  (d+k) mod D and receives block from (d-k) mod D — after D-1 hops every
  block has moved exactly once, and the recv layout is byte-identical to
  `all_to_all(split_axis=0, concat_axis=0)` by construction;
* hops are DOUBLE-BUFFERED: hop k+1's transfer starts before hop k's
  completion wait, so transfer (k+1) overlaps the receive-side merge of
  hop k instead of the hops serializing end-to-end.

Two lowerings share that schedule:

* **TPU** — a Pallas kernel (`_ring_pallas`): per-hop
  `pltpu.make_async_remote_copy` with two send/recv DMA-semaphore slots
  alternating per hop parity (the remote-DMA pattern of the jax Pallas TPU
  distributed-programming recipe). The send buffer stays in HBM
  (memory_space ANY); the DMA engines move blocks while the core is free.
  **Refused by the compiler as written** (jax 0.9.0 / libtpu 0.0.34, TPU
  v5 lite, PR 21): the kernel is traced under x64 over int64 blocks, and
  the Pallas TPU lowering recurses without end converting its 64-bit
  index arithmetic (`RecursionError` in `_convert_element_type_lowering_
  rule`); XLA's x64 rewriter also has no rule for a pallas_call with s64
  operands. Selecting it raises that error; ROADMAP S3/D1 decides whether
  it is repaired (32-bit views of the blocks, i32 scalars) or deleted.
* **CPU / parity oracle** — per-hop `lax.ppermute` shifts
  (`_ring_shifts`): the same hop decomposition expressed in XLA
  collectives, runnable on the simulated CPU meshes, byte-identical to
  the Pallas schedule AND to the all_to_all oracle. This is the lowering
  the parity suites (tests/test_ring_exchange.py, ci mesh_smoke) pin.

`GUBER_A2A_IMPL=auto` (default) is the collective everywhere: it is the
one lowering that compiles on the chip. `ring` stays selectable — the
ppermute schedule on CPU meshes (the parity suites), the refused Pallas
kernel on TPU.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from gubernator_tpu.parallel.mesh import (
    devices_per_host,
    mesh_hosts,
    shard_axes,
)

A2A_IMPLS = ("auto", "ring", "collective")


def a2a_impl(override: "str | None" = None) -> str:
    """Resolve the exchange implementation: explicit override, then
    GUBER_A2A_IMPL, then auto (= collective; the ring's TPU kernel does not
    compile — module docstring). Read at trace time like the sparse-write
    knobs — flipping the env re-selects on the next compile, no restart."""
    impl = override or os.environ.get("GUBER_A2A_IMPL", "auto")
    if impl not in A2A_IMPLS:
        raise ValueError(
            f"GUBER_A2A_IMPL must be one of {A2A_IMPLS}, got {impl!r}"
        )
    return "collective" if impl == "auto" else impl


def exchange(block: jnp.ndarray, mesh: Mesh, impl: str) -> jnp.ndarray:
    """Deliver per-destination blocks (leading axis = destination device)
    and return per-source blocks (leading axis = source device). Must be
    called INSIDE a shard_map over `mesh`'s axes. The recv layout is
    identical for every impl — `impl` is a schedule choice, never a
    semantics one."""
    D = int(mesh.devices.size)
    if D == 1 or impl == "collective":
        if D == 1:
            return block
        return jax.lax.all_to_all(
            block, shard_axes(mesh), split_axis=0, concat_axis=0
        )
    if impl != "ring":
        raise ValueError(f"unknown exchange impl {impl!r}")
    if jax.default_backend() == "tpu":
        return _ring_pallas(block, mesh)
    return _ring_shifts(block, shard_axes(mesh), D)


# ------------------------------------------------ ring: portable lowering


def _ring_shifts(
    block: jnp.ndarray, axes, D: int, hops: "int | None" = None
) -> jnp.ndarray:
    """The ring schedule in XLA collectives: hop k is one shift-k ppermute
    moving each device's block (me+k) directly to its owner. XLA schedules
    hop k+1's permute concurrently with hop k's recv-buffer update (the
    dynamic_update_slice below) — the collective-level rendering of the
    Pallas kernel's start-before-wait. `hops` truncates the loop (bench
    probes time k-hop prefixes to expose per-hop cost); full exchanges use
    hops=None = D-1."""
    me = jax.lax.axis_index(axes)
    own = jax.lax.dynamic_index_in_dim(block, me, axis=0, keepdims=True)
    out = jax.lax.dynamic_update_slice_in_dim(
        jnp.zeros_like(block), own, me, axis=0
    )
    n_hops = D - 1 if hops is None else min(hops, D - 1)
    for k in range(1, n_hops + 1):
        # my block for the device k steps ahead…
        blk = jax.lax.dynamic_index_in_dim(
            block, (me + k) % D, axis=0, keepdims=False
        )
        # …rides the shift-k permutation; the block landing here left
        # (me - k) mod D, which addressed it to me
        got = jax.lax.ppermute(
            blk, axes, perm=[(i, (i + k) % D) for i in range(D)]
        )
        out = jax.lax.dynamic_update_slice_in_dim(
            out, got[None], (me - k) % D, axis=0
        )
    return out


# ------------------------------------------------ ring: TPU Pallas lowering


def _ring_kernel(in_ref, out_ref, local_sem, send_sem, recv_sem, *, D, axes, dl):
    """Per-device body: D-1 remote-DMA hops, two semaphore slots alternating
    per hop parity so hop k+1's DMA starts before hop k's wait (hop k+2
    cannot start before hop k completed — its slot is still armed — which
    is exactly the depth-2 pipeline the staging ring already assumes)."""
    from jax.experimental.pallas import tpu as pltpu

    me = _linear_axis_index(axes, dl)

    def coords(t):
        # device_id as mesh coordinates, matching the mesh's axis order
        if isinstance(axes, tuple):
            return (t // dl, t % dl)
        return (t,)

    def rdma(k):
        t = (me + k) % D
        return pltpu.make_async_remote_copy(
            src_ref=in_ref.at[t],
            # slot index on the RECEIVER is the sender's id: device t files
            # my block under out[me], the all_to_all source-major layout
            dst_ref=out_ref.at[me],
            send_sem=send_sem.at[(k - 1) % 2],
            recv_sem=recv_sem.at[(k - 1) % 2],
            device_id=coords(t),
            device_id_type=pltpu.DeviceIdType.MESH,
        )

    # own block never crosses the wire: local async copy, overlapped with
    # every hop, waited last
    local = pltpu.make_async_copy(in_ref.at[me], out_ref.at[me], local_sem)
    local.start()
    if D > 1:
        rdma(1).start()
        for k in range(1, D):
            if k + 1 < D:
                rdma(k + 1).start()  # double-buffer: next hop in flight…
            rdma(k).wait()  # …while this hop's arrival completes
    local.wait()


def _linear_axis_index(axes, dl: int):
    if isinstance(axes, tuple):
        host, dev = axes
        return jax.lax.axis_index(host) * dl + jax.lax.axis_index(dev)
    return jax.lax.axis_index(axes)


def _ring_pallas(block: jnp.ndarray, mesh: Mesh) -> jnp.ndarray:
    """pl.pallas_call wrapper for the ring kernel: send/recv buffers live in
    HBM (memory space ANY — the DMA engines address them directly), two DMA
    semaphores per direction in scratch. TPU backends only; the portable
    `_ring_shifts` lowering carries the identical schedule elsewhere."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    D = int(mesh.devices.size)
    kernel = functools.partial(
        _ring_kernel, D=D, axes=shard_axes(mesh), dl=devices_per_host(mesh)
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0,
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=(
            [pltpu.SemaphoreType.DMA]  # local-copy completion
            + [pltpu.SemaphoreType.DMA((2,))] * 2  # send/recv, 2 slots each
        ),
    )
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(block.shape, block.dtype),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            has_side_effects=True, collective_id=0
        ),
    )(block)


# ------------------------------------------------------- bench/exp probes


def make_exchange_probe(
    mesh: Mesh,
    block_shape: tuple,
    impl: str,
    hops: "int | None" = None,
    dtype=jnp.int32,
):
    """Jitted exchange-only step for the pod-scaling bench and the MULTICHIP
    dryrun: (D, *block_shape) sharded array → exchanged array. For the ring
    impl `hops` truncates the schedule (hops=1, 2, … expose the marginal
    per-hop cost — the "per-hop exchange ms" column); the collective impl
    ignores `hops` (it has no hop structure to truncate). The probe moves
    the same bytes as a real a2a dispatch of that geometry, so its wall
    time is the exchange leg of the stage split."""
    from gubernator_tpu.parallel.mesh import shard_spec

    D = int(mesh.devices.size)
    axes = shard_axes(mesh)

    def per_device(x):
        x = x[0]
        if D == 1:
            out = x
        elif impl == "collective":
            out = jax.lax.all_to_all(x, axes, split_axis=0, concat_axis=0)
        elif jax.default_backend() == "tpu" and hops is None:
            out = _ring_pallas(x, mesh)
        else:
            out = _ring_shifts(x, axes, D, hops=hops)
        return out[None]

    spec = shard_spec(mesh)
    fn = jax.shard_map(
        per_device, mesh=mesh, in_specs=(spec,), out_specs=spec,
        check_vma=False,
    )
    return jax.jit(fn)
