"""Device-side request routing: ownership exchange as an ICI all_to_all.

The host-routed path (sharded.py `_stage`) sorts rows by owning shard on the
host and scatters them into a (D, b_local) grid — O(n) host work (argsort +
grid scatter) on every dispatch's critical path, run by a single Python
process feeding the whole mesh. That is fine on one host, but on a real
multi-host slice each host only feeds its local devices, and per-dispatch
host routing becomes the scaling bottleneck the r3 review flagged.

This module moves routing ONTO the mesh, MoE-dispatch style (the same
capacity-factor pattern expert-parallel layers use — see PAPERS.md; the
scaling-book recipe: annotate, exchange, let ICI do the work):

 1. the host ships arrival-order rows, zero routing work: the packed (12, n)
    columns reshape into a (D, 12, c) grid (row i → device i//c);
 2. each device computes owners for its c rows (the same high-bits hash as
    `mesh.shard_of`), sorts locally, and GATHERS rows into a (D, C, 12) send
    buffer — C is the per-(src,dst) capacity, mean + 5σ of the multinomial
    per-pair count; rows past a pair's capacity are marked dropped (claim
    retry re-dispatches them, the MoE "token dropping" analog);
 3. ONE exchange delivers every row to its owning device over the
    interconnect (the reference's N×N gRPC forwarding mesh, peer_client.go,
    collapsed into one `lax.all_to_all` over the shard axes);
 4. the owner runs the decision kernel on its received (D·C) rows;
 5. a second all_to_all returns responses to each row's arrival device,
    which un-sorts them to arrival order.

Output layout matches the host-routed path: (D, c+2, 4) per device — c
response rows (kernel2.pack_outputs flags) then the 2 stats rows — so the
engine decodes both paths with the same machinery and ONE fetch.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from gubernator_tpu.ops.kernel2 import (
    FLAG_DROPPED,
    FLAG_MEMBER,
    FLAG_UNPROCESSED,
    decide2_packed_cols_impl,
    decide2_packed_dedup_impl,
    dedup_packed_cols,
)
from gubernator_tpu.ops.engine import default_write_mode
from gubernator_tpu.ops.table2 import Table2
from gubernator_tpu.parallel.mesh import shard_axes, shard_of, shard_spec

i32 = jnp.int32
i64 = jnp.int64


def a2a_capacity_sigma() -> float:
    """Multinomial tail bound for the per-pair exchange capacity
    (GUBER_A2A_CAPACITY_SIGMA, default 5.0 standard deviations). Read
    host-side at trace time like the sparse-write knobs, so tuning runs can
    flip it between compiles without a restart. Lower values shrink the
    exchanged (D, C) buffers (less ICI traffic per dispatch) at the price of
    more capacity-overflow drops → engine retries; the overflow contract
    (FLAG_DROPPED|FLAG_UNPROCESSED → retry, never a lost request) is pinned
    by tests/test_a2a_capacity.py and does not change with the knob."""
    return float(os.environ.get("GUBER_A2A_CAPACITY_SIGMA", "5.0"))


def pair_capacity(c: int, D: int) -> int:
    """Per-(src,dst) row capacity: mean + σ·sqrt(mean) of the multinomial
    count of c hash-routed rows over D destinations (σ from
    a2a_capacity_sigma, default 5) plus a small-c slack of 8, rounded up to
    a power of two ≥ 8 for shape reuse. Overflow is dropped → engine retry
    (a perf knob, not correctness), exactly like the sweep's update-window
    bound (kernel2.sweep_geometry)."""
    mean = c / D
    cap = int(mean + a2a_capacity_sigma() * mean**0.5) + 8
    p = 8
    while p < cap:
        p *= 2
    return p


def exchange_traffic(c: int, D: int) -> "tuple[int, int, int]":
    """What one pass of `make_a2a_decide(mesh, c)` is traced with, from its
    shapes alone: (lanes the decide kernel runs on each device, row slots
    ONE chip sends plus receives over ICI, bytes of them). Each leg moves a
    (D, ·, C) buffer per chip, of which the chip's own block stays at home:
    D-1 blocks of C slots out and as many in — 12 int64 lanes a request
    slot on the way to the owners, 4 a response slot on the way back.
    Padding slots travel like live rows, so this is what the exchange
    moves, not what the traffic needed."""
    C = pair_capacity(c, D)
    slots = 2 * (D - 1) * C  # sent plus received, one leg
    return D * C, 2 * slots, slots * (12 + 4) * 8


def _exchange(block: jnp.ndarray, mesh: Mesh) -> jnp.ndarray:
    """Deliver per-destination blocks (leading axis = destination device)
    and return per-source blocks (leading axis = source device): one
    all_to_all over the shard axes. Called INSIDE a shard_map over `mesh`."""
    if int(mesh.devices.size) == 1:
        return block
    return jax.lax.all_to_all(
        block, shard_axes(mesh), split_axis=0, concat_axis=0
    )


def make_a2a_decide(
    mesh: Mesh, c: int, math: str = "mixed", write=None, dedup: bool = False,
    wire: bool = False,
):
    """Jitted all-shards decide with ON-DEVICE routing: (Table2[D,·],
    (D, 12, c) arrival-order grid, (D, c+2, 4) recycled egress buffer) →
    (Table2', (D, c+2, 4) packed outputs in arrival order). `c` rows per
    device; the per-pair exchange capacity derives from (c, mesh size) —
    pair_capacity is the single source of truth for the exchange geometry.

    All three inputs are DONATED: the table advances in place as before, the
    ingress grid's HBM is reclaimed at launch (the engine's staging pool
    re-puts into it next dispatch instead of growing the heap), and the
    egress buffer — a previous dispatch's already-fetched output, recycled
    by the engine (ShardedEngine._take_egress) — aliases this dispatch's
    output allocation, so steady-state serving allocates nothing.

    `dedup=True` aggregates duplicate keys IN-TRACE at both ends of the
    exchange (kernel2.dedup_packed_cols): once per source block before owner
    computation — local duplicates collapse to one exchanged row, so a
    Zipf-hot key costs ≤ 1 slot of each pair's capacity instead of flooding
    its owner's — and once on the owner over the received rows, merging the
    ≤ D cross-source carriers. Member rows answer from their carrier with
    FLAG_MEMBER, exactly like the host-grid dedup program.

    `wire=True` takes the compact 5-lane int32 ingress grid (trailing base
    column per device, ops/wire.py) and returns int32 compact outputs; the
    HOST boundary is what the narrow layout shrinks — the decode runs
    before the exchange, so the ICI legs still move the full 12-lane rows
    (ICI bandwidth is not the bottleneck the wire budget targets) and the
    exchange/dedup machinery below is shared byte-for-byte."""
    write = write or default_write_mode()
    D = int(mesh.devices.size)
    C = pair_capacity(c, D)

    def per_device(table: Table2, arr: jnp.ndarray, out_buf: jnp.ndarray):
        from gubernator_tpu.ops.wire import decode_wire_block, encode_wire_out

        table = jax.tree.map(lambda x: x[0], table)
        if wire:
            a, wire_base = decode_wire_block(arr[0])  # (12, c) i64
        else:
            a = arr[0]  # (12, c) i64, arrival order
        if dedup:
            # source-local merge: duplicate keys within this device's block
            # collapse onto their carrier; members deactivate (not sent)
            a, carrier0, member0 = dedup_packed_cols(a)
        fp = a[0]
        active = a[11] != 0
        # mesh.shard_of traces fine on jnp values — one ownership hash
        owner = jnp.where(active, shard_of(fp, D), D).astype(i32)
        idx = jnp.arange(c, dtype=i32)
        o_s, idx_s = jax.lax.sort((owner, idx), num_keys=1)
        gstart = jnp.searchsorted(o_s, o_s).astype(i32)
        rank = idx - gstart  # position within the destination's group
        ok_s = (rank < C) & (o_s < D)

        # send buffer by GATHER (scatters are slow on TPU): slot (d, j) takes
        # sorted row searchsorted(o_s, d) + j when j < count(d)
        d_iota = jnp.arange(D * C, dtype=i32) // C
        j_iota = jnp.arange(D * C, dtype=i32) % C
        g0 = jnp.searchsorted(o_s, d_iota).astype(i32)
        g1 = jnp.searchsorted(o_s, d_iota, side="right").astype(i32)
        src = g0 + j_iota
        valid = src < g1
        rows_sorted = a[:, idx_s]  # (12, c)
        send = jnp.where(
            valid[None, :], rows_sorted[:, jnp.clip(src, 0, c - 1)], i64(0)
        )  # (12, D*C); zeroed slots are inactive (fp=0, active=0)
        send3 = send.reshape(12, D, C).transpose(1, 0, 2)  # (D, 12, C)

        # ---- ICI: deliver rows to owners; leading axis src↔dst swaps
        recv = _exchange(send3, mesh)  # (D, 12, C), leading = source
        local = recv.transpose(1, 0, 2).reshape(12, D * C)

        if dedup:
            # owner-side merge: the same key can arrive from up to D source
            # carriers; aggregate them before the kernel (its unique-fp
            # contract) and fan the response back to every received row
            table, packed = decide2_packed_dedup_impl(
                table, local, write=write, math=math
            )
        else:
            table, packed = decide2_packed_cols_impl(
                table, local, write=write, math=math
            )
        resp = packed[: D * C].reshape(D, C, 4)
        stats_rows = packed[D * C :]  # (2, 4)

        # ---- ICI: responses ride back to each row's arrival device
        back = _exchange(resp, mesh).reshape(D * C, 4)

        # un-sort to arrival order: arrival row idx_s[p] sat in slot
        # o_s[p]*C + rank[p]
        slot_s = jnp.where(ok_s, o_s * C + rank, 0)
        _, slot_u, ok_u = jax.lax.sort(
            (idx_s, slot_s, ok_s.astype(i32)), num_keys=1
        )
        out = back[slot_u]  # (c, 4)
        sent = ok_u == 1
        # capacity-overflow rows: dropped + unprocessed flags — the engine's
        # claim-retry path re-dispatches them AND counts their hit/miss
        # outcome there (they appear in no kernel stats row)
        drop_flags = jnp.where(
            active, i64(FLAG_DROPPED | FLAG_UNPROCESSED), i64(0)
        )
        out = jnp.where(sent[:, None], out, i64(0))
        out = out.at[:, 3].set(jnp.where(sent, out[:, 3], drop_flags))
        if dedup:
            # source-local members were never exchanged: they answer from
            # their carrier's (aggregate) response. A capacity-dropped
            # carrier hands its members the drop flags too, so the engine's
            # retry re-dispatches the whole group and re-aggregates it.
            fan = out[carrier0]
            fan = fan.at[:, 3].set(fan[:, 3] | i64(FLAG_MEMBER))
            out = jnp.where(member0[:, None], fan, out)

        packed_out = jnp.concatenate([out, stats_rows], axis=0)
        if wire:
            packed_out = encode_wire_out(packed_out, wire_base)
        expand = lambda t: jax.tree.map(lambda x: x[None], t)
        return expand(table), packed_out[None]

    spec = shard_spec(mesh)
    fn = jax.shard_map(
        per_device, mesh=mesh, in_specs=(spec, spec, spec),
        # check_vma=False: the Pallas sweep's out_shape carries no vma
        # annotation, which the checker (jax>=0.9) rejects inside shard_map
        out_specs=(spec, spec), check_vma=False
    )
    # keep_unused: out_buf exists only to donate its buffer into the
    # same-shape output allocation (XLA aliases donated inputs to outputs
    # with matching shape/dtype); jit would otherwise prune the unused arg
    # and drop the aliasing with it. Staging donation is TPU-only
    # (sharded._staging_donate): XLA:CPU zero-copies host numpy buffers and
    # donating memory it doesn't own corrupts the process.
    from gubernator_tpu.parallel.sharded import _staging_donate

    return jax.jit(fn, donate_argnums=_staging_donate(), keep_unused=True)
