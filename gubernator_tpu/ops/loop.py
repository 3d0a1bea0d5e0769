"""On-device dispatch loop: K kernel iterations in ONE XLA launch.

The benchmark's headline must measure chip compute, not the host. Every
host-visible op (launch, fetch) is a host sync whose cost varies with what
else the host is doing, so a host-timed loop of K separate dispatches
measures K launches and syncs on top of the kernel. The fix is structural:
run the K iterations *inside* one jitted `lax.fori_loop`, threading the
donated table through the carry, so a whole timed window costs exactly one
launch + one scalar fetch and the host cost amortizes to nothing. How large
that host cost is on a co-located host is not measured; kernel time from a
profiler trace (ROADMAP S1) may make this harness unnecessary.

The trip count `k` is a *traced* scalar (fori_loop lowers to a while loop),
so one compile serves every window length — the adaptive sizing in bench.py
can grow K until device time dominates host jitter without paying a
recompile per K.

This is a measurement harness for the same `decide2_impl` graph the serving
engine dispatches (ops/kernel2.py); it adds no semantics. The reference's
analog is the b.N loop of its Go benchmarks (benchmark_test.go:30-148) —
there the harness overhead is nanoseconds so the loop can live on the host;
here the loop must live on the device for the same number to mean anything.
"""

from __future__ import annotations

import functools
from typing import List, Tuple

import jax
import jax.numpy as jnp

from gubernator_tpu.ops.batch import ReqBatch
from gubernator_tpu.ops.kernel2 import decide2_impl
from gubernator_tpu.ops.table2 import Table2

i64 = jnp.int64


def stack_batches(batches: List[ReqBatch]) -> ReqBatch:
    """Stack N same-shape request batches along a new leading axis → one
    device-resident pytree the loop cycles through with a dynamic slice.
    (One stacked (N, B) buffer per column beats N live batch pytrees: the
    loop body's gather is a contiguous dynamic-slice, and there is exactly
    one host→device staging op per column.)"""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *batches)


@functools.partial(
    jax.jit, donate_argnums=(0,), static_argnames=("write", "math", "probe")
)
def decide_loop(
    table: Table2,
    stacked: ReqBatch,
    k: jnp.ndarray,
    *,
    write: str = "sweep",
    math: str = "mixed",
    probe: str = "xla",
) -> Tuple[Table2, jnp.ndarray]:
    """Run `k` decide2 dispatches on-device, cycling over the stacked
    batches; returns (table', [hits, misses, over, dropped] i64 totals).

    The totals are the proof of work: bench.py asserts
    hits + misses == k * active_rows before publishing any rate derived
    from this loop, so a wedged transport or a silently-skipped iteration
    can never masquerade as throughput.
    """
    n = stacked.fp.shape[0]

    def body(i, carry):
        table, acc = carry
        b = jax.tree.map(
            lambda x: jax.lax.dynamic_index_in_dim(x, i % n, keepdims=False),
            stacked,
        )
        table, _resp, stats = decide2_impl(
            table, b, write=write, math=math, probe=probe
        )
        acc = acc + jnp.stack(
            [stats.cache_hits, stats.cache_misses, stats.over_limit,
             stats.dropped]
        )
        return table, acc

    table, acc = jax.lax.fori_loop(
        0, k.astype(jnp.int32), body, (table, jnp.zeros((4,), dtype=i64))
    )
    return table, acc
