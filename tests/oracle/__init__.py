"""v1 decision kernel, demoted to a differential test oracle.

This was the round-1 production kernel (15 f32-carrier plane scatters); the
round-2 packed-row kernel (gubernator_tpu/ops/kernel2.py) replaced it on every
production path after real-TPU measurements (exp/README.md, exp_mem*, ~4x faster).
It is kept here because the reference-semantics suites were originally
validated against it, making it an independent implementation to diff v2
against on randomized traffic (tests/test_kernel2.py).
"""

from tests.oracle.kernel_v1 import decide as decide_v1
from tests.oracle.table_v1 import new_table as new_table_v1


def v1_engine(capacity: int, **kw):
    """A LocalEngine running the v1 oracle kernel."""
    from gubernator_tpu.ops.engine import LocalEngine

    return LocalEngine(
        capacity=capacity,
        decide_fn=decide_v1,
        table=new_table_v1(capacity),
        **kw,
    )
