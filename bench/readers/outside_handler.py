"""What an RPC spends outside the program's raw handler: the client's mean
latency less the handler's mean time, over the same RPCs. The client's side
is every answered RPC of the stretch (warm-up and window: the ledger), timed
from the moment it was sent, not from when it was due, so the generator's
own lateness is not in it; the handler's side is the growth of
`gubernator_tpu_stage_duration{stage}` between the two `/metrics` scrapes
that bracket the same stretch. What is left is gRPC on both sides, the
accept path, and the server's event loop before the handler runs and after
it returns. None where the program has no such stage. params: stage.
Unit: ms."""

import numpy as np


def read(ctx, stage):
    before, after, led = ctx.get("stages_before"), ctx.get("stages_after"), ctx.get("ledger")
    if before is None or after is None or led is None:
        return None
    s0, n0 = before.get(stage, (0.0, 0.0))
    s1, n1 = after.get(stage, (0.0, 0.0))
    if n1 - n0 <= 0:
        return None
    _due, sent, done, ok, _items = led.arrays()
    answered = ok & ~np.isnan(sent)
    if not answered.any():
        return None
    client_ms = 1e3 * float(np.mean((done - sent)[answered]))
    return client_ms - 1e3 * (s1 - s0) / (n1 - n0)
