"""Have the untiered serving programs changed? Lowers the decide programs a
peer WITHOUT a shadow tier launches (compact wire and full width, token and
mixed math, the scatter and the sparse write, four pads) and the untiered
merge, over a 1 GiB table's shape, and prints one digest of each program's
StableHLO text (which carries no source location, so a moved line does not
show, as it does in the compile cache's key).

    PYTHONPATH=<tree A> JAX_PLATFORMS=cpu python ci/hlo_tieroff.py > a.txt
    PYTHONPATH=<tree B> JAX_PLATFORMS=cpu python ci/hlo_tieroff.py > b.txt
    diff a.txt b.txt

Equal output: every one of the 36 programs is the same program in both
trees. Needs no chip and allocates nothing. (PR 42: parent 28b3b5e against
the change, equal.)"""
import hashlib, sys
import jax, jax.numpy as jnp
import gubernator_tpu
from gubernator_tpu.ops import kernel2, wire as wire_mod
from gubernator_tpu.ops.layout import FULL
from gubernator_tpu.ops.table2 import Table2, ROW, K
NB = 16_777_216 // K
spec = lambda *shape, dtype=jnp.int32: jax.ShapeDtypeStruct(shape, dtype)
table = Table2(rows=spec(NB, ROW), layout=FULL)
out = {}
for pad in (16, 1024, 4096, 16384):
    for math in ("token", "mixed"):
        for write in ("xla", "sparse"):
            out[f"wire {math} {write} {pad}"] = wire_mod.decide2_wire_cols.lower(
                table, spec(wire_mod.WIRE_LANES, pad + 1), write=write, math=math, cascade=False, evictees=False)
            out[f"cols {math} {write} {pad}"] = kernel2.decide2_packed_cols.lower(
                table, spec(12, pad, dtype=jnp.int64), write=write, math=math, cascade=False, evictees=False)
    out[f"merge2 {pad}"] = kernel2.merge2.lower(
        table, spec(pad, dtype=jnp.int64), spec(pad, 16), spec(pad, dtype=jnp.int64), spec(pad, dtype=jnp.bool_), write="xla")
for k, v in sorted(out.items()):
    print(k, hashlib.sha256(v.as_text().encode()).hexdigest()[:16])
