"""Claim corners of the decide path, under the two Pallas write kernels.

`write="sparse"` and `write="sweep"` (kernel2._write_sparse / _write_sweep)
are what a TPU runs; `write="xla"` is the scatter every CPU mesh runs and the
reference here. Each scenario drives one table per write mode through the
same multi-step traffic and holds responses, stats AND raw table bytes
equal at every step: five algorithms, the mixed batch, the packed layouts,
bucket-full drops, eviction of live lanes by fresh keys, same-target dedup
(owner wins), expired-slot reclaim, negative-hit release on a missing key,
RESET/DRAIN, inactive padding, one bucket for the whole batch.

The tables are tiny (64–512 slots), so the sparse case pins the geometry
that makes the sparse grid real there: GUBER_WRITE_SPARSE_CROSSOVER=0 (no
fall-back to the sweep) and 8-row blocks (several dirty blocks, runs that
end on block boundaries). Both kernels run in the Pallas interpreter.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gubernator_tpu.ops.batch import ReqBatch
from gubernator_tpu.ops.kernel2 import decide2_impl, resolve_write
from gubernator_tpu.ops.layout import GCRA32, TOKEN32
from gubernator_tpu.ops.table2 import new_table2

NOW = 1_700_000_000_000

RESP_FIELDS = ("status", "limit", "remaining", "reset_time", "cache_hit",
               "dropped")

_decide = jax.jit(decide2_impl, static_argnames=("write", "math"))


def mkfp(rng, n, bucket_pool=None, pool_nb=64):
    """Unique fingerprints; `bucket_pool` concentrates them into that many
    hash buckets of a pool_nb-bucket table (collision pressure)."""
    if bucket_pool:
        base = rng.integers(1, pool_nb, size=bucket_pool, dtype=np.int64)
        fp = base[rng.integers(0, bucket_pool, size=2 * n)] + pool_nb * \
            rng.integers(1, 1 << 40, size=2 * n, dtype=np.int64)
    else:
        fp = rng.integers(1, 1 << 62, size=2 * n, dtype=np.int64)
    fp = np.unique(fp)
    while fp.shape[0] < n:
        fp = np.unique(np.concatenate(
            [fp, rng.integers(1, 1 << 62, size=n, dtype=np.int64)]
        ))
    fp = fp[:n]
    rng.shuffle(fp)
    return fp


def mkreq(rng, n, n_active=None, algos=(0,), hits=None, behavior=0,
          limit=100, dur=60_000, now=NOW, bucket_pool=None, pool_nb=64,
          greg=0):
    """Unique-fp request batch (mkfp's collision knobs pass through)."""
    n_active = n if n_active is None else n_active
    fp = mkfp(rng, n, bucket_pool, pool_nb)
    h = (np.asarray(hits, dtype=np.int64) if hits is not None
         else rng.integers(-2, 4, size=n).astype(np.int64))
    if h.ndim == 0:
        h = np.full(n, h, dtype=np.int64)
    algo = np.array([algos[i % len(algos)] for i in range(n)], dtype=np.int32)
    return ReqBatch(
        fp=jnp.asarray(fp),
        algo=jnp.asarray(algo),
        behavior=jnp.full(n, behavior, dtype=jnp.int32),
        hits=jnp.asarray(h),
        limit=jnp.full(n, limit, dtype=jnp.int64),
        burst=jnp.full(n, limit, dtype=jnp.int64),
        duration=jnp.full(n, dur, dtype=jnp.int64),
        created_at=jnp.full(n, now, dtype=jnp.int64),
        expire_new=jnp.full(n, now + dur, dtype=jnp.int64),
        greg_interval=jnp.full(n, greg, dtype=jnp.int64),
        duration_eff=jnp.full(n, dur, dtype=jnp.int64),
        active=jnp.asarray(np.arange(n) < n_active),
    )


def _run(cap, req, write, math, layout, steps, step_ms):
    """Every step's (responses, stats, table rows) as numpy, one table
    advanced under `write`. `req` is one batch replayed every step, or a
    list with one batch a step."""
    table = new_table2(cap, layout=layout)
    out = []
    for s in range(steps):
        req_s = req[s] if isinstance(req, list) else req
        r = req_s._replace(
            created_at=req_s.created_at + s * step_ms,
            expire_new=req_s.expire_new + s * step_ms,
        )
        table, resp, stats = _decide(table, r, write=write, math=math)
        act = np.asarray(r.active)
        fields = {f: np.asarray(getattr(resp, f)) for f in RESP_FIELDS}
        # aux/rem_store are broadcast-plane echoes, defined for ACTIVE rows
        fields.update(
            {f: np.asarray(getattr(resp, f))[act] for f in ("aux", "rem_store")}
        )
        out.append((fields, {f: int(getattr(stats, f)) for f in stats._fields},
                    np.asarray(table.rows)))
    return out


def pin_sparse(monkeypatch, cap, batch, layout):
    """Make `write="sparse"` the real sparse grid on a tiny table."""
    monkeypatch.setenv("GUBER_WRITE_SPARSE_CROSSOVER", "0")
    monkeypatch.setenv("GUBER_WRITE_SPARSE_BLK", "8")
    nb = new_table2(cap, layout=layout).rows.shape[0]
    # tripwire: a fall-back to the sweep would run the sweep case twice
    assert resolve_write("sparse", nb, batch, layout) == "sparse"


def assert_parity(write, monkeypatch, cap, req, math="mixed", layout=None,
                  steps=3, step_ms=20_000):
    if write == "sparse":
        batch = (req[0] if isinstance(req, list) else req).fp.shape[0]
        pin_sparse(monkeypatch, cap, batch, layout)
    want = _run(cap, req, "xla", math, layout, steps, step_ms)
    got = _run(cap, req, write, math, layout, steps, step_ms)
    for s, ((rx, sx, tx), (rw, sw, tw)) in enumerate(zip(want, got)):
        for f in rx:
            np.testing.assert_array_equal(
                rx[f], rw[f], err_msg=f"step {s}: RespBatch.{f}"
            )
        assert sx == sw, f"step {s}: BatchStats"
        np.testing.assert_array_equal(tx, tw, err_msg=f"step {s}: table bytes")


# ------------------------------------------------------------ the scenarios
# name → list of (seed, cap, request builder(rng), kwargs of assert_parity)


def _per_algorithm(algo, math):
    return [(algo + 1, 512, lambda rng: mkreq(rng, 128, algos=(algo,)),
             dict(math=math, steps=4))]


def _packed(lay, algo, math):
    return [
        (9, 512, lambda rng: mkreq(rng, 128, algos=(algo,)),
         dict(math=math, layout=lay, steps=4)),
        # under collision pressure (eviction on packed rows)
        (90, 128,
         lambda rng: mkreq(rng, 128, algos=(algo,), bucket_pool=6, pool_nb=16),
         dict(math=math, layout=lay, steps=4)),
    ]


def _gregorian(rng):
    req = mkreq(rng, 128, algos=(0,), behavior=4, hits=1)
    return req._replace(greg_interval=jnp.full(128, 86_400_000, jnp.int64))


SCENARIOS = {
    "token": _per_algorithm(0, "token"),
    "leaky": _per_algorithm(1, "mixed"),
    "gcra": _per_algorithm(2, "gcra"),
    "sliding_window": _per_algorithm(3, "int"),
    "concurrency": _per_algorithm(4, "int"),
    "mixed_all_algorithms": [
        (42, 512, lambda rng: mkreq(rng, 128, algos=(0, 1, 2, 3, 4)),
         dict(math="mixed", steps=4)),
    ],
    "packed_gcra32": _packed(GCRA32, 2, "gcra"),
    "packed_token32": _packed(TOKEN32, 0, "token"),
    # more unique keys per bucket than K=8 lanes: rank-overflow drops; the
    # owners are in every batch, so they keep their lanes (owner wins)
    "bucket_full_eviction": [
        (2, 64,
         lambda rng: mkreq(rng, 256, algos=(0, 2), bucket_pool=4, pool_nb=8,
                           hits=1),
         dict(math="int", steps=4)),
    ],
    # fresh keys every step into buckets whose lanes are all still live:
    # soonest-expiring eviction of LIVE lanes, several a bucket at once
    "live_lane_eviction": [
        (11, 64,
         lambda rng: [
             mkreq(rng, 48, algos=(0, 2), bucket_pool=4, pool_nb=8, hits=1,
                   dur=60_000 + 7_000 * s)
             for s in range(4)
         ],
         dict(math="int", steps=4, step_ms=1_000)),
    ],
    # owner-vs-inserter lane collisions (the sorted-dup rule): aged state
    # makes owners' lanes expired/evictable, so fresh inserters pick them
    "same_target_dedup": [
        (3, 128,
         lambda rng: mkreq(rng, 128, algos=(0,), bucket_pool=8, pool_nb=16,
                           dur=5_000, hits=1),
         dict(math="token", steps=5, step_ms=4_000)),
    ],
    # steps larger than the duration: every slot expires between steps and
    # is reclaimed through the vacant-first candidate order
    "expired_slot_reclaim": [
        (4, 128,
         lambda rng: mkreq(rng, 128, algos=(0, 2, 3, 4), bucket_pool=8,
                           pool_nb=16, dur=5_000, hits=2),
         dict(math="int", steps=4, step_ms=30_000)),
    ],
    # releases against keys with no live state must not install for the
    # extension algorithms
    "negative_hit_release_on_missing_key": [
        (5, 512, lambda rng: mkreq(rng, 128, algos=(2, 3, 4), hits=-3),
         dict(math="int", steps=3)),
    ],
    "reset_and_drain": [
        (6, 512, lambda rng: mkreq(rng, 128, algos=(0, 2), behavior=8),
         dict(math="int", steps=3)),  # RESET_REMAINING removes
        (60, 512,
         lambda rng: mkreq(rng, 128, algos=(0, 1, 2, 3, 4), behavior=16,
                           hits=60),
         dict(math="mixed", steps=3)),  # DRAIN_OVER_LIMIT
        (61, 512, _gregorian, dict(math="mixed", steps=3)),
    ],
    "inactive_padding": [
        (7, 512, lambda rng: mkreq(rng, 128, n_active=70),
         dict(math="mixed", steps=3)),
        # all-padding warm batch
        (70, 512, lambda rng: mkreq(rng, 64, n_active=0),
         dict(math="token", steps=2)),
    ],
    # EVERY request hashes to one bucket: one run spans the whole batch
    "single_bucket_whole_batch": [
        (10, 32,
         lambda rng: mkreq(rng, 64, algos=(0,), bucket_pool=1, pool_nb=4,
                           hits=1),
         dict(math="token", steps=3)),
    ],
}


@pytest.mark.parametrize("write", ["sparse", "sweep"])
@pytest.mark.parametrize("scenario", list(SCENARIOS))
def test_decide_corner_parity(scenario, write, monkeypatch):
    for seed, cap, build, kw in SCENARIOS[scenario]:
        req = build(np.random.default_rng(seed))
        assert_parity(write, monkeypatch, cap, req, **kw)


def test_scenarios_reach_their_corners():
    """The corners are real: drops, live-lane eviction, dedup losers and
    reclaims occur in the scenarios named for them (on the XLA reference)."""
    def totals(name):
        (seed, cap, build, kw), = SCENARIOS[name][:1]
        kw = dict(kw)
        runs = _run(cap, build(np.random.default_rng(seed)), "xla",
                    kw.pop("math"), kw.pop("layout", None),
                    kw.pop("steps"), kw.pop("step_ms", 20_000))
        return functools.reduce(
            lambda a, b: {k: a[k] + b[k] for k in a}, [st for _, st, _ in runs]
        )

    assert totals("bucket_full_eviction")["dropped"] > 0
    assert totals("live_lane_eviction")["evicted_unexpired"] > 0
    assert totals("same_target_dedup")["dropped"] > 0
    assert totals("expired_slot_reclaim")["cache_hits"] == 0  # all re-inserts
    assert totals("single_bucket_whole_batch")["dropped"] > 0  # 64 rows, 8 lanes
