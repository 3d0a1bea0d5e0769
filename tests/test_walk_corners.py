"""Claim corners of the install and merge walks, under the two Pallas write
kernels (kernel2.install2 / merge2 — GLOBAL installs, region/handoff merges,
tiering promotes).

Same contract as tests/test_decide_corners.py: `write="sparse"` and
`write="sweep"` against the XLA scatter, installed/merged masks, evictee
rows AND raw table bytes equal at every step — every slot layout a table
can run, collision pressure past K=8 lanes a bucket, the broadcast
fidelity lanes, padding, expired-slot reclaim, packed receivers of
full-width rows, merges that displace live rows, incoming rows already
expired. The conservative-merge rules (remaining=min, OVER sticks,
expiry=max, newest-stamp config, duplicate fingerprints as sequential
passes) are asserted behaviourally on the engine.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gubernator_tpu.ops.batch import InstallBatch, RequestColumns
from gubernator_tpu.ops.engine import LocalEngine
from gubernator_tpu.ops.kernel2 import install2_impl, merge2_impl
from gubernator_tpu.ops.layout import FULL, GCRA32, TOKEN32
from gubernator_tpu.ops.table2 import (
    EXP_HI,
    EXP_LO,
    FLAGS,
    LIMIT,
    REM_I,
    new_table2,
)
from tests.test_decide_corners import mkfp, pin_sparse

NOW = 1_700_000_000_000

_install = jax.jit(install2_impl, static_argnames=("write",))
_merge = jax.jit(merge2_impl, static_argnames=("write", "evictees"))


def mkinst(rng, n, algos=(0,), n_active=None, limit=100, dur=60_000,
           now=NOW, bucket_pool=None, pool_nb=64, fidelity=False):
    """InstallBatch of unique-fp owner-authoritative statuses (the
    UpdatePeerGlobals receive shape). `fidelity` attaches the
    sliding-window aux/rem_store broadcast lanes."""
    n_active = n if n_active is None else n_active
    fp = mkfp(rng, n, bucket_pool, pool_nb)
    algo = np.array([algos[i % len(algos)] for i in range(n)], dtype=np.int32)
    remaining = rng.integers(0, limit + 1, size=n).astype(np.int64)
    status = (rng.integers(0, 4, size=n) == 0).astype(np.int32)  # ~25% OVER
    stamp = now - rng.integers(0, 5_000, size=n).astype(np.int64)
    active = np.arange(n) < n_active
    j = jnp.asarray
    return InstallBatch(
        fp=j(fp),
        algo=j(algo),
        status=j(status),
        limit=j(np.full(n, limit, dtype=np.int64)),
        remaining=j(remaining),
        reset_time=j(np.full(n, now + dur, dtype=np.int64)),
        duration=j(np.full(n, dur, dtype=np.int64)),
        now=j(np.full(n, now, dtype=np.int64)),
        active=j(active),
        burst=j(np.full(n, limit, dtype=np.int64)),
        stamp=j(stamp),
        aux=j(rng.integers(0, limit, size=n).astype(np.int64))
        if fidelity else None,
        rem_store=j(remaining.copy()) if fidelity else None,
    )


def cols(fp, algo, hits=1, limit=64, now=NOW, dur=8_000):
    n = fp.shape[0]
    h = (np.asarray(hits, dtype=np.int64) if np.ndim(hits)
         else np.full(n, hits, dtype=np.int64))
    return RequestColumns(
        fp=fp.astype(np.int64),
        algo=np.full(n, algo, dtype=np.int32),
        behavior=np.zeros(n, dtype=np.int32),
        hits=h,
        limit=np.full(n, limit, dtype=np.int64),
        burst=np.zeros(n, dtype=np.int64),
        duration=np.full(n, dur, dtype=np.int64),
        created_at=np.full(n, now, dtype=np.int64),
        err=np.zeros(n, dtype=np.int8),
    )


def donor_rows(rng, n, algo, now=NOW, dur=8_000, cap=1 << 11, fp=None,
               hits=None):
    """Realistic live slot rows: drive serving traffic through a donor
    engine, then extract — the handoff sender's exact staging form."""
    eng = LocalEngine(capacity=cap, write_mode="xla")
    fp = mkfp(rng, n) if fp is None else fp
    hits = rng.integers(0, 3, size=n) if hits is None else hits
    eng.check_columns(cols(fp, algo, hits=hits, now=now, dur=dur), now_ms=now)
    fps, slots = eng.extract_live(now_ms=now)
    assert fps.shape[0] > 0
    return fps, slots


# ---------------------------------------------------------------- installs


def run_install(cap, batches, write, layout):
    table = new_table2(cap, layout=layout)
    out = []
    for inst in batches:
        table, mask = _install(table, inst, write=write)
        out.append((np.asarray(mask), np.asarray(table.rows)))
    return out


def _inst_steps(seed, n, steps=3, step_ms=20_000, **kw):
    def build():
        rng = np.random.default_rng(seed)
        return [mkinst(rng, n, now=NOW + s * step_ms, **kw)
                for s in range(steps)]
    return build


LAYOUTS = {
    "default": (None, (0, 1, 2, 3, 4)),
    "full": (FULL, (0, 1, 2, 3, 4)),
    "gcra32": (GCRA32, (2,)),
    "token32": (TOKEN32, (0,)),
}

# name → list of (cap, layout, builder of the per-step InstallBatches)
INSTALL_SCENARIOS = {
    **{
        f"per_layout_{name}": [
            (512, lay, _inst_steps(21, 128, steps=4, algos=algos))
        ]
        for name, (lay, algos) in LAYOUTS.items()
    },
    # more unique keys per bucket than K=8 lanes: the install walk evicts
    # soonest-expiring LIVE lanes and drops rank overflow
    **{
        f"collision_pressure_{name}": [
            (64, lay, _inst_steps(22, 192, steps=4, algos=algos,
                                  bucket_pool=4, pool_nb=8))
        ]
        for name, (lay, algos) in LAYOUTS.items()
    },
    "fidelity_and_padding": [
        (512, None, _inst_steps(24, 128, algos=(3,), fidelity=True)),
        (512, None, _inst_steps(25, 96, n_active=50, algos=(0, 1, 2, 3, 4))),
        # all-padding warm batch (the warm_up shape)
        (256, None, _inst_steps(26, 32, steps=2, n_active=0)),
    ],
    # steps larger than the duration: every slot expires between steps and
    # the walk reclaims through the vacant-first candidate order
    "expired_slot_reclaim": [
        (128, None, _inst_steps(27, 128, steps=4, step_ms=30_000,
                                algos=(0, 2, 3), dur=5_000, bucket_pool=8,
                                pool_nb=16)),
    ],
}


@pytest.mark.parametrize("write", ["sparse", "sweep"])
@pytest.mark.parametrize("scenario", list(INSTALL_SCENARIOS))
def test_install_corner_parity(scenario, write, monkeypatch):
    for cap, layout, build in INSTALL_SCENARIOS[scenario]:
        batches = build()
        if write == "sparse":
            pin_sparse(monkeypatch, cap, batches[0].fp.shape[0], layout)
        want = run_install(cap, batches, "xla", layout)
        got = run_install(cap, batches, write, layout)
        for s, ((mx, tx), (mw, tw)) in enumerate(zip(want, got)):
            np.testing.assert_array_equal(mx, mw, err_msg=f"step {s}: mask")
            np.testing.assert_array_equal(tx, tw, err_msg=f"step {s}: table")


def test_install_collision_pressure_evicts_and_drops():
    """The pressure scenario is real: some rows are refused (rank past the
    K lanes) and later steps displace rows that were still live."""
    (cap, layout, build), = INSTALL_SCENARIOS["collision_pressure_default"]
    runs = run_install(cap, build(), "xla", layout)
    assert any(not m.all() for m, _ in runs)
    first, last = runs[0][1], runs[-1][1]
    assert (first != 0).any() and not np.array_equal(first, last)


# ------------------------------------------------------------------ merges


def run_merge(cap, batches, write, layout=None, now=NOW, step_ms=3_000,
              evictees=False):
    """Merge one (fps, canonical rows) batch a step into one table."""
    table = new_table2(cap, layout=layout)
    j = jnp.asarray
    out = []
    for s, (fps, slots) in enumerate(batches):
        n = fps.shape[0]
        args = (j(fps), j(slots), j(np.full(n, now + s * step_ms, np.int64)),
                j(np.ones(n, dtype=bool)))
        if evictees:
            table, mask, ev = _merge(table, *args, write=write, evictees=True)
            out.append((np.asarray(mask), np.asarray(table.rows),
                        np.asarray(ev)))
        else:
            table, mask = _merge(table, *args, write=write)
            out.append((np.asarray(mask), np.asarray(table.rows)))
    return out


def _repeated(seed, n, algo, steps, **donor_kw):
    """The same transferred rows merged `steps` times, each repeat with
    smaller remainings, so it hits the live-lane tighten branch with
    different winners."""
    def build():
        rng = np.random.default_rng(seed)
        fps, slots = donor_rows(rng, n, algo, **donor_kw)
        batches = []
        for _ in range(steps):
            batches.append((fps, slots))
            slots = slots.copy()
            slots[:, REM_I] = np.maximum(
                slots[:, REM_I]
                - rng.integers(0, 5, size=fps.shape[0]).astype(np.int32), 0
            )
        return batches
    return build


def _displacing():
    """Two disjoint key sets over the same four buckets, merged A, B, A:
    each batch finds every lane of its buckets live under the OTHER set's
    keys, so its installs displace live rows."""
    rng = np.random.default_rng(36)
    fp = mkfp(rng, 128, bucket_pool=4, pool_nb=8)
    fps, slots = donor_rows(rng, 128, 0, fp=fp, hits=1)
    a, b = (fps[:64], slots[:64]), (fps[64:], slots[64:])
    return [a, b, a]


# name → (cap, builder of the per-step (fps, rows) batches, run_merge kwargs)
MERGE_SCENARIOS = {
    **{
        f"per_algorithm_{algo}": (
            1 << 11, _repeated(31 + algo, 256, algo, steps=3), {}
        )
        for algo in (0, 2, 3)
    },
    # a packed receiver merging full-width transferred rows (the
    # cross-layout handoff)
    "packed_receiver_gcra32": (
        512, _repeated(35, 128, 2, steps=3), dict(layout=GCRA32)
    ),
    "packed_receiver_token32": (
        512, _repeated(35, 128, 0, steps=3), dict(layout=TOKEN32)
    ),
    # bucket-full pressure with evictee collection: displaced LIVE rows
    # ride home (the tiering promote contract)
    "collision_and_evictees": (64, _displacing, dict(evictees=True)),
    # incoming rows whose expiry predates the receiver clock are inert
    "expired_incoming_rows": (
        512, _repeated(37, 128, 0, steps=2, dur=2_000),
        dict(now=NOW + 10_000),
    ),
}


@pytest.mark.parametrize("write", ["sparse", "sweep"])
@pytest.mark.parametrize("scenario", list(MERGE_SCENARIOS))
def test_merge_corner_parity(scenario, write, monkeypatch):
    cap, build, kw = MERGE_SCENARIOS[scenario]
    batches = build()
    if write == "sparse":
        pin_sparse(monkeypatch, cap, batches[0][0].shape[0], kw.get("layout"))
    want = run_merge(cap, batches, "xla", **kw)
    got = run_merge(cap, batches, write, **kw)
    for s, (x, w) in enumerate(zip(want, got)):
        for what, a, b in zip(("mask", "table", "evictees"), x, w):
            np.testing.assert_array_equal(a, b, err_msg=f"step {s}: {what}")


def test_merge_scenarios_reach_their_corners():
    cap, build, kw = MERGE_SCENARIOS["collision_and_evictees"]
    runs = run_merge(cap, build(), "xla", **kw)
    assert all((ev != 0).any() for _, _, ev in runs[1:])  # live rows displaced
    cap, build, kw = MERGE_SCENARIOS["expired_incoming_rows"]
    assert not any(m.any() for m, _ in run_merge(cap, build(), "xla", **kw))


# --------------------------------------------- conservatism, behaviourally


def _engines(cap=256):
    return [LocalEngine(capacity=cap, write_mode=w) for w in ("xla", "sweep")]


def _install_one(e, fp, status, remaining, stamp, dur=60_000, algo=0,
                 now=NOW, limit=100):
    one = lambda v, dt: np.array([v], dtype=dt)
    e.install_columns(
        one(fp, np.int64), one(algo, np.int32), one(status, np.int32),
        one(limit, np.int64), one(remaining, np.int64),
        one(now + dur, np.int64), one(dur, np.int64), now_ms=now,
        stamp=one(stamp, np.int64),
    )


def _merged_row(donor_kw, stored_kw, fp):
    """Install `stored_kw` at the receiver, merge the donor's extracted
    row over it, and return the stored row — the same under both engines'
    write kernels."""
    donor = LocalEngine(capacity=256, write_mode="xla")
    _install_one(donor, fp, **donor_kw)
    dfps, drows = donor.extract_live(now_ms=NOW)
    outs = []
    for e in _engines():
        _install_one(e, fp, **stored_kw)
        assert e.merge_rows(dfps, drows, now_ms=NOW + 10) == 1
        found, rows = e.read_state(np.array([fp], dtype=np.int64))
        assert found[0]
        outs.append(rows[0])
    np.testing.assert_array_equal(outs[0], outs[1])
    return outs[0]


def test_merge_conservatism_over_sticks_min_remaining():
    """remaining=min and OVER-sticks: a generous incoming row can never
    re-grant capacity a stored OVER denied."""
    row = _merged_row(
        dict(status=0, remaining=80, stamp=NOW + 5),
        dict(status=1, remaining=20, stamp=NOW), 0x5EED_F00D,
    )
    assert int(row[REM_I]) == 20  # min(stored 20, incoming 80)
    assert (int(row[FLAGS]) >> 8) & 0xFF == 1  # OVER sticks


def test_merge_conservatism_expiry_max_and_newest_config():
    """expiry=max (state lives at least as long) and newest-stamp config
    (the later limit wins)."""
    row = _merged_row(
        dict(status=0, remaining=150, stamp=NOW + 9, dur=120_000, limit=200),
        dict(status=0, remaining=50, stamp=NOW, dur=60_000), 0xC0FF_EE11,
    )
    exp = (int(row[EXP_HI]) << 32) | (int(row[EXP_LO]) & 0xFFFFFFFF)
    assert exp == NOW + 120_000  # max of the two expiries
    assert int(row[REM_I]) == 50  # min still tightens
    assert int(row[LIMIT]) == 200  # newest stamp's config won


def test_merge_duplicate_fps_sequential_passes():
    """Duplicate fingerprints inside one merge batch resolve as sequential
    passes (the unique-fp contract): the same final state and merged count
    under both write kernels, and the tighter copy wins."""
    rng = np.random.default_rng(41)
    fps, slots = donor_rows(rng, 96, 0)
    # duplicate every key, second copy strictly tighter (smaller remaining)
    dup_rows = slots.copy()
    dup_rows[:, REM_I] = np.maximum(dup_rows[:, REM_I] - 7, 0)
    all_fps = np.concatenate([fps, fps])
    all_rows = np.concatenate([slots, dup_rows])
    engines = _engines(cap=1 << 11)
    counts = [e.merge_rows(all_fps, all_rows, now_ms=NOW + 5) for e in engines]
    assert counts[0] == counts[1]
    np.testing.assert_array_equal(engines[0].snapshot(), engines[1].snapshot())
    found, rows = engines[0].read_state(fps)
    np.testing.assert_array_equal(rows[found, REM_I], dup_rows[found, REM_I])
