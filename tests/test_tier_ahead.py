"""The fault-back ahead of the launch (PR 44; docs/tiering.md "Where a key's
state may be"): with a shadow attached a pipelined dispatch's issue job takes
the grid's shadowed keys out of the shadow and installs them with ONE `merge2`
launched ahead of the dispatch's passes and not fetched in that job
(`LocalEngine.fault_ahead`); the launch's outputs wait in `_sidecars` for the
next engine-thread job that touches the shadow (`drain_sidecars`).

Held here: a key whose state is on its way is never granted afresh, a promote
that finds no lane comes back and is decided by the miss path, every job that
touches the shadow drains first, a launch that raises gives the taken rows
back, and the counters count what the plain reference
(`tests/oracle/stored_table.py`) says was promoted.

The table is ONE bucket of eight lanes, so which key a promote pushes out is
the test's to arrange: the least recently touched.
"""

import ast
import asyncio
import inspect
import types

import numpy as np
import pytest

from gubernator_tpu.ops import engine as engine_mod
from gubernator_tpu.ops.engine import (
    LocalEngine,
    finish_check_columns,
    issue_check_columns,
    prepare_check_wire,
)
from gubernator_tpu.service import runner as runner_mod
from gubernator_tpu.service.runner import EngineRunner
from gubernator_tpu.tier import ShadowTable
from gubernator_tpu.tier import manager as manager_mod
from gubernator_tpu.tier.manager import TierManager

from tests.oracle.stored_table import K, StoredTable
from tests.test_tiered_deployment import (
    DURATION,
    LIMIT,
    N_KEYS,
    NOW,
    SLOTS,
    STEP_MS,
    case_chunks,
    fp_of,
    rpc,
    served,
)


def tiered(capacity: int = K) -> LocalEngine:
    eng = LocalEngine(capacity=capacity, wire="compact")
    eng.attach_shadow(ShadowTable(max_bytes=1 << 22))
    return eng


def issue(eng, keys, now):
    """The prepare and issue halves of one pipelined dispatch."""
    pending = prepare_check_wire(eng, [rpc(keys, now)], now_ms=now)
    assert pending is not None
    return issue_check_columns(eng, pending)


def finish(eng, pending):
    """The finish half, its fix-ups run here (this thread is the engine's)."""
    rc, delta = finish_check_columns(eng, pending, lambda fn: fn())
    eng.stats.merge(delta)
    assert not rc.err.any()
    return rc


def check(eng, keys, now):
    return finish(eng, issue(eng, keys, now))


def fps_of(keys) -> np.ndarray:
    return np.asarray([fp_of(k) for k in keys], dtype=np.int64)


def places(eng, keys):
    """(resident, shadowed) masks of `keys`, the sidecars left as they are."""
    fps = fps_of(keys)
    found, _rows = eng.read_state(fps)
    return np.asarray(found), eng.shadow.contains(fps)


B = list(range(100, 108))  # the first eight: they end in the shadow
A = list(range(200, 208))  # the next eight: they push the first out


def b_shadowed_a_resident(eng, lru: int = A[0]):
    """Eight keys B created, then eight keys A that push them out, then every
    A but `lru` touched again: the shadow holds B, the one bucket holds A,
    and `lru` is the lane a promote takes. Returns the next dispatch's clock."""
    check(eng, B, NOW)
    check(eng, A, NOW + STEP_MS)
    check(eng, [a for a in A if a != lru], NOW + 2 * STEP_MS)
    eng.drain_sidecars()
    resident, shadowed = places(eng, B + A)
    assert shadowed[:8].all() and resident[8:].all()
    assert not (resident & shadowed).any()
    return NOW + 3 * STEP_MS


# ------------------------------------------- (a) a key on its way


def test_a_key_on_its_way_is_never_granted_afresh():
    """Two dispatches issued back to back before either is finished. N's
    merge ahead of its launch brings B[1] back and pushes A[0] out: A[0]'s
    count is in N's sidecar while N's own passes (A[0] twice: pass 0 and
    pass 1) and N+1's issue job run. N's passes see A[0] absent and defer it;
    N+1's issue job drains the sidecar before its take, finds A[0] in the
    shadow and brings it back ahead of its own pass, which hits. No check of
    A[0] starts from a fresh bucket."""
    eng = tiered()
    now = b_shadowed_a_resident(eng)
    n0 = issue(eng, [B[1], A[0], A[0]], now)
    assert len(eng._sidecars) == 1
    resident, shadowed = places(eng, [B[1], A[0]])
    assert resident[0] and not shadowed[0]  # installed, nothing fetched
    assert not resident[1] and not shadowed[1]  # on its way: in the sidecar
    n1 = issue(eng, [A[0], B[1]], now)
    assert len(eng._sidecars) == 1  # N's was drained, N+1's is pending
    got0, got1 = finish(eng, n0), finish(eng, n1)
    eng.drain_sidecars()
    # B[1]: one fill hit, then one in each dispatch; A[0]: one fill hit, then
    # three checks. Concurrent dispatches may be served in either order, so
    # each key's answers are held as a set: every count given out once.
    rem_a = sorted([*got0.remaining[1:3].tolist(), int(got1.remaining[0])])
    rem_b = sorted([int(got0.remaining[0]), int(got1.remaining[1])])
    assert rem_a == [LIMIT - 4, LIMIT - 3, LIMIT - 2]
    assert rem_b == [LIMIT - 3, LIMIT - 2]
    for got in (got0, got1):
        assert not got.status.any()
    # the reset time is the one the key's first check gave it: never renewed
    assert set(got0.reset_time[1:3].tolist()) == {NOW + STEP_MS + DURATION}
    assert int(got1.reset_time[0]) == NOW + STEP_MS + DURATION
    assert int(got0.reset_time[0]) == int(got1.reset_time[1]) == NOW + DURATION
    # read back, hits = 0: limit - sent, wherever the key lies
    peek = finish(eng, issue_peek(eng, [A[0], B[1], B[0], A[1]], now + STEP_MS))
    assert peek.remaining.tolist() == [LIMIT - 4, LIMIT - 3, LIMIT - 1, LIMIT - 2]
    assert eng.stats.lost_live == 0 and eng.shadow.shed == 0 and eng.stats.dropped == 0
    eng.drain_sidecars()
    resident, shadowed = places(eng, B + A)
    assert (resident ^ shadowed).all()  # every key in exactly one place


def issue_peek(eng, keys, now):
    pending = prepare_check_wire(eng, [rpc(keys, now, hits=0)], now_ms=now)
    return issue_check_columns(eng, pending)


# ------------------------------------------- (b) a promote with no lane


def test_a_ninth_promote_of_one_bucket_is_returned_and_decided_by_the_fixup():
    """Nine shadowed keys of one bucket in one dispatch: eight install ahead
    of the launch and hit, the ninth finds no lane, is returned to the shadow
    at the drain, deferred by the pass and decided by the miss path."""
    eng = tiered()
    nine = list(range(300, 309))
    check(eng, nine, NOW)  # nine creates in eight lanes: one is pushed out
    check(eng, A, NOW + STEP_MS)  # eight more: all nine lie in the shadow
    eng.drain_sidecars()
    resident, shadowed = places(eng, nine)
    assert shadowed.all() and not resident.any()
    before = eng.tier_counts()
    pending = issue(eng, nine, NOW + 2 * STEP_MS)
    assert len(eng._sidecars) == 1
    got = finish(eng, pending)
    eng.drain_sidecars()
    t = eng.tier_counts()
    assert t["promoted_ahead"] - before["promoted_ahead"] == 8
    assert t["returned"] - before["returned"] == 1
    assert t["promoted"] - before["promoted"] == 9  # the ninth by the miss path
    assert t["rehydrate_dispatches"] > before["rehydrate_dispatches"]
    # every one of the nine answers continues its count: none starts anew
    assert got.remaining.tolist() == [LIMIT - 2] * 9 and not got.status.any()
    assert set(got.reset_time.tolist()) == {NOW + DURATION}
    assert eng.stats.lost_live == 0 and eng.stats.dropped == 0
    resident, shadowed = places(eng, nine + A)
    assert (resident ^ shadowed).all() and int(resident.sum()) == K


# ------------------------------------------- (c) who drains


def _with_a_sidecar_pending():
    """An engine whose last issue job left one sidecar: A[0]'s count."""
    eng = tiered()
    now = b_shadowed_a_resident(eng)
    pending = issue(eng, [B[1]], now)
    assert len(eng._sidecars) == 1
    assert not eng.shadow.contains(fps_of([A[0]])).any()
    return eng, pending, now


def _manager(eng, runner) -> TierManager:
    conf = types.SimpleNamespace(
        tier_enabled=True, tier_idle_ms=60_000.0, telemetry_interval_ms=5_000.0,
        tier_shadow_bytes=1 << 22, tier_spill_path="",
    )
    daemon = types.SimpleNamespace(
        conf=conf, engine=eng, runner=runner, metrics=None, _shutting_down=False,
    )
    tm = TierManager(daemon)
    tm.shadow = eng.shadow
    return tm


def _toucher_decide_faulting(eng, now):
    pending = prepare_check_wire(eng, [rpc([A[1]], now)], now_ms=now)
    eng._decide_faulting(pending.passes[0][2]._materialize(), 1)


def _toucher_demote_idle(eng, now):
    runner = EngineRunner(eng)
    try:
        asyncio.run(runner.tier_demote_idle(1 << 40, now_ms=now))
    finally:
        runner.close()


def _toucher_attach_shadow(eng, now):
    eng.attach_shadow(ShadowTable(max_bytes=1 << 22))


def _toucher_manager_close(eng, now):
    runner = EngineRunner(eng)
    try:
        _manager(eng, runner).close(now)
    finally:
        runner.close()


def _toucher_serial_check(eng, now):
    eng.check_columns(rpc([A[1]], now).cols, now_ms=now)


def _toucher_next_issue(eng, now):
    issue(eng, [A[2]], now)  # holds no shadowed key: drains, launches no merge


def _toucher_apply(eng, now):
    runner = EngineRunner(eng)
    try:
        runner._exec.submit(runner._apply, []).result()
    finally:
        runner.close()


TOUCHERS = {
    "decide_faulting": _toucher_decide_faulting,
    "tier_demote_idle": _toucher_demote_idle,
    "attach_shadow": _toucher_attach_shadow,
    "manager_close": _toucher_manager_close,
    "serial_check_columns": _toucher_serial_check,
    "the_next_issue_job": _toucher_next_issue,
    "the_dispatchs_apply_job": _toucher_apply,
}


@pytest.mark.parametrize("name", sorted(TOUCHERS))
def test_every_shadow_toucher_drains_first(name):
    """With a sidecar left pending, each job that reads or writes the shadow
    leaves none, and what the sidecar held (A[0], pushed out by the promote
    of B[1]) is in the shadow that was attached when it was launched."""
    eng, _pending, now = _with_a_sidecar_pending()
    shadow = eng.shadow
    TOUCHERS[name](eng, now)
    assert eng._sidecars == []
    assert shadow.contains(fps_of([A[0]])).all()
    assert shadow.demoted_evict == 8 + 1  # the fill's eight, then A[0]
    if name == "attach_shadow":
        # the warm-up's scratch shadow leaks nothing into the real one
        assert eng.shadow is not shadow and eng.shadow.ram_rows == 0


def _shadow_callers(module) -> set:
    """Functions of `module` that call `.take(` or `.offer(` on a shadow."""
    tree = ast.parse(inspect.getsource(module))
    out = set()

    def visit(node, path):
        for child in ast.iter_child_nodes(node):
            here = path
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)):
                here = path + [child.name]
            if (
                isinstance(child, ast.Call)
                and isinstance(child.func, ast.Attribute)
                and child.func.attr in ("take", "offer")
                and "shadow" in ast.unparse(child.func.value)
            ):
                out.add(".".join(path))
            visit(child, here)

    visit(tree, [])
    return out


def test_the_list_of_drainers_is_the_list_of_shadow_callers():
    """Whoever calls `shadow.take` / `shadow.offer` is on this list, and
    each entry on it is reached only behind a drain: `fault_ahead` and
    `drain_sidecars` themselves; `_fault_in` and `_harvest_evictees` from
    `_decide_faulting`, which drains at its head; the sweep's sink inside
    `runner.tier_demote_idle`'s job, which drains at its head. The mesh
    engine's `shadow_probe` / `promote_rows` launch nothing unfetched."""
    assert _shadow_callers(engine_mod) == {
        "shadow_probe", "promote_rows",
        "LocalEngine._harvest_evictees", "LocalEngine.drain_sidecars",
        "LocalEngine._take_shadowed", "LocalEngine.fault_ahead",
        "LocalEngine._fault_in",
    }
    assert _shadow_callers(manager_mod) == {"TierManager.sweep_once"}
    assert _shadow_callers(runner_mod) == set()

    def src_calls(obj, text) -> int:
        return inspect.getsource(obj).count(text)

    def drains_before(fn, *touches) -> bool:
        src = inspect.getsource(fn).replace(fn.__doc__ or "", "")
        at = src.index("drain_sidecars") if "drain_sidecars" in src else (
            src.index("tier_drain_sync"))
        return all(at < src.index(t) for t in touches if t in src)

    assert drains_before(LocalEngine.fault_ahead, "_take_shadowed", "shadow.offer")
    # the one `take` of the local engine is reached from those two alone
    assert src_calls(LocalEngine, "self._take_shadowed(") == 2
    assert drains_before(LocalEngine._decide_faulting, "_fault_in", "_decide_packed")
    assert drains_before(LocalEngine.attach_shadow, "self.shadow =")
    assert drains_before(LocalEngine.check_columns, "serve_columns(")
    assert drains_before(EngineRunner.tier_demote_idle, "extract_idle", "sink(")
    assert drains_before(TierManager.close, "flush(")
    # the miss path's two are reached from `_decide_faulting` alone
    assert src_calls(LocalEngine, "self._fault_in(") == 1
    assert src_calls(LocalEngine._decide_faulting, "self._fault_in(") == 1
    assert src_calls(LocalEngine, "self._harvest_evictees(") == 1
    assert src_calls(LocalEngine._decide_packed, "self._harvest_evictees(") == 1


def test_the_pending_record_is_empty_after_close():
    """A daemon's last dispatches leave sidecars; `TierManager.close` runs
    the drain on the engine thread before it flushes the shadow."""
    eng, _pending, now = _with_a_sidecar_pending()
    runner = EngineRunner(eng)
    try:
        tm = _manager(eng, runner)
        assert tm.pipeline()["promoted_ahead"] == 0  # counted at the drain
        tm.close(now)
        assert eng._sidecars == []
        assert tm.pipeline()["promoted_ahead"] == 1
        assert tm.demoted() == 9 and tm.lost() == 0
    finally:
        runner.close()


# ------------------------------------------- (d) a launch that raises


def test_a_launch_that_raises_gives_the_taken_rows_back(monkeypatch):
    eng = tiered()
    now = b_shadowed_a_resident(eng)
    rows_before = eng.shadow.ram_rows

    def boom(self, *a, **k):
        raise RuntimeError("launch refused")

    monkeypatch.setattr(LocalEngine, "_merge_launch", boom)
    with pytest.raises(RuntimeError, match="launch refused"):
        issue(eng, [B[1], B[2], A[3]], now)
    assert eng._sidecars == []
    assert eng.shadow.contains(fps_of([B[1], B[2]])).all()
    assert eng.shadow.ram_rows == rows_before
    assert eng.tier_counts()["merge_launches"] == 0  # the fill's were the miss path's
    monkeypatch.undo()
    # and the keys are served from the state they had
    got = check(eng, [B[1], B[2], A[3]], now)
    assert got.remaining.tolist() == [LIMIT - 2, LIMIT - 2, LIMIT - 3]
    assert eng.stats.lost_live == 0


# ------------------------------------------- (e) the counters


@pytest.mark.parametrize("case", ["zipf", "promote_and_hit", "returned_promote", "redispatch"])
def test_promoted_ahead_and_promoted_count_what_the_reference_says(case):
    n_buckets = SLOTS // K
    chunks = case_chunks(case, n_buckets)
    _got, _back, state, eng = asyncio.run(served(chunks))
    table = StoredTable(n_buckets)
    for i, keys in enumerate(chunks):
        table.check_together(
            [fp_of(k) for k in keys], NOW + i * STEP_MS, 1, LIMIT, DURATION
        )
    # `served` read the tier's counts before its read-back, which promotes too
    assert state["promoted"] == table.promoted
    t = eng.tier_counts()
    assert 0 < t["promoted_ahead"] <= t["promoted"]
    # the read-back (hits = 0 over every key, in fill order) is two more
    # dispatches of the same engine: the reference serves them as well
    now = NOW + len(chunks) * STEP_MS
    for lo in range(0, N_KEYS, 1_000):
        table.check_together(
            [fp_of(k) for k in range(lo, min(lo + 1_000, N_KEYS))], now, 0,
            LIMIT, DURATION,
        )
    assert t["promoted"] == table.promoted
    assert t["promoted_ahead"] == table.promoted_ahead
    assert t["returned"] == table.returned
    # most come back ahead; the rest are the miss path's: at four tracked
    # keys a slot and 1,000 rows over 64 buckets a merge pushes out keys of
    # its own dispatch, and a bucket's ninth promote waits
    assert t["promoted_ahead"] > 0.5 * t["promoted"]
