"""Slot-layout descriptors: algorithm-specialized compressed table rows.

BENCH_r05 pinned the decision kernel's scaling wall on HBM bandwidth: at
100M live keys every probe and every sweep block drags 16×i32 (64 B) of
slot state per slot through HBM regardless of algorithm, and decisions/s
falls 13.4M → 9.8M from 10M → 100M keys. But a single-algorithm table does
not NEED 16 fields: an all-GCRA table is fully described by fp + TAT +
config (the TAT doubles as the expiry — ops/math.py "State is
self-expiring"), an all-token table by fp + remaining + expiry + config.
PR 10 already specializes the decision *graph* per algorithm
(engine._math_mode); this module extends the specialization to the table
bytes themselves.

A **SlotLayout** describes everything a surface needs to address slot
bytes: fields per slot (``F``), bytes/slot, where the fingerprint and
expiry pairs live, which math modes the layout can serve, and the
pack/unpack rules to and from the canonical 16-field full layout. Every
layer that touches slot bytes — the kernel's probe/write
(ops/kernel2.py), handoff extract/merge (ops/table2.py,
service/handoff.py), checkpoint frames (ops/checkpoint.py, store.py),
the telemetry scan (ops/telemetry.py) and the mesh staging
(parallel/) — goes through the descriptor instead of the module
constants, so a future layout (f32/quantized lanes, tiered cold rows) is
a registry entry, not a rewrite.

Three layouts ship:

* ``full``   — the existing 16×i32 (64 B) row, bit-compatible with every
  table written before this module existed. Pack/unpack are identity.
* ``gcra32`` — 8×i32 (32 B) for all-GCRA tables:
  ``fp_lo fp_hi tat_lo tat_hi limit burst dur_lo meta`` where
  ``meta = dur_hi[0:23] | status<<23``. The TAT pair IS the expiry pair
  (exp ≡ TAT — the kernel's own self-expiry rule) and the stored stamp is
  dropped (GCRA math never reads it; the conservative merge's
  config-newest-wins then defaults to the incoming side, documented in
  docs/layout.md).
* ``token32`` — 8×i32 (32 B) for all-token tables:
  ``fp_lo fp_hi rem_i limit exp_lo exp_hi dur_lo meta`` with the same
  ``meta`` packing. The stamp is derived as ``exp - duration`` — exact
  for every non-Gregorian token write (the token math maintains
  ``exp == stamp + stored_duration`` invariantly); Gregorian batches
  migrate the table to ``full`` first (``greg_ok``).

**Conversion contract.** Cross-layout state movement (checkpoint replay
under a different layout, handoff between daemons booted with different
layouts, layout migration) always round-trips through the canonical
full-width row: ``unpack`` → full 16-field slots → (merge2 / pack). The
conservative-merge rules (remaining=min, expiry=max, aux=max,
OVER-sticks) therefore apply verbatim whatever layouts the two sides run
— replay/transfer can only under-grant.

**Selection.** ``resolve_layout(mode, math_hint)`` implements the
``GUBER_SLOT_LAYOUT`` knob: ``full`` forces the bit-compatible layout,
``gcra32``/``token32`` force a packed one, and ``auto``/``packed`` pick
the packed layout matching a single-algorithm math hint (``gcra`` /
``token``) when the caller provides one, full otherwise — so default
deployments behave exactly like today and single-algorithm fleets opt in
with one env var. A packed table that sees off-family traffic is
migrated to ``full`` by the engine (one in-place unpack of the rows
array) rather than serving wrong bytes.
"""

from __future__ import annotations

import os

import jax.numpy as jnp
import numpy as np

K = 8  # slots per bucket — shared with table2 by construction

# canonical full-layout field indices (ops/table2.py)
_FP_LO, _FP_HI, _LIMIT, _BURST, _REM_I, _FLAGS = 0, 1, 2, 3, 4, 5
_DUR_LO, _DUR_HI, _STAMP_LO, _STAMP_HI, _EXP_LO, _EXP_HI = 6, 7, 8, 9, 10, 11
_TOUCH = 14  # ops/table2.TOUCH
_REMF_HI, _REMF_LO = 12, 13

_ALGO_TOKEN = 0
_ALGO_GCRA = 2

_DUR_HI_MASK = 0x7FFFFF  # 23 bits of dur_hi → durations < 2^55 ms
_STATUS_SHIFT = 23


def _xp(arr):
    """numpy for host arrays, jnp for device arrays/tracers — the same
    pack/unpack source serves both the traced kernel and host converters."""
    return np if isinstance(arr, np.ndarray) else jnp


class SlotLayout:
    """One slot layout: geometry + pack/unpack to the canonical full row.

    Instances are module-level singletons (identity hash/eq), which makes
    them valid jit static arguments and Table2 pytree aux data — a table's
    layout is part of its treedef, so every compiled program is keyed by
    it automatically."""

    __slots__ = (
        "name", "code", "F", "modes", "algos", "greg_ok",
        "exp_lo_i", "exp_hi_i",
    )

    def __init__(self, name, code, F, modes, algos, greg_ok,
                 exp_lo_i, exp_hi_i):
        self.name = name
        self.code = code  # frame/wire version byte (full=0 — legacy value)
        self.F = F  # int32 fields per slot
        self.modes = modes  # math modes this layout can serve
        self.algos = algos  # storable algorithm ids (None = all)
        self.greg_ok = greg_ok  # Gregorian batches representable?
        # expiry pair position in the PACKED row (fp is always fields 0/1 —
        # the cross-layout invariant fps_from_slots and the extract filters
        # rely on)
        self.exp_lo_i = exp_lo_i
        self.exp_hi_i = exp_hi_i

    @property
    def row(self) -> int:
        return K * self.F

    @property
    def slot_bytes(self) -> int:
        return self.F * 4

    def __repr__(self) -> str:  # pragma: no cover - debugging nicety
        return f"SlotLayout({self.name}, F={self.F})"

    # --------------------------------------------------------- conversion

    def unpack(self, slots):
        """(..., F) packed slot fields → (..., 16) canonical full fields."""
        if self is FULL:
            return slots
        xp = _xp(slots)
        p = lambda i: slots[..., i]
        zero = xp.zeros_like(p(0))
        dur_hi = p(7) & _DUR_HI_MASK
        status = (p(7) >> _STATUS_SHIFT) & 0xFF
        if self is GCRA32:
            flags = (status << 8) | _ALGO_GCRA
            # EXP ≡ TAT; the aux (REMF) pair is the same raw TAT
            cols = [p(0), p(1), p(4), p(5), zero, flags, p(6), dur_hi,
                    zero, zero, p(2), p(3), p(3), p(2), zero, zero]
        elif self is TOKEN32:
            flags = (status << 8) | _ALGO_TOKEN
            # stamp = exp - duration (token invariant; Gregorian excluded
            # by greg_ok)
            i64 = xp.int64
            exp = (p(5).astype(i64) << 32) | (p(4).astype(i64) & 0xFFFFFFFF)
            dur = (dur_hi.astype(i64) << 32) | (p(6).astype(i64) & 0xFFFFFFFF)
            stamp = exp - dur
            st_lo = (stamp & 0xFFFFFFFF).astype(p(0).dtype)
            st_hi = (stamp >> 32).astype(p(0).dtype)
            cols = [p(0), p(1), p(3), zero, p(2), flags, p(6), dur_hi,
                    st_lo, st_hi, p(4), p(5), zero, zero, zero, zero]
        else:  # pragma: no cover - registry guards
            raise ValueError(f"no unpack rule for layout {self.name}")
        return xp.stack(cols, axis=-1)

    def pack(self, full):
        """(..., 16) canonical full fields → (..., F) packed fields.
        Lossy by design: fields the layout's algorithm family never reads
        are dropped (see the module docstring's per-layout notes)."""
        if self is FULL:
            return full
        xp = _xp(full)
        g = lambda i: full[..., i]
        status = (g(_FLAGS) >> 8) & 0xFF
        meta = (g(_DUR_HI) & _DUR_HI_MASK) | (status << _STATUS_SHIFT)
        if self is GCRA32:
            # raw aux pair (REMF_LO = lo32, REMF_HI = hi32) is the TAT
            cols = [g(_FP_LO), g(_FP_HI), g(_REMF_LO), g(_REMF_HI),
                    g(_LIMIT), g(_BURST), g(_DUR_LO), meta]
        elif self is TOKEN32:
            cols = [g(_FP_LO), g(_FP_HI), g(_REM_I), g(_LIMIT),
                    g(_EXP_LO), g(_EXP_HI), g(_DUR_LO), meta]
        else:  # pragma: no cover - registry guards
            raise ValueError(f"no pack rule for layout {self.name}")
        return xp.stack(cols, axis=-1)

    def unpack_rows(self, rows):
        """(..., K·F) packed bucket rows → (..., K·16) full bucket rows."""
        if self is FULL:
            return rows
        shape = rows.shape[:-1]
        out = self.unpack(rows.reshape(shape + (K, self.F)))
        return out.reshape(shape + (K * 16,))

    def pack_rows(self, rows_full):
        """(..., K·16) full bucket rows → (..., K·F) packed bucket rows."""
        if self is FULL:
            return rows_full
        shape = rows_full.shape[:-1]
        out = self.pack(rows_full.reshape(shape + (K, 16)))
        return out.reshape(shape + (K * self.F,))

    def idle_ref(self, slots):
        """Per-slot last-activity reference (ms, int64) for the tiering
        idle sweep (gubernator_tpu/tier/): the stored stamp (UpdatedAt)
        when the layout keeps one, else ``exp - duration`` — exact for
        token32 (the pack derives the stamp the same way) and the best
        available proxy for gcra32 (the stamp is dropped; TAT-duration
        under-estimates activity, which only makes the sweep demote
        LATER, never wrongly expire state — demote/fault-back is
        correctness-preserving either way). Works on numpy and traced
        arrays ((…, F) slot fields in THIS layout)."""
        xp = _xp(slots)
        i64 = xp.int64
        p = lambda i: slots[..., i]
        exp = (p(self.exp_hi_i).astype(i64) << 32) | (
            p(self.exp_lo_i).astype(i64) & 0xFFFFFFFF
        )
        if self is FULL:
            dur_hi = p(_DUR_HI)
        else:
            dur_hi = p(7) & _DUR_HI_MASK
        dur = (dur_hi.astype(i64) << 32) | (p(_DUR_LO).astype(i64) & 0xFFFFFFFF)
        ref = exp - dur
        if self is FULL:
            stamp = (p(_STAMP_HI).astype(i64) << 32) | (
                p(_STAMP_LO).astype(i64) & 0xFFFFFFFF
            )
            ref = xp.where(stamp != 0, stamp, ref)
            # a tiered table's programs write the time of a row's last use
            # into the TOUCH lane (ops/table2.TOUCH, units of 1,024 ms; 0
            # on every row an untiered program wrote): a token bucket's
            # stamp is its window's creation, and a key in use is not idle
            ref = xp.maximum(ref, p(_TOUCH).astype(i64) << 10)
        return ref

    # ---------------------------------------------------------- predicates

    def supports_math(self, math: str) -> bool:
        return math in self.modes

    def supports_algos(self, algo: np.ndarray, active=None) -> bool:
        """Host-side: can every ACTIVE row's algorithm live in this
        layout? (padding rows carry algo=0 and never persist)."""
        if self.algos is None:
            return True
        a = np.asarray(algo)
        if active is not None:
            a = a[np.asarray(active)]
        if a.size == 0:
            return True
        ok = np.zeros(a.shape, dtype=bool)
        for v in self.algos:
            ok |= a == v
        return bool(ok.all())


FULL = SlotLayout(
    name="full", code=0, F=16,
    modes=("token", "gcra", "int", "mixed"),
    algos=None, greg_ok=True, exp_lo_i=_EXP_LO, exp_hi_i=_EXP_HI,
)
GCRA32 = SlotLayout(
    name="gcra32", code=1, F=8,
    modes=("gcra",), algos=(_ALGO_GCRA,), greg_ok=True,
    exp_lo_i=2, exp_hi_i=3,  # the TAT pair IS the expiry pair
)
TOKEN32 = SlotLayout(
    name="token32", code=2, F=8,
    modes=("token",), algos=(_ALGO_TOKEN,), greg_ok=False,
    exp_lo_i=4, exp_hi_i=5,
)

LAYOUTS = {l.name: l for l in (FULL, GCRA32, TOKEN32)}
_BY_CODE = {l.code: l for l in LAYOUTS.values()}


def layout_by_code(code: int) -> SlotLayout:
    """Layout for a frame/wire version byte; raises on unknown codes (a
    reader must refuse bytes it cannot interpret, not guess)."""
    l = _BY_CODE.get(int(code))
    if l is None:
        raise ValueError(f"unknown slot-layout code {code}")
    return l


def layout_for_row(row_lanes: int) -> SlotLayout:
    """Layout inferred from a rows array's lane width. Only the full
    layout's 128-lane row is unambiguous — both packed layouts are 64
    lanes wide, so packed tables must carry their layout explicitly
    (Table2 aux, frame version byte, TransferState layout field)."""
    if row_lanes == FULL.row:
        return FULL
    raise ValueError(
        f"cannot infer slot layout from row width {row_lanes}; "
        "packed layouts must be passed explicitly"
    )


def slot_layout_env() -> str:
    """The GUBER_SLOT_LAYOUT knob: auto | full | packed | gcra32 | token32
    (see resolve_layout). Read per engine construction."""
    v = os.environ.get("GUBER_SLOT_LAYOUT", "auto")
    if v not in ("auto", "full", "packed") and v not in LAYOUTS:
        raise ValueError(
            f"GUBER_SLOT_LAYOUT must be auto, full, packed or a layout "
            f"name ({', '.join(LAYOUTS)}), got {v!r}"
        )
    return v


def resolve_layout(mode=None, math_hint=None) -> SlotLayout:
    """Resolve the table layout for an engine.

    `mode`: explicit engine arg (wins) or the GUBER_SLOT_LAYOUT env —
    "full" (today's bytes, pinned bit-identical), a layout name
    ("gcra32"/"token32"), or "auto"/"packed" which pick the packed layout
    matching `math_hint` ("gcra" → gcra32, "token" → token32) and fall
    back to full when the hint is absent or multi-algorithm — so a
    default boot without a hint is byte-identical to every earlier PR."""
    mode = mode or slot_layout_env()
    if mode in LAYOUTS:
        return LAYOUTS[mode]
    if mode == "full":
        return FULL
    if mode in ("auto", "packed"):
        if math_hint == "gcra":
            return GCRA32
        if math_hint == "token":
            return TOKEN32
        return FULL
    raise ValueError(f"unknown slot-layout mode {mode!r}")
