"""Native host ingress/egress — build + load the _guberhost C++ extension.

`load()` returns the extension module (building it with g++ on first use) or
None when no toolchain is available; callers keep a pure-Python fallback.
The build is a single translation unit against Python.h only — no
libprotobuf, no numpy C API (buffers cross as bytes; numpy wraps them with
np.frombuffer zero-copy).

Entry points, each one pass with the GIL released and each with a Python
twin that the tests hold it to: `parse_get_rate_limits` (request bytes →
columns + compact-wire lanes; service/wire.py), `encode_responses` and
`encode_responses_many` (response columns → wire bytes, one RPC or every
plain RPC of a dispatch), `stage_wire_chunk` (a fused chunk's lanes → its
grid and every pass behind it, staged; ops/wire.stage_wire_chunk, twin
ops/engine._stage_chunk_numpy), `finish_wire_chunk` (the way out of the
same dispatch: its passes' fetched egress blocks → its response columns,
written in place, and the rows to retry; ops/wire.finish_wire_chunk, twin
ops/engine._finish_numpy), `fp_index_find` and `fp_index_place` (the
shadow tier's fingerprint index probed and filled a batch at a time over
the caller's two arrays; tier/shadow._FpIndex holds the NumPy twins), the
hashes `fingerprint64` and `fnv1a32`, and `set_error_strings` (the
encoder's table, set once by `load`).
"""

from __future__ import annotations

import contextlib
import glob
import hashlib
import importlib.machinery
import importlib.util
import logging
import os
import subprocess
import sysconfig
from typing import Optional

log = logging.getLogger("gubernator_tpu.native")

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "guberhost.cpp")
_CXXFLAGS = ("-O2", "-std=c++17", "-shared", "-fPIC")
_mod = None
_tried = False
# "built" (this process compiled it), "reused" (a binary of the same
# source hash was already there) or None (pure-Python door) — surfaced by
# /v1/debug/pipeline so a run that silently lost the parser can be refused
state: Optional[str] = None


def _so_path() -> str:
    """The binary's name carries the hash of the source and the flags it
    was built from, so a stale or foreign `.so` left in the tree (they are
    git-ignored, and tools copy the tree as it stands) can never be loaded
    for a different guberhost.cpp."""
    h = hashlib.sha256(" ".join(_CXXFLAGS).encode())
    with open(_SRC, "rb") as f:
        h.update(f.read())
    suffix = sysconfig.get_config_var("EXT_SUFFIX") or ".so"
    return os.path.join(_DIR, f"_guberhost_{h.hexdigest()[:16]}{suffix}")


def build(force: bool = False) -> Optional[str]:
    """Compile the extension in-place; returns the .so path or None."""
    global state
    so = _so_path()
    if not force and os.path.exists(so):
        state = "reused"
        return so
    include = sysconfig.get_paths()["include"]
    tmp = f"{so}.{os.getpid()}.tmp"  # concurrent builders never share a file
    cmd = ["g++", *_CXXFLAGS, f"-I{include}", "-o", tmp, _SRC]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, so)
    except (OSError, subprocess.SubprocessError) as exc:
        detail = getattr(exc, "stderr", b"") or b""
        log.warning(
            "native guberhost build failed (%s): %s — using the Python path",
            exc, detail.decode(errors="replace")[:500],
        )
        return None
    for old in glob.glob(os.path.join(_DIR, "_guberhost*.so")):
        if old != so:  # binaries of earlier sources
            with contextlib.suppress(OSError):
                os.remove(old)
    state = "built"
    return so


def load():
    """The extension module, building if needed; None if unavailable."""
    global _mod, _tried, state
    if _mod is not None or _tried:
        return _mod
    _tried = True
    if os.environ.get("GUBER_NATIVE", "").lower() in ("0", "false", "off"):
        return None
    so = build()
    if so is None:
        return None
    try:
        # the file name carries a hash, the module (and its PyInit symbol)
        # does not: load by path under the fixed name
        name = "gubernator_tpu.native._guberhost"
        spec = importlib.util.spec_from_loader(
            name, importlib.machinery.ExtensionFileLoader(name, so)
        )
        _mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(_mod)
        # the engine's error codes as the wire says them, for the encoder
        # that reads an `err` column (encode_responses_many)
        from gubernator_tpu.ops.batch import ERROR_STRINGS

        _mod.set_error_strings(
            [ERROR_STRINGS.get(c, "") for c in range(max(ERROR_STRINGS) + 1)]
        )
    except ImportError as exc:  # pragma: no cover - toolchain-specific
        log.warning("native guberhost import failed: %s", exc)
        _mod = None
        state = None
    return _mod
