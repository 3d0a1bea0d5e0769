"""Test fixture: force a virtual 8-device CPU platform before jax initializes.

Multi-chip sharding (parallel/) is exercised on a host-platform mesh exactly as
the reference exercises its cluster in-process (reference cluster/cluster.go
boots N daemons in one test binary); what only a chip can show (Mosaic
lowering, the TPU-only default paths) is covered by chip_smoke.py.
"""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
# the suite compiles a handful of batch-shape variants of the decision
# kernel; they persist in the package's fixed in-checkout cache directory
# (gubernator_tpu.COMPILE_CACHE_DIR, or JAX_COMPILATION_CACHE_DIR when set)
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.5")

import pytest  # noqa: E402


@pytest.fixture
def frozen_now() -> int:
    """A fixed epoch-ms 'now' — the analog of holster/clock frozen time
    (reference Makefile:20 -tags holster_test_mode). The kernel takes time from
    request.created_at, so tests simply pass timestamps."""
    return 1_700_000_000_000
