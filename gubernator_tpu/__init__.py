"""gubernator_tpu — a TPU-native distributed rate-limiting framework.

A ground-up re-design of the capabilities of gubernator (reference:
/root/reference, pure Go) for TPU hardware:

* the counter hot path (token/leaky bucket mutation over millions of keys) runs
  as vectorized int64/f64 kernels over an HBM-resident hash-slotted
  struct-of-arrays state table (replaces reference algorithms.go + lrucache.go
  + workers.go);
* cluster key-ownership maps onto TPU mesh axes via shard_map/pjit (replaces
  reference replicated_hash.go node spread);
* GLOBAL-behavior hit aggregation + authoritative broadcast become mesh
  collectives over ICI/DCN (replaces reference global.go gRPC fan-out);
* a thin host front door keeps the gRPC/HTTP API surface, peer discovery,
  health and Prometheus metrics (reference daemon.go / gubernator.go).

int64 timestamps (epoch milliseconds) and float64 leaky-bucket remainders
require jax x64 mode, enabled at import.
"""

import os

import jax

jax.config.update("jax_enable_x64", True)

# Persistent kernel-compile cache: the decision kernel compiles per batch
# shape and math variant (seconds to a minute each on TPU), so a daemon
# restart or a second process of the same run should hit the cache. With
# JAX_COMPILATION_CACHE_DIR set JAX reads it itself and nothing is set
# here; otherwise the cache lives at ONE fixed path inside the checkout,
# resolved from this file — the directory is part of what makes a cache
# hit, so it must not depend on $HOME, the cwd, a pid or a temp name. The
# server, chip_smoke.py's children and pytest all share it.
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache"
)
if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
if not os.environ.get("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"):
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)

from gubernator_tpu.types import (  # noqa: E402
    Algorithm,
    Behavior,
    Status,
    RateLimitRequest,
    RateLimitResponse,
    has_behavior,
)

__version__ = "0.1.0"

__all__ = [
    "Algorithm",
    "Behavior",
    "Status",
    "RateLimitRequest",
    "RateLimitResponse",
    "has_behavior",
    "__version__",
]
