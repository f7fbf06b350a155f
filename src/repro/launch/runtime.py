"""Process-wide JAX settings the entry points make before any tracing.

`setup_runtime` is called by entry points (`launch.train`,
`launch.serve_gp`, `benchmarks.run`, `chip_smoke.py`), never when a module
is imported. It does two things:

* float32 matmuls run as float32. XLA on a TPU runs a float32 matmul at
  DEFAULT precision as one bf16 pass; on a v5e that put the float32
  K_hat @ V about 1e3 times outside the fp32 tolerance of a float64
  reference (the distance expansion ||x||^2 + ||y||^2 - 2<x,y> cancels
  for nearby points). HIGH (3 passes) still missed; HIGHEST (6 passes)
  met it. The default precision is set here once, so every XLA matmul
  of the program (distances, slabs, preconditioner, solves, Lanczos)
  gets it; JAX applies it to float32 operands only, so the bf16 compute
  path is unchanged. The Pallas kernels choose their dot precision
  themselves (`repro.kernels.kmvm.mxu_precision`). It has no effect on
  the CPU.
* JAX's persistent compilation cache is placed from outside: when
  `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself and nothing
  here sets another path. Otherwise it goes to `<checkout>/.jax_cache`.
  The path is fixed because it is part of what a later process must match
  to hit the cache.
"""

from __future__ import annotations

import os

import jax

# <checkout>/src/repro/launch/runtime.py -> <checkout>
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


MATMUL_PRECISION = "highest"


def setup_runtime() -> str:
    """Set float32 matmul precision and turn the persistent compilation
    cache on; returns the cache directory."""
    jax.config.update("jax_default_matmul_precision", MATMUL_PRECISION)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
