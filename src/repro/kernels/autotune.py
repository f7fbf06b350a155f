"""(bm, bn) tile-size autotuner for the fused Pallas kernels.

The fused kernel's throughput is a function of tile geometry: bm/bn set
the VMEM working set, the MXU utilization per step, and the grid's step
count (in interpret mode, each grid step pays interpreter overhead, so
fewer/larger tiles usually win; on TPU the pipeliner prefers tiles that
double-buffer inside VMEM). The right choice depends on dtype, backend
(TPU vs interpret), and problem shape — none of which the static defaults
can see. This module sweeps a small candidate set once per
(platform, dtype, kernel structure, shape bucket) and caches the winner
on disk, so the cost is paid once per machine, not once per process.

Cache design
------------
* The key is a plain dict of everything the measurement depends on:
  platform, interpret flag, compute dtype, the STATIC component structure
  of the fused pass, and the (m, n, d, t) shape bucketed to the next
  power of two (a 50k-row problem reuses the 65536-bucket entry; exact
  shapes would make the cache useless under data growth).
* The on-disk filename is the sha1 of the canonical-JSON key — content
  hashing, no coordination, safe across concurrent processes (writes go
  through an atomic rename).
* Entries store the full timing table, so `BENCH`/debug tooling can see
  why a tile was chosen; lookups only read (bm, bn).
* A process-level memo avoids re-reading the file. Lookups (memo/disk)
  are safe from inside jit traces — shapes are static — but the SWEEP is
  not (a launch timed under an active trace returns tracers, not
  numbers), so a cache miss while tracing falls back to the static
  defaults without sweeping or memoizing; `prewarm` exists precisely so
  callers populate the cache eagerly before jitting.

Determinism: candidates are swept in a fixed order and ties break toward
the FIRST candidate at the minimal time (then smaller bm, bn), so a fixed
`measure` function always yields the same choice — pinned by
tests/test_autotune.py.
"""

from __future__ import annotations

import hashlib
import json
import os
import time
from typing import Callable

import jax
import jax.numpy as jnp

from repro import obs

# Sweep order is part of the determinism contract (ties break earliest).
# Small on purpose: 5 candidates x ~3 timed reps per cache miss.
DEFAULT_CANDIDATES: tuple[tuple[int, int], ...] = (
    (128, 128),
    (128, 256),
    (256, 256),
    (256, 512),
    (512, 512),
)

_MEMO: dict[str, tuple[int, int]] = {}


def default_cache_dir() -> str:
    """`REPRO_AUTOTUNE_CACHE`, else `<checkout>/.autotune_cache`: a fixed
    path inside the checkout, so a run reads and writes nothing outside it."""
    env = os.environ.get("REPRO_AUTOTUNE_CACHE")
    if env:
        return env
    from repro.launch.runtime import CHECKOUT

    return os.path.join(CHECKOUT, ".autotune_cache")


def shape_bucket(x: int) -> int:
    """Next power of two (>= 1): the cache's shape granularity."""
    b = 1
    while b < x:
        b *= 2
    return b


def cache_key(components, m: int, n: int, d: int, t: int, *,
              compute_dtype: str, interpret: bool,
              platform: str | None = None) -> dict:
    """Everything the winning tile depends on, as a canonical plain dict."""
    return {
        "platform": platform if platform is not None
        else jax.default_backend(),
        "interpret": bool(interpret),
        "compute_dtype": str(compute_dtype),
        "components": [list(kinds) for kinds in components],
        "m": shape_bucket(m),
        "n": shape_bucket(n),
        "d": shape_bucket(d),
        "t": shape_bucket(t),
    }


def key_hash(key: dict) -> str:
    return hashlib.sha1(
        json.dumps(key, sort_keys=True).encode()).hexdigest()


def _default_measure(key: dict) -> Callable[[int, int], float]:
    """Time one fused launch at the key's bucketed shapes.

    Operands are synthesized zeros — the kernel has no data-dependent
    control flow, so timing is data-independent — and the launch is the
    REAL `kmvm_pallas` path (jitted; one warmup call compiles).
    """
    from repro.kernels import ops  # lazy: ops imports this module
    from repro.kernels.kmvm import kmvm_pallas, scalar_layout

    components = tuple(tuple(kinds) for kinds in key["components"])
    cdt = jnp.dtype(key["compute_dtype"])
    interpret = key["interpret"]
    m, n, d, t = key["m"], key["n"], key["d"], key["t"]
    L = scalar_layout(components)
    scalars = jnp.ones((1, L), jnp.float32)

    def measure(bm: int, bn: int) -> float:
        bm_eff, bn_eff, lane = ops._tile_geometry(m, n, bm, bn, cdt,
                                                  interpret)
        d_pad = ops._round_up(d, lane)
        t_pad = ops._round_up(t, lane)
        Xi = jnp.zeros((ops._round_up(m, bm_eff), d_pad), cdt)
        Xj = jnp.zeros((ops._round_up(n, bn_eff), d_pad), cdt)
        V = jnp.zeros((ops._round_up(n, bn_eff), t_pad), cdt)

        def run():
            return kmvm_pallas(components, Xi, Xj, V, scalars,
                               bm=bm_eff, bn=bn_eff, interpret=interpret,
                               compute_dtype=str(cdt))

        run().block_until_ready()  # compile outside the timed region
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            run().block_until_ready()
            best = min(best, time.perf_counter() - t0)
        return best

    return measure


def _under_jit_trace() -> bool:
    """True while a jit trace is active: an array made here is then a
    Tracer, and so would be a timed launch's result. (Eager autodiff and
    vmap still compute concrete values, so a sweep there is fine.)"""
    return isinstance(jnp.zeros(()), jax.core.Tracer)


def autotune_tiles(
    components,
    m: int,
    n: int,
    d: int,
    t: int,
    *,
    compute_dtype: str = "float32",
    interpret: bool | None = None,
    candidates: tuple[tuple[int, int], ...] | None = None,
    measure: Callable[[int, int], float] | None = None,
    cache_dir: str | None = None,
) -> tuple[int, int]:
    """The cached (bm, bn) for this (structure, dtype, backend, shape
    bucket) — swept and persisted on first sight.

    measure: (bm, bn) -> seconds; injectable for tests. The default times
    a real fused launch at the bucketed shapes.
    """
    from repro.kernels.ops import resolve_interpret  # lazy: ops -> kmvm

    interpret = resolve_interpret(interpret)
    key = cache_key(components, m, n, d, t,
                    compute_dtype=compute_dtype, interpret=interpret)
    h = key_hash(key)
    if h in _MEMO:
        obs.counter("autotune.hits").inc()
        return _MEMO[h]

    cdir = cache_dir if cache_dir is not None else default_cache_dir()
    path = os.path.join(cdir, h + ".json")
    try:
        with open(path) as f:
            entry = json.load(f)
        choice = (int(entry["bm"]), int(entry["bn"]))
        _MEMO[h] = choice
        obs.counter("autotune.hits").inc()
        return choice
    except (OSError, ValueError, KeyError):
        pass

    if _under_jit_trace():
        # cache miss under an active trace: a timed launch would return
        # tracers. Fall back to the static defaults and do NOT memoize,
        # so a later eager call (prewarm) can still run the sweep.
        from repro.kernels.kmvm import DEFAULT_BM, DEFAULT_BN
        obs.counter("autotune.trace_fallbacks").inc()
        return DEFAULT_BM, DEFAULT_BN

    # miss: sweep. The historical code swallowed the outcome (the winner,
    # the timings, and the cost of finding it were invisible outside the
    # JSON file); the registry + span now carry it to obs_report.
    obs.counter("autotune.misses").inc()
    if measure is None:
        measure = _default_measure(key)
    cands = candidates if candidates is not None else DEFAULT_CANDIDATES
    timings = {}
    best = None
    sweep_t0 = time.perf_counter()
    with obs.span("autotune_sweep", candidates=len(cands),
                  m=key["m"], n=key["n"]) as sp:
        for bm, bn in cands:
            secs = float(measure(bm, bn))
            timings[f"{bm}x{bn}"] = secs
            # strict < : ties break toward the earliest candidate in sweep
            if best is None or secs < best[0]:
                best = (secs, bm, bn)
        sp.set(bm=best[1], bn=best[2])
    choice = (best[1], best[2])
    sweep_ms = (time.perf_counter() - sweep_t0) * 1e3
    obs.counter("autotune.sweeps").inc()
    obs.histogram("autotune.sweep_ms").observe(sweep_ms)

    os.makedirs(cdir, exist_ok=True)
    tmp = path + f".tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump({"key": key, "bm": choice[0], "bn": choice[1],
                   "timings": timings}, f, indent=1, sort_keys=True)
    os.replace(tmp, path)  # atomic: concurrent processes race benignly
    _MEMO[h] = choice
    return choice


def clear_memo() -> None:
    """Drop the process-level memo (tests; disk entries are untouched)."""
    _MEMO.clear()


def tiles_for_spec(kernel, params, m: int, n: int, d: int, t: int, *,
                   compute_dtype=None, interpret: bool | None = None,
                   cache_dir: str | None = None) -> tuple[int, int]:
    """Operator-facing entry: resolve the spec's fused-pass structure and
    return the autotuned tiles (or the static defaults when the spec has
    no fused pass to tune)."""
    from repro.kernels.kmvm import DEFAULT_BM, DEFAULT_BN
    from repro.kernels.ops import mvm_plan

    plan = mvm_plan(kernel, params)
    if not plan.passes:
        return DEFAULT_BM, DEFAULT_BN
    cdt = str(jnp.dtype(compute_dtype if compute_dtype is not None
                        else jnp.float32))
    return autotune_tiles(plan.passes[0].components, m, n, d, t,
                          compute_dtype=cdt, interpret=interpret,
                          cache_dir=cache_dir)


def prewarm(kernel, params, n: int, d: int, *, num_probes: int = 8,
            compute_dtype=None, interpret: bool | None = None,
            cache_dir: str | None = None) -> tuple[int, int]:
    """Resolve (and persist) the training-shape tiles OUTSIDE jit.

    The trainer calls this before jitting its full-data stages so the
    sweep's wall time lands in setup, not inside the first traced step
    (`repro.train.gp_trainer`). t is the mBCG RHS count: y + probes.
    """
    return tiles_for_spec(kernel, params, n, n, d, num_probes + 1,
                          compute_dtype=compute_dtype, interpret=interpret,
                          cache_dir=cache_dir)
