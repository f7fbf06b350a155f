"""Shared benchmark harness utilities.

Every benchmark mirrors one table/figure of the paper on synthetic
UCI-analogue data (offline container), scaled by --scale so CPU runs finish
in minutes while preserving the comparisons. Results go to
experiments/benchmarks/<name>.csv + .md.
"""

from __future__ import annotations

import csv
import datetime
import json
import os
import subprocess
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import ExactGP, ExactGPConfig, gaussian_nll, rmse
from repro.data import make_regression_dataset

OUT_DIR = os.environ.get("REPRO_BENCH_OUT", "experiments/benchmarks")


def _git_sha() -> str | None:
    try:
        return subprocess.run(
            ["git", "rev-parse", "HEAD"], capture_output=True, text=True,
            timeout=5, cwd=os.path.dirname(os.path.abspath(__file__)),
        ).stdout.strip() or None
    except (OSError, subprocess.SubprocessError):
        return None


def bench_meta() -> dict:
    """Provenance block embedded in every BENCH JSON: enough to answer
    "what produced this number" when comparing across PRs/machines."""
    import jaxlib

    from repro.kernels.ops import resolve_interpret

    devices = jax.devices()
    return {
        "git_sha": _git_sha(),
        "jax_version": jax.__version__,
        "jaxlib_version": jaxlib.__version__,
        "device_kind": devices[0].device_kind if devices else None,
        "device_count": jax.device_count(),
        "platform": jax.default_backend(),
        # Pallas kernels run under pl.pallas_call(interpret=...) on the
        # CPU — timing columns from interpret-mode runs are shapes, not
        # speeds
        "interpret_mode": resolve_interpret(),
        "timestamp_utc": datetime.datetime.now(
            datetime.timezone.utc).isoformat(timespec="seconds"),
    }

# CPU-scale dataset list: name -> max_points cap (None = paper size).
# --scale full lifts the caps (hardware run).
CPU_DATASETS = {
    "poletele": 2400,
    "elevators": 2400,
    "bike": 2400,
    "kin40k": 3600,
    "protein": 3600,
}


def write_rows(name: str, header: list, rows: list):
    os.makedirs(OUT_DIR, exist_ok=True)
    path = os.path.join(OUT_DIR, f"{name}.csv")
    with open(path, "w", newline="") as f:
        w = csv.writer(f)
        w.writerow(header)
        w.writerows(rows)
    md = os.path.join(OUT_DIR, f"{name}.md")
    with open(md, "w") as f:
        f.write("| " + " | ".join(header) + " |\n")
        f.write("|" + "---|" * len(header) + "\n")
        for r in rows:
            f.write("| " + " | ".join(
                f"{v:.4g}" if isinstance(v, float) else str(v)
                for v in r) + " |\n")
    # machine-readable companion: one BENCH_<name>.json per CSV so the
    # perf trajectory across PRs is diffable/scriptable without parsing
    # the human-facing tables (records stay keyed by column name)
    def jsonable(v):
        # numpy scalars -> Python numbers so trackers never re-parse
        # strings; anything else non-native falls back to str
        if isinstance(v, (np.floating, np.integer)):
            return v.item()
        return str(v)

    summary = {
        "bench": name,
        "backend": jax.default_backend(),
        "device_count": jax.device_count(),
        "meta": bench_meta(),
        "header": list(header),
        "records": [dict(zip(header, r)) for r in rows],
        # obs registry snapshot at write time: CG totals, autotune
        # hit/miss, solver step modes, serve distributions — the counters
        # behind the rows, for cross-PR perf archaeology
        "metrics": obs.registry().snapshot(),
    }
    with open(os.path.join(OUT_DIR, f"BENCH_{name}.json"), "w") as f:
        json.dump(summary, f, indent=1, default=jsonable)
        f.write("\n")
    print(f"[bench] wrote {path}")
    return path


def load(name: str, cap: int | None, seed: int = 0):
    s = make_regression_dataset(name, seed=seed, max_points=cap)
    to32 = lambda a: jnp.asarray(a, jnp.float32)
    return (to32(s.X_train), to32(s.y_train), to32(s.X_val), to32(s.y_val),
            to32(s.X_test), to32(s.y_test))


def eval_exact(gp: ExactGP, X, y, Xt, yt, params, key):
    t0 = time.time()
    cache = gp.precompute(X, y, params, key)
    pre_s = time.time() - t0
    t0 = time.time()
    mean, var = gp.predict(X, Xt, params, cache)
    jax.block_until_ready(mean)
    pred_s = time.time() - t0
    return (float(rmse(mean, yt)), float(gaussian_nll(mean, var, yt)),
            pre_s, pred_s)


def default_gp(n: int, backend: str = "partitioned",
               compute_dtype: str | None = None) -> ExactGP:
    """Benchmark-default ExactGP on the given KernelOperator backend.

    backend/compute_dtype select the MVM engine (see repro.core.operators):
    "dense" | "partitioned" | "pallas", optionally with the bf16-compute
    fast path — every benchmark can sweep them without other changes.
    """
    return ExactGP(ExactGPConfig(
        kernel="matern32",
        precond_rank=min(100, max(20, n // 50)),
        row_block=512,
        train_max_cg_iters=50,
        pred_max_cg_iters=400,
        lanczos_rank=min(128, n // 2),
        backend=backend,
        compute_dtype=compute_dtype,
    ))
