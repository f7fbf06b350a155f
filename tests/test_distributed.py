"""Distributed GP engine (shard_map on 8 fake devices, via subprocess —
the main test process must keep its single real device)."""

import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
jax.config.update("jax_enable_x64", True)
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P
from jax import shard_map

from repro.core import dense_khat, dense_mll, init_params, pivoted_cholesky
from repro.core.distributed import (
    DistMLLConfig, dist_kmvm, make_dist_preconditioner, make_geometry,
    make_mean_cache_solve, make_mll_value_and_grad, replicate, shard_vector,
)

mesh = jax.make_mesh((4, 2), ("data", "model"))
rng = np.random.default_rng(0)
n, d = 256, 6
X = jnp.asarray(rng.normal(size=(n, d)))
y = jnp.asarray(np.sin(np.asarray(X) @ rng.normal(size=d))
                + 0.1 * rng.normal(size=n))
params = init_params(noise=0.2, dtype=jnp.float64)
Khat = dense_khat("matern32", X, params)

for mode in ("1d", "2d"):
    geom = make_geometry(mesh, n, d, mode=mode, row_block=32)
    V = jnp.asarray(rng.normal(size=(n, 3)))

    f = jax.jit(shard_map(
        lambda Xr, V_loc: dist_kmvm(geom, "matern32", Xr, V_loc, params),
        mesh=mesh, in_specs=(P(), geom.vector_pspec()),
        out_specs=geom.vector_pspec(), check_vma=False))
    out = f(replicate(mesh, X), shard_vector(mesh, geom, V))
    assert float(jnp.max(jnp.abs(out - Khat @ V))) < 1e-10, mode

    # distributed pivoted cholesky == single-device (deterministic pivots)
    g = jax.jit(shard_map(
        lambda Xr: make_dist_preconditioner(geom, "matern32", Xr, params, 40).L_local,
        mesh=mesh, in_specs=(P(),), out_specs=geom.vector_pspec(),
        check_vma=False))
    L_dist = g(replicate(mesh, X))
    L_ref = pivoted_cholesky("matern32", X, params, 40)
    assert float(jnp.max(jnp.abs(L_dist - L_ref))) < 1e-9, mode

    cfg = DistMLLConfig(kernel="matern32", precond_rank=40, num_probes=16,
                        max_cg_iters=150, cg_tol=1e-8)
    vg = make_mll_value_and_grad(mesh, geom, cfg)
    loss, aux, grads = vg(replicate(mesh, X), shard_vector(mesh, geom, y),
                          replicate(mesh, params), jax.random.PRNGKey(0))
    g_dense = jax.grad(lambda p: -dense_mll("matern32", X, y, p) / n)(params)
    # quad-term-dominated grads must track the dense oracle
    for fname in ("raw_mean",):
        a, b = float(getattr(grads, fname)), float(getattr(g_dense, fname))
        assert abs(a - b) < 1e-6, (mode, fname, a, b)
    for fname in ("raw_lengthscale", "raw_outputscale", "raw_noise"):
        a, b = float(getattr(grads, fname)), float(getattr(g_dense, fname))
        assert abs(a - b) < 0.15 * abs(b) + 0.02, (mode, fname, a, b)

    solve = make_mean_cache_solve(mesh, geom, cfg, tol=1e-10, max_iters=400)
    a_cache, rel = solve(replicate(mesh, X), shard_vector(mesh, geom, y),
                         params)
    direct = jnp.linalg.solve(Khat, y)
    assert float(jnp.max(jnp.abs(a_cache - direct))) < 1e-7, mode

# 1d vs 2d MLL value consistency (same algorithm, different layout)
vals = []
for mode in ("1d", "2d"):
    geom = make_geometry(mesh, n, d, mode=mode, row_block=32)
    cfg = DistMLLConfig(kernel="matern32", precond_rank=40, num_probes=64,
                        max_cg_iters=150, cg_tol=1e-8)
    vg = make_mll_value_and_grad(mesh, geom, cfg)
    loss, _, _ = vg(replicate(mesh, X), shard_vector(mesh, geom, y),
                    replicate(mesh, params), jax.random.PRNGKey(0))
    vals.append(float(loss) * n)
assert abs(vals[0] - vals[1]) < 0.02 * abs(vals[0]), vals

print("DISTRIBUTED_OK")
"""


@pytest.mark.slow
def test_distributed_engine_8dev():
    env = dict(os.environ,
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..", "src"))
    out = subprocess.run([sys.executable, "-c", SCRIPT],
                         capture_output=True, text=True, env=env, timeout=900)
    assert "DISTRIBUTED_OK" in out.stdout, (out.stdout[-1000:],
                                            out.stderr[-3000:])
