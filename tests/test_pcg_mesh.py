"""The CG loop's early exit on a 2x2 mesh (four virtual CPU devices, in a
child process: the main test process keeps its single device).

The loop leaves once no column is active, and the predicate comes from
all-reduced norms, so every shard must leave at the same iteration. Each
shard's copy of a replicated output is what that shard computed, so the
per-shard `traversals` show it. Against the same solve on one device:
  * `pcg` through the sharded operator on a fixed right-hand side block,
    both loop structures: solution, alpha/beta and active records,
    iterations and traversals;
  * `DistWarmStartEngine` over a cold, a warm and a refresh step: the
    targets' column (its solution and iterations; the probe columns
    differ by design, since each shard draws its own probe chunk).
"""

import os
import subprocess
import sys

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
jax.config.update("jax_enable_x64", True)
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.core import init_params_for
from repro.core.distributed import (DistMLLConfig, ShardedOperator,
                                    make_geometry, replicate, shard_vector)
from repro.core.pcg import pcg
from repro.launch.mesh import make_host_mesh
from repro.train.solver_state import DistWarmStartEngine, WarmStartConfig

# the tolerance tests/test_distributed.py holds a mesh solve to
TOL = 1e-7
MAX_ITERS = 40
rng = np.random.default_rng(5)
n, d = 128, 3
X = jnp.asarray(rng.normal(size=(n, d)))
y = jnp.asarray(np.sin(np.asarray(X) @ rng.normal(size=d))
                + 0.1 * rng.normal(size=n))
B = jnp.asarray(rng.normal(size=(n, 3)))
params = init_params_for("matern32", noise=0.3, dtype=jnp.float64)
cfg = DistMLLConfig(precond_rank=10, num_probes=4, max_cg_iters=MAX_ITERS,
                    cg_tol=1.0)


def per_shard(a):
    # each device's copy of a replicated output, as that device computed it
    return [np.asarray(s.data) for s in a.addressable_shards]


def same_on_every_shard(a, what):
    shards = per_shard(a)
    assert len(shards) == a.sharding.num_devices, what
    for s in shards[1:]:
        assert np.array_equal(s, shards[0]), (what, shards)
    return shards[0]


def close(a, b, what):
    a, b = np.asarray(a), np.asarray(b)
    err = float(np.max(np.abs(a - b)))
    assert err <= TOL * max(float(np.max(np.abs(b))), 1.0), (what, err)


def solve(shape, method):
    mesh = make_host_mesh(*shape)
    geom = make_geometry(mesh, n, d, mode="2d", row_block=32)
    vec = geom.vector_pspec()

    def local(Xr, B_loc, p):
        op = ShardedOperator(cfg.operator_config(geom), Xr, p)
        pre = op.preconditioner(cfg.precond_rank)
        res = pcg(op, B_loc, pre.solve, max_iters=MAX_ITERS, min_iters=3,
                  tol=1e-3, method=method)
        full = jax.lax.all_gather(res.solution, geom.all_axes, axis=0,
                                  tiled=True)
        return res._replace(solution=full[:n])

    f = jax.jit(shard_map(local, mesh=mesh, in_specs=(P(), vec, P()),
                          out_specs=P(), check_vma=False))
    return f(replicate(mesh, X), shard_vector(mesh, geom, B),
             replicate(mesh, params))


for method in ("standard", "pipelined"):
    one, mesh = solve((1, 1), method), solve((2, 2), method)
    trav = int(same_on_every_shard(mesh.traversals, (method, "traversals")))
    iters = same_on_every_shard(mesh.iterations, (method, "iterations"))
    assert np.array_equal(iters, np.asarray(one.iterations)), method
    assert trav == int(one.traversals), (method, trav, int(one.traversals))
    assert trav < MAX_ITERS + (method == "pipelined"), (method, trav)
    assert np.array_equal(np.asarray(mesh.active), np.asarray(one.active))
    for name in ("solution", "alphas", "betas"):
        close(getattr(mesh, name), getattr(one, name), (method, name))
    print("SOLVE_OK", method, trav, iters.tolist())

runs = {}
for shape in ((1, 1), (2, 2)):
    mesh = make_host_mesh(*shape)
    geom = make_geometry(mesh, n, d, mode="2d", row_block=32)
    eng = DistWarmStartEngine(mesh, geom, cfg,
                              WarmStartConfig(enabled=True, refresh_every=2,
                                              drift_threshold=10.0))
    Xr, ys = replicate(mesh, X), shard_vector(mesh, geom, y)
    key = jax.random.PRNGKey(0)
    steps = []
    for i in range(3):
        _, aux, _ = eng.step(Xr, ys, params, jax.random.fold_in(key, i))
        trav = int(same_on_every_shard(aux.traversals, (shape, i)))
        iters = same_on_every_shard(aux.cg_iterations, (shape, i))
        mode = eng.telemetry[-1]["mode"]
        assert trav == eng.telemetry[-1]["traversals"], (shape, i)
        assert trav == min(int(iters.max()) + 1, MAX_ITERS) + (mode != "cold")
        steps.append((mode, trav, iters,
                      np.asarray(eng.state.solutions)[:n, 0]))
    runs[shape] = steps
for (mode1, _, it1, u1), (mode4, trav4, it4, u4) in zip(runs[(1, 1)],
                                                       runs[(2, 2)]):
    assert mode1 == mode4, (mode1, mode4)
    assert it1[0] == it4[0], (mode4, it1, it4)
    close(u4, u1, (mode4, "targets' solution"))
    print("STEP_OK", mode4, trav4, it4.tolist())
print("MESH_EXIT_DONE")
"""


def test_early_exit_on_a_2x2_mesh_matches_one_device():
    env = dict(os.environ,
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    out = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                         text=True, env=env, timeout=900)
    assert "MESH_EXIT_DONE" in out.stdout, (out.stdout[-2000:],
                                            out.stderr[-3000:])
    modes = [line.split()[1] for line in out.stdout.splitlines()
             if line.startswith("STEP_OK")]
    assert modes == ["cold", "warm", "refresh"]
