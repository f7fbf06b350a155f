"""2-D mesh engine: goldens, overlap parity, padded geometry, pad-not-drop.

The four pillars the collective-overlap + padded-layout work must keep
standing (subprocess on 8 fake devices, like `tests/test_distributed.py`):

* goldens — the 1-D serial path is BITWISE the seed path (pre-change hex
  values), and the 2-D path is pinned at its post-change baseline (the 2-D
  serial MVM was restructured into the same chunked contraction the
  overlap pipeline walks, so overlap on/off stays bitwise by construction;
  the 2-D hexes below are that re-baselined value, within-noise of the old
  ones — see the value-level 1d/2d agreement check in test_distributed);
* overlap on/off bitwise agreement on the chunked path, dense AND
  blocksparse, divisible AND padded n;
* non-divisible n — the padded geometry's MLL value/quadratic term and
  gradients track the unpadded dense oracle (statistical tolerances for
  the SLQ-contaminated leaves, tight for the probe-free ones);
* `prepare_gp_data` pads instead of truncating (the shard-boundary
  data-loss regression), checked in-process below.
"""

import os
import subprocess
import sys

import pytest

# mesh (4, 2), seed 7, n=256, d=6, matern32, fp64 — see _GOLDEN_SCRIPT.
# 1d: the seed path, captured BEFORE the chunked-contraction change and
# required to stay bitwise forever. 2d: re-baselined at the chunked
# contraction (one dynamic-slice GEMM per source chunk instead of a single
# gathered GEMM — different summation grouping, same algorithm).
GOLDEN = {
    "1d": {
        "mvm_sum": "0x1.bf3c23cb7e8d0p+4",
        "mvm_00": "-0x1.43915550f0629p-1",
        "mvm_last": "-0x1.0d6350640f4a5p-2",
        "loss": "0x1.10ada9a87cb7ep+0",
        "grad_raw_lengthscale": "-0x1.a6f905426f893p-4",
        "grad_raw_outputscale": "0x1.2c53b9d0c182dp-3",
        "grad_raw_noise": "0x1.2d18592092fcep-4",
        "grad_raw_mean": "0x1.2f1823a69e122p-6",
    },
    "2d": {
        "mvm_sum": "0x1.bf3c23cb7e8d3p+4",
        "mvm_00": "-0x1.43915550f0627p-1",
        "mvm_last": "-0x1.0d6350640f4a5p-2",
        "loss": "0x1.10ada9a87d225p+0",
        "grad_raw_lengthscale": "-0x1.a6f905427a0b0p-4",
        "grad_raw_outputscale": "0x1.2c53b9d0bd1eep-3",
        "grad_raw_noise": "0x1.2d18592091a07p-4",
        "grad_raw_mean": "0x1.2f1823a5ac506p-6",
    },
}

_GOLDEN_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
jax.config.update("jax_enable_x64", True)
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P
from jax import shard_map

from repro.core import init_params
from repro.core.distributed import (
    DistMLLConfig, dist_kmvm, make_geometry, make_mll_value_and_grad,
    replicate, shard_vector,
)

mesh = jax.make_mesh((4, 2), ("data", "model"))
rng = np.random.default_rng(7)
n, d = 256, 6
X = jnp.asarray(rng.normal(size=(n, d)))
y = jnp.asarray(np.sin(np.asarray(X) @ rng.normal(size=d))
                + 0.1 * rng.normal(size=n))
V = jnp.asarray(rng.normal(size=(n, 3)))
params = init_params(noise=0.2, dtype=jnp.float64)

for mode in ("1d", "2d"):
    geom = make_geometry(mesh, n, d, mode=mode, row_block=32)
    f = jax.jit(shard_map(
        lambda Xr, Vl: dist_kmvm(geom, "matern32", Xr, Vl, params),
        mesh=mesh, in_specs=(P(), geom.vector_pspec()),
        out_specs=geom.vector_pspec(), check_vma=False))
    out = np.asarray(f(replicate(mesh, X), shard_vector(mesh, geom, V)))
    cfg = DistMLLConfig(kernel="matern32", precond_rank=40, num_probes=8,
                        max_cg_iters=30, cg_tol=1e-8)
    vg = make_mll_value_and_grad(mesh, geom, cfg)
    loss, aux, grads = vg(replicate(mesh, X), shard_vector(mesh, geom, y),
                          replicate(mesh, params), jax.random.PRNGKey(0))
    print(f"GOLDEN {mode} mvm_sum {float(out.sum()).hex()}")
    print(f"GOLDEN {mode} mvm_00 {float(out[0,0]).hex()}")
    print(f"GOLDEN {mode} mvm_last {float(out[-1,-1]).hex()}")
    print(f"GOLDEN {mode} loss {float(loss).hex()}")
    for fn_ in grads._fields:
        print(f"GOLDEN {mode} grad_{fn_} {float(getattr(grads, fn_)).hex()}")
print("GOLDEN_DONE")
"""

_OVERLAP_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
jax.config.update("jax_enable_x64", True)
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P
from jax import shard_map

from repro.core import init_params, parse_kernel
from repro.core.kernels_math import init_kernel_params
from repro.core.distributed import (
    dist_kmvm, make_geometry, pad_to_geometry, replicate, shard_vector,
)
from repro.sparse import (
    build_plan, dist_blocksparse_kmvm, morton_order, validate_dist_plan,
)

mesh = jax.make_mesh((4, 2), ("data", "model"))
rng = np.random.default_rng(11)

def run_dense(geom, X, V, params, overlap):
    f = jax.jit(shard_map(
        lambda Xr, Vl: dist_kmvm(geom, "matern32", Xr, Vl, params,
                                 overlap=overlap),
        mesh=mesh, in_specs=(P(), geom.vector_pspec()),
        out_specs=geom.vector_pspec(), check_vma=False))
    return np.asarray(f(replicate(mesh, X), shard_vector(mesh, geom, V)))

for n in (256, 250):
    d = 4
    X = jnp.asarray(rng.normal(size=(n, d)))
    V = jnp.asarray(rng.normal(size=(n, 3)))
    params = init_params(noise=0.2, dtype=jnp.float64)
    geom = make_geometry(mesh, n, d, mode="2d", row_block=32)
    Xp, Vp = pad_to_geometry(geom, X), pad_to_geometry(geom, V)
    a = run_dense(geom, Xp, Vp, params, False)
    b = run_dense(geom, Xp, Vp, params, True)
    assert (a == b).all(), f"dense n={n}: overlap not bitwise"
    print(f"dense n={n} overlap bitwise OK")

spec = parse_kernel("matern32 * wendland2")
for n in (256, 250):
    d, tile = 2, 32
    X = jnp.asarray(rng.uniform(size=(n, d)))
    V = jnp.asarray(rng.normal(size=(n, 3)))
    kp = init_kernel_params(spec, noise=0.3, radius=0.2, dtype=jnp.float64)
    Xs = X[jnp.asarray(morton_order(np.asarray(X)))]
    geom = make_geometry(mesh, n, d, mode="2d", row_block=tile,
                         tile_multiple=tile)
    Xp, Vp = pad_to_geometry(geom, Xs), pad_to_geometry(geom, V)
    plan = build_plan(spec, Xp, kp, tile=tile, assume_sorted=True)
    validate_dist_plan(geom, plan)
    outs = []
    for overlap in (False, True):
        f = jax.jit(shard_map(
            lambda Xr, Vl: dist_blocksparse_kmvm(geom, spec, Xr, Vl, kp,
                                                 plan, overlap=overlap),
            mesh=mesh, in_specs=(P(), geom.vector_pspec()),
            out_specs=geom.vector_pspec(), check_vma=False))
        outs.append(np.asarray(f(replicate(mesh, Xp),
                                 shard_vector(mesh, geom, Vp))))
    assert (outs[0] == outs[1]).all(), f"blocksparse n={n}: not bitwise"
    print(f"blocksparse n={n} overlap bitwise OK")
print("OVERLAP_DONE")
"""

_PADDED_MLL_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
jax.config.update("jax_enable_x64", True)
import jax.numpy as jnp
import numpy as np

from repro.core import dense_khat, dense_mll, init_params
from repro.core.distributed import (
    DistMLLConfig, make_geometry, make_mean_cache_solve,
    make_mll_value_and_grad, pad_to_geometry, replicate, shard_vector,
)

mesh = jax.make_mesh((4, 2), ("data", "model"))
rng = np.random.default_rng(2)
n, d = 250, 5   # 250 % 8 != 0 -> every geometry below pads to 256
X = jnp.asarray(rng.normal(size=(n, d)))
y = jnp.asarray(np.sin(np.asarray(X) @ rng.normal(size=d))
                + 0.1 * rng.normal(size=n))
params = init_params(noise=0.2, dtype=jnp.float64)
Khat = dense_khat("matern32", X, params)

oracle_loss, g_oracle = jax.value_and_grad(
    lambda p: -dense_mll("matern32", X, y, p) / n)(params)

for mode in ("1d", "2d"):
    for overlap in ((False, True) if mode == "2d" else (False,)):
        geom = make_geometry(mesh, n, d, mode=mode, row_block=32,
                             overlap=overlap)
        assert geom.has_pad and geom.n_padded == 256 and geom.n == n
        Xp = pad_to_geometry(geom, X)
        cfg = DistMLLConfig(kernel="matern32", precond_rank=40,
                            num_probes=16, max_cg_iters=150, cg_tol=1e-8)
        vg = make_mll_value_and_grad(mesh, geom, cfg)
        loss, aux, grads = vg(replicate(mesh, Xp),
                              shard_vector(mesh, geom, y),
                              replicate(mesh, params), jax.random.PRNGKey(0))
        tag = f"{mode}{'+ov' if overlap else ''}"
        # the loss carries the 16-probe SLQ logdet estimate: statistical
        assert abs(float(loss) - float(oracle_loss)) < \
            0.15 * abs(float(oracle_loss)) + 1e-3, \
            (tag, float(loss), float(oracle_loss))
        # probe-free leaf: tight
        assert abs(float(grads.raw_mean) - float(g_oracle.raw_mean)) \
            < 1e-6, tag
        for fname in ("raw_lengthscale", "raw_outputscale", "raw_noise"):
            a = float(getattr(grads, fname))
            b = float(getattr(g_oracle, fname))
            assert abs(a - b) < 0.15 * abs(b) + 0.02, (tag, fname, a, b)
        print(f"{tag} padded MLL parity OK")

        # the quadratic surface has no probe noise: the padded mean-cache
        # solve must hit the n-row dense solve to solver precision
        solve = make_mean_cache_solve(mesh, geom, cfg, tol=1e-10,
                                      max_iters=400)
        a_cache, rel = solve(replicate(mesh, Xp),
                             shard_vector(mesh, geom, y), params)
        assert a_cache.shape[0] == n
        direct = jnp.linalg.solve(Khat, y)
        err = float(jnp.max(jnp.abs(a_cache - direct)))
        assert err < 1e-7, (tag, err)
        print(f"{tag} padded quad solve OK ({err:.1e})")
print("PADDED_DONE")
"""


def _run(script):
    env = dict(os.environ,
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    return subprocess.run([sys.executable, "-c", script],
                          capture_output=True, text=True, env=env,
                          timeout=900)


@pytest.mark.slow
def test_dist_goldens_1d_bitwise_2d_pinned():
    out = _run(_GOLDEN_SCRIPT)
    assert "GOLDEN_DONE" in out.stdout, (out.stdout[-1000:],
                                         out.stderr[-3000:])
    got = {}
    for line in out.stdout.splitlines():
        if line.startswith("GOLDEN "):
            _, mode, key, hexval = line.split()
            got.setdefault(mode, {})[key] = hexval
    assert got == GOLDEN, got


@pytest.mark.slow
def test_overlap_on_off_bitwise():
    out = _run(_OVERLAP_SCRIPT)
    assert "OVERLAP_DONE" in out.stdout, (out.stdout[-1000:],
                                          out.stderr[-3000:])


@pytest.mark.slow
def test_padded_mll_matches_unpadded_oracle():
    out = _run(_PADDED_MLL_SCRIPT)
    assert "PADDED_DONE" in out.stdout, (out.stdout[-1000:],
                                         out.stderr[-3000:])


def test_prepare_gp_data_pads_not_truncates():
    """The shard-boundary regression: n not divisible by the layout used to
    be silently truncated to n_local * num_devices rows by the blocksparse
    CLI path. `prepare_gp_data` must instead PAD — every original row
    survives, the geometry records the true n, and the pad is masked."""
    import jax
    import numpy as np

    from repro.launch.train import prepare_gp_data

    mesh = jax.make_mesh((1,), ("data",))
    rng = np.random.default_rng(0)
    n, d = 30, 2
    X_host = rng.uniform(size=(n, d)).astype(np.float32)
    y_host = rng.normal(size=(n,)).astype(np.float32)

    from repro.core.kernels_math import init_kernel_params
    from repro.core import parse_kernel
    spec = parse_kernel("matern32 * wendland2")
    params = init_kernel_params(spec, noise=0.3, radius=0.4)

    geom, X, y, plan = prepare_gp_data(
        mesh, X_host, y_host, backend="blocksparse", gp_mode="1d",
        kernel=spec, params=params, tile=8)
    # tile=8 forces n_padded=32: rows padded, never dropped
    assert geom.n == n and geom.n_padded == 32 and geom.has_pad
    assert X.shape[0] == geom.n_padded and y.shape[0] == geom.n_padded
    assert plan is not None and plan.n == geom.n_padded
    # every original row is present (plan path Morton-reorders)
    sums = {round(float(s), 5) for s in X_host.sum(axis=1)}
    got = {round(float(s), 5) for s in np.asarray(X[:, :d].sum(axis=1))}
    assert sums <= got, "original rows missing after prepare_gp_data"

    geom2, X2, y2, plan2 = prepare_gp_data(
        mesh, X_host, y_host, backend="partitioned", gp_mode="1d",
        kernel="matern32", params=None, row_block=8)
    assert geom2.n == n and X2.shape[0] == geom2.n_padded
    assert plan2 is None
