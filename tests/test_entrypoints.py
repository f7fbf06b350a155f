"""Plumbing the entry points share: the float32 matmul precision and the
compile cache's placement (`repro.launch.runtime`), the host mesh, and the
multi-device benchmark's one-cell child process."""

import json
import os
import shutil
import subprocess
import sys

import jax

from repro.launch.runtime import MATMUL_PRECISION

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_COMPILE = ("from repro.launch.runtime import setup_runtime;"
            "import jax, jax.numpy as jnp;"
            "print(setup_runtime());"
            "jax.jit(lambda x: 2 * x + 1)(jnp.ones(3)).block_until_ready()")


def _run_compile(src_dir, **env):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"} | env
    env.update(PYTHONPATH=src_dir, JAX_PLATFORMS="cpu",
               # cache every entry, however fast it compiled
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0")
    out = subprocess.run([sys.executable, "-c", _COMPILE], env=env,
                         capture_output=True, text=True, check=True)
    return out.stdout.strip().splitlines()[-1]


def _copy_checkout(dst):
    """The compile-cache module in a checkout of its own, so the default
    path can be exercised without writing into this one."""
    for rel in ("src/repro/__init__.py", "src/repro/launch/__init__.py",
                "src/repro/launch/runtime.py"):
        os.makedirs(os.path.dirname(dst / rel), exist_ok=True)
        shutil.copy(os.path.join(ROOT, rel), dst / rel)
    return str(dst / "src")


def test_compile_cache_defaults_to_the_checkout(tmp_path):
    src = _copy_checkout(tmp_path / "checkout")
    path = _run_compile(src)
    assert path == str(tmp_path / "checkout" / ".jax_cache")
    assert os.listdir(path), "no compiled entry was cached"


def test_compile_cache_env_var_wins(tmp_path):
    src = _copy_checkout(tmp_path / "checkout")
    cache = tmp_path / "elsewhere"
    path = _run_compile(src, JAX_COMPILATION_CACHE_DIR=str(cache))
    assert path == str(cache)
    assert os.listdir(cache), "no compiled entry was cached"
    assert not (tmp_path / "checkout" / ".jax_cache").exists()


def test_runtime_requests_highest_fp32_matmuls(tmp_path):
    src = _copy_checkout(tmp_path / "checkout")
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(PYTHONPATH=src, JAX_PLATFORMS="cpu")
    code = ("from repro.launch.runtime import setup_runtime; import jax;"
            "print(jax.config.jax_default_matmul_precision);"
            "setup_runtime(); print(jax.config.jax_default_matmul_precision)")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, check=True)
    assert out.stdout.split() == ["None", "highest"]


def _dot_precisions(jaxpr):
    """(operand dtypes, precision) of every dot_general in a jaxpr, the
    ones nested in jit, loops, custom VJPs, shard_map and Pallas kernels
    included."""
    from jax.extend import core as jcore

    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            found.append((tuple(str(v.aval.dtype) for v in eqn.invars),
                          eqn.params["precision"]))
        for val in eqn.params.values():
            for sub in val if isinstance(val, (tuple, list)) else (val,):
                if isinstance(sub, jcore.ClosedJaxpr):
                    sub = sub.jaxpr
                if isinstance(sub, jcore.Jaxpr):
                    found += _dot_precisions(sub)
    return found


def test_every_fp32_matmul_of_the_main_path_runs_highest():
    """Under the precision `setup_runtime` sets, every float32 matmul of
    the training step, the posterior precompute and the served chunk
    (the fused kernels' own dots included) asks for HIGHEST; the kernels'
    dots on bf16 operands stay DEFAULT."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core import OperatorConfig, init_params_for, make_operator
    from repro.core.distributed import DistMLLConfig, make_mll_value_and_grad
    from repro.core.predcache import (build_prediction_cache, predict_mean,
                                      predict_var_cached)
    from repro.launch.mesh import make_host_mesh
    from repro.launch.train import prepare_gp_data

    rng = np.random.default_rng(0)
    X = rng.standard_normal((256, 9)).astype(np.float32)
    y = rng.standard_normal(256).astype(np.float32)
    Xq = jnp.asarray(rng.standard_normal((32, 9)), jnp.float32)
    key = jax.random.PRNGKey(0)
    params = init_params_for("matern32", noise=0.3, dtype=jnp.float32)
    mesh = make_host_mesh(data=1, model=1)
    geom, Xp, yp, _ = prepare_gp_data(
        mesh, X, y, backend="pallas", gp_mode="2d", kernel="matern32",
        params=params, row_block=128)
    step = make_mll_value_and_grad(mesh, geom, DistMLLConfig(
        kernel="matern32", precond_rank=16, num_probes=4, max_cg_iters=5,
        cg_tol=1.0, backend="pallas"))

    def serve(X, y, params, Xq, dtype):
        op = make_operator(OperatorConfig(kernel="matern32", backend="pallas",
                                          compute_dtype=dtype), X, params)
        cache = build_prediction_cache(op, y, key, precond_rank=16,
                                       lanczos_rank=8, max_cg_iters=5)
        return predict_mean(op, Xq, cache), predict_var_cached(op, Xq, cache)

    with jax.default_matmul_precision(MATMUL_PRECISION):
        fp32 = (_dot_precisions(jax.make_jaxpr(step)(
                    jnp.asarray(Xp), jnp.asarray(yp), params, key).jaxpr)
                + _dot_precisions(jax.make_jaxpr(serve, static_argnums=4)(
                    jnp.asarray(X), jnp.asarray(y), params, Xq, None).jaxpr))
        bf16 = _dot_precisions(jax.make_jaxpr(serve, static_argnums=4)(
            jnp.asarray(X), jnp.asarray(y), params, Xq, "bfloat16").jaxpr)
    highest = (jax.lax.Precision.HIGHEST,) * 2
    f32_dots = [p for dt, p in fp32 if "float32" in dt]
    assert len(f32_dots) > 20
    assert all(p == highest for p in f32_dots), set(f32_dots)
    bf16_dots = [p for dt, p in bf16 if dt == ("bfloat16", "bfloat16")]
    assert bf16_dots
    assert all(p == (jax.lax.Precision.DEFAULT,) * 2 for p in bf16_dots)


def test_host_mesh_takes_only_the_devices_it_needs():
    from jax.sharding import AxisType

    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh(data=1, model=1)
    assert mesh.devices.size == 1
    assert mesh.devices.flat[0] == jax.devices()[0]
    assert all(t == AxisType.Auto for t in mesh.axis_types)


def test_fig2_cell_runs_in_a_child_with_fake_devices():
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.fig2_multidevice", "--cell", "2",
         "2d", "overlap"], cwd=ROOT, env=env, capture_output=True,
        text=True, check=True, timeout=600)
    cell = json.loads(out.stdout.strip().splitlines()[-1])
    assert cell["ndev"] == 2 and cell["step_s"] > 0
