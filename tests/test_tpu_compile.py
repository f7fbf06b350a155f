"""Compile the main path's kernels and step for a described TPU v5e.

Nothing here runs: each test compiles for a `v5e:2x2` topology that the
installed TPU compiler describes without a chip, which refuses what the
chip would refuse (unaligned tiles, VMEM overuse, programs that do not fit
its 16 GB) and which interpret mode cannot show. The topology is described
inside a fixture so that only the worker given this file loads the TPU
library; where it cannot be described, every test here skips.
"""

import os

import jax
import jax.numpy as jnp
import pytest

COMPONENTS = (("matern32",),)
LANE = 128
DTYPES = ("float32", "bfloat16")


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler installed, or it refuses
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with x64 off while this module runs: the
    program runs 32-bit on the chip (conftest turns x64 on for the suite),
    and Mosaic refuses the 64-bit index maps x64 would give the kernels."""
    from jax.sharding import SingleDeviceSharding

    x64 = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_x64", x64)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=sharding)


def _kernel_args(name, dtype, sh, *, m=4096, n=8192, bm=256, bn=512):
    """(fn, shapes) of one fused-kernel launch at real tiles, d and t
    lane-padded as `kernels.ops` pads them on the chip."""
    from repro.kernels.kmvm import (kmvm_pallas, kmvm_pallas_chunk,
                                    kmvm_pallas_dots, scalar_layout)

    L = scalar_layout(COMPONENTS)
    scal = _spec((1, L), "float32", sh)
    Xi = _spec((m, LANE), dtype, sh)
    Xj = _spec((n, LANE), dtype, sh)
    V = _spec((n, LANE), dtype, sh)
    rows = _spec((m, LANE), "float32", sh)
    kw = dict(bm=bm, bn=bn, interpret=False, compute_dtype=dtype)
    if name == "kmvm_pallas":
        return (lambda a, b, v, s: kmvm_pallas(COMPONENTS, a, b, v, s, **kw),
                (Xi, Xj, V, scal))
    if name == "kmvm_pallas_dots":
        return (lambda a, b, v, vr, r, s: kmvm_pallas_dots(
            COMPONENTS, a, b, v, vr, r, s, **kw), (Xi, Xj, V, rows, rows, scal))
    return (lambda a, b, v, s, acc: kmvm_pallas_chunk(
        COMPONENTS, a, b, v, s, acc, **kw), (Xi, Xj, V, scal, rows))


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize(
    "name", ("kmvm_pallas", "kmvm_pallas_dots", "kmvm_pallas_chunk"))
def test_fused_kernel_compiles_for_v5e(one_chip, name, dtype):
    fn, shapes = _kernel_args(name, dtype, one_chip)
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("dtype", DTYPES)
def test_blocksparse_kernel_compiles_for_v5e(one_chip, dtype):
    from repro.kernels.kmvm import scalar_layout
    from repro.sparse.kmvm_sparse import kmvm_blocksparse_pallas

    tile, n_pad, pairs = 256, 16384, 192
    L = scalar_layout(COMPONENTS)

    def fn(X, V, s, rows, cols, first):
        return kmvm_blocksparse_pallas(
            COMPONENTS, X, V, s, rows, cols, first, tile=tile,
            interpret=False, compute_dtype=dtype)

    ints = _spec((pairs,), "int32", one_chip)
    compiled = jax.jit(fn).lower(
        _spec((n_pad, LANE), dtype, one_chip),
        _spec((n_pad, LANE), dtype, one_chip),
        _spec((1, L), "float32", one_chip), ints, ints, ints).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_one_chip_mll_step_fits_at_2_20(one_chip, monkeypatch):
    """The pallas exact_mll value-and-grad at the gp-exact-1m size (n =
    2^20, d = 9, matern32, rank 100, 8 probes, 20 CG iterations), at the
    float32 matmul precision the entry points set, compiles to the fused
    kernel and fits one v5e's 16 GiB."""
    from repro.configs.gp_exact_1m import CONFIG
    from repro.core import MLLConfig, exact_mll, init_params_for
    from repro.launch.runtime import MATMUL_PRECISION

    # kernels.ops decides interpret mode from the platform; the compile is
    # for the chip, so the program must take its TPU branch
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = MLLConfig(kernel=CONFIG.kernel, precond_rank=CONFIG.precond_rank,
                    num_probes=CONFIG.num_probes,
                    max_cg_iters=CONFIG.train_cg_iters, backend="pallas")
    params = jax.tree.map(
        lambda a: _spec(a.shape, a.dtype, one_chip),
        init_params_for(CONFIG.kernel, noise=0.3, dtype=jnp.float32))
    X = _spec((CONFIG.n, CONFIG.d), "float32", one_chip)
    y = _spec((CONFIG.n,), "float32", one_chip)
    key = _spec((2,), "uint32", one_chip)

    def step(X, y, p, k):
        return jax.value_and_grad(
            lambda p: exact_mll(cfg, X, y, p, k)[0])(p)

    with jax.default_matmul_precision(MATMUL_PRECISION):
        compiled = jax.jit(step).lower(X, y, params, key).compile()
    assert "tpu_custom_call" in compiled.as_text()
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert 0 < temp < 16 * 2**30, temp / 2**30
