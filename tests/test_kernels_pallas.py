"""Pallas fused kernel-MVM vs the pure-jnp oracle: shape/dtype sweep.

interpret=True executes the kernel body on CPU (no TPU in this container);
the BlockSpec tiling/padding logic is identical either way.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # optional dep; deterministic fallback (conftest dir is on sys.path)
    from _hypothesis_shim import given, settings, strategies as st

from repro.core.kernels_math import init_params
from repro.kernels.ops import kmvm_block, pallas_block_fn
from repro.kernels.ref import kmvm_ref

KINDS = ("rbf", "matern12", "matern32", "matern52")


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("shape", [
    (8, 8, 1, 2),       # tiny, all dims sub-tile
    (64, 128, 4, 1),    # n == one lane tile
    (100, 130, 3, 3),   # ragged everything
    (256, 512, 9, 8),   # multiple full tiles (houseelectric-like d=9)
    (33, 700, 385, 2),  # wide features (ctslice d=385 > lane)
])
def test_kmvm_block_matches_ref(kind, shape):
    m, n, d, t = shape
    rng = np.random.default_rng(hash((kind, shape)) % 2**31)
    Xi = jnp.asarray(rng.normal(size=(m, d)), jnp.float32)
    Xj = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    V = jnp.asarray(rng.normal(size=(n, t)), jnp.float32)
    params = init_params(lengthscale=0.9, outputscale=1.3, dtype=jnp.float32)
    out = kmvm_block(kind, Xi, Xj, V, params, interpret=True)
    ref = kmvm_ref(kind, Xi, Xj, V, params)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_kmvm_block_dtypes(dtype):
    rng = np.random.default_rng(7)
    Xi = jnp.asarray(rng.normal(size=(32, 5)), dtype)
    Xj = jnp.asarray(rng.normal(size=(48, 5)), dtype)
    V = jnp.asarray(rng.normal(size=(48, 2)), dtype)
    params = init_params(dtype=jnp.float32)
    out = kmvm_block("matern32", Xi, Xj, V, params, interpret=True)
    ref = kmvm_ref("matern32", Xi.astype(jnp.float32),
                   Xj.astype(jnp.float32), V.astype(jnp.float32), params)
    tol = 5e-2 if dtype == jnp.bfloat16 else 2e-4
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               rtol=tol, atol=tol)


def test_kmvm_block_1d_rhs():
    rng = np.random.default_rng(3)
    Xi = jnp.asarray(rng.normal(size=(16, 3)), jnp.float32)
    Xj = jnp.asarray(rng.normal(size=(24, 3)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(24,)), jnp.float32)
    params = init_params(dtype=jnp.float32)
    out = kmvm_block("rbf", Xi, Xj, v, params, interpret=True)
    assert out.shape == (16,)
    ref = kmvm_ref("rbf", Xi, Xj, v[:, None], params)[:, 0]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4,
                               atol=2e-4)


@settings(deadline=None, max_examples=12)
@given(m=st.integers(1, 80), n=st.integers(1, 160), d=st.integers(1, 12),
       t=st.integers(1, 5), kind=st.sampled_from(KINDS),
       seed=st.integers(0, 2**16))
def test_kmvm_block_property_sweep(m, n, d, t, kind, seed):
    rng = np.random.default_rng(seed)
    Xi = jnp.asarray(rng.normal(size=(m, d)), jnp.float32)
    Xj = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    V = jnp.asarray(rng.normal(size=(n, t)), jnp.float32)
    params = init_params(lengthscale=float(rng.uniform(0.5, 2.0)),
                         dtype=jnp.float32)
    out = kmvm_block(kind, Xi, Xj, V, params, interpret=True)
    ref = kmvm_ref(kind, Xi, Xj, V, params)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-4, atol=3e-4)


def test_pallas_block_fn_in_partitioned_kmvm(rng):
    """The Pallas path drops into partitioned.kmvm as block_fn."""
    from repro.core import dense_khat, kmvm

    X = jnp.asarray(rng.normal(size=(90, 4)), jnp.float32)
    V = jnp.asarray(rng.normal(size=(90, 2)), jnp.float32)
    params = init_params(noise=0.2, dtype=jnp.float32)
    out = kmvm("matern32", X, V, params, row_block=32,
               block_fn=pallas_block_fn("matern32", interpret=True))
    dense = dense_khat("matern32", X, params) @ V
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense), rtol=2e-3,
                               atol=2e-3)


def test_custom_tile_sizes():
    rng = np.random.default_rng(11)
    Xi = jnp.asarray(rng.normal(size=(300, 7)), jnp.float32)
    Xj = jnp.asarray(rng.normal(size=(500, 7)), jnp.float32)
    V = jnp.asarray(rng.normal(size=(500, 3)), jnp.float32)
    params = init_params(dtype=jnp.float32)
    ref = kmvm_ref("matern52", Xi, Xj, V, params)
    for bm, bn in ((64, 128), (128, 256), (8, 128)):
        out = kmvm_block("matern52", Xi, Xj, V, params, bm=bm, bn=bn,
                         interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)


def test_kmvm_pallas_chunk_matches_single_launch():
    """Walking the columns chunk-by-chunk through the accumulator entry
    (`kmvm_pallas_chunk`, the per-chunk TPU launch for the distributed
    collective-matmul pipeline in `core.distributed._chunked_contraction`)
    is bitwise-identical to one fused `kmvm_pallas` launch: the chunk
    kernel visits the same (bm, bn) tiles in the same order, only seeding
    the output tile from the carried accumulator instead of zeros."""
    from repro.kernels.kmvm import kmvm_pallas, kmvm_pallas_chunk

    rng = np.random.default_rng(3)
    m, n, d, t = 64, 128, 4, 128
    n_chunks = 2
    nc = n // n_chunks
    components = (("rbf",),)
    Xi = jnp.asarray(rng.normal(size=(m, d)), jnp.float32)
    Xj = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    V = jnp.asarray(rng.normal(size=(n, t)), jnp.float32)
    scalars = jnp.asarray([[1.3, 0.7]], jnp.float32)  # (w, q)

    full = kmvm_pallas(components, Xi, Xj, V, scalars,
                       bm=32, bn=32, interpret=True)
    acc = jnp.zeros((m, t), jnp.float32)
    for s in range(n_chunks):
        acc = kmvm_pallas_chunk(
            components, Xi, Xj[s * nc:(s + 1) * nc], V[s * nc:(s + 1) * nc],
            scalars, acc, bm=32, bn=32, interpret=True)
    np.testing.assert_array_equal(np.asarray(acc), np.asarray(full))


@pytest.mark.parametrize("platform,want", [("tpu", False), ("cpu", True),
                                           ("gpu", None)])
def test_interpret_mode_follows_the_platform(monkeypatch, platform, want):
    """Compiled on a TPU, interpreted on the CPU, an error anywhere else —
    never a silent fall-back to the interpreter; an explicit bool wins."""
    from repro.kernels.ops import resolve_interpret

    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    if want is None:
        with pytest.raises(RuntimeError, match="'gpu'"):
            resolve_interpret()
    else:
        assert resolve_interpret() is want
    assert resolve_interpret(True) is True
    assert resolve_interpret(False) is False
