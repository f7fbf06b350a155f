"""Data pipeline + optimizer substrates."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data import DATASET_SPECS, make_regression_dataset
from repro.optim import (
    adam_init, adam_update, clip_by_global_norm, lbfgs_minimize, warmup_cosine,
)


def test_dataset_splits_and_whitening():
    s = make_regression_dataset("protein", max_points=900)
    n = sum(x.shape[0] for x in (s.X_train, s.X_val, s.X_test))
    assert n == 900
    assert abs(s.X_train.shape[0] / n - 4 / 9) < 0.01
    assert s.X_train.shape[1] == DATASET_SPECS["protein"][1]
    # whitened by train stats
    np.testing.assert_allclose(s.X_train.mean(0), 0.0, atol=1e-7)
    np.testing.assert_allclose(s.X_train.std(0), 1.0, atol=1e-6)
    np.testing.assert_allclose(s.y_train.mean(), 0.0, atol=1e-7)


def test_dataset_has_signal():
    """A GP must beat predicting the mean (the target is a function draw)."""
    s = make_regression_dataset("kin40k", max_points=600)
    from repro.core import ExactGP, ExactGPConfig, init_params, rmse
    gp = ExactGP(ExactGPConfig(precond_rank=20, row_block=128,
                               pred_max_cg_iters=200))
    X = jnp.asarray(s.X_train, jnp.float64)
    y = jnp.asarray(s.y_train, jnp.float64)
    params = init_params(noise=0.1, lengthscale=1.0, dtype=jnp.float64)
    cache = gp.precompute(X, y, params, jax.random.PRNGKey(0))
    mean, _ = gp.predict(X, jnp.asarray(s.X_test, jnp.float64), params, cache)
    err = float(rmse(mean, jnp.asarray(s.y_test, jnp.float64)))
    assert err < 0.9  # baseline (predict 0) would be ~1.0


def test_unknown_dataset_raises():
    with pytest.raises(KeyError):
        make_regression_dataset("nope")


def test_token_pipeline_shapes():
    from repro.data.tokens import TokenPipeline
    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh(model=1)
    pipe = TokenPipeline(mesh, vocab=100, batch=4, seq=16, seed=0)
    try:
        b = next(pipe)
        assert b.tokens.shape == (4, 16) and b.targets.shape == (4, 16)
        assert b.tokens.dtype == jnp.int32
        assert int(b.tokens.max()) < 100
        # next-token alignment
        np.testing.assert_array_equal(np.asarray(b.tokens)[:, 1:],
                                      np.asarray(b.targets)[:, :-1])
    finally:
        pipe.close()


def test_adam_minimizes_quadratic():
    params = {"w": jnp.asarray([3.0, -2.0])}
    state = adam_init(params)
    for _ in range(300):
        g = jax.grad(lambda p: jnp.sum(p["w"] ** 2))(params)
        params, state = adam_update(params, g, state, 0.05)
    assert float(jnp.abs(params["w"]).max()) < 1e-2


def test_adam_dtype_preserved():
    params = {"w": jnp.zeros((3,), jnp.bfloat16)}
    state = adam_init(params)
    g = {"w": jnp.ones((3,), jnp.bfloat16)}
    params, state = adam_update(params, g, state, 0.1)
    assert params["w"].dtype == jnp.bfloat16
    assert state.mu["w"].dtype == jnp.float32


def test_clip_by_global_norm():
    g = {"a": jnp.asarray([3.0, 4.0])}
    clipped, norm = clip_by_global_norm(g, 1.0)
    assert np.isclose(float(norm), 5.0)
    assert np.isclose(float(jnp.linalg.norm(clipped["a"])), 1.0)


def test_lbfgs_minimizes_rosenbrock():
    def rosen(p):
        x, y = p["x"][0], p["x"][1]
        return (1 - x) ** 2 + 100 * (y - x * x) ** 2

    p0 = {"x": jnp.asarray([-1.0, 1.0], jnp.float64)}
    p, trace = lbfgs_minimize(rosen, p0, max_steps=100)
    assert trace[-1] < 1e-5
    np.testing.assert_allclose(np.asarray(p["x"]), [1.0, 1.0], atol=1e-2)


def test_warmup_cosine_shape():
    s = warmup_cosine(1.0, 10, 100)
    assert float(s(0)) == 0.0
    assert float(s(10)) == pytest.approx(1.0)
    assert float(s(100)) == pytest.approx(0.1, abs=1e-6)
    assert float(s(55)) < float(s(20))


def test_dataset_is_the_same_in_every_process():
    """Seeding must not depend on Python's per-process str-hash salt: a
    chip run and its host reference have to see the same split."""
    import os
    import subprocess
    import sys

    code = ("import hashlib; from repro.data import make_regression_dataset;"
            "s = make_regression_dataset('bike', max_points=900);"
            "print(hashlib.sha1(b''.join(a.tobytes() for a in s)).hexdigest())")
    src = os.path.join(os.path.dirname(os.path.dirname(__file__)), "src")
    digests = set()
    for salt in ("1", "2"):
        env = dict(os.environ, PYTHONHASHSEED=salt, PYTHONPATH=src)
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, text=True, check=True)
        digests.add(out.stdout.strip().splitlines()[-1])
    assert len(digests) == 1, digests
