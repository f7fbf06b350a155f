"""Regression data made on the device from a seed.

The recipe of the program's synthetic UCI analogues (a 3-component
correlated Gaussian mixture for the inputs, a random-Fourier-feature
target near the Matern class, observation noise 0.1, the paper's 4/9
train split, whitening with the training split's statistics), written
again here with `jax.random` so that the data costs one jitted call on the
chip instead of minutes of host numpy. The program receives only the
arrays this returns.

Rows are independent draws, so the train and test splits are taken as
the first n_train rows and the last n_test rows without a permutation.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

NUM_COMPONENTS = 3
NUM_FEATURES = 2048
NOISE_STD = 0.1


class Splits(NamedTuple):
    X_train: jax.Array   # (n_train, d) float32, whitened
    y_train: jax.Array   # (n_train,) float32, whitened
    X_test: jax.Array    # (n_test, d) float32, whitened with train stats


def split_sizes(total: int) -> tuple[int, int, int]:
    """(train, val, test) of `total` points: 4/9, 2/9 and the rest."""
    n_train = round(total * 4 / 9)
    n_val = round(total * 2 / 9)
    return n_train, n_val, total - n_train - n_val


def total_for_train(n_train: int) -> int:
    """The smallest total whose 4/9 split has at least n_train points."""
    total = -(-n_train * 9 // 4)
    while split_sizes(total)[0] < n_train:
        total += 1
    return total


def seed_key(seed: int) -> jax.Array:
    """A key from any non-negative seed, also one wider than 32 bits."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


@functools.partial(jax.jit, static_argnames=("total", "d", "n_train",
                                             "n_test", "with_targets"))
def _make(key, *, total: int, d: int, n_train: int, n_test: int,
          with_targets: bool):
    k_mean, k_comp, k_x, k_scale, k_w1, k_w2, k_b, k_a, k_eps = \
        jax.random.split(key, 9)
    means = 1.5 * jax.random.normal(k_mean, (NUM_COMPONENTS, d))
    comp = jax.random.randint(k_comp, (total,), 0, NUM_COMPONENTS)
    scale = jax.random.uniform(k_scale, (1, d), minval=0.3, maxval=1.2)
    X = jax.random.normal(k_x, (total, d)) * scale + means[comp]
    X_train, X_test = X[:n_train], X[total - n_test:]
    mu = X_train.mean(0)
    sd = X_train.std(0) + 1e-8
    out_X = ((X_train - mu) / sd, (X_test - mu) / sd)
    if not with_targets:
        return out_X + (jnp.zeros((n_train,), jnp.float32),)
    # random Fourier features: half Gaussian (RBF), half Student-t with 3
    # degrees of freedom (Matern-like) frequencies, lengthscale sqrt(d)
    lengthscale = jnp.sqrt(jnp.float32(d))
    half = NUM_FEATURES // 2
    W = jnp.concatenate([
        jax.random.normal(k_w1, (half, d)),
        jax.random.t(k_w2, 3.0, (NUM_FEATURES - half, d))], 0) / lengthscale
    b = jax.random.uniform(k_b, (NUM_FEATURES,), maxval=2.0 * jnp.pi)
    a = jax.random.normal(k_a, (NUM_FEATURES,)) * jnp.sqrt(2.0 / NUM_FEATURES)
    Xt = X[:n_train]
    f = jnp.cos(jnp.dot(Xt, W.T, precision="highest") + b) @ a
    y = f + NOISE_STD * jax.random.normal(k_eps, (n_train,))
    y = (y - y.mean()) / (y.std() + 1e-8)
    return out_X + (y,)


def make(seed: int, *, total: int, d: int, with_targets: bool = True,
         n_train: int | None = None) -> Splits:
    """The splits of a `total`-point dataset of dimension d.

    n_train (default: the 4/9 split) cuts the training split to fewer
    points; the test split stays the last 3/9 of the total."""
    n_tr, _, n_test = split_sizes(total)
    n_train = n_tr if n_train is None else int(n_train)
    if not 0 < n_train <= n_tr:
        raise ValueError(f"n_train={n_train} outside (0, {n_tr}]")
    X_train, X_test, y = _make(seed_key(seed), total=total, d=d,
                               n_train=n_train, n_test=n_test,
                               with_targets=with_targets)
    return Splits(X_train, y, X_test)
