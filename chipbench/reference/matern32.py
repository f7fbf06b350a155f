"""Plain reference of the Matern-3/2 exact GP, in jax.numpy.

Independent of the program: it imports nothing of `repro` and takes no
array the program made. It is given the data (made by the benchmark from
the seed), the hyperparameters at which a step or a request was served,
and the program's answers to check.

    k(a, b)   = s (1 + sqrt(3) r) exp(-sqrt(3) r),   r = |a - b| / l
    K_hat     = K + (softplus(raw_noise) + noise_floor) I
    l, s      = softplus(raw_lengthscale), softplus(raw_outputscale)

Every matrix product takes a `precision`:

* "highest": float32 at `jax.lax.Precision.HIGHEST` (six bf16 passes on a
  TPU), which is the precision the configurations state. This is the
  reference.
* "high": the three-pass bf16 product, one precision step below the
  configurations'. On a TPU this is `jax.lax.Precision.HIGH` itself. A
  CPU computes every float32 product exactly whatever the precision, so
  there it is written out: hi*hi + hi*lo + lo*hi with bf16 halves and
  float32 accumulation. (Written out on a TPU, XLA may keep the bf16
  halves in float32, so the TPU takes the native pass count.) The
  control runs at it.

Blocks of rows are mapped one at a time, so no n x n matrix is held.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

SQRT3 = 3.0 ** 0.5
PRECISIONS = ("highest", "high")


class Hyper(NamedTuple):
    lengthscale: jax.Array
    outputscale: jax.Array
    noise: jax.Array      # noise variance, floor included
    mean: jax.Array


def hyper(raw: dict, noise_floor: float) -> Hyper:
    """Constrained hyperparameters from the raw values (softplus)."""
    sp = jax.nn.softplus
    return Hyper(sp(jnp.float32(raw["raw_lengthscale"])),
                 sp(jnp.float32(raw["raw_outputscale"])),
                 sp(jnp.float32(raw["raw_noise"])) + jnp.float32(noise_floor),
                 jnp.float32(raw["raw_mean"]))


def _split(a):
    hi = a.astype(jnp.bfloat16)
    lo = (a - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, lo


def matmul(a, b, precision: str):
    """a @ b in float32 at the named precision."""
    if precision == "highest":
        return jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)
    if precision == "high":
        if jax.default_backend() == "tpu":
            return jnp.dot(a, b, precision=jax.lax.Precision.HIGH,
                           preferred_element_type=jnp.float32)
        (ah, al), (bh, bl) = _split(a), _split(b)

        def dot(x, y):
            return jnp.dot(x, y, preferred_element_type=jnp.float32)

        return dot(ah, bh) + (dot(ah, bl) + dot(al, bh))
    raise ValueError(f"unknown precision {precision!r}; one of {PRECISIONS}")


def _scaled_distance(A, B, h: Hyper, precision: str):
    """sqrt(3) |a - b| / lengthscale for every pair of rows."""
    A = A / h.lengthscale
    B = B / h.lengthscale
    d2 = (jnp.sum(A * A, 1)[:, None] + jnp.sum(B * B, 1)[None, :]
          - 2.0 * matmul(A, B.T, precision))
    return SQRT3 * jnp.sqrt(jnp.maximum(d2, 0.0))


def kernel_block(A, B, h: Hyper, precision: str):
    """K(A, B) (no noise term)."""
    r = _scaled_distance(A, B, h, precision)
    return h.outputscale * (1.0 + r) * jnp.exp(-r)


def kernel_dl_block(A, B, h: Hyper, precision: str):
    """dK(A, B) / d lengthscale = s (sqrt(3) r)^2 exp(-sqrt(3) r) / l."""
    r = _scaled_distance(A, B, h, precision)
    return h.outputscale * r * r * jnp.exp(-r) / h.lengthscale


def _row_blocks(Z, block: int):
    m, d = Z.shape
    pad = -m % block
    Zp = jnp.pad(Z, ((0, pad), (0, 0)))
    return Zp.reshape(-1, block, d), m


@functools.partial(jax.jit, static_argnames=("precision", "block"))
def cross_matvec(Z, X, V, h: Hyper, *, precision: str = "highest",
                 block: int = 256):
    """K(Z, X) @ V, V of shape (n, t), mapped over blocks of Z's rows."""
    Zb, m = _row_blocks(Z, block)
    out = jax.lax.map(
        lambda z: matmul(kernel_block(z, X, h, precision), V, precision), Zb)
    return out.reshape(-1, V.shape[1])[:m]


def khat_matvec(X, V, h: Hyper, *, precision: str = "highest",
                block: int = 256):
    """K_hat @ V over the training points themselves."""
    return cross_matvec(X, X, V, h, precision=precision,
                        block=block) + h.noise * V


@functools.partial(jax.jit, static_argnames=("precision", "block"))
def true_rel_residual(X, y, u, h: Hyper, *, precision: str = "highest",
                      block: int = 256):
    """||(y - mean) - K_hat u|| / ||y - mean||: how far a solution u of
    K_hat u = y - mean really is from solving it."""
    b = y - h.mean
    r = b - khat_matvec(X, u[:, None], h, precision=precision,
                        block=block)[:, 0]
    return jnp.sqrt(jnp.sum(r * r) / jnp.sum(b * b))


@functools.partial(jax.jit, static_argnames=("iters", "precision", "block"))
def cg_solve(X, y, h: Hyper, x0=None, *, iters: int, precision: str,
             block: int = 256):
    """Plain conjugate gradients on K_hat u = y - mean from x0 (zero by
    default), with no preconditioner: (u, ||r|| / ||b|| by the
    recurrence)."""
    b = y - h.mean
    u0 = jnp.zeros_like(b) if x0 is None else x0
    r0 = b - khat_matvec(X, u0[:, None], h, precision=precision,
                         block=block)[:, 0]
    U, rel, _ = block_cg(X, r0[:, None], h, tol=0.0, max_iters=iters,
                         precision=precision, block=block)
    return u0 + U[:, 0], rel[0] * jnp.sqrt(jnp.sum(r0 * r0)
                                           / jnp.sum(b * b))


@functools.partial(jax.jit,
                   static_argnames=("max_iters", "precision", "block"))
def block_cg(X, B, h: Hyper, *, tol, max_iters: int, precision: str,
             block: int = 256):
    """Plain conjugate gradients on K_hat W = B from zero, every column at
    once and each with its own steps, until every column's ||r|| / ||b||
    is under tol (a scalar, or one per column) or max_iters have run: (W, ||r|| / ||b|| per column by
    the recurrence, iterations)."""

    def cond(c):
        i, _, _, _, rr = c
        return (i < max_iters) & jnp.any(rr > tol * tol * rr0)

    def body(c):
        i, W, R, P, rr = c
        KP = khat_matvec(X, P, h, precision=precision, block=block)
        # a column that has reached tol stays where it is
        on = rr > tol * tol * rr0
        alpha = jnp.where(on, rr / jnp.where(on, jnp.sum(P * KP, 0), 1.0),
                          0.0)
        W, R = W + alpha * P, R - alpha * KP
        rr_new = jnp.where(on, jnp.sum(R * R, 0), rr)
        beta = jnp.where(on, rr_new / jnp.where(on, rr, 1.0), 0.0)
        return i + 1, W, R, jnp.where(on, R + beta * P, P), rr_new

    rr0 = jnp.sum(B * B, 0)
    i, W, _, _, rr = jax.lax.while_loop(
        cond, body, (0, jnp.zeros_like(B), B, B, rr0))
    return W, jnp.sqrt(rr / rr0), i


@functools.partial(jax.jit, static_argnames=("precision", "block"))
def grad_matvecs(X, V, h: Hyper, *, precision: str = "highest",
                 block: int = 256):
    """(K @ V, dK/dlengthscale @ V) over the training points, in one pass
    over blocks of rows."""
    Xb, n = _row_blocks(X, block)

    def one(x):
        return (matmul(kernel_block(x, X, h, precision), V, precision),
                matmul(kernel_dl_block(x, X, h, precision), V, precision))

    KV, LV = jax.lax.map(one, Xb)
    c = V.shape[1]
    return KV.reshape(-1, c)[:n], LV.reshape(-1, c)[:n]


def rademacher(key, n: int, t: int):
    return jax.random.rademacher(key, (n, t), jnp.float32)


def mll_grad(X, y, u, raw: dict, h: Hyper, key, *, probes: int, tol: float,
             max_iters: int, x0=None, precision: str = "highest",
             block: int = 256):
    """The gradient of loss = -log p(y) / n (paper Eq. 2) with respect to
    the raw hyperparameters (lengthscale, outputscale, noise, mean):

        d loss / d theta = -(1/n) (u' dK u / 2 - tr(K_hat^-1 dK) / 2),
        d loss / d mean  = -(1/n) sum(u),   u = K_hat^-1 (y - mean).

    The data-fit terms take the solution u given (the step's own, to check
    how its gradient was assembled from it) and, for comparison, the
    reference's own solve of y to `tol`. The trace terms are Hutchinson
    estimates over `probes` Rademacher vectors drawn from `key`, each
    solved by plain CG to `tol`, from x0 (the `solves` of an earlier call
    with the same key, at nearby hyperparameters) if given.

    Returns a dict of host floats and lists: `at_u` and `converged` (the
    gradient with either solution in the data-fit terms), `scale` (per
    leaf, the sizes of the terms that `at_u` sums), `halves` (the
    gradient at u with each half of the probes alone: the estimate's own
    spread), `quad_u` and `quad_converged` ((y - mean)' u), `iterations`
    and `rel_residual` (the largest column's) of the reference's solve,
    and `solves`, its solutions, for the next call's x0."""
    import numpy as np

    n = X.shape[0]
    b = y - h.mean
    Z = rademacher(key, n, probes)
    B = jnp.concatenate([b[:, None], Z], 1)
    start = jnp.zeros_like(B) if x0 is None else x0
    R = B - khat_matvec(X, start, h, precision=precision, block=block)
    # tol is relative to each column of B, not to where the solve starts
    shrink = jnp.sqrt(jnp.sum(R * R, 0) / jnp.sum(B * B, 0))
    D, rel, iters = block_cg(X, R, h, tol=tol / shrink,
                             max_iters=max_iters, precision=precision,
                             block=block)
    solves = start + D
    rel = rel * shrink
    u_star, W = solves[:, 0], solves[:, 1:]
    V = jnp.concatenate([u[:, None], u_star[:, None], Z], 1)
    KV, LV = grad_matvecs(X, V, h, precision=precision, block=block)
    f = np.asarray
    sig = {k: float(jax.nn.sigmoid(jnp.float32(raw[k])))
           for k in ("raw_lengthscale", "raw_outputscale", "raw_noise")}
    s = float(h.outputscale)
    # per probe: W_i' M Z_i for M = dK/dl, K, I
    tr = np.stack([f(jnp.sum(W * LV[:, 2:], 0)), f(jnp.sum(W * KV[:, 2:], 0)),
                   f(jnp.sum(W * Z, 0))]).astype(np.float64)

    def terms(col, v, tr_l, tr_k, tr_i):
        """Per leaf, (data-fit term, trace term) in the gradient's units:
        the gradient is their sum."""
        c = [-sig["raw_lengthscale"] * 0.5 / n,
             -sig["raw_outputscale"] / s * 0.5 / n,
             -sig["raw_noise"] * 0.5 / n]
        fit = [float(jnp.dot(v, LV[:, col])), float(jnp.dot(v, KV[:, col])),
               float(jnp.dot(v, v))]
        out = [(ci * f, -ci * t) for ci, f, t in zip(c, fit,
                                                     (tr_l, tr_k, tr_i))]
        return out + [(-float(jnp.sum(v)) / n, 0.0)]

    def grad(col, v, *tr_):
        return [f + t for f, t in terms(col, v, *tr_)]

    half = probes // 2
    return {
        "at_u": grad(0, u, *tr.mean(1)),
        # the size of the terms each leaf's gradient sums: |fit| + |trace|,
        # and sum(|u|) / n for the mean, whose one term is a sum over u
        "scale": [abs(f) + abs(t) for f, t in terms(0, u, *tr.mean(1))[:3]]
        + [float(jnp.sum(jnp.abs(u))) / n],
        "converged": grad(1, u_star, *tr.mean(1)),
        "halves": [grad(0, u, *tr[:, :half].mean(1)),
                   grad(0, u, *tr[:, half:].mean(1))],
        "quad_u": float(jnp.dot(b, u)),
        "quad_converged": float(jnp.dot(b, u_star)),
        "iterations": int(iters),
        "rel_residual": float(jnp.max(rel)),
        "solves": solves,
    }


@functools.partial(jax.jit, static_argnames=("precision", "block"))
def posterior(Z, X, mean_cache, var_Q, var_T_chol, h: Hyper, *,
              precision: str = "highest", block: int = 256):
    """Predictive mean and variance (noise included) from the caches:
    mean = m + K(Z, X) a, var = s - diag(P T^-1 P^T) + noise with
    P = K(Z, X) Q and T = L L^T."""
    V = jnp.concatenate([mean_cache[:, None], var_Q], axis=1)
    KV = cross_matvec(Z, X, V, h, precision=precision, block=block)
    mean = h.mean + KV[:, 0]
    W = jax.scipy.linalg.solve_triangular(var_T_chol, KV[:, 1:].T,
                                          lower=True)
    corr = jnp.sum(W * W, axis=0)
    var = jnp.maximum(h.outputscale - corr, 1e-10) + h.noise
    return mean, var


def adam(params: list, grads: list, lr: float, b1: float, b2: float,
         eps: float) -> list:
    """Plain Adam over lists of scalar leaves: the parameters after each
    of the given steps (one gradient list per step)."""
    import math

    p = [float(x) for x in params]
    m = [0.0] * len(p)
    v = [0.0] * len(p)
    out = []
    for step, g in enumerate(grads, start=1):
        for i, gi in enumerate(g):
            gi = float(gi)
            m[i] = b1 * m[i] + (1 - b1) * gi
            v[i] = b2 * v[i] + (1 - b2) * gi * gi
            mhat = m[i] / (1 - b1 ** step)
            vhat = v[i] / (1 - b2 ** step)
            p[i] -= lr * mhat / (math.sqrt(vhat) + eps)
        out.append(list(p))
    return out
