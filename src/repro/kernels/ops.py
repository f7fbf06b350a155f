"""Public jit'd wrappers for the fused kernel-MVM Pallas kernel.

Handles everything the raw kernel does not: planning a KernelSpec into
fused passes, lengthscale/weight application, padding of (m, n, d, t) to
tile multiples, dtype policy, the interpret-mode decision
(`resolve_interpret`: compiled on TPU, interpreted on CPU), and a
`block_fn` adapter so `repro.core.partitioned.kmvm` can route its
per-partition slab MVMs through the Pallas path transparently.

Planning (`mvm_plan`)
---------------------
The spec is normalized to a weighted sum of primitive products
(`kernels_math.normalize_components`) and split into:

* ONE fused Pallas pass carrying every component whose factors are all
  stationary with a SHARED-SCALAR lengthscale. The tile is pre-scaled by
  the first such component's lengthscale; every other component is
  evaluated on the same d2 tile through its lengthscale ratio
  q = (l_ref / l_c)^2 — the whole sum kernel costs one pass over HBM.
* one fused pass PER component with an ARD lengthscale (its own metric:
  no shared d2 tile exists), still slab-free in VMEM.
* `linear` components, computed outside Pallas as two thin matmuls
  w * (Xi/s) @ ((Xj/s)^T V) — O((m+n) d t), no (m, n) tile at all.
* a dense-slab fallback for anything else (products mixing linear with
  stationary factors, multi-factor ARD products) — correct for every
  spec, O(m n) transient memory for those terms only.

A single-component spec plans to exactly one fused pass with
w = q = 1.0 — bitwise the pre-algebra behavior.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.kernels_math import (
    canonicalize_kernel,
    leaf_matrix,
    normalize_components,
    softplus,
)
from repro.obs.profiling import named_scope

from .kmvm import (
    DEFAULT_BM,
    DEFAULT_BN,
    kmvm_pallas,
    kmvm_pallas_dots,
    scalar_layout,
)

_LANE = 128


def _pad_axis(A: jax.Array, axis: int, multiple: int) -> jax.Array:
    size = A.shape[axis]
    rem = (-size) % multiple
    if rem == 0:
        return A
    widths = [(0, 0)] * A.ndim
    widths[axis] = (0, rem)
    return jnp.pad(A, widths)


def resolve_interpret(interpret: bool | None = None) -> bool:
    """Whether a Pallas TPU kernel runs in interpret mode.

    The one place the decision is made (the fused kernels, the autotuner
    and the blocksparse gathered grid all ask here). An explicit bool
    wins. Otherwise: compiled on a TPU, interpreted on the CPU (tests,
    local runs), and an error on any other platform — a machine whose TPU
    failed to initialise must not quietly fall back to the interpreter.
    """
    if interpret is not None:
        return bool(interpret)
    platform = jax.default_backend()
    if platform == "tpu":
        return False
    if platform == "cpu":
        return True
    raise RuntimeError(
        f"the Pallas kernels run compiled on 'tpu' or interpreted on 'cpu'; "
        f"the default JAX platform is {platform!r}")


class _PallasPass(NamedTuple):
    components: tuple        # static tuple of factor-kind tuples
    lengthscale: jax.Array   # () or (d,) reference pre-scaling
    base_weight: jax.Array   # V pre-multiplier (first component's weight)
    scalars: list            # flat per-component scalar list (see kmvm.py)


class MVMPlan(NamedTuple):
    """How a spec executes on the Pallas backend (returned by `mvm_plan`)."""

    passes: tuple            # _PallasPass fused passes
    linear_terms: tuple      # (weight, LinearParams) thin-matmul terms
    fallback_terms: tuple    # kernels_math.Term dense-slab terms

    @property
    def num_fused_passes(self) -> int:
        return len(self.passes)

    @property
    def num_fallback_terms(self) -> int:
        return len(self.fallback_terms)


def _is_scalar_stationary(factors) -> bool:
    return all(kind != "linear" and p.raw_lengthscale.ndim == 0
               for kind, p in factors)


def _pass_scalars(terms, l_ref, w0) -> list:
    scal = []
    for t in terms:
        scal.append(t.weight / w0)
        for kind, p in t.factors:
            ls = softplus(p.raw_lengthscale)
            if ls.ndim:
                # ARD factor: only planned as a single-factor pass whose
                # pre-scaling IS this lengthscale, so its ratio is exactly 1
                scal.append(jnp.float32(1.0))
            else:
                scal.append(jnp.square(l_ref / ls))
            if kind == "rq":
                scal.append(softplus(p.raw_alpha))
    return scal


def mvm_plan(kernel, params) -> MVMPlan:
    """Plan the fused execution of `kernel` under `params` (trace-safe:
    the plan's structure is static, its scalars are traced)."""
    spec, kp = canonicalize_kernel(kernel, params)
    terms = normalize_components(spec, kp)

    fused, ard, linear, fallback = [], [], [], []
    for t in terms:
        kinds = tuple(kind for kind, _ in t.factors)
        if _is_scalar_stationary(t.factors):
            fused.append(t)
        elif kinds == ("linear",):
            linear.append((t.weight, t.factors[0][1]))
        elif len(t.factors) == 1 and kinds[0] != "linear":
            ard.append(t)  # single stationary ARD factor: own metric, own pass
        else:
            fallback.append(t)

    passes = []
    if fused:
        l_ref = softplus(fused[0].factors[0][1].raw_lengthscale)
        w0 = fused[0].weight
        passes.append(_PallasPass(
            components=tuple(tuple(k for k, _ in t.factors) for t in fused),
            lengthscale=l_ref, base_weight=w0,
            scalars=_pass_scalars(fused, l_ref, w0)))
    for t in ard:
        l_ref = softplus(t.factors[0][1].raw_lengthscale)
        passes.append(_PallasPass(
            components=(tuple(k for k, _ in t.factors),),
            lengthscale=l_ref, base_weight=t.weight,
            scalars=_pass_scalars([t], l_ref, t.weight)))
    return MVMPlan(passes=tuple(passes), linear_terms=tuple(linear),
                   fallback_terms=tuple(fallback))


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _tile_geometry(m: int, n: int, bm: int, bn: int, cdt, interpret: bool):
    """(bm_eff, bn_eff, lane): the padded tile geometry of one launch.

    On TPU, sublane tiling wants block row counts in multiples of 8 (fp32)
    or 16 (16-bit dtypes) and lane dims (d, t, bn) padded to 128. In
    interpret mode there is no MXU to align for, and the unconditional
    lane padding is a measured 16-32x flop multiplier on CPU (d 8->128
    squares through the distance matmul, t 4->128 through K@V) — so the
    emulation path skips it entirely.
    """
    if interpret:
        return min(bm, m), min(bn, n), 1
    sublane = 16 if cdt.itemsize < 4 else 8
    bm_eff = min(_round_up(bm, sublane), _round_up(m, sublane))
    bn_eff = min(_round_up(bn, sublane), _round_up(n, _LANE))
    return bm_eff, bn_eff, _LANE


def _pass_inputs(ppass: _PallasPass, cdt):
    """The fp32 SMEM scalar vector of one pass (the kernel body is fp32
    math at any operand dtype — see conformance tolerances)."""
    return jnp.stack(
        [jnp.asarray(s).astype(jnp.float32) for s in ppass.scalars])[None, :]


def _run_pass(ppass: _PallasPass, Xi, Xj, V, *, bm, bn, interpret, cdt):
    """One fused Pallas launch; returns the (m, t) fp32 contribution."""
    m, _ = Xi.shape
    n, t = V.shape
    bm_eff, bn_eff, lane = _tile_geometry(m, n, bm, bn, cdt, interpret)
    # the launch's operands: scaled, cast and padded to the tile geometry
    with named_scope("kmvm.prep"):
        Xi_s = (Xi / ppass.lengthscale).astype(cdt)
        Xj_s = (Xj / ppass.lengthscale).astype(cdt)
        Vs = (ppass.base_weight * V.astype(jnp.float32)).astype(cdt)
        scalars = _pass_inputs(ppass, cdt)
        Xi_p = _pad_axis(_pad_axis(Xi_s, 0, bm_eff), 1, lane)
        Xj_p = _pad_axis(_pad_axis(Xj_s, 0, bn_eff), 1, lane)
        V_p = _pad_axis(_pad_axis(Vs, 0, bn_eff), 1, lane)

    out = kmvm_pallas(ppass.components, Xi_p, Xj_p, V_p, scalars,
                      bm=bm_eff, bn=bn_eff, interpret=interpret,
                      compute_dtype=str(cdt))
    return out[:m, :t]


def _mixed_dot(A, B, cdt):
    """A @ B on cdt operands with fp32 MXU accumulation."""
    return jax.lax.dot_general(
        A.astype(cdt), B.astype(cdt), (((A.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def kmvm_block(
    kernel,
    Xi: jax.Array,
    Xj: jax.Array,
    V: jax.Array,
    params,
    *,
    bm: int = DEFAULT_BM,
    bn: int = DEFAULT_BN,
    interpret: bool | None = None,
    compute_dtype: str | None = None,
) -> jax.Array:
    """K(Xi, Xj) @ V via the fused Pallas plan; arbitrary shapes/dtypes.

    kernel: legacy kind string or a KernelSpec/expression; params the
    matching GPParams / KernelParams. Semantics identical to
    `repro.kernels.ref.kmvm_ref` (no noise term — the diagonal sigma^2 V
    is the caller's O(n) epilogue).

    compute_dtype: MXU operand dtype of the in-kernel matmuls. "bfloat16"
    halves the HBM operand traffic as well (tiles are stored pre-cast) and
    accumulates in fp32; None/"float32" is the exact path. All elementwise
    kernel math stays fp32 regardless.
    """
    interpret = resolve_interpret(interpret)
    cdt = jnp.dtype(compute_dtype if compute_dtype is not None else jnp.float32)
    squeeze = V.ndim == 1
    if squeeze:
        V = V[:, None]

    plan = mvm_plan(kernel, params)
    acc = None
    for ppass in plan.passes:
        out = _run_pass(ppass, Xi, Xj, V, bm=bm, bn=bn,
                        interpret=interpret, cdt=cdt)
        acc = out if acc is None else acc + out
    for w, p in plan.linear_terms:
        s = softplus(p.raw_scale)
        # two thin matmuls: K_lin @ V = (Xi/s) (Xj/s)^T V — never (m, n)
        proj = _mixed_dot((Xj / s).T, V.astype(jnp.float32), cdt)  # (d, t)
        out = w * _mixed_dot(Xi / s, proj, cdt)
        acc = out if acc is None else acc + out
    for term in plan.fallback_terms:
        # dense-slab fallback (fp32 math, matching the kernel's contract)
        K = None
        for kind, p in term.factors:
            Kf = leaf_matrix(kind, p, Xi.astype(jnp.float32),
                             Xj.astype(jnp.float32))
            K = Kf if K is None else K * Kf
        out = term.weight * _mixed_dot(K, V.astype(jnp.float32), cdt)
        acc = out if acc is None else acc + out

    out = acc.astype(V.dtype)
    return out[:, 0] if squeeze else out


def fused_pass_or_none(kernel, params) -> _PallasPass | None:
    """The single fused Pallas pass covering the WHOLE spec, or None when
    the spec needs anything else (ARD metrics, linear terms, dense
    fallbacks). The gate for every all-in-one-launch fast path: the
    blocksparse gathered grid and the fused-CG megakernel both require the
    complete kernel sum to live in one tile epilogue."""
    mp = mvm_plan(kernel, params)
    if len(mp.passes) == 1 and not mp.linear_terms and not mp.fallback_terms:
        return mp.passes[0]
    return None


def kmvm_fused_matmat(
    kernel,
    X: jax.Array,        # (n, d)
    V: jax.Array,        # (n, t) the direction block
    R: jax.Array,        # (n, t) the residual block
    params,
    *,
    bm: int = DEFAULT_BM,
    bn: int = DEFAULT_BN,
    interpret: bool | None = None,
    compute_dtype: str | None = None,
) -> tuple[jax.Array, jax.Array]:
    """K(X, X) @ V plus the CG dot block, in ONE Pallas launch.

    Returns (KV (n, t) fp32, dots (4, t) fp32) with dots rows
    [<Kv, v>, <r, v>, <r, r>, <v, v>] per column — exactly the reductions a
    CG iteration needs (standard: pKp and ||r||^2; pipelined: gamma, delta,
    ||r||^2), formed from VMEM while the output row tile is still resident
    instead of via separate HBM-traversing reduction passes. NO noise term
    anywhere: the caller adds sigma^2 V to KV and sigma^2 <v,v> to dots[0].

    Requires the spec to plan to a single fused pass
    (`fused_pass_or_none`); raises ValueError otherwise — callers gate.
    """
    interpret = resolve_interpret(interpret)
    cdt = jnp.dtype(compute_dtype if compute_dtype is not None else jnp.float32)
    ppass = fused_pass_or_none(kernel, params)
    if ppass is None:
        raise ValueError(
            f"kmvm_fused_matmat needs a single-fused-pass plan; "
            f"{kernel!r} plans to {mvm_plan(kernel, params)}")
    n, _ = X.shape
    t = V.shape[1]
    bm_eff, bn_eff, lane = _tile_geometry(n, n, bm, bn, cdt, interpret)
    with named_scope("kmvm.prep"):
        Xs = (X / ppass.lengthscale).astype(cdt)
        Vs = (ppass.base_weight * V.astype(jnp.float32)).astype(cdt)
        scalars = _pass_inputs(ppass, cdt)
        Xi_p = _pad_axis(_pad_axis(Xs, 0, bm_eff), 1, lane)
        Xj_p = _pad_axis(_pad_axis(Xs, 0, bn_eff), 1, lane)
        V_p = _pad_axis(_pad_axis(Vs, 0, bn_eff), 1, lane)
        # row views enter UNSCALED and fp32: zero-padded rows contribute
        # zero to every dot, so the dot block is exact despite row padding
        Vr_p = _pad_axis(_pad_axis(V.astype(jnp.float32), 0, bm_eff), 1,
                         lane)
        R_p = _pad_axis(_pad_axis(R.astype(jnp.float32), 0, bm_eff), 1,
                        lane)

    out, dots = kmvm_pallas_dots(
        ppass.components, Xi_p, Xj_p, V_p, Vr_p, R_p, scalars,
        bm=bm_eff, bn=bn_eff, interpret=interpret, compute_dtype=str(cdt))
    return out[:n, :t], jnp.sum(dots, axis=0)[:4, :t]


def pallas_block_fn(kernel, *, bm: int = DEFAULT_BM, bn: int = DEFAULT_BN,
                    interpret: bool | None = None,
                    compute_dtype: str | None = None):
    """Adapter for `partitioned.kmvm(..., block_fn=...)`: per-partition slab
    MVMs go through the fused kernel instead of the dense jnp path."""

    def fn(Xb, X, V, params):
        return kmvm_block(kernel, Xb, X, V, params, bm=bm, bn=bn,
                          interpret=interpret, compute_dtype=compute_dtype)

    return fn
