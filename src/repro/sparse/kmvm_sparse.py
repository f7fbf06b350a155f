"""Pallas TPU kernel: block-sparse fused kernel-MVM over a gathered grid.

The dense fused kernel (`repro.kernels.kmvm`) walks a full (m/bm, n/bn)
grid. Here the grid is the ACTIVE-PAIR LIST the sparsity planner emitted:
grid = (P,), and three scalar-prefetch vectors — pair_rows, pair_cols,
pair_first — drive the BlockSpec index maps, so the kernel only ever DMAs
the (tile, d) X blocks and (tile, t) V blocks of pairs the plan kept.
Inactive tiles are never touched: no HBM reads, no FLOPs — the
"bitwise-skip" the `blocksparse` backend advertises.

Per grid step p (one active (i, j) tile pair):

    1. @pl.when(pair_first[p]) zero the output tile (pairs are sorted by
       row, so each output tile's visits are consecutive and it stays
       resident in VMEM across its whole reduction)
    2. MXU: G = Xi_i @ Xj_j^T; VPU: D2 from the norm expansion
    3. VPU: K = sum_c w_c * prod_f phi_cf(q_cf D2) — the same static
       multi-component epilogue as the dense kernel (Wendland tapers are
       just another phi), scalars broadcast from SMEM
    4. MXU: out_i += K @ V_j, fp32 accumulation at any operand dtype

Off-TPU the `blocksparse` backend uses the masked-partitioned jnp path
instead (`repro.sparse.blocksparse.masked_kmvm`); this kernel still runs
under interpret mode for conformance tests (OperatorConfig.interpret=True).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.kernels_math import kernel_from_sqdist
from repro.kernels.kmvm import mxu_precision

_LANE = 128


def _bs_kernel(components, compute_dtype, rows_ref, cols_ref, first_ref,
               scal_ref, xi_ref, xj_ref, v_ref, out_ref):
    """One active pair: out[rows[p]] += K_tile @ V[cols[p]].

    rows/cols/first are the scalar-prefetch vectors (SMEM); the component
    scalars share the dense kernel's flat layout (`kmvm.scalar_layout`).
    """
    del rows_ref, cols_ref  # consumed by the BlockSpec index maps
    p = pl.program_id(0)

    @pl.when(first_ref[p] == 1)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    xi = xi_ref[...].astype(compute_dtype)   # (tile, d)
    xj = xj_ref[...].astype(compute_dtype)   # (tile, d)
    v = v_ref[...].astype(compute_dtype)     # (tile, t)

    g = jax.lax.dot_general(
        xi, xj, (((1,), (1,)), ((), ())),
        precision=mxu_precision(compute_dtype),
        preferred_element_type=jnp.float32)
    xi32 = xi.astype(jnp.float32)
    xj32 = xj.astype(jnp.float32)
    ni = jnp.sum(xi32 * xi32, axis=1, keepdims=True)
    nj = jnp.sum(xj32 * xj32, axis=1, keepdims=True).T
    d2 = jnp.maximum(ni + nj - 2.0 * g, 0.0)

    k = None
    s = 0
    for kinds in components:
        w = scal_ref[0, s]
        s += 1
        term = None
        for kind in kinds:
            q = scal_ref[0, s]
            s += 1
            if kind == "rq":
                alpha = scal_ref[0, s]
                s += 1
                f = kernel_from_sqdist("rq", q * d2, alpha)
            else:
                f = kernel_from_sqdist(kind, q * d2)
            term = f if term is None else term * f
        term = w * term
        k = term if k is None else k + term

    out_ref[...] += jax.lax.dot_general(
        k.astype(compute_dtype), v, (((1,), (0,)), ((), ())),
        precision=mxu_precision(compute_dtype),
        preferred_element_type=jnp.float32)


@functools.partial(
    jax.jit, static_argnames=("components", "tile", "interpret",
                              "compute_dtype"))
def kmvm_blocksparse_pallas(
    components,          # static tuple of factor-kind tuples
    Xs: jax.Array,       # (n_pad, d) pre-scaled SORTED rows, n_pad % tile == 0
    V: jax.Array,        # (n_pad, t) pre-scaled sorted RHS, t % 128 == 0
    scalars: jax.Array,  # (1, L) fp32 per-component scalars
    pair_rows: jax.Array,   # (P,) int32 active row-tile indices, sorted
    pair_cols: jax.Array,   # (P,) int32 active col-tile indices
    pair_first: jax.Array,  # (P,) int32: 1 at the first pair of each row
    *,
    tile: int,
    interpret: bool = False,
    compute_dtype: str = "float32",
) -> jax.Array:
    """[sum_c w_c prod_f phi(q d2)] @ V over active tile pairs only.

    Shapes must be pre-padded (d/t to 128 lanes, rows to the tile); output
    rows whose tiles have no active pair never initialize, so the caller
    must rely only on rows the plan covers (every row tile carries at least
    its diagonal pair — box distance to itself is zero).
    """
    n_pad, d = Xs.shape
    _, t = V.shape
    P = pair_rows.shape[0]
    assert n_pad % tile == 0, (n_pad, tile)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(P,),
        in_specs=[
            pl.BlockSpec((1, scalars.shape[1]), lambda p, r, c, f: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((tile, d), lambda p, r, c, f: (r[p], 0)),
            pl.BlockSpec((tile, d), lambda p, r, c, f: (c[p], 0)),
            pl.BlockSpec((tile, t), lambda p, r, c, f: (c[p], 0)),
        ],
        out_specs=pl.BlockSpec((tile, t), lambda p, r, c, f: (r[p], 0)),
    )
    return pl.pallas_call(
        functools.partial(_bs_kernel, components, jnp.dtype(compute_dtype)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_pad, t), jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(pair_rows, pair_cols, pair_first, scalars, Xs, Xs, V)
