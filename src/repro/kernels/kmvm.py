"""Pallas TPU kernel: fused distance -> kernel-sum -> MVM for one row partition.

The paper's compute hot spot is `K_{X^(l) X} @ V`: materialize a (rb, n)
kernel slab in HBM, GEMM it into V, discard it. On TPU we go further — the
slab never reaches HBM at all. The kernel fuses, per (bm, bn) VMEM tile:

    1. MXU:  G  = Xi_tile @ Xj_tile^T              (the -2<x,y> term)
    2. VPU:  D2 = |xi|^2 + |xj|^2 - 2 G            (squared distances)
    3. VPU:  K  = sum_c w_c * prod_f phi_cf(q_cf D2)   (multi-component
             epilogue: every stationary component that shares the tile's
             pre-scaling is evaluated on the SAME D2 and accumulated)
    4. MXU:  acc += K @ V_tile                     (fp32 accumulation)

HBM traffic drops from O(rb * n) slab writes+reads to just the X/V tile
reads — and, new with the kernel algebra, a whole SUM kernel costs one pass
over HBM instead of one pass per component (see EXPERIMENTS.md §Kernel
algebra for the roofline reading).

Components are a STATIC tuple of factor-kind tuples (e.g. ``(("rbf",),
("matern32",))`` for rbf + matern32); their hyperparameters arrive as a
flat per-component scalar vector in SMEM (layout below), so the kernel body
still specializes only on structure:

    for each component c:  w_c                     (relative weight)
        for each factor f: q_cf                    (lengthscale ratio^2:
                                                    D2_cf = q_cf * D2_tile)
                           alpha_cf  (rq only)     (mixture parameter)

Inputs arrive pre-scaled by the pass's reference lengthscale and V
pre-scaled by the base weight (both O(n d) host-side ops); a single
component degenerates to w = q = 1.0 — bitwise the pre-algebra kernel.

Grid: (rb/bm, n/bn), with the n axis innermost so each output tile stays
resident in VMEM across the whole reduction. On TPU tile sizes are
multiples of (8, 128) sublane x lane and the feature dim d and RHS count t
are zero-padded to 128 by the wrapper (exact: padded features contribute 0
to distances, padded V columns are sliced off); in interpret mode the
wrapper skips the lane/sublane padding entirely — there is no MXU to
align for, and padding d 8->128 and t 4->128 was measured as a 16-32x
flop multiplier on the CPU emulation path.

Validated against `repro.kernels.ref` in interpret mode on the CPU by the
tests, and compiled for a described v5e by `tests/test_tpu_compile.py`;
`repro.kernels.ops.resolve_interpret` decides which mode a launch uses.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.kernels_math import kernel_from_sqdist

# Tile defaults: (bm, bn) = (256, 512) fp32.
# VMEM budget per tile set:
#   Xi (256,128)*4B = 128 KiB, Xj (512,128)*4B = 256 KiB, V (512,128)*4B = 256 KiB,
#   K tile (256,512)*4B = 512 KiB, acc (256,128)*4B = 128 KiB  => ~1.3 MiB << 16 MiB VMEM,
# leaving room for double-buffered input pipelining. The multi-component
# epilogue reuses the same K tile accumulator, so the budget is unchanged.
DEFAULT_BM = 256
DEFAULT_BN = 512


def mxu_precision(compute_dtype):
    """The kernels' dot precision: HIGHEST (6 bf16 passes) for float32
    operands, DEFAULT (one pass) for 16-bit ones.

    On a v5e the one-pass float32 K_hat @ V missed a float64 reference by
    ~1e3 times the fp32 tolerance, and Mosaic refuses HIGH. The choice is
    explicit both ways: Pallas lowers a precision without looking at the
    operand dtype, so the process-wide default `repro.launch.runtime` sets
    for XLA's float32 matmuls would otherwise reach the bf16 kernels too.
    """
    return (jax.lax.Precision.HIGHEST if jnp.dtype(compute_dtype).itemsize >= 4
            else jax.lax.Precision.DEFAULT)


def scalar_layout(components: tuple) -> int:
    """Length of the flat SMEM scalar vector for a static component tuple."""
    n = 0
    for kinds in components:
        n += 1  # w_c
        for kind in kinds:
            n += 2 if kind == "rq" else 1  # q_cf (+ alpha_cf)
    return n


def _kernel_tile(components, compute_dtype, scal_ref, xi_ref, xj_ref):
    """The shared tile body: d2 on the MXU/VPU, then the multi-component
    epilogue — every component evaluated on the SAME d2 tile (VMEM only).
    Returns the (bm, bn) fp32 kernel tile."""
    xi = xi_ref[...].astype(compute_dtype)   # (bm, d)
    xj = xj_ref[...].astype(compute_dtype)   # (bn, d)

    # MXU: cross term (fp32 accumulation); VPU: norms in fp32
    g = jax.lax.dot_general(
        xi, xj, (((1,), (1,)), ((), ())),
        precision=mxu_precision(compute_dtype),
        preferred_element_type=jnp.float32)
    xi32 = xi.astype(jnp.float32)
    xj32 = xj.astype(jnp.float32)
    ni = jnp.sum(xi32 * xi32, axis=1, keepdims=True)       # (bm, 1)
    nj = jnp.sum(xj32 * xj32, axis=1, keepdims=True).T     # (1, bn)
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
        k = term if k is None else k + term                # (bm, bn)
    return k


def _kmvm_kernel(components, compute_dtype, scal_ref, xi_ref, xj_ref, v_ref,
                 out_ref):
    """One (i, j) grid step: out[i] += K_tile @ V_j with
    K_tile = sum_c w_c prod_f phi_cf(q_cf * d2(Xi_i, Xj_j)).

    compute_dtype is the MXU operand dtype of the two matmuls (fp32 by
    default, bf16 on the mixed-precision path); BOTH accumulate in fp32
    via preferred_element_type, and phi/norms always run fp32 on the VPU.
    """
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    k = _kernel_tile(components, compute_dtype, scal_ref, xi_ref, xj_ref)
    v = v_ref[...].astype(compute_dtype)     # (bn, t)
    out_ref[...] += jax.lax.dot_general(
        k.astype(compute_dtype), v, (((1,), (0,)), ((), ())),
        precision=mxu_precision(compute_dtype),
        preferred_element_type=jnp.float32)


def _kmvm_dots_kernel(components, compute_dtype, scal_ref, xi_ref, xj_ref,
                      v_ref, vr_ref, r_ref, out_ref, dots_ref):
    """The fused-CG megakernel step: out[i] += K_tile @ V_j as above, plus —
    at the LAST column step, when the row tile of K@V is complete in VMEM —
    the per-row-tile partial dot block the CG iteration needs:

        dots[i] = [ <Kv, v>, <r, v>, <r, r>, <v, v> ]   (per RHS column)

    vr/r are the i-indexed (bm, t) row views of the UNSCALED direction block
    and the residual block (zero rows in the padding region, so every dot is
    exact despite row padding even though the padded rows of K@V are not).
    Summing the (grid_m, ...) partials and adding the noise correction
    sigma^2 <v, v> happens outside; one launch replaces an MVM plus two
    HBM-traversing reduction passes.
    """
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)
        dots_ref[...] = jnp.zeros_like(dots_ref)

    k = _kernel_tile(components, compute_dtype, scal_ref, xi_ref, xj_ref)
    v = v_ref[...].astype(compute_dtype)     # (bn, t)
    out_ref[...] += jax.lax.dot_general(
        k.astype(compute_dtype), v, (((1,), (0,)), ((), ())),
        precision=mxu_precision(compute_dtype),
        preferred_element_type=jnp.float32)

    @pl.when(j == pl.num_programs(1) - 1)
    def _dots():
        kv = out_ref[...]                          # (bm, t) complete fp32
        vr = vr_ref[...].astype(jnp.float32)
        r = r_ref[...].astype(jnp.float32)
        d0 = jnp.sum(kv * vr, axis=0)              # <Kv, v>
        d1 = jnp.sum(r * vr, axis=0)               # <r, v>
        d2 = jnp.sum(r * r, axis=0)                # <r, r>
        d3 = jnp.sum(vr * vr, axis=0)              # <v, v>
        z = jnp.zeros_like(d0)
        dots_ref[...] = jnp.stack([d0, d1, d2, d3, z, z, z, z])[None]


@functools.partial(
    jax.jit, static_argnames=("components", "bm", "bn", "interpret",
                              "compute_dtype"))
def kmvm_pallas_dots(
    components,
    Xi: jax.Array,       # (m, d)  pre-scaled rows, m % bm == 0
    Xj: jax.Array,       # (n, d)  pre-scaled columns, n % bn == 0
    V: jax.Array,        # (n, t)  pre-scaled RHS (column view)
    Vrow: jax.Array,     # (m, t)  UNSCALED RHS, row view (zero-padded rows)
    R: jax.Array,        # (m, t)  unscaled residual block, row view
    scalars: jax.Array,  # (1, L)
    *,
    bm: int = DEFAULT_BM,
    bn: int = DEFAULT_BN,
    interpret: bool = False,
    compute_dtype: str = "float32",
) -> tuple[jax.Array, jax.Array]:
    """Fused K @ V plus the CG dot block; returns (out (m, t) fp32,
    dots (m/bm, 8, t) fp32 per-row-tile partials, rows [<Kv,v>, <r,v>,
    <r,r>, <v,v>, 0...])."""
    m, d = Xi.shape
    n, t = V.shape
    assert Xj.shape == (n, d), (Xi.shape, Xj.shape, V.shape)
    assert Vrow.shape == (m, t) and R.shape == (m, t), (Vrow.shape, R.shape)
    assert m % bm == 0 and n % bn == 0, (m, bm, n, bn)
    L = scalar_layout(components)
    assert scalars.shape == (1, L), (scalars.shape, components)

    grid = (m // bm, n // bn)
    out, dots = pl.pallas_call(
        functools.partial(_kmvm_dots_kernel, components,
                          jnp.dtype(compute_dtype)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, L), lambda i, j: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((bm, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, d), lambda i, j: (j, 0)),
            pl.BlockSpec((bn, t), lambda i, j: (j, 0)),
            pl.BlockSpec((bm, t), lambda i, j: (i, 0)),
            pl.BlockSpec((bm, t), lambda i, j: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bm, t), lambda i, j: (i, 0)),
            pl.BlockSpec((1, 8, t), lambda i, j: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, t), jnp.float32),
            jax.ShapeDtypeStruct((m // bm, 8, t), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="kmvm_pallas_dots",
    )(scalars, Xi, Xj, V, Vrow, R)
    return out, dots


def _kmvm_acc_kernel(components, compute_dtype, scal_ref, xi_ref, xj_ref,
                     v_ref, acc_ref, out_ref):
    """Chunk step of the collective-matmul pipeline: out[i] = acc[i] +
    K(Xi_i, Xj_j) @ V_j — identical to `_kmvm_kernel` except the output
    tile initializes from a carried accumulator instead of zeros, so one
    launch advances the contraction by one source chunk while the ring
    transfer for the NEXT chunk is in flight (see
    `core.distributed._chunked_contraction`)."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = acc_ref[...]

    k = _kernel_tile(components, compute_dtype, scal_ref, xi_ref, xj_ref)
    v = v_ref[...].astype(compute_dtype)     # (bn, t)
    out_ref[...] += jax.lax.dot_general(
        k.astype(compute_dtype), v, (((1,), (0,)), ((), ())),
        precision=mxu_precision(compute_dtype),
        preferred_element_type=jnp.float32)


@functools.partial(
    jax.jit, static_argnames=("components", "bm", "bn", "interpret",
                              "compute_dtype"))
def kmvm_pallas_chunk(
    components,
    Xi: jax.Array,       # (m, d)  pre-scaled rows, m % bm == 0
    Xj: jax.Array,       # (nc, d) pre-scaled columns of ONE chunk, nc % bn == 0
    V: jax.Array,        # (nc, t) pre-scaled RHS chunk
    scalars: jax.Array,  # (1, L)
    acc: jax.Array,      # (m, t)  fp32 running partial (aliased in place)
    *,
    bm: int = DEFAULT_BM,
    bn: int = DEFAULT_BN,
    interpret: bool = False,
    compute_dtype: str = "float32",
) -> jax.Array:
    """acc + K(Xi, Xj_chunk) @ V_chunk — the chunked-contraction entry.

    The distributed overlap path splits the tile contraction over source
    chunks and needs each chunk's contribution as a separate launch (so the
    ppermute for chunk s+1 can overlap chunk s's compute). The accumulator
    is input/output-aliased: the partial stays in place in HBM across the
    d_row chunk steps, costing one extra (m, t) read per step over the
    single-launch kernel — negligible next to the (m, nc) tile work.
    """
    m, d = Xi.shape
    nc, t = V.shape
    assert Xj.shape == (nc, d), (Xi.shape, Xj.shape, V.shape)
    assert acc.shape == (m, t), (acc.shape, (m, t))
    assert m % bm == 0 and nc % bn == 0, (m, bm, nc, bn)
    L = scalar_layout(components)
    assert scalars.shape == (1, L), (scalars.shape, components)

    grid = (m // bm, nc // bn)
    return pl.pallas_call(
        functools.partial(_kmvm_acc_kernel, components,
                          jnp.dtype(compute_dtype)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, L), lambda i, j: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((bm, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, d), lambda i, j: (j, 0)),
            pl.BlockSpec((bn, t), lambda i, j: (j, 0)),
            pl.BlockSpec((bm, t), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bm, t), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, t), jnp.float32),
        input_output_aliases={4: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="kmvm_pallas_chunk",
    )(scalars, Xi, Xj, V, acc)


@functools.partial(
    jax.jit, static_argnames=("components", "bm", "bn", "interpret",
                              "compute_dtype"))
def kmvm_pallas(
    components,      # static tuple of factor-kind tuples, e.g. (("rbf",),)
    Xi: jax.Array,   # (m, d)  pre-scaled rows, m % bm == 0
    Xj: jax.Array,   # (n, d)  pre-scaled columns, n % bn == 0
    V: jax.Array,    # (n, t)  pre-scaled RHS, t % 128 == 0
    scalars: jax.Array,  # (1, L) fp32 per-component scalars, L = scalar_layout
    *,
    bm: int = DEFAULT_BM,
    bn: int = DEFAULT_BN,
    interpret: bool = False,
    compute_dtype: str = "float32",
) -> jax.Array:
    """Fused [sum_c w_c prod_f phi(q d2(Xi, Xj))] @ V.

    Shapes must be pre-padded (see ops.py); the scalar vector lives in SMEM
    and is broadcast to every grid step.
    """
    m, d = Xi.shape
    n, t = V.shape
    assert Xj.shape == (n, d), (Xi.shape, Xj.shape, V.shape)
    assert m % bm == 0 and n % bn == 0, (m, bm, n, bn)
    L = scalar_layout(components)
    assert scalars.shape == (1, L), (scalars.shape, components)

    grid = (m // bm, n // bn)
    return pl.pallas_call(
        functools.partial(_kmvm_kernel, components, jnp.dtype(compute_dtype)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, L), lambda i, j: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((bm, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, d), lambda i, j: (j, 0)),
            pl.BlockSpec((bn, t), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bm, t), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, t), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="kmvm_pallas",
    )(scalars, Xi, Xj, V)
