"""Distributed partitioned-MVM GP engine over a TPU mesh (shard_map).

This is the paper's Section 3 ("Distributed MVMs in Parallel") mapped onto
jax-native constructs. Two modes:

  * ``mode="1d"`` — the paper's scheme, faithfully. Kernel-matrix ROWS are
    partitioned over the row axes; each device holds a row shard of every
    CG vector. One iteration: `all_gather` the new search direction p over
    the row axes (O(n) bytes per device — the paper's communication claim),
    compute the local `K(B_i, X) @ p_full` slab-blockwise, add the local
    noise diagonal, psum the two CG dot products. No column parallelism.

  * ``mode="2d"`` — beyond-paper. Rows are sharded over the row axes AND
    columns over the col axes (`model`). CG vectors are sharded over ALL
    mesh axes (chunk c = B_i[sub_j], the j-th sub-slice of row block i).
    One iteration:
        v[C_j]  = all_gather(v_local over row axes)          (n/tp bytes)
        partial = K(B_i, C_j) @ v[C_j]                        (local tile)
        o_local = psum_scatter(partial over col axes)         (n/dp bytes)
    so per-device collective volume drops from n to n/tp + n/dp (8x on a
    16x16 mesh) and the tile compute parallelizes over all dp*tp devices.
    The column blocks C_j = U_i B_i[sub_j] are strided, which makes the
    scatter output land exactly in the vector's storage layout — the scheme
    closes with zero re-sharding.

Everything else (preconditioner, SLQ, the MLL custom-VJP) is re-derived in
sharded form below. X (n, d) is replicated: at n = 10^6, d <= 400 this is
<= 1.6 GB fp32 and is the paper's own assumption ("requires access to the
full training set X, which we assume fits in memory"); the pivoted-Cholesky
factor and all CG state are sharded.

The engine plugs into the rest of the stack as `ShardedOperator`, the
"sharded" entry of the `repro.core.operators` registry: it exposes the same
matvec/preconditioner/allreduce/quad_form_grads surface as the
single-device backends (composing any inner slab backend — dense jnp,
mixed-precision, or the fused Pallas kernel — for the local tiles), so the
MLL forward is literally `mll.operator_mll_forward` running inside
shard_map.
"""

from __future__ import annotations

from functools import partial
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from repro import obs
from repro.obs.profiling import named_scope

from .kernels_math import (
    constant_mean,
    kernel_diag,
    kernel_matrix,
    noise_variance,
)
from .operators import (
    KernelOperator,
    OperatorConfig,
    register_operator,
    slab_block_fn_for,
)
from .partitioned import kmvm_rect, quad_form_partials
from .pcg import pcg
from .mll import operator_mll_forward, operator_mll_quad_grads


class DistGeometry(NamedTuple):
    """Static layout of the distributed engine on a mesh.

    When n does not divide the shard grid the layout is PADDED: arrays carry
    `n_padded` rows (pad rows zero in X/y), every collective and tile runs on
    the padded shapes, and a static per-chunk mask confines the solver to the
    true rows — K_hat_pad = M K M + s2 I is block-diagonal
    (K_hat_true, s2 I_pad), so masked CG vectors never mix with the pad
    block and the MLL/gradients cover exactly the n true rows. With
    `n_pad is None` (n divides) every mask is compiled out and the engine is
    bitwise-identical to the unpadded layout (golden-pinned).
    """

    n: int                      # global TRUE training-set size
    d: int                      # input dimension
    row_axes: tuple             # mesh axes sharding kernel ROWS (e.g. ("pod","data"))
    col_axes: tuple             # mesh axes sharding kernel COLUMNS (() = paper 1-D)
    d_row: int                  # prod of row-axis sizes
    d_col: int                  # prod of col-axis sizes (1 in 1-D mode)
    row_block: int = 1024       # inner slab blocking of the local tile
    n_pad: int | None = None    # padded global size (None = n divides, no pad)
    overlap: bool = False       # ring-pipeline the gather with tile compute
    row_sizes: tuple = ()       # per-axis sizes of row_axes (static ring bounds)
    col_sizes: tuple = ()       # per-axis sizes of col_axes

    @property
    def all_axes(self) -> tuple:
        return (*self.row_axes, *self.col_axes)

    @property
    def n_padded(self) -> int:  # array-layout size (== n when no padding)
        return self.n if self.n_pad is None else self.n_pad

    @property
    def has_pad(self) -> bool:
        return self.n_padded != self.n

    @property
    def pad_rows(self) -> int:
        return self.n_padded - self.n

    @property
    def n_local(self) -> int:   # CG-vector chunk per device
        return self.n_padded // (self.d_row * self.d_col)

    @property
    def rows_local(self) -> int:  # kernel rows per row-group
        return self.n_padded // self.d_row

    @property
    def cols_local(self) -> int:  # kernel cols per col-group
        return self.n_padded // self.d_col

    def vector_pspec(self) -> P:
        return P(self.all_axes)


def make_geometry(mesh: Mesh, n: int, d: int, *, mode: str = "2d",
                  row_block: int = 1024, overlap: bool = False,
                  tile_multiple: int = 1) -> DistGeometry:
    """1d (paper-faithful): rows partitioned over EVERY mesh axis — the
    paper round-robins row blocks over all w devices. 2d (beyond-paper):
    rows over (pod, data), columns over model.

    Any n runs on any mesh: when n does not divide the shard grid the
    geometry pads to the next multiple (masked rows — see DistGeometry).
    `tile_multiple` additionally forces every per-device chunk to hold
    whole sparsity tiles (blocksparse: pass the plan's tile size).
    `overlap=True` pipelines the per-iteration gather against the local
    tile compute (collective-matmul chunking over the contraction axis).
    """
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    if mode == "1d":
        row_axes = tuple(a for a in ("pod", "data", "model") if a in sizes)
        col_axes = ()
    else:
        row_axes = tuple(a for a in ("pod", "data") if a in sizes)
        col_axes = ("model",) if "model" in sizes else ()
    d_row = int(np.prod([sizes[a] for a in row_axes]))
    d_col = int(np.prod([sizes[a] for a in col_axes])) if col_axes else 1
    m = d_row * d_col * max(int(tile_multiple), 1)
    n_padded = -(-n // m) * m
    n_pad = None if n_padded == n else n_padded
    if n_pad is not None:
        obs.gauge("dist.pad_rows").set(n_padded - n)
    return DistGeometry(n=n, d=d, row_axes=row_axes, col_axes=col_axes,
                        d_row=d_row, d_col=d_col, row_block=row_block,
                        n_pad=n_pad, overlap=overlap,
                        row_sizes=tuple(sizes[a] for a in row_axes),
                        col_sizes=tuple(sizes[a] for a in col_axes))


def pad_to_geometry(geom: DistGeometry, arr: jax.Array) -> jax.Array:
    """Zero-pad axis 0 from geom.n to geom.n_padded (no-op when n divides).

    Apply to X / y / any full-length vector BEFORE replicate/shard_vector;
    the pad rows are masked out of every solve, so zeros are just layout.
    """
    extra = geom.n_padded - arr.shape[0]
    if extra <= 0:
        return arr
    widths = [(0, extra)] + [(0, 0)] * (arr.ndim - 1)
    return jnp.pad(arr, widths)


# ---------------------------------------------------------------------------
# local-shard helpers (only valid inside shard_map over geom's mesh)
# ---------------------------------------------------------------------------


def _linear_index(axes: tuple, sizes: tuple) -> jax.Array:
    idx = jnp.zeros((), jnp.int32)
    for a, s in zip(axes, sizes):
        idx = idx * s + jax.lax.axis_index(a)
    return idx


def _axis_sizes(axes: tuple) -> tuple:
    return tuple(jax.lax.psum(1, a) for a in axes)


def _x_rows(geom: DistGeometry, X: jax.Array) -> jax.Array:
    """X[B_i] for this device's row group (rows_local, d)."""
    if not geom.row_axes:
        return X
    i = _linear_index(geom.row_axes, _axis_sizes(geom.row_axes))
    return jax.lax.dynamic_slice_in_dim(X, i * geom.rows_local, geom.rows_local, 0)


def _x_cols(geom: DistGeometry, X: jax.Array) -> jax.Array:
    """X[C_j] for this device's column group (cols_local, d).

    C_j is strided: the j-th n_local sub-slice of every row block B_i.
    """
    if not geom.col_axes:
        return X
    j = _linear_index(geom.col_axes, _axis_sizes(geom.col_axes))
    Xr = X.reshape(geom.d_row, geom.d_col * geom.n_local, geom.d)
    sl = jax.lax.dynamic_slice_in_dim(Xr, j * geom.n_local, geom.n_local, 1)
    return sl.reshape(geom.d_row * geom.n_local, geom.d)


def _x_chunk(geom: DistGeometry, X: jax.Array) -> jax.Array:
    """X rows for this device's CG-vector chunk (n_local, d)."""
    c = _linear_index(geom.all_axes, _axis_sizes(geom.all_axes))
    return jax.lax.dynamic_slice_in_dim(X, c * geom.n_local, geom.n_local, 0)


def _chunk_offset(geom: DistGeometry) -> jax.Array:
    c = _linear_index(geom.all_axes, _axis_sizes(geom.all_axes))
    return c * geom.n_local


def _psum_all(geom: DistGeometry, x):
    return jax.lax.psum(x, geom.all_axes)


def _chunk_mask(geom: DistGeometry, dtype) -> jax.Array | None:
    """(n_local,) 1/0 mask of TRUE rows in this device's vector chunk, or
    None when the geometry has no padding (every mask compiles out — the
    unpadded path stays bitwise-identical). Pad rows are the global tail,
    so only trailing chunks carry zeros."""
    if not geom.has_pad:
        return None
    gidx = _chunk_offset(geom) + jnp.arange(geom.n_local)
    return (gidx < geom.n).astype(dtype)


# ---------------------------------------------------------------------------
# distributed K_hat MVM (the paper's partitioned MVM on the mesh)
# ---------------------------------------------------------------------------
#
# The 2-D tile contraction K(B_i, :) @ V is decomposed over SOURCE chunks:
# each device accumulates sum_s K(B_i, chunk_s) @ V[chunk_s] over the d_row
# chunks its column group holds. Two executions of the SAME accumulation
# order:
#
#   serial  — one all_gather over the row axes up front, then slice chunk s
#             out of the gathered buffer per step;
#   overlap — collective matmul (Wang et al., ASPLOS'23 style): the chunks
#             ring-rotate via ppermute, and the transfer for step s+1 is
#             issued BEFORE the tile compute of step s, so XLA's async
#             scheduler hides the collective behind the matmul.
#
# Both walk source chunks in the same per-device ring order, so overlap
# on/off is bitwise-identical by construction (fp accumulation order is
# part of the contract — see test_distributed).


def _ring_schedule(sizes: tuple) -> list[tuple[int | None, tuple]]:
    """Static per-step plan for a multi-axis ring over `sizes`.

    Returns prod(sizes) entries (shift_axis, offsets): `shift_axis` is the
    row-axis position to ppermute by +1 to ARRIVE at this step (None for
    step 0), `offsets[j]` the accumulated shift count of axis j — a device
    at coords (i_j) then holds the chunk of row group prod-index over
    ((i_j - offsets[j]) mod sizes[j]). Nested-odometer order: one single-hop
    shift per step visits all d_row sources."""
    m = len(sizes)
    total = int(np.prod(sizes)) if sizes else 1
    inner = [int(np.prod(sizes[j + 1:])) for j in range(m)]  # cycle lengths
    counts = [0] * m
    sched: list[tuple[int | None, tuple]] = []
    for k in range(total):
        if k == 0:
            ax = None
        else:
            ax = m - 1
            for j in range(m):
                if k % inner[j] == 0:
                    ax = j
                    break
            counts[ax] += 1
        sched.append((ax, tuple(counts)))
    return sched


def _ring_src_index(geom: DistGeometry, offsets: tuple) -> jax.Array:
    """Linear row-group index of the chunk this device holds at the ring
    step with the given per-axis shift counts."""
    idx = jnp.zeros((), jnp.int32)
    for a, s, off in zip(geom.row_axes, geom.row_sizes, offsets):
        idx = idx * s + (jax.lax.axis_index(a) - off) % s
    return idx


def _chunked_contraction(geom: DistGeometry, chunk_fn: Callable,
                         V_local: jax.Array, *, overlap: bool) -> jax.Array:
    """sum_s chunk_fn(c_s, V[chunk c_s]) -> (rows_local, t) partial.

    chunk_fn(c, v): the local tile's contribution from GLOBAL vector chunk
    c (an int32 scalar; chunk c covers rows [c*n_local, (c+1)*n_local)).
    The d_row sources are walked in ring order from this device's own chunk;
    serial (overlap=False) slices an up-front all_gather in that same order.
    """
    if not geom.row_sizes:
        raise ValueError(
            "chunked contraction needs DistGeometry.row_sizes (build the "
            "geometry with make_geometry, not the raw constructor)")
    sched = _ring_schedule(geom.row_sizes)
    if geom.col_axes:
        j_col = _linear_index(geom.col_axes, _axis_sizes(geom.col_axes))
    else:
        j_col = jnp.zeros((), jnp.int32)

    partial = None
    if overlap:
        v = V_local
        for k, (_, offsets) in enumerate(sched):
            v_next = None
            if k + 1 < len(sched):
                ax = sched[k + 1][0]
                name, size = geom.row_axes[ax], geom.row_sizes[ax]
                perm = [(r, (r + 1) % size) for r in range(size)]
                # issue the transfer for step k+1 BEFORE step k's compute
                v_next = jax.lax.ppermute(v, name, perm)
            src = _ring_src_index(geom, offsets)
            out = chunk_fn(src * geom.d_col + j_col, v)
            partial = out if partial is None else partial + out
            if v_next is not None:
                v = v_next
    else:
        v_all = jax.lax.all_gather(V_local, geom.row_axes, axis=0, tiled=True)
        for _, offsets in sched:
            src = _ring_src_index(geom, offsets)
            v = jax.lax.dynamic_slice_in_dim(
                v_all, src * geom.n_local, geom.n_local, 0)
            out = chunk_fn(src * geom.d_col + j_col, v)
            partial = out if partial is None else partial + out
    return partial


def dist_kmvm(geom: DistGeometry, kernel, X: jax.Array, V_local: jax.Array,
              params, *, add_noise: bool = True,
              noise_floor: float = 1e-4,
              block_fn: Callable | None = None,
              overlap: bool | None = None) -> jax.Array:
    """K_hat @ V with V sharded per geom. Local in, local out.

    1-D serial: all_gather(V) -> (n, t); rows B_i x full columns (the
        paper's scheme, byte-for-byte the seed path).
    2-D / overlap: chunked contraction over source chunks (see
        `_chunked_contraction`); 2-D closes with a psum_scatter of the
        row partials over the col axes.
    Padded geometries mask V in and the kernel part out, then add the
    noise diagonal unmasked — K_hat_pad stays SPD and block-diagonal.
    """
    squeeze = V_local.ndim == 1
    if squeeze:
        V_local = V_local[:, None]
    overlap = geom.overlap if overlap is None else overlap

    mask = _chunk_mask(geom, V_local.dtype)
    Vk = V_local if mask is None else V_local * mask[:, None]
    x_rows = _x_rows(geom, X)
    if geom.col_axes or overlap:
        def chunk_fn(c, v):
            x_c = jax.lax.dynamic_slice_in_dim(
                X, c * geom.n_local, geom.n_local, 0)
            return kmvm_rect(kernel, x_rows, x_c, v, params,
                             row_block=geom.row_block, block_fn=block_fn)

        partial_rows = _chunked_contraction(geom, chunk_fn, Vk,
                                            overlap=overlap)
    else:
        v_cols = jax.lax.all_gather(Vk, geom.row_axes, axis=0, tiled=True)
        partial_rows = kmvm_rect(kernel, x_rows, _x_cols(geom, X), v_cols,
                                 params, row_block=geom.row_block,
                                 block_fn=block_fn)
    if geom.col_axes:
        out = jax.lax.psum_scatter(partial_rows, geom.col_axes,
                                   scatter_dimension=0, tiled=True)
    else:
        out = partial_rows
    if mask is not None:
        out = out * mask[:, None]
    if add_noise:
        out = out + noise_variance(params, noise_floor) * V_local
    return out[:, 0] if squeeze else out


# ---------------------------------------------------------------------------
# distributed rank-k pivoted Cholesky (L sharded congruent with CG vectors)
# ---------------------------------------------------------------------------


class DistPreconditioner(NamedTuple):
    L_local: jax.Array     # (n_local, k) rows of L for this device's chunk
    sigma2: jax.Array      # () replicated
    chol_inner: jax.Array  # (k, k) replicated Cholesky of s2 I + L^T L
    n: int

    def solve(self, geom: DistGeometry, V_local: jax.Array) -> jax.Array:
        LtV = _psum_all(geom, self.L_local.T @ V_local)       # (k, t) replicated
        inner = jax.scipy.linalg.cho_solve((self.chol_inner, True), LtV)
        return (V_local - self.L_local @ inner) / self.sigma2

    def logdet(self) -> jax.Array:
        k = self.L_local.shape[1]
        ld_inner = 2.0 * jnp.sum(jnp.log(jnp.diagonal(self.chol_inner)))
        return (self.n - k) * jnp.log(self.sigma2) + ld_inner

    def sample(self, geom: DistGeometry, key: jax.Array, num: int) -> jax.Array:
        """(n_local, num) probe chunk of z ~ N(0, P) — masked to the true
        rows on padded geometries, which keeps CG in the masked subspace;
        the SLQ quadrature is unaffected because log(P^-1/2 K_hat P^-1/2)
        is identically zero on the pad block."""
        k = self.L_local.shape[1]
        k1, k2 = jax.random.split(key)
        e1 = jax.random.normal(k1, (k, num), self.L_local.dtype)  # same on all devices
        c = _linear_index(geom.all_axes, _axis_sizes(geom.all_axes))
        k2 = jax.random.fold_in(k2, c)
        e2 = jax.random.normal(k2, (geom.n_local, num), self.L_local.dtype)
        out = self.L_local @ e1 + jnp.sqrt(self.sigma2) * e2
        mask = _chunk_mask(geom, out.dtype)
        return out if mask is None else out * mask[:, None]


def dist_pivoted_cholesky(geom: DistGeometry, kernel, X: jax.Array,
                          params, rank: int) -> jax.Array:
    """Rank-k pivoted Cholesky with rows sharded over the mesh.

    The greedy pivot search needs three tiny collectives per step: a pmax of
    the residual diagonal, and psum-broadcasts of the pivot point x_p (d,)
    and the pivot's L row (k,). Total communication O(rank*(d+rank)) —
    negligible next to one CG iteration.
    """
    x_chunk = _x_chunk(geom, X)             # (n_local, d)
    offset = _chunk_offset(geom)
    gidx = offset + jnp.arange(geom.n_local)
    diag0 = kernel_diag(kernel, x_chunk, params)
    mask = _chunk_mask(geom, X.dtype)
    if mask is not None:
        # pad rows: zero residual diagonal (never chosen as pivot while a
        # true row remains) and zero L rows (P stays block-diagonal)
        diag0 = diag0 * mask
    L0 = jnp.zeros((geom.n_local, rank), X.dtype)

    def body(i, carry):
        L, diag = carry
        local_arg = jnp.argmax(diag)
        local_max = diag[local_arg]
        global_max = jax.lax.pmax(local_max, geom.all_axes)
        # deterministic tie-break: lowest global pivot index among maxima
        cand = jnp.where(local_max >= global_max, gidx[local_arg],
                         geom.n_padded)
        pivot_gidx = jax.lax.pmin(cand, geom.all_axes)
        own = gidx[local_arg] == pivot_gidx
        ownf = own.astype(X.dtype)
        xp = _psum_all(geom, ownf * x_chunk[local_arg])          # (d,)
        lp = _psum_all(geom, ownf * L[local_arg])                # (rank,)
        pivot_val = jnp.maximum(global_max, 1e-12)

        row = kernel_matrix(kernel, xp[None], x_chunk, params)[0]  # (n_local,)
        if mask is not None:
            row = row * mask
        row = row - L @ lp
        li = row / jnp.sqrt(pivot_val)
        li = jnp.where(gidx == pivot_gidx, jnp.sqrt(pivot_val), li)
        if mask is not None:
            li = li * mask  # rank > true rows: a pad pivot still stays zero
        L = L.at[:, i].set(li)
        diag = jnp.maximum(diag - li * li, 0.0)
        diag = jnp.where(gidx == pivot_gidx, -jnp.inf, diag)
        return L, diag

    L, _ = jax.lax.fori_loop(0, rank, body, (L0, diag0))
    return L


def make_dist_preconditioner(geom: DistGeometry, kernel, X: jax.Array,
                             params, rank: int,
                             noise_floor: float = 1e-4,
                             jitter: float = 1e-6) -> DistPreconditioner:
    s2 = noise_variance(params, noise_floor)
    if rank <= 0:
        L = jnp.zeros((geom.n_local, 0), X.dtype)
        return DistPreconditioner(L, s2, jnp.zeros((0, 0), X.dtype), geom.n)
    L = dist_pivoted_cholesky(geom, kernel, X, params, rank)
    inner = _psum_all(geom, L.T @ L)
    inner = s2 * jnp.eye(rank, dtype=L.dtype) + inner
    inner = inner + jitter * jnp.eye(rank, dtype=L.dtype)
    chol = jnp.linalg.cholesky(inner)
    return DistPreconditioner(L, s2, chol, geom.n)


# ---------------------------------------------------------------------------
# ShardedOperator — the "sharded" registry backend (valid inside shard_map)
# ---------------------------------------------------------------------------


class _BoundDistPreconditioner(NamedTuple):
    """DistPreconditioner with geom bound in, matching the single-device
    `Preconditioner.solve/logdet/sample` surface the solvers expect."""

    geom: DistGeometry
    pre: DistPreconditioner

    def solve(self, V_local: jax.Array) -> jax.Array:
        return self.pre.solve(self.geom, V_local)

    def logdet(self) -> jax.Array:
        return self.pre.logdet()

    def sample(self, key: jax.Array, num: int, dtype=None) -> jax.Array:
        del dtype  # probes inherit the sharded factor's dtype
        return self.pre.sample(self.geom, key, num)


@register_operator("sharded")
class ShardedOperator(KernelOperator):
    """K_hat over a TPU mesh: rows (and optionally columns) sharded per
    `config.geom` (a DistGeometry), composing any inner slab backend for
    the local tiles (`config.inner_backend`: "partitioned" = dense jnp
    slabs, "pallas" = the fused kernel; both honor `compute_dtype`).

    Only meaningful INSIDE shard_map over geom's mesh: matvec takes and
    returns this device's (n_local, t) chunk, scalar reductions must go
    through `allreduce`, and `quad_form_grads` returns this device's
    PARTIAL gradients (the MLL custom VJP psums them — see
    `make_dist_mll`). shape/`shape[0]` report the GLOBAL n.

    Prediction-time surfaces (cross_matvec / kernel_rows) are single-device
    by design — the paper runs predictions on one device from the gathered
    mean cache (`make_mean_cache_solve`).

    The fused-CG surface (`fused_matvec_dots`) is inherited from the base
    class as the column-batched fallback: the local matvec plus shard-local
    partial dots, which PCG allreduces exactly like its unfused reductions
    — so the sharded backend keeps the same solver surface without
    claiming `supports_fused_step` (the cross-shard launch cannot fuse).
    """

    def __init__(self, config: OperatorConfig, X: jax.Array, params):
        super().__init__(config, X, params)
        if config.geom is None:
            raise ValueError("backend='sharded' requires OperatorConfig.geom")
        self.geom: DistGeometry = config.geom
        if config.inner_backend == "blocksparse":
            # the mask-aware composition replaces the per-slab path: each
            # row shard owns a contiguous range of the plan's row tiles
            # (pre-sorted data, 1-D layout — validated here, at trace time)
            from repro.sparse import validate_dist_plan

            if config.plan is None:
                raise ValueError(
                    "inner_backend='blocksparse' requires a pre-built "
                    "OperatorConfig.plan (assume_sorted=True)")
            validate_dist_plan(self.geom, config.plan)

    @property
    def shape(self) -> tuple[int, int]:
        return (self.geom.n, self.geom.n)

    @property
    def local_mask(self) -> jax.Array | None:
        """(n_local,) true-row mask of this device's vector chunk (None
        when the geometry is unpadded) — the `mll` forward multiplies it
        into the centered targets so every solve stays in the true-row
        subspace of the padded layout."""
        return _chunk_mask(self.geom, self.dtype)

    @classmethod
    def slab_block_fn(cls, config: OperatorConfig, operand_dtype):
        raise ValueError("'sharded' cannot be an inner slab backend")

    def _inner_block_fn(self) -> Callable | None:
        # registry-resolved: a new slab backend registers once and is
        # immediately composable here; unknown names raise
        return slab_block_fn_for(
            self.config.inner_backend, self.config, self.dtype)

    def matvec(self, V_local: jax.Array) -> jax.Array:
        if self.config.inner_backend == "blocksparse":
            from repro.sparse import dist_blocksparse_kmvm
            from .operators import _compute_dtype_of

            return dist_blocksparse_kmvm(
                self.geom, self.config.kernel, self.X, V_local, self.params,
                self.config.plan,
                add_noise=self.config.add_noise,
                noise_floor=self.config.noise_floor,
                compute_dtype=_compute_dtype_of(self.config, self.dtype))
        return dist_kmvm(
            self.geom, self.config.kernel, self.X, V_local, self.params,
            add_noise=self.config.add_noise,
            noise_floor=self.config.noise_floor,
            block_fn=self._inner_block_fn())

    def allreduce(self, x: jax.Array) -> jax.Array:
        return _psum_all(self.geom, x)

    def preconditioner(self, rank: int,
                       reuse=None) -> _BoundDistPreconditioner:
        """Sharded analogue of the base-class hook: `reuse` accepts either
        the bound preconditioner a previous call returned or the raw
        DistPreconditioner a DistSolveState carries, and returns it bound
        (same amortization semantics as `pivchol.make_preconditioner`)."""
        if reuse is not None:
            pre = reuse.pre if isinstance(reuse, _BoundDistPreconditioner) \
                else reuse
            if pre.L_local.shape[1] != max(rank, 0):
                raise ValueError(
                    f"cannot reuse a rank-{pre.L_local.shape[1]} "
                    f"preconditioner for rank={rank}")
            return _BoundDistPreconditioner(self.geom, pre)
        return _BoundDistPreconditioner(
            self.geom,
            make_dist_preconditioner(
                self.geom, self.config.kernel, self.X, self.params, rank,
                self.config.noise_floor))

    def cross_matvec(self, Z, V):
        raise NotImplementedError(
            "ShardedOperator is solve-only; gather the mean cache "
            "(make_mean_cache_solve) and predict with a single-device "
            "operator")

    def kernel_rows(self, Z):
        raise NotImplementedError(
            "ShardedOperator is solve-only; see cross_matvec")

    def quad_form_grads(self, A_loc: jax.Array, V_loc: jax.Array):
        """This device's PARTIAL (g_params, g_X) of sum_j a_j^T K_hat v_j.

        Identity: with o = psum_scatter(partial_rows), sum_dev <A_loc, o_loc>
        = sum_dev <A_rows, partial_rows> where A_rows = all_gather(A_loc)
        over the COLUMN axes — so each device owns the disjoint tile term
        <A[B_i], K(B_i, C_j) V[C_j]> and its gradient, evaluated blockwise
        with bounded memory by `quad_form_partials`. The caller psums the
        results. (AD through the forward would over-count by the device
        count: under shard_map(check_vma=False) the transpose of a trailing
        psum is psum again.)
        """
        geom = self.geom
        X = self.X
        params = self.params
        if A_loc.ndim == 1:
            A_loc = A_loc[:, None]
        if V_loc.ndim == 1:
            V_loc = V_loc[:, None]
        v_cols = jax.lax.all_gather(V_loc, geom.row_axes, axis=0, tiled=True)
        if geom.col_axes:
            a_rows = jax.lax.all_gather(A_loc, geom.col_axes, axis=0,
                                        tiled=True)
        else:
            a_rows = A_loc
        x_rows = _x_rows(geom, X)
        x_cols = _x_cols(geom, X)
        gp, g_rows, g_cols = quad_form_partials(
            self.config.kernel, x_rows, x_cols, a_rows, v_cols, params,
            row_block=max(geom.row_block // 2, 64))

        # noise diagonal (vector-chunk layout): sigma^2 * sum(A_loc o V_loc)
        dot_ab = jnp.sum(A_loc * V_loc)
        gp_noise = jax.grad(
            lambda p: noise_variance(p, self.config.noise_floor) * dot_ab)(
                params)
        gp = jax.tree.map(jnp.add, gp, gp_noise)

        # scatter row/col gradients back into the replicated-X layout
        g_X = jnp.zeros_like(X)
        if geom.row_axes:
            i = _linear_index(geom.row_axes, _axis_sizes(geom.row_axes))
            g_X = jax.lax.dynamic_update_slice_in_dim(
                g_X, g_rows, i * geom.rows_local, axis=0)
        else:
            g_X = g_X + g_rows
        if geom.col_axes:
            j = _linear_index(geom.col_axes, _axis_sizes(geom.col_axes))
            gc = jnp.zeros((geom.d_row, geom.d_col * geom.n_local, geom.d),
                           X.dtype)
            zero = jnp.zeros((), j.dtype)
            gc = jax.lax.dynamic_update_slice(
                gc, g_cols.reshape(geom.d_row, geom.n_local, geom.d),
                (zero, j * geom.n_local, zero))
            g_X = g_X + gc.reshape(geom.n_padded, geom.d)
        else:
            g_X = g_X + g_cols
        return gp, g_X


# ---------------------------------------------------------------------------
# distributed MLL with custom VJP (paper Eq. 1 & 2, sharded)
# ---------------------------------------------------------------------------


class DistMLLConfig(NamedTuple):
    # legacy kind string (GPParams) or a KernelSpec/expression
    # (KernelParams); hashable either way, so shard_map closures stay static
    kernel: str = "matern32"
    precond_rank: int = 100
    num_probes: int = 8
    max_cg_iters: int = 20
    min_cg_iters: int = 3
    cg_tol: float = 1.0
    noise_floor: float = 1e-4
    pcg_method: str = "standard"
    backend: str = "partitioned"          # inner slab backend per tile
    compute_dtype: str | None = None      # "bfloat16" = MXU fast path
    plan: object | None = None            # SparsePlan (backend="blocksparse":
                                          # pre-sorted data, 1-D mode only)

    def operator_config(self, geom: DistGeometry) -> OperatorConfig:
        return OperatorConfig(
            kernel=self.kernel,
            backend="sharded",
            row_block=geom.row_block,
            add_noise=True,
            noise_floor=self.noise_floor,
            compute_dtype=self.compute_dtype,
            geom=geom,
            inner_backend=self.backend,
            plan=self.plan,
        )


def _aux_tuple(aux) -> tuple:
    """MLLAux as the (logdet, quad, cg_iterations, rel_residual,
    traversals) tuple the sharded steps return, all replicated."""
    return (aux.logdet, aux.quad, aux.cg_iterations, aux.rel_residual,
            aux.traversals)


AUX_SPECS = (P(),) * 5


def _dist_mll_forward(geom, cfg, X, y_loc, params, key):
    op = ShardedOperator(cfg.operator_config(geom), X, params)
    (value, aux), (yc, u_y, U, pinv_z), _state = operator_mll_forward(
        op, y_loc, key,
        precond_rank=cfg.precond_rank, num_probes=cfg.num_probes,
        max_cg_iters=cfg.max_cg_iters, min_cg_iters=cfg.min_cg_iters,
        cg_tol=cfg.cg_tol, pcg_method=cfg.pcg_method)
    # plain tuple: shard_map out_specs are written as tuples, not MLLAux
    aux = _aux_tuple(aux)
    saved = (X, params, yc, u_y, U, pinv_z)
    return (value, aux), saved


def dist_mll_backward(geom, cfg, X, params, u_y, U, pinv_z, g_value):
    """This device's slice of (g_X, g_y, g_params) of g_value * mll.

    The sharded analogue of `mll.operator_mll_backward`, factored out so the
    custom VJP (`make_dist_mll`) and the warm-start engine's explicit
    gradient path (`make_warm_mll_step`) assemble paper Eq. 2 identically.
    g_params / g_X come back replicated (psum'd); g_y stays a local chunk.
    """
    # backward always contracts in full precision (see mll module doc);
    # ShardedOperator.quad_form_grads returns PER-DEVICE partials
    # (explicit blockwise tiles, NOT AD through the distributed
    # forward), so the shared Eq. 2 assembly yields partials too
    bwd_cfg = cfg.operator_config(geom)._replace(compute_dtype=None)
    with named_scope("eq2_backward"):
        g_params, g_X = operator_mll_quad_grads(
            lambda x: ShardedOperator(bwd_cfg, x, params), X, u_y, U, pinv_z)
        # local partials -> global sums (replicated outputs)
        g_params = jax.tree.map(lambda a: _psum_all(geom, a), g_params)
        g_X = _psum_all(geom, g_X)
        g_params = g_params._replace(
            raw_mean=g_params.raw_mean + _psum_all(geom, jnp.sum(u_y)))
    g_params = jax.tree.map(lambda a: g_value * a, g_params)
    g_X = g_value * g_X
    g_y = g_value * (-u_y)
    return g_X, g_y, g_params


def make_dist_mll(geom: DistGeometry, cfg: DistMLLConfig):
    """Returns mll(X, y_loc, params, key) usable inside shard_map, with the
    BBMM custom VJP re-derived for sharded operands (param/X grads psum'd)."""

    @partial(jax.custom_vjp, nondiff_argnums=())
    def mll(X, y_loc, params, key):
        out, _ = _dist_mll_forward(geom, cfg, X, y_loc, params, key)
        return out

    def fwd(X, y_loc, params, key):
        out, saved = _dist_mll_forward(geom, cfg, X, y_loc, params, key)
        return out, saved

    def bwd(saved, cotangents):
        g_value = cotangents[0]
        X, params, yc, u_y, U, pinv_z = saved
        g_X, g_y, g_params = dist_mll_backward(
            geom, cfg, X, params, u_y, U, pinv_z, g_value)
        g_key = np.zeros((2,), jax.dtypes.float0)
        return (g_X, g_y, g_params, g_key)

    mll.defvjp(fwd, bwd)
    return mll


# ---------------------------------------------------------------------------
# public jit'd entry points (shard_map wrapped)
# ---------------------------------------------------------------------------


def _specs(mesh: Mesh, geom: DistGeometry):
    vec = geom.vector_pspec()
    rep = P()
    return mesh, vec, rep


def make_mll_value_and_grad(mesh: Mesh, geom: DistGeometry, cfg: DistMLLConfig):
    """jit'd (X, y, params, key) -> ((value, aux), grads) on the mesh.

    X replicated; y sharded P(all axes); params replicated; grads replicated.
    """
    mll = make_dist_mll(geom, cfg)
    vec = geom.vector_pspec()

    def local_fn(X, y_loc, params, key):
        def loss(p):
            (value, aux) = mll(X, y_loc, p, key)
            return -value / geom.n, aux
        (val, aux), g = jax.value_and_grad(loss, has_aux=True)(params)
        return val, aux, g

    sharded = shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(), vec, P(), P()),
        out_specs=(P(), AUX_SPECS, P()),
        check_vma=False)
    return jax.jit(sharded)


class DistSolveState(NamedTuple):
    """Sharded warm-start state threaded across optimizer steps.

    solutions (n, 1+t) and probes (n, t) are sharded like every CG vector
    (P(all axes)); precond is the UNBOUND DistPreconditioner — L_local
    sharded congruent with the vectors, chol_inner/sigma2 replicated — so
    the state is a plain pytree of arrays (no DistGeometry inside; the step
    fns rebind geom from their closure). logdet is the SLQ estimate from
    the last refresh, carried through warm steps (see
    `mll.operator_mll_forward` on why warm iterates cannot re-estimate it).
    """

    solutions: jax.Array
    probes: jax.Array
    precond: DistPreconditioner
    logdet: jax.Array


class WarmMLLStepFns(NamedTuple):
    """jit'd step functions returned by `make_warm_mll_step`; all return
    (loss, aux, grads, state) with aux = (logdet, quad, cg_iterations,
    rel_residual, traversals) replicated."""

    cold: Callable     # (X, y, params, key)            fresh precond+probes
    refresh: Callable  # (X, y, params, key, state)     fresh precond+probes,
                       #   y-column warm-started from the previous solve
    warm: Callable     # (X, y, params, key, state)     reuse everything


def make_warm_mll_step(mesh: Mesh, geom: DistGeometry, cfg: DistMLLConfig,
                       *, warm_min_iters: int = 1) -> WarmMLLStepFns:
    """The distributed stateful training engine: explicit-gradient MLL steps
    that carry a DistSolveState across optimizer steps.

    Unlike `make_mll_value_and_grad` (stateless custom VJP), these compute
    paper Eq. 2 directly from the forward's saved solves via
    `dist_mll_backward` — same math, same psums — and additionally return
    the warm-start state. The refresh schedule (when to call which fn)
    lives host-side in `repro.train.solver_state`; these stay pure.

    warm_min_iters: min CG iterations on WARM steps. The cold/refresh paths
    keep cfg.min_cg_iters (the floor that makes a zero start do any work at
    the paper's eps=1 tolerance, where ||r0||/||b|| = 1 is never above
    tol); a warm start begins from a meaningful x0, so one iteration
    suffices as its floor.
    """
    vec = geom.vector_pspec()
    rep = P()
    state_specs = DistSolveState(
        solutions=vec, probes=vec,
        precond=DistPreconditioner(L_local=vec, sigma2=rep,
                                   chol_inner=rep, n=rep),
        logdet=rep)
    g_value = -1.0 / geom.n

    def _run(X, y_loc, params, key, *, precond, probes, x0, logdet_carry,
             min_iters):
        op = ShardedOperator(cfg.operator_config(geom), X, params)
        if precond is None:
            with named_scope("precond_build"):
                precond = op.preconditioner(cfg.precond_rank)
        (value, aux), (yc, u_y, U, pinv_z), st = operator_mll_forward(
            op, y_loc, key,
            precond_rank=cfg.precond_rank, num_probes=cfg.num_probes,
            max_cg_iters=cfg.max_cg_iters, min_cg_iters=min_iters,
            cg_tol=cfg.cg_tol, pcg_method=cfg.pcg_method,
            precond=precond, probes=probes, x0=x0,
            logdet_carry=logdet_carry)
        _, _, g_params = dist_mll_backward(
            geom, cfg, X, params, u_y, U, pinv_z, g_value)
        state = DistSolveState(solutions=st.solutions, probes=st.probes,
                               precond=precond.pre, logdet=aux.logdet)
        return -value / geom.n, _aux_tuple(aux), g_params, state

    def local_cold(X, y_loc, params, key):
        return _run(X, y_loc, params, key, precond=None, probes=None,
                    x0=None, logdet_carry=None, min_iters=cfg.min_cg_iters)

    def local_refresh(X, y_loc, params, key, state):
        # fresh precond + probes (so SLQ is re-estimated), but the y column
        # still warm-starts from the previous solve
        x0 = jnp.concatenate(
            [state.solutions[:, :1],
             jnp.zeros((state.solutions.shape[0], cfg.num_probes),
                       state.solutions.dtype)], axis=1)
        return _run(X, y_loc, params, key, precond=None, probes=None,
                    x0=x0, logdet_carry=None, min_iters=cfg.min_cg_iters)

    def local_warm(X, y_loc, params, key, state):
        pre = _BoundDistPreconditioner(geom, state.precond)
        return _run(X, y_loc, params, key, precond=pre, probes=state.probes,
                    x0=state.solutions, logdet_carry=state.logdet,
                    min_iters=warm_min_iters)

    out_specs = (rep, AUX_SPECS, rep, state_specs)
    cold = jax.jit(shard_map(
        local_cold, mesh=mesh, in_specs=(P(), vec, P(), P()),
        out_specs=out_specs, check_vma=False))
    refresh = jax.jit(shard_map(
        local_refresh, mesh=mesh,
        in_specs=(P(), vec, P(), P(), state_specs),
        out_specs=out_specs, check_vma=False))
    warm = jax.jit(shard_map(
        local_warm, mesh=mesh,
        in_specs=(P(), vec, P(), P(), state_specs),
        out_specs=out_specs, check_vma=False))
    return WarmMLLStepFns(cold=cold, refresh=refresh, warm=warm)


def make_mean_cache_solve(mesh: Mesh, geom: DistGeometry, cfg: DistMLLConfig,
                          *, tol: float = 0.01, max_iters: int = 400):
    """jit'd tight-tolerance solve a = K_hat^{-1} (y - mu); returns the full
    (n,) cache replicated (prediction then runs on one device, per paper)."""
    vec = geom.vector_pspec()

    def local_fn(X, y_loc, params):
        yc = y_loc - constant_mean(params)
        op = ShardedOperator(cfg.operator_config(geom), X, params)
        if op.local_mask is not None:
            yc = yc * op.local_mask
        precond = op.preconditioner(cfg.precond_rank)
        res = pcg(op, yc[:, None], precond.solve,
                  max_iters=max_iters, min_iters=10, tol=tol)
        a_loc = res.solution[:, 0]
        a_full = jax.lax.all_gather(a_loc, geom.all_axes, axis=0, tiled=True)
        return a_full[:geom.n], res.rel_residual

    sharded = shard_map(local_fn, mesh=mesh,
                        in_specs=(P(), vec, P()),
                        out_specs=(P(), P()),
                        check_vma=False)
    return jax.jit(sharded)


def shard_vector(mesh: Mesh, geom: DistGeometry, y: jax.Array) -> jax.Array:
    if y.shape[0] == geom.n:
        y = pad_to_geometry(geom, y)
    return jax.device_put(y, NamedSharding(mesh, geom.vector_pspec()))


def replicate(mesh: Mesh, x) -> jax.Array:
    return jax.device_put(x, NamedSharding(mesh, P()))


def collective_bench_fns(mesh: Mesh, geom: DistGeometry) -> dict:
    """Jitted micro-bench bodies for the mesh's two collective primitives
    (the measurement half of `obs.costmodel.dist_collective_cost`).

    Returns name -> jitted fn(V) -> V', where V is a CG-vector-sharded
    (n_padded, t) array:

      * "ppermute_ring" — ONE +1 hop along the first multi-device row
        axis: the unit transfer of `_chunked_contraction`'s overlap
        pipeline (per-device volume = one chunk = n_local * t * itemsize).
      * "psum_scatter"  — the 2-D scheme's closing reduce-scatter over the
        col axes, fed a tiled stand-in for the row partials (same shape,
        same collective volume as `dist_kmvm`'s).

    Axes with a single device contribute no transfer and are omitted; on a
    1-device mesh the dict is empty (`obs.measure.collective_microbench`
    degrades to an empty report).
    """
    vec = geom.vector_pspec()
    fns: dict[str, Callable] = {}
    ring_axes = [(i, s) for i, s in enumerate(geom.row_sizes) if s > 1]
    if ring_axes:
        ax, size = ring_axes[0]
        name = geom.row_axes[ax]
        perm = [(r, (r + 1) % size) for r in range(size)]

        def ring_hop(v_loc):
            return jax.lax.ppermute(v_loc, name, perm)

        fns["ppermute_ring"] = jax.jit(shard_map(
            ring_hop, mesh=mesh, in_specs=(vec,), out_specs=vec,
            check_vma=False))
    if geom.col_axes and geom.d_col > 1:
        def scatter(v_loc):
            parts = jnp.tile(v_loc, (geom.d_col, 1))
            return jax.lax.psum_scatter(parts, geom.col_axes,
                                        scatter_dimension=0, tiled=True)

        fns["psum_scatter"] = jax.jit(shard_map(
            scatter, mesh=mesh, in_specs=(vec,), out_specs=vec,
            check_vma=False))
    return fns
