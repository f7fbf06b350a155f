"""`blocksparse` — the distance-pruned KernelOperator backend.

Registered in the `repro.core.operators` registry (lazily, like
"sharded"): every MVM consumer — PCG, SLQ, the MLL forward, the
prediction caches, the serving engine — picks it up with zero changes,
because the paper's contract (touch K_hat only through matvec) is exactly
what makes sparsity composable. The operator executes a
`repro.sparse.plan.SparsePlan`:

  * `matvec` permutes V into the plan's Morton order, runs only the
    active tile pairs, and permutes back — externally identical to the
    dense backends (same X/V/output order), internally fill * n^2 work.
  * On TPU (or with `OperatorConfig.interpret=True`, the test hook) the
    active pairs run on the Pallas gathered grid
    (`repro.sparse.kmvm_sparse`): one fused distance->kernel-sum->MVM
    launch whose grid IS the pair list, fp32-accumulated bf16 tiles under
    `compute_dtype="bfloat16"` like the dense fast path. Off-TPU, or for
    specs the fused pass cannot express (ARD / linear factors), the
    masked-partitioned path scans the same pair list in plain jnp
    (reusing the mixed-precision block evaluator), so both paths do work
    exactly proportional to the pair count.
  * `quad_form_grads` (the Eq. 2 backward surface) walks the same
    row-grouped structure with a scan — one gathered slab + its VJP
    residuals live at a time — so single-device training gradients scale
    with fill too (the mll backward routes here via `grad_backend`; the
    SHARDED composition's backward still runs the dense per-tile
    partials — see `dist_blocksparse_kmvm`). Pruned tiles contribute
    EXACTLY zero gradient: the Wendland taper is identically zero (with
    zero slope) beyond its support, so dropping them is exact for values
    and gradients alike.
  * `cross_matvec` prunes at predict time with a RUNTIME test: the query
    chunk's bounding box is computed on device and tiles beyond the
    current (traced) support radius are skipped via `lax.cond` — no
    static plan needed on the query side, and it stays exact for any
    radius the optimizer reached.

Plans are static. When `OperatorConfig.plan` is None the operator builds
one on construction (concrete X only — under jit you must thread a
pre-built plan through the config). The mask stays valid while
hyperparameter drift remains inside the plan's margin; the training loops
(`repro.train.gp_trainer`, `repro.launch.train`) replan via
`repro.sparse.plan.needs_replan` — the same drift machinery that
schedules preconditioner refreshes.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from repro.core.kernels_math import kernel_matrix, noise_variance
from repro.core.operators import (
    KernelOperator,
    OperatorConfig,
    _compute_dtype_of,
    mixed_block_fn,
    register_operator,
)
from repro.core.partitioned import lax_map

from .plan import SparsePlan, build_plan, spec_support_radius


def _pad_rows_to(A: jax.Array, n_pad: int) -> jax.Array:
    if A.shape[0] == n_pad:
        return A
    widths = [(0, n_pad - A.shape[0])] + [(0, 0)] * (A.ndim - 1)
    return jnp.pad(A, widths)


def _inner_block_fn(kernel, compute_dtype) -> Callable:
    """Per-slab K(Xb, Xc) @ Vc — the mixed evaluator when a compute dtype
    is set, the exact dense slab otherwise (matches partitioned kmvm)."""
    if compute_dtype is not None:
        return mixed_block_fn(kernel, compute_dtype)

    def exact(Xb, Xc, Vc, params):
        return kernel_matrix(kernel, Xb, Xc, params) @ Vc

    return exact


def masked_kmvm(kernel, Xs: jax.Array, Vs: jax.Array, params,
                plan: SparsePlan, *, compute_dtype=None) -> jax.Array:
    """K_sorted @ V_sorted over active tiles only — the off-TPU path.

    A scan over the plan's ACTIVE-PAIR LIST (the same list the Pallas
    gathered grid consumes): each step evaluates one (tile, tile) kernel
    block and accumulates its MVM contribution into the output row tile.
    Work is exactly pair-count-proportional — a row-gathered layout would
    instead pay the MAX row degree for every row, which on skewed masks
    (a few dense rows, many sparse ones) eats most of the pruning win.
    Memory: the (T, tile, t) accumulator carry plus one (tile, tile)
    block — O(n t), never fill * n^2.
    """
    T, tile = plan.num_tiles, plan.tile
    d = Xs.shape[1]
    t = Vs.shape[1]
    Xt = Xs.reshape(T, tile, d)
    Vt = Vs.reshape(T, tile, t)
    inner = _inner_block_fn(kernel, compute_dtype)

    def body(acc, pair):
        i, j = pair
        # tie the block to the RHS (opaque zero, bitwise identity) so XLA
        # LICM cannot hoist every pair's X-only kernel block out of the CG
        # loop — same hazard and same fix as partitioned.kmvm_rect
        zero = jax.lax.optimization_barrier(jnp.zeros((), Xt.dtype))
        Xi = Xt[i] + zero * Vs[0, 0].astype(Xt.dtype)
        contrib = inner(Xi, Xt[j], Vt[j], params).astype(Vs.dtype)
        return acc.at[i].add(contrib), None

    acc0 = jnp.zeros((T, tile, t), Vs.dtype)
    out, _ = jax.lax.scan(
        body, acc0,
        (jnp.asarray(plan.pair_rows), jnp.asarray(plan.pair_cols)))
    return out.reshape(T * tile, t)


def _fused_pass_or_none(kernel, params):
    """The single fused Pallas pass covering the WHOLE spec, or None when
    the spec needs anything else (ARD metrics, linear terms, fallbacks) —
    in which case the masked-partitioned path handles it. Now the shared
    gate in `repro.kernels.ops` (the fused-CG megakernel uses the same
    condition); kept as a lazy re-export to avoid the import cycle."""
    from repro.kernels.ops import fused_pass_or_none

    return fused_pass_or_none(kernel, params)


def pallas_sorted_kmvm(ppass, Xs: jax.Array, Vs: jax.Array,
                       plan: SparsePlan, *, interpret: bool,
                       compute_dtype) -> jax.Array:
    """Run the gathered-grid Pallas kernel on pre-sorted padded operands."""
    from .kmvm_sparse import kmvm_blocksparse_pallas

    t = Vs.shape[1]
    cdt = jnp.dtype(compute_dtype if compute_dtype is not None
                    else jnp.float32)
    Xp = (Xs / ppass.lengthscale).astype(cdt)
    Vp = (ppass.base_weight * Vs.astype(jnp.float32)).astype(cdt)
    pad_lane = lambda A, ax: jnp.pad(
        A, [(0, (-A.shape[ax]) % 128) if i == ax else (0, 0)
            for i in range(A.ndim)])
    Xp = pad_lane(Xp, 1)
    Vp = pad_lane(Vp, 1)
    scalars = jnp.stack(
        [jnp.asarray(s).astype(jnp.float32) for s in ppass.scalars])[None, :]
    out = kmvm_blocksparse_pallas(
        ppass.components, Xp, Vp, scalars,
        jnp.asarray(plan.pair_rows), jnp.asarray(plan.pair_cols),
        jnp.asarray(plan.pair_first),
        tile=plan.tile, interpret=interpret, compute_dtype=str(cdt))
    return out[:, :t]


def sparse_quad_form_partials(kernel, Xs: jax.Array, A: jax.Array,
                              V: jax.Array, params, plan: SparsePlan):
    """Gradients of q = sum_j a_j^T K_sorted v_j over ACTIVE tiles only.

    The blocksparse analogue of `partitioned.quad_form_partials`: a scan
    over row tiles (one gathered slab + VJP residuals live at a time,
    serialized by the accumulator carry), with column gradients
    scatter-added back through the gather indices. Dropped tiles carry
    identically-zero kernel values AND derivatives (the Wendland clamp),
    so the result equals the dense quad-form gradients exactly.
    Returns (g_params, g_X_sorted) with g_X_sorted shaped like Xs.
    """
    T, tile = plan.num_tiles, plan.tile
    d = Xs.shape[1]
    t = V.shape[1]
    Xt = Xs.reshape(T, tile, d)
    Vt = V.reshape(T, tile, t)
    At = A.reshape(T, tile, t)
    cols = jnp.asarray(plan.row_cols)
    valid = jnp.asarray(plan.row_valid, Xs.dtype)

    def block_q(p_, Xb, Xc, Ab, Vc):
        K = kernel_matrix(kernel, Xb, Xc, p_)
        return jnp.sum(Ab * (K @ Vc))

    gp0 = jax.tree.map(jnp.zeros_like, params)
    gXt0 = jnp.zeros_like(Xt)

    def body(carry, inputs):
        gp_acc, gX_acc = carry
        r, Xb, Ab, cr, vr = inputs
        # serialize the blocks on the accumulated carry (opaque zero): the
        # expensive slab+residual work must not be scheduled concurrently
        link = jax.lax.optimization_barrier(
            jnp.zeros((), Xb.dtype)) * gX_acc[0, 0, 0].astype(Xb.dtype)
        Xb = Xb + link
        Xc = Xt[cr].reshape(cr.shape[0] * tile, d)
        Vc = (Vt[cr] * vr[:, None, None]).reshape(cr.shape[0] * tile, t)
        gp, gxb, gxc = jax.grad(block_q, argnums=(0, 1, 2))(
            params, Xb, Xc, Ab, Vc)
        gp_acc = jax.tree.map(jnp.add, gp_acc, gp)
        gxc = gxc.reshape(cr.shape[0], tile, d) * vr[:, None, None]
        gX_acc = gX_acc.at[cr].add(gxc)
        gX_acc = gX_acc.at[r].add(gxb)
        return (gp_acc, gX_acc), None

    (g_params, gXt), _ = jax.lax.scan(
        body, (gp0, gXt0), (jnp.arange(T), Xt, At, cols, valid))
    return g_params, gXt.reshape(T * tile, d)


@register_operator("blocksparse")
class BlockSparseOperator(KernelOperator):
    """Distance-pruned MVMs for compactly-supported kernel specs.

    Non-compact specs are accepted and plan to the all-active mask — every
    tile pair runs, results stay pinned to the other backends — so the
    backend is safe to select unconditionally and only pays off once a
    Wendland taper enters the spec.
    """

    grad_backend = "blocksparse"   # mll routes Eq. 2 through our own surface

    def __init__(self, config: OperatorConfig, X: jax.Array, params):
        plan = config.plan
        if plan is None:
            tile = max(8, min(config.row_block, 256))
            try:
                plan = build_plan(config.kernel, X, params, tile=tile)
            except ValueError as e:
                raise ValueError(
                    "backend='blocksparse' under jit needs a pre-built "
                    "plan: OperatorConfig(plan=repro.sparse.build_plan(...))"
                ) from e
            # record the auto-built plan on the config so downstream
            # consumers (posterior artifacts) capture the executed plan
            config = config._replace(plan=plan)
        super().__init__(config, X, params)
        if not isinstance(plan, SparsePlan):
            raise TypeError(f"OperatorConfig.plan must be a SparsePlan, "
                            f"got {type(plan)}")
        if plan.n != X.shape[0]:
            raise ValueError(
                f"plan covers n={plan.n} rows but X has {X.shape[0]}")
        self.plan = plan

    @classmethod
    def slab_block_fn(cls, config: OperatorConfig, operand_dtype):
        raise ValueError(
            "'blocksparse' cannot be a per-slab inner backend; the sharded "
            "engine composes it through its own rect path "
            "(inner_backend='blocksparse' with a pre-sorted plan)")

    # -- the pruned MVM -----------------------------------------------------

    def _use_pallas(self) -> bool:
        """The gathered grid runs compiled on a TPU and, off it, only in
        interpret mode when the config forces it (the test hook);
        interpret=False off-TPU selects the masked-partitioned path."""
        if self.config.interpret is None:
            return jax.default_backend() == "tpu"
        return self.config.interpret

    def _sorted_kmvm(self, Xs: jax.Array, Vs: jax.Array) -> jax.Array:
        from repro.kernels.ops import resolve_interpret

        cdt = _compute_dtype_of(self.config, self.dtype)
        if self._use_pallas():
            ppass = _fused_pass_or_none(self.config.kernel, self.params)
            if ppass is not None:
                out = pallas_sorted_kmvm(
                    ppass, Xs, Vs, self.plan,
                    interpret=resolve_interpret(self.config.interpret),
                    compute_dtype=cdt)
                return out.astype(Vs.dtype)
        return masked_kmvm(self.config.kernel, Xs, Vs, self.params,
                           self.plan, compute_dtype=cdt)

    def matvec(self, V: jax.Array) -> jax.Array:
        squeeze = V.ndim == 1
        if squeeze:
            V = V[:, None]
        plan = self.plan
        perm = jnp.asarray(plan.perm)
        inv_perm = jnp.asarray(plan.inv_perm)
        Xs = _pad_rows_to(self.X[perm], plan.n_pad)
        Vs = _pad_rows_to(V[perm], plan.n_pad)
        out = self._sorted_kmvm(Xs, Vs)[:plan.n][inv_perm]
        out = self._add_noise(out, V)
        return out[:, 0] if squeeze else out

    # -- prediction-time pruning --------------------------------------------

    def cross_matvec(self, Z: jax.Array, V: jax.Array) -> jax.Array:
        """K(Z, X) @ V, skipping X tiles beyond the CURRENT support radius
        of the query chunk's bounding box (runtime `lax.cond`: exact, and
        valid for any radius — no static plan on the query side). The skip
        only bites when queries are spatially clustered; the serving
        engine Morton-sorts each batch before chunking for exactly that.
        """
        if not self.plan.compact:
            return super().cross_matvec(Z, V)
        squeeze = V.ndim == 1
        if squeeze:
            V = V[:, None]
        plan = self.plan
        perm = jnp.asarray(plan.perm)
        Xs = _pad_rows_to(self.X[perm], plan.n_pad)
        Vs = _pad_rows_to(V[perm], plan.n_pad)
        T, tile = plan.num_tiles, plan.tile
        Xt = Xs.reshape(T, tile, Xs.shape[1])
        Vt = Vs.reshape(T, tile, V.shape[1])

        support = spec_support_radius(self.config.kernel, self.params)
        zlo = jnp.min(Z, axis=0)
        zhi = jnp.max(Z, axis=0)
        lo = jnp.asarray(plan.box_lo, Z.dtype)
        hi = jnp.asarray(plan.box_hi, Z.dtype)
        gap = jnp.maximum(lo - zhi[None, :], 0.0)
        gap = jnp.maximum(gap, jnp.maximum(zlo[None, :] - hi, 0.0))
        active = jnp.sum(gap * gap, axis=1) < (support * support)  # (T,)

        cdt = _compute_dtype_of(self.config, self.dtype)
        inner = _inner_block_fn(self.config.kernel, cdt)

        def body(acc, inputs):
            Xc, Vc, act = inputs
            contrib = jax.lax.cond(
                act,
                lambda: inner(Z, Xc, Vc, self.params).astype(acc.dtype),
                lambda: jnp.zeros_like(acc))
            return acc + contrib, None

        acc0 = jnp.zeros((Z.shape[0], V.shape[1]), V.dtype)
        out, _ = jax.lax.scan(body, acc0, (Xt, Vt, active))
        return out[:, 0] if squeeze else out

    # -- Eq. 2 backward surface ---------------------------------------------

    def quad_form_grads(self, A: jax.Array, V: jax.Array):
        if A.ndim == 1:
            A = A[:, None]
        if V.ndim == 1:
            V = V[:, None]
        plan = self.plan
        perm = jnp.asarray(plan.perm)
        inv_perm = jnp.asarray(plan.inv_perm)
        Xs = _pad_rows_to(self.X[perm], plan.n_pad)
        As = _pad_rows_to(A[perm], plan.n_pad)
        Vs = _pad_rows_to(V[perm], plan.n_pad)
        gp, gX_sorted = sparse_quad_form_partials(
            self.config.kernel, Xs, As, Vs, self.params, plan)
        g_X = gX_sorted[:plan.n][inv_perm]
        dot_av = jnp.sum(A * V)
        gp_noise = jax.grad(
            lambda p: noise_variance(p, self.config.noise_floor) * dot_av)(
                self.params)
        gp = jax.tree.map(jnp.add, gp, gp_noise)
        return gp, g_X


# ---------------------------------------------------------------------------
# distributed composition: each device owns the mask slice of its tile
# ---------------------------------------------------------------------------


def _dist_legacy_1d(geom, kernel, X, v_full, params, plan, compute_dtype):
    """The paper's 1-D scheme: rows over every axis, one gathered V, the
    local row-tile loop gathered at the GLOBAL kmax (SPMD needs the same
    static structure on every device). Kept verbatim as the serial 1-D
    path — it is the seed behavior the 1-D goldens pin."""
    T, tile = plan.num_tiles, plan.tile
    d = X.shape[1]
    t = v_full.shape[1]
    T_loc = geom.rows_local // tile

    from repro.core.distributed import _axis_sizes, _linear_index

    i = _linear_index(geom.row_axes, _axis_sizes(geom.row_axes))
    cols_all = jnp.asarray(plan.row_cols)
    valid_all = jnp.asarray(plan.row_valid, v_full.dtype)
    cols = jax.lax.dynamic_slice_in_dim(cols_all, i * T_loc, T_loc, 0)
    valid = jax.lax.dynamic_slice_in_dim(valid_all, i * T_loc, T_loc, 0)

    Xt = X.reshape(T, tile, d)
    Vt = v_full.reshape(T, tile, t)
    x_rows = jax.lax.dynamic_slice_in_dim(
        X, i * geom.rows_local, geom.rows_local, 0).reshape(T_loc, tile, d)
    inner = _inner_block_fn(kernel, compute_dtype)

    @jax.checkpoint
    def one_row(args):
        Xb, cr, vr = args
        zero = jax.lax.optimization_barrier(jnp.zeros((), Xb.dtype))
        Xb = Xb + zero * v_full[0, 0].astype(Xb.dtype)
        Xc = Xt[cr].reshape(cr.shape[0] * tile, d)
        Vc = (Vt[cr] * vr[:, None, None]).reshape(cr.shape[0] * tile, t)
        return inner(Xb, Xc, Vc, params).astype(v_full.dtype)

    if T_loc == 1:
        out = one_row((x_rows[0], cols[0], valid[0]))[None]
    else:
        out = lax_map(one_row, (x_rows, cols, valid))
    return out.reshape(geom.rows_local, t)


def dist_blocksparse_kmvm(geom, kernel, X: jax.Array, V_local: jax.Array,
                          params, plan: SparsePlan, *,
                          add_noise: bool = True, noise_floor: float = 1e-4,
                          compute_dtype=None,
                          overlap: bool | None = None) -> jax.Array:
    """Distance-pruned distributed MVM — 1-D or (rows x cols) 2-D mesh.

    Contract (validated by ShardedOperator): X and the CG vectors are
    PRE-SORTED in Morton order (plan built with assume_sorted=True on the
    PADDED X, so perm is the identity) and every per-device vector chunk
    holds whole plan tiles (make_geometry(..., tile_multiple=plan.tile)).

    1-D serial keeps the seed path: one all_gather of V, local row-tile
    loop over the shard's slice of the row-grouped mask. On column axes
    (2-D) or with overlap the MVM runs as the dense engine's chunked
    contraction (`core.distributed._chunked_contraction`): per source
    chunk, each row tile gathers only its ACTIVE in-chunk col tiles from
    the chunk-sliced mask (`plan.chunk_sliced_plan`), so the per-step
    compute is kmax_chunk*tile wide — fill-proportional cost composes
    with the mesh, and overlap=True ring-pipelines the chunk transfers
    against it. Only the FORWARD MVMs are pruned —
    `ShardedOperator.quad_form_grads` keeps the dense blockwise partials
    (correct at any fill; a fill-proportional sharded Eq. 2 backward is
    open follow-up work).
    """
    squeeze = V_local.ndim == 1
    if squeeze:
        V_local = V_local[:, None]
    overlap = geom.overlap if overlap is None else overlap

    from repro.core.distributed import (
        _axis_sizes, _chunk_mask, _chunked_contraction, _linear_index,
    )

    mask = _chunk_mask(geom, V_local.dtype)
    Vk = V_local if mask is None else V_local * mask[:, None]

    if geom.col_axes or overlap:
        from .plan import chunk_sliced_plan

        T, tile = plan.num_tiles, plan.tile
        d = X.shape[1]
        t = Vk.shape[1]
        T_rloc = geom.rows_local // tile
        T_chunk = geom.n_local // tile
        n_chunks = geom.d_row * geom.d_col
        sl = chunk_sliced_plan(plan, n_chunks)

        i = _linear_index(geom.row_axes, _axis_sizes(geom.row_axes))
        cols_all = jnp.asarray(sl.cols)                 # (T, n_chunks, kc)
        valid_all = jnp.asarray(sl.valid, Vk.dtype)
        cols_loc = jax.lax.dynamic_slice_in_dim(cols_all, i * T_rloc,
                                                T_rloc, 0)
        valid_loc = jax.lax.dynamic_slice_in_dim(valid_all, i * T_rloc,
                                                 T_rloc, 0)
        x_rows = jax.lax.dynamic_slice_in_dim(
            X, i * geom.rows_local, geom.rows_local,
            0).reshape(T_rloc, tile, d)
        inner = _inner_block_fn(kernel, compute_dtype)

        def chunk_fn(c, v):
            x_c = jax.lax.dynamic_slice_in_dim(
                X, c * geom.n_local, geom.n_local, 0).reshape(T_chunk, tile, d)
            v_t = v.reshape(T_chunk, tile, t)
            cr_all = jax.lax.dynamic_slice_in_dim(cols_loc, c, 1, 1)[:, 0]
            vr_all = jax.lax.dynamic_slice_in_dim(valid_loc, c, 1, 1)[:, 0]

            @jax.checkpoint
            def one_row(args):
                Xb, cr, vr = args
                zero = jax.lax.optimization_barrier(jnp.zeros((), Xb.dtype))
                Xb = Xb + zero * v_t[0, 0, 0].astype(Xb.dtype)
                Xc = x_c[cr].reshape(cr.shape[0] * tile, d)
                Vc = (v_t[cr] * vr[:, None, None]).reshape(
                    cr.shape[0] * tile, t)
                return inner(Xb, Xc, Vc, params).astype(v.dtype)

            if T_rloc == 1:
                out = one_row((x_rows[0], cr_all[0], vr_all[0]))[None]
            else:
                out = lax_map(one_row, (x_rows, cr_all, vr_all))
            return out.reshape(geom.rows_local, t)

        partial_rows = _chunked_contraction(geom, chunk_fn, Vk,
                                            overlap=overlap)
        if geom.col_axes:
            out = jax.lax.psum_scatter(partial_rows, geom.col_axes,
                                       scatter_dimension=0, tiled=True)
        else:
            out = partial_rows
    else:
        v_full = jax.lax.all_gather(Vk, geom.row_axes, axis=0, tiled=True)
        out = _dist_legacy_1d(geom, kernel, X, v_full, params, plan,
                              compute_dtype)
    if mask is not None:
        out = out * mask[:, None]
    if add_noise:
        out = out + noise_variance(params, noise_floor) * V_local
    return out[:, 0] if squeeze else out


def validate_dist_plan(geom, plan: SparsePlan) -> None:
    """The sharded-composition contract (raise early, at config time)."""
    import numpy as np

    if not np.array_equal(plan.perm, np.arange(plan.n)):
        raise ValueError(
            "distributed blocksparse needs PRE-SORTED data: Morton-sort "
            "X/y first and build the plan with assume_sorted=True")
    if plan.n != geom.n_padded or plan.n_pad != plan.n:
        raise ValueError(
            f"plan covers n={plan.n} rows but the geometry lays out "
            f"{geom.n_padded} (pad X to geom.n_padded with "
            f"distributed.pad_to_geometry, then build the plan on the "
            f"padded data so it holds whole tiles)")
    if geom.n_local % plan.tile:
        raise ValueError(
            f"per-device chunk ({geom.n_local}) must hold whole plan tiles "
            f"({plan.tile}): build the geometry with "
            f"tile_multiple={plan.tile}")
