"""Warm-started training engine: solver state amortized across optimizer steps.

The paper's training loop evaluates the BBMM MLL once per Adam/L-BFGS step,
and hyperparameters move slowly between steps — successive calls solve
nearly identical systems K_hat^{-1}[y_c, z_1..z_t] and refactorize the same
rank-k pivoted-Cholesky preconditioner. This module makes the solver a
long-lived stateful engine instead of a per-step black box (the gp2Scale
lesson, Noack et al.):

  * the previous step's converged solutions seed mBCG (`pcg(..., x0=...)`),
  * the SLQ probe block is drawn ONCE per refresh and reused, so the probe
    solutions stay valid initial guesses,
  * the preconditioner (including its k x k `chol_inner`) is reused until a
    `refresh_every` schedule or a relative hyperparameter-drift threshold
    triggers recomputation (`pivchol.make_preconditioner(reuse=...)`).

Correctness envelope: CG is exact under any fixed SPD preconditioner and any
x0, and the Eq. 2 gradient estimator contracts converged solves — so warm
steps change ITERATION COUNTS, not the estimator. The one quantity warm
iterates cannot re-estimate is the SLQ log-determinant (their Lanczos
tridiag describes Krylov(K, r0), not Krylov(K, z)); warm steps carry the
estimate from the last refresh, so the reported loss VALUE between
refreshes is O(drift)-stale while gradients stay current. See
EXPERIMENTS.md §Warm-start for the measured iteration savings.

Engines are host-loop objects (the refresh decision branches in Python on
concrete hyperparameters): `WarmStartEngine` for the single-device
KernelOperator backends (dense / partitioned / pallas), and
`DistWarmStartEngine` wrapping `distributed.make_warm_mll_step` for the
sharded engine. Both expose `step(X, y, params, key) -> (loss, aux, grads)`
plus a per-step `telemetry` list (CG iterations applied, preconditioner
refreshes, drift, wall time) that `repro.launch.train` surfaces.
"""

from __future__ import annotations

import time
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.obs import health as obs_health
from repro.core.kernels_math import constant_mean
from repro.core.mll import (
    MLLAux,
    MLLConfig,
    operator_mll_backward,
    operator_mll_forward,
)
from repro.core.operators import make_operator
from repro.core.pcg import SolveState, pcg
from repro.core.slq import slq_logdet_correction


class WarmStartConfig(NamedTuple):
    """Host-side refresh schedule for the stateful solve engine.

    enabled:         False = every step is cold (the pre-engine behavior).
    refresh_every:   rebuild the preconditioner + redraw SLQ probes every k
                     steps (k=1 still warm-starts the y column from the
                     previous solve on the fresh system).
    drift_threshold: max relative change of the constrained kernel/noise
                     hyperparameters (see `param_drift`) since the last
                     refresh before a refresh is forced — the
                     stale-preconditioner safety valve.
    warm_min_iters:  min CG iterations on warm steps (cold steps keep the
                     MLLConfig floor, which is what makes a zero start do
                     any work at the paper's eps=1 tolerance).
    """

    enabled: bool = True
    refresh_every: int = 5
    drift_threshold: float = 0.1
    warm_min_iters: int = 1


class SolverState(NamedTuple):
    """Device-side engine state threaded between steps (a plain pytree)."""

    solve: SolveState   # solutions (n, 1+t) + probes (n, t)
    precond: Any        # Preconditioner (reused until refresh)
    logdet: jax.Array   # SLQ logdet at the last refresh (carried when warm)


def _softplus_np(x):
    x = np.asarray(x, np.float64)
    return np.log1p(np.exp(-np.abs(x))) + np.maximum(x, 0.0)


def _constrained_leaves(params) -> list:
    """Host-side CONSTRAINED hyperparameter leaves of a params pytree,
    excluding the mean: softplus of every raw_* leaf that shapes K_hat.

    Works uniformly over GPParams and the kernel algebra's KernelParams —
    any spec tree flattens to its per-node raw leaves (all of which are
    softplus-constrained: lengthscales, outputscales, rq alphas, linear
    scales, noise); raw_mean never enters K_hat and is dropped (otherwise
    a mean moving off its zero init would read as unbounded drift).
    """
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    out = []
    for path, leaf in flat:
        if jax.tree_util.keystr(path).endswith("raw_mean"):
            continue
        out.append(_softplus_np(leaf))
    return out


def param_drift(ref, params) -> float:
    """Max relative change of the CONSTRAINED hyperparameters that the
    preconditioner actually depends on (host-side, concrete params),
    measured over the flattened constrained pytree (`_constrained_leaves`).

    The pivoted-Cholesky factor is a function of every kernel
    hyperparameter and its Woodbury solve of sigma^2; the constant mean is
    excluded.
    """
    drift = 0.0
    for a, b in zip(_constrained_leaves(ref), _constrained_leaves(params)):
        denom = np.maximum(np.abs(a), 1e-8)
        drift = max(drift, float(np.max(np.abs(b - a) / denom)))
    return drift


class _WarmEngineBase:
    """Host-side schedule + telemetry shared by both engines.

    Subclasses provide `_dispatch(mode, X, y, params, key)` returning
    (loss, MLLAux, g_params, new_state); everything else — the refresh
    decision, state/params_ref bookkeeping, per-step telemetry — lives
    here exactly once.
    """

    def __init__(self, warm: WarmStartConfig | None = None,
                 track_residuals: bool | None = None):
        self.warm = warm or WarmStartConfig()
        self.state = None
        self.telemetry: list[dict] = []
        self._params_ref = None
        self._steps_since_refresh = 0
        # Residual-trajectory capture (the health monitor's stagnation /
        # divergence feed) changes the compiled program (an extra scan
        # output), so it is resolved ONCE at construction — None follows
        # the health sink's enablement — and baked statically into the
        # jitted step functions. Off keeps the jaxpr byte-identical.
        if track_residuals is None:
            track_residuals = obs_health.health_enabled()
        self.track_residuals = bool(track_residuals)
        self._last_phase_ms: dict | None = None

    def _dispatch(self, mode, X, y, params, key):
        raise NotImplementedError

    def _dispatch_phased(self, mode, X, y, params, key):
        """Tracing-mode dispatch. Subclasses that can split the step into
        separately-fenced phases (precond / solve / slq / backward)
        override this; the default is the single-jit step, so the
        `mll_step` span still times the whole thing."""
        return self._dispatch(mode, X, y, params, key)

    def _modeled_cost(self, mode, X) -> tuple[int | None, float | None]:
        """(launches, hbm_bytes) from the §Roofline cost model, or
        (None, None) when the engine's config doesn't expose the solver
        geometry (the distributed config differs; stay best-effort)."""
        cfg = getattr(self, "cfg", None)
        try:
            n, d = int(X.shape[0]), int(X.shape[-1])
            plan = getattr(cfg, "plan", None)
            cost = obs.mll_step_cost(
                n, d,
                num_rhs=1 + int(cfg.num_probes),
                max_cg_iters=int(cfg.max_cg_iters),
                backend=getattr(cfg, "backend", "partitioned"),
                row_block=int(getattr(cfg, "row_block", 1024)),
                fill=float(getattr(plan, "fill", 1.0)) if plan is not None
                     else 1.0,
                warm_init=mode != "cold",
            )
            return cost.launches, cost.hbm_bytes
        except (AttributeError, TypeError, ValueError):
            return None, None

    def _trip_traversals(self, mode) -> int:
        """The traversals the CG loop's whole trip count would run: every
        iteration, the warm start's residual and the pipelined loop's
        pre-loop MVM (`PCGResult.traversals` of a solve that never
        exits early)."""
        cfg = self.cfg
        return (int(cfg.max_cg_iters) + (mode != "cold")
                + (cfg.pcg_method == "pipelined"))

    def _mode(self, params) -> tuple[str, float]:
        if self.state is None or not self.warm.enabled:
            return "cold", 0.0
        drift = param_drift(self._params_ref, params)
        if drift > self.warm.drift_threshold:
            obs_health.precond_stale(step=len(self.telemetry), drift=drift,
                                     threshold=self.warm.drift_threshold)
            return "refresh", drift
        if self._steps_since_refresh >= self.warm.refresh_every:
            return "refresh", drift
        return "warm", drift

    def step(self, X, y, params, key):
        """One MLL evaluation: (loss, MLLAux, g_params). Appends telemetry.

        The telemetry record is sourced from the obs metrics registry
        (`obs.record_solver_step`) — same keys as the historical bare
        dicts plus per-RHS iteration counts, the most iterations any
        column applied (`cg_iters_max`), the kernel traversals the solve
        executed (`traversals`), those its early exit left out of the
        whole trip count (`traversals_saved`) and the §Roofline-modeled
        MVM cost.
        Iteration counts arrive via the RETURNED MLLAux (device aux),
        never host callbacks; under tracing the step runs through
        `_dispatch_phased` so the span tree decomposes into phases.

        Spans: `mll_step` (mode, drift, cg_iters, cg_iters_max,
        traversals, traversals_saved) over its children
        `mll_step.dispatch` (mode decision and the call), `mll_step.wait`
        (the device's finish and the aux read) and `mll_step.bookkeeping`
        (health check, state, cost model, registry)."""
        t0 = time.perf_counter()
        with obs.span("mll_step") as sp:
            with obs.span("mll_step.dispatch"):
                mode, drift = self._mode(params)
                sp.set(mode=mode, drift=float(drift))
                self._last_phase_ms = None
                if obs.tracing_enabled():
                    loss, aux, g_params, state = self._dispatch_phased(
                        mode, X, y, params, key)
                else:
                    loss, aux, g_params, state = self._dispatch(
                        mode, X, y, params, key)
            with obs.span("mll_step.wait"):
                jax.block_until_ready(loss)
                # one fetch for the host's reads of aux (None stays None)
                iters, rel_residual, traversals = jax.device_get(
                    (aux.cg_iterations, aux.rel_residual, aux.traversals))
                iters = np.asarray(iters)
                saved = None
                if traversals is not None:
                    traversals = int(traversals)
                    saved = self._trip_traversals(mode) - traversals
            sp.set(cg_iters=int(iters.sum()), cg_iters_max=int(iters.max()),
                   traversals=traversals, traversals_saved=saved)
            with obs.span("mll_step.bookkeeping"):
                # health sentinels run on host-concrete aux, after the fences
                cfg = getattr(self, "cfg", None)
                obs_health.check_solver_step(
                    step=len(self.telemetry), mode=mode,
                    tol=float(getattr(cfg, "cg_tol", 1.0)),
                    max_iters=int(getattr(cfg, "max_cg_iters", 100)),
                    iters_per_rhs=iters,
                    rel_residual=np.asarray(rel_residual),
                    residuals=(None if aux.residuals is None
                               else np.asarray(aux.residuals)),
                    drift=drift)
                if self.warm.enabled:
                    self.state = state
                    if mode != "warm":
                        self._params_ref = params
                        self._steps_since_refresh = 0
                    self._steps_since_refresh += 1
                launches, hbm_bytes = self._modeled_cost(mode, X)
                phase_ms, self._last_phase_ms = self._last_phase_ms, None
                self.telemetry.append(obs.record_solver_step(
                    mode=mode, iters_per_rhs=iters, drift=drift,
                    seconds=time.perf_counter() - t0,
                    launches=launches, hbm_bytes=hbm_bytes,
                    phase_ms=phase_ms, traversals=traversals,
                    traversals_saved=saved))
        return loss, aux, g_params

    def extend_rows(self, m: int) -> None:
        """Absorb m appended training rows into the carried solver state
        (streaming observations between optimizer steps — the training-side
        twin of `predcache.update_prediction_cache`).

        The previous solutions are zero-padded (`SolveState.pad_rows`) so
        the y column still warm-starts the (n+m)-row system, and the
        preconditioner factor is zero-row-extended
        (`pivchol.extend_preconditioner`) so the state stays shape-
        consistent. The padded probe solutions are NOT carried — their SLQ
        tridiagonals describe the old system — so the next step is forced
        to run as a refresh: fresh probes, and a preconditioner whose
        pivots can land on the new rows.
        """
        if m < 0:
            raise ValueError(f"cannot extend solver state by {m} rows")
        if self.state is None or m == 0:
            return
        from repro.core.pivchol import extend_preconditioner

        self.state = self.state._replace(
            solve=self.state.solve.pad_rows(m),
            precond=extend_preconditioner(self.state.precond, m))
        self._steps_since_refresh = self.warm.refresh_every

    def reset(self):
        self.state = None
        self._params_ref = None
        self._steps_since_refresh = 0


class WarmStartEngine(_WarmEngineBase):
    """Stateful MLL value+grad engine for single-device operator backends.

    step() returns (loss, aux, g_params) with loss = -mll/n — the same
    quantity `jax.value_and_grad(gp.loss)` produced before, with gradients
    assembled by the identical Eq. 2 code path (`operator_mll_backward`),
    so a disabled engine reproduces the stateless trainer's numbers.
    """

    def __init__(self, cfg: MLLConfig, warm: WarmStartConfig | None = None,
                 track_residuals: bool | None = None):
        super().__init__(warm, track_residuals)
        self.cfg = cfg
        self._fns = {mode: jax.jit(self._make_step(mode))
                     for mode in ("cold", "refresh", "warm")}
        self._phase_fns: dict[str, dict] = {}  # built lazily (tracing only)

    def _dispatch(self, mode, X, y, params, key):
        if mode == "cold":
            return self._fns["cold"](X, y, params, key)
        return self._fns[mode](X, y, params, key, self.state)

    # -- jitted step bodies -------------------------------------------------

    def _make_step(self, mode: str):
        cfg = self.cfg
        warm_min_iters = self.warm.warm_min_iters
        track = self.track_residuals

        def fn(X, y, params, key, state=None):
            op = make_operator(cfg.operator_config(), X, params)
            n = X.shape[0]
            if mode == "warm":
                precond = op.preconditioner(cfg.precond_rank,
                                            reuse=state.precond)
                probes, x0 = state.solve.probes, state.solve.solutions
                logdet_carry = state.logdet
                min_iters = warm_min_iters
            else:
                precond = op.preconditioner(cfg.precond_rank)
                probes = logdet_carry = None
                min_iters = cfg.min_cg_iters
                if mode == "refresh":
                    # fresh probes invalidate the previous probe solutions,
                    # but the y column still warm-starts
                    x0 = jnp.concatenate(
                        [state.solve.solutions[:, :1],
                         jnp.zeros((n, cfg.num_probes), y.dtype)], axis=1)
                else:
                    x0 = None
            (value, aux), (yc, u_y, U, pinv_z), solve = operator_mll_forward(
                op, y, key,
                precond_rank=cfg.precond_rank, num_probes=cfg.num_probes,
                max_cg_iters=cfg.max_cg_iters, min_cg_iters=min_iters,
                cg_tol=cfg.cg_tol, pcg_method=cfg.pcg_method,
                precond=precond, probes=probes, x0=x0,
                logdet_carry=logdet_carry, track_residuals=track)
            _, _, g_params = operator_mll_backward(
                cfg, X, params, u_y, U, pinv_z, -1.0 / n)
            new_state = SolverState(solve=solve, precond=precond,
                                    logdet=aux.logdet)
            return -value / n, aux, g_params, new_state

        return fn

    # -- phased step (tracing mode only) ------------------------------------
    #
    # The single-jit step above is one opaque device program — a span
    # around it can't say how long the preconditioner build vs the CG
    # iterations vs the Eq. 2 backward took. When tracing is on, the
    # engine dispatches through four separately-jitted phase functions,
    # each fenced with block_until_ready inside its own span, so
    # obs_report's per-phase table decomposes real wall-clock. The phases
    # run the SAME math as `_make_step` (precond build / mBCG / SLQ
    # quadrature / Eq. 2 assembly literally share the code paths); only
    # the jit partitioning differs, which may cost some fusion — that's
    # the price of attribution, paid only when tracing is enabled.

    def _make_phases(self, mode: str) -> dict:
        cfg = self.cfg
        warm_min_iters = self.warm.warm_min_iters
        track = self.track_residuals

        def precond_fn(X, params, precond_prev=None):
            op = make_operator(cfg.operator_config(), X, params)
            if mode == "warm":
                return op.preconditioner(cfg.precond_rank, reuse=precond_prev)
            return op.preconditioner(cfg.precond_rank)

        def solve_fn(X, y, params, key, precond, state=None):
            op = make_operator(cfg.operator_config(), X, params)
            n = X.shape[0]
            yc = y - constant_mean(params)
            if mode == "warm":
                probes, x0 = state.solve.probes, state.solve.solutions
                min_iters = warm_min_iters
            else:
                probes = precond.sample(key, cfg.num_probes, dtype=yc.dtype)
                min_iters = cfg.min_cg_iters
                if mode == "refresh":
                    x0 = jnp.concatenate(
                        [state.solve.solutions[:, :1],
                         jnp.zeros((n, cfg.num_probes), y.dtype)], axis=1)
                else:
                    x0 = None
            B = jnp.concatenate([yc[:, None], probes], axis=1)
            res = pcg(op, B, precond.solve,
                      max_iters=cfg.max_cg_iters, min_iters=min_iters,
                      tol=cfg.cg_tol, method=cfg.pcg_method, x0=x0,
                      track_residuals=track)
            pinv_z = precond.solve(probes)
            quad = op.allreduce(jnp.dot(yc, res.solution[:, 0]))
            return res, probes, pinv_z, quad

        def slq_fn(precond, alphas, betas, active, rz0):
            return precond.logdet() + slq_logdet_correction(
                alphas[:, 1:], betas[:, 1:], active[:, 1:], rz0[1:])

        def backward_fn(X, params, u_y, U, pinv_z):
            n = X.shape[0]
            _, _, g_params = operator_mll_backward(
                cfg, X, params, u_y, U, pinv_z, -1.0 / n)
            return g_params

        return {"precond": jax.jit(precond_fn),
                "solve": jax.jit(solve_fn),
                "slq": jax.jit(slq_fn),
                "backward": jax.jit(backward_fn)}

    def _modeled_phase_costs(self, mode, X) -> dict:
        """Per-phase §Roofline StepCosts keyed by the phase-span names —
        attached to each measured phase span so `obs_report
        --compare-model` can join measured ms against modeled bytes."""
        cfg = self.cfg
        try:
            n, d = int(X.shape[0]), int(X.shape[-1])
            plan = getattr(cfg, "plan", None)
            return obs.mll_phase_costs(
                n, d,
                num_rhs=1 + int(cfg.num_probes),
                max_cg_iters=int(cfg.max_cg_iters),
                backend=getattr(cfg, "backend", "partitioned"),
                row_block=int(getattr(cfg, "row_block", 1024)),
                fill=float(getattr(plan, "fill", 1.0)) if plan is not None
                     else 1.0,
                warm_init=mode != "cold",
                precond_rank=int(cfg.precond_rank) if mode != "warm" else 0,
            )
        except (AttributeError, TypeError, ValueError):
            return {}

    def _dispatch_phased(self, mode, X, y, params, key):
        fns = self._phase_fns.get(mode)
        if fns is None:
            fns = self._phase_fns[mode] = self._make_phases(mode)
        state = self.state
        n = X.shape[0]
        modeled = self._modeled_phase_costs(mode, X)
        backend = getattr(self.cfg, "backend", "partitioned")
        phase_ms: dict[str, float] = {}

        def annotate(sp, phase, t_start):
            ms = (time.perf_counter() - t_start) * 1e3
            phase_ms[phase] = ms
            cost = modeled.get(phase)
            if cost is not None:
                sp.set(measured_ms=ms, backend=backend,
                       modeled_hbm_bytes=cost.hbm_bytes,
                       modeled_launches=cost.launches)
            else:
                sp.set(measured_ms=ms, backend=backend)

        with obs.span("precond_build", mode=mode) as sp:
            t = time.perf_counter()
            if mode == "warm":
                precond = fns["precond"](X, params, state.precond)
            else:
                precond = fns["precond"](X, params)
            jax.block_until_ready(precond)
            annotate(sp, "precond_build", t)

        with obs.span("cg_solve", mode=mode) as sp:
            t = time.perf_counter()
            if mode == "cold":
                res, probes, pinv_z, quad = fns["solve"](
                    X, y, params, key, precond)
            else:
                res, probes, pinv_z, quad = fns["solve"](
                    X, y, params, key, precond, state)
            jax.block_until_ready(res.solution)
            annotate(sp, "cg_solve", t)
            sp.set(cg_iters=int(np.sum(np.asarray(res.iterations))))

        with obs.span("slq_logdet", mode=mode) as sp:
            t = time.perf_counter()
            if mode == "warm":
                logdet = state.logdet  # carried (see module docstring)
            else:
                logdet = fns["slq"](precond, res.alphas, res.betas,
                                    res.active, res.rz0)
            jax.block_until_ready(logdet)
            annotate(sp, "slq_logdet", t)

        with obs.span("eq2_backward", mode=mode) as sp:
            t = time.perf_counter()
            u_y, U = res.solution[:, 0], res.solution[:, 1:]
            g_params = fns["backward"](X, params, u_y, U, pinv_z)
            jax.block_until_ready(g_params)
            annotate(sp, "eq2_backward", t)

        self._last_phase_ms = phase_ms
        value = -0.5 * (quad + logdet + n * np.log(2.0 * np.pi))
        aux = MLLAux(logdet=logdet, quad=quad,
                     cg_iterations=res.iterations,
                     rel_residual=res.rel_residual,
                     residuals=res.residuals, traversals=res.traversals)
        new_state = SolverState(solve=res.state._replace(probes=probes),
                                precond=precond, logdet=logdet)
        return -value / n, aux, g_params, new_state


class DistWarmStartEngine(_WarmEngineBase):
    """The same engine over the sharded backend (shard_map on a mesh).

    Wraps `repro.core.distributed.make_warm_mll_step`; the refresh schedule
    and telemetry come from the shared base. aux comes back as the
    (logdet, quad, cg_iterations, rel_residual, traversals) tuple the
    distributed MLL uses, repacked into MLLAux here.
    """

    def __init__(self, mesh, geom, cfg, warm: WarmStartConfig | None = None):
        from repro.core.distributed import make_warm_mll_step, replicate

        super().__init__(warm)
        self.mesh = mesh
        self.geom = geom
        self.cfg = cfg
        self._replicate = replicate
        self._fns = make_warm_mll_step(
            mesh, geom, cfg, warm_min_iters=self.warm.warm_min_iters)

    def _dispatch(self, mode, X, y, params, key):
        params_r = self._replicate(self.mesh, params)
        if mode == "cold":
            out = self._fns.cold(X, y, params_r, key)
        elif mode == "refresh":
            out = self._fns.refresh(X, y, params_r, key, self.state)
        else:
            out = self._fns.warm(X, y, params_r, key, self.state)
        loss, aux_t, g_params, state = out
        aux = MLLAux(logdet=aux_t[0], quad=aux_t[1],
                     cg_iterations=aux_t[2], rel_residual=aux_t[3],
                     traversals=aux_t[4])
        return loss, aux, g_params, state
