"""Back-to-back exact-GP training steps: the `train` kind of traffic.

A step is what `repro.launch.train.train_gp` runs for each optimizer step:
`DistWarmStartEngine.step` (the BBMM MLL value and Eq. 2 gradient, warm-
started across steps), then `repro.optim.adam_update`, then the loss and
the gradients brought to the host.

Set-up makes the data on the device from the seed, prepares it with
`repro.launch.train.prepare_gp_data`, builds one engine and drives it
through its first `setup_steps` steps, as many for every seed: with a
refresh due every `refresh_every` steps, refresh_every + 1 steps run
every solve mode the window will use (cold, warm, refresh), and so
compile them. That same engine,
with its solver state and the Adam state, then runs the window:
whole steps until `seconds` have passed. `step_s` is the window's time
over its steps.

The first `checked_steps` steps, and for `resid_gap` the window's last
`window_checked_steps` too, are checked once the window has closed and
the program's state is freed:

* `resid_gap`: the solve of the targets' column. For each checked step
  the program reports, per right-hand side, the relative residual
  ||r|| / ||b|| its CG recurrence reached. The reference computes the
  true residual of the solution the step left in its state, with its
  own K_hat at HIGHEST, and the number is the largest gap between the
  two. It reads the kernel's matvecs (a wrong or low-precision K_hat @ V
  leaves the recurrence's residual apart from the true one), the
  sharded matvec's exchange and the rows the solve covers.
* `grad_gap`: the gradient the step reported (paper Eq. 2) against the
  reference's gradient at the step's own solution u of the targets: the
  data-fit terms u' dK u exactly, the trace terms tr(K_hat^-1 dK) by the
  reference's own Hutchinson estimate over its own probes, solved by
  plain CG. Leaf by leaf, the gap over the size of the two terms the
  leaf's gradient sums (|data fit| + |trace|; for the mean, whose
  gradient is -sum(u) / n, sum(|u|) / n), so that a gradient near zero,
  as near the optimum, where the terms cancel, is judged by the terms'
  own precision; the worst leaf of the worst of the set-up's checked
  steps. It reads the backward's assembly and a solve that did no work
  (a zero u carries no data fit, and its zero probe solutions no
  trace). The gap also holds the error of the program's own trace
  estimate (8 probes, loose solves), which sets how low its limit can
  be.
* `adam_gap`: the parameters after the set-up's checked steps against
  plain Adam applied to the gradients the program reported, leaf by
  leaf: the gap between the two changes' sizes over the larger of the
  reference's change of that leaf and of the median leaf.

`PERF.md` gives the readings the limits were set from, and why the loss
itself is not compared.
"""

from __future__ import annotations

import time

import numpy as np

from chipbench import datagen
from chipbench.common import (Checks, Profile, annotate, load_reference,
                              log)

LEAVES = ("raw_lengthscale", "raw_outputscale", "raw_noise", "raw_mean")
# the reference's gradient: Rademacher probes for its trace estimate, and
# the tolerance and iteration cap of its plain CG solves (a trace term's
# error is then about 1e-3 of its size, against the program's own trace
# estimate's error of some 1e-2 to 1e-1: PERF.md)
REF_PROBES = 16
REF_TOL = 1e-2
REF_MAX_ITERS = 1000


class Record:
    """What a checked step left for the check."""

    def __init__(self, step, params, u_y, claimed, grads, mode, loss):
        self.step = step          # index of the step in the run
        self.params = params      # raw hyperparameters before the step
        self.u_y = u_y            # the targets' solution (n,), on device
        self.claimed = claimed    # reported ||r|| / ||b|| of that column
        self.grads = grads        # reported gradients, host floats
        self.mode = mode
        self.loss = loss


def leaves(params) -> list[float]:
    return [float(np.asarray(getattr(params, k))) for k in LEAVES]


def make_data(ctx):
    cfg = ctx.config
    total = datagen.total_for_train(cfg["n"])
    return datagen.make(ctx.seed, total=total, d=cfg["d"], n_train=cfg["n"])


class ProgramTrainer:
    """The program's training step as `train_gp` runs it."""

    def __init__(self, ctx, X, y):
        import jax
        import jax.numpy as jnp

        from repro.core import KERNEL_KINDS, init_params_for, parse_kernel
        from repro.core.distributed import (DistMLLConfig, replicate,
                                            shard_vector)
        from repro.launch.mesh import make_host_mesh
        from repro.launch.train import prepare_gp_data
        from repro.optim import adam_init
        from repro.train.solver_state import WarmStartConfig

        cfg, tr = ctx.config, ctx.traffic
        self.n = cfg["n"]
        rows, cols = cfg["mesh"]
        self.mesh = make_host_mesh(data=rows, model=cols)
        kernel = cfg["kernel"]
        self.kernel = (kernel if kernel in KERNEL_KINDS
                       else parse_kernel(kernel))
        self.params = init_params_for(self.kernel, noise=tr["init_noise"],
                                      dtype=jnp.float32)
        dtype = cfg["compute_dtype"]
        self.drift = tr["drift_threshold"]
        self.geom, Xp, yp, self.plan = prepare_gp_data(
            self.mesh, X, y, backend=cfg["backend"], gp_mode=cfg["mode"],
            kernel=self.kernel, params=self.params, margin=self.drift,
            overlap=cfg["overlap"], row_block=cfg["row_block"])
        assert self.geom.n == self.n
        self.mll = DistMLLConfig(
            kernel=self.kernel, precond_rank=cfg["precond_rank"],
            num_probes=cfg["num_probes"], max_cg_iters=cfg["train_cg_iters"],
            cg_tol=cfg["cg_tol"], backend=cfg["backend"],
            compute_dtype=None if dtype == "float32" else dtype,
            plan=self.plan)
        self.warm = WarmStartConfig(enabled=tr["refresh_every"] > 0,
                                    refresh_every=max(tr["refresh_every"], 1),
                                    drift_threshold=self.drift)
        self.engine = self._engine()
        self.Xp = Xp
        self.X = replicate(self.mesh, Xp)
        self.y = shard_vector(self.mesh, self.geom, yp)
        self.opt = adam_init(self.params)
        self.lr = tr["lr"]
        self.jax = jax

    def _engine(self):
        from repro.train.solver_state import DistWarmStartEngine

        return DistWarmStartEngine(self.mesh, self.geom, self.mll, self.warm)

    def _replan(self) -> None:
        """A sparse plan follows the hyperparameters as `train_gp` has it
        follow them: rebuilt, with a new engine, past the drift margin."""
        from repro.sparse import build_plan, needs_replan

        replan, _ = needs_replan(self.plan, self.params, self.drift,
                                 kernel=self.kernel)
        if replan:
            self.plan = build_plan(self.kernel, self.Xp, self.params,
                                   tile=self.plan.tile, margin=self.drift,
                                   assume_sorted=True)
            self.mll = self.mll._replace(plan=self.plan)
            self.engine = self._engine()

    def step(self, key, trace: bool, keep: bool = False):
        """One training step; with keep, also what the check needs (its
        `step` index is filled in by the caller)."""
        from repro.optim import adam_update

        jax = self.jax
        before = self.params
        if self.plan is not None:
            self._replan()
        with annotate("step", trace):
            loss, aux, grads = self.engine.step(self.X, self.y, self.params,
                                                key)
        with annotate("adam", trace):
            self.params, self.opt = adam_update(self.params, grads, self.opt,
                                                self.lr)
        with annotate("loss_to_host", trace):
            loss = float(loss)
            grads = jax.device_get(grads)
        tel = self.engine.telemetry[-1]
        mode = tel["mode"]
        self.cg_iters_max = tel["cg_iters_max"]
        finite = bool(np.isfinite(loss)) and all(
            np.all(np.isfinite(g)) for g in jax.tree.leaves(grads))
        rec = None
        if keep:
            rec = Record(None, leaves(before),
                         self.engine.state.solutions[:self.n, 0],
                         float(np.asarray(aux.rel_residual)[0]),
                         leaves(grads), mode, loss)
        return mode, finite, rec

    def current(self) -> list[float]:
        return leaves(self.params)

    def free(self) -> None:
        self.engine = self.X = self.Xp = self.y = self.opt = None


def setup(ctx, X, y, trainer_cls=ProgramTrainer):
    """The trainer driven through its first steps; (trainer, records,
    params after the checked steps)."""
    import jax

    tr = ctx.traffic
    base = datagen.seed_key(ctx.seed)
    trainer = trainer_cls(ctx, X, y)
    records, seen = [], set()
    after_checked = None
    for i in range(tr["setup_steps"]):
        keep = i < tr["checked_steps"]
        mode, finite, rec = trainer.step(jax.random.fold_in(base, i),
                                         trace=False, keep=keep)
        seen.add(mode)
        if keep:
            rec.step = i
            records.append(rec)
            if i + 1 == tr["checked_steps"]:
                after_checked = trainer.current()
        log(f"[setup] step {i}: mode={mode} finite={finite}"
            + (f" loss={rec.loss:.6f} claimed_resid={rec.claimed:.6e}"
               if rec else ""))
    if not set(tr["modes"]) <= seen:
        raise RuntimeError(f"set-up ran modes {sorted(seen)}, not all of "
                           f"{tr['modes']}: the window would compile")
    return trainer, records, after_checked, tr["setup_steps"]


def window(ctx, trainer, first_step: int):
    """Whole steps until ctx.seconds have passed: (steps, seconds, modes,
    the most CG iterations any column of each step applied (None where
    the trainer does not count them), failed, the records of the last
    `window_checked_steps`)."""
    import jax

    base = datagen.seed_key(ctx.seed)
    keep = ctx.traffic["window_checked_steps"]
    modes, iters, failed, kept = [], [], 0, []
    i = first_step
    t0 = time.perf_counter()
    deadline = t0 + ctx.seconds
    while True:
        mode, finite, rec = trainer.step(jax.random.fold_in(base, i),
                                         trace=ctx.trace, keep=keep > 0)
        if rec is not None:
            rec.step = i
            kept = (kept + [rec])[-keep:]
        modes.append(mode)
        iters.append(trainer.cg_iters_max)
        failed += not finite
        i += 1
        if time.perf_counter() >= deadline:
            break
    return len(modes), time.perf_counter() - t0, modes, iters, failed, kept


def leaf_gap(got, want, scale) -> float:
    """The worst leaf's |got - want| over that leaf's scale."""
    gap = np.abs(np.subtract(got, want, dtype=np.float64))
    return float(np.max(gap / np.maximum(scale, 1e-30)))


def check(ctx, X, y, records, after_checked, checks: Checks) -> None:
    """The checked steps against the reference."""
    import jax
    import jax.numpy as jnp

    ref = load_reference(ctx.config["reference"])
    floor = ctx.config["noise_floor"]
    ref_key = jax.random.fold_in(datagen.seed_key(ctx.seed), 2**31 - 1)
    tr = ctx.traffic
    resid, grad, solves = [], [], None
    for rec in records:
        raw = dict(zip(LEAVES, rec.params))
        h = ref.hyper(raw, floor)
        u = jnp.asarray(rec.u_y)
        true = float(ref.true_rel_residual(X, y, u, h))
        resid.append(abs(true - rec.claimed))
        log(f"[check] step {rec.step} ({rec.mode}): claimed residual "
            f"{rec.claimed!r} true {true!r} gap {resid[-1]!r}")
        if rec.step >= tr["checked_steps"]:
            continue
        # the set-up's checked steps lie close together: each solve
        # starts from the last one's
        g = ref.mll_grad(X, y, u, raw, h, ref_key, probes=REF_PROBES,
                         tol=REF_TOL, max_iters=REF_MAX_ITERS, x0=solves)
        solves = g["solves"]
        grad.append(leaf_gap(rec.grads, g["at_u"], g["scale"]))
        log(f"[check] step {rec.step} ({rec.mode}): gradient {rec.grads} "
            f"reference at its u {g['at_u']} terms {g['scale']} gap "
            f"{grad[-1]!r}; the reference's own spread "
            f"{leaf_gap(*g['halves'], g['scale']) / 2!r} "
            f"({g['iterations']} CG iterations to {g['rel_residual']!r})")
        conv = leaf_gap(rec.grads, g["converged"], g["scale"])
        log(f"[check] step {rec.step} ({rec.mode}) against the reference's "
            f"converged solve (read, not compared): gradient gap {conv!r}, "
            f"data-fit term (y - m)'u {g['quad_u']!r} against "
            f"{g['quad_converged']!r}, loss {rec.loss!r}")
    checks.record("resid_gap", max(resid))
    checks.record("grad_gap", max(grad))
    first = records[:tr["checked_steps"]]
    p0 = first[0].params
    ref_after = ref.adam(p0, [r.grads for r in first], tr["lr"],
                         tr["adam_b1"], tr["adam_b2"], tr["adam_eps"])[-1]
    d_ref = np.abs(np.subtract(ref_after, p0))
    d_prog = np.abs(np.subtract(after_checked, p0))
    # a leaf whose first gradient is nought to rounding moves by round-off
    # alone under Adam: it is left out, by this rule and not by name
    g1 = np.abs(first[0].grads)
    kept = g1 >= 1e-3 * np.median(g1)
    scale = np.maximum(d_ref, np.median(d_ref[kept]))
    gap = float(np.max((np.abs(d_prog - d_ref) / np.maximum(scale, 1e-30))
                       [kept]))
    log(f"[check] parameter change after {len(first)} steps: program "
        f"{d_prog.tolist()} reference {d_ref.tolist()}; first gradient "
        f"{first[0].grads}; leaves kept {kept.tolist()}")
    checks.record("adam_gap", gap)


def run(ctx, clock, trainer_cls=ProgramTrainer) -> dict:
    """One run of a training cell; returns what run.py prints."""
    import gc

    from chipbench.common import memory_peak_bytes

    data = make_data(ctx)
    X, y = data.X_train, data.y_train
    trainer, records, after_checked, n_setup = setup(ctx, X, y, trainer_cls)
    setup_s = time.perf_counter() - ctx.t_start
    log(f"[setup] {n_setup} steps; setup_s={setup_s:.3f} "
        f"compile={clock.seconds:.3f}s compiles={clock.compiles} "
        f"cache_hits={clock.cache_hits}")

    clock.mark()
    prof = Profile(ctx)
    with prof:
        steps, secs, modes, iters, failed, last = window(ctx, trainer,
                                                         n_setup)
    log(f"[window] {steps} steps in {secs:.3f}s modes={modes} "
        f"cg_iters_max={iters} compiles_in_window={clock.since_mark}")
    mem = memory_peak_bytes(ctx.devices)
    trainer.free()
    del trainer
    gc.collect()

    checks = Checks(ctx.limits)
    t0 = time.perf_counter()
    check(ctx, X, y, records + last, after_checked, checks)
    log(f"[check] {len(records + last)} steps checked in "
        f"{time.perf_counter() - t0:.3f}s")
    return {
        "e2e": {"setup_s": setup_s, "step_s": secs / steps},
        "attempted": steps, "failed": failed,
        "checks": checks, "memory_peak_bytes": mem,
        "profile": prof.path,
        "layer_ctx": {"kind": "train", "steps": steps, "modes": modes,
                      "cg_iters": iters, "window_host_s": secs,
                      "step_s": secs / steps},
    }
