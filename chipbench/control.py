"""The control and the planted faults of a cell, read at the cell's size.

    python3 chipbench/control.py --workload <cell> --seeds 11 12 13 \
        [--seconds 3] [--variants sound control no_trace ...]

Not part of a benchmark run. Each variant runs the cell's own kind (set-up,
a short window, the check against the reference with the cell's limits)
with one thing put in the program's place, and prints every number the
run compared beside its limit, and whether the run came out correct.

The control is the reference put in the program's place, computed one
precision step below the configuration's float32 at HIGHEST: the
three-pass bf16 product ("high").

training cells
  sound        the program as the cell runs it;
  reference    the reference in the program's place at HIGHEST: plain CG
               on the program's schedule (cold: min_cg_iters from zero,
               warm: one iteration from the last solution, refresh:
               min_cg_iters from it), its own Hutchinson gradient, Adam;
  control      the same at "high";
  no_trace     the program's Eq. 2 backward with its trace term left out
               (a wrong backward);
  skipped      the program's cold solve returning zero, with the residual
               it has (1), as if it had run no iteration;
  half_rows    the program's step over the first half of the points, the
               mean taken over those rows only;
  altered      one entry of the targets' solution changed as the step
               leaves it;
  unchanged    a step that returns its state unchanged.

(The exchange between chips left out, a fault of cells on 4 chips, is
planted by `chipbench/tests/test_faults.py` on four virtual devices.)

serving cells (a short open-loop window through the program's batcher)
  control     the reference's posterior at "high" as the engine;
  altered     the engine's answers with one served mean changed per block;
  half_rows   the engine's answers for the first half of each block's
              rows only, the rest left at zero.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from chipbench.common import (CompileClock, Context,  # noqa: E402
                              inv_softplus, load_reference, log)


def init_raw(tr: dict) -> dict:
    return {"raw_lengthscale": inv_softplus(tr["init_lengthscale"]),
            "raw_outputscale": inv_softplus(tr["init_outputscale"]),
            "raw_noise": inv_softplus(tr["init_noise"]),
            "raw_mean": 0.0}


class ReferenceTrainer:
    """The reference in the program's place, at `precision`: plain CG on
    the program's schedule of cold, warm and refresh solves, the
    reference's own gradient (its probes solved by CG to a tight
    tolerance, at most `train_cg_iters` iterations), plain Adam."""

    precision = "highest"
    # its solves are not the program's, so `train_mfu` has no count of
    # the iterations a step needed to charge, and reads nothing
    cg_iters_max = None

    def __init__(self, ctx, X, y):
        from chipbench.kinds import train

        cfg, tr = ctx.config, ctx.traffic
        self.ref = load_reference(cfg["reference"])
        self.cfg, self.tr = cfg, tr
        self.X, self.y = X, y
        self.p0 = [init_raw(tr)[k] for k in train.LEAVES]
        self.params = list(self.p0)
        self.grads: list = []
        self.u = None
        self.since_refresh = 0

    def _mode(self) -> str:
        if self.u is None:
            return "cold"
        if self.since_refresh >= self.tr["refresh_every"]:
            return "refresh"
        return "warm"

    def step(self, key, trace: bool, keep: bool = False):
        import jax.numpy as jnp

        from chipbench.kinds import train

        ref, cfg = self.ref, self.cfg
        raw = dict(zip(train.LEAVES, self.params))
        h = ref.hyper(raw, cfg["noise_floor"])
        mode = self._mode()
        iters = 1 if mode == "warm" else cfg["min_cg_iters"]
        self.u, claimed = ref.cg_solve(self.X, self.y, h, self.u,
                                       iters=iters,
                                       precision=self.precision)
        self.since_refresh = 1 if mode != "warm" else self.since_refresh + 1
        g = ref.mll_grad(self.X, self.y, self.u, raw, h, key,
                         probes=cfg["num_probes"], tol=1e-4,
                         max_iters=cfg["train_cg_iters"],
                         precision=self.precision)
        grads = g["at_u"]
        loss = 0.5 * g["quad_u"] / self.X.shape[0]
        before = list(self.params)
        self.grads.append(grads)
        tr = self.tr
        self.params = ref.adam(self.p0, self.grads, tr["lr"], tr["adam_b1"],
                               tr["adam_b2"], tr["adam_eps"])[-1]
        finite = bool(np.all(np.isfinite(grads + [loss])))
        rec = (train.Record(None, before, jnp.asarray(self.u),
                            float(claimed), grads, mode, loss)
               if keep else None)
        return mode, finite, rec

    def current(self) -> list:
        return list(self.params)

    def free(self) -> None:
        self.u = None


class ControlTrainer(ReferenceTrainer):
    """The control: the reference in the program's place at "high"."""

    precision = "high"


def _patched(module, name, make):
    """A ProgramTrainer subclass whose program runs with `module.name`
    replaced by make(original) while the trainer lives."""
    from chipbench.kinds import train

    class Patched(train.ProgramTrainer):
        def __init__(self, ctx, X, y):
            import importlib

            self._mod = importlib.import_module(module)
            self._orig = getattr(self._mod, name)
            setattr(self._mod, name, make(self._orig))
            super().__init__(ctx, X, y)

        def free(self) -> None:
            setattr(self._mod, name, self._orig)
            super().free()

    return Patched


def _no_trace(orig):
    """The Eq. 2 assembly with the probe solutions zeroed: the trace term
    left out, the data-fit term kept."""
    def quad_grads(make_op, X, u_y, U, pinv_z):
        return orig(make_op, X, u_y, U * 0, pinv_z)

    return quad_grads


def _skipped(orig):
    """A cold solve (no x0) that returns zero and the residual of zero."""
    def pcg(*a, **kw):
        import jax.numpy as jnp

        res = orig(*a, **kw)
        if kw.get("x0") is not None:
            return res
        return res._replace(solution=jnp.zeros_like(res.solution),
                            rel_residual=jnp.ones_like(res.rel_residual))

    return pcg


def _trainers() -> dict:
    from chipbench.kinds import train

    class Unchanged(train.ProgramTrainer):
        """A step that returns its state unchanged: the gradient is
        computed and reported, the parameters and Adam's state stay."""

        def step(self, key, trace, keep=False):
            params, opt = self.params, self.opt
            out = super().step(key, trace, keep)
            self.params, self.opt = params, opt
            return out

    class HalfRows(train.ProgramTrainer):
        """The step over the first half of the points, the mean taken over
        those rows only."""

        def __init__(self, ctx, X, y):
            half = X.shape[0] // 2
            super().__init__(ctx._replace(config=dict(ctx.config, n=half)),
                             X[:half], y[:half])
            self.full = X.shape[0]

        def step(self, key, trace, keep=False):
            import jax.numpy as jnp

            mode, finite, rec = super().step(key, trace, keep)
            if rec is not None:
                rec.u_y = jnp.concatenate(
                    [rec.u_y, jnp.zeros(self.full - rec.u_y.shape[0])])
            return mode, finite, rec

    class Altered(train.ProgramTrainer):
        """One entry of the targets' solution changed as the step leaves
        it."""

        def step(self, key, trace, keep=False):
            mode, finite, rec = super().step(key, trace, keep)
            if rec is not None:
                rec.u_y = rec.u_y.at[rec.u_y.shape[0] // 3].add(1.0)
            return mode, finite, rec

    return {
        "sound": train.ProgramTrainer,
        "reference": ReferenceTrainer,
        "control": ControlTrainer,
        "no_trace": _patched("repro.core.distributed",
                             "operator_mll_quad_grads", _no_trace),
        "skipped": _patched("repro.core.mll", "pcg", _skipped),
        "half_rows": HalfRows,
        "altered": Altered,
        "unchanged": Unchanged,
    }


TRAIN_VARIANTS = ("sound", "reference", "control", "no_trace", "skipped",
                  "half_rows", "altered", "unchanged")


def train_trainer(name: str):
    """The trainer class of a training variant."""
    return _trainers()[name]


def train_readings(ctx, names=TRAIN_VARIANTS) -> dict:
    """{variant: (correct, {number: value})} of training runs at ctx."""
    from chipbench.common import is_correct
    from chipbench.kinds import train

    out = {}
    for name in names:
        res = train.run(ctx._replace(t_start=time.perf_counter()),
                        CompileClock(), trainer_cls=train_trainer(name))
        out[name] = (is_correct(res),
                     {k: v["value"] for k, v in res["checks"].as_dict()
                      .items()})
    return out


class AlteredEngine:
    """An answer altered where it is produced: one served mean per block
    changed."""

    def __init__(self, engine):
        self.engine = engine

    def predict(self, X):
        import jax.numpy as jnp

        mean, var = self.engine.predict(X)
        return jnp.asarray(mean).at[0].add(1.0), var


class HalfRowsEngine:
    """Half of each block's rows answered, the rest left at zero."""

    def __init__(self, engine):
        self.engine = engine

    def predict(self, X):
        import jax.numpy as jnp

        half = max(X.shape[0] // 2, 1)
        mean, var = self.engine.predict(X[:half])
        pad = X.shape[0] - half
        return (jnp.concatenate([jnp.asarray(mean), jnp.zeros(pad)]),
                jnp.concatenate([jnp.asarray(var), jnp.zeros(pad)]))


class ControlEngine:
    """The reference's posterior, at "high", in the engine's place."""

    def __init__(self, ctx, X_train, raw, mean_cache, var_Q, var_T_chol):
        self.ref = load_reference(ctx.config["reference"])
        self.h = self.ref.hyper(raw, ctx.config["noise_floor"])
        self.args = (X_train, mean_cache, var_Q, var_T_chol)
        self.block = ctx.traffic["chunk_size"]

    def predict(self, X):
        import jax.numpy as jnp

        return self.ref.posterior(jnp.asarray(X), *self.args, self.h,
                                  precision="high", block=self.block)


def serve_faults(ctx) -> dict:
    """{name: (mean_gap, var_gap)} of the control and the faults."""
    from chipbench.kinds import serve_open_loop as so

    def program(*a):
        return so.build_engine(*a)

    variants = {
        "control": ControlEngine,
        "altered": lambda *a: AlteredEngine(program(*a)),
        "half_rows": lambda *a: HalfRowsEngine(program(*a)),
    }
    out = {}
    for name, factory in variants.items():
        res = so.run(ctx._replace(t_start=time.perf_counter()),
                     CompileClock(), engine_factory=factory)
        c = res["checks"].as_dict()
        out[name] = (c["mean_gap"]["value"], c["var_gap"]["value"])
    return out


def main(argv=None) -> int:
    from chipbench.run import chips_or_exit, load_cell, setup_jax

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=3.0,
                    help="window of each run")
    ap.add_argument("--variants", nargs="*", default=None,
                    help="training variants to run (default: all)")
    args = ap.parse_args(argv)
    _, cell, config, traffic, limits = load_cell(args.workload)
    devices = chips_or_exit(int(cell["chips"]))
    setup_jax()
    for seed in args.seeds:
        ctx = Context(cell, config, traffic, limits, seed, args.seconds,
                      False, T_START, devices, "")
        if traffic["kind"] == "train":
            names = args.variants or TRAIN_VARIANTS
            for name, (ok, nums) in train_readings(ctx, names).items():
                shown = " ".join(
                    f"{k}={v!r} (limit {limits[k]['limit']!r})"
                    for k, v in nums.items())
                log(f"[control] {cell['name']} seed={seed} {name}: {shown} "
                    f"correct={ok}")
        else:
            lm, lv = limits["mean_gap"]["limit"], limits["var_gap"]["limit"]
            for name, (m, v) in serve_faults(ctx).items():
                fails = m > lm or v > lv
                log(f"[control] {cell['name']} seed={seed} {name}: "
                    f"mean_gap={m!r} (limit {lm!r}) var_gap={v!r} "
                    f"(limit {lv!r}) {'fails' if fails else 'passes'}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
