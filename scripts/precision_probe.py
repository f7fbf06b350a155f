"""Float32 K_hat @ V on a TPU at each matmul precision, against float64.

    python scripts/precision_probe.py [--n 32768] [--seed 0]

For DEFAULT (one bf16 pass), HIGH (three) and HIGHEST (six), runs the
partitioned (XLA) and the fused (Pallas) float32 K_hat @ V over all n
columns of the `houseelectric` analogue and prints, per pair, the worst
error on 256 rows over the fp32 tolerance of `chip_smoke.py` (a float64
numpy reference on the host) and the median of 5 timed calls. The program
itself runs HIGHEST (`repro.launch.runtime`, `repro.kernels.kmvm
.mxu_precision`); this shows why. A precision the compiler refuses is
printed as refused. Exits 2 when JAX finds no TPU.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

import jax
import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PRECISIONS = ("DEFAULT", "HIGH", "HIGHEST")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--n", type=int, default=32768)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    if jax.devices()[0].platform != "tpu":
        print("precision_probe: needs a TPU", file=sys.stderr)
        return 2
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax.numpy as jnp

    import chip_smoke as cs
    import repro.kernels.kmvm as kmvm
    from repro.configs.gp_exact_1m import CONFIG
    from repro.core import OperatorConfig, init_params_for, make_operator

    X, _, _, _ = cs.make_data(args.n, args.seed)
    params = init_params_for(CONFIG.kernel, noise=0.3, dtype=jnp.float32)
    rng = np.random.default_rng(args.seed)
    V = rng.standard_normal((args.n, 1 + CONFIG.num_probes)).astype(np.float32)
    idx = np.sort(rng.choice(args.n, 256, replace=False))
    ref = cs.khat_rows_np(X, idx, V, cs._raw(params))
    Xd, Vd = jnp.asarray(X), jnp.asarray(V)
    print(f"[probe] {jax.devices()[0].device_kind} n={args.n} "
          f"t={V.shape[1]} tolerance {cs.MVM_ATOL:g} + {cs.MVM_RTOL:g}|ref|")
    for backend in ("partitioned", "pallas"):
        cfg = OperatorConfig(kernel=CONFIG.kernel, backend=backend)
        for name in PRECISIONS:
            prec = jax.lax.Precision[name]
            # the fused kernels fix their own dot precision; the probe
            # swaps it for the one under test
            kmvm.mxu_precision = lambda _dtype, prec=prec: prec
            try:
                with jax.default_matmul_precision(name.lower()):
                    mv = jax.jit(
                        lambda X, V, p: make_operator(cfg, X, p).matvec(V))
                    out = jax.block_until_ready(mv(Xd, Vd, params))
                    times = []
                    for _ in range(5):
                        t0 = time.perf_counter()
                        jax.block_until_ready(mv(Xd, Vd, params))
                        times.append(time.perf_counter() - t0)
            except Exception as e:  # a precision the compiler refuses
                msg = str(e).strip().splitlines()[0][:160]
                print(f"[probe] {backend} {name}: refused ({msg})")
                continue
            err = np.abs(np.asarray(out)[idx].astype(np.float64) - ref)
            viol = np.max(err / (cs.MVM_ATOL + cs.MVM_RTOL * np.abs(ref)))
            print(f"[probe] {backend} {name}: max|err|/tol={viol:.4g} "
                  f"max|err|={err.max():.4g} median={np.median(times):.6f}s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
