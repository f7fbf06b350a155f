"""Run every benchmark: `PYTHONPATH=src python -m benchmarks.run [--quick]`.

One module per paper table/figure (+ extra ablations):
    table1_accuracy     Table 1  exact vs SGPR vs SVGP (RMSE/NLL)
    table2_timing       Table 2  train / precompute / sub-second predictions
    fig1_fig5_init      Fig 1&5  pretrain-init vs plain Adam
    fig2_multidevice    Fig 2    multi-device speedup (subprocess scaling)
    fig3_inducing       Fig 3    inducing-point saturation vs exact floor
    fig4_subset         Fig 4    subset-of-data curves
    ablation_tolerance  Sec 3    CG tolerance train vs predict
    ablation_warmstart  §Warm-start  cold vs warm-started finetune solves
    ablation_kernels    §Kernel algebra  1/2/4-component sums x backends
    ablation_sparsity   §Sparsity  fill-ratio sweep: blocksparse vs dense
    roofline_report     §Roofline tables from experiments/dryrun/*.json
    serve_latency       §Serving p50/p99/QPS: backend x chunk x batch sweep

Each benchmark writes <name>.csv/.md plus a machine-readable
BENCH_<name>.json (keyed records) under experiments/benchmarks/, so the
perf trajectory stays comparable across PRs.
"""

import argparse
import sys
import time
import traceback


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, help="comma list of bench names")
    ap.add_argument("--quick", action="store_true",
                    help="single-seed Table 1")
    args = ap.parse_args()

    from repro.launch.runtime import setup_runtime

    setup_runtime()
    from . import (ablation_kernels, ablation_sparsity, ablation_tolerance,
                   ablation_warmstart, fig1_fig5_init, fig2_multidevice,
                   fig3_inducing, fig4_subset, roofline_report,
                   serve_latency, table1_accuracy, table2_timing)

    benches = {
        "table1_accuracy": (lambda: table1_accuracy.run(
            seeds=(0,) if args.quick else (0, 1, 2))),
        "table2_timing": table2_timing.run,
        "fig1_fig5_init": fig1_fig5_init.run,
        "fig2_multidevice": fig2_multidevice.run,
        "fig3_inducing": fig3_inducing.run,
        "fig4_subset": fig4_subset.run,
        "ablation_tolerance": ablation_tolerance.run,
        "ablation_warmstart": ablation_warmstart.run,
        "ablation_kernels": ablation_kernels.run,
        "ablation_sparsity": ablation_sparsity.run,
        "roofline_report": roofline_report.run,
        "serve_latency": serve_latency.run,
    }
    if args.only:
        keep = args.only.split(",")
        benches = {k: v for k, v in benches.items() if k in keep}

    failures = []
    for name, fn in benches.items():
        print(f"\n===== {name} =====", flush=True)
        t0 = time.time()
        try:
            fn()
            print(f"[bench] {name} done in {time.time() - t0:.0f}s")
        except Exception:
            failures.append(name)
            traceback.print_exc()
    if failures:
        print(f"\nFAILED: {failures}")
        sys.exit(1)
    print("\nALL BENCHMARKS DONE")


if __name__ == "__main__":
    main()
