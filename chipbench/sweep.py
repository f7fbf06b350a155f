"""Find the knee of a serving cell: one set-up, then open-loop windows at
rising rates.

    python3 chipbench/sweep.py --workload he-serve-1chip --seed 5 \
        --seconds 10 --rates 400 800 1200 1600

For each rate it prints the latency percentiles, the rows completed per
second, the generator's lateness, and whether the backlog grew (the
median latency of the window's last quarter of requests against its
first). The knee is the highest rate whose backlog does not grow. Not part
of a benchmark run: the cell's rate is fixed in its traffic file.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import numpy as np  # noqa: E402

from chipbench.common import Context, log  # noqa: E402


def main(argv=None) -> int:
    from chipbench.kinds import serve_open_loop as so
    from chipbench.run import chips_or_exit, load_cell, setup_jax

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    _, cell, config, traffic, limits = load_cell(args.workload)
    devices = chips_or_exit(int(cell["chips"]))
    setup_jax()
    ctx = Context(cell, config, traffic, limits, args.seed, args.seconds,
                  False, T_START, devices, "")
    st = so.Served(ctx)
    try:
        for rate in args.rates:
            sched = so.Schedule(dict(traffic, rate_per_s=rate), args.seconds,
                                args.seed, traffic["query_pool"])
            w = st.window(ctx, sched)
            q = max(len(sched) // 4, 1)
            first = np.median(w["lat"][:q])
            last = np.median(w["lat"][-q:])
            log(f"[sweep] rate={rate} {so.summary(w)}; rows/s="
                f"{w['rows'] / w['window_s']:.1f}; backlog median ms "
                f"first quarter {first:.3f} last quarter {last:.3f}")
    finally:
        st.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
