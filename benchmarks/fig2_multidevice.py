"""Figure 2: training speedup from additional devices.

Runs the distributed MLL step on 1/2/4/8 devices. On a TPU host the cells
run in this process over subsets of `jax.devices()`: a chip belongs to one
process, and the parent already holds them. On the CPU each cell is a
child process with that many fake host devices (so the parent keeps one);
wall-clock there includes real thread-level parallelism across the
partitioned MVM, so the SHAPE of the scaling curve is observable, if noisy.

Beyond the paper's 1-D curve, the grid carries a 2-D (rows x cols) row per
device count plus an overlap ablation column: the ring-pipelined chunked
contraction vs the serial gather on the SAME layout (bitwise-identical
results — see core.distributed). On fake CPU devices the overlap delta
mostly reflects scheduling noise; the modeled exposed-collective-bytes
story lives in repro.obs.costmodel.dist_collective_cost and EXPERIMENTS.
"""

import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from .common import write_rows

DEVICE_COUNTS = (1, 2, 4, 8)


def step_time(ndev: int, mode: str, overlap: bool, reps: int = 3) -> float:
    """Seconds per distributed MLL value+grad step on the first `ndev`
    devices."""
    from repro.core import init_params
    from repro.core.distributed import (DistMLLConfig, make_geometry,
                                        make_mll_value_and_grad, replicate,
                                        shard_vector)
    from repro.launch.mesh import make_host_mesh

    n, d = 4096, 8
    rng = np.random.default_rng(0)
    X = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    y = jnp.asarray(rng.normal(size=(n,)), jnp.float32)
    params = init_params(noise=0.2, dtype=jnp.float32)
    if mode == "2d" and ndev > 1:
        mesh = make_host_mesh(data=ndev // 2, model=2)
    else:
        mesh = make_host_mesh(data=ndev, model=1)
    geom = make_geometry(mesh, n, d, mode=mode, row_block=256,
                         overlap=overlap)
    cfg = DistMLLConfig(precond_rank=50, num_probes=8, max_cg_iters=20,
                        cg_tol=1.0)
    vg = make_mll_value_and_grad(mesh, geom, cfg)
    args = (replicate(mesh, X), shard_vector(mesh, geom, y),
            replicate(mesh, params), jax.random.PRNGKey(0))
    jax.block_until_ready(vg(*args))  # compile
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(vg(*args))
    return (time.perf_counter() - t0) / reps


def _cell(ndev: int, mode: str, overlap: bool) -> float:
    if jax.default_backend() == "tpu":
        return step_time(ndev, mode, overlap)
    env = dict(os.environ, PYTHONPATH="src",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={ndev}")
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.fig2_multidevice", "--cell",
         str(ndev), mode, "overlap" if overlap else "serial"],
        capture_output=True, text=True, env=env, timeout=1200, check=True)
    line = [l for l in out.stdout.splitlines() if l.startswith("{")][-1]
    return json.loads(line)["step_s"]


def run():
    counts = DEVICE_COUNTS
    if jax.default_backend() == "tpu":
        counts = tuple(c for c in counts if c <= len(jax.devices()))
    rows = []
    base = None
    for ndev in counts:
        s_1d = _cell(ndev, "1d", False)
        # 2-D needs a model axis; on 1 device it degenerates to 1-D
        s_2d = _cell(ndev, "2d", False) if ndev > 1 else s_1d
        s_2d_ov = _cell(ndev, "2d", True) if ndev > 1 else s_1d
        if base is None:
            base = s_1d
        rows.append([ndev, round(s_1d, 3), round(base / s_1d, 2),
                     round(s_2d, 3), round(s_2d_ov, 3)])
        print(f"[fig2] {ndev} devices: 1d={s_1d:.2f}s/step "
              f"speedup={base / s_1d:.2f}x 2d={s_2d:.2f}s "
              f"2d+overlap={s_2d_ov:.2f}s")
    write_rows("fig2_multidevice",
               ["devices", "step_s", "speedup", "step_s_2d",
                "step_s_2d_overlap"], rows)
    return rows


if __name__ == "__main__":
    if sys.argv[1:2] == ["--cell"]:
        # child of a CPU run: XLA_FLAGS gave this process the fake devices
        ndev, mode, ov = int(sys.argv[2]), sys.argv[3], sys.argv[4]
        print(json.dumps({"ndev": ndev, "step_s": step_time(
            ndev, mode, ov == "overlap")}))
    else:
        run()
