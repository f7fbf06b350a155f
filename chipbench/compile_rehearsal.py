"""Compile each cell's programs at their real sizes for a described TPU v5e.

    JAX_PLATFORMS=cpu PYTHONPATH=src python chipbench/compile_rehearsal.py

Nothing runs and no chip is needed: the installed TPU compiler compiles
for a `v5e:2x2` topology it describes, and refuses what the chip would
refuse (unaligned tiles, VMEM overuse, programs that do not fit 16 GB).
For each program it prints `memory_analysis()` and whether the fused
kernel is in it:

* the one-chip training step (cold, warm and refresh solves) at the
  training cells' sizes, among them CTslice's d = 385, whose lanes pad to
  512;
* the serving chunk program (`PredictionEngine`'s mean and variance for
  one chunk of query rows) against HouseElectric's 1,311,539 points;
* the training step on the 2x2 mesh at n = 131,072.

The program picks interpret mode from `jax.default_backend()`, which is
the CPU here, so this script reports "tpu" to it while it compiles.
"""

from __future__ import annotations

import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

from chipbench.common import load_json  # noqa: E402

GiB = 2 ** 30


def _report(name: str, compiled) -> None:
    m = compiled.memory_analysis()
    kernel = "tpu_custom_call" in compiled.as_text()
    print(f"{name}: args {m.argument_size_in_bytes / GiB:.3f} GiB, "
          f"outputs {m.output_size_in_bytes / GiB:.3f} GiB, temps "
          f"{m.temp_size_in_bytes / GiB:.3f} GiB, code "
          f"{m.generated_code_size_in_bytes / 2**20:.1f} MiB; fused kernel "
          f"{'present' if kernel else 'ABSENT'}", flush=True)


def _mesh(devices, shape):
    import numpy as np
    from jax.sharding import AxisType

    return Mesh(np.array(devices[:shape[0] * shape[1]]).reshape(shape),
                ("data", "model"), axis_types=(AxisType.Auto,) * 2)


def train_step(topo, config_file: str, **override) -> None:
    """cold, warm and refresh of the warm-start engine's sharded step, for
    a configuration with some of its keys overridden."""
    from repro.core import init_params_for
    from repro.core.distributed import (DistMLLConfig, make_geometry,
                                        make_warm_mll_step)

    cfg = dict(load_json(os.path.join(HERE, "configs", config_file)),
               **override)
    mesh = _mesh(topo.devices, cfg["mesh"])
    n, d = cfg["n"], cfg["d"]
    geom = make_geometry(mesh, n, d, mode=cfg["mode"],
                         row_block=cfg["row_block"], overlap=cfg["overlap"])
    mll = DistMLLConfig(kernel=cfg["kernel"],
                        precond_rank=cfg["precond_rank"],
                        num_probes=cfg["num_probes"],
                        max_cg_iters=cfg["train_cg_iters"],
                        cg_tol=cfg["cg_tol"], backend=cfg["backend"])
    fns = make_warm_mll_step(mesh, geom, mll)
    rep = NamedSharding(mesh, P())
    vec = NamedSharding(mesh, geom.vector_pspec())

    def spec(shape, dtype, sh):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=sh)

    params = jax.tree.map(lambda a: spec(a.shape, a.dtype, rep),
                          init_params_for(cfg["kernel"], noise=0.3))
    args = (spec((geom.n_padded, d), "float32", rep),
            spec((geom.n_padded,), "float32", vec), params,
            spec((2,), "uint32", rep))
    with jax.default_matmul_precision(cfg["matmul_precision"]):
        cold = fns.cold.lower(*args).compile()
        _report(f"{cfg['name']} step cold", cold)
        state = cold.out_info[3]
        state = jax.tree.map(lambda s: spec(s.shape, s.dtype, s.sharding),
                             state)
        for mode in ("warm", "refresh"):
            compiled = getattr(fns, mode).lower(*args, state).compile()
            _report(f"{cfg['name']} step {mode}", compiled)


def serve_chunk(topo, config_file: str, traffic_file: str) -> None:
    from repro.core import OperatorConfig, init_params_for, make_operator
    from repro.core.predcache import (PredictionCache, predict_mean,
                                      predict_var_cached)

    cfg = load_json(os.path.join(HERE, "configs", config_file))
    tr = load_json(os.path.join(HERE, "traffic", traffic_file))
    one = jax.sharding.SingleDeviceSharding(topo.devices[0])
    n, d, r = cfg["n"], cfg["d"], cfg["lanczos_rank"]
    op_config = OperatorConfig(kernel=cfg["kernel"], backend=cfg["backend"],
                               noise_floor=cfg["noise_floor"])

    def chunk(X, params, cache, Xc):
        op = make_operator(op_config, X, params)
        return (predict_mean(op, Xc, cache),
                predict_var_cached(op, Xc, cache, include_noise=True))

    def spec(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one)

    params = jax.tree.map(lambda a: spec(a.shape),
                          init_params_for(cfg["kernel"], noise=0.3))
    cache = PredictionCache(spec((n,)), spec((n, r)), spec((r, r)),
                            spec((1,)))
    with jax.default_matmul_precision(cfg["matmul_precision"]):
        compiled = jax.jit(chunk).lower(spec((n, d)), params, cache,
                                        spec((tr["chunk_size"], d))).compile()
    _report(f"{cfg['name']} serve chunk of {tr['chunk_size']} rows", compiled)


def main() -> int:
    from jax.experimental import topologies

    jax.config.update("jax_enable_x64", False)
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    # the program takes its TPU branch (compiled kernels, not interpret)
    jax.default_backend = lambda: "tpu"
    train_step(topo, "ctslice-m32.json")
    train_step(topo, "houseelectric-m32-n65536.json")
    serve_chunk(topo, "houseelectric-m32.json", "open_loop_16rows.json")
    train_step(topo, "houseelectric-m32-n65536.json", n=131072, mesh=[2, 2],
               name="houseelectric-m32 at n=131072 on 2x2")
    return 0


if __name__ == "__main__":
    sys.exit(main())
