import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# unroll all fixed-trip INNER loops: cost_analysis counts while bodies once
os.environ.setdefault("REPRO_DRYRUN_UNROLL", "1")

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The two env lines above MUST precede every other import (jax locks the
device count at first init): the dry-run — and only the dry-run — sees 512
placeholder CPU devices so `make_production_mesh` can build the production
16x16 (single-pod) and 2x16x16 (multi-pod) meshes.

Cost accounting: XLA's cost_analysis counts a while-loop body ONCE, so each
cell is compiled twice — depth-loop unroll=1 and unroll=2 — and per-layer
costs are linearly extrapolated: total = A + (depth-1) * (B - A). All
assigned depths are even, so unroll=2 divides exactly. Inner loops
(attention/CE/SSD chunks, kernel row blocks) are fully unrolled via
REPRO_DRYRUN_UNROLL. memory_analysis comes from the rolled (unroll=1)
program, which is the deployed form.

Nothing is allocated: inputs are ShapeDtypeStructs throughout.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch smollm-360m
    PYTHONPATH=src python -m repro.launch.dryrun --multi-pod --out exp/dryrun
    PYTHONPATH=src python -m repro.launch.dryrun --cells train_4k,decode_32k
"""

import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.launch import roofline as rl
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import (
    SHAPES, Cell, cell_for, decode_specs, gp_cells, gp_input_specs,
    input_specs,
)
from repro.launch.steps import (
    init_train_state, make_decode_step, make_gp_predict_setup,
    make_gp_train_step, make_prefill_step, make_train_step,
    train_state_shardings,
)
from repro.models import get_arch, init_params as lm_init_params, list_archs
from repro.models.sharding import (
    batch_shardings, decode_state_shardings, logits_sharding, param_shardings,
    token_sharding,
)

LM_ARCHS = tuple(a for a in list_archs() if a != "gp-exact-1m")


def _mem_summary(compiled) -> dict:
    try:
        m = compiled.memory_analysis()
        return {
            "argument_bytes": int(getattr(m, "argument_size_in_bytes", 0)),
            "output_bytes": int(getattr(m, "output_size_in_bytes", 0)),
            "temp_bytes": int(getattr(m, "temp_size_in_bytes", 0)),
            "generated_code_bytes": int(getattr(m, "generated_code_size_in_bytes", 0)),
        }
    except Exception as e:  # some backends lack memory_analysis
        return {"error": str(e)}


def _raw_counts(compiled) -> dict:
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    coll = rl.collective_bytes(compiled.as_text())
    return {
        "flops": float(cost.get("flops", 0.0) or 0.0),
        "bytes": float(cost.get("bytes accessed", 0.0) or 0.0),
        "transcendentals": float(cost.get("transcendentals", 0.0) or 0.0),
        "coll": coll,
    }


def _extrapolate(a: dict, b: dict, depth: int) -> dict:
    """total = A + (depth - 1) * max(B - A, 0), per counter."""
    def ext(x, y):
        return x + (depth - 1) * max(y - x, 0.0)

    coll = {k: ext(a["coll"][k], b["coll"][k])
            for k in a["coll"] if k not in ("counts",)}
    coll["counts"] = {k: int(_e) for k, _e in
                      ((kk, ext(a["coll"]["counts"][kk],
                                b["coll"]["counts"][kk]))
                       for kk in a["coll"]["counts"])}
    return {
        "flops": ext(a["flops"], b["flops"]),
        "bytes": ext(a["bytes"], b["bytes"]),
        "transcendentals": ext(a["transcendentals"], b["transcendentals"]),
        "coll": coll,
    }


def _two_pass(build_lowered, cfg, cell, n_devices: int, depth: int) -> dict:
    t0 = time.time()
    os.environ["REPRO_LAYER_UNROLL"] = "1"
    compiled_a = build_lowered().compile()
    raw_a = _raw_counts(compiled_a)
    mem = _mem_summary(compiled_a)
    t_a = time.time() - t0

    os.environ["REPRO_LAYER_UNROLL"] = "2"
    try:
        compiled_b = build_lowered().compile()
        raw_b = _raw_counts(compiled_b)
    finally:
        os.environ["REPRO_LAYER_UNROLL"] = "1"
    t_b = time.time() - t0 - t_a

    total = _extrapolate(raw_a, raw_b, depth)
    cost = {"flops": total["flops"], "bytes accessed": total["bytes"],
            "transcendentals": total["transcendentals"]}
    mf = rl.model_flops_for(cfg, cell)
    # GP cells: charge the operator's matmul dtype (fp32 default, bf16 on
    # the mixed-precision path); LM cells train in bf16
    cdt = getattr(cfg, "compute_dtype", "bf16") or "float32"
    roof = rl.analyze(cost, total["coll"], mf, n_devices, compute_dtype=cdt)
    return {
        "cost": cost,
        "collectives": total["coll"],
        "memory": mem,
        "roofline": roof._asdict(),
        "raw_pass_a": {k: raw_a[k] for k in ("flops", "bytes")},
        "raw_pass_b": {k: raw_b[k] for k in ("flops", "bytes")},
        "depth": depth,
        "compile_s": round(t_a + t_b, 1),
    }


def run_lm_cell(arch_id: str, shape_name: str, mesh, *, lr=3e-4,
                overrides: dict | None = None) -> dict:
    cfg = get_arch(arch_id)
    if overrides:
        cfg = cfg._replace(**overrides)
    cell = cell_for(cfg, shape_name)
    if cell.skip:
        return {"cell": cell._asdict(), "status": "skipped", "reason": cell.skip}
    n_devices = mesh.devices.size
    dp = ("pod", "data") if "pod" in mesh.axis_names else "data"

    if cell.kind == "train":
        def build():
            step = make_train_step(cfg, mesh, lr=lr)
            state_specs = jax.eval_shape(
                lambda: init_train_state(cfg, jax.random.PRNGKey(0)))
            st_sh = train_state_shardings(mesh, state_specs)
            batch = input_specs(cfg, cell)
            b_sh = batch_shardings(mesh, batch)
            metrics_specs = jax.eval_shape(step, state_specs, batch)[1]
            m_sh = jax.tree.map(lambda _: NamedSharding(mesh, P()), metrics_specs)
            fn = jax.jit(step, in_shardings=(st_sh, b_sh),
                         out_shardings=(st_sh, m_sh), donate_argnums=0)
            return fn.lower(state_specs, batch)
    elif cell.kind == "prefill":
        def build():
            step = make_prefill_step(cfg, mesh)
            params_specs = jax.eval_shape(
                lambda: lm_init_params(cfg, jax.random.PRNGKey(0)))
            p_sh = param_shardings(mesh, params_specs)
            state_specs, _ = decode_specs(cfg, cell)
            s_sh = decode_state_shardings(mesh, state_specs)
            batch = input_specs(cfg, cell)
            b_sh = batch_shardings(mesh, batch)
            o_sh = (s_sh, logits_sharding(mesh, cell.batch, cfg.vocab))
            fn = jax.jit(step, in_shardings=(p_sh, s_sh, b_sh),
                         out_shardings=o_sh, donate_argnums=1)
            return fn.lower(params_specs, state_specs, batch)
    elif cell.kind == "decode":
        def build():
            step = make_decode_step(cfg, mesh)
            params_specs = jax.eval_shape(
                lambda: lm_init_params(cfg, jax.random.PRNGKey(0)))
            p_sh = param_shardings(mesh, params_specs)
            state_specs, tok_specs = decode_specs(cfg, cell)
            s_sh = decode_state_shardings(mesh, state_specs)
            t_sh = token_sharding(mesh, cell.batch)
            l_sh = logits_sharding(mesh, cell.batch, cfg.vocab)
            fn = jax.jit(step, in_shardings=(p_sh, s_sh, t_sh),
                         out_shardings=(s_sh, l_sh), donate_argnums=1)
            return fn.lower(params_specs, state_specs, tok_specs)
    else:
        raise ValueError(cell.kind)

    depth = cfg.n_layers
    res = _two_pass(build, cfg, cell, n_devices, depth)
    res.update({"cell": cell._asdict(), "status": "ok",
                "n_devices": n_devices})
    return res


def run_gp_cell(kind: str, mesh, pcg_method="standard", mode=None,
                backend=None, compute_dtype=None, overlap=False) -> dict:
    from repro.configs.gp_exact_1m import CONFIG
    GP = CONFIG if mode is None else CONFIG._replace(mode=mode)
    if overlap:
        GP = GP._replace(overlap=True)
    if backend == "pallas":
        # Off-TPU the Pallas kernel runs in interpret mode, so the
        # compiled artifact would be the interpreter's emulation HLO —
        # cost_analysis would report the emulation's flops/bytes (every
        # kernel tile materialized), describing neither the fused kernel's
        # compute nor its HBM traffic. Refuse rather than dump bogus cells;
        # run this on real TPU hosts where the kernel actually lowers.
        raise ValueError(
            "--gp-backend pallas is only meaningful on a TPU host: the "
            "CPU dry-run would measure the Pallas interpreter, not the "
            "fused kernel (see repro.kernels.ops.resolve_interpret)")
    if backend is not None:
        GP = GP._replace(backend=backend)
    if compute_dtype is not None:
        GP = GP._replace(compute_dtype=compute_dtype)
    cell = [c for c in gp_cells(GP) if c.kind == kind][0]
    n_devices = mesh.devices.size
    xs = gp_input_specs(GP)
    from repro.core.kernels_math import init_params as gp_init
    gp_params = jax.eval_shape(lambda: gp_init(noise=0.5))

    if kind == "gp_train":
        def build():
            step, geom = make_gp_train_step(GP, mesh, pcg_method=pcg_method)
            stepc = jax.ShapeDtypeStruct((), jnp.int32)
            key = jax.ShapeDtypeStruct((2,), jnp.uint32)
            vec_sh = NamedSharding(mesh, geom.vector_pspec())
            rep = NamedSharding(mesh, P())
            reps = jax.tree.map(lambda _: rep, gp_params)
            fn = jax.jit(step,
                         in_shardings=(rep, vec_sh, reps, reps, reps, rep, rep),
                         out_shardings=(rep, reps, reps, reps, rep))
            return fn.lower(xs["X"], xs["y"], gp_params, gp_params, gp_params,
                            stepc, key)
        depth = GP.train_cg_iters
    else:
        def build():
            solve, _ = make_gp_predict_setup(GP, mesh)
            return solve.lower(xs["X"], xs["y"], gp_params)
        depth = GP.pred_cg_iters

    res = _two_pass(build, GP, cell, n_devices, depth)
    res.update({"cell": cell._asdict(), "status": "ok",
                "n_devices": n_devices, "gp_mode": GP.mode,
                "pcg_method": pcg_method, "gp_backend": GP.backend,
                "gp_overlap": GP.overlap,
                "gp_compute_dtype": GP.compute_dtype or "float32"})
    return res


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all",
                    help="arch id, comma list, or 'all'")
    ap.add_argument("--cells", default="all",
                    help="shape names, comma list, or 'all'")
    ap.add_argument("--multi-pod", action="store_true",
                    help="use the 2x16x16 (pod,data,model) mesh")
    ap.add_argument("--gp-mode", default=None, choices=("1d", "2d"))
    ap.add_argument("--pcg-method", default="standard",
                    choices=("standard", "pipelined"))
    ap.add_argument("--gp-backend", default=None,
                    choices=("partitioned", "pallas"))
    ap.add_argument("--gp-dtype", default=None, choices=("bfloat16",))
    ap.add_argument("--gp-overlap", action="store_true",
                    help="ring-pipelined chunked contraction (overlap the "
                         "gather with tile compute)")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--tag", default="", help="suffix for output filenames")
    ap.add_argument("--override", default="",
                    help="ArchConfig overrides, e.g. 'remat=False,ce_chunk=1024'")
    args = ap.parse_args()

    overrides = {}
    for kv in filter(None, args.override.split(",")):
        k, v = kv.split("=")
        overrides[k] = eval(v)  # ints/bools/tuples from trusted CLI

    mesh = make_production_mesh(multi_pod=args.multi_pod)
    mesh_name = "2x16x16" if args.multi_pod else "16x16"
    print(f"[dryrun] mesh {mesh_name}: {mesh.devices.size} devices "
          f"{dict(zip(mesh.axis_names, mesh.devices.shape))}", flush=True)
    os.makedirs(args.out, exist_ok=True)

    archs = LM_ARCHS if args.arch == "all" else tuple(args.arch.split(","))
    shapes = tuple(SHAPES) if args.cells == "all" else tuple(args.cells.split(","))

    results = []
    for arch in archs:
        if arch == "gp-exact-1m":
            for kind in ("gp_train", "gp_predict"):
                tag = f"{arch}__{kind}__{mesh_name}{args.tag}"
                try:
                    r = run_gp_cell(kind, mesh, pcg_method=args.pcg_method,
                                    mode=args.gp_mode,
                                    backend=args.gp_backend,
                                    compute_dtype=args.gp_dtype,
                                    overlap=args.gp_overlap)
                except Exception:
                    r = {"cell": {"arch": arch, "shape": kind}, "status": "error",
                         "traceback": traceback.format_exc()}
                r["mesh"] = mesh_name
                _dump(args.out, tag, r)
                results.append(r)
            continue
        for shape in shapes:
            tag = f"{arch}__{shape}__{mesh_name}{args.tag}"
            try:
                r = run_lm_cell(arch, shape, mesh, overrides=overrides)
            except Exception:
                r = {"cell": {"arch": arch, "shape": shape}, "status": "error",
                     "traceback": traceback.format_exc()}
            r["mesh"] = mesh_name
            _dump(args.out, tag, r)
            results.append(r)

    ok = sum(1 for r in results if r["status"] == "ok")
    skip = sum(1 for r in results if r["status"] == "skipped")
    err = sum(1 for r in results if r["status"] == "error")
    print(f"[dryrun] done: {ok} ok, {skip} skipped, {err} errors")
    if err:
        for r in results:
            if r["status"] == "error":
                print(f"  ERROR {r['cell']['arch']} {r['cell'].get('shape')}")
        raise SystemExit(1)


def _dump(out_dir, tag, result):
    path = os.path.join(out_dir, tag + ".json")
    with open(path, "w") as f:
        json.dump(result, f, indent=1, default=str)
    st = result["status"]
    extra = ""
    if st == "ok":
        ro = result["roofline"]
        extra = (f" compile={result['compile_s']}s flops={ro['flops']:.2e} "
                 f"coll={ro['coll_bytes']:.2e} bott={ro['bottleneck']} "
                 f"useful={ro['useful_ratio']:.2f}")
    print(f"[dryrun] {tag}: {st}{extra}", flush=True)


if __name__ == "__main__":
    main()
