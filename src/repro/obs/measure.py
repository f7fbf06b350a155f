"""Measured-vs-modeled cost accounting: the measurement plane.

Everything `repro.obs.costmodel` reports is napkin math — a consistent
ruler, but not evidence. This module is the other half: *measured*
numbers from the same phases the model prices, and the machinery to set
the two against each other so EXPERIMENTS can cite real ratios instead of
extrapolations (the paper's Table 2 is measured wall clock; ours must be
too).

Three pieces:

* **Per-phase measured timing** rides the training engine's phased
  dispatch (`repro.train.solver_state._dispatch_phased`, tracing mode):
  each of the four separately-jitted phase fns (precond_build / cg_solve /
  slq_logdet / eq2_backward) is fenced with `block_until_ready` and its
  span carries `measured_ms` + the phase's modeled HBM bytes
  (`costmodel.mll_phase_costs`) + the backend. `phase_model_comparison`
  aggregates those spans per (backend, phase) into a measured-vs-modeled
  table — `launch/obs_report --compare-model`.
* **Modeled-ms conversion**: modeled bytes become modeled milliseconds
  through the HBM bandwidth of a device kind (`--device-kind`, looked up
  in `repro.obs.peaks`; an unlisted kind is an error). The measured/modeled RATIO is
  the honest quantity: ~1 means the byte model explains the time; >> 1
  means launch overhead / host sync dominates (expected on CPU emulation);
  << 1 means the model overcharges (e.g. cached slabs).
* **Timed-collective micro-harness**: `collective_microbench` times the
  2-D mesh's two primitives — one `ppermute` ring hop and the closing
  `psum_scatter` — against `costmodel.dist_collective_cost`'s byte
  volumes, yielding achieved GB/s per collective. Degrades to an empty
  report on a single device (nothing to transfer).
"""

from __future__ import annotations

import time
from typing import Any

import numpy as np

from . import costmodel
from . import metrics as _metrics
from .peaks import V5E, peaks_for

# the four phase-span names the training engine emits (and the order the
# comparison table lists them in)
PHASE_SPANS = ("precond_build", "cg_solve", "slq_logdet", "eq2_backward")


def phase_model_comparison(spans: list[dict], *,
                           device_kind: str = V5E) -> list[dict]:
    """Aggregate phase spans into measured-vs-modeled rows.

    spans: trace events (`obs.report.load_trace`). Only spans carrying BOTH
    `measured_ms` and `modeled_hbm_bytes` in args participate (i.e. the
    engine's phased dispatch); everything else is ignored, so the function
    is safe on any trace. Returns one row per (backend, phase), ordered by
    backend then PHASE_SPANS order.
    """
    groups: dict[tuple, dict] = {}
    for ev in spans:
        args = ev.get("args") or {}
        if "measured_ms" not in args or "modeled_hbm_bytes" not in args:
            continue
        key = (str(args.get("backend", "?")), ev.get("name", "?"))
        g = groups.setdefault(key, {"steps": 0, "measured_ms": 0.0,
                                    "modeled_hbm_bytes": 0.0,
                                    "modeled_launches": 0})
        g["steps"] += 1
        g["measured_ms"] += float(args["measured_ms"])
        g["modeled_hbm_bytes"] += float(args["modeled_hbm_bytes"])
        g["modeled_launches"] += int(args.get("modeled_launches", 0))

    def order(key):
        backend, phase = key
        try:
            pi = PHASE_SPANS.index(phase)
        except ValueError:
            pi = len(PHASE_SPANS)
        return (backend, pi, phase)

    hbm_bytes_per_s = peaks_for(device_kind).hbm_bytes_per_s
    rows = []
    for key in sorted(groups, key=order):
        backend, phase = key
        g = groups[key]
        modeled_ms = g["modeled_hbm_bytes"] / hbm_bytes_per_s * 1e3
        rows.append({
            "backend": backend,
            "phase": phase,
            "steps": g["steps"],
            "measured_ms": g["measured_ms"],
            "modeled_gb": g["modeled_hbm_bytes"] / 1e9,
            "modeled_ms": modeled_ms,
            "modeled_launches": g["modeled_launches"],
            "ratio": (g["measured_ms"] / modeled_ms) if modeled_ms > 0
                     else float("nan"),
        })
    return rows


def format_model_comparison(rows: list[dict], *,
                            device_kind: str = V5E) -> str:
    """Render the measured-vs-modeled table (obs_report --compare-model)."""
    gbps = peaks_for(device_kind).hbm_bytes_per_s / 1e9
    lines = [f"measured vs modeled (HBM bandwidth of {device_kind}: "
             f"{gbps:g} GB/s)",
             f"{'backend':<12} {'phase':<14} {'steps':>5} "
             f"{'measured_ms':>12} {'modeled_ms':>11} {'modeled_GB':>11} "
             f"{'ratio':>8}"]
    if not rows:
        lines.append("  (no phase spans with modeled costs in this trace — "
                     "run a traced fit)")
        return "\n".join(lines)
    for r in rows:
        ratio = f"{r['ratio']:8.2f}" if np.isfinite(r["ratio"]) else \
            f"{'-':>8}"
        lines.append(
            f"{r['backend']:<12} {r['phase']:<14} {r['steps']:>5} "
            f"{r['measured_ms']:>12.2f} {r['modeled_ms']:>11.3f} "
            f"{r['modeled_gb']:>11.4f} {ratio}")
    lines.append(
        "ratio = measured / modeled: ~1 bandwidth-bound as modeled; "
        ">>1 launch/sync overhead dominates (expected on CPU emulation); "
        "<<1 the model overcharges.")
    return "\n".join(lines)


def collective_microbench(mesh=None, geom=None, *, num_rhs: int = 8,
                          reps: int = 10, dtype=None) -> list[dict]:
    """Time the distributed engine's collectives against the byte model.

    mesh/geom: a `jax.sharding.Mesh` + `core.distributed.DistGeometry`;
    None builds a mesh over all local devices (2-D when the device count
    factors, 1-D otherwise) at a small default n. Each primitive runs once
    for warmup, then `reps` fenced repetitions; achieved GB/s uses the
    SAME per-device byte volume `dist_collective_cost` charges, so the
    measured bandwidth and the model's exposed-byte estimates are directly
    comparable. Returns [] when no collective exists (single device).
    """
    import jax
    import jax.numpy as jnp
    from repro.core.distributed import collective_bench_fns, make_geometry

    if mesh is None:
        devs = np.asarray(jax.devices())
        if devs.size == 1:
            return []
        from jax.sharding import Mesh
        # favor a 2-D (rows x cols) split so BOTH collectives get measured
        d_col = 1
        for c in (2, 4, 8):
            if devs.size % c == 0 and devs.size // c >= 2:
                d_col = c
        if d_col > 1:
            mesh = Mesh(devs.reshape(devs.size // d_col, d_col),
                        ("data", "model"))
        else:
            mesh = Mesh(devs, ("data",))
    if geom is None:
        n = 4096 * int(np.prod(mesh.devices.shape))
        geom = make_geometry(
            mesh, n, 8,
            mode="2d" if "model" in mesh.axis_names else "1d")

    fns = collective_bench_fns(mesh, geom)
    if not fns:
        return []
    if dtype is None:
        dtype = jnp.float32
    v = jnp.ones((geom.n_padded, num_rhs), dtype)
    itemsize = jnp.dtype(dtype).itemsize
    cost = costmodel.dist_collective_cost(
        geom.n, num_rhs, d_row=int(np.prod(geom.row_sizes)),
        d_col=geom.d_col, dtype_bytes=itemsize)
    # per-device bytes moved by ONE invocation of each primitive
    chunk = geom.n_local * num_rhs * itemsize
    bytes_per = {"ppermute_ring": float(chunk),
                 "psum_scatter": float(cost.scatter_bytes)}

    rows = []
    for name, fn in fns.items():
        out = fn(v)
        jax.block_until_ready(out)  # compile + warm
        t0 = time.perf_counter()
        for _ in range(reps):
            out = fn(v)
        jax.block_until_ready(out)
        ms = (time.perf_counter() - t0) * 1e3 / reps
        nbytes = bytes_per.get(name, float(chunk))
        gbps = nbytes / 1e9 / (ms / 1e3) if ms > 0 else float("nan")
        _metrics.gauge(f"collective.{name}.ms").set(ms)
        _metrics.gauge(f"collective.{name}.gbps").set(gbps)
        rows.append({"collective": name, "reps": reps, "ms_per_op": ms,
                     "bytes_per_device": nbytes, "achieved_gbps": gbps,
                     "devices": int(np.prod(mesh.devices.shape))})
    return rows


def format_collective_bench(rows: list[dict]) -> str:
    if not rows:
        return ("collectives: single device — nothing to measure "
                "(run under a multi-device mesh)")
    lines = [f"{'collective':<16} {'devices':>7} {'ms/op':>9} "
             f"{'KB/device':>10} {'achieved_GB/s':>13}"]
    for r in rows:
        lines.append(
            f"{r['collective']:<16} {r['devices']:>7} "
            f"{r['ms_per_op']:>9.3f} {r['bytes_per_device'] / 1e3:>10.1f} "
            f"{r['achieved_gbps']:>13.3f}")
    return "\n".join(lines)


def phase_histogram_summary(reg: Any | None = None) -> dict:
    """The registry's measured per-phase ms histograms (`phase.<name>_ms`),
    keyed by phase — the no-trace-file view of the same measurements."""
    r = reg if reg is not None else _metrics.registry()
    out = {}
    for phase in PHASE_SPANS:
        h = r.histogram(f"phase.{phase}_ms")
        if h.count:
            out[phase] = h.summary()
    return out
