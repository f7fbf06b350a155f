"""Run one cell of the chip benchmark once.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell is an entry of `workloads` in BENCHMARK.json. Its configuration
is the file that BENCHMARK.json names, its traffic is
`chipbench/traffic/<traffic>.json`, whose `kind` selects the generator
`chipbench/kinds/<kind>.py`, and its correctness limits are
`chipbench/limits/<cell>.json`. With `--trace 0` the run prints the
cell's end-to-end metrics; with `--trace 1` it profiles the window and
prints the cell's per-layer metrics, each from its reader
`chipbench/layer_metrics/<metric>.py`.

It needs a TPU with at least as many chips as the cell asks for, and
exits 2 with no result otherwise. Progress goes to earlier lines; the
last line of standard output is one JSON object, and the numbers compared
for `correct` are also the last lines of standard error.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
SRC = os.path.join(ROOT, "src")
if SRC not in sys.path:
    sys.path.insert(0, SRC)

from chipbench.common import (Context, CompileClock, is_correct,  # noqa: E402
                              load_json, log)

TRACE_DIR = os.path.join(ROOT, ".chipbench", "trace")


def load_cell(name: str) -> tuple[dict, dict, dict, dict, dict]:
    """(benchmark, cell, config, traffic, limits) of the named cell."""
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; one of {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(os.path.join(ROOT, configs[cell["config"]]["file"]))
    traffic = load_json(os.path.join(HERE, "traffic",
                                     cell["traffic"] + ".json"))
    limits = load_json(os.path.join(HERE, "limits", name + ".json"))
    return bench, cell, config, traffic, limits


def metrics_of(bench: dict, cell: str, section: str) -> list[dict]:
    """The metrics of a section that this cell reports."""
    return [m for m in bench[section]
            if "workloads" not in m or cell in m["workloads"]]


def chips_or_exit(chips: int) -> list:
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"chipbench: the cell needs {chips} TPU chip(s); JAX reports "
              f"{len(devices)} device(s) on platform "
              f"{devices[0].platform!r}", file=sys.stderr)
        raise SystemExit(2)
    return devices[:chips]


def setup_jax() -> str:
    import jax

    from repro.launch.runtime import setup_runtime

    cache = setup_runtime()
    # every program, however quick to compile, comes from the cache in a
    # later run, so that set-up repeats the same work each time
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache


def layer_metrics(bench, cell_name, ctx, out, trace) -> dict:
    metrics = {}
    for m in metrics_of(bench, cell_name, "per_layer"):
        path = os.path.join(HERE, "layer_metrics", m["name"] + ".py")
        spec = importlib.util.spec_from_file_location(
            "chipbench_metric_" + m["name"].replace(".", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        value = mod.read(trace, ctx, out["layer_ctx"])
        if value is None:
            log(f"[trace] {m['name']}: nothing to read")
            continue
        metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        log(f"[trace] {m['name']} = {value!r} {m['unit']}")
    return metrics


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default="",
                    help="directory to keep the traced window's profile in "
                         "(by default it is deleted once reduced)")
    args = ap.parse_args(argv)
    if args.seed < 0:
        raise SystemExit("--seed must be >= 0")

    bench, cell, config, traffic, limits = load_cell(args.workload)
    devices = chips_or_exit(int(cell["chips"]))
    cache = setup_jax()

    import jax

    dev = devices[0]
    log(f"[env] jax={jax.__version__} device_kind={dev.device_kind!r} "
        f"count={len(jax.devices())} using={len(devices)} cache={cache}")
    log(f"[cell] {cell['name']}: config={cell['config']} "
        f"traffic={cell['traffic']} seed={args.seed} seconds={args.seconds}")
    trace_dir = args.keep_trace or TRACE_DIR
    if args.trace and os.path.isdir(trace_dir):
        shutil.rmtree(trace_dir)
    ctx = Context(cell, config, traffic, limits, args.seed, args.seconds,
                  bool(args.trace), T_START, devices, trace_dir)
    clock = CompileClock()
    kind = importlib.import_module(f"chipbench.kinds.{traffic['kind']}")
    out = kind.run(ctx, clock)

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": out["memory_peak_bytes"]}
    result = {"correct": False, "attempted": out["attempted"],
              "failed": out["failed"]}
    if args.trace:
        from chipbench import trace_reduce

        trace = trace_reduce.load(out["profile"])
        metrics = layer_metrics(bench, cell["name"], ctx, out, trace)
        device["busy_s"] = trace_reduce.busy_s(trace)
        device["window_s"] = trace_reduce.window_s(trace)
        result["breakdown"] = trace_reduce.breakdown(trace)
        log(f"[trace] busy_s={device['busy_s']!r} "
            f"window_s={device['window_s']!r}")
        if not args.keep_trace:
            shutil.rmtree(out["profile"], ignore_errors=True)
    else:
        metrics = {}
        for m in metrics_of(bench, cell["name"], "end_to_end"):
            metrics[m["name"]] = {"value": float(out["e2e"][m["name"]]),
                                  "unit": m["unit"]}
    checks = out["checks"]
    result["correct"] = is_correct(out)
    result["metrics"] = metrics
    result["device"] = device
    result["checks"] = checks.as_dict()
    checks.print_stderr()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
