"""obs_report — turn a span-trace JSONL into the paper-Table-2-style table.

    PYTHONPATH=src python -m repro.launch.obs_report trace.jsonl \
        [--root fit_exact_gp] [--compare-model] [--hbm-gbps 100] \
        [--health health.jsonl] [--json]

Input is what `repro.obs` tracing writes (REPRO_OBS_TRACE=trace.jsonl, or
`obs.trace_session(path)` around any entry point — e.g. `repro.launch.train
--obs-trace`). Output: the per-phase wall-clock breakdown (self-time
attribution, so phase rows partition the root span's duration exactly —
untracked host time appears as "(self)" rows, never silently), a
per-request serve section when the trace carries `req:<rid>` flows, plus
the metrics-registry snapshot the trace carries (CG iteration totals,
solver step modes, autotune hit/miss/sweep, serve distributions).

`--compare-model` adds the measurement plane's headline table: per
(backend, phase) measured wall ms set against the cost model's HBM-byte
prediction, converted to ms at `--hbm-gbps` (see `repro.obs.measure`).
`--health <jsonl>` summarizes a solver health-event log
(REPRO_OBS_HEALTH) alongside the trace.

The same JSONL loads in Perfetto / chrome://tracing after
`jq -s . trace.jsonl > trace.json` for a visual timeline.
"""

from __future__ import annotations

import argparse
import json

from repro.obs.health import load_health, summarize_health
from repro.obs.measure import format_model_comparison, phase_model_comparison
from repro.obs.peaks import V5E
from repro.obs.report import (
    assign_self_times,
    format_report,
    load_trace,
    phase_breakdown,
    request_breakdown,
    split_request_spans,
)


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="obs_report",
        description="Per-phase breakdown of a repro.obs trace JSONL")
    ap.add_argument("trace", help="trace JSONL written by repro.obs")
    ap.add_argument("--root", default="fit_exact_gp",
                    help="span name treated as the wall-clock root "
                         "(default: fit_exact_gp; falls back to the trace "
                         "extent when absent)")
    ap.add_argument("--compare-model", action="store_true",
                    help="append the measured-vs-modeled per-phase table "
                         "(needs a trace from a traced fit: the engine's "
                         "phased dispatch stamps measured_ms + modeled "
                         "bytes on each phase span)")
    ap.add_argument("--device-kind", default=V5E,
                    help="device kind (jax device_kind) whose published HBM "
                         "bandwidth converts modeled bytes to modeled ms; "
                         "must be listed in repro.obs.peaks "
                         "(default %(default)r)")
    ap.add_argument("--health", default=None,
                    help="solver health-event JSONL (REPRO_OBS_HEALTH) to "
                         "summarize alongside the trace")
    ap.add_argument("--json", action="store_true",
                    help="emit the breakdown as JSON instead of markdown")
    args = ap.parse_args(argv)

    events, metrics = load_trace(args.trace)
    spans = assign_self_times(events)
    phase_spans, req_spans = split_request_spans(spans)

    if args.json:
        rows, wall = phase_breakdown(phase_spans, root=args.root)
        payload = {
            "trace": args.trace,
            "wall_ms": wall,
            "phases": [r._asdict() for r in rows],
            "requests": request_breakdown(req_spans),
            "metrics": metrics,
        }
        if args.compare_model:
            payload["model_comparison"] = phase_model_comparison(
                events, device_kind=args.device_kind)
        if args.health:
            payload["health"] = summarize_health(load_health(args.health))
        print(json.dumps(payload, indent=1))
        return

    print(format_report(args.trace, root=args.root))
    if args.compare_model:
        rows = phase_model_comparison(events, device_kind=args.device_kind)
        print("\n## Measured vs modeled\n")
        print(format_model_comparison(rows, device_kind=args.device_kind))
    if args.health:
        summary = summarize_health(load_health(args.health))
        print("\n## Solver health\n")
        if not summary:
            print("(no health events)")
        for kind, info in sorted(summary.items()):
            print(f"- {kind}: {info['count']} event(s), worst severity "
                  f"{info['severity']}; last: {info['last']}")


if __name__ == "__main__":
    main()
