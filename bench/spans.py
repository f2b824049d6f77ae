#!/usr/bin/env python3
"""Run one cell traced and charge its time to the program's own spans.

    python3 bench/spans.py --workload paper-table6-20.plan --seed 7

The run is that of ``bench/run.py --trace 1``: the same set-up, the same
traced window of the traffic's ``trace_calls`` calls, the same checks and
result line.  It also keeps the trace's events and adds a ``spans``
object to the line: each program span's self milliseconds and count per
call, the layer metrics of `bench.lib.spantime.LAYERS`, the idle device
time by the program step the host was in, the share of it that a named
step (not a call's root span) holds, and the mean per call of each
whole-number counter in the answers' diagnostics.  The line is the last of
standard output and is also written to
``chiprun_out/spans/<workload>.<seed>.json``.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
OUT = ROOT / "chiprun_out" / "spans"


def traced_run(work, seed: int, seconds: float, t_process: float) -> dict:
    """`harness.run` with a trace, plus the ``spans`` object.  The harness
    keeps neither the trace's events nor the call records; they are
    caught on their way through `tracereduce.reduce_events` and
    `harness.read_metrics`."""
    from bench.lib import harness, spantime, tracereduce
    kept: list = []
    runs: list = []
    reduce_events, read_metrics = (tracereduce.reduce_events,
                                   harness.read_metrics)

    def keep(events, *args, **kwargs):
        kept.append(events)
        return reduce_events(events, *args, **kwargs)

    def keep_run(work, run, per_layer):
        runs.append(run)
        return read_metrics(work, run, per_layer)

    tracereduce.reduce_events, harness.read_metrics = keep, keep_run
    try:
        result = harness.run(work, seed, seconds, True, t_process)
    finally:
        tracereduce.reduce_events, harness.read_metrics = (reduce_events,
                                                           read_metrics)
    call = work.traffic["span"]
    times = spantime.span_times(kept[-1], call)
    n = times.calls.get(call, 0)
    result["spans"] = {
        "calls": n,
        "traced_s_per_call": result["device"]["window_s"] / n if n else None,
        "spans_per_call": sum(v for k, v in times.calls.items()
                              if spantime.is_program_span(k)) / max(n, 1),
        "layers_ms": {m: spantime.self_ms_per_call(times, n, inc, exc)
                      for m, (inc, exc) in spantime.LAYERS.items()},
        "self_ms": {k: v * 1e3 / max(n, 1) for k, v in sorted(
            times.self_s.items(), key=lambda kv: -kv[1])},
        "count": {k: v / max(n, 1) for k, v in sorted(times.calls.items())},
        "idle_s": times.idle_s,
        "idle_by_step_s": dict(sorted(times.idle_by_step.items(),
                                      key=lambda kv: -kv[1])),
        "named_idle_share": times.named_idle_share,
        "counters": _counters(runs[-1].records),
    }
    return result


def _counters(records) -> dict:
    """Mean per completed call of each whole-number counter the program
    reports in its answers' diagnostics."""
    sums: dict = {}
    for rec in records:
        for k, v in rec.answer.get("diagnostics", {}).items():
            if isinstance(v, int) and not isinstance(v, bool):
                sums[k] = sums.get(k, 0) + v
    return {k: v / len(records) for k, v in sorted(sums.items())}


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    args = ap.parse_args(argv)
    sys.path[0] = str(ROOT)
    sys.path.insert(1, str(ROOT / "src"))
    from bench.lib import harness
    try:
        work = harness.Workload.find(args.workload)
        result = traced_run(work, args.seed, args.seconds, T_PROCESS)
    except harness.NoDevice as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    line = json.dumps(result)
    OUT.mkdir(parents=True, exist_ok=True)
    (OUT / f"{args.workload}.{args.seed}.json").write_text(line + "\n")
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
