#!/usr/bin/env python3
"""Run one cell of the planning service's benchmark once, on this machine.

    python3 bench/run.py --workload paper-table6-20.plan --seed 7 \
        --seconds 30 --trace 0

The cell, its configuration, traffic and metrics are named in
`BENCHMARK.json` at the checkout's root.  The run sets up the cell
(instance, deployment, warm-up on the cell's own shapes), runs a closed
loop of service calls for `--seconds` seconds, checks the answers
against the plain references under `bench/lib/`, and prints one JSON
object as the last line of standard output: the end-to-end metrics with
``--trace 0``, the per-layer metrics (from a profiler trace of the
window) with ``--trace 1``.  The numbers compared for `correct` are the
last lines of standard error.

It exits non-zero, with no result, when JAX finds no TPU or fewer chips
than the cell asks for, or when the program (``src/repro``) is not in
the checkout.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be >= 0")
    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: the program is not in this checkout "
              f"({ROOT / 'src' / 'repro'} is missing)", file=sys.stderr)
        return 2
    sys.path[0] = str(ROOT)
    sys.path.insert(1, str(ROOT / "src"))
    from bench.lib import harness
    try:
        work = harness.Workload.find(args.workload)
        result = harness.run(work, args.seed, args.seconds,
                             bool(args.trace), T_PROCESS)
    except harness.NoDevice as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
