#!/usr/bin/env python3
"""Readings that set the limits of a cell's correctness checks.

    python3 bench/control.py --workload paper-table6-20.plan \
        --seeds 101-112 --control-seeds 3 --seconds 10

Sets the cell up once, then runs one short window per seed at the cell's
own load and prints, per seed, each number the check compares: as the
program's answers give it (its lower reading) and, for the first
`--control-seeds` seeds, with the control in the program's place (the
plain reference computed in float32, the precision below the float64
the configuration states).  A limit sits above every sound reading and
below every control reading.  The benchmark's own runs never run the
control.
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def seeds_arg(text: str) -> list[int]:
    out: list[int] = []
    for part in text.split(","):
        a, _, b = part.partition("-")
        out.extend(range(int(a), int(b or a) + 1))
    return out


def main(argv: list[str] | None = None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seeds_arg, required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path[0] = str(ROOT)
    sys.path.insert(1, str(ROOT / "src"))
    from bench.lib import harness
    work = harness.Workload.find(args.workload)
    harness.use_checkout_cache()
    try:
        harness.accelerator(work.chips)
    except harness.NoDevice as e:
        print(f"control: {e}", file=sys.stderr)
        return 3
    cell = harness.new_cell(work)
    for k, seed in enumerate(args.seeds):
        cell.start(seed)
        records, window_s, _, failed = harness.run_window(
            cell, args.seconds, int(work.traffic.get("min_calls", 1)),
            work.traffic["span"])
        line = {"seed": seed, "calls": len(records), "failed": failed,
                "window_s": window_s,
                "program": {c.name: c.value
                            for c in cell.check(records, seed)}}
        if k < args.control_seeds:
            line["control"] = {c.name: c.value for c in
                               cell.check(records, seed, control=True)}
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
