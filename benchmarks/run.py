"""Benchmark harness entry point — one section per paper table/figure plus
the roofline summary.

Prints ``name,us_per_call,derived`` CSV lines (harness contract).

    PYTHONPATH=src python -m benchmarks.run            # standard pass
    PYTHONPATH=src python -m benchmarks.run --quick    # fastest smoke
    PYTHONPATH=src python -m benchmarks.run --full     # paper-scale (slow)
"""
from __future__ import annotations

import argparse
import os
import sys
import time


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset: table2,table3,...")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also dump every section's row dicts as one JSON "
                         "file (CI uploads it as a workflow artifact)")
    args = ap.parse_args(argv)

    from repro.runtime import use_compile_cache
    use_compile_cache()

    S = 30 if args.quick else (500 if args.full else 120)
    trials = 1 if args.quick else (30 if args.full else 3)
    windows = 48 if args.quick else (288 if args.full else 96)

    from . import (allocator_scaling, extensions, failure_replay, figs,
                   risk_scaling, serve_closed_loop, stage2_scaling, table2,
                   table3, table4, table5, table6)

    sections = {
        "table2": lambda: table2.run(S=S, include_dm=False),
        "table3": lambda: table3.run(),
        "table4": lambda: table4.run(trials=trials, n_windows=windows,
                                     dm_limit=120.0 if not args.full else 600.0,
                                     replan_every=4 if not args.full else 1),
        "table5": lambda: table5.run(n_windows=windows,
                                     dm_limit=60.0 if not args.full else 120.0,
                                     include_baselines=not args.quick,
                                     replan_every=4 if not args.full else 1),
        "table6": lambda: table6.run(
            dm_limit=120.0 if not args.full else 600.0,
            dm_max_size=1000 if not args.full else 10**9,
            sizes=(table6.SIZES[:3] if args.quick
                   else (table6.SIZES_EXT if args.full else table6.SIZES))),
        "allocator_scaling": lambda: allocator_scaling.run(
            sizes=(allocator_scaling.QUICK_SIZES if args.quick
                   else (allocator_scaling.SIZES_XL if args.full
                         else allocator_scaling.SIZES))),
        "stage2_scaling": lambda: stage2_scaling.run(
            quick=args.quick, S=(500 if args.full else 120)),
        "risk_scaling": lambda: risk_scaling.run(quick=args.quick,
                                                 full=args.full),
        "failure_replay": lambda: failure_replay.run(quick=args.quick),
        "serve_closed_loop": lambda: serve_closed_loop.run(quick=args.quick),
        "figs": lambda: figs.run(S=max(20, S // 4)),
        "extensions": extensions.run,
        "roofline": _roofline_summary,
    }
    only = set(args.only.split(",")) if args.only else None
    t0 = time.time()
    collected: dict[str, object] = {}
    for name, fn in sections.items():
        if only and name not in only:
            continue
        print(f"# === {name} ===", flush=True)
        try:
            rows = fn()
            if args.json and isinstance(rows, list):
                collected[name] = rows
        except Exception as e:  # keep the harness running
            print(f"{name},0,ERROR:{type(e).__name__}:{e}", flush=True)
            if args.json:
                collected[name] = {"error": f"{type(e).__name__}: {e}"}
    if args.json:
        import json

        from .common import JSON_SCHEMA_VERSION, ensure_outdir, git_sha

        ensure_outdir(args.json)
        payload = {
            "schema_version": JSON_SCHEMA_VERSION,
            "git_sha": git_sha(),
            "sections": collected,
        }
        with open(args.json, "w") as fh:
            json.dump(payload, fh, indent=2, default=str)
        print(f"# wrote {args.json}", flush=True)
    print(f"# benchmarks done in {time.time()-t0:.0f}s", flush=True)
    return 0


def _roofline_summary() -> None:
    """Per (arch x shape x mesh) roofline rows from the dry-run artifact."""
    import json

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "experiments", "dryrun_results.json")
    if not os.path.exists(path):
        print("roofline,0,missing-dryrun-artifact", flush=True)
        return
    from repro.analysis.roofline import analyze_row
    rows = json.load(open(path))
    for r in rows:
        a = analyze_row(r)
        if a is None:
            continue
        print(f"roofline.{a['arch']}.{a['shape']}.{a['mesh']},0,"
              f"compute={a['compute_s']:.3e};memory={a['memory_s']:.3e};"
              f"collective={a['collective_s']:.3e};dom={a['dominant']};"
              f"useful={a['useful_ratio']:.3f}", flush=True)


if __name__ == "__main__":
    sys.exit(main())
