"""The benchmark's harness: find a cell by name, set it up, run its
window, check its answers, and reduce its trace.

Everything that belongs to one configuration, traffic mix or metric is
a file of its own, found by the name `BENCHMARK.json` gives it:

- ``bench/configs/<config>.json``: the deployment and its guarantees;
- ``bench/traffic/<traffic>.json``: the mix's parameters, with ``kind``
  naming the driver in ``bench/kinds/<kind>.py``;
- ``bench/metrics/<metric>.py``: one reader per metric, end to end or
  per layer, with ``read(run) -> float | None``.

The window is a closed loop with one caller, as an operator uses the
service: call *i* starts when call *i-1* has returned its answer to the
host.  It ends at the first completion after ``--seconds`` at which at
least the traffic's ``min_calls`` calls have completed, so rates and
per-call times are whole work over the whole time.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"
TRACE_DIR = ROOT / "bench-out" / "trace"
CACHE_DIR = ROOT / ".jax_cache"

JAXPR_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class NoDevice(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def load_json(path: Path) -> dict:
    with open(path) as fh:
        return json.load(fh)


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Workload:
    """One cell of `BENCHMARK.json` with its files loaded."""
    name: str
    chips: int
    config: dict
    traffic: dict
    metrics: list[dict]             # end_to_end + per_layer entries

    @staticmethod
    def find(name: str, bench_file: Path = ROOT / "BENCHMARK.json"
             ) -> "Workload":
        bench = load_json(bench_file)
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r}; the benchmark has "
                           f"{', '.join(sorted(cells))}")
        w = cells[name]
        metrics = ([dict(m, per_layer=False) for m in bench["end_to_end"]]
                   + [dict(m, per_layer=True) for m in bench["per_layer"]])
        return Workload(
            name=name, chips=int(w["chips"]),
            config=load_json(BENCH / "configs" / f"{w['config']}.json"),
            traffic=load_json(BENCH / "traffic" / f"{w['traffic']}.json"),
            metrics=[m for m in metrics
                     if name in m.get("workloads", [name])])


@dataclasses.dataclass
class CallRecord:
    i: int                  # window call index
    t0: float               # host clock at call start
    t1: float               # host clock once the answer is on the host
    answer: dict            # what the kind keeps for checks and counters


@dataclasses.dataclass
class Check:
    """One number compared for `correct`; it passes while value <= limit
    (so a NaN never passes)."""
    name: str
    value: float
    limit: float

    def __post_init__(self):
        self.value = float(self.value)
        self.limit = float(self.limit)

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclasses.dataclass
class RunData:
    """What the metric readers read."""
    records: list[CallRecord]
    window_s: float
    setup_s: float
    traffic: dict
    config: dict
    trace: object | None = None     # tracereduce.TraceSummary


class CompileWatch:
    """Counts JAX traces, backend compiles and persistent-cache hits."""

    def __init__(self):
        import jax.monitoring as mon
        self.counts = {"traces": 0, "compiles": 0, "cache_hits": 0}
        self.seconds = {"traces": 0.0, "compiles": 0.0}
        self.armed = False

        def on_duration(event, duration, **_):
            key = {JAXPR_TRACE_EVENT: "traces",
                   BACKEND_COMPILE_EVENT: "compiles"}.get(event)
            if key and self.armed:
                self.counts[key] += 1
                self.seconds[key] += duration

        def on_event(event, **_):
            if event == CACHE_HIT_EVENT and self.armed:
                self.counts["cache_hits"] += 1

        mon.register_event_duration_secs_listener(on_duration)
        mon.register_event_listener(on_event)

    def report(self) -> dict:
        return {"window_traces": self.counts["traces"],
                "window_compiles": self.counts["compiles"],
                "window_cache_hits": self.counts["cache_hits"],
                "window_compile_s": self.seconds["compiles"],
                "window_trace_s": self.seconds["traces"]}


def use_checkout_cache() -> str:
    """Point JAX's persistent compilation cache at the checkout's own
    fixed directory, then let the program turn it on.  Must run before
    jax is imported: JAX reads the variable once."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    from repro.runtime import use_compile_cache
    return use_compile_cache()


def accelerator(chips: int):
    """The first `chips` TPU devices; NoDevice when JAX has fewer."""
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise NoDevice(f"JAX found no accelerator ({e})") from e
    if devices[0].platform != "tpu":
        raise NoDevice(f"JAX's default backend is {devices[0].platform!r}, "
                       f"not a TPU")
    if len(devices) < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX has "
                       f"{len(devices)}")
    return devices[:chips]


def make_instance(config: dict, **overrides):
    """The program's `Instance` of the configuration's deployment."""
    from repro.core.instance import Instance

    from .instances import make_fields
    fields = make_fields(config)
    fields.update(overrides)
    return Instance(**fields), fields


def new_cell(work: Workload):
    """Set up the traffic's kind driver for this cell (warm-up included)."""
    kind = work.traffic["kind"]
    mod = load_module(BENCH / "kinds" / f"{kind}.py", f"bench_kind_{kind}")
    return mod.Cell(work.config, work.traffic)


def run_window(cell, seconds: float, min_calls: int, span: str,
               log=None, max_calls: int | None = None
               ) -> tuple[list[CallRecord], float, int, int]:
    """The closed loop, cut short after `max_calls` completions when
    given.  Returns (completed records, window seconds, calls attempted,
    calls failed)."""
    import jax
    log = log or sys.stderr
    records: list[CallRecord] = []
    attempted = failed = 0
    t_start = time.perf_counter()
    t_end = t_start
    with jax.profiler.TraceAnnotation("bench.window"):
        while True:
            t0 = time.perf_counter()
            attempted += 1
            try:
                with jax.profiler.TraceAnnotation(span):
                    answer = cell.call(attempted - 1)
            except Exception:
                failed += 1
                print(f"call {attempted - 1} failed:", file=log)
                traceback.print_exc(file=log)
                answer = None
            t_end = time.perf_counter()
            if answer is not None:
                records.append(CallRecord(attempted - 1, t0, t_end, answer))
            if t_end - t_start >= seconds and (len(records) >= min_calls
                                               or failed):
                break
            if max_calls and len(records) >= max_calls:
                break
    return records, t_end - t_start, attempted, failed


def memory_peak(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def read_metrics(work: Workload, run: RunData, per_layer: bool) -> dict:
    out = {}
    for m in work.metrics:
        if m["per_layer"] != per_layer:
            continue
        mod = load_module(BENCH / "metrics" / f"{m['name']}.py",
                          "bench_metric_" + m["name"].replace(".", "_"))
        value = mod.read(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run(work: Workload, seed: int, seconds: float, trace: bool,
        t_process: float, log=None) -> dict:
    """One run of one cell; returns the result line's object."""
    log = log or sys.stderr
    use_checkout_cache()
    devices = accelerator(work.chips)
    import jax
    watch = CompileWatch()
    cell = new_cell(work)
    cell.start(seed)
    traffic = work.traffic
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    setup_s = time.perf_counter() - t_process
    watch.armed = True
    # A traced run traces the first `trace_calls` calls only: the
    # profiler records every HLO op, and writing a whole window of the
    # risk cells would take minutes.
    records, window_s, attempted, failed = run_window(
        cell, seconds, int(traffic.get("min_calls", 1)), traffic["span"],
        log, max_calls=int(traffic["trace_calls"]) if trace else None)
    watch.armed = False
    if trace:
        t0 = time.perf_counter()
        jax.profiler.stop_trace()
        print(f"trace written in {time.perf_counter() - t0:.1f} s",
              file=log)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(jax.devices()),
              "memory_peak_bytes": memory_peak(devices)}
    compiles = watch.report()
    print(json.dumps(compiles), flush=True)
    print(f"compiles inside the window: {compiles}", file=log)
    cell.release()
    gc.collect()
    checks = cell.check(records, seed, control=False) if records else []
    summary = None
    if trace:
        from . import tracereduce
        t0 = time.perf_counter()
        files = sorted(TRACE_DIR.glob("**/*.xplane.pb"))
        summary = tracereduce.reduce_events(tracereduce.load(str(files[-1])))
        print(f"trace of {files[-1].stat().st_size} bytes read in "
              f"{time.perf_counter() - t0:.1f} s", file=log)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
    data = RunData(records=records, window_s=window_s, setup_s=setup_s,
                   traffic=traffic, config=work.config, trace=summary)
    correct = bool(records) and failed == 0 and all(c.ok for c in checks)
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": read_metrics(work, data, per_layer=trace),
              "device": device}
    if summary is not None:
        result["breakdown"] = summary.breakdown()
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in checks}
    for c in checks:
        print(f"check {c.name}: {c.value!r} limit {c.limit!r} "
              f"{'ok' if c.ok else 'FAILED'}", file=log)
    return result
