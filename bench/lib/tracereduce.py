"""Reduce a JAX profiler trace to device metrics.

What a TPU trace looks like (JAX 0.9, TPU v5 lite, read by hand): one
plane per chip named ``/device:TPU:<n>``.  Its line ``XLA Modules`` holds
one event per executed program, named ``jit_<function>(<hash>)``, e.g.
``jit__phase2_keys_jit(11273308398267362166)``; its line ``XLA Ops``
holds the HLO operations inside them, named by their HLO text, and is
by far the largest part of the trace, so it is not read: the device is
counted busy while it executes a program.  Host annotations
(`jax.profiler.TraceAnnotation`) and the dispatches
(``PjitFunction(<function>)``) sit on one line of the ``/host:CPU``
plane, that of the thread that made them.  Device and host events share
one clock, in ns from the start of the trace.

`load` flattens the trace into plain tuples; `reduce_events` does the
arithmetic on them, so the tests can feed it a small recorded trace.
"""
from __future__ import annotations

import bisect
import dataclasses
import re

WINDOW_SPAN = "bench.window"
_MODULE = re.compile(r"^jit_(?P<fn>.+?)(\(\d+\))?$")


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def load(path: str, window: str = WINDOW_SPAN) -> list[Event]:
    """The device programs of one trace file, and the host spans on the
    thread that ran the `window` span."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        if is_device_plane(plane.name):
            lines = [ln for ln in plane.lines if ln.name == "XLA Modules"]
        elif plane.name == "/host:CPU":
            lines = [ln for ln in plane.lines
                     if any(e.name == window for e in ln.events)]
        else:
            continue
        for line in lines:
            for e in line.events:
                if e.name.startswith("$"):
                    continue            # python-tracer frames, if any
                out.append(Event(plane.name, line.name, e.name,
                                 float(e.start_ns), float(e.duration_ns)))
    return out


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and "CUSTOM" not in name


def module_function(name: str) -> str:
    """``jit__pdhg_block(123)`` -> ``_pdhg_block``."""
    m = _MODULE.match(name)
    return m.group("fn") if m else name


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


@dataclasses.dataclass
class TraceSummary:
    window_s: float                   # length of the traced window
    busy_s: float                     # device busy, mean over chips
    n_devices: int
    module_s: dict[str, float]        # device seconds by jit function
    module_calls: dict[str, int]      # executions by jit function
    idle_by_host: dict[str, float]    # idle device seconds by host span

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def kernel_s(self, *functions: str) -> float:
        """Device seconds of the named jit functions (0 if none ran)."""
        return sum(self.module_s.get(f, 0.0) for f in functions)

    def breakdown(self, n: int = 10) -> dict:
        top = lambda d: [[k, v] for k, v in sorted(
            d.items(), key=lambda kv: -kv[1])[:n]]
        return {"device_ops": top(self.module_s),
                "idle_gaps": top(self.idle_by_host)}


def reduce_events(events: list[Event], window: str = WINDOW_SPAN
                  ) -> TraceSummary:
    """Busy time, per-program device time and idle attribution inside
    the host span named `window`."""
    spans = [e for e in events if not is_device_plane(e.plane)]
    win = [e for e in spans if e.name == window]
    if len(win) != 1:
        raise ValueError(f"expected one {window!r} span, found {len(win)}")
    w0, w1 = win[0].start_ns, win[0].end_ns
    planes = sorted({e.plane for e in events if is_device_plane(e.plane)})
    if not planes:
        raise ValueError("the trace holds no device plane")
    busy_total = 0.0
    module_s: dict[str, float] = {}
    module_calls: dict[str, int] = {}
    idle_by_host: dict[str, float] = {}
    locate = _SpanIndex([e for e in spans if e.name != window
                         and e.start_ns < w1 and e.end_ns > w0])
    for plane in planes:
        mods = [e for e in events
                if e.plane == plane and e.line == "XLA Modules"]
        busy = _union([(max(e.start_ns, w0), min(e.end_ns, w1))
                       for e in mods if e.start_ns < w1 and e.end_ns > w0])
        busy_total += sum(b - a for a, b in busy)
        for e in mods:
            if e.start_ns < w1 and e.end_ns > w0:
                fn = module_function(e.name)
                d = min(e.end_ns, w1) - max(e.start_ns, w0)
                module_s[fn] = module_s.get(fn, 0.0) + d * 1e-9
                module_calls[fn] = module_calls.get(fn, 0) + 1
        edges = [w0] + [t for ab in busy for t in ab] + [w1]
        for a, b in zip(edges[::2], edges[1::2], strict=True):
            if b > a:
                who = locate.innermost(0.5 * (a + b)) or window
                idle_by_host[who] = (idle_by_host.get(who, 0.0)
                                     + (b - a) * 1e-9 / len(planes))
    return TraceSummary(window_s=(w1 - w0) * 1e-9,
                        busy_s=busy_total * 1e-9 / len(planes),
                        n_devices=len(planes), module_s=module_s,
                        module_calls=module_calls,
                        idle_by_host=idle_by_host)


class _SpanIndex:
    """Innermost covering span of a time, for properly nested spans."""

    def __init__(self, spans: list[Event]):
        self.spans = sorted(spans, key=lambda e: (e.start_ns, -e.dur_ns))
        self.starts = [e.start_ns for e in self.spans]
        self.parent: list[int] = []
        stack: list[int] = []
        for k, e in enumerate(self.spans):
            while stack and self.spans[stack[-1]].end_ns < e.end_ns:
                stack.pop()
            self.parent.append(stack[-1] if stack else -1)
            stack.append(k)

    def innermost(self, t: float) -> str | None:
        k = bisect.bisect_right(self.starts, t) - 1
        while k >= 0 and self.spans[k].end_ns < t:
            k = self.parent[k]
        return self.spans[k].name if k >= 0 else None
