"""Self time of the program's host spans, and the device's idle time
charged to them, from the events `tracereduce.load` returns.

The program marks the steps of a service call with host spans
(`repro.spans.span`): ``facade.*``, ``agh.*``, ``xla.*``, ``stage2.*``
and ``oracle.*``.  They sit on the calling thread's line of the host
plane, with the benchmark's own spans (``bench.window``, the call span)
around them and JAX's own events (``PjitFunction(...)``,
``np.asarray(jax.Array)``, ...) inside them.

A span's self time is its duration minus the part of it that its child
spans cover.  JAX's events are not steps of the program: their time
stays with the innermost program or benchmark span around them, so
``xla.phase2.fetch`` keeps the ``np.asarray`` it makes.
"""
from __future__ import annotations

import bisect
import dataclasses
import fnmatch

from .tracereduce import (WINDOW_SPAN, Event, _SpanIndex, _union,
                          is_device_plane)

PROGRAM = ("facade.", "agh.", "xla.", "stage2.", "oracle.")
ROOTS = frozenset({"facade.plan", "facade.replan", "facade.repair",
                   "facade.risk"})

# The layers' metrics (PERF.md section 3): name -> (span patterns
# counted, span patterns left out), each in ms of self time per call.
LAYERS = {
    "agh_host_ms.plan": (("agh.*",), ()),
    "xla_host_ms.plan": (("xla.tensors", "xla.*.pack", "xla.*.dispatch"),
                         ()),
    "readback_ms.plan": (("xla.*.wait", "xla.*.fetch"), ()),
    "facade_ms.plan": (("facade.*",), ()),
    "readback_ms.risk": (("stage2.*.wait", "stage2.*.fetch"), ()),
    "stage2_host_ms.risk": (("stage2.*", "facade.risk"),
                            ("stage2.*.wait", "stage2.*.fetch")),
    "exact_ms.risk": (("oracle.exact",), ()),
}


def is_program_span(name: str) -> bool:
    return name.startswith(PROGRAM)


@dataclasses.dataclass
class SpanTimes:
    self_s: dict[str, float]          # self seconds by span name
    calls: dict[str, int]             # spans opened, by name
    idle_s: float                     # device idle seconds in the window
    idle_by_step: dict[str, float]    # idle seconds by program or bench span

    @property
    def named_idle_share(self) -> float:
        """Share of the idle device time charged to a program step other
        than the call's root span."""
        named = sum(v for k, v in self.idle_by_step.items()
                    if is_program_span(k) and k not in ROOTS)
        return named / self.idle_s if self.idle_s else 0.0


def _marked(e: Event, bench: frozenset) -> bool:
    return is_program_span(e.name) or e.name in bench


def span_times(events: list[Event], call_span: str,
               window: str = WINDOW_SPAN) -> SpanTimes:
    """Self time and count of every program and benchmark span inside
    the `window` span, and the window's idle device time charged to the
    step the host was in.

    As in `tracereduce.reduce_events`, an idle gap goes whole to the
    innermost host span at its midpoint; a JAX event there hands it on
    to the program or benchmark span around it."""
    bench = frozenset({window, call_span})
    spans = [e for e in events if not is_device_plane(e.plane)]
    win = [e for e in spans if e.name == window]
    if len(win) != 1:
        raise ValueError(f"expected one {window!r} span, found {len(win)}")
    w0, w1 = win[0].start_ns, win[0].end_ns
    inside = [e for e in spans if e.start_ns >= w0 and e.end_ns <= w1]
    index = _SpanIndex(inside)
    # The nearest marked span at or above each span (-1: none).
    owner: list[int] = []
    for k, e in enumerate(index.spans):
        up = index.parent[k]
        owner.append(k if _marked(e, bench)
                     else (owner[up] if up >= 0 else -1))
    self_s: dict[str, float] = {}
    calls: dict[str, int] = {}
    for k, e in enumerate(index.spans):
        if not _marked(e, bench):
            continue
        calls[e.name] = calls.get(e.name, 0) + 1
        self_s[e.name] = self_s.get(e.name, 0.0) + e.dur_ns * 1e-9
        up = index.parent[k]
        if up >= 0 and owner[up] >= 0:
            name = index.spans[owner[up]].name
            self_s[name] -= e.dur_ns * 1e-9
    planes = sorted({e.plane for e in events if is_device_plane(e.plane)})
    idle_by_step: dict[str, float] = {}
    idle_s = 0.0
    for plane in planes:
        busy = _union([(max(e.start_ns, w0), min(e.end_ns, w1))
                       for e in events
                       if e.plane == plane and e.line == "XLA Modules"
                       and e.start_ns < w1 and e.end_ns > w0])
        edges = [w0] + [t for ab in busy for t in ab] + [w1]
        for a, b in zip(edges[::2], edges[1::2], strict=True):
            if b <= a:
                continue
            k = _innermost(index, 0.5 * (a + b))
            who = index.spans[owner[k]].name if k >= 0 and owner[k] >= 0 \
                else window
            share = (b - a) * 1e-9 / len(planes)
            idle_by_step[who] = idle_by_step.get(who, 0.0) + share
            idle_s += share
    return SpanTimes(self_s=self_s, calls=calls, idle_s=idle_s,
                     idle_by_step=idle_by_step)


def _innermost(index: _SpanIndex, t: float) -> int:
    k = bisect.bisect_right(index.starts, t) - 1
    while k >= 0 and index.spans[k].end_ns < t:
        k = index.parent[k]
    return k


def self_ms_per_call(times: SpanTimes, n_calls: int,
                     include: tuple[str, ...],
                     exclude: tuple[str, ...] = ()) -> float | None:
    """Self milliseconds per completed call of the spans whose names
    match a pattern of `include` and none of `exclude` (`fnmatch`
    patterns); None when no such span was opened."""
    def hit(name: str) -> bool:
        return (any(fnmatch.fnmatchcase(name, p) for p in include)
                and not any(fnmatch.fnmatchcase(name, p) for p in exclude))
    names = [n for n in times.self_s if hit(n)]
    if not names or not n_calls:
        return None
    return sum(times.self_s[n] for n in names) * 1e3 / n_calls
