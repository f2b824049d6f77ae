"""Arithmetic the metric readers under `bench/metrics/` share."""
from __future__ import annotations


def per_call_ms(run, *functions: str) -> float | None:
    """Device milliseconds of the named jit functions per completed call
    of the traced window; None without a trace."""
    if run.trace is None or not run.records:
        return None
    return run.trace.kernel_s(*functions) * 1e3 / len(run.records)


def idle_percent(run) -> float | None:
    """Share of the traced window in which no operation ran on the
    device, in percent; None without a trace."""
    if run.trace is None:
        return None
    return 100.0 * run.trace.idle_share


def diag_sum(run, *keys: str) -> float:
    """Sum of the named counters over the window's completed calls."""
    return float(sum(rec.answer["diagnostics"].get(k, 0)
                     for rec in run.records for k in keys))
