"""device_calls.plan: xla-tier device calls (Phase-2 keys plus relocate
screen) per plan call, from the engine's counters."""
from bench.lib.readers import diag_sum


def read(run):
    if not run.records:
        return None
    return diag_sum(run, "device_calls_phase2",
                    "device_calls_screen") / len(run.records)
