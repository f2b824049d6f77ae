"""readback_kb.plan: kilobytes the xla tier reads back from the device
per plan call (the engine's `readback_bytes` counter: Phase-2 keys and
screen verdicts).  None where the program keeps no such counter."""
from bench.lib.readers import diag_sum


def read(run):
    if not run.records or any("readback_bytes" not in rec.answer["diagnostics"]
                              for rec in run.records):
        return None
    return diag_sum(run, "readback_bytes") / len(run.records) / 1e3
