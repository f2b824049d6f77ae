"""phase2_keys_ms.plan: device milliseconds of the Phase-2 ranking-key
program (`_phase2_keys_jit`) per plan call, from the profiler trace."""
from bench.lib.readers import per_call_ms


def read(run):
    return per_call_ms(run, "_phase2_keys_jit")
