"""candidate_ms.risk: device milliseconds of the anchor candidate
program (`_candidate_kernel`) per report, from the profiler trace."""
from bench.lib.readers import per_call_ms


def read(run):
    return per_call_ms(run, "_candidate_kernel")
