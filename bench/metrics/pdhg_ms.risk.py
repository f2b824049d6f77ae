"""pdhg_ms.risk: device milliseconds of the PDHG programs (`_pdhg_setup`
and `_pdhg_block`) per report, from the profiler trace."""
from bench.lib.readers import per_call_ms


def read(run):
    return per_call_ms(run, "_pdhg_setup", "_pdhg_block")
