"""idle_share.plan: share of the traced plan window with no operation
running on the device."""
from bench.lib.readers import idle_percent


def read(run):
    return idle_percent(run)
