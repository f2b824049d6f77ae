"""idle_share.risk: share of the traced risk window with no operation
running on the device."""
from bench.lib.readers import idle_percent


def read(run):
    return idle_percent(run)
