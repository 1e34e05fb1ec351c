"""1 - device busy / traced sub-window, from the profiler trace (%)."""
from chipbench import readings


def read(r):
    return readings.idle_share(r)
