"""Share of the traced sub-window in which the device runs no op while
some engine loop is inside ``engine.step`` (%)."""
from chipbench import spans


def read(r):
    return spans.idle_engine_host_share(spans.analysis(r))
