"""Median wait of a request for an engine slot (``engine.queue``, from
submit until it has a slot or joins a leader) over the requests admitted
in the traced sub-window (ms)."""
from chipbench import spans


def read(r):
    return spans.admit_wait_p50_ms(spans.analysis(r))
