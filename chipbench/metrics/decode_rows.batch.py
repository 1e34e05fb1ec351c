"""Rows a decode step carries: real rows over ``engine.decode`` spans
(one per decode step) in the traced sub-window."""
from chipbench import spans


def read(r):
    return spans.decode_rows(spans.analysis(r))
