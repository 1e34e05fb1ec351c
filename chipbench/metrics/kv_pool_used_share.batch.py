"""KV pool pages in use over the pool's pages, summed over the decode
steps of the traced sub-window (%)."""
from chipbench import spans


def read(r):
    return spans.kv_pool_used_share(spans.analysis(r))
