"""Bytes a decode step must read (matmul parameters and live KV at 2
bytes) over the mean device time of ``jit_paged_decode_step`` in the
traced sub-window, as a share of the chip's HBM bandwidth (%)."""
from chipbench import spans


def read(r):
    return spans.decode_hbm_share(spans.analysis(r), r.conf, r.peak)
