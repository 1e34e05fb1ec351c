"""Prompt tokens served from shared KV pages over all prompt tokens (%)."""
from chipbench import readings


def read(r):
    return readings.prefill_saved_share(r)
