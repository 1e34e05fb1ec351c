"""Whole-step model FLOPs utilization of the chip's bf16 peak (%)."""
from chipbench import readings


def read(r):
    return readings.mfu(r)
