"""Arithmetic the metric readers share.

Each reader (``metrics/<name>.py``) gets one ``reading``: the load loop's
``window`` (bounds, queries, counters at both ends, programs built), the
harness ``spans``, the mix and configuration files, the chip's ``peak``
(None in a rehearsal), the reduced ``trace`` (None unless traced) and
``served``, the requests the engines finished in the window.
"""
from __future__ import annotations

from typing import Optional

from chipbench import harness


def mfu(reading) -> Optional[float]:
    """Model FLOPs of the tokens the engines computed in the window over
    the window and the chip's bf16 peak, in %.  Every prefilled and
    decoded token passes the layers' matmuls; the head runs once per
    decoded token and once per prefill (the last prompt position).
    The matmul parameters are the architecture's count
    (``matmul_params``); attention's own FLOPs are left out, so this
    undercounts."""
    if reading.peak is None:
        return None
    win = reading.window
    layers, head = harness.arch_of(reading.conf).matmul_params(reading.conf)
    tokens = win.delta("prefill_tokens") + win.delta("decode_tokens")
    prefills = max(reading.served - win.delta("coalesced_requests"), 0)
    flops = 2.0 * (layers * tokens
                   + head * (win.delta("decode_tokens") + prefills))
    return 100.0 * flops / win.seconds / float(reading.peak["bf16_flops"])


def prefill_saved_share(reading) -> Optional[float]:
    win = reading.window
    saved = win.delta("prefill_tokens_saved")
    total = saved + win.delta("prefill_tokens")
    return None if total == 0 else 100.0 * saved / total


def idle_share(reading) -> Optional[float]:
    t = reading.trace
    if t is None or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
