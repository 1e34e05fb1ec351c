"""What the expert-kernel readers take from a traced run: the device time
of the grouped expert matmul's op events in the traced sub-window,
against the work the program's spans recorded there.

The architecture module names both: ``expert_matmul_op`` tells the
kernel's events apart by their HLO text (op events carry no source
names), and ``expert_work`` / ``expert_matmul_bound_s`` give the slots
and experts read and the least time they could take.  An architecture
without them, a run that was not traced, a program that records no
spans and a trace in which no event is the kernel's all read None.
"""
from __future__ import annotations

from typing import Dict, Optional

from chipbench import harness, spans

_planes: Dict[str, tuple] = {}


def kernel_seconds(ops: dict, w0: float, w1: float, is_kernel) -> float:
    """Device seconds, clipped to ``[w0, w1]`` ns, of the op events
    (``spans.read_planes``'s ops per device) that ``is_kernel(text)``
    picks, averaged over the devices that ran any."""
    per_dev = [sum(min(e, w1) - max(s, w0) for n, s, e in evs
                   if e > w0 and s < w1 and is_kernel(n))
               for evs in ops.values() if evs]
    return sum(per_dev) / len(per_dev) / 1e9 if per_dev else 0.0


def _kernel(r) -> Optional[tuple]:
    """(kernel seconds, the run's reduction, the architecture module) of
    a traced reading, or None."""
    arch = harness.arch_of(r.conf)
    if not hasattr(arch, "expert_matmul_op"):
        return None
    a = spans.analysis(r)
    path = spans._run_trace(r.window.t0) if a is not None else None
    if path is None:
        return None
    if path not in _planes:
        ops, _, host = spans.read_planes(path)
        _planes[path] = (ops, spans.window_of(host))
    ops, win = _planes[path]
    if win is None:
        return None
    t = kernel_seconds(ops, *win, lambda n: arch.expert_matmul_op(n, r.conf))
    return (t, a, arch) if t > 0 else None


def roofline_share(r) -> Optional[float]:
    """The least time for the window's expert work over the kernel's
    device time (%)."""
    got = _kernel(r)
    if got is None or r.peak is None:
        return None
    t, a, arch = got
    slots, read = arch.expert_work(a, r.conf)
    if not slots:
        return None
    return 100.0 * arch.expert_matmul_bound_s(slots, read, r.conf,
                                              r.peak) / t


def time_share(r) -> Optional[float]:
    """The kernel's device time over the device's busy time in the
    traced sub-window (%)."""
    got = _kernel(r)
    if got is None or not r.trace or r.trace["busy_s"] <= 0:
        return None
    return 100.0 * got[0] / r.trace["busy_s"]
