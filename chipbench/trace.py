"""Reduce a profiler trace to the device's busy time, its top operations
and its longest idle gaps, each gap named by what the host was doing.

The trace is JAX's ``.xplane.pb``, read with
``jax.profiler.ProfileData``.  Device planes are those named
``/device:TPU:<n>``; on each, the ``XLA Ops`` line holds one event per
operation the chip ran.  The harness marks the traced window with a
host event ``chipbench.trace_window`` and its own spans as
``chipbench.<span>``; host planes carry them with the runtime's events
on the same clock.

- busy: the union of the device-op intervals inside the window, averaged
  over the devices that ran anything;
- top ops: device time summed by operation name (the HLO instruction's
  name; a loop's event spans its body's events);
- idle gaps: the ten longest spaces between busy intervals, each named
  by the innermost host event (a harness span or a runtime event) active
  at its midpoint, else ``host idle``.
"""
from __future__ import annotations

import glob
import os
from typing import Dict, List, Optional, Tuple

WINDOW_MARK = "chipbench.trace_window"
OPS_LINE = "XLA Ops"


def _events(line):
    for ev in line.events:
        yield ev.name, ev.start_ns, ev.start_ns + ev.duration_ns


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def op_name(name: str) -> str:
    """A device op's short name: the HLO instruction's name without the
    ``%`` and without its text after `` = ``."""
    return name.split(" = ", 1)[0].lstrip("%")


def reduce_planes(device_ops: Dict[str, List[Tuple[str, float, float]]],
                  host_events: List[Tuple[str, float, float]],
                  top: int = 10) -> Optional[dict]:
    """The reduction on plain event lists (ns): ``device_ops`` maps a
    device to its ``(op, start, end)`` events, ``host_events`` holds every
    host event, the window mark among them.  None when the window mark
    is missing or no device ran an operation."""
    marks = [(s, e) for n, s, e in host_events if n == WINDOW_MARK]
    if not marks:
        return None
    w0, w1 = marks[0]
    busy_by_dev, by_op = [], {}
    gaps: List[Tuple[float, float]] = []
    for dev, evs in sorted(device_ops.items()):
        inside = [(op_name(n), max(s, w0), min(e, w1)) for n, s, e in evs
                  if e > w0 and s < w1]
        if not inside:
            continue
        for name, s, e in inside:
            by_op[name] = by_op.get(name, 0.0) + e - s
        u = union([(s, e) for _, s, e in inside])
        busy_by_dev.append(sum(e - s for s, e in u))
        edges = [w0] + [x for iv in u for x in iv] + [w1]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    if not busy_by_dev:
        return None
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    host = [(n, s, e) for n, s, e in host_events if n != WINDOW_MARK]

    def name_gap(s, e):
        """The innermost host event (harness span or runtime event)
        active at the gap's midpoint."""
        mid = (s + e) / 2
        cover = [(ee - ss, n) for n, ss, ee in host if ss <= mid <= ee]
        if not cover:
            return "host idle"
        n = min(cover)[1]
        return n[len("chipbench."):] if n.startswith("chipbench.") else n

    ndev = len(busy_by_dev)
    ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
    return {"window_s": (w1 - w0) / 1e9,
            "busy_s": sum(busy_by_dev) / ndev / 1e9,
            "device_ops": [[n, t / 1e9 / ndev] for n, t in ops],
            "idle_gaps": [[name_gap(s, e), (e - s) / 1e9] for s, e in gaps]}


def read_xplane(path: str) -> Tuple[dict, list]:
    """(device ops per device plane, host events) of one ``.xplane.pb``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    device_ops: Dict[str, list] = {}
    host: list = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device_ops[plane.name] = list(_events(line))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(_events(line))
    return device_ops, host


def find_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    return found[-1] if found else None


def reduce_dir(trace_dir: str) -> Optional[dict]:
    path = find_xplane(trace_dir)
    if path is None:
        return None
    return reduce_planes(*read_xplane(path))
