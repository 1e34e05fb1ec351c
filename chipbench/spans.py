"""The program's own spans on the device trace's clock, and what the
per-layer readers take from them.

The program records spans (``repro.obs``) while a profiler trace is
captured, so in a ``--trace 1`` run they cover the traced sub-window.
They are stamped with ``time.perf_counter_ns()``.  To put them on the
trace's clock the program writes an anchor when the capture starts: a
host annotation ``repro.obs.anchor#<id>`` entered between two
``perf_counter_ns()`` reads, kept as the span ``repro.obs.anchor`` with
that id.  The offset is the annotation's start in the trace minus the
midpoint of the two reads; half their distance is its uncertainty.

Reductions, next to ``trace.py``'s (which stay as they are):

- device time and event count per program on the ``XLA Modules`` line,
  named without its ``(hash)``;
- the device's idle intervals in the window;
- the gap table: each idle gap of at least 1 ms with the innermost
  program span open at its midpoint, and the compiles each span name
  triggered.

The first reader of a traced run drains the spans, reduces them with the
trace and writes ``chipbench/out/spans/<cell>-<seed>.json`` (the trace's
directory name): the mapped spans, the program times and the gap table.
Where the program records no spans (no ``repro.obs``, no anchor), or
the run was not traced, every reading here is None.
"""
from __future__ import annotations

import json
import os
import statistics
import time
from typing import Dict, List, Optional, Tuple

from chipbench import harness
from chipbench import trace as T

HERE = os.path.dirname(os.path.abspath(__file__))
TRACES = os.path.join(HERE, "out", "traces")
SPANS_OUT = os.path.join(HERE, "out", "spans")
MODULES_LINE = "XLA Modules"
ANCHOR = "repro.obs.anchor"
DECODE_PROGRAM = "jit_paged_decode_step"
MIN_GAP_NS = 1_000_000
LOOP_SPANS = ("engine.step", "engine.admit", "engine.prefill",
              "engine.decode", "engine.sync")

_cache: Dict[str, dict] = {}


def program_name(name: str) -> str:
    """A program's name on the ``XLA Modules`` line without its hash:
    ``jit_paged_decode_step(1234)`` -> ``jit_paged_decode_step``."""
    return name.split("(", 1)[0]


def read_planes(path: str) -> Tuple[dict, dict, list]:
    """(ops per device, programs per device, host events) of one
    ``.xplane.pb``; events are ``(name, start_ns, end_ns)``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    ops: Dict[str, list] = {}
    modules: Dict[str, list] = {}
    host: list = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == T.OPS_LINE:
                    ops[plane.name] = list(T._events(line))
                elif line.name == MODULES_LINE:
                    modules[plane.name] = list(T._events(line))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(T._events(line))
    return ops, modules, host


def window_of(host: list) -> Optional[Tuple[float, float]]:
    marks = [(s, e) for n, s, e in host if n == T.WINDOW_MARK]
    return marks[0] if marks else None


def program_times(modules: dict, w0: float, w1: float) -> Dict[str, dict]:
    """Device seconds (clipped to the window) and events per program."""
    out: Dict[str, dict] = {}
    for evs in modules.values():
        for n, s, e in evs:
            if e > w0 and s < w1:
                p = out.setdefault(program_name(n), {"s": 0.0, "count": 0})
                p["s"] += (min(e, w1) - max(s, w0)) / 1e9
                p["count"] += 1
    return dict(sorted(out.items(), key=lambda kv: -kv[1]["s"]))


def idle_intervals(ops: dict, w0: float, w1: float) -> List[tuple]:
    """The window's stretches in which a device that ran anything ran
    no op (``(start, end)`` ns, over all such devices)."""
    gaps: List[tuple] = []
    for evs in ops.values():
        busy = T.union([(max(s, w0), min(e, w1)) for _, s, e in evs
                        if e > w0 and s < w1])
        if not busy:
            continue
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    return gaps


def anchor_of(records: list, host: list) -> Optional[Tuple[float, float]]:
    """(offset, uncertainty) in ns that map ``perf_counter_ns`` onto the
    trace's clock, from the program's anchor with the least uncertainty."""
    marks = {r[0]: r for r in records if r[2] == ANCHOR}
    best = None
    for n, s, _ in host:
        if not n.startswith(ANCHOR + "#"):
            continue
        r = marks.get(int(n.rpartition("#")[2]))
        if r is None:
            continue
        cand = (s - (r[3] + r[4]) / 2, (r[4] - r[3]) / 2)
        if best is None or cand[1] < best[1]:
            best = cand
    return best


def _overlap(a: List[tuple], b: List[tuple]) -> float:
    """Length of the intersection of two unions of intervals."""
    a, b = T.union(a), T.union(b)
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        total += max(0.0, hi - lo)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def reduce(records: list, ops: dict, modules: dict, host: list,
           threads: Optional[dict] = None) -> Optional[dict]:
    """Everything the readers and the spans file take from one traced
    run: program ``records`` (``repro.obs`` tuples) and the trace's
    events.  None without a window mark, an anchor or a device op."""
    win = window_of(host)
    anchor = anchor_of(records, host)
    if win is None or anchor is None:
        return None
    w0, w1 = win
    off, unc = anchor
    threads = threads or {}
    if not any(ops.values()):
        return None
    spans = [(i, p, n, s + off, e + off, th, a)
             for i, p, n, s, e, th, a in records if n != ANCHOR]
    idle = idle_intervals(ops, w0, w1)

    def inside(t):
        return w0 <= t <= w1

    by_id = {r[0]: r for r in spans}
    queue_ms = [(e - s) / 1e6 for _, _, n, s, e, _, _ in spans
                if n == "engine.queue" and inside(e)]
    decodes = [r for r in spans if r[2] == "engine.decode" and inside(r[3])]
    # an engine loop is inside engine.step while any loop span is open:
    # a step begun before the capture is seen only through its children
    steps = [(s, e) for _, _, n, s, e, _, _ in spans if n in LOOP_SPANS]
    # host intervals in which a decode step is in flight: from its
    # engine.decode start to the engine.sync end that follows on its loop
    in_flight, open_at = [], {}
    for _, _, n, s, e, th, _ in sorted(spans, key=lambda r: r[3]):
        if n == "engine.decode":
            open_at[th] = s
        elif n == "engine.sync" and th in open_at:
            in_flight.append((open_at.pop(th), e))
    dec_events = [(s, e) for evs in modules.values() for n, s, e in evs
                  if program_name(n) == DECODE_PROGRAM and inside(s)]
    missed = [s for s, _ in dec_events
              if not any(a <= s <= b for a, b in in_flight)]
    # how far from the nearest such interval a missed one starts: the
    # profiler places device events within about a millisecond of its
    # host clock, and this says whether a miss is of that size
    edges = [t for iv in in_flight for t in iv]
    outside = [min(abs(s - t) for t in edges) for s in missed] if edges \
        else []

    def innermost(t):
        cover = [(e - s, r) for r in spans for s, e in [r[3:5]]
                 if s <= t <= e]
        return min(cover, key=lambda c: c[0])[1] if cover else None

    gaps = []
    for s, e in sorted(idle, key=lambda g: g[0] - g[1]):
        if e - s < MIN_GAP_NS:
            break
        r = innermost((s + e) / 2)
        gaps.append({"start_ms": (s - w0) / 1e6, "ms": (e - s) / 1e6,
                     "span": r[2] if r else None,
                     "parent": by_id[r[1]][2] if r and r[1] in by_id
                     else None,
                     "thread": threads.get(r[5]) if r else None,
                     "attrs": r[6] if r else None})
    compiles: Dict[str, dict] = {}
    for _, _, n, _, _, _, a in spans:
        if a.get("compiles"):
            c = compiles.setdefault(n, {"compiles": 0, "compile_s": 0.0})
            c["compiles"] += a["compiles"]
            c["compile_s"] += a["compile_s"]
    return {
        "window_s": (w1 - w0) / 1e9,
        "anchor": {"offset_ns": off, "uncertainty_us": unc / 1e3},
        "programs": program_times(modules, w0, w1),
        "idle_s": sum(e - s for s, e in idle) / 1e9,
        "idle_in_engine_step_s": _overlap(idle, steps) / 1e9,
        "queue_ms": queue_ms,
        "decode_steps": len(decodes),
        "decode_rows": sum(r[6]["rows"] for r in decodes),
        "decode_kv_tokens": sum(r[6].get("kv_tokens", 0) for r in decodes),
        "pool_pages_used": sum(r[6].get("pages_used", 0) for r in decodes),
        "pool_pages_total": sum(r[6].get("pages_total", 0)
                                for r in decodes),
        "decode_program_s": [(e - s) / 1e9 for s, e in dec_events],
        "decode_program_in_host_step": (1 - len(missed) / len(dec_events)
                                        if dec_events else None),
        "decode_program_outside_ms": (max(outside) / 1e6 if outside
                                      else None),
        "gaps": gaps,
        "compiles": compiles,
        "spans": [[n, (s - w0) / 1e6, (e - s) / 1e6, threads.get(th, th),
                   by_id[p][2] if p in by_id else None, a]
                  for _, p, n, s, e, th, a in spans],
    }


def _run_trace(window_t0: float) -> Optional[str]:
    """The newest trace written since the window started."""
    path = T.find_xplane(TRACES)
    if path is None:
        return None
    started = time.time() - (time.perf_counter() - window_t0)
    return path if os.path.getmtime(path) >= started else None


def analysis(reading) -> Optional[dict]:
    """The traced run's reduction (once per run; later readers get the
    same), with its spans file written.  None where nothing was traced
    or the program records no spans."""
    if reading.trace is None:
        return None
    path = _run_trace(reading.window.t0)
    if path is None:
        return None
    if path not in _cache:
        try:
            from repro import obs
        except ImportError:
            return None
        got = obs.drain()
        out = reduce(got["records"], *read_planes(path), got["threads"])
        if out is not None:
            out["dropped"] = got["dropped"]
            out["compiles"]["unattributed"] = got["unattributed"]
            run = os.path.relpath(path, TRACES).split(os.sep)[0]
            os.makedirs(SPANS_OUT, exist_ok=True)
            with open(os.path.join(SPANS_OUT, f"{run}.json"), "w") as f:
                json.dump(out, f)
        _cache[path] = out
    return _cache[path]


# ------------------------------------------------- the readers' arithmetic
def admit_wait_p50_ms(a: Optional[dict]) -> Optional[float]:
    if not a or not a["queue_ms"]:
        return None
    return statistics.median(a["queue_ms"])


def decode_rows(a: Optional[dict]) -> Optional[float]:
    if not a or not a["decode_steps"]:
        return None
    return a["decode_rows"] / a["decode_steps"]


def kv_pool_used_share(a: Optional[dict]) -> Optional[float]:
    if not a or not a["pool_pages_total"]:
        return None
    return 100.0 * a["pool_pages_used"] / a["pool_pages_total"]


def decode_hbm_share(a: Optional[dict], conf: dict,
                     peak: Optional[dict]) -> Optional[float]:
    """Bytes a decode step must read (the architecture's count,
    ``decode_step_bytes``) over the mean device time of
    ``jit_paged_decode_step``, as a share of the chip's HBM bandwidth."""
    if not a or peak is None or not a["decode_steps"] \
            or not a["decode_program_s"]:
        return None
    t = statistics.fmean(a["decode_program_s"])
    return 100.0 * harness.arch_of(conf).decode_step_bytes(a, conf) / t \
        / float(peak["hbm_bytes_per_s"])


def idle_engine_host_share(a: Optional[dict]) -> Optional[float]:
    if not a or a["window_s"] <= 0:
        return None
    return 100.0 * a["idle_in_engine_step_s"] / a["window_s"]
