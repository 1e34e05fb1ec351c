"""Nightly benchmark smoke — tiny-N e2e latency + online serving.

    PYTHONPATH=src python -m benchmarks.smoke

Runs the simulated baselines at small N plus the REAL continuous-
batching engines (pipelined-vs-barrier WT rows, calibrated online
stream, streaming-session-vs-micro-batched A/B) and writes one
``BENCH_<section>.json`` per section into ``experiments/results/`` —
CI uploads them as artifacts so the perf trajectory is recorded run
over run.

The run FAILS (nonzero exit) when a guarded A/B inverts, instead of
silently uploading an artifact that contradicts the design claims:

* ``halo-real-pipelined`` must not lose to ``halo-real-barrier``
  (tool pipelining exists to hide CPU latency under decode);
* ``session-stream`` must not lose to ``micro-batched`` on makespan
  OR interactive p95 TTFT, and the arms' temp-0 outputs must match
  bitwise (DESIGN.md §10);
* ``halo-real-kernel-fused`` must not lose to ``-single`` on
  tokens/s-per-device, and the two kernel arms' temp-0 outputs must
  match bitwise — timing checks apply only to non-interpret rows
  (real hardware), the output check always.

On top of the A/B pairs, the kernel section self-compares run over
run: tokens/s-per-device from the PREVIOUS ``BENCH_kernels.json``
artifact (if present — CI restores it before overwriting) gates the
current run with the same 15% slack, so a kernel regression fails the
nightly even when both variants regress together.

``_AB_SLACK`` absorbs CI timing noise; a genuine inversion (like the
2026-08 artifact that showed pipelined at 4.51s vs barrier at 1.69s,
which never reproduced locally) is far outside it.
"""
from __future__ import annotations

import json
import os
import time
from typing import Dict, List

from benchmarks import e2e_latency, kernel_bench, online_serving, scalability
from repro.launch.compile_cache import enable_compile_cache

OUT = os.path.join(os.path.dirname(__file__), "..", "experiments", "results")

_AB_SLACK = 1.15                 # winner may be up to 15% "slower" (noise)


def _row(rows: List[Dict], system: str) -> Dict:
    return next(r for r in rows if r.get("system") == system)


def static_analysis_rows() -> List[Dict]:
    """Counters from ``tools.analysis`` (DESIGN.md §11) as one row —
    the nightly artifact makes the host-sync budget and guarded-attr
    coverage a tracked series, not a one-time assertion."""
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from tools.analysis import run as analysis_run
    res = analysis_run()
    row = {"system": "tools-analysis", **res.counts,
           "strict_clean": res.ok(strict=True)}
    return [row]


def load_previous(name: str) -> List[Dict]:
    """Rows from the previous run's artifact, [] if absent/unreadable.
    Must be called BEFORE main() overwrites the file."""
    try:
        with open(os.path.join(OUT, f"{name}.json")) as f:
            rows = json.load(f)
        return rows if isinstance(rows, list) else []
    except (OSError, json.JSONDecodeError):
        return []


def check_kernel_regressions(rows: List[Dict],
                             prev: List[Dict]) -> List[str]:
    """Run-over-run tokens/s gate for the kernel section: each system's
    tokens/s-per-device must stay within ``_AB_SLACK`` of the previous
    artifact's.  Interpret-mode rows (Pallas interpreter on CPU CI) are
    never compared — their timings measure the interpreter."""
    bad = []
    prev_by_system = {r["system"]: r for r in prev
                      if not r.get("interpret")}
    for r in rows:
        if r.get("interpret"):
            continue
        p = prev_by_system.get(r.get("system"))
        if p is None:
            continue
        cur, old = (r.get("tokens_per_s_per_device"),
                    p.get("tokens_per_s_per_device"))
        if cur is None or old is None:
            continue
        if cur * _AB_SLACK < old:
            bad.append(f"KERNEL REGRESSION: {r['system']} "
                       f"tokens/s-per-device {cur} vs previous {old}")
    return bad


def check_inversions(sections: Dict[str, List[Dict]]) -> List[str]:
    """Guarded A/B pairs that must not invert.  Returns violations."""
    bad = []

    def must_beat(rows, winner, loser, metric):
        try:
            w, l = _row(rows, winner), _row(rows, loser)
        except StopIteration:
            return                           # section ran without the pair
        if w[metric] > l[metric] * _AB_SLACK:
            bad.append(f"A/B INVERSION: {winner} {metric}={w[metric]} vs "
                       f"{loser} {metric}={l[metric]}")

    rows = sections.get("BENCH_e2e_latency", [])
    must_beat(rows, "halo-real-pipelined", "halo-real-barrier",
              "makespan_s")
    rows = sections.get("BENCH_online_serving", [])
    must_beat(rows, "session-stream", "micro-batched", "makespan_s")
    must_beat(rows, "session-stream", "micro-batched",
              "interactive_p95_ttft_s")
    for r in rows:
        if r.get("outputs_match") is False:
            bad.append(f"OUTPUT MISMATCH: {r['system']} temp-0 outputs "
                       "differ between streaming and micro-batched arms")

    rows = sections.get("BENCH_scale", [])
    for r in rows:
        if r.get("outputs_match") is False:
            bad.append(f"OUTPUT MISMATCH: {r['system']} outputs differ "
                       "from the cold run's")
        if r.get("system") == "halo-real-resumed":
            re_exec = r.get("jobstore", {}).get("re_executed_signatures")
            if re_exec:
                bad.append(f"RESUME REGRESSION: resumed run re-executed "
                           f"{re_exec} journaled signatures (want 0)")
            if r.get("decode_tokens"):
                bad.append(f"RESUME REGRESSION: resumed run decoded "
                           f"{r['decode_tokens']} tokens (want 0)")

    rows = sections.get("BENCH_kernels", [])
    try:
        w = _row(rows, "halo-real-kernel-fused")
        l = _row(rows, "halo-real-kernel-single")
    except StopIteration:
        w = l = None
    if w is not None and not (w.get("interpret") or l.get("interpret")):
        # higher is better here, so the inversion test flips relative
        # to the makespan pairs above
        if w["tokens_per_s_per_device"] * _AB_SLACK < \
                l["tokens_per_s_per_device"]:
            bad.append(
                "A/B INVERSION: halo-real-kernel-fused tokens/s-per-device"
                f"={w['tokens_per_s_per_device']} vs halo-real-kernel-"
                f"single={l['tokens_per_s_per_device']}")
    for r in rows:
        if r.get("outputs_match") is False:
            bad.append(f"OUTPUT MISMATCH: {r['system']} temp-0 outputs "
                       "differ between fused and single kernel arms")
    return bad


def main() -> int:
    enable_compile_cache()
    sections = {
        "BENCH_e2e_latency": lambda: e2e_latency.run(
            64, include_real=True),
        "BENCH_online_serving": lambda: (
            online_serving.run(32)
            + online_serving.real_stream_rows()
            + online_serving.session_stream_rows()),
        "BENCH_kernels": lambda: (
            kernel_bench.bench_rows(smoke=True)
            + e2e_latency.kernel_rows()),
        "BENCH_static_analysis": static_analysis_rows,
        "BENCH_scale": lambda: (scalability.scale_rows(2048)
                                + scalability.recovery_rows()),
    }
    os.makedirs(OUT, exist_ok=True)
    prev_kernels = load_previous("BENCH_kernels")
    results: Dict[str, List[Dict]] = {}
    for name, fn in sections.items():
        t0 = time.perf_counter()
        rows = fn()
        dt = time.perf_counter() - t0
        results[name] = rows
        path = os.path.join(OUT, f"{name}.json")
        with open(path, "w") as f:
            json.dump(rows, f, indent=1, default=str)
        print(f"{name}: {len(rows)} rows in {dt:.1f}s -> {path}")
        for r in rows:
            if str(r.get("system", "")).startswith(
                    ("halo-real", "session-stream", "micro-batched")):
                print("  ", r)
    violations = check_inversions(results)
    violations += check_kernel_regressions(
        results.get("BENCH_kernels", []), prev_kernels)
    for v in violations:
        print(v)
    return 1 if violations else 0


if __name__ == "__main__":
    raise SystemExit(main())
