"""Benchmark driver — one section per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--full]

Default sizes are CI-friendly (N=256); ``--full`` uses the paper's
N=1024.  Results land in experiments/results/*.json and a CSV summary
(`name,us_per_call,derived`) is printed per the harness convention.
"""
from __future__ import annotations

import argparse
import json
import os
import time

from benchmarks import (ablation, case_study, e2e_latency, online_serving,
                        optimality, scalability, sensitivity)
from repro.launch.compile_cache import enable_compile_cache

OUT = os.path.join(os.path.dirname(__file__), "..", "experiments", "results")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale N=1024 (slower)")
    ap.add_argument("--only", default=None)
    args = ap.parse_args()
    enable_compile_cache()
    n = 1024 if args.full else 256

    sections = {
        "e2e_latency_fig6": lambda: e2e_latency.run(n),
        "optimality_table4": lambda: optimality.run(min(n, 256)),
        "ablation_table5": lambda: ablation.run(min(n, 256)),
        "online_serving_fig7": lambda: online_serving.run(min(n, 128)),
        "scalability_fig8": lambda: scalability.run(),
        "sensitivity_fig9_10": lambda: sensitivity.run(n_queries=min(n, 256)),
        "case_study_fig11": lambda: case_study.run(n_queries=max(n, 1024)),
    }
    os.makedirs(OUT, exist_ok=True)
    print("name,us_per_call,derived")
    for name, fn in sections.items():
        if args.only and args.only not in name:
            continue
        t0 = time.perf_counter()
        rows = fn()
        dt = time.perf_counter() - t0
        with open(os.path.join(OUT, f"{name}.json"), "w") as f:
            json.dump(rows, f, indent=1, default=str)
        derived = ""
        if name.startswith("e2e"):
            sp = [r["speedup_vs_halo"] for r in rows
                  if r["system"] in ("opwise", "langgraph", "agentscope",
                                     "parrot")]
            derived = f"max_speedup_vs_baselines={max(sp):.2f}x"
        elif name.startswith("optimality"):
            halo = [r for r in rows if r["scheduler"] == "halo"]
            derived = "opt=" + "/".join(str(r["opt"]) for r in halo)
        elif name.startswith("online"):
            derived = "halo_qps=" + "/".join(
                str(r["qps"]) for r in rows if r["system"] == "halo")
        elif name.startswith("case"):
            derived = f"gpu_seconds_reduction={rows[-1]['gpu_seconds_reduction']}x"
        print(f"{name},{dt * 1e6 / max(len(rows), 1):.0f},{derived}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
