"""Serving launcher: Halo end-to-end over a workload.

    python -m repro.launch.serve --workload w1 --queries 64 --mode sim
    python -m repro.launch.serve --workload wt --queries 4 --mode real
    python -m repro.launch.serve --workload wt --queries 4 --mode real \\
        --smoke --hardware h200                       # on a CPU

``sim`` reproduces paper-scale behaviour via the discrete-event backend
on the ``--hardware`` profile.  ``real`` serves every model id the
workload names with the published config of ``--arch`` (``--smoke``: its
reduced preset) on the JAX default device, against minidb, and plans
with that device's hardware profile; a CPU has none, so CPU runs name
one with ``--hardware``.
"""
from __future__ import annotations

import argparse
import json
from typing import List, Optional, Tuple

import jax

from repro.configs import SERVED_ARCH, models_for
from repro.core import (CostModel, EpochDPSolver, HARDWARE, PAPER_MODELS,
                        HardwareProfile, SolverConfig, consolidate,
                        hardware_for_device_kind)
from repro.engine.engine import InferenceEngine
from repro.launch.compile_cache import enable_compile_cache
from repro.runtime import (ProcessorConfig, RealProcessor,
                           SimulatedProcessor)
from repro.runtime.events import RunReport
from repro.runtime.executors import EngineHost
from repro.workloads import build_workload
from repro.workloads.datagen import build_database
from repro.workloads.tools import ToolRuntime


def build_cost_model(graph, cons, hardware: HardwareProfile, **kw):
    batch_sizes = {}
    for nid in graph.nodes:
        m = cons.macro(nid)
        batch_sizes[nid] = (m.n_logical if graph.nodes[nid].is_llm()
                            else m.n_unique)
    return CostModel(graph, hardware, PAPER_MODELS,
                     batch_sizes=batch_sizes, **kw)


def device_hardware(name: Optional[str] = None) -> HardwareProfile:
    """``HARDWARE[name]`` when named, else the default device's profile
    (an error on a device with none, e.g. the CPU)."""
    if name:
        return HARDWARE[name]
    return hardware_for_device_kind(jax.devices()[0].device_kind)


def workers_that_fit(models, requested: int) -> Tuple[int, str]:
    """How many workers' engines fit the default device's memory at
    once, and why.  Each worker holds its own resident model
    (``InferenceEngine.hbm_bytes``); a device that reports no limit (the
    CPU) takes ``requested``."""
    stats = jax.devices()[0].memory_stats() or {}
    limit = stats.get("bytes_limit")
    if not limit:
        return requested, "device reports no memory limit"
    need = max(InferenceEngine(cfg).hbm_bytes() for cfg in models.values())
    n = max(1, min(requested, int(limit // need)))
    return n, (f"{n} worker(s): each needs {need / 1e9:.2f} GB (params + KV "
               f"pool), device limit {limit / 1e9:.2f} GB")


def solve_plan(graph, cons, hardware: HardwareProfile, workers: int):
    """Halo's epoch-DP plan for a consolidated batch on ``workers``."""
    return EpochDPSolver(graph.llm_dag(),
                         build_cost_model(graph, cons, hardware),
                         SolverConfig(num_workers=workers)).solve()


def run_real(graph, cons, plan, dbname: str, models, *, workers: int,
             seed: int = 0, hosts: Optional[List[EngineHost]] = None
             ) -> RunReport:
    """Execute ``plan`` with real engines serving ``models`` (see
    ``repro.configs.models_for``) and minidb tools.  ``hosts`` keeps
    engines warm across calls."""
    proc = RealProcessor(graph, models, ToolRuntime(build_database(dbname)),
                         config=ProcessorConfig(num_workers=workers,
                                                seed=seed))
    return proc.run(cons, plan, hosts=hosts)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="w1")
    ap.add_argument("--queries", type=int, default=64)
    ap.add_argument("--workers", type=int, default=3)
    ap.add_argument("--mode", choices=("sim", "real"), default="sim")
    ap.add_argument("--hardware", default=None,
                    help="profile to plan with (default: h200 in sim "
                         "mode, the device's own in real mode)")
    ap.add_argument("--arch", default=SERVED_ARCH,
                    help="architecture serving every model id (real mode)")
    ap.add_argument("--smoke", action="store_true",
                    help="serve the arch's reduced preset (CPU runs)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    graph, bindings, dbname = build_workload(args.workload, args.queries,
                                             seed=args.seed)
    cons = consolidate(graph, bindings)
    workers = args.workers
    if args.mode == "sim":
        hw = HARDWARE[args.hardware or "h200"]
    else:
        enable_compile_cache()
        hw = device_hardware(args.hardware)
        models = models_for(graph, args.arch, smoke=args.smoke)
        workers, why = workers_that_fit(models, workers)
        print(f"[real mode] {args.arch}{' (smoke)' if args.smoke else ''} "
              f"on {jax.devices()[0].device_kind}, planned as {hw.name}; "
              f"{why}")
    plan = solve_plan(graph, cons, hw, workers)
    print(f"plan: {len(plan.epochs)} epochs, predicted {plan.predicted_cost:.2f}s,"
          f" solver {plan.solver_seconds*1e3:.1f}ms")

    if args.mode == "sim":
        rep = SimulatedProcessor(graph, build_cost_model(graph, cons, hw),
                                 workers).run(cons, plan)
    else:
        rep = run_real(graph, cons, plan, dbname, models, workers=workers,
                       seed=args.seed)
        rep.extra.pop("results", None)
    print(json.dumps(rep.summary(), indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
