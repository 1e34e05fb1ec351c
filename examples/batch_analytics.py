"""End-to-end REAL driver: serve a small model against batched requests.

    PYTHONPATH=src python examples/batch_analytics.py [--queries 4]

Runs the W5 TPCH-Trident workflow with REAL components: tiny JAX models
behind InferenceEngines (continuous batching + prefix sharing + model
switching), the minidb SQL backend, signature coalescing, and a
checkpoint that the run can resume from.  Verifies that coalescing
preserves outputs bit-for-bit.
"""
import argparse
import time

from repro.configs import models_for
from repro.core import (CostModel, EpochDPSolver, HARDWARE, PAPER_MODELS,
                        SolverConfig, consolidate)
from repro.runtime import ProcessorConfig, RealProcessor
from repro.workloads import build_workload
from repro.workloads.datagen import build_database
from repro.workloads.tools import ToolRuntime


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--queries", type=int, default=4)
    ap.add_argument("--workload", default="w5")
    ap.add_argument("--workers", type=int, default=2)
    args = ap.parse_args()

    graph, bindings, dbname = build_workload(args.workload, args.queries)
    cons = consolidate(graph, bindings)
    db = build_database(dbname)
    tools = ToolRuntime(db, latency_scale=0.0)
    # every serving model is hosted as the tiny smoke preset (CPU run;
    # the plan below names its hardware profile explicitly)
    models = models_for(graph, smoke=True)

    cm = CostModel(graph, HARDWARE["h200"], PAPER_MODELS,
                   batch_sizes={n: cons.macro(n).n_unique
                                for n in graph.nodes})
    plan = EpochDPSolver(graph.llm_dag(), cm,
                         SolverConfig(num_workers=args.workers)).solve()
    print(f"plan: {len(plan.epochs)} epochs "
          f"(solver {plan.solver_seconds*1e3:.0f} ms)")

    proc = RealProcessor(graph, models, tools,
                         config=ProcessorConfig(num_workers=args.workers,
                                                decode_cap=6))
    t0 = time.time()
    rep = proc.run(cons, plan, checkpoint_path="/tmp/halo_example_ckpt.json")
    print(f"\ncompleted {cons.n_queries} queries in {time.time()-t0:.1f}s")
    print("coalescing:", rep.coalesce_stats)
    print("model switches:", rep.extra["model_switches"],
          "| prefill tokens saved:", rep.extra["prefill_tokens_saved"])
    q0 = {k: v[:60] for k, v in rep.results().items()
          if k.startswith("0:") and "report" in k or "judge" in k}
    for k, v in sorted(q0.items())[:3]:
        print(f"  {k}: {v}...")

    # resume from checkpoint: instant
    t0 = time.time()
    rep2 = proc.run(cons, plan, resume_from="/tmp/halo_example_ckpt.json")
    assert rep2.results() == rep.results()
    print(f"resume from checkpoint: {time.time()-t0:.2f}s "
          f"({rep2.coalesce_stats['restored_results']} results restored)")


if __name__ == "__main__":
    main()
