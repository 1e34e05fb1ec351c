"""Fig. 8 — scalability: batch-size scaling and worker elasticity (W3),
plus the data-scale enumerated batch (DESIGN.md §12.1) and the durable
job-store recovery arms (DESIGN.md §12.2)."""
from __future__ import annotations

import os
import tempfile
from typing import Dict, List

from benchmarks.common import (make_real_processor, run_halo, run_opwise,
                               setup)


def run(workload: str = "w3") -> List[Dict]:
    rows = []
    for n in (256, 512, 1024, 2048):
        g, cons, _ = setup(workload, n)
        halo = run_halo(g, cons, 3)
        opw = run_opwise(g, cons, 3)
        rows.append({"axis": "batch", "value": n,
                     "halo_s": round(halo.makespan, 1),
                     "opwise_s": round(opw.makespan, 1),
                     "halo_qps": round(halo.throughput_qps(), 3)})
    # worker elasticity on a workload WITH branch parallelism (W1 diamond;
    # a pure chain like W3 cannot use >1 worker at macro granularity)
    g, cons, _ = setup("w1", 256)
    for wk in (1, 2, 3):
        halo = run_halo(g, cons, wk)
        opw = run_opwise(g, cons, wk)
        rows.append({"axis": "workers", "value": wk,
                     "halo_s": round(halo.makespan, 1),
                     "opwise_s": round(opw.makespan, 1),
                     "halo_qps": round(halo.throughput_qps(), 3)})
    return rows


def scale_rows(limit: int = 2048) -> List[Dict]:
    """Data-scale smoke: >= 2000 ENUMERATED queries (one per finewiki
    pages row, DESIGN.md §12.1) consolidated and run through the
    simulator path whole — pins that the mega-DAG machinery holds at
    the paper's thousands-of-queries scale."""
    from repro.core.consolidate import consolidate
    from repro.workloads import build_enumerated_workload
    g, bindings, _, _ = build_enumerated_workload("ws", limit=limit)
    cons = consolidate(g, bindings)
    halo = run_halo(g, cons, 3)
    opw = run_opwise(g, cons, 3)
    uniq = sum(cons.macros[nid].n_unique for nid in g.nodes)
    return [{"system": "halo-sim-enumerated", "workload": "ws",
             "n_queries": limit, "unique_signatures": uniq,
             "makespan_s": round(halo.makespan, 1),
             "opwise_s": round(opw.makespan, 1),
             "halo_qps": round(halo.throughput_qps(), 3)}]


def recovery_rows() -> List[Dict]:
    """Durable job-store + fault-injection arms on the REAL engines
    (DESIGN.md §12.2/§12.3): a cold run journals, the resumed run must
    replay everything (zero re-executed signatures, zero decode), and a
    seeded chaos run (worker kill + tool faults) must still produce the
    cold run's outputs bitwise."""
    from repro.runtime import FaultPlan
    js = os.path.join(tempfile.mkdtemp(), "journal.jsonl")

    def go(**kw):
        proc, _, cons, _, plan = make_real_processor(
            "wt", n=6, workers=2, decode_cap=3, seed=0, **kw)
        return proc.run(cons, plan)

    cold = go(jobstore_path=js)
    warm = go(jobstore_path=js)
    chaos = go(faults=FaultPlan(seed=1, tool_fail_rate=0.5,
                                max_tool_failures=1, kill_worker={0: 1}),
               tool_retries=3)
    return [
        {"system": "halo-real-cold",
         "makespan_s": round(cold.makespan, 3),
         "jobstore": cold.extra["jobstore"]},
        {"system": "halo-real-resumed",
         "makespan_s": round(warm.makespan, 3),
         "jobstore": warm.extra["jobstore"],
         "decode_tokens": warm.extra["decode_tokens"],
         "outputs_match": warm.extra["results"] == cold.extra["results"]},
        {"system": "halo-real-chaos",
         "makespan_s": round(chaos.makespan, 3),
         "faults": chaos.extra["faults"],
         "tool_retries": chaos.extra["tool_retries"],
         "outputs_match": chaos.extra["results"] == cold.extra["results"]},
    ]


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    for r in run() + scale_rows() + recovery_rows():
        print(r)
