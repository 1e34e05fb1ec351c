"""Shared benchmark plumbing: cost models, plans, baseline system models.

Baseline systems are modeled per §6.1:
* vllm-serial   — query-by-query: N × single-query makespan;
* opwise        — stage-synchronous executor (OpWiseSimulator);
* langgraph     — decoupled orchestration: engine-level batching still
                  applies (requests submitted together) but NO workflow-
                  level coalescing and topology-blind RR placement;
* agentscope    — actor isolation: like langgraph but placement is
                  random (actors don't coordinate workers);
* parrot        — prefix/semantic-aware serving: engine batching +
                  locality-greedy (HEFT-style) placement, but no tool
                  coalescing and no CPU-GPU co-scheduling.
"""
from __future__ import annotations

from typing import Dict, Tuple

from repro.core import (CostModel, EpochDPSolver, HARDWARE, PAPER_MODELS,
                        SolverConfig, consolidate, consolidate_multi,
                        heft_plan, random_plan, round_robin_plan)
from repro.core.consolidate import ConsolidatedGraph, MultiConsolidatedGraph
from repro.core.graphspec import GraphSpec
from repro.runtime import OpWiseSimulator, SimulatedProcessor
from repro.workloads import MIXED_PARTS, build_mixed_workload, build_workload


def setup(workload: str, n: int, seed: int = 0
          ) -> Tuple[GraphSpec, ConsolidatedGraph]:
    g, bindings, _ = build_workload(workload, n, seed=seed)
    return g, consolidate(g, bindings), bindings


def setup_multi(n: int, seed: int = 0, parts=MIXED_PARTS
                ) -> Tuple[GraphSpec, MultiConsolidatedGraph, list, str]:
    """(merged graph, multi-cons, per-template batches, database) for a
    mixed multi-template batch."""
    batches, db = build_mixed_workload(n, seed=seed, parts=parts)
    mc = consolidate_multi(batches)
    return mc.template, mc, batches, db


def make_cm(g: GraphSpec, cons: ConsolidatedGraph, *, logical_tools=False,
            hardware="h200", **kw) -> CostModel:
    batch = {}
    for nid in g.nodes:
        m = cons.macro(nid)
        # tools price their PHYSICAL count — multi-template mega-DAGs
        # drop signatures another template's node already owns
        batch[nid] = (m.n_logical if (g.nodes[nid].is_llm() or logical_tools)
                      else len(cons.physical_signatures(nid)))
    return CostModel(g, HARDWARE[hardware], PAPER_MODELS,
                     batch_sizes=batch, warm_aliases=cons.warm_aliases(),
                     **kw)


def halo_plan(g, cons, workers=3, **cm_kw):
    cm = make_cm(g, cons, **cm_kw)
    return EpochDPSolver(g.llm_dag(), cm,
                         SolverConfig(num_workers=workers)).solve()


def run_halo(g, cons, workers=3, hardware="h200", processor_batch=256,
             plan=None):
    plan = plan or halo_plan(g, cons, workers, hardware=hardware)
    sim = SimulatedProcessor(g, make_cm(g, cons, hardware=hardware), workers,
                             processor_batch=processor_batch)
    return sim.run(cons, plan)


def run_opwise(g, cons, workers=3, hardware="h200", processor_batch=256):
    return OpWiseSimulator(g, make_cm(g, cons, hardware=hardware), workers,
                           processor_batch=processor_batch).run(cons)


def run_langgraph(g, cons, workers=3, hardware="h200"):
    cm = make_cm(g, cons, logical_tools=True, hardware=hardware)
    plan = round_robin_plan(g.llm_dag(), cm, workers)
    sim = SimulatedProcessor(g, cm, workers, coalescing=False,
                             kv_migration=False)
    rep = sim.run(cons, plan)
    rep.name = "langgraph"
    return rep


def run_agentscope(g, cons, workers=3, hardware="h200", seed=1):
    cm = make_cm(g, cons, logical_tools=True, hardware=hardware)
    plan = random_plan(g.llm_dag(), cm, workers, seed=seed)
    sim = SimulatedProcessor(g, cm, workers, coalescing=False,
                             kv_migration=False)
    rep = sim.run(cons, plan)
    rep.name = "agentscope"
    return rep


def run_parrot(g, cons, workers=3, hardware="h200"):
    cm = make_cm(g, cons, logical_tools=True, hardware=hardware)
    plan = heft_plan(g.llm_dag(), cm, workers)
    sim = SimulatedProcessor(g, cm, workers, coalescing=False,
                             kv_migration=False)
    rep = sim.run(cons, plan)
    rep.name = "parrot"
    return rep


def run_vllm_serial(g, cons_full, workers=3, hardware="h200"):
    """Query-by-query: the whole DAG for one query completes before the
    next starts (engine sees batch=1 everywhere)."""
    g1, cons1, _ = setup_from(g, cons_full, 1)
    cm = make_cm(g1, cons1, logical_tools=True, hardware=hardware)
    plan = round_robin_plan(g1.llm_dag(), cm, workers)
    rep1 = SimulatedProcessor(g1, cm, workers, coalescing=False,
                              kv_migration=False).run(cons1, plan)
    n = cons_full.n_queries
    rep1.makespan *= n
    rep1.num_queries = n
    rep1.name = "vllm-serial"
    return rep1


def setup_from(g, cons, n):
    sub = ConsolidatedGraph(g, cons.bindings[:n])
    return g, sub, cons.bindings[:n]


BASELINES = {
    "halo": run_halo,
    "opwise": run_opwise,
    "langgraph": run_langgraph,
    "agentscope": run_agentscope,
    "parrot": run_parrot,
}


# ---------------------------------------------------------------------------
# real-engine mode (tiny smoke models behind the continuous-batching engine)
# ---------------------------------------------------------------------------

def smoke_models_for(g: GraphSpec):
    """Every model the graph names on the served arch's smoke preset, so
    the real continuous-batching engines can run it on CPU."""
    from repro.configs import models_for
    return models_for(g, smoke=True)


def make_real_processor(workload="w+", n=6, workers=2, decode_cap=4,
                        seed=0, latency_scale=0.0, **proc_kw):
    """(processor, graph, cons, bindings, plan) for real-engine runs.

    ``proc_kw`` holds further ProcessorConfig fields (``pipelining``,
    ``engine_kwargs``, ...)."""
    from repro.runtime import ProcessorConfig, RealProcessor
    from repro.workloads.datagen import build_database
    from repro.workloads.tools import ToolRuntime
    g, bindings, dbname = build_workload(workload, n, seed=seed)
    cons = consolidate(g, bindings)
    plan = halo_plan(g, cons, workers)
    proc = RealProcessor(
        g, smoke_models_for(g),
        ToolRuntime(build_database(dbname), latency_scale=latency_scale),
        config=ProcessorConfig(num_workers=workers, decode_cap=decode_cap,
                               seed=seed, **proc_kw))
    return proc, g, cons, bindings, plan


def swapped_tail(plan, g, workers: int):
    """Forced-replan tail moving EVERY LLM node to the next worker
    (singleton topo-order epochs) — the migration A/B stimulus shared by
    benchmarks and tests."""
    from repro.core.plan import Epoch, ExecutionPlan
    amap = plan.assignment_map()
    llm = set(g.llm_dag().node_ids)
    topo = [v for v in g.topo_order() if v in llm]
    return ExecutionPlan(
        [Epoch([[n]], [(amap[n] + 1) % workers]) for n in topo],
        scheduler_name="forced-swap")


def run_migration_ab(workload="w+", n=4, workers=2, decode_cap=3):
    """Warm persistent hosts, then re-run under a forced splice that
    moves every node across workers — once with cross-worker KV
    migration, once without.  Returns (rep_on, rep_off, warm_rep);
    the shared harness behind the migration benchmark rows AND the
    acceptance test."""
    from repro.runtime import OnlineOptimizer
    from repro.runtime.executors import EngineHost
    reps = {}
    for migration in (True, False):
        proc, g, cons, _, plan = make_real_processor(
            workload, n, workers, decode_cap, kv_migration=migration)
        hosts = [EngineHost(proc.model_configs, seed=proc.seed)
                 for _ in range(workers)]
        try:
            warm = proc.run(cons, plan, hosts=hosts)
            # drift threshold pinned high: ONLY the queued forced splice
            # may fire, so the A/B stimulus is deterministic (CPU smoke
            # latencies sit far off the roofline and would otherwise
            # drift-replan on their own, timing-dependently)
            opt = OnlineOptimizer(make_cm(g, cons), drift_threshold=1e9)
            opt.queue_splice(swapped_tail(plan, g, workers))
            reps[migration] = proc.run(cons, plan, hosts=hosts,
                                       optimizer=opt)
        finally:
            for h in hosts:
                h.shutdown()
    return reps[True], reps[False], warm


def run_paged_ab(workload="wt", n=4, workers=2, decode_cap=4):
    """Warm persistent hosts, then measure the SAME run with the
    device-resident paged decode path vs the dense-view reference path.
    Returns (rep_paged, rep_dense); the paged row shows
    ``view_rebuilds == 0`` and an order-of-magnitude drop in
    ``h2d_bytes + d2h_bytes`` (per-step KV traffic is O(batch) ints,
    not O(batch x seq_len) KV), with bitwise-identical temp-0 outputs.
    KV migration is off in both arms so the counters isolate the decode
    path (migration staging is legitimate h2d/d2h on both)."""
    from repro.runtime.executors import EngineHost
    reps = {}
    for paged in (True, False):
        proc, g, cons, _, plan = make_real_processor(
            workload, n, workers, decode_cap, kv_migration=False,
            engine_kwargs={"paged_decode": paged})
        hosts = [EngineHost(proc.model_configs, seed=proc.seed,
                            engine_kwargs=proc.engine_kwargs)
                 for _ in range(workers)]
        try:
            proc.run(cons, plan, hosts=hosts)     # warm pages + JIT caches
            reps[paged] = proc.run(cons, plan, hosts=hosts)
        finally:
            for h in hosts:
                h.shutdown()
    return reps[True], reps[False]


def run_kernel_ab(workload="wt", n=4, workers=2, decode_cap=4):
    """Warm persistent hosts, then measure the SAME paged run with the
    autotuned fused multi-page kernel vs the single-page baseline.
    Returns (rep_fused, rep_single, interpret).

    Both arms run the Pallas paged-decode path (``paged_decode`` on);
    only ``kernel_variant`` differs, so the delta isolates the kernel:
    multi-page double-buffered KV blocks plus the fused append
    (eliminating the separate scatter dispatch per decode step).
    Temp-0 outputs are bitwise identical across arms — masked pages are
    exact no-ops in the online-softmax recurrence.  The kernels run
    under the Pallas interpreter only when asked
    (REPRO_PALLAS_INTERPRET=1), where timings are meaningless; callers
    gate throughput claims on the returned flag.  Without a TPU and
    without that request it raises."""
    from benchmarks.kernel_bench import interpret_mode
    from repro.runtime.executors import EngineHost
    interp = interpret_mode()
    impl = "pallas_interpret" if interp else "pallas"
    reps = {}
    for variant in ("fused", "single"):
        proc, g, cons, _, plan = make_real_processor(
            workload, n, workers, decode_cap, kv_migration=False,
            engine_kwargs={"paged_decode": True,
                           "kernel_variant": variant})
        proc.model_configs = {m: c.replace(attention_impl=impl)
                              for m, c in proc.model_configs.items()}
        hosts = [EngineHost(proc.model_configs, seed=proc.seed,
                            engine_kwargs=proc.engine_kwargs)
                 for _ in range(workers)]
        try:
            proc.run(cons, plan, hosts=hosts)     # warm pages + JIT caches
            reps[variant] = proc.run(cons, plan, hosts=hosts)
        finally:
            for h in hosts:
                h.shutdown()
    return reps["fused"], reps["single"], interp


def interleaved_epochs(plan, mc: MultiConsolidatedGraph) -> int:
    """Epochs whose macro-nodes come from >= 2 templates — the shared
    decode batches only a mega-DAG plan can form."""
    n = 0
    for e in plan.epochs:
        tmpls = {mc.template_of[v] for comp in e.components for v in comp}
        if len(tmpls) >= 2:
            n += 1
    return n


def run_multi_sim_ab(n: int = 384, workers: int = 3, seed: int = 0,
                     parts=MIXED_PARTS):
    """Simulated consolidated-multi vs per-template-serial A/B.

    The multi arm plans ONE mega-DAG over the mixed batch (epoch packing
    may interleave templates; cross-template signatures dedup); the
    serial arm consolidates and runs each template's slice on its own,
    one after another.  Returns (rep_multi, serial_makespan, plan, mc).
    """
    g, mc, batches, _ = setup_multi(n, seed=seed, parts=parts)
    plan = halo_plan(g, mc, workers)
    rep = SimulatedProcessor(g, make_cm(g, mc), workers).run(mc, plan)
    serial = 0.0
    for tg, tb in batches:
        cons = consolidate(tg, tb)
        p = halo_plan(tg, cons, workers)
        serial += SimulatedProcessor(
            tg, make_cm(tg, cons), workers).run(cons, p).makespan
    return rep, serial, plan, mc


def make_real_multi_processor(n=6, workers=2, decode_cap=3, seed=0,
                              parts=MIXED_PARTS, **proc_kw):
    """(processor, merged graph, multi-cons, batches, plan, db) for a
    real-engine mixed-batch run."""
    from repro.runtime import ProcessorConfig, RealProcessor
    from repro.workloads.datagen import build_database
    from repro.workloads.tools import ToolRuntime
    g, mc, batches, db = setup_multi(n, seed=seed, parts=parts)
    plan = halo_plan(g, mc, workers)
    proc = RealProcessor(
        g, smoke_models_for(g),
        ToolRuntime(build_database(db), latency_scale=0.0),
        config=ProcessorConfig(num_workers=workers, decode_cap=decode_cap,
                               seed=seed, **proc_kw))
    return proc, g, mc, batches, plan, db


def run_real_multi_ab(n: int = 6, workers: int = 2, decode_cap: int = 3,
                      seed: int = 0, parts=MIXED_PARTS):
    """REAL-engine consolidated-multi vs per-template-serial A/B.

    Returns (rep_multi, serial_reports, serial_seconds, mc, plan).  The
    serial arm runs each template's slice as its own batch, one after
    another.  BOTH arms run on warm persistent hosts (one throwaway run
    first, like the other A/B harnesses) so the measurement is
    steady-state serving, not JIT compilation, and both arms are timed
    the SAME way (their reports' makespans; serial sums them) so fixed
    setup cost can't bias the comparison; outputs are
    bitwise-comparable to the multi arm's at temperature 0.
    """
    from repro.runtime import ProcessorConfig, RealProcessor
    from repro.runtime.executors import EngineHost
    from repro.workloads.datagen import build_database
    from repro.workloads.tools import ToolRuntime
    proc, g, mc, batches, plan, db = make_real_multi_processor(
        n, workers, decode_cap, seed, parts)
    hosts = [EngineHost(proc.model_configs, seed=proc.seed)
             for _ in range(workers)]
    try:
        proc.run(mc, plan, hosts=hosts)              # warm (JIT + pages)
        rep_multi = proc.run(mc, plan, hosts=hosts)
    finally:
        for h in hosts:
            h.shutdown()
    serial_reports = []
    serial_seconds = 0.0
    for tg, tb in batches:
        cons = consolidate(tg, tb)
        p = halo_plan(tg, cons, workers)
        pr = RealProcessor(
            tg, smoke_models_for(tg),
            ToolRuntime(build_database(db), latency_scale=0.0),
            config=ProcessorConfig(num_workers=workers,
                                   decode_cap=decode_cap, seed=seed))
        shosts = [EngineHost(pr.model_configs, seed=pr.seed)
                  for _ in range(workers)]
        try:
            pr.run(cons, p, hosts=shosts)            # warm
            rep = pr.run(cons, p, hosts=shosts)
            serial_reports.append(rep)
            serial_seconds += rep.makespan
        finally:
            for h in shosts:
                h.shutdown()
    return rep_multi, serial_reports, serial_seconds, mc, plan


def engine_stat_cols(rep) -> Dict[str, float]:
    """The continuous-batching engine counters a RunReport carries."""
    x = rep.extra
    return {
        "prefill_tokens_saved": x.get("prefill_tokens_saved", 0),
        "kv_pages_shared": x.get("pages_shared", 0),
        "kv_tokens_reused": x.get("tokens_reused", 0),
        "admission_waves": x.get("admission_waves", 0),
        "peak_batch": x.get("peak_batch", 0),
        "coalesced_requests": x.get("coalesced_requests", 0),
        "cpu_gpu_overlap_s": x.get("cpu_gpu_overlap_s", 0.0),
        "replans": x.get("replans", 0),
        "pages_migrated": x.get("pages_migrated_in", 0),
        "migrate_s": x.get("migrate_seconds", 0.0),
        "h2d_bytes": x.get("h2d_bytes", 0),
        "d2h_bytes": x.get("d2h_bytes", 0),
        "view_rebuilds": x.get("view_rebuilds", 0),
    }
