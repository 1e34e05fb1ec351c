"""Fig. 6 — end-to-end batch latency, W1–W6, Halo vs baselines.

``real_rows`` additionally executes the continuous-batching engine for
real (tiny smoke models on CPU) and reports its paged-KV serving
counters — pages shared, tokens reused, admission waves — next to the
makespan.
"""
from __future__ import annotations

from typing import Dict, List

from benchmarks.common import (BASELINES, engine_stat_cols,
                               interleaved_epochs, make_real_processor,
                               run_multi_sim_ab, run_real_multi_ab,
                               run_vllm_serial, setup)

WORKLOADS = ("w1", "w2", "w3", "w4", "w5", "w6")


def run(n_queries: int = 1024, workers: int = 3,
        include_real: bool = False) -> List[Dict]:
    rows = []
    for w in WORKLOADS:
        g, cons, _ = setup(w, n_queries)
        halo_t = None
        for name, fn in BASELINES.items():
            rep = fn(g, cons, workers)
            if name == "halo":
                halo_t = rep.makespan
            rows.append({"workload": w, "system": name,
                         "makespan_s": round(rep.makespan, 2),
                         "speedup_vs_halo": round(rep.makespan /
                                                  max(halo_t, 1e-9), 2)})
        serial = run_vllm_serial(g, cons, workers)
        rows.append({"workload": w, "system": "vllm-serial",
                     "makespan_s": round(serial.makespan, 2),
                     "speedup_vs_halo": round(serial.makespan /
                                              max(halo_t, 1e-9), 2)})
    rows.extend(multi_rows(n_queries, workers))
    if include_real:
        rows.extend(real_rows())
    return rows


def multi_rows(n_queries: int = 384, workers: int = 3) -> List[Dict]:
    """Mixed wd+wt+w4 batch: ONE consolidated mega-DAG vs planning and
    running each template's slice separately (simulated backend).  The
    multi row reports the cross-template static dedup and how many plan
    epochs interleave macro-nodes from different templates — the wins
    per-template planning cannot see (docs/BENCHMARKS.md)."""
    rep, serial_s, plan, mc = run_multi_sim_ab(n_queries, workers)
    xt = mc.cross_template_summary()
    return [
        {"workload": "mixed", "system": "consolidated-multi",
         "makespan_s": round(rep.makespan, 2),
         "epochs": len(plan.epochs),
         "interleaved_epochs": interleaved_epochs(plan, mc),
         "cross_template_deduped": xt["cross_template_deduped"],
         # physical/unique across the mega-DAG's tool macros — NOT the
         # per-node unique/logical ratio ConsolidatedGraph
         # .static_dedup_ratio measures, hence the distinct name
         "xt_physical_ratio": round(
             xt["tool_physical"] / max(xt["tool_unique"], 1), 3)},
        {"workload": "mixed", "system": "per-template-serial",
         "makespan_s": round(serial_s, 2),
         "speedup_vs_multi": round(serial_s / max(rep.makespan, 1e-9), 2)},
    ]


def real_rows(n_queries: int = 6, workers: int = 2,
              decode_cap: int = 4) -> List[Dict]:
    """Real continuous-batching engines on the pure-LLM chain (w+)."""
    proc, _, cons, _, plan = make_real_processor(
        "w+", n_queries, workers, decode_cap)
    rep = proc.run(cons, plan)
    return [{"workload": "w+", "system": "halo-real",
             "makespan_s": round(rep.makespan, 2),
             **engine_stat_cols(rep)}] + pipelining_rows(
        n_queries, workers, max(decode_cap, 6)) + migration_rows(
        min(n_queries, 4), workers) + paged_rows(
        min(n_queries, 4), workers) + real_multi_rows(
        n_queries, workers)


def real_multi_rows(n_queries: int = 6, workers: int = 2,
                    decode_cap: int = 3) -> List[Dict]:
    """Mixed wd+wt+w4 batch through REAL engines: one mega-DAG run vs
    each template's slice run serially.  BOTH arms are measured warm
    (per-arm throwaway run first) and by their reports' makespans, so
    the comparison is steady-state serving throughput, not JIT/setup
    cost.  The multi row's makespan is <= the serial row's sum, it
    reports runtime cross-template tool merges (``xt_merged_requests``)
    next to the engine's page-sharing counters, and temp-0 outputs are
    bitwise-identical across the arms (pinned in
    tests/test_multi_template.py)."""
    rep, serial_reports, serial_s, mc, plan = run_real_multi_ab(
        n_queries, workers, decode_cap)
    xt = mc.cross_template_summary()
    return [
        {"workload": "mixed", "system": "consolidated-multi-real",
         "makespan_s": round(rep.makespan, 3),
         "epochs": len(plan.epochs),
         "interleaved_epochs": interleaved_epochs(plan, mc),
         "xt_deduped_static": xt["cross_template_deduped"],
         "xt_merged_requests": rep.coalesce_stats.get(
             "cross_template_merged_requests", 0),
         **engine_stat_cols(rep)},
        {"workload": "mixed", "system": "per-template-serial-real",
         "makespan_s": round(serial_s, 3),
         "speedup_vs_multi": round(serial_s / max(rep.makespan, 1e-9), 2)},
    ]


def pipelining_rows(n_queries: int = 6, workers: int = 2,
                    decode_cap: int = 6) -> List[Dict]:
    """WT tool-pipeline: per-request pipelining vs the macro barrier on
    WARM engines (steady-state serving; a first run pays JIT compile).
    The pipelined row shows ``cpu_gpu_overlap_s > 0`` — tool tasks of
    early-retiring queries running under the stragglers' decode."""
    from repro.runtime.executors import EngineHost
    rows = []
    for pipe, name in ((False, "halo-real-barrier"),
                       (True, "halo-real-pipelined")):
        proc, _, cons, _, plan = make_real_processor(
            "wt", n_queries, workers, decode_cap,
            latency_scale=1.0, pipelining=pipe)
        hosts = [EngineHost(proc.model_configs, seed=proc.seed)
                 for _ in range(workers)]
        try:
            proc.run(cons, plan, hosts=hosts)          # warm the engines
            rep = proc.run(cons, plan, hosts=hosts)
        finally:
            for h in hosts:
                h.shutdown()
        rows.append({"workload": "wt", "system": name,
                     "makespan_s": round(rep.makespan, 3),
                     **engine_stat_cols(rep)})
    return rows


def migration_rows(n_queries: int = 4, workers: int = 2,
                   decode_cap: int = 3) -> List[Dict]:
    """Cross-worker KV migration A/B on warm hosts: a forced replan
    moves every w+ node to the other worker, with migration on vs off.
    The on-row shows ``pages_migrated > 0`` and strictly more
    ``prefill_tokens_saved`` (the moved nodes' warm lineage follows them
    instead of stranding); outputs are identical either way."""
    from benchmarks.common import run_migration_ab
    rep_on, rep_off, _ = run_migration_ab(
        "w+", n_queries, workers, decode_cap)
    return [{"workload": "w+", "system": name,
             "makespan_s": round(rep.makespan, 3),
             **engine_stat_cols(rep)}
            for name, rep in (("halo-real-migrate", rep_on),
                              ("halo-real-no-migrate", rep_off))]


def paged_rows(n_queries: int = 4, workers: int = 2,
               decode_cap: int = 4) -> List[Dict]:
    """Device-resident paged decode vs the dense-view reference path on
    warm WT hosts.  The paged row shows ``view_rebuilds == 0`` and a
    >=10x drop in ``h2d_bytes + d2h_bytes`` (the host gather and the
    per-step KV tap sync are gone); outputs are identical either way."""
    from benchmarks.common import run_paged_ab
    rep_p, rep_d = run_paged_ab("wt", n_queries, workers, decode_cap)
    return [{"workload": "wt", "system": name,
             "makespan_s": round(rep.makespan, 3),
             **engine_stat_cols(rep)}
            for name, rep in (("halo-real-paged", rep_p),
                              ("halo-real-dense-view", rep_d))]


def kernel_rows(n_queries: int = 4, workers: int = 2,
                decode_cap: int = 4) -> List[Dict]:
    """Autotuned fused multi-page paged-decode kernel vs the single-page
    baseline on warm WT hosts (both arms paged + Pallas).  Rows carry
    tokens/s-per-device — the quantity the nightly gate and the >=1.3x
    fused-vs-single check track — plus ``outputs_match`` pinning the
    bitwise-identity contract.  Under the Pallas interpreter
    (``interpret: true``, asked for with REPRO_PALLAS_INTERPRET=1) the
    throughput numbers measure the interpreter and every timing gate
    skips them."""
    from benchmarks.common import run_kernel_ab
    rep_f, rep_s, interp = run_kernel_ab("wt", n_queries, workers,
                                         decode_cap)
    match = rep_f.extra.get("results") == rep_s.extra.get("results")
    rows = []
    for name, rep in (("halo-real-kernel-fused", rep_f),
                      ("halo-real-kernel-single", rep_s)):
        tps = rep.extra.get("decode_tokens", 0.0) / max(
            rep.makespan, 1e-9) / workers
        rows.append({"workload": "wt", "system": name,
                     "makespan_s": round(rep.makespan, 3),
                     "tokens_per_s_per_device": round(tps, 2),
                     "outputs_match": match, "interpret": interp,
                     **engine_stat_cols(rep)})
    return rows


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    for r in run(256, include_real=True):
        print(r)
