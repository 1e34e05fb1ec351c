"""Bring-up check: the Halo serving path on one TPU at qwen3-1.7b's
published widths (28 layers, d_model 2048, 16/8 heads, vocab 151936).

    python chip_smoke.py                  # on a host with a TPU
    python chip_smoke.py --cpu-rehearsal  # the same phases on the CPU, on
                                          # the smoke preset, with the
                                          # Pallas kernels interpreted

Phases, in one process; a failing phase raises, and the run then exits
nonzero without a result line:

a. device     versions, platform, device kind and count.  Anything but a
              TPU is an error, except under --cpu-rehearsal.
b. engine     ``InferenceEngine`` with XLA attention serves 4 prompts (two
              share a 3-page prefix), greedy, 16 tokens each.  Pages must
              be shared, and the logits of prefill then decode through the
              paged cache must match a cache-free ``model.forward``.
c. pallas     the same with ``attention_impl="pallas"``: flash prefill and
              the paged-decode variant ``kernel_config`` picks.  Its tokens
              equal phase b's, or its logits meet the same tolerance.
d. processor  the ``wt`` workload through the real-mode path of
              ``repro.launch.serve``, twice on the same hosts: every query
              completes with output, and the warm rerun is identical.

The last line of stdout is ``{"ok": true, "device": {...}}``.  Times are
printed for information only; this is not a benchmark.
"""
from __future__ import annotations

import argparse
import importlib.metadata
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))

SEED = 0
PROMPT_LEN = 32          # 4 pages of 8 tokens
SHARED_LEN = 24          # prompts 0 and 1 share 3 whole pages
MAX_NEW = 16
WT_QUERIES = 8
# |engine logits - cache-free logits| <= TOL_REL * max|cache-free logits|:
# both sides run the same bf16 weights, but they round the activations
# of 28 layers at different points (attention over the paged cache vs
# over the whole sequence), which moves logits by a few percent of their
# range; a wrong page, position or mask moves them by their whole range
TOL_REL = 0.05


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise RuntimeError(f"check failed: {msg}")


class CompileCounter:
    """XLA programs compiled (or loaded from the persistent cache), and
    the seconds that took, from JAX's own monitoring events."""

    def __init__(self):
        import jax
        self.programs = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.programs += 1
            self.seconds += secs

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self):
        return self.programs, self.seconds, self.cache_hits

    def since(self, snap) -> str:
        n, s, h = self.snapshot()
        return (f"{n - snap[0]} programs compiled in {s - snap[1]:.2f} s "
                f"({h - snap[2]} from the persistent cache)")


# ------------------------------------------------------------------ phase a
def phase_device(rehearsal: bool):
    import jax
    import jaxlib
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "not installed"
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    log(f"[a] jax {jax.__version__}, jaxlib {jaxlib.__version__}, "
        f"libtpu {libtpu}; platform {device['platform']}, "
        f"device_kind {device['kind']!r}, {device['count']} device(s)")
    stats = dev.memory_stats() or {}
    log(f"[a] device memory: bytes_limit {stats.get('bytes_limit')}, "
        f"bytes_in_use {stats.get('bytes_in_use')}")
    want = "cpu" if rehearsal else "tpu"
    check(device["platform"] == want,
          f"backend is {device['platform']!r}, this run needs {want!r}")
    return device


# ------------------------------------------------------------ phases b, c
def make_prompts(vocab: int):
    import numpy as np
    rng = np.random.default_rng(SEED)
    shared = rng.integers(1, vocab, SHARED_LEN).tolist()
    tail = PROMPT_LEN - SHARED_LEN
    return [shared + rng.integers(1, vocab, tail).tolist(),
            shared + rng.integers(1, vocab, tail).tolist(),
            rng.integers(1, vocab, PROMPT_LEN).tolist(),
            rng.integers(1, vocab, PROMPT_LEN).tolist()]


def cached_logits(model, params, prompts, gens, impl: str):
    """Teacher-forced logits ``(n_prompts, MAX_NEW, vocab)`` of prefill,
    then one paged decode step per generated token, through a
    ``PagedKVCache`` — the model entry points the engine runs."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.engine.kvcache import PagedKVCache
    prefill = jax.jit(model.prefill, static_argnames=("impl",))
    step = jax.jit(model.paged_decode_step, static_argnames=("impl",))
    layers, kv_heads, head_dim = model.paged_kv_layout()
    page = 8
    n_pages = -(-(PROMPT_LEN + MAX_NEW) // page)
    vocab = model.cfg.vocab_size
    out = []
    for prompt, gen in zip(prompts, gens):
        kv = PagedKVCache(layers, n_pages, page, kv_heads, head_dim)
        logits, cache = prefill(params, jnp.asarray([prompt], jnp.int32),
                                impl=impl)
        seq = kv.add_sequence(*model.cache_kv_rows_dev(cache, 0,
                                                       len(prompt)))
        rows = [logits[0, :vocab]]
        for tok in gen[:-1]:
            kv.prepare_append(seq)
            pt = np.zeros((1, n_pages), np.int32)
            ids = kv.page_table(seq)
            pt[0, :len(ids)] = ids
            length = kv.sequences[seq].length
            logits, k, v = step(params, jnp.asarray([tok], jnp.int32), kv.k,
                                kv.v, jnp.asarray(pt),
                                jnp.asarray([length], jnp.int32), impl=impl)
            kv.adopt_pages(k, v)
            kv.commit_append(seq)
            rows.append(logits[0, :vocab])
        out.append(np.asarray(jnp.stack(rows), np.float32))
    return np.stack(out)


def reference_logits(model, params, prompts, gens):
    """Cache-free logits over prompt + generated tokens, at the positions
    that predicted each generated token."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    forward = jax.jit(model.forward)
    vocab = model.cfg.vocab_size
    toks = jnp.asarray([p + g[:-1] for p, g in zip(prompts, gens)],
                       jnp.int32)
    logits, _ = forward(params, toks)
    return np.asarray(logits[:, PROMPT_LEN - 1:, :vocab], np.float32)


def serve_prompts(tag: str, cfg, prompts, counter):
    """Load an engine for ``cfg``, serve ``prompts`` as one continuous
    batch, and return (engine, generated tokens)."""
    from repro.engine.engine import InferenceEngine
    eng = InferenceEngine(cfg, seed=SEED)
    load_s = eng.load()
    snap = counter.snapshot()
    t0 = time.perf_counter()
    handles = [eng.submit(p, max_new_tokens=MAX_NEW) for p in prompts]
    gens = [h.result() for h in handles]
    run_s = time.perf_counter() - t0
    log(f"[{tag}] load {load_s:.2f} s; {len(prompts)} prompts x {MAX_NEW} "
        f"tokens in {run_s:.2f} s incl. {counter.since(snap)}")
    log(f"[{tag}] stats {eng.stats.as_dict()}")
    check(all(len(g) == MAX_NEW for g in gens), "a request came back short")
    return eng, gens


def logit_delta(tag: str, got, ref) -> float:
    import numpy as np
    delta = float(np.abs(got - ref).max())
    scale = float(np.abs(ref).max())
    agree = float((got.argmax(-1) == ref.argmax(-1)).mean())
    log(f"[{tag}] max|logit - cache-free logit| = {delta:.5f}, tolerance "
        f"{TOL_REL} x max|logit| = {TOL_REL * scale:.5f}; argmax agrees "
        f"at {agree:.1%} of positions")
    return delta / scale


def phase_engine(cfg, counter):
    import gc
    prompts = make_prompts(cfg.vocab_size)
    eng, gens = serve_prompts("b", cfg.replace(attention_impl="xla"),
                              prompts, counter)
    check(eng.stats.pages_shared > 0, "no KV page was shared")
    snap = counter.snapshot()
    ref = reference_logits(eng.model, eng.params, prompts, gens)
    got = cached_logits(eng.model, eng.params, prompts, gens, "xla")
    log(f"[b] logit check: {counter.since(snap)}")
    check(logit_delta("b", got, ref) <= TOL_REL,
          "paged-cache logits disagree with the cache-free forward")
    eng.shutdown()
    del eng
    gc.collect()
    return prompts, gens, ref


def phase_pallas(cfg, impl, prompts, gens_xla, ref, counter):
    import gc
    from repro.kernels.paged_decode_attention.ops import kernel_config
    eng, gens = serve_prompts("c", cfg.replace(attention_impl=impl),
                              prompts, counter)
    kc = kernel_config(eng.page_size, cfg.num_kv_heads, eng.model.head_dim,
                       cfg.num_heads // cfg.num_kv_heads)
    log(f"[c] paged decode kernel: {kc}")
    same = gens == gens_xla
    log(f"[c] {impl}: greedy tokens {'equal' if same else 'differ from'} "
        f"phase b's")
    snap = counter.snapshot()
    got = cached_logits(eng.model, eng.params, prompts, gens_xla, impl)
    log(f"[c] logit check: {counter.since(snap)}")
    within = logit_delta("c", got, ref) <= TOL_REL
    check(same or within, "Pallas arm disagrees with the XLA arm")
    eng.shutdown()
    del eng
    gc.collect()


# ------------------------------------------------------------------ phase d
def phase_processor(rehearsal: bool, counter):
    import jax
    from repro.configs import SERVED_ARCH, models_for
    from repro.core import consolidate
    from repro.launch import serve
    from repro.runtime.executors import EngineHost
    from repro.workloads import build_workload

    stats = jax.devices()[0].memory_stats() or {}
    log(f"[d] bytes_in_use before the hosts load: "
        f"{stats.get('bytes_in_use')}")
    graph, bindings, dbname = build_workload("wt", WT_QUERIES, seed=SEED)
    cons = consolidate(graph, bindings)
    models = models_for(graph, SERVED_ARCH, smoke=rehearsal)
    workers, why = serve.workers_that_fit(models, 2)
    log(f"[d] wt x {WT_QUERIES} queries, models {sorted(models)} on "
        f"{SERVED_ARCH}; {why}")
    hw = serve.device_hardware("h200" if rehearsal else None)
    plan = serve.solve_plan(graph, cons, hw, workers)
    log(f"[d] planned as {hw.name}: {len(plan.epochs)} epochs")
    hosts = [EngineHost(models, seed=SEED) for _ in range(workers)]
    reports = []
    try:
        for run in ("cold", "warm"):
            snap = counter.snapshot()
            rep = serve.run_real(graph, cons, plan, dbname, models,
                                 workers=workers, seed=SEED, hosts=hosts)
            x = rep.extra
            log(f"[d] {run} run: makespan {rep.makespan:.2f} s incl. "
                f"{counter.since(snap)}; decode_tokens "
                f"{x['decode_tokens']}, prefill_tokens_saved "
                f"{x['prefill_tokens_saved']}, model switches "
                f"{x['model_switches']}")
            reports.append(rep)
    finally:
        for h in hosts:
            h.shutdown()
    results = reports[0].results()
    missing = [f"{q}:{n}" for q in range(cons.n_queries)
               for n in graph.nodes if not results.get(f"{q}:{n}")]
    check(not missing, f"queries without output: {missing[:5]}")
    check(reports[1].results() == results,
          "warm rerun results differ from the cold run")
    log(f"[d] all {cons.n_queries} queries completed with output; warm "
        f"rerun identical")
    stats = jax.devices()[0].memory_stats()
    peak = stats.get("peak_bytes_in_use") if stats else None
    log(f"[d] peak_bytes_in_use {peak if peak is not None else 'not reported'}"
        f"{'' if peak is None else f' ({peak / 1e9:.2f} GB)'}")


# --------------------------------------------------------------------- main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="run the phases on the CPU at the smoke preset, "
                         "kernels under the Pallas interpreter")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(HERE, "src", "repro")):
        print(f"chip_smoke: no repository sources beside {__file__}",
              file=sys.stderr)
        return 2
    if args.cpu_rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, os.path.join(HERE, "src"))

    from repro.configs import SERVED_ARCH, get_config, get_smoke
    from repro.launch.compile_cache import enable_compile_cache
    t_start = time.perf_counter()
    device = phase_device(args.cpu_rehearsal)
    if not args.cpu_rehearsal:
        log(f"compile cache: {enable_compile_cache()}")
    counter = CompileCounter()
    cfg = (get_smoke if args.cpu_rehearsal else get_config)(SERVED_ARCH)
    log(f"model {SERVED_ARCH}: {cfg.num_layers} layers, d_model "
        f"{cfg.d_model}, {cfg.num_heads}/{cfg.num_kv_heads} heads, vocab "
        f"{cfg.vocab_size}, {cfg.param_count() / 1e9:.3f} B params")
    prompts, gens, ref = phase_engine(cfg, counter)
    phase_pallas(cfg, "pallas_interpret" if args.cpu_rehearsal else "pallas",
                 prompts, gens, ref, counter)
    phase_processor(args.cpu_rehearsal, counter)
    n, s, h = counter.snapshot()
    log(f"total {time.perf_counter() - t_start:.1f} s, of which {n} XLA "
        f"programs compiled in {s:.1f} s ({h} from the persistent cache)")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
