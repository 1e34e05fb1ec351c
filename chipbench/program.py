"""The benchmark's one boundary with the system under test.

Everything the harness knows about the program's internals is here:
which modules it imports, how it hands the engines the benchmark's
weights, how it reads the engines' counters and the requests they
served, and how it builds the Processor objects a driver calls.  The
program's sources live in ``<checkout>/src``.
"""
from __future__ import annotations

import os
import sys
import threading
from typing import Dict, List, Optional, Tuple

COUNTERS = ("prefill_tokens", "prefill_tokens_saved", "decode_tokens",
            "coalesced_requests")


def import_program(root: str):
    """Put ``<root>/src`` on the path; an error when it holds no program."""
    src = os.path.join(root, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise FileNotFoundError(f"no program sources under {src}")
    if src not in sys.path:
        sys.path.insert(0, src)


def model_config(conf: dict, rehearsal: bool, arch):
    """The ``ModelConfig`` a configuration file states: its arch's
    published config with the file's ``overrides`` (``rehearsal``: the
    arch's smoke preset with ``rehearsal_overrides``), checked key by key
    against what the file fixes, ``arch.expect(conf)``."""
    from repro.configs import get_config, get_smoke
    if rehearsal:
        mc = get_smoke(conf["arch"]).replace(
            **conf.get("rehearsal_overrides", {}))
        return mc
    mc = get_config(conf["arch"]).replace(**conf.get("overrides", {}))
    want = arch.expect(conf)
    bad = {k: (getattr(mc, k), v) for k, v in want.items()
           if getattr(mc, k) != v}
    if bad:
        raise ValueError(f"{conf['arch']} as run differs from its "
                         f"configuration file: {bad}")
    return mc


def param_shapes(mc):
    """The served model's parameter tree as ``ShapeDtypeStruct``s."""
    import jax
    from repro.engine.models import build_model
    return jax.eval_shape(build_model(mc).init, jax.random.PRNGKey(0))


def install_weights(seed: int) -> None:
    """Make every engine load the benchmark's weights for ``seed``: the
    engine's one weight source, ``_init_params(cfg, key)``, is replaced
    by the benchmark's seeded draw of the same tree (same leaves, shapes
    and dtypes, checked)."""
    import jax
    from repro.engine import engine as E
    from chipbench import weights

    def init(cfg, key):
        shapes = param_shapes(cfg)
        params = weights.make_params(shapes, seed)
        if jax.tree.structure(params) != jax.tree.structure(shapes):
            raise RuntimeError("benchmark weights do not match the "
                               "program's parameter tree")
        return params

    E._init_params = init


class RequestLog:
    """Every request the engines served, as ``(prompt, max_new) ->
    tokens``, recorded from ``InferenceEngine.submit``'s handles.  The
    hook is installed once per process; the newest log records."""

    _current: Optional["RequestLog"] = None

    def __init__(self):
        self.lock = threading.Lock()
        self.served: Dict[Tuple[Tuple[int, ...], int], List[int]] = {}
        self.completed = 0
        if RequestLog._current is None:
            self._install()
        RequestLog._current = self

    @staticmethod
    def _install() -> None:
        from repro.engine.engine import InferenceEngine
        orig = InferenceEngine.submit

        def submit(eng, prompt, *a, **kw):
            h = orig(eng, prompt, *a, **kw)
            key = (tuple(int(t) for t in prompt),
                   int(kw.get("max_new_tokens", 16)))
            log = RequestLog._current

            def done(handle):
                if handle.exception() is None:
                    toks = handle.result(timeout=0)
                    with log.lock:
                        log.served[key] = list(toks)
                        log.completed += 1
            h.add_done_callback(done)
            return h

        InferenceEngine.submit = submit

    def clear(self) -> None:
        with self.lock:
            self.served = {}
            self.completed = 0


def counters(hosts) -> Dict[str, int]:
    """Engine counters summed over every engine of ``hosts``, and model
    switches."""
    engines = [e for h in hosts for e in h.engines()]
    out = {k: sum(getattr(e.stats, k) for e in engines) for k in COUNTERS}
    out["switches"] = sum(h.switches for h in hosts)
    return out


def workers_that_fit(models, requested: int) -> int:
    from repro.launch.serve import workers_that_fit as fit
    return fit(models, requested)[0]


def models_for(graphs, mc) -> dict:
    """Every model id the graphs' LLM nodes name, served by ``mc``."""
    names = {g.nodes[n].model for g in graphs for n in g.llm_nodes()}
    return {m: mc.replace(name=m) for m in sorted(names)}


def parse(workflow: dict):
    """A workflow dict as the Processor's ``GraphSpec``, with the
    program's own paper-scale latency estimates on its tool nodes (what
    its planner prices a tool at when the template gives no estimate)."""
    from repro.core.graphspec import GraphSpec
    from repro.core.parser import parse_workflow
    from repro.workloads.library import _paper_scale_estimate
    g = parse_workflow(workflow)
    nodes = [spec.with_(est_seconds=_paper_scale_estimate(spec.op,
                                                          spec.args))
             if not spec.is_llm() and not spec.est_seconds else spec
             for spec in g.nodes.values()]
    return GraphSpec(g.name, nodes, g.edges)


def database(name: str):
    from repro.workloads.datagen import build_database
    return build_database(name)


def tool_runtime(db, latency_scale: float = 1.0):
    from repro.workloads.tools import ToolRuntime
    return ToolRuntime(db, latency_scale=latency_scale)


def hosts(models, workers: int) -> list:
    from repro.runtime.executors import EngineHost
    return [EngineHost(models, seed=0) for _ in range(workers)]


def free_hosts(hosts) -> None:
    """Stop every engine loop and drop its weights and KV pool."""
    for h in hosts:
        for e in h.engines():
            e.shutdown()
            e.params = None
            e.kv = None
            e._view = None


def hardware(rehearsal: bool):
    from repro.core import HARDWARE
    from repro.launch.serve import device_hardware
    return HARDWARE["h200"] if rehearsal else device_hardware()


def consolidate(graph, bindings):
    from repro.core import consolidate as c
    return c(graph, bindings)


def solve_plan(graph, cons, hw, workers: int):
    from repro.launch.serve import solve_plan as s
    return s(graph, cons, hw, workers)


def run_batch(graph, models, tools, cons, plan, hosts, workers: int):
    """One ``RealProcessor.run`` on persistent hosts; its answers as
    ``{query: {node: text}}``."""
    from repro.runtime import ProcessorConfig, RealProcessor
    proc = RealProcessor(graph, models, tools,
                         config=ProcessorConfig(num_workers=workers, seed=0))
    rep = proc.run(cons, plan, hosts=hosts)
    out: Dict[int, Dict[str, str]] = {}
    for key, val in rep.results().items():
        q, _, node = key.partition(":")
        out.setdefault(int(q), {})[node] = val
    return out
