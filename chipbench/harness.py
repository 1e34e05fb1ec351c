"""One run of one cell: set-up, the measured window, the check.

Everything a run reads comes from files found by name: the cell in
``BENCHMARK.json``, its configuration file, the module of the
configuration's architecture (``chipbench/arch/<model_type>.py``: its
reference and work counts), its traffic mix
(``chipbench/traffic/<traffic>.json``), the chip's peaks
(``chipbench/peaks.json``) and one reader per metric
(``chipbench/metrics/<metric>.py``).
"""
from __future__ import annotations

import gc
import importlib.util
import json
import os
import sys
import threading
import time
from types import ModuleType, SimpleNamespace
from typing import Dict, List, Optional

from chipbench import check, drivers, generate, program

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
OUT = os.path.join(HERE, "out")
CACHE_DIR = os.path.join(OUT, "jax_cache")


class SetupError(RuntimeError):
    """The run cannot measure: no chip, an unknown chip, a missing file."""


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str) -> ModuleType:
    """The Python file at ``path`` as a module named ``name``."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_ARCHS: Dict[str, ModuleType] = {}


def arch_of(conf: dict, root: str = ROOT) -> ModuleType:
    """The module of a configuration's architecture,
    ``<root>/chipbench/arch/<model_type>.py`` (each module's docstring
    lists what it provides), loaded once per path so that its jitted
    reference compiles once per process.  The metric readers find it
    under the checkout they run from."""
    path = os.path.join(root, "chipbench", "arch",
                        f"{conf['model_type']}.py")
    if path not in _ARCHS:
        if not os.path.isfile(path):
            raise SetupError(f"no module for architecture "
                             f"{conf['model_type']!r} at {path}")
        _ARCHS[path] = load_module(
            path, f"chipbench_arch_{conf['model_type']}")
    return _ARCHS[path]


class Bench:
    """``BENCHMARK.json`` and the files its names lead to."""

    def __init__(self, root: str = ROOT):
        self.root = root
        self.spec = load_json(os.path.join(root, "BENCHMARK.json"))

    def cell(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise SetupError(f"no cell {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        for c in self.spec["configs"]:
            if c["name"] == name:
                return load_json(os.path.join(self.root, c["file"]))
        raise SetupError(f"no configuration {name!r} in BENCHMARK.json")

    def arch(self, conf: dict) -> ModuleType:
        return arch_of(conf, self.root)

    def mix(self, traffic: str) -> dict:
        return load_json(os.path.join(self.root, "chipbench", "traffic",
                                      f"{traffic}.json"))

    def metrics_for(self, cell: str, per_layer: bool) -> List[dict]:
        key = "per_layer" if per_layer else "end_to_end"
        return [m for m in self.spec[key]
                if cell in m.get("workloads", [cell])]

    def reader(self, metric: str):
        path = os.path.join(self.root, "chipbench", "metrics",
                            f"{metric}.py")
        if not os.path.isfile(path):
            raise SetupError(f"no reader for metric {metric!r} at {path}")
        return load_module(
            path, f"chipbench_metric_{metric.replace('.', '_')}").read


def peaks_for(kind: str, root: str = ROOT) -> dict:
    """The chip's published peaks; an unknown ``device_kind`` is an error."""
    table = load_json(os.path.join(root, "chipbench", "peaks.json"))
    if kind not in table["devices"]:
        raise SetupError(f"device kind {kind!r} is not in peaks.json "
                         f"(known: {sorted(table['devices'])})")
    return table["devices"][kind]


class CompileCounter:
    """XLA programs built (compiled, or loaded from the persistent cache),
    from JAX's monitoring events; one per process (``get()``)."""

    _one: Optional["CompileCounter"] = None

    def __init__(self):
        import jax
        self.built = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    @classmethod
    def get(cls) -> "CompileCounter":
        if cls._one is None:
            cls._one = cls()
        return cls._one

    def _duration(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.built += 1

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.built += 1

    def count(self) -> int:
        return self.built


def start_jax(rehearsal: bool, chips: int):
    """Import JAX with the checkout's compile cache; the device facts.
    Without a TPU (or with fewer chips than the cell asks for) this is a
    SetupError, unless ``rehearsal`` asks for the CPU on purpose."""
    if rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
    # libtpu logs to /tmp/tpu_logs unless told otherwise: keep it inside
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(OUT, "tpu_logs"))
    import jax
    if not rehearsal:
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devs = jax.devices()
    platform = devs[0].platform
    if rehearsal:
        if platform != "cpu":
            raise SetupError("--rehearsal runs on the CPU only")
    elif platform != "tpu":
        raise SetupError(f"no accelerator: JAX found {platform!r}")
    if not rehearsal and len(devs) < chips:
        raise SetupError(f"the cell needs {chips} chips, JAX found "
                         f"{len(devs)}")
    return devs


class Tracer:
    """Records one profiler trace of a steady sub-window of the run."""

    def __init__(self, trace_dir: str, offset: float, length: float):
        self.dir, self.offset, self.length = trace_dir, offset, length
        self.thread: Optional[threading.Thread] = None
        self.error: Optional[BaseException] = None

    def start_at(self, t0: float) -> None:
        def run():
            import jax
            from chipbench.trace import WINDOW_MARK
            try:
                time.sleep(max(0.0, t0 + self.offset - time.perf_counter()))
                # the Python tracer records every call and would slow the
                # host and swell the trace; runtime events are enough
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                jax.profiler.start_trace(self.dir, profiler_options=opts)
                with jax.profiler.TraceAnnotation(WINDOW_MARK):
                    time.sleep(self.length)
                jax.profiler.stop_trace()
            except BaseException as e:
                self.error = e
        self.thread = threading.Thread(target=run, name="chipbench-trace",
                                       daemon=True)
        self.thread.start()

    def join(self) -> None:
        if self.thread is not None:
            self.thread.join()


def run_cell(name: str, seed: int, seconds: float, trace: bool,
             rehearsal: bool = False, t_process: Optional[float] = None,
             control: bool = False, bench: Optional[Bench] = None) -> dict:
    """One run of cell ``name``: the result object of ``run.py``, with the
    numbers compared under ``checks`` (last).  With ``control`` the check
    also scores the float8 control in the program's place, and that
    verdict joins ``correct``: a control run is not correct."""
    t_process = time.perf_counter() if t_process is None else t_process
    bench = bench or Bench()
    cell = bench.cell(name)
    conf = bench.config(cell["config"])
    mix = bench.mix(cell["traffic"])
    drive = drivers.DRIVERS.get(mix["driver"])
    if drive is None:
        raise SetupError(f"mix {cell['traffic']!r} names no known driver: "
                         f"{mix['driver']!r}")
    devs = start_jax(rehearsal, int(cell["chips"]))
    dev = devs[0]
    peak = None if rehearsal else peaks_for(dev.device_kind, bench.root)
    program.import_program(bench.root)
    ctx = setup(conf, bench.arch(conf), mix, seed, rehearsal, drive)
    ctx.spans.tracing = trace
    tracer = None
    if trace:
        tracer = Tracer(os.path.join(OUT, "traces", f"{name}-{seed}"),
                        0.25 * seconds, min(4.0, 0.5 * seconds))
    win = drive(ctx, seconds, seed, on_start=tracer.start_at if tracer
                else None)
    if tracer:
        tracer.join()
    stats = dev.memory_stats() or {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs),
              "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}
    reduced = None
    if tracer and tracer.error is None:
        from chipbench import trace as T
        reduced = T.reduce_dir(tracer.dir)
        if reduced is not None:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
    reading = SimpleNamespace(window=win, spans=ctx.spans, mix=mix,
                              conf=conf, peak=peak, trace=reduced,
                              served=ctx.log.completed,
                              setup_s=win.t0 - t_process)
    metrics: Dict[str, dict] = {}
    for m in bench.metrics_for(name, per_layer=trace):
        value = bench.reader(m["name"])(reading)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    checks = verify(ctx, win, mix, seed, control)
    failed = sum(1 for q in win.queries if q["failed"])
    result = {"correct": all(c["ok"] for c in checks.values()),
              "attempted": len(win.queries), "failed": failed,
              "metrics": metrics, "device": device}
    if reduced is not None:
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    if win.error is not None:
        result["error"] = repr(win.error)[:500]
    result["checks"] = {k: {"value": c["value"], "limit": c["limit"],
                            "ok": c["ok"]} for k, c in checks.items()}
    return result


def setup(conf: dict, arch: ModuleType, mix: dict, seed: int,
          rehearsal: bool, drive) -> SimpleNamespace:
    """Weights, database, hosts and a warm-up pass of the cell's own
    traffic drawn from another seed, through the window's own driver."""
    mc = program.model_config(conf, rehearsal, arch)
    program.install_weights(seed)
    ctx = SimpleNamespace(mix=mix, mc=mc, arch=arch, spans=drivers.Spans(),
                          limits=conf["rehearsal_limits" if rehearsal
                                      else "limits"],
                          log=program.RequestLog(),
                          compiles=CompileCounter.get())
    ctx.workflows = mix["templates"]
    ctx.graphs = {k: program.parse(v) for k, v in ctx.workflows.items()}
    ctx.models = program.models_for(ctx.graphs.values(), mc)
    ctx.workers = int(conf["workers"])
    if not rehearsal:
        fit = program.workers_that_fit(ctx.models, int(conf["workers"]))
        if fit < ctx.workers:
            raise SetupError(f"{ctx.workers} workers stated, {fit} fit")
    ctx.db = program.database(mix["database"])
    ctx.tables = {t: (tb.columns, tb.rows) for t, tb in ctx.db.tables.items()}
    ctx.tools = program.tool_runtime(ctx.db)
    ctx.hosts = program.hosts(ctx.models, ctx.workers)
    ctx.hw = program.hardware(rehearsal)
    w = drive(ctx, 0, seed ^ generate.WARMUP_SALT, **mix["warmup"])
    if w.error is not None:
        raise RuntimeError(f"warm-up failed: {w.error!r}") from w.error
    ctx.log.clear()
    return ctx


def verify(ctx, win, mix: dict, seed: int,
           control: bool = False) -> Dict[str, dict]:
    """The numbers that decide ``correct``, each with its limit.  Frees
    the engines first: the reference runs on what they held."""
    from chipbench import weights
    finished = [q for q in win.queries if not q["failed"]]
    sample = check.sample_queries(finished, int(mix["check"]["queries"]),
                                  seed)
    alone = program.tool_runtime(ctx.db, latency_scale=0.0)
    counts, calls = check.processor_check(
        finished, ctx.workflows, ctx.log.served,
        lambda op, args: alone.execute(op, args)[0], ctx.mc.vocab_size,
        sample)
    program.free_hosts(ctx.hosts)
    ctx.hosts = None
    gc.collect()
    params = weights.make_params(program.param_shapes(ctx.mc), seed)
    dims = ctx.arch.dims(ctx.mc)
    gap, n_tok, raw = check.model_gap(ctx.arch, params, dims, calls)
    limits = ctx.limits
    out = {
        "failed": {"value": sum(1 for q in win.queries if q["failed"]),
                   "limit": 0},
        "unanswered": {"value": counts["unanswered"], "limit": 0},
        "tool_mismatch": {"value": counts["tool_mismatch"], "limit": 0},
        "llm_mismatch": {"value": counts["llm_mismatch"], "limit": 0},
        "gap_sd": {"value": gap, "limit": limits["gap_sd"]},
    }
    for c in out.values():
        c["ok"] = c["value"] is not None and c["value"] <= c["limit"]
    out["tokens_checked"] = {"value": n_tok,
                             "limit": int(mix["check"]["min_tokens"]),
                             "ok": n_tok >= int(mix["check"]["min_tokens"])}
    if control:
        cgap, _, craw = check.model_gap(ctx.arch, params, dims, calls,
                                        fp8=True)
        out["control_gap_sd"] = {"value": cgap, "limit": limits["gap_sd"],
                                 "ok": cgap is not None
                                 and cgap <= limits["gap_sd"]}
        out["raw_gap"] = {"value": raw, "limit": None, "ok": True}
        out["control_raw_gap"] = {"value": craw, "limit": None, "ok": True}
    del params
    gc.collect()
    return out


def report(result: dict) -> None:
    """Each compared number beside its limit as the last lines on
    standard error, then the result as the last line of standard out."""
    for k, c in result["checks"].items():
        print(f"check {k}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
