"""Spans and counters inside the engine, runtime and planner (repro.obs).

The smoke model runs the real paths: a pipelined ``wt`` batch through
``RealProcessor`` on persistent hosts with tracing on, one engine with
tracing off, and a profiler capture that turns tracing on.  What is
checked is what the benchmark's per-layer readers rely on: one
``engine.queue`` per request under its ``runtime.wave``, one
``engine.decode`` per counted decode step, rows and pool pages within
their bounds, compiles attributed to the span that triggered them, the
clock anchor in the profiler's trace, and the device trace's program
names.
"""
import glob
import threading
from collections import Counter

import jax
import jax.numpy as jnp
import pytest

from repro import obs
from repro.configs import get_smoke
from repro.core import (CostModel, EpochDPSolver, HARDWARE, PAPER_MODELS,
                        SolverConfig, consolidate)
from repro.engine.engine import InferenceEngine
from repro.runtime import ProcessorConfig, RealProcessor
from repro.runtime.executors import EngineHost
from repro.workloads import build_workload
from repro.workloads.datagen import build_database
from repro.workloads.tools import ToolRuntime

MAX_BATCH = 2


@pytest.fixture
def tracing():
    obs.drain()
    obs.enable()
    try:
        yield
    finally:
        obs.disable()
        obs.drain()


def _by_name(records):
    out = {}
    for r in records:
        out.setdefault(r[2], []).append(r)
    return out


def test_recorder_parents_threads_and_cap(tracing):
    with obs.span("outer") as outer:
        with obs.span("inner", k=1) as inner:
            inner.set(n=2)
    wave = obs.begin("wave")
    t = threading.Thread(target=lambda: obs.end(wave))
    t.start()
    t.join(timeout=10)
    assert not t.is_alive()
    obs.add("stamped", 5, 9, parent=wave, rid=3)
    recs = {r[2]: r for r in obs.drain()["records"]}
    assert recs["inner"][1] == outer.id and recs["outer"][1] is None
    assert recs["inner"][6] == {"k": 1, "n": 2}
    assert recs["stamped"][1] == wave.id
    assert recs["stamped"][3:5] == (5, 9)
    assert recs["wave"][5] == threading.get_ident()   # the opening thread
    old = obs._REC.cap
    obs._REC.cap = 2
    try:
        for _ in range(5):
            with obs.span("x"):
                pass
        got = obs.drain()
    finally:
        obs._REC.cap = old
    assert len(got["records"]) == 2 and got["dropped"] == 3


def test_tracing_off_records_nothing():
    obs.drain()
    assert obs.span("x") is obs.NOOP
    assert obs.begin("x") is obs.NOOP
    eng = InferenceEngine(get_smoke("qwen3-1.7b"), seed=0)
    try:
        eng.generate([[3, 4, 5, 6, 7]], max_new_tokens=3)
    finally:
        eng.shutdown()
    got = obs.drain()
    assert got["records"] == [] and got["dropped"] == 0
    assert got["unattributed"]["compiles"] == 0


def test_a_profiler_capture_turns_tracing_on_with_an_anchor(tmp_path):
    obs.drain()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.span("captured"):
            pass
    finally:
        jax.profiler.stop_trace()
    assert obs.span("after") is obs.NOOP
    recs = obs.drain()["records"]
    assert [r[2] for r in recs] == [obs.ANCHOR, "captured"]
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    planes = jax.profiler.ProfileData.from_file(path[0]).planes
    marks = [ev.name for p in planes for line in p.lines
             for ev in line.events if ev.name.startswith(obs.ANCHOR)]
    assert marks == [f"{obs.ANCHOR}#{recs[0][0]}"]


@pytest.fixture(scope="module")
def traced_batch():
    """A pipelined wt batch on two persistent hosts whose engines admit
    at most MAX_BATCH rows, run with tracing on."""
    g, bindings, dbname = build_workload("wt", 6, seed=0)
    cons = consolidate(g, bindings)
    sizes = {n: (cons.macro(n).n_logical if g.nodes[n].is_llm()
                 else cons.macro(n).n_unique) for n in g.nodes}
    cm = CostModel(g, HARDWARE["h200"], PAPER_MODELS, batch_sizes=sizes)
    plan = EpochDPSolver(g.llm_dag(), cm,
                         SolverConfig(num_workers=2)).solve()
    names = {g.nodes[x].model for x in g.llm_nodes()}
    models = {m: get_smoke("qwen3-1.7b").replace(name=m) for m in names}
    proc = RealProcessor(g, models, ToolRuntime(build_database(dbname),
                                                latency_scale=0.0),
                         config=ProcessorConfig(num_workers=2))
    hosts = [EngineHost(models, seed=0,
                        engine_kwargs={"max_batch": MAX_BATCH})
             for _ in range(2)]
    obs.drain()
    obs.enable()
    try:
        proc.run(cons, plan, hosts=hosts)
    finally:
        obs.disable()
        for h in hosts:
            h.shutdown()
    got = obs.drain()
    engines = [e for h in hosts for e in h.engines()]
    return got, engines


def test_every_request_has_one_queue_span_under_its_wave(traced_batch):
    got, engines = traced_batch
    by = _by_name(got["records"])
    queues = by["engine.queue"]
    keys = Counter((r[5], r[6]["rid"]) for r in queues)
    assert len(keys) == sum(e._rid for e in engines) > 0
    assert set(keys.values()) == {1}
    waves = {r[0] for r in by["runtime.wave"]}
    assert all(r[1] in waves for r in queues)
    assert all(r[4] >= r[3] for r in queues)
    assert got["dropped"] == 0


def test_decode_spans_match_counters(traced_batch):
    got, engines = traced_batch
    decodes = _by_name(got["records"])["engine.decode"]
    steps = sum(e.stats.decode_steps for e in engines)
    assert len(decodes) == steps > 0
    tokens = sum(e.stats.decode_tokens for e in engines)
    assert tokens / steps <= MAX_BATCH
    assert sum(r[6]["rows"] for r in decodes) == tokens
    assert all(r[6]["rows"] <= MAX_BATCH for r in decodes)
    assert sum(r[6]["kv_tokens"] for r in decodes) == \
        sum(e.stats.decode_kv_tokens for e in engines)
    used = sum(e.stats.pool_pages_used for e in engines)
    total = sum(e.stats.pool_pages_total for e in engines)
    assert 0 < used <= total
    assert all(r[6]["pages_used"] <= r[6]["pages_total"] for r in decodes)


def test_spans_cover_each_layer(traced_batch):
    got, _ = traced_batch
    names = set(_by_name(got["records"]))
    assert {"engine.step", "engine.admit", "engine.prefill", "engine.decode",
            "engine.sync", "engine.queue", "runtime.wave",
            "runtime.tool"} <= names
    by = {r[0]: r for r in got["records"]}
    for r in got["records"]:
        if r[2] in ("engine.admit", "engine.decode", "engine.sync"):
            assert by[r[1]][2] == "engine.step"
        if r[2] == "engine.prefill":
            assert by[r[1]][2] == "engine.admit"


def test_new_prefill_bucket_compile_is_attributed_to_prefill(tracing):
    eng = InferenceEngine(get_smoke("qwen3-1.7b"), seed=0)
    try:
        eng.generate([[3, 4, 5, 6, 7]], max_new_tokens=2)
        obs.drain()
        # 40 tokens pad to the 48-token chunk-prefill bucket: a new shape
        eng.generate([list(range(10, 50))], max_new_tokens=2)
    finally:
        eng.shutdown()
    prefills = _by_name(obs.drain()["records"])["engine.prefill"]
    assert [r[6]["prompt_tokens"] for r in prefills] == [40]
    assert prefills[0][6].get("compiles", 0) >= 1
    assert prefills[0][6]["compile_s"] > 0


def test_jitted_steps_are_named_programs():
    eng = InferenceEngine(get_smoke("qwen3-1.7b"), seed=0)
    try:
        eng.generate([[3, 4, 5, 6, 7]], max_new_tokens=2)
        kv = eng.kv
        i32 = jnp.zeros((1,), jnp.int32)
        hlo = eng._paged_step_jit.lower(
            eng.params, i32, kv.k, kv.v, jnp.zeros((1, 4), jnp.int32),
            i32).as_text()
    finally:
        eng.shutdown()
    assert "module @jit_paged_decode_step" in hlo
    assert [f.__name__ for f in (eng._decode_jit, eng._prefill_jit,
                                 eng._chunk_prefill_jit)] == \
        ["decode_step", "prefill", "chunk_prefill"]
