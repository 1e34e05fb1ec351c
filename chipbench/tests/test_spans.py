"""The program spans' mapping onto the device trace's clock, the trace
reductions next to ``trace.py``'s, and the per-layer readers that read
them (``chipbench/spans.py``), on the CPU.

    JAX_PLATFORMS=cpu python -m pytest -q chipbench/tests/test_spans.py
"""
from __future__ import annotations

import glob
import os
import sys
import time
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from chipbench import harness, program, spans  # noqa: E402
from chipbench import trace as T  # noqa: E402

DATA = os.path.join(ROOT, "chipbench", "tests", "data")
MS = 1_000_000
program.import_program(ROOT)


def _rec(i, name, t0, t1, parent=None, thread=1, **attrs):
    return (i, parent, name, t0, t1, thread, attrs)


def test_anchor_maps_spans_by_a_known_offset():
    off = 7_000_000_123
    # the annotation starts between the two reads (1000 and 1010 ns)
    recs = [_rec(3, spans.ANCHOR, 1_000, 1_010),
            _rec(4, "engine.step", 2 * MS, 5 * MS)]
    host = [(f"{spans.ANCHOR}#3", 1_005 + off, 1_020 + off),
            (f"{spans.ANCHOR}#9", 0, 1)]            # no such record
    got = spans.anchor_of(recs, host)
    assert got == (pytest.approx(off), pytest.approx(5.0))
    assert spans.anchor_of(recs, host[1:]) is None


def test_anchor_on_a_cpu_capture_lands_within_its_uncertainty(tmp_path):
    """A real profiler capture: a probe annotation whose entry is
    bracketed by perf_counter_ns reads maps inside that bracket, give or
    take the anchor's own uncertainty."""
    import jax
    from repro import obs
    obs.drain()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.span("engine.step"):
            pass
        a = time.perf_counter_ns()
        ann = jax.profiler.TraceAnnotation("probe")
        ann.__enter__()
        b = time.perf_counter_ns()
        ann.__exit__(None, None, None)
    finally:
        jax.profiler.stop_trace()
    recs = obs.drain()["records"]
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0]
    _, _, host = spans.read_planes(path)
    off, unc = spans.anchor_of(recs, host)
    probe = [s for n, s, _ in host if n == "probe"][0]
    slack = unc + 50_000                  # 50 us for the reads themselves
    assert a - slack <= probe - off <= b + slack


def test_program_times_and_idle_on_the_chip_fixture():
    path = os.path.join(DATA, "chip.xplane.pb")
    ops, modules, host = spans.read_planes(path)
    w0, w1 = spans.window_of(host)
    progs = spans.program_times(modules, w0, w1)
    assert progs["jit__lambda"]["count"] == 14
    assert progs["jit__batched_sample"]["count"] == 11
    assert all("(" not in n for n in progs)
    assert sum(p["s"] for p in progs.values()) <= (w1 - w0) / 1e9
    base = T.reduce_planes(ops, host)
    idle = spans.idle_intervals(ops, w0, w1)
    assert sum(e - s for s, e in idle) / 1e9 == \
        pytest.approx(base["window_s"] - base["busy_s"])


def _synthetic():
    """Two engine loops (threads 1 and 2), a 100-ms window, idle gaps
    inside engine steps and one outside any, two decode programs."""
    recs = [_rec(1, spans.ANCHOR, 0, 0),
            _rec(2, "engine.step", 10 * MS, 60 * MS),
            _rec(3, "engine.decode", 12 * MS, 14 * MS, parent=2, rows=4,
                 kv_tokens=400, pages_used=30, pages_total=40),
            _rec(4, "engine.sync", 15 * MS, 40 * MS, parent=2),
            _rec(5, "engine.decode", 41 * MS, 43 * MS, parent=2, rows=2,
                 kv_tokens=200, pages_used=10, pages_total=40),
            _rec(6, "engine.sync", 43 * MS, 58 * MS, parent=2),
            _rec(7, "engine.queue", 5 * MS, 12 * MS, thread=1, rid=1),
            _rec(8, "engine.queue", 5 * MS, 30 * MS, thread=1, rid=2),
            _rec(9, "engine.queue", 5 * MS, 50 * MS, thread=1, rid=3),
            _rec(10, "engine.prefill", 11 * MS, 12 * MS, parent=2,
                 compiles=2, compile_s=0.5),
            # a step begun before the capture: only its prefill is seen
            _rec(11, "engine.prefill", 60 * MS, 62 * MS, thread=2)]
    host = [(T.WINDOW_MARK, 0, 100 * MS), (f"{spans.ANCHOR}#1", 0, 1)]
    ops = {"/device:TPU:0": [("fusion.1", 14 * MS, 35 * MS),
                             ("fusion.2", 44 * MS, 55 * MS)]}
    modules = {"/device:TPU:0": [
        ("jit_paged_decode_step(11)", 14 * MS, 34 * MS),
        # placed 0.5 ms before the span that dispatched it
        ("jit_paged_decode_step(11)", 40_500_000, 54 * MS),
        ("jit__batched_sample(2)", 34 * MS, 35 * MS)]}
    return recs, ops, modules, host


def test_reduce_attributes_gaps_and_counts_the_window():
    a = spans.reduce(*_synthetic(), threads={1: "engine-m"})
    assert a["window_s"] == pytest.approx(0.1)
    # idle: 0-14, 35-44, 55-100 ms; in a step: 10-14, 35-44, 55-62
    assert a["idle_s"] == pytest.approx(0.068)
    assert a["idle_in_engine_step_s"] == pytest.approx(0.020)
    assert [g["ms"] for g in a["gaps"]] == pytest.approx([45, 14, 9])
    assert [g["span"] for g in a["gaps"]] == [None, "engine.queue",
                                             "engine.sync"]
    assert a["gaps"][2]["thread"] == "engine-m"
    assert a["gaps"][2]["parent"] == "engine.step"
    assert a["queue_ms"] == pytest.approx([7, 25, 45])
    assert a["decode_steps"] == 2 and a["decode_rows"] == 6
    assert a["decode_program_s"] == pytest.approx([0.02, 0.0135])
    assert a["decode_program_in_host_step"] == 0.5
    assert a["decode_program_outside_ms"] == pytest.approx(0.5)
    assert a["compiles"] == {"engine.prefill": {"compiles": 2,
                                                "compile_s": 0.5}}
    assert a["programs"]["jit_paged_decode_step"]["count"] == 2
    recs, ops, modules, host = _synthetic()
    assert spans.reduce(recs[1:], ops, modules, host) is None   # no anchor
    bare = spans.reduce(recs[:1], ops, modules, host)           # no spans
    assert bare["decode_program_in_host_step"] == 0.0
    assert bare["decode_program_outside_ms"] is None
    assert spans.decode_rows(bare) is None


def _reading(a, peak=True):
    conf = harness.load_json(os.path.join(ROOT, "chipbench", "configs",
                                          "qwen3-1.7b.json"))
    return SimpleNamespace(conf=conf, analysis=a,
                           peak={"hbm_bytes_per_s": 819e9} if peak
                           else None)


@pytest.fixture
def bench_with_analysis(monkeypatch):
    """The real readers, with ``spans.analysis`` handing each reading's
    synthetic reduction."""
    monkeypatch.setattr(spans, "analysis", lambda r: r.analysis)
    return harness.Bench(ROOT)


def test_each_new_reader_on_a_synthetic_reading(bench_with_analysis):
    b = bench_with_analysis
    a = spans.reduce(*_synthetic())
    r = _reading(a)
    assert b.reader("admit_wait_p50_ms.batch")(r) == pytest.approx(25.0)
    assert b.reader("decode_rows.batch")(r) == 3.0
    assert b.reader("kv_pool_used_share.batch")(r) == pytest.approx(50.0)
    assert b.reader("idle_engine_host_share.batch")(r) == \
        pytest.approx(20.0)
    arch = b.arch(r.conf)
    layers, head = arch.matmul_params(r.conf)
    # mean live KV 300 tokens x 28 layers x (K, V) x 8 heads x 128 x 2 B
    want = 2 * (layers + head) + 300 * 28 * 2 * 8 * 128 * 2
    assert arch.decode_step_bytes(a, r.conf) == want
    share = b.reader("decode_hbm_share.batch")(r)
    assert share == pytest.approx(100 * want / 0.01675 / 819e9)
    assert 0 < share <= 100
    for name in ("admit_wait_p50_ms.batch", "decode_rows.batch",
                 "kv_pool_used_share.batch", "decode_hbm_share.batch",
                 "idle_engine_host_share.batch"):
        assert b.reader(name)(_reading(None)) is None
    assert b.reader("decode_hbm_share.batch")(_reading(a, peak=False)) \
        is None


def test_untraced_or_spanless_runs_read_nothing():
    r = SimpleNamespace(trace=None, window=SimpleNamespace(t0=0.0))
    assert spans.analysis(r) is None
    # a trace of another, earlier run is not this run's
    r = SimpleNamespace(trace={}, window=SimpleNamespace(
        t0=time.perf_counter() + 3600))
    assert spans.analysis(r) is None
