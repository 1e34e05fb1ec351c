"""The benchmark's own tests, on the CPU at the smoke size.

    JAX_PLATFORMS=cpu python -m pytest -q chipbench/tests

They check the yardstick, not the speed: the traffic generator, the
metric arithmetic, the refusal of an unknown chip or of a CPU run, a
cell and an architecture module added by files alone, a configuration
file checked against its architecture, the plain reference against the
program's cold prefill, prefix-shared chunk prefill and paged decode,
the float8 control turning ``correct`` false under the configuration's
limit, and a broken timed path turning it false too.  Rehearsals run
the real harness with ``rehearsal=True``.
"""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from types import SimpleNamespace

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from chipbench import generate, harness, program, readings  # noqa: E402
from chipbench import trace as T  # noqa: E402

DATA = os.path.join(ROOT, "chipbench", "tests", "data")
program.import_program(ROOT)


@pytest.fixture(scope="module")
def bench():
    return harness.Bench(ROOT)


# ------------------------------------------------------------- generator
def test_batches_are_deterministic_and_keep_their_sizes(bench):
    mix = bench.mix("docqa-batch")
    a = generate.batch(mix, 2**33 + 7, 0)
    assert a == generate.batch(mix, 2**33 + 7, 0)
    b = generate.batch(mix, 8, 0)
    assert a != b

    def doc_sizes(batch):
        return sorted(len(x["doc"].split()) for x in batch[1][::4])
    assert doc_sizes(a) == doc_sizes(b) == sorted([512] * 8 + [1024] * 5
                                                  + [2048] * 3)
    # four questions per document
    docs = [x["doc"] for x in a[1]]
    assert all(docs[i] == docs[i - i % 4] for i in range(len(docs)))


def test_rows_source_takes_consecutive_rows(bench):
    mix = bench.mix("ws-batch")
    rows = [(i, f"page_{i}", f"topic{i % 3}") for i in range(1000)]
    tables = {"pages": (["id", "title", "topic"], rows)}
    tpl, b = generate.batch(mix, 5, 0, tables)
    ranks = [int(x["rank"]) for x in b]
    assert tpl == "ws" and len(b) == 128
    assert ranks == list(range(ranks[0], ranks[0] + 128))
    assert b[0]["title"] == f"page_{ranks[0]}"


# ------------------------------------------------- clocks and arithmetic
def _window(queries, seconds=10.0, **delta):
    w = SimpleNamespace(queries=queries, t0=100.0, t1=100.0 + seconds,
                        seconds=seconds, compiles=0)
    w.delta = lambda k: delta.get(k, 0)
    return w


def test_mfu_arithmetic(bench):
    conf = bench.config("qwen3-1.7b")
    arch = bench.arch(conf)
    layers, head = arch.matmul_params(conf)
    assert layers + head == pytest.approx(1.721e9, rel=1e-3)
    l8, h8 = arch.matmul_params(bench.config("qwen3-8b-pp2"))
    assert l8 + h8 == pytest.approx(4.096e9, rel=1e-3)
    r = SimpleNamespace(window=_window([], prefill_tokens=1000,
                                       decode_tokens=9000,
                                       coalesced_requests=10),
                        conf=conf, served=110, peak={"bf16_flops": 197e12})
    want = 2 * (layers * 10000 + head * (9000 + 100)) / 10.0 / 197e12 * 100
    assert readings.mfu(r) == pytest.approx(want)
    r.peak = None
    assert readings.mfu(r) is None


def test_unknown_device_kind_is_refused():
    with pytest.raises(harness.SetupError):
        harness.peaks_for("TPU v99 imaginary")
    assert harness.peaks_for("TPU v5 lite")["bf16_flops"] == 197e12


def test_cpu_run_without_rehearsal_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "chipbench/run.py", "--workload",
                        "qwen3-1.7b.ws-batch", "--seed", "1", "--seconds",
                        "1", "--trace", "0"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "{" not in p.stdout


def test_trace_reduction_on_plain_events():
    ms = 1_000_000
    host = [(T.WINDOW_MARK, 0, 100 * ms), ("chipbench.solve_plan", 40 * ms,
                                           70 * ms),
            ("PjitFunction(step)", 0, 30 * ms)]
    dev = {"/device:TPU:0": [("fusion.1", 0, 20 * ms),
                             ("fusion.2", 10 * ms, 30 * ms),
                             ("fusion.1", 80 * ms, 90 * ms)]}
    out = T.reduce_planes(dev, host)
    assert out["window_s"] == pytest.approx(0.1)
    assert out["busy_s"] == pytest.approx(0.04)
    assert out["device_ops"][0] == ["fusion.1", pytest.approx(0.03)]
    assert out["idle_gaps"][0] == ["solve_plan", pytest.approx(0.05)]
    assert T.reduce_planes(dev, host[1:]) is None


def test_trace_reduction_on_a_chip_trace():
    """A short trace of a traced run on one v5e, and what the reduction
    gave for it when it was recorded."""
    path = os.path.join(DATA, "chip.xplane.pb")
    with open(os.path.join(DATA, "chip.expected.json")) as f:
        want = json.load(f)
    out = T.reduce_planes(*T.read_xplane(path))
    assert out["window_s"] == pytest.approx(want["window_s"])
    assert out["busy_s"] == pytest.approx(want["busy_s"])
    assert 0 < out["busy_s"] <= out["window_s"]
    assert [n for n, _ in out["device_ops"]] == \
        [n for n, _ in want["device_ops"]]


# ---------------------------------------------------- cells from files
def fixture_root(tmp_path):
    """A checkout with one more configuration, mix, metric and cell, and
    one more architecture module (a copy of qwen3's under another
    ``model_type``), all added as files and entries only."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "chipbench"), root / "chipbench",
                    ignore=shutil.ignore_patterns("out", "__pycache__",
                                                  "tests"))
    os.symlink(os.path.join(ROOT, "src"), root / "src")
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    conf = json.load(open(os.path.join(ROOT, "chipbench", "configs",
                                       "qwen3-1.7b.json")))
    conf["name"] = "fixture-model"
    (root / "chipbench" / "configs" / "fixture-model.json").write_text(
        json.dumps(conf))
    mix = json.load(open(os.path.join(ROOT, "chipbench", "traffic",
                                      "wt-batch.json")))
    mix["parts"][0]["queries"] = 16
    mix["warmup"] = {"batches": 1}
    mix["check"] = {"queries": 4, "min_tokens": 100}
    (root / "chipbench" / "traffic" / "fixture-mix.json").write_text(
        json.dumps(mix))
    shutil.copy(os.path.join(ROOT, "chipbench", "arch", "qwen3.py"),
                root / "chipbench" / "arch" / f"{FIXTURE_ARCH}.py")
    (root / "chipbench" / "metrics" / "decode_per_query.batch.py"
     ).write_text("def read(r):\n    n = len(r.window.queries)\n"
                  "    return r.window.delta('decode_tokens') / n\n")
    spec["configs"].append({"name": "fixture-model", "source": "x",
                            "file": "chipbench/configs/fixture-model.json",
                            "reduced": [], "why": "fixture"})
    spec["workloads"].append({"name": "fixture-model.fixture-mix",
                              "config": "fixture-model",
                              "traffic": "fixture-mix", "chips": 1,
                              "why": "fixture"})
    cell = "fixture-model.fixture-mix"
    spec["end_to_end"][1]["workloads"].append(cell)
    spec["per_layer"].append({"name": "decode_per_query.batch",
                              "unit": "tokens", "better": "higher",
                              "source": "program_counter",
                              "layer": "engine and KV pool",
                              "moves": "queries_per_s",
                              "workloads": [cell]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return str(root)


FIXTURE_ARCH = "fixture_arch"


@pytest.fixture(scope="module")
def fixture_bench(tmp_path_factory):
    return harness.Bench(fixture_root(tmp_path_factory.mktemp("fx")))


CELL = "fixture-model.fixture-mix"


def test_cell_configuration_mix_and_metric_found_from_files(fixture_bench):
    b = fixture_bench
    assert b.cell(CELL)["traffic"] == "fixture-mix"
    assert b.config("fixture-model")["arch"] == "qwen3-1.7b"
    assert b.mix("fixture-mix")["parts"][0]["queries"] == 16
    names = [m["name"] for m in b.metrics_for(CELL, per_layer=True)]
    assert "decode_per_query.batch" in names
    r = SimpleNamespace(window=_window([{}] * 4, decode_tokens=40))
    assert b.reader("decode_per_query.batch")(r) == 10.0


def test_rehearsal_of_an_added_batch_cell(fixture_bench):
    res = harness.run_cell(CELL, 2**32 + 11, 1.0, True, rehearsal=True,
                           bench=fixture_bench)
    assert res["correct"], res["checks"]
    assert res["device"]["platform"] == "cpu"
    # 24 + 16 tokens a query, the first of each from its prefill
    assert res["metrics"]["decode_per_query.batch"]["value"] == 38.0
    assert "mfu.batch" not in res["metrics"]        # no chip, no peak
    assert list(res)[-1] == "checks"
    res = harness.run_cell(CELL, 12, 1.0, False, rehearsal=True,
                           bench=fixture_bench)
    assert res["correct"] and res["failed"] == 0
    assert set(res["metrics"]) == {"setup_s", "queries_per_s"}


# ------------------------------------- architectures found by model_type
def test_a_configuration_with_no_architecture_module_is_refused(bench):
    conf = dict(bench.config("qwen3-1.7b"), model_type="no_such_arch")
    path = os.path.join(ROOT, "chipbench", "arch", "no_such_arch.py")
    with pytest.raises(harness.SetupError, match=path):
        bench.arch(conf)


def test_an_architecture_module_added_under_a_root_is_found(fixture_bench,
                                                             bench):
    conf = dict(bench.config("qwen3-1.7b"), model_type=FIXTURE_ARCH)
    arch = fixture_bench.arch(conf)
    assert arch.__file__ == os.path.join(fixture_bench.root, "chipbench",
                                         "arch", f"{FIXTURE_ARCH}.py")
    assert fixture_bench.arch(conf) is arch
    qwen3 = bench.arch(bench.config("qwen3-1.7b"))
    assert arch is not qwen3 and arch.expect(conf) == qwen3.expect(conf)
    assert program.model_config(conf, False, arch).d_model == 2048
    with pytest.raises(harness.SetupError):
        bench.arch(conf)                # not in this checkout


DISAGREE = {"hidden_size": lambda v: v // 2,
            "intermediate_size": lambda v: v + 128,
            "tie_word_embeddings": lambda v: not v,
            "rms_norm_eps": lambda v: v * 10}


@pytest.mark.parametrize("key", sorted(DISAGREE))
def test_a_configuration_that_disagrees_with_its_architecture_is_refused(
        bench, key):
    for name in ("qwen3-1.7b", "qwen3-8b-pp2"):
        conf = bench.config(name)
        arch = bench.arch(conf)
        program.model_config(conf, False, arch)        # as committed: agrees
        with pytest.raises(ValueError, match="differs from its "
                                             "configuration file"):
            program.model_config(dict(conf, **{key: DISAGREE[key](
                conf[key])}), False, arch)


# ------------------------------------------ reference against the program
@pytest.mark.parametrize("arch", ["qwen3-1.7b", "qwen3-8b"])
def test_reference_matches_prefill_chunk_prefill_and_paged_decode(arch,
                                                                   bench):
    import jax
    import jax.numpy as jnp
    from chipbench import weights
    from repro.configs import get_smoke
    from repro.engine.kvcache import PagedKVCache
    from repro.engine.models import build_model
    reference = bench.arch(bench.config({"qwen3-1.7b": "qwen3-1.7b",
                                         "qwen3-8b": "qwen3-8b-pp2"}[arch]))
    mc = get_smoke(arch)
    model = build_model(mc)
    params = weights.make_params(program.param_shapes(mc), 2**40 + 3)
    dims = reference.dims(mc)
    rng = np.random.default_rng(0)
    toks = rng.integers(8, mc.vocab_size, 40).tolist()
    ref = reference.logits_at(params, dims, [(toks, 23)])[0]
    scale = np.abs(ref).max()

    def close(got, want):
        got = np.asarray(got, np.float32)[..., :mc.vocab_size]
        assert np.abs(got - want).max() <= 0.03 * scale
    # cold prefill of the first 24 tokens
    logits, cache = model.prefill(params, jnp.asarray([toks[:24]]))
    close(logits[0], ref[0])
    # chunk prefill of 8 more on top of a 16-token prefix
    _, pre = model.prefill(params, jnp.asarray([toks[:16]]))
    pre = model.extend_cache(pre, 16)
    logits, _ = model.prefill_with_cache(params, jnp.asarray([toks[16:24]]),
                                         pre)
    close(logits[0], ref[0])
    # paged decode of the next 16 through the page pool
    layers, kvh, dh = model.paged_kv_layout()
    kv = PagedKVCache(layers, 8, 8, kvh, dh)
    seq = kv.add_sequence(*model.cache_kv_rows_dev(cache, 0, 24))
    step = jax.jit(model.paged_decode_step)
    for j, tok in enumerate(toks[24:39]):
        kv.prepare_append(seq)
        pt = np.zeros((1, 8), np.int32)
        ids = kv.page_table(seq)
        pt[0, :len(ids)] = ids
        logits, k, v = step(params, jnp.asarray([tok]), kv.k, kv.v,
                            jnp.asarray(pt), jnp.asarray(
                                [kv.sequences[seq].length], jnp.int32))
        kv.adopt_pages(k, v)
        kv.commit_append(seq)
        close(logits[0], ref[j + 1])


# a seed whose rehearsal the control was calibrated on (PERF.md)
CONTROL_SEED = 2**31 + 97


def test_control_is_not_correct_where_the_program_is(fixture_bench):
    """The float8 control in the program's place, judged by the harness
    against the configuration's own limit: the run is not correct, and
    only because of the control."""
    res = harness.run_cell(CELL, CONTROL_SEED, 1.0, False, rehearsal=True,
                           control=True, bench=fixture_bench)
    checks = res["checks"]
    limit = fixture_bench.config("fixture-model")["rehearsal_limits"][
        "gap_sd"]
    assert checks["control_gap_sd"]["limit"] == limit
    assert checks["control_gap_sd"]["value"] > limit
    assert not checks["control_gap_sd"]["ok"]
    assert res["correct"] is False
    assert all(c["ok"] for k, c in checks.items() if k != "control_gap_sd")
    assert checks["gap_sd"]["value"] <= limit


# ---------------------------------------------- a broken timed path
def _broken_token(monkeypatch):
    from repro.engine import engine as E
    orig = E._batched_sample

    def altered(logits, keys, temps, *, vocab_size):
        toks, keys = orig(logits, keys, temps, vocab_size=vocab_size)
        return (toks + 1) % vocab_size, keys
    monkeypatch.setattr(E, "_batched_sample", altered)


def _state_unchanged(monkeypatch):
    from repro.engine.models.transformer import TransformerLM
    orig = TransformerLM.paged_decode_step

    def stale(self, params, token, k_pages, v_pages, *a, **kw):
        logits, _, _ = orig(self, params, token, k_pages, v_pages, *a, **kw)
        return logits, k_pages, v_pages
    monkeypatch.setattr(TransformerLM, "paged_decode_step", stale)


def _half_the_answers_dropped(monkeypatch):
    from repro.runtime import executors as X
    orig, n = X.detokenize, [0]

    def dropped(tokens):
        n[0] += 1
        return "" if n[0] % 2 else orig(tokens)
    monkeypatch.setattr(X, "detokenize", dropped)


@pytest.mark.parametrize("fault", [_broken_token, _state_unchanged,
                                   _half_the_answers_dropped])
def test_a_broken_timed_path_is_not_correct(fault, fixture_bench,
                                            monkeypatch):
    fault(monkeypatch)
    res = harness.run_cell(CELL, 31, 1.0, False, rehearsal=True,
                           bench=fixture_bench)
    assert res["correct"] is False
