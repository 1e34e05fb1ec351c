"""The deepseek-moe-16b-pp4 configuration and its architecture module
(``chipbench/arch/deepseek.py``): the file checked against the config
run, the work counts, the expert-kernel readers, and a CPU rehearsal of
its cell.

    JAX_PLATFORMS=cpu python -m pytest -q chipbench/tests/test_deepseek.py
"""
from __future__ import annotations

import os
import sys
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from chipbench import experts, harness, program, spans  # noqa: E402

DATA = os.path.join(ROOT, "chipbench", "tests", "data")
CONFIG = "deepseek-moe-16b-pp4"
CELL = "deepseek-moe-16b-pp4.docqa-batch"
PEAK = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
program.import_program(ROOT)


@pytest.fixture(scope="module")
def bench():
    return harness.Bench(ROOT)


@pytest.fixture(scope="module")
def conf(bench):
    return bench.config(CONFIG)


@pytest.fixture(scope="module")
def arch(bench, conf):
    return bench.arch(conf)


# ------------------------------------------------ the configuration run
def test_the_file_agrees_with_the_config_run(conf, arch):
    mc = program.model_config(conf, False, arch)
    assert (mc.num_layers, mc.d_model, mc.d_ff) == (7, 2048, 10944)
    assert (mc.moe.num_experts, mc.moe.top_k, mc.moe.d_ff_expert) == \
        (64, 6, 1408)
    assert mc.moe.norm_topk_prob is False
    assert conf["published"]["num_hidden_layers"] == 28


@pytest.mark.parametrize("key, value", [
    ("n_routed_experts", 32), ("num_experts_per_tok", 4),
    ("moe_intermediate_size", 1024), ("n_shared_experts", 1),
    ("first_k_dense_replace", 2), ("norm_topk_prob", True),
    ("intermediate_size", 11008), ("num_key_value_heads", 8)])
def test_a_changed_field_is_refused(conf, arch, key, value):
    with pytest.raises(ValueError, match="differs from its configuration"):
        program.model_config(dict(conf, **{key: value}), False, arch)


@pytest.mark.parametrize("key, value", [("moe_layer_freq", 2),
                                        ("scoring_func", "sigmoid")])
def test_a_layout_the_program_cannot_serve_is_refused(conf, arch, key,
                                                      value):
    with pytest.raises(ValueError, match="serves MoE"):
        program.model_config(dict(conf, **{key: value}), False, arch)


# ------------------------------------------------------ the work counts
def test_matmul_params(conf, arch):
    layers, head = arch.matmul_params(conf)
    # attention 7 x 16.78M, dense 67.24M, 6 x (shared 17.30M + 6 routed
    # x 8.65M + router 0.13M)
    assert layers == pytest.approx(600.7e6, rel=1e-3)
    assert head == pytest.approx(209.7e6, rel=1e-3)
    assert arch.expert_params(conf) == 3 * 2048 * 1408


def _reduction(read=None, rows=8.0, steps=10, kv=9000):
    attrs = {} if read is None else {"experts_read": read}
    sync = [["engine.sync", 1.0, 1.0, "e", "engine.step", dict(attrs)]
            for _ in range(steps)]
    return {"spans": sync, "decode_rows": rows * steps,
            "decode_steps": steps, "decode_kv_tokens": kv * steps}


def test_decode_step_bytes_count_the_experts_read(conf, arch):
    assert arch.uniform_experts(8, conf) == pytest.approx(34.9, abs=0.05)
    base = 2 * (7 * 16_777_216 + 3 * 2048 * 10944 + 6 * 3 * 2048 * 2816
                + 2048 * 102400) + 6 * 2048 * 64 * 4 \
        + 9000 * 7 * 2 * 16 * 128 * 2
    per_expert = 2 * 3 * 2048 * 1408
    got = arch.decode_step_bytes(_reduction(read=6 * 35), conf)
    assert got == pytest.approx(base + 6 * 35 * per_expert)
    # 2.31G parameters read at 8 rows of uniform routing
    assert (got - 9000 * 7 * 2 * 16 * 128 * 2) / 2 == \
        pytest.approx(2.31e9, rel=0.01)
    # a program that records no experts_read: uniform routing's count
    assert arch.decode_step_bytes(_reduction(), conf) == pytest.approx(
        base + 6 * arch.uniform_experts(8, conf) * per_expert)
    # every expert of every layer would be 1.11 GB a layer more
    assert 64 * per_expert == pytest.approx(1.107e9, rel=1e-3)


# --------------------------------------------- the expert kernel in a trace
GMM = ("%gmm.14 = bf16[48,1408]{1,0:T(8,128)(2,1)} custom-call(s32[] "
       "%get-tuple-element.1351, bf16[48,2048]{1,0:T(8,128)(2,1)} %fusion."
       "359, bf16[384,2048,1408]{2,1,0:T(8,128)(2,1)} %bitcast.350), "
       "custom_call_target=\"tpu_custom_call\"")


@pytest.mark.parametrize("text, picked", [
    (GMM, True),
    (GMM.replace("384,2048,1408", "64,1408,2048"), True),
    ("%copy.3 = bf16[64,2048,1408]{2,1,0} copy(bf16[64,2048,1408]{2,1,0} "
     "%p)", False),
    ("%dynamic-slice_bitcast_fusion.7 = bf16[64,2048,1408]{2,1,0} fusion("
     "bf16[6,64,2048,1408]{3,2,1,0} %g, s32[] %i), kind=kLoop", False),
    ("%while.5 = (s32[], bf16[6,64,2048,1408]{3,2,1,0}) while((s32[], "
     "bf16[6,64,2048,1408]{3,2,1,0}) %t)", False),
    ("%fusion.1 = bf16[8,2048]{1,0} fusion(bf16[8,2048]{1,0} %x, "
     "bf16[2048,1408]{1,0} %w), kind=kOutput", False)])
def test_the_expert_kernel_rule(conf, arch, text, picked):
    assert arch.expert_matmul_op(text, conf) is picked


def test_roofline_arithmetic_on_a_synthetic_window(conf, arch):
    ms = 1_000_000
    ops = {"/device:TPU:0": [(GMM, 10 * ms, 12 * ms),
                             ("%fusion.2 = f32[8] fusion()", 12 * ms,
                              20 * ms),
                             (GMM, 98 * ms, 104 * ms)]}   # clipped at 100
    t = experts.kernel_seconds(ops, 0, 100 * ms,
                               lambda n: arch.expert_matmul_op(n, conf))
    assert t == pytest.approx(0.004)
    a = {"window_s": 0.1, "spans": [
        ["engine.prefill", 5.0, 3.0, "e", None,
         {"slots": 600 * 36, "experts_read": 384}],
        ["engine.decode", 20.0, 1.0, "e", None, {"slots": 288}],
        ["engine.sync", 22.0, 1.0, "e", None, {"experts_read": 210}],
        ["engine.decode", 120.0, 1.0, "e", None, {"slots": 288}]]}
    slots, read = arch.expert_work(a, conf)
    assert (slots, read) == (600 * 36 + 288, 384 + 210)
    bound = arch.expert_matmul_bound_s(slots, read, conf, PEAK)
    flops = 2 * 3 * 2048 * 1408 * slots / 197e12
    bytes_ = 2 * (3 * 2048 * 1408 * read + slots * (2 * 2048 + 3 * 1408)) \
        / 819e9
    assert bound == pytest.approx(max(flops, bytes_))


def _fixture_reading(bench, conf, monkeypatch):
    """A traced reading of the committed qwen3 chip trace, with a
    reduction that carries MoE spans."""
    path = os.path.join(DATA, "chip.xplane.pb")
    a = {"window_s": 0.3, "spans": [
        ["engine.prefill", 1.0, 1.0, "e", None,
         {"slots": 100, "experts_read": 64}]]}
    monkeypatch.setattr(spans, "analysis", lambda r: a)
    monkeypatch.setattr(spans, "_run_trace", lambda t0: path)
    return SimpleNamespace(conf=conf, peak=PEAK, window=SimpleNamespace(
        t0=0.0), trace={"busy_s": 0.25, "window_s": 0.3})


@pytest.mark.parametrize("metric", ["expert_matmul_roofline.batch",
                                    "expert_matmul_time_share.batch"])
def test_the_new_readers_read_nothing_on_the_qwen3_fixture(
        bench, conf, monkeypatch, metric):
    """The committed trace is of a dense qwen3 run: no op event is the
    expert kernel's, so the readers read None, for the deepseek rule
    and for an architecture that has none."""
    read = bench.reader(metric)
    r = _fixture_reading(bench, conf, monkeypatch)
    assert read(r) is None
    r.conf = bench.config("qwen3-8b-pp2")
    assert read(r) is None
    monkeypatch.setattr(spans, "analysis", lambda r: None)
    assert read(SimpleNamespace(conf=conf, peak=PEAK, trace=None,
                                window=SimpleNamespace(t0=0.0))) is None


# ------------------------------------------------------------ rehearsal
def test_a_rehearsal_of_the_cell_is_correct(bench):
    res = harness.run_cell(CELL, 2**33 + 5, 1.0, True, rehearsal=True,
                           bench=bench)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] == 64
    assert res["device"]["platform"] == "cpu"
    assert res["metrics"]["prefill_saved_share.batch"]["value"] > 50
