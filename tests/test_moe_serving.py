"""deepseek-moe-16b served through the engine's paths at the smoke size:
drop-free grouped experts against the plain float32 reference
(``chipbench/arch/deepseek.py``), routing that does not depend on how a
prompt was chunked or batched, the un-renormalised gate, and the counts
of routed work the engine reports.

Weights are the benchmark's seeded draw (random RMSNorm gains, so a
dropped or misplaced norm shows).  Comparisons against the reference
run the program in float32: agreement is then at float32 rounding
(observed below 2e-6 of the logits' scale), so the 1e-4 tolerance is
far below what a bf16 step leaves (about 1.5e-2 of the scale here).
"""
import functools
import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs import get_smoke
from repro.configs.base import MoEConfig
from repro.engine.engine import InferenceEngine
from repro.engine.kvcache import PagedKVCache
from repro.engine.models import build_model
from repro.engine.models import moe as M

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from chipbench import program, weights  # noqa: E402

ARCH = "deepseek-moe-16b"
SEED = 2**40 + 17
TOL = 1e-4              # of the reference logits' largest magnitude


@pytest.fixture(scope="module")
def reference():
    path = os.path.join(ROOT, "chipbench", "arch", "deepseek.py")
    spec = importlib.util.spec_from_file_location("deepseek_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _model(dtype="float32"):
    mc = get_smoke(ARCH).replace(dtype=dtype, param_dtype=dtype)
    model = build_model(mc)
    return mc, model, weights.make_params(program.param_shapes(mc), SEED)


def _stepper(model):
    return jax.jit(functools.partial(model.paged_decode_step,
                                     count_experts=True))


def _paged_step(step, params, kv, seqs, tokens, pad_rows=0):
    """One jitted ``paged_decode_step`` (``_stepper``) over ``seqs`` (and
    ``pad_rows`` padded rows): (logits of the real rows, experts read)."""
    for s in seqs:
        kv.prepare_append(s)
    n_pages = 8
    B = len(seqs) + pad_rows
    pt = np.zeros((B, n_pages), np.int32)
    lens = np.full((B,), -1, np.int32)
    for b, s in enumerate(seqs):
        ids = kv.page_table(s)
        pt[b, :len(ids)] = ids
        lens[b] = kv.sequences[s].length
    tok = jnp.asarray(list(tokens) + [0] * pad_rows, jnp.int32)
    logits, k, v, read = step(params, tok, kv.k, kv.v, jnp.asarray(pt),
                              jnp.asarray(lens))
    kv.adopt_pages(k, v)
    for s in seqs:
        kv.commit_append(s)
    return np.asarray(logits[:len(seqs)], np.float32), int(read)


def test_prefill_then_paged_decode_match_the_reference(reference):
    """A cold prefill of 24 tokens, then 15 paged decode steps through
    the page pool, against the reference's full forward pass over the
    same 39 tokens, logits compared at every position."""
    mc, model, params = _model()
    toks = np.random.default_rng(1).integers(8, mc.vocab_size, 40).tolist()
    ref = reference.logits_at(params, reference.dims(mc), [(toks, 23)])[0]
    scale = np.abs(ref).max()

    def close(got, want):
        got = np.asarray(got, np.float32)[..., :mc.vocab_size]
        assert np.abs(got - want).max() <= TOL * scale

    logits, cache = model.prefill(params, jnp.asarray([toks[:24]]))
    close(logits[0], ref[0])
    layers, kvh, dh = model.paged_kv_layout()
    kv = PagedKVCache(layers, 8, 8, kvh, dh)
    seq = kv.add_sequence(*model.cache_kv_rows_dev(cache, 0, 24))
    step = _stepper(model)
    for j, tok in enumerate(toks[24:39]):
        logits, _ = _paged_step(step, params, kv, [seq], [tok])
        close(logits[0], ref[j + 1])


def test_the_reference_tolerance_refuses_a_bf16_step(reference):
    """The same comparison with the program in bf16 fails the tolerance:
    it is tight enough to tell the precision the configuration states
    from the float32 the reference claims."""
    mc, model, params = _model("bfloat16")
    toks = np.random.default_rng(1).integers(8, mc.vocab_size, 24).tolist()
    ref = reference.logits_at(params, reference.dims(mc), [(toks, 23)])[0]
    logits, _ = model.prefill(params, jnp.asarray([toks]))
    gap = np.abs(np.asarray(logits[0], np.float32)[:mc.vocab_size] - ref[0])
    assert gap.max() > 10 * TOL * np.abs(ref).max()


def test_cold_and_prefix_shared_chunk_prefill_agree():
    """The engine's two prefill paths over the same 56 tokens: a cold
    chunk prefill of the whole prompt (padded to the engine's 16-token
    quantum) and a prefix-shared one, 40 tokens from the cache plus a
    16-token chunk.  Every token routes by its own hidden state, so the
    last position's logits and the whole cache agree; a capacity that
    follows the chunk's length drops different tokens in each.  Logits
    to float32 rounding of a different matmul row count (1e-5 of the
    scale), the cache likewise."""
    mc, model, params = _model()
    toks = np.random.default_rng(2).integers(8, mc.vocab_size, 56).tolist()
    layers, kvh, dh = model.paged_kv_layout()
    T = 96

    def view(prefix_rows=None, n=0):
        k = np.zeros((1, layers, T, kvh, dh), np.float32)
        v = np.zeros_like(k)
        if prefix_rows is not None:
            k[0, :, :n] = prefix_rows[0]
            v[0, :, :n] = prefix_rows[1]
        return model.paged_cache_view(k, v, [n])

    pad = [0] * 8
    cold, c1 = model.prefill_with_cache(
        params, jnp.asarray([toks + pad]), view(), valid_len=jnp.asarray([56]))
    _, pre = model.prefill_with_cache(
        params, jnp.asarray([toks[:40]]), view())
    rows = [np.asarray(a, np.float32)
            for a in model.cache_kv_rows_dev(pre, 0, 40)]
    shared, c2 = model.prefill_with_cache(
        params, jnp.asarray([toks[40:]]), view(rows, 40),
        valid_len=jnp.asarray([16]))
    scale = np.abs(np.asarray(cold)).max()
    np.testing.assert_allclose(np.asarray(shared), np.asarray(cold),
                               atol=1e-5 * scale, rtol=0)
    for a, b in zip(model.cache_kv_rows_dev(c1, 0, 56),
                    model.cache_kv_rows_dev(c2, 0, 56)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5, rtol=1e-5)


def test_a_padded_decode_row_changes_no_real_row():
    """Two real rows decoded with and without two padded rows
    (``lengths == -1``): the real rows' logits are equal bit for bit,
    and the padded rows route to no expert, so the experts read are
    the same count."""
    mc, model, params = _model("bfloat16")
    layers, kvh, dh = model.paged_kv_layout()
    prompts = [list(range(3, 14)), list(range(40, 47))]
    step = _stepper(model)
    runs = []
    for pad in (0, 2):
        kv = PagedKVCache(layers, 16, 8, kvh, dh)
        seqs = []
        for p in prompts:
            _, cache = model.prefill(params, jnp.asarray([p]))
            seqs.append(kv.add_sequence(
                *model.cache_kv_rows_dev(cache, 0, len(p))))
        runs.append([_paged_step(step, params, kv, seqs, [5, 9], pad),
                     _paged_step(step, params, kv, seqs, [11, 13], pad)])
    for (lg0, r0), (lg1, r1) in zip(*runs):
        np.testing.assert_array_equal(lg0, lg1)
        assert r0 == r1


@pytest.mark.parametrize("layers", [1, 3])
def test_grouped_matmul_equals_a_per_expert_loop(layers):
    """The grouped SwiGLU over ragged groups (three empty, one of all
    but a few rows; with ``layers`` > 1 the experts of every layer
    stacked, the groups of one) against each expert's dense SwiGLU over
    its own rows.  Float32; the kernel accumulates in tiles, so to
    float32 rounding (1e-5)."""
    E, D, F, rows = 6, 32, 48, 40
    rng = np.random.default_rng(3)
    sizes = np.array([0, 30, 0, 7, 3, 0], np.int32)
    xs = jnp.asarray(rng.normal(size=(rows, D)), jnp.float32)
    wg, wu = (jnp.asarray(rng.normal(size=(layers * E, D, F)) / D ** .5,
                          jnp.float32) for _ in range(2))
    wd = jnp.asarray(rng.normal(size=(layers * E, F, D)) / F ** .5,
                     jnp.float32)
    at = layers - 1                          # the groups of the last layer
    full = np.zeros(layers * E, np.int32)
    full[at * E:(at + 1) * E] = sizes
    got = M.grouped_swiglu(xs, jnp.asarray(full), wg, wu, wd, 8)
    start = 0
    for e, n in enumerate(sizes):
        g = at * E + e
        x = xs[start:start + n]
        want = (jax.nn.silu(x @ wg[g]) * (x @ wu[g])) @ wd[g]
        np.testing.assert_allclose(np.asarray(got[start:start + n]),
                                   np.asarray(want), atol=1e-5, rtol=1e-5)
        start += n


def test_gate_renormalises_only_where_the_config_says():
    """``norm_topk_prob`` false keeps the softmax's top-k weights as they
    are (summing below 1); true divides them by their sum.  Without
    shared experts the routed output scales by exactly that sum."""
    cfg = MoEConfig(num_experts=8, top_k=2, d_ff_expert=16,
                    norm_topk_prob=False)
    p = M.moe_init(jax.random.PRNGKey(0), 32, cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 5, 32), jnp.float32)
    probs, raw, idx = M.route(x.reshape(10, 32), p["router"], cfg)
    on = cfg.__class__(**{**cfg.__dict__, "norm_topk_prob": True})
    _, normed, idx_on = M.route(x.reshape(10, 32), p["router"], on)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(idx_on))
    want = np.take_along_axis(np.asarray(probs), np.asarray(idx), axis=1)
    np.testing.assert_array_equal(np.asarray(raw), want)
    total = want.sum(-1, keepdims=True)
    assert (total < 1).all()
    np.testing.assert_allclose(np.asarray(normed), want / total, rtol=1e-6)
    y_off, _, _ = M.moe_ffn(x, p, cfg)
    y_on, _, _ = M.moe_ffn(x, p, on)
    np.testing.assert_allclose(np.asarray(y_off).reshape(10, 32),
                               np.asarray(y_on).reshape(10, 32) * total,
                               rtol=1e-5, atol=1e-6)


def test_slot_and_expert_counters_against_a_hand_count():
    """An engine whose routers are zero: the gate is uniform, so every
    token takes experts 0..K-1 and each MoE layer call reads exactly K
    experts.  Hand count for two distinct prompts (no shared prefix) of
    9 and 13 tokens decoding 4 each
    (the first token from the prefill, then 3 decode steps of 2 rows):
    slots = 34 tokens x K x MoE layers; experts read = (2 prefills + 3
    steps) x K x MoE layers; and the spans carry the same counts."""
    mc = get_smoke(ARCH)
    eng = InferenceEngine(mc, seed=0)
    eng.load()
    blocks = dict(eng.params["blocks"])
    blocks["moe"] = dict(blocks["moe"],
                         router=jnp.zeros_like(blocks["moe"]["router"]))
    eng.params = dict(eng.params, blocks=blocks)
    K = mc.moe.top_k
    n_moe = mc.num_layers - mc.moe.first_dense_layers
    obs.enable()
    try:
        obs.drain()
        h = [eng.submit(list(range(a, a + n)), max_new_tokens=4)
             for a, n in ((10, 9), (50, 13))]
        for x in h:
            x.result()
        recs = obs.drain()["records"]
    finally:
        obs.disable()
    assert eng.stats.decode_steps == 3
    assert eng.stats.moe_token_slots == (9 + 13 + 2 * 3) * K * n_moe
    assert eng.stats.moe_experts_read == (2 + 3) * K * n_moe

    def attr(name, key):
        return [r[6][key] for r in recs if r[2] == name and key in r[6]]
    assert attr("engine.prefill", "slots") == [9 * K * n_moe, 13 * K * n_moe]
    assert attr("engine.prefill", "experts_read") == [K * n_moe] * 2
    assert attr("engine.decode", "slots") == [2 * K * n_moe] * 3
    assert attr("engine.sync", "experts_read") == [K * n_moe] * 3
    eng.shutdown()
