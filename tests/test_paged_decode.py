"""Device-resident paged decode: bitwise identity with the dense-view
reference path (fresh / warm / mid-batch admission / COW-shared partial
pages), page-scatter append round trips, host<->device traffic
acceptance, grace-window admission and claim throttling.

Fast suite: tiny configs, n<=3 queries, decode_cap<=3 for e2e runs.
"""
import math
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.engine.engine import InferenceEngine
from repro.engine.kvcache import PagedKVCache


def _wait(cond, timeout=120.0):
    deadline = time.monotonic() + timeout
    while not cond():
        if time.monotonic() > deadline:
            raise TimeoutError("condition not reached")
        time.sleep(0.005)


# ---------------------------------------------------------------------------
# paged kernel path vs dense-view path: bitwise-identical outputs
# ---------------------------------------------------------------------------

def test_paged_vs_dense_view_identity_fresh_and_warm():
    """Fresh prompts, then a warm re-run aliasing the first run's pages
    (including a COW-shared NON-ALIGNED partial page): token outputs are
    identical on both decode paths, and the paged path never
    materializes a dense view."""
    cfg = get_smoke("qwen3-1.7b")
    prefix = list(range(10, 20))                 # 10 tokens: full + partial
    prompts = [prefix + [100], prefix + [101], list(range(40, 47))]
    outs = {}
    for paged in (True, False):
        eng = InferenceEngine(cfg, seed=0, page_size=8, paged_decode=paged)
        try:
            first = eng.generate(prompts, max_new_tokens=4)
            again = eng.generate(prompts, max_new_tokens=4)   # warm aliases
            assert eng.stats.prefix_hits >= 1
            assert eng.stats.tokens_reused >= len(prefix)
            outs[paged] = (first, again)
            if paged:
                assert eng.stats.view_rebuilds == 0
            else:
                assert eng.stats.view_rebuilds > 0
        finally:
            eng.shutdown()
    assert outs[True] == outs[False]
    assert outs[True][0] == outs[True][1]        # warm run bitwise stable


def test_paged_vs_dense_view_identity_mid_batch_admission():
    cfg = get_smoke("llama3.2-3b")
    p1, p2 = list(range(10, 18)), list(range(60, 66))
    outs = {}
    for paged in (True, False):
        eng = InferenceEngine(cfg, seed=0, paged_decode=paged)
        try:
            h1 = eng.submit(p1, max_new_tokens=24)
            _wait(lambda: eng.stats.decode_tokens >= 1)
            h2 = eng.submit(p2, max_new_tokens=4)
            outs[paged] = (h1.result(), h2.result())
            assert eng.stats.peak_batch == 2
        finally:
            eng.shutdown()
    assert outs[True] == outs[False]


def test_paged_engine_frees_pages_and_preserves_donor_after_cow():
    """COW safety through the paged decode path: the donor's stored KV
    is untouched after a sharer wrote through the aliased partial page,
    and releasing the warm set returns every page."""
    cfg = get_smoke("qwen3-1.7b")
    prefix = list(range(10, 20))
    eng = InferenceEngine(cfg, seed=0, page_size=8)
    try:
        eng.generate([prefix + [100]], max_new_tokens=4)
        donor_seq = next(iter(eng._warm))
        k_before, v_before = eng.kv.gather(donor_seq)
        k_before, v_before = np.asarray(k_before), np.asarray(v_before)
        eng.generate([prefix + [101]], max_new_tokens=4)    # aliases + COWs
        assert eng.stats.tokens_reused == len(prefix)
        k_after, v_after = eng.kv.gather(donor_seq)
        np.testing.assert_array_equal(k_before, np.asarray(k_after))
        np.testing.assert_array_equal(v_before, np.asarray(v_after))
        eng.release_warm()
        assert eng.kv.pages_in_use == 0 and not eng.kv.sequences
    finally:
        eng.shutdown()


# ---------------------------------------------------------------------------
# model level: paged step's Pallas kernel == its XLA gather fallback
# ---------------------------------------------------------------------------

def test_paged_decode_step_kernel_matches_xla_gather():
    """paged_decode_step under the paged Pallas kernel (interpret mode)
    matches the on-device-gather XLA fallback: logits and the scattered
    pool agree to fp tolerance (layers past the first see the previous
    layer's attention output, so bitwise equality is not expected)."""
    from repro.engine.models import build_model
    cfg = get_smoke("qwen3-1.7b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompt = jnp.arange(10, 21, dtype=jnp.int32)[None, :]    # 11 tokens
    S = prompt.shape[1]
    _, cache = model.prefill(params, prompt)
    layers, heads, dh = model.paged_kv_layout()
    kv = PagedKVCache(layers, num_pages=8, page_size=8, kv_heads=heads,
                      head_dim=dh)
    seq = kv.add_sequence(*model.cache_kv_rows_dev(cache, 0, S))
    kv.prepare_append(seq)
    pt = jnp.asarray([kv.page_table(seq)], jnp.int32)
    lens = jnp.asarray([S], jnp.int32)
    token = jnp.asarray([42], jnp.int32)
    lg_x, kx, vx = model.paged_decode_step(params, token, kv.k, kv.v,
                                           pt, lens, impl="xla")
    lg_p, kp, vp = model.paged_decode_step(params, token, kv.k, kv.v,
                                           pt, lens,
                                           impl="pallas_interpret")
    np.testing.assert_allclose(np.asarray(lg_p, np.float32),
                               np.asarray(lg_x, np.float32),
                               atol=2e-2, rtol=2e-2)
    np.testing.assert_allclose(np.asarray(kx), np.asarray(kp),
                               atol=5e-2, rtol=5e-2)
    np.testing.assert_allclose(np.asarray(vx), np.asarray(vp),
                               atol=5e-2, rtol=5e-2)


@pytest.mark.parametrize("name", ["qwen3-1.7b", "deepseek-moe-16b"])
def test_chained_paged_decode_steps_match_dense_decode_step(name):
    """Six chained ``paged_decode_step`` calls against the dense
    ``decode_step`` over the same prompts: rows of 5, 7 and 20 tokens
    cross page boundaries (page size 4), a fourth row is padded
    (``lengths == -1``).  Logits agree, every written ``(page, offset)``
    slot holds the dense cache's KV for that position, and nothing else
    in the pool changes, so the padded row wrote nothing.  The MoE
    config's first layer is a dense lead block, outside the layer scan."""
    from repro.engine.models import build_model
    model = build_model(get_smoke(name))
    params = model.init(jax.random.PRNGKey(0))
    layers, heads, dh = model.paged_kv_layout()
    ps, n_pages, steps = 4, 7, 6
    T = n_pages * ps
    prompts = [list(range(3, 8)), list(range(30, 37)), list(range(50, 70))]
    kv = PagedKVCache(layers, num_pages=16, page_size=ps, kv_heads=heads,
                      head_dim=dh)
    B = len(prompts) + 1                          # the last row is padding
    k_rows = np.zeros((B, layers, T, heads, dh), np.float32)
    v_rows = np.zeros_like(k_rows)
    seqs, lengths, token = [], [], []
    for b, prompt in enumerate(prompts):
        logits, cache = model.prefill(params, jnp.asarray([prompt], jnp.int32))
        k, v = model.cache_kv_rows_dev(cache, 0, len(prompt))
        seqs.append(kv.add_sequence(k, v))
        k_rows[b, :, :len(prompt)] = np.asarray(k, np.float32)
        v_rows[b, :, :len(prompt)] = np.asarray(v, np.float32)
        lengths.append(len(prompt))
        token.append(int(jnp.argmax(logits[0])))
    dense = model.paged_cache_view(k_rows, v_rows, lengths + [0])
    paged_step = jax.jit(model.paged_decode_step)
    dense_step = jax.jit(model.decode_step)
    k_want, v_want = np.array(kv.k), np.array(kv.v)
    for _ in range(steps):
        slots = [kv.prepare_append(s) for s in seqs]
        pt = np.zeros((B, n_pages), np.int32)
        for b, s in enumerate(seqs):
            pages = kv.page_table(s)
            pt[b, :len(pages)] = pages
        tok = jnp.asarray(token + [0], jnp.int32)
        lg_p, kv.k, kv.v = paged_step(params, tok, kv.k, kv.v,
                                      jnp.asarray(pt),
                                      jnp.asarray(lengths + [-1], jnp.int32))
        lg_d, dense = dense_step(params, tok, dense)
        np.testing.assert_array_equal(np.asarray(lg_p[:-1], np.float32),
                                      np.asarray(lg_d[:-1], np.float32))
        k_tap, v_tap = model.decode_kv_taps(dense, lengths + [0])
        for b, (page, off) in enumerate(slots):
            k_want[:, page, off] = k_tap[:, b]
            v_want[:, page, off] = v_tap[:, b]
        np.testing.assert_array_equal(np.asarray(kv.k), k_want)
        np.testing.assert_array_equal(np.asarray(kv.v), v_want)
        for s in seqs:
            kv.commit_append(s)
        lengths = [n + 1 for n in lengths]
        token = [int(t) for t in jnp.argmax(lg_d[:-1], axis=-1)]
    assert min(lengths) == 11 and max(lengths) == 26


def test_engine_kernel_variants_bitwise_identical():
    """The full engine under each Pallas paged-decode kernel variant —
    single-page, multi-page blocked, and fused append+attend (which
    skips the separate scatter dispatch) — emits IDENTICAL tokens.
    Prompts exercise COW-aliased partial pages via the shared prefix."""
    import dataclasses
    cfg = dataclasses.replace(get_smoke("qwen3-1.7b"),
                              attention_impl="pallas_interpret")
    prefix = list(range(10, 20))
    prompts = [prefix + [100], prefix + [101], list(range(40, 47))]
    outs = {}
    for variant in ("single", "blocked", "fused"):
        eng = InferenceEngine(cfg, seed=0, page_size=8, paged_decode=True,
                              kernel_variant=variant)
        try:
            first = eng.generate(prompts, max_new_tokens=5)
            again = eng.generate(prompts, max_new_tokens=5)  # warm aliases
            outs[variant] = (first, again)
        finally:
            eng.shutdown()
    assert outs["single"] == outs["blocked"] == outs["fused"]


# ---------------------------------------------------------------------------
# cache level: in-jit page scatter == append_token, device pool round trip
# ---------------------------------------------------------------------------

def test_page_scatter_append_round_trip_matches_append_token():
    """The batched (page, offset) scatter the decode step uses writes
    the same pool state as the per-token append_token loop — including
    across page boundaries and a COW'd shared partial page."""
    rng = np.random.default_rng(0)
    k0 = rng.standard_normal((2, 6, 2, 8)).astype(np.float32)
    v0 = rng.standard_normal((2, 6, 2, 8)).astype(np.float32)

    def fresh():
        pc = PagedKVCache(num_layers=2, num_pages=16, page_size=4,
                          kv_heads=2, head_dim=8)
        a = pc.add_sequence(k0, v0)                      # 6 tokens: partial
        b = pc.add_sequence(shared_from=a, shared_len=6)  # aliases partial
        return pc, a, b

    steps = [(rng.standard_normal((2, 2, 2, 8)).astype(np.float32),
              rng.standard_normal((2, 2, 2, 8)).astype(np.float32))
             for _ in range(5)]                          # crosses a boundary

    ref, a1, b1 = fresh()
    for k_t, v_t in steps:
        ref.append_token(a1, k_t[:, 0], v_t[:, 0])
        ref.append_token(b1, k_t[:, 1], v_t[:, 1])

    dev, a2, b2 = fresh()
    for k_t, v_t in steps:
        # the decode-step shape: metadata prep, one scatter, commit
        pages, slots = zip(*(dev.prepare_append(s) for s in (a2, b2)))
        pi, si = jnp.asarray(pages), jnp.asarray(slots)
        dev.k = dev.k.at[:, pi, si].set(jnp.asarray(k_t))
        dev.v = dev.v.at[:, pi, si].set(jnp.asarray(v_t))
        dev.commit_append(a2)
        dev.commit_append(b2)

    for s_ref, s_dev in ((a1, a2), (b1, b2)):
        kr, vr = ref.gather(s_ref)
        kd, vd = dev.gather(s_dev)
        np.testing.assert_array_equal(np.asarray(kr), np.asarray(kd))
        np.testing.assert_array_equal(np.asarray(vr), np.asarray(vd))
    assert ref.pages_in_use == dev.pages_in_use


def _eager_blocks(a, ps):
    """(L, S, H, D) -> whole zero-padded pages, as the eager page write
    built them."""
    n = -(-a.shape[1] // ps)
    a = np.pad(a, ((0, 0), (0, n * ps - a.shape[1]), (0, 0), (0, 0)))
    return a.reshape(a.shape[0], n, ps, *a.shape[2:])


@pytest.mark.parametrize("case", ["cold", "shared_aligned",
                                  "shared_unaligned", "shared_in_page",
                                  "cow_first_decode", "import"])
def test_pool_writer_matches_eager_page_writes(case):
    """Each write into the pool outside the decode step is ONE writer
    dispatch whose pools come out bitwise equal to the eager
    ``.at[].set`` writes it replaced: whole zero-padded pages, the
    partial-page fill, the copy-on-write page copy.  Pages of other
    sequences and rows past the sequence's end are untouched."""
    ps, L, H, D = 4, 2, 2, 8
    rng = np.random.default_rng(7)
    rows = lambda n: rng.standard_normal((L, n, H, D)).astype(np.float32)
    pc = PagedKVCache(num_layers=L, num_pages=16, page_size=ps,
                      kv_heads=H, head_dim=D)
    # stale contents everywhere, so an untouched row differs from zero
    pc.k = jnp.asarray(rng.standard_normal(pc.k.shape), jnp.float32)
    pc.v = jnp.asarray(rng.standard_normal(pc.v.shape), jnp.float32)
    donor = pc.add_sequence(jnp.asarray(rows(10), jnp.bfloat16),
                            jnp.asarray(rows(10), jnp.bfloat16))
    other = pc.add_sequence(rows(5), rows(5))
    k0, v0 = np.array(pc.k), np.array(pc.v)
    writes, written = pc.pool_writes, pc.pool_write_rows
    k_new = jnp.asarray(rows(7), jnp.bfloat16)   # a prefill's rows
    v_new = jnp.asarray(rows(7), jnp.bfloat16)
    f32 = lambda a: np.asarray(a, np.float32)
    want_k, want_v = k0.copy(), v0.copy()

    def whole_pages(pages, k, v):
        want_k[:, pages] = _eager_blocks(f32(k), ps)
        want_v[:, pages] = _eager_blocks(f32(v), ps)

    def cow(src, dst):
        want_k[:, dst] = want_k[:, src]
        want_v[:, dst] = want_v[:, src]

    if case == "cold":
        seq = pc.add_sequence(k_new, v_new)
        whole_pages(pc.page_table(seq), k_new, v_new)
        rows_written = 7
    elif case == "shared_aligned":
        seq = pc.add_sequence(k_new, v_new, shared_from=donor, shared_len=8)
        assert pc.page_table(seq)[:2] == pc.page_table(donor)[:2]
        whole_pages(pc.page_table(seq)[2:], k_new, v_new)
        rows_written = 7
    elif case == "shared_unaligned":            # 6 shared: 2 into page 1
        seq = pc.add_sequence(shared_from=donor, shared_len=6)
        pc.extend_sequence(seq, k_new, v_new)
        table = pc.page_table(seq)
        cow(pc.page_table(donor)[1], table[1])
        want_k[:, table[1], 2:] = f32(k_new[:, :2])
        want_v[:, table[1], 2:] = f32(v_new[:, :2])
        whole_pages(table[2:], k_new[:, 2:], v_new[:, 2:])
        rows_written = 7 + ps
    elif case == "shared_in_page":              # ends inside page 1
        seq = pc.add_sequence(shared_from=donor, shared_len=5)
        pc.extend_sequence(seq, k_new[:, :2], v_new[:, :2])
        table = pc.page_table(seq)
        cow(pc.page_table(donor)[1], table[1])
        want_k[:, table[1], 1:3] = f32(k_new[:, :2])
        want_v[:, table[1], 1:3] = f32(v_new[:, :2])
        rows_written = 2 + ps
    elif case == "cow_first_decode":            # first append COWs page 1
        seq = pc.add_sequence(shared_from=donor, shared_len=5)
        pc.prepare_appends([seq])
        table = pc.page_table(seq)
        assert table[1] != pc.page_table(donor)[1]
        cow(pc.page_table(donor)[1], table[1])
        rows_written = ps
    else:                                       # a migrated host block
        k_host, v_host = rows(7), rows(7)
        seq = pc.import_sequence(k_host, v_host)
        whole_pages(pc.page_table(seq), k_host, v_host)
        rows_written = 7
    assert pc.pool_writes == writes + 1
    assert pc.pool_write_rows == written + rows_written
    np.testing.assert_array_equal(np.asarray(pc.k), want_k)
    np.testing.assert_array_equal(np.asarray(pc.v), want_v)
    private = [p for p in pc.page_table(seq) if pc.refcount[p] == 1]
    for s in (donor, other):
        kept = [p for p in pc.page_table(s) if p not in private]
        np.testing.assert_array_equal(np.asarray(pc.k)[:, kept],
                                      k0[:, kept])
    # past the sequence's end, its last page holds what the eager
    # writes left there (zeros after a whole-page write, the donor's
    # rows after a copy-on-write), nothing the writer made up
    n = pc.sequences[seq].length
    last = pc.page_table(seq)[-1]
    np.testing.assert_array_equal(np.asarray(pc.k)[:, last, n % ps or ps:],
                                  want_k[:, last, n % ps or ps:])


def test_pool_writer_compiles_a_bounded_set_of_shapes():
    """Prompts of 1 to 2100 tokens build at most ceil(log2(pages)) + 1
    writer programs: the rows written are bucketed to a power of two
    pages, and page ids are traced operands."""
    pc = PagedKVCache(num_layers=1, num_pages=300, page_size=8,
                      kv_heads=1, head_dim=2)
    built = pc._write_jit._cache_size()     # one cache per pool_write
    for n in range(1, 2101):
        seq = pc.add_sequence(np.ones((1, n, 1, 2), np.float32),
                              np.ones((1, n, 1, 2), np.float32))
        pc.free_sequence(seq)
    built = pc._write_jit._cache_size() - built
    assert built <= math.ceil(math.log2(300)) + 1
    assert pc.pool_writes == 2100


# ---------------------------------------------------------------------------
# e2e acceptance: O(batch) per-step traffic, not O(batch x seq_len)
# ---------------------------------------------------------------------------

def test_paged_ab_kills_host_gather_traffic():
    """Warm WT A/B: paged decode moves >=10x fewer host<->device bytes
    than the dense-view path, rebuilds no views, and produces identical
    temperature-0 outputs."""
    from benchmarks.common import run_paged_ab
    rep_p, rep_d = run_paged_ab("wt", n=3, workers=2, decode_cap=3)
    assert rep_p.results() == rep_d.results()
    assert rep_p.extra["view_rebuilds"] == 0
    assert rep_d.extra["view_rebuilds"] > 0
    paged_traffic = rep_p.extra["h2d_bytes"] + rep_p.extra["d2h_bytes"]
    dense_traffic = rep_d.extra["h2d_bytes"] + rep_d.extra["d2h_bytes"]
    assert paged_traffic > 0                     # honest accounting
    assert dense_traffic >= 10 * paged_traffic


# ---------------------------------------------------------------------------
# grace-window admission
# ---------------------------------------------------------------------------

def test_admission_window_batches_staggered_arrivals():
    """With a grace window, a burst of staggered submissions forms ONE
    admission wave (one batch shape); outputs are unchanged."""
    cfg = get_smoke("qwen3-1.7b")
    prompts = [list(range(10, 18)), list(range(30, 41)), [3, 4, 5, 6]]
    eng = InferenceEngine(cfg, seed=0, admission_window=0.05)
    try:
        handles = []
        for p in prompts:                        # staggered inside window
            handles.append(eng.submit(p, max_new_tokens=4))
            time.sleep(0.01)
        outs = [h.result() for h in handles]
        assert eng.stats.admission_waves == 1
        assert eng.stats.peak_batch == 3
    finally:
        eng.shutdown()
    ref = InferenceEngine(cfg, seed=0)
    try:
        assert ref.generate(prompts, max_new_tokens=4) == outs
    finally:
        ref.shutdown()


# ---------------------------------------------------------------------------
# claim throttling keeps the replanning window open
# ---------------------------------------------------------------------------

def test_claim_throttling_lets_drift_replan_fire_late():
    """With claim_ahead=1 a worker cannot race ahead and claim the whole
    chain at admission, so a splice queued AFTER the first node's
    results land still finds unclaimed work to re-place — and outputs
    match an unthrottled control run."""
    from benchmarks.common import (make_cm, make_real_processor,
                                   swapped_tail)
    from repro.runtime import OnlineOptimizer

    proc, g, cons, _, plan = make_real_processor(
        "w+", 2, 2, 2, kv_migration=False, claim_ahead=1)
    opt = OnlineOptimizer(make_cm(g, cons), drift_threshold=1e9)
    done = threading.Event()
    report = {}

    def _run():
        try:
            report["rep"] = proc.run(cons, plan, optimizer=opt)
        finally:
            done.set()

    t = threading.Thread(target=_run, daemon=True)
    t.start()
    # queue the forced splice once the run is underway but long before
    # the chain's first node completes (its first-run JIT compile alone
    # takes far longer than this) — with claim_ahead=1 the two
    # downstream nodes are provably still unclaimed at that point,
    # whereas unthrottled workers claim the whole chain at admission
    time.sleep(0.5)
    assert not done.is_set()
    opt.queue_splice(swapped_tail(plan, g, 2))
    assert done.wait(timeout=300.0)
    rep = report["rep"]
    assert rep.extra["plan_splices"] >= 1         # window survived
    assert rep.extra["replans"] >= 1

    ctrl, _, cons2, _, plan2 = make_real_processor(
        "w+", 2, 2, 2, kv_migration=False)
    rep2 = ctrl.run(cons2, plan2)
    assert rep.results() == rep2.results()
