"""Per-arch smoke tests: reduced config, one forward/train step on CPU,
output shapes + finiteness; prefill+decode consistency for each family."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_smoke
from repro.engine.models import build_model

RNG = jax.random.PRNGKey(0)


def _batch(cfg, B=2, S=16):
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                              cfg.vocab_size)
    batch = {"tokens": toks, "labels": toks}
    if cfg.family == "audio":
        batch["frames"] = jnp.ones((B, 8, cfg.d_model), jnp.bfloat16)
    if cfg.family == "vlm":
        batch["patch_embeds"] = jnp.ones((B, cfg.num_patches, cfg.d_model),
                                         jnp.bfloat16)
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_forward_and_loss(arch):
    cfg = get_smoke(arch)
    model = build_model(cfg)
    params = model.init(RNG)
    batch = _batch(cfg)
    if cfg.family == "audio":
        logits, _ = model.forward(params, batch["tokens"], batch["frames"])
    elif cfg.family == "vlm":
        logits, _ = model.forward(params, batch["tokens"],
                                  prefix_embeds=batch["patch_embeds"])
        assert logits.shape[1] == 16 + cfg.num_patches
        logits = logits[:, cfg.num_patches:]
    else:
        logits, _ = model.forward(params, batch["tokens"])
    assert logits.shape == (2, 16, cfg.padded_vocab)
    assert bool(jnp.isfinite(logits.astype(jnp.float32)).all())
    loss = model.loss_fn(params, batch)
    assert bool(jnp.isfinite(loss))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_train_step_no_nans(arch):
    from repro.training import TrainerConfig, make_train_step
    from repro.training.optimizer import adamw_init
    cfg = get_smoke(arch)
    model = build_model(cfg)
    params = model.init(RNG)
    opt = adamw_init(params)
    step = jax.jit(make_train_step(cfg, TrainerConfig(remat=False)))
    params2, opt2, metrics = step(params, opt, _batch(cfg))
    assert bool(jnp.isfinite(metrics["loss"]))
    assert bool(jnp.isfinite(metrics["grad_norm"]))
    # params actually changed
    l0 = jax.tree.leaves(params)[0]
    l1 = jax.tree.leaves(params2)[0]
    assert not np.allclose(np.asarray(l0, np.float32),
                           np.asarray(l1, np.float32))


@pytest.mark.parametrize("arch", ["qwen3-1.7b", "mixtral-8x22b",
                                  "whisper-tiny", "xlstm-350m",
                                  "recurrentgemma-2b"])
def test_prefill_decode_matches_forward(arch):
    """Teacher-forced forward logits == prefill+decode_step logits.

    MoE routing drops nothing, so a token's experts do not depend on
    the sequence length and the MoE arch runs as served."""
    cfg = get_smoke(arch).replace(dtype="float32", param_dtype="float32")
    model = build_model(cfg)
    params = model.init(RNG)
    B, S = 2, 12
    toks = jax.random.randint(jax.random.PRNGKey(2), (B, S), 0,
                              cfg.vocab_size)
    if cfg.family == "audio":
        frames = jnp.ones((B, 8, cfg.d_model), jnp.float32)
        full, _ = model.forward(params, toks, frames)
        logits, cache = model.prefill(params, toks[:, :S - 2], frames)
    else:
        full, _ = model.forward(params, toks)
        logits, cache = model.prefill(params, toks[:, :S - 2])
    cache = model.extend_cache(cache, 4)
    np.testing.assert_allclose(np.asarray(logits, np.float32),
                               np.asarray(full[:, S - 3], np.float32),
                               atol=2e-3, rtol=2e-3)
    for t in range(S - 2, S):
        logits, cache = model.decode_step(params, toks[:, t], cache)
        np.testing.assert_allclose(np.asarray(logits, np.float32),
                                   np.asarray(full[:, t], np.float32),
                                   atol=2e-3, rtol=2e-3)


def test_swa_ring_buffer_decode():
    """Mixtral ring-buffer cache stays bounded and finite past the window."""
    cfg = get_smoke("mixtral-8x22b")
    model = build_model(cfg)
    params = model.init(RNG)
    toks = jax.random.randint(jax.random.PRNGKey(3), (1, cfg.swa_window), 0,
                              cfg.vocab_size)
    logits, cache = model.prefill(params, toks)
    assert cache["k"].shape[2] == cfg.swa_window
    for _ in range(4):                      # decode past the window: wraps
        nxt = jnp.argmax(logits, -1).astype(jnp.int32)
        logits, cache = model.decode_step(params, nxt, cache)
        assert cache["k"].shape[2] == cfg.swa_window
        assert bool(jnp.isfinite(logits.astype(jnp.float32)).all())


def test_chunked_attention_equals_dense():
    from repro.engine.models.layers import attention_xla, attention_xla_chunked
    rng = np.random.default_rng(0)
    B, S, H, Hkv, Dh = 2, 64, 4, 2, 16
    q = jnp.asarray(rng.normal(size=(B, S, H, Dh)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, Hkv, Dh)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, Hkv, Dh)), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    for window in (0, 16):
        a = attention_xla(q, k, v, q_positions=pos, kv_positions=pos,
                          causal=True, window=window)
        b = attention_xla_chunked(q, k, v, q_positions=pos,
                                  kv_positions=pos, causal=True,
                                  window=window, block_q=16)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5, rtol=1e-5)
