"""Public jit'd wrapper for the flash prefill attention kernel."""
from __future__ import annotations

import functools

import jax

from repro.kernels import env_interpret

from repro.kernels.flash_attention.kernel import flash_attention_kernel


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "block_q", "block_kv", "interpret"))
def _flash_attention_jit(q, k, v, *, q_positions, kv_positions, causal=True,
                         window=0, block_q=512, block_kv=512,
                         interpret=False):
    return flash_attention_kernel(
        q, k, v, q_positions, kv_positions, causal=causal, window=window,
        block_q=block_q, block_kv=block_kv, interpret=interpret)


def flash_attention(q, k, v, *, q_positions, kv_positions, causal=True,
                    window=0, block_q=512, block_kv=512, interpret=False):
    """``interpret`` is resolved against REPRO_PALLAS_INTERPRET before
    the jit boundary so the env override is part of the jit cache key."""
    return _flash_attention_jit(
        q, k, v, q_positions=q_positions, kv_positions=kv_positions,
        causal=causal, window=window, block_q=block_q, block_kv=block_kv,
        interpret=env_interpret(interpret))
