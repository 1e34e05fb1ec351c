"""Public jit'd wrapper for GQA flash-decode."""
from __future__ import annotations

import functools

import jax

from repro.kernels import env_interpret

from repro.kernels.decode_attention.kernel import decode_attention_kernel


@functools.partial(jax.jit, static_argnames=(
    "window", "block_t", "return_lse", "interpret"))
def _decode_attention_jit(q, k, v, *, q_positions, kv_positions, window=0,
                          block_t=1024, return_lse=False, interpret=False):
    squeeze = q.ndim == 4
    if squeeze:
        assert q.shape[1] == 1
        q = q[:, 0]
    out, m, l = decode_attention_kernel(
        q, k, v, q_positions, kv_positions, window=window, block_t=block_t,
        interpret=interpret)
    if squeeze:
        out = out[:, None]
    if return_lse:
        return out, m, l
    return out


def decode_attention(q, k, v, *, q_positions, kv_positions, window=0,
                     block_t=1024, return_lse=False, interpret=False):
    """q: (B,1,H,Dh) or (B,H,Dh). Returns same rank as q (plus lse).

    ``interpret`` is resolved against REPRO_PALLAS_INTERPRET before the
    jit boundary so the env override is part of the jit cache key.
    """
    return _decode_attention_jit(
        q, k, v, q_positions=q_positions, kv_positions=kv_positions,
        window=window, block_t=block_t, return_lse=return_lse,
        interpret=env_interpret(interpret))
