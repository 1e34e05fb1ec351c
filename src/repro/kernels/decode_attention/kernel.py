"""GQA flash-decode — Pallas TPU kernel.

Decode attention is HBM-bandwidth-bound: each step streams the whole KV
cache once.  The kernel tiles KV into VMEM chunks — grid (B, Hkv, n_t),
the KV-chunk dim innermost — and keeps the online-softmax state for all
G = H/Hkv query heads of one KV head in VMEM scratch, so each KV byte is
read exactly once per step (roofline-optimal for the memory term).

The optional (m, l) outputs expose the log-sum-exp state for combining
partial results across KV shards (shard_map flash-decoding, see
distribution/collectives.py) or across the shared-prefix/suffix split
(shared_prefix_attention).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import (NEG_INF, finalize_online_softmax,
                                  online_softmax_update, qk_logits)


def _decode_kernel(qp_ref, kp_ref, q_ref, k_ref, v_ref,
                   o_ref, m_out_ref, l_out_ref,
                   acc_ref, m_ref, l_ref, *,
                   scale: float, window: int, n_t: int):
    b = pl.program_id(0)
    it = pl.program_id(2)

    @pl.when(it == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32)                  # (G, Dh)
    k = k_ref[0, 0].astype(jnp.float32)                  # (bt, Dh)
    v = v_ref[0, 0].astype(jnp.float32)
    qp = qp_ref[b]                                       # scalar int32 (SMEM)
    kp = kp_ref[0]                                       # (1, bt)

    logits = qk_logits(q, k, scale)                      # (G, bt)

    mask = (kp >= 0) & (kp <= qp)
    if window > 0:
        mask = mask & (kp > qp - window)

    acc_ref[...], m_ref[:, 0], l_ref[:, 0] = online_softmax_update(
        logits, mask, v, acc_ref[...], m_ref[:, 0], l_ref[:, 0])

    @pl.when(it == n_t - 1)
    def _done():
        out, m, l = finalize_online_softmax(
            acc_ref[...], m_ref[:, 0], l_ref[:, 0])
        o_ref[0, 0, :, :] = out.astype(o_ref.dtype)
        m_out_ref[0, 0, :, 0] = m
        l_out_ref[0, 0, :, 0] = l


# vmem-budget: 1.5 MiB @ block_t=1024 T=4096 Dh=128 H=32 Hkv=8
def decode_attention_kernel(q, k, v, q_positions, kv_positions, *,
                            window: int, block_t: int,
                            interpret: bool = False):
    """q: (B,H,Dh); k,v: (B,T,Hkv,Dh); q_positions (B,) or (B,1);
    kv_positions (B,T), -1 = invalid slot.

    The kernel runs KV-head-major — (B, Hkv, T, Dh) — so each KV block's
    last two dims are (tokens, Dh), the layout the TPU tiles; the query
    positions ride in SMEM as prefetched scalars.  A cache longer than
    ``block_t`` is padded to whole blocks of masked (-1) slots.

    Returns (out (B,H,Dh), m (B,H), l (B,H)).
    """
    B, H, Dh = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    G = H // Hkv
    bt = min(block_t, T)
    n_t = -(-T // bt)
    t_pad = n_t * bt - T
    assert (T + t_pad) % bt == 0
    grid = (B, Hkv, n_t)
    qg = q.reshape(B, Hkv, G, Dh)
    kh = jnp.pad(k.swapaxes(1, 2), ((0, 0), (0, 0), (0, t_pad), (0, 0)))
    vh = jnp.pad(v.swapaxes(1, 2), ((0, 0), (0, 0), (0, t_pad), (0, 0)))
    kp = jnp.pad(kv_positions.astype(jnp.int32), ((0, 0), (0, t_pad)),
                 constant_values=-1)[:, None, :]          # (B, 1, T')

    kernel = functools.partial(
        _decode_kernel, scale=1.0 / math.sqrt(Dh), window=window, n_t=n_t)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,               # q_positions
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bt), lambda b, h, it, qp: (b, 0, it)),
            pl.BlockSpec((1, 1, G, Dh), lambda b, h, it, qp: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bt, Dh), lambda b, h, it, qp: (b, h, it, 0)),
            pl.BlockSpec((1, 1, bt, Dh), lambda b, h, it, qp: (b, h, it, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, G, Dh), lambda b, h, it, qp: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, G, 1), lambda b, h, it, qp: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, G, 1), lambda b, h, it, qp: (b, h, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((G, Dh), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
        ],
    )
    out, m, l = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, Hkv, G, Dh), q.dtype),
            jax.ShapeDtypeStruct((B, Hkv, G, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, Hkv, G, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q_positions.reshape(B).astype(jnp.int32), kp, qg, kh, vh)
    return (out.reshape(B, H, Dh), m.reshape(B, H), l.reshape(B, H))
