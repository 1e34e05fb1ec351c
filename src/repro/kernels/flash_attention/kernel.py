"""Flash prefill attention — Pallas TPU kernel.

Grid (B, H, n_q, n_kv); the kv dim is innermost so the online-softmax
running state (acc/m/l) lives in VMEM scratch across kv steps.

VMEM working set per step (bq=512, bk=512, Dh=128, bf16 in / f32 acc):
  q tile 512·128·2 = 128 KiB, k/v tiles 2·128 KiB, acc 512·128·4 = 256 KiB,
  logits 512·512·4 = 1 MiB  →  ~1.8 MiB, comfortably inside ~16 MiB VMEM.
MXU alignment: all matmul dims (bq, bk, Dh) are multiples of 128 at
production shapes; q rows fold the GQA group so the (bq, Dh)×(Dh, bk)
products keep the systolic array full.

Positions are explicit inputs (−1 = invalid slot), so causal masks,
sliding windows and ring-buffer caches all reduce to the same predicate —
no separate mask tensors in HBM.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(qp_ref, kp_ref, q_ref, k_ref, v_ref, o_ref,
                  acc_ref, m_ref, l_ref, *,
                  scale: float, causal: bool, window: int, n_kv: int):
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32)                  # (bq, Dh)
    k = k_ref[0, 0].astype(jnp.float32)                  # (bk, Dh)
    v = v_ref[0, 0].astype(jnp.float32)
    qp = qp_ref[0]                                       # (bq, 1) int32
    kp = kp_ref[0]                                       # (1, bk)

    logits = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale      # (bq, bk)

    mask = kp >= 0
    if causal:
        mask = mask & (kp <= qp)
    if window > 0:
        mask = mask & (kp > qp - window)
    logits = jnp.where(mask, logits, NEG_INF)

    m_prev = m_ref[:, 0]                                 # (bq,)
    m_new = jnp.maximum(m_prev, logits.max(axis=-1))
    alpha = jnp.exp(m_prev - m_new)                      # (bq,)
    p = jnp.exp(logits - m_new[:, None])                 # (bq, bk)
    l_new = alpha * l_ref[:, 0] + p.sum(axis=-1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[:, 0] = m_new
    l_ref[:, 0] = l_new

    @pl.when(ik == n_kv - 1)
    def _done():
        l = l_ref[:, 0]
        denom = jnp.where(l == 0.0, 1.0, l)              # fully-masked rows
        o_ref[0, 0] = (acc_ref[...] / denom[:, None]).astype(o_ref.dtype)


def _pad_axis(x, axis: int, size: int, value=0):
    pad = size - x.shape[axis]
    if not pad:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


# vmem-budget: 2.0 MiB @ block_q=512 block_kv=512 Sq=4096 Skv=4096 Dh=128
def flash_attention_kernel(q, k, v, q_positions, kv_positions, *,
                           causal: bool, window: int,
                           block_q: int, block_kv: int,
                           interpret: bool = False):
    """q: (B,Sq,H,Dh); k,v: (B,Skv,Hkv,Dh); positions (B,S) int32, -1 =
    invalid slot.

    The kernel runs head-major — (B, H, S, Dh) — so each block's last two
    dims are (rows, Dh), the layout the TPU tiles.  A sequence longer
    than its block is padded to a whole number of blocks: padded KV slots
    carry position -1 (masked), padded query rows are sliced off.
    """
    B, Sq, H, Dh = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    G = H // Hkv
    bq, bk = min(block_q, Sq), min(block_kv, Skv)
    n_q, n_kv = -(-Sq // bq), -(-Skv // bk)
    sq_pad, skv_pad = n_q * bq, n_kv * bk
    assert sq_pad % bq == 0 and skv_pad % bk == 0
    grid = (B, H, n_q, n_kv)

    qh = _pad_axis(q.swapaxes(1, 2), 2, sq_pad)           # (B,H,Sq',Dh)
    kh = _pad_axis(k.swapaxes(1, 2), 2, skv_pad)          # (B,Hkv,Skv',Dh)
    vh = _pad_axis(v.swapaxes(1, 2), 2, skv_pad)
    qp = _pad_axis(q_positions.astype(jnp.int32), 1, sq_pad, -1)[:, :, None]
    kp = _pad_axis(kv_positions.astype(jnp.int32), 1, skv_pad, -1)[:, None, :]

    kernel = functools.partial(
        _flash_kernel, scale=1.0 / math.sqrt(Dh), causal=causal,
        window=window, n_kv=n_kv)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, 1), lambda b, h, iq, ik: (b, iq, 0)),
            pl.BlockSpec((1, 1, bk), lambda b, h, iq, ik: (b, 0, ik)),
            pl.BlockSpec((1, 1, bq, Dh), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, bk, Dh),
                         lambda b, h, iq, ik: (b, h // G, ik, 0)),
            pl.BlockSpec((1, 1, bk, Dh),
                         lambda b, h, iq, ik: (b, h // G, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, Dh),
                               lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, sq_pad, Dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, Dh), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        interpret=interpret,
    )(qp, kp, qh, kh, vh)
    return out[:, :, :Sq].swapaxes(1, 2)
