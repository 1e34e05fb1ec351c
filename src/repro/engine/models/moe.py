"""Mixture-of-Experts FFN: drop-free top-K routing over grouped experts.

Each token goes to its top-K experts with no capacity, so nothing is
dropped and a token's result depends on its own hidden state alone: a
cold prefill, a prefix-shared chunked prefill and a decode step route
the same token the same way.  The token-expert slots are sorted by
expert and each SwiGLU matrix runs as one grouped matmul over the
ragged groups: the Pallas megablox ``gmm`` kernel, which visits only
the groups that have rows, so an expert no token routes to is neither
computed nor read (on a TPU compiled, elsewhere under the Pallas
interpreter).  ``jax.lax.ragged_dot`` computes the same; on one v5e, at
8 decode rows over 35 of 64 experts and at 2048-token prefills, it took
2.0x and 2.7x gmm's time.  The gate is a float32 softmax over all
experts, then top-K, renormalised only where the config says so
(``MoEConfig.norm_topk_prob``).

The expert dim is sharded over the ``model`` axis when divisible
(deepseek-moe: 64/16), otherwise the per-expert FFN dim is sharded
(mixtral: 8 experts x d_ff/16).  See distribution/sharding.py.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox import gmm

from repro.configs.base import MoEConfig
from repro.engine.models.layers import dense_init
from repro.kernels import env_interpret

# memspace: device (model arrays are device-resident jnp values)


def moe_init(rng, d_model: int, cfg: MoEConfig, dtype):
    ks = jax.random.split(rng, 5)
    E, F = cfg.num_experts, cfg.d_ff_expert
    scale = 1.0 / math.sqrt(d_model)
    p = {
        "router": (jax.random.normal(ks[0], (d_model, E), jnp.float32) * scale
                   ).astype(jnp.float32),                       # router in f32
        "w_gate": (jax.random.normal(ks[1], (E, d_model, F), jnp.float32) * scale).astype(dtype),
        "w_up": (jax.random.normal(ks[2], (E, d_model, F), jnp.float32) * scale).astype(dtype),
        "w_down": (jax.random.normal(ks[3], (E, F, d_model), jnp.float32)
                   * (1.0 / math.sqrt(F))).astype(dtype),
    }
    if cfg.num_shared_experts:
        Fs = cfg.num_shared_experts * F
        kss = jax.random.split(ks[4], 3)
        p["shared"] = {
            "w_gate": dense_init(kss[0], d_model, Fs, dtype),
            "w_up": dense_init(kss[1], d_model, Fs, dtype),
            "w_down": dense_init(kss[2], Fs, d_model, dtype),
        }
    return p


def route(x: jax.Array, router: jax.Array, cfg: MoEConfig):
    """x: (N, D) -> (probs (N, E), top-K weights (N, K), top-K experts
    (N, K)), all from a float32 softmax over every expert."""
    probs = jax.nn.softmax(x.astype(jnp.float32) @ router, axis=-1)
    top_w, top_i = jax.lax.top_k(probs, cfg.top_k)
    if cfg.norm_topk_prob:
        top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-20)
    return probs, top_w, top_i


def row_tile(slots: int, experts: int) -> int:
    """The grouped matmul's m-tile for ``slots`` rows over ``experts``
    groups.  Each visit of a tile reads its group's weights again and
    computes all its rows, so the tile follows the rows an expert gets:
    8 at decode, 256 for long prefills."""
    per = slots // experts
    return 8 if per < 8 else 128 if per < 128 else 256


def grouped_matmul(xs: jax.Array, w: jax.Array, group_sizes: jax.Array,
                   tm: int) -> jax.Array:
    """Rows ``xs`` (M, K) sorted by group, ``group_sizes[g]`` rows times
    ``w[g]`` (G, K, N) each, M a multiple of ``tm``; rows past
    ``sum(group_sizes)`` belong to no group and their output is not
    defined."""
    tile = [512 if d % 512 == 0 else d for d in w.shape[1:]]
    return gmm(xs, w, group_sizes, xs.dtype, (tm, *tile),
               interpret=env_interpret(jax.default_backend() != "tpu"))


def grouped_swiglu(xs: jax.Array, group_sizes: jax.Array, w_gate: jax.Array,
                   w_up: jax.Array, w_down: jax.Array, tm: int) -> jax.Array:
    """SwiGLU of rows ``xs`` (M, D) sorted by expert, ``group_sizes[e]``
    rows for expert e, against stacked (E, D, F) / (E, F, D) weights."""
    h = (jax.nn.silu(grouped_matmul(xs, w_gate, group_sizes, tm))
         * grouped_matmul(xs, w_up, group_sizes, tm))
    return grouped_matmul(h, w_down, group_sizes, tm)


def moe_ffn(x: jax.Array, p, cfg: MoEConfig,
            valid: Optional[jax.Array] = None,
            layer: Optional[jax.Array] = None
            ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x: (B, S, D) -> (out (B, S, D), aux loss, experts read).

    ``valid`` (B, S) marks real tokens: a padded token's slots go to no
    expert, so it reads no weights and its routed output is zero.
    ``experts read`` (int32) counts the experts with at least one slot.
    ``p``'s expert weights are (E, D, F) / (E, F, D), or, with ``layer``,
    every scanned layer's stacked (L, E, D, F): the grouped matmul then
    reads layer ``layer``'s groups of the whole stack, where a layer
    scan's own slice would copy all E experts out of it each step.
    """
    B, S, D = x.shape
    N, E, K = B * S, cfg.num_experts, cfg.top_k
    xf = x.reshape(N, D)
    probs, top_w, top_i = route(xf, p["router"], cfg)

    # ---- sort the N*K token-expert slots by expert -----------------------
    expert = top_i.reshape(-1)
    if valid is not None:
        keep = jnp.repeat(valid.reshape(N), K)
        expert = jnp.where(keep, expert, E)           # sorts past every group
    order = jnp.argsort(expert, stable=True)
    group_sizes = jnp.bincount(expert, length=E).astype(jnp.int32)
    ws = p["w_gate"], p["w_up"], p["w_down"]
    sizes = group_sizes
    if layer is not None:
        n = ws[0].shape[0] * E
        ws = [w.reshape((n,) + w.shape[2:]) for w in ws]
        sizes = jax.lax.dynamic_update_slice(
            jnp.zeros((n,), jnp.int32), group_sizes, (layer * E,))
    tm = row_tile(N * K, E)
    rows = jnp.pad(xf[order // K], ((0, -(N * K) % tm), (0, 0)))
    ys = grouped_swiglu(rows, sizes, *ws, tm)[:N * K]

    # ---- combine: each token's K outputs in slot order, gate-weighted ----
    back = jnp.zeros_like(order).at[order].set(
        jnp.arange(N * K, dtype=order.dtype))
    ys = ys[back].astype(jnp.float32) * top_w.reshape(-1, 1)
    if valid is not None:
        ys = jnp.where(keep[:, None], ys, 0.0)
    y = ys.reshape(N, K, D).sum(axis=1).astype(x.dtype).reshape(B, S, D)

    if cfg.num_shared_experts:
        sp = p["shared"]
        y = y + (jax.nn.silu(x @ sp["w_gate"]) * (x @ sp["w_up"])) @ sp["w_down"]

    # ---- load-balancing stats (Switch aux loss terms) ---------------------
    frac_tokens = jnp.mean(jax.nn.one_hot(top_i[:, 0], E, dtype=jnp.float32),
                           axis=0)
    aux = E * jnp.mean(frac_tokens * probs.mean(axis=0))
    read = jnp.sum(group_sizes > 0).astype(jnp.int32)
    return y, aux.astype(jnp.float32), read
