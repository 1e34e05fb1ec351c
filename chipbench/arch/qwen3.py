"""The Qwen3 architecture (``model_type`` ``"qwen3"``), as the benchmark
knows it.

The harness finds this module by a configuration file's ``model_type``
(``harness.arch_of``); another architecture brings a module of its own
beside it with the same names:

- ``expect(conf)``: the program's ``ModelConfig`` fields that the
  configuration file fixes, which ``program.model_config`` compares key
  by key;
- ``dims(mc)``: the sizes the reference reads from the config run;
- ``canonical_layer``, ``canonical_head``, ``num_layers``: the
  reference's view of the served parameter tree;
- ``logits_at``: the plain forward pass that decides ``correct``, and
  its float8 control;
- ``matmul_params``, ``decode_step_bytes``: the FLOPs and bytes of the
  work, whatever implements it.

The reference follows the published Qwen3 decoder (hf:Qwen/Qwen3-1.7B,
Qwen3-8B ``modeling_qwen3``): RMSNorm before attention and MLP, per-head
RMSNorm of queries and keys (qk-norm) before rotary embedding
(rotate-half, ``rope_theta``), grouped-query causal attention, SwiGLU
MLP, final RMSNorm and a tied or untied output head.  No cache, no
kernels, no batching tricks: every matmul in float32 under
``default_matmul_precision("highest")``.  It runs layer by layer so
that a full-width model fits beside its bf16 weights, and it imports
nothing of the system under test.

``fp8=True`` is the control: the same forward with every
projection matrix and the output head rounded to float8 (e4m3, one scale
per output channel), the step below the bf16 the configurations state.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

BUCKET = 128                 # sequence lengths pad to this (causal: inert)
HEAD_BLOCK = 16384           # vocabulary rows scored per head matmul
MATRICES = ("q_proj", "k_proj", "v_proj", "o_proj", "gate_proj", "up_proj",
            "down_proj")


# ------------------------------------------------- the configuration run
def expect(conf: dict) -> dict:
    """``ModelConfig`` field -> the value the configuration file fixes."""
    return {"d_model": conf["hidden_size"], "num_layers":
            conf["num_hidden_layers"], "num_heads":
            conf["num_attention_heads"], "num_kv_heads":
            conf["num_key_value_heads"], "d_ff": conf["intermediate_size"],
            "vocab_size": conf["vocab_size"], "resolved_head_dim":
            conf["head_dim"], "tie_embeddings": conf["tie_word_embeddings"],
            "rope_theta": float(conf["rope_theta"]), "norm_eps":
            conf["rms_norm_eps"], "qk_norm": True, "family": "dense",
            "dtype": conf["torch_dtype"], "param_dtype":
            conf["torch_dtype"], "attention_impl": "xla"}


def dims(mc) -> dict:
    """The sizes the plain reference needs, read from the config run."""
    return {"heads": mc.num_heads, "kv_heads": mc.num_kv_heads,
            "head_dim": mc.resolved_head_dim, "rope_theta": mc.rope_theta,
            "eps": mc.norm_eps, "vocab": mc.vocab_size}


# ----------------------------------------------------- the reference's view
def canonical_layer(params, i: int) -> dict:
    """Layer ``i``'s weights as the published architecture names them,
    in float32: projection matrices (in, out) and RMSNorm gains."""
    b = params["blocks"]

    def f32(a):
        return jnp.asarray(a[i], jnp.float32)

    return {
        "input_norm": 1.0 + f32(b["ln1"]),
        "post_attn_norm": 1.0 + f32(b["ln2"]),
        "q_proj": f32(b["attn"]["wq"]), "k_proj": f32(b["attn"]["wk"]),
        "v_proj": f32(b["attn"]["wv"]), "o_proj": f32(b["attn"]["wo"]),
        "q_norm": 1.0 + f32(b["attn"]["q_norm"]),
        "k_norm": 1.0 + f32(b["attn"]["k_norm"]),
        "gate_proj": f32(b["ffn"]["w_gate"]),
        "up_proj": f32(b["ffn"]["w_up"]),
        "down_proj": f32(b["ffn"]["w_down"]),
    }


def canonical_head(params) -> dict:
    """The embedding table (rows = token ids, padded past the published
    vocabulary), the final norm gain in float32, and the output head as
    ``head_rows``: the (vocab, d) matrix whose row v scores token v —
    the embedding itself when the model ties them.  Tables stay in their
    stored dtype; the reference upcasts the rows it uses."""
    head = params["embed"] if "lm_head" not in params else \
        params["lm_head"].T
    return {"embed": params["embed"],
            "final_norm": 1.0 + jnp.asarray(params["final_norm"],
                                            jnp.float32),
            "head_rows": head}


def num_layers(params) -> int:
    return int(params["blocks"]["ln1"].shape[0])


# ------------------------------------------------------- the reference
def fp8_round(w: jax.Array, axis: int = -2) -> jax.Array:
    """``w`` rounded to float8 e4m3 with one scale per output channel
    (max |w| over the input axis maps to 448), returned in float32."""
    amax = jnp.max(jnp.abs(w), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def rms_norm(x, gain, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * gain


def rope(x, positions, theta):
    """Rotate-half rotary embedding; x (B, S, H, Dh), positions (S,)."""
    dh = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = positions[:, None].astype(jnp.float32) * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


@functools.partial(jax.jit, static_argnames=("shape", "fp8"))
def _layer(x, w: Dict[str, jax.Array], *, shape: Tuple, fp8: bool):
    heads, kv_heads, head_dim, theta, eps = shape
    with jax.default_matmul_precision("highest"):
        if fp8:
            w = {k: (fp8_round(v) if k in MATRICES else v)
                 for k, v in w.items()}
        B, S, _ = x.shape
        h = rms_norm(x, w["input_norm"], eps)
        q = (h @ w["q_proj"]).reshape(B, S, heads, head_dim)
        k = (h @ w["k_proj"]).reshape(B, S, kv_heads, head_dim)
        v = (h @ w["v_proj"]).reshape(B, S, kv_heads, head_dim)
        pos = jnp.arange(S)
        q = rope(rms_norm(q, w["q_norm"], eps), pos, theta)
        k = rope(rms_norm(k, w["k_norm"], eps), pos, theta)
        g = heads // kv_heads
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(head_dim)
        causal = pos[None, :] <= pos[:, None]
        s = jnp.where(causal[None, None], s, -jnp.inf)
        o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
        x = x + o.reshape(B, S, heads * head_dim) @ w["o_proj"]
        h = rms_norm(x, w["post_attn_norm"], eps)
        mlp = jax.nn.silu(h @ w["gate_proj"]) * (h @ w["up_proj"])
        return x + mlp @ w["down_proj"]


@functools.partial(jax.jit, static_argnames=("eps", "fp8"))
def _head_block(x, gain, rows, *, eps: float, fp8: bool):
    with jax.default_matmul_precision("highest"):
        h = rms_norm(x, gain, eps)
        r = rows.astype(jnp.float32)
        if fp8:
            r = fp8_round(r, axis=-1)
        return h @ r.T


@jax.jit
def _embed(table, tokens):
    return table[tokens].astype(jnp.float32)


def _hidden(params, dims, tokens: np.ndarray, fp8: bool) -> jax.Array:
    """Final hidden states (B, S, d) of token rows (B, S), float32."""
    head = canonical_head(params)
    x = _embed(head["embed"], jnp.asarray(tokens, jnp.int32))
    shape = (dims["heads"], dims["kv_heads"], dims["head_dim"],
             float(dims["rope_theta"]), float(dims["eps"]))
    for i in range(num_layers(params)):
        x = _layer(x, canonical_layer(params, i), shape=shape, fp8=fp8)
    return x


def logits_at(params, dims, seqs: Sequence[Tuple[Sequence[int], int]],
              fp8: bool = False, rows_per_call: int = 8) -> List[np.ndarray]:
    """For each ``(tokens, first)`` the float32 logits over the published
    vocabulary at positions ``first .. len(tokens)-1`` (the positions
    that predicted tokens ``first+1 ..`` and one more), as numpy
    ``(len(tokens) - first, vocab)``.  Sequences are padded to a bucket
    and run a few rows at a time, layer by layer."""
    vocab, eps = dims["vocab"], float(dims["eps"])
    head = canonical_head(params)
    out: List[np.ndarray] = [None] * len(seqs)        # type: ignore
    order = sorted(range(len(seqs)), key=lambda i: -len(seqs[i][0]))
    while order:
        S = -(-len(seqs[order[0]][0]) // BUCKET) * BUCKET
        # attention scores (rows, heads, S, S) float32 stay under 1 GiB
        rows = max(1, min(rows_per_call,
                          (1 << 28) // (dims["heads"] * S * S)))
        group, order = order[:rows], order[rows:]
        toks = np.zeros((len(group), S), np.int32)
        for r, i in enumerate(group):
            toks[r, :len(seqs[i][0])] = seqs[i][0]
        x = _hidden(params, dims, toks, fp8)
        for r, i in enumerate(group):
            tokens, first = seqs[i]
            xs = x[r, first:len(tokens)]
            blocks = [np.asarray(_head_block(
                xs, head["final_norm"], head["head_rows"][v:v + HEAD_BLOCK],
                eps=eps, fp8=fp8)) for v in range(0, vocab, HEAD_BLOCK)]
            out[i] = np.concatenate(blocks, axis=-1)[:, :vocab]
    return out


# ------------------------------------------------------ the work counts
def matmul_params(conf: dict) -> tuple:
    """(per-layer matmul parameters summed over the layers run, output
    head parameters) of a configuration file."""
    d, ff = conf["hidden_size"], conf["intermediate_size"]
    h, kv, dh = (conf["num_attention_heads"], conf["num_key_value_heads"],
                 conf["head_dim"])
    layer = d * (h + 2 * kv) * dh + h * dh * d + 3 * d * ff
    return conf["num_hidden_layers"] * layer, d * conf["vocab_size"]


def decode_step_bytes(a: dict, conf: dict) -> float:
    """Bytes a decode step must read: every layer and head matmul
    parameter at 2 bytes, and the live KV tokens' keys and values at 2
    bytes, whatever implements the step or stores the pool; ``a`` is the
    traced run's reduction (``spans.reduce``)."""
    layers, head = matmul_params(conf)
    kv_tokens = a["decode_kv_tokens"] / a["decode_steps"]
    kv = (kv_tokens * conf["num_hidden_layers"] * 2
          * conf["num_key_value_heads"] * conf["head_dim"] * 2)
    return 2.0 * (layers + head) + kv
