"""The DeepSeekMoE architecture (``model_type`` ``"deepseek"``), as the
benchmark knows it.  The same names as ``arch/qwen3.py`` (see its
docstring), and one more: ``expert_matmul_op``, the rule that tells the
grouped expert matmul's events apart in a device trace.

The reference follows the published DeepSeekMoE decoder
(hf:deepseek-ai/deepseek-moe-16b-base ``modeling_deepseek``, arXiv
2401.06066): RMSNorm before attention and MLP, multi-head causal
attention with rotate-half rotary embedding (``rope_theta``) and no
qk-norm, then either a dense SwiGLU MLP (the first
``first_k_dense_replace`` layers) or the MoE block: a softmax gate in
float32 over all routed experts, the top ``num_experts_per_tok`` kept
with their weights (renormalised only under ``norm_topk_prob``), each
token's output the gate-weighted sum of its experts' SwiGLU plus the
shared experts' SwiGLU (one MLP of width ``n_shared_experts *
moe_intermediate_size``); final RMSNorm and an untied output head.
Every matmul in float32 under ``default_matmul_precision("highest")``,
layer by layer so that it fits beside the bf16 weights; it imports
nothing of the system under test.

Departures from ``modeling_deepseek``, none of which changes the
mathematics of inference: every routed expert is computed for every
token and weighted by a dense gate matrix that is zero outside the
token's top experts (the published code gathers each expert's tokens);
the expert outputs are summed in float32 where the published code sums
in the model's dtype; the gate's auxiliary loss (training only) is left
out.

``fp8=True`` is the control: the same forward with every attention,
dense, shared-expert and routed-expert matrix and the output head
rounded to float8 (e4m3, one scale per output channel), the step below
the bf16 the configuration states; the router is not rounded.
"""
from __future__ import annotations

import functools
import math
import re
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

BUCKET = 128                 # sequence lengths pad to this (causal: inert)
HEAD_BLOCK = 16384           # vocabulary rows scored per head matmul
MATRICES = ("q_proj", "k_proj", "v_proj", "o_proj", "gate_proj", "up_proj",
            "down_proj", "shared_gate_proj", "shared_up_proj",
            "shared_down_proj")
EXPERTS = ("experts_gate_proj", "experts_up_proj", "experts_down_proj")
_MISSING = object()


class Fields(dict):
    """The attributes a nested config object must have, as a dict that
    compares equal to any object whose named attributes hold these
    values: ``program.model_config``'s ``!=`` then checks a nested group
    key by key."""

    def __eq__(self, other):
        return all(getattr(other, k, _MISSING) == v
                   for k, v in self.items())

    def __ne__(self, other):
        return not self.__eq__(other)


# ------------------------------------------------- the configuration run
def expect(conf: dict) -> dict:
    """``ModelConfig`` field -> the value the configuration file fixes;
    the MoE group as ``Fields``.  A file stating what the program cannot
    serve (experts on every ``k``-th layer only, a gate other than a
    softmax) is refused here."""
    if conf["moe_layer_freq"] != 1 or conf["scoring_func"] != "softmax":
        raise ValueError(f"{conf['name']}: the program serves MoE on every "
                         f"layer after the dense ones, softmax-gated only")
    d, heads = conf["hidden_size"], conf["num_attention_heads"]
    return {"d_model": d, "num_layers": conf["num_hidden_layers"],
            "num_heads": heads, "num_kv_heads": conf["num_key_value_heads"],
            "d_ff": conf["intermediate_size"],
            "vocab_size": conf["vocab_size"], "resolved_head_dim": d // heads,
            "tie_embeddings": conf["tie_word_embeddings"],
            "rope_theta": float(conf["rope_theta"]),
            "norm_eps": conf["rms_norm_eps"], "qk_norm": False,
            "family": "moe", "dtype": conf["torch_dtype"],
            "param_dtype": conf["torch_dtype"], "attention_impl": "xla",
            "moe": Fields(num_experts=conf["n_routed_experts"],
                          top_k=conf["num_experts_per_tok"],
                          d_ff_expert=conf["moe_intermediate_size"],
                          num_shared_experts=conf["n_shared_experts"],
                          first_dense_layers=conf["first_k_dense_replace"],
                          d_ff_dense=conf["intermediate_size"],
                          norm_topk_prob=conf["norm_topk_prob"])}


def dims(mc) -> dict:
    """The sizes the plain reference needs, read from the config run."""
    m = mc.moe
    return {"heads": mc.num_heads, "kv_heads": mc.num_kv_heads,
            "head_dim": mc.resolved_head_dim, "rope_theta": mc.rope_theta,
            "eps": mc.norm_eps, "vocab": mc.vocab_size, "top_k": m.top_k,
            "norm_topk_prob": m.norm_topk_prob}


# ----------------------------------------------------- the reference's view
def canonical_layer(params, i: int) -> dict:
    """Layer ``i``'s weights as the published architecture names them, in
    float32: projection matrices (in, out), RMSNorm gains, and for a MoE
    layer the router (d, E) and the routed experts stacked (E, in, out).
    Layer 0.. are the lead (dense) blocks, then the scanned MoE blocks."""
    lead = params.get("lead_blocks", [])
    if i < len(lead):
        b, ix = lead[i], ()
    else:
        b, ix = params["blocks"], (i - len(lead),)

    def f32(a):
        return jnp.asarray(a[ix], jnp.float32)

    w = {"input_norm": 1.0 + f32(b["ln1"]),
         "post_attn_norm": 1.0 + f32(b["ln2"]),
         "q_proj": f32(b["attn"]["wq"]), "k_proj": f32(b["attn"]["wk"]),
         "v_proj": f32(b["attn"]["wv"]), "o_proj": f32(b["attn"]["wo"])}
    if "ffn" in b:
        w.update(gate_proj=f32(b["ffn"]["w_gate"]),
                 up_proj=f32(b["ffn"]["w_up"]),
                 down_proj=f32(b["ffn"]["w_down"]))
    else:
        m = b["moe"]
        w.update(router=f32(m["router"]),
                 experts_gate_proj=f32(m["w_gate"]),
                 experts_up_proj=f32(m["w_up"]),
                 experts_down_proj=f32(m["w_down"]),
                 shared_gate_proj=f32(m["shared"]["w_gate"]),
                 shared_up_proj=f32(m["shared"]["w_up"]),
                 shared_down_proj=f32(m["shared"]["w_down"]))
    return w


def canonical_head(params) -> dict:
    """The embedding table (rows = token ids, padded past the published
    vocabulary), the final norm gain in float32, and the untied output
    head as ``head_rows`` (vocab, d), row v scoring token v.  Tables stay
    in their stored dtype; the reference upcasts the rows it uses."""
    return {"embed": params["embed"],
            "final_norm": 1.0 + jnp.asarray(params["final_norm"],
                                            jnp.float32),
            "head_rows": params["lm_head"].T}


def num_layers(params) -> int:
    return (len(params.get("lead_blocks", []))
            + int(params["blocks"]["ln1"].shape[0]))


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


def swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def moe_mlp(h, w: Dict[str, jax.Array], top_k: int, norm_topk_prob: bool,
            fp8: bool):
    """The MoE block on h (B, S, d): the softmax gate's top-k weights as a
    dense (B, S, E) matrix, every routed expert on every token weighted
    by it (one expert at a time), plus the shared experts."""
    probs = jax.nn.softmax(h @ w["router"], axis=-1)
    top_w, top_i = jax.lax.top_k(probs, top_k)
    if norm_topk_prob:
        top_w = top_w / (top_w.sum(-1, keepdims=True) + 1e-20)
    gate = jnp.sum(jax.nn.one_hot(top_i, probs.shape[-1]) * top_w[..., None],
                   axis=-2)

    def expert(y, xs):
        wg, wu, wd, g = xs
        if fp8:
            wg, wu, wd = fp8_round(wg), fp8_round(wu), fp8_round(wd)
        return y + g[..., None] * swiglu(h, wg, wu, wd), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(h),
                        tuple(w[k] for k in EXPERTS)
                        + (jnp.moveaxis(gate, -1, 0),))
    return y + swiglu(h, w["shared_gate_proj"], w["shared_up_proj"],
                      w["shared_down_proj"])


@functools.partial(jax.jit, static_argnames=("shape", "fp8"))
def _layer(x, w: Dict[str, jax.Array], *, shape: Tuple, fp8: bool):
    heads, kv_heads, head_dim, theta, eps, top_k, norm_topk = shape
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
        q, k = rope(q, pos, theta), rope(k, pos, theta)
        g = heads // kv_heads
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(head_dim)
        causal = pos[None, :] <= pos[:, None]
        s = jnp.where(causal[None, None], s, -jnp.inf)
        o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
        x = x + o.reshape(B, S, heads * head_dim) @ w["o_proj"]
        h = rms_norm(x, w["post_attn_norm"], eps)
        if "router" in w:
            return x + moe_mlp(h, w, top_k, norm_topk, fp8)
        return x + swiglu(h, w["gate_proj"], w["up_proj"], w["down_proj"])


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
             float(dims["rope_theta"]), float(dims["eps"]), dims["top_k"],
             bool(dims["norm_topk_prob"]))
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
def _widths(conf: dict) -> tuple:
    d, f = conf["hidden_size"], conf["moe_intermediate_size"]
    heads, kv = conf["num_attention_heads"], conf["num_key_value_heads"]
    dh = d // heads
    attn = d * (heads + 2 * kv) * dh + heads * dh * d
    n_dense = conf["first_k_dense_replace"]
    n_moe = conf["num_hidden_layers"] - n_dense
    return d, f, attn, n_dense, n_moe


def expert_params(conf: dict) -> int:
    """Matmul parameters of one routed expert: 3 * d * F."""
    return 3 * conf["hidden_size"] * conf["moe_intermediate_size"]


def matmul_params(conf: dict) -> tuple:
    """(per-token active matmul parameters summed over the layers run,
    output head parameters) of a configuration file: attention in every
    layer, the dense MLP in the dense layers, and in each MoE layer the
    shared experts, the ``num_experts_per_tok`` routed experts a token
    takes and the router."""
    d, f, attn, n_dense, n_moe = _widths(conf)
    dense = 3 * d * conf["intermediate_size"]
    moe = (3 * d * f * conf["n_shared_experts"]
           + conf["num_experts_per_tok"] * expert_params(conf)
           + d * conf["n_routed_experts"])
    layers = conf["num_hidden_layers"] * attn + n_dense * dense + n_moe * moe
    return layers, d * conf["vocab_size"]


def uniform_experts(rows: float, conf: dict) -> float:
    """Distinct experts a MoE layer reads when ``rows`` tokens route
    uniformly at random: E * (1 - (1 - K/E)^rows)."""
    e, k = conf["n_routed_experts"], conf["num_experts_per_tok"]
    return e * (1.0 - (1.0 - k / e) ** rows)


def experts_read_per_step(a: dict) -> float:
    """Experts a decode step read, summed over its MoE layers: the mean
    ``experts_read`` of the program's ``engine.sync`` spans in the
    traced run's reduction ``a`` (``spans.reduce``); None where the
    program records none."""
    got = [s[5]["experts_read"] for s in a["spans"]
           if s[0] == "engine.sync" and "experts_read" in s[5]]
    return sum(got) / len(got) if got else None


def decode_step_bytes(a: dict, conf: dict) -> float:
    """Bytes a decode step must read, whatever implements it: attention
    and the dense MLP, the shared experts and the output head at 2 bytes
    a parameter, the router at its stored 4, the routed experts the
    step's rows routed to at 2 (``experts_read_per_step``; where the
    program records none, what uniform routing of the window's mean
    rows would read), and the live KV tokens' keys and values at 2."""
    d, f, attn, n_dense, n_moe = _widths(conf)
    read = experts_read_per_step(a)
    if read is None:
        read = n_moe * uniform_experts(a["decode_rows"] / a["decode_steps"],
                                       conf)
    params = (conf["num_hidden_layers"] * attn
              + n_dense * 3 * d * conf["intermediate_size"]
              + n_moe * 3 * d * f * conf["n_shared_experts"]
              + read * expert_params(conf) + d * conf["vocab_size"])
    router = n_moe * d * conf["n_routed_experts"] * 4
    kv_tokens = a["decode_kv_tokens"] / a["decode_steps"]
    kv = (kv_tokens * conf["num_hidden_layers"] * 2
          * conf["num_key_value_heads"] * (d // conf["num_attention_heads"])
          * 2)
    return 2.0 * params + router + kv


# ------------------------------------------- the expert kernel in a trace
def expert_matmul_op(text: str, conf: dict) -> bool:
    """Whether a device op event, named by its HLO text, is the grouped
    expert matmul: an op that reads an operand of the routed experts'
    weights, bf16 (n * E, d, F) or (n * E, F, d) (one layer's experts or
    every scanned layer's), and whose result is not of that shape (a
    copy or a slice of them) and is no loop over a body."""
    d, f = conf["hidden_size"], conf["moe_intermediate_size"]
    e = conf["n_routed_experts"]

    def experts_in(s: str) -> bool:
        return any(int(n) % e == 0 and {int(a), int(b)} == {d, f}
                   for n, a, b in re.findall(r"bf16\[(\d+),(\d+),(\d+)\]",
                                             s))

    m = re.match(r"\S+ = (.*?) ([a-z][\w-]*)\((.*)", text, re.S)
    if m is None or m.group(2) == "while" or experts_in(m.group(1)):
        return False
    return experts_in(m.group(3))


def expert_work(a: dict, conf: dict) -> tuple:
    """(token-expert slots, experts read) the program's spans in the
    traced window recorded: slots on ``engine.prefill`` and
    ``engine.decode``, experts read on ``engine.prefill`` and
    ``engine.sync``; spans that start inside the window."""
    win_ms = a["window_s"] * 1e3
    slots = read = 0
    for name, start, _, _, _, attrs in a["spans"]:
        if not 0 <= start <= win_ms:
            continue
        if name in ("engine.prefill", "engine.decode"):
            slots += attrs.get("slots", 0)
        if name in ("engine.prefill", "engine.sync"):
            read += attrs.get("experts_read", 0)
    return slots, read


def expert_matmul_bound_s(slots: int, read: int, conf: dict,
                          peak: dict) -> float:
    """The least time the chip could take for the grouped SwiGLU of
    ``slots`` token-expert slots over ``read`` experts: the larger of its
    FLOPs (2 * 3 * d * F a slot) at the bf16 peak and its bytes (each
    expert read's weights once at 2 bytes, and each slot's activations:
    its input row and output row of d, its gate, up and product of F, at
    2 bytes) at the HBM bandwidth."""
    d, f = conf["hidden_size"], conf["moe_intermediate_size"]
    flops = 2.0 * expert_params(conf) * slots
    bytes_ = 2.0 * (expert_params(conf) * read + slots * (2 * d + 3 * f))
    return max(flops / float(peak["bf16_flops"]),
               bytes_ / float(peak["hbm_bytes_per_s"]))
