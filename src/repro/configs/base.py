"""Configuration system for the repro framework.

Every assigned architecture is expressed as a :class:`ModelConfig`.  The
config is a frozen dataclass so it can be hashed / used as a jit static
argument, and every field is serializable for checkpoint manifests.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Optional, Tuple

# ---------------------------------------------------------------------------
# Hardware constants (TPU v5e-class target; see DESIGN.md §2)
# ---------------------------------------------------------------------------
PEAK_FLOPS_BF16 = 197e12      # per chip, FLOP/s
HBM_BW = 819e9                # per chip, bytes/s
HBM_BYTES = 16 * 1024**3      # per chip
ICI_BW = 50e9                 # per link, bytes/s
DCN_BW = 25e9                 # per host, bytes/s (cross-pod)
HOST_TO_HBM_BW = 32e9         # weight-loading path (PCIe-class), bytes/s


def round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


@dataclass(frozen=True)
class MoEConfig:
    num_experts: int
    top_k: int
    d_ff_expert: int
    num_shared_experts: int = 0
    first_dense_layers: int = 0          # leading layers that use a dense FFN
    d_ff_dense: int = 0                  # width of those dense FFNs
    norm_topk_prob: bool = True          # renormalise the top-K gate weights
    router_aux_weight: float = 0.01


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                          # dense|moe|audio|vlm|ssm|hybrid
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0                    # 0 -> d_model // num_heads
    max_seq_len: int = 532480            # generous default; shapes clamp it
    rope_theta: float = 500000.0
    qk_norm: bool = False
    swa_window: int = 0                  # 0 -> full attention
    tie_embeddings: bool = False
    norm_eps: float = 1e-6
    moe: Optional[MoEConfig] = None
    # --- encoder/decoder (whisper) ---
    enc_layers: int = 0                  # >0 => encoder-decoder model
    enc_max_len: int = 0
    # --- hybrid / ssm block pattern ---
    # e.g. ("rglru", "rglru", "attn") repeated; ("mlstm", "slstm") repeated
    block_pattern: Tuple[str, ...] = ()
    local_attn_window: int = 2048        # for hybrid local attention blocks
    lru_width: int = 0                   # RG-LRU width (0 -> d_model)
    conv1d_width: int = 4                # temporal conv inside recurrent block
    # --- vlm ---
    num_patches: int = 0                 # prepended patch embeddings (stub frontend)
    # --- numerics ---
    dtype: str = "bfloat16"
    param_dtype: str = "bfloat16"
    # --- attention implementation: "xla" (ref) or "pallas" ---
    attention_impl: str = "xla"
    # Whether the KV/prefix-sharing discount of the Halo cost model may be
    # applied at sub-prefix granularity (False for pure-recurrent archs).
    supports_partial_prefix: bool = True

    # ------------------------------------------------------------------
    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // self.num_heads

    @property
    def padded_vocab(self) -> int:
        """Vocab padded so embedding/lm_head shard evenly on 16-way TP."""
        return round_up(self.vocab_size, 256)

    @property
    def is_encdec(self) -> bool:
        return self.enc_layers > 0

    @property
    def is_recurrent(self) -> bool:
        return self.family in ("ssm", "hybrid")

    @property
    def full_attention(self) -> bool:
        """True if the arch relies on unbounded dense self-attention."""
        if self.family == "ssm":
            return False
        if self.family == "hybrid":
            return False                  # local attention windows are bounded
        return self.swa_window == 0

    def param_count(self) -> int:
        """Analytic parameter count (embedding + blocks + head)."""
        d, dh = self.d_model, self.resolved_head_dim
        h, hkv = self.num_heads, self.num_kv_heads
        embed = self.padded_vocab * d
        head = 0 if self.tie_embeddings else self.padded_vocab * d

        def attn_params():
            return d * h * dh + 2 * d * hkv * dh + h * dh * d

        def dense_ffn(ff):
            return 3 * d * ff

        total = embed + head + d  # final norm
        pattern = self.block_pattern or ("attn",) * self.num_layers
        for i in range(self.num_layers):
            kind = pattern[i % len(pattern)]
            total += 2 * d  # norms
            if kind == "attn":
                total += attn_params()
                if self.moe is not None:
                    m = self.moe
                    if i < m.first_dense_layers:
                        total += dense_ffn(m.d_ff_dense or self.d_ff)
                    else:
                        total += m.num_experts * 3 * d * m.d_ff_expert
                        total += m.num_shared_experts * 3 * d * m.d_ff_expert
                        total += d * m.num_experts  # router
                elif self.d_ff:
                    total += dense_ffn(self.d_ff)
            elif kind == "rglru":
                w = self.lru_width or d
                # in/out proj + gates + conv
                total += 2 * d * w + 2 * w + self.conv1d_width * w + w * d
                total += dense_ffn(self.d_ff) if self.d_ff else 0
            elif kind in ("mlstm", "slstm"):
                inner = 2 * d
                total += d * inner * 4 + inner * d  # projections + gates (approx)
        if self.is_encdec:
            # encoder blocks + cross attention in decoder
            total += self.enc_layers * (2 * d + attn_params() + dense_ffn(self.d_ff))
            total += self.num_layers * (d + attn_params())
        return int(total)

    def active_param_count(self) -> int:
        """Params touched per token (MoE activates top_k + shared only)."""
        if self.moe is None:
            return self.param_count()
        m = self.moe
        d = self.d_model
        total_experts = self.num_layers - m.first_dense_layers
        inactive = total_experts * (m.num_experts - m.top_k) * 3 * d * m.d_ff_expert
        return int(self.param_count() - inactive)

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


# ---------------------------------------------------------------------------
# Input shapes assigned to the LM family (all 10 archs share this set)
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class ShapeSpec:
    name: str
    seq_len: int
    global_batch: int
    kind: str          # "train" | "prefill" | "decode"


SHAPES = {
    "train_4k": ShapeSpec("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeSpec("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeSpec("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeSpec("long_500k", 524288, 1, "decode"),
}


def shape_applicable(cfg: ModelConfig, shape: ShapeSpec) -> Tuple[bool, str]:
    """Whether an (arch, shape) cell is runnable; reason if not.

    long_500k requires sub-quadratic attention (bounded window / recurrent
    state); pure full-attention archs skip it (documented in DESIGN.md).
    """
    if shape.name == "long_500k" and cfg.full_attention:
        return False, "full dense attention cannot hold a 512k KV (O(S^2))"
    return True, ""
