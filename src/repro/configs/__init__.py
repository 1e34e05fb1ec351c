"""Architecture registry: ``--arch <id>`` resolves here.

``get_config(arch)`` returns the FULL assigned config; ``get_smoke(arch)``
returns the reduced same-family config used by CPU smoke tests;
``models_for(graph)`` maps the model ids a workload names onto the
config that serves them.
``input_specs(cfg, shape)`` builds ShapeDtypeStruct stand-ins for the
dry-run (no device allocation).
"""
from __future__ import annotations

import importlib
from typing import Dict

import jax
import jax.numpy as jnp

from repro.configs.base import (
    ModelConfig, MoEConfig, ShapeSpec, SHAPES, shape_applicable,
)

_MODULES = {
    "deepseek-moe-16b": "deepseek_moe_16b",
    "mixtral-8x22b": "mixtral_8x22b",
    "whisper-tiny": "whisper_tiny",
    "deepseek-67b": "deepseek_67b",
    "llama3.2-3b": "llama32_3b",
    "qwen3-1.7b": "qwen3_1_7b",
    "qwen3-8b": "qwen3_8b",
    "internvl2-2b": "internvl2_2b",
    "xlstm-350m": "xlstm_350m",
    "recurrentgemma-2b": "recurrentgemma_2b",
}

ARCH_IDS = tuple(_MODULES)


def _module(arch: str):
    if arch not in _MODULES:
        raise KeyError(f"unknown arch {arch!r}; known: {sorted(_MODULES)}")
    return importlib.import_module(f"repro.configs.{_MODULES[arch]}")


def get_config(arch: str) -> ModelConfig:
    return _module(arch).CONFIG


def get_smoke(arch: str) -> ModelConfig:
    return _module(arch).smoke_config()


# the architecture behind every model id a workload names
SERVED_ARCH = "qwen3-1.7b"


def models_for(graph, arch: str = SERVED_ARCH,
               smoke: bool = False) -> Dict[str, ModelConfig]:
    """``{model id: ModelConfig}`` for every model the graph's LLM nodes
    name: ``arch`` at its published widths (``smoke``: its reduced
    preset, for CPU runs), renamed to the id so engines, switches and
    reports keep the workload's names."""
    base = get_smoke(arch) if smoke else get_config(arch)
    names = {graph.nodes[n].model for n in graph.llm_nodes()}
    return {m: base.replace(name=m) for m in sorted(names)}


def all_configs() -> Dict[str, ModelConfig]:
    return {a: get_config(a) for a in ARCH_IDS}


# ---------------------------------------------------------------------------
# dry-run input specs (ShapeDtypeStruct, no allocation)
# ---------------------------------------------------------------------------

def input_specs(cfg: ModelConfig, shape: ShapeSpec) -> Dict[str, jax.ShapeDtypeStruct]:
    """Model inputs for one (arch, shape) cell as ShapeDtypeStructs.

    train  -> {tokens, labels}
    prefill-> {tokens}
    decode -> {token} (one new token; the KV cache itself is created by the
              step factory, also as specs)
    Modality frontends are stubs: audio adds ``frames`` (B, enc_len, D)
    precomputed frame embeddings; vlm adds ``patch_embeds`` (B, P, D).
    """
    i32 = jnp.int32
    bf16 = jnp.bfloat16
    B, S = shape.global_batch, shape.seq_len
    specs: Dict[str, jax.ShapeDtypeStruct] = {}
    if shape.kind == "train":
        specs["tokens"] = jax.ShapeDtypeStruct((B, S), i32)
        specs["labels"] = jax.ShapeDtypeStruct((B, S), i32)
    elif shape.kind == "prefill":
        specs["tokens"] = jax.ShapeDtypeStruct((B, S), i32)
    elif shape.kind == "decode":
        specs["token"] = jax.ShapeDtypeStruct((B,), i32)
    else:
        raise ValueError(shape.kind)

    if cfg.family == "audio" and shape.kind != "decode":
        # enc-dec: frame embeddings from the (stubbed) conv frontend
        enc_len = min(cfg.enc_max_len, S)
        specs["frames"] = jax.ShapeDtypeStruct((B, enc_len, cfg.d_model), bf16)
    if cfg.family == "vlm" and shape.kind != "decode":
        specs["patch_embeds"] = jax.ShapeDtypeStruct(
            (B, cfg.num_patches, cfg.d_model), bf16)
    return specs


__all__ = [
    "ModelConfig", "MoEConfig", "ShapeSpec", "SHAPES", "ARCH_IDS",
    "get_config", "get_smoke", "all_configs", "input_specs",
    "shape_applicable", "SERVED_ARCH", "models_for",
]
