"""The dense grouped-query decoder (``models/gpt.py``): RMSNorm, rotary
positions, GQA, SwiGLU, untied head. ``mistral-7b-d16`` and
``internlm2-1.8b-d6`` are of it; a configuration file that names no
architecture is too. The contract is in ``chipbench/architectures/__init__.py``.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

WIDTHS = ("hidden_size", "intermediate_size", "head_dim",
          "num_attention_heads", "num_key_value_heads")
AS_PUBLISHED = ("hidden_size", "intermediate_size", "vocab_size",
                "num_attention_heads", "num_key_value_heads")
REQUIRED = WIDTHS + ("vocab_size", "num_hidden_layers", "rope_theta",
                     "rms_norm_eps", "max_position_embeddings",
                     "param_dtype", "activation_dtype")


# ------------------------------------------------------------- the program
def program_config(cfg: Dict[str, Any], **over):
    """The program's ``TransformerConfig`` of a configuration file."""
    import jax.numpy as jnp

    from ray_memory_management_tpu.models import gpt

    if cfg["hidden_size"] != cfg["num_attention_heads"] * cfg["head_dim"]:
        raise ValueError("the program derives head_dim from hidden_size")
    fields = dict(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], d_ff=cfg["intermediate_size"],
        max_seq=cfg["max_position_embeddings"], rope_theta=cfg["rope_theta"],
        dtype=jnp.dtype(cfg["activation_dtype"]),
        param_dtype=jnp.dtype(cfg["param_dtype"]))
    fields.update(over)
    return gpt.TransformerConfig(**fields)


def init_program_params(key, program_cfg):
    from ray_memory_management_tpu.models import gpt

    return gpt.init_params(key, program_cfg)


def program_loss(params, batch, program_cfg, mesh):
    from ray_memory_management_tpu.models import gpt

    return gpt.loss_fn(params, batch, program_cfg, mesh=mesh)


def server_class():
    from ray_memory_management_tpu.serve.llm import LLMServer

    return LLMServer


def server_kwargs(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """``LLMServer`` takes a preset's name, so the file's configuration is
    registered under its own (PERF.md, Open questions: program seams)."""
    from ray_memory_management_tpu.models import gpt

    gpt.PRESETS[cfg["name"]] = program_config(cfg)
    return {"preset": cfg["name"]}


# ----------------------------------------------------------- the reference
def reference():
    from chipbench.reference import model

    return model


# ------------------------------------------------------ counts from shapes
def cache_token_bytes(cfg: Dict[str, Any]) -> int:
    """K and V of every layer's KV heads, in the activations' type."""
    itemsize = 2 if cfg["activation_dtype"] == "bfloat16" else 4
    return (2 * cfg["num_hidden_layers"] * cfg["num_key_value_heads"]
            * cfg["head_dim"] * itemsize)


def matmul_params(cfg: dict) -> Tuple[int, int]:
    """(parameters of one layer's matmuls, parameters of the output head).
    The embedding is a lookup and counts no FLOPs."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, hkv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    layer = d * h * dh + 2 * d * hkv * dh + h * dh * d + 3 * d * f
    return layer, d * cfg["vocab_size"]


def n_params(cfg: dict) -> int:
    """All parameters: layers, norms, embedding and untied head."""
    layer, head = matmul_params(cfg)
    d, n = cfg["hidden_size"], cfg["num_hidden_layers"]
    return n * (layer + 2 * d) + d + 2 * head


def forward_flops(cfg: dict, tokens: int, attended: int) -> float:
    """Forward FLOPs of ``tokens`` token positions that attend, between
    them, to ``attended`` (query, key) pairs (causal: position p attends
    p + 1 keys). Two FLOPs per multiply-add; softmax and norms are not
    counted. Every parameter of a dense layer is used by every token."""
    layer, head = matmul_params(cfg)
    n = cfg["num_hidden_layers"]
    dense = 2.0 * tokens * (n * layer + head)
    # QK^T and PV: 2 matmuls x 2 FLOPs x heads x head_dim per attended pair
    attn = 4.0 * attended * n * cfg["num_attention_heads"] * cfg["head_dim"]
    return dense + attn


def attention_shape(cfg: dict) -> Tuple[int, int]:
    return cfg["num_attention_heads"], cfg["head_dim"]
