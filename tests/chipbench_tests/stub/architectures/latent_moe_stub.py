"""A stub architecture for the tests of chipbench's seam: latent attention
(compressed queries and a compressed KV cache with a shared rotary key) and
sparse experts behind a sigmoid router, beside a shared expert, after
leading dense layers. It has the key set of the catalog's rows of that kind
(no ``head_dim``, a hidden size that is no multiple of the head count) at
sizes a hand can count. The program has no such model, so the program's
side answers with a plain record and no server; everything the benchmark
reckons itself (keys, cache bytes, counts, the reference) is real.

The contract is in ``chipbench/architectures/__init__.py``.
"""

from __future__ import annotations

import types
from typing import Any, Dict, Tuple

WIDTHS = ("hidden_size", "intermediate_size", "moe_intermediate_size",
          "num_attention_heads", "num_key_value_heads", "q_lora_rank",
          "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
          "v_head_dim", "num_experts_per_tok")
AS_PUBLISHED = WIDTHS + ("vocab_size", "n_routed_experts",
                         "n_shared_experts", "first_k_dense_replace",
                         "routed_scaling_factor")
REQUIRED = AS_PUBLISHED + ("num_hidden_layers", "rope_theta", "rms_norm_eps",
                           "max_position_embeddings", "param_dtype",
                           "activation_dtype")


# ------------------------------------------------------------- the program
def program_config(cfg: Dict[str, Any], **over):
    """No program stands behind the stub: the file's sizes as a record."""
    return types.SimpleNamespace(**{k: cfg[k] for k in REQUIRED}, **over)


def _no_program(*_a, **_k):
    raise NotImplementedError("the program has no latent-attention model")


init_program_params = program_loss = server_class = server_kwargs = _no_program


# ----------------------------------------------------------- the reference
def reference():
    from chipbench.reference import latent_moe_stub

    return latent_moe_stub


# ------------------------------------------------------ counts from shapes
def cache_token_bytes(cfg: Dict[str, Any]) -> int:
    """One compressed KV vector and one shared rotary key a layer."""
    itemsize = 2 if cfg["activation_dtype"] == "bfloat16" else 4
    return cfg["num_hidden_layers"] * (
        cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * itemsize


def _parts(cfg: dict) -> Dict[str, int]:
    """Matmul parameters of each part of a layer."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    attn = (d * cfg["q_lora_rank"] + cfg["q_lora_rank"] * h * qk
            + d * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
            + cfg["kv_lora_rank"] * h * (cfg["qk_nope_head_dim"]
                                         + cfg["v_head_dim"])
            + h * cfg["v_head_dim"] * d)
    return {"attn": attn, "dense_mlp": 3 * d * cfg["intermediate_size"],
            "expert": 3 * d * cfg["moe_intermediate_size"],
            "router": d * cfg["n_routed_experts"]}


def _layers(cfg: dict) -> Tuple[int, int]:
    """(leading dense layers, expert layers)."""
    dense = min(cfg["first_k_dense_replace"], cfg["num_hidden_layers"])
    return dense, cfg["num_hidden_layers"] - dense


def matmul_params(cfg: dict) -> Tuple[int, int]:
    """(matmul parameters a layer *holds*, every expert among them, as the
    mean over the layers, which differ; parameters of the output head)."""
    p, (dense, sparse) = _parts(cfg), _layers(cfg)
    held = cfg["n_routed_experts"] + cfg["n_shared_experts"]
    total = (cfg["num_hidden_layers"] * p["attn"] + dense * p["dense_mlp"]
             + sparse * (held * p["expert"] + p["router"]))
    return total / cfg["num_hidden_layers"], \
        cfg["hidden_size"] * cfg["vocab_size"]


def n_params(cfg: dict) -> int:
    """All parameters held: layers with every expert, the norms (two a
    layer over the hidden size, one each over the two latent ranks, the
    final one), the router's selection bias, embedding and untied head."""
    p, (dense, sparse) = _parts(cfg), _layers(cfg)
    held = cfg["n_routed_experts"] + cfg["n_shared_experts"]
    n, d = cfg["num_hidden_layers"], cfg["hidden_size"]
    norms = n * (2 * d + cfg["q_lora_rank"] + cfg["kv_lora_rank"]) + d
    return (n * p["attn"] + dense * p["dense_mlp"]
            + sparse * (held * p["expert"] + p["router"]
                        + cfg["n_routed_experts"])
            + norms + 2 * d * cfg["vocab_size"])


def forward_flops(cfg: dict, tokens: int, attended: int) -> float:
    """FLOPs of the parameters a token *uses*: attention, the dense MLP in
    a leading layer, and in an expert layer the router, the experts a token
    is routed to and the shared ones, not the experts that are only held.
    Attention over the decompressed keys and values: QK^T over the query /
    key head, PV over the value head, two FLOPs each an attended pair."""
    p, (dense, sparse) = _parts(cfg), _layers(cfg)
    used = cfg["num_experts_per_tok"] + cfg["n_shared_experts"]
    a_token = (cfg["num_hidden_layers"] * p["attn"] + dense * p["dense_mlp"]
               + sparse * (used * p["expert"] + p["router"])
               + cfg["hidden_size"] * cfg["vocab_size"])
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    pair = 2.0 * cfg["num_attention_heads"] * (qk + cfg["v_head_dim"])
    return 2.0 * tokens * a_token \
        + attended * cfg["num_hidden_layers"] * pair


def attention_shape(cfg: dict) -> Tuple[int, int]:
    return (cfg["num_attention_heads"],
            cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"])
