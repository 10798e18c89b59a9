"""The latent-attention, routed-expert decoder (``models/latent_moe.py``):
MLA with a compressed cache, leading dense layers, then sigmoid-routed SwiGLU
experts without drops beside a shared expert. ``glm-4.7-flash-d7`` is of it.
The contract is in ``chipbench/architectures/__init__.py``; below it, the
counts of bytes and FLOPs that this architecture's roofline readers ask for.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

WIDTHS = ("hidden_size", "intermediate_size", "moe_intermediate_size",
          "num_attention_heads", "num_key_value_heads", "q_lora_rank",
          "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
          "v_head_dim", "num_experts_per_tok")
AS_PUBLISHED = WIDTHS + ("vocab_size", "n_routed_experts",
                         "n_shared_experts", "first_k_dense_replace",
                         "routed_scaling_factor", "norm_topk_prob",
                         "rope_theta", "rms_norm_eps", "n_group",
                         "topk_group", "topk_method")
REQUIRED = AS_PUBLISHED + ("num_hidden_layers", "max_position_embeddings",
                           "param_dtype", "activation_dtype")
LANES = 128  # the pool pads the cached vector to whole lanes


# ------------------------------------------------------------- the program
def program_config(cfg: Dict[str, Any], **over):
    """The program's ``LatentMoEConfig`` of a configuration file."""
    import jax.numpy as jnp

    from ray_memory_management_tpu.models import latent_moe

    if cfg["n_group"] != 1 or cfg["topk_group"] != 1:
        raise ValueError("the program's router has no group limit")
    fields = dict(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"], q_lora_rank=cfg["q_lora_rank"],
        kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], d_ff=cfg["intermediate_size"],
        moe_d_ff=cfg["moe_intermediate_size"],
        n_routed_experts=cfg["n_routed_experts"],
        n_shared_experts=cfg["n_shared_experts"],
        experts_per_tok=cfg["num_experts_per_tok"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        norm_topk_prob=cfg["norm_topk_prob"],
        first_k_dense=cfg["first_k_dense_replace"],
        max_seq=cfg["max_position_embeddings"], rope_theta=cfg["rope_theta"],
        rms_norm_eps=cfg["rms_norm_eps"],
        dtype=jnp.dtype(cfg["activation_dtype"]),
        param_dtype=jnp.dtype(cfg["param_dtype"]))
    fields.update(over)
    return latent_moe.LatentMoEConfig(**fields)


def init_program_params(key, program_cfg):
    """The weights of the configuration file's recipe, which is the
    reference's (the program's own ``init_params`` is a plain one: it makes
    the same tree of independent experts and a bias of 0)."""
    import jax.numpy as jnp

    c = program_cfg
    return reference().init_params(key, dict(
        vocab_size=c.vocab_size, hidden_size=c.d_model,
        num_hidden_layers=c.n_layers, num_attention_heads=c.n_heads,
        q_lora_rank=c.q_lora_rank, kv_lora_rank=c.kv_lora_rank,
        qk_nope_head_dim=c.qk_nope_head_dim,
        qk_rope_head_dim=c.qk_rope_head_dim, v_head_dim=c.v_head_dim,
        intermediate_size=c.d_ff, moe_intermediate_size=c.moe_d_ff,
        n_routed_experts=c.n_routed_experts,
        n_shared_experts=c.n_shared_experts,
        num_experts_per_tok=c.experts_per_tok,
        first_k_dense_replace=c.first_k_dense), jnp.dtype(c.param_dtype))


def program_loss(params, batch, program_cfg, mesh):
    raise NotImplementedError("the program serves this model; no train loss")


def server_class():
    # a program without the model fails here, in the benchmark's own
    # process and before anything is deployed
    from ray_memory_management_tpu.models import latent_moe  # noqa: F401
    from ray_memory_management_tpu.serve.llm import LLMServer

    return LLMServer


def server_kwargs(cfg: Dict[str, Any]) -> Dict[str, Any]:
    return {"config": program_config(cfg), "init": init_program_params}


# ----------------------------------------------------------- the reference
def reference():
    from chipbench.reference import latent_moe

    return latent_moe


# ------------------------------------------------------ counts from shapes
def _itemsize(name: str) -> int:
    return 2 if name == "bfloat16" else 4


def cache_width(cfg: Dict[str, Any]) -> int:
    """Values the pool holds a token and layer: the normed compressed KV
    and the rotary key (576 for GLM-4.7-Flash), padded to whole lanes (640)."""
    held = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    return -(-held // LANES) * LANES


def cache_token_bytes(cfg: Dict[str, Any]) -> int:
    """What the pool really holds a token, padding counted."""
    return cfg["num_hidden_layers"] * cache_width(cfg) \
        * _itemsize(cfg["activation_dtype"])


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


def matmul_params(cfg: dict) -> Tuple[float, int]:
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
    final one), the router's choosing bias, embedding and untied head."""
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
    is routed to and the shared ones, not the experts that are only held;
    the head. Attention in its plain form: QK^T over the query / key head,
    PV over the value head, two FLOPs each an attended pair (the absorbed
    decode does more arithmetic than that; it is not counted)."""
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


# ------------------------------------------- what the roofline readers ask
def decode_step_work(cfg: dict, rows: float, positions: float,
                     experts_touched: float) -> Tuple[float, float]:
    """(FLOPs, bytes) the least one decode token-step needs with ``rows``
    live rows that attend over ``positions`` cached positions between them
    and whose tokens reach ``experts_touched`` routed experts in a mean
    expert layer: the touched experts' weights, every other weight the step
    uses once (attention, routers, shared experts, the dense layers, the
    head; the embedding is a lookup), and the live positions' cache; FLOPs
    as ``forward_flops`` counts them. Whatever implements the step."""
    p, (dense, sparse) = _parts(cfg), _layers(cfg)
    size = _itemsize(cfg["param_dtype"])
    weights = (cfg["num_hidden_layers"] * p["attn"] + dense * p["dense_mlp"]
               + sparse * (p["router"] + cfg["n_shared_experts"]
                           * p["expert"] + experts_touched * p["expert"])
               + cfg["hidden_size"] * cfg["vocab_size"])
    return (forward_flops(cfg, rows, positions),
            weights * size + positions * cache_token_bytes(cfg))


def latent_attention_work(cfg: dict, fetched: float,
                          live: float) -> Tuple[float, float]:
    """(FLOPs, bytes) of one call of the latent decode kernel (one layer,
    every row): the pages it must fetch hold ``fetched`` positions, each
    ``cache_width`` values, once for all heads; over the ``live`` positions
    among them every head takes a dot product over the cached vector (the
    normed KV and the rotary key) and a weighted sum over the normed KV."""
    held = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    flops = 2.0 * live * cfg["num_attention_heads"] \
        * (held + cfg["kv_lora_rank"])
    return flops, float(fetched * cache_width(cfg)
                        * _itemsize(cfg["activation_dtype"]))
