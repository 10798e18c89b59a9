"""The layer-pattern decoder (``models/nemotron_h.py``): Mamba-2 mixers,
attention without a positional embedding and experts in a latent space, one
mixer a layer in the order ``hybrid_override_pattern`` gives, **with one
chip's share of each layer's routed experts and of the vocabulary**.
``nemotron-3-super-d11-e128`` is of it. The contract is in
``chipbench/architectures/__init__.py``.

Beyond the contract, what this architecture's readers ask (each ``(FLOPs,
bytes)`` of the least work, whatever implements it):

``state_row_bytes(cfg)``
    what a slot holds whatever its length, over the Mamba layers.
``ssm_layers(cfg)``, ``expert_layers(cfg)``
    how many layers of the pattern keep a state, and hold experts.
``held_expert_share(cfg)``
    held experts over the router's width: the share of a token's
    assignments that land here when the load is even.
``decode_step_work(cfg, rows, positions, experts_touched)``
    one decode token-step: the touched *held* experts, every other weight
    once, live rows' state twice, live positions' K and V.
``ssm_update_work(cfg, rows)``
    one call of the state-update kernel (one Mamba layer).
``expert_kernel_tiles(cfg, rows)`` and ``expert_kernel_work(cfg,
assignments, experts_touched)``
    the expert kernel's grid for a step of ``rows`` tokens (its name in the
    trace ends in that count), and one call of it (one expert layer).

A configuration file of this architecture gives, beside the published keys,
``n_routed_experts`` as the count **held here** (in ``reduced``),
``n_router_experts`` as the published count the router keeps, and
``first_held_expert``.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

WIDTHS = ("hidden_size", "intermediate_size", "head_dim",
          "num_attention_heads", "num_key_value_heads", "mamba_num_heads",
          "mamba_head_dim", "ssm_state_size", "n_groups", "conv_kernel",
          "expand", "moe_intermediate_size", "moe_latent_size",
          "moe_shared_expert_intermediate_size", "num_experts_per_tok")
AS_PUBLISHED = WIDTHS + (
    "layer_norm_epsilon", "norm_eps", "routed_scaling_factor",
    "norm_topk_prob", "n_group", "topk_group", "n_shared_experts",
    "rope_theta", "partial_rotary_factor", "use_bias", "mlp_bias",
    "attention_bias", "mamba_proj_bias", "use_conv_bias",
    "tie_word_embeddings", "mlp_hidden_act", "mamba_hidden_act",
    "time_step_min", "time_step_max", "time_step_floor", "chunk_size",
    "sliding_window", "moe_shared_expert_overlap", "model_type")
REQUIRED = AS_PUBLISHED + (
    "num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
    "vocab_size", "max_position_embeddings", "num_nextn_predict_layers",
    "n_router_experts", "first_held_expert", "param_dtype",
    "activation_dtype")
# (key of a configuration file, field of the program's configuration)
_FIELDS = (("vocab_size", "vocab_size"), ("hidden_size", "d_model"),
           ("hybrid_override_pattern", "pattern"),
           ("num_attention_heads", "n_heads"),
           ("num_key_value_heads", "kv_heads"), ("head_dim", "head_dim"),
           ("mamba_num_heads", "ssm_heads"),
           ("mamba_head_dim", "ssm_head_dim"),
           ("ssm_state_size", "ssm_state"), ("n_groups", "ssm_groups"),
           ("conv_kernel", "ssm_conv"), ("moe_latent_size", "moe_latent"),
           ("moe_intermediate_size", "moe_d_ff"),
           ("moe_shared_expert_intermediate_size", "shared_d_ff"),
           ("n_router_experts", "n_routed_experts"),
           ("n_routed_experts", "n_held_experts"),
           ("first_held_expert", "first_held_expert"),
           ("num_experts_per_tok", "experts_per_tok"),
           ("routed_scaling_factor", "routed_scaling_factor"),
           ("norm_topk_prob", "norm_topk_prob"),
           ("max_position_embeddings", "max_seq"),
           ("layer_norm_epsilon", "rms_norm_eps"))


# ------------------------------------------------------------- the program
def program_config(cfg: Dict[str, Any], **over):
    """The program's ``NemotronHConfig`` of a configuration file."""
    import jax.numpy as jnp

    from ray_memory_management_tpu.models import nemotron_h

    if len(cfg["hybrid_override_pattern"]) != cfg["num_hidden_layers"]:
        raise ValueError("the pattern has another length than the layers")
    if cfg["expand"] * cfg["hidden_size"] \
            != cfg["mamba_num_heads"] * cfg["mamba_head_dim"]:
        raise ValueError("expand x hidden is not heads x head size")
    if cfg["n_group"] != 1 or cfg["topk_group"] != 1 \
            or cfg["n_shared_experts"] != 1 or not cfg["use_conv_bias"] \
            or cfg["tie_word_embeddings"] or cfg["num_nextn_predict_layers"] \
            or cfg["mlp_hidden_act"] != "relu2" \
            or cfg["mamba_hidden_act"] != "silu" \
            or cfg["sliding_window"] is not None or any(
                cfg[k] for k in ("use_bias", "mlp_bias", "attention_bias",
                                 "mamba_proj_bias")):
        raise ValueError("the program has a router without a group limit, "
                         "one shared expert, squared-ReLU experts, a "
                         "convolution bias and no other, untied embeddings, "
                         "full attention and no drafting head")
    fields = {ours: cfg[key] for key, ours in _FIELDS}
    fields.update(dtype=jnp.dtype(cfg["activation_dtype"]),
                  param_dtype=jnp.dtype(cfg["param_dtype"]))
    fields.update(over)
    return nemotron_h.NemotronHConfig(**fields)


def init_program_params(key, program_cfg):
    """The weights of the configuration file's recipe, which is the
    reference's (the program's own ``init_params`` is a plain one: the same
    tree, a router of even gains, both biases 0)."""
    import jax.numpy as jnp

    c = program_cfg
    cfg = {key_: getattr(c, ours) for key_, ours in _FIELDS}
    return reference().init_params(key, cfg, jnp.dtype(c.param_dtype))


def program_loss(params, batch, program_cfg, mesh):
    raise NotImplementedError("the program serves this model; no train loss")


def server_class():
    # a program without the model fails here, in the benchmark's own
    # process and before anything is deployed
    from ray_memory_management_tpu.models import nemotron_h  # noqa: F401
    from ray_memory_management_tpu.serve.llm import LLMServer

    return LLMServer


def server_kwargs(cfg: Dict[str, Any]) -> Dict[str, Any]:
    return {"config": program_config(cfg), "init": init_program_params}


# ----------------------------------------------------------- the reference
def reference():
    from chipbench.reference import nemotron_h

    return nemotron_h


# ------------------------------------------------------ counts from shapes
def _itemsize(name: str) -> int:
    return 2 if name == "bfloat16" else 4


def _inner(cfg: dict) -> int:
    return cfg["mamba_num_heads"] * cfg["mamba_head_dim"]


def _conv_width(cfg: dict) -> int:
    return _inner(cfg) + 2 * cfg["n_groups"] * cfg["ssm_state_size"]


def ssm_layers(cfg: dict) -> int:
    return cfg["hybrid_override_pattern"].count("M")


def expert_layers(cfg: dict) -> int:
    return cfg["hybrid_override_pattern"].count("E")


def _attention_layers(cfg: dict) -> int:
    return cfg["hybrid_override_pattern"].count("*")


def held_expert_share(cfg: dict) -> float:
    return cfg["n_routed_experts"] / cfg["n_router_experts"]


def _parts(cfg: dict) -> Dict[str, int]:
    """Matmul parameters of each part: a Mamba layer, an attention layer,
    one routed expert, and an expert layer outside its routed experts (the
    router, the two latent projections, the shared expert)."""
    d, z = cfg["hidden_size"], cfg["moe_latent_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    proj = _inner(cfg) + _conv_width(cfg) + cfg["mamba_num_heads"]
    return {"mamba": d * proj + _inner(cfg) * d,
            "attn": 2 * d * q + 2 * d * kv,
            "expert": 2 * z * cfg["moe_intermediate_size"],
            "expert_layer": d * cfg["n_router_experts"] + 2 * d * z
            + 2 * d * cfg["moe_shared_expert_intermediate_size"]}


def _mamba_small(cfg: dict) -> int:
    """A Mamba layer's parameters outside its two matmuls: the convolution's
    taps and bias, ``A_log``, ``D``, ``dt_bias``, the gated norm's scale."""
    return _conv_width(cfg) * (cfg["conv_kernel"] + 1) \
        + 3 * cfg["mamba_num_heads"] + _inner(cfg)


def cache_token_bytes(cfg: Dict[str, Any]) -> int:
    """K and V of the attention layers, a position."""
    return _attention_layers(cfg) * 2 * cfg["num_key_value_heads"] \
        * cfg["head_dim"] * _itemsize(cfg["activation_dtype"])


def state_row_bytes(cfg: Dict[str, Any]) -> int:
    """What a slot holds whatever its length, over the Mamba layers: the
    recurrence's state in float32 and the convolution's last inputs."""
    ssm = _inner(cfg) * cfg["ssm_state_size"] * 4
    conv = (cfg["conv_kernel"] - 1) * _conv_width(cfg) \
        * _itemsize(cfg["activation_dtype"])
    return ssm_layers(cfg) * (ssm + conv)


def _held_matmuls(cfg: dict) -> int:
    """Matmul parameters of all layers as held here (every held expert)."""
    p = _parts(cfg)
    return (ssm_layers(cfg) * p["mamba"] + _attention_layers(cfg) * p["attn"]
            + expert_layers(cfg) * (p["expert_layer"]
                                    + cfg["n_routed_experts"] * p["expert"]))


def matmul_params(cfg: dict) -> Tuple[float, int]:
    """(matmul parameters a layer *holds*, every held expert among them, as
    the mean over the layers, which differ; parameters of the output head
    over the held slice of the vocabulary)."""
    return _held_matmuls(cfg) / cfg["num_hidden_layers"], \
        cfg["hidden_size"] * cfg["vocab_size"]


def n_params(cfg: dict) -> int:
    """All parameters held here: the layers' matmuls with the held experts,
    a Mamba layer's small ones, the router's choosing bias, a norm a layer,
    the final norm, embedding and untied head over the held vocabulary."""
    d, n = cfg["hidden_size"], cfg["num_hidden_layers"]
    return (_held_matmuls(cfg) + ssm_layers(cfg) * _mamba_small(cfg)
            + expert_layers(cfg) * cfg["n_router_experts"] + n * d + d
            + 2 * d * cfg["vocab_size"])


def _ssm_token_flops(cfg: dict) -> float:
    """FLOPs a token and Mamba layer of the recurrence in its sequential
    form and of the convolution: the state's decay, the outer product added
    to it and its contraction with C (2 FLOPs each an element of the state),
    D x, and two a tap and channel."""
    state = _inner(cfg) * cfg["ssm_state_size"]
    return 6.0 * state + 2.0 * _inner(cfg) \
        + 2.0 * cfg["conv_kernel"] * _conv_width(cfg)


def forward_flops(cfg: dict, tokens: int, attended: int) -> float:
    """FLOPs of the parameters a token uses **here**: the Mamba and
    attention layers, in an expert layer the router, the two latent
    projections, the shared expert and the routed experts a token is sent to
    *among those held* (``num_experts_per_tok`` x the held share: 5.5 of 22
    for 128 of 512; the experts that live on other chips are not run here,
    and the experts that are only held are not used), the head over the held
    vocabulary; attention's QK^T and PV over ``attended`` (query, key)
    pairs in each attention layer; the recurrence and the convolution in
    their sequential form (the chunked scan does more arithmetic; it is not
    counted)."""
    p = _parts(cfg)
    routed = cfg["num_experts_per_tok"] * held_expert_share(cfg)
    a_token = (ssm_layers(cfg) * p["mamba"]
               + _attention_layers(cfg) * p["attn"]
               + expert_layers(cfg) * (p["expert_layer"]
                                       + routed * p["expert"])
               + cfg["hidden_size"] * cfg["vocab_size"])
    pair = 4.0 * cfg["num_attention_heads"] * cfg["head_dim"]
    return tokens * (2.0 * a_token
                     + ssm_layers(cfg) * _ssm_token_flops(cfg)) \
        + attended * _attention_layers(cfg) * pair


def attention_shape(cfg: dict) -> Tuple[int, int]:
    return cfg["num_attention_heads"], cfg["head_dim"]


# ------------------------------------------- what the roofline readers ask
def decode_step_work(cfg: dict, rows: float, positions: float,
                     experts_touched: float) -> Tuple[float, float]:
    """(FLOPs, bytes) the least one decode token-step needs with ``rows``
    live rows that attend over ``positions`` cached positions between them
    and whose tokens reach ``experts_touched`` of the *held* routed experts
    in a mean expert layer: the touched held experts' weights, every other
    weight of the step once (the Mamba and attention layers, routers, latent
    projections, shared experts, the head; the embedding is a lookup), each
    live row's state read and written once, the live positions' K and V;
    FLOPs as ``forward_flops`` counts them. An expert that lives elsewhere,
    an expert no live token reached and an idle slot's state are not
    counted."""
    p = _parts(cfg)
    weights = (ssm_layers(cfg) * (p["mamba"] + _mamba_small(cfg))
               + _attention_layers(cfg) * p["attn"]
               + expert_layers(cfg) * (p["expert_layer"]
                                       + experts_touched * p["expert"])
               + cfg["hidden_size"] * cfg["vocab_size"]) \
        * _itemsize(cfg["param_dtype"])
    return (forward_flops(cfg, rows, positions),
            weights + 2.0 * rows * state_row_bytes(cfg)
            + positions * cache_token_bytes(cfg))


def ssm_update_work(cfg: dict, rows: float) -> Tuple[float, float]:
    """(FLOPs, bytes) of one call of the state-update kernel (one Mamba
    layer, ``rows`` live rows): a live row's recurrent state of that layer
    read once and written once in float32 (the token's x, B, C and dt are a
    thousandth of it and not counted); 6 FLOPs an element of the state."""
    state = _inner(cfg) * cfg["ssm_state_size"]
    return 6.0 * rows * state, 2.0 * rows * state * 4


EXPERT_TILE = 128  # rows of a tile of the program's expert kernel


def expert_kernel_tiles(cfg: dict, rows: int) -> int:
    """Tiles in the grid of ``ops/moe.py``'s expert kernel for ``rows``
    tokens: a tile a held expert where the rows are no more than a tile (a
    decode step), else each assignment's row, and for each held expert its
    last tile's empty rest. The kernel is named ``moe_expert_tiles_<tiles>``, so the
    decode step's calls and a prefill's do not share a name."""
    if rows <= EXPERT_TILE:     # a tile a held expert: the step's own rows
        return cfg["n_routed_experts"]
    return -(-rows * cfg["num_experts_per_tok"] // EXPERT_TILE) \
        + cfg["n_routed_experts"]


def expert_kernel_work(cfg: dict, assignments: float,
                       experts_touched: float) -> Tuple[float, float]:
    """(FLOPs, bytes) of one call of the expert kernel (one expert layer):
    the two matrices of the ``experts_touched`` held experts that have a row,
    once each; each of the ``assignments`` to held experts' rows read in the
    latent width and its result written in float32; two matmuls a row. The
    rest of a tile that an expert's rows do not fill is the kernel's own
    waste and is not counted."""
    z, f = cfg["moe_latent_size"], cfg["moe_intermediate_size"]
    return (assignments * 4.0 * z * f,
            experts_touched * 2 * z * f * _itemsize(cfg["param_dtype"])
            + assignments * z * (_itemsize(cfg["activation_dtype"]) + 4))
