"""The hybrid state-space decoder (``models/hybrid_ssm.py``): attention
heads and a Mamba-2 mixer side by side in every layer, then a SwiGLU MLP.
``falcon-h1-34b-d5`` is of it. The contract is in
``chipbench/architectures/__init__.py``; below it, the counts of bytes and
FLOPs that this architecture's roofline readers ask for.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

MULTIPLIERS = ("embedding_multiplier", "lm_head_multiplier",
               "attention_in_multiplier", "attention_out_multiplier",
               "key_multiplier", "ssm_in_multiplier", "ssm_out_multiplier",
               "ssm_multipliers", "mlp_multipliers")
WIDTHS = ("hidden_size", "intermediate_size", "num_attention_heads",
          "num_key_value_heads", "head_dim", "mamba_d_ssm", "mamba_n_heads",
          "mamba_d_head", "mamba_d_state", "mamba_n_groups", "mamba_d_conv",
          "mamba_expand", "mlp_expansion_factor")
AS_PUBLISHED = WIDTHS + MULTIPLIERS + (
    "vocab_size", "rope_theta", "rms_norm_eps", "mamba_rms_norm",
    "mamba_norm_before_gate", "mamba_conv_bias", "mamba_proj_bias",
    "attention_bias", "mlp_bias", "projectors_bias", "tie_word_embeddings",
    "hidden_act", "rope_scaling")
REQUIRED = AS_PUBLISHED + ("num_hidden_layers", "max_position_embeddings",
                           "param_dtype", "activation_dtype")
# (key of a configuration file, field of the program's configuration)
_FIELDS = (("vocab_size", "vocab_size"), ("hidden_size", "d_model"),
           ("num_hidden_layers", "n_layers"),
           ("num_attention_heads", "n_heads"),
           ("num_key_value_heads", "kv_heads"), ("head_dim", "head_dim"),
           ("intermediate_size", "d_ff"), ("mamba_n_heads", "ssm_heads"),
           ("mamba_d_head", "ssm_head_dim"), ("mamba_d_state", "ssm_state"),
           ("mamba_n_groups", "ssm_groups"), ("mamba_d_conv", "ssm_conv"),
           ("max_position_embeddings", "max_seq"),
           ("rope_theta", "rope_theta"), ("rms_norm_eps", "rms_norm_eps")) \
    + tuple((m, m) for m in MULTIPLIERS)


# ------------------------------------------------------------- the program
def program_config(cfg: Dict[str, Any], **over):
    """The program's ``HybridSSMConfig`` of a configuration file."""
    import jax.numpy as jnp

    from ray_memory_management_tpu.models import hybrid_ssm

    if cfg["mamba_d_ssm"] != cfg["mamba_n_heads"] * cfg["mamba_d_head"]:
        raise ValueError("mamba_d_ssm is not heads x head size")
    if not cfg["mamba_rms_norm"] or cfg["mamba_norm_before_gate"] \
            or not cfg["mamba_conv_bias"] or cfg["tie_word_embeddings"] \
            or cfg["rope_scaling"] is not None or any(
                cfg[k] for k in ("attention_bias", "mlp_bias",
                                 "projectors_bias", "mamba_proj_bias")):
        raise ValueError("the program has the gated norm after the gate, a "
                         "convolution bias, untied embeddings, plain RoPE "
                         "and no other bias")
    fields = {ours: tuple(cfg[key]) if isinstance(cfg[key], list)
              else cfg[key] for key, ours in _FIELDS}
    fields.update(dtype=jnp.dtype(cfg["activation_dtype"]),
                  param_dtype=jnp.dtype(cfg["param_dtype"]))
    fields.update(over)
    return hybrid_ssm.HybridSSMConfig(**fields)


def init_program_params(key, program_cfg):
    """The weights of the configuration file's recipe, which is the
    reference's (the program's own ``init_params`` is a plain one: the same
    tree, every matrix normal * fan_in**-0.5 whatever multiplies it)."""
    import jax.numpy as jnp

    c = program_cfg
    cfg = {key_: list(v) if isinstance(v, tuple) else v
           for key_, v in ((k, getattr(c, ours)) for k, ours in _FIELDS)}
    cfg["mamba_d_ssm"] = c.ssm_inner
    return reference().init_params(key, cfg, jnp.dtype(c.param_dtype))


def program_loss(params, batch, program_cfg, mesh):
    raise NotImplementedError("the program serves this model; no train loss")


def server_class():
    # a program without the model fails here, in the benchmark's own
    # process and before anything is deployed
    from ray_memory_management_tpu.models import hybrid_ssm  # noqa: F401
    from ray_memory_management_tpu.serve.llm import LLMServer

    return LLMServer


def server_kwargs(cfg: Dict[str, Any]) -> Dict[str, Any]:
    return {"config": program_config(cfg), "init": init_program_params}


# ----------------------------------------------------------- the reference
def reference():
    from chipbench.reference import hybrid_ssm

    return hybrid_ssm


# ------------------------------------------------------ counts from shapes
def _itemsize(name: str) -> int:
    return 2 if name == "bfloat16" else 4


def _conv_width(cfg: dict) -> int:
    return cfg["mamba_d_ssm"] + 2 * cfg["mamba_n_groups"] \
        * cfg["mamba_d_state"]


def _parts(cfg: dict) -> Dict[str, int]:
    """Matmul parameters of each part of a layer."""
    d = cfg["hidden_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    proj = cfg["mamba_d_ssm"] + _conv_width(cfg) + cfg["mamba_n_heads"]
    return {"attn": 2 * d * q + 2 * d * kv,
            "ssm": d * proj + cfg["mamba_d_ssm"] * d,
            "mlp": 3 * d * cfg["intermediate_size"]}


def _ssm_small(cfg: dict) -> int:
    """A layer's state-space parameters outside its two matmuls: the
    convolution's taps and bias, ``A_log``, ``D``, ``dt_bias``, the gated
    norm's scale."""
    return _conv_width(cfg) * (cfg["mamba_d_conv"] + 1) \
        + 3 * cfg["mamba_n_heads"] + cfg["mamba_d_ssm"]


def cache_token_bytes(cfg: Dict[str, Any]) -> int:
    """K and V of every layer, a position."""
    return cfg["num_hidden_layers"] * 2 * cfg["num_key_value_heads"] \
        * cfg["head_dim"] * _itemsize(cfg["activation_dtype"])


def state_row_bytes(cfg: Dict[str, Any]) -> int:
    """What a slot holds whatever its length, over all layers: the
    recurrence's state in float32 and the convolution's last inputs."""
    ssm = cfg["mamba_n_heads"] * cfg["mamba_d_head"] * cfg["mamba_d_state"] * 4
    conv = (cfg["mamba_d_conv"] - 1) * _conv_width(cfg) \
        * _itemsize(cfg["activation_dtype"])
    return cfg["num_hidden_layers"] * (ssm + conv)


def matmul_params(cfg: dict) -> Tuple[float, int]:
    """(matmul parameters a layer, parameters of the output head)."""
    return float(sum(_parts(cfg).values())), \
        cfg["hidden_size"] * cfg["vocab_size"]


def n_params(cfg: dict) -> int:
    """All parameters held: the layers (matmuls, the state-space branch's
    small ones, two norms), the final norm, embedding and untied head."""
    d, n = cfg["hidden_size"], cfg["num_hidden_layers"]
    return n * (sum(_parts(cfg).values()) + _ssm_small(cfg) + 2 * d) + d \
        + 2 * d * cfg["vocab_size"]


def _ssm_token_flops(cfg: dict) -> float:
    """FLOPs a token and layer of the recurrence in its sequential form and
    of the convolution: the state's decay, the outer product added to it and
    its contraction with C (2 FLOPs each an element of the state), D x, and
    two a tap and channel."""
    state = cfg["mamba_n_heads"] * cfg["mamba_d_head"] * cfg["mamba_d_state"]
    return 6.0 * state + 2.0 * cfg["mamba_d_ssm"] \
        + 2.0 * cfg["mamba_d_conv"] * _conv_width(cfg)


def forward_flops(cfg: dict, tokens: int, attended: int) -> float:
    """Two a matmul parameter and token, the head among them; attention's
    QK^T and PV over ``attended`` (query, key) pairs; the recurrence and the
    convolution as :func:`_ssm_token_flops` counts them (the chunked scan
    does more arithmetic than that; it is not counted)."""
    layer, head = matmul_params(cfg)
    n = cfg["num_hidden_layers"]
    pair = 4.0 * cfg["num_attention_heads"] * cfg["head_dim"]
    return tokens * (2.0 * (n * layer + head) + n * _ssm_token_flops(cfg)) \
        + attended * n * pair


def attention_shape(cfg: dict) -> Tuple[int, int]:
    return cfg["num_attention_heads"], cfg["head_dim"]


# ------------------------------------------- what the roofline readers ask
def decode_step_work(cfg: dict, rows: float,
                     positions: float) -> Tuple[float, float]:
    """(FLOPs, bytes) the least one decode token-step needs with ``rows``
    live rows that attend over ``positions`` cached positions between them:
    every weight of the step once (the layers and the head; the embedding is
    a lookup), each live row's state read and written once, and the live
    positions' K and V; FLOPs as ``forward_flops`` counts them. Whatever
    implements the step."""
    layer, head = matmul_params(cfg)
    n = cfg["num_hidden_layers"]
    weights = (n * (layer + _ssm_small(cfg)) + head) \
        * _itemsize(cfg["param_dtype"])
    return (forward_flops(cfg, rows, positions),
            weights + 2.0 * rows * state_row_bytes(cfg)
            + positions * cache_token_bytes(cfg))


def ssm_update_work(cfg: dict, rows: float) -> Tuple[float, float]:
    """(FLOPs, bytes) of one call of the state-update kernel (one layer,
    ``rows`` live rows): a live row's recurrent state of that layer read
    once and written once in float32 (the token's x, B, C and dt are a
    thousandth of it and not counted); 6 FLOPs an element of the state."""
    state = cfg["mamba_n_heads"] * cfg["mamba_d_head"] * cfg["mamba_d_state"]
    return 6.0 * rows * state, 2.0 * rows * state * 4
