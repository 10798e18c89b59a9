"""Latent attention over the positions a learned indexer selects, with one
chip's share of each layer's routed experts and of the vocabulary
(``models/latent_sparse_moe.py``): MLA with a compressed cache, an indexer in
the ``full`` layers whose selection the ``shared`` layers above it reuse,
leading dense layers, then sigmoid-routed SwiGLU experts beside a shared
expert. ``glm-5.2-d6-e16`` is of it. The contract is in
``chipbench/architectures/__init__.py``.

Beyond the contract, what this architecture's readers ask (each ``(FLOPs,
bytes)`` of the least work, whatever implements it):

``layer_counts(cfg)``
    (layers, layers with an indexer of their own, expert layers).
``held_expert_share(cfg)``
    held experts over the router's width.
``selected_pairs(cfg, length, start)``
    the (query, position) pairs the published model attends in one layer.
``index_score_work(cfg, pairs, keys)``
    one call of the scoring kernel: ``pairs`` (query, position) scores over
    ``keys`` cached index keys, each read once.
``sparse_attention_work(cfg, pairs, fetched)``
    one call of the attention kernel: ``pairs`` attended (query, selected
    position) pairs in absorbed form, ``fetched`` cached vectors read, once
    for all heads.
``step_work(cfg, tokens, logit_rows, selected, scored, fetched, keys,
experts_touched)``
    one step of ``tokens`` rows (a chunk's real positions and the live decode
    rows together) of which ``logit_rows`` go through the head; ``selected``
    attended and ``scored`` indexed pairs, ``fetched`` cached vectors and
    ``keys`` index keys read, each summed over the layers;
    ``experts_touched`` held experts a mean expert layer reaches.

A configuration file of this architecture gives, beside the published keys,
``n_routed_experts`` as the count **held here** (in ``reduced``),
``n_router_experts`` as the published count the router keeps, and
``first_held_expert``.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

# the latent vector's width in the pool (576 padded to 640), a type's bytes
# and the attention kernel's operand shape are the sibling architecture's
from chipbench.architectures.latent_moe import (  # noqa: F401
    _itemsize, attention_shape, cache_width)

WIDTHS = ("hidden_size", "intermediate_size", "moe_intermediate_size",
          "num_attention_heads", "num_key_value_heads", "head_dim",
          "q_lora_rank", "kv_lora_rank", "qk_head_dim", "qk_nope_head_dim",
          "qk_rope_head_dim", "v_head_dim", "num_experts_per_tok",
          "index_head_dim", "index_n_heads", "index_topk")
AS_PUBLISHED = WIDTHS + (
    "n_shared_experts", "routed_scaling_factor", "norm_topk_prob",
    "scoring_func", "topk_method", "n_group", "topk_group", "rms_norm_eps",
    "rope_parameters", "rope_interleave", "indexer_rope_interleave",
    "index_topk_freq", "index_skip_topk_offset", "index_topk_pattern",
    "index_share_for_mtp_iteration", "moe_layer_freq", "attention_bias",
    "hidden_act", "tie_word_embeddings", "ep_size", "model_type")
REQUIRED = AS_PUBLISHED + (
    "num_hidden_layers", "first_k_dense_replace", "mlp_layer_types",
    "indexer_types", "n_routed_experts", "vocab_size",
    "max_position_embeddings", "num_nextn_predict_layers",
    "n_router_experts", "first_held_expert", "param_dtype",
    "activation_dtype")
# (key of a configuration file, field of the program's configuration)
_FIELDS = (("vocab_size", "vocab_size"), ("hidden_size", "d_model"),
           ("num_attention_heads", "n_heads"),
           ("q_lora_rank", "q_lora_rank"), ("kv_lora_rank", "kv_lora_rank"),
           ("qk_nope_head_dim", "qk_nope_head_dim"),
           ("qk_rope_head_dim", "qk_rope_head_dim"),
           ("v_head_dim", "v_head_dim"), ("intermediate_size", "d_ff"),
           ("moe_intermediate_size", "moe_d_ff"),
           ("n_router_experts", "n_routed_experts"),
           ("n_routed_experts", "n_held_experts"),
           ("first_held_expert", "first_held_expert"),
           ("n_shared_experts", "n_shared_experts"),
           ("num_experts_per_tok", "experts_per_tok"),
           ("routed_scaling_factor", "routed_scaling_factor"),
           ("norm_topk_prob", "norm_topk_prob"),
           ("index_n_heads", "index_n_heads"),
           ("index_head_dim", "index_head_dim"),
           ("index_topk", "index_topk"), ("indexer_types", "indexer_types"),
           ("first_k_dense_replace", "first_k_dense"),
           ("max_position_embeddings", "max_seq"),
           ("rms_norm_eps", "rms_norm_eps"))


# ------------------------------------------------------------- the program
def program_config(cfg: Dict[str, Any], **over):
    """The program's ``LatentSparseMoEConfig`` of a configuration file."""
    import jax.numpy as jnp

    from ray_memory_management_tpu.models import latent_sparse_moe

    if cfg["n_group"] != 1 or cfg["topk_group"] != 1:
        raise ValueError("the program's router has no group limit")
    n, dense = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    if len(cfg["indexer_types"]) != n or list(cfg["mlp_layer_types"]) \
            != ["dense"] * dense + ["sparse"] * (n - dense):
        raise ValueError("indexer_types and mlp_layer_types are a layer each, "
                         "the dense layers leading")
    fields = {field: cfg[key] for key, field in _FIELDS}
    fields.update(indexer_types=tuple(cfg["indexer_types"]),
                  rope_theta=cfg["rope_parameters"]["rope_theta"],
                  dtype=jnp.dtype(cfg["activation_dtype"]),
                  param_dtype=jnp.dtype(cfg["param_dtype"]))
    fields.update(over)
    return latent_sparse_moe.LatentSparseMoEConfig(**fields)


def init_program_params(key, program_cfg):
    """The weights of the configuration file's recipe, which is the
    reference's (the program's own ``init_params`` is a plain one: it makes
    the same tree with plain weights, a choosing bias of 0 and independent
    experts)."""
    import jax.numpy as jnp

    c = program_cfg
    cfg = {key: getattr(c, field) for key, field in _FIELDS}
    cfg.update(num_hidden_layers=c.n_layers,
               rope_parameters={"rope_theta": c.rope_theta})
    return reference().init_params(key, cfg, jnp.dtype(c.param_dtype))


def program_loss(params, batch, program_cfg, mesh):
    raise NotImplementedError("the program serves this model; no train loss")


def server_class():
    # a program without the model fails here, in the benchmark's own
    # process and before anything is deployed
    from ray_memory_management_tpu.models import latent_sparse_moe  # noqa: F401
    from ray_memory_management_tpu.serve.llm import LLMServer

    return LLMServer


def server_kwargs(cfg: Dict[str, Any]) -> Dict[str, Any]:
    return {"config": program_config(cfg), "init": init_program_params}


# ----------------------------------------------------------- the reference
def reference():
    from chipbench.reference import latent_sparse_moe

    return latent_sparse_moe


# ------------------------------------------------------ counts from shapes
def layer_counts(cfg: dict) -> Tuple[int, int, int]:
    """(layers, layers with an indexer of their own, expert layers)."""
    n = cfg["num_hidden_layers"]
    return n, list(cfg["indexer_types"]).count("full"), \
        n - min(cfg["first_k_dense_replace"], n)


def cache_token_bytes(cfg: Dict[str, Any]) -> int:
    """What the pool really holds a token over both arrays, padding counted:
    the latent vector in every layer, the index key in the ``full`` ones."""
    n, full, _ = layer_counts(cfg)
    return (n * cache_width(cfg) + full * cfg["index_head_dim"]) \
        * _itemsize(cfg["activation_dtype"])


def _parts(cfg: dict) -> Dict[str, int]:
    """Matmul parameters of each part of a layer."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    ql = cfg["q_lora_rank"]
    attn = (d * ql + ql * h * cfg["qk_head_dim"]
            + d * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
            + cfg["kv_lora_rank"] * h * (cfg["qk_nope_head_dim"]
                                         + cfg["v_head_dim"])
            + h * cfg["v_head_dim"] * d)
    j, di = cfg["index_n_heads"], cfg["index_head_dim"]
    return {"attn": attn, "indexer": ql * j * di + d * di + d * j,
            "dense_mlp": 3 * d * cfg["intermediate_size"],
            "expert": 3 * d * cfg["moe_intermediate_size"],
            "router": d * cfg["n_router_experts"]}


def _layer_params(cfg: dict, experts: float) -> float:
    """Matmul parameters of all layers with ``experts`` routed experts (and
    the shared ones) counted in each expert layer."""
    p, (n, full, sparse) = _parts(cfg), layer_counts(cfg)
    return (n * p["attn"] + full * p["indexer"]
            + (n - sparse) * p["dense_mlp"]
            + sparse * ((experts + cfg["n_shared_experts"]) * p["expert"]
                        + p["router"]))


def matmul_params(cfg: dict) -> Tuple[float, int]:
    """(matmul parameters a layer *holds*, the held experts among them, as
    the mean over the layers, which differ; parameters of the output head)."""
    return _layer_params(cfg, cfg["n_routed_experts"]) \
        / cfg["num_hidden_layers"], cfg["hidden_size"] * cfg["vocab_size"]


def n_params(cfg: dict) -> int:
    """All parameters held here: layers with the held experts, the norms (two
    a layer over the hidden size, one each over the two latent ranks, the
    final one; scale and bias of each index key's LayerNorm), the router's
    choosing bias, embedding and untied head over the held vocabulary."""
    (n, full, sparse), d = layer_counts(cfg), cfg["hidden_size"]
    norms = n * (2 * d + cfg["q_lora_rank"] + cfg["kv_lora_rank"]) + d \
        + full * 2 * cfg["index_head_dim"]
    return int(_layer_params(cfg, cfg["n_routed_experts"])
               + sparse * cfg["n_router_experts"] + norms
               + 2 * d * cfg["vocab_size"])


def selected_pairs(cfg: dict, length: int, start: int = 0) -> int:
    """(query, position) pairs the published model attends in one layer over
    positions start..length-1 of a row: ``min(t + 1, index_topk)`` each."""
    k = cfg["index_topk"]

    def upto(n):  # positions 0..n-1
        return n * (n + 1) // 2 if n <= k else k * (k + 1) // 2 + (n - k) * k

    return upto(length) - upto(start)


def _pair_flops(cfg: dict) -> Tuple[float, float]:
    """FLOPs of one attended pair in the plain form (QK^T over the query /
    key head, PV over the value head), and of one indexed pair."""
    return (2.0 * cfg["num_attention_heads"]
            * (cfg["qk_head_dim"] + cfg["v_head_dim"]),
            2.0 * cfg["index_n_heads"] * cfg["index_head_dim"])


def held_expert_share(cfg: dict) -> float:
    """Held experts over the router's width: the share of a token's
    assignments that land here when the load is even."""
    return cfg["n_routed_experts"] / cfg["n_router_experts"]


def forward_flops(cfg: dict, tokens: int, attended: int) -> float:
    """FLOPs of the parameters a token uses **here** (the routed experts a
    token is sent to *among those held*: ``num_experts_per_tok`` x the held
    share, 0.5 of 8 for 16 of 256; the experts on other chips are not run
    here, and those that are only held are not used; the shared expert; the
    indexer in the ``full`` layers; the head over the held vocabulary) plus
    attention **as the published model attends**: the caller hands the dense
    causal pairs of a row of ``tokens`` positions; every one of them is
    indexed in the ``full`` layers, and ``selected_pairs`` of them are
    attended in each layer (handed anything else: at most ``index_topk`` a
    token)."""
    n, full, _ = layer_counts(cfg)
    a_token = _layer_params(
        cfg, cfg["num_experts_per_tok"] * held_expert_share(cfg)) \
        + cfg["hidden_size"] * cfg["vocab_size"]
    if attended == tokens * (tokens + 1) // 2:
        chosen = selected_pairs(cfg, tokens)
    else:
        chosen = min(attended, tokens * cfg["index_topk"])
    pair, index_pair = _pair_flops(cfg)
    return 2.0 * tokens * a_token + n * chosen * pair \
        + full * attended * index_pair


# ------------------------------------------- what the roofline readers ask
def index_score_work(cfg: dict, pairs: float,
                     keys: float) -> Tuple[float, float]:
    size = _itemsize(cfg["activation_dtype"])
    return (pairs * _pair_flops(cfg)[1],
            float(keys * cfg["index_head_dim"] * size + pairs * 4))


def sparse_attention_work(cfg: dict, pairs: float,
                          fetched: float) -> Tuple[float, float]:
    held = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    return (2.0 * pairs * cfg["num_attention_heads"]
            * (held + cfg["kv_lora_rank"]),
            float(fetched * cache_width(cfg)
                  * _itemsize(cfg["activation_dtype"])))


def step_work(cfg: dict, tokens: float, logit_rows: float, selected: float,
              scored: float, fetched: float, keys: float,
              experts_touched: float) -> Tuple[float, float]:
    """The touched *held* experts' weights, every other weight the step uses
    once (attention, indexers, routers, shared experts, the dense layers, the
    head; the embedding is a lookup), ``fetched`` cached vectors and ``keys``
    index keys read once; FLOPs as ``forward_flops`` counts them, the head
    over ``logit_rows`` only."""
    (n, full, sparse), d = layer_counts(cfg), cfg["hidden_size"]
    here = cfg["num_experts_per_tok"] * held_expert_share(cfg)
    pair, index_pair = _pair_flops(cfg)
    f = 2.0 * tokens * _layer_params(cfg, here) \
        + 2.0 * logit_rows * d * cfg["vocab_size"] \
        + selected * pair + scored * index_pair
    weights = _layer_params(cfg, experts_touched) + d * cfg["vocab_size"]
    cache = (fetched * cache_width(cfg) + keys * cfg["index_head_dim"]) \
        * _itemsize(cfg["activation_dtype"])
    return f, float(weights * _itemsize(cfg["param_dtype"]) + cache)
