"""MiniCPM-SALA's decoder (``models/sparse_linear.py``): Lightning linear
attention in the ``lightning-attn`` layers, MiniCPM4's block-sparse attention
over pooled keys in the ``minicpm4`` ones, a SwiGLU MLP in every layer.
``minicpm-sala-d8`` is of it. The contract is in
``chipbench/architectures/__init__.py``.

Beyond the contract, what this architecture's readers ask (each ``(FLOPs,
bytes)`` of the least work, whatever implements it):

``layer_counts(cfg)``
    (layers, sparse layers, lightning layers).
``attended_positions(cfg, length, start)``
    the (query, position) pairs the published model attends in one sparse
    layer over positions start..length-1 of a row.
``row_blocks(cfg, positions)``
    the (block, K/V head) pairs a row of ``positions`` holds over the sparse
    layers.
``block_score_work(cfg, blocks, fetched)``
    the pooled keys of ``blocks`` (query, block, K/V head) triples scored
    for the group's query heads, those of ``fetched`` (block, K/V head)
    pairs read once.
``block_attention_work(cfg, blocks, fetched)``
    the positions of ``blocks`` chosen (query, block, K/V head) triples
    attended by the group's query heads, the keys and values of ``fetched``
    (block, K/V head) pairs read once.
``lightning_update_work(cfg, rows)``
    one lightning layer's state of ``rows`` slots read and written once.
``step_work(cfg, rows, selected, cached)``
    a decode token-step of ``rows`` live rows whose K/V groups attended
    ``selected`` blocks and had ``cached`` blocks to score, each summed over
    the sparse layers and the K/V heads: every weight once, each row's state
    read and written, the pooled keys of the cached blocks and the chosen
    blocks' keys and values read once.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import numpy as np

SPARSE, LIGHTNING = "minicpm4", "lightning-attn"
WIDTHS = ("hidden_size", "intermediate_size", "num_attention_heads",
          "num_key_value_heads", "head_dim", "lightning_nh", "lightning_nkv",
          "lightning_head_dim")
AS_PUBLISHED = WIDTHS + (
    "vocab_size", "rope_theta", "rms_norm_eps", "scale_emb", "scale_depth",
    "mup_denominator", "dim_model_base", "attention_bias", "attn_use_rope",
    "attn_use_output_gate", "hidden_act", "lightning_scale",
    "lightning_use_rope", "model_type", "qk_norm", "rand_init",
    "tie_word_embeddings", "use_output_gate", "use_output_norm")
REQUIRED = AS_PUBLISHED + (
    "num_hidden_layers", "mixer_types", "max_position_embeddings",
    "sparse_config", "residual_depth", "param_dtype", "activation_dtype")
# (key of a configuration file, field of the program's configuration)
_FIELDS = (("vocab_size", "vocab_size"), ("hidden_size", "d_model"),
           ("intermediate_size", "d_ff"), ("mixer_types", "mixer_types"),
           ("num_attention_heads", "n_heads"),
           ("num_key_value_heads", "kv_heads"), ("head_dim", "head_dim"),
           ("lightning_nh", "lin_heads"),
           ("lightning_head_dim", "lin_head_dim"),
           ("scale_emb", "scale_emb"), ("scale_depth", "scale_depth"),
           ("residual_depth", "depth_layers"),
           ("dim_model_base", "dim_model_base"),
           ("lightning_use_rope", "lin_use_rope"),
           ("max_position_embeddings", "max_seq"),
           ("rope_theta", "rope_theta"), ("rms_norm_eps", "rms_norm_eps"))
# (key of ``sparse_config``, field of the program's configuration)
_SPARSE = (("block_size", "block_size"), ("topk", "top_k"),
           ("kernel_size", "kernel_size"), ("kernel_stride", "kernel_stride"),
           ("init_blocks", "init_blocks"), ("window_size", "window_size"),
           ("dense_len", "dense_len"))
# what the program's layers are, and the file must say so
_FORMS = {"attn_use_rope": False, "qk_norm": True, "use_output_gate": True,
          "use_output_norm": True, "attn_use_output_gate": True,
          "tie_word_embeddings": False, "attention_bias": False,
          "hidden_act": "silu", "lightning_scale": "1/sqrt(d)"}


# ------------------------------------------------------------- the program
def program_config(cfg: Dict[str, Any], **over):
    """The program's ``SparseLinearConfig`` of a configuration file."""
    import jax.numpy as jnp

    from ray_memory_management_tpu.models import sparse_linear

    wrong = {k: cfg[k] for k, v in _FORMS.items() if cfg[k] != v}
    if wrong or cfg["lightning_nkv"] != cfg["lightning_nh"]:
        raise ValueError(f"the program's layers are {_FORMS} with a key a "
                         f"lightning head; the file says {wrong}")
    if len(cfg["mixer_types"]) != cfg["num_hidden_layers"]:
        raise ValueError("mixer_types is a layer each")
    fields = {ours: tuple(cfg[k]) if isinstance(cfg[k], list) else cfg[k]
              for k, ours in _FIELDS}
    fields.update({ours: cfg["sparse_config"][k] for k, ours in _SPARSE})
    fields.update(dtype=jnp.dtype(cfg["activation_dtype"]),
                  param_dtype=jnp.dtype(cfg["param_dtype"]))
    fields.update(over)
    return sparse_linear.SparseLinearConfig(**fields)


def init_program_params(key, program_cfg):
    """The weights of the configuration file's recipe, which is the
    reference's (the program's own ``init_params`` makes the same tree)."""
    import jax.numpy as jnp

    c = program_cfg
    cfg = {"hidden_size": c.d_model, "intermediate_size": c.d_ff,
           "vocab_size": c.vocab_size, "num_hidden_layers": c.n_layers,
           "mixer_types": list(c.mixer_types),
           "num_attention_heads": c.n_heads,
           "num_key_value_heads": c.kv_heads, "head_dim": c.head_dim,
           "lightning_nh": c.lin_heads, "lightning_nkv": c.lin_heads,
           "lightning_head_dim": c.lin_head_dim}
    return reference().init_params(key, cfg, jnp.dtype(c.param_dtype))


def program_loss(params, batch, program_cfg, mesh):
    raise NotImplementedError("the program serves this model; no train loss")


def server_class():
    # a program without the model fails here, in the benchmark's own
    # process and before anything is deployed
    from ray_memory_management_tpu.models import sparse_linear  # noqa: F401
    from ray_memory_management_tpu.serve.llm import LLMServer

    return LLMServer


def server_kwargs(cfg: Dict[str, Any]) -> Dict[str, Any]:
    return {"config": program_config(cfg), "init": init_program_params}


# ----------------------------------------------------------- the reference
def reference():
    from chipbench.reference import sparse_linear

    return sparse_linear


# ------------------------------------------------------ counts from shapes
def _itemsize(name: str) -> int:
    return {"bfloat16": 2, "float32": 4, "float16": 2}[name]


def layer_counts(cfg: dict) -> Tuple[int, int, int]:
    """(layers, sparse layers, lightning layers)."""
    kinds = list(cfg["mixer_types"])
    return len(kinds), kinds.count(SPARSE), kinds.count(LIGHTNING)


def _parts(cfg: dict) -> Dict[str, int]:
    """Matmul parameters of each part of a layer: the sparse mixer (q, the
    gate and o at query heads, k and v at K/V heads), the lightning mixer
    (q, k, v, the gate and o), the MLP."""
    d = cfg["hidden_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    lin = cfg["lightning_nh"] * cfg["lightning_head_dim"]
    return {"sparse": 3 * d * q + 2 * d * kv, "lightning": 5 * d * lin,
            "mlp": 3 * d * cfg["intermediate_size"]}


def _layer_params(cfg: dict) -> int:
    (n, sparse, lin), p = layer_counts(cfg), _parts(cfg)
    return sparse * p["sparse"] + lin * p["lightning"] + n * p["mlp"]


def cache_token_bytes(cfg: Dict[str, Any]) -> int:
    """What the pool holds a position: k and v of the sparse layers' K/V
    heads, and a pooled key a ``kernel_stride`` positions."""
    _, sparse, _ = layer_counts(cfg)
    one = sparse * cfg["num_key_value_heads"] * cfg["head_dim"] \
        * _itemsize(cfg["activation_dtype"])
    return 2 * one + one // cfg["sparse_config"]["kernel_stride"]


def state_row_bytes(cfg: Dict[str, Any]) -> int:
    """What a slot holds whatever its length: each lightning layer's state,
    float32."""
    return layer_counts(cfg)[2] * cfg["lightning_nh"] \
        * cfg["lightning_head_dim"] ** 2 * 4


def matmul_params(cfg: dict) -> Tuple[float, int]:
    """(matmul parameters a layer, as the mean over the layers, which
    differ; parameters of the output head)."""
    return _layer_params(cfg) / cfg["num_hidden_layers"], \
        cfg["hidden_size"] * cfg["vocab_size"]


def n_params(cfg: dict) -> int:
    """All parameters: the layers' matmuls, their norms (two over the hidden
    size a layer, q's and k's over a head, a lightning layer's output norm
    over its heads), the final norm, embedding and untied head."""
    (n, sparse, lin), d = layer_counts(cfg), cfg["hidden_size"]
    norms = n * 2 * d + sparse * 2 * cfg["head_dim"] \
        + lin * (2 * cfg["lightning_head_dim"]
                 + cfg["lightning_nh"] * cfg["lightning_head_dim"]) + d
    return _layer_params(cfg) + norms + 2 * d * cfg["vocab_size"]


def attention_shape(cfg: dict) -> Tuple[int, int]:
    return cfg["num_attention_heads"], cfg["head_dim"]


def _positions(cfg: dict, length: int, start: int = 0):
    """The positions start..length-1 of a row, int64."""
    return np.arange(start, length, dtype=np.int64)


def attended_positions(cfg: dict, length: int, start: int = 0) -> int:
    """(query, position) pairs the published model attends in one sparse
    layer over positions start..length-1 of a row: every position up to its
    own before ``dense_len``; after it ``topk`` blocks, the last of them the
    query's own up to the query (or every block while it has no more)."""
    sc, t = cfg["sparse_config"], _positions(cfg, length, start)
    bs = sc["block_size"]
    sparse = (t >= sc["dense_len"]) & (t // bs + 1 > sc["topk"])
    return int(np.where(sparse, (sc["topk"] - 1) * bs + t % bs + 1,
                        t + 1).sum())


def _windows(cfg: dict, length: int, start: int = 0) -> int:
    """(query, pooled key) pairs scored over positions start..length-1."""
    sc, t = cfg["sparse_config"], _positions(cfg, length, start)
    return int(np.maximum(0, (t + 1 - sc["kernel_size"])
                          // sc["kernel_stride"] + 1).sum())


def _lightning_token_flops(cfg: dict) -> float:
    """A token's recurrence in one lightning layer: the state's decay and
    outer product, and the query's product with it."""
    return 4.0 * cfg["lightning_nh"] * cfg["lightning_head_dim"] ** 2


def forward_flops(cfg: dict, tokens: int, attended: int) -> float:
    """FLOPs of the parameters a token uses, two a multiply-add, the head
    over the vocabulary, each lightning layer's recurrence, and attention
    **as the published model attends**: handed the dense causal pairs of a
    row of ``tokens`` positions, :func:`attended_positions` of them in each
    sparse layer and every complete window scored; handed anything else, at
    most ``topk`` blocks a token."""
    (_, sparse, lin), sc = layer_counts(cfg), cfg["sparse_config"]
    if attended == tokens * (tokens + 1) // 2:
        pairs, windows = attended_positions(cfg, tokens), \
            _windows(cfg, tokens)
    else:
        pairs = min(attended, tokens * sc["topk"] * sc["block_size"])
        windows = attended // sc["kernel_stride"]
    pair = 4.0 * cfg["num_attention_heads"] * cfg["head_dim"]
    window = 2.0 * cfg["num_attention_heads"] * cfg["head_dim"]
    return 2.0 * tokens * (_layer_params(cfg)
                           + cfg["hidden_size"] * cfg["vocab_size"]) \
        + sparse * (pairs * pair + windows * window) \
        + lin * tokens * _lightning_token_flops(cfg)


# ------------------------------------------- what the roofline readers ask
def _rep(cfg: dict) -> int:
    return cfg["num_attention_heads"] // cfg["num_key_value_heads"]


def row_blocks(cfg: dict, positions: float) -> float:
    return positions / cfg["sparse_config"]["block_size"] \
        * cfg["num_key_value_heads"] * layer_counts(cfg)[1]


def block_score_work(cfg: dict, blocks: float,
                     fetched: float) -> Tuple[float, float]:
    sc = cfg["sparse_config"]
    per = sc["block_size"] / sc["kernel_stride"]     # pooled keys a block
    return (2.0 * blocks * per * _rep(cfg) * cfg["head_dim"],
            float(fetched * per * cfg["head_dim"]
                  * _itemsize(cfg["activation_dtype"])))


def block_attention_work(cfg: dict, blocks: float,
                         fetched: float) -> Tuple[float, float]:
    bs = cfg["sparse_config"]["block_size"]
    return (4.0 * blocks * bs * _rep(cfg) * cfg["head_dim"],
            float(2 * fetched * bs * cfg["head_dim"]
                  * _itemsize(cfg["activation_dtype"])))


def lightning_update_work(cfg: dict, rows: float) -> Tuple[float, float]:
    one = cfg["lightning_nh"] * cfg["lightning_head_dim"] ** 2
    return 4.0 * rows * one, float(2 * rows * one * 4)


def step_work(cfg: dict, rows: float, selected: float,
              cached: float) -> Tuple[float, float]:
    """A decode token-step's least work: see the module's docstring."""
    _, _, lin = layer_counts(cfg)
    d = cfg["hidden_size"]
    weights = _layer_params(cfg) + d * cfg["vocab_size"]
    f_score, b_score = block_score_work(cfg, cached, cached)
    f_attend, b_attend = block_attention_work(cfg, selected, selected)
    f_state, b_state = lightning_update_work(cfg, rows)
    f = 2.0 * rows * weights + f_score + f_attend + lin * f_state
    return f, float(weights * _itemsize(cfg["param_dtype"]) + b_score
                    + b_attend + lin * b_state)
