"""Mixture-of-Experts FFN with expert parallelism (EP).

Net-new versus the reference: SURVEY.md §2.4 lists expert parallelism as
absent there (no MoE anywhere in the snapshot) and marks it a net-new
target for this framework. The design is the GShard/Switch dense-dispatch
formulation, TPU-first:

  - routing, dispatch and combine are einsums over a STATIC capacity —
    no ragged shapes, no host control flow, everything jit-traceable and
    MXU-friendly;
  - expert weights carry a leading expert dim ([E, D, F]); under an
    ``ep`` mesh axis that dim is sharded one-expert-group-per-device and
    the dispatch/combine einsums lower to XLA all-to-alls over ICI
    (param_pspecs places the weights; with_sharding_constraint pins the
    per-expert buffers so GSPMD picks the all-to-all, not an all-gather);
  - top-k gating (k=1 Switch, k=2 GShard) with the standard
    load-balancing auxiliary loss (fraction-dispatched x mean-gate x E).

Capacity: each expert processes at most C = ceil(k * T / E) x
capacity_factor tokens per batch; overflow tokens fall through the
residual connection (their combine weights are zero), the Switch
"token dropping" behavior.

:func:`moe_dropless` is the layer without a capacity (the serve path's:
models/latent_moe.py): every token gets every expert it chose, whatever
else is in the batch.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def init_moe_params(key, n_layers: int, d_model: int, d_ff: int,
                    n_experts: int, param_dtype=jnp.float32):
    """Layer-stacked MoE FFN params: router + per-expert SwiGLU weights
    ([L, E, ...]); drop-in replacement for the dense w1/w3/w2 stack."""
    keys = jax.random.split(key, 4)
    L, D, F, E = n_layers, d_model, d_ff, n_experts

    def dense(k, shape, fan_in):
        return jax.random.normal(k, shape, param_dtype) * (fan_in ** -0.5)

    return {
        "router": dense(keys[0], (L, D, E), D),
        "w1": dense(keys[1], (L, E, D, F), D),
        "w3": dense(keys[2], (L, E, D, F), D),
        "w2": dense(keys[3], (L, E, F, D), F),
    }


def capacity(group_size: int, n_experts: int, top_k: int,
             capacity_factor: float) -> int:
    return max(1, math.ceil(group_size * top_k / n_experts
                            * capacity_factor))


def _group_size(n_tokens: int, target: int) -> int:
    """Largest divisor of ``n_tokens`` that is <= target (GShard's group
    dimension: capacity scales with tokens-per-group, NOT total tokens, so
    the dispatch/combine tensors stay O(T * E * C_group) instead of the
    O(T^2)-ish blowup of one global group)."""
    g = min(n_tokens, max(1, target))
    while n_tokens % g != 0:
        g -= 1
    return g


def moe_ffn(x, layer, cfg, mesh: Optional[Mesh] = None):
    """MoE feed-forward: x [B, S, D] -> ([B, S, D], aux_loss scalar).

    ``layer`` holds this layer's slices: router [D, E], w1/w3 [E, D, F],
    w2 [E, F, D]. Gating/softmax run in fp32; expert matmuls in cfg.dtype
    (bf16 on the MXU). Tokens dispatch in groups of ~expert_group_size
    with per-group capacity (the GShard group dimension).
    """
    B, S, D = x.shape
    E = layer["router"].shape[-1]
    k = cfg.expert_top_k
    T = B * S
    g = _group_size(T, cfg.expert_group_size)
    G = T // g
    C = capacity(g, E, k, cfg.expert_capacity_factor)

    xg = x.reshape(G, g, D)
    logits = jnp.einsum("gtd,de->gte", xg.astype(jnp.float32),
                        layer["router"].astype(jnp.float32))  # [G, g, E]
    probs = jax.nn.softmax(logits, axis=-1)

    # top-k dispatch with per-expert positions (GShard's cumsum trick);
    # experts fill in routing-priority order, one chosen expert at a time
    combine = jnp.zeros((G, g, E, C), jnp.float32)
    dispatch_total = jnp.zeros((G, g, E), jnp.float32)
    fill = jnp.zeros((G, E), jnp.float32)   # per-group expert fill level
    remaining = probs
    for _ in range(k):
        idx = jnp.argmax(remaining, axis=-1)                # [G, g]
        onehot = jax.nn.one_hot(idx, E, dtype=jnp.float32)  # [G, g, E]
        gate = jnp.sum(probs * onehot, axis=-1)             # [G, g]
        pos = (jnp.cumsum(onehot, axis=1) - 1.0) + fill[:, None, :]
        pos = jnp.sum(pos * onehot, axis=-1)                # [G, g]
        keep = (pos < C).astype(jnp.float32) * jnp.sum(onehot, -1)
        pos_oh = jax.nn.one_hot(pos.astype(jnp.int32), C,
                                dtype=jnp.float32)          # [G, g, C]
        combine = combine + (gate * keep)[..., None, None] \
            * onehot[..., None] * pos_oh[..., None, :]
        dispatch_total = dispatch_total + onehot * keep[..., None]
        fill = fill + jnp.sum(onehot * keep[..., None], axis=1)
        remaining = remaining * (1.0 - onehot)              # mask chosen

    # normalize top-k gates so kept weights sum to 1 per token
    denom = jnp.sum(combine, axis=(2, 3), keepdims=True)
    combine = combine / jnp.maximum(denom, 1e-9)
    dispatch = (combine > 0.0).astype(cfg.dtype)            # [G, g, E, C]

    # per-expert buffers; pinned to the ep axis so GSPMD lowers the
    # dispatch/combine einsums to all-to-alls over ICI
    expert_in = jnp.einsum("gtec,gtd->egcd", dispatch,
                           xg.astype(cfg.dtype))            # [E, G, C, D]
    if mesh is not None and "ep" in mesh.shape:
        expert_in = jax.lax.with_sharding_constraint(
            expert_in, NamedSharding(mesh, P("ep", None, None, None)))
    gate_h = jax.nn.silu(jnp.einsum(
        "egcd,edf->egcf", expert_in, layer["w1"].astype(cfg.dtype)))
    up = jnp.einsum("egcd,edf->egcf", expert_in,
                    layer["w3"].astype(cfg.dtype))
    expert_out = jnp.einsum("egcf,efd->egcd", gate_h * up,
                            layer["w2"].astype(cfg.dtype))  # [E, G, C, D]
    if mesh is not None and "ep" in mesh.shape:
        expert_out = jax.lax.with_sharding_constraint(
            expert_out, NamedSharding(mesh, P("ep", None, None, None)))
    out = jnp.einsum("gtec,egcd->gtd", combine.astype(cfg.dtype),
                     expert_out)

    # load-balancing aux loss (Switch eq. 4): E * sum_e f_e * p_e, where
    # f_e = fraction of tokens dispatched to e, p_e = mean router prob
    f = jnp.mean(dispatch_total, axis=(0, 1))
    p = jnp.mean(probs, axis=(0, 1))
    aux = E * jnp.sum(f * p)

    return out.reshape(B, S, D), aux


# ------------------------------------------------------------ without drops
def route_sigmoid_top_k(x, router, bias, top_k: int, scale: float,
                        normalize: bool = True):
    """The router of the DeepSeek-V3 family (``noaux_tc`` with one group):
    scores ``s = sigmoid(x . router)`` in float32; a token takes the
    ``top_k`` experts largest in ``s + bias``; the bias chooses and does
    not weigh: the weights are ``s`` at the chosen experts, over their sum
    where ``normalize``, times ``scale``. x [T, D] -> (experts int32
    [T, k], weights float32 [T, k])."""
    with jax.named_scope("moe_route"):
        s = jax.nn.sigmoid(jnp.dot(
            x.astype(jnp.float32), router.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST))
        _, chosen = jax.lax.top_k(s + bias.astype(jnp.float32), top_k)
        w = jnp.take_along_axis(s, chosen, axis=-1)
        if normalize:
            w = w / jnp.sum(w, axis=-1, keepdims=True)
        return chosen.astype(jnp.int32), w * scale


def moe_dropless(x, layer, top_k: int, scale: float, normalize: bool = True,
                 live=None):
    """Routed SwiGLU experts without a capacity: x [T, D] -> (y [T, D],
    expert_tokens int32 [E]).

    ``layer``: router [D, E], bias [E], w1 / w3 [E, D, F], w2 [E, F, D].
    One algorithm for any T: the T x k (token, expert) assignments are
    sorted by expert, the three matmuls run grouped over the sorted rows
    (``jax.lax.ragged_dot``: on a TPU a grouped-matmul kernel that visits
    only the experts that have rows, so an expert no token chose is not
    read), and each token's k results are weighed and summed where the token
    lies. No row is dropped or reweighed for room, so a token's result does
    not depend on what else is in the batch. A row of ``live`` (bool [T])
    that is False is routed to no expert: it sorts past every group, reads
    0 and is counted nowhere. ``expert_tokens[e]`` is how many live rows
    chose expert ``e``."""
    T, D = x.shape
    E = layer["router"].shape[-1]
    chosen, w = route_sigmoid_top_k(x, layer["router"], layer["bias"],
                                    top_k, scale, normalize)
    with jax.named_scope("moe_experts"):
        if live is not None:
            chosen = jnp.where(live[:, None], chosen, E)  # past every group
            w = jnp.where(live[:, None], w, 0.0)
        flat = chosen.reshape(-1)                         # [T * k]
        order = jnp.argsort(flat, stable=True)
        sizes = jnp.bincount(flat, length=E).astype(jnp.int32)
        xs = x[order // top_k]                            # rows by expert

        def grouped(a, b):
            return jax.lax.ragged_dot(a, b.astype(a.dtype), sizes,
                                      preferred_element_type=jnp.float32)

        h = jax.nn.silu(grouped(xs, layer["w1"])) * grouped(xs, layer["w3"])
        ys = grouped(h.astype(x.dtype), layer["w2"])      # [T * k, D] f32
        back = jnp.argsort(order)                         # where each lies
        y = jnp.sum(ys[back].reshape(T, top_k, D) * w[..., None], axis=1)
    return y.astype(x.dtype), sizes
