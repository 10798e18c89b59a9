"""The plain reference of the stub architecture: float32 ``jax.numpy``,
no cache, no batching, one row at a time; it imports nothing of the program.

A layer: RMSNorm; latent attention (queries through a normed rank-
``q_lora_rank`` bottleneck; keys and values decompressed from one normed
rank-``kv_lora_rank`` vector a position, which with one rotary key shared
by all heads is all a cache would hold); RMSNorm; a SwiGLU MLP in the
leading ``first_k_dense_replace`` layers, and after them
``n_routed_experts`` SwiGLU experts of which a token takes
``num_experts_per_tok``, chosen by sigmoid score plus a selection bias, the
chosen scores normalised and scaled by ``routed_scaling_factor``, beside
``n_shared_experts`` that every token takes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax


def _mm(compute: str):
    if compute == "f32":
        return functools.partial(jnp.matmul, precision=lax.Precision.HIGHEST)
    low = {"fp8": jnp.float8_e4m3fn, "bf16": jnp.bfloat16}[compute]

    def mm(a, b):
        return jnp.matmul(a.astype(low).astype(jnp.bfloat16),
                          b.astype(low).astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    return mm


def _layer_kinds(cfg: dict):
    dense = min(cfg["first_k_dense_replace"], cfg["num_hidden_layers"])
    return dense, cfg["num_hidden_layers"] - dense


def init_params(key, cfg: dict, dtype=None):
    """Weights from ``key`` in the order listed here, normal * fan_in**-0.5
    a leaf; norm scales 1, the router's selection bias 0."""
    pd = jnp.dtype(dtype or cfg["param_dtype"])
    d, h, v = cfg["hidden_size"], cfg["num_attention_heads"], \
        cfg["vocab_size"]
    ql, kl = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    f, fe = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    e, s = cfg["n_routed_experts"], cfg["n_shared_experts"]
    n = cfg["num_hidden_layers"]
    dense, sparse = _layer_kinds(cfg)
    keys = iter(jax.random.split(key, 16))

    def w(shape, fan_in):
        return jax.random.normal(next(keys), shape, pd) * (fan_in ** -0.5)

    return {
        "tok_embed": w((v, d), d), "lm_head": w((d, v), d),
        "final_ln": jnp.ones((d,), pd),
        "attn": {
            "ln": jnp.ones((n, d), pd), "q_ln": jnp.ones((n, ql), pd),
            "kv_ln": jnp.ones((n, kl), pd),
            "q_a": w((n, d, ql), d), "q_b": w((n, ql, h * (nope + rope)), ql),
            "kv_a": w((n, d, kl + rope), d),
            "kv_b": w((n, kl, h * (nope + vd)), kl),
            "o": w((n, h * vd, d), h * vd)},
        "mlp_ln": jnp.ones((n, d), pd),
        "dense": {"w1": w((dense, d, f), d), "w3": w((dense, d, f), d),
                  "w2": w((dense, f, d), f)},
        "moe": {"router": w((sparse, d, e), d),
                "bias": jnp.zeros((sparse, e), pd),
                "w1": w((sparse, e + s, d, fe), d),
                "w3": w((sparse, e + s, d, fe), d),
                "w2": w((sparse, e + s, fe, d), fe)},
    }


def _rms(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                         + eps) * scale


def _rope(x, theta):
    """x [S, H, R]: rotate (first half, second half) pairs by position."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def _attention(x, p, cfg, mm):
    S = x.shape[0]
    h, kl = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    q = mm(_rms(mm(x, p["q_a"]), p["q_ln"], eps), p["q_b"]).reshape(
        S, h, nope + rope)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], theta)], -1)
    kv = mm(x, p["kv_a"])                     # what a cache would hold
    latent = _rms(kv[:, :kl], p["kv_ln"], eps)
    k_rope = _rope(kv[:, None, kl:], theta)   # one rotary key for all heads
    kvb = mm(latent, p["kv_b"]).reshape(S, h, nope + vd)
    k = jnp.concatenate([kvb[..., :nope],
                         jnp.broadcast_to(k_rope, (S, h, rope))], -1)
    s = mm(q.transpose(1, 0, 2), k.transpose(1, 2, 0)) \
        * ((nope + rope) ** -0.5)             # [H, S, S]
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    o = mm(jax.nn.softmax(s, -1), kvb[..., nope:].transpose(1, 0, 2))
    return mm(o.transpose(1, 0, 2).reshape(S, h * vd), p["o"])


def _swiglu(x, w1, w3, w2, mm):
    return mm(jax.nn.silu(mm(x, w1)) * mm(x, w3), w2)


def _experts(x, p, cfg, mm):
    e, k = cfg["n_routed_experts"], cfg["num_experts_per_tok"]
    score = jax.nn.sigmoid(mm(x, p["router"]))             # [S, E]
    _, chosen = lax.top_k(score + p["bias"], k)            # bias: choice only
    took = jnp.take_along_axis(score, chosen, -1)
    weight = took / jnp.sum(took, -1, keepdims=True) \
        * cfg["routed_scaling_factor"]
    gate = jnp.zeros_like(score).at[
        jnp.arange(x.shape[0])[:, None], chosen].set(weight)
    gate = jnp.concatenate(                                # shared: weight 1
        [gate, jnp.ones((x.shape[0], cfg["n_shared_experts"]))], -1)
    out = jax.vmap(lambda w1, w3, w2: _swiglu(x, w1, w3, w2, mm))(
        p["w1"], p["w3"], p["w2"])                         # [E + s, S, D]
    return jnp.einsum("se,esd->sd", gate, out)


def logits(params, tokens, cfg: dict, compute: str = "f32"):
    """tokens [S] -> logits [S, V] (float32)."""
    mm, eps = _mm(compute), cfg["rms_norm_eps"]
    p = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    dense, _ = _layer_kinds(cfg)
    x = p["tok_embed"][tokens]
    for i in range(cfg["num_hidden_layers"]):
        a = jax.tree.map(lambda w: w[i], p["attn"])
        x = x + _attention(_rms(x, a["ln"], eps), a, cfg, mm)
        h = _rms(x, p["mlp_ln"][i], eps)
        if i < dense:
            d = jax.tree.map(lambda w: w[i], p["dense"])
            x = x + _swiglu(h, d["w1"], d["w3"], d["w2"], mm)
        else:
            x = x + _experts(h, jax.tree.map(lambda w: w[i - dense],
                                             p["moe"]), cfg, mm)
    return mm(_rms(x, p["final_ln"], eps), p["lm_head"])
