"""LatentMoELM: a decoder with latent attention (MLA) and routed experts, the
DeepSeek-V3 / GLM-4.7-Flash family's layer, on the serve path.

A layer, with RMSNorm before each half and a residual around it:

  - *latent attention*: queries through a normed rank-``q_lora_rank``
    bottleneck, each head split into a part without position
    (``qk_nope_head_dim``) and a rotary part (``qk_rope_head_dim``); keys and
    values from one normed rank-``kv_lora_rank`` vector a token (``c_kv``)
    through ``kv_b``, and one rotary key a token shared by all heads. **What
    a token leaves in the cache is ``c_kv`` after its norm and the rotary key
    after RoPE**, side by side in one vector (``cfg.cache_width`` wide, the
    rest zero padding up to whole lanes), not K and V.
  - prefill reads it *plain*: K and V are expanded from ``c_kv`` and go
    through the flash forward kernel (ops/flash_attention.py);
  - decode reads it *absorbed*: ``kv_b``'s key half is folded into the query
    and its value half applied after the attention, so the cached vector is
    key and value of every head and ops/paged_attention.py's latent kernel
    fetches each page once. ``kv_b`` is held once; both products are taken
    from it inside the step;
  - a SwiGLU MLP in the first ``first_k_dense`` layers; after them
    ``n_routed_experts`` SwiGLU experts of which a token takes
    ``experts_per_tok`` with no capacity (ops/moe.py::moe_dropless), beside a
    shared expert every token takes.

Parameters are one dict a layer (no stacking: an expert layer's weights go
to the grouped matmul as they lie, and a slice of a stack would be copied
for it), and the layer loop is unrolled.

The serve engine (serve/llm.py) asks a configuration's model for four
things, which models/gpt.py offers too: ``init_params``, ``cache_spec``,
``prefill_row`` and ``paged_decode``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..ops import moe
from ..ops.flash_attention import flash_attention, reference_attention
from ..ops.paged_attention import latent_attention

_LANES = 128


@dataclasses.dataclass(frozen=True)
class LatentMoEConfig:
    vocab_size: int
    d_model: int
    n_layers: int
    n_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    d_ff: int                      # the leading dense layers' SwiGLU width
    moe_d_ff: int                  # one expert's (and the shared one's)
    n_routed_experts: int
    n_shared_experts: int
    experts_per_tok: int
    routed_scaling_factor: float
    norm_topk_prob: bool = True
    first_k_dense: int = 1
    max_seq: int = 2048
    rope_theta: float = 10_000.0
    rms_norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16      # activations and the cache
    param_dtype: Any = jnp.bfloat16

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_width(self) -> int:
        """Values a token leaves in the cache, a layer."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def cache_width(self) -> int:
        """The cached vector as the pool holds it: padded to whole lanes."""
        return -(-self.latent_width // _LANES) * _LANES


# ------------------------------------------------------------------ weights
def init_params(key, cfg: LatentMoEConfig) -> Dict[str, Any]:
    """One dict a layer: ``split(key, 2 + n_layers)`` gives the embedding's
    key, the head's, then one a layer; a matrix is normal * fan_in**-0.5,
    norm scales 1, the router's choosing bias 0 (training sets it). Weights
    made elsewhere with this tree go to ``LLMServer(init=...)``."""
    pd = cfg.param_dtype
    D, H, E = cfg.d_model, cfg.n_heads, cfg.n_routed_experts
    ql, kl, Fe = cfg.q_lora_rank, cfg.kv_lora_rank, cfg.moe_d_ff
    Fs = cfg.n_shared_experts * Fe
    keys = jax.random.split(key, 2 + cfg.n_layers)

    def w(k, shape, fan_in):
        return jax.random.normal(k, shape, pd) * (fan_in ** -0.5)

    def layer(k, i):
        k = jax.random.split(k, 16)
        out = {
            "ln": jnp.ones((D,), pd), "q_ln": jnp.ones((ql,), pd),
            "kv_ln": jnp.ones((kl,), pd), "mlp_ln": jnp.ones((D,), pd),
            "q_a": w(k[0], (D, ql), D),
            "q_b": w(k[1], (ql, H * cfg.qk_head_dim), ql),
            "kv_a": w(k[2], (D, cfg.latent_width), D),
            "kv_b": w(k[3], (kl, H * (cfg.qk_nope_head_dim
                                      + cfg.v_head_dim)), kl),
            "o": w(k[4], (H * cfg.v_head_dim, D), H * cfg.v_head_dim),
        }
        if i < cfg.first_k_dense:
            out["mlp"] = {"w1": w(k[5], (D, cfg.d_ff), D),
                          "w3": w(k[6], (D, cfg.d_ff), D),
                          "w2": w(k[7], (cfg.d_ff, D), cfg.d_ff)}
        else:
            out["moe"] = {"router": w(k[5], (D, E), D),
                          "bias": jnp.zeros((E,), pd),
                          "w1": w(k[7], (E, D, Fe), D),
                          "w3": w(k[8], (E, D, Fe), D),
                          "w2": w(k[9], (E, Fe, D), Fe)}
            out["shared"] = {"w1": w(k[10], (D, Fs), D),
                             "w3": w(k[11], (D, Fs), D),
                             "w2": w(k[12], (Fs, D), Fs)}
        return out

    return {"tok_embed": w(keys[0], (cfg.vocab_size, D), D),
            "lm_head": w(keys[1], (D, cfg.vocab_size), D),
            "final_ln": jnp.ones((D,), pd),
            "layers": [layer(keys[2 + i], i) for i in range(cfg.n_layers)]}


# ------------------------------------------------------------------- pieces
def _rmsnorm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * lax.rsqrt(var + eps)
            * scale.astype(jnp.float32)).astype(x.dtype)


def _rope(x, positions, theta: float):
    """Rotary positions over x [T, ..., R], one position a leading row;
    (first half, second half) pairs, as models/gpt.py pairs them."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * freqs       # [T, half]
    ang = ang.reshape(ang.shape[:1] + (1,) * (x.ndim - 2) + ang.shape[1:])
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x32 = x.astype(jnp.float32)
    a, b = x32[..., :half], x32[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos],
                           -1).astype(x.dtype)


def _mm(x, w, cfg):
    return jnp.dot(x, w.astype(cfg.dtype),
                   preferred_element_type=jnp.float32).astype(cfg.dtype)


def _swiglu(x, p, cfg):
    return _mm(jax.nn.silu(_mm(x, p["w1"], cfg)) * _mm(x, p["w3"], cfg),
               p["w2"], cfg)


def _queries_and_latent(h, p, positions, cfg: LatentMoEConfig):
    """h [T, D] at ``positions`` [T] -> (q_nope [T, H, nope], q_rope
    [T, H, rope] after RoPE, c_kv [T, kl] after its norm, k_rope [T, rope]
    after RoPE): the last two are what the cache holds."""
    T, H, kl = h.shape[0], cfg.n_heads, cfg.kv_lora_rank
    eps = cfg.rms_norm_eps
    q = _mm(_rmsnorm(_mm(h, p["q_a"], cfg), p["q_ln"], eps), p["q_b"],
            cfg).reshape(T, H, cfg.qk_head_dim)
    q_nope, q_rope = jnp.split(q, [cfg.qk_nope_head_dim], axis=-1)
    kv = _mm(h, p["kv_a"], cfg)
    c_kv = _rmsnorm(kv[:, :kl], p["kv_ln"], eps)
    return (q_nope, _rope(q_rope, positions, cfg.rope_theta), c_kv,
            _rope(kv[:, kl:], positions, cfg.rope_theta))


def _cached(c_kv, k_rope, cfg: LatentMoEConfig):
    """[T, cache_width]: the vector as the pool holds it."""
    pad = cfg.cache_width - cfg.latent_width
    return jnp.pad(jnp.concatenate([c_kv, k_rope], -1), ((0, 0), (0, pad)))


def _kv_b_halves(p, cfg: LatentMoEConfig):
    """``kv_b`` as [kl, H, nope] (to keys) and [kl, H, vd] (to values)."""
    w = p["kv_b"].astype(cfg.dtype).reshape(
        cfg.kv_lora_rank, cfg.n_heads, cfg.qk_nope_head_dim + cfg.v_head_dim)
    return w[..., :cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim:]


def _attend_plain(h, p, positions, cfg: LatentMoEConfig):
    """Causal latent attention of one row in its plain form: K and V
    expanded from c_kv. h [S, D] -> (o [S, H * vd], cached [S, cache_width])."""
    S, H = h.shape[0], cfg.n_heads
    q_nope, q_rope, c_kv, k_rope = _queries_and_latent(h, p, positions, cfg)
    to_k, to_v = _kv_b_halves(p, cfg)
    k_nope = jnp.einsum("sl,lhn->shn", c_kv, to_k)
    v = jnp.einsum("sl,lhv->shv", c_kv, to_v)
    q = jnp.concatenate([q_nope, q_rope], -1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, None], (S, H) + k_rope.shape[1:])],
        -1)
    q, k, v = (a.transpose(1, 0, 2)[None] for a in (q, k, v))   # [1, H, S, *]
    scale = cfg.qk_head_dim ** -0.5
    # the flash kernel takes one head size for q, k and v
    attend = flash_attention if cfg.qk_head_dim == cfg.v_head_dim \
        else reference_attention
    o = attend(q, k, v, causal=True, scale=scale)
    with jax.named_scope("latent_kv_write"):
        cached = _cached(c_kv, k_rope, cfg)
    return o[0].transpose(1, 0, 2).reshape(S, H * cfg.v_head_dim), cached


def _attend_absorbed(h, p, positions, attend, cfg: LatentMoEConfig):
    """One token a row in absorbed form: ``attend(q [B, H, cache_width],
    cur [B, cache_width]) -> [B, H, kl]`` reads the cache. h [B, D] ->
    (o [B, H * vd], cur)."""
    B, H = h.shape[0], cfg.n_heads
    q_nope, q_rope, c_kv, k_rope = _queries_and_latent(h, p, positions, cfg)
    to_k, to_v = _kv_b_halves(p, cfg)
    q_lat = jnp.einsum("bhn,lhn->bhl", q_nope, to_k)
    pad = cfg.cache_width - cfg.latent_width
    q = jnp.pad(jnp.concatenate([q_lat, q_rope], -1),
                ((0, 0), (0, 0), (0, pad)))
    cur = _cached(c_kv, k_rope, cfg)
    o = jnp.einsum("bhl,lhv->bhv", attend(q, cur), to_v)
    return o.reshape(B, H * cfg.v_head_dim), cur


def _ffn(x, layer, cfg: LatentMoEConfig, live=None):
    """The layer's second half on h = norm(x): [T, D] -> (y, expert_tokens
    or None)."""
    h = _rmsnorm(x, layer["mlp_ln"], cfg.rms_norm_eps)
    if "mlp" in layer:
        return _swiglu(h, layer["mlp"], cfg), None
    y, counts = moe.moe_dropless(
        h, layer["moe"], cfg.experts_per_tok, cfg.routed_scaling_factor,
        cfg.norm_topk_prob, live)
    with jax.named_scope("moe_shared"):
        y = y + _swiglu(h, layer["shared"], cfg)
    return y, counts


def _head(x, params, cfg: LatentMoEConfig):
    x = _rmsnorm(x, params["final_ln"], cfg.rms_norm_eps)
    return jnp.dot(x, params["lm_head"].astype(cfg.dtype),
                   preferred_element_type=jnp.float32)


# ------------------------------------------------------------- whole forward
def _forward_row(params, tokens, cfg: LatentMoEConfig):
    """tokens [S] -> (hidden [S, D] before the final norm, cached
    [L, S, cache_width])."""
    positions = jnp.arange(tokens.shape[0])
    x = params["tok_embed"][tokens].astype(cfg.dtype)
    cached = []
    for layer in params["layers"]:
        h = _rmsnorm(x, layer["ln"], cfg.rms_norm_eps)
        o, c = _attend_plain(h, layer, positions, cfg)
        cached.append(c)
        x = x + _mm(o, layer["o"], cfg)
        x = x + _ffn(x, layer, cfg)[0]
    return x, jnp.stack(cached)


def forward(params, tokens, cfg: LatentMoEConfig):
    """tokens [B, S] -> logits [B, S, V] (fp32), without a cache; a row at
    a time (the grouped matmul takes no batch dimension)."""
    return lax.map(lambda t: _head(_forward_row(params, t, cfg)[0], params,
                                   cfg), tokens)


# --------------------------------------------------- what the engine asks for
def cache_spec(cfg: LatentMoEConfig) -> Dict[str, Tuple]:
    """What a token leaves in the cache, as the page pool lays it out: name
    -> (dims before the pages, dims after a page's positions, dtype). One
    array: ``latent`` [L, pages, page_tokens, cache_width]."""
    return {"latent": ((cfg.n_layers,), (cfg.cache_width,), cfg.dtype)}


def prefill_row(params, tokens, cfg: LatentMoEConfig, n_positions: int,
                true_len):
    """Prefill one row: tokens [1, S], of which the first ``true_len`` are
    the prompt -> (logits [V] fp32 at the prompt's last token, the row's
    cache {"latent": [L, n_positions, cache_width]}, zero past S)."""
    x, cached = _forward_row(params, tokens[0], cfg)
    with jax.named_scope("head_sample"):
        logits = _head(lax.dynamic_index_in_dim(x, true_len - 1, 0, False),
                       params, cfg)
    pad = n_positions - tokens.shape[1]
    return logits, {"latent": jnp.pad(cached, ((0, 0), (0, pad), (0, 0)))}


def paged_decode(params, tokens, pool, positions, lengths, page_table,
                 cfg: LatentMoEConfig):
    """One decode token a row against the pool of latent pages, read and
    written in place (serve/kv_cache.py): ``pool`` is {"latent":
    [L, P, page_tokens, cache_width]} whose last page is the sink. Row
    ``i``'s token sits at ``positions[i]`` and attends over its first
    ``lengths[i]`` cached positions and itself; an idle row has length 0 and
    a table row of sink entries: it reads nothing, writes the sink, and is
    routed to no expert. As in models/gpt.py::paged_decode the layer
    loop only reads the pool and the new vectors of all layers are written
    after it. Returns (logits [B, V] fp32, pool, counts), the counts int32:
    ``expert_tokens`` [E], the live rows' assignments summed over the expert
    layers; ``experts_touched``, the experts with at least one live row's
    token, summed over the expert layers; ``expert_layer_steps``, how many
    expert layers ran with a live row."""
    pages_of = pool["latent"]
    page, width = pages_of.shape[2], page_table.shape[1]
    sink = pages_of.shape[1] - 1
    live = lengths > 0
    scale = cfg.qk_head_dim ** -0.5
    x = params["tok_embed"][tokens].astype(cfg.dtype)            # [B, D]
    new = []
    expert_tokens = jnp.zeros((cfg.n_routed_experts,), jnp.int32)
    touched = jnp.int32(0)
    for i, layer in enumerate(params["layers"]):

        def attend(q, cur, i=i):
            with jax.named_scope("latent_decode_attention"):
                return latent_attention(
                    q, pages_of, lengths, page_table, cur, layer=i,
                    value_width=cfg.kv_lora_rank, scale=scale)

        h = _rmsnorm(x, layer["ln"], cfg.rms_norm_eps)
        o, cur = _attend_absorbed(h, layer, positions, attend, cfg)
        new.append(cur)
        x = x + _mm(o, layer["o"], cfg)
        y, counts = _ffn(x, layer, cfg, live)
        x = x + y
        if counts is not None:
            expert_tokens = expert_tokens + counts
            touched = touched + jnp.sum(counts > 0, dtype=jnp.int32)
    with jax.named_scope("latent_kv_write"):
        at = positions // page
        inside = jnp.minimum(at, width - 1)[:, None]
        pages = jnp.where(
            at < width,
            jnp.take_along_axis(page_table, inside, axis=1)[:, 0], sink)
        offs = positions % page
        # one position a row, every layer of it, by patching the tile of 16
        # positions around it: as in models/gpt.py, a scatter or a one-row
        # update makes the TPU compiler copy the whole pool into a layout of
        # its own and back, every step (tests/test_latent_moe.py holds it)
        fresh = jnp.stack(new, 1)[:, :, None, None, :]   # [B, L, 1, 1, W]
        L, W = fresh.shape[1], fresh.shape[-1]
        tile = 16 if page % 16 == 0 else 1
        rows = jnp.arange(tile)[None, None, :, None]

        def one(b, c):
            base = offs[b] // tile * tile
            where = (0, pages[b], base, 0)
            old = lax.dynamic_slice(c, where, (L, 1, tile, W))
            return lax.dynamic_update_slice(
                c, jnp.where(rows == offs[b] - base, fresh[b], old), where)

        pool = {"latent": lax.fori_loop(0, fresh.shape[0], one, pages_of)}
    with jax.named_scope("head_sample"):  # the engine's sampler joins it
        logits = _head(x, params, cfg)
    n_sparse = cfg.n_layers - min(cfg.first_k_dense, cfg.n_layers)
    steps = jnp.where(jnp.any(live), n_sparse, 0).astype(jnp.int32)
    return logits, pool, {"expert_tokens": expert_tokens,
                          "experts_touched": touched,
                          "expert_layer_steps": steps}


def prefill_takes_kernel(cfg: LatentMoEConfig, n_tokens: int) -> bool:
    """Whether :func:`prefill_row` attends in the flash forward kernel (the
    choice ``_attend_plain`` makes: one head size for q, k and v, and
    ``flash_attention`` itself goes by the platform alone). Whatever
    ``n_tokens``: on a TPU a length the kernel cannot tile is refused, never
    handed to the reference. At the end of the file so that no line of the
    programs above moves."""
    from ..ops.flash_attention import _on_tpu

    return cfg.qk_head_dim == cfg.v_head_dim and _on_tpu()
