"""HybridSSMLM: a decoder whose every layer runs attention heads and a
Mamba-2 state-space mixer side by side on one normed input (the Falcon-H1
family's layer), on the serve path.

A layer, on ``u = RMSNorm(x)``:

  - *attention branch*: grouped-query attention with RoPE over the whole
    head; queries, keys and values from ``u * attention_in_multiplier``, keys
    times ``key_multiplier``, the output projection times
    ``attention_out_multiplier``. **What a token leaves in the pages is K and
    V**, as models/gpt.py's;
  - *state-space branch*: ``u * ssm_in_multiplier`` through one projection
    into ``z | x | B | C | dt`` (each segment times its entry of
    ``ssm_multipliers``), a causal depthwise convolution of ``ssm_conv`` taps
    and a SiLU over ``x | B | C``, ``dt = softplus(dt + dt_bias)``, the
    recurrence of ops/ssm.py, a gate ``y * silu(z)`` under a grouped RMSNorm,
    the output projection times ``ssm_out_multiplier``. **What a slot holds
    besides its pages is one state of fixed size**, whatever its length: the
    recurrence's ``h`` (``ssm_heads x ssm_state x ssm_head_dim`` float32, the
    state dimension first: ops/ssm.py says why) and the convolution's last
    ``ssm_conv - 1`` inputs;
  - ``x <- x + attention + state-space``, then a SwiGLU MLP on its own norm
    (the gate times ``mlp_multipliers[0]``, the result times
    ``mlp_multipliers[1]``).

Embedding rows times ``embedding_multiplier``; logits times
``lm_head_multiplier``. Parameters are one dict a layer and the layer loop is
unrolled (as models/latent_moe.py).

The serve engine (serve/llm.py) asks a configuration's model for
``init_params``, ``cache_spec``, ``prefill_row``, ``prefill_takes_kernel`` and
``paged_decode``, as of the others, and of this one also for ``state_spec``:
the arrays the pool holds a slot and not a position. It offers
``mixed_step``, so its prompts ride the decode step in chunks: a chunk after
a prompt's first starts from the slot's state and convolution tail.
``prefill_row`` stays for whoever prefills a whole prompt (models/nemotron_h.py
shares the mixer's pieces; the benchmark's references and fit tests).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..ops import ssm
from ..ops.flash_attention import (DEFAULT_BLOCK_Q, _on_tpu, _pick_block,
                                   flash_attention)
from ..ops.paged_attention import paged_attention
from .latent_moe import _rmsnorm, _rope


@dataclasses.dataclass(frozen=True)
class HybridSSMConfig:
    vocab_size: int
    d_model: int
    n_layers: int
    n_heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    ssm_heads: int
    ssm_head_dim: int
    ssm_state: int
    ssm_groups: int
    ssm_conv: int = 4
    embedding_multiplier: float = 1.0
    lm_head_multiplier: float = 1.0
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    key_multiplier: float = 1.0
    ssm_in_multiplier: float = 1.0
    ssm_out_multiplier: float = 1.0
    ssm_multipliers: Tuple[float, ...] = (1.0,) * 5   # z, x, B, C, dt
    mlp_multipliers: Tuple[float, float] = (1.0, 1.0)  # gate, down
    max_seq: int = 2048
    rope_theta: float = 10_000.0
    rms_norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16      # activations, K and V, the conv tail
    param_dtype: Any = jnp.bfloat16

    @property
    def ssm_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_width(self) -> int:
        """Channels the convolution runs over: x, B and C."""
        return self.ssm_inner + 2 * self.ssm_groups * self.ssm_state

    @property
    def ssm_proj_width(self) -> int:
        return self.ssm_inner + self.conv_width + self.ssm_heads


# ------------------------------------------------------------------ weights
def init_params(key, cfg: HybridSSMConfig) -> Dict[str, Any]:
    """One dict a layer: ``split(key, 2 + n_layers)`` gives the embedding's
    key, the head's, then one a layer, split in 16; a matrix is normal *
    fan_in**-0.5, norm scales 1, the convolution's bias 0. ``A_log``,
    ``dt_bias`` and ``D`` are float32 whatever ``param_dtype``, by Mamba-2's
    convention: ``A`` uniform in 1-16, ``dt`` log-uniform in 0.001-0.1 (the
    bias its inverse softplus), ``D`` 1. Weights made elsewhere with this
    tree go to ``LLMServer(init=...)``."""
    pd = cfg.param_dtype
    D, H, Hkv, Dh = cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.head_dim
    keys = jax.random.split(key, 2 + cfg.n_layers)

    def w(k, shape, fan_in):
        return jax.random.normal(k, shape, pd) * (fan_in ** -0.5)

    def layer(k):
        k = jax.random.split(k, 16)
        dt = jnp.exp(jax.random.uniform(
            k[7], (cfg.ssm_heads,), jnp.float32, jnp.log(1e-3),
            jnp.log(1e-1)))
        return {
            "ln": jnp.ones((D,), pd), "mlp_ln": jnp.ones((D,), pd),
            "wq": w(k[0], (D, H * Dh), D), "wk": w(k[1], (D, Hkv * Dh), D),
            "wv": w(k[2], (D, Hkv * Dh), D), "wo": w(k[3], (H * Dh, D),
                                                      H * Dh),
            "ssm_in": w(k[4], (D, cfg.ssm_proj_width), D),
            "conv_w": w(k[5], (cfg.ssm_conv, cfg.conv_width), cfg.ssm_conv),
            "conv_b": jnp.zeros((cfg.conv_width,), pd),
            "A_log": jnp.log(jax.random.uniform(
                k[6], (cfg.ssm_heads,), jnp.float32, 1.0, 16.0)),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "D": jnp.ones((cfg.ssm_heads,), jnp.float32),
            "ssm_norm": jnp.ones((cfg.ssm_inner,), pd),
            "ssm_out": w(k[8], (cfg.ssm_inner, D), cfg.ssm_inner),
            "w_gate": w(k[9], (D, cfg.d_ff), D),
            "w_up": w(k[10], (D, cfg.d_ff), D),
            "w_down": w(k[11], (cfg.d_ff, D), cfg.d_ff),
        }

    return {"tok_embed": w(keys[0], (cfg.vocab_size, D), D),
            "lm_head": w(keys[1], (D, cfg.vocab_size), D),
            "final_ln": jnp.ones((D,), pd),
            "layers": [layer(keys[2 + i]) for i in range(cfg.n_layers)]}


# ------------------------------------------------------------------- pieces
def _mm32(x, w, cfg):
    return jnp.dot(x, w.astype(cfg.dtype), preferred_element_type=jnp.float32)


def _mm(x, w, cfg):
    return _mm32(x, w, cfg).astype(cfg.dtype)


def _scaled(x, by: float):
    return x if by == 1.0 else x * jnp.asarray(by, x.dtype)


def _qkv(u, p, positions, cfg: HybridSSMConfig):
    """u [T, D] at ``positions`` [T] -> q [T, H, Dh], k and v [T, Hkv, Dh],
    q and k after RoPE: k and v are what the pages hold."""
    T = u.shape[0]
    u = _scaled(u, cfg.attention_in_multiplier)
    q = _mm(u, p["wq"], cfg).reshape(T, cfg.n_heads, cfg.head_dim)
    k = _scaled(_mm32(u, p["wk"], cfg), cfg.key_multiplier).astype(
        cfg.dtype).reshape(T, cfg.kv_heads, cfg.head_dim)
    v = _mm(u, p["wv"], cfg).reshape(T, cfg.kv_heads, cfg.head_dim)
    theta = float(cfg.rope_theta)
    return _rope(q, positions, theta), _rope(k, positions, theta), v


def _attn_out(o, p, cfg: HybridSSMConfig):
    return _scaled(_mm32(o.reshape(o.shape[0], -1), p["wo"], cfg),
                   cfg.attention_out_multiplier).astype(cfg.dtype)


def _ssm_project(u, p, cfg: HybridSSMConfig):
    """u [T, D] -> (z [T, inner], xBC [T, conv_width] before the
    convolution, dt [T, heads] float32 after its softplus)."""
    with jax.named_scope("ssm_in_proj"):
        by = jnp.concatenate([
            jnp.full((n,), m, jnp.float32) for n, m in zip(
                (cfg.ssm_inner, cfg.ssm_inner,
                 cfg.ssm_groups * cfg.ssm_state,
                 cfg.ssm_groups * cfg.ssm_state, cfg.ssm_heads),
                cfg.ssm_multipliers)])
        proj = _mm32(_scaled(u, cfg.ssm_in_multiplier), p["ssm_in"],
                     cfg) * by
        z, xbc, dt = jnp.split(
            proj, [cfg.ssm_inner, cfg.ssm_inner + cfg.conv_width], axis=-1)
        return (z.astype(cfg.dtype), xbc.astype(cfg.dtype),
                jax.nn.softplus(dt + p["dt_bias"]))


def _conv(taps, p, cfg: HybridSSMConfig):
    """taps: ``ssm_conv`` arrays [..., conv_width], a position's input last
    and the ``ssm_conv - 1`` before it in order -> silu(conv)."""
    w = p["conv_w"].astype(jnp.float32)
    y = sum(t.astype(jnp.float32) * w[i] for i, t in enumerate(taps)) \
        + p["conv_b"].astype(jnp.float32)
    return jax.nn.silu(y).astype(cfg.dtype)


def _split_xbc(xbc, cfg: HybridSSMConfig):
    """[T, conv_width] -> x [T, heads, head_dim], B and C [T, groups,
    state]."""
    T, gn = xbc.shape[0], cfg.ssm_groups * cfg.ssm_state
    x, b, c = jnp.split(xbc, [cfg.ssm_inner, cfg.ssm_inner + gn], axis=-1)
    return (x.reshape(T, cfg.ssm_heads, cfg.ssm_head_dim),
            b.reshape(T, cfg.ssm_groups, cfg.ssm_state),
            c.reshape(T, cfg.ssm_groups, cfg.ssm_state))


def _gate_out(y, z, p, cfg: HybridSSMConfig):
    """y [T, heads, head_dim] float32, z [T, inner] -> [T, D]: the gate,
    then an RMSNorm over each group's channels, then the projection."""
    with jax.named_scope("ssm_gate_out"):
        T = y.shape[0]
        g = y.reshape(T, cfg.ssm_inner) * jax.nn.silu(z.astype(jnp.float32))
        g = g.reshape(T, cfg.ssm_groups, -1)
        g = g * lax.rsqrt(jnp.mean(jnp.square(g), -1, keepdims=True)
                          + cfg.rms_norm_eps)
        g = (g.reshape(T, cfg.ssm_inner)
             * p["ssm_norm"].astype(jnp.float32)).astype(cfg.dtype)
        return _scaled(_mm32(g, p["ssm_out"], cfg),
                       cfg.ssm_out_multiplier).astype(cfg.dtype)


def _mlp(x, p, cfg: HybridSSMConfig):
    v = _rmsnorm(x, p["mlp_ln"], cfg.rms_norm_eps)
    gate = jax.nn.silu(_scaled(_mm32(v, p["w_gate"], cfg),
                               cfg.mlp_multipliers[0]))
    h = (_mm32(v, p["w_up"], cfg) * gate).astype(cfg.dtype)
    return _scaled(_mm32(h, p["w_down"], cfg),
                   cfg.mlp_multipliers[1]).astype(cfg.dtype)


def _embed(params, tokens, cfg: HybridSSMConfig):
    return _scaled(params["tok_embed"][tokens].astype(jnp.float32),
                   cfg.embedding_multiplier).astype(cfg.dtype)


def _head(x, params, cfg: HybridSSMConfig):
    x = _rmsnorm(x, params["final_ln"], cfg.rms_norm_eps)
    return _mm32(x, params["lm_head"], cfg) * cfg.lm_head_multiplier


# ------------------------------------------------------------- whole forward
def _forward_row(params, tokens, cfg: HybridSSMConfig, true_len, use: str):
    """tokens [S], of which the first ``true_len`` are real -> (hidden
    [S, D] before the final norm, K and V [L, Hkv, S, Dh], the recurrence's
    state [L, heads, state, head_dim] float32 and the convolution's tail
    [L, ssm_conv - 1, conv_width], both as of position ``true_len - 1``)."""
    S, rep, tail = tokens.shape[0], cfg.n_heads // cfg.kv_heads, \
        cfg.ssm_conv - 1
    positions = jnp.arange(S)
    x = _embed(params, tokens, cfg)
    ks, vs, hs, tails = [], [], [], []
    for p in params["layers"]:
        u = _rmsnorm(x, p["ln"], cfg.rms_norm_eps)
        q, k, v = _qkv(u, p, positions, cfg)
        kt, vt = k.transpose(1, 0, 2), v.transpose(1, 0, 2)    # [Hkv, S, Dh]
        with jax.named_scope("prefill_attention"):
            # the kernel wants as many K/V heads as query heads
            o = flash_attention(
                q.transpose(1, 0, 2)[None], jnp.repeat(kt, rep, axis=0)[None],
                jnp.repeat(vt, rep, axis=0)[None], causal=True,
                use_pallas=use)[0].transpose(1, 0, 2)
        a = _attn_out(o, p, cfg)
        z, xbc, dt = _ssm_project(u, p, cfg)
        with jax.named_scope("ssm_conv"):
            behind = jnp.pad(xbc, ((tail, 0), (0, 0)))         # zeros before 0
            xs, b, c = _split_xbc(_conv(
                [behind[i:i + S] for i in range(cfg.ssm_conv)], p, cfg), cfg)
        with jax.named_scope("ssm_scan"):
            y, h = ssm.ssd_scan(xs, dt, -jnp.exp(p["A_log"]), b, c, p["D"],
                                true_len=true_len)
        with jax.named_scope("state_write"):
            # the last real inputs: rows true_len - tail .. true_len - 1
            tails.append(lax.dynamic_slice_in_dim(behind, true_len, tail))
        ks.append(kt), vs.append(vt), hs.append(h)
        x = x + a + _gate_out(y, z, p, cfg)
        x = x + _mlp(x, p, cfg)
    return x, jnp.stack(ks), jnp.stack(vs), jnp.stack(hs), jnp.stack(tails)


def forward(params, tokens, cfg: HybridSSMConfig):
    """tokens [B, S] -> logits [B, S, V] (fp32), without a cache; a row at
    a time."""
    return lax.map(lambda t: _head(_forward_row(
        params, t, cfg, t.shape[0], _kernel_use(cfg, t.shape[0]))[0], params,
        cfg), tokens)


# --------------------------------------------------- what the engine asks for
def cache_spec(cfg: HybridSSMConfig) -> Dict[str, Tuple]:
    """What a token leaves in the cache, as the page pool lays it out: name
    -> (dims before the pages, dims after a page's positions, dtype). K and
    V, each [L, Hkv, pages, page_tokens, Dh]."""
    one = ((cfg.n_layers, cfg.kv_heads), (cfg.head_dim,), cfg.dtype)
    return {"k": one, "v": one}


def state_spec(cfg: HybridSSMConfig) -> Dict[str, Tuple]:
    """What a slot holds whatever its length: name -> (dims before the
    slots, dims after, dtype). ``ssm``: the recurrence's state, float32
    [L, slots, heads, state, head_dim]; ``conv``: the convolution's last
    inputs, [L, ssm_conv - 1, slots, conv_width] (a tap of all slots lies
    together, as the decode step reads it). A prefill overwrites its
    slot's; the decode step moves a live slot's and leaves an idle slot's."""
    return {"ssm": ((cfg.n_layers,),
                    (cfg.ssm_heads, cfg.ssm_state, cfg.ssm_head_dim),
                    jnp.float32),
            "conv": ((cfg.n_layers, cfg.ssm_conv - 1), (cfg.conv_width,),
                     cfg.dtype)}


def prefill_takes_kernel(cfg: HybridSSMConfig, n_tokens: int) -> bool:
    """Whether :func:`prefill_row` over a bucket of ``n_tokens`` attends in
    the flash forward kernel: on a TPU and for a length it can tile."""
    if not _on_tpu():
        return False
    try:
        _pick_block(n_tokens, DEFAULT_BLOCK_Q, False)
    except ValueError:  # no block of 8 rows divides it: the plain path
        return False
    return True


def _kernel_use(cfg: HybridSSMConfig, n_tokens: int) -> str:
    return "on" if prefill_takes_kernel(cfg, n_tokens) else "off"


def prefill_row(params, tokens, cfg: HybridSSMConfig, n_positions: int,
                true_len):
    """Prefill one row: tokens [1, S], of which the first ``true_len`` are
    the prompt -> (logits [V] fp32 at the prompt's last token, the row's
    cache: {"k", "v"} of [L, Hkv, n_positions, Dh], zero past S, and the
    row's state **as of the prompt's last token** {"ssm", "conv"}: the
    bucket's padding moves neither (ops/ssm.py masks its ``dt``; the tail is
    cut at ``true_len``), where for K and V it is only never read)."""
    S = tokens.shape[1]
    x, k, v, h, tail = _forward_row(params, tokens[0], cfg, true_len,
                                    _kernel_use(cfg, S))
    with jax.named_scope("kv_write"):
        pad = ((0, 0), (0, 0), (0, n_positions - S), (0, 0))
        row = {"k": jnp.pad(k, pad), "v": jnp.pad(v, pad), "ssm": h,
               "conv": tail}
    with jax.named_scope("head_sample"):  # the engine's sampler joins it
        logits = _head(lax.dynamic_index_in_dim(x, true_len - 1, 0, False),
                       params, cfg)
    return logits, row


def _write_kv(pages_of, new, pages, offs):
    """One position a row, every layer and head of it (``new``
    [B, L, Hkv, Dh]), by reading the tile of 16 positions around it,
    patching and writing it back: for a scatter or an update of the one
    position the TPU compiler picks a layout of its own for the whole pool
    and copies the pool into it and back, every step (models/gpt.py)."""
    page = pages_of.shape[3]
    new = new[:, :, :, None, None, :]
    L, Hkv, Dh = new.shape[1], new.shape[2], new.shape[-1]
    tile = 16 if page % 16 == 0 else 1
    rows = jnp.arange(tile)[None, None, None, :, None]

    def one(b, c):
        base = offs[b] // tile * tile
        at = (0, 0, pages[b], base, 0)
        old = lax.dynamic_slice(c, at, (L, Hkv, 1, tile, Dh))
        return lax.dynamic_update_slice(
            c, jnp.where(rows == offs[b] - base, new[b], old), at)

    return lax.fori_loop(0, new.shape[0], one, pages_of)


def _write_rows(pool, k_new, v_new, positions, page_table):
    """One decode position a row into the pages: ``k_new`` / ``v_new``
    [B, L, Hkv, Dh] go to ``(page_table[i, positions[i] // page_tokens],
    positions[i] % page_tokens)``, or to the sink where that lies beyond the
    table. What :func:`paged_decode` and :func:`mixed_step` do after their
    layer loops."""
    page, width = pool["k"].shape[3], page_table.shape[1]
    sink = pool["k"].shape[2] - 1
    at = positions // page
    inside = jnp.minimum(at, width - 1)[:, None]
    pages = jnp.where(
        at < width,
        jnp.take_along_axis(page_table, inside, axis=1)[:, 0], sink)
    offs = positions % page
    return {"k": _write_kv(pool["k"], k_new, pages, offs),
            "v": _write_kv(pool["v"], v_new, pages, offs)}


def paged_decode(params, tokens, pool, positions, lengths, page_table,
                 cfg: HybridSSMConfig):
    """One decode token a row (row ``i`` is slot ``i``) against the pool, read
    and written in place (serve/kv_cache.py): ``pool`` holds {"k", "v"} of
    [L, Hkv, P, page_tokens, Dh] whose last page is the sink, and the slots'
    state {"ssm", "conv"} (:func:`state_spec`). Row ``i``'s token sits at
    ``positions[i]`` and attends over its first ``lengths[i]`` cached
    positions and itself; an idle row has length 0: it reads no page,
    writes the sink, **and its state is neither fetched nor moved**. The
    layer loop reads the pages (the new K and V of all layers are written
    after it, models/gpt.py) and updates the recurrence's state a layer at a
    time where it lies (ops/ssm.py). Returns (logits [B, V] fp32, pool,
    counts), the counts int32: ``state_rows_stepped``, the live rows summed
    over the layers; ``state_rows_fetched``, the rows whose state the update
    read (the same where idle slots are skipped); ``ssm_layer_steps``, the
    layers that ran with a live row."""
    live = lengths > 0
    x = _embed(params, tokens, cfg)                              # [B, D]
    state, k_new, v_new, tails = pool["ssm"], [], [], []
    fetched = jnp.int32(0)
    for i, p in enumerate(params["layers"]):
        u = _rmsnorm(x, p["ln"], cfg.rms_norm_eps)
        q, k, v = _qkv(u, p, positions, cfg)
        with jax.named_scope("decode_attention"):
            o = paged_attention(q, pool["k"], pool["v"], lengths, page_table,
                                layer=i, k_cur=k, v_cur=v)
        a = _attn_out(o, p, cfg)
        z, xbc, dt = _ssm_project(u, p, cfg)
        with jax.named_scope("ssm_conv"):
            old = pool["conv"][i]                       # [taps - 1, B, C]
            xs, b, c = _split_xbc(_conv([*old, xbc], p, cfg), cfg)
            tails.append(jnp.where(
                live[None, :, None],
                jnp.concatenate([old[1:], xbc[None]], axis=0), old))
        with jax.named_scope("ssm_decode_update"):
            y, state, n = ssm.ssm_decode_update(
                state, xs, dt, -jnp.exp(p["A_log"]), b, c, p["D"], live,
                layer=i)
        fetched = fetched + n
        k_new.append(k), v_new.append(v)
        x = x + a + _gate_out(y, z, p, cfg)
        x = x + _mlp(x, p, cfg)
    with jax.named_scope("kv_write"):
        # the pages are written only once every layer has read them: without
        # the barrier nothing orders the last layer's attention before the
        # write, and the compiler copies both pools to be safe, every step
        x, k_new, v_new = lax.optimization_barrier(
            (x, jnp.stack(k_new, 1), jnp.stack(v_new, 1)))
        pool = _write_rows(pool, k_new, v_new, positions, page_table)
    with jax.named_scope("state_write"):
        pool.update(ssm=state, conv=jnp.stack(tails))
    with jax.named_scope("head_sample"):  # the engine's sampler joins it
        logits = _head(x, params, cfg)
    n_live = jnp.sum(live, dtype=jnp.int32)
    return logits, pool, {
        "state_rows_stepped": cfg.n_layers * n_live,
        "state_rows_fetched": fetched,
        "ssm_layer_steps": jnp.where(n_live > 0, cfg.n_layers, 0).astype(
            jnp.int32)}


def _carried(entry, first):
    """What a chunk takes over from its slot's ``entry``: what the chunk
    before it left there, and zeros where the chunk is its prompt's
    ``first``, as before position 0 of :func:`prefill_row` (what an earlier
    request left in the slot is never read, and nothing clears it)."""
    return jnp.where(first, jnp.zeros_like(entry), entry)


def mixed_step(params, pool, chunk_tokens, chunk_pages, chunk_last, tokens,
               positions, lengths, page_table, cfg: HybridSSMConfig, *,
               chunk_index, slot):
    """One chunk of one row's prompt and one decode token a live row, in one
    pass over the layers: the decode rows' weights are the chunk's. The
    arguments are ``models/gpt.py::mixed_step``'s, and ``slot``: the index of
    the row being prefilled, whose state entry the chunk continues.

    ``chunk_tokens`` int32 [C] are the prompt's positions
    ``[chunk_index * C, (chunk_index + 1) * C)`` (``C`` a whole number of
    pages), of which the first ``chunk_last + 1`` are real: ``chunk_last`` is
    the position inside the chunk whose logits are wanted, the prompt's last
    token in its last chunk and ``C - 1`` in every other. ``chunk_pages`` is
    the prompt's row of the block table in whole chunks. ``tokens``,
    ``positions``, ``lengths`` and ``page_table`` are :func:`paged_decode`'s;
    the row being prefilled is idle among them (length 0, a table row of
    sink entries): the decode half neither fetches nor moves its state.

    Embedding, norms, every projection, the gate and the MLP run once over
    the ``C + B`` rows. The attention splits them as ``gpt.mixed_step``
    does: the chunk in the flash forward kernel over the row's pages before
    it (gathered outside a ``lax.switch`` over the prefix lengths a prompt
    can have, ``chunk_index`` a run-time int32: ONE program) and itself, the
    decode rows through the block table. So does the state-space branch:

      - the chunk's convolution takes the taps before its first position
        from the slot's tail and its scan starts from the slot's state
        (``ssd_scan(..., h0=)``), **zeros both where ``chunk_index == 0``**:
        whatever an earlier request left in the slot is never read, and no
        program clears it. The scan stops at the chunk's real positions
        (``true_len``), and the slot's entry then holds the state and the
        last ``ssm_conv - 1`` inputs as of the chunk's last real position
        (fewer real positions than that: the rest from the old tail);
      - the live rows' update is :func:`paged_decode`'s kernel where the
        state lies, under a name of its own (``ssm_mixed_update``: whoever
        counts the decode program's token-steps by ``ssm_decode_update``'s
        calls counts none here).

    The slot's 4 MiB a layer are sliced out of the state after the live
    rows' update and put back into it after the scan, so the state stays
    where it is. The layer loop only reads the pages; after it the chunk's K
    and V go to whole pages and each decode row's to its one position.
    Returns (logits [B + 1, V] fp32: the decode rows', then the chunk's at
    ``chunk_last``; the pool; a count int32 under a name of the mixed step's
    own: ``mixed_state_rows_stepped``, the live rows whose state moved, summed
    over the layers)."""
    C, page = chunk_tokens.shape[0], pool["k"].shape[3]
    Hkv, Dh, rep = cfg.kv_heads, cfg.head_dim, cfg.n_heads // cfg.kv_heads
    tail, n_pages = cfg.ssm_conv - 1, C // page
    n_chunks = chunk_pages.shape[0] // n_pages  # the longest prompt's
    chunk_index = jnp.asarray(chunk_index, jnp.int32)
    slot = jnp.asarray(slot, jnp.int32)
    n_real = chunk_last + 1
    first = chunk_index == 0
    before = chunk_pages[:(n_chunks - 1) * n_pages]
    use = _kernel_use(cfg, C)
    live = lengths > 0
    at = jnp.concatenate([chunk_index * C + jnp.arange(C), positions])
    x = _embed(params, jnp.concatenate([chunk_tokens, tokens]), cfg)

    def over(n_before):  # the chunk over ``n_before`` earlier chunks + itself
        def attend(q, ks, vs):
            # the kernel wants as many K/V heads as query heads
            return flash_attention(
                q, jnp.repeat(ks[:, :(n_before + 1) * C], rep, axis=0)[None],
                jnp.repeat(vs[:, :(n_before + 1) * C], rep, axis=0)[None],
                causal=True, use_pallas=use)
        return attend

    def row_so_far(pages_of, own, layer):
        # the row's pages before its last chunk, then the chunk's own
        # positions laid over them where the chunk starts: the first
        # ``(chunk_index + 1) * C`` positions are what the chunk sees
        so_far = jnp.concatenate([
            lax.dynamic_slice(pages_of, (layer, 0, before[i], 0, 0),
                              (1, Hkv, 1, page, Dh)).reshape(Hkv, page, Dh)
            for i in range(before.shape[0])] + [own], axis=1)
        return lax.dynamic_update_slice(so_far, own, (0, chunk_index * C, 0))

    state, conv = pool["ssm"], pool["conv"]
    k_chunk, v_chunk, k_rows, v_rows, tails = [], [], [], [], []
    for i, p in enumerate(params["layers"]):
        u = _rmsnorm(x, p["ln"], cfg.rms_norm_eps)
        q, k, v = _qkv(u, p, at, cfg)
        kt, vt = k[:C].transpose(1, 0, 2), v[:C].transpose(1, 0, 2)
        with jax.named_scope("prefix_gather"):
            ks, vs = row_so_far(pool["k"], kt, i), row_so_far(pool["v"], vt, i)
        with jax.named_scope("prefill_attention"):
            o_chunk = lax.switch(
                chunk_index, [over(n) for n in range(n_chunks)],
                q[:C].transpose(1, 0, 2)[None], ks, vs)[0]
        with jax.named_scope("decode_attention"):
            o_rows = paged_attention(
                q[C:], pool["k"], pool["v"], lengths, page_table, layer=i,
                k_cur=k[C:], v_cur=v[C:])
        a = _attn_out(jnp.concatenate([o_chunk.transpose(1, 0, 2), o_rows]),
                      p, cfg)
        z, xbc, dt = _ssm_project(u, p, cfg)
        with jax.named_scope("ssm_conv"):
            old = conv[i]                               # [taps - 1, B, C]
            mine = lax.dynamic_slice_in_dim(old, slot, 1, axis=1)[:, 0]
            behind = jnp.concatenate([_carried(mine, first), xbc[:C]])
            xs, b, c = _split_xbc(jnp.concatenate([
                _conv([behind[j:j + C] for j in range(cfg.ssm_conv)], p, cfg),
                _conv([*old, xbc[C:]], p, cfg)]), cfg)
            # the live rows' tails move on by their token; the slot's holds
            # the last real inputs: rows n_real - tail .. n_real - 1
            tails.append(lax.dynamic_update_slice_in_dim(
                jnp.where(live[None, :, None],
                          jnp.concatenate([old[1:], xbc[None, C:]]), old),
                lax.dynamic_slice_in_dim(behind, n_real, tail)[:, None],
                slot, axis=1))
        A = -jnp.exp(p["A_log"])
        with jax.named_scope("ssm_mixed_update"):
            y_rows, state, _ = ssm.ssm_decode_update(
                state, xs[C:], dt[C:], A, b[C:], c[C:], p["D"], live,
                layer=i, name="ssm_mixed_update")
        with jax.named_scope("ssm_scan"):
            h0 = lax.dynamic_slice(state, (i, slot, 0, 0, 0),
                                   (1, 1) + state.shape[2:])[0, 0]
            y_chunk, h = ssm.ssd_scan(
                xs[:C], dt[:C], A, b[:C], c[:C], p["D"], true_len=n_real,
                h0=_carried(h0, first))
        with jax.named_scope("state_write"):
            state = lax.dynamic_update_slice(state, h[None, None],
                                             (i, slot, 0, 0, 0))
        k_chunk.append(kt), v_chunk.append(vt)
        k_rows.append(k[C:]), v_rows.append(v[C:])
        x = x + a + _gate_out(jnp.concatenate([y_chunk, y_rows]), z, p, cfg)
        x = x + _mlp(x, p, cfg)
    with jax.named_scope("kv_write"):
        # the pages are written only once every layer has read them
        # (:func:`paged_decode`)
        x, k_chunk, v_chunk, k_rows, v_rows = lax.optimization_barrier(
            (x, jnp.stack(k_chunk), jnp.stack(v_chunk), jnp.stack(k_rows, 1),
             jnp.stack(v_rows, 1)))
        now = lax.dynamic_slice_in_dim(chunk_pages, chunk_index * n_pages,
                                       n_pages)

        def whole_pages(pages_of, new):  # new [L, Hkv, C, Dh]
            return pages_of.at[:, :, now].set(
                new.reshape(new.shape[:2] + (n_pages, page, Dh)))

        pool = _write_rows({"k": whole_pages(pool["k"], k_chunk),
                            "v": whole_pages(pool["v"], v_chunk)},
                           k_rows, v_rows, positions, page_table)
    with jax.named_scope("state_write"):
        pool.update(ssm=state, conv=jnp.stack(tails))
    with jax.named_scope("head_sample"):  # the engine's sampler joins it
        logits = _head(jnp.concatenate(
            [x[C:], lax.dynamic_slice_in_dim(x, chunk_last, 1)]), params, cfg)
    return logits, pool, {"mixed_state_rows_stepped": cfg.n_layers * jnp.sum(
        live, dtype=jnp.int32)}
