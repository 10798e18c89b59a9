"""The plain reference of the decoder block both configurations share.

Straightforward ``jax.numpy``: RMSNorm, rotary positions (half-split
convention), grouped-query causal attention, SwiGLU, untied head. No kernel,
no cache, no batching; float32 with ``jax.default_matmul_precision("highest")``
unless a lower ``compute`` is named, which is how the controls are made
(``fp8``: operands of every matmul rounded to float8_e4m3; ``bf16``: operands
in bfloat16). It imports nothing of the program and takes nothing the program
made: weights come from the seed by the recipe the configuration file states.

Memory: attention and the loss run in blocks of query rows under
``jax.checkpoint``, so a 4096-token row needs a few hundred MB, not the
[heads, S, S] scores at once.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

Q_BLOCK = 512


def dims(cfg: dict):
    return (cfg["num_hidden_layers"], cfg["hidden_size"],
            cfg["intermediate_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"], cfg["vocab_size"])


def init_params(key, cfg: dict, dtype=None):
    """Weights from ``key`` (``jax.random.PRNGKey(seed)``), by the
    configuration's stated recipe, in the configuration's parameter type
    (or ``dtype``)."""
    L, D, F, H, Hkv, Dh, V = dims(cfg)
    pd = jnp.dtype(dtype or cfg["param_dtype"])
    keys = jax.random.split(key, 8)

    def dense(k, shape, fan_in):
        return jax.random.normal(k, shape, pd) * (fan_in ** -0.5)

    return {
        "tok_embed": dense(keys[0], (V, D), D),
        "layers": {
            "ln1": jnp.ones((L, D), pd), "ln2": jnp.ones((L, D), pd),
            "wq": dense(keys[1], (L, D, H * Dh), D),
            "wk": dense(keys[2], (L, D, Hkv * Dh), D),
            "wv": dense(keys[3], (L, D, Hkv * Dh), D),
            "wo": dense(keys[4], (L, H * Dh, D), H * Dh),
            "w1": dense(keys[5], (L, D, F), D),
            "w3": dense(keys[6], (L, D, F), D),
            "w2": dense(keys[7], (L, F, D), F),
        },
        "final_ln": jnp.ones((D,), pd),
        "lm_head": dense(keys[0], (D, V), D),
    }


def _mm(compute: str):
    """The matmul of one precision: f32 'highest', or a lower control."""
    if compute == "f32":
        return functools.partial(jnp.matmul, precision=lax.Precision.HIGHEST)
    low = {"fp8": jnp.float8_e4m3fn, "bf16": jnp.bfloat16}[compute]

    def mm(a, b):
        return jnp.matmul(a.astype(low).astype(jnp.bfloat16),
                          b.astype(low).astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    return mm


def _rmsnorm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * lax.rsqrt(var + eps) * scale


def _rope(x, positions, theta):
    """x [S, H, Dh]; rotate (first half, second half) pairs."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(q, k, v, mm):
    """Causal grouped-query attention of one row: q [S, H, Dh], k/v
    [S, Hkv, Dh] -> [S, H * Dh], in blocks of query positions."""
    S, H, Dh = q.shape
    rep = H // k.shape[1]
    kk = jnp.repeat(k, rep, axis=1).transpose(1, 2, 0)   # [H, Dh, S]
    vv = jnp.repeat(v, rep, axis=1).transpose(1, 0, 2)   # [H, S, Dh]
    block = min(Q_BLOCK, S)
    pad = (-S) % block
    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
    qb = qp.reshape(-1, block, H, Dh).transpose(0, 2, 1, 3)  # [n, H, b, Dh]
    starts = jnp.arange(qb.shape[0]) * block

    @jax.checkpoint
    def one(args):
        qi, start = args
        s = mm(qi, kk) * (Dh ** -0.5)                     # [H, b, S]
        rows = start + jnp.arange(block)[:, None]
        s = jnp.where(jnp.arange(S)[None, :] <= rows, s, -jnp.inf)
        return mm(jax.nn.softmax(s, axis=-1), vv)         # [H, b, Dh]

    o = lax.map(one, (qb, starts))                        # [n, H, b, Dh]
    return o.transpose(0, 2, 1, 3).reshape(-1, H * Dh)[:S]


def hidden_states(params, tokens, cfg: dict, compute: str = "f32",
                  remat: bool = False):
    """tokens [S] -> final normed hidden states [S, D] of one row."""
    L, D, F, H, Hkv, Dh, V = dims(cfg)
    mm = _mm(compute)
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    S = tokens.shape[0]
    pos = jnp.arange(S)
    x = f32(params["tok_embed"][tokens])

    def block(x, layer):
        layer = jax.tree.map(f32, layer)
        h = _rmsnorm(x, layer["ln1"], eps)
        q = _rope(mm(h, layer["wq"]).reshape(S, H, Dh), pos, theta)
        k = _rope(mm(h, layer["wk"]).reshape(S, Hkv, Dh), pos, theta)
        v = mm(h, layer["wv"]).reshape(S, Hkv, Dh)
        x = x + mm(_attention(q, k, v, mm), layer["wo"])
        h = _rmsnorm(x, layer["ln2"], eps)
        x = x + mm(jax.nn.silu(mm(h, layer["w1"])) * mm(h, layer["w3"]),
                   layer["w2"])
        return x, None

    x, _ = lax.scan(jax.checkpoint(block) if remat else block, x,
                    params["layers"])
    return _rmsnorm(x, f32(params["final_ln"]), eps)


def logits(params, tokens, cfg: dict, compute: str = "f32"):
    """tokens [S] -> logits [S, V] (float32)."""
    x = hidden_states(params, tokens, cfg, compute)
    return _mm(compute)(x, params["lm_head"].astype(jnp.float32))


def row_loss_sum(params, tokens, targets, cfg: dict, compute: str = "f32"):
    """Sum over one row's positions of the cross entropy, the head and the
    softmax taken in blocks of positions."""
    x = hidden_states(params, tokens, cfg, compute, remat=True)
    mm = _mm(compute)
    head = params["lm_head"].astype(jnp.float32)
    S = tokens.shape[0]
    block = min(Q_BLOCK, S)
    pad = (-S) % block
    xb = jnp.pad(x, ((0, pad), (0, 0))).reshape(-1, block, x.shape[-1])
    tb = jnp.pad(targets, (0, pad)).reshape(-1, block)
    wb = jnp.pad(jnp.ones((S,), jnp.float32), (0, pad)).reshape(-1, block)

    @jax.checkpoint
    def one(args):
        xi, ti, wi = args
        lg = mm(xi, head)
        lse = jax.scipy.special.logsumexp(lg, axis=-1)
        took = jnp.take_along_axis(lg, ti[:, None], axis=-1)[:, 0]
        return jnp.sum((lse - took) * wi)

    return jnp.sum(lax.map(one, (xb, tb, wb)))


def mean_loss(params, batch, cfg: dict, compute: str = "f32"):
    """batch {"tokens", "targets"} [B, S] -> mean cross entropy."""
    B, S = batch["tokens"].shape
    total = jax.vmap(
        lambda t, y: row_loss_sum(params, t, y, cfg, compute))(
            batch["tokens"], batch["targets"])
    return jnp.sum(total) / (B * S)
