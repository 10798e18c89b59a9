"""The plain reference of MiniCPM-SALA's decoder (architecture
``sparse_linear``, ``model_type`` ``minicpm_sala``): Lightning linear
attention in the ``lightning-attn`` layers, MiniCPM4's InfLLM-v2 block-sparse
attention in the ``minicpm4`` ones, a SwiGLU MLP in every layer, MiniCPM's
scaled embedding, residual and head.

Straightforward ``jax.numpy``, one row at a time, no kernel, no cache, no
batching; float32 with every matmul at ``highest`` unless a lower ``compute``
is named, which is how the controls are made (``fp8``: operands of every
matmul rounded to float8_e4m3; ``bf16``: operands in bfloat16). It imports
nothing of the program and takes nothing the program made: weights come from
the seed by the recipe of :func:`init_params`, which the configuration file
states and which the benchmark hands the program too
(``architectures/sparse_linear.py::init_program_params``). The small pieces
(a matmul of one precision, RMSNorm, RoPE, SwiGLU) are
``reference/latent_moe.py``'s, the sibling reference's.

The model (keys as the configuration file has them; ``r = scale_depth /
sqrt(residual_depth)``, the published depth):

  - ``x = scale_emb * E[token]``; a layer ``x += r * Mixer(RMSNorm(x))``,
    then ``x += r * SwiGLU(RMSNorm(x))``; logits ``W_head RMSNorm(x) /
    (hidden_size / dim_model_base)``;
  - ``lightning-attn``: ``lightning_nh`` heads of ``lightning_head_dim``, a
    key a head; ``q = RoPE(RMSNorm(W_q u))``, ``k = RoPE(RMSNorm(W_k u))``
    (RMSNorm over a head, RoPE where ``lightning_use_rope``), ``v = W_v u``;
    **the recurrence as written**, a position at a time: ``S_t = lambda_h
    S_{t-1} + k_t^T v_t``, ``o_t = q_t S_t / sqrt(lightning_head_dim)``,
    ``lambda_h = exp(-2 ** (-8 (h + 1) / lightning_nh))``; ``W_o(RMSNorm(
    concat o) * sigmoid(W_g u))``;
  - ``minicpm4``: ``num_attention_heads`` query heads on
    ``num_key_value_heads``, no position; ``q = RMSNorm(W_q u)``, ``k =
    RMSNorm(W_k u)``, ``v = W_v u``; pooled keys ``c_j = mean(k[s j .. s j
    + w - 1])`` (``s = kernel_stride``, ``w = kernel_size``, of
    ``sparse_config``); a query at ``t`` scores each window complete at it
    (``s j + w - 1 <= t``): the softmax over them of ``q_h . c_j /
    sqrt(head_dim)``, summed over its K/V group's heads; a block of
    ``block_size`` positions the largest over the windows that meet it;
    it chooses the first ``init_blocks`` blocks, every block holding one of
    its last ``window_size`` positions, then the highest others up to
    ``topk`` blocks (ties to the lower block, ``lax.top_k``'s order), never
    a block that starts after ``t``, and every block up to its own while
    ``t < dense_len``; softmax of ``q_h . k / sqrt(head_dim)`` over the
    chosen positions up to ``t``; ``W_o(o * sigmoid(W_g u))``.

Departures from the published description, each stated in the configuration
file: the decay, the gates' and the output norm's form, and the
``sparse_config`` values (taken from MiniCPM4-8B's ``config.json``) are
assumed; MiniCPM4's own kernels compute the pooled scores per head and keep
the first and the local blocks by their own rules (the technical report,
arXiv:2506.07900, describes the selection as written here).

Memory: weights stay in the configuration's type and are widened a matrix or
a block of the head's columns at a time; the sparse layer runs in blocks of
query rows, the recurrence in a scan over positions, the MLP in blocks of
rows; so a row of 31,744 positions fits beside 5.6 GB of bf16 weights.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from chipbench.reference.latent_moe import (HEAD_BLOCK, _f32, _mm, _rms,
                                            _rope, _swiglu)

Q_BLOCK = 128        # query rows of one block of the sparse layer
ROW_BLOCK = 4096     # rows of one block of the MLP
SPARSE, LIGHTNING = "minicpm4", "lightning-attn"


def init_params(key, cfg: dict, dtype=None):
    """Weights from ``key`` (``jax.random.PRNGKey(seed)``), one dict a
    layer: ``split(key, 2 + layers)`` gives the embedding's key, the head's,
    then one a layer; a layer's is split in 8 and taken in the order written
    here (q, k, v, g, o, the MLP's gate, up and down); a matrix is normal *
    fan_in**-0.5, a norm's scale 1."""
    pd = jnp.dtype(dtype or cfg["param_dtype"])
    d, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    keys = jax.random.split(key, 2 + cfg["num_hidden_layers"])

    def w(k, shape):
        return jax.random.normal(k, shape, pd) * (shape[0] ** -0.5)

    def layer(k, kind):
        k = jax.random.split(k, 8)
        if kind == SPARSE:
            hq, hkv, dh = (cfg["num_attention_heads"],
                           cfg["num_key_value_heads"], cfg["head_dim"])
        else:
            hq, hkv, dh = (cfg["lightning_nh"], cfg["lightning_nkv"],
                           cfg["lightning_head_dim"])
        out = {"ln": jnp.ones((d,), pd), "mlp_ln": jnp.ones((d,), pd),
               "q": w(k[0], (d, hq * dh)), "k": w(k[1], (d, hkv * dh)),
               "v": w(k[2], (d, hkv * dh)), "g": w(k[3], (d, hq * dh)),
               "o": w(k[4], (hq * dh, d)),
               "q_ln": jnp.ones((dh,), pd), "k_ln": jnp.ones((dh,), pd),
               "mlp": {"w1": w(k[5], (d, f)), "w3": w(k[6], (d, f)),
                       "w2": w(k[7], (f, d))}}
        if kind == LIGHTNING:
            out["out_ln"] = jnp.ones((hq * dh,), pd)
        return out

    return {"tok_embed": w(keys[0], (v, d)), "lm_head": w(keys[1], (d, v)),
            "final_ln": jnp.ones((d,), pd),
            "layers": [layer(keys[2 + i], kind)
                       for i, kind in enumerate(cfg["mixer_types"])]}


def _blocks(a, block):
    """a [S, ...] -> [n, block, ...], zero rows past S."""
    pad = (-a.shape[0]) % block
    a = jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
    return a.reshape((-1, block) + a.shape[1:])


def _heads(u, w, dim, mm):
    return mm(u, _f32(w)).reshape(u.shape[0], -1, dim)


def _lightning(u, p, cfg, mm):
    """A lightning layer's mixer over u [S, D] (after its norm)."""
    h, dh, eps = cfg["lightning_nh"], cfg["lightning_head_dim"], \
        cfg["rms_norm_eps"]
    q = _rms(_heads(u, p["q"], dh, mm), p["q_ln"], eps)
    k = _rms(_heads(u, p["k"], dh, mm), p["k_ln"], eps)
    if cfg["lightning_use_rope"]:
        q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    v = _heads(u, p["v"], dh, mm)
    lam = jnp.exp(-2.0 ** (-8.0 * (jnp.arange(h, dtype=jnp.float32) + 1)
                           / h))

    def step(s, qkv):
        qt, kt, vt = qkv                                    # [h, dh] each
        s = lam[:, None, None] * s + kt[:, :, None] * vt[:, None, :]
        return s, mm(qt[:, None, :], s)[:, 0]               # [h, dh]

    _, o = lax.scan(step, jnp.zeros((h, dh, dh), jnp.float32), (q, k, v),
                    unroll=8)
    o = _rms(o.reshape(u.shape[0], h * dh) * dh ** -0.5, p["out_ln"], eps)
    return mm(o * jax.nn.sigmoid(mm(u, _f32(p["g"]))), _f32(p["o"]))


def _sparse(u, p, cfg, mm):
    """A sparse layer's mixer over u [S, D] (after its norm): each block of
    queries scores, chooses and attends for itself."""
    S = u.shape[0]
    hq, hkv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    sc, eps = cfg["sparse_config"], cfg["rms_norm_eps"]
    bs, stride, w = sc["block_size"], sc["kernel_stride"], sc["kernel_size"]
    if w > bs:
        raise ValueError("a pooled window wider than a block")
    rep, scale = hq // hkv, dh ** -0.5
    q = _rms(_heads(u, p["q"], dh, mm), p["q_ln"], eps)     # [S, hq, dh]
    k = _rms(_heads(u, p["k"], dh, mm), p["k_ln"], eps)     # [S, hkv, dh]
    v = _heads(u, p["v"], dh, mm)
    n_win = max(0, (S - w) // stride + 1)
    starts = stride * jnp.arange(n_win)
    pooled = jnp.mean(jnp.stack([k[starts + i] for i in range(w)]), 0) \
        if n_win else jnp.zeros((1, hkv, dh), jnp.float32)  # [J, hkv, dh]
    ends = starts + w - 1 if n_win else jnp.array([S + w])
    n_blocks = -(-S // bs)
    b = jnp.arange(n_blocks)
    # a window (no wider than a block) meets the blocks of its first and
    # its last position
    meets = (starts // bs, jnp.minimum(ends, S - 1) // bs) if n_win else \
        (jnp.zeros(1, jnp.int32),) * 2
    kk = k.transpose(1, 2, 0)                               # [hkv, dh, S]
    vv = v.transpose(1, 0, 2)                               # [hkv, S, dh]
    cc = pooled.transpose(1, 2, 0)                          # [hkv, dh, J]
    block = min(Q_BLOCK, S)

    def one(args):
        qb, t = args                                        # [n, hq, dh], [n]
        n = qb.shape[0]
        qg = qb.reshape(n, hkv, rep, dh).transpose(1, 0, 2, 3)
        qg = qg.reshape(hkv, n * rep, dh)
        # the pooled scores: softmax over the complete windows, summed over
        # the group's heads; a block takes its windows' largest
        s = mm(qg, cc).reshape(hkv, n, rep, -1) * scale
        done = (ends[None, :] <= t[:, None])[None, :, None]
        s = jnp.where(done, s, -jnp.inf)
        prob = jnp.where(done, jax.nn.softmax(s, -1), 0.0).sum(2)
        r = jnp.maximum(*(jax.ops.segment_max(
            jnp.moveaxis(prob, 2, 0), m, num_segments=n_blocks)
            for m in meets))
        r = jnp.maximum(jnp.moveaxis(r, 0, 2), 0.0)         # none: 0
        exists = b[None, None] * bs <= t[None, :, None]     # [1, n, NB]
        forced = (b[None, None] < sc["init_blocks"]) | (
            (b[None, None] + 1) * bs > t[None, :, None] - sc["window_size"]
            + 1)
        score = jnp.where(exists, jnp.where(forced, jnp.inf, r), -jnp.inf)
        got, idx = lax.top_k(score, min(sc["topk"], n_blocks))
        chosen = jnp.zeros(score.shape, bool).at[
            jnp.arange(hkv)[:, None, None], jnp.arange(n)[None, :, None],
            idx].set(got > -jnp.inf)
        chosen = jnp.where(t[None, :, None] < sc["dense_len"], exists,
                           chosen)                          # [hkv, n, NB]
        pos = jnp.arange(S)
        seen = chosen[:, :, pos // bs] & (pos[None, None] <= t[None, :, None])
        a = mm(qg, kk).reshape(hkv, n, rep, S) * scale
        a = jnp.where(seen[:, :, None], a, -jnp.inf)
        o = mm(jax.nn.softmax(a, -1).reshape(hkv, n * rep, S), vv)
        return o.reshape(hkv, n, rep, dh).transpose(1, 0, 2, 3).reshape(
            n, hq * dh)

    o = lax.map(one, (_blocks(q, block), _blocks(jnp.arange(S), block)))
    o = o.reshape(-1, hq * dh)[:S]
    return mm(o * jax.nn.sigmoid(mm(u, _f32(p["g"]))), _f32(p["o"]))


def _mlp(x, p, cfg, r, mm):
    """x + r * the MLP of its norm, in blocks of rows."""
    S = x.shape[0]

    def one(xb):
        h = _rms(xb, p["mlp_ln"], cfg["rms_norm_eps"])
        return xb + r * _swiglu(h, p["mlp"]["w1"], p["mlp"]["w3"],
                                p["mlp"]["w2"], mm)

    return lax.map(one, _blocks(x, min(ROW_BLOCK, S))).reshape(
        -1, x.shape[1])[:S]


def hidden_states(params, tokens, cfg: dict, compute: str = "f32"):
    """tokens [S] -> final normed hidden states [S, D] of one row."""
    mm, eps = _mm(compute), cfg["rms_norm_eps"]
    r = cfg["scale_depth"] / cfg["residual_depth"] ** 0.5
    x = cfg["scale_emb"] * _f32(params["tok_embed"][tokens])
    for p, kind in zip(params["layers"], cfg["mixer_types"]):
        u = _rms(x, p["ln"], eps)
        mix = _sparse if kind == SPARSE else _lightning
        x = _mlp(x + r * mix(u, p, cfg, mm), p, cfg, r, mm)
    return _rms(x, params["final_ln"], eps)


def logits(params, tokens, cfg: dict, compute: str = "f32"):
    """tokens [S] -> logits [S, V] (float32), divided by ``hidden_size /
    dim_model_base``; the head's columns widened a block at a time."""
    with jax.default_matmul_precision("highest"):
        x = hidden_states(params, tokens, cfg, compute) \
            / (cfg["hidden_size"] / cfg["dim_model_base"])
        mm, head = _mm(compute), params["lm_head"]
        V = head.shape[1]
        n = next(n for n in range(1, V + 1)
                 if V % n == 0 and V // n <= HEAD_BLOCK)
        width = V // n

        def one(i, out):
            cols = lax.dynamic_slice_in_dim(head, i * width, width, axis=1)
            return lax.dynamic_update_slice_in_dim(
                out, mm(x, _f32(cols)), i * width, axis=1)

        return lax.fori_loop(0, n, one,
                             jnp.zeros((x.shape[0], V), jnp.float32))
