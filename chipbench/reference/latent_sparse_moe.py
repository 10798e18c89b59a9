"""The plain reference of latent attention under a learned selection with a
share of the routed experts (architecture ``latent_sparse_moe``: GLM-5.2's
layer, ``model_type`` ``glm_moe_dsa``; the family's public description is
DeepSeek-V3.2-Exp's inference code, class ``Indexer``).

Straightforward ``jax.numpy``, one row at a time, no kernel, no cache, no
batching; float32 with every matmul at ``highest`` unless a lower ``compute``
is named, which is how the controls are made (``fp8``: operands of every
matmul, the indexer's and the router's among them, rounded to float8_e4m3;
``bf16``: operands in bfloat16). Attention is in its plain form, never
absorbed, over **every** position under a mask built from ``lax.top_k``'s
indices; the experts are a plain loop over the experts held, every token
through each. It imports nothing of the program and takes nothing the program
made: weights come from the seed by the recipe of :func:`init_params`, which
the configuration file states and which the benchmark hands the program too
(``architectures/latent_sparse_moe.py::init_program_params``). The small
pieces (a matmul of one precision, RMSNorm, RoPE, SwiGLU) are
``reference/latent_moe.py``'s, the sibling reference's.

A layer (keys as the published ``config.json`` has them), ``h = RMSNorm(x)``:

  - queries and latent as ``reference/latent_moe.py``: ``c_q = RMSNorm(W_qa
    h)``; a head's query ``W_qb c_q`` = [``qk_nope_head_dim`` without
    position | RoPE(``qk_rope_head_dim``)]; ``c_kv = RMSNorm((W_kva h)[:
    kv_lora_rank])`` and one rotary key a position from the rest;
  - in a layer whose ``indexer_types`` entry is ``full``: ``index_n_heads``
    index queries of ``index_head_dim`` from ``c_q``, one index key a position
    ``LayerNorm(W_Ik h)``, RoPE on the first ``qk_rope_head_dim`` columns of
    both, head weights ``(W_Iw h) * index_n_heads**-0.5 *
    index_head_dim**-0.5``; ``I[t, s] = sum_j w[t, j] relu(qI[t, j] .
    kI[s])`` for ``s <= t``; ``S_t`` = the ``index_topk`` positions of
    largest ``I[t, s]`` (all while ``t < index_topk``; ties to the lower
    position, ``lax.top_k``'s order);
  - in a ``shared`` layer ``S_t`` is that of the nearest ``full`` layer below;
  - attention over ``S_t`` only: keys and values of every head expanded from
    ``c_kv`` (``W_kvb``), scores scaled by (nope + rope) ** -0.5, softmax over
    ``s in S_t``; ``W_o``;
  - second half as ``reference/latent_moe.py``: SwiGLU of
    ``intermediate_size`` in the first ``first_k_dense_replace`` layers; after
    them scores ``sigmoid(h W_r)`` over **all** ``n_router_experts`` (the
    published count), the ``num_experts_per_tok`` largest of score + bias,
    weights the scores over their sum times ``routed_scaling_factor``, beside
    the shared expert. **Of the routed experts only those held here are
    computed** (``n_routed_experts`` of them from ``first_held_expert``: one
    chip's share of the layer): what a token's other experts would have added
    is left out, here as in the program, and the partial sum goes on.

Memory: weights stay in the configuration's type and are widened a matrix, a
block of heads, one expert or a block of the head's columns at a time; the
second half runs in blocks of rows, the indexer and the attention in blocks
of query rows (the attention inside a loop over blocks of heads); so a
30,208-token row fits beside 9.4 GB of bf16 weights.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from chipbench.reference.latent_moe import (EXPERT_SPREAD, HEAD_BLOCK,
                                            ROUTER_GAIN_SIGMA, _f32, _mm,
                                            _rms, _rope, _swiglu)

Q_BLOCK = 256        # query rows of one block of scores
ROW_BLOCK = 4096     # rows of one block of a layer's second half
HEADS_A_BLOCK = 16   # attention heads expanded from the latent at a time
# the recipe's number of its own, stated in the configuration file
INDEX_KEY_BIAS = 0.1   # spread of the index key's LayerNorm bias


def init_params(key, cfg: dict, dtype=None):
    """Weights from ``key`` (``jax.random.PRNGKey(seed)``), one dict a
    layer: ``split(key, 2 + layers)`` gives the embedding's key, the head's,
    then one a layer; a layer's is split in 16, taken in the order written
    here; a matrix is normal * fan_in**-0.5, norm scales 1.

    *Carried over from ``reference/latent_moe.py``* (``glm-4.7-flash-d7``'s
    recipe, which says why): the router's uneven gains with the choosing bias
    that evens the load again, computed for the router's published width; and
    the routed experts as one matrix a layer plus ``EXPERT_SPREAD`` of a
    matrix each, drawn for the experts held here.

    *Its own*: the index key's LayerNorm has a bias of normal *
    ``INDEX_KEY_BIAS``, so that a program that dropped it is another model.
    **Attention and index scores keep plain weights.** ISSUE 39 asked for a
    gain on them, lest a wrong selection hide inside the tolerance; measured
    on the chip at the published widths (PERF.md, PR 39) plain weights
    already let the selection decide the output (a program that attends every
    cached position reads 5.3 on the check's statistic where the faithful one
    reads 1.0 and the fp8 control 5.3), and a gain of 2 or 3 on ``W_qb``
    only raised the faithful program's own reading (1.6; 4.1), because a
    sharper head makes more of each near-tie in the top-2,048."""
    pd = jnp.dtype(dtype or cfg["param_dtype"])
    d, h, v = cfg["hidden_size"], cfg["num_attention_heads"], \
        cfg["vocab_size"]
    ql, kl = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    f, fe = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    e_all, e = cfg["n_router_experts"], cfg["n_routed_experts"]
    fs = cfg["n_shared_experts"] * fe
    j, di = cfg["index_n_heads"], cfg["index_head_dim"]
    n, dense = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    keys = jax.random.split(key, 2 + n)
    z = jax.scipy.special.ndtri(1.0 - cfg["num_experts_per_tok"] / e_all)

    def w(k, shape, fan_in):
        return jax.random.normal(k, shape, pd) * (fan_in ** -0.5)

    def experts(k, shape, fan_in):
        shared, own = jax.random.split(k)
        both = jax.random.normal(shared, shape[1:], pd) \
            + EXPERT_SPREAD * jax.random.normal(own, shape, pd)
        return both * ((fan_in * (1 + EXPERT_SPREAD ** 2)) ** -0.5)

    def layer(k, i):
        k = jax.random.split(k, 16)
        out = {
            "ln": jnp.ones((d,), pd), "q_ln": jnp.ones((ql,), pd),
            "kv_ln": jnp.ones((kl,), pd), "mlp_ln": jnp.ones((d,), pd),
            "q_a": w(k[0], (d, ql), d),
            "q_b": w(k[1], (ql, h * (nope + rope)), ql),
            "kv_a": w(k[2], (d, kl + rope), d),
            "kv_b": w(k[3], (kl, h * (nope + vd)), kl),
            "o": w(k[4], (h * vd, d), h * vd),
        }
        if cfg["indexer_types"][i] == "full":
            kb, kw = jax.random.split(k[15])
            out["index"] = {
                "wq_b": w(k[13], (ql, j * di), ql),
                "wk": w(k[14], (d, di), d),
                "k_ln": jnp.ones((di,), pd),
                "k_ln_b": (INDEX_KEY_BIAS
                           * jax.random.normal(kb, (di,))).astype(pd),
                "w": w(kw, (d, j), d)}
        if i < dense:
            out["mlp"] = {"w1": w(k[5], (d, f), d), "w3": w(k[6], (d, f), d),
                          "w2": w(k[7], (f, d), f)}
        else:
            gain = jnp.exp(ROUTER_GAIN_SIGMA * jax.random.uniform(
                k[6], (e_all,), jnp.float32, -3 ** 0.5, 3 ** 0.5))
            out["moe"] = {
                "router": (jax.random.normal(k[5], (d, e_all), jnp.float32)
                           * gain * d ** -0.5).astype(pd),
                "bias": (jax.nn.sigmoid(z)
                         - jax.nn.sigmoid(z * gain)).astype(pd),
                "w1": experts(k[7], (e, d, fe), d),
                "w3": experts(k[8], (e, d, fe), d),
                "w2": experts(k[9], (e, fe, d), fe)}
            out["shared"] = {"w1": w(k[10], (d, fs), d),
                             "w3": w(k[11], (d, fs), d),
                             "w2": w(k[12], (fs, d), fs)}
        return out

    return {"tok_embed": w(keys[0], (v, d), d),
            "lm_head": w(keys[1], (d, v), d),
            "final_ln": jnp.ones((d,), pd),
            "layers": [layer(keys[2 + i], i) for i in range(n)]}


def _theta(cfg):
    return cfg["rope_parameters"]["rope_theta"]


def _partly_rotary(x, cfg):
    """x [S, heads, dim]: RoPE on the first ``qk_rope_head_dim`` columns."""
    r = cfg["qk_rope_head_dim"]
    return jnp.concatenate([_rope(x[..., :r], _theta(cfg)), x[..., r:]], -1)


def _blocks(a, block):
    """a [S, ...] -> [n, block, ...], zero rows past S."""
    pad = (-a.shape[0]) % block
    a = jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
    return a.reshape((-1, block) + a.shape[1:])


def _selection(h, c_q, p, cfg, mm):
    """The indexer of a ``full`` layer: int32 [S, k], the positions each
    query attends, ``S`` (one past the last position) where it has fewer."""
    S = h.shape[0]
    j, di = cfg["index_n_heads"], cfg["index_head_dim"]
    k = min(cfg["index_topk"], S)
    q = _partly_rotary(mm(c_q, _f32(p["wq_b"])).reshape(S, j, di), cfg)
    key = mm(h, _f32(p["wk"]))
    key = (key - jnp.mean(key, -1, keepdims=True)) * lax.rsqrt(
        jnp.var(key, -1, keepdims=True) + 1e-6)
    key = _partly_rotary((key * _f32(p["k_ln"])
                          + _f32(p["k_ln_b"]))[:, None, :], cfg)[:, 0]
    weight = mm(h, _f32(p["w"])) * (j * di) ** -0.5
    block = min(Q_BLOCK, S)
    starts = jnp.arange(-(-S // block)) * block

    def one(args):
        qb, wb, start = args                     # [b, j, di], [b, j]
        s = jax.nn.relu(mm(qb.transpose(1, 0, 2), key.T))       # [j, b, S]
        score = jnp.sum(s * wb.T[:, :, None], axis=0)           # [b, S]
        rows = start + jnp.arange(block)[:, None]
        seen = jnp.arange(S)[None, :] <= rows
        got, idx = lax.top_k(jnp.where(seen, score, -jnp.inf), k)
        return jnp.where(got > -jnp.inf, idx, S)

    return lax.map(one, (_blocks(q, block), _blocks(weight, block),
                         starts)).reshape(-1, k)[:S]


def _attention(c_q, latent, k_rope, chosen, p, cfg, mm):
    """Plain-form attention of every head over the chosen positions only:
    c_q [S, q_lora_rank], latent [S, kv_lora_rank], k_rope [S, 1, rope],
    chosen int32 [S, k] -> [S, H * v_head_dim]."""
    S, H = c_q.shape[0], cfg["num_attention_heads"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    hb = min(HEADS_A_BLOCK, H)
    block = min(Q_BLOCK, S)
    starts = jnp.arange(-(-S // block)) * block
    scale = (nope + rope) ** -0.5
    q_b = p["q_b"].reshape(-1, H // hb, hb, nope + rope).transpose(1, 0, 2, 3)
    kv_b = p["kv_b"].reshape(-1, H // hb, hb, nope + vd).transpose(1, 0, 2, 3)
    picked = _blocks(chosen, block)

    def heads(args):
        wq, wkv = args                    # [ql, hb, nope+rope], [kl, hb, ..]
        q = mm(c_q, _f32(wq).reshape(wq.shape[0], -1)).reshape(
            S, hb, nope + rope)
        q = jnp.concatenate([q[..., :nope],
                             _rope(q[..., nope:], _theta(cfg))], -1)
        kv = mm(latent, _f32(wkv).reshape(wkv.shape[0], -1)).reshape(
            S, hb, nope + vd)
        k = jnp.concatenate([kv[..., :nope],
                             jnp.broadcast_to(k_rope, (S, hb, rope))], -1)
        kk, vv = k.transpose(1, 2, 0), kv[..., nope:].transpose(1, 0, 2)

        def one(args):
            qi, idx = args                # [b, hb, nope+rope], [b, k]
            mask = jnp.zeros((block, S + 1), bool).at[
                jnp.arange(block)[:, None], idx].set(True)[:, :S]
            s = mm(qi.transpose(1, 0, 2), kk) * scale          # [hb, b, S]
            s = jnp.where(mask[None], s, -jnp.inf)
            return mm(jax.nn.softmax(s, axis=-1), vv)          # [hb, b, vd]

        o = lax.map(one, (_blocks(q, block), picked))          # [n,hb,b,vd]
        return o.transpose(0, 2, 1, 3).reshape(-1, hb, vd)[:S]

    o = lax.map(heads, (q_b, kv_b))                            # [H/hb,S,hb,vd]
    return o.transpose(1, 0, 2, 3).reshape(S, H * vd)


def _experts(x, p, shared, cfg, mm):
    """Router over all the published experts; the held ones' terms and the
    shared expert's."""
    k, first = cfg["num_experts_per_tok"], cfg["first_held_expert"]
    score = jax.nn.sigmoid(mm(x, _f32(p["router"])))      # [S, every expert]
    _, chosen = lax.top_k(score + _f32(p["bias"]), k)     # bias: choice only
    weight = jnp.take_along_axis(score, chosen, -1)
    if cfg.get("norm_topk_prob", True):
        weight = weight / jnp.sum(weight, -1, keepdims=True)
    gate = jnp.zeros_like(score).at[
        jnp.arange(x.shape[0])[:, None], chosen].set(
            weight * cfg["routed_scaling_factor"])
    held = lax.dynamic_slice_in_dim(gate, first, p["w1"].shape[0], axis=1)

    def one(acc, expert):  # one expert widened at a time, over every token
        w1, w3, w2, g = expert
        return acc + g[:, None] * _swiglu(x, w1, w3, w2, mm), None

    routed, _ = lax.scan(one, jnp.zeros_like(x),
                         (p["w1"], p["w3"], p["w2"], held.T))
    return routed + _swiglu(x, shared["w1"], shared["w3"], shared["w2"], mm)


def _second_half(x, p, cfg, mm):
    """x + the layer's second half, in blocks of rows."""
    S = x.shape[0]
    block = min(ROW_BLOCK, S)

    def one(xb):
        h = _rms(xb, p["mlp_ln"], cfg["rms_norm_eps"])
        if "mlp" in p:
            return xb + _swiglu(h, p["mlp"]["w1"], p["mlp"]["w3"],
                                p["mlp"]["w2"], mm)
        return xb + _experts(h, p["moe"], p["shared"], cfg, mm)

    return lax.map(one, _blocks(x, block)).reshape(-1, x.shape[1])[:S]


def hidden_states(params, tokens, cfg: dict, compute: str = "f32"):
    """tokens [S] -> final normed hidden states [S, D] of one row."""
    mm, eps, kl = _mm(compute), cfg["rms_norm_eps"], cfg["kv_lora_rank"]
    x = _f32(params["tok_embed"][tokens])
    chosen = None
    for p in params["layers"]:
        h = _rms(x, p["ln"], eps)
        c_q = _rms(mm(h, _f32(p["q_a"])), p["q_ln"], eps)
        kv = mm(h, _f32(p["kv_a"]))               # what a cache would hold
        if "index" in p:                          # else: the layer below's
            chosen = _selection(h, c_q, p["index"], cfg, mm)
        o = _attention(c_q, _rms(kv[:, :kl], p["kv_ln"], eps),
                       _rope(kv[:, None, kl:], _theta(cfg)), chosen, p, cfg,
                       mm)
        x = _second_half(x + mm(o, _f32(p["o"])), p, cfg, mm)
    return _rms(x, params["final_ln"], eps)


def logits(params, tokens, cfg: dict, compute: str = "f32"):
    """tokens [S] -> logits [S, V] (float32) over the held slice of the
    vocabulary; the head's columns widened a block at a time."""
    x, mm = hidden_states(params, tokens, cfg, compute), _mm(compute)
    head = params["lm_head"]
    V = head.shape[1]
    n = next(n for n in range(1, V + 1)
             if V % n == 0 and V // n <= HEAD_BLOCK)
    width = V // n

    def one(i, out):
        cols = lax.dynamic_slice_in_dim(head, i * width, width, axis=1)
        return lax.dynamic_update_slice_in_dim(
            out, mm(x, _f32(cols)), i * width, axis=1)

    return lax.fori_loop(0, n, one, jnp.zeros((x.shape[0], V), jnp.float32))
