"""The plain reference of the latent-attention, routed-expert decoder
(architecture ``latent_moe``: GLM-4.7-Flash's layer, the DeepSeek-V3
family's).

Straightforward ``jax.numpy``, one row at a time, no kernel, no cache, no
batching; float32 with every matmul at ``highest`` unless a lower ``compute``
is named, which is how the controls are made (``fp8``: operands of every
matmul, the router's among them, rounded to float8_e4m3; ``bf16``: operands
in bfloat16). It imports nothing of the program and takes nothing the program
made: weights come from the seed by the recipe of :func:`init_params`, which
the configuration file states and which the benchmark hands the program too
(``architectures/latent_moe.py::init_program_params``).

A layer (keys as the published ``config.json`` has them), with RMSNorm before
each half and a residual around it:

  - latent attention in its plain form, never absorbed: queries through a
    normed rank-``q_lora_rank`` bottleneck, each head split into
    ``qk_nope_head_dim`` columns without position and ``qk_rope_head_dim``
    rotary ones; one normed rank-``kv_lora_rank`` vector a position from
    which every head's keys (``qk_nope_head_dim``) and values
    (``v_head_dim``) are expanded, and one rotary key a position shared by
    all heads; scores scaled by (nope + rope) ** -0.5, causal softmax;
  - a SwiGLU MLP of ``intermediate_size`` in the first
    ``first_k_dense_replace`` layers; after them ``n_routed_experts`` SwiGLU
    experts of ``moe_intermediate_size``: scores sigmoid(h . W_r), a token
    takes the ``num_experts_per_tok`` largest of score + bias, weighs them
    with the scores (without the bias) over their sum (``norm_topk_prob``)
    times ``routed_scaling_factor``, and takes the shared expert beside
    them. No capacity: every token gets its experts.

Memory: weights stay in the configuration's type and are widened a block at a
time (one expert, one block of the head's columns); attention runs in blocks
of query rows; so a 5,632-token row fits beside 9 GB of bf16 weights.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

Q_BLOCK = 512
HEAD_BLOCK = 16384   # columns of the output head widened at a time
# the recipe's two numbers, stated in the configuration file (``assumed``)
EXPERT_SPREAD = 0.2  # how far a layer's routed experts lie apart
ROUTER_GAIN_SIGMA = 0.5  # spread of the log of the router columns' gains


def _layer_kinds(cfg: dict):
    dense = min(cfg["first_k_dense_replace"], cfg["num_hidden_layers"])
    return dense, cfg["num_hidden_layers"] - dense


def init_params(key, cfg: dict, dtype=None):
    """Weights from ``key`` (``jax.random.PRNGKey(seed)``), one dict a
    layer: ``split(key, 2 + layers)`` gives the embedding's key, the head's,
    then one a layer; a layer's is split in 16, taken in the order written
    here; a matrix is normal * fan_in**-0.5, norm scales 1.

    *The router and its choosing bias*, both from the seed. The router's
    columns have uneven gains, ``g = exp(ROUTER_GAIN_SIGMA * u)`` with ``u``
    uniform of unit variance (the seventh key), so on the scores alone the
    experts with the largest gains would take most tokens (the busiest about
    three times the mean). The bias is what training would make it, the
    one that evens the load:
    ``b = sigmoid(z) - sigmoid(z * g)`` with ``z`` the normal quantile of
    ``1 - experts_per_tok / experts``, so that every expert passes the
    common threshold equally often. It is about normal * 0.1 in size and it
    decides two of a token's four experts: a router that chooses on the
    scores alone, or weighs with score + bias, is another model.

    *The routed experts* of a layer are one matrix they share plus
    ``EXPERT_SPREAD`` of a matrix each ((shared + spread * own) * (fan_in *
    (1 + spread**2))**-0.5, the leaf's key split in two for the pair). Why:
    top-k routing is discontinuous; a bf16 step chooses another expert than
    this float32 reference wherever two scores lie within its rounding of
    each other (3 to 9% of all tokens, a layer), and with independent
    experts the widest gap of the served tokens then reads what the fp8
    control reads. The spread sets how much one such choice moves the
    logits; a fault that sends every token to wrong experts moves them
    about three times as far (PERF.md, the output check, has the readings
    the spread and the limit were set from)."""
    pd = jnp.dtype(dtype or cfg["param_dtype"])
    d, h, v = cfg["hidden_size"], cfg["num_attention_heads"], \
        cfg["vocab_size"]
    ql, kl = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    f, fe = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    e, fs = cfg["n_routed_experts"], cfg["n_shared_experts"] * fe
    n = cfg["num_hidden_layers"]
    dense, _ = _layer_kinds(cfg)
    keys = jax.random.split(key, 2 + n)
    z = jax.scipy.special.ndtri(1.0 - cfg["num_experts_per_tok"] / e)

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
        if i < dense:
            out["mlp"] = {"w1": w(k[5], (d, f), d), "w3": w(k[6], (d, f), d),
                          "w2": w(k[7], (f, d), f)}
        else:
            gain = jnp.exp(ROUTER_GAIN_SIGMA * jax.random.uniform(
                k[6], (e,), jnp.float32, -3 ** 0.5, 3 ** 0.5))
            out["moe"] = {
                "router": (jax.random.normal(k[5], (d, e), jnp.float32)
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


def _f32(a):
    return a.astype(jnp.float32)


def _rms(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                         + eps) * _f32(scale)


def _rope(x, theta):
    """x [S, H, R]: rotate (first half, second half) pairs by position."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def _causal(q, k, v, scale, mm):
    """q, k [S, H, Dq], v [S, H, Dv] -> [S, H * Dv], in blocks of query
    positions so that the [H, S, S] scores never exist at once."""
    S, H, _ = q.shape
    kk, vv = k.transpose(1, 2, 0), v.transpose(1, 0, 2)  # [H,Dq,S] [H,S,Dv]
    block = min(Q_BLOCK, S)
    pad = (-S) % block
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        -1, block, H, q.shape[-1]).transpose(0, 2, 1, 3)   # [n, H, b, Dq]
    starts = jnp.arange(qb.shape[0]) * block

    def one(args):
        qi, start = args
        s = mm(qi, kk) * scale                             # [H, b, S]
        rows = start + jnp.arange(block)[:, None]
        s = jnp.where(jnp.arange(S)[None, :] <= rows, s, -jnp.inf)
        return mm(jax.nn.softmax(s, axis=-1), vv)          # [H, b, Dv]

    o = lax.map(one, (qb, starts))                         # [n, H, b, Dv]
    return o.transpose(0, 2, 1, 3).reshape(-1, H * v.shape[-1])[:S]


def _attention(x, p, cfg, mm):
    S = x.shape[0]
    h, kl = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    q = mm(_rms(mm(x, _f32(p["q_a"])), p["q_ln"], eps),
           _f32(p["q_b"])).reshape(S, h, nope + rope)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], theta)], -1)
    kv = mm(x, _f32(p["kv_a"]))               # what a cache would hold
    latent = _rms(kv[:, :kl], p["kv_ln"], eps)
    k_rope = _rope(kv[:, None, kl:], theta)   # one rotary key for all heads
    kvb = mm(latent, _f32(p["kv_b"])).reshape(S, h, nope + vd)
    k = jnp.concatenate([kvb[..., :nope],
                         jnp.broadcast_to(k_rope, (S, h, rope))], -1)
    o = _causal(q, k, kvb[..., nope:], (nope + rope) ** -0.5, mm)
    return mm(o, _f32(p["o"]))


def _swiglu(x, w1, w3, w2, mm):
    return mm(jax.nn.silu(mm(x, _f32(w1))) * mm(x, _f32(w3)), _f32(w2))


def _experts(x, p, shared, cfg, mm):
    k = cfg["num_experts_per_tok"]
    score = jax.nn.sigmoid(mm(x, _f32(p["router"])))       # [S, E]
    _, chosen = lax.top_k(score + _f32(p["bias"]), k)      # bias: choice only
    weight = jnp.take_along_axis(score, chosen, -1)
    if cfg.get("norm_topk_prob", True):
        weight = weight / jnp.sum(weight, -1, keepdims=True)
    gate = jnp.zeros_like(score).at[
        jnp.arange(x.shape[0])[:, None], chosen].set(
            weight * cfg["routed_scaling_factor"])

    def one(acc, expert):  # one expert widened at a time, over every token
        w1, w3, w2, g = expert
        return acc + g[:, None] * _swiglu(x, w1, w3, w2, mm), None

    routed, _ = lax.scan(one, jnp.zeros_like(x),
                         (p["w1"], p["w3"], p["w2"], gate.T))
    return routed + _swiglu(x, shared["w1"], shared["w3"], shared["w2"], mm)


def hidden_states(params, tokens, cfg: dict, compute: str = "f32"):
    """tokens [S] -> final normed hidden states [S, D] of one row."""
    mm, eps = _mm(compute), cfg["rms_norm_eps"]
    x = _f32(params["tok_embed"][tokens])
    for p in params["layers"]:
        x = x + _attention(_rms(x, p["ln"], eps), p, cfg, mm)
        h = _rms(x, p["mlp_ln"], eps)
        if "mlp" in p:
            x = x + _swiglu(h, p["mlp"]["w1"], p["mlp"]["w3"],
                            p["mlp"]["w2"], mm)
        else:
            x = x + _experts(h, p["moe"], p["shared"], cfg, mm)
    return _rms(x, params["final_ln"], eps)


def logits(params, tokens, cfg: dict, compute: str = "f32"):
    """tokens [S] -> logits [S, V] (float32); the head's columns widened a
    block at a time."""
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
