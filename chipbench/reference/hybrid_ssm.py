"""The plain reference of the hybrid state-space decoder (architecture
``hybrid_ssm``: Falcon-H1's layer, attention heads and a Mamba-2 mixer side
by side on one normed input).

Straightforward ``jax.numpy``, one row at a time, no kernel, no cache, no
batching; float32 with every matmul at ``highest`` unless a lower ``compute``
is named, which is how the controls are made (``fp8``: operands of every
matmul rounded to float8_e4m3; ``bf16``: operands in bfloat16). **The
recurrence is a sequential ``lax.scan`` over positions**, in Mamba's own
layout (a head's state ``[head_dim, state]``), so that it shares neither an
algorithm nor a layout with the program's chunked scan and its decode kernel.
It imports nothing of the program and takes nothing the program made:
weights come from the seed by the recipe of :func:`init_params`, which the
configuration file states and which the benchmark hands the program too
(``architectures/hybrid_ssm.py::init_program_params``).

A layer (keys as the published ``config.json`` has them), on
``u = RMSNorm(x)``:

  - attention: ``q = W_q(u * attention_in_multiplier)``, ``k = W_k(.) *
    key_multiplier``, ``v = W_v(.)``; RoPE over the whole head
    (``rope_theta``), (first half, second half) pairs; causal softmax at
    ``head_dim ** -0.5``, a group of query heads sharing a K/V head;
    ``a = W_o(.) * attention_out_multiplier``;
  - state space: ``W_in(u * ssm_in_multiplier)`` = ``z | x | B | C | dt``,
    each segment times its entry of ``ssm_multipliers``; ``x | B | C`` through
    a causal depthwise convolution of ``mamba_d_conv`` taps with a bias, then
    SiLU; ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``; a head's
    ``h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t``, ``y_t = h_t C_t + D
    x_t``, ``B`` and ``C`` of a group shared by its heads; ``y * silu(z)``
    under an RMSNorm over each group's channels (``mamba_rms_norm`` true,
    ``mamba_norm_before_gate`` false); ``s = W_out(.) * ssm_out_multiplier``;
  - ``x <- x + a + s``; then on ``v = RMSNorm(x)``: ``x <- x + W_down(W_up v
    * silu(W_gate v * mlp_multipliers[0])) * mlp_multipliers[1]``.

Embedding rows times ``embedding_multiplier``; logits ``W_head RMSNorm(x) *
lm_head_multiplier``.

Memory: weights stay in the configuration's type and are widened a matrix or
a block of the head's columns at a time; the MLP and the attention run in
blocks of rows; so a 4,096-token row fits beside 9.65 GB of bf16 weights.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

ROW_BLOCK = 512      # rows of the attention's queries and of the MLP at a time
HEAD_BLOCK = 16384   # columns of the output head widened at a time
WEIGHT_BLOCKS = 16   # row blocks a matrix is drawn in (where they divide it)
CONV_BIAS_SIGMA = 0.1


# ------------------------------------------------------------------ weights
@functools.partial(jax.jit, static_argnames=("shape", "dtype"))
def _matrix(key, scale, shape, dtype):
    """normal * scale (a scalar, or one value a column), drawn in
    ``WEIGHT_BLOCKS`` blocks of rows (block ``i`` from ``fold_in(key, i)``) in
    float32 and rounded to ``dtype``: a 2.7 GB embedding is then made beside
    a sixteenth of its float32 form, not beside all of it."""
    n = WEIGHT_BLOCKS if shape[0] % WEIGHT_BLOCKS == 0 else 1
    rows = shape[0] // n

    def one(i, out):
        block = jax.random.normal(jax.random.fold_in(key, i),
                                  (rows,) + shape[1:], jnp.float32) * scale
        return lax.dynamic_update_slice_in_dim(out, block.astype(dtype),
                                               i * rows, 0)

    return lax.fori_loop(0, n, one, jnp.zeros(shape, dtype))


def _segments(cfg: dict):
    """Widths of ``z | x | B | C | dt`` in the input projection."""
    gn = cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    return (cfg["mamba_d_ssm"], cfg["mamba_d_ssm"], gn, gn,
            cfg["mamba_n_heads"])


def _segment_multipliers(cfg: dict):
    return jnp.concatenate([jnp.full((n,), m, jnp.float32) for n, m in zip(
        _segments(cfg), cfg["ssm_multipliers"])])


def init_params(key, cfg: dict, dtype=None):
    """Weights from ``key`` (``jax.random.PRNGKey(seed)``), one dict a
    layer: ``split(key, 2 + layers)`` gives the embedding's key, the head's,
    then one a layer; a layer's is split in 16, taken in the order written
    here.

    *A matrix* is normal * fan_in**-0.5 **over the multipliers that follow
    it**, drawn by :func:`_matrix`. The published multipliers are muP's: they
    belong to trained weights whose scales they undo, and with plain
    fan_in**-0.5 weights they would flatten the model (keys times 0.011:
    attention uniform; logits times 0.0078: every margin inside bf16's
    rounding). Dividing each matrix by what multiplies its input and its
    output gives what training arrives at: every projection's output has unit
    variance, scores and logits are of order one, and each multiplier is
    still applied where the layer applies it, so one left out, or applied to
    the wrong segment, moves the logits by its whole factor.

    *The recurrence's own* by Mamba-2's convention, float32 whatever
    ``dtype``: ``A`` uniform in 1-16 (``A_log`` its log), ``dt`` log-uniform
    in 0.001-0.1 (``dt_bias`` its inverse softplus), ``D`` 1: a head's state
    then forgets over a few positions (dt A = 1.6) to thousands (0.001). Norm
    scales 1; the convolution's taps normal * taps**-0.5, its bias normal *
    ``CONV_BIAS_SIGMA`` (``mamba_conv_bias`` true: a bias of 0 would hide a
    program that dropped it)."""
    pd = jnp.dtype(dtype or cfg["param_dtype"])
    d, v, f = cfg["hidden_size"], cfg["vocab_size"], cfg["intermediate_size"]
    h, hkv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    inner, heads, taps = (cfg["mamba_d_ssm"], cfg["mamba_n_heads"],
                          cfg["mamba_d_conv"])
    conv = inner + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    n = cfg["num_hidden_layers"]
    keys = jax.random.split(key, 2 + n)

    def w(k, shape, fan_in, *followed_by):
        scale = fan_in ** -0.5
        for m in followed_by:
            scale = scale / m
        return _matrix(k, scale, shape, pd)

    def layer(k):
        k = jax.random.split(k, 16)
        dt = jnp.exp(jax.random.uniform(k[7], (heads,), jnp.float32,
                                        jnp.log(1e-3), jnp.log(1e-1)))
        return {
            "ln": jnp.ones((d,), pd), "mlp_ln": jnp.ones((d,), pd),
            "wq": w(k[0], (d, h * dh), d, cfg["attention_in_multiplier"]),
            "wk": w(k[1], (d, hkv * dh), d, cfg["attention_in_multiplier"],
                    cfg["key_multiplier"]),
            "wv": w(k[2], (d, hkv * dh), d, cfg["attention_in_multiplier"]),
            "wo": w(k[3], (h * dh, d), h * dh,
                    cfg["attention_out_multiplier"]),
            "ssm_in": w(k[4], (d, sum(_segments(cfg))), d,
                        cfg["ssm_in_multiplier"], _segment_multipliers(cfg)),
            "conv_w": w(k[5], (taps, conv), taps),
            "conv_b": w(k[12], (conv,), CONV_BIAS_SIGMA ** -2),
            "A_log": jnp.log(jax.random.uniform(k[6], (heads,), jnp.float32,
                                                1.0, 16.0)),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "D": jnp.ones((heads,), jnp.float32),
            "ssm_norm": jnp.ones((inner,), pd),
            "ssm_out": w(k[8], (inner, d), inner, cfg["ssm_out_multiplier"]),
            "w_gate": w(k[9], (d, f), d, cfg["mlp_multipliers"][0]),
            "w_up": w(k[10], (d, f), d),
            "w_down": w(k[11], (f, d), f, cfg["mlp_multipliers"][1]),
        }

    return {"tok_embed": w(keys[0], (v, d), 1, cfg["embedding_multiplier"]),
            "lm_head": w(keys[1], (d, v), d, cfg["lm_head_multiplier"]),
            "final_ln": jnp.ones((d,), pd),
            "layers": [layer(keys[2 + i]) for i in range(n)]}


# ------------------------------------------------------------------- pieces
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
    freqs = float(theta) ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def _row_blocks(fn, *xs):
    """``fn`` over the rows of ``xs`` ([S, ...] each) in blocks of
    ``ROW_BLOCK`` where they divide S, so that what ``fn`` makes of a block
    never exists for all rows at once."""
    S = xs[0].shape[0]
    if S <= ROW_BLOCK or S % ROW_BLOCK:
        return fn(*xs)
    out = lax.map(lambda b: fn(*b), tuple(
        x.reshape((-1, ROW_BLOCK) + x.shape[1:]) for x in xs))
    return out.reshape((S,) + out.shape[2:])


def _attention(u, p, cfg, mm):
    S = u.shape[0]
    h, hkv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    u = u * cfg["attention_in_multiplier"]
    q = _rope(mm(u, _f32(p["wq"])).reshape(S, h, dh), cfg["rope_theta"])
    k = _rope((mm(u, _f32(p["wk"])) * cfg["key_multiplier"]).reshape(
        S, hkv, dh), cfg["rope_theta"])
    v = mm(u, _f32(p["wv"])).reshape(S, hkv, dh)
    # a group of h // hkv query heads shares a K/V head
    kk = jnp.repeat(k, h // hkv, axis=1).transpose(1, 2, 0)      # [H, Dh, S]
    vv = jnp.repeat(v, h // hkv, axis=1).transpose(1, 0, 2)      # [H, S, Dh]
    at = jnp.arange(S)

    def rows(qi, row):                                   # [b, H, Dh], [b]
        s = mm(qi.transpose(1, 0, 2), kk) * dh ** -0.5   # [H, b, S]
        s = jnp.where(at[None, :] <= row[:, None], s, -jnp.inf)
        return mm(jax.nn.softmax(s, axis=-1), vv).transpose(1, 0, 2)

    o = _row_blocks(rows, q, at)
    return mm(o.reshape(S, h * dh), _f32(p["wo"])) \
        * cfg["attention_out_multiplier"]


def _state_space(u, p, cfg, mm):
    S = u.shape[0]
    inner, heads, dh = (cfg["mamba_d_ssm"], cfg["mamba_n_heads"],
                        cfg["mamba_d_head"])
    groups, n, taps = (cfg["mamba_n_groups"], cfg["mamba_d_state"],
                       cfg["mamba_d_conv"])
    eps = cfg["rms_norm_eps"]
    proj = mm(u * cfg["ssm_in_multiplier"], _f32(p["ssm_in"])) \
        * _segment_multipliers(cfg)
    z, xbc, dt = jnp.split(proj, [inner, proj.shape[1] - heads], axis=1)
    # causal, depthwise: position t sees inputs t - taps + 1 .. t
    behind = jnp.pad(xbc, ((taps - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(behind[i:i + S] * _f32(p["conv_w"])[i]
                          for i in range(taps)) + _f32(p["conv_b"]))
    x = xbc[:, :inner].reshape(S, heads, dh)
    b, c = (a.reshape(S, groups, n) for a in jnp.split(
        xbc[:, inner:], 2, axis=1))
    dt = jax.nn.softplus(dt + p["dt_bias"])              # [S, heads]
    a_rate = -jnp.exp(p["A_log"])

    def step(h, inp):                                    # h [heads, dh, n]
        x_t, b_t, c_t, dt_t = inp
        b_t, c_t = (jnp.repeat(a, heads // groups, axis=0)
                    for a in (b_t, c_t))                 # a head's group's
        h = jnp.exp(dt_t * a_rate)[:, None, None] * h \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return h, jnp.sum(h * c_t[:, None, :], -1) + p["D"][:, None] * x_t

    _, y = lax.scan(step, jnp.zeros((heads, dh, n), jnp.float32),
                    (x, b, c, dt))
    y = (y.reshape(S, inner) * jax.nn.silu(z)).reshape(S, groups, -1)
    y = y * lax.rsqrt(jnp.mean(jnp.square(y), -1, keepdims=True) + eps)
    return mm(y.reshape(S, inner) * _f32(p["ssm_norm"]),
              _f32(p["ssm_out"])) * cfg["ssm_out_multiplier"]


def _mlp(v, p, cfg, mm):
    def rows(vb):
        gate = jax.nn.silu(mm(vb, _f32(p["w_gate"]))
                           * cfg["mlp_multipliers"][0])
        return mm(mm(vb, _f32(p["w_up"])) * gate, _f32(p["w_down"]))

    return _row_blocks(rows, v) * cfg["mlp_multipliers"][1]


def hidden_states(params, tokens, cfg: dict, compute: str = "f32"):
    """tokens [S] -> final normed hidden states [S, D] of one row."""
    mm, eps = _mm(compute), cfg["rms_norm_eps"]
    x = _f32(params["tok_embed"][tokens]) * cfg["embedding_multiplier"]
    for p in params["layers"]:
        u = _rms(x, p["ln"], eps)
        x = x + _attention(u, p, cfg, mm) + _state_space(u, p, cfg, mm)
        x = x + _mlp(_rms(x, p["mlp_ln"], eps), p, cfg, mm)
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
            out, mm(x, _f32(cols)) * cfg["lm_head_multiplier"], i * width,
            axis=1)

    return lax.fori_loop(0, n, one, jnp.zeros((x.shape[0], V), jnp.float32))
