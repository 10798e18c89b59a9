"""The plain reference of the layer-pattern decoder (architecture
``nemotron_h``: Nemotron-H's layers, one mixer each under a pre-norm and a
residual, the kind read from ``hybrid_override_pattern``: ``M`` a Mamba-2
mixer, ``*`` attention, ``E`` experts in a latent space).

Straightforward ``jax.numpy``, one row at a time, no kernel, no cache, no
batching; float32 with every matmul at ``highest`` unless a lower ``compute``
is named, which is how the controls are made (``fp8``: operands of every
matmul, the router's among them, rounded to float8_e4m3; ``bf16``: operands
in bfloat16). **The recurrence is a sequential ``lax.scan`` over positions**
in Mamba's own layout (a head's state ``[head_dim, state]``, a head a row),
and **the experts are a plain loop over the experts held, every token
through each**: neither an algorithm nor a layout is shared with the
program's chunked scan, its update kernel on heads side by side, or its
sorted grouped matmuls. It imports nothing of the program and takes nothing
the program made: weights come from the seed by the recipe of
:func:`init_params`, which the configuration file states and which the
benchmark hands the program too
(``architectures/nemotron_h.py::init_program_params``).

A layer (keys as the published ``config.json`` has them), on ``u =
RMSNorm(x; layer_norm_epsilon)``, ``x <- x + mixer(u)``, no bias in any
projection:

  - ``M``: ``W_in u`` = ``z | xBC | dt`` (``d_inner`` = ``mamba_num_heads``
    x ``mamba_head_dim``; ``d_inner + 2 n_groups ssm_state_size``; heads);
    ``xBC`` through a causal depthwise convolution of ``conv_kernel`` taps
    with a bias, then SiLU; ``dt = softplus(dt + dt_bias)``, ``A =
    -exp(A_log)``; a head's ``h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x)
    B_t``, ``y_t = h_t C_t + D x_t``, ``B`` and ``C`` of a group shared by its
    heads; ``y * silu(z)`` under an RMSNorm over each group's channels;
    ``W_out``;
  - ``*``: ``q, k, v`` from ``u``; **no positional embedding**; causal
    softmax at ``head_dim ** -0.5``, a group of query heads sharing a K/V
    head; ``W_o``;
  - ``E``: scores ``sigmoid(u W_r)`` over **all** ``n_router_experts``
    (the published count); a token takes the ``num_experts_per_tok`` largest
    of score + ``e_score_correction_bias``, weighs them with the scores
    (without the bias) over their sum (``norm_topk_prob``) times
    ``routed_scaling_factor``; the routed experts read ``v = u W_down``
    (``moe_latent_size``), expert ``e`` is ``relu(v W1_e) ** 2 W2_e``, and
    the weighted sum goes back through ``W_up``; the shared expert,
    ``relu(u S1) ** 2 S2``, is added. **Of the routed experts only those held
    here are computed** (``n_routed_experts`` of them from
    ``first_held_expert``: one chip's share of the layer): what a token's
    other experts would have added is left out, here as in the program, and
    the partial sum is what goes on.

A final RMSNorm and an untied head over the held slice of the vocabulary.

Memory: weights stay in the configuration's type and are widened a matrix,
one expert or a block of the head's columns at a time; attention and the
shared expert run in blocks of rows; so a 4,096-token row fits beside 9.3 GB
of bf16 weights.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from chipbench.reference.hybrid_ssm import (HEAD_BLOCK, _f32, _matrix, _mm,
                                            _rms, _row_blocks)

# the recipe's numbers, stated in the configuration file (``assumed``)
ROUTER_GAIN_SIGMA = 0.5  # spread of the log of the router columns' gains
CONV_BIAS_SIGMA = 0.1
ROUTED_GAIN = 0.25       # on W_up: what one routed expert's term moves


def widths(cfg: dict):
    """(d_inner, the convolution's channels, the in-projection's width)."""
    inner = cfg["mamba_num_heads"] * cfg["mamba_head_dim"]
    conv = inner + 2 * cfg["n_groups"] * cfg["ssm_state_size"]
    return inner, conv, inner + conv + cfg["mamba_num_heads"]


@jax.jit
def _centred(w):
    """``w`` [..., fan_in, out] with the mean over ``fan_in`` taken off each
    column, in float32, back in ``w``'s type."""
    w32 = w.astype(jnp.float32)
    return (w32 - jnp.mean(w32, axis=-2, keepdims=True)).astype(w.dtype)


def init_params(key, cfg: dict, dtype=None):
    """Weights from ``key`` (``jax.random.PRNGKey(seed)``), one dict a layer
    of its kind: ``split(key, 2 + layers)`` gives the embedding's key, the
    head's, then one a layer; a layer's is split in 16, taken in the order
    written here. A matrix is normal * fan_in**-0.5, drawn in blocks of rows
    in float32 and rounded to ``dtype`` (``hybrid_ssm._matrix``); norm scales
    1; an embedding row normal * 1 (there is no multiplier to carry it).

    *The recurrence's own* by Mamba-2's convention, float32 whatever
    ``dtype``: ``A`` uniform in 1-16, ``dt`` log-uniform in
    ``time_step_min``-``time_step_max`` (``dt_bias`` its inverse softplus),
    ``D`` 1; the convolution's taps normal * taps**-0.5, its bias normal *
    ``CONV_BIAS_SIGMA`` (``use_conv_bias`` true: a bias of 0 would hide a
    program that dropped it).

    *The router and its choosing bias*, both from the seed, as
    ``reference/latent_moe.py``'s: the router's columns have uneven gains
    ``g = exp(ROUTER_GAIN_SIGMA * u)``, ``u`` uniform of unit variance, and
    the bias is the one that evens the load again, ``b = sigmoid(z) -
    sigmoid(z * g)`` with ``z`` the normal quantile of ``1 -
    experts_per_tok / experts``: not zero, so a router that chooses on the
    scores alone, or weighs with score + bias, is another model, and not so
    even that every expert sees the same count.

    *The routed experts* are independent matrices, each normal *
    fan_in**-0.5, and ``W_up`` carries ``ROUTED_GAIN``. Why: top-22 routing
    of 512 is discontinuous (the 22nd and 23rd scores lie 2% of their spread
    apart), a bf16 step chooses another expert than this float32 reference at
    such a near-tie in most tokens and layers, and with a share of the experts
    held such a choice moves a whole expert's term (5/22 of one expert's
    output) or nothing, whichever side of the share's edge the two lie on: no
    likeness between experts softens that (``reference/latent_moe.py``'s
    recipe for a layer that holds them all). The gain sets what one term
    moves beside the rest of the layer; a router that is wrong at every token
    moves all 5.5 of a token's held terms and reads five to ten times what
    the near-ties read (PERF.md, the output check, has the readings the gain
    and the limit were set from).

    *The second matrix of every squared-ReLU expert* (routed and shared) has
    its columns centred over the fan-in (:func:`_centred`): ``relu(.)**2``
    has a mean of its own (half its input's variance), which a random second
    matrix turns into one direction shared by every token; it grows from
    expert layer to expert layer, the router then prefers the experts that
    lie along it (the busiest of 512 took six times the mean in the fifth
    expert layer at a middle size, 2.4 times with centred columns: counts on
    the CPU, PR 35), and no trained model keeps such a direction."""
    pd = jnp.dtype(dtype or cfg["param_dtype"])
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    h, hkv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    heads, taps = cfg["mamba_num_heads"], cfg["conv_kernel"]
    inner, conv, proj = widths(cfg)
    z_, f, fs = (cfg["moe_latent_size"], cfg["moe_intermediate_size"],
                 cfg["moe_shared_expert_intermediate_size"])
    e_all, e = cfg["n_router_experts"], cfg["n_routed_experts"]
    pattern = cfg["hybrid_override_pattern"]
    keys = jax.random.split(key, 2 + len(pattern))
    z = jax.scipy.special.ndtri(1.0 - cfg["num_experts_per_tok"] / e_all)

    def w(k, shape, fan_in, gain=1.0):
        return _matrix(k, gain * fan_in ** -0.5, shape, pd)

    def mamba(k):
        dt = jnp.exp(jax.random.uniform(
            k[3], (heads,), jnp.float32,
            jnp.log(cfg.get("time_step_min", 1e-3)),
            jnp.log(cfg.get("time_step_max", 1e-1))))
        return {"ssm_in": w(k[0], (d, proj), d),
                "conv_w": w(k[1], (taps, conv), taps),
                "conv_b": w(k[5], (conv,), CONV_BIAS_SIGMA ** -2),
                "A_log": jnp.log(jax.random.uniform(
                    k[2], (heads,), jnp.float32, 1.0, 16.0)),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "D": jnp.ones((heads,), jnp.float32),
                "ssm_norm": jnp.ones((inner,), pd),
                "ssm_out": w(k[4], (inner, d), inner)}

    def attention(k):
        return {"wq": w(k[0], (d, h * dh), d), "wk": w(k[1], (d, hkv * dh), d),
                "wv": w(k[2], (d, hkv * dh), d),
                "wo": w(k[3], (h * dh, d), h * dh)}

    def experts(k):
        gain = jnp.exp(ROUTER_GAIN_SIGMA * jax.random.uniform(
            k[7], (e_all,), jnp.float32, -3 ** 0.5, 3 ** 0.5))
        return {"moe": {"router": w(k[0], (d, e_all), d, gain),
                        "bias": (jax.nn.sigmoid(z)
                                 - jax.nn.sigmoid(z * gain)).astype(pd),
                        "w1": w(k[1], (e, z_, f), z_),
                        "w2": _centred(w(k[2], (e, f, z_), f))},
                "down": w(k[3], (d, z_), d),
                "up": w(k[4], (z_, d), z_, ROUTED_GAIN),
                "shared": {"w1": w(k[5], (d, fs), d),
                           "w2": _centred(w(k[6], (fs, d), fs))}}

    make = {"M": mamba, "*": attention, "E": experts}
    return {"tok_embed": w(keys[0], (v, d), 1),
            "lm_head": w(keys[1], (d, v), d),
            "final_ln": jnp.ones((d,), pd),
            "layers": [dict(make[kind](jax.random.split(keys[2 + i], 16)),
                            ln=jnp.ones((d,), pd))
                       for i, kind in enumerate(pattern)]}


# ------------------------------------------------------------------- pieces
def _mamba(u, p, cfg, mm):
    S = u.shape[0]
    heads, dh = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    groups, n, taps = cfg["n_groups"], cfg["ssm_state_size"], \
        cfg["conv_kernel"]
    inner, _, _ = widths(cfg)
    proj = mm(u, _f32(p["ssm_in"]))
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
    y = y * lax.rsqrt(jnp.mean(jnp.square(y), -1, keepdims=True)
                      + cfg["layer_norm_epsilon"])
    return mm(y.reshape(S, inner) * _f32(p["ssm_norm"]), _f32(p["ssm_out"]))


def _attention(u, p, cfg, mm):
    S = u.shape[0]
    h, hkv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    q = mm(u, _f32(p["wq"])).reshape(S, h, dh)           # no position enters
    k = mm(u, _f32(p["wk"])).reshape(S, hkv, dh)
    v = mm(u, _f32(p["wv"])).reshape(S, hkv, dh)
    # a group of h // hkv query heads shares a K/V head
    kk = jnp.repeat(k, h // hkv, axis=1).transpose(1, 2, 0)      # [H, Dh, S]
    vv = jnp.repeat(v, h // hkv, axis=1).transpose(1, 0, 2)      # [H, S, Dh]
    at = jnp.arange(S)

    def rows(qi, row):                                   # [b, H, Dh], [b]
        s = mm(qi.transpose(1, 0, 2), kk) * dh ** -0.5   # [H, b, S]
        s = jnp.where(at[None, :] <= row[:, None], s, -jnp.inf)
        return mm(jax.nn.softmax(s, axis=-1), vv).transpose(1, 0, 2)

    return mm(_row_blocks(rows, q, at).reshape(S, h * dh), _f32(p["wo"]))


def _relu2(x, w1, w2, mm):
    return mm(jnp.square(jax.nn.relu(mm(x, _f32(w1)))), _f32(w2))


def routed_part(u, p, cfg, mm, first=None, weights=None):
    """[S, D] -> the part of an expert layer's result that the routed
    experts ``first`` .. ``first + len(weights[0]) - 1`` give (by default the
    file's share and the layer's own matrices), back in the hidden width."""
    moe = p["moe"]
    first = cfg.get("first_held_expert", 0) if first is None else first
    w1, w2 = weights or (moe["w1"], moe["w2"])
    score = jax.nn.sigmoid(mm(u, _f32(moe["router"])))   # [S, all experts]
    _, chosen = lax.top_k(score + _f32(moe["bias"]),
                          cfg["num_experts_per_tok"])    # bias: choice only
    weight = jnp.take_along_axis(score, chosen, -1)
    if cfg.get("norm_topk_prob", True):
        weight = weight / jnp.sum(weight, -1, keepdims=True)
    gate = jnp.zeros_like(score).at[
        jnp.arange(u.shape[0])[:, None], chosen].set(
            weight * cfg["routed_scaling_factor"])
    held = lax.dynamic_slice_in_dim(gate, first, w1.shape[0], axis=1)
    v = mm(u, _f32(p["down"]))

    def one(acc, expert):  # one expert widened at a time, over every token
        a, b, g = expert
        return acc + g[:, None] * _relu2(v, a, b, mm), None

    routed, _ = lax.scan(one, jnp.zeros_like(v), (w1, w2, held.T))
    return mm(routed, _f32(p["up"]))


def _experts(u, p, cfg, mm):
    shared = _row_blocks(lambda ub: _relu2(ub, p["shared"]["w1"],
                                           p["shared"]["w2"], mm), u)
    return routed_part(u, p, cfg, mm) + shared


MIXERS = {"M": _mamba, "*": _attention, "E": _experts}


def hidden_states(params, tokens, cfg: dict, compute: str = "f32"):
    """tokens [S] -> final normed hidden states [S, D] of one row."""
    mm, eps = _mm(compute), cfg["layer_norm_epsilon"]
    x = _f32(params["tok_embed"][tokens])
    for kind, p in zip(cfg["hybrid_override_pattern"], params["layers"]):
        x = x + MIXERS[kind](_rms(x, p["ln"], eps), p, cfg, mm)
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
        return lax.dynamic_update_slice_in_dim(out, mm(x, _f32(cols)),
                                               i * width, axis=1)

    return lax.fori_loop(0, n, one, jnp.zeros((x.shape[0], V), jnp.float32))
