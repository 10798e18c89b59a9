"""NemotronHLM: a decoder whose layers are of three kinds in a published
order (the Nemotron-H family's ``hybrid_override_pattern``), on the serve
path. Every layer is one mixer under a pre-norm and a residual,
``x <- x + mixer(RMSNorm(x))``, the kind read from the pattern's character:

  - ``M``, *Mamba-2*: one projection into ``z | x | B | C | dt``, a causal
    depthwise convolution of ``ssm_conv`` taps and a SiLU over ``x | B | C``,
    ``dt = softplus(dt + dt_bias)``, the recurrence of ops/ssm.py, a gate
    ``y * silu(z)`` under a grouped RMSNorm, the output projection:
    models/hybrid_ssm.py's mixer, whose pieces run here as they are (this
    family publishes no multipliers: each reads 1). **What a slot holds** is
    the recurrence's ``h`` (float32, heads of 64 channels two to a row of
    lanes: ops/ssm.py says why) and the convolution's last inputs;
  - ``*``, *attention*: grouped-query attention **with no positional
    embedding** (the family's report: none is used; the recurrent layers
    carry the order). **What a token leaves in the pages is K and V**, of
    the attention layers only;
  - ``E``, *experts in a latent space*: the router reads the hidden vector
    (ops/moe.py::route_sigmoid_top_k over all ``n_routed_experts``); the
    routed experts read ``u W_down`` (``moe_latent`` wide), are two matrices
    around a squared ReLU, and their weighted sum goes back through ``W_up``;
    one shared expert of the same form on the hidden vector is added. **This
    chip holds a share of a layer's experts** (``n_held_experts`` from
    ``first_held_expert``; ops/moe.py::grouped_experts): an assignment to an
    expert that lives elsewhere is computed nowhere and adds nothing, and the
    partial sum is what goes on to the next layer. Nothing stands in for the
    other chips or for the exchange with them.

A final RMSNorm and an untied head. Parameters are one dict a layer, in the
pattern's order (an expert layer's weights go to the grouped matmul as they
lie; a slice of a stack would be copied), and the pattern is walked when the
program is traced.

**The specifications are by kind**: ``cache_spec`` has as many layers as the
pattern has ``*``, ``state_spec`` as many as it has ``M``; an expert layer
holds nothing a request leaves behind. Attention layer ``j`` and Mamba layer
``j`` (counted among their own kind) are entry ``j`` of their arrays.

**A prompt rides the decode step in chunks** (:func:`mixed_step`, which the
engine finds by name: serve/llm.py): one chunk of one row's prompt and one
decode token of every live row in one walk over the pattern, so the weights
(the held experts' among them) are read once for both.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..ops import moe, ssm
from ..ops.flash_attention import flash_attention
from ..ops.paged_attention import paged_attention
from .hybrid_ssm import (_carried, _conv, _gate_out, _kernel_use, _mm, _mm32,
                         _split_xbc, _ssm_project, _write_kv, _write_rows,
                         prefill_takes_kernel)  # noqa: F401 (the engine's)
from .latent_moe import _rmsnorm

KINDS = "ME*"


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    vocab_size: int
    d_model: int
    pattern: str                   # a character a layer: M, E or *
    n_heads: int
    kv_heads: int
    head_dim: int
    ssm_heads: int
    ssm_head_dim: int
    ssm_state: int
    ssm_groups: int
    moe_latent: int                # what a routed expert reads and writes
    moe_d_ff: int                  # a routed expert's width
    shared_d_ff: int               # the shared expert's
    n_routed_experts: int          # the router's width: every chip's experts
    n_held_experts: int            # those that live here
    experts_per_tok: int
    routed_scaling_factor: float
    first_held_expert: int = 0
    norm_topk_prob: bool = True
    ssm_conv: int = 4
    max_seq: int = 2048
    rms_norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16      # activations, K and V, the conv tail
    param_dtype: Any = jnp.bfloat16
    # what models/hybrid_ssm.py's mixer pieces multiply by; not published
    # for this family, so every one is 1
    ssm_in_multiplier: float = 1.0
    ssm_out_multiplier: float = 1.0
    ssm_multipliers: Tuple[float, ...] = (1.0,) * 5

    def __post_init__(self):
        if set(self.pattern) - set(KINDS):
            raise ValueError(f"a layer is one of {KINDS!r}: {self.pattern!r}")
        if self.first_held_expert + self.n_held_experts \
                > self.n_routed_experts:
            raise ValueError("the held experts are not among the routed")

    @property
    def n_layers(self) -> int:
        return len(self.pattern)

    def count(self, kind: str) -> int:
        return self.pattern.count(kind)

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

    @property
    def state_pack(self) -> int:
        """Heads side by side in a row of the resident state."""
        return ssm.heads_a_row(self.ssm_head_dim)


# ------------------------------------------------------------------ weights
def init_params(key, cfg: NemotronHConfig) -> Dict[str, Any]:
    """One dict a layer, of its kind: ``split(key, 2 + n_layers)`` gives the
    embedding's key, the head's, then one a layer, split in 16; a matrix is
    normal * fan_in**-0.5, norm scales 1, the convolution's and the router's
    choosing bias 0. ``A_log``, ``dt_bias`` and ``D`` are float32 whatever
    ``param_dtype`` (``A`` uniform in 1-16, ``dt`` log-uniform in 0.001-0.1,
    ``D`` 1, as models/hybrid_ssm.py). Weights made elsewhere with this tree
    go to ``LLMServer(init=...)``."""
    pd = cfg.param_dtype
    D, H, Hkv, Dh = cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.head_dim
    E, Z, F = cfg.n_held_experts, cfg.moe_latent, cfg.moe_d_ff
    keys = jax.random.split(key, 2 + cfg.n_layers)

    def w(k, shape, fan_in):
        return jax.random.normal(k, shape, pd) * (fan_in ** -0.5)

    def mamba(k):
        dt = jnp.exp(jax.random.uniform(
            k[3], (cfg.ssm_heads,), jnp.float32, jnp.log(1e-3),
            jnp.log(1e-1)))
        return {"ssm_in": w(k[0], (D, cfg.ssm_proj_width), D),
                "conv_w": w(k[1], (cfg.ssm_conv, cfg.conv_width),
                            cfg.ssm_conv),
                "conv_b": jnp.zeros((cfg.conv_width,), pd),
                "A_log": jnp.log(jax.random.uniform(
                    k[2], (cfg.ssm_heads,), jnp.float32, 1.0, 16.0)),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "D": jnp.ones((cfg.ssm_heads,), jnp.float32),
                "ssm_norm": jnp.ones((cfg.ssm_inner,), pd),
                "ssm_out": w(k[4], (cfg.ssm_inner, D), cfg.ssm_inner)}

    def attention(k):
        return {"wq": w(k[0], (D, H * Dh), D), "wk": w(k[1], (D, Hkv * Dh), D),
                "wv": w(k[2], (D, Hkv * Dh), D),
                "wo": w(k[3], (H * Dh, D), H * Dh)}

    def experts(k):
        return {"moe": {"router": w(k[0], (D, cfg.n_routed_experts), D),
                        "bias": jnp.zeros((cfg.n_routed_experts,), pd),
                        "w1": w(k[1], (E, Z, F), Z),
                        "w2": w(k[2], (E, F, Z), F)},
                "down": w(k[3], (D, Z), D), "up": w(k[4], (Z, D), Z),
                "shared": {"w1": w(k[5], (D, cfg.shared_d_ff), D),
                           "w2": w(k[6], (cfg.shared_d_ff, D),
                                   cfg.shared_d_ff)}}

    make = {"M": mamba, "*": attention, "E": experts}
    return {"tok_embed": w(keys[0], (cfg.vocab_size, D), D),
            "lm_head": w(keys[1], (D, cfg.vocab_size), D),
            "final_ln": jnp.ones((D,), pd),
            "layers": [dict(make[kind](jax.random.split(keys[2 + i], 16)),
                            ln=jnp.ones((D,), pd))
                       for i, kind in enumerate(cfg.pattern)]}


# ------------------------------------------------------------------- pieces
def _qkv(u, p, cfg: NemotronHConfig):
    """u [T, D] -> q [T, H, Dh], k and v [T, Hkv, Dh]; no position enters."""
    T = u.shape[0]
    return (_mm(u, p["wq"], cfg).reshape(T, cfg.n_heads, cfg.head_dim),
            _mm(u, p["wk"], cfg).reshape(T, cfg.kv_heads, cfg.head_dim),
            _mm(u, p["wv"], cfg).reshape(T, cfg.kv_heads, cfg.head_dim))


def _relu2(x, p, cfg: NemotronHConfig):
    """Two matrices around a squared ReLU."""
    return _mm(jnp.square(jax.nn.relu(_mm32(x, p["w1"], cfg))).astype(
        cfg.dtype), p["w2"], cfg)


def _experts(u, p, cfg: NemotronHConfig, live=None):
    """The expert layer on u = norm(x): [T, D] -> (y [T, D], expert_tokens
    [n_held_experts]: the live rows' assignments to the experts held here)."""
    chosen, w = moe.route_sigmoid_top_k(
        u, p["moe"]["router"], p["moe"]["bias"], cfg.experts_per_tok,
        cfg.routed_scaling_factor, cfg.norm_topk_prob)
    with jax.named_scope("latent_down"):
        v = _mm(u, p["down"], cfg)
    r, counts = moe.grouped_experts(v, chosen, w, p["moe"], live,
                                    cfg.first_held_expert)
    with jax.named_scope("latent_up"):
        y = _mm(r, p["up"], cfg)
    with jax.named_scope("moe_shared"):
        y = y + _relu2(u, p["shared"], cfg)
    return y, counts


def _head(x, params, cfg: NemotronHConfig):
    x = _rmsnorm(x, params["final_ln"], cfg.rms_norm_eps)
    return _mm32(x, params["lm_head"], cfg)


# ------------------------------------------------------------- whole forward
def _forward_row(params, tokens, cfg: NemotronHConfig, true_len, use: str):
    """tokens [S], of which the first ``true_len`` are real -> (hidden
    [S, D] before the final norm, K and V [attention layers, Hkv, S, Dh], the
    recurrence's state [Mamba layers, heads, state, head_dim] float32 and the
    convolution's tail [Mamba layers, ssm_conv - 1, conv_width], both as of
    position ``true_len - 1``)."""
    S, rep, tail = tokens.shape[0], cfg.n_heads // cfg.kv_heads, \
        cfg.ssm_conv - 1
    x = params["tok_embed"][tokens].astype(cfg.dtype)
    real = jnp.arange(S) < true_len
    ks, vs, hs, tails = [], [], [], []
    for kind, p in zip(cfg.pattern, params["layers"]):
        u = _rmsnorm(x, p["ln"], cfg.rms_norm_eps)
        if kind == "M":
            z, xbc, dt = _ssm_project(u, p, cfg)
            with jax.named_scope("ssm_conv"):
                behind = jnp.pad(xbc, ((tail, 0), (0, 0)))   # zeros before 0
                xs, b, c = _split_xbc(_conv(
                    [behind[i:i + S] for i in range(cfg.ssm_conv)], p, cfg),
                    cfg)
            with jax.named_scope("ssm_scan"):
                y, h = ssm.ssd_scan(xs, dt, -jnp.exp(p["A_log"]), b, c,
                                    p["D"], true_len=true_len)
            with jax.named_scope("state_write"):
                # the last real inputs: rows true_len - tail .. true_len - 1
                tails.append(lax.dynamic_slice_in_dim(behind, true_len, tail))
                hs.append(h)
            x = x + _gate_out(y, z, p, cfg)
        elif kind == "*":
            q, k, v = _qkv(u, p, cfg)
            kt, vt = k.transpose(1, 0, 2), v.transpose(1, 0, 2)  # [Hkv,S,Dh]
            with jax.named_scope("prefill_attention"):
                # the kernel wants as many K/V heads as query heads
                o = flash_attention(
                    q.transpose(1, 0, 2)[None],
                    jnp.repeat(kt, rep, axis=0)[None],
                    jnp.repeat(vt, rep, axis=0)[None], causal=True,
                    use_pallas=use)[0].transpose(1, 0, 2)
            ks.append(kt), vs.append(vt)
            x = x + _mm(o.reshape(S, -1), p["wo"], cfg)
        else:
            # a bucket's padding is routed nowhere: its rows sort past every
            # group and cost the grouped matmuls nothing
            x = x + _experts(u, p, cfg, real)[0]
    return x, jnp.stack(ks), jnp.stack(vs), jnp.stack(hs), jnp.stack(tails)


def forward(params, tokens, cfg: NemotronHConfig):
    """tokens [B, S] -> logits [B, S, V] (fp32), without a cache; a row at
    a time."""
    return lax.map(lambda t: _head(_forward_row(
        params, t, cfg, t.shape[0], _kernel_use(cfg, t.shape[0]))[0], params,
        cfg), tokens)


# --------------------------------------------------- what the engine asks for
def cache_spec(cfg: NemotronHConfig) -> Dict[str, Tuple]:
    """What a token leaves in the cache, as the page pool lays it out: name
    -> (dims before the pages, dims after a page's positions, dtype). K and
    V of the attention layers alone, each [attention layers, Hkv, pages,
    page_tokens, Dh]."""
    one = ((cfg.count("*"), cfg.kv_heads), (cfg.head_dim,), cfg.dtype)
    return {"k": one, "v": one}


def state_spec(cfg: NemotronHConfig) -> Dict[str, Tuple]:
    """What a slot holds whatever its length: name -> (dims before the
    slots, dims after, dtype), of the Mamba layers alone. ``ssm``: the
    recurrence's state, float32 [Mamba layers, slots, heads / k, state,
    k x head_dim] with ``k`` heads side by side a row (ops/ssm.py::
    pack_heads); ``conv``: the convolution's last inputs, [Mamba layers,
    ssm_conv - 1, slots, conv_width]."""
    k = cfg.state_pack
    return {"ssm": ((cfg.count("M"),),
                    (cfg.ssm_heads // k, cfg.ssm_state, k * cfg.ssm_head_dim),
                    jnp.float32),
            "conv": ((cfg.count("M"), cfg.ssm_conv - 1), (cfg.conv_width,),
                     cfg.dtype)}


def prefill_row(params, tokens, cfg: NemotronHConfig, n_positions: int,
                true_len):
    """Prefill one row: tokens [1, S], of which the first ``true_len`` are
    the prompt -> (logits [V] fp32 at the prompt's last token, the row's
    cache: {"k", "v"} of [attention layers, Hkv, n_positions, Dh], zero past
    S, and the row's state **as of the prompt's last token** {"ssm",
    "conv"}, as :func:`state_spec` lays a slot's out)."""
    S = tokens.shape[1]
    x, k, v, h, tail = _forward_row(params, tokens[0], cfg, true_len,
                                    _kernel_use(cfg, S))
    with jax.named_scope("kv_write"):
        pad = ((0, 0), (0, 0), (0, n_positions - S), (0, 0))
        row = {"k": jnp.pad(k, pad), "v": jnp.pad(v, pad),
               "ssm": ssm.pack_heads(h, cfg.state_pack), "conv": tail}
    with jax.named_scope("head_sample"):  # the engine's sampler joins it
        logits = _head(lax.dynamic_index_in_dim(x, true_len - 1, 0, False),
                       params, cfg)
    return logits, row


def paged_decode(params, tokens, pool, positions, lengths, page_table,
                 cfg: NemotronHConfig):
    """One decode token a row (row ``i`` is slot ``i``) against the pool, read
    and written in place (serve/kv_cache.py): ``pool`` holds {"k", "v"} of
    [attention layers, Hkv, P, page_tokens, Dh] whose last page is the sink,
    and the slots' state {"ssm", "conv"} (:func:`state_spec`). Row ``i``'s
    token sits at ``positions[i]`` and attends over its first ``lengths[i]``
    cached positions and itself; an idle row has length 0: it reads no page,
    writes the sink, is routed to no expert, **and its state is neither
    fetched nor moved**. Returns (logits [B, V] fp32, pool, counts), the
    counts int32: ``expert_tokens`` [n_held_experts], the live rows'
    assignments to the experts held here, summed over the expert layers;
    ``experts_touched``, the held experts with at least one of them, summed
    over the expert layers; ``expert_layer_steps``, the expert layers that
    ran with a live row; ``expert_assignments``, all the live rows'
    assignments, held here or not, and ``expert_assignments_held``, those of
    them to experts held here; ``state_rows_stepped``, the live rows summed
    over the Mamba layers; ``state_rows_fetched``, the rows whose state the
    update read (the same where idle slots are skipped); ``ssm_layer_steps``,
    the Mamba layers that ran with a live row."""
    page, width = pool["k"].shape[3], page_table.shape[1]
    sink = pool["k"].shape[2] - 1
    live = lengths > 0
    x = params["tok_embed"][tokens].astype(cfg.dtype)            # [B, D]
    state, k_new, v_new, tails = pool["ssm"], [], [], []
    fetched, touched = jnp.int32(0), jnp.int32(0)
    expert_tokens = jnp.zeros((cfg.n_held_experts,), jnp.int32)
    for kind, p in zip(cfg.pattern, params["layers"]):
        u = _rmsnorm(x, p["ln"], cfg.rms_norm_eps)
        if kind == "M":
            i = len(tails)                       # among the Mamba layers
            z, xbc, dt = _ssm_project(u, p, cfg)
            with jax.named_scope("ssm_conv"):
                old = pool["conv"][i]                   # [taps - 1, B, C]
                xs, b, c = _split_xbc(_conv([*old, xbc], p, cfg), cfg)
                tails.append(jnp.where(
                    live[None, :, None],
                    jnp.concatenate([old[1:], xbc[None]], axis=0), old))
            with jax.named_scope("ssm_decode_update"):
                y, state, n = ssm.ssm_decode_update(
                    state, xs, dt, -jnp.exp(p["A_log"]), b, c, p["D"], live,
                    layer=i)
            fetched = fetched + n
            x = x + _gate_out(y, z, p, cfg)
        elif kind == "*":
            q, k, v = _qkv(u, p, cfg)
            with jax.named_scope("decode_attention"):
                o = paged_attention(q, pool["k"], pool["v"], lengths,
                                    page_table, layer=len(k_new), k_cur=k,
                                    v_cur=v)
            k_new.append(k), v_new.append(v)
            x = x + _mm(o.reshape(o.shape[0], -1), p["wo"], cfg)
        else:
            y, counts = _experts(u, p, cfg, live)
            expert_tokens = expert_tokens + counts
            touched = touched + jnp.sum(counts > 0, dtype=jnp.int32)
            x = x + y
    with jax.named_scope("kv_write"):
        # the pages are written only once every layer has read them: without
        # the barrier nothing orders the last layer's attention before the
        # write, and the compiler copies both pools to be safe, every step
        x, k_new, v_new = lax.optimization_barrier(
            (x, jnp.stack(k_new, 1), jnp.stack(v_new, 1)))
        at = positions // page
        inside = jnp.minimum(at, width - 1)[:, None]
        pages = jnp.where(
            at < width,
            jnp.take_along_axis(page_table, inside, axis=1)[:, 0], sink)
        offs = positions % page
        pool = {"k": _write_kv(pool["k"], k_new, pages, offs),
                "v": _write_kv(pool["v"], v_new, pages, offs)}
    with jax.named_scope("state_write"):
        pool.update(ssm=state, conv=jnp.stack(tails))
    with jax.named_scope("head_sample"):  # the engine's sampler joins it
        logits = _head(x, params, cfg)
    n_live = jnp.sum(live, dtype=jnp.int32)
    ran = (n_live > 0).astype(jnp.int32)
    return logits, pool, {
        "expert_tokens": expert_tokens,
        "experts_touched": touched,
        "expert_layer_steps": ran * cfg.count("E"),
        "expert_assignments": n_live * (cfg.count("E")
                                        * cfg.experts_per_tok),
        "expert_assignments_held": jnp.sum(expert_tokens, dtype=jnp.int32),
        "state_rows_stepped": n_live * cfg.count("M"),
        "state_rows_fetched": fetched,
        "ssm_layer_steps": ran * cfg.count("M")}


def mixed_step(params, pool, chunk_tokens, chunk_pages, chunk_last, tokens,
               positions, lengths, page_table, cfg: NemotronHConfig, *,
               chunk_index, slot):
    """One chunk of one row's prompt and one decode token a live row, in one
    pass over the pattern: the decode rows' weights are the chunk's. The
    arguments are ``models/hybrid_ssm.py::mixed_step``'s: ``chunk_tokens``
    int32 [C] are the prompt's positions ``[chunk_index * C, (chunk_index +
    1) * C)`` (``C`` a whole number of pages), of which the first
    ``chunk_last + 1`` are real; ``chunk_pages`` is the prompt's row of the
    block table in whole chunks; ``slot`` is the index of the row being
    prefilled, whose state entry the chunk continues; ``tokens``,
    ``positions``, ``lengths`` and ``page_table`` are :func:`paged_decode`'s,
    the row being prefilled idle among them (length 0, a table row of sink
    entries): the decode half neither fetches nor moves its state.

    Embedding, norms and every projection run once over the ``C + B`` rows,
    the chunk's first. By the kind of layer:

      - ``M``: the chunk's convolution takes the taps before its first
        position from the slot's tail and its scan starts from the slot's
        state (``ssd_scan(..., h0=)``), **zeros both where ``chunk_index ==
        0``** (``hybrid_ssm._carried``: what an earlier request left in the
        slot is never read, and no program clears it); the scan stops at the
        chunk's real positions, and the slot's entry then holds the state
        and the last ``ssm_conv - 1`` inputs as of the chunk's last real
        position. The live rows' update is :func:`paged_decode`'s kernel on
        the packed state where it lies, under a name of its own
        (``ssm_mixed_update``: whoever counts the decode program's
        token-steps by ``ssm_decode_update``'s calls counts none here). The
        slot's entry of the layer is sliced out of the state after that
        update (unpacked for ``h0``) and put back after the scan (packed),
        so the state stays where it is;
      - ``*``: no position enters. The chunk attends in the flash forward
        kernel over the row's pages before it (gathered outside a
        ``lax.switch`` over the prefix lengths a prompt can have,
        ``chunk_index`` a run-time int32: ONE program) and itself, the decode
        rows through the block table; the layer loop only reads the pages,
        and after it the chunk's K and V go to whole pages and each decode
        row's to its one position;
      - ``E``: router, latent projections, the held experts and the shared
        expert **once over the ``C + B`` rows**, so a held expert's matrices
        are read once a layer and mixed step; the chunk's padding and the
        idle rows are routed nowhere.

    Returns (logits [B + 1, V] fp32: the decode rows', then the chunk's at
    ``chunk_last``; the pool; counts int32 **under names of the mixed step's
    own**, so that every mean a decode token-step stays the decode
    program's): ``mixed_state_rows_stepped``, the live decode rows whose
    state the update kernel moved, summed over the Mamba layers (the chunk's
    row moves in the scan and is not among them); ``mixed_ssm_layer_steps``,
    the calls of that kernel (the Mamba layers); ``mixed_expert_layer_steps``,
    the expert layers that ran; ``mixed_expert_assignments_held``, the
    assignments of the chunk's real positions and of the live decode rows
    together to experts held here, summed over the expert layers;
    ``mixed_experts_touched``, the held experts with at least one of them,
    summed over the expert layers."""
    C, page = chunk_tokens.shape[0], pool["k"].shape[3]
    Hkv, Dh, rep = cfg.kv_heads, cfg.head_dim, cfg.n_heads // cfg.kv_heads
    tail, n_pages, pack = cfg.ssm_conv - 1, C // page, cfg.state_pack
    n_chunks = chunk_pages.shape[0] // n_pages  # the longest prompt's
    chunk_index = jnp.asarray(chunk_index, jnp.int32)
    slot = jnp.asarray(slot, jnp.int32)
    n_real = chunk_last + 1
    first = chunk_index == 0
    before = chunk_pages[:(n_chunks - 1) * n_pages]
    use = _kernel_use(cfg, C)
    live = lengths > 0
    routed = jnp.concatenate([jnp.arange(C) < n_real, live])
    x = params["tok_embed"][jnp.concatenate([chunk_tokens, tokens])].astype(
        cfg.dtype)

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
    touched, held = jnp.int32(0), jnp.int32(0)
    for kind, p in zip(cfg.pattern, params["layers"]):
        u = _rmsnorm(x, p["ln"], cfg.rms_norm_eps)
        if kind == "M":
            i = len(tails)                       # among the Mamba layers
            # the three parts are held as they are made: the gate is read
            # after the scan, and the compiler would rather run the whole
            # projection over the 640 rows again for it than keep it
            z, xbc, dt = lax.optimization_barrier(_ssm_project(u, p, cfg))
            with jax.named_scope("ssm_conv"):
                old = conv[i]                           # [taps - 1, B, C]
                mine = lax.dynamic_slice_in_dim(old, slot, 1, axis=1)[:, 0]
                behind = jnp.concatenate([_carried(mine, first), xbc[:C]])
                xs, b, c = _split_xbc(jnp.concatenate([
                    _conv([behind[j:j + C] for j in range(cfg.ssm_conv)], p,
                          cfg),
                    _conv([*old, xbc[C:]], p, cfg)]), cfg)
                # the live rows' tails move on by their token; the slot's
                # holds the last real inputs: rows n_real - tail .. n_real - 1
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
                h0 = ssm.unpack_heads(lax.dynamic_slice(
                    state, (i, slot, 0, 0, 0),
                    (1, 1) + state.shape[2:])[0, 0], pack)
                y_chunk, h = ssm.ssd_scan(
                    xs[:C], dt[:C], A, b[:C], c[:C], p["D"], true_len=n_real,
                    h0=_carried(h0, first))
            with jax.named_scope("state_write"):
                state = lax.dynamic_update_slice(
                    state, ssm.pack_heads(h, pack)[None, None],
                    (i, slot, 0, 0, 0))
            x = x + _gate_out(jnp.concatenate([y_chunk, y_rows]), z, p, cfg)
        elif kind == "*":
            j = len(k_chunk)                     # among the attention layers
            q, k, v = _qkv(u, p, cfg)
            kt, vt = k[:C].transpose(1, 0, 2), v[:C].transpose(1, 0, 2)
            with jax.named_scope("prefix_gather"):
                ks = row_so_far(pool["k"], kt, j)
                vs = row_so_far(pool["v"], vt, j)
            with jax.named_scope("prefill_attention"):
                o_chunk = lax.switch(
                    chunk_index, [over(n) for n in range(n_chunks)],
                    q[:C].transpose(1, 0, 2)[None], ks, vs)[0]
            with jax.named_scope("decode_attention"):
                o_rows = paged_attention(
                    q[C:], pool["k"], pool["v"], lengths, page_table,
                    layer=j, k_cur=k[C:], v_cur=v[C:])
            k_chunk.append(kt), v_chunk.append(vt)
            k_rows.append(k[C:]), v_rows.append(v[C:])
            o = jnp.concatenate([o_chunk.transpose(1, 0, 2), o_rows])
            x = x + _mm(o.reshape(o.shape[0], -1), p["wo"], cfg)
        else:
            y, counts = _experts(u, p, cfg, routed)
            held = held + jnp.sum(counts, dtype=jnp.int32)
            touched = touched + jnp.sum(counts > 0, dtype=jnp.int32)
            x = x + y
    with jax.named_scope("kv_write"):
        # the pages are written only once every layer has read them
        # (:func:`paged_decode`)
        x, k_chunk, v_chunk, k_rows, v_rows = lax.optimization_barrier(
            (x, jnp.stack(k_chunk), jnp.stack(v_chunk), jnp.stack(k_rows, 1),
             jnp.stack(v_rows, 1)))
        now = lax.dynamic_slice_in_dim(chunk_pages, chunk_index * n_pages,
                                       n_pages)

        def whole_pages(pages_of, new):  # new [attention layers, Hkv, C, Dh]
            return pages_of.at[:, :, now].set(
                new.reshape(new.shape[:2] + (n_pages, page, Dh)))

        kv = _write_rows({"k": whole_pages(pool["k"], k_chunk),
                          "v": whole_pages(pool["v"], v_chunk)},
                         k_rows, v_rows, positions, page_table)
    with jax.named_scope("state_write"):
        pool = dict(kv, ssm=state, conv=jnp.stack(tails))
    with jax.named_scope("head_sample"):  # the engine's sampler joins it
        logits = _head(jnp.concatenate(
            [x[C:], lax.dynamic_slice_in_dim(x, chunk_last, 1)]), params, cfg)
    return logits, pool, {
        "mixed_state_rows_stepped": jnp.sum(live, dtype=jnp.int32)
        * cfg.count("M"),
        "mixed_ssm_layer_steps": jnp.int32(cfg.count("M")),
        "mixed_expert_layer_steps": jnp.int32(cfg.count("E")),
        "mixed_expert_assignments_held": held,
        "mixed_experts_touched": touched}
