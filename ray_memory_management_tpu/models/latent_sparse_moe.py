"""LatentSparseMoELM: latent attention (MLA) over the positions a learned
indexer selects, and one chip's share of each layer's routed experts: the
DeepSeek-V3.2 / GLM-5.2 family's layer (``model_type`` ``glm_moe_dsa``), on
the serve path. The projections, norms, RoPE and the absorbed product are
models/latent_moe.py's, imported as they are.

A layer, with RMSNorm before each half and a residual around it:

  - *queries and latent* as models/latent_moe.py: a token leaves ``c_kv``
    after its norm and the rotary key after RoPE in the cache of every layer
    (``cfg.cache_width`` values, padding counted);
  - *the indexer*, in a layer whose ``indexer_types`` entry is ``full``:
    ``index_n_heads`` queries of ``index_head_dim`` from the normed query
    bottleneck, one key a token from the hidden vector under a LayerNorm
    (**cached**: ``index_head_dim`` values a token, of the ``full`` layers
    only), RoPE on the first ``qk_rope_head_dim`` columns of both, and a
    weight a head from the hidden vector; position ``s <= t`` scores
    ``sum_j w[t, j] relu(q[t, j] . k[s])`` in float32, and the query attends
    the ``index_topk`` positions of largest score (all of them while there
    are no more; ties to the lower position);
  - a ``shared`` layer has no indexer and no index key: it attends the
    selection of the nearest ``full`` layer below it;
  - *attention* over the selected positions only (ops/paged_attention.py:
    ``index_scores``, ``index_select``, then ``sparse_latent_attention`` or
    ``sparse_expanded_attention``: one attention in two algebraic forms,
    below);
  - a SwiGLU MLP in the first ``first_k_dense`` layers; after them the
    router over all ``n_routed_experts`` (sigmoid, a choosing bias, top
    ``experts_per_tok``) beside one shared expert. **This chip holds a share
    of a layer's experts** (``n_held_experts`` from ``first_held_expert``;
    ops/moe.py::grouped_experts): an assignment to an expert that lives
    elsewhere is computed nowhere and adds nothing, and the partial sum goes
    on. Nothing stands in for the other chips or for the exchange with them.

**Two paged arrays of different depth** (:func:`cache_spec`): ``latent``
[layers, pages, page_tokens, cache_width] and ``index`` [``full`` layers,
pages, page_tokens, index_head_dim]; ``full`` layer ``j`` (counted among its
kind) is entry ``j`` of ``index``. One page id is a page of both.

**One mechanism serves prompt and answer.** A chunk's query does what a
decode row's does: its own vector and index key go into the pool first, then
it scores the row's cached index keys up to its own position, selects, and
attends over the selection. So there is no prefill program: the
engine (serve/llm.py) finds :func:`mixed_step` by name and sends every prompt
through it in chunks, beside the decode rows' tokens; :func:`paged_decode` is
the same walk without a chunk.

**A decode row attends absorbed, a chunk expanded.** ``(q W_k) . c`` is ``q .
(W_k c)``: the absorbed form carries every head's query to the cached vector's
width and pays ``2 * cache_width`` FLOPs for a (query, position, head)'s score
and ``2 * kv_lora_rank`` for its value (2,304 at GLM-5.2's widths); the
expanded form makes a position's key and value of every head from ``c_kv``
first (``2 * kv_lora_rank * H * (qk_nope_head_dim + v_head_dim)`` FLOPs, 29.4 M,
once) and then pays ``2 * qk_head_dim + 2 * v_head_dim`` (1,024). A decode
row's one query cannot share its row's expansion with anyone and stays
absorbed; the ``n`` queries of a chunk share one row of the table, and from
:func:`_expanded_pays` on (``n * H * 2 * 640 > 2 * 512 * H * 448``: 359
queries) the expanded form is the cheaper, 2.1 times at a chunk of 4,096. The
group's size chooses, not a switch; the same bf16 operands and float32 sums
either way. **Both kernels are called ``sparse_latent_attention`` in the
trace**: the benchmark's readers count the traced steps from the calls under
that name (two a layer in a mixed step, one in a decode step) and divide the
chunks' and the rows' work by the seconds under it.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..ops import moe
from ..ops.paged_attention import (expanded_form_runs, index_scores,
                                   index_select, sparse_expanded_attention,
                                   sparse_latent_attention)
from .latent_moe import (_LANES, _cached, _head, _kv_b_halves, _mm, _rmsnorm,
                         _rope, _swiglu)


@dataclasses.dataclass(frozen=True)
class LatentSparseMoEConfig:
    vocab_size: int
    d_model: int
    n_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    d_ff: int                      # the leading dense layers' SwiGLU width
    moe_d_ff: int                  # one expert's (and the shared one's)
    n_routed_experts: int          # the router's width: every chip's experts
    n_held_experts: int            # those that live here
    n_shared_experts: int
    experts_per_tok: int
    routed_scaling_factor: float
    index_n_heads: int
    index_head_dim: int
    index_topk: int
    indexer_types: Tuple[str, ...]  # a layer each: "full" or "shared"
    first_held_expert: int = 0
    norm_topk_prob: bool = True
    first_k_dense: int = 1
    max_seq: int = 2048
    rope_theta: float = 10_000.0
    rms_norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16      # activations and both caches
    param_dtype: Any = jnp.bfloat16

    def __post_init__(self):
        kinds = tuple(self.indexer_types)
        object.__setattr__(self, "indexer_types", kinds)
        if set(kinds) - {"full", "shared"} or kinds[:1] != ("full",):
            raise ValueError("a layer's indexer is 'full' or 'shared', the "
                             f"first layer's 'full': {kinds!r}")
        if self.first_held_expert + self.n_held_experts \
                > self.n_routed_experts:
            raise ValueError("the held experts are not among the routed")

    @property
    def n_layers(self) -> int:
        return len(self.indexer_types)

    @property
    def n_full(self) -> int:
        """Layers with an indexer of their own: those that cache a key."""
        return self.indexer_types.count("full")

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_width(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def cache_width(self) -> int:
        """The cached vector as the pool holds it: padded to whole lanes."""
        return -(-self.latent_width // _LANES) * _LANES


# ------------------------------------------------------------------ weights
def init_params(key, cfg: LatentSparseMoEConfig) -> Dict[str, Any]:
    """One dict a layer, models/latent_moe.py's tree with two differences: a
    ``full`` layer has ``index`` (``wq_b`` [q_lora_rank, heads * dim], ``wk``
    [D, dim], the key's LayerNorm ``k_ln`` / ``k_ln_b``, ``w`` [D, heads]),
    and an expert layer's ``moe`` holds the matrices of the held experts only
    under a router of the published width. ``split(key, 2 + n_layers)`` gives
    the embedding's key, the head's, then one a layer, split in 16; a matrix
    is normal * fan_in**-0.5, norm scales 1, biases 0. Weights made elsewhere
    with this tree go to ``LLMServer(init=...)``."""
    pd = cfg.param_dtype
    D, H, E = cfg.d_model, cfg.n_heads, cfg.n_held_experts
    ql, kl, Fe = cfg.q_lora_rank, cfg.kv_lora_rank, cfg.moe_d_ff
    Fs, J, Di = cfg.n_shared_experts * Fe, cfg.index_n_heads, \
        cfg.index_head_dim
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
        if cfg.indexer_types[i] == "full":
            out["index"] = {"wq_b": w(k[13], (ql, J * Di), ql),
                            "wk": w(k[14], (D, Di), D),
                            "k_ln": jnp.ones((Di,), pd),
                            "k_ln_b": jnp.zeros((Di,), pd),
                            "w": w(k[15], (D, J), D)}
        if i < cfg.first_k_dense:
            out["mlp"] = {"w1": w(k[5], (D, cfg.d_ff), D),
                          "w3": w(k[6], (D, cfg.d_ff), D),
                          "w2": w(k[7], (cfg.d_ff, D), cfg.d_ff)}
        else:
            out["moe"] = {"router": w(k[5], (D, cfg.n_routed_experts), D),
                          "bias": jnp.zeros((cfg.n_routed_experts,), pd),
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
def _project(h, p, positions, cfg: LatentSparseMoEConfig):
    """h [T, D] at ``positions`` [T] -> (c_q [T, q_lora_rank] after its
    norm, which the indexer reads too; every head's plain query [T, H,
    qk_head_dim], ``q_nope`` beside the rotated ``q_rope``; the vector the
    cache holds [T, cache_width])."""
    T, H, kl = h.shape[0], cfg.n_heads, cfg.kv_lora_rank
    c_q = _rmsnorm(_mm(h, p["q_a"], cfg), p["q_ln"], cfg.rms_norm_eps)
    q = _mm(c_q, p["q_b"], cfg).reshape(T, H, cfg.qk_head_dim)
    q_nope, q_rope = jnp.split(q, [cfg.qk_nope_head_dim], axis=-1)
    kv = _mm(h, p["kv_a"], cfg)
    cached = _cached(_rmsnorm(kv[:, :kl], p["kv_ln"], cfg.rms_norm_eps),
                     _rope(kv[:, kl:], positions, cfg.rope_theta), cfg)
    q = jnp.concatenate(
        [q_nope, _rope(q_rope, positions, cfg.rope_theta)], -1)
    return c_q, q, cached


def _absorbed(q, to_k, cfg: LatentSparseMoEConfig):
    """Plain queries [..., H, qk_head_dim] -> every head's absorbed query
    [..., H, cache_width]: ``q_nope`` carried through ``to_k`` to the
    latent's width, what models/latent_moe.py::_attend_absorbed makes of a
    token."""
    pad = cfg.cache_width - cfg.latent_width
    return jnp.pad(jnp.concatenate(
        [jnp.einsum("...hn,lhn->...hl", q[..., :cfg.qk_nope_head_dim], to_k),
         q[..., cfg.qk_nope_head_dim:]], -1),
        ((0, 0),) * (q.ndim - 1) + ((0, pad),))


def _expanded_pays(cfg: LatentSparseMoEConfig, n: int) -> bool:
    """Whether a group of ``n`` queries on one row of the table attends
    cheaper in the expanded form, by the configuration's own widths: what
    ``n`` queries save on a cached position (absorbed: the cached width for a
    head's score and ``kv_lora_rank`` for its value; expanded: ``qk_head_dim``
    and ``v_head_dim``) against what expanding that position for every head
    costs. At GLM-5.2's widths 640 n against 229,376: from 359 queries on."""
    saved = n * cfg.n_heads * 2 * (
        (cfg.cache_width + cfg.kv_lora_rank)
        - (cfg.qk_head_dim + cfg.v_head_dim))
    return saved > 2 * cfg.kv_lora_rank * cfg.n_heads * (
        cfg.qk_nope_head_dim + cfg.v_head_dim)


def _partly_rotary(x, positions, cfg: LatentSparseMoEConfig):
    """RoPE on the first ``qk_rope_head_dim`` columns of x [T, ..., dim]."""
    r = cfg.qk_rope_head_dim
    return jnp.concatenate(
        [_rope(x[..., :r], positions, cfg.rope_theta), x[..., r:]], -1)


def _indexer(h, c_q, p, positions, cfg: LatentSparseMoEConfig):
    """The indexer's three parts of h [T, D]: queries [T, heads, dim], the
    key a token leaves in the cache [T, dim], the heads' weights float32
    [T, heads] (scaled by heads**-0.5 * dim**-0.5)."""
    T, J, Di = h.shape[0], cfg.index_n_heads, cfg.index_head_dim
    q = _partly_rotary(_mm(c_q, p["wq_b"], cfg).reshape(T, J, Di), positions,
                       cfg)
    k = _mm(h, p["wk"], cfg).astype(jnp.float32)
    k = (k - jnp.mean(k, -1, keepdims=True)) * lax.rsqrt(
        jnp.var(k, -1, keepdims=True) + 1e-6)
    k = (k * p["k_ln"].astype(jnp.float32)
         + p["k_ln_b"].astype(jnp.float32)).astype(cfg.dtype)
    w = jnp.dot(h, p["w"].astype(cfg.dtype),
                preferred_element_type=jnp.float32) * (J * Di) ** -0.5
    return q, _partly_rotary(k, positions, cfg), w


def _ffn(x, layer, cfg: LatentSparseMoEConfig, live):
    """The layer's second half on norm(x): [T, D] -> (y, expert_tokens
    [n_held_experts] or None: the live rows' assignments to held experts)."""
    h = _rmsnorm(x, layer["mlp_ln"], cfg.rms_norm_eps)
    if "mlp" in layer:
        return _swiglu(h, layer["mlp"], cfg), None
    chosen, w = moe.route_sigmoid_top_k(
        h, layer["moe"]["router"], layer["moe"]["bias"], cfg.experts_per_tok,
        cfg.routed_scaling_factor, cfg.norm_topk_prob)
    y, counts = moe.grouped_experts(h, chosen, w, layer["moe"], live,
                                    cfg.first_held_expert)
    with jax.named_scope("moe_shared"):
        y = y + _swiglu(h, layer["shared"], cfg)
    return y, counts


def _put_rows(pages_of, layer: int, fresh, pages, offs):
    """One position a row into ``pages_of`` [L, P, page, W] at ``layer``:
    fresh [B, W] to (pages[b], offs[b]), by patching the tile of 16 positions
    around it (models/latent_moe.py::paged_decode says why)."""
    page, W = pages_of.shape[2], pages_of.shape[3]
    tile = 16 if page % 16 == 0 else 1
    rows = jnp.arange(tile)[:, None]

    def one(b, c):
        base = offs[b] // tile * tile
        where = (layer, pages[b], base, 0)
        old = lax.dynamic_slice(c, where, (1, 1, tile, W))
        return lax.dynamic_update_slice(
            c, jnp.where(rows == offs[b] - base, fresh[b], old), where)

    return lax.fori_loop(0, fresh.shape[0], one, pages_of)


def _put_pages(pages_of, layer: int, fresh, pages):
    """Whole pages into ``pages_of`` at ``layer``: fresh [n * page, W] to
    the pages ``pages`` [n]."""
    page = pages_of.shape[2]
    for j in range(pages.shape[0]):
        pages_of = lax.dynamic_update_slice(
            pages_of, fresh[None, None, j * page:(j + 1) * page],
            (layer, pages[j], 0, 0))
    return pages_of


def _walk(params, pool, tokens, positions, routed, parts,
          cfg: LatentSparseMoEConfig):
    """The layers over ``tokens`` [T] at ``positions`` [T], in ``parts``:
    (rows of the T, (groups, queries a group), block-table rows [groups,
    pages a row], put) each, ``put(pages_of, layer, fresh)`` writing the
    part's vectors where its positions lie. In every layer each part's vectors
    go into the pool, then its queries read the pool alone. Returns (hidden
    [T, D] before the final norm, the pool, expert_tokens [n_held_experts],
    experts touched summed over the expert layers)."""
    scale = cfg.qk_head_dim ** -0.5
    H, vd = cfg.n_heads, cfg.v_head_dim
    latent, index = pool["latent"], pool["index"]
    x = params["tok_embed"][tokens].astype(cfg.dtype)
    expert_tokens = jnp.zeros((cfg.n_held_experts,), jnp.int32)
    touched, n_full, masks = jnp.int32(0), 0, None
    for i, layer in enumerate(params["layers"]):
        h = _rmsnorm(x, layer["ln"], cfg.rms_norm_eps)
        c_q, q, cached = _project(h, layer, positions, cfg)
        with jax.named_scope("latent_kv_write"):
            for rows, _, _, put in parts:
                latent = put(latent, i, cached[rows])
        if "index" in layer:
            q_i, k_i, w_i = _indexer(h, c_q, layer["index"], positions, cfg)
            with jax.named_scope("index_key_write"):
                for rows, _, _, put in parts:
                    index = put(index, n_full, k_i[rows])
            masks = []
            for rows, (G, n), table, _ in parts:
                with jax.named_scope("index_scores"):
                    scores = index_scores(
                        q_i[rows].reshape(G, n, *q_i.shape[1:]),
                        w_i[rows].reshape(G, n, -1), index, table,
                        positions[rows].reshape(G, n), layer=n_full)
                with jax.named_scope("index_select"):
                    masks.append(index_select(scores, cfg.index_topk))
            n_full += 1
        to_k, to_v = _kv_b_halves(layer, cfg)
        outs = []
        for (rows, (G, n), table, _), mask in zip(parts, masks):
            at = positions[rows].reshape(G, n)
            mine = q[rows].reshape(G, n, H, cfg.qk_head_dim)
            # one attention in two algebraic forms, and the group's size
            # says which is cheaper: many queries on one row of the table
            # share the row's expansion, a decode row's one query does not
            with jax.named_scope("sparse_latent_attention"):
                if _expanded_pays(cfg, n) and expanded_form_runs(
                        mine, latent, to_k, to_v):
                    o = sparse_expanded_attention(
                        mine, latent, table, mask, at, to_k, to_v, layer=i,
                        scale=scale)
                else:
                    o = jnp.einsum("gnhl,lhv->gnhv", sparse_latent_attention(
                        _absorbed(mine, to_k, cfg), latent, table, mask, at,
                        layer=i, top_k=cfg.index_topk,
                        value_width=cfg.kv_lora_rank, scale=scale), to_v)
            outs.append(o.reshape(G * n, H * vd))
        o = jnp.concatenate(outs)
        x = x + _mm(o, layer["o"], cfg)
        # the next layer writes the pool only once this one has read it:
        # without the barrier nothing orders the two, and the compiler
        # copies the pool to be safe
        x, latent, index = lax.optimization_barrier((x, latent, index))
        y, counts = _ffn(x, layer, cfg, routed)
        x = x + y
        if counts is not None:
            expert_tokens = expert_tokens + counts
            touched = touched + jnp.sum(counts > 0, dtype=jnp.int32)
    return x, {"latent": latent, "index": index}, expert_tokens, touched


def _row_part(at, positions, page_table, pages_of):
    """The decode rows' part of a walk: rows ``at`` of its tokens, a group
    of one query each, each row's token written at its own position (past
    the table's width: to the sink, the pool's last page)."""
    page, width = pages_of.shape[2], page_table.shape[1]
    inside = jnp.minimum(positions // page, width - 1)[:, None]
    pages = jnp.where(
        positions // page < width,
        jnp.take_along_axis(page_table, inside, axis=1)[:, 0],
        pages_of.shape[1] - 1)
    offs = positions % page

    def put(pages_of, layer, fresh):
        return _put_rows(pages_of, layer, fresh, pages, offs)

    return at, (positions.shape[0], 1), page_table, put


def _counts(cfg: LatentSparseMoEConfig, seen, expert_tokens, touched,
            n_routed, prefix: str = ""):
    """What a step counted of itself, int32: ``seen`` [queries] is how many
    positions each live query had cached (its own among them), 0 for a query
    that is not live; ``n_routed`` the live queries."""
    n_sparse = cfg.n_layers - min(cfg.first_k_dense, cfg.n_layers)
    cached = jnp.sum(seen, dtype=jnp.int32)
    ran = (n_routed > 0).astype(jnp.int32)
    out = {
        "positions_cached": cached * cfg.n_layers,
        "positions_selected": jnp.sum(
            jnp.minimum(seen, cfg.index_topk), dtype=jnp.int32)
        * cfg.n_layers,
        "positions_scored": cached * cfg.n_full,
        "expert_tokens": expert_tokens,
        "experts_touched": touched,
        "expert_layer_steps": ran * n_sparse,
        "expert_assignments": n_routed * (n_sparse * cfg.experts_per_tok),
        "expert_assignments_held": jnp.sum(expert_tokens, dtype=jnp.int32)}
    return {prefix + k: v for k, v in out.items()}


# --------------------------------------------------- what the engine asks for
def cache_spec(cfg: LatentSparseMoEConfig) -> Dict[str, Tuple]:
    """What a token leaves in the cache, as the page pool lays it out: name
    -> (dims before the pages, dims after a page's positions, dtype). **Two
    arrays, by kind**: ``latent`` [layers, pages, page_tokens, cache_width],
    every layer's, and ``index`` [full layers, pages, page_tokens,
    index_head_dim], the index key of the layers that have an indexer. A
    page id is a page of both."""
    return {"latent": ((cfg.n_layers,), (cfg.cache_width,), cfg.dtype),
            "index": ((cfg.n_full,), (cfg.index_head_dim,), cfg.dtype)}


def prefill_takes_kernel(cfg: LatentSparseMoEConfig, n_tokens: int) -> bool:
    """Whether a chunk of ``n_tokens`` attends in the flash forward kernel:
    never, it attends where a decode row does (:func:`mixed_step`)."""
    return False


def paged_decode(params, tokens, pool, positions, lengths, page_table,
                 cfg: LatentSparseMoEConfig):
    """One decode token a row against the pool, read and written in place
    (serve/kv_cache.py): ``pool`` holds :func:`cache_spec`'s two arrays,
    whose last page is the sink. Row ``i``'s token sits at ``positions[i]``
    with ``lengths[i]`` positions cached before it; an idle row has length
    0 and a table row of sink entries: it writes the sink, reads what it
    wrote there, and is routed to no expert. In every layer the rows' vectors
    (and, in a ``full`` layer, their index keys) are written first, then each
    row scores, selects and attends through its row of the block table, its
    own position among the candidates. Returns (logits [B, V] fp32, pool,
    counts), the counts int32 over the live rows: ``positions_cached`` (each
    row's cached positions, its own among them, summed over the layers),
    ``positions_selected`` (those of them it attended: at most
    ``index_topk`` a layer), ``positions_scored`` (those the indexers scored:
    the cached ones, in the ``full`` layers), ``expert_tokens``
    [n_held_experts], ``experts_touched``, ``expert_layer_steps``,
    ``expert_assignments`` (held here or not) and
    ``expert_assignments_held`` as models/nemotron_h.py counts them."""
    live = lengths > 0
    part = _row_part(slice(None), positions, page_table, pool["latent"])
    x, pool, expert_tokens, touched = _walk(params, pool, tokens, positions,
                                            live, [part], cfg)
    with jax.named_scope("head_sample"):  # the engine's sampler joins it
        logits = _head(x, params, cfg)
    return logits, pool, _counts(
        cfg, jnp.where(live, lengths + 1, 0), expert_tokens, touched,
        jnp.sum(live, dtype=jnp.int32))


def mixed_step(params, pool, chunk_tokens, chunk_pages, chunk_last, tokens,
               positions, lengths, page_table, cfg: LatentSparseMoEConfig, *,
               chunk_index):
    """One chunk of one row's prompt and one decode token a live row, in one
    pass over the layers. ``chunk_tokens`` int32 [C] are the prompt's
    positions ``[chunk_index * C, (chunk_index + 1) * C)`` (``C`` a whole
    number of pages), of which the first ``chunk_last + 1`` are real;
    ``chunk_pages`` is the prompt's row of the block table in whole chunks;
    ``tokens``, ``positions``, ``lengths`` and ``page_table`` are
    :func:`paged_decode`'s, the row being prefilled idle among them.

    **A chunk's query does what a decode row's does.** Embedding, norms and
    every projection run once over the ``C + B`` rows, the chunk's first. In
    every layer the chunk's vectors go to its whole pages and the decode
    rows' to their positions (in a ``full`` layer the index keys too); then
    the chunk, one group of ``C`` queries on the prompt's row of the table,
    and the decode rows, a group of one each, score the cached index keys up
    to their own position, select and attend: the rows absorbed, the chunk
    expanded where its size pays for it (the module's header; the chunk's
    padding beyond ``chunk_last`` computes what nobody reads, and writes what
    the row's first decode tokens overwrite). ``chunk_index`` is a run-time
    int32: ONE program. The experts run once over the chunk's real positions
    and the live rows together.

    Returns (logits [B + 1, V] fp32: the decode rows', then the chunk's at
    ``chunk_last``; the pool; counts int32 **under names of the mixed step's
    own**: :func:`paged_decode`'s, each with ``mixed_`` before it, over the
    chunk's real positions and the live rows together,
    ``mixed_chunk_positions``, the chunk's real positions, and
    ``mixed_chunk_positions_cached``, the positions they had cached between
    them in one layer)."""
    C, page = chunk_tokens.shape[0], pool["latent"].shape[2]
    per = C // page
    chunk_index = jnp.asarray(chunk_index, jnp.int32)
    n_real = jnp.asarray(chunk_last, jnp.int32) + 1
    at = chunk_index * C + jnp.arange(C, dtype=jnp.int32)
    now = lax.dynamic_slice_in_dim(chunk_pages, chunk_index * per, per)
    live = lengths > 0
    real = jnp.arange(C) < n_real

    def put(pages_of, layer, fresh):
        return _put_pages(pages_of, layer, fresh, now)

    parts = [(slice(0, C), (1, C), chunk_pages[None], put),
             _row_part(slice(C, None), positions, page_table,
                       pool["latent"])]
    x, pool, expert_tokens, touched = _walk(
        params, pool, jnp.concatenate([chunk_tokens, tokens]),
        jnp.concatenate([at, positions.astype(jnp.int32)]),
        jnp.concatenate([real, live]), parts, cfg)
    with jax.named_scope("head_sample"):  # the engine's sampler joins it
        logits = _head(jnp.concatenate(
            [x[C:], lax.dynamic_slice_in_dim(x, chunk_last, 1)]), params, cfg)
    seen = jnp.concatenate([jnp.where(real, at + 1, 0),
                            jnp.where(live, lengths + 1, 0)])
    counts = _counts(cfg, seen, expert_tokens, touched,
                     n_real + jnp.sum(live, dtype=jnp.int32), "mixed_")
    counts["mixed_chunk_positions"] = n_real
    counts["mixed_chunk_positions_cached"] = jnp.sum(
        jnp.where(real, at + 1, 0), dtype=jnp.int32)
    return logits, pool, counts
