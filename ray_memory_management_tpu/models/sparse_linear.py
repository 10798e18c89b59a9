"""SparseLinearLM: MiniCPM-SALA's decoder (``model_type`` ``minicpm_sala``) on
the serve path: two kinds of mixer by the configuration's ``mixer_types``,
a SwiGLU MLP in every layer, and MiniCPM's scaled embedding, residual and
head. The norms, RoPE and matmuls are models/latent_moe.py's, imported as
they are.

  - ``x = scale_emb * E[token]``; each layer ``x += r * Mixer(RMSNorm(x))``,
    then ``x += r * MLP(RMSNorm(x))`` with ``r = scale_depth /
    sqrt(depth_layers)`` (the *published* depth, whatever this chip holds);
    logits ``W_head RMSNorm(x) / (d_model / dim_model_base)``.
  - *Lightning attention* (``lightning-attn``): a query, key and value a
    head (``lin_heads`` of ``lin_head_dim``), q and k under RMSNorm and RoPE;
    a head's state ``S_t = lambda_h S_{t-1} + k_t^T v_t`` (float32,
    ``lin_head_dim`` squared) and ``o_t = q_t S_t / sqrt(lin_head_dim)``;
    ``lambda_h = exp(-2 ** (-8 (h + 1) / lin_heads))`` (:func:`decay_rates`);
    the heads' outputs under one RMSNorm over all of them, times
    ``sigmoid(W_g u)``, through ``W_o``. This is ops/ssm.py's recurrence
    with ``dt`` = 1, ``A`` = -slope, ``B`` = k, ``x`` = v, ``C`` = q and ``D``
    = 0, a group a head: ``ssd_scan`` for a chunk from the slot's state, the
    decode update for a token (``lightning_decode_update`` in the decode
    program, ``lightning_mixed_update`` beside a chunk).
  - *Sparse attention* (``minicpm4``, MiniCPM4's InfLLM-v2): ``n_heads``
    query heads on ``kv_heads`` K/V heads, q and k under RMSNorm, no
    position; k and v go to the pages, and a **pooled key** a
    ``kernel_stride`` positions, the mean of the ``kernel_size`` keys from
    there, to a strided array of the pool once its window is complete.
    A query scores the complete windows (softmax over them of ``q_h . c_j /
    sqrt(head_dim)``, summed over its group's heads), a block of
    ``block_size`` positions the largest of the windows that meet it; it
    attends the first ``init_blocks`` blocks, every block holding one of the
    last ``window_size`` positions, and the highest-scoring others up to
    ``top_k`` blocks (ties to the lower block; every block while it stands
    before ``dense_len``); ``o * sigmoid(W_g u)`` through ``W_o``
    (ops/paged_attention.py: ``block_scores``, ``block_select``,
    ``block_sparse_attention``).

**The pool** (:func:`cache_spec`): ``k`` and ``v`` [sparse layers, kv_heads,
pages, page_tokens, head_dim], ``pooled`` [sparse layers, kv_heads, pages,
page_tokens / kernel_stride, head_dim] (a strided array, serve/kv_cache.py:
entry ``e`` of a page is the window that starts at its ``kernel_stride *
e``-th position); the state :func:`state_spec` [lightning layers, slots,
lin_heads, lin_head_dim, lin_head_dim] float32. Sparse layer ``j`` counted
among its kind is entry ``j`` of the cache, lightning layer ``j`` entry ``j``
of the state.

**One mechanism serves prompt and answer**, as models/latent_sparse_moe.py's:
a chunk's query does what a decode row's does (its k, v and the pooled keys
its chunk completes are in the pool before any query reads them, the window
that starts in the page before the chunk among them), so there is no prefill
program: the engine finds :func:`mixed_step` by name and sends every prompt
through it in chunks beside the decode rows; :func:`paged_decode` is the same
walk without a chunk.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..ops import ssm
from ..ops.paged_attention import (block_scores, block_select,
                                   block_sparse_attention)
from .latent_moe import _mm, _rmsnorm, _rope, _swiglu

SPARSE, LIGHTNING = "minicpm4", "lightning-attn"


@dataclasses.dataclass(frozen=True)
class SparseLinearConfig:
    vocab_size: int
    d_model: int
    d_ff: int
    mixer_types: Tuple[str, ...]   # a layer each: SPARSE or LIGHTNING
    n_heads: int                   # the sparse layers' query heads
    kv_heads: int
    head_dim: int
    lin_heads: int                 # the lightning layers' (a key a head)
    lin_head_dim: int
    scale_emb: float
    scale_depth: float
    depth_layers: int              # the published depth the residual is of
    dim_model_base: int
    block_size: int = 64
    top_k: int = 64                # blocks a query attends at most
    kernel_size: int = 32          # keys a pooled key is the mean of
    kernel_stride: int = 16        # positions between pooled keys
    init_blocks: int = 1
    window_size: int = 2048        # the local positions always attended
    dense_len: int = 8192          # before it a query attends everything
    lin_use_rope: bool = True
    max_seq: int = 2048
    rope_theta: float = 10_000.0
    rms_norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16      # activations and the cache
    param_dtype: Any = jnp.bfloat16

    def __post_init__(self):
        kinds = tuple(self.mixer_types)
        object.__setattr__(self, "mixer_types", kinds)
        if set(kinds) - {SPARSE, LIGHTNING} or SPARSE not in kinds:
            raise ValueError(f"mixer_types are {SPARSE!r} or {LIGHTNING!r}, "
                             f"at least one {SPARSE!r}: {kinds!r}")
        if self.block_size % self.kernel_stride \
                or self.kernel_size % self.kernel_stride:
            raise ValueError("a block and a pooled window are whole strides")
        forced = self.init_blocks + -(-self.window_size // self.block_size) + 1
        if forced > self.top_k:
            raise ValueError(f"{forced} blocks are always chosen, more than "
                             f"top_k={self.top_k}")

    @property
    def n_layers(self) -> int:
        return len(self.mixer_types)

    @property
    def n_sparse(self) -> int:
        return self.mixer_types.count(SPARSE)

    @property
    def n_lin(self) -> int:
        return self.mixer_types.count(LIGHTNING)

    @property
    def residual_scale(self) -> float:
        return self.scale_depth / math.sqrt(self.depth_layers)

    @property
    def most_blocks(self) -> int:
        """The most blocks a query attends: ``top_k``, or every block up to
        ``dense_len``."""
        return max(self.top_k, -(-self.dense_len // self.block_size))


def decay_rates(cfg: SparseLinearConfig):
    """A lightning head's decay rate, ``-log lambda_h = 2 ** (-8 (h + 1) /
    lin_heads)``: Lightning Attention's ALiBi slopes (assumed; the
    configuration file says so)."""
    return 2.0 ** (-8.0 * (jnp.arange(cfg.lin_heads, dtype=jnp.float32) + 1)
                   / cfg.lin_heads)


# ------------------------------------------------------------------ weights
def init_params(key, cfg: SparseLinearConfig) -> Dict[str, Any]:
    """One dict a layer: ``ln``, ``mlp_ln``, ``mlp`` {``w1`` gate, ``w3``
    up, ``w2`` down}, and the mixer's ``q``, ``k``, ``v`` [D, heads * dim],
    ``g`` [D, query heads * dim], ``o`` [query heads * dim, D], ``q_ln`` and
    ``k_ln`` [dim], and in a lightning layer ``out_ln`` [heads * dim]. A
    matrix is normal * fan_in**-0.5, a norm's scale 1; ``split(key, 2 +
    n_layers)`` gives the embedding's key, the head's, then one a layer.
    Weights made elsewhere with this tree go to ``LLMServer(init=...)``."""
    pd, D, F = cfg.param_dtype, cfg.d_model, cfg.d_ff
    keys = jax.random.split(key, 2 + cfg.n_layers)

    def w(k, shape):
        return jax.random.normal(k, shape, pd) * (shape[0] ** -0.5)

    def layer(k, kind):
        k = jax.random.split(k, 8)
        if kind == SPARSE:
            hq, hkv, dh = cfg.n_heads, cfg.kv_heads, cfg.head_dim
        else:
            hq = hkv = cfg.lin_heads
            dh = cfg.lin_head_dim
        out = {"ln": jnp.ones((D,), pd), "mlp_ln": jnp.ones((D,), pd),
               "q": w(k[0], (D, hq * dh)), "k": w(k[1], (D, hkv * dh)),
               "v": w(k[2], (D, hkv * dh)), "g": w(k[3], (D, hq * dh)),
               "o": w(k[4], (hq * dh, D)),
               "q_ln": jnp.ones((dh,), pd), "k_ln": jnp.ones((dh,), pd),
               "mlp": {"w1": w(k[5], (D, F)), "w3": w(k[6], (D, F)),
                       "w2": w(k[7], (F, D))}}
        if kind == LIGHTNING:
            out["out_ln"] = jnp.ones((hq * dh,), pd)
        return out

    return {"tok_embed": w(keys[0], (cfg.vocab_size, D)),
            "lm_head": w(keys[1], (D, cfg.vocab_size)),
            "final_ln": jnp.ones((D,), pd),
            "layers": [layer(keys[2 + i], kind)
                       for i, kind in enumerate(cfg.mixer_types)]}


# ------------------------------------------------------------------- pieces
def _scaled(y, by: float):
    return (y.astype(jnp.float32) * by).astype(y.dtype)


def _embed(params, tokens, cfg: SparseLinearConfig):
    return _scaled(params["tok_embed"][tokens].astype(cfg.dtype),
                   cfg.scale_emb)


def _head(x, params, cfg: SparseLinearConfig):
    x = _rmsnorm(x, params["final_ln"], cfg.rms_norm_eps)
    return jnp.dot(x, params["lm_head"].astype(cfg.dtype),
                   preferred_element_type=jnp.float32) \
        / (cfg.d_model / cfg.dim_model_base)


def _heads(u, p, name, heads, dim, cfg):
    """u [T, D] through ``p[name]``, as [T, heads, dim]."""
    return _mm(u, p[name], cfg).reshape(u.shape[0], heads, dim)


def _put_rows(pages_of, layer: int, fresh, pages, offs):
    """One entry a row into ``pages_of`` [L, Hkv, P, page, W] at ``layer``:
    fresh [B, Hkv, W] to (pages[b], offs[b]), by patching the tile of 16
    entries around it (models/latent_moe.py::paged_decode says why)."""
    H, page, W = pages_of.shape[1], pages_of.shape[3], pages_of.shape[4]
    tile = 16 if page % 16 == 0 else 1
    rows = jnp.arange(tile)[:, None]

    def one(b, c):
        base = offs[b] // tile * tile
        where = (layer, 0, pages[b], base, 0)
        old = lax.dynamic_slice(c, where, (1, H, 1, tile, W))
        return lax.dynamic_update_slice(c, jnp.where(
            rows == offs[b] - base, fresh[b][None, :, None, None, :], old),
            where)

    return lax.fori_loop(0, fresh.shape[0], one, pages_of)


def _put_pages(pages_of, layer: int, fresh, pages):
    """Whole pages into ``pages_of`` at ``layer``: fresh [n * page, Hkv, W]
    to the pages ``pages`` [n], a head at a time (the whole K/V width at once
    would have the compiler lay the pool out as ``fresh`` is, and copy it)."""
    page = pages_of.shape[3]
    for j in range(pages.shape[0]):
        for h in range(pages_of.shape[1]):
            pages_of = lax.dynamic_update_slice(
                pages_of, fresh[None, None, None, j * page:(j + 1) * page, h],
                (layer, h, pages[j], 0, 0))
    return pages_of


def _sum_rows(pages_of, layer: int, pages, offs, n: int):
    """Each row's sum of the ``n`` entries from (pages[b], offs[b]) of
    ``pages_of`` [L, Hkv, P, page, W] at ``layer``, float32 [B, Hkv, W]: a
    slice a row, as :func:`_put_rows` writes (a gather from the pool would
    have the compiler pick a layout of its own for the whole pool, and copy
    the pool into it)."""
    H, W = pages_of.shape[1], pages_of.shape[4]

    def one(b, acc):
        got = lax.dynamic_slice(pages_of, (layer, 0, pages[b], offs[b], 0),
                                (1, H, 1, n, W))
        return acc.at[b].set(jnp.sum(got.astype(jnp.float32), (0, 2, 3)))

    return lax.fori_loop(0, pages.shape[0], one,
                         jnp.zeros((pages.shape[0], H, W), jnp.float32))


def _window_means(keys, cfg: SparseLinearConfig):
    """keys [n * stride + (m - 1) * stride, Hkv, D] -> the ``n`` pooled keys
    whose windows (``m`` strides each) start at every stride of it, float32
    means of ``kernel_size`` keys."""
    stride, m = cfg.kernel_stride, cfg.kernel_size // cfg.kernel_stride
    sums = jnp.sum(keys.astype(jnp.float32).reshape(
        (-1, stride) + keys.shape[1:]), axis=1)
    n = sums.shape[0] - (m - 1)
    return sum(sums[i:i + n] for i in range(m)) / cfg.kernel_size


# ----------------------------------------------------- a walk over the layers
def _walk(params, pool, tokens, positions, parts, cfg: SparseLinearConfig):
    """The layers over ``tokens`` [T] at ``positions`` [T], in ``parts``:
    each a dict of ``rows`` (of the T), ``groups`` (groups, queries a group),
    ``table`` (block-table rows [groups, pages a row]), ``live`` (the queries
    that count, [rows]), ``write(pool, j, k, v)`` (a sparse layer's k and v
    of the part's rows, and the pooled keys they complete, into the pool) and
    ``recur(state, j, q, k, v)`` (a lightning layer's recurrence over the
    part's rows -> (y [rows, heads, dim] float32, state)). In every sparse
    layer each part writes first, then its queries read the pool alone.
    Returns (hidden [T, D] before the final norm, pool, counts a part: the
    blocks its live queries' K/V groups attended and those that held a
    position up to each, and its live queries before ``dense_len``, summed
    over the sparse layers)."""
    H, R, Hkv, Dh = cfg.n_heads, cfg.n_heads // cfg.kv_heads, cfg.kv_heads, \
        cfg.head_dim
    Hl, Dl = cfg.lin_heads, cfg.lin_head_dim
    eps, r = cfg.rms_norm_eps, cfg.residual_scale
    x = _embed(params, tokens, cfg)
    cache = {name: pool[name] for name in ("k", "v", "pooled")}
    state = pool["lin"]
    count = [{"blocks_selected": jnp.int32(0), "blocks_cached": jnp.int32(0),
              "dense_queries": jnp.int32(0)} for _ in parts]
    j_sparse = j_lin = 0
    for p, kind in zip(params["layers"], cfg.mixer_types):
        u = _rmsnorm(x, p["ln"], eps)
        gate = jax.nn.sigmoid(_mm(u, p["g"], cfg).astype(jnp.float32))
        if kind == SPARSE:
            q = _rmsnorm(_heads(u, p, "q", H, Dh, cfg), p["q_ln"], eps)
            # a K/V head at a time: a chunk's page of one head is then a
            # slice of a projection as it comes out (written from a slice
            # across the heads, the compiler lays the whole pool out as the
            # projection is, and copies it)
            k = jnp.stack([_rmsnorm(_mm(u, p["k"][:, h * Dh:(h + 1) * Dh],
                                        cfg), p["k_ln"], eps)
                           for h in range(Hkv)], 1)
            v = jnp.stack([_mm(u, p["v"][:, h * Dh:(h + 1) * Dh], cfg)
                           for h in range(Hkv)], 1)
            with jax.named_scope("sparse_kv_write"):
                for part in parts:
                    rows = part["rows"]
                    cache = part["write"](cache, j_sparse, k[rows], v[rows])
            outs = []
            for part, mine_count in zip(parts, count):
                rows, (G, n) = part["rows"], part["groups"]
                at = positions[rows].reshape(G, n)
                mine = q[rows].reshape(G, n, Hkv, R, Dh)
                with jax.named_scope("block_scores"):
                    scores = block_scores(
                        mine, cache["pooled"], part["table"], at,
                        layer=j_sparse, stride=cfg.kernel_stride,
                        window=cfg.kernel_size, block=cfg.block_size,
                        scale=Dh ** -0.5)
                with jax.named_scope("block_select"):
                    chosen = block_select(
                        scores, at, top_k=cfg.top_k, block=cfg.block_size,
                        init_blocks=cfg.init_blocks, local=cfg.window_size,
                        dense_len=cfg.dense_len)
                with jax.named_scope("block_sparse_attention"):
                    o = block_sparse_attention(
                        mine, cache["k"], cache["v"], part["table"], chosen,
                        at, layer=j_sparse, block=cfg.block_size,
                        scale=Dh ** -0.5, most=cfg.most_blocks,
                        live=part["live"] if n == 1 else None)
                outs.append(o.reshape(G * n, H * Dh))
                live = part["live"].reshape(G, n)
                mine_count["blocks_selected"] += jnp.sum(
                    jnp.where(live[..., None, None], chosen, False),
                    dtype=jnp.int32)
                mine_count["blocks_cached"] += Hkv * jnp.sum(
                    jnp.where(live, at // cfg.block_size + 1, 0),
                    dtype=jnp.int32)
                mine_count["dense_queries"] += jnp.sum(
                    live & (at < cfg.dense_len), dtype=jnp.int32)
            o = jnp.concatenate(outs).astype(jnp.float32)
            j_sparse += 1
        else:
            q = _rmsnorm(_heads(u, p, "q", Hl, Dl, cfg), p["q_ln"], eps)
            k = _rmsnorm(_heads(u, p, "k", Hl, Dl, cfg), p["k_ln"], eps)
            if cfg.lin_use_rope:
                q = _rope(q, positions, cfg.rope_theta)
                k = _rope(k, positions, cfg.rope_theta)
            v = _heads(u, p, "v", Hl, Dl, cfg)
            ys = []
            for part in parts:
                rows = part["rows"]
                y, state = part["recur"](state, j_lin, q[rows], k[rows],
                                         v[rows])
                ys.append(y)
            y = jnp.concatenate(ys) * Dl ** -0.5              # [T, Hl, Dl]
            o = _rmsnorm(y.reshape(y.shape[0], Hl * Dl), p["out_ln"], eps)
            j_lin += 1
        x = x + _scaled(_mm((o * gate).astype(cfg.dtype), p["o"], cfg), r)
        # the next layer writes the pool only once this one has read it:
        # without the barrier nothing orders the two, and the compiler
        # copies the pool to be safe
        x, cache, state = lax.optimization_barrier((x, cache, state))
        x = x + _scaled(_swiglu(_rmsnorm(x, p["mlp_ln"], eps), p["mlp"], cfg),
                        r)
    return x, dict(pool, **cache, lin=state), count


def _lin_inputs(q, k, v, cfg: SparseLinearConfig):
    """ops/ssm.py's operands of a lightning layer: x = v, dt = 1, A =
    -slope, B = k, C = q, D = 0."""
    ones = jnp.ones(v.shape[:2], jnp.float32)
    return (v, ones, -decay_rates(cfg), k, q,
            jnp.zeros((cfg.lin_heads,), jnp.float32))


def _row_part(at, positions, page_table, live, sink, page,
              cfg: SparseLinearConfig, update: str):
    """The decode rows' part of a walk: rows ``at`` of its tokens, a group
    of one query each; a row's k and v go to its own position (past the
    table's width: to the sink), and where its token completes a window,
    the window's pooled key to the entry of the window's first position;
    a lightning layer moves the live rows' state by one token in the
    kernel named ``update``."""
    width, stride = page_table.shape[1], cfg.kernel_stride

    def page_of(pos):  # pos [B, ...]: each row's through its own table
        inside = jnp.minimum(pos // page, width - 1).reshape(pos.shape[0], -1)
        return jnp.where((pos >= 0) & (pos // page < width),
                         jnp.take_along_axis(page_table, inside, 1).reshape(
                             pos.shape), sink)

    start = positions + 1 - cfg.kernel_size     # the window ending here
    complete = live & ((positions + 1) % stride == 0) & (start >= 0)

    def write(cache, j, k, v):
        pages, offs = page_of(positions), positions % page
        cache = dict(cache, k=_put_rows(cache["k"], j, k, pages, offs),
                     v=_put_rows(cache["v"], j, v, pages, offs))
        # the window's keys, this token's among them, from the pool: a
        # stride at a time, each inside one page
        mean = sum(
            _sum_rows(cache["k"], j, page_of(at), at % page, stride)
            for at in (jnp.maximum(start, 0) + i * stride
                       for i in range(cfg.kernel_size // stride))) \
            / cfg.kernel_size
        return dict(cache, pooled=_put_rows(
            cache["pooled"], j, mean.astype(cache["pooled"].dtype),
            jnp.where(complete, page_of(start), sink), start % page // stride))

    def recur(state, j, q, k, v):
        y, state, _ = ssm.ssm_decode_update(
            state, *_lin_inputs(q, k, v, cfg), live, layer=j, name=update)
        return y, state

    return {"rows": at, "groups": (positions.shape[0], 1),
            "table": page_table, "live": live, "write": write,
            "recur": recur}


def _counts(cfg: SparseLinearConfig, count, n_rows, n_queries,
            prefix: str = ""):
    """What a step counted of itself, int32, ``n_rows`` the live decode rows
    and ``n_queries`` every live query: ``lin_layer_steps`` (the lightning
    layers' calls of the decode update), ``lin_rows_stepped`` (the live rows
    they moved, summed over them), ``block_select_steps`` (sparse layers that
    selected for a query), and ``count``'s, summed over the parts:
    ``blocks_selected`` and ``blocks_cached`` (the blocks each live query's
    K/V groups attended, and those that held a position up to it, summed
    over the sparse layers and the K/V heads) and ``dense_queries`` (queries
    before ``dense_len``, summed over the sparse layers)."""
    out = {k: sum(c[k] for c in count) for k in count[0]}
    out.update(lin_layer_steps=jnp.int32(cfg.n_lin),
               lin_rows_stepped=n_rows * cfg.n_lin,
               block_select_steps=(n_queries > 0).astype(jnp.int32)
               * cfg.n_sparse)
    return {prefix + k: v for k, v in out.items()}


# --------------------------------------------------- what the engine asks for
def cache_spec(cfg: SparseLinearConfig) -> Dict[str, Tuple]:
    """What a token leaves in the cache, as the page pool lays it out: name
    -> (dims before the pages, dims after a page's entries, dtype[, stride]).
    ``k`` and ``v`` of the sparse layers' K/V heads, a position each, and
    ``pooled``, the pooled keys, **an entry a ``kernel_stride`` positions**
    (serve/kv_cache.py's strided array). A page id is a page of all three."""
    lead, trail = (cfg.n_sparse, cfg.kv_heads), (cfg.head_dim,)
    return {"k": (lead, trail, cfg.dtype), "v": (lead, trail, cfg.dtype),
            "pooled": (lead, trail, cfg.dtype, cfg.kernel_stride)}


def state_spec(cfg: SparseLinearConfig) -> Dict[str, Tuple]:
    """What a slot holds whatever its length: ``lin``, the lightning
    layers' states, float32 [layers, slots, heads, dim, dim] (key dimension
    first: ops/ssm.py's layout)."""
    d = cfg.lin_head_dim
    return {"lin": ((cfg.n_lin,), (cfg.lin_heads, d, d), jnp.float32)}


def prefill_takes_kernel(cfg: SparseLinearConfig, n_tokens: int) -> bool:
    """Never: a chunk attends where a decode row does (:func:`mixed_step`)."""
    return False


def paged_decode(params, tokens, pool, positions, lengths, page_table,
                 cfg: SparseLinearConfig):
    """One decode token a row against the pool, read and written in place
    (serve/kv_cache.py): ``pool`` holds :func:`cache_spec`'s three arrays,
    whose last page is the sink, and :func:`state_spec`'s state. Row ``i``'s
    token sits at ``positions[i]`` with ``lengths[i]`` positions cached
    before it; an idle row has length 0 and a table row of sink entries: it
    writes the sink, reads nothing, and its state is neither fetched nor
    moved. Returns (logits [B, V] fp32, pool, :func:`_counts`'s counts over
    the live rows)."""
    live = lengths > 0
    sink, page = pool["k"].shape[2] - 1, pool["k"].shape[3]
    part = _row_part(slice(None), positions, page_table, live, sink, page,
                     cfg, "lightning_decode_update")
    x, pool, count = _walk(params, pool, tokens, positions, [part], cfg)
    with jax.named_scope("head_sample"):  # the engine's sampler joins it
        logits = _head(x, params, cfg)
    n_live = jnp.sum(live, dtype=jnp.int32)
    return logits, pool, _counts(cfg, count, n_live, n_live)


def mixed_step(params, pool, chunk_tokens, chunk_pages, chunk_last, tokens,
               positions, lengths, page_table, cfg: SparseLinearConfig, *,
               chunk_index, slot):
    """One chunk of one row's prompt and one decode token a live row, in one
    pass over the layers (models/__init__.py says what each argument is;
    ``slot`` is the row being prefilled, whose state the chunk continues).

    Embedding, norms, every projection and the MLP run once over the ``C +
    B`` rows, the chunk's first. In a sparse layer the chunk's k and v go to
    its whole pages and the pooled keys of the windows that end inside it to
    their entries (the first window starts in the page before the chunk,
    whose last keys are read back from the pool; a chunk's last pooled
    entries, whose windows end in the next chunk, are left to it or to the
    decode step that completes them), the decode rows' as in
    :func:`paged_decode`; then the chunk, one group of ``C`` queries on the
    prompt's row of the table, and the decode rows, a group of one each,
    score, select and attend. In a lightning layer the live rows' states
    move by one token (``lightning_mixed_update``: whoever counts the decode
    program's token-steps by ``lightning_decode_update`` counts none here)
    and the chunk is scanned (``ssd_scan``) from the slot's state, **from
    zeros where ``chunk_index == 0``** (what an earlier request left there is
    never read), stopping at its real positions; the slot's entry then holds
    the state as of its last real position. ``chunk_index`` is a run-time
    int32: ONE program.

    Returns (logits [B + 1, V] fp32: the decode rows', then the chunk's at
    ``chunk_last``; the pool; counts under names of the mixed step's own:
    :func:`_counts`' with ``mixed_`` before each, over the chunk's real
    positions and the live rows; ``mixed_chunk_blocks_selected`` and
    ``mixed_chunk_blocks_cached``, the chunk's share of theirs;
    ``mixed_chunk_positions``, the chunk's real positions, and
    ``mixed_chunk_positions_cached``, the positions they had cached between
    them)."""
    C, page = chunk_tokens.shape[0], pool["k"].shape[3]
    sink, per, stride = pool["k"].shape[2] - 1, C // page, cfg.kernel_stride
    back = cfg.kernel_size - stride       # keys a window reaches behind
    chunk_index = jnp.asarray(chunk_index, jnp.int32)
    slot = jnp.asarray(slot, jnp.int32)
    n_real = jnp.asarray(chunk_last, jnp.int32) + 1
    first = chunk_index == 0
    at = chunk_index * C + jnp.arange(C, dtype=jnp.int32)
    now = lax.dynamic_slice_in_dim(chunk_pages, chunk_index * per, per)
    before = jnp.where(first, sink, chunk_pages[jnp.maximum(
        chunk_index * per - 1, 0)])
    live = lengths > 0
    real = jnp.arange(C) < n_real

    def write(cache, j, k, v):
        cache = dict(cache, k=_put_pages(cache["k"], j, k, now),
                     v=_put_pages(cache["v"], j, v, now))
        # the keys of the page before that the first window reaches, a head
        # at a time as they are written
        behind = jnp.stack([lax.dynamic_slice(
            cache["k"], (j, h, before, page - back, 0),
            (1, 1, 1, back, cfg.head_dim))[0, 0, 0]
            for h in range(cfg.kv_heads)], 1)           # [back, Hkv, D]
        behind = jnp.where(first, jnp.zeros_like(behind), behind)
        means = _window_means(jnp.concatenate([behind, k]), cfg).astype(
            cache["pooled"].dtype)            # [C / stride, Hkv, D]
        lag = back // stride                  # windows that start before
        mine = jnp.pad(means[lag:], ((0, lag), (0, 0), (0, 0)))
        pooled = _put_pages(cache["pooled"], j, mine, now)
        return dict(cache, pooled=lax.dynamic_update_slice(
            pooled, means[None, :lag].transpose(0, 2, 1, 3)[:, :, None],
            (j, 0, before, page // stride - lag, 0)))

    def recur(state, j, q, k, v):
        h0 = lax.dynamic_slice(state, (j, slot, 0, 0, 0),
                               (1, 1) + state.shape[2:])[0, 0]
        y, h = ssm.ssd_scan(*_lin_inputs(q, k, v, cfg), true_len=n_real,
                            h0=jnp.where(first, jnp.zeros_like(h0), h0))
        return y, lax.dynamic_update_slice(state, h[None, None],
                                           (j, slot, 0, 0, 0))

    rows = _row_part(slice(C, None), positions, page_table, live, sink, page,
                     cfg, "lightning_mixed_update")
    chunk = {"rows": slice(0, C), "groups": (1, C),
             "table": chunk_pages[None], "live": real, "write": write,
             "recur": recur}
    x, pool, count = _walk(
        params, pool, jnp.concatenate([chunk_tokens, tokens]),
        jnp.concatenate([at, positions.astype(jnp.int32)]), [chunk, rows],
        cfg)
    with jax.named_scope("head_sample"):  # the engine's sampler joins it
        logits = _head(jnp.concatenate(
            [x[C:], lax.dynamic_slice_in_dim(x, chunk_last, 1)]), params, cfg)
    n_live = jnp.sum(live, dtype=jnp.int32)
    counts = _counts(cfg, count, n_live, n_real + n_live, "mixed_")
    counts.update({"mixed_chunk_" + k: v for k, v in count[0].items()
                   if k != "dense_queries"})
    counts["mixed_chunk_positions"] = n_real
    counts["mixed_chunk_positions_cached"] = jnp.sum(
        jnp.where(real, at + 1, 0), dtype=jnp.int32)
    return logits, pool, counts
