"""Decode attention over a paged KV pool, read in place through a block table.

The serve engine keeps one resident pool of KV pages on the device
(serve/kv_cache.py): K and V as ``[L, Hkv, P, page_tokens, Dh]``. A decode
step has one query token a row; row ``b`` attends over its first
``lengths[b]`` cached positions, which lie in the pages
``page_indices[b, :ceil(lengths[b] / page_tokens)]`` of layer ``layer``, and
beside them over the token it is computing now (``k_cur`` / ``v_cur``), so
that the step writes the pool once for all layers and only reads it inside
the layer loop.

  - the Pallas kernel: grid ``(B,)``, lengths, block table and layer index as
    scalar prefetch, the pool left in HBM (``pl.ANY``) and fetched a page at a
    time, all KV heads of the page in one strided DMA, double buffered. A
    page at or past ``ceil(length / page_tokens)`` is never fetched, a row of
    length 0 reads nothing, and the ``H // Hkv`` query heads of a group share
    the K/V block they load (no ``jnp.repeat``). bf16 operands, f32 scores,
    softmax and accumulation.
  - ``paged_attention_reference``: the same reading in plain ``jax.numpy``
    (gather the row's pages, mask by length). The engine's CPU path, the
    fallback for a shape the kernel cannot tile, and the oracle of the
    kernel's tests.

Which one runs is decided from the platform and the shape, never by a flag;
``"interpret"`` (the kernel under the Pallas interpreter) only by name.

:func:`latent_attention`, below the K/V kernel, is the same reading of a pool
of latent-attention pages: one vector a token that is key and value of every
head (``name="latent_decode_attention"``).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from .flash_attention import _NEG_INF, _on_tpu

# rows of a query-head group as the kernel sees it: a whole bf16 tile
_GROUP_ROWS = 16


def _as_pool(pages):
    """[Hkv, P, page, Dh] (one layer) -> [1, Hkv, P, page, Dh]."""
    return pages[None] if pages.ndim == 4 else pages


def paged_attention_reference(q, k_pages, v_pages, lengths, page_indices,
                              layer=0, k_cur=None, v_cur=None,
                              scale: Optional[float] = None):
    """Plain jnp reading of the block table; see :func:`paged_attention`."""
    k_pages, v_pages = _as_pool(k_pages), _as_pool(v_pages)
    B, H, Dh = q.shape
    _, Hkv, _, page, _ = k_pages.shape
    G, T = H // Hkv, page_indices.shape[1] * page
    scale = scale if scale is not None else Dh ** -0.5

    def rows(pages):  # the row's pages, in table order: [B, Hkv, T, Dh]
        got = pages[layer, :, page_indices]          # [B, W, Hkv, page, Dh]
        return jnp.moveaxis(got, 2, 1).reshape(B, Hkv, T, Dh)

    qg = q.reshape(B, Hkv, G, Dh)
    seen = jnp.arange(T)[None, :] < lengths[:, None]            # [B, T]
    s = jnp.einsum("bhgd,bhtd->bhgt", qg, rows(k_pages),
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(seen[:, None, None, :], s, _NEG_INF)
    # what lies past a row's length is never read, whatever it holds
    v = jnp.where(seen[:, None, :, None], rows(v_pages), 0)
    if k_cur is not None:
        s_cur = jnp.einsum("bhgd,bhd->bhg", qg, k_cur,
                           preferred_element_type=jnp.float32) * scale
        s = jnp.concatenate([s, s_cur[..., None]], axis=-1)
        v = jnp.concatenate([v, v_cur[:, :, None, :]], axis=2)
    p = jax.nn.softmax(s, axis=-1)
    if k_cur is None:  # a row with nothing to attend over reads 0
        p = jnp.where(lengths[:, None, None, None] > 0, p, 0.0)
    o = jnp.einsum("bhgt,bhtd->bhgd", p.astype(v.dtype), v)
    return o.reshape(B, H, Dh).astype(q.dtype)


# ---------------------------------------------------------------------- kernel
def _kernel(lengths_ref, table_ref, layer_ref, q_ref, *refs, page, width,
            scale, has_cur):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if has_cur:
        kc_ref, vc_ref, k_hbm, v_hbm, o_ref, k_buf, v_buf, sem = refs
    else:
        k_hbm, v_hbm, o_ref, k_buf, v_buf, sem = refs
    b = pl.program_id(0)
    length = lengths_ref[b]
    layer = layer_ref[0]
    n_pages = lax.div(length + (page - 1), page)

    def copies(i, slot):
        """The DMAs of the row's i-th page, every KV head of it, K and V."""
        pid = table_ref[b * width + i]
        return (pltpu.make_async_copy(k_hbm.at[layer, :, pid],
                                      k_buf.at[slot], sem.at[0, slot]),
                pltpu.make_async_copy(v_hbm.at[layer, :, pid],
                                      v_buf.at[slot], sem.at[1, slot]))

    @pl.when(n_pages > 0)
    def _first():
        for c in copies(0, 0):
            c.start()

    q = q_ref[...]                                   # [Hkv, rows, Dh]
    stat = q.shape[:2] + (1,)
    if has_cur:
        # the token being computed: one more position, seen by every row
        m0 = jnp.sum(q.astype(jnp.float32) * kc_ref[...].astype(jnp.float32),
                     axis=-1, keepdims=True) * scale
        l0 = jnp.ones(stat, jnp.float32)
        acc0 = vc_ref[...].astype(jnp.float32)
    else:
        m0 = jnp.full(stat, _NEG_INF, jnp.float32)
        l0 = jnp.zeros(stat, jnp.float32)
        acc0 = jnp.zeros(q.shape, jnp.float32)

    def body(i, carry):
        m, l, acc = carry
        slot = lax.rem(i, 2)

        @pl.when(i + 1 < n_pages)
        def _next():
            for c in copies(i + 1, 1 - slot):
                c.start()

        for c in copies(i, slot):
            c.wait()
        k, v = k_buf[slot], v_buf[slot]              # [Hkv, page, Dh]
        s = jnp.einsum("hgd,htd->hgt", q, k,
                       preferred_element_type=jnp.float32) * scale
        pos = i * page + lax.broadcasted_iota(jnp.int32, s.shape, 2)
        s = jnp.where(pos < length, s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        acc = alpha * acc + jnp.einsum(
            "hgt,htd->hgd", p.astype(v.dtype), v,
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    _, l, acc = lax.fori_loop(0, n_pages, body, (m0, l0, acc0))
    o_ref[...] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def _paged_attention_pallas(q, k_pages, v_pages, lengths, page_indices,
                            layer, k_cur, v_cur, scale, interpret):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, Dh = q.shape
    _, Hkv, _, page, _ = k_pages.shape
    G, width = H // Hkv, page_indices.shape[1]
    rows = -(-G // _GROUP_ROWS) * _GROUP_ROWS
    has_cur = k_cur is not None

    def grouped(x):  # [B, H, Dh] -> [B, Hkv, rows, Dh], zero rows below G
        x = x.reshape(B, Hkv, G, Dh)
        return jnp.pad(x, ((0, 0), (0, 0), (0, rows - G), (0, 0)))

    def spread(x):  # [B, Hkv, Dh] -> one copy a row of the group
        return jnp.broadcast_to(x[:, :, None, :], (B, Hkv, rows, Dh))

    row_block = pl.BlockSpec((None, Hkv, rows, Dh),
                             lambda b, *_: (b, 0, 0, 0))
    in_pool = pl.BlockSpec(memory_space=pl.ANY)
    operands = [grouped(q)]
    if has_cur:
        operands += [spread(k_cur), spread(v_cur)]
    out = pl.pallas_call(
        functools.partial(_kernel, page=page, width=width, scale=scale,
                          has_cur=has_cur),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B,),
            in_specs=[row_block] * len(operands) + [in_pool, in_pool],
            out_specs=row_block,
            scratch_shapes=[
                pltpu.VMEM((2, Hkv, page, Dh), k_pages.dtype),
                pltpu.VMEM((2, Hkv, page, Dh), v_pages.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
            ]),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, rows, Dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_decode_attention",
    )(lengths.astype(jnp.int32), page_indices.reshape(-1).astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), *operands, k_pages, v_pages)
    return out[:, :, :G].reshape(B, H, Dh)


def kernel_takes(q, k_pages) -> bool:
    """Can the compiled kernel tile these shapes on a TPU? A page is a
    whole number of sublane tiles of its dtype and a head fills the lanes."""
    sublanes = 8 * 4 // jnp.dtype(k_pages.dtype).itemsize
    return (q.shape[-1] % 128 == 0 and k_pages.shape[-2] % sublanes == 0
            and q.dtype == k_pages.dtype)


def paged_attention(q, k_pages, v_pages, lengths, page_indices, layer=0,
                    k_cur=None, v_cur=None, scale: Optional[float] = None,
                    use_pallas: Optional[str] = None):
    """One decode token a row against a paged KV pool.

    ``q`` [B, H, Dh]; ``k_pages`` / ``v_pages`` [L, Hkv, P, page_tokens, Dh]
    with ``layer`` (a traced scalar is fine: the layer is read where it
    lies, not sliced out), or one layer's [Hkv, P, page_tokens, Dh];
    ``lengths`` int32 [B], the cached positions row ``b`` attends over;
    ``page_indices`` int32 [B, pages_per_row], of which the row's first
    ``ceil(lengths[b] / page_tokens)`` entries are read. ``k_cur`` /
    ``v_cur`` [B, Hkv, Dh], if given, are one more position every row
    sees (the token being computed); without them a row of length 0 reads 0.
    Returns [B, H, Dh] in ``q``'s dtype.

    ``use_pallas``: "on" (compiled kernel), "interpret" (the kernel under
    the Pallas interpreter, only ever by name), "off" (the plain reading),
    or None = auto: the kernel on a TPU for a shape it can tile
    (:func:`kernel_takes`), the plain reading anywhere else.
    """
    k_pages, v_pages = _as_pool(k_pages), _as_pool(v_pages)
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    if use_pallas is None:
        use_pallas = "on" if _on_tpu() and kernel_takes(q, k_pages) \
            else "off"
    if use_pallas == "off":
        return paged_attention_reference(q, k_pages, v_pages, lengths,
                                         page_indices, layer, k_cur, v_cur,
                                         scale)
    return _paged_attention_pallas(q, k_pages, v_pages, lengths,
                                   page_indices, layer, k_cur, v_cur, scale,
                                   interpret=(use_pallas == "interpret"))


# ------------------------------------------------------------ latent pages
# A latent-attention (MLA) cache holds one vector a token and layer, shared by
# every head: the normed compressed KV and the rotary key side by side
# (models/latent_moe.py). In absorbed form a head's query has the vector's
# width, the vector is the key, and its first ``value_width`` columns are the
# value: one page fetch serves as both, for all heads.
def latent_attention_reference(q, pages, lengths, page_indices, cur, *,
                               layer=0, value_width: int, scale: float):
    """Plain jnp reading of the block table; see :func:`latent_attention`."""
    B, H, W = q.shape
    page = pages.shape[2]
    T = page_indices.shape[1] * page
    rows = pages[layer][page_indices].reshape(B, T, W)  # in table order
    seen = jnp.arange(T)[None, :] < lengths[:, None]            # [B, T]
    s = jnp.einsum("bhw,btw->bht", q, rows,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(seen[:, None, :], s, _NEG_INF)
    # what lies past a row's length is never read, whatever it holds
    v = jnp.where(seen[:, :, None], rows[..., :value_width], 0)
    s_cur = jnp.einsum("bhw,bw->bh", q, cur,
                       preferred_element_type=jnp.float32) * scale
    s = jnp.concatenate([s, s_cur[..., None]], axis=-1)
    v = jnp.concatenate([v, cur[:, None, :value_width]], axis=1)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bht,btv->bhv", p.astype(v.dtype), v).astype(q.dtype)


def _latent_kernel(lengths_ref, table_ref, layer_ref, q_ref, cur_ref,
                   pool_hbm, o_ref, buf, sem, *, page, width, value_width,
                   scale):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b = pl.program_id(0)
    length = lengths_ref[b]
    layer = layer_ref[0]
    n_pages = lax.div(length + (page - 1), page)

    def copy(i, slot):
        """The DMA of the row's i-th page: key and value of every head."""
        return pltpu.make_async_copy(
            pool_hbm.at[layer, table_ref[b * width + i]], buf.at[slot],
            sem.at[slot])

    @pl.when(n_pages > 0)
    def _first():
        copy(0, 0).start()

    q = q_ref[...]                                   # [rows, W]
    cur = cur_ref[...].astype(jnp.float32)
    # the token being computed: one more position, seen by every row
    m0 = jnp.sum(q.astype(jnp.float32) * cur, axis=-1, keepdims=True) * scale
    l0 = jnp.ones_like(m0)
    acc0 = cur[:, :value_width]

    def body(i, carry):
        m, l, acc = carry
        slot = lax.rem(i, 2)

        @pl.when(i + 1 < n_pages)
        def _next():
            copy(i + 1, 1 - slot).start()

        copy(i, slot).wait()
        kv = buf[slot]                               # [page, W]
        s = lax.dot_general(q, kv, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        pos = i * page + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < length, s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        acc = alpha * acc + jnp.dot(p.astype(kv.dtype), kv[:, :value_width],
                                    preferred_element_type=jnp.float32)
        return m_new, l, acc

    _, l, acc = lax.fori_loop(0, n_pages, body, (m0, l0, acc0))
    o_ref[...] = (acc / l).astype(o_ref.dtype)


def _latent_attention_pallas(q, pages, lengths, page_indices, cur, layer,
                             value_width, scale, interpret):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, W = q.shape
    page, width = pages.shape[2], page_indices.shape[1]
    rows = -(-H // _GROUP_ROWS) * _GROUP_ROWS
    out = pl.pallas_call(
        functools.partial(_latent_kernel, page=page, width=width,
                          value_width=value_width, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B,),
            in_specs=[pl.BlockSpec((None, rows, W), lambda b, *_: (b, 0, 0)),
                      pl.BlockSpec((None, rows, W), lambda b, *_: (b, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((None, rows, value_width),
                                   lambda b, *_: (b, 0, 0)),
            scratch_shapes=[pltpu.VMEM((2, page, W), pages.dtype),
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=jax.ShapeDtypeStruct((B, rows, value_width), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="latent_decode_attention",
    )(lengths.astype(jnp.int32), page_indices.reshape(-1).astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1),
      jnp.pad(q, ((0, 0), (0, rows - H), (0, 0))),   # zero rows below H
      jnp.broadcast_to(cur[:, None, :], (B, rows, W)), pages)
    return out[:, :H]


def latent_kernel_takes(q, pages, value_width: int) -> bool:
    """Can the compiled latent kernel tile these shapes on a TPU? The
    vector and its value part fill whole lanes, a page whole sublanes."""
    sublanes = 8 * 4 // jnp.dtype(pages.dtype).itemsize
    return (q.shape[-1] % 128 == 0 and value_width % 128 == 0
            and pages.shape[2] % sublanes == 0 and q.dtype == pages.dtype)


def latent_attention(q, pages, lengths, page_indices, cur, *, layer=0,
                     value_width: int, scale: float,
                     use_pallas: Optional[str] = None):
    """One decode token a row against a pool of latent pages, read in place.

    ``q`` [B, H, W]: every head's absorbed query at the cached vector's
    width; ``pages`` [L, P, page_tokens, W], of which ``layer`` is read
    where it lies; ``lengths`` int32 [B]; ``page_indices`` int32
    [B, pages_per_row], of which the row's first ``ceil(lengths[b] /
    page_tokens)`` entries are fetched, each once, as key (all ``W``
    columns) and value (the first ``value_width``) of all ``H`` heads;
    ``cur`` [B, W], the vector of the token being computed, which every row
    sees beside its cache. Returns [B, H, value_width] in ``q``'s dtype.
    ``use_pallas`` as :func:`paged_attention`'s: None = the kernel on a TPU
    for a shape it can tile, the plain reading anywhere else."""
    if use_pallas is None:
        use_pallas = "on" if _on_tpu() and latent_kernel_takes(
            q, pages, value_width) else "off"
    if use_pallas == "off":
        return latent_attention_reference(
            q, pages, lengths, page_indices, cur, layer=layer,
            value_width=value_width, scale=scale)
    return _latent_attention_pallas(q, pages, lengths, page_indices, cur,
                                    layer, value_width, scale,
                                    interpret=(use_pallas == "interpret"))


# ------------------------------------------- latent pages under a selection
# (Everything from here on was appended below the kernels above, and the
# module's docstring left as it was: a kernel's place in this file is part of
# its compiled program's cache key.)
# A learned indexer (DeepSeek-V3.2's sparse attention, GLM-5.2) lets a query
# attend only the ``top_k`` cached positions whose index score is largest.
# Three steps, each a plain ``jax.numpy`` form (the contract, the CPU's path
# and the kernels' oracle) and a Pallas kernel of the same name:
#
#   index_scores             I[t, s] = sum_j w[t, j] relu(q[t, j] . k[s]), s <= t
#   index_select             the top_k largest a query, ties to the lower
#                            position, as an additive mask (0 / _NEG_INF)
#   sparse_latent_attention  latent attention under that mask: absorbed for
#                            a decode row, expanded for a chunk (last section)
# Queries come in groups that share a row of the block table and lie at
# consecutive positions: a chunk of a prompt is one group of C queries, a
# decode step B groups of one. Every step reads the pool only: a query's own
# key and vector are written before it scores and attends.
#
# The plain forms do what the words say (``lax.top_k``, then the selected
# rows gathered from the pages). The kernels compute the same numbers another
# way, because a TPU gathers 1,280-byte rows at a fraction of its bandwidth
# and sorts slowly: the selection is a threshold (the k-th largest score by a
# search over the bits of the float, ties cut at a position by a second
# search), and the attention walks the row's pages in order with the mask
# added to the scores, skipping the pages past the group's last position.
_SELECT_ROWS = 16       # queries a tile of the selection kernel (a bf16 tile)
_ATTEND_QUERIES = 16    # queries a tile of the attention kernel
_KEY_BLOCK = 1024       # cached positions a grid step of either walk


def index_scores_reference(q, w, pages, page_indices, positions, *, layer=0):
    """Plain jnp reading of the block table; see :func:`index_scores`."""
    G, T = q.shape[0], page_indices.shape[1] * pages.shape[2]
    keys = pages[layer][page_indices].reshape(G, T, q.shape[-1])
    s = jnp.einsum("gnjd,gtd->gnjt", q, keys,
                   preferred_element_type=jnp.float32)
    s = jnp.sum(jax.nn.relu(s) * w[..., None], axis=2)
    return jnp.where(jnp.arange(T) <= positions[..., None], s, _NEG_INF)


def _index_scores_kernel(table_ref, first_ref, layer_ref, q_ref, w_ref, k_ref,
                         o_ref, *, bq, tk, heads):
    import jax.experimental.pallas as pl

    g, qi, ki = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    p0 = first_ref[g] + qi * bq               # the tile's first query
    seen = ki * tk <= p0 + bq - 1             # a key no query is behind: none

    @pl.when(seen)
    def _a_block_some_query_sees():
        keys = k_ref[...]                                    # [tk, Di]
        acc = jnp.zeros((bq, tk), jnp.float32)
        for j in range(heads):
            s = lax.dot_general(q_ref[j], keys, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
            acc = acc + jnp.maximum(s, 0.0) * w_ref[:, j:j + 1]
        at_q = p0 + lax.broadcasted_iota(jnp.int32, acc.shape, 0)
        at_k = ki * tk + lax.broadcasted_iota(jnp.int32, acc.shape, 1)
        o_ref[...] = jnp.where(at_k <= at_q, acc, _NEG_INF)

    @pl.when(jnp.logical_not(seen))
    def _a_block_past_every_query():
        o_ref[...] = jnp.full(o_ref.shape, _NEG_INF, jnp.float32)


def _walk(page, width, tk, bq):
    """Index maps of a walk over a row's pages in blocks of ``tk`` positions:
    ``at(g, qi, ki, table, first)`` -> (page id, block inside the page) of
    the ``ki``-th block, held at the last block the tile of queries sees (a
    block past it is skipped, and is not fetched either)."""
    per = page // tk

    def at(g, qi, ki, table, first):
        last = (first[g] + (qi + 1) * bq - 1) // tk
        k = jnp.minimum(ki, last)
        return table[g * width + k // per], k % per

    return at


def _index_scores_pallas(q, w, pages, page_indices, positions, layer,
                         interpret):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    G, n, J, Di = q.shape
    page, width = pages.shape[2], page_indices.shape[1]
    T, tk, bq = width * page, min(_KEY_BLOCK, page), min(256, n)
    at = _walk(page, width, tk, bq)

    def keys_at(g, qi, ki, table, first, layer):
        pid, sub = at(g, qi, ki, table, first)
        return layer[0], pid, sub, 0

    return pl.pallas_call(
        functools.partial(_index_scores_kernel, bq=bq, tk=tk, heads=J),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(G, n // bq, T // tk),
            in_specs=[
                pl.BlockSpec((None, J, bq, Di),
                             lambda g, qi, ki, *_: (g, 0, qi, 0)),
                pl.BlockSpec((None, bq, J),
                             lambda g, qi, ki, *_: (g, qi, 0)),
                pl.BlockSpec((None, None, tk, Di), keys_at)],
            out_specs=pl.BlockSpec((None, bq, tk),
                                   lambda g, qi, ki, *_: (g, qi, ki))),
        out_shape=jax.ShapeDtypeStruct((G, n, T), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3,
            vmem_limit_bytes=64 << 20),
        interpret=interpret,
        name="index_scores",
    )(page_indices.reshape(-1).astype(jnp.int32),
      positions[:, 0].astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1),
      q.transpose(0, 2, 1, 3), w.astype(jnp.float32), pages)


def index_kernel_takes(q, pages) -> bool:
    """Can the compiled scoring kernel tile these shapes on a TPU? A group
    of whole tiles of queries (a chunk: a decode row's one query takes the
    plain form, which is a few small matmuls), an index key that fills the
    lanes, a page of whole key blocks."""
    page = pages.shape[2]
    return (q.shape[1] % 256 == 0 and q.shape[-1] % 128 == 0
            and page % min(_KEY_BLOCK, page) == 0 and page % 128 == 0
            and q.dtype == pages.dtype)


def index_scores(q, w, pages, page_indices, positions, *, layer=0,
                 use_pallas: Optional[str] = None):
    """The indexer's score of every cached position a query may attend.

    ``q`` [G, n, J, Di]: ``G`` groups of ``n`` queries, ``J`` index heads;
    ``w`` float32 [G, n, J], the heads' weights; ``pages`` [Lf, P,
    page_tokens, Di], the paged index keys (one key a token), of which
    ``layer`` is read where it lies; ``page_indices`` int32 [G,
    pages_per_row], a row of the block table a group; ``positions`` int32
    [G, n], each query's own position, **consecutive inside a group**: a
    query scores the positions ``s <= positions[g, i]`` of its group's row,
    its own among them (its key is in the pool already). Returns float32
    [G, n, pages_per_row * page_tokens]: ``sum_j w[j] * relu(q[j] . k[s])``
    accumulated in float32, ``_NEG_INF`` past the query's position.
    ``use_pallas`` as :func:`paged_attention`'s."""
    if use_pallas is None:
        use_pallas = "on" if _on_tpu() and index_kernel_takes(q, pages) \
            else "off"
    if use_pallas == "off":
        return index_scores_reference(q, w, pages, page_indices, positions,
                                      layer=layer)
    return _index_scores_pallas(q, w, pages, page_indices, positions, layer,
                                interpret=(use_pallas == "interpret"))


def index_select_reference(scores, top_k: int):
    """Plain jnp selection; see :func:`index_select`."""
    T = scores.shape[-1]
    flat = scores.reshape(-1, T)
    _, idx = lax.top_k(flat, min(top_k, T))     # ties: the lower index first
    hit = jnp.zeros(flat.shape, bool).at[
        jnp.arange(flat.shape[0])[:, None], idx].set(True)
    hit = hit & (flat > _NEG_INF / 2)           # fewer than top_k to choose
    return jnp.where(hit, 0.0, _NEG_INF).astype(jnp.bfloat16).reshape(
        scores.shape)


def _index_select_kernel(s_ref, o_ref, *, top_k):
    s = s_ref[...]                                           # [rows, T]
    T = s.shape[-1]
    bits = lax.bitcast_convert_type(s, jnp.int32)
    # the floats' order in signed integers
    key = jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)
    k = jnp.float32(top_k)

    def count(mask):
        return jnp.sum(mask.astype(jnp.float32), axis=-1, keepdims=True)

    # the k-th largest key, bit by bit: the largest ``lo`` that at least k
    # keys reach
    lo = jnp.where(count(key >= 0) >= k, jnp.int32(0),
                   jnp.int32(-2 ** 31))

    def value_bit(i, lo):
        cand = lo + jnp.left_shift(jnp.int32(1), 30 - i)
        return jnp.where(count(key >= cand) >= k, cand, lo)

    lo = lax.fori_loop(0, 31, value_bit, lo)
    above, tie = key > lo, key == lo
    # of the keys that tie at the threshold, those at the lowest positions
    # fill what is left: the position of the last of them, bit by bit
    left = k - count(above)                                  # at least 1
    at = lax.broadcasted_iota(jnp.int32, s.shape, 1)
    n_bits = max(1, (T - 1).bit_length())

    def place_bit(i, last):
        cand = last | jnp.left_shift(jnp.int32(1), n_bits - 1 - i)
        return jnp.where(count(tie & (at < cand)) <= left - 1.0, cand, last)

    last = lax.fori_loop(0, n_bits, place_bit, jnp.zeros_like(lo))
    hit = (above | (tie & (at <= last))) & (s > _NEG_INF / 2)
    o_ref[...] = jnp.where(hit, 0.0, _NEG_INF).astype(o_ref.dtype)


def _index_select_pallas(scores, top_k, interpret, name="index_select"):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    T = scores.shape[-1]
    flat = scores.reshape(-1, T)
    N = flat.shape[0]
    rows = -(-N // _SELECT_ROWS) * _SELECT_ROWS
    out = pl.pallas_call(
        functools.partial(_index_select_kernel, top_k=top_k),
        grid=(rows // _SELECT_ROWS,),
        in_specs=[pl.BlockSpec((_SELECT_ROWS, T), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((_SELECT_ROWS, T), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, T), jnp.bfloat16),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=64 << 20),
        interpret=interpret,
        name=name,
    )(jnp.pad(flat, ((0, rows - N), (0, 0)), constant_values=_NEG_INF))
    return out[:N].reshape(scores.shape)


def index_select(scores, top_k: int, *, use_pallas: Optional[str] = None):
    """The ``top_k`` positions of largest score a query, as a mask to add to
    attention scores: ``scores`` float32 [..., T] (``_NEG_INF`` where a query
    may not look) -> bfloat16 [..., T], 0 at the selected positions and
    ``_NEG_INF`` elsewhere. A query with no more than ``top_k`` positions to
    choose from selects them all; **ties go to the lower position**
    (``lax.top_k``'s order), so the selection is a function of the scores.
    ``use_pallas`` as :func:`paged_attention`'s: None = the kernel on a TPU
    for a row of whole lanes."""
    if use_pallas is None:
        use_pallas = "on" if _on_tpu() and scores.shape[-1] % 128 == 0 \
            else "off"
    if use_pallas == "off":
        return index_select_reference(scores, top_k)
    return _index_select_pallas(scores, top_k,
                                interpret=(use_pallas == "interpret"))


def sparse_latent_attention_reference(q, pages, page_indices, mask, *,
                                      layer=0, top_k: int, value_width: int,
                                      scale: float):
    """Plain jnp form: the selected rows gathered from the pages; see
    :func:`sparse_latent_attention`."""
    G, n, H, W = q.shape
    page, T = pages.shape[2], mask.shape[-1]
    k, block = min(top_k, T), min(n, 64)

    def attend(args):
        qb, mb = args                             # [G, b, H, W], [G, b, T]
        # the selected positions first, the lower first
        held, idx = lax.top_k(mb.astype(jnp.float32), k)     # [G, b, k]
        pid = jnp.take_along_axis(page_indices[:, None, :], idx // page, 2)
        rows = pages[layer, pid, idx % page]                 # [G, b, k, W]
        s = jnp.einsum("gbhw,gbkw->gbhk", qb, rows,
                       preferred_element_type=jnp.float32) * scale
        p = jax.nn.softmax(s + held[:, :, None, :], axis=-1)
        return jnp.einsum("gbhk,gbkv->gbhv", p.astype(rows.dtype),
                          rows[..., :value_width]).astype(q.dtype)

    pad = (-n) % block
    qs = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    ms = jnp.pad(mask, ((0, 0), (0, pad), (0, 0)))
    ms = ms.at[:, n:, 0].set(0)                  # a padded query sees one
    nb = (n + pad) // block
    out = lax.map(attend, (
        qs.reshape(G, nb, block, H, W).transpose(1, 0, 2, 3, 4),
        ms.reshape(G, nb, block, T).transpose(1, 0, 2, 3)))
    return out.transpose(1, 0, 2, 3, 4).reshape(G, n + pad, H,
                                                value_width)[:, :n]


def _sparse_latent_kernel(table_ref, first_ref, layer_ref, q_ref, mask_ref,
                          kv_ref, o_ref, m_ref, l_ref, acc_ref, *, bq, tk,
                          pair, value_width, scale):
    import jax.experimental.pallas as pl

    g, qi, ki = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _first_block():
        m_ref[...] = jnp.full(m_ref.shape, _NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    @pl.when(ki * tk <= first_ref[g] + (qi + 1) * bq - 1)
    def _a_block_some_query_sees():
        kv = kv_ref[...]                          # [tk, W]: key and value
        H = q_ref.shape[1]
        # ``pair`` queries a matmul: their heads fill the MXU's rows
        for i in range(0, bq, pair):
            rows = pair * H
            q = q_ref[i:i + pair].reshape(rows, q_ref.shape[2])
            s = lax.dot_general(q, kv, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
            mask = mask_ref[i:i + 1, :].astype(jnp.float32)
            if pair == 2:   # each query's own row of the mask
                second = lax.broadcasted_iota(jnp.int32, s.shape, 0) >= H
                mask = jnp.where(
                    second, mask_ref[i + 1:i + 2, :].astype(jnp.float32),
                    mask)
            s = s + mask                                       # [rows, tk]
            m = m_ref[i:i + pair].reshape(rows, 1)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new)
            l_ref[i:i + pair] = (
                alpha * l_ref[i:i + pair].reshape(rows, 1)
                + jnp.sum(p, axis=-1, keepdims=True)).reshape(pair, H, 1)
            acc_ref[i:i + pair] = (
                alpha * acc_ref[i:i + pair].reshape(rows, value_width)
                + jnp.dot(p.astype(kv.dtype), kv[:, :value_width],
                          preferred_element_type=jnp.float32)).reshape(
                              pair, H, value_width)
            m_ref[i:i + pair] = m_new.reshape(pair, H, 1)

    @pl.when(ki == pl.num_programs(2) - 1)
    def _last_block():
        o_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(
            o_ref.dtype)


def _sparse_latent_pallas(q, pages, page_indices, mask, positions, layer,
                          value_width, scale, interpret):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    G, n, H, W = q.shape
    page, width = pages.shape[2], page_indices.shape[1]
    T, tk, bq = width * page, min(_KEY_BLOCK, page), min(_ATTEND_QUERIES, n)
    at = _walk(page, width, tk, bq)

    def kv_at(g, qi, ki, table, first, layer):
        pid, sub = at(g, qi, ki, table, first)
        return layer[0], pid, sub, 0

    def mask_at(g, qi, ki, table, first, layer):
        return g, qi, jnp.minimum(ki, (first[g] + (qi + 1) * bq - 1) // tk)

    return pl.pallas_call(
        functools.partial(_sparse_latent_kernel, bq=bq, tk=tk,
                          pair=2 if bq % 2 == 0 else 1,
                          value_width=value_width, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(G, n // bq, T // tk),
            in_specs=[
                pl.BlockSpec((None, bq, H, W),
                             lambda g, qi, ki, *_: (g, qi, 0, 0)),
                pl.BlockSpec((None, bq, tk), mask_at),
                pl.BlockSpec((None, None, tk, W), kv_at)],
            out_specs=pl.BlockSpec((None, bq, H, value_width),
                                   lambda g, qi, ki, *_: (g, qi, 0, 0)),
            scratch_shapes=[pltpu.VMEM((bq, H, 1), jnp.float32),
                            pltpu.VMEM((bq, H, 1), jnp.float32),
                            pltpu.VMEM((bq, H, value_width), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((G, n, H, value_width), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3,
            vmem_limit_bytes=64 << 20),
        interpret=interpret,
        name="sparse_latent_attention",
    )(page_indices.reshape(-1).astype(jnp.int32),
      positions[:, 0].astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), q, mask, pages)


def sparse_kernel_takes(q, pages, value_width: int) -> bool:
    """Can the compiled kernel tile these shapes on a TPU? The latent
    kernel's conditions, heads in whole sublane tiles, and a group of one
    query (a decode row) or of whole tiles of queries (a chunk)."""
    n, page = q.shape[1], pages.shape[2]
    return (latent_kernel_takes(q, pages, value_width)
            and q.shape[2] % _GROUP_ROWS == 0
            and (n == 1 or n % _ATTEND_QUERIES == 0)
            and page % min(_KEY_BLOCK, page) == 0 and page % 128 == 0)


def sparse_latent_attention(q, pages, page_indices, mask, positions, *,
                            layer=0, top_k: int, value_width: int,
                            scale: float, use_pallas: Optional[str] = None):
    """Absorbed latent attention over the selected positions of a row.

    ``q`` [G, n, H, W]: every head's absorbed query at the cached vector's
    width, ``G`` groups of ``n`` queries; ``pages`` [L, P, page_tokens, W],
    of which ``layer`` is read where it lies; ``page_indices`` int32 [G,
    pages_per_row]; ``mask`` bfloat16 [G, n, pages_per_row * page_tokens],
    :func:`index_select`'s: 0 at the at most ``top_k`` positions a query
    attends (its own among them or not, as the indexer chose; the vector of
    a query's own position is in the pool already), ``_NEG_INF`` elsewhere;
    ``positions`` int32 [G, n], consecutive inside a group, past which no
    query of the group has a selected position (the kernel stops there).
    A selected position is key (all ``W`` columns) and value (the first
    ``value_width``) of all ``H`` heads. Returns [G, n, H, value_width] in
    ``q``'s dtype. ``use_pallas`` as :func:`paged_attention`'s."""
    if use_pallas is None:
        use_pallas = "on" if _on_tpu() and sparse_kernel_takes(
            q, pages, value_width) else "off"
    if use_pallas == "off":
        return sparse_latent_attention_reference(
            q, pages, page_indices, mask, layer=layer, top_k=top_k,
            value_width=value_width, scale=scale)
    return _sparse_latent_pallas(q, pages, page_indices, mask, positions,
                                 layer, value_width, scale,
                                 interpret=(use_pallas == "interpret"))


# -------------------------------- a chunk under a selection: the expanded form
# (Appended below the last kernel, as the section above was.)
# ``sparse_latent_attention`` above is the *absorbed* form: every head's query
# is carried to the cached vector's width, so a (query, key, head) triple
# costs 2 * W FLOPs for its score and 2 * kv_lora_rank for its value: 2,304 at
# GLM-5.2's widths (W 640, rank 512). The *expanded* form makes a cached
# position's key and value of every head from ``c_kv`` first (``k_nope = c_kv
# @ to_k[h]``, ``v = c_kv @ to_v[h]``) and pays 2 * qk_head_dim + 2 *
# v_head_dim a triple: 1,024 there. It is the same product in another order,
# ``(q W_k) . c = q . (W_k c)``, in the same bf16 operands with float32
# accumulation. Expanding a position costs 2 * rank * H * (nope + v_head_dim)
# FLOPs once (29.4 M), and a group of ``n`` queries on one row of the table
# shares it: it pays when ``n * H * 2 * ((W + rank) - (qk_head_dim +
# v_head_dim))`` exceeds that, from about 360 queries on. So a chunk of a
# prompt attends expanded (:func:`sparse_expanded_attention`) and a decode
# row, a group of one, stays absorbed; the caller chooses by the group's
# size (models/latent_sparse_moe.py::_expanded_pays), never by a switch.
#
# The kernel keeps the expansion in VMEM: keys and values of all heads for a
# row of 32,768 positions would be 2.1 GB in HBM. Grid (group, head group,
# key block, query tile): at the first query tile of a (head group, key
# block) the block's keys and values of the group's heads are made into
# scratch, and every tile of the chunk's queries that can see the block
# attends it there; the online softmax's state of *all* the group's queries
# for those heads stays in VMEM scratch while the key blocks pass (4 heads x
# 4,096 queries: 16 MB of accumulators, 16 MB of maxima and sums), and the
# values leave at the chunk's last block. Blocks past the chunk's last
# position are neither fetched nor computed, and a tile skips the blocks
# ahead of it.
#
# **Both kernels are called ``sparse_latent_attention`` in the trace.** The
# benchmark's readers count the traced steps from the calls under that name
# (two a layer in a mixed step, the chunk and the rows; one in a decode step)
# and sum the seconds under it: a chunk's kernel under a name of its own
# would leave the rows' seconds alone under the chunks' work.
_EXPAND_QUERIES = 1024  # queries a grid step of the expanded kernel
_EXPAND_ROWS = 512      # of them a matmul
_EXPAND_HEADS = 4       # heads whose expansion and softmax state share VMEM
_EXPAND_VMEM = 100 << 20


def sparse_expanded_attention_reference(q, pages, page_indices, mask, to_k,
                                        to_v, *, layer=0, scale: float):
    """Plain jnp form: the row's pages expanded, a masked softmax; see
    :func:`sparse_expanded_attention`."""
    (G, _, H, Dq), T = q.shape, mask.shape[-1]
    kl, nope = to_k.shape[0], to_k.shape[2]
    rows = pages[layer][page_indices].reshape(G, T, pages.shape[3])
    c_kv, k_rope = rows[..., :kl], rows[..., kl:kl + Dq - nope]
    k = jnp.concatenate(
        [jnp.einsum("gtl,lhn->gthn", c_kv, to_k),
         jnp.broadcast_to(k_rope[:, :, None], (G, T, H, Dq - nope))], -1)
    v = jnp.einsum("gtl,lhv->gthv", c_kv, to_v)
    s = jnp.einsum("gnhd,gthd->gnht", q, k,
                   preferred_element_type=jnp.float32) * scale
    p = jax.nn.softmax(s + mask[:, :, None, :].astype(jnp.float32), -1)
    return jnp.einsum("gnht,gthv->gnhv", p.astype(v.dtype), v).astype(q.dtype)


def _sparse_expanded_kernel(table_ref, first_ref, layer_ref, q_ref, mask_ref,
                            kv_ref, wk_ref, wv_ref, o_ref, k_ref, v_ref,
                            m_ref, l_ref, acc_ref, *, n, bq, rq, tk, rank,
                            scale):
    import jax.experimental.pallas as pl

    g, ki, qi = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    first = first_ref[g]
    last = (first + n - 1) // tk          # the last block any query sees
    heads = q_ref.shape[0]
    tile = pl.ds(pl.multiple_of(qi * bq, bq), bq)

    @pl.when(ki == 0)
    def _first_block():
        m_ref[:, tile] = jnp.full((heads, bq, 1), _NEG_INF, jnp.float32)
        l_ref[:, tile] = jnp.zeros((heads, bq, 1), jnp.float32)
        acc_ref[:, tile] = jnp.zeros((heads, bq) + acc_ref.shape[2:],
                                     jnp.float32)

    @pl.when((qi == 0) & (ki <= last))
    def _expand_the_block():
        kv = kv_ref[...]                          # [tk, W]
        for h in range(heads):
            k_ref[h] = jnp.dot(kv, wk_ref[h],
                               preferred_element_type=jnp.float32).astype(
                                   k_ref.dtype)
            v_ref[h] = jnp.dot(kv[:, :rank], wv_ref[h],
                               preferred_element_type=jnp.float32).astype(
                                   v_ref.dtype)

    @pl.when((ki <= last) & (ki * tk <= first + (qi + 1) * bq - 1))
    def _a_block_some_query_sees():
        for i in range(0, bq, rq):
            at = pl.ds(pl.multiple_of(qi * bq + i, rq), rq)
            mask = mask_ref[i:i + rq, :].astype(jnp.float32)
            for h in range(heads):
                s = lax.dot_general(q_ref[h, i:i + rq], k_ref[h],
                                    (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
                s = s * scale + mask                          # [rq, tk]
                m = m_ref[h, at]
                m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
                alpha = jnp.exp(m - m_new)
                p = jnp.exp(s - m_new)
                l_ref[h, at] = alpha * l_ref[h, at] \
                    + jnp.sum(p, axis=-1, keepdims=True)
                acc_ref[h, at] = alpha * acc_ref[h, at] + jnp.dot(
                    p.astype(v_ref.dtype), v_ref[h],
                    preferred_element_type=jnp.float32)
                m_ref[h, at] = m_new

    @pl.when(ki == last)
    def _last_block():
        o_ref[...] = (acc_ref[:, tile] / jnp.maximum(l_ref[:, tile], 1e-30)
                      ).astype(o_ref.dtype)


def _sparse_expanded_pallas(q, pages, page_indices, mask, positions, to_k,
                            to_v, layer, scale, interpret):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    G, n, H, Dq = q.shape
    rank, _, nope = to_k.shape
    vd, W = to_v.shape[2], pages.shape[3]
    page, width = pages.shape[2], page_indices.shape[1]
    T, tk, bq = width * page, min(_KEY_BLOCK, page), min(_EXPAND_QUERIES, n)
    hg, per, tiles = min(_EXPAND_HEADS, H), page // tk, n // bq
    # a head's key from the cached vector in one matmul: ``to_k`` over the
    # latent's columns, and a one that carries each rotary column across
    wk = jnp.pad(to_k.astype(pages.dtype).transpose(1, 0, 2),
                 ((0, 0), (0, W - rank), (0, Dq - nope)))
    wk = wk.at[:, rank:rank + Dq - nope, nope:].set(
        jnp.eye(Dq - nope, dtype=pages.dtype))
    wv = to_v.astype(pages.dtype).transpose(1, 0, 2)

    def live(g, ki, qi, first):
        """The (key block, query tile) a step works on, held where it has no
        work: at a block's first tile that sees it, and at the chunk's last
        step for the blocks past its last position (nothing is fetched)."""
        last = (first[g] + n - 1) // tk
        ke = jnp.minimum(ki, last)
        ahead = jnp.maximum(ke * tk - first[g], 0) // bq
        return ke, jnp.where(ki > last, tiles - 1, jnp.maximum(qi, ahead))

    def q_at(g, h, ki, qi, table, first, layer):
        return g, h, live(g, ki, qi, first)[1], 0

    def mask_at(g, h, ki, qi, table, first, layer):
        ke, qe = live(g, ki, qi, first)
        return g, qe, ke

    def kv_at(g, h, ki, qi, table, first, layer):
        ke = live(g, ki, qi, first)[0]
        return layer[0], table[g * width + ke // per], ke % per, 0

    def out_at(g, h, ki, qi, table, first, layer):
        # written at the chunk's last block, a tile a step; before it and
        # after it the block stays, so nothing unwritten goes out
        last = (first[g] + n - 1) // tk
        return g, h, jnp.where(ki < last, 0,
                               jnp.where(ki == last, qi, tiles - 1)), 0

    out = pl.pallas_call(
        functools.partial(_sparse_expanded_kernel, n=n, bq=bq,
                          rq=min(_EXPAND_ROWS, bq), tk=tk, rank=rank,
                          scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(G, H // hg, T // tk, tiles),
            in_specs=[
                pl.BlockSpec((None, hg, bq, Dq), q_at),
                pl.BlockSpec((None, bq, tk), mask_at),
                pl.BlockSpec((None, None, tk, W), kv_at),
                pl.BlockSpec((hg, W, Dq), lambda g, h, ki, qi, *_: (h, 0, 0)),
                pl.BlockSpec((hg, rank, vd),
                             lambda g, h, ki, qi, *_: (h, 0, 0))],
            out_specs=pl.BlockSpec((None, hg, bq, vd), out_at),
            scratch_shapes=[pltpu.VMEM((hg, tk, Dq), pages.dtype),
                            pltpu.VMEM((hg, tk, vd), pages.dtype),
                            pltpu.VMEM((hg, n, 1), jnp.float32),
                            pltpu.VMEM((hg, n, 1), jnp.float32),
                            pltpu.VMEM((hg, n, vd), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((G, H, n, vd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 4,
            vmem_limit_bytes=_EXPAND_VMEM),
        interpret=interpret,
        name="sparse_latent_attention",
    )(page_indices.reshape(-1).astype(jnp.int32),
      positions[:, 0].astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1),
      q.transpose(0, 2, 1, 3), mask, pages, wk, wv)
    return out.transpose(0, 2, 1, 3)


def expanded_kernel_takes(q, pages, to_k, to_v) -> bool:
    """Can the compiled expanded kernel tile these shapes on a TPU? Whole
    tiles of queries, whole groups of heads, every width in whole lanes, a
    page of whole key blocks, and the softmax state of a head group's queries
    inside the VMEM the kernel asks for."""
    n, H, Dq = q.shape[1:]
    rank, vd, page = to_k.shape[0], to_v.shape[2], pages.shape[2]
    bq, hg = min(_EXPAND_QUERIES, n), min(_EXPAND_HEADS, H)
    state = hg * n * (vd + 2 * 128) * 4
    return (n % bq == 0 and bq % min(_EXPAND_ROWS, bq) == 0
            and bq % _GROUP_ROWS == 0 and H % hg == 0
            and all(w % 128 == 0 for w in (Dq, vd, rank, pages.shape[3]))
            and page % min(_KEY_BLOCK, page) == 0 and page % 128 == 0
            and q.dtype == pages.dtype and state <= _EXPAND_VMEM // 2)


def expanded_form_runs(q, pages, to_k, to_v) -> bool:
    """Whether :func:`sparse_expanded_attention` has a way to run these shapes
    where computation lands: off the TPU the plain form takes any; on it the
    kernel alone, since the plain form expands the whole row into HBM."""
    return not _on_tpu() or expanded_kernel_takes(q, pages, to_k, to_v)


def sparse_expanded_attention(q, pages, page_indices, mask, positions, to_k,
                              to_v, *, layer=0, scale: float,
                              use_pallas: Optional[str] = None):
    """Latent attention over the selected positions of a row in the expanded
    form, for groups of many queries (a chunk of a prompt).

    ``q`` [G, n, H, nope + rope]: every head's plain query, ``q_nope`` beside
    the rotated ``q_rope``; ``pages``, ``page_indices``, ``mask`` and
    ``positions`` as :func:`sparse_latent_attention`'s; ``to_k`` [rank, H,
    nope] and ``to_v`` [rank, H, v_head_dim], the two halves of ``kv_b``. A
    cached vector's first ``rank`` columns are ``c_kv``, the next ``rope`` the
    rotary key every head shares. Returns the heads' values [G, n, H,
    v_head_dim] in ``q``'s dtype: what :func:`sparse_latent_attention` returns
    carried through ``to_v``. ``use_pallas`` as :func:`paged_attention`'s."""
    if use_pallas is None:
        use_pallas = "on" if _on_tpu() and expanded_kernel_takes(
            q, pages, to_k, to_v) else "off"
    if use_pallas == "off":
        return sparse_expanded_attention_reference(
            q, pages, page_indices, mask, to_k, to_v, layer=layer,
            scale=scale)
    return _sparse_expanded_pallas(q, pages, page_indices, mask, positions,
                                   to_k, to_v, layer, scale,
                                   interpret=(use_pallas == "interpret"))


# ----------------------------- whole blocks chosen through pooled keys
# (Appended below the last kernel, as the sections above were.)
# MiniCPM4's InfLLM-v2 attention (models/sparse_linear.py): a query attends
# whole blocks of ``block`` positions, those its K/V group chose through
# *pooled keys*, one key a ``stride`` positions, the mean of the ``window``
# keys from there (``c_j = mean(k[stride j .. stride j + window - 1])``,
# kept in a paged array of its own at a ``stride``-th of the position rate,
# serve/kv_cache.py). Three steps, each a plain ``jax.numpy`` form (the
# contract, the CPU's path and the kernels' oracle) and a Pallas kernel of
# the same name:
#
#   block_scores            p[h, j] = softmax_j(q_h . c_j * scale) over the
#                           windows complete at the query, summed over the
#                           group's heads; a block scores the largest sum of
#                           a window that meets it
#   block_select            the first ``init_blocks`` blocks and those that
#                           hold one of the last ``local`` positions, then the
#                           highest scores up to ``top_k`` blocks, ties to the
#                           lower block; every block up to the query's while
#                           it stands before ``dense_len``
#   block_sparse_attention  softmax attention over the chosen blocks' positions
#                           up to the query's: a decode row fetches its chosen
#                           blocks alone, a chunk walks the row's key tiles
#                           and skips those no query of a tile chose
# Queries come in groups on a row of the block table at consecutive positions
# (a chunk: one group of C; a decode step: B groups of one), each query as its
# K/V heads' ``R`` query heads ([G, n, Hkv, R, D]).
_SCORE_QUERIES = 32     # queries a grid step of the scoring kernel (a chunk)
_BLOCK_TILE = 512       # key positions a grid step of a chunk's attention
_BLOCK_QUERIES = 64     # queries a tile of a chunk's attention
_FORCED = 1e30          # the score of a block that is always chosen
_BLOCK_VMEM = 64 << 20


def _window_probs_reference(q, pooled, page_indices, positions, layer,
                            stride, window, scale):
    """[G, n, Hkv, pooled keys a row]: each window's softmax share, summed
    over a group's heads; 0 for a window not complete at the query."""
    G, n, Hkv, R, D = q.shape
    NJ = page_indices.shape[1] * pooled.shape[3]
    c = pooled[layer][:, page_indices].reshape(Hkv, G, NJ, D)
    s = jnp.einsum("gnhrd,hgjd->gnhrj", q, c,
                   preferred_element_type=jnp.float32) * scale
    done = (stride * jnp.arange(NJ) + window - 1
            <= positions[:, :, None, None, None])
    s = jnp.where(done, s, _NEG_INF)
    p = jnp.where(done, jnp.exp(s - jnp.max(s, -1, keepdims=True)), 0.0)
    p = p / jnp.maximum(jnp.sum(p, -1, keepdims=True), 1e-30)
    return jnp.sum(p, axis=3)


def _block_max(s, per_block: int, per_window: int):
    """[..., NJ] window sums -> [..., NJ / per_block]: block ``b`` takes the
    largest sum of the windows that meet it, ``j`` from ``per_block * b -
    per_window + 1`` to ``per_block * (b + 1) - 1`` (none below 0: a sum is
    never negative, so the padding of 0 adds nothing)."""
    nb = s.shape[-1] // per_block
    s = jnp.pad(s, ((0, 0),) * (s.ndim - 1) + ((per_window - 1, 0),))
    return functools.reduce(jnp.maximum, [
        s[..., i:i + per_block * nb:per_block]
        for i in range(per_block + per_window - 1)])


def _block_scores_kernel(table_ref, first_ref, layer_ref, q_ref, pooled_hbm,
                         o_ref, buf, sem, *, width, bq, stride, window,
                         scale):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    g, h, qi = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    entries, D = buf.shape[1], buf.shape[2]
    first = first_ref[g] + qi * bq             # the tile's first query
    n_pages = jnp.minimum((first + bq - 1) // (entries * stride) + 1, width)

    def copy(i):
        return pltpu.make_async_copy(
            pooled_hbm.at[layer_ref[0], h, table_ref[g * width + i]],
            buf.at[i], sem.at[0])

    lax.fori_loop(0, n_pages, lambda i, c: (copy(i).start(), c)[1], 0)
    lax.fori_loop(0, n_pages, lambda i, c: (copy(i).wait(), c)[1], 0)
    R = q_ref.shape[1]
    keys = buf[...].reshape(width * entries, D)
    s = lax.dot_general(q_ref[...].reshape(bq * R, D), keys,
                        (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32) * scale
    s = s.reshape(bq, R, width * entries)
    at = first + lax.broadcasted_iota(jnp.int32, (bq, 1, 1), 0)
    j = lax.broadcasted_iota(jnp.int32, (1, 1, width * entries), 2)
    # a window of a page past the tile's last query is never complete: what
    # the buffer holds there is never read
    done = stride * j + window - 1 <= at
    s = jnp.where(done, s, _NEG_INF)
    p = jnp.where(done, jnp.exp(s - jnp.max(s, -1, keepdims=True)), 0.0)
    p = p / jnp.maximum(jnp.sum(p, -1, keepdims=True), 1e-30)
    o_ref[...] = jnp.sum(p, axis=1)


def _block_scores_pallas(q, pooled, page_indices, positions, layer, stride,
                         window, interpret, scale):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    G, n, Hkv, R, D = q.shape
    entries, width = pooled.shape[3], page_indices.shape[1]
    bq = min(_SCORE_QUERIES, n)
    NJ = width * entries
    out = pl.pallas_call(
        functools.partial(_block_scores_kernel, width=width, bq=bq,
                          stride=stride, window=window, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(G, Hkv, n // bq),
            in_specs=[pl.BlockSpec((None, bq, None, R, D),
                                   lambda g, h, qi, *_: (g, qi, h, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((None, None, bq, NJ),
                                   lambda g, h, qi, *_: (g, h, qi, 0)),
            scratch_shapes=[pltpu.VMEM((width, entries, D), pooled.dtype),
                            pltpu.SemaphoreType.DMA((1,))]),
        out_shape=jax.ShapeDtypeStruct((G, Hkv, n, NJ), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3,
            vmem_limit_bytes=_BLOCK_VMEM),
        interpret=interpret,
        name="block_scores",
    )(page_indices.reshape(-1).astype(jnp.int32),
      positions[:, 0].astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), q, pooled)
    return out.transpose(0, 2, 1, 3)


def block_scores_kernel_takes(q, pooled) -> bool:
    """Can the compiled scoring kernel tile these shapes on a TPU? A head
    that fills the lanes, a group of heads in whole bf16 tiles, a page of
    pooled keys in whole tiles, and a group of one query (a decode row) or
    of whole tiles of queries (a chunk)."""
    n, R, D = q.shape[1], q.shape[3], q.shape[4]
    return (D % 128 == 0 and R % _GROUP_ROWS == 0
            and pooled.shape[3] % _GROUP_ROWS == 0
            and (n == 1 or n % _SCORE_QUERIES == 0)
            and q.dtype == pooled.dtype)


def block_scores(q, pooled, page_indices, positions, *, layer=0, stride: int,
                 window: int, block: int, scale: float,
                 use_pallas: Optional[str] = None):
    """Every block's score of every query's K/V group.

    ``q`` [G, n, Hkv, R, D]: ``G`` groups of ``n`` queries, each as the ``R``
    query heads of each K/V head; ``pooled`` [L, Hkv, P, page_tokens /
    stride, D], the paged pooled keys, of which ``layer`` is read where it
    lies; ``page_indices`` int32 [G, pages_per_row]; ``positions`` int32 [G,
    n], consecutive inside a group. A query scores the windows complete at
    its position (``stride * j + window - 1 <= t``): the softmax over them of
    ``q_h . c_j * scale``, summed over the group's heads; a block of
    ``block`` positions takes the largest sum of a window that meets it (0
    where none is complete). Returns float32 [G, n, Hkv, pages_per_row *
    page_tokens / block]. ``use_pallas`` as :func:`paged_attention`'s: the
    kernel computes the sums, the block's largest is a few ``jax.numpy``
    maxima of strided slices either way."""
    if use_pallas is None:
        use_pallas = "on" if _on_tpu() and block_scores_kernel_takes(
            q, pooled) else "off"
    if use_pallas == "off":
        s = _window_probs_reference(q, pooled, page_indices, positions, layer,
                                    stride, window, scale)
    else:
        s = _block_scores_pallas(q, pooled, page_indices, positions, layer,
                                 stride, window,
                                 interpret=(use_pallas == "interpret"),
                                 scale=scale)
    return _block_max(s, block // stride, window // stride)


def block_select(scores, positions, *, top_k: int, block: int,
                 init_blocks: int, local: int, dense_len: int,
                 use_pallas: Optional[str] = None):
    """The blocks each query's K/V group attends: ``scores`` float32 [G, n,
    Hkv, NB] (:func:`block_scores`), ``positions`` int32 [G, n] -> bool [G,
    n, Hkv, NB]. A block that starts after the query is never chosen; the
    first ``init_blocks`` and every block holding one of the last ``local``
    positions up to the query's always are; the highest scores fill the rest
    up to ``top_k`` blocks, **ties to the lower block**; a query before
    ``dense_len`` takes every block up to its own. ``use_pallas`` as
    :func:`index_select`'s: the kernel is its threshold search (one pass over
    the bits of the float, ties cut by a second), under its own name."""
    NB = scores.shape[-1]
    b = jnp.arange(NB)
    t = positions[:, :, None, None]
    exists = b * block <= t
    forced = (b < init_blocks) | ((b + 1) * block > t - local + 1)
    prepared = jnp.where(exists, jnp.where(forced, _FORCED, scores),
                         _NEG_INF).astype(jnp.float32)
    if use_pallas is None:
        use_pallas = "on" if _on_tpu() and NB % 128 == 0 else "off"
    if use_pallas == "off":
        mask = index_select_reference(prepared, top_k)
    else:
        mask = _index_select_pallas(prepared, top_k,
                                    interpret=(use_pallas == "interpret"),
                                    name="block_select")
    return jnp.where(t < dense_len, exists, mask > _NEG_INF / 2)


def block_sparse_attention_reference(q, k_pages, v_pages, page_indices,
                                     chosen, positions, *, layer=0,
                                     block: int, scale: float):
    """Plain jnp form: the row's pages read whole, masked by the chosen
    blocks and the query's position; see :func:`block_sparse_attention`."""
    G, n, Hkv, R, D = q.shape
    T = page_indices.shape[1] * k_pages.shape[3]

    def rows(pages):  # the row's pages, in table order: [G, Hkv, T, D]
        got = pages[layer][:, page_indices]      # [Hkv, G, W, page, D]
        return jnp.moveaxis(got, 0, 1).reshape(G, Hkv, T, D)

    seen = jnp.repeat(chosen, block, axis=-1)[..., :T] & (
        jnp.arange(T) <= positions[:, :, None, None])       # [G, n, Hkv, T]
    s = jnp.einsum("gnhrd,ghtd->gnhrt", q, rows(k_pages),
                   preferred_element_type=jnp.float32) * scale
    seen = seen[:, :, :, None]
    s = jnp.where(seen, s, _NEG_INF)
    p = jnp.where(seen, jnp.exp(s - jnp.max(s, -1, keepdims=True)), 0.0)
    o = jnp.einsum("gnhrt,ghtd->gnhrd", p.astype(q.dtype), rows(v_pages),
                   preferred_element_type=jnp.float32)
    return (o / jnp.maximum(jnp.sum(p, -1)[..., None], 1e-30)).astype(
        q.dtype)


def _block_rows_kernel(table_ref, ids_ref, count_ref, pos_ref, layer_ref,
                       q_ref, k_hbm, v_hbm, o_ref, k_buf, v_buf, sem, *,
                       width, per, block, hkv, most, scale):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    i = pl.program_id(0)             # (row, K/V head): i // hkv, i % hkv
    slot = lax.rem(i, 2)
    layer = layer_ref[0]

    def copies(step, buf, j):
        """The DMAs of the ``j``-th chosen block of ``step``: its keys and
        values, where its page lies."""
        b = ids_ref[step * most + j]
        pid = table_ref[(step // hkv) * width + b // per]
        at = (layer, lax.rem(step, hkv), pid, pl.ds((b % per) * block, block))
        return (pltpu.make_async_copy(k_hbm.at[at], k_buf.at[buf, j],
                                      sem.at[0, buf]),
                pltpu.make_async_copy(v_hbm.at[at], v_buf.at[buf, j],
                                      sem.at[1, buf]))

    def each(step, buf, go):
        def one(j, c):
            for cp in copies(step, buf, j):
                go(cp)
            return c
        lax.fori_loop(0, count_ref[step], one, 0)

    @pl.when(i == 0)
    def _first():
        each(0, 0, lambda cp: cp.start())

    @pl.when(i + 1 < pl.num_programs(0))
    def _next():
        each(i + 1, 1 - slot, lambda cp: cp.start())

    each(i, slot, lambda cp: cp.wait())
    n_blk = count_ref[i]
    t = pos_ref[i // hkv]
    # the chosen blocks lie in order and the last holds the query: what is
    # seen is every position of the others and the last's up to ``t``
    last = ids_ref[i * most + jnp.maximum(n_blk - 1, 0)] * block
    limit = jnp.where(n_blk > 0, (n_blk - 1) * block + t - last + 1, 0)
    span, D = most * block, k_buf.shape[-1]
    k = k_buf[slot].reshape(span, D)
    v = v_buf[slot].reshape(span, D)
    s = lax.dot_general(q_ref[...], k, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32) * scale
    ok = lax.broadcasted_iota(jnp.int32, s.shape, 1) < limit
    s = jnp.where(ok, s, _NEG_INF)
    p = jnp.where(ok, jnp.exp(s - jnp.max(s, -1, keepdims=True)), 0.0)
    # a slot of the buffer this row left unfilled is never read
    v = jnp.where(lax.broadcasted_iota(jnp.int32, v.shape, 0) < limit, v, 0)
    o = jnp.dot(p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    o_ref[...] = (o / jnp.maximum(jnp.sum(p, -1, keepdims=True), 1e-30)
                  ).astype(o_ref.dtype)


def _block_rows_pallas(q, k_pages, v_pages, page_indices, chosen, positions,
                       live, layer, block, most, scale, interpret):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    G, _, Hkv, R, D = q.shape
    page, width = k_pages.shape[3], page_indices.shape[1]
    NB = chosen.shape[-1]
    most = min(most, NB)
    picked = chosen[:, 0].reshape(G * Hkv, NB)
    # the chosen blocks' ids in order, the lowest first; a row that is not
    # live fetches nothing
    ids = lax.top_k(jnp.where(picked, -jnp.arange(NB), -NB), most)[1]
    count = jnp.where(jnp.repeat(live, Hkv),
                      jnp.sum(picked, -1, dtype=jnp.int32), 0)
    one = pl.BlockSpec((None, R, D), lambda i, *_: (i, 0, 0))
    out = pl.pallas_call(
        functools.partial(_block_rows_kernel, width=width,
                          per=page // block, block=block, hkv=Hkv,
                          most=most, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(G * Hkv,),
            in_specs=[one, pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=one,
            scratch_shapes=[pltpu.VMEM((2, most, block, D), k_pages.dtype),
                            pltpu.VMEM((2, most, block, D), v_pages.dtype),
                            pltpu.SemaphoreType.DMA((2, 2))]),
        out_shape=jax.ShapeDtypeStruct((G * Hkv, R, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_BLOCK_VMEM),
        interpret=interpret,
        name="block_sparse_attention",
    )(page_indices.reshape(-1).astype(jnp.int32),
      ids.reshape(-1).astype(jnp.int32), count,
      positions[:, 0].astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1),
      q.reshape(G * Hkv, R, D), k_pages, v_pages)
    return out.reshape(G, 1, Hkv, R, D)


def _block_chunk_kernel(table_ref, fetch_ref, any_ref, first_ref, layer_ref,
                        q_ref, chosen_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                        acc_ref, *, bq, tk, block, scale):
    import jax.experimental.pallas as pl

    g, h, qi, ki = (pl.program_id(0), pl.program_id(1), pl.program_id(2),
                    pl.program_id(3))
    QT, KT = pl.num_programs(2), pl.num_programs(3)
    bq_, R, D = q_ref.shape

    @pl.when(ki == 0)
    def _first_tile():
        m_ref[...] = jnp.full(m_ref.shape, _NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    @pl.when(any_ref[((g * pl.num_programs(1) + h) * QT + qi) * KT + ki] > 0)
    def _a_tile_some_query_chose():
        s = lax.dot_general(q_ref[...].reshape(bq * R, D), k_ref[...],
                            (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        s = s.reshape(bq, R, tk)
        # the chosen blocks as positions of the tile: a one-hot product,
        # exact for 0 and 1
        NB = chosen_ref.shape[-1]
        spread = (lax.broadcasted_iota(jnp.int32, (NB, tk), 0)
                  == ki * (tk // block)
                  + lax.broadcasted_iota(jnp.int32, (NB, tk), 1) // block)
        seen = jnp.dot(chosen_ref[...], spread.astype(chosen_ref.dtype),
                       preferred_element_type=jnp.float32) > 0.5
        at_q = first_ref[g] + qi * bq + lax.broadcasted_iota(
            jnp.int32, (bq, tk), 0)
        at_k = ki * tk + lax.broadcasted_iota(jnp.int32, (bq, tk), 1)
        seen = (seen & (at_k <= at_q))[:, None, :]
        s = jnp.where(seen, s, _NEG_INF)
        m = m_ref[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.where(seen, jnp.exp(s - m_new), 0.0)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jnp.dot(
            p.reshape(bq * R, tk).astype(v_ref.dtype), v_ref[...],
            preferred_element_type=jnp.float32).reshape(bq, R, D)
        m_ref[...] = m_new

    @pl.when(ki == KT - 1)
    def _last_tile():
        o_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(
            o_ref.dtype)


def _block_chunk_pallas(q, k_pages, v_pages, page_indices, chosen, positions,
                        layer, block, scale, interpret):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    G, n, Hkv, R, D = q.shape
    page, width = k_pages.shape[3], page_indices.shape[1]
    NB = chosen.shape[-1]
    tk, bq = min(_BLOCK_TILE, page), min(_BLOCK_QUERIES, n)
    QT, KT, per = n // bq, width * page // tk, tk // block
    # which key tiles a tile of queries chose a block of; where it chose
    # none, the tile to hold (the last it fetched, or its first): nothing new
    # is fetched for a tile it skips
    hit = jnp.any(chosen.reshape(G, QT, bq, Hkv, KT, per), axis=(2, 5))
    hit = hit.transpose(0, 2, 1, 3)                          # [G, Hkv, QT, KT]
    tiles = jnp.arange(KT)
    held = lax.cummax(jnp.where(hit, tiles, -1), axis=3)
    fetch = jnp.where(held >= 0, held, jnp.argmax(hit, axis=3)[..., None])

    def kv_at(g, h, qi, ki, table, fetch, any_, first, layer):
        f = fetch[((g * Hkv + h) * QT + qi) * KT + ki]
        return layer[0], h, table[g * width + f * tk // page], \
            (f * tk % page) // tk, 0

    q_spec = pl.BlockSpec((None, bq, None, R, D),
                          lambda g, h, qi, ki, *_: (g, qi, h, 0, 0))
    return pl.pallas_call(
        functools.partial(_block_chunk_kernel, bq=bq, tk=tk, block=block,
                          scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(G, Hkv, QT, KT),
            in_specs=[q_spec,
                      pl.BlockSpec((None, None, bq, NB),
                                   lambda g, h, qi, ki, *_: (g, h, qi, 0)),
                      pl.BlockSpec((None, None, None, tk, D), kv_at),
                      pl.BlockSpec((None, None, None, tk, D), kv_at)],
            out_specs=q_spec,
            scratch_shapes=[pltpu.VMEM((bq, R, 1), jnp.float32),
                            pltpu.VMEM((bq, R, 1), jnp.float32),
                            pltpu.VMEM((bq, R, D), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 4,
            vmem_limit_bytes=_BLOCK_VMEM),
        interpret=interpret,
        name="block_sparse_attention",
    )(page_indices.reshape(-1).astype(jnp.int32),
      fetch.reshape(-1).astype(jnp.int32), hit.reshape(-1).astype(jnp.int32),
      positions[:, 0].astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), q,
      chosen.transpose(0, 2, 1, 3).astype(q.dtype), k_pages, v_pages)


def block_attention_kernel_takes(q, k_pages, block: int) -> bool:
    """Can the compiled kernels tile these shapes on a TPU? A head that
    fills the lanes, a group of heads in whole bf16 tiles, a page of whole
    key tiles of whole blocks, and a group of one query (a decode row) or of
    whole tiles of queries (a chunk)."""
    n, R, D = q.shape[1], q.shape[3], q.shape[4]
    page = k_pages.shape[3]
    tk = min(_BLOCK_TILE, page)
    return (D % 128 == 0 and R % _GROUP_ROWS == 0 and block % 16 == 0
            and page % tk == 0 and tk % block == 0
            and (n == 1 or n % min(_BLOCK_QUERIES, n) == 0 and n >= 16)
            and q.dtype == k_pages.dtype)


def block_sparse_attention(q, k_pages, v_pages, page_indices, chosen,
                           positions, *, layer=0, block: int, scale: float,
                           most: int, live=None,
                           use_pallas: Optional[str] = None):
    """Attention of each query over the positions of the blocks its K/V
    group chose, up to its own.

    ``q`` [G, n, Hkv, R, D]; ``k_pages`` / ``v_pages`` [L, Hkv, P,
    page_tokens, D], of which ``layer`` is read where it lies;
    ``page_indices`` int32 [G, pages_per_row]; ``chosen`` bool [G, n, Hkv,
    NB] (:func:`block_select`: blocks of ``block`` positions, none past a
    query's own and the one that holds it always among them); ``positions``
    int32 [G, n], consecutive inside a group; ``most``: the most blocks a
    query chooses; ``live`` bool [G] (decode rows): a row that is not live
    reads nothing and gets 0. Returns [G, n, Hkv, R, D] in ``q``'s dtype.

    A decode row (``n`` = 1) fetches its ``most`` blocks at most, each once,
    keys and values, all of them in flight together and the next row's
    behind them; a chunk walks its row's key tiles with an online softmax,
    and a tile that no query of a tile of queries chose a block of is
    neither fetched nor computed. ``use_pallas`` as
    :func:`paged_attention`'s."""
    if use_pallas is None:
        use_pallas = "on" if _on_tpu() and block_attention_kernel_takes(
            q, k_pages, block) else "off"
    if use_pallas == "off":
        o = block_sparse_attention_reference(
            q, k_pages, v_pages, page_indices, chosen, positions, layer=layer,
            block=block, scale=scale)
        if live is not None:
            o = jnp.where(live[:, None, None, None, None], o, 0)
        return o
    interpret = use_pallas == "interpret"
    if q.shape[1] == 1:
        live = jnp.ones(q.shape[:1], bool) if live is None else live
        return _block_rows_pallas(q, k_pages, v_pages, page_indices, chosen,
                                  positions, live, layer, block, most, scale,
                                  interpret)
    return _block_chunk_pallas(q, k_pages, v_pages, page_indices, chosen,
                               positions, layer, block, scale, interpret)
