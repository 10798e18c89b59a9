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
