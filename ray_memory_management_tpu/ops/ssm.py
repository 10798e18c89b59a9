"""The state-space recurrence of a Mamba-2 layer: a chunked scan for prefill
and a one-token update of the resident state for decode.

With ``x`` [T, H, P] (heads of ``P`` channels), a step size ``dt`` [T, H] > 0,
a decay rate ``A`` [H] < 0 and ``B``, ``C`` [T, G, N] shared by the ``H // G``
heads of a group, a head's state ``h`` [N, P] follows

    h_t = exp(dt_t A) h_{t-1} + B_t (x) (dt_t x_t),    y_t = C_t . h_t + D x_t

The state is held **state dimension first** (``[.., H, N, P]``, not Mamba's
``[.., H, P, N]``): the contraction with ``C`` then runs over sublanes, ``x``
and ``y`` lie along the lanes as they come out of a projection, and the
decode kernel needs no transposition. Heads of fewer channels than a row of
lanes (``P`` = 64) lie **side by side in the resident state**, ``k = 128 //
P`` of one group to a row (``[.., H / k, N, k P]``, :func:`pack_heads`): a
float32 array whose last dimension is 64 is padded to 128 in HBM and in
VMEM, so held a head a row the state would take twice its bytes and every
fetch would move half padding; side by side it is dense, and the update is
the same elementwise pass, the decay and the input taking a head's value in
that head's lanes. Both kernels take ``P`` = 128 (Falcon-H1: 32 heads,
state 256, 2 groups) and ``P`` = 64 (Nemotron-H: 128 heads, state 128, 8
groups of 16), and a third shape, **a group a head** (MiniCPM-SALA's
Lightning attention, models/sparse_linear.py: 32 heads of 128, state 128,
``G`` = ``H``: linear attention with a key a head is this recurrence with
``dt`` = 1, ``A`` = -slope, ``B`` = k, ``x`` = v, ``C`` = q and ``D`` = 0).
The decode kernel's grid step never crosses a group where a group has several
rows of state; where each has one, a step moves a block of groups, each row
with its own ``B`` and ``C`` (:func:`_blocks_of`).

  - :func:`ssd_scan`: the recurrence over a whole prompt in chunks of
    ``SSD_CHUNK`` positions (Mamba-2's state-space duality): inside a chunk a
    masked ``[Q, Q]`` product, between chunks the state carried on; operands
    in ``x``'s type, float32 accumulation and a float32 state. Positions at
    or past ``true_len`` leave the state untouched, so the state that comes
    back is the state of the prompt's last real token whatever the bucket.
    Two forms of the one algorithm: plain ``jax.numpy`` einsums under a
    ``lax.scan`` over the chunks (other platforms, untileable shapes), and
    the Pallas kernel ``name="ssd_chunk_scan"`` (grid over blocks of heads
    and, in order, the chunks: a chunk's decay tile, scores and the block's
    state stay in VMEM, where the plain form writes them to HBM between its
    fusions). :func:`ssd_sequential` is the same recurrence a position at a
    time, the oracle of both.
  - :func:`ssm_decode_update`: one token-step of one layer against the
    resident state ``[L, slots, H / k, N, k P]``. The Pallas kernel
    (``name="ssm_decode_update"``) leaves the array in HBM and aliases it in
    and out: the live slots, in order, are its grid; a live slot's block of
    heads is fetched, updated, written back and contracted with ``C`` in the
    same visit (the state crosses HBM twice a token-step, not three times),
    double buffered across grid steps, and **an idle slot's state is never
    fetched**. :func:`ssm_decode_update_reference` is the same in plain
    ``jax.numpy``: other platforms and shapes the kernel cannot tile take it.
    Which one runs is decided from the platform and the shape, never by a
    flag; ``"interpret"`` only by name (as ops/paged_attention.py).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from .flash_attention import _on_tpu

# positions a chunk of the prefill scan holds: an implementation constant
# that suits the MXU (two 128 tiles a side), not the model's
SSD_CHUNK = 256
# bytes of state one grid step of the decode kernel moves each way
_BLOCK_BYTES = 1 << 20


# ------------------------------------------------------------------ prefill
def _grouped(a, groups: int):
    """[T, H, ...] -> [T, G, H // G, ...]."""
    return a.reshape(a.shape[:1] + (groups, a.shape[1] // groups)
                     + a.shape[2:])


def _stopped(dt, true_len):
    """``dt`` in float32, 0 at and past ``true_len``: a decay of 1 and an
    input of 0, so such a position moves no state (padding with zeros behind
    the row does the same)."""
    dt = dt.astype(jnp.float32)
    if true_len is None:
        return dt
    return jnp.where(jnp.arange(dt.shape[0])[:, None] < true_len, dt, 0.0)


def ssd_sequential(x, dt, A, B, C, D, *, true_len=None, h0=None):
    """The recurrence a position at a time (``lax.scan``), float32
    throughout; arguments and results as :func:`ssd_scan`."""
    T, H, P = x.shape
    G, N = B.shape[1:]
    f32 = jnp.float32
    dt = _stopped(dt, true_len)
    h0 = jnp.zeros((H, N, P), f32) if h0 is None else h0.astype(f32)

    def step(h, inp):
        x_t, dt_t, b_t, c_t = inp
        b_t, c_t = (jnp.repeat(a, H // G, axis=0) for a in (b_t, c_t))
        h = jnp.exp(dt_t * A)[:, None, None] * h \
            + b_t[:, :, None] * (dt_t[:, None] * x_t)[:, None, :]
        return h, jnp.einsum("hn,hnp->hp", c_t, h,
                             precision=lax.Precision.HIGHEST) \
            + D[:, None] * x_t

    h, y = lax.scan(step, h0, (x.astype(f32), dt, B.astype(f32),
                               C.astype(f32)))
    return y, h


def ssd_scan(x, dt, A, B, C, D, *, chunk: int = SSD_CHUNK, true_len=None,
             h0=None, use_pallas: Optional[str] = None):
    """The recurrence over a whole row, in chunks.

    ``x`` [T, H, P]; ``dt`` [T, H] float32, after its softplus; ``A`` [H]
    float32, negative; ``B``, ``C`` [T, G, N]; ``D`` [H]; ``true_len`` (a
    traced scalar is fine): positions at or past it have their ``dt`` set to
    0, which is a decay of 1 and an input of 0; ``h0`` [H, N, P], the state
    before position 0 (zero when absent). Returns ``(y [T, H, P] float32,
    h [H, N, P] float32)``: ``h`` the state after position
    ``min(true_len, T) - 1``; ``y`` past ``true_len`` is junk of the padding.

    Matmul operands are taken in ``x``'s type (bf16 on the serve path, as
    every other matmul of the layer) and accumulated in float32; the decays
    and the carried state are float32 throughout.

    ``use_pallas``: "on", "interpret", "off", or None = the kernel
    (``name="ssd_chunk_scan"``, the same algorithm with a chunk's decay
    tile, scores and state in VMEM) on a TPU for a shape it can tile
    (:func:`ssd_kernel_takes`), else the ``jax.numpy`` form below.
    """
    T, H, P = x.shape
    G, N = B.shape[1:]
    if use_pallas is None:
        use_pallas = "on" if _on_tpu() and ssd_kernel_takes(x, B, chunk) \
            else "off"
    if use_pallas != "off":
        return _ssd_scan_pallas(x, dt, A, B, C, D, chunk, true_len, h0,
                                interpret=(use_pallas == "interpret"))
    R, f32, op = H // G, jnp.float32, x.dtype
    Q = min(chunk, -(-T // 8) * 8)
    pad = (-T) % Q

    def chunks(a):  # [T, ...] -> [chunks, Q, ...], zeros behind the row
        a = jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
        return a.reshape((-1, Q) + a.shape[1:])

    dt_c = chunks(_grouped(_stopped(dt, true_len), G))  # [c, Q, G, R]
    x_c = chunks(_grouped(x, G))                     # [c, Q, G, R, P]
    B_c, C_c = chunks(B), chunks(C)                  # [c, Q, G, N]
    A = A.astype(f32).reshape(G, R)
    later = jnp.tril(jnp.ones((Q, Q), bool))         # [t, s]: s <= t
    h0 = jnp.zeros((G, R, N, P), f32) if h0 is None \
        else h0.astype(f32).reshape(G, R, N, P)

    def dot(spec, a, b):
        return jnp.einsum(spec, a.astype(op), b.astype(op),
                          preferred_element_type=f32)

    def one(h, inp):
        x_, dt_, b_, c_ = inp
        cs = jnp.cumsum(dt_ * A, axis=0)             # [Q, G, R], <= 0
        # inside the chunk: what position s adds to position t >= s
        seg = cs[:, None] - cs[None, :]              # [t, s, G, R]
        decay = jnp.exp(jnp.where(later[:, :, None, None], seg, -jnp.inf))
        scores = dot("tgn,sgn->gts", c_, b_)[:, None] \
            * (decay * dt_[None]).transpose(2, 3, 0, 1)      # [G, R, t, s]
        y = dot("grts,sgrp->tgrp", scores, x_)
        # what came in from the chunks before
        y = y + dot("tgn,grnp->tgrp", c_, h) * jnp.exp(cs)[..., None]
        # and what this chunk hands on
        w = jnp.exp(cs[-1][None] - cs) * dt_         # [Q, G, R]
        h = jnp.exp(cs[-1])[..., None, None] * h \
            + dot("sgn,sgrp->grnp", b_, x_.astype(f32) * w[..., None])
        return h, y

    h, y = lax.scan(one, h0, (x_c, dt_c, B_c, C_c))
    y = y.reshape(-1, H, P)[:T] + D.astype(f32)[:, None] * x.astype(f32)
    return y, h.reshape(H, N, P)


def ssd_kernel_takes(x, B, chunk: int = SSD_CHUNK) -> bool:
    """Can the compiled scan kernel tile these shapes on a TPU? A row of at
    least one whole chunk of whole lanes, a state that fills the lanes, heads
    of 64 channels or whole lanes of them whose block (``_head_block``) is
    whole lanes wide, bf16 or float32 operands."""
    H, P = x.shape[1:]
    G, N = B.shape[1:]
    return (x.shape[0] >= chunk and chunk % 128 == 0
            and P % 64 == 0 and N % 128 == 0 and H % G == 0
            and _scan_blocks(H, G, N, P)[0] * P % 128 == 0
            and x.dtype in (jnp.bfloat16, jnp.float32))


def _scan_blocks(H: int, G: int, n: int, p: int):
    """(heads a grid step of the scan kernel takes, groups among them):
    within one group where a group has several heads (:func:`_head_block`);
    eight heads, each its own group, where every head is a group (a key a
    head of whole rows of lanes), so that a block of heads fills the
    sublanes of its rows."""
    if G == H and H % 8 == 0 and p % 128 == 0:
        return 8, 8
    return _head_block(H // G, n, p), 1


def _scan_kernel(x_ref, cs_row, dt_row, cs_col, w_col, keep_ref, bt_ref, c_ref,
                 h0_ref, y_ref, h_ref, *, hb: int, p: int, gb: int = 1):
    import jax.experimental.pallas as pl

    f32, op = jnp.float32, x_ref.dtype
    q = c_ref.shape[0]
    n = bt_ref.shape[0] // gb

    @pl.when(pl.program_id(1) == 0)
    def _first_chunk():
        h_ref[...] = h0_ref[...]

    def group(g):
        """The chunk's C [Q, N] and B^T [N, Q] of the block's ``g``-th group,
        and the scores between them."""
        c, bt = c_ref[:, g * n:(g + 1) * n], bt_ref[g * n:(g + 1) * n, :]
        return c, bt, jnp.dot(c, bt, preferred_element_type=f32)  # [t, s]

    if gb == 1:
        c, bt, scores = group(0)
    t_at = lax.broadcasted_iota(jnp.int32, (q, q), 0)
    s_at = lax.broadcasted_iota(jnp.int32, (q, q), 1)
    for i in range(hb):
        if gb > 1:
            c, bt, scores = group(i * gb // hb)
        csr, dtr = cs_row[i:i + 1, :], dt_row[i:i + 1, :]        # [1, Q]
        csc, w = cs_col[:, i:i + 1], w_col[:, i:i + 1]           # [Q, 1]
        x = x_ref[:, i * p:(i + 1) * p]                          # [Q, P]
        h = h_ref[i]                                             # [N, P]
        # inside the chunk: what position s adds to position t >= s
        decay = jnp.exp(jnp.where(s_at <= t_at, csc - csr, -jnp.inf))
        y = jnp.dot((scores * decay * dtr).astype(op), x,
                    preferred_element_type=f32)
        # what came in from the chunks before
        y_ref[:, i * p:(i + 1) * p] = y + jnp.exp(csc) * jnp.dot(
            c, h.astype(op), preferred_element_type=f32)
        # and what this chunk hands on: the state decayed over the whole
        # chunk, and each position's input decayed to the chunk's end
        h_ref[i] = keep_ref[i:i + 1, :] * h + jnp.dot(
            bt, (x.astype(f32) * w).astype(op), preferred_element_type=f32)


def _ssd_scan_pallas(x, dt, A, B, C, D, chunk, true_len, h0, interpret):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    T, H, P = x.shape
    G, N = B.shape[1:]
    R, f32, Q = H // G, jnp.float32, chunk
    pad = (-T) % Q
    n = (T + pad) // Q
    hb, gb = _scan_blocks(H, G, N, P)
    dt_m = jnp.pad(_stopped(dt, true_len), ((0, pad), (0, 0)))
    # a chunk's own running sum of dt A, as rows and as columns; what a
    # position's input keeps to the chunk's end (w) and the state over it
    cs = jnp.cumsum((dt_m * A.astype(f32)).reshape(n, Q, H), axis=1)
    w = jnp.exp(cs[:, -1:] - cs) * dt_m.reshape(n, Q, H)
    keep = jnp.broadcast_to(jnp.exp(cs[:, -1])[..., None], (n, H, P))

    def rows(a):  # [n Q, H] -> a chunk's heads as rows: [n, H, Q]
        return a.reshape(n, Q, H).transpose(0, 2, 1)

    def cols(a):  # [n Q, H] -> a block of heads as columns: [H / hb, n Q, hb]
        return a.reshape(n * Q, H // hb, hb).transpose(1, 0, 2)

    def padded(a):  # zeros behind the row, to whole chunks
        return jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))

    x2 = padded(x).reshape(n * Q, H * P)
    bt = padded(B).astype(x.dtype).reshape(n * Q, G * N).T      # [G N, T]
    c2 = padded(C).astype(x.dtype).reshape(n * Q, G * N)
    h0 = jnp.zeros((H, N, P), f32) if h0 is None else h0.astype(f32)
    row = pl.BlockSpec((None, hb, Q), lambda j, c: (c, j, 0))
    col = pl.BlockSpec((None, Q, hb), lambda j, c: (j, c, 0))
    heads = pl.BlockSpec((hb, N, P), lambda j, c: (j, 0, 0))
    wide = pl.BlockSpec((Q, hb * P), lambda j, c: (c, j))
    y, h = pl.pallas_call(
        functools.partial(_scan_kernel, hb=hb, p=P, gb=gb),
        grid=(H // hb, n),
        in_specs=[wide, row, row, col, col,
                  pl.BlockSpec((None, hb, P), lambda j, c: (c, j, 0)),
                  pl.BlockSpec((gb * N, Q), lambda j, c: (j * hb // R // gb,
                                                          c)),
                  pl.BlockSpec((Q, gb * N), lambda j, c: (c, j * hb // R
                                                          // gb)),
                  heads],
        out_specs=[wide, heads],
        out_shape=[jax.ShapeDtypeStruct((n * Q, H * P), f32),
                   jax.ShapeDtypeStruct((H, N, P), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="ssd_chunk_scan",
    )(x2, rows(cs), rows(dt_m), cols(cs), cols(w), keep, bt, c2, h0)
    y = y.reshape(n * Q, H, P)[:T] + D.astype(f32)[:, None] * x.astype(f32)
    return y, h


# ------------------------------------------------------------------- decode
def ssm_decode_update_reference(state, x, dt, A, B, C, D, live, *, layer=0):
    """Plain jnp form of :func:`ssm_decode_update`: every slot's state is
    read, a live slot's is replaced, an idle slot's is put back as it was."""
    S, H, P = x.shape
    G = B.shape[1]
    f32 = jnp.float32
    k = H // state.shape[2]                       # heads side by side a row
    h = unpack_heads(state[layer], k)                         # [S, H, N, P]
    b, c = (jnp.repeat(a.astype(f32), H // G, axis=1) for a in (B, C))
    dt = dt.astype(f32)
    new = jnp.exp(dt * A)[..., None, None] * h \
        + b[..., None] * (dt[..., None] * x.astype(f32))[:, :, None, :]
    y = jnp.einsum("shn,shnp->shp", c, new,
                   precision=lax.Precision.HIGHEST) \
        + D[:, None] * x.astype(f32)
    keep = live[:, None, None, None]
    state = lax.dynamic_update_index_in_dim(
        state, pack_heads(jnp.where(keep, new, h), k), layer, 0)
    return (jnp.where(live[:, None, None], y, 0.0), state,
            jnp.int32(S))


def _head_block(heads_a_group: int, n: int, p: int) -> int:
    """Heads a grid step moves: the most that divide a group (so a block has
    one ``B`` and one ``C``) within ``_BLOCK_BYTES`` of float32 state."""
    fit = max(1, _BLOCK_BYTES // (n * p * 4))
    return max(d for d in range(1, heads_a_group + 1)
               if heads_a_group % d == 0 and d <= fit)


def _blocks_of(rows_h: int, groups: int, n: int, p: int):
    """(rows a grid step of the decode kernel moves, groups among them):
    within one group where a group has several rows of state
    (:func:`_head_block`); where each row is a group of its own (a key a
    head, a state as wide as it is deep), as many whole rows as
    ``_BLOCK_BYTES`` holds, each its own group."""
    if groups == rows_h and n == p:
        hb = _head_block(rows_h, n, p)
        return hb, hb
    return _head_block(rows_h // groups, n, p), 1


def _update_kernel(order_ref, n_live_ref, layer_ref, decay_ref, xdt_ref,
                   bc_ref, h_in, y_ref, h_out, fetched_ref, in_buf, out_buf,
                   in_sem, out_sem, *, blocks: int, hb: int, gb: int):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    i, j = pl.program_id(0), pl.program_id(1)
    t = i * blocks + j                    # this visit, in the order of work
    n = n_live_ref[0] * blocks            # the visits that have work
    layer = layer_ref[0]
    k = lax.rem(t, 2)

    def where(step):
        return layer, order_ref[step // blocks], \
            pl.ds(lax.rem(step, blocks) * hb, hb)

    def fetch(step, buf):
        return pltpu.make_async_copy(h_in.at[where(step)], in_buf.at[buf],
                                     in_sem.at[buf])

    def store(step, buf):
        return pltpu.make_async_copy(out_buf.at[buf], h_out.at[where(step)],
                                     out_sem.at[buf])

    @pl.when(t == 0)
    def _count_from_zero():
        fetched_ref[0] = 0

    @pl.when(t < n)
    def _live():
        @pl.when(t == 0)
        def _first():
            fetch(0, 0).start()

        @pl.when(t + 1 < n)
        def _next():
            fetch(t + 1, 1 - k).start()

        fetch(t, k).wait()

        @pl.when(t >= 2)  # the buffer this visit writes must have left
        def _left():
            store(t - 2, k).wait()

        # B and C of each of the block's ``gb`` groups, as [N, P]: a group's
        # pair as two columns, or, a group a row, as two rows across the
        # lanes (two columns would be padded to a row of lanes each in HBM
        # and moved so) turned into columns
        if gb == 1:
            shape = bc_ref.shape[1:2] + decay_ref.shape[-1:]
            bc = [(jnp.broadcast_to(bc_ref[0, :, 0:1], shape),
                   jnp.broadcast_to(bc_ref[0, :, 1:2], shape))]
        else:
            shape = decay_ref.shape[-1:] + bc_ref.shape[-1:]
            bc = [(jnp.broadcast_to(bc_ref[g, 0:1, :], shape).T,
                   jnp.broadcast_to(bc_ref[g, 1:2, :], shape).T)
                  for g in range(gb)]
        for hh in range(hb):
            b, c = bc[hh * gb // hb]
            h = in_buf[k, hh] * decay_ref[hh:hh + 1, :] \
                + b * xdt_ref[hh:hh + 1, :]
            out_buf[k, hh] = h
            y_ref[hh:hh + 1, :] = jnp.sum(h * c, axis=0, keepdims=True)
        store(t, k).start()

        @pl.when(j == 0)
        def _count():
            fetched_ref[0] += 1

        @pl.when(t + 1 == n)  # the last visit with work: all of it lands
        def _drain():
            store(t, k).wait()

            @pl.when(t >= 1)
            def _before():
                store(t - 1, 1 - k).wait()

    @pl.when(t >= n)
    def _idle():
        y_ref[...] = jnp.zeros_like(y_ref)


def _ssm_decode_update_pallas(state, x, dt, A, B, C, D, live, layer,
                              interpret, name):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S, H, P = x.shape
    G, N = B.shape[1:]
    f32 = jnp.float32
    # rows of the resident state: ``k`` heads side by side (1: a head a row)
    rows_h, lanes = state.shape[2], state.shape[-1]
    hb, gb = _blocks_of(rows_h, G, N, lanes)
    blocks, per_group = rows_h // hb, rows_h // G
    dt, x = dt.astype(f32), x.astype(f32)
    # the live slots first, in order: the kernel's work list
    order = jnp.argsort(jnp.logical_not(live), stable=True).astype(jnp.int32)
    n_live = jnp.sum(live, dtype=jnp.int32).reshape(1)
    # a head's decay and input in that head's lanes of its row
    decay = jnp.broadcast_to(jnp.exp(dt * A)[..., None],
                             (S, H, P)).reshape(S, rows_h, lanes)
    xdt = (dt[..., None] * x).reshape(S, rows_h, lanes)
    # B and C of a group as columns (the state dimension along the
    # sublanes), or, a group a row, as rows
    bc = jnp.stack([B.astype(f32), C.astype(f32)], axis=-1 if gb == 1 else 2)
    pair = (N, 2) if gb == 1 else (2, N)               # [S, G, pair]
    rows = pl.BlockSpec((None, hb, lanes),
                        lambda i, j, order, *_: (order[i], j, 0))
    y, state, fetched = pl.pallas_call(
        functools.partial(_update_kernel, blocks=blocks, hb=hb, gb=gb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(S, blocks),
            in_specs=[rows, rows,
                      pl.BlockSpec((None, gb) + pair,
                                   lambda i, j, order, *_:
                                   (order[i], j * hb // per_group // gb, 0,
                                    0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[rows, pl.BlockSpec(memory_space=pl.ANY),
                       pl.BlockSpec(memory_space=pltpu.SMEM)],
            scratch_shapes=[pltpu.VMEM((2, hb, N, lanes), f32),
                            pltpu.VMEM((2, hb, N, lanes), f32),
                            pltpu.SemaphoreType.DMA((2,)),
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=[jax.ShapeDtypeStruct((S, rows_h, lanes), f32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((1,), jnp.int32)],
        # operands count the three prefetched scalars: the state is the 7th
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name=name,
    )(order, n_live, jnp.asarray(layer, jnp.int32).reshape(1), decay, xdt,
      bc, state)
    # an idle slot's block was written as zeros and stays so
    return (jnp.where(live[:, None, None],
                      y.reshape(S, H, P) + D[:, None] * x, 0.0), state,
            fetched[0])


def ssm_kernel_takes(state, x) -> bool:
    """Can the compiled kernel tile these shapes on a TPU? A float32 state
    whose rows (a head's channels, or :func:`pack_heads`' heads side by
    side) fill the lanes and whose state dimension whole sublanes."""
    return (state.dtype == jnp.float32 and state.shape[-1] % 128 == 0
            and state.shape[-2] % 8 == 0)


def heads_a_row(head_dim: int) -> int:
    """Heads of ``head_dim`` channels that lie side by side in a row of the
    resident state: as many as fill a row of 128 lanes (1 from 128 up)."""
    return 128 // head_dim if head_dim < 128 and 128 % head_dim == 0 else 1


def pack_heads(h, k: int):
    """``[..., H, N, P]`` -> ``[..., H / k, N, k P]``: ``k`` consecutive
    heads side by side along the lanes (the resident state's layout; ``k``
    divides a group's heads, so a row has one ``B`` and one ``C``)."""
    if k == 1:
        return h
    *lead, H, N, P = h.shape
    return jnp.swapaxes(h.reshape(*lead, H // k, k, N, P), -2, -3).reshape(
        *lead, H // k, N, k * P)


def unpack_heads(h, k: int):
    """The inverse of :func:`pack_heads`."""
    if k == 1:
        return h
    *lead, R, N, W = h.shape
    return jnp.swapaxes(h.reshape(*lead, R, N, k, W // k), -2, -3).reshape(
        *lead, R * k, N, W // k)


def ssm_decode_update(state, x, dt, A, B, C, D, live, *, layer=0,
                      use_pallas: Optional[str] = None,
                      name: str = "ssm_decode_update"):
    """One token-step of one layer against the resident state.

    ``state`` [L, S, H / k, N, k P] float32 (``k`` heads side by side a row,
    :func:`pack_heads`; ``k`` = 1 is [L, S, H, N, P]), of which ``layer`` (a
    traced scalar is fine) is read and written where it lies; one token a
    slot: ``x`` [S, H, P], ``dt`` [S, H] after its softplus, ``A`` [H]
    negative, ``B``, ``C`` [S, G, N], ``D`` [H]; ``live`` bool [S]. ``k`` is
    read from the shapes. A live slot's state becomes
    ``exp(dt A) h + B (x) (dt x)`` and its ``y = C . h + D x``; an idle
    slot's state stays as it is, bit for bit, and its ``y`` is 0. Returns
    ``(y [S, H, P] float32, state, fetched)``: ``fetched`` int32, the slots
    whose state was read (the kernel counts its own fetches: the live slots;
    the plain form reads them all).

    ``use_pallas``: "on", "interpret", "off", or None = the kernel on a TPU
    for a shape it can tile (:func:`ssm_kernel_takes`), else the plain form.
    ``name``: the kernel's name in the compiled program and in a trace; a
    caller that is not the decode program's token-step gives its own, so
    that whoever counts token-steps by the kernel's calls counts its own.
    """
    if use_pallas is None:
        use_pallas = "on" if _on_tpu() and ssm_kernel_takes(state, x) \
            else "off"
    if use_pallas == "off":
        return ssm_decode_update_reference(state, x, dt, A, B, C, D, live,
                                           layer=layer)
    return _ssm_decode_update_pallas(state, x, dt, A, B, C, D, live, layer,
                                     interpret=(use_pallas == "interpret"),
                                     name=name)
