"""Flash attention as Pallas TPU kernels (forward AND backward), with a
pure-jnp reference.

Net-new versus the reference (SURVEY.md §2.4: the reference has NO attention
kernels — GPU attention lives inside user torch code). Here the hot op is a
first-class TPU kernel; three ``pallas_call``s over [BH, S, D] operands:

  - forward: online-softmax blockwise attention, grid (BH, n_q, groups of
    K/V blocks); returns the output and, for the backward, the logsumexp
    as a (BH, S, 1) column.
  - backward: a dq kernel on the same grid and a dk/dv kernel on grid
    (BH, n_k, groups of q/dO blocks), each recomputing the p tile from q, k
    and the saved lse (rematerialisation: one extra QK^T product for never
    materialising the S×S matrix — training memory is O(S·D), not O(S²)).
    The dk/dv kernel holds its tile transposed (s^T = k q^T), so dv += p^T dO
    and dk += ds^T q are plain products and nothing the size of a tile is
    transposed; lse and delta reach it as rows.

What a score tile costs around its MXU products (measured on a v5e,
PERF.md §6, PR 32):

  - operands reach the MXU in the dtype they arrive in, with float32
    accumulation; the second operands the kernels make themselves (p, ds)
    are cast to that dtype before their product, which is what the MXU did
    to float32 operands anyway. float32 inputs stay float32 throughout.
    ``scale`` is folded into the resident (block, D) tile (q, or k in the
    dk/dv kernel) and into dq / dk at the end, never into a score tile.
    Softmax statistics, accumulators, lse and delta are float32.
  - the streamed operand pair (K and V; q and dO for dk/dv) is resident a
    group of blocks at a time (``_pick_group``: as many as fit
    ``_STREAM_BYTES``, the whole head at the lengths served and trained),
    seen as [BH, n_blocks, block, D], and a ``fori_loop`` inside the grid
    step walks the blocks by index: first those wholly below the diagonal,
    with no mask built, then the ones the diagonal crosses, where alone the
    two iotas, the compare and the select are made. Blocks wholly above
    the diagonal are never touched. Where a head needs more than one group,
    the index map clamps a step past the diagonal to the group already
    resident, so it fetches nothing.
  - the forward keeps the running max in all 128 lanes and the denominator
    as 128 lane partial sums a row: the row max is the one reduction across
    lanes a tile costs, the sum's waits for the last step, and no per-row
    value is broadcast across lanes inside the loop.
  - tile shape: ``_pick_block`` (the largest divisor of the length up to
    512 rows, from S and Skv alone) and ``_pick_group`` (from the block's
    bytes: D and dtype). No flag, environment variable or model decides it.
  - the calls are jitted (``_fwd_call``, ``_bwd_calls``), so a program of
    many layers traces and lowers each kernel once.
  - CPU/testing: the same kernels run under interpret mode; tests compare
    against the jnp reference on a virtual device.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

_NEG_INF = -1e30
DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512


def _largest_divisor(n: int, at_most: int) -> int:
    d = max(1, min(n, at_most))
    while n % d:
        d -= 1
    return d


def _pick_block(n: int, target: int, interpret: bool) -> int:
    """Largest divisor of n that is <= target. The compiled TPU lowering
    takes a block whose row count is a multiple of 8 or the whole
    dimension; a length whose largest divisor is neither is refused here,
    by name, rather than in the compiler."""
    b = _largest_divisor(n, target)
    if not interpret and b % 8 and b != n:
        raise ValueError(
            f"flash attention cannot tile a sequence of length {n} for the "
            f"TPU: its largest divisor <= {target} is {b}, and a block must "
            f"have a multiple of 8 rows or span the whole sequence. Pad the "
            f"sequence to a multiple of 8, or ask for attention='ref' by "
            f"name")
    return b


def reference_attention(q, k, v, causal: bool = True,
                        scale: Optional[float] = None):
    """Plain jnp attention (the correctness oracle)."""
    *_, S, D = q.shape
    Skv = k.shape[-2]
    scale = scale if scale is not None else D ** -0.5
    s = jnp.einsum("...qd,...kd->...qk", q, k).astype(jnp.float32) * scale
    if causal:
        qi = jnp.arange(S)[:, None] + (Skv - S)
        ki = jnp.arange(Skv)[None, :]
        s = jnp.where(ki <= qi, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("...qk,...kd->...qd", p.astype(v.dtype), v)


_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_NN = (((1,), (0,)), ((), ()))  # a @ b
_LANES = 128
# what one grid step may hold of the streamed operand pair (K and V, or q
# and dO); the pipeline keeps two such buffers
_STREAM_BYTES = 4 << 20
_VMEM_LIMIT_BYTES = 64 << 20


def _pick_group(n_blocks: int, pair_bytes: int, stream_bytes: int) -> int:
    """How many blocks of the streamed pair one grid step holds: the largest
    divisor of n_blocks whose pair fits stream_bytes (at least one)."""
    return _largest_divisor(n_blocks, stream_bytes // pair_bytes)


def _dot(a, b, dims):
    return lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _causal_mask(s, limit, q_axis):
    """Mask a score tile the diagonal crosses: key position - query
    position <= limit survives, both counted from the tile's corner (limit
    = first query row + off - first key column, a scalar). ``q_axis`` is
    the tile's query axis: 0 for (block_q, block_k), 1 for the transposed
    tile of the dk/dv kernel."""
    d = (lax.broadcasted_iota(jnp.int32, s.shape, 1 - q_axis)
         - lax.broadcasted_iota(jnp.int32, s.shape, q_axis))
    return jnp.where(d <= limit, s, _NEG_INF)


def _div_from_zero(x, d):
    """max(x, 0) // d for a traced scalar: every caller clips the quotient
    to a range that starts at 0, and truncating division is floor there."""
    return lax.div(jnp.maximum(x, 0), d)


def _key_slices(reach, block_q, block_k, group, causal):
    """Of a step's ``group`` resident key slices, for a tile of block_q
    query rows whose first sees ``reach`` columns past the step's first:
    (how many lie wholly at or below the diagonal, how many it sees at
    all). Without a mask all of them are the first kind."""
    if not causal:
        return group, group
    return (jnp.minimum(_div_from_zero(reach + 1, block_k), group),
            jnp.minimum(_div_from_zero(reach + block_q - 1 + block_k, block_k),
                        group))


def _key_group_index(causal, block_q, off, span):
    """Index map of the K and V groups on grid (bh, qi, kj): a step past
    the diagonal names the group already resident, so nothing is fetched
    for it."""
    def index(bh, qi, kj):
        if causal:
            kj = jnp.minimum(
                kj, jnp.maximum(qi * block_q + block_q - 1 + off, 0) // span)
        return (bh, kj, 0, 0)

    return index


def _lanes(x, n):
    """A per-row value kept in all 128 lanes, (rows, 128), at the width of
    an (rows, n) tile it is combined with: whole vregs side by side where n
    is a multiple of 128, so that nothing is broadcast across lanes in the
    loop."""
    if n % _LANES == 0:
        return jnp.tile(x, (1, n // _LANES))
    if n < _LANES:
        return x[:, :n]
    return jnp.broadcast_to(x[:, :1], (x.shape[0], n))


def _lane_sums(p):
    """Row sums of p left as 128 partial sums a row (plain adds of whole
    vregs); the one reduction across lanes waits for the kernel's end. A
    width that is no multiple of 128 puts its sum in lane 0."""
    rows, n = p.shape
    if n % _LANES == 0:
        part = p[:, :_LANES]
        for c in range(1, n // _LANES):
            part = part + p[:, c * _LANES:(c + 1) * _LANES]
        return part
    lane = lax.broadcasted_iota(jnp.int32, (rows, _LANES), 1)
    return jnp.where(lane == 0, jnp.sum(p, axis=-1, keepdims=True), 0.0)


def _each_slice(tile, lo, mid, hi, masked_first):
    """Run ``tile(j, masked)`` over the resident slices lo..hi: the ones
    the diagonal crosses (masked) on one side of ``mid``, the ones wholly
    below it on the other, two to a loop step, so that one slice's
    products can be scheduled beside the other's vector work."""

    def loop(a, b, masked, width):
        def body(i, carry):
            for j in range(width):
                tile(a + width * i + j, masked)
            return carry

        steps = lax.div(b - a, width)
        lax.fori_loop(0, steps, body, 0)
        return a + width * steps

    def unmasked(a, b):
        loop(loop(a, b, False, 2), b, False, 1)

    if masked_first:
        loop(lo, mid, True, 1)
        unmasked(mid, hi)
    else:
        unmasked(lo, mid)
        loop(mid, hi, True, 1)


# --------------------------------------------------------------------- forward
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, causal, scale, off):
    import jax.experimental.pallas as pl

    block_q = q_ref.shape[1]
    group, block_k = k_ref.shape[1:3]
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0] * scale
    # the last key column the tile's first query row sees, counted from the
    # first column resident in this step
    reach = qi * block_q + off - kj * (group * block_k)

    def tile(j, masked):
        k = k_ref[0, j]
        v = v_ref[0, j]
        s = _dot(q, k, _NT)
        if masked:
            s = _causal_mask(s, reach - j * block_k, 0)
        # m in every lane and l as lane partial sums, both (block_q, 128):
        # the row max is the one reduction across lanes a slice costs
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - _lanes(m_new, block_k))
        l_scr[...] = l_scr[...] * alpha + _lane_sums(p)
        acc_scr[...] = acc_scr[...] * _lanes(alpha, acc_scr.shape[1]) \
            + _dot(p.astype(v.dtype), v, _NN)
        m_scr[...] = m_new

    # slices wholly at or below the diagonal, then those it crosses; the
    # ones wholly above it are never touched
    n_full, n_run = _key_slices(reach, block_q, block_k, group, causal)
    _each_slice(tile, 0, n_full, n_run, masked_first=False)

    @pl.when(kj == pl.num_programs(2) - 1)
    def _finish():
        l = jnp.maximum(jnp.sum(l_scr[...], axis=-1, keepdims=True), 1e-30)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)
        if lse_ref is not None:
            lse_ref[0] = m_scr[:, :1] + jnp.log(l)


def _blocked(x, block):
    """[BH, S, D] seen as [BH, S // block, block, D]: a grid step holds a
    group of blocks and the kernel takes one by its index, so no slice of a
    ref ever starts at a row the tiling cannot take."""
    BH, S, D = x.shape
    return x.reshape(BH, S // block, block, D)


def _compiler_params(interpret):
    if interpret:
        return {}
    from jax.experimental.pallas import tpu as pltpu

    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT_BYTES)}


def _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret,
               save_lse=True):
    """Returns (out, lse) when save_lse else out; lse is (BH, S, 1) fp32.
    Inference callers pass save_lse=False so the kernel never writes the
    lse array (pallas outputs are not dead-code-eliminated)."""
    return _fwd_call(q, k, v, causal, scale, block_q, block_k, interpret,
                     save_lse, _STREAM_BYTES)


# jitted with everything but the arrays static, so that a program's layers
# trace and lower each kernel once and not once a layer (a train step holds
# 24 calls of three kernels)
@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7, 8, 9))
def _fwd_call(q, k, v, causal, scale, block_q, block_k, interpret, save_lse,
              stream_bytes):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    BH, S, D = q.shape
    Skv = k.shape[1]
    off = Skv - S
    block_q = _pick_block(S, block_q, interpret)
    block_k = _pick_block(Skv, block_k, interpret)
    n_k = Skv // block_k
    group = _pick_group(n_k, 2 * block_k * D * k.dtype.itemsize,
                        stream_bytes)
    grid = (BH, S // block_q, n_k // group)
    kernel = functools.partial(_fwd_kernel, causal=causal, scale=scale,
                               off=off)
    if not save_lse:
        def kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                   _inner=kernel):
            _inner(q_ref, k_ref, v_ref, o_ref, None, m_scr, l_scr, acc_scr)

    kv_index = _key_group_index(causal, block_q, off, group * block_k)
    out_shape = [jax.ShapeDtypeStruct(q.shape, q.dtype)]
    out_specs = [pl.BlockSpec((1, block_q, D), lambda bh, qi, kj: (bh, qi, 0))]
    if save_lse:
        out_shape.append(jax.ShapeDtypeStruct((BH, S, 1), jnp.float32))
        out_specs.append(
            pl.BlockSpec((1, block_q, 1), lambda bh, qi, kj: (bh, qi, 0)))
    res = pl.pallas_call(
        kernel,
        out_shape=tuple(out_shape),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda bh, qi, kj: (bh, qi, 0)),
            pl.BlockSpec((1, group, block_k, D), kv_index),
            pl.BlockSpec((1, group, block_k, D), kv_index),
        ],
        out_specs=tuple(out_specs),
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        interpret=interpret,
        **_compiler_params(interpret),
    )(q, _blocked(k, block_k), _blocked(v, block_k))
    return res if save_lse else res[0]


# -------------------------------------------------------------------- backward
def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_scr, *, causal, scale, off):
    import jax.experimental.pallas as pl

    block_q = q_ref.shape[1]
    group, block_k = k_ref.shape[1:3]
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    q = q_ref[0] * scale
    do = do_ref[0]
    lse = lse_ref[0]
    delta = delta_ref[0]
    reach = qi * block_q + off - kj * (group * block_k)

    def tile(j, masked):
        k = k_ref[0, j]
        v = v_ref[0, j]
        s = _dot(q, k, _NT)
        if masked:
            s = _causal_mask(s, reach - j * block_k, 0)
        p = jnp.exp(s - lse)
        ds = p * (_dot(do, v, _NT) - delta)
        dq_scr[...] += _dot(ds.astype(k.dtype), k, _NN)

    n_full, n_run = _key_slices(reach, block_q, block_k, group, causal)
    _each_slice(tile, 0, n_full, n_run, masked_first=False)

    @pl.when(kj == pl.num_programs(2) - 1)
    def _finish():
        dq_ref[0] = (dq_scr[...] * scale).astype(dq_ref.dtype)


def _dkv_kernel(k_ref, v_ref, q_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_scr, dv_scr, *, causal, scale, off):
    """The tile is held transposed, (block_k, block_q): s^T = k q^T, so that
    dv += p^T dO and dk += ds^T q are plain products and nothing the size
    of the tile is transposed; lse and delta come as rows for it."""
    import jax.experimental.pallas as pl

    block_k = k_ref.shape[1]
    group, block_q = q_ref.shape[1:3]
    ki = pl.program_id(1)
    qj = pl.program_id(2)

    @pl.when(qj == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    k = k_ref[0] * scale
    v = v_ref[0]
    # the first query row resident in this step sees key columns up to
    # reach, counted from the tile's first column
    reach = qj * (group * block_q) + off - ki * block_k

    def tile(j, masked):
        q = q_ref[0, j]
        do = do_ref[0, j]
        st = _dot(k, q, _NT)
        if masked:
            st = _causal_mask(st, reach + j * block_q, 1)
        pt = jnp.exp(st - lse_ref[0, j])
        dv_scr[...] += _dot(pt.astype(do.dtype), do, _NN)
        dst = pt * (_dot(v, do, _NT) - delta_ref[0, j])
        dk_scr[...] += _dot(dst.astype(q.dtype), q, _NN)

    j_run = j_full = 0
    if causal:
        # slices wholly above the diagonal are never touched; then the
        # ones it crosses, then those wholly below it
        j_run = jnp.minimum(_div_from_zero(-reach, block_q), group)
        j_full = jnp.clip(_div_from_zero(block_k - 1 - reach + block_q - 1,
                                    block_q), j_run, group)
    _each_slice(tile, j_run, j_full, group, masked_first=True)

    @pl.when(qj == pl.num_programs(2) - 1)
    def _finish():
        dk_ref[0] = (dk_scr[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _flash_bwd(q, k, v, o, lse, g, causal, scale, block_q, block_k,
               interpret):
    return _bwd_calls(q, k, v, o, lse, g, causal, scale, block_q, block_k,
                      interpret, _STREAM_BYTES)


@functools.partial(jax.jit, static_argnums=(6, 7, 8, 9, 10, 11))
def _bwd_calls(q, k, v, o, lse, g, causal, scale, block_q, block_k,
               interpret, stream_bytes):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    BH, S, D = q.shape
    Skv = k.shape[1]
    off = Skv - S
    block_q = _pick_block(S, block_q, interpret)
    block_k = _pick_block(Skv, block_k, interpret)
    n_q, n_k = S // block_q, Skv // block_k
    # delta_i = rowsum(dO_i * O_i) — tiny elementwise pass XLA fuses
    delta = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)

    k_group = _pick_group(n_k, 2 * block_k * D * k.dtype.itemsize,
                          stream_bytes)
    kv_index = _key_group_index(causal, block_q, off, k_group * block_k)
    q_tile = pl.BlockSpec((1, block_q, D), lambda bh, qi, kj: (bh, qi, 0))
    q_column = pl.BlockSpec((1, block_q, 1), lambda bh, qi, kj: (bh, qi, 0))
    kv_group = pl.BlockSpec((1, k_group, block_k, D), kv_index)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, causal=causal, scale=scale, off=off),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        grid=(BH, n_q, n_k // k_group),
        in_specs=[q_tile, kv_group, kv_group, q_tile, q_column, q_column],
        out_specs=q_tile,
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        interpret=interpret,
        **_compiler_params(interpret),
    )(q, _blocked(k, block_k), _blocked(v, block_k), g, lse,
      delta[..., None])

    q_group = _pick_group(n_q, 2 * block_q * D * q.dtype.itemsize,
                          stream_bytes)
    q_span = q_group * block_q

    def q_index(bh, ki, qj):
        if causal:  # a step before the diagonal names the first group needed
            qj = jnp.maximum(
                qj, jnp.minimum(jnp.maximum(ki * block_k - off, 0) // q_span,
                                n_q // q_group - 1))
        return (bh, qj, 0, 0)

    k_tile = pl.BlockSpec((1, block_k, D), lambda bh, ki, qj: (bh, ki, 0))
    q_rows = pl.BlockSpec((1, q_group, block_q, D), q_index)
    q_row = pl.BlockSpec((1, q_group, 1, block_q), q_index)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, causal=causal, scale=scale, off=off),
        out_shape=(
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ),
        grid=(BH, n_k, n_q // q_group),
        in_specs=[k_tile, k_tile, q_rows, q_rows, q_row, q_row],
        out_specs=(k_tile, k_tile),
        scratch_shapes=[
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, D), jnp.float32),
        ],
        interpret=interpret,
        **_compiler_params(interpret),
    )(k, v, _blocked(q, block_q), _blocked(g, block_q),
      lse.reshape(BH, n_q, 1, block_q), delta.reshape(BH, n_q, 1, block_q))
    return dq, dk, dv


# ------------------------------------------------------------------ public API
def _on_tpu() -> bool:
    """Is default computation placed on TPU? jax_default_device (set by CPU
    test harnesses) wins over the default backend, because compiled Pallas
    only lowers on the TPU backend."""
    dd = jax.config.jax_default_device
    if dd is not None:
        return (dd if isinstance(dd, str) else dd.platform) == "tpu"
    return jax.default_backend() == "tpu"


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_attention(q, k, v, causal, scale, use_pallas, block_q, block_k):
    if use_pallas == "off":
        return reference_attention(q, k, v, causal, scale)
    return _flash_fwd(q, k, v, causal, scale, block_q, block_k,
                      interpret=(use_pallas == "interpret"), save_lse=False)


def _flash_fwd_rule(q, k, v, causal, scale, use_pallas, block_q, block_k):
    if use_pallas == "off":
        out = reference_attention(q, k, v, causal, scale)
        return out, (q, k, v, out, None)
    out, lse = _flash_fwd(q, k, v, causal, scale, block_q, block_k,
                          interpret=(use_pallas == "interpret"))
    return out, (q, k, v, out, lse)


def _flash_bwd_rule(causal, scale, use_pallas, block_q, block_k,
                    residuals, g):
    q, k, v, out, lse = residuals
    if use_pallas == "off":
        _, vjp = jax.vjp(
            lambda q_, k_, v_: reference_attention(q_, k_, v_, causal, scale),
            q, k, v)
        return vjp(g)
    return _flash_bwd(q, k, v, out, lse, g, causal, scale, block_q, block_k,
                      interpret=(use_pallas == "interpret"))


_flash_attention.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def flash_attention(q, k, v, causal: bool = True,
                    scale: Optional[float] = None,
                    use_pallas: Optional[str] = None,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K,
                    mesh=None):
    """Multi-head attention over [B, H, S, D] (or [BH, S, D]) inputs.

    ``use_pallas``: "on" (compiled kernel), "interpret" (kernel under the
    Pallas interpreter — CPU testing, only ever by name), "off" (jnp
    reference), or None = auto: "on" when running on TPU, "off" elsewhere.
    Auto decides by the platform alone: on a TPU a shape the kernel cannot
    take raises (``_pick_block``), it never gives way to the reference.
    Differentiable either way: the Pallas path uses the blockwise backward
    kernels.

    ``mesh``: inside a jit sharded over a mesh of several devices the
    compiler refuses to partition a Mosaic kernel ("wrap the call in a
    shard_map"), so pass the mesh and [B, H, S, D] inputs: each device
    then runs the kernel on its own block — batch over the data axes
    (dp, fsdp), heads over tp, which is how column-parallel wq/wk/wv
    leave q/k/v laid out anyway.
    """
    if use_pallas is None:
        use_pallas = "on" if _on_tpu() else "off"
    if use_pallas != "off" and mesh is not None and mesh.size > 1:
        from jax.sharding import PartitionSpec as P

        batch_axes = tuple(a for a in ("dp", "fsdp") if a in mesh.shape)
        spec = P(batch_axes or None, "tp" if "tp" in mesh.shape else None,
                 None, None)
        return jax.shard_map(
            functools.partial(flash_attention, causal=causal, scale=scale,
                              use_pallas=use_pallas, block_q=block_q,
                              block_k=block_k),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False)(q, k, v)
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    squeeze = q.ndim == 4
    if squeeze:
        B, H, S, D = q.shape
        qf = q.reshape(B * H, S, D)
        kf = k.reshape(B * H, k.shape[-2], D)
        vf = v.reshape(B * H, v.shape[-2], D)
    else:
        qf, kf, vf = q, k, v
    out = _flash_attention(qf, kf, vf, causal, scale, use_pallas,
                           block_q, block_k)
    return out.reshape(q.shape) if squeeze else out
