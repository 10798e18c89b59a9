"""Flash attention as Pallas TPU kernels (forward AND backward), with a
pure-jnp reference.

Net-new versus the reference (SURVEY.md §2.4: the reference has NO attention
kernels — GPU attention lives inside user torch code). Here the hot op is a
first-class TPU kernel:

  - forward: online-softmax blockwise attention. Grid is (BH, n_q, n_k): the
    K/V sequence streams through VMEM one (block_k, D) tile per grid step —
    VMEM stays O(block), so S is bounded by HBM, not VMEM. Running max /
    denominator / output accumulate in VMEM scratch across the innermost
    grid dimension; the logsumexp is saved for the backward in a (BH, S, 1)
    layout — blocks of (1, block_q, 1) are legal on TPU because the last
    block dim equals the array dim, so the per-row vector costs S fp32
    words, not a lane-replicated tile.
  - backward: two Pallas kernels, both O(block) VMEM: a dq kernel on grid
    (BH, n_q, n_k) and a dk/dv kernel on grid (BH, n_k, n_q), each
    recomputing the p tile from q, k and the saved lse (rematerialisation:
    trades one extra QK^T matmul for never materialising the S×S matrix —
    training memory is O(S·D), not O(S²)).
  - causal masking skips fully-masked tiles via pl.when on both passes, so
    the causal schedule does ~half the tile work.
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


def _pick_block(n: int, target: int, interpret: bool) -> int:
    """Largest divisor of n that is <= target. The compiled TPU lowering
    takes a block whose row count is a multiple of 8 or the whole
    dimension; a length whose largest divisor is neither is refused here,
    by name, rather than in the compiler."""
    b = min(n, target)
    while n % b:
        b -= 1
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


def _causal_mask(s, qi, ki, block_q, block_k, off):
    """Mask the (block_q, block_k) score tile: col <= row + off survives
    (off = Skv - S supports cross/prefix attention like the reference)."""
    rows = qi * block_q + lax.broadcasted_iota(jnp.int32, s.shape, 0) + off
    cols = ki * block_k + lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(cols <= rows, s, _NEG_INF)


# --------------------------------------------------------------------- forward
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, causal, scale, block_q, block_k, off):
    import jax.experimental.pallas as pl

    qi = pl.program_id(1)
    ki = pl.program_id(2)
    n_k = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # a tile is fully masked iff its smallest col exceeds its largest row+off
    run_pred = (ki * block_k <= qi * block_q + (block_q - 1) + off
                if causal else True)

    @pl.when(run_pred)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
        if causal:
            s = _causal_mask(s, qi, ki, block_q, block_k, off)
        m_prev = m_scr[:, :1]
        l_prev = l_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ki == n_k - 1)
    def _finish():
        l = jnp.maximum(l_scr[:, :1], 1e-30)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)
        if lse_ref is not None:
            lse_ref[0] = m_scr[:, :1] + jnp.log(l)


def _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret,
               save_lse=True):
    """Returns (out, lse) when save_lse else out; lse is (BH, S, 1) fp32.
    Inference callers pass save_lse=False so the kernel never writes the
    lse array (pallas outputs are not dead-code-eliminated)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    BH, S, D = q.shape
    Skv = k.shape[1]
    off = Skv - S
    block_q = _pick_block(S, block_q, interpret)
    block_k = _pick_block(Skv, block_k, interpret)
    grid = (BH, S // block_q, Skv // block_k)
    kernel = functools.partial(_fwd_kernel, causal=causal, scale=scale,
                               block_q=block_q, block_k=block_k, off=off)
    if not save_lse:
        def kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                   _inner=kernel):
            _inner(q_ref, k_ref, v_ref, o_ref, None, m_scr, l_scr, acc_scr)
    out_shape = [jax.ShapeDtypeStruct(q.shape, q.dtype)]
    out_specs = [pl.BlockSpec((1, block_q, D), lambda bh, qi, ki: (bh, qi, 0))]
    if save_lse:
        out_shape.append(jax.ShapeDtypeStruct((BH, S, 1), jnp.float32))
        out_specs.append(
            pl.BlockSpec((1, block_q, 1), lambda bh, qi, ki: (bh, qi, 0)))
    res = pl.pallas_call(
        kernel,
        out_shape=tuple(out_shape),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, D), lambda bh, qi, ki: (bh, ki, 0)),
            pl.BlockSpec((1, block_k, D), lambda bh, qi, ki: (bh, ki, 0)),
        ],
        out_specs=tuple(out_specs),
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return res if save_lse else res[0]


# -------------------------------------------------------------------- backward
def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_scr, *, causal, scale, block_q, block_k, off):
    import jax.experimental.pallas as pl

    qi = pl.program_id(1)
    ki = pl.program_id(2)
    n_k = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    run_pred = (ki * block_k <= qi * block_q + (block_q - 1) + off
                if causal else True)

    @pl.when(run_pred)
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0][:, :1]
        delta = delta_ref[0][:, :1]
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        if causal:
            s = _causal_mask(s, qi, ki, block_q, block_k, off)
        p = jnp.exp(s - lse)
        dp = lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dq_scr[...] += lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == n_k - 1)
    def _finish():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _dkv_kernel(k_ref, v_ref, q_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_scr, dv_scr,
                *, causal, scale, block_q, block_k, off):
    import jax.experimental.pallas as pl

    ki = pl.program_id(1)
    qi = pl.program_id(2)
    n_q = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    # fully masked iff the tile's largest row+off is below its smallest col
    run_pred = (qi * block_q + (block_q - 1) + off >= ki * block_k
                if causal else True)

    @pl.when(run_pred)
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0][:, :1]
        delta = delta_ref[0][:, :1]
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        if causal:
            s = _causal_mask(s, qi, ki, block_q, block_k, off)
        p = jnp.exp(s - lse)  # (block_q, block_k)
        # dv += p^T @ do; dk += ds^T @ q — contract over the q rows so no
        # explicit transpose materialises
        dv_scr[...] += lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dk_scr[...] += lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(qi == n_q - 1)
    def _finish():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _flash_bwd(q, k, v, o, lse, g, causal, scale, block_q, block_k,
               interpret):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    BH, S, D = q.shape
    Skv = k.shape[1]
    off = Skv - S
    block_q = _pick_block(S, block_q, interpret)
    block_k = _pick_block(Skv, block_k, interpret)
    # delta_i = rowsum(dO_i * O_i) — tiny elementwise pass XLA fuses
    delta = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)[..., None]  # (BH, S, 1)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, causal=causal, scale=scale,
                          block_q=block_q, block_k=block_k, off=off),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        grid=(BH, S // block_q, Skv // block_k),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, D), lambda bh, qi, ki: (bh, ki, 0)),
            pl.BlockSpec((1, block_k, D), lambda bh, qi, ki: (bh, ki, 0)),
            pl.BlockSpec((1, block_q, D), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_q, 1), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_q, 1), lambda bh, qi, ki: (bh, qi, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, D),
                               lambda bh, qi, ki: (bh, qi, 0)),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        interpret=interpret,
    )(q, k, v, g, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, causal=causal, scale=scale,
                          block_q=block_q, block_k=block_k, off=off),
        out_shape=(
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ),
        grid=(BH, Skv // block_k, S // block_q),
        in_specs=[
            pl.BlockSpec((1, block_k, D), lambda bh, ki, qi: (bh, ki, 0)),
            pl.BlockSpec((1, block_k, D), lambda bh, ki, qi: (bh, ki, 0)),
            pl.BlockSpec((1, block_q, D), lambda bh, ki, qi: (bh, qi, 0)),
            pl.BlockSpec((1, block_q, D), lambda bh, ki, qi: (bh, qi, 0)),
            pl.BlockSpec((1, block_q, 1), lambda bh, ki, qi: (bh, qi, 0)),
            pl.BlockSpec((1, block_q, 1), lambda bh, ki, qi: (bh, qi, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, block_k, D), lambda bh, ki, qi: (bh, ki, 0)),
            pl.BlockSpec((1, block_k, D), lambda bh, ki, qi: (bh, ki, 0)),
        ),
        scratch_shapes=[
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, D), jnp.float32),
        ],
        interpret=interpret,
    )(k, v, q, g, lse, delta)
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
