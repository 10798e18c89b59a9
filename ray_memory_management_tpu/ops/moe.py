"""Mixture-of-Experts FFN with expert parallelism (EP).

Net-new versus the reference: SURVEY.md §2.4 lists expert parallelism as
absent there (no MoE anywhere in the snapshot) and marks it a net-new
target for this framework. The design is the GShard/Switch dense-dispatch
formulation, TPU-first:

  - routing, dispatch and combine are einsums over a STATIC capacity —
    no ragged shapes, no host control flow, everything jit-traceable and
    MXU-friendly;
  - expert weights carry a leading expert dim ([E, D, F]); under an
    ``ep`` mesh axis that dim is sharded one-expert-group-per-device and
    the dispatch/combine einsums lower to XLA all-to-alls over ICI
    (param_pspecs places the weights; with_sharding_constraint pins the
    per-expert buffers so GSPMD picks the all-to-all, not an all-gather);
  - top-k gating (k=1 Switch, k=2 GShard) with the standard
    load-balancing auxiliary loss (fraction-dispatched x mean-gate x E).

Capacity: each expert processes at most C = ceil(k * T / E) x
capacity_factor tokens per batch; overflow tokens fall through the
residual connection (their combine weights are zero), the Switch
"token dropping" behavior.

:func:`moe_dropless` is the layer without a capacity (the serve path's:
models/latent_moe.py): every token gets every expert it chose, whatever
else is in the batch. Its two halves are :func:`route_sigmoid_top_k` and
:func:`grouped_experts`; the second takes experts in either of two forms
(SwiGLU on three matrices, or a squared ReLU on two), reading whatever
vector its caller hands it (the hidden one, or a latent one), and **a share
of a layer's experts**: told the first id it holds, it computes the part of
the result that the held experts give and nothing in place of the rest (the
guide's share cut: a router as wide as published, one chip's experts; the
exchange between chips is not here: ``parallel/sharding.py`` has none). On a
TPU the two-matrix form runs in a Pallas kernel, ``moe_expert_tiles_<tiles>``
(tiles of 128 rows, one expert each, the expert's matrices whole in VMEM; a
decode step's rows are every held expert's tile as they lie), the SwiGLU form
in a decode step, ``moe_swiglu_tiles_<E>``, and SwiGLU experts too wide for
VMEM in blocks of F at any step, ``moe_swiglu_blocks_<grid>``; else ragged_dot.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .flash_attention import _on_tpu


def init_moe_params(key, n_layers: int, d_model: int, d_ff: int,
                    n_experts: int, param_dtype=jnp.float32):
    """Layer-stacked MoE FFN params: router + per-expert SwiGLU weights
    ([L, E, ...]); drop-in replacement for the dense w1/w3/w2 stack."""
    keys = jax.random.split(key, 4)
    L, D, F, E = n_layers, d_model, d_ff, n_experts

    def dense(k, shape, fan_in):
        return jax.random.normal(k, shape, param_dtype) * (fan_in ** -0.5)

    return {
        "router": dense(keys[0], (L, D, E), D),
        "w1": dense(keys[1], (L, E, D, F), D),
        "w3": dense(keys[2], (L, E, D, F), D),
        "w2": dense(keys[3], (L, E, F, D), F),
    }


def capacity(group_size: int, n_experts: int, top_k: int,
             capacity_factor: float) -> int:
    return max(1, math.ceil(group_size * top_k / n_experts
                            * capacity_factor))


def _group_size(n_tokens: int, target: int) -> int:
    """Largest divisor of ``n_tokens`` that is <= target (GShard's group
    dimension: capacity scales with tokens-per-group, NOT total tokens, so
    the dispatch/combine tensors stay O(T * E * C_group) instead of the
    O(T^2)-ish blowup of one global group)."""
    g = min(n_tokens, max(1, target))
    while n_tokens % g != 0:
        g -= 1
    return g


def moe_ffn(x, layer, cfg, mesh: Optional[Mesh] = None):
    """MoE feed-forward: x [B, S, D] -> ([B, S, D], aux_loss scalar).

    ``layer`` holds this layer's slices: router [D, E], w1/w3 [E, D, F],
    w2 [E, F, D]. Gating/softmax run in fp32; expert matmuls in cfg.dtype
    (bf16 on the MXU). Tokens dispatch in groups of ~expert_group_size
    with per-group capacity (the GShard group dimension).
    """
    B, S, D = x.shape
    E = layer["router"].shape[-1]
    k = cfg.expert_top_k
    T = B * S
    g = _group_size(T, cfg.expert_group_size)
    G = T // g
    C = capacity(g, E, k, cfg.expert_capacity_factor)

    xg = x.reshape(G, g, D)
    logits = jnp.einsum("gtd,de->gte", xg.astype(jnp.float32),
                        layer["router"].astype(jnp.float32))  # [G, g, E]
    probs = jax.nn.softmax(logits, axis=-1)

    # top-k dispatch with per-expert positions (GShard's cumsum trick);
    # experts fill in routing-priority order, one chosen expert at a time
    combine = jnp.zeros((G, g, E, C), jnp.float32)
    dispatch_total = jnp.zeros((G, g, E), jnp.float32)
    fill = jnp.zeros((G, E), jnp.float32)   # per-group expert fill level
    remaining = probs
    for _ in range(k):
        idx = jnp.argmax(remaining, axis=-1)                # [G, g]
        onehot = jax.nn.one_hot(idx, E, dtype=jnp.float32)  # [G, g, E]
        gate = jnp.sum(probs * onehot, axis=-1)             # [G, g]
        pos = (jnp.cumsum(onehot, axis=1) - 1.0) + fill[:, None, :]
        pos = jnp.sum(pos * onehot, axis=-1)                # [G, g]
        keep = (pos < C).astype(jnp.float32) * jnp.sum(onehot, -1)
        pos_oh = jax.nn.one_hot(pos.astype(jnp.int32), C,
                                dtype=jnp.float32)          # [G, g, C]
        combine = combine + (gate * keep)[..., None, None] \
            * onehot[..., None] * pos_oh[..., None, :]
        dispatch_total = dispatch_total + onehot * keep[..., None]
        fill = fill + jnp.sum(onehot * keep[..., None], axis=1)
        remaining = remaining * (1.0 - onehot)              # mask chosen

    # normalize top-k gates so kept weights sum to 1 per token
    denom = jnp.sum(combine, axis=(2, 3), keepdims=True)
    combine = combine / jnp.maximum(denom, 1e-9)
    dispatch = (combine > 0.0).astype(cfg.dtype)            # [G, g, E, C]

    # per-expert buffers; pinned to the ep axis so GSPMD lowers the
    # dispatch/combine einsums to all-to-alls over ICI
    expert_in = jnp.einsum("gtec,gtd->egcd", dispatch,
                           xg.astype(cfg.dtype))            # [E, G, C, D]
    if mesh is not None and "ep" in mesh.shape:
        expert_in = jax.lax.with_sharding_constraint(
            expert_in, NamedSharding(mesh, P("ep", None, None, None)))
    gate_h = jax.nn.silu(jnp.einsum(
        "egcd,edf->egcf", expert_in, layer["w1"].astype(cfg.dtype)))
    up = jnp.einsum("egcd,edf->egcf", expert_in,
                    layer["w3"].astype(cfg.dtype))
    expert_out = jnp.einsum("egcf,efd->egcd", gate_h * up,
                            layer["w2"].astype(cfg.dtype))  # [E, G, C, D]
    if mesh is not None and "ep" in mesh.shape:
        expert_out = jax.lax.with_sharding_constraint(
            expert_out, NamedSharding(mesh, P("ep", None, None, None)))
    out = jnp.einsum("gtec,egcd->gtd", combine.astype(cfg.dtype),
                     expert_out)

    # load-balancing aux loss (Switch eq. 4): E * sum_e f_e * p_e, where
    # f_e = fraction of tokens dispatched to e, p_e = mean router prob
    f = jnp.mean(dispatch_total, axis=(0, 1))
    p = jnp.mean(probs, axis=(0, 1))
    aux = E * jnp.sum(f * p)

    return out.reshape(B, S, D), aux


# ------------------------------------------------------------ without drops
def route_sigmoid_top_k(x, router, bias, top_k: int, scale: float,
                        normalize: bool = True):
    """The router of the DeepSeek-V3 family (``noaux_tc`` with one group):
    scores ``s = sigmoid(x . router)`` in float32; a token takes the
    ``top_k`` experts largest in ``s + bias``; the bias chooses and does
    not weigh: the weights are ``s`` at the chosen experts, over their sum
    where ``normalize``, times ``scale``. x [T, D] -> (experts int32
    [T, k], weights float32 [T, k])."""
    with jax.named_scope("moe_route"):
        s = jax.nn.sigmoid(jnp.dot(
            x.astype(jnp.float32), router.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST))
        _, chosen = jax.lax.top_k(s + bias.astype(jnp.float32), top_k)
        w = jnp.take_along_axis(s, chosen, axis=-1)
        if normalize:
            w = w / jnp.sum(w, axis=-1, keepdims=True)
        return chosen.astype(jnp.int32), w * scale


def moe_dropless(x, layer, top_k: int, scale: float, normalize: bool = True,
                 live=None):
    """Routed experts without a capacity: x [T, D] -> (y [T, D],
    expert_tokens int32 [E]): :func:`route_sigmoid_top_k` on ``x``, then
    :func:`grouped_experts` on ``x`` with what it chose. ``layer``: router
    [D, E], bias [E] and the experts' matrices. The layer of a model whose
    experts read the vector the router reads and are all held
    (models/latent_moe.py); one whose experts read another vector, or that
    holds a share of them, calls the two itself (models/nemotron_h.py)."""
    chosen, w = route_sigmoid_top_k(x, layer["router"], layer["bias"],
                                    top_k, scale, normalize)
    return grouped_experts(x, chosen, w, layer, live)


def grouped_experts(x, chosen, w, layer, live=None, first: int = 0,
                    use_pallas: Optional[str] = None):
    """The experts a router chose, without a capacity: x [T, D] (what the
    experts read: the hidden vector, or a latent one), ``chosen`` int32
    [T, k] (ids among all the experts the router knows) and ``w`` float32
    [T, k] -> (y [T, D'], expert_tokens int32 [E]).

    ``layer`` holds the matrices of the ``E`` experts that live here, ids
    ``first`` .. ``first + E - 1``, in one of two forms: SwiGLU (w1 / w3
    [E, D, F], w2 [E, F, D']: ``w2(silu(w1 x) * w3 x)``) or, without a
    ``w3``, two matrices and a squared ReLU (``w2(relu(w1 x) ** 2)``).

    One algorithm for any T: the T x k (token, expert) assignments are
    sorted by expert, the matmuls run grouped over the sorted rows, and each
    token's k results are weighed and summed where the token lies. No row is
    dropped or reweighed for room, so a token's result does not depend on
    what else is in the batch. A row of ``live`` (bool [T]) that is False is
    routed to no expert: it sorts past every group, reads 0 and is counted
    nowhere. Two forms of the grouped matmuls:

      - ``jax.lax.ragged_dot`` over the sorted rows as they lie (on a TPU
        the compiler's own grouped-matmul kernel: an expert no token chose
        is not read and rows past the last group cost nothing, **but they
        come back as junk, not as zeros**, and every expert's rows are
        walked in tiles of 512: at 5 to 90 rows an expert it runs at a
        third of the bandwidth and a twentieth of the peak);
      - on a TPU, the Pallas kernel ``name="moe_expert_tiles_<tiles>"``
        (SwiGLU: ``moe_swiglu_tiles_<E>``, a step of a tile at most, else
        the grouped matmul; too wide: :func:`_blocked_swiglu`): sorted rows
        are laid out from a tile boundary of their own (``EXPERT_TILE``
        rows: one pass of the MXU), so a tile has one expert; the grid walks
        the tiles that have rows, in expert order; a tile's matmuls and the
        activation between them run in one visit with the expert's matrices
        in VMEM (fetched once an expert, whole and contiguous, double
        buffered against the tile before); an expert no token chose has no
        tile and is never fetched. **A step of no more rows than a tile (a
        decode step) needs no sort**: every held expert's tile is the step's
        own rows, the grid walks the held experts, a column of the [T, E]
        matrix of weights says which rows count for the expert (0: not its),
        and the results add up in one block that stays in VMEM. By platform
        and shapes (:func:`expert_kernel_takes`, :func:`expert_blocks_take`),
        never a flag; ``use_pallas`` "on", "interpret", "off", or None: that.

    **A share of the experts** (``E`` less than the router's width, or
    ``first`` > 0: one chip's part of a layer that several chips share): an
    assignment to an expert that is not held is computed nowhere and adds
    nothing. It sorts past every group like an idle row's, its weight stays
    out of the sum, and the weights of the assignments that are held are
    what the router gave them (normalised over all k, held or not). ``y`` is
    then this chip's part of the layer's result, and the parts of all the
    shares add up to the whole. ``expert_tokens[e]`` is how many live rows
    chose held expert ``first + e``."""
    T = x.shape[0]
    E, top_k = layer["w1"].shape[0], chosen.shape[-1]
    if use_pallas is None:
        use_pallas = "on" if _on_tpu() and (expert_kernel_takes(
            x, layer) or expert_blocks_take(x, layer)) else "off"
    with jax.named_scope("moe_experts"):
        held = None
        if first or E != layer["router"].shape[-1]:
            chosen = chosen - first
            held = (chosen >= 0) & (chosen < E)
        if live is not None:
            held = live[:, None] if held is None else held & live[:, None]
        if held is not None:
            chosen = jnp.where(held, chosen, E)           # past every group
            w = jnp.where(held, w, 0.0)
        if use_pallas != "off" and (call := _one_call(x, layer)):
            return call(
                x, chosen, w, layer, interpret=(use_pallas == "interpret"))
        flat = chosen.reshape(-1)                         # [T * k]
        order = jnp.argsort(flat, stable=True)
        sizes = jnp.bincount(flat, length=E).astype(jnp.int32)
        back = jnp.argsort(order)                         # where each lies
        if use_pallas != "off" and "w3" not in layer:
            ys = _tiled_experts(x, flat, order, back, sizes, layer, top_k,
                                interpret=(use_pallas == "interpret"))
            # a row that lies nowhere reads whatever its index points at
            ys = jnp.where((flat < E)[:, None], ys, 0.0)
            y = jnp.sum(ys.reshape(T, top_k, -1) * w[..., None], axis=1)
            return y.astype(x.dtype), sizes
        xs = x[order // top_k]                            # rows by expert

        def grouped(a, b):
            return jax.lax.ragged_dot(a, b.astype(a.dtype), sizes,
                                      preferred_element_type=jnp.float32)

        if "w3" in layer:
            h = jax.nn.silu(grouped(xs, layer["w1"])) \
                * grouped(xs, layer["w3"])
        else:
            h = jnp.square(jax.nn.relu(grouped(xs, layer["w1"])))
        ys = grouped(h.astype(x.dtype), layer["w2"])      # [T * k, D'] f32
        y = jnp.sum(_held(ys[back], flat, layer, first, T) * w[..., None], 1)
    return y.astype(x.dtype), sizes


# rows of a tile of the expert kernel: one pass of the MXU's 128 rows
EXPERT_TILE = 128
# what the kernel may hold in VMEM: two experts' two matrices (one computed
# on, one arriving) and a tile's rows; a v5e core has 128 MiB
_EXPERT_VMEM = 96 << 20


def expert_tiles(rows: int, top_k: int, experts: int) -> int:
    """Tiles the expert kernel's grid has for ``rows`` tokens: a tile an
    expert where the rows are no more than a tile; else each assignment's
    row, and for each expert the last tile's empty rest."""
    if rows <= EXPERT_TILE:
        return experts
    return -(-rows * top_k // EXPERT_TILE) + experts


def expert_kernel_takes(x, layer) -> bool:
    """Can the compiled expert kernel run this layer on a TPU? Either form
    of expert, widths in whole lanes, rows and matrices of one type, and two
    experts' matrices (one computed on, one arriving) within the kernel's
    VMEM: wider SwiGLU experts keep the compiler's grouped matmul."""
    mats = [layer[k] for k in ("w1", "w3", "w2") if k in layer]
    return (all(m.dtype == x.dtype for m in mats)
            and x.dtype in (jnp.bfloat16, jnp.float32)
            and all(n % 128 == 0 for m in mats for n in m.shape[1:])
            and 2 * sum(m.size for m in mats) // mats[0].shape[0]
            * jnp.dtype(x.dtype).itemsize <= _EXPERT_VMEM * 3 // 4)


def _expert_rows(x_ref, w1_ref, w2_ref, weigh=None):
    """A tile's rows through one expert: ``w2(relu(w1 x) ** 2)`` in float32,
    each row's middle times ``weigh`` [rows, 1] where given."""
    h = jnp.square(jnp.maximum(jnp.dot(
        x_ref[...], w1_ref[...], preferred_element_type=jnp.float32), 0.0))
    if weigh is not None:
        h = h * weigh
    return jnp.dot(h.astype(x_ref.dtype), w2_ref[...],
                   preferred_element_type=jnp.float32)


def _tiles_kernel(expert_ref, active_ref, x_ref, w1_ref, w2_ref, o_ref):
    import jax.experimental.pallas as pl

    @pl.when(pl.program_id(0) < active_ref[0])
    def _a_tile_with_rows():
        o_ref[...] = _expert_rows(x_ref, w1_ref, w2_ref)


def _one_tile_kernel(fetch_ref, count_ref, x_ref, gate_ref, w1_ref, w2_ref,
                     o_ref):
    import jax.experimental.pallas as pl

    e = pl.program_id(0)

    @pl.when(e == 0)
    def _first_expert():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(count_ref[e] > 0)
    def _an_expert_with_rows():
        at = jax.lax.broadcasted_iota(jnp.int32, gate_ref.shape, 1)
        mine = jnp.sum(jnp.where(at == e, gate_ref[...], 0.0), axis=1,
                       keepdims=True)                          # [T, 1]
        o_ref[...] += _expert_rows(x_ref, w1_ref, w2_ref, mine)


def _one_tile_experts(x, chosen, w, layer, interpret):
    """The rows of a step that is no longer than a tile, through every held
    expert that a row chose: (y [T, D'] in ``x``'s type, expert_tokens)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    T, D = x.shape
    w1, w2 = layer["w1"], layer["w2"]
    E, F, Dout = w1.shape[0], w1.shape[2], w2.shape[2]
    hit = chosen[..., None] == jnp.arange(E)                   # [T, k, E]
    gate = jnp.sum(jnp.where(hit, w[..., None], 0.0), axis=1)  # [T, E]
    sizes = jnp.sum(hit, axis=(0, 1), dtype=jnp.int32)
    # an expert without rows is not fetched: the grid stays at the one
    # before it (experts before the first with rows: at expert 0)
    fetch = jax.lax.cummax(jnp.where(sizes > 0, jnp.arange(E), 0)).astype(
        jnp.int32)
    y = pl.pallas_call(
        _one_tile_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(E,),
            in_specs=[
                pl.BlockSpec((T, D), lambda e, fetch, n: (0, 0)),
                pl.BlockSpec((T, E), lambda e, fetch, n: (0, 0)),
                pl.BlockSpec((None, D, F),
                             lambda e, fetch, n: (fetch[e], 0, 0)),
                pl.BlockSpec((None, F, Dout),
                             lambda e, fetch, n: (fetch[e], 0, 0))],
            out_specs=pl.BlockSpec((T, Dout), lambda e, fetch, n: (0, 0))),
        out_shape=jax.ShapeDtypeStruct((T, Dout), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_EXPERT_VMEM),
        interpret=interpret,
        name=f"moe_expert_tiles_{E}",
    )(fetch, sizes, x, gate, w1, w2)
    return y.astype(x.dtype), sizes


def _tiled_experts(x, flat, order, back, sizes, layer, top_k, interpret):
    """Each assignment's result [T * k, D'] float32, in the assignments'
    own order (an assignment that lies nowhere reads another's)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    T, D = x.shape
    w1, w2 = layer["w1"], layer["w2"]
    E, F, Dout = w1.shape[0], w1.shape[2], w2.shape[2]
    tile, n_tiles = EXPERT_TILE, expert_tiles(T, top_k, E)
    # an expert's sorted rows start at ``start`` and, laid out from a tile
    # boundary of their own, at ``tile * first_tile``
    tiles_of = -(-sizes // tile)
    tile_end = jnp.cumsum(tiles_of)
    first_tile, start = tile_end - tiles_of, jnp.cumsum(sizes) - sizes
    active = tile_end[-1:].astype(jnp.int32)
    tile_expert = jnp.minimum(jnp.searchsorted(
        tile_end, jnp.arange(n_tiles), side="right"), E - 1).astype(jnp.int32)
    # the row each place of the layout holds (a tile's empty rest holds
    # rows of the experts behind: computed, read by nobody). A tile's first
    # sorted row is worked out a tile (a lookup a layout row costs the TPU
    # more than the tile's matmuls), the rows behind it follow on
    tile_start = start[tile_expert] + tile * (
        jnp.arange(n_tiles) - first_tile[tile_expert])
    sorted_at = jnp.clip(tile_start[:, None] + jnp.arange(tile), 0,
                         flat.shape[0] - 1).reshape(-1)
    rows = x[order[sorted_at] // top_k]                   # [tiles * tile, D]

    def last(i, active):  # a tile without rows: stay where we are
        return jnp.minimum(i, jnp.maximum(active[0], 1) - 1)

    ys = pl.pallas_call(
        _tiles_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n_tiles,),
            in_specs=[
                pl.BlockSpec((tile, D), lambda i, e, a: (last(i, a), 0)),
                pl.BlockSpec((None, D, F),
                             lambda i, e, a: (e[last(i, a)], 0, 0)),
                pl.BlockSpec((None, F, Dout),
                             lambda i, e, a: (e[last(i, a)], 0, 0))],
            out_specs=pl.BlockSpec((tile, Dout),
                                   lambda i, e, a: (last(i, a), 0))),
        out_shape=jax.ShapeDtypeStruct((n_tiles * tile, Dout), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_EXPERT_VMEM),
        interpret=interpret,
        name=f"moe_expert_tiles_{n_tiles}",
    )(tile_expert, active, rows, w1, w2)
    # where each assignment's row lies in the layout: as far behind its
    # expert's first tile as it is behind the expert's first sorted row
    return ys[(tile * first_tile - start)[jnp.minimum(flat, E - 1)] + back]


def _held(ys, flat, layer, first, T):
    """The grouped matmul's rows [T * k, D'] as [T, k, D']. With a share of
    the experts, a row whose expert is not held (``flat`` says ``E``) lay
    past every group and came back as junk, and most of a live token's rows
    are such: they read 0 here, since junk times a weight of 0 need not be 0.
    With every expert held the rows are handed on as they are (the program of
    a model that holds them all does not change). At the end of the file so
    that no kernel above moves."""
    E = layer["w1"].shape[0]
    if first or E != layer["router"].shape[-1]:
        ys = jnp.where((flat < E)[:, None], ys, 0.0)
    return ys.reshape(T, -1, ys.shape[-1])


# ------------------------------------------ the kernel's three-matrix form
# (below the two-matrix form's functions, whose lines the programs of the
# models that run it carry). A step of a tile at most alone: past a tile the
# SwiGLU experts keep the grouped matmul, whose programs set up sooner
def _swiglu_rows(x_ref, w1_ref, w3_ref, w2_ref, weigh):
    """A tile's rows through one SwiGLU expert: ``w2(silu(w1 x) * w3 x)``
    in float32, each row's middle times ``weigh`` [rows, 1]."""
    x = x_ref[...]
    h = jax.nn.silu(jnp.dot(x, w1_ref[...],
                            preferred_element_type=jnp.float32)) \
        * jnp.dot(x, w3_ref[...], preferred_element_type=jnp.float32)
    return jnp.dot((h * weigh).astype(x.dtype), w2_ref[...],
                   preferred_element_type=jnp.float32)


def _swiglu_one_tile_kernel(fetch_ref, count_ref, x_ref, gate_ref, w1_ref,
                            w3_ref, w2_ref, o_ref):
    import jax.experimental.pallas as pl

    e = pl.program_id(0)

    @pl.when(e == 0)
    def _first_expert():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(count_ref[e] > 0)
    def _an_expert_with_rows():
        at = jax.lax.broadcasted_iota(jnp.int32, gate_ref.shape, 1)
        mine = jnp.sum(jnp.where(at == e, gate_ref[...], 0.0), axis=1,
                       keepdims=True)                          # [T, 1]
        o_ref[...] += _swiglu_rows(x_ref, w1_ref, w3_ref, w2_ref, mine)


def _one_tile_swiglu(x, chosen, w, layer, interpret):
    """:func:`_one_tile_experts` for SwiGLU experts: the step's rows through
    every held expert that a row chose, the expert's three matrices fetched
    whole (an expert without rows: not at all). (y [T, D'], expert_tokens)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    T, D = x.shape
    w1, w3, w2 = layer["w1"], layer["w3"], layer["w2"]
    E, F, Dout = w1.shape[0], w1.shape[2], w2.shape[2]
    hit = chosen[..., None] == jnp.arange(E)                   # [T, k, E]
    gate = jnp.sum(jnp.where(hit, w[..., None], 0.0), axis=1)  # [T, E]
    sizes = jnp.sum(hit, axis=(0, 1), dtype=jnp.int32)
    fetch = jax.lax.cummax(jnp.where(sizes > 0, jnp.arange(E), 0)).astype(
        jnp.int32)
    expert = lambda e, fetch, n: (fetch[e], 0, 0)  # noqa: E731
    whole = lambda e, fetch, n: (0, 0)  # noqa: E731
    y = pl.pallas_call(
        _swiglu_one_tile_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(E,),
            in_specs=[pl.BlockSpec((T, D), whole),
                      pl.BlockSpec((T, E), whole),
                      pl.BlockSpec((None, D, F), expert),
                      pl.BlockSpec((None, D, F), expert),
                      pl.BlockSpec((None, F, Dout), expert)],
            out_specs=pl.BlockSpec((T, Dout), whole)),
        out_shape=jax.ShapeDtypeStruct((T, Dout), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_EXPERT_VMEM),
        interpret=interpret,
        name=f"moe_swiglu_tiles_{E}",
    )(fetch, sizes, x, gate, w1, w3, w2)
    return y.astype(x.dtype), sizes



# ------------------------------------------- the three-matrix form in blocks
# (below every kernel above, whose lines the programs of the models that run
# them carry). SwiGLU experts too wide for two of them in VMEM: an expert's
# matrices cross in blocks of the intermediate width F (columns of w1 and w3,
# rows of w2), and the down projection adds up over the blocks in float32
def _expert_block(x, layer) -> int:
    """Columns of F a block takes: the widest whole lanes that divide F and
    whose slices of the three matrices, two of each (one computed on, one
    arriving), take no more than three eighths of the kernel's VMEM (a
    pass's rows take the rest); 0 where none does."""
    D, F = layer["w1"].shape[1:]
    per = 2 * (2 * D + layer["w2"].shape[2]) * jnp.dtype(x.dtype).itemsize
    return max((b for b in range(EXPERT_TILE, F + 1, EXPERT_TILE)
                if F % b == 0 and per * b <= _EXPERT_VMEM * 3 // 8),
               default=0)


def expert_blocks_take(x, layer) -> bool:
    """SwiGLU experts that :func:`expert_kernel_takes` refuses by VMEM alone
    (widths in whole lanes, rows and matrices of one type) and whose
    intermediate width splits into blocks that fit: the blocked kernel's."""
    mats = [layer[k] for k in ("w1", "w3", "w2") if k in layer]
    return ("w3" in layer and not expert_kernel_takes(x, layer)
            and all(m.dtype == x.dtype for m in mats)
            and x.dtype in (jnp.bfloat16, jnp.float32)
            and all(n % 128 == 0 for m in mats for n in m.shape[1:])
            and _expert_block(x, layer) > 0)


def _one_call(x, layer):
    """The kernel that :func:`grouped_experts` hands a whole step to, or
    None: the blocked form for SwiGLU experts too wide for VMEM, at any T;
    else the one-tile form of the whole-matrix kernel, up to a tile of rows
    (a longer step is sorted there)."""
    if expert_blocks_take(x, layer):
        return _blocked_swiglu
    if x.shape[0] <= EXPERT_TILE:
        return _one_tile_swiglu if "w3" in layer else _one_tile_experts
    return None


def _slab_rows(width: int) -> int:
    """Rows of 128 lanes that a token's vector of ``width`` takes in a slab
    (:func:`_blocked_swiglu`): whole tiles of 8, so that a token's rows are
    one DMA from an aligned row."""
    return -(-width // 1024) * 8


def _pass_rows(x, layer) -> int:
    """Rows of a pass of the blocked kernel, in whole tiles: its rows as they
    arrive (float32) and for the MXU, the sums it adds up and those it reads
    back, within three eighths of the VMEM; no more than the step's rows."""
    D, Dout = x.shape[1], layer["w2"].shape[2]
    per = 128 * 4 * (_slab_rows(D) + _slab_rows(Dout)) \
        + D * jnp.dtype(x.dtype).itemsize + Dout * 4
    most = _EXPERT_VMEM * 3 // 8 // per // EXPERT_TILE * EXPERT_TILE
    return max(EXPERT_TILE,
               min(most, -(-x.shape[0] // EXPERT_TILE) * EXPERT_TILE))


def _as_column(row):
    """A sub-tile's weights [1, EXPERT_TILE] as a column [EXPERT_TILE, 1]."""
    tile = EXPERT_TILE
    at = jax.lax.broadcasted_iota(jnp.int32, (tile, tile), 1)
    mine = jax.lax.broadcasted_iota(jnp.int32, (tile, tile), 0)
    return jnp.sum(jnp.where(at == mine, row, 0.0), axis=1, keepdims=True)


def _swiglu_blocks_kernel(fe_ref, fb_ref, count_ref, x_ref, gate_ref, w1_ref,
                          w3_ref, w2_ref, o_ref):
    import jax.experimental.pallas as pl

    e, j = pl.program_id(0), pl.program_id(1)

    @pl.when((e == 0) & (j == 0))
    def _first_block():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(count_ref[e] > 0)
    def _an_expert_with_rows():
        at = jax.lax.broadcasted_iota(jnp.int32, gate_ref.shape, 1)
        mine = jnp.sum(jnp.where(at == e, gate_ref[...], 0.0), axis=1,
                       keepdims=True)                          # [T, 1]
        x = x_ref[...]

        def columns(c, carry):  # 128 of the block's columns: less code
            cols = pl.ds(pl.multiple_of(c * 128, 128), 128)
            h = jax.nn.silu(jnp.dot(x, w1_ref[:, cols],
                                    preferred_element_type=jnp.float32)) \
                * jnp.dot(x, w3_ref[:, cols],
                          preferred_element_type=jnp.float32)
            o_ref[...] += jnp.dot((h * mine).astype(x.dtype),
                                  w2_ref[cols, :],
                                  preferred_element_type=jnp.float32)
            return carry

        jax.lax.fori_loop(0, w1_ref.shape[1] // 128, columns, 0)


def _swiglu_pass_kernel(we_ref, wb_ref, count_ref, tok_ref, gate_ref, x_hbm,
                        w1_ref, w3_ref, w2_ref, _, o_hbm, slab, rows, acc,
                        sums, sem):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    p, j = pl.program_id(0), pl.program_id(1)
    n, tile, sub = count_ref[p], rows.shape[0], EXPERT_TILE
    rx, ro = slab.shape[0] // tile, sums.shape[0] // tile

    def each(copy, act):  # the pass's ``n`` rows, one DMA a token
        def one(r, c):
            act(copy(r, tok_ref[p * tile + r]))
            return c
        jax.lax.fori_loop(0, n, one, 0)

    def rows_of(ref, i, per):
        return ref.at[pl.ds(pl.multiple_of(i * per, 8), per)]

    def x_in(r, t):
        return pltpu.make_async_copy(rows_of(x_hbm, t, rx),
                                     rows_of(slab, r, rx), sem.at[0])

    def o_in(r, t):
        return pltpu.make_async_copy(rows_of(o_hbm, t, ro),
                                     rows_of(sums, r, ro), sem.at[1])

    def o_out(r, t):
        return pltpu.make_async_copy(rows_of(sums, r, ro),
                                     rows_of(o_hbm, t, ro), sem.at[2])

    def sub_tiles(body):  # those that hold rows, in one copy of the code
        def one(s, carry):
            body(s, pl.ds(pl.multiple_of(s * sub, sub), sub))
            return carry
        jax.lax.fori_loop(0, (n + sub - 1) // sub, one, 0)

    def lanes(width, body):  # a sub-tile's columns, 128 at a time
        def one(c, carry):
            body(c, pl.ds(pl.multiple_of(c * 128, 128), 128))
            return carry
        jax.lax.fori_loop(0, width // 128, one, 0)

    def unslab(s, at):  # a sub-tile's rows from the slab, as the MXU takes
        def chunk(c, cols):
            rows[at, cols] = slab[pl.ds(s * sub * rx + c, sub, stride=rx),
                                  :].astype(rows.dtype)
        lanes(rows.shape[1], chunk)

    @pl.when((n > 0) & (j == 0))
    def _fetch_the_rows():
        each(x_in, lambda c: c.start())
        each(o_in, lambda c: c.start())
        each(x_in, lambda c: c.wait())
        sub_tiles(unslab)

    def through(s, at):
        part = _swiglu_rows(rows.at[at], w1_ref, w3_ref, w2_ref,
                            _as_column(gate_ref[pl.ds(s, 1), :]))
        # the first block's sum is the sub-tile's first: what ``acc`` held
        # before is another pass's, or nothing
        acc[at] = jnp.where(j > 0, acc[at], 0.0) + part

    sub_tiles(through)

    def add_back(s, at):
        def chunk(c, cols):
            where = pl.ds(s * sub * ro + c, sub, stride=ro)
            sums[where, :] = sums[where, :] + acc[at, cols]
        lanes(acc.shape[1], chunk)

    @pl.when((n > 0) & (j == pl.num_programs(1) - 1))
    def _add_the_rows_back():
        each(o_in, lambda c: c.wait())
        sub_tiles(add_back)
        each(o_out, lambda c: c.start())
        each(o_out, lambda c: c.wait())


def _blocked_swiglu(x, chosen, w, layer, interpret):
    """SwiGLU experts too wide for VMEM, their matrices in blocks of F
    (:func:`_expert_block`): (y [T, D'] in ``x``'s type, expert_tokens).
    Each held expert that a row chose crosses HBM once, block by block; one
    that no row chose is never fetched (a grid step without rows stays on the
    block before it, or takes the next expert's first).

      - **At most a tile of rows** (a decode step): the grid of
        :func:`_one_tile_swiglu`, each held expert's blocks in turn, and the
        step's rows add up in one block that stays in VMEM;
      - **more** (a step with a chunk of a prompt): the held assignments
        alone are sorted by expert and laid out in passes of
        :func:`_pass_rows` rows, one expert a pass (an expert past a pass's
        rows takes more, and its matrices cross again for each). A pass
        fetches its tokens' rows itself, a DMA a token from a float32 slab
        of ``x`` (a token's vector in whole tiles of rows of 128 lanes:
        :func:`_slab_rows`), skips the sub-tiles of 128 rows that hold none,
        and adds its weighted sums to its tokens' float32 rows of the result
        in HBM, which it reads and writes back the same way. Assignments to
        experts held elsewhere, and idle rows, cost nothing, and no
        [T * k, D'] array is made.

    Named ``moe_swiglu_blocks_<grid>`` for the grid's first axis (the held
    experts; the passes), so a decode step's calls and a longer step's
    differ."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    T, D = x.shape
    w1, w3, w2 = layer["w1"], layer["w3"], layer["w2"]
    E, F, Dout = w1.shape[0], w1.shape[2], w2.shape[2]
    top_k = chosen.shape[-1]
    bf = _expert_block(x, layer)
    nF = F // bf
    params = pltpu.CompilerParams(
        dimension_semantics=("arbitrary", "arbitrary"),
        vmem_limit_bytes=_EXPERT_VMEM)
    if T <= EXPERT_TILE:
        hit = chosen[..., None] == jnp.arange(E)               # [T, k, E]
        gate = jnp.sum(jnp.where(hit, w[..., None], 0.0), axis=1)
        sizes = jnp.sum(hit, axis=(0, 1), dtype=jnp.int32)
        has = sizes > 0
        # an expert without rows stays on the last block of the one before
        # with rows; before the first with rows, on the first's first block
        ids = jnp.arange(E)
        before = jax.lax.cummax(jnp.where(has, ids, -1))
        after = jax.lax.cummin(jnp.where(has, ids, E - 1), reverse=True)
        fe = jnp.where(has, ids, jnp.where(before >= 0, before, after))
        fb = jnp.where(has[:, None], jnp.arange(nF),
                       jnp.where(before[:, None] >= 0, nF - 1, 0))

        def block(e, j, fe, fb, n):  # w1 / w3 (the F axis last), w2
            return fe[e], fb[e * nF + j]

        whole = lambda e, j, fe, fb, n: (0, 0)  # noqa: E731
        y = pl.pallas_call(
            _swiglu_blocks_kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3,
                grid=(E, nF),
                in_specs=[
                    pl.BlockSpec((T, D), whole),
                    pl.BlockSpec((T, E), whole),
                    pl.BlockSpec((None, D, bf), lambda *a: (
                        block(*a)[0], 0, block(*a)[1])),
                    pl.BlockSpec((None, D, bf), lambda *a: (
                        block(*a)[0], 0, block(*a)[1])),
                    pl.BlockSpec((None, bf, Dout), lambda *a: (
                        *block(*a), 0))],
                out_specs=pl.BlockSpec((T, Dout), whole)),
            out_shape=jax.ShapeDtypeStruct((T, Dout), jnp.float32),
            compiler_params=params,
            interpret=interpret,
            name=f"moe_swiglu_blocks_{E}",
        )(fe.astype(jnp.int32), fb.reshape(-1).astype(jnp.int32), sizes, x,
          gate, w1, w3, w2)
        return y.astype(x.dtype), sizes
    tile = _pass_rows(x, layer)
    n_sub, passes = tile // EXPERT_TILE, -(-T * top_k // tile) + E
    rx, ro = _slab_rows(D), _slab_rows(Dout)
    flat = chosen.reshape(-1)                             # [T * k]
    order = jnp.argsort(flat, stable=True)
    sizes = jnp.bincount(flat, length=E).astype(jnp.int32)
    # an expert's sorted rows start at ``start`` and fill passes from
    # ``first_pass`` on; a pass past the last with rows has none
    passes_of = -(-sizes // tile)
    pass_end = jnp.cumsum(passes_of)
    first_pass, start = pass_end - passes_of, jnp.cumsum(sizes) - sizes
    active = pass_end[-1]
    at = jnp.arange(passes)
    expert = jnp.minimum(jnp.searchsorted(pass_end, at, side="right"), E - 1)
    done = tile * (at - first_pass[expert])
    count = jnp.where(at < active, jnp.clip(sizes[expert] - done, 0, tile), 0)
    slot = jnp.arange(tile)
    which = order[jnp.clip((start[expert] + done)[:, None] + slot, 0,
                           flat.shape[0] - 1)]            # [passes, tile]
    inside = slot < count[:, None]
    tok = jnp.where(inside, which // top_k, 0)
    gate = jnp.where(inside, w.reshape(-1)[which], 0.0)
    # a pass without rows stays on the last block of the last with rows
    last = jnp.maximum(active - 1, 0)
    we = jnp.where(at < active, expert, expert[last])
    wb = jnp.where((at < active)[:, None], jnp.arange(nF), nF - 1)

    def block(p, j, we, wb, n, t):  # w1 / w3 (the F axis last), w2
        return we[p], wb[p * nF + j]

    anywhere = pl.BlockSpec(memory_space=pl.ANY)
    slab_x = jnp.pad(x.astype(jnp.float32), ((0, 0), (0, rx * 128 - D)))
    y = pl.pallas_call(
        _swiglu_pass_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(passes, nF),
            in_specs=[
                pl.BlockSpec((None, n_sub, EXPERT_TILE),
                             lambda p, j, *_: (p, 0, 0)),
                anywhere,
                pl.BlockSpec((None, D, bf), lambda *a: (
                    block(*a)[0], 0, block(*a)[1])),
                pl.BlockSpec((None, D, bf), lambda *a: (
                    block(*a)[0], 0, block(*a)[1])),
                pl.BlockSpec((None, bf, Dout), lambda *a: (*block(*a), 0)),
                anywhere],
            out_specs=anywhere,
            scratch_shapes=[pltpu.VMEM((tile * rx, 128), jnp.float32),
                            pltpu.VMEM((tile, D), x.dtype),
                            pltpu.VMEM((tile, Dout), jnp.float32),
                            pltpu.VMEM((tile * ro, 128), jnp.float32),
                            pltpu.SemaphoreType.DMA((3,))]),
        out_shape=jax.ShapeDtypeStruct((T * ro, 128), jnp.float32),
        input_output_aliases={9: 0},
        compiler_params=params,
        interpret=interpret,
        name=f"moe_swiglu_blocks_{passes}",
    )(we.astype(jnp.int32), wb.reshape(-1).astype(jnp.int32),
      count.astype(jnp.int32), tok.reshape(-1).astype(jnp.int32),
      gate.reshape(passes, n_sub, EXPERT_TILE).astype(jnp.float32),
      slab_x.reshape(T * rx, 128), w1, w3, w2,
      jnp.zeros((T * ro, 128), jnp.float32))
    y = y.reshape(T, ro * 128)[:, :Dout]
    return y.astype(x.dtype), sizes
