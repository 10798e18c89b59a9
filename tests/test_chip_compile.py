"""The main path's kernels compile for the chip, checked without the chip.

The TPU compiler is installed here and compiles for a chip that is described
and not attached (``jax.experimental.topologies``): a slice the tiling cannot
take, too much fast memory or a kernel the compiler cannot partition are
refused here as they would be on the chip, at no chip time. A compile that
passes is not a run and says nothing about results or times.

The topology is described inside a module-scoped fixture and nowhere else:
only one process may load the TPU library, so nothing here may touch it while
a module is imported (every xdist worker imports every test file), and every
compile runs in this process.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from ray_memory_management_tpu.ops.flash_attention import flash_attention


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # what a described-chip compile writes to the persistent cache cannot
    # be read back without the chip
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _forward(q, k, v):
    return flash_attention(q, k, v, use_pallas="on")


def _backward(q, k, v):
    def loss(q, k, v):
        return jnp.sum(_forward(q, k, v).astype(jnp.float32) ** 2)

    return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)


# (BH, S, D): gpt2-small at B=16 S=1024; 128 heads-by-batch at S=2048;
# long context at head_dim 128; a length whose only tile is an odd
# multiple of 8 rows (1032 -> 344); a short one the kernel spans whole;
# pretrain-1chip's step (B=2 x 16 heads, S=4096)
SHAPES = [(192, 1024, 64), (128, 2048, 64), (4, 8192, 128), (4, 1032, 64),
          (4, 40, 64), (32, 4096, 128)]
# forward only, the serve cells' prompts: longprompt-batch's longest bucket
# (mistral-7b-d16, 32 heads) and reasoning-batch's (glm-4.7-flash-d7, 20
# heads of 256)
PROMPTS = [(32, 3584, 128), (20, 4096, 256)]
CASES = [(s, fn) for s in SHAPES for fn in ("fwd", "bwd")] \
    + [(s, "fwd") for s in PROMPTS]


@pytest.mark.parametrize(
    "shape,fn", CASES, ids=lambda v: v if isinstance(v, str)
    else "x".join(map(str, v)))
def test_flash_kernels_compile_for_v5e(one_chip, shape, fn):
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    fn, n_kernels = {"fwd": (_forward, 1), "bwd": (_backward, 3)}[fn]
    compiled = jax.jit(fn).lower(x, x, x).compile()
    # the kernels themselves, not the jnp reference: fwd is one Mosaic
    # call; bwd is the fwd recompute plus the dq and dk/dv kernels
    assert compiled.as_text().count("tpu_custom_call") == n_kernels


def test_the_roofline_reader_still_tells_the_three_kernels_apart(one_chip):
    """``train.flash_roofline`` finds the kernels in a trace by their
    shapes (``chipbench.readers.flash_roofline.kind_of``): first operand
    [BH, S, D]; the forward returns that and a [BH, S, 1] column, dk/dv two
    such tensors, dq one. The compiled backward at pretrain-1chip's shape
    holds one of each by that reading, whatever else the calls take."""
    from chipbench.readers.flash_roofline import kind_of

    bh, seq, head_dim = 32, 4096, 128
    x = jax.ShapeDtypeStruct((bh, seq, head_dim), jnp.bfloat16,
                             sharding=one_chip)
    text = jax.jit(_backward).lower(x, x, x).compile().as_text()
    kinds = [kind_of(line, bh, seq, head_dim)
             for line in text.splitlines() if "tpu_custom_call" in line]
    assert sorted(kinds) == ["dkv", "dq", "fwd"]


@pytest.mark.parametrize("seq", [1000, 520])
def test_untileable_length_is_refused_by_name(one_chip, seq):
    """S=1000 tiles to 500 rows and S=520 to 260, neither a multiple of 8:
    the Pallas TPU lowering refuses such a block. The dispatch says so
    itself, naming the length, before the compiler is reached, and never
    gives way to the reference."""
    x = jax.ShapeDtypeStruct((4, seq, 64), jnp.bfloat16, sharding=one_chip)
    with pytest.raises(ValueError, match=f"length {seq}"):
        jax.jit(_forward).lower(x, x, x)


def test_kernel_in_a_tp_sharded_jit_needs_the_mesh(topo):
    """The compiler does not partition a Mosaic kernel: inside a jit
    sharded over dp x tp it is refused unless the call is wrapped in a
    shard_map, which ``flash_attention(mesh=...)`` does (batch over dp,
    heads over tp)."""
    mesh = Mesh([[topo.devices[0], topo.devices[1]],
                 [topo.devices[2], topo.devices[3]]], ("dp", "tp"))
    x = jax.ShapeDtypeStruct(
        (16, 12, 1024, 64), jnp.bfloat16,
        sharding=NamedSharding(mesh, P("dp", "tp", None, None)))
    with pytest.raises(NotImplementedError, match="shard_map"):
        jax.jit(_forward).lower(x, x, x)
    compiled = jax.jit(
        lambda q, k, v: flash_attention(q, k, v, use_pallas="on",
                                        mesh=mesh)).lower(x, x, x).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1


# ------------------------------------------------- the paged decode path
# (Hkv, heads a group, page, table width): Mistral-7B's GQA at chat-online's
# page, MHA at a small page, a wide group, and conversation-batch's (the
# hybrid state-space model's 20 query heads on 4 K/V heads, pages of 512),
# and longanswer-batch's (the layer-pattern model's 32 on 2, pages of 512)
PAGED = [(8, 4, 256, 16), (4, 1, 16, 8), (2, 16, 128, 4), (4, 5, 512, 8),
         (2, 16, 512, 8)]


@pytest.mark.parametrize("hkv,group,page,width", PAGED,
                         ids=lambda v: str(v))
def test_paged_attention_kernel_compiles_for_v5e(one_chip, hkv, group, page,
                                                 width):
    from ray_memory_management_tpu.ops.paged_attention import paged_attention

    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    B, L, P_, Dh = 16, 2, 9, 128
    pool = s((L, hkv, P_, page, Dh))
    compiled = jax.jit(
        lambda q, k, v, n, t, layer, kc, vc: paged_attention(
            q, k, v, n, t, layer=layer, k_cur=kc, v_cur=vc,
            use_pallas="on")).lower(
        s((B, hkv * group, Dh)), pool, pool, s((B,), jnp.int32),
        s((B, width), jnp.int32), s((), jnp.int32), s((B, hkv, Dh)),
        s((B, hkv, Dh))).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1


def test_state_update_kernel_compiles_for_v5e(one_chip):
    """``ssm_decode_update`` at conversation-batch's shape (5 layers, 64
    slots, 32 heads of a [256, 128] float32 state in 2 groups): one Mosaic
    call, and the 1.34 GB state aliased in and out, not copied."""
    from ray_memory_management_tpu.ops.ssm import ssm_decode_update

    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    L, S, H, N, P_, G = 5, 64, 32, 256, 128, 2
    f32 = jnp.float32
    compiled = jax.jit(
        lambda st, x, dt, A, B, C, D, live, layer: ssm_decode_update(
            st, x, dt, A, B, C, D, live, layer=layer, use_pallas="on"),
        donate_argnums=(0,)).lower(
        s((L, S, H, N, P_), f32), s((S, H, P_)), s((S, H), f32),
        s((H,), f32), s((S, G, N)), s((S, G, N)), s((H,), f32),
        s((S,), jnp.bool_), s((), jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert "ssm_decode_update" in text
    m = compiled.memory_analysis()
    state = L * S * H * N * P_ * 4
    assert m.alias_size_in_bytes >= state
    assert m.temp_size_in_bytes < 64 << 20


@pytest.mark.parametrize("T", [512, 3072])
def test_chunked_scan_kernel_compiles_for_v5e(one_chip, T):
    """``ssd_chunk_scan`` at conversation-batch's shortest and longest
    bucket (32 heads of 128 channels, a state of 256, 2 groups, bf16
    operands): one Mosaic call."""
    from ray_memory_management_tpu.ops.ssm import ssd_scan

    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    H, P_, G, N = 32, 128, 2, 256
    f32 = jnp.float32
    compiled = jax.jit(lambda x, dt, A, B, C, D, n: ssd_scan(
        x, dt, A, B, C, D, true_len=n, use_pallas="on")).lower(
        s((T, H, P_)), s((T, H), f32), s((H,), f32), s((T, G, N)),
        s((T, G, N)), s((H,), f32), s((), jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert "ssd_chunk_scan" in text


def test_state_update_kernel_compiles_for_v5e_at_heads_of_64_channels(
        one_chip):
    """``ssm_decode_update`` at longanswer-batch's shape (5 Mamba layers, 128
    slots, 128 heads of 64 channels in 8 groups, a state of 128): the
    resident state holds two heads a row of lanes, ``[5, 128, 64, 128,
    128]`` float32, 2.68 GB with no padding; one Mosaic call, the state
    aliased in and out, not copied."""
    from ray_memory_management_tpu.ops import ssm

    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    L, S, H, N, P_, G = 5, 128, 128, 128, 64, 8
    f32 = jnp.float32
    k = ssm.heads_a_row(P_)
    resident = s((L, S, H // k, N, k * P_), f32)
    assert k == 2 and ssm.ssm_kernel_takes(resident, s((S, H, P_)))
    compiled = jax.jit(
        lambda st, x, dt, A, B, C, D, live, layer: ssm.ssm_decode_update(
            st, x, dt, A, B, C, D, live, layer=layer, use_pallas="on"),
        donate_argnums=(0,)).lower(
        resident, s((S, H, P_)), s((S, H), f32), s((H,), f32),
        s((S, G, N)), s((S, G, N)), s((H,), f32), s((S,), jnp.bool_),
        s((), jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert "ssm_decode_update" in text
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= L * S * H * N * P_ * 4
    assert m.temp_size_in_bytes < 96 << 20


@pytest.mark.parametrize("T", [512, 2048])
def test_chunked_scan_kernel_compiles_for_v5e_at_heads_of_64_channels(
        one_chip, T):
    """``ssd_chunk_scan`` at longanswer-batch's shortest and longest bucket
    (128 heads of 64 channels, a state of 128, 8 groups of 16, bf16
    operands): a head's channels are half a row of lanes and a block of 16
    heads eight whole rows; one Mosaic call."""
    from ray_memory_management_tpu.ops import ssm

    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    H, P_, G, N = 128, 64, 8, 128
    f32 = jnp.float32
    assert ssm.ssd_kernel_takes(s((T, H, P_)), s((T, G, N)))
    compiled = jax.jit(lambda x, dt, A, B, C, D, n: ssm.ssd_scan(
        x, dt, A, B, C, D, true_len=n, use_pallas="on")).lower(
        s((T, H, P_)), s((T, H), f32), s((H,), f32), s((T, G, N)),
        s((T, G, N)), s((H,), f32), s((), jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert "ssd_chunk_scan" in text


@pytest.mark.parametrize("T", [128, 2048])
def test_expert_tile_kernel_compiles_for_v5e(one_chip, T):
    """``moe_expert_tiles`` at longanswer-batch's decode step and longest
    bucket (128 held experts of a router 512 wide, 22 a token, two matrices
    of 1,024 x 2,688 an expert, bf16): one Mosaic call named for its tiles,
    two experts' matrices (22 MB) inside the VMEM it asks for."""
    from ray_memory_management_tpu.ops import moe

    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    E, Z, F = 128, 1024, 2688
    layer = {"router": s((4096, 512)), "bias": s((512,)),
             "w1": s((E, Z, F)), "w2": s((E, F, Z))}
    assert moe.expert_kernel_takes(s((T, Z)), layer)
    compiled = jax.jit(lambda x, c, w, layer, live: moe.grouped_experts(
        x, c, w, layer, live, 0, use_pallas="on")).lower(
        s((T, Z)), s((T, 22), jnp.int32), s((T, 22), jnp.float32), layer,
        s((T,), jnp.bool_)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert moe.expert_tiles(T, 22, E) == {128: 128, 2048: 480}[T]
    assert f"moe_expert_tiles_{moe.expert_tiles(T, 22, E)}" in text
    assert "ragged-dot" not in text


def _cell_engine(one_chip, monkeypatch, config, traffic):
    """A cell's engine, its program configuration, and its weights and pool
    as shapes on a described chip, the kernels' dispatch steered there:
    (engine, program configuration, params, pool, shape maker)."""
    import importlib

    from chipbench import architectures, manifest
    from chipbench.drivers import serve as serve_driver
    from ray_memory_management_tpu.serve.llm import ContinuousBatcher

    for name in ("ops.flash_attention", "ops.paged_attention", "ops.moe"):
        monkeypatch.setattr(importlib.import_module(
            "ray_memory_management_tpu." + name), "_on_tpu", lambda: True)

    def arr(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    cfg = manifest.config(config)
    arch = architectures.of(cfg)
    e = serve_driver.engine_kwargs(cfg, manifest.traffic(traffic))
    pc = arch.program_config(cfg)
    shaped = lambda tree: jax.tree.map(  # noqa: E731
        lambda x: arr(x.shape, x.dtype), tree)
    params = shaped(jax.eval_shape(
        lambda: arch.init_program_params(jax.random.PRNGKey(0), pc)))
    eng = ContinuousBatcher(
        None, pc, max_slots=e["max_batch_size"],
        max_new_tokens=e["max_new_tokens"], pad_multiple=e["pad_multiple"],
        steps_per_iter=e["steps_per_iter"], kv_page_tokens=e["kv_page_tokens"],
        kv_pool_bytes=e["kv_pool_bytes"])
    return eng, pc, params, shaped(jax.eval_shape(eng.kv_pool.allocate)), arr


@pytest.mark.parametrize("bucket", [None, 4096], ids=["decode", "4096"])
def test_swiglu_expert_kernel_holds_reasoning_batchs_decode_program(
        one_chip, monkeypatch, bucket):
    """``reasoning-batch``'s decode program (``glm-4.7-flash-d7``: 32 rows,
    64 SwiGLU experts of 2,048 x 1,536, 4 a token, six expert layers) holds
    the three-matrix expert kernel once an expert layer, under its own name
    (``moe_swiglu_tiles_64``; none of the two-matrix form's), and no grouped
    matmul of the compiler's; two experts' three matrices (37.7 MB) lie
    inside the VMEM the kernel asks for, which the compile holds it to. The
    prefill program of its longest bucket, 4,096 rows, keeps the compiler's
    grouped matmul, three an expert layer, and no expert kernel."""
    import re

    from ray_memory_management_tpu.ops import moe

    eng, pc, params, pool, arr = _cell_engine(
        one_chip, monkeypatch, "glm-4.7-flash-d7", "reasoning-batch")
    try:
        slots, width = eng.max_slots, eng.kv_pool.table_width
        if bucket is None:
            rows = slots
            compiled = eng._paged_step.lower(
                params, pool, arr((slots,)), arr((slots,)),
                arr((slots, width)), arr((2,), jnp.uint32)).compile()
        else:
            rows = bucket
            compiled = eng._paged_prefill_fn(bucket).lower(
                params, pool, arr((1, bucket)), arr((width,)), arr(()),
                arr((2,), jnp.uint32)).compile()
    finally:
        eng.close()
    layer = params["layers"][-1]["moe"]
    assert layer["w1"].shape == (64, 2048, 1536) and slots == 32
    assert moe.expert_kernel_takes(arr((rows, 2048), jnp.bfloat16), layer)
    text = compiled.as_text()
    experts = pc.n_layers - pc.first_k_dense
    kernels = re.findall(r"%(moe_\w+_tiles_\d+)[.\d]* = ", text)
    assert experts == 6
    if bucket is None:
        assert kernels == ["moe_swiglu_tiles_64"] * experts
        assert "ragged-dot" not in text
    else:
        assert kernels == []
        assert len(re.findall(r"%ragged-dot-none[.\d]* = ", text)) \
            == 3 * experts


@pytest.mark.parametrize("program", ["decode", "mixed"])
def test_experts_too_wide_for_the_kernel_take_its_blocked_form(
        one_chip, monkeypatch, program):
    """``longcontext-batch``'s decode program and its mixed program
    (``glm-5.2-d6-e16``: a share of 16 SwiGLU experts of 6,144 x 2,048 a
    layer, five expert layers; two experts' three matrices are 151 MB) are
    refused the whole-matrix kernel by its VMEM and take the blocked one
    (blocks of 512 of the 2,048 columns): each expert layer lowers to one
    ``moe_swiglu_blocks_<grid>`` call, the decode step's named for its 16
    held experts and the mixed step's for its passes, and to none of the
    compiler's grouped matmuls and no whole-matrix kernel."""
    import re

    from ray_memory_management_tpu.ops import moe

    eng, pc, params, pool, arr = _cell_engine(
        one_chip, monkeypatch, "glm-5.2-d6-e16", "longcontext-batch")
    try:
        slots, width = eng.max_slots, eng.kv_pool.table_width
        key, chunk = arr((2,), jnp.uint32), eng._chunk
        if program == "decode":
            rows = slots
            lowered = eng._paged_step.lower(
                params, pool, arr((slots,)), arr((slots,)),
                arr((slots, width)), key)
        else:
            rows = chunk + slots
            lowered = eng._mixed_step.lower(
                params, pool, arr((chunk,)), arr((width,)), arr(()), arr(()),
                arr(()), arr((slots,)), arr((slots,)), arr((slots, width)),
                key)
    finally:
        eng.close()
    layer = params["layers"][-1]["moe"]
    x = arr((rows, 6144), jnp.bfloat16)
    assert layer["w1"].shape == (16, 6144, 2048)
    assert not moe.expert_kernel_takes(x, layer)
    assert moe.expert_blocks_take(x, layer)
    assert moe._expert_block(x, layer) == 512
    grid = 16 if program == "decode" else \
        -(-rows * 8 // moe._pass_rows(x, layer)) + 16
    text = lowered.as_text()
    experts = pc.n_layers - pc.first_k_dense
    assert experts == 5
    assert re.findall(r'kernel_name = "(moe_\w+)"', text) \
        == [f"moe_swiglu_blocks_{grid}"] * experts
    assert '"chlo.ragged_dot"' not in text
    assert "moe_swiglu_tiles_" not in text


def test_paged_step_keeps_the_pool_where_it_is(one_chip, monkeypatch):
    """The engine's one decode program at chat-online's size (Mistral-7B
    widths, 16 layers, 16 slots, 96 pages of 256 and the sink): the kernel
    is in it, every value of the pool's shape keeps the layout the pool
    came in, and none is a copy. (A scatter or a one-row
    dynamic_update_slice of the new K and V made the compiler pick its own
    layout for the pool and copy 1.5 GiB into it and back every step.)"""
    import re

    from ray_memory_management_tpu.models import gpt
    from ray_memory_management_tpu.ops import paged_attention as pa
    from ray_memory_management_tpu.serve.kv_cache import row_token_bytes
    from ray_memory_management_tpu.serve.llm import ContinuousBatcher

    # the dispatch asks where default computation lands: steer it here
    monkeypatch.setattr(pa, "_on_tpu", lambda: True)
    cfg = gpt.TransformerConfig(
        vocab_size=32_000, d_model=4096, n_layers=16, n_heads=32,
        n_kv_heads=8, d_ff=14336, max_seq=4096, param_dtype=jnp.bfloat16)
    slots = 16

    def shaped(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    def arr(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    eng = ContinuousBatcher(
        None, cfg, max_slots=slots, max_new_tokens=384, pad_multiple=256,
        steps_per_iter=8, kv_page_tokens=256,
        kv_pool_bytes=slots * 1536 * row_token_bytes(cfg))
    try:
        params = shaped(jax.eval_shape(
            lambda: gpt.init_params(jax.random.PRNGKey(0), cfg)))
        pool = shaped(jax.eval_shape(eng.kv_pool.allocate))
        assert pool["k"].shape == (16, 8, 97, 256, 128)
        compiled = eng._paged_step.lower(
            params, pool, arr((slots,)), arr((slots,)),
            arr((slots, eng.kv_pool.table_width)),
            arr((2,), jnp.uint32)).compile()
    finally:
        eng.close()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    made = re.findall(r"= bf16\[16,8,97,256,128\]\{([\d,]+)[^ ]* (\S+?)\(",
                      text)
    assert made and {layout for layout, _ in made} == {"4,3,2,1,0"}
    assert "copy" not in {op for _, op in made}
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    # 7.0 GiB of weights, 1.5 GiB of pool, under 1 GiB of temporaries
    assert 8.4 * 2 ** 30 < total < 9.6 * 2 ** 30
    assert m.alias_size_in_bytes >= 2 * pool["k"].size * 2  # donated


# ------------------------------------------------- the dense decoder's prefill
def _old_prefill_row(params, tokens, cfg, n_positions, true_len):
    """The prefill as it was before the kernel: a fresh row cache through
    ``forward_with_cache``, the head over every position."""
    from ray_memory_management_tpu.models import gpt

    row_cache = gpt.init_kv_cache(cfg, 1, n_positions)
    logits, row_cache = gpt.forward_with_cache(
        params, tokens, row_cache, 0, cfg)
    return logits[0, true_len - 1], {k: c[:, 0] for k, c in row_cache.items()}


# (bucket, pad_multiple = page): chat-online's one block, its 768 (blocks of
# 384) and longprompt-batch's shortest and longest
PREFILLS = [(256, 256), (768, 256), (512, 512), (3584, 512)]


@pytest.mark.parametrize("bucket,page", PREFILLS, ids=lambda v: str(v))
def test_prefill_program_holds_the_kernel_and_no_scores(one_chip, monkeypatch,
                                                        bucket, page):
    """The prefill program as the engine builds it (``_paged_prefill_fn``)
    at ``mistral-7b-d16`` widths: the flash forward kernel is in it, and no
    array of S x S x heads or S x V elements. At 3,584 it needs at least
    2 GiB of temporaries less than the old path's program, lowered the same
    way."""
    import importlib
    import re

    from ray_memory_management_tpu.models import gpt
    from ray_memory_management_tpu.serve.kv_cache import row_token_bytes
    from ray_memory_management_tpu.serve.llm import ContinuousBatcher

    # the dispatch asks where default computation lands: steer it here
    # (the package's ``flash_attention`` is the function, so by full name)
    fa = importlib.import_module(
        "ray_memory_management_tpu.ops.flash_attention")
    monkeypatch.setattr(fa, "_on_tpu", lambda: True)
    cfg = gpt.TransformerConfig(
        vocab_size=32_000, d_model=4096, n_layers=16, n_heads=32,
        n_kv_heads=8, d_ff=14336, max_seq=4096, param_dtype=jnp.bfloat16)
    slots = 8

    def shaped(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    def arr(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = shaped(jax.eval_shape(
        lambda: gpt.init_params(jax.random.PRNGKey(0), cfg)))

    def compiled():
        eng = ContinuousBatcher(
            None, cfg, max_slots=slots, max_new_tokens=256,
            pad_multiple=page, steps_per_iter=8, kv_page_tokens=page,
            kv_pool_bytes=slots * 4096 * row_token_bytes(cfg))
        try:
            pool = shaped(jax.eval_shape(eng.kv_pool.allocate))
            program = eng._paged_prefill_fn(bucket).lower(
                params, pool, arr((1, bucket)),
                arr((eng.kv_pool.table_width,)), arr(()),
                arr((2,), jnp.uint32)).compile()
            return program, bucket in eng._prefill_kernel
        finally:
            eng.close()

    new, counted = compiled()
    text = new.as_text()
    # scores are [.., S, S] and all positions' logits [.., S, V], whatever
    # dimensions of one the compiler drops
    scores = rf"\[(\d+,)*{bucket},{bucket}\]"
    all_logits = rf"\[(\d+,)*{bucket},32000\]"
    assert counted and text.count("tpu_custom_call") == 1
    assert not re.search(scores, text) and not re.search(all_logits, text)
    if bucket != 3584:
        return
    monkeypatch.setattr(gpt, "prefill_row", _old_prefill_row)
    monkeypatch.setattr(gpt, "prefill_takes_kernel", lambda cfg, n: False)
    old, counted = compiled()
    text = old.as_text()
    assert not counted and "tpu_custom_call" not in text
    assert re.search("f32" + scores, text)
    assert re.search("f32" + all_logits, text)
    saved = (old.memory_analysis().temp_size_in_bytes
             - new.memory_analysis().temp_size_in_bytes)
    assert saved >= 2 * 2 ** 30, saved


# ------------------------------------- a prompt's chunk on the decode step
def test_the_mixed_step_fits_and_keeps_the_pool_where_it_is(one_chip,
                                                            monkeypatch):
    """``longprompt-batch``'s mixed-step program (``mistral-7b-d16`` widths,
    8 rows, pages of 512, a chunk table of 8 chunks: 512 prompt positions
    over up to 4,096 keys beside one decode token a row): ONE switch over
    the prefix lengths in the layer loop, a flash Mosaic call a branch and
    one paged-attention call, every value of the pool's shape in the layout
    the pool came in and none a copy (the pages before the chunk are sliced
    out of the pool outside the switch), and the whole program beside the
    2 GiB pool inside the chip's memory."""
    import importlib
    import re

    from ray_memory_management_tpu.models import gpt
    from ray_memory_management_tpu.ops import paged_attention as pa
    from ray_memory_management_tpu.serve.kv_cache import row_token_bytes
    from ray_memory_management_tpu.serve.llm import ContinuousBatcher

    # the dispatches ask where default computation lands: steer them here
    fa = importlib.import_module(
        "ray_memory_management_tpu.ops.flash_attention")
    monkeypatch.setattr(fa, "_on_tpu", lambda: True)
    monkeypatch.setattr(pa, "_on_tpu", lambda: True)
    cfg = gpt.TransformerConfig(
        vocab_size=32_000, d_model=4096, n_layers=16, n_heads=32,
        n_kv_heads=8, d_ff=14336, max_seq=4096, param_dtype=jnp.bfloat16)
    slots, page, reach = 8, 512, 8

    def shaped(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    def arr(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    eng = ContinuousBatcher(
        None, cfg, max_slots=slots, max_new_tokens=256, pad_multiple=page,
        steps_per_iter=8, kv_page_tokens=page,
        kv_pool_bytes=slots * 4096 * row_token_bytes(cfg))
    try:
        params = shaped(jax.eval_shape(
            lambda: gpt.init_params(jax.random.PRNGKey(0), cfg)))
        pool = shaped(jax.eval_shape(eng.kv_pool.allocate))
        assert pool["k"].shape == (16, 8, 65, 512, 128)
        width = eng.kv_pool.table_width
        assert eng._mixed and eng._chunk == page and width == 8
        compiled = eng._mixed_step.lower(
            params, pool, arr((page,)), arr((reach,)), arr(()), arr(()),
            arr(()), arr((slots,)), arr((slots,)), arr((slots, width)),
            arr((2,), jnp.uint32)).compile()
        assert page in eng._prefill_kernel
    finally:
        eng.close()
    text = compiled.as_text()
    # the layer loop is one body: the chunk's flash call in each branch of
    # one switch, the rows' paged one
    assert text.count("tpu_custom_call") == reach + 1
    assert len(re.findall(r" conditional\(", text)) == 1
    assert "prefill_attention" in text and "paged_decode_attention" in text
    made = re.findall(r"= bf16\[16,8,65,512,128\]\{([\d,]+)[^ ]* (\S+?)\(",
                      text)
    assert made and {layout for layout, _ in made} == {"4,3,2,1,0"}
    assert "copy" not in {op for _, op in made}
    # no scores: the chunk's are [32, 512, 512] to [32, 512, 4096]
    assert not re.search(r"\[(\d+,)*32,512,(512|1024|2048|3584|4096)\]",
                         text)
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    # 7.0 GiB of weights, 2 GiB of pool, under 1 GiB of temporaries
    assert 8.9 * 2 ** 30 < total < 10.1 * 2 ** 30
    assert m.alias_size_in_bytes >= 2 * pool["k"].size * 2  # donated


def test_the_hybrid_mixed_step_fits_and_keeps_pool_and_state_where_they_are(
        one_chip, monkeypatch):
    """``conversation-batch``'s mixed-step program (``falcon-h1-34b-d5``
    widths, 64 rows, pages of 512, a chunk table of 6 chunks, the pool of
    the cell's mix file): a layer holds the scan kernel for the chunk, the
    live rows' update kernel **under the mixed step's own name** (none under
    ``ssm_decode_update``, by whose calls the cell's rooflines count the
    decode program's token-steps), a flash Mosaic call a branch of the
    switch and one paged-attention call; K, V and the recurrence's state in
    the layout they came in, none a copy (a slot's 4 MiB a layer are sliced
    out and put back around the kernel that updates the live rows in
    place); the pool donated, and the whole program inside the chip's
    memory with 2 GB to spare."""
    import importlib
    import re

    from chipbench import architectures, flops, manifest
    from chipbench.drivers import serve as serve_driver
    from ray_memory_management_tpu.serve.llm import ContinuousBatcher

    # the dispatches ask where default computation lands: steer them here
    for name in ("ops.flash_attention", "ops.paged_attention", "ops.ssm",
                 "models.hybrid_ssm"):
        monkeypatch.setattr(importlib.import_module(
            "ray_memory_management_tpu." + name), "_on_tpu", lambda: True)

    def shaped(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    def arr(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    cfg = manifest.config("falcon-h1-34b-d5")
    arch = architectures.of(cfg)
    e = serve_driver.engine_kwargs(cfg,
                                   manifest.traffic("conversation-batch"))
    pc = arch.program_config(cfg)
    slots, page, layers = e["max_batch_size"], e["kv_page_tokens"], 5
    eng = ContinuousBatcher(
        None, pc, max_slots=slots, max_new_tokens=e["max_new_tokens"],
        pad_multiple=e["pad_multiple"], steps_per_iter=e["steps_per_iter"],
        kv_page_tokens=page, kv_pool_bytes=e["kv_pool_bytes"])
    try:
        params = shaped(jax.eval_shape(
            lambda: arch.init_program_params(jax.random.PRNGKey(0), pc)))
        pool = shaped(jax.eval_shape(eng.kv_pool.allocate))
        assert {k: v.shape for k, v in pool.items()} == {
            "k": (5, 4, 257, 512, 128), "v": (5, 4, 257, 512, 128),
            "ssm": (5, 64, 32, 256, 128), "conv": (5, 3, 64, 5120)}
        width = eng.kv_pool.table_width
        # the longest prompt is 3,072 positions: six chunks of 512
        reach = -(-(pc.max_seq - e["max_new_tokens"]) // page)
        assert eng._mixed and eng._chunk == page and reach == 6
        compiled = eng._mixed_step.lower(
            params, pool, arr((page,)), arr((reach,)), arr(()), arr(()),
            arr(()), arr((slots,)), arr((slots,)), arr((slots, width)),
            arr((2,), jnp.uint32), arr(())).compile()
        assert page in eng._prefill_kernel
    finally:
        eng.close()
    text = compiled.as_text()

    def calls(name):
        return len(set(re.findall(r"%(" + name + r"[.\d]*) = ", text)))

    # a layer: a flash call a branch, paged attention, the update, the scan
    assert text.count("tpu_custom_call") == layers * (reach + 3)
    assert calls("ssd_chunk_scan") == calls("ssm_mixed_update") == layers
    assert calls("paged_decode_attention") == layers
    # no operation of that name (the text's table of stack frames holds the
    # wrapper function's; a trace reader goes by an operation's own name)
    assert calls("ssm_decode_update") == 0
    for shape in ("f32[5,64,32,256,128]", "bf16[5,4,257,512,128]"):
        made = re.findall(
            "= " + re.escape(shape) + r"\{([\d,]+)[^ ]* (\S+?)\(", text)
        assert made and {lay for lay, _ in made} == {"4,3,2,1,0"}, shape
        assert not {op for _, op in made} & {"copy", "copy-start"}, shape
    held = sum(v.size * v.dtype.itemsize for v in pool.values())
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= held  # donated
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    # 9.65 GB of weights, 1.35 + 1.35 GB of pages and state, and under
    # 0.3 GB of temporaries (a 3,072 prefill's were 0.5 GB)
    weights = 2 * arch.n_params(cfg)
    assert weights + held < total < weights + held + 0.3e9
    assert total < flops.peak("TPU v5 lite")["hbm_bytes"] - 2.0e9


def test_the_pattern_mixed_step_fits_and_keeps_pool_and_state_where_they_are(
        one_chip, monkeypatch):
    """``longanswer-batch``'s mixed-step program (``nemotron-3-super-d11-e128``
    widths, 128 rows, pages and chunks of 512, a chunk table of 5 chunks, the
    pool of the cell's mix file): each of the five Mamba layers holds the
    scan kernel for the chunk and the live rows' update kernel **under the
    mixed step's own name** (none under ``ssm_decode_update``, by whose calls
    the cell's rooflines count the decode program's token-steps); each of
    the five expert layers ONE call of the expert kernel over the chunk's
    and the decode rows' 640 rows together (``moe_expert_tiles_238``; none
    of the decode step's ``moe_expert_tiles_128``, so a held expert's
    matrices are read once a layer); the one attention layer a flash Mosaic
    call a branch of the switch and one paged-attention call. K, V and the
    packed state keep the layout they came in and none is a copy (a slot's
    4 MiB a layer are sliced out and put back around the kernel that updates
    the live rows in place); the pool is donated, and the whole program is
    inside the chip's memory."""
    import importlib
    import re

    from chipbench import architectures, flops, manifest
    from chipbench.drivers import serve as serve_driver
    from ray_memory_management_tpu.ops import moe
    from ray_memory_management_tpu.serve.llm import ContinuousBatcher

    # the dispatches ask where default computation lands: steer them here
    for name in ("ops.flash_attention", "ops.paged_attention", "ops.ssm",
                 "ops.moe", "models.hybrid_ssm"):
        monkeypatch.setattr(importlib.import_module(
            "ray_memory_management_tpu." + name), "_on_tpu", lambda: True)

    def shaped(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    def arr(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    cfg = manifest.config("nemotron-3-super-d11-e128")
    arch = architectures.of(cfg)
    e = serve_driver.engine_kwargs(cfg, manifest.traffic("longanswer-batch"))
    pc = arch.program_config(cfg)
    slots, page = e["max_batch_size"], e["kv_page_tokens"]
    eng = ContinuousBatcher(
        None, pc, max_slots=slots, max_new_tokens=e["max_new_tokens"],
        pad_multiple=e["pad_multiple"], steps_per_iter=e["steps_per_iter"],
        kv_page_tokens=page, kv_pool_bytes=e["kv_pool_bytes"])
    try:
        params = shaped(jax.eval_shape(
            lambda: arch.init_program_params(jax.random.PRNGKey(0), pc)))
        pool = shaped(jax.eval_shape(eng.kv_pool.allocate))
        assert {k: v.shape for k, v in pool.items()} == {
            "k": (1, 2, 1025, 512, 128), "v": (1, 2, 1025, 512, 128),
            "ssm": (5, 128, 64, 128, 128), "conv": (5, 3, 128, 10240)}
        width = eng.kv_pool.table_width
        # the longest prompt is 2,048 positions of the 2,560 a prompt may
        # have beside a full answer: five chunks of 512
        reach = -(-(pc.max_seq - e["max_new_tokens"]) // page)
        assert eng._mixed and eng._chunk == page and reach == 5
        compiled = eng._mixed_step.lower(
            params, pool, arr((page,)), arr((reach,)), arr(()), arr(()),
            arr(()), arr((slots,)), arr((slots,)), arr((slots, width)),
            arr((2,), jnp.uint32), arr(())).compile()
        assert page in eng._prefill_kernel
    finally:
        eng.close()
    text = compiled.as_text()

    def calls(name):
        return len(set(re.findall(r"%(" + name + r"[.\d]*) = ", text)))

    mamba, experts = arch.ssm_layers(cfg), arch.expert_layers(cfg)
    tiles = moe.expert_tiles(page + slots, pc.experts_per_tok,
                             pc.n_held_experts)
    assert (mamba, experts, tiles) == (5, 5, 238)
    assert tiles == arch.expert_kernel_tiles(cfg, page + slots)
    # a Mamba layer: the update and the scan; an expert layer: one call; the
    # attention layer: a flash call a branch and paged attention
    assert text.count("tpu_custom_call") == 2 * mamba + experts + reach + 1
    assert calls("ssd_chunk_scan") == calls("ssm_mixed_update") == mamba
    assert calls(f"moe_expert_tiles_{tiles}") == experts
    assert calls("paged_decode_attention") == 1
    # no operation of the decode program's names (the text's table of stack
    # frames holds the wrapper functions'; a trace reader goes by an
    # operation's own name)
    assert calls("ssm_decode_update") == 0
    assert calls(f"moe_expert_tiles_{pc.n_held_experts}") == 0
    assert "ragged-dot" not in text
    # a Mamba layer's in-projection over the 640 rows runs once: its parts
    # are held behind a barrier (without it the compiler ran the whole
    # projection again, 0.52 ms a time on the chip, for the gate it reads
    # after the scan); and the expert layout's lookups are a tile's, not a
    # layout row's: one of 30,464 a layer (the sorted order's) where
    # four were
    wide = pc.ssm_proj_width
    assert len(re.findall(rf"^\s+%fusion\S* = \(?bf16\[640,{wide}\]", text,
                          flags=re.M)) == mamba
    assert len(re.findall(rf"= s32\[{tiles * moe.EXPERT_TILE}\]\S* gather\(",
                          text)) == experts
    for shape in ("f32[5,128,64,128,128]", "bf16[1,2,1025,512,128]"):
        made = re.findall(
            "= " + re.escape(shape) + r"\{([\d,]+)[^ ]* (\S+?)\(", text)
        assert made and {lay for lay, _ in made} == {"4,3,2,1,0"}, shape
        assert not {op for _, op in made} & {"copy", "copy-start"}, shape
    held = sum(v.size * v.dtype.itemsize for v in pool.values())
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= held  # donated
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    # 9.30 GB of weights, 0.54 + 2.72 GB of pages and state, and 0.33 GB
    # of temporaries (the 2,048 prefill's were 0.55 GB): 12.89 GB
    weights = 2 * arch.n_params(cfg)
    assert weights + held < total < weights + held + 0.4e9
    assert total < flops.peak("TPU v5 lite")["hbm_bytes"] - 2.0e9


def test_expanded_chunk_attention_kernel_compiles_for_v5e(one_chip):
    """The chunk's attention in the expanded form at ``glm-5.2-d6-e16``'s
    shape (a chunk of 4,096 queries, 64 heads of 192 + 64 and 256, a row of
    8 pages of 4,096 in the cell's pool of 97, bf16): one Mosaic call under
    the name the rows' kernel carries, the softmax state of four heads' 4,096
    queries (32 MB) and their expansion of a key block inside the VMEM the
    kernel asks for, and the pool read where it lies: the program's
    temporaries are the transposed queries and values and the two weight
    arrays, no copy of the pool."""
    import re

    from ray_memory_management_tpu.ops import paged_attention as pa

    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    C, H = 4096, 64
    q, pages = s((1, C, H, 256)), s((6, 97, 4096, 640))
    to_k, to_v = s((512, H, 192)), s((512, H, 256))
    assert pa.expanded_kernel_takes(q, pages, to_k, to_v)
    compiled = jax.jit(
        lambda q, pages, table, mask, at, to_k, to_v, layer:
        pa.sparse_expanded_attention(q, pages, table, mask, at, to_k, to_v,
                                     layer=layer, scale=1 / 16,
                                     use_pallas="on")).lower(
        q, pages, s((1, 8), jnp.int32), s((1, C, 32768)),
        s((1, C), jnp.int32), to_k, to_v, s((), jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert len(set(re.findall(r"%(sparse_latent_attention[.\d]*) = ",
                              text))) == 1
    assert "bf16[6,97,4096,640]" in text and not re.findall(
        r"= bf16\[6,97,4096,640\]\{[^ ]* copy", text)
    m = compiled.memory_analysis()
    assert m.output_size_in_bytes == C * H * 256 * 2
    assert m.temp_size_in_bytes < 0.2e9


# --------------- MiniCPM-SALA's kernels (models/sparse_linear.py, longdoc-batch)
# at the cell's shapes: 32 decode rows or a chunk of 4,096 queries, 2 K/V
# heads of 16 query heads at 128, pages of 4,096 (pooled keys: 256 a page),
# blocks of 64, rows of 8 pages
SALA = dict(L=2, hkv=2, rep=16, dh=128, P=257, page=4096, width=8, rows=32,
            chunk=4096)


def _sala_shapes(one_chip, n):
    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    c = SALA
    G = c["rows"] if n == 1 else 1
    pages = s((c["L"], c["hkv"], c["P"], c["page"], c["dh"]))
    return (s, G, s((G, n, c["hkv"], c["rep"], c["dh"])), pages,
            s((c["L"], c["hkv"], c["P"], c["page"] // 16, c["dh"])),
            s((G, c["width"]), jnp.int32), s((G, n), jnp.int32))


@pytest.mark.parametrize("n", [1, 4096], ids=["rows", "chunk"])
@pytest.mark.parametrize("kernel", ["block_scores", "block_select",
                                    "block_sparse_attention"])
def test_block_sparse_kernels_compile_for_v5e(one_chip, kernel, n):
    """The three kernels of the block selection, each one Mosaic call under
    its own name, for the decode rows and for a chunk, at the cell's shapes:
    the scores with their softmax and the sum over a group's heads; the
    threshold top-64 of 512 blocks; the attention over the chosen blocks
    (128 at most: every block before ``dense_len``)."""
    from ray_memory_management_tpu.ops import paged_attention as pa

    s, G, q, pages, pooled, table, pos = _sala_shapes(one_chip, n)
    NB = SALA["width"] * SALA["page"] // 64
    if kernel == "block_scores":
        fn, args = (lambda q, c, t, p: pa.block_scores(
            q, c, t, p, layer=1, stride=16, window=32, block=64,
            scale=128 ** -0.5, use_pallas="on")), (q, pooled, table, pos)
    elif kernel == "block_select":
        fn, args = (lambda r, p: pa.block_select(
            r, p, top_k=64, block=64, init_blocks=1, local=2048,
            dense_len=8192, use_pallas="on")), (
                s((G, n, SALA["hkv"], NB), jnp.float32), pos)
    else:
        fn, args = (lambda q, k, v, t, ch, p, live: pa.block_sparse_attention(
            q, k, v, t, ch, p, layer=1, block=64, scale=128 ** -0.5,
            most=128, live=live if n == 1 else None, use_pallas="on")), (
                q, pages, pages, table,
                s((G, n, SALA["hkv"], NB), jnp.bool_), pos,
                s((G,), jnp.bool_))
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert kernel in text
    # the pool is read where it lies: no copy of a page array
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


def test_lightning_state_update_compiles_for_v5e_with_a_group_a_head(
        one_chip):
    """``ssm_decode_update`` at longdoc-batch's shape (6 lightning layers, 32
    slots, 32 heads of a [128, 128] float32 state, **a group a head**):
    one Mosaic call under the name the cell's reader counts, a grid step a
    block of 16 heads each with its own B and C, and the 403 MB state
    aliased in and out, not copied."""
    from ray_memory_management_tpu.ops import ssm

    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    L, S, H, N = 6, 32, 32, 128
    f32 = jnp.float32
    compiled = jax.jit(
        lambda st, x, dt, A, B, C, D, live, layer: ssm.ssm_decode_update(
            st, x, dt, A, B, C, D, live, layer=layer, use_pallas="on",
            name="lightning_decode_update"),
        donate_argnums=(0,)).lower(
        s((L, S, H, N, N), f32), s((S, H, N)), s((S, H), f32),
        s((H,), f32), s((S, H, N)), s((S, H, N)), s((H,), f32),
        s((S,), jnp.bool_), s((), jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert "lightning_decode_update" in text
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= L * S * H * N * N * 4
    assert m.temp_size_in_bytes < 64 << 20


def test_lightning_chunk_scan_compiles_for_v5e_with_a_group_a_head(one_chip):
    """``ssd_chunk_scan`` over a chunk of 4,096 with a group a head (32
    heads of 128, a state of 128, bf16 operands): one Mosaic call, eight
    heads a grid step."""
    from ray_memory_management_tpu.ops.ssm import ssd_scan

    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    T, H, N = 4096, 32, 128
    f32 = jnp.float32
    compiled = jax.jit(lambda x, dt, A, B, C, D, n, h0: ssd_scan(
        x, dt, A, B, C, D, true_len=n, h0=h0, use_pallas="on")).lower(
        s((T, H, N)), s((T, H), f32), s((H,), f32), s((T, H, N)),
        s((T, H, N)), s((H,), f32), s((), jnp.int32),
        s((H, N, N), f32)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert "ssd_chunk_scan" in text
