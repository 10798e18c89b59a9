"""A chunk of a prompt attends in the expanded form
(ops/paged_attention.py::sparse_expanded_attention): the kernel under the
Pallas interpreter against its plain ``jax.numpy`` form and against the
absorbed form it replaces for a chunk (``sparse_latent_attention_reference``,
then the ``to_v`` product); the choice between the two forms by the group's
size (models/latent_sparse_moe.py::_expanded_pays); and the model through
chunks in which the expanded form really runs, against the plain reference's
whole forward. The kernel's compile for a described v5e at the published
shape is in tests/test_chip_compile.py. No time read here is a device number.
"""

import numpy as np
import pytest

from chipbench import architectures

PAGE, TOP_K = 128, 64
# widths in whole lanes, as the kernel wants them: 8 heads in two groups of
# four, the latent beside a rotary key of 64 and padding to 256
H, RANK, NOPE, ROPE, VD, W = 8, 128, 64, 64, 128, 256


@pytest.fixture
def small_tiles(monkeypatch):
    """The kernel's tiles cut down so that a chunk of 256 queries is four
    tiles of two matmuls each: every branch of its grid at a size the
    interpreter walks in seconds."""
    from ray_memory_management_tpu.ops import paged_attention as pa

    monkeypatch.setattr(pa, "_EXPAND_QUERIES", 64)
    monkeypatch.setattr(pa, "_EXPAND_ROWS", 32)
    return pa


def _inputs(first, n, ties=False):
    """A chunk of ``n`` queries at positions ``first``.. on a row of six
    scattered pages (768 positions), its selection made by the plain forms
    from random index scores (with whole runs of equal scores: ``ties``)."""
    import jax
    import jax.numpy as jnp

    from ray_memory_management_tpu.ops import paged_attention as pa

    ks = jax.random.split(jax.random.PRNGKey(first + n), 6)
    q = jax.random.normal(ks[0], (1, n, H, NOPE + ROPE))
    latent = jax.random.normal(ks[1], (3, 10, PAGE, W))
    to_k = jax.random.normal(ks[2], (RANK, H, NOPE)) * RANK ** -0.5
    to_v = jax.random.normal(ks[3], (RANK, H, VD)) * RANK ** -0.5
    table = jnp.array([[3, 1, 7, 8, 0, 5]], jnp.int32)
    at = (first + jnp.arange(n))[None]
    scores = jax.random.normal(ks[4], (1, n, 6 * PAGE))
    if ties:   # quantised: many equal scores, some across the threshold
        scores = jnp.round(scores * 2) / 2
    scores = jnp.where(jnp.arange(6 * PAGE) <= at[..., None], scores,
                       pa._NEG_INF)
    return q, latent, table, pa.index_select_reference(scores, TOP_K), at, \
        to_k, to_v


def _absorbed(q, latent, table, mask, to_k, to_v, scale):
    """What the chunk did before: every head's query carried to the cache's
    width, the absorbed plain form, then the ``to_v`` product."""
    import jax.numpy as jnp

    from ray_memory_management_tpu.ops import paged_attention as pa

    q_abs = jnp.pad(jnp.concatenate(
        [jnp.einsum("gthn,lhn->gthl", q[..., :NOPE], to_k), q[..., NOPE:]],
        -1), ((0, 0), (0, 0), (0, 0), (0, W - RANK - ROPE)))
    o = pa.sparse_latent_attention_reference(
        q_abs, latent, table, mask, layer=2, top_k=TOP_K, value_width=RANK,
        scale=scale)
    return jnp.einsum("gthl,lhv->gthv", o, to_v)


@pytest.mark.parametrize("first,n,real,ties", [
    (0, 256, 256, False),       # a first chunk: its first 64 queries have
                                # no more than top_k positions and select all
    (384, 256, 256, False),     # a later chunk under a real selection
    (256, 256, 150, False),     # its last real position inside a tile
    (192, 128, 128, False),     # a chunk that starts inside a key block
    (256, 256, 256, True)],     # ties in the selection
    ids=["first", "later", "mid-tile", "unaligned", "ties"])
def test_the_kernel_equals_the_plain_form_and_the_absorbed_one(
        small_tiles, first, n, real, ties):
    pa = small_tiles
    q, latent, table, mask, at, to_k, to_v = _inputs(first, n, ties)
    hit = np.asarray(mask) == 0
    assert (hit.sum(-1)[0] == np.minimum(first + np.arange(n) + 1,
                                         TOP_K)).all()
    if real < n:  # the padding's rows are computed and nobody reads them,
        # whatever they select: here nothing at all
        mask = mask.at[:, real:].set(pa._NEG_INF)
    kw = dict(layer=2, scale=0.09)
    plain = pa.sparse_expanded_attention(q, latent, table, mask, at, to_k,
                                         to_v, use_pallas="off", **kw)
    kernel = pa.sparse_expanded_attention(q, latent, table, mask, at, to_k,
                                          to_v, use_pallas="interpret", **kw)
    assert kernel.shape == (1, n, H, VD) and kernel.dtype == q.dtype
    np.testing.assert_allclose(np.asarray(kernel)[:, :real],
                               np.asarray(plain)[:, :real], atol=2e-5)
    np.testing.assert_allclose(
        np.asarray(kernel)[:, :real],
        np.asarray(_absorbed(q, latent, table, mask, to_k, to_v,
                             0.09))[:, :real], atol=2e-5)
    # pages the table does not name, and layers other than the one asked
    # for, are never read
    other = latent.at[:2].set(np.nan).at[2, _pages_outside(table)].set(np.nan)
    again = pa.sparse_expanded_attention(q, other, table, mask, at, to_k,
                                         to_v, use_pallas="interpret", **kw)
    assert np.isfinite(np.asarray(kernel)).all()
    assert np.array_equal(np.asarray(again), np.asarray(kernel))


def _pages_outside(table):
    return np.array(sorted(set(range(10)) - set(np.asarray(table).ravel())))


def test_groups_of_rows_share_nothing(small_tiles):
    """Two groups on rows of their own, at different places: each equals the
    group alone (the softmax state starts anew a group and head group)."""
    import jax.numpy as jnp

    pa = small_tiles
    a = _inputs(384, 128)
    b = _inputs(0, 128)
    q, mask, at = (jnp.concatenate([x, y]) for x, y in zip(
        (a[0], a[3], a[4]), (b[0], b[3], b[4])))
    table = jnp.array([[3, 1, 7, 8, 0, 5], [2, 9, 4, 6, 6, 6]], jnp.int32)
    latent, to_k, to_v = a[1], a[5], a[6]
    kw = dict(layer=1, scale=0.09, use_pallas="interpret")
    both = pa.sparse_expanded_attention(q, latent, table, mask, at, to_k,
                                        to_v, **kw)
    for g in range(2):
        alone = pa.sparse_expanded_attention(
            q[g:g + 1], latent, table[g:g + 1], mask[g:g + 1], at[g:g + 1],
            to_k, to_v, **kw)
        np.testing.assert_allclose(np.asarray(both[g]), np.asarray(alone[0]),
                                   atol=1e-6)


def test_what_the_kernel_can_tile():
    import jax
    import jax.numpy as jnp

    from ray_memory_management_tpu.ops import paged_attention as pa

    def takes(n=4096, heads=64, dq=256, vd=256, rank=512, w=640, page=4096,
              dtype=jnp.bfloat16):
        s = jax.ShapeDtypeStruct
        return pa.expanded_kernel_takes(
            s((1, n, heads, dq), dtype), s((6, 97, page, w), jnp.bfloat16),
            s((rank, heads, dq - 64), dtype), s((rank, heads, vd), dtype))

    assert takes()                         # GLM-5.2's chunk of 4,096
    assert takes(n=1024) and takes(n=512)
    assert not takes(n=1536)               # not whole tiles of 1,024
    assert not takes(n=32768)              # its softmax state: 256 MB
    assert not takes(heads=6)              # not whole groups of four
    assert not takes(dq=192) and not takes(vd=192) and not takes(w=576)
    assert not takes(dtype=jnp.float32)    # the pool's type is the queries'


# ----------------------------------------------------- the choice by shape
GLM = dict(n_heads=64, kv_lora_rank=512, qk_nope_head_dim=192,
           qk_rope_head_dim=64, v_head_dim=256)
TOY = dict(n_heads=4, kv_lora_rank=96, qk_nope_head_dim=32,
           qk_rope_head_dim=32, v_head_dim=32)


@pytest.mark.parametrize("widths,under,over", [(GLM, 358, 359),
                                               (TOY, 48, 49)],
                         ids=["glm-5.2", "toy"])
def test_the_groups_size_chooses_the_form(widths, under, over):
    """GLM-5.2: 640 n saved a head against 512 x 448 = 229,376 to expand one:
    from 359 queries on. The benchmark's toy (cache width 128): 128 n against
    96 x 64 = 6,144: from 49 on, so its chunks of 16 stay absorbed."""
    from ray_memory_management_tpu.models.latent_sparse_moe import (
        LatentSparseMoEConfig, _expanded_pays)

    cfg = LatentSparseMoEConfig(
        vocab_size=512, d_model=64, q_lora_rank=32, d_ff=128, moe_d_ff=64,
        n_routed_experts=4, n_held_experts=4, n_shared_experts=1,
        experts_per_tok=2, routed_scaling_factor=2.5, index_n_heads=4,
        index_head_dim=64, index_topk=8, indexer_types=("full",), **widths)
    assert not _expanded_pays(cfg, 1)          # a decode row
    assert not _expanded_pays(cfg, 16)
    assert not _expanded_pays(cfg, under) and _expanded_pays(cfg, over)
    assert _expanded_pays(cfg, 4096)


# -------------------------------------- the model, where the chunk expands
MODEL = dict(
    name="toy-glm-chunks", architecture="latent_sparse_moe",
    model_type="glm_moe_dsa", vocab_size=512, hidden_size=64,
    num_attention_heads=4, num_key_value_heads=4, head_dim=32,
    q_lora_rank=32, kv_lora_rank=96, qk_nope_head_dim=32,
    qk_rope_head_dim=32, qk_head_dim=64, v_head_dim=32,
    intermediate_size=128, moe_intermediate_size=64, n_router_experts=16,
    n_routed_experts=4, first_held_expert=4, n_shared_experts=1,
    num_experts_per_tok=2, routed_scaling_factor=2.5, norm_topk_prob=True,
    index_n_heads=4, index_head_dim=64, index_topk=24,
    indexer_types=["full", "shared", "full"],
    mlp_layer_types=["dense", "sparse", "sparse"], num_hidden_layers=3,
    num_nextn_predict_layers=0, first_k_dense_replace=1,
    max_position_embeddings=256, rms_norm_eps=1e-5,
    rope_parameters={"rope_theta": 10000.0, "rope_type": "default"},
    n_group=1, topk_group=1, param_dtype="float32",
    activation_dtype="float32")
M_PAGE, M_CHUNK, M_ROWS, M_PAGES = 16, 64, 3, 20


@pytest.mark.parametrize("n_prompt", [150, 128, 40])
def test_a_prompt_in_expanded_chunks_then_decode_against_the_reference(
        n_prompt, monkeypatch):
    """Chunks of 64 at the toy widths, where the expanded form pays from 49
    queries on: a prompt that ends inside its third chunk, one that ends on
    a chunk's edge, one shorter than a chunk; then decode (a group of one:
    absorbed) to position 160. Every served position's logits equal the
    plain reference's whole forward; the selection binds (24 of up to 160).
    The chunk's attention is counted: expanded once a layer a chunk, and the
    absorbed kernel's caller sees the decode rows alone."""
    import jax
    import jax.numpy as jnp

    from ray_memory_management_tpu.models import latent_sparse_moe as model

    arch = architectures.of(MODEL)
    pc = arch.program_config(MODEL)
    params = arch.init_program_params(jax.random.PRNGKey(2 ** 31 + 40), pc)
    assert model._expanded_pays(pc, M_CHUNK) and not model._expanded_pays(
        pc, 1)
    seen = {"expanded": [], "absorbed": []}
    for name, kind in (("sparse_expanded_attention", "expanded"),
                       ("sparse_latent_attention", "absorbed")):
        def spy(q, *a, _f=getattr(model, name), _kind=kind, **kw):
            seen[_kind].append(q.shape[:2])
            return _f(q, *a, **kw)
        monkeypatch.setattr(model, name, spy)
    jax.clear_caches()
    toks = jax.random.randint(jax.random.PRNGKey(n_prompt), (160,), 2, 512)
    spec = model.cache_spec(pc)
    pool = {k: jnp.zeros(lead + (M_PAGES + 1, M_PAGE) + trail, dt)
            for k, (lead, trail, dt) in spec.items()}
    width = pc.max_seq // M_PAGE
    mine = np.full(width, M_PAGES, np.int32)
    mine[:12] = [7, 3, 11, 0, 5, 9, 2, 14, 1, 19, 4, 16]
    table = np.full((M_ROWS, width), M_PAGES, np.int32)
    lengths = np.zeros(M_ROWS, np.int32)
    mixed = jax.jit(model.mixed_step, static_argnames=("cfg",))
    decode = jax.jit(model.paged_decode, static_argnames=("cfg",))
    per, got, put = M_CHUNK // M_PAGE, {}, jnp.array
    reach = -(-n_prompt // M_CHUNK) * per
    with jax.default_matmul_precision("highest"):
        want = np.asarray(arch.reference().logits(params, toks, MODEL))
        for ci in range(reach // per):
            real = min(M_CHUNK, n_prompt - ci * M_CHUNK)
            chunk = np.ones(M_CHUNK, np.int32)
            chunk[:real] = np.asarray(toks)[ci * M_CHUNK:ci * M_CHUNK + real]
            logits, pool, _ = mixed(
                params, pool, put(chunk), put(mine[:reach]),
                jnp.int32(real - 1), jnp.ones(M_ROWS, jnp.int32),
                put(lengths), put(lengths), put(table), pc,
                chunk_index=jnp.int32(ci))
        got[n_prompt - 1] = np.asarray(logits[-1])
        table[1], lengths[1] = mine, n_prompt
        for t in range(n_prompt, 160):
            tk = np.ones(M_ROWS, np.int32)
            tk[1] = int(toks[t])
            logits, pool, _ = decode(params, put(tk), pool, put(lengths),
                                     put(lengths), put(table), pc)
            got[t] = np.asarray(logits[1])
            lengths[1] += 1
    monkeypatch.undo()
    jax.clear_caches()           # nobody after this test runs its trace
    for t, g in got.items():
        np.testing.assert_allclose(g, want[t], atol=3e-4)
    # traced once a program: the mixed step's chunk expanded in each of the
    # three layers, its rows and the decode program's rows absorbed
    assert seen["expanded"] == [(1, M_CHUNK)] * 3
    assert seen["absorbed"] == [(M_ROWS, 1)] * 6
