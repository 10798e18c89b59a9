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
# multiple of 8 rows (1032 -> 344); a short one the kernel spans whole
SHAPES = [(192, 1024, 64), (128, 2048, 64), (4, 8192, 128), (4, 1032, 64),
          (4, 40, 64)]


@pytest.mark.parametrize("shape", SHAPES, ids=lambda s: "x".join(map(str, s)))
@pytest.mark.parametrize("fn,n_kernels", [(_forward, 1), (_backward, 3)],
                         ids=["fwd", "bwd"])
def test_flash_kernels_compile_for_v5e(one_chip, shape, fn, n_kernels):
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    compiled = jax.jit(fn).lower(x, x, x).compile()
    # the kernels themselves, not the jnp reference: fwd is one Mosaic
    # call; bwd is the fwd recompute plus the dq and dk/dv kernels
    assert compiled.as_text().count("tpu_custom_call") == n_kernels


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
