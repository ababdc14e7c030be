"""The token cell's three Pallas kernels compile for a described TPU v5e at
the cell's own shapes (no chip: the ``on-chip-measurement`` guide's third
rehearsal, kept as a test; topology described inside a fixture, this file
only). What the chip's compiler refuses here costs no chip time."""
import json
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from chipbench.trace_reduce import roles_missing

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever keeps libtpu away
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip cannot be read back from the cache
    without one: keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def shapes(one_chip):
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "mistral7b-v03-d2.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(ROOT, "chipbench", "traffic",
                           "tok4k-b4.json")) as f:
        traffic = json.load(f)
    b, s, d = traffic["per_chip_batch"], traffic["window"], cfg["head_dim"]

    def arr(heads):
        return jax.ShapeDtypeStruct((b, s, heads, d), jnp.bfloat16,
                                    sharding=one_chip)
    return arr(cfg["num_attention_heads"]), arr(cfg["num_key_value_heads"])


def kernels_in(compiled) -> set:
    from chipbench.run import mosaic_kernel_names
    return mosaic_kernel_names(compiled)


def test_flash_forward_compiles_at_the_cells_shapes(shapes, no_persistent_cache):
    from petastorm_tpu.ops.flash_attn import make_flash_attention
    q, kv = shapes
    attn = make_flash_attention(causal=True, interpret=False)
    compiled = jax.jit(attn).lower(q, kv, kv).compile()
    assert kernels_in(compiled) == {"flash_fwd"}


def test_flash_backward_compiles_at_the_cells_shapes(shapes, no_persistent_cache):
    from petastorm_tpu.ops.flash_attn import make_flash_attention
    q, kv = shapes
    attn = make_flash_attention(causal=True, interpret=False)

    def loss(q, k, v):
        return jnp.sum(attn(q, k, v).astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv).compile()
    # By role: the forward, and a backward of the pair or of one kernel.
    assert roles_missing(("flash",), kernels_in(compiled)) == 0
    assert all(k.startswith("flash_") for k in kernels_in(compiled))
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 2e9
