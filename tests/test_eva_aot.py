"""The three EVA Pallas kernels compile for a described TPU v5e at the byte
cell's own shapes (no chip: the ``on-chip-measurement`` guide's third
rehearsal, kept as a test; the topology is described inside a fixture of
this file only). What the chip's compiler refuses here costs no chip
time: a tile the lanes cannot hold, more fast memory than a kernel may
use, a scalar-prefetch schedule Mosaic cannot index."""
import json
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


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
def cell(topo):
    """(attention callable, q/k/v shape, phi/mu shape) of
    ``evabyte-byte16k-1chip`` on one described chip."""
    from petastorm_tpu.ops.eva_attn import make_eva_attention
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "evabyte-6.5b-d4.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(ROOT, "chipbench", "traffic",
                           "byte16k-b1.json")) as f:
        traffic = json.load(f)
    one_chip = SingleDeviceSharding(topo.devices[0])
    heads, d = cfg["num_attention_heads"], cfg["head_dim"]
    rows = jax.ShapeDtypeStruct(
        (traffic["per_chip_batch"], traffic["window"], heads, d),
        jnp.bfloat16, sharding=one_chip)
    per_head = jax.ShapeDtypeStruct((heads, d), jnp.float32,
                                    sharding=one_chip)
    attn = make_eva_attention(cfg["window_size"], cfg["chunk_size"],
                              interpret=False)
    return attn, rows, per_head


def kernels_in(compiled) -> set:
    from chipbench.run import mosaic_kernel_names
    return mosaic_kernel_names(compiled)


def test_eva_forward_compiles_at_the_cells_shapes(cell, no_persistent_cache):
    attn, rows, per_head = cell
    compiled = jax.jit(attn).lower(rows, rows, rows, per_head,
                                   per_head).compile()
    assert kernels_in(compiled) == {"eva_fwd"}


def test_eva_backward_compiles_at_the_cells_shapes(cell, no_persistent_cache):
    attn, rows, per_head = cell

    def loss(*args):
        return jnp.sum(attn(*args).astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        rows, rows, rows, per_head, per_head).compile()
    assert kernels_in(compiled) == {"eva_fwd", "eva_bwd_dq", "eva_bwd_dkv"}
    assert compiled.memory_analysis().temp_size_in_bytes < 2e9
