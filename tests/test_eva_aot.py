"""The three EVA Pallas kernels, the flash / sliding-window ones (forward,
the one backward kernel, and the pair it falls back to), and
the flash kernels at latent attention's two widths with the whole step
around them, compile for a described TPU v5e at their cells' own shapes (no chip: the
``on-chip-measurement`` guide's third rehearsal, kept as a test; the
topology is described inside a fixture of this file only, which is why the
flash kernels' compiles live here too). What the chip's compiler refuses here costs no chip
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


def flash_backward_compiled(q, k, v, window=None):
    """The gradient of a flash attention call (launch tiles) compiled for
    the described chip; a fresh callable a compile, so that nothing traced
    before is found again."""
    from petastorm_tpu.ops.flash_attn import make_flash_attention
    attn = make_flash_attention(causal=True, interpret=False, window=window)
    return jax.jit(jax.grad(
        lambda q, k, v: jnp.sum(attn(q, k, v).astype(jnp.float32)),
        argnums=(0, 1, 2))).lower(q, k, v).compile()


def assert_the_backward_is_one_kernel(prefix, q, k, v, window, monkeypatch):
    """The call's backward compiles as ``<prefix>_bwd`` under the scoped
    limit reckoned from its shapes, with neither of the pair's kernels in
    it, and keeps no more temporaries in HBM than the pair's program but
    for dk and dv in kernel layout (they leave one call together with dq,
    each to be transposed, where the pair's second call could reuse the
    room of the first's: AOT 839.2 against 805.9 MB at the dense cell's
    call, 1,745.0 against 1,611.0 MB at the latent one's, 235.3 against
    235.4 MB at the sparse one's; a whole step's peak is elsewhere)."""
    from petastorm_tpu.ops import flash_attn
    limit = flash_attn._bwd_vmem_limit(k.shape[1], k.shape[3], v.shape[3],
                                       k.dtype.itemsize, 1024, 1024)
    assert flash_attn._VMEM_LIMIT <= limit <= flash_attn._VMEM_CEILING
    one = flash_backward_compiled(q, k, v, window)
    assert kernels_in(one) == {f"{prefix}_fwd", f"{prefix}_bwd"}
    monkeypatch.setattr(flash_attn, "_bwd_vmem_limit", lambda *a: None)
    pair = flash_backward_compiled(q, k, v, window)
    assert kernels_in(pair) == {f"{prefix}_fwd", f"{prefix}_bwd_dq",
                                f"{prefix}_bwd_dkv"}
    dk_and_dv = (k.size + v.size) * k.dtype.itemsize
    assert (one.memory_analysis().temp_size_in_bytes
            <= pair.memory_analysis().temp_size_in_bytes + dk_and_dv)


# (configuration, traffic, windowed): the dense decoder's call and the sparse
# one's full and windowed layers' (``chipbench/pipelines/token_decoder.py``,
# ``token_moe_decoder.py``: default tiles, the window the configuration's).
FLASH_CALLS = [("mistral7b-v03-d2", "tok4k-b4", False, "flash"),
               ("smallthinker21b-tp4-d4", "tok16k-b2", False, "flash"),
               ("smallthinker21b-tp4-d4", "tok16k-b2", True, "swa")]


@pytest.mark.parametrize("config,traffic,windowed,prefix", FLASH_CALLS)
def test_flash_kernels_compile_at_the_cells_shapes(topo, no_persistent_cache,
                                                   monkeypatch, config,
                                                   traffic, windowed, prefix):
    """The forward and the one backward kernel on their scalar-prefetch
    schedules, at the launch defaults and the calls' VMEM limits; the
    pair, kept for heads that do not fit, beside it."""
    with open(os.path.join(ROOT, "chipbench", "configs",
                           f"{config}.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(ROOT, "chipbench", "traffic",
                           f"{traffic}.json")) as f:
        rows = json.load(f)
    one_chip = SingleDeviceSharding(topo.devices[0])

    def operand(heads):
        return jax.ShapeDtypeStruct(
            (rows["per_chip_batch"], rows["window"], heads, cfg["head_dim"]),
            jnp.bfloat16, sharding=one_chip)

    q, kv = (operand(cfg["num_attention_heads"]),
             operand(cfg["num_key_value_heads"]))
    assert_the_backward_is_one_kernel(
        prefix, q, kv, kv,
        cfg["sliding_window_size"] if windowed else None, monkeypatch)


def test_flash_backward_compiles_for_wide_float32_rows(topo,
                                                       no_persistent_cache,
                                                       monkeypatch):
    """Float32 operands at head 256 need more scoped VMEM at the launch
    tiles than the default 16 MiB holds: the pair's dQ call is what
    ``_VMEM_LIMIT`` is for, the one kernel asks for 56 MiB of its own
    reckoning."""
    one_chip = SingleDeviceSharding(topo.devices[0])
    q = jax.ShapeDtypeStruct((1, 4096, 8, 256), jnp.float32,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 4096, 2, 256), jnp.float32,
                              sharding=one_chip)
    assert_the_backward_is_one_kernel("flash", q, kv, kv, None, monkeypatch)


def test_the_pair_compiles_where_a_head_does_not_fit(topo,
                                                     no_persistent_cache):
    """Float32 at head 256 over 16,384 positions: 96 MiB do not hold the
    head's dK and dV, the reckoning says so and the backward is the pair
    at tile residency, as before."""
    from petastorm_tpu.ops import flash_attn
    assert flash_attn._bwd_vmem_limit(16384, 256, 256, 4, 1024, 1024) is None
    one_chip = SingleDeviceSharding(topo.devices[0])
    q = jax.ShapeDtypeStruct((1, 16384, 2, 256), jnp.float32,
                             sharding=one_chip)
    compiled = flash_backward_compiled(q, q, q)
    assert kernels_in(compiled) == {"flash_fwd", "flash_bwd_dq",
                                    "flash_bwd_dkv"}


def latent_cell():
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "kanana2-30b-a3b-ep8-d6.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(ROOT, "chipbench", "traffic",
                           "tok16k-b1.json")) as f:
        return cfg, json.load(f)


def test_flash_kernels_compile_at_latent_attentions_widths(
        topo, no_persistent_cache, monkeypatch):
    """``kanana2-tok16k-1chip``'s call: scores over 192 columns (one and a
    half lane tiles), values, output and their gradients over 128, 32
    heads, nothing padded in HBM; the backward one kernel with 25 MB of
    float32 dK + dV resident, under 72 MiB."""
    from petastorm_tpu.ops.flash_attn import make_flash_attention
    cfg, rows = latent_cell()
    one_chip = SingleDeviceSharding(topo.devices[0])

    def operand(width):
        return jax.ShapeDtypeStruct(
            (rows["per_chip_batch"], rows["window"],
             cfg["num_attention_heads"], width), jnp.bfloat16,
            sharding=one_chip)

    attn = make_flash_attention(causal=True, interpret=False)
    qk, v = operand(cfg["qk_head_dim"]), operand(cfg["v_head_dim"])
    assert (qk.shape[-1], v.shape[-1]) == (192, 128)
    fwd = jax.jit(attn).lower(qk, qk, v).compile()
    assert fwd.output_shardings is not None and kernels_in(fwd) == {
        "flash_fwd"}
    assert_the_backward_is_one_kernel("flash", qk, qk, v, None, monkeypatch)


def test_split_flash_kernels_compile_at_latent_attentions_widths(
        topo, no_persistent_cache, monkeypatch):
    """``kanana2-tok16k-1chip``'s call in the split form the step hands
    over: q of 32 heads of 128 + 64, k of 32 heads of 128 beside ONE
    rotary head of 64, values of 128; the backward one kernel (the rotary
    key's dK summed over the heads in its scratch) under the limit
    reckoned from the parts' lanes, and the pair beside it."""
    from petastorm_tpu.ops import flash_attn
    cfg, rows = latent_cell()
    one_chip = SingleDeviceSharding(topo.devices[0])

    def operand(heads, width):
        return jax.ShapeDtypeStruct(
            (rows["per_chip_batch"], rows["window"], heads, width),
            jnp.bfloat16, sharding=one_chip)

    heads = cfg["num_attention_heads"]
    nope, rot = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    q = (operand(heads, nope), operand(heads, rot))
    k = (operand(heads, nope), operand(1, rot))
    v = operand(heads, cfg["v_head_dim"])
    assert flash_attn._bwd_vmem_limit(
        rows["window"], (nope, rot), v.shape[-1], 2, 1024, 1024) == \
        flash_attn._bwd_vmem_limit(rows["window"], nope + rot, v.shape[-1],
                                   2, 1024, 1024)
    one = flash_backward_compiled(q, k, v)
    assert kernels_in(one) == {"flash_fwd", "flash_bwd"}
    monkeypatch.setattr(flash_attn, "_bwd_vmem_limit", lambda *a: None)
    pair = flash_backward_compiled(q, k, v)
    assert kernels_in(pair) == {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"}


def test_the_latent_cells_whole_step_compiles_and_fits(topo,
                                                       no_persistent_cache):
    """The donated AdamW step of ``kanana2-tok16k-1chip`` as its pipeline
    builds it (six layers, 687.5M parameters, one 16,384-token window,
    ``remat_layers``, ``xent_chunk`` 2048), for one described chip: the
    compiler's peak (arguments + results - aliased + temporaries + program
    text) is under the chip's 16.9 GB with room."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from chipbench.pipelines.token_mla_moe_decoder import llama_config
    from petastorm_tpu.models import llama
    from petastorm_tpu.ops.flash_attn import make_flash_attention
    cfg, rows = latent_cell()
    mesh = Mesh(np.array(topo.devices[:1]), ("data",))
    replicated = NamedSharding(mesh, P())
    attn = jax.shard_map(make_flash_attention(causal=True, interpret=False),
                         mesh=mesh, in_specs=(P("data"),) * 3,
                         out_specs=P("data"), check_vma=False)
    attn.supports_gqa = True
    lcfg = llama_config(cfg)
    init_opt, step = llama.make_train_step(
        lcfg, learning_rate=cfg["optimizer"]["learning_rate"], shift="roll",
        attn_fn=attn, xent_chunk=rows["xent_chunk"],
        remat_layers=rows["remat_layers"], with_stats=True)
    params = jax.eval_shape(lambda key: llama.init_params(key, lcfg),
                            jax.random.PRNGKey(0))
    opt_state = jax.eval_shape(init_opt, params)
    assert sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(
        (params, params, opt_state))) - 4 == cfg["state_bytes"]  # + a count

    def described(tree, sharding):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=sharding), tree)

    tokens = jax.ShapeDtypeStruct(
        (rows["per_chip_batch"], rows["window"]), jnp.int32,
        sharding=NamedSharding(mesh, P("data")))
    compiled = jax.jit(
        lambda p, o, t: step(p, o, {"tokens": t}),
        donate_argnums=(0, 1)).lower(
            described(params, replicated),
            described(opt_state, replicated), tokens).compile()
    # (the grouped expert products are Mosaic calls of XLA's own, unnamed)
    assert kernels_in(compiled) >= {"flash_fwd", "flash_bwd"}
    assert not {"flash_bwd_dq", "flash_bwd_dkv"} & kernels_in(compiled)
    m = compiled.memory_analysis()
    peak = (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes
            + m.generated_code_size_in_bytes)
    assert cfg["state_bytes"] < peak < 16.2e9
    # The operands reach the kernels in their split form: no q or k of
    # 128 + 64 columns, and the rotary key one head.
    text = compiled.as_text()
    assert "bf16[1,32,16384,192]" not in text
    assert "bf16[1,16384,32,192]" not in text
    assert "bf16[1,1,16384,64]" in text
