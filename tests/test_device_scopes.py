"""The device-side vocabulary (``petastorm_tpu/device_scopes.py``): the
``(scope, phase)`` rule on the ``op_name`` forms JAX writes; that the
scopes tile the compiled train steps of the four decoder kinds and the
ResNet at their cells' toy sizes, with and without rematerialization; and
that a named scope changes nothing but metadata: the optimised programs
are text-equal with ``jax.named_scope`` patched away."""
import ast
import contextlib
import importlib
import os
import re

import jax
import pytest

from petastorm_tpu import device_scopes
from petastorm_tpu.device_scopes import classify

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELLS = ("mistral7b-tok4k-1chip", "smallthinker21b-tok16k-1chip",
         "evabyte-byte16k-1chip", "kanana2-tok16k-1chip",
         "rn50-jpeg224-1chip")

INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?([^\s=]+) = (\S+) ([a-z\-]+)\(")
OP_NAME = re.compile(r'op_name="([^"]*)"')
# An instruction's metadata, and the module's tables of the source
# locations that the metadata points into.
METADATA = re.compile(
    r", metadata=\{[^}]*\}|^(?:FileNames|FunctionNames|FileLocations|"
    r"StackFrames)\n(?:\d+ .*\n)*", re.MULTILINE)
# What may carry an op_name outside every scope: a computation's
# parameters (the step's arguments are named after their pytree path),
# constants, the copies and conversions the compiler puts at a call's
# boundary, and the bodies of applied computations (a reduction's ``add`` is
# named ``reduce_sum``: no name stack, never an event of its own).
MOVES = {"parameter", "constant", "copy", "convert", "bitcast", "transpose",
         "tuple", "get-tuple-element"}
# The benchmark's own jitted wrapper around the program's step: the image
# cell's uint8 -> float32 / 255, the byte cell's widening and + 64.
WRAPPER = re.compile(r"^jit\([^)]*\)/(convert_element_type|div|add)$")


@pytest.mark.parametrize("op_name,expected", [
    # as value_and_grad over a jax.checkpoint'ed block writes them
    ("jit(step)/jvp(petastorm_tpu.ffn)/dot_general", ("ffn", "fwd")),
    ("jit(step)/transpose(jvp(jvp()))/checkpoint/petastorm_tpu.ffn/"
     "dot_general", ("ffn", "bwd")),
    ("jit(step)/transpose(jvp(jvp()))/checkpoint/rematted_computation/"
     "petastorm_tpu.ffn/sub", ("ffn", "remat")),
    # remat before bwd: a recomputation sits inside the transposed pass
    ("jit(step)/transpose(jvp(petastorm_tpu.block))/rematted_computation/"
     "petastorm_tpu.attn_qkv/mul", ("attn_qkv", "remat")),
    # the innermost scope wins
    ("jit(step)/jvp(petastorm_tpu.block/petastorm_tpu.attn_eva/"
     "petastorm_tpu.eva_prep)/reduce_sum", ("eva_prep", "fwd")),
    ("jit(f)/jvp(petastorm_tpu.attn_qkv)/petastorm_tpu.mla_latent/concat",
     ("mla_latent", "fwd")),
    ("jit(f)/transpose(jvp(petastorm_tpu.ffn/petastorm_tpu.moe_experts/"
     "petastorm_tpu.moe_rows_in))/scatter-add", ("moe_rows_in", "bwd")),
    # the optimizer's phase is its scope's
    ("jit(step)/petastorm_tpu.optimizer/add", ("optimizer", "update")),
    ("jit(step)/jvp(petastorm_tpu.stage2)/conv_general_dilated",
     ("stage2", "fwd")),
    # a Pallas call's event: the kernel's name is no scope
    ("jit(f)/transpose(jvp(petastorm_tpu.attn_full))/shard_map/"
     "pallas_call[name=flash_bwd]", ("attn_full", "bwd")),
    # no scope, or a name that is not in the vocabulary: None, with a phase
    ("jit(step)/transpose(jvp(jvp()))/remat2", (None, "bwd")),
    ("jit(step)/convert_element_type", (None, "fwd")),
    ("jit(step)/petastorm_tpu.worker_decode/add", (None, "fwd")),
    ("", (None, "fwd")),
])
def test_classify_reads_scope_and_phase(op_name, expected):
    assert classify(op_name) == expected


def test_the_vocabulary_is_spelled_in_one_place():
    """No literal scope name under ``models/`` or ``ops/``; the module is
    a leaf (strings and ``re`` alone: no JAX, no telemetry package)."""
    literal = re.compile(r'"petastorm_tpu\.')
    for sub in ("models", "ops"):
        folder = os.path.join(ROOT, "petastorm_tpu", sub)
        for name in sorted(os.listdir(folder)):
            if name.endswith(".py"):
                with open(os.path.join(folder, name)) as f:
                    assert not literal.search(f.read()), name
    with open(device_scopes.__file__) as f:
        tree = ast.parse(f.read())
    imported = {a.name for node in ast.walk(tree)
                if isinstance(node, ast.Import) for a in node.names} | {
        node.module for node in ast.walk(tree)
        if isinstance(node, ast.ImportFrom)}
    assert imported == {"__future__", "re"}
    names = device_scopes.DEVICE_SCOPES
    assert len(set(names)) == len(names) and "unscoped" not in names
    assert all(re.fullmatch(r"[a-z0-9_]+", n) for n in names)


def compiled_step_text(cell: str, store_dir, remat=None) -> str:
    """The optimised text of ``cell``'s jitted train step at its rehearsal
    (toy) sizes on the CPU backend, built by the cell's own pipeline;
    ``remat`` overrides the traffic's ``remat_layers``."""
    from chipbench import run
    _, _, config, traffic = run.load_cell(cell, rehearsal=True)
    if remat is not None:
        traffic = {**traffic, "remat_layers": remat}
    pipeline = importlib.import_module(
        f"chipbench.pipelines.{config['pipeline']}")
    job = pipeline.Job(config, traffic, jax.devices()[:1], 7,
                       str(store_dir / cell))
    job.write_store()
    job.start()
    try:
        return job.compile(job.next_batch()).as_text()
    finally:
        job.free()


def resnet_step_text(remat: bool) -> str:
    """The ResNet step with ``remat`` (no cell sets it)."""
    import jax.numpy as jnp
    from petastorm_tpu.models import resnet
    params = jax.eval_shape(lambda k: resnet.init_params(k, 10),
                            jax.random.PRNGKey(0))
    batch = {"image": jax.ShapeDtypeStruct((2, 32, 32, 3), jnp.float32),
             "label": jax.ShapeDtypeStruct((2,), jnp.int32)}
    return jax.jit(resnet.make_train_step(remat=remat)).lower(
        params, params, batch).compile().as_text()


def outside_every_scope(text: str) -> list:
    """``(opcode, op_name)`` of the instructions that carry an ``op_name``
    and classify to no scope, moves and the benchmark's wrapper aside."""
    left = []
    for row in text.splitlines():
        head, op_name = INSTRUCTION.match(row), OP_NAME.search(row)
        if not head or not op_name or classify(op_name.group(1))[0]:
            continue
        if head.group(3) in MOVES or WRAPPER.match(op_name.group(1)):
            continue
        if not op_name.group(1).startswith("jit("):
            continue    # a reduction's or a scatter's applied computation
        left.append((head.group(3), op_name.group(1)))
    return left


def program(text: str) -> str:
    """The optimised program without its metadata, its values renamed in
    the order they first appear (the CPU compiler numbers the instructions
    its parallel passes add in the order they finish)."""
    seen = {}
    return re.sub(r"%[\w.\-]+",
                  lambda m: seen.setdefault(m.group(0), f"%v{len(seen)}"),
                  METADATA.sub("", text))


def phases_in(text: str) -> set:
    return {classify(m.group(1))[1] for m in OP_NAME.finditer(text)}


# What each decoder kind carries beside the scopes every decoder has.
ITS_OWN = {
    CELLS[0]: {"attn_full"},
    CELLS[1]: {"attn_full", "attn_window", "moe_route", "moe_experts",
               "moe_rows_in", "moe_rows_back"},
    CELLS[2]: {"attn_eva", "eva_prep"},
    CELLS[3]: {"attn_full", "mla_latent", "moe_route", "moe_experts",
               "moe_rows_in", "moe_rows_back", "moe_shared"}}


@pytest.mark.parametrize("remat", [True, False], ids=["remat", "no-remat"])
@pytest.mark.parametrize("cell", CELLS[:4])
def test_the_scopes_tile_a_decoder_step(cell, remat, tmp_path):
    text = compiled_step_text(cell, tmp_path, remat)
    assert outside_every_scope(text) == []
    want = {"fwd", "bwd", "update"} | ({"remat"} if remat else set())
    assert phases_in(text) == want
    scoped = {classify(m.group(1))[0] for m in OP_NAME.finditer(text)}
    assert {"embed", "attn_qkv", "attn_out", "ffn", "loss_head",
            "optimizer"} | ITS_OWN[cell] <= scoped
    assert scoped - {None} <= set(device_scopes.DEVICE_SCOPES)


@pytest.mark.parametrize("remat", [True, False], ids=["remat", "no-remat"])
def test_the_scopes_tile_the_resnet_step(remat):
    text = resnet_step_text(remat)
    assert outside_every_scope(text) == []
    scoped = {classify(m.group(1))[0] for m in OP_NAME.finditer(text)}
    assert scoped - {None} == {"stem", "stage0", "stage1", "stage2",
                               "stage3", "head", "optimizer"}
    assert ("remat" in phases_in(text)) is remat


@pytest.mark.parametrize("cell", CELLS)
def test_a_named_scope_changes_nothing_but_metadata(cell, tmp_path,
                                                    monkeypatch):
    """The optimised program with ``metadata={...}`` stripped is text-equal
    with the scopes and with ``jax.named_scope`` a no-op: what the chip
    runs is the parent's program."""
    with_scopes = compiled_step_text(cell, tmp_path)
    assert "petastorm_tpu." in with_scopes
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    without = compiled_step_text(cell, tmp_path)
    assert "petastorm_tpu." not in without
    assert program(with_scopes) == program(without)
