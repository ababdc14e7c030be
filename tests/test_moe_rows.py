"""``ops/moe_rows.py``: the expert buffer's rows moved by DMA. Both kernels
(Pallas interpret mode) against the XLA forms they replace in
``models/llama.py``, on a short and a full buffer; the ``custom_vjp``
pairing's gradients; a toy expert step with and without the kernels; and
the lowering guard: each kernel's body stays small when lowered for the
TPU, whatever the row tile, and a step lowers each (kernel, shape) once;
and each call site's kernel instructions booked under its scope and
phase."""
import json
import os
import re
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from petastorm_tpu.models import llama
from petastorm_tpu.ops import moe_rows

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

N_TOK, K = 64, 3


def toy_sizes(**over):
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "smallthinker21b-tp4-d4.json")) as f:
        sizes = json.load(f)
    return {**sizes, **sizes["rehearsal"], **over}


def program_config(sizes):
    from chipbench.pipelines.token_moe_decoder import llama_config
    return llama_config(sizes)


def kernels_off(monkeypatch):
    """``models/llama.py`` with the kernels off: XLA's gather and
    scatter-add, as where the shapes do not tile."""
    monkeypatch.setattr(moe_rows, "gather_tile", lambda *a: None)
    monkeypatch.setattr(moe_rows, "sum_tile", lambda *a: None)


def buffer(n_buf: int, d: int, dtype, seed: int = 0):
    """``(x, head, rows)``: token rows, the first ``n_buf`` rows of a
    permutation of the ``N_TOK x K`` assignments, and buffer rows whose
    tail (past a held count of two thirds) is zeros, as the layer's are."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(keys[0], (N_TOK, d)).astype(dtype)
    head = jax.random.permutation(keys[1], N_TOK * K)[:n_buf]
    rows = jax.random.normal(keys[2], (n_buf, d))
    held = (jnp.arange(n_buf) < 2 * n_buf // 3)[:, None]
    return x, head, jnp.where(held, rows, 0).astype(dtype)


@pytest.mark.parametrize("dtype,d", [(jnp.float32, 128),
                                     (jnp.bfloat16, 256)],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("n_buf", [96, N_TOK * K], ids=["short", "full"])
def test_the_row_gather_is_xlas_gather(dtype, d, n_buf):
    """Every row with every row live, and with a live count the rows
    before it (the rest are the caller's to mask)."""
    x, head, _ = buffer(n_buf, d, dtype)
    tile = moe_rows.gather_tile(n_buf, N_TOK, d, dtype)
    assert tile is not None
    want = np.asarray(x[head // K], np.float32)
    got = moe_rows.gather_rows(x, head // K, n_buf, tile=tile)
    np.testing.assert_array_equal(np.asarray(got, np.float32), want)
    live = 2 * n_buf // 3 - 5
    got = moe_rows.gather_rows(x, head // K, live, tile=tile)
    np.testing.assert_array_equal(np.asarray(got, np.float32)[:live],
                                  want[:live])


@pytest.mark.parametrize("dtype,d", [(jnp.float32, 128),
                                     (jnp.bfloat16, 256)],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("n_buf", [96, 192 - 8, N_TOK * K],
                         ids=["short", "nearly_full", "full"])
def test_the_gather_and_sum_is_the_scatter_add(dtype, d, n_buf):
    """Every token's float32 sum over the buffer rows of its assignments,
    rounded once: bit-equal in bfloat16 (the k rows are added in the same
    order here), within float32 rounding in float32. A token none of whose
    assignments is in the buffer gets zeros; with a live count the zero
    rows past it are not read and the sums do not change."""
    _, head, rows = buffer(n_buf, d, dtype, seed=1)
    tile = moe_rows.sum_tile(N_TOK, n_buf, d, K, dtype)
    want = np.asarray(jax.ops.segment_sum(
        rows.astype(jnp.float32), head // K, num_segments=N_TOK).astype(dtype),
        np.float32)
    for live in (n_buf, 2 * n_buf // 3):
        got = np.asarray(moe_rows.gather_sum(rows, head, live, K, N_TOK,
                                             tile=tile), np.float32)
        np.testing.assert_allclose(got, want, rtol=0,
                                   atol=0 if dtype == jnp.bfloat16 else 1e-6)
    missing = np.setdiff1d(np.arange(N_TOK), np.asarray(head) // K)
    assert not got[missing].any()


@pytest.mark.parametrize("n_buf", [96, N_TOK * K], ids=["short", "full"])
def test_llamas_row_helpers_take_the_kernels(n_buf, monkeypatch):
    """``_buffer_rows`` / ``_token_sums`` on kernel-sized rows give what
    their XLA forms give; the full buffer's token sums stay XLA's gather
    through the whole permutation's inverse."""
    x, head, rows = buffer(n_buf, 128, jnp.float32, seed=2)
    live = jnp.int32(n_buf)
    got = (llama._buffer_rows(x, head, K, live),
           llama._token_sums(rows, head, K, N_TOK, live))
    text = str(jax.make_jaxpr(lambda x, r: (
        llama._buffer_rows(x, head, K, live),
        llama._token_sums(r, head, K, N_TOK, live)))(x, rows))
    assert "moe_gather_rows" in text
    assert ("moe_gather_sum" in text) == (n_buf < N_TOK * K)
    kernels_off(monkeypatch)
    want = (llama._buffer_rows(x, head, K, live),
            llama._token_sums(rows, head, K, N_TOK, live))
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_allclose(got[1], want[1], rtol=0, atol=1e-6)


def test_the_custom_vjp_pairs_are_each_others_transpose():
    """``_to_buffer`` and ``_from_buffer`` with the kernels: values and
    cotangents against ``jax.vjp`` of the plain XLA forms."""
    x, head, rows = buffer(96, 128, jnp.float32, seed=3)
    g_rows = jax.random.normal(jax.random.PRNGKey(7), rows.shape)
    g_tok = jax.random.normal(jax.random.PRNGKey(8), x.shape)

    def pulls():
        live = jnp.int32(rows.shape[0])
        to = jax.vjp(lambda x: llama._to_buffer(x, head, live, K), x)
        back = jax.vjp(lambda r: llama._from_buffer(r, head, live, K, N_TOK),
                       rows)
        return to[0], to[1](g_rows)[0], back[0], back[1](g_tok)[0]

    got = pulls()
    plain = (jax.vjp(lambda x: x[head // K], x),
             jax.vjp(lambda r: jax.ops.segment_sum(r, head // K,
                                                   num_segments=N_TOK), rows))
    want = (plain[0][0], plain[0][1](g_rows)[0], plain[1][0],
            plain[1][1](g_tok)[0])
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-5)


def loss_and_grads(cfg, params, tokens):
    return jax.jit(jax.value_and_grad(partial(
        llama.loss_fn, cfg=cfg, shift="roll", xent_chunk=64,
        remat_layers=True, compute_dtype=jnp.float32, with_stats=True),
        has_aux=True))(params, {"tokens": tokens})


@pytest.fixture
def short_tile(monkeypatch):
    """A row tile of 16 leaves the toy buffer short (48 of 256 rows a
    layer-step): the ``cond`` between the short and the full buffer."""
    monkeypatch.setattr(llama, "_ROW_TILE", 16)


def test_a_toy_expert_step_is_the_same_with_the_kernels(short_tile,
                                                        monkeypatch):
    """Two expert layers at width 128, float32: loss and every gradient
    leaf with the kernels are XLA's within float32 rounding."""
    cfg = program_config(toy_sizes(hidden_size=128, num_hidden_layers=2))
    params = llama.init_params(jax.random.PRNGKey(3), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(5), (2, 64), 0,
                                cfg.vocab, jnp.int32)
    with jax.default_matmul_precision("highest"):
        (loss, stats), grads = loss_and_grads(cfg, params, tokens)
        assert stats["short_buffer"].tolist() == [1, 1]
        kernels_off(monkeypatch)
        (want, _), want_grads = loss_and_grads(cfg, params, tokens)
    assert abs(float(loss) - float(want)) <= 1e-6 * abs(float(want))
    for got, ref in zip(jax.tree.leaves(grads), jax.tree.leaves(want_grads)):
        scale = float(jnp.abs(ref).max()) + 1e-30
        assert float(jnp.abs(got - ref).max()) <= 1e-5 * scale


def test_the_full_buffer_branch_takes_the_kernels(short_tile, monkeypatch):
    """Every assignment held (the full buffer, in the same step as the
    short one's ``cond``): the block's value and gradient with the
    kernels are XLA's within float32 rounding (``test_llama_kinds.py``
    holds XLA's to the plain reference)."""
    sizes = toy_sizes(hidden_size=128, num_hidden_layers=1)
    cfg = program_config(sizes)
    layer = llama.init_params(jax.random.PRNGKey(1), cfg)["layers"][0]
    first, held = cfg.experts_held
    layer["router"] = layer["router"].at[0].set(
        jnp.where((jnp.arange(cfg.n_router_outputs) >= first)
                  & (jnp.arange(cfg.n_router_outputs) < first + held),
                  100.0, 0.0))
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 128, cfg.dim))
    x = x.at[..., 0].set(10.0)
    h = jax.random.normal(jax.random.PRNGKey(4), (1, 128, cfg.dim))
    g = jax.random.normal(jax.random.PRNGKey(8), (128, cfg.dim))

    def mine(h):
        out, stats = llama._dropless_moe_block(x, h, layer, cfg)
        return jnp.sum(out[0] * g), stats

    def close(a, b, tol):
        scale = float(jnp.abs(b).max())
        assert float(jnp.abs(a - b).max()) <= tol * scale

    with jax.default_matmul_precision("highest"):
        text = str(jax.make_jaxpr(jax.grad(lambda h: mine(h)[0]))(h))
        (value, stats), grad = jax.jit(jax.value_and_grad(
            mine, has_aux=True))(h)
        kernels_off(monkeypatch)
        (xla, _), xla_grad = jax.jit(jax.value_and_grad(
            mine, has_aux=True))(h)
    assert " cond[" in text and "moe_gather_rows" in text
    assert (int(stats["short_buffer"]), int(stats["rows_buffer"])) == (0, 256)
    close(value, xla, 1e-6)
    close(grad, xla_grad, 1e-6)


def subjaxprs(eqn):
    """The jaxprs an equation holds in its parameters (loop and branch
    bodies, a kernel's body)."""
    from jax.extend.core import ClosedJaxpr, Jaxpr
    for value in eqn.params.values():
        for item in value if isinstance(value, (tuple, list)) else (value,):
            if isinstance(item, ClosedJaxpr):
                yield item.jaxpr
            elif isinstance(item, Jaxpr):
                yield item


def kernel_bodies(fn, *args) -> dict:
    """``{kernel name: [body jaxpr of each pallas_call]}`` of ``fn``."""
    found = {}

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                name = eqn.params["name"]
                found.setdefault(name, []).append(eqn.params["jaxpr"])
            else:
                for sub in subjaxprs(eqn):
                    walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


def count(jaxpr, primitive: str) -> int:
    return sum((eqn.primitive.name == primitive)
               + sum(count(sub, primitive) for sub in subjaxprs(eqn))
               for eqn in jaxpr.eqns)


# Bytes of a lowered kernel's custom call at the toy step's shapes (the
# Mosaic module, serialized): 11-12 KB with the per-row loops as loops;
# with the gather's row loop written out in Python 31-101 KB, and more at
# a larger tile.
BODY_CEILING = 20 << 10


def toy_step_grads(layers: int):
    """The toy expert step's gradient (bfloat16, width 256: the kernels'
    shapes) and its abstract arguments; with a row tile of 16 the buffer
    is short, so each layer holds the ``cond`` of both buffers."""
    cfg = program_config(toy_sizes(hidden_size=256, num_hidden_layers=layers))
    params = jax.eval_shape(lambda: llama.init_params(jax.random.PRNGKey(0),
                                                      cfg))
    tokens = jax.ShapeDtypeStruct((2, 128), jnp.int32)

    def grads(p, t):
        return jax.grad(lambda p: llama.loss_fn(
            p, {"tokens": t}, cfg=cfg, shift="roll", xent_chunk=64,
            remat_layers=True, compute_dtype=jnp.bfloat16,
            with_stats=True)[0])(p)

    return grads, params, tokens


def test_each_kernel_lowers_small_and_once_per_shape(monkeypatch):
    """The lowering guard. A four-layer toy expert step (bfloat16, width
    256, the short buffer's ``cond``), its gradient lowered for the TPU on
    the CPU: each row kernel's lowered call stays under
    :data:`BODY_CEILING` bytes, the per-row copy loops are loops (no more
    copies started in a body than one unrolled trip and a remainder's),
    and the module holds a few kernel calls, not one a site."""
    monkeypatch.setattr(llama, "_ROW_TILE", 16)
    grads, params, tokens = toy_step_grads(4)
    bodies = kernel_bodies(grads, params, tokens)
    assert set(bodies) == {"moe_gather_rows", "moe_gather_sum"}
    for name, found in bodies.items():
        for body in found:
            # One unrolled trip, and the trip of a count's remainder.
            assert count(body, "dma_start") <= moe_rows._UNROLL + 1, name
    monkeypatch.setattr(moe_rows, "_resolve_interpret", lambda i: False)
    # A new function: the jaxpr above was traced for the interpreter.
    text = jax.jit(lambda p, t: grads(p, t)).trace(params, tokens).lower(
        lowering_platforms=("tpu",)).as_text()
    calls = re.findall(r"stablehlo\.custom_call @tpu_custom_call\(.*", text)
    sites = sum(len(found) for found in bodies.values())
    assert sites >= 4 * 4             # per layer: in, back and transposes
    assert 2 <= len(calls) <= 8 < sites
    assert max(map(len, calls)) <= BODY_CEILING


OP_NAME = re.compile(r'op_name="([^"]*)"')
LAUNCHER = re.compile(r"jit\(_(gather|sum)_call\)")


def test_each_kernel_call_is_booked_under_its_sites_scope_and_phase(
        monkeypatch):
    """A launcher is lowered once a module, without its callers' name
    stack; XLA's inliner puts each call site's ``op_name`` in front of the
    body it copies there. So in the compiled step (CPU, the interpreter's
    instructions standing for the kernel) every instruction of a launcher
    classifies to the scope of its site, ``moe_rows_in`` or
    ``moe_rows_back``, and to ``bwd`` under a transpose, else ``fwd``: what
    ``moe_rows_ms_per_step`` and the phase metrics read off the chip's
    trace. Both kernels run in both phases and both scopes."""
    from petastorm_tpu.device_scopes import classify
    monkeypatch.setattr(llama, "_ROW_TILE", 16)
    grads, params, tokens = toy_step_grads(1)
    text = jax.jit(grads).lower(params, tokens).compile().as_text()
    booked = set()
    for name in OP_NAME.findall(text):
        launcher = LAUNCHER.search(name)
        if launcher is None:
            continue
        scope, phase = classify(name)
        assert scope in ("moe_rows_in", "moe_rows_back"), name
        site = name[:launcher.start()]
        assert phase == ("bwd" if "transpose(" in site else "fwd"), name
        booked.add((launcher.group(1), scope, phase))
    assert {(kernel, phase) for kernel, _, phase in booked} == {
        (kernel, phase) for kernel in ("gather", "sum")
        for phase in ("fwd", "bwd")}
    assert {scope for _, scope, _ in booked} == {"moe_rows_in",
                                                 "moe_rows_back"}
