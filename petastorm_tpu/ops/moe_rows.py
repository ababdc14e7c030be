"""The dropless expert layer's rows moved by DMA: two Pallas TPU kernels
for ``models/llama.py``'s expert buffer, each the other's transpose.

* :func:`gather_rows` (``moe_gather_rows``): ``out[r] = x[src[r]]``. A grid
  step owns a tile of output rows: it starts one ``make_async_copy`` a row
  from ``x`` (left in HBM, ``memory_space=pl.ANY``) into a VMEM stage, waits
  for them all, and writes the stage out as its dense output block.
* :func:`gather_sum` (``moe_gather_sum``): each token's sum over the buffer
  rows of its ``k`` assignments (``head[r] // k == t``). The buffer rows
  are sorted by assignment (one ``jax.lax.sort`` of the buffer's indices),
  so a tile of tokens owns one run of them: a grid step copies each row of
  its run into the stage slot of its assignment, then adds a token's ``k``
  slots in float32 in the order ``j = 0 .. k - 1`` (a slot with no row is
  zeros) and rounds the sum once to the rows' dtype. It is the scatter-add
  of ``jax.ops.segment_sum`` done as a gather: no two grid steps write one
  row.

**A row where XLA keeps it.** XLA lays a TPU array out in tiles of 8 rows
x 128 columns, 16-bit rows packed in pairs into 32-bit words, and Mosaic
slices a tiled dimension only at whole tiles. So the sources go in as a
view that is the same bytes with the tiles spelled out, ``(n / 8, d / 128,
8 / p, p, 128)`` for ``p`` rows a word (:func:`_view`; XLA makes it a
bitcast, no copy): a row, or the pair of 16-bit rows its words hold, is a
slice of untiled dimensions. One copy moves a row's ``d / 128`` lane tiles
(a 16-bit row moves with its pair) into a stage of contiguous lines, which
strided loads read back as dense tiles; a 16-bit row's half of each word
is chosen by the parity of its index (:func:`_parity`).

The per-row loops are ``jax.lax.fori_loop``s of :data:`_UNROLL` copies a
trip. A Python loop over a tile's rows lowers to one copy instruction a row
in the kernel's body: at 256 rows a tile that is ~0.4 s of lowering a call
site, paid by every run of a step that holds dozens of them, compile cache
or not (lowered for the TPU on a CPU host; ``tests/test_moe_rows.py``
holds the body's size).

On the ``cpu`` backend both run in Pallas interpret mode (tests); every
other backend compiles them or raises (``flash_attn._resolve_interpret``).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from petastorm_tpu.ops.flash_attn import _resolve_interpret

#: Copies started (or waited for) in one trip of a per-row loop.
_UNROLL = 8
#: Rows (tokens) a grid step takes: the largest of these that divides the
#: count and, for :func:`gather_sum`, whose stage fits :data:`_STAGE_BYTES`.
_ROW_TILES = (256, 128, 64, 32, 16)
_STAGE_BYTES = 4 << 20
_VMEM_LIMIT = 32 << 20
#: The most bytes of row indices a call holds in SMEM (a v5e core has
#: 1 MiB of it): the gather's one index a row, the sum's two.
_SMEM_INDEX_BYTES = 800 << 10


def _per_word(dtype) -> int | None:
    """Rows of ``dtype`` that one 32-bit word of XLA's tiles holds: 1 for
    32-bit types, 2 for 16-bit ones; None for others."""
    return {4: 1, 2: 2}.get(jnp.dtype(dtype).itemsize)


def _tileable(n_in: int, d: int, dtype) -> bool:
    return _per_word(dtype) is not None and d % 128 == 0 and n_in % 8 == 0


def gather_tile(n_out: int, n_in: int, d: int, dtype) -> int | None:
    """Rows a grid step of :func:`gather_rows` copies from ``n_in`` rows of
    ``d`` columns of ``dtype`` into ``n_out``; None where the shapes do not
    tile onto the hardware (a width that is not whole 128-lane tiles,
    ``n_in`` not whole 8-row tiles, a dtype of another width, indices past
    :data:`_SMEM_INDEX_BYTES`, or no tile that divides ``n_out``)."""
    if not _tileable(n_in, d, dtype) or 4 * n_out > _SMEM_INDEX_BYTES:
        return None
    return next((t for t in _ROW_TILES if n_out % t == 0), None)


def sum_tile(n_tok: int, n_rows: int, d: int, k: int, dtype) -> int | None:
    """Tokens a grid step of :func:`gather_sum` sums from ``n_rows`` buffer
    rows, the stage of their ``k`` rows each (a 16-bit row with its pair)
    within :data:`_STAGE_BYTES`; None as :func:`gather_tile`."""
    if not _tileable(n_rows, d, dtype) or 8 * n_rows > _SMEM_INDEX_BYTES:
        return None
    return next((t for t in _ROW_TILES
                 if n_tok % t == 0 and k * t * d * 4 <= _STAGE_BYTES), None)


def _view(x):
    """``x`` (n, d) as ``(n / 8, d / 128, 8 / p, p, 128)``: XLA's tiles of
    8 rows x 128 columns, ``p`` rows a 32-bit word, made dimensions. The
    same bytes in the same order, so XLA passes it as a bitcast."""
    n, d = x.shape
    p = _per_word(x.dtype)
    return x.reshape(n // 8, 8 // p, p, d // 128, 128).transpose(0, 3, 1, 2,
                                                                  4)


def _loop(n, body, carry=0, unroll: int = 1):
    """``carry = body(i, carry)`` for ``i < n`` (``n`` may be traced),
    ``unroll`` calls a loop trip and the rest one a trip."""
    def trip(step, carry):
        first = jax.lax.mul(step, unroll)
        for u in range(unroll):
            carry = body(jax.lax.add(first, u) if u else first, carry)
        return carry

    carry = jax.lax.fori_loop(0, n // unroll, trip, carry)
    if unroll == 1 or (isinstance(n, int) and n % unroll == 0):
        return carry
    return jax.lax.fori_loop(n // unroll * unroll, n, body, carry)


def _copy(src_hbm, stage, sem, at, row):
    """The copy of source row ``row`` (a 16-bit one with its pair) into
    stage row ``at``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    tile = jax.lax.shift_right_logical(row, 3)
    sub = jax.lax.bitwise_and(row, 7)
    if stage.shape[3] == 2:
        sub = jax.lax.shift_right_logical(sub, 1)
    return pltpu.make_async_copy(src_hbm.at[pl.ds(tile, 1), :, pl.ds(sub, 1)],
                                 stage.at[pl.ds(at, 1)], sem)


def _lines(stage):
    """The stage as ``(rows x d / 128, 128)`` lines of 32-bit words: row
    ``i``'s lane tile ``c`` is line ``i d / 128 + c``."""
    rows, lanes = stage.shape[:2]
    words = stage.bitcast(jnp.uint32) if stage.shape[3] == 2 else stage
    return words.reshape(rows * lanes, 128)


def _parity(shift_ref, at, row) -> None:
    """``shift_ref[at, :] = 16 * (row & 1)``: the shift that brings 16-bit
    row ``row``'s half of its words to the low bits (the even row of a
    pair is the low half)."""
    from jax.experimental import pallas as pl
    odd = jax.lax.bitwise_and(row, 1)
    shift_ref[pl.ds(at, 1), :] = jnp.full((1, 128), jax.lax.mul(odd, 16),
                                          jnp.int32)


def _values(lines, shift):
    """Float32 values of loaded stage lines: 32-bit rows as they are, a
    16-bit row's half of each word (``shift``, :func:`_parity`)."""
    from jax.experimental.pallas import tpu as pltpu
    if shift is None:
        return lines.astype(jnp.float32)
    low = jax.lax.shift_right_logical(lines, shift.astype(jnp.uint32))
    return pltpu.bitcast(jax.lax.shift_left(low, jnp.uint32(16)),
                         jnp.float32)


def _store(out_ref, c, value) -> None:
    """Lane tile ``c`` of the output block."""
    from jax.experimental import pallas as pl
    lane = pl.multiple_of(jax.lax.mul(c, 128), 128)
    out_ref[:, pl.ds(lane, 128)] = value.astype(out_ref.dtype)


def _gather_kernel(src_ref, live_ref, x_hbm, out_ref, stage, shift, sem):
    from jax.experimental import pallas as pl

    n, lanes, _, per, _ = stage.shape
    base = jax.lax.mul(pl.program_id(0), n)
    live = jnp.clip(jax.lax.sub(live_ref[0], base), 0, n)

    def start(i, carry):
        row = src_ref[jax.lax.add(base, i)]
        _copy(x_hbm, stage, sem, i, row).start()
        if per == 2:
            _parity(shift, i, row)
        return carry

    def wait(i, carry):
        # A wait takes one copy's bytes off the semaphore: any row's
        # descriptor does.
        _copy(x_hbm, stage, sem, 0, 0).wait()  # timeout-ok: DMA started above
        return carry

    _loop(live, start, unroll=_UNROLL)
    _loop(live, wait, unroll=_UNROLL)
    lines = _lines(stage)

    def unpack(c, carry):
        _store(out_ref, c, _values(lines[pl.ds(c, n, stride=lanes), :],
                                   shift[...] if per == 2 else None))
        return carry

    _loop(lanes, unpack)


def _sum_kernel(rows_ref, slots_ref, bounds_ref, rows_hbm, out_ref, stage,
                shift, sem, *, k, interpret):
    from jax.experimental import pallas as pl

    n, lanes, _, per, _ = stage.shape           # assignments of the tile
    i = pl.program_id(0)
    first = bounds_ref[i]
    lines = _lines(stage)
    # A missing row reads as zeros: the stage is cleared first (the
    # interpreter stores through no reshaped ref).
    clear = stage if interpret else lines
    clear[...] = jnp.zeros(clear.shape, clear.dtype)

    def start(e, carry):
        e = jax.lax.add(first, e)
        row, slot = rows_ref[e], slots_ref[e]
        _copy(rows_hbm, stage, sem, slot, row).start()
        if per == 2:
            _parity(shift, slot, row)
        return carry

    def wait(_, carry):
        _copy(rows_hbm, stage, sem, 0, 0).wait()  # timeout-ok: as the gather
        return carry

    held = jax.lax.sub(bounds_ref[jax.lax.add(i, 1)], first)
    _loop(held, start, unroll=_UNROLL)
    _loop(held, wait, unroll=_UNROLL)
    # Assignment a = t k + j of the tile is stage row a: slot j of the
    # tile's tokens is every (k d / 128)-th line from line j d / 128 + c.
    toks = n // k

    def lane_tile(c, carry):
        def add(j, acc):
            line = jax.lax.add(jax.lax.mul(j, lanes), c)
            got = _values(
                lines[pl.ds(line, toks, stride=k * lanes), :],
                shift[pl.ds(j, toks, stride=k), :] if per == 2 else None)
            return jax.lax.add(acc, got)

        _store(out_ref, c, _loop(k, add, jnp.zeros((toks, 128), jnp.float32)))
        return carry

    _loop(lanes, lane_tile)


def _scratch(rows: int, x):
    """The stage of ``rows`` rows of ``x`` (16-bit rows with their pair),
    the parities' shifts, the copies' semaphore."""
    from jax.experimental.pallas import tpu as pltpu
    per = _per_word(x.dtype)
    return [pltpu.VMEM((rows, x.shape[1] // 128, 1, per, 128), x.dtype),
            pltpu.VMEM((rows if per == 2 else 8, 128), jnp.int32),
            pltpu.SemaphoreType.DMA(())]


def _params():
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(dimension_semantics=("parallel",),
                                vmem_limit_bytes=_VMEM_LIMIT)


def gather_rows(x, src, live, *, tile: int):
    """``x[src]`` for ``x`` (n_in, d) and ``src`` (n,) int32 indices into
    its rows, the shapes as :func:`gather_tile` takes them. The rows from
    ``live`` (a traced count) on are not copied and hold anything: the
    caller masks them."""
    return _gather_call(x, src.astype(jnp.int32),
                        jnp.full((1,), live, jnp.int32), tile=tile,
                        interpret=_resolve_interpret(None))


def gather_sum(rows, head, live, k: int, n_tok: int, *, tile: int):
    """The transpose of ``gather_rows(x, head // k, live)``: ``(n_tok, d)``
    in ``rows``' dtype, token ``t``'s float32 sum over the buffer rows
    ``r`` with ``head[r] // k == t``, in the order of ``head[r] % k``. The
    rows from ``live`` (a traced count) on are zeros (the caller's mask)
    and are not read. The shapes as :func:`sum_tile` takes them."""
    at = jnp.arange(head.shape[0], dtype=jnp.int32)
    # Rows past the live count sort past every token.
    key = jnp.where(at < live, head.astype(jnp.int32), n_tok * k)
    return _sum_call(rows, key, at, k=k, n_tok=n_tok, tile=tile,
                     interpret=_resolve_interpret(None))


# Each launcher is jitted: a step holds a dozen call sites an expert layer
# (two ``cond`` branches, forward, recomputation and transposes) of two or
# three distinct shapes, and a jitted function is traced once per shape
# and lowered once per module, not once per site. The sites keep their own
# name stacks: XLA's ``op_name`` of a kernel is its caller's scope and
# phase, then ``jit(_gather_call)``.
@partial(jax.jit, static_argnames=("tile", "interpret"))
def _gather_call(x, src, live, *, tile: int, interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, d = src.shape[0], x.shape[1]
    return pl.pallas_call(
        _gather_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(n // tile,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((tile, d), lambda i, *_: (i, 0)),
            scratch_shapes=_scratch(tile, x)),
        out_shape=jax.ShapeDtypeStruct((n, d), x.dtype),
        compiler_params=_params(),
        interpret=interpret, name="moe_gather_rows",
    )(src, live, _view(x))


@partial(jax.jit, static_argnames=("k", "n_tok", "tile", "interpret"))
def _sum_call(rows, key, at, *, k: int, n_tok: int, tile: int,
              interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    # The buffer rows by assignment: a tile of tokens owns one run of them
    # (between its bounds), each row going to stage slot ``assignment %
    # (tile k)``.
    assignment, order = jax.lax.sort((key, at), num_keys=1)
    span = tile * k
    bounds = jnp.searchsorted(
        assignment, jnp.arange(n_tok // tile + 1, dtype=jnp.int32) * span)
    d = rows.shape[1]
    return pl.pallas_call(
        partial(_sum_kernel, k=k, interpret=interpret),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(n_tok // tile,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((tile, d), lambda i, *_: (i, 0)),
            scratch_shapes=_scratch(span, rows)),
        out_shape=jax.ShapeDtypeStruct((n_tok, d), rows.dtype),
        compiler_params=_params(),
        interpret=interpret, name="moe_gather_sum",
    )(order, jax.lax.rem(assignment, span), bounds.astype(jnp.int32),
      _view(rows))
