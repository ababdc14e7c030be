"""EVA chunked linear attention as Pallas TPU kernels: one softmax over two
key sources, O(seq) memory.

A query at position ``i`` of block ``w = i // window`` sees, in ONE softmax,

* the keys of its own block up to itself (``j // window == w``, ``j <= i``:
  causal, exact), and
* one learned summary per ``chunk`` keys of every EARLIER block (none of
  its own): with the head's ``phi``, ``mu`` in ``R^d``, chunk ``c`` pools its
  rotated keys and its values by ``a_cj = softmax_j(k_j . phi)`` into
  ``kbar_c = sum_j a_cj k_j + mu`` and ``vbar_c = sum_j a_cj v_j``.

So a window of ``n`` blocks scores ``n W (W + 1) / 2`` local pairs and
``W (W / C) n (n - 1) / 2`` summary pairs a head where causal attention
scores ``n W (n W + 1) / 2``.

Design:

* the summaries (:func:`eva_summaries`) are plain ``jax.numpy``: they read
  ``k`` and ``v`` once (memory-bound) and autodiff gives their gradient;
* three kernels under stable names, ``eva_fwd``, ``eva_bwd_dq`` and
  ``eva_bwd_dkv`` (``custom_vjp``). Each walks a STATIC schedule of tile
  pairs, made in numpy from the shapes and handed to the kernel as
  scalar-prefetch arrays (:func:`_q_schedule`, :func:`_kv_schedule`): a
  query tile's causal key tiles of its own block, then the summary tiles of
  the earlier blocks, one running max and sum over both. Tiles above the
  diagonal and summaries of later blocks are not in the schedule: they cost
  no grid step and no DMA, and no ``[S, S]`` or ``[S, S / C]`` score array
  touches HBM. The tile bodies and the causal mask are
  :mod:`petastorm_tpu.ops.flash_attn`'s (one copy of the numerics);
* ``eva_bwd_dkv`` returns ``dk, dv`` of the local keys and ``dkbar,
  dvbar`` of the summaries: its schedule walks the local key tiles (each
  with the query tiles of its block at or below it) and then the summary
  tiles (each with the query tiles of every later block);
* the forward rule names its output and row logsumexp with
  :data:`petastorm_tpu.ops.flash_attn.SAVED_NAMES`, so
  ``llama.apply(remat_layers=True)`` keeps them and runs the kernel once a
  step;
* on the ``cpu`` backend the kernels run in Pallas interpret mode (tests).
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from petastorm_tpu.device_scopes import EVA_PREP
from petastorm_tpu.ops.flash_attn import (SAVED_NAMES, _mask_causal,
                                          _p_ds_tile, _resolve_interpret,
                                          _softmax_tile)

# Launch defaults, from a sweep of 22 tilings on the chip at 32 heads x
# 16,384 positions, window 2048, chunk 16 (forward + backward with the
# summaries, TPU v5 lite, PR 33): 26.9 ms at 1024 x 1024 keys with all 896
# summaries seen in one tile, against 36.3 at the flash kernels' 256 x
# 1024 with 256 summaries a tile and 44.5 at 256 x 512 x 128: fewer,
# larger steps win although a diagonal tile is then half masked. 2048-row
# query tiles, or 1024 x 2048, do not fit the kernels' fast memory.
_TILES = (1024, 1024, 1024)     # query rows, key rows, summaries a tile
_LANES = 128

# Bits of a schedule item's flags.
_SUMMARY, _FIRST, _LAST = 1, 2, 4


def eva_summaries(k, v, phi, mu, chunk: int):
    """The chunks' pooled keys and values. k, v: ``(b, s, h, d)`` (k as the
    attention sees it, rotated); phi, mu: ``(h, d)`` -> ``kbar, vbar``
    ``(b, s // chunk, h, d)`` in the inputs' dtype. Float32 scores, softmax
    and sums; elementwise products and reductions only, so XLA fuses each
    into one pass over ``k`` or ``v``."""
    b, s, h, d = k.shape
    kc = k.reshape(b, s // chunk, chunk, h, d).astype(jnp.float32)
    vc = v.reshape(b, s // chunk, chunk, h, d).astype(jnp.float32)
    score = jnp.sum(kc * phi.astype(jnp.float32), axis=-1)     # (b, n, C, h)
    a = jax.nn.softmax(score, axis=2)[..., None]
    kbar = jnp.sum(a * kc, axis=2) + mu.astype(jnp.float32)
    vbar = jnp.sum(a * vc, axis=2)
    return kbar.astype(k.dtype), vbar.astype(v.dtype)


@dataclass(frozen=True)
class _Shape:
    """The static tiling of one call (:func:`_shape`): what the schedules
    and the three launches share. ``bq``, ``bk``: rows of a query and of a
    key tile; ``bs``: summaries a tile."""
    seq: int
    window: int
    chunk: int
    bq: int
    bk: int
    bs: int

    @property
    def n_blocks(self) -> int:
        return self.seq // self.window

    @property
    def per_block(self) -> int:
        """Summaries a block makes."""
        return self.window // self.chunk

    @property
    def n_seen(self) -> int:
        """Summaries some query sees: the first ``n - 1`` blocks'."""
        return (self.n_blocks - 1) * self.per_block

    @property
    def n_s_tiles(self) -> int:
        """Tiles of the summary array the kernels read: the summaries
        seen, padded to whole tiles (at least one, so that a one-block
        call has something to name)."""
        return max(1, -(-self.n_seen // self.bs))

    def summary_tiles_seen(self, block: int) -> int:
        return -(-block * self.per_block // self.bs)


def _shape(seq: int, window: int, chunk: int, block_q: int, block_k: int,
           block_s: int) -> _Shape:
    if window % chunk or seq % window:
        raise ValueError(f"EVA needs chunk ({chunk}) | window ({window}) "
                         f"| seq ({seq})")
    bq, bk = min(block_q, window), min(block_k, window)
    if window % bq or window % bk or bq % 8 or bk % 8:
        raise ValueError(
            f"EVA cannot tile a window of {window} with blocks "
            f"({block_q}, {block_k}): they must divide it, 8-aligned")
    n_seen = (seq // window - 1) * (window // chunk)
    bs = min(block_s, -(-max(n_seen, 1) // _LANES) * _LANES)
    return _Shape(seq, window, chunk, bq, bk, bs)


def _carry(values, live, start=0):
    """``values`` where ``live``, else the last live value before (so that
    a block whose source an item does not read keeps its index, and is not
    fetched again)."""
    out, last = [], start
    for v, ok in zip(values, live):
        last = v if ok else last
        out.append(last)
    return out


def _q_schedule(t: _Shape) -> tuple:
    """Items of ``eva_fwd`` and ``eva_bwd_dq``, query tile by query tile:
    its block's key tiles up to the diagonal, then the summary tiles of
    the earlier blocks -> int32 arrays ``(q tile, key tile, summary tile,
    flags)``."""
    per_window = t.window // t.bq
    items = []
    for qi in range(t.n_blocks * per_window):
        block, q_in = divmod(qi, per_window)
        last_key = (q_in * t.bq + t.bq - 1) // t.bk
        sources = [(0, block * (t.window // t.bk) + kt)
                   for kt in range(last_key + 1)]
        sources += [(_SUMMARY, st)
                    for st in range(t.summary_tiles_seen(block))]
        for n, (src, tile) in enumerate(sources):
            items.append((qi, src, tile, src | (_FIRST if n == 0 else 0)
                          | (_LAST if n == len(sources) - 1 else 0)))
    qi, src, tile, flags = zip(*items)
    local = [s == 0 for s in src]
    return tuple(np.asarray(a, np.int32) for a in (
        qi, _carry(tile, local), _carry(tile, [not x for x in local]),
        flags))


def _kv_schedule(t: _Shape) -> tuple:
    """Items of ``eva_bwd_dkv``: every local key tile with the query tiles
    of its block at or below it, then every summary tile with the query
    tiles of the later blocks that see it -> int32 arrays ``(key tile,
    summary tile, q tile, flags)``."""
    per_window = t.window // t.bq
    n_q = t.n_blocks * per_window
    walks = []
    for kt in range(t.seq // t.bk):
        block = kt * t.bk // t.window
        walks.append((0, kt, [
            qi for qi in range(block * per_window, (block + 1) * per_window)
            if qi * t.bq + t.bq - 1 >= kt * t.bk]))
    if t.n_seen:
        for st in range(t.n_s_tiles):
            walks.append((_SUMMARY, st, [
                qi for qi in range(n_q)
                if st * t.bs < (qi // per_window) * t.per_block]))
    items = [(src, tile, qi, src | (_FIRST if n == 0 else 0)
              | (_LAST if n == len(qs) - 1 else 0))
             for src, tile, qs in walks for n, qi in enumerate(qs)]
    src, tile, qi, flags = zip(*items)
    local = [s == 0 for s in src]
    return tuple(np.asarray(a, np.int32) for a in (
        _carry(tile, local), _carry(tile, [not x for x in local]), qi,
        flags))


def _summary_mask(t: _Shape, q_off, s_off):
    """Mask of a (q tile, summary tile) score tile: a query of block ``w``
    sees the summaries of blocks before ``w`` (padding lies past them
    all)."""
    seen = (q_off // t.window) * t.per_block

    def mask(s):
        col = s_off + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        return jnp.where(col < seen, s, -jnp.inf)
    return mask


def _local_mask(t: _Shape, q_off, k_off):
    return lambda s: _mask_causal(s, True, q_off, k_off, t.bq, t.bk)


def _fwd_kernel(qt_ref, kt_ref, st_ref, flags_ref, q_ref, k_ref, v_ref,
                kb_ref, vb_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref, *,
                t: _Shape, scale: float):
    from jax.experimental import pallas as pl

    item = pl.program_id(2)
    flags = flags_ref[item]
    q_off = qt_ref[item] * t.bq

    @pl.when((flags & _FIRST) != 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[:] = jnp.zeros_like(l_ref)

    # A row's first item is its block's first key tile, which holds a key
    # at or before it: the running max is finite from there on.
    @pl.when((flags & _SUMMARY) == 0)
    def _local():
        _softmax_tile(q_ref[0, 0, :, :], k_ref[0, 0, :, :], v_ref[0, 0, :, :],
                      _local_mask(t, q_off, kt_ref[item] * t.bk),
                      acc_ref, m_ref, l_ref, scale)

    @pl.when((flags & _SUMMARY) != 0)
    def _summaries():
        _softmax_tile(q_ref[0, 0, :, :], kb_ref[0, 0, :, :],
                      vb_ref[0, 0, :, :],
                      _summary_mask(t, q_off, st_ref[item] * t.bs),
                      acc_ref, m_ref, l_ref, scale)

    @pl.when((flags & _LAST) != 0)
    def _emit():
        o_ref[0, 0, :, :] = (acc_ref[:] / l_ref[:, 0][:, None]).astype(
            o_ref.dtype)
        lse_ref[0, 0, :, :] = m_ref[:] + jnp.log(l_ref[:])


def _dq_kernel(qt_ref, kt_ref, st_ref, flags_ref, q_ref, k_ref, v_ref,
               kb_ref, vb_ref, do_ref, lse_ref, dd_ref, dq_ref, dq_acc, *,
               t: _Shape, scale: float):
    from jax.experimental import pallas as pl

    item = pl.program_id(2)
    flags = flags_ref[item]
    q_off = qt_ref[item] * t.bq

    @pl.when((flags & _FIRST) != 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    def add(keys_ref, values_ref, mask):
        keys = keys_ref[0, 0, :, :]
        _, ds = _p_ds_tile(q_ref[0, 0, :, :], keys, values_ref[0, 0, :, :],
                           do_ref[0, 0, :, :], lse_ref[0, 0, :, 0],
                           dd_ref[0, 0, :, 0], mask, scale)
        dq_acc[:] += jax.lax.dot_general(
            ds.astype(keys.dtype), keys, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when((flags & _SUMMARY) == 0)
    def _local():
        add(k_ref, v_ref, _local_mask(t, q_off, kt_ref[item] * t.bk))

    @pl.when((flags & _SUMMARY) != 0)
    def _summaries():
        add(kb_ref, vb_ref, _summary_mask(t, q_off, st_ref[item] * t.bs))

    @pl.when((flags & _LAST) != 0)
    def _emit():
        dq_ref[0, 0, :, :] = dq_acc[:].astype(dq_ref.dtype)


def _dkv_kernel(kt_ref, st_ref, qt_ref, flags_ref, k_ref, v_ref, kb_ref,
                vb_ref, q_ref, do_ref, lse_ref, dd_ref, dk_ref, dv_ref,
                dkb_ref, dvb_ref, dk_acc, dv_acc, dkb_acc, dvb_acc, *,
                t: _Shape, scale: float):
    from jax.experimental import pallas as pl

    item = pl.program_id(2)
    flags = flags_ref[item]
    q_off = qt_ref[item] * t.bq
    first, last = (flags & _FIRST) != 0, (flags & _LAST) != 0

    def walk(live, keys_ref, values_ref, mask, dkeys_ref, dvalues_ref,
             dkeys_acc, dvalues_acc):
        @pl.when(jnp.logical_and(live, first))
        def _init():
            dkeys_acc[:] = jnp.zeros_like(dkeys_acc)
            dvalues_acc[:] = jnp.zeros_like(dvalues_acc)

        @pl.when(live)
        def _step():
            q, do = q_ref[0, 0, :, :], do_ref[0, 0, :, :]
            p, ds = _p_ds_tile(q, keys_ref[0, 0, :, :],
                               values_ref[0, 0, :, :], do,
                               lse_ref[0, 0, :, 0], dd_ref[0, 0, :, 0], mask,
                               scale)
            dvalues_acc[:] += jax.lax.dot_general(
                p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dkeys_acc[:] += jax.lax.dot_general(
                ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        @pl.when(jnp.logical_and(live, last))
        def _emit():
            dkeys_ref[0, 0, :, :] = dkeys_acc[:].astype(dkeys_ref.dtype)
            dvalues_ref[0, 0, :, :] = dvalues_acc[:].astype(
                dvalues_ref.dtype)

    walk((flags & _SUMMARY) == 0, k_ref, v_ref,
         _local_mask(t, q_off, kt_ref[item] * t.bk), dk_ref, dv_ref,
         dk_acc, dv_acc)
    walk((flags & _SUMMARY) != 0, kb_ref, vb_ref,
         _summary_mask(t, q_off, st_ref[item] * t.bs), dkb_ref, dvb_ref,
         dkb_acc, dvb_acc)


def _specs(t: _Shape, d: int, q_tiles: int, k_tiles: int, s_tiles: int):
    """Block specs of a (b, h, rows, d) operand read a query tile, a key
    tile or a summary tile at a time, and of the (b, h, seq, 1) row
    statistics. ``*_tiles``: which of the schedule's arrays holds that
    tile's index for each item."""
    from jax.experimental import pallas as pl

    def spec(rows, width, tiles):
        return pl.BlockSpec((1, 1, rows, width),
                            lambda bi, hi, item, *sched: (
                                bi, hi, sched[tiles][item], 0))
    return (spec(t.bq, d, q_tiles), spec(t.bk, d, k_tiles),
            spec(t.bs, d, s_tiles), spec(t.bq, 1, q_tiles))


def _pad_summaries(x, t: _Shape):
    """(b, s / chunk, h, d) summaries -> (b, h, whole tiles, d) of the
    ones seen (the first n - 1 blocks'), zero-padded."""
    x = x[:, :t.n_seen].transpose(0, 2, 1, 3)
    return jnp.pad(x, ((0, 0), (0, 0), (0, t.n_s_tiles * t.bs - t.n_seen),
                       (0, 0)))


def _unpad_summaries(dx, t: _Shape, like):
    """The transpose of :func:`_pad_summaries`."""
    dx = dx[:, :, :t.n_seen].transpose(0, 2, 1, 3)
    return jnp.pad(dx, ((0, 0), (0, like.shape[1] - t.n_seen), (0, 0),
                        (0, 0))).astype(like.dtype)


def _forward(t: _Shape, interpret: bool, q, k, v, kbar, vbar):
    """-> o (b, s, h, d) in q's dtype, lse (b, h, s, 1) float32 (kernel
    layout: only the backward launches read it)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, s, h, d = q.shape
    sched = _q_schedule(t)                  # (q tile, key tile, summary, .)
    q_spec, k_spec, s_spec, stat_spec = _specs(t, d, 0, 1, 2)
    o, lse = pl.pallas_call(
        partial(_fwd_kernel, t=t, scale=1.0 / np.sqrt(d)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(b, h, len(sched[0])),
            in_specs=[q_spec, k_spec, k_spec, s_spec, s_spec],
            out_specs=[q_spec, stat_spec],
            scratch_shapes=[pltpu.VMEM((t.bq, d), jnp.float32),
                            pltpu.VMEM((t.bq, 1), jnp.float32),
                            pltpu.VMEM((t.bq, 1), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((b, h, s, d), q.dtype),
                   jax.ShapeDtypeStruct((b, h, s, 1), jnp.float32)],
        interpret=interpret, name="eva_fwd",
    )(*sched, q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
      v.transpose(0, 2, 1, 3), _pad_summaries(kbar, t),
      _pad_summaries(vbar, t))
    return o.transpose(0, 2, 1, 3), lse


def _backward(t: _Shape, interpret: bool, q, k, v, kbar, vbar, o, lse, do):
    """-> dq, dk, dv, dkbar, dvbar in the model's layouts."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, s, h, d = q.shape
    scale = 1.0 / np.sqrt(d)
    # D_i = rowsum(dO o O): O(seq d) elementwise, fine outside the kernels.
    dd = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    keys = (k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
            _pad_summaries(kbar, t), _pad_summaries(vbar, t))
    rows = (do.transpose(0, 2, 1, 3), lse, dd.transpose(0, 2, 1)[..., None])
    qT = q.transpose(0, 2, 1, 3)

    sched = _q_schedule(t)                  # (q tile, key tile, summary, .)
    q_spec, k_spec, s_spec, stat_spec = _specs(t, d, 0, 1, 2)
    dq = pl.pallas_call(
        partial(_dq_kernel, t=t, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(b, h, len(sched[0])),
            in_specs=[q_spec, k_spec, k_spec, s_spec, s_spec, q_spec,
                      stat_spec, stat_spec],
            out_specs=q_spec,
            scratch_shapes=[pltpu.VMEM((t.bq, d), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((b, h, s, d), q.dtype),
        interpret=interpret, name="eva_bwd_dq",
    )(*sched, qT, *keys, *rows)

    sched = _kv_schedule(t)                 # (key tile, summary, q tile, .)
    q_spec, k_spec, s_spec, stat_spec = _specs(t, d, 2, 0, 1)
    n_s = t.n_s_tiles * t.bs
    # A summary tile's gradient block stays put while the local key tiles
    # are walked (and the last key tile's while the summary tiles are):
    # each is written back when its index moves on, by when its own walk
    # has emitted it.
    dk, dv, dkb, dvb = pl.pallas_call(
        partial(_dkv_kernel, t=t, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(b, h, len(sched[0])),
            in_specs=[k_spec, k_spec, s_spec, s_spec, q_spec, q_spec,
                      stat_spec, stat_spec],
            out_specs=[k_spec, k_spec, s_spec, s_spec],
            scratch_shapes=[pltpu.VMEM((t.bk, d), jnp.float32),
                            pltpu.VMEM((t.bk, d), jnp.float32),
                            pltpu.VMEM((t.bs, d), jnp.float32),
                            pltpu.VMEM((t.bs, d), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((b, h, s, d), k.dtype),
                   jax.ShapeDtypeStruct((b, h, s, d), v.dtype),
                   jax.ShapeDtypeStruct((b, h, n_s, d), kbar.dtype),
                   jax.ShapeDtypeStruct((b, h, n_s, d), vbar.dtype)],
        interpret=interpret, name="eva_bwd_dkv",
    )(*sched, *keys, qT, *rows)
    if not t.n_seen:    # one block: no summary is seen, none was walked
        dkb, dvb = jnp.zeros_like(dkb), jnp.zeros_like(dvb)
    return (dq.transpose(0, 2, 1, 3), dk.transpose(0, 2, 1, 3),
            dv.transpose(0, 2, 1, 3), _unpad_summaries(dkb, t, kbar),
            _unpad_summaries(dvb, t, vbar))


@partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _eva_vjp(t, interpret, q, k, v, kbar, vbar):
    return _forward(t, interpret, q, k, v, kbar, vbar)[0]


def _eva_vjp_fwd(t, interpret, q, k, v, kbar, vbar):
    o, lse = _forward(t, interpret, q, k, v, kbar, vbar)
    # Named as the flash kernels name theirs, for a checkpoint around the
    # caller to keep; lse as (b, h, s): the trailing 1 of its kernel layout
    # pads to a lane tile of 128 in HBM.
    o = checkpoint_name(o, SAVED_NAMES[0])
    lse = checkpoint_name(lse[..., 0], SAVED_NAMES[1])
    return o, (q, k, v, kbar, vbar, o, lse)


def _eva_vjp_bwd(t, interpret, residual, g):
    q, k, v, kbar, vbar, o, lse = residual
    return _backward(t, interpret, q, k, v, kbar, vbar, o, lse[..., None], g)


_eva_vjp.defvjp(_eva_vjp_fwd, _eva_vjp_bwd)


def eva_attention(q, k, v, phi, mu, *, window: int, chunk: int,
                  interpret=None, _tiles: tuple = _TILES):
    """EVA attention of q, k, v ``(b, s, h, d)`` (one key/value head a
    query head; q and k rotated by the caller) with the heads' ``phi``,
    ``mu`` ``(h, d)`` -> ``(b, s, h, d)``. ``s`` is a whole number of
    ``window``-long blocks and ``window`` of ``chunk``-long chunks; a shape
    the tiles cannot divide raises (there is no dense route). The summaries
    run under ``jax.named_scope(device_scopes.EVA_PREP)``."""
    if k.shape != q.shape or v.shape != q.shape:
        raise ValueError(f"EVA takes one key/value head a query head: q "
                         f"{q.shape}, k {k.shape}, v {v.shape}")
    t = _shape(q.shape[1], window, chunk, *_tiles)
    with jax.named_scope(EVA_PREP):
        kbar, vbar = eva_summaries(k, v, phi, mu, chunk)
    return _eva_vjp(t, _resolve_interpret(interpret), q, k, v, kbar, vbar)


def make_eva_attention(window: int, chunk: int, interpret=None):
    """An ``eva_attn_fn`` for :func:`petastorm_tpu.models.llama.apply`:
    ``(q, k, v, phi, mu) -> out``."""
    return partial(eva_attention, window=window, chunk=chunk,
                   interpret=interpret)
