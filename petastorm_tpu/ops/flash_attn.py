"""Flash attention as a Pallas TPU kernel: O(seq) memory attention.

XLA's plain softmax attention materializes the O(seq^2) score matrix in
HBM; this kernel never does. Design (flash-attention-2 style, TPU-first):

* every kernel walks a STATIC schedule of live tiles: the (q tile, K/V
  tile) pairs that hold at least one pair inside the mask, made in numpy
  at trace time from the shapes, the tiles and the mask
  (:func:`_live_tiles`) and handed to the kernel as scalar-prefetch
  arrays, as :mod:`petastorm_tpu.ops.eva_attn` does. The forward grid
  is ``(batch, q_heads, items)``, a q tile's K/V tiles in
  ascending order (:func:`_q_schedule`) — TPU grids execute
  sequentially, so the online-softmax state (running max ``m``,
  normalizer ``l``, unnormalized accumulator ``acc``) lives in VMEM
  scratch carried across a q tile's items, flags marking its first and
  last. VMEM holds ONE q tile and ONE K/V tile at a time (O(block * d),
  not O(seq * d)), which is what makes long sequences fit;
* grouped-query attention is native: the K/V BlockSpec index-maps the
  q-head grid coordinate onto its kv head (``h // rep``) — K/V are never
  repeated in memory;
* a tile above the causal diagonal is not in the schedule: it costs no
  grid step, no K/V copy and no MXU work (:func:`grid_steps` counts what
  is left: at the launch tiles, 1024 x 1024, 10 of 16 steps a head at
  4096 positions and 136 of 256 at 16,384);
* a static ``window`` (with ``causal``) is sliding-window attention: the
  mask also drops keys ``window`` or more behind their query, which only
  changes the tiles the schedule lists (70 of 256 a head at 16,384
  positions under a window of 4096); the calls are then named
  ``swa_fwd`` / ``swa_bwd`` (one kernel body per pass, the window an
  argument). ``causal=False`` lists every tile;
* scores accumulate in float32 regardless of input dtype (numerics parity
  with :func:`petastorm_tpu.parallel.attention.dense_attention`);
* the key width and the value width are two numbers: q and k are ``d``
  wide, v (so o, ``do``, ``dv`` and the accumulator) ``v.shape[-1]``, the
  scores scaled by ``1 / sqrt(d)``; with equal widths the calls are what
  they were;
* q and k may each be a pair, ``(position-free part, rotary part)``, the
  key's rotary part ONE head that every query head shares — latent
  attention's operands in their own form (``llama`` ``attention="mla"``:
  32 heads of 128 + 64 over one rotary key of 64, values of 128). A
  kernel joins a split tile along the lanes in VMEM (the position-free
  part fills whole lane tiles) and makes each product once over the
  summed width, scaled by its root: the tile math of the joined call, bit
  for bit. The gradients come back as pairs, the rotary key's summed over
  every query head inside the kernel (the pair's dK/dV call leaves it a
  key/value head at a time, summed after it). No 192-wide q or k and no
  repeated rotary key exists in HBM. Arrays, not pairs, lower to the
  kernels they lowered to before the split form was added;
* the backward pass is ONE Pallas kernel (``custom_vjp``; ``flash_bwd``,
  under a window ``swa_bwd``): the forward saves ``(q, k, v, o, lse)``,
  then a grid ``(batch, kv_heads, items)`` walks, for each query head of
  the group, the forward's own schedule (:func:`_bwd_schedule`). A live
  tile's S, P, dP and dS are made once and dV, dK and dQ all taken from
  them: the five products a backward needs, one mask, one ``exp``, one
  read of each operand. dQ accumulates in a tile of float32 scratch across
  a q tile's K/V tiles; dK and dV of the K/V head stay in float32 VMEM
  scratch for the whole walk — grouped-query head gradients summed there —
  and leave as whole-head blocks at its end. The call's scoped-VMEM limit
  is reckoned from its shapes (:func:`_bwd_vmem_limit`). Where a head's
  accumulators do not fit (float32 at head 256 beyond 8192 positions,
  bfloat16 at head 128 beyond 32,768) the backward is the flash-attention-2
  pair at tile residency, ``flash_bwd_dq`` (kv-innermost,
  :func:`_q_schedule`) + ``flash_bwd_dkv`` (q-innermost over every (group
  head, live q tile) pair of one K/V tile, :func:`_kv_schedule`): the same
  tile math and the same order of every sum, so the same gradients bit for
  bit, at seven products a tile (S and dP twice). No
  O(seq^2) or O(block*seq) tensors touch HBM in training either way. The
  forward rule names ``o`` and ``lse`` (:data:`SAVED_NAMES`, via
  ``jax.ad_checkpoint.checkpoint_name``): a ``jax.checkpoint`` around the
  caller whose policy saves those names keeps the two arrays only the
  kernel can make, and its recomputation launches no second forward
  (``llama.apply(remat_layers=True)`` does); under a bare checkpoint, or
  none, the names are identities;
* on the ``cpu`` backend the kernel runs in Pallas interpret mode
  (tests); every other backend compiles it or raises. Shapes that don't
  tile cleanly (seq not divisible by an 8-aligned block, or ``causal``
  with ``sq != sk``) take the dense path in :func:`flash_attention` —
  numerically identical — and are an error in the ``attn_fn`` built by
  :func:`make_flash_attention`, whose caller asked for the kernel.

Used as a drop-in ``attn_fn`` for :mod:`petastorm_tpu.models.llama` via
:func:`make_flash_attention` (``supports_gqa`` — K/V stay at kv-head
width), and fused into the ring-attention local step via
:func:`flash_attention_stats`, which emits the online-softmax partials
(unnormalized o, m, l) the ring's cross-device merge consumes
(``ring_attention(..., local_attn="flash")``).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

# What :func:`_flash_vjp_fwd` names: the attention output and its rows'
# logsumexp. ``jax.checkpoint_policies.save_only_these_names(*SAVED_NAMES)``
# keeps them across a checkpoint (``llama.apply(remat_layers=True)``).
SAVED_NAMES = ("flash_attn_out", "flash_attn_lse")

_DEFAULT_BLOCK = 128
# Launch defaults, from a sweep of 16 tilings on the chip on the static
# schedule (TPU v5 lite, 2026-10-03, PR 34; forward / dQ / dK+dV timed
# apart, bfloat16, head 128, ms a call with its transposes) at the token
# cells' three calls: b4 h32/8 s4096, b2 h7/1 s16384, the same under a
# window of 4096. 1024 x 1024 won every kernel at every shape:
#   b4 h32/8 s4096        6.63 /  8.64 / 10.51  (256 x 1024: 8.21 /  9.86 / 11.61)
#   b2 h7/1 s16384        7.67 /  9.30 / 12.37  (256 x 1024: 10.19 / 11.35 / 14.21)
#   the same, window 4096 4.15 /  5.44 /  6.80  (256 x 1024: 5.45 /  6.40 /  7.56)
# with 512 x 1024 next (7.03 / 9.07 / 10.65 at the first), key tiles of
# 512 or less 20-60% slower in the forward, and 2048-row or 2048-key tiles
# (under a 96 MiB VMEM limit) 10-15% slower: with no dead step left,
# a step's fixed cost is what a larger tile amortizes, although a tile on
# the diagonal is then half masked. One tiling for the three kernels.
# Seqs the big tiles don't divide halve down to _DEFAULT_BLOCK before
# falling back to dense, so the kernel-path coverage of the old 128
# defaults (e.g. seq 1280) is preserved.
_DEFAULT_BLOCK_Q = 1024
_DEFAULT_BLOCK_K = 1024
# The scoped-VMEM limit of the forward and of the pair's two calls, and the
# least the one-kernel backward asks for. The default 16 MiB holds a 1024 x
# 1024 tile's float32 scores and their gradient beside bfloat16 operands
# at head 128, not beside float32 operands at head 256 (the dQ call);
# 32 MiB holds both with room, a quarter of what a v5e core has. The token
# cells' steps read the same under either limit (chip runs, PR 34).
_VMEM_LIMIT = 32 << 20
# The most the one-kernel backward may ask for of a v5e core's 128 MiB (PR
# 34's sweep compiled these kernels under 96 MiB).
_VMEM_CEILING = 96 << 20


def _bwd_vmem_limit(sk: int, d, vd: int, itemsize: int, block_q: int,
                    block_k: int):
    """Scoped-VMEM limit of the one-kernel backward, reckoned from its
    call's shapes (lanes padded to 128; ``d`` the key width, or the tuple
    of a split key's part widths, each padded), or None where a K/V head's
    accumulators do not fit :data:`_VMEM_CEILING` and the backward is the
    pair: float32 dK + dV of the head and their whole-head output blocks
    (double-buffered), the four float32 tiles s, p, dP, dS, the
    double-buffered operand tiles and row statistics, dQ's tile. At the
    launch tiles 30 MiB at 4096 positions and head 128 (so
    :data:`_VMEM_LIMIT`), 54 MiB at 16,384, 72 MiB there at widths 192 |
    128 or (128, 64) | 128 (Mosaic refuses that call at 48 MiB), 56 MiB
    for float32 at head 256 and 4096; bfloat16 at head 128 fits to 32,768
    positions, float32 at head 256 to 8192."""
    wide = sum(-(-w // 128) * 128
               for w in (*(d if isinstance(d, tuple) else (d,)), vd))
    need = (sk * wide * (4 + 2 * itemsize)
            + 4 * block_q * block_k * 4
            + 2 * (block_q + block_k) * wide * itemsize
            + block_q * (wide * (4 + 2 * itemsize) + 4 * 128 * 4))
    return None if need > _VMEM_CEILING else max(_VMEM_LIMIT, need)


def _pick_block(requested: int, seq: int) -> int:
    """Clamp ``requested`` to ``seq``; if it doesn't divide, halve it down
    to the 128 granule (1280 takes 256, not 1024), and retry the granule
    itself before :func:`_tiles` rejects it."""
    blk = min(requested, seq)
    while seq % blk and not blk % 2 and blk > _DEFAULT_BLOCK:
        blk //= 2
    if seq % blk and not seq % _DEFAULT_BLOCK:
        blk = _DEFAULT_BLOCK
    return blk


def _tiles(sq: int, sk: int, causal: bool, block_q: int, block_k: int):
    """``(block_q, block_k)`` the kernels launch with, or None when the
    shape cannot tile onto the hardware: seq not divisible by the
    (clamped) block, a clamped block not a multiple of 8 (Mosaic's
    second-minor tile granule — catches e.g. seq=100), or ``causal`` with
    ``sq != sk`` (the mask diagonal would straddle blocks)."""
    block_q = _pick_block(block_q, sq)
    block_k = _pick_block(block_k, sk)
    if (sq % block_q or sk % block_k or block_q % 8 or block_k % 8
            or (causal and sq != sk)):
        return None
    return block_q, block_k


def require_flash_tiles(sq: int, sk: int, causal: bool,
                        block_q: int = _DEFAULT_BLOCK_Q,
                        block_k: int = _DEFAULT_BLOCK_K):
    """:func:`_tiles` for a caller that asked for the kernel: an untileable
    shape raises instead of quietly taking the O(seq^2) dense route (at 32k
    that is a 34 GB score tensor)."""
    tiles = _tiles(sq, sk, causal, block_q, block_k)
    if tiles is None:
        raise ValueError(
            f"flash attention cannot tile sq={sq}, sk={sk}, causal={causal} "
            f"with blocks ({block_q}, {block_k}): seq must divide into "
            f"8-aligned blocks and causal needs sq == sk")
    return tiles


def _resolve_interpret(interpret) -> bool:
    """Pallas interpret mode exists for the ``cpu`` backend only: ``None``
    selects it there and nowhere else, and asking for it on any other
    backend is an error — an accelerator compiles the kernel or raises."""
    on_cpu = jax.default_backend() == "cpu"
    if interpret is None:
        return on_cpu
    if interpret and not on_cpu:
        raise ValueError(
            f"interpret=True on the {jax.default_backend()!r} backend: the "
            f"Pallas interpreter is for cpu only")
    return bool(interpret)


# Bits of a schedule item's flags: the first and the last item of a run
# (one q tile's K/V tiles, or one K/V tile's (head, q tile) pairs).
_FIRST, _LAST = 1, 2


def _live_tiles(sq: int, sk: int, block_q: int, block_k: int, causal: bool,
                window=None) -> np.ndarray:
    """``(sq // block_q, sk // block_k)`` booleans: True where the (q tile,
    K/V tile) pair has any element inside the mask — on or below the
    diagonal and, with a ``window``, fewer than ``window`` keys behind its
    query. What every kernel walks, and nothing else."""
    q_off = np.arange(sq // block_q)[:, None] * block_q
    k_off = np.arange(sk // block_k)[None, :] * block_k
    live = np.ones((sq // block_q, sk // block_k), bool)
    if causal:
        live &= q_off + block_q - 1 >= k_off
    if window is not None:
        live &= q_off - (k_off + block_k - 1) < window
    return live


def _run_flags(outer: np.ndarray) -> np.ndarray:
    """``_FIRST`` / ``_LAST`` on the items where ``outer`` (ascending)
    takes a new value / holds one for the last time."""
    edge = outer[1:] != outer[:-1]
    return (np.r_[True, edge] * _FIRST | np.r_[edge, True] * _LAST).astype(
        np.int32)


def _q_schedule(live: np.ndarray) -> tuple:
    """Items of the forward and the dQ kernel, q tile by q tile: its live
    K/V tiles in ascending order -> int32 arrays ``(q tile, K/V tile,
    flags)``."""
    qt, kt = (a.astype(np.int32) for a in np.nonzero(live))
    return qt, kt, _run_flags(qt)


def _kv_schedule(live: np.ndarray, rep: int) -> tuple:
    """Items of the dK/dV kernel, K/V tile by K/V tile: every (head of the
    group, live q tile) pair, heads outermost -> int32 arrays ``(K/V tile,
    head in group, q tile, flags)``."""
    kt, head, qt = (np.asarray(a, np.int32) for a in zip(*(
        (ki, r, qi) for ki in range(live.shape[1]) for r in range(rep)
        for qi in np.flatnonzero(live[:, ki]))))
    return kt, head, qt, _run_flags(kt)


def _bwd_schedule(live: np.ndarray, rep: int) -> tuple:
    """Items of the one-kernel backward, a K/V head at a time:
    :func:`_q_schedule`'s walk once for each head of the group, heads
    outermost -> int32 arrays ``(K/V tile, head in group, q tile, flags)``
    as :func:`_kv_schedule` orders them, the flags a q tile's first and
    last item. A K/V tile meets its (head, q tile) pairs in
    :func:`_kv_schedule`'s order and a q tile its K/V tiles in
    :func:`_q_schedule`'s, so every sum is the pair's, term by term."""
    qt, kt, flags = _q_schedule(live)
    return (np.tile(kt, rep), np.repeat(np.arange(rep, dtype=np.int32),
                                        len(qt)),
            np.tile(qt, rep), np.tile(flags, rep))


def grid_steps(sq: int, sk: int, block_q: int, block_k: int, causal: bool,
               window=None, rep: int = 1) -> dict:
    """Length of the innermost grid axis of the kernels a (batch, head) —
    a (batch, kv head) for ``"bwd"`` (the one-kernel backward) and
    ``"dkv"`` — at these tiles: the schedules' own lengths, every item a
    live tile."""
    live = _live_tiles(sq, sk, block_q, block_k, causal, window)
    n_q_items = len(_q_schedule(live)[0])
    return {"fwd": n_q_items, "bwd": len(_bwd_schedule(live, rep)[0]),
            "dq": n_q_items, "dkv": len(_kv_schedule(live, rep)[0])}


def _mask_causal(s, causal: bool, q_off, k_off, block_q: int, block_k: int,
                 window=None):
    """Apply the causal (and sliding-window) mask to a (block_q, block_k)
    score tile — ONE home for the mask numerics so the backward recompute
    can never drift from what the forward computed."""
    if not causal:
        return s
    qpos = q_off + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    kpos = k_off + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    keep = qpos >= kpos
    if window is not None:
        keep = jnp.logical_and(keep, qpos - kpos < window)
    return jnp.where(keep, s, -jnp.inf)


def _parts(x) -> tuple:
    """A split operand's pair, or the one array as a tuple of one."""
    return x if isinstance(x, tuple) else (x,)


def _lead(x):
    """An operand's first part: the array itself, or a split one's
    position-free part (its heads, positions and batch are the operand's)."""
    return _parts(x)[0]


def _tile(ref):
    """The (rows, width) tile of a (1, 1, rows, width) block, or the
    pair of tiles of a split operand's pair of blocks."""
    if isinstance(ref, tuple):
        return tuple(_tile(r) for r in ref)
    return ref[0, 0, :, :]


def _joined(x):
    """A tile, or a split operand's pair of tiles joined along the lanes:
    the position-free part fills whole lane tiles, so the join is aligned,
    and the joined tile is the one the unsplit operand would have loaded
    (the rotary key's one head beside each head's own part)."""
    return jnp.concatenate(x, axis=1) if isinstance(x, tuple) else x


def _scores(q, k):
    """``q k^T`` of a tile in float32, split operands joined first: one
    product over their summed width, as for an unsplit operand."""
    return jax.lax.dot_general(_joined(q), _joined(k),
                               (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _accumulate(acc, rows, product):
    """``acc[rows, :] += product()`` (``rows`` None: ``acc[:]``); a split
    operand's pair of accumulators each adds its own columns of the one
    product."""
    index = slice(None) if rows is None else (rows, slice(None))
    if not isinstance(acc, tuple):
        acc[index] += product()
        return
    full, start = product(), 0
    for part in acc:
        width = part.shape[1]
        part[index] += full[:, start:start + width]
        start += width


def _softmax_tile(q, k, v, mask, acc_ref, m_ref, l_ref, scale: float,
                  rows_may_be_dead: bool = False):
    """One (q tile, key tile) step of the online softmax, the tile body of
    every forward kernel here and of :mod:`petastorm_tpu.ops.eva_attn`:
    scores ``q k^T * scale`` through ``mask`` (a callable on the float32
    score tile), then the running max ``m_ref``, normalizer ``l_ref`` and
    unnormalized accumulator ``acc_ref`` (VMEM scratch) take the tile in.
    q and k may be split operands' pairs of tiles (:func:`_scores`).

    Matmuls stay in the input dtype (bf16 on the training path) with f32
    accumulation — the MXU's native mode; upcasting the operands to f32
    first would run the systolic array at a fraction of peak. All softmax
    bookkeeping (max, exp, normalizer) is f32."""
    s = _scores(q, k) * scale                                    # (bq, bk)
    s = mask(s)
    m_prev, l_prev = m_ref[:, 0], l_ref[:, 0]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    # m_new is finite from the first live block (causal keeps the
    # diagonal), so exp never sees inf-inf; a still--inf running max
    # contributes alpha=0 exactly. Under a window a row's first live
    # tile can lie wholly behind its own window (``rows_may_be_dead``):
    # exponentiate against 0 there, so that p and alpha are 0 and not
    # exp(inf - inf).
    m_exp = jnp.where(m_new == -jnp.inf, 0.0, m_new) if rows_may_be_dead \
        else m_new
    p = jnp.exp(s - m_exp[:, None])
    alpha = jnp.exp(m_prev - m_exp)
    l_ref[:, 0] = l_prev * alpha + p.sum(axis=-1)
    m_ref[:, 0] = m_new
    # p rounds to the v dtype for the second MXU pass (standard flash
    # practice: p is in [0, 1], the f32 accumulator absorbs the sum).
    acc_ref[:] = acc_ref[:] * alpha[:, None] + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _flash_kernel(qt_ref, kt_ref, flags_ref, q_ref, k_ref, v_ref, o_ref,
                  *rest, block_q: int, block_k: int, causal: bool,
                  scale: float, emit_stats: bool = False,
                  emit_lse: bool = False, window=None):
    from jax.experimental import pallas as pl

    if emit_stats:
        m_out_ref, l_out_ref, acc_ref, m_ref, l_ref = rest
    elif emit_lse:
        lse_ref, acc_ref, m_ref, l_ref = rest
    else:
        acc_ref, m_ref, l_ref = rest

    item = pl.program_id(2)
    flags = flags_ref[item]
    q_off, k_off = qt_ref[item] * block_q, kt_ref[item] * block_k

    @pl.when((flags & _FIRST) != 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[:] = jnp.zeros_like(l_ref)

    _softmax_tile(
        _tile(q_ref), _tile(k_ref), v_ref[0, 0, :, :],
        lambda s: _mask_causal(s, causal, q_off, k_off, block_q, block_k,
                               window),
        acc_ref, m_ref, l_ref, scale, rows_may_be_dead=window is not None)

    @pl.when((flags & _LAST) != 0)
    def _emit():
        if emit_stats:
            # Unnormalized accumulator + online-softmax stats, f32: the
            # caller (ring attention's cross-device merge) rescales and
            # normalizes once after combining every block's contribution.
            o_ref[0, 0, :, :] = acc_ref[:]
            m_out_ref[0, 0, :, :] = m_ref[:]
            l_out_ref[0, 0, :, :] = l_ref[:]
        else:
            o_ref[0, 0, :, :] = (acc_ref[:] / l_ref[:, 0][:, None]).astype(
                o_ref.dtype)
            if emit_lse:
                # logsumexp per q row — the softmax residual the flash
                # backward kernels re-exponentiate against.
                lse_ref[0, 0, :, :] = m_ref[:] + jnp.log(l_ref[:])


def _q_walk_specs(block_q: int, block_k: int, rep: int):
    """Block specs of the forward and the dQ kernel over
    :func:`_q_schedule`'s arrays ``(q tile, K/V tile, flags)`` ->
    ``(q_rows, kv_rows, stat_spec)``: ``q_rows(width)`` reads a (b, h,
    rows, width) operand a q tile at a time, ``kv_rows(width)`` K or V a
    tile at a time from the q head's kv head (``kv_rows(width, one=True)``
    from head 0: a split key's one rotary head), and ``stat_spec`` the (b,
    h, seq, 1) row statistics."""
    from jax.experimental import pallas as pl

    def q_index(bi, hi, item, qt, kt, flags):
        return bi, hi, qt[item], 0

    def kv_index(bi, hi, item, qt, kt, flags):
        return bi, hi // rep, kt[item], 0

    def one_index(bi, hi, item, qt, kt, flags):
        return bi, 0, kt[item], 0
    return (lambda width: pl.BlockSpec((1, 1, block_q, width), q_index),
            lambda width, one=False: pl.BlockSpec(
                (1, 1, block_k, width), one_index if one else kv_index),
            pl.BlockSpec((1, 1, block_q, 1), q_index))


def _q_specs(q, q_rows):
    """``q_rows`` of each part of a (b, h, seq, width) q-side operand."""
    if isinstance(q, tuple):
        return tuple(q_rows(part.shape[3]) for part in q)
    return q_rows(q.shape[3])


def _key_specs(k, kv_rows):
    """``kv_rows`` of a (b, kv_h, seq, width) key, or of a split key's
    parts: its position-free part a head per kv head, its rotary part the
    one head all share."""
    if isinstance(k, tuple):
        return (kv_rows(k[0].shape[3]), kv_rows(k[1].shape[3], one=True))
    return kv_rows(k.shape[3])


def _to_kernel(x):
    """(b, seq, heads, width) -> the kernels' (b, heads, seq, width), a
    split operand part by part (and back: the transpose is its own
    inverse)."""
    return jax.tree.map(lambda a: a.transpose(0, 2, 1, 3), x)


def _flash_launch(q, k, v, causal: bool, block_q: int, block_k: int,
                  interpret: bool, mode: str, window=None):
    """One launcher for every forward variant — same grid, BlockSpecs and
    scratch; ``mode`` picks the kernel's emit: ``"out"`` (normalized
    output), ``"lse"`` (output + logsumexp, the backward's residual), or
    ``"stats"`` (unnormalized o + m/l, the ring-merge contract). The grid
    is ``(b, h, items)`` over :func:`_q_schedule`; with a ``window`` the
    call is named ``swa_fwd``.

    Kernel-internal layout is (b, heads, seq, d): Mosaic requires the
    block's minor-most two dims to tile as (sublane, lane) — (block_q, d)
    satisfies the (8, 128) granule, whereas the model-side (b, seq,
    heads, d) layout would put a size-1 block dim over the heads axis,
    which the TPU lowering rejects. XLA fuses the boundary transposes
    into the surrounding copies."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, sq, h, _ = _lead(q).shape
    sk, kv_h, vd = _lead(k).shape[1], _lead(k).shape[2], v.shape[3]
    d = sum(part.shape[3] for part in _parts(q))
    sched = _q_schedule(_live_tiles(sq, sk, block_q, block_k, causal, window))
    kernel = partial(_flash_kernel, block_q=block_q, block_k=block_k,
                     causal=causal, scale=1.0 / np.sqrt(d),
                     emit_stats=(mode == "stats"), emit_lse=(mode == "lse"),
                     window=window)
    q_rows, kv_rows, stat_spec = _q_walk_specs(block_q, block_k, h // kv_h)
    o_spec = q_rows(vd)
    stat_shape = jax.ShapeDtypeStruct((b, h, sq, 1), jnp.float32)
    dtype = _lead(q).dtype
    if mode == "out":
        out_specs = o_spec
        out_shape = jax.ShapeDtypeStruct((b, h, sq, vd), dtype)
    elif mode == "lse":
        out_specs = [o_spec, stat_spec]
        out_shape = [jax.ShapeDtypeStruct((b, h, sq, vd), dtype),
                     stat_shape]
    else:  # stats: unnormalized f32 accumulator + m/l
        out_specs = [o_spec, stat_spec, stat_spec]
        out_shape = [jax.ShapeDtypeStruct((b, h, sq, vd), jnp.float32),
                     stat_shape, stat_shape]
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(sched), grid=(b, h, len(sched[0])),
            in_specs=[_q_specs(q, q_rows), _key_specs(k, kv_rows),
                      kv_rows(vd)],
            out_specs=out_specs,
            scratch_shapes=[
                pltpu.VMEM((block_q, vd), jnp.float32),     # acc
                pltpu.VMEM((block_q, 1), jnp.float32),      # running max m
                pltpu.VMEM((block_q, 1), jnp.float32),      # normalizer l
            ]),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="flash_fwd" if window is None else "swa_fwd",
    )(*sched, _to_kernel(q), _to_kernel(k), v.transpose(0, 2, 1, 3))


def _flash_forward(q, k, v, causal: bool, block_q: int, block_k: int,
                   interpret: bool, window=None):
    out = _flash_launch(q, k, v, causal, block_q, block_k, interpret, "out",
                        window)
    return out.transpose(0, 2, 1, 3)


def _flash_forward_lse(q, k, v, causal: bool, block_q: int, block_k: int,
                       interpret: bool, window=None):
    """Forward that also emits logsumexp per q row — the residual the
    Pallas backward needs. Returns (o (b, sq, h, d) in q.dtype,
    lse (b, h, sq, 1) f32 — KERNEL layout: only the backward launch
    consumes it, so the model-side transpose round-trip is skipped)."""
    o, lse = _flash_launch(q, k, v, causal, block_q, block_k, interpret,
                           "lse", window)
    return o.transpose(0, 2, 1, 3), lse


def _flash_stats_forward(q, k, v, causal: bool, block_q: int, block_k: int,
                         interpret: bool):
    """Kernel launch emitting the ring-merge contract:
    (unnormalized o f32 (b, sq, h, d), running max m (b, sq, h),
    normalizer l (b, sq, h))."""
    o, m, l = _flash_launch(q, k, v, causal, block_q, block_k, interpret,
                            "stats")
    return (o.transpose(0, 2, 1, 3), m[..., 0].transpose(0, 2, 1),
            l[..., 0].transpose(0, 2, 1))


def _bwd_p_ds(q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref, q_off, k_off,
              block_q, block_k, causal, scale, window=None):
    """Shared softmax-gradient tile math for every backward kernel:
    recompute scores from the refs, re-exponentiate against the saved
    lse (lse >= running max, so exp(s - lse) <= 1), and return
    ``(p, ds)`` with ``ds`` already scaled — keeping the numerics in ONE
    place so dQ and dK/dV cannot drift apart."""
    return _p_ds_tile(
        _tile(q_ref), _tile(k_ref), v_ref[0, 0, :, :],
        do_ref[0, 0, :, :], lse_ref[0, 0, :, 0], dd_ref[0, 0, :, 0],
        lambda s: _mask_causal(s, causal, q_off, k_off, block_q, block_k,
                               window), scale)


def _p_ds_tile(q, k, v, do, lse, dd, mask, scale: float):
    """The tile math of :func:`_bwd_p_ds` on loaded tiles: q (bq, d), do
    (bq, vd); k (bk, d), v (bk, vd); lse, dd (bq,); ``mask`` a callable on
    the score tile. q and k may be split operands' pairs of tiles
    (:func:`_scores`)."""
    s = _scores(q, k) * scale
    s = mask(s)
    p = jnp.exp(s - lse[:, None])                               # (bq, bk)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - dd[:, None]) * scale
    return p, ds


def _flash_bwd_kernel(kt_ref, head_ref, qt_ref, flags_ref, k_ref, v_ref,
                      q_ref, do_ref, lse_ref, dd_ref, dq_ref, dk_ref, dv_ref,
                      dq_acc, dk_acc, dv_acc, *, block_q: int, block_k: int,
                      causal: bool, scale: float, window=None):
    """The whole backward in one pass: grid (b, kv_heads, items) over
    :func:`_bwd_schedule`. S, P, dP and dS are made once a live tile and
    all three gradients taken from them: dq accumulates in a tile of
    scratch across a q tile's K/V tiles as in the dQ pass, dk and dv in
    float32 scratch that holds the whole K/V head (every head of the
    group adds into it), written out at the head's last item. With split
    operands q, k, dq, dk and their scratch are pairs; the rotary key's dk,
    one head for every query head, holds the batch row's whole walk, every
    K/V head adding into it, and is written out at its last item."""
    from jax.experimental import pallas as pl

    item = pl.program_id(2)
    flags = flags_ref[item]
    q_off, k_off = qt_ref[item] * block_q, kt_ref[item] * block_k
    dk_head, dk_row = (dk_acc if isinstance(dk_acc, tuple)
                       else (dk_acc, None))

    @pl.when(item == 0)
    def _init_head():
        dk_head[:] = jnp.zeros_like(dk_head)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    if dk_row is not None:
        @pl.when(jnp.logical_and(item == 0, pl.program_id(1) == 0))
        def _init_row():
            dk_row[:] = jnp.zeros_like(dk_row)

    @pl.when((flags & _FIRST) != 0)
    def _init():
        for acc in _parts(dq_acc):
            acc[:] = jnp.zeros_like(acc)

    p, ds = _bwd_p_ds(q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref, q_off,
                      k_off, block_q, block_k, causal, scale, window)
    q, k, do = _tile(q_ref), _tile(k_ref), do_ref[0, 0, :, :]
    rows = pl.ds(pl.multiple_of(k_off, block_k), block_k)
    dv_acc[rows, :] += jax.lax.dot_general(
        p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                     # (bk, vd)
    ds = ds.astype(_lead(q).dtype)
    _accumulate(dk_acc, rows, lambda: jax.lax.dot_general(
        ds, _joined(q), (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32))                    # (bk, d)
    _accumulate(dq_acc, None, lambda: jax.lax.dot_general(
        ds, _joined(k), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32))                    # (bq, d)

    @pl.when((flags & _LAST) != 0)
    def _emit():
        for out, acc in zip(_parts(dq_ref), _parts(dq_acc)):
            out[0, 0, :, :] = acc[:].astype(out.dtype)

    @pl.when(item == pl.num_programs(2) - 1)
    def _emit_head():
        out = _lead(dk_ref)
        out[0, 0, :, :] = dk_head[:].astype(out.dtype)
        dv_ref[0, 0, :, :] = dv_acc[:].astype(dv_ref.dtype)

    if dk_row is not None:
        @pl.when(jnp.logical_and(item == pl.num_programs(2) - 1,
                                 pl.program_id(1) == pl.num_programs(1) - 1))
        def _emit_row():
            dk_ref[1][0, 0, :, :] = dk_row[:].astype(dk_ref[1].dtype)


def _flash_bwd_dq_kernel(qt_ref, kt_ref, flags_ref, q_ref, k_ref, v_ref,
                         do_ref, lse_ref, dd_ref, dq_ref, dq_acc, *,
                         block_q: int, block_k: int, causal: bool,
                         scale: float, window=None):
    """dQ pass (flash-attention-2 backward): grid (b, h, items) over
    :func:`_q_schedule`, a q tile's K/V tiles innermost; dq accumulates in
    VMEM scratch across them (a pair of them for split operands). P is
    re-exponentiated from the saved lse, so no softmax state needs
    carrying."""
    from jax.experimental import pallas as pl

    item = pl.program_id(2)
    flags = flags_ref[item]
    q_off, k_off = qt_ref[item] * block_q, kt_ref[item] * block_k

    @pl.when((flags & _FIRST) != 0)
    def _init():
        for acc in _parts(dq_acc):
            acc[:] = jnp.zeros_like(acc)

    _, ds = _bwd_p_ds(q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref, q_off,
                      k_off, block_q, block_k, causal, scale, window)
    k = _tile(k_ref)
    _accumulate(dq_acc, None, lambda: jax.lax.dot_general(
        ds.astype(_lead(k).dtype), _joined(k), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32))

    @pl.when((flags & _LAST) != 0)
    def _emit():
        for out, acc in zip(_parts(dq_ref), _parts(dq_acc)):
            out[0, 0, :, :] = acc[:].astype(out.dtype)


def _flash_bwd_dkv_kernel(kt_ref, head_ref, qt_ref, flags_ref, k_ref, v_ref,
                          q_ref, do_ref, lse_ref, dd_ref, dk_ref, dv_ref,
                          dk_acc, dv_acc, *, block_q: int, block_k: int,
                          causal: bool, scale: float, window=None):
    """dK/dV pass: grid (b, kv_heads, items) over :func:`_kv_schedule` —
    the innermost dimension walks every (grouped-query head, live q tile)
    pair that attends to a K/V tile, accumulating dk/dv in VMEM scratch
    (GQA gradients sum over the head group here instead of a host-side
    reduction over repeated K/V). A split key's two parts accumulate
    alike: the rotary part's gradient leaves a K/V head at a time, for its
    caller to sum over the heads."""
    from jax.experimental import pallas as pl

    item = pl.program_id(2)
    flags = flags_ref[item]
    q_off, k_off = qt_ref[item] * block_q, kt_ref[item] * block_k

    @pl.when((flags & _FIRST) != 0)
    def _init():
        for acc in _parts(dk_acc):
            acc[:] = jnp.zeros_like(acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    p, ds = _bwd_p_ds(q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref, q_off,
                      k_off, block_q, block_k, causal, scale, window)
    q = _tile(q_ref)
    do = do_ref[0, 0, :, :]
    dv_acc[:] += jax.lax.dot_general(
        p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                     # (bk, vd)
    _accumulate(dk_acc, None, lambda: jax.lax.dot_general(
        ds.astype(_lead(q).dtype), _joined(q), (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32))                    # (bk, d)

    @pl.when((flags & _LAST) != 0)
    def _emit():
        for out, acc in zip(_parts(dk_ref), _parts(dk_acc)):
            out[0, 0, :, :] = acc[:].astype(out.dtype)
        dv_ref[0, 0, :, :] = dv_acc[:].astype(dv_ref.dtype)


def _kv_walk_specs(block_q: int, block_k: int, rep: int):
    """Block specs of the backward kernels whose grid is ``(b, kv_heads,
    items)`` over arrays ``(K/V tile, head in group, q tile, flags)``
    (:func:`_kv_schedule`, :func:`_bwd_schedule`) -> ``(q_rows, kv_rows,
    stat_spec)`` as :func:`_q_walk_specs` gives them: the q-side operands
    are read from the item's head of the group."""
    from jax.experimental import pallas as pl

    def kv_index(bi, gi, item, kt, head, qt, flags):
        return bi, gi, kt[item], 0

    def one_index(bi, gi, item, kt, head, qt, flags):
        return bi, 0, kt[item], 0

    def q_index(bi, gi, item, kt, head, qt, flags):
        return bi, gi * rep + head[item], qt[item], 0
    return (lambda width: pl.BlockSpec((1, 1, block_q, width), q_index),
            lambda width, one=False: pl.BlockSpec(
                (1, 1, block_k, width), one_index if one else kv_index),
            pl.BlockSpec((1, 1, block_q, 1), q_index))


def _widths(x) -> tuple:
    return tuple(part.shape[3] for part in _parts(x))


def _flash_backward(q, k, v, o, lse, do, causal: bool, block_q: int,
                    block_k: int, interpret: bool, window=None):
    """Pallas flash backward: one kernel that makes S, P, dP and dS once a
    live tile (:func:`_flash_backward_one`) where a K/V head's float32 dK
    and dV fit VMEM (:func:`_bwd_vmem_limit`), else the pair of a
    kv-innermost dQ pass and a q-innermost dK/dV pass
    (:func:`_flash_backward_pair`); in-kernel GQA group accumulation and
    no O(seq^2) or O(block*seq) HBM tensors either way — the memory story
    of the forward, extended to training. Split q and k get split
    gradients."""
    widths, sk, vd = _widths(q), _lead(k).shape[1], v.shape[3]
    d = sum(widths)
    # D_i = rowsum(dO ∘ O): O(seq·d) elementwise, fine outside the kernel.
    dd = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    operands = (_to_kernel(q), _to_kernel(k),
                v.transpose(0, 2, 1, 3), do.transpose(0, 2, 1, 3),
                lse,                            # already (b, h, sq, 1)
                dd.transpose(0, 2, 1)[..., None])
    tile = dict(block_q=block_q, block_k=block_k, causal=causal,
                scale=1.0 / np.sqrt(d), window=window)
    limit = _bwd_vmem_limit(sk, widths, vd, _lead(k).dtype.itemsize,
                            block_q, block_k)
    grads = (_flash_backward_pair(*operands, tile, interpret) if limit is None
             else _flash_backward_one(*operands, tile, interpret, limit))
    return tuple(_to_kernel(g) for g in grads)


def _scratch(x, rows: int):
    """float32 VMEM scratch of ``rows`` a part of operand ``x``."""
    from jax.experimental.pallas import tpu as pltpu
    if isinstance(x, tuple):
        return tuple(_scratch(part, rows) for part in x)
    return pltpu.VMEM((rows, x.shape[3]), jnp.float32)


def _flash_backward_one(qT, kT, vT, doT, lseT, ddT, tile: dict,
                        interpret: bool, vmem_limit: int):
    """``flash_bwd`` (with a window ``swa_bwd``): grid (b, kv_heads,
    items) over :func:`_bwd_schedule`; a K/V head's dK and dV stay in
    float32 scratch for the whole walk and leave as whole-head blocks; a
    split key's rotary dK, summed over every head, leaves as one block a
    batch row."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    (b, h, sq, _), (_, kv_h, sk, vd) = _lead(qT).shape, vT.shape
    block_q, block_k, rep = tile["block_q"], tile["block_k"], h // kv_h
    sched = _bwd_schedule(_live_tiles(sq, sk, block_q, block_k,
                                      tile["causal"], tile["window"]), rep)
    q_rows, kv_rows, stat_spec = _kv_walk_specs(block_q, block_k, rep)

    def kv_head(width):
        return pl.BlockSpec((1, 1, sk, width),
                            lambda bi, gi, item, *sched: (bi, gi, 0, 0))

    own = _lead(kT)
    dk_spec = kv_head(own.shape[3])
    dk_shape = jax.ShapeDtypeStruct((b, kv_h, sk, own.shape[3]), own.dtype)
    if isinstance(kT, tuple):   # the rotary key's dK: one block a batch row
        rot = kT[1]
        dk_spec = (dk_spec, pl.BlockSpec(
            (1, 1, sk, rot.shape[3]),
            lambda bi, gi, item, *sched: (bi, 0, 0, 0)))
        dk_shape = (dk_shape, jax.ShapeDtypeStruct((b, 1, sk, rot.shape[3]),
                                                   rot.dtype))
    return pl.pallas_call(
        partial(_flash_bwd_kernel, **tile),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(sched), grid=(b, kv_h, len(sched[0])),
            in_specs=[_key_specs(kT, kv_rows), kv_rows(vd),
                      _q_specs(qT, q_rows), q_rows(vd), stat_spec, stat_spec],
            out_specs=[_q_specs(qT, q_rows), dk_spec, kv_head(vd)],
            scratch_shapes=[_scratch(qT, block_q), _scratch(kT, sk),
                            pltpu.VMEM((sk, vd), jnp.float32)]),
        out_shape=[jax.tree.map(lambda a: jax.ShapeDtypeStruct(
                       a.shape, a.dtype), qT),
                   dk_shape,
                   jax.ShapeDtypeStruct((b, kv_h, sk, vd), vT.dtype)],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem_limit),
        interpret=interpret,
        name="flash_bwd" if tile["window"] is None else "swa_bwd",
    )(*sched, kT, vT, qT, doT, lseT, ddT)


def _flash_backward_pair(qT, kT, vT, doT, lseT, ddT, tile: dict,
                         interpret: bool):
    """The backward at tile residency, for a K/V head whose float32 dK and
    dV do not fit VMEM: ``flash_bwd_dq`` over :func:`_q_schedule` and
    ``flash_bwd_dkv`` over :func:`_kv_schedule` (``swa_*`` with a
    window), each making S, P, dP and dS for itself. A split key's rotary
    dK leaves the second call a K/V head at a time, in float32, and is
    summed over the heads here."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    (b, h, sq, _), (_, kv_h, sk, vd) = _lead(qT).shape, vT.shape
    block_q, block_k = tile["block_q"], tile["block_k"]
    rep = h // kv_h
    prefix = "flash" if tile["window"] is None else "swa"
    live = _live_tiles(sq, sk, block_q, block_k, tile["causal"],
                       tile["window"])
    sched = _q_schedule(live)
    q_rows, kv_rows, stat_spec = _q_walk_specs(block_q, block_k, rep)
    dq, = pl.pallas_call(
        partial(_flash_bwd_dq_kernel, **tile),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(sched), grid=(b, h, len(sched[0])),
            in_specs=[_q_specs(qT, q_rows), _key_specs(kT, kv_rows),
                      kv_rows(vd), q_rows(vd), stat_spec, stat_spec],
            out_specs=[_q_specs(qT, q_rows)],
            scratch_shapes=[_scratch(qT, block_q)]),
        out_shape=[jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype), qT)],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret, name=f"{prefix}_bwd_dq",
    )(*sched, qT, kT, vT, doT, lseT, ddT)

    sched = _kv_schedule(live, rep)     # (K/V tile, head in group, q tile, .)
    q_rows, kv_rows, stat_spec = _kv_walk_specs(block_q, block_k, rep)

    def dk_part(part, dtype):
        return jax.ShapeDtypeStruct((b, kv_h, sk, part.shape[3]), dtype)
    dk_shape = ((dk_part(kT[0], kT[0].dtype), dk_part(kT[1], jnp.float32))
                if isinstance(kT, tuple) else dk_part(kT, kT.dtype))
    dk, dv = pl.pallas_call(
        partial(_flash_bwd_dkv_kernel, **tile),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(sched), grid=(b, kv_h, len(sched[0])),
            in_specs=[_key_specs(kT, kv_rows), kv_rows(vd),
                      _q_specs(qT, q_rows), q_rows(vd), stat_spec, stat_spec],
            out_specs=[_q_specs(kT, kv_rows), kv_rows(vd)],
            scratch_shapes=[_scratch(kT, block_k),
                            pltpu.VMEM((block_k, vd), jnp.float32)]),
        out_shape=[dk_shape,
                   jax.ShapeDtypeStruct((b, kv_h, sk, vd), vT.dtype)],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret, name=f"{prefix}_bwd_dkv",
    )(*sched, kT, vT, qT, doT, lseT, ddT)
    if isinstance(kT, tuple):
        dk = (dk[0], dk[1].sum(axis=1, keepdims=True).astype(kT[1].dtype))
    return dq, dk, dv


def _dense_stats(q, k, v, causal: bool, block_q: int):
    """The kernel's stats contract computed through the ring's chunked
    dense block math — the fallback path AND the backward-recompute body
    (one numerics home: f32 scores, GQA grouping, per-chunk remat).
    Returns (o_unnormalized f32 (b, sq, h, d), m (b, sq, h), l (b, sq, h))."""
    from petastorm_tpu.parallel.ring_attention import _block_attention_chunked

    sq, sk = q.shape[1], k.shape[1]
    bq = min(block_q, sq)
    if sq % bq:
        bq = sq  # chunking needs divisibility; fall back to one dense block
    o, m, l = _block_attention_chunked(
        q, k, v, k_pos=jnp.arange(sk), q_pos=jnp.arange(sq), causal=causal,
        block_q=bq)
    # ring layout (b, h, lq) -> kernel layout (b, sq, h)
    return o, m.transpose(0, 2, 1), l.transpose(0, 2, 1)


@partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _flash_stats_vjp(causal, block_q, block_k, interpret, q, k, v):
    return _flash_stats_forward(q, k, v, causal, block_q, block_k, interpret)


def _flash_stats_vjp_fwd(causal, block_q, block_k, interpret, q, k, v):
    return (_flash_stats_forward(q, k, v, causal, block_q, block_k,
                                 interpret), (q, k, v))


def _flash_stats_vjp_bwd(causal, block_q, block_k, interpret, residual, g):
    # Pallas kernels are not auto-differentiable: recompute through the
    # chunked dense stats (mathematically the same function) and pull the
    # (do, dm, dl) cotangents back through it. The ring's merge consumes
    # m and l, so their cotangents are live, not zero.
    q, k, v = residual
    _, vjp = jax.vjp(
        lambda q_, k_, v_: _dense_stats(q_, k_, v_, causal, block_q), q, k, v)
    return vjp(g)


_flash_stats_vjp.defvjp(_flash_stats_vjp_fwd, _flash_stats_vjp_bwd)


def flash_attention_stats(q, k, v, causal: bool = False,
                          block_q: int = _DEFAULT_BLOCK_Q,
                          block_k: int = _DEFAULT_BLOCK_K, interpret=None):
    """Flash kernel emitting the online-softmax partials instead of the
    normalized output: ``(o_unnormalized f32, m, l)``, each ``(b, sq, h,
    d)`` / ``(b, sq, h)`` — the contract ring attention's cross-device
    merge consumes (``parallel.ring_attention`` step carry). Falls back to
    the chunked dense path on shapes the kernel can't tile, numerically
    identical. Differentiable via dense recompute (``custom_vjp``)."""
    b, sq, h, d = q.shape
    sk, kv_h = k.shape[1], k.shape[2]
    if h % kv_h:
        raise ValueError(f"heads ({h}) must be a multiple of kv_heads ({kv_h})")
    tiles = _tiles(sq, sk, causal, block_q, block_k)
    if tiles is None:
        return _dense_stats(q, k, v, causal, _pick_block(block_q, sq))
    return _flash_stats_vjp(causal, *tiles, _resolve_interpret(interpret),
                            q, k, v)


def _dense(q, k, v, causal, window=None):
    from petastorm_tpu.parallel.attention import dense_attention
    return dense_attention(q, k, v, causal=causal, window=window)


@partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3, 4))
def _flash_vjp(causal, block_q, block_k, interpret, window, q, k, v):
    return _flash_forward(q, k, v, causal, block_q, block_k, interpret,
                          window)


def _flash_vjp_fwd(causal, block_q, block_k, interpret, window, q, k, v):
    # The lse-emitting launch costs one extra (b, h, sq) f32 write over
    # the plain forward and saves the backward an entire forward
    # recompute (the old chunked-dense bwd re-ran the whole attention).
    o, lse = _flash_forward_lse(q, k, v, causal, block_q, block_k,
                                interpret, window)
    # Named for a checkpoint around the caller to keep (SAVED_NAMES). The
    # primal output is the named ``o``, so that what follows the kernel
    # reads the saved array too and the recomputation needs no second
    # launch. ``lse`` is kept as (b, h, sq): the trailing 1 of its kernel
    # layout pads to a lane tile of 128 in HBM.
    o = checkpoint_name(o, SAVED_NAMES[0])
    lse = checkpoint_name(lse[..., 0], SAVED_NAMES[1])
    return o, (q, k, v, o, lse)


def _flash_vjp_bwd(causal, block_q, block_k, interpret, window, residual, g):
    q, k, v, o, lse = residual
    return _flash_backward(q, k, v, o, lse[..., None], g, causal, block_q,
                           block_k, interpret, window)


_flash_vjp.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_attention(q, k, v, causal: bool = False,
                    block_q: int = _DEFAULT_BLOCK_Q,
                    block_k: int = _DEFAULT_BLOCK_K,
                    interpret=None, window=None):
    """Drop-in for :func:`...parallel.attention.dense_attention`:
    q ``(b, sq, heads, d)``, k ``(b, sk, kv_heads, d)``, v ``(b, sk,
    kv_heads, vd)`` -> ``(b, sq, heads, vd)``, grouped-query native; ``vd``
    need not be ``d`` (the scores' scale is the key width's). ``window`` (static, needs
    ``causal``) keeps of each query's keys its own and the ``window - 1``
    before it; the schedule then lists the band's tiles alone and the
    calls are named ``swa_fwd`` / ``swa_bwd`` (``swa_bwd_dq`` /
    ``swa_bwd_dkv`` where a K/V head's gradients do not fit VMEM).

    q and k may instead be pairs, q ``(q_nope (b, sq, heads, dn), q_rope
    (b, sq, heads, dr))`` and k ``(k_nope (b, sk, kv_heads, dn), k_rope
    (b, sk, 1, dr))``: the scores are ``q_nope k_nope^T + q_rope
    k_rope^T`` scaled by ``1 / sqrt(dn + dr)``, the rotary key one head
    for every query head (latent attention, ``llama``
    ``attention="mla"``), and the gradients of q and k come back as such
    pairs. The calls keep their names.

    Falls back to the dense path when the shape can't tile onto the
    hardware (:func:`_tiles`). ``interpret=None`` selects the Pallas
    interpreter on the ``cpu`` backend only, so tests run there
    (:func:`_resolve_interpret`).
    """
    if isinstance(q, tuple) or isinstance(k, tuple):
        _check_split(q, k)
    b, sq, h, _ = _lead(q).shape
    sk, kv_h = _lead(k).shape[1], _lead(k).shape[2]
    if h % kv_h:
        raise ValueError(f"heads ({h}) must be a multiple of kv_heads ({kv_h})")
    if window is not None and (not causal or window < 1):
        raise ValueError(f"window ({window}) needs causal=True and >= 1")
    tiles = _tiles(sq, sk, causal, block_q, block_k)
    if tiles is None:
        return _dense(q, k, v, causal, window)
    return _flash_vjp(causal, *tiles, _resolve_interpret(interpret), window,
                      q, k, v)


def _check_split(q, k):
    """Split q and k are two pairs whose parts agree in width, the key's
    rotary part one head."""
    if not (isinstance(q, tuple) and isinstance(k, tuple)
            and len(q) == len(k) == 2 and _widths(q) == _widths(k)
            and k[1].shape[2] == 1):
        raise ValueError(
            "split operands are q = (q_nope, q_rope) and k = (k_nope, "
            "k_rope) of equal part widths, k_rope one head: got q "
            f"{jax.tree.map(jnp.shape, q)}, k {jax.tree.map(jnp.shape, k)}")


def make_flash_attention(causal: bool = True, block_q: int = _DEFAULT_BLOCK_Q,
                         block_k: int = _DEFAULT_BLOCK_K, interpret=None,
                         window=None):
    """An ``attn_fn`` for :func:`petastorm_tpu.models.llama.apply`
    (``supports_gqa``: K/V arrive at native kv-head width; q and k as
    :func:`flash_attention` takes them, arrays or split pairs); with a
    ``window`` the one for the model's sliding-window layers
    (``window_attn_fn``). Its caller asked for the kernel, so a shape the
    tiles cannot divide raises (:func:`require_flash_tiles`) instead of
    taking the dense route."""
    def attn(q, k, v):
        require_flash_tiles(_lead(q).shape[1], _lead(k).shape[1], causal,
                            block_q, block_k)
        return flash_attention(q, k, v, causal=causal, block_q=block_q,
                               block_k=block_k, interpret=interpret,
                               window=window)
    attn.supports_gqa = True
    return attn
