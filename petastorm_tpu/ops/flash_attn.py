"""Flash attention as a Pallas TPU kernel: O(seq) memory attention.

XLA's plain softmax attention materializes the O(seq^2) score matrix in
HBM; this kernel never does. Design (flash-attention-2 style, TPU-first):

* grid = (batch, q_heads, q_blocks, kv_blocks), kv innermost — TPU grids
  execute sequentially, so the online-softmax state (running max ``m``,
  normalizer ``l``, unnormalized accumulator ``acc``) lives in VMEM
  scratch carried across the kv dimension. VMEM holds ONE q tile and ONE
  K/V tile at a time (O(block * d), not O(seq * d)), which is what makes
  long sequences fit;
* grouped-query attention is native: the K/V BlockSpec index-maps the
  q-head grid coordinate onto its kv head (``h // rep``) — K/V are never
  repeated in memory;
* causal programs whose K/V tile lies entirely above the diagonal skip
  the matmuls via ``pl.when`` (the tile DMA still happens — acceptable:
  bandwidth is prefetch-pipelined, MXU time is not);
* a static ``window`` (with ``causal``) is sliding-window attention: the
  mask also drops keys ``window`` or more behind their query, and the
  innermost grid axis walks only the tiles of the band (:func:`_band`),
  so dead tiles on either side cost neither MXU work nor a grid step;
  the three calls are then named ``swa_fwd`` / ``swa_bwd_dq`` /
  ``swa_bwd_dkv`` (one kernel body per pass, the window an argument);
* scores accumulate in float32 regardless of input dtype (numerics parity
  with :func:`petastorm_tpu.parallel.attention.dense_attention`);
* the backward pass is two Pallas kernels (flash-attention-2 style,
  ``custom_vjp``): the forward saves ``(q, k, v, o, lse)``, then a
  kv-innermost pass accumulates dQ and a q-innermost pass accumulates
  dK/dV — with grouped-query head gradients summed inside the kernel by
  walking every (group head, q block) pair over one K/V tile. No
  O(seq^2) or O(block*seq) tensors touch HBM in training either. The
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
# Launch defaults: bigger tiles amortize per-program overhead (an 8k seq
# at 128x128 is a 32k-program grid; at 256x1024 it is 1k) while staying
# far under VMEM (q 64KB + k/v 256KB each + f32 scores 1MB per step).
# 256x1024 measured fastest of a 6-config on-chip sweep at both 8k
# (10.2 ms vs 11.6 at 256x512) and near-best at 16k (14.3 vs 17.5) —
# TPU v5 lite, 2026-07-31; fewer kv iterations amortize the K/V DMA.
# Seqs the big tiles don't divide step down to _DEFAULT_BLOCK before
# falling back to dense, so the kernel-path coverage of the old 128
# defaults (e.g. seq 1280) is preserved.
_DEFAULT_BLOCK_Q = 256
_DEFAULT_BLOCK_K = 1024


def _pick_block(requested: int, seq: int) -> int:
    """Clamp ``requested`` to ``seq``; if it doesn't divide, retry the
    128 granule before :func:`_tiles` rejects it."""
    blk = min(requested, seq)
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


def _causal_live(causal: bool, q_off, k_off, block_q: int,
                 block_k: int = 0, window=None):
    """True when this (q tile, kv tile) pair has any element inside the
    mask: on or below the diagonal and, with a ``window``, fewer than
    ``window`` keys behind its query — the skip predicate shared by the
    forward and both backward kernels."""
    live = jnp.logical_or(not causal, q_off + block_q - 1 >= k_off)
    if window is not None:
        live = jnp.logical_and(live, q_off - (k_off + block_k - 1) < window)
    return live


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


def _band(window, block_in: int, block_out: int, n_out: int, behind: int):
    """The tiles of the walked (innermost) grid axis that one tile of the
    outer axis can see -> ``(first(outer index), tiles walked)``.

    Without a window every tile is walked from 0, as ever. With one, a q
    tile (walking kv: ``behind = window - 1``) sees keys ``q_off - window
    + 1 .. q_off + block_q - 1`` and a kv tile (walking q: ``behind = 0``)
    is seen by queries ``k_off .. k_off + block_k + window - 2``: in both
    ``window + block_in - 1`` positions, which touch at most ``(window +
    block_in - 2) // block_out + 2`` tiles. The grid walks only those, so
    dead tiles on either side of the band cost no grid step; an index past
    the last tile is clamped in the index maps and dead in the kernel.
    """
    if window is None:
        return (lambda i: 0), n_out
    walked = min(n_out, (window + block_in - 2) // block_out + 2)
    return (lambda i: jnp.maximum(i * block_in - behind, 0) // block_out,
            walked)


def _softmax_tile(q, k, v, mask, acc_ref, m_ref, l_ref, scale: float,
                  rows_may_be_dead: bool = False):
    """One (q tile, key tile) step of the online softmax, the tile body of
    every forward kernel here and of :mod:`petastorm_tpu.ops.eva_attn`:
    scores ``q k^T * scale`` through ``mask`` (a callable on the float32
    score tile), then the running max ``m_ref``, normalizer ``l_ref`` and
    unnormalized accumulator ``acc_ref`` (VMEM scratch) take the tile in.

    Matmuls stay in the input dtype (bf16 on the training path) with f32
    accumulation — the MXU's native mode; upcasting the operands to f32
    first would run the systolic array at a fraction of peak. All softmax
    bookkeeping (max, exp, normalizer) is f32."""
    s = jax.lax.dot_general(                                     # (bq, bk)
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
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


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *rest, block_q: int,
                  block_k: int, causal: bool, scale: float,
                  emit_stats: bool = False, emit_lse: bool = False,
                  window=None, k_first=lambda qi: 0):
    from jax.experimental import pallas as pl

    if emit_stats:
        m_out_ref, l_out_ref, acc_ref, m_ref, l_ref = rest
    elif emit_lse:
        lse_ref, acc_ref, m_ref, l_ref = rest
    else:
        acc_ref, m_ref, l_ref = rest

    qi, step = pl.program_id(2), pl.program_id(3)
    n_steps = pl.num_programs(3)
    ki = k_first(qi) + step     # the kv tile: the band's with a window

    @pl.when(step == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[:] = jnp.zeros_like(l_ref)

    q_off, k_off = qi * block_q, ki * block_k
    # Tiles fully above the causal diagonal (or wholly behind the window)
    # contribute nothing: skip the MXU work (roughly halves causal kernel
    # time at long seq).
    live = _causal_live(causal, q_off, k_off, block_q, block_k, window)

    @pl.when(live)
    def _step():
        _softmax_tile(
            q_ref[0, 0, :, :], k_ref[0, 0, :, :], v_ref[0, 0, :, :],
            lambda s: _mask_causal(s, causal, q_off, k_off, block_q, block_k,
                                   window),
            acc_ref, m_ref, l_ref, scale, rows_may_be_dead=window is not None)

    @pl.when(step == n_steps - 1)
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


def _flash_launch(q, k, v, causal: bool, block_q: int, block_k: int,
                  interpret: bool, mode: str, window=None):
    """One launcher for every forward variant — same grid, BlockSpecs and
    scratch; ``mode`` picks the kernel's emit: ``"out"`` (normalized
    output), ``"lse"`` (output + logsumexp, the backward's residual), or
    ``"stats"`` (unnormalized o + m/l, the ring-merge contract). With a
    ``window`` the kv axis of the grid walks the band's tiles only
    (:func:`_band`) and the call is named ``swa_fwd``.

    Kernel-internal layout is (b, heads, seq, d): Mosaic requires the
    block's minor-most two dims to tile as (sublane, lane) — (block_q, d)
    satisfies the (8, 128) granule, whereas the model-side (b, seq,
    heads, d) layout would put a size-1 block dim over the heads axis,
    which the TPU lowering rejects. XLA fuses the boundary transposes
    into the surrounding copies."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, sq, h, d = q.shape
    sk, kv_h = k.shape[1], k.shape[2]
    rep = h // kv_h
    n_k = sk // block_k
    k_first, k_walked = _band(window, block_q, block_k, n_k,
                              behind=(window or 1) - 1)
    kernel = partial(_flash_kernel, block_q=block_q, block_k=block_k,
                     causal=causal, scale=1.0 / np.sqrt(d),
                     emit_stats=(mode == "stats"), emit_lse=(mode == "lse"),
                     window=window, k_first=k_first)
    if window is None:
        kv_index = lambda bi, hi, qi, ki: (bi, hi // rep, ki, 0)  # noqa: E731
    else:
        kv_index = lambda bi, hi, qi, step: (  # noqa: E731
            bi, hi // rep, jnp.minimum(k_first(qi) + step, n_k - 1), 0)
    o_spec = pl.BlockSpec((1, 1, block_q, d),
                          lambda bi, hi, qi, ki: (bi, hi, qi, 0))
    stat_spec = pl.BlockSpec((1, 1, block_q, 1),
                             lambda bi, hi, qi, ki: (bi, hi, qi, 0))
    stat_shape = jax.ShapeDtypeStruct((b, h, sq, 1), jnp.float32)
    if mode == "out":
        out_specs = o_spec
        out_shape = jax.ShapeDtypeStruct((b, h, sq, d), q.dtype)
    elif mode == "lse":
        out_specs = [o_spec, stat_spec]
        out_shape = [jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
                     stat_shape]
    else:  # stats: unnormalized f32 accumulator + m/l
        out_specs = [o_spec, stat_spec, stat_spec]
        out_shape = [jax.ShapeDtypeStruct((b, h, sq, d), jnp.float32),
                     stat_shape, stat_shape]
    return pl.pallas_call(
        kernel,
        grid=(b, h, sq // block_q, k_walked),
        in_specs=[
            o_spec,
            pl.BlockSpec((1, 1, block_k, d), kv_index),
            pl.BlockSpec((1, 1, block_k, d), kv_index),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),      # acc
            pltpu.VMEM((block_q, 1), jnp.float32),      # running max m
            pltpu.VMEM((block_q, 1), jnp.float32),      # normalizer l
        ],
        interpret=interpret,
        name="flash_fwd" if window is None else "swa_fwd",
    )(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
      v.transpose(0, 2, 1, 3))


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
    """Shared softmax-gradient tile math for both backward kernels:
    recompute scores from the refs, re-exponentiate against the saved
    lse (lse >= running max, so exp(s - lse) <= 1), and return
    ``(p, ds)`` with ``ds`` already scaled — keeping the numerics in ONE
    place so dQ and dK/dV cannot drift apart."""
    return _p_ds_tile(
        q_ref[0, 0, :, :], k_ref[0, 0, :, :], v_ref[0, 0, :, :],
        do_ref[0, 0, :, :], lse_ref[0, 0, :, 0], dd_ref[0, 0, :, 0],
        lambda s: _mask_causal(s, causal, q_off, k_off, block_q, block_k,
                               window), scale)


def _p_ds_tile(q, k, v, do, lse, dd, mask, scale: float):
    """The tile math of :func:`_bwd_p_ds` on loaded tiles: q, do (bq, d);
    k, v (bk, d); lse, dd (bq,); ``mask`` a callable on the score tile."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    s = mask(s)
    p = jnp.exp(s - lse[:, None])                               # (bq, bk)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - dd[:, None]) * scale
    return p, ds


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref,
                         dq_ref, dq_acc, *, block_q: int, block_k: int,
                         causal: bool, scale: float, window=None,
                         k_first=lambda qi: 0):
    """dQ pass (flash-attention-2 backward): grid (b, h, q_blocks,
    kv_blocks), kv innermost; dq accumulates in VMEM scratch across the
    kv dimension. P is re-exponentiated from the saved lse, so no
    softmax state needs carrying."""
    from jax.experimental import pallas as pl

    qi, step = pl.program_id(2), pl.program_id(3)
    n_steps = pl.num_programs(3)
    ki = k_first(qi) + step

    @pl.when(step == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    q_off, k_off = qi * block_q, ki * block_k
    live = _causal_live(causal, q_off, k_off, block_q, block_k, window)

    @pl.when(live)
    def _step():
        _, ds = _bwd_p_ds(q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref,
                          q_off, k_off, block_q, block_k, causal, scale,
                          window)
        k = k_ref[0, 0, :, :]
        dq_acc[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(step == n_steps - 1)
    def _emit():
        dq_ref[0, 0, :, :] = dq_acc[:].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(k_ref, v_ref, q_ref, do_ref, lse_ref, dd_ref,
                          dk_ref, dv_ref, dk_acc, dv_acc, *, block_q: int,
                          block_k: int, n_q: int, causal: bool,
                          scale: float, window=None, q_walked=None,
                          q_first=lambda ki: 0):
    """dK/dV pass: grid (b, kv_heads, kv_blocks, rep * q_blocks) — the
    innermost dimension walks every (grouped-query head, q block) pair
    that attends to this K/V tile, accumulating dk/dv in VMEM scratch
    (GQA gradients sum over the head group here instead of a host-side
    reduction over repeated K/V). With a ``window`` only the ``q_walked``
    q blocks from ``q_first(ki)`` on are walked for each head."""
    from jax.experimental import pallas as pl

    ki, t = pl.program_id(2), pl.program_id(3)
    qi = q_first(ki) + t % (n_q if q_walked is None else q_walked)
    n_t = pl.num_programs(3)

    @pl.when(t == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    q_off, k_off = qi * block_q, ki * block_k
    live = _causal_live(causal, q_off, k_off, block_q, block_k, window)
    if window is not None:      # a walked index past the last q block
        live = jnp.logical_and(live, qi < n_q)

    @pl.when(live)
    def _step():
        p, ds = _bwd_p_ds(q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref,
                          q_off, k_off, block_q, block_k, causal, scale,
                          window)
        q = q_ref[0, 0, :, :]
        do = do_ref[0, 0, :, :]
        dv_acc[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                 # (bk, d)
        dk_acc[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                 # (bk, d)

    @pl.when(t == n_t - 1)
    def _emit():
        dk_ref[0, 0, :, :] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0, :, :] = dv_acc[:].astype(dv_ref.dtype)


def _flash_backward(q, k, v, o, lse, do, causal: bool, block_q: int,
                    block_k: int, interpret: bool, window=None):
    """Pallas flash backward: dq via a kv-innermost pass, dk/dv via a
    q-innermost pass with in-kernel GQA group accumulation. O(block)
    VMEM per program, no O(seq^2) or O(block*seq) HBM tensors — the
    memory story of the forward, extended to training."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, sq, h, d = q.shape
    sk, kv_h = k.shape[1], k.shape[2]
    rep = h // kv_h
    scale = 1.0 / np.sqrt(d)
    # D_i = rowsum(dO ∘ O): O(seq·d) elementwise, fine outside the kernel.
    dd = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)

    qT = q.transpose(0, 2, 1, 3)
    kT = k.transpose(0, 2, 1, 3)
    vT = v.transpose(0, 2, 1, 3)
    doT = do.transpose(0, 2, 1, 3)
    lseT = lse                                  # already (b, h, sq, 1)
    ddT = dd.transpose(0, 2, 1)[..., None]

    n_q, n_k = sq // block_q, sk // block_k
    k_first, k_walked = _band(window, block_q, block_k, n_k,
                              behind=(window or 1) - 1)
    q_first, q_walked = _band(window, block_k, block_q, n_q, behind=0)
    q_spec = pl.BlockSpec((1, 1, block_q, d),
                          lambda bi, hi, qi, ki: (bi, hi, qi, 0))
    if window is None:
        kv_spec = pl.BlockSpec((1, 1, block_k, d),
                               lambda bi, hi, qi, ki: (bi, hi // rep, ki, 0))
    else:
        kv_spec = pl.BlockSpec(
            (1, 1, block_k, d), lambda bi, hi, qi, step: (
                bi, hi // rep, jnp.minimum(k_first(qi) + step, n_k - 1), 0))
    stat_spec = pl.BlockSpec((1, 1, block_q, 1),
                             lambda bi, hi, qi, ki: (bi, hi, qi, 0))
    dq = pl.pallas_call(
        partial(_flash_bwd_dq_kernel, block_q=block_q, block_k=block_k,
                causal=causal, scale=scale, window=window, k_first=k_first),
        grid=(b, h, sq // block_q, k_walked),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, stat_spec, stat_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dq" if window is None else "swa_bwd_dq",
    )(qT, kT, vT, doT, lseT, ddT)

    kv_out_spec = pl.BlockSpec((1, 1, block_k, d),
                               lambda bi, gi, ki, t: (bi, gi, ki, 0))
    # Per-(kv head, q tile) inputs: head gi*rep + t//n_q, q block t%n_q
    # (with a window: of the q_walked blocks from q_first(ki) on).
    if window is None:
        q_index = lambda bi, gi, ki, t: (  # noqa: E731
            bi, gi * rep + t // n_q, t % n_q, 0)
    else:
        q_index = lambda bi, gi, ki, t: (  # noqa: E731
            bi, gi * rep + t // q_walked,
            jnp.minimum(q_first(ki) + t % q_walked, n_q - 1), 0)
    q_in = pl.BlockSpec((1, 1, block_q, d), q_index)
    stat_in = pl.BlockSpec((1, 1, block_q, 1), q_index)
    kv_in = pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, gi, ki, t: (bi, gi, ki, 0))
    dk, dv = pl.pallas_call(
        partial(_flash_bwd_dkv_kernel, block_q=block_q, block_k=block_k,
                n_q=n_q, causal=causal, scale=scale, window=window,
                q_walked=None if window is None else q_walked,
                q_first=q_first),
        grid=(b, kv_h, sk // block_k, rep * q_walked),
        in_specs=[kv_in, kv_in, q_in, q_in, stat_in, stat_in],
        out_specs=[kv_out_spec, kv_out_spec],
        out_shape=[jax.ShapeDtypeStruct((b, kv_h, sk, d), k.dtype),
                   jax.ShapeDtypeStruct((b, kv_h, sk, d), v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dkv" if window is None else "swa_bwd_dkv",
    )(kT, vT, qT, doT, lseT, ddT)
    return (dq.transpose(0, 2, 1, 3), dk.transpose(0, 2, 1, 3),
            dv.transpose(0, 2, 1, 3))


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
    q ``(b, sq, heads, d)``, k/v ``(b, sk, kv_heads, d)`` ->
    ``(b, sq, heads, d)``, grouped-query native. ``window`` (static, needs
    ``causal``) keeps of each query's keys its own and the ``window - 1``
    before it; the grid then walks the band's tiles alone and the three
    calls are named ``swa_fwd`` / ``swa_bwd_dq`` / ``swa_bwd_dkv``.

    Falls back to the dense path when the shape can't tile onto the
    hardware (:func:`_tiles`). ``interpret=None`` selects the Pallas
    interpreter on the ``cpu`` backend only, so tests run there
    (:func:`_resolve_interpret`).
    """
    b, sq, h, d = q.shape
    sk, kv_h = k.shape[1], k.shape[2]
    if h % kv_h:
        raise ValueError(f"heads ({h}) must be a multiple of kv_heads ({kv_h})")
    if window is not None and (not causal or window < 1):
        raise ValueError(f"window ({window}) needs causal=True and >= 1")
    tiles = _tiles(sq, sk, causal, block_q, block_k)
    if tiles is None:
        return _dense(q, k, v, causal, window)
    return _flash_vjp(causal, *tiles, _resolve_interpret(interpret), window,
                      q, k, v)


def make_flash_attention(causal: bool = True, block_q: int = _DEFAULT_BLOCK_Q,
                         block_k: int = _DEFAULT_BLOCK_K, interpret=None,
                         window=None):
    """An ``attn_fn`` for :func:`petastorm_tpu.models.llama.apply`
    (``supports_gqa``: K/V arrive at native kv-head width); with a
    ``window`` the one for the model's sliding-window layers
    (``window_attn_fn``). Its caller asked for the kernel, so a shape the
    tiles cannot divide raises (:func:`require_flash_tiles`) instead of
    taking the dense route."""
    def attn(q, k, v):
        require_flash_tiles(q.shape[1], k.shape[1], causal, block_q, block_k)
        return flash_attention(q, k, v, causal=causal, block_q=block_q,
                               block_k=block_k, interpret=interpret,
                               window=window)
    attn.supports_gqa = True
    return attn
