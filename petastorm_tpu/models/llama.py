"""Llama-style decoder-only transformer — the NGram->token-stream consumer
(BASELINE config 5), built TPU-first:

* RMSNorm (float32 stats), RoPE, grouped-query attention, SwiGLU MLP;
* one block function built from (attention kind x FFN kind): per layer,
  rotary positions or none and full or sliding-window attention
  (``rope_layout`` / ``sliding_window_layout``), or for the whole model
  EVA's one softmax over a block's own keys and the earlier blocks' chunk
  summaries (``attention="eva"``, :mod:`petastorm_tpu.ops.eva_attn`) or
  latent attention (``attention="mla"``: keys and values made from one
  normed low-rank latent, one rotary key head for all query heads, scores
  wider than values); per
  model, a dense MLP,
  the ``soft`` / ``switch`` expert paths, or the dropless top-k expert
  layer that is told which experts it holds (``n_router_outputs``), after
  ``n_dense_layers`` leading dense layers, beside ``n_shared_experts``
  experts every token meets, its router a softmax over the selected
  logits or sigmoid scores selected through a bias (``router_score``);
* optionally RMSNorm scales stored as offsets from one
  (``norm_unit_offset``) and ``n_pred_heads`` output heads that predict
  the next 1..n tokens in the loss head's one pass;
* bfloat16 activations, float32 master params;
* **3-D parallelism layout**: batch on ``data``, sequence on ``seq``
  (ring attention over the ICI ring — :mod:`petastorm_tpu.parallel.ring_attention`),
  and megatron-style tensor parallelism on ``model`` —
  :func:`param_shardings` returns the NamedSharding pytree and ``apply``
  constrains activations so GSPMD inserts the right collectives;
* static config via :class:`LlamaConfig` (never traced).
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from petastorm_tpu import device_scopes as scopes
from petastorm_tpu.ops import moe_rows
from petastorm_tpu.ops.flash_attn import SAVED_NAMES

# The policy of ``apply(remat_layers=True)``: recompute the block, except
# what the attention kernel already wrote.
_SAVE_ATTENTION = jax.checkpoint_policies.save_only_these_names(*SAVED_NAMES)


@dataclass(frozen=True)
class LlamaConfig:
    vocab: int = 32000
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    hidden: int = 14336
    rope_theta: float = 500000.0
    norm_eps: float = 1e-5
    # Standard deviation of the embedding's init. With the 0.02 default a
    # deep stack of RANDOM weights is soon dominated by what its blocks
    # add, part of it common to all tokens; a router that reads the stream
    # (n_router_outputs) then sends most tokens to one expert. 1.0 keeps
    # the stream token-specific and the experts' load near uniform.
    embed_std: float = 0.02
    # Mixture-of-experts: every ``moe_every``-th layer uses ``n_experts``
    # soft-mixture experts (0 = dense MLP everywhere). Expert weights carry a
    # leading expert axis that param_shardings places on the model axis —
    # expert parallelism sharing the TP mesh axis (the common ep=tp layout).
    n_experts: int = 0
    moe_every: int = 2
    # "soft": dense soft-mixture (every expert on every token, no routing
    # collectives). "switch": GShard/Switch sparse dispatch with top-k
    # routing and per-expert capacity — with an expert sharding constraint
    # GSPMD lowers it to all-to-alls (petastorm_tpu.parallel.moe).
    moe_dispatch: str = "soft"
    moe_top_k: int = 1
    moe_capacity_factor: float = 1.25
    # Width of one head; None = dim // n_heads (models whose heads are not
    # hidden / heads wide state it: 28 x 128 on a hidden size of 2560).
    head_dim: Optional[int] = None
    # Per-layer attention kind, as the source configs write it: layer l has
    # rotary positions where rope_layout[l] (None = every layer) and sees
    # only its own and the sliding_window - 1 keys before it where
    # sliding_window_layout[l] (None = full causal attention everywhere).
    rope_layout: Optional[tuple] = None
    sliding_window_layout: Optional[tuple] = None
    sliding_window: Optional[int] = None
    # Dropless top-k expert FFN (n_router_outputs > 0) in every layer after
    # the first n_dense_layers, which keep the dense MLP of ``hidden``: the
    # router scores all n_router_outputs experts and selects top_k; of them
    # this shard holds experts_held = (first, count) and computes their
    # part of the result, no assignment dropped
    # (:func:`_dropless_moe_block`). router_input: "layer_input" routes on
    # the block's input, before attention; "mlp_norm" on the normed
    # post-attention stream. router_score: "softmax_topk" selects the
    # largest logits and softmaxes them; "sigmoid" scores every expert
    # s = sigmoid(logit), selects the largest of s + b (b the layer's
    # ``router_bias`` leaf, drawn as zeros; no gradient reaches it and
    # :func:`make_train_step` keeps it out of the optimizer) and weighs
    # the selected by s / sum(s) * router_scale. n_shared_experts
    # experts of expert_hidden each, as one MLP of their summed width,
    # serve every token beside the routed ones, whole on every shard.
    n_router_outputs: int = 0
    top_k: int = 1
    experts_held: Optional[tuple] = None
    expert_hidden: int = 0
    expert_act: str = "silu"
    router_input: str = "mlp_norm"
    n_dense_layers: int = 0
    n_shared_experts: int = 0
    router_score: str = "softmax_topk"
    router_scale: float = 1.0
    # "softmax": one softmax over every earlier key (or the window's).
    # "eva": every layer attends within blocks of eva_window positions and
    # to one learned summary per eva_chunk keys of each earlier block
    # (per-head ``eva_phi``, ``eva_mu`` leaves; one KV head a query head).
    # "mla": latent attention. ``wq`` makes n_heads queries of qk_nope_dim
    # + qk_rope_dim; ``wkv_a`` a latent of kv_lora_rank and ONE rotary key
    # of qk_rope_dim for all heads; the latent is normed (``kv_norm``) and
    # ``wkv_b`` makes each head's qk_nope_dim key and v_dim value from it;
    # the rotary parts alone are rotated (adjacent pairs where
    # rope_interleave, else the two halves). Scores contract over
    # qk_nope_dim + qk_rope_dim and are scaled by its root; ``wo`` reads
    # n_heads x v_dim. head_dim and n_kv_heads are not read.
    attention: str = "softmax"
    eva_window: int = 0
    eva_chunk: int = 0
    kv_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_dim: int = 0
    rope_interleave: bool = False
    # RMSNorm multiplies by 1 + g, g drawn as zeros (else by g, drawn as
    # ones).
    norm_unit_offset: bool = False
    # Output heads: head m (0-based) of ``lm_head``'s n_pred_heads x vocab
    # columns predicts the token m + 1 positions on; the loss is the mean
    # over heads and positions that have their target in the window.
    n_pred_heads: int = 1
    # The residual adds in float32: each branch's closing product leaves
    # its float32 accumulator unrounded, is added to the stream widened
    # to float32, and the sum is rounded once to the stream's dtype (else
    # the product is rounded first and the add is the compute dtype's).
    fp32_skip_add: bool = False

    def __post_init__(self):
        if self.head_dim is None:
            object.__setattr__(self, "head_dim", self.dim // self.n_heads)
        for name in ("rope_layout", "sliding_window_layout"):
            layout = getattr(self, name)
            if layout is not None:
                if len(layout) != self.n_layers:
                    raise ValueError(f"{name} has {len(layout)} entries for "
                                     f"{self.n_layers} layers")
                object.__setattr__(self, name, tuple(bool(v) for v in layout))
        if self.sliding_window_layout and any(self.sliding_window_layout) \
                and not self.sliding_window:
            raise ValueError("sliding_window_layout needs sliding_window")
        if self.attention not in ("softmax", "eva", "mla"):
            raise ValueError(f"unknown attention {self.attention!r}")
        if self.attention == "mla" and (
                min(self.kv_lora_rank, self.qk_nope_dim, self.v_dim) < 1
                or self.qk_rope_dim < 2 or self.qk_rope_dim % 2):
            raise ValueError(
                f"latent attention needs kv_lora_rank ({self.kv_lora_rank}), "
                f"qk_nope_dim ({self.qk_nope_dim}), v_dim ({self.v_dim}) and "
                f"an even qk_rope_dim ({self.qk_rope_dim})")
        if self.attention == "eva":
            if (self.eva_chunk < 1 or self.eva_window % self.eva_chunk
                    or self.eva_window < self.eva_chunk):
                raise ValueError(f"eva_window ({self.eva_window}) must be a "
                                 f"multiple of eva_chunk ({self.eva_chunk})")
            if self.n_kv_heads != self.n_heads or self.sliding_window_layout:
                raise ValueError("EVA attention takes one KV head a query "
                                 "head and no sliding-window layers")
        if self.n_pred_heads < 1:
            raise ValueError(f"n_pred_heads ({self.n_pred_heads}) < 1")
        if self.fp32_skip_add and (self.n_experts or self.n_router_outputs):
            raise ValueError("fp32_skip_add is the dense FFN's: the expert "
                             "layers add their own sums")
        if self.n_router_outputs:
            if self.n_experts:
                raise ValueError("n_router_outputs (dropless experts) and "
                                 "n_experts (soft / switch) exclude each other")
            if self.experts_held is None:
                object.__setattr__(self, "experts_held",
                                   (0, self.n_router_outputs))
            first, count = self.experts_held
            if not (0 <= first and count >= 1
                    and first + count <= self.n_router_outputs
                    and 1 <= self.top_k <= self.n_router_outputs
                    and self.expert_hidden > 0):
                raise ValueError(f"bad expert layer: held {self.experts_held} "
                                 f"of {self.n_router_outputs}, top "
                                 f"{self.top_k}, width {self.expert_hidden}")
            if self.expert_act not in _EXPERT_ACTS:
                raise ValueError(f"unknown expert_act {self.expert_act!r}")
            if self.router_input not in ("layer_input", "mlp_norm"):
                raise ValueError(f"unknown router_input {self.router_input!r}")
            if self.router_score not in ("softmax_topk", "sigmoid"):
                raise ValueError(f"unknown router_score {self.router_score!r}")
            if not 0 <= self.n_dense_layers < self.n_layers:
                raise ValueError(f"n_dense_layers ({self.n_dense_layers}) of "
                                 f"{self.n_layers} layers")
        elif self.n_dense_layers or self.n_shared_experts:
            raise ValueError("n_dense_layers and n_shared_experts belong to "
                             "the dropless expert layers (n_router_outputs)")

    def attention_kind(self, layer_idx: int) -> tuple:
        """``(rope, window)`` of layer ``layer_idx``: whether it rotates q
        and k, and its sliding window (None = full causal attention, or
        EVA's where ``attention == "eva"``)."""
        rope = self.rope_layout is None or self.rope_layout[layer_idx]
        windowed = (self.sliding_window_layout is not None
                    and self.sliding_window_layout[layer_idx])
        return rope, (self.sliding_window if windowed else None)

    def holds_experts(self, layer_idx: int) -> bool:
        """Whether layer ``layer_idx``'s FFN is an expert layer of any
        kind (dropless after the leading dense layers, or every
        ``moe_every``-th ``soft`` / ``switch`` one)."""
        if self.n_router_outputs:
            return layer_idx >= self.n_dense_layers
        return (self.n_experts > 0
                and layer_idx % self.moe_every == self.moe_every - 1)


_EXPERT_ACTS = {"silu": jax.nn.silu, "relu": jax.nn.relu}


TINY = LlamaConfig(vocab=256, dim=64, n_layers=2, n_heads=8, n_kv_heads=4,
                   hidden=128)


def init_params(rng_key, cfg: LlamaConfig):
    keys = iter(jax.random.split(rng_key, 4 + cfg.n_layers * 8))

    def mat(key, fan_in, fan_out):
        return jax.random.normal(key, (fan_in, fan_out), jnp.float32) / np.sqrt(fan_in)

    def norm_scale(width=cfg.dim):
        return (jnp.zeros if cfg.norm_unit_offset else jnp.ones)(
            (width,), jnp.float32)

    params = {
        "embed": jax.random.normal(next(keys), (cfg.vocab, cfg.dim),
                                   jnp.float32) * cfg.embed_std,
        "layers": [],
        "norm_out": norm_scale(),
        "lm_head": mat(next(keys), cfg.dim, cfg.n_pred_heads * cfg.vocab),
    }
    hd = cfg.head_dim
    for li in range(cfg.n_layers):
        if cfg.attention == "mla":
            attn = {
                "wq": mat(next(keys), cfg.dim,
                          cfg.n_heads * (cfg.qk_nope_dim + cfg.qk_rope_dim)),
                "wkv_a": mat(next(keys), cfg.dim,
                             cfg.kv_lora_rank + cfg.qk_rope_dim),
                "kv_norm": norm_scale(cfg.kv_lora_rank),
                "wkv_b": mat(next(keys), cfg.kv_lora_rank,
                             cfg.n_heads * (cfg.qk_nope_dim + cfg.v_dim)),
                "wo": mat(next(keys), cfg.n_heads * cfg.v_dim, cfg.dim),
            }
        else:
            attn = {
                "wq": mat(next(keys), cfg.dim, cfg.n_heads * hd),
                "wk": mat(next(keys), cfg.dim, cfg.n_kv_heads * hd),
                "wv": mat(next(keys), cfg.dim, cfg.n_kv_heads * hd),
                "wo": mat(next(keys), cfg.n_heads * hd, cfg.dim),
            }
        layer = {"attn_norm": norm_scale(), **attn, "mlp_norm": norm_scale()}
        if cfg.holds_experts(li):
            # Dropless: the router keeps its full width, the experts are
            # the ``count`` held here at their own width.
            n_out, E, width = (
                (cfg.n_router_outputs, cfg.experts_held[1], cfg.expert_hidden)
                if cfg.n_router_outputs
                else (cfg.n_experts, cfg.n_experts, cfg.hidden))
            k1, k2, k3, k4 = jax.random.split(next(keys), 4)
            layer["router"] = jax.random.normal(k1, (cfg.dim, n_out), jnp.float32) * 0.02
            layer["ew1"] = jax.random.normal(k2, (E, cfg.dim, width),
                                             jnp.float32) / np.sqrt(cfg.dim)
            layer["ew3"] = jax.random.normal(k3, (E, cfg.dim, width),
                                             jnp.float32) / np.sqrt(cfg.dim)
            layer["ew2"] = jax.random.normal(k4, (E, width, cfg.dim),
                                             jnp.float32) / np.sqrt(width)
            if cfg.router_score == "sigmoid":
                layer["router_bias"] = jnp.zeros((n_out,), jnp.float32)
            if cfg.n_shared_experts:
                shared = cfg.n_shared_experts * cfg.expert_hidden
                k1, k2, k3 = jax.random.split(next(keys), 3)
                layer["sw1"] = mat(k1, cfg.dim, shared)       # gate
                layer["sw3"] = mat(k2, cfg.dim, shared)       # up
                layer["sw2"] = mat(k3, shared, cfg.dim)       # down
        else:
            layer["w1"] = mat(next(keys), cfg.dim, cfg.hidden)   # gate
            layer["w3"] = mat(next(keys), cfg.dim, cfg.hidden)   # up
            layer["w2"] = mat(next(keys), cfg.hidden, cfg.dim)   # down
        if cfg.attention == "eva":
            # The chunk summaries' per-head pooling direction and key
            # offset: clip(N(0, 1), -1, 1) / sqrt(head_dim).
            for name, key in zip(("eva_phi", "eva_mu"),
                                 jax.random.split(next(keys))):
                layer[name] = jnp.clip(jax.random.normal(
                    key, (cfg.n_heads, hd), jnp.float32), -1, 1) / np.sqrt(hd)
        params["layers"].append(layer)
    return params


def _param_pspec_tuples(cfg: LlamaConfig, model_axis):
    """PartitionSpec entry tuples per parameter (Megatron TP layout when
    ``model_axis`` is an axis name; all-replicated when None). Empty tuple =
    fully replicated (norm scales, router)."""
    m = model_axis
    attn = {"attn_norm": (), "wq": (None, m), "wo": (m, None),
            "mlp_norm": ()}
    if cfg.attention == "mla":
        # The latent and its norm are whole on every shard; heads are
        # sharded with the up-projection's columns.
        attn.update(wkv_a=(None, None), kv_norm=(), wkv_b=(None, m))
    else:
        attn.update(wk=(None, m), wv=(None, m))
    if cfg.attention == "eva":
        # Heads are sharded with the projections' columns.
        attn.update(eva_phi=(m, None), eva_mu=(m, None))
    dense_ffn = {"w1": (None, m), "w3": (None, m), "w2": (m, None)}
    moe_ffn = {
        "router": (),
        # Expert parallelism: the leading expert axis is sharded over the
        # model axis (ep shares the tp mesh axis).
        "ew1": (m, None, None),
        "ew3": (m, None, None),
        "ew2": (m, None, None),
    }
    if cfg.router_score == "sigmoid":
        moe_ffn["router_bias"] = ()
    if cfg.n_shared_experts:
        moe_ffn.update(sw1=(None, m), sw3=(None, m), sw2=(m, None))
    return {
        "embed": (m, None),     # vocab-sharded embedding
        "layers": [{**attn, **(moe_ffn if cfg.holds_experts(li)
                               else dense_ffn)}
                   for li in range(cfg.n_layers)],
        "norm_out": (),
        "lm_head": (None, m),
    }


def param_shardings(mesh, cfg: LlamaConfig, model_axis: str = "model"):
    """Megatron TP layout as a NamedSharding pytree matching init_params."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    return jax.tree.map(lambda spec: NamedSharding(mesh, P(*spec)),
                        _param_pspec_tuples(cfg, model_axis),
                        is_leaf=lambda x: isinstance(x, tuple))


def param_shardings_fsdp(mesh, cfg: LlamaConfig, data_axis: str = "data",
                         model_axis: Optional[str] = "model"):
    """ZeRO-3/FSDP layout: each matrix additionally sharded over the DATA
    axis on its first TP-free dimension, so parameter (and, by propagation,
    optimizer-state) memory scales down with the dp size; XLA/GSPMD inserts
    the all-gathers for use and reduce-scatters for grads. Composes with
    Megatron TP (``model_axis``) or runs pure-FSDP (``model_axis=None``).
    Rank<2 leaves (norm scales, router biases) stay replicated — gathering
    them would cost more than the bytes saved."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    def add_data(spec: tuple):
        specs = list(spec)
        for i, s in enumerate(specs):
            if s is None:
                specs[i] = data_axis
                break
        return NamedSharding(mesh, P(*specs))

    return jax.tree.map(add_data, _param_pspec_tuples(cfg, model_axis),
                        is_leaf=lambda x: isinstance(x, tuple))


def _rmsnorm(x, scale, eps, unit_offset: bool = False):
    """``unit_offset``: the stored scale is an offset from one."""
    x32 = x.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    if unit_offset:
        scale = 1.0 + scale
    return (x32 * inv * scale).astype(x.dtype)


def _skip_add(x, h, w, fp32: bool = False):
    """The residual add ``x + h @ w``. ``fp32`` (``cfg.fp32_skip_add``):
    the product keeps its float32 accumulator, the add is float32 and the
    sum is rounded once to the stream's dtype."""
    if not fp32:
        return x + h @ w.astype(h.dtype)
    branch = jnp.matmul(h, w.astype(h.dtype),
                        preferred_element_type=jnp.float32)
    return (x.astype(jnp.float32) + branch).astype(x.dtype)


def _rope(x, theta):
    """x: (b, s, h, d) -> rotated. Positions are global sequence indices.
    Pair ``i`` is columns ``(i, i + d/2)``; a model whose pairs are
    adjacent columns (``rope_interleave``) puts its weights' columns in
    this order first (:func:`_pairs_to_halves`: a product of two vectors
    rotated alike does not see the order of their pairs)."""
    b, s, h, d = x.shape
    half = d // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    pos = jnp.arange(s, dtype=jnp.float32)
    angles = pos[:, None] * freqs[None, :]               # (s, half)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., :half], x[..., half:]
    cos = cos[None, :, None, :].astype(x.dtype)
    sin = sin[None, :, None, :].astype(x.dtype)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def _moe_block(h, layer):
    """Soft-mixture MoE with dense dispatch: every expert runs on every
    token and outputs combine by router probability. O(E) FLOPs, but fully
    GSPMD-shardable on the expert axis with no all-to-all — the ep pattern
    used for the multi-chip dry run (switch-style sparse dispatch is a
    later-round optimization)."""
    probs = jax.nn.softmax(
        (h.astype(jnp.float32) @ layer["router"]), axis=-1).astype(h.dtype)
    gate = jax.nn.silu(jnp.einsum("bsd,edh->besh", h, layer["ew1"].astype(h.dtype)))
    up = jnp.einsum("bsd,edh->besh", h, layer["ew3"].astype(h.dtype))
    expert_out = jnp.einsum("besh,ehd->besd", gate * up, layer["ew2"].astype(h.dtype))
    return jnp.einsum("besd,bse->bsd", expert_out, probs)


# The grouped product's row tile on the chip (the compiled step of PR 29
# walks 399 tiles of 512 rows): the short buffer is a whole number of them.
_ROW_TILE = 512
# Room over the expected share of rows in the short buffer. A layer that
# holds more runs the full buffer in the same step, so a wrong guess here
# costs speed and never an answer.
_BUFFER_HEADROOM = 1.5


def _short_buffer_rows(n_rows: int, cfg: LlamaConfig) -> int:
    """Rows of the short expert buffer: the share of the ``n_rows``
    assignments that the held experts expect, with headroom, in whole row
    tiles; ``n_rows`` where that is no shorter."""
    expected = n_rows * cfg.experts_held[1] / cfg.n_router_outputs
    tiles = int(np.ceil(expected * _BUFFER_HEADROOM / _ROW_TILE))
    return min(n_rows, tiles * _ROW_TILE)


def _buffer_rows(x, head, k: int, live):
    """Token rows ``x`` (T, d) into the expert buffer: buffer row ``r`` is
    assignment ``head[r]``, which is token ``head[r] // k``'s (``k = 1``:
    the rows themselves). ``head`` is the sort's permutation of the
    ``T k`` assignments or its first rows. One DMA copy a buffer row
    (``moe_rows.gather_rows``) where the shapes tile onto the hardware;
    else, and for the row weights (one column), XLA's gather. The rows
    from ``live`` (the held rows, a traced count) on are the caller's to
    mask: the kernel need not copy them."""
    tile = moe_rows.gather_tile(head.shape[0], *x.shape, x.dtype)
    if tile is None:
        return x[head // k]
    return moe_rows.gather_rows(x, head // k, live, tile=tile)


def _token_sums(rows, head, k: int, n_tok: int, live):
    """The transpose of :func:`_buffer_rows`: each token's sum (float32)
    over the buffer rows of its assignments. Of a whole permutation every
    token has exactly ``k`` rows, found through its inverse: a gather and
    a sum, and no scatter. Of its first rows only, the buffer rows sorted
    by assignment are copied to their tokens and added in assignment order
    (``moe_rows.gather_sum``); the rows from ``live`` on are zeros (the
    caller's mask) and are not read. Where the shapes do not tile onto
    the hardware, the rows are added where they belong (XLA's
    scatter-add)."""
    if head.shape[0] == n_tok * k:
        rows = rows[jnp.argsort(head)].reshape(n_tok, k, rows.shape[-1])
        return jnp.sum(rows, axis=1, dtype=jnp.float32).astype(rows.dtype)
    tile = moe_rows.sum_tile(n_tok, *rows.shape, k, rows.dtype)
    if tile is None:
        return jax.ops.segment_sum(rows.astype(jnp.float32), head // k,
                                   num_segments=n_tok).astype(rows.dtype)
    return moe_rows.gather_sum(rows, head, live, k, n_tok, tile=tile)


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def _to_buffer(x, head, live, k):
    """:func:`_buffer_rows` whose backward is :func:`_token_sums`."""
    return _buffer_rows(x, head, k, live)


def _to_buffer_fwd(x, head, live, k):
    return _buffer_rows(x, head, k, live), (head, live, x.shape[0])


def _to_buffer_bwd(k, residuals, g):
    head, live, n_tok = residuals
    return _token_sums(g, head, k, n_tok, live), None, None


_to_buffer.defvjp(_to_buffer_fwd, _to_buffer_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _from_buffer(rows, head, live, k, n_tok):
    """:func:`_token_sums` whose backward is :func:`_buffer_rows`."""
    return _token_sums(rows, head, k, n_tok, live)


def _from_buffer_fwd(rows, head, live, k, n_tok):
    return _token_sums(rows, head, k, n_tok, live), (head, live)


def _from_buffer_bwd(k, n_tok, residuals, g):
    head, live = residuals
    return _buffer_rows(g, head, k, live), None, None


_from_buffer.defvjp(_from_buffer_fwd, _from_buffer_bwd)

MOE_STATS = ("rows_held", "load_max", "rows_buffer", "short_buffer")


def _expert_rows(n_buf: int, cfg: LlamaConfig, h, weights, w_gate, w_up,
                 w_down, perm, group_sizes):
    """The held experts' part of every token's result, through a buffer of
    the sort's first ``n_buf`` rows (at least ``group_sizes.sum()``).
    h: (T, d); weights: (T, k), nought where the expert is not held; perm:
    (T k,) buffer row -> assignment, the held experts' rows leading."""
    n_tok, k = weights.shape
    act = _EXPERT_ACTS[cfg.expert_act]
    head = perm[:n_buf]
    # Rows past the last held group belong to no group: the grouped
    # product never writes them, in its result or in its transpose (on the
    # chip they come back as whatever the buffer held), so they are masked
    # on the way in, for dx, and on the way out. The row movers need not
    # move them.
    held = group_sizes.sum()
    live = (jnp.arange(n_buf) < held)[:, None]
    with jax.named_scope(scopes.MOE_ROWS_IN):
        row_weights = _to_buffer(weights.reshape(n_tok * k, 1), head, held,
                                 1)
        xs = _to_buffer(h, head, held, k)                        # (n_buf, d)
    xs = jnp.where(live, xs, 0)
    gate = jax.lax.ragged_dot(xs, w_gate.astype(h.dtype), group_sizes)
    up = jax.lax.ragged_dot(xs, w_up.astype(h.dtype), group_sizes)
    rows = jax.lax.ragged_dot(act(gate) * up, w_down.astype(h.dtype),
                              group_sizes)
    rows = jnp.where(live, rows * row_weights, 0).astype(h.dtype)
    # Back to the tokens: each sums the weighted rows of its k choices.
    with jax.named_scope(scopes.MOE_ROWS_BACK):
        return _from_buffer(rows, head, held, k, n_tok)


@partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _expert_rows_short_or_full(n_short: int, cfg: LlamaConfig, short,
                               *operands):
    """:func:`_expert_rows` through the short buffer where ``short`` (the
    held rows fit it: a traced bool), else through the full one.
    Differentiating the ``cond`` itself would keep the residuals of both
    branches (the one not taken filled with zeros, every step: the step
    then no longer fits the chip), so the choice stands outside: forward
    and backward each branch on ``short``, and the backward recomputes
    inside the branch it takes."""
    n_rows = operands[-2].shape[0]
    return jax.lax.cond(short, partial(_expert_rows, n_short, cfg),
                        partial(_expert_rows, n_rows, cfg), *operands)


def _expert_rows_fwd(n_short, cfg, short, *operands):
    return (_expert_rows_short_or_full(n_short, cfg, short, *operands),
            (short, operands))


def _expert_rows_bwd(n_short, cfg, residuals, g):
    short, operands = residuals

    def pull(n_buf, g, *operands):
        *diff, perm, group_sizes = operands
        return jax.vjp(lambda *diff: _expert_rows(
            n_buf, cfg, *diff, perm, group_sizes), *diff)[1](g)

    grads = jax.lax.cond(short, partial(pull, n_short),
                         partial(pull, operands[-2].shape[0]), g, *operands)
    return (None, *grads, None, None)


_expert_rows_short_or_full.defvjp(_expert_rows_fwd, _expert_rows_bwd)


def _dropless_moe_block(route_x, h, layer, cfg: LlamaConfig):
    """Top-k expert FFN over the experts this shard holds, no assignment
    dropped -> ``(out (b, s, d), stats)``.

    The router scores all ``n_router_outputs`` experts from ``route_x``
    (float32), the ``top_k`` selected logits are softmaxed (or, with
    ``router_score="sigmoid"``, the largest of ``sigmoid(logits) +
    router_bias`` are selected and weighed by their unbiased scores,
    renormalised and scaled by ``router_scale``), and of the
    ``tokens x top_k`` assignments those to experts ``first .. first +
    count - 1`` are computed here: ``sum_e w_e * (act(h Wg_e) * (h Wu_e))
    Wd_e`` over the held ``e`` a token chose. What the experts held
    elsewhere would add is their shard's to compute (the partial sums meet
    in the expert-parallel exchange, which one shard runs without).

    The assignments are sorted by expert with the held experts' rows
    leading, and the expert computation (:func:`_expert_rows`: gather the
    rows' tokens, three ``jax.lax.ragged_dot`` products over the held
    experts' group sizes, weight, sum back into the tokens) moves the
    sort's first rows through a static buffer. The buffer is SHORT where
    the held rows fit it: the share the held experts expect of ``tokens x
    top_k`` rows, times :data:`_BUFFER_HEADROOM`, in whole row tiles
    (:func:`_short_buffer_rows`). A layer that holds more takes the FULL
    buffer of ``tokens x top_k`` rows in the same step (``jax.lax.cond`` on
    the traced count): whatever the imbalance, every assignment has its
    row. Where the short size is the full one (all experts held, toy
    shapes) there is one path and no ``cond``. ``stats``: int32 scalars,
    the rows routed to held experts, the largest held expert's rows, the
    rows of the buffer taken, and 1 where that was the short one
    (:data:`MOE_STATS`).
    """
    b, s, d = h.shape
    n_tok, k = b * s, cfg.top_k
    first, count = cfg.experts_held
    n_rows = n_tok * k
    n_short = _short_buffer_rows(n_rows, cfg)
    with jax.named_scope(scopes.MOE_ROUTE):
        logits = jnp.dot(route_x.reshape(n_tok, d).astype(jnp.float32),
                         layer["router"],
                         precision=jax.lax.Precision.HIGHEST)    # (T, n_out)
        if cfg.router_score == "sigmoid":
            # The bias selects, it does not weigh; no gradient reaches it.
            scores = jax.nn.sigmoid(logits)
            _, ids = jax.lax.top_k(
                scores + jax.lax.stop_gradient(layer["router_bias"]), k)
            top = jnp.take_along_axis(scores, ids, axis=-1)
            weights = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20) \
                * cfg.router_scale                               # (T, k)
        else:
            top, ids = jax.lax.top_k(logits, k)
            weights = jax.nn.softmax(top, axis=-1)               # (T, k)
        # Held experts sort first, in order; the rest follow.
        key = ((ids - first) % cfg.n_router_outputs).reshape(n_rows)
        perm = jnp.argsort(key, stable=True)   # buffer row -> assignment
        group_sizes = jnp.sum(
            key[:, None] == jnp.arange(count, dtype=key.dtype)[None, :],
            axis=0, dtype=jnp.int32)                             # (count,)
        rows_held = group_sizes.sum()
        # Each assignment's weight: nought where its expert is not held.
        weights = jnp.where(key.reshape(n_tok, k) < count, weights, 0.0)
    with jax.named_scope(scopes.MOE_EXPERTS):
        operands = (h.reshape(n_tok, d), weights, layer["ew1"], layer["ew3"],
                    layer["ew2"], perm, group_sizes)
        if n_short == n_rows:
            short = jnp.zeros((), bool)
            out = _expert_rows(n_rows, cfg, *operands)
        else:
            short = rows_held <= n_short
            out = _expert_rows_short_or_full(n_short, cfg, short, *operands)
    stats = {"rows_held": rows_held, "load_max": group_sizes.max(),
             "rows_buffer": jnp.where(short, n_short, n_rows).astype(jnp.int32),
             "short_buffer": short.astype(jnp.int32)}
    return out.reshape(b, s, d), stats


def publish_moe_stats(registry, stats) -> None:
    """Add a step's (or several steps') expert-layer statistics, as
    ``make_train_step(..., with_stats=True)`` returns them, to the
    registry's counters, each summed over layers and steps:
    ``model.moe.rows_held`` (rows routed to held experts),
    ``model.moe.rows_buffer`` (rows of the buffer the layer moved them
    through: the short one's or, where it fell back, ``tokens x top_k``),
    ``model.moe.short_buffer`` (layer-steps that took the short buffer)
    and ``model.moe.load_max`` (the largest held expert's rows). Reads the
    device arrays: call it between windows, never inside one."""
    for name in MOE_STATS:
        registry.counter(f"model.moe.{name}").add(
            float(np.sum(np.asarray(stats[name], dtype=np.float64))))


def _pairs_to_halves(w):
    """A weight's last axis of 2n columns from pair order ``(2i, 2i + 1)``
    to halves order ``(i, n + i)``: a permutation of the columns, so the
    product's columns are the same columns permuted."""
    *lead, width = w.shape
    return w.reshape(*lead, width // 2, 2).swapaxes(-1, -2).reshape(
        *lead, width)


def _latent_qkv(layer, h, cfg: LlamaConfig, rope: bool):
    """Latent attention's operands from the normed stream ``h`` (b, s, d),
    in the flash kernels' split form (``ops/flash_attn.flash_attention``):
    q ``(q_nope (b, s, heads, qk_nope_dim), q_rope (b, s, heads,
    qk_rope_dim))``, k ``(k_nope (b, s, heads, qk_nope_dim), k_rope (b, s,
    1, qk_rope_dim))`` — ONE rotary key, rotated once, for every head —
    and v ``(b, s, heads, v_dim)``. Keys and values come from one latent
    of ``kv_lora_rank`` columns, normed before it is projected up.

    Each operand is the product of its own columns of ``wq`` / ``wkv_a`` /
    ``wkv_b`` (viewed a head at a time; the parameters keep their
    published layout), so no head of qk_nope_dim + qk_rope_dim columns is
    made, sliced or joined. Under ``rope_interleave`` the rotary columns
    of ``wq`` and ``wkv_a`` are put from pair order into halves order
    before their products (:func:`_pairs_to_halves`): the rotation of
    halves then gives, column for column, what rotating adjacent pairs of
    the product gives."""
    nh, nope, rot = cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim
    rank = cfg.kv_lora_rank
    heads = partial(jnp.einsum, "bsd,dhe->bshe")
    with jax.named_scope(scopes.MLA_LATENT):
        wq = layer["wq"].astype(h.dtype).reshape(-1, nh, nope + rot)
        wkv_a = layer["wkv_a"].astype(h.dtype)
        wkv_b = layer["wkv_b"].astype(h.dtype).reshape(rank, nh,
                                                       nope + cfg.v_dim)
        wq_rot, wk_rot = wq[..., nope:], wkv_a[:, rank:]
        if cfg.rope_interleave:
            wq_rot, wk_rot = _pairs_to_halves(wq_rot), _pairs_to_halves(wk_rot)
        q_nope, q_rot = heads(h, wq[..., :nope]), heads(h, wq_rot)
        k_rot = (h @ wk_rot)[:, :, None, :]               # (b, s, 1, rot)
        latent = _rmsnorm(h @ wkv_a[:, :rank], layer["kv_norm"],
                          cfg.norm_eps, cfg.norm_unit_offset)
        k_nope = heads(latent, wkv_b[..., :nope])
        v = heads(latent, wkv_b[..., nope:])
        if rope:
            q_rot, k_rot = (_rope(q_rot, cfg.rope_theta),
                            _rope(k_rot, cfg.rope_theta))
        return (q_nope, q_rot), (k_nope, k_rot), v


def _embed_lookup(embed, tokens, compute_dtype):
    """Sharding-friendly embedding lookup: one-hot contraction over vocab.

    A plain gather (``table[tokens]``) from a vocab-sharded table
    (:func:`param_shardings` places ``embed`` as ``(model, None)``) with
    batch-sharded indices forces GSPMD into involuntary full
    rematerialization — the whole table is all-gathered every step. The
    one-hot matmul keeps the contraction on the sharded vocab axis: each
    device multiplies against its local vocab shard and partial results meet
    in a psum, so the bytes moved are activations (b*s*dim), not the table.
    Numerically identical to the gather: every product is exactly 0 or the
    embedding value and the accumulation adds only zeros to it.
    """
    onehot = jax.nn.one_hot(tokens, embed.shape[0], dtype=compute_dtype)
    return onehot @ embed.astype(compute_dtype)


def apply_block(layer, x, cfg: LlamaConfig, attn_fn=None, constrain=None,
                expert_spec=None, layer_idx: int = 0, window_attn_fn=None,
                with_stats: bool = False, eva_attn_fn=None):
    """One transformer block (attention + MLP/MoE residuals) -> (x, aux),
    or (x, aux, stats) ``with_stats`` (:data:`MOE_STATS`, zeros where the
    FFN is not the dropless expert layer).

    The block is built from the layer's attention kind
    (``cfg.attention_kind(layer_idx)``: rotary positions or none, full or
    sliding-window; or EVA's blocks and chunk summaries, through
    ``eva_attn_fn(q, k, v, phi, mu)``; or latent attention, whose
    :func:`_latent_qkv` hands ``attn_fn`` q and k as (position-free part,
    rotary part) pairs, the rotary key one head, which
    ``flash_attention`` and ``dense_attention`` take) and
    the layer's FFN kind (dense, ``soft`` / ``switch`` experts, dropless
    held experts with or without shared ones: the leaves it holds say
    which); the norms, the residuals and the sharding constraints are the
    same code for all.
    Shared by :func:`apply`'s sequential layer loop and GPipe pipeline
    stages (:mod:`petastorm_tpu.parallel.pipeline`), so a pipelined model
    runs the exact same math per layer as the sequential one.
    """
    from petastorm_tpu.parallel.attention import dense_attention
    if constrain is None:
        constrain = lambda t: t  # noqa: E731 - trivial identity
    hd = cfg.head_dim
    rep = cfg.n_heads // cfg.n_kv_heads
    rope, window = cfg.attention_kind(layer_idx)
    fn = attn_fn if window is None else window_attn_fn
    gqa_native = fn is None or getattr(fn, "supports_gqa", False)
    aux = jnp.zeros((), jnp.float32)
    layer_input = x
    with jax.named_scope(scopes.ATTN_QKV):
        h = _rmsnorm(x, layer["attn_norm"], cfg.norm_eps,
                     cfg.norm_unit_offset)
        b, s, _ = h.shape
        if cfg.attention == "mla":
            q, k, v = _latent_qkv(layer, h, cfg, rope)
        else:
            q = (h @ layer["wq"].astype(h.dtype)).reshape(b, s, cfg.n_heads,
                                                          hd)
            k = (h @ layer["wk"].astype(h.dtype)).reshape(
                b, s, cfg.n_kv_heads, hd)
            v = (h @ layer["wv"].astype(h.dtype)).reshape(
                b, s, cfg.n_kv_heads, hd)
            if rope:
                q, k = _rope(q, cfg.rope_theta), _rope(k, cfg.rope_theta)
            if not gqa_native and rep > 1:
                k = jnp.repeat(k, rep, axis=2)
                v = jnp.repeat(v, rep, axis=2)
    if cfg.attention == "eva":
        if eva_attn_fn is None:
            from petastorm_tpu.ops.eva_attn import make_eva_attention
            eva_attn_fn = make_eva_attention(cfg.eva_window, cfg.eva_chunk)
        with jax.named_scope(scopes.ATTN_EVA):
            attn = eva_attn_fn(q, k, v, layer["eva_phi"], layer["eva_mu"])
    else:
        with jax.named_scope(scopes.ATTN_FULL if window is None
                             else scopes.ATTN_WINDOW):
            attn = (fn or partial(dense_attention, causal=True,
                                  window=window))(q, k, v)
    with jax.named_scope(scopes.ATTN_OUT):
        attn = attn.reshape(b, s, -1)     # heads x the value width
        x = constrain(_skip_add(x, attn, layer["wo"], cfg.fp32_skip_add))
    stats = None
    with jax.named_scope(scopes.FFN):
        h = _rmsnorm(x, layer["mlp_norm"], cfg.norm_eps,
                     cfg.norm_unit_offset)
        if cfg.n_router_outputs and "router" in layer:
            moe_out, stats = _dropless_moe_block(
                layer_input if cfg.router_input == "layer_input" else h,
                h, layer, cfg)
            if cfg.n_shared_experts:
                with jax.named_scope(scopes.MOE_SHARED):
                    act = _EXPERT_ACTS[cfg.expert_act]
                    gate = act(h @ layer["sw1"].astype(h.dtype))
                    up = h @ layer["sw3"].astype(h.dtype)
                    moe_out = moe_out + (gate * up) @ layer["sw2"].astype(
                        h.dtype)
            x = constrain(x + moe_out)
        elif "router" in layer:
            if cfg.moe_dispatch == "switch":
                from petastorm_tpu.parallel.moe import switch_moe_block
                moe_out, layer_aux = switch_moe_block(
                    h, layer["router"], layer["ew1"], layer["ew3"],
                    layer["ew2"], top_k=cfg.moe_top_k,
                    capacity_factor=cfg.moe_capacity_factor,
                    expert_spec=expert_spec)
                aux = aux + layer_aux
                x = constrain(x + moe_out)
            else:
                x = constrain(x + _moe_block(h, layer))
        else:
            gate = jax.nn.silu(h @ layer["w1"].astype(h.dtype))
            up = h @ layer["w3"].astype(h.dtype)
            x = constrain(_skip_add(x, gate * up, layer["w2"],
                                    cfg.fp32_skip_add))
    if not with_stats:
        return x, aux
    if stats is None:
        stats = {name: jnp.zeros((), jnp.int32) for name in MOE_STATS}
    return x, aux, stats


def apply(params, tokens, cfg: LlamaConfig, attn_fn=None,
          activation_spec=None, compute_dtype=jnp.bfloat16,
          expert_spec=None, with_aux=False, layers_fn=None,
          embed_lookup: str = "gather", return_hidden: bool = False,
          remat_layers: bool = False, window_attn_fn=None,
          with_stats: bool = False, eva_attn_fn=None):
    """tokens: (batch, seq) int32 -> logits (batch, seq, n_pred_heads x vocab)
    (or the pre-lm_head hidden states when ``return_hidden`` — the
    chunked-cross-entropy path computes per-chunk logits itself).

    :param attn_fn: attention callable ``(q, k, v) -> out`` on
        (b, s, h, hd) tensors; ``None`` uses dense causal attention. Pass a
        :func:`petastorm_tpu.parallel.ring_attention.make_ring_attention`
        instance for sequence parallelism. Built-in attentions
        (dense/ring/ulysses) handle grouped-query K/V natively — K/V stay at
        n_kv_heads width; only user-supplied attentions without the
        ``supports_gqa`` flag get the repeated layout.
    :param window_attn_fn: the attention callable of the sliding-window
        layers (``cfg.sliding_window_layout``), e.g.
        ``make_flash_attention(window=cfg.sliding_window)``; ``None`` uses
        dense attention under the banded mask. ``attn_fn`` serves the
        full-attention layers.
    :param eva_attn_fn: the attention callable of an ``attention="eva"``
        model, ``(q, k, v, phi, mu) -> out``; ``None`` builds
        :func:`petastorm_tpu.ops.eva_attn.make_eva_attention` from the
        config (wrap that in ``jax.shard_map`` where the batch is sharded:
        a Pallas call is not partitioned by ``jit``).
    :param with_stats: also return, last, the expert layers' statistics:
        ``{name: (n_layers,) int32}`` over :data:`MOE_STATS`.
    :param activation_spec: optional ``PartitionSpec`` for (b, s, d)
        activations; applied with ``with_sharding_constraint`` so GSPMD keeps
        the intended layout between layers.
    :param expert_spec: sharding for (E, C, d) switch-MoE expert buffers
        (``moe_dispatch="switch"``); on the expert mesh axis it makes GSPMD
        lower dispatch/combine to all-to-alls.
    :param with_aux: also return the summed MoE load-balancing loss.
    :param layers_fn: optional ``f(params["layers"], x) -> (x, aux)``
        replacing the sequential layer loop — the pipeline-parallel hook
        (pass a :func:`petastorm_tpu.parallel.pipeline.make_pipeline`
        wrapper over :func:`apply_block` with stacked stage params).
    :param remat_layers: wrap each transformer block in ``jax.checkpoint``
        (the long-context memory lever: the backward recomputes each
        block from its layer-boundary activation). One thing is saved
        beside the boundaries: the output and row logsumexp that the
        Pallas flash kernel's forward rule names
        (:data:`petastorm_tpu.ops.flash_attn.SAVED_NAMES`; a bf16
        activation and ``(b, heads, seq)`` float32 a layer), so the
        recomputation launches no second forward kernel. An attention
        that does not run that kernel (dense, ring) names nothing and is
        recomputed whole. Applies
        to the sequential layer loop only — a ``layers_fn`` (pipeline
        parallelism) owns its own rematerialization and combining the
        two is rejected below.
    :param embed_lookup: ``"gather"`` (default) | ``"onehot"``. A plain
        gather is O(1) FLOPs and right for a replicated table, but forces
        GSPMD into involuntary full rematerialization (an all-gather of the
        whole table every step) when the table is vocab-sharded. Pass
        ``"onehot"`` whenever the embed param is sharded on its vocab axis
        (:func:`param_shardings` / :func:`param_shardings_fsdp` layouts):
        the contraction (:func:`_embed_lookup`) partitions cleanly at
        O(b*s*vocab*dim) FLOPs. Explicit because the table's sharding is
        not visible on a tracer inside jit.
    """
    constrain = (lambda x: x) if activation_spec is None else \
        (lambda x: jax.lax.with_sharding_constraint(x, activation_spec))
    aux = jnp.zeros((), jnp.float32)
    if embed_lookup not in ("gather", "onehot"):
        raise ValueError(f"unknown embed_lookup {embed_lookup!r}")
    with jax.named_scope(scopes.EMBED):
        x = constrain(_embed_lookup(params["embed"], tokens, compute_dtype)
                      if embed_lookup == "onehot"
                      else params["embed"].astype(compute_dtype)[tokens])
    stats = []
    if layers_fn is not None:
        if with_stats:
            raise ValueError("with_stats reads the sequential layer loop; a "
                             "layers_fn returns none")
        if remat_layers:
            raise ValueError(
                "remat_layers applies to the sequential layer loop; a "
                "layers_fn (pipeline parallelism) owns its own "
                "rematerialization — wrap it there instead")
        x, layers_aux = layers_fn(params["layers"], x)
        aux = aux + layers_aux
    else:
        for li, layer in enumerate(params["layers"]):
            one_block = partial(
                apply_block, cfg=cfg, attn_fn=attn_fn, constrain=constrain,
                expert_spec=expert_spec, layer_idx=li,
                window_attn_fn=window_attn_fn, with_stats=with_stats,
                eva_attn_fn=eva_attn_fn)
            if remat_layers:
                # Long-context lever: save the layer-boundary activations
                # and what the Pallas attention kernel names (its output
                # and row statistics, one activation's size); the backward
                # recomputes the rest of each block, but launches no
                # second forward kernel to remake what the first one wrote.
                one_block = jax.checkpoint(one_block, policy=_SAVE_ATTENTION)
            with jax.named_scope(scopes.BLOCK):
                x, layer_aux, *layer_stats = one_block(layer, x)
                aux = aux + layer_aux
            stats.extend(layer_stats)
    with jax.named_scope(scopes.LOSS_HEAD):
        x = _rmsnorm(x, params["norm_out"], cfg.norm_eps,
                     cfg.norm_unit_offset)
        if not return_hidden:
            x = (x @ params["lm_head"].astype(x.dtype)).astype(jnp.float32)
    out = (x, aux) if with_aux else (x,)
    if with_stats:
        with jax.named_scope(scopes.BLOCK):
            out += ({name: jnp.stack([st[name] for st in stats])
                     for name in MOE_STATS},)
    return out if len(out) > 1 else out[0]


def _head_xent_chunks(xf, head, targets, weights, with_grads):
    """One ``lax.scan`` over token chunks of the weighted cross-entropy
    ``sum(weights * (logsumexp(x @ head) - (x @ head)[target]))``.

    xf: (n_chunks, chunk, dim) hidden states in the compute dtype; head:
    (dim, vocab) master weights; targets, weights: (n_chunks, chunk). With
    several prediction heads, head is (dim, heads x vocab) and targets,
    weights are (n_chunks, chunk, heads): a token's row of logits is read
    as (heads, vocab) and each head has its own logsumexp, in the same
    pass. With ``with_grads`` the same iteration that forms a chunk's logits
    also forms its ``d loss / d logits`` and multiplies it out, so the
    head product is never recomputed: returns ``(loss, dxf, dhead)``
    (``dhead`` a float32 carry), else ``loss`` alone.
    """
    head_c = head.astype(xf.dtype)
    heads = targets.shape[2:]           # () or (n_pred_heads,)
    vocab_ids = jnp.arange(head.shape[1] // int(np.prod(heads)))

    def chunk(carry, args):
        loss, dhead = carry
        xc, tc, wc = args
        logits = jax.lax.dot_general(xc, head_c, (((1,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)
        if heads:
            logits = logits.reshape(*tc.shape, -1)
        lse = jax.nn.logsumexp(logits, axis=-1)
        tl = jnp.take_along_axis(logits, tc[..., None], axis=-1)[..., 0]
        loss = loss + jnp.sum(wc * (lse - tl))
        if not with_grads:
            return (loss, dhead), None
        # softmax - onehot, weighted, rounded to the compute dtype: what
        # autodiff's cotangent of ``.astype(float32)`` is.
        dlogits = ((jnp.exp(logits - lse[..., None])
                    - (vocab_ids == tc[..., None])) * wc[..., None]).astype(xc.dtype)
        if heads:
            dlogits = dlogits.reshape(tc.shape[0], -1)
        # Both products below read dlogits. Without the barrier XLA fuses
        # the exp/onehot arithmetic into each of them and derives dlogits
        # twice from the float32 logits (ledger, PR 27 probes: 605.7 ms a
        # step of the token cell against 593.9 with it).
        dlogits = jax.lax.optimization_barrier(dlogits)
        dxc = jax.lax.dot_general(dlogits, head_c, (((1,), (1,)), ((), ())))
        dhead = dhead + jax.lax.dot_general(
            xc, dlogits, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return (loss, dhead), dxc

    dhead0 = jnp.zeros(head.shape if with_grads else (), jnp.float32)
    (loss, dhead), dxf = jax.lax.scan(
        chunk, (jnp.zeros((), jnp.float32), dhead0), (xf, targets, weights))
    return (loss, dxf, dhead) if with_grads else loss


@jax.custom_vjp
def _chunked_xent(xf, head, targets, weights):
    """Weighted chunked cross-entropy (see :func:`_head_xent_chunks`).
    Outside ``grad`` only the forward product runs."""
    return _head_xent_chunks(xf, head, targets, weights, with_grads=False)


def _chunked_xent_fwd(xf, head, targets, weights):
    loss, dxf, dhead = _head_xent_chunks(xf, head, targets, weights,
                                         with_grads=True)
    return loss, (dxf, dhead.astype(head.dtype))


def _chunked_xent_bwd(residuals, g):
    dxf, dhead = residuals
    return ((g * dxf).astype(dxf.dtype), (g * dhead).astype(dhead.dtype),
            None, None)


_chunked_xent.defvjp(_chunked_xent_fwd, _chunked_xent_bwd)


def _multi_head_targets(tokens, n_heads: int):
    """Targets and mask of ``n_heads`` prediction heads over the FULL
    window (the ``"roll"`` layout): ``targets[b, t, m] = tokens[b, t + 1 +
    m]`` and ``mask[t, m] = 1`` where that position is inside the window
    -> ``(b, s, heads)`` int, ``(s, heads)`` float32."""
    s = tokens.shape[1]
    targets = jnp.stack([jnp.roll(tokens, -(m + 1), axis=1)
                         for m in range(n_heads)], axis=-1)
    ahead = jnp.arange(s)[:, None] + 1 + jnp.arange(n_heads)[None, :]
    return targets, (ahead < s).astype(jnp.float32)


def loss_fn(params, batch, cfg: LlamaConfig, attn_fn=None, activation_spec=None,
            expert_spec=None, aux_weight: float = 1e-2, layers_fn=None,
            embed_lookup: str = "gather", compute_dtype=jnp.bfloat16,
            shift: str = "split", xent_chunk: int | None = None,
            remat_layers: bool = False, window_attn_fn=None,
            with_stats: bool = False, eva_attn_fn=None):
    """Next-token cross entropy (+ MoE load-balancing aux for switch
    dispatch). batch: {'tokens': (b, s) int32}. ``compute_dtype=float32``
    makes activation math exact — the PP-parity pinning mode (microbatched
    accumulation reorders bf16 sums; in f32 the pipeline and the sequential
    loop agree to ~1e-5 at dryrun shapes).

    ``shift`` picks how inputs/targets derive from the token window:

    * ``"split"`` (default): inputs ``tokens[:, :-1]``, targets
      ``tokens[:, 1:]`` — the textbook layout, model seq = s - 1.
    * ``"roll"``: inputs are the FULL window, targets are
      ``roll(tokens, -1)`` with the wraparound position masked out of the
      mean — model seq = s. This is the sharding-friendly layout (the one
      production TPU trainers use): a ``P("data", "seq")``-sharded batch
      stays divisible by the mesh seq axis end to end, whereas split mode
      would need an s = multiple-of-sp **plus one** window that cannot be
      device_put evenly.

    ``with_stats`` returns ``(loss, stats)``: the expert layers'
    statistics of :func:`apply`, for ``value_and_grad(has_aux=True)``.

    With ``cfg.n_pred_heads > 1`` (needs ``shift="roll"`` and
    ``xent_chunk``) head ``m``
    predicts the token ``m + 1`` positions on, and the loss is the mean
    cross-entropy over the heads and the positions whose target lies in
    the window (:func:`_multi_head_targets`).

    ``xent_chunk`` (must divide ``batch * model seq``) computes the loss
    head ``xent_chunk`` tokens at a time in one loop that, under ``grad``,
    also yields the hidden states' and the head's gradients: the
    ``(b, s, V)`` logits never exist, peak logit memory is
    O(``xent_chunk`` x V), plus one float32 ``(dim, vocab)`` accumulator
    for the head's gradient (537 MB at 4096 x 32768). ``None`` keeps the
    full-logits form.
    """
    tokens = batch["tokens"]
    if shift not in ("split", "roll"):
        raise ValueError(f"unknown shift {shift!r}")
    if cfg.n_pred_heads > 1 and (shift != "roll" or not xent_chunk):
        raise ValueError("n_pred_heads > 1 needs shift='roll' (every head "
                         "reads the full window) and xent_chunk (the heads "
                         "share the chunked loss head's one pass)")
    inputs = tokens if shift == "roll" else tokens[:, :-1]
    run = partial(apply, params, inputs, cfg, attn_fn=attn_fn,
                  activation_spec=activation_spec, expert_spec=expert_spec,
                  with_aux=True, layers_fn=layers_fn,
                  embed_lookup=embed_lookup, compute_dtype=compute_dtype,
                  remat_layers=remat_layers, window_attn_fn=window_attn_fn,
                  with_stats=with_stats, eva_attn_fn=eva_attn_fn)
    if xent_chunk:
        # Never materialize the (b, s, V) logits: at 32k context and 32k
        # vocab the full tensor is ~4.2 GB f32 (plus its cotangent), which
        # alone decides whether a single 16 GB chip can train. One scan over
        # token chunks (_chunked_xent) forms each chunk's logits once and,
        # under grad, its dx and dhead in the same iteration: three head
        # products a step, O(chunk * V) logit memory, a float32 dhead carry.
        # Recomputing the logits in a second, backward loop (autodiff of a
        # checkpointed chunk body) costs a fourth product: 620.23 against
        # 593.79 ms a step at Mistral-7B widths, 16k tokens, chunk 2048
        # (ledger, PR 27, mistral7b-tok4k-1chip, resident_step_ms.tokens).
        x, aux, *stats = run(return_hidden=True)
        with jax.named_scope(scopes.LOSS_HEAD):
            b, s, dm = x.shape
            if cfg.n_pred_heads > 1:
                targets, mask = _multi_head_targets(tokens, cfg.n_pred_heads)
            elif shift == "roll":
                targets = jnp.roll(tokens, -1, axis=1)
                mask = (jnp.arange(s) < s - 1).astype(jnp.float32)
            else:
                targets = tokens[:, 1:]
                mask = jnp.ones((s,), jnp.float32)
            n_tok = b * s
            if n_tok % xent_chunk:
                raise ValueError(f"xent_chunk ({xent_chunk}) must divide "
                                 f"batch*seq ({n_tok})")
            # The weights carry the roll mask and the mean's denominator.
            weights = jnp.broadcast_to(mask / (mask.sum() * b), (b, *mask.shape))
            chunks = (n_tok // xent_chunk, xent_chunk, *mask.shape[1:])
            nll = _chunked_xent(x.reshape(*chunks[:2], dm), params["lm_head"],
                                targets.reshape(chunks), weights.reshape(chunks))
            loss = nll + aux_weight * aux
        return (loss, stats[0]) if with_stats else loss
    logits, aux, *stats = run()
    with jax.named_scope(scopes.LOSS_HEAD):
        # Fused form: nll = logsumexp(logits) - logits[target]. Identical math
        # to log_softmax + gather (log_softmax = logits - lse), but XLA skips
        # materializing the full (b, s, V) log-prob tensor — measured 13%
        # faster for the 4k-token loss+grad on TPU v5 lite (10.8 -> 9.4 ms;
        # a chunked/remat variant measured slower at this scale, 11.4 ms).
        lse = jax.nn.logsumexp(logits, axis=-1)                      # (b, s)
        if shift == "roll":
            targets = jnp.roll(tokens, -1, axis=1)
            tl = jnp.take_along_axis(logits, targets[..., None],
                                     axis=-1)[..., 0]                # (b, s)
            nll_tok = lse - tl
            mask = (jnp.arange(tokens.shape[1]) < tokens.shape[1] - 1)
            nll = (nll_tok * mask).sum() / (mask.sum() * tokens.shape[0])
        else:
            targets = tokens[:, 1:]
            tl = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
            nll = (lse - tl).mean()
        loss = nll + aux_weight * aux
    return (loss, stats[0]) if with_stats else loss


def make_train_step(cfg: LlamaConfig, learning_rate: float = 3e-4,
                    attn_fn=None, activation_spec=None, expert_spec=None,
                    layers_fn=None, embed_lookup: str = "gather",
                    compute_dtype=jnp.bfloat16, shift: str = "split",
                    xent_chunk: int | None = None,
                    remat_layers: bool = False, window_attn_fn=None,
                    with_stats: bool = False, eva_attn_fn=None):
    """AdamW train step via optax; jit with sharded params for TP/DP/SP.
    ``with_stats``: the step returns ``(params, opt_state, loss, stats)``,
    ``stats`` the expert layers' per-layer rows (:data:`MOE_STATS`): small
    device arrays, for :func:`publish_moe_stats` between windows. A
    sigmoid router's ``router_bias`` is no weight: it is held outside
    AdamW (no moments, no decay) and comes back from the step as it went
    in; the weights' state then sits at
    ``opt_state.inner_states["weight"].inner_state``."""
    import optax
    tx = optax.adamw(learning_rate, weight_decay=0.1)
    if cfg.router_score == "sigmoid":
        tx = optax.multi_transform(
            {"weight": tx, "held": optax.set_to_zero()},
            lambda params: jax.tree_util.tree_map_with_path(
                lambda path, _: "held" if path[-1] == jax.tree_util.DictKey(
                    "router_bias") else "weight", params))

    def init_opt(params):
        return tx.init(params)

    def train_step(params, opt_state, batch):
        out, grads = jax.value_and_grad(
            partial(loss_fn, cfg=cfg, attn_fn=attn_fn,
                    activation_spec=activation_spec,
                    expert_spec=expert_spec, layers_fn=layers_fn,
                    embed_lookup=embed_lookup,
                    compute_dtype=compute_dtype, shift=shift,
                    xent_chunk=xent_chunk, remat_layers=remat_layers,
                    window_attn_fn=window_attn_fn, with_stats=with_stats,
                    eva_attn_fn=eva_attn_fn),
            has_aux=with_stats)(params, batch)
        with jax.named_scope(scopes.OPTIMIZER):
            updates, opt_state = tx.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
        return (params, opt_state) + (out if with_stats else (out,))

    return init_opt, train_step
