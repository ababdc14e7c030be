"""Long-context training end to end: NGram token windows -> global batches
on a dp x seq mesh -> Llama with GQA ring attention (sequence parallelism).

This wires the framework's long-context pieces together in one script:

* **Data**: a chunked token-stream Parquet store read as NGram windows
  (``rowgroup_coalescing`` merges small groups so windows can span them);
* **Staging**: ``DataLoader`` assembles fixed-shape global ``jax.Array``
  batches sharded (data, seq) over the mesh — each sequence lands already
  split across the ``seq`` axis devices;
* **Compute**: ring attention streams K/V blocks around the ``seq`` axis
  with ``ppermute`` (online softmax, block-level causal skip), K/V at
  native GQA width; the decoder's activations carry a
  ``P("data", "seq", None)`` constraint so GSPMD keeps the layout.

Run on real chips or on a virtual mesh:
    XLA_FLAGS=--xla_force_host_platform_device_count=8 python main.py
"""
import argparse
import time

import numpy as np

from petastorm_tpu import Unischema, UnischemaField
from petastorm_tpu.codecs import NdarrayCodec, ScalarCodec
from petastorm_tpu.etl.writer import materialize_dataset_local
from petastorm_tpu.ngram import NGram
from petastorm_tpu.reader import make_reader

CHUNK = 64  # tokens per stored row

TokenSchema = Unischema("TokenSchema", [
    UnischemaField("seq", np.int64, (), ScalarCodec(np.int64), False),
    UnischemaField("tokens", np.int32, (CHUNK,), NdarrayCodec(), False),
])


def write_token_stream(url: str, n_chunks: int, vocab: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    tokens = np.empty(n_chunks * CHUNK, np.int32)
    tokens[0] = 1
    noise = rng.integers(0, 4, n_chunks * CHUNK)
    for i in range(1, len(tokens)):
        tokens[i] = (tokens[i - 1] * 31 + noise[i]) % vocab
    with materialize_dataset_local(url, TokenSchema, rows_per_row_group=64) as w:
        for c in range(n_chunks):
            w.write_row({"seq": c, "tokens": tokens[c * CHUNK:(c + 1) * CHUNK]})


def train(url: str, steps: int = 30, per_shard_batch: int = 2,
          window: int = 8, vocab: int = 256, dp: int = 2, sp: int = 4,
          attn_kind: str = "ring"):
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from petastorm_tpu.models import llama
    from petastorm_tpu.parallel.ring_attention import make_ring_attention

    assert len(jax.devices()) >= dp * sp, (
        f"need {dp * sp} devices for a dp{dp} x sp{sp} mesh, have "
        f"{len(jax.devices())} — run with XLA_FLAGS="
        f"--xla_force_host_platform_device_count={dp * sp} (or shrink dp/sp)")
    devices = np.array(jax.devices()[:dp * sp]).reshape(dp, sp)
    mesh = Mesh(devices, ("data", "seq"))
    # Tokens shard on data only; the activation constraint below places the
    # sequence dim on the seq axis right after embedding, and ring
    # attention's shard_map keeps it there.
    batch_sharding = NamedSharding(mesh, P("data", None))
    seq_len = window * CHUNK  # the MODEL input length; must divide by sp
    assert seq_len % sp == 0
    batch_size = per_shard_batch * dp

    cfg = llama.LlamaConfig(vocab=vocab, dim=128, n_layers=2, n_heads=8,
                            n_kv_heads=4, hidden=256)
    # Sequence-parallel attention menu (all exact, all GQA-native):
    # ring ppermute streaming; ring with chunked+remat local steps
    # (bounded per-step score memory); Ulysses all-to-all; Ulysses with
    # the Pallas flash local step.
    if attn_kind == "ring":
        attn = make_ring_attention(mesh, seq_axis="seq", data_axis="data",
                                   causal=True)
    elif attn_kind == "ring-chunked":
        attn = make_ring_attention(mesh, seq_axis="seq", data_axis="data",
                                   causal=True, local_block_q=CHUNK // 2)
    elif attn_kind == "ring-flash":
        # Fused Pallas local step: each ring hop computes its block's
        # online-softmax partials in VMEM (no HBM score tile at all).
        attn = make_ring_attention(mesh, seq_axis="seq", data_axis="data",
                                   causal=True, local_attn="flash")
    elif attn_kind in ("ulysses", "ulysses-flash"):
        from petastorm_tpu.parallel.ulysses_attention import \
            make_ulysses_attention
        attn = make_ulysses_attention(
            mesh, seq_axis="seq", data_axis="data", causal=True,
            local_attn="flash" if attn_kind == "ulysses-flash" else "dense")
    else:
        raise ValueError(f"unknown attn kind {attn_kind!r}")
    act_spec = NamedSharding(mesh, P("data", "seq", None))
    params = jax.device_put(llama.init_params(jax.random.PRNGKey(0), cfg),
                            NamedSharding(mesh, P()))
    init_opt, train_step = llama.make_train_step(cfg, learning_rate=1e-3,
                                                 attn_fn=attn,
                                                 activation_spec=act_spec)
    opt_state = init_opt(params)
    step = jax.jit(train_step, donate_argnums=(0, 1))

    # window+1 chunks per sample: seq_len tokens of input + 1 for the shifted
    # next-token target (loss_fn uses tokens[:-1] -> predict tokens[1:]).
    # dense=True: each window arrives as {"tokens": (window+1, CHUNK)}.
    ngram = NGram({i: ["tokens"] for i in range(window + 1)},
                  delta_threshold=1, timestamp_field="seq",
                  timestamp_overlap=True, dense=True)

    def batches():
        while True:
            with make_reader(url, schema_fields=ngram, num_epochs=1,
                             shuffle_row_groups=True, seed=0,
                             workers_count=2, rowgroup_coalescing=4) as reader:
                buf = []
                for win in reader:
                    seq = win["tokens"].reshape(-1)
                    # seq_len model inputs + 1 shifted target token
                    buf.append(seq[:seq_len + 1])
                    if len(buf) == batch_size:
                        arr = np.stack(buf).astype(np.int32)
                        yield {"tokens": jax.device_put(
                            jnp.asarray(arr), batch_sharding)}
                        buf = []

    it = batches()
    batch = next(it)
    params, opt_state, loss = step(params, opt_state, batch)  # compile
    jax.block_until_ready(loss)

    losses = []
    t0 = time.perf_counter()
    for i in range(steps):
        batch = next(it)
        params, opt_state, loss = step(params, opt_state, batch)
        losses.append(float(loss))
        if (i + 1) % 10 == 0:
            print(f"step {i+1}: loss={np.mean(losses[-10:]):.4f}")
    jax.block_until_ready(loss)
    dt = time.perf_counter() - t0
    tps = steps * batch_size * seq_len / dt
    print(f"mesh dp{dp} x sp{sp}  seq_len={seq_len}  "
          f"throughput={tps:,.0f} tokens/sec  final_loss={losses[-1]:.4f} "
          f"(random={np.log(vocab):.2f})")
    assert losses[-1] < losses[0], "loss did not decrease"
    return losses


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--url", default="file:///tmp/long_context_tokens")
    parser.add_argument("--chunks", type=int, default=8192)
    parser.add_argument("--vocab", type=int, default=256)
    parser.add_argument("--steps", type=int, default=30)
    parser.add_argument("--window", type=int, default=8)
    parser.add_argument("--dp", type=int, default=2)
    parser.add_argument("--sp", type=int, default=4)
    parser.add_argument("--attn", default="ring",
                        choices=["ring", "ring-chunked", "ring-flash",
                                 "ulysses", "ulysses-flash"])
    args = parser.parse_args()
    from petastorm_tpu.jax.compile_cache import ensure_compile_cache
    ensure_compile_cache()
    import os
    if not os.path.exists(args.url.replace("file://", "") + "/_common_metadata"):
        write_token_stream(args.url, args.chunks, args.vocab)
    train(args.url, steps=args.steps, window=args.window, vocab=args.vocab,
          dp=args.dp, sp=args.sp, attn_kind=args.attn)


if __name__ == "__main__":
    main()
