"""LLM pretraining tokens through the petastorm-tpu pipeline (BASELINE
config 5): a token-stream Parquet store read as NGram windows, batched and
staged to device, feeding a Llama-style decoder train step.

Each row is one fixed-size token *chunk* of a document stream (``seq`` =
chunk ordinal — the NGram timestamp); an NGram of length W concatenates W
consecutive chunks into one training sequence, never crossing row groups
(so row-group sharding across TPU hosts needs no inter-host coordination).
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
    """A synthetic markov-ish token stream with learnable structure."""
    rng = np.random.default_rng(seed)
    # token t+1 depends on t: next = (t * 31 + noise) % vocab
    tokens = np.empty(n_chunks * CHUNK, np.int32)
    tokens[0] = 1
    noise = rng.integers(0, 4, n_chunks * CHUNK)
    for i in range(1, len(tokens)):
        tokens[i] = (tokens[i - 1] * 31 + noise[i]) % vocab
    with materialize_dataset_local(url, TokenSchema, rows_per_row_group=256) as w:
        for c in range(n_chunks):
            w.write_row({"seq": c, "tokens": tokens[c * CHUNK:(c + 1) * CHUNK]})


def train(url: str, steps: int = 40, batch_size: int = 8, window: int = 4,
          vocab: int = 256):
    import jax
    import jax.numpy as jnp

    from petastorm_tpu.models import llama

    # For long contexts, the same model exposes three levers this example
    # keeps off at its toy scale: make_train_step(xent_chunk=...) (chunked
    # cross-entropy, no (b, s, V) logits), remat_layers=True (per-layer
    # jax.checkpoint that keeps the flash kernel's output, so the backward
    # pass recomputes the block but launches no second forward kernel), and
    # attn_fn=make_flash_attention() (O(seq) memory)
    # (see the docstrings of llama.loss_fn and llama.apply).
    cfg = llama.LlamaConfig(vocab=vocab, dim=128, n_layers=2, n_heads=8,
                            n_kv_heads=4, hidden=256)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    init_opt, train_step = llama.make_train_step(cfg, learning_rate=1e-3)
    opt_state = init_opt(params)
    step = jax.jit(train_step, donate_argnums=(0, 1))

    # dense=True: each sample arrives as {"tokens": (window, CHUNK) array}
    # instead of {offset: namedtuple} — one reshape away from a training
    # sequence. (On scalar token stores — one token per row — dense also
    # unlocks the fully vectorized column-major assembly; see
    # petastorm_tpu/ngram.py.)
    ngram = NGram({i: ["tokens"] for i in range(window)},
                  delta_threshold=1, timestamp_field="seq",
                  timestamp_overlap=True, dense=True)

    def batches():
        while True:
            with make_reader(url, schema_fields=ngram, num_epochs=1,
                             shuffle_row_groups=True, seed=0,
                             workers_count=2) as reader:
                buf = []
                for win in reader:
                    buf.append(win["tokens"].reshape(-1))  # (window*CHUNK,)
                    if len(buf) == batch_size:
                        yield {"tokens": jnp.asarray(np.stack(buf), jnp.int32)}
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
    tokens_per_s = steps * batch_size * window * CHUNK / dt
    print(f"throughput={tokens_per_s:,.0f} tokens/sec  "
          f"seq_len={window * CHUNK}  final_loss={losses[-1]:.4f} "
          f"(random={np.log(vocab):.2f})")
    assert losses[-1] < losses[0], "loss did not decrease"


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--url", default="file:///tmp/llm_tokens_tpu")
    parser.add_argument("--chunks", type=int, default=4096)
    parser.add_argument("--steps", type=int, default=40)
    parser.add_argument("--vocab", type=int, default=256)
    args = parser.parse_args()
    from petastorm_tpu.jax.compile_cache import ensure_compile_cache
    ensure_compile_cache()
    import os
    if not os.path.exists(args.url.replace("file://", "") + "/_common_metadata"):
        print("writing token stream store...")
        write_token_stream(args.url, args.chunks, args.vocab)
    train(args.url, steps=args.steps, vocab=args.vocab)


if __name__ == "__main__":
    main()
