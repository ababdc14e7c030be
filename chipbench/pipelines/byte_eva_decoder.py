"""``token_decoder``'s path (store -> ``make_reader`` + ``NGram(dense=True)``
-> ``DataLoader`` -> the donated AdamW step of ``llama.make_train_step``)
for a byte-level decoder with EVA attention and several prediction heads:
the store's ``token`` column is **uint8** (a token is one byte on disk, in
the row group, on the wire and in HBM; the id offset and the widening
happen inside the jitted step), the attention is the three ``eva_*`` Pallas
kernels under ``shard_map``, and the float32 reference is
``reference/evabyte.py``. Fills in ``token_decoder``'s hooks (the
``LlamaConfig``, the attention callable, the operations of a step, the
kernel family, the step's batch, the reference a row at a time) and keeps
the byte store."""
from __future__ import annotations

import numpy as np

from chipbench import flops_eva, stores
from chipbench.pipelines import token_decoder


def byte_schema():
    from petastorm_tpu.codecs import ScalarCodec
    from petastorm_tpu.unischema import Unischema, UnischemaField
    return Unischema("ChipbenchBytes", [
        UnischemaField("ts", np.int64, (), ScalarCodec(np.int64), False),
        UnischemaField("token", np.uint8, (), ScalarCodec(np.uint8), False),
    ])


def llama_config(c: dict):
    """The program's static configuration from the file's keys."""
    from petastorm_tpu.models import llama
    return llama.LlamaConfig(
        vocab=c["vocab_size"], dim=c["hidden_size"],
        n_layers=c["num_hidden_layers"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        hidden=c["intermediate_size"], rope_theta=float(c["rope_theta"]),
        norm_eps=c["rms_norm_eps"], attention="eva",
        eva_window=c["window_size"], eva_chunk=c["chunk_size"],
        norm_unit_offset=c["norm_add_unit_offset"],
        n_pred_heads=c["num_pred_heads"], fp32_skip_add=c["fp32_skip_add"])


class Job(token_decoder.Job):
    llama_config = staticmethod(llama_config)
    train_flops = staticmethod(flops_eva.train_flops)
    expected_kernels = ("eva",)
    ref = "evabyte"
    ref_by_rows = True

    def attention_fns(self, lcfg, sharded) -> dict:
        from petastorm_tpu.ops.eva_attn import make_eva_attention
        return {"eva_attn_fn": sharded(
            make_eva_attention(lcfg.eva_window, lcfg.eva_chunk),
            replicated=2, gqa=False)}

    def step_batch(self, tokens) -> dict:
        """The staged bytes are widened, and become ids, on the device."""
        import jax.numpy as jnp
        return {"tokens": tokens.astype(jnp.int32)
                + self.cfg["token_id_offset"]}

    # ------------------------------------------------------------ program
    def write_store(self) -> None:
        """Timestamped bytes uniform over 0..255, one window a row group:
        ``stores.write_token_store`` with a uint8 column."""
        import pyarrow as pa
        stores._fresh_dir(self.store_path)
        rng = np.random.default_rng(self.seed)
        n = self.n_groups * self.window
        schema = byte_schema()
        table = pa.Table.from_pydict(
            {"ts": pa.array(np.arange(n, dtype=np.int64)),
             "token": pa.array(rng.integers(0, 256, n, dtype=np.uint8))},
            schema=schema.as_arrow_schema())
        stores._write_parts(self.store_path, table, self.window, schema)

    def next_batch(self):
        batch = super().next_batch()
        if batch["token"].dtype != np.uint8:
            raise ValueError(f"staged bytes are {batch['token'].dtype}")
        return batch

    # ---------------------------------------------------------- reference
    def reference_rows(self, tokens: np.ndarray) -> np.ndarray:
        return tokens.astype(np.int32) + self.cfg["token_id_offset"]

    def counted(self, ref, positions) -> int:
        """(position, head) pairs of one row that the loss counts."""
        return ref.counted_pairs(self.window, self.cfg["num_pred_heads"],
                                 positions)
