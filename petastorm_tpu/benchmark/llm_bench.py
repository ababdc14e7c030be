"""LLM-pretrain pipeline benchmark: token store -> NGram windows ->
DataLoader -> llama train step (BASELINE config 5's shape).

This is the end-to-end counterpart to :mod:`.imagenet_bench` for the
sequence path: the reference's only sequence feature is NGram windowed
readout (``/root/reference/petastorm/ngram.py:225`` ``form_ngram``), and
the BASELINE LLM config feeds token windows to a decoder. Here the whole
chain runs on the local device(s): rows decode in reader workers, NGram
assembles timestamp-ordered windows per row group, the loader stacks
windows into a dense ``(batch, window)`` int32 array staged into HBM,
and a real AdamW llama step consumes it. Metrics mirror
:func:`.imagenet_bench.run_imagenet_bench`: pipelined wall-clock window
closed by one :func:`.imagenet_bench.hard_sync`, per-step host-side
stall attribution, and a resident-batch phase isolating chip compute.

``echo`` exercises data echoing (jax/loader.py) in the regime it was
built for: when the single-host reader cannot feed the step rate,
``echo=k`` re-yields each staged batch k times as device-side copies —
the stall comparison echo=1 vs echo>1 is the feature's measurement.
"""
from __future__ import annotations

import time

import numpy as np


def write_token_store(url: str, windows: int, window: int,
                      vocab: int = 32000, seed: int = 0) -> None:
    """Timestamped token store, one NGram window per row group (windows
    never cross row groups — same layout contract as the reference's
    NGram, ngram.py:86-91 there)."""
    from petastorm_tpu.codecs import ScalarCodec
    from petastorm_tpu.etl.writer import materialize_dataset_local
    from petastorm_tpu.unischema import Unischema, UnischemaField

    schema = Unischema("TokSchema", [
        UnischemaField("ts", np.int64, (), ScalarCodec(np.int64), False),
        UnischemaField("token", np.int32, (), ScalarCodec(np.int32), False),
    ])
    rng = np.random.default_rng(seed)
    with materialize_dataset_local(url, schema,
                                   rows_per_row_group=window) as w:
        for i in range(windows * window):
            w.write_row({"ts": np.int64(i),
                         "token": np.int32(rng.integers(0, vocab))})


def run_llm_bench(url: str, steps: int = 20, batch_size: int = 8,
                  window: int = 512, workers_count: int = 8,
                  pool_type: str = "thread", echo: int = 1,
                  resident_steps: int = 0, dense: bool = True,
                  flash: bool = False, xent_chunk: int | None = None,
                  remat_layers: bool = False,
                  model_kwargs: dict | None = None,
                  mesh_ingest: bool = False,
                  mesh_hosts: int | None = None) -> dict:
    """Token windows through the full reader stack into a real llama
    train step; returns ``{tokens_per_sec, input_stall_pct,
    step_time_ms, loss_first, loss_last[, *_resident], ...}``.

    Timing methodology is identical to
    :func:`.imagenet_bench.run_imagenet_bench` (pipelined window, single
    readback sync, per-step host-side stall split, wait/compute-overlap
    caveat and all).

    ``mesh_ingest=True`` swaps the single-reader ``DataLoader`` for the
    multi-host :class:`~petastorm_tpu.jax.mesh_loader.MeshDataLoader`
    (docs/mesh.md): ``mesh_hosts`` per-host readers each decode a
    disjoint row-group shard and every step assembles one global
    ``(batch, window)`` token array across the whole slice — the
    ctx32k/ctx64k single-chip baselines scaled out. The result then
    carries the loader's ``mesh_report`` (per-host stall/skew/reshard).
    Requires ``dense=True`` (windows need the fixed-shape layout) and
    ``batch_size`` divisible by the data-axis size.

    ``flash=True`` asks for the Pallas kernel: a ``window`` its tiles
    cannot divide raises here, before anything is traced, instead of
    quietly training through dense attention.
    """
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from petastorm_tpu.benchmark.imagenet_bench import (pipelined_window,
                                                        recording_layouts,
                                                        window_result)
    from petastorm_tpu.jax import DataLoader
    from petastorm_tpu.models import llama
    from petastorm_tpu.ngram import NGram
    from petastorm_tpu.reader import make_reader

    devices = jax.devices()
    mesh = Mesh(np.array(devices).reshape(len(devices)), ("data",))
    # flash=True swaps the Pallas flash kernel in for XLA dense attention
    # (the win regime is window >= 8k — the long-context pipeline config).
    # GSPMD cannot partition a Mosaic call by itself ("wrap the call in a
    # shard_map"): each device runs the kernel on its own rows of the batch.
    attn_fn = None
    if flash:
        from petastorm_tpu.ops.flash_attn import (make_flash_attention,
                                                  require_flash_tiles)
        require_flash_tiles(window, window, causal=True)
        rows = P("data")
        attn_fn = jax.shard_map(make_flash_attention(causal=True), mesh=mesh,
                                in_specs=(rows, rows, rows), out_specs=rows,
                                check_vma=False)
        attn_fn.supports_gqa = True
    kw = dict(vocab=32000, dim=1024, n_layers=8, n_heads=8, n_kv_heads=4,
              hidden=2816)
    kw.update(model_kwargs or {})
    cfg = llama.LlamaConfig(**kw)

    params = jax.device_put(llama.init_params(jax.random.PRNGKey(0), cfg),
                            NamedSharding(mesh, P()))
    init_opt, raw_step = llama.make_train_step(cfg, shift="roll",
                                               attn_fn=attn_fn,
                                               xent_chunk=xent_chunk,
                                               remat_layers=remat_layers)
    opt = init_opt(params)

    def step_fn(params, opt, tokens):
        return raw_step(params, opt, {"tokens": tokens})

    step = jax.jit(step_fn, donate_argnums=(0, 1))

    # dense=True is the TPU-first readout (column-major window assembly in
    # the worker, no per-row namedtuples); dense=False measures the
    # reference-parity row path for comparison.
    ngram = NGram({o: ["ts", "token"] for o in range(window)},
                  delta_threshold=1, timestamp_field="ts",
                  timestamp_overlap=False, dense=dense)
    if mesh_ingest:
        if not dense:
            raise ValueError("mesh_ingest requires dense=True NGram readout")
        from petastorm_tpu.jax import MeshDataLoader, MeshReaderFactory
        factory = MeshReaderFactory(url, batched=False, schema_fields=ngram,
                                    reader_pool_type=pool_type)
        loader = MeshDataLoader(factory, batch_size=batch_size, mesh=mesh,
                                partition_spec=P("data"),
                                num_hosts=mesh_hosts, num_epochs=None,
                                seed=0, echo=echo)
    else:
        reader = make_reader(url, schema_fields=ngram, num_epochs=None,
                             shuffle_row_groups=True, seed=0,
                             reader_pool_type=pool_type,
                             workers_count=workers_count)
        try:
            loader = DataLoader(reader, batch_size=batch_size,
                                sharding=NamedSharding(mesh, P("data")),
                                echo=echo)
        except BaseException:
            # The loader owns reader shutdown only once constructed.
            reader.stop()
            reader.join()
            raise
    layouts = set()
    with loader:  # closes the underlying reader(s) on exit
        it = (b["token"] for b in recording_layouts(iter(loader), layouts))
        tokens = next(it)
        if tokens.shape != (batch_size, window):
            raise ValueError(f"staged tokens are {tokens.shape}, expected "
                             f"{(batch_size, window)}")
        t0 = time.perf_counter()
        step = step.lower(params, opt, tokens).compile()
        compile_s = time.perf_counter() - t0
        params, opt, loss = step(params, opt, tokens)

        def run_step(toks):
            nonlocal params, opt
            params, opt, loss = step(params, opt, toks)
            return loss

        window_obs = pipelined_window(run_step, lambda: next(it), steps,
                                      resident_steps, warm_loss=loss)
        result = window_result(window_obs, steps, devices, step, compile_s,
                               layouts)
        mesh_report = loader.mesh_report() if mesh_ingest else None

    tokens_per_step = batch_size * window
    result.update({
        "tokens_per_sec": tokens_per_step * steps / window_obs["total_wall_s"],
        "tokens_per_step": tokens_per_step,
        "echo": echo,
        "dense": dense,
        "flash": flash,
        "xent_chunk": xent_chunk,
        "remat_layers": remat_layers,
        "window": window,
    })
    resident_s = window_obs["resident_s"]
    if resident_s is not None:
        result["tokens_per_sec_resident"] = tokens_per_step / resident_s
        result["tokens_per_sec_per_chip_resident"] = (
            tokens_per_step / resident_s / len(devices))
    if mesh_report is not None:
        result["mesh_ingest"] = True
        result["mesh_hosts"] = mesh_report["hosts"]
        result["mesh_report"] = mesh_report
    return result


def _ctx_label(window: int) -> str:
    """32768 -> "32k" (the ``ctx<N>k_`` key prefix of :func:`main`)."""
    return f"{window // 1024}k" if window % 1024 == 0 else str(window)


def main(argv=None) -> int:
    """Long-context llama phase CLI; ``--mesh`` scales ingestion from one
    device to every device of the host::

        python -m petastorm_tpu.benchmark.llm_bench --ctx 32768 --mesh \
            --flash --xent-chunk 2048 --out MULTICHIP_r06.json

    ``--out`` writes a MULTICHIP_r0*.json-shape record: the wrapper keys
    (``n_devices``/``rc``/``ok``/``tail``), the ``platform`` and
    ``device_kind`` it ran on, and ``parsed`` carrying ``ctx<N>k_``-prefixed
    metrics — the keys ``tools/bench_compare.py --prefix MULTICHIP``
    consumes. It runs on whatever backend JAX finds and says which;
    ``chip_smoke.py`` is the entry that refuses a CPU.
    """
    import argparse
    import json
    import os
    import sys

    parser = argparse.ArgumentParser(
        description="llama train-step pipeline benchmark (ctx32k/ctx64k "
                    "phases; --mesh = multi-host GSPMD mesh ingestion)")
    parser.add_argument("--ctx", type=int, default=32768,
                        help="context window (tokens per row group)")
    parser.add_argument("--mesh", action="store_true",
                        help="ingest through MeshDataLoader across every "
                             "device (docs/mesh.md) instead of the "
                             "single-reader DataLoader")
    parser.add_argument("--hosts", type=int, default=None,
                        help="feeding hosts for --mesh (default: JAX "
                             "process count, or one per device in a "
                             "single-process simulation)")
    parser.add_argument("--steps", type=int, default=8)
    parser.add_argument("--batch-size", type=int, default=None,
                        help="GLOBAL batch; default 1 per data-axis shard")
    parser.add_argument("--windows", type=int, default=None,
                        help="windows in the token store (default: enough "
                             "for warmup+steps at the chosen batch)")
    parser.add_argument("--flash", action="store_true",
                        help="Pallas flash attention (the >=8k-context "
                             "config; TPU-only in practice)")
    parser.add_argument("--xent-chunk", type=int, default=None)
    parser.add_argument("--remat-layers", action="store_true")
    parser.add_argument("--tiny-model", action="store_true",
                        help="4-layer dim-256 config for CPU-simulation "
                             "dry runs; full BASELINE llama otherwise")
    parser.add_argument("--data-dir",
                        default=os.environ.get("BENCH_DATA_DIR",
                                               "/tmp/pt_bench"))
    parser.add_argument("--out", default=None,
                        help="write a MULTICHIP-shape record JSON here")
    args = parser.parse_args(argv)

    import jax

    from petastorm_tpu.jax.compile_cache import ensure_compile_cache
    ensure_compile_cache()
    n_devices = jax.device_count()
    batch = args.batch_size
    if batch is None:
        from petastorm_tpu.parallel.mesh import batch_shard_count, make_mesh
        from jax.sharding import PartitionSpec
        batch = batch_shard_count(make_mesh([-1], ["data"]),
                                  PartitionSpec("data"))
    label = _ctx_label(args.ctx)
    windows = args.windows or max(4 * batch, batch * (args.steps + 2))
    store = os.path.join(args.data_dir, f"tokens_ctx{label}_w{windows}")
    url = f"file://{store}"
    if not os.path.exists(os.path.join(store, "_common_metadata")):
        write_token_store(url, windows=windows, window=args.ctx)

    model_kwargs = ({"dim": 256, "n_layers": 4, "n_heads": 4,
                     "n_kv_heads": 2, "hidden": 704} if args.tiny_model
                    else None)
    result = run_llm_bench(url, steps=args.steps, batch_size=batch,
                           window=args.ctx, flash=args.flash,
                           xent_chunk=args.xent_chunk,
                           remat_layers=args.remat_layers,
                           model_kwargs=model_kwargs,
                           mesh_ingest=args.mesh, mesh_hosts=args.hosts)

    parsed = {f"ctx{label}_{k}": v for k, v in result.items()
              if not isinstance(v, (dict, list))}
    parsed[f"ctx{label}_mesh"] = bool(args.mesh)
    if "mesh_report" in result:
        rep = result["mesh_report"]
        parsed[f"ctx{label}_mesh_hosts"] = rep["hosts"]
        parsed[f"ctx{label}_mesh_host_skew_s"] = rep["host_skew_s"]
        parsed[f"ctx{label}_mesh_reshard_events"] = rep["reshard_events"]
        parsed[f"ctx{label}_mesh_max_host_stall_pct"] = max(
            (h["input_stall_pct"] for h in rep["per_host"].values()),
            default=0.0)
    tail = (f"llm ctx{label} {'mesh' if args.mesh else 'single-reader'} "
            f"ingestion on {n_devices} {result['platform']} device(s): "
            f"{result['tokens_per_sec']:.1f} tok/s, input stall "
            f"{result['input_stall_pct']:.2f}%, step "
            f"{result['step_time_ms']:.1f} ms, loss "
            f"{result['loss_first']:.4f} -> {result['loss_last']:.4f}")
    print(tail)
    print(json.dumps(parsed))
    if args.out:
        record = {"n_devices": n_devices, "rc": 0, "ok": True,
                  "platform": result["platform"],
                  "device_kind": result["device_kind"],
                  "parsed": parsed, "tail": tail + "\n"}
        with open(args.out, "w") as f:
            json.dump(record, f, indent=1)
        print(f"record -> {args.out}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    import sys as _sys
    _sys.exit(main())
