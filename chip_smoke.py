"""The quickest proof that the main path still starts on the chip.

``python chip_smoke.py`` — no arguments, one process, from a plain copy of
the tree (no git, no network, no installed package). It drives store ->
``make_reader`` -> ``DataLoader`` / ``MeshDataLoader`` -> HBM staging ->
jitted, donated train step through the repo's own entry points
(:func:`run_imagenet_bench`, :func:`run_llm_bench`), at full model width
and a few steps each, over one ``("data",)`` mesh of every device JAX
reports — so the same script serves one chip and four:

============== ======================================================
``image``      JPEG 224x224 store -> ResNet-50, per-chip batch 128,
               8 thread workers
``tokens_4k``  4096-token windows -> the llama preset, AdamW, Pallas
               flash attention, dense NGram readout
``tokens_32k`` 32768-token windows, batch 1 per chip, chunked
               cross-entropy
``tokens_4k``  again through ``MeshDataLoader``: the other loader, and
(mesh)         the same shapes, so its compile is a persistent-cache hit
============== ======================================================

Every line printed is one JSON object. The last but one is the summary
(per-leg results, failures, compile cache, native libraries); the last is
the verdict and nothing else, ``{"ok": ..., "device": {"platform", "kind",
"count"}}``, the device as JAX reports it. The exit status is non-zero
when the platform is not ``tpu``, when any check of any leg fails, or when
anything raises: no leg is wrapped in a ``try``.

``--cpu-dry-run`` is the only other mode, for rehearsing the command in a
sandbox without a chip: toy sizes, Pallas in interpret mode,
``"dry_run": true`` on every line. The caller types it; the script never
chooses it. Step times printed here are sanity observations, not
benchmark results.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import tempfile

_HERE = os.path.dirname(os.path.abspath(__file__))

# model_kwargs=None is run_llm_bench's preset: dim 1024, 8 layers, 8/4
# heads, hidden 2816, vocab 32000. Full width; only step counts are cut.
_FULL = {
    "image": dict(image_size=224, per_device_batch=128, workers_count=8,
                  steps=30, resident_steps=8),
    "tokens_4k": dict(window=4096, per_device_batch=4, steps=20,
                      resident_steps=4, xent_chunk=None, model_kwargs=None),
    "tokens_32k": dict(window=32768, per_device_batch=1, steps=2,
                       resident_steps=0, xent_chunk=2048, model_kwargs=None),
}
_TOY_MODEL = dict(vocab=256, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
                  hidden=128)
_DRY = {
    "image": dict(image_size=32, per_device_batch=2, workers_count=2,
                  steps=2, resident_steps=1),
    "tokens_4k": dict(window=64, per_device_batch=1, steps=2,
                      resident_steps=1, xent_chunk=None,
                      model_kwargs=_TOY_MODEL),
    "tokens_32k": dict(window=128, per_device_batch=1, steps=2,
                       resident_steps=0, xent_chunk=64,
                       model_kwargs=_TOY_MODEL),
}


def _cache_entries(cache_dir: str) -> int:
    """Compiled executables in the persistent cache (``<key>-cache``)."""
    if not os.path.isdir(cache_dir):
        return 0
    return sum(1 for f in os.listdir(cache_dir) if f.endswith("-cache"))


def _check(result: dict, *, platform: str, n_devices: int, global_batch: int,
           falling: bool, flash: bool, dry_run: bool) -> list:
    """Every way this leg's result can be wrong, as readable strings."""
    bad = []
    if result["platform"] != platform or result["devices"] != n_devices:
        bad.append(f"ran on {result['devices']} x {result['platform']}, "
                   f"expected {n_devices} x {platform}")
    losses = result["losses"]
    if not all(math.isfinite(x) for x in losses):
        bad.append(f"non-finite loss: {losses}")
    elif falling and not dry_run and not losses[-1] < losses[0]:
        bad.append(f"loss did not fall: {losses[0]} -> {losses[-1]}")
    want_rows = (global_batch // n_devices,)
    for layout in result["staged_layouts"]:
        for shape, shards, rows, on_devices in layout:
            if (shape[0], shards, rows, on_devices) != (
                    global_batch, n_devices, want_rows, n_devices):
                bad.append(f"staged array {shape}: {shards} shards of rows "
                           f"{rows} on {on_devices} devices, expected "
                           f"{n_devices} of {want_rows} on {n_devices}")
    if dry_run:
        # The CPU backend keeps no memory statistics and interprets Pallas,
        # and a toy step is too short to time: the rest is for the chip.
        return bad
    if not all(result["peak_bytes_in_use"]):
        bad.append(f"no peak memory: {result['peak_bytes_in_use']}")
    if flash and len(result["mosaic_kernels"]) < 3:
        bad.append("flash step holds fewer than three Mosaic kernels: "
                   f"{result['mosaic_kernels']}")
    readback = result.get("step_time_ms_resident")
    if readback is not None:
        blocked = result["step_time_ms_resident_block_until_ready"]
        if max(readback, blocked) > 2.0 * min(readback, blocked):
            bad.append(f"sync methods disagree: readback {readback:.3f} ms "
                       f"vs block_until_ready {blocked:.3f} ms per step")
    return bad


def _observations(name: str, result: dict, failures: list) -> dict:
    keep = ("platform", "device_kind", "devices", "global_batch",
            "tokens_per_step", "window", "compile_s", "step_time_ms",
            "step_time_ms_resident",
            "step_time_ms_resident_block_until_ready", "input_stall_pct",
            "peak_bytes_in_use", "peak_bytes_reserved", "bytes_limit",
            "compiled_hbm_bytes", "mosaic_kernels", "staged_layouts",
            "mesh_hosts", "mfu_pct_resident")
    line = {"leg": name, "ok": not failures, "failures": failures}
    line.update({k: result[k] for k in keep if k in result})
    line["losses"] = [round(x, 4) for x in result["losses"]]
    return line


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--cpu-dry-run", action="store_true",
                        help="rehearse on the CPU backend at toy sizes "
                             "(never a substitute for the chip run)")
    dry_run = parser.parse_args(argv).cpu_dry_run
    sizes = _DRY if dry_run else _FULL
    want_platform = "cpu" if dry_run else "tpu"

    sys.path.insert(0, _HERE)  # the package is used from the checkout
    import jax
    import jaxlib

    from petastorm_tpu.benchmark.imagenet_bench import (
        run_imagenet_bench, write_synthetic_imagenet)
    from petastorm_tpu.benchmark.llm_bench import (run_llm_bench,
                                                   write_token_store)
    from petastorm_tpu.jax.compile_cache import ensure_compile_cache
    from petastorm_tpu.native import ring_available
    from petastorm_tpu.native.imgcodec import imgcodec_available

    cache_dir = ensure_compile_cache()
    devices = jax.devices()
    platform, n_dev = devices[0].platform, len(devices)
    if platform != want_platform:
        print(f"chip_smoke: JAX found {n_dev} x {platform!r} "
              f"({devices[0].device_kind}), this run needs "
              f"{want_platform!r}" + ("" if dry_run else
              "; --cpu-dry-run rehearses without a chip"), file=sys.stderr)
        return 1

    def emit(obj: dict) -> None:
        print(json.dumps({**obj, "dry_run": True} if dry_run else obj),
              flush=True)

    try:
        from importlib.metadata import version
        libtpu = version("libtpu")
    except ImportError:
        libtpu = None
    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": n_dev}
    native = {"imgcodec": imgcodec_available(), "ring": ring_available()}
    entries_before = _cache_entries(cache_dir)
    emit({"chip_smoke": "start", "jax": jax.__version__,
          "jaxlib": jaxlib.__version__, "libtpu": libtpu, "device": device,
          "compile_cache_dir": cache_dir,
          "compile_cache_entries": entries_before, "native": native})

    legs, all_failures = {}, []

    def leg(name: str, result: dict, **expect) -> None:
        failures = _check(result, platform=platform, n_devices=n_dev,
                          dry_run=dry_run, **expect)
        legs[name] = _observations(name, result, failures)
        emit(legs[name])
        all_failures.extend(f"{name}: {f}" for f in failures)

    if not native["imgcodec"]:
        # S1's question is what the native decoder delivers; a smoke that
        # quietly decoded through cv2/PIL would answer another one.
        all_failures.append("native image decoder did not build")

    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as data:
        cfg = sizes["image"]
        batch = cfg["per_device_batch"] * n_dev
        url = f"file://{data}/imagenet"
        write_synthetic_imagenet(url, rows=4 * batch, seed=0,
                                 image_size=cfg["image_size"])
        leg("image",
            run_imagenet_bench(url, steps=cfg["steps"],
                               per_device_batch=cfg["per_device_batch"],
                               workers_count=cfg["workers_count"],
                               pool_type="thread",
                               resident_steps=cfg["resident_steps"]),
            global_batch=batch, falling=True, flash=False)

        def token_leg(leg_name: str, name: str, falling: bool,
                      mesh_ingest: bool = False) -> None:
            cfg = sizes[name]
            batch = cfg["per_device_batch"] * n_dev
            url = f"file://{data}/{name}"
            if not os.path.exists(f"{data}/{name}"):
                vocab = (cfg["model_kwargs"] or {}).get("vocab", 32000)
                write_token_store(url, windows=4 * batch,
                                  window=cfg["window"], vocab=vocab, seed=0)
            leg(leg_name,
                run_llm_bench(url, steps=cfg["steps"], batch_size=batch,
                              window=cfg["window"], flash=True, dense=True,
                              xent_chunk=cfg["xent_chunk"],
                              resident_steps=cfg["resident_steps"],
                              model_kwargs=cfg["model_kwargs"],
                              mesh_ingest=mesh_ingest),
                global_batch=batch, falling=falling, flash=True)

        token_leg("tokens_4k_flash", "tokens_4k", falling=True)
        token_leg("tokens_32k_flash", "tokens_32k", falling=False)
        entries_mid = _cache_entries(cache_dir)
        token_leg("tokens_4k_flash_mesh", "tokens_4k", falling=True,
                  mesh_ingest=True)

    entries_after = _cache_entries(cache_dir)
    if entries_after != entries_mid:
        all_failures.append(
            f"MeshDataLoader repeat of tokens_4k added "
            f"{entries_after - entries_mid} compile-cache entries; identical "
            f"shapes must hit the cache")
    verdict = {"ok": not all_failures, "device": device}
    summary = {"chip_smoke": "summary", **verdict, "failures": all_failures,
               "legs": legs, "native": native,
               "compile_cache": {"dir": cache_dir,
                                 "entries_before": entries_before,
                                 "entries_after": entries_after},
               "compile_s_tokens_4k": [
                   legs["tokens_4k_flash"]["compile_s"],
                   legs["tokens_4k_flash_mesh"]["compile_s"]]}
    out_dir = os.path.join(_HERE, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "chip_smoke.json"), "w") as f:
        json.dump({**summary, "dry_run": dry_run}, f, indent=1)
    emit(summary)
    # The driver reads the last line and accepts these two keys only.
    emit(verdict)
    return 1 if all_failures else 0


if __name__ == "__main__":
    sys.exit(main())
