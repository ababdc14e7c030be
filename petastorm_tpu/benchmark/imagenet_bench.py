"""ImageNet-style benchmark: jpeg-decode-bound reader feeding a real
ResNet-50 train step on the local device(s).

This is the BASELINE.md target workload — **samples/sec/chip** and
**input-stall % of step time** — the numbers the reference framework never
published for any accelerator (BASELINE.md:26-28). The store is synthetic
but class-separable (loss goes down), with real jpeg encode/decode through
:class:`petastorm_tpu.codecs.CompressedImageCodec`, so the host-side work
matches a real ImageNet ingest: parquet row-group read -> jpeg decode ->
batch assembly -> HBM staging.
"""
from __future__ import annotations

import time

import numpy as np

from petastorm_tpu.codecs import CompressedImageCodec, ScalarCodec
from petastorm_tpu.etl.writer import materialize_dataset_local
from petastorm_tpu.unischema import Unischema, UnischemaField

def make_imagenet_schema(image_size: int = 224) -> Unischema:
    return Unischema("ImagenetSchema", [
        UnischemaField("image", np.uint8, (image_size, image_size, 3),
                       CompressedImageCodec("jpeg", 85), False),
        UnischemaField("label", np.int32, (), ScalarCodec(np.int32), False),
    ])


ImagenetSchema = make_imagenet_schema()


def write_synthetic_imagenet(url: str, rows: int, classes: int = 100,
                             seed: int = 0, rows_per_row_group: int = 64,
                             image_size: int = 224):
    """Class-separable synthetic images: a per-class 8x8 proto upsampled to
    ``image_size`` plus uniform noise — compresses like a photo, trains like
    a toy. ``image_size`` must be a multiple of 8; smaller sizes make the
    ResNet step CPU-feasible for tests (ResNet is fully convolutional)."""
    if image_size % 8:
        raise ValueError("image_size must be a multiple of 8")
    rng = np.random.default_rng(seed)
    protos = rng.integers(60, 195, (classes, 8, 8, 3)).astype(np.uint8)
    up = image_size // 8
    with materialize_dataset_local(url, make_imagenet_schema(image_size),
                                   rows_per_row_group=rows_per_row_group) as w:
        for _ in range(rows):
            label = int(rng.integers(0, classes))
            base = np.kron(protos[label], np.ones((up, up, 1), np.uint8))
            noise = rng.integers(0, 60, (image_size, image_size, 3)).astype(np.uint8)
            w.write_row({"image": np.clip(base + noise, 0, 255).astype(np.uint8),
                         "label": np.int32(label)})


# Per-chip bf16 peak, keyed by the exact ``device_kind`` string the chip
# reports. A TPU kind missing here is an error (:func:`_peak_flops`), never
# a default: add it with its source when such a chip first runs this code.
_PEAK_BF16_FLOPS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16 per chip. The
    # v5e reports itself as "TPU v5 lite" (chip_smoke.py's first line).
    "TPU v5 lite": 197e12,
}


def hard_sync(x) -> float:
    """Force device-side completion of ``x`` (and everything it depends
    on) by host readback of one element, returning it as a float.

    The one sync primitive of the benchmark loops here: they need the
    loss on the host anyway, and a value transfer cannot return before
    the value exists. ``chip_smoke.py`` times the same resident steps
    closed by this and by ``jax.block_until_ready`` and fails if the two
    disagree by more than 2x."""
    import jax.numpy as jnp
    return float(jnp.ravel(x)[0])


def _peak_flops(platform: str, device_kind: str) -> float | None:
    """bf16 peak FLOP/s of one chip of this kind. The ``cpu`` platform has
    no peak (its results carry ``platform`` and no MFU); any other
    ``device_kind`` must be in :data:`_PEAK_BF16_FLOPS`."""
    if platform == "cpu":
        return None
    try:
        return _PEAK_BF16_FLOPS[device_kind]
    except KeyError:
        raise ValueError(
            f"no bf16 peak known for device_kind {device_kind!r} (platform "
            f"{platform!r}); add it to _PEAK_BF16_FLOPS with its source"
        ) from None


def _flops_of_compiled(compiled) -> float | None:
    """FLOP count from XLA's own cost model
    (``Compiled.cost_analysis()['flops']``); None when the model reports
    none. Undercounts Pallas custom calls (ROADMAP.md S0)."""
    flops = (compiled.cost_analysis() or {}).get("flops")
    return float(flops) if flops and flops > 0 else None


def mosaic_kernels(compiled) -> list:
    """Names of the distinct Pallas kernels that reached the compiled
    step as Mosaic custom calls (``tpu_custom_call`` in
    ``compiled.as_text()``) — empty on a dense or interpret-mode route,
    which is how ``chip_smoke.py`` tells that the kernel a caller asked
    for is the one that ran. The name is the ``pallas_call(name=...)``
    scope in the instruction's ``op_name`` metadata."""
    import re
    names = set()
    for line in compiled.as_text().splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        op_name = re.search(r'op_name="([^"]*)"', line)
        scope = re.search(r"(\w+)\)*/pallas_call", op_name.group(1)
                          ) if op_name else None
        names.add(scope.group(1) if scope
                  else op_name.group(1) if op_name else "unnamed")
    return sorted(names)


def staged_layout(batch) -> tuple:
    """How a staged batch actually sits on the devices: per array,
    ``(global shape, addressable shards, rows per shard, distinct
    devices)`` read off the shard buffers themselves (host-side metadata,
    no device sync)."""
    import jax
    layout = []
    for arr in jax.tree.leaves(batch):
        shards = arr.addressable_shards
        layout.append((tuple(arr.shape), len(shards),
                       tuple(sorted({s.data.shape[0] for s in shards})),
                       len({s.device for s in shards})))
    return tuple(layout)


def recording_layouts(batches, layouts: set):
    """Pass ``batches`` through, adding each one's :func:`staged_layout`
    to ``layouts`` — a fed run reports every layout it was handed, so a
    batch that landed on one device of four cannot go unnoticed."""
    for batch in batches:
        layouts.add(staged_layout(batch))
        yield batch


def device_report(devices) -> dict:
    """What every benchmark result says about where it ran: ``platform``,
    ``device_kind``, device count, and per device the allocator's
    high-water marks since the process started and its limit
    (``memory_stats()``; ``None`` where the backend keeps none, as on
    CPU). On the v5e runtime ``peak_bytes_in_use`` counts live buffers
    only — a compiled step's temporaries are booked under
    ``peak_bytes_reserved`` (chip run, PR 21) — so peak HBM is read from
    both."""
    stats = [d.memory_stats() or {} for d in devices]
    report = {"platform": devices[0].platform,
              "device_kind": devices[0].device_kind,
              "devices": len(devices)}
    for key in ("peak_bytes_in_use", "peak_bytes_reserved", "bytes_limit"):
        report[key] = [s.get(key) for s in stats]
    return report


def pipelined_window(run_step, next_batch, steps: int, resident_steps: int,
                     warm_loss) -> dict:
    """Shared measurement harness for the training benchmarks
    (:func:`run_imagenet_bench`, :func:`..llm_bench.run_llm_bench` —
    one home so their methodologies cannot drift).

    Timing design for an async backend: the measured window is
    wall-clock over ``steps`` pipelined step dispatches, closed by ONE
    :func:`hard_sync` readback. Per-step syncing would serialize
    transfer against compute and measure a regime no real training loop
    runs in. Stall is attributed per-step: ``next_batch()`` waits are
    host-side and need no device sync. Caveat: under async dispatch,
    device execution can overlap a loader wait, so ``wall - wait`` is an
    UPPER-bound attribution of stall and LOWER-bound of step time; the
    resident phase (re-running the step on the last staged batch, no
    host transfer in the loop) is the overlap-free step-time
    measurement. It runs twice, closed once by :func:`hard_sync` and once
    by ``jax.block_until_ready``, so every run shows whether the two
    agree on this backend.

    ``run_step(batch) -> loss`` threads the caller's train state via
    closure; ``next_batch()`` returns a staged batch. Returns
    ``{loss_first, loss_last, losses, wait_s, total_wall_s, resident_s,
    resident_block_until_ready_s}``: ``losses`` is the warm-up loss and
    then every step's, read back after the window closes; the resident
    entries are None when ``resident_steps`` is 0."""
    import jax

    if steps < 1:
        raise ValueError(f"steps must be >= 1, got {steps}")
    loss_first = hard_sync(warm_loss)  # warmup's loss; syncs pre-window
    wait_s = 0.0
    batch = None
    losses = []
    t_start = time.perf_counter()
    for _ in range(steps):
        t0 = time.perf_counter()
        batch = next_batch()
        wait_s += time.perf_counter() - t0
        losses.append(run_step(batch))
    loss_last = hard_sync(losses[-1])  # closes the window
    total_wall = time.perf_counter() - t_start

    resident_s = resident_bur_s = None
    if resident_steps:
        t0 = time.perf_counter()
        for _ in range(resident_steps):
            loss = run_step(batch)
        hard_sync(loss)
        resident_s = (time.perf_counter() - t0) / resident_steps
        t0 = time.perf_counter()
        for _ in range(resident_steps):
            loss = run_step(batch)
        jax.block_until_ready(loss)
        resident_bur_s = (time.perf_counter() - t0) / resident_steps
    return {"loss_first": loss_first, "loss_last": loss_last,
            "losses": [loss_first] + [float(x) for x in losses],
            "wait_s": wait_s, "total_wall_s": total_wall,
            "resident_s": resident_s,
            "resident_block_until_ready_s": resident_bur_s}


def window_result(window: dict, steps: int, devices, compiled,
                  compile_s: float, layouts: set) -> dict:
    """The part of a benchmark result both training benchmarks share:
    where it ran (:func:`device_report`), what the window observed
    (stall, step time, every loss, both resident closings), and what the
    run was made of — compile seconds, the device memory the compiled
    step needs by XLA's own account (arguments + outputs + temporaries,
    less what donation aliases; per device), the Mosaic kernels in it,
    every layout a staged batch arrived in, and the FLOPs/MFU block
    (:func:`utilization_metrics`)."""
    mem = compiled.memory_analysis()
    result = device_report(devices)
    result.update({
        "input_stall_pct": 100.0 * window["wait_s"] / window["total_wall_s"],
        "step_time_ms": 1000.0 * (window["total_wall_s"] - window["wait_s"])
        / steps,
        "loss_first": window["loss_first"],
        "loss_last": window["loss_last"],
        "losses": window["losses"],
        "compile_s": compile_s,
        "compiled_hbm_bytes": (mem.argument_size_in_bytes
                               + mem.output_size_in_bytes
                               + mem.temp_size_in_bytes
                               - mem.alias_size_in_bytes),
        "mosaic_kernels": mosaic_kernels(compiled),
        "staged_layouts": sorted(layouts),
    })
    if window["resident_s"] is not None:
        result["step_time_ms_resident"] = 1000.0 * window["resident_s"]
        result["step_time_ms_resident_block_until_ready"] = (
            1000.0 * window["resident_block_until_ready_s"])
    utilization_metrics(result, _flops_of_compiled(compiled),
                        result["step_time_ms"] / 1e3, window["resident_s"],
                        result["platform"], result["device_kind"])
    return result


def utilization_metrics(result: dict, flops_per_step, step_time_s: float,
                        resident_s, platform: str, device_kind: str) -> None:
    """Fill the shared FLOPs/MFU block (pipelined + resident variants,
    physical-plausibility guard) into ``result`` in place. Per-chip by
    construction: ``flops_per_step`` comes from
    :func:`_flops_of_compiled`, which reports per-device FLOPs on SPMD
    executables. Raises on a non-CPU ``device_kind`` with no known peak."""
    if flops_per_step is None:
        return
    result["model_flops_per_step_per_chip"] = flops_per_step
    achieved = flops_per_step / step_time_s
    result["achieved_tflops_per_chip"] = achieved / 1e12
    peak = _peak_flops(platform, device_kind)
    if peak:
        result["mfu_pct"] = 100.0 * achieved / peak
        if achieved > peak:
            # wall - wait underestimates step time when device execution
            # overlaps a loader wait (see pipelined_window): physically
            # impossible rate = that regime was hit, not a measurement.
            # Drop the bogus pipelined numbers rather than carrying them;
            # the resident metrics below remain valid, so the capture as
            # a whole is still good evidence.
            del result["mfu_pct"]
            del result["achieved_tflops_per_chip"]
            result["mfu_pipelined_dropped"] = (
                "achieved exceeded chip peak: loader-bound window, "
                "wait/compute overlap; "
                + ("use the resident metrics" if resident_s is not None
                   else "re-run with resident_steps>0 for valid MFU"))
    if resident_s is not None:
        r_achieved = flops_per_step / resident_s
        result["achieved_tflops_per_chip_resident"] = r_achieved / 1e12
        if peak:
            result["mfu_pct_resident"] = 100.0 * r_achieved / peak
            if r_achieved > peak:
                # Same physical-plausibility bar as the pipelined window:
                # a resident rate above chip peak means the sync lied
                # (e.g. an async readback returning early), not that the
                # chip did. Drop rather than carry impossible numbers.
                del result["mfu_pct_resident"]
                del result["achieved_tflops_per_chip_resident"]
                result["mfu_resident_dropped"] = (
                    "resident achieved exceeded chip peak: timing/sync "
                    "artifact; no valid MFU for this run")
                if "mfu_pipelined_dropped" in result:
                    # Don't point readers at resident metrics this same
                    # call just deleted.
                    result["mfu_pipelined_dropped"] = (
                        "achieved exceeded chip peak: loader-bound window, "
                        "wait/compute overlap; resident metrics were also "
                        "dropped — no valid MFU for this run")


def run_imagenet_bench(url: str, steps: int = 30, per_device_batch: int = 32,
                       workers_count: int = 4, pool_type: str = "thread",
                       classes: int = 100, prefetch: int = 2,
                       remat: bool = False, resident_steps: int = 0,
                       echo: int = 1) -> dict:
    """One DP training run over all local devices; returns
    ``{samples_per_sec, samples_per_sec_per_chip, input_stall_pct,
    step_time_ms, model_flops_per_step_per_chip, achieved_tflops_per_chip
    [, mfu_pct], ...}`` measured against the real jitted ResNet-50 step.

    Methodology: a PIPELINED wall-clock window over ``steps`` async
    step dispatches, closed by one :func:`hard_sync` readback, with
    per-step host-side timing of ``next(loader)`` for the stall split —
    NOT the per-step-synced loop of
    :func:`throughput.training_input_stall` (per-step syncing
    serializes transfer against compute). The two stall numbers are
    therefore not directly comparable.

    FLOP/s is XLA's compiled cost model over the measured device-step
    time; ``mfu_pct`` is reported against the bf16 peak of the chip's
    exact ``device_kind`` (:data:`_PEAK_BF16_FLOPS`; an unknown TPU kind
    raises). Every result names the ``platform`` it ran on: nothing here
    refuses a CPU (tests call it at toy size) — ``chip_smoke.py`` does."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from petastorm_tpu.jax import DataLoader, DTypePolicy
    from petastorm_tpu.models import resnet
    from petastorm_tpu.reader import make_reader

    devices = jax.devices()
    mesh = Mesh(np.array(devices).reshape(len(devices)), ("data",))
    batch_sharding = NamedSharding(mesh, P("data"))
    replicated = NamedSharding(mesh, P())
    batch_size = per_device_batch * len(devices)

    params = jax.device_put(resnet.init_params(jax.random.PRNGKey(0), classes),
                            replicated)
    velocity = jax.device_put(jax.tree.map(lambda p: p * 0, params), replicated)
    # remat bounds activation memory (~83 MiB/image without it) for
    # per-device batches that would otherwise overflow a 16 GiB chip.
    # No warm-up schedule here, so the rate must train from the first step:
    # on the v5e at batch 128, 0.05 overshoots (loss 4.69 -> 11.6 by step 7,
    # back under its start only after ~45 steps) while 0.01 falls 4.69 ->
    # 4.13 in 30 (chip run, PR 21; PERF.md Findings).
    raw_step = resnet.make_train_step(learning_rate=0.01, remat=remat)

    def preprocess_and_step(params, velocity, batch):
        images = batch["image"].astype(jnp.float32) / 255.0
        return raw_step(params, velocity,
                        {"image": images, "label": batch["label"]})

    step = jax.jit(preprocess_and_step, donate_argnums=(0, 1))

    layouts = set()
    with make_reader(url, num_epochs=None, shuffle_row_groups=True, seed=0,
                     reader_pool_type=pool_type,
                     workers_count=workers_count) as reader:
        loader = DataLoader(reader, batch_size=batch_size,
                            sharding=batch_sharding, prefetch=prefetch,
                            dtype_policy=DTypePolicy(), echo=echo)
        it = recording_layouts(iter(loader), layouts)
        batch = next(it)
        # AOT-compile once: the compiled object both runs the loop and
        # exposes XLA's cost model (no second trace/compile).
        t0 = time.perf_counter()
        step = step.lower(params, velocity, batch).compile()
        compile_s = time.perf_counter() - t0
        params, velocity, loss, acc = step(params, velocity, batch)

        def run_step(b):
            nonlocal params, velocity, acc
            params, velocity, loss, acc = step(params, velocity, b)
            return loss

        window = pipelined_window(run_step, lambda: next(it), steps,
                                  resident_steps, warm_loss=loss)
        result = window_result(window, steps, devices, step, compile_s,
                               layouts)

    sps = steps * batch_size / window["total_wall_s"]
    result.update({
        "samples_per_sec": sps,
        "samples_per_sec_per_chip": sps / len(devices),
        "global_batch": batch_size,
        "echo": echo,
    })
    resident_s = window["resident_s"]
    if resident_s is not None:
        result["samples_per_sec_resident"] = batch_size / resident_s
        result["samples_per_sec_per_chip_resident"] = (
            batch_size / resident_s / len(devices))
    return result
