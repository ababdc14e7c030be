"""``python3 -m chipbench.run --workload <cell> --seed <n> --seconds <s>
--trace <0|1>``: one run of one cell, in one process, on the chips of the
machine it is started on.

Every line of standard output is one JSON object; the last is the result.
The run fails, and prints no result, when JAX's platform is not ``tpu`` or
there are fewer chips than the cell asks for. ``--rehearse-cpu``, typed by
the caller and marked on every line, drives the same control flow at the
toy sizes of the configuration's and the traffic's ``rehearsal`` blocks,
for the sandbox and the tests; what it reads is never written under a
metric's name.
"""
from __future__ import annotations

import argparse
import gc
import importlib
import importlib.util
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
STATE_DIR = os.path.join(ROOT, ".chipbench")     # stores, native libs, traces
TRACED_SECONDS = 4.0     # a trace of the whole window would be too large
RESIDENT_SECONDS = 1.5
KEPT_BATCHES = 4         # staged batches held for the check, by the seed


def load_cell(workload: str, rehearsal: bool = False) -> tuple:
    """``(benchmark, cell, config, traffic)`` found by the names in
    ``BENCHMARK.json``; ``rehearsal`` lays the toy sizes over both."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {c["name"]: c for c in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"chipbench: no workload {workload!r}; BENCHMARK.json "
                         f"has {sorted(cells)}")
    cell = cells[workload]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(ROOT, "chipbench", "traffic",
                           f"{cell['traffic']}.json")) as f:
        traffic = json.load(f)
    if rehearsal:
        config = {**config, **config["rehearsal"]}
        traffic = {**traffic, **traffic["rehearsal"]}
    return bench, cell, config, traffic


def layer_metric_reader(name: str):
    """The ``read(run)`` of ``chipbench/layer_metrics/<name>.py``."""
    path = os.path.join(ROOT, "chipbench", "layer_metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_layer_metric_" + name.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def prepare_environment() -> str:
    """Caches inside the checkout, at fixed paths; returns the compile
    cache's directory."""
    os.makedirs(STATE_DIR, exist_ok=True)
    os.environ.setdefault("PETASTORM_TPU_CACHE",
                          os.path.join(STATE_DIR, "native"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from petastorm_tpu.jax.compile_cache import ensure_compile_cache
    cache_dir = ensure_compile_cache()
    # Small programs (init, norms) are set-up too: cache them all.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir


def process_start() -> float:
    """``time.time()`` at which this process began (interpreter start-up
    and imports are set-up too)."""
    import psutil
    return psutil.Process().create_time()


def readback(x) -> float:
    """Wait for ``x`` and bring it to the host. A plain transfer of the
    buffer: it enqueues nothing, so it does not wait for later steps."""
    import numpy as np
    return float(np.asarray(x))


class CompileCounter:
    """Counts backend compilations and cache loads while ``armed``."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax.monitoring
        self.armed, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kwargs):
        if self.armed and event in self.EVENTS:
            self.count += 1


def mosaic_kernel_names(compiled) -> set:
    """``pallas_call`` names of the Mosaic custom calls in a compiled
    step's text."""
    import re
    names = set()
    for line in compiled.as_text().splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            scope = re.search(r'op_name="[^"]*?(\w+)\)*/pallas_call', line)
            names.add(scope.group(1) if scope else "unnamed")
    return names


def memory_peak_bytes(devices):
    """Peak on the fullest chip. On the v5e runtime ``peak_bytes_in_use``
    counts live buffers only and a program's temporaries are booked under
    ``peak_bytes_reserved`` (chip run, PR 21), so the peak is both."""
    peaks = []
    for d in devices:
        stats = d.memory_stats()
        if not stats:
            return None
        peaks.append(stats.get("peak_bytes_in_use", 0)
                     + stats.get("peak_bytes_reserved", 0))
    return max(peaks)


def first_steps(job, emit, started_at: float) -> dict:
    """Store, state, loader, compile, and the first steps through the
    window's own call and feed; the reference follows them from the stored
    bytes once the window has closed."""
    from chipbench import check
    from chipbench.pipelines.common import staged_layout_faults
    chips = len(job.devices)
    job.write_store()
    job.start()
    batch = job.next_batch()
    kernels = mosaic_kernel_names(job.compile(batch))
    emit({"event": "compiled", "workers": job.workers,
          "global_batch": job.global_batch, "mosaic_kernels": sorted(kernels),
          "since_start_s": time.time() - started_at})
    out = {"keys": [], "followed": [], "layout_faults": 0,
           "kernels": kernels, "program": {"losses": []}}
    for n in range(check.FOLLOWED_STEPS):
        batch = batch if n == 0 else job.next_batch()
        out["followed"].append(job.host_copy(batch))
        out["keys"].append(job.batch_key(batch))
        out["layout_faults"] += staged_layout_faults(batch, chips)
        out["program"]["losses"].append(readback(job.step(batch)))
        if n == 0:
            out["program"]["grad_norms"] = job.grad_leaf_norms()
    out["program"]["delta_norms"] = job.delta_leaf_norms()
    out["batch"] = batch
    return out


def drive(job, *, cell: dict, bench: dict, seconds: float, trace: bool,
          seed: int, device: dict, emit, started_at: float,
          rehearsal: bool = False) -> dict:
    """Everything a run does once it has its chips and its job: set-up,
    the window, the comparison; returns the result line's object."""
    import numpy as np

    from chipbench import check, flops, trace_reduce, window
    from chipbench.pipelines.common import staged_layout_faults

    chips = len(job.devices)
    counter = CompileCounter()
    first = first_steps(job, emit, started_at)
    keys, program = first["keys"], first["program"]

    rng = np.random.default_rng(seed)
    keep = set(rng.choice(32, KEPT_BATCHES, replace=False).tolist())
    kept, state = {}, {"n": 0, "layout_faults": first["layout_faults"]}

    def dispatch(b):
        n = state["n"]
        state["n"] = n + 1
        keys.append(job.batch_key(b))
        state["layout_faults"] += staged_layout_faults(b, chips)
        if n in keep:
            kept[n] = b
        kept["last"] = b
        return job.step(b)

    run = {"job": job, "chips": chips, "trace": None, "traced_log": None,
           "resident_log": None}
    if trace:
        # The ceiling: the same compiled step on one staged batch.
        resident = first["batch"]
        run["resident_log"] = window.closed_loop(
            lambda: resident, job.step, readback, RESIDENT_SECONDS)
        run["traced_log"], run["trace"] = traced_window(
            job, dispatch, min(TRACED_SECONDS, seconds))
        emit({"event": "traced", "steps": run["traced_log"].steps,
              "wall_s": run["traced_log"].wall_s,
              "spans": len(run["trace"]["spans"])})
        state["n"] = 0
        kept.clear()

    gc.collect()
    cpu0 = os.times()
    counter.armed = True
    setup_s = time.time() - started_at
    log = window.closed_loop(job.next_batch, dispatch, readback, seconds)
    counter.armed = False
    cpu1 = os.times()
    run["log"] = log
    run["cpu_s"] = (cpu1.user + cpu1.system) - (cpu0.user + cpu0.system)
    peak = memory_peak_bytes(job.devices)

    host_keys = [np_tree(k) for k in keys]
    numbers = dict(job.delivery(host_keys))
    numbers["staged_elements_wrong"] = sum(
        job.staged_faults(b) for b in
        first["followed"] + [job.host_copy(b) for b in kept.values()])
    numbers["layouts_wrong"] = state["layout_faults"]
    numbers["compiles_in_window"] = counter.count
    # Interpreted on the CPU, a Pallas kernel is no Mosaic call.
    expected = () if rehearsal else job.expected_kernels
    numbers["kernels_missing"] = trace_reduce.roles_missing(
        expected, first["kernels"])
    numbers["losses_not_finite"] = int(
        sum(not np.isfinite(x) for x in log.losses))
    stall = job.stall_report()
    kept.clear()
    followed_keys = host_keys[:check.FOLLOWED_STEPS]
    job.free()
    gc.collect()

    t0 = time.time()
    reference = job.reference(followed_keys)
    numbers.update(check.training_numbers(program, reference))
    limits = check.load_limits(ROOT, cell["name"], rehearsal)
    correct, compared = check.verdict(numbers, limits)
    emit({"event": "compared", "reference_s": time.time() - t0,
          "program_losses": program["losses"],
          "reference_losses": reference["losses"],
          "training_numbers": {k: numbers[k] for k in check.TRAINING_NUMBERS},
          "worst_leaves": numbers["_worst_leaves"],
          "window_loss_first": log.losses[0], "window_loss_last":
          log.losses[-1], "loader_delivery_wait_s": stall.get(
              "delivery_wait_s"), "runner_wait_s": sum(log.wait_s)})

    run["peak"] = None if rehearsal else flops.peaks(device["kind"])
    metrics = {}
    wanted = bench["per_layer"] if trace else bench["end_to_end"]
    for m in wanted:
        if "workloads" in m and cell["name"] not in m["workloads"]:
            continue
        if not trace:
            value = end_to_end(m["name"], log, job, chips, setup_s)
        else:
            value = layer_metric_reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = dict(device, memory_peak_bytes=peak)
    result = {"correct": correct, "attempted": log.steps,
              "failed": numbers["losses_not_finite"]}
    if rehearsal:
        result.update({"metrics": {}, "rehearsal_readings": metrics})
    else:
        result["metrics"] = metrics
    if trace:
        device["busy_s"] = trace_reduce.busy_seconds(run["trace"])
        device["window_s"] = run["traced_log"].wall_s
        result["breakdown"] = {
            "device_ops": trace_reduce.top_ops(run["trace"]),
            "idle_gaps": trace_reduce.idle_gaps(run["trace"])}
    result["device"] = device
    result["compared"] = compared
    return result


def np_tree(tree):
    import jax
    import numpy as np
    return jax.tree.map(np.asarray, tree)


def end_to_end(name: str, log, job, chips: int, setup_s: float):
    """The end-to-end metrics, all from the host's clock around the
    window; one that is not this cell's returns None."""
    from chipbench import window
    if name == "setup_s":
        return setup_s
    if name == "step_p95_ms":
        return window.percentile(window.step_intervals_ms(log), 95)
    if name == f"{job.unit}_per_s_per_chip":
        return window.rate_per_chip(log, job.items_per_step, chips)
    return None


def traced_window(job, dispatch, seconds: float) -> tuple:
    """A short window under the profiler, with the loop's three host
    activities as spans -> (its log, the reduced trace)."""
    import jax

    from chipbench import trace_reduce, window
    trace_dir = os.path.join(STATE_DIR, "trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    # The host is part of what is measured: no Python tracer, and only the
    # spans written on purpose (at the default level the runtime's own
    # per-chunk events of a 38 MB transfer starve the loader: 311 MB of
    # trace for ten steps, chip run, PR 25).
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        log = window.closed_loop(job.next_batch, dispatch, readback, seconds,
                                 span=jax.profiler.TraceAnnotation)
    finally:
        jax.profiler.stop_trace()
    reduced = trace_reduce.load(trace_reduce.find_xplane(trace_dir))
    shutil.rmtree(trace_dir, ignore_errors=True)
    return log, reduced


def cell_devices(cell: dict, rehearsal: bool):
    """The cell's chips, or None (said on stderr) when JAX's platform is
    not ``tpu`` (``cpu`` in a rehearsal) or holds fewer than it asks for.
    There is no falling back."""
    import jax
    devices = jax.devices()
    want = "cpu" if rehearsal else "tpu"
    if devices[0].platform != want or len(devices) < cell["chips"]:
        print(f"chipbench: JAX found {len(devices)} x "
              f"{devices[0].platform!r} ({devices[0].device_kind}); cell "
              f"{cell['name']!r} needs {cell['chips']} x {want!r}"
              + ("" if rehearsal else
                 " (--rehearse-cpu rehearses without a chip)"),
              file=sys.stderr)
        return None
    return devices[:cell["chips"]]


def main(argv=None) -> int:
    started_at = process_start()
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--rehearse-cpu", action="store_true",
                        help="toy sizes on the CPU backend: control flow "
                             "only, never a measurement")
    args = parser.parse_args(argv)
    rehearsal = args.rehearse_cpu
    bench, cell, config, traffic = load_cell(args.workload, rehearsal)

    sys.path.insert(0, ROOT)    # the program is used from the checkout
    cache_dir = prepare_environment()
    import jax
    devices = cell_devices(cell, rehearsal)
    if devices is None:
        return 1
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}

    def emit(obj: dict) -> None:
        print(json.dumps({"rehearsal": True, **obj} if rehearsal else obj),
              flush=True)

    emit({"event": "start", "workload": cell["name"], "seed": args.seed,
          "seconds": args.seconds, "trace": args.trace, "device": device,
          "compile_cache_dir": cache_dir, "jax": jax.__version__})
    pipeline = importlib.import_module(
        f"chipbench.pipelines.{config['pipeline']}")
    job = pipeline.Job(config, traffic, devices, args.seed,
                       os.path.join(STATE_DIR, "stores", cell["name"]))
    result = drive(job, cell=cell, bench=bench, seconds=args.seconds,
                   trace=bool(args.trace), seed=args.seed, device=device,
                   emit=emit, started_at=started_at, rehearsal=rehearsal)
    for name, c in result["compared"].items():
        print(f"chipbench: {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(f"chipbench: correct = {result['correct']}", file=sys.stderr)
    emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
