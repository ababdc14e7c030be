"""``petastorm-tpu-throughput`` CLI (parity: reference benchmark/cli.py,
``petastorm-throughput.py``)."""
from __future__ import annotations

import argparse
import json
import logging
import sys


def build_parser():
    parser = argparse.ArgumentParser(
        description="Measure petastorm-tpu reader throughput on a dataset")
    parser.add_argument("dataset_url", help="Dataset URL (file://, s3://, hdfs://, ...)")
    parser.add_argument("-f", "--field-regex", nargs="+",
                        help="Read only fields matching these regexes")
    parser.add_argument("-w", "--workers-count", type=int, default=3)
    parser.add_argument("-p", "--pool-type", default="thread",
                        choices=["thread", "process", "dummy"])
    parser.add_argument("-m", "--warmup-cycles", type=int, default=200)
    parser.add_argument("-n", "--measure-cycles", type=int, default=1000)
    parser.add_argument("-d", "--read-method", default="python",
                        choices=["python", "jax", "tf"])
    parser.add_argument("-q", "--shuffling-queue-size", type=int, default=500)
    parser.add_argument("--min-after-dequeue", type=int, default=400)
    parser.add_argument("--device-step-ms", type=float, default=None,
                        help="With -d jax: overlap batches against a calibrated "
                             "on-device step of this duration and report honest "
                             "input-stall%% (approaches 0 when the step dominates)")
    parser.add_argument("--profile-threads", action="store_true",
                        help="With -p thread: cProfile the reader pool and "
                             "print stats (cumulative-sorted) when the reader "
                             "closes. Per-worker profiles pre-3.12; on 3.12+ "
                             "one process-wide profile (cProfile's global "
                             "sys.monitoring slot) that also includes the "
                             "measurement thread's frames and overhead")
    parser.add_argument("--spawn-new-process", action="store_true",
                        help="Re-run the measurement in a fresh interpreter so "
                             "RSS is not polluted by this process's history")
    parser.add_argument("--rowgroup-coalescing", type=int, default=1,
                        help="Read up to N same-file row groups per IO call")
    parser.add_argument("--json", action="store_true", help="Emit one JSON line")
    parser.add_argument("-v", action="store_true", help="INFO logging")
    parser.add_argument("-vv", action="store_true", help="DEBUG logging")
    return parser


def main(argv=None):
    args = build_parser().parse_args(argv)
    if args.vv:
        logging.basicConfig(level=logging.DEBUG)
    elif args.v:
        logging.basicConfig(level=logging.INFO)

    if args.spawn_new_process:
        # Fresh-interpreter respawn for clean RSS numbers (methodology
        # parity: reference benchmark/throughput.py:144-149).
        import subprocess
        argv = list(sys.argv[1:] if argv is None else argv)
        # The flag may appear as any unambiguous argparse prefix
        # (--spawn-new, --sp, ...) — match by prefix, not literal.
        argv = [a for a in argv
                if not (a.startswith("--sp") and "--spawn-new-process".startswith(a))]
        return subprocess.call(
            [sys.executable, "-m", "petastorm_tpu.benchmark.cli", *argv])

    if args.read_method == "jax":
        # The jax read path jits its calibrated device step.
        from petastorm_tpu.jax.compile_cache import ensure_compile_cache
        ensure_compile_cache()
    from petastorm_tpu.benchmark.throughput import reader_throughput
    result = reader_throughput(
        args.dataset_url, field_regex=args.field_regex,
        warmup_cycles=args.warmup_cycles, measure_cycles=args.measure_cycles,
        pool_type=args.pool_type, loaders_count=args.workers_count,
        shuffling_queue_size=args.shuffling_queue_size,
        min_after_dequeue=args.min_after_dequeue,
        read_method=args.read_method,
        device_step_ms=args.device_step_ms,
        profile_threads=args.profile_threads,
        reader_extra_kwargs=(
            {"rowgroup_coalescing": args.rowgroup_coalescing}
            if args.rowgroup_coalescing > 1 else None))
    if args.json:
        print(json.dumps({"samples_per_second": result.samples_per_second,
                          "memory_rss_mb": result.memory_rss_mb,
                          "cpu_percent": result.cpu_percent,
                          "input_stall_percent": result.input_stall_percent,
                          "device_step_ms_actual": result.device_step_ms_actual}))
    else:
        print(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
