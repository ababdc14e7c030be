"""Readings that a cell's limits are set from (``PERF.md`` gives them):
``python3 -m chipbench.calibrate --workload <cell> --seeds a,b,c
[--controls 3]``. For each seed, in one process: the program's first steps
against the plain reference (the lower reading), and for the first
``--controls`` seeds the reference put in the program's place in the
nearest precision below the configuration's (fp8 for bfloat16: the control)
and with each fault a training cell can have planted in it (half of the
batch left out; on several chips, one chip's rows alone, which is what a
missing exchange leaves). Needs no measured window. Not run by the
benchmark's own runs; writes ``chiprun_out/calibrate_<cell>.json``.
"""
from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

from chipbench import run

CONTROL_PRECISION = {"bfloat16": "float8_e4m3fn", "float32": "bfloat16"}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--controls", type=int, default=3)
    parser.add_argument("--rehearse-cpu", action="store_true")
    args = parser.parse_args(argv)
    _, cell, config, traffic = run.load_cell(args.workload, args.rehearse_cpu)
    sys.path.insert(0, run.ROOT)
    run.prepare_environment()
    from chipbench import check
    devices = run.cell_devices(cell, args.rehearse_cpu)
    if devices is None:
        return 1
    pipeline = importlib.import_module(
        f"chipbench.pipelines.{config['pipeline']}")
    precision = CONTROL_PRECISION[config["compute_dtype"]]
    readings = []
    for n, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t0 = time.time()
        job = pipeline.Job(config, traffic, devices, seed, os.path.join(
            run.STATE_DIR, "stores", cell["name"]))
        first = run.first_steps(job, lambda obj: None, t0)
        keys = [run.np_tree(k) for k in first["keys"]]
        job.free()
        reference = job.reference(keys)
        moved = check.moved_leaves(reference["grad_norms"])
        row = {"seed": seed, "program": check.training_numbers(
            first["program"], reference),
            "program_leaf_gaps": {
                kind: check.leaf_gaps(first["program"][kind + "_norms"],
                                      reference[kind + "_norms"], moved)
                for kind in ("grad", "delta")},
            "reference_grad_norms": reference["grad_norms"],
            "program_losses": first["program"]["losses"],
            "reference_losses": reference["losses"]}
        if n < args.controls:
            per_chip = job.global_batch // len(devices)
            plants = {"control_" + precision: dict(precision=precision),
                      "fault_half_batch": dict(rows=job.global_batch // 2)}
            if len(devices) > 1:
                plants["fault_no_exchange"] = dict(rows=per_chip)
            for name, how in plants.items():
                row[name] = check.training_numbers(
                    job.reference(keys, **how), reference)
        row["seconds"] = time.time() - t0
        readings.append(row)
        print(json.dumps(row), flush=True)
    out_dir = os.path.join(run.ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"calibrate_{cell['name']}.json"),
              "w") as f:
        json.dump({"cell": cell["name"], "device": devices[0].device_kind,
                   "readings": readings}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
