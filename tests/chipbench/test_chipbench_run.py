"""The runner's contract with the driver, as far as a CPU can show it: no
chip, no result; the rehearsal flag drives the whole control flow and says
so on every line."""
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "mistral7b-tok4k-1chip"


def run_cell(*extra, cwd=ROOT, timeout=400):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "BENCH_RUN": "ignored"}
    return subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", CELL, "--seed",
         "2147483659", "--seconds", "1", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=timeout)


def test_without_a_chip_the_runner_reports_nothing():
    done = run_cell("--trace", "0")
    assert done.returncode != 0
    assert done.stdout == ""
    assert "needs 1 x 'tpu'" in done.stderr


def test_an_unknown_workload_is_refused():
    done = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", "nope", "--seed",
         "1", "--seconds", "1", "--trace", "0"], cwd=ROOT,
        capture_output=True, text=True, timeout=120)
    assert done.returncode != 0 and done.stdout == ""


def test_alone_in_a_directory_the_benchmark_fails(tmp_path):
    """BENCHMARK.json and the files under ``paths``, without the program."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for path in ("chipbench", "tests/chipbench"):
        shutil.copytree(os.path.join(ROOT, path), tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    done = run_cell("--trace", "0", "--rehearse-cpu", cwd=tmp_path)
    assert done.returncode != 0
    assert done.stdout == ""


@pytest.fixture(scope="module")
def rehearsal():
    done = run_cell("--trace", "1", "--rehearse-cpu")
    assert done.returncode == 0, done.stderr[-2000:]
    return done


def test_rehearsal_marks_every_line_and_names_no_metric(rehearsal):
    lines = [json.loads(line) for line in rehearsal.stdout.splitlines()]
    assert len(lines) >= 4 and all(line["rehearsal"] is True for line in lines)
    result = lines[-1]
    assert result["metrics"] == {}
    assert result["device"]["platform"] == "cpu"
    assert {"host_cpu_us_per_token", "input_stall_pct.tokens",
            "resident_step_ms.tokens"} <= set(result["rehearsal_readings"])
    # Shares of a peak need a chip's peak: the readers return nothing.
    assert not any("mfu" in k or "roofline" in k
                   for k in result["rehearsal_readings"])


def test_rehearsal_follows_the_whole_control_flow(rehearsal):
    result = json.loads(rehearsal.stdout.splitlines()[-1])
    assert list(result)[-1] == "compared"
    assert {"correct", "attempted", "failed", "metrics", "device",
            "breakdown"} <= set(result)
    assert result["correct"] is True, result["compared"]
    assert result["attempted"] >= 3 and result["failed"] == 0
    assert {"window_s", "busy_s", "memory_peak_bytes"} <= set(result["device"])
    for name in ("loss_gap", "grad_norm_gap", "update_norm_gap",
                 "rows_out_of_group", "staged_elements_wrong",
                 "compiles_in_window"):
        assert result["compared"][name]["value"] <= \
            result["compared"][name]["limit"]
    tail = rehearsal.stderr.strip().splitlines()
    assert tail[-1] == "chipbench: correct = True"
    assert any(line.startswith("chipbench: loss_gap = ") for line in tail)
