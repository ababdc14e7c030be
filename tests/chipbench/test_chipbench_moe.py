"""What the sparse-expert configuration adds: ``flops_moe.py`` against hand
counts, the three new readers on a synthetic ``run``, and the cell's
rehearsal."""
import json
import os
import subprocess
import sys

import pytest

from chipbench import flops, flops_moe
from chipbench.run import layer_metric_reader

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TOKEN_CELL = "smallthinker21b-tok16k-1chip"


def sizes():
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "smallthinker21b-tp4-d4.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("seq,window,pairs", [
    (3, 4, 6),                  # s < w: the triangle 1 + 2 + 3
    (4, 4, 10),                 # s = w: still the triangle
    (16, 4, 10 + 12 * 4),       # s = 4w: the triangle's head, then 4 a row
    (16, None, 136),
    (16384, 4096, 4096 * 4097 // 2 + 12288 * 4096)])
def test_band_pairs_by_hand(seq, window, pairs):
    assert flops_moe.band_pairs(seq, window) == pairs
    by_rows = sum(min(i + 1, window or seq) for i in range(seq))
    assert by_rows == pairs


def test_the_configuration_states_its_state_bytes():
    c = sizes()
    d, hd, v = c["hidden_size"], c["head_dim"], c["vocab_size"]
    layer = (2 * d * c["num_attention_heads"] * hd
             + 2 * d * c["num_key_value_heads"] * hd
             + d * c["moe_router_outputs"]
             + c["moe_num_primary_experts"] * 3 * d * c["moe_ffn_hidden_size"]
             + 2 * d)
    params = c["num_hidden_layers"] * layer + 2 * v * d + d
    assert layer - 2 * d == 99_778_560 and params == 593_615_360
    assert c["state_bytes"] == 16 * params


def test_one_step_by_hand():
    c = sizes()
    # 6 per matrix parameter per token: attention 5.24M, router 0.16M, and
    # 6 * 16/64 = 1.5 experts of 3 x 2560 x 768 a token; the head's slice.
    layer = 2 * 2560 * 896 + 2 * 2560 * 128 + 2560 * 64 + 1.5 * 3 * 2560 * 768
    assert flops_moe.layer_matrix_params(c) == layer
    tokens = 2 * 16384
    matmul = 6 * (4 * layer + 2560 * 37984) * tokens
    full = 4 * 2 * 7 * 128 * (16384 * 16385 // 2)
    band = 4 * 2 * 7 * 128 * (4096 * 4097 // 2 + 12288 * 4096)
    assert flops_moe.train_flops(c, 2, 16384) == matmul + 3 * (full + 3 * band)
    assert 36.5e12 < flops_moe.train_flops(c, 2, 16384) < 37.5e12
    # The band is 44% of the triangle at four windows: 56% of it skipped.
    assert 0.43 < band / full < 0.44


def test_swa_call_counts_the_band_and_each_operand_once():
    fwd = flops_moe.swa_call("fwd", 2, 7, 1, 16384, 128, 4096)
    bwd = flops_moe.swa_call("bwd", 2, 7, 1, 16384, 128, 4096)
    pairs = 4096 * 4097 // 2 + 12288 * 4096
    assert fwd["flops"] == 2 * 2 * 2 * 7 * 128 * pairs
    assert bwd["flops"] == 5 * fwd["flops"] // 2
    q, kv, stats = 2 * 16384 * 7 * 128 * 2, 2 * 16384 * 128 * 2, 2 * 7 * 16384 * 4
    assert fwd["bytes"] == 2 * q + 2 * kv + stats
    assert bwd["bytes"] == 4 * q + 4 * kv + 2 * stats
    # A window as long as the sequence is the causal call (to one diagonal).
    whole = flops_moe.swa_call("fwd", 2, 7, 1, 4096, 128, 4096)
    causal = flops.flash_call("fwd", 2, 7, 1, 4096, 128)
    assert whole["bytes"] == causal["bytes"]
    assert 0 < whole["flops"] - causal["flops"] < causal["flops"] / 4000
    with pytest.raises(ValueError):
        flops_moe.swa_call("both", 2, 7, 1, 4096, 128, 4096)


class FakeJob:
    def __init__(self, cfg, counters=None):
        self.cfg = cfg
        self.traffic = {"per_chip_batch": 2, "window": 16384}
        self.counters, self.asked = counters, []

    def publish_moe_stats(self, last_steps):
        self.asked.append(last_steps)
        return self.counters


class FakeLog:
    steps = 60


def synthetic_run(job, events):
    return {"job": job, "log": FakeLog(), "trace": {"devices": {0: events},
                                                    "spans": []},
            "peak": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}


def test_swa_rooflines_read_their_own_kernels_only():
    c = sizes()
    fwd = flops_moe.swa_call("fwd", 2, 7, 1, 16384, 128, 4096)
    bwd = flops_moe.swa_call("bwd", 2, 7, 1, 16384, 128, 4096)
    least_fwd = int(1e9 * fwd["flops"] / 197e12)
    least_bwd = int(1e9 * bwd["flops"] / 197e12)
    events = [("%swa_fwd.1 = custom-call()", 0, 4 * least_fwd),
              ("%checkpoint_swa_fwd.2 = custom-call()", 0, 4 * least_fwd),
              ("%swa_bwd_dq.1 = custom-call()", 0, least_bwd),
              ("%swa_bwd_dkv.1 = custom-call()", 0, least_bwd),
              ("%flash_fwd.1 = custom-call()", 0, 123456789),
              ("%fusion.7 = fusion()", 0, 5)]
    run = synthetic_run(FakeJob(c), events)
    assert layer_metric_reader("swa_fwd_roofline")(run) == \
        pytest.approx(25.0, rel=1e-6)
    assert layer_metric_reader("swa_bwd_roofline")(run) == \
        pytest.approx(50.0, rel=1e-6)
    # Compute-bound at these shapes, like the causal kernels.
    assert flops.least_seconds(fwd, run["peak"])[1] == "compute"
    # The causal readers find the full layer's call alone.
    assert layer_metric_reader("flash_fwd_roofline")(run) is not None
    assert layer_metric_reader("flash_bwd_roofline")(run) is None


def test_readers_find_nothing_where_the_program_has_nothing():
    """On a program without the windowed kernels, the counters or the
    configuration's keys (the parent commit), each returns None."""
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "mistral7b-v03-d2.json")) as f:
        mistral = json.load(f)
    job = FakeJob(mistral)
    del FakeJob.publish_moe_stats
    try:
        run = synthetic_run(job, [("%flash_fwd.1 = custom-call()", 0, 10 ** 6),
                                  ("%swa_fwd.1 = custom-call()", 0, 10 ** 6)])
        assert layer_metric_reader("swa_fwd_roofline")(run) is None
        assert layer_metric_reader("swa_bwd_roofline")(run) is None
        assert layer_metric_reader("moe_buffer_fill_pct")(run) is None
        assert layer_metric_reader("swa_fwd_roofline")(
            dict(run, trace=None)) is None
    finally:
        FakeJob.publish_moe_stats = lambda self, n: (
            self.asked.append(n), self.counters)[1]


def test_buffer_fill_reads_the_counters_of_the_windows_steps():
    job = FakeJob(sizes(), {"rows_held": 49152.0 * 240, "load_max": 1.0,
                            "rows_buffer": 196608.0 * 240})
    run = synthetic_run(job, [])
    assert layer_metric_reader("moe_buffer_fill_pct")(run) == 25.0
    assert job.asked == [60]
    job.counters = {"rows_held": 0.0, "load_max": 0.0, "rows_buffer": 0.0}
    assert layer_metric_reader("moe_buffer_fill_pct")(run) is None


def rehearse(cell):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "BENCH_RUN": "ignored"}
    done = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", cell, "--seed",
         "2147483777", "--seconds", "1", "--trace", "1", "--rehearse-cpu"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    lines = [json.loads(line) for line in done.stdout.splitlines()]
    assert all(line["rehearsal"] is True for line in lines)
    return lines[-1]


def test_token_cell_rehearsal_runs_the_new_block_and_reads_its_counters():
    result = rehearse(TOKEN_CELL)
    assert result["correct"] is True, result["compared"]
    assert result["attempted"] >= 3 and result["failed"] == 0
    assert result["metrics"] == {}
    readings = result["rehearsal_readings"]
    # 2 of 8 experts held, top 2: a quarter of the buffer by expectation.
    assert 15.0 < readings["moe_buffer_fill_pct"]["value"] < 35.0
    assert not any("roofline" in k or "mfu" in k for k in readings)
    # (the loss gap has no upper reading at the toy sizes: not compared)
    for name in ("grad_norm_gap", "update_norm_gap", "kernels_missing",
                 "compiles_in_window"):
        assert result["compared"][name]["value"] <= \
            result["compared"][name]["limit"]
