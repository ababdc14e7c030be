"""What the EVA byte-level configuration adds: ``flops_eva.py`` against a
brute-force count of the mask and hand arithmetic, the four new readers on
a recorded trace stub and on a program without the kernels, the
configuration file against the source's numbers, and the new cell's CPU
rehearsal through ``chipbench.run``."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from chipbench import flops, flops_eva
from chipbench.run import layer_metric_reader

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "evabyte-byte16k-1chip"
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def sizes() -> dict:
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "evabyte-6.5b-d4.json")) as f:
        return json.load(f)


def brute_pairs(seq: int, window: int, chunk: int) -> tuple:
    """Count the mask: local ``j`` in ``i``'s block with ``j <= i``;
    summary ``c`` with ``(c chunk) // window < i // window``."""
    i = np.arange(seq)[:, None]
    j = np.arange(seq)[None, :]
    c = np.arange(seq // chunk)[None, :]
    return (int(((i // window == j // window) & (j <= i)).sum()),
            int(((c * chunk) // window < i // window).sum()))


@pytest.mark.parametrize("seq,window,chunk", [
    (2048, 2048, 16), (4096, 2048, 16), (6144, 2048, 16), (128, 32, 4),
    (160, 32, 8), (512, 64, 16)])
def test_pair_counts_are_a_brute_force_count_of_the_mask(seq, window, chunk):
    assert flops_eva.pairs(seq, window, chunk) == brute_pairs(seq, window,
                                                              chunk)


def test_pairs_at_the_cells_window():
    local, summary = flops_eva.pairs(16384, 2048, 16)
    assert (local, summary) == (16_785_408, 7_340_032)
    causal = 16384 * 16385 // 2
    assert 0.17 < (local + summary) / causal < 0.19
    assert 100 * summary / (local + summary) == pytest.approx(30.42, abs=0.01)


def test_layer_and_step_operations_by_hand():
    c = sizes()
    layer = 4 * 4096 * 4096 + 3 * 4096 * 11008
    assert flops_eva.layer_matrix_params(c) == layer == 202_375_168
    attn = 4 * 32 * 128 * (16_785_408 + 7_340_032)
    assert flops_eva.attention_forward_flops(c, 1, 16384) == attn
    assert 0.39e12 < attn < 0.40e12
    prep = 6 * 16384 * 32 * 128
    matmul = 4 * layer + 4096 * 8 * 320
    assert flops_eva.train_flops(c, 1, 16384) == \
        6 * matmul * 16384 + 3 * 4 * (attn + prep)
    assert 85e12 < flops_eva.train_flops(c, 1, 16384) < 86e12
    # Twice the rows, twice the step.
    assert flops_eva.train_flops(c, 2, 16384) == \
        2 * flops_eva.train_flops(c, 1, 16384)


def test_kernel_calls_count_each_operand_once():
    fwd = flops_eva.eva_call("fwd", 1, 32, 16384, 128, 2048, 16)
    bwd = flops_eva.eva_call("bwd", 1, 32, 16384, 128, 2048, 16)
    unit = 2 * 32 * 128 * (16_785_408 + 7_340_032)
    rows = 16384 * 32 * 128 * 2
    summaries = 2 * (7 * 128) * 32 * 128 * 2
    stats = 32 * 16384 * 4
    assert fwd == {"flops": 2 * unit, "bytes": 4 * rows + summaries + stats}
    assert bwd == {"flops": 5 * unit,
                   "bytes": 7 * rows + 2 * summaries + 2 * stats}
    assert flops.least_seconds(fwd, PEAK)[1] == "compute"
    assert flops.least_seconds(bwd, PEAK)[1] == "compute"
    # One block: no summary is read.
    one = flops_eva.eva_call("fwd", 1, 32, 2048, 128, 2048, 16)
    assert one["bytes"] == 4 * 2048 * 32 * 128 * 2 + 32 * 2048 * 4
    with pytest.raises(ValueError):
        flops_eva.eva_call("both", 1, 32, 16384, 128, 2048, 16)


def test_config_file_keeps_the_sources_numbers():
    c = sizes()
    published = {"chunk_size": 16, "hidden_size": 4096, "init_std": 0.01275,
                 "intermediate_size": 11008, "max_position_embeddings": 32768,
                 "max_seq_length": 32768, "num_attention_heads": 32,
                 "num_key_value_heads": 32, "num_pred_heads": 8,
                 "rms_norm_eps": 1e-05, "rope_theta": 100000,
                 "vocab_size": 320, "window_size": 2048}
    assert {k: c[k] for k in published} == published
    assert c["reduced"] == ["num_hidden_layers"]
    assert c["num_hidden_layers"] == 4
    assert c["published"] == {"num_hidden_layers": 32}
    assert c["head_dim"] * c["num_attention_heads"] == c["hidden_size"]
    params = (4 * (flops_eva.layer_matrix_params(c) + 2 * 4096 + 2 * 32 * 128)
              + 320 * 4096 + 8 * 320 * 4096 + 4096)
    assert c["state_bytes"] == 16 * params == 13_141_868_544
    assert {"a_chunk_score", "b_mu", "c_rotated_keys"} <= set(c["assumed"])
    assert c["token_id_offset"] + 256 == c["vocab_size"]


class FakeJob:
    def __init__(self, cfg):
        self.cfg = cfg
        self.traffic = {"per_chip_batch": 1, "window": 16384}


class FakeLog:
    steps = 30


def synthetic_run(job, events):
    return {"job": job, "log": FakeLog(), "peak": PEAK,
            "trace": {"devices": {0: events}, "spans": []}}


def test_eva_readers_read_their_own_kernels_only():
    fwd = flops_eva.eva_call("fwd", 1, 32, 16384, 128, 2048, 16)
    bwd = flops_eva.eva_call("bwd", 1, 32, 16384, 128, 2048, 16)
    least_fwd = int(1e9 * fwd["flops"] / 197e12)
    least_bwd = int(1e9 * bwd["flops"] / 197e12)
    start = 10 ** 7
    events = [("%eva_fwd.1 = custom-call()", 0, 4 * least_fwd),
              ("%jvp_eva_fwd_.2 = custom-call()", start, 4 * least_fwd),
              ("%eva_bwd_dq.1 = custom-call()", 2 * start, least_bwd),
              ("%eva_bwd_dkv.1 = custom-call()", 3 * start, least_bwd),
              ("%eva_bwd_dq.2 = custom-call()", 4 * start, least_bwd),
              ("%eva_bwd_dkv.2 = custom-call()", 5 * start, least_bwd),
              ("%flash_fwd.1 = custom-call()", 6 * start, 1234567),
              ("%fusion.7 = fusion()", 7 * start, 8 * least_fwd
               + 4 * least_bwd - 1234567)]
    run = synthetic_run(FakeJob(sizes()), events)
    assert layer_metric_reader("eva_fwd_roofline")(run) == \
        pytest.approx(25.0, rel=1e-6)
    assert layer_metric_reader("eva_bwd_roofline")(run) == \
        pytest.approx(50.0, rel=1e-6)
    # The kernels' seconds are half of the busy seconds here.
    assert layer_metric_reader("eva_kernel_share_pct")(run) == \
        pytest.approx(50.0, rel=1e-6)
    # Two forward launches (the second autodiff's) for two backward pairs.
    assert layer_metric_reader("eva_fwd_calls_per_bwd")(run) == 1.0
    # The causal readers do not take the EVA kernels for theirs.
    assert layer_metric_reader("flash_bwd_roofline")(run) is None
    assert layer_metric_reader("attn_fwd_calls_per_bwd")(run) is None


@pytest.mark.parametrize("forward,expected", [(4, 1.0), (8, 2.0)],
                         ids=["kept", "recomputed"])
def test_forward_calls_per_backward_shows_a_recomputed_kernel(forward,
                                                              expected):
    """Four layers' backward pairs a step: a forward kernel that the
    backward pass launches again reads 2, not a drift of a share."""
    events = [(f"%eva_fwd.{n} = custom-call()", n * 10 ** 6, 5 * 10 ** 5)
              for n in range(forward)]
    events += [(f"%eva_bwd_{which}.{n} = custom-call()",
                (10 + 2 * n + (which == "dkv")) * 10 ** 6, 5 * 10 ** 5)
               for n in range(4) for which in ("dq", "dkv")]
    run = synthetic_run(FakeJob(sizes()), events)
    assert layer_metric_reader("eva_fwd_calls_per_bwd")(run) == expected


def test_readers_find_nothing_where_the_program_has_nothing():
    """On a program without the EVA kernels or the configuration's keys
    (the parent commit, another cell), each returns None and does not
    raise."""
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "mistral7b-v03-d2.json")) as f:
        mistral = json.load(f)
    job = FakeJob(mistral)
    readers = ("eva_fwd_roofline", "eva_bwd_roofline", "eva_kernel_share_pct",
               "eva_fwd_calls_per_bwd")
    run = synthetic_run(job, [("%flash_fwd.1 = custom-call()", 0, 10 ** 6)])
    for name in readers:
        assert layer_metric_reader(name)(run) is None, name
    # The kernels' names without the configuration's keys: still None.
    run = synthetic_run(job, [("%eva_fwd.1 = custom-call()", 0, 10 ** 6)])
    assert layer_metric_reader("eva_fwd_roofline")(run) is None
    for name in readers:
        assert layer_metric_reader(name)(dict(run, trace=None)) is None


# Today's other cells, by name: none of them lists the EVA readers.
OTHERS = ("rn50-jpeg224-1chip", "mistral7b-tok4k-1chip",
          "smallthinker21b-tok16k-1chip", "kanana2-tok16k-1chip")


def check_the_new_metrics_list_the_new_cell(bench: dict, root: str = ROOT):
    """Membership, not lists or positions (see the latent cell's twin in
    ``test_chipbench_kanana2.py``)."""
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    for name in ("eva_fwd_roofline", "eva_bwd_roofline",
                 "eva_kernel_share_pct", "eva_fwd_calls_per_bwd"):
        assert CELL in per_layer[name]["workloads"]
        assert not set(OTHERS) & set(per_layer[name]["workloads"])
        assert per_layer[name]["moves"] == "tokens_per_s_per_chip"
    for name in ("flash_fwd_roofline", "flash_bwd_roofline",
                 "swa_fwd_roofline", "moe_buffer_fill_pct",
                 "attn_fwd_calls_per_bwd"):
        assert CELL not in per_layer[name]["workloads"]
    for name in ("step_mfu_pct.tokens", "device_idle_pct.tokens",
                 "host_cpu_us_per_token"):
        assert CELL in per_layer[name]["workloads"]


def test_manifest_lists_the_new_metrics_for_the_new_cell_alone():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    check_the_new_metrics_list_the_new_cell(bench)


def test_byte_store_is_uint8_and_seeded(tmp_path):
    from chipbench import run, stores
    from chipbench.pipelines import byte_eva_decoder
    _, _, config, traffic = run.load_cell(CELL, rehearsal=True)
    columns = []
    for seed in (11, 11, 12):
        job = byte_eva_decoder.Job(config, traffic, [None], seed,
                                   str(tmp_path / f"store{len(columns)}"))
        job.write_store()
        columns.append(stores.read_columns(job.store_path, ["ts", "token"]))
    tokens = columns[0]["token"]
    assert tokens.dtype == np.uint8
    assert len(tokens) == traffic["store_windows"] * traffic["window"]
    assert np.array_equal(columns[0]["ts"], np.arange(len(tokens)))
    assert np.array_equal(tokens, columns[1]["token"])
    assert not np.array_equal(tokens, columns[2]["token"])
    assert tokens.max() > 127       # the top bit is drawn too


def test_reference_reads_no_rows_as_the_rows_second_half_left_out(tmp_path):
    """``calibrate`` plants half a batch as ``reference(rows=global_batch
    // 2)``: at one row a step that is ``rows=0``, which the job reads as
    the row's second half of positions left out of the loss and its mean."""
    import jax
    from chipbench import run
    from chipbench.pipelines import byte_eva_decoder, common
    from chipbench.reference import evabyte as ref
    _, _, config, traffic = run.load_cell(CELL, rehearsal=True)
    job = byte_eva_decoder.Job(config, traffic, jax.devices()[:1], 17,
                               str(tmp_path / "store"))
    assert job.global_batch // 2 == 0
    job.write_store()
    job.mesh, job.rows, job.replicated = common.mesh_and_shardings(
        job.devices)
    window = traffic["window"]
    keys = [{"ts": (3 * window + np.arange(window))[None, :]}]
    whole = job.reference(keys)
    half = job.reference(keys, rows=0)
    ids = job.stored_batch(np.array([3 * window])).astype(np.int32) + 64
    params = ref.init_params(common.seed_key(17), config)
    with jax.default_matmul_precision("highest"):
        assert half["losses"][0] == pytest.approx(float(ref.loss(
            params, ids, config, positions=window // 2)), rel=1e-5)
        assert whole["losses"][0] == pytest.approx(float(ref.loss(
            params, ids, config)), rel=1e-5)
    assert half["losses"][0] != pytest.approx(whole["losses"][0], rel=1e-4)
    # A tenth of the gradient or more is missing at some leaf: the fault
    # is one the comparison can see.
    gaps = [abs(half["grad_norms"][k] - v) / v
            for k, v in whole["grad_norms"].items() if v > 0]
    assert max(gaps) > 0.1


def test_job_stages_the_stored_bytes_unwidened(tmp_path):
    """The job's loader hands the step ``uint8`` rows of one window; the
    configuration's ``fp32_skip_add`` reaches the program's config."""
    import jax
    from chipbench import run
    from chipbench.pipelines import byte_eva_decoder
    _, _, config, traffic = run.load_cell(CELL, rehearsal=True)
    job = byte_eva_decoder.Job(config, traffic, jax.devices()[:1], 23,
                               str(tmp_path / "store"))
    job.write_store()
    job.start()
    try:
        batch = job.next_batch()
        assert batch["token"].dtype == np.uint8
        assert batch["token"].shape == (1, traffic["window"])
        assert job.lcfg.fp32_skip_add is config["fp32_skip_add"] is True
    finally:
        job.free()


def test_byte_cell_rehearsal_runs_the_eva_step_through_chipbench_run():
    """``--trace 0``: the traced rehearsals of the other cells' tests share
    one trace directory under ``.chipbench/``, and this one stays out of
    their way; the readers are driven above."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "BENCH_RUN": "ignored"}
    done = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", CELL, "--seed",
         "2147483777", "--seconds", "1", "--trace", "0", "--rehearse-cpu"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-2000:]
    lines = [json.loads(line) for line in done.stdout.splitlines()]
    assert all(line["rehearsal"] is True for line in lines)
    result = lines[-1]
    assert result["correct"] is True, result["compared"]
    assert result["attempted"] >= 3 and result["failed"] == 0
    assert result["metrics"] == {}
    assert set(result["rehearsal_readings"]) == {
        "tokens_per_s_per_chip", "step_p95_ms", "setup_s"}
    for name in ("grad_norm_gap", "update_norm_gap", "kernels_missing",
                 "compiles_in_window", "staged_elements_wrong"):
        assert result["compared"][name]["value"] <= \
            result["compared"][name]["limit"]
