"""What the latent-attention configuration adds: ``flops_mla.py`` against
hand counts, the configuration file against its source's numbers, the three
new readers on a synthetic ``run``, the reference's reading of half a batch
at one row a step, and the cell's rehearsal."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from chipbench import flops, flops_mla
from chipbench.run import layer_metric_reader

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "kanana2-tok16k-1chip"
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
PAIRS = 16384 * 16385 // 2
NEW_READERS = ("mla_fwd_roofline", "mla_bwd_roofline", "mla_kernel_share_pct")


def sizes():
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "kanana2-30b-a3b-ep8-d6.json")) as f:
        return json.load(f)


def test_config_file_keeps_the_sources_numbers():
    c = sizes()
    published = {
        "first_k_dense_replace": 1, "head_dim": 64, "hidden_size": 2048,
        "intermediate_size": 6144, "kv_lora_rank": 512,
        "max_position_embeddings": 32768, "moe_intermediate_size": 768,
        "moe_layer_freq": 1, "n_group": 1, "n_shared_experts": 2,
        "num_attention_heads": 32, "num_experts_per_tok": 6,
        "num_key_value_heads": 32, "qk_head_dim": 192,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-06, "rope_theta": 1000000,
        "routed_scaling_factor": 2.448, "topk_group": 1, "v_head_dim": 128,
        "q_lora_rank": None, "rope_scaling": None, "rope_interleave": True,
        "scoring_func": "sigmoid", "topk_method": "noaux_tc",
        "norm_topk_prob": True, "tie_word_embeddings": False,
        "attention_bias": False, "hidden_act": "silu",
        "model_type": "deepseek_v3"}
    assert {k: c[k] for k in published} == published
    assert c["reduced"] == ["num_hidden_layers", "n_routed_experts",
                            "vocab_size"]
    assert {k: c[k] for k in c["reduced"]} == {
        "num_hidden_layers": 6, "n_routed_experts": 16, "vocab_size": 16032}
    assert c["published"] == {"num_hidden_layers": 48,
                              "n_routed_experts": 128, "vocab_size": 128256}
    # The floors: the dense layer + at least four expert layers, at least
    # 8 routed experts, at least an eighth of the vocabulary.
    assert c["num_hidden_layers"] - c["first_k_dense_replace"] >= 4
    assert c["n_routed_experts"] >= 8 and c["moe_router_outputs"] == 128
    assert 8 * c["vocab_size"] == 128256
    assert {"correction_bias", "aux_loss", "rope", "optimizer", "init",
            "compute"} <= set(c["assumed"])
    assert "short buffer" in c["guarantees"]["dropless"] \
        and "full buffer" in c["guarantees"]["dropless"]
    assert "8 chips" in c["deployment"]


def test_the_configuration_states_its_state_bytes():
    c = sizes()
    attention = flops_mla.attention_matrix_params(c)
    assert attention == (2048 * 6144 + 2048 * 576 + 512 * 8192
                         + 4096 * 2048) == 26_345_472
    shared, router = 3 * 2048 * 1536, 2048 * 128
    experts = 16 * 3 * 2048 * 768
    expert_layer = attention + shared + router + experts
    dense_layer = attention + 3 * 2048 * 6144
    assert (expert_layer, dense_layer) == (111_542_272, 64_094_208)
    norms = 2 * 2048 + 512
    weights = (dense_layer + norms + 5 * (expert_layer + norms)
               + 2 * 16032 * 2048 + 2048)
    assert weights == 687_502_336
    # The correction bias is outside AdamW: the leaf and its zero gradient.
    assert c["state_bytes"] == 16 * weights + 8 * 5 * 128 == 11_000_042_496


def test_one_step_by_hand():
    c = sizes()
    # A token meets 0.75 held experts by expectation (6 x 16 / 128).
    expert_ffn = 2048 * 128 + 2 * 3 * 2048 * 768 + 0.75 * 3 * 2048 * 768
    assert flops_mla.ffn_matrix_params(c, 0) == 3 * 2048 * 6144
    assert flops_mla.ffn_matrix_params(c, 1) == expert_ffn
    assert flops_mla.ffn_matrix_params(c, 5) == expert_ffn
    per_token = (6 * 26_345_472 + 3 * 2048 * 6144 + 5 * expert_ffn
                 + 2048 * 16032)
    assert 294.8e6 < per_token < 294.9e6
    attention = 2 * 32 * 320 * PAIRS
    assert flops_mla.attention_forward_flops(c, 1, 16384) == attention
    want = 6 * per_token * 16384 + 3 * 6 * attention
    assert flops_mla.train_flops(c, 1, 16384) == want
    assert 78.4e12 < want < 78.6e12
    # Attention is 63% of the counted work at 16,384 positions.
    assert 0.625 < 3 * 6 * attention / want < 0.635
    assert flops_mla.train_flops(c, 2, 16384) == 2 * want


def test_a_call_counts_the_mathematics_and_each_operand_once():
    fwd = flops_mla.mla_call("fwd", 1, 32, 16384, 128, 64, 128)
    bwd = flops_mla.mla_call("bwd", 1, 32, 16384, 128, 64, 128)
    assert fwd["flops"] == 2 * 32 * (192 + 128) * PAIRS
    assert bwd["flops"] == 2 * 32 * (3 * 192 + 2 * 128) * PAIRS
    assert bwd["flops"] / fwd["flops"] == 2.6
    q = 16384 * 32 * 192 * 2
    keys = 16384 * (32 * 128 + 64) * 2      # the rotary key is one head
    v, stats = 16384 * 32 * 128 * 2, 32 * 16384 * 4
    assert fwd["bytes"] == q + keys + 2 * v + stats
    assert bwd["bytes"] == 2 * q + 2 * keys + 4 * v + 2 * stats
    # Compute-bound by a wide margin, like the other attention kernels.
    assert flops.least_seconds(fwd, PEAK)[1] == "compute"
    assert flops.least_seconds(bwd, PEAK)[1] == "compute"
    # At equal widths and one key head a query head it is the flash
    # call's operations (the causal count there leaves out the diagonal's
    # half).
    same = flops_mla.mla_call("fwd", 2, 8, 4096, 128, 0, 128)["flops"]
    flash = flops.flash_call("fwd", 2, 8, 8, 4096, 128)["flops"]
    assert 0 < same - flash < flash / 4000
    with pytest.raises(ValueError):
        flops_mla.mla_call("both", 1, 32, 16384, 128, 64, 128)


class FakeJob:
    def __init__(self, cfg):
        self.cfg = cfg
        self.traffic = {"per_chip_batch": 1, "window": 16384}


class FakeLog:
    steps = 25


def synthetic_run(job, events):
    return {"job": job, "log": FakeLog(), "peak": PEAK,
            "trace": {"devices": {0: events}, "spans": []}}


def test_mla_readers_read_the_flash_kernels_against_the_latent_call():
    c = sizes()
    fwd = flops_mla.mla_call("fwd", 1, 32, 16384, 128, 64, 128)
    bwd = flops_mla.mla_call("bwd", 1, 32, 16384, 128, 64, 128)
    least_fwd = int(1e9 * fwd["flops"] / 197e12)
    least_bwd = int(1e9 * bwd["flops"] / 197e12)
    events = [("%flash_fwd.1 = custom-call()", 0, 2 * least_fwd),
              ("%flash_fwd.2 = custom-call()", 3 * least_fwd, 2 * least_fwd),
              ("%flash_bwd_dq.1 = custom-call()", 10 * least_fwd, least_bwd),
              ("%flash_bwd_dkv.1 = custom-call()", 10 * least_fwd + least_bwd,
               3 * least_bwd),
              ("%flash_bwd_dq.2 = custom-call()", 30 * least_fwd, least_bwd),
              ("%flash_bwd_dkv.2 = custom-call()", 30 * least_fwd + least_bwd,
               3 * least_bwd),
              ("%fusion.7 = fusion()", 60 * least_fwd,
               4 * least_fwd + 8 * least_bwd)]
    run = synthetic_run(FakeJob(c), events)
    assert layer_metric_reader("mla_fwd_roofline")(run) == \
        pytest.approx(50.0, rel=1e-6)
    assert layer_metric_reader("mla_bwd_roofline")(run) == \
        pytest.approx(25.0, rel=1e-6)
    # The kernels are half of the busy time here.
    assert layer_metric_reader("mla_kernel_share_pct")(run) == \
        pytest.approx(50.0, rel=1e-6)
    assert layer_metric_reader("attn_fwd_calls_per_bwd")(run) == 1.0


def test_readers_find_nothing_where_the_program_has_nothing():
    """On a configuration without latent attention (every cell of the
    parent commit), with no trace, or with no such kernel in it, each
    returns None and does not raise."""
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "mistral7b-v03-d2.json")) as f:
        mistral = json.load(f)
    run = synthetic_run(FakeJob(mistral),
                        [("%flash_fwd.1 = custom-call()", 0, 10 ** 6),
                         ("%flash_bwd_dq.1 = custom-call()", 0, 10 ** 6)])
    for name in NEW_READERS:
        assert layer_metric_reader(name)(run) is None
        assert layer_metric_reader(name)(
            dict(synthetic_run(FakeJob(sizes()), []), trace=None)) is None
    empty = synthetic_run(FakeJob(sizes()), [("%fusion.1 = fusion()", 0, 5)])
    for name in NEW_READERS:
        assert layer_metric_reader(name)(empty) is None


# Today's other cells, by name: none of them lists the latent readers.
OTHERS = ("rn50-jpeg224-1chip", "mistral7b-tok4k-1chip",
          "smallthinker21b-tok16k-1chip", "evabyte-byte16k-1chip")


def check_the_new_metrics_list_the_new_cell(bench: dict, root: str = ROOT):
    """Membership, not lists or positions: the cell is in each list it
    reports, wherever it stands there, and today's other cells are not in
    its own readers' lists."""
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_READERS:
        assert CELL in per_layer[name]["workloads"]
        assert not set(OTHERS) & set(per_layer[name]["workloads"])
        assert per_layer[name]["moves"] == "tokens_per_s_per_chip"
        assert per_layer[name]["layer"] == "kernels"
    for name in ("flash_fwd_roofline", "flash_bwd_roofline",
                 "swa_fwd_roofline", "eva_fwd_roofline"):
        assert CELL not in per_layer[name]["workloads"]
    for name in ("step_mfu_pct.tokens", "device_idle_pct.tokens",
                 "host_cpu_us_per_token", "moe_buffer_fill_pct",
                 "attn_fwd_calls_per_bwd", "resident_step_ms.tokens"):
        assert CELL in per_layer[name]["workloads"]
    cell = next(c for c in bench["workloads"] if c["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "kanana2-30b-a3b-ep8-d6", "tok16k-b1", 1)
    assert "63%" in cell["why"] and "eighth" in cell["why"]
    with open(os.path.join(root, "chipbench", "limits", CELL + ".json")) as f:
        limits = json.load(f)
    # The loss hardly tells bfloat16 from fp8 here, so the file names its
    # readings and holds no run to one; the two norm gaps decide.
    assert set(limits) == {"grad_norm_gap", "update_norm_gap", "_readings",
                           "rehearsal"}
    assert "loss_gap is NOT compared" in limits["_readings"]


def test_manifest_lists_the_new_metrics_for_the_new_cell_alone():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    check_the_new_metrics_list_the_new_cell(bench)


def test_reference_reads_no_rows_as_the_rows_second_half_left_out(tmp_path):
    """``calibrate`` plants half a batch as ``reference(rows=global_batch
    // 2)``: at one row a step that is ``rows=0``, which the job reads as
    the row's second half of positions left out of the loss and its mean."""
    import jax
    from chipbench import run
    from chipbench.pipelines import common, token_mla_moe_decoder
    from chipbench.reference import kanana2 as ref
    _, _, config, traffic = run.load_cell(CELL, rehearsal=True)
    job = token_mla_moe_decoder.Job(config, traffic, jax.devices()[:1], 17,
                                    str(tmp_path / "store"))
    assert job.global_batch // 2 == 0
    assert job.expected_kernels == ("flash",)      # a family, by role
    assert job.flops_per_step == flops_mla.train_flops(config, 1, 128)
    job.write_store()
    job.mesh, job.rows, job.replicated = common.mesh_and_shardings(
        job.devices)
    window = traffic["window"]
    keys = [{"ts": (3 * window + np.arange(window))[None, :]}]
    whole = job.reference(keys)
    half = job.reference(keys, rows=0)
    tokens = job.stored_batch(np.array([3 * window]))
    assert tokens.dtype == np.int32 and tokens.max() < config["vocab_size"]
    params = ref.init_params(common.seed_key(17), config)
    with jax.default_matmul_precision("highest"):
        assert half["losses"][0] == pytest.approx(float(ref.loss(
            params, tokens, config, positions=window // 2)), rel=1e-5)
        assert whole["losses"][0] == pytest.approx(float(ref.loss(
            params, tokens, config)), rel=1e-5)
    assert half["losses"][0] != pytest.approx(whole["losses"][0], rel=1e-4)
    gaps = [abs(half["grad_norms"][k] - v) / v
            for k, v in whole["grad_norms"].items() if v > 0]
    assert max(gaps) > 0.1
    # No gradient reaches the correction bias, in the reference either.
    assert all(v == 0 for k, v in whole["grad_norms"].items()
               if k.endswith("['router_bias']"))


# ``chipbench.run`` with its state (store, trace) in a directory of the
# test's own: the traced rehearsals of other test files, run beside this
# one by other workers, share ``<checkout>/.chipbench/trace``.
RUN_WITH_STATE_DIR = ("import sys; from chipbench import run; "
                      "run.STATE_DIR = sys.argv.pop(1); sys.exit(run.main())")


def test_the_cells_rehearsal_runs_the_step_through_chipbench_run(tmp_path):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "BENCH_RUN": "ignored"}
    done = subprocess.run(
        [sys.executable, "-c", RUN_WITH_STATE_DIR, str(tmp_path),
         "--workload", CELL, "--seed", "2147483999", "--seconds", "1",
         "--trace", "1", "--rehearse-cpu"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    lines = [json.loads(line) for line in done.stdout.splitlines()]
    assert all(line["rehearsal"] is True for line in lines)
    result = lines[-1]
    assert result["correct"] is True, result["compared"]
    assert result["attempted"] >= 3 and result["failed"] == 0
    assert result["metrics"] == {}
    readings = result["rehearsal_readings"]
    # 2 of 8 experts held, top 2: a quarter of the buffer's rows by
    # expectation, over the two expert layers (the dense one adds none).
    assert 10.0 < readings["moe_buffer_fill_pct"]["value"] < 40.0
    assert not any("roofline" in k or "mfu" in k or "share" in k
                   for k in readings)
    for name in ("grad_norm_gap", "update_norm_gap", "kernels_missing",
                 "compiles_in_window", "staged_elements_wrong"):
        assert result["compared"][name]["value"] <= \
            result["compared"][name]["limit"]
    assert "loss_gap" not in result["compared"]
