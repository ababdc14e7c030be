"""The kernels by role (``chipbench/trace_reduce.py``): a family's backward
is the pair ``<family>_bwd_dq`` + ``<family>_bwd_dkv`` or the one kernel
``<family>_bwd``, and ``kernels_missing``, the rooflines, the calls per
backward pass and the kernels' shares read both forms alike. Hand-made
traces and name sets; no chip, no interpreter."""
import json
import os

import pytest

from chipbench import trace_reduce
from chipbench.run import layer_metric_reader

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
# family -> (its cell's configuration and traffic, the readers of its
# kernels: forward roofline, backward roofline, calls per pass, share)
FAMILIES = {
    "flash": ("mistral7b-v03-d2", "tok4k-b4",
              ("flash_fwd_roofline", "flash_bwd_roofline",
               "attn_fwd_calls_per_bwd")),
    "swa": ("smallthinker21b-tp4-d4", "tok16k-b2",
            ("swa_fwd_roofline", "swa_bwd_roofline",
             "attn_fwd_calls_per_bwd")),
    "eva": ("evabyte-6.5b-d4", "byte16k-b1",
            ("eva_fwd_roofline", "eva_bwd_roofline", "eva_fwd_calls_per_bwd",
             "eva_kernel_share_pct")),
    # Latent attention runs the flash family against a call of its own.
    "flash-latent": ("kanana2-30b-a3b-ep8-d6", "tok16k-b1",
                     ("mla_fwd_roofline", "mla_bwd_roofline",
                      "attn_fwd_calls_per_bwd", "mla_kernel_share_pct")),
}
PASSES, FWD_NS, BWD_NS = 3, 7_000_000, 30_000_000


class FakeJob:
    def __init__(self, config: str, traffic: str):
        with open(os.path.join(ROOT, "chipbench", "configs",
                               config + ".json")) as f:
            self.cfg = json.load(f)
        with open(os.path.join(ROOT, "chipbench", "traffic",
                               traffic + ".json")) as f:
            self.traffic = json.load(f)


def backward_events(family: str, form: str, n: int, start: int) -> list:
    """One backward pass of ``BWD_NS`` device nanoseconds in either form,
    named as the chip's trace names a transposed ``pallas_call``."""
    def op(kernel):
        return f"%transpose_jvp_{family}_{kernel}__.{n} = custom-call()"
    if form == "one":
        return [(op("bwd"), start, BWD_NS)]
    return [(op("bwd_dq"), start, BWD_NS // 3),
            (op("bwd_dkv"), start + BWD_NS // 3, BWD_NS - BWD_NS // 3)]


def step_events(family: str, form: str, chips: int = 1) -> dict:
    events = []
    for n in range(PASSES):
        start = n * 100_000_000
        events += [(f"%jvp_{family}_fwd_.{n} = custom-call()", start, FWD_NS),
                   (f"%fusion.{n} = fusion()", start + FWD_NS, 9_000_000)]
        events += backward_events(family, form, n, start + 20_000_000)
    return {"devices": {c: list(events) for c in range(chips)}, "spans": []}


def run_of(case: str, form: str, chips: int = 1) -> dict:
    config, traffic, _ = FAMILIES[case]
    return {"job": FakeJob(config, traffic), "peak": PEAK,
            "trace": step_events(case.split("-")[0], form, chips)}


@pytest.mark.parametrize("form", ["pair", "one"])
@pytest.mark.parametrize("family", ["flash", "swa", "eva"])
def test_backward_seconds_and_passes_by_role(family, form):
    trace = step_events(family, form, chips=2)
    seconds, passes = trace_reduce.backward_seconds(trace, family)
    assert passes == PASSES            # never the sum of a pair's calls
    assert seconds == pytest.approx(PASSES * BWD_NS / 1e9, rel=1e-12)
    assert trace_reduce.forward_seconds(trace, family) == (
        pytest.approx(PASSES * FWD_NS / 1e9), PASSES)
    other = {"flash": "swa", "swa": "eva", "eva": "flash"}[family]
    assert trace_reduce.backward_seconds(trace, other) == (0.0, 0)


@pytest.mark.parametrize("case", sorted(FAMILIES))
def test_the_readers_read_one_backward_kernel_as_they_read_the_pair(case):
    """The same backward seconds and passes in either form: every reader
    of the family returns the same number, to the last digit."""
    pair, one = run_of(case, "pair"), run_of(case, "one")
    for name in FAMILIES[case][2]:
        read = layer_metric_reader(name)
        assert read(pair) is not None, name
        assert read(one) == read(pair), name
    assert layer_metric_reader(FAMILIES[case][2][2])(one) == 1.0


@pytest.mark.parametrize("case", sorted(FAMILIES))
def test_a_roofline_holds_the_one_kernel_to_the_five_products(case):
    """``least x passes / seconds`` with the call of ``flops*.py``: the
    count does not ask what implements the pass."""
    from chipbench import flops
    from chipbench.layer_metrics import _kernels
    run = run_of(case, "one")
    call = {"flash": _kernels.flash_call, "swa": _kernels.swa_call,
            "eva": _kernels.eva_call, "flash-latent": _kernels.mla_call}[case]
    traffic = run["job"].traffic
    one = call("bwd", run["job"].cfg, traffic["per_chip_batch"],
               traffic["window"])
    least, _ = flops.least_seconds(one, PEAK)
    want = 100.0 * least * PASSES / (PASSES * BWD_NS / 1e9)
    assert layer_metric_reader(FAMILIES[case][2][1])(run) == \
        pytest.approx(want, rel=1e-12)


def test_the_one_name_inside_the_pairs_counts_each_pass_once():
    """``kernel_seconds`` matches by substring, as it always has, and
    ``flash_bwd`` is inside ``flash_bwd_dq``: ``backward_seconds`` must not
    read the pair as the one kernel too, nor count a kernel twice."""
    pair = step_events("flash", "pair")
    assert trace_reduce.kernel_seconds(pair, "flash_bwd")[1] == 2 * PASSES
    assert trace_reduce.kernel_seconds(pair, "flash_bwd_dq")[1] == PASSES
    assert trace_reduce.kernel_seconds(pair, "flash_bwd_dkv")[1] == PASSES
    assert trace_reduce.backward_seconds(pair, "flash")[1] == PASSES
    one = step_events("flash", "one")
    assert trace_reduce.kernel_seconds(one, "flash_bwd")[1] == PASSES
    assert trace_reduce.kernel_seconds(one, "flash_bwd_dq") == (0.0, 0)
    assert trace_reduce.backward_seconds(one, "flash")[1] == PASSES
    # Both forms in one window (a step half moved over): each pass once.
    both = {"devices": {0: pair["devices"][0] + one["devices"][0]},
            "spans": []}
    seconds, passes = trace_reduce.backward_seconds(both, "flash")
    assert passes == 2 * PASSES
    assert seconds == pytest.approx(2 * PASSES * BWD_NS / 1e9)


def test_the_recorded_trace_reads_by_role_what_it_read_by_name():
    """The v5e trace of PR 25 (three flash calls, the pair): the role
    helpers give the pair's seconds and the ``_bwd_dq`` calls, which is
    what the readers took when they went by the two names."""
    recorded = trace_reduce.load(os.path.join(
        os.path.dirname(__file__), "data", "flash_probe.xplane.pb.gz"),
        span_prefix="cb/")
    by_name = [trace_reduce.kernel_seconds(recorded, k)
               for k in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")]
    assert [calls for _, calls in by_name] == [3, 3, 3]
    assert trace_reduce.forward_seconds(recorded, "flash") == by_name[0]
    seconds, passes = trace_reduce.backward_seconds(recorded, "flash")
    assert passes == by_name[1][1]
    assert seconds == pytest.approx(by_name[1][0] + by_name[2][0], rel=1e-12)
    assert seconds == pytest.approx((24782781 + 31083022) / 1e9)
    assert trace_reduce.backward_seconds(recorded, "swa") == (0.0, 0)


def test_half_a_pair_makes_no_pass_and_reads_nothing():
    """``_bwd_dkv`` without ``_bwd_dq`` is no backward pass: the readers
    return None, as they did when they went by the pair's names."""
    run = run_of("flash", "pair")
    run["trace"]["devices"][0] = [
        e for e in run["trace"]["devices"][0] if "bwd_dq" not in e[0]]
    assert trace_reduce.backward_seconds(run["trace"], "flash")[1] == 0
    assert layer_metric_reader("flash_bwd_roofline")(run) is None
    assert layer_metric_reader("attn_fwd_calls_per_bwd")(run) is None
    assert layer_metric_reader("flash_fwd_roofline")(run) is not None


PAIR = {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"}
SWA_PAIR = {"swa_fwd", "swa_bwd_dq", "swa_bwd_dkv"}


@pytest.mark.parametrize("families,names,missing", [
    (("flash",), PAIR, 0),                              # the pair
    (("flash",), {"flash_fwd", "flash_bwd"}, 0),        # the one kernel
    (("flash",), PAIR | {"flash_bwd"}, 0),              # both, half moved
    (("flash",), {"flash_fwd", "flash_bwd_dq"}, 1),     # half a pair
    (("flash",), {"flash_fwd", "flash_bwd_dkv"}, 1),
    (("flash",), {"flash_fwd", "flash_bwd_dqx"}, 1),    # no whole name
    (("flash",), {"flash_fwd"}, 1),                     # a forward alone
    (("flash",), {"flash_bwd_dq", "flash_bwd_dkv"}, 1),     # no forward
    (("flash",), {"flash_bwd"}, 1),
    (("flash",), set(), 2),                 # fell back to dense_attention
    (("flash",), {"unnamed"}, 2),
    (("flash",), SWA_PAIR, 2),              # another family's kernels
    (("flash",), {"eva_fwd", "eva_bwd"}, 2),
    (("flash", "swa"), PAIR | SWA_PAIR, 0),
    (("flash", "swa"), PAIR | {"swa_fwd", "swa_bwd"}, 0),   # one family fused
    (("flash", "swa"), {"flash_fwd", "flash_bwd", "swa_fwd", "swa_bwd"}, 0),
    (("flash", "swa"), PAIR, 2),            # the windowed layers fell back
    (("flash", "swa"), PAIR | {"swa_fwd", "swa_bwd_dq"}, 1),
    (("flash", "swa"), set(), 4),
    (("eva",), {"eva_fwd", "eva_bwd_dq", "eva_bwd_dkv"}, 0),
    (("eva",), {"eva_fwd", "eva_bwd"}, 0),
    (("eva",), {"eva_fwd", "eva_bwd_dq"}, 1),
    (("eva",), PAIR, 2),
    ((), set(), 0),                         # the image cell expects none
    ((), PAIR, 0),
])
def test_kernels_missing_counts_roles(families, names, missing):
    """Over ``run.mosaic_kernel_names``' output sets (a toy ``jax.jit`` of
    a ``pl.pallas_call`` under the interpreter is no Mosaic call)."""
    assert trace_reduce.roles_missing(families, names) == missing


@pytest.mark.parametrize("pipeline,cell,families", [
    ("token_decoder", "mistral7b-tok4k-1chip", ("flash",)),
    ("token_moe_decoder", "smallthinker21b-tok16k-1chip", ("flash", "swa")),
    ("token_mla_moe_decoder", "kanana2-tok16k-1chip", ("flash",)),
    ("byte_eva_decoder", "evabyte-byte16k-1chip", ("eva",)),
    ("image_classifier", "rn50-jpeg224-1chip", ()),
])
def test_each_pipeline_expects_families_not_kernel_names(pipeline, cell,
                                                         families, tmp_path):
    import importlib

    import jax

    from chipbench import run
    _, _, config, traffic = run.load_cell(cell, rehearsal=True)
    assert config["pipeline"] == pipeline
    job = importlib.import_module(f"chipbench.pipelines.{pipeline}").Job(
        config, traffic, jax.devices()[:1], 17, str(tmp_path / "store"))
    assert job.expected_kernels == families
    # Today's program: each family's three kernels, by their exact names.
    today = {f"{f}_{k}" for f in families
             for k in ("fwd", "bwd_dq", "bwd_dkv")}
    assert trace_reduce.roles_missing(job.expected_kernels, today) == 0
    assert trace_reduce.roles_missing(job.expected_kernels, set()) == \
        2 * len(families)


def manifest_cells() -> list:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return [c["name"] for c in json.load(f)["workloads"]]


@pytest.mark.parametrize("cell", manifest_cells())
def test_every_cells_pipeline_expects_families(cell, tmp_path):
    """The families check of the case above, over every cell of the
    manifest: a new cell's pipeline is held to it without an edit here."""
    import importlib

    import jax

    from chipbench import run
    _, _, config, traffic = run.load_cell(cell, rehearsal=True)
    job = importlib.import_module(
        f"chipbench.pipelines.{config['pipeline']}").Job(
            config, traffic, jax.devices()[:1], 17, str(tmp_path / "store"))
    families = job.expected_kernels
    assert isinstance(families, tuple)
    assert not any(f.endswith(("_fwd", "_bwd")) or "_bwd_" in f
                   for f in families), families
    today = {f"{f}_{k}" for f in families
             for k in ("fwd", "bwd_dq", "bwd_dkv")}
    assert trace_reduce.roles_missing(families, today) == 0
    assert trace_reduce.roles_missing(families, set()) == 2 * len(families)
