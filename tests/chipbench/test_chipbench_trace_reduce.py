"""chipbench/trace_reduce.py on a trace recorded on a TPU v5e (PR 25: three
calls of flash attention forward and backward at the token cell's shapes,
``data/flash_probe.xplane.pb.gz``) and on hand-made events."""
import os

import pytest

from chipbench import trace_reduce

RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "flash_probe.xplane.pb.gz")


@pytest.fixture(scope="module")
def recorded():
    return trace_reduce.load(RECORDED, span_prefix="cb/")


def test_recorded_trace_has_one_chip_and_the_runners_spans(recorded):
    assert list(recorded["devices"]) == [0]
    assert len(recorded["devices"][0]) == 75
    assert [s[0] for s in recorded["spans"]].count("dispatch") == 3


@pytest.mark.parametrize("kernel,nanoseconds", [
    ("flash_fwd", 25501149), ("flash_bwd_dq", 24782781),
    ("flash_bwd_dkv", 31083022)])
def test_kernel_time_by_stable_name(recorded, kernel, nanoseconds):
    seconds, calls = trace_reduce.kernel_seconds(recorded, kernel)
    assert calls == 3 and seconds == pytest.approx(nanoseconds / 1e9)


def test_busy_union_and_top_ops_of_the_recorded_trace(recorded):
    busy = trace_reduce.busy_seconds(recorded)
    assert busy == pytest.approx(0.095644, abs=1e-5)
    top = trace_reduce.top_ops(recorded, 3)
    assert [n.split(":")[0] for n, _ in top] == [
        "transpose_jvp_flash_bwd_dkv__.1", "jvp_flash_fwd_.1",
        "transpose_jvp_flash_bwd_dq__.1"]
    assert top[0][1] == pytest.approx(0.031083022)
    assert sum(s for _, s in trace_reduce.top_ops(recorded, 100)) == \
        pytest.approx(busy)   # nothing overlaps in a one-stream trace


def test_union_counts_overlap_once_and_averages_over_chips():
    trace = {"devices": {0: [("a", 0, 10), ("b", 5, 10), ("c", 30, 5)],
                         1: [("a", 0, 10)]}, "spans": []}
    assert trace_reduce.merged(trace["devices"][0]) == [[0, 15], [30, 35]]
    assert trace_reduce.busy_seconds(trace) == pytest.approx(15e-9)
    assert trace_reduce.kernel_seconds(trace, "a") == (pytest.approx(10e-9), 1)
    assert trace_reduce.kernel_seconds(trace, "zz") == (0.0, 0)


def test_idle_gaps_go_to_the_span_that_overlaps_them_most():
    trace = {"devices": {0: [("op", 0, 10), ("op", 110, 10), ("op", 125, 5)]},
             "spans": [("next_batch", 8, 90), ("dispatch", 98, 10)]}
    gaps = dict(trace_reduce.idle_gaps(trace))
    assert gaps == {"next_batch": pytest.approx(100e-9),
                    "other": pytest.approx(5e-9)}
    assert trace_reduce.idle_gaps({"devices": {}, "spans": []}) == []


def test_short_names():
    assert trace_reduce.short_name(
        "%fusion.12 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop") == "fusion.12"
    assert trace_reduce.short_name(
        "%jvp_flash_fwd_.1 = (bf16[4]{0}) custom-call(bf16[4]{0} %q)"
    ) == "jvp_flash_fwd_.1:custom-call"
