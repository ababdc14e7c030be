"""``attn_fwd_calls_per_bwd`` on hand-made traces: the forward attention
kernels' calls over the backward ones', by the kernels' stable names."""
import pytest

from chipbench.run import layer_metric_reader

STEP = [("%flash_fwd.2 = custom-call()", 0, 7),
        ("%swa_fwd.3 = custom-call()", 10, 3),
        ("%fusion.7 = fusion()", 20, 5),
        ("%flash_bwd_dq.2 = custom-call()", 30, 9),
        ("%flash_bwd_dkv.2 = custom-call()", 40, 9),
        ("%swa_bwd_dq.3 = custom-call()", 50, 4),
        ("%swa_bwd_dkv.3 = custom-call()", 60, 4)]
AGAIN = [("%checkpoint_flash_fwd.4 = custom-call()", 25, 7),
         ("%checkpoint_swa_fwd.5 = custom-call()", 27, 3)]


@pytest.mark.parametrize("events,chips,want", [
    (STEP + AGAIN, 1, 2.0),             # each layer's forward kernel ran twice
    (STEP, 1, 1.0),                     # its output was kept
    (STEP[:1] + AGAIN[:1] + STEP[3:5], 1, 2.0),     # the causal kernels alone
    (STEP * 3 + AGAIN * 3, 4, 2.0),     # every chip holds the same calls
    (STEP[:3], 1, None),                # no backward call in the window
    (None, 1, None),                    # no trace
])
def test_forward_calls_over_backward_calls(events, chips, want):
    trace = events and {"devices": {n: events for n in range(chips)},
                        "spans": []}
    assert layer_metric_reader("attn_fwd_calls_per_bwd")({"trace": trace}) \
        == want
