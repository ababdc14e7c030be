"""Decode seconds of every worker thread (`worker_decode` spans: item taken to its first publish) per token of the window."""
from chipbench.layer_metrics._spans import decode_thread_s_per_item


def read(run):
    value = decode_thread_s_per_item(run)
    return None if value is None else 1e6 * value
