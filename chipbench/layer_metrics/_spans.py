"""Bodies of the readers that read the program's own spans: the loader's
span ring (``petastorm_tpu.telemetry``), reached through
``run["job"]._loader.telemetry``, cut to the measured window
``[log.t_open, log.t_close]`` on ``time.perf_counter()``, the clock both
the window and the ring use.

A reader returns None, never 0, when there is nothing it can vouch for: a
program that records no spans (the parent of the PR that brought them), a
window the ring did not hold whole (it evicts oldest first, so a window is
whole when nothing was dropped or something older than the window is still
retained), or a staging thread whose spans do not tile the window.

The span vocabulary (thread; parent), as ``PERF.md`` section 3 has it:
``worker_decode`` and ``publish_wait`` (worker n; the ventilated item),
``host_batch`` with its children ``pool_wait`` and ``collate`` (stager;
batch ``b{n}``), ``stage`` and ``queue_full`` (stager; ``b{n}``), ``h2d``
(watcher; ``b{n}``), ``deliver`` (consumer; ``b{n}``).
"""
from __future__ import annotations

import statistics
from collections import defaultdict

from chipbench import trace_reduce

PREFIX = "petastorm_tpu."
STAGER_THREAD = "petastorm-tpu-stage"
TILE_SHARE = 0.98          # of the window the stager's spans must cover
CLOCK_RESIDUAL_US = 200.0  # a mapped stamp may miss its span's end by this


def recorder(run):
    """The loader's span recorder, or None where the program has none."""
    loader = getattr(run.get("job"), "_loader", None)
    return getattr(getattr(loader, "telemetry", None), "recorder", None)


def spans_in(run, log) -> dict | None:
    """``{name without prefix: [(start_s, end_s, span), ...]}`` of the
    spans that overlap ``log``'s window, each cut to it; None when the
    ring cannot vouch for the window."""
    rec = recorder(run)
    if rec is None or log is None:
        return None
    spans = rec.spans()
    if not spans:
        return None
    # The ring evicts in the order spans closed: the window is whole if
    # nothing was ever dropped, or the oldest span kept closed before it.
    if rec.dropped and spans[0].start_s + spans[0].duration_s > log.t_open:
        return None
    out = defaultdict(list)
    for s in spans:
        start, end = max(s.start_s, log.t_open), min(
            s.start_s + s.duration_s, log.t_close)
        if end > start and s.name.startswith(PREFIX):
            out[s.name[len(PREFIX):]].append((start, end, s))
    return out


def seconds(cut, thread=None) -> float:
    return sum(e - b for b, e, s in cut
               if thread is None or s.thread == thread)


def stager_tile_share(run, log, by_name=None):
    """Share of the window that the staging thread's top-level spans
    (``host_batch``, ``stage``, ``queue_full``) cover."""
    by_name = by_name or spans_in(run, log)
    if not by_name:
        return None
    return sum(seconds(by_name[n], STAGER_THREAD)
               for n in ("host_batch", "stage", "queue_full")) / log.wall_s


def tiled(run, log) -> dict | None:
    """``spans_in`` if the staging thread's spans tile the window: they
    cover ``TILE_SHARE`` of it."""
    by_name = spans_in(run, log)
    if not by_name or stager_tile_share(run, log, by_name) < TILE_SHARE:
        return None
    return by_name


def self_seconds(by_name: dict, name: str, children=("pool_wait", "collate")):
    """Self time of ``name`` in the window: its duration minus its child
    spans' (children point at their parent by ``parent_id``)."""
    ids = {s.span_id for _, _, s in by_name.get(name, ())}
    inside = sum(e - b for child in children
                 for b, e, s in by_name.get(child, ()) if s.parent_id in ids)
    return seconds(by_name.get(name, ())) - inside


def decode_thread_s_per_item(run):
    """Seconds of ``worker_decode`` (every worker thread; the blocked
    publish is ``publish_wait``, not this) per item of the window."""
    by_name = tiled(run, run["log"])
    if not by_name or not by_name.get("worker_decode"):
        return None
    log = run["log"]
    return seconds(by_name["worker_decode"]) / (
        log.steps * run["job"].items_per_step)


def stager_busy_pct(run):
    """100 x (1 - the staging thread's idle share): idle is blocked on the
    pool (``pool_wait``) or parked on a full prefetch queue
    (``queue_full``); the rest is row walk, collate, stage and its own
    bookkeeping. One thread feeds every chip."""
    by_name = tiled(run, run["log"])
    if not by_name:
        return None
    idle = (seconds(by_name.get("pool_wait", ()), STAGER_THREAD)
            + seconds(by_name.get("queue_full", ()), STAGER_THREAD))
    return 100.0 * (1.0 - idle / run["log"].wall_s)


def h2d_ms(run, log=None):
    """Median ``h2d`` span (end of ``stage`` to every staged device array
    of the batch ready) of the batches staged in ``log``'s window (the
    measured one unless given). A batch whose arrays were deleted before
    the watcher saw them ready is left out."""
    log = log or run["log"]
    by_name = spans_in(run, log)
    whole = [s.duration_s for _, _, s in (by_name or {}).get("h2d", ())
             if not (s.extra or {}).get("deleted")
             and s.start_s >= log.t_open
             and s.start_s + s.duration_s <= log.t_close]
    return 1e3 * statistics.median(whole) if whole else None


def delivery_wait_pct(run):
    """Share of the window inside ``deliver`` spans: the consumer's ask to
    the hand-over, queue get and bookkeeping. The inside twin of
    ``input_stall_pct``."""
    by_name = tiled(run, run["log"])
    if not by_name or not by_name.get("deliver"):
        return None
    return 100.0 * seconds(by_name["deliver"]) / run["log"].wall_s


def queue_depth_mean(run):
    """Mean number of staged batches the consumer found waiting when it
    asked (``deliver.extra.depth``)."""
    by_name = tiled(run, run["log"])
    depths = [s.extra["depth"] for _, _, s in (by_name or {}).get(
        "deliver", ()) if s.extra and "depth" in s.extra]
    return statistics.fmean(depths) if depths else None


# ------------------------------------------------- the profiler's clock
def clock_check(run) -> dict | None:
    """Places the ring's clock on the device trace's. The benchmark holds
    pairs of the same instant on both: each ``chipbench/readback`` span's
    end (profiler clock) and ``traced_log.completed_at[i]``
    (``perf_counter``, stamped right after it). ``offset_ns`` is the
    median of ``profiler_ns - perf_counter_ns`` over the pairs,
    ``residual_us`` the largest distance of a pair from it, and
    ``anchor_gap_us`` how far ``SpanRecorder.anchor()`` (``time_ns`` against
    ``perf_counter_ns``) lies from the pairs' offset: near 0 when the
    xplane's ``start_ns`` are Unix nanoseconds."""
    trace, log = run.get("trace"), run.get("traced_log")
    if not trace or log is None:
        return None
    ends = [start + dur for name, start, dur in trace["spans"]
            if name == "readback"]
    if not ends or len(ends) != len(log.completed_at):
        return None
    offsets = [end - stamp * 1e9
               for end, stamp in zip(ends, log.completed_at)]
    offset = statistics.median(offsets)
    out = {"pairs": len(offsets), "offset_ns": offset,
           "residual_us": max(abs(o - offset) for o in offsets) / 1e3}
    rec = recorder(run)
    if rec is not None and hasattr(rec, "anchor"):
        perf_ns, unix_ns = rec.anchor()
        out["anchor_gap_us"] = (unix_ns - perf_ns - offset) / 1e3
    out["ok"] = out["residual_us"] < CLOCK_RESIDUAL_US
    return out


#: The one serial chain that produces a batch: the staging thread's spans
#: and the watcher's. A starved device always finds the consumer inside
#: ``deliver`` and every worker inside ``worker_decode``, which says
#: nothing; which link of the chain the gap falls on says where to look
#: (``pool_wait``: the workers are behind; ``host_batch``: the row walk).
CHAIN = ("pool_wait", "collate", "host_batch", "stage", "queue_full", "h2d")


def idle_seconds_by_stage(run, k: int = 10):
    """``[[span name, seconds], ...]``: the first chip's idle gaps of the
    traced window, each given to the link of ``CHAIN`` that overlaps it
    most (``other`` where none does), most first. ``host_batch`` competes
    with its self time: its overlap less its children's (``pool_wait``,
    ``collate``), so a gap goes to the innermost span it falls in."""
    check = clock_check(run)
    by_name = spans_in(run, run.get("traced_log"))
    if not check or not check["ok"] or not by_name:
        return None
    if not run["trace"]["devices"]:
        return None
    offset = check["offset_ns"]
    chain = {name: [(b * 1e9 + offset, e * 1e9 + offset)
                    for b, e, s in by_name.get(name, ())
                    if name == "h2d" or s.thread == STAGER_THREAD]
             for name in CHAIN}
    busy = trace_reduce.merged(
        run["trace"]["devices"][min(run["trace"]["devices"])])
    total = defaultdict(float)
    for (_, gap_start), (gap_end, _) in zip(busy, busy[1:]):
        overlap = {name: sum(max(0.0, min(gap_end, e) - max(gap_start, b))
                             for b, e in cuts)
                   for name, cuts in chain.items()}
        overlap["host_batch"] -= overlap["pool_wait"] + overlap["collate"]
        best = max(overlap, key=overlap.get)
        total[best if overlap[best] > 0 else "other"] += (
            gap_end - gap_start) / 1e9
    return [[n, s] for n, s in
            sorted(total.items(), key=lambda kv: -kv[1])[:k]]
